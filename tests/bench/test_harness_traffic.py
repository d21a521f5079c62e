"""The traffic generator: deterministic from the seed, at its stated mean
rate and coefficient of variation, with the same work for every seed."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.traffic import generate  # noqa: E402


def traffic(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


MIXES = [{"kind": "poisson", "rate": 12.0},
         {"kind": "gamma", "rate": 400.0, "shape": 0.25}]


@pytest.mark.parametrize("mix", MIXES, ids=["poisson", "gamma"])
def test_arrivals_are_deterministic_from_the_seed(mix):
    seed = 2 ** 31 + 987654321
    a = generate.arrivals(mix, 20.0, seed)
    np.testing.assert_array_equal(a, generate.arrivals(mix, 20.0, seed))
    assert not np.array_equal(a, generate.arrivals(mix, 20.0, seed + 1))


@pytest.mark.parametrize("mix", MIXES, ids=["poisson", "gamma"])
def test_arrivals_reach_the_stated_rate_and_variation(mix):
    seconds = 200.0
    a = generate.arrivals(mix, seconds, 7)
    assert a[0] == 0.0 and a[-1] < seconds and np.all(np.diff(a) >= 0)
    assert len(a) / seconds == pytest.approx(mix["rate"], rel=1e-3)
    gaps = np.diff(a)
    cv = gaps.std() / gaps.mean()
    shape = mix.get("shape", 1.0)
    assert cv == pytest.approx(1 / np.sqrt(shape), rel=0.1)


@pytest.mark.parametrize("mix", MIXES, ids=["poisson", "gamma"])
def test_every_seed_gets_the_same_gaps_in_another_order(mix):
    a = np.sort(np.diff(generate.arrivals(mix, 20.0, 1)))
    b = np.sort(np.diff(generate.arrivals(mix, 20.0, 2)))
    full = np.sort(generate.gaps(mix, 20.0))
    # each window drops one gap (the one after its last arrival)
    assert len(a) == len(b) == len(full) - 1
    for got in (a, b):
        i = np.clip(np.searchsorted(full, got), 1, len(full) - 1)
        near = np.minimum(np.abs(full[i] - got), np.abs(full[i - 1] - got))
        assert near.max() < 1e-9


def test_backlog_depth_follows_the_slots():
    mix = traffic("backlog")
    assert not generate.is_open(mix)
    assert generate.backlog_depth(mix, 8) == 8
    assert generate.backlog_depth(mix, 32) == 32


@pytest.mark.parametrize("name", ["dit-i256-cfg", "dit-s4"])
def test_requests_are_deterministic_per_seed(name):
    cfg = config(name)
    a = generate.Requests(cfg, 5).take(50)
    b = generate.Requests(cfg, 5).take(20) + generate.Requests(cfg, 5).take(
        50)[20:]
    assert a == b
    assert a != generate.Requests(cfg, 6).take(50)
    assert all(0 <= r.seed < 2 ** 31 for r in a)
    if cfg["serving"]["guided"]:
        assert {r.cfg_scale for r in a} == set(cfg["serving"]["cfg_scales"])
    else:
        assert all(r.cfg_scale is None for r in a)
    assert cfg["conditioning"] == {"kind": "class"}
    if cfg["serving"]["class_conditional"]:
        assert all(0 <= r.cond < cfg["model"]["num_classes"] for r in a)
    else:
        assert all(r.cond is None for r in a)


def test_unknown_kind_is_refused():
    with pytest.raises(ValueError):
        generate.is_open({"kind": "zipf"})
    for cond in ({"kind": "pixels"}, {"kind": "Class"}, {}):
        with pytest.raises(ValueError):
            generate.conditioning({"conditioning": cond})


@pytest.mark.parametrize("name,open_", [("backlog", False), ("poisson", True),
                                        ("gamma", True)])
def test_each_kind_is_a_module_found_by_name(name, open_):
    mod = generate.kind({"kind": name})
    assert mod.__name__ == f"bench.traffic.gen_{name}"
    assert (ROOT / "bench" / "traffic" / f"gen_{name}.py").is_file()
    assert generate.is_open({"kind": name}) is open_


@pytest.mark.parametrize("name", ["class", "text"])
def test_each_conditioning_kind_is_a_module_found_by_name(name):
    mod = generate.conditioning({"conditioning": {"kind": name}})
    assert mod.__name__ == f"bench.traffic.cond_{name}"
    for f in ("draw", "extras", "extras_init", "reference_inputs"):
        assert callable(getattr(mod, f))
