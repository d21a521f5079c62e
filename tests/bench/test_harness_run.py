"""A whole benchmark run at a tiny size on the CPU, the look for a chip
skipped: the program against the reference, `correct` false under each
fault a served cell can have, and the command's refusals."""

import copy
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cell as cells  # noqa: E402
from bench import faults, harness, system  # noqa: E402

BACKLOG = {"kind": "backlog", "queue_per_slot": 1.0}
BURSTY = {"kind": "gamma", "rate": 40.0, "shape": 0.25}
HOST_METRICS = ["tick_ms", "admission_us_per_tick", "gen_lag_p99_ms",
                "queue_wait_p95_s", "service_p50_s"]


def tiny(base: str, limit: float = 1e-4) -> dict:
    """The configuration `base` at a CPU size, computing in float32 (where
    the program agrees with the reference to rounding)."""
    c = copy.deepcopy(cells.load_config(base))
    c["model"].update(num_layers=2, d_model=64, num_heads=2, num_kv_heads=2,
                      head_dim=32, d_ff=128, latent_dim=8, patch_tokens=16,
                      dtype="float32")
    c["serving"]["slots"] = 4
    c["check"].update(per_slot=1000, block=8, limit=limit)
    return c


def run_tiny(base, traffic, seconds=0.6, traced=False, seed=2 ** 32 + 3):
    c = cells.Cell(name="tiny", config=tiny(base), traffic=traffic, chips=1,
                   end_to_end=[{"name": "images_per_s", "unit": "images/s"},
                               {"name": "latency_p95_s", "unit": "s"},
                               {"name": "setup_s", "unit": "s"}],
                   per_layer=[{"name": n, "unit": "u"}
                              for n in HOST_METRICS])
    return harness.run(c, seed, seconds, traced, since_start=lambda: 1.0)


@pytest.mark.parametrize("base,traffic", [("dit-i256-cfg", BACKLOG),
                                          ("dit-s4", BURSTY)],
                         ids=["guided-backlog", "unguided-bursty"])
def test_sound_run_matches_the_reference(base, traffic):
    out = run_tiny(base, traffic)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["latent_rel_err_max"]["value"] < 1e-4
    assert list(out)[-1] == "checks"
    assert "setup_s" in out["metrics"]


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    real = system.build

    def build(config, params):
        served = real(config, params)
        faults.plant(served, fault, monkeypatch.setattr)
        return served

    monkeypatch.setattr(system, "build", build)
    out = run_tiny("dit-i256-cfg", BACKLOG)
    assert not out["correct"]
    # far outside rounding, and outside the cell's own limit too
    err = out["checks"]["latent_rel_err_max"]["value"]
    assert err > 1e-4
    assert err > cells.load_config("dit-i256-cfg")["check"]["limit"]


def test_traced_run_reads_host_metrics_and_the_trace():
    out = run_tiny("dit-s4", BURSTY, traced=True)
    assert out["correct"], out["checks"]
    for k in ("busy_s", "window_s"):
        assert k in out["device"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"gen_lag_p99_ms", "queue_wait_p95_s",
            "service_p50_s"} <= set(out["metrics"])


def test_a_tpu_trace_with_no_device_operation_fails(monkeypatch):
    # a wrong device plane or line name must not read as an idle device
    monkeypatch.setattr(harness, "device_info", lambda jax: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    with pytest.raises(RuntimeError, match="no device operation"):
        run_tiny("dit-s4", BACKLOG, seconds=0.2, traced=True)


def _run_py(cwd, env_extra):
    env = {**os.environ, **env_extra}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "s4-backlog",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _run_py(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        cells.load_cell("no-such-cell")
    assert jax.default_backend() == "cpu"
