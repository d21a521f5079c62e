"""The control the comparison has to fail, at a size a test run holds: the
reference computed at float8 e4m3 precision in the program's place reads
far above the program as configured (bfloat16 activations), so a limit
between the two readings passes the program and fails the control."""

import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cell as cells  # noqa: E402
from bench import check, harness  # noqa: E402

SEEDS = (11, 12, 13)


def small(base: str) -> dict:
    c = copy.deepcopy(cells.load_config(base))
    c["model"].update(num_layers=2, d_model=128, num_heads=2, num_kv_heads=2,
                      head_dim=64, d_ff=256, latent_dim=8, patch_tokens=16)
    c["serving"]["slots"] = 4
    c["check"].update(per_slot=2, block=8, limit=None)
    return c


@pytest.mark.parametrize("base", ["dit-i256-cfg", "dit-s4"])
def test_e4m3_reference_reads_far_above_the_program(base):
    config = small(base)
    assert config["model"]["dtype"] == "bfloat16"
    cell = cells.Cell(name="small", config=config, chips=1,
                      traffic={"kind": "backlog", "queue_per_slot": 1.0})
    program = [harness.run(cell, s, 0.3, False, since_start=lambda: 0.0)
               ["checks"]["latent_rel_err_max"]["value"] for s in SEEDS]
    control = [check.control_error(config, s)
               for s in SEEDS]
    assert 0 < max(program)
    assert min(control) >= 3 * max(program), (program, control)
    # the control fails the configuration's own limit, already at this size
    assert min(control) > cells.load_config(base)["check"]["limit"]


@pytest.mark.parametrize("base", ["dit-i256-cfg", "dit-s4"])
def test_control_in_the_programs_place_is_not_correct(base, monkeypatch):
    """A run judged against the configuration's own limit passes the
    program, and fails when the control's latents stand in for what the
    window delivered."""
    config = small(base)
    config["check"]["limit"] = cells.load_config(base)["check"]["limit"]
    cell = cells.Cell(name="small", config=config, chips=1,
                      traffic={"kind": "backlog", "queue_per_slot": 1.0})
    seed = SEEDS[0]
    assert harness.run(cell, seed, 0.3, False, lambda: 0.0)["correct"]

    compare = check.compare

    def control_in_place(config, seed, sample):
        specs = [s for s, _ in sample]
        ctl = check.reference_latents(config, seed, specs,
                                      bits=check.CONTROL_BITS)
        return compare(config, seed, list(zip(specs, ctl)))

    monkeypatch.setattr(check, "compare", control_in_place)
    out = harness.run(cell, seed, 0.3, False, lambda: 0.0)
    assert not out["correct"]
    assert out["checks"][check.NAME]["value"] > config["check"]["limit"]
