"""Readings for a configuration's correctness limit, on the chip, in one
process: the compared number over many seeds of the program as the
configuration states it (the lower reading), over a few seeds of the control
(the upper reading), and under each fault planted in the timed path
(bench/faults.py), at the cell's own size and load.

    python3 bench/limits.py --workload i256-cfg-backlog --seeds 1-12 \
        --control-seeds 3 --faults unchanged_state,half_batch,altered_answer \
        --seconds 5 --out limits.json

A program or fault reading is a short run of the cell, checked as a
benchmark run is. The control is the reference itself computed at float8
e4m3 precision (3 mantissa bits) in the program's place, over as many
requests as a run's check takes, compared with the float32 reference. Not
run by the benchmark.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def seeds(spec: str) -> list:
    lo, _, hi = spec.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def faulty_run(cell, seed, seconds, fault):
    """One run of `cell` with `fault` planted under its timed path."""
    from bench import faults, harness, system

    undo = []

    def patch(obj, name, value):
        undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    real = system.build

    def build(config, params):
        served = real(config, params)
        faults.plant(served, fault, patch)
        return served

    patch(system, "build", build)
    try:
        return harness.run(cell, seed, seconds, False, lambda: 0.0)
    finally:
        for obj, name, value in reversed(undo):
            setattr(obj, name, value)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--base-seed", type=int, default=1000)
    ap.add_argument("--out")
    args = ap.parse_args()
    import jax

    from bench import cell as cells
    from bench import check, harness
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("limits: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    cell = cells.load_cell(args.workload)
    runs = [("program", s) for s in seeds(args.seeds)]
    runs += [("ref-e4m3", args.base_seed + k)
             for k in range(args.control_seeds)]
    for f in filter(None, args.faults.split(",")):
        runs += [(f, args.base_seed + 100 + k)
                 for k in range(args.control_seeds)]
    readings = []
    for what, seed in runs:
        t0 = time.perf_counter()
        r = {"what": what, "seed": seed}
        try:
            if what == "ref-e4m3":
                r["value"] = check.control_error(cell.config, seed)
            else:
                out = (harness.run(cell, seed, args.seconds, False,
                                   lambda: 0.0) if what == "program"
                       else faulty_run(cell, seed, args.seconds, what))
                r.update(value=out["checks"][check.NAME]["value"],
                         attempted=out["attempted"], failed=out["failed"],
                         correct=out["correct"])
        except Exception as e:  # a control that crashes sets no upper end
            r.update(value=None, error=f"{type(e).__name__}: {e}"[:500])
        r["seconds"] = time.perf_counter() - t0
        readings.append(r)
        print(json.dumps(r), flush=True)
    summary = {}
    for what in dict.fromkeys(w for w, _ in runs):
        v = [r["value"] for r in readings if r["what"] == what]
        v = [x if x is not None else float("inf") for x in v]
        summary[what] = {"n": len(v), "min": min(v), "max": max(v)}
    print(json.dumps(summary), flush=True)
    if args.out:
        Path(args.out).write_text(json.dumps(
            {"workload": args.workload, "readings": readings,
             "summary": summary}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
