"""Knee sweep: serve one cell's open-loop mix at a few fixed rates, in one
process on the chip, and print each rate's latency and backlog growth.

    python3 bench/knee.py --config dit-i256-cfg --traffic poisson \
        --rates 8,10,12,14 --seconds 10 --out knee.json

`--traffic` names an open-loop mix's file, bench/traffic/<name>.json, or
gives the mix itself as JSON (`'{"kind": "poisson"}'`); its `rate` is
replaced by each rate of the sweep. The cell need not be in
BENCHMARK.json yet.

The knee is the highest rate whose queue does not grow over the window (the
last quarter's median latency within 1.5x the first quarter's). The cell's
traffic file then carries 0.8 x the knee as a number; the sweep is run once,
not by the benchmark.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def sweep(config, traffic, rates, seconds, seed):
    import numpy as np

    from bench import loadgen, system, weights
    from bench.traffic import generate

    served = system.build(config, weights.make_params(config, seed))
    rows = []
    for k, rate in enumerate(rates):
        mix = dict(traffic, rate=rate)
        drv = loadgen.LoadGen(served, mix, config, seed + k, per_slot=0)
        drv.warm_up(ticks=2 * served.sched.program.n_rows + 2, drain=True)
        win = drv.open_loop(seconds, generate.arrivals(mix, seconds, seed))
        rids = sorted(win.due, key=win.due.get)
        lat = np.array([win.done[r] - win.due[r] if r in win.done else np.inf
                        for r in rids])
        q = max(1, len(lat) // 4)
        first, last = np.median(lat[:q]), np.median(lat[-q:])
        rows.append({"rate": rate, "requests": len(lat),
                     "p50_s": float(np.percentile(lat, 50)),
                     "p95_s": float(np.percentile(lat, 95)),
                     "first_quarter_p50_s": float(first),
                     "last_quarter_p50_s": float(last),
                     "growing": bool(last > 1.5 * first),
                     "ticks": win.ticks, "drain_s": win.t1 - win.t0 - seconds})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out")
    args = ap.parse_args()
    import jax

    from bench import cell as cells
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("knee: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    rows = sweep(cells.load_config(args.config),
                 (json.loads(args.traffic) if args.traffic.startswith("{")
                  else cells.load_traffic(args.traffic)),
                 [float(r) for r in args.rates.split(",")], args.seconds,
                 args.seed)
    ok = [r["rate"] for r in rows if not r["growing"]]
    knee = max(ok) if ok else None
    print(json.dumps({"knee": knee, "rate_0.8": knee and 0.8 * knee}))
    if args.out:
        Path(args.out).write_text(json.dumps({"rows": rows, "knee": knee},
                                             indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
