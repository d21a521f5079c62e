"""One run of one cell: build, warm up, measure, check, report.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device`, with `--trace 1` `breakdown`,
and last `checks`, each compared number beside its limit. The compared
numbers are also the last lines of standard error.
"""

from __future__ import annotations

import gc
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass

from bench import cell as cells
from bench import check, loadgen, system, trace, weights
from bench.traffic import generate


@dataclass
class Record:
    """What the metric readers read (bench/metrics/<name>.py)."""
    config: dict
    window: loadgen.Window
    setup_s: float
    rows_per_slot: int
    peaks: dict | None
    trace: dict | None = None


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks)) if peaks else 0


def run(cell: cells.Cell, seed: int, seconds: float, traced: bool,
        since_start) -> dict:
    """Measure one run of `cell` on whatever devices JAX has."""
    import jax

    config = cell.config
    dev = device_info(jax)
    peaks = cells.peaks(dev["kind"]) if dev["platform"] == "tpu" else None
    served = system.build(config, weights.make_params(config, seed))
    drv = loadgen.LoadGen(served, cell.traffic, config, seed,
                          per_slot=config["check"]["per_slot"],
                          annotate=traced)
    is_open = generate.is_open(cell.traffic)
    drv.warm_up(ticks=2 * served.sched.program.n_rows + 2, drain=is_open)
    due = (generate.arrivals(cell.traffic, seconds, seed) if is_open
           else None)
    setup_s = since_start()
    logdir = None
    if traced:
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        # no Python call tracing: the benchmark's own annotations and the
        # device's operations are what the reduction reads
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(logdir, profiler_options=opts)
    win = drv.open_loop(seconds, due) if is_open else drv.backlog(seconds)
    if traced:
        jax.profiler.stop_trace()
    print(f"window: {win.t1 - win.t0:.3f} s, {win.ticks} ticks, "
          f"{len(win.done)} delivered, {win.compiles} compilations inside "
          f"the window, compile_s {served.compile_s:.3f}", file=sys.stderr,
          flush=True)
    dev["memory_peak_bytes"] = memory_peak(jax)
    rows = served.rows_per_slot
    del drv, served
    gc.collect()
    red = None
    if traced:
        red = trace.reduce(trace.read(trace.load(trace.find_xplane(logdir))))
        shutil.rmtree(logdir, ignore_errors=True)
        if dev["platform"] == "tpu" and red["busy_s"] <= 0:
            # the reduction found no device plane or no operation on it:
            # every device metric would be missing or read as idle
            raise RuntimeError("the profiler trace holds no device operation "
                               "inside the window")
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
    t_check = time.perf_counter()
    err = check.compare(config, seed, win.sample)
    print(f"check: {len(win.sample)} requests from "
          f"{len(set(win.slot.values()))} slots against the reference in "
          f"{time.perf_counter() - t_check:.1f} s", file=sys.stderr,
          flush=True)
    limit = config["check"]["limit"]

    rec = Record(config=config, window=win,
                 setup_s=setup_s, rows_per_slot=rows, peaks=peaks, trace=red)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = cells.metric_reader(m["name"])(rec)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    if win.closed_loop:
        attempted = len(win.done)
        failed = sum(1 for ok in win.ok.values() if not ok)
    else:
        attempted = len(win.due)
        failed = win.undelivered + sum(1 for ok in win.ok.values() if not ok)
    checks = {check.NAME: {"value": _finite(err), "limit": limit},
              "failed_requests": {"value": failed, "limit": 0}}
    correct = (limit is not None and math.isfinite(err) and err <= limit
               and failed == 0 and attempted > 0)
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": dev}
    if red is not None:
        out["breakdown"] = trace.breakdown(red)
    out["checks"] = checks
    return out


def _finite(v: float):
    return v if math.isfinite(v) else None


def emit(out: dict) -> None:
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(args, since_start) -> int:
    cell = cells.load_cell(args.workload)
    import jax

    dev = device_info(jax)
    if dev["platform"] != "tpu":
        print(f"bench: needs a TPU; JAX found platform {dev['platform']!r}",
              file=sys.stderr)
        return 2
    if dev["count"] < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips; JAX found "
              f"{dev['count']}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    # every program of the run goes to the persistent cache, small ones too,
    # so that only a cell's first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"bench: {cell.name} seed {args.seed} on {dev['kind']} x"
          f"{dev['count']}, compile cache {cache}", file=sys.stderr,
          flush=True)
    emit(run(cell, args.seed, float(args.seconds), bool(args.trace),
             since_start))
    return 0

