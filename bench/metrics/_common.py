"""Helpers the metric readers share: per-request times of a window."""

import numpy as np


def per_request(win, start: str, end: str) -> np.ndarray:
    """end - start over the window's delivered requests (seconds)."""
    a, b = getattr(win, start), getattr(win, end)
    v = np.array([b[r] - a[r] for r in win.done if r in a and r in b],
                 np.float64)
    return v[np.isfinite(v)]


def percentile(v: np.ndarray, q: float):
    return float(np.percentile(v, q)) if v.size else None


def counter(win, name: str) -> float:
    """A program counter's increase over the window."""
    return win.counters1.get(name, 0) - win.counters0.get(name, 0)


def roofline(rec, kernel: str):
    """Share (%) of the least time the kernel's calls could take (the larger
    of operations over peak and bytes over HBM bandwidth, counted from the
    algorithm's shapes for each call) in the device time its calls took.

    Each traced op is charged the call of `per_call`'s list that it makes:
    the only one, or the one the cost module's `call_of` tells from the op's
    operands. Where an op's call is not known there is no value."""
    from bench.cell import cost_module

    if rec.trace is None or rec.peaks is None:
        return None
    cost = cost_module(kernel)
    ops = {name: (cost.kind(name), op) for name, op in rec.trace["ops"].items()}
    least = took = 0.0
    for name, (kind, op) in ops.items():
        if kind is None:
            continue
        each = cost.per_call(kind, rec.config, rec.rows_per_slot)
        i = 0 if len(each) == 1 else cost.call_of(
            name, [n for n, (k, _) in ops.items() if k == kind], rec.config)
        if i is None:
            return None
        flops, nbytes = each[i]
        least += op["calls"] * max(flops / rec.peaks["bf16_flops"],
                                   nbytes / rec.peaks["hbm_bytes_per_s"])
        took += op["seconds"]
    return 100.0 * least / took if took > 0 else None
