"""From a profiler trace (`.xplane.pb`) to device busy time, per-operation
device time and the idle gaps, each gap put down to the host span it fell in.

Device planes are the `/device:TPU:<n>` planes; their operations are the
events of the line named `XLA Ops` (of `XLA Modules` where a plane has no
such line), named after the compiled HLO instruction, so a Pallas kernel's
events carry its jitted wrapper's name (`flash_attention.6`,
`adaln_modulate.14`, `gate_residual.12`). The host spans are the benchmark's own
`TraceAnnotation`s (`bench.*`) on the host plane. Everything is clipped to
the `bench.window` span.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINES = ("XLA Ops", "XLA Modules")
WINDOW = "bench.window"
HOST_PREFIX = "bench."


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def read(profile) -> dict:
    """Raw events: {"devices": [[(name, start, end), ...] per device],
    "host": [(name, start, end), ...]} in nanoseconds."""
    devices, host = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            name = next((n for n in OPS_LINES if n in lines), None)
            devices.append(_events(lines[name]) if name else [])
        else:
            for line in plane.lines:
                host.extend(ev for ev in _events(line)
                            if ev[0].startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def merge(intervals) -> list:
    """Union of [start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def segments(spans) -> list:
    """Nested host spans [(start, end, name)] flattened into disjoint
    (start, end, name) pieces, each named by its innermost span."""
    out, stack, t = [], [], None
    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, name = stack.pop()
            if t < end:
                out.append((t, end, name))
            t = max(t, end)
        if stack and t < s:
            out.append((t, s, stack[-1][1]))
        stack.append((e, n))
        t = s
    while stack:
        end, name = stack.pop()
        if t < end:
            out.append((t, end, name))
        t = max(t, end)
    return out


def _attribute(gaps, segs, idle, weight) -> None:
    """Add each gap's overlap with each host piece to idle[name]; what no
    piece covers goes to "other"."""
    starts = [s for s, _, _ in segs]
    for a, b in gaps:
        covered = 0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(segs) and segs[i][0] < b:
            s, e, n = segs[i]
            o = min(b, e) - max(a, s)
            if o > 0:
                idle[n] += o * 1e-9 * weight
                covered += o
            i += 1
        if b - a > covered:
            idle["other"] += (b - a - covered) * 1e-9 * weight


def reduce(raw: dict) -> dict:
    """Busy and idle seconds of the window, per-op device seconds and call
    counts, and the idle time by the host span it fell in. Times are
    averaged over the device planes."""
    wins = [(s, e) for n, s, e in raw["host"] if n == WINDOW]
    if not wins:
        raise ValueError("no bench.window span in the trace")
    w0, w1 = wins[0]
    segs = segments([(max(s, w0), min(e, w1), n) for n, s, e in raw["host"]
                     if n != WINDOW and e > w0 and s < w1])
    ops = defaultdict(lambda: [0, 0.0])
    busy_total, idle = 0.0, defaultdict(float)
    n_dev = max(1, len(raw["devices"]))
    for dev in raw["devices"]:
        clipped = []
        for name, s, e in dev:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            clipped.append((s, e))
            ops[name][0] += 1
            ops[name][1] += (e - s) * 1e-9
        busy = merge(clipped)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        gaps, prev = [], w0
        for s, e in busy + [[w1, w1]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        _attribute(gaps, segs, idle, 1.0 / n_dev)
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy_total / n_dev,
            "devices": len(raw["devices"]),
            "ops": {k: {"calls": v[0], "seconds": v[1] / n_dev}
                    for k, v in ops.items()},
            "idle": dict(idle)}


def breakdown(red: dict, top: int = 10) -> dict:
    ops = sorted(((k, v["seconds"]) for k, v in red["ops"].items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["idle"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
