"""Scheduler: host time in admission (the program's
host_phase_ns{phase="admission"} counter) per tick of the window (us)."""

from bench.metrics._common import counter


def read(rec):
    w = rec.window
    if not w.ticks:
        return None
    return counter(w, 'host_phase_ns{phase="admission"}') / w.ticks / 1e3
