"""Scheduler: host time blocked reading the admitted requests' initial
latents back from the device (the program's host_blocked_ns{site="draw"}
counter, bumped at the read in `SlotScheduler._draw`) per tick of the
window (us). None where the program has no such counter."""

from bench.metrics._common import counter

NAME = 'host_blocked_ns{site="draw"}'


def read(rec):
    w = rec.window
    if not w.ticks or NAME not in w.counters1:
        return None
    return counter(w, NAME) / w.ticks / 1e3
