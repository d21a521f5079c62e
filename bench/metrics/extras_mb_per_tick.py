"""Scheduler: megabytes of per-request conditioning the admission apply
put on the device (the program's serve_extras_bytes counter) per tick of
the window. No value where the program keeps no such counter."""

from bench.metrics._common import counter

NAME = "serve_extras_bytes"


def read(rec):
    w = rec.window
    if not w.ticks or NAME not in w.counters1:
        return None
    return counter(w, NAME) / w.ticks / 1e6
