"""Step program: window seconds / ticks dispatched in it (ms)."""


def read(rec):
    w = rec.window
    return 1e3 * (w.t1 - w.t0) / w.ticks if w.ticks else None
