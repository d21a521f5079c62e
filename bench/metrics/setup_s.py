"""Process start to the first request of the window: imports, weights on
the device, engine build, compile (cached after a cell's first run), warm-up."""


def read(rec):
    return rec.setup_s
