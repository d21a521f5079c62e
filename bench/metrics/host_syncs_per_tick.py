"""Scheduler: blocking device->host reads (the program's host_syncs{site=*}
counters: initial-latent draws, flight readbacks, desync recoveries) per
tick of the window. None where the program has no such counter."""

from bench.metrics._common import counter


def read(rec):
    w = rec.window
    names = [k for k in w.counters1 if k.startswith("host_syncs{")]
    if not w.ticks or not names:
        return None
    return sum(counter(w, k) for k in names) / w.ticks
