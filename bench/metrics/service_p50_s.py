"""Step program: median of the admitting tick's start to delivery (the
request's evals plus the trailing readback)."""

from bench.metrics._common import per_request, percentile


def read(rec):
    if rec.window.closed_loop:
        return None
    return percentile(per_request(rec.window, "admit", "done"), 50)
