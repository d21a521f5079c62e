"""95th percentile, over all requests due in an open-loop window, of due
time to delivery."""

from bench.metrics._common import per_request, percentile


def read(rec):
    if rec.window.closed_loop:
        return None
    return percentile(per_request(rec.window, "due", "done"), 95)
