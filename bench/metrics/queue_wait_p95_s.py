"""Scheduler: 95th percentile of due time to the start of the tick that
admitted the request."""

from bench.metrics._common import per_request, percentile


def read(rec):
    if rec.window.closed_loop:
        return None
    return percentile(per_request(rec.window, "due", "admit"), 95)
