"""Traffic generator: 99th percentile of how late the load generator submitted a
request after its due time (ms)."""

from bench.metrics._common import per_request, percentile


def read(rec):
    if rec.window.closed_loop:
        return None
    v = percentile(per_request(rec.window, "due", "submit"), 99)
    return None if v is None else 1e3 * v
