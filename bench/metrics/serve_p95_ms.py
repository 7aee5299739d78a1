"""95th percentile of request latency, due time to predictions on the
host, over every request of the window (a failed one counts as late)."""
from bench.readers import request_ms


def read(rec):
    return request_ms(rec, "due", "done", 95, ok_only=False)
