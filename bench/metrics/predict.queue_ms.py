"""95th percentile of admission wait: flush start minus due time."""
from bench.readers import request_ms


def read(rec):
    return request_ms(rec, "due", "flush_start", 95)
