"""99th percentile of how late the load generator sent a request."""
from bench.readers import request_ms


def read(rec):
    return request_ms(rec, "due", "sent", 99)
