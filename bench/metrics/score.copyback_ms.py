"""Mean time to copy one wave's predictions back and scatter them into
the requests' arrays (obs spans ``serve.copyback``)."""
from bench.spans import mean_ms


def read(rec):
    return mean_ms(rec, "serve.copyback")
