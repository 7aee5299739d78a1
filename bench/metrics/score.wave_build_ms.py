"""Mean time to build one wave's inputs on the host (obs spans
``serve.wave.build``): the requests' rows, targets, one-hot and carried
sums written into the staging buffers, and the zeros around them."""
from bench.spans import mean_ms


def read(rec):
    return mean_ms(rec, "serve.wave.build")
