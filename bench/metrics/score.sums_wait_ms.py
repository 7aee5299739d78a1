"""Mean wait for a scored wave's Pearson sums on the host (obs spans
``serve.wave.sums_wait``: the wave program's end and the pull)."""
from bench.spans import mean_ms


def read(rec):
    return mean_ms(rec, "serve.wave.sums_wait")
