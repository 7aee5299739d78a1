"""Mean time to send one wave's inputs to the device (obs spans
``serve.wave.h2d``, which wait for the copies while traced)."""
from bench.spans import mean_ms


def read(rec):
    return mean_ms(rec, "serve.wave.h2d")
