"""Seconds per fit in host-to-device copies of the streamed chunks
(obs spans ``fit.chunk.h2d``, which wait for the copy while traced)."""
from bench.spans import seconds_per_fit


def read(rec):
    return seconds_per_fit(rec, "fit.chunk.h2d")
