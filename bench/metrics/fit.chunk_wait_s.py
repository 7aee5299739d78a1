"""Seconds per fit waiting on each chunk's update to finish (obs spans
``fit.chunk.wait``: the per-chunk sync of the fold statistics)."""
from bench.spans import seconds_per_fit


def read(rec):
    return seconds_per_fit(rec, "fit.chunk.wait")
