"""Seconds per fit in the CV's held-out scoring (obs spans
``fit.cv.score``, one per fold)."""
from bench.spans import seconds_per_fit


def read(rec):
    return seconds_per_fit(rec, "fit.cv.score")
