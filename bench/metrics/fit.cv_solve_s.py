"""Seconds per fit in the CV's per-fold solves (obs spans
``fit.cv.solve``: on the TPU the r per-λ Cholesky solves of a fold)."""
from bench.spans import seconds_per_fit


def read(rec):
    return seconds_per_fit(rec, "fit.cv.solve")
