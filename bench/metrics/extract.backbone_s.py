"""Seconds per fit in the backbone's feature extraction (obs span
``fit.extract``: packed batches of runs through the backbone, pooled to
TRs; its device work is waited for before it ends)."""
from bench.spans import seconds_per_fit


def read(rec):
    return seconds_per_fit(rec, "fit.extract")
