"""Seconds per fit in CV factorisation and scoring (obs span
``fit.eigh``: on the TPU the per-λ Cholesky solves and their scores)."""


def read(rec):
    v = [f["fit.eigh"] for f in rec.fits if "fit.eigh" in f]
    return sum(v) / len(v) if v else None
