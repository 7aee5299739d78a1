"""Seconds per fit in the fold statistics (obs span ``fit.stats``,
synced while a tracer is installed)."""


def read(rec):
    v = [f["fit.stats"] for f in rec.fits if "fit.stats" in f]
    return sum(v) / len(v) if v else None
