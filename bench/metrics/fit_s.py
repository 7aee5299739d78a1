"""Seconds per subject fit: the window over the whole fits in it."""


def read(rec):
    return rec.window_s / len(rec.fits) if rec.fits else None
