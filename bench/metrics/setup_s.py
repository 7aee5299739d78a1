"""Set-up: process start to the window (imports, chip, data or bundles,
warm-up, compiles)."""


def read(rec):
    return rec.setup_s
