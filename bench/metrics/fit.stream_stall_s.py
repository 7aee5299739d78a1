"""Seconds per fit the chunk consumer waited on the store reader
(``PrefetchStats.read_stall_s``)."""


def read(rec):
    v = [f["read_stall_s"] for f in rec.fits]
    return sum(v) / len(v) if v else None
