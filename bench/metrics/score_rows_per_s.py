"""Scored rows completed per second of the window."""


def read(rec):
    rows = sum(r["rows"] for r in rec.requests if r["ok"])
    return rows / rec.window_s if rec.requests else None
