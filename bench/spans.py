"""Arithmetic the span readers share: the program's ``repro.obs`` spans
of a traced run, per fit or per wave.  A program that records no span of
the name read gives None, and the harness leaves the metric out."""
from __future__ import annotations


def named(rec, name: str) -> list[dict]:
    return [s for s in rec.spans if s["name"] == name]


def seconds_per_fit(rec, name: str):
    """Seconds per fit in the spans called ``name``."""
    spans = named(rec, name)
    if not spans or not rec.fits:
        return None
    total = sum(s["dur"] for f in rec.fits for s in spans
                if f["t0"] <= s["t0"] < f["t0"] + f["dur_s"])
    return total / len(rec.fits)


def mean_ms(rec, name: str):
    """Mean duration of one span called ``name``, in ms."""
    spans = named(rec, name)
    return 1e3 * sum(s["dur"] for s in spans) / len(spans) if spans else None
