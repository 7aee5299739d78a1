"""Bytes sent to the device per real row of the traced waves: the
``bytes`` of the ``serve.wave.h2d`` spans over their ``fill``."""
from bench.spans import named


def read(rec):
    spans = named(rec, "serve.wave.h2d")
    rows = sum(s["attrs"]["fill"] for s in spans)
    return sum(s["attrs"]["bytes"] for s in spans) / rows if rows else None
