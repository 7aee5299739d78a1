"""Share of the waves built in host buffers an earlier wave had already
touched: the ``serve.wave.build`` spans whose ``reused`` is 1, in %.
Silent where the spans carry no ``reused`` (a program that builds every
wave in fresh arrays)."""
from bench.spans import named


def read(rec):
    spans = [s for s in named(rec, "serve.wave.build")
             if "reused" in s["attrs"]]
    if not spans:
        return None
    return 100.0 * sum(s["attrs"]["reused"] for s in spans) / len(spans)
