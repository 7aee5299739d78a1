"""One driver per kind of traffic mix (the ``kind`` key of its file).

A driver builds the system under test from a configuration, runs its
set-up, drives the measured window and compares what the window produced
with the plain reference.  Drivers know kinds of work, never a cell.
"""
from __future__ import annotations


def driver(kind: str):
    """The ``run`` function of the driver for a traffic ``kind``."""
    if kind == "fit_back_to_back":
        from bench.drivers.fit import run
    elif kind in ("open_loop", "closed_loop"):
        from bench.drivers.serve import run
    else:
        raise ValueError(f"no driver for traffic kind {kind!r}")
    return run
