"""One driver per kind of run, found by the ``kind`` of a traffic mix.

A driver is a module ``bench/drivers/<name>.py`` that declares ``KINDS``,
the traffic kinds it runs, and exposes:

- ``run(h, seed, seconds, budget)``: build the system under test from the
  configuration, set it up, drive the measured window and compare what
  the window produced with the plain reference;
- ``control_checks(cell, seed, seconds, precision)``: the same comparison
  with the reference, at ``precision``, in the program's place;
- ``tiny(cell)``: the cell at a size the CPU runs in a test;
- ``faults(kind)``: the faults a test plants in the program, as
  ``{name: (target, attr, replacement)}``, each of which must read
  incorrect.

Drivers know kinds of work, never a cell: a new kind of run is a new
driver file, found by name like a metric's reader.
"""
from __future__ import annotations

import glob
import os

from bench import spec


def module(kind: str, root: str = spec.ROOT):
    """The one driver module under ``root`` whose ``KINDS`` holds
    ``kind``; none, or more than one, is a ``spec.SpecError``."""
    found = []
    for path in sorted(glob.glob(os.path.join(root, "bench", "drivers",
                                              "*.py"))):
        stem = os.path.basename(path)[:-3]
        if stem.startswith("_"):
            continue
        mod = spec.load_file(path, f"bench_driver_{stem}")
        if kind in getattr(mod, "KINDS", ()):
            found.append(mod)
    if len(found) != 1:
        names = [os.path.basename(m.__file__) for m in found]
        raise spec.SpecError(f"traffic kind {kind!r} is run by "
                             f"{len(found)} driver modules {names}; "
                             f"want exactly one")
    return found[0]

