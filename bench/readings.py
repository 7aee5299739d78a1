"""Readings that the correctness limits are set from (run by hand on the
chip, at the cell's own size; the benchmark's runs never run this).

    python3 -m bench.readings --workload parcels.fit --seeds 1,2,3 \\
        --control-seeds 4,5,6 --seconds 10

For each of ``--seeds`` the program runs as in a benchmark run and its
compared numbers are printed (the lower readings).  For each of
``--control-seeds`` the control stands in for the program: the reference
itself, one precision step below the configuration's (``high``, three
bf16 passes, for f32 at ``highest``), compared the same way (the upper
readings).  One JSON line per seed, all in one process.
"""
from __future__ import annotations

import argparse
import json
import time

from bench import drivers, run, spec

# One precision step below each configuration's.
CONTROL_PRECISION = {"highest": "high"}


def _print(kind: str, seed: int, checks) -> None:
    print(json.dumps({"kind": kind, "seed": seed,
                      **{c.name: c.value for c in checks}}), flush=True)


def control_checks(cell: spec.Cell, seed: int, seconds: float):
    """The compared numbers with the control in the program's place."""
    lower = CONTROL_PRECISION[cell.config["precision"]]
    return drivers.module(cell.traffic["kind"], cell.root).control_checks(
        cell, seed, seconds, lower)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    run.setup_jax()
    devs = run.find_chips(cell.chips)
    budget = int(devs[0].memory_stats()["bytes_limit"])
    for s in filter(None, args.seeds.split(",")):
        rec = run.execute(cell, int(s), args.seconds, False,
                          device_kind=devs[0].device_kind, budget=budget,
                          t_start=time.perf_counter())
        _print("program", int(s), rec.checks)
    for s in filter(None, args.control_seeds.split(",")):
        _print("control", int(s), control_checks(cell, int(s), args.seconds))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
