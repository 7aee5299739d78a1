"""Run one benchmark cell on the chips of this machine and print its result.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with the
reference beside its limit.  The checks are also the last lines of
standard error.  With no TPU, or fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):              # run as a file: python3 bench/run.py
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))))

from bench import spec  # noqa: E402
from bench.harness import log  # noqa: E402

SRC = os.path.join(spec.ROOT, "src")
CACHE_DIR = os.path.join(spec.ROOT, ".jax_compile_cache")
WORK_DIR = os.path.join(spec.ROOT, ".bench_work")


class NoChip(RuntimeError):
    """No TPU, fewer chips than the cell asks for, or a chip with no
    entry in the peaks table."""


def setup_jax() -> None:
    """The program on the path and JAX's persistent compile cache in the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` already points),
    keeping every program, however small or quick to compile."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise ImportError(f"no program under {SRC}")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_chips(chips: int):
    """The devices to run on; raises ``NoChip`` rather than fall back."""
    import jax
    from bench import peaks

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    try:
        peaks.peaks(devs[0].device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from None
    return devs


def execute(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
            device_kind: str, budget: int, t_start: float):
    """Set up, run and check one cell on the current JAX backend; returns
    the run record.  Callers check the chips first."""
    from bench import drivers, xplane
    from bench.harness import Harness, RunRecord

    rec = RunRecord(cell=cell.name, kind=cell.traffic["kind"],
                    config=cell.config, traffic=cell.traffic,
                    limits=cell.limits, device_kind=device_kind)
    h = Harness(rec, trace=trace, workdir=os.path.join(WORK_DIR, cell.name),
                t_start=t_start)
    try:
        drivers.module(rec.kind, cell.root).run(h, seed, seconds, budget)
        if trace:
            spans = [s for s in rec.spans if s["tid"] == rec.main_thread]
            rec.trace = xplane.reduce(
                xplane.load(xplane.find(h.path("trace"))), spans,
                rec.window_t0)
    finally:
        h.close()
    return rec


def result(cell: spec.Cell, rec, trace: bool, device: dict) -> dict:
    """The result line: every metric of the cell that has a reading."""
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.reader(m.name, cell.root)(rec)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    device = dict(device, memory_peak_bytes=int(rec.memory_peak_bytes))
    out = {"correct": bool(rec.checks) and all(c.ok for c in rec.checks),
           "attempted": int(rec.attempted), "failed": int(rec.failed),
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = float(rec.trace["busy_s"])
        device["window_s"] = float(rec.trace["window_s"])
        out["breakdown"] = rec.trace["breakdown"]
    out["checks"] = {c.name: {"value": float(c.value),
                              "limit": float(c.limit)} for c in rec.checks}
    return out


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.cell(args.workload)
        setup_jax()
        devs = find_chips(cell.chips)
    except (spec.SpecError, ImportError, NoChip) as e:
        log(f"bench: {e}")
        return 1
    import jax
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": jax.device_count()}
    budget = int(devs[0].memory_stats()["bytes_limit"])
    log(f"bench: {cell.name} seed {args.seed} on {device}")
    rec = execute(cell, args.seed, args.seconds, bool(args.trace),
                  device_kind=device["kind"], budget=budget, t_start=t_start)
    out = result(cell, rec, bool(args.trace), device)
    log(f"bench: set-up {rec.setup_s:.3f} s, window {rec.window_s:.3f} s, "
        f"compiles inside the window: {rec.compiles_in_window}")
    for c in rec.checks:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAIL'}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
