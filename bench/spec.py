"""Find a cell's configuration, traffic, limits and metric readers by name.

Nothing here knows a particular cell: ``BENCHMARK.json`` names them, and
each name maps to a file of its own under ``bench/``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


class SpecError(ValueError):
    """A name that ``BENCHMARK.json`` or a data file does not resolve."""


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: tuple[str, ...] | None   # None: see ``cell``
    moves: str | None = None            # per-layer: the end-to-end metric


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]
    root: str = ROOT                    # the checkout its files came from


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _metrics(entries: list[dict]) -> list[Metric]:
    return [Metric(name=m["name"], unit=m["unit"],
                   workloads=(tuple(m["workloads"]) if "workloads" in m
                              else None), moves=m.get("moves"))
            for m in entries]


def cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with every file it names
    read in."""
    bench = load_benchmark(root)
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; known: "
                        f"{[w['name'] for w in bench['workloads']]}")
    w = found[0]
    return assemble(name, w["config"], w["traffic"], int(w["chips"]), root)


def assemble(name: str, config: str, traffic: str, chips: int = 1,
             root: str = ROOT) -> Cell:
    """A cell from a configuration and a traffic mix by their names, with
    the metrics ``BENCHMARK.json`` gives a cell called ``name``."""
    bench = load_benchmark(root)
    cfgs = [c for c in bench["configs"] if c["name"] == config]
    if not cfgs:
        raise SpecError(f"cell {name!r} names config {config!r}, which "
                        f"BENCHMARK.json does not list")
    cfg = _read_json(os.path.join(root, cfgs[0]["file"]))
    mix = _read_json(os.path.join(root, "bench", "traffic",
                                  f"{traffic}.json"))
    limits = _read_json(os.path.join(root, "bench", "limits",
                                     f"{name}.json"))
    # A metric without a ``workloads`` list: an end-to-end one is in every
    # cell, a per-layer one in every cell that reports the metric it moves.
    e2e = [m for m in _metrics(bench["end_to_end"])
           if m.workloads is None or name in m.workloads]
    moved = {m.name for m in e2e}
    layer = [m for m in _metrics(bench["per_layer"])
             if (name in m.workloads if m.workloads is not None
                 else m.moves in moved)]
    return Cell(name=name, config_name=config, traffic_name=traffic,
                chips=chips, config=cfg, traffic=mix, limits=limits,
                end_to_end=tuple(e2e), per_layer=tuple(layer), root=root)


def reader(metric: str, root: str = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``.

    A reader returns the metric's value, or None where the run holds
    nothing for it to read; the harness then leaves the metric out.
    """
    path = os.path.join(root, "bench", "metrics", f"{metric}.py")
    if not os.path.exists(path):
        raise SpecError(f"no reader bench/metrics/{metric}.py")
    return load_file(path, f"bench_metric_{metric}").read


def load_file(path: str, name: str):
    """The Python file ``path`` run as a module called ``name``."""
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
