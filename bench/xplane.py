"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Device planes are the ``/device:TPU:<i>`` planes.  On each, the ops line
(``XLA Ops``) gives the device's busy intervals and per-op self time (an op that encloses others, such as a ``while``,
keeps only the time they leave), and the
modules line (``XLA Modules``) gives one event per execution of a compiled
program.  Busy time is the union of the op intervals inside the traced
window, averaged over the chips.  Host time is put on the device's clock
through the ``bench.window`` annotation, which the harness opens at a
known ``perf_counter`` reading; each idle gap of the device is then named
by the innermost host span open at its midpoint.  The profiler puts the
device's events on the host clock to within about a millisecond, so an
op or program counts when it overlaps the window at all.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

ANCHOR = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def short(name: str) -> str:
    """An HLO op's name and result type, without its operands:
    ``%xty_folds_masked.1 = f32[2,16384,16896]{...} custom-call(...)``
    becomes ``xty_folds_masked.1 f32[2,16384,16896]``."""
    head, eq, rest = name.partition(" = ")
    if not eq or rest.startswith("("):       # no name, or a tuple result
        return head.lstrip("%")
    return f"{head.lstrip('%')} {rest.split('{', 1)[0].split(' ', 1)[0]}"


def self_times(events) -> list[tuple[str, float, float, float]]:
    """(name, start, duration, self time) of each event of one line:
    an event that encloses others (a ``while`` around its body's ops)
    keeps only the time its direct children do not cover."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    child = [0.0] * len(evs)
    stack: list[tuple[float, int]] = []
    for i, (_, t, d) in enumerate(evs):
        while stack and stack[-1][0] <= t:
            stack.pop()
        if stack:
            child[stack[-1][1]] += d
        stack.append((t + d, i))
    return [(n, t, d, max(0.0, d - c)) for (n, t, d), c in zip(evs, child)]


def load(path: str) -> dict:
    """Planes of the trace as plain data, times in seconds on the
    trace's clock: ``{"device": {plane: {line: [(name, t0, dur)]}},
    "host": [(name, t0, dur, line)]}``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            device[plane.name] = {
                line.name: [(e.name, e.start_ns / 1e9, e.duration_ns / 1e9)
                            for e in line.events]
                for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns / 1e9, e.duration_ns / 1e9,
                             line.name) for e in line.events)
    return {"device": device, "host": host}


def reduce(raw: dict, spans: list[dict] | None = None,
           window_t0: float | None = None, top: int = 10) -> dict:
    """Busy and idle time, per-op and per-program device time, and the
    longest idle gaps by the host span open in them.

    ``spans`` are host spans on the ``perf_counter`` clock (``t0``,
    ``dur``, ``name``, ``depth``), ``window_t0`` the ``perf_counter``
    reading at which the ``bench.window`` annotation opened; without them
    gaps are named by the profiler's own host events.
    """
    anchors = [h for h in raw["host"] if h[0] == ANCHOR]
    if not anchors:
        raise ValueError(f"trace has no {ANCHOR!r} annotation")
    _, w0, wdur, _ = max(anchors, key=lambda h: h[2])
    w1 = w0 + wdur
    shift = 0.0 if window_t0 is None else w0 - window_t0

    busy, ops, modules = [], defaultdict(float), []
    op_count: dict[str, int] = defaultdict(int)
    gaps_all = []
    for plane, lines in sorted(raw["device"].items()):
        evs = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        iv = _union(_clip([(t, t + d) for _, t, d in evs], w0, w1))
        busy.append(sum(b - a for a, b in iv))
        for name, t, d, own in self_times(lines.get(OPS_LINE, [])):
            if t + d > w0 and t < w1:
                ops[short(name)] += own
                op_count[short(name)] += 1
        for name, t, d in lines.get(MODULES_LINE, []):
            if t + d > w0 and t < w1:
                modules.append((name, t - shift, d))
        edges = [w0] + [x for ab in iv for x in ab] + [w1]
        gaps_all.extend((a, b) for a, b in zip(edges[::2], edges[1::2])
                        if b > a)
    n = max(1, len(raw["device"]))

    if spans is not None:
        host = [(s["name"], s["t0"] + shift, s["dur"], s.get("depth", 0))
                for s in spans if s["dur"] > 0]
    else:
        host = [(nm, t, d, 0) for nm, t, d, _ in raw["host"]
                if nm != ANCHOR and d > 0]
    idle = defaultdict(float)
    for (a, b), name in zip(gaps_all, _open_at([(a + b) / 2
                                                for a, b in gaps_all], host)):
        idle[name] += (b - a) / n

    def top_n(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]

    return {"window_s": wdur, "busy_s": sum(busy) / n, "chips": n,
            "ops": dict(ops), "op_count": dict(op_count),
            "modules": modules,
            "breakdown": {"device_ops": top_n({k: v / n
                                               for k, v in ops.items()}),
                          "idle_gaps": top_n(idle)}}


def _open_at(points: list[float], host) -> list[str]:
    """For each point, the innermost host span open there (the deepest,
    then the latest to start), by one sweep over sorted points."""
    order = sorted(range(len(points)), key=points.__getitem__)
    edges = sorted([(t, 1, i) for i, (_, t, d, _) in enumerate(host)]
                   + [(t + d, 0, i) for i, (_, t, d, _) in enumerate(host)])
    active: set[int] = set()
    names = ["no host span"] * len(points)
    j = 0
    for k in order:
        while j < len(edges) and edges[j][0] <= points[k]:
            _, is_start, i = edges[j]
            (active.add if is_start else active.discard)(i)
            j += 1
        if active:
            best = max(active, key=lambda i: (host[i][3], host[i][1]))
            names[k] = host[best][0]
    return names


def module_events(trace: dict, *fragments: str) -> list[tuple[str, float, float]]:
    """Executions of the programs whose name holds any of ``fragments``."""
    return [m for m in trace["modules"] if any(f in m[0] for f in fragments)]


def op_time(trace: dict, *fragments: str) -> tuple[float, int]:
    """Device seconds and event count of the ops whose name holds any of
    ``fragments``, summed over the chips."""
    names = [k for k in trace["ops"] if any(f in k for f in fragments)]
    return (sum(trace["ops"][k] for k in names),
            sum(trace["op_count"][k] for k in names))
