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

Each op also has its scope: the ``tf_op`` stat of its event's metadata,
the ``jax.named_scope`` and ``jit`` path of the op's HLO ``op_name``
(``jit(cv_solve)/cv_solve/while/body/...``).  ``jax.profiler`` does not
show metadata stats, so ``load`` reads them from the file itself, by the
few fields of the trace's protocol buffer (``tsl/profiler/protobuf/
xplane.proto``) that it needs.
"""
from __future__ import annotations

import glob
import os
import re
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


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protocol buffer
    message: an int for a varint, a memoryview for a length-delimited
    field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


# Field numbers of xplane.proto.  XSpace: planes 1.  XPlane: name 2,
# lines 3, event_metadata 4, stat_metadata 5 (maps: key 1, value 2).
# XLine: name 2, events 4.  XEvent: metadata_id 1.  XEventMetadata:
# name 2, stats 5.  XStatMetadata: name 2.  XStat: metadata_id 1,
# str_value 5, ref_value 7 (a stat metadata id whose name is the string).
def _op_scopes(plane) -> tuple[list[str], list[str]]:
    """The metadata name and the ``tf_op`` path (``""`` where there is
    none) of each event of one device plane's ops line, in file order."""
    ops_line, event_meta, stat_names = None, {}, {}
    for field, value in _fields(plane):
        if field == 3 and ops_line is None:
            # A line's name (field 2) is written before its events.
            if _text(next((v for f, v in _fields(value) if f == 2),
                          b"")) == OPS_LINE:
                ops_line = value
        elif field in (4, 5):
            entry = dict(_fields(value))
            if field == 4:
                event_meta[entry.get(1, 0)] = entry.get(2, b"")
            else:
                stat_names[entry.get(1, 0)] = _text(
                    dict(_fields(entry.get(2, b""))).get(2, b""))
    tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
    meta = {}
    for mid, value in event_meta.items():
        name, path = "", ""
        for field, v in _fields(value):
            if field == 2:
                name = _text(v)
            elif field == 5:
                stat = dict(_fields(v))
                if stat.get(1) in tf_op:
                    path = (_text(stat[5]) if 5 in stat
                            else stat_names.get(stat.get(7), ""))
        # An op's tf_op is "<op_name>:<op_type>"; the type is empty here.
        meta[mid] = (name, path.rpartition(":")[0] if ":" in path else path)
    names, paths = [], []
    for field, event in _fields(ops_line or b""):
        if field == 4:
            mid = next((v for f, v in _fields(event) if f == 1), 0)
            name, path = meta.get(mid, ("", ""))
            names.append(name)
            paths.append(path)
    return names, paths


def _device_scopes(path: str) -> dict[str, tuple[list[str], list[str]]]:
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name = next((_text(v) for f, v in _fields(plane) if f == 2), "")
        if name.startswith("/device:TPU:"):
            out[name] = _op_scopes(plane)
    return out


def load(path: str) -> dict:
    """Planes of the trace as plain data, times in seconds on the
    trace's clock: ``{"device": {plane: {line: [(name, t0, dur)]}},
    "host": [(name, t0, dur, line)], "scopes": {plane: [path]}}``, where
    ``scopes`` holds each ops-line event's ``tf_op`` path, in order."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    decoded = _device_scopes(path)
    device, host, scopes = {}, [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            device[plane.name] = {
                line.name: [(e.name, e.start_ns / 1e9, e.duration_ns / 1e9)
                            for e in line.events]
                for line in plane.lines}
            names, scopes[plane.name] = decoded[plane.name]
            ops = device[plane.name].get(OPS_LINE, [])
            if names != [n for n, _, _ in ops]:
                raise ValueError(f"{plane.name}: the ops decoded from "
                                 f"{path} are not those jax.profiler read")
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns / 1e9, e.duration_ns / 1e9,
                             line.name) for e in line.events)
    return {"device": device, "host": host, "scopes": scopes}


def reduce(raw: dict, spans: list[dict] | None = None,
           window_t0: float | None = None, top: int = 10) -> dict:
    """Busy and idle time, per-op, per-scope and per-program device
    time, and the longest idle gaps by the host span open in them.

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
    scopes, scope_count = defaultdict(float), defaultdict(int)
    gaps_all = []
    for plane, lines in sorted(raw["device"].items()):
        evs = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        iv = _union(_clip([(t, t + d) for _, t, d in evs], w0, w1))
        busy.append(sum(b - a for a, b in iv))
        # Each op keeps its own scope path through the self-time sweep:
        # ops of two programs can share a short name, never an event.
        named = [((name, path), t, d) for (name, t, d), path in
                 zip(lines.get(OPS_LINE, []), raw["scopes"][plane])]
        for (name, path), t, d, own in self_times(named):
            if t + d > w0 and t < w1:
                ops[short(name)] += own
                op_count[short(name)] += 1
                if path:
                    scopes[path] += own
                    scope_count[path] += 1
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
            "scopes": dict(scopes), "scope_count": dict(scope_count),
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


_JIT = re.compile(r"^p?jit\((.*)\)$")


def scope_parts(path: str) -> list[str]:
    """The components of a ``tf_op`` path, ``jit(f)`` read as ``f``."""
    return [_JIT.sub(r"\1", part) for part in path.split("/")]


def scope_time(trace: dict, scope: str) -> tuple[float, int]:
    """Device self seconds and event count of the ops whose scope path
    has ``scope`` as a component, summed over the chips."""
    paths = [p for p in trace["scopes"] if scope in scope_parts(p)]
    return (sum(trace["scopes"][p] for p in paths),
            sum(trace["scope_count"][p] for p in paths))
