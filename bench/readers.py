"""Arithmetic the metric readers share: percentiles of request times,
shares of the device's peak, and the traced window's idle share."""
from __future__ import annotations

import math

from bench import peaks, work, xplane
from bench.arrivals import percentile


def request_ms(rec, start: str, end: str, q: float, ok_only: bool = True):
    """The q-th percentile of ``end − start`` over the window's requests,
    in ms.  A request that failed counts as infinitely late."""
    vals = [(r[end] - r[start]) * 1e3 if r["ok"] else math.inf
            for r in rec.requests if r["ok"] or not ok_only]
    return percentile(vals, q) if vals else None


def idle_percent(rec):
    if rec.trace is None or not rec.trace["window_s"]:
        return None
    return 100.0 * (1.0 - rec.trace["busy_s"] / rec.trace["window_s"])


def flush_mfu_percent(rec):
    """Real rows' prediction work over the time the server spent in
    flushes, as a share of the peak: the whole serving step."""
    c = rec.config
    flushes: dict[float, list[int]] = {}
    for r in rec.requests:
        if r["ok"]:
            flushes.setdefault((r["flush_start"], r["done"]), []).append(
                r["rows"])
    if not flushes:
        return None
    busy = sum(b - a for a, b in flushes)
    flops = sum(work.wave_flops(sum(rows), c["p"], c["t"])
                for rows in flushes.values())
    return 100.0 * flops / busy / peaks.peaks(rec.device_kind)["flops_per_s"]


def wave_device_ms(rec):
    if rec.trace is None:
        return None
    ev = xplane.module_events(rec.trace, "_predict_mixed")
    return 1e3 * sum(d for _, _, d in ev) / len(ev) if ev else None


def wave_roofline_percent(rec, scored: bool):
    """The mean least time of a traced wave (from the wave shapes the
    service recorded) over its mean device time (from the trace)."""
    if rec.trace is None:
        return None
    ev = xplane.module_events(rec.trace, "_predict_mixed")
    spans = [s for s in rec.spans if s["name"] == "serve.wave.execute"]
    if not ev or not spans:
        return None
    c = rec.config
    slots = c["serving"]["score_slots"]
    least = sum(peaks.roofline_seconds(
        work.wave_flops(s["attrs"]["rows"], c["p"], c["t"], scored),
        work.wave_bytes(s["attrs"]["rows"], c["p"], c["t"], scored, slots),
        rec.device_kind) for s in spans) / len(spans)
    return 100.0 * least / (sum(d for _, _, d in ev) / len(ev))
