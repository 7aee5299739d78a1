"""Unified observability layer: spans, metrics, recompile sentinels.

Lockdown contracts of ``repro.obs``:

* spans nest (parent/depth reflect the per-thread stack) and record
  safely from concurrent threads onto distinct tracks;
* the Perfetto export carries every key the trace_event spec requires;
* ``CompileCounter.expect`` windows raise :class:`RecompileError` AT
  TRACE TIME when a fixed-shape tier retraces under
  ``REPRO_OBS_STRICT=1`` — and never raise when strict mode is off;
* the metrics snapshot round-trips losslessly through JSON;
* with no tracer installed the instrumented hot paths are free: the
  spans a chunked fit would emit cost <2% of that fit's wall time.
"""
import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.launch import obs_report

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Every test starts (and ends) with no tracer installed."""
    obs.uninstall()
    yield
    obs.uninstall()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_records_parent_depth_attrs():
    tracer = obs.install()
    with obs.span("outer", phase="a"):
        with obs.span("inner") as sp:
            sp.set(bytes=128)
        obs.instant("marker", hit=True)
    obs.uninstall()

    by_name = {e["name"]: e for e in tracer.events()}
    assert set(by_name) == {"outer", "inner", "marker"}
    outer, inner, marker = (by_name[k] for k in ("outer", "inner", "marker"))
    assert outer["depth"] == 0 and outer["parent"] is None
    assert inner["depth"] == 1 and inner["parent"] == "outer"
    assert inner["attrs"] == {"bytes": 128}
    assert outer["attrs"] == {"phase": "a"}
    assert marker["instant"] is True and marker["parent"] == "outer"
    # children are contained in the parent on the monotonic clock
    assert outer["ts_us"] <= inner["ts_us"]
    assert inner["ts_us"] + inner["dur_us"] \
        <= outer["ts_us"] + outer["dur_us"] + 1.0


def test_span_disabled_is_shared_noop():
    assert obs.current() is None
    s1 = obs.span("anything", big=1)
    s2 = obs.span("else")
    assert s1 is s2                       # the shared singleton
    with s1 as sp:
        sp.set(x=1)                       # no-op, no state


def test_timed_measures_without_tracer():
    with obs.timed("region") as t:
        time.sleep(0.01)
    assert t.dur_s >= 0.009               # measured even when disabled
    tracer = obs.install()
    with obs.timed("region") as t2:
        pass
    obs.uninstall()
    (ev,) = tracer.events()
    assert ev["name"] == "region"
    assert abs(ev["dur_us"] - t2.dur_s * 1e6) < 1.0   # same measurement


def test_span_thread_safety_distinct_tracks():
    tracer = obs.install()
    n_threads, spans_each = 8, 25
    barrier = threading.Barrier(n_threads)

    def work(i):
        barrier.wait()
        for j in range(spans_each):
            with obs.span(f"t{i}", j=j):
                with obs.span(f"t{i}.child"):
                    pass

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    obs.uninstall()

    events = tracer.events()
    assert len(events) == n_threads * spans_each * 2
    tracks = {e["track"] for e in events}
    assert len(tracks) == n_threads       # one track per thread
    # nesting never leaked across threads: every child's parent is its
    # own thread's outer span
    for e in events:
        if e["name"].endswith(".child"):
            assert e["parent"] == e["name"][:-len(".child")]
            assert e["depth"] == 1


def test_span_ids_identify_parents_when_names_repeat():
    tracer = obs.install()
    for _ in range(2):
        with obs.span("flush"):
            with obs.span("wave"):
                with obs.timed("wait"):
                    pass
            obs.instant("mark")
    obs.uninstall()

    ev = tracer.events()
    assert len({e["id"] for e in ev}) == len(ev) == 8
    flushes = [e for e in ev if e["name"] == "flush"]
    waves = [e for e in ev if e["name"] == "wave"]
    assert [f["parent_id"] for f in flushes] == [None, None]
    for f, w in zip(flushes, waves):
        kids = [e for e in ev if e["parent_id"] == f["id"]]
        assert sorted(k["name"] for k in kids) == ["mark", "wave"]
        (wait,) = [e for e in ev if e["parent_id"] == w["id"]]
        assert wait["name"] == "wait" and wait["parent"] == "wave"
    for rec in tracer.to_perfetto()["traceEvents"]:
        assert {"id", "parent_id"} <= set(rec["args"])



def _sample_tracer():
    tracer = obs.install()
    with obs.span("fit.wholebrain", n=64):
        with obs.span("fit.eigh"):
            pass
        obs.instant("registry.hit", model="m0")
    obs.uninstall()
    return tracer


def test_perfetto_export_required_keys():
    doc = _sample_tracer().to_perfetto()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert len(doc["traceEvents"]) == 3
    for rec in doc["traceEvents"]:
        for key in ("name", "cat", "ph", "ts", "pid", "tid", "args"):
            assert key in rec, (key, rec)
        if rec["ph"] == "X":
            assert "dur" in rec and rec["dur"] >= 0
        else:
            assert rec["ph"] == "i" and rec["s"] == "t"
    cats = {r["cat"] for r in doc["traceEvents"]}
    assert cats == {"fit", "registry"}    # dotted prefix becomes category
    json.dumps(doc)                       # serialisable as-is


def test_write_trace_picks_format_by_suffix(tmp_path):
    tracer = _sample_tracer()
    jpath, lpath = str(tmp_path / "t.json"), str(tmp_path / "t.jsonl")
    assert obs.write_trace(tracer, jpath) == "perfetto"
    assert obs.write_trace(tracer, lpath) == "jsonl"
    assert "traceEvents" in json.load(open(jpath))
    lines = [json.loads(ln) for ln in open(lpath)]
    assert [e["name"] for e in lines] \
        == [e["name"] for e in tracer.events()]


def test_obs_report_coverage_and_render(tmp_path):
    tracer = obs.install()
    with obs.span("root"):
        with obs.span("a"):
            time.sleep(0.02)
        with obs.span("b"):
            time.sleep(0.02)
    obs.uninstall()
    path = str(tmp_path / "trace.jsonl")
    tracer.write_jsonl(path)

    events = obs_report.load_events(path)
    root, cov = obs_report.root_coverage(events)
    assert root["name"] == "root"
    assert cov > 0.9                      # sleeps dominate the root
    out = obs_report.render(events)
    assert "root" in out and "%wall" in out and "self_s" in out


def test_obs_report_self_time_by_span_id():
    events = [
        {"name": "flush", "id": 0, "parent_id": None, "ts_us": 0.0,
         "dur_us": 100.0, "depth": 0},
        {"name": "wave", "id": 1, "parent_id": 0, "ts_us": 0.0,
         "dur_us": 30.0, "depth": 1},
        {"name": "wave", "id": 2, "parent_id": 0, "ts_us": 40.0,
         "dur_us": 50.0, "depth": 1},
        {"name": "wait", "id": 3, "parent_id": 2, "ts_us": 50.0,
         "dur_us": 20.0, "depth": 2},
        {"name": "mark", "id": 4, "parent_id": 2, "ts_us": 60.0,
         "dur_us": 0.0, "depth": 2, "instant": True},
    ]
    agg = obs_report.summarize(events)
    assert agg["flush"]["self_us"] == 20.0
    assert agg["wave"]["self_us"] == 60.0
    assert agg["wait"]["self_us"] == 20.0
    root, cov = obs_report.root_coverage(events)
    assert root["id"] == 0 and cov == pytest.approx(0.8)
    # A trace without ids: no child time attributed to self time.
    bare = [{k: v for k, v in e.items() if k not in ("id", "parent_id")}
            for e in events]
    assert obs_report.summarize(bare)["flush"]["self_us"] == 100.0


def test_parse_sweep_log_accepts_obs_traces(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "parse_sweep_log",
        os.path.join(REPO, "benchmarks", "parse_sweep_log.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    path = str(tmp_path / "trace.jsonl")
    _sample_tracer().write_jsonl(path)
    recs = mod.parse(path)                # sniffed as an obs trace
    assert len(recs) == 3
    kinds = {r["kind"] for r in recs}
    assert kinds == {"span", "instant"}
    assert any(r.get("model") == "m0" for r in recs)   # attrs flattened

    # legacy sweep logs still parse through the same entry point
    legacy = tmp_path / "sweep.log"
    legacy.write_text(
        "== archA × 4x8 × 1x1 (rules=on) ==\n"
        "memory_analysis: temp_size_in_bytes=10 argument_size_in_bytes=4\n"
        "cost_analysis: flops=100.0 bytes=200.0\n")
    (rec,) = mod.parse(str(legacy))
    assert rec["arch"] == "archA" and rec["flops"] == 100.0


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_snapshot_json_roundtrip(tmp_path):
    reg = obs.MetricsRegistry()
    reg.counter("compiles", tier="foldstats.chunk_update").inc()
    reg.counter("compiles", tier="foldstats.chunk_update").inc(2)
    reg.counter("bytes_staged").inc(4096)
    reg.gauge("rss_bytes").set(100.0)
    reg.gauge("rss_bytes").set(50.0)      # peak stays at the high-water
    for v in (1.0, 3.0, 2.0):
        reg.histogram("flush_ms").observe(v)

    snap = reg.snapshot()
    assert snap["schema"] == obs.SCHEMA_VERSION
    assert snap["counters"]["compiles{tier=foldstats.chunk_update}"] == 3.0
    assert snap["gauges"]["rss_bytes"] == {"value": 50.0, "peak": 100.0}
    hist = snap["histograms"]["flush_ms"]
    assert hist["count"] == 3 and hist["min"] == 1.0 and hist["max"] == 3.0
    assert hist["mean"] == pytest.approx(2.0)

    path = str(tmp_path / "metrics.json")
    reg.write_json(path)
    assert json.load(open(path)) == json.loads(json.dumps(snap))

    reg.reset()
    assert reg.snapshot()["counters"] == {}


def test_same_instrument_same_object():
    reg = obs.MetricsRegistry()
    assert reg.counter("x", a=1, b=2) is reg.counter("x", b=2, a=1)
    assert reg.counter("x") is not reg.counter("y")


# ---------------------------------------------------------------------------
# recompile sentinel
# ---------------------------------------------------------------------------

def test_sentinel_fires_on_shape_polymorphic_jit(monkeypatch):
    import jax
    import jax.numpy as jnp

    ctr = obs.CompileCounter("test.polymorphic")

    @jax.jit
    def f(x):
        ctr.mark()                        # trace-time side effect
        return jnp.sum(x * 2.0)

    monkeypatch.setenv("REPRO_OBS_STRICT", "1")
    with ctr.expect(at_most=1):
        f(jnp.ones((4,)))                 # first shape: allowed
        f(jnp.ones((4,)))                 # cache hit: no mark
        with pytest.raises(obs.RecompileError):
            f(jnp.ones((8,)))             # new shape retraces → raises
    assert ctr.count == 2


def test_sentinel_silent_without_strict(monkeypatch):
    import jax
    import jax.numpy as jnp

    ctr = obs.CompileCounter("test.lenient")

    @jax.jit
    def f(x):
        ctr.mark()
        return x + 1

    monkeypatch.delenv("REPRO_OBS_STRICT", raising=False)
    with ctr.expect(at_most=1):
        f(jnp.ones((4,)))
        f(jnp.ones((8,)))                 # over the window — counted only
    assert ctr.count == 2
    # the shared compiles{tier=...} metric saw both traces
    snap = obs.snapshot()
    assert snap["counters"]["compiles{tier=test.lenient}"] >= 2.0


def test_sentinel_windows_nest(monkeypatch):
    monkeypatch.setenv("REPRO_OBS_STRICT", "1")
    ctr = obs.CompileCounter("test.nested")
    with ctr.expect(at_most=5):
        with ctr.expect(at_most=0):       # inner window shadows outer
            with pytest.raises(obs.RecompileError):
                ctr.mark()
        ctr.mark()                        # outer window allows it again
    assert ctr.count == 2


# ---------------------------------------------------------------------------
# disabled-path overhead
# ---------------------------------------------------------------------------

def test_disabled_tracer_overhead_under_2pct(make_run_store):
    """The spans a chunked fit emits must cost <2% of its wall when no
    tracer is installed.  Measured as: (per-span disabled cost) × (spans
    an instrumented run actually records) vs the fit's own wall time."""
    from repro.encoding import BrainEncoder

    rng = np.random.default_rng(0)
    n, p, t = 4096, 32, 16
    X = rng.normal(size=(n, p)).astype(np.float32)
    Y = rng.normal(size=(n, t)).astype(np.float32)
    store = make_run_store(X, Y, n_runs=4)

    def fit():
        return BrainEncoder(n_folds=5, device_memory_budget=1,
                            chunk_rows=512).fit(store=store)

    fit()                                 # warm: compiles cached
    assert obs.current() is None
    t0 = time.perf_counter()
    fit()
    fit_wall = time.perf_counter() - t0

    tracer = obs.install()
    fit()
    obs.uninstall()
    n_spans = len(tracer.events())
    assert n_spans > 0                    # the fit path IS instrumented

    reps = 200                            # amortise timer noise
    t0 = time.perf_counter()
    for _ in range(reps * n_spans):
        with obs.span("fit.stats", bytes=1024):
            pass
    disabled_cost = (time.perf_counter() - t0) / reps
    overhead = disabled_cost / fit_wall
    assert overhead < 0.02, (
        f"disabled spans cost {overhead:.2%} of the chunked fit wall "
        f"({n_spans} spans, {disabled_cost * 1e6:.1f} µs/run vs "
        f"{fit_wall * 1e3:.1f} ms fit)")
