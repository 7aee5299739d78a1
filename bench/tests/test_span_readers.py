"""CPU tests of the readers of the program's chunk, CV and wave spans:
by hand on a synthetic run record, silent where the program records no
such span, and end to end on tiny traced runs of both cells."""
from __future__ import annotations

import pytest

from bench import run, spec
from bench.harness import RunRecord
from bench.tests.test_bench import execute, tiny

FIT = ("fit.chunk_h2d_s", "fit.chunk_wait_s", "fit.cv_solve_s",
       "fit.cv_score_s")
SCORE = ("score.h2d_ms", "score.h2d_bytes_per_row", "score.sums_wait_ms",
         "score.copyback_ms")
SCOPES = ("fit.cv_solve_device_s", "score.wave_scan_device_ms")


def _span(name, t0, dur, **attrs):
    return {"name": name, "t0": t0, "dur": dur, "attrs": attrs}


def _record(spans, fits=()):
    rec = RunRecord(cell="x", kind="x", config={}, traffic={}, limits={},
                    device_kind="TPU v5 lite")
    rec.spans, rec.fits = list(spans), list(fits)
    return rec


def test_fit_span_readers_by_hand():
    fits = [{"t0": 0.0, "dur_s": 10.0}, {"t0": 10.0, "dur_s": 10.0}]
    spans = [_span("fit.chunk.h2d", 1.0, 0.25),
             _span("fit.chunk.h2d", 2.0, 0.25),
             _span("fit.chunk.h2d", 11.0, 0.5),
             _span("fit.chunk.wait", 3.0, 1.0),
             _span("fit.cv.solve", 4.0, 3.0),
             _span("fit.cv.solve", 14.0, 5.0),
             _span("fit.cv.score", 8.0, 0.5),
             _span("fit.cv.score", 19.0, 0.25),
             _span("fit.stats", 0.5, 5.0)]
    rec = _record(spans, fits)
    want = {"fit.chunk_h2d_s": 0.5, "fit.chunk_wait_s": 0.5,
            "fit.cv_solve_s": 4.0, "fit.cv_score_s": 0.375}
    for metric, value in want.items():
        assert spec.reader(metric)(rec) == pytest.approx(value), metric


def test_score_span_readers_by_hand():
    spans = [_span("serve.wave.h2d", 0.0, 0.004, bytes=1000, fill=8),
             _span("serve.wave.h2d", 1.0, 0.002, bytes=600, fill=2),
             _span("serve.wave.sums_wait", 0.5, 0.040),
             _span("serve.copyback", 2.0, 0.010),
             _span("serve.copyback", 3.0, 0.030),
             _span("serve.wave.execute", 0.1, 0.001, rows=8)]
    rec = _record(spans)
    want = {"score.h2d_ms": 3.0, "score.h2d_bytes_per_row": 160.0,
            "score.sums_wait_ms": 40.0, "score.copyback_ms": 20.0}
    for metric, value in want.items():
        assert spec.reader(metric)(rec) == pytest.approx(value), metric


def test_scope_readers_by_hand():
    """Device time under a named scope, per fit and per wave program."""
    rec = _record([], [{"t0": 0.0, "dur_s": 10.0},
                       {"t0": 10.0, "dur_s": 10.0}])
    scopes = {"jit(cv_solve)/cv_solve/while/body/dot_general": 3.0,
              "jit(cv_solve)/cv_solve/triangular_solve": 0.5,
              "jit(refit)/refit/while/body/dot_general": 0.75,
              "jit(_predict_mixed)/wave_scan/while/body/add": 0.012,
              "jit(_predict_mixed)/wave_matmul/dot_general": 0.1}
    rec.trace = {"chips": 1, "scopes": scopes,
                 "scope_count": {k: 1 for k in scopes},
                 "modules": [("jit__predict_mixed(7)", 0.0, 0.05),
                             ("jit__predict_mixed(7)", 1.0, 0.05),
                             ("jit_cv_solve(9)", 2.0, 1.0)]}
    assert spec.reader("fit.cv_solve_device_s")(rec) == pytest.approx(1.75)
    assert spec.reader("score.wave_scan_device_ms")(rec) == pytest.approx(6.0)
    # A trace with no such scope (a program that names none) is silent.
    rec.trace = dict(rec.trace, scopes={}, scope_count={})
    for metric in SCOPES:
        assert spec.reader(metric)(rec) is None


@pytest.mark.parametrize("metric", FIT + SCORE + SCOPES)
def test_span_readers_silent_without_their_spans(metric):
    """A program that records none of the new spans (or an untraced run)
    leaves the metric out instead of raising."""
    fits = [{"t0": 0.0, "dur_s": 10.0}]
    older = [_span("fit.stats", 0.5, 5.0), _span("fit.eigh", 6.0, 3.0),
             _span("serve.wave.build", 0.0, 0.1, rows=8, fill=8),
             _span("serve.wave.execute", 0.1, 0.001, rows=8, fill=8),
             _span("fleet.flush", 0.0, 1.0)]
    assert spec.reader(metric)(_record(older, fits)) is None
    assert spec.reader(metric)(_record([], fits)) is None


@pytest.fixture(scope="module")
def traced_runs(tmp_path_factory):
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        # A work directory of its own: other test files run cells at once.
        mp.setattr(run, "WORK_DIR", str(tmp_path_factory.mktemp("work")))
        for cell in ("parcels.fit", "wholebrain_q4.score"):
            c = tiny(cell)
            rec = execute(c, trace=True)
            out[cell] = (c, rec, run.result(c, rec, True, {
                "platform": "cpu", "kind": "cpu", "count": 1}))
    return out


def test_traced_fit_reads_every_new_metric(traced_runs):
    c, rec, out = traced_runs["parcels.fit"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert out["correct"] is True and set(FIT) <= set(m)
    assert m["fit.cv_solve_s"] + m["fit.cv_score_s"] <= m["fit.cv_s"]
    assert (m["fit.chunk_h2d_s"] + m["fit.chunk_wait_s"]
            + m["fit.stream_stall_s"]) <= m["fit.stats_s"]


def test_traced_score_reads_every_new_metric(traced_runs):
    c, rec, out = traced_runs["wholebrain_q4.score"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert out["correct"] is True and set(SCORE) <= set(m)
    p, t = c.config["p"], c.config["t"]
    s = c.config["serving"]["score_slots"]
    waves = [x["attrs"] for x in rec.spans if x["name"] == "serve.wave.h2d"]
    by_hand = sum(w["rows"] * (p + t + s) * 4 + s * 5 * t * 4
                  for w in waves) / sum(w["fill"] for w in waves)
    assert m["score.h2d_bytes_per_row"] == pytest.approx(by_hand)
    assert m["score.h2d_bytes_per_row"] >= (p + t) * 4
