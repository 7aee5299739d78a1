"""CPU tests of the readers of the serving wave's host build: by hand on
a synthetic run record, silent where the program records no such span or
no ``reused`` attribute, and end to end on a tiny traced score run."""
from __future__ import annotations

import pytest

from bench import run, spec
from bench.tests.test_bench import execute, tiny
from bench.tests.test_span_readers import _record, _span

STAGING = ("score.wave_build_ms", "score.staging_reuse_share")


def test_staging_readers_by_hand():
    spans = [_span("serve.wave.build", 0.0, 0.030, rows=8, fill=8,
                   reused=0, zeroed_rows=0),
             _span("serve.wave.build", 1.0, 0.010, rows=8, fill=5,
                   reused=1, zeroed_rows=3),
             _span("serve.wave.build", 2.0, 0.020, rows=8, fill=8,
                   reused=1, zeroed_rows=0),
             _span("serve.wave.build", 3.0, 0.020, rows=8, fill=2,
                   reused=1, zeroed_rows=6),
             _span("serve.wave.h2d", 0.1, 0.004, bytes=1000, fill=8)]
    rec = _record(spans)
    assert spec.reader("score.wave_build_ms")(rec) == pytest.approx(20.0)
    assert spec.reader("score.staging_reuse_share")(rec) == \
        pytest.approx(75.0)


def test_staging_readers_silent_without_their_spans():
    """No wave built: both silent.  Builds without ``reused`` (a program
    that builds every wave in fresh arrays): the build time is read, the
    reuse share is not."""
    for metric in STAGING:
        assert spec.reader(metric)(_record([])) is None
    fresh = _record([_span("serve.wave.build", 0.0, 0.1, rows=8, fill=8)])
    assert spec.reader("score.wave_build_ms")(fresh) == pytest.approx(100.0)
    assert spec.reader("score.staging_reuse_share")(fresh) is None


def test_traced_score_reads_the_staging_metrics(tmp_path, monkeypatch):
    """Warm-up builds a wave of every shape for every subject, so every
    set is allocated before the window and each wave in it reuses one."""
    # A work directory of its own: other test files run cells at once.
    monkeypatch.setattr(run, "WORK_DIR", str(tmp_path))
    c = tiny("wholebrain_q4.score")
    rec = execute(c, trace=True)
    out = run.result(c, rec, True, {"platform": "cpu", "kind": "cpu",
                                    "count": 1})
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert out["correct"] is True and set(STAGING) <= set(m)
    builds = [x for x in rec.spans if x["name"] == "serve.wave.build"]
    assert m["score.wave_build_ms"] == pytest.approx(
        1e3 * sum(x["dur"] for x in builds) / len(builds))
    assert m["score.staging_reuse_share"] == 100.0
