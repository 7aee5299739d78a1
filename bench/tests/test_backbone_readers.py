"""CPU tests of the backbone cell's readers and work counts: by hand on a
synthetic run record, silent where the program records no extraction
span or scope (as the parent commit's program does), and the counts of
the published shapes."""
from __future__ import annotations

import pytest

from bench import peaks, spec, work, work_backbone
from bench.harness import RunRecord

CELL = "granite_parcels.extract_fit"
READERS = ("extract.backbone_s", "extract.backbone_device_s",
           "extract.ssd_device_s", "extract.moe_device_s",
           "extract.moe_roofline", "extract_fit.mfu")


def _record(spans=(), fits=(), trace=None):
    c = spec.cell(CELL)
    rec = RunRecord(cell=CELL, kind="x", config=c.config, traffic=c.traffic,
                    limits={}, device_kind="TPU v5 lite")
    rec.spans, rec.fits, rec.trace = list(spans), list(fits), trace
    return rec


def _fits():
    return [{"t0": 0.0, "dur_s": 20.0, "pairs": 3.0e6, "moe_calls": 200,
             "run_tokens": [1900] * 144 + [330]},
            {"t0": 20.0, "dur_s": 20.0, "pairs": 3.0e6, "moe_calls": 200,
             "run_tokens": [1900] * 144 + [330]}]


def test_readers_by_hand():
    spans = [{"name": "fit.extract", "t0": 1.0, "dur": 8.0, "attrs": {}},
             {"name": "fit.extract", "t0": 21.0, "dur": 9.0, "attrs": {}}]
    # The grouped matmuls' events carry their HLO name and no scope path,
    # as a v5e trace shows them.
    scopes = {"jit(_batch)/backbone/while/body/ssd/dot_general": 4.0,
              "ragged-dot-none": 1.0,
              "jit(_batch)/backbone/while/body/moe_routed/sort": 0.5,
              "jit(_batch)/backbone/while/body/moe_routed/scatter-add": 0.25,
              "jit(_batch)/backbone/while/body/shared_mlp/dot_general": 2.0,
              "jit(cv_solve)/cv_solve/dot_general": 9.0}
    trace = {"chips": 1, "scopes": scopes,
             "scope_count": {k: 1 for k in scopes}}
    rec = _record(spans, _fits(), trace)
    rec.window_s = 40.0
    read = {m: spec.reader(m)(rec) for m in READERS}
    assert read["extract.backbone_s"] == pytest.approx(8.5)
    assert read["extract.backbone_device_s"] == pytest.approx(3.875)
    assert read["extract.ssd_device_s"] == pytest.approx(2.0)
    assert read["extract.moe_device_s"] == pytest.approx(0.875)
    c = rec.config
    flops = 6.0e6 * 2 * 3 * 4096 * 768
    nbytes = 400 * 2 * (9 * 3 * 4096 * 768 + 15000 * 2 * 4096)
    least = max(flops / 197e12, nbytes / 819e9)
    assert read["extract.moe_roofline"] == pytest.approx(100 * least / 1.0)
    fit = work.fit_flops(c["n"], c["p"], c["t"], c["k"], len(c["lambdas"]))
    backbone = work_backbone.backbone_flops(c, _fits()[0]["run_tokens"])
    assert read["extract_fit.mfu"] == pytest.approx(
        100 * (fit + backbone) / 20.0 / peaks.peaks("TPU v5 lite")[
            "flops_per_s"])


@pytest.mark.parametrize("metric", READERS)
def test_readers_silent_without_the_extraction(metric):
    """A program with no backbone source (the parent commit's) records no
    extraction span, scope or fit field: every reader leaves its metric
    out instead of raising."""
    fits = [{"t0": 0.0, "dur_s": 10.0}]
    older = [{"name": "fit.stats", "t0": 0.5, "dur": 5.0, "attrs": {}}]
    trace = {"chips": 1, "scopes": {"jit(cv_solve)/cv_solve/add": 1.0},
             "scope_count": {"jit(cv_solve)/cv_solve/add": 1}}
    for rec in (_record(older, fits), _record(older, fits, trace),
                _record([], [])):
        rec.window_s = 10.0
        assert spec.reader(metric)(rec) is None


def test_backbone_work_of_the_published_period():
    """One period at the published widths: about 2.64e9 FLOP a token with
    1.25 held pairs, the Mamba projections the largest part, and the
    backbone well over four fifths of the cell's work."""
    c = spec.cell(CELL).config
    parts = work_backbone.layer_flops(c)
    assert parts["mamba_proj"] == pytest.approx(9 * 204.47e6, rel=1e-3)
    assert parts["shared"] == pytest.approx(10 * 6 * 4096 * 1536)
    assert work_backbone.held_pairs_per_token(c) == pytest.approx(12.5)
    assert work_backbone.pair_flops(c) == 6 * 4096 * 768
    runs = [1910] * 145
    per_token = work_backbone.backbone_flops(c, runs) / sum(runs)
    assert per_token == pytest.approx(2.64e9, rel=0.02)
    fit = work.fit_flops(c["n"], c["p"], c["t"], c["k"], len(c["lambdas"]))
    assert work_backbone.backbone_flops(c, runs) / (
        work_backbone.backbone_flops(c, runs) + fit) > 0.8
    # A call: nine experts' three matrices and each pair's row in and out.
    assert work_backbone.moe_call_bytes(c, 100.0) == 2 * (
        9 * 3 * 4096 * 768 + 100 * 2 * 4096)
