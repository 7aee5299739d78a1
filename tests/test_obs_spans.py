"""Spans and byte counters inside the chunk stream, the per-λ CV and the
serving wave, and the named scopes of the programs they time.

Contracts:

* under a tracer, a streamed fit records ``fit.chunk.h2d`` and
  ``fit.chunk.wait`` per chunk inside its shard span, and ``fit.cv.solve``
  and ``fit.cv.score`` per fold inside ``fit.eigh``; a scored flush
  records ``serve.wave.build/h2d/execute/sums_wait`` and
  ``serve.copyback`` per wave, ``serve.results`` once, all inside its
  ``fleet.flush``;
* each span's ``bytes`` is the hand count of the arrays sent or copied
  back, and the ``h2d_bytes`` / ``d2h_bytes`` counters add the same
  numbers whether or not a tracer is installed;
* with no tracer, the stream, the CV and a flush wait on the device and
  pull to the host exactly as often as they did before these spans;
* the lowered HLO of each program carries its scope in ``op_name``.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import foldstats, ridge, spd


@pytest.fixture(autouse=True)
def _clean_obs_state():
    obs.uninstall()
    yield
    obs.uninstall()


def _counter(name: str) -> float:
    return obs.snapshot()["counters"].get(name, 0.0)


@pytest.fixture
def counted_syncs(monkeypatch):
    """Counts ``jax.block_until_ready`` calls made by the program."""
    calls = {"block": 0}
    real = jax.block_until_ready

    def counting(x):
        calls["block"] += 1
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    return calls


def _by_id(events):
    return {e["id"]: e for e in events}


# -- the chunk stream ---------------------------------------------------------

N, P, T, K, CHUNK = 1200, 24, 10, 5, 256


def _chunks():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(N, P)).astype(np.float32)
    Y = rng.normal(size=(N, T)).astype(np.float32)
    return [(X[i:i + CHUNK], Y[i:i + CHUNK]) for i in range(0, N, CHUNK)]


def _stream(chunks):
    return foldstats.compute_sharded_chunked([iter(chunks)], N, K,
                                             chunk_rows=CHUNK)


def test_chunk_stream_spans_and_bytes():
    chunks = _chunks()
    _stream(chunks)                       # compiled outside the trace
    before = _counter("h2d_bytes{site=chunk}")
    tracer = obs.install()
    _stream(chunks)
    obs.uninstall()

    ev = tracer.events()
    ids = _by_id(ev)
    (shard,) = [e for e in ev if e["name"] == "fit.foldstats.shard"]
    h2d = [e for e in ev if e["name"] == "fit.chunk.h2d"]
    wait = [e for e in ev if e["name"] == "fit.chunk.wait"]
    assert len(h2d) == len(wait) == len(chunks)
    for e in h2d + wait:
        assert e["parent_id"] == shard["id"]
        assert ids[e["parent_id"]]["name"] == "fit.foldstats.shard"
    # Hand count: every chunk is sent padded to CHUNK rows, with a
    # (CHUNK, s_max) f32 slot mask and an (s_max,) i32 fold index.
    s_max = min(K, (CHUNK - 2) // (N // K) + 2)
    per_chunk = CHUNK * (P + T) * 4 + CHUNK * s_max * 4 + s_max * 4
    assert [e["attrs"]["bytes"] for e in h2d] == [per_chunk] * len(chunks)
    assert all(e["attrs"]["rows"] == CHUNK for e in h2d)
    # Only the ragged tail is zero-padded, inside its own span.
    (pad,) = [e for e in ev if e["name"] == "fit.chunk.pad"]
    assert pad["attrs"]["rows"] == N % CHUNK
    assert pad["parent_id"] == shard["id"]
    assert _counter("h2d_bytes{site=chunk}") - before \
        == per_chunk * len(chunks)


def test_chunk_stream_syncs_unchanged_without_tracer(counted_syncs):
    chunks = _chunks()
    _stream(chunks)
    counted_syncs["block"] = 0
    before = _counter("h2d_bytes{site=chunk}")
    _stream(chunks)
    # One wait per chunk, as before the spans; the counter still counts.
    assert counted_syncs["block"] == len(chunks)
    assert _counter("h2d_bytes{site=chunk}") > before
    obs.install()
    counted_syncs["block"] = 0
    _stream(chunks)
    # Traced: each chunk's copy is waited for as well.
    assert counted_syncs["block"] == 2 * len(chunks)


def test_prefetcher_close_is_one_span(make_run_store):
    rng = np.random.default_rng(4)
    store = make_run_store(rng.normal(size=(N, P)).astype(np.float32),
                           rng.normal(size=(N, T)).astype(np.float32))
    stream = store.iter_chunks(CHUNK, prefetch=True)
    tracer = obs.install()
    chunks = sum(1 for _ in stream)       # exhausting the stream closes it
    stream.close()                        # a second close records nothing
    obs.uninstall()
    assert chunks == -(-N // CHUNK)
    closes = [e for e in tracer.events() if e["name"] == "prefetch.close"]
    assert len(closes) == 1


def test_column_block_stream_records_chunk_h2d():
    from repro.wholebrain.stats import ColumnBlockAccumulator

    chunks = _chunks()
    acc = ColumnBlockAccumulator(N, K, t_pad=16, chunk_rows=CHUNK)
    tracer = obs.install()
    for X, Y in chunks:
        acc.update(X, Y)
    obs.uninstall()
    h2d = [e for e in tracer.events() if e["name"] == "fit.chunk.h2d"]
    assert len(h2d) == len(chunks)
    s_max = acc._max_slots()
    # Targets are sent padded to t_pad columns.
    assert h2d[0]["attrs"]["bytes"] == (CHUNK * (P + 16) * 4
                                        + CHUNK * s_max * 4 + s_max * 4)


# -- the per-λ CV -------------------------------------------------------------

@pytest.fixture(scope="module")
def stats():
    return _stream(_chunks())


@pytest.mark.parametrize("per_lambda,method",
                         [(True, "per_lambda_cholesky"), (False, "eigh")])
def test_cv_spans_per_fold(stats, per_lambda, method):
    cfg = ridge.RidgeCVConfig(lambdas=(0.1, 1.0, 10.0))
    ridge._cv_from_stats(stats, cfg, per_lambda=per_lambda)
    tracer = obs.install()
    ridge._cv_from_stats(stats, cfg, per_lambda=per_lambda)
    obs.uninstall()
    ev = tracer.events()
    (outer,) = [e for e in ev if e["name"] == "fit.eigh"]
    assert outer["attrs"]["method"] == method
    if per_lambda:      # the band plan that ran, from the shape
        assert outer["attrs"]["bands"] == len(spd.band_plan(P))
        assert outer["attrs"]["factor_update_flops"] == spd.update_flops(P)
    else:
        assert "bands" not in outer["attrs"]
    for name in ("fit.cv.solve", "fit.cv.score"):
        spans = [e for e in ev if e["name"] == name]
        assert [e["attrs"]["fold"] for e in spans] == list(range(K))
        assert all(e["parent_id"] == outer["id"] for e in spans)
    solve = [e for e in ev if e["name"] == "fit.cv.solve"]
    assert all(e["attrs"]["lambdas"] == 3 for e in solve)
    children = sum(e["dur_us"] for e in ev
                   if e["parent_id"] == outer["id"])
    assert children <= outer["dur_us"] + 1.0


@pytest.mark.parametrize("per_lambda", [True, False])
def test_cv_syncs_unchanged_without_tracer(stats, counted_syncs, per_lambda):
    cfg = ridge.RidgeCVConfig(lambdas=(0.1, 1.0, 10.0))
    ridge._cv_from_stats(stats, cfg, per_lambda=per_lambda)
    counted_syncs["block"] = 0
    ridge._cv_from_stats(stats, cfg, per_lambda=per_lambda)
    assert counted_syncs["block"] == 0
    obs.install()
    ridge._cv_from_stats(stats, cfg, per_lambda=per_lambda)
    # Traced: each fold's solve and score, the CV curve and the refit.
    assert counted_syncs["block"] == 2 * K + 2


def test_named_cv_programs_match_the_plain_calls(stats):
    """The per-λ solve, the refit and the scoring compiled under their
    scopes give bit for bit what the plain calls give."""
    G_tr, C_tr = stats.train(1)
    eye = 1e-6 * jnp.eye(P, dtype=jnp.float32)
    lam = jnp.float32(3.0)
    want = spd.ridge_solve(G_tr + eye, C_tr, lam)
    np.testing.assert_array_equal(ridge._cv_solve(G_tr + eye, C_tr, lam),
                                  want)
    np.testing.assert_array_equal(ridge._refit(G_tr + eye, C_tr, lam), want)
    Ws = jnp.stack([want, 2 * want])
    np.testing.assert_allclose(
        ridge._cv_score(stats, 1, Ws, "r2"),
        jnp.mean(foldstats.validation_scores_from_weights(stats, 1, Ws,
                                                          "r2"), axis=1),
        rtol=1e-6, atol=1e-7)


# -- the serving wave ---------------------------------------------------------

WP, WT, SLOTS = 16, 40, 2


@pytest.fixture(scope="module")
def bundle_path(tmp_path_factory):
    from repro.encoding import BrainEncoder

    rng = np.random.default_rng(5)
    X = rng.normal(size=(200, WP)).astype(np.float32)
    Y = (X @ rng.normal(size=(WP, WT)).astype(np.float32)
         + rng.normal(size=(200, WT)).astype(np.float32))
    path = str(tmp_path_factory.mktemp("bundle") / "b")
    BrainEncoder(n_folds=4).fit(X, Y).save(path)
    return path


def _frontend(path):
    from repro.serving_encoders import EncoderRegistry, EncoderService
    from repro.serving_encoders.fleet import FleetFrontend

    reg = EncoderRegistry(wave_rows=32)
    reg.add("sub-01", path)
    svc = EncoderService(reg, wave_buckets=(8, 32), score_slots=SLOTS)
    return FleetFrontend(svc, max_pending_rows=10_000)


def _requests(scored=(True, True, False, True)):
    from repro.serving_encoders import PredictRequest

    rng = np.random.default_rng(9)
    rows = (20, 13, 7, 30)
    return [PredictRequest(
        "sub-01", rng.normal(size=(r, WP)).astype(np.float32),
        targets=(rng.normal(size=(r, WT)).astype(np.float32) if sc
                 else None))
        for r, sc in zip(rows, scored)]


def _flush(fe, reqs):
    for r in reqs:
        fe.submit(r)
    return fe.flush()


def test_scored_flush_spans_and_bytes(bundle_path):
    fe = _frontend(bundle_path)
    reqs = _requests()
    want = _flush(fe, reqs)               # warm: programs compiled
    h2d0 = _counter("h2d_bytes{site=wave}")
    d2h0 = _counter("d2h_bytes{site=wave}")
    tracer = obs.install()
    got = _flush(fe, reqs)
    obs.uninstall()
    for a, b in zip(want, got):           # tracing changes no answer
        np.testing.assert_array_equal(a.predictions, b.predictions)

    ev = tracer.events()
    (flush,) = [e for e in ev if e["name"] == "fleet.flush"]
    assert flush["attrs"]["flush"] == 1
    waves = {}
    for e in ev:
        if e["name"].startswith("serve."):
            assert e["parent_id"] == flush["id"], e
        if "wave" in e["attrs"]:
            waves.setdefault(e["attrs"]["wave"], []).append(e["name"])
    assert waves and min(waves) > 0      # numbered across flushes
    scored_waves = 0
    for names in waves.values():
        assert names[:3] == ["serve.wave.build", "serve.wave.h2d",
                             "serve.wave.execute"]
        assert names[-1] == "serve.copyback"
        scored_waves += "serve.wave.sums_wait" in names
    assert scored_waves >= 1
    assert [e["name"] for e in ev].count("serve.results") == 1

    h2d = [e for e in ev if e["name"] == "serve.wave.h2d"]
    for e in h2d:
        rows = e["attrs"]["rows"]
        assert e["attrs"]["bytes"] == (rows * (WP + WT + SLOTS) * 4
                                       + SLOTS * 5 * WT * 4)
    assert sum(e["attrs"]["fill"] for e in h2d) == sum(
        r.features.shape[0] for r in reqs)
    back = [e for e in ev if e["name"] == "serve.copyback"]
    assert [e["attrs"]["bytes"] for e in back] == [
        e["attrs"]["rows"] * WT * 4 for e in back]
    assert _counter("h2d_bytes{site=wave}") - h2d0 == sum(
        e["attrs"]["bytes"] for e in h2d)
    assert _counter("d2h_bytes{site=wave}") - d2h0 == sum(
        e["attrs"]["bytes"] for e in back)


class _CountingNumpy:
    """numpy, with ``asarray`` counting its pulls of device arrays."""

    def __init__(self):
        self.pulls = 0

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kwargs):
        if isinstance(a, jax.Array):
            self.pulls += 1
        return np.asarray(a, *args, **kwargs)


def test_flush_syncs_unchanged_without_tracer(bundle_path, counted_syncs,
                                              monkeypatch):
    from repro.serving_encoders import service

    fe = _frontend(bundle_path)
    reqs = _requests()
    _flush(fe, reqs)
    plan = service.plan_mixed_waves(
        [r.features.shape[0] for r in reqs],
        [r.targets is not None for r in reqs],
        lambda rem: fe.service._next_wave(rem, None), SLOTS)
    scored_waves = sum(any(g.slot is not None for g in w.segments)
                       for w in plan)
    assert scored_waves >= 1
    shim = _CountingNumpy()
    monkeypatch.setattr(service, "np", shim)
    counted_syncs["block"] = 0
    _flush(fe, reqs)
    # As before the spans: no wait on the device, and one pull of the
    # sums per scored wave and of the predictions per wave (r is
    # finalised from the host's sums).
    assert counted_syncs["block"] == 0
    assert shim.pulls == len(plan) + scored_waves

    obs.install()
    shim.pulls = 0
    _flush(fe, reqs)
    obs.uninstall()
    assert counted_syncs["block"] == len(plan)  # each wave's copy, traced
    assert shim.pulls == len(plan) + scored_waves


# -- named scopes -------------------------------------------------------------

def _op_names(lowered) -> str:
    return lowered.as_text(debug_info=True)


def test_programs_carry_their_scope_in_op_name(stats, bundle_path):
    from repro.serving_encoders import EncoderRegistry, EncoderService

    s_max = 2
    X = jnp.zeros((CHUNK, P)), jnp.zeros((CHUNK, T))
    onehot = jnp.zeros((CHUNK, s_max))
    slot = jnp.zeros((s_max,), jnp.int32)
    init = foldstats.FoldStatsAccumulator(N, K)._init_stats(P, T)
    G, C = stats.train(0)
    Ws = jnp.zeros((3, P, T))
    reg = EncoderRegistry(wave_rows=32)
    reg.add("sub-01", bundle_path)
    svc = EncoderService(reg, wave_buckets=(8, 32), score_slots=SLOTS)
    e = reg.get("sub-01", wave_rows=32, score_slots=SLOTS)
    enc = (e.weights, e.mu_x, e.sd_x, e.mu_y, e.sd_y)
    wave = (jnp.zeros((8, WP)), jnp.zeros((8, WT)), jnp.zeros((8, SLOTS)),
            jnp.zeros((SLOTS, 5, WT)))
    programs = {
        "chunk_update": foldstats._FIXED_UPDATE._fn.lower(
            init, *X, onehot, slot),
        "cv_solve": ridge._cv_solve.lower(G, C, jnp.float32(1.0)),
        "refit": ridge._refit.lower(G, C, jnp.float32(1.0)),
        "cv_score": ridge._cv_score.lower(stats, 0, Ws, "r2"),
        "wave_matmul": svc._predict.lower(wave[0], *enc),
    }
    for scope, lowered in programs.items():
        assert f"/{scope}/" in _op_names(lowered), scope
    mixed = _op_names(svc._predict_mixed.lower(*wave, *enc))
    assert "/wave_matmul/" in mixed and "/wave_scan/" in mixed
