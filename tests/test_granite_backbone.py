"""granite-4.0-h-small as a feature backbone, at a tiny size on the CPU:
each layer kind and the whole period against the plain reference
(``repro.models.reference.granite``), the gated norm's group, dropless
routing over the held experts, the held shares adding up to the uncut
layer, the delay embedding across chunk and run boundaries, and the
streamed fit from the backbone source against the in-memory fit."""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.encoding import BrainEncoder, EncoderConfig
from repro.encoding import backbone as bb
from repro.models import build_model, hybrid, layers, moe, ssm
from repro.models.reference import granite as ref

ARCH = "granite-4.0-h-small"
PUBLISHED = os.path.join(os.path.dirname(__file__), os.pardir, "bench", "configs",
                         "granite_parcels.json")


def _cfg(**kw):
    """10 layers of the published pattern, d 64, 8 experts, top-2, 4 held;
    f32 weights so the program and the reference agree to f32 rounding."""
    kw.setdefault("param_dtype", jnp.float32)
    return dataclasses.replace(configs.tiny_backbone(configs.get_config(ARCH)),
                               **kw)


def _params(cfg, seed=0):
    """Seeded weights, with Mamba-2's decays spread over the heads."""
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    for i, kind in enumerate(cfg.pattern):
        if kind == "mamba":
            mx = params["blocks"][f"b{i}"]["mixer"]
            H = mx["A_log"].shape[-1]
            mx["A_log"] = jnp.log(jnp.linspace(1.0, 16.0, H))[None]
            mx["dt_bias"] = jnp.linspace(-5.0, -1.0, H)[None]
    return params


def _block(params, i):
    return jax.tree_util.tree_map(lambda a: a[0], params["blocks"][f"b{i}"])


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.linalg.norm(a - b) <= tol * np.linalg.norm(b), (
        np.linalg.norm(a - b) / np.linalg.norm(b))


# -- the configuration -----------------------------------------------------------

def test_config_keeps_every_published_width():
    c = configs.get_config(ARCH)
    with open(PUBLISHED) as f:
        row = json.load(f)
    assert row["catalog_name"] == ARCH
    pub = {**row, **row["published"]}        # the uncut numbers of config.json
    assert c.d_model == pub["hidden_size"]
    assert (c.n_heads, c.n_kv_heads) == (pub["num_attention_heads"],
                                         pub["num_key_value_heads"])
    assert c.moe.n_experts == pub["num_local_experts"]
    assert c.moe.top_k == pub["num_experts_per_tok"]
    assert c.moe.d_expert == pub["intermediate_size"]
    assert c.d_ff == pub["shared_intermediate_size"]
    assert c.pattern == tuple(pub["layer_types"][:10])
    assert c.attention_multiplier == pub["attention_multiplier"]
    assert c.residual_multiplier == pub["residual_multiplier"]
    assert c.embedding_multiplier == pub["embedding_multiplier"]
    assert c.head_dim == 128
    s = c.ssm
    assert (s.head_dim, s.d_state, s.n_groups, s.conv_kernel, s.expand,
            s.chunk, s.norm) == (64, 128, 1, 4, 2, 256, "group")
    mod = __import__("repro.configs.granite_4_0_h_small", fromlist=["x"])
    assert mod.REDUCED == {"layers": (10, 40), "experts": (9, 72),
                           "vocab": (12_544, 100_352)}
    assert c.n_layers == 10 and len(c.moe.held_experts) == 9
    assert c.vocab * 8 == 100_352
    assert "8 chips" in mod.DEPLOYMENT and "4 pipeline stages" in mod.DEPLOYMENT
    assert ARCH in configs.BACKBONE_IDS and ARCH not in configs.ARCH_IDS


# -- each layer kind and the whole period ------------------------------------------

def _packed(cfg, lengths, seed=1):
    """Runs packed into one row, each starting on an SSD chunk."""
    Q = cfg.ssm.chunk
    rng = np.random.default_rng(seed)
    runs = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lengths]
    spans = [-(-n // Q) * Q for n in lengths]
    S = sum(spans)
    tok = np.zeros(S, np.int32)
    pos = np.zeros(S, np.int32)
    starts = np.cumsum([0] + spans[:-1])
    for r, o, L in zip(runs, starts, spans):
        tok[o:o + len(r)] = r
        pos[o:o + L] = np.arange(L)
    return runs, starts, tok[None], pos[None], max(spans) // Q


def test_mamba_layer_matches_the_recurrence():
    cfg = _cfg()
    params = _params(cfg)
    p = _block(params, 0)["mixer"]
    rng = np.random.default_rng(2)
    lengths = [21, 8, 13]
    _, starts, _, pos, _ = _packed(cfg, lengths)
    u = rng.standard_normal((1, pos.shape[1], cfg.d_model)).astype(np.float32)
    y = np.asarray(ssm.mamba_runs(p, cfg, jnp.asarray(u), jnp.asarray(pos)))
    with jax.default_matmul_precision("highest"):
        for o, n in zip(starts, lengths):
            _close(y[0, o:o + n], ref.mamba(p, cfg, jnp.asarray(u[0, o:o + n])),
                   2e-5)


def test_attention_layer_matches_materialised_softmax():
    cfg = _cfg()
    params = _params(cfg)
    p = _block(params, 5)["attn"]
    lengths = [19, 30, 5]
    _, starts, _, pos, span = _packed(cfg, lengths)
    u = np.random.default_rng(3).standard_normal(
        (1, pos.shape[1], cfg.d_model)).astype(np.float32)
    y = np.asarray(layers.run_attention(p, cfg, jnp.asarray(u),
                                        jnp.asarray(pos), cfg.ssm.chunk, span))
    with jax.default_matmul_precision("highest"):
        for o, n in zip(starts, lengths):
            _close(y[0, o:o + n],
                   ref.attention(p, cfg, jnp.asarray(u[0, o:o + n])), 2e-5)


def test_whole_period_matches_the_reference():
    cfg = _cfg()
    params = _params(cfg)
    runs, starts, tok, pos, span = _packed(cfg, [37, 16, 29])
    h, counts = build_model(cfg).features(params, jnp.asarray(tok),
                                          jnp.asarray(pos), span)
    assert counts.shape == (1, 10, 4)
    for r, o in zip(runs, starts):
        _close(np.asarray(h)[0, o:o + len(r)], ref.features(params, cfg, r),
               1e-5)


def test_every_layer_reads_each_layer_input_and_the_routers_picks():
    """``features(every_layer=True)``: the same last layer; each layer's
    input is the previous layer of the reference applied to the one
    before; the picks are the router's top-k of that layer's input."""
    cfg = _cfg()
    params = _params(cfg)
    runs, starts, tok, pos, span = _packed(cfg, [37, 16])
    model = build_model(cfg)
    h0, _ = model.features(params, jnp.asarray(tok), jnp.asarray(pos), span)
    h, counts, ins, picks = model.features(
        params, jnp.asarray(tok), jnp.asarray(pos), span, every_layer=True)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h0))
    n = len(cfg.pattern)
    assert ins.shape == (n,) + h.shape
    assert picks.shape == (n,) + h.shape[:-1] + (cfg.moe.top_k,)
    emb = params["embed"]["tok"][jnp.asarray(tok)] * cfg.embedding_multiplier
    np.testing.assert_array_equal(np.asarray(ins[0]), np.asarray(emb))
    outs = list(ins[1:]) + [h]
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(cfg.pattern):
            blk = _block(params, i)
            for r, o in zip(runs, starts):
                x = ins[i][0, o:o + len(r)]
                _close(outs[i][0, o:o + len(r)],
                       ref.layer(blk, cfg, kind, x), 1e-5)
                hm = x + cfg.residual_multiplier * (
                    ref.mamba(blk["mixer"], cfg, layers.rmsnorm(
                        blk["norm"], x, cfg.norm_eps)) if kind == "mamba"
                    else ref.attention(blk["attn"], cfg, layers.rmsnorm(
                        blk["norm"], x, cfg.norm_eps)))
                logits = layers.rmsnorm(blk["ffn_norm"], hm,
                                        cfg.norm_eps) @ blk["moe"]["router"]
                want = np.sort(np.asarray(
                    jax.lax.top_k(logits, cfg.moe.top_k)[1]), -1)
                got = np.sort(np.asarray(picks[i][0, o:o + len(r)]), -1)
                assert (got == want).mean() > 0.99


def test_whole_period_in_bf16_matches_the_reference_at_its_precision():
    cfg = _cfg(param_dtype=jnp.bfloat16)
    params = build_model(cfg).init(jax.random.PRNGKey(4))
    runs, starts, tok, pos, span = _packed(cfg, [40, 24])
    h, _ = build_model(cfg).features(params, jnp.asarray(tok),
                                     jnp.asarray(pos), span)
    for r, o in zip(runs, starts):
        want = ref.features(params, cfg, r, act_dtype=jnp.bfloat16)
        _close(np.asarray(h)[0, o:o + len(r)], want, 5e-3)
        # The pure f32 reference is further away: the activations' bf16
        # rounding is the configuration's, not noise.
        far = ref.features(params, cfg, r)
        assert (np.linalg.norm(np.asarray(h)[0, o:o + len(r)] - far)
                > np.linalg.norm(np.asarray(h)[0, o:o + len(r)] - want))


@pytest.mark.parametrize("groups", [1, 2])
def test_gated_norm_is_over_the_group(groups):
    cfg = _cfg()
    cfg = dataclasses.replace(cfg, ssm=dataclasses.replace(cfg.ssm,
                                                           n_groups=groups))
    H, P = cfg.ssm.expand * cfg.d_model // cfg.ssm.head_dim, cfg.ssm.head_dim
    rng = np.random.default_rng(5)
    y = rng.standard_normal((2, 3, H, P)).astype(np.float32)
    z = rng.standard_normal((2, 3, H, P)).astype(np.float32)
    p = {"norm": jnp.ones((H, P), jnp.float32)}
    got = np.asarray(ssm._gated_norm(p, jnp.asarray(y), jnp.asarray(z), cfg))
    g = (y * z / (1 + np.exp(-z))).reshape(2, 3, groups, -1)
    want = g / np.sqrt(np.mean(g * g, -1, keepdims=True) + cfg.norm_eps)
    np.testing.assert_allclose(got, want.reshape(y.shape), rtol=2e-5,
                               atol=2e-6)
    assert g.shape[-1] == cfg.ssm.expand * cfg.d_model // groups
    head = dataclasses.replace(cfg, ssm=dataclasses.replace(cfg.ssm,
                                                            norm="head"))
    other = np.asarray(ssm._gated_norm(p, jnp.asarray(y), jnp.asarray(z),
                                       head))
    assert np.abs(other - got).max() > 1e-2


# -- dropless routing over the held experts -------------------------------------------

def _moe_inputs(cfg, T=64, seed=6):
    params = _params(cfg, seed)
    p = _block(params, 0)["moe"]
    u = np.random.default_rng(seed).standard_normal(
        (T, cfg.d_model)).astype(np.float32)
    return p, jnp.asarray(u)


def test_dropless_routing_matches_the_dense_held_experts():
    cfg = _cfg()
    p, u = _moe_inputs(cfg)
    out, counts, _ = moe.moe_dropless(p, cfg, u)
    with jax.default_matmul_precision("highest"):
        want = ref.moe_routed(p, cfg, u)
        g = np.asarray(ref.gates(cfg, u @ p["router"]))
    _close(out, want, 1e-5)
    assert counts.tolist() == (g[:, :4] > 0).sum(0).tolist()


def test_an_expert_that_gets_no_token():
    cfg = _cfg()
    p, u = _moe_inputs(cfg)
    u = u.at[:, 0].set(1.0)
    p = dict(p, router=p["router"].at[:, 2].set(0.0).at[0, 2].set(-1e3))
    out, counts, _ = moe.moe_dropless(p, cfg, u)
    assert int(counts[2]) == 0 and int(counts.sum()) > 0
    with jax.default_matmul_precision("highest"):
        _close(out, ref.moe_routed(p, cfg, u), 1e-5)


def test_every_pair_computed_when_the_fast_buffer_overflows():
    """All tokens pick held experts: more pairs than the fast program's
    buffer holds, and none is dropped."""
    cfg = _cfg()
    p, u = _moe_inputs(cfg, T=1024)
    bias = jnp.where(jnp.arange(8) < 4, 50.0, -50.0)
    p = dict(p, router=p["router"] * 0.01)
    u_b = u.at[:, 0].set(1.0)
    p["router"] = p["router"].at[0].set(bias)
    fast, every = moe.pair_buffers(cfg, 1024)
    out, counts, _ = moe.moe_dropless(p, cfg, u_b)
    assert int(counts.sum()) == every > fast
    with jax.default_matmul_precision("highest"):
        _close(out, ref.moe_routed(p, cfg, u_b), 1e-5)


def test_held_shares_add_up_to_the_uncut_layer():
    """Each chip's share of the experts, with the shared expert counted
    once, adds up to the whole layer (every expert held in one place)."""
    whole = _cfg()
    whole = dataclasses.replace(whole, moe=dataclasses.replace(
        whole.moe, held=tuple(range(8))))
    params = build_model(whole).init(jax.random.PRNGKey(7))
    blk = _block(params, 0)
    u = jnp.asarray(np.random.default_rng(7).standard_normal(
        (96, whole.d_model)).astype(np.float32))
    shared = hybrid._shared_expert(blk["shared"], u[None])[0]
    uncut, _, _ = moe.moe_dropless(blk["moe"], whole, u)
    total = shared
    for held in ((0, 1, 2), (3, 4), (5, 6, 7)):
        cut = dataclasses.replace(whole, moe=dataclasses.replace(
            whole.moe, held=held))
        p = dict(blk["moe"], wi=blk["moe"]["wi"][np.array(held)],
                 wo=blk["moe"]["wo"][np.array(held)])
        part, _, _ = moe.moe_dropless(p, cut, u)
        total = total + part
    _close(total, uncut + shared, 1e-5)
    with jax.default_matmul_precision("highest"):
        _close(uncut, ref.moe_routed(blk["moe"], whole, u), 1e-5)


# -- the delay embedding ---------------------------------------------------------------

def test_delays_across_chunk_and_run_boundaries():
    rng = np.random.default_rng(8)
    lengths, lags, d = [7, 12, 3, 9], 4, 5
    n = sum(lengths)
    F = rng.standard_normal((n, d)).astype(np.float32)
    buf = np.zeros((lags + n + 1, d), np.float32)
    buf[lags:lags + n] = F
    pos = np.concatenate([np.arange(r) for r in lengths]).astype(np.int32)
    want = ref.delays(F, lengths, lags)
    for rows in (5, 8, n):                         # chunks that cut runs
        got = np.concatenate([
            np.asarray(bb.delay_rows(jnp.asarray(buf), lo,
                                     jnp.asarray(pos[lo:lo + rows]), lags,
                                     len(pos[lo:lo + rows])))
            for lo in range(0, n, rows)])
        np.testing.assert_array_equal(got, want)


def test_packing_places_each_tr_at_its_last_token():
    scripts = [bb.Transcript(tokens=np.arange(5, dtype=np.int32),
                             tr_end=np.array([-1, 1, 1, 4], np.int32)),
               bb.Transcript(tokens=np.arange(3, dtype=np.int32),
                             tr_end=np.array([0, 2], np.int32))]
    pk = bb.pack(scripts, chunk=4, rows_per_batch=1, lags=4)
    S = pk.tokens.shape[1]
    assert S == 256 and pk.span == 2
    assert pk.pos[0, :12].tolist() == list(range(8)) + list(range(4))
    assert pk.src[0, :6].tolist() == [-1, 1, 1, 4, 8, 10]
    assert pk.dst[0, :6].tolist() == [4, 5, 6, 7, 8, 9]
    assert pk.n_tokens == 8 and pk.pad_tokens == S - 8


# -- the streamed fit from the backbone source -------------------------------------------

def _subject(tmp_path, cfg, params, lengths=(37, 50, 23, 41), seed=9):
    rng = np.random.default_rng(seed)
    scripts = []
    for n in lengths:
        per = np.where(rng.random(n) < 0.25, 0, 1 + rng.poisson(1.5, n))
        end = np.cumsum(per)
        scripts.append(bb.Transcript(
            tokens=rng.integers(0, cfg.vocab, int(end[-1])).astype(np.int32),
            tr_end=(end - 1).astype(np.int32)))
    F = np.concatenate([np.where((s.tr_end >= 0)[:, None],
                                 ref.features(params, cfg, s.tokens)[
                                     np.maximum(s.tr_end, 0)], 0.0)
                        for s in scripts])
    X = ref.delays(F, list(lengths), 4)
    Y = rng.standard_normal((len(X), 6)).astype(np.float32) + 0.3 * X[:, :6]
    Y = ((Y - Y.mean(0)) / Y.std(0)).astype(np.float32)
    offs = np.cumsum((0,) + tuple(lengths))
    store = bb.write_subject(str(tmp_path / "subject"), scripts,
                             [Y[a:b] for a, b in zip(offs[:-1], offs[1:])],
                             n_folds=5)
    return store, F, X, Y


def test_fit_chunks_from_the_source_equals_the_fit_on_delayed_features(
        tmp_path):
    from repro.data.store import RunStore

    cfg = _cfg()
    params = _params(cfg)
    store, F, X, Y = _subject(tmp_path, cfg, params)
    budget = 1 << 20                      # streams, as on the chip
    src = bb.BackboneSource(bb.Extractor(cfg, params, budget=budget), store)
    got = np.asarray(src.features())[4:4 + len(F)]
    _close(got, F, 1e-5)
    ecfg = EncoderConfig(n_folds=5, chunk_rows=64,
                         device_memory_budget=budget)
    enc = BrainEncoder(ecfg).fit_chunks(src)
    # The same rows as a RunStore of the materialised delayed features,
    # fitted through fit(store=): the same dispatch decision.
    mat = RunStore.create(str(tmp_path / "materialised"), n_folds=5)
    mat.write(X, Y, "all")
    by_store = BrainEncoder(ecfg).fit(store=RunStore.open(mat.root))
    d, want = enc.report_.decision, by_store.report_.decision
    assert d.method == want.method == "chunked"
    assert d.use_pallas == want.use_pallas
    assert dataclasses.replace(d, rationale="") == dataclasses.replace(
        want, rationale="")
    in_memory = BrainEncoder(EncoderConfig(n_folds=5)).fit(
        jnp.asarray(X), jnp.asarray(Y))
    for other in (by_store, in_memory):
        assert other.report_.best_lambda == enc.report_.best_lambda
        _close(enc.weights_, other.weights_, 1e-4)
    assert enc.stream_stats_["chunks"] == -(-len(X) // 64)


def test_weights_leave_the_device_between_fits(tmp_path):
    from repro import obs

    cfg = _cfg()
    params = _params(cfg)
    store, F, _, _ = _subject(tmp_path, cfg, params, lengths=(20, 30))
    assert bb.Extractor(cfg, params).resident
    ex = bb.Extractor(cfg, params, budget=1 << 30)
    assert not ex.resident
    first = np.asarray(bb.BackboneSource(ex, store).features())
    assert ex._params is None
    tracer = obs.install(obs.Tracer())
    try:
        again = np.asarray(bb.BackboneSource(ex, store).features())
    finally:
        obs.uninstall()
    names = [e["name"] for e in tracer.events()]
    assert "extract.weights_h2d" in names and "fit.extract" in names
    assert names.count("extract.batch") == ex.last["batches"]
    np.testing.assert_array_equal(first, again)


def test_extraction_runs_before_the_fold_statistics(tmp_path):
    """The source extracts when the fit opens its stream, so the
    ``fit.stats`` span times the statistics alone."""
    from repro import obs

    cfg = _cfg()
    params = _params(cfg)
    store, _, _, _ = _subject(tmp_path, cfg, params, lengths=(20, 30))
    src = bb.BackboneSource(bb.Extractor(cfg, params, budget=1 << 20), store)
    tracer = obs.install(obs.Tracer())
    try:
        BrainEncoder(EncoderConfig(n_folds=5, chunk_rows=16,
                                   device_memory_budget=1 << 20)
                     ).fit_chunks(src)
    finally:
        obs.uninstall()
    ev = {e["name"]: e for e in tracer.events() if not e.get("instant")}
    extract, stats = ev["fit.extract"], ev["fit.stats"]
    assert extract["ts_us"] + extract["dur_us"] <= stats["ts_us"]


@pytest.mark.timeout(600)
def test_encode_driver_streams_a_backbone(tmp_path):
    """``launch/encode.py --backbone`` goes through the backbone source."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.encode", "--backbone", ARCH,
         "--smoke", "--store", str(tmp_path / "s"), "--n", "300",
         "--targets", "16", "--chunk-rows", "128", "--budget-mb", "0.5"],
        capture_output=True, text=True, env=env, timeout=600, cwd=repo)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "backbone features from granite-4.0-h-small-tiny" in p.stdout
    assert "method=chunked" in p.stdout
