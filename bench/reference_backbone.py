"""Plain reference of the Granite-4.0-H backbone cell, importing nothing of
the program: its weights and stimulus from the seed, its features, and the
delay embedding.

* ``weights`` — every parameter from the seed, in the parameter tree the
  program reads (``HybridLM.param_defs``): bf16 matrices, f32 norms,
  Mamba-2 ``A_log``/``dt_bias``/``D`` as Mamba-2 initialises them, and a
  router whose f32 values are bf16 numbers.
* ``transcripts`` — per run of ``run_trs`` TRs, tokens per TR 0 with
  probability ``zero_share`` else 1 + Poisson(``poisson_mean``), ids
  uniform over the vocabulary slice.
* ``features`` — the residual stream after the last layer at each TR's
  last token, run by run (runs batched, each padded at its end, which a
  causal model never reads): ``h = 12·embed``; per layer
  ``h += 0.22·mixer(rmsnorm(h))``, ``h += 0.22·(moe(u) + shared(u))`` with
  ``u = rmsnorm(h)``.  The Mamba-2 layer is the sequential recurrence
  ``s ← exp(Δ·A)·s + Δ·B⊗x``, ``y = C·s + D·x``; attention a materialised
  causal softmax scaled by 1/128 with no position encoding; the MoE dense
  over the held experts, each weighted by a gate that is zero where the
  router did not pick it (top-k of all the router's logits, softmaxed over
  those k).
* ``delays`` — NumPy slicing per run: TR t carries TRs t−1 … t−lags of
  its own run, zero before the run's start.
* ``layer_states``, ``layer_errors`` — the chain layer by layer, and each
  layer checked on another chain's own input to it, with that chain's
  router picks (so a pick that a last-bit difference turns over is
  compared as a pick, not as a jump of the stream).

Precision: the configuration's.  Every weight matmul but the router's
takes bf16 inputs (the program's activations) with f32 accumulation —
exact products, so the bf16 operands stand for an f32 product at
``HIGHEST`` of the rounded values; the residual stream, norms, the router
(its f32 input and logits, ``HIGHEST``), softmax, the probabilities'
product with V (``HIGHEST``) and the SSD state are f32.
``control=True`` is one step below: the SSD state, the norms and the
router logits in bf16 as well.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen

BF16, F32 = jnp.bfloat16, jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def dims(config: dict) -> dict:
    """The shapes the reference needs, from the catalog's keys."""
    d = config["hidden_size"]
    return {
        "d": d, "H": config["mamba_n_heads"], "P": config["mamba_d_head"],
        "N": config["mamba_d_state"], "G": config["mamba_n_groups"],
        "K": config["mamba_d_conv"], "Q": config["mamba_chunk_size"],
        "Hq": config["num_attention_heads"],
        "Hk": config["num_key_value_heads"],
        "D": d // config["num_attention_heads"],
        "E": config["router_experts"], "held": config["num_local_experts"],
        "top_k": config["num_experts_per_tok"],
        "f": config["intermediate_size"],
        "fs": config["shared_intermediate_size"],
        "V": config["vocab_size"], "eps": config["rms_norm_eps"],
        "emb": float(config["embedding_multiplier"]),
        "res": float(config["residual_multiplier"]),
        "att": float(config["attention_multiplier"]),
        "layers": tuple(config["layer_types"]),
    }


# -- weights ---------------------------------------------------------------------

@partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(k, *, shape, std, dtype):
    return (std * jax.random.normal(k, shape, F32)).astype(dtype)


@partial(jax.jit, static_argnames=("shape", "lo", "hi"))
def _uniform(k, *, shape, lo, hi):
    return jax.random.uniform(k, shape, F32, lo, hi)


def weights(seed: int, config: dict) -> dict:
    """Every parameter of the held layers and experts, from ``seed``."""
    m = dims(config)
    d, H, P, N, G, K = m["d"], m["H"], m["P"], m["N"], m["G"], m["K"]
    di, conv = H * P, H * P + 2 * G * N
    keys = iter(range(1 << 30))

    def nrm(shape, std, dtype=BF16):
        return _normal(gen.key(seed, 20, next(keys)), shape=(1,) + shape,
                       std=float(std), dtype=dtype)

    def ones(shape):
        return jnp.ones((1,) + shape, F32)

    blocks = {}
    for i, kind in enumerate(m["layers"]):
        blk = {"norm": {"scale": ones((d,))}}
        if kind == "mamba":
            dt0 = jnp.exp(_uniform(gen.key(seed, 21, i), shape=(1, H),
                                   lo=float(np.log(1e-3)),
                                   hi=float(np.log(1e-1))))
            blk["mixer"] = {
                "wz": nrm((d, H, P), d ** -0.5), "wx": nrm((d, H, P), d ** -0.5),
                "wB": nrm((d, G, N), d ** -0.5), "wC": nrm((d, G, N), d ** -0.5),
                "wdt": nrm((d, H), d ** -0.5),
                "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                "A_log": jnp.log(_uniform(gen.key(seed, 22, i), shape=(1, H),
                                          lo=1.0, hi=16.0)),
                "D": ones((H,)),
                "conv_w": nrm((K, conv), K ** -0.5),
                "conv_b": nrm((conv,), 0.1),
                "norm": ones((H, P)),
                "wo": nrm((H, P, d), di ** -0.5)}
        else:
            Hq, Hk, D = m["Hq"], m["Hk"], m["D"]
            blk["attn"] = {"wq": nrm((d, Hq, D), d ** -0.5),
                           "wk": nrm((d, Hk, D), d ** -0.5),
                           "wv": nrm((d, Hk, D), d ** -0.5),
                           "wo": nrm((Hq, D, d), (Hq * D) ** -0.5)}
        blk["ffn_norm"] = {"scale": ones((d,))}
        blk["moe"] = {
            "router": nrm((d, m["E"]), d ** -0.5).astype(F32),
            "wi": nrm((m["held"], d, 2, m["f"]), d ** -0.5),
            "wo": nrm((m["held"], m["f"], d), m["f"] ** -0.5)}
        blk["shared"] = {"wi": nrm((d, 2, m["fs"]), d ** -0.5),
                         "wo": nrm((m["fs"], d), m["fs"] ** -0.5)}
        blocks[f"b{i}"] = blk
    tok = _normal(gen.key(seed, 23), shape=(m["V"], d), std=0.1, dtype=BF16)
    return {"embed": {"tok": tok}, "blocks": blocks,
            "final_norm": {"scale": jnp.ones((d,), F32)}}


# -- stimulus ---------------------------------------------------------------------

def transcripts(seed: int, n: int, traffic: dict, vocab: int):
    """(tokens, tr_end) per run: runs of ``run_trs`` TRs and a last, short
    one; ``tr_end`` is the index of each TR's last token, −1 before the
    run's first."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 24])
    tk = traffic["tokens"]
    out, left = [], n
    while left:
        trs = min(traffic["run_trs"], left)
        per = np.where(rng.random(trs) < tk["zero_share"], 0,
                       1 + rng.poisson(tk["poisson_mean"], trs))
        end = np.cumsum(per)
        out.append((rng.integers(0, vocab, int(end[-1]), dtype=np.int32),
                    (end - 1).astype(np.int32)))
        left -= trs
    return out


# -- the forward pass --------------------------------------------------------------

def _mm(x, w):
    """A weight matmul: bf16 inputs, f32 accumulation."""
    return jnp.matmul(x.astype(BF16), w.astype(BF16),
                      preferred_element_type=F32)


def _rms(x, scale, eps, control):
    if control:
        x = x.astype(BF16)
        v = jnp.mean(x * x, axis=-1, keepdims=True).astype(BF16)
        return (x * jax.lax.rsqrt(v + eps) * scale.astype(BF16)).astype(F32)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _mamba(p, m, u, control):
    """Mamba-2 over runs.  u: (R, L, d) → (R, L, d)."""
    R, L, d = u.shape
    H, P, G, N, K = m["H"], m["P"], m["G"], m["N"], m["K"]
    x = _mm(u, p["wx"].reshape(d, H * P))
    B = _mm(u, p["wB"].reshape(d, G * N))
    C = _mm(u, p["wC"].reshape(d, G * N))
    z = _mm(u, p["wz"].reshape(d, H * P))
    dt = _mm(u, p["wdt"])
    xbc = jnp.concatenate([x, B, C], axis=-1)
    w = p["conv_w"].astype(F32)
    conv = sum(jnp.pad(xbc, ((0, 0), (K - 1 - i, 0), (0, 0)))[:, :L] * w[i]
               for i in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(F32))
    J = H // G
    x = xbc[..., :H * P].reshape(R, L, G, J, P)
    B = xbc[..., H * P:H * P + G * N].reshape(R, L, G, N)
    C = xbc[..., H * P + G * N:].reshape(R, L, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"]).reshape(R, L, G, J)
    A = -jnp.exp(p["A_log"]).reshape(G, J)
    sdt = BF16 if control else F32

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp           # (R,G,J,P) (R,G,N) (R,G,N) (R,G,J)
        s = (jnp.exp(dt_t * A)[..., None, None] * s.astype(F32)
             + dt_t[..., None, None] * b_t[:, :, None, :, None]
             * x_t[..., None, :])
        s = s.astype(sdt)
        return s, jnp.einsum("rgn,rgjnp->rgjp", c_t, s.astype(F32),
                             precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((R, G, J, N, P), sdt),
                        (x.swapaxes(0, 1), B.swapaxes(0, 1),
                         C.swapaxes(0, 1), dt.swapaxes(0, 1)), unroll=8)
    x = x.reshape(R, L, H, P)
    y = y.swapaxes(0, 1).reshape(R, L, H, P)
    y = y + p["D"][:, None] * x                                # (R, L, H, P)
    g = (y * jax.nn.silu(z.reshape(R, L, H, P))).reshape(R, L, G, -1)
    if control:
        g = g.astype(BF16)
        v = jnp.mean(g * g, axis=-1, keepdims=True).astype(BF16)
        g = (g * jax.lax.rsqrt(v + m["eps"])).astype(F32)
    else:
        g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True) + m["eps"])
    y = g.reshape(R, L, H, P) * p["norm"]
    return _mm(y.reshape(R, L, H * P), p["wo"].reshape(H * P, d))


def _attention(p, m, u):
    """Causal attention without position encoding.  u: (R, L, d)."""
    R, L, d = u.shape
    Hq, Hk, D = m["Hq"], m["Hk"], m["D"]
    q = _mm(u, p["wq"].reshape(d, Hq * D)).reshape(R, L, Hq, D)
    k = _mm(u, p["wk"].reshape(d, Hk * D)).reshape(R, L, Hk, D)
    v = _mm(u, p["wv"].reshape(d, Hk * D)).reshape(R, L, Hk, D)
    k = jnp.repeat(k, Hq // Hk, axis=2).astype(BF16)
    v = jnp.repeat(v, Hq // Hk, axis=2).astype(BF16).astype(F32)
    causal = jnp.tril(jnp.ones((L, L), bool))

    def one_run(args):                      # one run at a time
        qr, kr, vr = args
        s = jnp.einsum("qhd,khd->hqk", qr.astype(BF16), kr,
                       preferred_element_type=F32) * m["att"]
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), vr,
                          precision=HIGHEST)

    o = jax.lax.map(one_run, (q, k, v))
    return _mm(o.reshape(R, L, Hq * D), p["wo"].reshape(Hq * D, d))


def _expert(wi, wo, u):
    h = _mm(u, wi.reshape(u.shape[-1], -1))
    f = h.shape[-1] // 2
    return _mm(jax.nn.silu(h[..., :f]) * h[..., f:], wo)


def _moe(p, m, u, control, picks=None):
    """Held experts, dense, plus nothing of the experts held elsewhere.
    The router's own picks are returned; ``picks`` (expert ids, top-k a
    token), where given, are weighted in their place, each by the softmax
    of the router's logits over those ids."""
    logits = jnp.matmul(u, p["router"], precision=HIGHEST)   # (R, L, E)
    if control:
        logits = logits.astype(BF16).astype(F32)
    own = jax.lax.top_k(logits, m["top_k"])[1]
    ids = own if picks is None else picks
    g = jax.nn.softmax(jnp.take_along_axis(logits, ids, axis=-1), axis=-1)
    out = jnp.zeros_like(u)
    for j in range(m["held"]):
        gate = jnp.sum(jnp.where(ids == j, g, 0.0), axis=-1, keepdims=True)
        out = out + gate * _expert(p["wi"][j], p["wo"][j], u)
    return out, own


def _layer(blk, m, kind, h, control, picks=None):
    """One layer: ``h += 0.22·mixer(rmsnorm(h))``, then ``h += 0.22·(moe(u)
    + shared(u))`` with ``u = rmsnorm(h)``.  Returns (h, the router's own
    picks)."""
    u = _rms(h, blk["norm"]["scale"], m["eps"], control)
    y = (_mamba(blk["mixer"], m, u, control) if kind == "mamba"
         else _attention(blk["attn"], m, u))
    h = h + m["res"] * y
    u = _rms(h, blk["ffn_norm"]["scale"], m["eps"], control)
    r, own = _moe(blk["moe"], m, u, control, picks)
    return h + m["res"] * (r + _expert(blk["shared"]["wi"],
                                       blk["shared"]["wo"], u)), own


def _block(params, i):
    return jax.tree_util.tree_map(lambda a: a[0], params["blocks"][f"b{i}"])


def _embed(params, m, tokens):
    return jnp.take(params["embed"]["tok"], tokens, axis=0).astype(F32) * m["emb"]


@partial(jax.jit, static_argnames=("shapes", "control"))
def _forward(params, tokens, tr_end, *, shapes, control):
    """Pooled features of R runs: tokens (R, L), tr_end (R, T) → (R, T, d)."""
    m = dict(shapes)
    h = _embed(params, m, tokens)
    for i, kind in enumerate(m["layers"]):
        h, _ = _layer(_block(params, i), m, kind, h, control)
    pooled = jnp.take_along_axis(h, jnp.maximum(tr_end, 0)[..., None], axis=1)
    return jnp.where((tr_end >= 0)[..., None], pooled, 0.0)


def features(params, config: dict, runs, *, control: bool = False,
             group: int = 16) -> np.ndarray:
    """(n, d) f32 features of every TR, runs in order, ``group`` runs per
    compiled call (each padded at its end to one length)."""
    m = dims(config)
    Q = m["Q"]
    L = max(Q, -(-max(len(t) for t, _ in runs) // Q) * Q)
    T = max(len(e) for _, e in runs)
    shapes = tuple(sorted(m.items()))
    out = []
    for lo in range(0, len(runs), group):
        part = runs[lo:lo + group]
        tok = np.zeros((group, L), np.int32)
        end = np.full((group, T), -1, np.int32)
        for r, (t, e) in enumerate(part):
            tok[r, :len(t)], end[r, :len(e)] = t, e
        with jax.default_matmul_precision("highest"):
            F = np.asarray(_forward(params, tok, end, shapes=shapes,
                                    control=control))
        out += [F[r, :len(e)] for r, (_, e) in enumerate(part)]
    return np.concatenate(out)


# -- layer by layer ----------------------------------------------------------------

@partial(jax.jit, static_argnames=("emb",))
def _embed_jit(tok, tokens, *, emb):
    return jnp.take(tok, tokens, axis=0).astype(F32) * emb


@partial(jax.jit, static_argnames=("shapes", "kind", "control"))
def _layer_jit(blk, h, picks, *, shapes, kind, control):
    return _layer(blk, dict(shapes), kind, h, control, picks)


@jax.jit
def _compare(got, want, real):
    """Over real tokens: (‖got − want‖², ‖want‖², the largest row's
    ‖got − want‖/‖want‖)."""
    e = jnp.sum(jnp.square(got - want), axis=-1)
    w = jnp.sum(jnp.square(want), axis=-1)
    rows = jnp.sqrt(e / jnp.where(w > 0, w, 1.0))
    return (jnp.sum(jnp.where(real, e, 0.0)), jnp.sum(jnp.where(real, w, 0.0)),
            jnp.max(jnp.where(real, rows, 0.0)))


@jax.jit
def _differ(a, b, real):
    """Tokens whose sets of picked experts differ."""
    same = jnp.all(jnp.sort(a, axis=-1) == jnp.sort(b, axis=-1), axis=-1)
    return jnp.sum(real & ~same)


def layer_states(params, config: dict, tokens, *, control: bool = False):
    """The reference chain layer by layer over R runs (tokens (R, L), each
    run from its start): each layer's input residual stream, the last
    layer's output last, and each layer's router picks."""
    m = dims(config)
    shapes = tuple(sorted(m.items()))
    with jax.default_matmul_precision("highest"):
        h = _embed_jit(params["embed"]["tok"], tokens, emb=m["emb"])
        states, picks = [h], []
        for i, kind in enumerate(m["layers"]):
            h, own = _layer_jit(_block(params, i), h, None, shapes=shapes,
                                kind=kind, control=control)
            states.append(h)
            picks.append(own)
    return states, picks


def layer_errors(params, config: dict, tokens, states, picks, n_real
                 ) -> dict:
    """Each layer of the reference, at the configuration's precision, run
    on the checked side's own input to that layer with the checked side's
    router picks, against the checked side's update of the stream.

    tokens (R, L); states: n_layers + 1 arrays (R, L, d), each layer's
    input and the last output; picks: n_layers arrays (R, L, top_k);
    n_real: (R,) tokens of each run.  Returns, over real tokens, per
    layer (the embedding first) the sums ``err2`` and ``ref2`` and the
    largest row ratio ``row``; ``differ``, the (token, layer) pairs whose
    picks are not the reference's own on that input, out of ``pairs``."""
    m = dims(config)
    shapes = tuple(sorted(m.items()))
    L = tokens.shape[1]
    real = jnp.arange(L)[None] < jnp.asarray(n_real)[:, None]
    out = {"err2": [], "ref2": [], "row": [], "differ": 0,
           "pairs": int(np.sum(n_real)) * len(m["layers"])}

    def put(got, want):
        e, w, r = (float(v) for v in _compare(got, want, real))
        out["err2"].append(e)
        out["ref2"].append(w)
        out["row"].append(r)

    with jax.default_matmul_precision("highest"):
        put(states[0], _embed_jit(params["embed"]["tok"], tokens,
                                  emb=m["emb"]))
        for i, kind in enumerate(m["layers"]):
            ref, own = _layer_jit(_block(params, i), states[i], picks[i],
                                  shapes=shapes, kind=kind, control=False)
            put(states[i + 1] - states[i], ref - states[i])
            out["differ"] += int(_differ(picks[i], own, real))
    return out


def delays(F: np.ndarray, run_lengths, lags: int) -> np.ndarray:
    """(n, d) per-TR features → (n, lags·d) delayed, per run."""
    n, d = F.shape
    X = np.zeros((n, lags * d), F.dtype)
    lo = 0
    for r in run_lengths:
        for j in range(1, lags + 1):
            X[lo + j:lo + r, (j - 1) * d:j * d] = F[lo:lo + r - j]
        lo += r
    return X
