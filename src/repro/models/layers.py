"""Shared transformer building blocks: norms, RoPE, GQA attention (train +
cached decode), gated MLPs, embeddings.

All blocks are pure functions over ``ParamDef``-described parameter trees
(``repro.models.params``).  Attention supports the variant axes required by
the assigned pool: grouped KV heads (all archs), qk-norm (qwen3), attention
logit softcapping (gemma2), sliding windows (gemma2/3, long_500k overrides),
and ring-buffer KV caches for windowed decode.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.params import ParamDef

Params = Any  # nested dict of arrays


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_defs(d: int) -> dict:
    return {"scale": ParamDef((d,), (None,), init="ones", dtype=jnp.float32)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * p["scale"]).astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings (NeoX interleaving)
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions broadcastable to (..., seq)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos = jnp.cos(angles)[..., None, :]                        # (..., seq, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _softcap(x: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def attention_defs(cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = cfg.param_dtype
    defs = {
        "wq": ParamDef((d, h, hd), ("embed", "heads", None), dtype=dt,
                       fan_in=d),
        "wk": ParamDef((d, kv, hd), ("embed", "kv", None), dtype=dt, fan_in=d),
        "wv": ParamDef((d, kv, hd), ("embed", "kv", None), dtype=dt, fan_in=d),
        "wo": ParamDef((h, hd, d), ("heads", None, "embed"), dtype=dt),
    }
    if cfg.qk_norm:
        defs["q_norm"] = rmsnorm_defs(hd)
        defs["k_norm"] = rmsnorm_defs(hd)
    return defs


@dataclasses.dataclass
class AttnVariant:
    window: int | None = None            # None → global causal
    softcap: float | None = None
    causal: bool = True                  # False for encoder self-attn
    use_rope: bool = True                # False for cross-attention


def _qkv(p: Params, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
         use_rope: bool = True):
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dnk->bsnk", x, p["wk"])
    v = jnp.einsum("bsd,dnk->bsnk", x, p["wv"])
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q * (hd ** -0.5), k, v


def _gqa_scores(q: jax.Array, k: jax.Array, n_kv: int) -> jax.Array:
    """q: (B,S,H,K), k: (B,T,N,K) → (B,N,G,S,T) with H = N·G."""
    b, s, h, hd = q.shape
    g = h // n_kv
    qg = q.reshape(b, s, n_kv, g, hd)
    return jnp.einsum("bsngk,btnk->bngst", qg, k,
                      preferred_element_type=jnp.float32)


def _gqa_out(probs: jax.Array, v: jax.Array) -> jax.Array:
    """probs: (B,N,G,S,T), v: (B,T,N,K) → (B,S,H,K)."""
    b, n, g, s, t = probs.shape
    out = jnp.einsum("bngst,btnk->bsngk", probs.astype(v.dtype), v)
    return out.reshape(b, s, n * g, v.shape[-1])


def _blockwise_attention(cfg: ModelConfig, var: AttnVariant, q: jax.Array,
                         k: jax.Array, v: jax.Array, positions: jax.Array,
                         kv_pos: jax.Array) -> jax.Array:
    """Streaming (flash-style) attention: two-level block scan with a
    running-softmax carry — S×T scores never materialise (§Perf iter 4).

    For sliding-window attention the inner loop is *banded*: only the
    ``window//kb + 1`` KV blocks that can intersect the window are visited
    per Q block, so local-attention FLOPs scale with S·window, not S·T.
    q: (B,S,H,K) pre-scaled; k/v: (B,T,N,K).  → (B,S,H,K).
    """
    B, S, H, K = q.shape
    T, N = k.shape[1], cfg.n_kv_heads
    G = H // N
    bs = cfg.flash_block
    qb, kb = min(bs, S), min(bs, T)
    nq, nk = S // qb, T // kb
    neg = jnp.float32(-1e30)

    q_blocks = q.reshape(B, nq, qb, H, K).transpose(1, 0, 2, 3, 4)
    qpos_blocks = positions.reshape(B, nq, qb).transpose(1, 0, 2)
    k_all = k.reshape(B, nk, kb, N, K)
    v_all = v.reshape(B, nk, kb, N, K)
    kpos_all = kv_pos.reshape(B, nk, kb)

    banded = var.window is not None and var.causal
    n_inner = min(nk, var.window // kb + 2) if banded else nk

    def q_body(_, q_sl):
        q_blk, q_pos, q_idx = q_sl                   # (B,qb,H,K),(B,qb),()
        qg = q_blk.reshape(B, qb, N, G, K)

        def kv_body(carry, j):
            m, l, acc = carry
            raw = (q_idx - (n_inner - 1) + j) if banded else j
            blk = jnp.clip(raw, 0, nk - 1)
            # Out-of-range banded visits are clipped for safe indexing and
            # masked out below (revisiting block 0 must not double-count).
            visit_ok = (raw >= 0) & (raw <= nk - 1)
            k_blk = jax.lax.dynamic_index_in_dim(k_all, blk, 1, False)
            v_blk = jax.lax.dynamic_index_in_dim(v_all, blk, 1, False)
            k_pos = jax.lax.dynamic_index_in_dim(kpos_all, blk, 1, False)
            s = jnp.einsum("bqngk,btnk->bngqt", qg, k_blk,
                           preferred_element_type=jnp.float32)
            s = _softcap(s, var.softcap)
            dist = q_pos[:, None, None, :, None] - \
                k_pos[:, None, None, None, :]
            mask = jnp.broadcast_to(visit_ok, dist.shape)
            if var.causal:
                mask &= dist >= 0
            if var.window is not None:
                mask &= dist < var.window
            s = jnp.where(mask, s, neg)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            upd = jnp.einsum("bngqt,btnk->bngqk", p,
                             v_blk.astype(jnp.float32))
            acc_new = acc * corr[..., None] + upd
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, N, G, qb), neg)
        l0 = jnp.zeros((B, N, G, qb), jnp.float32)
        a0 = jnp.zeros((B, N, G, qb, K), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0),
                                      jnp.arange(n_inner))
        out = acc / jnp.maximum(l, 1e-30)[..., None]         # (B,N,G,qb,K)
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, qb, H, K)
        return None, out.astype(q.dtype)

    _, out_blocks = jax.lax.scan(
        q_body, None,
        (q_blocks, qpos_blocks, jnp.arange(nq)))
    return out_blocks.transpose(1, 0, 2, 3, 4).reshape(B, S, H, K)


def attention(p: Params, cfg: ModelConfig, var: AttnVariant, x: jax.Array,
              positions: jax.Array, kv_x: jax.Array | None = None,
              kv_positions: jax.Array | None = None) -> jax.Array:
    """Full-sequence attention (training / prefill).

    ``kv_x`` enables cross-attention (keys/values from another sequence).
    Switches to the blockwise streaming path when the sequence exceeds
    ``cfg.flash_threshold`` (None → always dense-materialised scores).
    """
    if kv_x is None:
        q, k, v = _qkv(p, cfg, x, positions, use_rope=var.use_rope)
        kv_pos = positions
    else:
        hd = cfg.resolved_head_dim
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if cfg.qk_norm:
            q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        if var.use_rope:
            q = rope(q, positions, cfg.rope_theta)
        q = q * (hd ** -0.5)
        k = jnp.einsum("bsd,dnk->bsnk", kv_x, p["wk"])
        v = jnp.einsum("bsd,dnk->bsnk", kv_x, p["wv"])
        if cfg.qk_norm:
            k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
        kv_pos = kv_positions if kv_positions is not None else \
            jnp.broadcast_to(jnp.arange(kv_x.shape[1], dtype=jnp.int32)[None],
                             kv_x.shape[:2])
        if var.use_rope:
            k = rope(k, kv_pos, cfg.rope_theta)

    if cfg.flash_threshold is not None and \
            x.shape[1] >= cfg.flash_threshold and \
            x.shape[1] % cfg.flash_block == 0 and \
            k.shape[1] % cfg.flash_block == 0:
        if cfg.flash_kernel:
            from repro.kernels import ops as kernel_ops
            out = kernel_ops.mha_flash(
                q, k, v, cfg.n_kv_heads, causal=var.causal,
                window=var.window, softcap=var.softcap,
                block_q=cfg.flash_block, block_k=cfg.flash_block)
        else:
            out = _blockwise_attention(cfg, var, q, k, v, positions, kv_pos)
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"])

    scores = _gqa_scores(q, k, cfg.n_kv_heads)       # (B,N,G,S,T)
    scores = _softcap(scores, var.softcap)
    dist = positions[:, None, None, :, None] - kv_pos[:, None, None, None, :]
    mask = jnp.ones_like(dist, dtype=bool)
    if var.causal:
        mask &= dist >= 0
    if var.window is not None:
        mask &= dist < var.window
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, v)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# -- cached decode -----------------------------------------------------------

def attn_cache_defs(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    dt = cfg.param_dtype
    return {
        "k": ParamDef((batch, cache_len, kv, hd),
                      ("batch", "cache_seq", "kv", None), dtype=dt,
                      init="zeros"),
        "v": ParamDef((batch, cache_len, kv, hd),
                      ("batch", "cache_seq", "kv", None), dtype=dt,
                      init="zeros"),
    }


def attention_decode(p: Params, cfg: ModelConfig, var: AttnVariant,
                     x: jax.Array, pos: jax.Array, cache: dict
                     ) -> tuple[jax.Array, dict]:
    """One-token decode against a (possibly ring) KV cache.

    x: (B, 1, d); pos: scalar int32 — current absolute position (shared by
    the batch, as in steady-state batched serving); cache["k"/"v"]:
    (B, C, N, K) where C = min(window, max_seq).  Keys are stored
    RoPE-rotated at their absolute write position, so ring wraparound keeps
    relative phases exact.
    """
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    q, k_new, v_new = _qkv(p, cfg, x, positions)
    C = cache["k"].shape[1]
    slot = (pos % C).astype(jnp.int32)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype),
                                     (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype),
                                     (0, slot, 0, 0))
    scores = _gqa_scores(q, k, cfg.n_kv_heads)       # (B,N,G,1,C)
    scores = _softcap(scores, var.softcap)
    # Slot j holds absolute position pos - ((pos - j) mod C); valid iff ≥ 0.
    j = jnp.arange(C, dtype=jnp.int32)
    age = (pos - j) % C                              # distance to current token
    valid = age <= pos
    if var.window is not None:
        valid &= age < var.window
    scores = jnp.where(valid[None, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, v)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"k": k, "v": v}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_defs(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, dff, dt = cfg.d_model, d_ff or cfg.d_ff, cfg.param_dtype
    if cfg.mlp_act in ("swiglu", "geglu"):
        return {
            "wi": ParamDef((d, 2, dff), ("embed", None, "mlp"), dtype=dt,
                           fan_in=d),
            "wo": ParamDef((dff, d), ("mlp", "embed"), dtype=dt),
        }
    return {
        "wi": ParamDef((d, dff), ("embed", "mlp"), dtype=dt),
        "wo": ParamDef((dff, d), ("mlp", "embed"), dtype=dt),
    }


def mlp(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.mlp_act in ("swiglu", "geglu"):
        h = jnp.einsum("bsd,dcf->bscf", x, p["wi"])
        gate, up = h[..., 0, :], h[..., 1, :]
        act = jax.nn.silu(gate) if cfg.mlp_act == "swiglu" else \
            jax.nn.gelu(gate, approximate=True)
        h = act * up
    else:
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["wi"]),
                        approximate=True)
    return jnp.einsum("bsf,fd->bsd", h, p["wo"])


# ---------------------------------------------------------------------------
# Embeddings / head
# ---------------------------------------------------------------------------

def embed_defs(cfg: ModelConfig) -> dict:
    # std 0.02: keeps tied-unembedding logits O(1) at init (GPT-2 convention).
    defs = {"tok": ParamDef((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                            dtype=cfg.param_dtype, scale=0.02)}
    if not cfg.tie_embeddings:
        defs["out"] = ParamDef((cfg.d_model, cfg.vocab), ("embed", "vocab"),
                               dtype=cfg.param_dtype)
    return defs


def embed(p: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0)
    if cfg.scale_embedding:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def unembed(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, p["tok"],
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, p["out"],
                            preferred_element_type=jnp.float32)
    return _softcap(logits, cfg.final_logit_softcap)


# ---------------------------------------------------------------------------
# Attention over packed runs (the feature-extractor path)
# ---------------------------------------------------------------------------

HIGHEST = jax.lax.Precision.HIGHEST


def run_attention(p: Params, cfg: ModelConfig, x: jax.Array, pos: jax.Array,
                  block: int, span: int) -> jax.Array:
    """Causal GQA within each run of a packed sequence, with no position
    encoding (Granite-4.0-H's NoPE attention).

    x: (B, S, d) activations; pos: (B, S) position within the run.  Runs
    are contiguous and start on ``block`` boundaries, and none is longer
    than ``span`` blocks, so query block i attends to keys in blocks
    i − span + 1 … i, masked to its own run and to the past.  Q, K and V
    are cast to the activations' dtype for the scores (f32 accumulation),
    scaled by ``cfg.attention_multiplier``; the softmax and the
    probabilities' product with V are f32 (``HIGHEST``).  → (B, S, d) f32.
    """
    B, S, _ = x.shape
    Hq, Hk, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    G = Hq // Hk
    f32, dt = jnp.float32, x.dtype
    scale = cfg.attention_multiplier
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"], preferred_element_type=f32)
    k = jnp.einsum("bsd,dnk->bsnk", x, p["wk"], preferred_element_type=f32)
    v = jnp.einsum("bsd,dnk->bsnk", x, p["wv"], preferred_element_type=f32)
    q, k = q.astype(dt), k.astype(dt)
    v = v.astype(dt).astype(f32)
    nb, W = S // block, span * block
    lead = ((0, 0), (W - block, 0), (0, 0), (0, 0))
    kp, vp = jnp.pad(k, lead), jnp.pad(v, lead)
    idx = jnp.arange(S, dtype=jnp.int32)
    start = idx[None, :] - pos                                   # run start

    def one_block(i):
        lo = i * block
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, 1)
        qb = qb.reshape(B, block, Hk, G, D)
        kb = jax.lax.dynamic_slice_in_dim(kp, lo, W, 1)          # keys lo-W+b…
        vb = jax.lax.dynamic_slice_in_dim(vp, lo, W, 1)
        s = jnp.einsum("bqngk,btnk->bngqt", qb, kb,
                       preferred_element_type=f32) * scale
        key = lo + block - W + jnp.arange(W, dtype=jnp.int32)     # (W,)
        qi = jax.lax.dynamic_slice_in_dim(idx, lo, block)         # (block,)
        st = jax.lax.dynamic_slice_in_dim(start, lo, block, 1)    # (B, block)
        ok = ((key[None, None, :] <= qi[None, :, None])
              & (key[None, None, :] >= st[:, :, None]))           # (B,q,t)
        s = jnp.where(ok[:, None, None], s, -jnp.inf)
        probs = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bngqt,btnk->bqngk", probs, vb, precision=HIGHEST)
        return o.reshape(B, block, Hq, D)

    with jax.named_scope("attention"):
        out = jax.lax.map(one_block, jnp.arange(nb, dtype=jnp.int32))
    out = out.swapaxes(0, 1).reshape(B, S, Hq, D).astype(dt)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"], preferred_element_type=f32)
