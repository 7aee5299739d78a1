"""Granite-4.0-H forward pass, written plainly, for checking the backbone.

One run (one token sequence) at a time, float32, under
``jax.default_matmul_precision("highest")``, from the program's parameter
tree (``HybridLM.param_defs``).  Following
``transformers.models.granitemoehybrid`` (``GraniteMoeHybridDecoderLayer``,
``GraniteMoeHybridMambaLayer.torch_forward``, ``GraniteMoeHybridTopKGating``,
``GraniteMoeHybridRMSNormGated``):

* ``h = 12·embed(tokens)``; per layer ``h += 0.22·mixer(rmsnorm(h))``, then
  ``u = rmsnorm(h)``, ``h += 0.22·(moe(u) + shared_mlp(u))``;
* Mamba-2: causal depthwise conv with bias and SiLU over [x, B, C];
  ``Δ = softplus(dt + dt_bias)``, ``A = −exp(A_log)``; the recurrence
  ``s ← exp(Δ·A)·s + Δ·B⊗x``, ``y = C·s + D·x`` token by token; the gated
  RMSNorm of ``y·silu(z)`` over each group's ``d_inner / n_groups`` values;
* attention: no position encoding, scores scaled by 1/128, materialised
  causal softmax;
* MoE: top-10 of the 72 logits, softmax over those ten; every held expert
  runs over every token and is weighted by a gate that is zero where the
  router did not choose it.

Departures from the Hugging Face module: the feature is the residual
stream after the last layer held here, with no final norm; the experts
held elsewhere (and the layers of later pipeline stages) are absent, as in
the program.  ``act_dtype`` rounds the input of every weight matmul but the
router's to the activations' dtype (bf16 in the program) and leaves all
else f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _act(x, act_dtype):
    return x if act_dtype is None else x.astype(act_dtype).astype(jnp.float32)


def _mm(x, w, act_dtype):
    """``x @ w`` of an activation and a weight, in f32."""
    return jnp.matmul(_act(x, act_dtype), w.astype(jnp.float32))


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def mamba(p, cfg, u, act_dtype=None):
    """One Mamba-2 mixer over one run.  u: (n, d) f32 → (n, d)."""
    s = cfg.ssm
    n, d = u.shape
    H, P, G, N = s.expand * d // s.head_dim, s.head_dim, s.n_groups, s.d_state
    wx = p["wx"].reshape(d, H * P)
    x = _mm(u, wx, act_dtype)
    Bm = _mm(u, p["wB"].reshape(d, G * N), act_dtype)
    Cm = _mm(u, p["wC"].reshape(d, G * N), act_dtype)
    z = _mm(u, p["wz"].reshape(d, H * P), act_dtype)
    dt = _mm(u, p["wdt"], act_dtype)
    xbc = jnp.concatenate([x, Bm, Cm], axis=1)
    w = p["conv_w"].astype(jnp.float32)                  # (K, C)
    K = w.shape[0]
    conv = sum(jnp.pad(xbc, ((K - 1 - i, 0), (0, 0)))[:n] * w[i]
               for i in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))
    x = xbc[:, :H * P].reshape(n, H, P)
    Bm = jnp.repeat(xbc[:, H * P:H * P + G * N].reshape(n, G, N), H // G, 1)
    Cm = jnp.repeat(xbc[:, H * P + G * N:].reshape(n, G, N), H // G, 1)
    dt = jax.nn.softplus(dt + p["dt_bias"])              # (n, H)
    A = -jnp.exp(p["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + dt_t[:, None, None] * b_t[:, :, None] * x_t[:, None, :])
        return state, jnp.einsum("hn,hnp->hp", c_t, state)

    _, y = jax.lax.scan(step, jnp.zeros((H, N, P), jnp.float32),
                        (x, Bm, Cm, dt))
    y = y + p["D"][None, :, None] * x
    g = (y * jax.nn.silu(z.reshape(n, H, P))).reshape(n, G, -1)
    g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.norm_eps)
    y = g.reshape(n, H, P) * p["norm"]
    return _mm(y.reshape(n, H * P), p["wo"].reshape(H * P, d), act_dtype)


def attention(p, cfg, u, act_dtype=None):
    """Causal NoPE GQA over one run.  u: (n, d) → (n, d)."""
    n, d = u.shape
    Hq, Hk, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = _act(_mm(u, p["wq"].reshape(d, Hq * D), act_dtype), act_dtype)
    k = _act(_mm(u, p["wk"].reshape(d, Hk * D), act_dtype), act_dtype)
    v = _act(_mm(u, p["wv"].reshape(d, Hk * D), act_dtype), act_dtype)
    q = q.reshape(n, Hq, D)
    k = jnp.repeat(k.reshape(n, Hk, D), Hq // Hk, axis=1)
    v = jnp.repeat(v.reshape(n, Hk, D), Hq // Hk, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * cfg.attention_multiplier
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return _mm(o.reshape(n, Hq * D), p["wo"].reshape(Hq * D, d), act_dtype)


def gates(cfg, logits):
    """(n, n_experts) router gates: softmax over each token's top-k
    logits, zero elsewhere."""
    k = cfg.moe.top_k
    kth = jnp.sort(logits, axis=-1)[:, -k][:, None]
    chosen = logits >= kth
    e = jnp.where(chosen, jnp.exp(logits - logits.max(-1, keepdims=True)), 0)
    return e / e.sum(-1, keepdims=True)


def expert(wi, wo, u, act_dtype=None):
    """One SwiGLU expert: ``W_o(silu(a) ⊙ b)`` with ``[a | b] = u W_i``."""
    d = u.shape[1]
    h = _mm(u, wi.reshape(d, -1), act_dtype)
    f = h.shape[1] // 2
    return _mm(jax.nn.silu(h[:, :f]) * h[:, f:], wo, act_dtype)


def moe_routed(p, cfg, u, act_dtype=None):
    """The held experts' part of the MoE, dense: every held expert over
    every token, times its gate."""
    g = gates(cfg, _mm(u, p["router"], None))
    out = jnp.zeros_like(u)
    for j, e in enumerate(cfg.moe.held_experts):
        out = out + g[:, e:e + 1] * expert(p["wi"][j], p["wo"][j], u,
                                           act_dtype)
    return out


def layer(blk, cfg, kind, h, act_dtype=None):
    """One decoder layer: mixer, then MoE plus shared expert."""
    u = _rmsnorm(h, blk["norm"]["scale"], cfg.norm_eps)
    y = (mamba(blk["mixer"], cfg, u, act_dtype) if kind == "mamba"
         else attention(blk["attn"], cfg, u, act_dtype))
    h = h + cfg.residual_multiplier * y
    u = _rmsnorm(h, blk["ffn_norm"]["scale"], cfg.norm_eps)
    f = (moe_routed(blk["moe"], cfg, u, act_dtype)
         + expert(blk["shared"]["wi"], blk["shared"]["wo"], u, act_dtype))
    return h + cfg.residual_multiplier * f


def features(params, cfg, tokens, act_dtype=None) -> np.ndarray:
    """The residual stream after the last layer for one run: (n, d) f32."""
    with jax.default_matmul_precision("highest"):
        h = (jnp.take(params["embed"]["tok"], jnp.asarray(tokens), axis=0)
             .astype(jnp.float32) * cfg.embedding_multiplier)
        for r in range(cfg.n_repeats):
            for i, kind in enumerate(cfg.pattern):
                blk = jax.tree_util.tree_map(lambda a: a[r],
                                             params["blocks"][f"b{i}"])
                h = layer(blk, cfg, kind, h, act_dtype)
        return np.asarray(h)


def delays(F: np.ndarray, runs: list[int], lags: int) -> np.ndarray:
    """Per-TR features (n, d) of consecutive runs of ``runs`` TRs each →
    (n, lags·d): TR t carries [F[t−1], …, F[t−lags]] of its own run, zero
    where that lies before the run's start."""
    out = np.zeros((F.shape[0], lags * F.shape[1]), F.dtype)
    lo = 0
    for n in runs:
        for j in range(1, lags + 1):
            out[lo + j:lo + n, (j - 1) * F.shape[1]:j * F.shape[1]] = \
                F[lo:lo + n - j]
        lo += n
    return out
