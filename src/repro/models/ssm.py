"""Mamba2 — state-space duality (SSD) blocks [arXiv:2405.21060].

Training/prefill uses the chunked SSD algorithm: within-chunk terms are
attention-like masked matmuls (MXU-friendly), across-chunk state is a short
``lax.scan`` over ``T/chunk`` steps carrying the (H, N, P) state — this is
the TPU adaptation of the paper's hardware mapping (the CUDA kernel's
block-parallel structure becomes chunk matmuls + a tiny sequential scan).

Decode is the classical single-step recurrence on the carried state:
``h ← exp(ΔA)·h + (ΔB)⊗x``, ``y = C·h + D·x`` — constant memory, which is
why SSM/hybrid archs run ``long_500k`` natively (DESIGN §4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig, SSMConfig
from repro.models.params import ParamDef


def _dims(cfg: ModelConfig) -> tuple[int, int, int, int, int]:
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    heads = d_inner // s.head_dim
    return d_inner, heads, s.head_dim, s.n_groups, s.d_state


def mamba_defs(cfg: ModelConfig) -> dict:
    s: SSMConfig = cfg.ssm
    d, dt = cfg.d_model, cfg.param_dtype
    d_inner, H, Pd, G, N = _dims(cfg)
    conv_ch = d_inner + 2 * G * N          # conv over [x, B, C] channels
    return {
        "wz": ParamDef((d, H, Pd), ("embed", "heads", None), dtype=dt,
                       fan_in=d),
        "wx": ParamDef((d, H, Pd), ("embed", "heads", None), dtype=dt,
                       fan_in=d),
        "wB": ParamDef((d, G, N), ("embed", None, "state"), dtype=dt,
                       fan_in=d),
        "wC": ParamDef((d, G, N), ("embed", None, "state"), dtype=dt,
                       fan_in=d),
        "wdt": ParamDef((d, H), ("embed", "heads"), dtype=dt),
        "dt_bias": ParamDef((H,), ("heads",), dtype=jnp.float32, init="zeros"),
        "A_log": ParamDef((H,), ("heads",), dtype=jnp.float32, init="zeros"),
        "D": ParamDef((H,), ("heads",), dtype=jnp.float32, init="ones"),
        "conv_w": ParamDef((s.conv_kernel, conv_ch), (None, None), dtype=dt,
                           scale=0.5),
        "conv_b": ParamDef((conv_ch,), (None,), dtype=dt, init="zeros"),
        "norm": ParamDef((H, Pd), ("heads", None), dtype=jnp.float32,
                         init="ones"),
        "wo": ParamDef((H, Pd, d), ("heads", None, "embed"), dtype=dt),
    }


def ssm_cache_defs(cfg: ModelConfig, batch: int) -> dict:
    s = cfg.ssm
    d_inner, H, Pd, G, N = _dims(cfg)
    conv_ch = d_inner + 2 * G * N
    return {
        "state": ParamDef((batch, H, N, Pd), ("batch", "heads", None, None),
                          dtype=jnp.float32, init="zeros"),
        "conv": ParamDef((batch, s.conv_kernel - 1, conv_ch),
                         ("batch", None, None), dtype=cfg.param_dtype,
                         init="zeros"),
    }


def _proj_xbc(p, cfg: ModelConfig, u: jax.Array):
    """Project input to x/B/C channels (pre-conv) and z/dt."""
    d_inner, H, Pd, G, N = _dims(cfg)
    x = jnp.einsum("bsd,dhp->bshp", u, p["wx"]).reshape(*u.shape[:2], H * Pd)
    Bm = jnp.einsum("bsd,dgn->bsgn", u, p["wB"]).reshape(*u.shape[:2], G * N)
    Cm = jnp.einsum("bsd,dgn->bsgn", u, p["wC"]).reshape(*u.shape[:2], G * N)
    xbc = jnp.concatenate([x, Bm, Cm], axis=-1)      # (B, S, conv_ch)
    z = jnp.einsum("bsd,dhp->bshp", u, p["wz"])      # (B, S, H, P)
    dt = jnp.einsum("bsd,dh->bsh", u, p["wdt"])      # (B, S, H)
    return xbc, z, dt


def _split_xbc(cfg: ModelConfig, xbc: jax.Array):
    d_inner, H, Pd, G, N = _dims(cfg)
    b, s, _ = xbc.shape
    x = xbc[..., :d_inner].reshape(b, s, H, Pd)
    Bm = xbc[..., d_inner:d_inner + G * N].reshape(b, s, G, N)
    Cm = xbc[..., d_inner + G * N:].reshape(b, s, G, N)
    return x, Bm, Cm


def _causal_conv(p, xbc: jax.Array, kernel: int) -> jax.Array:
    """Depthwise causal conv over time.  xbc: (B, S, C)."""
    pad = jnp.pad(xbc, ((0, 0), (kernel - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * p["conv_w"][i][None, None, :]
              for i in range(kernel))
    return jax.nn.silu(out + p["conv_b"][None, None, :])


def _gated_norm(p, y: jax.Array, z: jax.Array, cfg: ModelConfig
                ) -> jax.Array:
    """Mamba2 gated RMSNorm: norm(y · silu(z)) with per-(head, dim) scale,
    normalised over each head (``ssm.norm == "head"``) or over each
    group's ``d_inner / n_groups`` values (``"group"``).  y: (..., H, P)."""
    g = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)))
    if cfg.ssm.norm == "group":
        G = cfg.ssm.n_groups
        gg = g.reshape(*g.shape[:-2], G, -1)
        var = jnp.mean(gg * gg, axis=-1, keepdims=True)
        normed = (gg * jax.lax.rsqrt(var + cfg.norm_eps)).reshape(g.shape)
    else:
        var = jnp.mean(g * g, axis=-1, keepdims=True)
        normed = g * jax.lax.rsqrt(var + cfg.norm_eps)
    return (normed * p["norm"]).astype(y.dtype)


def mamba_apply(p, cfg: ModelConfig, u: jax.Array,
                return_cache: bool = False):
    """Full-sequence SSD (training / prefill).  u: (B, S, d) → (B, S, d).

    With ``return_cache`` also returns the decode cache {state, conv}: the
    final SSD state is the last carry of the inter-chunk scan (no sequential
    token replay needed — this is the parallel prefill path)."""
    s_cfg = cfg.ssm
    d_inner, H, Pd, G, N = _dims(cfg)
    B_, S, _ = u.shape
    Q = min(s_cfg.chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q

    xbc, z, dt = _proj_xbc(p, cfg, u)
    xbc_raw = xbc
    xbc = _causal_conv(p, xbc, s_cfg.conv_kernel)
    x, Bm, Cm = _split_xbc(cfg, xbc)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,S,H)
    A = -jnp.exp(p["A_log"])                                      # (H,) < 0

    hpg = H // G
    # Chunked views.
    xc = (x.astype(jnp.float32) * dt[..., None]).reshape(B_, nc, Q, H, Pd)
    Bc = Bm.astype(jnp.float32).reshape(B_, nc, Q, G, N)
    Cc = Cm.astype(jnp.float32).reshape(B_, nc, Q, G, N)
    la = (dt * A[None, None, :]).reshape(B_, nc, Q, H)            # log decay
    La = jnp.cumsum(la, axis=2)                                   # within-chunk

    # Within-chunk (attention-like) term with decay mask
    #   L[i,j] = exp(La_i − La_j) · 1[j ≤ i].
    if s_cfg.use_kernel and G == 1:
        # Fused Pallas path: decay·scores·x stays in VMEM (kernels/ssd.py).
        from repro.kernels import ops as kernel_ops
        cb = jnp.einsum("bcqgn,bckgn->bcqk", Cc, Bc)
        y_intra = kernel_ops.ssd_intra(
            cb.reshape(B_ * nc, Q, Q), La.reshape(B_ * nc, Q, H),
            xc.reshape(B_ * nc, Q, H, Pd)).reshape(B_, nc, Q, H, Pd)
    else:
        diff = La[:, :, :, None, :] - La[:, :, None, :, :]        # (B,nc,Q,Q,H)
        mask = jnp.tril(jnp.ones((Q, Q), bool))
        # Mask in log space before exp: diff > 0 above the diagonal would
        # overflow.
        decay = jnp.exp(jnp.where(mask[None, None, :, :, None], diff,
                                  -jnp.inf))
        scores = jnp.einsum("bcqgn,bckgn->bcqkg", Cc, Bc)         # (B,nc,Q,Q,G)
        scores = jnp.repeat(scores, hpg, axis=-1) * decay         # → heads
        y_intra = jnp.einsum("bcqkh,bckhp->bcqhp", scores, xc)

    # Chunk-boundary states and the sequential inter-chunk scan.
    seg = jnp.exp(La[:, :, -1:, :] - La)                          # decay to end
    Bh = jnp.repeat(Bc, hpg, axis=-2)                             # (B,nc,Q,H,N)
    S_local = jnp.einsum("bcqh,bcqhn,bcqhp->bchnp", seg, Bh, xc)
    chunk_decay = jnp.exp(La[:, :, -1, :])                        # (B,nc,H)

    def scan_body(carry, inp):
        s_loc, dec = inp                    # (B,H,N,P), (B,H)
        new = carry * dec[..., None, None] + s_loc
        return new, carry                   # emit state *before* this chunk

    init = jnp.zeros((B_, H, N, Pd), jnp.float32)
    S_final, S_prev = jax.lax.scan(scan_body,
                                   init,
                                   (S_local.swapaxes(0, 1),
                                    chunk_decay.swapaxes(0, 1)))
    S_prev = S_prev.swapaxes(0, 1)                                # (B,nc,H,N,P)

    Ch = jnp.repeat(Cc, hpg, axis=-2)                             # (B,nc,Q,H,N)
    y_inter = jnp.einsum("bcqh,bcqhn,bchnp->bcqhp", jnp.exp(La), Ch, S_prev)

    y = (y_intra + y_inter).reshape(B_, S, H, Pd)
    y = y + p["D"][None, None, :, None] * x.astype(jnp.float32)
    y = _gated_norm(p, y, z, cfg)
    out = jnp.einsum("bshp,hpd->bsd", y.astype(u.dtype), p["wo"])
    if not return_cache:
        return out
    k = s_cfg.conv_kernel
    cache = {"state": S_final,
             "conv": xbc_raw[:, S - (k - 1):, :].astype(cfg.param_dtype)}
    return out, cache


def mamba_decode(p, cfg: ModelConfig, u: jax.Array, cache: dict
                 ) -> tuple[jax.Array, dict]:
    """Single-token recurrent step.  u: (B, 1, d)."""
    s_cfg = cfg.ssm
    d_inner, H, Pd, G, N = _dims(cfg)
    xbc, z, dt = _proj_xbc(p, cfg, u)                 # (B,1,·)
    hist = jnp.concatenate([cache["conv"], xbc.astype(cache["conv"].dtype)],
                           axis=1)                    # (B, K, C)
    conv_out = jnp.einsum("bkc,kc->bc", hist, p["conv_w"]) + p["conv_b"]
    conv_out = jax.nn.silu(conv_out)[:, None, :]
    new_conv = hist[:, 1:, :]

    x, Bm, Cm = _split_xbc(cfg, conv_out)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])[:, 0]   # (B,H)
    A = -jnp.exp(p["A_log"])
    a = jnp.exp(dt * A[None, :])                                        # (B,H)
    hpg = H // G
    Bh = jnp.repeat(Bm[:, 0], hpg, axis=-2)           # (B,H,N)
    Ch = jnp.repeat(Cm[:, 0], hpg, axis=-2)
    xd = x[:, 0].astype(jnp.float32) * dt[..., None]  # (B,H,P)
    state = cache["state"] * a[..., None, None] + \
        jnp.einsum("bhn,bhp->bhnp", Bh.astype(jnp.float32), xd)
    y = jnp.einsum("bhn,bhnp->bhp", Ch.astype(jnp.float32), state)
    y = y + p["D"][None, :, None] * x[:, 0].astype(jnp.float32)
    y = _gated_norm(p, y[:, None], z, cfg)
    out = jnp.einsum("bshp,hpd->bsd", y.astype(u.dtype), p["wo"])
    return out, {"state": state, "conv": new_conv}


# -- packed runs, f32 SSD (the feature-extractor path) -------------------------

HIGHEST = jax.lax.Precision.HIGHEST


def _causal_conv_runs(p, xbc: jax.Array, pos: jax.Array, kernel: int
                      ) -> jax.Array:
    """Depthwise causal conv over time in f32 that never reaches back past
    the start of a run.  xbc: (B, S, C) f32; pos: (B, S) position of each
    token within its run."""
    S = xbc.shape[1]
    pad = jnp.pad(xbc, ((0, 0), (kernel - 1, 0), (0, 0)))
    w = p["conv_w"].astype(jnp.float32)
    out = jnp.zeros_like(xbc)
    for i in range(kernel):
        back = kernel - 1 - i
        tap = pad[:, i:i + S, :] * w[i][None, None, :]
        out = out + jnp.where((pos >= back)[..., None], tap, 0.0)
    return jax.nn.silu(out + p["conv_b"].astype(jnp.float32)[None, None, :])


def ssd_runs(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, reset: jax.Array, chunk: int) -> jax.Array:
    """Chunked SSD in f32, every contraction at ``HIGHEST``, with the state
    zeroed before each chunk that starts a run (runs are packed along the
    sequence, each starting on a chunk boundary).

    x: (B, S, H, P); dt: (B, S, H) post-softplus; A: (H,) < 0;
    Bm, Cm: (B, S, G, N); reset: (B, S // chunk) bool.  → y (B, S, H, P)
    without the ``D·x`` skip.  The within-chunk term is formed one chunk at
    a time, so its (Q, Q, H) decay never exists for more than one chunk.
    """
    B_, S, H, Pd = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q, hpg = chunk, H // G
    nc = S // Q
    xc = (x * dt[..., None]).reshape(B_, nc, Q, H, Pd)
    Bc = Bm.reshape(B_, nc, Q, G, N)
    Cc = Cm.reshape(B_, nc, Q, G, N)
    La = jnp.cumsum((dt * A[None, None, :]).reshape(B_, nc, Q, H), axis=2)
    tril = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]

    def intra(args):
        xq, bq, cq, la = args                 # (B,Q,H,P) (B,Q,G,N) (B,Q,H)
        scores = jnp.einsum("bqgn,bkgn->bqkg", cq, bq, precision=HIGHEST)
        decay = jnp.exp(jnp.where(tril, la[:, :, None, :] - la[:, None, :, :],
                                  -jnp.inf))                      # (B,Q,K,H)
        w = (decay.reshape(B_, Q, Q, G, hpg)
             * scores[..., None]).reshape(B_, Q, Q, H)
        return jnp.einsum("bqkh,bkhp->bqhp", w, xq, precision=HIGHEST)

    y_intra = jax.lax.map(intra, (xc.swapaxes(0, 1), Bc.swapaxes(0, 1),
                                  Cc.swapaxes(0, 1), La.swapaxes(0, 1)))
    y_intra = y_intra.swapaxes(0, 1)                              # (B,nc,Q,H,P)

    # Each chunk's own contribution to the state at its end, and the
    # carried state before each chunk (zero where a run starts).
    seg = jnp.exp(La[:, :, -1:, :] - La)                          # (B,nc,Q,H)
    sx = (seg[..., None] * xc).reshape(B_, nc, Q, G, hpg, Pd)
    S_local = jnp.einsum("bcqgn,bcqgjp->bcgjnp", Bc, sx, precision=HIGHEST)
    chunk_decay = jnp.exp(La[:, :, -1, :]).reshape(B_, nc, G, hpg)

    def scan_body(carry, inp):
        s_loc, dec, rst = inp
        prev = jnp.where(rst[:, None, None, None, None], 0.0, carry)
        return prev * dec[..., None, None] + s_loc, prev

    init = jnp.zeros((B_, G, hpg, N, Pd), jnp.float32)
    _, S_prev = jax.lax.scan(scan_body, init,
                             (S_local.swapaxes(0, 1),
                              chunk_decay.swapaxes(0, 1), reset.swapaxes(0, 1)))
    S_prev = S_prev.swapaxes(0, 1)                                # (B,nc,G,j,N,P)
    y_inter = jnp.einsum("bcqgn,bcgjnp->bcqgjp", Cc, S_prev,
                         precision=HIGHEST).reshape(B_, nc, Q, H, Pd)
    y_inter = y_inter * jnp.exp(La)[..., None]
    return (y_intra + y_inter).reshape(B_, S, H, Pd)


def mamba_runs(p, cfg: ModelConfig, u: jax.Array, pos: jax.Array
               ) -> jax.Array:
    """Mamba-2 mixer over packed runs.  u: (B, S, d) activations (the
    matmul inputs' dtype); pos: (B, S) position within the run, each run
    starting on an SSD chunk boundary.  → (B, S, d) f32.

    Projections take the activations as they are and accumulate in f32;
    the conv, Δ, the SSD state and the gated norm stay in f32; the norm's
    output is cast back to the activations' dtype for ``out_proj``.
    """
    s_cfg = cfg.ssm
    d_inner, H, Pd, G, N = _dims(cfg)
    B_, S, _ = u.shape
    Q = s_cfg.chunk
    assert S % Q == 0, (S, Q)
    f32 = jnp.float32
    with jax.named_scope("mamba_in_proj"):
        x = jnp.einsum("bsd,dhp->bshp", u, p["wx"],
                       preferred_element_type=f32).reshape(B_, S, H * Pd)
        Bm = jnp.einsum("bsd,dgn->bsgn", u, p["wB"],
                        preferred_element_type=f32).reshape(B_, S, G * N)
        Cm = jnp.einsum("bsd,dgn->bsgn", u, p["wC"],
                        preferred_element_type=f32).reshape(B_, S, G * N)
        z = jnp.einsum("bsd,dhp->bshp", u, p["wz"],
                       preferred_element_type=f32)
        dt = jnp.einsum("bsd,dh->bsh", u, p["wdt"],
                        preferred_element_type=f32)
    xbc = _causal_conv_runs(p, jnp.concatenate([x, Bm, Cm], axis=-1), pos,
                            s_cfg.conv_kernel)
    x, Bm, Cm = _split_xbc(cfg, xbc)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    with jax.named_scope("ssd"):
        y = ssd_runs(x, dt, A, Bm, Cm, pos[:, ::Q] == 0, Q)
    y = y + p["D"][None, None, :, None] * x
    y = _gated_norm(p, y, z, cfg).astype(u.dtype)
    return jnp.einsum("bshp,hpd->bsd", y, p["wo"], preferred_element_type=f32)
