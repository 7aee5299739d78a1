"""Mixture-of-Experts FFN: top-k router + capacity-bounded one-hot dispatch,
or dropless routing over the experts this chip holds.

TPU-idiomatic (GShard/Switch-style) dispatch: tokens are processed in groups
of ``group_size`` so the (tokens × experts × capacity) one-hot dispatch
einsums stay a small fraction of the expert FLOPs; experts are sharded over
the ``model``/``expert`` mesh axis (expert parallelism), so dispatch/combine
lower to all-to-all-like collectives on the production mesh.

Used by phi3.5-moe (16e top-2) and grok-1 (8e top-2).

``moe_dropless`` is the expert-parallel layer run without its exchange
(granite-4.0-h: 72 experts, top-10, of which this chip holds a slice): the
router scores all ``n_experts`` in f32, and every (token, held expert) pair
it chose is computed — sorted by expert, one grouped matmul per projection
(``jax.lax.ragged_dot``), gated and scatter-added back.  Nothing is dropped:
the pair buffer is sized for the expected load with room to spare, and a
batch that overflows it takes a second program sized for every pair.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.params import ParamDef


def moe_defs(cfg: ModelConfig) -> dict:
    """Router over all ``n_experts``; weights of the held experts only."""
    assert cfg.moe is not None
    m = cfg.moe
    d, dt = cfg.d_model, cfg.param_dtype
    dff, e = m.d_expert or cfg.d_ff, len(m.held_experts)
    return {
        "router": ParamDef((d, m.n_experts), ("embed", None),
                           dtype=jnp.float32),
        "wi": ParamDef((e, d, 2, dff), ("expert", "embed", None, "mlp"),
                       dtype=dt, fan_in=d),
        "wo": ParamDef((e, dff, d), ("expert", "mlp", "embed"), dtype=dt,
                       fan_in=dff),
    }


def _dispatch_one_group(p, cfg: ModelConfig, x: jax.Array
                        ) -> tuple[jax.Array, jax.Array]:
    """x: (G, d) → (out (G, d), aux loss scalar)."""
    m = cfg.moe
    G, d = x.shape
    E, K = m.n_experts, m.top_k
    C = max(1, int(G * K * m.capacity_factor / E))

    logits = jnp.einsum("gd,de->ge", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                      # (G, E)
    gate_vals, idx = jax.lax.top_k(probs, K)                     # (G, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # One-hot expert selection per (token, k) slot, flattened in priority
    # order: slot 0 of every token outranks slot 1 (standard top-k priority).
    sel = jax.nn.one_hot(idx, E, dtype=jnp.float32)              # (G, K, E)
    sel_flat = sel.transpose(1, 0, 2).reshape(K * G, E)          # (K·G, E)
    pos = jnp.cumsum(sel_flat, axis=0) - 1.0                     # position in expert
    keep = (pos < C).astype(jnp.float32) * sel_flat
    disp_flat = keep[..., None] * jax.nn.one_hot(pos, C, dtype=jnp.float32)
    dispatch = disp_flat.reshape(K, G, E, C).transpose(1, 0, 2, 3)  # (G,K,E,C)

    combine = jnp.einsum("gk,gkec->gec", gate_vals, dispatch)    # (G, E, C)
    disp = jnp.sum(dispatch, axis=1)                             # (G, E, C)

    xin = jnp.einsum("gec,gd->ecd", disp, x.astype(jnp.float32)
                     ).astype(x.dtype)                           # (E, C, d)
    h = jnp.einsum("ecd,edgf->ecgf", xin, p["wi"])               # (E,C,2,f)
    h = jax.nn.silu(h[..., 0, :]) * h[..., 1, :]
    eout = jnp.einsum("ecf,efd->ecd", h, p["wo"])                # (E, C, d)
    out = jnp.einsum("gec,ecd->gd", combine, eout.astype(jnp.float32))

    # Switch-style load-balance auxiliary loss.
    frac_tokens = jnp.mean(jnp.sum(sel, axis=1), axis=0)         # (E,)
    frac_probs = jnp.mean(probs, axis=0)                         # (E,)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return out.astype(x.dtype), aux


def moe_apply(p, cfg: ModelConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) → (out, aux_loss).  Tokens regrouped to ``group_size``."""
    m = cfg.moe
    b, s, d = x.shape
    tokens = b * s
    g = min(m.group_size, tokens)
    assert tokens % g == 0, (tokens, g)
    xg = x.reshape(tokens // g, g, d)
    out, aux = jax.vmap(lambda xx: _dispatch_one_group(p, cfg, xx))(xg)
    return out.reshape(b, s, d), jnp.mean(aux)


# -- dropless routing over the held experts ------------------------------------

HIGHEST = jax.lax.Precision.HIGHEST

#: Pair buffer of the fast program, as a multiple of the expected held
#: pairs (top_k · held / n_experts per token).
PAIR_SLACK = 1.5


def route_topk(logits: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Granite's router: the top ``k`` raw logits, softmaxed over those k.
    Returns (gates (T, k) f32, expert ids (T, k))."""
    vals, idx = jax.lax.top_k(logits, k)
    return jax.nn.softmax(vals, axis=-1), idx


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def pair_buffers(cfg: ModelConfig, tokens: int) -> tuple[int, int]:
    """(fast, every) row counts of the grouped matmuls for ``tokens``.

    ``every`` holds each pair a batch can have (a token picks each held
    expert at most once); ``fast`` holds ``PAIR_SLACK`` times the expected
    count, and serves whenever the batch's pairs fit in it."""
    m = cfg.moe
    held = len(m.held_experts)
    every = tokens * min(m.top_k, held)
    fast = _round_up(int(tokens * m.top_k * held / m.n_experts * PAIR_SLACK)
                     + 1, 256)
    return min(fast, every), every


def _grouped_experts(p, x: jax.Array, tok: jax.Array, gate: jax.Array,
                     counts: jax.Array, n_pairs: jax.Array, rows: int
                     ) -> jax.Array:
    """``Σ gate · W_o(silu(a) ⊙ b)`` over the first ``rows`` sorted pairs,
    scatter-added into (T, d) f32.  Rows past ``n_pairs`` are computed on
    the last held expert with their gate zeroed."""
    T, d = x.shape
    tok, gate = tok[:rows], gate[:rows]
    live = jnp.arange(rows) < n_pairs
    sizes = counts.at[-1].add(rows - n_pairs).astype(jnp.int32)
    xin = jnp.take(x, tok, axis=0)                               # (M, d)
    wi = p["wi"].reshape(p["wi"].shape[0], d, -1)                # (H, d, 2f)
    h = jax.lax.ragged_dot(xin, wi, sizes,
                           preferred_element_type=jnp.float32)   # (M, 2f)
    f = h.shape[-1] // 2
    act = (jax.nn.silu(h[:, :f]) * h[:, f:]).astype(x.dtype)
    y = jax.lax.ragged_dot(act, p["wo"], sizes,
                           preferred_element_type=jnp.float32)   # (M, d)
    y = y * jnp.where(live, gate, 0.0)[:, None]
    return jnp.zeros((T, d), jnp.float32).at[tok].add(y)


def moe_dropless(p, cfg: ModelConfig, u: jax.Array
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """u: (T, d) f32 normed input → (out (T, d) f32, pairs per held
    expert (H,), the router's picks (T, top_k) expert ids).

    Router logits over all ``n_experts`` in f32 from ``u`` itself; the
    experts take ``u`` in ``param_dtype``.  ``route_topk`` picks and gates;
    pairs on experts held elsewhere contribute nothing here (their chips
    add them)."""
    m = cfg.moe
    held = m.held_experts
    T = u.shape[0]
    x = u.astype(cfg.param_dtype)
    logits = jnp.einsum("td,de->te", u.astype(jnp.float32), p["router"],
                        precision=HIGHEST)
    gates, idx = route_topk(logits, m.top_k)                      # (T, K)
    table = np.full((m.n_experts,), len(held), np.int32)
    table[list(held)] = np.arange(len(held), dtype=np.int32)
    slot = jnp.asarray(table)[idx].reshape(-1)                    # (T·K,)
    order = jnp.argsort(slot, stable=True)
    tok = (order // m.top_k).astype(jnp.int32)
    gate = gates.reshape(-1)[order]
    counts = jnp.zeros((len(held) + 1,), jnp.int32).at[slot].add(1)[:-1]
    n_pairs = jnp.sum(counts)
    fast, every = pair_buffers(cfg, T)

    def run(rows):
        return lambda: _grouped_experts(p, x, tok, gate, counts, n_pairs,
                                        rows)

    if fast == every:
        out = run(every)()
    else:
        out = jax.lax.cond(n_pairs <= fast, run(fast), run(every))
    return out, counts, idx
