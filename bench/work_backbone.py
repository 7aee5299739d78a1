"""Operations and bytes the backbone cell's work needs, from shapes.

As in ``bench/work.py``, these count what the algorithm needs, never what
one implementation does: a causal attention's mean context, a chunked
SSD's within-chunk square, and the routed experts at the pairs the router
sends to the experts held here.  FLOP counts a multiply and an add as two.
"""
from __future__ import annotations

from bench import reference_backbone as rb

BF16 = 2


def layer_flops(config: dict) -> dict:
    """FLOP per token of each part of one period, attention's scores and
    probabilities left out (``attention_context_flops``)."""
    m = rb.dims(config)
    d, H, P, N, G, Q = m["d"], m["H"], m["P"], m["N"], m["G"], m["Q"]
    di = H * P
    mamba_layers = sum(k == "mamba" for k in m["layers"])
    attn_layers = len(m["layers"]) - mamba_layers
    proj = 2 * d * (2 * di + 2 * G * N + H) + 2 * di * d
    # Within-chunk term over the chunk's square, C·B scores, and the
    # state's write and read: per token, per layer.
    ssd = 2 * Q * H * P + 2 * Q * G * N + 2 * 2 * H * N * P
    attn_proj = 2 * d * (m["Hq"] + 2 * m["Hk"]) * m["D"] + 2 * m["Hq"] * m["D"] * d
    return {"mamba_proj": mamba_layers * proj,
            "ssd": mamba_layers * ssd,
            "attention_proj": attn_layers * attn_proj,
            "router": len(m["layers"]) * 2 * d * m["E"],
            "shared": len(m["layers"]) * 2 * 3 * d * m["fs"]}


def pair_flops(config: dict) -> float:
    """One routed (token, expert) pair: ``W_o(silu(a) ⊙ b)``."""
    m = rb.dims(config)
    return 2.0 * 3 * m["d"] * m["f"]


def held_pairs_per_token(config: dict) -> float:
    """Pairs a token sends to the held experts, expected over the layers
    of one period: top_k · held / experts per layer."""
    m = rb.dims(config)
    return len(m["layers"]) * m["top_k"] * m["held"] / m["E"]


def attention_context_flops(config: dict, run_tokens) -> float:
    """Scores and probabilities·V of causal attention over runs of
    ``run_tokens`` tokens: 4·Hq·D per (query, key ≤ query) pair."""
    m = rb.dims(config)
    attn_layers = sum(k != "mamba" for k in m["layers"])
    pairs = sum(n * (n + 1) / 2 for n in run_tokens)
    return attn_layers * 4.0 * m["Hq"] * m["D"] * pairs


def backbone_flops(config: dict, run_tokens) -> float:
    """One extraction over runs of ``run_tokens`` tokens (padding not
    counted)."""
    tokens = float(sum(run_tokens))
    per = sum(layer_flops(config).values())
    routed = held_pairs_per_token(config) * pair_flops(config)
    return tokens * (per + routed) + attention_context_flops(config,
                                                             run_tokens)


def moe_call_bytes(config: dict, pairs: float) -> float:
    """A grouped expert matmul call of one layer: the held experts'
    weights read once, and each pair's row in and out (bf16)."""
    m = rb.dims(config)
    return BF16 * (m["held"] * 3 * m["d"] * m["f"] + pairs * 2 * m["d"])


def grouped_matmul_seconds(trace: dict) -> float:
    """Device seconds of the grouped expert matmuls in a reduced trace:
    the ``ragged-dot`` ops, whose events carry their HLO name and no
    ``tf_op`` scope path."""
    return sum(t for path, t in trace["scopes"].items()
               if path.startswith("ragged-dot"))
