"""Model-zoo configuration.

One ``ModelConfig`` describes any architecture in the assigned pool; the
family-specific builders in ``transformer.py`` / ``hybrid.py`` / ``encdec.py``
consume it.  Layer heterogeneity (gemma2/gemma3 local:global alternation,
zamba2 mamba:shared-attention interleave) is expressed as a repeating
``pattern`` so the runtime can ``lax.scan`` over pattern *repeats* — keeping
the traced HLO O(pattern length), not O(depth), which is what makes the
512-virtual-device dry-run compiles tractable (DESIGN §6).
"""
from __future__ import annotations

import dataclasses
from typing import Literal

import jax.numpy as jnp

LayerKind = Literal["global_attn", "local_attn", "mamba", "shared_attn",
                    "attention"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    group_size: int = 4096        # dispatch group (bounds one-hot matmul cost)
    router_aux_weight: float = 0.01
    # The experts this chip holds, by index into the router's
    # ``n_experts`` outputs (None: all of them); the dropless layer
    # (models/moe.moe_dropless) computes only their pairs.
    held: tuple[int, ...] | None = None
    # Width of one routed expert (None: ``ModelConfig.d_ff``).
    d_expert: int | None = None

    @property
    def held_experts(self) -> tuple[int, ...]:
        return self.held if self.held is not None else tuple(
            range(self.n_experts))


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    conv_kernel: int = 4
    chunk: int = 256              # SSD chunk length
    dt_min: float = 0.001
    dt_max: float = 0.1
    # Use the fused Pallas SSD within-chunk kernel (kernels/ssd.py) instead
    # of the XLA einsum chain (requires n_groups == 1).  TPU-only in
    # production (interpret-mode on CPU, for tests).
    use_kernel: bool = False
    # Gated RMSNorm over each head's ``head_dim`` values ("head") or over
    # each group's ``d_inner / n_groups`` values ("group", as Granite-4.0-H
    # and the Mamba-2 reference kernels do).
    norm: Literal["head", "group"] = "head"


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Literal["dense", "moe", "ssm", "hybrid", "vlm", "audio"]
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None          # default d_model // n_heads
    # Repeating layer pattern; cycled n_layers/len(pattern) times.
    pattern: tuple[LayerKind, ...] = ("global_attn",)
    window: int = 4096                   # local_attn window size
    mlp_act: Literal["swiglu", "geglu", "gelu"] = "swiglu"
    qk_norm: bool = False
    attn_logit_softcap: float | None = None
    final_logit_softcap: float | None = None
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    scale_embedding: bool = False        # gemma: embed × sqrt(d_model)
    use_post_norm: bool = False          # gemma2/3 pre+post norm sandwich
    moe: MoEConfig | None = None
    ssm: SSMConfig | None = None
    shared_attn_every: int = 0           # zamba2: shared block cadence
    shared_attn_window: int | None = None  # window for the shared block
    # What follows each layer's mixer: nothing (zamba2's mamba blocks), or
    # a routed MoE plus a shared expert of width ``d_ff`` (granite-4.0-h).
    ffn: Literal["none", "moe_shared"] = "none"
    # Granite's scalings: the embedding is multiplied by
    # ``embedding_multiplier``, each branch by ``residual_multiplier``
    # before it is added, and attention scores by ``attention_multiplier``
    # (its attention has no position encoding).
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float | None = None
    # Encoder-decoder (audio family): encoder depth; decoder uses n_layers.
    n_encoder_layers: int = 0
    # Modality frontend stub: number of prefix embedding tokens consumed.
    frontend: Literal["none", "vision_stub", "audio_stub"] = "none"
    # Blockwise (flash-style) attention: full-sequence attention switches to
    # the streaming two-level block scan when S ≥ flash_threshold.  None →
    # always dense-materialised scores (the naive baseline; see §Perf).
    flash_threshold: int | None = None
    flash_block: int = 512
    # Use the Pallas flash-attention kernel (kernels/flash_attention.py)
    # instead of the jnp block-scan when the flash path triggers.  TPU-only
    # in production (interpret-mode on CPU, for tests).
    flash_kernel: bool = False
    # Chunked-vocab logsumexp in the CE loss: peak f32 logits memory drops
    # ~chunks× (checkpointed scan over vocab chunks).  1 → single pass.
    ce_vocab_chunks: int = 1
    param_dtype: jnp.dtype = jnp.bfloat16
    # Citation of the source model card / paper for the exact numbers.
    source: str = ""

    def __post_init__(self):
        assert self.n_layers % len(self.pattern) == 0, \
            (self.name, self.n_layers, self.pattern)
        assert self.n_heads % self.n_kv_heads == 0

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def n_repeats(self) -> int:
        return self.n_layers // len(self.pattern)

    def with_sliding_windows(self, window: int = 4096) -> "ModelConfig":
        """long_500k override: every attention layer becomes sliding-window
        so the KV cache is bounded (DESIGN §4 policy)."""
        new_pattern = tuple(
            "local_attn" if k == "global_attn" else k for k in self.pattern)
        return dataclasses.replace(self, pattern=new_pattern,
                                   window=min(self.window, window),
                                   shared_attn_window=window)


@dataclasses.dataclass(frozen=True)
class InputShape:
    """One benchmark input shape from the assignment table."""
    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


INPUT_SHAPES: dict[str, InputShape] = {
    "train_4k":    InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k":  InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k":   InputShape("long_500k", 524_288, 1, "decode"),
}
