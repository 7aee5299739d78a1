"""Assigned-architecture configs (``--arch <id>``) + smoke reduction.

Every module in this package defines ``CONFIG`` with the exact assigned
numbers (source cited in ``ModelConfig.source``).  ``smoke(cfg)`` derives
the reduced same-family variant used by CPU smoke tests (≤2 effective
layers, d_model ≤ 512, ≤ 4 experts, per the assignment rules).
"""
from __future__ import annotations

import dataclasses
import importlib

from repro.models.config import ModelConfig, MoEConfig, SSMConfig

ARCH_IDS = (
    "mamba2-130m",
    "qwen3-1.7b",
    "phi3.5-moe-42b-a6.6b",
    "llava-next-34b",
    "zamba2-2.7b",
    "gemma-7b",
    "grok-1-314b",
    "gemma3-12b",
    "seamless-m4t-medium",
    "gemma2-2b",
)

_MODULES = {
    "mamba2-130m": "mamba2_130m",
    "qwen3-1.7b": "qwen3_1_7b",
    "phi3.5-moe-42b-a6.6b": "phi35_moe",
    "llava-next-34b": "llava_next_34b",
    "zamba2-2.7b": "zamba2_2_7b",
    "gemma-7b": "gemma_7b",
    "grok-1-314b": "grok1_314b",
    "gemma3-12b": "gemma3_12b",
    "seamless-m4t-medium": "seamless_m4t_medium",
    "gemma2-2b": "gemma2_2b",
    "granite-4.0-h-small": "granite_4_0_h_small",
}

#: Feature backbones: run only as brain-encoding feature extractors
#: (``repro.encoding.backbone``), one chip's share of a deployment each.
BACKBONE_IDS = ("granite-4.0-h-small",)


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.CONFIG


def tiny_backbone(cfg: ModelConfig, *, d_model: int = 64, experts: int = 8,
                  top_k: int = 2, held: int = 4) -> ModelConfig:
    """A feature backbone at a size the CPU runs in a test: the same layer
    pattern and mechanisms, narrow widths, ``held`` of ``experts``."""
    return dataclasses.replace(
        cfg, name=cfg.name + "-tiny", d_model=d_model, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=32, vocab=97,
        moe=dataclasses.replace(cfg.moe, n_experts=experts, top_k=top_k,
                                held=tuple(range(held)), d_expert=16),
        ssm=dataclasses.replace(cfg.ssm, d_state=8, head_dim=8, chunk=8))


def smoke(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family variant for single-CPU smoke tests."""
    # Keep pattern diversity with ≤2 entries: first and last kinds.
    pattern = cfg.pattern if len(cfg.pattern) <= 2 else \
        (cfg.pattern[0], cfg.pattern[-1])
    n_heads = 4
    n_kv = min(cfg.n_kv_heads, 2) if cfg.n_kv_heads < cfg.n_heads else n_heads
    moe = None
    if cfg.moe is not None:
        moe = dataclasses.replace(cfg.moe, n_experts=4,
                                  top_k=min(cfg.moe.top_k, 2), group_size=64)
    ssm = None
    if cfg.ssm is not None:
        ssm = dataclasses.replace(cfg.ssm, d_state=16, head_dim=32, chunk=8)
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        n_layers=len(pattern) * 1,           # one repeat of a ≤2-entry pattern
        pattern=pattern,
        d_model=256,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        head_dim=64,
        d_ff=512 if cfg.d_ff else 0,
        vocab=512,
        window=min(cfg.window, 32),
        shared_attn_window=(min(cfg.shared_attn_window, 32)
                            if cfg.shared_attn_window else None),
        moe=moe,
        ssm=ssm,
        n_encoder_layers=2 if cfg.n_encoder_layers else 0,
        param_dtype=cfg.param_dtype,
    )
