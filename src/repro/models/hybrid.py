"""Hybrid Mamba-2 backbones, scanned over repeats of a layer pattern.

Each layer is a mixer — ``mamba``, ``attention`` (weights of its own) or
``shared_attn`` — followed by an FFN: none, or a routed MoE plus a shared
expert (``ModelConfig.ffn``).

* Zamba2 [arXiv:2411.15242]: ``(mamba × k, shared_attn)``; the shared
  attention+MLP block's parameters live once at the top level and are
  closed over inside the ``lax.scan`` body, so every application reuses the
  same weights (the defining property of Zamba2) while each application
  keeps its *own* KV cache slice.  No FFN after the Mamba blocks.
* Granite-4.0-H [hf:ibm-granite/granite-4.0-h-small]: a period of nine
  Mamba-2 layers and one NoPE attention layer, each followed by a dropless
  MoE and a shared expert, with Granite's embedding and residual
  multipliers.  ``features`` runs it over packed runs for brain encoding
  (``repro.encoding.backbone``).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers, moe, ssm
from repro.models.scanning import scan_blocks
from repro.models.config import ModelConfig
from repro.models.params import ParamDef, init as init_params

Params = Any


def _shared_variant(cfg: ModelConfig) -> layers.AttnVariant:
    return layers.AttnVariant(window=cfg.shared_attn_window,
                              softcap=cfg.attn_logit_softcap)


def _shared_block_defs(cfg: ModelConfig) -> dict:
    return {
        "norm1": layers.rmsnorm_defs(cfg.d_model),
        "attn": layers.attention_defs(cfg),
        "norm2": layers.rmsnorm_defs(cfg.d_model),
        "mlp": layers.mlp_defs(cfg),
    }


def _shared_block_train(p, cfg, h, positions):
    a = layers.attention(p["attn"], cfg, _shared_variant(cfg),
                         layers.rmsnorm(p["norm1"], h, cfg.norm_eps),
                         positions)
    h = h + a
    f = layers.mlp(p["mlp"], cfg, layers.rmsnorm(p["norm2"], h, cfg.norm_eps))
    return h + f


def _shared_block_decode(p, cfg, h, pos, cache):
    a, nc = layers.attention_decode(
        p["attn"], cfg, _shared_variant(cfg),
        layers.rmsnorm(p["norm1"], h, cfg.norm_eps), pos, cache)
    h = h + a
    f = layers.mlp(p["mlp"], cfg, layers.rmsnorm(p["norm2"], h, cfg.norm_eps))
    return h + f, nc


def _mamba_block_defs(cfg: ModelConfig) -> dict:
    return {"norm": layers.rmsnorm_defs(cfg.d_model), "mixer": ssm.mamba_defs(cfg)}


def _block_defs(cfg: ModelConfig, kind: str) -> dict:
    """One pattern slot's own parameters: its mixer, then its FFN."""
    defs = (_mamba_block_defs(cfg) if kind == "mamba" else
            {"norm": layers.rmsnorm_defs(cfg.d_model),
             "attn": layers.attention_defs(cfg)})
    if cfg.ffn == "moe_shared":
        defs.update(ffn_norm=layers.rmsnorm_defs(cfg.d_model),
                    moe=moe.moe_defs(cfg), shared=layers.mlp_defs(cfg))
    return defs


def _branch(h: jax.Array, y: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The residual update ``h + residual_multiplier · y``."""
    return h + cfg.residual_multiplier * y


def _shared_expert(p, x: jax.Array) -> jax.Array:
    """Shared SwiGLU expert, f32 accumulation.  x: (B, S, d) → f32."""
    h = jnp.einsum("bsd,dcf->bscf", x, p["wi"],
                   preferred_element_type=jnp.float32)
    act = (jax.nn.silu(h[..., 0, :]) * h[..., 1, :]).astype(x.dtype)
    return jnp.einsum("bsf,fd->bsd", act, p["wo"],
                      preferred_element_type=jnp.float32)


@dataclasses.dataclass
class HybridLM:
    cfg: ModelConfig
    remat: bool = True        # checkpoint each scanned repeat (see DecoderLM)
    unroll: bool = False      # unrolled layer loop for dry-run cost probes

    @property
    def _n_mamba_per_repeat(self) -> int:
        return sum(1 for k in self.cfg.pattern if k == "mamba")

    def param_defs(self) -> dict:
        cfg = self.cfg
        stack = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda d: ParamDef((cfg.n_repeats, *d.shape), ("layer", *d.axes),
                               dtype=d.dtype, init=d.init, scale=d.scale),
            tree, is_leaf=lambda x: isinstance(x, ParamDef))
        blocks = {f"b{i}": stack(_block_defs(cfg, kind))
                  for i, kind in enumerate(cfg.pattern)
                  if kind in ("mamba", "attention")}
        defs = {
            "embed": layers.embed_defs(cfg),
            "blocks": blocks,
            "final_norm": layers.rmsnorm_defs(cfg.d_model),
        }
        if "shared_attn" in cfg.pattern:
            defs["shared"] = _shared_block_defs(cfg)  # single copy — tied
        return defs

    def cache_defs(self, batch: int, seq_len: int) -> dict:
        cfg = self.cfg
        stack = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda d: ParamDef((cfg.n_repeats, *d.shape), ("layer", *d.axes),
                               dtype=d.dtype, init=d.init),
            tree, is_leaf=lambda x: isinstance(x, ParamDef))
        out = {f"b{i}": stack(ssm.ssm_cache_defs(cfg, batch))
               for i, kind in enumerate(cfg.pattern) if kind == "mamba"}
        if "shared_attn" in cfg.pattern:
            shared_len = min(seq_len, cfg.shared_attn_window or seq_len)
            out["shared"] = stack(layers.attn_cache_defs(cfg, batch,
                                                         shared_len))
        return out

    def init(self, key):
        return init_params(key, self.param_defs())

    def init_cache(self, batch: int, seq_len: int):
        return init_params(jax.random.PRNGKey(0),
                           self.cache_defs(batch, seq_len))

    # -- forward --------------------------------------------------------------
    def hidden_states(self, params: Params, batch: dict) -> jax.Array:
        cfg = self.cfg
        h = layers.embed(params["embed"], cfg, batch["tokens"])
        b, s, _ = h.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                     (b, s))
        shared = params.get("shared")

        def body(hh, layer_params):
            for i, kind in enumerate(cfg.pattern):
                if kind == "mamba":
                    blk = layer_params[f"b{i}"]
                    hh = hh + ssm.mamba_apply(
                        blk["mixer"], cfg,
                        layers.rmsnorm(blk["norm"], hh, cfg.norm_eps))
                else:
                    hh = _shared_block_train(shared, cfg, hh, positions)
            return hh, None

        if self.remat:
            body = jax.checkpoint(body)
        h, _ = scan_blocks(body, h, params["blocks"], self.unroll)
        self._last_aux = jnp.float32(0.0)
        return layers.rmsnorm(params["final_norm"], h, cfg.norm_eps)

    def features(self, params: Params, tokens: jax.Array, pos: jax.Array,
                 attn_span: int, every_layer: bool = False) -> tuple:
        """The residual stream after the last layer, with no final norm,
        over packed runs: the feature a brain encoder reads.

        tokens, pos: (B, S) token ids and each token's position within its
        run; runs start on SSD chunk boundaries and span at most
        ``attn_span`` chunks.  Every layer is followed by the MoE and the
        shared expert (``ffn == "moe_shared"``).  Matmul inputs are
        ``param_dtype``; the residual stream, norms, router logits, softmax
        and SSD state are f32 (the router reads the f32 normed input).
        Returns (h (B, S, d) f32, held-expert pair counts (n_repeats, moe
        layers per repeat, held)); with ``every_layer`` also each layer's
        input residual stream (n_layers, B, S, d), the first being the
        scaled embedding, and its router's picks (n_layers, B, S, top_k),
        as a model's hidden states of every layer are read."""
        cfg = self.cfg
        assert cfg.ffn == "moe_shared", cfg.ffn
        act = cfg.param_dtype
        Q = cfg.ssm.chunk
        with jax.named_scope("backbone"):
            h = (jnp.take(params["embed"]["tok"], tokens, axis=0)
                 .astype(jnp.float32) * cfg.embedding_multiplier)

            def body(hh, lp):
                counts, ins, picks = [], [], []
                for i, kind in enumerate(cfg.pattern):
                    blk = lp[f"b{i}"]
                    ins.append(hh)
                    u = layers.rmsnorm(blk["norm"], hh, cfg.norm_eps).astype(act)
                    if kind == "mamba":
                        y = ssm.mamba_runs(blk["mixer"], cfg, u, pos)
                    else:
                        y = layers.run_attention(blk["attn"], cfg, u, pos, Q,
                                                 attn_span)
                    hh = _branch(hh, y, cfg)
                    u = layers.rmsnorm(blk["ffn_norm"], hh, cfg.norm_eps)
                    with jax.named_scope("moe_routed"):
                        r, c, idx = moe.moe_dropless(
                            blk["moe"], cfg, u.reshape(-1, cfg.d_model))
                    with jax.named_scope("shared_mlp"):
                        f = _shared_expert(blk["shared"], u.astype(act))
                    hh = _branch(hh, r.reshape(hh.shape) + f, cfg)
                    counts.append(c)
                    picks.append(idx.reshape(*hh.shape[:-1], -1))
                out = {"counts": jnp.stack(counts)}
                if every_layer:
                    out.update(ins=jnp.stack(ins), picks=jnp.stack(picks))
                return hh, out

            h, out = scan_blocks(body, h, params["blocks"], self.unroll)
        if not every_layer:
            return h, out["counts"]
        flat = lambda a: a.reshape(-1, *a.shape[2:])  # noqa: E731
        return h, out["counts"], flat(out["ins"]), flat(out["picks"])

    def forward(self, params, batch):
        h = self.hidden_states(params, batch)
        return layers.unembed(params["embed"], self.cfg, h), self._last_aux

    def loss(self, params, batch):
        from repro.models import losses
        h = self.hidden_states(params, batch)
        return losses.next_token_nll(params["embed"], self.cfg, h,
                                     batch["tokens"])

    # -- decode -----------------------------------------------------------------
    def prefill(self, params: Params, batch: dict) -> tuple[jax.Array, dict]:
        """Parallel prefill: one chunked-SSD forward pass; the decode cache
        (SSM final states + conv tails + shared-attention KV) falls out of
        the same pass — no sequential token replay."""
        cfg = self.cfg
        h = layers.embed(params["embed"], cfg, batch["tokens"])
        b, s, _ = h.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                     (b, s))
        shared = params.get("shared")
        shared_len = min(s, cfg.shared_attn_window or s)

        def body(hh, layer_params):
            caches = {}
            for i, kind in enumerate(cfg.pattern):
                if kind == "mamba":
                    blk = layer_params[f"b{i}"]
                    y, nc = ssm.mamba_apply(
                        blk["mixer"], cfg,
                        layers.rmsnorm(blk["norm"], hh, cfg.norm_eps),
                        return_cache=True)
                    hh = hh + y
                    caches[f"b{i}"] = nc
                else:
                    x_in = layers.rmsnorm(shared["norm1"], hh, cfg.norm_eps)
                    q, k, v = layers._qkv(shared["attn"], cfg, x_in,
                                          positions)
                    k_c = jnp.roll(k[:, -shared_len:], s % shared_len, axis=1)
                    v_c = jnp.roll(v[:, -shared_len:], s % shared_len, axis=1)
                    caches["shared"] = {"k": k_c.astype(cfg.param_dtype),
                                        "v": v_c.astype(cfg.param_dtype)}
                    hh = _shared_block_train(shared, cfg, hh, positions)
            return hh, caches

        h, cache = scan_blocks(body, h, params["blocks"], self.unroll)
        h = layers.rmsnorm(params["final_norm"], h, cfg.norm_eps)
        logits = layers.unembed(params["embed"], cfg, h[:, -1:, :])
        return logits, cache

    def decode_step(self, params: Params, cache: dict, tokens: jax.Array,
                    pos: jax.Array) -> tuple[jax.Array, dict]:
        """Cache travels in the scan carry (in-place update per repeat) —
        see DecoderLM.decode_step for the double-buffering rationale."""
        cfg = self.cfg
        h = layers.embed(params["embed"], cfg, tokens)
        shared = params.get("shared")

        def body(carry, xs):
            hh, full_cache = carry
            layer_params, idx = xs

            def take(tree):
                return jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0, False),
                    tree)

            def put(tree, new):
                return jax.tree_util.tree_map(
                    lambda a, x: jax.lax.dynamic_update_slice_in_dim(
                        a, x[None].astype(a.dtype), idx, 0), tree, new)

            for i, kind in enumerate(cfg.pattern):
                if kind == "mamba":
                    blk = layer_params[f"b{i}"]
                    y, nc = ssm.mamba_decode(
                        blk["mixer"], cfg,
                        layers.rmsnorm(blk["norm"], hh, cfg.norm_eps),
                        take(full_cache[f"b{i}"]))
                    hh = hh + y
                    full_cache[f"b{i}"] = put(full_cache[f"b{i}"], nc)
                else:
                    hh, nc = _shared_block_decode(shared, cfg, hh, pos,
                                                  take(full_cache["shared"]))
                    full_cache["shared"] = put(full_cache["shared"], nc)
            return (hh, full_cache), None

        idxs = jnp.arange(cfg.n_repeats, dtype=jnp.int32)
        (h, new_cache), _ = scan_blocks(body, (h, dict(cache)),
                                        (params["blocks"], idxs), self.unroll)
        h = layers.rmsnorm(params["final_norm"], h, cfg.norm_eps)
        return layers.unembed(params["embed"], cfg, h), new_cache
