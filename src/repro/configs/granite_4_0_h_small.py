"""granite-4.0-h-small — hybrid Mamba-2 / NoPE GQA with a dropless MoE
[hf:ibm-granite/granite-4.0-h-small, config.json; model_type
granitemoehybrid, 32B-A9B].

Published: 40 layers in periods of ten (nine Mamba-2 layers, then one
attention layer at indices 5, 15, 25, 35), hidden 4,096.  Mamba-2: 128
heads × 64, d_state 128, 1 group, conv 4, expand 2 (d_inner 8,192), chunk
256, no projection bias, conv bias.  Attention: 32 query and 8 KV heads of
128, no position encoding (``position_embedding_type: nope``), scores
scaled by ``attention_multiplier`` 1/128.  After every layer a MoE of 72
experts, top-10, each of width 768 (``intermediate_size``: the catalog
reads it as one routed expert's width, an inference), plus a shared expert
of width 1,536.  ``embedding_multiplier`` 12, ``residual_multiplier``
0.22, RMSNorm eps 1e-5, SiLU, tied embeddings, vocabulary 100,352.
``logits_scaling`` (16) is not used: a feature extractor has no head.

``CONFIG`` is what one chip runs of a deployment of four pipeline stages
of one period each, with each layer's 72 experts spread over eight chips
by expert parallelism: this chip holds layers 0–9, experts 0–8 of each
layer and vocabulary ids 0–12,543 (an eighth of 100,352).  The router
still scores all 72 experts and picks ten; the chip computes the picks
that fall on its nine (``models/moe.moe_dropless``).  No width is cut.
"""
from repro.models.config import ModelConfig, MoEConfig, SSMConfig

#: Published values of the keys this chip's share cuts: (here, published).
REDUCED = {"layers": (10, 40), "experts": (9, 72), "vocab": (12_544, 100_352)}

DEPLOYMENT = ("4 pipeline stages of one period (10 layers) each; every "
              "layer's 72 experts over 8 chips by expert parallelism; this "
              "chip: layers 0-9, experts 0-8, vocabulary ids 0-12,543")

#: Sizes the source does not give and this repository sets.
ASSUMED = {
    "weights": "random from a seed (no checkpoint is read)",
    "feature": "the residual stream after layer 10 (hidden_states[10] in "
               "the Hugging Face numbering), no final norm",
}

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=10,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=1536,                        # shared expert
    vocab=12_544,
    pattern=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
    mlp_act="swiglu",
    norm_eps=1e-5,
    tie_embeddings=True,
    ffn="moe_shared",
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.0078125,
    moe=MoEConfig(n_experts=72, top_k=10, held=tuple(range(9)),
                  d_expert=768),
    ssm=SSMConfig(d_state=128, expand=2, head_dim=64, n_groups=1,
                  conv_kernel=4, chunk=256, norm="group"),
    source="[hf:ibm-granite/granite-4.0-h-small] config.json: 40L (period "
           "9 mamba + 1 attention), d=4096, 32H/kv8 x128 NoPE, mamba 128x64 "
           "state 128, MoE 72e top-10 x768 + shared 1536; one chip's share",
)
