"""LFM2-8B-A1B (LiquidAI, 2025; ``model_type`` ``lfm2_moe``, 8.3B-A1.5B): 24
layers of hidden 2048.  ``layer_types`` is ``conv conv | full_attention
conv conv conv`` x 4 ``| full_attention conv conv | full_attention conv
conv``: 18 gated short convolutions to 6 grouped-query attentions.  Layers
0 and 1 (``num_dense_layers`` 2) carry a dense SwiGLU of 7168, the other 22
an expert layer: 32 SwiGLU experts of 1792, 4 a token, chosen by a sigmoid
router on score + bias, the gates the chosen plain scores renormalised
(their sum + 1e-6), times ``routed_scaling_factor`` 1; no shared expert.
Pre-norm RMSNorm (``norm_eps`` 1e-5), tied head.

* ``conv`` (``models/gated_conv.py``): ``[B | C | z] = n W_in``, ``u`` the
  3-tap causal depthwise convolution of ``B * z``, ``(C * u) W_out``; no
  activation, no position, no state beyond two rows.
* ``full_attention`` (``models/attention.py``): 32 query heads over 8 key
  and value heads of 64, an RMSNorm PER HEAD on q and on k (one ``[64]``
  scale each) before rotate-half RoPE at theta 1,000,000.

The program's trunk scans whole periods after the dense prefix, so its
pattern is the published one read from the first expert layer (published
layer 2) on: :data:`TRUNK_PATTERN`, ``full_attention, conv, conv, conv``;
the prefix's mixers continue it backwards (both ``conv``; a prefix cut to
one layer is published layer 1).  The published 24 layers are 2 + 4 periods
+ two runs of ``full_attention conv conv``: the last six are no whole
period, which the scanned trunk does not take, so the default here is the
18 layers that are the prefix and the whole periods; the benchmark's cut
(``benchmark/configs/lfm2-8b-a1b.json``) is one dense layer and the four
periods, published layers 1 to 17.  ``experts_held`` / ``first_expert``
tell a chip its share of the experts.  The plain reference is
``dlrover_tpu/models/references/lfm2_moe.py``.  The model trains; it has
no decode path (``decode=True`` raises).
"""

from __future__ import annotations

from typing import Tuple

from dlrover_tpu.models.transformer import (
    CONV,
    FULL_ATTENTION,
    TransformerConfig,
)

LAYER_TYPES: Tuple[str, ...] = (
    (CONV, CONV) + (FULL_ATTENTION, CONV, CONV, CONV) * 4
    + (FULL_ATTENTION, CONV, CONV) * 2
)
NUM_DENSE_LAYERS = 2
PERIOD = 4

# one period of the published kinds, from the first expert layer on
TRUNK_PATTERN: Tuple[str, ...] = LAYER_TYPES[
    NUM_DENSE_LAYERS: NUM_DENSE_LAYERS + PERIOD
]


def lfm2_moe_config(**overrides) -> TransformerConfig:
    defaults = dict(
        vocab_size=65536,
        num_layers=18,             # published 24: see the module's text
        d_model=2048,
        num_heads=32,
        num_kv_heads=8,
        d_ff=7168,                 # the leading dense layers'
        max_seq_len=8192,
        position="rope",
        rope_theta=1000000.0,
        norm="rmsnorm",
        norm_eps=1e-5,
        activation="swiglu",
        use_bias=False,
        tie_embeddings=True,       # the family's; the config is silent
        qk_norm="per_head",
        layer_pattern=TRUNK_PATTERN,
        first_k_dense=NUM_DENSE_LAYERS,
        conv_kernel=3,
        num_experts=32,
        top_k=4,
        moe_d_ff=1792,
        moe_dispatch="grouped",
        router_scoring="sigmoid",
        router_bias=True,
        router_bias_rate=0.001,    # DeepSeek-V3's; the config is silent
        router_norm_eps=1e-6,      # the family's code; the config is silent
        norm_topk_prob=True,
        routed_scaling_factor=1.0,
        num_shared_experts=0,
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)
