"""Mellum2-12B-A2.5B (JetBrains, 2026; ``model_type`` ``mellum``,
12B-A2.5B): 28 layers of hidden 2304, ``layer_types`` ``(sliding_attention,
sliding_attention, sliding_attention, full_attention)`` x 7, every layer
with an expert layer (``mlp_layer_types`` all ``sparse``; the published
``intermediate_size`` 7168 belongs to no layer).  Pre-norm RMSNorm
(``rms_norm_eps`` 1e-6), untied head, no biases, no scalar multipliers.

* Attention, both kinds (``models/attention.py``): 32 query heads over 4 key
  and value heads of 128, rotate-half over the whole head, no QK-norm (the
  config has no key for one).
* ``sliding_attention``: a query sees itself and the ``sliding_window - 1``
  = 1,023 tokens before it (``0 <= i - j < 1024``, the ``transformers``
  library's reading); plain RoPE at theta 500,000.  On the chip the flash
  kernels skip what lies outside the band (``ops/flash_attention.py``).
* ``full_attention``: the causal mask; YaRN at theta 500,000: factor 16
  over an original 8,192 positions, ``beta_fast`` 32, ``beta_slow`` 1
  (columns 0 to 18 keep their frequency, 35 to 63 are interpolated), cos
  and sin times ``attention_factor`` 1.2772588722239782 = 0.1 ln 16 + 1,
  so a full layer's scores carry its square.
* Expert layer: 64 SwiGLU experts of 896, 8 a token by a softmax router
  over all 64, the chosen gates renormalised (``norm_topk_prob``); no shared
  expert, no router bias.  ``experts_held`` / ``first_expert`` tell a chip
  its share of the experts.

Left out: the multi-token head the model card mentions; the published
config gives it no key, no width and no depth.  The plain reference is
``dlrover_tpu/models/references/mellum.py``; the benchmark's cut
(``benchmark/configs/mellum2-12b-a2.5b.json``) is published layers 0 to 7,
two whole periods.  The model trains; it has no decode path (``decode=True``
raises: the windowed layers' ring cache does not exist yet).
"""

from __future__ import annotations

import math
from typing import Tuple

from dlrover_tpu.models.transformer import (
    FULL_ATTENTION,
    SLIDING_ATTENTION,
    TransformerConfig,
)

PERIOD = 4
TRUNK_PATTERN: Tuple[str, ...] = (SLIDING_ATTENTION,) * 3 + (FULL_ATTENTION,)
LAYER_TYPES: Tuple[str, ...] = TRUNK_PATTERN * 7
YARN_FACTOR = 16.0


def mellum_config(**overrides) -> TransformerConfig:
    defaults = dict(
        vocab_size=98304,
        num_layers=28,
        d_model=2304,
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        d_ff=7168,                 # published, and no layer's
        max_seq_len=131072,
        position="rope",
        rope_theta=500000.0,
        norm="rmsnorm",
        norm_eps=1e-6,
        activation="swiglu",
        use_bias=False,
        tie_embeddings=False,
        layer_pattern=TRUNK_PATTERN,
        sliding_window=1024,
        rope_scaling="yarn",
        rope_scaling_factor=YARN_FACTOR,
        rope_original_max_position=8192,
        rope_beta_fast=32.0,
        rope_beta_slow=1.0,
        rope_attention_factor=0.1 * math.log(YARN_FACTOR) + 1.0,
        num_experts=64,
        top_k=8,
        moe_d_ff=896,
        moe_dispatch="grouped",
        router_scoring="softmax",
        norm_topk_prob=True,
        moe_aux_form="topk",
        moe_aux_weight=0.001,      # the config names no coefficient
        num_shared_experts=0,
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)
