"""Solar-Open2-250B (upstage, 2026; ``solar_open2``, 250B-A15B): 48 layers
of hidden 4096.  Published layer ``i`` is gated grouped-query attention
where ``i`` is in ``gqa_layers`` (0, 4, .. 44: ``gqa_interval`` 3 linear
layers between two of them) and Kimi Delta Attention elsewhere, three to
one; every layer (``first_k_dense_replace`` 0) carries an expert layer: 320
routed experts of 1,280, 8 a token, chosen by a softmax router, the chosen
scores renormalised (``norm_topk_prob``) times ``routed_scaling_factor`` 1,
one shared expert.  Pre-norm, untied head.

* KDA (``models/linear_attention.py`` ``KimiDeltaAttention``, ``ops/kda``):
  64 heads of 128 / 128, a 4-tap convolution, a decay PER CHANNEL under the
  published gate ``-exp(A_log) softplus(.)`` with NO lower bound (the
  rule's exact form), the decay's and the output gate's projections
  through 128 columns (``kda_use_full_proj`` false), beta doubled
  (``kda_allow_neg_eigval``), no rotation.
* Grouped-query attention (``models/attention.py``): 64 query heads over 8
  key/value heads of 128, NO rotation (``use_rope`` false: ``rope_theta``
  and ``partial_rotary_factor`` are read by nothing), every channel of
  every head's output under its own sigmoid gate (``use_gqa_gate``).

The published pattern starts with a GQA layer, so the program's trunk is
the published one from layer 0 on: :data:`TRUNK_PATTERN`.  ``experts_held``
/ ``first_expert`` tell a chip its share of the experts.  The plain
reference is ``dlrover_tpu/models/references/solar_open.py``.  The model
trains; it has no decode path (``decode=True`` raises).
"""

from __future__ import annotations

from typing import Tuple

from dlrover_tpu.models.transformer import (
    FULL_ATTENTION,
    LINEAR_ATTENTION,
    TransformerConfig,
)

GQA_INTERVAL = 3


def published_kind(layer: int) -> str:
    return (
        FULL_ATTENTION if layer % (GQA_INTERVAL + 1) == 0
        else LINEAR_ATTENTION
    )


# one period of the published kinds, from layer 0 on
TRUNK_PATTERN: Tuple[str, ...] = tuple(
    published_kind(i) for i in range(GQA_INTERVAL + 1)
)


def solar_open2_config(**overrides) -> TransformerConfig:
    defaults = dict(
        vocab_size=196608,
        num_layers=48,
        d_model=4096,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        d_ff=10240,                # published; no layer is dense
        max_seq_len=16384,
        position="none",
        norm="rmsnorm",
        norm_eps=1e-5,
        activation="swiglu",
        use_bias=False,
        tie_embeddings=False,
        layer_pattern=TRUNK_PATTERN,
        linear_rule="kda",
        linear_num_heads=64,
        linear_key_head_dim=128,
        linear_value_head_dim=128,
        linear_conv_kernel=4,
        linear_decay_bound=0.0,    # the published gate: no bound
        linear_gate_rank=128,
        linear_allow_neg_eigval=True,
        attention_gate="elementwise",
        num_experts=320,
        top_k=8,
        moe_d_ff=1280,
        moe_dispatch="grouped",
        router_scoring="softmax",
        moe_aux_weight=0.0,        # the config has no key for a balance term
        norm_topk_prob=True,
        routed_scaling_factor=1.0,
        num_shared_experts=1,
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)
