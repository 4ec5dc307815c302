"""Ling-3.0-flash-VL's language model (inclusionAI, 2026; ~125B-A5.5B): 42
layers of hidden 2560.  Published layer ``i`` is gated latent attention
where ``(i + 1) % layer_group_size == 0`` (``layer_group_size`` 6: layers
5, 11, .. 41) and Kimi Delta Attention elsewhere, five to one; layers 0
and 1 (``first_k_dense_replace`` 2) carry a dense SwiGLU of 6144, the other
40 an expert layer: 512 routed experts of 768, 8 a token, chosen by a
sigmoid router on score + bias through a GROUP LIMIT (8 groups of 64
consecutive experts, the 4 best groups a token), gates renormalised and
scaled by 2.5, one shared expert.  Pre-norm, untied head.

* KDA (``models/linear_attention.py`` ``KimiDeltaAttention``, ``ops/kda``):
  32 heads of 128 / 128, a 4-tap convolution, a decay PER CHANNEL under the
  safe gate (``kda_lower_bound`` -5), a full-rank decay projection, a
  sigmoid output gate, no rotation.
* Latent attention (``models/attention.py``): ``q_lora_rank`` null (q
  straight from the stream), k and v from a 512-wide latent, 64 rotary
  columns shared by the heads, keys 192 wide and values 128, each head's
  output through a head-wise sigmoid gate.

The program's trunk scans whole periods after the dense prefix, so its
pattern is the published one read from the first expert layer (published
layer 2) on: :data:`TRUNK_PATTERN`, KDA, KDA, KDA, latent, KDA, KDA; the
prefix's mixers continue it backwards (both KDA; a prefix cut to one
layer is published layer 1).  The published
42 layers are 2 + 6 periods + (KDA, KDA, KDA, latent): the last four are a
partial period, which the scanned trunk does not take, so the default here
is the 38 layers that are whole periods; the benchmark's cut
(``benchmark/configs/ling-3.0-flash-vl.json``) is one dense layer and one
period.  The vision tower and the MTP module are no part of this model
(the config counts no MTP layer).  ``experts_held`` / ``first_expert``
tell a chip its share of the experts.  The plain reference is
``dlrover_tpu/models/references/ling_flash.py``.  The model trains; it has
no decode path (``decode=True`` raises).
"""

from __future__ import annotations

from typing import Tuple

from dlrover_tpu.models.transformer import (
    FULL_ATTENTION,
    LINEAR_ATTENTION,
    TransformerConfig,
)

LAYER_GROUP_SIZE = 6
FIRST_K_DENSE = 2


def published_kind(layer: int) -> str:
    return (
        FULL_ATTENTION if (layer + 1) % LAYER_GROUP_SIZE == 0
        else LINEAR_ATTENTION
    )


# one period of the published kinds, from the first expert layer on
TRUNK_PATTERN: Tuple[str, ...] = tuple(
    published_kind(FIRST_K_DENSE + i) for i in range(LAYER_GROUP_SIZE)
)


def ling_flash_config(**overrides) -> TransformerConfig:
    defaults = dict(
        vocab_size=157184,
        num_layers=38,             # published 42: see the module's text
        d_model=2560,
        num_heads=32,
        d_ff=6144,                 # the leading dense layers'
        max_seq_len=8192,
        position="rope",
        rope_theta=6000000.0,
        norm="rmsnorm",
        norm_eps=1e-6,
        activation="swiglu",
        use_bias=False,
        tie_embeddings=False,
        layer_pattern=TRUNK_PATTERN,
        first_k_dense=FIRST_K_DENSE,
        linear_rule="kda",
        linear_num_heads=32,
        linear_key_head_dim=128,
        linear_value_head_dim=128,
        linear_conv_kernel=4,
        linear_decay_bound=-5.0,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        attention_gate="head_wise",
        num_experts=512,
        top_k=8,
        moe_d_ff=768,
        moe_dispatch="grouped",
        router_scoring="sigmoid",
        router_bias=True,
        router_bias_rate=0.001,    # the family's; the config is silent
        router_groups=8,
        router_topk_groups=4,
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        num_shared_experts=1,
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)
