"""Granite-4.0-H-Small (ibm-granite, 2025-10; ``model_type``
``granitemoehybrid``, 32B-A9B): 40 layers of hidden 4096, each a mixer and
an expert layer on two pre-norm residual branches, ``x + r f(RMSNorm(x))``
twice with ``r = residual_multiplier`` 0.22.  By ``layer_types`` the mixer
is ``mamba``, a Mamba-2 mixer (128 heads of 64, a 128-wide state, ONE group:
all 128 heads share one B and C; a 4-tap convolution with bias; published
chunk 256), or ``attention``, grouped-query attention (32 heads of 128 over
8 key/value heads, no position of any kind, scores times
``attention_multiplier`` 1/128) at layers 5, 15, 25, 35.  EVERY layer's
second branch is an expert layer: 72 gated SiLU experts of 768, 10 a token,
gates a softmax over the ten chosen logits, beside a gated shared expert of
1536.  The embedding's output is multiplied by ``embedding_multiplier`` 12,
the logits (tied head) divided by ``logits_scaling`` 16.

The program runs a Granite layer as TWO one-branch layers of its trunk
(``transformer.BranchBlock``): ``ssm`` or ``attention``, then ``experts``;
:func:`kinds` maps ``layer_types`` to them, so the published period of ten
layers (``mamba`` x 5, ``attention``, ``mamba`` x 4) is twenty of the
program's, and the published 40 layers are ``num_layers`` 80.

Values from ``ibm-granite/granite-4.0-h-small``'s ``config.json``; what it
leaves open (the range ``dt`` is drawn from, the balance loss's weight) is
said in ``benchmark/configs/granite-4.0-h-small.json``.  ``experts_held`` /
``first_expert`` tell a chip its share of the experts.  The plain reference
is ``dlrover_tpu/models/references/granite_moe_hybrid.py``.  The model
trains; it has no decode path (``decode=True`` raises: the one-group state
``[128, 64, 128]`` a sequence has no place in ``serving/decode.py``).
"""

from __future__ import annotations

from typing import Sequence, Tuple

from dlrover_tpu.models.transformer import (
    ATTENTION,
    EXPERTS,
    SSM,
    TransformerConfig,
)

MIXERS = {"mamba": SSM, "attention": ATTENTION}
PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
PUBLISHED_LAYER_TYPES = PERIOD * 4


def kinds(layer_types: Sequence[str]) -> Tuple[str, ...]:
    """``layer_types`` as the program's layer kinds: each published layer
    is its mixer's branch, then its expert layer's."""
    return tuple(
        kind for name in layer_types for kind in (MIXERS[name], EXPERTS)
    )


def granite_moe_hybrid_config(**overrides) -> TransformerConfig:
    defaults = dict(
        vocab_size=100352,
        num_layers=2 * len(PUBLISHED_LAYER_TYPES),
        d_model=4096,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        d_ff=768,                  # a dense MLP's; the pattern has none
        max_seq_len=8192,
        position="none",
        norm="rmsnorm",
        norm_eps=1e-5,
        activation="swiglu",
        use_bias=False,
        tie_embeddings=True,
        layer_pattern=kinds(PERIOD),
        embed_scale=12.0,
        attention_scale=0.0078125,
        residual_scale=0.22,
        logit_scale=1.0 / 16,
        ssm_num_heads=128,
        ssm_head_dim=64,
        ssm_state_size=128,
        ssm_groups=1,
        ssm_conv_kernel=4,
        ssm_chunk=256,
        ssm_dt_min=0.001,          # Mamba-2's; the config is silent
        ssm_dt_max=0.1,
        ssm_dt_floor=1e-4,
        num_experts=72,
        top_k=10,
        moe_d_ff=768,
        shared_expert_d_ff=1536,
        num_shared_experts=1,
        moe_dispatch="grouped",
        router_scoring="softmax",
        norm_topk_prob=True,
        moe_aux_form="topk",
        moe_aux_weight=0.001,      # the family's default; the config is silent
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)
