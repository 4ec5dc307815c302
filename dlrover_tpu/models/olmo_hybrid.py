"""Olmo-Hybrid-7B (allenai, 2026): three gated-delta-rule layers to each
full-attention layer, 32 layers of hidden 3840, SwiGLU 11008, untied head.

Values from ``allenai/Olmo-Hybrid-7B``'s ``config.json``; what it leaves
open (norm placement, ``rope_theta``) is the OLMo family's and is said in
``benchmark/configs/olmo-hybrid-7b.json``.  The plain reference is
``dlrover_tpu/models/references/olmo_hybrid.py``.  The model trains; it has
no decode path (``decode=True`` raises).
"""

from __future__ import annotations

from dlrover_tpu.models.transformer import (
    FULL_ATTENTION,
    LINEAR_ATTENTION,
    TransformerConfig,
)


def olmo_hybrid_config(**overrides) -> TransformerConfig:
    defaults = dict(
        vocab_size=100352,
        num_layers=32,
        d_model=3840,
        num_heads=30,              # full layers: MHA, head_dim 128
        d_ff=11008,
        max_seq_len=8192,
        position="rope",
        rope_theta=500000.0,       # published null; the family's
        norm="rmsnorm",
        norm_eps=1e-6,
        norm_placement="post",
        activation="swiglu",
        use_bias=False,
        tie_embeddings=False,
        qk_norm=True,
        layer_pattern=(LINEAR_ATTENTION,) * 3 + (FULL_ATTENTION,),
        linear_num_heads=30,
        linear_key_head_dim=96,
        linear_value_head_dim=192,
        linear_conv_kernel=4,
        linear_allow_neg_eigval=True,
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)
