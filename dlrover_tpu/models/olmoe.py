"""OLMoE-1B-7B (Muennighoff et al. 2024, arXiv:2409.02060): 64 experts of
width 1024, 8 a token, dropless, QK-norm, gates not renormalised.

Values from ``allenai/OLMoE-1B-7B-0125-Instruct``'s ``config.json``; the
plain reference is ``dlrover_tpu/models/references/olmoe.py``.
"""

from __future__ import annotations

from dlrover_tpu.models.transformer import TransformerConfig


def olmoe_config(**overrides) -> TransformerConfig:
    defaults = dict(
        vocab_size=50304,
        num_layers=16,
        d_model=2048,
        num_heads=16,
        d_ff=1024,                 # one expert's width
        max_seq_len=4096,
        position="rope",
        rope_theta=10000.0,
        norm="rmsnorm",
        activation="swiglu",
        use_bias=False,
        tie_embeddings=False,
        qk_norm=True,
        num_experts=64,
        top_k=8,
        norm_topk_prob=False,
        moe_aux_form="topk",
        moe_aux_weight=0.01,
        # Dropless, as trained: the capacity einsum would spend 3.3x the
        # expert matmuls' FLOPs on dispatch at 64 experts (PERF.md).
        moe_dispatch="grouped",
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)
