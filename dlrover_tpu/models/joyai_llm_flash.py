"""JoyAI-LLM-Flash 48B-A2.7B (jdopensource, 2026), the DeepSeek-V3 family's
shape: 40 layers of hidden 2048, latent attention (q through a 1536-wide
latent, k and v from a 512-wide one, 64 rotary columns shared by all 32
heads; keys 192 wide, values 128), one leading dense layer (SwiGLU 7168)
and 39 expert layers (256 routed experts of 768, 8 a token, chosen by a
sigmoid router on score + bias, gates renormalised and scaled by 2.5, one
shared expert), a multi-token-prediction module of depth 1, untied head.

Values from ``jdopensource/JoyAI-LLM-Flash``'s ``config.json``; what it
leaves open (the bias rule's rate, the MTP loss's weight) is the family's
and is said in ``benchmark/configs/joyai-llm-flash.json``.  ``experts_held``
/ ``first_expert`` tell a chip its share of the experts.  The plain
reference is ``dlrover_tpu/models/references/joyai_llm_flash.py``.  The
model trains; it has no decode path (``decode=True`` raises).
"""

from __future__ import annotations

from dlrover_tpu.models.transformer import TransformerConfig


def joyai_llm_flash_config(**overrides) -> TransformerConfig:
    defaults = dict(
        vocab_size=129280,
        num_layers=40,
        d_model=2048,
        num_heads=32,
        d_ff=7168,                 # the leading dense layer's
        max_seq_len=8192,
        position="rope",
        rope_theta=32000000.0,
        norm="rmsnorm",
        norm_eps=1e-6,
        activation="swiglu",
        use_bias=False,
        tie_embeddings=False,
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        num_experts=256,
        top_k=8,
        moe_d_ff=768,
        moe_dispatch="grouped",
        router_scoring="sigmoid",
        router_bias=True,
        router_bias_rate=0.001,    # the family's; the config is silent
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        num_shared_experts=1,
        first_k_dense=1,
        mtp_depth=1,
        mtp_weight=0.3,            # the family's; the config is silent
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)
