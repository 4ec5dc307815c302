"""GLM-5.2 (zai-org, 2026; ``model_type`` ``glm_moe_dsa``, ~750B-A40B): the
DeepSeek-V3 family's shape with DeepSeek-V3.2's sparse attention.  78 layers
of hidden 6144; latent attention (q through a 2048-wide latent, k and v from
a 512-wide one, 64 rotary columns shared by all 64 heads; keys 256 wide,
values 256; theta 8e6) over 2,048 keys a query that a learned indexer picks
(``index_n_heads`` 32 of ``index_head_dim`` 128 over the q latent:
``models/sparse_attention.py``); three leading dense layers (SwiGLU 12288)
and 75 expert layers (256 routed experts of 2048, 8 a token by a sigmoid
router on score + bias, gates renormalised and scaled by 2.5, one shared
expert); one multi-token-prediction module; untied head.

IndexShare (``indexer_types``): layers 0-2 and every fourth layer from 6 on
hold an indexer and CHOOSE (``index_attention``); the others REUSE the
nearest earlier choice (``reuse_attention``).  From layer 3 on the order is
(reuse, reuse, reuse, index) repeated, ``TRUNK_PATTERN``.  Two things in
the published order have no place yet (ROADMAP "cannot run yet"): the 75
layers after the dense ones end three layers into a period (layers 75-77
reuse layer 74's choice), which a trunk of whole periods cannot hold, and
all THREE leading dense layers choose, where a dense prefix continues the
pattern backwards (``TransformerConfig.layer_kind``) and so can say it of
one.  ``glm_dsa_config`` therefore runs 77 layers unless told a cut: the
leading dense layers counted once (published layer 2, whose choice layers
3-5 reuse) and the 76 layers of 19 whole periods.

What the config leaves open (the indexer's training term and its weight,
the MTP module's layer kind, the bias rule's rate, the MTP loss's weight) is
the family's and is said in ``benchmark/configs/glm-5.2.json``.  The plain
reference is ``dlrover_tpu/models/references/glm_dsa.py``.  The model
trains; it has no decode path (``decode=True`` raises).
"""

from __future__ import annotations

from typing import Tuple

from dlrover_tpu.models.transformer import (
    INDEX_ATTENTION,
    REUSE_ATTENTION,
    TransformerConfig,
)

TRUNK_PATTERN: Tuple[str, ...] = (REUSE_ATTENTION,) * 3 + (INDEX_ATTENTION,)
# ``indexer_types`` as published: "full" chooses, "shared" reuses
INDEXER_TYPES: Tuple[str, ...] = ("full",) * 3 + (
    ("shared",) * 3 + ("full",)
) * 18 + ("shared",) * 3
KIND_OF = {"full": INDEX_ATTENTION, "shared": REUSE_ATTENTION}


def glm_dsa_config(**overrides) -> TransformerConfig:
    defaults = dict(
        vocab_size=154880,
        num_layers=77,             # 1 dense + 19 whole periods (module text)
        d_model=6144,
        num_heads=64,
        d_ff=12288,                # the leading dense layers'
        max_seq_len=16384,
        position="rope",
        rope_theta=8000000.0,
        norm="rmsnorm",
        norm_eps=1e-5,
        activation="swiglu",
        use_bias=False,
        tie_embeddings=False,
        q_lora_rank=2048,
        kv_lora_rank=512,
        qk_nope_head_dim=192,
        qk_rope_head_dim=64,
        v_head_dim=256,
        index_n_heads=32,
        index_head_dim=128,
        index_topk=2048,
        layer_pattern=TRUNK_PATTERN,
        num_experts=256,
        top_k=8,
        moe_d_ff=2048,
        moe_dispatch="grouped",
        router_scoring="sigmoid",
        router_bias=True,
        router_bias_rate=0.001,    # the family's; the config is silent
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        num_shared_experts=1,
        first_k_dense=1,           # published 3, every one choosing
        mtp_depth=1,
        mtp_weight=0.3,            # the family's; the config is silent
        mtp_layer_kind=INDEX_ATTENTION,
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)
