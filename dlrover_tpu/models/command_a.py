"""Command A+ (CohereLabs ``command-a-plus-05-2026``, 2026; ``model_type``
``cohere2_moe``, 218B-A25B): 32 layers of hidden 4096, ``layer_types``
``(sliding_attention, sliding_attention, sliding_attention, full_attention)``
x 8 (``layer_switch`` 4, ``local_attn_first``), every layer an attention AND
an expert layer (``first_k_dense_replace`` 0; the published
``prefix_dense_*`` keys belong to no layer).  Every norm a LayerNorm WITHOUT
a bias (``layer_norm_eps`` 1e-5), a tied head, ``logit_scale`` 1, no biases,
no QK-norm.

* The block is PARALLEL (``use_parallel_block``): ``n = LN(x)``, ``x' = x +
  Attn(n) + Experts(n)``: one norm a layer, neither branch sees the other
  (``models/transformer.py`` ``Block``).
* Attention, both kinds (``models/attention.py``): 128 query heads over 8
  key and value heads of 128.
* ``sliding_attention``: a query sees itself and the ``sliding_window - 1`` =
  4,095 tokens before it; plain RoPE at theta 50,000 over the whole head
  (``rotary_pct`` 1).  The published pairing is ``rope_gptj`` (columns ``2i,
  2i + 1``); the program rotates halves (``i, i + 64``), which on seeded
  weights is one fixed permutation of a head's query and key columns (the
  reference writes the published pairing; a test holds the two equal).
* ``full_attention``: the causal mask and NO rotation (``full_rope`` False).
* Expert layer: 128 SwiGLU experts of 4,096, 8 a token by a sigmoid router
  without a bias, the chosen gates renormalised (``norm_topk_prob``), no
  auxiliary term; beside them 4 shared experts of the same shape whose
  outputs are AVERAGED (``shared_expert_combine``).  ``experts_held`` /
  ``first_expert`` and ``shared_experts_held`` tell a chip its share.

Left out: the vision tower.  The plain reference is
``dlrover_tpu/models/references/command_a.py``; the benchmark's cut
(``benchmark/configs/command-a-plus-05-2026.json``) is published layers 0 to
3, one whole period, on one chip's share of a sixteen-chip stage.  The model
trains; it has no decode path (``decode=True`` raises: the windowed layers'
ring cache does not exist yet).
"""

from __future__ import annotations

from typing import Tuple

from dlrover_tpu.models.transformer import (
    FULL_ATTENTION,
    SLIDING_ATTENTION,
    TransformerConfig,
)

PERIOD = 4
TRUNK_PATTERN: Tuple[str, ...] = (SLIDING_ATTENTION,) * 3 + (FULL_ATTENTION,)
LAYER_TYPES: Tuple[str, ...] = TRUNK_PATTERN * 8


def command_a_config(**overrides) -> TransformerConfig:
    defaults = dict(
        vocab_size=262144,
        num_layers=32,
        d_model=4096,
        num_heads=128,
        num_kv_heads=8,
        head_dim=128,
        d_ff=16384,                # prefix_dense_intermediate_size: no layer's
        max_seq_len=200000,
        position="rope",
        rope_theta=50000.0,
        full_rope=False,
        norm="layernorm",
        norm_eps=1e-5,
        norm_use_bias=False,
        parallel_block=True,
        activation="swiglu",
        use_bias=False,
        tie_embeddings=True,
        logit_scale=1.0,
        layer_pattern=TRUNK_PATTERN,
        sliding_window=4096,
        num_experts=128,
        top_k=8,
        moe_d_ff=4096,
        moe_dispatch="grouped",
        router_scoring="sigmoid",
        norm_topk_prob=True,
        moe_aux_weight=0.0,        # a sigmoid router has no balance term
        num_shared_experts=4,
        shared_expert_combine="average",
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)
