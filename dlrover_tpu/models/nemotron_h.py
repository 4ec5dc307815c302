"""NVIDIA-Nemotron-3-Nano-30B-A3B (nvidia, 2025-12; ``model_type``
``nemotron_h``): 52 layers of hidden 2688, each ONE residual branch by the
letter of ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer (64 heads of
64, a 128-wide state, 8 groups, a 4-tap convolution with bias, chunk 128),
``*`` grouped-query attention (32 heads of 128 over 2 key/value heads, no
positional rotation), ``E`` an expert layer (128 routed experts of 1856,
6 a token, chosen by a sigmoid router on score + bias, gates renormalised
and scaled by 2.5, one shared expert of 3712; every expert
``W_down relu(W_up n)^2``, no gate), ``-`` a dense MLP alone; untied head.

Values from ``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``'s
``config.json``; what it leaves open (the bias rule's rate) is said in
``benchmark/configs/nemotron-3-nano-30b-a3b.json``.  The published order
is not periodic (runs of 6, 7, 7, 7, 7, 9, 9 layers between attention
layers) and the trunk scans whole periods: the default pattern here is the
nine-layer run ``EMEMEMEM*`` (published layers 34-42), whose 4 : 4 : 1 is
the model's 23 : 23 : 6 to the nearest layer.  ``experts_held`` /
``first_expert`` tell a chip its share of the experts.  The plain reference
is ``dlrover_tpu/models/references/nemotron_h.py``.  The model trains; it
has no decode path (``decode=True`` raises).
"""

from __future__ import annotations

from typing import Tuple

from dlrover_tpu.models.transformer import (
    ATTENTION,
    EXPERTS,
    MLP,
    SSM,
    TransformerConfig,
)

PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
PERIOD = "EMEMEMEM*"
LETTERS = {"M": SSM, "*": ATTENTION, "E": EXPERTS, "-": MLP}


def kinds(letters: str) -> Tuple[str, ...]:
    """``hybrid_override_pattern``'s letters as layer kinds."""
    return tuple(LETTERS[c] for c in letters)


def nemotron_h_config(**overrides) -> TransformerConfig:
    defaults = dict(
        vocab_size=131072,
        num_layers=54,             # six periods; published 52, not periodic
        d_model=2688,
        num_heads=32,
        num_kv_heads=2,
        head_dim=128,
        d_ff=1856,                 # a ``-`` layer's; the pattern has none
        max_seq_len=8192,
        position="none",
        norm="rmsnorm",
        norm_eps=1e-5,
        activation="relu2",
        use_bias=False,
        tie_embeddings=False,
        layer_pattern=kinds(PERIOD),
        ssm_num_heads=64,
        ssm_head_dim=64,
        ssm_state_size=128,
        ssm_groups=8,
        ssm_conv_kernel=4,
        ssm_chunk=128,
        ssm_dt_min=0.001,
        ssm_dt_max=0.1,
        ssm_dt_floor=1e-4,
        num_experts=128,
        top_k=6,
        moe_d_ff=1856,
        shared_expert_d_ff=3712,
        num_shared_experts=1,
        moe_dispatch="grouped",
        router_scoring="sigmoid",
        router_bias=True,
        router_bias_rate=0.001,    # the DeepSeek-V3 rule's; the config is silent
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)
