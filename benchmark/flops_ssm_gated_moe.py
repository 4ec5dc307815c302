"""Operations and bytes from shapes for a model whose layers are a Mamba-2
state-space mixer or an attention on one residual branch and, on the next,
an expert layer of GATED experts on one chip's share beside a GATED shared
expert (Granite-4.0-H): what no function of ``flops_ssm_moe.py`` counts.
The state-space projections, the chunked scan, attention on the causal
half, the pairs routed here and ``_sizes`` are that module's, imported and
not copied; ``ssd_cost`` and ``gqa_flash_cost`` there serve this model
unedited (``ssm_groups`` 1, ``num_kv_heads`` 8).  Plain numbers in, counts
out: no JAX, no program code.

``model`` is the ``model`` group of a configuration file (the program's
``TransformerConfig`` fields).  A published layer is two of the program's
(``layer_pattern``: ``"ssm"`` or ``"attention"``, then ``"experts"``), and
layers are counted from that pattern as ``flops_ssm_moe`` counts them.

What differs from ``flops_ssm_moe``: an expert, the shared expert and a
dense MLP are THREE matrices (``W_down (SiLU(W_gate n) * W_up n)``), where
its ungated ones are two.  The four scalar multipliers are elementwise and
count as nothing.
"""

from __future__ import annotations

from typing import Dict, Mapping

from benchmark.flops_ssm_moe import (
    _sizes,
    flops_per_token_by_part as _ungated_parts,
    pairs_here_per_token,
)

# the parts whose matrices a gate adds one to: 3 where the ungated count 2
_GATED = ("shared_experts", "routed_here", "dense_mlp")


def flops_per_token_by_part(model: Mapping, seq_len: int) -> Dict[str, float]:
    """Forward + backward model FLOPs of one token at ``seq_len``, by part
    (the parts sum to ``model_flops_per_token``): ``flops_ssm_moe``'s parts
    with three matrices an expert, a shared expert and a dense MLP."""
    return {
        part: value * (1.5 if part in _GATED else 1.0)
        for part, value in _ungated_parts(model, seq_len).items()
    }


def model_flops_per_token(model: Mapping, seq_len: int) -> float:
    return sum(flops_per_token_by_part(model, seq_len).values())


def gated_held_expert_matmul_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """FLOPs and HBM bytes the grouped GEMMs of ONE training step need for
    the pairs routed HERE (expected): THREE matrices of 2 x d x moe_d_ff a
    pair, three times (forward, d-input, d-weight), every expert layer.
    Padding and recomputation are not needed, so not counted.  Bytes: the
    HELD experts' weights read forward and backward and their gradients
    written once (bf16), plus the routed rows in (d), the up and gate
    products out and their product in (3 moe_d_ff) and the rows out (d)."""
    g = _sizes(model)
    routed = sequences * seq_len * pairs_here_per_token(model)
    flops = 3 * 3 * 2.0 * routed * g["d"] * g["moe_d_ff"] * g["expert_layers"]
    weights = 2.0 * g["held"] * 3 * g["d"] * g["moe_d_ff"]
    acts = 2.0 * routed * (2 * g["d"] + 3 * g["moe_d_ff"])
    return {
        "flops": flops,
        "bytes": (3 * weights + 3 * acts) * g["expert_layers"],
    }
