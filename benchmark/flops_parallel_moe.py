"""Operations and bytes from shapes for a model whose layers are PARALLEL
blocks (one norm feeds attention and the expert layer) of grouped-query
attention over the causal triangle or over a window of it, by a layer
pattern, with an expert layer on one chip's share AND shared experts held
here in every layer (Command A+): ``flops_window_moe``'s parts plus the
shared experts, which that module does not count.  Plain numbers in, counts
out: no JAX, no program code.

``model`` is the ``model`` group of a configuration file (the program's
``TransformerConfig`` fields).  A model that is not a parallel block
(``parallel_block`` absent or false) is not this module's: every function
raises ``KeyError`` for it, which the readers take as nothing to read; so
does a model without windowed layers (``flops_window_moe`` raises).

Conventions: the sibling's (6 x the matmul weights a token meets;
recomputation never counted; scores and values on the LIVE pairs).  The
shared experts HELD here are ``shared_experts_held`` (absent or 0: all
``num_shared_experts``) SwiGLU experts of the routed experts' width, three
matrices each, which every token meets; their average's scale is
elementwise.  The norm, the rotation and the residual add are elementwise.
"""

from __future__ import annotations

from typing import Dict, Mapping

from benchmark import flops_window_moe
from benchmark.flops_window_moe import (  # noqa: F401
    band_flash_cost,
    full_flash_cost,
    held_expert_matmul_cost,
    layer_counts,
    live_pairs,
)


def _parallel(model: Mapping) -> None:
    if not model["parallel_block"]:
        raise KeyError("parallel_block is false: not a parallel block")


def shared_experts_here(model: Mapping) -> int:
    """Shared experts whose weights this chip holds."""
    _parallel(model)
    return int(
        model.get("shared_experts_held") or model["num_shared_experts"]
    )


def shared_params(model: Mapping) -> int:
    """The matmul weights of a layer's shared experts held here."""
    width = int(model.get("moe_d_ff") or model["d_ff"])
    return shared_experts_here(model) * 3 * int(model["d_model"]) * width


def flops_per_token_by_part(model: Mapping, seq_len: int) -> Dict[str, float]:
    """Forward + backward model FLOPs of one token at ``seq_len``, by part
    (the parts sum to ``model_flops_per_token``)."""
    _parallel(model)
    parts = flops_window_moe.flops_per_token_by_part(model, seq_len)
    layers = sum(layer_counts(model).values())
    parts["shared_here"] = 6.0 * layers * shared_params(model)
    return parts


def model_flops_per_token(model: Mapping, seq_len: int) -> float:
    return sum(flops_per_token_by_part(model, seq_len).values())
