"""Operations and bytes from shapes for a model whose layers are
grouped-query attention over the causal triangle or over a WINDOW of it, by
a layer pattern, with an expert layer on one chip's share and NO shared
expert in every layer (Mellum2-12B-A2.5B): what no other module here counts
(the siblings' attention is the causal half everywhere).  Plain numbers in,
counts out: no JAX, no program code.

``model`` is the ``model`` group of a configuration file (the program's
``TransformerConfig`` fields).  Layers are counted from ``layer_pattern``
(one period of the trunk): :func:`layer_counts`.  A model without a
``sliding_attention`` layer in its pattern, or without a pattern, is not
this module's: every function raises ``KeyError`` for it, which the readers
take as nothing to read.

Conventions (the siblings': 6 x the matmul weights a token meets;
recomputation never counted):

* Attention's matmul weights are q (d x H hd), k and v (d x H_kv hd each)
  and the output projection.  The rotation is elementwise.
* Scores and values are counted on the pairs that are LIVE, in the step's
  FLOPs as in the kernels' rooflines, the same work whatever tiles
  implement it: ``S (S + 1) / 2`` pairs a head and sequence under the
  causal mask, ``W S - W (W - 1) / 2`` under a window of ``W`` keys (``S >=
  W``; a row ``i < W`` sees ``i + 1`` keys, every other ``W``).
* An expert layer's routed part counts the pairs routed HERE: of the
  ``top_k`` a token chooses, the expected ``top_k x held / total``; three
  matrices an expert (SwiGLU); the router is d x num_experts.
"""

from __future__ import annotations

from typing import Dict, Mapping

# the same grouped-query projections and the same share of the routed pairs
from benchmark.flops_conv_moe import (  # noqa: F401
    attention_projection_params,
    pairs_here_per_token,
)

SLIDING = "sliding_attention"
FULL = "full_attention"


def layer_counts(model: Mapping) -> Dict[str, int]:
    """Layers by kind (``sliding_attention`` / ``full_attention``)."""
    pattern = list(model["layer_pattern"])
    if SLIDING not in pattern:
        raise KeyError("layer_pattern has no sliding_attention layer")
    periods = int(model["num_layers"]) // len(pattern)
    return {
        SLIDING: periods * pattern.count(SLIDING),
        FULL: periods * pattern.count(FULL),
    }


def _sizes(model: Mapping) -> Dict[str, int]:
    d, h = int(model["d_model"]), int(model["num_heads"])
    total = int(model["num_experts"])
    return {
        "d": d, "h": h,
        "h_kv": int(model.get("num_kv_heads") or h),
        "hd": int(model.get("head_dim") or d // h),
        "window": int(model["sliding_window"]),
        "moe_d_ff": int(model.get("moe_d_ff") or model["d_ff"]),
        "total": total,
        "held": int(model.get("experts_held") or total),
        "top_k": int(model["top_k"]),
        "vocab": int(model["vocab_size"]),
        "tied": bool(model.get("tie_embeddings", True)),
    }


def live_pairs(seq_len: int, window: int = 0) -> float:
    """(query, key) pairs one head of one sequence computes: the causal
    triangle, or the band of ``window`` keys inside it."""
    w = min(window, seq_len) if window else seq_len
    return float(w) * seq_len - w * (w - 1) / 2.0


def flops_per_token_by_part(model: Mapping, seq_len: int) -> Dict[str, float]:
    """Forward + backward model FLOPs of one token at ``seq_len``, by part
    (the parts sum to ``model_flops_per_token``)."""
    n, g = layer_counts(model), _sizes(model)
    expert = 3 * g["d"] * g["moe_d_ff"]
    layers = n[SLIDING] + n[FULL]
    # scores and values over hd each, two FLOPs a multiply-add, three
    # passes, the live pairs a token
    pair = 6.0 * g["h"] * 2 * g["hd"] / seq_len
    return {
        "attention_projections": 6.0 * layers
        * attention_projection_params(model),
        "full_attention": pair * n[FULL] * live_pairs(seq_len),
        "sliding_attention": pair * n[SLIDING]
        * live_pairs(seq_len, g["window"]),
        "routed_here": 6.0 * layers * pairs_here_per_token(model) * expert,
        "router": 6.0 * layers * g["d"] * g["total"],
        "head": 6.0 * g["vocab"] * g["d"],
    }


def model_flops_per_token(model: Mapping, seq_len: int) -> float:
    return sum(flops_per_token_by_part(model, seq_len).values())


def _flash_cost(model, seq_len, sequences, layers, pairs):
    """FLOPs and HBM bytes the flash kernels of ``layers`` layers of ONE
    training step need (forward + backward).  Forward: QK^T and PV.
    Backward (the kernel keeps no probabilities): QK^T again, dV, dP, dQ
    and dK.  Seven matmuls of 2 x hd a live pair and query head.  Bytes at 2
    (bf16): q in and o out (H heads), k and v in (H_kv heads) forward; q, o,
    do in and dq out (H), k, v in and dk, dv out (H_kv) backward; the
    log-sum-exp rows at 4 bytes a query head, once each way."""
    g = _sizes(model)
    flops = 7 * 2.0 * pairs * g["hd"] * g["h"] * sequences * layers
    row = 2.0 * sequences * seq_len * g["hd"]           # bf16 bytes a head
    lse = 4.0 * sequences * seq_len * g["h"]
    fwd = row * (2 * g["h"] + 2 * g["h_kv"]) + lse
    bwd = row * (4 * g["h"] + 4 * g["h_kv"]) + lse
    return {"flops": flops, "bytes": (fwd + bwd) * layers}


def band_flash_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """The SLIDING layers' flash kernels: the band's pairs, whatever blocks
    and grid a kernel walks them with."""
    window = _sizes(model)["window"]
    return _flash_cost(
        model, seq_len, sequences, layer_counts(model)[SLIDING],
        live_pairs(seq_len, window),
    )


def full_flash_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """The FULL layers' flash kernels: the causal triangle's pairs."""
    return _flash_cost(
        model, seq_len, sequences, layer_counts(model)[FULL],
        live_pairs(seq_len),
    )


def held_expert_matmul_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """``flops_conv_moe.held_expert_matmul_cost`` over THIS module's layers
    (the sibling counts its own kinds and raises for a pattern without
    them): three matrices of 2 x d x moe_d_ff a pair routed here, three
    times (forward, d-input, d-weight), every layer; the held experts'
    weights read twice and their gradients written once (bf16), the routed
    rows in (d), the up and gate products and their product (3 moe_d_ff)
    and the rows out (d)."""
    n, g = layer_counts(model), _sizes(model)
    layers = n[SLIDING] + n[FULL]
    routed = sequences * seq_len * pairs_here_per_token(model)
    flops = 3 * 3 * 2.0 * routed * g["d"] * g["moe_d_ff"] * layers
    weights = 2.0 * g["held"] * 3 * g["d"] * g["moe_d_ff"]
    acts = 2.0 * routed * (2 * g["d"] + 3 * g["moe_d_ff"])
    return {"flops": flops, "bytes": (3 * weights + 3 * acts) * layers}
