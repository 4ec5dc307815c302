"""Operations and bytes from shapes for a model whose mixers are gated short
convolutions or grouped-query attention under a per-head QK norm by a layer
pattern, behind a dense prefix, with an expert layer on one chip's share
and NO shared expert in every layer of the trunk (LFM2-8B-A1B): what no
other module here counts (``flops_latent_moe`` and ``flops_kda_latent_moe``
need latent attention's sizes, ``flops_ssm_moe`` a state-space mixer's).
Plain numbers in, counts out: no JAX, no program code.

``model`` is the ``model`` group of a configuration file (the program's
``TransformerConfig`` fields).  Layers are counted from ``layer_pattern``
(one period of the trunk) and ``first_k_dense`` (the prefix's mixers
continue the pattern backwards, its MLPs are dense): :func:`layer_counts`.
A model without a ``conv`` layer in its pattern, or without a pattern, is
not this module's: every function raises ``KeyError`` for it, which the
readers take as nothing to read.

Conventions (the siblings': 6 x the matmul weights a token meets;
recomputation never counted):

* A conv mixer's matmul weights are its two projections, ``[d, 3d]`` and
  ``[d, d]``.  Its core ``C * conv(B * z)`` is elementwise (``2 K + 2``
  FLOPs a channel forward) and is NOT in the step's model FLOPs; its cost
  is bytes (:func:`conv_core_cost`).
* Attention's matmul weights are q (d x H hd), k and v (d x H_kv hd each)
  and the output projection; ``hd = head_dim or d / H``.  Scores and values
  are counted on the CAUSAL half, in the step's FLOPs as in the kernels'
  roofline (the flash kernels skip the dead blocks).  The per-head norms
  and the rotation are elementwise.
* An expert layer's routed part counts the pairs routed HERE: of the
  ``top_k`` a token chooses, the expected ``top_k x held / total``; three
  matrices an expert (SwiGLU); the router is d x num_experts; there is no
  shared expert.
* The core's bytes are the FEWEST a correct program under the stated
  precision moves: the projection's three ranges in (bf16) and the product
  out forward; those and the cotangent in, the three cotangents out
  backward.  No second forward under remat, no statistics' pass.
"""

from __future__ import annotations

from typing import Dict, Mapping

CONV = "conv"
FULL = "full_attention"


def layer_counts(model: Mapping) -> Dict[str, int]:
    """Layers by mixer (``conv`` / ``full_attention``) and by second branch
    (``dense`` / ``experts``)."""
    layers = int(model["num_layers"])
    dense = int(model.get("first_k_dense") or 0)
    pattern = list(model["layer_pattern"])
    if CONV not in pattern:
        raise KeyError("layer_pattern has no conv layer")
    kinds = [pattern[(i - dense) % len(pattern)] for i in range(layers)]
    return {
        CONV: kinds.count(CONV), FULL: kinds.count(FULL),
        "dense": dense, "experts": layers - dense,
    }


def _sizes(model: Mapping) -> Dict[str, int]:
    d, h = int(model["d_model"]), int(model["num_heads"])
    total = int(model["num_experts"])
    return {
        "d": d, "h": h,
        "h_kv": int(model.get("num_kv_heads") or h),
        "hd": int(model.get("head_dim") or d // h),
        "taps": int(model.get("conv_kernel") or 3),
        "d_ff": int(model["d_ff"]),
        "moe_d_ff": int(model.get("moe_d_ff") or model["d_ff"]),
        "total": total,
        "held": int(model.get("experts_held") or total),
        "top_k": int(model["top_k"]),
        "vocab": int(model["vocab_size"]),
    }


def conv_projection_params(model: Mapping) -> int:
    d = int(model["d_model"])
    return 3 * d * d + d * d


def attention_projection_params(model: Mapping) -> int:
    g = _sizes(model)
    return 2 * g["d"] * g["h"] * g["hd"] + 2 * g["d"] * g["h_kv"] * g["hd"]


def pairs_here_per_token(model: Mapping) -> float:
    """Routed (token, expert) pairs a token brings to THIS chip, expected."""
    g = _sizes(model)
    return g["top_k"] * g["held"] / g["total"]


def flops_per_token_by_part(model: Mapping, seq_len: int) -> Dict[str, float]:
    """Forward + backward model FLOPs of one token at ``seq_len``, by part
    (the parts sum to ``model_flops_per_token``)."""
    n, g = layer_counts(model), _sizes(model)
    expert = 3 * g["d"] * g["moe_d_ff"]
    return {
        "conv_projections": 6.0 * n[CONV] * conv_projection_params(model),
        "attention_projections": 6.0 * n[FULL]
        * attention_projection_params(model),
        # scores and values over hd each, two FLOPs a multiply-add, three
        # passes, the causal half
        "attention": 0.5 * 6.0 * n[FULL] * g["h"] * seq_len * 2 * g["hd"],
        "dense_mlp": 6.0 * n["dense"] * 3 * g["d"] * g["d_ff"],
        "routed_here": 6.0 * n["experts"] * pairs_here_per_token(model)
        * expert,
        "router": 6.0 * n["experts"] * g["d"] * g["total"],
        "head": 6.0 * g["vocab"] * g["d"],
    }


def model_flops_per_token(model: Mapping, seq_len: int) -> float:
    return sum(flops_per_token_by_part(model, seq_len).values())


def conv_core_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """FLOPs and HBM bytes the cores ``C * conv(B * z)`` of ONE training
    step need (forward + backward, every conv layer, ``sequences`` on this
    chip).  Forward a channel: one product, ``K`` multiply-adds, one
    product; backward twice that and ``K`` more for the taps' gradient.
    Bytes at 2 (bf16): 3d in and d out forward; 3d and d in, 3d out
    backward.  The bound is the bytes by three orders of magnitude."""
    n, g = layer_counts(model)[CONV], _sizes(model)
    tokens = float(sequences) * seq_len
    per_channel = 3 * (2 * g["taps"] + 2) + 2 * g["taps"]
    return {
        "flops": tokens * g["d"] * per_channel * n,
        "bytes": tokens * 2.0 * g["d"] * ((3 + 1) + (3 + 1 + 3)) * n,
    }


def gqa_flash_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """FLOPs and HBM bytes the flash kernels of ONE training step need
    (forward + backward, the ATTENTION layers only), causal, ``num_heads``
    query heads over ``num_kv_heads`` key/value heads of ``hd``.

    Forward: QK^T and PV.  Backward (the kernel keeps no probabilities):
    QK^T again, dV, dP, dQ and dK.  Seven matmuls of 2 x S x S x hd per
    query head and sequence, halved by the causal mask.  Bytes at 2 (bf16):
    q in and o out (H heads), k and v in (H_kv heads) forward; q, o, do in
    and dq out (H), k, v in and dk, dv out (H_kv) backward; the log-sum-exp
    rows at 4 bytes a query head, once each way."""
    layers, g = layer_counts(model)[FULL], _sizes(model)
    square = 2.0 * seq_len * seq_len * g["h"] * sequences
    flops = square * 7 * g["hd"] * 0.5 * layers
    row = 2.0 * sequences * seq_len * g["hd"]           # bf16 bytes a head
    lse = 4.0 * sequences * seq_len * g["h"]
    fwd = row * (2 * g["h"] + 2 * g["h_kv"]) + lse
    bwd = row * (4 * g["h"] + 4 * g["h_kv"]) + lse
    return {"flops": flops, "bytes": (fwd + bwd) * layers}


def held_expert_matmul_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """FLOPs and HBM bytes the grouped GEMMs of ONE training step need for
    the pairs routed HERE (expected): three matrices of 2 x d x moe_d_ff a
    pair, three times (forward, d-input, d-weight), every expert layer.
    Padding and recomputation are not needed, so not counted.  Bytes: the
    HELD experts' weights read forward and backward and their gradients
    written once (bf16), plus the routed rows in (d), the up and gate
    products out and their product in (3 moe_d_ff) and the rows out (d)."""
    layers, g = layer_counts(model)["experts"], _sizes(model)
    routed = sequences * seq_len * pairs_here_per_token(model)
    flops = 3 * 3 * 2.0 * routed * g["d"] * g["moe_d_ff"] * layers
    weights = 2.0 * g["held"] * 3 * g["d"] * g["moe_d_ff"]
    acts = 2.0 * routed * (2 * g["d"] + 3 * g["moe_d_ff"])
    return {"flops": flops, "bytes": (3 * weights + 3 * acts) * layers}
