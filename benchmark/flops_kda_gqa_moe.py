"""Operations and bytes from shapes for a model whose mixers are Kimi Delta
Attention with LOW-RANK gate projections or grouped-query softmax attention
under an output gate, by a layer pattern, with an expert layer on one chip's
share in every layer (Solar-Open2): what no function of
``flops_kda_latent_moe.py`` or ``flops_latent_moe.py`` counts.  The rule's
cost is ``flops_kda_latent_moe``'s (``kda_rule_flops_per_token``,
``kda_cost``: the chunk mathematics at chunk 64 WHATEVER form the program
runs, so both KDA cells stand on one yardstick and the exact form's extra
products read as what they are); the pairs routed here are
``flops_latent_moe``'s.  Imported, not copied.  Plain numbers in, counts
out: no JAX, no program code.

``model`` is the ``model`` group of a configuration file (the program's
``TransformerConfig`` fields).  Layers are counted from ``layer_pattern``
(``flops_kda_latent_moe.layer_counts``).

Conventions, beyond ``flops_kda_latent_moe``'s (6 x the matmul weights a
token meets; recomputation never counted):

* A KDA layer's matmul weights are its q, k, v and output projections,
  beta (``[d, H]``) and the decay's and the output gate's projections AT
  THEIR RANK: ``d r + r H dk`` and ``d r + r H dv`` with ``r`` =
  ``linear_gate_rank`` (0: full rank, ``d H dk`` and ``d H dv``).
* A GQA layer's matmul weights are ``wq`` (d x H hd), ``wk`` and ``wv``
  (d x H_kv hd each), ``wo`` and the output gate (``elementwise``: d x H hd;
  ``head_wise``: d x H).  Its scores and values are counted on the CAUSAL
  half, in the step's FLOPs as in the kernels' roofline (the flash kernels
  skip the dead blocks).
* The flash kernels' bytes are the fewest a grouped-query kernel moves: a
  key/value head is read once for its group of query heads.
"""

from __future__ import annotations

from typing import Dict, Mapping

from benchmark.flops_kda_latent_moe import (
    FULL, LINEAR, kda_cost, kda_rule_flops_per_token, layer_counts,
)
from benchmark.flops_latent_moe import pairs_here_per_token

__all__ = [
    "flops_per_token_by_part", "gqa_flash_cost", "kda_cost",
    "model_flops_per_token",
]


def _gqa(model: Mapping) -> Dict[str, int]:
    h = int(model["num_heads"])
    return {
        "d": int(model["d_model"]), "h": h,
        "h_kv": int(model.get("num_kv_heads") or h),
        "hd": int(model.get("head_dim") or int(model["d_model"]) // h),
    }


def kda_projection_params(model: Mapping) -> int:
    d = int(model["d_model"])
    h = int(model.get("linear_num_heads") or model["num_heads"])
    dk = int(model["linear_key_head_dim"])
    dv = int(model["linear_value_head_dim"])
    r = int(model.get("linear_gate_rank") or 0)
    gates = d * r + r * h * dk + d * r + r * h * dv if r else (
        d * h * dk + d * h * dv
    )
    return 2 * d * h * dk + 2 * d * h * dv + gates + d * h


def gqa_projection_params(model: Mapping) -> int:
    g = _gqa(model)
    gate = {
        "": 0, "head_wise": g["d"] * g["h"],
        "elementwise": g["d"] * g["h"] * g["hd"],
    }[model.get("attention_gate") or ""]
    return (
        2 * g["d"] * g["h"] * g["hd"] + 2 * g["d"] * g["h_kv"] * g["hd"]
        + gate
    )


def flops_per_token_by_part(model: Mapping, seq_len: int) -> Dict[str, float]:
    """Forward + backward model FLOPs of one token at ``seq_len``, by part
    (the parts sum to ``model_flops_per_token``)."""
    n = layer_counts(model)
    g = _gqa(model)
    d = g["d"]
    expert = 3 * d * int(model.get("moe_d_ff") or model["d_ff"])
    shared = int(model.get("num_shared_experts") or 0) * expert
    return {
        "kda_projections": 6.0 * n[LINEAR] * kda_projection_params(model),
        "kda_rule": 3.0 * n[LINEAR] * kda_rule_flops_per_token(model),
        "gqa_projections": 6.0 * n[FULL] * gqa_projection_params(model),
        # scores and values over hd each, two FLOPs a multiply-add, three
        # passes, the causal half
        "attention": 0.5 * 6.0 * n[FULL] * g["h"] * seq_len * 2 * g["hd"],
        "shared_experts": 6.0 * n["experts"] * shared,
        "routed_here": 6.0 * n["experts"] * pairs_here_per_token(model)
        * expert,
        "router": 6.0 * n["experts"] * d * int(model["num_experts"]),
        "heads": 6.0 * int(model["vocab_size"]) * d,
    }


def model_flops_per_token(model: Mapping, seq_len: int) -> float:
    return sum(flops_per_token_by_part(model, seq_len).values())


def gqa_flash_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """FLOPs and HBM bytes the flash kernels of ONE training step need
    (forward + backward, every GQA layer, ``sequences`` on this chip),
    causal.

    Forward: QK^T and PV.  Backward (the kernel keeps no probabilities):
    QK^T again, dV, dP, dQ and dK.  Seven matmuls of 2 x S x S x hd per
    query head and sequence, halved by the causal mask.  Bytes at 2
    (bf16): q in and o out of the forward a query head, k and v a
    key/value head; q, o, do in and dq out of the backward a query head,
    k, v in and dk, dv out a key/value head; the log-sum-exp rows at 4
    bytes, once each way."""
    g = _gqa(model)
    layers = layer_counts(model)[FULL]
    square = 2.0 * seq_len * seq_len * g["h"] * sequences
    flops = square * 7 * g["hd"] * 0.5 * layers
    row = 2.0 * sequences * seq_len * g["hd"]       # bf16 bytes a head's rows
    lse = 4.0 * sequences * seq_len * g["h"]
    fwd = row * (2 * g["h"] + 2 * g["h_kv"]) + lse
    bwd = row * (4 * g["h"] + 4 * g["h_kv"]) + lse
    return {"flops": flops, "bytes": (fwd + bwd) * layers}
