"""Operations and bytes from shapes for a model whose mixers are Kimi Delta
Attention (a delta rule with a decay per channel) or gated latent attention
by a layer pattern, behind a dense prefix, with an expert layer on one
chip's share in every layer of the trunk (Ling-3.0-flash): what no function
of ``flops_latent_moe.py`` or ``flops_by_kind.py`` counts.  The pairs
routed here, the held experts' grouped GEMMs and the flash kernels' cost a
latent layer are ``flops_latent_moe``'s, imported and not copied.  Plain
numbers in, counts out: no JAX, no program code.

``model`` is the ``model`` group of a configuration file (the program's
``TransformerConfig`` fields).  Layers are counted from ``layer_pattern``
(one period of the trunk) and ``first_k_dense`` (the prefix's mixers
continue the pattern backwards, its MLPs are dense): :func:`layer_counts`.

Conventions, beyond ``flops_latent_moe``'s (6 x the matmul weights a token
meets; recomputation never counted):

* A KDA layer's matmul weights are its q, k, v, decay (``W_f``, full rank
  ``[d, H dk]``), beta (``[d, H]``), output-gate (``[d, H dv]``) and
  output projections.  The convolution, the norms, the safe gate's
  sigmoid and the decay's exponentials are not matmuls and not counted.
* The rule is counted in its chunked form at chunk 64, WHATEVER chunk and
  sub-chunk the program runs, as ``flops_by_kind`` counts the scalar rule:
  per chunk and head ``K K^T`` and ``Q K^T`` under the per-channel decay
  (2 C^2 dk each), the unit-lower-triangular solve (C^2 (dk + dv): a
  substitution, the least it takes), ``W S``, ``(Q exp G) S`` and the
  state's update (2 C dk dv each) and the masked ``Q K^T`` times the
  chunk's writes (2 C^2 dv); the backward at twice the forward.  An
  inverse built by products, bands recomputed per sub-chunk, a second
  forward under remat all read as waste.
* The rule's bytes are the FEWEST a correct program under the stated
  precision moves: q, k, v (bf16), g (float32, a channel), beta (float32)
  in and o (bf16) out forward; those and do in and the five gradients out
  backward.  No chunk-boundary state, no transpose to heads-first, no
  second forward.
* Latent attention without a q latent: ``q`` (d x H (nope + rope)),
  ``kv_a``, ``kv_b``, ``wo`` and the head-wise gate (d x H).  Its scores
  and values are counted on the CAUSAL half, in the step's FLOPs as in the
  kernels' roofline (the flash kernels skip the dead blocks).
"""

from __future__ import annotations

from typing import Dict, Mapping

from benchmark import flops_latent_moe
from benchmark.flops_latent_moe import pairs_here_per_token

LINEAR = "linear_attention"
FULL = "full_attention"
RULE_CHUNK = 64


def layer_counts(model: Mapping) -> Dict[str, int]:
    """Layers by mixer (``linear_attention`` / ``full_attention``) and by
    second branch (``dense`` / ``experts``)."""
    layers = int(model["num_layers"])
    dense = int(model.get("first_k_dense") or 0)
    pattern = list(model["layer_pattern"])
    kinds = [
        pattern[(i - dense) % len(pattern)] for i in range(layers)
    ]
    return {
        LINEAR: kinds.count(LINEAR), FULL: kinds.count(FULL),
        "dense": dense, "experts": layers - dense,
    }


def _kda(model: Mapping) -> Dict[str, int]:
    return {
        "d": int(model["d_model"]),
        "h": int(model.get("linear_num_heads") or model["num_heads"]),
        "dk": int(model["linear_key_head_dim"]),
        "dv": int(model["linear_value_head_dim"]),
    }


def kda_projection_params(model: Mapping) -> int:
    g = _kda(model)
    d, h, dk, dv = g["d"], g["h"], g["dk"], g["dv"]
    return 3 * d * h * dk + 3 * d * h * dv + d * h


def latent_projection_params(model: Mapping) -> int:
    d, h = int(model["d_model"]), int(model["num_heads"])
    nope, rope = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    v, rank = int(model["v_head_dim"]), int(model["kv_lora_rank"])
    gate = d * h if model.get("attention_gate") else 0
    return (
        d * h * (nope + rope) + d * (rank + rope) + rank * h * (nope + v)
        + h * v * d + gate
    )


def kda_rule_flops_per_token(model: Mapping) -> float:
    """Forward FLOPs of the chunked rule for ONE token of ONE layer, all
    heads (a chunk's count over its ``RULE_CHUNK`` tokens)."""
    g = _kda(model)
    c, dk, dv = RULE_CHUNK, g["dk"], g["dv"]
    per_chunk = (
        4 * c * c * dk + c * c * (dk + dv) + 6 * c * dk * dv + 2 * c * c * dv
    )
    return g["h"] * per_chunk / c


def flops_per_token_by_part(model: Mapping, seq_len: int) -> Dict[str, float]:
    """Forward + backward model FLOPs of one token at ``seq_len``, by part
    (the parts sum to ``model_flops_per_token``)."""
    n = layer_counts(model)
    d = int(model["d_model"])
    h = int(model["num_heads"])
    qk = int(model["qk_nope_head_dim"]) + int(model["qk_rope_head_dim"])
    expert = 3 * d * int(model.get("moe_d_ff") or model["d_ff"])
    shared = 3 * d * int(
        model.get("shared_expert_d_ff")
        or int(model.get("num_shared_experts") or 0)
        * int(model.get("moe_d_ff") or model["d_ff"])
    )
    return {
        "kda_projections": 6.0 * n[LINEAR] * kda_projection_params(model),
        "kda_rule": 3.0 * n[LINEAR] * kda_rule_flops_per_token(model),
        "latent_projections": 6.0 * n[FULL] * latent_projection_params(model),
        # scores over nope + rope and values over v, two FLOPs a
        # multiply-add, three passes, the causal half
        "attention": 0.5 * 6.0 * n[FULL] * h * seq_len
        * (qk + int(model["v_head_dim"])),
        "dense_mlp": 6.0 * n["dense"] * 3 * d * int(model["d_ff"]),
        "shared_experts": 6.0 * n["experts"] * shared,
        "routed_here": 6.0 * n["experts"] * pairs_here_per_token(model)
        * expert,
        "router": 6.0 * n["experts"] * d * int(model["num_experts"]),
        "heads": 6.0 * int(model["vocab_size"]) * d,
    }


def model_flops_per_token(model: Mapping, seq_len: int) -> float:
    return sum(flops_per_token_by_part(model, seq_len).values())


def kda_cost(model: Mapping, seq_len: int, sequences: int) -> Dict[str, float]:
    """FLOPs and HBM bytes the rule's calls of ONE training step need
    (forward + backward, every KDA layer, ``sequences`` on this chip): the
    module's text has what is counted."""
    layers = layer_counts(model)[LINEAR]
    g = _kda(model)
    tokens = float(sequences) * seq_len
    qkv = 2.0 * (2 * g["dk"] + g["dv"])       # bf16 q, k, v of a head
    out = 2.0 * g["dv"]                       # bf16 o (or do) of a head
    gates = 4.0 * (g["dk"] + 1)               # float32 g (a channel), beta
    fwd_bytes = qkv + gates + out
    bwd_bytes = (qkv + gates + out) + (qkv + gates)
    return {
        "flops": 3.0 * tokens * kda_rule_flops_per_token(model) * layers,
        "bytes": tokens * g["h"] * (fwd_bytes + bwd_bytes) * layers,
    }


def latent_flash_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """``flops_latent_moe.latent_flash_cost`` (causal, 192 / 128, forward
    and backward) over the latent layers this model has, and no other."""
    return flops_latent_moe.latent_flash_cost(
        dict(model, num_layers=layer_counts(model)[FULL], mtp_depth=0),
        seq_len, sequences,
    )
