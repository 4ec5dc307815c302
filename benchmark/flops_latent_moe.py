"""Operations and bytes from shapes for a model of the DeepSeek-V3 family
on one chip's share of its experts: the benchmark's own arithmetic, beside
``flops.py`` and ``flops_by_kind.py`` (neither describes this model: one
head size for q, k and v, every routed pair computed here, one kind of
MLP, one head).  Plain numbers in, counts out: no JAX, no program code.
What ``flops.py`` and its readers should fold in of this is PERF.md §7,
item (6).

``model`` is the ``model`` group of a configuration file (the program's
``TransformerConfig`` fields).  Layers, counted once here:

* attention layers: ``num_layers + mtp_depth`` (every layer has latent
  attention, the MTP module's too);
* dense layers: ``first_k_dense``; expert layers: the rest of
  ``num_layers`` and the MTP module's.

Conventions, beyond ``flops.py``'s (6 x the matmul weights a token meets;
attention at the full square in model FLOPs, at the causal half in a
kernel's roofline; recomputation never counted):

* Latent attention's matmul weights are its five projections: ``q_a``
  (d x q_lora), ``q_b`` (q_lora x H (nope + rope)), ``kv_a`` (d x (kv_lora
  + rope)), ``kv_b`` (kv_lora x H (nope + v)), ``wo`` (H v x d).  Scores
  contract over ``nope + rope`` (192), values over ``v_head_dim`` (128).
* An expert layer's routed part counts the pairs routed HERE: of the
  ``top_k`` a token chooses, the expected ``top_k x held / total`` (the
  router is near uniform at seeded weights; the cell's ``moe_pairs_here``
  says how near).  ``flops.expert_matmul_cost`` would count all ``top_k``,
  eight times too many at an eighth of the experts.  The shared expert is
  one expert every token meets; the router is d x num_experts.
* Two heads (the main one and the MTP module's, one shared matrix met
  twice) and the module's ``eh_proj`` (2d x d).
"""

from __future__ import annotations

from typing import Dict, Mapping


def _sizes(model: Mapping) -> Dict[str, int]:
    layers = int(model["num_layers"])
    dense = int(model.get("first_k_dense") or 0)
    mtp = int(model.get("mtp_depth") or 0)
    total = int(model["num_experts"])
    return {
        "d": int(model["d_model"]),
        "h": int(model["num_heads"]),
        "q_lora": int(model["q_lora_rank"]),
        "kv_lora": int(model["kv_lora_rank"]),
        "nope": int(model["qk_nope_head_dim"]),
        "rope": int(model["qk_rope_head_dim"]),
        "v": int(model["v_head_dim"]),
        "d_ff": int(model["d_ff"]),
        "moe_d_ff": int(model.get("moe_d_ff") or model["d_ff"]),
        "total": total,
        "held": int(model.get("experts_held") or total),
        "top_k": int(model["top_k"]),
        "shared": int(model.get("num_shared_experts") or 0),
        "vocab": int(model["vocab_size"]),
        "mtp": mtp,
        "attn_layers": layers + mtp,
        "dense_layers": dense,
        "expert_layers": layers - dense + mtp,
    }


def latent_projection_params(model: Mapping) -> int:
    """Matmul weights of ONE layer's five latent-attention projections."""
    g = _sizes(model)
    qk = g["nope"] + g["rope"]
    return (
        g["d"] * g["q_lora"] + g["q_lora"] * g["h"] * qk
        + g["d"] * (g["kv_lora"] + g["rope"])
        + g["kv_lora"] * g["h"] * (g["nope"] + g["v"])
        + g["h"] * g["v"] * g["d"]
    )


def pairs_here_per_token(model: Mapping) -> float:
    """Routed (token, expert) pairs a token brings to THIS chip, expected."""
    g = _sizes(model)
    return g["top_k"] * g["held"] / g["total"]


def flops_per_token_by_part(model: Mapping, seq_len: int) -> Dict[str, float]:
    """Forward + backward model FLOPs of one token at ``seq_len``, by part
    (the parts sum to ``model_flops_per_token``)."""
    g = _sizes(model)
    expert = 3 * g["d"] * g["moe_d_ff"]
    return {
        "latent_projections": 6.0 * g["attn_layers"]
        * latent_projection_params(model),
        # scores over nope + rope and values over v, two FLOPs a
        # multiply-add, the full square, three passes
        "attention": 6.0 * g["attn_layers"] * g["h"] * seq_len
        * (g["nope"] + g["rope"] + g["v"]),
        "dense_mlp": 6.0 * g["dense_layers"] * 3 * g["d"] * g["d_ff"],
        "shared_experts": 6.0 * g["expert_layers"] * g["shared"] * expert,
        "routed_here": 6.0 * g["expert_layers"]
        * pairs_here_per_token(model) * expert,
        "router": 6.0 * g["expert_layers"] * g["d"] * g["total"],
        "heads": 6.0 * (1 + g["mtp"]) * g["vocab"] * g["d"],
        "eh_proj": 6.0 * g["mtp"] * 2 * g["d"] * g["d"],
    }


def model_flops_per_token(model: Mapping, seq_len: int) -> float:
    return sum(flops_per_token_by_part(model, seq_len).values())


def latent_flash_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """FLOPs and HBM bytes the flash kernels of ONE training step need
    (forward + backward, every attention layer, ``sequences`` on this
    chip), causal.

    Forward: QK^T (192 wide) and PV (128).  Backward (the kernel keeps no
    probabilities): QK^T again (192), dV (128), dP (128), dQ and dK (192
    each).  Seven matmuls of 2 x S x S x width per head and sequence,
    halved by the causal mask.  Bytes at 2 (bf16): q, k (192), v (128) in
    and o (128) out of the forward; q, k, v, o, do in and dq, dk, dv out of
    the backward; the log-sum-exp rows at 4 bytes, once each way.
    """
    g = _sizes(model)
    qk, v = g["nope"] + g["rope"], g["v"]
    square = 2.0 * seq_len * seq_len * g["h"] * sequences
    flops = square * (4 * qk + 3 * v) * 0.5 * g["attn_layers"]
    rows = 2.0 * sequences * seq_len * g["h"]           # bf16 bytes a column
    lse = 4.0 * sequences * seq_len * g["h"]
    fwd = rows * (2 * qk + 2 * v) + lse
    bwd = rows * (4 * qk + 4 * v) + lse
    return {"flops": flops, "bytes": (fwd + bwd) * g["attn_layers"]}


def held_expert_matmul_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """FLOPs and HBM bytes the grouped GEMMs of ONE training step need for
    the pairs routed HERE (expected): three matrices of 2 x d x moe_d_ff a
    pair, three times (forward, d-input, d-weight), every expert layer.
    Padding and recomputation are not needed, so not counted.  Bytes: the
    HELD experts' weights read forward and backward and their gradients
    written once (bf16), plus the routed rows in and out."""
    g = _sizes(model)
    routed = sequences * seq_len * pairs_here_per_token(model)
    flops = 3 * 3 * 2.0 * routed * g["d"] * g["moe_d_ff"] * g["expert_layers"]
    weights = 2.0 * g["held"] * 3 * g["d"] * g["moe_d_ff"]
    acts = 2.0 * routed * (2 * g["d"] + 3 * g["moe_d_ff"])
    return {
        "flops": flops,
        "bytes": (3 * weights + 3 * acts) * g["expert_layers"],
    }
