"""Operations and bytes from shapes for a model of the DeepSeek-V3 family
whose latent attention runs over a CHOSEN set of keys (DeepSeek-V3.2's DSA,
GLM-5.2), on one chip's share of its experts and heads:
``flops_latent_moe``'s parts with the attention's pairs the chosen ones,
plus the indexers.  Plain numbers in, counts out: no JAX, no program code.

``model`` is the ``model`` group of a configuration file (the program's
``TransformerConfig`` fields).  A model without an indexer (no
``index_topk``) is not this module's: every function raises ``KeyError``
for it, which the readers take as nothing to read.

Conventions, beyond the sibling's (6 x the matmul weights a token meets;
recomputation never counted):

* Attention's scores and values are counted on the CHOSEN pairs, exactly:
  query ``t`` of a sequence keeps ``min(t + 1, index_topk)`` keys, whatever
  form computes them (a masked kernel over the whole causal triangle does
  4.3 times that at 16,384 tokens: the gap is the kernel's roofline share
  to win back, not model work).  Every attention layer, choosing or
  reusing, and the MTP module's.
* An indexer (the layers that CHOOSE: ``index_attention`` in the dense
  prefix, the pattern and the MTP module) is its three projections'
  weights (``wq_b`` q_lora x J x D, ``wk`` d x D, ``weights_proj`` d x J)
  and its scores over the CAUSAL TRIANGLE, J x D wide a pair, forward and
  backward (the indexer trains): three passes, as a matmul's.
* The KL term recomputes the indexer's scores and the main attention's
  probabilities; that is not model work and is not counted.  The selection
  is compares and counts: no FLOPs, no floor.
"""

from __future__ import annotations

from typing import Dict, Mapping

from benchmark import flops_latent_moe
from benchmark.flops_latent_moe import held_expert_matmul_cost  # noqa: F401

INDEX = "index_attention"


def choosing_layers(model: Mapping) -> int:
    """Layers that hold an indexer: the trunk's ``index_attention``
    layers (the dense prefix continues the pattern backwards) and the MTP
    module's."""
    int(model["index_topk"])                 # no indexer: not this module's
    pattern = list(model["layer_pattern"])
    dense = int(model.get("first_k_dense") or 0)
    layers = int(model["num_layers"])
    kinds = [pattern[(i - dense) % len(pattern)] for i in range(layers)]
    mtp = int(model.get("mtp_depth") or 0)
    return kinds.count(INDEX) + mtp * (model.get("mtp_layer_kind") == INDEX)


def chosen_pairs(model: Mapping, seq_len: int) -> int:
    """(query, key) pairs a sequence's choice keeps in ONE layer."""
    topk = int(model["index_topk"])
    full = min(topk, seq_len)
    return full * (full + 1) // 2 + (seq_len - full) * topk


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def indexer_params(model: Mapping) -> int:
    """Matmul weights of ONE indexer."""
    heads, dim = int(model["index_n_heads"]), int(model["index_head_dim"])
    d = int(model["d_model"])
    return int(model["q_lora_rank"]) * heads * dim + d * dim + d * heads


def flops_per_token_by_part(model: Mapping, seq_len: int) -> Dict[str, float]:
    """Forward + backward model FLOPs of one token at ``seq_len``, by part
    (the parts sum to ``model_flops_per_token``)."""
    parts = flops_latent_moe.flops_per_token_by_part(model, seq_len)
    g = flops_latent_moe._sizes(model)
    choosing = choosing_layers(model)
    # scores over nope + rope and values over v, two FLOPs a multiply-add,
    # three passes, on the chosen pairs of a sequence over its tokens
    parts["attention"] = (
        6.0 * g["attn_layers"] * g["h"] * (g["nope"] + g["rope"] + g["v"])
        * chosen_pairs(model, seq_len) / seq_len
    )
    parts["indexer_projections"] = 6.0 * choosing * indexer_params(model)
    parts["index_scores"] = (
        6.0 * choosing * int(model["index_n_heads"])
        * int(model["index_head_dim"]) * causal_pairs(seq_len) / seq_len
    )
    return parts


def model_flops_per_token(model: Mapping, seq_len: int) -> float:
    return sum(flops_per_token_by_part(model, seq_len).values())


def index_score_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """FLOPs and HBM bytes the indexers' scores of ONE training step need
    (forward and backward, every choosing layer) over the causal triangle:
    ``2 x J x D`` a pair and pass.  Bytes at 2: q (J x D a row), k (D), the
    weights (J) in each way, their gradients out."""
    heads, dim = int(model["index_n_heads"]), int(model["index_head_dim"])
    choosing = choosing_layers(model)
    flops = (
        3 * 2.0 * heads * dim * causal_pairs(seq_len) * sequences * choosing
    )
    rows = 2.0 * sequences * seq_len * (heads * dim + dim + heads)
    return {"flops": flops, "bytes": 3 * rows * choosing}


def sparse_flash_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """FLOPs and HBM bytes attention over the choice needs in ONE training
    step (forward + backward, every attention layer): the sibling's seven
    matmuls (``4 x qk + 3 x v`` wide) on the CHOSEN pairs, and its rows."""
    g = flops_latent_moe._sizes(model)
    qk, v = g["nope"] + g["rope"], g["v"]
    pairs = chosen_pairs(model, seq_len) * sequences * g["h"]
    flops = 2.0 * pairs * (4 * qk + 3 * v) * g["attn_layers"]
    rows = 2.0 * sequences * seq_len * g["h"]
    lse = 4.0 * sequences * seq_len * g["h"]
    fwd = rows * (2 * qk + 2 * v) + lse
    bwd = rows * (4 * qk + 4 * v) + lse
    return {"flops": flops, "bytes": (fwd + bwd) * g["attn_layers"]}
