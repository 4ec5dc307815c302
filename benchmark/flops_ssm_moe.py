"""Operations and bytes from shapes for a model whose layers are ONE
residual branch each (a Mamba-2 state-space mixer, an attention, an expert
layer of ungated experts on one chip's share, a dense MLP): the benchmark's
own arithmetic, beside ``flops.py``, ``flops_by_kind.py`` and
``flops_latent_moe.py`` (none describes this model: every layer of theirs is
a mixer AND an MLP, their experts are gated, none has a state-space layer).
Plain numbers in, counts out: no JAX, no program code.

``model`` is the ``model`` group of a configuration file (the program's
``TransformerConfig`` fields).  Layers are counted from ``layer_pattern``
(one period of kinds, ``"ssm"``, ``"attention"``, ``"experts"``, ``"mlp"``)
repeated over ``num_layers``.

Conventions, beyond ``flops.py``'s (6 x the matmul weights a token meets;
recomputation never counted):

* A state-space layer's matmul weights are its two projections: ``in_proj``
  (d x (2 H P + 2 G N + H)) and ``out_proj`` (H P x d).  Its scan is counted
  in the CHUNKED form the layer trains with, four products a chunk of Q
  tokens: ``C B^T`` once a group (2 Q N a token and group), and a head's
  ``M X`` (2 Q P), ``C S^T`` and ``B^T X`` (2 N P each); forward, and twice
  that backward.  The token-by-token recurrence would need 4 N P a head and
  token and no chunk, but no matrix unit runs it.
* Attention's scores and values are counted on the CAUSAL HALF here, in
  model FLOPs too (ISSUE 37's reckoning; ``flops.py`` takes the full square
  there and the half only in a kernel's roofline): with 2 of 18 layers
  attention, the other half would be a tenth of the model's FLOPs that no
  kernel runs.  q and o are ``num_heads`` wide, k and v ``num_kv_heads``.
* An expert is TWO matrices (no gate).  An expert layer's routed part counts
  the pairs routed HERE: of the ``top_k`` a token chooses, the expected
  ``top_k x held / total``.  The shared expert has its OWN width
  (``shared_expert_d_ff``) and every token meets it; the router is
  d x num_experts.
"""

from __future__ import annotations

from typing import Dict, Mapping


def _sizes(model: Mapping) -> Dict[str, float]:
    pattern = list(model["layer_pattern"])
    periods = int(model["num_layers"]) // len(pattern)
    total = int(model["num_experts"])
    heads = int(model["num_heads"])
    return {
        "d": int(model["d_model"]),
        "h": heads,
        "h_kv": int(model.get("num_kv_heads") or heads),
        "hd": int(model["head_dim"]),
        "ssm_h": int(model["ssm_num_heads"]),
        "ssm_p": int(model["ssm_head_dim"]),
        "ssm_n": int(model["ssm_state_size"]),
        "ssm_g": int(model["ssm_groups"]),
        "chunk": int(model.get("ssm_chunk") or 128),
        "d_ff": int(model["d_ff"]),
        "moe_d_ff": int(model.get("moe_d_ff") or model["d_ff"]),
        "shared_d_ff": int(model["shared_expert_d_ff"]),
        "total": total,
        "held": int(model.get("experts_held") or total),
        "top_k": int(model["top_k"]),
        "vocab": int(model["vocab_size"]),
        "ssm_layers": periods * pattern.count("ssm"),
        "attn_layers": periods * pattern.count("attention"),
        "expert_layers": periods * pattern.count("experts"),
        "mlp_layers": periods * pattern.count("mlp"),
    }


def ssm_projection_params(model: Mapping) -> int:
    """Matmul weights of ONE state-space layer's two projections."""
    g = _sizes(model)
    inner = g["ssm_h"] * g["ssm_p"]
    return (
        g["d"] * (2 * inner + 2 * g["ssm_g"] * g["ssm_n"] + g["ssm_h"])
        + inner * g["d"]
    )


def scan_flops_per_token(model: Mapping) -> float:
    """FORWARD FLOPs a token of ONE layer's chunked scan: ``C B^T`` once a
    group, and ``M X``, ``C S^T``, ``B^T X`` a head."""
    g = _sizes(model)
    q, n, p = g["chunk"], g["ssm_n"], g["ssm_p"]
    return 2.0 * q * n * g["ssm_g"] + g["ssm_h"] * (2.0 * q * p + 4.0 * n * p)


def pairs_here_per_token(model: Mapping) -> float:
    """Routed (token, expert) pairs a token brings to THIS chip, expected."""
    g = _sizes(model)
    return g["top_k"] * g["held"] / g["total"]


def flops_per_token_by_part(model: Mapping, seq_len: int) -> Dict[str, float]:
    """Forward + backward model FLOPs of one token at ``seq_len``, by part
    (the parts sum to ``model_flops_per_token``)."""
    g = _sizes(model)
    expert = 2 * g["d"] * g["moe_d_ff"]
    return {
        "ssm_projections": 6.0 * g["ssm_layers"]
        * ssm_projection_params(model),
        "ssm_scan": 3.0 * g["ssm_layers"] * scan_flops_per_token(model),
        "attention_projections": 6.0 * g["attn_layers"] * g["d"] * g["hd"]
        * (2 * g["h"] + 2 * g["h_kv"]),
        # scores and values, two FLOPs a multiply-add, the causal half,
        # three passes
        "attention": 6.0 * g["attn_layers"] * g["h"] * seq_len
        * 2 * g["hd"] * 0.5,
        "shared_experts": 6.0 * g["expert_layers"] * 2 * g["d"]
        * g["shared_d_ff"],
        "routed_here": 6.0 * g["expert_layers"]
        * pairs_here_per_token(model) * expert,
        "router": 6.0 * g["expert_layers"] * g["d"] * g["total"],
        "dense_mlp": 6.0 * g["mlp_layers"] * 2 * g["d"] * g["d_ff"],
        "head": 6.0 * g["vocab"] * g["d"],
    }


def model_flops_per_token(model: Mapping, seq_len: int) -> float:
    return sum(flops_per_token_by_part(model, seq_len).values())


def ssd_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """FLOPs and HBM bytes the chunked scan of ONE training step needs
    (forward + backward, every state-space layer, ``sequences`` on this
    chip).  FLOPs: the chunked form's four products forward and twice that
    backward.  Bytes: x and y (H P wide), B and C (G N each) at 2 (bf16) and
    dt (H) at 4 forward; x, B, C, dt and dy in, dx, dB, dC and ddt out
    backward.  No stored state, no second forward, no transpose: what a
    program spends on those reads as distance from the floor."""
    g = _sizes(model)
    tokens = float(sequences * seq_len)
    inner, bc = g["ssm_h"] * g["ssm_p"], g["ssm_g"] * g["ssm_n"]
    fwd = 2.0 * (2 * inner + 2 * bc) + 4.0 * g["ssm_h"]
    bwd = 2.0 * (3 * inner + 4 * bc) + 4.0 * 2 * g["ssm_h"]
    return {
        "flops": 3.0 * scan_flops_per_token(model) * tokens
        * g["ssm_layers"],
        "bytes": (fwd + bwd) * tokens * g["ssm_layers"],
    }


def gqa_flash_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """FLOPs and HBM bytes the flash kernels of ONE training step need
    (forward + backward, the ATTENTION layers only), causal, ``num_heads``
    query heads over ``num_kv_heads`` key/value heads.

    Forward: QK^T and PV.  Backward (the kernel keeps no probabilities):
    QK^T again, dV, dP, dQ and dK.  Seven matmuls of 2 x S x S x hd per
    query head and sequence, halved by the causal mask.  Bytes at 2 (bf16):
    q in and o out (H heads), k and v in (H_kv heads) forward; q, o, do in
    and dq out (H), k, v in and dk, dv out (H_kv) backward; the log-sum-exp
    rows at 4 bytes a query head, once each way."""
    g = _sizes(model)
    square = 2.0 * seq_len * seq_len * g["h"] * sequences
    flops = square * 7 * g["hd"] * 0.5 * g["attn_layers"]
    row = 2.0 * sequences * seq_len * g["hd"]           # bf16 bytes a head
    lse = 4.0 * sequences * seq_len * g["h"]
    fwd = row * (2 * g["h"] + 2 * g["h_kv"]) + lse
    bwd = row * (4 * g["h"] + 4 * g["h_kv"]) + lse
    return {"flops": flops, "bytes": (fwd + bwd) * g["attn_layers"]}


def relu2_expert_matmul_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """FLOPs and HBM bytes the grouped GEMMs of ONE training step need for
    the pairs routed HERE (expected): TWO matrices of 2 x d x moe_d_ff a
    pair, three times (forward, d-input, d-weight), every expert layer.
    Padding and recomputation are not needed, so not counted.  Bytes: the
    HELD experts' weights read forward and backward and their gradients
    written once (bf16), plus the routed rows in and out."""
    g = _sizes(model)
    routed = sequences * seq_len * pairs_here_per_token(model)
    flops = 2 * 3 * 2.0 * routed * g["d"] * g["moe_d_ff"] * g["expert_layers"]
    weights = 2.0 * g["held"] * 2 * g["d"] * g["moe_d_ff"]
    acts = 2.0 * routed * (2 * g["d"] + 2 * g["moe_d_ff"])
    return {
        "flops": flops,
        "bytes": (3 * weights + 3 * acts) * g["expert_layers"],
    }
