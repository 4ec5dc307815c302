"""Operations and bytes from shapes: the benchmark's own arithmetic.

Everything here takes plain numbers (the ``model`` group of a
configuration file, a sequence length, a batch) and returns counts.  No
JAX, no program code: a later PR cannot move these by changing the
program.

Conventions, stated once:

* Model FLOPs per token are the matrix multiplications the forward and
  backward passes require: 6 x (matmul parameters a token meets) plus the
  attention scores and values.  Recomputation (``remat``) is not counted.
* Attention is counted at the full ``seq_len`` x ``seq_len`` square, the
  convention of ``bench.py``'s ``flops_per_token`` and of bench round r03's
  MFU, so that ``step_mfu`` continues that series.  (The causal half would
  be 4% lower at GPT-2 1.5B / seq 1024.)
* A lookup is not a matmul: the position table is not counted, and with
  untied embeddings only the output head is.
* A sparse-expert MLP counts ``top_k`` experts per token plus the router.
* A kernel's roofline counts what its algorithm needs at the *causal*
  half: the chip could skip the masked half, so the floor is the half.
"""

from __future__ import annotations

from typing import Dict, Mapping


def _heads(model: Mapping) -> Dict[str, int]:
    d = int(model["d_model"])
    h = int(model["num_heads"])
    hkv = int(model.get("num_kv_heads") or h)
    hd = int(model.get("head_dim") or d // h)
    return {"d": d, "h": h, "hkv": hkv, "hd": hd}


def _d_ff(model: Mapping) -> int:
    if model.get("d_ff"):
        return int(model["d_ff"])
    return 4 * int(model["d_model"])


def _mlp_matrices(model: Mapping) -> int:
    return 3 if model.get("activation") == "swiglu" else 2


def matmul_params_per_token(model: Mapping) -> int:
    """Matmul weights one token passes through in a forward pass."""
    g = _heads(model)
    d, h, hkv, hd = g["d"], g["h"], g["hkv"], g["hd"]
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    ff = _mlp_matrices(model) * d * _d_ff(model)
    experts = int(model.get("num_experts") or 0)
    if experts:
        ff = ff * int(model.get("top_k", 2)) + d * experts
    head = int(model["vocab_size"]) * d
    return int(model["num_layers"]) * (attn + ff) + head


def model_flops_per_token(model: Mapping, seq_len: int) -> float:
    """Forward + backward model FLOPs for one token at ``seq_len``."""
    g = _heads(model)
    # scores and values: 2 matmuls x 2 FLOPs x seq_len x (h x hd), x3 for
    # forward + backward.
    attn = 12 * int(model["num_layers"]) * g["h"] * g["hd"] * seq_len
    return 6.0 * matmul_params_per_token(model) + attn


def flash_attention_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """FLOPs and HBM bytes the flash kernels of ONE training step need
    (forward + backward, every layer, ``sequences`` on this chip), causal.

    Forward: QK^T and PV.  Backward (the kernel keeps no probabilities):
    QK^T again, dV, dP, dQ, dK.  Seven matmuls of 2 x S x S x hd per head
    and sequence, halved by the causal mask.  Bytes: q, k, v, o in and out
    of the forward; q, k, v, o, do in and dq, dk, dv out of the backward,
    at 2 bytes (bf16); the log-sum-exp rows are counted at 4 bytes.
    """
    g = _heads(model)
    layers = int(model["num_layers"])
    per_matmul = 2.0 * seq_len * seq_len * g["hd"] * g["h"] * sequences
    flops = 7 * per_matmul * 0.5 * layers
    q_bytes = 2.0 * sequences * seq_len * g["h"] * g["hd"]
    kv_bytes = 2.0 * sequences * seq_len * g["hkv"] * g["hd"]
    lse = 4.0 * sequences * seq_len * g["h"]
    fwd = 2 * q_bytes + 2 * kv_bytes + lse
    bwd = 4 * q_bytes + 4 * kv_bytes + lse
    return {"flops": flops, "bytes": (fwd + bwd) * layers}


def expert_matmul_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """FLOPs and HBM bytes the expert matmuls of ONE training step need.

    Each routed (token, expert) pair meets ``_mlp_matrices`` matmuls of
    2 x d x d_ff, three times (forward, d-input, d-weight).  Capacity
    padding and recomputation are not needed, so not counted.  Bytes: every
    expert's weights read in the forward and in the backward and their
    gradients written once (bf16), plus the routed activations in and out.
    """
    d = int(model["d_model"])
    ff = _d_ff(model)
    mats = _mlp_matrices(model)
    experts = int(model["num_experts"])
    layers = int(model["num_layers"])
    routed = sequences * seq_len * int(model.get("top_k", 2))
    flops = 3 * mats * 2.0 * routed * d * ff * layers
    weights = 2.0 * experts * mats * d * ff
    acts = 2.0 * routed * (2 * d + mats * ff)
    return {"flops": flops, "bytes": (3 * weights + 3 * acts) * layers}


def roofline_seconds(cost: Mapping, peak: Mapping) -> Dict[str, float]:
    """The least time the chip could take, and which bound sets it."""
    compute = cost["flops"] / peak["bf16_flops_per_s"]
    memory = cost["bytes"] / peak["hbm_bytes_per_s"]
    return {
        "seconds": max(compute, memory),
        "bound": "compute" if compute >= memory else "memory",
    }
