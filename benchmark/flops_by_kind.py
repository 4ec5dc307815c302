"""Operations and bytes from shapes for a model whose layers differ in
kind: the benchmark's own arithmetic, beside ``flops.py``.

``flops.py`` counts every one of ``num_layers`` as softmax attention.  A
configuration with a ``layer_pattern`` (one period of layer kinds,
repeated) is counted here, by kind; without a pattern every function gives
what ``flops.py`` gives.  Plain numbers in, counts out: no JAX, no program
code.  What ``flops.py``, ``readers/kernel_roofline.py`` and
``readers/mfu.py`` should fold in of this is PERF.md §7, item (6).

Conventions beyond those of ``flops.py``:

* A ``linear_attention`` layer's matmul weights are its q, k, v, gate and
  output projections and the two gate projections (``W_a``, ``W_b``).
  The depthwise convolution (``taps`` multiply-adds a channel), the norms
  and the gates' elementwise work are not matmuls and not counted.
* The gated delta rule is counted in its chunked (WY) form at chunk 64,
  WHATEVER chunk the program runs: per chunk and head ``K K^T`` and
  ``Q K^T`` (2 C^2 dk each), the unit-lower-triangular solve that turns
  ``beta``, ``K``, ``V`` into ``W`` and ``U`` (C^2 (dk + dv): a
  substitution, the least it takes), ``W S^T``, ``(Q gamma) S^T`` and the
  state's update (2 C dk dv each) and the masked ``Q K^T`` times the
  chunk's writes (2 C^2 dv).  The backward is counted at twice the
  forward, as a matmul's is.
* The rule's bytes are what its calls must move: q, k, v (bf16), g, beta
  (float32) in and o (bf16) out forward; those and o, do in and the five
  gradients out backward.  No chunk-boundary state is counted, so storing
  or recomputing states reads as waste.
"""

from __future__ import annotations

from typing import Dict, Mapping

from benchmark import flops

LINEAR = "linear_attention"
FULL = "full_attention"
RULE_CHUNK = 64


def layer_counts(model: Mapping) -> Dict[str, int]:
    """How many layers of each kind the model runs."""
    layers = int(model["num_layers"])
    pattern = list(model.get("layer_pattern") or [])
    if not pattern:
        return {FULL: layers, LINEAR: 0}
    if layers % len(pattern):
        raise ValueError(
            f"{layers} layers are no whole number of periods of {pattern}"
        )
    periods = layers // len(pattern)
    return {
        FULL: periods * pattern.count(FULL),
        LINEAR: periods * pattern.count(LINEAR),
    }


def _linear_heads(model: Mapping) -> Dict[str, int]:
    return {
        "d": int(model["d_model"]),
        "h": int(model.get("linear_num_heads") or model["num_heads"]),
        "dk": int(model["linear_key_head_dim"]),
        "dv": int(model["linear_value_head_dim"]),
    }


def linear_mixer_matmul_params(model: Mapping) -> int:
    g = _linear_heads(model)
    d, h, dk, dv = g["d"], g["h"], g["dk"], g["dv"]
    return 2 * d * h * dk + 3 * d * h * dv + 2 * d * h


def matmul_params_per_token(model: Mapping) -> int:
    """Matmul weights one token passes through in a forward pass."""
    counts = layer_counts(model)
    head = int(model["vocab_size"]) * int(model["d_model"])
    # ``flops.py``'s layer (attention and MLP, dense or sparse) and head
    full_layer = flops.matmul_params_per_token(
        dict(model, num_layers=1)
    ) - head
    total = counts[FULL] * full_layer + head
    if counts[LINEAR]:
        mlp = full_layer - _attention_params(model)
        total += counts[LINEAR] * (linear_mixer_matmul_params(model) + mlp)
    return total


def _attention_params(model: Mapping) -> int:
    d = int(model["d_model"])
    h = int(model["num_heads"])
    hkv = int(model.get("num_kv_heads") or h)
    hd = int(model.get("head_dim") or d // h)
    return d * h * hd + 2 * d * hkv * hd + h * hd * d


def delta_rule_flops_per_token(model: Mapping) -> float:
    """Forward FLOPs of the chunked rule for ONE token of ONE layer, all
    heads (a chunk's count over its ``RULE_CHUNK`` tokens)."""
    g = _linear_heads(model)
    c, dk, dv = RULE_CHUNK, g["dk"], g["dv"]
    per_chunk = (
        4 * c * c * dk + c * c * (dk + dv) + 6 * c * dk * dv + 2 * c * c * dv
    )
    return g["h"] * per_chunk / c


def model_flops_per_token(model: Mapping, seq_len: int) -> float:
    """Forward + backward model FLOPs for one token at ``seq_len``: 6 x
    the matmul weights a token meets, the full-attention layers' scores and
    values at the full square (``flops.py``'s convention), and the linear
    layers' rule, three times its forward."""
    counts = layer_counts(model)
    h = int(model["num_heads"])
    hd = int(model.get("head_dim") or int(model["d_model"]) // h)
    scores = 12 * counts[FULL] * h * hd * seq_len
    rule = (
        3 * counts[LINEAR] * delta_rule_flops_per_token(model)
        if counts[LINEAR] else 0.0
    )
    return 6.0 * matmul_params_per_token(model) + scores + rule


def flash_attention_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """``flops.flash_attention_cost`` over the full-attention layers only."""
    return flops.flash_attention_cost(
        dict(model, num_layers=layer_counts(model)[FULL]), seq_len, sequences
    )


def gated_delta_rule_cost(
    model: Mapping, seq_len: int, sequences: int
) -> Dict[str, float]:
    """FLOPs and HBM bytes the delta rule's calls of ONE training step
    need (forward + backward, every linear layer, ``sequences`` on this
    chip), counted at ``RULE_CHUNK`` = 64 tokens a chunk while the program
    runs 128 (its configuration's ``assumed.chunk``): the cost is the
    algorithm's at a fixed chunk, so the program's larger chunks read as
    what they are against one yardstick.  The module docstring has what is
    counted."""
    layers = layer_counts(model)[LINEAR]
    g = _linear_heads(model)
    tokens = float(sequences) * seq_len
    fwd_flops = tokens * delta_rule_flops_per_token(model)
    qkv = 2.0 * (2 * g["dk"] + g["dv"])       # bf16 q, k, v of a head
    out = 2.0 * g["dv"]                       # bf16 o (or do) of a head
    gates = 2 * 4.0                           # float32 g and beta
    fwd_bytes = qkv + gates + out
    bwd_bytes = (qkv + gates + 2 * out) + (qkv + gates)
    return {
        "flops": 3.0 * fwd_flops * layers,
        "bytes": tokens * g["h"] * (fwd_bytes + bwd_bytes) * layers,
    }
