"""OLMoE-1B-7B's forward pass, loss and gradients, written plainly.

The reference the program is held to (``tests/test_olmoe_reference.py``):
float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, no
kernel, no scan, no sort, no cache.  Written from the published
description (Muennighoff et al. 2024, arXiv:2409.02060, and the model's
``config.json``); it reads the program's parameter tree only for the
numbers in it.

The equations::

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))
    final RMSNorm, untied head

    Attn(n):  q, k, v = W_q n, W_k n, W_v n          (no bias, no clipping)
              q <- RMSNorm_q(q),  k <- RMSNorm_k(k)   each over all
                  H * hd outputs, its own [H * hd] scale, eps 1e-5
              split into H heads of hd; rotate-half RoPE (theta) on q, k
              causal softmax(q k^T / sqrt(hd)) v;  W_o

    MoE(n):   p = softmax(W_r n) over the E experts, in float32
              the k largest p_e and their experts; the gates are those
              p_e AS THEY ARE (``norm_topk_prob`` false: not renormalised)
              out = sum_e p_e W_down,e (silu(W_gate,e n) * W_up,e n)
              every chosen (token, expert) pair is computed: no capacity

    loss = mean token NLL + w * sum_layers E * sum_e f_e P_e
              f_e the share of tokens that chose e in any of their k
              slots, P_e the mean router probability of e

Departures from the published model, each noted where it is made:

* the load-balancing term is taken per layer and summed over the layers
  (the program's convention); the Hugging Face port concatenates every
  layer's router logits before taking ``f`` and ``P``.
* the paper's router z-loss (weight 0.001) is left out: the program has
  none.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import jax
import jax.numpy as jnp

F32 = jnp.float32
EPS = 1e-5


def _model(model) -> Mapping[str, Any]:
    if dataclasses.is_dataclass(model):
        return {f.name: getattr(model, f.name)
                for f in dataclasses.fields(model)}
    return model


def _f32(x):
    return jnp.asarray(x).astype(F32)


def rms_norm(x, scale):
    """Over the last axis of ``x``; ``scale`` has its length."""
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + EPS) * _f32(scale)


def rope(x, theta):
    """Rotate-half RoPE on [B, S, H, hd], positions 0..S-1."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _projections(n, p):
    """q, k, v as [B, S, H * hd], from the program's fused ``[d, H, 3 hd]``
    kernel (per head: q | k | v) or its three separate ones."""
    if "qkv" in p:
        w = _f32(p["qkv"]["kernel"])
        hd = w.shape[-1] // 3
        parts = (w[..., :hd], w[..., hd:2 * hd], w[..., 2 * hd:])
    else:
        parts = tuple(_f32(p[name]["kernel"])
                      for name in ("query", "key", "value"))
    return tuple(
        jnp.einsum("bsd,dhk->bshk", n, w).reshape(*n.shape[:2], -1)
        for w in parts
    )


def attention(model, n, p):
    heads = int(model["num_heads"])
    q, k, v = _projections(n, p)
    q = rms_norm(q, p["q_norm"]["scale"])
    k = rms_norm(k, p["k_norm"]["scale"])
    b, s, width = q.shape
    hd = width // heads
    q, k, v = (a.reshape(b, s, heads, hd) for a in (q, k, v))
    theta = float(model.get("rope_theta", 10000.0))
    q, k = rope(q, theta), rope(k, theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bqhd,hdm->bqm", o, _f32(p["out"]["kernel"]))


def moe(model, n, p):
    """``(out, E * sum_e f_e P_e)`` of one layer."""
    e, k = int(model["num_experts"]), int(model["top_k"])
    probs = jax.nn.softmax(n @ _f32(p["router"]["kernel"]), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    chosen = jax.nn.one_hot(top_i, e, dtype=F32)             # [B, S, k, E]
    gates = (chosen * top_p[..., None]).sum(-2)              # [B, S, E]
    out = jnp.zeros_like(n)
    for i in range(e):
        up = n @ _f32(p["wi"][i])
        gate = n @ _f32(p["wg"][i])
        out = out + gates[..., i:i + 1] * (
            (jax.nn.silu(gate) * up) @ _f32(p["wo"][i])
        )
    f = chosen.sum(-2).mean(axis=(0, 1))
    aux = e * jnp.sum(f * probs.mean(axis=(0, 1)))
    return out, aux


def _layer(params, i):
    if "blocks" in params:
        return jax.tree.map(lambda a: a[i], params["blocks"])
    return params[f"block_{i}"]


def forward(model, params, tokens):
    """``(logits [B, S, V], sum over layers of the balancing term)``."""
    model = _model(model)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"]["embedding"])[tokens]
        aux = F32(0.0)
        for i in range(int(model["num_layers"])):
            p = _layer(params, i)
            x = x + attention(
                model, rms_norm(x, p["ln_attn"]["scale"]), p["attn"]
            )
            y, layer_aux = moe(
                model, rms_norm(x, p["ln_mlp"]["scale"]), p["moe"]
            )
            x, aux = x + y, aux + layer_aux
        x = rms_norm(x, params["ln_final"]["scale"])
        return x @ _f32(params["lm_head"]["kernel"]), aux


def _nll(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def token_nll(model, params, tokens, targets):
    """Per-token negative log-likelihood [B, S]."""
    return _nll(forward(model, params, tokens)[0], targets)


def loss(model, params, tokens, targets):
    """Mean token NLL plus ``moe_aux_weight`` x the balancing terms."""
    model = _model(model)
    logits, aux = forward(model, params, tokens)
    return _nll(logits, targets).mean() + F32(model["moe_aux_weight"]) * aux


def loss_and_grads(model, params, tokens, targets):
    return jax.value_and_grad(loss, argnums=1)(model, params, tokens, targets)
