"""The benchmark's own plain reference for OLMoE-1B-7B: forward and loss.

The equations (Muennighoff et al. 2024, arXiv:2409.02060; the model's
``config.json``)::

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))
    final RMSNorm, untied head

    Attn(n):  q, k, v = W_q n, W_k n, W_v n          (no bias, no clipping)
              q <- RMSNorm_q(q),  k <- RMSNorm_k(k)   over all H * hd
                  outputs, own [H * hd] scale each, eps 1e-5
              H heads of hd; rotate-half RoPE (theta) on q and k
              causal softmax(q k^T / sqrt(hd)) v;  W_o
    MoE(n):   p = softmax(W_r n) over the E experts, float32
              the k largest p_e; gates = those p_e as they are (NOT
              renormalised); out = sum_e p_e W_down,e (silu(W_gate,e n)
              * W_up,e n); every chosen pair is computed (no capacity)

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
kernel, no cache, no sort, no sharding.  It reads the program's parameter
tree only for the numbers in it.  One layer at a time: a layer's weights
are cast to float32 inside one jitted function that every layer re-uses,
the experts one after another (each over all tokens, weighted by a gate
that is zero where the expert was not chosen), so no second copy of the
model is held and the check fits beside the model on the chip.

Departures from the published model: none in the forward pass.  The
load-balancing and z losses are training terms and not part of the
per-token loss compared here (the repository's own reference,
``dlrover_tpu/models/references/olmoe.py``, has the balancing term and
its gradients; ``tests/benchmark_suite`` holds the two together).

``lowered`` computes part of the model in bfloat16, to show that the
comparison's limit would catch it: ``"router"`` the router's logits,
softmax and gates; ``"all"`` every product, activation, the logits and
the loss as well.  A run never sets it.
"""

from __future__ import annotations

import functools
from typing import Any, Mapping

import jax
import jax.numpy as jnp

F32, BF16 = jnp.float32, jnp.bfloat16
EPS = 1e-5


def _rms_norm(x, scale, dtype):
    x32 = x.astype(F32)
    y = x32 / jnp.sqrt((x32 * x32).mean(-1, keepdims=True) + EPS)
    return (y * scale.astype(F32)).astype(dtype)


def _rope(x, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1
    ).astype(x.dtype)


def _attention(model, n, p, dtype):
    heads = int(model["num_heads"])
    if "qkv" in p:
        w = p["qkv"]["kernel"].astype(dtype)          # [d, H, 3 hd]
        hd = w.shape[-1] // 3
        parts = (w[..., :hd], w[..., hd:2 * hd], w[..., 2 * hd:])
    else:
        parts = tuple(p[name]["kernel"].astype(dtype)
                      for name in ("query", "key", "value"))
    q, k, v = (
        jnp.einsum("bsd,dhk->bshk", n, w).reshape(*n.shape[:2], -1)
        for w in parts
    )
    q = _rms_norm(q, p["q_norm"]["scale"], dtype)
    k = _rms_norm(k, p["k_norm"]["scale"], dtype)
    b, s, width = q.shape
    hd = width // heads
    q, k, v = (a.reshape(b, s, heads, hd) for a in (q, k, v))
    theta = float(model.get("rope_theta", 10000.0))
    q, k = _rope(q, theta), _rope(k, theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(F32) / jnp.sqrt(
        F32(hd)
    )
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return jnp.einsum("bqhd,hdm->bqm", o, p["out"]["kernel"].astype(dtype))


def _moe(model, n, p, dtype, router_dtype):
    e, k = int(model["num_experts"]), int(model["top_k"])
    logits = n.astype(router_dtype) @ p["router"]["kernel"].astype(
        router_dtype
    )
    probs = jax.nn.softmax(logits, axis=-1)                   # [B, S, E]
    top_p, top_i = jax.lax.top_k(probs, k)
    # gates[b, s, e]: p_e where e is one of the token's k, else 0
    gates = (
        jax.nn.one_hot(top_i, e, dtype=router_dtype) * top_p[..., None]
    ).sum(-2)

    def add_expert(i, out):
        def w(name):
            return jax.lax.dynamic_index_in_dim(
                p[name], i, 0, False
            ).astype(dtype)

        y = (jax.nn.silu(n @ w("wg")) * (n @ w("wi"))) @ w("wo")
        gate = jax.lax.dynamic_index_in_dim(gates, i, 2, True)
        return out + (y.astype(router_dtype) * gate).astype(dtype)

    # one expert after another into one accumulator: 64 outputs side by
    # side would be 4.3 GB at two sequences of 4096
    return jax.lax.fori_loop(0, e, add_expert, jnp.zeros_like(n))


def _dtypes(lowered: str):
    """(trunk dtype, router dtype) of a ``lowered`` mode."""
    return {
        "": (F32, F32), "router": (F32, BF16), "all": (BF16, BF16),
    }[lowered]


@functools.partial(jax.jit, static_argnums=(0, 3))
def _block(model_items, x, p, lowered):
    model = dict(model_items)
    dtype, router_dtype = _dtypes(lowered)
    x = x + _attention(
        model, _rms_norm(x, p["ln_attn"]["scale"], dtype), p["attn"], dtype
    )
    return x + _moe(
        model, _rms_norm(x, p["ln_mlp"]["scale"], dtype), p["moe"], dtype,
        router_dtype,
    )


@functools.partial(jax.jit, static_argnums=(3,))
def _head_nll(params, x, targets, lowered):
    dtype, _ = _dtypes(lowered)
    x = _rms_norm(x, params["ln_final"]["scale"], dtype)
    logits = x @ params["lm_head"]["kernel"].astype(dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0].astype(
        F32
    )


def token_nll(model: Mapping[str, Any], params, tokens, targets,
              lowered: str = ""):
    """Per-token negative log-likelihood [B, S] in float32.

    ``model`` is the ``model`` group of a configuration file (plain
    numbers and strings); ``params`` the program's parameter tree, the
    layers stacked on a leading axis under ``blocks`` or listed as
    ``block_<i>``."""
    items = tuple(sorted(
        (k, v) for k, v in model.items()
        if isinstance(v, (int, float, str, bool)) or v is None
    ))
    dtype, _ = _dtypes(lowered)
    with jax.default_matmul_precision("highest"):
        rest = {k: v for k, v in params.items() if not k.startswith("block")}
        x = rest["embed"]["embedding"].astype(dtype)[tokens]
        for i in range(int(model["num_layers"])):
            if "blocks" in params:
                layer = jax.tree.map(lambda a: a[i], params["blocks"])
            else:
                layer = params[f"block_{i}"]
            x = _block(items, x, layer, lowered)
        return _head_nll(rest, x, targets, lowered)
