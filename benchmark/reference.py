"""The plain reference: the models' forward pass and loss, nothing else.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
kernels, no cache, no sharding, no scan.  It is written from the published
descriptions (GPT-2: learned positions, pre-LayerNorm, tanh-GELU, tied
head; Mixtral: RoPE, RMSNorm, grouped-query attention, SwiGLU experts with
a softmax-then-top-k router renormalised over the chosen experts, untied
head) and reads the program's parameter tree only for the numbers in it.

Departures from the published models, each because the *configuration
file* says so, never silently:

* ``capacity_factor`` (Mixtral is dropless).  Where the file sets one, each
  expert takes at most ``int(cf * seq * top_k / experts)`` tokens of one
  sequence, first choices before second, earlier positions first, and the
  rest are dropped for that expert, as the file's ``assumed`` group says.
* no dropout (GPT-2's is 0.1 in training); the program has none.

One layer at a time: a layer's weights are cast to float32 inside one
jitted function that every layer re-uses, experts one after another, so
no second copy of the model is ever held and the whole check costs
seconds on the chip.
"""

from __future__ import annotations

import functools
from typing import Any, Mapping

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(x):
    return jnp.asarray(x).astype(F32)


def _layer_norm(x, p, eps=1e-5):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + eps) * _f32(p["scale"])
    return y + _f32(p["bias"]) if "bias" in p else y


def _rms_norm(x, p, eps=1e-5):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(
        p["scale"]
    )


def _norm(model, x, p):
    return _rms_norm(x, p) if model["norm"] == "rmsnorm" else _layer_norm(x, p)


def _dense(x, p, n_in=1):
    """``x`` contracted over its last ``n_in`` dims with ``p['kernel']``."""
    w = _f32(p["kernel"])
    y = jnp.tensordot(x, w, axes=n_in)
    return y + _f32(p["bias"]) if "bias" in p else y


def _rope(x, theta):
    """Rotate-half RoPE on [B, S, H, D] (the HF / Mixtral convention)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(model, x, p):
    hd = model.get("head_dim") or model["d_model"] // model["num_heads"]
    if "qkv" in p:
        qkv = _dense(x, p["qkv"])                       # [B, S, H, 3*hd]
        q, k, v = qkv[..., :hd], qkv[..., hd:2 * hd], qkv[..., 2 * hd:]
    else:
        q, k, v = (_dense(x, p[n]) for n in ("query", "key", "value"))
    if model["position"] == "rope":
        theta = float(model.get("rope_theta", 10000.0))
        q, k = _rope(q, theta), _rope(k, theta)
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    n = x.shape[1]
    causal = jnp.tril(jnp.ones((n, n), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return _dense(o, p["out"], n_in=2)


def _act(model, up, gate=None):
    if model["activation"] == "swiglu":
        return jax.nn.silu(gate) * up
    return jax.nn.gelu(up, approximate=True)


def _mlp(model, x, p):
    gate = _dense(x, p["wg"]) if "wg" in p else None
    return _dense(_act(model, _dense(x, p["wi"]), gate), p["wo"])


def _moe(model, x, p):
    e, k = int(model["num_experts"]), int(model.get("top_k", 2))
    b, s, d = x.shape
    probs = jax.nn.softmax(_dense(x, p["router"]), axis=-1)   # [B, S, E]
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    # weight[b, s, e]: the renormalised gate of expert e for this token, 0
    # where it was not chosen or fell past the expert's capacity.
    weight = jnp.zeros((b, s, e), F32)
    cf = model.get("capacity_factor")
    cap = max(1, int(cf * s * k / e)) if cf else None
    taken = jnp.zeros((b, 1, e), F32)
    for c in range(k):
        chose = jax.nn.one_hot(top_i[..., c], e, dtype=F32)
        if cap is not None:
            before = jnp.cumsum(chose, axis=1) - chose + taken
            chose = chose * (before < cap)
            taken = taken + chose.sum(1, keepdims=True)
        weight = weight + chose * top_p[..., c:c + 1]

    def one_expert(i):
        gate = None
        if "wg" in p:
            gate = x @ _f32(jax.lax.dynamic_index_in_dim(p["wg"], i, 0, False))
        up = x @ _f32(jax.lax.dynamic_index_in_dim(p["wi"], i, 0, False))
        return _act(model, up, gate) @ _f32(
            jax.lax.dynamic_index_in_dim(p["wo"], i, 0, False)
        )

    outs = jax.lax.map(one_expert, jnp.arange(e))             # [E, B, S, D]
    return jnp.einsum("bse,ebsd->bsd", weight, outs)


@functools.partial(jax.jit, static_argnums=0)
def _block(model_items, x, p):
    model = dict(model_items)
    x = x + _attention(model, _norm(model, x, p["ln_attn"]), p["attn"])
    y = _norm(model, x, p["ln_mlp"])
    y = _moe(model, y, p["moe"]) if "moe" in p else _mlp(model, y, p["mlp"])
    return x + y


@functools.partial(jax.jit, static_argnums=0)
def _embed(model_items, params, tokens):
    model = dict(model_items)
    x = _f32(params["embed"]["embedding"])[tokens]
    if model["position"] == "learned":
        x = x + _f32(params["pos_embedding"])[None, : tokens.shape[1]]
    return x


@functools.partial(jax.jit, static_argnums=0)
def _head_nll(model_items, params, x, targets):
    model = dict(model_items)
    x = _norm(model, x, params["ln_final"])
    if model.get("tie_embeddings", True):
        logits = x @ _f32(params["embed"]["embedding"]).T
    else:
        logits = _dense(x, params["lm_head"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def token_nll(model: Mapping[str, Any], params, tokens, targets):
    """Per-token negative log-likelihood [B, S] in float32.

    ``model`` is the ``model`` group of a configuration file (plain
    numbers and strings); ``params`` the program's parameter tree, with
    the layers stacked on a leading axis under ``blocks`` or listed as
    ``block_<i>``.
    """
    items = tuple(sorted(
        (k, v) for k, v in model.items()
        if isinstance(v, (int, float, str, bool)) or v is None
    ))
    with jax.default_matmul_precision("highest"):
        rest = {k: v for k, v in params.items() if not k.startswith("block")}
        x = _embed(items, rest, tokens)
        for i in range(int(model["num_layers"])):
            if "blocks" in params:
                layer = jax.tree.map(lambda a: a[i], params["blocks"])
            else:
                layer = params[f"block_{i}"]
            x = _block(items, x, layer)
        return _head_nll(items, rest, x, targets)
