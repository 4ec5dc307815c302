"""The benchmark's own plain reference for Olmo-Hybrid: forward and loss.

The equations (the model's ``config.json``; the linear layers are the
gated delta rule of Gated DeltaNet, Yang et al., arXiv:2412.06464; norm
placement and QK-norm are the OLMo family's, the configuration's
``assumed`` says why)::

    block:   h = x + Norm(Mixer(x));  x' = h + Norm(SwiGLU(h))
             layer i's mixer is layer_pattern[i mod period]
             final RMSNorm, untied head; every RMSNorm with eps norm_eps

    linear:  q, k, v, z = W_q n, W_k n, W_v n, W_g n          (no bias)
             q, k, v <- SiLU(causal depthwise conv over the sequence,
                 ``taps`` taps, own taps a channel)
             per head:  q <- q / ||q|| * dk^-1/2,   k <- k / ||k||
                 (||x|| = sqrt(sum x^2 + 1e-6))
             beta = sigmoid(W_b n), doubled where linear_allow_neg_eigval
             g = -exp(A_log) * softplus(W_a n + dt_bias);  alpha = exp(g)
             S_0 = 0 in R^{dv x dk}
             S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
             o_t = S_t q_t                      one token at a time
             y = RMSNorm_dv(o_t; one [dv] scale) * SiLU(z);   W_o y

    full:    q, k, v = W_q n, W_k n, W_v n;  RMSNorm over all H * hd
             outputs of q and of k, own scale each; H heads of hd;
             rotate-half RoPE (theta); causal softmax(q k^T / sqrt(hd)) v; W_o

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
chunk, no kernel, no cache, no sharding.  It reads the program's
parameter tree only for the numbers in it.  One layer at a time: a
layer's weights are cast to float32 inside one jitted function per layer
kind, the full layers one head after another (the scores of all heads at
8192 tokens would be 16 GB), so the check fits beside the model on the
chip.

Departures from the published model: none beyond what the configuration
lists under ``assumed`` (norm placement, ``rope_theta``, the norm's
epsilon inside the root).

``lowered`` computes part of the model in bfloat16, to show that the
comparison's limit would catch it: ``"rule"`` the delta rule alone (its
inputs, its state, its outputs); ``"all"`` every product, activation, the
state, the logits and the loss as well.  A run never sets it.
"""

from __future__ import annotations

import functools
from typing import Any, Mapping

import jax
import jax.numpy as jnp

F32, BF16 = jnp.float32, jnp.bfloat16
L2_EPS = 1e-6
LINEAR = "linear_attention"


def _rms_norm(x, scale, eps, dtype):
    x32 = x.astype(F32)
    y = x32 / jnp.sqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(dtype)


def _l2(x):
    x32 = x.astype(F32)
    return (x32 / jnp.sqrt((x32 * x32).sum(-1, keepdims=True) + L2_EPS)
            ).astype(x.dtype)


def _rope(x, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1
    ).astype(x.dtype)


def _full_attention(model, n, p, dtype):
    heads = int(model["num_heads"])
    w = p["qkv"]["kernel"].astype(dtype)              # [d, H, 3 hd]
    hd = w.shape[-1] // 3
    q, k, v = (
        jnp.einsum("bsd,dhk->bshk", n, part).reshape(*n.shape[:2], -1)
        for part in (w[..., :hd], w[..., hd:2 * hd], w[..., 2 * hd:])
    )
    eps = float(model["norm_eps"])
    q = _rms_norm(q, p["q_norm"]["scale"], eps, dtype)
    k = _rms_norm(k, p["k_norm"]["scale"], eps, dtype)
    b, s, _ = q.shape
    q, k, v = (a.reshape(b, s, heads, hd) for a in (q, k, v))
    theta = float(model["rope_theta"])
    q, k = _rope(q, theta), _rope(k, theta)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(qkv):
        q_h, k_h, v_h = qkv                           # [B, S, hd]
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k_h).astype(F32) / jnp.sqrt(
            F32(hd)
        )
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        return jnp.einsum("bqk,bkd->bqd", probs, v_h)

    # one head after another: [B, S, S] float32 scores at a time
    o = jax.lax.map(head, tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v)))
    return jnp.einsum("hbqd,hdm->bqm", o, p["out"]["kernel"].astype(dtype))


def _short_conv(x, taps):
    """``y[t] = sum_j taps[j] x[t - (K - 1) + j]``, zeros before the start."""
    k, s = taps.shape[0], x.shape[1]
    y = jnp.zeros_like(x)
    for j in range(k):
        back = k - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, : s - back]], axis=1
        )
        y = y + shifted * taps[j]
    return y


def _recurrence(q, k, v, g, beta, dtype):
    """The rule, a token at a time; state and products in ``dtype``."""
    b, _, h, dk = q.shape
    dv = v.shape[-1]

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = (a.astype(dtype) for a in xs)
        alpha = jnp.exp(g_t)[..., None, None]
        bt = beta_t[..., None, None]
        s_k = jnp.einsum("bhvk,bhk->bhv", state, k_t)          # S k
        state = alpha * (
            state - bt * s_k[..., :, None] * k_t[..., None, :]
        ) + bt * v_t[..., :, None] * k_t[..., None, :]
        return state.astype(dtype), jnp.einsum("bhvk,bhk->bhv", state, q_t)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(token, jnp.zeros((b, h, dv, dk), dtype), xs)
    return jnp.moveaxis(o, 0, 1)


def _linear_attention(model, n, p, dtype, rule_dtype):
    dk = int(model["linear_key_head_dim"])
    dv = int(model["linear_value_head_dim"])
    qkvz = jnp.einsum("bsd,dhc->bshc", n, p["qkvg"]["kernel"].astype(dtype))
    qkv, z = qkvz[..., : 2 * dk + dv], qkvz[..., 2 * dk + dv:]
    qkv = jax.nn.silu(_short_conv(qkv, p["conv_kernel"].astype(dtype)))
    q = (_l2(qkv[..., :dk]).astype(F32) * dk ** -0.5).astype(dtype)
    k = _l2(qkv[..., dk: 2 * dk])
    v = qkv[..., 2 * dk:]
    ab = jnp.einsum("bsd,dhc->bshc", n, p["ab_kernel"].astype(dtype))
    g = -jnp.exp(p["A_log"].astype(dtype)) * jax.nn.softplus(
        ab[..., 0] + p["dt_bias"].astype(dtype)
    )
    beta = jax.nn.sigmoid(ab[..., 1])
    if model["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    o = _recurrence(q, k, v, g, beta, rule_dtype).astype(dtype)
    y = _rms_norm(
        o, p["out_norm_scale"], float(model["norm_eps"]), dtype
    ) * jax.nn.silu(z)
    return jnp.einsum("bshv,hvm->bsm", y, p["wo"]["kernel"].astype(dtype))


def _swiglu(n, p, dtype):
    def w(name):
        return p[name]["kernel"].astype(dtype)

    return (jax.nn.silu(n @ w("wg")) * (n @ w("wi"))) @ w("wo")


def _dtypes(lowered: str):
    """(trunk dtype, delta rule's dtype) of a ``lowered`` mode."""
    return {"": (F32, F32), "rule": (F32, BF16), "all": (BF16, BF16)}[lowered]


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _block(model_items, kind, x, p, lowered):
    model = dict(model_items)
    dtype, rule_dtype = _dtypes(lowered)
    eps = float(model["norm_eps"])
    if kind == LINEAR:
        y = _linear_attention(model, x, p["linear_attn"], dtype, rule_dtype)
    else:
        y = _full_attention(model, x, p["attn"], dtype)
    x = x + _rms_norm(y, p["ln_attn"]["scale"], eps, dtype)
    return x + _rms_norm(
        _swiglu(x, p["mlp"], dtype), p["ln_mlp"]["scale"], eps, dtype
    )


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head_nll(params, x, targets, eps, lowered):
    dtype, _ = _dtypes(lowered)
    x = _rms_norm(x, params["ln_final"]["scale"], eps, dtype)
    logits = x @ params["lm_head"]["kernel"].astype(dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0].astype(
        F32
    )


def token_nll(model: Mapping[str, Any], params, tokens, targets,
              lowered: str = ""):
    """Per-token negative log-likelihood [B, S] in float32.

    ``model`` is the ``model`` group of a configuration file (plain
    numbers, strings and the ``layer_pattern`` list); ``params`` the
    program's parameter tree: each slot of a period (``linear_0`` ..
    ``full_3``) stacked over the periods under ``blocks``, or the layers
    listed as ``block_<i>``."""
    pattern = tuple(model["layer_pattern"])
    items = tuple(sorted(
        (k, v) for k, v in model.items()
        if isinstance(v, (int, float, str, bool)) or v is None
    ))
    dtype, _ = _dtypes(lowered)
    with jax.default_matmul_precision("highest"):
        rest = {k: v for k, v in params.items() if not k.startswith("block")}
        x = rest["embed"]["embedding"].astype(dtype)[tokens]
        for i in range(int(model["num_layers"])):
            position = i % len(pattern)
            kind = pattern[position]
            if "blocks" in params:
                slot = f"{kind.split('_')[0]}_{position}"
                layer = jax.tree.map(
                    lambda a: a[i // len(pattern)], params["blocks"][slot]
                )
            else:
                layer = params[f"block_{i}"]
            x = _block(items, kind, x, layer, lowered)
        return _head_nll(
            rest, x, targets, float(model["norm_eps"]), lowered
        )
