"""Olmo-Hybrid's forward pass, loss and gradients, written plainly.

The reference the program is held to (``tests/test_olmo_hybrid_reference.py``):
float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, the
gated delta rule as its recurrence ONE TOKEN AT A TIME, no chunk, no
kernel, no cache.  Written from the model's ``config.json``
(huggingface.co/allenai/Olmo-Hybrid-7B) and the description of its linear
layers (Gated DeltaNet, Yang et al., arXiv:2412.06464; the delta rule's
parallel form, arXiv:2406.06484); it reads the program's parameter tree
only for the numbers in it.

The equations (``d`` hidden, ``H`` heads, key heads ``dk``, value heads
``dv``, ``n`` the mixer's input)::

    block:    h = x + Norm(Mixer(x));  x' = h + Norm(SwiGLU(h))
              layer i's mixer is ``layer_pattern[i mod period]``;
              final RMSNorm, untied head

    linear:   q, k, v = W_q n, W_k n, W_v n            (no bias)
              q, k, v <- SiLU(causal depthwise conv over the sequence,
                  ``taps`` taps, own taps a channel, no bias)
              per head:  q <- q / ||q|| * dk^-1/2,   k <- k / ||k||
              beta = sigmoid(W_b n);  allow_neg_eigval: beta <- 2 beta
              g = -exp(A_log) * softplus(W_a n + dt_bias);  alpha = exp(g)
              S_0 = 0 in R^{dv x dk}
              S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
              o_t = S_t q_t
              y = RMSNorm_dv(o_t; one [dv] scale) * SiLU(W_g n)   per head
              out = W_o y

    full:     q, k, v = W_q n, W_k n, W_v n;  q <- RMSNorm_q(q),
              k <- RMSNorm_k(k) over all H * hd outputs, own scale each;
              H heads of hd; rotate-half RoPE (theta) on q and k;
              causal softmax(q k^T / sqrt(hd)) v;  W_o

    loss = mean token NLL

Departures from the published model, each noted where it is made:

* the norm placement and the joint QK-norm are the family's (OLMo 2 and
  3); the published config names neither.
* ``rope_theta`` is published as null; the caller's value is used (the
  configuration takes the family's 500,000).
* ``||x||`` is ``sqrt(sum x^2 + 1e-6)``, as the Gated DeltaNet kernels
  take it.

``undo`` switches one piece of the layer off (``beta_doubling``,
``decay``, ``k_norm``, ``conv``, ``out_gate``, ``post_norm``): only the
tests set it, to show that the comparison would catch a program without
that piece.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import jax
import jax.numpy as jnp

F32 = jnp.float32
L2_EPS = 1e-6
LINEAR = "linear_attention"


def _model(model) -> Mapping[str, Any]:
    if dataclasses.is_dataclass(model):
        return {f.name: getattr(model, f.name)
                for f in dataclasses.fields(model)}
    return model


def _f32(x):
    return jnp.asarray(x).astype(F32)


def rms_norm(x, scale, eps):
    """Over the last axis of ``x``; ``scale`` has its length."""
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def l2_normalise(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def rope(x, theta):
    """Rotate-half RoPE on [B, S, H, hd], positions 0..S-1."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def full_attention(model, n, p):
    heads = int(model["num_heads"])
    w = _f32(p["qkv"]["kernel"])          # [d, H, 3 hd], per head q | k | v
    hd = w.shape[-1] // 3
    q, k, v = (
        jnp.einsum("bsd,dhk->bshk", n, part).reshape(*n.shape[:2], -1)
        for part in (w[..., :hd], w[..., hd:2 * hd], w[..., 2 * hd:])
    )
    eps = float(model["norm_eps"])
    q = rms_norm(q, p["q_norm"]["scale"], eps)
    k = rms_norm(k, p["k_norm"]["scale"], eps)
    b, s, _ = q.shape
    q, k, v = (a.reshape(b, s, heads, hd) for a in (q, k, v))
    theta = float(model["rope_theta"])
    q, k = rope(q, theta), rope(k, theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bqhd,hdm->bqm", o, _f32(p["out"]["kernel"]))


def short_conv(x, taps):
    """``y[t] = sum_j taps[j] x[t - (K - 1) + j]`` per channel; what lies
    before the sequence is zero.  ``x`` [B, S, C...], ``taps`` [K, C...]."""
    k, s = taps.shape[0], x.shape[1]
    y = jnp.zeros_like(x)
    for j in range(k):
        back = k - 1 - j                  # tap j reads ``back`` tokens back
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, : s - back]], axis=1
        )
        y = y + shifted * taps[j]
    return y


def delta_rule_recurrence(q, k, v, g, beta):
    """The rule, a token at a time.  ``q, k`` [B, S, H, dk], ``v``
    [B, S, H, dv], ``g`` (log decay) and ``beta`` [B, S, H]."""
    b, _, h, dk = q.shape
    dv = v.shape[-1]

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        alpha = jnp.exp(g_t)[..., None, None]
        bt = beta_t[..., None, None]
        kk = k_t[..., :, None] * k_t[..., None, :]            # k k^T
        state = alpha * (state - bt * state @ kk) + bt * (
            v_t[..., :, None] * k_t[..., None, :]
        )
        return state, jnp.einsum("bhvk,bhk->bhv", state, q_t)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(token, jnp.zeros((b, h, dv, dk), F32), xs)
    return jnp.moveaxis(o, 0, 1)


def linear_attention(model, n, p, undo=""):
    dk = int(model["linear_key_head_dim"])
    dv = int(model["linear_value_head_dim"])
    # the program's one [d, H, 2 dk + 2 dv] kernel, per head q | k | v | z
    qkvz = jnp.einsum("bsd,dhc->bshc", n, _f32(p["qkvg"]["kernel"]))
    qkv, z = qkvz[..., : 2 * dk + dv], qkvz[..., 2 * dk + dv:]
    if undo != "conv":
        qkv = short_conv(qkv, _f32(p["conv_kernel"]))
    qkv = jax.nn.silu(qkv)
    q = l2_normalise(qkv[..., :dk]) * dk ** -0.5
    k = qkv[..., dk: 2 * dk]
    if undo != "k_norm":
        k = l2_normalise(k)
    v = qkv[..., 2 * dk:]
    ab = jnp.einsum("bsd,dhc->bshc", n, _f32(p["ab_kernel"]))
    g = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(
        ab[..., 0] + _f32(p["dt_bias"])
    )
    if undo == "decay":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(ab[..., 1])
    if model["linear_allow_neg_eigval"] and undo != "beta_doubling":
        beta = 2.0 * beta
    o = delta_rule_recurrence(q, k, v, g, beta)
    y = rms_norm(o, p["out_norm_scale"], float(model["norm_eps"]))
    if undo != "out_gate":
        y = y * jax.nn.silu(z)
    return jnp.einsum("bshv,hvm->bsm", y, _f32(p["wo"]["kernel"]))


def swiglu(n, p):
    return (
        jax.nn.silu(n @ _f32(p["wg"]["kernel"])) * (n @ _f32(p["wi"]["kernel"]))
    ) @ _f32(p["wo"]["kernel"])


def layer_params(model, params, i):
    """``(kind, parameters)`` of layer ``i``: from the scanned tree (slot
    ``<kind>_<position>`` of period ``i // len(pattern)``) or the unrolled
    one (``block_<i>``)."""
    pattern = tuple(model["layer_pattern"])
    position = i % len(pattern)
    kind = pattern[position]
    if "blocks" in params:
        slot = f"{kind.split('_')[0]}_{position}"
        return kind, jax.tree.map(
            lambda a: a[i // len(pattern)], params["blocks"][slot]
        )
    return kind, params[f"block_{i}"]


def block(model, x, kind, p, undo=""):
    eps = float(model["norm_eps"])
    if kind == LINEAR:
        def mixer(n):
            return linear_attention(model, n, p["linear_attn"], undo)
    else:
        def mixer(n):
            return full_attention(model, n, p["attn"])
    if undo == "post_norm":               # the pre-norm placement instead
        x = x + mixer(rms_norm(x, p["ln_attn"]["scale"], eps))
        return x + swiglu(rms_norm(x, p["ln_mlp"]["scale"], eps), p["mlp"])
    x = x + rms_norm(mixer(x), p["ln_attn"]["scale"], eps)
    return x + rms_norm(swiglu(x, p["mlp"]), p["ln_mlp"]["scale"], eps)


def forward(model, params, tokens, undo=""):
    """Logits [B, S, V]."""
    model = _model(model)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"]["embedding"])[tokens]
        for i in range(int(model["num_layers"])):
            kind, p = layer_params(model, params, i)
            x = block(model, x, kind, p, undo)
        x = rms_norm(x, params["ln_final"]["scale"], float(model["norm_eps"]))
        return x @ _f32(params["lm_head"]["kernel"])


def _nll(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def token_nll(model, params, tokens, targets, undo=""):
    """Per-token negative log-likelihood [B, S]."""
    return _nll(forward(model, params, tokens, undo), targets)


def loss(model, params, tokens, targets, undo=""):
    return token_nll(model, params, tokens, targets, undo).mean()


def loss_and_grads(model, params, tokens, targets):
    return jax.value_and_grad(loss, argnums=1)(model, params, tokens, targets)
