"""Plain reference for Granite-4.0-H (ibm-granite/granite-4.0-h-small's
layers, ``model_type`` ``granitemoehybrid``): forward, per-token losses, the
training loss and its gradients.

The equations (``config.json`` of ibm-granite/granite-4.0-h-small; Mamba-2,
Dao & Gu, arXiv:2405.21060 §6-7, for the mixer).  ``RMSNorm`` has eps
``norm_eps``; ``r`` = ``residual_multiplier``; no bias but the
convolution's::

    x_0 = embedding_multiplier E[t]
    layer:    a  = x + r Mix(RMSNorm(x))               by ``layer_types``
              x' = a + r (Routed(m) + Shared(m)),  m = RMSNorm(a)
    mamba:    [z | xBC | dt] = n W_in        (H P | H P + 2 G N | H)
              xBC = SiLU(conv(xBC) + b_conv): causal, depthwise, ``taps``
                  taps, zeros before the start;  x, B, C = xBC split
              dt = softplus(dt + dt_bias) a head;  A = -exp(A_log)
              per head h of group g(h) = h // (H / G), S_0 = 0 in R^{P x N}:
                  S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
                  y_t = S_t C_t + D x_t              ONE TOKEN AT A TIME
              (G = 1: ALL heads read the same B_t and C_t)
              y = RMSNorm_groups(y * SiLU(z)): gate first, the mean square
                  over each group's H P / G columns (G = 1: all of them),
                  one [H P] scale;  W_out
    attention: q = n W_q (H heads of hd), k, v = n W_k, n W_v (H_kv heads,
              each shared by H / H_kv query heads), NO rotation and no
              position of any kind; causal softmax(q k attention_multiplier)
              v;  W_o      (the multiplier is 1/128, not 128^-1/2)
    Routed:   l = m W_r over ALL num_experts, float32
              chosen = the top_k of l;  g = softmax over the CHOSEN l
              sum over the chosen e HELD HERE (first_expert .. first_expert
              + experts_held - 1) of g_e W_o,e (SiLU(W_g,e m) * W_u,e m):
              what the experts held elsewhere would add is left out, as in
              the program
    Shared:   W_so (SiLU(W_sg m) * W_su m), every token
    ends:     final RMSNorm, the TIED head: logits = n E^T / logits_scaling
    loss:     mean token NLL + moe_aux_weight x the sum over the layers of
              E sum_e f_e P_e  (f_e the share of tokens that chose e among
              their top_k, P_e the mean softmax probability over all E)

The program runs a published layer as two of its own (a mixer's branch,
then the expert layer's): ``layer_pattern`` holds those kinds and
``num_layers`` counts them, and this file walks them as they stand, one
branch ``x + r f(RMSNorm(x))`` after another, which is the layer above.
``embed_scale``, ``attention_scale``, ``residual_scale`` and ``logit_scale``
(1 / ``logits_scaling``) are the program's names for the four multipliers.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
chunk, no kernel, no cache, no sort, no sharding, no scan over layers.  It
reads the program's parameter tree only for the numbers in it.  One branch
at a time in one jitted function that every branch of its kind re-uses,
attention one head after another ([B, S, S] float32 scores at a time), the
held experts one after another into one accumulator, a group's B and C
handed to its heads inside the token's step, so it fits beside the model on
the chip at the published widths.

``lowered`` computes part of the model in bfloat16, to show that a
comparison's limit would catch it: ``"router"`` the router's logits, softmax
and gates; ``"ssm"`` the recurrence's decay, state and products; ``"all"``
every product, activation, the state, the logits and the loss as well.
``wrong`` breaks one piece (``no_embedding_multiplier``,
``no_attention_multiplier``, ``no_residual_multiplier``,
``no_logits_scaling``, ``sqrt_scale``, ``rotate``, ``softmax_all``,
``own_bc``, ``norm_before_gate``, ``ungated_expert``, ``no_shared``,
``untied_head``): only the tests set either, to show that the comparison
would catch a program with that fault.  A run sets neither.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp

F32, BF16 = jnp.float32, jnp.bfloat16
SSM, ATTENTION, EXPERTS = "ssm", "attention", "experts"


def _dtypes(lowered: str):
    """(trunk, router, recurrence) dtypes of a ``lowered`` mode."""
    return {
        "": (F32, F32, F32), "router": (F32, BF16, F32),
        "ssm": (F32, F32, BF16), "all": (BF16, BF16, BF16),
    }[lowered]


def _model(model) -> Mapping[str, Any]:
    if dataclasses.is_dataclass(model):
        return {f.name: getattr(model, f.name)
                for f in dataclasses.fields(model)}
    return model


def _items(model: Mapping[str, Any]) -> Tuple:
    return tuple(sorted(
        (k, v) for k, v in model.items()
        if isinstance(v, (int, float, str, bool)) or v is None
    ))


def rms_norm(x, scale, eps, dtype=F32):
    x32 = x.astype(F32)
    y = x32 / jnp.sqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(dtype)


def short_conv(x, taps, bias):
    """``y[t] = sum_j taps[j] x[t - (K - 1) + j] + bias`` per channel; what
    lies before the sequence is zero.  ``x`` [B, S, C], ``taps`` [K, C]."""
    k, s = taps.shape[0], x.shape[1]
    y = jnp.zeros_like(x)
    for j in range(k):
        back = k - 1 - j                  # tap j reads ``back`` tokens back
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, : s - back]], axis=1
        )
        y = y + shifted * taps[j]
    return y + bias


def ssm_recurrence(x, dt, a_head, b, c, d, dtype=F32):
    """The state-space recurrence, a token at a time; decay, state and
    products in ``dtype``.  ``x`` [B, S, H, P], ``dt`` [B, S, H], ``a_head``
    and ``d`` [H], ``b`` and ``c`` [B, S, G, N]: head ``h`` reads the rows
    of group ``h // (H / G)``."""
    batch, _, h, p = x.shape
    n = b.shape[3]

    def token(state, xs):
        x_t, dt_t, b_t, c_t = (v.astype(dtype) for v in xs)
        b_t, c_t = (
            jnp.repeat(v, h // v.shape[1], axis=1) for v in (b_t, c_t)
        )
        decay = jnp.exp(dt_t * a_head.astype(dtype))[..., None, None]
        state = (
            decay * state
            + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        ).astype(dtype)
        y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t)
        return state, (y_t + d.astype(dtype)[:, None] * x_t).astype(dtype)

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c))
    _, y = jax.lax.scan(token, jnp.zeros((batch, h, p, n), dtype), xs)
    return jnp.moveaxis(y, 0, 1)


def ssm_mixer(model, n, p, dtype=F32, ssm_dtype=F32, wrong=""):
    h, hp = int(model["ssm_num_heads"]), int(model["ssm_head_dim"])
    g, ns = int(model["ssm_groups"]), int(model["ssm_state_size"])
    inner, bc = h * hp, g * ns
    batch, s, _ = n.shape
    proj = n @ p["in_proj"]["kernel"].astype(dtype)
    z, xbc = proj[..., :inner], proj[..., inner: 2 * inner + 2 * bc]
    dt = proj[..., 2 * inner + 2 * bc:]
    xbc = jax.nn.silu(short_conv(
        xbc, p["conv_kernel"].astype(dtype), p["conv_bias"].astype(dtype)
    ))
    x = xbc[..., :inner].reshape(batch, s, h, hp)
    b = xbc[..., inner: inner + bc].reshape(batch, s, g, ns)
    c = xbc[..., inner + bc:].reshape(batch, s, g, ns)
    if wrong == "own_bc":
        # every head a B of its own: its group's, turned by the head's
        # number along the state (C stays the group's)
        b = jnp.stack([
            jnp.roll(b[:, :, i // (h // g)], i, axis=-1) for i in range(h)
        ], axis=2)
    dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32))
    a_head = -jnp.exp(p["A_log"].astype(F32))
    d = p["D"].astype(F32)
    y = ssm_recurrence(x, dt, a_head, b, c, d, ssm_dtype).astype(dtype)
    y = y.reshape(batch, s, inner)
    eps = float(model["norm_eps"])

    def group_norm(v):
        v32 = v.astype(F32).reshape(batch, s, g, inner // g)
        v32 = v32 / jnp.sqrt((v32 * v32).mean(-1, keepdims=True) + eps)
        return v32.reshape(batch, s, inner)

    gate = jax.nn.silu(z)
    if wrong == "norm_before_gate":
        y = group_norm(y) * p["out_norm_scale"].astype(F32) * gate.astype(F32)
    else:
        y = group_norm(y * gate) * p["out_norm_scale"].astype(F32)
    return y.astype(dtype) @ p["out_proj"]["kernel"].astype(dtype)


def _rope(x, theta):
    """Rotate-half RoPE on [B, S, H, hd]: what this model does NOT do."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1
    ).astype(x.dtype)


def attention(model, n, p, dtype=F32, wrong=""):
    def w(name):
        return p[name]["kernel"].astype(dtype)

    q = jnp.einsum("bsd,dhk->bshk", n, w("query"))        # [B, S, H, hd]
    k = jnp.einsum("bsd,dhk->bshk", n, w("key"))          # [B, S, H_kv, hd]
    v = jnp.einsum("bsd,dhk->bshk", n, w("value"))
    if wrong == "rotate":
        q, k = _rope(q, 10000.0), _rope(k, 10000.0)
    heads, hd = q.shape[2], q.shape[3]
    multiplier = float(model["attention_scale"])
    if wrong == "sqrt_scale":
        multiplier = hd ** -0.5
    if wrong == "no_attention_multiplier":
        multiplier = 1.0
    share = heads // k.shape[2]
    s = n.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    # query head h reads key/value head h // share
    k, v = (jnp.repeat(a, share, axis=2) for a in (k, v))

    def head(qkv):
        q_h, k_h, v_h = qkv                               # [B, S, hd]
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k_h).astype(F32) * F32(
            multiplier
        )
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        return jnp.einsum("bqk,bkd->bqd", probs, v_h)

    # one head after another: [B, S, S] float32 scores at a time
    o = jax.lax.map(head, tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v)))
    return jnp.einsum("hbqd,hdm->bqm", o, w("out"))


def gated_mlp(n, w_up, w_gate, w_down, wrong=""):
    up = n @ w_up
    if wrong == "ungated_expert":
        return jax.nn.silu(up) @ w_down
    return (jax.nn.silu(n @ w_gate) * up) @ w_down


def router(model, n, p, router_dtype=F32, wrong=""):
    """``(gates [B, S, E], balance term)`` over ALL the experts: a token's
    gate for each expert (0 where it was not chosen), and ``E sum_e f_e
    P_e`` of this layer."""
    e, k = int(model["num_experts"]), int(model["top_k"])
    logits = n.astype(router_dtype) @ p["router"]["kernel"].astype(
        router_dtype
    )
    top_l, top_i = jax.lax.top_k(logits, k)
    probs = jax.nn.softmax(logits, axis=-1)
    if wrong == "softmax_all":            # over all E, not renormalised
        top_g = jnp.take_along_axis(probs, top_i, axis=-1)
    else:
        top_g = jax.nn.softmax(top_l, axis=-1)
    chosen = jax.nn.one_hot(top_i, e, dtype=router_dtype)  # [B, S, k, E]
    gates = (chosen * top_g[..., None]).sum(-2)
    f = chosen.astype(F32).sum(-2).mean(axis=(0, 1))
    balance = e * jnp.sum(f * probs.astype(F32).mean(axis=(0, 1)))
    return gates, balance


def routed_part(model, n, p, dtype=F32, router_dtype=F32, wrong=""):
    """``(sum over the chosen experts HELD HERE of g_e W_o,e (SiLU(W_g,e m)
    * W_u,e m), balance term)``; ``p["wi"]`` (up), ``p["wg"]`` (gate) and
    ``p["wo"]`` hold the held experts only."""
    held = p["wi"].shape[0]
    first = int(model.get("first_expert") or 0)
    gates, balance = router(model, n, p, router_dtype, wrong)

    def add_expert(i, out):
        def w(name):
            return jax.lax.dynamic_index_in_dim(
                p[name], i, 0, False
            ).astype(dtype)

        y = gated_mlp(n, w("wi"), w("wg"), w("wo"), wrong)
        gate = jax.lax.dynamic_index_in_dim(gates, first + i, 2, True)
        return out + (y.astype(router_dtype) * gate).astype(dtype)

    # one expert after another into one accumulator
    out = jax.lax.fori_loop(0, held, add_expert, jnp.zeros_like(n))
    return out, balance


def shared_part(n, p, dtype=F32, wrong=""):
    return gated_mlp(
        n, p["wi"]["kernel"].astype(dtype), p["wg"]["kernel"].astype(dtype),
        p["wo"]["kernel"].astype(dtype), wrong,
    )


def expert_layer(model, n, p, dtype=F32, router_dtype=F32, wrong=""):
    out, balance = routed_part(model, n, p, dtype, router_dtype, wrong)
    if "shared" in p and wrong != "no_shared":
        out = out + shared_part(n, p["shared"], dtype, wrong)
    return out, balance


@functools.partial(jax.jit, static_argnums=(0, 1, 4, 5))
def _branch(model_items, kind, x, p, lowered, wrong):
    """One residual branch ``x + r f(RMSNorm(x))``; ``balance`` is ``None``
    but for an expert layer."""
    model = dict(model_items)
    dtype, router_dtype, ssm_dtype = _dtypes(lowered)
    n = rms_norm(x, p["ln"]["scale"], float(model["norm_eps"]), dtype)
    balance = None
    if kind == SSM:
        y = ssm_mixer(model, n, p["ssm"], dtype, ssm_dtype, wrong)
    elif kind == ATTENTION:
        y = attention(model, n, p["attn"], dtype, wrong)
    elif kind == EXPERTS:
        y, balance = expert_layer(
            model, n, p["moe"], dtype, router_dtype, wrong
        )
    else:
        raise ValueError(f"Granite-4.0-H has no layer of kind {kind!r}")
    r = 1.0 if wrong == "no_residual_multiplier" else float(
        model["residual_scale"]
    )
    return x + dtype(r) * y, balance


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _head_nll(norm_scale, table, x, targets, eps, divide, lowered, wrong):
    dtype = _dtypes(lowered)[0]
    x = rms_norm(x, norm_scale, eps, dtype)
    head = table.astype(dtype)
    if wrong == "untied_head":            # another matrix than the table
        head = jnp.roll(head, 1, axis=0)
    logits = (x @ head.T) * dtype(1.0 if wrong == "no_logits_scaling"
                                  else divide)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0].astype(
        F32
    )


def trunk_layers(model, params) -> List[Tuple[str, Any]]:
    """``(kind, parameters)`` of each of the program's layers in order:
    from the scanned tree (slot ``<kind>_<position>`` of period ``i //
    len(pattern)``) or the unrolled one (``block_<i>``)."""
    pattern = tuple(model["layer_pattern"])
    out = []
    for i in range(int(model["num_layers"])):
        position = i % len(pattern)
        kind = pattern[position]
        if "blocks" in params:
            layer = jax.tree.map(
                lambda a: a[i // len(pattern)],
                params["blocks"][f"{kind}_{position}"],
            )
        else:
            layer = params[f"block_{i}"]
        out.append((kind, layer))
    return out


def forward(model, params, tokens, targets=None, lowered: str = "",
            wrong: str = "") -> Dict[str, Any]:
    """``hidden`` (before the final norm), ``balance`` (the sum over the
    expert layers of their balance terms) and, with ``targets``, ``nll``
    [B, S]."""
    model = _model(model)
    items = _items(model)
    dtype = _dtypes(lowered)[0]
    with jax.default_matmul_precision("highest"):
        table = params["embed"]["embedding"]
        multiplier = 1.0 if wrong == "no_embedding_multiplier" else float(
            model["embed_scale"]
        )
        x = table.astype(dtype)[tokens] * dtype(multiplier)
        balance = F32(0.0)
        for kind, layer in trunk_layers(model, params):
            x, layer_balance = _branch(items, kind, x, layer, lowered, wrong)
            if layer_balance is not None:
                balance = balance + layer_balance
        out = {"hidden": x, "balance": balance}
        if targets is not None:
            out["nll"] = _head_nll(
                params["ln_final"]["scale"], table, x, targets,
                float(model["norm_eps"]), float(model["logit_scale"]),
                lowered, wrong,
            )
        return out


def token_nll(model, params, tokens, targets, lowered: str = "",
              wrong: str = ""):
    """Per-token negative log-likelihood [B, S], float32.

    ``model`` is the ``model`` group of a configuration file (the
    program's ``TransformerConfig`` fields as plain numbers, strings and
    the ``layer_pattern`` list) or the config itself; ``params`` the
    program's parameter tree."""
    return forward(model, params, tokens, targets, lowered, wrong)["nll"]


def loss(model, params, tokens, targets):
    """Mean token NLL plus ``moe_aux_weight`` x the balance terms: what
    the step trains."""
    model = _model(model)
    out = forward(model, params, tokens, targets)
    return out["nll"].mean() + F32(model["moe_aux_weight"]) * out["balance"]


def loss_and_grads(model, params, tokens, targets):
    return jax.value_and_grad(loss, argnums=1)(model, params, tokens, targets)
