"""Plain reference for Nemotron-H (NVIDIA-Nemotron-3-Nano-30B-A3B's layers):
forward, per-token losses, the training loss and its gradients, the
router-bias rule.

The equations (``config.json`` of nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16;
Nemotron-H, arXiv:2504.03624, for the layer and its attention without
positions; Mamba-2, Dao & Gu, arXiv:2405.21060 §6-7, for the mixer;
DeepSeek-V3, arXiv:2412.19437 §2.1.2, for the router and its bias).
``n = RMSNorm(x)``, eps ``norm_eps``, one norm a layer, pre-norm, no bias
but the convolution's::

    layer:    x' = x + f(n),  f by the layer's kind (``layer_pattern``):
    ssm:      [z | xBC | dt] = n W_in        (H P | H P + 2 G N | H)
              xBC = SiLU(conv(xBC) + b_conv): causal, depthwise, ``taps``
                  taps, zeros before the start;  x, B, C = xBC split
              dt = softplus(dt + dt_bias) a head;  A = -exp(A_log)
              per head h of group g(h) = h // (H / G), S_0 = 0 in R^{P x N}:
                  S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
                  y_t = S_t C_t + D x_t              ONE TOKEN AT A TIME
              y = RMSNorm_groups(y * SiLU(z)): gate first, the mean square
                  over each group's H P / G columns, one [H P] scale;  W_out
    attention: q = n W_q (H heads of hd), k, v = n W_k, n W_v (H_kv heads,
              each shared by H / H_kv query heads), NO positional rotation;
              causal softmax(q k / sqrt(hd)) v;  W_o
    experts:  s = sigmoid(n W_r) over ALL num_experts, float32
              chosen = the top_k of s + b  (b picks, it never weighs)
              g_e = routed_scaling_factor s_e / (sum_chosen s + 1e-20)
              out = W_sd relu(W_su n)^2 + sum over the chosen e HELD HERE
                    (first_expert .. first_expert + experts_held - 1) of
                    g_e W_d,e relu(W_u,e n)^2: what the experts held
                    elsewhere would add is left out, as in the program
    mlp:      W_d relu(W_u n)^2
    ends:     embedding, final RMSNorm, untied head
    loss:     mean token NLL
    after a step: b_e += router_bias_rate x sign(mean load - load_e), the
              loads that step's own counts over all num_experts, per layer

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
chunk, no kernel, no cache, no sort, no sharding, no scan over layers.  It
reads the program's parameter tree only for the numbers in it.  One layer at
a time in one jitted function that every layer of its kind re-uses,
attention one head after another ([B, S, S] float32 scores at a time), the
held experts one after another into one accumulator, so it fits beside the
model on the chip at the published widths.

Departures from the published model: the family's balance loss is LEFT
OUT, as in the program (the bias rule balances); ``rope_theta``,
``partial_rotary_factor`` and ``max_position_embeddings`` are keys the
attention does not read (Nemotron-H's attention layers carry no position
encoding: the state-space layers give the order).

``lowered`` computes part of the model in bfloat16, to show that a
comparison's limit would catch it: ``"router"`` the router's logits, scores
and gates; ``"ssm"`` the recurrence's decay, state and products; ``"all"``
every product, activation, the state, the logits and the loss as well.
``wrong`` breaks one piece of a layer (``decay_sign``, ``no_skip``,
``norm_before_gate``, ``own_bc``, ``gated_expert``, ``no_square``,
``bias_weighs``, ``rotate``): only the tests set either, to show that the
comparison would catch a program with that fault.  A run sets neither.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp

F32, BF16 = jnp.float32, jnp.bfloat16
SSM, ATTENTION, EXPERTS, MLP = "ssm", "attention", "experts", "mlp"


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
    and ``d`` [H], ``b`` and ``c`` [B, S, H, N] (a head's own rows: the
    caller repeats a group's)."""
    batch, _, h, p = x.shape
    n = b.shape[-1]

    def token(state, xs):
        x_t, dt_t, b_t, c_t = (v.astype(dtype) for v in xs)
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
    # a head reads the B and C of its group
    b, c = (jnp.repeat(v, h // g, axis=2) for v in (b, c))
    if wrong == "own_bc":                 # every head the FIRST group's
        b, c = (
            jnp.repeat(v[:, :, :1], h, axis=2) for v in (b, c)
        )
    dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32))
    a_head = -jnp.exp(p["A_log"].astype(F32))
    if wrong == "decay_sign":
        a_head = -a_head
    d = p["D"].astype(F32)
    if wrong == "no_skip":
        d = jnp.zeros_like(d)
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
    share = heads // k.shape[2]
    s = n.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    # query head h reads key/value head h // share
    k, v = (jnp.repeat(a, share, axis=2) for a in (k, v))

    def head(qkv):
        q_h, k_h, v_h = qkv                               # [B, S, hd]
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k_h).astype(F32) / jnp.sqrt(
            F32(hd)
        )
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        return jnp.einsum("bqk,bkd->bqd", probs, v_h)

    # one head after another: [B, S, S] float32 scores at a time
    o = jax.lax.map(head, tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v)))
    return jnp.einsum("hbqd,hdm->bqm", o, w("out"))


def relu2_mlp(n, w_up, w_down, wrong=""):
    hidden = jax.nn.relu(n @ w_up)
    if wrong != "no_square":
        hidden = hidden * hidden
    return hidden @ w_down


def router(model, n, p, router_dtype=F32, wrong=""):
    """``(gates [B, S, E], counts [E])`` over ALL the experts: a token's
    gate for each expert (0 where it was not chosen), and how many tokens
    chose each."""
    e, k = int(model["num_experts"]), int(model["top_k"])
    logits = n.astype(router_dtype) @ p["router"]["kernel"].astype(
        router_dtype
    )
    scores = jax.nn.sigmoid(logits)
    pick = scores
    if "router_bias" in p:
        pick = scores + p["router_bias"].astype(router_dtype)
    _, top_i = jax.lax.top_k(pick, k)
    top_s = jnp.take_along_axis(
        pick if wrong == "bias_weighs" else scores, top_i, axis=-1
    )
    if model.get("norm_topk_prob", True):
        top_s = top_s / (top_s.sum(-1, keepdims=True) + 1e-20)
    top_s = top_s * router_dtype(model.get("routed_scaling_factor", 1.0))
    chosen = jax.nn.one_hot(top_i, e, dtype=router_dtype)  # [B, S, k, E]
    gates = (chosen * top_s[..., None]).sum(-2)
    return gates, chosen.astype(F32).sum(axis=(0, 1, 2))


def routed_part(model, n, p, dtype=F32, router_dtype=F32, wrong=""):
    """``(sum over the chosen experts HELD HERE of g_e W_d,e relu(W_u,e
    n)^2, counts [E])``; ``p["wi"]``, ``p["wo"]`` hold the held experts
    only."""
    held = p["wi"].shape[0]
    first = int(model.get("first_expert") or 0)
    gates, counts = router(model, n, p, router_dtype, wrong)

    def add_expert(i, out):
        def w(name):
            return jax.lax.dynamic_index_in_dim(
                p[name], i, 0, False
            ).astype(dtype)

        if wrong == "gated_expert":       # the up matrix as its own gate
            up = n @ w("wi")
            y = (jax.nn.silu(up) * up) @ w("wo")
        else:
            y = relu2_mlp(n, w("wi"), w("wo"), wrong)
        gate = jax.lax.dynamic_index_in_dim(gates, first + i, 2, True)
        return out + (y.astype(router_dtype) * gate).astype(dtype)

    # one expert after another into one accumulator
    out = jax.lax.fori_loop(0, held, add_expert, jnp.zeros_like(n))
    return out, counts


def expert_layer(model, n, p, dtype=F32, router_dtype=F32, wrong=""):
    out, counts = routed_part(model, n, p, dtype, router_dtype, wrong)
    if "shared" in p:
        shared = p["shared"]
        out = out + relu2_mlp(
            n, shared["wi"]["kernel"].astype(dtype),
            shared["wo"]["kernel"].astype(dtype), wrong,
        )
    return out, counts


@functools.partial(jax.jit, static_argnums=(0, 1, 4, 5))
def _layer(model_items, kind, x, p, lowered, wrong):
    """One layer; ``counts`` is ``None`` but for an expert layer."""
    model = dict(model_items)
    dtype, router_dtype, ssm_dtype = _dtypes(lowered)
    n = rms_norm(x, p["ln"]["scale"], float(model["norm_eps"]), dtype)
    counts = None
    if kind == SSM:
        y = ssm_mixer(model, n, p["ssm"], dtype, ssm_dtype, wrong)
    elif kind == ATTENTION:
        y = attention(model, n, p["attn"], dtype, wrong)
    elif kind == EXPERTS:
        y, counts = expert_layer(
            model, n, p["moe"], dtype, router_dtype, wrong
        )
    else:
        mlp = p["mlp"]
        y = relu2_mlp(
            n, mlp["wi"]["kernel"].astype(dtype),
            mlp["wo"]["kernel"].astype(dtype), wrong,
        )
    return x + y, counts


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head_nll(norm_scale, head, x, targets, eps, lowered):
    dtype = _dtypes(lowered)[0]
    x = rms_norm(x, norm_scale, eps, dtype)
    logits = x @ head.astype(dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0].astype(
        F32
    )


def trunk_layers(model, params) -> List[Tuple[str, Any]]:
    """``(kind, parameters)`` of each layer in order: from the scanned tree
    (slot ``<kind>_<position>`` of period ``i // len(pattern)``) or the
    unrolled one (``block_<i>``)."""
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
    """``hidden`` (before the final norm), ``counts`` (each expert layer's
    tokens per expert over all ``num_experts``, in order) and, with
    ``targets``, ``nll`` [B, S]."""
    model = _model(model)
    items = _items(model)
    dtype = _dtypes(lowered)[0]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"].astype(dtype)[tokens]
        counts = []
        for kind, layer in trunk_layers(model, params):
            x, layer_counts = _layer(items, kind, x, layer, lowered, wrong)
            if layer_counts is not None:
                counts.append(layer_counts)
        out = {"hidden": x, "counts": counts}
        if targets is not None:
            out["nll"] = _head_nll(
                params["ln_final"]["scale"], params["lm_head"]["kernel"], x,
                targets, float(model["norm_eps"]), lowered,
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
    """``mean(nll)``: what the step trains."""
    return token_nll(model, params, tokens, targets).mean()


def loss_and_grads(model, params, tokens, targets):
    return jax.value_and_grad(loss, argnums=1)(model, params, tokens, targets)


def bias_rule(bias, counts, rate: float):
    """``b_e += rate x sign(mean load - load_e)`` from one step's counts."""
    counts = counts.astype(F32)
    return bias.astype(F32) + F32(rate) * jnp.sign(counts.mean() - counts)
