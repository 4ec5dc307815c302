"""Plain reference for Mellum2-12B-A2.5B (``model_type`` ``mellum``):
forward, per-token losses, the training loss and its gradients.

The equations (``config.json`` of JetBrains/Mellum2-12B-A2.5B-Instruct;
YaRN is arXiv:2309.00071 as the ``transformers`` library computes it).
``n = RMSNorm(x)``, eps ``norm_eps``, pre-norm, no biases, no multipliers::

    a = x + Attn_kind(RMSNorm(x));  x' = a + Experts(RMSNorm(a))
    layer i's kind is layer_pattern[i mod period]: three sliding_attention
    to one full_attention.  Final RMSNorm; an UNTIED head.

    Attn(n):  q_h = Rot_kind(W_q n),  k_g = Rot_kind(W_k n): H query heads
              over H_kv key/value heads of hd, rotate-half over the whole
              head; softmax(q_h k_g^T / sqrt(hd) + mask_kind) v_g;  W_o.
              NO QK-norm.
      sliding_attention:  mask(i, j) = 0 if 0 <= i - j < sliding_window else
              -inf (a query sees itself and the W - 1 tokens before it);
              Rot = plain RoPE, inv_i = theta^(-2 i / hd), i = 0 .. hd/2 - 1
      full_attention:  the causal mask; Rot = YaRN: with
              c(beta) = hd ln(L0 / (2 pi beta)) / (2 ln theta),
              low = max(floor(c(beta_fast)), 0), high = min(ceil(c(beta_slow)),
              hd - 1), ramp_i = clip((i - low) / (high - low), 0, 1),
              inv'_i = inv_i (1 - ramp_i) + (inv_i / factor) ramp_i;
              cos and sin of p inv' BOTH times attention_factor, so a score
              carries its square
    Experts(n): p = softmax(n W_r) over ALL num_experts, float32; the top_k
              largest; g_e = p_e / sum of the chosen p (norm_topk_prob)
              out = sum over the chosen e HELD HERE (first_expert ..
                    first_expert + experts_held - 1) of g_e W_o,e (SiLU(W_g,e
                    n) * W_u,e n): what the experts held elsewhere would add
                    is left out, as in the program.  No shared expert.
    loss:     mean token NLL + moe_aux_weight x the sum over the layers of
              E sum_e f_e P_e (f_e the share of tokens that chose e in any
              of their k places, P_e the mean probability)

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
kernel, no cache, no sharding, no scan over layers.  The band is an explicit
``[rows, S]`` mask built from ``i - j``; YaRN's table is made here from the
five published numbers.  It reads the program's parameter tree only for the
numbers in it.  It takes the share (``experts_held`` is the held weights'
leading axis, ``first_expert`` a field; the vocabulary is whatever the
embedding and the head hold, so a sliced one is a smaller one) and works in
blocks so that the published widths at 32,768 tokens fit beside the model
on the chip: one layer at a time in one jitted function a kind, attention
one head after another and a head in blocks of :data:`ROWS` query rows (a
block's scores ``[B, 2048, S]`` float32: 268 MB at 32,768), the held experts
one after another into one accumulator, the head and the loss in blocks of
:data:`ROWS` tokens.

Departures from the published model, each the configuration file's
(``benchmark/configs/mellum2-12b-a2.5b.json`` ``assumed``): pre-norm
placement, no QK-norm, the half-open window and the balance term's weight
are not settled by the catalog's config; the multi-token head the model
card mentions has no key there and is LEFT OUT.

``lowered`` computes part of the model in bfloat16, to show that a
comparison's limit would catch it: ``"attention"`` the whole attention
branch (projections; the rotation's positions, angles, cos and sin, so a
position past 256 is no longer itself; scores, softmax, output);
``"router"`` the router's logits, probabilities and gates; ``"all"`` every
product, the logits and the loss as well.  ``wrong`` makes one fault, for the tests that
show the comparison sharp (:data:`FAULTS`).  A run sets neither.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp

F32, BF16 = jnp.float32, jnp.bfloat16
SLIDING, FULL = "sliding_attention", "full_attention"
ROWS = 2048

FAULTS = (
    "no_window",              # every layer the causal mask
    "window_plus_1",          # W + 1 keys
    "window_minus_1",         # W - 1 keys
    "window_on_full",         # the full layers banded too
    "one_sliding_unwindowed", # layer 1 under the causal mask
    "kinds_reordered",        # (full, sliding, sliding, sliding)
    "yarn_on_sliding",        # the sliding layers rotated as the full ones
    "no_yarn",                # plain RoPE everywhere
    "interpolate_all",        # every column's frequency divided by factor
    "low_high_swapped",       # the ramp between high and low
    "low_off_by_one",         # low + 1
    "high_off_by_one",        # high - 1
    "factor_on_cos_only",     # sin without the attention factor
    "factor_once",            # the scores times the factor, not its square
    "no_factor",              # attention factor 1
    "theta_10000",            # rope_theta 10,000
    "qk_norm",                # an RMSNorm per head on q and on k
    "no_renorm",              # the chosen probabilities as they are
    "top_k_of_held",          # the top_k of the experts held here alone
    "sigmoid_router",         # sigmoid scores in place of softmax
)


def _dtypes(lowered: str):
    """(trunk dtype, attention dtype, router dtype) of a ``lowered`` mode."""
    return {
        "": (F32, F32, F32), "attention": (F32, BF16, F32),
        "router": (F32, F32, BF16), "all": (BF16, BF16, BF16),
    }[lowered]


def _items(model: Mapping[str, Any]) -> Tuple:
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, (list, tuple)) else v)
        for k, v in model.items()
        if isinstance(v, (int, float, str, bool, list, tuple)) or v is None
    ))


def rms_norm(x, scale, eps, dtype=F32):
    x32 = x.astype(F32)
    y = x32 / jnp.sqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(dtype)


def yarn_range(model, wrong="") -> Tuple[int, int]:
    """``(low, high)`` out of the published numbers."""
    hd = int(model["head_dim"])
    theta = float(model["rope_theta"])
    original = float(model["rope_original_max_position"])

    def c(beta):
        return hd * math.log(original / (2 * math.pi * beta)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(c(float(model["rope_beta_fast"]))), 0)
    high = min(math.ceil(c(float(model["rope_beta_slow"]))), hd - 1)
    if wrong == "low_high_swapped":
        low, high = high, low
    if wrong == "low_off_by_one":
        low += 1
    if wrong == "high_off_by_one":
        high -= 1
    return low, high


def rotation_table(model, rotation: str, wrong=""):
    """``(inverse frequencies [hd / 2], factor on cos, factor on sin)`` of
    ``rotation`` ``"plain"`` or ``"yarn"``."""
    hd = int(model["head_dim"])
    theta = 10000.0 if wrong == "theta_10000" else float(model["rope_theta"])
    i = jnp.arange(hd // 2, dtype=F32)
    inv = theta ** (-2.0 * i / hd)
    if rotation == "plain" or wrong == "no_yarn":
        return inv, 1.0, 1.0
    s = float(model["rope_scaling_factor"])
    if wrong == "interpolate_all":
        inv = inv / s
    else:
        low, high = yarn_range(dict(model, rope_theta=theta), wrong)
        ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
        inv = inv * (1.0 - ramp) + (inv / s) * ramp
    f = float(model["rope_attention_factor"])
    if wrong in ("no_factor", "factor_once"):
        return inv, 1.0, 1.0
    return inv, f, 1.0 if wrong == "factor_on_cos_only" else f


def rotate(x, table):
    """Rotate-half on ``[B, S, H, D]``, positions 0 .. S - 1, in ``x``'s
    precision: the positions, the angles, cos, sin and the products."""
    inv, on_cos, on_sin = table
    half = x.shape[-1] // 2
    dtype = x.dtype
    ang = (
        jnp.arange(x.shape[1]).astype(dtype)[:, None]
        * inv.astype(dtype)[None, :]
    )
    cos = (jnp.cos(ang) * on_cos).astype(dtype)[None, :, None, :]
    sin = (jnp.sin(ang) * on_sin).astype(dtype)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(model, n, p, mask: str, rotation: str, dtype=F32, wrong=""):
    """``mask`` ``"band"`` or ``"causal"``, ``rotation`` ``"plain"`` or
    ``"yarn"``: a layer kind's pair, or a fault's."""
    def w(name):
        return p[name]["kernel"].astype(dtype)

    n = n.astype(dtype)
    q = jnp.einsum("bsd,dhk->bshk", n, w("query"))          # [B, S, H, hd]
    k = jnp.einsum("bsd,dhk->bshk", n, w("key"))            # [B, S, Hkv, hd]
    v = jnp.einsum("bsd,dhk->hbsk", n, w("value"))          # [Hkv, B, S, hd]
    hd = q.shape[-1]
    if wrong == "qk_norm":
        ones = jnp.ones((hd,), F32)
        eps = float(model["norm_eps"])
        q, k = rms_norm(q, ones, eps, dtype), rms_norm(k, ones, eps, dtype)
    table = rotation_table(model, rotation, wrong)
    q = jnp.moveaxis(rotate(q, table), 2, 0)                # [H, B, S, hd]
    k = jnp.moveaxis(rotate(k, table), 2, 0)
    group = q.shape[0] // k.shape[0]
    s = n.shape[1]
    window = int(model["sliding_window"]) + {
        "window_plus_1": 1, "window_minus_1": -1,
    }.get(wrong, 0)
    on_scores = 1.0
    if wrong == "factor_once" and rotation == "yarn":
        on_scores = float(model["rope_attention_factor"])
    rows = ROWS if s % ROWS == 0 else s
    j = jnp.arange(s)[None, :]

    def head(xs):
        q_h, g = xs
        k_h = jax.lax.dynamic_index_in_dim(k, g, 0, False)
        v_h = jax.lax.dynamic_index_in_dim(v, g, 0, False)

        def block(xs):
            q_rows, first = xs                              # [B, rows, hd]
            i = first + jnp.arange(rows)[:, None]
            seen = i - j >= 0
            if mask == "band":
                seen = seen & (i - j < window)
            scores = jnp.einsum("bqd,bkd->bqk", q_rows, k_h) * jnp.asarray(
                on_scores / math.sqrt(hd), dtype
            )
            scores = jnp.where(seen[None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            return jnp.einsum("bqk,bkd->bqd", probs, v_h)

        # a head in blocks of query rows: [B, rows, S] scores at a time
        blocks = jnp.moveaxis(
            q_h.reshape(q_h.shape[0], s // rows, rows, hd), 1, 0
        )
        out = jax.lax.map(block, (blocks, jnp.arange(0, s, rows)))
        return jnp.moveaxis(out, 0, 1).reshape(q_h.shape)

    # one head after another
    o = jax.lax.map(head, (q, jnp.arange(q.shape[0]) // group))
    return jnp.einsum("hbqd,hdm->bqm", o, w("out")).astype(F32)


def _descending(x):
    """(values, indices) of the last axis, largest first: a sort."""
    order = jnp.argsort(-x, axis=-1)
    return jnp.take_along_axis(x, order, axis=-1), order


def router(model, n, p, router_dtype=F32, wrong="", held=None):
    """``(gates [B, S, E], balance term)`` over ALL the experts: a token's
    gate for each expert (0 where it was not chosen), and ``E sum_e f_e
    P_e`` of this layer."""
    e, k = int(model["num_experts"]), int(model["top_k"])
    logits = n.astype(router_dtype) @ p["router"]["kernel"].astype(
        router_dtype
    )
    if wrong == "sigmoid_router":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    pick = probs
    if wrong == "top_k_of_held":
        first = int(model.get("first_expert") or 0)
        here = (jnp.arange(e) >= first) & (jnp.arange(e) < first + held)
        pick = jnp.where(here, probs, -1.0)
    _, order = _descending(pick)
    top_i = order[..., :k]
    top_p = jnp.take_along_axis(probs, top_i, axis=-1)
    if model.get("norm_topk_prob", True) and wrong != "no_renorm":
        top_p = top_p / top_p.sum(-1, keepdims=True)
    chosen = jax.nn.one_hot(top_i, e, dtype=router_dtype)  # [B, S, k, E]
    gates = (chosen * top_p[..., None]).sum(-2)
    f = chosen.astype(F32).sum(-2).mean(axis=(0, 1))
    balance = e * jnp.sum(f * probs.astype(F32).mean(axis=(0, 1)))
    return gates, balance


def routed_part(model, n, p, dtype=F32, router_dtype=F32, wrong=""):
    """``(sum over the chosen experts HELD HERE of g_e SwiGLU_e(n), balance
    term)``; ``p["wi"]`` (up), ``p["wg"]`` (gate) and ``p["wo"]`` hold the
    held experts only."""
    held = p["wi"].shape[0]
    first = int(model.get("first_expert") or 0)
    gates, balance = router(model, n, p, router_dtype, wrong, held)
    n = n.astype(dtype)

    def add_expert(i, out):
        def w(name):
            return jax.lax.dynamic_index_in_dim(
                p[name], i, 0, False
            ).astype(dtype)

        y = (jax.nn.silu(n @ w("wg")) * (n @ w("wi"))) @ w("wo")
        gate = jax.lax.dynamic_index_in_dim(gates, first + i, 2, True)
        return out + (y.astype(router_dtype) * gate).astype(dtype)

    # one expert after another into one accumulator
    out = jax.lax.fori_loop(0, held, add_expert, jnp.zeros_like(n))
    return out, balance


def expert_layer(model, n, p, dtype=F32, router_dtype=F32, wrong=""):
    """The whole expert layer: there is no shared expert, so the routed
    part is all of it."""
    return routed_part(model, n, p, dtype, router_dtype, wrong)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 5, 6))
def _block(model_items, mask, rotation, x, p, lowered, wrong):
    model = dict(model_items)
    dtype, attn_dtype, router_dtype = _dtypes(lowered)
    eps = float(model["norm_eps"])
    n = rms_norm(x, p["ln_attn"]["scale"], eps, dtype)
    x = x + attention(
        model, n, p["attn"], mask, rotation, attn_dtype, wrong
    ).astype(dtype)
    n = rms_norm(x, p["ln_mlp"]["scale"], eps, dtype)
    y, balance = expert_layer(model, n, p["moe"], dtype, router_dtype, wrong)
    return x + y, balance


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head_nll(norm_scale, head, x, targets, eps, lowered):
    dtype = _dtypes(lowered)[0]
    head = head.astype(dtype)
    b, s, d = x.shape
    rows = ROWS if (b * s) % ROWS == 0 else s

    def block(xs):
        x_rows, target_rows = xs
        logits = rms_norm(x_rows, norm_scale, eps, dtype) @ head
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logp, target_rows[..., None], -1
        )[..., 0].astype(F32)

    # a block of tokens after another: [rows, V] logits at a time
    nll = jax.lax.map(
        block, (x.reshape(-1, rows, d), targets.reshape(-1, rows))
    )
    return nll.reshape(b, s)


def layer_kinds(model, wrong="") -> List[Tuple[str, str]]:
    """Each layer's ``(mask, rotation)``: what its kind says, or a fault."""
    pattern = list(model["layer_pattern"])
    if wrong == "kinds_reordered":
        pattern = pattern[-1:] + pattern[:-1]
    out = []
    for i in range(int(model["num_layers"])):
        kind = pattern[i % len(pattern)]
        mask = "band" if kind == SLIDING else "causal"
        rotation = "plain" if kind == SLIDING else "yarn"
        if wrong == "no_window" or (
            wrong == "one_sliding_unwindowed" and i == 1
        ):
            mask = "causal"
        if wrong == "window_on_full":
            mask = "band"
        if wrong == "yarn_on_sliding":
            rotation = "yarn"
        if not model.get("rope_scaling"):
            rotation = "plain"
        out.append((mask, rotation))
    return out


def _trunk_layers(model, params) -> List[Any]:
    """The layers' parameters in order (the kinds' slots are the PROGRAM's
    pattern whatever a fault makes of the kinds)."""
    pattern = tuple(model["layer_pattern"])
    layers = []
    for i in range(int(model["num_layers"])):
        position = i % len(pattern)
        if "blocks" in params:
            slot = f"{pattern[position].split('_')[0]}_{position}"
            layers.append(jax.tree.map(
                lambda a: a[i // len(pattern)], params["blocks"][slot]
            ))
        else:
            layers.append(params[f"block_{i}"])
    return layers


def forward(model: Mapping[str, Any], params, tokens, targets=None,
            lowered: str = "", wrong: str = "") -> Dict[str, Any]:
    """``hidden`` (before the final norm), ``balance`` (the sum over the
    layers of their balance terms) and, with ``targets``, ``nll`` [B, S]."""
    if wrong and wrong not in FAULTS:
        raise ValueError(f"wrong must be one of {FAULTS}, got {wrong!r}")
    items = _items(model)
    dtype = _dtypes(lowered)[0]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"].astype(dtype)[tokens]
        balance = F32(0.0)
        for (mask, rotation), layer in zip(
            layer_kinds(model, wrong), _trunk_layers(model, params)
        ):
            x, layer_balance = _block(
                items, mask, rotation, x, layer, lowered, wrong
            )
            balance = balance + layer_balance
        out = {"hidden": x, "balance": balance}
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
    the ``layer_pattern`` list); ``params`` the program's parameter
    tree."""
    return forward(model, params, tokens, targets, lowered, wrong)["nll"]


def loss(model, params, tokens, targets):
    """Mean token NLL plus ``moe_aux_weight`` x the balance terms: what
    the step trains."""
    out = forward(model, params, tokens, targets)
    return out["nll"].mean() + F32(model["moe_aux_weight"]) * out["balance"]


def loss_and_grads(model, params, tokens, targets):
    return jax.value_and_grad(loss, argnums=1)(model, params, tokens, targets)
