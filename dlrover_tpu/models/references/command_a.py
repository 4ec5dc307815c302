"""Plain reference for Command A+ (CohereLabs ``command-a-plus-05-2026``,
``model_type`` ``cohere2_moe``): forward, per-token losses, the training
loss and its gradients.

The equations (``config.json`` of CohereLabs/command-a-plus-05-2026).
``LN(x) = (x - mean(x)) / sqrt(var(x) + eps) * g``: a LayerNorm WITHOUT a
bias, eps ``norm_eps``; no biases anywhere, no QK-norm::

    n = LN_l(x);  x' = x + Attn_kind(n) + FFN(n)        (use_parallel_block:
    ONE norm a layer, both branches read the same n, one residual add)
    layer i's kind is layer_pattern[i mod period]: three sliding_attention
    to one full_attention.  Final LN; the head is the TIED embedding:
    logits = LN_f(x) E^T x logit_scale.

    Attn(n):  q_h = Rot_kind(W_q n),  k_g = Rot_kind(W_k n): H query heads
              over H_kv key/value heads of hd;
              softmax(q_h k_g^T / sqrt(hd) + mask_kind) v_g;  W_o.
      sliding_attention:  mask(i, j) = 0 if 0 <= i - j < sliding_window else
              -inf (a query sees itself and the W - 1 tokens before it);
              Rot = RoPE over the whole head in the PUBLISHED pairing
              (position_embedding_type rope_gptj): columns (2i, 2i + 1) turn
              by p inv_i, inv_i = theta^(-2 i / hd), i = 0 .. hd/2 - 1
      full_attention:  the causal mask; Rot = NOTHING (no positions)
    FFN(n):   s = sigmoid(n W_r) over ALL num_experts, float32; the top_k
              largest; g_e = s_e / (sum of the chosen s + router_norm_eps)
              (norm_topk_prob); no bias, no scaling factor, no balance term
              routed = sum over the chosen e HELD HERE (first_expert ..
                    first_expert + experts_held - 1) of g_e W_o,e (SiLU(W_g,e
                    n) * W_i,e n)
              shared = (1 / num_shared_experts) x the sum over the shared
                    experts HELD HERE of W_o,s (SiLU(W_g,s n) * W_i,s n): the
                    mean over the PUBLISHED count
              FFN = routed + shared
    loss:     mean token NLL (a sigmoid router trains with no balance term)

What the heads, shared experts and routed experts held elsewhere would add
to a layer's output is left out, as in the program, and the partial sum goes
on to the next layer.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
kernel, no cache, no sharding, no scan over layers.  The band is an explicit
``[rows, S]`` mask built from ``i - j``.  It reads the program's parameter
tree only for the numbers in it, and takes the share from it: the heads are
the leading axes of the attention kernels, ``experts_held`` the held
weights' leading axis (``first_expert`` a field), the shared experts held
the columns of the ONE shared MLP in runs of ``moe_d_ff``, the vocabulary
whatever the embedding holds.  The program rotates HALVES (columns ``i`` and
``i + hd/2``), so its query and key columns are the published ones under
one fixed permutation (:func:`to_published_pairing`), which the reference
undoes before it rotates: scores do not change under a permutation that q
and k share.  It works in blocks so that the published widths at 16,384
tokens fit beside the model on the chip: one layer at a time in one jitted
function a kind, attention one head after another and a head in blocks of
:data:`ROWS` query rows, the held experts one after another into one
accumulator, the head and the loss in blocks of :data:`ROWS` tokens.

Departures from the published model, each the configuration file's
(``benchmark/configs/command-a-plus-05-2026.json`` ``assumed``):
``intermediate_size`` read as ONE expert's width, routed and shared alike;
``average`` read as the mean of the shared experts' outputs; full layers
without rotation; the half-open window; 1e-20 beside the renormalising sum.
The vision tower is LEFT OUT.

``lowered`` computes part of the model in bfloat16, to show that a
comparison's limit would catch it: ``"attention"`` the whole attention
branch (projections; the rotation's positions, angles, cos and sin, so a
position past 256 is no longer itself; scores, softmax, output);
``"router"`` the router's logits, scores and gates; ``"all"`` every product,
the logits and the loss as well.  ``wrong`` makes one fault, for the tests
that show the comparison sharp (:data:`FAULTS`).  A run sets neither.
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
    "sequential_block",       # a = x + Attn(LN(x)); x' = a + FFN(LN(a))
    "rope_on_full",           # the full layers rotated as the sliding ones
    "no_rope",                # no layer rotated
    "pairing_not_permuted",   # the program's columns taken as published ones
    "theta_10000",            # rope_theta 10,000
    "no_window",              # every layer the causal mask
    "window_plus_1",          # W + 1 keys
    "window_minus_1",         # W - 1 keys
    "window_on_full",         # the full layers banded too
    "kinds_reordered",        # (full, sliding, sliding, sliding)
    "shared_summed",          # the shared experts summed, not averaged
    "shared_left_out",        # no shared expert
    "softmax_router",         # softmax scores in place of sigmoid
    "no_renorm",              # the chosen scores as they are
    "top_k_of_held",          # the top_k of the experts held here alone
    "layernorm_keeps_mean",   # an RMSNorm where the LayerNorm stands
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


def layer_norm(x, scale, eps, dtype=F32, wrong=""):
    """The bias-free LayerNorm, statistics in float32."""
    x32 = x.astype(F32)
    if wrong != "layernorm_keeps_mean":
        x32 = x32 - x32.mean(-1, keepdims=True)
    y = x32 / jnp.sqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(dtype)


def to_published_pairing(x):
    """The program's column ``i`` is the published column ``2i``, its
    column ``i + hd/2`` the published ``2i + 1`` (last axis)."""
    half = x.shape[-1] // 2
    return jnp.stack([x[..., :half], x[..., half:]], -1).reshape(x.shape)


def rotate(x, theta: float):
    """``rope_gptj`` on ``[B, S, H, D]`` in the published pairing,
    positions 0 .. S - 1, in ``x``'s precision: the positions, the angles,
    cos, sin and the products.  Columns ``(2i, 2i + 1)`` turn by ``p
    theta^(-2i / D)``."""
    dtype, hd = x.dtype, x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(hd // 2, dtype=F32) / hd)
    ang = (
        jnp.arange(x.shape[1]).astype(dtype)[:, None]
        * inv.astype(dtype)[None, :]
    )
    cos = jnp.cos(ang).astype(dtype)[None, :, None, :]
    sin = jnp.sin(ang).astype(dtype)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [even * cos - odd * sin, odd * cos + even * sin], -1
    ).reshape(x.shape)


def attention(model, n, p, mask: str, rotated: bool, dtype=F32, wrong=""):
    """``mask`` ``"band"`` or ``"causal"``, ``rotated`` whether q and k
    turn: a layer kind's pair, or a fault's.  The heads are the ones ``p``
    holds."""
    def w(name):
        return p[name]["kernel"].astype(dtype)

    n = n.astype(dtype)
    q = jnp.einsum("bsd,dhk->bshk", n, w("query"))          # [B, S, H, hd]
    k = jnp.einsum("bsd,dhk->bshk", n, w("key"))            # [B, S, Hkv, hd]
    v = jnp.einsum("bsd,dhk->hbsk", n, w("value"))          # [Hkv, B, S, hd]
    hd = q.shape[-1]
    if rotated:
        theta = 10000.0 if wrong == "theta_10000" else float(
            model["rope_theta"]
        )
        if wrong != "pairing_not_permuted":
            q, k = to_published_pairing(q), to_published_pairing(k)
        q, k = rotate(q, theta), rotate(k, theta)
    q, k = jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0)     # [H, B, S, hd]
    group = q.shape[0] // k.shape[0]
    s = n.shape[1]
    window = int(model["sliding_window"]) + {
        "window_plus_1": 1, "window_minus_1": -1,
    }.get(wrong, 0)
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
                1.0 / math.sqrt(hd), dtype
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
    """``gates [B, S, E]`` over ALL the experts: a token's gate for each
    expert, 0 where it was not chosen."""
    e, k = int(model["num_experts"]), int(model["top_k"])
    logits = n.astype(router_dtype) @ p["router"]["kernel"].astype(
        router_dtype
    )
    if wrong == "softmax_router":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
    pick = scores
    if wrong == "top_k_of_held":
        first = int(model.get("first_expert") or 0)
        here = (jnp.arange(e) >= first) & (jnp.arange(e) < first + held)
        pick = jnp.where(here, scores, -1.0)
    _, order = _descending(pick)
    top_i = order[..., :k]
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if model.get("norm_topk_prob", True) and wrong != "no_renorm":
        eps = model.get("router_norm_eps")
        top_s = top_s / (
            top_s.sum(-1, keepdims=True)
            + jnp.asarray(1e-20 if eps is None else eps, router_dtype)
        )
    chosen = jax.nn.one_hot(top_i, e, dtype=router_dtype)  # [B, S, k, E]
    return (chosen * top_s[..., None]).sum(-2)


def _swiglu(n, wg, wi, wo):
    return (jax.nn.silu(n @ wg) * (n @ wi)) @ wo


def routed_part(model, n, p, dtype=F32, router_dtype=F32, wrong=""):
    """``(sum over the chosen experts HELD HERE of g_e SwiGLU_e(n), 0)``:
    ``p["wi"]`` (up), ``p["wg"]`` (gate) and ``p["wo"]`` hold the held
    experts only; the second value is the balance term, which a sigmoid
    router has none of."""
    held = p["wi"].shape[0]
    first = int(model.get("first_expert") or 0)
    gates = router(model, n, p, router_dtype, wrong, held)
    n = n.astype(dtype)

    def add_expert(i, out):
        def w(name):
            return jax.lax.dynamic_index_in_dim(
                p[name], i, 0, False
            ).astype(dtype)

        y = _swiglu(n, w("wg"), w("wi"), w("wo"))
        gate = jax.lax.dynamic_index_in_dim(gates, first + i, 2, True)
        return out + (y.astype(router_dtype) * gate).astype(dtype)

    # one expert after another into one accumulator
    out = jax.lax.fori_loop(0, held, add_expert, jnp.zeros_like(n))
    return out, F32(0.0)


def shared_part(model, n, p, dtype=F32, wrong=""):
    """``(1 / num_shared_experts) x`` the sum of the shared experts HELD
    HERE: ``p`` is the program's ONE shared MLP, whose columns are the held
    experts' in runs of ``moe_d_ff``."""
    if wrong == "shared_left_out":
        return jnp.zeros_like(n.astype(dtype))
    width = int(model["moe_d_ff"])
    published = int(model["num_shared_experts"])
    n = n.astype(dtype)
    held = p["wi"]["kernel"].shape[1] // width
    out = jnp.zeros_like(n)
    for s in range(held):
        cols = slice(s * width, (s + 1) * width)
        out = out + _swiglu(
            n, p["wg"]["kernel"][:, cols].astype(dtype),
            p["wi"]["kernel"][:, cols].astype(dtype),
            p["wo"]["kernel"][cols].astype(dtype),
        )
    if wrong == "shared_summed":
        return out
    return out / jnp.asarray(published, dtype)


def expert_layer(model, n, p, dtype=F32, router_dtype=F32, wrong=""):
    """``(routed + shared, 0)``: the whole expert layer on this share."""
    out, balance = routed_part(model, n, p, dtype, router_dtype, wrong)
    if "shared" in p:
        out = out + shared_part(model, n, p["shared"], dtype, wrong)
    return out, balance


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 5, 6))
def _block(model_items, mask, rotated, x, p, lowered, wrong):
    model = dict(model_items)
    dtype, attn_dtype, router_dtype = _dtypes(lowered)
    eps = float(model["norm_eps"])
    n = layer_norm(x, p["ln"]["scale"], eps, dtype, wrong)
    a = attention(
        model, n, p["attn"], mask, rotated, attn_dtype, wrong
    ).astype(dtype)
    if wrong == "sequential_block":
        x = x + a
        n = layer_norm(x, p["ln"]["scale"], eps, dtype, wrong)
        a = jnp.zeros_like(a)
    y, _ = expert_layer(model, n, p["moe"], dtype, router_dtype, wrong)
    return x + a + y


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _head_nll(norm_scale, table, x, targets, eps, logit_scale, lowered,
              wrong):
    dtype = _dtypes(lowered)[0]
    head = table.astype(dtype).T
    b, s, d = x.shape
    rows = ROWS if (b * s) % ROWS == 0 else s

    def block(xs):
        x_rows, target_rows = xs
        logits = layer_norm(x_rows, norm_scale, eps, dtype, wrong) @ head
        if logit_scale != 1.0:
            logits = logits * jnp.asarray(logit_scale, dtype)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logp, target_rows[..., None], -1
        )[..., 0].astype(F32)

    # a block of tokens after another: [rows, V] logits at a time
    nll = jax.lax.map(
        block, (x.reshape(-1, rows, d), targets.reshape(-1, rows))
    )
    return nll.reshape(b, s)


def layer_kinds(model, wrong="") -> List[Tuple[str, bool]]:
    """Each layer's ``(mask, rotated)``: what its kind says, or a fault."""
    pattern = list(model["layer_pattern"])
    if wrong == "kinds_reordered":
        pattern = pattern[-1:] + pattern[:-1]
    out = []
    for i in range(int(model["num_layers"])):
        sliding = pattern[i % len(pattern)] == SLIDING
        mask = "band" if sliding else "causal"
        rotated = sliding or bool(model.get("full_rope", False))
        if wrong == "no_window":
            mask = "causal"
        if wrong == "window_on_full":
            mask = "band"
        if wrong == "rope_on_full":
            rotated = True
        if wrong == "no_rope":
            rotated = False
        out.append((mask, rotated))
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
    """``hidden`` (before the final norm), ``balance`` (0: a sigmoid router
    has no balance term) and, with ``targets``, ``nll`` [B, S]."""
    if wrong and wrong not in FAULTS:
        raise ValueError(f"wrong must be one of {FAULTS}, got {wrong!r}")
    items = _items(model)
    dtype = _dtypes(lowered)[0]
    with jax.default_matmul_precision("highest"):
        table = params["embed"]["embedding"]
        x = table.astype(dtype)[tokens]
        for (mask, rotated), layer in zip(
            layer_kinds(model, wrong), _trunk_layers(model, params)
        ):
            x = _block(items, mask, rotated, x, layer, lowered, wrong)
        out = {"hidden": x, "balance": F32(0.0)}
        if targets is not None:
            out["nll"] = _head_nll(
                params["ln_final"]["scale"], table, x, targets,
                float(model["norm_eps"]),
                float(model.get("logit_scale") or 1.0), lowered, wrong,
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
    """Mean token NLL: what the step trains."""
    return forward(model, params, tokens, targets)["nll"].mean()


def loss_and_grads(model, params, tokens, targets):
    return jax.value_and_grad(loss, argnums=1)(model, params, tokens, targets)
