"""Plain reference for JoyAI-LLM-Flash (the DeepSeek-V3 family's layers):
forward, per-token losses, the training loss and its gradients, the
router-bias rule.

The equations (``config.json`` of jdopensource/JoyAI-LLM-Flash; DeepSeek-V2,
arXiv:2405.04434 §2.1 for latent attention; DeepSeek-V3, arXiv:2412.19437
§2.1.2 for the router and its bias, §2.2 for multi-token prediction).
``n = RMSNorm(x)``, eps ``norm_eps``, pre-norm, no biases::

    h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    layer 0 .. first_k_dense - 1: FFN = SwiGLU(d_ff); the others: Experts
    final RMSNorm, untied head

    Attn(n):  c_q = RMSNorm(n W_qa);  [q_nope | q_pe]_h = c_q W_qb
              [c_kv | k_pe] = n W_kva;  [k_nope | v]_h = RMSNorm(c_kv) W_kvb
              rotate-half RoPE(theta) on q_pe and on the ONE k_pe all heads
              share;  k_h = [k_nope_h | k_pe]
              causal softmax(q_h k_h / sqrt(nope + rope)) v_h;  W_o
    Experts(n): s = sigmoid(n W_r) over ALL num_experts, float32
              chosen = the top_k of s + b  (b picks, it never weighs)
              g_e = routed_scaling_factor s_e / (sum_chosen s + 1e-20)
              out = SwiGLU_shared(n) + sum over the chosen e HELD HERE
                    (first_expert .. first_expert + experts_held - 1) of
                    g_e SwiGLU_e(n): what the experts held elsewhere would
                    add is left out, as in the program
    MTP:      h'_i = [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))] W_eh, h the
              trunk's output before the final norm; one more expert layer;
              a norm of its own; the SHARED head; predicts t_{i+2}
    loss:     mean CE(main, t_{i+1}) + mtp_weight x mean CE(mtp, t_{i+2})
              over the positions that have a t_{i+2} (all but the last)
    after a step: b_e += router_bias_rate x sign(mean load - load_e), the
              loads that step's own counts over all num_experts, per layer

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
kernel, no cache, no sort, no sharding, no scan over layers.  It reads the
program's parameter tree only for the numbers in it.  One layer at a time
in one jitted function that every layer of its kind re-uses, attention one
head after another ([B, S, S] float32 scores at a time), the held experts
one after another into one accumulator, so it fits beside the model on the
chip at the published widths.

Departures from the published model: the family's sequence-wise balance
term (weight 1e-4) is LEFT OUT, as in the program; ``rope_interleave`` is a
fixed permutation of the rotary columns of ``W_qb`` and ``W_kva`` and is
not applied (seeded weights: program and reference agree on rotate-half
over the last ``rope`` columns).

``lowered`` computes part of the model in bfloat16, to show that a
comparison's limit would catch it: ``"router"`` the router's logits,
scores and gates; ``"all"`` every product, activation, the logits and the
loss as well.  A run never sets it.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp

F32, BF16 = jnp.float32, jnp.bfloat16


def _dtypes(lowered: str):
    """(trunk dtype, router dtype) of a ``lowered`` mode."""
    return {
        "": (F32, F32), "router": (F32, BF16), "all": (BF16, BF16),
    }[lowered]


def _items(model: Mapping[str, Any]) -> Tuple:
    return tuple(sorted(
        (k, v) for k, v in model.items()
        if isinstance(v, (int, float, str, bool)) or v is None
    ))


def rms_norm(x, scale, eps, dtype=F32):
    x32 = x.astype(F32)
    y = x32 / jnp.sqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(dtype)


def rope(x, theta):
    """Rotate-half RoPE on ``[B, S, ..., D]``, positions 0 .. S - 1."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape(1, x.shape[1], *([1] * (x.ndim - 3)), half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1
    ).astype(x.dtype)


def latent_attention(model, n, p, dtype=F32):
    nope = int(model["qk_nope_head_dim"])
    rank = int(model["kv_lora_rank"])
    eps, theta = float(model["norm_eps"]), float(model["rope_theta"])

    def w(name):
        return p[name]["kernel"].astype(dtype)

    c_q = rms_norm(n @ w("q_a"), p["q_norm"]["scale"], eps, dtype)
    q = jnp.einsum("bsl,lhk->hbsk", c_q, w("q_b"))        # [H, B, S, 192]
    row = n @ w("kv_a")                                    # [B, S, 512 + 64]
    c_kv = rms_norm(row[..., :rank], p["kv_norm"]["scale"], eps, dtype)
    kv = jnp.einsum("bsl,lhk->hbsk", c_kv, w("kv_b"))     # [H, B, S, 256]
    k_pe = rope(row[..., rank:], theta)                    # [B, S, 64], shared
    width = q.shape[-1]
    s = n.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(xs):
        q_h, kv_h = xs
        q_h = jnp.concatenate(
            [q_h[..., :nope], rope(q_h[..., nope:], theta)], -1
        )
        k_h = jnp.concatenate([kv_h[..., :nope], k_pe], -1)
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k_h).astype(F32) / jnp.sqrt(
            F32(width)
        )
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        return jnp.einsum("bqk,bkd->bqd", probs, kv_h[..., nope:])

    # one head after another: [B, S, S] float32 scores at a time
    o = jax.lax.map(head, (q, kv))                         # [H, B, S, 128]
    return jnp.einsum("hbqd,hdm->bqm", o, w("wo"))


def swiglu(n, p, dtype=F32):
    def w(name):
        return p[name]["kernel"].astype(dtype)

    return (jax.nn.silu(n @ w("wg")) * (n @ w("wi"))) @ w("wo")


def router(model, n, p, router_dtype=F32):
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
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if model.get("norm_topk_prob", True):
        top_s = top_s / (top_s.sum(-1, keepdims=True) + 1e-20)
    top_s = top_s * router_dtype(model.get("routed_scaling_factor", 1.0))
    chosen = jax.nn.one_hot(top_i, e, dtype=router_dtype)  # [B, S, k, E]
    gates = (chosen * top_s[..., None]).sum(-2)
    return gates, chosen.astype(F32).sum(axis=(0, 1, 2))


def routed_part(model, n, p, dtype=F32, router_dtype=F32):
    """``(sum over the chosen experts HELD HERE of g_e SwiGLU_e(n),
    counts [E])``; ``p["wi"]`` .. hold the held experts only."""
    held = p["wi"].shape[0]
    first = int(model.get("first_expert") or 0)
    gates, counts = router(model, n, p, router_dtype)

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
    return out, counts


def expert_layer(model, n, p, dtype=F32, router_dtype=F32):
    out, counts = routed_part(model, n, p, dtype, router_dtype)
    if "shared" in p:
        out = out + swiglu(n, p["shared"], dtype)
    return out, counts


@functools.partial(jax.jit, static_argnums=(0, 3))
def _block(model_items, x, p, lowered):
    """One layer; ``counts`` is ``None`` for a dense one."""
    model = dict(model_items)
    dtype, router_dtype = _dtypes(lowered)
    eps = float(model["norm_eps"])
    x = x + latent_attention(
        model, rms_norm(x, p["ln_attn"]["scale"], eps, dtype), p["attn"],
        dtype,
    )
    n = rms_norm(x, p["ln_mlp"]["scale"], eps, dtype)
    if "moe" in p:
        y, counts = expert_layer(model, n, p["moe"], dtype, router_dtype)
        return x + y, counts
    return x + swiglu(n, p["mlp"], dtype), None


@functools.partial(jax.jit, static_argnums=(0, 4))
def _mtp_input(model_items, p, hidden, next_embed, lowered):
    model = dict(model_items)
    dtype, _ = _dtypes(lowered)
    eps = float(model["norm_eps"])
    both = jnp.concatenate([
        rms_norm(hidden, p["hnorm"]["scale"], eps, dtype),
        rms_norm(next_embed, p["enorm"]["scale"], eps, dtype),
    ], axis=-1)
    return both @ p["proj"]["kernel"].astype(dtype)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head_nll(norm_scale, head, x, targets, eps, lowered):
    dtype, _ = _dtypes(lowered)
    x = rms_norm(x, norm_scale, eps, dtype)
    logits = x @ head.astype(dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0].astype(
        F32
    )


def _trunk_layers(model, params) -> List:
    dense = int(model.get("first_k_dense") or 0)
    layers = [params[f"dense_{i}"] for i in range(dense)]
    for i in range(int(model["num_layers"]) - dense):
        if "blocks" in params:
            layers.append(jax.tree.map(lambda a: a[i], params["blocks"]))
        else:
            layers.append(params[f"block_{dense + i}"])
    return layers


def forward(model: Mapping[str, Any], params, tokens, targets=None,
            lowered: str = "") -> Dict[str, Any]:
    """``nll`` [B, S] of the main head against ``targets``; with an MTP
    module in ``params``, ``mtp_nll`` [B, S - 1] (position ``i`` against
    ``targets[i + 1]``); ``counts``: each expert layer's tokens per expert
    over all ``num_experts``, the trunk's layers in order and then the
    module's.  Without ``targets`` only ``hidden`` (before the final norm)
    and ``counts`` of the trunk."""
    items = _items(model)
    dtype, _ = _dtypes(lowered)
    eps = float(model["norm_eps"])
    with jax.default_matmul_precision("highest"):
        table = params["embed"]["embedding"].astype(dtype)
        x = table[tokens]
        counts = []
        for layer in _trunk_layers(model, params):
            x, layer_counts = _block(items, x, layer, lowered)
            if layer_counts is not None:
                counts.append(layer_counts)
        out = {"hidden": x, "counts": counts}
        if targets is None:
            return out
        head = params["lm_head"]["kernel"]
        out["nll"] = _head_nll(
            params["ln_final"]["scale"], head, x, targets, eps, lowered
        )
        if "mtp" in params:
            mtp = params["mtp"]
            y = _mtp_input(items, mtp, x, table[targets], lowered)
            y, layer_counts = _block(items, y, mtp["block"], lowered)
            counts.append(layer_counts)
            out["mtp_nll"] = _head_nll(
                mtp["norm"]["scale"], head, y[:, :-1], targets[:, 1:], eps,
                lowered,
            )
        return out


def token_nll(model, params, tokens, targets, lowered: str = ""):
    """Per-token negative log-likelihood [B, S] of the main head, float32.

    ``model`` is the ``model`` group of a configuration file (the
    program's ``TransformerConfig`` fields as plain numbers and strings);
    ``params`` the program's parameter tree."""
    return forward(model, params, tokens, targets, lowered)["nll"]


def mtp_token_nll(model, params, tokens, targets, lowered: str = ""):
    """The MTP module's per-token nll [B, S - 1]: position ``i`` (hidden
    state ``i``, embedding of ``targets[i]``) against ``targets[i + 1]``."""
    return forward(model, params, tokens, targets, lowered)["mtp_nll"]


def loss(model, params, tokens, targets):
    """``mean(nll) + mtp_weight x mean(mtp_nll)``: what the step trains."""
    out = forward(model, params, tokens, targets)
    total = out["nll"].mean()
    if "mtp_nll" in out:
        total = total + F32(model.get("mtp_weight", 0.3)) * out[
            "mtp_nll"
        ].mean()
    return total


def loss_and_grads(model, params, tokens, targets):
    return jax.value_and_grad(loss, argnums=1)(model, params, tokens, targets)


def bias_rule(bias, counts, rate: float):
    """``b_e += rate x sign(mean load - load_e)`` from one step's counts."""
    counts = counts.astype(F32)
    return bias.astype(F32) + F32(rate) * jnp.sign(counts.mean() - counts)
