"""Plain reference for LFM2-8B-A1B (``model_type`` ``lfm2_moe``): forward,
per-token losses, the training loss and its gradients, the router-bias rule.

The equations (``config.json`` of LiquidAI/LFM2-8B-A1B; the gated short
convolution and the per-head QK norm are the LFM2 family's; the sigmoid
router whose choice is by score + bias is DeepSeek-V3's, arXiv:2412.19437
§2.1.2).  ``n = RMSNorm(x)``, eps ``norm_eps``, pre-norm, no biases::

    a = x + Mix(RMSNorm(x));  x' = a + FF(RMSNorm(a))
    the trunk's layer i mixes by layer_pattern[i mod period]; the
    first_k_dense layers before it continue the pattern backwards and have
    FF = SwiGLU(d_ff); the trunk's FF = Experts.  Final RMSNorm; the head
    is the embedding transposed.

    Conv(n):  [B | C | z] = n W_in          (three d-wide ranges, THIS order)
              u[t] = sum_{j<K} w[j] (B * z)[t - (K - 1) + j]   per channel,
                  zeros before t = 0; K = 3; no bias, NO activation
              (C * u) W_out
    Attn(n):  q_h = RoPE(RMSNorm_hd(W_q n; s_q)),  k_g = RoPE(RMSNorm_hd(W_k
              n; s_k)): each head's hd columns normed alone, ONE [hd] scale
              for all q heads and one for all k heads, BEFORE the rotation
              (rotate-half over the whole head, theta rope_theta); H query
              heads over H_kv key/value heads; causal softmax(q k / sqrt(hd))
              v;  W_o
    Experts(n): s = sigmoid(n W_r) over ALL num_experts, float32
              chosen = the top_k of s + b   (b picks, it never weighs)
              g_e = routed_scaling_factor s_e / (sum_chosen s + router_norm_eps)
              out = sum over the chosen e HELD HERE (first_expert ..
                    first_expert + experts_held - 1) of g_e SwiGLU_e(n): what
                    the experts held elsewhere would add is left out, as in
                    the program.  No shared expert.
    after a step: b_e += router_bias_rate x sign(mean load - load_e), the
              loads that step's own counts over all num_experts, per layer

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
kernel, no cache, no ``top_k`` (the choice is a sort), no sharding, no scan
over layers; the convolution is K shifted products.  It reads the program's
parameter tree only for the numbers in it.  It takes the share
(``experts_held`` is the held weights' leading axis, ``first_expert`` a
field; the vocabulary is whatever the embedding holds, so a sliced one is a
smaller one) and computes in blocks so that the published widths fit beside
the model on the chip: one layer at a time in one jitted function a kind,
attention one head after another ([B, S, S] float32 scores at a time), the
held experts one after another into one accumulator, the head and the loss
one sequence after another.

Departures from the published model, each the configuration file's
(``benchmark/configs/lfm2-8b-a1b.json`` ``assumed``): the tied head, the
per-head norms' one scale for all heads, the 1e-6 beside the renormalising
sum and the bias rule's rate are the family's and not in the catalog's
config; nothing is guessed beyond them.

``lowered`` computes part of the model in bfloat16, to show that a
comparison's limit would catch it: ``"conv"`` the gated convolution's core
alone (its three inputs, products, taps and output); ``"router"`` the
router's logits, scores and gates; ``"all"`` every product, activation,
the logits and the loss as well.  ``wrong`` makes one fault, for the tests
that show the comparison sharp (:data:`FAULTS`).  A run sets neither.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp

F32, BF16 = jnp.float32, jnp.bfloat16
CONV = "conv"

FAULTS = (
    "silu_after_taps",    # SiLU(conv(B z)), the siblings' short convolution
    "four_taps",          # K = 4: one more tap, three tokens back
    "gate_after_conv",    # C * B * conv(z): the first gate after the taps
    "no_out_gate",        # conv(B z) alone: C missing
    "gate_order",         # the ranges read as [C | B | z]
    "reads_ahead",        # the taps over t - 1 .. t + 1: a token reads t + 1
    "qk_norm_joint",      # one mean square over all heads of a projection
    "qk_norm_after_rope", # the rotation first, then the norm's scale
    "rope_in_conv",       # B z rotated by position as 64-wide heads
    "softmax_router",     # softmax scores in place of sigmoid
    "choice_by_score",    # the top_k of s alone: the bias ignored
    "bias_weighs",        # the gates from s + b
    "no_renorm",          # the chosen s as they are
    "scaled_2_5",         # routed_scaling_factor 2.5, the DeepSeek family's
    "shared_expert",      # the first held expert also as a shared one
)


def _dtypes(lowered: str):
    """(trunk dtype, core's dtype, router dtype) of a ``lowered`` mode."""
    return {
        "": (F32, F32, F32), "conv": (F32, BF16, F32),
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


def _shifted(x, back: int):
    """``x[t - back]`` along axis 1, zeros outside the sequence (``back``
    negative: a token ahead)."""
    s = x.shape[1]
    if back >= 0:
        return jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, : s - back]], axis=1
        )
    return jnp.concatenate(
        [x[:, -back:], jnp.zeros_like(x[:, :-back])], axis=1
    )


def short_conv(x, taps, ahead: int = 0):
    """``y[t] = sum_j taps[j] x[t - (K - 1) + j + ahead]``, zeros outside
    the sequence: K shifted products (``ahead`` 0: causal)."""
    k = taps.shape[0]
    y = jnp.zeros_like(x)
    for j in range(k):
        y = y + _shifted(x, k - 1 - j - ahead) * taps[j]
    return y


def conv_mixer(model, n, p, dtype=F32, core_dtype=F32, wrong=""):
    d = n.shape[-1]
    proj = (n @ p["in_proj"]["kernel"].astype(dtype)).astype(core_dtype)
    b, c, z = (proj[..., i * d: (i + 1) * d] for i in range(3))
    if wrong == "gate_order":
        b, c = c, b
    taps = p["conv_kernel"].astype(core_dtype)
    if wrong == "four_taps":
        taps = jnp.concatenate([taps[:1], taps], axis=0)
    ahead = 1 if wrong == "reads_ahead" else 0
    if wrong == "gate_after_conv":
        u = b * short_conv(z, taps, ahead)
    else:
        bz = b * z
        if wrong == "rope_in_conv":
            heads = bz.reshape(*bz.shape[:2], d // 64 if d >= 64 else 1, -1)
            bz = rope(heads, float(model["rope_theta"])).reshape(bz.shape)
        u = short_conv(bz, taps, ahead)
    if wrong == "silu_after_taps":
        u = jax.nn.silu(u)
    y = u if wrong == "no_out_gate" else c * u
    return y.astype(dtype) @ p["out_proj"]["kernel"].astype(dtype)


def attention(model, n, p, dtype=F32, wrong=""):
    eps, theta = float(model["norm_eps"]), float(model["rope_theta"])

    def w(name):
        return p[name]["kernel"].astype(dtype)

    q = jnp.einsum("bsd,dhk->bshk", n, w("query"))          # [B, S, H, hd]
    k = jnp.einsum("bsd,dhk->bshk", n, w("key"))            # [B, S, Hkv, hd]
    v = jnp.einsum("bsd,dhk->hbsk", n, w("value"))          # [Hkv, B, S, hd]
    hd = q.shape[-1]

    def normed(x, scale):
        if wrong == "qk_norm_joint":
            x32 = x.astype(F32)
            y = x32 / jnp.sqrt(
                (x32 * x32).mean((-2, -1), keepdims=True) + eps
            )
            return (y * scale.astype(F32)).astype(dtype)
        if wrong == "qk_norm_after_rope":
            ones = jnp.ones_like(scale)
            return (
                rope(rms_norm(x, ones, eps, F32), theta) * scale.astype(F32)
            ).astype(dtype)
        return rope(rms_norm(x, scale, eps, dtype), theta)

    q = jnp.moveaxis(normed(q, p["q_norm"]["scale"]), 2, 0)  # [H, B, S, hd]
    k = jnp.moveaxis(normed(k, p["k_norm"]["scale"]), 2, 0)
    group = q.shape[0] // k.shape[0]
    s = n.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(xs):
        q_h, g = xs
        k_h = jax.lax.dynamic_index_in_dim(k, g, 0, False)
        v_h = jax.lax.dynamic_index_in_dim(v, g, 0, False)
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k_h).astype(F32) / jnp.sqrt(
            F32(hd)
        )
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        return jnp.einsum("bqk,bkd->bqd", probs, v_h)

    # one head after another: [B, S, S] float32 scores at a time
    o = jax.lax.map(head, (q, jnp.arange(q.shape[0]) // group))
    return jnp.einsum("hbqd,hdm->bqm", o, w("out"))


def swiglu(n, p, dtype=F32):
    def w(name):
        return p[name]["kernel"].astype(dtype)

    return (jax.nn.silu(n @ w("wg")) * (n @ w("wi"))) @ w("wo")


def _descending(x):
    """(values, indices) of the last axis, largest first: a sort."""
    order = jnp.argsort(-x, axis=-1)
    return jnp.take_along_axis(x, order, axis=-1), order


def router(model, n, p, router_dtype=F32, wrong=""):
    """``(gates [B, S, E], counts [E])`` over ALL the experts: a token's
    gate for each expert (0 where it was not chosen), and how many tokens
    chose each."""
    e, k = int(model["num_experts"]), int(model["top_k"])
    logits = n.astype(router_dtype) @ p["router"]["kernel"].astype(
        router_dtype
    )
    if wrong == "softmax_router":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
    pick = scores
    if "router_bias" in p and wrong != "choice_by_score":
        pick = scores + p["router_bias"].astype(router_dtype)
    weigh = pick if wrong == "bias_weighs" else scores
    _, order = _descending(pick)
    top_i = order[..., :k]
    top_s = jnp.take_along_axis(weigh, top_i, axis=-1)
    if model.get("norm_topk_prob", True) and wrong != "no_renorm":
        top_s = top_s / (top_s.sum(-1, keepdims=True) + router_dtype(
            model.get("router_norm_eps", 1e-20)
        ))
    scale = 2.5 if wrong == "scaled_2_5" else model.get(
        "routed_scaling_factor", 1.0
    )
    top_s = top_s * router_dtype(scale)
    chosen = jax.nn.one_hot(top_i, e, dtype=router_dtype)  # [B, S, k, E]
    gates = (chosen * top_s[..., None]).sum(-2)
    return gates, chosen.astype(F32).sum(axis=(0, 1, 2))


def routed_part(model, n, p, dtype=F32, router_dtype=F32, wrong=""):
    """``(sum over the chosen experts HELD HERE of g_e SwiGLU_e(n),
    counts [E])``; ``p["wi"]`` .. hold the held experts only."""
    held = p["wi"].shape[0]
    first = int(model.get("first_expert") or 0)
    gates, counts = router(model, n, p, router_dtype, wrong)

    def expert(i):
        def w(name):
            return jax.lax.dynamic_index_in_dim(
                p[name], i, 0, False
            ).astype(dtype)

        return (jax.nn.silu(n @ w("wg")) * (n @ w("wi"))) @ w("wo")

    def add_expert(i, out):
        gate = jax.lax.dynamic_index_in_dim(gates, first + i, 2, True)
        return out + (expert(i).astype(router_dtype) * gate).astype(dtype)

    # one expert after another into one accumulator
    out = jax.lax.fori_loop(0, held, add_expert, jnp.zeros_like(n))
    if wrong == "shared_expert":
        out = out + expert(0)
    return out, counts


def expert_layer(model, n, p, dtype=F32, router_dtype=F32, wrong=""):
    """The whole expert layer: LFM2 has no shared expert, so the routed
    part is all of it."""
    return routed_part(model, n, p, dtype, router_dtype, wrong)


@functools.partial(jax.jit, static_argnums=(0, 1, 4, 5))
def _block(model_items, kind, x, p, lowered, wrong):
    """One layer; ``counts`` is ``None`` for a dense one."""
    model = dict(model_items)
    dtype, core_dtype, router_dtype = _dtypes(lowered)
    eps = float(model["norm_eps"])
    n = rms_norm(x, p["ln_attn"]["scale"], eps, dtype)
    if kind == CONV:
        x = x + conv_mixer(model, n, p["conv"], dtype, core_dtype, wrong)
    else:
        x = x + attention(model, n, p["attn"], dtype, wrong)
    n = rms_norm(x, p["ln_mlp"]["scale"], eps, dtype)
    if "moe" in p:
        y, counts = expert_layer(
            model, n, p["moe"], dtype, router_dtype, wrong
        )
        return x + y, counts
    return x + swiglu(n, p["mlp"], dtype), None


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head_nll(norm_scale, embedding, x, targets, eps, lowered):
    dtype = _dtypes(lowered)[0]
    head = embedding.astype(dtype).T

    def row(xs):
        x_row, target_row = xs
        logits = rms_norm(x_row, norm_scale, eps, dtype) @ head
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logp, target_row[..., None], -1
        )[..., 0].astype(F32)

    # one sequence after another: [S, V] float32 logits at a time
    return jax.lax.map(row, (x, targets))


def layer_kind(model, layer: int) -> str:
    """Layer ``layer``'s mixer: the trunk starts a period after the dense
    prefix, whose layers continue the pattern backwards."""
    pattern = tuple(model["layer_pattern"])
    dense = int(model.get("first_k_dense") or 0)
    return pattern[(layer - dense) % len(pattern)]


def _trunk_layers(model, params) -> List[Tuple[str, Any]]:
    """``(kind, the layer's parameters)``, the dense prefix first."""
    pattern = tuple(model["layer_pattern"])
    dense = int(model.get("first_k_dense") or 0)
    layers = [
        (layer_kind(model, i), params[f"dense_{i}"]) for i in range(dense)
    ]
    for i in range(int(model["num_layers"]) - dense):
        position = i % len(pattern)
        kind = pattern[position]
        if "blocks" in params:
            slot = f"{kind.split('_')[0]}_{position}"
            layers.append((kind, jax.tree.map(
                lambda a: a[i // len(pattern)], params["blocks"][slot]
            )))
        else:
            layers.append((kind, params[f"block_{dense + i}"]))
    return layers


def forward(model: Mapping[str, Any], params, tokens, targets=None,
            lowered: str = "", wrong: str = "") -> Dict[str, Any]:
    """``nll`` [B, S] against ``targets``; ``counts``: each expert layer's
    tokens per expert over all ``num_experts``, in order.  Without
    ``targets`` only ``hidden`` (before the final norm) and ``counts``."""
    if wrong and wrong not in FAULTS:
        raise ValueError(f"wrong must be one of {FAULTS}, got {wrong!r}")
    items = _items(model)
    dtype = _dtypes(lowered)[0]
    eps = float(model["norm_eps"])
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"].astype(dtype)[tokens]
        counts = []
        for kind, layer in _trunk_layers(model, params):
            x, layer_counts = _block(items, kind, x, layer, lowered, wrong)
            if layer_counts is not None:
                counts.append(layer_counts)
        out = {"hidden": x, "counts": counts}
        if targets is None:
            return out
        out["nll"] = _head_nll(
            params["ln_final"]["scale"], params["embed"]["embedding"], x,
            targets, eps, lowered,
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
    return forward(model, params, tokens, targets)["nll"].mean()


def loss_and_grads(model, params, tokens, targets):
    return jax.value_and_grad(loss, argnums=1)(model, params, tokens, targets)


def bias_rule(bias, counts, rate: float):
    """``b_e += rate x sign(mean load - load_e)`` from one step's counts."""
    counts = counts.astype(F32)
    return bias.astype(F32) + F32(rate) * jnp.sign(counts.mean() - counts)
