"""Plain reference for Ling-3.0-flash-VL's language model: forward,
per-token losses, the training loss and its gradients, the router-bias rule.

The equations (``config.json`` of inclusionAI/Ling-3.0-flash-VL; Kimi Delta
Attention: Kimi Linear, arXiv:2510.26692, with its open kernels' safe gate;
latent attention: DeepSeek-V2, arXiv:2405.04434 §2.1; the head-wise output
gate: Gated Attention, arXiv:2505.06708; the router, its bias and its group
limit: DeepSeek-V3, arXiv:2412.19437 §2.1.2).  ``n = RMSNorm(x)``, eps
``norm_eps``, pre-norm, no biases::

    a = x + Mix(RMSNorm(x));  x' = a + FF(RMSNorm(a))
    the trunk's layer i mixes by layer_pattern[i mod period]; the
    first_k_dense layers before it continue the pattern backwards and have
    FF = SwiGLU(d_ff); the trunk's FF = Experts.  Final RMSNorm, untied head.

    KDA(n):   q, k, v = SiLU(conv(W_q n)), SiLU(conv(W_k n)), SiLU(conv(W_v n))
                  (causal depthwise, ``taps`` taps, own taps a channel)
              per head:  q <- q / ||q|| * dk^-1/2,  k <- k / ||k||
                  (||x|| = sqrt(sum x^2 + 1e-6))
              beta = sigmoid(W_b n)                          [H]
              g = bound * sigmoid(exp(A_log_h) * (W_f n + dt_bias))  [H, dk]
              S_0 = 0 in R^{dk x dv}, one token at a time:
              S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1}
                    + beta_t k_t v_t^T;    o_t = S_t^T q_t
              y = RMSNorm_dv(o; one [dv] scale) * sigmoid(W_g n);  W_o y
    Attn(n):  [q_nope | q_pe]_h = n W_q         (no q latent, no q norm)
              [c_kv | k_pe] = n W_kva;  [k_nope | v]_h = RMSNorm(c_kv) W_kvb
              rotate-half RoPE(theta) on q_pe and on the ONE k_pe all heads
              share;  causal softmax(q_h k_h / sqrt(nope + rope)) v_h
              o_h <- o_h * sigmoid(n W_gate)_h;  W_o
    Experts(n): s = sigmoid(n W_r) over ALL num_experts, float32
              p = s + b  (b picks, it never weighs); the experts are
              router_groups runs of consecutive ones; a group's score is
              the sum of its two largest p; the router_topk_groups best
              groups stay; chosen = the top_k of p among their experts
              g_e = routed_scaling_factor s_e / (sum_chosen s + 1e-20)
              out = SwiGLU_shared(n) + sum over the chosen e HELD HERE
                    (first_expert .. first_expert + experts_held - 1) of
                    g_e SwiGLU_e(n): what the experts held elsewhere would
                    add is left out, as in the program
    after a step: b_e += router_bias_rate x sign(mean load - load_e), the
              loads that step's own counts over all num_experts, per layer

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
chunk, no kernel, no cache, no ``top_k`` (the group limit and the choice
are sorts), no sharding, no scan over layers.  It reads the program's
parameter tree only for the numbers in it.  One layer at a time in one
jitted function a kind, attention one head after another ([B, S, S] float32
scores at a time), the held experts one after another into one
accumulator, so it fits beside the model on the chip at the published
widths.

Departures from the published model: ``expert_swiglu_limit_list`` and
``share_expert_swiglu_limit_list`` are 0 (no clamp) for every published
layer below 34, so for every layer a cut runs: no clamp is applied; the
MTP module and the vision tower are not part of this model; what the
config does not settle (norm placement, no rotation in a KDA layer, the
per-head L2 norm of q and k, the group's score, the initialisers) is
listed under ``assumed`` in ``benchmark/configs/ling-3.0-flash-vl.json``.

``lowered`` computes part of the model in bfloat16, to show that a
comparison's limit would catch it: ``"rule"`` the KDA recurrence alone
(its inputs, its decay, its state, its outputs); ``"router"`` the router's
logits, scores and gates; ``"all"`` every product, activation, the state,
the logits and the loss as well.  ``wrong`` makes one fault, for the tests
that show the comparison sharp (:data:`FAULTS`).  A run sets neither.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp

F32, BF16 = jnp.float32, jnp.bfloat16
L2_EPS = 1e-6
LINEAR = "linear_attention"

FAULTS = (
    "scalar_decay",       # one decay a head (the channels' mean)
    "softplus_gate",      # g = -exp(A_log) softplus(..): no lower bound
    "beta_doubled",       # beta = 2 sigmoid(..), the hybrid's
    "no_group_limit",     # the top_k of all the experts
    "group_by_max",       # a group's score its largest p alone
    "no_head_gate",       # attention's output ungated
    "gate_per_channel",   # the gate read by a head's channel, not its head
    "q_norm",             # an RMS norm on each head's q
    "bias_weighs",        # the gates from s + b
)


def _dtypes(lowered: str):
    """(trunk dtype, rule's dtype, router dtype) of a ``lowered`` mode."""
    return {
        "": (F32, F32, F32), "rule": (F32, BF16, F32),
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


def _l2(x):
    x32 = x.astype(F32)
    return (x32 / jnp.sqrt((x32 * x32).sum(-1, keepdims=True) + L2_EPS)
            ).astype(x.dtype)


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


def kda_recurrence(q, k, v, g, beta, dtype=F32):
    """The rule, a token at a time.  ``q, k, g`` [B, S, H, dk], ``v``
    [B, S, H, dv], ``beta`` [B, S, H]; the state [B, H, dk, dv] in
    ``dtype``."""
    b, _, h, dk = q.shape
    dv = v.shape[-1]

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t).astype(dtype)[..., None]
        read = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + (
            beta_t.astype(dtype)[..., None, None] * k_t[..., None]
            * (v_t - read)[..., None, :]
        )
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    xs = tuple(
        jnp.moveaxis(a.astype(dtype), 1, 0) for a in (q, k, v, g, beta)
    )
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), dtype), xs)
    return jnp.moveaxis(o, 0, 1)


def kda_mixer(model, n, p, dtype=F32, rule_dtype=F32, wrong=""):
    dk = int(model["linear_key_head_dim"])
    dv = int(model["linear_value_head_dim"])
    bound = float(model.get("linear_decay_bound", -5.0))
    eps = float(model["norm_eps"])
    w = p["qkv"]["kernel"].astype(dtype)                   # [d, H, 2dk + dv]
    qkv = jnp.einsum("bsd,dhc->bshc", n, w)
    qkv = jax.nn.silu(_short_conv(qkv, p["conv_kernel"].astype(dtype)))
    q = (_l2(qkv[..., :dk]).astype(F32) * dk ** -0.5).astype(dtype)
    k = _l2(qkv[..., dk: 2 * dk])
    v = qkv[..., 2 * dk:]
    # the gates: float32 whatever the trunk's dtype, as the program's
    n32 = n.astype(dtype)
    f = jnp.einsum(
        "bsd,dhk->bshk", n32, p["f_kernel"].astype(dtype),
        preferred_element_type=F32,
    ).astype(F32)
    a = jnp.exp(p["A_log"].astype(F32))[:, None]
    pre = f + p["dt_bias"].astype(F32)
    if wrong == "softplus_gate":
        g = -a * jax.nn.softplus(pre)
    else:
        g = bound * jax.nn.sigmoid(a * pre)
    if wrong == "scalar_decay":
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(jnp.einsum(
        "bsd,dh->bsh", n32, p["b_kernel"].astype(dtype),
        preferred_element_type=F32,
    ).astype(F32))
    if wrong == "beta_doubled":
        beta = 2.0 * beta
    o = kda_recurrence(q, k, v, g, beta, rule_dtype).astype(dtype)
    gate = jnp.einsum(
        "bsd,dhc->bshc", n, p["g_proj"]["kernel"].astype(dtype)
    )
    y = rms_norm(o, p["out_norm_scale"], eps, F32) * jax.nn.sigmoid(
        gate.astype(F32)
    )
    return jnp.einsum(
        "bshc,hcd->bsd", y.astype(dtype), p["wo"]["kernel"].astype(dtype)
    )


def latent_attention(model, n, p, dtype=F32, wrong=""):
    nope = int(model["qk_nope_head_dim"])
    rank = int(model["kv_lora_rank"])
    eps, theta = float(model["norm_eps"]), float(model["rope_theta"])

    def w(name):
        return p[name]["kernel"].astype(dtype)

    q = jnp.einsum("bsd,dhk->hbsk", n, w("q_b"))           # [H, B, S, 192]
    if wrong == "q_norm":
        q = rms_norm(q, jnp.ones((q.shape[-1],), F32), eps, dtype)
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
    if "gate" in p and wrong != "no_head_gate":
        gate = jax.nn.sigmoid((n @ w("gate")).astype(F32))  # [B, S, H]
        if wrong == "gate_per_channel":
            heads, dv = o.shape[0], o.shape[-1]
            gate = gate[..., jnp.arange(dv) % heads]        # [B, S, dv]
            o = (o.astype(F32) * gate[None]).astype(dtype)
        else:
            o = (
                o.astype(F32) * jnp.moveaxis(gate, -1, 0)[..., None]
            ).astype(dtype)
    return jnp.einsum("hbqd,hdm->bqm", o, w("wo"))


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
    groups = int(model.get("router_groups") or 1)
    keep = int(model.get("router_topk_groups") or 1)
    logits = n.astype(router_dtype) @ p["router"]["kernel"].astype(
        router_dtype
    )
    scores = jax.nn.sigmoid(logits)
    pick = scores
    if "router_bias" in p:
        pick = scores + p["router_bias"].astype(router_dtype)
    weigh = pick if wrong == "bias_weighs" else scores
    if groups > 1 and wrong != "no_group_limit":
        grouped = pick.reshape(*pick.shape[:-1], groups, e // groups)
        best, _ = _descending(grouped)
        score = best[..., 0] if wrong == "group_by_max" else (
            best[..., 0] + best[..., 1]
        )
        _, group_order = _descending(score)                # [B, S, G]
        rank = jnp.argsort(group_order, axis=-1)           # a group's place
        pick = jnp.where(
            (rank < keep)[..., None], grouped, -jnp.inf
        ).reshape(pick.shape)
    _, order = _descending(pick)
    top_i = order[..., :k]
    top_s = jnp.take_along_axis(weigh, top_i, axis=-1)
    if model.get("norm_topk_prob", True):
        top_s = top_s / (top_s.sum(-1, keepdims=True) + 1e-20)
    top_s = top_s * router_dtype(model.get("routed_scaling_factor", 1.0))
    chosen = jax.nn.one_hot(top_i, e, dtype=router_dtype)  # [B, S, k, E]
    gates = (chosen * top_s[..., None]).sum(-2)
    return gates, chosen.astype(F32).sum(axis=(0, 1, 2))


def routed_part(model, n, p, dtype=F32, router_dtype=F32, wrong=""):
    """``(sum over the chosen experts HELD HERE of g_e SwiGLU_e(n),
    counts [E])``; ``p["wi"]`` .. hold the held experts only."""
    held = p["wi"].shape[0]
    first = int(model.get("first_expert") or 0)
    gates, counts = router(model, n, p, router_dtype, wrong)

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


def expert_layer(model, n, p, dtype=F32, router_dtype=F32, wrong=""):
    out, counts = routed_part(model, n, p, dtype, router_dtype, wrong)
    if "shared" in p:
        out = out + swiglu(n, p["shared"], dtype)
    return out, counts


@functools.partial(jax.jit, static_argnums=(0, 1, 4, 5))
def _block(model_items, kind, x, p, lowered, wrong):
    """One layer; ``counts`` is ``None`` for a dense one."""
    model = dict(model_items)
    dtype, rule_dtype, router_dtype = _dtypes(lowered)
    eps = float(model["norm_eps"])
    n = rms_norm(x, p["ln_attn"]["scale"], eps, dtype)
    if kind == LINEAR:
        x = x + kda_mixer(model, n, p["linear_attn"], dtype, rule_dtype, wrong)
    else:
        x = x + latent_attention(model, n, p["attn"], dtype, wrong)
    n = rms_norm(x, p["ln_mlp"]["scale"], eps, dtype)
    if "moe" in p:
        y, counts = expert_layer(
            model, n, p["moe"], dtype, router_dtype, wrong
        )
        return x + y, counts
    return x + swiglu(n, p["mlp"], dtype), None


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head_nll(norm_scale, head, x, targets, eps, lowered):
    dtype = _dtypes(lowered)[0]
    x = rms_norm(x, norm_scale, eps, dtype)
    logits = x @ head.astype(dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0].astype(
        F32
    )


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
            params["ln_final"]["scale"], params["lm_head"]["kernel"], x,
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
