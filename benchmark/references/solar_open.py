"""Plain reference for Solar-Open2-250B: forward, per-token losses, the
training loss and its gradients.

The equations (``config.json`` of upstage/Solar-Open2-250B, ``solar_open2``;
Kimi Delta Attention: Kimi Linear, arXiv:2510.26692, in the paper's form;
the output gate on softmax attention: Gated Attention, arXiv:2505.06708;
the router: DeepSeek-V2, arXiv:2405.04434 §2.1.2, the family's default
where the config names no ``scoring_func``).  ``n = RMSNorm(x)``, eps
``norm_eps``, pre-norm, no biases::

    a = x + Mix(RMSNorm(x));  x' = a + Experts(RMSNorm(a))
    layer i mixes by layer_pattern[i mod period] (GQA, KDA, KDA, KDA).
    Final RMSNorm, untied head.

    KDA(n):   q, k, v = SiLU(conv(W_q n)), SiLU(conv(W_k n)), SiLU(conv(W_v n))
                  (causal depthwise, ``taps`` taps, own taps a channel)
              per head:  q <- q / ||q|| * dk^-1/2,  k <- k / ||k||
                  (||x|| = sqrt(sum x^2 + 1e-6))
              beta = 2 sigmoid(W_b n)                            [H]
              g = -exp(A_log_h) softplus((n W_f_down) W_f_up + dt_bias)
                  [H, dk], float32, g < 0 with NO lower bound
              S_0 = 0 in R^{dk x dv}, one token at a time:
              S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1}
                    + beta_t k_t v_t^T;    o_t = S_t^T q_t
              y = RMSNorm_dv(o; one [dv] scale)
                  * sigmoid((n W_g_down) W_g_up);  W_o y
    Attn(n):  q_h = n W_q, k_g = n W_k, v_g = n W_v (64 query heads over 8
              key/value heads), NO rotation, no QK-norm;
              causal softmax(q_h k_g / sqrt(head_dim)) v_g
              o <- o * sigmoid(n W_gate), element by element;  W_o
    Experts(n): s = softmax(n W_r) over ALL num_experts, float32
              chosen = the top_k of s
              g_e = routed_scaling_factor s_e / (sum_chosen s + 1e-20)
              out = SwiGLU_shared(n) + sum over the chosen e HELD HERE
                    (first_expert .. first_expert + experts_held - 1) of
                    g_e SwiGLU_e(n): what the experts held elsewhere would
                    add is left out, as in the program

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
chunk, no kernel, no cache, no ``top_k`` (the choice is a sort), no
sharding, no scan over layers.  It reads the program's parameter tree only
for the numbers in it.  One layer at a time in one jitted function a kind,
attention's scores by blocks of ``ROW_BLOCK`` queries of one head
([B, ROW_BLOCK, S] float32 at a time), the held experts one after another
into one accumulator, so it fits beside the model on the chip at the
published widths and 16,384 tokens.

What the config does not settle (the gate's granularity, the low rank, no
gate bias, the softmax router, the initialisers) is listed under
``assumed`` in ``benchmark/configs/solar-open2-250b.json``.

``lowered`` computes part of the model in bfloat16, to show that a
comparison's limit would catch it: ``"rule"`` the KDA recurrence alone
(its inputs, its decay, its state, its outputs); ``"router"`` the router's
logits, scores and gates; ``"all"`` those and every product and activation,
and every part the configuration's ``assumed.precision`` keeps in float32
(``g`` and beta from their pre-activations on, the norms' statistics, the
q/k lengths, the attention softmax, the sigmoid gates, the logits and the
loss).  ``wrong`` makes one fault, for the tests
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
ROW_BLOCK = 2048
SAFE_BOUND = -5.0
ROPE_THETA = 10000.0

FAULTS = (
    "beta_not_doubled",   # beta = sigmoid(..): no negative eigenvalue
    "safe_gate",          # g = -5 sigmoid(exp(A_log) (..)), Ling's
    "scalar_decay",       # one decay a head (the channels' mean)
    "no_gqa_gate",        # attention's output ungated
    "gate_head_wise",     # one gate a head (its first channel's)
    "rope_on_gqa",        # rotate-half RoPE(10000) on q and k
    "sigmoid_router",     # s = sigmoid(n W_r)
    "no_renorm",          # the chosen scores as they are
    "no_shared",          # the shared expert missing
)


def _dtypes(lowered: str):
    """(trunk dtype, rule's dtype, router dtype, the dtype of what the
    program keeps in float32 beside them) of a ``lowered`` mode."""
    return {
        "": (F32, F32, F32, F32), "rule": (F32, BF16, F32, F32),
        "router": (F32, F32, BF16, F32), "all": (BF16, BF16, BF16, BF16),
    }[lowered]


def _items(model: Mapping[str, Any]) -> Tuple:
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, (list, tuple)) else v)
        for k, v in model.items()
        if isinstance(v, (int, float, str, bool, list, tuple)) or v is None
    ))


def rms_norm(x, scale, eps, dtype=F32, stat=F32):
    """``stat``: the dtype of the statistics (float32 unless lowered)."""
    xs = x.astype(stat)
    y = xs / jnp.sqrt((xs * xs).mean(-1, keepdims=True) + stat(eps))
    return (y * scale.astype(stat)).astype(dtype)


def rope(x, theta):
    """Rotate-half RoPE on ``[B, S, ..., D]``, positions 0 .. S - 1 (the
    ``rope_on_gqa`` fault's alone: the model rotates nothing)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape(1, x.shape[1], *([1] * (x.ndim - 3)), half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1
    ).astype(x.dtype)


def _l2(x, stat=F32):
    xs = x.astype(stat)
    return (xs / jnp.sqrt((xs * xs).sum(-1, keepdims=True) + stat(L2_EPS))
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


def _low_rank(n, p, name, dtype):
    """``(n W_down) W_up`` [B, S, H, width], accumulated in float32."""
    low = n @ p[f"{name}_down"].astype(dtype)
    return jnp.einsum(
        "bsr,rhk->bshk", low, p[f"{name}_up"].astype(dtype),
        preferred_element_type=F32,
    ).astype(F32)


def kda_gates(model, n, p, dtype=F32, wrong="", stat=F32):
    """``(g [B, S, H, dk], beta [B, S, H])``, computed in ``stat`` from the
    pre-activations on: float32 whatever the trunk's dtype, as the
    program's, unless lowered."""
    n = n.astype(dtype)
    pre = _low_rank(n, p, "f", dtype).astype(stat) + p["dt_bias"].astype(stat)
    a = jnp.exp(p["A_log"].astype(stat))[:, None]
    if wrong == "safe_gate":
        g = SAFE_BOUND * jax.nn.sigmoid(a * pre)
    else:
        g = -a * jax.nn.softplus(pre)
    if wrong == "scalar_decay":
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(jnp.einsum(
        "bsd,dh->bsh", n, p["b_kernel"].astype(dtype),
        preferred_element_type=F32,
    ).astype(stat))
    if wrong != "beta_not_doubled":
        beta = 2.0 * beta
    return g.astype(F32), beta.astype(F32)


def kda_mixer(model, n, p, dtype=F32, rule_dtype=F32, wrong="", stat=F32):
    dk = int(model["linear_key_head_dim"])
    eps = float(model["norm_eps"])
    w = p["qkv"]["kernel"].astype(dtype)                   # [d, H, 2dk + dv]
    qkv = jnp.einsum("bsd,dhc->bshc", n, w)
    qkv = jax.nn.silu(_short_conv(qkv, p["conv_kernel"].astype(dtype)))
    q = (_l2(qkv[..., :dk], stat).astype(stat) * dk ** -0.5).astype(dtype)
    k = _l2(qkv[..., dk: 2 * dk], stat)
    v = qkv[..., 2 * dk:]
    g, beta = kda_gates(model, n, p, dtype, wrong, stat)
    o = kda_recurrence(q, k, v, g, beta, rule_dtype).astype(dtype)
    gate = _low_rank(n.astype(dtype), p, "g", dtype).astype(stat)
    y = rms_norm(o, p["out_norm_scale"], eps, stat, stat) * jax.nn.sigmoid(
        gate
    )
    return jnp.einsum(
        "bshc,hcd->bsd", y.astype(dtype), p["wo"]["kernel"].astype(dtype)
    )


def gqa_attention(model, n, p, dtype=F32, wrong="", stat=F32):
    """Causal softmax attention of ``H`` query heads over ``H_kv`` shared
    key/value heads, no rotation, under the element-wise output gate."""
    def w(name):
        return p[name]["kernel"].astype(dtype)

    q = jnp.einsum("bsd,dhk->bshk", n, w("query"))         # [B, S, H, hd]
    k = jnp.einsum("bsd,dhk->bshk", n, w("key"))           # [B, S, Hkv, hd]
    v = jnp.einsum("bsd,dhk->bshk", n, w("value"))
    if wrong == "rope_on_gqa":
        q, k = rope(q, ROPE_THETA), rope(k, ROPE_THETA)
    b, s, h, hd = q.shape
    group = h // k.shape[2]
    block = min(ROW_BLOCK, s)
    blocks = -(-s // block)
    pad = blocks * block - s
    q = jnp.pad(q, [(0, 0), (0, pad), (0, 0), (0, 0)])
    # [H, blocks, B, block, hd]: a head's queries by blocks of rows
    q = jnp.moveaxis(q.reshape(b, blocks, block, h, hd), (3, 1), (0, 1))
    k, v = jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)    # [Hkv, B, S, hd]
    cols = jnp.arange(s)

    def head(xs):
        q_h, index = xs
        k_h, v_h = k[index // group], v[index // group]

        def rows(ys):
            q_b, start = ys
            scores = jnp.einsum("bqd,bkd->bqk", q_b, k_h).astype(
                stat
            ) / jnp.sqrt(stat(hd))
            seen = cols[None, :] <= (start + jnp.arange(block))[:, None]
            scores = jnp.where(seen[None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
            return jnp.einsum("bqk,bkd->bqd", probs, v_h)

        return jax.lax.map(rows, (q_h, jnp.arange(blocks) * block))

    o = jax.lax.map(head, (q, jnp.arange(h)))      # [H, blocks, B, block, hd]
    o = jnp.moveaxis(o, (0, 1), (3, 1)).reshape(b, blocks * block, h, hd)
    o = o[:, :s]
    if "gate" in p and wrong != "no_gqa_gate":
        gate = jax.nn.sigmoid(
            jnp.einsum("bsd,dhk->bshk", n, w("gate")).astype(stat)
        )
        if wrong == "gate_head_wise":
            gate = jnp.broadcast_to(gate[..., :1], gate.shape)
        o = (o.astype(stat) * gate).astype(dtype)
    return jnp.einsum("bshk,hkd->bsd", o, w("out"))


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
    if wrong == "sigmoid_router":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    _, order = _descending(scores)
    top_i = order[..., :k]
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if model.get("norm_topk_prob", True) and wrong != "no_renorm":
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
    if "shared" in p and wrong != "no_shared":
        out = out + swiglu(n, p["shared"], dtype)
    return out, counts


@functools.partial(jax.jit, static_argnums=(0, 1, 4, 5))
def _block(model_items, kind, x, p, lowered, wrong):
    model = dict(model_items)
    dtype, rule_dtype, router_dtype, stat = _dtypes(lowered)
    eps = float(model["norm_eps"])
    n = rms_norm(x, p["ln_attn"]["scale"], eps, dtype, stat)
    if kind == LINEAR:
        x = x + kda_mixer(
            model, n, p["linear_attn"], dtype, rule_dtype, wrong, stat
        )
    else:
        x = x + gqa_attention(model, n, p["attn"], dtype, wrong, stat)
    n = rms_norm(x, p["ln_mlp"]["scale"], eps, dtype, stat)
    y, counts = expert_layer(model, n, p["moe"], dtype, router_dtype, wrong)
    return x + y, counts


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head_nll(norm_scale, head, x, targets, eps, lowered):
    dtype, _, _, stat = _dtypes(lowered)
    x = rms_norm(x, norm_scale, eps, dtype, stat)
    logits = x @ head.astype(dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0].astype(
        F32
    )


def _trunk_layers(model, params) -> List[Tuple[str, Any]]:
    """``(kind, the layer's parameters)`` in the model's order."""
    pattern = tuple(model["layer_pattern"])
    layers = []
    for i in range(int(model["num_layers"])):
        position = i % len(pattern)
        kind = pattern[position]
        if "blocks" in params:
            slot = f"{kind.split('_')[0]}_{position}"
            layers.append((kind, jax.tree.map(
                lambda a: a[i // len(pattern)], params["blocks"][slot]
            )))
        else:
            layers.append((kind, params[f"block_{i}"]))
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
