"""Plain reference for GLM-5.2 (``glm_moe_dsa``): the DeepSeek-V3 family's
layers with DeepSeek-V3.2's sparse attention (DSA) and IndexShare: forward,
per-token losses, the indexers' KL terms, the training loss and its
gradients.

The equations (``config.json`` of zai-org/GLM-5.2; DeepSeek-V2,
arXiv:2405.04434 §2.1 for latent attention; DeepSeek-V3, arXiv:2412.19437
§2.1.2 and §2.2 for the router and multi-token prediction; DeepSeek-V3.2,
arXiv:2512.02556 §2.1 for the indexer, the choice and what trains it).
``n = RMSNorm(x)``, eps ``norm_eps``, pre-norm, no biases::

    h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    layer 0 .. first_k_dense - 1: FFN = SwiGLU(d_ff); the others: Experts
    a layer's kind: ``layer_pattern`` from layer first_k_dense on, the
    dense layers before it continue the pattern backwards; the MTP module's
    is ``mtp_layer_kind``

    Attn(n):  c_q = RMSNorm(n W_qa);  [q_nope | q_pe]_h = c_q W_qb
              [c_kv | k_pe] = n W_kva;  [k_nope | v]_h = RMSNorm(c_kv) W_kvb
              rotate-half RoPE(theta) on q_pe and on the ONE k_pe all heads
              share;  k_h = [k_nope_h | k_pe]
              A_h[t, .] = softmax over S_t of q_h k_h / sqrt(nope + rope)
              o_h = A_h v_h;  W_o
    indexer (kind index_attention), on n and c_q DETACHED:
              q^I_{t,j} = (c_q W^I_qb)_j, J heads of D;  k^I_s =
              LayerNorm(n_s W^I_k) (scale and bias), one key for all heads
              RoPE(theta) on the FIRST rope columns of each
              w_t = (n_t W^I_w) J^-1/2 D^-1/2
              I[t, s] = sum_j w_{t,j} ReLU(q^I_{t,j} . k^I_s),  s <= t
    choice:   S_t = the index_topk keys s <= t with the largest I[t, s], by
              a STABLE sort of -I (ties to the lower key); every s <= t
              where t < index_topk
    a reuse_attention layer has no indexer: S is the nearest choosing
              layer's before it (the trunk's last, for the MTP module)
    L^I (each choosing layer) = mean_t KL(p_t || softmax_{S_t}(I_t)),
              p_t = (1 / H) sum_h A_h[t, S_t] over the heads HELD, detached
    Experts, MTP: the DeepSeek-V3 family's, as the sibling reference
              ``joyai_llm_flash`` writes them (sigmoid router over ALL
              num_experts, top_k on s + b, gates renormalised and scaled,
              the experts held here, one shared expert)
    loss:     mean CE(main) + mtp_weight x mean CE(mtp) + sum of the L^I

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
kernel, no cache, no sharding, no scan over layers.  It reads the program's
parameter tree only for the numbers in it.  One layer at a time; attention
and the choice one block of query rows at a time (``ROWS`` rows against all
the keys: scores, a stable argsort, a dense softmax under the mask), the
held experts one after another, so that one sequence of 16,384 tokens fits
beside the trainer's state on the chip.

Departures from the published model, each because the configuration file
says so: the family's balance term is LEFT OUT; ``rope_interleave`` and
``indexer_rope_interleave`` are fixed permutations of rotary columns and
are not applied (seeded weights: rotate-half over the last ``rope`` columns
of a head's q and of the ``kv_a`` row, over the FIRST ``rope`` columns of the
indexer's q and k); the inference code's Hadamard rotation of ``q^I`` and
``k^I`` (orthogonal: no score changes) and their FP8 quantisation (a
precision choice) are left out; the indexer's dense warm-up stage is not
modelled (the sparse stage's term alone); a share of the heads: ``p_t``
averages the heads held.

``lowered`` computes part of the model in bfloat16, to show that a
comparison's limit would catch it: ``"rotation"`` the rotary embedding alone
(positions, angles, cos, sin and the products, of the main attention and of
the indexer: a position past 256 is then no longer itself); ``"indexer"``
the indexer alone, its rotation aside (its projections; each head's scores,
the rectifier, the weighted sum over heads and so the scores the choice is
made on); ``"all"`` both and every other product, the attention's scores and
softmax, the router, the logits and the loss.  Norms' statistics and the KL
term stay float32 throughout.  ``wrong`` makes one fault, for
the tests and the control tool: ``dense`` (no choice: every key s <= t),
``window`` (the most recent index_topk keys), ``half_topk`` (index_topk /
2 keys), ``reuse_chooses`` (a reuse_attention layer chooses for itself with
the nearest choosing layer's indexer weights), ``no_relu`` (scores without
the rectifier).  A run sets neither.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp

F32, BF16 = jnp.float32, jnp.bfloat16
INDEX, REUSE = "index_attention", "reuse_attention"
ROWS = 256          # query rows a step of attention and the choice holds
WRONG = ("", "dense", "window", "half_topk", "reuse_chooses", "no_relu")


LOWERED = ("", "all", "rotation", "indexer")


def _dtype(lowered: str):
    """The trunk's precision under ``lowered``."""
    return BF16 if lowered == "all" else F32


def _rotation_dtype(lowered: str):
    return BF16 if lowered in ("all", "rotation") else F32


def _indexer_dtype(lowered: str):
    return BF16 if lowered in ("all", "indexer") else F32


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


def layer_norm(x, p, eps, dtype=F32):
    x32 = x.astype(F32)
    mean = x32.mean(-1, keepdims=True)
    var = ((x32 - mean) ** 2).mean(-1, keepdims=True)
    y = (x32 - mean) / jnp.sqrt(var + eps)
    return (y * p["scale"].astype(F32) + p["bias"].astype(F32)).astype(dtype)


def rope(x, theta, dtype=F32):
    """Rotate-half RoPE on ``[B, S, ..., D]``, positions 0 .. S - 1, in
    ``dtype``: the positions, the angles, cos, sin and the products."""
    half = x.shape[-1] // 2
    inv = (1.0 / theta ** (jnp.arange(half, dtype=F32) / half)).astype(dtype)
    ang = jnp.arange(x.shape[1]).astype(dtype)[:, None] * inv[None, :]
    ang = ang.reshape(1, x.shape[1], *([1] * (x.ndim - 3)), half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(dtype), x[..., half:].astype(dtype)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1
    ).astype(x.dtype)


def _row_block(seq_len: int) -> int:
    rows = min(ROWS, seq_len)
    while seq_len % rows:
        rows -= 1
    return rows


def _by_rows(fn, seq_len: int, *per_row):
    """``fn(first row, *blocks)`` over blocks of query rows of the
    ``per_row`` arrays ``[B, T, ...]``; the results put together again."""
    rows = _row_block(seq_len)

    def step(i):
        blocks = [
            jax.lax.dynamic_slice_in_dim(x, i * rows, rows, axis=1)
            for x in per_row
        ]
        return fn(i * rows, *blocks)

    outs = jax.lax.map(step, jnp.arange(seq_len // rows))
    return jax.tree.map(
        lambda x: jnp.moveaxis(x, 0, 1).reshape(
            x.shape[1], seq_len, *x.shape[3:]
        ), outs,
    )


def _valid(first, rows, seq_len, segment_ids=None):
    """``[B | 1, rows, T]``: key s at or before the query, in its document."""
    at = first + jnp.arange(rows)
    valid = (jnp.arange(seq_len)[None, :] <= at[:, None])[None]
    if segment_ids is not None:
        seg_q = jax.lax.dynamic_slice_in_dim(segment_ids, first, rows, axis=1)
        valid = valid & (seg_q[:, :, None] == segment_ids[:, None, :])
    return valid


def indexer(model, n, c_q, p, lowered=""):
    """``(q^I [B, T, J, D], k^I [B, T, D], w [B, T, J])``, in the indexer's
    precision under ``lowered``."""
    theta, eps = float(model["rope_theta"]), float(model["norm_eps"])
    r = int(model["qk_rope_head_dim"])
    heads, dim = int(model["index_n_heads"]), int(model["index_head_dim"])
    dtype, turn = _indexer_dtype(lowered), _rotation_dtype(lowered)
    n, c_q = n.astype(dtype), c_q.astype(dtype)
    q = jnp.einsum("btl,ljd->btjd", c_q, p["wq_b"]["kernel"].astype(dtype))
    k = layer_norm(
        n @ p["wk"]["kernel"].astype(dtype), p["k_norm"], eps, dtype
    )
    q = jnp.concatenate([rope(q[..., :r], theta, turn), q[..., r:]], -1)
    k = jnp.concatenate([rope(k[..., :r], theta, turn), k[..., r:]], -1)
    w = (n @ p["weights_proj"]["kernel"].astype(dtype)) * dtype(
        heads ** -0.5 * dim ** -0.5
    )
    return q, k, w


def index_scores(q_rows, k, w_rows, relu=True):
    """``I [B, R, T]`` of a block of rows against all the keys: float32
    (the products accumulated, rectified and summed over heads in it) from
    float32 operands, bfloat16 throughout from bfloat16 ones."""
    z = jnp.einsum("brjd,btd->brjt", q_rows, k)
    if relu:
        z = jnp.maximum(z, 0)
    return (z * w_rows[..., None]).sum(axis=2)


def choose(score, valid, topk: int):
    """The ``topk`` valid keys of each row with the largest score by a
    stable sort of ``-score`` (ties to the lower key), as a mask."""
    order = jnp.argsort(
        jnp.where(valid, -score, jnp.inf), axis=-1, stable=True
    )[..., :topk]
    first = jnp.put_along_axis(
        jnp.zeros(score.shape, bool), order, True, axis=-1, inplace=False
    )
    return valid & first


def selection(model, n, c_q, p, segment_ids=None, lowered="", wrong=""):
    """The choice ``[B, T, T]`` (bool) of a choosing layer's indexer ``p``
    on the normed stream ``n`` and the q latent ``c_q``."""
    t, topk = n.shape[1], int(model["index_topk"])
    if wrong == "half_topk":
        topk //= 2
    q, k, w = indexer(model, n, c_q, p, lowered)

    def rows(first, q_rows, w_rows):
        valid = _valid(first, q_rows.shape[1], t, segment_ids)
        valid = jnp.broadcast_to(valid, (q_rows.shape[0], *valid.shape[1:]))
        if wrong == "dense":
            return valid
        if wrong == "window":
            at = first + jnp.arange(q_rows.shape[1])
            return valid & (
                jnp.arange(t)[None, :] > at[:, None] - topk
            )[None]
        score = index_scores(q_rows, k, w_rows, relu=wrong != "no_relu")
        return choose(score, valid, topk)

    return _by_rows(rows, t, q, w)


def latent_qkv(model, n, p, lowered=""):
    """``(q [B, T, H, nope + rope], k likewise, v [B, T, H, v], c_q)``."""
    nope = int(model["qk_nope_head_dim"])
    rank = int(model["kv_lora_rank"])
    eps, theta = float(model["norm_eps"]), float(model["rope_theta"])
    dtype, turn = _dtype(lowered), _rotation_dtype(lowered)

    def w(name):
        return p[name]["kernel"].astype(dtype)

    c_q = rms_norm(n @ w("q_a"), p["q_norm"]["scale"], eps, dtype)
    q = jnp.einsum("bsl,lhk->bshk", c_q, w("q_b"))
    row = n @ w("kv_a")
    c_kv = rms_norm(row[..., :rank], p["kv_norm"]["scale"], eps, dtype)
    kv = jnp.einsum("bsl,lhk->bshk", c_kv, w("kv_b"))
    k_pe = rope(row[..., rank:], theta, turn)[:, :, None, :]
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta, turn)], -1)
    k_pe = jnp.broadcast_to(k_pe, (*kv.shape[:3], k_pe.shape[-1]))
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    return q, k, kv[..., nope:], c_q


def _probabilities(model, q_rows, k, mask_rows):
    """Each head's softmax ``[B, H, R, T]`` over the keys ``mask_rows``
    names, in ``q_rows``' precision (scores and softmax)."""
    scale = float(model.get("attention_scale") or 0.0) or (
        q_rows.shape[-1] ** -0.5
    )
    s = jnp.einsum("brhd,bthd->bhrt", q_rows, k) * q_rows.dtype.type(scale)
    return jax.nn.softmax(jnp.where(mask_rows[:, None], s, -jnp.inf), axis=-1)


def attention_over(model, q, k, v, mask):
    """``(o [B, T, H, v], p [B, T, T])``: each head's softmax over the keys
    ``mask`` names, and the heads' mean probabilities (float32)."""
    def rows(first, q_rows, mask_rows):
        a = _probabilities(model, q_rows, k, mask_rows)
        o = jnp.einsum("bhrt,bthd->brhd", a, v)
        return o, a.astype(F32).mean(axis=1)

    return _by_rows(rows, q.shape[1], q, mask)


def kl_rows(score, mask_rows, p_rows):
    """``KL(p_t || softmax over mask_t of score_t)`` of each row ``[B, R]``;
    ``p_rows`` is read as a constant."""
    p_rows = jax.lax.stop_gradient(p_rows)
    log_q = jax.nn.log_softmax(
        jnp.where(mask_rows, score.astype(F32), -jnp.inf), axis=-1
    )
    live = mask_rows & (p_rows > 0)
    safe = jnp.where(live, p_rows, 1.0)
    return jnp.where(
        live, safe * (jnp.log(safe) - jnp.where(live, log_q, 0.0)), 0.0
    ).sum(axis=-1)


def index_kl(model, n, c_q, p, mask, probs, lowered="", wrong=""):
    """``L^I = mean_t KL(probs_t || softmax over mask_t of I_t)`` of a
    choosing layer's indexer ``p``; ``probs`` is read as a constant."""
    q, k, w = indexer(model, n, c_q, p, lowered)

    def rows(first, q_rows, w_rows, mask_rows, p_rows):
        score = index_scores(q_rows, k, w_rows, relu=wrong != "no_relu")
        return kl_rows(score, mask_rows, p_rows)

    return _by_rows(rows, n.shape[1], q, w, mask, probs).mean()


def attention_and_kl(model, q, k, v, mask, index, wrong=""):
    """``attention_over`` and ``index_kl`` in ONE walk over the rows, so
    that the heads' mean probabilities ``[B, T, T]`` are never whole:
    ``(o, L^I)``; ``index`` is the indexer's ``(q^I, k^I, w)``."""
    q_i, k_i, w = index

    def rows(first, q_rows, mask_rows, q_i_rows, w_rows):
        a = _probabilities(model, q_rows, k, mask_rows)
        o = jnp.einsum("bhrt,bthd->brhd", a, v)
        score = index_scores(q_i_rows, k_i, w_rows, relu=wrong != "no_relu")
        return o, kl_rows(score, mask_rows, a.astype(F32).mean(axis=1))

    o, kl = _by_rows(rows, q.shape[1], q, mask, q_i, w)
    return o, kl.mean()


def sparse_attention(model, n, p, kind, handed, segment_ids=None, lowered="",
                     wrong="", indexer_of=None):
    """One sparse attention layer: ``(y, mask, L^I or None)``.  ``handed``
    is the choice an earlier layer made; ``indexer_of`` the nearest
    choosing layer's indexer weights (the ``reuse_chooses`` fault)."""
    q, k, v, c_q = latent_qkv(model, n, p, lowered)
    detached = jax.lax.stop_gradient((n, c_q))
    chooses = kind == INDEX
    weights = p.get("indexer") if chooses else None
    if not chooses and wrong == "reuse_chooses":
        weights = indexer_of
    mask = handed
    if weights is not None:
        mask = jax.lax.stop_gradient(selection(
            model, *detached, weights, segment_ids, lowered, wrong
        ))
    kl = None
    if chooses:
        o, kl = attention_and_kl(
            model, q, k, v, mask, indexer(model, *detached, weights, lowered),
            wrong,
        )
    else:
        o, _ = attention_over(model, q, k, v, mask)
    y = jnp.einsum("bqhd,hdm->bqm", o, p["wo"]["kernel"].astype(q.dtype))
    return y, mask, kl


def swiglu(n, p, dtype=F32):
    def w(name):
        return p[name]["kernel"].astype(dtype)

    return (jax.nn.silu(n @ w("wg")) * (n @ w("wi"))) @ w("wo")


def router(model, n, p, dtype=F32):
    """``(gates [B, S, E], counts [E])`` over ALL the experts."""
    e, k = int(model["num_experts"]), int(model["top_k"])
    scores = jax.nn.sigmoid(
        n.astype(dtype) @ p["router"]["kernel"].astype(dtype)
    )
    pick = scores
    if "router_bias" in p:
        pick = scores + p["router_bias"].astype(dtype)
    _, top_i = jax.lax.top_k(pick, k)
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if model.get("norm_topk_prob", True):
        top_s = top_s / (top_s.sum(-1, keepdims=True) + 1e-20)
    top_s = top_s * dtype(model.get("routed_scaling_factor", 1.0))
    chosen = jax.nn.one_hot(top_i, e, dtype=dtype)
    gates = (chosen * top_s[..., None]).sum(-2)
    return gates, chosen.astype(F32).sum(axis=(0, 1, 2))


def routed_part(model, n, p, dtype=F32):
    """``(sum over the chosen experts HELD HERE of g_e SwiGLU_e(n), counts
    [E])``; ``p["wi"]`` .. hold the held experts only."""
    held = p["wi"].shape[0]
    first = int(model.get("first_expert") or 0)
    gates, counts = router(model, n, p, dtype)

    def add_expert(i, out):
        def w(name):
            return jax.lax.dynamic_index_in_dim(
                p[name], i, 0, False
            ).astype(dtype)

        y = (jax.nn.silu(n @ w("wg")) * (n @ w("wi"))) @ w("wo")
        gate = jax.lax.dynamic_index_in_dim(gates, first + i, 2, True)
        return out + (y * gate).astype(dtype)

    out = jax.lax.fori_loop(0, held, add_expert, jnp.zeros_like(n))
    return out, counts


def expert_layer(model, n, p, dtype=F32):
    out, counts = routed_part(model, n, p, dtype)
    if "shared" in p:
        out = out + swiglu(n, p["shared"], dtype)
    return out, counts


@functools.partial(jax.jit, static_argnums=(0, 3, 5, 6))
def _block(model_items, x, p, kind, handed, lowered, wrong, indexer_of=None):
    """One layer: ``(x, mask, L^I or None, counts or None)``."""
    model = dict(model_items)
    dtype, eps = _dtype(lowered), float(model["norm_eps"])
    y, mask, kl = sparse_attention(
        model, rms_norm(x, p["ln_attn"]["scale"], eps, dtype), p["attn"],
        kind, handed, None, lowered, wrong, indexer_of,
    )
    x = x + y
    n = rms_norm(x, p["ln_mlp"]["scale"], eps, dtype)
    if "moe" in p:
        y, counts = expert_layer(model, n, p["moe"], dtype)
        return x + y, mask, kl, counts
    return x + swiglu(n, p["mlp"], dtype), mask, kl, None


@functools.partial(jax.jit, static_argnums=(0, 4))
def _mtp_input(model_items, p, hidden, next_embed, lowered):
    model = dict(model_items)
    dtype, eps = _dtype(lowered), float(model["norm_eps"])
    both = jnp.concatenate([
        rms_norm(hidden, p["hnorm"]["scale"], eps, dtype),
        rms_norm(next_embed, p["enorm"]["scale"], eps, dtype),
    ], axis=-1)
    return both @ p["proj"]["kernel"].astype(dtype)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head_nll(norm_scale, head, x, targets, eps, lowered):
    dtype = _dtype(lowered)
    x = rms_norm(x, norm_scale, eps, dtype)
    logp = jax.nn.log_softmax(x @ head.astype(dtype), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0].astype(
        F32
    )


def layer_kind(model, layer: int) -> str:
    """The trunk starts a period after the dense prefix, whose layers
    continue the pattern backwards."""
    pattern = tuple(model["layer_pattern"])
    dense = int(model.get("first_k_dense") or 0)
    return pattern[(layer - dense) % len(pattern)]


def _trunk_layers(model, params) -> List[Tuple[str, Any]]:
    """``(kind, weights)`` of the trunk's layers in order."""
    dense = int(model.get("first_k_dense") or 0)
    pattern = tuple(model["layer_pattern"])
    layers = [
        (layer_kind(model, i), params[f"dense_{i}"]) for i in range(dense)
    ]
    for i in range(int(model["num_layers"]) - dense):
        kind = pattern[i % len(pattern)]
        if "blocks" in params:
            slot = f"{kind.split('_')[0]}_{i % len(pattern)}"
            layers.append((kind, jax.tree.map(
                lambda a: a[i // len(pattern)], params["blocks"][slot]
            )))
        else:
            layers.append((kind, params[f"block_{dense + i}"]))
    return layers


def forward(model: Mapping[str, Any], params, tokens, targets=None,
            lowered: str = "", wrong: str = "",
            masks: bool = True) -> Dict[str, Any]:
    """``hidden`` (before the final norm), ``masks`` (each layer's choice,
    ``[B, T, T]`` bool, the trunk's layers in order and then the MTP
    module's), ``index_kl`` (each choosing layer's ``L^I``, likewise) and
    ``counts`` (each expert layer's tokens per expert); with ``targets``
    ``nll`` ``[B, S]`` and, with an MTP module in ``params``, ``mtp_nll``
    ``[B, S - 1]`` (position ``i`` against ``targets[i + 1]``).  Without
    ``masks`` the choices are dropped as the layers go."""
    if wrong not in WRONG:
        raise ValueError(f"wrong must be one of {WRONG}, got {wrong!r}")
    if lowered not in LOWERED:
        raise ValueError(f"lowered must be one of {LOWERED}, got {lowered!r}")
    items = _items(model)
    dtype, eps = _dtype(lowered), float(model["norm_eps"])
    with jax.default_matmul_precision("highest"):
        table = params["embed"]["embedding"].astype(dtype)
        x = table[tokens]
        out = {"masks": [], "index_kl": [], "counts": []}
        mask, indexer_of = None, None

        def run(x, kind, layer, mask, indexer_of):
            x, mask, kl, counts = _block(
                items, x, layer, kind, mask, lowered, wrong, indexer_of
            )
            if masks:           # 268 MB a layer at 16,384 tokens
                out["masks"].append(mask)
            if kl is not None:
                out["index_kl"].append(kl)
            if counts is not None:
                out["counts"].append(counts)
            return x, mask, layer["attn"].get("indexer", indexer_of)

        for kind, layer in _trunk_layers(model, params):
            x, mask, indexer_of = run(x, kind, layer, mask, indexer_of)
        out["hidden"] = x
        if targets is None:
            return out
        head = params["lm_head"]["kernel"]
        out["nll"] = _head_nll(
            params["ln_final"]["scale"], head, x, targets, eps, lowered
        )
        if "mtp" in params:
            mtp = params["mtp"]
            y = _mtp_input(items, mtp, x, table[targets], lowered)
            y, _, _ = run(
                y, model["mtp_layer_kind"], mtp["block"], mask, indexer_of
            )
            out["mtp_nll"] = _head_nll(
                mtp["norm"]["scale"], head, y[:, :-1], targets[:, 1:], eps,
                lowered,
            )
        return out


def token_nll(model, params, tokens, targets, lowered: str = "",
              wrong: str = ""):
    """Per-token negative log-likelihood [B, S] of the main head, float32.

    ``model`` is the ``model`` group of a configuration file (the
    program's ``TransformerConfig`` fields as plain numbers and strings);
    ``params`` the program's parameter tree."""
    return forward(
        model, params, tokens, targets, lowered, wrong, masks=False
    )["nll"]


def mtp_token_nll(model, params, tokens, targets, lowered: str = ""):
    """The MTP module's per-token nll [B, S - 1]: position ``i`` (hidden
    state ``i``, embedding of ``targets[i]``) against ``targets[i + 1]``."""
    return forward(model, params, tokens, targets, lowered)["mtp_nll"]


def loss(model, params, tokens, targets):
    """``mean(nll) + mtp_weight x mean(mtp_nll) + the sum of the choosing
    layers' L^I``: what the step trains."""
    out = forward(model, params, tokens, targets)
    total = out["nll"].mean()
    if "mtp_nll" in out:
        total = total + F32(model.get("mtp_weight", 0.3)) * out[
            "mtp_nll"
        ].mean()
    return total + sum(out["index_kl"])


def loss_and_grads(model, params, tokens, targets):
    return jax.value_and_grad(loss, argnums=1)(model, params, tokens, targets)
