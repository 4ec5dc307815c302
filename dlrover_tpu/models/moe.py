"""Mixture-of-experts layer with expert parallelism.

Counterpart of the reference's MoE stack
(ref ``atorch/atorch/modules/moe/moe_layer.py:22-611`` — ``_AllToAll`` token
dispatch, ``topk_gating.py``, ``grouped_gemm_moe.py:46``).

TPU-first design: the classic dense-dispatch MoE (Shazeer/mesh-TF lineage) —
gating produces a static-shaped dispatch tensor ``[B, S, E, C]`` and the token
shuffle is an einsum whose expert dim is sharded over the ``expert`` mesh
axis, so GSPMD inserts the a2a the reference writes by hand.  Everything is
static-shaped and MXU-friendly; the grouped-GEMM Pallas kernel
(``dlrover_tpu.ops.grouped_matmul``) is the drop-in upgrade for the expert
matmuls at larger expert counts.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.models import layers
from dlrover_tpu.models.family import Family
from dlrover_tpu.ops import row_gather_sum
from dlrover_tpu.parallel import rules as lr


def ungated(h: jax.Array, activation: str) -> jax.Array:
    """An MLP's hidden activation where no gate multiplies it: ``gelu``, or
    ``relu2``, the rectifier squared (Nemotron-H's experts and MLPs)."""
    if activation == "relu2":
        return jnp.square(nn.relu(h))
    return nn.gelu(h)


# Both jitted, so that a step program traces each body once and calls it from
# every router, forward, recomputed and transposed: traced in line, Ling's
# warm set-up read 9 % longer (95.0 s against 86.7) and its step 1.8 % slower.
@functools.partial(jax.jit, static_argnames="k")
def top_places(x: jax.Array, k: int) -> jax.Array:
    """The places of the k largest entries of ``x`` [..., E], largest
    first, equal entries by place: ``lax.top_k``'s indices, int32, by k
    passes of compare and select (the largest entry's first place, then
    that entry at -inf) where ``top_k`` compiles to a sort of all E.  ``x``
    holds k entries above -inf."""
    x = jax.lax.stop_gradient(x)
    lanes = jnp.arange(x.shape[-1], dtype=jnp.int32)
    places = []
    for _ in range(k):
        at = jax.lax.argmax(x, x.ndim - 1, jnp.int32)
        places.append(at)
        x = jnp.where(lanes == at[..., None], -jnp.inf, x)
    return jnp.stack(places, axis=-1)


@jax.jit
def scores_at(scores: jax.Array, idx: jax.Array) -> jax.Array:
    """``scores[..., idx]`` ([..., E] at [..., k] -> [..., k]) by compare and
    select, a slot a pass: slot j keeps the one entry whose place is
    ``idx[..., j]`` and sums over the experts, so the forward is
    elementwise-and-reduce over ``scores`` and autodiff's transpose a masked
    broadcast of the cotangent, summed over the k slots; nothing wider than
    ``[..., E]`` is ever written.  The same numbers as ``take_along_axis``
    gives, bit for bit, gradient too (a sum has one term that is not 0)."""
    lanes = jnp.arange(scores.shape[-1], dtype=idx.dtype)
    return jnp.stack([
        jnp.where(
            lanes == jax.lax.index_in_dim(idx, j, axis=-1), scores, 0
        ).sum(axis=-1)
        for j in range(idx.shape[-1])
    ], axis=-1)


def group_limited(pick: jax.Array, groups: int, topk_group: int) -> jax.Array:
    """``pick`` [..., E] with every expert outside a token's ``topk_group``
    best groups at -inf (DeepSeek-V3 §2.1.2's node-limited choice): the E
    experts are ``groups`` runs of E / groups consecutive ones, a group's
    score is the sum of its two largest entries."""
    e = pick.shape[-1]
    grouped = pick.reshape(*pick.shape[:-1], groups, e // groups)
    score = scores_at(grouped, top_places(grouped, 2)).sum(axis=-1)  # [..., G]
    best = top_places(score, topk_group)
    keep = jax.nn.one_hot(best, groups, dtype=jnp.bool_).any(axis=-2)
    return jnp.where(keep[..., None], grouped, -jnp.inf).reshape(pick.shape)


def _gate(logits: jax.Array, k: int, norm_topk_prob: bool = True,
          aux_form: str = "top1", scoring: str = "softmax", bias=None,
          scale: float = 1.0, groups: int = 1, topk_group: int = 1,
          norm_eps: float = 1e-20):
    """Shared top-k gate: (gate_vals, gate_idx, aux_loss), float32.

    ``norm_topk_prob`` renormalises the chosen gates to sum to one
    (Mixtral); without it they are the chosen experts' softmax
    probabilities as they are (OLMoE).  ``aux_form`` picks the
    load-balancing loss: ``"top1"`` (Switch: share of first choices x mean
    probability, x E^2/k) or ``"topk"`` (E x sum_e f_e x P_e, ``f_e`` the
    share of tokens that chose ``e`` in any of their k slots).

    ``scoring="sigmoid"`` is the DeepSeek-V3 family's router
    (``topk_method: noaux_tc``): ``s = sigmoid(logits)``; the k experts are
    chosen on ``s + bias`` (``bias`` picks, it never weighs, and takes no
    gradient); the gates are the chosen ``s``, renormalised under
    ``norm_topk_prob`` (divided by their sum + ``norm_eps``: 1e-20 in the
    DeepSeek-V3 family's code, 1e-6 in LFM2's), times ``scale``; there is
    no auxiliary loss (the
    bias is moved by :func:`bias_update` after each step instead).  With
    ``groups`` > 1 the choice is group-limited (:func:`group_limited`, on
    ``s + bias`` too): the k come from a token's ``topk_group`` best
    groups.  The k places are found and their scores read by compare and
    select over the experts axis (:func:`top_places`, :func:`scores_at`),
    because on the chip ``take_along_axis`` here was a gather run an element
    at a time (1.5-1.7 ms a call for 131,072 picks, 43 ms of a JoyAI step,
    its transpose a scatter behind a sort) and ``lax.top_k`` a sort of all E.
    """
    if groups > 1 and scoring != "sigmoid":
        raise ValueError(
            "a group-limited choice is the sigmoid router's, got "
            f"scoring={scoring!r}"
        )
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        pick = scores if bias is None else scores + jax.lax.stop_gradient(
            bias.astype(jnp.float32)
        )
        if groups > 1:
            pick = group_limited(pick, groups, topk_group)
        gate_idx = top_places(pick, k)
        gate_vals = scores_at(scores, gate_idx)
        if norm_topk_prob:
            gate_vals = gate_vals / (
                jnp.sum(gate_vals, axis=-1, keepdims=True) + norm_eps
            )
        return gate_vals * scale, gate_idx, jnp.zeros((), jnp.float32)
    if scoring != "softmax":
        raise ValueError(
            f"unknown router scoring {scoring!r}; expected 'softmax' or "
            "'sigmoid'"
        )
    e = logits.shape[-1]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)            # [B,S,k]
    if norm_topk_prob:
        gate_vals = gate_vals / jnp.clip(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
        )
    density_proxy = jnp.mean(probs, axis=(0, 1))             # [E]
    if aux_form == "top1":
        top1_onehot = jax.nn.one_hot(gate_idx[..., 0], e, dtype=jnp.float32)
        density = jnp.mean(top1_onehot, axis=(0, 1))         # [E]
        aux_loss = jnp.sum(density * density_proxy) * (e ** 2) / k
    elif aux_form == "topk":
        chosen = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32).sum(axis=-2)
        aux_loss = jnp.sum(
            jnp.mean(chosen, axis=(0, 1)) * density_proxy
        ) * e
    else:
        raise ValueError(
            f"unknown MoE aux_form {aux_form!r}; expected 'top1' or 'topk'"
        )
    return gate_vals, gate_idx, aux_loss


def bias_update(bias: jax.Array, load: jax.Array, rate: float) -> jax.Array:
    """The auxiliary-loss-free balancing rule (DeepSeek-V3): after a step,
    ``b_e += rate * sign(mean load - load_e)`` from that step's own
    per-expert loads (any positive multiple of the counts: only the order
    against the mean matters).  ``bias`` and ``load`` are ``[..., E]``."""
    load = load.astype(jnp.float32)
    step = jnp.sign(load.mean(axis=-1, keepdims=True) - load)
    return (bias.astype(jnp.float32) + rate * step).astype(bias.dtype)


def top_k_gating(
    logits: jax.Array, k: int, capacity: int, norm_topk_prob: bool = True,
    aux_form: str = "top1",
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Top-k gating with per-expert capacity (Switch/GShard style).

    Returns ``(dispatch, combine, aux_loss, routed)`` with
    ``dispatch: [B, S, E, C]`` bool-ish one-hot of (expert, slot) per token,
    ``combine: [B, S, E, C]`` gate-weighted dispatch, the load-balancing
    auxiliary loss (ref ``topk_gating.py`` capability), and ``routed: [E]``
    the (token, choice) pairs each expert keeps: ``dispatch`` summed over
    all but its expert axis, read off the slot counters instead (the train
    step books it every step, and ``dispatch`` is a gigabyte at Mixtral's
    shapes).
    """
    b, s, e = logits.shape
    gate_vals, gate_idx, aux_loss = _gate(
        logits, k, norm_topk_prob, aux_form
    )

    # Assign capacity slots expert-by-expert in token order.  Slots taken by
    # earlier choice ranks offset later ranks (`prior`), so a token picked
    # 2nd-choice never collides with one picked 1st-choice.
    dispatch = jnp.zeros((b, s, e, capacity), dtype=jnp.float32)
    combine = jnp.zeros((b, s, e, capacity), dtype=jnp.float32)
    prior = jnp.zeros((b, 1, e), dtype=jnp.float32)          # slots used so far
    for choice in range(k):
        idx = gate_idx[..., choice]                          # [B,S]
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)   # [B,S,E]
        # position of this token within its expert's queue
        pos = jnp.cumsum(onehot, axis=1) - onehot + prior    # [B,S,E]
        in_cap = pos < capacity
        onehot = onehot * in_cap
        prior = prior + onehot.sum(axis=1, keepdims=True)
        slot = jax.nn.one_hot(
            (pos * onehot).sum(-1).astype(jnp.int32), capacity, dtype=jnp.float32
        )                                                     # [B,S,C]
        d = onehot[..., None] * slot[..., None, :]            # [B,S,E,C]
        dispatch = dispatch + d
        combine = combine + d * gate_vals[..., choice][..., None, None]
    return dispatch, combine, aux_loss, prior.sum(axis=(0, 1))


def _router_entropy(router_logits: jax.Array,
                    scoring: str = "softmax") -> jax.Array:
    """Mean per-token entropy of the router distribution (nats): the
    softmax, or a sigmoid router's scores normalised over the experts."""
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
        probs = scores / scores.sum(axis=-1, keepdims=True)
    else:
        probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    return jnp.mean(-jnp.sum(probs * jnp.log(probs + 1e-9), axis=-1))


# What a layer told its share of the experts sows beside ``moe_stats``:
# ``[pairs_here, bias_absmax]``, the share of the routed pairs this chip
# computed and the router bias's largest entry; under a group-limited
# choice a third entry, ``tokens_here``, the share of the tokens with at
# least one pair here (the rows an exchange would have to send this chip:
# fewer than a binomial share of every token's k would give, because a
# token whose best groups leave this chip's experts out sends nothing;
# without a limit it is that binomial share and is not sown).
STATS_NAME = "moe_stats"
SHARE_STATS_NAME = "moe_share_stats"


STATS_TAIL = 2  # [pad_share, max_expert_load] after the per-expert loads


def split_stats(vec):
    """``(entropy, drop_fraction, load[E], pad_share, max_expert_load)`` of
    one ``moe_stats`` vector (:meth:`MoEMlp._sow_router_stats`), or of a
    stack of them along leading axes."""
    return (vec[..., 0], vec[..., 1], vec[..., 2:-STATS_TAIL], vec[..., -2],
            vec[..., -1])


def fold_share_stats(rows):
    """``[n, 2]`` ``moe_share_stats`` vectors (layers, microbatches) as
    one: the mean share of the routed pairs computed here, the largest
    router-bias entry; of ``[n, 3]`` also the mean share of the tokens
    with a pair here."""
    folded = [rows[:, 0].mean(), rows[:, 1].max()]
    if rows.shape[1] > 2:
        folded.append(rows[:, 2].mean())
    return jnp.stack(folded)


# -- dropless dispatch: every move of rows is a gather -------------------------
#
# A (token, expert) pair has one row among the expert-grouped rows and each
# token exactly k of them, so rows <- tokens, tokens <- rows and both their
# transposes are gathers (plus a sum over k), never a scatter.  The two
# directions are not alike on a TPU (v5e, 139,264 rows of 2048 bf16):
#
# * rows <- tokens (``x[token of row]``, output in row order from a 64 MiB
#   source) is fast as XLA writes it: 0.9-1.2 ms for 570 MB.
# * tokens <- rows (a token's k rows, fetched and summed) is not.  XLA's
#   gather lands ``[T, k, D]`` first, whose second-minor k = 8 pads to the
#   16-row bf16 tile, converts it to float32 and reduces it in a second
#   pass: 2.7 GB of traffic for 67 MB of output, 5.4 ms.  A choice-major
#   ``[k * T, D]`` gather is no faster (5.4-8.7 ms): a row of a 2-D bf16
#   array is sixteen strided 256-byte pieces of a (16, 128) tile.
#
# So the k-row fetch is fused (``ops/row_gather_sum.py``: one DMA a row out
# of a ROW-TILED ``[R, D // 128, 128]`` array, summed in float32 in VMEM,
# 2.3 ms, bound by the DMA issue rate), no ``[T, k, D]`` array exists, and
# where that kernel fits the d_model-wide rows stay row-tiled from the
# gather that makes them to the GEMMs, which reshape blocks in VMEM for
# nothing; turning a plain ``[R, D]`` array row-tiled is a 1.8 ms copy.
#
# Under a share of the experts a pair routed elsewhere (or past the budget)
# has no row here and its ``dest`` names the last row, which stays zero.
# XLA's gather fetches that row like any other; the kernel, bound by the
# DMAs it issues, is instead handed the pairs that have a row (the plan's
# ``live``: ``row_gather_sum.live_pairs`` of ``dest``, made once a layer
# for the combine and for the scatter's transpose) and fetches and adds
# those alone: an eighth of the DMAs, 0.5 ms a call where every pair costs
# 2.6.  With every expert held no list is made and the call is as it was.


def _row_budget(pairs: int, block: int, experts: int) -> int:
    """Rows the grouped GEMMs run for ``pairs`` routed pairs: every pair
    plus at most one partial block of padding per expert, in whole blocks."""
    return ((pairs + block - 1) // block + experts) * block


def _share_row_budget(pairs: int, block: int, held: int, total: int,
                      multiple: float) -> int:
    """Rows set aside where ``held`` of ``total`` experts live here:
    ``multiple`` x the expected share of the ``pairs`` the router chose,
    one block of padding an expert, and one block more that no pair is
    ever given (its rows stay zero: where the pairs routed elsewhere, and
    any beyond this budget, point).  The GEMMs skip the row blocks after
    the last live one, so the slack costs memory and no GEMM time."""
    expected = int(multiple * pairs * held / total)
    return _row_budget(expected, block, held) + block


def _dispatch_plan(gate_idx, experts: int, block: int, n_pad: int,
                   first: int = 0, total: int = 0):
    """Where each (token, choice) pair's row lives among the expert-grouped
    rows, and which pair each row holds, from ``gate_idx`` ``[T, k]``.

    The plan is over the ``experts`` held here, ``first .. first +
    experts - 1`` of the ``total`` the router chose among (all of them by
    default).  A pair routed to an expert that lives elsewhere has no row
    here; nor has a pair past the budget (``n_pad`` rows less the zero
    block): both point at the last row, which no pair is given and the
    GEMMs leave zero.

    ``padded`` [E]: rows of each expert's group (whole blocks);
    ``dest`` [T, k]: the pair's row; ``row_pair`` [n_pad]: the row's pair
    (flat ``token * k + choice``), ``T * k`` where the row is padding;
    under a share also ``kept`` []: the pairs that have a row, and
    ``here`` []: the pairs routed to an expert held here.  (Where its rows
    go through the fetch-and-sum kernel the layer adds ``live``: the list
    of the pairs that have a row, in the form that kernel reads.)"""
    t, k = gate_idx.shape
    n = t * k
    share = bool(total) and experts < total
    flat = gate_idx.reshape(n)
    if share:
        # experts elsewhere sort after every expert held here
        flat = flat - first
        flat = jnp.where((flat >= 0) & (flat < experts), flat, experts)
    onehot = (flat[:, None] == jnp.arange(experts)[None, :]).astype(jnp.int32)
    seen = jnp.cumsum(onehot, axis=0)                           # [N, E]
    counts = seen[-1]
    padded = ((counts + block - 1) // block) * block
    group_ends = jnp.cumsum(padded)
    group_starts = group_ends - padded
    kept = counts
    if share:
        # a group ends where the budget does, less the zero block
        limit = n_pad - block
        padded = jnp.clip(limit - group_starts, 0, padded)
        kept = jnp.minimum(counts, padded)
        group_ends = group_starts + padded
    count_starts = jnp.cumsum(counts) - counts
    # group start + rank of the pair within its expert's group, token order
    dest = jnp.sum(onehot * (seen - 1 + group_starts[None, :]), axis=1)
    if share:
        placed = jnp.sum(onehot * (seen <= kept[None, :]), axis=1) > 0
        dest = jnp.where(placed, dest, n_pad - 1)
    # pairs in expert order: a stable sort keeps the token order per expert
    _, order = jax.lax.sort(
        (flat, jnp.arange(n, dtype=jnp.int32)), num_keys=1, is_stable=True
    )
    row = jnp.arange(n_pad, dtype=jnp.int32)
    expert_of_row = jnp.minimum(
        jnp.sum(row[:, None] >= group_ends[None, :], axis=1), experts - 1
    )
    rank = row - group_starts[expert_of_row]
    live = rank < kept[expert_of_row]
    if share:
        live = live & (rank >= 0)
    row_pair = jnp.where(
        live,
        order[jnp.clip(count_starts[expert_of_row] + rank, 0, n - 1)], n,
    )
    plan = {"padded": padded, "dest": dest.reshape(t, k), "row_pair": row_pair}
    if share:
        plan.update(kept=kept.sum(), here=counts.sum())
    return plan


def _zero_row(x):
    """``x`` with one row of zeros after its last: what a padding row's
    index (one past the end) gathers, so no pass has to mask them."""
    return jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)])


def _row_path(d: int, k: int, dtype) -> Tuple[bool, int]:
    """How ``d``-wide rows of ``dtype``, ``k`` a token, move through the
    dropless dispatch: whether they live row-tiled between the gathers and
    the GEMMs (``row_gather_sum.kernel_fits``), and the width the
    fetch-and-sum kernel fetches of them (``d``, else the padded view's,
    else 0: XLA's gather and reduction)."""
    tiled = row_gather_sum.kernel_fits(d, k, dtype)
    return tiled, d if tiled else row_gather_sum.padded_width(d, k, dtype)


def _rows_for(x, pair_token, tiled: bool):
    """``x[pair_token]`` with a zero row for the index one past the end:
    ``[R, D]``, or row-tiled ``[R, D // 128, 128]`` (a row is then whole
    native tiles, which is what the fetch-and-sum kernel can DMA)."""
    src = _zero_row(x)
    return (row_gather_sum.row_tiled(src) if tiled else src)[pair_token]


def _k_rows_summed(rows, dest, gates=None, live=None):
    """``out[t] = sum_j gates[t, j] * rows[dest[t, j]]`` (no ``gates``: the
    plain sum) as ``[T, D]``, the gates and the sum in float32.  Row-tiled
    ``rows`` go through the kernel, which fetches and sums in one pass:
    every pair, or given a share's ``live`` list only those that have a row
    here (the others name the zero row).  So do plain rows of whole lanes
    that a pad makes whole tiles (``row_gather_sum.padded_width``), padded
    at the kernel's door and the sums cut back."""
    if rows.ndim == 3:
        return row_gather_sum.gather_sum(rows, dest, gates, live=live)
    if row_gather_sum.padded_width(rows.shape[1], dest.shape[1], rows.dtype):
        return row_gather_sum.padded_gather_sum(rows, dest, gates, live=live)
    picked = rows[dest].astype(jnp.float32)                     # [T, k, D]
    if gates is not None:
        picked = picked * gates[..., None]
    return picked.sum(axis=1).astype(rows.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rows_of_tokens(x, plan, tiled=False):
    """``rows[r] = x[token of row r]``, zero where the row is padding."""
    k = plan["dest"].shape[1]
    return _rows_for(x, plan["row_pair"] // k, tiled)


def _rows_of_tokens_fwd(x, plan, tiled):
    return _rows_of_tokens(x, plan, tiled), plan


def _rows_of_tokens_bwd(tiled, plan, d_rows):
    # each token's k rows, summed: the scatter-add's transpose as a gather
    return _k_rows_summed(d_rows, plan["dest"], live=plan.get("live")), None


_rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)


@jax.custom_vjp
def _tokens_of_rows(out_rows, gates, plan):
    """``out[t] = sum_j gates[t, j] * out_rows[row of pair (t, j)]``, the
    gates and the sum in float32; ``out_rows`` plain or row-tiled."""
    return _k_rows_summed(out_rows, plan["dest"], gates, plan.get("live"))


def _tokens_of_rows_fwd(out_rows, gates, plan):
    return _tokens_of_rows(out_rows, gates, plan), (out_rows, gates, plan)


def _tokens_of_rows_bwd(residuals, d_out):
    out_rows, gates, plan = residuals
    k = gates.shape[1]
    pair = plan["row_pair"]
    within_row = tuple(range(1, out_rows.ndim))
    row_gate = jnp.expand_dims(_zero_row(gates.reshape(-1))[pair], within_row)
    # d_out in ROW order (the fast direction) serves both cotangents: a
    # gate's is the dot of its row with its token's d_out, formed here and
    # read back as k scalars a token, not from a second fetch of k rows.
    d_out_rows = _rows_for(d_out, pair // k, out_rows.ndim == 3).astype(
        jnp.float32
    )
    d_rows = (d_out_rows * row_gate).astype(out_rows.dtype)
    row_dot = jnp.sum(
        out_rows.astype(jnp.float32) * d_out_rows, axis=within_row
    )
    return d_rows, row_dot[plan["dest"]].astype(gates.dtype), None


_tokens_of_rows.defvjp(_tokens_of_rows_fwd, _tokens_of_rows_bwd)


class MoEMlp(nn.Module):
    """Expert-parallel MLP with top-k routing.

    Dispatch paths:

    * ``"einsum"`` — classic dense capacity dispatch (Shazeer/mesh-TF
      lineage): static [B, S, E, C] tensors whose expert dim shards over the
      ``expert`` mesh axis, GSPMD inserting the a2a.  Tokens beyond an
      expert's capacity are dropped; capacity padding burns FLOPs.
    * ``"a2a"`` / ``"a2a_int8"`` — the einsum math with an EXPLICIT
      all-to-all wire leg (ref ``moe_layer.py`` ``_AllToAll``): under
      ``shard_map`` each expert shard exchanges its local batch chunks
      with every other expert-axis peer before the expert matmuls, and
      the inverse exchange routes results home before the combine.  The
      expert compute is elementwise over the batch dim, so the
      shuffle/unshuffle pair is semantically the identity — what it buys
      is control of the transport: ``"a2a_int8"`` rides
      :func:`~dlrover_tpu.parallel.quantized_collectives.quantized_all_to_all`
      (~(1 + 4/block) bytes/element vs 4 for ``"a2a"``'s fp32 wire, both
      legs, forward and backward).  With a unit expert axis both modes
      are exactly ``"einsum"`` (no wire → no-op, no quantization).
    * ``"grouped"`` — dropless megablocks-style dispatch through the Pallas
      grouped-matmul kernel (ref
      ``atorch/atorch/modules/moe/grouped_gemm_moe.py:46``): token-choices
      are sorted by expert and each expert's ragged row group runs as one
      grouped GEMM — no token drops, padding bounded by E x block rows
      instead of the capacity factor.  **Per-device only**: the kernel
      sees local rows, so it cannot shard over an expert mesh axis > 1 —
      that combination raises (see PROFILE.md round 19) rather than
      silently computing with the wrong experts; use an a2a/einsum mode
      under expert parallelism.

    A chip's share of the experts (``experts_held`` < ``num_experts``,
    ``"grouped"`` only): the layer holds experts ``first_expert ..
    first_expert + experts_held - 1`` of a layer whose other experts live
    on further chips.  The router keeps its ``num_experts`` outputs and its
    ``top_k`` a token; the layer computes the part of the result its own
    experts give for the pairs routed to them, and what the absent
    experts would add is left out (on one chip the layer runs without its
    exchange; nothing stands in for the absent chips).  The dispatch plan
    is over the held experts, the row budget a stated multiple of the
    expected share (:func:`_share_row_budget`) whose dead row blocks the
    GEMMs skip, and a pair beyond it is dropped and counted in
    ``drop_fraction``, never silently.

    ``scoring="sigmoid"`` with ``router_bias`` is the DeepSeek-V3 router
    (:func:`_gate`); the bias ``router_bias`` ``[num_experts]`` is a
    parameter no gradient reaches, moved by :func:`bias_update` in the
    train step.  ``shared_d_ff`` adds a shared expert (a plain MLP of the
    layer's ``activation`` and that width, ``shared``) every token passes
    through, its output times ``shared_scale``: several shared experts
    SUMMED are one MLP as wide as all of them (scale 1), AVERAGED that sum
    over their published count, and a chip's share of them the same MLP
    narrower, under the same scale.  ``router_groups`` > 1 makes the choice group-limited
    (``router_topk_groups`` of the groups a token, :func:`group_limited`);
    under a share the pairs that land here are then no binomial thinning of
    every token's k (a token whose best groups leave this chip's out sends
    nothing), which ``tokens_here`` reads.  Without a gate (``activation``
    ``"gelu"`` or ``"relu2"``) an expert is two matrices and the grouped
    path two grouped GEMMs.

    Router observability: every forward ``sow``s a ``moe_stats`` vector
    ``[gate_entropy, drop_fraction, load_0..load_{E-1}, pad_share,
    max_expert_load]`` (:func:`split_stats`) into the
    ``"intermediates"`` collection — a no-op (zero cost) unless the
    caller applies with ``mutable=["intermediates"]``.  The train step is
    that caller (``train_lib._forward_sums``): the vector leaves the step
    program as ``metrics["moe_stats"]``, and the trainer reads it on the
    report cadence.  Serving, RL and the references apply without it.
    """

    num_experts: int
    d_ff: int
    top_k: int = 2
    capacity_factor: float = 1.25
    activation: str = "swiglu"
    dtype: layers.Dtype = jnp.bfloat16
    param_dtype: layers.Dtype = jnp.float32
    dispatch: str = "einsum"        # "einsum" | "a2a" | "a2a_int8" | "grouped"
    gmm_block_rows: int = 128
    norm_topk_prob: bool = True     # see :func:`_gate`
    aux_form: str = "top1"
    scoring: str = "softmax"        # "softmax" | "sigmoid"
    router_bias: bool = False       # choose on score + bias (sigmoid only)
    routed_scale: float = 1.0       # the gates' factor (sigmoid only)
    experts_held: int = 0           # 0 -> all num_experts live here
    first_expert: int = 0
    shared_d_ff: int = 0            # 0 -> no shared expert
    # What the shared MLP's output is multiplied by before it joins (1 / the
    # published count where the shared experts are AVERAGED, whether the MLP
    # holds all of them or a chip's share).
    shared_scale: float = 1.0
    row_budget_multiple: float = 1.25
    router_groups: int = 1          # > 1: a group-limited choice
    router_topk_groups: int = 1
    router_norm_eps: float = 1e-20  # beside the renormalising sum (sigmoid)

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        b, s, d = x.shape
        e = self.held
        check_share(
            self.num_experts, self.experts_held, self.first_expert,
            self.dispatch,
        )
        if self.router_bias and self.scoring != "sigmoid":
            raise ValueError(
                "router_bias corrects a sigmoid router's choice "
                f"(scoring='sigmoid'), got scoring={self.scoring!r}"
            )

        router_logits = layers.DenseGeneral(
            self.num_experts,
            kernel_axes=(lr.EMBED, None),
            dtype=jnp.float32,
            param_dtype=self.param_dtype,
            name="router",
        )(x.astype(jnp.float32))

        wi_shape = (e, d, self.d_ff)
        wi_axes = (lr.EXPERT, lr.EMBED, lr.MLP)
        wo = self.param(
            "wo",
            nn.with_logical_partitioning(
                layers.default_kernel_init, (lr.EXPERT, lr.MLP, lr.EMBED)
            ),
            (e, self.d_ff, d),
            self.param_dtype,
        ).astype(self.dtype)
        wi = self.param(
            "wi",
            nn.with_logical_partitioning(layers.default_kernel_init, wi_axes),
            wi_shape,
            self.param_dtype,
        ).astype(self.dtype)
        wg = None
        if self.activation == "swiglu":
            wg = self.param(
                "wg",
                nn.with_logical_partitioning(layers.default_kernel_init, wi_axes),
                wi_shape,
                self.param_dtype,
            ).astype(self.dtype)

        bias = None
        if self.router_bias:
            bias = self.param(
                "router_bias",
                nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), (None,)
                ),
                (self.num_experts,), jnp.float32,
            )
        shared = None
        if self.shared_d_ff:
            from dlrover_tpu.models.transformer import Mlp

            shared = Mlp(
                d_ff=self.shared_d_ff, activation=self.activation,
                use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype, name="shared",
            )(x)
            if self.shared_scale != 1.0:
                shared = shared * jnp.asarray(self.shared_scale, shared.dtype)
        out, aux = self._routed(x, router_logits, bias, wi, wg, wo)
        return (out if shared is None else out + shared), aux

    def _routed(self, x, router_logits, bias, wi, wg, wo):
        from dlrover_tpu.runtime.mesh import EXPERT_AXIS, mesh_axis_size

        ep = mesh_axis_size(EXPERT_AXIS)
        if self.dispatch != "grouped" and self.scoring != "softmax":
            raise ValueError(
                f"scoring={self.scoring!r} is routed by dispatch='grouped' "
                f"only, got dispatch={self.dispatch!r}"
            )
        if self.dispatch == "grouped":
            if ep > 1:
                raise ValueError(
                    "dispatch='grouped' runs the per-device Pallas grouped-"
                    f"GEMM kernel and cannot shard over the {ep}-way "
                    f"{EXPERT_AXIS!r} mesh axis: the kernel only sees local "
                    "rows, so cross-device token groups would silently "
                    "multiply against the wrong experts.  Use dispatch="
                    "'einsum', 'a2a', or 'a2a_int8' under expert "
                    "parallelism (see PROFILE.md round 19)."
                )
            return self._grouped_forward(x, router_logits, bias, wi, wg, wo)
        if self.dispatch not in ("einsum", "a2a", "a2a_int8"):
            raise ValueError(
                f"unknown MoE dispatch {self.dispatch!r}; expected one of "
                "'einsum', 'a2a', 'a2a_int8', 'grouped'"
            )
        if self.dispatch in ("a2a", "a2a_int8") and ep > 1:
            return self._a2a_forward(x, router_logits, wi, wg, wo, ep)
        # With a unit expert axis the a2a modes have no wire to ride —
        # they fall through to the (exactly equal) einsum path.
        return self._einsum_forward(x, router_logits, wi, wg, wo)

    # -- capacity einsum dispatch (EP-shardable) ------------------------------

    def _einsum_forward(self, x, router_logits, wi, wg, wo):
        b, s, d = x.shape
        e = self.num_experts
        capacity = max(1, int(self.capacity_factor * s * self.top_k / e))
        dispatch, combine, aux_loss, routed = top_k_gating(
            router_logits, self.top_k, capacity, self.norm_topk_prob,
            self.aux_form,
        )
        self._sow_router_stats(
            _router_entropy(router_logits),
            routed=routed,
            total=b * s * self.top_k,
            rows_run=b * e * capacity,
        )
        dispatch = dispatch.astype(self.dtype)
        combine = combine.astype(self.dtype)

        # Token shuffle: expert dim sharded over the `expert` mesh axis —
        # this einsum IS the all-to-all under EP.
        expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, x.astype(self.dtype))
        expert_in = nn.with_logical_constraint(
            expert_in, (lr.EXPERT, lr.BATCH, None, lr.ACT_EMBED)
        )
        h = jnp.einsum("ebcd,edf->ebcf", expert_in, wi)
        if wg is not None:
            g = jnp.einsum("ebcd,edf->ebcf", expert_in, wg)
            h = nn.silu(g) * h
        else:
            h = ungated(h, self.activation)
        expert_out = jnp.einsum("ebcf,efd->ebcd", h, wo)
        expert_out = nn.with_logical_constraint(
            expert_out, (lr.EXPERT, lr.BATCH, None, lr.ACT_EMBED)
        )

        # Un-shuffle (second a2a) + weighted combine.
        out = jnp.einsum("bsec,ebcd->bsd", combine, expert_out)
        return out, aux_loss.astype(jnp.float32)

    # -- explicit all-to-all dispatch (shard_map) -----------------------------

    def _a2a_forward(self, x, router_logits, wi, wg, wo, ep):
        """Capacity dispatch with an EXPLICIT all-to-all wire (ref
        ``moe_layer.py`` ``_AllToAll``): each device routes a batch
        sub-chunk to ALL experts locally, then the dispatch a2a transposes
        expert-sharded ← batch-sharded (chunk for expert group ``r`` goes
        to expert-axis peer ``r``), the expert matmuls run on the local
        expert slice, and the inverse a2a routes results home for the
        combine.  Numerically this is :meth:`_einsum_forward` exactly —
        the slot assignment is independent per batch row, and the aux
        loss pmean-composes over equal chunks — up to int8 rounding when
        ``dispatch == "a2a_int8"`` puts the two legs on the quantized
        wire (~(1 + 4/block) bytes/element vs 4 fp32; both directions,
        forward and backward, see ``quantized_all_to_all``)."""
        from jax.sharding import PartitionSpec as P

        from dlrover_tpu.parallel.quantized_collectives import (
            quantized_all_to_all,
        )
        from dlrover_tpu.runtime.mesh import (
            EXPERT_AXIS, current_mesh, mesh_axis_size, shard_map_compat,
        )

        b, s, d = x.shape
        e, k = self.num_experts, self.top_k
        capacity = max(1, int(self.capacity_factor * s * k / e))
        int8 = self.dispatch == "a2a_int8"
        if self.aux_form != "top1":
            raise ValueError(
                "a2a dispatch computes the 'top1' load-balancing loss only "
                f"(got aux_form={self.aux_form!r}); use dispatch='einsum'"
            )
        for axis in ("seq", "tensor"):
            if mesh_axis_size(axis) > 1:
                raise ValueError(
                    f"a2a dispatch does not compose with a {axis!r} mesh "
                    "axis > 1 yet; use dispatch='einsum' (GSPMD) there"
                )
        dp = mesh_axis_size("data") * mesh_axis_size("fsdp")
        if b % (dp * ep):
            raise ValueError(
                f"a2a dispatch splits the batch over data x expert: got "
                f"batch {b} not divisible by {dp} (data*fsdp) x {ep} "
                f"(expert)"
            )
        if e % ep:
            raise ValueError(
                f"num_experts {e} must divide by the {ep}-way expert axis"
            )
        batch_axes = ("data", "fsdp", EXPERT_AXIS)

        def wire(v, split_axis, concat_axis):
            if int8:
                return quantized_all_to_all(
                    v, EXPERT_AXIS,
                    split_axis=split_axis, concat_axis=concat_axis,
                )
            return jax.lax.all_to_all(
                v, EXPERT_AXIS, split_axis, concat_axis, tiled=True
            )

        def body(x_loc, logits_loc, *weights):
            wi_loc = weights[0]
            wg_loc = weights[1] if len(weights) == 3 else None
            wo_loc = weights[-1]
            # Slot assignment is per (batch row, expert) — identical on a
            # batch chunk to what the full batch computes.
            dispatch, combine, _, routed = top_k_gating(
                logits_loc, k, capacity, self.norm_topk_prob
            )
            probs = jax.nn.softmax(logits_loc.astype(jnp.float32), axis=-1)
            # Exact global aux loss: pmean the densities BEFORE the
            # product (chunk means over equal chunks compose exactly).
            top1 = jax.nn.one_hot(
                jnp.argmax(probs, axis=-1), e, dtype=jnp.float32
            )
            density = jax.lax.pmean(
                jnp.mean(top1, axis=(0, 1)), batch_axes
            )
            proxy = jax.lax.pmean(
                jnp.mean(probs, axis=(0, 1)), batch_axes
            )
            aux = jnp.sum(density * proxy) * (e ** 2) / k
            entropy = jax.lax.pmean(
                jnp.mean(-jnp.sum(probs * jnp.log(probs + 1e-9), -1)),
                batch_axes,
            )
            routed = jax.lax.psum(routed, batch_axes)
            dispatch = dispatch.astype(self.dtype)
            combine = combine.astype(self.dtype)
            # Local dispatch to ALL experts: [E, b_chunk, C, D].
            expert_in = jnp.einsum(
                "bsec,bsd->ebcd", dispatch, x_loc.astype(self.dtype)
            )
            # Dispatch leg: expert-split, batch-concat — each peer keeps
            # its expert group's tokens from every batch chunk.
            expert_in = wire(expert_in, 0, 1)      # [E/ep, b_chunk*ep, C, D]
            h = jnp.einsum("ebcd,edf->ebcf", expert_in, wi_loc)
            if wg_loc is not None:
                g = jnp.einsum("ebcd,edf->ebcf", expert_in, wg_loc)
                h = nn.silu(g) * h
            else:
                h = ungated(h, self.activation)
            expert_out = jnp.einsum("ebcf,efd->ebcd", h, wo_loc)
            # Combine leg home: the exact inverse exchange.
            expert_out = wire(expert_out, 1, 0)    # [E, b_chunk, C, D]
            out = jnp.einsum("bsec,ebcd->bsd", combine, expert_out)
            return out, aux, entropy, routed

        bspec = P(batch_axes, None, None)
        espec = P(EXPERT_AXIS, None, None)
        args = [x, router_logits, wi] + ([wg] if wg is not None else [])
        args.append(wo)
        in_specs = tuple([bspec, bspec] + [espec] * (len(args) - 2))
        out, aux, entropy, routed = shard_map_compat(
            body, mesh=current_mesh(), in_specs=in_specs,
            out_specs=(bspec, P(), P(), P()),
        )(*args)
        self._sow_router_stats(entropy, routed, b * s * k, b * e * capacity)
        return out, aux.astype(jnp.float32)

    def _sow_router_stats(self, entropy, routed, total, rows_run, kept=None):
        """Book ``[entropy, drop_fraction, load_0..load_{E-1}, pad_share,
        max_expert_load]`` into the ``"intermediates"`` collection (no-op
        unless mutable; the train step is the caller that makes it so).
        ``routed`` are the (token, expert) pairs each expert computes
        (under a share: that the router chose for each of ALL the experts),
        ``total`` the pairs the router chose (under a share: for the
        experts held here), ``rows_run`` the rows the expert matmuls run
        (capacity slots, the grouped GEMMs' row budget, or under a share
        their live row blocks): what is
        not a routed pair is padding.  ``kept`` are the pairs computed
        here where that is not ``routed``'s sum."""
        routed = routed.astype(jnp.float32)
        chosen = routed.sum()
        if kept is None:
            kept = chosen
        if isinstance(total, int):
            def share_missing(whole):
                return 1.0 - kept / max(1, whole)
        else:
            # counted on the device: the difference first, so that nothing
            # missing reads exactly 0 (a TPU's x / x need not be 1)
            def share_missing(whole):
                return (whole - kept) / jnp.maximum(whole, 1.0)

        drop = share_missing(total)
        load = routed / jnp.clip(chosen, 1.0)
        pad_share = share_missing(rows_run)
        max_load = load.max() * routed.shape[0]
        self.sow(
            "intermediates", STATS_NAME,
            jnp.concatenate([
                jnp.stack([entropy, drop]), load,
                jnp.stack([pad_share, max_load]),
            ]),
        )

    # -- dropless grouped-GEMM dispatch ---------------------------------------

    def _grouped_forward(self, x, router_logits, bias, wi, wg, wo):
        from jax.sharding import PartitionSpec as P

        from dlrover_tpu.ops.grouped_matmul import grouped_matmul
        from dlrover_tpu.runtime.mesh import mesh_axis_size, shard_local

        b, s, d = x.shape
        e, k = self.held, self.top_k
        total, first = self.num_experts, self.first_expert
        share = e < total
        block = self.gmm_block_rows

        # Routing and its statistics are global and plain XLA; only the
        # sort + grouped GEMMs below run per device.
        with jax.named_scope("router"):
            gate_vals, gate_idx, aux_loss = _gate(
                router_logits, k, self.norm_topk_prob, self.aux_form,
                self.scoring, bias, self.routed_scale, self.router_groups,
                self.router_topk_groups, self.router_norm_eps,
            )
        # Tokens stay split over their batch and sequence axes (an MLP is
        # token-wise); the embed dim and the expert weights are whole on
        # every device, so peers on a tensor axis repeat each other's work.
        tokens = nn.logical_to_mesh_axes((lr.BATCH, lr.ACT_SEQ, None))
        token_axes = tuple(
            axis for axes in tokens
            for axis in ((axes,) if isinstance(axes, str) else (axes or ()))
        )

        def budget(pairs):
            if share:
                return _share_row_budget(
                    pairs, block, e, total, self.row_budget_multiple
                )
            return _row_budget(pairs, block, e)

        def routed():
            """The pairs the router chose for each of ALL the experts."""
            return jax.nn.one_hot(
                gate_idx.reshape(-1), total, dtype=jnp.int32
            ).sum(axis=0)

        if not share:
            shards = 1
            for axis in token_axes:
                shards *= mesh_axis_size(axis)
            # Dropless: routed == total, so drop_fraction books as exactly 0.
            self._sow_router_stats(
                _router_entropy(router_logits, self.scoring),
                routed=routed(),
                total=b * s * k,
                rows_run=shards * budget(b * s * k // shards),
            )

        def local(x, gate_vals, gate_idx, *weights):
            """Group this device's (token, expert) pairs by expert and run
            each expert's ragged row group through the grouped GEMMs."""
            wi, wo = weights[0], weights[-1]
            wg = weights[1] if len(weights) == 3 else None
            b, s, d = x.shape
            t = b * s
            n_pad = budget(t * k)
            with jax.named_scope("sort"):
                plan = _dispatch_plan(
                    gate_idx.reshape(t, k), e, block, n_pad, first, total,
                )
            # The d_model-wide rows live row-tiled between the gathers and
            # the GEMMs wherever the fetch-and-sum kernel can read them;
            # rows it reads only padded stay plain, and ``_k_rows_summed``
            # pads them at the kernel's door.
            tiled, fetched = _row_path(d, k, self.dtype)
            if share and fetched:
                # most of a token's pairs have no row here: the kernel is
                # handed the ones that have, once for its two calls (its
                # grid step follows from the width it fetches)
                with jax.named_scope("sort"):
                    plan["live"] = row_gather_sum.live_pairs(
                        plan["dest"], n_pad - 1, fetched, self.dtype
                    )
            with jax.named_scope("scatter"):
                rows = _rows_of_tokens(
                    x.reshape(t, d).astype(self.dtype), plan, tiled
                )
            # A share's budget is a multiple of the expected rows: the
            # GEMMs skip its dead blocks.  With every expert held the slack
            # is a block an expert at most, and skipping costs more than it
            # saves (ops/grouped_matmul.py).
            with jax.named_scope("gmm_wi"):
                h = grouped_matmul(
                    rows, wi, plan["padded"], block, False, share
                )
            if wg is not None:
                with jax.named_scope("gmm_wg"):
                    g = grouped_matmul(
                        rows, wg, plan["padded"], block, False, share
                    )
                h = nn.silu(g) * h
            else:
                h = ungated(h, self.activation)
            with jax.named_scope("gmm_wo"):
                out_rows = grouped_matmul(
                    h, wo, plan["padded"], block, tiled, share
                )
            with jax.named_scope("combine"):
                out = _tokens_of_rows(
                    out_rows, gate_vals.reshape(t, k), plan
                )
            if not share:
                return out.reshape(b, s, d)
            # this device's [pairs with a row, pairs routed to an expert
            # held here, rows its GEMMs run: the live blocks]
            counted = jnp.stack(
                [plan["kept"], plan["here"], plan["padded"].sum()]
            ).astype(jnp.float32)
            return out.reshape(b, s, d), counted[None]

        weights = [wi] + ([wg] if wg is not None else []) + [wo]
        out = shard_local(
            local,
            in_specs=(tokens,) * 3 + (P(),) * len(weights),
            out_specs=(tokens, P(token_axes or None, None)) if share
            else tokens,
        )(x, gate_vals, gate_idx, *weights)
        here = b * s * k
        if share:
            # A pair past the row budget is dropped, and counted; the
            # rows run are the live blocks, the budget's slack is skipped.
            out, counted = out
            kept, here, rows_run = jax.lax.stop_gradient(counted.sum(axis=0))
            self._sow_router_stats(
                _router_entropy(router_logits, self.scoring),
                routed=routed(), total=here, rows_run=rows_run, kept=kept,
            )
        if share or bias is not None:
            share_stats = [
                here / (b * s * k),
                jnp.zeros((), jnp.float32) if bias is None
                else jnp.abs(jax.lax.stop_gradient(bias)).max(),
            ]
            if self.router_groups > 1:
                held_here = (gate_idx >= first) & (gate_idx < first + e)
                share_stats.append(
                    held_here.any(axis=-1).mean(dtype=jnp.float32)
                )
            self.sow(
                "intermediates", SHARE_STATS_NAME, jnp.stack(share_stats)
            )
        return out, aux_loss.astype(jnp.float32)


def check_share(num_experts: int, held: int, first: int, dispatch: str):
    """A share of the experts is whole: ``held`` divides ``num_experts``,
    ``first`` is a multiple of it, and only the grouped dispatch can be
    told one."""
    if not held or held == num_experts:
        if first:
            raise ValueError(
                f"first_expert {first} without a share of the experts "
                "(experts_held)"
            )
        return
    if held < 0 or num_experts % held or first % held or not (
        0 <= first < num_experts
    ):
        raise ValueError(
            f"experts_held {held} must divide num_experts {num_experts} "
            f"and first_expert {first} be a multiple of it below "
            f"{num_experts}: a chip holds one of num_experts / held equal "
            "shares"
        )
    if dispatch != "grouped":
        raise ValueError(
            f"a share of the experts ({held} of {num_experts}) is computed "
            f"by dispatch='grouped' only, got dispatch={dispatch!r}: the "
            "capacity dispatches build their [B, S, E, C] tensors over "
            "every expert"
        )


def from_config(cfg, **kwargs) -> MoEMlp:
    """The config's expert layer: the one place that reads the config's
    fields into the layer's, for the blocks that run it and for
    :func:`kernel_facts`."""
    return MoEMlp(
        num_experts=cfg.num_experts,
        d_ff=cfg.resolved_moe_d_ff,
        top_k=cfg.top_k,
        capacity_factor=cfg.capacity_factor,
        activation=cfg.activation,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        dispatch=cfg.moe_dispatch,
        norm_topk_prob=cfg.norm_topk_prob,
        aux_form=cfg.moe_aux_form,
        scoring=cfg.router_scoring,
        router_bias=cfg.router_bias,
        routed_scale=cfg.routed_scaling_factor,
        experts_held=cfg.experts_held,
        first_expert=cfg.first_expert,
        shared_d_ff=cfg.resolved_shared_d_ff,
        shared_scale=cfg.shared_expert_scale,
        row_budget_multiple=cfg.moe_row_budget,
        router_groups=cfg.router_groups,
        router_topk_groups=cfg.router_topk_groups,
        router_norm_eps=cfg.router_norm_eps,
        **kwargs,
    )


def _grouped_rows(cfg):
    """``(the layer, tiled, fetched)`` of a model with grouped experts:
    :func:`_row_path` of a ``d_model`` wide input, which the layer asks;
    ``None`` for any other model."""
    if not cfg.num_experts or cfg.moe_dispatch != "grouped":
        return None
    layer = from_config(cfg)
    return (layer,) + _row_path(cfg.d_model, layer.top_k, layer.dtype)


def row_moves(cfg) -> str:
    """Which path a token's ``top_k`` rows take through the dropless
    dispatch's combine and the scatter's transpose: ``kernel``
    (``ops/row_gather_sum.py``: fetched and summed in one pass) /
    ``kernel_live`` (the same under a share of the experts: only the pairs
    that have a row here are fetched and added) / ``kernel_padded`` and
    ``kernel_live_padded`` (the same two for rows of whole lanes that are
    whole tiles only padded: plain between the gathers and the GEMMs,
    padded at the kernel's door) / ``xla`` (a gather, then a reduction),
    ``none`` for a model without grouped experts."""
    grouped = _grouped_rows(cfg)
    if grouped is None:
        return "none"
    layer, tiled, fetched = grouped
    if not fetched:
        return "xla"
    live = "_live" if layer.held < layer.num_experts else ""
    return "kernel" + live + ("" if tiled else "_padded")


def kernel_facts(cfg, seq_len: int) -> Dict[str, str]:
    """``row_moves`` (:func:`row_moves`).  ``gmm_strips``: whether the
    grouped experts' forward and ``dx`` GEMMs hold an expert's whole-K
    strip of weights in VMEM across its row blocks, ``resident``, or
    ``split_k:<n>/<of>`` where ``n`` of a layer's distinct forward/dx calls
    (six for gated experts, four for ungated) split K and stream the
    weights once a row block (``ops/grouped_matmul.py`` ``plan_tiles``,
    which the kernel asks).  ``gmm_dw_tiles``: how many tiles the
    weight-gradient GEMMs cut an expert's matrix into, ``into:<K tiles>x<M
    tiles> out_of:<K>x<M>`` (``wi`` / ``wg`` and ``wo``; every M tile reads
    the rows again, every K tile their cotangents: ``plan_dw_tiles``, which
    the kernel asks).  Each ``none`` for a model without grouped experts."""
    del seq_len  # a token's rows and an expert's matrix: no sequence in it
    grouped = _grouped_rows(cfg)
    if grouped is None:
        return dict.fromkeys(
            ("row_moves", "gmm_strips", "gmm_dw_tiles"), "none"
        )
    from dlrover_tpu.ops import grouped_matmul

    layer, tiled, _ = grouped
    widths = (cfg.d_model, layer.d_ff)
    return {
        "row_moves": row_moves(cfg),
        "gmm_strips": grouped_matmul.expert_strips(
            *widths, layer.activation == "swiglu", tiled, layer.dtype
        ),
        "gmm_dw_tiles": grouped_matmul.expert_dw_tiles(
            *widths, tiled, layer.dtype
        ),
    }


def _read(cfg, vec, share=None) -> Dict[str, Any]:
    """The ``moe`` event of the step's folded vectors: router health of
    the parameters the step routed with (layout: :func:`split_stats`).  A
    layer told its share of the experts (or a router bias) hands
    ``[pairs_here, bias_absmax]`` out beside the vector; any other computes
    every pair it routes and has no bias."""
    vec = np.asarray(vec, np.float64)
    entropy, drop, load, pad_share, max_load = split_stats(vec)
    share = (1.0, 0.0) if share is None else np.asarray(share, np.float64)
    pairs_here, bias_absmax = share[:2]
    # a group-limited router's layers also count the tokens with a pair
    # here (``SHARE_STATS_NAME``)
    grouped = {} if len(share) < 3 else {"tokens_here": float(share[2])}
    # Of a token's top_k row fetches, the share that is issued: all of
    # them, but where the live-only kernel runs those of the pairs the
    # plan kept (routed here, less the dropped ones).  From the two means
    # the step already returns: the layers' mean of kept / pairs to the
    # digit while no layer drops a pair, the product of two means (not the
    # mean of the layers' products) once one does.
    row_fetch_share = 1.0
    if row_moves(cfg).startswith("kernel_live"):
        row_fetch_share = float(pairs_here) * (1.0 - float(drop))
    layer = from_config(cfg)
    return dict(
        entropy=float(entropy),
        drop_fraction=float(drop),
        experts=int(load.size),
        top_k=int(layer.top_k),
        load=json.dumps([round(float(v), 6) for v in load]),
        pad_share=float(pad_share),
        max_expert_load=float(max_load),
        experts_total=int(load.size),
        held=int(layer.held),
        pairs_here=float(pairs_here),
        bias_absmax=float(bias_absmax),
        row_fetch_share=row_fetch_share,
        groups=int(layer.router_groups),
        topk_group=int(layer.router_topk_groups),
        # the shared experts: held here, published, and what their summed
        # output is multiplied by (1 / published where they are averaged)
        shared_held=int(cfg.resolved_shared_held),
        shared_published=int(cfg.num_shared_experts),
        shared_scale=float(layer.shared_scale),
        **grouped,
    )


FAMILY = Family(
    event="moe",
    stats={
        STATS_NAME: lambda stacked: stacked.mean(axis=0),
        SHARE_STATS_NAME: fold_share_stats,
    },
    has=lambda cfg: cfg.num_experts,
    read=_read,
    kernel_facts=kernel_facts,
)
