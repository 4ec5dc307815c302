"""The choice of a sparse-attention indexer (DeepSeek-V3.2's DSA, GLM-5.2):
its scores and the ``topk`` keys of each query, as a mask.

``scores``: ``I[t, s] = sum_j w[t, j] ReLU(q[t, j] . k[s])`` over the
indexer's heads ``j``; bfloat16 operands where the model's are, the
products accumulated, rectified and summed over the heads in float32.

``choose``: query ``t``'s set is the ``topk`` keys with the largest score
among the keys it may see (``valid``: the causal triangle, one document),
ties to the lower key index, every key it may see where those are ``topk``
or fewer.  A row's ``topk``-th largest score is FOUND BY COUNTING, not by
sorting: a float32's bit pattern maps to an unsigned number that orders as
the float does, and the largest threshold that ``topk`` or more scores
reach is built bit by bit from the top, one count over the row a bit, 32 in
all (a sort of a row of 16,384 is about 1.6e6 compare-exchanges, the 32
counts 5.2e5 compares with no data movement).  What ties at the threshold
is taken in key order by a running count.  Plain ``jax.numpy`` throughout:
each count is one fused compare-and-reduce over the row block.

``choose_blocked`` walks the query rows in blocks so that ``[rows, T]``
float32 scores, not ``[T, T]``, are ever live, each run of rows against the
keys up to its own end (``key_runs``), and hands out the choice as an int8
mask ``[B, T, T]`` (1: chosen) with what the ``index`` event reads.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def scores(q: jax.Array, k: jax.Array, w: jax.Array) -> jax.Array:
    """``q [B, R, J, D]``, ``k [B, T, D]``, ``w [B, R, J]`` (float32) ->
    ``I [B, R, T]`` float32."""
    z = jnp.einsum("brjd,btd->brjt", q, k, preferred_element_type=F32)
    return (jax.nn.relu(z) * w.astype(F32)[..., None]).sum(axis=2)


def _ordered(x: jax.Array) -> jax.Array:
    """float32 -> uint32 that orders as the floats do (-inf lowest)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def choose(score: jax.Array, valid: jax.Array, topk: int) -> jax.Array:
    """``score [..., T]`` float32, ``valid [..., T]`` bool -> bool mask of
    the ``topk`` largest valid scores of each row, ties to the lower
    index; every valid entry where a row has ``topk`` or fewer."""
    # a zero is +0.0 whatever the signs of the weights that made it, so
    # that equal scores have equal images; 0 stands below every float's
    # image, -inf's too
    score = jnp.where(score == 0.0, 0.0, score)
    u = jnp.where(valid, _ordered(score), jnp.uint32(0))

    def grow(i, prefix):
        candidate = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        count = (u >= candidate[..., None]).sum(axis=-1)
        return jnp.where(count >= topk, candidate, prefix)

    tau = jax.lax.fori_loop(
        0, 32, grow, jnp.zeros(u.shape[:-1], jnp.uint32)
    )[..., None]
    above = u > tau
    left = topk - above.sum(axis=-1, keepdims=True)
    tied = u == tau
    first_tied = jnp.cumsum(tied.astype(jnp.int32), axis=-1) <= left
    return valid & (above | (tied & first_tied))


def valid_keys(
    rows: jax.Array, keys: int, segment_ids: Optional[jax.Array]
) -> jax.Array:
    """``[B | 1, R, keys]``: key ``s < keys`` at or before query
    ``rows[r]``, in its document where ``segment_ids [B, T]`` are given."""
    valid = (jnp.arange(keys)[None, :] <= rows[:, None])[None]
    if segment_ids is not None:
        valid = valid & (
            segment_ids[:, rows][:, :, None]
            == segment_ids[:, None, :keys]
        )
    return valid


def row_block(seq_len: int, want: int) -> int:
    """The largest divisor of ``seq_len`` that is at most ``want``."""
    rows = min(want, seq_len)
    while seq_len % rows:
        rows -= 1
    return rows


# Runs of query rows that are walked against the keys up to their own end
# only: a row sees no later key, so four runs do 10 / 16 of the square.
KEY_RUNS = 4


def key_runs(seq_len: int, rows: int):
    """``(first row block, row blocks, keys)`` of each run of query rows:
    the blocked passes walk a run's rows against its first ``keys`` keys
    (static sizes, one loop a run).  One run where the blocks do not split
    evenly."""
    blocks = seq_len // rows
    runs = KEY_RUNS if blocks % KEY_RUNS == 0 else 1
    per = blocks // runs
    return [(g * per, per, (g + 1) * per * rows) for g in range(runs)]


def choose_blocked(
    q: jax.Array, k: jax.Array, w: jax.Array,
    segment_ids: Optional[jax.Array], topk: int, block_rows: int = 256,
) -> Tuple[jax.Array, jax.Array]:
    """The choice of every query of ``q [B, T, J, D]`` over ``k [B, T, D]``
    as an int8 mask ``[B, T, T]``, and ``[chosen pairs, pairs a query may
    see, largest |score| of such a pair]`` (float32) beside it."""
    b, t = q.shape[:2]
    rows = row_block(t, block_rows)

    def block(keys, i):
        at = i * rows + jnp.arange(rows)
        qb = jax.lax.dynamic_slice_in_dim(q, i * rows, rows, axis=1)
        wb = jax.lax.dynamic_slice_in_dim(w, i * rows, rows, axis=1)
        score = scores(qb, k[:, :keys], wb)
        valid = jnp.broadcast_to(
            valid_keys(at, keys, segment_ids), score.shape
        )
        mask = choose(score, valid, topk)
        stats = jnp.stack([
            mask.sum().astype(F32), valid.sum().astype(F32),
            jnp.where(valid, jnp.abs(score), 0.0).max(),
        ])
        return jnp.pad(
            mask.astype(jnp.int8), ((0, 0), (0, 0), (0, t - keys))
        ), stats

    masks, stats = zip(*(
        jax.lax.map(
            functools.partial(block, keys), first + jnp.arange(count)
        )
        for first, count, keys in key_runs(t, rows)
    ))
    masks, stats = jnp.concatenate(masks), jnp.concatenate(stats)
    mask = masks.transpose(1, 0, 2, 3).reshape(b, t, t)
    return mask, jnp.stack(
        [stats[:, 0].sum(), stats[:, 1].sum(), stats[:, 2].max()]
    )
