"""Fetch k rows per token and sum them in one pass (dropless MoE's combine).

``out[t] = sum_j weights[t, j] * rows[index[t, j]]``, the weights and the
sum in float32, for ``rows`` ``[R, D]`` and ``index`` ``[T, k]``.  XLA's
gather lands the picked rows as ``[T, k, D]`` first and reduces them in a
second pass; this kernel never holds such an array.  For a chunk of tokens
it starts one DMA per picked row from HBM into a VMEM slot, and while the
next chunk's rows are in flight it multiplies and sums the chunk that has
arrived.  A chunk is whole native tiles of tokens, as many as fill a slot
of about 1 MiB and never fewer than one tile (:func:`_chunk_tokens`: the
slot's bytes follow from ``k``, ``D`` and the dtype); :func:`kernel_fits`
says which ``(D, k, dtype)`` the kernel holds.

A row of a 2-D bf16 array is sixteen strided 256-byte pieces under the
(16, 128) tiling, and Mosaic refuses to slice one row out of it; the kernel
reads the row-contiguous view ``[R, D // 128, 128]``, in which a row is
whole tiles.  ``rows`` may come in that form already, or as ``[R, D]``, in
which case the reshape is a copy XLA makes first.

A row of whole lanes that is no whole native tiles (21 lane tiles of
bfloat16: 2,688 columns) has no such view: the array ``[R, 21, 128]`` lies
in HBM with its 21 padded to 24 and Mosaic refuses one row of it.
:func:`padded_width` says how wide the view of such a row is (the next
multiple of 8 lane tiles), and :func:`padded_gather_sum` is the kernel's
door for plain ``[R, D]`` rows of such a width: it pads a row with zero
columns, runs the same kernels on the view and cuts the sums back to D.
The zero columns add zeros to columns that are cut off; the float32 terms
of the D columns and their order are the kernel's.

Under a share of the experts most of a token's k indices name one row of
zeros (seven of eight where an eighth of the experts live here), and the
kernel above would start a DMA for every one: it is bound by the rate at
which DMAs are issued, so those cost what live rows cost.  Handed
:func:`live_pairs` of the index (``gather_sum``'s ``live``) the call runs
the live-only kernel instead: a grid step reads the list of its pairs that
name another row and their count, starts one DMA a listed pair, waits for
as many rows as it started, and adds each row to its token's float32 sum,
in the list's order, which is the order of the token's choices; a token
with no listed pair leaves as zeros.  The same float32 terms in the same
order, less additions of ``+ 0.0``.  The list is XLA's to make (a sort a
grid step: the scalar core, walking every index itself, spent 0.45
ms of a 0.98 ms call on it; handed the list the call takes 0.53 where the
full fetch takes 2.6, at 16,384 x 8 with an eighth live on a v5e); a call
without it is the kernel above, to the letter.  Both kernels stay because
each loses on the other's ground: a listed pair costs 27.7 ns (its word, its
token's float32 row read and written) where a pair of the full fetch costs
17-18, so with every pair live the live-only call takes 3.7 ms against 2.4,
and the two cross at 0.6 of the pairs live; a share holds a half at most.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops import backend

LANES = 128
# Tokens one grid step writes, and the bytes of picked rows one VMEM slot
# aims to hold (two slots: one is summed while the other fills).  The
# kernel is bound by the rate at which DMAs can be issued (19 ns a row on a
# v5e), not by their latency: slots of 0.25 to 4 MiB run alike.  A slot
# holds whole tiles of tokens, so where ONE tile of tokens' k rows is more
# than the aim (10 rows of 4096 bfloat16 a token: 1.25 MiB) the slot is
# that tile, as long as the two slots, the summed chunk and the grid step's
# double-buffered output block stay within the VMEM plan.
_BLOCK_TOKENS = 512
_SLOT_BYTES = 1 << 20
_VMEM_BUDGET = 12 << 20
# DMAs started per turn of the issue loop (its body is unrolled this far);
# the live-only kernel also waits and adds this many a turn.
_ISSUE_UNROLL = 16
# Lane tiles the view of a padded row is a multiple of: the second-minor
# extent of the HBM tiling of a ``[R, tiles, 128]`` array, which is 8 for
# 16-bit elements too (a bfloat16 array lies there as (8, 128)(2, 1)).
_PAD_LANE_TILES = 8
# A row is padded to at most this many times its width.  The pad, every
# DMA and the cut move the padded width, so past twice the row they move
# more zeros than row, and the padded call's gain over XLA's gather (which
# lands a token's k rows in float32 and reads them again) has not been
# measured there: 2,688 and 2,560 pad to 3,072 (1.14 and 1.2), and the
# widths of toy models (128, 256, 384) keep the path they had.
_PAD_MOST = 2


def tile_rows(dtype) -> int:
    """Second-minor extent of ``dtype``'s native tile: 8 rows of 32 bits."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def row_tiled(rows):
    """``[R, D]`` (or already row-tiled) as ``[R, D // 128, 128]``."""
    return rows.reshape(rows.shape[0], -1, LANES)


def _chunk_tokens(d: int, k: int, dtype) -> int:
    """Tokens whose rows fill a slot: whole tiles of output rows, at least
    one; 0 where even one tile of tokens overflows the VMEM plan."""
    size, tile = jnp.dtype(dtype).itemsize, tile_rows(dtype)
    tokens = _SLOT_BYTES // (k * d * size)
    chunk = min(_BLOCK_TOKENS, max(tile, tokens // tile * tile))
    planned = (2 * chunk * k + chunk + 2 * _BLOCK_TOKENS) * d * size
    return chunk if planned <= _VMEM_BUDGET else 0


def _live_steps(t: int, k: int, d: int, dtype):
    """``(block, span, t_pad)`` of the live-only kernel over ``t`` tokens:
    the tokens one grid step writes (``_BLOCK_TOKENS``, halved while the
    float32 sums of a block overflow the VMEM plan), the tokens whose
    indices one SMEM block holds (the step's own or, where those are no
    whole tiles of 1024 words, as many steps' as are), and ``t`` padded to
    whole SMEM blocks."""
    size, chunk = jnp.dtype(dtype).itemsize, _chunk_tokens(d, k, dtype)
    block = _BLOCK_TOKENS
    while block > chunk and (
        2 * chunk * k * size + block * (4 + 2 * size)
    ) * d > _VMEM_BUDGET:
        block //= 2
    block = min(block // chunk, -(-t // chunk)) * chunk
    span = block
    while span * k % 1024 and span < t:
        span *= 2
    return block, span, -(-t // span) * span


def _word_bits(k: int) -> int:
    """A listed pair is (token of the grid step, choice) in one word: the
    choice in this many low bits."""
    return (k - 1).bit_length()


def live_pairs(index, zero_row: int, d: int, dtype):
    """``gather_sum``'s ``live`` for ``index`` ``[T, k]`` into rows of ``d``
    x ``dtype`` whose row ``zero_row`` is zeros: for each grid step's
    tokens the pairs that name another row, moved to the front in their
    order, and how many they are.  XLA's part of the live-only kernel, made
    once for every call that shares the index (a sort a grid step: 0.09 ms
    for 16,384 x 8 on a v5e, 0.17 for 16,384 x 10)."""
    t, k = index.shape
    block, _, t_pad = _live_steps(t, k, d, dtype)
    live = jnp.pad(index != zero_row, ((0, t_pad - t), (0, 0)))
    live = live.reshape(t_pad // block, block * k)
    pair = jnp.arange(block * k, dtype=jnp.int32)
    word = (pair // k) << _word_bits(k) | pair % k
    # a word rises with its pair, so one sort moves the live ones to the
    # front in their order; the others, past the count, are never read
    words = jnp.sort(jnp.where(live, word, word | 1 << 30), axis=1)
    return words.reshape(-1), live.sum(axis=1, dtype=jnp.int32)


def _whole_tiles(d: int, dtype) -> bool:
    return d % (LANES * tile_rows(dtype)) == 0


def kernel_fits(d: int, k: int, dtype) -> bool:
    """Whether a row of ``d`` elements is whole native tiles in the view the
    kernel DMAs from, and a tile of tokens' ``k`` rows each fit the slots
    the VMEM plan leaves room for."""
    return _whole_tiles(d, dtype) and _chunk_tokens(d, k, dtype) > 0


def padded_width(d: int, k: int, dtype) -> int:
    """For a row of ``d`` elements that is whole lanes and no whole native
    tiles (:func:`kernel_fits` is false for it), the width of the view
    :func:`padded_gather_sum` hands the kernel: the next multiple of
    ``_PAD_LANE_TILES`` lane tiles.  0 where the row is whole native tiles
    (it needs no pad), is no whole number of lanes, would grow past
    ``_PAD_MOST`` times its width, or a tile of tokens' ``k`` padded rows
    overflows the VMEM plan."""
    if d % LANES or _whole_tiles(d, dtype):
        return 0
    quantum = LANES * _PAD_LANE_TILES
    width = -(-d // quantum) * quantum
    fits = width <= _PAD_MOST * d and _chunk_tokens(width, k, dtype) > 0
    return width if fits else 0


def _kernel(*refs, k, chunk, weighted):
    if weighted:
        index_ref, weights_ref, rows_ref, out_ref, buf, summed, sems = refs
    else:
        index_ref, rows_ref, out_ref, buf, summed, sems = refs
    n_chunks = out_ref.shape[0] // chunk

    picked = chunk * k
    unroll = max(u for u in range(1, _ISSUE_UNROLL + 1) if picked % u == 0)

    def fetch(c, slot):
        def start(i, _):
            for u in range(unroll):
                pltpu.make_async_copy(
                    rows_ref.at[index_ref[c * picked + i * unroll + u]],
                    buf.at[slot, i * unroll + u], sems.at[slot],
                ).start()
            return 0

        jax.lax.fori_loop(0, picked // unroll, start, 0)

    fetch(0, 0)

    def step(c, _):
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            fetch(c + 1, 1 - slot)

        # One wait for the slot's bytes, whichever rows they came from.
        pltpu.make_async_copy(
            rows_ref.at[pl.ds(0, picked)], buf.at[slot], sems.at[slot]
        ).wait()

        def token(t, _):
            acc = None
            for j in range(k):
                row = buf[slot, t * k + j].astype(jnp.float32)
                if weighted:
                    row = row * weights_ref[(c * chunk + t) * k + j]
                acc = row if acc is None else acc + row
            summed[t] = acc.astype(summed.dtype)
            return 0

        jax.lax.fori_loop(0, chunk, token, 0)
        # the chunk's rows leave row-tiled form here, in VMEM
        out_ref[pl.ds(pl.multiple_of(c * chunk, chunk), chunk), :] = (
            summed[...].reshape(chunk, -1)
        )
        return 0

    jax.lax.fori_loop(0, n_chunks, step, 0)


def _live_kernel(*refs, k, chunk, weighted):
    """The grid step's listed pairs and no other: fetched a slot at a time,
    each added to its token's float32 sum, in the order of the list."""
    if weighted:
        (words_ref, count_ref, index_ref, weights_ref, rows_ref, out_ref,
         buf, acc, sems) = refs
    else:
        words_ref, count_ref, index_ref, rows_ref, out_ref, buf, acc, sems = refs
    block, slot_rows = out_ref.shape[0], buf.shape[1]
    # the SMEM block may span several grid steps' tokens: this step's part
    step_id = pl.program_id(0)
    first = step_id % (index_ref.shape[0] // (block * k)) * block * k
    bits = _word_bits(k)
    n = count_ref[step_id]
    n_slots = (n + slot_rows - 1) // slot_rows
    acc[...] = jnp.zeros_like(acc)

    def each(count, one, turn=None):
        """``one(i)`` for i in [0, count): ``_ISSUE_UNROLL`` a turn of the
        loop (``turn(g)`` where a whole turn has a form of its own), then
        the rest singly."""
        def unrolled(g):
            for u in range(_ISSUE_UNROLL):
                one(g * _ISSUE_UNROLL + u)

        def loop(lo, hi, body):
            def turn_of(i, carry):
                body(i)
                return carry

            jax.lax.fori_loop(lo, hi, turn_of, 0)

        whole = count // _ISSUE_UNROLL
        loop(0, whole, turn or unrolled)
        loop(whole * _ISSUE_UNROLL, count, one)

    def pair_of(i):
        word = words_ref[first + i]
        t = word >> bits
        return t, first + t * k + (word & ((1 << bits) - 1))

    def fetch(c, slot):
        def start(i):
            _, pair = pair_of(c * slot_rows + i)
            pltpu.make_async_copy(
                rows_ref.at[index_ref[pair]], buf.at[slot, i], sems.at[slot]
            ).start()

        each(jnp.minimum(slot_rows, n - c * slot_rows), start)

    @pl.when(n > 0)
    def _():
        fetch(0, 0)

    def step(c, _):
        slot = c % 2

        @pl.when(c + 1 < n_slots)
        def _():
            fetch(c + 1, 1 - slot)

        def wait(rows):
            pltpu.make_async_copy(
                rows_ref.at[pl.ds(0, rows)], buf.at[slot, pl.ds(0, rows)],
                sems.at[slot],
            ).wait()

        def add(i):
            t, pair = pair_of(c * slot_rows + i)
            row = buf[slot, i].astype(jnp.float32)
            if weighted:
                row = row * weights_ref[pair]
            acc[t] = acc[t] + row

        count = jnp.minimum(slot_rows, n - c * slot_rows)
        # as many rows waited for as were started
        each(count, lambda i: wait(1), lambda g: wait(_ISSUE_UNROLL))
        each(count, add)
        return 0

    jax.lax.fori_loop(0, n_slots, step, 0)

    def leave(c, _):
        # a token with no listed pair leaves as the zeros its sum started from
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        out_ref[at, :] = acc[at].astype(out_ref.dtype).reshape(chunk, -1)
        return 0

    jax.lax.fori_loop(0, block // chunk, leave, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_sum(rows, index, weights=None, *, live=None, interpret=None):
    """``[T, D]`` of ``rows.dtype``; ``rows`` is ``[R, D]`` or its tiled view
    ``[R, D // 128, 128]``, and ``kernel_fits`` must hold for it.  Given
    ``live`` (:func:`live_pairs` of this ``index``, for rows of this width
    and dtype, whose zero row holds zeros), the pairs that name the zero
    row are neither fetched nor added: ``+ 0.0`` is all the sum loses.  (Jitted so that a step which uses it forward, recomputed and
    transposed traces the kernel body once per signature and not eight
    times.)"""
    d = rows.size // rows.shape[0]
    if not kernel_fits(d, index.shape[1], rows.dtype):
        raise ValueError(
            f"rows of {d} x {rows.dtype}, {index.shape[1]} a token, do not "
            "fit the fetch-and-sum kernel; see kernel_fits"
        )
    return _fetch_and_sum(rows, index, weights, live, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def padded_gather_sum(rows, index, weights=None, *, live=None, interpret=None):
    """:func:`gather_sum` for plain ``[R, D]`` rows with a
    :func:`padded_width`: padded with zero columns to that width, fetched
    and summed in its view, and the ``[T, width]`` sums cut back to
    ``[T, D]``.  ``live`` is :func:`live_pairs` for rows of the PADDED
    width.  (Jitted as ``gather_sum`` is: one trace a signature of the pad,
    the kernel body and the cut.)"""
    d, k = rows.shape[1], index.shape[1]
    width = padded_width(d, k, rows.dtype)
    if not width:
        raise ValueError(
            f"rows of {d} x {rows.dtype}, {k} a token, have no padded form "
            "the fetch-and-sum kernel takes; see padded_width"
        )
    padded = jnp.pad(rows, ((0, 0), (0, width - d)))
    return _fetch_and_sum(padded, index, weights, live, interpret)[:, :d]


def _fetch_and_sum(rows, index, weights, live, interpret):
    """The call itself, for rows whose width the caller has checked."""
    r = rows.shape[0]
    d = rows.size // r
    tiles = d // LANES
    t, k = index.shape
    chunk = _chunk_tokens(d, k, rows.dtype)
    if live is None:
        block = min(_BLOCK_TOKENS // chunk, -(-t // chunk)) * chunk
        span, t_pad = block, -(-t // block) * block
    else:
        block, span, t_pad = _live_steps(t, k, d, rows.dtype)
        if (live[0].shape, live[1].shape) != ((t_pad * k,), (t_pad // block,)):
            raise ValueError(
                f"a live list of {live[0].shape[0]} words in "
                f"{live[1].shape[0]} grid steps is not live_pairs of a "
                f"{t} x {k} index into rows of {d} x {rows.dtype}"
            )

    def flat(a, dtype):
        # tokens past T fetch row 0 with weight 0 (none is ever listed as
        # live) and are cut off below
        return jnp.pad(a.astype(dtype), ((0, t_pad - t), (0, 0))).reshape(-1)

    # (one SMEM block a step is the form the full fetch always had: its
    # traced program stays what it was)
    steps = span // block
    per_block = pl.BlockSpec(
        (span * k,), (lambda i: (i // steps,)) if steps > 1 else lambda i: (i,),
        memory_space=pltpu.SMEM,
    )
    operands, in_specs = [flat(index, jnp.int32)], [per_block]
    if weights is not None:
        operands.append(flat(weights, jnp.float32))
        in_specs.append(per_block)
    operands.append(row_tiled(rows))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    slots = pltpu.VMEM((2, chunk * k, tiles, LANES), rows.dtype)
    if live is None:
        kernel = _kernel
        summed = pltpu.VMEM((chunk, tiles, LANES), rows.dtype)
    else:
        kernel = _live_kernel
        summed = pltpu.VMEM((block, tiles, LANES), jnp.float32)
        operands = list(live) + operands
        in_specs = [per_block, pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs
    out = pl.pallas_call(
        functools.partial(
            kernel, k=k, chunk=chunk, weighted=weights is not None
        ),
        grid=(t_pad // block,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (block, d), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((t_pad, d), rows.dtype),
        scratch_shapes=[slots, summed, pltpu.SemaphoreType.DMA((2,))],
        interpret=backend.interpret() if interpret is None else interpret,
        name="row_gather_sum",
    )(*operands)
    return out[:t]
