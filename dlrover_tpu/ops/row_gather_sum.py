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
# DMAs started per turn of the issue loop (its body is unrolled this far).
_ISSUE_UNROLL = 16


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


def kernel_fits(d: int, k: int, dtype) -> bool:
    """Whether a row of ``d`` elements is whole native tiles in the view the
    kernel DMAs from, and a tile of tokens' ``k`` rows each fit the slots
    the VMEM plan leaves room for."""
    return d % (LANES * tile_rows(dtype)) == 0 and _chunk_tokens(d, k, dtype) > 0


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


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_sum(rows, index, weights=None, *, interpret=None):
    """``[T, D]`` of ``rows.dtype``; ``rows`` is ``[R, D]`` or its tiled view
    ``[R, D // 128, 128]``, and ``kernel_fits`` must hold for it.  (Jitted
    so that a step which uses it forward, recomputed and transposed traces
    the kernel body once per signature and not eight times.)"""
    r = rows.shape[0]
    d = rows.size // r
    tiles = d // LANES
    t, k = index.shape
    if not kernel_fits(d, k, rows.dtype):
        raise ValueError(
            f"rows of {d} x {rows.dtype}, {k} a token, do not fit the "
            "fetch-and-sum kernel; see kernel_fits"
        )
    chunk = _chunk_tokens(d, k, rows.dtype)
    block = min(_BLOCK_TOKENS // chunk, -(-t // chunk)) * chunk
    t_pad = -(-t // block) * block

    def flat(a, dtype):
        # tokens past T fetch row 0 with weight 0 and are cut off below
        return jnp.pad(a.astype(dtype), ((0, t_pad - t), (0, 0))).reshape(-1)

    per_block = pl.BlockSpec(
        (block * k,), lambda i: (i,), memory_space=pltpu.SMEM
    )
    operands, in_specs = [flat(index, jnp.int32)], [per_block]
    if weights is not None:
        operands.append(flat(weights, jnp.float32))
        in_specs.append(per_block)
    operands.append(row_tiled(rows))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    out = pl.pallas_call(
        functools.partial(
            _kernel, k=k, chunk=chunk, weighted=weights is not None
        ),
        grid=(t_pad // block,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (block, d), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((t_pad, d), rows.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, chunk * k, tiles, LANES), rows.dtype),
            pltpu.VMEM((chunk, tiles, LANES), rows.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=backend.interpret() if interpret is None else interpret,
        name="row_gather_sum",
    )(*operands)
    return out[:t]
