"""Grouped (ragged) matmul kernel: per-expert GEMM for dropless MoE.

Capability ref: ``atorch/atorch/modules/moe/grouped_gemm_moe.py:46``
(``Grouped_GEMM_MoE`` batching per-expert GEMMs into one kernel).

``x`` rows are sorted by expert; ``group_sizes[e]`` rows belong to expert
``e`` and multiply ``w[e]``.  The row->expert mapping is data-dependent, so
the expert index for each row block is computed on device (searchsorted over
the group offsets) and fed to the kernel through scalar prefetch, where the
*index maps* use it to stream the right expert's weights — the Pallas TPU
pattern for ragged work (PrefetchScalarGridSpec).

Group sizes must be multiples of ``block_rows``; the MoE layer guarantees
this by padding each expert's token group (capacity-style or to the block).
``x`` may have more rows than ``sum(group_sizes)`` (the caller's static
row budget).  As a rule those rows meet the last expert's weights: they
hold zeros, so the work is inert, and where the slack is a few blocks in a
thousand (every expert held: one block an expert at most) that is the
fastest form.  With ``skip_dead`` the row blocks after the last live one
are DEAD instead: they do no matmul, fetch nothing new (their index maps
stay on the last live block) and come out zero.  That costs every block a
guard and a little scalar work (3% of the GEMM time at OLMoE's shapes, on
the chip: PERF.md section 6, PR 33) and pays where the slack is large by
construction: a chip's share of the experts, whose budget is a multiple of
the expected rows.

Rows may come and go *row-tiled*, ``[N, K // 128, 128]`` for ``[N, K]``: in
that view a row is whole native tiles, contiguous in HBM, which is the form
``ops/row_gather_sum.py`` can DMA single rows from.  The kernels take such
blocks and reshape them in VMEM, so no relayout pass over HBM is needed on
either side of a GEMM.

What a forward or ``dx`` call costs in HBM traffic is decided by its tiles
(:func:`plan_tiles`).  The grid is (output strip, row block, contraction
step), so while the whole contraction is one step an expert's ``[K, tm]``
strip of weights is fetched when the expert changes and stays in VMEM
across that expert's row blocks: the weights cross HBM once an expert and
strip, and the dot's result is the output block (no accumulator).  Once K
is split the weight block changes at every grid step, and EVERY row block
of 128 rows streams its expert's whole ``[K, tm]`` strip again: 7 MiB for
4.8 us of MXU work at LFM2's 1792 x 2048, bound by HBM at half the MXU's
rate whatever the body's schedule (on the chip 2.98 ms a call against 1.51
resident, PERF.md section 6, PR 53; it costs less only where XLA happens to
hold the whole expert matrix in VMEM itself, which no call can count on).
K is therefore split only where the whole-K strip fits under no VMEM limit
the kernel may ask for (``_VMEM_CAP``; Mixtral's 14336 x 2048 under
``grouped``).  Command A+'s 4096 x 4096 was such a call under a cap of 32
MiB: rows leave the expert width row-tiled, a row-tiled block's narrowest
width in bf16 is 2,048 columns, the whole-K strip ``[4096, 2048]`` counts
38.0 MiB, and split in four every row block streamed 16 MiB again.  On the
chip, at ``[11392, 4096]`` rows, eight experts, 68 live row blocks
(PERF.md section 6, PR 61): split 3.417 ms a call, resident under the 38.0
MiB it asks for 1.841 (the MXU needs 1.48); the dx call of the same plan,
XLA's transpose of the weights included, 4.168 against 2.759; the calls
INTO the width, whose strips of 512 columns were always whole, 2.020 on
both sides.

The ``dW`` call asks for more before it tiles, too (:func:`plan_dw_tiles`).
Its grid is (K tile, M tile, row block): an expert's ``[tk, tm]`` tile stays
in a float32 accumulator across its row blocks, x crosses HBM once an M
tile, dy once a K tile, and every (tile, row block) pair is a grid step of
0.3-0.45 us before any work (on the chip: LFM2's 2048 x 1792 in seven, two
and one tile 2.19, 1.58 and 1.48 ms a call; PERF.md section 6, PR 55).  The
default budget cut Mellum2's 2304 x 896 into seven tiles of 128 lanes, 2.7
ms of HBM time for 1.4 ms of MXU work (3.91 ms a call; whole 1.77), so the
whole tile is taken wherever ``_VMEM_CAP`` holds it, and the fewest tiles
where it does not (Command A+'s 4096 x 4096 in four tiles of ``[2048,
2048]`` under 35.0 MiB where 32 MiB held eight: 2.888 -> 2.773 ms a call,
PR 61).  The sum's order is the same whatever the tile, and so is every
number of dW.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops import backend
from dlrover_tpu.ops.row_gather_sum import LANES, tile_rows


# What a kernel's weight tiles may take of the DEFAULT scoped VMEM: the
# double-buffered [tk, tm] block of one expert's weights (dw: the f32
# accumulator and the double-buffered output tile), 8 of the 16 MiB a
# kernel gets that asks for nothing; the row blocks of x / dy / out, the
# f32 accumulator and Mosaic's temporaries take the rest.  16 MiB is that
# default, not the chip's VMEM (a v5e core has 128 MiB): a forward or dx
# call whose whole-K strip overflows this budget asks for more
# (``_VMEM_CAP``) before it splits K, because a split K costs the weights
# once a ROW BLOCK and not once an expert (the module docstring).  The dw
# call asks for more before it tiles as well (``plan_dw_tiles``): its tiles
# cost x and dy a pass over HBM each and the grid its steps; Mosaic accepts
# 17.8 MiB for Mellum2's whole [2304, 896] tile (18.1 counted), 30.3 for
# LFM2's [2048, 1792] (30.8) and 34.4 for a [2048, 2048] quarter of Command
# A+'s (35.0) for the described chip.  A whole [K, M] expert
# block overflows the budget at MoE widths (K=1600, M=3200), so under the
# default limit the kernels tile M, and K where they must.
_TILE_BYTES = 8 * 2**20

# The most VMEM a call may ask for (``vmem_limit_bytes``), a forward/dx call
# to keep a whole-K strip resident, a dw call to keep its tile whole: the
# least round value that holds Command A+'s [4096, 2048] bf16 strip (38.0
# MiB counted, 32 of them the strip twice; Mosaic accepts 36.0 for the
# described chip), of a v5e core's 128 MiB.  ``_strip_vmem_bytes`` counts
# LFM2's [1792, 2048] at 18.3 MiB (14.0 the strip twice; Mosaic accepts
# 16.4) and Nemotron's [2688, 1856] at 24.5 (19.7; 21.8); Mixtral's [14336,
# 2048] would take 125.5 and keeps the K-split.  What a call asks for XLA
# cannot use beside it: XLA keeps whole operands of a kernel in VMEM where
# they fit (two of LFM2's 56 MiB expert matrices under the default limit,
# one under 18.3 MiB: PERF.md section 6, PR 53), so the call asks for what
# its plan needs and no more.  What the chip read between 25 and 40 MiB
# (PERF.md section 6, PR 61; Command A+'s shapes): the resident strip and
# the four dW tiles are the faster forms (the module docstring has the
# calls' times), Mosaic takes the limit alone and in the cell's step, and
# the compiled step holds the same large operands in VMEM (``S(1)``) under
# either cap, the shared expert's 32 MiB matrices and the replayed ``wo``
# call's 89 MiB of rows among them.  From 48 MiB on Nemotron's whole dW
# tile (42.8) would fit too: not measured, nor anything up to 125.
_VMEM_CAP = 40 * 2**20


def _lane_tiles(dim: int, quantum: int = LANES):
    """Every block width ``dim`` can be cut into, narrowest first: its
    divisors that are multiples of ``quantum`` (128 lanes), or ``dim`` alone
    when it is no multiple of it (such a dim can only be a block's full
    extent)."""
    if dim % quantum:
        return [dim]
    groups = dim // quantum
    return [quantum * t for t in range(1, groups + 1) if groups % t == 0]


def _lane_tile(dim: int, limit: int, quantum: int = LANES) -> int:
    """The widest of ``_lane_tiles`` that is at most ``limit`` (the
    narrowest where none is)."""
    widths = _lane_tiles(dim, quantum)
    return max((t for t in widths if t <= limit), default=widths[0])


def _quantum(tiled: bool, dtype) -> int:
    """What a block's width is a multiple of: lanes, and for a row-tiled
    array whole native tiles (its lane groups are the second-minor dim)."""
    return LANES * tile_rows(dtype) if tiled else LANES


class Tiles(NamedTuple):
    """A call's weight block (dw: its tile of dw) ``[tk, tm]`` and the scoped
    VMEM it has to ask for (``None``: the default limit holds it)."""

    tk: int
    tm: int
    vmem_limit_bytes: Optional[int]


def _padded(n: int, tile: int) -> int:
    """``n`` rounded up to whole ``tile``s, as an array's side lies in VMEM."""
    return -(-n // tile) * tile


def _strip_vmem_bytes(k, tm, dtype, block_rows):
    """VMEM a forward/dx call takes with the whole ``[k, tm]`` strip as its
    weight block, as the array lies there (lanes padded to 128, sublanes to
    the dtype's tile): the strip and the row blocks of x and out double-
    buffered (one contraction step keeps no accumulator), and Mosaic's
    temporaries: the x block once more (a row-tiled block's reshape) and
    two f32 blocks of the output's shape (the dot's result and its cast's
    operand)."""

    size = jnp.dtype(dtype).itemsize
    k_lanes, tm_lanes = _padded(k, LANES), _padded(tm, LANES)
    strip = 2 * _padded(k, tile_rows(dtype)) * tm_lanes * size
    x_blocks = 3 * block_rows * k_lanes * size
    out_blocks = 2 * block_rows * tm_lanes * size
    f32_blocks = 2 * block_rows * tm_lanes * 4
    return strip + x_blocks + out_blocks + f32_blocks


def plan_tiles(
    k: int, m: int, x_tiled: bool, out_tiled: bool, dtype,
    block_rows: int = 128,
) -> Tiles:
    """The tiles of a forward/dx call ``[N, k] @ [E, k, m]`` (rows in
    row-tiled if ``x_tiled``, out if ``out_tiled``), which the kernel and
    the ``compile`` event's ``gmm_strips`` both ask.

    ``tm`` is the widest block of M whose double-buffered whole-K strip
    fits ``_TILE_BYTES`` (at least one quantum: M itself where it is no
    multiple of one).  Where that strip fits, or K cannot be cut, ``tk`` is
    K under the default limit.  Where it overflows, the call asks for the
    VMEM the whole strip needs while that is within ``_VMEM_CAP``, and only
    beyond it splits K under the default limit."""
    tile_elems = _TILE_BYTES // (2 * jnp.dtype(dtype).itemsize)
    tm = _lane_tile(m, tile_elems // k, _quantum(out_tiled, dtype))
    tk = _lane_tile(k, tile_elems // tm, _quantum(x_tiled, dtype))
    if tk == k:
        return Tiles(k, tm, None)
    need = _strip_vmem_bytes(k, tm, dtype, block_rows)
    if need <= _VMEM_CAP:
        return Tiles(k, tm, need)
    return Tiles(tk, tm, None)


def expert_strips(
    d_model: int, d_ff: int, gated: bool, rows_tiled: bool, dtype
) -> str:
    """For the ``compile`` event's ``gmm_strips``: of the distinct
    forward/dx calls of one expert layer (``wi``, ``wg`` where ``gated``
    and ``wo`` forward, and the ``dx`` of each; the replay repeats the
    forward's) how many split K: ``resident`` where none does, else
    ``split_k:<calls>/<of>``.  ``rows_tiled``: whether the d_model-wide
    rows come and go row-tiled (``row_gather_sum.kernel_fits``, which the
    layer asks)."""
    # Two plans, each met by as many calls as the layer has matrices: wi /
    # wg forward and wo's dx go INTO the expert width, wo forward and wi /
    # wg's dx come OUT OF it.
    into = plan_tiles(d_model, d_ff, rows_tiled, False, dtype)
    out_of = plan_tiles(d_ff, d_model, False, rows_tiled, dtype)
    each = 3 if gated else 2
    split = each * ((into.tk < d_model) + (out_of.tk < d_ff))
    return f"split_k:{split}/{2 * each}" if split else "resident"


def _dw_vmem_bytes(tk, tm, dtype, block_rows):
    """VMEM a dW call takes with ``[tk, tm]`` as its tile, as the arrays lie
    there: the float32 accumulator, the double-buffered output tile, and
    three of each row block of x and dy: two in flight and the body's own
    (x transposed for the contraction over rows, a row-tiled block
    reshaped).  The dot adds into the accumulator in place: Mosaic keeps no
    second float32 tile.  Its refusal line for the described chip states
    17.31 MiB for Mellum2's whole [2304, 896] before its own temporaries
    (this count less the third blocks, to the byte) and it accepts a limit
    of 17.8 (counted: 18.1); 30.3 for LFM2's [2048, 1792] (30.8), 17.9 for
    OLMoE's [2048, 1024] (18.3), 13.6 for Nemotron's [896, 1856] (15.2)."""

    size = jnp.dtype(dtype).itemsize
    tk_lanes, tm_lanes = _padded(tk, LANES), _padded(tm, LANES)
    acc = _padded(tk, 8) * tm_lanes * 4
    out_tiles = 2 * _padded(tk, tile_rows(dtype)) * tm_lanes * size
    row_blocks = 3 * block_rows * (tk_lanes + tm_lanes) * size
    return acc + out_tiles + row_blocks


def plan_dw_tiles(
    k: int, m: int, x_tiled: bool, out_tiled: bool, dtype,
    block_rows: int = 128,
) -> Tiles:
    """The ``[tk, tm]`` tile of a dW call ``x[N, k]^T @ dy[N, m]`` (x
    row-tiled if ``x_tiled``, dy if ``out_tiled``), which the kernel and the
    ``compile`` event's ``gmm_dw_tiles`` both ask.

    The grid is (K tiles, M tiles, row blocks), so x crosses HBM once an M
    tile and dy once a K tile, and every (tile, row block) pair is a grid
    step.  Where the whole ``[k, m]`` tile fits ``_TILE_BYTES`` the call
    asks for nothing.  Else it asks for what the fewest tiles need that fit
    ``_VMEM_CAP`` (the whole tile where that does), cut along the side whose
    re-read operand is the narrower; and where no tile fits the cap, it
    keeps the tiles of the default limit."""
    k_quantum = _quantum(x_tiled, dtype)
    m_quantum = _quantum(out_tiled, dtype)
    tile_elems = _TILE_BYTES // (4 + 2 * jnp.dtype(dtype).itemsize)
    tm = _lane_tile(m, tile_elems // k, m_quantum)
    tk = _lane_tile(k, tile_elems // tm, k_quantum)
    if (tk, tm) == (k, m):
        return Tiles(k, m, None)
    fits = []
    for a in _lane_tiles(k, k_quantum):
        for b in _lane_tiles(m, m_quantum):
            need = _dw_vmem_bytes(a, b, dtype, block_rows)
            if need <= _VMEM_CAP:
                # fewest grid steps, then fewest re-read bytes a row block
                k_tiles, m_tiles = k // a, m // b
                fits.append(
                    (k_tiles * m_tiles, m_tiles * k + k_tiles * m, need, a, b)
                )
    if not fits:
        return Tiles(tk, tm, None)
    _, _, need, tk, tm = min(fits)
    return Tiles(tk, tm, need)


def expert_dw_tiles(d_model: int, d_ff: int, rows_tiled: bool, dtype) -> str:
    """For the ``compile`` event's ``gmm_dw_tiles``: how many tiles the two
    dW plans of one expert layer cut an expert's matrix into, ``into:<K
    tiles>x<M tiles>`` for ``wi`` (and ``wg``), ``out_of:`` for ``wo``."""
    into = plan_dw_tiles(d_model, d_ff, rows_tiled, False, dtype)
    out_of = plan_dw_tiles(d_ff, d_model, False, rows_tiled, dtype)
    return (
        f"into:{d_model // into.tk}x{d_ff // into.tm} "
        f"out_of:{d_ff // out_of.tk}x{d_model // out_of.tm}"
    )


def _row_block(tiled, block_rows, width, index):
    """BlockSpec of ``block_rows`` rows by ``width`` columns at block
    ``index(*grid) -> (i, j)``, of a plain or a row-tiled array."""
    if not tiled:
        return pl.BlockSpec((block_rows, width), index, memory_space=pltpu.VMEM)
    return pl.BlockSpec(
        (block_rows, width // LANES, LANES),
        lambda *grid: index(*grid) + (0,), memory_space=pltpu.VMEM,
    )


def _plain(ref):
    """A row block as ``[rows, width]``, whichever form it is held in."""
    block = ref[...]
    return block.reshape(block.shape[0], -1) if block.ndim == 3 else block


def _gmm_kernel(*refs, skip_dead, k_steps):
    live_blocks = refs[1] if skip_dead else None
    if k_steps == 1:
        # One contraction step: the dot's own result is the output block,
        # and no float32 accumulator is zeroed, added into and read back.
        x_ref, w_ref, out_ref = refs[-3:]

        def product():
            out_ref[...] = jax.lax.dot(
                _plain(x_ref), w_ref[0], preferred_element_type=jnp.float32
            ).astype(out_ref.dtype).reshape(out_ref.shape)

        if skip_dead:
            live = pl.program_id(1) < live_blocks[0]
            pl.when(live)(product)

            @pl.when(jnp.logical_not(live))
            def _():
                out_ref[...] = jnp.zeros_like(out_ref)
        else:
            product()
        return

    x_ref, w_ref, out_ref, acc_ref = refs[-4:]
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def accumulate():
        acc_ref[:] += jax.lax.dot(
            _plain(x_ref), w_ref[0], preferred_element_type=jnp.float32
        )

    if skip_dead:
        pl.when(pl.program_id(1) < live_blocks[0])(accumulate)
    else:
        accumulate()

    @pl.when(kk == k_steps - 1)
    def _():
        # a dead block writes the zeros its accumulator still holds
        out_ref[...] = acc_ref[:].astype(out_ref.dtype).reshape(out_ref.shape)


def _expert_of_block(group_sizes, num_blocks, block_rows):
    offsets = jnp.cumsum(group_sizes)
    block_starts = jnp.arange(num_blocks, dtype=jnp.int32) * block_rows
    eob = jnp.searchsorted(offsets, block_starts, side="right")
    # Rows past sum(group_sizes) (caller's static padding budget) clamp to
    # the last expert: they hold zeros, so the extra GEMM work is inert.
    return jnp.minimum(eob, group_sizes.shape[0] - 1).astype(jnp.int32)


def _block_plan(group_sizes, num_blocks, block_rows, skip_dead):
    """The kernels' prefetched scalars: ``(expert_of_block,)``, or with
    ``skip_dead`` ``(expert_of_block, live_blocks [1])``: how many row
    blocks hold rows of a group, the dead blocks after them taking the
    last live block's expert so that no index map moves on them."""
    eob = _expert_of_block(group_sizes, num_blocks, block_rows)
    if not skip_dead:
        return (eob,)
    live = (jnp.sum(group_sizes) // block_rows).astype(jnp.int32)
    last = eob[jnp.maximum(live - 1, 0)]
    eob = jnp.where(jnp.arange(num_blocks) < live, eob, last)
    return eob, live.reshape(1)


def _live(i, scalars):
    """The row block the index maps give for grid row ``i``: itself, or
    under ``skip_dead`` (a second scalar) the last live one where ``i`` is
    dead."""
    if len(scalars) == 1:
        return i
    return jnp.minimum(i, jnp.maximum(scalars[1][0] - 1, 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def grouped_matmul(
    x: jax.Array,           # [N, K] rows sorted by expert, or row-tiled
    w: jax.Array,           # [E, K, M]
    group_sizes: jax.Array, # [E] int32, sum <= N, multiples of block_rows
    block_rows: int = 128,
    out_tiled: bool = False,
    skip_dead: bool = False,
) -> jax.Array:
    """Returns [N, M] where out[r] = x[r] @ w[expert_of_row(r)]; with
    ``out_tiled`` the same rows as ``[N, M // 128, 128]``.  ``dx`` comes
    back in the form ``x`` came in.  ``skip_dead``: the module docstring."""
    return _gmm_fwd_impl(x, w, group_sizes, block_rows, out_tiled, skip_dead)


def _gmm_fwd_impl(x, w, group_sizes, block_rows, out_tiled, skip_dead):
    n = x.shape[0]
    e, k, m = w.shape
    assert n % block_rows == 0, f"N={n} not a multiple of {block_rows}"
    num_blocks = n // block_rows
    scalars = _block_plan(group_sizes, num_blocks, block_rows, skip_dead)

    # Output columns outermost, contraction innermost.  While the whole K
    # is one step (tk == k) an expert's [K, tm] strip stays resident across
    # its consecutive row blocks; split, every row block fetches it again.
    tk, tm, vmem_limit = plan_tiles(
        k, m, x.ndim == 3, out_tiled, x.dtype, block_rows
    )
    k_steps = k // tk
    last_k = k_steps - 1

    def k_of(i, kk, s):
        # a dead block stays on the contraction tile the last live one ended on
        return kk if len(s) == 1 else jnp.where(i < s[1][0], kk, last_k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(m // tm, num_blocks, k_steps),
        in_specs=[
            _row_block(
                x.ndim == 3, block_rows, tk,
                lambda j, i, kk, *s: (_live(i, s), k_of(i, kk, s)),
            ),
            pl.BlockSpec(
                (1, tk, tm), lambda j, i, kk, *s: (s[0][i], k_of(i, kk, s), j),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=_row_block(
            out_tiled, block_rows, tm, lambda j, i, kk, *s: (i, j)
        ),
        scratch_shapes=[pltpu.VMEM((block_rows, tm), jnp.float32)]
        if k_steps > 1 else [],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, skip_dead=skip_dead, k_steps=k_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (n, m // LANES, LANES) if out_tiled else (n, m), x.dtype
        ),
        compiler_params=vmem_limit and pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit
        ),
        interpret=backend.interpret(),
    )(*scalars, x, w)


def _gmm_dw_kernel(*refs, skip_dead):
    """Accumulate x_block^T @ dy_block into the owning expert's dw tile.

    Row blocks of one expert are consecutive (rows sorted by expert), so the
    expert's output tile stays resident across its run of grid steps; the
    accumulator resets at each expert boundary.  Under ``skip_dead`` the
    dead blocks carry the last live block's expert and add nothing.
    """
    eob_ref = refs[0]
    x_ref, dy_ref, dw_ref, acc_ref = refs[-4:]
    i = pl.program_id(2)
    last_i = pl.num_programs(2) - 1
    first = jnp.logical_or(i == 0, eob_ref[i] != eob_ref[jnp.maximum(i - 1, 0)])
    last = jnp.logical_or(
        i == last_i, eob_ref[i] != eob_ref[jnp.minimum(i + 1, last_i)]
    )

    @pl.when(first)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def accumulate():
        acc_ref[:] += jax.lax.dot_general(
            _plain(x_ref), _plain(dy_ref), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if skip_dead:
        pl.when(i < refs[1][0])(accumulate)
    else:
        accumulate()

    @pl.when(last)
    def _():
        dw_ref[0] = acc_ref[:].astype(dw_ref.dtype)


def _gmm_fwd(x, w, group_sizes, block_rows, out_tiled, skip_dead):
    out = _gmm_fwd_impl(x, w, group_sizes, block_rows, out_tiled, skip_dead)
    return out, (x, w, group_sizes)


def _gmm_bwd(block_rows, out_tiled, skip_dead, residuals, dy):
    x, w, group_sizes = residuals
    n = x.shape[0]
    e, k, m = w.shape
    num_blocks = n // block_rows
    # dx: grouped matmul against w^T, in the form x came in.
    dx = _gmm_fwd_impl(
        dy, jnp.swapaxes(w, 1, 2), group_sizes, block_rows, x.ndim == 3,
        skip_dead,
    ).astype(x.dtype)
    # dw: per-expert accumulation over that expert's row blocks.
    scalars = _block_plan(group_sizes, num_blocks, block_rows, skip_dead)
    # Tiles of dw outermost, row blocks innermost: the f32 accumulator and
    # the double-buffered output block hold one [tk, tm] tile across an
    # expert's consecutive row blocks.
    tk, tm, vmem_limit = plan_dw_tiles(
        k, m, x.ndim == 3, out_tiled, x.dtype, block_rows
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(k // tk, m // tm, num_blocks),
        in_specs=[
            _row_block(
                x.ndim == 3, block_rows, tk,
                lambda a, j, i, *s: (_live(i, s), a),
            ),
            _row_block(
                out_tiled, block_rows, tm,
                lambda a, j, i, *s: (_live(i, s), j),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, tk, tm), lambda a, j, i, *s: (s[0][i], a, j),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[pltpu.VMEM((tk, tm), jnp.float32)],
    )
    dw = pl.pallas_call(
        functools.partial(_gmm_dw_kernel, skip_dead=skip_dead),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, k, m), w.dtype),
        compiler_params=vmem_limit and pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit
        ),
        interpret=backend.interpret(),
    )(*scalars, x, dy)
    # An expert with no rows is never visited (or only by dead blocks) and
    # the kernel leaves its dw block unwritten: that expert's gradient is
    # zero.
    dw = jnp.where((group_sizes > 0)[:, None, None], dw, 0.0).astype(w.dtype)
    return dx, dw, None


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul_ref(x, w, group_sizes):
    """Plain XLA reference: one masked dense matmul per expert.

    E times the kernel's FLOPs but only [N, K] of extra memory, so it
    stands beside the kernel at MoE widths too (gathering ``w[experts]``
    per row would take N*K*M elements).  Rows past ``sum(group_sizes)``
    belong to no expert and come out zero.
    """
    ends = jnp.cumsum(group_sizes)
    rows = jnp.arange(x.shape[0])[:, None]
    out = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
    for e in range(w.shape[0]):
        mine = (rows >= ends[e] - group_sizes[e]) & (rows < ends[e])
        out = out + jnp.dot(
            jnp.where(mine, x, 0), w[e], preferred_element_type=jnp.float32
        )
    return out.astype(x.dtype)
