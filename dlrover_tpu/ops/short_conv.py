"""The short causal depthwise convolution with its epilogue, one Pallas pass
forward and one backward.

Per channel, with ``K`` taps (Mamba-2's and Gated DeltaNet's ``conv1d``)::

    u[t] = sum_j taps[j] x[t - (K - 1) + j]  (+ bias)     zeros before t = 0
    y[t] = SiLU(u[t])

``x`` is ``[B, S, W]`` or ``[B, S, H, W]`` and is read WHERE IT LIES: the
``C = taps.shape[-1]`` channels are columns ``offset .. offset + C`` of the
last axis (of each of the ``H`` heads), found by the index map, so no slice
of a wider projection is copied out first.  ``splits`` cuts the output's
last axis into several arrays (Mamba-2's ``x | B | C``, Gated DeltaNet's
``q | k | v``), so none follows either; an output whose ``l2_scales`` is a
number leaves L2-normalised over its columns (a head's q and k) and
multiplied by it, the norm and its transpose computed in the same passes.

**The tokens are the lane axis.**  XLA holds a projection's ``[B, S, (H,)
W]`` output channel-major on the chip: the tokens the minor axis, a head's
``W`` channels the sublanes (``{1,3,2,0}``: no padding, 576 = 36 row tiles
of 16).  The kernels read ``x`` as ``[B (H), W, S]``, a transpose that moves
nothing there, and write their outputs the same way.  A token shift is then
a lane rotation, which bfloat16's packing of two ROWS a sublane does not
touch, so a tile needs no float32 stage.  Reading ``[.., S, W]`` as it is
written (tokens on the sublanes: a float32 stage cut into units of eight
tokens, a shifted unit two neighbours' rows chosen by sublane and rotated)
gave kernels as fast, but cost the projections and the norms around them
their layout (PERF.md §6, PR 38, has the layouts tried and their numbers).

**What lives where.**  A grid step holds one row's ``[wc channels, ts
tokens]`` tile and, from a second small block of the same input, the 128
tokens before it (zeros at a sequence's first tile: no token crosses a
batch row).  A loop runs over strips of 16 channels (one bfloat16 row
tile), each with its taps spread over the lanes once; inside it the strip's
lane tiles are worked through side by side, every one of them the previous
lane tile and itself chosen by lane and rotated, ``K - 1`` times: products,
sum, bias and SiLU in float32 in registers, ONE rounding at the write.  The
backward reads x (with the tokens before AND after its tile) and dy (with
the tokens after), rebuilds ``u`` from x rather than keeping it, forms ``du
= dy SiLU'(u)`` on the tile and the lane tile after it (float32 VMEM
scratch), and writes ``dx[t] = sum_j taps[j] du[t + (K - 1) - j]``;
``d_taps[j] = sum x[t - (K - 1) + j] du[t]`` and ``d_bias = sum du`` are
summed in registers over a strip's tokens and land, tap ``j`` in lane
``j``, in one float32 output block that stays in VMEM for the whole grid.
HBM sees x once forward, x and dy once and dx once backward; no padded
copy, no ``K`` shifted arrays.

An L2-normalised output is ONE channel tile, so a token's sum over the
channels is a step's own: the forward keeps the tile's float32 activations
in VMEM beside the strips' sums of squares, then multiplies them by ``scale
/ sqrt(sum + eps)`` on their way out; the backward's first pass rebuilds
``y = SiLU(u)`` and ``SiLU'(u)`` into VMEM and sums ``y y`` and ``dn y``
over the strips, and its second forms ``du = scale / |y| (dn - y (dn . y) /
|y|^2) SiLU'(u)`` from them where the plain output forms ``dy SiLU'(u)``.

:func:`plan` says whether a shape tiles (tokens whole lane tiles, channels
and offset whole row tiles, a normalised output one tile); the caller
(``models/linear_attention.causal_depthwise_conv``) takes the written-out
XLA form for anything else.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops import backend

F32 = jnp.float32
LANES = 128
L2_EPS = 1e-6   # under the root of an output's L2 norm
STRIP = 16      # channels a strip: one bfloat16 row tile, two float32
# the most channels and tokens a tile takes: the backward holds three
# double-buffered operand tiles and a float32 tile of du (two more of a
# normalised output, which is never wider than a head's q or k)
_TILE_CHANNELS = 512
_TILE_TOKENS = 2048
_VMEM_LIMIT = 64 * 2 ** 20
# lane tiles a trip of the inner loops works through side by side: their
# chains are independent, so the scheduler fills one's latencies with the
# others' (4: 3.49 ms a forward pass of the hybrid's layer, 8: 2.30, 16:
# 1.33, where moving the tiles through VMEM alone takes 1.17)
_UNROLL = 16


class Plan(NamedTuple):
    """How one call is tiled, on the input as ``[rows, W, S]`` (``rows``:
    the batch, times the heads where there is a head axis)."""
    ts: int          # tokens a tile (lanes)
    wc: int          # channels a tile (sublanes)
    first: int       # the input's block index of channel tile 0
    heads: int       # rows that differ in their taps
    ranges: Tuple[Tuple[int, int], ...]   # each output's channel tiles
    # each output's L2 norm: the factor of its normalised rows, or None
    norms: Tuple[Optional[float], ...]


def plan(
    x_shape: Sequence[int], taps_shape: Sequence[int], offset: int = 0,
    splits: Optional[Sequence[int]] = None,
    l2_scales: Optional[Sequence[Optional[float]]] = None,
) -> Optional[Plan]:
    """The tiling of ``x`` under ``taps``, or None where the kernels cannot
    tile it.  Asks nothing of the batch size, nor of the number of heads.
    An output that is L2-normalised is one channel tile: its sum over the
    channels is a step's own."""
    if len(x_shape) not in (3, 4) or len(taps_shape) != len(x_shape) - 1:
        return None
    if tuple(x_shape[2:-1]) != tuple(taps_shape[1:-1]):
        return None
    k, channels, width = taps_shape[0], taps_shape[-1], x_shape[-1]
    # lane K of a channel's parameters is its bias
    if not 2 <= k < LANES or offset + channels > width:
        return None
    widths = tuple(splits) if splits else (channels,)
    norms = tuple(l2_scales) if l2_scales else (None,) * len(widths)
    if sum(widths) != channels or len(norms) != len(widths):
        return None
    # a channel tile is a block of the input, ``offset`` whole blocks in
    wc = next((
        t for t in range(_TILE_CHANNELS, 0, -STRIP)
        if offset % t == 0 and all(w % t == 0 for w in widths)
    ), None)
    seq = x_shape[1]
    ts = min(_TILE_TOKENS, seq) // LANES * LANES
    while ts > LANES and seq % ts:
        ts -= LANES
    if wc is None or ts < LANES or seq % ts:
        return None
    if any(w != wc for w, scale in zip(widths, norms) if scale is not None):
        return None
    bounds = [0]
    for w in widths:
        bounds.append(bounds[-1] + w // wc)
    heads = x_shape[2] if len(x_shape) == 4 else 1
    return Plan(
        ts, wc, offset // wc, heads, tuple(zip(bounds, bounds[1:])), norms
    )


def _for_each(count, body, carry=None):
    """``carry = body(n, carry)`` for ``n`` in ``range(count)``, ``_UNROLL``
    of them a trip of the loop (the body is traced once and the lowering
    lays its copies side by side)."""
    whole = count // _UNROLL * _UNROLL
    if whole:
        carry = jax.lax.fori_loop(0, whole, body, carry, unroll=_UNROLL)
    for n in range(whole, count):
        carry = body(n, carry)
    return carry


def _sigmoid(u):
    """As XLA's own logistic on the chip: the reciprocal is the
    transcendental unit's (the forward then agrees with the XLA form bit
    for bit; interpret mode stands in a bfloat16 rounding for it, so there
    the division is written out)."""
    one_more = 1.0 + jnp.exp(-u)
    if backend.interpret():
        return 1.0 / one_more
    return pl.reciprocal(one_more, approx=True)


def _lane_masks(k, ahead=False):
    """For each shift of 1 .. K - 1 tokens, the lanes a shifted tile takes
    from its neighbour."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (STRIP, LANES), 1)
    return [None] + [lane >= (s if ahead else LANES - s) for s in range(1, k)]


def _shifted(near, far, s, masks, ahead=False):
    """The lane tile ``near`` with each token replaced by the one ``s``
    behind it (``ahead``: after it); ``far`` is the lane tile before
    (after) it: the two chosen by lane and rotated into place."""
    if s == 0:
        return near
    if ahead:
        return pltpu.roll(jnp.where(masks[s], near, far), LANES - s, axis=1)
    return pltpu.roll(jnp.where(masks[s], far, near), s, axis=1)


def _columns(par_ref, rows, k, has_bias):
    """A strip's taps (and bias), each a column spread over the lanes."""
    par = par_ref[rows, :]
    spread = [
        jnp.broadcast_to(par[:, j: j + 1], (STRIP, LANES))
        for j in range(k + has_bias)
    ]
    return spread[:k], spread[k] if has_bias else None


def _strip_rows(r):
    return pl.ds(pl.multiple_of(r * STRIP, STRIP), STRIP)


def _tile_cols(n):
    first = n * LANES
    if not isinstance(first, int):
        first = pl.multiple_of(first, LANES)
    return pl.ds(first, LANES)


def _halo(ref, rows, inside):
    """A strip of a neighbouring lane tile as float32; zeros where there is
    no neighbour (a sequence's first and last tile)."""
    return jnp.where(inside, ref[0, rows, :].astype(F32), 0.0)


def _in_range(c, lo, hi):
    return jnp.logical_and(c >= lo, c < hi)


def _u_of(taps, bias, cur, prev, masks):
    """``u`` on one lane tile, and the shifted inputs it was made of, tap
    by tap."""
    k = len(taps)
    shifted = [_shifted(cur, prev, k - 1 - j, masks) for j in range(k)]
    u = sum(taps[j] * shifted[j] for j in range(k))
    if bias is not None:
        u = u + bias
    return u, shifted


def _silu_slope(u, sig):
    """``SiLU'(u)`` from ``sig = sigmoid(u)``."""
    return sig * (1.0 + u * (1.0 - sig))


def _norm_factor(ssq, cols, scale):
    """Lane tile ``cols`` of ``ssq`` holds sums of squares, a strip's rows
    apart; leaves there, on every row, ``scale / sqrt(their sum + eps)``:
    what an L2-normalised tile is multiplied by.  Returns ``1 / sqrt``."""
    rinv = jax.lax.rsqrt(
        jnp.sum(ssq[:, cols], axis=0, keepdims=True) + L2_EPS
    )
    ssq[:, cols] = jnp.broadcast_to(scale * rinv, (STRIP, LANES))
    return rinv


def _fwd_kernel(*refs, p: Plan, k: int, has_bias: bool):
    x_ref, before_ref, par_ref = refs[:3]
    n_out = len(p.ranges)
    out_refs, scratch = refs[3: 3 + n_out], refs[3 + n_out:]
    i, c = pl.program_id(1), pl.program_id(2)
    masks = _lane_masks(k)
    tiles = p.ts // LANES

    def strips(body):
        def strip(r, carry):
            body(_strip_rows(r))
            return carry

        jax.lax.fori_loop(0, p.wc // STRIP, strip, 0)

    def activations(emit):
        """``emit(rows, cols, SiLU(u))`` on every strip's every lane
        tile."""
        def strip(rows):
            taps, bias = _columns(par_ref, rows, k, has_bias)

            def tile(n, prev):
                cols = _tile_cols(n)
                cur = x_ref[0, rows, cols].astype(F32)
                u, _ = _u_of(taps, bias, cur, prev, masks)
                emit(rows, cols, u * _sigmoid(u))
                return cur

            _for_each(tiles, tile, _halo(before_ref, rows, i > 0))

        strips(strip)

    def write(out_ref, scale):
        def rounded(rows, cols, y):
            out_ref[0, rows, cols] = y.astype(out_ref.dtype)

        if scale is None:
            activations(rounded)
            return
        # an L2 norm over the tile's channels: the activations wait in
        # float32 for the sum of their squares, a token's over the strips
        ybuf, ssq = scratch
        ssq[...] = jnp.zeros_like(ssq)

        def kept(rows, cols, y):
            ybuf[rows, cols] = y
            ssq[:, cols] += y * y

        activations(kept)

        def factor(n, carry):
            _norm_factor(ssq, _tile_cols(n), scale)
            return carry

        _for_each(tiles, factor)

        def strip(rows):
            def tile(n, carry):
                cols = _tile_cols(n)
                rounded(rows, cols, ybuf[rows, cols] * ssq[:, cols])
                return carry

            _for_each(tiles, tile)

        strips(strip)

    # several outputs: a step writes the one its channel tile lies in; the
    # others' blocks stay where they are (their index does not move while
    # the channel axis, the grid's last, runs through another's tiles)
    for out_ref, (lo, hi), scale in zip(out_refs, p.ranges, p.norms):
        if n_out == 1:
            write(out_ref, scale)
        else:
            pl.when(_in_range(c, lo, hi))(
                functools.partial(write, out_ref, scale)
            )


def _bwd_kernel(*refs, p: Plan, k: int, has_bias: bool):
    x_ref, before_ref, after_ref, par_ref = refs[:4]
    n_out = len(p.ranges)
    dy_refs = refs[4: 4 + 2 * n_out]
    dx_ref, acc_ref, dus = refs[4 + 2 * n_out: 7 + 2 * n_out]
    scratch = refs[7 + 2 * n_out:]
    b, i, c = (pl.program_id(a) for a in range(3))
    last = i == pl.num_programs(1) - 1
    tiles = p.ts // LANES
    behind, ahead = _lane_masks(k), _lane_masks(k, ahead=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, (STRIP, LANES), 1)
    zero = jnp.zeros((STRIP, LANES), F32)

    @pl.when((b == 0) & (i == 0) & (c == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def walk(dy_ref, dy_after, visit, sums, finish):
        """Strip by strip ``sums = visit(rows, taps, bias, cols, x, the
        lane tile of x before it, dy, sums)`` on the tile's lane tiles and
        once more, its result dropped, on the lane tile after them (whose
        first K - 1 tokens' du the tile's last tokens of dx take); then
        ``finish(r, rows, taps, sums)``."""
        def strip(r, carry):
            rows = _strip_rows(r)
            taps, bias = _columns(par_ref, rows, k, has_bias)

            def tile(n, carry):
                prev, sums = carry
                cols = _tile_cols(n)
                cur = x_ref[0, rows, cols].astype(F32)
                dy = dy_ref[0, rows, cols].astype(F32)
                return cur, visit(rows, taps, bias, cols, cur, prev, dy, sums)

            prev, found = _for_each(
                tiles, tile, (_halo(before_ref, rows, i > 0), sums)
            )
            visit(
                rows, taps, bias, _tile_cols(tiles),
                _halo(after_ref, rows, ~last), prev,
                _halo(dy_after, rows, ~last), sums,
            )
            finish(r, rows, taps, found)
            return carry

        jax.lax.fori_loop(0, p.wc // STRIP, strip, 0)

    def run(dy_ref, dy_after, scale):
        if scale is None:
            def du_of(rows, cols, u, dy):
                return dy * _silu_slope(u, _sigmoid(u))
        else:
            # the cotangent goes back through the L2 norm of the tile's
            # channels first: a pass that rebuilds y = SiLU(u) and SiLU'(u)
            # and sums y y and dy y over the strips, a token's each
            ybuf, dbuf, ssq, dotq = scratch
            ssq[...] = jnp.zeros_like(ssq)
            dotq[...] = jnp.zeros_like(dotq)

            def keep(rows, taps, bias, cols, cur, prev, dy, sums):
                u, _ = _u_of(taps, bias, cur, prev, behind)
                sig = _sigmoid(u)
                y = u * sig
                ybuf[rows, cols] = y
                dbuf[rows, cols] = _silu_slope(u, sig)
                ssq[:, cols] += y * y
                dotq[:, cols] += dy * y
                return sums

            walk(dy_ref, dy_after, keep, (), lambda *a: None)

            # n = scale y / |y|:  dy = scale / |y| (dn - y (dn . y) / |y|^2)
            def factors(n, carry):
                cols = _tile_cols(n)
                dot = jnp.sum(dotq[:, cols], axis=0, keepdims=True)
                rinv = _norm_factor(ssq, cols, scale)
                dotq[:, cols] = jnp.broadcast_to(
                    rinv * rinv * dot, (STRIP, LANES)
                )
                return carry

            _for_each(tiles + 1, factors)

            def du_of(rows, cols, u, dy):
                return ssq[:, cols] * (
                    dy - ybuf[rows, cols] * dotq[:, cols]
                ) * dbuf[rows, cols]

        # du on the tile, lane tile by lane tile, the parameters'
        # gradients summed in registers beside it
        def visit(rows, taps, bias, cols, cur, prev, dy, sums):
            u, shifted = _u_of(taps, bias, cur, prev, behind)
            du = du_of(rows, cols, u, dy)
            dus[rows, cols] = du
            return [a + x * du for a, x in zip(sums, shifted)] + [
                sums[k] + du
            ]

        def finish(r, rows, taps, sums):
            # a channel's sums over the tokens: tap j in lane j, the bias
            # in lane K
            found = zero
            for j in range(k + has_bias):
                found = jnp.where(
                    lane == j, jnp.sum(sums[j], axis=1, keepdims=True), found
                )
            head_tile = b % p.heads * p.ranges[-1][1] + c
            acc_ref[pl.ds(
                pl.multiple_of(head_tile * p.wc + r * STRIP, STRIP), STRIP
            ), :] += found

            def dx_tile(n, carry):
                here, after = dus[rows, _tile_cols(n)], dus[
                    rows, _tile_cols(n + 1)
                ]
                dx = sum(
                    taps[j] * _shifted(here, after, k - 1 - j, ahead, True)
                    for j in range(k)
                )
                dx_ref[0, rows, _tile_cols(n)] = dx.astype(dx_ref.dtype)
                return carry

            _for_each(tiles, dx_tile)

        walk(dy_ref, dy_after, visit, [zero] * (k + 1), finish)

    for s, ((lo, hi), scale) in enumerate(zip(p.ranges, p.norms)):
        if n_out == 1:
            run(*dy_refs, scale)
        else:
            pl.when(_in_range(c, lo, hi))(functools.partial(
                run, dy_refs[2 * s], dy_refs[2 * s + 1], scale
            ))


def _tokens_last(x):
    """``[B, S, (H,) W]`` as ``[B (H), W, S]``."""
    if x.ndim == 4:
        x = x.transpose(0, 2, 3, 1)
        return x.reshape((-1,) + x.shape[2:])
    return x.transpose(0, 2, 1)


def _tokens_back(y, like):
    """``[B (H), C, S]`` as ``[B, S, (H,) C]``, the axes of ``like``."""
    if like.ndim == 3:
        return y.transpose(0, 2, 1)
    batch, _, heads, _ = like.shape
    return y.reshape((batch, heads) + y.shape[1:]).transpose(0, 3, 1, 2)


def _lane_params(taps, bias):
    """``[heads x channels, 128]`` float32: a channel's tap ``j`` in lane
    ``j``, its bias in lane ``K``."""
    k = taps.shape[0]
    cols = [taps.reshape(k, -1).astype(F32)]
    if bias is not None:
        cols.append(bias.reshape(1, -1).astype(F32))
    par = jnp.concatenate(cols, axis=0).T
    return jnp.pad(par, [(0, 0), (0, LANES - par.shape[1])])


def _specs(p: Plan, seq: int):
    """Index maps of a step ``(b, i, c)``: the input's tile, the lane
    tiles before and after it, a parameter's tile, and an output's (its own
    channel tiles; it stays at its nearest while the step is another's)."""
    per, blocks = p.ts // LANES, seq // LANES
    tiles = p.ranges[-1][1]

    def tile(b, i, c):
        return b, c + p.first, i

    def before(b, i, c):
        return b, c + p.first, jnp.maximum(i * per - 1, 0)

    def after_of(column):
        def index(b, i, c):
            # past the last tile: any block (the kernel zeroes it)
            return b, column(c), jnp.minimum((i + 1) * per, blocks - 1)
        return index

    def out_column(lo, hi):
        return lambda c: jnp.clip(c - lo, 0, hi - lo - 1)

    def out_tile(lo, hi):
        column = out_column(lo, hi)
        return lambda b, i, c: (b, column(c), i)

    def param(b, i, c):
        return b % p.heads * tiles + c, 0

    return tile, before, after_of, out_column, out_tile, param


def _compiler_params(semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT,
    )


def _norm_scratch(p: Plan, tokens: int, each: int):
    """Float32 VMEM of an L2-normalised output's pass, ``tokens`` wide:
    ``each`` tiles of the channels, then ``each`` strips of sums."""
    if all(scale is None for scale in p.norms):
        return []
    return [pltpu.VMEM((p.wc, tokens), F32)] * each + [
        pltpu.VMEM((STRIP, tokens), F32)
    ] * each


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _forward(x, taps, bias, offset, splits, l2_scales):
    """(Jitted, as the backward is and as ``gated_delta_rule``'s are, so
    that a step which runs the convolution in several slots, forward,
    recomputed and transposed, traces and lowers each kernel body once:
    the bodies are long, and a start from a warm compile cache pays for
    every lowering again.)"""
    p = plan(x.shape, taps.shape, offset, splits, l2_scales)
    like, k = x, taps.shape[0]
    x, par = _tokens_last(x), _lane_params(taps, bias)
    rows, _, seq = x.shape
    tile, before, _, _, out_tile, param = _specs(p, seq)
    outs = pl.pallas_call(
        functools.partial(
            _fwd_kernel, p=p, k=k, has_bias=bias is not None
        ),
        grid=(rows, seq // p.ts, p.ranges[-1][1]),
        in_specs=[
            pl.BlockSpec((1, p.wc, p.ts), tile),
            pl.BlockSpec((1, p.wc, LANES), before),
            pl.BlockSpec((p.wc, LANES), param),
        ],
        out_specs=[
            pl.BlockSpec((1, p.wc, p.ts), out_tile(lo, hi))
            for lo, hi in p.ranges
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, (hi - lo) * p.wc, seq), x.dtype)
            for lo, hi in p.ranges
        ],
        scratch_shapes=_norm_scratch(p, p.ts, 1),
        # the channel axis last and in order: an output's block is left once
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")
        ),
        interpret=backend.interpret(), name="short_conv_fwd",
    )(x, x, par)
    outs = [_tokens_back(y, like) for y in outs]
    return tuple(outs) if splits else outs[0]


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _backward(x, taps, bias, dys, offset, splits, l2_scales):
    p = plan(x.shape, taps.shape, offset, splits, l2_scales)
    like, k, taps_shape = x, taps.shape[0], taps.shape
    x, par = _tokens_last(x), _lane_params(taps, bias)
    dys = [_tokens_last(dy) for dy in dys]
    rows, _, seq = x.shape
    channels = taps_shape[-1]
    tile, before, after_of, out_column, out_tile, param = _specs(p, seq)
    tiles = p.ranges[-1][1]
    in_specs = [
        pl.BlockSpec((1, p.wc, p.ts), tile),
        pl.BlockSpec((1, p.wc, LANES), before),
        pl.BlockSpec((1, p.wc, LANES), after_of(lambda c: c + p.first)),
        pl.BlockSpec((p.wc, LANES), param),
    ]
    operands = [x, x, x, par]
    for dy, (lo, hi) in zip(dys, p.ranges):
        in_specs += [
            pl.BlockSpec((1, p.wc, p.ts), out_tile(lo, hi)),
            pl.BlockSpec((1, p.wc, LANES), after_of(out_column(lo, hi))),
        ]
        operands += [dy, dy]
    # every channel's sums, the whole of them one block that stays in VMEM
    acc_shape = (p.heads * channels, LANES)
    dx, acc = pl.pallas_call(
        functools.partial(
            _bwd_kernel, p=p, k=k, has_bias=bias is not None
        ),
        grid=(rows, seq // p.ts, tiles),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, p.wc, p.ts), out_tile(0, tiles)),
            pl.BlockSpec(acc_shape, lambda b, i, c: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, channels, seq), x.dtype),
            jax.ShapeDtypeStruct(acc_shape, F32),
        ],
        scratch_shapes=[pltpu.VMEM((p.wc, p.ts + LANES), F32)]
        + _norm_scratch(p, p.ts + LANES, 2),
        compiler_params=_compiler_params(("arbitrary",) * 3),
        interpret=backend.interpret(), name="short_conv_bwd",
    )(*operands)
    d_taps = acc[:, :k].T.reshape(taps_shape).astype(taps.dtype)
    d_bias = None
    if bias is not None:
        d_bias = acc[:, k].reshape(taps_shape[1:]).astype(bias.dtype)
    return _tokens_back(dx, like), d_taps, d_bias


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def short_conv(x, taps, bias, offset, splits, l2_scales):
    """``SiLU(causal depthwise conv(x[..., offset: offset + C]) + bias)``
    through the kernels; a tuple of the ``splits`` column ranges with
    ``splits``, the range ``n`` of them L2-normalised over its columns and
    multiplied by ``l2_scales[n]`` where that is a number.  Only for a
    shape :func:`plan` tiles."""
    return _forward(x, taps, bias, offset, splits, l2_scales)


def _vjp_fwd(x, taps, bias, offset, splits, l2_scales):
    return _forward(x, taps, bias, offset, splits, l2_scales), (
        x, taps, bias
    )


def _vjp_bwd(offset, splits, l2_scales, res, dys):
    x, taps, bias = res
    dx, d_taps, d_bias = _backward(
        x, taps, bias, tuple(dys) if splits else (dys,), offset, splits,
        l2_scales,
    )
    # the cotangent of the whole input: zeros outside the channels read
    # (XLA folds the padding into the sum with the other columns')
    rest = x.shape[-1] - offset - dx.shape[-1]
    dx = jnp.pad(dx, [(0, 0)] * (x.ndim - 1) + [(offset, rest)])
    return dx, d_taps, d_bias


short_conv.defvjp(_vjp_fwd, _vjp_bwd)


# -- the gated form: C * conv(B * z), no activation ---------------------------
#
# LFM2's mixer core (``models/gated_conv.py``).  ``x`` is ``[B, S, 3d]``, the
# three d-wide column ranges ``B | C | z`` of ONE projection, read where
# they lie as ``[B, 3, d, S]`` (the same transpose that moves nothing, then
# a split of the channel axis): one block of a grid step holds a channel
# tile of all three.  ``y[t] = C[t] sum_j taps[j] (B z)[t - (K - 1) + j]``;
# the backward rebuilds ``u = conv(B z)`` from x, forms ``g = dy C`` on the
# tile and the lane tile after it (float32 VMEM scratch), and writes the
# THREE cotangents into one ``[B, 3, d, S]`` array: ``dC = dy u``, and with
# ``dBz[t] = sum_j taps[j] g[t + (K - 1) - j]``, ``dB = z dBz`` and ``dz = B
# dBz``; ``d_taps[j] = sum (B z)[t - (K - 1) + j] g[t]`` lands as the SiLU
# form's does.  Layout, strips, lane rotations and the unrolled lane-tile
# loop are the SiLU form's, whose code above is untouched by this.

_GATED_TILE_CHANNELS = 256


class GatedPlan(NamedTuple):
    ts: int          # tokens a tile (lanes)
    wc: int          # channels a tile (sublanes), of each of the three


def plan_gated(
    x_shape: Sequence[int], taps_shape: Sequence[int]
) -> Optional[GatedPlan]:
    """The tiling of ``x`` ``[B, S, 3d]`` under ``taps`` ``[K, d]``, or None
    where the kernels cannot tile it: tokens whole lane tiles, ``d`` whole
    row tiles."""
    if len(x_shape) != 3 or len(taps_shape) != 2:
        return None
    k, d = taps_shape
    seq = x_shape[1]
    if x_shape[2] != 3 * d or not 2 <= k < LANES or d % STRIP:
        return None
    wc = next(
        (t for t in range(_GATED_TILE_CHANNELS, 0, -STRIP) if d % t == 0),
        None,
    )
    ts = min(_TILE_TOKENS, seq) // LANES * LANES
    while ts > LANES and seq % ts:
        ts -= LANES
    if wc is None or ts < LANES or seq % ts:
        return None
    return GatedPlan(ts, wc)


def _gated_fwd_kernel(x_ref, before_ref, par_ref, out_ref, *, p, k):
    i = pl.program_id(1)
    masks = _lane_masks(k)
    tiles = p.ts // LANES

    def strip(r, carry):
        rows = _strip_rows(r)
        taps, _ = _columns(par_ref, rows, k, False)

        def tile(n, prev):
            cols = _tile_cols(n)
            cur = x_ref[0, 0, rows, cols].astype(F32) * x_ref[
                0, 2, rows, cols
            ].astype(F32)
            u, _ = _u_of(taps, None, cur, prev, masks)
            out_ref[0, rows, cols] = (
                x_ref[0, 1, rows, cols].astype(F32) * u
            ).astype(out_ref.dtype)
            return cur

        first = jnp.where(
            i > 0,
            before_ref[0, 0, rows, :].astype(F32)
            * before_ref[0, 2, rows, :].astype(F32),
            0.0,
        )
        _for_each(tiles, tile, first)
        return carry

    jax.lax.fori_loop(0, p.wc // STRIP, strip, 0)


def _gated_bwd_kernel(
    x_ref, before_ref, after_ref, dy_ref, dy_after, par_ref,
    dx_ref, acc_ref, gs, *, p, k,
):
    b, i, c = (pl.program_id(a) for a in range(3))
    last = i == pl.num_programs(1) - 1
    tiles = p.ts // LANES
    behind, ahead = _lane_masks(k), _lane_masks(k, ahead=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, (STRIP, LANES), 1)
    zero = jnp.zeros((STRIP, LANES), F32)

    @pl.when((b == 0) & (i == 0) & (c == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def strip(r, carry):
        rows = _strip_rows(r)
        taps, _ = _columns(par_ref, rows, k, False)

        # g = dy C on the tile (and dC = dy u, the taps' sums beside it) ...
        def tile(n, carry):
            prev, sums = carry
            cols = _tile_cols(n)
            cur = x_ref[0, 0, rows, cols].astype(F32) * x_ref[
                0, 2, rows, cols
            ].astype(F32)
            dy = dy_ref[0, rows, cols].astype(F32)
            u, shifted = _u_of(taps, None, cur, prev, behind)
            g = dy * x_ref[0, 1, rows, cols].astype(F32)
            gs[rows, cols] = g
            dx_ref[0, 1, rows, cols] = (dy * u).astype(dx_ref.dtype)
            return cur, [a + s * g for a, s in zip(sums, shifted)]

        first = jnp.where(
            i > 0,
            before_ref[0, 0, rows, :].astype(F32)
            * before_ref[0, 2, rows, :].astype(F32),
            0.0,
        )
        _, sums = _for_each(tiles, tile, (first, [zero] * k))
        # ... and on the lane tile after it, whose first K - 1 tokens the
        # tile's last tokens of dBz take (zeros past a sequence's end)
        gs[rows, _tile_cols(tiles)] = jnp.where(
            ~last,
            dy_after[0, rows, :].astype(F32)
            * after_ref[0, 1, rows, :].astype(F32),
            0.0,
        )
        found = zero
        for j in range(k):
            found = jnp.where(
                lane == j, jnp.sum(sums[j], axis=1, keepdims=True), found
            )
        acc_ref[pl.ds(
            pl.multiple_of(c * p.wc + r * STRIP, STRIP), STRIP
        ), :] += found

        def back(n, carry):
            cols = _tile_cols(n)
            here, after = gs[rows, cols], gs[rows, _tile_cols(n + 1)]
            dbz = sum(
                taps[j] * _shifted(here, after, k - 1 - j, ahead, True)
                for j in range(k)
            )
            dx_ref[0, 0, rows, cols] = (
                x_ref[0, 2, rows, cols].astype(F32) * dbz
            ).astype(dx_ref.dtype)
            dx_ref[0, 2, rows, cols] = (
                x_ref[0, 0, rows, cols].astype(F32) * dbz
            ).astype(dx_ref.dtype)
            return carry

        _for_each(tiles, back)
        return carry

    jax.lax.fori_loop(0, p.wc // STRIP, strip, 0)


def _ranges_first(x):
    """``[B, S, 3d]`` as ``[B, 3, d, S]``."""
    batch, seq, width = x.shape
    return x.transpose(0, 2, 1).reshape(batch, 3, width // 3, seq)


def _gated_specs(p: GatedPlan, seq: int):
    """Index maps of a step ``(b, i, c)`` on ``[B, 3, d, S]``: the tile,
    the lane tiles before and after it; on ``[B, d, S]``: the tile and the
    lane tile after it; a parameter's tile."""
    per, blocks = p.ts // LANES, seq // LANES

    def ahead(i):
        # past the last tile: any block (the kernel zeroes it)
        return jnp.minimum((i + 1) * per, blocks - 1)

    return (
        lambda b, i, c: (b, 0, c, i),
        lambda b, i, c: (b, 0, c, jnp.maximum(i * per - 1, 0)),
        lambda b, i, c: (b, 0, c, ahead(i)),
        lambda b, i, c: (b, c, i),
        lambda b, i, c: (b, c, ahead(i)),
        lambda b, i, c: (c, 0),
    )


@jax.jit
def _gated_forward(x, taps):
    p = plan_gated(x.shape, taps.shape)
    k = taps.shape[0]
    x4, par = _ranges_first(x), _lane_params(taps, None)
    batch, _, d, seq = x4.shape
    tile, before, _, row_tile, _, param = _gated_specs(p, seq)
    y = pl.pallas_call(
        functools.partial(_gated_fwd_kernel, p=p, k=k),
        grid=(batch, seq // p.ts, d // p.wc),
        in_specs=[
            pl.BlockSpec((1, 3, p.wc, p.ts), tile),
            pl.BlockSpec((1, 3, p.wc, LANES), before),
            pl.BlockSpec((p.wc, LANES), param),
        ],
        out_specs=pl.BlockSpec((1, p.wc, p.ts), row_tile),
        out_shape=jax.ShapeDtypeStruct((batch, d, seq), x.dtype),
        compiler_params=_compiler_params(("parallel",) * 3),
        interpret=backend.interpret(), name="gated_conv_fwd",
    )(x4, x4, par)
    return y.transpose(0, 2, 1)


@jax.jit
def _gated_backward(x, taps, dy):
    p = plan_gated(x.shape, taps.shape)
    k = taps.shape[0]
    x4, par = _ranges_first(x), _lane_params(taps, None)
    dy = dy.transpose(0, 2, 1)
    batch, _, d, seq = x4.shape
    tile, before, after, row_tile, row_after, param = _gated_specs(p, seq)
    dx, acc = pl.pallas_call(
        functools.partial(_gated_bwd_kernel, p=p, k=k),
        grid=(batch, seq // p.ts, d // p.wc),
        in_specs=[
            pl.BlockSpec((1, 3, p.wc, p.ts), tile),
            pl.BlockSpec((1, 3, p.wc, LANES), before),
            pl.BlockSpec((1, 3, p.wc, LANES), after),
            pl.BlockSpec((1, p.wc, p.ts), row_tile),
            pl.BlockSpec((1, p.wc, LANES), row_after),
            pl.BlockSpec((p.wc, LANES), param),
        ],
        out_specs=[
            pl.BlockSpec((1, 3, p.wc, p.ts), tile),
            # every channel's sums, one block that stays in VMEM
            pl.BlockSpec((d, LANES), lambda b, i, c: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x4.shape, x.dtype),
            jax.ShapeDtypeStruct((d, LANES), F32),
        ],
        scratch_shapes=[pltpu.VMEM((p.wc, p.ts + LANES), F32)],
        compiler_params=_compiler_params(("arbitrary",) * 3),
        interpret=backend.interpret(), name="gated_conv_bwd",
    )(x4, x4, x4, dy, dy, par)
    d_taps = acc[:, :k].T.astype(taps.dtype)
    return dx.reshape(batch, 3 * d, seq).transpose(0, 2, 1), d_taps


@jax.custom_vjp
def gated_conv(x, taps):
    """``C * conv(B * z)`` of ``x = [B | C | z]`` ``[batch, S, 3d]`` under
    ``taps`` ``[K, d]`` (causal, depthwise, no bias, no activation) through
    the kernels.  Only for a shape :func:`plan_gated` tiles."""
    return _gated_forward(x, taps)


def _gated_vjp_fwd(x, taps):
    return _gated_forward(x, taps), (x, taps)


def _gated_vjp_bwd(res, dy):
    return _gated_backward(*res, dy)


gated_conv.defvjp(_gated_vjp_fwd, _gated_vjp_bwd)
