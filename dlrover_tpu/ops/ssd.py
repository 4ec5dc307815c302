"""Mamba-2's state-space recurrence in its chunked form (the "SSD"
algorithm, Dao & Gu, arXiv:2405.21060 §6-7), as a Pallas kernel pair and
as plain ``jax.numpy``.

Per head ``h`` of ``H``, with inputs ``x_t`` in R^P, a step ``dt_t > 0``,
a log-decay ``a_t = dt_t A_h <= 0`` (``A_h`` one negative scalar a head),
and ``B_t``, ``C_t`` in R^N shared by the heads of a group, the layer
keeps a state ``S`` in R^{P x N}::

    S_t = exp(a_t) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D_h x_t

The transition is a scalar a head: no ``k k^T`` term, so nothing is solved
(``ops/gated_delta_rule.py`` is the rule with one).  The sequence is cut
into chunks of ``Q`` tokens; with ``b_t`` the running sum of ``a`` inside a
chunk and ``S`` the state the chunk starts from::

    L[t, i] = exp(b_t - b_i)  for i <= t, else 0
    Y  = ((C B^T) * L) (dt * X) + exp(b) * (C S^T) + D X
    S' = exp(b_Q) S + (dt * exp(b_Q - b) * X)^T B

``C B^T`` is a group's, computed once for the heads of a grid step.

**What lives where** (``impl="kernel"``).  One kernel runs the forward and
one the backward.  Their grids are (batch, tile of heads, chunk): a TILE is
:func:`heads_per_step` heads of one group, a whole group where it is small
enough (Nemotron-H's 8 heads) and a part of it where it is not
(Granite-4.0-H's one group of 128 heads runs as 16 tiles of 8), and tile
``t`` reads the ``B`` and ``C`` of group ``t // tiles_per_group``.  The
chunk axis is sequential and the float32 state of the tile's heads (the
backward: its cotangent) stays in VMEM scratch as ``S^T`` ``[N, heads x
P]`` from one chunk to the next.  ``x`` and ``y`` are read and written IN
PLACE as ``[B, S, H P]`` (a grid step takes the ``[Q, heads x P]`` columns
of its tile), ``B`` and ``C`` as ``[B, S, G N]``: no transpose is made for
the kernels but of the two per-token scalars ``dt`` and ``a`` (``[B, S,
H]`` float32, 1/64 of ``x``).  Where a group is several tiles, ``C B^T`` is
formed once a tile, and the backward writes each tile's part of ``dB`` and
``dC`` in float32 (``[B, S, tiles x N]``), summed over a group's tiles
outside the kernel.  Heads narrower than the 128 lanes lie side
by side in a lane tile: the products that do not depend on the head's
decay (``C S^T``, ``B^T X``, ``B dS'``) run once a tile, the two that do
(``M_h X``, ``M_h^T dY``) once a head on the whole tile, the head's lanes
selected after.  HBM sees x, B, C, dt, a, D in, y, each chunk's START state
in the operands' dtype and the largest ``|S|`` out; backward: the same
inputs, the start states and ``dy`` in, dx, dB, dC, ddt, da out.  No
``[Q, Q]`` tensor reaches HBM.  ``dD`` is one reduction of ``dy x`` outside
the kernel.

**Precision.**  Products take operands in the inputs' dtype and accumulate
in float32; ``dt``, ``a``, its running sums, every ``exp`` and the state
are float32.  With float32 operands (the CPU tests) every product is
float32.

``impl="xla"`` is the same chunked form in ``jax.numpy`` under autodiff: the
path of a backend without the kernels' tiling, and what the tests hold the
kernels to.
"""

from __future__ import annotations

import functools
import types
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops import backend
from dlrover_tpu.ops.row_gather_sum import tile_rows

F32 = jnp.float32
LANES = 128
IMPLS = ("xla", "kernel")

# dot_general's dimension numbers: x y, x y^T, x^T y
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(x, y, dims=_NN):
    """Operands as they are, float32 accumulation; float32 operands (the
    CPU tests) multiply in full precision."""
    return jax.lax.dot_general(
        x, y, dims, preferred_element_type=F32,
        precision=jax.lax.Precision.HIGHEST if x.dtype == F32 else None,
    )


def _to_col(x_row, eye):
    """[1, Q] -> [Q, 1], through the diagonal of a [Q, Q]."""
    return jnp.sum(jnp.where(eye, x_row, 0.0), axis=1, keepdims=True)


def _to_row(x_col, eye):
    return jnp.sum(jnp.where(eye, x_col, 0.0), axis=0, keepdims=True)


def _masks(q):
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return col <= row, col == row


def _head_terms(a_row, dt_row, lower, eye):
    """What one head's chunk builds from its ``a`` and ``dt`` rows [1, Q]
    (float32), forward and backward alike.  A vector indexed by the token
    comes as a column [Q, 1] where it scales rows."""
    q = a_row.shape[-1]
    b_col = jnp.sum(jnp.where(lower, a_row, 0.0), axis=1, keepdims=True)
    total = b_col[q - 1:, :]                              # b_Q  [1, 1]
    # exp(b_t - b_i) where i <= t; the other half would overflow
    decay = jnp.exp(
        jnp.where(lower, b_col - _to_row(b_col, eye), -jnp.inf)
    )
    return types.SimpleNamespace(
        decay=decay, dt=_to_col(dt_row, eye), from_start=jnp.exp(b_col),
        to_end=jnp.exp(total - b_col), whole=jnp.exp(total),
    )


def _by_lane(values, lane_head):
    """One lane tile out of its heads' columns (or scalars): lanes of head
    ``j`` take ``values[j]``."""
    out = values[0]
    for j in range(1, len(values)):
        out = jnp.where(lane_head == j, values[j], out)
    return out


def _tile_terms(a_ref, dt_ref, first, count, lower, eye, lane_head):
    heads = [
        _head_terms(a_ref[first + j, 0], dt_ref[first + j, 0], lower, eye)
        for j in range(count)
    ]
    return heads, types.SimpleNamespace(**{
        name: _by_lane([getattr(h, name) for h in heads], lane_head)
        for name in ("dt", "from_start", "to_end", "whole")
    })


def _fwd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, start_ref, top_ref,
    state, *, heads, head_dim,
):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)
        top_ref[...] = jnp.zeros_like(top_ref)

    bm, cm = b_ref[0], c_ref[0]                           # [Q, N]
    cd = bm.dtype
    lower, eye = _masks(bm.shape[0])
    scores = _dot(cm, bm, _NT)                            # C B^T, the group's
    per_tile = LANES // head_dim
    lane_head = jax.lax.broadcasted_iota(
        jnp.int32, (1, LANES), 1
    ) // head_dim
    top = jnp.zeros((1, 1), F32)
    for tile in range(heads // per_tile):
        lanes = pl.ds(tile * LANES, LANES)
        each, t = _tile_terms(
            a_ref, dt_ref, tile * per_tile, per_tile, lower, eye, lane_head
        )
        x = x_ref[0, :, lanes].astype(F32)                # [Q, 128]
        x_dt = x * t.dt
        x_dt_cd = x_dt.astype(cd)
        start = state[:, lanes]                           # S^T  [N, 128]
        start_cd = start.astype(cd)
        start_ref[0, 0, :, lanes] = start_cd
        within = _by_lane(
            [_dot((scores * h.decay).astype(cd), x_dt_cd) for h in each],
            lane_head,
        )
        y = within + t.from_start * _dot(cm, start_cd) + d_ref[:, lanes] * x
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        end = t.whole * start + _dot(bm, (x_dt * t.to_end).astype(cd), _TN)
        state[:, lanes] = end
        top = jnp.maximum(top, jnp.max(
            jnp.max(jnp.abs(end), axis=1, keepdims=True), axis=0,
            keepdims=True,
        ))
    top_ref[0] = jnp.maximum(top_ref[0], top)


def _bwd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, start_ref, dy_ref,
    dx_ref, ddt_ref, da_ref, db_ref, dc_ref, d_state, *, heads, head_dim,
):
    """One chunk, walked last to first.  ``d_state`` holds the cotangent
    of the chunk's END state on entry and of its start state on exit."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    bm, cm = b_ref[0], c_ref[0]
    cd = bm.dtype
    lower, eye = _masks(bm.shape[0])
    scores = _dot(cm, bm, _NT)
    per_tile = LANES // head_dim
    lane_head = jax.lax.broadcasted_iota(
        jnp.int32, (1, LANES), 1
    ) // head_dim
    d_scores = jnp.zeros_like(scores)
    d_b = jnp.zeros(bm.shape, F32)
    d_c = jnp.zeros(cm.shape, F32)

    def head_sums(v, axis0=False):
        """Each head's own sum over its lanes of ``v`` [., 128]: columns
        [., 1] (``axis0``: over the rows too, [1, 1])."""
        out = []
        for j in range(per_tile):
            s = jnp.sum(
                jnp.where(lane_head == j, v, 0.0), axis=1, keepdims=True
            )
            out.append(jnp.sum(s, axis=0, keepdims=True) if axis0 else s)
        return out

    for tile in range(heads // per_tile):
        lanes = pl.ds(tile * LANES, LANES)
        first = tile * per_tile
        each, t = _tile_terms(
            a_ref, dt_ref, first, per_tile, lower, eye, lane_head
        )
        x = x_ref[0, :, lanes].astype(F32)
        dy_cd = dy_ref[0, :, lanes]
        dy = dy_cd.astype(F32)
        x_dt = x * t.dt
        x_dt_cd = x_dt.astype(cd)
        x_end_cd = (x_dt * t.to_end).astype(cd)
        start_cd = start_ref[0, 0, :, lanes]              # S^T  [N, 128]
        d_end = d_state[:, lanes]
        d_end_cd = d_end.astype(cd)
        # Y = M (dt X) + from_start * (C S^T) + D X
        # S' = whole S + (dt to_end X)^T B
        c_start = _dot(cm, start_cd)                      # [Q, 128]
        b_d_end = _dot(bm, d_end_cd)                      # [Q, 128]
        dy_start_cd = (dy * t.from_start).astype(cd)
        d_c = d_c + _dot(dy_start_cd, start_cd, _NT)
        d_b = d_b + _dot(x_end_cd, d_end_cd, _NT)
        d_state[:, lanes] = t.whole * d_end + _dot(cm, dy_start_cd, _TN)
        d_x_dt = _by_lane(
            [_dot((scores * h.decay).astype(cd), dy_cd, _TN) for h in each],
            lane_head,
        ) + t.to_end * b_d_end
        d_from_start = head_sums(dy * c_start)
        d_to_end = head_sums(x_dt * b_d_end)
        d_whole = head_sums(d_end * start_cd.astype(F32), axis0=True)
        d_dt = head_sums(d_x_dt * x)
        for j, h in enumerate(each):
            own = lane_head == j
            d_within = jnp.where(lower, _dot(
                jnp.where(own, dy, 0.0).astype(cd), x_dt_cd, _NT
            ), 0.0)
            d_scores = d_scores + d_within * h.decay
            # decay[t, i] = exp(b_t - b_i), from_start = exp(b),
            # to_end = exp(b_Q - b), whole = exp(b_Q)
            d_decay = d_within * scores * h.decay
            d_b_end = d_to_end[j] * h.to_end
            d_b_col = (
                jnp.sum(d_decay, axis=1, keepdims=True)
                + d_from_start[j] * h.from_start - d_b_end
                - _to_col(jnp.sum(d_decay, axis=0, keepdims=True), eye)
            )
            d_total = (
                jnp.sum(d_b_end, axis=0, keepdims=True)
                + d_whole[j] * h.whole
            )
            # b = cumsum(a):  da_t = the sum of db_j over j >= t; b_Q holds all
            da_ref[first + j, 0] = d_total + jnp.sum(
                jnp.where(lower, d_b_col, 0.0), axis=0, keepdims=True
            )
            ddt_ref[first + j, 0] = _to_row(d_dt[j], eye)
        dx_ref[0, :, lanes] = (
            d_x_dt * t.dt + d_ref[:, lanes] * dy
        ).astype(dx_ref.dtype)
    d_scores_cd = d_scores.astype(cd)
    dc_ref[0] = (d_c + _dot(d_scores_cd, bm)).astype(dc_ref.dtype)
    db_ref[0] = (d_b + _dot(d_scores_cd, cm, _TN)).astype(db_ref.dtype)


def _grid(x, dt, b, groups, head_dim):
    """The grid ``(batch, tiles, n)`` of these operands and a grid step's
    sizes."""
    batch, _, total = x.shape
    n, chunk = dt.shape[1], dt.shape[-1]
    state = b.shape[-1] // groups
    heads = heads_per_step(
        total // head_dim, head_dim, groups, state, chunk, x.dtype
    )
    width = heads * head_dim
    tiles = total // width
    return types.SimpleNamespace(
        grid=(batch, tiles, n), tiles=tiles, per_group=tiles // groups,
        heads=heads, width=width, state=state, chunk=chunk, n=n,
    )


def _specs(g, reverse):
    """Block specs of one grid step (batch, tile of heads, chunk) of the
    grid ``g`` (:func:`_grid`): the tile's columns of x-like and D-like
    operands and its per-token rows, its group's columns of B and C
    (``bc``), and a tile's own columns of a B-like output (``bc_part``: the
    backward's partial dB, dC where a group is several tiles)."""
    def at(c):
        return g.n - 1 - c if reverse else c

    def group(t):
        return t if g.per_group == 1 else t // g.per_group

    return types.SimpleNamespace(
        x=pl.BlockSpec((1, g.chunk, g.width), lambda i, t, c: (i, at(c), t)),
        bc=pl.BlockSpec(
            (1, g.chunk, g.state), lambda i, t, c: (i, at(c), group(t))
        ),
        bc_part=pl.BlockSpec(
            (1, g.chunk, g.state), lambda i, t, c: (i, at(c), t)
        ),
        token=pl.BlockSpec(
            (g.heads, 1, 1, g.chunk),
            lambda i, t, c: (i * g.tiles + t, at(c), 0, 0),
        ),
        d=pl.BlockSpec((1, g.width), lambda i, t, c: (0, t)),
        start=pl.BlockSpec(
            (1, 1, g.state, g.width),
            lambda i, t, c: (i * g.tiles + t, at(c), 0, 0),
        ),
    )


@functools.partial(jax.jit, static_argnames=("groups", "head_dim"))
def _forward(x, dt, a, b, c, d, *, groups, head_dim):
    """``x`` [B, S, H P]; ``dt``, ``a`` [B H, n, 1, Q] float32; ``b``, ``c``
    [B, S, G N]; ``d`` [1, H P] float32 (a head's ``D`` on its lanes).
    Returns ``y`` [B, S, H P], the chunks' start states ``S^T`` [B tiles, n,
    N, heads P] (both in ``x``'s dtype) and each (batch, tile)'s largest
    ``|S|`` at a chunk's end [B tiles] (float32).  (Jitted, as the backward is,
    so that a step which runs the scan in several slots, forward, recomputed
    and transposed, traces and lowers each kernel body once.)"""
    g = _grid(x, dt, b, groups, head_dim)
    batch, tiles = g.grid[:2]
    sp = _specs(g, False)
    y, starts, top = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=g.heads, head_dim=head_dim),
        grid=g.grid,
        in_specs=[sp.x, sp.token, sp.token, sp.bc, sp.bc, sp.d],
        out_specs=[
            sp.x, sp.start,
            pl.BlockSpec(
                (1, 1, LANES), lambda i, t, c: (i * tiles + t, 0, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(
                (batch * tiles, g.n, g.state, g.width), x.dtype
            ),
            jax.ShapeDtypeStruct((batch * tiles, 1, LANES), F32),
        ],
        scratch_shapes=[pltpu.VMEM((g.state, g.width), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=backend.interpret(),
        name="ssd_fwd",
    )(x, dt, a, b, c, d)
    return y, starts, top[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("groups", "head_dim"))
def _backward(x, dt, a, b, c, d, starts, dy, *, groups, head_dim):
    g = _grid(x, dt, b, groups, head_dim)
    batch, tiles = g.grid[:2]
    sp = _specs(g, True)
    # a group of several tiles: each writes its own part of dB and dC
    split = g.per_group > 1
    part = jax.ShapeDtypeStruct(
        (batch, x.shape[1], tiles * g.state), F32
    )
    dx, ddt, da, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=g.heads, head_dim=head_dim),
        grid=g.grid,
        in_specs=[
            sp.x, sp.token, sp.token, sp.bc, sp.bc, sp.d, sp.start, sp.x,
        ],
        out_specs=[sp.x, sp.token, sp.token] + (
            [sp.bc_part] * 2 if split else [sp.bc] * 2
        ),
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(dt.shape, F32),
            jax.ShapeDtypeStruct(a.shape, F32),
        ] + (
            [part, part] if split else [
                jax.ShapeDtypeStruct(b.shape, b.dtype),
                jax.ShapeDtypeStruct(c.shape, c.dtype),
            ]
        ),
        scratch_shapes=[pltpu.VMEM((g.state, g.width), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=backend.interpret(),
        name="ssd_bwd",
    )(x, dt, a, b, c, d, starts, dy)
    if split:
        def over_tiles(parts, like):
            return parts.reshape(
                *parts.shape[:2], groups, g.per_group, g.state
            ).sum(axis=3).reshape(like.shape).astype(like.dtype)

        db, dc = over_tiles(db, b), over_tiles(dc, c)
    return dx, ddt, da, db, dc


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, dt, a, b, c, d, groups, head_dim):
    return _scan_fwd(x, dt, a, b, c, d, groups, head_dim)[0]


def _scan_fwd(x, dt, a, b, c, d, groups, head_dim):
    y, starts, top = _forward(
        x, dt, a, b, c, d, groups=groups, head_dim=head_dim
    )
    return (y, top), (x, dt, a, b, c, d, starts)


def _scan_bwd(groups, head_dim, res, cts):
    dy, _ = cts          # the largest |S| is a reading, not a result
    x, dt, a, b, c, d, starts = res
    dx, ddt, da, db, dc = _backward(
        x, dt, a, b, c, d, starts, dy, groups=groups, head_dim=head_dim
    )
    dd = jnp.sum(
        dy.astype(F32) * x.astype(F32), axis=(0, 1)
    )[None].astype(d.dtype)
    return dx, ddt, da, db, dc, dd


_scan.defvjp(_scan_fwd, _scan_bwd)


# What a grid step may plan to hold in VMEM (a v5e's scoped default is
# 16 MiB, the blocks double-buffered), and the heads one kernel body
# unrolls: every head is a ``[Q, Q]`` decay and two products written out
# in the body, and bodies of more than 8 heads have compiled for minutes.
_VMEM_BUDGET = 12 << 20
_MAX_HEADS = 8


def step_vmem_bytes(
    heads: int, head_dim: int, state: int, chunk: int, dtype
) -> int:
    """VMEM a grid step of ``heads`` heads plans for, by the backward
    kernel (the larger): the double-buffered blocks (x, dy, dx tiles, the
    chunk's start state, B, C, dB, dC, and the per-token rows of dt, a,
    ddt, da, each padded to 8 sublanes), the float32 state scratch, and
    the body's float32 terms: ``C B^T`` and its cotangent, four ``[Q, Q]``
    a head of the lane tile in hand, a dozen ``[Q, 128]`` tiles."""
    size = jnp.dtype(dtype).itemsize
    width = heads * head_dim
    blocks = 2 * (
        3 * chunk * width * size + state * width * size
        + 2 * chunk * state * size + 2 * chunk * state * 4
        + 4 * heads * 8 * chunk * 4
    )
    terms = (
        (2 + 4 * (LANES // head_dim)) * chunk * chunk + 12 * chunk * LANES
    ) * 4
    return blocks + state * width * 4 + terms


def heads_per_step(
    heads: int, head_dim: int, groups: int, state: int = 128,
    chunk: int = 128, dtype=jnp.bfloat16,
) -> int:
    """Heads one grid step of the kernels holds (the grid's second axis is
    ``heads // heads_per_step`` tiles): the most heads of ONE group, in
    whole 128-lane tiles, that :func:`step_vmem_bytes` puts within the
    VMEM budget and a body may unroll; 0 where the lane layout does not
    hold the sizes (``head_dim`` must divide 128, a group be whole lane
    tiles wide) or not even one lane tile fits."""
    if heads % groups or LANES % head_dim:
        return 0
    per_group, per_tile = heads // groups, LANES // head_dim
    if per_group % per_tile:
        return 0
    most = max(per_tile, min(per_group, _MAX_HEADS))
    fitting = [
        n for n in range(per_tile, most + 1, per_tile)
        if per_group % n == 0
        and step_vmem_bytes(n, head_dim, state, chunk, dtype) <= _VMEM_BUDGET
    ]
    return max(fitting, default=0)


def kernel_fits(
    heads: int, head_dim: int, groups: int, state: int = 128,
    chunk: int = 128, dtype=jnp.bfloat16,
) -> bool:
    """Whether the kernels hold these sizes: :func:`heads_per_step` finds
    a tile of heads."""
    return heads_per_step(heads, head_dim, groups, state, chunk, dtype) > 0


def _ssd_kernel(x, dt, a, b, c, d, chunk):
    batch, s, heads, head_dim = x.shape
    groups, state = b.shape[2], b.shape[3]
    n = s // chunk

    def per_token(v):
        """[B, S, H] float32 -> [B H, n, 1, Q]"""
        return jnp.moveaxis(v, 2, 1).reshape(batch * heads, n, 1, chunk)

    y, top = _scan(
        x.reshape(batch, s, heads * head_dim), per_token(dt), per_token(a),
        b.reshape(batch, s, groups * state),
        c.reshape(batch, s, groups * state),
        jnp.repeat(d.astype(F32), head_dim)[None], groups, head_dim,
    )
    return y.reshape(x.shape), jnp.max(top)


def _ssd_xla(x, dt, a, b, c, d, chunk):
    """The chunked form, a chunk at a time under ``lax.scan``."""
    cd = x.dtype
    batch, s, heads, head_dim = x.shape
    groups, state = b.shape[2], b.shape[3]
    n, per_group = s // chunk, heads // groups
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None]

    def chunks(v):
        """[B, S, ...] -> [n, B, Q, ...]"""
        return jnp.moveaxis(
            v.reshape(batch, n, chunk, *v.shape[2:]), 1, 0
        )

    def by_head(v):
        """[B, Q, G, N] -> [B, Q, H, N]: a group's row for each head."""
        return jnp.repeat(v, per_group, axis=2)

    def mm(spec, u, v):
        return jnp.einsum(
            spec, u, v, preferred_element_type=F32,
            precision=jax.lax.Precision.HIGHEST if cd == F32 else None,
        )

    def one(carry, xs):
        start, top = carry                                # [B, H, P, N] f32
        x_c, dt_c, a_c, b_c, c_c = xs
        sums = jnp.cumsum(a_c, axis=1)                    # b_t  [B, Q, H]
        total = sums[:, -1]
        decay = jnp.exp(jnp.where(
            lower, sums[:, :, None] - sums[:, None], -jnp.inf
        ))                                                # [B, t, i, H]
        scores = mm("btgn,bign->btig", c_c, b_c)          # C B^T, a group's
        within = (jnp.repeat(scores, per_group, axis=3) * decay).astype(cd)
        x32 = x_c.astype(F32)
        x_dt = x32 * dt_c[..., None]
        y = (
            mm("btih,bihp->bthp", within, x_dt.astype(cd))
            + jnp.exp(sums)[..., None] * mm(
                "bthn,bhpn->bthp", by_head(c_c), start.astype(cd)
            )
            + d.astype(F32)[:, None] * x32
        )
        to_end = jnp.exp(total[:, None] - sums)[..., None]
        end = jnp.exp(total)[..., None, None] * start + mm(
            "bthp,bthn->bhpn", (x_dt * to_end).astype(cd), by_head(b_c)
        )
        return (end, jnp.maximum(top, jnp.abs(end).max())), y.astype(cd)

    zeros = jnp.zeros((batch, heads, head_dim, state), F32)
    (_, top), y = jax.lax.scan(
        one, (zeros, jnp.zeros((), F32)),
        tuple(chunks(v) for v in (x, dt, a, b, c)),
    )
    return jnp.moveaxis(y, 0, 1).reshape(x.shape), top


def ssd(
    x: jax.Array,
    dt: jax.Array,
    a_head: jax.Array,
    b: jax.Array,
    c: jax.Array,
    d: jax.Array,
    chunk: int = 128,
    impl: str = "kernel",
) -> Tuple[jax.Array, jax.Array]:
    """``x`` [B, S, H, P]; ``dt`` [B, S, H] float32, after its softplus;
    ``a_head`` [H] float32, negative (``-exp(A_log)``); ``b``, ``c``
    [B, S, G, N] in ``x``'s dtype, head ``h`` reading group ``h // (H /
    G)``; ``d`` [H].  Returns ``y`` [B, S, H, P] in ``x``'s dtype and,
    under ``stop_gradient``, the largest ``|S|`` entry at any chunk boundary
    (float32 scalar).

    A sequence that is no whole number of chunks is padded with tokens
    that neither write nor decay (``dt`` 0).  ``chunk`` is whole tiles of
    the operands' dtype."""
    cd = x.dtype
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if chunk % tile_rows(cd):
        raise ValueError(
            f"chunk must be a multiple of the {tile_rows(cd)} rows of a "
            f"{jnp.dtype(cd).name} tile, got {chunk}"
        )
    if b.dtype != cd or c.dtype != cd:
        raise ValueError(
            f"x, b, c must share a dtype, got {cd}, {b.dtype}, {c.dtype}"
        )
    heads, head_dim, groups = x.shape[2], x.shape[3], b.shape[2]
    if impl == "kernel" and not kernel_fits(
        heads, head_dim, groups, b.shape[3], chunk, cd
    ):
        raise ValueError(
            f"the kernels lay heads side by side in {LANES}-lane tiles: "
            f"head_dim {head_dim} must divide {LANES}, a group's "
            f"{heads}/{groups} heads be whole tiles wide and one tile's "
            f"chunk of {chunk} with its state of {b.shape[3]} fit VMEM "
            "(heads_per_step); impl='xla' takes any sizes"
        )
    s = x.shape[1]
    pad = -s % chunk

    def padded(v):
        return jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))

    dt = dt.astype(F32)
    a = dt * a_head.astype(F32)
    run = _ssd_kernel if impl == "kernel" else _ssd_xla
    y, top = run(
        padded(x), padded(dt), padded(a), padded(b), padded(c), d, chunk
    )
    return y[:, :s], jax.lax.stop_gradient(top)
