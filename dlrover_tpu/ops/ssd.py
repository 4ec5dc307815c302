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
one the backward.  Their grids are (batch, group, chunk, tile of the
group's heads): a TILE is :func:`heads_per_step` heads of one group, a
whole group where it is small enough (Nemotron-H's 8 heads: the last axis
is 1) and a part of it where it is not (Granite-4.0-H's one group of 128
heads runs as 16 tiles of 8).  The chunk axis is sequential, the tile axis
inside it, and the float32 state of ALL the group's tiles (the backward:
its cotangent) stays in VMEM scratch as ``S^T`` ``[tiles, N, heads x P]``
from one chunk to the next.  ``x`` and ``y`` are read and written IN PLACE
as ``[B, S, H P]`` (a grid step takes the ``[Q, heads x P]`` columns of
its tile), ``B`` and ``C`` TRANSPOSED, as ``[B, G N, S]`` (a block is
``B^T`` ``[N, Q]``: what a convolution kernel that keeps the tokens on the
lanes hands over, so its transpose and this one cancel and XLA copies
neither; ``dB`` and ``dC`` go back the same way).  A group's block is the
same for all its tiles, so it is fetched once a (batch, chunk); ``C B^T``
and the token-major ``B``, ``C`` are formed at the group's first tile into
scratch (:func:`_at_first`), and the backward adds the tiles' parts of
``dB^T`` and ``dC^T`` in float32 scratch and writes them once, at the last
tile, in the operands' dtype.  No other transpose is made for the kernels
but of the two per-token scalars: ``dt`` and the running sums ``b`` of
``a`` inside each chunk come as rows ``[B, n, H, Q]`` float32 (1/64 of
``x``; ``b`` from one product with the triangle outside the kernels, whose
transpose turns the backward's ``db`` into ``da``).

What depends on a head's decay is built ONCE A GRID STEP from those rows:
``exp(b)``, ``exp(b_Q - b)`` on ``[heads, Q]``; a head's column of ``b``
for its ``[Q, Q]`` decay is its row laid on every sublane and transposed,
and a lane tile's ``[Q, 128]`` factors likewise (no masked lane reduction
and no ``[Q, 1]`` column anywhere).  Heads narrower than the 128 lanes lie
side by side in a lane tile: the products that do not depend on the head's
decay (``C S^T``, ``B dS'``) run once a tile, those that do (``M_h X``,
``(B^T * to_end_h) X``, ``M_h^T dY``) once a head on the whole tile, the
head's lanes selected after.  The backward builds ``M^T`` directly, so
``M^T dY`` needs no transpose and the sum of ``dM M`` over a row of ``M``
is a sum down the sublanes; the sum over a column is ``<dt X, M^T dY>``
with the same rounded operands, a head's lanes of a transposed tile; and
``d b_Q = <dS', S'>``.  ``dD``'s ``sum_t dy x`` is added up in the backward
kernel, in a float32 block that stays in VMEM over a (batch, group)'s
chunks; the batch and a head's P lanes are summed outside.  HBM sees x,
dt, b, B, C, D in, y, each chunk's START state in the operands' dtype and
the largest ``|S|`` a lane out; backward: the same inputs, the start
states and ``dy`` in, dx, dB, dC, ddt, db, dD out.  No ``[Q, Q]`` tensor
reaches HBM.

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

# dot_general's dimension numbers: x y, x y^T
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))


def _dot(x, y, dims=_NN):
    """Operands as they are, float32 accumulation; float32 operands (the
    CPU tests) multiply in full precision."""
    return jax.lax.dot_general(
        x, y, dims, preferred_element_type=F32,
        precision=jax.lax.Precision.HIGHEST if x.dtype == F32 else None,
    )


def _triangles(q):
    """``upper`` [i, t] true where ``i <= t`` (it masks ``M^T``) and its
    transpose ``lower`` (it masks ``M``)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return row <= col, col <= row


def _when(condition):
    """``pl.when`` that a condition known while tracing (a group of one
    tile: every tile is its group's first and last) settles there."""
    if isinstance(condition, bool):
        return (lambda body: body()) if condition else (lambda body: None)
    return pl.when(condition)


def _at_first(first, ref, make):
    """What ``make()`` gives, made once a group: at the group's first tile
    into the scratch ``ref`` and read from there by every tile; a group of
    one tile makes it in place."""
    if first is True:
        return make()

    @pl.when(first)
    def _():
        ref[...] = make()

    return ref[...]


def _step_terms(sums_ref, dt_ref):
    """What a grid step's heads build from the running sums ``b`` of ``a``
    and from ``dt``, forward and backward alike, ONCE for the tile of heads
    and as ROWS ``[heads, Q]`` (float32): one ``exp`` each on ``heads x Q``
    numbers."""
    b, dt = sums_ref[0, 0], dt_ref[0, 0]
    to_end = jnp.exp(b[:, b.shape[1] - 1:] - b)           # exp(b_Q - b)
    return types.SimpleNamespace(
        b=b, dt=dt, from_start=jnp.exp(b), to_end=to_end,
    )


def _down(row, q):
    """A head's row [1, Q] as the column it is, on every lane: [Q, Q]
    whose entry [t, i] is ``row[t]`` (a transpose of the row laid on
    every sublane: the unit that transposes is far cheaper than a lane
    reduction or a lane broadcast a head)."""
    return jnp.broadcast_to(row, (q, row.shape[1])).T


def _spread(rows, first, count, head_dim):
    """One lane tile [Q, 128] out of the rows [heads, Q] of its ``count``
    heads: head ``first + j``'s value of token ``t`` at ``[t, j head_dim
    : (j + 1) head_dim]``."""
    q = rows.shape[1]
    return jnp.concatenate([
        jnp.broadcast_to(rows[first + j:first + j + 1], (head_dim, q))
        for j in range(count)
    ], axis=0).T


def _head_rows(tile, count, head_dim):
    """Each head's own sum over its lanes of ``tile`` [Q, 128], as ROWS
    [1, Q]: the tile transposed, a head's lanes are sublanes and their sum
    is plain adds."""
    tall = tile.T                                         # [128, Q]
    return [
        jnp.sum(
            tall[j * head_dim:(j + 1) * head_dim], axis=0, keepdims=True
        )
        for j in range(count)
    ]


def _stack(rows, like):
    """``[heads, Q]`` out of each head's [1, Q] (or [1, 1])."""
    sub = jax.lax.broadcasted_iota(jnp.int32, like.shape, 0)
    out = jnp.zeros(like.shape, F32)
    for j, r in enumerate(rows):
        out = jnp.where(sub == j, r, out)
    return out


def _by_lane(values, lane_head):
    """One lane tile out of its heads' tiles (or rows): lanes of head
    ``j`` take ``values[j]``."""
    out = values[0]
    for j in range(1, len(values)):
        out = jnp.where(lane_head == j, values[j], out)
    return out


def _fwd_kernel(
    x_ref, dt_ref, sums_ref, b_ref, c_ref, d_ref, y_ref, top_ref, start_ref,
    state, scores_ref, c_tok, *, heads, head_dim, per_group,
):
    chunk, tile_of_group = pl.program_id(2), pl.program_id(3)
    first = True if per_group == 1 else tile_of_group == 0

    @pl.when(chunk == 0)
    def _():
        state[tile_of_group] = jnp.zeros(state.shape[1:], F32)

        @_when(first)
        def _():
            top_ref[...] = jnp.zeros_like(top_ref)

    bm_t = b_ref[0]                                       # B^T  [N, Q]
    cd = bm_t.dtype
    q = bm_t.shape[1]
    _, lower = _triangles(q)

    cm = _at_first(first, c_tok, lambda: c_ref[0].T)      # C  [Q, N]
    # C B^T, the group's
    scores = _at_first(first, scores_ref, lambda: _dot(cm, bm_t))
    bm_t = bm_t.astype(F32)
    terms = _step_terms(sums_ref, dt_ref)
    to_end_dt = terms.to_end * terms.dt
    whole = terms.from_start[:, q - 1:]                   # exp(b_Q)  [heads, 1]
    per_tile = LANES // head_dim
    lane_head = jax.lax.broadcasted_iota(
        jnp.int32, (1, LANES), 1
    ) // head_dim
    top = jnp.zeros((1, LANES), F32)
    for tile in range(heads // per_tile):
        lanes = pl.ds(tile * LANES, LANES)
        here = range(tile * per_tile, (tile + 1) * per_tile)
        from_start = _spread(terms.from_start, here[0], per_tile, head_dim)
        x_cd = x_ref[0, :, lanes]                         # [Q, 128]
        x = x_cd.astype(F32)
        start = state[tile_of_group, :, lanes]            # S^T  [N, 128]
        start_cd = start.astype(cd)
        start_ref[0, 0, :, lanes] = start_cd
        within, added = [], []
        for h in here:
            # what the chunk adds to the state: (B^T * dt to_end) X, the
            # head's row on B^T's lanes (no column of it is needed)
            added.append(_dot(
                (bm_t * to_end_dt[h:h + 1]).astype(cd), x_cd
            ))
            # exp(b_t - b_i) where i <= t; the other half would overflow
            decay = jnp.exp(jnp.where(
                lower, _down(terms.b[h:h + 1], q) - terms.b[h:h + 1],
                -jnp.inf,
            ))
            within.append(_dot(
                (scores * decay * terms.dt[h:h + 1]).astype(cd), x_cd
            ))
        y = (
            _by_lane(within, lane_head)
            + from_start * _dot(cm, start_cd) + d_ref[:, lanes] * x
        )
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        end = _by_lane(
            [whole[h:h + 1] for h in here], lane_head
        ) * start + _by_lane(added, lane_head)
        state[tile_of_group, :, lanes] = end
        top = jnp.maximum(top, jnp.max(jnp.abs(end), axis=0, keepdims=True))
    top_ref[0] = jnp.maximum(top_ref[0], top)


def _bwd_kernel(
    x_ref, dt_ref, sums_ref, b_ref, c_ref, d_ref, start_ref, dy_ref,
    dx_ref, ddt_ref, dsums_ref, db_ref, dc_ref, dd_ref,
    d_state, scores_ref, d_scores, d_b, d_c, b_tok, c_tok, *, heads, head_dim,
    per_group,
):
    """One chunk, walked last to first.  ``d_state`` holds the cotangent
    of the chunk's END state on entry and of its start state on exit.
    The ``[Q, Q]`` terms are built TRANSPOSED (``[i, t]``): ``M^T dY``
    needs no transpose then, and the sums over ``i`` that ``da`` needs are
    sums down the sublanes."""
    chunk, tile_of_group = pl.program_id(2), pl.program_id(3)
    first = True if per_group == 1 else tile_of_group == 0
    last = True if per_group == 1 else tile_of_group == per_group - 1

    @pl.when(chunk == 0)
    def _():
        d_state[tile_of_group] = jnp.zeros(d_state.shape[1:], F32)
        dd_ref[0, tile_of_group] = jnp.zeros(dd_ref.shape[2:], F32)

    bm_t, cm_t = b_ref[0], c_ref[0]                       # B^T, C^T  [N, Q]
    cd = bm_t.dtype
    q = bm_t.shape[1]
    upper, _ = _triangles(q)

    bm = _at_first(first, b_tok, lambda: bm_t.T)          # B, C  [Q, N]
    cm = _at_first(first, c_tok, lambda: cm_t.T)
    # (C B^T)^T
    scores_t = _at_first(first, scores_ref, lambda: _dot(bm, cm_t))

    @_when(first)
    def _():
        d_scores[...] = jnp.zeros_like(d_scores)
        d_b[...] = jnp.zeros_like(d_b)
        d_c[...] = jnp.zeros_like(d_c)

    terms = _step_terms(sums_ref, dt_ref)
    per_tile = LANES // head_dim
    lane_head = jax.lax.broadcasted_iota(
        jnp.int32, (1, LANES), 1
    ) // head_dim
    d_dt, d_sums, d_total = [], [], []
    for tile in range(heads // per_tile):
        lanes = pl.ds(tile * LANES, LANES)
        here = range(tile * per_tile, (tile + 1) * per_tile)
        sums = _spread(terms.b, here[0], per_tile, head_dim)
        dt = _spread(terms.dt, here[0], per_tile, head_dim)
        from_start = jnp.exp(sums)
        to_end = jnp.exp(sums[q - 1:] - sums)
        x = x_ref[0, :, lanes].astype(F32)
        dy_cd = dy_ref[0, :, lanes]
        dy = dy_cd.astype(F32)
        x_dt = x * dt
        # dt X as the products see it
        x_dt_seen = x_dt.astype(cd).astype(F32)
        x_end = x_dt * to_end
        x_end_cd = x_end.astype(cd)
        start_cd = start_ref[0, 0, :, lanes]              # S^T  [N, 128]
        d_end = d_state[tile_of_group, :, lanes]
        d_end_cd = d_end.astype(cd)
        # Y = M (dt X) + from_start * (C S^T) + D X
        # S' = whole S + (dt to_end X)^T B
        c_start = _dot(cm, start_cd)                      # [Q, 128]
        b_d_end = _dot(bm, d_end_cd)                      # [Q, 128]
        dy_start = dy * from_start
        dy_start_cd = dy_start.astype(cd)
        d_c[...] += _dot(start_cd, dy_start_cd, _NT)      # dC^T  [N, Q]
        d_b[...] += _dot(d_end_cd, x_end_cd, _NT)
        whole = from_start[q - 1:]                        # exp(b_Q)  [1, 128]
        d_state[tile_of_group, :, lanes] = whole * d_end + _dot(
            cm_t, dy_start_cd
        )
        d_x_dt, d_rows = [], []
        for j, h in enumerate(here):
            # [i, t]: exp(b_t - b_i) where i <= t, M^T beside it
            decay_t = jnp.exp(jnp.where(
                upper, terms.b[h:h + 1] - _down(terms.b[h:h + 1], q),
                -jnp.inf,
            ))
            m_t = (scores_t * decay_t).astype(cd)
            d_x_dt.append(_dot(m_t, dy_cd))
            d_m_t = jnp.where(upper, _dot(
                jnp.where(lane_head == j, x_dt_seen, 0.0).astype(cd), dy_cd,
                _NT,
            ), 0.0)
            d_scores[...] += d_m_t * decay_t
            # b_t moves row t of M: the sum over i of dM M, down the
            # sublanes here
            d_rows.append(jnp.sum(
                d_m_t * m_t.astype(F32), axis=0, keepdims=True
            ))
        within = _by_lane(d_x_dt, lane_head)              # M^T dY
        across = to_end * b_d_end
        d_x_dt = within + across
        dx_ref[0, :, lanes] = (
            d_x_dt * dt + d_ref[:, lanes] * dy
        ).astype(dx_ref.dtype)
        dd_ref[0, tile_of_group, :, lanes] += jnp.sum(
            dy * x, axis=0, keepdims=True
        )
        d_dt += _head_rows(d_x_dt * x, per_tile, head_dim)
        # b_i moves column i of M and to_end the other way: the sum over
        # t of dM M is <dt X, M^T dY> with the SAME rounded M and dt X as
        # the row sums above, so that what cancels between them cancels
        d_sums += [
            rows + start - cols for rows, start, cols in zip(
                d_rows,
                _head_rows(dy_start * c_start, per_tile, head_dim),
                _head_rows(
                    within * x_dt_seen + across * x_dt, per_tile, head_dim
                ),
            )
        ]
        # b_Q = the whole chunk's sum scales S' and what each token adds
        # to it: d b_Q = <dS', S'>, a head's lanes of it
        ends = whole * jnp.sum(
            d_end * start_cd.astype(F32), axis=0, keepdims=True
        ) + jnp.sum(x_end * b_d_end, axis=0, keepdims=True)
        d_total += [
            jnp.sum(
                jnp.where(lane_head == j, ends, 0.0), axis=1, keepdims=True
            )
            for j in range(per_tile)
        ]
    # from_start = exp(b), decay[t, i] = exp(b_t - b_i), to_end =
    # exp(b_Q - b): the chunk's last sum b_Q holds what moves them all
    token = jax.lax.broadcasted_iota(jnp.int32, terms.b.shape, 1)
    dsums_ref[0, 0] = _stack(d_sums, terms.b) + jnp.where(
        token == q - 1, _stack(d_total, terms.b), 0.0
    )
    ddt_ref[0, 0] = _stack(d_dt, terms.b)

    @_when(last)
    def _():
        d_scores_cd = d_scores[...].astype(cd)            # [i, t]
        dc_ref[0] = (
            d_c[...] + _dot(bm_t, d_scores_cd)
        ).astype(dc_ref.dtype)
        db_ref[0] = (
            d_b[...] + _dot(cm_t, d_scores_cd, _NT)
        ).astype(db_ref.dtype)


def _grid(x, dt, b, groups, head_dim):
    """The grid ``(batch, groups, n, tiles a group)`` of these operands
    and a grid step's sizes."""
    batch, _, total = x.shape
    n, chunk = dt.shape[1], dt.shape[-1]
    state = b.shape[1] // groups
    heads = heads_per_step(
        total // head_dim, head_dim, groups, state, chunk, x.dtype
    )
    width = heads * head_dim
    tiles = total // width
    per_group = tiles // groups
    return types.SimpleNamespace(
        grid=(batch, groups, n, per_group), batch=batch, groups=groups,
        tiles=tiles, per_group=per_group, heads=heads, width=width,
        state=state, chunk=chunk, n=n,
    )


def _specs(g, reverse):
    """Block specs of one grid step (batch, group, chunk, tile of the
    group's heads) of the grid ``g`` (:func:`_grid`): the tile's columns of
    x-like and D-like operands and its per-token rows, its group's rows
    of B^T and C^T (``bc``: the same block for all the group's tiles, so
    fetched once a (batch, chunk) and, as an output, written once)."""
    def at(c):
        return g.n - 1 - c if reverse else c

    def tile(j, t):
        return j if g.per_group == 1 else j * g.per_group + t

    return types.SimpleNamespace(
        x=pl.BlockSpec(
            (1, g.chunk, g.width), lambda i, j, c, t: (i, at(c), tile(j, t))
        ),
        bc=pl.BlockSpec(
            (1, g.state, g.chunk), lambda i, j, c, t: (i, j, at(c))
        ),
        token=pl.BlockSpec(
            (1, 1, g.heads, g.chunk),
            lambda i, j, c, t: (i, at(c), tile(j, t), 0),
        ),
        d=pl.BlockSpec((1, g.width), lambda i, j, c, t: (0, tile(j, t))),
        start=pl.BlockSpec(
            (1, 1, g.state, g.width),
            lambda i, j, c, t: (i * g.tiles + tile(j, t), at(c), 0, 0),
        ),
    )


_SEMANTICS = ("parallel", "parallel", "arbitrary", "arbitrary")


@functools.partial(jax.jit, static_argnames=("groups", "head_dim"))
def _forward(x, dt, sums, b, c, d, *, groups, head_dim):
    """``x`` [B, S, H P]; ``dt`` and ``sums`` (the running sums of ``a``
    inside each chunk) [B, n, H, Q] float32; ``b``, ``c`` [B, G N, S];
    ``d`` [1, H P] float32 (a head's ``D`` on its lanes).  Returns ``y``
    [B, S, H P], each (batch, group)'s largest ``|S|`` at a chunk's end, a
    lane's own [B G, 128] (float32) and the chunks' start states ``S^T``
    [B tiles, n, N, heads P] in ``x``'s dtype (what the backward reads).  (Jitted, as the backward is, so that a step which runs the scan
    in several slots, forward, recomputed and transposed, traces and lowers
    each kernel body once.)"""
    g = _grid(x, dt, b, groups, head_dim)
    sp = _specs(g, False)
    y, top, starts = pl.pallas_call(
        functools.partial(
            _fwd_kernel, heads=g.heads, head_dim=head_dim,
            per_group=g.per_group,
        ),
        grid=g.grid,
        in_specs=[sp.x, sp.token, sp.token, sp.bc, sp.bc, sp.d],
        out_specs=[
            sp.x,
            pl.BlockSpec(
                (1, 1, LANES), lambda i, j, c, t: (i * groups + j, 0, 0)
            ),
            sp.start,
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((g.batch * groups, 1, LANES), F32),
            jax.ShapeDtypeStruct(
                (g.batch * g.tiles, g.n, g.state, g.width), x.dtype
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((g.per_group, g.state, g.width), F32),
            pltpu.VMEM((g.chunk, g.chunk), F32),
            pltpu.VMEM((g.chunk, g.state), c.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS,
        ),
        interpret=backend.interpret(),
        name="ssd_fwd",
    )(x, dt, sums, b, c, d)
    return y, top[:, 0], starts


@functools.partial(jax.jit, static_argnames=("groups", "head_dim"))
def _backward(x, dt, sums, b, c, d, starts, dy, *, groups, head_dim):
    """The six cotangents; ``dD`` as each batch row's ``sum_t dy x`` on the
    lanes [B G, tiles a group, 1, heads P] (float32)."""
    g = _grid(x, dt, b, groups, head_dim)
    sp = _specs(g, True)
    return pl.pallas_call(
        functools.partial(
            _bwd_kernel, heads=g.heads, head_dim=head_dim,
            per_group=g.per_group,
        ),
        grid=g.grid,
        in_specs=[
            sp.x, sp.token, sp.token, sp.bc, sp.bc, sp.d, sp.start, sp.x,
        ],
        out_specs=[
            sp.x, sp.token, sp.token, sp.bc, sp.bc,
            pl.BlockSpec(
                (1, g.per_group, 1, g.width),
                lambda i, j, c, t: (i * groups + j, 0, 0, 0),
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(dt.shape, F32),
            jax.ShapeDtypeStruct(sums.shape, F32),
            jax.ShapeDtypeStruct(b.shape, b.dtype),
            jax.ShapeDtypeStruct(c.shape, c.dtype),
            jax.ShapeDtypeStruct(
                (g.batch * groups, g.per_group, 1, g.width), F32
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((g.per_group, g.state, g.width), F32),
            pltpu.VMEM((g.chunk, g.chunk), F32),
            pltpu.VMEM((g.chunk, g.chunk), F32),
            pltpu.VMEM((g.state, g.chunk), F32),
            pltpu.VMEM((g.state, g.chunk), F32),
            pltpu.VMEM((g.chunk, g.state), b.dtype),
            pltpu.VMEM((g.chunk, g.state), c.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS,
        ),
        interpret=backend.interpret(),
        name="ssd_bwd",
    )(x, dt, sums, b, c, d, starts, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, dt, sums, b, c, d, groups, head_dim):
    return _scan_fwd(x, dt, sums, b, c, d, groups, head_dim)[0]


def _scan_fwd(x, dt, sums, b, c, d, groups, head_dim):
    y, top, starts = _forward(
        x, dt, sums, b, c, d, groups=groups, head_dim=head_dim
    )
    return (y, top), (x, dt, sums, b, c, d, starts)


def _scan_bwd(groups, head_dim, res, cts):
    dy, _ = cts          # the largest |S| is a reading, not a result
    x, dt, sums, b, c, d, starts = res
    dx, ddt, dsums, db, dc, dd = _backward(
        x, dt, sums, b, c, d, starts, dy, groups=groups, head_dim=head_dim
    )
    # over the batch here; _ssd_kernel's repeat of D sums a head's lanes
    dd = dd.reshape(x.shape[0], -1).sum(axis=0)[None].astype(d.dtype)
    return dx, ddt, dsums, db, dc, dd


_scan.defvjp(_scan_fwd, _scan_bwd)


# What a grid step may plan to hold in VMEM (a v5e's scoped default is
# 16 MiB, the blocks double-buffered), and the heads one kernel body
# unrolls: every head is a ``[Q, Q]`` decay and two products written out
# in the body, and bodies of more than 8 heads have compiled for minutes.
_VMEM_BUDGET = 12 << 20
_MAX_HEADS = 8


def step_vmem_bytes(
    heads: int, head_dim: int, state: int, chunk: int, dtype,
    tiles_per_group: int = 1,
) -> int:
    """VMEM a grid step of ``heads`` heads plans for, by the backward
    kernel (the larger), where a group is ``tiles_per_group`` such steps:
    the double-buffered blocks (x, dy, dx tiles, the chunk's start state,
    B, C, dB, dC, the rows of dt, a, ddt, da, ``D`` and the group's ``dD``
    block, a row padded to 8 sublanes), the scratch (float32: the state of
    ALL the group's tiles, ``(C B^T)^T`` and its cotangent, the group's dB
    and dC sums; the token-major B and C), and the body's float32 terms: the two triangles, four
    ``[Q, Q]`` a head of the lane tile in hand, a dozen ``[Q, 128]``
    tiles."""
    size = jnp.dtype(dtype).itemsize
    width = heads * head_dim
    blocks = 2 * (
        3 * chunk * width * size + state * width * size
        + 4 * chunk * state * size + 4 * max(heads, 8) * chunk * 4
        + (1 + tiles_per_group) * 8 * width * 4
    )
    scratch = (
        tiles_per_group * state * width + 2 * chunk * chunk
        + 2 * chunk * state
    ) * 4 + 2 * chunk * state * size
    terms = (
        (2 + 4 * (LANES // head_dim)) * chunk * chunk + 12 * chunk * LANES
    ) * 4
    return blocks + scratch + terms


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
        if per_group % n == 0 and step_vmem_bytes(
            n, head_dim, state, chunk, dtype, per_group // n
        ) <= _VMEM_BUDGET
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
        """[B, S, H] float32 -> [B, n, H, Q]: a chunk's tokens on the lanes"""
        return jnp.swapaxes(v.reshape(batch, n, chunk, heads), 2, 3)

    def by_channel(v):
        """[B, S, G, N] -> [B, G N, S]: a token's state vector down the
        sublanes, as a convolution kernel that keeps the tokens on the
        lanes (``ops/short_conv.py``) hands it over: its transpose and
        this one cancel, and no copy of B or C is made"""
        return jnp.swapaxes(v.reshape(batch, s, groups * state), 1, 2)

    # b_t, the running sum of a inside its chunk: one product with the
    # triangle for all heads and chunks (its transpose, the sums from a
    # token to the chunk's end, turns the kernel's db into da)
    sums = jnp.matmul(
        per_token(a), jnp.triu(jnp.ones((chunk, chunk), F32)),
        precision=jax.lax.Precision.HIGHEST,
    )
    y, top = _scan(
        x.reshape(batch, s, heads * head_dim), per_token(dt), sums,
        by_channel(b), by_channel(c),
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
