"""The gated delta rule in its chunk-parallel (WY) form, as Pallas kernels.

Per head, with keys ``k_t`` in R^dk, values ``v_t`` in R^dv, a write
strength ``beta_t`` and a log-decay ``g_t <= 0`` (``alpha_t = exp(g_t)``),
the rule (Gated DeltaNet, Yang et al., arXiv:2412.06464) keeps a state
``S`` in R^{dv x dk}::

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

A scan over single tokens is not a training path.  Here the sequence is
cut into chunks of ``chunk`` tokens (arXiv:2406.06484): with ``b_t`` the
running sum of ``g`` inside a chunk, ``gamma_t = exp(b_t)`` and ``S`` the
state the chunk starts from,

    A[t, i] = beta_t exp(b_t - b_i) (k_t . k_i)     for i < t, else 0
    T       = (I + A)^-1                           (unit lower triangular)
    W = T (beta gamma * K)      U = T (beta * V)
    V' = U - W S^T                                 (the chunk's writes)
    O  = (Q * gamma) S^T + M V'    M[t, i] = exp(b_t - b_i) (q_t . k_i), i <= t
    S' = gamma_C S + V'^T (K * exp(b_C - b))

**What lives where.**  One kernel runs the forward and one the backward.
Their grids are (groups of heads, chunks); the chunk axis is sequential
and the float32 state ``S`` (the backward: its cotangent ``dS``) of each
head of the group stays in VMEM scratch from one chunk to the next.  A
grid step reads a chunk's q, k ``[C, dk]``, v ``[C, dv]``, g and beta
``[C]`` and builds everything else in VMEM: the running sums, the decay
matrix, ``A``, ``T``, ``W``, ``U``, ``V'``, ``M``.  HBM sees, forward: q,
k, v, g, beta in, ``o``, each chunk's START state in the operands' dtype
and the largest ``|S|`` out; backward: the same inputs, the start states
and ``do`` in, dq, dk, dv, dg, dbeta out.  No ``[C, C]`` tensor, no ``W``,
``U`` or ``V'`` is ever written to HBM (XLA's form wrote each of a dozen
``[B, H, N, C, C]`` float32 tensors, 252 MB at the benchmark's shapes, and
took 57.7 ms a layer for 1.7 GB of kernel-shaped traffic: PERF.md §6).

**Residuals.**  The rule is one ``custom_vjp``: it keeps q, k, v, g, beta
and the chunk-start states.  The backward walks the chunks in reverse and
rebuilds a chunk's ``A``, ``T``, ``W``, ``U``, ``V'`` from the inputs and
its start state, which is cheaper than reading them (they were never
written); ``dA = -T^T dT T^T``, the inverse's own VJP.  Under a layer's
remat the forward kernel runs a second time to write the start states
again: keeping them (0.19 GB a layer at the benchmark's shapes) pushed
the compiler into recomputing two projections, and the step was slower
for it (PERF.md §6, PR 32).

**Precision.**  Matrix products take operands in the inputs' dtype and
accumulate in float32; ``g``, its running sums, ``T`` and the state are
float32.  ``T`` is built by halves (:func:`_unit_lower_inverse`), as
stable as substitution, in float32 products of three bfloat16 passes (the
operands split into a high and a low half: ``Precision.HIGH``): twelve a
128-token chunk, six with a left operand of 64 rows (the rows of a level
that are not structural zeros) and six of 16 (the eight diagonal blocks
folded onto one block's rows), where until PR 51 all twelve were whole
128 x 128 x 128; the numbers are the same to the last bit.  The
shorter product ``(I - A)(I + A^2)(I + A^4)...`` loses every digit on keys
that resemble each other, as trained keys do, and one-pass products move
the program's distance from its reference (PERF.md §6, PR 31): neither is
here.  With float32 operands (the CPU tests) every product is float32.

**Heads in lockstep.**  A head's chunk is a chain of products each of
which waits for the last: twelve in the inverse, a handful around it, and
the matrix unit answers one in about a hundred cycles whatever its size.
The compiler schedules a kernel body in program order, so heads written
one after another wait one after another: on the chip the forward kernel
took the same 9.4 ms a call at the benchmark's shapes with a third fewer
instructions, and 5.4 with the same instructions in another order
(PERF.md §6, PR 51).  So a head's work is a generator that yields where it
has asked for a product the next stage waits for, and a grid step
advances its heads' generators in turn (:func:`_in_lockstep`): one head's
wait is the others' work.  No number changes, only the program's order.

The kernels want ``[B * H, S, d]``; the transposes from the call site's
``[B, S, H, d]`` are made here, inside the caller's scope.  The chunk is
128 tokens: whole 128 x 128 MXU tiles (PERF.md §6, PR 31).
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
BF16 = jnp.bfloat16

# dot_general's dimension numbers: x y, x y^T, x^T y
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))

# Heads one grid step works through.  Their chains of products are
# independent, so the compiler is free to interleave them.
_HEADS_PER_STEP = 3
_TOP_LANES = 128


def _dot(x, y, dims=_NN):
    """Operands as they are, float32 accumulation; float32 operands (the
    CPU tests) multiply in full precision."""
    return jax.lax.dot_general(
        x, y, dims, preferred_element_type=F32,
        precision=jax.lax.Precision.HIGHEST if x.dtype == F32 else None,
    )


def _halves(x, exact):
    """What a float32 matrix enters a float32 product as: itself, or its
    bfloat16 high and low halves."""
    if exact:
        return (x,)
    high = x.astype(BF16)
    return high, (x - high.astype(F32)).astype(BF16)


def _mm_f32(x, y, dims=_NN):
    """The product of two float32 matrices given by :func:`_halves`: three
    bfloat16 passes (high x high, high x low, low x high), about 1e-5 of a
    product's size."""
    if len(x) == 1:
        return _dot(x[0], y[0], dims)
    return _dot(x[0], y[0], dims) + (
        _dot(x[0], y[1], dims) + _dot(x[1], y[0], dims)
    )


def _alone(head):
    """One head's stages (see :func:`_in_lockstep`) run to the end: what
    the generator returns."""
    try:
        while True:
            next(head)
    except StopIteration as done:
        return done.value


def _in_lockstep(heads):
    """Runs the heads of a grid step, each a generator that yields where
    it has just asked the matrix unit for a product the next stage waits
    for, a stage of every head in turn.  The order of the arithmetic
    inside a head, and so every number, stays what it is; only the
    program's order interleaves the heads' chains, which is the order the
    compiler schedules in."""
    heads = list(heads)
    while heads:
        for head in list(heads):
            try:
                next(head)
            except StopIteration:
                heads.remove(head)


_FOLD = 16     # the diagonal blocks folded onto one block's rows below it


def _lower_left(row, col, level):
    """Where ``row``, ``col`` lie in the lower-left quarter of one diagonal
    block of ``2 << level``."""
    return (
        ((row >> (level + 1)) == (col >> (level + 1)))
        & (((row >> level) & 1) == 1) & (((col >> level) & 1) == 0)
    )


def _live(x, m):
    """The rows of ``x`` [C, C] whose bit ``m`` is set, [C / 2, C]: aligned
    slices of ``m`` rows."""
    return jnp.concatenate(
        [x[lo + m:lo + 2 * m] for lo in range(0, x.shape[0], 2 * m)], axis=0
    )


def _weave(x, live, m):
    """``x`` [C, C] with ``live`` [C / 2, C] for its rows whose bit ``m``
    is set."""
    return jnp.concatenate([
        part for lo in range(0, x.shape[0], 2 * m)
        for part in (x[lo:lo + m], live[lo // 2:lo // 2 + m])
    ], axis=0)


def _unit_lower_inverse(a, row, col, exact):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` [C, C], C a power
    of two, by halves: with the inverses ``P`` of the diagonal blocks of
    size m in hand, those of size 2m are ``P - P E P``, ``E`` the
    lower-left m x m block of each (``[[L1, 0], [E, L2]]^-1 = [[P1, 0],
    [-P2 E P1, P2]]``), from m = 1 (``P = I``): 2 (log2(C) - 1) float32
    products, none of them C x C x C.  ``P E P`` is zero outside the rows
    whose bit m is set, and lands where ``P`` is zero, so a level multiplies
    those rows alone, against ``-a`` whole and ``P``, and keeps of the
    result the quarter blocks (what else ``-a`` put there never reaches
    them: ``P`` is block-diagonal).  From m = 16 up the rows are aligned
    slices, half the matrix.  Below, all is block-diagonal in blocks of
    16, and the sum ``X'`` of a block-diagonal ``X``'s 16-row slices holds
    all of it, with ``(X Y)' = X' Y``: the levels run on ``[16, C]`` and
    unfold (tile, mask to the block diagonal) what they multiply by; a
    chunk of 16 or less is its own fold.  ``-a`` is split into halves
    once, and of ``P`` only the new rows.  The entries that are not zero
    meet the arithmetic of the whole-matrix build, and the result is its
    result bit for bit (tests/test_gated_delta_rule.py keeps that build).

    A generator, one stage a product (:func:`_in_lockstep`): ``t = yield
    from ...`` inside a head, :func:`_alone` around it elsewhere."""
    c = a.shape[-1]
    fold = min(c, _FOLD)
    blocks, fold_bits = c // fold, fold.bit_length() - 1
    same_block = (row >> fold_bits) == (col >> fold_bits)
    neg = -a

    def unfold(parts):
        """[fold, C] -> [C, C], each part in its own dtype (the select in
        float32: the vector unit has no other)."""
        if blocks == 1:
            return parts
        return tuple(
            jnp.where(
                same_block,
                jnp.concatenate([x.astype(F32)] * blocks, axis=0), 0.0,
            ).astype(x.dtype)
            for x in parts
        )

    in_block = jnp.where(same_block, neg, 0.0) if blocks > 1 else neg
    by_block = _halves(in_block, exact)
    inv = in_block[:fold]
    for b in range(1, blocks):
        inv = inv + in_block[b * fold:(b + 1) * fold]
    # (new iotas: Mosaic cannot slice one)
    at = jax.lax.broadcasted_iota(jnp.int32, (fold, c), 0)
    to = jax.lax.broadcasted_iota(jnp.int32, (fold, c), 1) & (fold - 1)
    inv = jnp.where(
        at == to, 1.0, jnp.where((at == to + 1) & ((to & 1) == 0), inv, 0.0)
    )
    for level in range(1, fold_bits):
        p = _halves(inv, exact)
        x = _halves(_mm_f32(p, by_block), exact)
        yield
        inv = jnp.where(
            _lower_left(at, to, level), _mm_f32(x, unfold(p)), inv
        )
        yield
    if fold == c:
        return inv
    whole = _halves(neg, exact)
    p = unfold(_halves(inv, exact))
    inv, = unfold((inv,))
    at = jax.lax.broadcasted_iota(jnp.int32, (c // 2, c), 0)
    to = jax.lax.broadcasted_iota(jnp.int32, (c // 2, c), 1)
    for level in range(fold_bits, c.bit_length() - 1):
        m = 1 << level
        x = _halves(_mm_f32(tuple(_live(h, m) for h in p), whole), exact)
        yield
        # live row i is row 2m (i // m) + m + i % m
        new = jnp.where(
            ((to >> (level + 1)) == (at >> level))
            & (((to >> level) & 1) == 0),
            _mm_f32(x, p), _live(inv, m),
        )
        inv = _weave(inv, new, m)
        yield
        if 2 * m < c:
            p = tuple(
                _weave(old, h, m) for old, h in zip(p, _halves(new, exact))
            )
    return inv


def _to_col(x_row, eye):
    """[1, C] -> [C, 1], through the diagonal of a [C, C]."""
    return jnp.sum(jnp.where(eye, x_row, 0.0), axis=1, keepdims=True)


def _to_row(x_col, eye):
    return jnp.sum(jnp.where(eye, x_col, 0.0), axis=0, keepdims=True)


def _chunk_tensors(q, k, v, g_row, beta_row, start):
    """What a chunk builds from its own tokens and the state it starts
    from, forward and backward alike, in a head's stages (a generator:
    :func:`_in_lockstep`).  ``q``, ``k`` [C, dk], ``v``
    [C, dv]; ``g_row``, ``beta_row`` [1, C] float32; ``start`` [dv, dk] in
    the operands' dtype.  A vector indexed by the token comes as a column
    [C, 1] where it scales rows and as a row [1, C] where it scales
    columns; one turns into the other through the diagonal of a [C, C]."""
    cd = v.dtype
    c = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    lower, strict, eye = col <= row, col < row, col == row
    # b_t, the running sum of g, as a column and as a row
    b_col = jnp.sum(jnp.where(lower, g_row, 0.0), axis=1, keepdims=True)
    b_row = _to_row(b_col, eye)
    total = b_col[c - 1:, :]                              # b_C  [1, 1]
    # exp(b_t - b_i) where i <= t; the other half would overflow
    decay = jnp.exp(jnp.where(lower, b_col - b_row, -jnp.inf))
    beta_col = _to_col(beta_row, eye)
    kk = _dot(k, k, _NT)
    yield
    t = yield from _unit_lower_inverse(
        jnp.where(strict, kk * decay * beta_col, 0.0), row, col,
        exact=cd == F32,
    )
    gamma_row = jnp.exp(b_row)
    # W = T (beta gamma * K), U = T (beta * V): the scales go onto T's
    # columns, so K and V are read as they are
    t_w = (t * (beta_row * gamma_row)).astype(cd)
    t_u = (t * beta_row).astype(cd)
    w = _dot(t_w, k).astype(cd)
    yield
    gamma_col, to_end = jnp.exp(b_col), jnp.exp(total - b_col)
    qk = _dot(q, k, _NT)
    writes = (_dot(t_u, v) - _dot(w, start, _NT)).astype(cd)         # V'
    yield
    return types.SimpleNamespace(
        lower=lower, strict=strict, eye=eye, decay=decay, kk=kk, t=t,
        beta_col=beta_col, gamma_row=gamma_row, gamma_col=gamma_col,
        gamma_end=jnp.exp(total), to_end=to_end, t_w=t_w, t_u=t_u, w=w,
        writes=writes,
        k_end=(k.astype(F32) * to_end).astype(cd),
        q_in=(q.astype(F32) * gamma_col).astype(cd),
        qk=qk, within=(qk * decay).astype(cd),                       # M
    )


def _fwd_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, start_ref, top_ref, state,
    *, heads,
):
    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)
        top_ref[...] = jnp.zeros_like(top_ref)

    def head(h):
        q, k, v = q_ref[h], k_ref[h], v_ref[h]
        start = state[h].astype(v.dtype)                  # [dv, dk]
        start_ref[h, 0] = start
        x = yield from _chunk_tensors(
            q, k, v, g_ref[h, 0], beta_ref[h, 0], start
        )
        end = state[h] * x.gamma_end + _dot(x.writes, x.k_end, _TN)
        state[h] = end
        top = jnp.max(
            jnp.max(jnp.abs(end), axis=1, keepdims=True), axis=0,
            keepdims=True,
        )
        top_ref[h] = jnp.maximum(top_ref[h], top)
        o_ref[h] = (
            _dot(x.q_in, start, _NT) + _dot(x.within, x.writes)
        ).astype(o_ref.dtype)

    _in_lockstep(head(h) for h in range(heads))


def _bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, start_ref, do_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, d_state,
    *, heads,
):
    """One chunk, walked last to first.  ``d_state`` holds the cotangent
    of the chunk's END state on entry and of its start state on exit."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    def head(h):
        q, k, v, do = q_ref[h], k_ref[h], v_ref[h], do_ref[h]
        cd = v.dtype
        beta_row = beta_ref[h, 0]
        start = start_ref[h, 0]                           # [dv, dk]
        x = yield from _chunk_tensors(
            q, k, v, g_ref[h, 0], beta_row, start
        )
        lower, decay, t = x.lower, x.decay, x.t
        w, writes, k_end, q_in = x.w, x.writes, x.k_end, x.q_in
        q32, k32 = q.astype(F32), k.astype(F32)
        d_end = d_state[h]
        d_end_cd = d_end.astype(cd)

        # O = q_in S^T + within V';  S' = gamma_C S + V'^T k_end
        d_writes = _dot(x.within, do, _TN) + _dot(k_end, d_end_cd, _NT)
        d_writes_cd = d_writes.astype(cd)
        d_within = jnp.where(lower, _dot(do, writes, _NT), 0.0)
        d_qk = (d_within * decay).astype(cd)
        d_decay = d_within * x.qk
        d_q_in = _dot(do, start)
        d_k_end = _dot(writes, d_end_cd)
        yield
        d_q = d_q_in * x.gamma_col + _dot(d_qk, k)
        d_k = _dot(d_qk, q, _TN) + d_k_end * x.to_end
        d_gamma_col = jnp.sum(d_q_in * q32, axis=1, keepdims=True)
        d_to_end = jnp.sum(d_k_end * k32, axis=1, keepdims=True)
        d_gamma_end = jnp.sum(
            jnp.sum(d_end * start.astype(F32), axis=1, keepdims=True),
            axis=0, keepdims=True,
        )
        # V' = U - W S^T
        d_state[h] = (
            d_end * x.gamma_end + _dot(do, q_in, _TN)
            - _dot(d_writes_cd, w, _TN)
        )
        d_w = (-_dot(d_writes_cd, start)).astype(cd)
        yield
        # W = t_w K, U = t_u V
        d_t_w = _dot(d_w, k, _NT)
        d_t_u = _dot(d_writes_cd, v, _NT)
        d_k = d_k + _dot(x.t_w, d_w, _TN)
        dv_ref[h] = _dot(x.t_u, d_writes_cd, _TN).astype(dv_ref.dtype)
        scale_w = beta_row * x.gamma_row
        d_scale_w = jnp.sum(d_t_w * t, axis=0, keepdims=True)
        d_beta_row = (
            jnp.sum(d_t_u * t, axis=0, keepdims=True)
            + d_scale_w * x.gamma_row
        )
        d_gamma_row = d_scale_w * beta_row
        yield
        # T = (I + A)^-1:  dA = -T^T dT T^T
        exact = cd == F32
        t_halves = _halves(t, exact)
        t_dt = _halves(_mm_f32(
            t_halves, _halves(d_t_w * scale_w + d_t_u * beta_row, exact),
            _TN,
        ), exact)
        yield
        d_a = _mm_f32(t_dt, t_halves, _NT)
        yield
        d_a = jnp.where(x.strict, -d_a, 0.0)
        # A = beta_t decay kk
        d_kk = (d_a * decay * x.beta_col).astype(cd)
        d_k = d_k + _dot(d_kk, k) + _dot(d_kk, k, _TN)
        d_a_kk = d_a * x.kk
        d_decay = d_decay + d_a_kk * x.beta_col
        d_beta_col = jnp.sum(d_a_kk * decay, axis=1, keepdims=True)
        # decay[t, i] = exp(b_t - b_i), gamma = exp(b), to_end = exp(b_C - b)
        d_decay = d_decay * decay
        d_to_end = d_to_end * x.to_end
        d_b_col = (
            jnp.sum(d_decay, axis=1, keepdims=True)
            + d_gamma_col * x.gamma_col - d_to_end
            + _to_col(
                d_gamma_row * x.gamma_row
                - jnp.sum(d_decay, axis=0, keepdims=True), x.eye,
            )
        )
        d_total = (
            jnp.sum(d_to_end, axis=0, keepdims=True)
            + d_gamma_end * x.gamma_end
        )
        # b = cumsum(g):  dg_t = the sum of db_j over j >= t; b_C holds all
        dg_ref[h, 0] = d_total + jnp.sum(
            jnp.where(lower, d_b_col, 0.0), axis=0, keepdims=True
        )
        dbeta_ref[h, 0] = d_beta_row + _to_row(d_beta_col, x.eye)
        dq_ref[h] = d_q.astype(dq_ref.dtype)
        dk_ref[h] = d_k.astype(dk_ref.dtype)

    _in_lockstep(head(h) for h in range(heads))


def _heads_per_step(heads: int) -> int:
    return max(
        n for n in range(1, _HEADS_PER_STEP + 1) if heads % n == 0
    )


def _specs(group, chunk, widths, index):
    """Block specs of [BH, S, width] arrays, a chunk of ``group`` heads."""
    return [
        pl.BlockSpec((group, chunk, width), index) for width in widths
    ]


@jax.jit
def _forward(q, k, v, g, beta):
    """``q, k`` [BH, S, dk], ``v`` [BH, S, dv], ``g, beta`` [BH, N, 1, C]
    float32.  Returns ``o`` [BH, S, dv], the chunks' start states
    [BH, N, dv, dk] (both in ``v``'s dtype) and each head's largest
    ``|S|`` at a chunk's end [BH] (float32).  (Jitted, as the backward
    is, so that a step which runs the rule in three slots, forward,
    recomputed and transposed, traces and lowers each kernel body once
    and not six times: 0.35 s a time.)"""
    heads, s, dk = q.shape
    dv = v.shape[-1]
    n, chunk = g.shape[1], g.shape[-1]
    group = _heads_per_step(heads)

    def tokens(i, c):
        return (i, c, 0)

    def scalars(i, c):
        return (i, c, 0, 0)

    o, starts, top = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=group),
        grid=(heads // group, n),
        in_specs=_specs(group, chunk, (dk, dk, dv), tokens) + [
            pl.BlockSpec((group, 1, 1, chunk), scalars),
            pl.BlockSpec((group, 1, 1, chunk), scalars),
        ],
        out_specs=[
            pl.BlockSpec((group, chunk, dv), tokens),
            pl.BlockSpec((group, 1, dv, dk), scalars),
            pl.BlockSpec((group, 1, _TOP_LANES), lambda i, c: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((heads, s, dv), v.dtype),
            jax.ShapeDtypeStruct((heads, n, dv, dk), v.dtype),
            jax.ShapeDtypeStruct((heads, 1, _TOP_LANES), F32),
        ],
        scratch_shapes=[pltpu.VMEM((group, dv, dk), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=backend.interpret(),
        name="delta_rule_fwd",
    )(q, k, v, g, beta)
    return o, starts, top[:, 0, 0]


@jax.jit
def _backward(q, k, v, g, beta, starts, do):
    heads, s, dk = q.shape
    dv = v.shape[-1]
    n, chunk = g.shape[1], g.shape[-1]
    group = _heads_per_step(heads)

    def tokens(i, c):
        return (i, n - 1 - c, 0)

    def scalars(i, c):
        return (i, n - 1 - c, 0, 0)

    per_token = pl.BlockSpec((group, 1, 1, chunk), scalars)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=group),
        grid=(heads // group, n),
        in_specs=_specs(group, chunk, (dk, dk, dv), tokens) + [
            per_token, per_token,
            pl.BlockSpec((group, 1, dv, dk), scalars),
        ] + _specs(group, chunk, (dv,), tokens),
        out_specs=_specs(group, chunk, (dk, dk, dv), tokens) + [
            per_token, per_token,
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(g.shape, F32),
            jax.ShapeDtypeStruct(beta.shape, F32),
        ],
        scratch_shapes=[pltpu.VMEM((group, dv, dk), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=backend.interpret(),
        name="delta_rule_bwd",
    )(q, k, v, g, beta, starts, do)


def _rule_fwd(q, k, v, g, beta):
    o, starts, top = _forward(q, k, v, g, beta)
    return (o, top), (q, k, v, g, beta, starts)


@jax.custom_vjp
def _rule(q, k, v, g, beta):
    return _rule_fwd(q, k, v, g, beta)[0]


def _rule_bwd(res, cts):
    do, _ = cts          # the largest |S| is a reading, not a result
    return tuple(_backward(*res, do))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    chunk: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    """``q, k`` [B, S, H, dk] (already normalised and scaled), ``v``
    [B, S, H, dv], ``g`` (log decay) and ``beta`` [B, S, H].  Returns the
    outputs [B, S, H, dv] in ``v``'s dtype and, under ``stop_gradient``,
    the largest ``|S|`` entry at any chunk boundary (float32 scalar).

    A sequence that is no whole number of chunks is padded with tokens
    that neither write (``beta`` 0) nor decay (``g`` 0).  ``chunk`` is a
    power of two and whole tiles of the operands' dtype."""
    cd = v.dtype
    if chunk & (chunk - 1) or chunk % tile_rows(cd):
        raise ValueError(
            f"chunk must be a power of two and a multiple of the "
            f"{tile_rows(cd)} rows of a {jnp.dtype(cd).name} tile, "
            f"got {chunk}"
        )
    if q.dtype != cd or k.dtype != cd:
        raise ValueError(
            f"q, k, v must share a dtype, got {q.dtype}, {k.dtype}, {cd}"
        )
    b, s, h, _ = q.shape
    pad = -s % chunk
    n = (s + pad) // chunk

    def heads_first(a):
        """[B, S, H, ...] -> [B * H, S + pad, ...]"""
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        return jnp.moveaxis(a, 2, 1).reshape(b * h, s + pad, *a.shape[3:])

    def per_token(a):
        return heads_first(a.astype(F32)).reshape(b * h, n, 1, chunk)

    o, top = _rule(
        heads_first(q), heads_first(k), heads_first(v), per_token(g),
        per_token(beta),
    )
    o = jnp.moveaxis(o.reshape(b, h, s + pad, -1), 1, 2)
    return o[:, :s], jax.lax.stop_gradient(jnp.max(top))
