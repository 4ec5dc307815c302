"""Kimi Delta Attention's rule (Kimi Linear, arXiv:2510.26692): the gated
delta rule with a decay PER CHANNEL of the key, chunk-parallel, as Pallas
kernels and as the same mathematics in ``jax.numpy``.

Per head, with keys ``k_t`` in R^dk, values ``v_t`` in R^dv, a write
strength ``beta_t`` and a log-decay VECTOR ``g_t`` in R^dk, ``g_t <= 0``
(``a_t = exp(g_t)``), the rule keeps a state ``S`` in R^{dk x dv}::

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``ops/gated_delta_rule.py`` is this with one decay a head, and its chunk
form rests on that: ``exp(b_t - b_i)`` is one number a pair of tokens, so
the decay multiplies ``k_t . k_i`` AFTER the product.  Here it sits inside
the sum over channels.  With ``G_t`` the running sum of ``g`` inside a
chunk of ``C`` tokens and ``S`` the state the chunk starts from::

    A'[t, i] = sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])
    A[t, i]  = beta_t A'[t, i]  for i < t, else 0;   T = (I + A)^-1
    W = T (beta * K * exp(G))        U_v = T (beta * V)
    U = U_v - W S                                  (the chunk's writes)
    O = (Q * exp(G)) S + M U     M[t, i] = sum_c q_t[c] k_i[c]
                                           exp(G_t[c] - G_i[c]),  i <= t
    S' = Diag(exp(G_C)) S + (K * exp(G_C - G))^T U

**Two forms of the pairs' decays.**  ``A'`` and ``M`` are matrix products
only once the exponent is split, ``(k_t exp(G_t - r)) . (k_i exp(r -
G_i))``, and which ``r`` serves depends on the gate.  A caller says which
form it needs (``exact``): the mixer decides from its configuration at
trace time (``models/linear_attention.py``), and ``kernel_facts`` names the
form in the ``compile`` event.

*The split form* (``exact=False``: Ling-3.0-flash, whose gate is bounded
below, ``kda_lower_bound`` -5: ``-5 < g < 0``).  ``exp(r - G_i)`` overflows
float32 unless the total decay between ``r`` and ``i`` is bounded, and a
chunk is cut into sub-chunks of ``SUB`` = 16 tokens: 16 x 5 = 80 <
log(3.4e38) = 88.7.  The rows of sub-chunk ``a`` take ``r_a``, the running
sum at its eighth token: ``exp(G_t - r_a)`` then lies in e^-40 .. e^35 for
its rows, ``exp(r_a - G_i)`` is at most 1 for every earlier column and at
most e^40 for a column of the same sub-chunk; later columns are masked
before the exponential.  (With ``r_a`` at the sub-chunk's start the last
row's factor is e^-80, and q's entries, 1e-2 x that, fall under float32's
smallest normal number and are flushed: the last token of every sub-chunk
then read 0.3% off.)  One ``[2 SUB, dk] x [dk, C]`` product a sub-chunk
gives its band of ``A'`` and ``M``.  A caller whose ``g`` goes below
``SPLIT_FLOOR`` = ``-88 / SUB`` a token gets infinities in this form.

*The exact form* (``exact=True``: Solar-Open2, whose gate is the paper's
``-exp(A_log) softplus(.)`` and has NO lower bound).  The triangle is cut
by halves: at the level of blocks of ``b`` = 1, 2, .. C / 2 tokens the rows
of every odd block meet the columns of the even block before it, and the
decay is split AT THE BOUNDARY between the two: ``exp(G_t - r)`` is the
decay from the odd block's start to ``t``, ``exp(r - G_i)`` the decay after
``i`` to the even block's end.  Both exponents are sums of ``g`` over
tokens that lie between column and row, so both factors are at most 1
whatever ``g`` is, a factor that underflows belongs to a product that
underflows too, and nothing is clamped.  The levels tile the strict
triangle exactly once (``gated_delta_rule._lower_left``); a token against
itself decays by nothing (``q_t . k_t``).  log2(C) products of ``[2 C, dk]
x [dk, C]`` where the split form has C / SUB of ``[2 SUB, dk] x [dk, C]``:
on the chip the kernels alone take 8.7 ms a forward call and 18.1 a
backward call at 1 x 16384 tokens and 64 heads where the split form takes
5.9 and 11.5 (PERF.md §6, PR 64), which is why Ling keeps the split form.
Both sums are built inside their blocks, a level's from the level below
(``_sibling_total``), never as differences of the chunk's running sum:
a difference carries the rounding of everything decayed before it (read
1.4e-5 of the output at 200 a token, against 4e-7 built this way), and for
the same reason the decay from a token to the chunk's end is the last
level's sum and not ``G_C - G_t``.  The boundary takes no gradient (a
pair's decay is ``exp(G_t - G_i)`` wherever it was split): the backward
sends a row's to ``G_t`` and a column's from ``G_i``.

**What lives where.**  As the scalar rule's kernels: one forward and one
backward kernel over (groups of heads, chunks), the chunk axis sequential,
the float32 state (its cotangent) in VMEM scratch from chunk to chunk,
kept transposed ``[dv, dk]`` so that a decay scales its columns.  A grid
step reads a chunk's q, k ``[C, dk]``, v ``[C, dv]``, g ``[C, dk]``
(float32) and beta ``[C]`` and builds the running sums (log2 C shifted
adds, exact), the scaled keys, ``A'``, ``T``, ``W``, ``U``, ``M`` in VMEM.
HBM sees, forward: the inputs, ``o``, each chunk's start state in the
operands' dtype, the largest ``|S|``; backward: those and ``do`` in, dq,
dk, dv, dg, dbeta out.  The custom VJP keeps the inputs and the start
states, and names the states ``kda_states``: a remat policy that keeps that
name beside the mixer's ``kda_out`` (``flash_only``, ops/remat_policy.py)
replays a forward kernel with no live output, so it runs once a layer and
the backward reads the first run's states; a policy that keeps no names
(``full``) runs it a second time for them.

**Precision.**  Products take operands in the inputs' dtype and accumulate
in float32; g, its running sums, the decays, ``T`` (built by halves in float32
products of three bfloat16 passes on the rows that are not structural
zeros, ``gated_delta_rule._unit_lower_inverse``) and the state are float32.
With float32 operands every product is float32.  A grid step advances its
heads in lockstep, a stage of each in turn, so that one head's wait for the
matrix unit is the others' work (``gated_delta_rule._in_lockstep``;
:func:`_chunk_tensors` and a kernel's ``head`` are generators for that).

:func:`kda` picks by shape (:func:`plan`): the kernels where both head
widths are whole lane tiles, the chunked ``jax.numpy`` form (the same
chunk mathematics under ``vmap`` and ``lax.scan``, differentiated by JAX)
anywhere else, either in the form the caller names.  ``beta`` may reach 2
(``allow_neg_eigval``): ``T`` is then built from a doubled ``A``, and
tests/test_kda.py reads the same distance from the recurrence as at 1.  The chunk is 128 tokens and a grid step holds four heads,
chosen on the chip at 2 x 8192 tokens and 32 heads of 128 / 128 (PERF.md
§6, PR 44): forward 10.4 ms and forward with backward 23.8 ms a layer from
``[B, S, H, d]``, against 15.1 and 33.8 at chunks of 64 (whose 64 x 64
products fill a quarter of the matrix unit) and 11.4 and 25.3 at one head
a step.  Since PR 51 (``T``'s twelve products on 64 and 16 rows where they
were whole, the heads in lockstep) the kernels alone take 2.97 ms a forward
call and 5.78 a backward call where they took 7.76 and 11.61 (PERF.md §6).
"""

from __future__ import annotations

import functools
import types
from typing import Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops import backend
from dlrover_tpu.ops.gated_delta_rule import (
    _NT, _TN, _alone, _dot, _halves, _in_lockstep, _lower_left, _mm_f32,
    _to_col, _to_row, _unit_lower_inverse,
)
from dlrover_tpu.ops.row_gather_sum import tile_rows

F32 = jnp.float32
SUB = 16            # tokens a sub-chunk: SUB x the gate's bound < log(f32 max)
SPLIT_FLOOR = -88.0 / SUB   # a token's log decay the split form holds above
_MID = SUB // 2 - 1  # the token of a sub-chunk whose running sum is its r_a
CHUNK = 128
LANES = 128
_HEADS_PER_STEP = 4
_TOP_LANES = 128


def _cumsum_rows(x, tok, roll):
    """Running sum down the rows of ``x`` [C, w], exact: log2(C) shifted
    adds.  ``roll(x, n)`` moves row r to row r + n."""
    shift = 1
    while shift < x.shape[0]:
        x = x + jnp.where(tok >= shift, roll(x, shift), 0.0)
        shift *= 2
    return x


def _split_pairs(q, k, g, tok, roll, strict, lower, cd):
    """``A'`` and ``M`` under a BOUNDED gate (the module's text): every
    decay split around the middle of the row's sub-chunk, one band a
    sub-chunk.  ``(q, k in float32, the running sum, its last row, kk, qk,
    what the backward reads)``."""
    c = k.shape[0]
    run = _cumsum_rows(g, tok, roll)                       # G  [C, dk]
    total = run[c - 1:, :]                                 # G_C  [1, dk]
    # r_a: the running sum in the middle of sub-chunk a
    refs = [
        run[a * SUB + _MID: a * SUB + _MID + 1, :] for a in range(c // SUB)
    ]
    own_ref = refs[0]
    for a in range(1, c // SUB):
        own_ref = jnp.where(tok >= a * SUB, refs[a], own_ref)
    e_row = jnp.exp(run - own_ref)                  # e^-40 .. e^35
    q32, k32 = q.astype(F32), k.astype(F32)
    k_r, q_r = (k32 * e_row).astype(cd), (q32 * e_row).astype(cd)
    e_cols, k_cols, bands_kk, bands_qk = [], [], [], []
    for a in range(c // SUB):
        lo, hi = a * SUB, (a + 1) * SUB
        # columns past the sub-chunk are masked BEFORE the exponential
        e_col = jnp.exp(jnp.where(tok < hi, refs[a] - run, -jnp.inf))
        k_c = (k32 * e_col).astype(cd)
        band = _dot(jnp.concatenate([k_r[lo:hi], q_r[lo:hi]], axis=0),
                    k_c, _NT)                              # [2 SUB, C]
        e_cols.append(e_col)
        k_cols.append(k_c)
        bands_kk.append(band[:SUB])
        bands_qk.append(band[SUB:])
    kk = jnp.where(strict, jnp.concatenate(bands_kk, axis=0), 0.0)   # A'
    qk = jnp.where(lower, jnp.concatenate(bands_qk, axis=0), 0.0)    # M
    return q32, k32, run, total, kk, qk, types.SimpleNamespace(
        e_row=e_row, k_r=k_r, q_r=q_r, e_cols=e_cols, k_cols=k_cols,
    )


def _split_pairs_bwd(p, d_kk, d_qk, q32, k32, tok, cd):
    """``(d_q, d_k, d_G)`` of :func:`_split_pairs`' ``kk`` and ``qk``."""
    c = k32.shape[0]
    # the bands: [k_r; q_r]_a k_c_a^T
    d_kk_cd, d_qk_cd = d_kk.astype(cd), d_qk.astype(cd)
    d_run = jnp.zeros_like(k32)
    d_k = jnp.zeros_like(k32)
    d_rows_k, d_rows_q = [], []
    for a in range(c // SUB):
        lo, hi = a * SUB, (a + 1) * SUB
        d_band = jnp.concatenate([d_kk_cd[lo:hi], d_qk_cd[lo:hi]], axis=0)
        d_rows = _dot(d_band, p.k_cols[a])             # [2 SUB, dk]
        d_rows_k.append(d_rows[:SUB])
        d_rows_q.append(d_rows[SUB:])
        d_k_c = _dot(
            d_band,
            jnp.concatenate([p.k_r[lo:hi], p.q_r[lo:hi]], axis=0), _TN,
        ) * p.e_cols[a]                                # [C, dk]
        d_k = d_k + d_k_c
        # e_col = exp(r_a - G): -z to G, z's sum to the row r_a reads
        z = d_k_c * k32
        d_run = d_run - z + jnp.where(
            tok == lo + _MID, jnp.sum(z, axis=0, keepdims=True), 0.0
        )
    # k_r = k e_row, q_r = q e_row with e_row = exp(G - r_own)
    d_k_r = jnp.concatenate(d_rows_k, axis=0) * p.e_row
    d_q_r = jnp.concatenate(d_rows_q, axis=0) * p.e_row
    z = d_k_r * k32 + d_q_r * q32
    d_run = d_run + z
    for a in range(c // SUB):
        mine = (tok >= a * SUB) & (tok < (a + 1) * SUB)
        d_run = d_run - jnp.where(
            tok == a * SUB + _MID,
            jnp.sum(jnp.where(mine, z, 0.0), axis=0, keepdims=True), 0.0,
        )
    return d_q_r, d_k + d_k_r, d_run


_ROW_TILE = 8        # rows of a float32 tile: from here a block is whole tiles


def _sibling_total(local, place, b, roll, onto_odd):
    """Of every pair of blocks of ``b`` rows, one block's total (``local``
    at its last row, ``local`` the running sum inside a block) on each row
    of the OTHER block, zero on its own: the even block's on the odd one's
    rows (``onto_odd``) or the odd block's on the even one's.  Blocks of
    whole tiles are laid out from their broadcast rows; smaller ones spread
    by shifted adds.  Nothing is added to anything but zeros."""
    c, w = local.shape
    if b >= _ROW_TILE:
        zeros, pieces = jnp.zeros((b, w), local.dtype), []
        for lo in range(0, c, 2 * b):
            last = lo + b if onto_odd else lo + 2 * b
            total = jnp.broadcast_to(local[last - 1: last], (b, w))
            pieces += [zeros, total] if onto_odd else [total, zeros]
        return jnp.concatenate(pieces, axis=0)
    if onto_odd:
        x = roll(jnp.where(place == b - 1, local, 0.0), 1)
    else:
        x = roll(jnp.where(place == 2 * b - 1, local, 0.0), c - b)
    shift = 1
    while shift < b:
        x = x + roll(x, shift if onto_odd else c - shift)
        shift *= 2
    return x


def _exact_pairs(q32, k32, g, tok, roll, row, col, cd):
    """``A'`` and ``M`` for ANY ``g <= 0`` (the module's text): the
    triangle cut by halves, a level of blocks of ``b`` = 1, 2, .. C / 2
    tokens pairing the rows of every odd block with the columns of the
    even block before it, each decay split at the boundary between the
    two, so that both factors are sums of ``g`` over tokens BETWEEN column
    and row and at most 1.  Both sums are built inside their blocks, a
    level's from the level below (:func:`_sibling_total`), never as
    differences of running sums, whose rounding would grow with the decay
    behind them.  ``(the chunk's running sum, the sum AFTER each token to
    the chunk's end, kk, qk, what the backward reads)``."""
    c = k32.shape[0]
    since = g                    # the sum of g from the block's start on
    until = jnp.zeros_like(g)    # the sum of g after the token, to its end
    levels = []
    kk = qk = jnp.zeros((c, c), F32)
    for level in range(c.bit_length() - 1):
        b = 1 << level
        place = tok & (2 * b - 1)
        odd = place >= b
        # rows of even blocks, columns of odd ones: masked BEFORE the
        # exponential
        e_row = jnp.exp(jnp.where(odd, since, -jnp.inf))
        e_col = jnp.exp(jnp.where(odd, -jnp.inf, until))
        rows = jnp.concatenate(
            [(k32 * e_row).astype(cd), (q32 * e_row).astype(cd)], axis=0
        )
        k_c = (k32 * e_col).astype(cd)
        band = _dot(rows, k_c, _NT)                        # [2 C, C]
        live = _lower_left(row, col, level)
        kk = jnp.where(live, band[:c], kk)
        qk = jnp.where(live, band[c:], qk)
        levels.append(types.SimpleNamespace(
            e_row=e_row, e_col=e_col, rows=rows, k_c=k_c, live=live,
        ))
        before = _sibling_total(since, place, b, roll, onto_odd=True)
        after = _sibling_total(since, place, b, roll, onto_odd=False)
        since, until = since + before, until + after
    # a token against itself decays by nothing
    qk = jnp.where(
        col == row, jnp.sum(q32 * k32, axis=1, keepdims=True), qk
    )
    return since, until, kk, qk, levels


def _exact_pairs_bwd(levels, d_kk, d_qk, q32, k32, eye, cd):
    """``(d_q, d_k, d_G)`` of :func:`_exact_pairs`' ``kk`` and ``qk``.  A
    pair's decay is ``exp(G_t - G_i)`` whatever boundary it was split at,
    so the boundary takes no gradient: a row's goes to ``G_t``, a
    column's from ``G_i``."""
    c = k32.shape[0]
    d_diag = jnp.sum(jnp.where(eye, d_qk, 0.0), axis=1, keepdims=True)
    d_q, d_k = d_diag * k32, d_diag * q32
    d_run = jnp.zeros_like(k32)
    for x in levels:
        d_band = jnp.concatenate([
            jnp.where(x.live, d_kk, 0.0), jnp.where(x.live, d_qk, 0.0),
        ], axis=0).astype(cd)                              # [2 C, C]
        d_rows = _dot(d_band, x.k_c)                       # [2 C, dk]
        d_k_r, d_q_r = d_rows[:c] * x.e_row, d_rows[c:] * x.e_row
        d_k_c = _dot(d_band, x.rows, _TN) * x.e_col        # [C, dk]
        d_q = d_q + d_q_r
        d_k = d_k + d_k_r + d_k_c
        d_run = d_run + d_k_r * k32 + d_q_r * q32 - d_k_c * k32
    return d_q, d_k, d_run


def _chunk_tensors(q, k, v, g, beta_row, start, roll, exact=False):
    """What a chunk builds from its own tokens and the state it starts
    from, forward and backward alike, in a head's stages (a generator).
    ``q``, ``k`` [C, dk], ``v`` [C, dv],
    ``g`` [C, dk] float32, ``beta_row`` [1, C] float32, ``start`` [dv, dk]
    in the operands' dtype.  ``exact``: the pairs' decays for any ``g <=
    0`` (:func:`_exact_pairs`), not under a bound (:func:`_split_pairs`)."""
    cd = v.dtype
    c, dk = k.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    lower, strict, eye = col <= row, col < row, col == row
    tok = jax.lax.broadcasted_iota(jnp.int32, (c, dk), 0)
    if exact:
        q32, k32 = q.astype(F32), k.astype(F32)
        run, to_end, kk, qk, pairs = _exact_pairs(
            q32, k32, g, tok, roll, row, col, cd
        )
        total = run[c - 1:, :]                             # G_C  [1, dk]
    else:
        q32, k32, run, total, kk, qk, pairs = _split_pairs(
            q, k, g, tok, roll, strict, lower, cd
        )
        to_end = None
    beta_col = _to_col(beta_row, eye)
    yield
    t = yield from _unit_lower_inverse(
        kk * beta_col, row, col, exact=cd == F32
    )
    gamma = jnp.exp(run)
    # the decay from a token to the chunk's end
    e_end = jnp.exp(total - run if to_end is None else to_end)
    k_g, q_g = (k32 * gamma).astype(cd), (q32 * gamma).astype(cd)
    t_b = (t * beta_row).astype(cd)
    w = _dot(t_b, k_g).astype(cd)
    yield
    writes = (_dot(t_b, v) - _dot(w, start, _NT)).astype(cd)         # U
    yield
    return types.SimpleNamespace(
        lower=lower, strict=strict, eye=eye, tok=tok, q32=q32, k32=k32,
        pairs=pairs, kk=kk, within=qk.astype(cd), beta_col=beta_col, t=t,
        gamma=gamma, e_end=e_end, gamma_end=jnp.exp(total), k_g=k_g,
        q_g=q_g, t_b=t_b, w=w,
        writes=writes,
        k_end=(k32 * e_end).astype(cd),
    )


def _chunk_forward(x, state, start):
    """``(o [C, dv] float32, the chunk's end state [dv, dk] float32)``."""
    o = _dot(x.q_g, start, _NT) + _dot(x.within, x.writes)
    return o, state * x.gamma_end + _dot(x.writes, x.k_end, _TN)


def _absmax(a):
    return jnp.max(
        jnp.max(jnp.abs(a), axis=1, keepdims=True), axis=0, keepdims=True
    )


def _roll_rows(x, n):
    return pltpu.roll(x, n, 0)


def _fwd_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, start_ref, top_ref, state,
    *, heads, exact_pairs,
):
    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)
        top_ref[...] = jnp.zeros_like(top_ref)

    def head(h):
        v = v_ref[h]
        start = state[h].astype(v.dtype)                   # [dv, dk]
        start_ref[h, 0] = start
        x = yield from _chunk_tensors(
            q_ref[h], k_ref[h], v, g_ref[h], beta_ref[h, 0], start,
            _roll_rows, exact_pairs,
        )
        o, end = _chunk_forward(x, state[h], start)
        state[h] = end
        top_ref[h] = jnp.maximum(top_ref[h], _absmax(end))
        o_ref[h] = o.astype(o_ref.dtype)

    _in_lockstep(head(h) for h in range(heads))


def _bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, start_ref, do_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, d_state,
    *, heads, exact_pairs,
):
    """One chunk, walked last to first.  ``d_state`` holds the cotangent
    of the chunk's END state on entry and of its start state on exit."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    def head(h):
        v, do = v_ref[h], do_ref[h]
        cd = v.dtype
        exact = cd == F32
        beta_row = beta_ref[h, 0]
        start = start_ref[h, 0]                            # [dv, dk]
        x = yield from _chunk_tensors(
            q_ref[h], k_ref[h], v, g_ref[h], beta_row, start, _roll_rows,
            exact_pairs,
        )
        c = v.shape[0]
        q32, k32, tok = x.q32, x.k32, x.tok
        d_end = d_state[h]
        d_end_cd = d_end.astype(cd)

        # O = q_g S^T + M U;  S' = gamma_C S + U^T k_end
        d_u = _dot(x.within, do, _TN) + _dot(x.k_end, d_end_cd, _NT)
        d_u_cd = d_u.astype(cd)
        d_qk = jnp.where(x.lower, _dot(do, x.writes, _NT), 0.0)
        d_q_g = _dot(do, start)
        d_k_end = _dot(x.writes, d_end_cd)
        yield
        d_gamma_end = jnp.sum(
            d_end * start.astype(F32), axis=0, keepdims=True
        )
        # U = t_b V - W S^T
        d_state[h] = (
            d_end * x.gamma_end + _dot(do, x.q_g, _TN)
            - _dot(d_u_cd, x.w, _TN)
        )
        d_w = (-_dot(d_u_cd, start)).astype(cd)
        yield
        # W = t_b k_g with t_b = T * beta (columns)
        d_t_b = _dot(d_w, x.k_g, _NT) + _dot(d_u_cd, v, _NT)
        d_k_g = _dot(x.t_b, d_w, _TN)
        dv_ref[h] = _dot(x.t_b, d_u_cd, _TN).astype(dv_ref.dtype)
        d_beta_row = jnp.sum(d_t_b * x.t, axis=0, keepdims=True)
        yield
        # T = (I + A)^-1:  dA = -T^T dT T^T
        t_halves = _halves(x.t, exact)
        t_dt = _halves(
            _mm_f32(t_halves, _halves(d_t_b * beta_row, exact), _TN), exact
        )
        yield
        d_a = _mm_f32(t_dt, t_halves, _NT)
        yield
        d_a = jnp.where(x.strict, -d_a, 0.0)
        # A = beta_t A'
        d_kk = d_a * x.beta_col
        d_beta_col = jnp.sum(d_a * x.kk, axis=1, keepdims=True)
        if exact_pairs:
            d_q_p, d_k_p, d_run = _exact_pairs_bwd(
                x.pairs, d_kk, d_qk, q32, k32, x.eye, cd
            )
        else:
            d_q_p, d_k_p, d_run = _split_pairs_bwd(
                x.pairs, d_kk, d_qk, q32, k32, tok, cd
            )
        # k_g = k gamma, q_g = q gamma, k_end = k e_end
        d_k_g, d_q_g = d_k_g * x.gamma, d_q_g * x.gamma
        d_k_end = d_k_end * x.e_end
        z_end = d_k_end * k32
        d_run = d_run + d_k_g * k32 + d_q_g * q32 - z_end
        d_total = (
            jnp.sum(z_end, axis=0, keepdims=True) + d_gamma_end * x.gamma_end
        )
        d_run = d_run + jnp.where(tok == c - 1, d_total, 0.0)
        # G = cumsum(g):  dg_t = the sum of dG_j over j >= t
        dg_ref[h] = (
            jnp.sum(d_run, axis=0, keepdims=True)
            - _cumsum_rows(d_run, tok, _roll_rows) + d_run
        )
        dbeta_ref[h, 0] = d_beta_row + _to_row(d_beta_col, x.eye)
        dq_ref[h] = (d_q_p + d_q_g).astype(dq_ref.dtype)
        dk_ref[h] = (d_k_p + d_k_g + d_k_end).astype(dk_ref.dtype)

    _in_lockstep(head(h) for h in range(heads))


def _heads_per_step(heads: int) -> int:
    return max(n for n in range(1, _HEADS_PER_STEP + 1) if heads % n == 0)


def _specs(group, chunk, widths, index):
    return [pl.BlockSpec((group, chunk, width), index) for width in widths]


@functools.partial(jax.jit, static_argnames="exact")
def _forward(q, k, v, g, beta, exact=False):
    """``q, k`` [BH, S, dk], ``v`` [BH, S, dv], ``g`` [BH, S, dk] float32,
    ``beta`` [BH, N, 1, C] float32.  Returns ``o`` [BH, S, dv], the chunks'
    start states [BH, N, dv, dk] (both in ``v``'s dtype) and each head's
    largest ``|S|`` at a chunk's end [BH]."""
    heads, s, dk = q.shape
    dv = v.shape[-1]
    n, chunk = beta.shape[1], beta.shape[-1]
    group = _heads_per_step(heads)

    def tokens(i, c):
        return (i, c, 0)

    def scalars(i, c):
        return (i, c, 0, 0)

    o, starts, top = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=group, exact_pairs=exact),
        grid=(heads // group, n),
        in_specs=_specs(group, chunk, (dk, dk, dv, dk), tokens) + [
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
        name="kda_fwd",
    )(q, k, v, g, beta)
    return o, starts, top[:, 0, 0]


@functools.partial(jax.jit, static_argnames="exact")
def _backward(q, k, v, g, beta, starts, do, exact=False):
    heads, s, dk = q.shape
    dv = v.shape[-1]
    n, chunk = beta.shape[1], beta.shape[-1]
    group = _heads_per_step(heads)

    def tokens(i, c):
        return (i, n - 1 - c, 0)

    def scalars(i, c):
        return (i, n - 1 - c, 0, 0)

    per_token = pl.BlockSpec((group, 1, 1, chunk), scalars)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=group, exact_pairs=exact),
        grid=(heads // group, n),
        in_specs=_specs(group, chunk, (dk, dk, dv, dk), tokens) + [
            per_token, pl.BlockSpec((group, 1, dv, dk), scalars),
        ] + _specs(group, chunk, (dv,), tokens),
        out_specs=_specs(group, chunk, (dk, dk, dv, dk), tokens) + [
            per_token,
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
        name="kda_bwd",
    )(q, k, v, g, beta, starts, do)


@functools.cache
def _kernel_rule(exact: bool):
    """The kernels under their custom VJP, in the form ``exact`` names."""

    def rule_fwd(q, k, v, g, beta):
        o, starts, top = _forward(q, k, v, g, beta, exact=exact)
        # kept by ``flash_only`` beside the mixer's ``kda_out``: the
        # replayed forward kernel then has no live output and the backward
        # reads these (ops/remat_policy.py).  Under any other policy the
        # name is a no-op.
        starts = jax.ad_checkpoint.checkpoint_name(starts, "kda_states")
        return (o, top), (q, k, v, g, beta, starts)

    @jax.custom_vjp
    def rule(q, k, v, g, beta):
        return rule_fwd(q, k, v, g, beta)[0]

    def rule_bwd(res, cts):
        do, _ = cts      # the largest |S| is a reading, not a result
        return tuple(_backward(*res, do, exact=exact))

    rule.defvjp(rule_fwd, rule_bwd)
    return rule


def _rule_xla(q, k, v, g, beta, exact=False):
    """:func:`_kernel_rule` in ``jax.numpy``: the chunk's mathematics under
    ``vmap`` over the heads and ``lax.scan`` over the chunks."""
    heads, s, dk = q.shape
    dv = v.shape[-1]
    n, chunk = beta.shape[1], beta.shape[-1]

    def chunks(a):
        return jnp.moveaxis(a.reshape(heads, n, chunk, a.shape[-1]), 1, 0)

    def roll(x, shift):
        return jnp.roll(x, shift, axis=0)

    def one_head(state, q, k, v, g, beta_row):
        start = state.astype(v.dtype)
        x = _alone(
            _chunk_tensors(q, k, v, g, beta_row, start, roll, exact)
        )
        o, end = _chunk_forward(x, state, start)
        return o.astype(v.dtype), end

    def step(carry, xs):
        state, top = carry
        o, end = jax.vmap(one_head)(state, *xs)
        return (end, jnp.maximum(top, jnp.abs(end).max(axis=(1, 2)))), o

    (_, top), o = jax.lax.scan(
        step,
        (jnp.zeros((heads, dv, dk), F32), jnp.zeros((heads,), F32)),
        (chunks(q), chunks(k), chunks(v), chunks(g), jnp.moveaxis(beta, 1, 0)),
    )
    return jnp.moveaxis(o, 0, 1).reshape(heads, s, dv), top


def plan(key_dim: int, value_dim: int, exact: bool = False) -> str:
    """``kernel`` where :func:`kda` runs the Pallas kernels on heads of
    these widths (both whole lane tiles), ``xla`` where it runs the chunked
    ``jax.numpy`` form; ``kernel_exact`` / ``xla_exact`` for the form that
    holds for any ``g <= 0``."""
    path = "xla" if key_dim % LANES or value_dim % LANES else "kernel"
    return path + "_exact" if exact else path


def kda(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    chunk: int = CHUNK,
    exact: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """``q, k`` [B, S, H, dk] (already normalised and scaled), ``v``
    [B, S, H, dv], ``g`` [B, S, H, dk] (log decay a channel, ``<= 0``;
    above ``-88 / SUB`` a token unless ``exact``: see the module's text)
    and ``beta`` [B, S, H] (0 .. 2).
    Returns the outputs [B, S, H, dv] in ``v``'s dtype and, under
    ``stop_gradient``, the largest ``|S|`` entry at any chunk boundary.

    A sequence that is no whole number of chunks is padded with tokens
    that neither write (``beta`` 0) nor decay (``g`` 0).  ``chunk`` is a
    power of two, whole sub-chunks and whole tiles of the operands'
    dtype."""
    cd = v.dtype
    if chunk & (chunk - 1) or chunk % SUB or chunk % tile_rows(cd):
        raise ValueError(
            f"chunk must be a power of two, a multiple of the {SUB}-token "
            f"sub-chunk and of the {tile_rows(cd)} rows of a "
            f"{jnp.dtype(cd).name} tile, got {chunk}"
        )
    if q.dtype != cd or k.dtype != cd:
        raise ValueError(
            f"q, k, v must share a dtype, got {q.dtype}, {k.dtype}, {cd}"
        )
    b, s, h, dk = q.shape
    pad = -s % chunk
    n = (s + pad) // chunk

    def heads_first(a):
        """[B, S, H, ...] -> [B * H, S + pad, ...]"""
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        return jnp.moveaxis(a, 2, 1).reshape(b * h, s + pad, *a.shape[3:])

    if plan(dk, v.shape[-1]) == "kernel":
        rule = _kernel_rule(exact)
    else:
        rule = functools.partial(_rule_xla, exact=exact)
    o, top = rule(
        heads_first(q), heads_first(k), heads_first(v),
        heads_first(g.astype(F32)),
        heads_first(beta.astype(F32)).reshape(b * h, n, 1, chunk),
    )
    o = jnp.moveaxis(o.reshape(b, h, s + pad, -1), 1, 2)
    return o[:, :s], jax.lax.stop_gradient(jnp.max(top))
