"""Pallas TPU flash attention: fwd + bwd, causal/segment masks, GQA, and
values of another width than the keys (``d_qk != d_v``).

Capability ref: the reference's flash-attention integration layer
(``atorch/atorch/modules/transformer/layers.py:1278-1640``: FA wrappers with
GLM/pack custom masks; ``tfplus/flash_attn/kernels/*``) — rebuilt as native
TPU kernels rather than bindings.  Online-softmax tiling keeps the S x S
score matrix out of HBM; the backward recomputes scores blockwise (flash-2
style), so activation memory is O(S * D) instead of O(S^2).

Block layout: grid (batch, q_heads, q_blocks, kv_blocks) with the kv axis
innermost so the running (m, l, acc) state lives in VMEM scratch across kv
steps.  With ONE kv block there is no state: a tile's rows are normalised
and written straight out (the state's ``[rows, 1]`` column arithmetic, its
stores and the finalize cost GPT-2's forward 0.4 of 1.65 ms a layer).

A block's class.  Under a causal mask a ``(q block, kv block)`` pair is one
of three things, a function of the shapes alone (:func:`block_classes`
counts them, ``_block_class`` tells them apart inside a kernel), and every
kernel here, forward, one-pass backward and the split pair, does only its
class's work:

* **dead** (the q block's last row precedes the kv block's first column):
  nothing is computed (``pl.when``) and nothing is fetched.  A grid step
  still happens, so its index maps name a block that is already there: the
  kv-inner kernels (forward, ``_bwd_dq_kernel``) keep k, v and the kv
  segment ids on the q block's LAST LIVE kv block (``_last_live_kv``), the
  q-inner ones (``_bwd_fused_kernel``, ``_bwd_dkv_kernel``) keep q, do, o,
  lse and the q segment ids on the kv block's FIRST LIVE q block
  (``_first_live_q``), and the pipeline copies nothing for an index that
  did not change.
* **interior** (every row sees every column): no causal mask is built, no
  iota, no compare.
* **diagonal** (the rest).  Where ``block_q == block_kv`` the block is
  worked as ROW STRIPS that end at the diagonal: strip ``r`` of
  ``_strip_rows`` rows takes ``q[r h : (r + 1) h]`` against the first
  ``(r + 1) h`` rows of k and v, static slices of blocks already in VMEM,
  and updates only its own rows of m, l, acc (forward) or of dq, and the
  first ``(r + 1) h`` rows of the dk / dv accumulators (backward).  Four
  strips do 10 of a block's 16 sub-tiles.  A row sees the same columns as
  in the masked square, so every sum holds the same terms.  Blocks of other
  shapes (``block_q != block_kv``, or too small for two strips of whole
  128-lane tiles) keep the masked square.

Non-causal attention has interior blocks only.

A window.  ``window=W`` keeps a BAND of the causal triangle: row ``i`` sees
column ``j`` iff ``0 <= i - j < W`` (itself and the ``W - 1`` before it).
The band has a second edge, so a block is also **dead** when it lies wholly
BELOW the band, and an edge block is one of three: crossed by the diagonal
alone (worked as without a window, row strips and all), by the band's LOWER
edge alone, or by both (``W`` no wider than a block: the masked square).
Where ``W`` is whole blocks of one size that hold strips, a lower-edge
block lies at ``iq - ik == W // block`` and its edge is its own diagonal,
row ``i`` seeing ``j > i``: it is worked as the diagonal block's MIRROR,
strip ``r`` taking ``q[r h : (r + 1) h]`` against columns ``[r h, block)``
(``_tiles(lower=True)``, ``BandClasses.lower_strip``), so that a q block of
one diagonal and one lower-edge block does 1.25 blocks' work for 1.0 live
where the masked square did 1.625; any other window's lower edge crosses
two blocks a q block at traced offsets and keeps the masked square under
``j > i - W``.  Under a band every block of a q block may be an edge block
and every tile a strip, so the forward and the one-pass backward advance a
block's strips IN LOCKSTEP (``_advance``: a strip is a generator that
yields where it has just asked the matrix unit for a product, and the
strips take their stages in turn; the numbers are the loop's, bit for
bit).  A (batch, head) of 32,768 tokens at blocks of 1,024
has 528 live blocks under the causal mask and 63 under a band of 1,024, so
the grids do not walk the dead ones either: the kv-inner kernels make
``kv_steps`` steps a q block (the most kv blocks the band gives a q block),
step ``t`` naming kv block ``first live + t``, and the q-inner kernels
``q_steps`` a kv block alike (:class:`BandClasses`; on a v5e at 32,768
tokens the banded forward read 11.96 ms so against 15.13 with the grid over
the whole sequence, the backward 18.43 against 24.79: PERF.md §6 PR 54).
A step past a block's last live partner is dead and names that partner
(``_last_live_kv`` / ``_first_live_q`` clamp on both sides:
``_band_first_kv``, ``_band_last_q``).  The one-pass backward's dq rows are assigned at a q
block's FIRST live kv block and written at its last.  Without a window
every kernel lowers to the text it had before there was one.

The segment compare is built only where ids were given or a length was
padded (``segments``, a static fact of the call :func:`mha` works out);
then every class keeps it, interior blocks too, and all-masked rows are
guarded.  Without it a row always sees column 0, so its running maximum is
finite after the first kv block and the guards go as well.  The segment
operands stay in every kernel's signature, unread.  ``scale`` rides the
``[rows, d]`` q tile where that is exact, a power of two (head 64), and
the ``[rows, cols]`` score tile otherwise: rounding ``q * scale`` to
bfloat16 would move the scores.

Backward: ONE pass (``_bwd_fused_kernel``: s, the mask, p, dp and ds once
a block feed dq, dk and dv; 5 matmuls a block) wherever its dq accumulator
fits VMEM, else the split pair (``_bwd_dq_kernel`` then ``_bwd_dkv_kernel``,
7 matmuls, every score block computed twice).  The choice is a function of
the shapes, :func:`backward_path`.  The one pass sweeps the kv blocks on the
outer grid axis, so a dq block is revisited non-consecutively, which an
output block may not be and a scratch may.  With one kv block there is
nothing to accumulate.  With several, a float32 scratch ``[S_q, d]`` holds
the whole sequence's dq of one (batch, head) and the dq output block is the
whole sequence, resident until the head changes: ``S_q * lanes(d) * (4 + 2 *
itemsize)`` bytes, 16 MiB at 8192 x 192 in bf16 (192 occupies 256 lanes), 8
at 8192 x 128, beside 23.5 / 21.5 MiB of blocks and temporaries at blocks of
1024.  The kernel asks for what :func:`_fused_bwd_vmem_bytes` counts as its
``vmem_limit_bytes``, and is chosen while that is within ``_VMEM_CAP`` = 64
MiB (half a v5e core's VMEM): in bf16 at blocks of 1024 up to 43008 tokens
at head 128 and 20480 at 192 / 128; longer sequences keep the split pair.
No float32 dq crosses HBM either way.

Padding: sequence lengths are padded to the block size by the wrapper; the
pad region is masked via an implicit segment id (pad tokens attend nowhere).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops import backend
from dlrover_tpu.ops.gated_delta_rule import _in_lockstep

NEG_INF = -1e30
_LANE = 128
# Row statistics (lse/delta) ride [.., S, _STAT] arrays: 8 lanes (one f32
# sublane tile) instead of 128 cuts their HBM footprint/traffic 16x — at
# bench shapes that is ~200 MB of pure padding per layer per tensor.
_STAT = 8
# The most VMEM the one-pass backward may ask for: half of a v5e core's
# 128 MiB (a kernel that asks for nothing gets 16 MiB).
_VMEM_CAP = 64 << 20
# Most rows of a diagonal block's strip (chosen on a v5e, PERF.md §6 PR 36).
_STRIP = 256


def _pad_to(x, size, axis, value=0):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _blocks_and_padding(sq, skv, block_q, block_kv):
    """Blocks clamped to the (pow2-padded) sequence, floor of 16 so that the
    sublane tile stays valid for bf16 when the whole sequence is one block,
    and both lengths padded to whole blocks."""
    block_q = min(block_q, max(16, 1 << (sq - 1).bit_length()))
    block_kv = min(block_kv, max(16, 1 << (skv - 1).bit_length()))
    return (
        block_q, block_kv,
        -(-sq // block_q) * block_q, -(-skv // block_kv) * block_kv,
    )


# ---------------------------------------------------------------------------
# a block's class
# ---------------------------------------------------------------------------


class BlockClasses(NamedTuple):
    """How many blocks of each class one (batch, head) holds, and the rows
    of a diagonal block's strips (0: the masked square)."""

    dead: int
    interior: int
    diagonal: int
    strip: int


class BandClasses(NamedTuple):
    """:class:`BlockClasses` under a window: ``diagonal`` counts the blocks
    the diagonal alone crosses, ``lower`` those the band's lower edge alone
    crosses, ``both`` those that carry both edges; ``kv_steps`` / ``q_steps``
    are the inner grid steps of the kv-inner / q-inner kernels;
    ``lower_strip`` the rows of a lower-edge block's strips (0: the masked
    square)."""

    dead: int
    interior: int
    diagonal: int
    strip: int
    lower: int
    both: int
    kv_steps: int
    q_steps: int
    lower_strip: int


def _pair_range(iq, ik, block_q, block_kv):
    """The least and the most ``i - j`` over the pairs of block ``(iq,
    ik)``."""
    q_start, kv_start = iq * block_q, ik * block_kv
    return q_start - (kv_start + block_kv - 1), q_start + block_q - 1 - kv_start


def _block_edges(iq, ik, block_q, block_kv, window):
    """``(live, diagonal, lower)`` of block ``(iq, ik)`` under a causal band
    of ``window`` keys, Python, numpy or traced: whether any pair of it is
    live, whether the diagonal crosses it, whether the band's lower edge
    does.  A live block that neither crosses is interior."""
    least, most = _pair_range(iq, ik, block_q, block_kv)
    live = (most >= 0) & (least < window)
    return live, live & (least < 0), live & (most >= window)


def _block_class(iq, ik, block_q, block_kv, causal):
    """``(dead, interior)`` of block ``(iq, ik)``, Python or traced; a block
    that is neither is diagonal.  Under a window: :func:`_block_edges`."""
    if not causal:
        return False, True
    q_start, kv_start = iq * block_q, ik * block_kv
    return (
        q_start + block_q - 1 < kv_start,
        kv_start + block_kv - 1 <= q_start,
    )


def _strip_rows(block_q, block_kv) -> int:
    """Rows of a diagonal block's strips, 0 to keep the masked square.  A
    strip's width lands on the score tile's lanes and its height on q's
    sublanes, so it is whole 128-lane tiles, and a block holds at least
    two."""
    rows = min(_STRIP, block_q // 2)
    if block_q != block_kv or rows % _LANE or block_q % rows:
        return 0
    return rows


def block_classes(sq, skv, block_q, block_kv, causal, window=None):
    """The classes of an attention of these sizes, given as :func:`mha`'s
    caller gives them (clamped and padded here as there):
    :class:`BlockClasses`, or :class:`BandClasses` under a ``window``."""
    block_q, block_kv, sq, skv = _blocks_and_padding(
        sq, skv, block_q, block_kv
    )
    nq, nk = sq // block_q, skv // block_kv
    if window is not None:
        return _band_classes(nq, nk, block_q, block_kv, window)
    if not causal:
        return BlockClasses(0, nq * nk, 0, 0)
    dead = interior = 0
    for iq in range(nq):
        for ik in range(nk):
            is_dead, is_interior = _block_class(iq, ik, block_q, block_kv, True)
            dead += is_dead
            interior += is_interior
    diagonal = nq * nk - dead - interior
    strip = _strip_rows(block_q, block_kv) if diagonal else 0
    return BlockClasses(dead, interior, diagonal, strip)


def _band_classes(nq, nk, block_q, block_kv, window) -> BandClasses:
    iq, ik = np.arange(nq)[:, None], np.arange(nk)[None, :]
    live, diagonal, lower = _block_edges(iq, ik, block_q, block_kv, window)
    both = diagonal & lower
    edge = diagonal | lower
    count = lambda blocks: int(blocks.sum())
    strip = _strip_rows(block_q, block_kv)
    # A window of whole blocks puts the lower edge on the diagonal of the
    # blocks it crosses (``iq - ik == window // block_kv``: row i of one
    # sees column j iff j > i), where strips can follow it; any other
    # window's edge crosses two blocks a q block at offsets of its own.
    on_block_diagonal = window % block_kv == 0
    return BandClasses(
        dead=nq * nk - count(live), interior=count(live & ~edge),
        diagonal=count(diagonal & ~both),
        strip=strip if (diagonal & ~both).any() else 0,
        lower=count(lower & ~both), both=count(both),
        kv_steps=max(1, int(live.sum(axis=1).max())),
        q_steps=max(1, int(live.sum(axis=0).max())),
        lower_strip=strip if on_block_diagonal and (lower & ~both).any()
        else 0,
    )


def _band_first_kv(iq, block_q, block_kv, window):
    """The first kv block a q block sees under a band: the one that holds
    the first column its first row sees."""
    return jnp.maximum(iq * block_q - (window - 1), 0) // block_kv


def _band_last_q(ik, nq, block_q, block_kv, window):
    """The last q block that sees a kv block under a band: the one that
    holds the last row its last column is seen by."""
    return jnp.minimum(
        (ik * block_kv + block_kv - 1 + window - 1) // block_q, nq - 1
    )


def _last_live_kv(iq, ik, block_q, block_kv, causal, window=None):
    """The kv block a kv-inner grid step names: its own, or on a dead step
    the q block's last live one, which is already in VMEM (under a
    ``window`` a step BELOW the band names the first live one)."""
    if not causal:
        return ik
    ik = jnp.minimum(ik, (iq * block_q + block_q - 1) // block_kv)
    if window is not None:
        ik = jnp.maximum(ik, _band_first_kv(iq, block_q, block_kv, window))
    return ik


def _first_live_q(iq, ik, nq, block_q, block_kv, causal, window=None):
    """The q block a q-inner grid step names: its own, or on a dead step
    the kv block's first live one, which the pipeline then fetches once and
    not for every dead step (JoyAI's layer 35.0 -> 31.2 ms on a v5e,
    PERF.md §6 PR 34).  A kv block past the last q row (more keys than
    queries) has no live q block: its steps stay on the last of the
    ``nq``.  Under a ``window`` a step past the band names the kv block's
    LAST live q block."""
    if not causal:
        return iq
    iq = jnp.maximum(iq, jnp.minimum((ik * block_kv) // block_q, nq - 1))
    if window is not None:
        iq = jnp.minimum(
            iq, _band_last_q(ik, nq, block_q, block_kv, window)
        )
    return iq


def _kv_of_step(iq, step, block_q, block_kv, window):
    """The kv block of a kv-inner grid step: without a window the step's
    own number, under one the q block's first live kv block and on."""
    if window is None:
        return step
    return _band_first_kv(iq, block_q, block_kv, window) + step


def _q_of_step(ik, step, nq, block_q, block_kv, window):
    """The q block of a q-inner grid step, as :func:`_kv_of_step`."""
    if window is None:
        return step
    return jnp.minimum((ik * block_kv) // block_q, nq - 1) + step


def _tiles(block_q, block_kv, strip, lower=False):
    """Static ``(rows, cols)`` slices that cover a block's live part: the
    whole block, or the strips of a diagonal one (each ends at the
    diagonal), or of a ``lower`` one, the band's lower edge on its own
    diagonal (each starts there)."""
    if not strip:
        return [(slice(0, block_q), slice(0, block_kv))]
    return [
        (
            slice(r * strip, (r + 1) * strip),
            slice(r * strip, block_kv) if lower
            else slice(0, (r + 1) * strip),
        )
        for r in range(block_q // strip)
    ]


def _one_by_one(tiles):
    """Runs a block's tiles, each a generator of one tile's stages (it
    yields where it has just asked the matrix unit for a product the next
    stage waits for), each to its end before the next one's first stage:
    the program order of a plain loop over the tiles."""
    for tile in tiles:
        for _ in tile:
            pass


def _advance(window):
    """How a kernel that can keep several tiles in flight runs a block's
    tiles: under a ``window`` every block of a q block is an edge block
    and every tile a strip, an independent chain (its rows of m, l, acc
    and dq are its own, and every strip adds to dk / dv at the same
    stage, so the adds keep the loop's order), so the strips take their
    stages in turn (``gated_delta_rule._in_lockstep``: only the program's
    order changes, every number is the loop's).  Without a window the
    loop, and the kernel's text, are what they were."""
    return _one_by_one if window is None else _in_lockstep


def _for_live_class(
    iq, ik, compute, *, causal, block_q, block_kv, classes, window=None,
    q_blocks=0,
):
    """Run ``compute(causal_offset, tiles)`` as block ``(iq, ik)``'s class
    asks: not at all (dead), over the whole block with no causal mask
    (interior, ``causal_offset`` None), or over a diagonal block's tiles,
    where row ``i`` of the block sees column ``j`` while ``i + causal_offset
    >= j``.  A class the shapes do not hold gets no branch.  Under a
    ``window``, ``compute(causal_offset, tiles, band_offset)``: an edge block
    masks the edges that cross it, the lower one ``i + band_offset < j +
    window``; ``q_blocks`` (the q-inner kernels give it) is where the q
    blocks end, since a kv block's last steps may lie past the sequence."""
    whole = _tiles(block_q, block_kv, 0)
    if not causal:
        compute(None, whole)
        return
    if window is not None:
        live, diagonal, lower = _block_edges(
            iq, ik, block_q, block_kv, window
        )
        if q_blocks:
            live, diagonal, lower = (
                kind & (iq < q_blocks) for kind in (live, diagonal, lower)
            )
        offset = iq * block_q - ik * block_kv
        # as below: blocks of one size meet the diagonal where iq == ik
        on_diagonal = 0 if block_q == block_kv else offset
        no = jnp.logical_not
        if classes.interior:
            pl.when(live & no(diagonal | lower))(
                lambda: compute(None, whole)
            )
        if classes.diagonal:
            pl.when(diagonal & no(lower))(lambda: compute(
                on_diagonal, _tiles(block_q, block_kv, classes.strip)
            ))
        if classes.lower:
            # in strips the edge lies on the block's own diagonal: the
            # offset is the window, static, and the mask a constant
            pl.when(lower & no(diagonal))(lambda: compute(
                None,
                _tiles(block_q, block_kv, classes.lower_strip, lower=True),
                window if classes.lower_strip else offset,
            ))
        if classes.both:
            pl.when(lower & diagonal)(
                lambda: compute(on_diagonal, whole, on_diagonal)
            )
        return
    dead, interior = _block_class(iq, ik, block_q, block_kv, True)
    if classes.interior:
        pl.when(interior)(lambda: compute(None, whole))
    if classes.diagonal:
        # Blocks of one size meet the diagonal where iq == ik: a static
        # offset, so the mask is a constant (JoyAI's step 1738.5 -> 1735.6
        # ms on a v5e against the traced difference, PERF.md §6 PR 36).
        offset = 0 if block_q == block_kv else iq * block_q - ik * block_kv
        pl.when(jnp.logical_not(jnp.logical_or(dead, interior)))(
            lambda: compute(
                offset, _tiles(block_q, block_kv, classes.strip)
            )
        )


def _masked(x, fill, rows, cols, causal_offset, seg_q_ref, seg_kv_ref,
            segments, band=None):
    """``x``, the scores or probabilities of tile ``(rows, cols)`` of a
    block, with ``fill`` where a row may not see a column; ``x`` itself
    where nothing is masked.  ``band``: ``(band_offset, window)`` where the
    band's lower edge crosses the block."""
    mask = None
    if causal_offset is not None or band is not None:
        shape = (rows.stop - rows.start, cols.stop - cols.start)
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if causal_offset is not None:
        mask = row + (causal_offset + rows.start - cols.start) >= col
    if band is not None:
        offset, window = band
        below = row + (offset + rows.start - cols.start - window) < col
        mask = below if mask is None else jnp.logical_and(mask, below)
    if segments:
        seg = seg_q_ref[0, 0, rows][:, None] == seg_kv_ref[0, 0, cols][None, :]
        mask = seg if mask is None else jnp.logical_and(mask, seg)
    return x if mask is None else jnp.where(mask, x, fill)


def _band(band_offset, window):
    """``_masked``'s ``band`` of a block's tile: none where the lower edge
    does not cross it."""
    return None if band_offset is None else (band_offset, window)


def _scores(q, k, scale):
    """``(q k^T) * scale`` in float32: matmul inputs stay bf16 (MXU native
    rate), accumulation is fp32 via preferred_element_type — the standard
    flash-attention numerics.  A power of two scales any float exactly, so
    it rides the narrow q tile and not the score tile."""
    exact = scale > 0 and math.frexp(scale)[0] == 0.5
    s = jax.lax.dot_general(
        q * scale if exact else q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return s if exact else s * scale


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    seg_q_ref, seg_kv_ref, q_ref, k_ref, v_ref,
    o_ref, lse_ref, *state,
    causal: bool, scale: float, block_q: int, block_kv: int,
    classes: BlockClasses, segments: bool, window: Optional[int] = None,
):
    """``state`` is the running (m, l, acc) scratch of several kv blocks.
    With ONE kv block it is empty: every row is visited once, and its tile
    is normalised and written straight out."""
    iq, step = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    ik = _kv_of_step(iq, step, block_q, block_kv, window)

    def _write(rows, m, l, acc):
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, rows, :] = (acc / safe_l).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(safe_l))
        lse_ref[0, 0, rows, :] = jnp.broadcast_to(lse, (lse.shape[0], _STAT))

    if state:
        m_ref, l_ref, acc_ref = state

        @pl.when(step == 0)
        def _init():
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

    def _tile(rows, cols, causal_offset, band_offset):
        v = v_ref[0, 0, cols, :]
        s = _scores(q_ref[0, 0, rows, :], k_ref[0, 0, cols, :], scale)
        yield
        s = _masked(
            s, NEG_INF, rows, cols, causal_offset, seg_q_ref, seg_kv_ref,
            segments, _band(band_offset, window),
        )

        m_new = jnp.max(s, axis=1)[:, None]  # [rows, 1]
        if state:
            m_prev = m_ref[rows, 0][:, None]
            m_new = jnp.maximum(m_prev, m_new)
        p = jnp.exp(s - m_new)
        if segments or band_offset is not None:
            # All-masked rows keep m at NEG_INF; freeze them to avoid
            # inf-inf.  Without segments column 0 is live for every row
            # (under a band, a row's first live block may hold none of
            # the columns it sees).
            p = jnp.where(m_new == NEG_INF, 0.0, p)
        l_new = jnp.sum(p, axis=1)[:, None]
        pv = jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        yield
        if not state:
            _write(rows, m_new, l_new, pv)
            return
        correction = jnp.exp(m_prev - m_new)
        if segments:
            correction = jnp.where(m_prev == NEG_INF, 0.0, correction)
        l_new += correction * l_ref[rows, 0][:, None]
        acc_ref[rows, :] = acc_ref[rows, :] * correction + pv
        m_ref[rows, :] = jnp.broadcast_to(m_new, (m_new.shape[0], _LANE))
        l_ref[rows, :] = jnp.broadcast_to(l_new, (l_new.shape[0], _LANE))

    def _compute(causal_offset, tiles, band_offset=None):
        _advance(window)(
            _tile(rows, cols, causal_offset, band_offset)
            for rows, cols in tiles
        )

    _for_live_class(
        iq, ik, _compute, causal=causal, block_q=block_q, block_kv=block_kv,
        classes=classes, window=window,
    )

    if state:
        @pl.when(step == nk - 1)
        def _finalize():
            _write(
                slice(0, block_q), m_ref[:, 0][:, None], l_ref[:, 0][:, None],
                acc_ref[:],
            )


def _flash_fwd(
    q, k, v, seg_q, seg_kv, *, causal, scale, block_q, block_kv,
    segments, window=None,
):
    """q [B,Hq,S,D], k [B,Hkv,S,D], v [B,Hkv,S,Dv], seg [B,S] ->
    (o [B,Hq,S,Dv], lse).  ``Dv`` may differ from ``D`` (latent attention:
    keys of 192, values of 128); the scores contract over ``D``, the
    accumulator and the output are ``Dv`` wide."""
    b, hq, sq, d = q.shape
    hkv, skv, d_v = k.shape[1], k.shape[2], v.shape[3]
    group = hq // hkv
    nq, nk = sq // block_q, skv // block_kv
    classes = block_classes(sq, skv, block_q, block_kv, causal, window)
    if window is not None:
        nk = classes.kv_steps

    def kv_block(iq, ik):
        return _last_live_kv(
            iq, _kv_of_step(iq, ik, block_q, block_kv, window),
            block_q, block_kv, causal, window,
        )

    def kv_rows(ib, ih, iq, ik):
        return (ib, ih // group, kv_block(iq, ik), 0)

    grid = (b, hq, nq, nk)
    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale,
        block_q=block_q, block_kv=block_kv, classes=classes,
        segments=segments, window=window,
    )
    out_shape = [
        jax.ShapeDtypeStruct((b, hq, sq, d_v), q.dtype),
        jax.ShapeDtypeStruct((b, hq, sq, _STAT), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q), lambda ib, ih, iq, ik: (ib, 0, iq)),
            pl.BlockSpec(
                (1, 1, block_kv),
                lambda ib, ih, iq, ik: (ib, 0, kv_block(iq, ik)),
            ),
            pl.BlockSpec(
                (1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
            ),
            pl.BlockSpec((1, 1, block_kv, d), kv_rows),
            pl.BlockSpec((1, 1, block_kv, d_v), kv_rows),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d_v), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_q, _STAT),
                lambda ib, ih, iq, ik: (ib, ih, iq, 0),
            ),
        ],
        scratch_shapes=[] if nk == 1 else [
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
        out_shape=out_shape,
        interpret=backend.interpret(),
    )(seg_q, seg_kv, q, k, v)
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _recompute_p_ds(
    q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
    seg_q_ref, seg_kv_ref,
    *, rows, cols, causal_offset, scale, segments, band=None,
):
    """Shared backward math of one tile of a block (``_tiles``: the whole
    block, or a diagonal block's strip): probabilities p, score-grads ds,
    and the tile's q, k and do as dk, dq and dv contract them.

    The softmax recompute from lse and its masking MUST be identical across
    the dq / dkv / fused kernels — one traced helper keeps them in sync,
    so that they sum the same terms in the same order.

    ``delta = rowsum(o * do)`` is computed IN-KERNEL from the o block (the
    head dim is whole per block, so the row sum is exact) instead of in a
    separate XLA fusion — that fusion plus the padded [B,H,S,STAT] delta
    array cost ~1 ms/layer of pure HBM traffic at bench shapes.

    A generator of the tile's first stages (``_one_by_one``): it yields
    after each of its two products and RETURNS the five (``yield from``).
    """
    k = k_ref[0, 0, cols, :]
    v = v_ref[0, 0, cols, :]
    do = do_ref[0, 0, rows, :]
    lse = lse_ref[0, 0, rows, :][:, 0][:, None]
    delta = jnp.sum(
        o_ref[0, 0, rows, :].astype(jnp.float32) * do.astype(jnp.float32),
        axis=1, keepdims=True,
    )
    q = q_ref[0, 0, rows, :]
    s = _scores(q, k, scale)
    yield
    p = _masked(
        jnp.exp(s - lse), 0.0,
        rows, cols, causal_offset, seg_q_ref, seg_kv_ref, segments, band,
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    yield
    ds = p * (dp - delta) * scale
    return p, ds.astype(q.dtype), q, k, do


def _bwd_dq_kernel(
    seg_q_ref, seg_kv_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
    dq_ref, dq_acc_ref,
    *, causal: bool, scale: float, block_q: int, block_kv: int,
    classes: BlockClasses, segments: bool, window: Optional[int] = None,
):
    iq, step = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    ik = _kv_of_step(iq, step, block_q, block_kv, window)

    @pl.when(step == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    def _tile(rows, cols, causal_offset, band_offset):
        _, ds, _, k, _ = yield from _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
            seg_q_ref, seg_kv_ref,
            rows=rows, cols=cols, causal_offset=causal_offset,
            scale=scale, segments=segments,
            band=_band(band_offset, window),
        )
        dq_acc_ref[rows, :] += jax.lax.dot(
            ds, k, preferred_element_type=jnp.float32
        )

    def _compute(causal_offset, tiles, band_offset=None):
        _one_by_one(
            _tile(rows, cols, causal_offset, band_offset)
            for rows, cols in tiles
        )

    _for_live_class(
        iq, ik, _compute, causal=causal, block_q=block_q, block_kv=block_kv,
        classes=classes, window=window,
    )

    @pl.when(step == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc_ref[:].astype(dq_ref.dtype)


def _add_dk_dv(dk_acc_ref, dv_acc_ref, cols, p, ds, q, do):
    dv_acc_ref[cols, :] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dk_acc_ref[cols, :] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _bwd_dkv_kernel(
    seg_q_ref, seg_kv_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
    *, causal: bool, scale: float, block_q: int, block_kv: int,
    classes: BlockClasses, segments: bool, window: Optional[int] = None,
    q_blocks: int = 0,
):
    ik, step = pl.program_id(2), pl.program_id(3)  # note: kv outer, q inner
    nq = pl.num_programs(3)
    iq = _q_of_step(ik, step, q_blocks, block_q, block_kv, window)

    @pl.when(step == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    def _tile(rows, cols, causal_offset, band_offset):
        p, ds, q, _, do = yield from _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
            seg_q_ref, seg_kv_ref,
            rows=rows, cols=cols, causal_offset=causal_offset,
            scale=scale, segments=segments,
            band=_band(band_offset, window),
        )
        _add_dk_dv(dk_acc_ref, dv_acc_ref, cols, p, ds, q, do)

    def _compute(causal_offset, tiles, band_offset=None):
        _one_by_one(
            _tile(rows, cols, causal_offset, band_offset)
            for rows, cols in tiles
        )

    _for_live_class(
        iq, ik, _compute, causal=causal, block_q=block_q, block_kv=block_kv,
        classes=classes, window=window, q_blocks=q_blocks,
    )

    @pl.when(step == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(
    seg_q_ref, seg_kv_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
    dq_ref, dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *dq_acc,
    causal: bool, scale: float, block_q: int, block_kv: int,
    classes: BlockClasses, segments: bool, window: Optional[int] = None,
    q_blocks: int = 0,
):
    """One-pass backward: s/p computed once feed dq, dk AND dv.

    Grid (batch, head, kv block, q block): dk/dv accumulate in VMEM scratch
    across the inner q sweep.  The split dq/dkv kernels each recompute
    s = q k^T and the softmax from lse (7 S^2 D matmul units + 2 exp sweeps
    per pair); fused it is 5 + 1, a ~25% cut of backward kernel FLOPs.

    A dq block takes one term from every live kv block, and the kv axis is
    the OUTER one here, so its visits are not consecutive.  An output block
    may not be revisited that way (Pallas TPU writes it back when its index
    changes and does not reload it), a scratch may: with several kv blocks
    ``dq_ref`` is the whole sequence of one (batch, head), resident until
    the head changes, and ``dq_acc`` one float32 scratch of the same extent.
    A q block's rows are assigned at kv block 0 (live for every q block),
    added to at the others in ascending kv order, which is the order and
    the precision of ``_bwd_dq_kernel``'s scratch, and cast into ``dq_ref``
    at the q block's last live kv block.  With ONE kv block (``dq_acc``
    empty) every dq block is visited once and written straight out.
    """
    ik, step = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)
    iq = _q_of_step(ik, step, q_blocks, block_q, block_kv, window)

    @pl.when(step == 0)
    def _init_kv():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    q_start = iq * block_q

    # ik == 0 always runs under causal (kv_start 0), so the dq init below
    # is guaranteed to execute for every row of every q block (under a
    # window: the q block's first live kv block, whose every row is worked,
    # masked or not).
    first = 0
    if window is not None:
        first = _band_first_kv(iq, block_q, block_kv, window)

    def _tile(rows, cols, causal_offset, band_offset):
        p, ds, q, k, do = yield from _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
            seg_q_ref, seg_kv_ref,
            rows=rows, cols=cols, causal_offset=causal_offset,
            scale=scale, segments=segments,
            band=_band(band_offset, window),
        )
        _add_dk_dv(dk_acc_ref, dv_acc_ref, cols, p, ds, q, do)
        dq = jax.lax.dot(ds, k, preferred_element_type=jnp.float32)
        yield
        if not dq_acc:
            dq_ref[0, 0, rows, :] = dq.astype(dq_ref.dtype)
            return
        _add_dq(dq, rows, *dq_acc)

    def _compute(causal_offset, tiles, band_offset=None):
        _advance(window)(
            _tile(rows, cols, causal_offset, band_offset)
            for rows, cols in tiles
        )

    def _add_dq(dq, rows, dq_acc_ref):
        height = rows.stop - rows.start
        at = pl.ds(pl.multiple_of(q_start + rows.start, height), height)

        @pl.when(ik == first)
        def _first():
            dq_acc_ref[at, :] = dq

        @pl.when(ik > first)
        def _later():
            dq_acc_ref[at, :] += dq

        last = pl.num_programs(2) - 1
        if causal:
            last = jnp.minimum(last, (q_start + block_q - 1) // block_kv)

        @pl.when(ik == last)
        def _write():
            dq_ref[0, 0, at, :] = dq_acc_ref[at, :].astype(dq_ref.dtype)

    _for_live_class(
        iq, ik, _compute, causal=causal, block_q=block_q, block_kv=block_kv,
        classes=classes, window=window, q_blocks=q_blocks,
    )

    @pl.when(step == nq - 1)
    def _finalize_kv():
        dk_ref[0, 0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _fused_bwd_vmem_bytes(sq, d, d_v, block_q, block_kv, dtype) -> int:
    """VMEM the one-pass backward asks for at several kv blocks, from its
    shapes alone (a last dimension occupies whole 128-lane tiles): the
    pipeline's two buffers of every block in and out, the lse block at 128
    lanes, the dk / dv accumulators, four float32 ``[block_q, block_kv]``
    temporaries, and dq over the WHOLE q sequence: the float32 scratch and
    two buffers of the output.  At 8192 x 192 / 128 in bf16 and blocks of
    1024: 6 + 1.5 + 16 + 16 = 39.5 MiB, where the v5e's compiler refuses
    less than 30.5 (it keeps two temporaries, not four); at 8192 x 128,
    29.5 for 21.5."""
    item = jnp.dtype(dtype).itemsize
    d, d_v = (-(-width // _LANE) * _LANE for width in (d, d_v))
    q_side = block_q * (d + 2 * d_v)        # q, do, o
    kv_side = block_kv * (d + d_v)          # k, v in; dk, dv out
    blocks = 2 * item * (q_side + 2 * kv_side) + 2 * 4 * block_q * _LANE
    temporaries = 4 * 4 * block_q * block_kv
    dq = sq * d * (4 + 2 * item)
    return blocks + 4 * kv_side + temporaries + dq


def _flash_bwd_fused(
    q, k, v, seg_q, seg_kv, o, lse, do,
    *, causal, scale, block_q, block_kv, segments, window=None,
):
    b, hq, sq, d = q.shape
    hkv, skv, d_v = k.shape[1], k.shape[2], v.shape[3]
    group = hq // hkv
    nq, nk = sq // block_q, skv // block_kv
    classes = block_classes(sq, skv, block_q, block_kv, causal, window)
    q_steps = nq if window is None else classes.q_steps

    lse_l = jnp.broadcast_to(lse[..., None], (*lse.shape, _STAT))

    def q_block(ik, iq):
        return _first_live_q(
            _q_of_step(ik, iq, nq, block_q, block_kv, window), ik, nq,
            block_q, block_kv, causal, window,
        )

    def q_rows(ib, ih, ik, iq):
        return (ib, ih, q_block(ik, iq), 0)

    def kv_rows(ib, ih, ik, iq):
        return (ib, ih // group, ik, 0)

    dq_spec = pl.BlockSpec((1, 1, block_q, d), q_rows)
    scratch = [
        pltpu.VMEM((block_kv, d), jnp.float32),
        pltpu.VMEM((block_kv, d_v), jnp.float32),
    ]
    one_pass = {}
    if nk > 1:
        dq_spec = pl.BlockSpec(
            (1, 1, sq, d), lambda ib, ih, ik, iq: (ib, ih, 0, 0)
        )
        scratch.append(pltpu.VMEM((sq, d), jnp.float32))
        one_pass = dict(
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(
                    "parallel", "parallel", "arbitrary", "arbitrary"
                ),
                vmem_limit_bytes=_fused_bwd_vmem_bytes(
                    sq, d, d_v, block_q, block_kv, q.dtype
                ),
            ),
            name="flash_bwd_one_pass",
        )

    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel, causal=causal, scale=scale,
            block_q=block_q, block_kv=block_kv, classes=classes,
            segments=segments, window=window, q_blocks=nq,
        ),
        grid=(b, hq, nk, q_steps),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q),
                lambda ib, ih, ik, iq: (ib, 0, q_block(ik, iq)),
            ),
            pl.BlockSpec((1, 1, block_kv), lambda ib, ih, ik, iq: (ib, 0, ik)),
            pl.BlockSpec((1, 1, block_q, d), q_rows),
            pl.BlockSpec((1, 1, block_kv, d), kv_rows),
            pl.BlockSpec((1, 1, block_kv, d_v), kv_rows),
            pl.BlockSpec((1, 1, block_q, d_v), q_rows),
            pl.BlockSpec((1, 1, block_q, _STAT), q_rows),
            pl.BlockSpec((1, 1, block_q, d_v), q_rows),
        ],
        out_specs=[
            dq_spec,
            pl.BlockSpec(
                (1, 1, block_kv, d), lambda ib, ih, ik, iq: (ib, ih, ik, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d_v), lambda ib, ih, ik, iq: (ib, ih, ik, 0)
            ),
        ],
        scratch_shapes=scratch,
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b, hq, skv, d_v), v.dtype),
        ],
        interpret=backend.interpret(),
        **one_pass,
    )(seg_q, seg_kv, q, k, v, do, lse_l, o)
    if group > 1:
        dk = dk.reshape(b, hkv, group, skv, d).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(b, hkv, group, skv, d_v).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


def _flash_bwd(
    q, k, v, seg_q, seg_kv, o, lse, do,
    *, causal, scale, block_q, block_kv, segments, window=None,
):
    b, hq, sq, d = q.shape
    hkv, skv, d_v = k.shape[1], k.shape[2], v.shape[3]
    group = hq // hkv
    nq, nk = sq // block_q, skv // block_kv
    classes = block_classes(sq, skv, block_q, block_kv, causal, window)
    kv_steps, q_steps = nk, nq
    if window is not None:
        kv_steps, q_steps = classes.kv_steps, classes.q_steps

    lse_l = jnp.broadcast_to(lse[..., None], (*lse.shape, _STAT))

    common_in = [seg_q, seg_kv, q, k, v, do, lse_l, o]
    static = dict(
        causal=causal, scale=scale, block_q=block_q, block_kv=block_kv,
        classes=classes, segments=segments, window=window,
    )

    # dq: kv inner, so a dead step parks the kv side (as the forward's).
    def kv_block(iq, ik):
        return _last_live_kv(
            iq, _kv_of_step(iq, ik, block_q, block_kv, window),
            block_q, block_kv, causal, window,
        )

    def q_rows(ib, ih, iq, ik):
        return (ib, ih, iq, 0)

    def parked_kv_rows(ib, ih, iq, ik):
        return (ib, ih // group, kv_block(iq, ik), 0)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **static),
        grid=(b, hq, nq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, block_q), lambda ib, ih, iq, ik: (ib, 0, iq)),
            pl.BlockSpec(
                (1, 1, block_kv),
                lambda ib, ih, iq, ik: (ib, 0, kv_block(iq, ik)),
            ),
            pl.BlockSpec((1, 1, block_q, d), q_rows),
            pl.BlockSpec((1, 1, block_kv, d), parked_kv_rows),
            pl.BlockSpec((1, 1, block_kv, d_v), parked_kv_rows),
            pl.BlockSpec((1, 1, block_q, d_v), q_rows),
            pl.BlockSpec((1, 1, block_q, _STAT), q_rows),
            pl.BlockSpec((1, 1, block_q, d_v), q_rows),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), q_rows),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        interpret=backend.interpret(),
    )(*common_in)

    # dk/dv: one pass per q-head; accumulated per kv head afterwards (GQA).
    # q inner, so a dead step parks the q side (as the one pass's).
    def q_block(ik, iq):
        return _first_live_q(
            _q_of_step(ik, iq, nq, block_q, block_kv, window), ik, nq,
            block_q, block_kv, causal, window,
        )

    def parked_q_rows(ib, ih, ik, iq):
        return (ib, ih, q_block(ik, iq), 0)

    def kv_rows(ib, ih, ik, iq):
        return (ib, ih // group, ik, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, q_blocks=nq, **static),
        grid=(b, hq, nk, q_steps),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q),
                lambda ib, ih, ik, iq: (ib, 0, q_block(ik, iq)),
            ),
            pl.BlockSpec((1, 1, block_kv), lambda ib, ih, ik, iq: (ib, 0, ik)),
            pl.BlockSpec((1, 1, block_q, d), parked_q_rows),
            pl.BlockSpec((1, 1, block_kv, d), kv_rows),
            pl.BlockSpec((1, 1, block_kv, d_v), kv_rows),
            pl.BlockSpec((1, 1, block_q, d_v), parked_q_rows),
            pl.BlockSpec((1, 1, block_q, _STAT), parked_q_rows),
            pl.BlockSpec((1, 1, block_q, d_v), parked_q_rows),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, block_kv, d), lambda ib, ih, ik, iq: (ib, ih, ik, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d_v), lambda ib, ih, ik, iq: (ib, ih, ik, 0)
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d_v), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b, hq, skv, d_v), v.dtype),
        ],
        interpret=backend.interpret(),
    )(*common_in)
    if group > 1:
        dk = dk.reshape(b, hkv, group, skv, d).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(b, hkv, group, skv, d_v).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def backward_path(sq, skv, d, d_v, block_q, block_kv, dtype) -> str:
    """``"fused"`` or ``"split"``: the backward :func:`mha` runs at these
    sizes, given as its caller gives them (clamping and padding them twice
    changes nothing, so the dispatch asks with the padded ones).  One kv
    block needs no dq scratch and is always fused; several are while
    :func:`_fused_bwd_vmem_bytes` is within ``_VMEM_CAP``."""
    block_q, block_kv, sq, skv = _blocks_and_padding(
        sq, skv, block_q, block_kv
    )
    if skv == block_kv:
        return "fused"
    need = _fused_bwd_vmem_bytes(sq, d, d_v, block_q, block_kv, dtype)
    return "fused" if need <= _VMEM_CAP else "split"


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10)
)
def _flash_core(
    q, k, v, seg_q, seg_kv, causal, scale, block_q, block_kv, segments,
    window=None,
):
    o, _ = _flash_fwd(
        q, k, v, seg_q, seg_kv, causal=causal, scale=scale,
        block_q=block_q, block_kv=block_kv, segments=segments,
        window=window,
    )
    return o


def _flash_core_fwd(
    q, k, v, seg_q, seg_kv, causal, scale, block_q, block_kv, segments,
    window=None,
):
    o, lse = _flash_fwd(
        q, k, v, seg_q, seg_kv, causal=causal, scale=scale,
        block_q=block_q, block_kv=block_kv, segments=segments,
        window=window,
    )
    # Named remat saveables: under the "flash_res" policy (models/transformer)
    # the first forward saves o+lse and the backward replay DCEs the whole
    # forward kernel recompute — the bwd kernels read the saved tensors
    # directly.  Under any other policy the names are no-ops.
    o = jax.ad_checkpoint.checkpoint_name(o, "flash_out")
    lse = jax.ad_checkpoint.checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, seg_q, seg_kv, o, lse)


def _flash_core_bwd(
    causal, scale, block_q, block_kv, segments, window, residuals, g
):
    q, k, v, seg_q, seg_kv, o, lse = residuals
    path = backward_path(
        q.shape[2], k.shape[2], q.shape[3], v.shape[3], block_q, block_kv,
        q.dtype,
    )
    impl = _flash_bwd_fused if path == "fused" else _flash_bwd
    dq, dk, dv = impl(
        q, k, v, seg_q, seg_kv, o, lse, g, causal=causal, scale=scale,
        block_q=block_q, block_kv=block_kv, segments=segments,
        window=window,
    )
    return dq, dk, dv, None, None


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    block_q: int = 512,
    block_kv: int = 512,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Flash attention on [B, S, H, D] tensors (layout of models/attention).

    ``v`` may be narrower or wider than ``q`` and ``k`` (``[B, S, H, Dv]``,
    latent attention's 192-wide keys and 128-wide values): the output is
    ``Dv`` wide and the default ``scale`` is ``D ** -0.5``, the keys'.

    ``segment_ids`` [B, S] activates packed-sequence masking: token i attends
    token j only if segment_ids[i] == segment_ids[j] (and j <= i when
    causal).  Pad positions use segment id -1 injected for padded tails.

    ``window`` (causal self-attention only) keeps the band ``0 <= i - j <
    window``: a query sees itself and the ``window - 1`` tokens before it,
    and blocks outside the band are neither computed nor walked.
    """
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    scale = d ** -0.5 if scale is None else scale
    if window is not None and (not causal or sq != skv or window < 1):
        raise ValueError(
            "window needs causal self-attention (as many keys as queries) "
            f"and at least one key, got causal={causal}, {sq} queries, "
            f"{skv} keys, window={window}"
        )

    block_q, block_kv, sq_p, skv_p = _blocks_and_padding(
        sq, skv, block_q, block_kv
    )

    if segment_ids is None:
        seg_q = jnp.zeros((b, sq), jnp.int32)
        seg_kv = jnp.zeros((b, skv), jnp.int32)
    else:
        seg_q = seg_kv = segment_ids.astype(jnp.int32)
    # Pad tokens get segment -1 (matches nothing, contributes nothing).
    seg_q = _pad_to(seg_q, sq_p, 1, value=-1)
    seg_kv = _pad_to(seg_kv, skv_p, 1, value=-1)

    qt = _pad_to(q.transpose(0, 2, 1, 3), sq_p, 2)
    kt = _pad_to(k.transpose(0, 2, 1, 3), skv_p, 2)
    vt = _pad_to(v.transpose(0, 2, 1, 3), skv_p, 2)

    # Nothing to compare where no ids were given and no length was padded.
    segments = segment_ids is not None or (sq_p, skv_p) != (sq, skv)
    o = _flash_core(
        qt, kt, vt, seg_q[:, None, :], seg_kv[:, None, :],
        causal, scale, block_q, block_kv, segments,
        None if window is None else int(window),
    )
    return o[:, :, :sq].transpose(0, 2, 1, 3)


def forward_grid_steps(sq, skv, block_q, block_kv, window=None) -> int:
    """The steps the forward's kv-inner grid makes a (batch, head) at these
    sizes, given as :func:`mha`'s caller gives them: every (q block, kv
    block) pair, under a ``window`` the band's ``kv_steps`` a q block."""
    block_q, block_kv, sq, skv = _blocks_and_padding(
        sq, skv, block_q, block_kv
    )
    nq, nk = sq // block_q, skv // block_kv
    if window is None:
        return nq * nk
    return nq * _band_classes(nq, nk, block_q, block_kv, window).kv_steps


def band_tile_live_share(sq, skv, block_q, block_kv, window) -> float:
    """The band's live pairs over the pairs the tiles of a banded kernel
    work, one (batch, head) at these sizes, given as :func:`mha`'s caller
    gives them: an interior block is all live, an edge block's tiles are
    its strips or the masked square (``_tiles``, from the classes alone)."""
    block_q, block_kv, sq, skv = _blocks_and_padding(
        sq, skv, block_q, block_kv
    )
    classes = _band_classes(
        sq // block_q, skv // block_kv, block_q, block_kv, window
    )

    def pairs(strip, lower=False):
        return sum(
            (rows.stop - rows.start) * (cols.stop - cols.start)
            for rows, cols in _tiles(block_q, block_kv, strip, lower)
        )

    worked = (
        (classes.interior + classes.both) * pairs(0)
        + classes.diagonal * pairs(classes.strip)
        + classes.lower * pairs(classes.lower_strip, lower=True)
    )
    ramp = min(window, sq)      # rows that see fewer than ``window`` keys
    live = ramp * (ramp + 1) // 2 + (sq - ramp) * window
    return live / worked
