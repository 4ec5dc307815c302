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
steps.  Causal blocks above the diagonal are skipped via ``@pl.when`` — for
long sequences that halves the FLOPs, which is exactly the regime the
north-star benchmark (long-context goodput) cares about.

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
from typing import Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops import backend

NEG_INF = -1e30
_LANE = 128
# Row statistics (lse/delta) ride [.., S, _STAT] arrays: 8 lanes (one f32
# sublane tile) instead of 128 cuts their HBM footprint/traffic 16x — at
# bench shapes that is ~200 MB of pure padding per layer per tensor.
_STAT = 8
# The most VMEM the one-pass backward may ask for: half of a v5e core's
# 128 MiB (a kernel that asks for nothing gets 16 MiB).
_VMEM_CAP = 64 << 20


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
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    seg_q_ref, seg_kv_ref, q_ref, k_ref, v_ref,
    o_ref, lse_ref,
    m_ref, l_ref, acc_ref,
    *, causal: bool, scale: float, block_q: int, block_kv: int,
):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    kv_start = ik * block_kv
    # Whole-block causal skip: the earliest q row can't see this kv block.
    run = (not causal) or (q_start + block_q - 1 >= kv_start)

    @pl.when(run)
    def _compute():
        # Matmul inputs stay bf16 (MXU native rate); accumulation is fp32 via
        # preferred_element_type — the standard flash-attention numerics.
        q = q_ref[0, 0]  # [block_q, d]
        k = k_ref[0, 0]  # [block_kv, d]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_kv]

        mask = None
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0
            )
            cols = kv_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1
            )
            mask = rows >= cols
        seg_q = seg_q_ref[0, 0]  # [block_q]
        seg_kv = seg_kv_ref[0, 0]  # [block_kv]
        seg = seg_q[:, None] == seg_kv[None, :]
        mask = seg if mask is None else jnp.logical_and(mask, seg)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0][:, None]  # [block_q, 1]
        m_cur = jnp.max(s, axis=1)[:, None]
        m_new = jnp.maximum(m_prev, m_cur)
        # All-masked rows keep m at NEG_INF; freeze them to avoid inf-inf.
        p = jnp.exp(s - m_new)
        p = jnp.where(m_new == NEG_INF, 0.0, p)
        correction = jnp.exp(m_prev - m_new)
        correction = jnp.where(m_prev == NEG_INF, 0.0, correction)
        l_new = correction * l_ref[:, 0][:, None] + jnp.sum(p, axis=1)[:, None]
        acc_ref[:] = acc_ref[:] * correction + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[:, 0][:, None]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        m = m_ref[:, 0][:, None]
        lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(safe_l))
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _flash_fwd(
    q, k, v, seg_q, seg_kv, *, causal, scale, block_q, block_kv
):
    """q [B,Hq,S,D], k [B,Hkv,S,D], v [B,Hkv,S,Dv], seg [B,S] ->
    (o [B,Hq,S,Dv], lse).  ``Dv`` may differ from ``D`` (latent attention:
    keys of 192, values of 128); the scores contract over ``D``, the
    accumulator and the output are ``Dv`` wide."""
    b, hq, sq, d = q.shape
    hkv, skv, d_v = k.shape[1], k.shape[2], v.shape[3]
    group = hq // hkv
    nq, nk = sq // block_q, skv // block_kv

    grid = (b, hq, nq, nk)
    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale,
        block_q=block_q, block_kv=block_kv,
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
            pl.BlockSpec((1, 1, block_kv), lambda ib, ih, iq, ik: (ib, 0, ik)),
            pl.BlockSpec(
                (1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d),
                lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d_v),
                lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0),
            ),
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
        scratch_shapes=[
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


def _block_mask(causal, q_start, kv_start, seg_q_ref, seg_kv_ref,
                block_q, block_kv):
    mask = None
    if causal:
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0
        )
        cols = kv_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1
        )
        mask = rows >= cols
    seg = seg_q_ref[0, 0][:, None] == seg_kv_ref[0, 0][None, :]
    return seg if mask is None else jnp.logical_and(mask, seg)


def _recompute_p_ds(
    q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
    seg_q_ref, seg_kv_ref,
    *, causal, scale, q_start, kv_start, block_q, block_kv,
):
    """Shared backward block math: probabilities p and score-grads ds.

    The softmax recompute from lse and its masking MUST be identical across
    the dq / dkv / fused kernels — one traced helper keeps them in sync.

    ``delta = rowsum(o * do)`` is computed IN-KERNEL from the o block (the
    head dim is whole per block, so the row sum is exact) instead of in a
    separate XLA fusion — that fusion plus the padded [B,H,S,STAT] delta
    array cost ~1 ms/layer of pure HBM traffic at bench shapes.
    """
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0][:, 0][:, None]
    delta = jnp.sum(
        o_ref[0, 0].astype(jnp.float32) * do.astype(jnp.float32),
        axis=1, keepdims=True,
    )
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    mask = _block_mask(
        causal, q_start, kv_start, seg_q_ref, seg_kv_ref, block_q, block_kv
    )
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = (p * (dp - delta) * scale).astype(q.dtype)
    return p, ds


def _bwd_dq_kernel(
    seg_q_ref, seg_kv_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
    dq_ref, dq_acc_ref,
    *, causal: bool, scale: float, block_q: int, block_kv: int,
):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    q_start, kv_start = iq * block_q, ik * block_kv
    run = (not causal) or (q_start + block_q - 1 >= kv_start)

    @pl.when(run)
    def _compute():
        _, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
            seg_q_ref, seg_kv_ref,
            causal=causal, scale=scale, q_start=q_start, kv_start=kv_start,
            block_q=block_q, block_kv=block_kv,
        )
        dq_acc_ref[:] += jax.lax.dot(
            ds, k_ref[0, 0], preferred_element_type=jnp.float32
        )

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    seg_q_ref, seg_kv_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
    *, causal: bool, scale: float, block_q: int, block_kv: int,
):
    ik, iq = pl.program_id(2), pl.program_id(3)  # note: kv outer, q inner
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    q_start, kv_start = iq * block_q, ik * block_kv
    run = (not causal) or (q_start + block_q - 1 >= kv_start)

    @pl.when(run)
    def _compute():
        p, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
            seg_q_ref, seg_kv_ref,
            causal=causal, scale=scale, q_start=q_start, kv_start=kv_start,
            block_q=block_q, block_kv=block_kv,
        )
        do = do_ref[0, 0]
        dv_acc_ref[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc_ref[:] += jax.lax.dot_general(
            ds, q_ref[0, 0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(
    seg_q_ref, seg_kv_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
    dq_ref, dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *dq_acc,
    causal: bool, scale: float, block_q: int, block_kv: int,
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
    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init_kv():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    q_start, kv_start = iq * block_q, ik * block_kv
    # ik == 0 always runs under causal (kv_start 0), so the dq init below
    # is guaranteed to execute for every q block.
    run = (not causal) or (q_start + block_q - 1 >= kv_start)

    @pl.when(run)
    def _compute():
        p, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
            seg_q_ref, seg_kv_ref,
            causal=causal, scale=scale, q_start=q_start, kv_start=kv_start,
            block_q=block_q, block_kv=block_kv,
        )
        do = do_ref[0, 0]
        dv_acc_ref[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc_ref[:] += jax.lax.dot_general(
            ds, q_ref[0, 0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq = jax.lax.dot(
            ds, k_ref[0, 0], preferred_element_type=jnp.float32
        )
        if not dq_acc:
            dq_ref[0, 0] = dq.astype(dq_ref.dtype)
            return
        (dq_acc_ref,) = dq_acc
        rows = pl.ds(pl.multiple_of(q_start, block_q), block_q)

        @pl.when(ik == 0)
        def _first():
            dq_acc_ref[rows, :] = dq

        @pl.when(ik > 0)
        def _later():
            dq_acc_ref[rows, :] += dq

        last = pl.num_programs(2) - 1
        if causal:
            last = jnp.minimum(last, (q_start + block_q - 1) // block_kv)

        @pl.when(ik == last)
        def _write():
            dq_ref[0, 0, rows, :] = dq_acc_ref[rows, :].astype(dq_ref.dtype)

    @pl.when(iq == nq - 1)
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
    *, causal, scale, block_q, block_kv
):
    b, hq, sq, d = q.shape
    hkv, skv, d_v = k.shape[1], k.shape[2], v.shape[3]
    group = hq // hkv
    nq, nk = sq // block_q, skv // block_kv

    lse_l = jnp.broadcast_to(lse[..., None], (*lse.shape, _STAT))

    def q_block(ik, iq):
        return iq

    def q_rows(ib, ih, ik, iq):
        return (ib, ih, q_block(ik, iq), 0)

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
        if causal:
            # A step above the diagonal computes nothing: let it name the
            # q-side blocks of the kv block's first live step, which the
            # pipeline then fetches once and not for every dead step
            # (JoyAI's layer 35.0 -> 31.2 ms on a v5e, PERF.md §6 PR 34).
            def q_block(ik, iq):
                return jnp.maximum(iq, (ik * block_kv) // block_q)

    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel, causal=causal, scale=scale,
            block_q=block_q, block_kv=block_kv,
        ),
        grid=(b, hq, nk, nq),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q),
                lambda ib, ih, ik, iq: (ib, 0, q_block(ik, iq)),
            ),
            pl.BlockSpec((1, 1, block_kv), lambda ib, ih, ik, iq: (ib, 0, ik)),
            pl.BlockSpec((1, 1, block_q, d), q_rows),
            pl.BlockSpec(
                (1, 1, block_kv, d),
                lambda ib, ih, ik, iq, g=group: (ib, ih // g, ik, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d_v),
                lambda ib, ih, ik, iq, g=group: (ib, ih // g, ik, 0),
            ),
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
    *, causal, scale, block_q, block_kv
):
    b, hq, sq, d = q.shape
    hkv, skv, d_v = k.shape[1], k.shape[2], v.shape[3]
    group = hq // hkv
    nq, nk = sq // block_q, skv // block_kv

    lse_l = jnp.broadcast_to(lse[..., None], (*lse.shape, _STAT))

    common_in = [seg_q, seg_kv, q, k, v, do, lse_l, o]
    lane_spec_q = pl.BlockSpec(
        (1, 1, block_q, _STAT), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
    )
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal=causal, scale=scale,
            block_q=block_q, block_kv=block_kv,
        ),
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q), lambda ib, ih, iq, ik: (ib, 0, iq)),
            pl.BlockSpec((1, 1, block_kv), lambda ib, ih, iq, ik: (ib, 0, ik)),
            pl.BlockSpec(
                (1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d),
                lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d_v),
                lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_q, d_v), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
            ),
            lane_spec_q,
            pl.BlockSpec(
                (1, 1, block_q, d_v), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
        ),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        interpret=backend.interpret(),
    )(*common_in)

    # dk/dv: one pass per q-head; accumulated per kv head afterwards (GQA).
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal=causal, scale=scale,
            block_q=block_q, block_kv=block_kv,
        ),
        grid=(b, hq, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q), lambda ib, ih, ik, iq: (ib, 0, iq)),
            pl.BlockSpec((1, 1, block_kv), lambda ib, ih, ik, iq: (ib, 0, ik)),
            pl.BlockSpec(
                (1, 1, block_q, d), lambda ib, ih, ik, iq: (ib, ih, iq, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d),
                lambda ib, ih, ik, iq, g=group: (ib, ih // g, ik, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d_v),
                lambda ib, ih, ik, iq, g=group: (ib, ih // g, ik, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_q, d_v), lambda ib, ih, ik, iq: (ib, ih, iq, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_q, _STAT), lambda ib, ih, ik, iq: (ib, ih, iq, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_q, d_v), lambda ib, ih, ik, iq: (ib, ih, iq, 0)
            ),
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
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8)
)
def _flash_core(q, k, v, seg_q, seg_kv, causal, scale, block_q, block_kv):
    o, _ = _flash_fwd(
        q, k, v, seg_q, seg_kv,
        causal=causal, scale=scale, block_q=block_q, block_kv=block_kv,
    )
    return o


def _flash_core_fwd(q, k, v, seg_q, seg_kv, causal, scale, block_q, block_kv):
    o, lse = _flash_fwd(
        q, k, v, seg_q, seg_kv,
        causal=causal, scale=scale, block_q=block_q, block_kv=block_kv,
    )
    # Named remat saveables: under the "flash_res" policy (models/transformer)
    # the first forward saves o+lse and the backward replay DCEs the whole
    # forward kernel recompute — the bwd kernels read the saved tensors
    # directly.  Under any other policy the names are no-ops.
    o = jax.ad_checkpoint.checkpoint_name(o, "flash_out")
    lse = jax.ad_checkpoint.checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, seg_q, seg_kv, o, lse)


def _flash_core_bwd(causal, scale, block_q, block_kv, residuals, g):
    q, k, v, seg_q, seg_kv, o, lse = residuals
    path = backward_path(
        q.shape[2], k.shape[2], q.shape[3], v.shape[3], block_q, block_kv,
        q.dtype,
    )
    impl = _flash_bwd_fused if path == "fused" else _flash_bwd
    dq, dk, dv = impl(
        q, k, v, seg_q, seg_kv, o, lse, g,
        causal=causal, scale=scale, block_q=block_q, block_kv=block_kv,
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
) -> jax.Array:
    """Flash attention on [B, S, H, D] tensors (layout of models/attention).

    ``v`` may be narrower or wider than ``q`` and ``k`` (``[B, S, H, Dv]``,
    latent attention's 192-wide keys and 128-wide values): the output is
    ``Dv`` wide and the default ``scale`` is ``D ** -0.5``, the keys'.

    ``segment_ids`` [B, S] activates packed-sequence masking: token i attends
    token j only if segment_ids[i] == segment_ids[j] (and j <= i when
    causal).  Pad positions use segment id -1 injected for padded tails.
    """
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    scale = d ** -0.5 if scale is None else scale

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

    o = _flash_core(
        qt, kt, vt, seg_q[:, None, :], seg_kv[:, None, :],
        causal, scale, block_q, block_kv,
    )
    return o[:, :, :sq].transpose(0, 2, 1, 3)
