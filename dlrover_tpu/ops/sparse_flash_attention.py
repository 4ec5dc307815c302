"""Pallas TPU flash attention over a CHOSEN set of keys: each query attends
to the keys a mask names (DeepSeek-V3.2's DSA, GLM-5.2: the ``topk`` keys an
indexer picked, ``ops/index_select.py``), forward and backward.

The form is the MASKED one: the kernels walk the causal triangle block by
block as ``ops/flash_attention.py``'s do and read the choice as an int8
block ``[block_q, block_kv]`` of the mask ``[B, T, T]`` (1: chosen), which
all heads share.  A chosen set is data, spread over every kv block, so no
live block can be skipped and no block is free of the compare; what is
skipped is what the causal order kills (a kv block past the q block: no
step's work, and its index maps name the block already there).  At 16,384
tokens and 2,048 keys a query the kernels do 4.3 times the chosen pairs'
work; the gathered form, which does the chosen pairs' alone and reads each
query's rows, wins past about 64k tokens and is not built (ROADMAP).

The mask holds everything a row may not see (the causal order and the
documents' borders are the choice's own), so the kernels build no iota.  A
row always sees itself, so its running maximum is finite once its diagonal
block is met; before that a block may hold none of its keys, and the
probabilities are zeroed by the mask, not by the fill.

Forward: grid ``(batch, head, q block, kv block)``, kv innermost, the
running ``(m, l, acc)`` in VMEM scratch; it writes the output and the rows'
log-sum-exp, which the backward and the indexer's KL term read.

Backward, by the shapes alone (:func:`backward_path`): ONE pass
(``_bwd_one_pass_kernel``: kv outer, q inner; a live block's probabilities
and score gradients recomputed once from the log-sum-exp feed dq, dk AND dv,
five matmuls a block) wherever dq over the whole sequence of a (batch, head)
fits in VMEM beside the blocks (:func:`_one_pass_vmem_bytes` within
``_VMEM_LIMIT``: 16,384 tokens at 256-wide keys do, 41.5 MiB), and with one
kv block (nothing to hold); the split pair otherwise (dq with kv inner, then
dk / dv with q inner, each recomputing the block: nine matmuls; 32,768
tokens at 256-wide keys).  Both sum the same terms in the same precision and
order.  Keys may be wider than values (latent attention's 256 and 256, or
192 and 128).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops import backend

NEG_INF = -1e30
_LANE = 128
_STAT = 8
_VMEM_LIMIT = 64 << 20


def block_size(seq_len: int, want: int) -> int:
    """The kernels' block at ``seq_len`` tokens: ``want`` or the largest
    power-of-two fraction of it that divides ``seq_len``, whole lanes wide;
    0 where none does (the caller then runs the ``jax.numpy`` form)."""
    block = min(want, seq_len)
    while block >= _LANE:
        if seq_len % block == 0 and block % _LANE == 0:
            return block
        block //= 2
    return 0


def _scores(q, k, scale):
    return jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale


def _chosen(mask_ref):
    return mask_ref[0].astype(jnp.int32) != 0


def _fwd_kernel(mask_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, scale: float):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(ik <= iq)
    def _live():
        chosen = _chosen(mask_ref)
        v = v_ref[0, 0]
        s = jnp.where(
            chosen, _scores(q_ref[0, 0], k_ref[0, 0], scale), NEG_INF
        )
        m_prev = m_ref[:, 0][:, None]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.where(chosen, jnp.exp(s - m_new), 0.0)
        correction = jnp.exp(m_prev - m_new)
        l_new = correction * l_ref[:, 0][:, None] + jnp.sum(
            p, axis=1
        )[:, None]
        acc_ref[:] = acc_ref[:] * correction + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == iq)
    def _finalize():
        m, l = m_ref[:, 0][:, None], l_ref[:, 0][:, None]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(safe_l))
        lse_ref[0, 0] = jnp.broadcast_to(lse, (lse.shape[0], _STAT))


def _params(outer: str = "parallel"):
    """``outer``: the third grid axis; ``arbitrary`` where a scratch is
    carried across its steps (the one pass's dq)."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", outer, "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT,
    )


def _flash_fwd(q, k, v, mask, *, scale, block):
    """q, k ``[B, H, T, D]``, v ``[B, H, T, Dv]``, mask ``[B, T, T]`` int8
    -> (o ``[B, H, T, Dv]``, lse ``[B, H, T]``)."""
    b, h, t, d = q.shape
    d_v = v.shape[3]
    n = t // block

    def q_rows(ib, ih, iq, ik):
        return (ib, ih, iq, 0)

    def kv_rows(ib, ih, iq, ik):
        return (ib, ih, jnp.minimum(ik, iq), 0)

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid=(b, h, n, n),
        in_specs=[
            pl.BlockSpec(
                (1, block, block),
                lambda ib, ih, iq, ik: (ib, iq, jnp.minimum(ik, iq)),
            ),
            pl.BlockSpec((1, 1, block, d), q_rows),
            pl.BlockSpec((1, 1, block, d), kv_rows),
            pl.BlockSpec((1, 1, block, d_v), kv_rows),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block, d_v), q_rows),
            pl.BlockSpec((1, 1, block, _STAT), q_rows),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, _LANE), jnp.float32),
            pltpu.VMEM((block, _LANE), jnp.float32),
            pltpu.VMEM((block, d_v), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, d_v), q.dtype),
            jax.ShapeDtypeStruct((b, h, t, _STAT), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=backend.interpret(),
    )(mask, q, k, v)
    return o, lse[..., 0]


def _p_ds(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, scale):
    """A block's probabilities and score gradients, as both backward
    kernels need them: one traced helper, so that they sum the same terms."""
    q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
    lse = lse_ref[0, 0][:, 0][:, None]
    delta = jnp.sum(
        o_ref[0, 0].astype(jnp.float32) * do.astype(jnp.float32),
        axis=1, keepdims=True,
    )
    p = jnp.where(
        _chosen(mask_ref), jnp.exp(_scores(q, k, scale) - lse), 0.0
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    ds = (p * (dp - delta) * scale).astype(q.dtype)
    return p, ds, q, k, do


def _add_dk_dv(dk_acc_ref, dv_acc_ref, p, ds, q, do):
    """A block's terms of dk and dv, as both q-inner kernels add them."""
    dv_acc_ref[:] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dk_acc_ref[:] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _bwd_dq_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
                   dq_ref, dq_acc_ref, *, scale: float):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    @pl.when(ik <= iq)
    def _live():
        _, ds, _, k, _ = _p_ds(
            mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, scale
        )
        dq_acc_ref[:] += jax.lax.dot(
            ds, k, preferred_element_type=jnp.float32
        )

    @pl.when(ik == iq)
    def _finalize():
        dq_ref[0, 0] = dq_acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *, scale: float):
    ik, iq = pl.program_id(2), pl.program_id(3)  # kv outer, q inner

    @pl.when(iq == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    @pl.when(iq >= ik)
    def _live():
        p, ds, q, _, do = _p_ds(
            mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, scale
        )
        _add_dk_dv(dk_acc_ref, dv_acc_ref, p, ds, q, do)

    @pl.when(iq == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _bwd_one_pass_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         o_ref, dq_ref, dk_ref, dv_ref, dk_acc_ref,
                         dv_acc_ref, *dq_acc, scale: float):
    """dq, dk and dv from ONE walk of the triangle: kv outer, q inner, dk /
    dv as ``_bwd_dkv_kernel`` has them.  A dq block takes one term from each
    kv block up to its own, and with kv outer its visits are not consecutive:
    an output block may not be revisited that way, a scratch may.  So with
    several kv blocks ``dq_ref`` is the whole sequence of one (batch, head),
    resident until the head changes, and ``dq_acc`` one float32 scratch of
    that extent: a q block's rows are assigned at kv block 0, added to in
    ascending kv order (``_bwd_dq_kernel``'s order and precision) and cast
    into ``dq_ref`` at the diagonal, the rows' last block.  With ONE kv block
    (``dq_acc`` empty) the only dq block is written straight out."""
    ik, iq = pl.program_id(2), pl.program_id(3)  # kv outer, q inner

    @pl.when(iq == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    @pl.when(iq >= ik)
    def _live():
        p, ds, q, k, do = _p_ds(
            mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, scale
        )
        _add_dk_dv(dk_acc_ref, dv_acc_ref, p, ds, q, do)
        dq = jax.lax.dot(ds, k, preferred_element_type=jnp.float32)
        if not dq_acc:
            dq_ref[0, 0] = dq.astype(dq_ref.dtype)
            return
        (dq_acc_ref,) = dq_acc
        block = dq.shape[0]
        rows = pl.ds(pl.multiple_of(iq * block, block), block)

        @pl.when(ik == 0)
        def _first():
            dq_acc_ref[rows, :] = dq

        @pl.when(ik > 0)
        def _later():
            dq_acc_ref[rows, :] += dq

        @pl.when(ik == iq)
        def _write():
            dq_ref[0, 0, rows, :] = dq_acc_ref[rows, :].astype(dq_ref.dtype)

    @pl.when(iq == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _one_pass_vmem_bytes(seq_len, d, d_v, block, dtype) -> int:
    """VMEM the one pass needs at several kv blocks, from its shapes alone
    as ``flash_attention._fused_bwd_vmem_bytes`` counts it (a last dimension
    occupies whole 128-lane tiles): the pipeline's two buffers of every block
    in and out, the mask's among them, the log-sum-exp block at 128 lanes,
    the dk / dv accumulators, four float32 ``[block, block]`` temporaries,
    and dq over the WHOLE sequence: the float32 scratch and two buffers of
    the output.  At 16,384 x 256 / 256 in bfloat16 and blocks of 512: 4.5 +
    1 + 4 + 32 = 41.5 MiB."""
    item = jnp.dtype(dtype).itemsize
    d, d_v = (-(-width // _LANE) * _LANE for width in (d, d_v))
    q_side = block * (d + 2 * d_v)          # q, do, o
    kv_side = block * (d + d_v)             # k, v in; dk, dv out
    blocks = (
        2 * item * (q_side + 2 * kv_side) + 2 * 4 * block * _LANE
        + 2 * block * block
    )
    temporaries = 4 * 4 * block * block
    dq = seq_len * d * (4 + 2 * item)
    return blocks + 4 * kv_side + temporaries + dq


def backward_path(seq_len, d, d_v, block, dtype) -> str:
    """``"one_pass"`` or ``"split"``: the backward :func:`mha` runs at these
    sizes.  One kv block holds no dq across blocks and always takes the one
    pass; several do while :func:`_one_pass_vmem_bytes` is within
    ``_VMEM_LIMIT``."""
    if seq_len == block:
        return "one_pass"
    need = _one_pass_vmem_bytes(seq_len, d, d_v, block, dtype)
    return "one_pass" if need <= _VMEM_LIMIT else "split"


def _kv_outer_specs(block, d, d_v):
    """Block specs of a grid ``(batch, head, kv block, q block)``, q inner:
    the operands' ``(mask, q, k, v, do, lse, o)`` and the outputs' ``(dk,
    dv)``.  A dead step (a q block before the kv block) parks the q side."""
    def parked_q_rows(ib, ih, ik, iq):
        return (ib, ih, jnp.maximum(iq, ik), 0)

    def kv_rows(ib, ih, ik, iq):
        return (ib, ih, ik, 0)

    in_specs = [
        pl.BlockSpec(
            (1, block, block),
            lambda ib, ih, ik, iq: (ib, jnp.maximum(iq, ik), ik),
        ),
        pl.BlockSpec((1, 1, block, d), parked_q_rows),
        pl.BlockSpec((1, 1, block, d), kv_rows),
        pl.BlockSpec((1, 1, block, d_v), kv_rows),
        pl.BlockSpec((1, 1, block, d_v), parked_q_rows),
        pl.BlockSpec((1, 1, block, _STAT), parked_q_rows),
        pl.BlockSpec((1, 1, block, d_v), parked_q_rows),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, block, d), kv_rows),
        pl.BlockSpec((1, 1, block, d_v), kv_rows),
    ]
    return in_specs, out_specs


def _flash_bwd_one_pass(q, k, v, mask, o, lse, do, *, scale, block):
    b, h, t, d = q.shape
    d_v = v.shape[3]
    n = t // block
    lse_l = jnp.broadcast_to(lse[..., None], (*lse.shape, _STAT))
    in_specs, dkv_specs = _kv_outer_specs(block, d, d_v)
    dq_spec = in_specs[1]       # one kv block: the q block's own
    scratch = [
        pltpu.VMEM((block, d), jnp.float32),
        pltpu.VMEM((block, d_v), jnp.float32),
    ]
    if n > 1:
        dq_spec = pl.BlockSpec(
            (1, 1, t, d), lambda ib, ih, ik, iq: (ib, ih, 0, 0)
        )
        scratch.append(pltpu.VMEM((t, d), jnp.float32))
    return pl.pallas_call(
        functools.partial(_bwd_one_pass_kernel, scale=scale),
        grid=(b, h, n, n),
        in_specs=in_specs,
        out_specs=[dq_spec, *dkv_specs],
        scratch_shapes=scratch,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, t, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, t, d_v), v.dtype),
        ],
        compiler_params=_params(outer="arbitrary"),
        interpret=backend.interpret(),
    )(mask, q, k, v, do, lse_l, o)


def _flash_bwd(q, k, v, mask, o, lse, do, *, scale, block):
    b, h, t, d = q.shape
    d_v = v.shape[3]
    n = t // block
    lse_l = jnp.broadcast_to(lse[..., None], (*lse.shape, _STAT))
    operands = (mask, q, k, v, do, lse_l, o)

    def q_rows(ib, ih, iq, ik):
        return (ib, ih, iq, 0)

    def parked_kv_rows(ib, ih, iq, ik):
        return (ib, ih, jnp.minimum(ik, iq), 0)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale),
        grid=(b, h, n, n),
        in_specs=[
            pl.BlockSpec(
                (1, block, block),
                lambda ib, ih, iq, ik: (ib, iq, jnp.minimum(ik, iq)),
            ),
            pl.BlockSpec((1, 1, block, d), q_rows),
            pl.BlockSpec((1, 1, block, d), parked_kv_rows),
            pl.BlockSpec((1, 1, block, d_v), parked_kv_rows),
            pl.BlockSpec((1, 1, block, d_v), q_rows),
            pl.BlockSpec((1, 1, block, _STAT), q_rows),
            pl.BlockSpec((1, 1, block, d_v), q_rows),
        ],
        out_specs=pl.BlockSpec((1, 1, block, d), q_rows),
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        compiler_params=_params(),
        interpret=backend.interpret(),
    )(*operands)

    in_specs, out_specs = _kv_outer_specs(block, d, d_v)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale),
        grid=(b, h, n, n),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block, d), jnp.float32),
            pltpu.VMEM((block, d_v), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, t, d_v), v.dtype),
        ],
        compiler_params=_params(),
        interpret=backend.interpret(),
    )(*operands)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _core(q, k, v, mask, scale, block):
    return _flash_fwd(q, k, v, mask, scale=scale, block=block)


def _core_fwd(q, k, v, mask, scale, block):
    o, lse = _flash_fwd(q, k, v, mask, scale=scale, block=block)
    # the names ``ops/flash_attention.py``'s outputs carry: a remat policy
    # that keeps a flash kernel's output and rows keeps these
    o = jax.ad_checkpoint.checkpoint_name(o, "flash_out")
    lse = jax.ad_checkpoint.checkpoint_name(lse, "flash_lse")
    return (o, lse), (q, k, v, mask, o, lse)


def _core_bwd(scale, block, residuals, cotangents):
    q, k, v, mask, o, lse = residuals
    do, _ = cotangents      # the rows' log-sum-exp feeds detached terms only
    path = backward_path(q.shape[2], q.shape[3], v.shape[3], block, q.dtype)
    impl = _flash_bwd_one_pass if path == "one_pass" else _flash_bwd
    dq, dk, dv = impl(q, k, v, mask, o, lse, do, scale=scale, block=block)
    return dq, dk, dv, None


_core.defvjp(_core_fwd, _core_bwd)


def mha(
    q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array, *,
    scale: float, block: int,
) -> Tuple[jax.Array, jax.Array]:
    """Attention of ``q [B, T, H, D]`` over the keys ``mask [B, T, T]``
    (int8, 1: chosen) names of ``k [B, T, H, D]``, ``v [B, T, H, Dv]``:
    ``(o [B, T, H, Dv], lse [B, T, H])``, the second the rows' log-sum-exp
    of the scaled scores over the chosen keys (float32; no gradient flows
    through it).  ``block`` is :func:`block_size`'s, not 0."""
    o, lse = _core(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), mask, float(scale), int(block),
    )
    return o.transpose(0, 2, 1, 3), lse.transpose(0, 2, 1)
