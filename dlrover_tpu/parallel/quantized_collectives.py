"""Quantized cross-replica all-reduce: int8 wire format for DCN gradients.

Capability ref: the reference's quantization stack exists for *memory*
(``atorch/atorch/ops/csrc/quantization``); the communication-side analogue
on TPU is quantizing the cross-slice (DCN) gradient all-reduce, the one
collective that rides the slow wire in the mesh layout policy
(``runtime/mesh.py``: only ``dcn_data`` crosses slices).  Scheme follows
the EQuARX shape (arXiv:2506.17615, PAPERS.md): two quantized phases
instead of one fp all-reduce —

  1. reduce-scatter phase: each replica quantizes its shard-of-others and
     all-to-alls int8 blocks + fp scales; the owner dequantizes and sums
     in fp32 (no int8 overflow);
  2. broadcast phase: owners re-quantize their reduced shard and
     all-gather int8 + scales.

Wire bytes: ~(1 + 4/block) bytes/element per phase vs 2 (bf16) or 4
(fp32) for the direct all-reduce — ~1.9x less DCN traffic than bf16 at
block 256.  Use inside ``shard_map`` over the DCN axis; gradients only
(symmetric-absmax block quantization error is well inside optimizer noise,
asserted by the tests).
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

# Below this payload the n-1 quantized ring hops are pure latency: the
# one-shot all-to-all (two logical hops) wins.  EQuARX's crossover on ICI
# sits near the MiB scale; the exact constant only shifts which tiny
# leaves take which lowering, both of which are correct.
RING_MIN_BYTES = 1 << 20


def axis_crosses_dcn(mesh, axis_name: str) -> bool:
    """Whether the mesh axis spans TPU slices (so its wire is DCN).

    Slice membership comes from the devices' ``slice_index``; CPU and
    single-slice devices have none, so they never cross.
    """
    try:
        import numpy as np

        ax = list(mesh.axis_names).index(axis_name)
        along = np.moveaxis(mesh.devices, ax, 0)
        slices = {
            getattr(along[i].flat[0], "slice_index", 0)
            for i in range(along.shape[0])
        }
        return len(slices) > 1
    except Exception:  # noqa: BLE001 - unknown topology: assume one slice
        return False


def select_reduce_algo(
    n: int, payload_bytes: int = 0, crosses_dcn: bool = False
) -> str:
    """EQuARX-style topology-aware algorithm choice: "oneshot" | "ring".

    The one-shot (all-to-all, tree-like two logical hops, one quantization
    round) wins when latency dominates — tiny groups, small payloads, or a
    DCN-crossing axis where per-hop latency is ~100x ICI.  The ring
    (``n-1`` neighbor hops, quantizing the travelling partial each hop) is
    bandwidth-optimal per element and wins for large ICI payloads; its
    price is one quantization round *per hop*, so its error grows with
    ``n`` — another reason to keep small groups on one-shot.
    """
    if crosses_dcn or n <= 2:
        return "oneshot"
    if payload_bytes and payload_bytes < RING_MIN_BYTES:
        return "oneshot"
    return "ring"


def _block_quant(x: jax.Array, block: int) -> Tuple[jax.Array, jax.Array]:
    """[N] fp -> (int8 [N], scales fp32 [N/block]); N padded by caller."""
    rows = x.reshape(-1, block)
    absmax = jnp.max(jnp.abs(rows), axis=1, keepdims=True)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / 127.0)
    q = jnp.clip(jnp.round(rows / scale), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale[:, 0].astype(jnp.float32)


def _block_dequant(q: jax.Array, scales: jax.Array, block: int) -> jax.Array:
    rows = q.reshape(-1, block).astype(jnp.float32)
    return (rows * scales[:, None]).reshape(-1)


def _oneshot_rs(
    chunks: jax.Array, axis_name: str, n: int, block: int
) -> jax.Array:
    """Tree/one-shot reduce-scatter core: quantize all n chunks, one
    all-to-all so member i receives every replica's chunk i, dequantize +
    fp32 sum.  ``chunks`` is fp32 [n, shard] with shard % block == 0;
    returns this member's reduced fp32 [shard]."""
    shard = chunks.shape[1]
    q, scales = _block_quant(chunks.reshape(-1), block)
    q_shards = q.reshape(n, shard)
    s_shards = scales.reshape(n, shard // block)
    q_recv = jax.lax.all_to_all(q_shards, axis_name, 0, 0, tiled=False)
    s_recv = jax.lax.all_to_all(s_shards, axis_name, 0, 0, tiled=False)
    contributions = jax.vmap(
        lambda qq, ss: _block_dequant(qq, ss, block)
    )(q_recv, s_recv)
    return jnp.sum(contributions, axis=0)


def _ring_rs(
    chunks: jax.Array, axis_name: str, n: int, block: int
) -> jax.Array:
    """Ring reduce-scatter core: ``n-1`` neighbor hops, the travelling
    partial re-quantized per hop (the EQuARX ring).  Bandwidth-optimal —
    each member sends one chunk per hop instead of n-1 chunks at once.
    Member i ends holding reduced chunk i (matching shard_map's member ->
    block placement along the axis)."""
    idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    # At hop t member i sends the partial for chunk (i - t - 1) mod n and
    # receives chunk (i - t - 2) mod n, adding its local copy; after n-1
    # hops the accumulated partial is chunk i, fully reduced.
    acc = jnp.take(chunks, (idx - 1) % n, axis=0)
    for t in range(n - 1):
        q, s = _block_quant(acc, block)
        q = jax.lax.ppermute(q, axis_name, perm)
        s = jax.lax.ppermute(s, axis_name, perm)
        received = _block_dequant(q, s, block)
        acc = received + jnp.take(chunks, (idx - t - 2) % n, axis=0)
    return acc


def quantized_all_reduce(
    x: jax.Array,
    axis_name: str,
    block: int = 256,
    mean: bool = True,
    algo: str = "oneshot",
) -> jax.Array:
    """All-reduce ``x`` over ``axis_name`` with an int8 wire format.

    Call inside ``shard_map``/``pmap`` where ``axis_name`` is bound.  The
    result is identical on every member (quantization error included), so
    replicated-parameter invariants hold.  ``algo`` selects the
    reduce-scatter phase's lowering ("oneshot" all-to-all vs "ring"
    neighbor hops — see :func:`select_reduce_algo`); the broadcast phase
    is an all-gather either way.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    # Pad so every member owns an equal whole-blocks shard.
    shard = -(-flat.size // (n * block)) * block
    flat = jnp.pad(flat, (0, shard * n - flat.size))

    # Phase 1: quantized reduce-scatter -> my reduced fp32 shard.
    chunks = flat.reshape(n, shard)
    rs = _ring_rs if algo == "ring" else _oneshot_rs
    reduced = rs(chunks, axis_name, n, block)
    if mean:
        reduced = reduced / n

    # Phase 2: re-quantize the reduced shard, all-gather int8 + scales.
    q2, s2 = _block_quant(reduced, block)
    q_all = jax.lax.all_gather(q2, axis_name, axis=0, tiled=False)
    s_all = jax.lax.all_gather(s2, axis_name, axis=0, tiled=False)
    out = jax.vmap(lambda qq, ss: _block_dequant(qq, ss, block))(
        q_all, s_all
    ).reshape(-1)
    return out[: x.size].reshape(orig_shape).astype(orig_dtype)


def quantized_reduce_scatter(
    x: jax.Array,
    axis_name: str,
    *,
    dim: int = 0,
    block: int = 256,
    mean: bool = True,
    algo: str = "oneshot",
) -> jax.Array:
    """Reduce-scatter ``x`` over ``axis_name`` on the int8 wire format.

    Member ``i`` returns chunk ``i`` of the reduction, split along ``dim``
    (which must divide evenly by the axis size) — exactly the shard_map
    out_specs contract when the caller adds ``axis_name`` to ``dim`` of
    the out spec.  This is the ZeRO-1 gradient leg: the quantized wire
    carries each gradient exactly once (vs twice for the all-reduce),
    feeding the shard-local optimizer update; the updated params ride back
    on a full-precision all-gather, so quantization noise never touches
    the master weights.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    if x.shape[dim] % n:
        raise ValueError(
            f"reduce-scatter dim {dim} (size {x.shape[dim]}) must divide "
            f"by the {n}-member axis {axis_name!r}"
        )
    orig_dtype = x.dtype
    moved = jnp.moveaxis(x, dim, 0)
    chunk_shape = (moved.shape[0] // n,) + moved.shape[1:]
    chunks = moved.astype(jnp.float32).reshape(n, -1)
    csize = chunks.shape[1]
    padded = -(-csize // block) * block
    chunks = jnp.pad(chunks, ((0, 0), (0, padded - csize)))
    rs = _ring_rs if algo == "ring" else _oneshot_rs
    reduced = rs(chunks, axis_name, n, block)
    if mean:
        reduced = reduced / n
    out = reduced[:csize].reshape(chunk_shape)
    return jnp.moveaxis(out, 0, dim).astype(orig_dtype)


def _ring_ag(
    q: jax.Array, s: jax.Array, axis_name: str, n: int
) -> Tuple[jax.Array, jax.Array]:
    """Ring all-gather core: each member's (int8, scales) payload travels
    ``n-1`` neighbor hops *unchanged* — quantized once at the source, so
    unlike the ring reduce-scatter the error does not grow with ``n``.
    Returns [n, ...] stacks ordered by source member index."""
    idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    qs, ss = [q], [s]
    for _ in range(n - 1):
        q = jax.lax.ppermute(q, axis_name, perm)
        s = jax.lax.ppermute(s, axis_name, perm)
        qs.append(q)
        ss.append(s)
    # Received order on member i is src = i, i-1, ..., i-(n-1) (mod n);
    # flip + roll by i+1 re-keys row j to src j on every member.
    q_stack = jnp.stack(qs)[::-1]
    s_stack = jnp.stack(ss)[::-1]
    return (
        jnp.roll(q_stack, idx + 1, axis=0),
        jnp.roll(s_stack, idx + 1, axis=0),
    )


def quantized_all_gather(
    x: jax.Array,
    axis_name: str,
    *,
    dim: int = 0,
    block: int = 256,
    algo: str = "oneshot",
) -> jax.Array:
    """All-gather ``x`` over ``axis_name`` on the int8 wire format.

    The mirror of :func:`quantized_reduce_scatter`: member ``i``
    contributes its shard and every member returns the full tensor with
    the ``n`` shards concatenated along ``dim`` in member order — exactly
    the shard_map contract when the caller *removes* ``axis_name`` from
    ``dim`` of the out spec.  This is the ZeRO-1 re-replication leg: each
    member block-quantizes its updated parameter shard once and the int8
    payload + fp32 scales ride the wire (~1.9x less than bf16 at block
    256); every member dequantizes all ``n`` shards, so the result is
    identical everywhere (quantization error included) and
    replicated-parameter invariants hold.

    ``algo`` picks the transport: "oneshot" (one logical all-gather hop)
    or "ring" (``n-1`` neighbor ``ppermute`` hops).  The payload is
    quantized exactly once at its source either way, so both algorithms
    produce bit-identical results — the split only trades launch latency
    against per-hop bandwidth, same as :func:`select_reduce_algo`.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    orig_dtype = x.dtype
    moved = jnp.moveaxis(x, dim, 0)
    flat = moved.astype(jnp.float32).reshape(-1)
    padded = -(-flat.size // block) * block
    q, s = _block_quant(jnp.pad(flat, (0, padded - flat.size)), block)
    if algo == "ring":
        q_all, s_all = _ring_ag(q, s, axis_name, n)
    else:
        q_all = jax.lax.all_gather(q, axis_name, axis=0, tiled=False)
        s_all = jax.lax.all_gather(s, axis_name, axis=0, tiled=False)
    shards = jax.vmap(lambda qq, ss: _block_dequant(qq, ss, block))(
        q_all, s_all
    )
    out = shards[:, : flat.size].reshape((n,) + moved.shape)
    out = out.reshape((n * moved.shape[0],) + moved.shape[1:])
    return jnp.moveaxis(out, 0, dim).astype(orig_dtype)


def a2a_wire_bytes(
    n_elems: int, quant: str = "none", *, block: int = 256,
    elem_bytes: int = 4,
) -> int:
    """Modeled wire bytes for ONE all-to-all leg over ``n_elems`` elements.

    The pure pricing twin of :func:`quantized_all_to_all`: the int8 wire
    carries 1 byte/element plus a 4-byte fp32 scale per quant block, vs
    ``elem_bytes`` (4 for fp32) on the plain transport.  ``auto.tune``'s
    ``est_comm_time`` and the MoE bench price the dispatch legs with this
    so the modeled discount and the implemented wire format cannot drift
    apart.
    """
    if quant == "int8":
        return n_elems + (-(-n_elems // block)) * 4
    return n_elems * elem_bytes


def quantized_all_to_all(
    x: jax.Array,
    axis_name: str,
    *,
    split_axis: int = 0,
    concat_axis: int = 0,
    block: int = 256,
) -> jax.Array:
    """All-to-all ``x`` over ``axis_name`` on the int8 wire format.

    The MoE dispatch transport: member ``i`` splits ``x`` into ``n``
    chunks along ``split_axis``, block-quantizes each chunk ONCE at the
    source, exchanges int8 payload + fp32 scales (chunk ``j`` to member
    ``j``), and every member dequantizes its ``n`` received chunks and
    concatenates them along ``concat_axis`` in member order — exactly
    ``jax.lax.all_to_all(..., tiled=True)`` semantics with ~(1 + 4/block)
    bytes/element on the wire instead of 4 (see :func:`a2a_wire_bytes`).

    Like the other quantized collectives this is dtype-preserving, pads
    partial blocks at the source and slices after dequant, and is the
    identity when the axis has one member (no wire → no quantization).
    When ``split_axis == concat_axis`` the exchange is an involution: a
    second call routes every chunk back to its source, which is how the
    MoE layer uses it (dispatch leg out, combine leg back).

    Differentiable: the permutation's exact adjoint is the inverse
    exchange (``split_axis``/``concat_axis`` swapped), and the cotangent
    rides the SAME int8 wire — the straight-through estimator every
    quantized-collective training scheme uses, so forward and backward
    dispatch legs both get the wire discount.
    """
    return _qa2a(x, axis_name, split_axis, concat_axis, block)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _qa2a(x, axis_name, split_axis, concat_axis, block):
    return _qa2a_impl(x, axis_name, split_axis, concat_axis, block)


def _qa2a_fwd(x, axis_name, split_axis, concat_axis, block):
    return _qa2a_impl(x, axis_name, split_axis, concat_axis, block), None


def _qa2a_bwd(axis_name, split_axis, concat_axis, block, _res, g):
    # Inverse permutation (roles swapped) on the quantized wire;
    # straight-through the rounding.
    return (_qa2a_impl(g, axis_name, concat_axis, split_axis, block),)


_qa2a.defvjp(_qa2a_fwd, _qa2a_bwd)


def _qa2a_impl(x, axis_name, split_axis, concat_axis, block):
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    if x.shape[split_axis] % n:
        raise ValueError(
            f"all-to-all split axis {split_axis} (size "
            f"{x.shape[split_axis]}) must divide by the {n}-member axis "
            f"{axis_name!r}"
        )
    orig_dtype = x.dtype
    moved = jnp.moveaxis(x, split_axis, 0)
    chunk_shape = (moved.shape[0] // n,) + moved.shape[1:]
    chunks = moved.astype(jnp.float32).reshape(n, -1)
    csize = chunks.shape[1]
    padded = -(-csize // block) * block
    chunks = jnp.pad(chunks, ((0, 0), (0, padded - csize)))
    # One quantization round at the source; per-chunk block alignment
    # holds because each row pads to a whole number of blocks.
    q, s = _block_quant(chunks.reshape(-1), block)
    q_recv = jax.lax.all_to_all(
        q.reshape(n, padded), axis_name, 0, 0, tiled=False
    )
    s_recv = jax.lax.all_to_all(
        s.reshape(n, padded // block), axis_name, 0, 0, tiled=False
    )
    deq = jax.vmap(lambda qq, ss: _block_dequant(qq, ss, block))(
        q_recv, s_recv
    )
    pieces = deq[:, :csize].reshape((n,) + chunk_shape)
    # Restore each piece to the original dim order, then merge the member
    # dim into ``concat_axis`` (row-major reshape == concat in member
    # order, matching the tiled all_to_all contract).
    pieces = jnp.moveaxis(pieces, 1, 1 + split_axis)
    out = jnp.moveaxis(pieces, 0, concat_axis)
    shape = (
        out.shape[:concat_axis]
        + (out.shape[concat_axis] * out.shape[concat_axis + 1],)
        + out.shape[concat_axis + 2:]
    )
    return out.reshape(shape).astype(orig_dtype)


def quantized_process_allgather(local_tree, block: int = 256):
    """Host-level quantized allgather: the Local-SGD outer-sync transport.

    Each host quantizes its parameter-delta pytree to int8 + block scales,
    allgathers the compressed payload across processes (DCN), and every
    host dequantizes all replicas — the drop-in ``allgather_fn`` for
    :class:`dlrover_tpu.parallel.local_sgd.LocalSGD` at ~1.9x less DCN
    bytes than bf16 deltas.  Returns ``[tree_per_host]``.
    """
    from jax.experimental import multihost_utils

    if jax.process_count() == 1:
        # No wire to compress: exact and free.
        return [local_tree]
    leaves, treedef = jax.tree_util.tree_flatten(local_tree)
    shapes = [leaf.shape for leaf in leaves]
    dtypes = [jnp.asarray(leaf).dtype for leaf in leaves]
    payload = []
    for leaf in leaves:
        flat = jnp.asarray(leaf, jnp.float32).reshape(-1)
        padded = -(-flat.size // block) * block
        flat = jnp.pad(flat, (0, padded - flat.size))
        q, s = _block_quant(flat, block)
        payload.append((q, s))
    gathered = multihost_utils.process_allgather(payload)
    n = jax.process_count()
    out = []
    for host in range(n):
        host_leaves = []
        for (q_all, s_all), shape, dtype in zip(gathered, shapes, dtypes):
            deq = _block_dequant(q_all[host], s_all[host], block)
            size = math.prod(shape)
            host_leaves.append(deq[:size].reshape(shape).astype(dtype))
        out.append(jax.tree_util.tree_unflatten(treedef, host_leaves))
    return out
