"""Multi-head attention with pluggable kernels and Ulysses-style SP.

Counterpart of the reference's flash-attention module zoo
(ref ``atorch/atorch/modules/transformer/layers.py:1278-1640``) and its
Ulysses sequence parallelism
(ref ``atorch/atorch/auto/opt_lib/sequence_parallel_optimization.py:9-103``,
``distributed/distributed.py:474-501`` ``_SeqAllToAll``).

TPU-first design notes:
  * Sequence parallelism needs no hand-written all-to-all: activations enter
    sharded ``[batch, act_seq, ...]`` (sequence split over the ``seq`` axis)
    and are constrained to ``[batch, ..., act_heads, ...]`` (heads split over
    ``seq`` x ``tensor``) inside attention.  GSPMD materializes exactly the
    Ulysses a2a pair at the boundaries.
  * The attention math itself is a pluggable ``attention_impl``: ``"xla"``
    (einsum softmax, XLA-fused) or ``"flash"`` (Pallas flash-attention
    kernel).  Ring-attention context parallelism lives in
    ``dlrover_tpu.parallel.ring_attention`` and wraps either impl.
  * Two modules share that math: :class:`Attention` (one ``head_dim`` for
    q, k and v; GQA; QK-norm) and :class:`LatentAttention` (the
    DeepSeek-V2/V3 family's multi-head latent attention: q through a
    low-rank latent, k and v rebuilt from a shared latent row, one rotary
    key for all heads, keys wider than values).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import flax.linen as nn
import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dlrover_tpu.models import layers
from dlrover_tpu.models.family import Family
from dlrover_tpu.parallel import rules as lr
from dlrover_tpu.runtime.mesh import (
    DATA_AXIS,
    FSDP_AXIS,
    SEQ_AXIS,
    TENSOR_AXIS,
    current_mesh,
    mesh_axis_size,
    shard_local,
    shard_map_compat,
)

NEG_INF = -1e15


def ulysses_attention(
    attn_fn: Callable,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: Optional[jax.Array],
) -> jax.Array:
    """Run ``attn_fn`` under explicit Ulysses all-to-alls over the seq axis.

    Counterpart of the reference's ``_SeqAllToAll`` autograd function
    (ref ``atorch/atorch/distributed/distributed.py:474-501``).  Inputs
    arrive sequence-sharded ``[B, S/sp, H, D]``; inside the shard_map an
    ``all_to_all`` swaps the shards to head-sharded ``[B, S, H/sp, D]``
    for the attention math, and back after.

    ``attn_fn`` sees the whole sequence, so it may not be windowed by a
    band that this function splits: ``Attention`` refuses a window here.

    Expressing the switch as annotations alone (``ACT_HEADS ->
    (seq, tensor)`` constraints) leaves the resharding decision to the
    SPMD partitioner, which falls back to "involuntary full
    rematerialization" (replicate + repartition) on the boundary reshapes
    — the explicit collective compiles to a clean ICI all-to-all instead.
    """
    mesh = current_mesh()
    batch_spec = (DATA_AXIS, FSDP_AXIS)
    io_spec = P(batch_spec, SEQ_AXIS, TENSOR_AXIS, None)
    specs = [io_spec, io_spec, io_spec]
    args = [q, k, v]
    if segment_ids is not None:
        specs.append(P(batch_spec, None))
        args.append(segment_ids)

    @functools.partial(
        shard_map_compat,
        mesh=mesh,
        in_specs=tuple(specs),
        out_specs=io_spec,
    )
    def inner(q, k, v, seg=None):
        swap = functools.partial(
            jax.lax.all_to_all, axis_name=SEQ_AXIS,
            split_axis=2, concat_axis=1, tiled=True,
        )
        out = attn_fn(swap(q), swap(k), swap(v), seg)
        return jax.lax.all_to_all(
            out, axis_name=SEQ_AXIS, split_axis=1, concat_axis=2, tiled=True
        )

    return inner(*args)


def xla_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Reference einsum attention; fp32 softmax; shapes [B, S, H, D];
    the scores times ``scale`` (default ``D ** -0.5``).  ``window`` (causal
    only) keeps the band ``0 <= i - j < window``: a query sees itself and
    the ``window - 1`` tokens before it.

    Supports GQA (H_kv dividing H_q) and packed-sequence masks via
    ``segment_ids`` — the capability match for the reference's GLM/pack mask
    support (ref ``layers.py:1255`` ``fa2_with_glm_mask``).
    """
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    scale = d ** -0.5 if scale is None else scale
    # GQA via broadcast, not jnp.repeat: grouping q keeps K/V (and their
    # remat recompute) at H_kv width instead of inflating HBM by `group`x.
    # (``v`` may have another width than ``q`` and ``k``: the output's.)
    qg = q.reshape(b, sq, hkv, group, d)
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) * scale
    sk = k.shape[1]
    mask = None
    if causal:
        qpos = jnp.arange(sq)[:, None]
        kpos = jnp.arange(sk)[None, :]
        mask = qpos >= kpos
        if window is not None:
            mask = jnp.logical_and(mask, qpos - kpos < window)
    elif window is not None:
        raise ValueError("a window needs causal attention")
    if segment_ids is not None:
        seg = segment_ids[:, :, None] == segment_ids[:, None, :]
        seg = seg[:, None, None, :, :]
        mask = seg if mask is None else jnp.logical_and(mask[None, None], seg)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(b, sq, hq, v.shape[-1])


def cached_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_positions: jax.Array,
    scale: Optional[float] = None,
) -> jax.Array:
    """Decode attention: queries at absolute ``q_positions`` [B, T]
    against the full KV cache [B, L, H_kv, D]; cache slots past a query's
    position (unwritten, or future) are masked.  GQA via grouped q.  It
    knows no window: a windowed layer would keep a ring of ``W`` rows, which
    nothing in ``serving/decode.py`` holds yet (``Attention`` refuses)."""
    b, sq, hq, d = q.shape
    cache_len, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = d ** -0.5 if scale is None else scale
    qg = q.reshape(b, sq, hkv, group, d)
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) * scale
    kpos = jnp.arange(cache_len)
    mask = kpos[None, None, None, None, :] <= (
        q_positions[:, None, None, :, None]
    )
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(b, sq, hq, d)


def _flash_local(
    q, k, v, segment_ids, *, block_q, block_kv, scale=None, window=None
):
    """Causal flash attention on each device's own batch rows and heads
    (:func:`shard_local`): q/k/v stay sharded as the active rule table
    lays out ``[batch, -, act_heads, kv]``, the sequence is whole.  Under a
    ``window`` the kernels skip what lies outside the band."""
    from dlrover_tpu.ops import flash_attention as fa

    qkv_spec = nn.logical_to_mesh_axes((lr.BATCH, None, lr.ACT_HEADS, lr.KV))
    args, specs = [q, k, v], [qkv_spec] * 3
    if segment_ids is not None:
        args.append(segment_ids)
        specs.append(nn.logical_to_mesh_axes((lr.BATCH, None)))

    def local(q, k, v, seg=None):
        return fa.mha(
            q, k, v, causal=True, segment_ids=seg, scale=scale,
            block_q=block_q, block_kv=block_kv, window=window,
        )

    return shard_local(
        local, in_specs=tuple(specs), out_specs=qkv_spec
    )(*args)


class QKNorm(nn.Module):
    """RMSNorm of a projection ``[..., H, hd]`` before the rotation, float32
    accumulation.  Jointly over all heads with one ``[H * hd]`` scale in the
    published (head-major) order (OLMoE / OLMo-2 ``q_norm`` and ``k_norm``),
    or ``per_head``: each head's ``hd`` columns by their own mean square
    under ONE ``[hd]`` scale all heads share (the LFM2 family's
    ``q_layernorm`` / ``k_layernorm``)."""

    epsilon: float = 1e-5
    param_dtype: layers.Dtype = jnp.float32
    per_head: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        heads, head_dim = x.shape[-2:]
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(
                nn.initializers.ones_init(), (lr.NORM,)
            ),
            (head_dim if self.per_head else heads * head_dim,),
            self.param_dtype,
        )
        x32 = x.astype(jnp.float32)
        var = jnp.mean(
            jnp.square(x32), axis=-1 if self.per_head else (-2, -1),
            keepdims=True,
        )
        y = x32 * jax.lax.rsqrt(var + self.epsilon)
        scale = scale.astype(jnp.float32)
        if not self.per_head:
            scale = scale.reshape(heads, head_dim)
        return (y * scale).astype(x.dtype)


STATS_NAME = "attn_stats"
# The kinds of softmax-attention layer a layer pattern names: the causal
# triangle, a band of it, and (latent attention only, DeepSeek-V3.2's DSA)
# a set of keys a query, which a layer's own indexer CHOOSES
# (``index_attention``) or the nearest such layer before it hands over
# (``reuse_attention``); ``models/sparse_attention.py``.
FULL_ATTENTION = "full_attention"
SLIDING_ATTENTION = "sliding_attention"
INDEX_ATTENTION = "index_attention"
REUSE_ATTENTION = "reuse_attention"


def score_bound(q: jax.Array, k: jax.Array, scale: float) -> jax.Array:
    """An upper bound of the largest ``|q_i . k_j| * scale`` of any head,
    ``[B, S, H, D]`` and ``[B, S, H_kv, D]`` in, a scalar out: the longest
    query row of a key head's group times its longest key row
    (Cauchy-Schwarz; ``S x d`` work, where the exact maximum is the ``S x
    S`` scores again).  It moves with whatever scales q and k, a rotation's
    factor included."""
    b, _, hq, _ = q.shape
    hkv = k.shape[2]
    q_len = jnp.linalg.norm(q.astype(jnp.float32), axis=-1).max(axis=1)
    k_len = jnp.linalg.norm(k.astype(jnp.float32), axis=-1).max(axis=1)
    q_len = q_len.reshape(b, hkv, hq // hkv).max(axis=-1)
    return (q_len * k_len).max() * scale


class Attention(nn.Module):
    """Causal self-attention block with RoPE/GQA and SP-aware shardings.
    ``window`` keeps the band ``0 <= i - j < window`` of the causal mask
    (a ``sliding_attention`` layer); ``rotation`` is the layer kind's rotary
    embedding where it is more than ``rope_theta`` (YaRN)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    use_rope: bool = True
    rope_theta: float = 10000.0
    use_bias: bool = False
    dtype: layers.Dtype = jnp.bfloat16
    param_dtype: layers.Dtype = jnp.float32
    attention_impl: str = "xla"
    qk_norm: Any = False            # False | True (joint) | "per_head"
    norm_eps: float = 1e-5
    flash_block_q: int = 512
    flash_block_kv: int = 512
    # What multiplies the scores before the softmax on every path (flash,
    # einsum, ring, cached); 0 -> ``head_dim ** -0.5``.  Granite's
    # ``attention_multiplier`` is 1/128 at heads of 128.
    scale: float = 0.0
    # Autoregressive decoding: keep K/V in a "cache" collection of
    # ``cache_len`` slots and attend incoming queries (prefill chunk or
    # single decode token) against it.
    decode: bool = False
    cache_len: int = 0
    window: int = 0                 # 0: the whole causal triangle
    rotation: Optional[layers.Rotation] = None
    # Sow ``STATS_NAME``: [the bound of a full layer's scores, of a windowed
    # layer's] (:func:`score_bound`; the other entry 0).
    score_stats: bool = False
    # The spread the scaled scores are seeded with: query and key kernels
    # at ``sqrt(init_score_std / features)``; 0: the default initialiser.
    init_score_std: float = 0.0
    # The output gate (Gated Attention, arXiv:2505.06708): the heads'
    # outputs times ``sigmoid(n W_gate)`` before the output projection,
    # ``W_gate`` ``[d, H]`` ("head_wise": one gate a head) or ``[d, H, hd]``
    # ("elementwise": one a channel); param and scope ``attn/gate``.
    gate: str = ""

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
    ) -> jax.Array:
        features = x.shape[-1]
        scale = self.scale or None
        if positions is None:
            positions = jnp.arange(x.shape[1])[None, :]
        window = self.window or None
        if self.window and (
            self.decode or self.attention_impl == "ring"
            or mesh_axis_size(SEQ_AXIS) > 1
        ):
            raise ValueError(
                f"a window of {self.window} keys runs on a whole sequence "
                "under attention_impl 'flash' or 'xla': cached_attention "
                "(decode=True) keeps no ring of window rows, and "
                "ulysses_attention and ring attention split the sequence "
                "the band runs along"
            )

        if self.num_kv_heads == self.num_heads:
            if self.init_score_std:
                raise ValueError(
                    "init_score_std seeds the separate query and key "
                    "kernels of grouped-query attention; the fused qkv "
                    "kernel has one initialiser"
                )
            # This is the whole rule: without GQA the three projections are
            # equally wide and run as one kernel (param ``qkv``), with GQA
            # they stay three (``query``/``key``/``value``).
            # One [d, H, 3*hd] matmul instead of three [d, H, hd] ones: the
            # wider N dim keeps the MXU tiled efficiently (measured 37% ->
            # ~75% MFU on v5e at GPT-2 1.5B shapes).  The split is on the
            # head_dim (KV) axis, which no strategy shards, so it is
            # TP/SP-clean.
            qkv = layers.DenseGeneral(
                (self.num_heads, 3 * self.head_dim),
                kernel_axes=(lr.EMBED, lr.HEADS, lr.KV),
                use_bias=self.use_bias,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="qkv",
            )(x)
            q = qkv[..., : self.head_dim]
            k = qkv[..., self.head_dim: 2 * self.head_dim]
            v = qkv[..., 2 * self.head_dim:]
        else:
            qk_init = layers.default_kernel_init
            if self.init_score_std:
                qk_init = nn.initializers.normal(
                    (self.init_score_std / features) ** 0.5
                )
            q = layers.DenseGeneral(
                (self.num_heads, self.head_dim),
                kernel_axes=(lr.EMBED, lr.HEADS, lr.KV),
                use_bias=self.use_bias,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=qk_init,
                name="query",
            )(x)
            k = layers.DenseGeneral(
                (self.num_kv_heads, self.head_dim),
                kernel_axes=(lr.EMBED, lr.HEADS, lr.KV),
                use_bias=self.use_bias,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=qk_init,
                name="key",
            )(x)
            v = layers.DenseGeneral(
                (self.num_kv_heads, self.head_dim),
                kernel_axes=(lr.EMBED, lr.HEADS, lr.KV),
                use_bias=self.use_bias,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="value",
            )(x)

        if self.qk_norm:
            # Train, prefill and cached decode all pass here.  The joint
            # norm is over all heads of a projection, so the fused kernel's
            # per-head [q | k | v] slices are normed over their two trailing
            # axes and the one wide QKV matmul is kept; "per_head" norms
            # each head's columns alone.
            per_head = self.qk_norm == "per_head"
            q = QKNorm(
                self.norm_eps, param_dtype=self.param_dtype,
                per_head=per_head, name="q_norm",
            )(q)
            k = QKNorm(
                self.norm_eps, param_dtype=self.param_dtype,
                per_head=per_head, name="k_norm",
            )(k)

        if self.use_rope:
            rotation = self.rotation or layers.Rotation(self.rope_theta)
            q, k = layers.rotary_embedding(
                q, k, positions, *rotation.table(self.head_dim)
            )
        if self.score_stats:
            bound = jax.lax.stop_gradient(
                score_bound(q, k, scale or self.head_dim ** -0.5)
            )
            zero = jnp.zeros_like(bound)
            self.sow(
                "intermediates", STATS_NAME,
                jnp.stack([zero, bound] if self.window else [bound, zero]),
            )

        if self.decode:
            b, t = x.shape[0], x.shape[1]
            cache_len = self.cache_len
            cached_k = self.variable(
                "cache", "cached_key", jnp.zeros,
                (b, cache_len, self.num_kv_heads, self.head_dim), self.dtype,
            )
            cached_v = self.variable(
                "cache", "cached_value", jnp.zeros,
                (b, cache_len, self.num_kv_heads, self.head_dim), self.dtype,
            )
            index = self.variable(
                "cache", "cache_index",
                lambda: jnp.zeros((), jnp.int32),
            )
            # Writes land at each row's OWN query positions (not a shared
            # scalar cursor): row r's contiguous chunk of t tokens starts at
            # positions[r, 0].  For the lockstep RL rollout every row shares
            # one position so this degrades to the old single-cursor write;
            # for the serving plane's slotted decode each slot sits at its
            # own depth, and the per-row write is what lets one jitted step
            # advance all of them.  cache_index is kept as a high-water
            # cursor for introspection only — no write reads it.
            q_positions = jnp.broadcast_to(positions, (b, t))
            row_start = q_positions[:, 0]

            def write_row(buf, new, start):
                return jax.lax.dynamic_update_slice(buf, new, (start, 0, 0))

            cached_k.value = jax.vmap(write_row)(
                cached_k.value, k.astype(self.dtype), row_start
            )
            cached_v.value = jax.vmap(write_row)(
                cached_v.value, v.astype(self.dtype), row_start
            )
            index.value = jnp.max(row_start) + t
            if self.attention_impl == "flash" and t >= 16:
                # Prefill chunks through the Pallas flash kernel: a chunk
                # this wide is a prompt prefill starting at position 0
                # (the serving engine's bucketed prefill; speculative
                # verify chunks are capped below 16 and single-token
                # decode is t == 1, so both stay on the cached path
                # below).  At position 0 the chunk IS the whole written
                # cache prefix, so causal flash over the fresh K/V equals
                # cached attention — without materializing [t, max_seq]
                # logits against the mostly-empty pool.  Narrower chunks
                # fall back to XLA: the kernel's 16-sublane tile floor
                # means a narrow bucket would be pure pad.
                out = _flash_local(
                    q, k.astype(self.dtype), v.astype(self.dtype), None,
                    block_q=self.flash_block_q,
                    block_kv=self.flash_block_kv, scale=scale,
                )
            else:
                out = cached_attention(
                    q, cached_k.value, cached_v.value, q_positions, scale
                )
        elif self.attention_impl == "ring":
            # Ring CP: sequence stays sharded; K/V stream around the ring.
            from dlrover_tpu.parallel.ring_attention import ring_attention

            spec = (lr.BATCH, lr.ACT_SEQ, lr.ACT_HEADS, lr.KV)
            q = nn.with_logical_constraint(q, spec)
            k = nn.with_logical_constraint(k, spec)
            v = nn.with_logical_constraint(v, spec)
            out = ring_attention(
                q, k, v, causal=True, segment_ids=segment_ids, scale=scale
            )
            out = nn.with_logical_constraint(out, spec)
        elif self.attention_impl in ("flash", "xla"):
            flash = self.attention_impl == "flash"
            blocks = dict(
                block_q=self.flash_block_q, block_kv=self.flash_block_kv,
                scale=scale,
            )
            if mesh_axis_size(SEQ_AXIS) > 1:
                # Ulysses SP: explicit seq<->heads all-to-alls (see
                # ulysses_attention docstring for why not annotations).
                # attn_fn runs inside its shard_map: already device-local.
                def attn_fn(q, k, v, seg):
                    if flash:
                        from dlrover_tpu.ops import flash_attention as fa

                        return fa.mha(
                            q, k, v, causal=True, segment_ids=seg, **blocks
                        )
                    return xla_attention(
                        q, k, v, causal=True, segment_ids=seg, scale=scale
                    )

                out = ulysses_attention(attn_fn, q, k, v, segment_ids)
            elif flash:
                out = _flash_local(
                    q, k, v, segment_ids, window=window, **blocks
                )
            else:
                attn_spec = (lr.BATCH, None, lr.ACT_HEADS, lr.KV)
                q = nn.with_logical_constraint(q, attn_spec)
                k = nn.with_logical_constraint(k, attn_spec)
                v = nn.with_logical_constraint(v, attn_spec)
                out = xla_attention(
                    q, k, v, causal=True, segment_ids=segment_ids,
                    scale=scale, window=window,
                )
                out = nn.with_logical_constraint(out, attn_spec)
        else:
            raise ValueError(
                f"unknown attention_impl {self.attention_impl!r}"
            )
        if self.gate:
            by_head = self.gate == "head_wise"
            gate = layers.DenseGeneral(
                self.num_heads if by_head
                else (self.num_heads, self.head_dim),
                kernel_axes=(lr.EMBED, lr.HEADS) + (() if by_head else (lr.KV,)),
                use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype, name="gate",
            )(x)
            with jax.named_scope("gate"):
                gate = jax.nn.sigmoid(gate.astype(jnp.float32))
                out = (
                    out.astype(jnp.float32)
                    * (gate[..., None] if by_head else gate)
                ).astype(self.dtype)
        out = layers.DenseGeneral(
            features,
            axis=(-2, -1),
            kernel_axes=(lr.HEADS, lr.KV, lr.EMBED),
            use_bias=self.use_bias,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="out",
        )(out)
        return out


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2/V3 family), training form.

    ``n`` is the normed residual stream ``[B, S, d]``::

        c_q = RMSNorm(n W_qa)                       # q_lora_rank
        [q_nope | q_pe]_h = c_q W_qb                # H heads, nope + rope
        [c_kv | k_pe] = n W_kva                     # kv_lora_rank | rope
        [k_nope | v]_h = RMSNorm(c_kv) W_kvb        # H heads, nope | v
        q_pe, k_pe <- RoPE(theta); ONE k_pe for all heads
        k_h = [k_nope_h | k_pe]; softmax(q_h k_h / sqrt(nope + rope)) v_h
        y = concat_h(o_h) W_o                       # H * v -> d

    ``q_lora_rank`` 0 (Ling-3.0-flash: published ``null``) takes q straight
    from the stream, ``[q_nope | q_pe]_h = n W_q`` with no latent and no q
    norm (the projection keeps the name ``q_b``: it is the one that makes
    the heads' queries).  ``gate="head_wise"`` multiplies each head's
    output by ``sigmoid(n W_gate)_h`` before ``W_o`` (Gated Attention,
    arXiv:2505.06708; ``W_gate`` ``[d, H]``, scope ``attn/gate``).

    Training keeps no cache: k and v are materialised per head and go
    through the attention kernel as ``H`` heads with keys ``nope + rope``
    wide and values ``v_head_dim`` wide (``ops/flash_attention.py`` takes
    the two widths; v is not padded).  The rotary halves follow the
    program's rotate-half convention on the LAST ``rope`` columns of a
    head's q and of the ``kv_a`` row (``rope_interleave`` in the published
    config is a fixed permutation of those columns).

    A sparse layer (``models/sparse_attention.py``, DeepSeek-V3.2's DSA)
    is this layer with ONE change: query ``t`` attends to a chosen set
    ``S_t`` of the keys before it (the softmax runs over ``S_t``), which
    an indexer that reads ``n`` and ``c_q`` picks or an earlier layer
    hands over; it builds on ``latent_qkv`` and ``latent_output`` below.

    There is no decode path, with a chosen set or without (``decode=True``
    is refused by ``TransformerConfig``): a latent cache would hold the normed
    ``c_kv`` row and the rotated ``k_pe`` (``kv_lora_rank + rope`` numbers
    a token, not ``2 H hd``) in ``serving/decode.py``'s cache pool, with
    ``W_kvb`` absorbed into the query and output sides; nothing there can
    hold it yet, so ``TransformerConfig`` refuses ``decode=True``.
    """

    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: layers.Dtype = jnp.bfloat16
    param_dtype: layers.Dtype = jnp.float32
    attention_impl: str = "xla"
    flash_block_q: int = 512
    flash_block_kv: int = 512
    scale: float = 0.0             # 0 -> (nope + rope) ** -0.5
    gate: str = ""                 # "" | "head_wise"
    # The spread the scaled scores are seeded with (0: the default
    # initialisers, which count the heads into the fan-in and spread them
    # by about 0.1): ``q_b`` and ``kv_b`` drawn so that a head's query
    # entries have this variance squared and its keys' one.
    init_score_std: float = 0.0

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
    ) -> jax.Array:
        if self.attention_impl not in ("flash", "xla"):
            raise ValueError(
                "latent attention runs under attention_impl 'flash' or "
                f"'xla', got {self.attention_impl!r}"
            )
        if self.gate not in ("", "head_wise"):
            raise ValueError(
                f"gate must be '' or 'head_wise', got {self.gate!r}"
            )
        q, k, v, _ = latent_qkv(self, x, positions)
        if self.attention_impl == "flash":
            out = _flash_local(
                q, k, v, segment_ids, block_q=self.flash_block_q,
                block_kv=self.flash_block_kv, scale=self.scale or None,
            )
        else:
            attn_spec = (lr.BATCH, None, lr.ACT_HEADS, lr.KV)
            q = nn.with_logical_constraint(q, attn_spec)
            k = nn.with_logical_constraint(k, attn_spec)
            v = nn.with_logical_constraint(v, attn_spec)
            out = xla_attention(
                q, k, v, causal=True, segment_ids=segment_ids,
                scale=self.scale or None,
            )
            out = nn.with_logical_constraint(out, attn_spec)
        return latent_output(self, out, x)


def _latent_dense(layer, width, axes, name, **kwargs):
    return layers.DenseGeneral(
        width, kernel_axes=axes, use_bias=False, dtype=layer.dtype,
        param_dtype=layer.param_dtype, name=name, **kwargs,
    )


def latent_qkv(layer, x: jax.Array, positions: Optional[jax.Array]):
    """``layer`` is a :class:`LatentAttention` inside its ``__call__`` (plain
    functions, not methods: a method of a module is a named scope of its
    own, and the projections' scopes are what they were).  The five
    projections but ``wo`` and the rotation: ``(q, k, v)``
    per head, and the normed q latent ``c_q`` (``None`` without one),
    which a sparse layer's indexer reads too."""
    nope, rope = layer.qk_nope_head_dim, layer.qk_rope_head_dim
    if positions is None:
        positions = jnp.arange(x.shape[1])[None, :]
    dense = functools.partial(_latent_dense, layer)

    def norm(name):
        return layers.make_norm(
            "rmsnorm", layer.dtype, layer.param_dtype, name,
            epsilon=layer.norm_eps,
        )

    c_q = None
    if layer.q_lora_rank:
        c_q = norm("q_norm")(
            dense(layer.q_lora_rank, (lr.EMBED, lr.LATENT), "q_a")(x)
        )
        q = dense(
            (layer.num_heads, nope + rope), (lr.LATENT, lr.HEADS, lr.KV),
            "q_b", **_head_init(layer, layer.init_score_std ** 2),
        )(c_q)
    else:
        q = dense(
            (layer.num_heads, nope + rope), (lr.EMBED, lr.HEADS, lr.KV),
            "q_b",
        )(x)
    kv_row = dense(
        layer.kv_lora_rank + rope, (lr.EMBED, lr.LATENT), "kv_a"
    )(x)
    c_kv = norm("kv_norm")(kv_row[..., : layer.kv_lora_rank])
    kv = dense(
        (layer.num_heads, nope + layer.v_head_dim),
        (lr.LATENT, lr.HEADS, lr.KV), "kv_b", **_head_init(layer, 1.0),
    )(c_kv)
    with jax.named_scope("rope"):
        q_pe, k_pe = layers.rotary_embedding(
            q[..., nope:], kv_row[..., None, layer.kv_lora_rank:],
            positions, layers.rope_frequencies(rope // 2, layer.rope_theta),
        )
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate([
            kv[..., :nope],
            jnp.broadcast_to(k_pe, (*kv.shape[:-1], rope)),
        ], axis=-1)
        v = kv[..., nope:]
    # Names for a remat policy that would KEEP the per-head keys and
    # values (``flash_only`` does not: the backward rebuilds them from
    # the latent row, which lost nothing measurable on the chip and
    # saves 2 x B x S x H x (192 + 128) bytes a layer; PERF.md §6).
    k = jax.ad_checkpoint.checkpoint_name(k, "latent_k")
    v = jax.ad_checkpoint.checkpoint_name(v, "latent_v")
    return q, k, v, c_q


def _head_init(layer, variance: float) -> Dict[str, Any]:
    """The initialiser of a projection from a normed latent to the
    heads where the scores' spread is seeded (``init_score_std``): a
    head's entries at ``variance``, the heads not counted into the
    fan-in, so that a scaled score spreads by ``init_score_std``."""
    if not layer.init_score_std:
        return {}
    return {"kernel_init": nn.initializers.variance_scaling(
        variance, "fan_in", "normal", in_axis=0, out_axis=(1, 2),
    )}


def latent_output(layer, out: jax.Array, x: jax.Array) -> jax.Array:
    """The heads' outputs ``[B, S, H, v]`` under the gate, through
    ``wo``."""
    if layer.gate:
        head_gate = _latent_dense(
            layer, layer.num_heads, (lr.EMBED, lr.HEADS), "gate"
        )(x)
        with jax.named_scope("gate"):
            out = (
                out.astype(jnp.float32)
                * jax.nn.sigmoid(head_gate.astype(jnp.float32))[..., None]
            ).astype(layer.dtype)
    return layers.DenseGeneral(
        x.shape[-1], axis=(-2, -1),
        kernel_axes=(lr.HEADS, lr.KV, lr.EMBED), use_bias=False,
        dtype=layer.dtype, param_dtype=layer.param_dtype, name="wo",
    )(out)


def from_config(cfg, kind: str = FULL_ATTENTION, **kwargs):
    """The config's softmax attention, latent or plain: the one place that
    reads the config's fields into the layer's, for the blocks that run it
    and for :func:`kernel_facts`.  ``kind`` ``sliding_attention`` under its
    window and its own rotation.  Only a model with windowed layers hands
    ``Attention`` a rotation or asks for its score statistics: every other
    model's program is the one it was.  A kind the config gives no rotation
    (``cfg.rotation(kind)`` ``None``: full layers without positions) does
    not rotate."""
    if cfg.latent_attention:
        return LatentAttention(
            num_heads=cfg.num_heads,
            q_lora_rank=cfg.q_lora_rank,
            kv_lora_rank=cfg.kv_lora_rank,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
            v_head_dim=cfg.v_head_dim,
            rope_theta=cfg.rope_theta,
            norm_eps=cfg.norm_eps,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            attention_impl=cfg.attention_impl,
            flash_block_q=cfg.flash_block_q,
            flash_block_kv=cfg.flash_block_kv,
            scale=cfg.attention_scale,
            gate=cfg.attention_gate,
            init_score_std=cfg.attn_init_score_std,
            **kwargs,
        )
    return Attention(
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.resolved_kv_heads,
        head_dim=cfg.resolved_head_dim,
        use_rope=rotation_of(cfg, kind) != "none",
        rope_theta=cfg.rope_theta,
        use_bias=cfg.use_bias,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        attention_impl=cfg.attention_impl,
        qk_norm=cfg.qk_norm,
        norm_eps=cfg.norm_eps,
        flash_block_q=cfg.flash_block_q,
        flash_block_kv=cfg.flash_block_kv,
        scale=cfg.attention_scale,
        decode=cfg.decode,
        cache_len=cfg.max_seq_len,
        init_score_std=cfg.attn_init_score_std,
        gate=cfg.attention_gate,
        **_by_kind(cfg, kind),
        **kwargs,
    )


def rotation_of(cfg, kind: str) -> str:
    """How ``kind``'s layers rotate q and k: ``none`` | ``rope`` | ``yarn``."""
    rotation = cfg.rotation(kind)
    if cfg.position != "rope" or rotation is None:
        return "none"
    return rotation.scaling or "rope"


def _by_kind(cfg, kind: str) -> Dict[str, Any]:
    sliding = kind == SLIDING_ATTENTION
    if not (sliding or cfg.rope_scaling or cfg.num_sliding_layers):
        return {}
    return dict(
        window=cfg.sliding_window if sliding else 0,
        rotation=cfg.rotation(kind),
        score_stats=bool(cfg.num_sliding_layers),
    )


def _read(cfg, vec) -> Dict[str, Any]:
    """The ``attn`` event of the step's folded vector: the layers of each
    kind, the window, each kind's rotation (:func:`rotation_of`; and
    ``rotated_layers``, the layers that rotate at all: the others see no
    position) and ``score_bound``: the bound of the largest ``|q
    k^T| * scale`` before the mask (:func:`score_bound`, which the exact
    maximum cannot pass) over the layers of each kind and over both, so
    that a rotation's factor on the full layers' scores shows."""
    full, sliding = (float(v) for v in vec)
    rotation = {
        kind: rotation_of(cfg, kind)
        for kind in (FULL_ATTENTION, SLIDING_ATTENTION)
    }
    return dict(
        full_layers=cfg.num_full_layers,
        sliding_layers=cfg.num_sliding_layers,
        full_rotation=rotation[FULL_ATTENTION],
        sliding_rotation=rotation[SLIDING_ATTENTION],
        rotated_layers=(
            cfg.num_full_layers * (rotation[FULL_ATTENTION] != "none")
            + cfg.num_sliding_layers * (rotation[SLIDING_ATTENTION] != "none")
        ),
        window=cfg.sliding_window, full_score_bound=full,
        sliding_score_bound=sliding,
        score_bound=float("nan") if full != full or sliding != sliding
        else max(full, sliding),
    )


def kernel_facts(cfg, seq_len: int) -> Dict[str, Any]:
    """What the step program's flash-attention kernels are:
    ``flash_backward``, the backward it holds (``fused``: one pass,
    ``split``: dq, then dk / dv, ``none``: no flash kernel), and
    ``flash_blocks``, how many causal blocks of each class one (batch,
    head) holds and the rows of a diagonal block's strips (0: the masked
    square).  Both are chosen at trace time from the shapes alone, so this
    asks the functions the dispatch asks, with the layer's own fields; the
    sequence is whole inside attention under every rule table."""
    from dlrover_tpu.ops import flash_attention as fa

    layer = from_config(cfg)
    if layer.attention_impl != "flash":
        return {"flash_backward": "none", "flash_blocks": None}
    if cfg.latent_attention:
        d = layer.qk_nope_head_dim + layer.qk_rope_head_dim
        d_v = layer.v_head_dim
    else:
        d = d_v = layer.head_dim
    sizes = (seq_len, seq_len, layer.flash_block_q, layer.flash_block_kv)
    backward = fa.backward_path(*sizes[:2], d, d_v, *sizes[2:], layer.dtype)
    classes = fa.block_classes(*sizes, causal=True)
    if not cfg.num_sliding_layers:
        return {
            "flash_backward": backward, "flash_blocks": classes._asdict(),
        }
    # A model with windowed layers: the counts of each kind, the steps its
    # forward's grid really makes a (batch, head) (``grid``), and
    # ``live_share``, the live steps among them.  The banded kernels say
    # three facts more, of theirs alone: ``lower_strip``, the rows of a
    # lower-edge block's strips (0: the masked square), ``tile_live_share``,
    # the band's live pairs among the pairs their tiles work, and
    # ``lockstep``, that a block's strips take their stages in turn.
    window = from_config(cfg, SLIDING_ATTENTION).window
    band = fa.block_classes(*sizes, causal=True, window=window)

    def facts(c, edge, grid):
        live = c.interior + edge
        return {
            "live": live, "interior": c.interior, "edge": edge,
            "dead": c.dead, "grid": grid, "strip": c.strip,
            "live_share": live / grid, "backward": backward,
        }

    return {
        "flash_backward": backward,
        "flash_blocks": {
            FULL_ATTENTION: facts(
                classes, classes.diagonal, fa.forward_grid_steps(*sizes)
            ),
            SLIDING_ATTENTION: dict(
                facts(
                    band, band.diagonal + band.lower + band.both,
                    fa.forward_grid_steps(*sizes, window),
                ),
                lower_strip=band.lower_strip,
                tile_live_share=fa.band_tile_live_share(*sizes, window),
                lockstep=True,
            ),
        },
    }


FAMILY = Family(
    event="attn",
    # [a full layer's score bound, a windowed layer's]: the largest
    stats={STATS_NAME: lambda stacked: stacked.max(axis=0)},
    has=lambda cfg: cfg.num_sliding_layers,
    read=_read,
    kernel_facts=kernel_facts,
)
