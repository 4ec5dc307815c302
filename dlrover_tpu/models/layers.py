"""Shared NN building blocks, annotated with logical sharding axes.

TPU-native counterparts of the reference's parallel layer zoo
(ref ``atorch/atorch/modules/distributed_modules/layers.py:239-763``:
``RowParallelLinear``, ``ColumnParallelLinear``, ``VocabParallelEmbedding``).
Here a single :class:`DenseGeneral` plays all of those roles — the row/column/
vocab split is decided by the logical axis names on its kernel, not by the
module class, so the same model code runs under any strategy.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Iterable, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from dlrover_tpu.parallel import rules as lax_rules
from dlrover_tpu.runtime.mesh import shard_local

Dtype = Any
Shape = Tuple[int, ...]
Initializer = Callable[..., Any]

default_kernel_init = nn.initializers.lecun_normal()
default_embed_init = nn.initializers.normal(stddev=0.02)


def _normalize_axes(axes: Union[int, Iterable[int]], ndim: int) -> Tuple[int, ...]:
    if isinstance(axes, int):
        axes = (axes,)
    return tuple(ax if ax >= 0 else ndim + ax for ax in axes)


class DenseGeneral(nn.Module):
    """Linear layer over arbitrary contraction axes with named kernel axes.

    ``kernel_axes`` gives the logical name of every kernel dim; the rule table
    (``dlrover_tpu.parallel.rules``) decides which mesh axis each maps to.
    E.g. a ``('embed', 'mlp')`` kernel under TP rules is a column-parallel
    linear; ``('mlp', 'embed')`` is row-parallel (XLA inserts the psum).
    """

    features: Union[int, Tuple[int, ...]]
    axis: Union[int, Tuple[int, ...]] = -1
    kernel_axes: Tuple[str, ...] = ()
    use_bias: bool = False
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    kernel_init: Initializer = default_kernel_init

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        features = (
            (self.features,) if isinstance(self.features, int) else tuple(self.features)
        )
        axis = _normalize_axes(self.axis, x.ndim)
        in_shape = tuple(x.shape[a] for a in axis)
        kernel_shape = in_shape + features
        assert len(self.kernel_axes) == len(kernel_shape), (
            f"kernel_axes {self.kernel_axes} must name every dim of "
            f"{kernel_shape}"
        )
        kernel = self.param(
            "kernel",
            nn.with_logical_partitioning(self.kernel_init, self.kernel_axes),
            kernel_shape,
            self.param_dtype,
        )
        kernel = kernel.astype(self.dtype)
        x = x.astype(self.dtype)
        out = jax.lax.dot_general(
            x, kernel, ((axis, tuple(range(len(axis)))), ((), ()))
        )
        if self.use_bias:
            bias = self.param(
                "bias",
                nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), self.kernel_axes[len(axis):]
                ),
                features,
                self.param_dtype,
            )
            out = out + bias.astype(self.dtype)
        return out


class Embed(nn.Module):
    """Token embedding with vocab-parallel-capable table.

    Counterpart of ``VocabParallelEmbedding`` (ref ``layers.py:549``); the
    table is named ``('vocab', 'embed')`` so the vocab split and the psum over
    the tensor axis come from the rule table, not the code.
    """

    num_embeddings: int
    features: int
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    embedding_init: Initializer = default_embed_init

    @nn.compact
    def __call__(self, ids: jax.Array) -> jax.Array:
        embedding = self.param(
            "embedding",
            nn.with_logical_partitioning(
                self.embedding_init, (lax_rules.VOCAB, lax_rules.EMBED)
            ),
            (self.num_embeddings, self.features),
            self.param_dtype,
        )
        # Gather from a table whose embed dim is force-unsharded: under FSDP
        # the storage stays sharded but the lookup runs on an explicitly
        # all-gathered copy (standard FSDP compute semantics).  Without this
        # the partitioner cannot reconcile an fsdp-sharded table dim with an
        # fsdp-sharded batch dim in the gather output and falls back to
        # "involuntary full rematerialization" (replicate + repartition).
        # The vocab split (tensor) stays on the table: XLA lowers that to a
        # masked local gather + psum.
        table = nn.with_logical_constraint(
            embedding.astype(self.dtype),
            (lax_rules.VOCAB, lax_rules.GATHERED),
        )
        return table[ids]

    def attend(self, x: jax.Array) -> jax.Array:
        """Project hidden states onto the (tied) embedding table -> logits."""
        embedding = self.get_variable("params", "embedding")
        if isinstance(embedding, nn.meta.AxisMetadata):
            embedding = embedding.unbox()
        return jnp.dot(x.astype(self.dtype), embedding.astype(self.dtype).T)


def _norm_local(fn, x: jax.Array, *params: jax.Array) -> jax.Array:
    """A fused-backward norm on each device's own rows (``shard_local``):
    ``x`` stays sharded over its batch and sequence dims, the feature dim
    and the scale/bias are whole (the row statistics need it), and
    shard_map sums the per-device dscale/dbias partials."""
    rows = (lax_rules.BATCH, lax_rules.ACT_SEQ)[: x.ndim - 1]
    spec = nn.logical_to_mesh_axes(rows + (None,) * (x.ndim - len(rows)))
    return shard_local(
        fn, in_specs=(spec,) + (PartitionSpec(),) * len(params),
        out_specs=spec,
    )(x, *params)


class RMSNorm(nn.Module):
    """Root-mean-square norm (Llama-style), fp32 accumulation.

    ``fused_backward``: one-pass Pallas backward (ops/fused_norm.py) —
    same flag semantics as :class:`LayerNorm`.
    """

    epsilon: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    fused_backward: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        orig_dtype = x.dtype
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(), (lax_rules.NORM,)),
            (x.shape[-1],),
            self.param_dtype,
        )
        if self.fused_backward:
            from dlrover_tpu.ops.fused_norm import fused_rmsnorm

            return _norm_local(
                lambda x, scale: fused_rmsnorm(x, scale, self.epsilon),
                x, scale,
            )
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.epsilon)
        return (y * scale.astype(jnp.float32)).astype(orig_dtype)


class LayerNorm(nn.Module):
    """Standard layernorm (GPT-2 style), fp32 accumulation.

    ``fused_backward``: route through ops/fused_norm.py's custom_vjp so
    the backward is a single Pallas pass over (x, dy) instead of XLA's
    multi-fusion re-reads (PROFILE.md r4's 6.4 ms/layer LN-bwd sink).
    Off by default until an on-chip trace prices it (not measured).
    """

    epsilon: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    use_bias: bool = True
    fused_backward: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        orig_dtype = x.dtype
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(), (lax_rules.NORM,)),
            (x.shape[-1],),
            self.param_dtype,
        )
        bias = None
        if self.use_bias:
            bias = self.param(
                "bias",
                nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), (lax_rules.NORM,)
                ),
                (x.shape[-1],),
                self.param_dtype,
            )
        if self.fused_backward:
            from dlrover_tpu.ops.fused_norm import fused_layernorm

            return _norm_local(
                lambda x, scale, bias=None: fused_layernorm(
                    x, scale, bias, self.epsilon
                ),
                x, *((scale,) if bias is None else (scale, bias)),
            )
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.epsilon)
        y = y * scale.astype(jnp.float32)
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        return y.astype(orig_dtype)


def make_norm(kind: str, dtype: Dtype, param_dtype: Dtype, name: str,
              fused_backward: bool = False,
              epsilon: float = 1e-5, use_bias: bool = True) -> nn.Module:
    """``use_bias`` is a LayerNorm's (GPT-2's has one, the ``cohere2``
    family's none); an RMSNorm has a scale alone."""
    if kind == "rmsnorm":
        return RMSNorm(dtype=dtype, param_dtype=param_dtype, name=name,
                       fused_backward=fused_backward, epsilon=epsilon)
    if kind == "layernorm":
        return LayerNorm(dtype=dtype, param_dtype=param_dtype, name=name,
                         fused_backward=fused_backward, epsilon=epsilon,
                         use_bias=use_bias)
    raise ValueError(f"unknown norm kind {kind!r}")


def rope_frequencies(half: int, rope_theta: float) -> jax.Array:
    """Plain RoPE's inverse frequencies ``theta ** (-i / half)``, ``[half]``."""
    return 1.0 / (
        rope_theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )


def yarn_range(
    head_dim: int, rope_theta: float, original_len: int, beta_fast: float,
    beta_slow: float,
) -> Tuple[int, int]:
    """YaRN's ``(low, high)``: the columns that make ``beta_fast`` and
    ``beta_slow`` turns over the original length, rounded out and kept
    inside the head.  Columns up to ``low`` keep their frequency, those
    from ``high`` on are interpolated, a linear ramp between."""
    def column(beta):
        return head_dim * math.log(
            original_len / (2 * math.pi * beta)
        ) / (2 * math.log(rope_theta))

    low = max(math.floor(column(beta_fast)), 0)
    high = min(math.ceil(column(beta_slow)), head_dim - 1)
    return low, high


@dataclasses.dataclass(frozen=True)
class Rotation:
    """A layer kind's rotary embedding: plain RoPE at ``theta``, or under
    ``scaling`` ``"yarn"`` (arXiv:2309.00071) the frequencies interpolated
    by ``factor`` past the columns that turn often enough over
    ``original_len`` positions, and cos and sin both times
    ``attention_factor`` (so that a score carries its square)."""

    theta: float = 10000.0
    scaling: str = ""
    factor: float = 0.0
    original_len: int = 0
    beta_fast: float = 0.0
    beta_slow: float = 0.0
    attention_factor: float = 0.0

    def __post_init__(self):
        if self.scaling not in ("", "yarn"):
            raise ValueError(
                f"rope scaling must be '' or 'yarn', got {self.scaling!r}"
            )
        numbers = (
            self.factor, self.original_len, self.beta_fast, self.beta_slow,
            self.attention_factor,
        )
        if self.scaling and not all(n > 0 for n in numbers):
            raise ValueError(
                "yarn needs its five numbers (factor, original length, "
                f"beta_fast, beta_slow, attention factor), got {numbers}"
            )

    def table(self, head_dim: int) -> Tuple[jax.Array, float]:
        """``(inverse frequencies [head_dim / 2], factor on cos and sin)``
        for :func:`rotary_embedding`."""
        half = head_dim // 2
        inv = rope_frequencies(half, self.theta)
        if not self.scaling:
            return inv, 1.0
        low, high = yarn_range(
            head_dim, self.theta, self.original_len, self.beta_fast,
            self.beta_slow,
        )
        ramp = jnp.clip(
            (jnp.arange(half, dtype=jnp.float32) - low)
            / max(high - low, 1e-3), 0.0, 1.0,
        )
        return (
            inv * (1.0 - ramp) + (inv / self.factor) * ramp,
            self.attention_factor,
        )


def rotary_embedding(
    q: jax.Array,
    k: jax.Array,
    positions: jax.Array,
    inv_freq: jax.Array,
    factor: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """Apply rotary position embeddings to q/k of shape [B, S, H, D] at the
    inverse frequencies ``inv_freq`` ``[D / 2]`` (:func:`rope_frequencies`,
    :meth:`Rotation.table`), cos and sin times ``factor``."""
    half = q.shape[-1] // 2
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor

    def rotate(x):
        x32 = x.astype(jnp.float32)
        x1, x2 = x32[..., :half], x32[..., half:]
        return jnp.concatenate(
            (x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1
        ).astype(x.dtype)

    return rotate(q), rotate(k)
