"""The gated-delta-rule mixer: a linear-attention layer with a recurrent
state in place of a KV cache (Gated DeltaNet, Yang et al.,
arXiv:2412.06464), as Olmo-Hybrid's ``linear_attention`` layers run it.

With ``n`` the mixer's input, ``H`` heads, key heads of ``dk`` and value
heads of ``dv``::

    q, k, v, z = W_q n, W_k n, W_v n, W_g n              (no bias)
    q, k, v <- SiLU(causal depthwise conv, ``taps`` taps, own taps a channel)
    per head:  q <- q / ||q|| * dk^-1/2,   k <- k / ||k||
    beta = sigmoid(W_b n)     (x 2 with ``allow_neg_eigval``: 0 < beta < 2)
    g    = -exp(A_log) * softplus(W_a n + dt_bias)       (float32, < 0)
    o    = gated delta rule over (q, k, v, g, beta)       (ops/gated_delta_rule)
    y    = RMSNorm_dv(o; [dv] scale shared by the heads) * SiLU(z)
    out  = W_o y

The four wide projections run as ONE ``[d, H, 2 dk + 2 dv]`` matmul
(param ``qkvg``; a head's row is ``[q | k | v | z]``), as the attention
layer's ``qkv`` does and for the same reason; the split is on the per-head
axis, which no strategy shards, so the heads shard as attention's do.  The
convolution is depthwise, so it runs on the ``[q | k | v]`` columns as
they lie in that row: :func:`causal_depthwise_conv` is the convolution,
the SiLU and the two L2 norms in one call, one Pallas pass forward and one
backward (``ops/short_conv.py``) where the tokens are whole lane tiles and
the widths whole row tiles, as at the published widths, and K shifted
multiply-adds with XLA's own norms anywhere else (:func:`conv_path` says
which; Mamba-2's mixer calls the same function with a bias and no norm).
``W_a`` and ``W_b`` are one ``[d, H, 2]`` matmul in float32
accumulation (param ``ab``).

Each forward ``sow``s ``linear_attn_stats`` = ``[mean alpha, mean beta,
largest |S| entry at a chunk boundary]`` (:func:`split_stats`) into
``"intermediates"``: a no-op unless the caller applies with that
collection mutable, as the train step does.

:class:`KimiDeltaAttention` is Kimi Linear's mixer (arXiv:2510.26692): the
same convolution and norms, then::

    beta = sigmoid(W_b n)     (x 2 with ``allow_neg_eigval``: 0 < beta < 2)
    g    = bound * sigmoid(exp(A_log_h) * (f + dt_bias))        [H, dk]
           (the SAFE gate, ``decay_bound`` = ``kda_lower_bound`` -5 < g < 0,
           as Ling-3.0-flash runs it), or
           -exp(A_log_h) * softplus(f + dt_bias)
           (the PUBLISHED gate, ``decay_bound`` 0: g < 0 with no lower
           bound, as Solar-Open2 runs it)
    f    = W_f n, W_f full rank [d, H, dk] (``gate_rank`` 0), or
           (n W_f_down) W_f_up through ``gate_rank`` columns
           (``kda_use_full_proj`` false); float32 accumulation
    o    = the rule with a decay PER CHANNEL over (q, k, v, g, beta)
           (ops/kda: S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T)
    y    = RMSNorm_dv(o; one [dv] scale) * sigmoid(W_g n)
           (W_g full rank, or its own low-rank pair)
    out  = W_o y

The gate decides the rule's form: under the safe gate a 16-token
sub-chunk's total decay stays inside float32 and ``ops/kda`` splits every
pair's decay around the sub-chunk's middle; the published gate has no
floor, so its layers ask for the form that is exact for any ``g <= 0``
(``exact``; ``kernel_facts`` says which a model's layers run).

Its scopes are ``linear_attn/qkv``, ``/conv``, ``/gates`` (the decay
projection, beta and the gate; the output gate's low-rank pair where it
has one), ``/kda``, ``/out_norm`` and ``/wo``; its sown vector has a fourth
entry, the smallest mean decay of a channel (``min_alpha``: a channel that
forgets everything reads near exp(bound)), and under the published gate a
fifth and a sixth: the most negative log decay of a token and channel
(``g_min``) and the share of (token, head, channel) triples below
``ops/kda.SPLIT_FLOOR`` = -88 / 16, where the split form would overflow
(``past_bound_share``).

The block names this module ``linear_attn``, so its ``named_scope``s reach
the compiled text as ``linear_attn/qkv``, ``/conv``, ``/gates``,
``/delta_rule``, ``/out_norm`` and (the output projection's own name)
``/wo``, forward and transposed ops alike, where the benchmark reads them.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from dlrover_tpu.models import layers
from dlrover_tpu.models.family import Family
from dlrover_tpu.ops import short_conv
from dlrover_tpu.ops.gated_delta_rule import gated_delta_rule
from dlrover_tpu.parallel import rules as lr
from dlrover_tpu.runtime.mesh import shard_local

F32 = jnp.float32
STATS_NAME = "linear_attn_stats"
L2_EPS = short_conv.L2_EPS


def split_stats(vec):
    """``(mean_alpha, mean_beta, state_absmax)`` of one stats vector."""
    return vec[0], vec[1], vec[2]


def fold_stats(stacked: jax.Array) -> jax.Array:
    """One vector out of the layers' (and microbatches') ``[n, 3]``: the
    means of the means, the largest of the largest; of a KDA layer's
    ``[n, 4]`` also the smallest of the smallest decays; of its ``[n, 6]``
    under a gate without a bound the most negative log decay and the mean
    of the shares past the split form's floor."""
    folded = [stacked[:, :2].mean(axis=0), stacked[:, 2:3].max(axis=0)]
    if stacked.shape[1] > 3:
        folded.append(stacked[:, 3:5].min(axis=0))
    if stacked.shape[1] > 5:
        folded.append(stacked[:, 5:].mean(axis=0))
    return jnp.concatenate(folded)


def read_stats(vec, first: str, second: str, largest: str = "state_absmax"):
    """A fetched vector of this layout by name, for a family's event: the
    two means as ``first`` and ``second``, the largest entry as ``largest``
    and, of a per-channel rule's ``[4]``, ``min_alpha``: the smallest mean
    decay of a channel; of its ``[6]``, ``g_min`` and ``past_bound_share``
    (the module's text)."""
    vec = np.asarray(vec, np.float64)
    mean_first, mean_second, absmax = split_stats(vec)
    read = {
        first: float(mean_first), second: float(mean_second),
        largest: float(absmax),
    }
    if vec.size > 3:
        read["min_alpha"] = float(vec[3])
    if vec.size > 5:
        read["g_min"] = float(vec[4])
        read["past_bound_share"] = float(vec[5])
    return read


def _a_log_init(key, shape, dtype):
    # Gated DeltaNet: A ~ U(0, 16), kept as its logarithm
    return jnp.log(
        jax.random.uniform(key, shape, F32, 1e-4, 16.0)
    ).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    # Gated DeltaNet (and Mamba-2): dt log-uniform in [1e-3, 1e-1], kept
    # as the inverse of softplus so that softplus(dt_bias) = dt
    dt = jnp.exp(
        jax.random.uniform(key, shape, F32)
        * (math.log(0.1) - math.log(0.001)) + math.log(0.001)
    )
    dt = jnp.maximum(dt, 1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _dt_bias_spread_init(std: float):
    """``dt_bias`` normal about the middle of the default's range (where
    ``softplus(dt_bias)`` is 1e-2) with ``std`` between CHANNELS: each
    channel's decay is its own and near constant over the tokens, from
    channels that keep a write for thousands of tokens to channels that
    forget it within one (a gate without a bound, ``g`` well below
    ``ops/kda.SPLIT_FLOOR``)."""
    def init(key, shape, dtype):
        return (
            math.log(math.expm1(1e-2))
            + std * jax.random.normal(key, shape, F32)
        ).astype(dtype)

    return init


def conv_init(key, shape, dtype):
    # torch's Conv1d default for a depthwise filter: U(+-1/sqrt(taps))
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, F32, -bound, bound).astype(dtype)


def _shifted_sum(x: jax.Array, taps: jax.Array, before: bool) -> jax.Array:
    """``sum_j taps[j] * x[t - (K - 1) + j]`` (``before``: a tap reads
    tokens behind t, zeros before the start) or ``sum_j taps[j] *
    x[t + (K - 1) - j]`` (tokens ahead of t, zeros past the end)."""
    k, s = taps.shape[0], x.shape[1]
    rest = [(0, 0)] * (x.ndim - 2)
    pad = jnp.pad(x, [(0, 0), (k - 1, 0) if before else (0, k - 1)] + rest)
    return sum(
        pad[:, (j if before else k - 1 - j):][:, :s] * taps[j]
        for j in range(k)
    )


@jax.custom_vjp
def _conv_xla(x: jax.Array, taps: jax.Array) -> jax.Array:
    """``y[t] = sum_j taps[j] * x[t - (K - 1) + j]`` per channel, zeros
    before the sequence starts.  ``x`` [B, S, ...], ``taps`` [K, ...]:
    K shifted multiply-adds, no convolution primitive.  The VJP is
    written out (the same K shifts the other way, and K dot products for
    the taps): autodiff's transposed slices each landed as a padded copy
    of the cotangent before they were summed."""
    return _shifted_sum(x, taps, before=True)


def _conv_fwd(x, taps):
    return _shifted_sum(x, taps, before=True), (x, taps)


def _conv_bwd(res, dy):
    x, taps = res
    k, s = taps.shape[0], x.shape[1]
    dx = _shifted_sum(dy, taps, before=False)
    pad = jnp.pad(x, [(0, 0), (k - 1, 0)] + [(0, 0)] * (x.ndim - 2))
    d_taps = jnp.stack([
        jnp.sum(
            pad[:, j: j + s].astype(F32) * dy.astype(F32), axis=(0, 1)
        )
        for j in range(k)
    ])
    return dx.astype(x.dtype), d_taps.astype(taps.dtype)


_conv_xla.defvjp(_conv_fwd, _conv_bwd)


def l2_normalise(x: jax.Array) -> jax.Array:
    x32 = x.astype(F32)
    return x32 * jax.lax.rsqrt(
        jnp.sum(x32 * x32, axis=-1, keepdims=True) + L2_EPS
    )


def _l2_scaled(y: jax.Array, scale: Optional[float]) -> jax.Array:
    """``scale`` times the L2-normalised ``y`` in its dtype; ``y`` itself
    for no scale."""
    if scale is None:
        return y
    normed = l2_normalise(y)
    return (normed if scale == 1.0 else normed * scale).astype(y.dtype)


def short_conv_path(
    x_shape, taps_shape, offset=0, splits=None, l2_scales=None,
) -> str:
    """``kernel`` where :func:`causal_depthwise_conv` runs ``ops/short_conv``
    on such an input, ``xla`` where it runs the written-out form."""
    tiled = short_conv.plan(x_shape, taps_shape, offset, splits, l2_scales)
    return "xla" if tiled is None else "kernel"


def causal_depthwise_conv(
    x: jax.Array, taps: jax.Array, bias: Optional[jax.Array] = None, *,
    offset: int = 0, splits: Optional[Tuple[int, ...]] = None,
    l2_scales: Optional[Tuple[Optional[float], ...]] = None,
):
    """``SiLU(conv(x[..., offset: offset + C]) + bias)`` with ``conv`` the
    causal depthwise convolution ``sum_j taps[j] * x[t - (K - 1) + j]`` per
    channel (zeros before the sequence starts) and ``C = taps.shape[-1]``.
    ``x`` [B, S, ..., W], ``taps`` [K, ..., C], ``bias`` [..., C] or None;
    with ``splits`` the result comes as the tuple of those widths of its
    last axis, the one whose ``l2_scales`` is a number L2-normalised over
    its width and multiplied by it.

    Chosen from the input's shape: tokens whole lane tiles and channels and
    offset whole row tiles (``ops/short_conv.plan``) run as one Pallas pass
    forward and one backward, which read ``x`` in place; anything else as K
    shifted multiply-adds in XLA, the form the kernels are held to."""
    channels = taps.shape[-1]
    path = short_conv_path(x.shape, taps.shape, offset, splits, l2_scales)
    if path == "xla":
        y = _conv_xla(x[..., offset: offset + channels], taps)
        if bias is not None:
            y = y + bias
        y = nn.silu(y)
        if not splits:
            return y
        edges = [sum(splits[:n]) for n in range(len(splits) + 1)]
        return tuple(
            _l2_scaled(y[..., lo: hi], scale)
            for lo, hi, scale in zip(
                edges, edges[1:], l2_scales or (None,) * len(splits)
            )
        )
    # a Mosaic kernel sees its device's block: batch rows may stay sharded,
    # the tokens and the channels are whole
    rows = nn.logical_to_mesh_axes((lr.BATCH,) + (None,) * (x.ndim - 1))
    args, specs = (x, taps), (rows, P())
    if bias is not None:
        args, specs = args + (bias,), specs + (P(),)

    def local(x, taps, bias=None):
        return short_conv.short_conv(x, taps, bias, offset, splits, l2_scales)

    return shard_local(
        local, in_specs=specs,
        out_specs=(rows,) * len(splits) if splits else rows,
    )(*args)


def _delta_rule_local(q, k, v, g, beta, *, chunk, rule=gated_delta_rule):
    """The rule on each device's own batch rows and heads
    (:func:`shard_local`, as attention's ``_flash_local``): a head's state
    is its own and the sequence is whole, so nothing crosses devices but
    the largest ``|S|``, which each device reports for itself.  ``rule`` is
    the scalar rule (``g`` one decay a head) or ``ops/kda.kda`` (``g`` laid
    out as the keys are)."""
    qkv_spec = nn.logical_to_mesh_axes((lr.BATCH, None, lr.ACT_HEADS, lr.KV))
    gate_spec = nn.logical_to_mesh_axes((lr.BATCH, None, lr.ACT_HEADS))

    def local(q, k, v, g, beta):
        o, state_absmax = rule(q, k, v, g, beta, chunk=chunk)
        return o, state_absmax[None, None]

    o, state_absmax = shard_local(
        local,
        in_specs=(qkv_spec,) * 3
        + (qkv_spec if g.ndim == q.ndim else gate_spec, gate_spec),
        out_specs=(
            qkv_spec, nn.logical_to_mesh_axes((lr.BATCH, lr.ACT_HEADS)),
        ),
    )(q, k, v, g, beta)
    return o, state_absmax.max()


def conv_path(
    seq: int, num_heads: int, key_dim: int, value_dim: int, taps: int,
    gate_in_row: bool = True,
) -> str:
    """How :class:`GatedDeltaNet` of these widths runs its convolution on
    ``seq`` tokens: what ``causal_depthwise_conv`` answers for its call
    (``gate_in_row`` False: :class:`KimiDeltaAttention`, whose projection's
    row is ``[q | k | v]`` alone)."""
    return short_conv_path(
        (1, seq, num_heads, 2 * key_dim + (1 + gate_in_row) * value_dim),
        (taps, num_heads, 2 * key_dim + value_dim),
        0, (key_dim, key_dim, value_dim), (key_dim ** -0.5, 1.0, None),
    )


class GatedDeltaNet(nn.Module):
    num_heads: int
    key_dim: int
    value_dim: int
    conv_taps: int = 4
    allow_neg_eigval: bool = False
    norm_eps: float = 1e-5
    chunk: int = 128
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        h, dk, dv = self.num_heads, self.key_dim, self.value_dim
        features = x.shape[-1]
        proj_init = nn.initializers.normal(stddev=0.02)
        with jax.named_scope("qkv"):
            qkvg = layers.DenseGeneral(
                (h, 2 * dk + 2 * dv),
                kernel_axes=(lr.EMBED, lr.HEADS, lr.KV),
                dtype=self.dtype, param_dtype=self.param_dtype,
                kernel_init=proj_init, name="qkvg",
            )(x)
        z = qkvg[..., 2 * dk + dv:]
        with jax.named_scope("conv"):
            taps = self.param(
                "conv_kernel",
                nn.with_logical_partitioning(
                    conv_init, (None, lr.HEADS, lr.KV)
                ),
                (self.conv_taps, h, 2 * dk + dv), self.param_dtype,
            )
            # q and k leave L2-normalised, q times dk ** -0.5 besides
            q, k, v = causal_depthwise_conv(
                qkvg, taps.astype(self.dtype), splits=(dk, dk, dv),
                l2_scales=(dk ** -0.5, 1.0, None),
            )
        with jax.named_scope("gates"):
            ab_kernel = self.param(
                "ab_kernel",
                nn.with_logical_partitioning(
                    proj_init, (lr.EMBED, lr.HEADS, None)
                ),
                (features, h, 2), self.param_dtype,
            )
            # 2 H numbers that set every decay: float32 whatever the
            # parameters' dtype, as Gated DeltaNet keeps them
            a_log = self.param(
                "A_log",
                nn.with_logical_partitioning(_a_log_init, (lr.HEADS,)),
                (h,), F32,
            )
            dt_bias = self.param(
                "dt_bias",
                nn.with_logical_partitioning(_dt_bias_init, (lr.HEADS,)),
                (h,), F32,
            )
            ab = jnp.einsum(
                "bsd,dhc->bshc", x.astype(self.dtype),
                ab_kernel.astype(self.dtype), preferred_element_type=F32,
            )
            g = -jnp.exp(a_log.astype(F32)) * jax.nn.softplus(
                ab[..., 0] + dt_bias.astype(F32)
            )
            beta = jax.nn.sigmoid(ab[..., 1])
            if self.allow_neg_eigval:
                beta = 2.0 * beta
        spec = (lr.BATCH, None, lr.ACT_HEADS, lr.KV)
        q, k, v = (nn.with_logical_constraint(a, spec) for a in (q, k, v))
        with jax.named_scope("delta_rule"):
            o, state_absmax = _delta_rule_local(
                q, k, v, g, beta, chunk=self.chunk
            )
        # The one activation of the mixer the layer's remat keeps
        # (ops/remat_policy.py): the backward runs the rule's forward
        # kernel again for its chunk-start states, not for the outputs.
        o = jax.ad_checkpoint.checkpoint_name(o, "delta_out")
        o = nn.with_logical_constraint(o, spec)
        self.sow(
            "intermediates", STATS_NAME,
            jax.lax.stop_gradient(jnp.stack([
                jnp.exp(g).mean(), beta.mean(), state_absmax,
            ])),
        )
        with jax.named_scope("out_norm"):
            scale = self.param(
                "out_norm_scale",
                nn.with_logical_partitioning(
                    nn.initializers.ones_init(), (lr.NORM,)
                ),
                (dv,), self.param_dtype,
            )
            o32 = o.astype(F32)
            y = o32 * jax.lax.rsqrt(
                jnp.mean(o32 * o32, axis=-1, keepdims=True) + self.norm_eps
            )
            y = (y * scale.astype(F32) * nn.silu(z.astype(F32))).astype(
                self.dtype
            )
        return layers.DenseGeneral(
            features, axis=(-2, -1),
            kernel_axes=(lr.HEADS, lr.KV, lr.EMBED),
            dtype=self.dtype, param_dtype=self.param_dtype,
            kernel_init=proj_init, name="wo",
        )(y)


class KimiDeltaAttention(nn.Module):
    """Kimi Delta Attention (the module's text): parameters ``qkv``
    ``[d, H, 2 dk + dv]``, ``conv_kernel``, the decay projection
    (``f_kernel`` ``[d, H, dk]`` at full rank, ``f_down`` ``[d, r]`` and
    ``f_up`` ``[r, H, dk]`` at ``gate_rank`` r), ``b_kernel`` ``[d, H]``,
    ``A_log`` ``[H]``, ``dt_bias`` ``[H, dk]``, the output gate (``g_proj``
    ``[d, H, dv]``, or ``g_down`` ``[d, r]`` and ``g_up`` ``[r, H, dv]``),
    ``out_norm_scale`` ``[dv]``, ``wo``."""

    num_heads: int
    key_dim: int
    value_dim: int
    conv_taps: int = 4
    decay_bound: float = -5.0      # 0: no bound, the published softplus gate
    gate_rank: int = 0             # 0: the two gate projections full rank
    allow_neg_eigval: bool = False
    # the spread of the decay's pre-activation between channels that
    # ``dt_bias`` is seeded with; 0: the default initialiser
    decay_init_std: float = 0.0
    norm_eps: float = 1e-5
    chunk: int = 128               # ops/kda.py CHUNK
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def exact(self) -> bool:
        """Whether the rule runs in the form that is exact for any ``g <=
        0`` (ops/kda.py): a gate without a bound needs it."""
        return not self.decay_bound

    def _low_rank(self, x, name, width, proj_init):
        """``(x W_down) W_up`` through ``gate_rank`` columns, float32
        accumulation: ``[B, S, H, width]`` float32."""
        h = self.num_heads
        down = self.param(
            f"{name}_down",
            nn.with_logical_partitioning(proj_init, (lr.EMBED, None)),
            (x.shape[-1], self.gate_rank), self.param_dtype,
        )
        up = self.param(
            f"{name}_up",
            nn.with_logical_partitioning(proj_init, (None, lr.HEADS, lr.KV)),
            (self.gate_rank, h, width), self.param_dtype,
        )
        low = jnp.einsum("bsd,dr->bsr", x, down.astype(self.dtype))
        return jnp.einsum(
            "bsr,rhk->bshk", low, up.astype(self.dtype),
            preferred_element_type=F32,
        )

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        h, dk, dv = self.num_heads, self.key_dim, self.value_dim
        features = x.shape[-1]
        proj_init = nn.initializers.normal(stddev=0.02)
        with jax.named_scope("qkv"):
            qkv = layers.DenseGeneral(
                (h, 2 * dk + dv),
                kernel_axes=(lr.EMBED, lr.HEADS, lr.KV),
                dtype=self.dtype, param_dtype=self.param_dtype,
                kernel_init=proj_init, name="qkv",
            )(x)
        with jax.named_scope("conv"):
            taps = self.param(
                "conv_kernel",
                nn.with_logical_partitioning(
                    conv_init, (None, lr.HEADS, lr.KV)
                ),
                (self.conv_taps, h, 2 * dk + dv), self.param_dtype,
            )
            q, k, v = causal_depthwise_conv(
                qkv, taps.astype(self.dtype), splits=(dk, dk, dv),
                l2_scales=(dk ** -0.5, 1.0, None),
            )
        with jax.named_scope("gates"):
            xc = x.astype(self.dtype)
            if self.gate_rank:
                f = self._low_rank(xc, "f", dk, proj_init)
                out_gate = self._low_rank(xc, "g", dv, proj_init)
            else:
                f_kernel = self.param(
                    "f_kernel",
                    nn.with_logical_partitioning(
                        proj_init, (lr.EMBED, lr.HEADS, lr.KV)
                    ),
                    (features, h, dk), self.param_dtype,
                )
                f = jnp.einsum(
                    "bsd,dhk->bshk", xc, f_kernel.astype(self.dtype),
                    preferred_element_type=F32,
                )
            b_kernel = self.param(
                "b_kernel",
                nn.with_logical_partitioning(proj_init, (lr.EMBED, lr.HEADS)),
                (features, h), self.param_dtype,
            )
            a_log = self.param(
                "A_log",
                nn.with_logical_partitioning(_a_log_init, (lr.HEADS,)),
                (h,), F32,
            )
            dt_bias = self.param(
                "dt_bias",
                nn.with_logical_partitioning(
                    _dt_bias_spread_init(self.decay_init_std)
                    if self.decay_init_std else _dt_bias_init,
                    (lr.HEADS, lr.KV),
                ),
                (h, dk), F32,
            )
            a = jnp.exp(a_log.astype(F32))[:, None]
            if self.decay_bound:
                # the safe gate: bounded below, so a sub-chunk's total
                # decay stays inside float32 (ops/kda.py)
                g = self.decay_bound * jax.nn.sigmoid(
                    a * (f + dt_bias.astype(F32))
                )
            else:
                # the published gate: no floor, the rule's exact form
                g = -a * jax.nn.softplus(f + dt_bias.astype(F32))
            beta = jax.nn.sigmoid(jnp.einsum(
                "bsd,dh->bsh", xc, b_kernel.astype(self.dtype),
                preferred_element_type=F32,
            ))
            if self.allow_neg_eigval:
                beta = 2.0 * beta
        spec = (lr.BATCH, None, lr.ACT_HEADS, lr.KV)
        q, k, v, g = (
            nn.with_logical_constraint(a, spec) for a in (q, k, v, g)
        )
        with jax.named_scope("kda"):
            # imported where a model has such a layer, and by no other
            from dlrover_tpu.ops import kda as kda_ops

            rule = kda_ops.kda
            if self.exact:
                rule = functools.partial(rule, exact=True)
            o, state_absmax = _delta_rule_local(
                q, k, v, g, beta, chunk=self.chunk, rule=rule
            )
        # kept by ``flash_only``, and beside it the kernel's chunk-start
        # states (``kda_states``, ops/kda.py; ops/remat_policy.py says why)
        o = jax.ad_checkpoint.checkpoint_name(o, "kda_out")
        o = nn.with_logical_constraint(o, spec)
        alpha = jnp.exp(g)
        stats = [
            alpha.mean(), beta.mean(), state_absmax,
            alpha.mean(axis=(0, 1)).min(),
        ]
        if self.exact:
            stats += [
                g.min(), (g < kda_ops.SPLIT_FLOOR).astype(F32).mean(),
            ]
        self.sow(
            "intermediates", STATS_NAME,
            jax.lax.stop_gradient(jnp.stack(stats)),
        )
        with jax.named_scope("out_norm"):
            scale = self.param(
                "out_norm_scale",
                nn.with_logical_partitioning(
                    nn.initializers.ones_init(), (lr.NORM,)
                ),
                (dv,), self.param_dtype,
            )
            if not self.gate_rank:
                out_gate = layers.DenseGeneral(
                    (h, dv), kernel_axes=(lr.EMBED, lr.HEADS, lr.KV),
                    dtype=self.dtype, param_dtype=self.param_dtype,
                    kernel_init=proj_init, name="g_proj",
                )(x)
            o32 = o.astype(F32)
            y = o32 * jax.lax.rsqrt(
                jnp.mean(o32 * o32, axis=-1, keepdims=True) + self.norm_eps
            )
            y = (
                y * scale.astype(F32) * jax.nn.sigmoid(out_gate.astype(F32))
            ).astype(self.dtype)
        return layers.DenseGeneral(
            features, axis=(-2, -1),
            kernel_axes=(lr.HEADS, lr.KV, lr.EMBED),
            dtype=self.dtype, param_dtype=self.param_dtype,
            kernel_init=proj_init, name="wo",
        )(y)


def from_config(cfg, **kwargs):
    """The config's ``linear_attention`` mixer, the scalar rule's or the
    per-channel one's: the one place that reads the config's fields into
    the layer's, for the block that runs it and for :func:`kernel_facts`."""
    widths = dict(
        num_heads=cfg.resolved_linear_heads,
        key_dim=cfg.linear_key_head_dim,
        value_dim=cfg.linear_value_head_dim,
        conv_taps=cfg.linear_conv_kernel,
        norm_eps=cfg.norm_eps,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        **kwargs,
    )
    if cfg.linear_rule == "kda":
        return KimiDeltaAttention(
            decay_bound=cfg.linear_decay_bound,
            gate_rank=cfg.linear_gate_rank,
            allow_neg_eigval=cfg.linear_allow_neg_eigval,
            decay_init_std=cfg.linear_decay_init_std, **widths
        )
    return GatedDeltaNet(
        allow_neg_eigval=cfg.linear_allow_neg_eigval, **widths
    )


def _read(cfg, vec) -> Dict[str, Any]:
    """The ``linear_attn`` event of the step's folded vector."""
    return dict(
        layers=cfg.num_linear_layers, chunk=from_config(cfg).chunk,
        rule=cfg.linear_rule, **read_stats(vec, "mean_alpha", "mean_beta"),
    )


def kernel_facts(cfg, seq_len: int) -> Dict[str, str]:
    """``short_conv``: how the mixers' convolution runs on ``seq_len``
    tokens, ``kernel`` (``ops/short_conv.py``) / ``xla`` (the written-out
    form), chosen at trace time from the shapes alone (:func:`conv_path`).
    ``kda``: how the per-channel rule runs, ``kernel`` / ``xla`` under a
    bounded gate, ``kernel_exact`` / ``xla_exact`` in the form for any ``g
    <= 0`` (``ops/kda.py`` ``plan``, which the rule asks).  Each ``none``
    for a model without such a layer."""
    if not cfg.num_linear_layers:
        return {"short_conv": "none", "kda": "none"}
    from dlrover_tpu.ops import kda

    mixer = from_config(cfg)
    per_channel = isinstance(mixer, KimiDeltaAttention)
    return {
        "short_conv": conv_path(
            seq_len, mixer.num_heads, mixer.key_dim, mixer.value_dim,
            mixer.conv_taps, gate_in_row=not per_channel,
        ),
        "kda": kda.plan(mixer.key_dim, mixer.value_dim, mixer.exact)
        if per_channel else "none",
    }


FAMILY = Family(
    event="linear_attn",
    stats={STATS_NAME: fold_stats},
    has=lambda cfg: cfg.num_linear_layers,
    read=_read,
    kernel_facts=kernel_facts,
    absmax="state_absmax",
)
