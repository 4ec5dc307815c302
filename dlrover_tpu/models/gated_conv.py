"""The gated short convolution: the LFM2 family's ``conv`` mixer, a layer
with no recurrent state, no softmax and no position.

With ``n`` the mixer's input ``[B, S, d]`` and ``K`` taps (LFM2:
``conv_L_cache`` 3, ``conv_bias`` false)::

    [B | C | z] = n W_in                 (W_in [d, 3d], no bias; three d-wide
                                          column ranges in THIS order)
    u[t] = sum_j w[j] (B * z)[t - (K - 1) + j]      per channel, causal,
                                          zeros before t = 0; w [K, d]
    out  = (C * u) W_out                 (W_out [d, d], no bias)

No activation anywhere: the mixer is a product of three linear maps of
``n``, two of them at token ``t`` and one over the ``K`` tokens up to it.

The core ``C * conv(B * z)`` reads the three column ranges where they lie
in the projection's output and is one custom-VJP function of the whole
``[B, S, 3d]`` array (:func:`gated_conv`): products, taps and sums in
float32, ONE rounding at the write; the backward rebuilds ``u`` from the
projection's output rather than keeping it and hands back the three
cotangents (``dB = z dBz``, ``dC = dy u``, ``dz = B dBz`` with ``dBz`` the
taps run the other way over ``dy C``) as one ``[B, S, 3d]`` array, so
nothing follows as a padded copy.  Two forms of it: one Pallas pass forward
and one backward (``ops/short_conv.py``'s gated form) where the tokens are
whole lane tiles and ``d`` whole row tiles, as at the published widths, and
the written-out XLA form (:func:`gated_conv_xla`: slices, two products, K
shifted multiply-adds), which is the form the kernels are held to and the
path of every other shape (:func:`core_path` says which; PERF.md, PR 52,
has both forms' readings on the chip).

Each forward ``sow``s ``conv_stats`` = ``[mean |B|, mean |C|, largest
|C * u|]`` (``linear_attention.split_stats``'s layout: two means and a
largest) into ``"intermediates"``: a no-op unless the caller applies with
that collection mutable, as the train step does.  ``C * u`` is cubic in the
stream's norm-ed rows, so its largest entry is where a scale that drifts
over the layers shows first.

The block names this module ``conv``, so its scopes reach the compiled text
as ``conv/in_proj``, ``conv/core`` and ``conv/out_proj``, forward and
transposed ops alike, where the benchmark reads them.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dlrover_tpu.models import layers
from dlrover_tpu.models.family import Family
from dlrover_tpu.models.linear_attention import (
    _conv_bwd,
    _shifted_sum,
    conv_init,
    fold_stats,
    read_stats,
)
from dlrover_tpu.ops import short_conv
from dlrover_tpu.parallel import rules as lr
from dlrover_tpu.runtime.mesh import shard_local

F32 = jnp.float32
STATS_NAME = "conv_stats"


def _ranges(x: jax.Array):
    """``B``, ``C`` and ``z`` of a ``[..., 3d]`` projection, float32."""
    d = x.shape[-1] // 3
    return tuple(x[..., i * d: (i + 1) * d].astype(F32) for i in range(3))


@jax.custom_vjp
def gated_conv_xla(x: jax.Array, taps: jax.Array) -> jax.Array:
    """``C * conv(B * z)`` of ``x = [B | C | z]`` ``[batch, S, 3d]`` under
    ``taps`` ``[K, d]``: ``conv`` the causal depthwise convolution ``sum_j
    taps[j] * v[t - (K - 1) + j]`` per channel, zeros before the sequence
    starts.  Float32 inside, ``x``'s dtype out."""
    b, c, z = _ranges(x)
    return (c * _shifted_sum(b * z, taps.astype(F32), before=True)).astype(
        x.dtype
    )


def _gated_conv_fwd(x, taps):
    return gated_conv_xla(x, taps), (x, taps)


def _gated_conv_bwd(res, dy):
    x, taps = res
    b, c, z = _ranges(x)
    w, dy = taps.astype(F32), dy.astype(F32)
    bz = b * z
    u = _shifted_sum(bz, w, before=True)
    # the plain convolution's own way back: the taps the other way over
    # dy C, and K dot products for the taps
    dbz, d_taps = _conv_bwd((bz, w), dy * c)
    dx = jnp.concatenate([z * dbz, dy * u, b * dbz], axis=-1)
    return dx.astype(x.dtype), d_taps.astype(taps.dtype)


gated_conv_xla.defvjp(_gated_conv_fwd, _gated_conv_bwd)


def core_path(x_shape: Sequence[int], taps_shape: Sequence[int]) -> str:
    """``kernel`` where :func:`gated_conv` runs ``ops/short_conv``'s gated
    form on such an input, ``xla`` where it runs the written-out form."""
    if short_conv.plan_gated(x_shape, taps_shape) is None:
        return "xla"
    return "kernel"


def gated_conv(x: jax.Array, taps: jax.Array):
    """The core by the path :func:`core_path` names.  The kernels see their
    device's block: batch rows may stay sharded, the tokens and the
    channels are whole."""
    if core_path(x.shape, taps.shape) == "xla":
        return gated_conv_xla(x, taps)
    rows = nn.logical_to_mesh_axes((lr.BATCH, None, None))
    return shard_local(
        short_conv.gated_conv, in_specs=(rows, P()), out_specs=rows
    )(x, taps)


class GatedShortConv(nn.Module):
    """The mixer of a ``conv`` layer (the module's text has the
    equations): ``in_proj`` ``[d, 3d]``, the core under ``conv_kernel``
    ``[K, d]``, ``out_proj`` ``[d, d]``."""

    conv_taps: int = 3
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, n: jax.Array) -> jax.Array:
        d = n.shape[-1]
        proj = layers.DenseGeneral(
            3 * d,
            kernel_axes=(lr.EMBED, lr.CONV_INNER),
            dtype=self.dtype, param_dtype=self.param_dtype,
            name="in_proj",
        )(n)
        taps = self.param(
            "conv_kernel",
            nn.with_logical_partitioning(conv_init, (None, lr.CONV_INNER)),
            (self.conv_taps, d), self.param_dtype,
        )
        with jax.named_scope("core"):
            y = gated_conv(proj, taps)
            self.sow(
                "intermediates", STATS_NAME,
                jax.lax.stop_gradient(jnp.stack([
                    jnp.abs(proj[..., :d].astype(F32)).mean(),
                    jnp.abs(proj[..., d: 2 * d].astype(F32)).mean(),
                    jnp.abs(y.astype(F32)).max(),
                ])),
            )
        return layers.DenseGeneral(
            d,
            kernel_axes=(lr.CONV_INNER, lr.EMBED),
            dtype=self.dtype, param_dtype=self.param_dtype,
            name="out_proj",
        )(y)


def from_config(cfg, **kwargs) -> GatedShortConv:
    """The config's ``conv`` mixer: the one place that reads the config's
    fields into the layer's, for the block that runs it and for
    :func:`kernel_facts`."""
    return GatedShortConv(
        conv_taps=cfg.conv_kernel, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype, **kwargs,
    )


def _read(cfg, vec) -> Dict[str, Any]:
    """The ``conv`` event of the step's folded vector: the gates' mean
    sizes and the core's largest output over the layers (no recurrent
    state: the vector is laid out as the delta-rule mixers')."""
    return dict(
        layers=cfg.num_conv_layers,
        **read_stats(vec, "gate_absmean", "out_gate_absmean", "out_absmax"),
    )


def kernel_facts(cfg, seq_len: int) -> Dict[str, str]:
    """``conv_core``: how the core ``C * conv(B * z)`` runs on ``seq_len``
    tokens, ``pallas`` (``ops/short_conv.py``'s gated form) / ``xla`` (the
    written-out form), chosen at trace time from the shapes alone
    (:func:`core_path`, of the projection the layer makes of a ``d_model``
    wide input); ``none`` for a model without a ``conv`` layer."""
    if not cfg.num_conv_layers:
        return {"conv_core": "none"}
    d = cfg.d_model
    path = core_path((1, seq_len, 3 * d), (from_config(cfg).conv_taps, d))
    return {"conv_core": "pallas" if path == "kernel" else "xla"}


FAMILY = Family(
    event="conv",
    stats={STATS_NAME: fold_stats},
    has=lambda cfg: cfg.num_conv_layers,
    read=_read,
    kernel_facts=kernel_facts,
)
