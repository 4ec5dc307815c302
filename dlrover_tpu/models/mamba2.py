"""The Mamba-2 mixer: a state-space layer whose recurrent state stands in
place of a KV cache (Dao & Gu, arXiv:2405.21060 §6-7), as Nemotron-H's
``M`` layers run it.

With ``n`` the mixer's input, ``H`` heads of ``P``, a state of ``N`` a
head, ``G`` groups of ``H / G`` heads that share ``B`` and ``C``, and the
inner width ``H P`` (its own number, not ``expand x hidden``; Nemotron-H:
64 heads in 8 groups, Granite-4.0-H: 128 heads in ONE, which the scan's
kernels cut into tiles of heads, ``ops/ssd.py`` ``heads_per_step``)::

    [z | xBC | dt] = n W_in                 (H P | H P + 2 G N | H; no bias)
    xBC = SiLU(causal depthwise conv(xBC), ``taps`` taps, + b_conv)
    x, B, C = xBC split (H P | G N | G N)
    dt = softplus(dt + dt_bias)             (float32, a head; no clamp)
    A  = -exp(A_log)                        (one scalar a head)
    per head:  S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
               y_t = S_t C_t + D x_t        (ops/ssd.py)
    y   = RMSNorm_groups(y * SiLU(z))       (gate first; the mean square
                                             over each group's H P / G
                                             columns, one [H P] scale)
    out = y W_out

The projection is ONE ``[d, 2 H P + 2 G N + H]`` matmul (param
``in_proj``), its ``dt`` columns leave it in the activations' dtype and
are float32 from the softplus on.  The convolution, its bias and the SiLU
are ONE call of ``linear_attention.causal_depthwise_conv`` over all ``H P
+ 2 G N`` channels, which reads them where they lie in the projection
(columns ``H P`` onwards) and returns ``x``, ``B`` and ``C`` apart: one
Pallas pass forward and one backward (``ops/short_conv.py``) where the
tokens are whole lane tiles and the widths whole row tiles, as at the
published widths; K shifted multiply-adds in XLA anywhere else
(:func:`conv_path` says which).

Each forward ``sow``s ``ssm_stats`` = ``[mean exp(dt A), mean dt, largest
|S| entry at a chunk boundary]`` (``linear_attention.split_stats`` reads
it) into ``"intermediates"``: a no-op unless the caller applies with that
collection mutable, as the train step does.

The block names this module ``ssm``, so its scopes reach the compiled text
as ``ssm/in_proj``, ``/conv``, ``/dt``, ``/scan``, ``/out_norm`` and
``/out_proj``, forward and transposed ops alike, where the benchmark reads
them.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import flax.linen as nn
import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from dlrover_tpu.models import layers
from dlrover_tpu.models.family import Family
from dlrover_tpu.models.linear_attention import (
    causal_depthwise_conv,
    conv_init,
    fold_stats,
    read_stats,
    short_conv_path,
)
from dlrover_tpu.ops import ssd as ssd_ops
from dlrover_tpu.ops.ssd import ssd
from dlrover_tpu.parallel import rules as lr
from dlrover_tpu.runtime.mesh import shard_local

F32 = jnp.float32
STATS_NAME = "ssm_stats"


def _a_log_init(key, shape, dtype):
    # Mamba-2: A = 1 .. H, kept as its logarithm
    del key
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=F32)).astype(dtype)


def dt_bias_init(dt_min: float, dt_max: float, dt_floor: float):
    """``dt`` log-uniform in ``[dt_min, dt_max]``, floored at ``dt_floor``,
    kept as the inverse of softplus so that softplus(dt_bias) = dt."""
    def init(key, shape, dtype):
        dt = jnp.exp(
            jax.random.uniform(key, shape, F32)
            * (math.log(dt_max) - math.log(dt_min)) + math.log(dt_min)
        )
        dt = jnp.maximum(dt, dt_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    return init


def _ssd_local(x, dt, a_head, b, c, d, *, chunk, impl):
    """The scan on each device's own batch rows (:func:`shard_local`, as
    attention's ``_flash_local``): ``B`` and ``C`` are a group's, so the
    heads stay whole, and nothing crosses devices but the largest ``|S|``,
    which each device reports for itself."""
    rows = nn.logical_to_mesh_axes((lr.BATCH, None, None, None))
    per_token = nn.logical_to_mesh_axes((lr.BATCH, None, None))
    whole = nn.logical_to_mesh_axes((None,))

    def local(x, dt, a_head, b, c, d):
        y, state_absmax = ssd(x, dt, a_head, b, c, d, chunk=chunk, impl=impl)
        return y, state_absmax[None]

    y, state_absmax = shard_local(
        local, in_specs=(rows, per_token, whole, rows, rows, whole),
        out_specs=(rows, nn.logical_to_mesh_axes((lr.BATCH,))),
    )(x, dt, a_head, b, c, d)
    return y, state_absmax.max()


def conv_path(
    seq: int, num_heads: int, head_dim: int, state_size: int,
    num_groups: int, taps: int,
) -> str:
    """How :class:`Mamba2` of these widths runs its convolution on ``seq``
    tokens: what ``causal_depthwise_conv`` answers for its call."""
    inner, bc = num_heads * head_dim, num_groups * state_size
    return short_conv_path(
        (1, seq, 2 * inner + 2 * bc + num_heads), (taps, inner + 2 * bc),
        inner, (inner, bc, bc),
    )


class Mamba2(nn.Module):
    num_heads: int
    head_dim: int
    state_size: int
    num_groups: int
    conv_taps: int = 4
    chunk: int = 128
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    norm_eps: float = 1e-5
    # ``out_proj``'s initial scale over lecun normal's (Mamba's reference
    # code rescales it by ``(residual branches) ** -0.5``)
    out_init_scale: float = 1.0
    impl: str = "xla"              # ops/ssd.py: "xla" | "kernel"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        h, p, n, g = (
            self.num_heads, self.head_dim, self.state_size, self.num_groups
        )
        inner, bc = h * p, g * n
        features = x.shape[-1]
        batch, s = x.shape[:2]
        proj = layers.DenseGeneral(
            2 * inner + 2 * bc + h,
            kernel_axes=(lr.EMBED, lr.SSM_INNER),
            dtype=self.dtype, param_dtype=self.param_dtype,
            name="in_proj",
        )(x)
        z = proj[..., :inner]
        with jax.named_scope("conv"):
            taps = self.param(
                "conv_kernel",
                nn.with_logical_partitioning(
                    conv_init, (None, lr.SSM_INNER)
                ),
                (self.conv_taps, inner + 2 * bc), self.param_dtype,
            )
            bias = self.param(
                "conv_bias",
                nn.with_logical_partitioning(
                    # torch's Conv1d default: the taps' own bound
                    lambda key, shape, dtype: conv_init(
                        key, (self.conv_taps,) + shape, dtype
                    )[0],
                    (lr.SSM_INNER,),
                ),
                (inner + 2 * bc,), self.param_dtype,
            )
            x_in, b, c = causal_depthwise_conv(
                proj, taps.astype(self.dtype), bias.astype(self.dtype),
                offset=inner, splits=(inner, bc, bc),
            )
            x_in = x_in.reshape(batch, s, h, p)
            b = b.reshape(batch, s, g, n)
            c = c.reshape(batch, s, g, n)
        with jax.named_scope("dt"):
            # 3 H numbers that set every decay and step: float32 whatever
            # the parameters' dtype, as Mamba-2 keeps them
            a_log = self.param(
                "A_log",
                nn.with_logical_partitioning(_a_log_init, (lr.SSM_HEADS,)),
                (h,), F32,
            )
            dt_bias = self.param(
                "dt_bias",
                nn.with_logical_partitioning(
                    dt_bias_init(self.dt_min, self.dt_max, self.dt_floor),
                    (lr.SSM_HEADS,),
                ),
                (h,), F32,
            )
            d_skip = self.param(
                "D",
                nn.with_logical_partitioning(
                    nn.initializers.ones_init(), (lr.SSM_HEADS,)
                ),
                (h,), F32,
            )
            dt = jax.nn.softplus(
                proj[..., 2 * inner + 2 * bc:].astype(F32)
                + dt_bias.astype(F32)
            )
            a_head = -jnp.exp(a_log.astype(F32))
        with jax.named_scope("scan"):
            y, state_absmax = _ssd_local(
                x_in, dt, a_head, b, c, d_skip, chunk=self.chunk,
                impl=self.impl,
            )
        # The one activation of the mixer the layer's remat keeps
        # (ops/remat_policy.py): the backward runs the forward kernel again
        # for its chunk-start states.
        y = jax.ad_checkpoint.checkpoint_name(y, "ssd_out")
        self.sow(
            "intermediates", STATS_NAME,
            jax.lax.stop_gradient(jnp.stack([
                jnp.exp(dt * a_head).mean(), dt.mean(), state_absmax,
            ])),
        )
        with jax.named_scope("out_norm"):
            scale = self.param(
                "out_norm_scale",
                nn.with_logical_partitioning(
                    nn.initializers.ones_init(), (lr.SSM_INNER,)
                ),
                (inner,), self.param_dtype,
            )
            gated = (
                y.reshape(batch, s, inner).astype(F32)
                * nn.silu(z.astype(F32))
            ).reshape(batch, s, g, inner // g)
            normed = gated * jax.lax.rsqrt(
                jnp.mean(gated * gated, axis=-1, keepdims=True)
                + self.norm_eps
            )
            y = (
                normed.reshape(batch, s, inner) * scale.astype(F32)
            ).astype(self.dtype)
        return layers.DenseGeneral(
            features,
            kernel_axes=(lr.SSM_INNER, lr.EMBED),
            dtype=self.dtype, param_dtype=self.param_dtype,
            # lecun normal at scale 1
            kernel_init=nn.initializers.variance_scaling(
                self.out_init_scale ** 2, "fan_in", "truncated_normal"
            ),
            name="out_proj",
        )(y)


def from_config(cfg, **kwargs) -> Mamba2:
    """The config's ``ssm`` mixer: the one place that reads the config's
    fields into the layer's, for the block that runs it and for
    :func:`kernel_facts`."""
    return Mamba2(
        num_heads=cfg.ssm_num_heads,
        head_dim=cfg.ssm_head_dim,
        state_size=cfg.ssm_state_size,
        num_groups=cfg.ssm_groups,
        conv_taps=cfg.ssm_conv_kernel,
        chunk=cfg.ssm_chunk,
        dt_min=cfg.ssm_dt_min,
        dt_max=cfg.ssm_dt_max,
        dt_floor=cfg.ssm_dt_floor,
        norm_eps=cfg.norm_eps,
        out_init_scale=cfg.ssm_out_init_scale,
        impl=cfg.ssm_impl,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        **kwargs,
    )


def _read(cfg, vec) -> Dict[str, Any]:
    """The ``ssm`` event of the step's folded vector (laid out as the
    delta-rule mixers')."""
    mixer = from_config(cfg)
    return dict(
        layers=cfg.num_ssm_layers, chunk=mixer.chunk, heads=mixer.num_heads,
        groups=mixer.num_groups, **read_stats(vec, "mean_decay", "mean_dt"),
    )


def kernel_facts(cfg, seq_len: int) -> Dict[str, Any]:
    """``ssm_scan``: how the scan runs, ``kernel`` / ``xla``
    (``ops/ssd.py``).  ``ssm_heads_per_step``: the heads one grid step of
    the scan kernels holds (``heads_per_step``, which the kernels ask), and
    ``ssm_tiles_per_group``: the grid steps that share one group's B and C
    (the kernels' innermost grid axis: Nemotron-H 1, Granite-4.0-H 16;
    where it is more than 1 the group's state stays in VMEM for all of
    them, ``C B^T`` is formed at the first and dB, dC are written at the
    last); both ``None`` where no kernel runs the scan.  ``short_conv``:
    how the mixer's convolution runs on ``seq_len`` tokens
    (:func:`conv_path`).  ``none`` for a model without such a layer."""
    if not cfg.num_ssm_layers:
        return {
            "ssm_scan": "none", "ssm_heads_per_step": None,
            "ssm_tiles_per_group": None, "short_conv": "none",
        }
    mixer = from_config(cfg)
    per_step = None
    if mixer.impl == "kernel":
        per_step = ssd_ops.heads_per_step(
            mixer.num_heads, mixer.head_dim, mixer.num_groups,
            mixer.state_size, mixer.chunk, mixer.dtype,
        )
    return {
        "ssm_scan": mixer.impl,
        "ssm_heads_per_step": per_step,
        "ssm_tiles_per_group": mixer.num_heads // mixer.num_groups
        // per_step if per_step else None,
        "short_conv": conv_path(
            seq_len, mixer.num_heads, mixer.head_dim, mixer.state_size,
            mixer.num_groups, mixer.conv_taps,
        ),
    }


FAMILY = Family(
    event="ssm",
    stats={STATS_NAME: fold_stats},
    has=lambda cfg: cfg.num_ssm_layers,
    read=_read,
    kernel_facts=kernel_facts,
    absmax="state_absmax",
)
