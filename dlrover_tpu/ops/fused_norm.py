"""Fused LayerNorm backward: one Pallas pass for dx + dscale + dbias.

PROFILE.md r4's remaining-sink table prices "LN backward x2 + gelu
backward fusions" at 6.4 ms/layer, bandwidth-bound: XLA splits the LN
backward across several fusions that re-read x and dy from HBM.  This
kernel computes dx and the per-row-block partials of dscale/dbias in a
SINGLE pass over (x, dy) — each operand crosses HBM exactly once — with
fp32 row statistics recomputed from the saved (mean, rstd) residuals.

Status: numerics-verified (interpret mode, and compiled on the chip by
``chip_smoke.py``); the on-chip speedup is not measured — the flag default
stays off until a trace prices it.

Capability ref: the reference leans on apex/Triton fused layernorm
kernels (``atorch/.../layers.py`` fused-norm paths); this is the Pallas
equivalent.

Backward math (per row, fp32):
    xhat  = (x - mean) * rstd
    g     = dy * scale
    dx    = rstd * (g - mean(g) - xhat * mean(g * xhat))
    dscale += sum_rows(dy * xhat);  dbias += sum_rows(dy)
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from dlrover_tpu.ops import backend

DEFAULT_BLOCK_ROWS = 256
# Elements of one [rows, D] block: three bf16 operands double-buffered plus
# the kernel's f32 temporaries stay inside v5e's 16 MiB scoped VMEM at this
# size (256 rows at D=1600 and 128 at D=4096 compile; 256 at 4096 does not).
_BLOCK_ELEMS = 2**19


def _make_bwd_kernel(center: bool):
    """One kernel body for both norms: ``center`` statically includes
    the mean-subtraction terms (LayerNorm) or drops them (RMSNorm)."""

    def kernel(x_ref, dy_ref, scale_ref, mean_ref, rstd_ref,
               dx_ref, dscale_ref, dbias_ref):
        x = x_ref[...].astype(jnp.float32)          # [bn, D]
        dy = dy_ref[...].astype(jnp.float32)        # [bn, D]
        scale = scale_ref[...].astype(jnp.float32)  # [1, D]
        rstd = rstd_ref[...].astype(jnp.float32)    # [bn, 1]

        if center:
            mean = mean_ref[...].astype(jnp.float32)  # [bn, 1]
            xhat = (x - mean) * rstd
        else:
            xhat = x * rstd
        g = dy * scale
        d = x.shape[-1]
        proj = jnp.sum(g * xhat, axis=-1, keepdims=True) / d
        dx = g - xhat * proj
        if center:
            dx = dx - jnp.sum(g, axis=-1, keepdims=True) / d
        dx_ref[...] = (rstd * dx).astype(dx_ref.dtype)
        # Per-block partials, summed over the (small) grid dim outside.
        dscale_ref[0] = jnp.sum(dy * xhat, axis=0, keepdims=True)
        dbias_ref[0] = jnp.sum(dy, axis=0, keepdims=True)

    return kernel


def _ln_fwd_math(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x32 - mean) * rstd * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y, mean[..., 0], rstd[..., 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_layernorm(
    x: jax.Array,
    scale: jax.Array,
    bias: Optional[jax.Array],
    eps: float = 1e-5,
    block_rows: int = DEFAULT_BLOCK_ROWS,
) -> jax.Array:
    """LayerNorm whose BACKWARD is the one-pass Pallas kernel.

    ``x``: [..., D]; ``scale``/``bias``: [D] (bias may be None).  Returns
    x.dtype like the module it backs.  The forward is plain jnp — XLA
    already fuses it well; the backward is where the bandwidth goes.
    """
    y, _, _ = _ln_fwd_math(x, scale, bias, eps)
    return y.astype(x.dtype)


def _fwd(x, scale, bias, eps, block_rows):
    y, mean, rstd = _ln_fwd_math(x, scale, bias, eps)
    return y.astype(x.dtype), (x, scale, bias is not None, mean, rstd)


def _bwd_common(res, dy, block_rows, center):
    x, scale, has_bias, mean, rstd = res
    orig_shape = x.shape
    d = orig_shape[-1]
    n = x.size // d
    x2 = x.reshape(n, d)
    dy2 = dy.reshape(n, d)
    bn = min(block_rows, n, max(16, _BLOCK_ELEMS // d // 16 * 16))
    if n % bn:
        # Pad rows to a block multiple; padded rows have dy=0 -> dx=0 and
        # contribute nothing to the partials (rstd padding of 0 is inert).
        pad = bn - n % bn
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        dy2 = jnp.pad(dy2, ((0, pad), (0, 0)))
        mean = jnp.pad(mean.reshape(-1), (0, pad))
        rstd = jnp.pad(rstd.reshape(-1), (0, pad))
    else:
        mean = mean.reshape(-1)
        rstd = rstd.reshape(-1)
    rows = x2.shape[0]
    grid = rows // bn

    dx, dscale_parts, dbias_parts = pl.pallas_call(
        _make_bwd_kernel(center),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),      # x
            pl.BlockSpec((bn, d), lambda i: (i, 0)),      # dy
            pl.BlockSpec((1, d), lambda i: (0, 0)),       # scale
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),      # mean
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),      # rstd
        ],
        out_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),      # dx
            # Partials ride [grid, 1, D] so each (1, 1, D) block spans the
            # array's whole last two dims: Mosaic refuses a (1, D) block of
            # a (grid, D) array (second-minor neither 8-aligned nor full).
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),  # dscale partial
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),  # dbias partial
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, d), x.dtype),
            jax.ShapeDtypeStruct((grid, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((grid, 1, d), jnp.float32),
        ],
        interpret=backend.interpret(),
    )(
        x2, dy2, scale.reshape(1, d).astype(jnp.float32),
        mean.reshape(rows, 1),
        rstd.reshape(rows, 1),
    )
    dx = dx[:n].reshape(orig_shape)
    dscale = jnp.sum(dscale_parts, axis=(0, 1)).astype(scale.dtype)
    dbias = (
        jnp.sum(dbias_parts, axis=(0, 1)).astype(scale.dtype)
        if has_bias else None
    )
    return dx, dscale, dbias


def _bwd(eps, block_rows, res, dy):
    return _bwd_common(res, dy, block_rows, center=True)


fused_layernorm.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_rmsnorm(
    x: jax.Array,
    scale: jax.Array,
    eps: float = 1e-5,
    block_rows: int = DEFAULT_BLOCK_ROWS,
) -> jax.Array:
    """RMSNorm (Llama-style) with the one-pass Pallas backward."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return y.astype(x.dtype)


def _rms_fwd(x, scale, eps, block_rows):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = x32 * rstd * scale.astype(jnp.float32)
    # mean slot carried as zeros: the uncentered kernel ignores it but
    # the pallas_call signature is shared.
    return y.astype(x.dtype), (
        x, scale, False, jnp.zeros(x.shape[:-1], jnp.float32),
        rstd[..., 0],
    )


def _rms_bwd(eps, block_rows, res, dy):
    dx, dscale, _ = _bwd_common(res, dy, block_rows, center=False)
    return dx, dscale


fused_rmsnorm.defvjp(_rms_fwd, _rms_bwd)
