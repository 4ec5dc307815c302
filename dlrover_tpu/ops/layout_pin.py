"""Pallas identity op that pins XLA's layout assignment to row-major.

Why this exists: custom calls (the Pallas flash-attention kernels) demand
descending default layouts on their ``[B, H, S, D]`` operands.  XLA's layout
assignment propagates that preference backwards through the q/k/v
projection into the residual stream, flipping the whole transformer layer
into a seq-minor layout in which the MLP matmuls lower to windowed
"convolution" emitters at ~40% MXU (measured: the wo forward ran 2.5x over
its matmul-parity time, PROFILE.md round 4).  There is no public XLA API to
pin an *intermediate* tensor's layout — but a Pallas call is itself a
custom call with default-layout operands, so an identity kernel acts as a
layout firewall at two HBM round-trips (~0.13 ms per [16,1024,1600] bf16
tensor — repaid ~20x by the healed matmuls).

Gradient: pinning is layout-transparent math, so the VJP pins the cotangent
stream the same way (the backward pass has its own layout contagion).
"""

from __future__ import annotations


import jax
from jax.experimental import pallas as pl

from dlrover_tpu.ops import backend


def _identity_kernel(x_ref, o_ref):
    o_ref[0] = x_ref[0]


def _pin_call(x: jax.Array) -> jax.Array:
    if x.ndim < 2 or backend.interpret():
        # CPU/interpret: layouts don't exist; keep the graph clean.
        return x
    *lead, s, f = x.shape
    lead_n = 1
    for d in lead:
        lead_n *= d
    x3 = x.reshape(lead_n, s, f)
    bs = s
    while bs > 1 and (s % bs or bs * f * x.dtype.itemsize > 4 * 2**20):
        bs //= 2
    out = pl.pallas_call(
        _identity_kernel,
        grid=(lead_n, s // bs),
        in_specs=[pl.BlockSpec((1, bs, f), lambda ib, i: (ib, i, 0))],
        out_specs=pl.BlockSpec((1, bs, f), lambda ib, i: (ib, i, 0)),
        out_shape=jax.ShapeDtypeStruct((lead_n, s, f), x.dtype),
    )(x3)
    return out.reshape(x.shape)


@jax.custom_vjp
def pin_layout(x: jax.Array) -> jax.Array:
    """Identity; forces ``x`` into the default row-major layout."""
    return _pin_call(x)


def _pin_fwd(x):
    return _pin_call(x), None


def _pin_bwd(_, g):
    return (_pin_call(g),)


pin_layout.defvjp(_pin_fwd, _pin_bwd)
