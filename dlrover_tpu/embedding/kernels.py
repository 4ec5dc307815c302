"""Jitted gather/scatter hot path for the HBM hot-row cache.

The device cache (``embedding/device_cache.py``) keeps hot embedding rows
resident in a fixed ``[capacity, dim]`` device array; every step gathers
the batch's slot set out of it and scatters freshly-fetched / updated rows
back in.  Both directions run through exactly two compiled programs:

- on TPU, a Pallas kernel using ``PrefetchScalarGridSpec`` scalar
  prefetch — the slot indices arrive before the kernel body runs, so each
  grid step DMAs one row block straight between HBM and the output
  without materializing a one-hot or a full-table copy.  The kernels see
  ``[N, 1, dim]`` views (a free bitcast) so that a one-row block spans the
  array's whole last two dims: Mosaic refuses a ``(1, dim)`` block of an
  ``(N, dim)`` array;
- everywhere else (the CPU tier-1 lane), a pure ``jnp.take`` /
  ``.at[].set`` body with the IDENTICAL contract — same shapes, same
  duplicate-slot semantics, same trace counters — so the fallback tests
  prove the interface the TPU kernel must honor.

Shapes are fixed by construction (the cache pads its slot arrays to a
configured maximum), so steady-state lookups trace exactly once per
direction — ``assert_no_retrace("embed_gather", "embed_scatter")`` pins
that.  ``DLROVER_TPU_EMBED_PALLAS=interpret`` forces the Pallas path in
interpreter mode (CPU-runnable), which is how the contract-parity test
exercises the kernel body without a TPU.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ENV_MODE = "DLROVER_TPU_EMBED_PALLAS"


def _bump(name: str):
    # Deferred import: embedding must not pull the trainer layer in at
    # module scope.  Runs at trace time only (inside jit), so the cost is
    # paid once per compiled program, never per step.
    from dlrover_tpu.trainer import train_lib

    train_lib.TRACE_COUNTS[name] += 1


def kernel_mode() -> str:
    """Which body the jitted hot path compiles: ``pallas`` (TPU),
    ``interpret`` (Pallas in interpreter mode — the env override for
    contract tests), or ``jnp`` (the fallback everywhere else)."""
    forced = os.environ.get(ENV_MODE, "").strip().lower()
    if forced in ("interpret", "pallas", "jnp"):
        return forced
    if jax.devices()[0].platform == "tpu":
        return "pallas"
    return "jnp"


# -- pallas bodies -------------------------------------------------------------


def _gather_kernel(slots_ref, cache_ref, out_ref):
    # Block specs already routed cache row slots[i] here; plain copy.
    out_ref[...] = cache_ref[...]


def _scatter_kernel(slots_ref, rows_ref, cache_ref, out_ref):
    # The output aliases the cache; this grid step overwrites row slots[i].
    out_ref[...] = rows_ref[...]


def _pallas_gather(cache: jax.Array, slots: jax.Array,
                   interpret: bool) -> jax.Array:
    n, dim = int(slots.shape[0]), int(cache.shape[1])
    row = (1, 1, dim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec(row, lambda i, slots: (slots[i], 0, 0))],
        out_specs=pl.BlockSpec(row, lambda i, slots: (i, 0, 0)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, dim), cache.dtype),
        interpret=interpret,
    )(slots, cache.reshape(-1, 1, dim)).reshape(n, dim)


def _pallas_scatter(cache: jax.Array, slots: jax.Array,
                    rows: jax.Array, interpret: bool) -> jax.Array:
    n, dim = int(slots.shape[0]), int(cache.shape[1])
    row = (1, 1, dim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(row, lambda i, slots: (i, 0, 0)),         # rows
            pl.BlockSpec(row, lambda i, slots: (slots[i], 0, 0)),  # cache
        ],
        out_specs=pl.BlockSpec(row, lambda i, slots: (slots[i], 0, 0)),
    )
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((cache.shape[0], 1, dim), cache.dtype),
        # Alias the cache operand (index 2: after the scalar-prefetch
        # slots and the rows) onto the output: untouched rows keep their
        # HBM contents in place instead of round-tripping the whole table.
        input_output_aliases={2: 0},
        interpret=interpret,
    )(
        slots, rows.reshape(n, 1, dim), cache.reshape(-1, 1, dim)
    ).reshape(cache.shape)


# -- jitted entry points -------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("mode",))
def _gather(cache, slots, *, mode: str):
    _bump("embed_gather")
    if mode in ("pallas", "interpret"):
        return _pallas_gather(cache, slots, interpret=(mode == "interpret"))
    return jnp.take(cache, slots, axis=0)


@functools.partial(
    jax.jit, static_argnames=("mode",), donate_argnums=(0,)
)
def _scatter(cache, slots, rows, *, mode: str):
    _bump("embed_scatter")
    if mode in ("pallas", "interpret"):
        return _pallas_scatter(
            cache, slots, rows, interpret=(mode == "interpret")
        )
    return cache.at[slots].set(rows)


def gather_rows(cache: jax.Array, slots) -> jax.Array:
    """``cache[slots]`` as one fixed-shape compiled program.

    ``slots`` is int32 ``[P]`` (P = the cache's padded slot width); padded
    tail entries point at the scratch slot 0, whose garbage rows the
    caller's inverse mapping never references.
    """
    return _gather(cache, jnp.asarray(slots, jnp.int32), mode=kernel_mode())


def scatter_rows(cache: jax.Array, slots, rows) -> jax.Array:
    """``cache.at[slots].set(rows)`` as one fixed-shape compiled program.

    The cache argument is DONATED — callers must rebind the returned
    array.  Duplicate slot indices are only ever the scratch slot 0
    (padding), so write order among duplicates is immaterial.
    """
    return _scatter(
        cache, jnp.asarray(slots, jnp.int32),
        jnp.asarray(rows, jnp.float32), mode=kernel_mode(),
    )
