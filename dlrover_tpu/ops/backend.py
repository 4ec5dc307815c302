"""Where the Pallas kernels of this package run."""

import jax


def interpret() -> bool:
    """Pallas interpret mode everywhere but on a TPU backend: the CPU tests
    run every kernel body as plain HLO; on the chip the kernels compile."""
    return jax.default_backend() != "tpu"
