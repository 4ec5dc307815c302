"""Which devices a bench or tool ran on, and virtual CPU devices for those
that only count (bytes, collectives, retraces) and need a mesh to count on.
"""

from __future__ import annotations

import os
from typing import Dict


def device_fields() -> Dict[str, object]:
    """``platform`` / ``device_kind`` / ``device_count`` as jax reports
    them: every printed result carries these, so that a number from the
    CPU is never read as a chip's."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def virtual_cpu_devices(n_devices: int) -> None:
    """Where the caller chose the CPU platform (``JAX_PLATFORMS=cpu``), ask
    XLA for ``n_devices`` virtual devices; call before jax initialises its
    backend.  On any other platform the devices are what they are — a tool
    never picks the platform itself."""
    env = os.environ
    if env.get("JAX_PLATFORMS", "") != "cpu":
        return
    flags = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "force_host_platform_device_count" not in f
    )
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n_devices}".strip()
    )
