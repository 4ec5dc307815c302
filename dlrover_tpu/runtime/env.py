"""Trainer-side runtime bootstrap: consume the agent's environment contract.

The inverse of ``dlrover_tpu.agent.training_agent``: the agent rendezvouses
with the master and exports coordinator/world env vars; the trainer calls
``initialize()`` here to join the jax multi-controller world and get its
master client (for data sharding, step reporting, kv barriers).
"""

from __future__ import annotations

import os
from typing import Optional

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.agent.training_agent import (
    ENV_COORDINATOR,
    ENV_MASTER_ADDR,
    ENV_NODE_ID,
    ENV_NUM_PROC,
    ENV_PROC_ID,
    ENV_RESTART_COUNT,
)


# XLA flag presets, selected by DLROVER_TPU_XLA_PRESET.  The "overlap"
# preset turns on the TPU latency-hiding scheduler for the collectives
# the overlap engine does NOT bucket explicitly (fsdp all-gathers, MoE
# all-to-alls, the non-zero1 gradient all-reduce): the scheduler
# reorders independent HLO to hide async collective latency under
# compute, complementing the structural overlap in parallel/overlap.py.
# TPU-only flags — a CPU XLA build rejects unknown flags at first
# compile, so apply_xla_preset refuses to install them on CPU worlds.
ENV_XLA_PRESET = "DLROVER_TPU_XLA_PRESET"

XLA_PRESETS = {
    "overlap": (
        "--xla_tpu_enable_latency_hiding_scheduler=true",
        "--xla_enable_async_collective_permute=true",
        "--xla_enable_async_all_gather=true",
        "--xla_tpu_enable_async_collective_fusion=true",
        "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
        "--xla_tpu_overlap_compute_collective_tc=true",
    ),
}


def apply_xla_preset(env: Optional[dict] = None, *, platform: str = "") -> str:
    """Merge the preset named by ``$DLROVER_TPU_XLA_PRESET`` into
    ``env["XLA_FLAGS"]``.

    Pure env-dict surgery (defaults to ``os.environ``) so it is testable
    without touching the process: existing XLA_FLAGS are preserved and
    flags already present win over the preset (user overrides stick).
    Returns the preset name applied, or "" when none was.  The flags are
    TPU compiler options; on an explicit CPU world (``platform="cpu"``
    or ``JAX_PLATFORMS=cpu``) the preset is skipped — XLA:CPU aborts on
    unknown flags — and "" is returned.
    """
    if env is None:
        env = os.environ
    name = env.get(ENV_XLA_PRESET, "")
    if not name:
        return ""
    if name not in XLA_PRESETS:
        logger.warning(
            "%s=%r is not a known preset (have: %s); ignoring",
            ENV_XLA_PRESET, name, ", ".join(sorted(XLA_PRESETS)),
        )
        return ""
    platform = platform or env.get("JAX_PLATFORMS", "")
    if "cpu" in platform:
        logger.info(
            "XLA preset %r skipped: TPU scheduler flags on a CPU world",
            name,
        )
        return ""
    existing = env.get("XLA_FLAGS", "")
    have = {
        tok.split("=", 1)[0] for tok in existing.split() if tok
    }
    added = [
        flag for flag in XLA_PRESETS[name]
        if flag.split("=", 1)[0] not in have
    ]
    if added:
        env["XLA_FLAGS"] = " ".join(filter(None, [existing] + added))
    logger.info(
        "XLA preset %r: %d flag(s) added, %d already set",
        name, len(added), len(XLA_PRESETS[name]) - len(added),
    )
    return name


def under_agent() -> bool:
    return ENV_COORDINATOR in os.environ


def process_id() -> int:
    return int(os.environ.get(ENV_PROC_ID, 0))


def num_processes() -> int:
    return int(os.environ.get(ENV_NUM_PROC, 1))


def restart_count() -> int:
    return int(os.environ.get(ENV_RESTART_COUNT, 0))


def node_id() -> int:
    return int(os.environ.get(ENV_NODE_ID, 0))


def initialize(force: bool = False):
    """Join the multi-host jax world the agent rendezvoused for us.

    No-op for single-host jobs (jax initializes locally).  Safe to call
    unconditionally at the top of a training script.

    Applies the ``DLROVER_TPU_XLA_PRESET`` flag preset first (before any
    jax import can snapshot XLA_FLAGS) — see :func:`apply_xla_preset`.
    """
    apply_xla_preset()
    if not under_agent():
        logger.info("no agent environment; single-process jax")
        return
    # Hang-diagnosis seam: the agent can SIGUSR1 this process for an
    # all-thread Python stack dump (agent/stack_collector.py).
    from dlrover_tpu.agent.stack_collector import install_stack_dump_handler

    install_stack_dump_handler()
    n = num_processes()
    if n <= 1 and not force:
        return
    if os.environ.get("DLROVER_TPU_SKIP_JAX_INIT", "") == "1":
        # Control-plane-only multi-host mode: each trainer keeps its own
        # single-process jax world while rendezvous/sharding/checkpoint
        # stay multi-host.  CPU backends cannot run multi-process XLA
        # computations, so drills and benches on dev boxes use this to
        # exercise the elastic control plane (the checkpoint world is
        # still the sealed rendezvous world — the agent's saver stamps
        # it — so cross-world restore paths stay real).
        logger.warning(
            "DLROVER_TPU_SKIP_JAX_INIT=1: not joining the %d-process jax "
            "world; control-plane-only multi-host mode", n,
        )
        return
    import jax

    jax.distributed.initialize(
        coordinator_address=os.environ[ENV_COORDINATOR],
        num_processes=n,
        process_id=process_id(),
    )
    logger.info(
        "joined jax world: process %d/%d (coordinator %s)",
        process_id(), n, os.environ[ENV_COORDINATOR],
    )


def read_paral_config() -> Optional[dict]:
    """Latest runtime-tunable config the agent fetched from the master
    (ref ``ParalConfigTuner``); None when absent/unset."""
    import json

    from dlrover_tpu.common.constants import ConfigKey

    path = os.environ.get(ConfigKey.PARAL_CONFIG_PATH)
    if not path or not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def master_client(node_type: str = "worker"):
    """The trainer's MasterClient, or None when running without a master."""
    addr = os.environ.get(ENV_MASTER_ADDR, "")
    if not addr:
        return None
    from dlrover_tpu.agent.master_client import MasterClient

    return MasterClient(addr, node_id=node_id(), node_type=node_type)
