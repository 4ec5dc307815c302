"""User-facing Flash Checkpoint API.

Capability ref: ``dlrover/trainer/torch/flash_checkpoint/checkpointer.py:23-60``
(``Checkpointer.save_checkpoint(step, storage_type)``) — one class instead of
the reference's per-framework zoo (DDP/FSDP/DeepSpeed/Megatron engines),
because in jax every distributed layout is the same object: a pytree of
sharded arrays.  Resharding on restore is therefore free, which collapses the
reference's hardest adapter (Megatron dist-optimizer resharding,
``megatron_dist_ckpt.py``) into ``jax.device_put`` with new shardings.
"""

from __future__ import annotations

from enum import Enum
from typing import Any, Dict, Optional

import jax

from dlrover_tpu.checkpoint.engine import CheckpointEngine


class StorageType(Enum):
    MEMORY = "memory"
    DISK = "disk"


class Checkpointer:
    """Save/restore a train-state pytree with second-scale blocking time.

    Usage::

        ckpt = Checkpointer(checkpoint_dir, local_saver=True)
        ckpt.save_checkpoint(step, state)                    # shm only
        ckpt.save_checkpoint(step, state, StorageType.DISK)  # + async persist
        step, state = ckpt.load_checkpoint(train.state_shardings, treedef)

    Either call returns once the state is in the shared-memory arena, so a
    SIGKILL after it finds that step there; the persist runs behind the
    training loop.  What the call blocks for is the device-to-host path:
    measured on a TPU v5e at 3.37 GB of state (PERF.md §5), half a second
    (6.7 GB/s) once the arena's pages are resident, and 6-7 s where a save
    falls back to the per-shard copy (0.5 GB/s; event
    ``checkpoint.d2h_fallback``).  Making the arena, touching its pages for
    the first time (8-18 s for those 3.37 GB) and compiling the staged
    programs (6.5 s on an empty compile cache) are a job's first save's
    only where nobody did them before it: ``prepare``, which a trainer
    calls at its start with the state's description, does them on a thread
    beside the step program's compile (PERF.md §6, PR 57).
    """

    def __init__(
        self,
        checkpoint_dir: str,
        storage=None,
        host_index: Optional[int] = None,
        num_hosts: Optional[int] = None,
        local_saver: bool = False,
    ):
        self._engine = CheckpointEngine(
            checkpoint_dir,
            storage=storage,
            host_index=host_index,
            num_hosts=num_hosts,
            local_saver=local_saver,
        )

    def prepare(
        self, state: Any, extra: Optional[Dict[str, Any]] = None, **ids
    ):
        """Start a job's first save's one-time work now, on a thread:
        ``state`` describes what will be saved (``ShapeDtypeStruct``
        leaves under their shardings; ``CheckpointEngine.prepare``)."""
        self._engine.prepare(state, extra, **ids)

    def take_arena_wait(self) -> Optional[float]:
        """Seconds the first save was blocked for ``prepare``'s thread
        (0.0: the work was hidden), once; None otherwise."""
        return self._engine.take_arena_wait()

    def save_checkpoint(
        self,
        step: int,
        state: Any,
        storage_type: StorageType = StorageType.MEMORY,
        extra: Optional[Dict[str, Any]] = None,
    ) -> bool:
        if storage_type == StorageType.MEMORY:
            return self._engine.save_to_memory(step, state, extra)
        return self._engine.save_to_storage(step, state, extra)

    def load_checkpoint(self, shardings: Any = None, state_template: Any = None):
        """Returns (step, state); step==-1 when nothing exists yet.

        ``state_template`` (any pytree with the target structure, e.g. an
        abstract eval_shape state) supplies the treedef; ``shardings`` places
        every leaf — pass the new mesh's shardings to reshard on restore.
        """
        treedef = None
        if state_template is not None:
            treedef = jax.tree_util.tree_structure(state_template)
        return self._engine.load(shardings=shardings, treedef=treedef)

    @property
    def last_extra(self) -> Dict[str, Any]:
        """The ``extra`` sidecar restored by the latest ``load_checkpoint``
        ({} when nothing restored or the checkpoint carried none)."""
        return dict(getattr(self._engine, "last_restored_extra", {}) or {})

    def wait(self, timeout: float = 600.0) -> bool:
        """Block until async persists drained (call before clean job exit)."""
        return self._engine.wait_saver(timeout)

    def close(self):
        self._engine.close()
