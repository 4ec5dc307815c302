"""Shared-memory checkpoint arena: pickle-free pytree <-> shm packing.

The TPU half of Flash Checkpoint's hot path (capability ref:
``dlrover/python/elastic_agent/torch/ckpt_saver.py:174-291``
``SharedMemoryHandler._traverse_copy_to_shm``): tensors are copied
device->host asynchronously and memcpy'd into one posix shm arena, with a
pickled *index* (not pickled tensors) describing every leaf.  The arena
outlives the trainer process, so the agent can persist it even after a
SIGKILL.

Layout of the arena::

    [8B meta_len][meta pickle][leaf0 bytes][leaf1 bytes]...

Sharded ``jax.Array`` leaves are stored as their addressable shards with
``replica_id == 0`` (exactly one copy fleet-wide); each shard record carries
its global index so restore can reassemble under any new sharding.
"""

from __future__ import annotations

import dataclasses
import pickle
import struct
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from dlrover_tpu.common import telemetry
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.multi_process import SharedMemory, attach_or_none

_HEADER = struct.Struct("<Q")


@dataclasses.dataclass
class ShardRecord:
    """One locally-stored contiguous block of a (possibly sharded) leaf."""

    index: Tuple[Tuple[int, Optional[int]], ...]  # (start, stop) per dim
    offset: int
    nbytes: int
    shape: Tuple[int, ...]
    # crc32 of this block's raw bytes, stamped by the saver when the shard
    # is persisted to durable storage (None in shm / legacy checkpoints —
    # restore treats a missing digest as "skip verify", never "reject").
    crc32: Optional[int] = None


@dataclasses.dataclass
class TensorMeta:
    path: Tuple[str, ...]
    global_shape: Tuple[int, ...]
    dtype: str
    shards: List[ShardRecord]

    @property
    def local_covers_global(self) -> bool:
        covered = sum(int(np.prod(s.shape)) for s in self.shards)
        return covered == int(np.prod(self.global_shape))


@dataclasses.dataclass
class CheckpointMeta:
    step: int
    created_at: float
    tensors: List[TensorMeta]
    extra: Dict[str, Any]  # small non-array state (pytree def, rng, config)
    # World booking, stamped by the saver at persist time (0/() in shm and
    # legacy checkpoints — readers use ``getattr`` with defaults, since old
    # pickles restore instances lacking these attributes entirely).  Every
    # host's meta lists EVERY tensor path + global shape, so together with
    # this booking any target world m can reshard a step saved by n hosts.
    world_size: int = 0
    world_hosts: Tuple[int, ...] = ()


def _slices_to_index(
    slices: Tuple[slice, ...], shape: Tuple[int, ...]
) -> Tuple[Tuple[int, int], ...]:
    out = []
    for sl, dim in zip(slices, shape):
        start = 0 if sl.start is None else sl.start
        stop = dim if sl.stop is None else sl.stop
        out.append((start, stop))
    return tuple(out)


def _select_shards(leaf) -> Tuple[Tuple[int, ...], str, List[Tuple[Tuple, Any]]]:
    """Return (global_shape, dtype, [(index, device_or_np_block)]) — no D2H."""
    if isinstance(leaf, jax.Array) and hasattr(leaf, "addressable_shards"):
        shards = []
        for shard in leaf.addressable_shards:
            if shard.replica_id != 0:
                continue
            shards.append((_slices_to_index(shard.index, leaf.shape), shard.data))
        if not shards and leaf.addressable_shards:
            # All local replicas are duplicates owned elsewhere; keep one so
            # single-host restore still works (harmless duplicate on disk).
            shard = leaf.addressable_shards[0]
            shards.append((_slices_to_index(shard.index, leaf.shape), shard.data))
        return tuple(leaf.shape), np.dtype(leaf.dtype).name, shards
    block = np.asarray(leaf)
    index = tuple((0, d) for d in block.shape)
    return tuple(block.shape), block.dtype.name, [(index, block)]


def pack_pytree(
    state: Any, step: int, extra: Optional[Dict[str, Any]] = None
) -> Tuple[CheckpointMeta, List[np.ndarray]]:
    """Flatten ``state`` into (meta, ordered blocks). Pure — no shm I/O.

    D2H cost model: every per-shard ``np.asarray`` is a blocking transfer, so
    we first start ``copy_to_host_async`` on *every shard array* (not the
    logical parent — a shard's ``.data`` is a distinct jax.Array whose host
    cache the parent's copy does not warm), then materialize; all transfers
    overlap and total time is max-transfer, not sum-of-round-trips.
    """
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(state)[0]
    selected = [
        (path, _select_shards(leaf)) for path, leaf in leaves_with_paths
    ]
    tensors: List[TensorMeta] = []
    blocks: List[np.ndarray] = []
    offset = 0
    # From the first copy launched to the last shard materialised on the
    # host: what the device-to-host link (and the device, if it still has
    # work in flight) makes the save wait for.
    with telemetry.span("checkpoint.d2h", step=step) as span:
        t0 = time.monotonic()
        for _, (_, _, shards) in selected:
            for _, block in shards:
                if isinstance(block, jax.Array):
                    try:
                        block.copy_to_host_async()
                    except Exception as e:
                        # Purely a prefetch optimization — np.asarray
                        # below still materializes the block synchronously
                        # — but a backend that rejects async copies is
                        # worth one line.
                        logger.debug(
                            "copy_to_host_async unavailable: %s", e
                        )
        launched = time.monotonic()
        for path, (global_shape, dtype, shards) in selected:
            shards = [(index, np.asarray(block)) for index, block in shards]
            records = []
            for index, block in shards:
                block = np.ascontiguousarray(block)
                records.append(
                    ShardRecord(
                        index=index,
                        offset=offset,
                        nbytes=block.nbytes,
                        shape=tuple(block.shape),
                    )
                )
                blocks.append(block)
                offset += block.nbytes
            tensors.append(
                TensorMeta(
                    path=tuple(jax.tree_util.keystr([k]) for k in path),
                    global_shape=global_shape,
                    dtype=dtype,
                    shards=records,
                )
            )
        if span is not None:
            span.attrs["bytes"] = offset
            span.attrs["shards"] = len(blocks)
            # Seconds the launches took; the rest is materialisation.
            span.attrs["launch_s"] = launched - t0
    meta = CheckpointMeta(
        step=step,
        created_at=time.time(),
        tensors=tensors,
        extra=dict(extra or {}),
    )
    return meta, blocks


class SharedMemoryHandler:
    """Owns one shm arena (per training process) and packs pytrees into it."""

    def __init__(self, name: str):
        import os

        job = os.environ.get("DLROVER_TPU_JOB", "")
        tag = f"{job}_" if job else ""
        self.name = f"dlrover_tpu_ckpt_{tag}{name}".replace("/", "_")
        self._shm: Optional[SharedMemory] = None

    # -- writer side (trainer) ------------------------------------------------

    def save_state_dict(
        self, state: Any, step: int, extra: Optional[Dict[str, Any]] = None
    ) -> CheckpointMeta:
        meta, blocks = pack_pytree(state, step, extra)
        meta_bytes = pickle.dumps(meta)
        data_offset = _HEADER.size + len(meta_bytes)
        total = data_offset + sum(b.nbytes for b in blocks)
        self._ensure_capacity(total)
        buf = self._shm.buf
        # Crash-consistency ordering: invalidate the header first, then write
        # data + meta, then publish the header *last*.  A trainer SIGKILLed
        # mid-copy leaves meta_len == 0, which readers treat as "no
        # checkpoint" instead of committing torn tensor bytes.
        with telemetry.span(
            "checkpoint.shm_write", step=step, bytes=total - data_offset
        ):
            buf[: _HEADER.size] = _HEADER.pack(0)
            blocks = iter(blocks)
            for tensor in meta.tensors:
                for record in tensor.shards:
                    start = data_offset + record.offset
                    dst = np.frombuffer(
                        buf, dtype=np.uint8, count=record.nbytes,
                        offset=start,
                    )
                    dst[:] = next(blocks).reshape(-1).view(np.uint8)
            buf[_HEADER.size : data_offset] = meta_bytes
            buf[: _HEADER.size] = _HEADER.pack(len(meta_bytes))
        return meta

    def _ensure_capacity(self, total: int):
        if self._shm is not None and self._shm.size >= total:
            return
        # Only a save that creates, grows or re-attaches the arena comes
        # here.  A new mapping's pages are not touched yet: the first write
        # into them (``checkpoint.shm_write``) pays for that.
        with telemetry.span("checkpoint.arena", bytes=total) as span:
            created = self._open_arena(total)
            if span is not None:
                span.attrs["created"] = created

    def _open_arena(self, total: int) -> bool:
        """Attach the arena if one of this name is large enough, else
        (re)create it; returns whether it was created."""
        if self._shm is not None:
            self._shm.close()
            self._shm.unlink()
            self._shm = None
        # Round up so small step-to-step growth doesn't recreate.
        size = max(total, 1 << 20)
        size = 1 << (size - 1).bit_length()
        existing = attach_or_none(self.name)
        if existing is not None:
            if existing.size >= total:
                self._shm = existing
                return False
            existing.close()
            existing.unlink()
        self._shm = SharedMemory(self.name, create=True, size=size)
        return True

    # -- reader side (agent or restarted trainer) -----------------------------

    def attach(self) -> bool:
        # The writer recreates (unlink + create, strictly larger) the arena
        # when state grows; a reader holding the old mapping would silently
        # read stale bytes forever.  Detect via the backing file's size and
        # re-attach.
        if self._shm is not None:
            try:
                import os

                live_size = os.stat(f"/dev/shm/{self.name}").st_size
            except FileNotFoundError:
                self._shm.close()
                self._shm = None
                return False
            if live_size != self._shm.size:
                logger.info(
                    "shm %s was recreated (%d -> %d bytes); re-attaching",
                    self.name, self._shm.size, live_size,
                )
                self._shm.close()
                self._shm = None
        if self._shm is None:
            self._shm = attach_or_none(self.name)
        return self._shm is not None

    def load_meta(self) -> Optional[CheckpointMeta]:
        if not self.attach():
            return None
        buf = self._shm.buf
        (meta_len,) = _HEADER.unpack(bytes(buf[: _HEADER.size]))
        if meta_len == 0 or meta_len > self._shm.size:
            return None
        try:
            return pickle.loads(bytes(buf[_HEADER.size : _HEADER.size + meta_len]))
        except Exception as e:
            logger.warning("shm %s meta unreadable: %s", self.name, e)
            return None

    def raw_data(self, meta: CheckpointMeta) -> memoryview:
        """The tensor byte region (agent streams this straight to storage)."""
        (meta_len,) = _HEADER.unpack(bytes(self._shm.buf[: _HEADER.size]))
        data_offset = _HEADER.size + meta_len
        end = data_offset + sum(
            r.nbytes for t in meta.tensors for r in t.shards
        )
        return self._shm.buf[data_offset:end]

    def load_block(self, meta: CheckpointMeta, record: ShardRecord) -> np.ndarray:
        (meta_len,) = _HEADER.unpack(bytes(self._shm.buf[: _HEADER.size]))
        data_offset = _HEADER.size + meta_len
        start = data_offset + record.offset
        flat = np.frombuffer(
            self._shm.buf, dtype=np.uint8, count=record.nbytes, offset=start
        )
        return flat

    def no_checkpoint_state(self) -> bool:
        return self.load_meta() is None

    def close(self, unlink: bool = False):
        if self._shm is not None:
            self._shm.close()
            if unlink:
                self._shm.unlink()
            self._shm = None


def assemble_tensor(
    meta: TensorMeta, block_loader
) -> np.ndarray:
    """Reassemble a full tensor from shard records via ``block_loader(record)``
    (returns flat uint8).  Requires the records to cover the global shape."""
    # The dtype's *name*: ``.str`` of bfloat16 (and every other ml_dtypes
    # type) is the opaque ``<V2``, which restores as raw void bytes.
    # ``jnp.dtype`` also reads the ``<f4`` spelling of older checkpoints.
    dtype = jax.numpy.dtype(meta.dtype)
    out = np.empty(meta.global_shape, dtype=dtype)
    for record in meta.shards:
        block = (
            block_loader(record)
            .view(dtype)
            .reshape(record.shape)
        )
        key = tuple(slice(b, e) for b, e in record.index) or ...
        out[key] = block
    return out
