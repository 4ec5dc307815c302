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

import collections
import dataclasses
import functools
import math
import mmap
import os
import pickle
import resource
import struct
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from dlrover_tpu.common import telemetry
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.multi_process import SharedMemory, attach_or_none
from dlrover_tpu.runtime import compile_cache

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


def _local_replica_zero(shape, sharding) -> List[Tuple[Tuple, Any]]:
    """``(index, device)`` of every block of a leaf of ``shape`` under
    ``sharding`` that this host stores: the addressable devices that hold
    replica 0 of their index, in the sharding's device order.  The
    numbering is the one jax gives a shard's ``replica_id`` (devices that
    hold one index count up in that order), read from the sharding alone,
    so a leaf that does not exist yet gives the records its array will."""
    copies: collections.Counter = collections.Counter()
    local, owned = [], []
    for device, slices in sharding.devices_indices_map(shape).items():
        index = _slices_to_index(slices, shape)
        replica, copies[index] = copies[index], copies[index] + 1
        if device in sharding.addressable_devices:
            local.append((index, device))
            if replica == 0:
                owned.append((index, device))
    # All local replicas are duplicates owned elsewhere: keep one so
    # single-host restore still works (harmless duplicate on disk).
    return owned or local[:1]


def _select_shards(leaf) -> Tuple[Tuple[int, ...], str, List[Tuple[Tuple, Any]]]:
    """Return (global_shape, dtype, [(index, block)]) — no D2H.

    A block is a single-device ``jax.Array``, a host array or, for a leaf
    that is only described (a ``jax.ShapeDtypeStruct``, with or without a
    sharding), the description of that block: what ``prepare`` plans a
    first save from.  Either kind of leaf is read by its shape, dtype and
    sharding, so both give the same records."""
    shape = tuple(np.shape(leaf))
    abstract = isinstance(leaf, jax.ShapeDtypeStruct)
    sharding = getattr(leaf, "sharding", None)
    if sharding is None or not (abstract or isinstance(leaf, jax.Array)):
        block = leaf if abstract else np.ascontiguousarray(leaf)
        index = tuple((0, d) for d in shape)
        return shape, np.dtype(block.dtype).name, [(index, block)]
    if abstract:
        def block_on(index, device):
            return jax.ShapeDtypeStruct(
                tuple(stop - start for start, stop in index), leaf.dtype,
                sharding=jax.sharding.SingleDeviceSharding(device),
            )
    else:
        data = {s.device: s.data for s in leaf.addressable_shards}

        def block_on(index, device):
            return data[device]
    shards = [
        (index, block_on(index, device))
        for index, device in _local_replica_zero(shape, sharding)
    ]
    return shape, np.dtype(leaf.dtype).name, shards


def _nbytes(block) -> int:
    """Bytes of a block, present or described."""
    return math.prod(block.shape) * np.dtype(block.dtype).itemsize


def _device_of(block):
    """The device of a block, or None for one on the host."""
    if isinstance(block, jax.Array):
        return block.device
    sharding = getattr(block, "sharding", None)
    return None if sharding is None else next(iter(sharding.device_set))


def _plan_pytree(
    state: Any, step: int, extra: Optional[Dict[str, Any]]
) -> Tuple[CheckpointMeta, List[Any]]:
    """The meta of a save and, in the order of its records, the block each
    record's bytes come from (a single-device ``jax.Array`` or a host
    array; its description where ``state`` is described).  Nothing is
    copied: a record's size is its block's."""
    tensors: List[TensorMeta] = []
    sources: List[Any] = []
    offset = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        global_shape, dtype, shards = _select_shards(leaf)
        records = []
        for index, block in shards:
            nbytes = _nbytes(block)
            records.append(
                ShardRecord(
                    index=index,
                    offset=offset,
                    nbytes=nbytes,
                    # A scalar's block is stored as one element, (1,).
                    shape=tuple(block.shape) or (1,),
                )
            )
            sources.append(block)
            offset += nbytes
        tensors.append(
            TensorMeta(
                path=tuple(jax.tree_util.keystr([k]) for k in path),
                global_shape=global_shape,
                dtype=dtype,
                shards=records,
            )
        )
    meta = CheckpointMeta(
        step=step,
        created_at=time.time(),
        tensors=tensors,
        extra=dict(extra or {}),
    )
    return meta, sources


def _minor_faults(who: int = resource.RUSAGE_SELF) -> int:
    """Pages this process (``RUSAGE_THREAD``: the calling thread) has
    touched for the first time, so far."""
    return resource.getrusage(who).ru_minflt


def _as_bytes(block: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(block).reshape(-1).view(np.uint8)


def _fetch_per_shard(sources: List[Any]) -> Tuple[List[np.ndarray], float]:
    """Every block on the host, each device block by a copy of its own
    into a host buffer the runtime allocates for it; also the seconds the
    launches took (the rest is the wait for the copies).

    Measured (TPU v5e, GPT-2 1.5B, 3.37 GB in 66 bf16 blocks; PERF.md §5):
    0.50-0.55 GB/s, whether or not the 66 copies are launched together
    and whether or not the host memory is recycled.  The runtime brings
    each block from its tiled (for some shapes transposed) device layout
    to row-major on the way, and that, not the link, is the time: the
    same bytes flattened on the device first are in the arena in 0.5 s.
    """
    t0 = time.monotonic()
    for block in sources:
        if isinstance(block, jax.Array):
            try:
                # A shard's ``.data`` is a distinct jax.Array whose host
                # cache the logical parent's copy does not warm.
                block.copy_to_host_async()
            except Exception as e:
                # Purely a prefetch — np.asarray below still materializes
                # the block — but a backend that rejects async copies is
                # worth one line.
                logger.debug("copy_to_host_async unavailable: %s", e)
    launch_s = time.monotonic() - t0
    return [np.ascontiguousarray(block) for block in sources], launch_s


def pack_pytree(
    state: Any, step: int, extra: Optional[Dict[str, Any]] = None
) -> Tuple[CheckpointMeta, List[np.ndarray]]:
    """Flatten ``state`` into (meta, ordered blocks). Pure — no shm I/O.

    Every block comes to the host by the per-shard copy
    (``_fetch_per_shard``: 0.5 GB/s on a v5e).  A save into the arena
    takes the staged path of ``SharedMemoryHandler.save_state_dict``.
    """
    meta, sources = _plan_pytree(state, step, extra)
    # From the first copy launched to the last shard materialised on the
    # host: what the device-to-host path (and the device, if it still has
    # work in flight) makes the caller wait for.
    with telemetry.span(
        "checkpoint.d2h", step=step, path="per_shard", groups=0
    ) as span:
        faults = _minor_faults()
        blocks, launch_s = _fetch_per_shard(sources)
        if span is not None:
            span.attrs["bytes"] = sum(b.nbytes for b in blocks)
            span.attrs["shards"] = len(blocks)
            span.attrs["launch_s"] = launch_s
            span.attrs["minflt"] = _minor_faults() - faults
    return meta, blocks


# -- the staged device-to-host path ---------------------------------------------

#: Bytes of one staged transfer: a run of a block's elements in row-major
#: order, as a 1-D array made on the device.  Measured on a v5e (PERF.md
#: §5; 3.37 GB, seconds a save): pieces of 8 and 16 MiB 0.49-0.54, of 32
#: MiB 1.18-1.20, of 64 MiB 1.2-1.7, of 128 MiB 2.7.  The runtime hands
#: each piece over in a host buffer it allocates for it; from 32 MiB on
#: glibc maps such a buffer afresh, and first touches are what is slow.
_PIECE_BYTES = 16 << 20
#: Bytes launched ahead of the piece being copied into the arena: with one
#: more program's pieces, all the host memory a save takes, whatever the
#: size of the state (128 MiB: 0.61 s, 256 MiB: 0.49-0.52, 512 MiB: 0.58).
_IN_FLIGHT_BYTES = 16 * _PIECE_BYTES
#: Pieces one program makes.  Every program flattens its whole block into
#: a temporary before it cuts, so with a program a piece a block of 1 GB
#: is flattened sixty times over and the device, busy all through the
#: save, is what the transfers wait for (0.96-0.98 s a save; 8 or 16 pieces
#: a program: 0.49-0.52 s, a third of it transfers, the rest the copy
#: into the arena).
_GROUP_PIECES = 8
#: A block smaller than this takes the per-shard copy: it is too small to
#: pay for a program.
_STAGED_MIN_BYTES = 4 << 20


def _flat_pieces(block, start, *, sizes: Tuple[int, ...]):
    """Consecutive runs of ``sizes`` elements of ``block`` in row-major
    order, from element ``start``, each as a 1-D array: the bytes as a
    host reader wants them, in a layout the runtime copies out without
    touching.

    XLA's TPU compiler flattens the whole block into a temporary (the
    device has the HBM between steps) and compiles this in 0.2-0.6 s
    whatever the shape.  A reshape of a run of rows alone needs no such
    temporary, but takes the compiler up to 30 s where the minor dimension
    is no multiple of 128; and pieces put together row by row in a loop on
    the device come out right and cross at a third of the rate (PERF.md
    §6, PR 25).
    """
    flat = block.reshape(-1)
    pieces = []
    for size in sizes:
        pieces.append(jax.lax.dynamic_slice(flat, (start,), (size,)))
        start = start + size
    return tuple(pieces)


def _program_bytes(compiled) -> int:
    """HBM a compiled piece program needs beside its input: its pieces and
    the flattened block (twice that for a block the TPU stores
    transposed)."""
    stats = compiled.memory_analysis()
    return int(stats.temp_size_in_bytes + stats.output_size_in_bytes)


def _free_device_bytes(device) -> Optional[int]:
    """Free memory of ``device``, or None where the backend keeps no
    count (the CPU's)."""
    stats = device.memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"] - stats["bytes_in_use"])


@dataclasses.dataclass(frozen=True)
class _StagedPlan:
    """How the blocks of one shape, dtype and device cross."""

    #: (first element, elements of each piece) of every program call
    groups: List[Tuple[int, Tuple[int, ...]]]
    programs: Dict[Tuple[int, ...], Any]  # sizes -> compiled _flat_pieces
    device_bytes: int  # HBM the largest program needs beside its input


class SharedMemoryHandler:
    """Owns one shm arena (per training process) and packs pytrees into it."""

    def __init__(self, name: str):
        job = os.environ.get("DLROVER_TPU_JOB", "")
        tag = f"{job}_" if job else ""
        self.name = f"dlrover_tpu_ckpt_{tag}{name}".replace("/", "_")
        self._shm: Optional[SharedMemory] = None
        # Staged plans by (shape, dtype, device): compiled by ``prepare``,
        # else at the first save that meets a block of that kind, and
        # never again.
        self._staged: Dict[Tuple, Optional[_StagedPlan]] = {}
        #: Path the last save took and the bytes a second it moved from
        #: the device into the arena.
        self.last_d2h: Dict[str, Any] = {}

    # -- writer side (trainer) ------------------------------------------------

    def save_state_dict(
        self, state: Any, step: int, extra: Optional[Dict[str, Any]] = None
    ) -> CheckpointMeta:
        """Write ``state`` into the arena; returns once every byte is
        there and the header is published.

        The device-to-host path, as measured on a v5e (PERF.md §5): the
        runtime brings 1-D arrays of 16 MiB to the host faster than one
        thread copies them on into the arena (3.37 GB in 0.5 s, both
        included), and arrays in their device layout at 0.5 GB/s.  So
        each large block is flattened on the device piece by piece
        (``_flat_pieces``), pieces are in flight while the last one is
        copied to its record's place in the arena, and the host memory a
        save takes does not grow with the state.
        Small blocks cross as they are, in the same pipeline; the
        per-shard copy of everything stays as the counted fallback.
        """
        meta, sources = _plan_pytree(state, step, extra)
        meta_bytes = pickle.dumps(meta)
        data_offset = _HEADER.size + len(meta_bytes)
        size = sum(r.nbytes for t in meta.tensors for r in t.shards)
        mapped = self._ensure_capacity(data_offset + size)
        buf = self._shm.buf
        # Crash-consistency ordering: invalidate the header first, then write
        # data + meta, then publish the header *last*.  A trainer SIGKILLed
        # mid-copy leaves meta_len == 0, which readers treat as "no
        # checkpoint" instead of committing torn tensor bytes.
        buf[: _HEADER.size] = _HEADER.pack(0)
        arena = np.frombuffer(buf, dtype=np.uint8, count=size, offset=data_offset)
        offsets = [r.offset for t in meta.tensors for r in t.shards]
        # From the first launch to the last device byte in the arena (the
        # per-shard path: on the host; ``checkpoint.shm_write`` copies).
        with telemetry.span("checkpoint.d2h", step=step) as span:
            t0 = time.monotonic()
            faults = _minor_faults()
            left, d2h = self._device_to_arena(sources, offsets, arena, step)
            d2h.update(bytes=size, shards=len(sources),
                       minflt=_minor_faults() - faults)
            if span is not None:
                span.attrs.update(d2h)
            seconds = max(time.monotonic() - t0, 1e-9)
            self.last_d2h = dict(d2h, gb_s=size / seconds / 1e9)
        with telemetry.span("checkpoint.shm_write", step=step, bytes=size):
            for offset, block in left:
                flat = _as_bytes(block)
                arena[offset : offset + flat.size] = flat
            del arena
            buf[_HEADER.size : data_offset] = meta_bytes
            buf[: _HEADER.size] = _HEADER.pack(len(meta_bytes))
        if mapped:
            self._settle(data_offset + size)
        return meta

    def prepare(
        self, state: Any, extra: Optional[Dict[str, Any]] = None,
        on_mapped=None, **ids,
    ) -> Dict[str, Any]:
        """Do a job's first save's one-time work for a state that does not
        exist yet, so that the first save is a later save.

        ``state`` describes what will be saved: a pytree whose leaves are
        ``jax.ShapeDtypeStruct``s under their shardings (and ``extra`` the
        sidecar a save will carry).  Its records are ``_plan_pytree``'s,
        so the bytes reckoned here are the bytes the save writes.  Then,
        on the paths the first save would take (``_ensure_capacity``,
        ``_settle``, ``_staged_plan``):

        * the arena is mapped; ``on_mapped()`` is called once it is, after
          which a reader (``load_meta``) may use this handler;
        * a mapping this call CREATED is written once, with zeros (its
          header then says "no checkpoint", which is true), and settled:
          on the v5e machines those are the two slow passes of a new
          mapping (``_settle``), and the first save's write is the third;
        * an arena that was THERE (a restart in place) holds the only copy
          of the last acknowledged save: it is attached and read a byte a
          page, never written, and one too small for ``state`` is left
          alone (the restore may still want it; the first save makes the
          larger one);
        * the staged programs of every kind of block the state has are
          compiled, so that ``_staged`` is full before the first save.

        Returns the attributes of its span, ``checkpoint.prepare`` (which
        also takes ``ids``, a trainer's ``restart_count``).  The caller
        holds the arena's lock as a save does.  A state that turns
        out larger than described costs nothing but this work: the save
        recreates the arena as it always did.
        """
        with telemetry.span("checkpoint.prepare", **ids) as span:
            faults = _minor_faults(resource.RUSAGE_THREAD)
            meta, sources = _plan_pytree(state, 0, extra)
            total = _HEADER.size + len(pickle.dumps(meta)) + sum(
                r.nbytes for t in meta.tensors for r in t.shards
            )
            try:
                mapped = self._ensure_capacity(total, ahead=True)
            finally:
                if on_mapped is not None:
                    on_mapped()
            write_s = 0.0
            if mapped == "created":
                # numpy fills without the GIL: a trainer's tracing, on
                # another thread, is not held up (PERF.md section 6, PR 57).
                t0 = time.monotonic()
                np.frombuffer(self._shm.buf, np.uint8, count=total).fill(0)
                write_s = time.monotonic() - t0
            if mapped:
                self._settle(total)
            for block in sources:
                device = _device_of(block)
                if device is not None:
                    self._staged_plan(block.shape, block.dtype, device)
            found = {
                "bytes": total, "created": mapped == "created",
                # the zeros' pass: the mapping's first (0.0: none made)
                "write_s": round(write_s, 6),
                # what the first save finds compiled
                "programs": sum(
                    len(plan.programs) for plan in self._staged.values()
                    if plan is not None
                ),
                "minflt": _minor_faults(resource.RUSAGE_THREAD) - faults,
            }
            if span is not None:
                span.attrs.update(found)
        return found

    def _settle(self, total: int):
        """Read a byte of every page of a mapping that has just been
        written for the first time.

        On the v5e machines a new mapping is at full speed only from its
        third pass on: 3.37 GB are written in 8.2-10.0 s, then in 1.5-1.8 s,
        then in 0.24 s, and the second pass costs 0.23 s where it reads one
        byte a page (PERF.md section 6, PR 26).  Left alone it falls on the
        job's next save, whose stall then swings with the host's memory
        from run to run (1.32-1.82 s against 0.46-0.50 s for every later
        save); here whoever made the mapping pays for all of it: ``prepare``
        at a trainer's start, else the save that found none.
        """
        with telemetry.span("checkpoint.arena_settle", bytes=total):
            pages = np.frombuffer(self._shm.buf, dtype=np.uint8, count=total)
            int(pages[:: mmap.PAGESIZE].max())

    def _device_to_arena(self, sources, offsets, arena, step):
        """Bring every device block into ``arena``; returns the (offset,
        host block) pairs still to be written there, and what the
        ``checkpoint.d2h`` span says of the path taken."""
        host = [
            (offset, block) for offset, block in zip(offsets, sources)
            if not isinstance(block, jax.Array)
        ]
        device = [
            (offset, block) for offset, block in zip(offsets, sources)
            if isinstance(block, jax.Array)
        ]
        try:
            plans = [
                self._staged_plan(block.shape, block.dtype, block.device)
                for _, block in device
            ]
            reason = self._refusal(device, plans)
            if reason is None:
                return host, self._stage(device, plans, arena)
        except jax.errors.JaxRuntimeError as e:
            # The arena may hold part of the state: its header says "no
            # checkpoint" until the per-shard path has rewritten all.
            logger.warning("step %d: staged save failed: %s", step, e)
            reason = "device_error"
        logger.warning(
            "step %d: save falls back to the per-shard copy (%s)",
            step, reason,
        )
        telemetry.event("checkpoint.d2h_fallback", step=step, reason=reason)
        blocks, launch_s = _fetch_per_shard([b for _, b in device])
        left = host + [(o, b) for (o, _), b in zip(device, blocks)]
        return left, {"path": "per_shard", "groups": 0, "launch_s": launch_s}

    def _staged_plan(self, shape, dtype, device) -> Optional[_StagedPlan]:
        """The plan for blocks of ``shape`` and ``dtype`` on ``device``
        (None: per-shard copy), compiled for a described block, so that
        it can be made before any block exists."""
        dtype = np.dtype(dtype)
        key = (tuple(shape), dtype.name, device.id)
        if key in self._staged:
            return self._staged[key]
        plan = None
        size = math.prod(shape)
        # (a flat block has nothing to undo)
        if len(shape) >= 2 and size * dtype.itemsize >= _STAGED_MIN_BYTES:
            most = _PIECE_BYTES // dtype.itemsize
            sizes = [
                min(most, size - first) for first in range(0, size, most)
            ]
            groups, first = [], 0
            for i in range(0, len(sizes), _GROUP_PIECES):
                group = tuple(sizes[i : i + _GROUP_PIECES])
                groups.append((first, group))
                first += sum(group)
            block = jax.ShapeDtypeStruct(
                tuple(shape), dtype,
                sharding=jax.sharding.SingleDeviceSharding(device),
            )
            programs = {
                group: compile_cache.staged_compile(
                    jax.jit(functools.partial(_flat_pieces, sizes=group)),
                    block, np.int32(0),
                )
                for group in sorted({group for _, group in groups})
            }
            plan = _StagedPlan(
                groups, programs,
                max(_program_bytes(p) for p in programs.values()),
            )
        self._staged[key] = plan
        return plan

    @staticmethod
    def _refusal(device, plans) -> Optional[str]:
        """Why this save cannot take the staged path, or None."""
        need: Dict[Any, int] = {}
        for (_, block), plan in zip(device, plans):
            if plan is not None:
                need[block.device] = max(
                    need.get(block.device, 0), plan.device_bytes
                )
        for dev, most in need.items():
            free = _free_device_bytes(dev)
            if free is not None and free < most + _IN_FLIGHT_BYTES:
                return "hbm_headroom"
        return None

    def _stage(self, device, plans, arena):
        """The pipeline: flatten the next piece on the device and launch
        its copy while earlier ones cross, and copy each into the arena at
        its record's offset as it arrives.  Returns the span's attrs."""
        pending: collections.deque = collections.deque()
        started = time.monotonic()
        landing_s = copy_s = 0.0
        groups = in_flight = 0

        def land():
            nonlocal landing_s, copy_s, in_flight
            t0 = time.monotonic()
            piece, offset = pending.popleft()
            flat = _as_bytes(np.asarray(piece))
            t1 = time.monotonic()
            arena[offset : offset + flat.size] = flat
            in_flight -= flat.size
            copy_s += time.monotonic() - t1
            landing_s += time.monotonic() - t0

        def pieces_of(offset, block, plan):
            if plan is None:
                yield block, offset
                return
            for first, sizes in plan.groups:
                at = offset + first * block.dtype.itemsize
                for piece in plan.programs[sizes](block, np.int32(first)):
                    yield piece, at
                    at += piece.nbytes

        for (offset, block), plan in zip(device, plans):
            groups += len(plan.groups) if plan is not None else 0
            for piece, at in pieces_of(offset, block, plan):
                piece.copy_to_host_async()
                pending.append((piece, at))
                in_flight += piece.nbytes
                while in_flight > _IN_FLIGHT_BYTES:
                    land()
        while pending:
            land()
        return {
            "path": "staged", "groups": groups, "arena_copy_s": copy_s,
            # what is neither the wait for a piece nor its copy
            "launch_s": time.monotonic() - started - landing_s,
        }

    def _ensure_capacity(self, total: int, ahead: bool = False) -> str:
        """How this call mapped the arena: ``"created"``, ``"attached"``
        or, where the mapping it has is large enough, ``""``."""
        if self._shm is not None and self._shm.size >= total:
            return ""
        # The one place the arena is created, grown or re-attached:
        # ``prepare`` comes here at a trainer's start (``ahead``), a save
        # only where that was skipped or the state outgrew it.  A new
        # mapping's pages are not touched yet: the first write into them
        # pays for that (``prepare``'s zeros; else ``checkpoint.d2h``), and
        # ``_settle`` for their second pass.
        with telemetry.span(
            "checkpoint.arena", bytes=total, ahead=ahead
        ) as span:
            mapped = self._open_arena(total, replace=not ahead)
            if span is not None:
                span.attrs["created"] = mapped == "created"
        return mapped

    def _open_arena(self, total: int, replace: bool = True) -> str:
        """Attach the arena if one of this name is large enough
        (``"attached"``), else (re)create it (``"created"``).  Without
        ``replace`` an arena that is there and too small is left as it is
        (``""``): ahead of a restore it may hold the only copy of the
        last acknowledged save, and the save that needs the room
        replaces it, as it always did."""
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
                return "attached"
            existing.close()
            if not replace:
                return ""
            existing.unlink()
        self._shm = SharedMemory(self.name, create=True, size=size)
        return "created"

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
