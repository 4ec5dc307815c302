"""Flash Checkpoint tests: IPC primitives, shm packing, engine/saver cycle.

Mirrors the reference's test approach (SURVEY.md §4:
``test_ckpt_saver.py``/``checkpoint_egine_test.py`` exercise shm handler +
saver single-node with temp dirs as storage).
"""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common import multi_process as mp_ipc
from dlrover_tpu.common.storage import (
    CheckpointDirLayout,
    KeepLatestStepStrategy,
    KeepStepIntervalStrategy,
    PosixDiskStorage,
)
from dlrover_tpu.checkpoint.checkpointer import Checkpointer, StorageType
from dlrover_tpu.checkpoint.shm_handler import SharedMemoryHandler, assemble_tensor


@pytest.fixture(autouse=True)
def _socket_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("DLROVER_TPU_SOCKET_DIR", str(tmp_path / "socks"))


def test_shared_queue_lock_dict_cross_object(tmp_path):
    server_q = mp_ipc.SharedQueue("q1", create=True)
    client_q = mp_ipc.SharedQueue("q1", create=False)
    client_q.put({"step": 3})
    assert server_q.get(timeout=2) == {"step": 3}
    assert client_q.get(timeout=0.1, default="empty") == "empty"

    server_l = mp_ipc.SharedLock("l1", create=True)
    client_l = mp_ipc.SharedLock("l1", create=False)
    assert client_l.acquire()
    # Reentrant for the same owner (lost-response retries must not deadlock).
    assert client_l.acquire(blocking=False)
    # Contended from a *different* thread -> refused.
    from_other: list = []
    t = threading.Thread(
        target=lambda: from_other.append(server_l.acquire(blocking=False))
    )
    t.start(); t.join()
    assert from_other == [False]
    assert client_l.release()
    assert server_l.acquire(blocking=False)
    server_l.release()

    server_d = mp_ipc.SharedDict("d1", create=True)
    client_d = mp_ipc.SharedDict("d1", create=False)
    client_d.set("k", [1, 2])
    assert server_d.get("k") == [1, 2]
    client_d.update({"a": 1, "b": 2})
    assert set(server_d.snapshot()) == {"k", "a", "b"}
    for obj in (server_q, server_l, server_d):
        obj.close()


def test_shm_handler_roundtrip():
    handler = SharedMemoryHandler(f"t{os.getpid()}")
    state = {
        "w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "b": np.ones(5, np.int32),
        "nested": {"s": jnp.float32(2.5)},
        # bf16 params (the 1.5B model's): numpy spells the dtype "<V2".
        "h": jnp.arange(6, dtype=jnp.bfloat16),
    }
    meta = handler.save_state_dict(state, step=7, extra={"note": "x"})
    assert meta.step == 7

    reader = SharedMemoryHandler(f"t{os.getpid()}")
    meta2 = reader.load_meta()
    assert meta2.step == 7 and meta2.extra == {"note": "x"}
    arrays = {
        t.path: assemble_tensor(t, lambda r: reader.load_block(meta2, r))
        for t in meta2.tensors
    }
    flat = {p: a for p, a in arrays.items()}
    w = [a for p, a in flat.items() if "'w'" in "".join(p)][0]
    np.testing.assert_array_equal(
        w, np.arange(12, dtype=np.float32).reshape(3, 4)
    )
    h = [a for p, a in flat.items() if "'h'" in "".join(p)][0]
    assert h.dtype == jnp.bfloat16
    np.testing.assert_array_equal(h.astype(np.float32), np.arange(6))
    handler.close(unlink=True)
    reader.close()


def test_shm_handler_sharded_array():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devices = np.asarray(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devices, ("x",))
    arr = jax.device_put(
        jnp.arange(32, dtype=jnp.float32).reshape(8, 4),
        NamedSharding(mesh, PartitionSpec("x")),
    )
    handler = SharedMemoryHandler(f"s{os.getpid()}")
    meta = handler.save_state_dict({"p": arr}, step=1)
    t = meta.tensors[0]
    assert t.global_shape == (8, 4)
    assert len(t.shards) == 4  # one block per device shard
    out = assemble_tensor(t, lambda r: handler.load_block(meta, r))
    np.testing.assert_array_equal(out, np.asarray(arr))
    handler.close(unlink=True)


# -- the two device-to-host paths of a save -------------------------------------


def _leaf(kind: str, dtype):
    """A leaf of the named kind, its values distinct, and the number of
    records a save stores for it."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    shape = {
        "scalar": (), "flat": (300,), "ragged_2d": (100, 33),
        "several_pieces": (6, 40, 50), "sharded_replicated": (64, 50),
    }[kind]
    values = np.arange(int(np.prod(shape)), dtype=np.int64).reshape(shape)
    leaf = jnp.asarray(values % 251 - 125, dtype)
    if kind != "sharded_replicated":
        return leaf, 1
    # Rows over "x", a second copy of every shard over "y": the save keeps
    # the copy with replica_id 0 of each.
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("x", "y"))
    return jax.device_put(
        leaf, NamedSharding(mesh, PartitionSpec("x", None))
    ), 2


def _named(events, name):
    return [e for e in events if e[0] == name]


@pytest.mark.parametrize("kind", [
    "scalar", "flat", "ragged_2d", "several_pieces", "sharded_replicated",
])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32", "int32"])
@pytest.mark.parametrize("path", ["staged", "per_shard"])
def test_arena_round_trip_by_either_path(
    path, dtype, kind, small_pieces, tap, monkeypatch
):
    """Whichever way the bytes leave the device, the arena holds each
    record's bytes at its offset in ``meta.tensors`` order, the meta
    describes them, and a restore is bitwise."""
    leaf, records = _leaf(kind, jnp.dtype(dtype))
    # (dict keys flatten in sorted order)
    state = {"a": np.arange(3, dtype=np.int16), "b": leaf,
             "c": jnp.ones((40, 50), jnp.float32)}
    if path == "per_shard":
        monkeypatch.setattr(small_pieces, "_free_device_bytes", lambda d: 0)
    handler = SharedMemoryHandler(f"rt{os.getpid()}")
    try:
        meta = handler.save_state_dict(state, step=4, extra={"k": 1})
        events = tap.take()
        (d2h,) = _named(events, "checkpoint.d2h")
        assert d2h[4]["path"] == path
        fallbacks = _named(events, "checkpoint.d2h_fallback")
        if path == "per_shard":
            (fallback,) = fallbacks
            assert fallback[1] == "event"
            assert fallback[4] == dict(
                fallback[4], step=4, reason="hbm_headroom"
            )
            assert d2h[4]["groups"] == 0
        else:
            assert not fallbacks
            # "c" is one program's two pieces; a scalar or a flat leaf is
            # not staged, a larger one is cut into pieces of at most 4 KiB,
            # eight to a program
            plans = [p for p in handler._staged.values() if p is not None]
            staged_leaf = kind not in ("scalar", "flat")
            # (a plan a device: the two stored shards are on two)
            assert len(plans) == 1 + records * staged_leaf
            assert d2h[4]["groups"] == 1 + records * (
                -(-leaf.nbytes // records // (8 * 4096))
            ) * staged_leaf
            assert all(
                size * leaf.dtype.itemsize <= 4096
                for plan in plans for _, sizes in plan.groups
                for size in sizes
            )
        # meta: three tensors in tree order, records back to back
        first, tensor, last = handler.load_meta().tensors
        assert [t.path for t in meta.tensors] == [
            first.path, tensor.path, last.path
        ]
        assert tensor.global_shape == leaf.shape and tensor.dtype == dtype
        assert len(tensor.shards) == records
        shards = [
            np.asarray(s.data) for s in leaf.addressable_shards
            if s.replica_id == 0
        ]
        offset = first.shards[0].nbytes
        for record, shard in zip(tensor.shards, shards):
            assert record.offset == offset
            assert record.nbytes == shard.nbytes
            assert record.shape == (shard.shape or (1,))
            offset += record.nbytes
        assert last.shards[0].offset == offset
        # arena bytes
        want = b"".join(
            [np.arange(3, dtype=np.int16).tobytes()]
            + [s.tobytes() for s in shards]
            + [np.ones((40, 50), np.float32).tobytes()]
        )
        assert bytes(handler.raw_data(meta)) == want
        # restore
        out = assemble_tensor(tensor, lambda r: handler.load_block(meta, r))
        assert out.dtype == leaf.dtype and out.shape == leaf.shape
        assert out.tobytes() == np.asarray(leaf).tobytes()
    finally:
        handler.close(unlink=True)


def test_staged_and_parent_layouts_restore_each_other(small_pieces, tap):
    """The arena the parent commit wrote (header, pickled meta, then the
    blocks of ``pack_pytree`` back to back) and the arena a staged save
    writes are the same bytes: each restores under the other's reader."""
    import pickle
    import struct

    from dlrover_tpu.checkpoint.shm_handler import pack_pytree

    state = {
        "w": jnp.asarray(
            np.arange(6 * 40 * 50).reshape(6, 40, 50) % 97, jnp.bfloat16
        ),
        "b": jnp.arange(300, dtype=jnp.float32),
        "step": jnp.int32(9),
        "host": np.arange(5),
    }
    handler = SharedMemoryHandler(f"pc{os.getpid()}")
    try:
        meta = handler.save_state_dict(state, step=9)
        (d2h,) = _named(tap.take(), "checkpoint.d2h")
        assert d2h[4]["path"] == "staged" and d2h[4]["groups"] == 1
        staged = bytes(handler.raw_data(meta))
        # the parent's writer, as it was
        parent_meta, blocks = pack_pytree(state, 9)
        parent = b"".join(
            np.ascontiguousarray(b).reshape(-1).view(np.uint8).tobytes()
            for b in blocks
        )
        assert staged == parent
        assert parent_meta.tensors == meta.tensors
        # the parent's reader over the staged arena: header, meta, offsets
        buf = handler._shm.buf
        (meta_len,) = struct.unpack("<Q", bytes(buf[:8]))
        read = pickle.loads(bytes(buf[8 : 8 + meta_len]))
        assert read.tensors == parent_meta.tensors and read.step == 9
        for tensor in read.tensors:
            out = assemble_tensor(
                tensor,
                lambda r: np.frombuffer(
                    buf, np.uint8, count=r.nbytes,
                    offset=8 + meta_len + r.offset,
                ),
            )
            name = tensor.path[0][2:-2]
            assert out.tobytes() == np.asarray(state[name]).tobytes()
            del out
    finally:
        handler.close(unlink=True)


def test_second_save_compiles_nothing(small_pieces):
    """The flatten programs of a state are compiled at its first save."""
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None
    )
    state = lambda k: {  # noqa: E731
        "w": jnp.full((6, 40, 50), k, jnp.bfloat16),
        "v": jnp.full((100, 33), k, jnp.float32),
    }
    handler = SharedMemoryHandler(f"sc{os.getpid()}")
    try:
        first = state(1)
        jax.block_until_ready(first)
        handler.save_state_dict(first, step=1)
        plans = dict(handler._staged)
        assert sum(len(p.programs) for p in plans.values()) >= 2
        second = state(2)
        jax.block_until_ready(second)
        before = len(compiles)
        handler.save_state_dict(second, step=2)
        assert len(compiles) == before
        assert handler._staged == plans
        assert handler.last_d2h["path"] == "staged"
    finally:
        handler.close(unlink=True)


def test_a_device_error_in_the_pipeline_falls_back(small_pieces, tap,
                                                   monkeypatch):
    state = {"w": jnp.ones((6, 40, 50), jnp.bfloat16)}
    handler = SharedMemoryHandler(f"de{os.getpid()}")

    def broken(*a, **k):
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: no HBM")

    monkeypatch.setattr(handler, "_stage", broken)
    try:
        meta = handler.save_state_dict(state, step=2)
        events = tap.take()
        (fallback,) = _named(events, "checkpoint.d2h_fallback")
        assert fallback[4]["reason"] == "device_error"
        (d2h,) = _named(events, "checkpoint.d2h")
        assert d2h[4]["path"] == "per_shard"
        out = assemble_tensor(
            meta.tensors[0], lambda r: handler.load_block(meta, r)
        )
        assert out.tobytes() == np.asarray(state["w"]).tobytes()
    finally:
        handler.close(unlink=True)


# -- the first save's one-time work, ahead of it ---------------------------------


def _described(state):
    """``state`` as a trainer knows it before it exists: every leaf a
    ``ShapeDtypeStruct`` under its sharding (a host leaf under none)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            np.shape(x), x.dtype, sharding=getattr(x, "sharding", None)
        ),
        state,
    )


def _mixed_state(k=1):
    """Replicated, sharded, sharded-and-replicated and scalar leaves."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("x", "y"))
    put = lambda x, *spec: jax.device_put(  # noqa: E731
        x, NamedSharding(mesh, P(*spec))
    )
    rows = np.arange(64 * 50).reshape(64, 50) % 97 + k
    return {
        "replicated": put(jnp.asarray(rows, jnp.bfloat16)),
        "sharded": put(jnp.asarray(rows, jnp.float32), "x", "y"),
        "sharded_replicated": put(jnp.asarray(rows, jnp.float32), "x", None),
        "columns": put(jnp.asarray(rows, jnp.int32), None, "y"),
        "scalar": put(jnp.int32(k)),
        "one_device": jnp.full((6, 40, 50), k, jnp.bfloat16),
        "host": np.arange(5, dtype=np.int16),
    }


def _join(thread):
    thread.join(timeout=60)
    assert not thread.is_alive()


def _compile_counter():
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None
    )
    return compiles


def _restored(handler, meta):
    return {
        t.path[0][2:-2]: assemble_tensor(
            t, lambda r: handler.load_block(meta, r)
        )
        for t in meta.tensors
    }


def _assert_restores(handler, meta, state):
    for name, out in _restored(handler, meta).items():
        assert out.dtype == state[name].dtype
        assert out.tobytes() == np.asarray(state[name]).tobytes(), name


def test_a_described_state_plans_the_records_of_the_state_itself():
    """``prepare`` reckons a first save from ``ShapeDtypeStruct``s: the
    records are the concrete state's, and jax's own ``replica_id`` picks
    the same shards."""
    import pickle

    from dlrover_tpu.checkpoint import shm_handler

    state = _mixed_state()
    meta, blocks = shm_handler._plan_pytree(state, 0, {"k": 1})
    described, abstract = shm_handler._plan_pytree(
        _described(state), 0, {"k": 1}
    )
    assert described.tensors == meta.tensors
    assert [
        (b.shape, np.dtype(b.dtype), shm_handler._device_of(b))
        for b in abstract
    ] == [
        (b.shape, np.dtype(b.dtype), shm_handler._device_of(b))
        for b in blocks
    ]
    for tensor, (name, leaf) in zip(meta.tensors, sorted(state.items())):
        if not isinstance(leaf, jax.Array):
            continue
        owned = [s for s in leaf.addressable_shards if s.replica_id == 0]
        assert [r.index for r in tensor.shards] == [
            shm_handler._slices_to_index(s.index, leaf.shape) for s in owned
        ], name
        assert [r.nbytes for r in tensor.shards] == [
            s.data.nbytes for s in owned
        ]
    assert [len(t.shards) for t in meta.tensors] == [2, 1, 1, 1, 1, 4, 2]
    handler = SharedMemoryHandler(f"pl{os.getpid()}")
    try:
        found = handler.prepare(_described(state), {"k": 1})
        # (equal metas pickle to within a few bytes: strings one of them
        # shares between records the other may hold twice)
        assert found["bytes"] == pytest.approx(
            8 + len(pickle.dumps(meta)) + sum(
                r.nbytes for t in meta.tensors for r in t.shards
            ), abs=64,
        )
    finally:
        handler.close(unlink=True)


@pytest.mark.parametrize("zero1", [False, True])
def test_a_step_program_describes_the_state_its_init_gives(zero1):
    """What a trainer hands ``prepare`` before any state exists
    (``ShardedTrain.abstract_state``) plans the records its first save
    will write, the ZeRO-1 optimizer state's too."""
    from dlrover_tpu.checkpoint import shm_handler
    from dlrover_tpu.models.gpt2 import gpt2_config
    from dlrover_tpu.models.transformer import TransformerLM
    from dlrover_tpu.parallel import rules as lr
    from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
    from dlrover_tpu.trainer import train_lib

    model = TransformerLM(gpt2_config(
        "124m", num_layers=2, d_model=64, num_heads=4, vocab_size=256,
        max_seq_len=64,
    ))
    train = train_lib.build_sharded_train(
        model, train_lib.make_optimizer("adamw", learning_rate=1e-2),
        build_mesh(ParallelConfig(data=4, fsdp=2)), lr.DEFAULT_RULES,
        global_batch_size=32, seq_len=16, zero1=zero1,
    )
    assert train.zero1 == zero1
    described, _ = shm_handler._plan_pytree(train.abstract_state(), 0, None)
    state = train.init(jax.random.PRNGKey(0))
    meta, blocks = shm_handler._plan_pytree(state, 0, None)
    assert described.tensors == meta.tensors
    assert any(len(t.shards) > 1 for t in meta.tensors)
    assert sum(r.nbytes for t in meta.tensors for r in t.shards) == sum(
        b.nbytes for b in blocks
    )


def test_a_prepared_first_save_is_a_later_save(small_pieces, tap):
    """After ``prepare`` the first save maps nothing, settles nothing and
    compiles nothing: it writes into pages that are there, through
    programs that are there."""
    compiles = _compile_counter()
    state = _mixed_state()
    jax.block_until_ready(state)
    handler = SharedMemoryHandler(f"pf{os.getpid()}")
    try:
        found = handler.prepare(_described(state), restart_count=0)
        events = tap.take()
        (prepare,) = _named(events, "checkpoint.prepare")
        assert prepare[4] == dict(prepare[4], **found)
        assert found["created"] is True and found["minflt"] >= 0
        assert prepare[4]["id"] == "restart:0"
        (arena,) = _named(events, "checkpoint.arena")
        assert arena[4] == dict(
            arena[4], ahead=True, created=True, bytes=found["bytes"],
            parent="checkpoint.prepare",
        )
        (settle,) = _named(events, "checkpoint.arena_settle")
        assert settle[4]["bytes"] == found["bytes"]
        assert settle[4]["parent"] == "checkpoint.prepare"
        # a plan for every kind of staged block, on every device that
        # holds one; the scalar and the host leaf have none to make
        plans = dict(handler._staged)
        assert found["programs"] == sum(
            len(p.programs) for p in plans.values() if p
        ) == len(_named(events, "compile.backend")) > 0
        assert all(
            e[4]["parent"] == "checkpoint.prepare"
            for e in _named(events, "compile.trace")
        )
        # the arena says "no checkpoint" until a save has published one
        assert handler.load_meta() is None
        assert not np.frombuffer(
            handler._shm.buf, np.uint8, count=found["bytes"]
        ).any()
        before = len(compiles)
        meta = handler.save_state_dict(state, step=1)
        events = tap.take()
        assert len(compiles) == before and handler._staged == plans
        assert not _named(events, "checkpoint.arena")
        assert not _named(events, "checkpoint.arena_settle")
        assert not [e for e in events if e[0].startswith("compile.")]
        (d2h,) = _named(events, "checkpoint.d2h")
        assert d2h[4]["path"] == "staged" and d2h[4]["groups"] > 0
        assert handler.load_meta().step == 1
        _assert_restores(handler, meta, state)
    finally:
        handler.close(unlink=True)


def test_preparing_over_a_published_checkpoint_only_reads_it(
    small_pieces, tap, tmp_path
):
    """A restart in place: the arena holds the only copy of the last
    acknowledged save, so the preparation attaches it, reads it, and
    leaves every byte and the header as they were; ``load`` restores it."""
    ckpt_dir = str(tmp_path / "ckpt")
    state = {k: v for k, v in _mixed_state().items() if k != "host"}
    dead = Checkpointer(ckpt_dir, host_index=0, num_hosts=1, local_saver=True)
    assert dead.save_checkpoint(5, state, StorageType.MEMORY)
    arena = dead._engine._shm._shm
    before = bytes(arena.buf)
    tap.take()
    # the trainer the agent starts in its place (same saver, same arena)
    ckpt = Checkpointer(ckpt_dir, host_index=0, num_hosts=1)
    try:
        ckpt.prepare(_described(state), restart_count=1)
        step, loaded = ckpt.load_checkpoint(state_template=state)
        _join(ckpt._engine._preparing)
        events = tap.take()
        assert step == 5
        for name, leaf in state.items():
            assert np.asarray(loaded[name]).tobytes() \
                == np.asarray(leaf).tobytes()
        assert bytes(arena.buf) == before
        (prepare,) = _named(events, "checkpoint.prepare")
        assert prepare[4]["created"] is False
        assert prepare[4]["id"] == "restart:1" and prepare[4]["programs"] > 0
        (mapped,) = _named(events, "checkpoint.arena")
        assert mapped[4] == dict(mapped[4], ahead=True, created=False)
        assert len(_named(events, "checkpoint.arena_settle")) == 1
        # ... and the next save goes into the arena that was attached
        state["scalar"] = state["scalar"] + 1
        assert ckpt.save_checkpoint(6, state, StorageType.MEMORY)
        assert ckpt.take_arena_wait() is not None
        assert ckpt.take_arena_wait() is None
        assert not _named(tap.take(), "checkpoint.arena")
        assert dead._engine._shm.load_meta().step == 6
    finally:
        ckpt.close()
        dead._engine._shm.close(unlink=True)
        dead.close()


@pytest.mark.parametrize("too_small", ["what_was_prepared", "the_arena_there"])
def test_a_state_that_needs_more_room_still_saves(too_small, small_pieces, tap):
    """The save that needs a larger arena makes it, as it always did: after
    a preparation for a smaller state, and after one that found an arena
    too small for its state and left it alone, since the restore may
    still want the checkpoint it holds."""
    name = f"pg{os.getpid()}{too_small}"
    small = {"w": jnp.ones((40, 50), jnp.float32)}
    grown = {"w": jnp.ones((1 << 10, 1 << 9), jnp.float32)}
    handler, dead = SharedMemoryHandler(name), SharedMemoryHandler(name)
    try:
        if too_small == "what_was_prepared":
            assert handler.prepare(_described(small))["created"] is True
        else:
            dead.save_state_dict(small, step=4)
            before = bytes(dead._shm.buf)
            tap.take()
            found = handler.prepare(_described(grown))
            assert found["created"] is False and found["programs"] > 0
            assert handler._shm is None and bytes(dead._shm.buf) == before
            (arena,) = _named(tap.take(), "checkpoint.arena")
            assert arena[4] == dict(arena[4], ahead=True, created=False)
            meta = handler.load_meta()
            assert meta.step == 4
            _assert_restores(handler, meta, small)
        tap.take()
        meta = handler.save_state_dict(grown, step=5)
        events = tap.take()
        (arena,) = _named(events, "checkpoint.arena")
        assert arena[4] == dict(arena[4], ahead=False, created=True)
        assert len(_named(events, "checkpoint.arena_settle")) == 1
        _assert_restores(handler, meta, grown)
    finally:
        dead.close()
        handler.close(unlink=True)


@pytest.mark.parametrize("why", ["error", "shm_busy"])
def test_a_skipped_preparation_leaves_the_first_save_as_it_was(
    why, small_pieces, tap, tmp_path, monkeypatch
):
    """No room in /dev/shm, a compile error, or the saver persisting the
    last save of the trainer before this one: the preparation says so and
    the first save makes the arena and its programs, as it always did."""
    compiles = _compile_counter()
    state = {k: v for k, v in _mixed_state().items() if k != "host"}
    jax.block_until_ready(state)
    ckpt = Checkpointer(
        str(tmp_path / "ckpt"), host_index=0, num_hosts=1, local_saver=True
    )
    engine = ckpt._engine
    held, leave = threading.Event(), threading.Event()

    def hold():
        # (as the saver does while it persists a step)
        engine._lock.acquire()
        held.set()
        leave.wait()
        engine._lock.release()

    holder = threading.Thread(target=hold)
    try:
        with monkeypatch.context() as patch:
            if why == "error":
                def no_room(total):
                    raise OSError(28, "No space left on device")

                patch.setattr(engine._shm, "_open_arena", no_room)
            else:
                holder.start()
                assert held.wait(timeout=60)
            ckpt.prepare(_described(state), restart_count=0)
            _join(engine._preparing)
            leave.set()
        events = tap.take()
        (skipped,) = _named(events, "checkpoint.prepare_skipped")
        assert skipped[1] == "event"
        assert skipped[4] == dict(skipped[4], reason=why, id="restart:0")
        assert not _named(events, "checkpoint.arena_settle")
        assert not engine._shm._staged and engine._shm._shm is None
        if why == "shm_busy":
            _join(holder)
        before = len(compiles)
        assert ckpt.save_checkpoint(1, state, StorageType.MEMORY)
        events = tap.take()
        assert len(compiles) > before
        (arena,) = _named(events, "checkpoint.arena")
        assert arena[4] == dict(arena[4], ahead=False, created=True)
        assert len(_named(events, "checkpoint.arena_settle")) == 1
        assert ckpt.take_arena_wait() == pytest.approx(0.0, abs=0.05)
        step, loaded = ckpt.load_checkpoint(state_template=state)
        assert step == 1
        for name, leaf in state.items():
            assert np.asarray(loaded[name]).tobytes() \
                == np.asarray(leaf).tobytes()
    finally:
        engine._shm.close(unlink=True)
        ckpt.close()


def test_checkpointer_memory_and_disk_cycle(tmp_path):
    ckpt_dir = str(tmp_path / "ckpt")
    ckpt = Checkpointer(ckpt_dir, host_index=0, num_hosts=1, local_saver=True)
    state = {
        "params": {"w": jnp.ones((4, 4)) * 3.0},
        "step": jnp.int32(11),
    }
    assert ckpt.save_checkpoint(11, state, StorageType.MEMORY)
    step, loaded = ckpt.load_checkpoint(state_template=state)
    assert step == 11
    np.testing.assert_allclose(loaded["params"]["w"], np.ones((4, 4)) * 3.0)

    state["step"] = jnp.int32(12)
    state["params"]["w"] = jnp.ones((4, 4)) * 4.0
    assert ckpt.save_checkpoint(12, state, StorageType.DISK)
    assert ckpt.wait(timeout=30)
    layout = CheckpointDirLayout(ckpt_dir)
    storage = PosixDiskStorage()
    assert layout.latest_step(storage) == 12

    # A fresh process-equivalent: new Checkpointer, shm gone -> storage load.
    ckpt._engine._shm.close(unlink=True)
    ckpt2 = Checkpointer(
        str(tmp_path / "ckpt"), host_index=0, num_hosts=1, local_saver=False
    )
    # reuse the running saver's queue/lock from ckpt's local saver
    step, loaded = ckpt2._engine.load_from_storage(
        treedef=jax.tree_util.tree_structure(state)
    )
    assert step == 12
    np.testing.assert_allclose(loaded["params"]["w"], np.ones((4, 4)) * 4.0)
    ckpt.close()


def test_restore_with_resharding(tmp_path):
    """Save under one sharding, restore under another (elastic resize)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    ckpt_dir = str(tmp_path / "ckpt")
    ckpt = Checkpointer(ckpt_dir, host_index=0, num_hosts=1, local_saver=True)
    mesh4 = Mesh(np.asarray(jax.devices()[:4]), ("x",))
    arr = jax.device_put(
        jnp.arange(16, dtype=jnp.float32).reshape(8, 2),
        NamedSharding(mesh4, PartitionSpec("x")),
    )
    assert ckpt.save_checkpoint(5, {"w": arr}, StorageType.DISK)
    assert ckpt.wait(timeout=30)

    mesh2 = Mesh(np.asarray(jax.devices()[:2]), ("x",))
    new_sharding = {"w": NamedSharding(mesh2, PartitionSpec(None, "x"))}
    step, state = ckpt.load_checkpoint(
        shardings=new_sharding, state_template={"w": arr}
    )
    assert step == 5
    assert state["w"].sharding.mesh.shape["x"] == 2
    np.testing.assert_array_equal(np.asarray(state["w"]), np.asarray(arr))
    ckpt.close()


def test_deletion_strategies(tmp_path):
    deleted = []
    keep_latest = KeepLatestStepStrategy(max_to_keep=2)
    for s in [10, 20, 30, 40]:
        keep_latest.clean_up(s, deleted.append)
    assert deleted == [10, 20]

    deleted = []
    keep_interval = KeepStepIntervalStrategy(keep_interval=100)
    for s in [50, 100, 150, 200]:
        keep_interval.clean_up(s, deleted.append)
    assert deleted == [50, 150]


def test_reader_reattaches_after_arena_growth():
    """Saver must not keep reading a stale mapping after the trainer
    recreates a larger arena (state grew between steps)."""
    name = f"g{os.getpid()}"
    writer = SharedMemoryHandler(name)
    writer.save_state_dict({"w": np.ones(8, np.float32)}, step=1)
    reader = SharedMemoryHandler(name)
    assert reader.load_meta().step == 1
    # Grow past the arena size -> writer unlinks + recreates.
    big = {"w": np.ones(1 << 19, np.float32), "v": np.ones(1 << 19)}
    writer.save_state_dict(big, step=2)
    meta = reader.load_meta()
    assert meta is not None and meta.step == 2
    writer.close(unlink=True)
    reader.close()


@pytest.mark.parametrize("second, settles_again", [
    ("same", False),      # the mapping is the first save's
    ("grown", True),      # unlink + create: a new mapping
    ("attached", True),   # another handler maps the arena that is there
])
def test_the_save_that_maps_the_arena_settles_its_pages(
    tap, second, settles_again
):
    """A mapping is read once, page by page, by the save that made it and
    wrote it first, so that no later save pays for its second pass."""
    name = f"st{os.getpid()}{second}"
    small = {"w": np.arange(5000, dtype=np.float32)}
    writer = SharedMemoryHandler(name)
    other = SharedMemoryHandler(name)
    try:
        meta = writer.save_state_dict(small, step=1)
        (settle,) = _named(tap.take(), "checkpoint.arena_settle")
        # header, meta and every byte of the state
        assert settle[4]["bytes"] > small["w"].nbytes
        assert writer.load_block(meta, meta.tensors[0].shards[0]).tobytes() \
            == small["w"].tobytes()
        if second == "grown":
            state = {"w": np.ones(1 << 19, np.float32)}
            saver = writer
        else:
            state = {"w": small["w"] + 1}
            saver = other if second == "attached" else writer
        meta = saver.save_state_dict(state, step=2)
        events = tap.take()
        assert len(_named(events, "checkpoint.arena_settle")) == int(
            settles_again
        )
        assert len(_named(events, "checkpoint.arena")) == int(settles_again)
        assert saver.load_block(meta, meta.tensors[0].shards[0]).tobytes() \
            == state["w"].tobytes()
    finally:
        other.close()
        writer.close(unlink=True)


class _Killed(BaseException):
    """Stands for a SIGKILL at the line that raises it."""


@pytest.mark.parametrize("killed_in", [
    "save", "preparation_of_a_new_arena", "preparation_of_an_arena_there",
])
def test_torn_write_is_invisible(killed_in, monkeypatch):
    """A crash mid-save must not leave a valid-looking checkpoint: the
    header is zeroed during the write and only published at the end.  A
    crash inside the preparation leaves "no checkpoint" in an arena it
    made, and an arena that was there as it was."""
    name = f"torn{os.getpid()}{killed_in}"
    state = {"w": np.arange(5000, dtype=np.float32)}
    handler = SharedMemoryHandler(name)
    try:
        if killed_in != "preparation_of_a_new_arena":
            handler.save_state_dict(state, step=1)
        if killed_in == "save":
            # Simulate death mid-write: corrupt by zeroing the header the
            # way save_state_dict does before copying blocks.
            import struct

            handler._shm.buf[:8] = struct.pack("<Q", 0)
            assert handler.load_meta() is None
            return
        before = None if handler._shm is None else bytes(handler._shm.buf)

        def killed(total):
            raise _Killed

        # (the pages are written, where they are, before they are settled)
        restarted = SharedMemoryHandler(name)
        monkeypatch.setattr(restarted, "_settle", killed)
        with pytest.raises(_Killed):
            restarted.prepare(_described(state))
        reader = SharedMemoryHandler(name)
        if before is None:
            assert reader.attach() and reader.load_meta() is None
        else:
            meta = reader.load_meta()
            assert meta.step == 1
            assert bytes(reader._shm.buf) == before
            assert reader.load_block(meta, meta.tensors[0].shards[0]) \
                .tobytes() == state["w"].tobytes()
        reader.close()
        restarted.close()
    finally:
        handler.close()
        last = SharedMemoryHandler(name)
        last.attach()
        last.close(unlink=True)


def test_saver_sigterm_persist_path(tmp_path):
    """save_shm_to_storage persists un-flushed shm (preemption path)."""
    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver

    ckpt_dir = str(tmp_path / "ckpt")
    saver = AsyncCheckpointSaver(ckpt_dir, host_index=0, num_hosts=1)
    # no saver.start(): simulate event loop not draining
    engine = CheckpointEngine(ckpt_dir, host_index=0, num_hosts=1)
    engine.save_to_memory(33, {"w": jnp.full((2, 2), 9.0)})
    assert saver.save_shm_to_storage()
    layout = CheckpointDirLayout(ckpt_dir)
    assert layout.latest_step(PosixDiskStorage()) == 33
    engine.close()
    saver.stop()


def test_sparse_host_ids_commit_and_restore(tmp_path, monkeypatch):
    """ADVICE high: after an elastic shrink the live hosts may be {1, 2} —
    the commit barrier must count actual done-files (not range(num_hosts)),
    the committer must be the lowest *live* host, and restore must enumerate
    the host files actually present."""
    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver

    ckpt_dir = str(tmp_path / "ckpt")
    savers, engines = {}, {}
    for host in (1, 2):
        savers[host] = AsyncCheckpointSaver(ckpt_dir, host_index=host)
        savers[host].set_world([1, 2])
        savers[host].start()
        engines[host] = CheckpointEngine(
            ckpt_dir, host_index=host, num_hosts=2,
            agree_step_fn=lambda c: c,
        )
    state = {"w": jnp.full((2, 2), 5.0)}
    for host in (1, 2):
        assert engines[host].save_to_storage(21, state)
    # Host 1 is the committer (lowest live id); host 2 only persists.
    assert engines[1].wait_saver(timeout=30)
    layout = CheckpointDirLayout(ckpt_dir)
    assert layout.latest_step(PosixDiskStorage()) == 21

    # Fresh-process restore: shm gone, storage globbed by actual host ids.
    for host in (1, 2):
        engines[host]._shm.close(unlink=True)
    fresh = CheckpointEngine(
        ckpt_dir, host_index=1, num_hosts=2, agree_step_fn=lambda c: c
    )
    step, loaded = fresh.load(treedef=jax.tree_util.tree_structure(state))
    assert step == 21
    np.testing.assert_allclose(loaded["w"], np.full((2, 2), 5.0))
    for host in (1, 2):
        savers[host].stop()


def test_restore_rejects_incomplete_step_and_falls_back(tmp_path):
    """ADVICE medium: a step with a missing host data file must not be
    restored from np.empty garbage — fall back to the older committed step."""
    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver

    ckpt_dir = str(tmp_path / "ckpt")
    saver = AsyncCheckpointSaver(ckpt_dir, host_index=0, num_hosts=1)
    saver.start()
    engine = CheckpointEngine(
        ckpt_dir, host_index=0, num_hosts=1, agree_step_fn=lambda c: c
    )
    good = {"w": jnp.full((3,), 1.0)}
    newer = {"w": jnp.full((3,), 2.0)}
    assert engine.save_to_storage(10, good)
    assert engine.wait_saver(timeout=30)
    assert engine.save_to_storage(20, newer)
    assert engine.wait_saver(timeout=30)

    layout = CheckpointDirLayout(ckpt_dir)
    os.remove(layout.data_path(20, 0, 1))
    engine._shm.close(unlink=True)
    step, loaded = engine.load_from_storage(
        treedef=jax.tree_util.tree_structure(good)
    )
    assert step == 10
    np.testing.assert_allclose(loaded["w"], np.full((3,), 1.0))
    saver.stop()


def test_world_agreed_step_overrides_newer_shm(tmp_path):
    """ADVICE medium: a surviving host whose shm holds step 30 must restore
    the world-agreed step 10 from storage, not its own newer shm."""
    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver

    ckpt_dir = str(tmp_path / "ckpt")
    saver = AsyncCheckpointSaver(ckpt_dir, host_index=0, num_hosts=1)
    saver.start()
    engine = CheckpointEngine(
        ckpt_dir, host_index=0, num_hosts=1, agree_step_fn=lambda c: 10
    )
    assert engine.save_to_storage(10, {"w": jnp.full((3,), 1.0)})
    assert engine.wait_saver(timeout=30)
    assert engine.save_to_memory(30, {"w": jnp.full((3,), 3.0)})
    step, loaded = engine.load(
        treedef=jax.tree_util.tree_structure({"w": jnp.zeros((3,))})
    )
    assert step == 10
    np.testing.assert_allclose(loaded["w"], np.full((3,), 1.0))
    engine._shm.close(unlink=True)
    saver.stop()


def test_lock_release_requires_owner_and_steals_from_dead(tmp_path):
    server = mp_ipc.SharedLock("ladv", create=True)
    client = mp_ipc.SharedLock("ladv", create=False)
    assert client.acquire()
    # ADVICE low: a release from a different owner (thread) is refused.
    stray: list = []
    t = threading.Thread(target=lambda: stray.append(server.release()))
    t.start(); t.join()
    assert stray == [False]
    assert server._lock.locked()
    assert client.release()
    # Dead-owner steal: lock held by a pid that no longer exists.
    assert client.acquire()
    server._owner = "999999999:1"
    other: list = []
    t = threading.Thread(
        target=lambda: other.append(server.acquire(blocking=False))
    )
    t.start(); t.join()
    assert other == [True]
    server.close()


@pytest.mark.slow
def test_flash_save_gb_scale_is_subsecond():
    """The Flash Checkpoint headline (BASELINE.md: 151s -> 0.5s saves) rests
    on the shm memcpy being fast: a ~1 GiB state must block the trainer for
    well under a second (round-2 verdict: measure it, don't assert it)."""
    import time

    state = {
        f"w{i}": np.ones((64, 1024, 1024), np.float32) for i in range(4)
    }  # 4 x 256 MiB = 1 GiB
    handler = SharedMemoryHandler(f"gb{os.getpid()}")
    try:
        handler.save_state_dict(state, step=1)  # first call sizes the arena
        t0 = time.perf_counter()
        handler.save_state_dict(state, step=2)
        dt = time.perf_counter() - t0
        gib = 2**30
        print(f"shm save of 1 GiB took {dt:.3f}s ({1 / max(dt, 1e-9):.1f} GiB/s)")
        assert dt < 1.0, f"1 GiB shm save took {dt:.2f}s (>1s)"
        meta = handler.load_meta()
        assert meta.step == 2
    finally:
        handler.close(unlink=True)


def test_forced_stop_leaves_shared_resources_open(tmp_path):
    """If the saver thread is wedged mid-persist past the forced-stop
    window, stop() must NOT close the shared queue/lock/status/shm under
    it — closing would corrupt the in-flight write or raise in the
    worker.  Leak the handles; the process is exiting anyway."""
    from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver

    saver = AsyncCheckpointSaver(
        str(tmp_path / "ckpt"), host_index=0, num_hosts=1
    )
    release = threading.Event()
    stuck = threading.Thread(target=release.wait, daemon=True)
    stuck.start()
    saver._thread = stuck  # a worker wedged inside a persist
    saver.DRAIN_TIMEOUT_S = 0.2  # instance attrs shadow the class windows
    saver.FORCED_JOIN_TIMEOUT_S = 0.2

    saver.stop()  # must return (leaking), not raise or hang

    assert stuck.is_alive()
    # The shared resources the "worker" may be holding are still usable.
    saver._status.update({"probe": 1})
    assert saver._status.get("probe") == 1
    assert saver._event_queue.get(timeout=1.0) is not None  # the EXIT event
    # Once the worker actually exits, a second stop() closes everything.
    release.set()
    stuck.join(timeout=5.0)
    saver.stop()
