"""Sparse embedding engine (KvVariable equivalent): store semantics, group
Adam, delta export, checkpoint replay, native/python parity, and a
wide-and-deep toy trained end-to-end with elastic restart."""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from dlrover_tpu.embedding import EmbeddingTable, KVStore
from dlrover_tpu.embedding.store import _load_native


def stores():
    out = [KVStore(8, native=False)]
    if _load_native() is not None:
        out.append(KVStore(8, native=True))
    return out


def test_native_library_builds():
    assert _load_native() is not None, (
        "native kv_store failed to build — g++ is expected in this image"
    )


def test_lookup_inserts_deterministically_and_counts():
    for store in stores():
        keys = np.array([5, 9, 5], np.int64)
        rows = store.lookup(keys, init_scale=0.1, seed=7, step=1)
        assert rows.shape == (3, 8)
        np.testing.assert_array_equal(rows[0], rows[2])  # same key, same row
        assert np.abs(rows).max() <= 0.1
        assert len(store) == 2
        again = store.lookup(np.array([5], np.int64), 0.1, 7, step=2)
        np.testing.assert_array_equal(again[0], rows[0])
        _, _, _, _, counts, steps = store.export()
        assert sorted(counts.tolist()) == [1, 3]
        assert steps.max() == 2


def test_capacity_growth_beyond_initial():
    store = KVStore(4, initial_capacity=64)
    keys = np.arange(10_000, dtype=np.int64)
    store.lookup(keys, 0.05, 0, 1)
    assert len(store) == 10_000
    row = store.peek(np.array([1234], np.int64))
    assert np.abs(row).max() > 0  # row survived the rehashes


def test_group_adam_matches_optax_dense():
    """The in-store sparse Adam must match optax.adam on the same rows."""
    for store in stores():
        keys = np.array([3, 8], np.int64)
        rows = store.lookup(keys, 0.1, 1, 1)
        params = jnp.asarray(rows)
        opt = optax.adam(0.05, b1=0.9, b2=0.999, eps=1e-8)
        state = opt.init(params)
        rng = np.random.default_rng(0)
        for t in range(1, 4):
            grads = rng.normal(size=(2, 8)).astype(np.float32)
            updates, state = opt.update(jnp.asarray(grads), state, params)
            params = optax.apply_updates(params, updates)
            store.apply_group_adam(keys, grads, lr=0.05, t=t)
        np.testing.assert_allclose(
            store.peek(keys), np.asarray(params), rtol=1e-5, atol=1e-6
        )


def test_peek_does_not_insert():
    for store in stores():
        out = store.peek(np.array([42], np.int64))
        np.testing.assert_array_equal(out, 0.0)
        assert len(store) == 0


def test_delta_export_only_recent_keys():
    for store in stores():
        store.lookup(np.array([1, 2], np.int64), 0.1, 0, step=1)
        store.lookup(np.array([3], np.int64), 0.1, 0, step=5)
        keys_all, *_ = store.export(min_step=0)
        keys_delta, *_ = store.export(min_step=5)
        assert sorted(keys_all.tolist()) == [1, 2, 3]
        assert keys_delta.tolist() == [3]


def test_eviction_drops_cold_stale_features():
    for store in stores():
        store.lookup(np.array([1], np.int64), 0.1, 0, step=1)
        store.lookup(np.array([2], np.int64), 0.1, 0, step=10)
        evicted = store.evict(min_step=5, min_count=2)
        assert evicted == 1
        assert len(store) == 1
        assert store.peek(np.array([2], np.int64)).any()


def test_native_python_parity_full_flow():
    if _load_native() is None:
        pytest.skip("no native build")
    native = KVStore(8, native=True)
    pure = KVStore(8, native=False)
    keys = np.array([11, 22, 33], np.int64)
    rows_n = native.lookup(keys, 0.1, 3, 1)
    pure.insert(keys, rows_n)  # same starting rows (init RNGs differ)
    grads = np.random.default_rng(1).normal(size=(3, 8)).astype(np.float32)
    native.apply_group_adam(keys, grads, lr=0.1, t=1)
    pure.apply_group_adam(keys, grads, lr=0.1, t=1)
    np.testing.assert_allclose(
        native.peek(keys), pure.peek(keys), rtol=1e-5, atol=1e-6
    )


def test_table_checkpoint_full_plus_delta_replay(tmp_path):
    table = EmbeddingTable("emb", dim=8, learning_rate=0.1, seed=2)
    rows, uniq, inv = table.lookup(np.array([[1, 2], [3, 1]], np.int64))
    assert rows.shape == (3, 8) and inv.shape == (4,)
    table.apply_gradients(uniq, np.ones((3, 8), np.float32))
    table.save(str(tmp_path), step=1)
    # More training -> delta with only the newly-touched key.
    rows2, uniq2, _ = table.lookup(np.array([7], np.int64))
    table.apply_gradients(uniq2, np.ones((1, 8), np.float32))
    table.save(str(tmp_path), step=2, delta=True)

    fresh = EmbeddingTable("emb", dim=8, learning_rate=0.1, seed=2)
    fresh.restore(str(tmp_path))
    assert len(fresh) == 4
    np.testing.assert_allclose(
        fresh.store.peek(np.array([1, 2, 3, 7], np.int64)),
        table.store.peek(np.array([1, 2, 3, 7], np.int64)),
        rtol=1e-6,
    )


def test_native_build_retries_once_before_latching(monkeypatch, tmp_path):
    """A transient compiler failure must not permanently demote the
    process to the NumPy fallback: the first failed build leaves the
    latch open, the next ``_load_native`` retries and succeeds, and only
    two consecutive failures set ``_lib_failed``."""
    import subprocess as real_subprocess

    from dlrover_tpu.embedding import store

    real_run = real_subprocess.run
    calls = {"n": 0}

    def flaky_run(cmd, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise real_subprocess.CalledProcessError(
                1, cmd, stderr="cc1plus: out of memory"
            )
        return real_run(cmd, **kw)

    # Fresh module state pointed at a build dir that forces a build.
    monkeypatch.setattr(store, "_BUILD_DIR", str(tmp_path))
    monkeypatch.setattr(store, "_lib", None)
    monkeypatch.setattr(store, "_lib_failed", False)
    monkeypatch.setattr(store, "_build_attempts", 0)
    monkeypatch.setattr(store.subprocess, "run", flaky_run)

    assert store._load_native() is None      # first build fails...
    assert store._lib_failed is False        # ...but does NOT latch
    lib = store._load_native()               # retry rebuilds for real
    assert lib is not None and calls["n"] == 2
    assert store._load_native() is lib       # cached, no third build


def test_native_build_latches_after_two_failures(monkeypatch, tmp_path):
    import subprocess as real_subprocess

    from dlrover_tpu.embedding import store

    def always_fail(cmd, **kw):
        raise real_subprocess.CalledProcessError(1, cmd, stderr="boom")

    monkeypatch.setattr(store, "_BUILD_DIR", str(tmp_path))
    monkeypatch.setattr(store, "_lib", None)
    monkeypatch.setattr(store, "_lib_failed", False)
    monkeypatch.setattr(store, "_build_attempts", 0)
    monkeypatch.setattr(store.subprocess, "run", always_fail)

    assert store._load_native() is None
    assert store._lib_failed is False
    assert store._load_native() is None
    assert store._lib_failed is True         # second failure latches
    # Latched: further calls return immediately without building.
    assert store._load_native() is None
    # The fallback store still works under the latch.
    fallback = KVStore(4)
    assert fallback.native is False
    fallback.lookup(np.array([1], np.int64), 0.1, 0, 1)
    assert len(fallback) == 1


def test_store_remove_deletes_keys_both_backends():
    """Targeted deletion (the reshard migration's remove leg): removed
    keys vanish, survivors keep their rows — including keys that shared
    a probe chain with the victim (backward-shift correctness)."""
    for store in stores():
        keys = np.arange(64, dtype=np.int64)
        before = store.lookup(keys, 0.1, 5, 1)
        removed = store.remove(np.array([3, 9, 63, 777], np.int64))
        assert removed == 3  # 777 was never inserted
        assert len(store) == 61
        np.testing.assert_array_equal(
            store.peek(np.array([3, 9, 63], np.int64)), 0.0
        )
        survivors = np.array(
            [k for k in range(64) if k not in (3, 9, 63)], np.int64
        )
        np.testing.assert_array_equal(
            store.peek(survivors), before[survivors]
        )


def test_wide_and_deep_toy_trains_with_restart(tmp_path):
    """End-to-end recsys slice: sparse table + dense tower trained jointly;
    kill mid-run, restore both halves, loss keeps falling (the verdict's
    'wide-and-deep toy trains with elastic restart')."""
    rng = np.random.default_rng(0)
    n_features, dim = 50, 8

    def make_batch():
        feats = rng.integers(0, n_features, size=(16, 3)).astype(np.int64)
        # Ground truth depends on feature identity: learnable signal.
        label = ((feats.sum(axis=1) % 7) / 7.0).astype(np.float32)
        return feats, label

    def dense_apply(w, emb_rows, inv, feats_shape):
        gathered = emb_rows[inv].reshape(*feats_shape, dim)
        pooled = gathered.mean(axis=1)
        return (pooled @ w).squeeze(-1)

    from functools import partial

    @partial(jax.jit, static_argnums=(4, 5))
    def step_fn(w, emb_rows, inv, label, shape0, shape1):
        def loss_fn(w, emb_rows):
            pred = dense_apply(w, emb_rows, inv, (shape0, shape1))
            return jnp.mean((pred - label) ** 2)

        loss, (dw, drows) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            w, emb_rows
        )
        return loss, dw, drows

    def train(table, w, steps, opt_state, opt):
        losses = []
        for _ in range(steps):
            feats, label = make_batch()
            rows, uniq, inv = table.lookup(feats)
            loss, dw, drows = step_fn(
                w, jnp.asarray(rows), jnp.asarray(inv),
                jnp.asarray(label), *feats.shape,
            )
            updates, opt_state = opt.update(dw, opt_state, w)
            w = optax.apply_updates(w, updates)
            table.apply_gradients(uniq, np.asarray(drows))
            losses.append(float(loss))
        return w, opt_state, losses

    table = EmbeddingTable("wd", dim=dim, learning_rate=0.05, seed=1)
    w = jnp.zeros((dim, 1), jnp.float32)
    opt = optax.adam(0.05)
    opt_state = opt.init(w)
    w, opt_state, losses1 = train(table, w, 30, opt_state, opt)
    table.save(str(tmp_path), step=30)
    np.save(tmp_path / "w.npy", np.asarray(w))

    # "Crash": rebuild everything from the checkpoint, keep training.
    table2 = EmbeddingTable("wd", dim=dim, learning_rate=0.05, seed=1)
    table2.restore(str(tmp_path))
    assert len(table2) == len(table)
    w2 = jnp.asarray(np.load(tmp_path / "w.npy"))
    opt_state2 = opt.init(w2)
    _, _, losses2 = train(table2, w2, 30, opt_state2, opt)
    assert np.mean(losses2[-5:]) < np.mean(losses1[:5]), (
        "loss did not improve across the restart"
    )
