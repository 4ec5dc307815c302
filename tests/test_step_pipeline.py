"""Async step pipeline (ISSUE 2): device prefetch, deferred metrics,
restart-fast compile.

Covers the three pipeline contracts on the virtual CPU mesh:

* **Overlap/order** — the DevicePrefetcher issues batch N+1's placement
  before batch N is handed out (and before step N's metrics are fetched),
  preserves order, and keeps the loader's ack-after-consume semantics.
* **Sync budget** — a pipelined fit performs ZERO per-step synchronous
  metric fetches (<= 1 blocking sync per ``metrics_lag`` steps, all of
  them "metrics-flush" blocks), with exact numeric parity and correct
  step attribution vs the synchronous loop.
* **Restart-fast compile** — a second trainer with identical
  (config, mesh-shape) reuses the compiled program with zero retraces,
  and the compile event lands in the master's goodput ledger with restart
  time booked separately.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dlrover_tpu.data.loader import DevicePrefetcher, ElasticDataLoader
from dlrover_tpu.models.gpt2 import gpt2_config
from dlrover_tpu.trainer import train_lib
from dlrover_tpu.trainer.elastic_trainer import (
    ElasticTrainer,
    TrainerConfig,
)
from dlrover_tpu.utils.profiler import pipeline_counters

import trace_asserts

BATCH, SEQ = 8, 32


@pytest.fixture(autouse=True)
def _isolated_shm(monkeypatch, tmp_path):
    monkeypatch.setenv("DLROVER_TPU_JOB", f"sp{os.getpid()}_{tmp_path.name}")
    monkeypatch.setenv("DLROVER_TPU_SOCKET_DIR", str(tmp_path / "socks"))


def _tiny_trainer(vocab=128, **cfg_kwargs):
    model_config = gpt2_config(
        "124m", num_layers=2, d_model=64, num_heads=2, vocab_size=vocab,
        max_seq_len=SEQ, param_dtype=jnp.float32,
    )
    cfg_kwargs.setdefault("report_every", 2)
    cfg = TrainerConfig(
        global_batch_size=BATCH, seq_len=SEQ, learning_rate=1e-2,
        ckpt_every=1000, **cfg_kwargs,
    )
    return ElasticTrainer(model_config, cfg, client=None)


def _batches(n, vocab=128, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        toks = rng.integers(0, vocab, size=(BATCH, SEQ + 1), dtype=np.int32)
        out.append({
            "inputs": toks[:, :-1].copy(), "targets": toks[:, 1:].copy(),
        })
    return out


# ---------------------------------------------------------------------------
# DevicePrefetcher
# ---------------------------------------------------------------------------


def test_prefetcher_preserves_order_and_places_ahead():
    n = 6
    placed = []

    def place(batch):
        placed.append(batch["i"])
        return batch

    pf = DevicePrefetcher([{"i": i} for i in range(n)], place, depth=2)
    placed_at_yield = []
    got = []
    for batch in pf:
        placed_at_yield.append(len(placed))
        got.append(batch["i"])
    assert got == list(range(n))            # order preserved
    assert placed == list(range(n))         # each batch placed exactly once
    for k, n_placed in enumerate(placed_at_yield):
        # When batch k is handed out, batch k+1 (at least) has already
        # been placed — the H2D-overlaps-compute contract.
        assert n_placed >= min(k + 2, n)


def test_prefetcher_reiterable_and_clean_shutdown():
    src = [{"i": i} for i in range(5)]
    pf = DevicePrefetcher(src, lambda b: b, depth=3)
    first = []
    for batch in pf:
        first.append(batch["i"])
        if batch["i"] == 1:
            break                            # abandon mid-pipeline
    assert first == [0, 1]
    assert [b["i"] for b in pf] == [0, 1, 2, 3, 4]  # fresh full pass


class _FakeTaskMaster:
    def __init__(self, num_shards, shard_size):
        self.tasks = [
            type("T", (), dict(
                task_id=i, start=i * shard_size, end=(i + 1) * shard_size,
                empty=False, epoch=0, dataset_name="d",
            ))()
            for i in range(num_shards)
        ]
        self.done = []

    def create_dataset(self, params):
        pass

    def get_task(self, name):
        if self.tasks:
            return self.tasks.pop(0)
        return type("T", (), dict(task_id=-1, empty=True))()

    def report_task(self, name, task_id, success):
        self.done.append(task_id)


def test_prefetcher_ack_only_after_consume():
    """Device-buffering a batch must NOT ack its shards — only the
    consumer coming back for the next batch proves batch N was trained."""
    from dlrover_tpu.data.sharding_client import ShardingClient

    fake = _FakeTaskMaster(num_shards=4, shard_size=8)
    loader = ElasticDataLoader(
        lambda i: {"x": np.asarray([i])}, batch_size=8,
        source=ShardingClient(fake, "d", create=False), prefetch=2,
    )
    pf = DevicePrefetcher(loader, lambda b: b, depth=2)
    it = iter(pf)
    next(it)   # batch 0 handed out (batches 1-2 already device-buffered)
    assert fake.done == []
    next(it)   # consumer came back: batch 0 consumed -> shard 0 acks
    assert fake.done == [0]
    it.close()  # abandon: buffered-but-unconsumed shards stay unacked
    assert fake.done == [0]

    fake2 = _FakeTaskMaster(num_shards=3, shard_size=8)
    loader2 = ElasticDataLoader(
        lambda i: {"x": np.asarray([i])}, batch_size=8,
        source=ShardingClient(fake2, "d", create=False), prefetch=2,
    )
    assert len(list(DevicePrefetcher(loader2, lambda b: b, depth=2))) == 3
    assert sorted(fake2.done) == [0, 1, 2]


def test_threaded_loader_generation_token_reiteration():
    """Abandoning a threaded iteration mid-pass must not let its producer
    leak items into (or consume source for) the next iteration."""
    loader = ElasticDataLoader(
        lambda i: {"x": np.asarray([i])}, batch_size=4,
        source=list(range(16)), prefetch=2,
    )
    it = iter(loader)
    first = next(it)
    assert list(first["x"].reshape(-1)) == [0, 1, 2, 3]
    it.close()  # producer of generation 1 must stand down
    gen_after_first = loader._generation
    assert gen_after_first == 1
    batches = list(loader)  # generation 2: a clean, complete pass
    assert loader._generation == 2
    flat = [int(v) for b in batches for v in b["x"].reshape(-1)]
    assert flat == list(range(16))


# ---------------------------------------------------------------------------
# Deferred metrics: sync budget, ordering, parity
# ---------------------------------------------------------------------------


def test_pipelined_fit_sync_budget_and_place_order():
    trainer = _tiny_trainer(
        metrics_lag=3, prefetch_to_device=2, report_every=1,
    )
    counters = pipeline_counters()
    counters.reset()
    trainer.fit(_batches(6), max_steps=6)
    summary = counters.summary()
    # ZERO per-step synchronous fetches; <= 1 blocking sync per lag steps.
    assert summary["sync_block_count"] == 0
    assert summary["flush_block_count"] == 2      # 6 steps / lag 3
    assert summary["host_block_count"] <= 6 // 3
    assert summary["dispatch_count"] == 6
    assert summary["place_count"] == 6
    # Order: batch N+1's device_put was issued before step N's metrics
    # were fetched.  The first block covers steps 1..3, so placements for
    # batches 1..4 (at least) must precede it in the event log.
    events = counters.events
    first_block = next(
        i for i, e in enumerate(events) if e.kind == "block"
    )
    covered = max(events[first_block].steps)
    places_before = sum(
        1 for e in events[:first_block] if e.kind == "place"
    )
    assert places_before >= covered + 1


def test_lagged_parity_with_sync_loop():
    """Same seed, same batches: the pipelined loop must report the exact
    losses of the synchronous loop, attributed to the exact same steps."""
    batches = _batches(6, seed=3)

    def run(**cfg):
        trainer = _tiny_trainer(**cfg)
        seen = []

        def on_step(step, metrics):
            seen.append((step, float(metrics["loss"])))

        trainer.fit(batches, max_steps=6, on_step=on_step)
        params = jax.device_get(
            jax.tree_util.tree_leaves(trainer.state.params)
        )
        return seen, params

    sync_seen, sync_params = run(metrics_lag=0, prefetch_to_device=0)
    lag_seen, lag_params = run(metrics_lag=4, prefetch_to_device=2)
    assert [s for s, _ in sync_seen] == [s for s, _ in lag_seen]
    for (s0, l0), (s1, l1) in zip(sync_seen, lag_seen):
        assert s0 == s1
        np.testing.assert_allclose(l0, l1, rtol=0, atol=0)
    for a, b in zip(sync_params, lag_params):
        np.testing.assert_array_equal(a, b)


def test_flush_on_eval_and_final_step_drains_ring():
    events = []

    class Rec:
        def on_train_begin(self, t):
            pass

        def on_step_end(self, t, step, metrics):
            events.append(("step", step, float(metrics["loss"])))

        def on_evaluate(self, t, step, m):
            events.append(("eval", step))

        def on_checkpoint(self, t, step):
            pass

        def on_epoch_end(self, t, epoch):
            pass

        def on_train_end(self, t, step):
            events.append(("end", step))

    trainer = _tiny_trainer(
        metrics_lag=10, prefetch_to_device=1, report_every=1,
        eval_every=3, eval_batches=2,
    )
    trainer.callbacks.append(Rec())
    trainer.fit(
        _batches(5), max_steps=5, eval_loader=_batches(2, seed=9),
    )
    # The eval at step 3 forces a flush: steps 1..3 must be delivered (in
    # order) before the eval event, despite lag 10 > 5 total steps.
    kinds = [e[0] for e in events]
    eval_at = kinds.index("eval")
    assert [e[1] for e in events[:eval_at] if e[0] == "step"] == [1, 2, 3]
    # End-of-fit barrier drains the rest before on_train_end.
    step_events = [e for e in events if e[0] == "step"]
    assert [e[1] for e in step_events] == [1, 2, 3, 4, 5]
    assert all(np.isfinite(e[2]) for e in step_events)
    assert kinds[-1] == "end"


def test_eval_accumulates_on_device_single_fetch():
    trainer = _tiny_trainer()
    counters = pipeline_counters()
    counters.reset()
    out = trainer.evaluate(_batches(3, seed=5), max_batches=3)
    assert out["eval_batches"] == 3
    assert np.isfinite(out["eval_loss"])
    assert out["eval_tokens"] > 0
    # One blocking fetch for the whole eval pass, no per-batch syncs.
    assert len(counters.blocks("eval-fetch")) == 1
    assert counters.sync_block_count() == 0


# ---------------------------------------------------------------------------
# Restart-fast compile
# ---------------------------------------------------------------------------


def test_second_trainer_zero_retraces():
    train_lib.reset_build_cache()
    t1 = _tiny_trainer(vocab=96)
    t1.fit(_batches(2, vocab=96), max_steps=2)
    assert train_lib.trace_count("train_step") >= 1
    with trace_asserts.assert_no_retrace("train_step", "init"):
        t2 = _tiny_trainer(vocab=96)   # identical (config, mesh-shape)
        assert t2.train is t1.train    # in-process program reuse
        t2.fit(_batches(2, vocab=96), max_steps=2)  # ZERO retraces


class _FakeClient:
    def __init__(self):
        self.events = []
        self.steps = []

    def report_event(self, event, detail=""):
        self.events.append((event, detail))

    def report_step(self, step, tokens=0, loss=0.0, anomalies=()):
        self.steps.append(step)


def _warmup_trainer(client):
    return ElasticTrainer(
        gpt2_config(
            "124m", num_layers=2, d_model=64, num_heads=2, vocab_size=80,
            max_seq_len=SEQ, param_dtype=jnp.float32,
        ),
        TrainerConfig(
            global_batch_size=BATCH, seq_len=SEQ, warmup_compile=True,
            ckpt_every=1000,
        ),
        client=client,
    )


def test_warmup_compile_reports_goodput_event(monkeypatch):
    train_lib.reset_build_cache()
    client = _FakeClient()
    _warmup_trainer(client)
    compile_events = [e for e in client.events if e[0] == "compile"]
    assert len(compile_events) == 1
    detail = json.loads(compile_events[0][1])
    assert detail["seconds"] > 0
    assert detail["restart"] is False
    assert detail["cached"] is False
    # A "restarted" trainer with the same (config, mesh-shape): cached,
    # zero compile seconds, restart flag from the agent env.
    monkeypatch.setenv("DLROVER_TPU_RESTART_COUNT", "1")
    client2 = _FakeClient()
    _warmup_trainer(client2)
    detail2 = json.loads(client2.events[0][1])
    assert detail2["cached"] is True
    assert detail2["seconds"] == 0.0
    assert detail2["restart"] is True


def test_persistent_compile_cache_configured(tmp_path, monkeypatch):
    from dlrover_tpu.runtime import compile_cache

    # Record what enable() would set instead of setting it: a cache left on
    # for the rest of the session would hand later CPU processes this one's
    # executables, which is the unsound reuse the CPU gate exists against.
    written = {}
    monkeypatch.setattr(jax.config, "update", written.__setitem__)
    monkeypatch.setattr(compile_cache, "_listening", True)
    monkeypatch.setattr(compile_cache, "_enabled_dir", None)
    monkeypatch.setenv(compile_cache.ENV_JAX_CACHE_DIR, str(tmp_path))
    # On the CPU backend maybe_enable declines (cross-process reuse of
    # persisted CPU executables crashes a resumed trainer); the dedicated
    # opt-in env lets single-process plumbing tests through.
    monkeypatch.delenv(compile_cache.ENV_COMPILE_CACHE_CPU_OK, raising=False)
    assert compile_cache.maybe_enable() is None
    assert compile_cache.enabled_dir() is None and not written
    monkeypatch.setenv(compile_cache.ENV_COMPILE_CACHE_CPU_OK, "1")
    enabled = compile_cache.maybe_enable()
    assert enabled == str(tmp_path) == compile_cache.enabled_dir()
    # Every program is cached, however small or quick to compile.
    assert written == {
        "jax_persistent_cache_min_entry_size_bytes": -1,
        "jax_persistent_cache_min_compile_time_secs": 0,
    }
    # Idempotent: the first resolution holds for the process.
    monkeypatch.delenv(compile_cache.ENV_JAX_CACHE_DIR)
    assert compile_cache.enable() == enabled
    assert set(compile_cache.stats()) == {"hits", "misses"}


def test_train_cache_key_sensitivity():
    from dlrover_tpu.runtime import compile_cache

    cfg_a = gpt2_config("124m", num_layers=2, d_model=64, num_heads=2,
                        vocab_size=128, max_seq_len=SEQ)
    cfg_b = gpt2_config("124m", num_layers=2, d_model=64, num_heads=2,
                        vocab_size=128, max_seq_len=SEQ)
    key = compile_cache.train_cache_key(
        cfg_a, (8, 1), global_batch_size=8, seq_len=SEQ, optimizer="adamw"
    )
    assert key == compile_cache.train_cache_key(
        cfg_b, (8, 1), global_batch_size=8, seq_len=SEQ, optimizer="adamw"
    )
    # Any program-shaping difference must miss.
    assert key != compile_cache.train_cache_key(
        cfg_b, (4, 2), global_batch_size=8, seq_len=SEQ, optimizer="adamw"
    )
    assert key != compile_cache.train_cache_key(
        cfg_b, (8, 1), global_batch_size=16, seq_len=SEQ, optimizer="adamw"
    )
    assert key != compile_cache.train_cache_key(
        cfg_b, (8, 1), global_batch_size=8, seq_len=SEQ, optimizer="sgd"
    )


# ---------------------------------------------------------------------------
# Goodput ledger: master side
# ---------------------------------------------------------------------------


def test_speed_monitor_compile_ledger():
    from dlrover_tpu.master.speed_monitor import SpeedMonitor

    sm = SpeedMonitor()
    sm.record_compile(2.0)
    sm.record_compile(0.5, restart=True)
    sm.record_compile(0.0, restart=True, cached=True)
    ledger = sm.compile_ledger()
    assert ledger["compile_s"] == pytest.approx(2.5)
    assert ledger["restart_compile_s"] == pytest.approx(0.5)
    assert ledger["compile_events"] == 3
    assert ledger["restart_compiles"] == 2
    assert ledger["cached_compiles"] == 1


def test_servicer_routes_compile_event_to_ledger():
    from dlrover_tpu.master import messages as msg
    from dlrover_tpu.master.servicer import MasterServicer
    from dlrover_tpu.master.speed_monitor import SpeedMonitor

    sm = SpeedMonitor()
    servicer = MasterServicer(speed_monitor=sm)
    resp = servicer.report(msg.Envelope(
        node_id=0,
        payload=msg.NodeEventReport(
            node_id=0, event="compile",
            detail=json.dumps(
                {"seconds": 1.5, "restart": True, "cached": False}
            ),
        ),
    ))
    assert resp.success
    ledger = sm.compile_ledger()
    assert ledger["restart_compile_s"] == pytest.approx(1.5)
    assert ledger["restart_compiles"] == 1
    # Malformed detail must not fail the RPC nor corrupt the ledger.
    resp = servicer.report(msg.Envelope(
        node_id=0,
        payload=msg.NodeEventReport(
            node_id=0, event="compile", detail="not json",
        ),
    ))
    assert resp.success
    assert sm.compile_ledger()["compile_events"] == 1


# ---------------------------------------------------------------------------
# tools/trace_steps.py — the tier-1 pipelined-mode assertion
# ---------------------------------------------------------------------------


@pytest.mark.slow  # subprocess jax import + compile, ~5s on 1 core
def test_trace_steps_tool_zero_syncs_in_pipelined_mode():
    from tools.trace_steps import run_trace

    out = run_trace(steps=4, metrics_lag=2, prefetch=2, report_every=1)
    assert out["mode"] == "pipelined"
    assert out["summary"]["sync_block_count"] == 0
    assert out["summary"]["flush_block_count"] == 2
    assert [row["step"] for row in out["per_step"]] == [1, 2, 3, 4]
    assert all(row["sync_blocks"] == 0 for row in out["per_step"])
    # The synchronous baseline, for contrast, blocks every reported step.
    sync = run_trace(steps=3, metrics_lag=0, prefetch=0, report_every=1)
    assert sync["mode"] == "sync"
    assert sync["summary"]["sync_block_count"] == 3
