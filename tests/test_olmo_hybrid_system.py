"""The layer pattern through the rest of the system, one small CPU test
each: ZeRO-1 on four host devices, a Flash Checkpoint save and restore of
the patterned tree, a live relayout 4 -> 2, and the ``linear_attn`` event
with its gauges."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import linear_attention
from dlrover_tpu.models.olmo_hybrid import olmo_hybrid_config
from dlrover_tpu.models.transformer import TransformerLM
from dlrover_tpu.parallel import rules as lr
from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
from dlrover_tpu.trainer import train_lib

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs four host devices"
)

SEQ, BATCH, VOCAB = 32, 8, 128


def config(**overrides):
    base = dict(
        vocab_size=VOCAB, num_layers=8, d_model=32, num_heads=4, d_ff=64,
        linear_num_heads=4, linear_key_head_dim=8, linear_value_head_dim=16,
        max_seq_len=SEQ, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    base.update(overrides)
    return olmo_hybrid_config(**base)


def batches(n, seed=0):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, VOCAB, (n, BATCH, SEQ + 1), dtype=np.int32)
    return [{"inputs": r[:, :-1], "targets": r[:, 1:]} for r in rows]


def build(devices, parallel, **kw):
    mesh = build_mesh(parallel, devices=jax.devices()[:devices])
    return train_lib.build_sharded_train(
        TransformerLM(config()),
        train_lib.make_optimizer("adafactor", learning_rate=1e-2),
        mesh, lr.DEFAULT_RULES, global_batch_size=BATCH, seq_len=SEQ, **kw,
    )


def run(train, steps=3):
    state = train.init(jax.random.PRNGKey(0))
    losses, stats = [], []
    for batch in batches(steps):
        state, metrics = train.step(
            state, train_lib.shard_batch(batch, train)
        )
        losses.append(float(metrics["loss"]))
        stats.append(np.asarray(metrics[linear_attention.STATS_NAME]))
    return state, losses, stats


def test_zero1_on_four_devices_trains_to_the_single_device_losses():
    one = build(1, ParallelConfig(data=1))
    four = build(4, ParallelConfig(data=2, fsdp=2), zero1=True)
    assert four.zero1
    _, want, want_stats = run(one)
    state, got, got_stats = run(four)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    # the stats fold over the batch's shards as over the whole batch
    np.testing.assert_allclose(got_stats, want_stats, rtol=1e-4)
    # every slot's leaves are stacked over the two periods, and the wide
    # ones are sharded as attention's are
    spec = state.params["blocks"]["linear_0"]["linear_attn"]["qkvg"][
        "kernel"
    ].sharding.spec
    assert spec[1] == "fsdp", spec


def digest(state):
    from dlrover_tpu.trainer import state_digest

    return int(state_digest._digest_tree(state))


def test_a_flash_checkpoint_of_the_patterned_tree_restores_its_digest(
    small_pieces,
):
    """Save through the staged path (its plans keyed by the new block
    shapes), drop the saver as a kill would, and restore in a second
    handler from the arena alone."""
    from dlrover_tpu.checkpoint import engine as ckpt_engine
    from dlrover_tpu.checkpoint.shm_handler import (
        SharedMemoryHandler,
        assemble_tensor,
    )

    train = build(4, ParallelConfig(data=2, fsdp=2), zero1=True)
    state, _, _ = run(train, steps=2)
    saved = digest(state)
    name = f"hybrid{os.getpid()}"
    writer = SharedMemoryHandler(name)
    try:
        writer.save_state_dict(state, step=2)
        assert writer.last_d2h["path"] == "staged"
        shapes = {key[0] for key, plan in writer._staged.items() if plan}
        # a stacked [periods, d, H, 2 dk + 2 dv] kernel's shard among them
        assert any(len(shape) == 4 for shape in shapes)
        writer.close()                       # the process is gone
        reader = SharedMemoryHandler(name)
        meta = reader.load_meta()
        assert meta.step == 2
        arrays = {
            t.path: assemble_tensor(t, lambda r: reader.load_block(meta, r))
            for t in meta.tensors
        }
        restored = ckpt_engine.materialize_records(
            arrays, meta, train.state_shardings,
            jax.tree_util.tree_structure(state),
        )
        assert digest(restored) == saved
        # and it trains on: one more step from either gives one loss
        batch = train_lib.shard_batch(batches(3)[2], train)
        _, a = train.step(restored, batch)
        assert np.isfinite(float(a["loss"]))
    finally:
        SharedMemoryHandler(name).close(unlink=True)


def test_relayout_state_four_to_two_keeps_every_leaf():
    from dlrover_tpu.runtime.virtual_mesh import relayout_state

    four = build(4, ParallelConfig(data=2, fsdp=2), zero1=True)
    two = build(2, ParallelConfig(data=1, fsdp=2))
    state, _, _ = run(four, steps=2)
    want = [np.asarray(x) for x in jax.tree.leaves(state)]
    moved = two.adopt(relayout_state(state, two.state_shardings))
    got = jax.tree.leaves(moved)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert len(g.sharding.device_set) <= 2
        np.testing.assert_array_equal(np.asarray(g), w)
    batch = batches(3)[2]
    _, a = four.step(state, train_lib.shard_batch(batch, four))
    _, b = two.step(moved, train_lib.shard_batch(batch, two))
    np.testing.assert_allclose(float(a["loss"]), float(b["loss"]), rtol=2e-5)


@pytest.mark.parametrize("metrics_lag", [0, 4])
def test_fit_books_one_linear_attn_event_per_report_from_the_step_itself(
    metrics_lag, monkeypatch, tmp_path
):
    """Ten steps at ``report_every=5``: exactly two ``linear_attn`` events,
    of steps 5 and 10, carrying the step's own numbers; one trace of the
    step program and no second program beside it."""
    from dlrover_tpu.common import telemetry
    from dlrover_tpu.trainer.elastic_trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    monkeypatch.setenv("DLROVER_TPU_JOB", f"la_{tmp_path.name}")
    monkeypatch.setenv("DLROVER_TPU_SOCKET_DIR", str(tmp_path / "socks"))
    train_lib.reset_build_cache()
    train_lib.reset_trace_counts()
    trainer = ElasticTrainer(
        config(),
        TrainerConfig(
            global_batch_size=BATCH, seq_len=SEQ, learning_rate=1e-2,
            optimizer="adafactor", ckpt_every=1000, report_every=5,
            metrics_lag=metrics_lag,
        ),
        client=None,
    )
    seen = {}
    with telemetry.recorder().open_tap() as tap:
        trainer.fit(
            batches(10), max_steps=10,
            on_step=lambda step, metrics: seen.update({
                step: metrics[linear_attention.STATS_NAME]
            }),
        )
        events = [
            e for e in tap.take()
            if e[0] == "linear_attn" and e[1] == "event"
        ]
    assert sorted(seen) == list(range(1, 11))
    assert [e[4]["step"] for e in events] == [5, 10]
    for event in events:
        attrs = event[4]
        assert attrs["layers"] == 6 and attrs["chunk"] == 128
        alpha, beta, absmax = linear_attention.split_stats(
            np.asarray(seen[attrs["step"]], np.float64)
        )
        assert attrs["mean_alpha"] == pytest.approx(float(alpha))
        assert attrs["mean_beta"] == pytest.approx(float(beta))
        assert attrs["state_absmax"] == pytest.approx(float(absmax))
        assert 0 < attrs["mean_alpha"] < 1 and 0 < attrs["mean_beta"] < 2
        assert 0 < attrs["state_absmax"] < 1e3
    assert train_lib.trace_count("train_step") == 1


def test_the_master_renders_the_events_as_gauges():
    from dlrover_tpu.master.speed_monitor import SpeedMonitor
    from dlrover_tpu.master.timeline import JobTimeline

    monitor = SpeedMonitor()
    monitor.record_linear_attn(
        0, step=5, layers=6, chunk=64, mean_alpha=0.8, mean_beta=1.0,
        state_absmax=2.5, later_attr="ignored",
    )
    monitor.record_linear_attn(
        1, step=5, layers=6, chunk=64, mean_alpha=0.6, mean_beta=1.2,
        state_absmax=7.5,
    )
    ledger = monitor.linear_attn_ledger()
    assert ledger["reporters"] == 2 and ledger["layers"] == 6
    assert ledger["mean_alpha"] == pytest.approx(0.7)
    assert ledger["state_absmax"] == 7.5          # the worst replica's
    text = JobTimeline().render_metrics(speed_monitor=monitor)
    for name, value in (
        ("dlrover_linear_attn_layers", "6"),
        ("dlrover_linear_attn_chunk", "64"),
        ("dlrover_linear_attn_mean_beta", "1.1"),
        ("dlrover_linear_attn_state_absmax", "7.5"),
        ("dlrover_linear_attn_reporters", "2"),
    ):
        assert f"# TYPE {name} gauge" in text
        assert any(
            line.startswith(name + " ") and line.split()[1].startswith(value)
            for line in text.splitlines()
        ), name
    # a state that diverged on one replica shows as such
    monitor.record_linear_attn(1, step=10, state_absmax=float("nan"))
    assert np.isnan(monitor.linear_attn_ledger()["state_absmax"])


def test_a_state_that_is_not_finite_is_the_anomaly_a_loss_would_be():
    from dlrover_tpu.trainer.numeric_health import NumericHealthMonitor

    monitor = NumericHealthMonitor()
    assert monitor.check(1, 5.0, 1.0, state_absmax=3.0) == []
    (found,) = monitor.check(2, 5.0, 1.0, state_absmax=float("inf"))
    assert found.kind == "nan" and "state_absmax=inf" in found.detail
    # a poisoned reading stays out of the rolling statistics
    assert len(monitor._losses) == 1
    assert monitor.check(3, 5.0, 1.0) == []
