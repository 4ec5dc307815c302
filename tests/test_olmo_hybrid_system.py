"""The layer pattern through the rest of the system, one small CPU test
each: ZeRO-1 on four host devices, a Flash Checkpoint save and restore of
the patterned tree, a live relayout 4 -> 2, the ``linear_attn`` event;
and, at the size of ``tests/test_olmo_hybrid_reference.py`` (``numerics``),
the train step's first loss.  (What the configuration refuses and the
master's gauges are ``tests/test_olmo_hybrid_config.py``'s; the earlier
models' pinned steps ``tests/test_step_scopes.py``'s.)

The file's step programs are each a property's own and stay apart: the
small model on one device, on four under ZeRO-1 (three cases share it) and
on two (the relayout's target), the trainer's on eight, and the reference's
size (144 tokens, where the rule's kernel runs) on one device and over
``data`` x ``tensor``, where each device's kernels see its own heads."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import linear_attention
from dlrover_tpu.models.olmo_hybrid import olmo_hybrid_config
from dlrover_tpu.trainer import train_lib
import reference_harness as harness
import test_olmo_hybrid_reference as numerics
from test_olmo_hybrid_reference import params, tokens  # noqa: F401

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


def batches(n):
    return harness.batches(n, BATCH, SEQ, VOCAB)


def build(devices, parallel, **kw):
    """Built once for all the cases that ask for the same one (three ask
    for the ZeRO-1 step on four devices): the state is each case's own."""
    return harness.built(
        config(), batch=BATCH, seq=SEQ, devices=devices, parallel=parallel,
        learning_rate=1e-2, **kw,
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
    one = build(1, dict(data=1))
    four = build(4, dict(data=2, fsdp=2), zero1=True)
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


digest = harness.digest


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

    train = build(4, dict(data=2, fsdp=2), zero1=True)
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

    four = build(4, dict(data=2, fsdp=2), zero1=True)
    two = build(2, dict(data=1, fsdp=2))
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
    metrics_lag, tmp_path, one_step_program
):
    """Ten steps at ``report_every=5``: exactly two ``linear_attn`` events,
    of steps 5 and 10, carrying the step's own numbers; one trace of the
    step program and no second program beside it."""
    fit = harness.fit(
        config(), str(tmp_path), seq=SEQ, batch=BATCH,
        metrics_lag=metrics_lag, warmup_compile=False,
    )
    seen = {
        step: metrics[linear_attention.STATS_NAME]
        for step, metrics in fit["seen"].items()
    }
    events = [
        e for e in fit["taken"] if e[0] == "linear_attn" and e[1] == "event"
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


# -- at the reference's size --------------------------------------------------


@pytest.mark.parametrize("parallel,devices", [
    (dict(data=1), 1), (dict(data=2, tensor=2), 4),
])
def test_the_train_step_s_first_loss_is_the_reference_s(
    parallel, devices, params, tokens
):
    """The normal path: ``build_sharded_train``'s compiled step, under the
    policy the cell runs (the rule's and the flash kernels' outputs kept);
    on one device, and with the batch over ``data`` and the heads over
    ``tensor``, where each device's kernels see its own rows and heads."""
    cfg = numerics.config(attention_impl="flash", remat="flash_only")
    train = harness.built(
        cfg, batch=numerics.BATCH, seq=numerics.SEQ, devices=devices,
        parallel=parallel,
    )
    _, metrics = harness.first_step(train, params, tokens)
    want = numerics.CHECK.reference("token_nll", cfg, params, tokens).mean()
    assert abs(float(metrics["loss"]) - float(want)) <= numerics.LOSS_ATOL
    alpha, beta, absmax = linear_attention.split_stats(
        np.asarray(metrics[linear_attention.STATS_NAME])
    )
    assert 0 < alpha < 1 and 0 < beta < 2 and 0 < absmax < 100
