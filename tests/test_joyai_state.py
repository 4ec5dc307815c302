"""JoyAI-LLM-Flash's router bias as train state (no optimizer moves it, a
Flash Checkpoint under FSDP keeps it) and the dense prefix ahead of a
two-stage pipeline.  (Beside ``tests/test_joyai_system.py``, whose sizes
and builders it takes: a file is one worker's, and eight distinct step
programs in one file were 260 to 310 s of it.)"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.transformer import TransformerLM
from dlrover_tpu.runtime.mesh import ParallelConfig
from dlrover_tpu.trainer import train_lib
from test_joyai_system import SEQ, batches, biases, build, config


@pytest.mark.parametrize("optimizer", ["adafactor", "adamw"])
def test_no_optimizer_moves_the_bias_only_the_rule_does(optimizer):
    train = build(optimizer=optimizer)
    state = train.init(jax.random.PRNGKey(0))
    for i, batch in enumerate(batches(3), start=1):
        state, metrics = train.step(
            state, train_lib.shard_batch(batch, train)
        )
        for name, bias in biases(state.params).items():
            # every entry has moved by whole steps of the rate, each way
            steps = bias / 0.001
            np.testing.assert_allclose(steps, np.rint(steps), atol=1e-3)
            assert np.abs(steps).max() <= i + 1e-3, name
        assert float(np.asarray(metrics[moe_lib.SHARE_STATS_NAME])[1]) == (
            pytest.approx(0.001 * (i - 1), abs=1e-6)
        )


def digest(state):
    from dlrover_tpu.trainer import state_digest

    return int(state_digest._digest_tree(state))


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs two host devices")
def test_a_flash_checkpoint_keeps_the_router_bias(small_pieces):
    """``b`` is train state no gradient moves: saved through the staged
    path with the rest of it and restored from the arena alone."""
    from dlrover_tpu.checkpoint import engine as ckpt_engine
    from dlrover_tpu.checkpoint.shm_handler import (
        SharedMemoryHandler,
        assemble_tensor,
    )

    train = build(
        devices=2, parallel=ParallelConfig(data=1, fsdp=2),
        optimizer="adafactor",
    )
    state = train.init(jax.random.PRNGKey(0))
    for batch in batches(3):
        state, _ = train.step(state, train_lib.shard_batch(batch, train))
    saved, saved_bias = digest(state), biases(state.params)
    assert all(np.abs(b).max() > 0 for b in saved_bias.values())
    name = f"joyai{os.getpid()}"
    writer = SharedMemoryHandler(name)
    try:
        writer.save_state_dict(state, step=3)
        writer.close()                       # the process is gone
        reader = SharedMemoryHandler(name)
        meta = reader.load_meta()
        assert meta.step == 3
        assert [t.path for t in meta.tensors if "router_bias" in str(t.path)]
        arrays = {
            t.path: assemble_tensor(t, lambda r: reader.load_block(meta, r))
            for t in meta.tensors
        }
        restored = ckpt_engine.materialize_records(
            arrays, meta, train.state_shardings,
            jax.tree_util.tree_structure(state),
        )
        assert digest(restored) == saved
        for key, bias in biases(restored.params).items():
            np.testing.assert_array_equal(bias, saved_bias[key])
        batch = train_lib.shard_batch(batches(4)[3], train)
        _, a = train.step(restored, batch)
        assert np.isfinite(float(a["loss"]))
    finally:
        SharedMemoryHandler(name).close(unlink=True)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs four host devices")
def test_the_dense_layer_runs_ahead_of_a_two_stage_pipeline():
    """A dense trunk with latent attention and one leading layer outside
    the stack: two pipeline stages over a real ``pipe`` axis give the loss
    the plain scan gives on the same weights."""
    dense = dict(
        num_experts=0, experts_held=0, first_expert=0, router_bias=False,
        router_scoring="softmax", num_shared_experts=0, moe_dispatch="einsum",
        mtp_depth=0, num_layers=5, first_k_dense=1,
    )
    tokens = batches(1)[0]
    losses, params1 = {}, None
    for pp in (1, 2):
        cfg = config(
            pipeline_stages=pp, num_microbatches=2 if pp > 1 else 0, **dense
        )
        assert cfg.num_scan_units == 4
        train = build(
            cfg, devices=2 * pp,
            parallel=ParallelConfig(data=2, pipe=pp), optimizer="sgd",
        )
        state = train.init(jax.random.PRNGKey(0))
        if pp == 1:
            params1 = jax.tree.map(np.asarray, state.params)
        else:
            # the first stage's input is the dense layer's output: the
            # layer lives outside the stage-stacked weights, whole
            assert set(state.params) == set(params1)
            stacked = jax.tree.map(
                lambda leaf: leaf.reshape(2, 2, *leaf.shape[1:]),
                params1["blocks"],
            )
            piped = dict(
                params1, blocks={"ticks": {"stages": {"layers": stacked}}}
            )
            assert jax.tree.structure(piped) == jax.tree.structure(
                jax.tree.map(np.asarray, state.params)
            )
            state = state.replace(params=jax.device_put(
                piped, train.state_shardings.params
            ))
            spec = state.params["blocks"]["ticks"]["stages"]["layers"][
                "attn"
            ]["q_b"]["kernel"].sharding.spec
            assert spec[0] == "pipe", spec
            assert "pipe" not in str(
                state.params["dense_0"]["attn"]["q_b"]["kernel"].sharding.spec
            )
        _, metrics = train.step(state, train_lib.shard_batch(tokens, train))
        losses[pp] = float(metrics["loss"])
    assert losses[2] == pytest.approx(losses[1], rel=1e-4)
    with pytest.raises(NotImplementedError, match="num_experts=0"):
        TransformerLM(config(pipeline_stages=2, num_layers=5)).init(
            jax.random.PRNGKey(0), jnp.zeros((2, SEQ), jnp.int32)
        )
