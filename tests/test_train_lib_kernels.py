"""The step's Pallas kernels under a mesh of several devices: each inside a
``shard_map``, and the sharded step's loss the one-device step's.  (Beside
``tests/test_train_lib.py``, whose sizes it takes: two files so that two
workers share the end of a run.)"""

import dataclasses

import jax
import numpy as np
import pytest

from dlrover_tpu.models.transformer import TransformerLM
from dlrover_tpu.parallel import rules as lr
from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
from dlrover_tpu.trainer import train_lib
from test_train_lib import TINY_GPT, make_batch


def _pallas_calls_outside_shard_map(jaxpr, inside=False):
    """Count ``pallas_call`` equations not nested in a ``shard_map``."""
    outside = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            outside += not inside
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            outside += _pallas_calls_outside_shard_map(
                sub, inside or name == "shard_map"
            )
    return outside


@pytest.mark.parametrize("overrides,optimizer", [
    (dict(attention_impl="flash", remat="flash_only"), "adamw"),
    (dict(fused_ln=True), "adamw"),
    (dict(num_experts=4, top_k=2, moe_dispatch="grouped"), "adamw"),
    (dict(), "q8_adam"),
], ids=["flash", "fused_ln", "moe_grouped", "q8_adam"])
def test_kernels_are_device_local_under_a_mesh(overrides, optimizer):
    """Every Pallas kernel of the step sits inside a shard_map (the TPU
    partitioner refuses a bare Mosaic call on a mesh of more than one
    device, which interpret mode hides), and the first step's loss matches
    the same step on one device."""
    config = dataclasses.replace(TINY_GPT, num_layers=1, **overrides)
    batch = make_batch(8, 16, config.vocab_size)
    losses = {}
    for name, parallel, devices in (
        ("mesh", ParallelConfig(data=2, fsdp=2), jax.devices()[:4]),
        ("one", ParallelConfig(data=1), jax.devices()[:1]),
    ):
        mesh = build_mesh(parallel, devices=devices)
        train = train_lib.build_sharded_train(
            TransformerLM(config),
            train_lib.make_optimizer(optimizer, learning_rate=1e-3),
            mesh, lr.DEFAULT_RULES, global_batch_size=8, seq_len=16,
            zero1=True,
        )
        state = train.init(jax.random.PRNGKey(0))
        placed = train_lib.shard_batch(batch, train)
        if name == "mesh":
            with train_lib.use_mesh(mesh):
                jaxpr = jax.make_jaxpr(train.step_fn)(state, placed)
            assert "pallas_call" in str(jaxpr)
            assert _pallas_calls_outside_shard_map(jaxpr.jaxpr) == 0
        # Two steps: the second loss has been through the optimizer too.
        for _ in range(2):
            state, metrics = train.step(state, placed)
        losses[name] = float(metrics["loss"])
    np.testing.assert_allclose(losses["mesh"], losses["one"], rtol=2e-2)
