"""End-to-end sharded training tests on the virtual CPU mesh.

Covers the strategy matrix the reference exercises in
``auto_accelerate_test.py`` / ``semi_auto_acc_test.py`` (SURVEY.md §4):
DDP, FSDP, TP, SP, EP and their composition — here each strategy is just a
mesh shape, so one parameterized test covers the matrix.
"""

import jax
import numpy as np
import pytest

from dlrover_tpu.models.gpt2 import gpt2_config
from dlrover_tpu.models.llama import llama_config, moe_llama_config
from dlrover_tpu.models.transformer import TransformerLM
from dlrover_tpu.parallel import rules as lr
from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
from dlrover_tpu.trainer import train_lib

TINY_GPT = gpt2_config(
    "124m",
    num_layers=2,
    d_model=64,
    num_heads=4,
    vocab_size=256,
    max_seq_len=64,
)


def make_batch(batch=8, seq=16, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab, size=(batch, seq + 1), dtype=np.int32)
    return {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}


def run_steps(config, parallel, n_steps=3, batch=8, seq=16):
    mesh = build_mesh(parallel)
    model = TransformerLM(config)
    opt = train_lib.make_optimizer(learning_rate=1e-3)
    train = train_lib.build_sharded_train(
        model, opt, mesh, lr.DEFAULT_RULES,
        global_batch_size=batch, seq_len=seq,
    )
    state = train.init(jax.random.PRNGKey(0))
    losses = []
    # Re-feed the same batch: loss must fall as the model memorizes it.
    b = train_lib.shard_batch(make_batch(batch, seq, config.vocab_size), train)
    for _ in range(n_steps):
        state, metrics = train.step(state, b)
        losses.append(float(metrics["loss"]))
    return losses, state, train


@pytest.mark.parametrize(
    "parallel",
    [
        ParallelConfig(),                          # pure DP over 8 devices
        ParallelConfig(fsdp=8, data=1),            # ZeRO/FSDP
        ParallelConfig(tensor=2),                  # DP x TP
        ParallelConfig(fsdp=2, tensor=2),          # DP x FSDP x TP
        ParallelConfig(seq=2, tensor=2),           # DP x SP x TP (Ulysses)
    ],
    ids=["dp", "fsdp", "tp", "fsdp_tp", "sp_tp"],
)
def test_train_step_strategies(parallel):
    losses, _, _ = run_steps(TINY_GPT, parallel)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]  # tiny model memorizes quickly


@pytest.mark.slow  # cross-compiles every strategy in one test, ~14s;
# each strategy keeps its own tier-1 witness in
# test_train_step_strategies.
def test_strategies_numerically_agree():
    """The same model must produce the same loss under any strategy."""
    losses_dp, _, _ = run_steps(TINY_GPT, ParallelConfig(), n_steps=2)
    losses_tp, _, _ = run_steps(
        TINY_GPT, ParallelConfig(fsdp=2, tensor=2), n_steps=2
    )
    np.testing.assert_allclose(losses_dp, losses_tp, rtol=2e-2)


def test_llama_variant_runs():
    cfg = llama_config(
        "tiny", num_layers=2, max_seq_len=64, vocab_size=256
    )
    losses, _, _ = run_steps(cfg, ParallelConfig(tensor=2))
    assert all(np.isfinite(losses))


@pytest.mark.slow  # superseded as tier-1 witness by the dedicated
# test_moe_trainer suite (layer-bitwise parity, compose, sharding).
def test_moe_expert_parallel():
    cfg = moe_llama_config(
        "tiny", num_experts=4, num_layers=2, max_seq_len=64, vocab_size=256
    )
    losses, _, _ = run_steps(cfg, ParallelConfig(expert=4, data=2))
    assert all(np.isfinite(losses))


def test_param_shardings_fsdp():
    """FSDP rules must actually shard the params over the fsdp axis."""
    _, state, train = run_steps(
        TINY_GPT, ParallelConfig(fsdp=8, data=1), n_steps=1
    )
    embed = state.params["embed"]["embedding"]
    spec = embed.sharding.spec
    assert "fsdp" in str(spec)


def test_remat_full():
    cfg = TINY_GPT.__class__(**{**TINY_GPT.__dict__, "remat": "full"})
    losses, _, _ = run_steps(cfg, ParallelConfig())
    assert all(np.isfinite(losses))


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
    import dataclasses

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
