"""End-to-end sharded training tests on the virtual CPU mesh.

Covers the strategy matrix the reference exercises in
``auto_accelerate_test.py`` / ``semi_auto_acc_test.py`` (SURVEY.md §4):
DDP, FSDP, TP, SP, EP and their composition — here each strategy is just a
mesh shape, so one parameterized test covers the matrix.  (That the
step's kernels stay device-local under a mesh is
``tests/test_train_lib_kernels.py``'s: a file is one worker's, and this one
is among the last to start.)
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
