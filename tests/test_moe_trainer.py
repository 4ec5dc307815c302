"""Elastic expert parallelism end-to-end (PR 19).

MoE as a first-class parallelism axis: the a2a dispatch modes against the
GSPMD einsum reference (layer-level fp32 is BITWISE — the explicit
exchange is a re-transport of the same math, not an approximation),
composition with the microbatch/ZeRO-1/overlap engines through
``build_sharded_train``, expert-axis param sharding, the grouped-dispatch
EP>1 guard, the router statistics a step hands out, cache-key coverage of the MoE knobs,
and the zero-retrace steady state.
"""

import dataclasses
import functools
import json

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import reference_harness as harness
import trace_asserts

from dlrover_tpu.models.llama import moe_llama_config
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.moe import MoEMlp
from dlrover_tpu.models.transformer import TransformerLM
from dlrover_tpu.parallel import rules as lr
from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
from dlrover_tpu.trainer import train_lib

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device virtual mesh"
)

EP_MESH = ParallelConfig(expert=4, data=2)


def _moe_config(dispatch="einsum", num_experts=8, **kw):
    return moe_llama_config(
        "tiny", num_experts=num_experts, num_layers=2, max_seq_len=64,
        vocab_size=256, moe_dispatch=dispatch, **kw,
    )


def _batches(n, batch=16, seq=16, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        t = rng.integers(0, vocab, size=(batch, seq + 1), dtype=np.int32)
        out.append({"inputs": t[:, :-1], "targets": t[:, 1:]})
    return out


def _sgd_step(config, parallel, batch=16, seq=16, **build_kw):
    """``reference_harness.built``'s program of ``config`` under plain SGD
    on the whole virtual mesh (kept for the process)."""
    return harness.built(
        config, batch=batch, seq=seq, devices=len(jax.devices()),
        parallel=dataclasses.asdict(parallel), optimizer="sgd",
        learning_rate=1e-2, **build_kw,
    )


def _run(config, parallel=EP_MESH, n_steps=3, batch=16, seq=16, **build_kw):
    train = _sgd_step(config, parallel, batch, seq, **build_kw)
    state = train.init(jax.random.PRNGKey(0))
    losses = []
    # Re-feed the same batch: loss must fall as the model memorizes it.
    b = train_lib.shard_batch(
        _batches(1, batch, seq, config.vocab_size)[0], train
    )
    for _ in range(n_steps):
        state, metrics = train.step(state, b)
        losses.append(float(metrics["loss"]))
    return losses, state, train


# -- layer-level dispatch parity ----------------------------------------------


def _layer_forward(dispatch, params, x, mesh, num_experts=8):
    layer = MoEMlp(
        num_experts=num_experts, d_ff=64, top_k=2, capacity_factor=2.0,
        activation="gelu", dtype=jnp.float32, param_dtype=jnp.float32,
        dispatch=dispatch,
    )
    with train_lib.use_mesh(mesh):
        out, aux = jax.jit(layer.apply)(params, x)
    return np.asarray(jax.device_get(out)), float(aux)


def test_a2a_layer_matches_einsum_to_float32_rounding():
    """fp32 layer forward: the explicit a2a exchange reproduces the GSPMD
    einsum dispatch — same routing, same expert matmuls, same combine;
    only the transport changed.  Not bit for bit: the a2a body runs the
    expert matmuls on each device's [E/ep, b_chunk*ep, C, .] block, the
    einsum path on the whole [E, b, C, .] array, and the backend's dot
    picks its accumulation order over the 32- and 64-term contractions
    from the operand shapes (the same ``ebcf,efd->ebcd`` on 2 of the 16
    batch rows differs from the rows of the whole by as much).  Both lie
    4e-8 from a float64 evaluation; a token routed or combined wrongly
    moves an output by its own size."""
    mesh = build_mesh(EP_MESH)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(16, 8, 32)), jnp.float32)
    layer = MoEMlp(
        num_experts=8, d_ff=64, top_k=2, capacity_factor=2.0,
        activation="gelu", dtype=jnp.float32, param_dtype=jnp.float32,
        dispatch="einsum",
    )
    params = layer.init(jax.random.PRNGKey(0), x)
    out_e, aux_e = _layer_forward("einsum", params, x, mesh)
    out_a, aux_a = _layer_forward("a2a", params, x, mesh)
    eps = np.finfo(np.float32).eps
    np.testing.assert_allclose(
        out_a, out_e, rtol=0, atol=8 * eps * np.abs(out_e).max()
    )
    assert aux_a == pytest.approx(aux_e, rel=4 * eps)


def test_a2a_int8_layer_close_to_einsum():
    """The int8 wire rounds the dispatch payload once per leg: close, not
    bitwise (block-quantized int8 + fp32 scales)."""
    mesh = build_mesh(EP_MESH)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(16, 8, 32)), jnp.float32)
    layer = MoEMlp(
        num_experts=8, d_ff=64, top_k=2, capacity_factor=2.0,
        activation="gelu", dtype=jnp.float32, param_dtype=jnp.float32,
        dispatch="einsum",
    )
    params = layer.init(jax.random.PRNGKey(1), x)
    out_e, _ = _layer_forward("einsum", params, x, mesh)
    out_q, _ = _layer_forward("a2a_int8", params, x, mesh)
    np.testing.assert_allclose(out_e, out_q, rtol=0.05, atol=0.02)


def test_grouped_dispatch_raises_under_expert_axis():
    """grouped is per-device only: under EP>1 it must raise with a clear
    pointer, never silently compute with the wrong experts."""
    mesh = build_mesh(EP_MESH)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(16, 8, 32)), jnp.float32)
    layer = MoEMlp(
        num_experts=8, d_ff=64, top_k=2, dtype=jnp.float32,
        param_dtype=jnp.float32, dispatch="grouped", gmm_block_rows=8,
    )
    params = layer.init(jax.random.PRNGKey(2), x)
    with train_lib.use_mesh(mesh):
        with pytest.raises(ValueError, match="grouped"):
            layer.apply(params, x)


# -- full-model training ------------------------------------------------------


# The einsum reference train is the baseline for every parity test;
# compile it once per process (tier-1 runs this file without xdist).
_EINSUM_LOSSES = None


def _einsum_ref_losses():
    global _EINSUM_LOSSES
    if _EINSUM_LOSSES is None:
        _EINSUM_LOSSES = _run(_moe_config("einsum"))[0]
    return _EINSUM_LOSSES


@pytest.mark.parametrize(
    "dispatch",
    ["a2a", pytest.param("a2a_int8", marks=pytest.mark.slow)],
)
def test_a2a_training_matches_einsum(dispatch):
    """End-to-end train losses under the explicit wire track the einsum
    reference inside the repo's cross-strategy tolerance (bf16 trunk
    reduction-order noise; the MoE layer itself is exact on fp32).  The
    int8 leg is slow-marked: the fast layer-level closeness test above
    is its tier-1 witness."""
    losses_e = _einsum_ref_losses()
    losses_a, _, _ = _run(_moe_config(dispatch))
    assert all(np.isfinite(losses_a))
    assert losses_a[-1] < losses_a[0]
    np.testing.assert_allclose(losses_e, losses_a, rtol=2e-2)


def test_moe_composes_with_accum_zero1_overlap():
    """The tentpole composition: MoE + grad-accum + ZeRO-1 + the overlap
    engine through one build_sharded_train — and on the same live state,
    expert weights land on the expert axis while the dense trunk (and
    the router, which every device must evaluate identically) does not."""
    losses, state, train = _run(
        _moe_config("a2a"),
        grad_accum=2, zero1=True, overlap=True, overlap_bucket_mb=0.2,
    )
    assert train.zero1 and train.overlap
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]

    flat, _ = jax.tree_util.tree_flatten_with_path(state.params)
    expert, dense = [], []
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        is_expert = "moe" in name and "router" not in name
        (expert if is_expert else dense).append(
            (name, str(leaf.sharding.spec))
        )
    assert expert, "MoE model must have expert param leaves"
    assert all("expert" in spec for _, spec in expert), expert
    assert all("expert" not in spec for _, spec in dense), dense


@pytest.mark.slow
def test_moe_steady_state_no_retrace():
    """After the first compile, further steps (fresh batches) must not
    retrace: routing is data-dependent in values, not in shapes.
    Slow-marked: the committed MOE.json artifact test certifies
    retraces == 0 for both builds in tier-1."""
    config = _moe_config("a2a_int8")
    mesh = build_mesh(EP_MESH)
    model = TransformerLM(config)
    opt = train_lib.make_optimizer("sgd", learning_rate=1e-2)
    train = train_lib.build_sharded_train(
        model, opt, mesh, lr.DEFAULT_RULES,
        global_batch_size=16, seq_len=16,
    )
    state = train.init(jax.random.PRNGKey(0))
    batches = _batches(4)
    state, _ = train.step(
        state, train_lib.shard_batch(batches[0], train)
    )  # first trace paid
    with trace_asserts.assert_no_retrace("train_step"):
        for b in batches[1:]:
            state, metrics = train.step(state, train_lib.shard_batch(b, train))
    assert np.isfinite(float(metrics["loss"]))


# -- the step hands out the router's numbers ----------------------------------

ONE_CHIP = ParallelConfig(data=-1)


def _sown_stats(model, params, tokens):
    """The layers' ``moe_stats`` vectors of one plain forward, reduced as
    the step reduces them: the mean over layers and stacking axes."""
    _, sown = jax.jit(lambda p, t: model.apply(
        {"params": p}, t, mutable=["intermediates"]
    ))(params, tokens)
    leaves = jax.tree_util.tree_leaves(sown)
    stacked = jnp.concatenate(
        [leaf.reshape(-1, leaf.shape[-1]) for leaf in leaves], axis=0
    )
    return np.asarray(jnp.mean(stacked, axis=0), np.float64)


@functools.cache
def _build(config, parallel=ONE_CHIP, **build_kw):
    """(model, train, its initial state), built once a configuration: two
    cases ask for the plain einsum and grouped steps each, and the state is
    not donated."""
    train = _sgd_step(config, parallel, donate_state=False, **build_kw)
    return TransformerLM(config), train, train.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize(
    "dispatch,grad_accum", [("einsum", 1), ("grouped", 1), ("einsum", 2)],
    ids=["einsum", "grouped", "einsum-accum2"],
)
def test_step_metrics_carry_the_router_stats_of_its_input_params(
    dispatch, grad_accum
):
    """``metrics["moe_stats"]`` is what a plain forward with the
    collection mutable sows on the step's INPUT parameters and batch
    (under ``grad_accum`` the mean over the microbatches), in
    ``split_stats``' ranges."""
    config = _moe_config(dispatch, dtype=jnp.float32)
    model, train, state = _build(config, grad_accum=grad_accum)
    raw = _batches(1)[0]
    # Under the step's mesh and rules: the grouped path's row budget (and
    # with it ``pad_share``) is per shard of the tokens.
    with train_lib.use_mesh(train.mesh), nn.logical_axis_rules(train.rules):
        want = np.mean([
            _sown_stats(model, state.params, jnp.asarray(rows))
            for rows in np.split(raw["inputs"], grad_accum)
        ], axis=0)
    _, metrics = train.step(state, train_lib.shard_batch(raw, train))
    got = metrics["moe_stats"]
    e = config.num_experts
    assert got.shape == (2 + e + moe_lib.STATS_TAIL,)
    assert got.dtype == jnp.float32
    got = np.asarray(got, np.float64)
    np.testing.assert_allclose(got, want, atol=1e-6)
    entropy, drop, load, pad_share, max_load = moe_lib.split_stats(got)
    assert 0.0 <= entropy <= np.log(e) + 1e-6
    assert 0.0 <= drop <= 1.0 and 0.0 <= pad_share < 1.0
    assert np.all(load >= 0.0)
    np.testing.assert_allclose(load.sum(), 1.0, atol=1e-5)
    # A mean over layers of each layer's busiest expert: no less than the
    # busiest of the layers' mean loads.
    assert load.max() * e - 1e-5 <= max_load <= e


@pytest.mark.parametrize("dispatch", ["einsum", "grouped"])
def test_the_extra_output_leaves_the_step_s_mathematics_alone(dispatch):
    """Loss, gradient norm and the updated parameters of an MoE step are
    those of ``jax.grad`` over the same loss with no collection mutable
    (the vector is an output under ``stop_gradient``, nothing more)."""
    import optax

    config = _moe_config(dispatch, dtype=jnp.float32)
    model, train, state = _build(config)
    raw = _batches(1)[0]
    batch = train_lib.shard_batch(raw, train)

    def plain_loss(params):
        logits, aux = model.apply({"params": params}, batch["inputs"])
        ce, _ = train_lib.cross_entropy_loss(
            logits, batch["targets"], batch["weights"]
        )
        return ce + aux, ce

    @jax.jit
    def plain_step(params, opt_state):
        grads, ce = jax.grad(plain_loss, has_aux=True)(params)
        updates, _ = train.tx.update(grads, opt_state, params)
        return (
            optax.apply_updates(params, updates), ce,
            optax.global_norm(grads),
        )

    with train_lib.use_mesh(train.mesh):
        want_params, want_loss, want_norm = plain_step(
            state.params, state.opt_state
        )
    new_state, metrics = train.step(state, batch)
    np.testing.assert_allclose(
        float(metrics["loss"]), float(want_loss), rtol=1e-6
    )
    np.testing.assert_allclose(
        float(metrics["grad_norm"]), float(want_norm), rtol=1e-6
    )
    jax.tree.map(
        lambda got, want: np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7
        ),
        new_state.params, want_params,
    )


def test_a_dense_step_has_no_router_output():
    """No experts, no new output: a dense model's step hands back the
    five scalars it always has, and nothing named ``moe_stats``."""
    from dlrover_tpu.models.gpt2 import gpt2_config

    config = gpt2_config(
        "124m", num_layers=2, d_model=64, num_heads=2, vocab_size=256,
        max_seq_len=64,
    )
    for grad_accum in (1, 2):
        _, train, state = _build(config, grad_accum=grad_accum)
        batch = train_lib.shard_batch(_batches(1)[0], train)
        with train_lib.use_mesh(train.mesh):
            _, metrics = jax.eval_shape(train.step_fn, state, batch)
        assert {
            k: (v.shape, v.dtype.name) for k, v in metrics.items()
        } == {
            "loss": ((), "float32"), "aux_loss": ((), "float32"),
            "tokens": ((), "float32"), "grad_norm": ((), "float32"),
            "step": ((), "int32"),
        }


MOE_EVENT_ATTRS = {
    "step", "entropy", "drop_fraction", "experts", "top_k", "load",
    "pad_share", "max_expert_load",
}


@pytest.mark.parametrize("metrics_lag", [0, 4])
def test_fit_books_one_moe_event_per_report_from_the_step_itself(
    metrics_lag, monkeypatch, tmp_path, one_step_program
):
    """Ten steps at ``report_every=5``: exactly two ``moe`` events, of
    steps 5 and 10, every attribute present, one trace of the step
    program and no second program beside it."""
    from dlrover_tpu.common import telemetry
    from dlrover_tpu.trainer.elastic_trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    monkeypatch.setenv("DLROVER_TPU_JOB", f"moe_{tmp_path.name}")
    monkeypatch.setenv("DLROVER_TPU_SOCKET_DIR", str(tmp_path / "socks"))
    # four experts, not the file's eight: the kernels' row budget (128 rows
    # an expert a device, interpreted) is the cost of these twenty steps,
    # and two events of every attribute ask for no more than top-2 of 4
    config = _moe_config("grouped", num_experts=4)
    trainer = ElasticTrainer(
        config,
        TrainerConfig(
            global_batch_size=16, seq_len=16, learning_rate=1e-2,
            ckpt_every=1000, report_every=5, metrics_lag=metrics_lag,
        ),
        client=None,
    )
    seen = []
    with telemetry.recorder().open_tap() as tap:
        trainer.fit(
            _batches(10), max_steps=10,
            on_step=lambda step, metrics: seen.append(step),
        )
        events = [
            e for e in tap.take() if e[0] == "moe" and e[1] == "event"
        ]
    assert seen == list(range(1, 11))
    assert [e[4]["step"] for e in events] == [5, 10]
    e_count = config.num_experts
    for event in events:
        attrs = event[4]
        assert MOE_EVENT_ATTRS <= set(attrs)
        assert attrs["experts"] == e_count and attrs["top_k"] == config.top_k
        load = json.loads(attrs["load"])
        assert len(load) == e_count and abs(sum(load) - 1.0) < 1e-4
        assert 0.0 <= attrs["entropy"] <= np.log(e_count) + 1e-6
        assert attrs["drop_fraction"] == 0.0  # dropless
        assert 1.0 <= attrs["max_expert_load"] <= e_count
    assert train_lib.trace_count("train_step") == 1
    # No second program, nor any state for one, on the trainer.
    assert not [name for name in vars(trainer) if "moe_stats" in name]


def test_train_cache_key_covers_moe_knobs():
    """Single witness that MoE knobs shape the compiled-program name.

    Exhaustive knob-by-knob pinning now lives in tracelint's CKY001
    (cache-key coverage, tests/test_lint_gate.py): the rule resolves
    ``train_cache_key``'s signature and proves every program-shaping
    knob reaches the key, so hand-enumerating them here only duplicated
    that contract one knob behind."""
    from dlrover_tpu.runtime.compile_cache import train_cache_key

    def key(config):
        return train_cache_key(
            config, (2, 1, 1, 4, 1, 1),
            global_batch_size=16, seq_len=16,
        )

    base = _moe_config("a2a")
    assert key(base) == key(_moe_config("a2a"))
    assert key(base) != key(_moe_config("a2a_int8"))
