"""Elastic expert parallelism end-to-end (PR 19).

MoE as a first-class parallelism axis: the a2a dispatch modes against the
GSPMD einsum reference (layer-level fp32 is BITWISE — the explicit
exchange is a re-transport of the same math, not an approximation),
composition with the microbatch/ZeRO-1/overlap engines through
``build_sharded_train``, expert-axis param sharding, the grouped-dispatch
EP>1 guard, router-stats harvest, cache-key coverage of the MoE knobs,
and the zero-retrace steady state.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
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


def _run(config, parallel=EP_MESH, n_steps=3, batch=16, seq=16, **build_kw):
    mesh = build_mesh(parallel)
    model = TransformerLM(config)
    opt = train_lib.make_optimizer("sgd", learning_rate=1e-2)
    train = train_lib.build_sharded_train(
        model, opt, mesh, lr.DEFAULT_RULES,
        global_batch_size=batch, seq_len=seq, **build_kw,
    )
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


def test_a2a_layer_bitwise_matches_einsum():
    """fp32 layer forward: the explicit a2a exchange reproduces the GSPMD
    einsum dispatch BITWISE — same routing, same expert matmuls, same
    combine; only the transport changed."""
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
    np.testing.assert_array_equal(out_e, out_a)
    assert aux_e == aux_a


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


@pytest.mark.slow
def test_moe_stats_harvest():
    """build_moe_stats_fn reads the sown router stats off the live state:
    ``moe.split_stats`` layout, with sane ranges.
    Slow-marked: the layer-level sow contract is witnessed in tier-1 by
    test_moe.py::test_router_stats_sown_as_intermediates."""
    config = _moe_config("a2a")
    mesh = build_mesh(EP_MESH)
    model = TransformerLM(config)
    opt = train_lib.make_optimizer("sgd", learning_rate=1e-2)
    train = train_lib.build_sharded_train(
        model, opt, mesh, lr.DEFAULT_RULES,
        global_batch_size=16, seq_len=16,
    )
    state = train.init(jax.random.PRNGKey(0))
    batch = train_lib.shard_batch(_batches(1)[0], train)
    state, _ = train.step(state, batch)
    stats_fn = train_lib.build_moe_stats_fn(model, train)
    vec = np.asarray(jax.device_get(stats_fn(state, batch)), np.float64)
    e = config.num_experts
    assert vec.shape == (2 + e + moe_lib.STATS_TAIL,)
    entropy, drop, load = moe_lib.split_stats(vec)[:3]
    assert 0.0 <= entropy <= np.log(e) + 1e-6
    assert 0.0 <= drop <= 1.0
    assert np.all(load >= 0.0)
    np.testing.assert_allclose(load.sum(), 1.0, atol=1e-5)


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
