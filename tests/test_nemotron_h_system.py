"""Nemotron-H's parts through the rest of the system, one small CPU test
each (its numerics against the reference are
``tests/test_nemotron_h_reference.py``'s): the scopes of the lowered text,
a pipelined trunk on a CPU mesh, the train step's loss, router-bias rule
and ``ssm_stats``; Mamba-2's initialisers; the ``ssm`` event.  (What the
configuration refuses and the master's gauges are
``tests/test_nemotron_h_config.py``'s.)"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_harness as harness
from dlrover_tpu.models import linear_attention, mamba2
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.references import nemotron_h as ref
from dlrover_tpu.models.transformer import TransformerLM
from dlrover_tpu.trainer import train_lib
from test_nemotron_h_reference import (  # noqa: F401 (fixtures)
    BATCH, CASES, CHECK, SEQ, TOL, VOCAB, config, dense_config, params, tokens,
)


# ONE step program for the file: the trainer's, which the step's case runs
# on the trainer's batch (``train_step`` is traced once)
pytestmark = pytest.mark.usefixtures("one_step_program")


def cell_config():
    """One period under the policy the cell runs."""
    return config(num_layers=9, **CASES["kernels"])


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    """Ten steps of 32 tokens at ``report_every=5``, each report read as
    its step ends (``metrics_lag=0``)."""
    return harness.fit(
        cell_config(), str(tmp_path_factory.mktemp("ssm")), seq=32,
        metrics_lag=0,
    )


def test_the_ungated_grouped_path_is_two_grouped_gemms(params, tokens):
    """``gmm_wi`` and ``gmm_wo`` and no ``gmm_wg``; the names reach the
    lowered text with the ``ssm/`` scopes."""
    cfg = config(ssm_impl="kernel")
    text = jax.jit(
        lambda p, i: TransformerLM(cfg).apply({"params": p}, i)
    ).lower(params, tokens[0]).as_text(debug_info=True)
    assert "gmm_wi" in text and "gmm_wo" in text and "gmm_wg" not in text
    for scope in ("in_proj", "conv", "dt", "scan", "out_norm", "out_proj"):
        assert f"ssm/{scope}" in text, scope
    assert "moe/shared" in text and "attn/" in text


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs two host devices")
def test_a_pipelined_trunk_of_one_branch_layers_is_the_scanned_one(tokens):
    """Two stages of one period each on a CPU mesh: the stage stack holds
    whole periods of the new kinds, and the logits are the plain scan's."""
    from dlrover_tpu.parallel import rules as lr
    from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh

    plain = dense_config()
    piped = dense_config(pipeline_stages=2, num_microbatches=2)
    tree = harness.init(piped, tokens[0], seed=5)
    layers = tree["blocks"]["ticks"]["stages"]["layers"]
    assert sorted(layers) == ["attention_2", "mlp_1", "mlp_3", "ssm_0"]
    flat = dict(tree, blocks=jax.tree.map(
        lambda a: a.reshape(-1, *a.shape[2:]), layers
    ))
    want = CHECK.nll(plain, flat, tokens)
    np.testing.assert_allclose(CHECK.nll(piped, tree, tokens), want, atol=TOL)
    mesh = build_mesh(
        ParallelConfig(pipe=2, data=1), devices=jax.devices()[:2]
    )
    with train_lib.use_mesh(mesh), nn.logical_axis_rules(lr.DEFAULT_RULES):
        got = jax.jit(
            lambda p, i, t: harness.program_nll(piped, p, i, t)
        )(tree, *tokens)
    np.testing.assert_allclose(got, want, atol=TOL)
    with pytest.raises(NotImplementedError, match="num_experts=0"):
        TransformerLM(
            config(pipeline_stages=2, num_microbatches=2)
        ).init(jax.random.PRNGKey(5), tokens[0])


def test_the_bias_moves_by_the_rule_and_the_step_hands_out_ssm_stats(
    params, fitted
):
    """The normal path: the trainer's compiled step under the policy the
    cell runs, on the trainer's batch and the first period of the seeded
    weights; its first loss is the reference's, the router biases move by
    ``rate x sign(mean load - load)`` of that step's own counts, and
    ``ssm_stats`` leaves with the metrics."""
    cfg = cell_config()
    params = dict(
        params, blocks=jax.tree.map(lambda a: a[:1], params["blocks"])
    )
    tokens = harness.tokens(1, jax.device_count(), 32, VOCAB)
    new_state, metrics = harness.first_step(fitted["train"], params, tokens)
    out = CHECK.reference("forward", cfg, params, tokens)
    assert abs(float(metrics["loss"]) - float(out["nll"].mean())) <= TOL
    decay, dt, absmax = linear_attention.split_stats(
        np.asarray(metrics[mamba2.STATS_NAME])
    )
    assert 0 < decay < 1 and 0 < dt < 1 and 0 < absmax < 100
    drop = moe_lib.split_stats(np.asarray(metrics["moe_stats"]))[1]
    assert abs(float(drop)) < 1e-6
    # expert layers in order: slots 0, 2, 4, 6 of period 0, then period 1
    slots = ("experts_0", "experts_2", "experts_4", "experts_6")
    for i, counts in enumerate(out["counts"]):
        period, slot = (i // 4, slots[i % 4]) if i < 4 else (1, slots[i - 4])
        old = params["blocks"][slot]["moe"]["router_bias"][period]
        new = new_state.params["blocks"][slot]["moe"]["router_bias"][period]
        np.testing.assert_allclose(
            new, ref.bias_rule(old, counts, cfg.router_bias_rate), atol=1e-7
        )


def test_the_initialisers_are_mamba_2s():
    cfg = config()
    tokens = jnp.zeros((1, 16), jnp.int32)
    tree = harness.init(cfg, tokens)["blocks"]["ssm_1"]["ssm"]
    np.testing.assert_allclose(
        jnp.exp(tree["A_log"][0]), jnp.arange(1.0, 5.0), rtol=1e-6
    )
    np.testing.assert_array_equal(tree["D"], jnp.ones((2, 4)))
    dt = jax.nn.softplus(tree["dt_bias"])
    assert float(dt.min()) >= cfg.ssm_dt_floor
    assert cfg.ssm_dt_min * 0.99 <= float(dt.min())
    assert float(dt.max()) <= cfg.ssm_dt_max * 1.01
    assert tree["conv_bias"].shape == (2, 4 * 64 + 2 * 2 * 16)


# -- the ``ssm`` event and its gauges -------------------------------------------


@pytest.mark.parametrize("metrics_lag", [0, 4])
def test_fit_books_one_ssm_event_per_report_from_the_step_itself(
    metrics_lag, tmp_path, fitted
):
    """Ten steps at ``report_every=5``: exactly two ``ssm`` events, of
    steps 5 and 10, carrying the step's own numbers; a ``moe`` event beside
    each; one trace of the step program, whatever the lag; the ``compile``
    event names the scan."""
    fit = fitted if not metrics_lag else harness.fit(
        cell_config(), str(tmp_path), seq=32, metrics_lag=metrics_lag
    )
    taken = fit["taken"]
    seen = {
        step: metrics[mamba2.STATS_NAME]
        for step, metrics in fit["seen"].items()
    }
    events = [e for e in taken if e[0] == "ssm" and e[1] == "event"]
    assert sorted(seen) == list(range(1, 11))
    assert [e[4]["step"] for e in events] == [5, 10]
    assert [
        e[4]["step"] for e in taken if e[0] == "moe" and e[1] == "event"
    ] == [5, 10]
    for event in events:
        attrs = event[4]
        assert attrs["layers"] == 4 and attrs["chunk"] == 16
        decay, dt, absmax = linear_attention.split_stats(
            np.asarray(seen[attrs["step"]], np.float64)
        )
        assert attrs["mean_decay"] == pytest.approx(float(decay))
        assert attrs["mean_dt"] == pytest.approx(float(dt))
        assert attrs["state_absmax"] == pytest.approx(float(absmax))
        assert 0 < attrs["mean_decay"] < 1 and 0 < attrs["mean_dt"] < 1
        assert 0 < attrs["state_absmax"] < 1e3
    assert train_lib.trace_count("train_step") == 1
    # (the second case is handed the first's program: nothing compiles)
    (compiled,) = [e for e in fitted["taken"] if e[0] == "compile"]
    assert compiled[-1]["ssm_scan"] == "kernel"
    assert compiled[-1]["gmm_strips"] == "resident"
    assert compiled[-1]["gmm_dw_tiles"] == "into:1x1 out_of:1x1"
