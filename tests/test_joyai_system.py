"""JoyAI-LLM-Flash's parts through the rest of the system, one small CPU
test each: the train step's loss, metrics and router-bias rule against the
reference, the microbatch engine, the ``moe`` and
``mtp`` events; the parameter count and the latent attention's scopes (at
the size of ``tests/test_joyai_reference.py``: ``numerics``); then the
router bias as train state (no optimizer moves it, a Flash Checkpoint
under FSDP keeps it) and the dense prefix ahead of a two-stage pipeline
(``tests/test_joyai_state.py``'s cases until PR 48: they take this file's
sizes and its builder, and a few-case file of two and a half minutes that
starts last was the end of the whole run).  (What the configuration
refuses and the master's gauges are ``tests/test_joyai_config.py``'s.)

The plain step of the small model is the trainer's (``fitted``), traced
once.  A step program of its own have: two microbatches (the engine is
another step), AdamW (another optimizer's state), FSDP over two devices
(the staged save path wants sharded leaves) and the dense trunk on one and
two pipeline stages (another model)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_harness as harness
import test_joyai_reference as numerics
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.joyai_llm_flash import joyai_llm_flash_config
from dlrover_tpu.models.references import joyai_llm_flash as ref
from dlrover_tpu.models.transformer import TransformerLM
from dlrover_tpu.trainer import train_lib

SEQ, BATCH, VOCAB = 32, 8, 256
biases = harness.router_biases

SMALL = dict(
    vocab_size=VOCAB, num_layers=3, d_model=64, num_heads=4, d_ff=96,
    max_seq_len=SEQ, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, num_experts=16, top_k=4, moe_d_ff=32,
    experts_held=4, first_expert=4, moe_row_budget=2.0, rope_theta=1e4,
    dtype=jnp.float32, param_dtype=jnp.float32,
)


def config(**overrides):
    return joyai_llm_flash_config(**{**SMALL, **overrides})


def batches(n):
    return harness.batches(n, BATCH, SEQ, VOCAB)


def build(cfg=None, devices=1, parallel=None, optimizer="adafactor", **kw):
    return harness.built(
        cfg or config(), batch=BATCH, seq=SEQ, devices=devices,
        parallel=parallel, optimizer=optimizer, learning_rate=1e-2, **kw,
    )


@pytest.fixture(scope="module")
def fitted(tmp_path_factory, one_step_program):
    """Ten steps of the small model at ``report_every=5``, each report
    read as its step ends, the step compiled by its first call.  Its step
    program is the file's plain step: the cases that want one step of the
    small model on a batch of eight run THIS one from a state of their
    own."""
    return harness.fit(
        config(), str(tmp_path_factory.mktemp("joy")), seq=SEQ, batch=BATCH,
        metrics_lag=0, warmup_compile=False,
    )


def test_the_step_trains_the_sum_reports_the_parts_and_moves_the_bias(fitted):
    """One step against the reference: ``loss`` is the main cross-entropy,
    ``mtp_loss`` the module's, the gradient is of their weighted sum, and
    each layer's bias moves by the rule on that layer's own counts."""
    cfg = config()
    train = fitted["train"]
    state = train.init(jax.random.PRNGKey(0))
    params = jax.tree.map(np.asarray, state.params)
    batch = batches(1)[0]
    traces = train_lib.trace_count("train_step")
    new_state, metrics = train.step(
        state, train_lib.shard_batch(batch, train)
    )
    assert train_lib.trace_count("train_step") == traces
    rows = (jnp.asarray(batch["inputs"]), jnp.asarray(batch["targets"]))
    want = numerics.CHECK.reference("forward", cfg, params, rows)
    assert float(metrics["loss"]) == pytest.approx(
        float(want["nll"].mean()), abs=1e-4
    )
    assert float(metrics["mtp_loss"]) == pytest.approx(
        float(want["mtp_nll"].mean()), abs=1e-4
    )
    assert float(metrics["aux_loss"]) == 0.0
    _, grads = numerics.CHECK.reference("loss_and_grads", cfg, params, rows)
    # the norm the step clips by is the reference gradient's
    norm = float(metrics["grad_norm"])
    want_norm = float(np.sqrt(sum(
        np.sum(g * g) for g in jax.tree.leaves(grads)
    )))
    assert norm == pytest.approx(want_norm, rel=1e-4)
    moved = biases(new_state.params)
    assert sorted(moved) == [
        "blocks/moe/router_bias", "mtp/block/moe/router_bias"
    ]
    trunk = np.stack([np.asarray(c) for c in want["counts"][:2]])
    np.testing.assert_allclose(
        moved["blocks/moe/router_bias"],
        np.stack([
            np.asarray(ref.bias_rule(jnp.zeros(16), c, cfg.router_bias_rate))
            for c in trunk
        ]), atol=1e-7,
    )
    np.testing.assert_allclose(
        moved["mtp/block/moe/router_bias"],
        ref.bias_rule(jnp.zeros(16), want["counts"][2], cfg.router_bias_rate),
        atol=1e-7,
    )
    share = np.asarray(metrics[moe_lib.SHARE_STATS_NAME])
    here = np.mean([float(c[4:8].sum() / c.sum()) for c in want["counts"]])
    assert share[0] == pytest.approx(here, rel=1e-5) and share[1] == 0.0
    _, drop, load, _, _ = moe_lib.split_stats(np.asarray(metrics["moe_stats"]))
    assert drop == 0.0 and load.shape == (16,)


def test_the_microbatch_engine_trains_the_same_step(fitted):
    one, two = fitted["train"], build(grad_accum=2)
    batch = batches(1)[0]
    results = []
    for train in (one, two):
        state = train.init(jax.random.PRNGKey(0))
        state, metrics = train.step(
            state, train_lib.shard_batch(batch, train)
        )
        results.append((state, metrics))
    (a_state, a), (b_state, b) = results
    for key in ("loss", "mtp_loss", "grad_norm"):
        assert float(a[key]) == pytest.approx(float(b[key]), rel=2e-4), key
    np.testing.assert_allclose(
        np.asarray(a[moe_lib.SHARE_STATS_NAME]),
        np.asarray(b[moe_lib.SHARE_STATS_NAME]), rtol=1e-5,
    )
    # the step's loads are the microbatches' together: one rule, one move
    for name, bias in biases(a_state.params).items():
        np.testing.assert_allclose(
            bias, biases(b_state.params)[name], atol=1e-7, err_msg=name
        )


@pytest.mark.parametrize("metrics_lag", [0, 4])
def test_fit_books_the_share_and_the_mtp_loss_on_the_report_cadence(
    metrics_lag, monkeypatch, tmp_path, fitted
):
    # rows of 64 go through XLA's gather (``row_moves: xla``); the second
    # case reports as a trainer whose rows fit the live-only kernel does
    assert moe_lib.row_moves(config()) == "xla"
    fit = fitted
    if metrics_lag:
        monkeypatch.setattr(moe_lib, "row_moves", lambda cfg: "kernel_live")
        fit = harness.fit(
            config(), str(tmp_path), seq=SEQ, batch=BATCH,
            metrics_lag=metrics_lag, warmup_compile=False,
        )
    seen = fit["seen"]
    events = [e for e in fit["taken"] if e[1] == "event"]
    moe = [e[4] for e in events if e[0] == "moe"]
    mtp = [e[4] for e in events if e[0] == "mtp"]
    assert [e["step"] for e in moe] == [5, 10] == [e["step"] for e in mtp]
    for event in moe:
        share = np.asarray(seen[event["step"]][moe_lib.SHARE_STATS_NAME])
        assert event["experts"] == event["experts_total"] == 16
        assert event["held"] == 4 and event["top_k"] == 4
        assert event["pairs_here"] == pytest.approx(float(share[0]))
        assert 0.1 < event["pairs_here"] < 0.4
        assert event["bias_absmax"] == pytest.approx(float(share[1]))
        assert event["bias_absmax"] == pytest.approx(
            0.001 * (event["step"] - 1), abs=1e-6
        )
        assert event["drop_fraction"] == 0.0
        # of a token's four row fetches: every one through XLA's gather,
        # those of the pairs kept here through the live-only kernel
        assert event["row_fetch_share"] == (
            event["pairs_here"] if metrics_lag else 1.0
        )
        assert len(__import__("json").loads(event["load"])) == 16
    for event in mtp:
        assert event["mtp_loss"] == pytest.approx(
            seen[event["step"]]["mtp_loss"]
        )
        assert event["weight"] == pytest.approx(0.3)
        assert 4.0 < event["mtp_loss"] < 7.0
    # the first trainer traced the plain step, the second was handed it
    assert fit["traces"] == (0 if metrics_lag else 1)


def test_num_params_counts_what_is_held():
    cfg = config()
    params = numerics.weights()
    held = sum(leaf.size for leaf in jax.tree.leaves(params))
    norms = sum(
        leaf.size for path, leaf in jax.tree_util.tree_leaves_with_path(params)
        if path[-2].key in ("ln_attn", "ln_mlp", "ln_final")
    )
    # the layer norms are the approximation num_params() always made
    assert cfg.num_params() == held - norms
    whole = config(experts_held=0, first_expert=0)
    one_expert = 3 * 64 * 32
    assert whole.num_params() - cfg.num_params() == 3 * 12 * one_expert
    # the published model, every expert held: 48.9 B and the MTP module
    published = joyai_llm_flash_config()
    assert published.num_params() == pytest.approx(50.16e9, rel=2e-3)
    assert joyai_llm_flash_config(mtp_depth=0).num_params() == pytest.approx(
        48.94e9, rel=2e-3
    )


def test_latent_attention_names_its_scopes_and_shares_one_rotary_key():
    cfg = config(num_layers=2, mtp_depth=0)
    inputs, _ = numerics.tokens()
    params = harness.init(cfg, inputs)
    text = jax.jit(
        lambda p, t: TransformerLM(cfg).apply({"params": p}, t)[0]
    ).lower(params, inputs).as_text(debug_info=True)
    for scope in ("attn/q_a", "attn/q_b", "attn/kv_a", "attn/kv_b",
                  "attn/rope", "attn/wo", "moe/shared", "moe/router"):
        assert scope in text, scope
    kv_a = params["dense_0"]["attn"]["kv_a"]["kernel"]
    assert kv_a.shape == (64, 32 + 8)    # one 8-wide rotary key, not 4


# -- the bias as train state, and the dense prefix ahead of a pipeline ---------


@pytest.mark.parametrize("optimizer", ["adafactor", "adamw"])
def test_no_optimizer_moves_the_bias_only_the_rule_does(optimizer, fitted):
    train = (
        fitted["train"] if optimizer == "adafactor"
        else build(optimizer=optimizer)
    )
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


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs two host devices")
def test_a_flash_checkpoint_keeps_the_router_bias(small_pieces):
    """``b`` is train state no gradient moves: saved through the staged
    path with the rest of it and restored from the arena alone."""
    from dlrover_tpu.checkpoint import engine as ckpt_engine
    from dlrover_tpu.checkpoint.shm_handler import (
        SharedMemoryHandler,
        assemble_tensor,
    )

    train = build(devices=2, parallel=dict(data=1, fsdp=2))
    state = train.init(jax.random.PRNGKey(0))
    for batch in batches(3):
        state, _ = train.step(state, train_lib.shard_batch(batch, train))
    saved, saved_bias = harness.digest(state), biases(state.params)
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
        assert harness.digest(restored) == saved
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
            parallel=dict(data=2, pipe=pp), optimizer="sgd",
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
