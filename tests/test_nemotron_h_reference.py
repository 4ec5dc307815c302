"""Nemotron-H (NVIDIA-Nemotron-3-Nano-30B-A3B's layers) against its plain
reference, at a small size on the CPU with seeded float32 weights: per-token
loss, the loss and every gradient; the one-branch layers in a period, the
unrolled and the pipelined trunk; each fault the comparison must catch; the
shares of a relu² expert layer; what the configuration refuses; the
``ssm`` event and its gauges."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import linear_attention, mamba2
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models import nemotron_h
from dlrover_tpu.models.nemotron_h import nemotron_h_config
from dlrover_tpu.models.references import nemotron_h as ref
from dlrover_tpu.models.transformer import (
    ATTENTION, EXPERTS, MLP, SSM, Mlp, TransformerConfig, TransformerLM,
)
from dlrover_tpu.trainer import train_lib

SEQ, BATCH, VOCAB = 40, 2, 128
# float32 on both sides: what is left is the order of the sums
TOL = 1e-4
GRAD_ATOL, GRAD_RTOL = 2e-5, 2e-4


def config(**overrides):
    base = dict(
        vocab_size=VOCAB, num_layers=18, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=48, max_seq_len=64,
        ssm_num_heads=4, ssm_head_dim=64, ssm_state_size=16, ssm_groups=2,
        ssm_chunk=16, num_experts=8, top_k=2, moe_d_ff=32,
        shared_expert_d_ff=48, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    base.update(overrides)
    return nemotron_h_config(**base)


@pytest.fixture(scope="module")
def tokens():
    rows = jax.random.randint(
        jax.random.PRNGKey(1), (BATCH, SEQ + 1), 0, VOCAB
    )
    return rows[:, :-1], rows[:, 1:]


def init(cfg, inputs, seed=0):
    """Seeded weights; the router's biases and the ``D`` of the mixers
    moved off their initial 0 and 1, so that a fault in either shows."""
    params = nn.meta.unbox(
        TransformerLM(cfg).init(jax.random.PRNGKey(seed), inputs)
    )["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 1000))

    def move(path, leaf):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            return 0.3 * jax.random.normal(next(keys), leaf.shape)
        if name.endswith("['D']"):
            return leaf + 0.5 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def params(tokens):
    return init(config(), tokens[0])


def program_nll(cfg, params, inputs, targets):
    logits, _ = TransformerLM(cfg).apply({"params": params}, inputs)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def program_loss(cfg, params, inputs, targets):
    return program_nll(cfg, params, inputs, targets).mean()


def nll_gap(cfg, params, tokens, **kw):
    got = program_nll(cfg, params, *tokens)
    want = ref.token_nll(cfg, params, *tokens, **kw)
    return float(jnp.abs(got - want).max())


CASES = {
    "xla": dict(),
    "kernels": dict(
        ssm_impl="kernel", attention_impl="flash", remat="flash_only",
        flash_block_q=16, flash_block_kv=16,
    ),
    "a_share": dict(experts_held=4, first_expert=4, moe_row_budget=4.0),
    "kernels_a_share": dict(
        ssm_impl="kernel", attention_impl="flash", remat="flash_only",
        experts_held=2, first_expert=0, moe_row_budget=4.0,
    ),
}


def held(params, cfg):
    """``params`` with each expert layer's ``wi`` / ``wo`` cut to the
    config's share."""
    first, count = cfg.first_expert, cfg.resolved_experts_held

    def cut(path, leaf):
        name = jax.tree_util.keystr(path)
        if "['moe']['wi']" in name or "['moe']['wo']" in name:
            return leaf[:, first:first + count]
        return leaf

    return jax.tree_util.tree_map_with_path(cut, params)


def test_the_tree_has_a_slot_per_position_stacked_over_the_periods(params):
    blocks = params["blocks"]
    pattern = nemotron_h.kinds(nemotron_h.PERIOD)
    assert sorted(blocks) == sorted(
        f"{kind}_{i}" for i, kind in enumerate(pattern)
    )
    part = {SSM: "ssm", ATTENTION: "attn", EXPERTS: "moe", MLP: "mlp"}
    for i, kind in enumerate(pattern):
        tree = blocks[f"{kind}_{i}"]
        # ONE branch: one norm and one part, never a second
        assert sorted(tree) == sorted(["ln", part[kind]])
        assert all(leaf.shape[0] == 2 for leaf in jax.tree.leaves(tree))
    assert sorted(blocks["ssm_1"]["ssm"]) == [
        "A_log", "D", "conv_bias", "conv_kernel", "dt_bias", "in_proj",
        "out_norm_scale", "out_proj",
    ]
    assert sorted(blocks["attention_8"]["attn"]) == [
        "key", "out", "query", "value",
    ]
    # ungated experts: two matrices an expert, the shared one its own width
    moe = blocks["experts_0"]["moe"]
    assert sorted(moe) == ["router", "router_bias", "shared", "wi", "wo"]
    assert moe["wi"].shape == (2, 8, 64, 32)
    assert sorted(moe["shared"]) == ["wi", "wo"]
    assert moe["shared"]["wi"]["kernel"].shape == (2, 64, 48)
    assert "pos_embedding" not in params


@pytest.mark.parametrize("case", sorted(CASES))
def test_token_nll_matches_the_reference(case, params, tokens):
    cfg = config(**CASES[case])
    assert nll_gap(cfg, held(params, cfg), tokens) <= TOL


@pytest.mark.parametrize("case", ["xla", "kernels_a_share"])
def test_loss_and_every_gradient_match_the_reference(case, params, tokens):
    cfg = config(**CASES[case])
    loss_and_every_gradient_match(cfg, held(params, cfg), tokens)


def test_loss_and_every_gradient_match_where_the_conv_kernel_runs():
    """64 tokens are half a lane tile, so the cases above take the
    convolution's XLA form; 256 tokens of the same x | B | C = 256 | 32 |
    32 channels from column 256 are two token tiles of
    ``ops/short_conv.py``'s kernels (the tokens on the lanes, 32 channels a
    tile, three outputs)."""
    from dlrover_tpu.models import mamba2
    from dlrover_tpu.ops import short_conv

    seq = 256
    rows = jax.random.randint(jax.random.PRNGKey(2), (BATCH, seq + 1), 0, VOCAB)
    tokens = rows[:, :-1], rows[:, 1:]
    assert mamba2.conv_path(SEQ, 4, 64, 16, 2, 4) == "xla"
    assert mamba2.conv_path(seq, 4, 64, 16, 2, 4) == "kernel"
    calls = []
    kernel = short_conv.short_conv
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(short_conv, "_TILE_TOKENS", 128)
        patch.setattr(
            short_conv, "short_conv",
            lambda *a: calls.append(a[0].shape) or kernel(*a),
        )
        for case in ("xla", "kernels_a_share"):
            cfg = config(max_seq_len=seq, **CASES[case])
            ours = held(init(config(max_seq_len=seq), tokens[0]), cfg)
            loss_and_every_gradient_match(cfg, ours, tokens)
    assert calls and set(calls) == {(BATCH, seq, 580)}


def loss_and_every_gradient_match(cfg, ours, tokens):
    got, got_grads = jax.value_and_grad(program_loss, argnums=1)(
        cfg, ours, *tokens
    )
    want, want_grads = ref.loss_and_grads(cfg, ours, *tokens)
    assert abs(float(got) - float(want)) <= TOL
    flat_got = jax.tree_util.tree_leaves_with_path(got_grads)
    flat_want = jax.tree_util.tree_leaves(want_grads)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        bound = GRAD_ATOL + GRAD_RTOL * float(jnp.abs(w).max())
        assert float(jnp.abs(g - w).max()) <= bound, name
        # the bias picks and never weighs: no gradient reaches it
        assert (float(jnp.abs(w).max()) > 0) != ("router_bias" in name), name


def test_the_unrolled_trunk_is_the_scanned_one(params, tokens):
    cfg = config(scan_layers=False)
    unrolled = {k: v for k, v in params.items() if k != "blocks"}
    layers = ref.trunk_layers(dataclasses.asdict(config()), params)
    for i, (_, layer) in enumerate(layers):
        unrolled[f"block_{i}"] = layer
    got = program_nll(cfg, unrolled, *tokens)
    want = program_nll(config(), params, *tokens)
    np.testing.assert_allclose(got, want, atol=TOL)
    assert nll_gap(cfg, unrolled, tokens) <= TOL


# a dense sibling: the state-space mixer, the attention and the ``-`` layer
# (a dense relu² MLP alone), no experts
DENSE = (SSM, MLP, ATTENTION, MLP)


def dense_config(**overrides):
    return config(
        layer_pattern=DENSE, num_layers=8, num_experts=0, top_k=2,
        router_scoring="softmax", router_bias=False, num_shared_experts=0,
        shared_expert_d_ff=0, moe_dispatch="einsum", **overrides,
    )


def test_a_dense_mlp_alone_is_a_layer(tokens):
    """The ``-`` kind: ``x + W_d relu(W_u n)^2``, one norm."""
    cfg = dense_config()
    params = init(cfg, tokens[0], seed=3)
    assert sorted(params["blocks"]["mlp_1"]) == ["ln", "mlp"]
    assert sorted(params["blocks"]["mlp_1"]["mlp"]) == ["wi", "wo"]
    assert nll_gap(cfg, params, tokens) <= TOL
    # and the square is there
    assert nll_gap(cfg, params, tokens, wrong="no_square") > 10 * TOL
    n = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 64))
    mlp = Mlp(48, "relu2", False, jnp.float32, jnp.float32)
    p = nn.meta.unbox(mlp.init(jax.random.PRNGKey(1), n))["params"]
    with jax.default_matmul_precision("highest"):
        want = jnp.square(jax.nn.relu(n @ p["wi"]["kernel"])) @ p["wo"][
            "kernel"
        ]
        np.testing.assert_allclose(
            mlp.apply({"params": p}, n), want, atol=1e-5
        )


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs two host devices")
def test_a_pipelined_trunk_of_one_branch_layers_is_the_scanned_one(tokens):
    """Two stages of one period each on a CPU mesh: the stage stack holds
    whole periods of the new kinds, and the logits are the plain scan's."""
    from dlrover_tpu.parallel import rules as lr
    from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh

    plain = dense_config()
    piped = dense_config(pipeline_stages=2, num_microbatches=2)
    tree = nn.meta.unbox(
        TransformerLM(piped).init(jax.random.PRNGKey(5), tokens[0])
    )["params"]
    layers = tree["blocks"]["ticks"]["stages"]["layers"]
    assert sorted(layers) == ["attention_2", "mlp_1", "mlp_3", "ssm_0"]
    flat = dict(tree, blocks=jax.tree.map(
        lambda a: a.reshape(-1, *a.shape[2:]), layers
    ))
    want = program_nll(plain, flat, *tokens)
    np.testing.assert_allclose(
        program_nll(piped, tree, *tokens), want, atol=TOL
    )
    mesh = build_mesh(
        ParallelConfig(pipe=2, data=1), devices=jax.devices()[:2]
    )
    with train_lib.use_mesh(mesh), nn.logical_axis_rules(lr.DEFAULT_RULES):
        got = jax.jit(
            lambda p, i, t: program_nll(piped, p, i, t)
        )(tree, *tokens)
    np.testing.assert_allclose(got, want, atol=TOL)
    with pytest.raises(NotImplementedError, match="num_experts=0"):
        TransformerLM(
            config(pipeline_stages=2, num_microbatches=2)
        ).init(jax.random.PRNGKey(5), tokens[0])


WRONG = [
    "decay_sign", "no_skip", "norm_before_gate", "own_bc", "gated_expert",
    "no_square", "bias_weighs", "rotate",
]


@pytest.mark.parametrize("wrong", WRONG)
def test_the_check_is_sharp(wrong, params, tokens):
    """Each fault, made on one side, moves a token's loss past the
    tolerance the tests above hold."""
    assert nll_gap(config(), params, tokens, wrong=wrong) > 10 * TOL, wrong
    if wrong == "rotate":
        # the program has the switch: with it set, the faulty reference
        # agrees again
        rotated = config(position="rope")
        assert nll_gap(rotated, params, tokens) > 10 * TOL
        assert nll_gap(rotated, params, tokens, wrong=wrong) <= TOL


def test_the_reference_computed_lower_is_another_result(params, tokens):
    fields = dataclasses.asdict(config())
    exact = ref.token_nll(fields, params, *tokens)
    for lowered, least in (("router", TOL), ("ssm", 10 * TOL),
                           ("all", 100 * TOL)):
        other = ref.token_nll(fields, params, *tokens, lowered)
        assert float(jnp.abs(other - exact).mean()) > least, lowered


@pytest.mark.parametrize("held_here", [2, 4])
def test_the_shares_add_up_to_the_uncut_layer(held_here):
    """The routed parts of all the shares of one relu² layer (eight of two
    experts, four of four), plus the shared expert counted once, are the
    uncut reference's layer."""
    total, d, width, shared_width = 16, 64, 32, 48
    keys = jax.random.split(jax.random.PRNGKey(5), 8)
    n = jax.random.normal(keys[0], (BATCH, SEQ, d))
    whole = {
        "router": {"kernel": jax.random.normal(keys[1], (d, total))},
        "router_bias": 0.05 * jax.random.normal(keys[2], (total,)),
        "wi": 0.1 * jax.random.normal(keys[3], (total, d, width)),
        "wo": 0.1 * jax.random.normal(keys[4], (total, width, d)),
        "shared": {
            "wi": {"kernel": 0.1 * jax.random.normal(
                keys[5], (d, shared_width)
            )},
            "wo": {"kernel": 0.1 * jax.random.normal(
                keys[6], (shared_width, d)
            )},
        },
    }
    fields = dict(
        num_experts=total, top_k=6, norm_topk_prob=True,
        routed_scaling_factor=2.5,
    )
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(fields, n, whole)
        shared = ref.relu2_mlp(
            n, whole["shared"]["wi"]["kernel"], whole["shared"]["wo"]["kernel"]
        )
        got = shared
        seen = 0.0
        for first in range(0, total, held_here):
            layer = moe_lib.MoEMlp(
                num_experts=total, d_ff=width, top_k=6, dispatch="grouped",
                activation="relu2", scoring="sigmoid", router_bias=True,
                routed_scale=2.5, experts_held=held_here, first_expert=first,
                shared_d_ff=shared_width, row_budget_multiple=4.0,
                dtype=jnp.float32, gmm_block_rows=8,
            )
            part = dict(
                whole,
                **{k: whole[k][first:first + held_here] for k in ("wi", "wo")},
            )
            (out, _), sown = layer.apply(
                {"params": part}, n, mutable=["intermediates"]
            )
            stats = sown["intermediates"]
            assert float(moe_lib.split_stats(stats["moe_stats"][0])[1]) == 0.0
            seen += float(stats[moe_lib.SHARE_STATS_NAME][0][0])
            # what every chip computes alike is counted once
            got = got + (out - shared)
            ours, _ = ref.routed_part(dict(fields, first_expert=first), n, part)
            np.testing.assert_allclose(out - shared, ours, atol=TOL)
    np.testing.assert_allclose(got, want, atol=TOL)
    assert seen == pytest.approx(1.0)


def test_the_ungated_grouped_path_is_two_grouped_gemms(tokens):
    """``gmm_wi`` and ``gmm_wo`` and no ``gmm_wg``; the names reach the
    lowered text with the ``ssm/`` scopes."""
    cfg = config(ssm_impl="kernel")
    params = init(cfg, tokens[0])
    text = jax.jit(
        lambda p, i: TransformerLM(cfg).apply({"params": p}, i)
    ).lower(params, tokens[0]).as_text(debug_info=True)
    assert "gmm_wi" in text and "gmm_wo" in text and "gmm_wg" not in text
    for scope in ("in_proj", "conv", "dt", "scan", "out_norm", "out_proj"):
        assert f"ssm/{scope}" in text, scope
    assert "moe/shared" in text and "attn/" in text


def test_the_bias_moves_by_the_rule_and_the_step_hands_out_ssm_stats(
    params, tokens
):
    """The normal path: ``build_sharded_train``'s compiled step under the
    policy the cell runs; its first loss is the reference's, the router
    biases move by ``rate x sign(mean load - load)`` of that step's own
    counts, and ``ssm_stats`` leaves with the metrics."""
    from dlrover_tpu.parallel import rules as lr
    from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh

    cfg = config(**CASES["kernels"])
    train = train_lib.build_sharded_train(
        TransformerLM(cfg),
        train_lib.make_optimizer("adafactor", learning_rate=1e-3),
        build_mesh(ParallelConfig(data=1), devices=jax.devices()[:1]),
        lr.DEFAULT_RULES, global_batch_size=BATCH, seq_len=SEQ,
    )
    state = train.init(jax.random.PRNGKey(0))
    state = state.replace(params=jax.tree.map(
        lambda new, old: jax.device_put(
            jnp.array(new, old.dtype, copy=True), old.sharding
        ), params, state.params,
    ))
    batch = {"inputs": np.asarray(tokens[0]), "targets": np.asarray(tokens[1])}
    new_state, metrics = train.step(state, train_lib.shard_batch(batch, train))
    out = ref.forward(cfg, params, *tokens)
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


# -- what the configuration refuses ---------------------------------------------


def test_decode_with_an_ssm_layer_raises_naming_what_is_missing():
    with pytest.raises(ValueError, match=r"recurrent state \[H, P, N\]"):
        config(decode=True)
    with pytest.raises(ValueError, match="serving/decode.py"):
        config(decode=True)


@pytest.mark.parametrize("overrides,message", [
    (dict(ssm_num_heads=0), "an ssm layer needs"),
    (dict(ssm_groups=3), "ssm_groups dividing the heads"),
    (dict(ssm_impl="pallas"), "ssm_impl must be one of"),
    (dict(ssm_impl="kernel", ssm_head_dim=48), "side by side"),
    (dict(num_experts=0, router_scoring="softmax", router_bias=False,
          num_shared_experts=0, moe_dispatch="einsum"),
     "an 'experts' layer needs num_experts"),
    (dict(layer_pattern=("ssm", "mamba")), "layer_pattern kinds"),
    (dict(num_layers=10), "no whole number of periods"),
    (dict(mtp_depth=1), "mtp_depth with a layer_pattern"),
    (dict(first_k_dense=1), "first_k_dense"),
    (dict(position="alibi"), "position must be"),
    (dict(activation="relu"), "activation must be"),
])
def test_bad_combinations_of_the_new_fields_raise(overrides, message):
    with pytest.raises(ValueError, match=message):
        config(**overrides)


def test_the_published_widths_count_what_the_issue_counts():
    """ISSUE 37's arithmetic, from the program's own shapes (a layer's own
    norm and the final one are left out of ``num_params``, as ever)."""
    cfg = nemotron_h_config(
        num_layers=18, experts_held=16, vocab_size=16384
    )
    assert cfg._ssm_mixer_params() == 38_744_896 - 2688
    assert cfg.num_ssm_layers == 8
    assert cfg.num_layers_of(EXPERTS) == 8
    assert cfg.num_layers_of(ATTENTION) == 2
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256
    assert attn == 23_399_040 - 2688
    experts = 16 * 2 * 2688 * 1856 + 2 * 2688 * 3712 + 2688 * 128 + 128
    assert experts == 179_948_288 - 2688
    assert cfg.num_params() == (
        8 * (38_744_896 - 2688) + 2 * attn + 8 * experts + 2 * 16384 * 2688
    )
    assert cfg.num_params() == 1_884_426_624 - 19 * 2688
    whole = nemotron_h_config(
        num_layers=52, layer_pattern=nemotron_h.kinds(
            nemotron_h.PUBLISHED_PATTERN
        ),
    )
    assert 31.5e9 < whole.num_params() < 31.7e9
    letters = nemotron_h.PUBLISHED_PATTERN
    assert (letters.count("M"), letters.count("E"), letters.count("*")) == (
        23, 23, 6
    )
    # the run taken: published layers 34-42, the only whole run at 4 : 4 : 1
    assert letters[34:43] == nemotron_h.PERIOD
    runs = [len(run) + 1 for run in letters.split("*")[:-1]]
    # ... and the last nine layers close with an expert layer, not an ``*``
    assert runs == [6, 7, 7, 7, 7, 9] and letters.split("*")[-1] == "EMEMEMEME"


def test_the_initialisers_are_mamba_2s():
    cfg = config()
    tokens = jnp.zeros((1, 16), jnp.int32)
    tree = nn.meta.unbox(
        TransformerLM(cfg).init(jax.random.PRNGKey(0), tokens)
    )["params"]["blocks"]["ssm_1"]["ssm"]
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


def batches(n, batch, seed=0):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, VOCAB, (n, batch, 32 + 1), dtype=np.int32)
    return [{"inputs": r[:, :-1], "targets": r[:, 1:]} for r in rows]


@pytest.mark.parametrize("metrics_lag", [0, 4])
def test_fit_books_one_ssm_event_per_report_from_the_step_itself(
    metrics_lag, monkeypatch, tmp_path
):
    """Ten steps at ``report_every=5``: exactly two ``ssm`` events, of
    steps 5 and 10, carrying the step's own numbers; a ``moe`` event beside
    each; one trace of the step program; the ``compile`` event names the
    scan."""
    from dlrover_tpu.common import telemetry
    from dlrover_tpu.trainer.elastic_trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    monkeypatch.setenv("DLROVER_TPU_JOB", f"ssm_{tmp_path.name}")
    monkeypatch.setenv("DLROVER_TPU_SOCKET_DIR", str(tmp_path / "socks"))
    train_lib.reset_build_cache()
    train_lib.reset_trace_counts()
    batch = jax.device_count()
    with telemetry.recorder().open_tap() as tap:
        was_enabled = telemetry.recorder().enabled
        telemetry.recorder().configure(enabled=True)
        trainer = ElasticTrainer(
            config(ssm_impl="kernel", num_layers=9),
            TrainerConfig(
                global_batch_size=batch, seq_len=32, learning_rate=1e-2,
                optimizer="adafactor", ckpt_every=1000, report_every=5,
                metrics_lag=metrics_lag, warmup_compile=True,
            ),
            client=None,
        )
        seen = {}
        trainer.fit(
            batches(10, batch), max_steps=10,
            on_step=lambda step, metrics: seen.update({
                step: metrics[mamba2.STATS_NAME]
            }),
        )
        taken = tap.take()
        telemetry.recorder().configure(enabled=was_enabled)
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
    (compiled,) = [e for e in taken if e[0] == "compile"]
    assert compiled[-1]["ssm_scan"] == "kernel"


def test_a_model_without_such_a_layer_names_no_scan():
    from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

    stub = type("T", (), {"model_config": TransformerConfig()})()
    assert ElasticTrainer._ssm_scan(stub) == "none"
    stub.model_config = config()
    assert ElasticTrainer._ssm_scan(stub) == "xla"


def test_the_master_renders_the_events_as_gauges():
    from dlrover_tpu.master.speed_monitor import SpeedMonitor
    from dlrover_tpu.master.timeline import JobTimeline

    monitor = SpeedMonitor()
    monitor.record_ssm(
        0, step=5, layers=8, chunk=128, mean_decay=0.8, mean_dt=0.02,
        state_absmax=2.5, later_attr="ignored",
    )
    monitor.record_ssm(
        1, step=5, layers=8, chunk=128, mean_decay=0.6, mean_dt=0.04,
        state_absmax=7.5,
    )
    ledger = monitor.ssm_ledger()
    assert ledger["reporters"] == 2 and ledger["layers"] == 8
    assert ledger["mean_decay"] == pytest.approx(0.7)
    assert ledger["state_absmax"] == 7.5          # the worst replica's
    text = JobTimeline().render_metrics(speed_monitor=monitor)
    for name, value in (
        ("dlrover_ssm_layers", "8"),
        ("dlrover_ssm_chunk", "128"),
        ("dlrover_ssm_mean_decay", "0.7"),
        ("dlrover_ssm_mean_dt", "0.03"),
        ("dlrover_ssm_state_absmax", "7.5"),
        ("dlrover_ssm_reporters", "2"),
    ):
        assert f"# TYPE {name} gauge" in text
        assert any(
            line.startswith(name + " ") and line.split()[1].startswith(value)
            for line in text.splitlines()
        ), name
    # a state that diverged on one replica shows as such, and the linear
    # layers' ledger is its own
    monitor.record_ssm(1, step=10, state_absmax=float("nan"))
    assert np.isnan(monitor.ssm_ledger()["state_absmax"])
    assert monitor.linear_attn_ledger()["reporters"] == 0


def test_the_servicer_routes_the_event_to_the_ledger():
    import inspect

    from dlrover_tpu.master import servicer

    source = inspect.getsource(servicer)
    assert 'name == "ssm"' in source and "record_ssm(node, **attrs)" in source
