"""Granite-4.0-H (ibm-granite/granite-4.0-h-small's layers) against its plain
reference, at a small size on the CPU with seeded float32 weights: per-token
loss, the loss and every gradient; a published layer as two one-branch
layers of a period, the unrolled trunk; each fault the comparison must
catch; the shares of a gated expert layer under the softmax gate; the
defaults that leave every other model's step as it was; what the
``compile`` and ``ssm`` events say."""

import dataclasses
import hashlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import granite_moe_hybrid as granite
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.granite_moe_hybrid import granite_moe_hybrid_config
from dlrover_tpu.models.nemotron_h import nemotron_h_config
from dlrover_tpu.models.references import granite_moe_hybrid as ref
from dlrover_tpu.models.transformer import (
    ATTENTION, EXPERTS, SSM, TransformerConfig, TransformerLM,
)
from dlrover_tpu.ops import ssd as ssd_lib

SEQ, BATCH, VOCAB = 40, 2, 128
# float32 on both sides: what is left is the order of the sums
TOL = 1e-4
GRAD_ATOL, GRAD_RTOL = 2e-5, 2e-4
# two mamba layers to one attention layer, each with its expert layer: six
# of the program's layers a period, two periods
TYPES = ("mamba", "mamba", "attention")


def config(**overrides):
    base = dict(
        vocab_size=VOCAB, num_layers=12, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=48, max_seq_len=64,
        layer_pattern=granite.kinds(TYPES), attention_scale=1.0 / 16,
        # ONE group of 16 heads: two tiles of 8 where the kernel runs
        ssm_num_heads=16, ssm_head_dim=64, ssm_state_size=16, ssm_groups=1,
        ssm_chunk=16, num_experts=8, top_k=3, moe_d_ff=32,
        shared_expert_d_ff=48, moe_aux_weight=0.01,
        # a table of 0.02 under logits / 16 is a uniform prediction whatever
        # the trunk does: at this width the check needs logits that move
        embed_init_std=0.1, logit_scale=0.5,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    base.update(overrides)
    return granite_moe_hybrid_config(**base)


@pytest.fixture(scope="module")
def tokens():
    rows = jax.random.randint(
        jax.random.PRNGKey(1), (BATCH, SEQ + 1), 0, VOCAB
    )
    return rows[:, :-1], rows[:, 1:]


def init(cfg, inputs, seed=0):
    """Seeded weights; the ``D`` of the mixers and the norms' scales moved
    off their initial 1, so that a fault in either shows."""
    params = nn.meta.unbox(
        TransformerLM(cfg).init(jax.random.PRNGKey(seed), inputs)
    )["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 1000))

    def move(path, leaf):
        name = jax.tree_util.keystr(path)
        if name.endswith("['D']") or name.endswith("['scale']"):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
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
    logits, aux = TransformerLM(cfg).apply({"params": params}, inputs)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(
        logp, targets[..., None], -1
    )[..., 0].mean() + aux


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
    """``params`` with each expert layer's ``wi`` / ``wg`` / ``wo`` cut to
    the config's share."""
    first, count = cfg.first_expert, cfg.resolved_experts_held

    def cut(path, leaf):
        name = jax.tree_util.keystr(path)
        if any(f"['moe']['{w}']" in name for w in ("wi", "wg", "wo")):
            return leaf[:, first:first + count]
        return leaf

    return jax.tree_util.tree_map_with_path(cut, params)


def test_a_published_layer_is_two_of_the_programs(params):
    """``layer_types`` maps to a mixer's branch and an expert layer's, in
    order; the published period of ten is twenty kinds, nine ``ssm`` to one
    ``attention`` with ``experts`` after each; the tree has a slot per
    position, stacked over the periods."""
    assert granite.kinds(TYPES) == (
        SSM, EXPERTS, SSM, EXPERTS, ATTENTION, EXPERTS
    )
    period = granite.kinds(granite.PERIOD)
    assert len(period) == 20 and period[1::2] == (EXPERTS,) * 10
    assert period[0::2] == (SSM,) * 5 + (ATTENTION,) + (SSM,) * 4
    assert granite.PUBLISHED_LAYER_TYPES.count("attention") == 4
    assert [
        i for i, t in enumerate(granite.PUBLISHED_LAYER_TYPES)
        if t == "attention"
    ] == [5, 15, 25, 35]
    full = granite_moe_hybrid_config()
    assert full.num_layers == 80 and full.num_scan_units == 4
    assert sorted(params["blocks"]) == sorted([
        "ssm_0", "experts_1", "ssm_2", "experts_3", "attention_4",
        "experts_5",
    ])
    assert params["blocks"]["ssm_0"]["ssm"]["in_proj"]["kernel"].shape == (
        2, 64, 2 * 1024 + 2 * 16 + 16
    )
    assert set(params["blocks"]["experts_1"]["moe"]) == {
        "router", "wi", "wg", "wo", "shared",
    }
    assert "lm_head" not in params and "pos_embedding" not in params


@pytest.mark.parametrize("case", sorted(CASES))
def test_token_nll_matches_the_reference(case, params, tokens):
    cfg = config(**CASES[case])
    if cfg.ssm_impl == "kernel":
        # the one group of 16 heads is cut into two tiles of 8
        assert ssd_lib.heads_per_step(16, 64, 1, 16, 16, jnp.float32) == 8
    assert nll_gap(cfg, held(params, cfg), tokens) <= TOL


@pytest.mark.parametrize("case", ["xla", "kernels_a_share"])
def test_loss_and_every_gradient_match_the_reference(case, params, tokens):
    cfg = config(**CASES[case])
    ours = held(params, cfg)
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
        assert float(jnp.abs(w).max()) > 0, name


def test_the_unrolled_trunk_is_the_scanned_one(params, tokens):
    cfg = config(scan_layers=False)
    unrolled = {k: v for k, v in params.items() if k != "blocks"}
    layers = ref.trunk_layers(dataclasses.asdict(config()), params)
    assert [kind for kind, _ in layers] == list(granite.kinds(TYPES)) * 2
    for i, (_, layer) in enumerate(layers):
        unrolled[f"block_{i}"] = layer
    got = program_nll(cfg, unrolled, *tokens)
    want = program_nll(config(), params, *tokens)
    np.testing.assert_allclose(got, want, atol=TOL)
    assert nll_gap(cfg, unrolled, tokens) <= TOL


# each fault, and the program's own switch that makes the faulty reference
# agree again (where the program has one)
WRONG = {
    "no_embedding_multiplier": dict(embed_scale=1.0),
    "no_attention_multiplier": dict(attention_scale=1.0),
    "no_residual_multiplier": dict(residual_scale=1.0),
    "no_logits_scaling": dict(logit_scale=1.0),
    "sqrt_scale": dict(attention_scale=0.0),
    "rotate": dict(position="rope"),
    "softmax_all": dict(norm_topk_prob=False),
    "own_bc": None,
    "norm_before_gate": None,
    "ungated_expert": None,
    "no_shared": None,
    "untied_head": None,
}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_the_check_is_sharp(wrong, params, tokens):
    """Each fault, made on one side, moves a token's loss past the
    tolerance the tests above hold."""
    assert nll_gap(config(), params, tokens, wrong=wrong) > 10 * TOL, wrong
    switch = WRONG[wrong]
    if switch is not None:
        # a program with that switch set is the faulty reference's model
        got = program_nll(config(**switch), params, *tokens)

        def gap(**kw):
            want = ref.token_nll(config(), params, *tokens, **kw)
            return float(jnp.abs(got - want).max())

        assert gap() > 10 * TOL
        assert gap(wrong=wrong) <= TOL


def test_the_reference_computed_lower_is_another_result(params, tokens):
    fields = dataclasses.asdict(config())
    exact = ref.token_nll(fields, params, *tokens)
    for lowered, least in (("router", TOL / 10), ("ssm", TOL),
                           ("all", 100 * TOL)):
        other = ref.token_nll(fields, params, *tokens, lowered)
        assert float(jnp.abs(other - exact).mean()) > least, lowered


@pytest.mark.parametrize("held_here,total", [(9, 72), (2, 16), (4, 16)])
def test_the_shares_add_up_to_the_uncut_layer(held_here, total):
    """The routed parts of all the shares of one gated layer under the
    softmax gate (eight of 9 of 72, eight of two, four of four), plus the
    shared expert counted once, are the uncut reference's layer."""
    d, width, shared_width, k = 64, 32, 48, 10 if total == 72 else 6
    keys = jax.random.split(jax.random.PRNGKey(5), 10)
    n = jax.random.normal(keys[0], (BATCH, SEQ, d))

    def kernel(key, shape):
        return {"kernel": 0.1 * jax.random.normal(key, shape)}

    whole = {
        "router": {"kernel": jax.random.normal(keys[1], (d, total))},
        "wi": 0.1 * jax.random.normal(keys[3], (total, d, width)),
        "wg": 0.1 * jax.random.normal(keys[7], (total, d, width)),
        "wo": 0.1 * jax.random.normal(keys[4], (total, width, d)),
        "shared": {
            "wi": kernel(keys[5], (d, shared_width)),
            "wg": kernel(keys[8], (d, shared_width)),
            "wo": kernel(keys[6], (shared_width, d)),
        },
    }
    fields = dict(num_experts=total, top_k=k)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(fields, n, whole)
        shared = ref.shared_part(n, whole["shared"])
        got = shared
        seen = 0.0
        for first in range(0, total, held_here):
            layer = moe_lib.MoEMlp(
                num_experts=total, d_ff=width, top_k=k, dispatch="grouped",
                activation="swiglu", scoring="softmax", norm_topk_prob=True,
                aux_form="topk", experts_held=held_here, first_expert=first,
                shared_d_ff=shared_width, row_budget_multiple=4.0,
                dtype=jnp.float32, gmm_block_rows=8,
            )
            part = dict(whole, **{
                w: whole[w][first:first + held_here]
                for w in ("wi", "wg", "wo")
            })
            (out, aux), sown = layer.apply(
                {"params": part}, n, mutable=["intermediates"]
            )
            stats = sown["intermediates"]
            assert float(moe_lib.split_stats(stats["moe_stats"][0])[1]) == 0.0
            seen += float(stats[moe_lib.SHARE_STATS_NAME][0][0])
            # what every chip computes alike is counted once
            got = got + (out - shared)
            ours, balance = ref.routed_part(
                dict(fields, first_expert=first), n, part
            )
            np.testing.assert_allclose(out - shared, ours, atol=TOL)
            # the balance term is the router's, whatever the share
            assert float(aux) == pytest.approx(float(balance), rel=1e-5)
    np.testing.assert_allclose(got, want, atol=TOL)
    assert seen == pytest.approx(1.0)


# sha256 of the lowered step text of the two tiny presets that
# tests/test_olmo_hybrid_reference.py does not hold, at the parent commit
# fed8b01 (its ``lowered_step_text``): JoyAI-LLM-Flash's (latent attention,
# the sigmoid router, a share) and Nemotron's, whose text holds its scan
# kernels' grids and index maps (one tile a group).  Nemotron's is the text
# since PR 40, which rewrote the scan kernels' bodies (``ops/ssd.py``: at
# fed8b01 it read 1b8a7fa8...d4e7b3); whoever edits those kernels next
# re-pins it, and JoyAI's says that nothing else in the step moved.
LOWERED_AT_PARENT = {
    "joyai-llm-flash":
        "680dda303fa8cbf13fc776cde453e40c8602a628464f5231c931c2402835c48d",
    "nemotron-3-nano-30b-a3b":
        "1ac0af4f4c3def1f692d608f179378d391e7055c6795817117ad3f15e16b47b4",
}


@pytest.mark.parametrize("preset", sorted(LOWERED_AT_PARENT))
def test_the_multipliers_and_the_tiles_default_to_nothing(preset):
    """A config that names none of the four multipliers, and a scan whose
    group is one grid step, lower to the step they lowered to (the other
    four pinned texts are held by tests/test_olmo_hybrid_reference.py)."""
    from test_olmo_hybrid_reference import lowered_step_text

    cfg = TransformerConfig()
    assert (cfg.embed_scale, cfg.attention_scale, cfg.residual_scale,
            cfg.logit_scale) == (1.0, 0.0, 1.0, 1.0)
    text = lowered_step_text(preset)
    assert hashlib.sha256(text.encode()).hexdigest() == (
        LOWERED_AT_PARENT[preset]
    )


@pytest.mark.parametrize("overrides,message", [
    (dict(ssm_impl="kernel", ssm_chunk=2048), "heads_per_step"),
    (dict(decode=True), "decode=True with an ssm layer"),
    (dict(experts_held=5), "must divide num_experts"),
])
def test_bad_combinations_raise(overrides, message):
    with pytest.raises(ValueError, match=message):
        config(**overrides)


def test_the_published_widths_count_what_the_issue_counts():
    """The whole model is 32 B; the cell's cut (one period, 9 of 72
    experts, an eighth of the vocabulary) 2,054,945,408 parameters without
    the layers' norms, the issue's 2,055,031,424 with them."""
    full = granite_moe_hybrid_config()
    assert 32.0e9 < full.num_params() < 32.5e9
    cut = granite_moe_hybrid_config(
        num_layers=20, experts_held=9, vocab_size=12544
    )
    assert cut._ssm_mixer_params() == 102_286_976
    assert cut.num_params() == 2_054_945_408
    assert cut.num_params() + 21 * 4096 == 2_055_031_424


def test_fit_books_the_scan_cut_and_the_row_moves(monkeypatch, tmp_path):
    """Five steps at ``report_every=5`` through ``ElasticTrainer``: the
    ``compile`` event says the scan is the kernel's, eight heads a grid
    step, and which path a token's rows take; the ``ssm`` event carries
    the heads and the one group; a ``moe`` event beside it with nothing
    dropped; the loss is finite and the step traced once."""
    from dlrover_tpu.common import telemetry
    from dlrover_tpu.trainer import train_lib
    from dlrover_tpu.trainer.elastic_trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    monkeypatch.setenv("DLROVER_TPU_JOB", f"granite_{tmp_path.name}")
    monkeypatch.setenv("DLROVER_TPU_SOCKET_DIR", str(tmp_path / "socks"))
    train_lib.reset_build_cache()
    train_lib.reset_trace_counts()
    batch = jax.device_count()
    rng = np.random.default_rng(0)
    rows = rng.integers(0, VOCAB, (5, batch, 32 + 1), dtype=np.int32)
    with telemetry.recorder().open_tap() as tap:
        was_enabled = telemetry.recorder().enabled
        telemetry.recorder().configure(enabled=True)
        trainer = ElasticTrainer(
            config(
                ssm_impl="kernel", num_layers=6, experts_held=4,
                moe_row_budget=4.0,
            ),
            TrainerConfig(
                global_batch_size=batch, seq_len=32, learning_rate=1e-2,
                optimizer="adafactor", ckpt_every=1000, report_every=5,
                warmup_compile=True,
            ),
            client=None,
        )
        losses = {}
        trainer.fit(
            [{"inputs": r[:, :-1], "targets": r[:, 1:]} for r in rows],
            max_steps=5,
            on_step=lambda step, m: losses.update({step: float(m["loss"])}),
        )
        taken = tap.take()
        telemetry.recorder().configure(enabled=was_enabled)
    assert sorted(losses) == [1, 2, 3, 4, 5]
    assert all(np.isfinite(v) for v in losses.values())
    assert train_lib.trace_count("train_step") == 1
    (compiled,) = [e for e in taken if e[0] == "compile"]
    assert compiled[-1]["ssm_scan"] == "kernel"
    assert compiled[-1]["ssm_heads_per_step"] == 8
    assert compiled[-1]["ssm_tiles_per_group"] == 2
    assert compiled[-1]["row_moves"] == "xla"      # rows of 64 are no tile
    (ssm,) = [e for e in taken if e[0] == "ssm" and e[1] == "event"]
    assert ssm[4]["heads"] == 16 and ssm[4]["groups"] == 1
    assert ssm[4]["layers"] == 2 and ssm[4]["chunk"] == 16
    (moe,) = [e for e in taken if e[0] == "moe" and e[1] == "event"]
    assert moe[4]["drop_fraction"] == 0.0
    # XLA's gather fetches every one of a token's rows, the zero row too
    assert moe[4]["row_fetch_share"] == 1.0 > moe[4]["pairs_here"]


@pytest.mark.parametrize("model,scan,heads,tiles,rows", [
    # the published widths: the fetch-and-sum takes ten rows of 4,096;
    # ONE group of 128 heads is sixteen grid steps of 8
    (lambda: granite_moe_hybrid_config(ssm_impl="kernel"), "kernel", 8, 16,
     "kernel"),
    (lambda: granite_moe_hybrid_config(ssm_impl="kernel", ssm_chunk=128),
     "kernel", 8, 16, "kernel"),
    (lambda: granite_moe_hybrid_config(), "xla", None, None, "kernel"),
    # the cell's cut: 9 of the 72 experts here, so most of a token's ten
    # pairs have no row here and the kernel is handed those that have
    (lambda: granite_moe_hybrid_config(
        ssm_impl="kernel", num_layers=20, experts_held=9, vocab_size=12544,
    ), "kernel", 8, 16, "kernel_live"),
    (lambda: TransformerConfig(
        d_model=2048, num_heads=16, num_experts=256, experts_held=32,
        top_k=8, moe_dispatch="grouped",
    ), "none", None, None, "kernel_live"),
    (lambda: TransformerConfig(
        d_model=2048, num_heads=16, num_experts=64, top_k=8,
        moe_dispatch="grouped",
    ), "none", None, None, "kernel"),
    # Nemotron-3-Nano's rows of 2,688 are no whole native tiles: its
    # row moves are XLA's gather and reduction; a group of 8 heads is one
    # grid step
    (lambda: nemotron_h_config(ssm_impl="kernel"), "kernel", 8, 1, "xla"),
    (lambda: nemotron_h_config(ssm_impl="kernel", experts_held=16),
     "kernel", 8, 1, "xla"),
    (lambda: TransformerConfig(), "none", None, None, "none"),
    (lambda: TransformerConfig(num_experts=8, moe_dispatch="einsum"),
     "none", None, None, "none"),
])
def test_the_compile_event_asks_what_the_dispatch_asks(
    model, scan, heads, tiles, rows
):
    from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

    class Stub:
        model_config = model()
        _ssm_scan = ElasticTrainer._ssm_scan
        _ssm_heads_per_step = ElasticTrainer._ssm_heads_per_step

    stub = Stub()
    assert ElasticTrainer._ssm_scan(stub) == scan
    assert ElasticTrainer._ssm_heads_per_step(stub) == heads
    assert ElasticTrainer._ssm_tiles_per_group(stub) == tiles
    assert ElasticTrainer._row_moves(stub) == rows


def test_the_mixers_output_projection_starts_rescaled_where_asked(tokens):
    """``ssm_out_init_scale`` scales ``out_proj``'s initial std over lecun
    normal's and touches nothing else; 1 (the default) is lecun normal."""
    plain = init(config(), tokens[0])["blocks"]["ssm_0"]["ssm"]
    scaled = nn.meta.unbox(TransformerLM(
        config(ssm_out_init_scale=0.25)
    ).init(jax.random.PRNGKey(0), tokens[0]))["params"]["blocks"]["ssm_0"]["ssm"]
    fan_in = plain["out_proj"]["kernel"].shape[1]
    assert float(plain["out_proj"]["kernel"].std()) == pytest.approx(
        fan_in ** -0.5, rel=0.05
    )
    assert float(scaled["out_proj"]["kernel"].std()) == pytest.approx(
        0.25 * fan_in ** -0.5, rel=0.05
    )
    np.testing.assert_array_equal(
        scaled["in_proj"]["kernel"], plain["in_proj"]["kernel"]
    )
    assert TransformerConfig().ssm_out_init_scale == 1.0
