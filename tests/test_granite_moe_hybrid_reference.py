"""Granite-4.0-H (ibm-granite/granite-4.0-h-small's layers) against its plain
reference, at a small size on the CPU with seeded float32 weights: per-token
loss, the loss and every gradient; a published layer as two one-branch
layers of a period, the unrolled trunk; the shares of a gated expert layer
under the softmax gate.  Each fault the comparison must catch is
``tests/test_granite_moe_hybrid_sharp.py``'s; the defaults that leave every
other model's step as it was and what the ``compile`` and ``ssm`` events
say ``tests/test_granite_moe_hybrid_system.py``'s; what the configuration
refuses ``tests/test_granite_moe_hybrid_config.py``'s."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_harness as harness
from dlrover_tpu.models import granite_moe_hybrid as granite
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.granite_moe_hybrid import granite_moe_hybrid_config
from dlrover_tpu.models.references import granite_moe_hybrid as ref
from dlrover_tpu.models.transformer import ATTENTION, EXPERTS, SSM
from dlrover_tpu.ops import ssd as ssd_lib

SEQ, BATCH, VOCAB = 40, 2, 128
# float32 on both sides: what is left is the order of the sums
TOL = 1e-4
CHECK = harness.Harness(
    ref, loss_atol=TOL, grad_atol=2e-5, grad_rtol=2e-4,
)
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


def move(name, leaf, draw):
    """The ``D`` of the mixers and the norms' scales moved off their
    initial 1, so that a fault in either shows."""
    if name.endswith("['D']") or name.endswith("['scale']"):
        return leaf + 0.3 * draw(leaf.shape)
    return leaf


@functools.cache
def seeded():
    """(tokens, weights of the uncut model)."""
    rows = harness.tokens(1, BATCH, SEQ, VOCAB)
    return rows, harness.init(config(), rows[0], move=move)


@functools.cache
def share(cfg):
    """The seeded weights cut to ``cfg``'s share of the experts."""
    return harness.held(seeded()[1], cfg)


@pytest.fixture(scope="module")
def tokens():
    return seeded()[0]


@pytest.fixture(scope="module")
def params():
    return seeded()[1]


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


@pytest.mark.parametrize("case", ["xla", "kernels_a_share"])
def test_loss_and_every_gradient_match_the_reference(case, tokens):
    cfg = config(**CASES[case])
    CHECK.loss_and_every_gradient_match(cfg, share(cfg), tokens)


@pytest.mark.parametrize("case", sorted(CASES))
def test_token_nll_matches_the_reference(case, tokens):
    cfg = config(**CASES[case])
    if cfg.ssm_impl == "kernel":
        # the one group of 16 heads is cut into two tiles of 8
        assert ssd_lib.heads_per_step(16, 64, 1, 16, 16, jnp.float32) == 8
    assert CHECK.nll_gap(cfg, share(cfg), tokens) <= TOL


def test_the_unrolled_trunk_is_the_scanned_one(params, tokens):
    cfg = config(scan_layers=False)
    unrolled = {k: v for k, v in params.items() if k != "blocks"}
    layers = ref.trunk_layers(dataclasses.asdict(config()), params)
    assert [kind for kind, _ in layers] == list(granite.kinds(TYPES)) * 2
    for i, (_, layer) in enumerate(layers):
        unrolled[f"block_{i}"] = layer
    got = CHECK.nll(cfg, unrolled, tokens)
    want = CHECK.nll(config(), params, tokens)
    np.testing.assert_allclose(got, want, atol=TOL)
    assert CHECK.nll_gap(cfg, unrolled, tokens) <= TOL


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
    with jax.default_matmul_precision("highest"):
        shared = ref.shared_part(n, whole["shared"])

    def balance(aux, term):
        # the balance term is the router's, whatever the share
        assert float(aux) == pytest.approx(float(term), rel=1e-5)

    harness.shares_add_up(
        ref, dict(num_experts=total, top_k=k), n, whole, held_here,
        lambda first: moe_lib.MoEMlp(
            num_experts=total, d_ff=width, top_k=k, dispatch="grouped",
            activation="swiglu", scoring="softmax", norm_topk_prob=True,
            aux_form="topk", experts_held=held_here, first_expert=first,
            shared_d_ff=shared_width, row_budget_multiple=4.0,
            dtype=jnp.float32, gmm_block_rows=8,
        ),
        shared, TOL, balance,
    )
