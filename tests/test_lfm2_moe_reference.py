"""LFM2-8B-A1B's model (three gated short convolutions to one grouped-query
attention under a per-head QK norm, a sigmoid router on score + bias over a
share of the experts, a dense prefix ahead of the patterned trunk) against
its plain reference, at a small size on the CPU with seeded float32
weights: per-token loss, the loss and every gradient; the gated
convolution's core against the written-out sum on its own; four shares of 8
of 32 experts.  Each fault the comparison must catch is
``tests/test_lfm2_moe_sharp.py``'s; the train step, the events and the
scopes ``tests/test_lfm2_moe_system.py``'s; what the configuration refuses
and counts ``tests/test_lfm2_moe_config.py``'s."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_harness as harness
from dlrover_tpu.models import gated_conv
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.lfm2_moe import lfm2_moe_config
from dlrover_tpu.models.references import lfm2_moe as ref

SEQ, BATCH, VOCAB = 40, 2, 256
# float32 on both sides under matmul precision "highest": what is left is
# the order of the sums (sorted rows, one head or one expert at a time), a
# few float32 ulps of a loss of ~5.5.  1e-4 is well under what the smallest
# fault moves (tests/test_lfm2_moe_sharp.py).
TOL = 1e-4
# b picks, it never weighs: no gradient reaches it
CHECK = harness.Harness(
    ref, loss_atol=TOL, grad_atol=2e-5, grad_rtol=2e-4,
    no_gradient=("router_bias",),
)

# one dense layer (conv: the pattern continued backwards) and TWO periods
# of attention, conv, conv, conv; heads of 16, four over two; 32 experts, 4
# a token, 8 held from the second share on
SMALL = dict(
    vocab_size=VOCAB, num_layers=9, first_k_dense=1, d_model=64, num_heads=4,
    num_kv_heads=2, d_ff=96, max_seq_len=48, rope_theta=1e4, moe_d_ff=32,
    experts_held=8, first_expert=8, moe_row_budget=3.0,
    dtype=jnp.float32, param_dtype=jnp.float32,
)


def config(**overrides):
    return lfm2_moe_config(**{**SMALL, **overrides})


def move(name, leaf, draw):
    """Router biases that are not zero (the choice on ``s + b`` then
    differs from the choice on ``s``), norm scales, the per-head ones too,
    off their initial 1, and experts and routers large enough beside the
    mixers that a fault in the gates moves a token's loss (at the program's
    0.02 an expert layer adds a hundredth of what a mixer adds)."""
    if "router_bias" in name:
        return 0.3 * draw(leaf.shape)
    if "['moe']" in name:
        return leaf * 4.0
    if name.endswith("['scale']"):
        return leaf + 0.3 * draw(leaf.shape)
    return leaf


@functools.cache
def seeded():
    """(tokens, weights of the uncut model)."""
    rows = harness.tokens(1, BATCH, SEQ, VOCAB)
    whole = config(experts_held=0, first_expert=0)
    return rows, harness.init(whole, rows[0], move=move)


@functools.cache
def share(cfg):
    """The seeded weights cut to ``cfg``'s share of the experts."""
    return harness.held(seeded()[1], cfg)


@pytest.fixture(scope="module")
def tokens():
    return seeded()[0]


@pytest.fixture(scope="module")
def params():
    return share(config())


CASES = {
    "share": {},
    "whole": dict(experts_held=0, first_expert=0),
    "flash": dict(
        attention_impl="flash", flash_block_q=8, flash_block_kv=8,
        num_layers=5,
    ),
    "two_dense_one_period": dict(num_layers=6, first_k_dense=2),
}
# the cases held to every gradient as well (the others to each token's
# loss: what they vary is the share or the prefix, whose gradients these
# two cover)
GRADIENTS = ("share", "flash")


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_matches_the_reference_in_float32(case, tokens):
    cfg = config(**CASES[case])
    if cfg.num_layers == SMALL["num_layers"]:
        weights = share(cfg)
    else:
        weights = harness.init(cfg, tokens[0], seed=2, move=move)
    if case not in GRADIENTS:
        assert CHECK.nll_gap(cfg, weights, tokens) <= TOL
        return
    _, (main, aux, _), _ = CHECK.loss_and_grads(cfg, weights, tokens)
    want = CHECK.reference("forward", cfg, weights, tokens)
    np.testing.assert_allclose(main, want["nll"], atol=TOL)
    assert float(aux) == 0.0
    CHECK.loss_and_every_gradient_match(cfg, weights, tokens)


def test_the_unrolled_trunk_is_the_scanned_one(tokens):
    """``scan_layers=False`` names its layers ``block_<i>`` after the dense
    prefix; layer i's kind is the pattern's from the prefix on."""
    cfg = config(scan_layers=False, num_layers=5)
    weights = harness.init(cfg, tokens[0], seed=3, move=move)
    assert "block_1" in weights and "block_5" not in weights
    assert "attn" in weights["block_1"] and "moe" in weights["block_1"]
    assert "conv" in weights["block_2"] and "moe" in weights["block_2"]
    assert "conv" in weights["dense_0"] and "mlp" in weights["dense_0"]
    assert CHECK.nll_gap(cfg, weights, tokens) <= TOL


# -- the core on its own -------------------------------------------------------


def _core_by_hand(x, taps):
    """``C[t] * sum_j taps[j] (B z)[t - 2 + j]`` a token at a time."""
    x, taps = np.asarray(x, np.float64), np.asarray(taps, np.float64)
    d = x.shape[-1] // 3
    b, c, z = x[..., :d], x[..., d: 2 * d], x[..., 2 * d:]
    k = taps.shape[0]
    out = np.zeros_like(b)
    for t in range(x.shape[1]):
        for j in range(k):
            at = t - (k - 1) + j
            if at >= 0:
                out[:, t] += taps[j] * b[:, at] * z[:, at]
    return c * out


@pytest.mark.parametrize("k", [3, 4])
def test_the_core_and_its_three_cotangents_are_the_written_out_sum(k):
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(keys[0], (2, 9, 3 * 8))
    taps = jax.random.normal(keys[1], (k, 8))
    dy = jax.random.normal(keys[2], (2, 9, 8))
    np.testing.assert_allclose(
        gated_conv.gated_conv(x, taps), _core_by_hand(x, taps), atol=1e-5
    )
    # t < K - 1 reads zeros, never another row of the batch
    np.testing.assert_allclose(
        gated_conv.gated_conv(x, taps)[1, 0],
        np.asarray(x[1, 0, 8:16] * taps[-1] * x[1, 0, :8] * x[1, 0, 16:]),
        atol=1e-6,
    )

    def plain(x, taps):
        b, c, z = x[..., :8], x[..., 8:16], x[..., 16:]
        return c * ref.short_conv(b * z, taps)

    got = jax.vjp(gated_conv.gated_conv, x, taps)[1](dy)
    want = jax.vjp(plain, x, taps)[1](dy)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5)


def test_the_core_rounds_once_in_bfloat16():
    """bfloat16 in and out, float32 between: the result is the float32
    core's, rounded."""
    keys = jax.random.split(jax.random.PRNGKey(6), 2)
    x = jax.random.normal(keys[0], (2, 16, 3 * 8)).astype(jnp.bfloat16)
    taps = jax.random.normal(keys[1], (3, 8)).astype(jnp.bfloat16)
    got = gated_conv.gated_conv(x, taps)
    want = gated_conv.gated_conv(
        x.astype(jnp.float32), taps.astype(jnp.float32)
    ).astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- the shares ----------------------------------------------------------------


def test_four_shares_of_8_of_32_add_up_to_the_uncut_layer():
    """The routed parts of the four shares of one layer (experts 0-7, 8-15,
    16-23, 24-31) are the uncut reference's layer; nothing is dropped and
    the shares' pairs add up to all.  No shared expert: nothing is counted
    once."""
    total, held, d, width = 32, 8, 32, 16
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    n = jax.random.normal(keys[0], (BATCH, 32, d))
    whole = {
        "router": {"kernel": jax.random.normal(keys[1], (d, total))},
        "router_bias": 0.05 * jax.random.normal(keys[2], (total,)),
        "wi": 0.2 * jax.random.normal(keys[3], (total, d, width)),
        "wg": 0.2 * jax.random.normal(keys[4], (total, d, width)),
        "wo": 0.2 * jax.random.normal(keys[5], (total, width, d)),
    }
    fields = dict(
        num_experts=total, top_k=4, norm_topk_prob=True,
        routed_scaling_factor=1.0, router_norm_eps=1e-6,
    )
    harness.shares_add_up(
        ref, fields, n, whole, held,
        lambda first: moe_lib.MoEMlp(
            num_experts=total, d_ff=width, top_k=4, dispatch="grouped",
            scoring="sigmoid", router_bias=True, routed_scale=1.0,
            experts_held=held, first_expert=first, router_norm_eps=1e-6,
            row_budget_multiple=8.0, dtype=jnp.float32, gmm_block_rows=8,
        ),
        jnp.zeros_like(n), TOL,
    )


def test_the_renormalising_sum_s_epsilon_is_the_field_s():
    """Gates of two chosen scores ``s`` are ``s / (sum + eps)``: 1e-6 here,
    1e-20 for every model that does not set it."""
    scores = jnp.asarray([[[0.6, 0.2, 0.1, 0.05]]])
    logits = jnp.log(scores / (1 - scores))
    for eps in (1e-20, 1e-6, 0.2):
        vals, idx, _ = moe_lib._gate(
            logits, 2, True, "top1", "sigmoid", None, 1.0, norm_eps=eps
        )
        assert np.asarray(idx)[0, 0].tolist() == [0, 1]
        np.testing.assert_allclose(
            np.asarray(vals)[0, 0], np.array([0.6, 0.2]) / (0.8 + eps),
            rtol=1e-6,
        )
