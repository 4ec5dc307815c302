"""Mellum2-12B-A2.5B's model (three sliding-window attention layers to one
full-attention layer, each kind with its own rotation: plain RoPE under the
window, YaRN on the full layers; a softmax router over a share of the
experts) against its plain reference, at a small size on the CPU with
seeded float32 weights: per-token loss, the loss and every gradient; four
shares of 16 of 64 experts; the rotation's table.  Each fault the comparison
must catch is ``tests/test_mellum_sharp.py``'s; the train step, the events
and the scopes ``tests/test_mellum_system.py``'s; what the configuration
refuses and counts ``tests/test_mellum_config.py``'s."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_harness as harness
from dlrover_tpu.models import layers
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.mellum import mellum_config
from dlrover_tpu.models.references import mellum as ref

SEQ, BATCH, VOCAB = 40, 2, 256
# float32 on both sides under matmul precision "highest": what is left is
# the order of the sums (a head, a block of rows or an expert at a time).
TOL = 1e-4
CHECK = harness.Harness(ref, loss_atol=TOL, grad_atol=2e-5, grad_rtol=2e-4)

# TWO periods of sliding x 3, full; heads of 16, four over two; a window of
# 12 keys (no multiple of the flash cases' blocks of 8, and wider than one);
# YaRN over an original 16 positions (the sequence is 40) whose ramp lies
# inside the head's 8 columns: 0-1 keep their frequency, 2-4 ramp, 5-7 are
# interpolated; 64 experts, 8 a token, 16 held from the second share on
SMALL = dict(
    vocab_size=VOCAB, num_layers=8, d_model=64, num_heads=4, num_kv_heads=2,
    head_dim=16, d_ff=96, max_seq_len=64, rope_theta=100.0, moe_d_ff=32,
    sliding_window=12, rope_scaling_factor=4.0,
    rope_original_max_position=16, rope_beta_fast=1.0, rope_beta_slow=0.25,
    rope_attention_factor=1.3, experts_held=16, first_expert=16,
    moe_row_budget=3.0, dtype=jnp.float32, param_dtype=jnp.float32,
)


def config(**overrides):
    return mellum_config(**{**SMALL, **overrides})


def move(name, leaf, draw):
    """Queries and keys large enough that the softmax is peaked (a mask or
    a rotation then moves a token's loss), experts and routers large enough
    beside the mixers that a fault in the gates does, norm scales off 1."""
    if "['query']" in name or "['key']" in name:
        return leaf * 4.0
    if "['moe']" in name:
        return leaf * 4.0
    if name.endswith("['scale']"):
        return leaf + 0.3 * draw(leaf.shape)
    return leaf


@functools.cache
def seeded(layers_=8):
    """(tokens, weights of the uncut model)."""
    rows = harness.tokens(1, BATCH, SEQ, VOCAB)
    whole = config(experts_held=0, first_expert=0, num_layers=layers_)
    return rows, harness.init(whole, rows[0], move=move)


@functools.cache
def share(cfg):
    """The seeded weights cut to ``cfg``'s share of the experts."""
    return harness.held(seeded(cfg.num_layers)[1], cfg)


@pytest.fixture(scope="module")
def tokens():
    return seeded()[0]


CASES = {
    "share": {},
    "whole": dict(experts_held=0, first_expert=0, num_layers=4),
    # blocks of 8 under a window of 12: wider than a block, no multiple
    "flash": dict(
        attention_impl="flash", flash_block_q=8, flash_block_kv=8,
        num_layers=4,
    ),
    # a window SMALLER than the block: one block carries both edges
    "flash_narrow": dict(
        attention_impl="flash", flash_block_q=8, flash_block_kv=8,
        num_layers=4, sliding_window=5,
    ),
}
GRADIENTS = ("share", "flash")


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_matches_the_reference_in_float32(case, tokens):
    cfg = config(**CASES[case])
    weights = share(cfg)
    if case not in GRADIENTS:
        assert CHECK.nll_gap(cfg, weights, tokens) <= TOL
        return
    _, (main, aux, _), _ = CHECK.loss_and_grads(cfg, weights, tokens)
    want = CHECK.reference("forward", cfg, weights, tokens)
    np.testing.assert_allclose(main, want["nll"], atol=TOL)
    np.testing.assert_allclose(
        aux, cfg.moe_aux_weight * want["balance"], atol=1e-6
    )
    CHECK.loss_and_every_gradient_match(cfg, weights, tokens)


def test_the_unrolled_trunk_is_the_scanned_one(tokens):
    cfg = config(scan_layers=False, num_layers=4)
    weights = harness.init(cfg, tokens[0], seed=3, move=move)
    assert "block_3" in weights and "block_4" not in weights
    assert CHECK.nll_gap(cfg, weights, tokens) <= TOL


# -- the rotation ---------------------------------------------------------------


def test_yarn_s_table_is_the_reference_s_and_meets_all_three_ranges():
    cfg = config()
    assert layers.yarn_range(16, 100.0, 16, 1.0, 0.25) == (1, 5)
    assert ref.yarn_range(
        dict(head_dim=16, rope_theta=100.0, rope_original_max_position=16,
             rope_beta_fast=1.0, rope_beta_slow=0.25)
    ) == (1, 5)
    inv, factor = cfg.rotation().table(16)
    plain, one = cfg.rotation("sliding_attention").table(16)
    assert (factor, one) == (1.3, 1.0)
    ratio = np.asarray(inv / plain)
    np.testing.assert_allclose(ratio[:2], 1.0)              # kept
    assert (np.diff(ratio[1:6]) < 0).all()                  # the ramp
    np.testing.assert_allclose(ratio[5:], 0.25, rtol=1e-6)  # interpolated
    fields = {k: getattr(cfg, k) for k in (
        "head_dim", "rope_theta", "rope_scaling_factor", "rope_beta_fast",
        "rope_original_max_position", "rope_beta_slow",
        "rope_attention_factor",
    )}
    want, on_cos, on_sin = ref.rotation_table(fields, "yarn")
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert (on_cos, on_sin) == (1.3, 1.3)


def test_plain_rope_s_callers_read_the_numbers_they_read(rng):
    """``rotary_embedding`` takes a table now: at plain RoPE's frequencies
    and a factor of 1 it is, to the bit, what it was when it computed
    ``theta ** (-i / half)`` inside."""
    q = jnp.asarray(rng.normal(size=(2, 24, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 24, 2, 16)), jnp.float32)
    positions = jnp.arange(24)[None, :]

    def as_it_was(x, theta):
        half = x.shape[-1] // 2
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
        angles = positions[..., None].astype(jnp.float32) * freqs
        cos = jnp.cos(angles)[:, :, None, :]
        sin = jnp.sin(angles)[:, :, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate(
            (x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1
        )

    for theta in (10000.0, 500000.0):
        got = layers.rotary_embedding(
            q, k, positions, *layers.Rotation(theta).table(16)
        )
        for g, x in zip(got, (q, k)):
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(as_it_was(x, theta))
            )
        got = layers.rotary_embedding(
            q, k, positions, layers.rope_frequencies(8, theta)
        )
        np.testing.assert_array_equal(
            np.asarray(got[0]), np.asarray(as_it_was(q, theta))
        )


def test_a_rotation_s_factor_is_squared_in_the_scores(rng):
    q = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    positions = jnp.arange(8)[None, :]
    inv = layers.rope_frequencies(8, 100.0)
    plain, _ = layers.rotary_embedding(q, q, positions, inv)
    scaled, _ = layers.rotary_embedding(q, q, positions, inv, 1.3)
    np.testing.assert_allclose(scaled, 1.3 * plain, rtol=1e-5, atol=1e-6)


# -- the shares ----------------------------------------------------------------


def test_four_shares_of_16_of_64_add_up_to_the_uncut_layer():
    """The routed parts of the four shares of one layer (experts 0-15,
    16-31, 32-47, 48-63) are the uncut reference's layer; nothing is
    dropped and the shares' pairs add up to all."""
    total, held, d, width = 64, 16, 32, 16
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    n = jax.random.normal(keys[0], (BATCH, 32, d))
    whole = {
        "router": {"kernel": jax.random.normal(keys[1], (d, total))},
        "wi": 0.2 * jax.random.normal(keys[2], (total, d, width)),
        "wg": 0.2 * jax.random.normal(keys[3], (total, d, width)),
        "wo": 0.2 * jax.random.normal(keys[4], (total, width, d)),
    }
    fields = dict(num_experts=total, top_k=8, norm_topk_prob=True)

    def balance(aux, term):
        np.testing.assert_allclose(aux, term, rtol=1e-5)

    harness.shares_add_up(
        ref, fields, n, whole, held,
        lambda first: moe_lib.MoEMlp(
            num_experts=total, d_ff=width, top_k=8, dispatch="grouped",
            norm_topk_prob=True, aux_form="topk", experts_held=held,
            first_expert=first, row_budget_multiple=8.0,
            dtype=jnp.float32, gmm_block_rows=8,
        ),
        jnp.zeros_like(n), TOL, balance=balance,
    )
