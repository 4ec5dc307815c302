"""Olmo-Hybrid through the program against the plain reference
(``dlrover_tpu/models/references/olmo_hybrid.py``), at a small size on the
CPU: the patterned trunk against the reference's loop over layers.  (The
chunked delta rule against the token-by-token recurrence is
``tests/test_gated_delta_rule.py``'s; the train step, what the
configuration refuses and the earlier models' pinned steps are
``tests/test_olmo_hybrid_system.py``'s.)

The rule runs as its Pallas kernels in interpret mode
(``ops/backend.interpret``), forward and backward.

Seeded weights; every norm scale is moved off one.  Tolerances, all
float32 against float32: the chunked form orders its sums differently
from the recurrence and builds ``(I + A)^-1`` by products, which moves an
output by about 1e-6 of the largest; each piece of the layer undone
(doubling of beta, decay, k's norm, convolution, output gate, norm
placement) moves a token's loss by 1e-2 or more, so 2e-4 lies a factor of
ten or more from either.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_harness as harness
from dlrover_tpu.models.olmo_hybrid import olmo_hybrid_config
from dlrover_tpu.models.references import olmo_hybrid as reference

NLL_ATOL = 2e-4
LOSS_ATOL = 2e-5
CHECK = harness.Harness(
    reference, loss_atol=LOSS_ATOL, grad_atol=1e-7,
    grad_rtol=2e-4,
)

SEQ, BATCH, VOCAB = 144, 2, 256


def config(**overrides):
    base = dict(
        vocab_size=VOCAB, num_layers=8, d_model=64, num_heads=4, d_ff=96,
        linear_num_heads=4, linear_key_head_dim=12, linear_value_head_dim=24,
        max_seq_len=SEQ, dtype=jnp.float32, param_dtype=jnp.float32,
        attention_impl="xla", flash_block_q=16, flash_block_kv=16,
    )
    base.update(overrides)
    return olmo_hybrid_config(**base)


# -- the model -----------------------------------------------------------------


def move(name, leaf, draw):
    """Every norm scale moved off one."""
    if name.endswith("scale']"):
        return leaf * (1 + 0.3 * draw(leaf.shape))
    return leaf


@functools.cache
def seeded():
    """(tokens, weights)."""
    rows = harness.tokens(11, BATCH, SEQ, VOCAB)
    return rows, harness.init(config(), rows[0], seed=5, move=move)


@pytest.fixture(scope="module")
def tokens():
    return seeded()[0]


@pytest.fixture(scope="module")
def params():
    return seeded()[1]


def nll_gap(cfg, params, tokens, undo=""):
    """Against the reference of the model as published, whatever ``cfg``
    switches in the program."""
    return CHECK.nll_gap(cfg, params, tokens, ref_cfg=config(), undo=undo)


IMPLS = pytest.mark.parametrize("attention_impl", ["xla", "flash"])


def test_the_tree_has_a_slot_per_position_stacked_over_the_periods(params):
    blocks = params["blocks"]
    assert sorted(blocks) == ["full_3", "linear_0", "linear_1", "linear_2"]
    for slot, tree in blocks.items():
        mixer = "attn" if slot == "full_3" else "linear_attn"
        assert sorted(tree) == sorted([mixer, "ln_attn", "ln_mlp", "mlp"])
        assert all(leaf.shape[0] == 2 for leaf in jax.tree.leaves(tree))
    assert sorted(blocks["linear_0"]["linear_attn"]) == [
        "A_log", "ab_kernel", "conv_kernel", "dt_bias", "out_norm_scale",
        "qkvg", "wo",
    ]


@IMPLS
def test_loss_and_every_gradient_match_the_reference(
    attention_impl, params, tokens
):
    cfg = config(attention_impl=attention_impl, remat=(
        "flash_only" if attention_impl == "flash" else "none"
    ))
    CHECK.loss_and_every_gradient_match(cfg, params, tokens)


@IMPLS
def test_token_nll_matches_the_reference(attention_impl, params, tokens):
    cfg = config(attention_impl=attention_impl)
    assert nll_gap(cfg, params, tokens) <= NLL_ATOL


def test_loss_and_every_gradient_match_where_the_conv_kernel_runs():
    """Heads of 12 | 12 | 24 columns are no whole row tiles and 144 tokens
    no whole lane tile, so the cases above take the convolution's XLA form;
    256 tokens of heads of 16 | 16 | 32 are two token tiles of
    ``ops/short_conv.py``'s kernels (the tokens on the lanes; q and k one
    row tile each, normalised inside; v two): a tile's first tokens read
    the tile before, in the forward and in ``dx``.  The layers around the
    mixer add no tile: one linear layer and one full one."""
    from dlrover_tpu.models import linear_attention
    from dlrover_tpu.ops import short_conv

    seq = 256
    tokens = harness.tokens(13, BATCH, seq, VOCAB)
    assert linear_attention.conv_path(seq, 4, 12, 24, 4) == "xla"
    assert linear_attention.conv_path(SEQ, 4, 16, 32, 4) == "xla"
    assert linear_attention.conv_path(seq, 4, 16, 32, 4) == "kernel"
    calls = []
    kernel = short_conv.short_conv
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(short_conv, "_TILE_TOKENS", 128)
        patch.setattr(
            short_conv, "short_conv",
            lambda *a: calls.append(a[0].shape) or kernel(*a),
        )
        cfg = config(
            attention_impl="flash", remat="flash_only", max_seq_len=seq,
            linear_key_head_dim=16, linear_value_head_dim=32, num_layers=2,
            layer_pattern=("linear_attention", "full_attention"),
        )
        CHECK.loss_and_every_gradient_match(
            cfg, harness.init(cfg, tokens[0], seed=5, move=move), tokens
        )
    assert calls and set(calls) == {(BATCH, seq, 4, 96)}


def test_the_unrolled_trunk_is_the_scanned_one(params, tokens):
    cfg = config(scan_layers=False)
    unrolled = {k: v for k, v in params.items() if k != "blocks"}
    for i in range(cfg.num_layers):
        unrolled[f"block_{i}"] = reference.layer_params(
            dataclasses.asdict(cfg), params, i
        )[1]
    got = CHECK.nll(cfg, unrolled, tokens)
    want = CHECK.nll(config(), params, tokens)
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert nll_gap(cfg, unrolled, tokens) <= NLL_ATOL


@pytest.mark.parametrize("undo", [
    "beta_doubling", "decay", "k_norm", "conv", "out_gate", "post_norm",
])
def test_the_check_is_sharp(undo, params, tokens):
    """Each piece of the layer, undone on one side, fails the comparison
    the tests above make."""
    cfg = {
        "beta_doubling": config(linear_allow_neg_eigval=False),
        "post_norm": config(norm_placement="pre"),
    }.get(undo)
    if cfg is not None:      # the program has the switch: undo it there
        assert nll_gap(cfg, params, tokens) > 10 * NLL_ATOL
        # and the reference with the same piece undone agrees again
        assert nll_gap(cfg, params, tokens, undo=undo) <= NLL_ATOL
        return
    assert nll_gap(config(), params, tokens, undo=undo) > 10 * NLL_ATOL


def test_a_pipelined_trunk_is_the_scanned_one(tokens):
    """Two stages of one period each: the stage stack holds whole
    periods, and the logits are the plain scan's."""
    plain, piped = config(), config(pipeline_stages=2, num_microbatches=2)
    tree = harness.init(piped, tokens[0], seed=5)
    layers = tree["blocks"]["ticks"]["stages"]["layers"]
    assert sorted(layers) == ["full_3", "linear_0", "linear_1", "linear_2"]
    # [stage, period of the stage, ...] -> [period, ...]
    flat = dict(tree, blocks=jax.tree.map(
        lambda a: a.reshape(-1, *a.shape[2:]), layers
    ))
    got = CHECK.nll(piped, tree, tokens)
    want = CHECK.nll(plain, flat, tokens)
    np.testing.assert_allclose(got, want, atol=1e-4)
