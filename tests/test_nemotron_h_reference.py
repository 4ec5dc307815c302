"""Nemotron-H (NVIDIA-Nemotron-3-Nano-30B-A3B's layers) against its plain
reference, at a small size on the CPU with seeded float32 weights: per-token
loss, the loss and every gradient; the one-branch layers in a period, the
unrolled trunk; the shares of a relu² expert layer.  Each fault the
comparison must catch is ``tests/test_nemotron_h_sharp.py``'s; the
pipelined trunk on a mesh, the train step and the ``ssm`` event
``tests/test_nemotron_h_system.py``'s; what the configuration refuses
``tests/test_nemotron_h_config.py``'s."""

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_harness as harness
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models import nemotron_h
from dlrover_tpu.models.nemotron_h import nemotron_h_config
from dlrover_tpu.models.references import nemotron_h as ref
from dlrover_tpu.models.transformer import (
    ATTENTION, EXPERTS, MLP, SSM, Mlp,
)

SEQ, BATCH, VOCAB = 40, 2, 128
# float32 on both sides: what is left is the order of the sums
TOL = 1e-4
# the bias picks and never weighs: no gradient reaches it
CHECK = harness.Harness(
    ref, loss_atol=TOL, grad_atol=2e-5, grad_rtol=2e-4,
    no_gradient=("router_bias",),
)


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


def move(name, leaf, draw):
    """The router's biases and the ``D`` of the mixers moved off their
    initial 0 and 1, so that a fault in either shows."""
    if "router_bias" in name:
        return 0.3 * draw(leaf.shape)
    if name.endswith("['D']"):
        return leaf + 0.5 * draw(leaf.shape)
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


@pytest.mark.parametrize("case", ["xla", "kernels_a_share"])
def test_loss_and_every_gradient_match_the_reference(case, tokens):
    cfg = config(**CASES[case])
    CHECK.loss_and_every_gradient_match(cfg, share(cfg), tokens)


@pytest.mark.parametrize("case", sorted(CASES))
def test_token_nll_matches_the_reference(case, tokens):
    cfg = config(**CASES[case])
    assert CHECK.nll_gap(cfg, share(cfg), tokens) <= TOL


def test_loss_and_every_gradient_match_where_the_conv_kernel_runs():
    """64 tokens are half a lane tile, so the cases above take the
    convolution's XLA form; 256 tokens of the same x | B | C = 256 | 32 |
    32 channels from column 256 are two token tiles of
    ``ops/short_conv.py``'s kernels (the tokens on the lanes, 32 channels a
    tile, three outputs): a tile's first tokens read the tile before, in
    the forward and in ``dx``.  The layers around the mixer add no tile:
    one period of the dense sibling (a mixer, an attention layer, two relu²
    MLPs), under the XLA scan and under the scan's and the flash kernels."""
    from dlrover_tpu.models import mamba2
    from dlrover_tpu.ops import short_conv

    seq = 256
    tokens = harness.tokens(2, BATCH, seq, VOCAB)
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
        plain = dataclasses.replace(
            dense_config(max_seq_len=seq), num_layers=4
        )
        weights = harness.init(plain, tokens[0], move=move)
        for kernels in (dict(), dict(
            ssm_impl="kernel", attention_impl="flash", remat="flash_only",
        )):
            cfg = dataclasses.replace(plain, **kernels)
            CHECK.loss_and_every_gradient_match(cfg, weights, tokens)
    assert calls and set(calls) == {(BATCH, seq, 580)}


def test_the_unrolled_trunk_is_the_scanned_one(params, tokens):
    cfg = config(scan_layers=False)
    unrolled = {k: v for k, v in params.items() if k != "blocks"}
    layers = ref.trunk_layers(dataclasses.asdict(config()), params)
    for i, (_, layer) in enumerate(layers):
        unrolled[f"block_{i}"] = layer
    got = CHECK.nll(cfg, unrolled, tokens)
    want = CHECK.nll(config(), params, tokens)
    np.testing.assert_allclose(got, want, atol=TOL)
    assert CHECK.nll_gap(cfg, unrolled, tokens) <= TOL


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
    params = harness.init(cfg, tokens[0], seed=3, move=move)
    assert sorted(params["blocks"]["mlp_1"]) == ["ln", "mlp"]
    assert sorted(params["blocks"]["mlp_1"]["mlp"]) == ["wi", "wo"]
    assert CHECK.nll_gap(cfg, params, tokens) <= TOL
    # and the square is there
    assert CHECK.nll_gap(cfg, params, tokens, wrong="no_square") > 10 * TOL
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
        shared = ref.relu2_mlp(
            n, whole["shared"]["wi"]["kernel"], whole["shared"]["wo"]["kernel"]
        )
    harness.shares_add_up(
        ref, fields, n, whole, held_here,
        lambda first: moe_lib.MoEMlp(
            num_experts=total, d_ff=width, top_k=6, dispatch="grouped",
            activation="relu2", scoring="sigmoid", router_bias=True,
            routed_scale=2.5, experts_held=held_here, first_expert=first,
            shared_d_ff=shared_width, row_budget_multiple=4.0,
            dtype=jnp.float32, gmm_block_rows=8,
        ),
        shared, TOL,
    )
