"""Ling-3.0-flash's language model (five Kimi-Delta-Attention layers to one
gated latent-attention layer, a group-limited sigmoid router over a share
of the experts, a dense prefix ahead of the patterned trunk) against its
plain reference, at a small size on the CPU with seeded float32 weights:
per-token loss, the loss and every gradient; the group-limited choice on
its own; sixteen shares of 32 of 512 experts under the group limit.  Each
fault the comparison must catch is ``tests/test_ling_flash_sharp.py``'s;
the train step, the events and the scopes ``tests/test_ling_flash_system.py``'s;
what the configuration refuses and the benchmark's file
``tests/test_ling_flash_config.py``'s; the rule itself ``tests/test_kda.py``'s."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_harness as harness
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models import ling_flash
from dlrover_tpu.models.ling_flash import ling_flash_config
from dlrover_tpu.models.references import ling_flash as ref
from dlrover_tpu.models.transformer import FULL_ATTENTION, LINEAR_ATTENTION

SEQ, BATCH, VOCAB = 40, 2, 256
# float32 on both sides under matmul precision "highest": what is left is
# the order of the sums (chunks against single tokens, sorted rows, one
# head or one expert at a time), a few float32 ulps of a loss of ~5.5
# (read 3.8e-6).  1e-4 is a hundredth of what the smallest fault moves
# (a bias that weighs: 1.8e-2).
TOL = 1e-4
# b picks, it never weighs: no gradient reaches it
CHECK = harness.Harness(
    ref, loss_atol=TOL, grad_atol=2e-5, grad_rtol=2e-4,
    no_gradient=("router_bias",),
)

# one dense layer (KDA: the pattern continued backwards) and one period of
# KDA, latent, KDA (the published period of six is one case below: what a
# case compiles grows with its layers); 32 experts in 4 groups of 8, 2
# groups and 4 experts a token, 8 held
SMALL = dict(
    vocab_size=VOCAB, num_layers=4, first_k_dense=1, d_model=64, num_heads=4,
    d_ff=96, max_seq_len=48, rope_theta=1e4,
    layer_pattern=(LINEAR_ATTENTION, FULL_ATTENTION, LINEAR_ATTENTION),
    linear_num_heads=4, linear_key_head_dim=16, linear_value_head_dim=16,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    num_experts=32, router_groups=4, router_topk_groups=2, top_k=4,
    moe_d_ff=32, experts_held=8, first_expert=8, moe_row_budget=3.0,
    dtype=jnp.float32, param_dtype=jnp.float32,
)


def config(**overrides):
    return ling_flash_config(**{**SMALL, **overrides})


def move(name, leaf, draw):
    """Router biases that are not zero (the choice on ``s + b`` then
    differs from the choice on ``s``) and norm scales off their initial 1."""
    if "router_bias" in name:
        return 0.05 * draw(leaf.shape)
    if name.endswith("['scale']") or "out_norm_scale" in name:
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


KDA_KERNEL_WIDTHS = dict(
    linear_num_heads=2, linear_key_head_dim=128, linear_value_head_dim=128,
    layer_pattern=(LINEAR_ATTENTION,), num_layers=2,
)
_DEEP = dict(
    num_layers=14, first_k_dense=2, layer_pattern=ling_flash.TRUNK_PATTERN
)
CASES = {
    "share": {},
    "whole": dict(experts_held=0, first_expert=0),
    "last_share": dict(first_expert=24),
    "flash": dict(attention_impl="flash", flash_block_q=8, flash_block_kv=8),
    # two dense layers ahead of two periods of the published six kinds: ONE
    # model under both names (each is a depth and a period's length, held to
    # every token's loss; apart they were two trees and four programs)
    "two_dense_two_periods": _DEEP,
    "published_period": _DEEP,
    # heads of 128 / 128 take the Pallas kernels (interpreted here): one
    # dense and one expert layer, both KDA, under the policy the cell runs
    # (the program ``test_keeping_the_kda_states_changes_no_gradient`` holds
    # ``full``'s to)
    "kda_kernel_widths": dict(
        KDA_KERNEL_WIDTHS, attention_impl="flash", remat="flash_only"
    ),
}


# the cases held to every gradient as well (the others to each token's
# loss: what they vary is a share's offset, the depth or the period's
# length, whose gradients these three cover)
GRADIENTS = ("share", "flash", "kda_kernel_widths")


@functools.cache
def drawn(cfg):
    """Seeded weights of ``cfg``'s own tree, drawn once for the cases that
    read the same model."""
    return harness.init(cfg, seeded()[0][0], seed=2, move=move)


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_matches_the_reference_in_float32(case, tokens):
    cfg = config(**CASES[case])
    if cfg.layer_pattern == SMALL["layer_pattern"] and (
        cfg.num_layers == SMALL["num_layers"]
    ):
        weights = share(cfg)
    else:
        weights = drawn(cfg)
    if case not in GRADIENTS:
        assert CHECK.nll_gap(cfg, weights, tokens) <= TOL
        return
    _, (main, aux, _), _ = CHECK.loss_and_grads(cfg, weights, tokens)
    want = CHECK.reference("forward", cfg, weights, tokens)
    np.testing.assert_allclose(main, want["nll"], atol=TOL)
    assert float(aux) == 0.0
    CHECK.loss_and_every_gradient_match(cfg, weights, tokens)


def test_keeping_the_kda_states_changes_no_gradient(tokens):
    """``flash_only`` keeps the KDA forward kernel's chunk-start states
    beside its output (``kda_states``, ``kda_out``), so the backward kernel
    reads the first run's where ``full`` runs the kernel again: the same
    kernels on the same inputs, held to what ``flash_only`` is held to in
    ``tests/test_remat_policies.py`` (whose case this was until PR 65: it
    reads the ``kda_kernel_widths`` case's program, so it lives beside it,
    and ``full``'s is the one program it adds)."""
    weights = drawn(config(**CASES["kda_kernel_widths"]))
    loss, grads = {}, {}
    for remat in ("flash_only", "full"):
        cfg = config(**{**CASES["kda_kernel_widths"], "remat": remat})
        loss[remat], _, tree = CHECK.loss_and_grads(cfg, weights, tokens)
        grads[remat] = jax.tree_util.tree_leaves(tree)
    np.testing.assert_allclose(
        float(loss["flash_only"]), float(loss["full"]), rtol=1e-5
    )
    assert len(grads["flash_only"]) == len(grads["full"])
    for a, b in zip(grads["flash_only"], grads["full"]):
        np.testing.assert_allclose(
            np.asarray(a, np.float64), np.asarray(b, np.float64),
            rtol=2e-4, atol=2e-6,
        )


def test_the_unrolled_trunk_is_the_scanned_one(tokens):
    """``scan_layers=False`` names its layers ``block_<i>`` after the dense
    prefix; layer i's kind is the pattern's from the prefix on."""
    cfg = config(scan_layers=False)
    weights = harness.init(cfg, tokens[0], seed=3, move=move)
    assert "block_1" in weights and "block_4" not in weights
    assert "attn" in weights["block_2"] and "moe" in weights["block_2"]
    assert "linear_attn" in weights["dense_0"] and "mlp" in weights["dense_0"]
    assert CHECK.nll_gap(cfg, weights, tokens) <= TOL
