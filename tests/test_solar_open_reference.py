"""Solar-Open2 (three Kimi-Delta-Attention layers under the published gate,
which has no lower bound, a doubled beta and low-rank gate projections, to
one position-free grouped-query layer under an element-wise output gate; a
softmax router over a share of the experts beside one shared) against its
plain reference, at a small size on the CPU with seeded float32 weights:
per-token loss, the loss and every gradient.  Each fault the comparison
must catch, the lowered references and the shares are
``tests/test_solar_open_sharp.py``'s; the train step, the events and the
scopes ``tests/test_solar_open_system.py``'s; what the configuration
refuses and the benchmark's file ``tests/test_solar_open_config.py``'s; the
rule itself ``tests/test_kda.py``'s."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

import reference_harness as harness
from dlrover_tpu.models import solar_open
from dlrover_tpu.models.references import solar_open as ref
from dlrover_tpu.models.solar_open import solar_open2_config  # noqa: F401
from dlrover_tpu.models.transformer import FULL_ATTENTION, LINEAR_ATTENTION

SEQ, BATCH, VOCAB = 40, 2, 256
# float32 on both sides under matmul precision "highest": what is left is
# the order of the sums (chunks against single tokens, sorted rows, one
# head or one expert at a time), a few float32 ulps of a loss of ~5.5.
# 1e-4 is under a hundredth of what the smallest fault moves
# (tests/test_solar_open_sharp.py).
TOL = 1e-4
CHECK = harness.Harness(ref, loss_atol=TOL, grad_atol=2e-5, grad_rtol=2e-4)

# one period cut to GQA, KDA (the published period of four is one case
# below: what a case compiles grows with its layers); 32 experts, 4 a
# token, 8 held
SMALL = dict(
    vocab_size=VOCAB, num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
    head_dim=16, d_ff=96, max_seq_len=48,
    layer_pattern=(FULL_ATTENTION, LINEAR_ATTENTION),
    linear_num_heads=4, linear_key_head_dim=16, linear_value_head_dim=16,
    linear_gate_rank=8,
    num_experts=32, top_k=4, moe_d_ff=32, experts_held=8, first_expert=8,
    moe_row_budget=3.0, dtype=jnp.float32, param_dtype=jnp.float32,
)


def config(**overrides):
    return solar_open2_config(**{**SMALL, **overrides})


def move(name, leaf, draw):
    """Norm scales off their initial 1, and a decay projection wide enough
    that some channels of some tokens decay far past the split form's
    floor of -5.5 (``g`` down to -100 and beyond here)."""
    if name.endswith("['scale']") or "out_norm_scale" in name:
        return leaf + 0.3 * draw(leaf.shape)
    if "f_up" in name or "f_down" in name:
        return 20.0 * leaf
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


_DEEP = dict(num_layers=8, layer_pattern=solar_open.TRUNK_PATTERN)
CASES = {
    "share": {},
    "whole": dict(experts_held=0, first_expert=0),
    "last_share": dict(first_expert=24),
    "flash": dict(attention_impl="flash", flash_block_q=8, flash_block_kv=8),
    # two periods of the published four kinds: ONE model under both names
    # (a depth and a period's length, each held to every token's loss)
    "published_period": _DEEP,
    "two_periods": _DEEP,
    # heads of 128 / 128 take the Pallas kernels (interpreted here) in the
    # form that is exact for any g <= 0
    "kda_kernel_widths": dict(
        linear_num_heads=2, linear_key_head_dim=128, linear_value_head_dim=128,
        layer_pattern=(LINEAR_ATTENTION,), num_layers=1,
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


def test_the_seeded_gate_goes_past_the_split_forms_floor(params, tokens):
    """The comparison above is of a gate that is really unbounded: some
    (token, head, channel) triples decay by more than the -88 / 16 a token
    under which ``ops/kda.py``'s split form would overflow."""
    from dlrover_tpu.ops import kda

    cfg = config()
    layer = jnp.asarray(0)
    p = {
        k: v[layer] for k, v in params["blocks"]["linear_1"][
            "linear_attn"
        ].items() if not isinstance(v, dict)
    }
    x = params["embed"]["embedding"][tokens[0]]
    n = ref.rms_norm(x, jnp.ones((cfg.d_model,)), cfg.norm_eps)
    g, beta = ref.kda_gates({}, n, p)
    assert float(g.min()) < 4 * kda.SPLIT_FLOOR
    assert float((g < kda.SPLIT_FLOOR).mean()) > 0.01
    assert 1.0 < float(beta.max()) < 2.0


def test_the_unrolled_trunk_is_the_scanned_one(tokens):
    """``scan_layers=False`` names its layers ``block_<i>``; layer i's kind
    is the pattern's."""
    cfg = config(scan_layers=False)
    weights = harness.init(cfg, tokens[0], seed=3, move=move)
    assert "block_1" in weights and "block_2" not in weights
    assert "attn" in weights["block_0"] and "gate" in weights["block_0"]["attn"]
    assert "linear_attn" in weights["block_1"] and "moe" in weights["block_1"]
    assert CHECK.nll_gap(cfg, weights, tokens) <= TOL
