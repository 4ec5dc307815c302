"""That the comparison of ``tests/test_command_a_reference.py`` is sharp:
each fault, made on the reference's side, moves a token's loss past the
tolerance held there (and the program's own switch, where it has one that
leaves the parameters' tree as it is, makes the faulty reference agree
again), and the reference computed in a lower precision is another result."""

import numpy as np
import pytest

from dlrover_tpu.models.references import command_a as ref
from test_command_a_reference import CHECK, SMALL, TOL, config, share, tokens  # noqa: F401,E501

# each fault, and the program's own switch that makes the faulty reference
# agree again (where the program has one: the serial block has two norms a
# layer, another tree)
WRONG = {
    "sequential_block": None,
    "rope_on_full": dict(full_rope=True),
    "no_rope": None,
    "pairing_not_permuted": None,
    "theta_10000": dict(rope_theta=10000.0),
    "no_window": None,
    "window_plus_1": dict(sliding_window=SMALL["sliding_window"] + 1),
    "window_minus_1": dict(sliding_window=SMALL["sliding_window"] - 1),
    "window_on_full": None,
    "kinds_reordered": None,
    "shared_summed": dict(shared_expert_combine="sum"),
    "shared_left_out": None,
    "softmax_router": None,
    "no_renorm": dict(norm_topk_prob=False),
    "top_k_of_held": None,
    "layernorm_keeps_mean": dict(norm="rmsnorm", norm_use_bias=True),
}


@pytest.fixture(scope="module")
def params():
    return share(config())


def test_every_fault_the_reference_can_make_is_tried():
    assert sorted(WRONG) == sorted(ref.FAULTS)
    with pytest.raises(ValueError, match="wrong must be one of"):
        ref.forward({}, {}, None, wrong="something_else")


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_the_check_is_sharp(wrong, params, tokens):
    """Each fault, made on one side, moves a token's loss past the
    tolerance the reference tests hold."""
    cfg = config()
    assert CHECK.nll_gap(cfg, params, tokens, wrong=wrong) > 10 * TOL
    switch = WRONG[wrong]
    if switch is None:
        return
    # a program with that switch set is the faulty reference's model
    assert CHECK.nll_gap(
        config(**switch), params, tokens, ref_cfg=cfg, wrong=wrong,
    ) <= TOL


def test_the_reference_computed_lower_is_another_result(params, tokens):
    cfg = config()
    exact = CHECK.reference("token_nll", cfg, params, tokens)
    # read at these sizes: 0.00025, 0.00068, 0.0092
    for lowered, least in (("router", TOL), ("attention", 5 * TOL),
                           ("all", 50 * TOL)):
        other = CHECK.reference(
            "token_nll", cfg, params, tokens, lowered=lowered
        )
        assert float(np.abs(other - exact).mean()) > least, lowered
