"""That the comparison of ``tests/test_mellum_reference.py`` is sharp: each
fault, made on the reference's side, moves a token's loss past the tolerance
held there (and the program's own switch, where it has one that leaves the
parameters' tree as it is, makes the faulty reference agree again), and the
reference computed in a lower precision is another result."""

import numpy as np
import pytest

from dlrover_tpu.models.references import mellum as ref
from test_mellum_reference import CHECK, SMALL, TOL, config, share, tokens  # noqa: F401,E501

# one period holds every kind and layer 1 is a sliding one
ONE_PERIOD = dict(num_layers=4)

# each fault, and the program's own switch that makes the faulty reference
# agree again (where the program has one)
WRONG = {
    "no_window": None,
    "window_plus_1": dict(sliding_window=SMALL["sliding_window"] + 1),
    "window_minus_1": None,
    "window_on_full": None,
    "one_sliding_unwindowed": None,
    "kinds_reordered": None,
    "yarn_on_sliding": None,
    "no_yarn": dict(rope_scaling=""),
    "interpolate_all": None,
    "low_high_swapped": None,
    "low_off_by_one": None,
    "high_off_by_one": None,
    "factor_on_cos_only": None,
    "factor_once": None,
    "no_factor": dict(rope_attention_factor=1.0),
    "theta_10000": None,
    "qk_norm": None,
    "no_renorm": dict(norm_topk_prob=False),
    "top_k_of_held": None,
    "sigmoid_router": None,
}


@pytest.fixture(scope="module")
def params():
    return share(config(**ONE_PERIOD))


def test_every_fault_the_reference_can_make_is_tried():
    assert sorted(WRONG) == sorted(ref.FAULTS)
    with pytest.raises(ValueError, match="wrong must be one of"):
        ref.forward({}, {}, None, wrong="something_else")


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_the_check_is_sharp(wrong, params, tokens):
    """Each fault, made on one side, moves a token's loss past the
    tolerance the reference tests hold."""
    cfg = config(**ONE_PERIOD)
    assert CHECK.nll_gap(cfg, params, tokens, wrong=wrong) > 10 * TOL
    switch = WRONG[wrong]
    if switch is None:
        return
    # a program with that switch set is the faulty reference's model
    assert CHECK.nll_gap(
        config(**ONE_PERIOD, **switch), params, tokens, ref_cfg=cfg,
        wrong=wrong,
    ) <= TOL


def test_the_reference_computed_lower_is_another_result(params, tokens):
    cfg = config(**ONE_PERIOD)
    exact = CHECK.reference("token_nll", cfg, params, tokens)
    for lowered, least in (("router", TOL), ("attention", 10 * TOL),
                           ("all", 100 * TOL)):
        other = CHECK.reference(
            "token_nll", cfg, params, tokens, lowered=lowered
        )
        assert float(np.abs(other - exact).mean()) > least, lowered
