"""That the comparison of ``tests/test_lfm2_moe_reference.py`` is sharp:
each fault, made on the reference's side, moves a token's loss past the
tolerance held there (and the program's own switch, where it has one that
leaves the parameters' tree as it is, makes the faulty reference agree
again), and the reference computed in a lower precision is another
result."""

import numpy as np
import pytest

from dlrover_tpu.models.references import lfm2_moe as ref
from test_lfm2_moe_reference import (  # noqa: F401 (fixtures)
    CHECK, TOL, config, params, tokens,
)

# each fault, and the program's own switch that makes the faulty reference
# agree again (where the program has one)
WRONG = {
    "silu_after_taps": None,
    "four_taps": None,
    "gate_after_conv": None,
    "no_out_gate": None,
    "gate_order": None,
    "reads_ahead": None,
    "qk_norm_joint": None,
    "qk_norm_after_rope": None,
    "rope_in_conv": None,
    "softmax_router": None,
    "choice_by_score": None,
    "bias_weighs": None,
    "no_renorm": dict(norm_topk_prob=False),
    "scaled_2_5": dict(routed_scaling_factor=2.5),
    "shared_expert": None,
}


def test_every_fault_the_reference_can_make_is_tried():
    assert sorted(WRONG) == sorted(ref.FAULTS)
    with pytest.raises(ValueError, match="wrong must be one of"):
        ref.forward({}, {}, None, wrong="something_else")


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_the_check_is_sharp(wrong, params, tokens):
    """Each fault, made on one side, moves a token's loss past the
    tolerance the reference tests hold."""
    assert CHECK.nll_gap(config(), params, tokens, wrong=wrong) > 10 * TOL
    switch = WRONG[wrong]
    if switch is None:
        return
    # a program with that switch set is the faulty reference's model
    assert CHECK.nll_gap(
        config(**switch), params, tokens, ref_cfg=config(), wrong=wrong
    ) <= TOL


def test_the_reference_computed_lower_is_another_result(params, tokens):
    exact = CHECK.reference("token_nll", config(), params, tokens)
    for lowered, least in (("router", TOL), ("conv", 10 * TOL),
                           ("all", 100 * TOL)):
        other = CHECK.reference(
            "token_nll", config(), params, tokens, lowered=lowered
        )
        assert float(np.abs(other - exact).mean()) > least, lowered
