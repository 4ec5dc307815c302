"""That the comparison of ``tests/test_nemotron_h_reference.py`` is sharp:
each fault, made on the reference's side, moves a token's loss past the
tolerance held there, and the reference computed in a lower precision is
another result.  The program runs once, whatever the fault."""

import numpy as np
import pytest

from test_nemotron_h_reference import (  # noqa: F401 (fixtures)
    CHECK, TOL, config, params, tokens,
)


WRONG = [
    "decay_sign", "no_skip", "norm_before_gate", "own_bc", "gated_expert",
    "no_square", "bias_weighs", "rotate",
]


@pytest.mark.parametrize("wrong", WRONG)
def test_the_check_is_sharp(wrong, params, tokens):
    """Each fault, made on one side, moves a token's loss past the
    tolerance the tests above hold."""
    assert CHECK.nll_gap(config(), params, tokens, wrong=wrong) > 10 * TOL
    if wrong == "rotate":
        # the program has the switch: with it set, the faulty reference
        # agrees again
        rotated = config(position="rope")
        assert CHECK.nll_gap(rotated, params, tokens) > 10 * TOL
        assert CHECK.nll_gap(rotated, params, tokens, wrong=wrong) <= TOL


def test_the_reference_computed_lower_is_another_result(params, tokens):
    exact = CHECK.reference("token_nll", config(), params, tokens)
    for lowered, least in (("router", TOL), ("ssm", 10 * TOL),
                           ("all", 100 * TOL)):
        other = CHECK.reference(
            "token_nll", config(), params, tokens, lowered=lowered
        )
        assert float(np.abs(other - exact).mean()) > least, lowered
