"""That the comparison of ``tests/test_granite_moe_hybrid_reference.py`` is
sharp: each fault, made on the reference's side, moves a token's loss past
the tolerance held there (and the program's own switch, where it has one,
makes the faulty reference agree again), and the reference computed in a
lower precision is another result."""

import numpy as np
import pytest

from test_granite_moe_hybrid_reference import (  # noqa: F401 (fixtures)
    CHECK, TOL, config, params, tokens,
)


# each fault, and the program's own switch that makes the faulty reference
# agree again (where the program has one)
WRONG = {
    "no_embedding_multiplier": dict(embed_scale=1.0),
    "no_attention_multiplier": dict(attention_scale=1.0),
    "no_residual_multiplier": dict(residual_scale=1.0),
    "no_logits_scaling": dict(logit_scale=1.0),
    "sqrt_scale": dict(attention_scale=0.0),
    "rotate": dict(position="rope"),
    "softmax_all": dict(norm_topk_prob=False),
    "own_bc": None,
    "norm_before_gate": None,
    "ungated_expert": None,
    "no_shared": None,
    "untied_head": None,
}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_the_check_is_sharp(wrong, params, tokens):
    """Each fault, made on one side, moves a token's loss past the
    tolerance the tests above hold."""
    assert CHECK.nll_gap(config(), params, tokens, wrong=wrong) > 10 * TOL
    switch = WRONG[wrong]
    if switch is not None:
        # a program with that switch set is the faulty reference's model
        switched = config(**switch)

        def gap(**kw):
            return CHECK.nll_gap(
                switched, params, tokens, ref_cfg=config(), **kw
            )

        assert gap() > 10 * TOL
        assert gap(wrong=wrong) <= TOL


def test_the_reference_computed_lower_is_another_result(params, tokens):
    exact = CHECK.reference("token_nll", config(), params, tokens)
    for lowered, least in (("router", TOL / 10), ("ssm", TOL),
                           ("all", 100 * TOL)):
        other = CHECK.reference(
            "token_nll", config(), params, tokens, lowered=lowered
        )
        assert float(np.abs(other - exact).mean()) > least, lowered
