"""That the comparison of ``tests/test_solar_open_reference.py`` is sharp:
each fault, made on the reference's side, moves a token's loss past the
tolerance held there (and the program's own switch, where it has one that
leaves the parameters' tree as it is, makes the faulty reference agree
again), and the reference computed in a lower precision is another result.
Then the router's choice on its own, and the sixteen shares of an expert
layer."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_harness as harness
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.references import solar_open as ref
from test_solar_open_reference import (  # noqa: F401 (fixtures)
    BATCH, CHECK, TOL, config, params, tokens,
)

# each fault, and the program's own switch that makes the faulty reference
# agree again
WRONG = {
    "beta_not_doubled": dict(linear_allow_neg_eigval=False),
    "safe_gate": dict(linear_decay_bound=ref.SAFE_BOUND),
    "scalar_decay": None,
    "no_gqa_gate": None,
    "gate_head_wise": None,
    "rope_on_gqa": dict(position="rope", rope_theta=ref.ROPE_THETA),
    "sigmoid_router": dict(router_scoring="sigmoid"),
    "no_renorm": dict(norm_topk_prob=False),
    "no_shared": None,
}


def test_every_fault_the_reference_can_make_is_tried():
    assert sorted(WRONG) == sorted(ref.FAULTS)
    with pytest.raises(ValueError, match="wrong must be one of"):
        ref.forward({}, {}, None, wrong="something_else")


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_the_check_is_sharp(wrong, params, tokens):
    """Each fault, made on one side, moves a token's loss by at least
    fifty times the tolerance the reference tests hold (the smallest read:
    a sigmoid router, 9.3e-3)."""
    assert CHECK.nll_gap(config(), params, tokens, wrong=wrong) > 50 * TOL
    switch = WRONG[wrong]
    if switch is not None:
        # a program with that switch set is the faulty reference's model
        switched = config(**switch)

        def gap(**kw):
            return CHECK.nll_gap(
                switched, params, tokens, ref_cfg=config(), **kw
            )

        assert gap() > 50 * TOL
        assert gap(wrong=wrong) <= TOL


def test_the_reference_computed_lower_is_another_result(params, tokens):
    exact = CHECK.reference("token_nll", config(), params, tokens)
    for lowered, least in (("router", 1e-6), ("rule", 5 * TOL),
                           ("all", 100 * TOL)):
        other = CHECK.reference(
            "token_nll", config(), params, tokens, lowered=lowered
        )
        assert float(np.abs(other - exact).mean()) > least, lowered


def test_the_program_s_choice_is_the_reference_s_sort():
    """320 outputs are two and a half lane tiles: the program's
    compare-and-select choice of 8 against the reference's sort."""
    keys = jax.random.split(jax.random.PRNGKey(9), 2)
    n = jax.random.normal(keys[0], (2, 64, 32))
    p = {"router": {"kernel": jax.random.normal(keys[1], (32, 320))}}
    fields = dict(num_experts=320, top_k=8, routed_scaling_factor=1.0)
    with jax.default_matmul_precision("highest"):
        want, counts = jax.jit(functools.partial(ref.router, fields))(n, p)
        vals, idx, _ = jax.jit(lambda n, p: moe_lib._gate(
            n @ p["router"]["kernel"], 8, True, "top1", "softmax", None, 1.0,
        ))(n, p)
    got = (jax.nn.one_hot(idx, 320) * vals[..., None]).sum(-2)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vals).sum(-1), 1.0, atol=1e-5)
    assert float(counts.sum()) == 2 * 64 * 8


def test_sixteen_shares_of_20_of_320_add_up_to_the_uncut_layer():
    """The routed parts of all 16 shares of one layer (a softmax router
    over 320, 8 a token, renormalised), plus the shared expert counted
    once, are the uncut reference's layer; nothing is dropped and the
    shares' pairs add up to all."""
    total, held, d, width = 320, 20, 32, 16
    keys = jax.random.split(jax.random.PRNGKey(5), 8)
    n = jax.random.normal(keys[0], (BATCH, 32, d))
    whole = {
        "router": {"kernel": jax.random.normal(keys[1], (d, total))},
        "wi": 0.2 * jax.random.normal(keys[2], (total, d, width)),
        "wg": 0.2 * jax.random.normal(keys[3], (total, d, width)),
        "wo": 0.2 * jax.random.normal(keys[4], (total, width, d)),
        "shared": {
            name: {"kernel": 0.2 * jax.random.normal(key, shape)}
            for name, key, shape in (
                ("wi", keys[5], (d, width)), ("wg", keys[6], (d, width)),
                ("wo", keys[7], (width, d)),
            )
        },
    }
    fields = dict(
        num_experts=total, top_k=8, norm_topk_prob=True,
        routed_scaling_factor=1.0,
    )
    with jax.default_matmul_precision("highest"):
        shared = ref.swiglu(n, whole["shared"])
    harness.shares_add_up(
        ref, fields, n, whole, held,
        lambda first: moe_lib.MoEMlp(
            num_experts=total, d_ff=width, top_k=8, dispatch="grouped",
            scoring="softmax", experts_held=held,
            first_expert=first, shared_d_ff=width, row_budget_multiple=8.0,
            dtype=jnp.float32, gmm_block_rows=8,
        ),
        shared, TOL,
    )
