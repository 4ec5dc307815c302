"""That the comparison of ``tests/test_ling_flash_reference.py`` is sharp:
each fault, made on the reference's side, moves a token's loss past the
tolerance held there (and the program's own switch, where it has one, makes
the faulty reference agree again), and the reference computed in a lower
precision is another result.  Then the group-limited choice on its own, and
the shares of an expert layer under it."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_harness as harness
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.references import ling_flash as ref
from test_ling_flash_reference import (  # noqa: F401 (fixtures)
    BATCH, CHECK, TOL, config, params, tokens,
)

# each fault, and the program's own switch that makes the faulty reference
# agree again (where the program has one that leaves the parameters' tree
# as it is)
WRONG = {
    "scalar_decay": None,
    "softplus_gate": None,
    "beta_doubled": None,
    "no_group_limit": dict(router_groups=1, router_topk_groups=1),
    "group_by_max": None,
    "no_head_gate": None,
    "gate_per_channel": None,
    "q_norm": None,
    "bias_weighs": None,
}


def test_every_fault_the_reference_can_make_is_tried():
    assert sorted(WRONG) == sorted(ref.FAULTS)
    with pytest.raises(ValueError, match="wrong must be one of"):
        ref.forward({}, {}, None, wrong="something_else")


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_the_check_is_sharp(wrong, params, tokens):
    """Each fault, made on one side, moves a token's loss past the
    tolerance the reference tests hold (the smallest read: a bias that
    weighs, 1.8e-2)."""
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
    for lowered, least in (("router", TOL), ("rule", 10 * TOL),
                           ("all", 100 * TOL)):
        other = CHECK.reference(
            "token_nll", config(), params, tokens, lowered=lowered
        )
        assert float(np.abs(other - exact).mean()) > least, lowered


# -- the group-limited choice ----------------------------------------------------


def test_the_group_limit_keeps_the_best_groups_by_their_two_largest():
    """Eight experts in four groups of two, two groups and two experts a
    token.  Group sums: 1.0, 1.1, 0.95, 0.3: groups 0 and 1 stay, though
    group 2 holds the third-largest score; the two chosen come from them."""
    scores = jnp.asarray([[[0.6, 0.4, 0.9, 0.2, 0.7, 0.25, 0.2, 0.1]]])
    logits = jnp.log(scores / (1 - scores))
    gates, idx, _ = moe_lib._gate(
        logits, 2, True, "top1", "sigmoid", None, 1.0, 4, 2
    )
    assert sorted(np.asarray(idx)[0, 0].tolist()) == [0, 2]
    # without the limit the third-largest (expert 4) is chosen
    _, free, _ = moe_lib._gate(logits, 2, True, "top1", "sigmoid", None, 1.0)
    assert sorted(np.asarray(free)[0, 0].tolist()) == [2, 4]
    # the bias moves a group's score as it moves an expert's
    bias = jnp.asarray([0.0, 0.0, -0.5, 0.0, 0.0, 0.3, 0.0, 0.0])
    gates, idx, _ = moe_lib._gate(
        logits, 2, True, "top1", "sigmoid", bias, 2.5, 4, 2
    )
    # groups: 1.0, 0.4 + 0.2 = 0.6, 0.7 + 0.55 = 1.25, 0.3: 2 and 0 stay
    assert sorted(np.asarray(idx)[0, 0].tolist()) == [0, 4]
    by_expert = dict(zip(np.asarray(idx)[0, 0].tolist(),
                         np.asarray(gates)[0, 0].tolist()))
    # weighed by s alone, renormalised over the chosen, times 2.5
    assert by_expert[0] == pytest.approx(2.5 * 0.6 / 1.3, rel=1e-5)
    assert by_expert[4] == pytest.approx(2.5 * 0.7 / 1.3, rel=1e-5)


def test_the_program_s_choice_is_the_reference_s_sort():
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    n = jax.random.normal(keys[0], (2, 64, 32))
    p = {
        "router": {"kernel": jax.random.normal(keys[1], (32, 64))},
        "router_bias": 0.1 * jax.random.normal(keys[2], (64,)),
    }
    fields = dict(
        num_experts=64, top_k=6, router_groups=8, router_topk_groups=3,
        routed_scaling_factor=2.5,
    )
    # one program a side (op by op the two are fifty)
    with jax.default_matmul_precision("highest"):
        want, counts = jax.jit(functools.partial(ref.router, fields))(n, p)
        vals, idx, _ = jax.jit(lambda n, p: moe_lib._gate(
            n @ p["router"]["kernel"], 6, True, "top1", "sigmoid",
            p["router_bias"], 2.5, 8, 3,
        ))(n, p)
    got = (jax.nn.one_hot(idx, 64) * vals[..., None]).sum(-2)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # every token's six come from three groups of eight
    groups = np.asarray(idx) // 8
    assert all(len(set(row)) <= 3 for row in groups.reshape(-1, 6))
    assert float(counts.sum()) == 2 * 64 * 6


def test_a_group_limit_on_a_softmax_router_raises():
    with pytest.raises(ValueError, match="sigmoid router's"):
        moe_lib._gate(jnp.zeros((1, 4, 8)), 2, groups=2, topk_group=1)


# -- the shares ------------------------------------------------------------------


def test_sixteen_shares_of_32_of_512_add_up_to_the_uncut_layer():
    """The routed parts of all 16 shares of one layer under the group limit
    (8 groups of 64, 4 groups and 8 experts a token: a share is half a
    group), plus the shared expert counted once, are the uncut reference's
    layer; nothing is dropped and the shares' pairs add up to all."""
    total, held, d, width = 512, 32, 32, 16
    keys = jax.random.split(jax.random.PRNGKey(5), 9)
    n = jax.random.normal(keys[0], (BATCH, 32, d))
    whole = {
        "router": {"kernel": jax.random.normal(keys[1], (d, total))},
        "router_bias": 0.05 * jax.random.normal(keys[2], (total,)),
        "wi": 0.2 * jax.random.normal(keys[3], (total, d, width)),
        "wg": 0.2 * jax.random.normal(keys[4], (total, d, width)),
        "wo": 0.2 * jax.random.normal(keys[5], (total, width, d)),
        "shared": {
            name: {"kernel": 0.2 * jax.random.normal(key, shape)}
            for name, key, shape in (
                ("wi", keys[6], (d, width)), ("wg", keys[7], (d, width)),
                ("wo", keys[8], (width, d)),
            )
        },
    }
    fields = dict(
        num_experts=total, top_k=8, norm_topk_prob=True,
        routed_scaling_factor=2.5, router_groups=8, router_topk_groups=4,
    )
    with jax.default_matmul_precision("highest"):
        shared = ref.swiglu(n, whole["shared"])
    harness.shares_add_up(
        ref, fields, n, whole, held,
        lambda first: moe_lib.MoEMlp(
            num_experts=total, d_ff=width, top_k=8, dispatch="grouped",
            scoring="sigmoid", router_bias=True, routed_scale=2.5,
            experts_held=held, first_expert=first, shared_d_ff=width,
            router_groups=8, router_topk_groups=4,
            row_budget_multiple=8.0, dtype=jnp.float32, gmm_block_rows=8,
        ),
        shared, TOL,
    )


def test_tokens_here_counts_the_tokens_with_a_pair_here():
    """Under the group limit half the tokens send nothing to a given group:
    ``tokens_here`` is read from the step's own choice."""
    keys = jax.random.split(jax.random.PRNGKey(11), 2)
    n = jax.random.normal(keys[0], (2, 64, 32))
    layer = moe_lib.MoEMlp(
        num_experts=64, d_ff=16, top_k=4, dispatch="grouped",
        scoring="sigmoid", router_bias=True, experts_held=8, first_expert=8,
        router_groups=8, router_topk_groups=2, row_budget_multiple=8.0,
        dtype=jnp.float32, gmm_block_rows=8,
    )
    import flax.linen as nn

    # the layer and the reference's router as a program each (op by op
    # they are two hundred)
    variables = nn.meta.unbox(jax.jit(layer.init)(keys[1], n))
    _, sown = jax.jit(
        lambda v, n: layer.apply(v, n, mutable=["intermediates"])
    )(variables, n)
    pairs, _, tokens_here = np.asarray(
        sown["intermediates"][moe_lib.SHARE_STATS_NAME][0]
    )
    p = jax.tree.map(lambda a: a, variables["params"])
    gates, counts = jax.tree.map(np.asarray, jax.jit(functools.partial(
        ref.router,
        dict(num_experts=64, top_k=4, router_groups=8, router_topk_groups=2),
    ))(n, p))
    assert pairs == pytest.approx(float(counts[8:16].sum() / counts.sum()))
    want = float((gates[..., 8:16] > 0).any(-1).mean())
    assert tokens_here == pytest.approx(want)
    # a token has a pair here only if group 1 is one of its two of eight
    assert 0 < tokens_here <= 0.5
