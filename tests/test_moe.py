"""MoE gating unit tests (dlrover_tpu/models/moe.py)."""

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.models.moe import (
    STATS_TAIL,
    MoEMlp,
    _router_entropy,
    split_stats,
    top_k_gating,
)


def test_top2_no_slot_collision():
    """First- and second-choice tokens must never share an (expert, slot)."""
    # Two tokens prefer expert 0 then 1; two prefer expert 1 then 0.
    logits = jnp.array(
        [[[2.0, 1.0], [2.0, 1.0], [1.0, 2.0], [1.0, 2.0]]]
    )  # [1, 4, 2]
    dispatch, combine, _, routed = top_k_gating(logits, k=2, capacity=4)
    occupancy = np.asarray(dispatch.sum(axis=1))  # [1, E, C]
    assert occupancy.max() <= 1.0 + 1e-6, occupancy
    # every token got both choices dispatched (capacity is ample)
    assert float(dispatch.sum()) == 8.0
    np.testing.assert_array_equal(routed, [4.0, 4.0])


def test_capacity_drops_overflow():
    logits = jnp.zeros((1, 8, 2))  # all tokens identical -> same expert order
    dispatch, _, _, routed = top_k_gating(logits, k=1, capacity=3)
    occupancy = np.asarray(dispatch.sum(axis=1))
    assert occupancy.max() <= 1.0 + 1e-6
    # only `capacity` tokens make it in
    assert float(dispatch.sum()) == 3.0
    np.testing.assert_array_equal(routed, [3.0, 0.0])


def test_combine_weights_normalized():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(2, 16, 4)).astype(np.float32))
    dispatch, combine, aux, routed = top_k_gating(logits, k=2, capacity=16)
    # the slot counters count what ``dispatch`` holds, expert by expert
    np.testing.assert_array_equal(routed, dispatch.sum(axis=(0, 1, 3)))
    # combine weights per token sum to ~1 where both choices kept
    token_mass = np.asarray(combine.sum(axis=(2, 3)))
    assert token_mass.max() <= 1.0 + 1e-5
    assert float(aux) > 0.0


def test_router_entropy_bounds():
    """Uniform logits hit ln(E); a collapsed router hits ~0."""
    e = 8
    uniform = jnp.zeros((2, 16, e))
    # float32: the softmax, the log and the 8-term sum each round once.
    np.testing.assert_allclose(
        float(_router_entropy(uniform)), np.log(e),
        rtol=4 * np.finfo(np.float32).eps,
    )
    collapsed = jnp.zeros((2, 16, e)).at[..., 0].set(100.0)
    assert float(_router_entropy(collapsed)) < 1e-3


def _stats_layer(dispatch, capacity_factor=2.0):
    return MoEMlp(
        num_experts=4, d_ff=32, top_k=2, capacity_factor=capacity_factor,
        activation="gelu", dtype=jnp.float32, param_dtype=jnp.float32,
        dispatch=dispatch, gmm_block_rows=8,
    )


def test_router_stats_sown_as_intermediates():
    """Every dispatch path sows the ``moe_stats`` vector — entropy, drop
    fraction, per-expert load — but only when the caller asks for the
    intermediates collection (the compiled step never pays for it)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 16, 16)), jnp.float32)
    layer = _stats_layer("einsum")
    params = layer.init(jax.random.PRNGKey(3), x)
    (out, aux), inter = layer.apply(
        params, x, mutable=["intermediates"]
    )
    (vec,) = jax.tree_util.tree_leaves(inter)
    vec = np.asarray(vec, np.float64).ravel()
    assert vec.shape == (2 + 4 + STATS_TAIL,)
    entropy, drop, load, pad_share, max_load = split_stats(vec)
    assert 0.0 <= entropy <= np.log(4) + 1e-6
    assert 0.0 <= drop <= 1.0
    np.testing.assert_allclose(load.sum(), 1.0, atol=1e-6)
    # 2 sequences x 4 experts x capacity 16 slots hold the 64 chosen pairs
    # less the dropped ones; the busiest expert's share over the mean's.
    np.testing.assert_allclose(pad_share, 1 - 64 * (1 - drop) / 128, atol=1e-6)
    np.testing.assert_allclose(max_load, load.max() * 4, rtol=1e-6)
    # The plain apply returns no intermediates: sow was a no-op.
    plain = layer.apply(params, x)
    assert isinstance(plain, tuple) and len(plain) == 2


def test_router_stats_grouped_is_dropless():
    """The grouped path books drop_fraction == 0 (dropless by design)
    even at a capacity factor that would drop most einsum dispatches."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2, 16, 16)), jnp.float32)
    layer = _stats_layer("grouped", capacity_factor=0.25)
    params = layer.init(jax.random.PRNGKey(4), x)
    _, inter = layer.apply(params, x, mutable=["intermediates"])
    (vec,) = jax.tree_util.tree_leaves(inter)
    vec = np.asarray(vec, np.float64).ravel()
    assert vec[1] == 0.0  # dropless: nothing hit a capacity wall
    # 64 pairs in a budget of (64 / 8 + 4 experts) blocks of 8 rows
    np.testing.assert_allclose(split_stats(vec)[3], 1 - 64 / 96, atol=1e-6)

    einsum_layer = _stats_layer("einsum", capacity_factor=0.25)
    _, inter = einsum_layer.apply(params, x, mutable=["intermediates"])
    (evec,) = jax.tree_util.tree_leaves(inter)
    assert float(np.ravel(evec)[1]) > 0.0  # the einsum path DID drop
