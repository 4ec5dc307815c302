"""The sigmoid router chooses and reads by compare and select: the same
experts, gates and gradient as ``lax.top_k`` with ``take_along_axis`` give,
bit for bit, and no gather, scatter or sort in the compiled scope."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import moe

TOKENS = (2, 16)


def reference_gate(logits, k, norm, bias, scale, groups, topk_group):
    """The router as the parent commit wrote it: sorts and a gather."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    pick = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    if groups > 1:
        e = pick.shape[-1]
        grouped = pick.reshape(*pick.shape[:-1], groups, e // groups)
        score = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)
        _, best = jax.lax.top_k(score, topk_group)
        keep = jax.nn.one_hot(best, groups, dtype=jnp.bool_).any(axis=-2)
        pick = jnp.where(keep[..., None], grouped, -jnp.inf).reshape(
            pick.shape
        )
    _, idx = jax.lax.top_k(pick, k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    if norm:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return gates * scale, idx


def bits(x):
    return np.asarray(x).view(np.uint32)


def both(logits, k, norm, bias, groups, topk_group):
    """(gates, places, d loss / d logits) of the program's gate and of the
    reference, op by op: inside one jitted program XLA's fusion decides the
    rounding of the arithmetic AROUND the read, on either side."""
    weigh = jax.random.normal(jax.random.PRNGKey(7), (*logits.shape[:-1], k))

    def program(x):
        gates, idx, aux = moe._gate(
            x, k, norm, "top1", "sigmoid", bias, 2.5, groups, topk_group
        )
        assert float(aux) == 0.0
        return (gates * weigh).sum(), (gates, idx)

    def reference(x):
        gates, idx = reference_gate(
            x, k, norm, bias, 2.5, groups, topk_group
        )
        return (gates * weigh).sum(), (gates, idx)

    out = []
    for f in (program, reference):
        d, (gates, idx) = jax.grad(f, has_aux=True)(logits)
        out.append((gates, idx, d))
    return out


@pytest.mark.parametrize("groups", [(1, 1), (8, 4)], ids=["flat", "8of4"])
@pytest.mark.parametrize("norm", [True, False], ids=["norm", "raw"])
@pytest.mark.parametrize("with_bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("k", [6, 8])
@pytest.mark.parametrize("experts", [128, 256, 512])
def test_the_sigmoid_gate_is_the_gathers_bit_for_bit(
    experts, k, with_bias, norm, groups
):
    logits = 2.0 * jax.random.normal(
        jax.random.PRNGKey(experts + k), (*TOKENS, experts)
    )
    bias = 0.1 * jax.random.normal(
        jax.random.PRNGKey(1), (experts,)
    ) if with_bias else None
    (gates, idx, d), (want_gates, want_idx, want_d) = both(
        logits, k, norm, bias, *groups
    )
    assert idx.dtype == want_idx.dtype == jnp.int32
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_array_equal(bits(gates), bits(want_gates))
    np.testing.assert_array_equal(bits(d), bits(want_d))
    assert float(jnp.abs(d).max()) > 0.0


@pytest.mark.parametrize("groups", [(1, 1), (8, 4)], ids=["flat", "8of4"])
def test_equal_scores_are_chosen_as_top_k_chooses_them(groups):
    """Runs of equal logits (and whole tokens of them): the places are
    ``lax.top_k``'s, the lower place first, in its order."""
    rng = np.random.default_rng(3)
    logits = rng.integers(-2, 3, (*TOKENS, 256)).astype(np.float32)
    logits[0, 0] = 0.0
    logits[1, 3, 10:200] = 1.5
    (gates, idx, d), (want_gates, want_idx, want_d) = both(
        jnp.asarray(logits), 8, True, None, *groups
    )
    np.testing.assert_array_equal(idx, want_idx)
    if groups == (1, 1):
        np.testing.assert_array_equal(idx[0, 0], np.arange(8))
    np.testing.assert_array_equal(bits(gates), bits(want_gates))
    np.testing.assert_array_equal(bits(d), bits(want_d))


@pytest.mark.parametrize("k", [1, 2, 8])
def test_top_places_are_top_ks_indices(k):
    rng = np.random.default_rng(k)
    x = rng.integers(0, 6, (3, 5, 64)).astype(np.float32)
    x[0, 0, 8:] = -np.inf      # k entries above -inf: what the gate hands it
    np.testing.assert_array_equal(
        moe.top_places(jnp.asarray(x), k), jax.lax.top_k(jnp.asarray(x), k)[1]
    )


def test_the_compiled_router_scope_gathers_scatters_and_sorts_nothing():
    """``jit(grad)`` of the layer, compiled: under ``router`` no gather, no
    scatter and no sort, and nothing there writes more than ``[T, E]``
    numbers (the ``[T, k, E]`` compare lives inside its reduction)."""
    tokens, experts, k = 64, 64, 4
    layer = moe.MoEMlp(
        num_experts=experts, d_ff=16, top_k=k, activation="swiglu",
        dtype=jnp.float32, param_dtype=jnp.float32, dispatch="grouped",
        gmm_block_rows=8, scoring="sigmoid", router_bias=True,
        routed_scale=2.5, router_groups=4, router_topk_groups=2,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (2, tokens // 2, 32))
    params = layer.init(jax.random.PRNGKey(1), x)

    def loss(p):
        out, _ = layer.apply(p, x)
        return jnp.square(out).sum()

    text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    instruction = re.compile(
        r"^\s*(?:ROOT )?%\S+ = (\([^=]*?\)|\S+) ([\w-]+)\("
    )
    largest, seen = 0, set()
    fused = False
    for line in text.splitlines():
        if not line.startswith(" "):
            # a computation's header: what a fused one holds is written
            # nowhere but in its caller's result
            fused = "fused_computation" in line or "region_" in line
        found = instruction.match(line)
        name = re.search(r'op_name="([^"]*)"', line)
        if not found or not name or "/router/" not in name.group(1):
            continue
        target = re.search(r'custom_call_target="(\w+)"', line)
        seen.add(target.group(1) if target else found.group(2))
        if fused:
            continue
        for dims in re.findall(r"\w+\[([\d,]*)\]", found.group(1)):
            size = int(np.prod([int(n) for n in dims.split(",") if n] or [1]))
            largest = max(largest, size)
    assert seen, "no instruction under the router scope was found"
    # (``lax.top_k`` is a sort on the chip and the call ``TopK`` here)
    assert not seen & {"gather", "scatter", "sort", "TopK"}, seen
    assert "reduce" in seen or "fusion" in seen
    # the router's product reads [T, d] and its transpose writes [d, E]
    assert 0 < largest <= tokens * experts, largest
    assert largest < tokens * k * experts
