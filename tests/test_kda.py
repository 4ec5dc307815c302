"""Kimi Delta Attention's rule (``ops/kda.py``): the chunked ``jax.numpy``
form and the Pallas kernels (in interpret mode, forward and backward)
against the token-by-token recurrence of Ling-3.0-flash's plain reference:
outputs and every gradient, with the log decay near its bound of -5, near 0
and mixed, on sequences that are no whole chunk; the largest boundary
state; the chunks it refuses.  The SPLIT form under its bound, and the
EXACT form (the triangle cut by halves: Solar-Open2's gate has no bound)
on the same cases and on its own: ``g`` down to -200 a token, ``g`` = 0,
beta up to 2.

Tolerances, float32 against float32: the chunked form splits every decay
``exp(G_t - G_i)`` into two factors around a sub-chunk's middle (each up to
e^40, so a product carries the rounding of two exponentials of arguments up
to 40: 40 x 6e-8 relative), orders its sums differently from the recurrence
and builds ``(I + A)^-1`` by products: 2e-5 of the largest entry."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.references import ling_flash as reference
from dlrover_tpu.ops import kda as kda_lib

BATCH, HEADS = 2, 2
# where the gate's pre-activation is centred: every channel near the bound
# of -5, spread over (-5, 0), every channel near 0
NEAR = {"bound": 6.0, "mixed": 0.0, "zero": -6.0}
# the gates only the exact form takes: half the channels slow, the others
# with a heavy tail down to -200 a token (a fifth of all past the split
# form's floor of -5.5); no decay at all; both with beta up to 2
FREE = {"free": -200.0, "none": 0.0}
EXACT_TOL = 5e-6


def rule_inputs(seed, length, dk, dv, near):
    if near in FREE:
        return free_inputs(seed, length, dk, dv, FREE[near])
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (BATCH, length, HEADS)
    q = jax.random.normal(keys[0], shape + (dk,))
    k = jax.random.normal(keys[1], shape + (dk,))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], shape + (dv,))
    g = -5.0 * jax.nn.sigmoid(
        2.0 * jax.random.normal(keys[3], shape + (dk,)) + NEAR[near]
    )
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape))
    do = jax.random.normal(keys[5], shape + (dv,))
    return (q, k, v, g, beta), do


def free_inputs(seed, length, dk, dv, g_min):
    (q, k, v, _, _), do = rule_inputs(seed, length, dk, dv, "mixed")
    keys = jax.random.split(jax.random.PRNGKey(seed + 1000), 3)
    shape = (BATCH, length, HEADS)
    g = g_min * jax.random.uniform(keys[0], shape + (dk,)) ** 6 * (
        jax.random.uniform(keys[1], shape + (dk,)) < 0.5
    )
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(keys[2], shape))
    return (q, k, v, g, beta), do


@functools.cache
def _jitted(rule):
    """``rule`` and the gradients of its output against a cotangent, a
    program each for a shape: the cases that differ in their numbers alone
    (where the gate is centred) run what the first of them compiled."""
    return jax.jit(rule), jax.jit(jax.grad(
        lambda do, *a: (rule(*a) * do).sum(), argnums=(1, 2, 3, 4, 5),
    ))


@functools.cache
def chunked(chunk, exact=False):
    return lambda *a: kda_lib.kda(*a, chunk=chunk, exact=exact)[0]


def rule_and_grads(rule, args, do):
    forward, grads = _jitted(rule)
    with jax.default_matmul_precision("highest"):
        return grads(do, *args), forward(*args)


# (dk, dv): the widths decide the path (``plan``)
WIDTHS = {"xla": (16, 24), "kernel": (128, 128)}


# (form, where the gate is centred): the split form under its bound, the
# exact form there and past it
FORMS = [("split", near) for near in sorted(NEAR)] + [
    ("exact", near) for near in sorted(NEAR) + sorted(FREE)
]


@pytest.mark.parametrize("form,near", FORMS)
@pytest.mark.parametrize("length,chunk", [(80, 32), (64, 64), (40, 16)])
@pytest.mark.parametrize("path", sorted(WIDTHS))
def test_chunked_rule_is_the_recurrence(path, length, chunk, form, near):
    """Outputs and every gradient; 80 and 40 are no multiples of their
    chunks, and every case but (64, 64) crosses a chunk boundary."""
    dk, dv = WIDTHS[path]
    exact = form == "exact"
    assert kda_lib.plan(dk, dv) == path
    assert kda_lib.plan(dk, dv, exact) == path + "_exact" * exact
    args, do = rule_inputs(length + chunk, length, dk, dv, near)
    if near == "bound":
        assert float(args[3].mean()) < -4.8
    if near == "zero":
        assert float(args[3].mean()) > -0.2
    if near == "free":
        assert float(args[3].min()) < -190 and float(args[4].max()) > 1.9
        assert float((args[3] < kda_lib.SPLIT_FLOOR).mean()) > 0.15
    if near == "none":
        assert float(jnp.abs(args[3]).max()) == 0.0
    tol = EXACT_TOL if exact else 2e-5
    want_grads, want = rule_and_grads(reference.kda_recurrence, args, do)
    got_grads, got = rule_and_grads(chunked(chunk, exact), args, do)
    scale = float(jnp.abs(want).max())
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) <= tol * scale
    for name, g, w in zip("q k v g beta".split(), got_grads, want_grads):
        top = float(jnp.abs(w).max())
        assert top > 0 or (name == "g" and near == "free"), name
        assert bool(jnp.isfinite(g).all()), name
        assert float(jnp.abs(g - w).max()) <= tol * top + 1e-7, name


def test_the_split_form_overflows_where_the_exact_form_does_not():
    """Why there are two: under a gate without a bound the split form's
    factor ``exp(r - G_i)`` passes float32 (infinities, then NaNs), and the
    exact form, whose every exponent is a sum of ``g`` between column and
    row, stays finite and right at a whole chunk of 128 and 200 a token."""
    (q, k, v, g, beta), _ = free_inputs(11, 256, 128, 128, -200.0)
    with jax.default_matmul_precision("highest"):
        split = kda_lib.kda(q, k, v, g, beta)[0]
        exact, absmax = kda_lib.kda(q, k, v, g, beta, exact=True)
        want = reference.kda_recurrence(q, k, v, g, beta)
    assert not bool(jnp.isfinite(split).all())
    assert bool(jnp.isfinite(exact).all()) and bool(jnp.isfinite(absmax))
    assert float(jnp.abs(exact - want).max()) <= EXACT_TOL * float(
        jnp.abs(want).max()
    )


def test_a_sub_chunk_at_the_bound_stays_finite_and_right():
    """Every channel of every token at the bound itself: a sub-chunk's
    total decay is 16 x 5 = 80, inside float32 only because the reference
    sum sits in the sub-chunk's middle (factors e^-40 .. e^40)."""
    (q, k, v, g, beta), _ = rule_inputs(3, 64, 128, 128, "mixed")
    g = jnp.full_like(g, -5.0)
    with jax.default_matmul_precision("highest"):
        got = kda_lib.kda(q, k, v, g, beta)[0]
        want = reference.kda_recurrence(q, k, v, g, beta)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) <= 2e-5 * float(
        jnp.abs(want).max()
    )


@pytest.mark.parametrize("exact", [False, True])
@pytest.mark.parametrize("path", sorted(WIDTHS))
def test_state_absmax_is_the_largest_boundary_state(path, exact):
    dk, dv = WIDTHS[path]
    (q, k, v, g, beta), _ = rule_inputs(7, 64, dk, dv, "zero")
    v = 3.0 * v
    with jax.default_matmul_precision("highest"):
        _, absmax = kda_lib.kda(q, k, v, g, beta, chunk=16, exact=exact)

    def states(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[..., None]
        read = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + beta_t[..., None, None] * k_t[..., None] * (
            v_t - read
        )[..., None, :]
        return state, jnp.abs(state).max()

    with jax.default_matmul_precision("highest"):
        _, tops = jax.lax.scan(
            states, jnp.zeros((BATCH, HEADS, dk, dv)),
            tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)),
        )
    boundary = float(tops[15::16].max())
    assert float(absmax) == pytest.approx(boundary, rel=1e-4)
    assert float(absmax) > 0


@pytest.mark.parametrize("exact", [False, True])
@pytest.mark.parametrize("path", sorted(WIDTHS))
def test_the_kernels_start_states_are_named_where_the_backward_reads_them(
    path, exact,
):
    """``kda_states`` is what ``flash_only`` keeps beside the mixer's
    ``kda_out`` (ops/remat_policy.py): the forward kernel's chunk-start
    states, named inside the custom VJP's forward rule.  The ``jax.numpy``
    form has no kernel to drop and names nothing."""
    dk, dv = WIDTHS[path]
    (q, k, v, g, beta), do = rule_inputs(0, 64, dk, dv, "mixed")
    text = str(jax.make_jaxpr(jax.grad(
        lambda q: (
            kda_lib.kda(q, k, v, g, beta, chunk=32, exact=exact)[0] * do
        ).sum()
    ))(q))
    assert ("name=kda_states" in text) == (path == "kernel")
    assert ("kda_bwd" in text) == (path == "kernel")


@pytest.mark.parametrize("chunk", [8, 24, 48])
def test_a_chunk_that_is_no_whole_sub_chunks_raises_with_the_numbers(chunk):
    (q, k, v, g, beta), _ = rule_inputs(0, 32, 16, 16, "mixed")
    with pytest.raises(ValueError, match=f"16-token sub-chunk.*got {chunk}"):
        kda_lib.kda(q, k, v, g, beta, chunk=chunk)


def test_mixed_dtypes_raise():
    (q, k, v, g, beta), _ = rule_inputs(0, 32, 16, 16, "mixed")
    with pytest.raises(ValueError, match="share a dtype"):
        kda_lib.kda(q.astype(jnp.bfloat16), k, v, g, beta)


@pytest.mark.parametrize("any_gate", [False, True])
def test_bfloat16_operands_keep_a_float32_decay_and_state(any_gate):
    """The cell's precision: bfloat16 q, k, v, float32 g and state.  With
    slow decay (where a state adds up hundreds of writes) the result lies
    within bfloat16's rounding of the float32 one (read 4.1e-5 of a mean
    entry of 1e-2), and the recurrence with a bfloat16 state does not
    (9.5e-5); under fast decay both are the output's own rounding.  The
    exact form with beta up to 2 (``T``'s entries from a doubled ``A``)
    reads the same."""
    near = "none" if any_gate else "zero"
    (q, k, v, g, beta), _ = rule_inputs(5, 256, 128, 128, near)
    low = tuple(a.astype(jnp.bfloat16) for a in (q, k, v))
    exact = reference.kda_recurrence(
        *(a.astype(jnp.float32) for a in low), g, beta
    )
    got = kda_lib.kda(*low, g, beta, exact=any_gate)[0].astype(jnp.float32)
    lowered = reference.kda_recurrence(*low, g, beta, jnp.bfloat16).astype(
        jnp.float32
    )
    err = float(jnp.abs(got - exact).mean())
    assert err < 0.6 * float(jnp.abs(lowered - exact).mean())
    assert err < 0.01 * float(jnp.abs(exact).mean())
