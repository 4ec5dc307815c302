"""The chunked gated delta rule (``ops/gated_delta_rule.py``: its Pallas
kernels in interpret mode, forward and backward) against the token-by-token
recurrence of Olmo-Hybrid's plain reference: outputs, every gradient, the
largest boundary state, the chunks it refuses; and the chunk's inverse
``(I + A)^-1`` alone against float64, beside the whole-matrix build it
replaced (PR 51), with the rows it multiplies counted.

Tolerances, float32 against float32: the chunked form orders its sums
differently from the recurrence and builds ``(I + A)^-1`` by products,
which moves an output by about 1e-6 of the largest."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.references import olmo_hybrid as reference
from dlrover_tpu.ops import gated_delta_rule as rule_lib
from dlrover_tpu.ops.gated_delta_rule import gated_delta_rule

BATCH = 2


def rule_inputs(seed, length, neg, heads=3, dk=8, dv=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (BATCH, length, heads)
    q = jax.random.normal(keys[0], shape + (dk,))
    k = jax.random.normal(keys[1], shape + (dk,))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], shape + (dv,))
    g = -0.5 * jax.nn.softplus(jax.random.normal(keys[3], shape))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape)) * (2 if neg else 1)
    do = jax.random.normal(keys[5], shape + (dv,))
    return (q, k, v, g, beta), do


@pytest.mark.parametrize("neg", [True, False])
@pytest.mark.parametrize("length,chunk", [
    (64, 16), (64, 64), (100, 16), (100, 64), (200, 128),
])
def test_chunked_rule_is_the_recurrence(chunk, length, neg):
    """Outputs and every gradient; 100 and 200 are no multiples of their
    chunks, and every case but (64, 64) crosses a chunk boundary."""
    args, do = rule_inputs(length + chunk, length, neg)
    want, want_grads = rule_and_grads(
        reference.delta_rule_recurrence, args, do
    )
    got, got_grads = rule_and_grads(
        lambda *a: gated_delta_rule(*a, chunk=chunk)[0], args, do
    )
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 1e-5 * scale
    for name, g, w in zip("q k v g beta".split(), got_grads, want_grads):
        assert float(jnp.abs(w).max()) > 0, name
        assert float(jnp.abs(g - w).max()) <= 2e-5 * float(
            jnp.abs(w).max()
        ), name


def test_keys_that_resemble_each_other_keep_their_digits():
    """Trained keys are alike (after SiLU most channels are positive), and
    with beta near 2 the chunk's ``(I + A)^-1`` is then ill-suited to a
    product of powers of ``A``: that form lost every digit here."""
    args, _ = rule_inputs(9, 256, True)
    q, k, v, g, beta = args
    k = k + 3.0 * jnp.ones_like(k[:1, :1, :1])
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    cos = jnp.einsum("bshk,bthk->bhst", k, k)
    assert float(cos.min()) > 0.6
    args = (q, k, v, 0.05 * g, 0.99 * jnp.full_like(beta, 2.0))
    want = reference.delta_rule_recurrence(*args)
    got, _ = gated_delta_rule(*args)          # the program's chunk, 128
    assert float(jnp.abs(got - want).max()) <= 1e-4 * float(
        jnp.abs(want).max()
    )


def test_state_absmax_is_the_largest_boundary_state():
    args, _ = rule_inputs(3, 96, True)
    q, k, v, g, beta = args
    _, got = gated_delta_rule(*args, chunk=16)
    # the recurrence's states at the chunk boundaries, by its own step
    state, top = jnp.zeros((BATCH, 3, 16, 8)), 0.0
    for t in range(96):
        alpha = jnp.exp(g[:, t])[..., None, None]
        bt = beta[:, t][..., None, None]
        kk = k[:, t][..., :, None] * k[:, t][..., None, :]
        state = alpha * (state - bt * state @ kk) + bt * (
            v[:, t][..., :, None] * k[:, t][..., None, :]
        )
        if (t + 1) % 16 == 0:
            top = max(top, float(jnp.abs(state).max()))
    np.testing.assert_allclose(float(got), top, rtol=1e-5)


def rule_and_grads(fn, args, do):
    """``fn``'s output and the gradients of ``sum(output x do)`` to all
    five arguments, as one program."""
    def loss(*a):
        out = fn(*a)
        return (out.astype(jnp.float32) * do).sum(), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True
    ))(*args)
    return out, grads


@pytest.mark.parametrize("chunk,dtype,message", [
    (96, jnp.float32, "power of two"),
    (8, jnp.bfloat16, "16 rows of a bfloat16 tile, got 8"),
])
def test_a_chunk_the_kernel_cannot_tile_raises_with_the_numbers(
    chunk, dtype, message
):
    args, _ = rule_inputs(1, 64, True)
    args = tuple(a.astype(dtype) for a in args[:3]) + args[3:]
    with pytest.raises(ValueError, match=message):
        gated_delta_rule(*args, chunk=chunk)


def whole_matrix_inverse(a, row, col, exact):
    """``_unit_lower_inverse`` as it was until PR 51, the yardstick: every
    level two whole [C, C] products, structural zeros and all."""
    c = a.shape[-1]
    inv = jnp.where(row == col, 1.0, 0.0) - jnp.where(
        (row == col + 1) & ((col & 1) == 0), a, 0.0
    )
    level = 1
    while (1 << level) < c:
        lower_left = (
            ((row >> (level + 1)) == (col >> (level + 1)))
            & (((row >> level) & 1) == 1) & (((col >> level) & 1) == 0)
        )
        p = rule_lib._halves(inv, exact)
        e = rule_lib._halves(jnp.where(lower_left, a, 0.0), exact)
        inv = inv - rule_lib._mm_f32(
            rule_lib._halves(rule_lib._mm_f32(p, e), exact), p
        )
        level += 1
    return inv


def live_rows_inverse(a, row, col, exact):
    return rule_lib._alone(rule_lib._unit_lower_inverse(a, row, col, exact))


def chunk_matrix(c, alike):
    """A chunk's strictly lower ``A`` and its iotas: keys drawn at random,
    or the hard case (cosines above 0.6, beta 1.98)."""
    rng = np.random.default_rng(c + alike)
    k = rng.standard_normal((c, 32)) + (3.0 if alike else 0.0)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    if alike:
        assert (k @ k.T).min() > 0.6
    beta = np.full((c, 1), 1.98) if alike else rng.uniform(0, 2, (c, 1))
    a = jnp.asarray(np.tril(beta * (k @ k.T), -1), jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return a, row, col


@pytest.mark.parametrize("alike", [False, True], ids=["random", "alike"])
@pytest.mark.parametrize("exact", [True, False], ids=["exact", "halves"])
@pytest.mark.parametrize("c", [16, 32, 64, 128])
def test_the_chunk_inverse_is_no_further_from_float64_than_it_was(
    c, exact, alike
):
    """The same recursion on the rows that are not zero: against
    ``numpy.linalg.inv`` in float64 no worse than the whole-matrix build,
    and with float32 operands the two agree to float32 rounding."""
    a, row, col = chunk_matrix(c, alike)
    want = np.linalg.inv(np.eye(c) + np.asarray(a, np.float64))
    got = np.asarray(jax.jit(live_rows_inverse, static_argnums=3)(
        a, row, col, exact
    ), np.float64)
    was = np.asarray(jax.jit(whole_matrix_inverse, static_argnums=3)(
        a, row, col, exact
    ), np.float64)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= np.abs(was - want).max() + 1e-7 * scale
    assert np.abs(got - want).max() <= (1e-5 if exact else 2e-3) * scale
    if exact:
        assert np.abs(got - was).max() <= 1e-6 * scale


def test_the_chunk_inverse_pushes_under_half_the_rows_it_did():
    """The mechanism's engagement, which is static: the left operands of
    a 128-token chunk's ``dot_general``s hold at most half the rows of the
    whole-matrix build's 36 passes of 128 (the levels from the sub-chunk
    up their 64 live rows, the levels under it the 16 of the fold)."""
    a, row, col = chunk_matrix(128, False)

    def rows_pushed(build):
        eqns = jax.make_jaxpr(
            lambda a: build(a, row, col, False)
        )(a).jaxpr.eqns
        return sum(
            e.invars[0].aval.shape[0] for e in eqns
            if e.primitive.name == "dot_general"
        )

    assert rows_pushed(whole_matrix_inverse) == 36 * 128
    assert rows_pushed(live_rows_inverse) == 18 * 64 + 18 * 16


def test_a_grid_steps_heads_take_their_stages_in_turn():
    """``_in_lockstep`` advances every head by a stage before any head's
    next, and a head that ends early leaves the turn to the others;
    ``_alone`` runs one head through and hands back what it returns."""
    said = []

    def head(name, stages):
        for n in range(stages):
            said.append((name, n))
            yield
        return name

    rule_lib._in_lockstep([head("a", 3), head("b", 1), head("c", 2)])
    assert said == [
        ("a", 0), ("b", 0), ("c", 0), ("a", 1), ("c", 1), ("a", 2),
    ]
    assert rule_lib._alone(head("d", 2)) == "d"


@pytest.mark.parametrize("kernel", ["forward", "backward"])
def test_the_kernels_products_interleave_the_heads(kernel):
    """What ``_in_lockstep`` is for, counted in a kernel body's program
    order: between the first and the last product that reads head 0's
    refs stand products of the other heads (written head after head, a
    grid step's chains of products waited one after another)."""
    args, do = rule_inputs(7, 32, True, heads=3, dk=8, dv=16)
    fn = lambda *a: gated_delta_rule(*a, chunk=16)[0]
    if kernel == "backward":
        fn = jax.grad(lambda *a: (gated_delta_rule(*a, chunk=16)[0] * do).sum())
    body = find_kernel(jax.make_jaxpr(fn)(*args).jaxpr, kernel)
    owner = {}          # a value's head: the index its ref was read at
    products = []
    for e in body.eqns:
        heads_read = {
            owner[v] for v in e.invars if not hasattr(v, "val") and v in owner
        }
        if e.primitive.name == "get" and hasattr(e.invars[1], "val"):
            heads_read = {int(e.invars[1].val)}
        if len(heads_read) == 1:
            head, = heads_read
            owner.update((v, head) for v in e.outvars)
            if e.primitive.name == "dot_general":
                products.append(head)
    assert set(products) == {0, 1, 2}
    first = products.index(0)
    last = len(products) - 1 - products[::-1].index(0)
    assert {1, 2} <= set(products[first:last])


def find_kernel(jaxpr, kernel):
    """The body of the rule's forward or backward ``pallas_call``."""
    name = f"delta_rule_{'fwd' if kernel == 'forward' else 'bwd'}"
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call" and name in str(
            e.params.get("name_and_src_info", e.params.get("name", ""))
        ):
            return e.params["jaxpr"]
        for sub in jax.core.jaxprs_in_params(e.params):
            found = find_kernel(sub, kernel)
            if found is not None:
                return found
    return None
