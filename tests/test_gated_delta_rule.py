"""The chunked gated delta rule (``ops/gated_delta_rule.py``: its Pallas
kernels in interpret mode, forward and backward) against the token-by-token
recurrence of Olmo-Hybrid's plain reference: outputs, every gradient, the
largest boundary state, the chunks it refuses.

Tolerances, float32 against float32: the chunked form orders its sums
differently from the recurrence and builds ``(I + A)^-1`` by products,
which moves an output by about 1e-6 of the largest."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.references import olmo_hybrid as reference
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
