"""The gated delta rule's kernels at Olmo-Hybrid's published head widths
(dk 96, dv 192, chunk 128), float32 and bfloat16 operands, against the
token-by-token recurrence; beside ``tests/test_gated_delta_rule.py``, whose
inputs and comparison it takes."""

import jax.numpy as jnp
import pytest

from dlrover_tpu.models.references import olmo_hybrid as reference
from dlrover_tpu.ops.gated_delta_rule import gated_delta_rule
from test_gated_delta_rule import rule_and_grads, rule_inputs


# bfloat16 operands: W, V', M and the start states are rounded to 8 bits
# inside a chunk, which moves a gradient by 0.3-1.2% of its largest entry
# (read here, both lengths); a dropped term moves it by tens of percent.
KERNEL_RTOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("length,cotangent", [
    (128, "all"), (384, "all"), (300, "all"), (384, "last_chunk"),
])
def test_kernel_gradients_at_the_published_head_widths(
    length, cotangent, dtype
):
    """dk 96, dv 192, chunk 128: one chunk, three, and a length that is
    no whole number of chunks; every gradient against autodiff of the
    token-by-token recurrence on the same (rounded) operands.  With the
    cotangent on the last chunk alone, all that reaches the first chunk's
    tokens has crossed two chunk boundaries as the state's cotangent."""
    args, do = rule_inputs(length, length, True, heads=2, dk=96, dv=192)
    args = tuple(a.astype(dtype) for a in args[:3]) + args[3:]
    if cotangent == "last_chunk":
        do = do * (jnp.arange(length) >= 256)[None, :, None, None]
    in_f32 = tuple(a.astype(jnp.float32) for a in args)
    want_o, want = rule_and_grads(
        reference.delta_rule_recurrence, in_f32, do
    )
    got_o, got = rule_and_grads(
        lambda *a: gated_delta_rule(*a)[0], args, do
    )
    assert got_o.dtype == dtype
    rtol = KERNEL_RTOL[dtype]
    assert float(jnp.abs(got_o - want_o).max()) <= rtol * float(
        jnp.abs(want_o).max()
    )
    for name, g, w in zip("q k v g beta".split(), got, want):
        assert g.dtype == (jnp.float32 if name in ("g", "beta") else dtype)
        first = jnp.abs(w[:, :128]).max()
        # (q_t reaches no output but its own token's)
        assert float(first) > 0 or (name, cotangent) == ("q", "last_chunk")
        assert float(jnp.abs(g - w).max()) <= rtol * float(
            jnp.abs(w).max()
        ), name
        # and the first chunk's own gradients, by their own size
        assert float(jnp.abs(g[:, :128] - w[:, :128]).max()) <= (
            rtol * float(first)
        ), name


def test_alike_keys_in_bfloat16_stay_with_the_recurrence():
    """The inverse's three-pass products (bfloat16 operands take that
    path; float32 operands multiply exactly) on keys at cosine 0.9 and
    beta 1.98: within the rounding of the operands, where one pass or
    the product of powers is not."""
    args, _ = rule_inputs(9, 256, True, heads=2, dk=96, dv=192)
    q, k, v, g, beta = args
    k = k + 0.3 * jnp.ones_like(k[:1, :1, :1])
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    cos = jnp.einsum("bshk,bthk->bhst", k, k)
    assert float(cos.min()) > 0.6
    args = tuple(a.astype(jnp.bfloat16) for a in (q, k, v)) + (
        0.05 * g, 0.99 * jnp.full_like(beta, 2.0),
    )
    want = reference.delta_rule_recurrence(
        *(a.astype(jnp.float32) for a in args)
    )
    got, _ = gated_delta_rule(*args)
    assert float(jnp.abs(got - want).max()) <= 3e-2 * float(
        jnp.abs(want).max()
    )
