"""The state-space scan (ops/ssd.py): the Pallas kernel pair (interpret
mode) and the chunked ``jax.numpy`` form against the recurrence one token at
a time (the reference's ``ssm_recurrence``), forward and every gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.references import nemotron_h as ref
from dlrover_tpu.ops import ssd as ssd_lib

F32 = jnp.float32
TOL = 2e-5


def inputs(batch=2, s=40, heads=4, head_dim=64, groups=2, state=16, seed=0,
           dtype=F32):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (batch, s, heads, head_dim)).astype(dtype),
        dt=jax.nn.softplus(jax.random.normal(k[1], (batch, s, heads))),
        a_head=-jnp.exp(jax.random.normal(k[2], (heads,))),
        b=jax.random.normal(k[3], (batch, s, groups, state)).astype(dtype),
        c=jax.random.normal(k[4], (batch, s, groups, state)).astype(dtype),
        d=jax.random.normal(k[5], (heads,)),
    ), jax.random.normal(k[6], (batch, s, heads, head_dim))


def recurrence(x, dt, a_head, b, c, d):
    share = x.shape[2] // b.shape[2]
    with jax.default_matmul_precision("highest"):
        return ref.ssm_recurrence(
            x, dt, a_head, jnp.repeat(b, share, axis=2),
            jnp.repeat(c, share, axis=2), d,
        )


# (sequence, chunk): one chunk, a length that is several chunks, one that
# ends inside a chunk (padded), one shorter than a chunk
SHAPES = [(16, 16), (64, 16), (40, 16), (24, 8), (8, 16)]


@pytest.mark.parametrize("impl", ssd_lib.IMPLS)
@pytest.mark.parametrize("s,chunk", SHAPES)
def test_forward_matches_the_recurrence(impl, s, chunk):
    args, _ = inputs(s=s)
    y, top = ssd_lib.ssd(**args, chunk=chunk, impl=impl)
    np.testing.assert_allclose(y, recurrence(**args), atol=TOL, rtol=TOL)
    assert np.isfinite(float(top)) and float(top) > 0


@pytest.mark.parametrize("impl", ssd_lib.IMPLS)
@pytest.mark.parametrize("s,chunk", [(64, 16), (40, 16), (24, 8)])
def test_gradients_match_the_recurrence(impl, s, chunk):
    args, weight = inputs(s=s)
    names = sorted(args)

    def through(fn):
        def loss(*values):
            return (fn(**dict(zip(names, values))) * weight).sum()

        return jax.grad(loss, argnums=tuple(range(len(names))))(
            *(args[n] for n in names)
        )

    got = through(lambda **kw: ssd_lib.ssd(**kw, chunk=chunk, impl=impl)[0])
    want = through(recurrence)
    for name, g, w in zip(names, got, want):
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(
            g, w, atol=TOL * scale, rtol=TOL, err_msg=name
        )


def test_the_state_carries_across_a_chunk_boundary():
    """Tokens after a boundary see what was written before it: cutting the
    sequence at the boundary and starting again from zero is another
    result, and the kernel's is the uncut recurrence's."""
    args, _ = inputs(s=32)
    y, _ = ssd_lib.ssd(**args, chunk=16, impl="kernel")
    tail = {
        k: (v[:, 16:] if v.ndim > 1 else v) for k, v in args.items()
    }
    fresh, _ = ssd_lib.ssd(**tail, chunk=16, impl="kernel")
    assert float(jnp.abs(y[:, 16:] - fresh).max()) > 1e-2
    np.testing.assert_allclose(y, recurrence(**args), atol=TOL, rtol=TOL)


def test_heads_of_a_group_share_b_and_c():
    """Head ``h`` reads group ``h // (H / G)``: with each group's rows
    repeated for its heads and G = H, the result is the same."""
    args, _ = inputs(heads=4, groups=2)
    y, _ = ssd_lib.ssd(**args, chunk=16, impl="xla")
    own = dict(
        args, b=jnp.repeat(args["b"], 2, axis=2),
        c=jnp.repeat(args["c"], 2, axis=2),
    )
    y_own, _ = ssd_lib.ssd(**own, chunk=16, impl="xla")
    np.testing.assert_allclose(y, y_own, atol=TOL, rtol=TOL)


def test_the_largest_state_entry_is_a_reading_not_a_result():
    args, _ = inputs()

    def top(x):
        return ssd_lib.ssd(**dict(args, x=x), chunk=16, impl="kernel")[1]

    assert float(jnp.abs(jax.grad(top)(args["x"])).max()) == 0.0
    tops = [
        float(ssd_lib.ssd(**args, chunk=16, impl=impl)[1])
        for impl in ssd_lib.IMPLS
    ]
    assert tops[0] == pytest.approx(tops[1], rel=1e-5)


def test_bfloat16_operands_keep_float32_decay_and_state():
    """bfloat16 x, B, C: products round, ``dt``, the running sums and the
    state do not; both forms stay within bfloat16's rounding of the float32
    recurrence on the same (rounded) inputs."""
    args, _ = inputs(s=64, dtype=jnp.bfloat16)
    want = recurrence(**{
        k: v.astype(F32) for k, v in args.items()
    })
    for impl in ssd_lib.IMPLS:
        y, _ = ssd_lib.ssd(**args, chunk=16, impl=impl)
        assert y.dtype == jnp.bfloat16
        err = float(jnp.abs(y.astype(F32) - want).mean())
        assert err < 0.02 * float(jnp.abs(want).mean()), (impl, err)


@pytest.mark.parametrize("kwargs,message", [
    (dict(impl="pallas"), "impl must be one of"),
    (dict(chunk=12), "chunk must be a multiple"),
])
def test_bad_arguments_raise(kwargs, message):
    args, _ = inputs()
    with pytest.raises(ValueError, match=message):
        ssd_lib.ssd(**args, **{"chunk": 16, "impl": "xla", **kwargs})


def test_the_kernel_refuses_sizes_its_lanes_do_not_hold():
    assert ssd_lib.kernel_fits(64, 64, 8)
    assert not ssd_lib.kernel_fits(4, 48, 2)       # 48 does not divide 128
    assert not ssd_lib.kernel_fits(4, 32, 2)       # a group is 64 lanes wide
    args, _ = inputs(heads=4, head_dim=32, groups=2)
    with pytest.raises(ValueError, match="side by side"):
        ssd_lib.ssd(**args, chunk=16, impl="kernel")
    y, _ = ssd_lib.ssd(**args, chunk=16, impl="xla")
    np.testing.assert_allclose(y, recurrence(**args), atol=TOL, rtol=TOL)
    mixed = dict(args, b=args["b"].astype(jnp.bfloat16))
    with pytest.raises(ValueError, match="share a dtype"):
        ssd_lib.ssd(**mixed, chunk=16, impl="xla")
