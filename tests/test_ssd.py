"""The state-space scan (ops/ssd.py): the Pallas kernel pair (interpret
mode) and the chunked ``jax.numpy`` form against the recurrence one token at
a time (the reference's ``ssm_recurrence``), forward and every gradient."""

import functools

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


def ssd(chunk, impl, **args):
    """``ssd_lib.ssd`` as one program: op by op its chunked form compiles a
    hundred pieces a shape."""
    return jax.jit(
        functools.partial(ssd_lib.ssd, chunk=chunk, impl=impl)
    )(**args)


@jax.jit
def recurrence(x, dt, a_head, b, c, d):
    share = x.shape[2] // b.shape[2]
    with jax.default_matmul_precision("highest"):
        return ref.ssm_recurrence(
            x, dt, a_head, jnp.repeat(b, share, axis=2),
            jnp.repeat(c, share, axis=2), d,
        )


def through(fn, args, weight):
    """``fn``'s output and the gradient of ``sum(y * weight)`` to every
    argument, as one program (op by op, the kernel pair in interpret mode
    and the chunked form each compile their hundred pieces twice)."""
    names = sorted(args)

    def loss(*values):
        y = fn(**dict(zip(names, values)))
        return (y.astype(F32) * weight).sum(), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(names))), has_aux=True
    ))(*(args[n] for n in names))
    return y, names, grads


# (sequence, chunk): one chunk, a length that is several chunks, one that
# ends inside a chunk (padded), one shorter than a chunk
SHAPES = [(16, 16), (64, 16), (40, 16), (24, 8), (8, 16)]


@pytest.mark.parametrize("impl", ssd_lib.IMPLS)
@pytest.mark.parametrize("s,chunk", SHAPES)
def test_forward_matches_the_recurrence(impl, s, chunk):
    args, _ = inputs(s=s)
    y, top = ssd(chunk, impl, **args)
    np.testing.assert_allclose(y, recurrence(**args), atol=TOL, rtol=TOL)
    assert np.isfinite(float(top)) and float(top) > 0


@pytest.mark.parametrize("impl", ssd_lib.IMPLS)
@pytest.mark.parametrize("s,chunk", [(64, 16), (40, 16), (24, 8)])
def test_gradients_match_the_recurrence(impl, s, chunk):
    args, weight = inputs(s=s)
    _, names, got = through(
        lambda **kw: ssd(chunk, impl, **kw)[0],
        args, weight,
    )
    _, _, want = through(recurrence, args, weight)
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
    y, _ = ssd(16, "kernel", **args)
    tail = {
        k: (v[:, 16:] if v.ndim > 1 else v) for k, v in args.items()
    }
    fresh, _ = ssd(16, "kernel", **tail)
    assert float(jnp.abs(y[:, 16:] - fresh).max()) > 1e-2
    np.testing.assert_allclose(y, recurrence(**args), atol=TOL, rtol=TOL)


def test_heads_of_a_group_share_b_and_c():
    """Head ``h`` reads group ``h // (H / G)``: with each group's rows
    repeated for its heads and G = H, the result is the same."""
    args, _ = inputs(heads=4, groups=2)
    y, _ = ssd(16, "xla", **args)
    own = dict(
        args, b=jnp.repeat(args["b"], 2, axis=2),
        c=jnp.repeat(args["c"], 2, axis=2),
    )
    y_own, _ = ssd(16, "xla", **own)
    np.testing.assert_allclose(y, y_own, atol=TOL, rtol=TOL)


def test_the_largest_state_entry_is_a_reading_not_a_result():
    args, _ = inputs()

    def top(x):
        return ssd_lib.ssd(**dict(args, x=x), chunk=16, impl="kernel")[1]

    assert float(jnp.abs(jax.grad(top)(args["x"])).max()) == 0.0
    tops = [
        float(ssd(16, impl, **args)[1])
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
        y, _ = ssd(16, impl, **args)
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
        ssd(16, "kernel", **args)
    y, _ = ssd(16, "xla", **args)
    np.testing.assert_allclose(y, recurrence(**args), atol=TOL, rtol=TOL)
    mixed = dict(args, b=args["b"].astype(jnp.bfloat16))
    with pytest.raises(ValueError, match="share a dtype"):
        ssd(16, "xla", **mixed)


# One group of many heads, cut into tiles of heads_per_step heads
# (Granite-4.0-H's layout: 128 heads in ONE group): (heads, head_dim,
# groups) -> tiles a group
TILED = [
    (32, 64, 1, 4),        # one group of 32: four tiles of 8 heads
    (32, 64, 2, 2),        # two groups of 16: two tiles each
    (16, 128, 1, 2),       # heads a lane tile wide
]


@pytest.mark.parametrize("heads,head_dim,groups,tiles", TILED)
def test_a_group_of_many_heads_runs_as_tiles(heads, head_dim, groups, tiles):
    """Forward and all six gradients of a group wider than a grid step,
    against the recurrence one token at a time: every tile reads its
    group's B and C, and dB, dC are the sum over the group's tiles."""
    per_step = ssd_lib.heads_per_step(heads, head_dim, groups, 16, 16, F32)
    assert heads // groups // per_step == tiles
    args, weight = inputs(
        batch=2, s=40, heads=heads, head_dim=head_dim, groups=groups
    )
    y, names, got = through(
        lambda **kw: ssd(16, "kernel", **kw)[0],
        args, weight,
    )
    y_want, _, want = through(recurrence, args, weight)
    np.testing.assert_allclose(y, y_want, atol=TOL, rtol=TOL)
    for name, g, w in zip(names, got, want):
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(
            g, w, atol=TOL * scale, rtol=TOL, err_msg=name
        )


@pytest.mark.parametrize("shape,want", [
    # Nemotron-H: a group of 8 heads is one grid step, as before the tiles
    (dict(heads=64, head_dim=64, groups=8, state=128, chunk=128), 8),
    # Granite-4.0-H: ONE group of 128 heads, sixteen tiles of 8
    (dict(heads=128, head_dim=64, groups=1, state=128, chunk=128), 8),
    (dict(heads=128, head_dim=64, groups=1, state=128, chunk=256), 8),
    (dict(heads=4, head_dim=64, groups=2, state=16, chunk=16), 2),
    (dict(heads=8, head_dim=128, groups=1, state=128, chunk=128), 8),
    # not even one lane tile of a 1024-token chunk fits
    (dict(heads=128, head_dim=64, groups=1, state=128, chunk=1024), 0),
    (dict(heads=4, head_dim=48, groups=2, state=16, chunk=16), 0),
])
def test_the_tile_of_heads_follows_from_the_shapes(shape, want):
    assert ssd_lib.heads_per_step(**shape) == want
    assert ssd_lib.kernel_fits(**shape) is (want > 0)
    if want:
        assert ssd_lib.step_vmem_bytes(
            want, shape["head_dim"], shape["state"], shape["chunk"],
            jnp.bfloat16, shape["heads"] // shape["groups"] // want,
        ) <= ssd_lib._VMEM_BUDGET


def test_nemotrons_kernels_are_lowered_as_before_the_tiles():
    """A group that is one grid step: B^T and C^T by the grid's own index
    (the group's rows as x's columns are the tile's, no arithmetic on it),
    dB and dC written whole by the kernel in the operands' dtype (no
    partial sums over tiles for XLA to add), and no ``dy x`` product
    outside the kernel."""
    args, weight = inputs(batch=1, s=32, heads=4, head_dim=64, groups=2)
    x2 = args["x"].reshape(1, 32, 256)
    b2 = jnp.swapaxes(args["b"].reshape(1, 32, 32), 1, 2)   # [B, G N, S]
    dt4 = jnp.zeros((1, 2, 4, 16), F32)
    g = ssd_lib._grid(x2, dt4, b2, 2, 64)
    assert g.grid == (1, 2, 2, 1) and g.per_group == 1
    for reverse, chunk in ((False, 1), (True, 0)):
        sp = ssd_lib._specs(g, reverse)
        assert sp.x.index_map(0, 1, 1, 0) == (0, chunk, 1)
        assert sp.bc.index_map(0, 1, 1, 0) == (0, 1, chunk)

    def loss(x, b):
        y, _ = ssd_lib.ssd(**dict(args, x=x, b=b), chunk=16, impl="kernel")
        return (y * weight[:1, :32]).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
        args["x"], args["b"]
    )
    text = str(jaxpr)
    assert "ssd_fwd" in text and "ssd_bwd" in text
    after = text.split("ssd_bwd")[-1]
    assert " div " not in after and " mul " not in after
    backward = [
        eqn for eqn in _flat(jaxpr.jaxpr)
        if eqn.primitive.name == "pallas_call"
        and "ssd_bwd" in str(eqn.params.get("name", eqn.params))
    ]
    assert len(backward) == 1
    shapes = [(v.aval.shape, v.aval.dtype) for v in backward[0].outvars]
    assert shapes[3] == (b2.shape, b2.dtype) == shapes[4]
    assert shapes[5] == ((2, 1, 1, 128), F32)             # dD on the lanes


def _flat(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _flat(sub)


# A grid step at its real sizes (8 heads of 64 side by side in four lane
# tiles, a state of 128, a chunk of 128, two chunks, batch 2): one group
# that is one tile, and one group of two tiles
@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16])
@pytest.mark.parametrize("heads,tiles", [(8, 1), (16, 2)])
def test_a_grid_step_at_its_real_sizes_matches_the_chunked_form(
    heads, tiles, dtype
):
    assert heads // ssd_lib.heads_per_step(heads, 64, 1, 128, 128, dtype) \
        == tiles
    args, weight = inputs(
        batch=2, s=256, heads=heads, head_dim=64, groups=1, state=128,
        dtype=dtype,
    )
    args["dt"] = args["dt"] * 0.1              # a state that lives 128 tokens
    got, want = {}, {}
    for impl, out in (("kernel", got), ("xla", want)):
        out["y"], names, grads = through(
            lambda **kw: ssd(128, impl, **kw)[0],
            args, weight,
        )
        out.update(zip(names, grads))
    if dtype == F32:
        for name in got:
            scale = float(jnp.abs(want[name]).max())
            np.testing.assert_allclose(
                got[name], want[name], atol=5 * TOL * scale, rtol=5 * TOL,
                err_msg=name,
            )
        return
    # bfloat16: both forms round their products; each stays within
    # bfloat16's rounding of the float32 chunked form on the same inputs
    exact = {k: v.astype(F32) for k, v in args.items()}
    true = {}
    true["y"], names, grads = through(
        lambda **kw: ssd(128, "xla", **kw)[0],
        exact, weight,
    )
    true.update(zip(names, grads))
    for name, t in true.items():
        norm = float(jnp.linalg.norm(t))
        errs = [
            float(jnp.linalg.norm(out[name].astype(F32) - t)) / norm
            for out in (got, want)
        ]
        assert errs[0] < max(2 * errs[1], 1e-5), (name, errs)
        assert errs[0] < 0.02, (name, errs)


@pytest.mark.parametrize("heads,groups", [(4, 2), (32, 1)])
def test_dd_is_the_sum_of_dy_x_for_a_head_whose_d_is_not_one(heads, groups):
    """``y`` holds ``D_h x``: ``dD_h`` is the sum of ``dy x`` over the
    head's tokens and lanes whatever ``D`` is (the backward kernel adds it
    up chunk by chunk, the batch and a head's lanes are summed outside),
    and ``dx`` moves by ``D dy``."""
    args, weight = inputs(batch=2, s=40, heads=heads, groups=groups)
    args["d"] = jnp.linspace(-1.5, 2.5, heads)

    def grads(d):
        def loss(x, d):
            y, _ = ssd_lib.ssd(**dict(args, x=x, d=d), chunk=16,
                               impl="kernel")
            return (y * weight).sum()

        return jax.grad(loss, argnums=(0, 1))(args["x"], d)

    dx, dd = grads(args["d"])
    want = (weight * args["x"]).sum(axis=(0, 1, 3))
    np.testing.assert_allclose(
        dd, want, atol=TOL * float(jnp.abs(want).max()), rtol=TOL
    )
    dx_none, _ = grads(jnp.zeros((heads,)))
    np.testing.assert_allclose(
        dx - dx_none, args["d"][:, None] * weight, atol=5 * TOL, rtol=TOL
    )
