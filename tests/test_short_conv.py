"""The short convolution's kernels (``ops/short_conv.py``, interpret mode)
against the written-out XLA form they replace, at the two published channel
layouts cut to three token tiles, and the choice between the two."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import linear_attention as la
from dlrover_tpu.models import mamba2
from dlrover_tpu.ops import short_conv

F32 = jnp.float32

# name: x's shape, taps' shape, a bias, the first column, the outputs'
# widths, the outputs' L2 norms
LAYOUTS = {
    # Nemotron's: 6,144 channels from column 4,096 of in_proj's 10,304, a
    # bias, x | B | C written apart
    "nemotron": (
        (2, 384, 10304), (4, 6144), True, 4096, (4096, 1024, 1024), None,
    ),
    # Olmo-Hybrid's: the first 384 of each head's 576 columns, no bias; a
    # head is a row of the kernels' input with its own taps; q | k | v
    # written apart, q and k L2-normalised over their 96 columns.  Three
    # heads of the published 30: the plan asks nothing of their number (a
    # head is a grid step), and three are a first, a middle and a last one,
    # each with taps of its own (30 were ten times the interpreter's work
    # for the same steps; the real 30 compile in ``test_chip_compile.py``)
    "hybrid": (
        (2, 384, 3, 576), (4, 3, 384), False, 0, (96, 96, 192),
        (96 ** -0.5, 1.0, None),
    ),
    # the hybrid's without its epilogue: one output, a head's 384 columns
    # one channel tile
    "heads": ((2, 384, 6, 576), (4, 6, 384), False, 0, None, None),
}


@pytest.fixture(autouse=True)
def few_tokens_a_tile(monkeypatch):
    """Three token tiles of 128 a sequence (the middle one has a tile on
    either side); channel tiles of 512 (twelve of Nemotron's) and 384 (a
    head of the hybrid's)."""
    monkeypatch.setattr(short_conv, "_TILE_TOKENS", 128)


def operands(layout, seed=0):
    x_shape, taps_shape, has_bias, offset, splits, scales = LAYOUTS[layout]
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], x_shape, F32)
    taps = jax.random.uniform(keys[1], taps_shape, F32, -0.5, 0.5)
    bias = (
        jax.random.uniform(keys[2], taps_shape[1:], F32, -0.5, 0.5)
        if has_bias else None
    )
    return (x, taps, bias), dict(
        offset=offset, splits=splits, l2_scales=scales
    )


def xla_form(x, taps, bias=None, *, offset=0, splits=None, l2_scales=None):
    """What both callers computed before the kernels: the slice, K shifted
    multiply-adds, the bias, the SiLU, the slices of the result and the
    hybrid's L2 norms of two of them."""
    y = la._conv_xla(x[..., offset: offset + taps.shape[-1]], taps)
    y = jax.nn.silu(y if bias is None else y + bias)
    if not splits:
        return y
    edges = np.cumsum((0,) + tuple(splits))
    return tuple(
        y[..., lo: hi] if scale is None
        else la.l2_normalise(y[..., lo: hi]) * scale
        for lo, hi, scale in zip(
            edges, edges[1:], l2_scales or (None,) * len(splits)
        )
    )


def joined(y):
    return jnp.concatenate(y, axis=-1) if isinstance(y, tuple) else y


def tile_rows(layout):
    (x, taps, _), how = operands(layout)
    return short_conv.plan(x.shape, taps.shape, **how).ts


@functools.lru_cache(maxsize=None)
def grads(fn, layout, weights_seed=7):
    """Gradients of a loss with its own random cotangent a value."""
    (x, taps, bias), how = operands(layout)
    w = jax.random.normal(
        jax.random.PRNGKey(weights_seed), x.shape[:-1] + taps.shape[-1:]
    )

    def loss(x, taps, bias):
        return (joined(fn(x, taps, bias, **how)) * w).sum()

    return jax.grad(loss, argnums=(0, 1, 2) if bias is not None else (0, 1))(
        x, taps, bias
    )


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_layout_takes_the_kernel(layout):
    (x, taps, _), how = operands(layout)
    assert la.short_conv_path(x.shape, taps.shape, **how) == "kernel"
    tiled = short_conv.plan(x.shape, taps.shape, **how)
    assert x.shape[1] // tiled.ts == 3      # three token tiles a sequence
    assert sum(hi - lo for lo, hi in tiled.ranges) * tiled.wc == (
        taps.shape[-1]
    )
    assert tiled.heads == {"hybrid": 3, "heads": 6}.get(layout, 1)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_forward_matches_the_xla_form(layout):
    args, how = operands(layout)
    got = la.causal_depthwise_conv(*args, **how)
    want = xla_form(*args, **how)
    assert isinstance(got, tuple) == isinstance(want, tuple)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=2e-6)


@pytest.mark.parametrize("layout,leaf", [
    ("nemotron", "dx"), ("nemotron", "d_taps"), ("nemotron", "d_bias"),
    ("hybrid", "dx"), ("hybrid", "d_taps"),
])
def test_gradient_matches_the_xla_form(layout, leaf):
    n = ("dx", "d_taps", "d_bias").index(leaf)
    got = grads(la.causal_depthwise_conv, layout)[n]
    want = grads(xla_form, layout)[n]
    assert got.shape == want.shape and got.dtype == want.dtype
    # a sum over the (token, batch) products of float32 in another order
    np.testing.assert_allclose(
        got, want, rtol=1e-5, atol=1e-5 * float(jnp.abs(want).max())
    )
    if leaf == "dx":
        # nothing flows into the columns the convolution does not read
        (x, taps, _), how = operands(layout)
        lo = how["offset"]
        outside = np.ones(x.shape[-1], bool)
        outside[lo: lo + taps.shape[-1]] = False
        assert not np.asarray(got)[..., outside].any()
        assert np.asarray(got)[..., ~outside].all()


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_tiles_first_rows_read_the_tile_before_and_a_sequences_zeros(
    layout,
):
    (x, taps, bias), how = operands(layout)
    ts, lo = tile_rows(layout), how["offset"]
    conv = jax.jit(lambda x: joined(
        la.causal_depthwise_conv(x, taps, bias, **how)
    ))
    y = conv(x)
    # the sequence's first row sees its own token under the last tap alone:
    # what a sequence of that one token gives
    alone = joined(xla_form(x[:, :1], taps, bias, **how))
    np.testing.assert_allclose(y[:, :1], alone, rtol=1e-5, atol=2e-6)
    # a change to the LAST row of tile 0 reaches the first three of tile 1
    # (as the XLA form says by how much) and no row beyond them
    moved = conv(x.at[:, ts - 1].add(1.0))
    changed = np.asarray(jnp.abs(moved - y).max(
        axis=tuple(a for a in range(y.ndim) if a != 1)
    )) > 0
    assert changed.tolist() == [
        ts - 1 <= t <= ts + 2 for t in range(x.shape[1])
    ]
    np.testing.assert_allclose(
        moved, joined(xla_form(x.at[:, ts - 1].add(1.0), taps, bias, **how)),
        rtol=1e-5, atol=2e-6,
    )


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_two_batch_rows_do_not_see_each_other(layout):
    (x, taps, bias), how = operands(layout)
    other = x.at[0].set(jax.random.normal(jax.random.PRNGKey(5), x.shape[1:]))

    def both(x):
        w = jnp.arange(x.shape[1], dtype=F32).reshape(
            (1, -1) + (1,) * (x.ndim - 2)
        )
        y, vjp = jax.vjp(lambda x: joined(
            la.causal_depthwise_conv(x, taps, bias, **how)
        ), x)
        return y, vjp(jnp.cos(y + w))[0]

    (y, dx), (y_other, dx_other) = both(x), both(other)
    np.testing.assert_array_equal(y[1], y_other[1])
    np.testing.assert_array_equal(dx[1], dx_other[1])
    assert np.abs(np.asarray(y[0] - y_other[0])).max() > 0.1


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_dx_at_a_tiles_last_rows_takes_the_next_tiles_du(layout):
    """A cotangent on tile 1's FIRST row alone: dx is that row's and the
    three before it, which lie in tile 0."""
    (x, taps, bias), how = operands(layout)
    ts = tile_rows(layout)

    def dx_of(fn):
        y, vjp = jax.vjp(lambda x: joined(fn(x, taps, bias, **how)), x)
        return vjp(jnp.zeros_like(y).at[:, ts].set(1.0))[0]

    got, want = dx_of(la.causal_depthwise_conv), dx_of(xla_form)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-6)
    rows = np.asarray(jnp.abs(got).max(
        axis=tuple(a for a in range(got.ndim) if a != 1)
    )) > 0
    assert rows.tolist() == [ts - 3 <= t <= ts for t in range(x.shape[1])]


@pytest.mark.parametrize("x_shape,taps_shape,offset,splits,why", [
    ((2, 64, 4, 72), (4, 4, 48), 0, None,
     "the hybrid's tiny: 4 x 48 = 192 channels of 64 tokens"),
    ((2, 64, 580), (4, 320), 256, (256, 32, 32),
     "Nemotron's tiny: 320 channels of 64 tokens"),
    ((2, 383, 10304), (4, 6144), 4096, (4096, 1024, 1024), "383 tokens"),
    ((2, 384, 10304), (4, 6144), 4001, None, "an offset inside a row tile"),
    ((2, 384, 200), (4, 200), 0, None, "200 channels: 12.5 row tiles"),
])
def test_a_shape_the_kernel_cannot_tile_takes_the_xla_form(
    monkeypatch, x_shape, taps_shape, offset, splits, why
):
    assert la.short_conv_path(x_shape, taps_shape, offset, splits) == "xla"
    monkeypatch.setattr(
        short_conv, "short_conv",
        lambda *a, **k: pytest.fail(f"the kernel ran on {why}"),
    )
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(keys[0], x_shape, jnp.bfloat16)
    taps = jax.random.normal(keys[1], taps_shape, jnp.bfloat16)
    bias = jax.random.normal(keys[2], taps_shape[1:], jnp.bfloat16)
    got = la.causal_depthwise_conv(
        x, taps, bias, offset=offset, splits=splits
    )
    # what it returned before it had an epilogue: the callers' own lines
    old = jax.nn.silu(
        la._conv_xla(x[..., offset: offset + taps_shape[-1]], taps) + bias
    )
    np.testing.assert_array_equal(
        np.asarray(joined(got), np.float32), np.asarray(old, np.float32)
    )
    if splits:
        assert [g.shape[-1] for g in got] == list(splits)


def test_the_mixers_ask_for_their_own_layout():
    # the published widths at the cells' 8,192 tokens
    assert mamba2.conv_path(8192, 64, 64, 128, 8, 4) == "kernel"
    assert la.conv_path(8192, 30, 96, 192, 4) == "kernel"
    # the tiny presets': 64 tokens are half a lane tile
    assert mamba2.conv_path(64, 4, 64, 16, 2, 4) == "xla"
    assert la.conv_path(64, 4, 12, 24, 4) == "xla"
    assert mamba2.conv_path(128, 4, 64, 16, 2, 4) == "kernel"


# -- the SiLU form is what it was at fe404a6 ----------------------------------

# the four configurations that share the SiLU form, at their cells' shapes:
# x, taps, the first column, the outputs' widths and L2 norms, and the
# tiling ``plan`` gave them at fe404a6 (PR 51)
SIBLINGS = {
    "nemotron-3-nano-30b-a3b": (
        (2, 8192, 10304), (4, 6144), 4096, (4096, 1024, 1024), None,
        (2048, 512, 8, 1, ((0, 8), (8, 10), (10, 12)), (None,) * 3),
    ),
    "granite-4.0-h-small": (
        (2, 8192, 16768), (4, 8448), 8192, (8192, 128, 128), None,
        (2048, 128, 64, 1, ((0, 64), (64, 65), (65, 66)), (None,) * 3),
    ),
    "olmo-hybrid-7b": (
        (2, 8192, 30, 576), (4, 30, 384), 0, (96, 96, 192),
        (96 ** -0.5, 1.0, None),
        (2048, 96, 0, 30, ((0, 1), (1, 2), (2, 4)), (96 ** -0.5, 1.0, None)),
    ),
    "ling-3.0-flash-vl": (
        (2, 8192, 32, 384), (4, 32, 384), 0, (128, 128, 128),
        (128 ** -0.5, 1.0, None),
        (2048, 128, 0, 32, ((0, 1), (1, 2), (2, 3)),
         (128 ** -0.5, 1.0, None)),
    ),
}
# sha256 of ``ops/short_conv.py`` as it stood at fe404a6
SILU_FORM_AT_FE404A6 = (
    "253db8c5e6eb4b3af23bea13e842b4ea62c85230d551d8d24480f93831c3e138"
)
GATED_FORM_STARTS = "\n\n# -- the gated form"


@pytest.mark.parametrize("sibling", sorted(SIBLINGS))
def test_the_silu_form_reads_bit_for_bit_what_it_read_at_fe404a6(
    monkeypatch, sibling
):
    """LFM2's gated form was ADDED below the SiLU form (PR 52): every byte
    of the module up to it is fe404a6's, so the same kernels run under the
    same tiling and each sibling's ``short_conv_ms`` / ``ssm_conv_ms`` has
    nothing to move by.  A PR that edits the SiLU form shows its outputs
    bit for bit equal on these four shapes and records the new digest."""
    import hashlib

    with open(short_conv.__file__) as f:
        text = f.read()
    silu_form = text[: text.index(GATED_FORM_STARTS)]
    assert hashlib.sha256(silu_form.encode()).hexdigest() == (
        SILU_FORM_AT_FE404A6
    )
    # nothing of the gated form is named above it, and nothing there
    # rebinds a name of the SiLU form
    assert "gated_conv" not in silu_form and "plan_gated" not in silu_form
    gated_form = text[text.index(GATED_FORM_STARTS):]
    for name in ("_fwd_kernel", "_bwd_kernel", "_forward", "_backward",
                 "short_conv", "plan", "_specs", "_shifted", "_u_of",
                 "_lane_masks", "_columns", "_for_each", "_TILE_TOKENS",
                 "_TILE_CHANNELS", "_UNROLL", "STRIP"):
        assert f"\ndef {name}(" not in gated_form
        assert f"\n{name} =" not in gated_form
    # the published tile sizes (the fixture above cuts the tokens' for the
    # interpreter's sake)
    monkeypatch.setattr(short_conv, "_TILE_TOKENS", 2048)
    x, taps, offset, splits, scales, want = SIBLINGS[sibling]
    assert tuple(short_conv.plan(x, taps, offset, splits, scales)) == want


# -- the gated form: C * conv(B * z) -------------------------------------------


def gated_operands(k=3, d=48, tokens=384, dtype=F32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (2, tokens, 3 * d), F32).astype(dtype)
    taps = jax.random.uniform(keys[1], (k, d), F32, -0.5, 0.5).astype(dtype)
    w = jax.random.normal(keys[2], (2, tokens, d), F32)
    return x, taps, w


@pytest.mark.parametrize("k", [3, 4])
def test_the_gated_form_and_its_cotangents_match_the_xla_form(k):
    """Forward, all three cotangents (in ONE ``[B, S, 3d]`` array) and
    ``d_taps``, three token tiles a sequence: a tile's first rows read the
    tile before, a sequence's first rows zeros."""
    from dlrover_tpu.models import gated_conv

    x, taps, w = gated_operands(k)
    tiled = short_conv.plan_gated(x.shape, taps.shape)
    assert tiled == (128, 48) and x.shape[1] // tiled.ts == 3
    assert gated_conv.core_path(x.shape, taps.shape) == "kernel"
    got = gated_conv.gated_conv(x, taps)
    want = gated_conv.gated_conv_xla(x, taps)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-6)

    def grads(fn):
        return jax.grad(
            lambda x, taps: (fn(x, taps) * w).sum(), argnums=(0, 1)
        )(x, taps)

    (dx, d_taps), (dx_want, d_taps_want) = (
        grads(gated_conv.gated_conv), grads(gated_conv.gated_conv_xla)
    )
    assert dx.shape == x.shape and d_taps.shape == taps.shape
    np.testing.assert_allclose(dx, dx_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(d_taps, d_taps_want, rtol=1e-4, atol=1e-4)
    # t < K - 1 of the second batch row reads zeros, not the first row's end
    d = taps.shape[1]
    by_hand = x[1, 0, d: 2 * d] * taps[-1] * x[1, 0, :d] * x[1, 0, 2 * d:]
    np.testing.assert_allclose(got[1, 0], by_hand, rtol=1e-5, atol=1e-6)
    # the tile edge: token 128 reads tokens 126 and 127 of the tile before
    bz = x[0, 126:129, :d] * x[0, 126:129, 2 * d:]
    edge = x[0, 128, d: 2 * d] * (taps[-3:] * bz).sum(0)
    if k == 3:
        np.testing.assert_allclose(got[0, 128], edge, rtol=1e-5, atol=1e-6)


def test_the_gated_form_rounds_once_in_bfloat16():
    from dlrover_tpu.models import gated_conv

    x, taps, _ = gated_operands(dtype=jnp.bfloat16)
    got = gated_conv.gated_conv(x, taps)
    want = gated_conv.gated_conv_xla(x, taps)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("x_shape,taps_shape,why", [
    ((2, 100, 96), (3, 32), "tokens no whole lane tiles"),
    ((2, 256, 120), (3, 40), "channels no whole row tiles"),
    ((2, 256, 100), (3, 32), "not three ranges of the taps' width"),
    ((2, 256, 4, 96), (3, 4, 32), "heads"),
])
def test_a_shape_the_gated_kernel_cannot_tile_takes_the_xla_form(
    x_shape, taps_shape, why
):
    from dlrover_tpu.models import gated_conv

    assert short_conv.plan_gated(x_shape, taps_shape) is None, why
    if len(x_shape) == 3 and x_shape[2] == 3 * taps_shape[1]:
        assert gated_conv.core_path(x_shape, taps_shape) == "xla"
    # the cell's shape takes the kernel
    assert gated_conv.core_path((4, 8192, 6144), (3, 2048)) == "kernel"
