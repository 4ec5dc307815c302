"""The flash kernels under a window (a band of the causal triangle) against
``xla_attention``'s band: forward, dq, dk and dv on both backward paths, at
shapes that keep every block class, and the classes counted by hand; the
lower-edge block's row strips against the masked square they replace, and
a banded block's strips in lockstep against the plain loop."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.attention import xla_attention
from dlrover_tpu.ops import flash_attention as fa


def _qkv(rng, b, s, hq, hkv, d):
    return (
        jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        for h in (hq, hkv, hkv)
    )


def _both(q, k, v, ids, window, block_q, block_kv):
    """(outputs, gradients) of the kernel and of the plain band."""
    def flash(q, k, v):
        return fa.mha(
            q, k, v, segment_ids=ids, window=window, block_q=block_q,
            block_kv=block_kv,
        )

    def plain(q, k, v):
        return xla_attention(q, k, v, segment_ids=ids, window=window)

    out = []
    for fn in (flash, plain):
        o, vjp = jax.vjp(fn, q, k, v)
        out.append((o, *vjp(jnp.cos(jnp.arange(o.size, dtype=o.dtype)
                                    .reshape(o.shape)))))
    return out


# (id, seq, window, block_q, block_kv, q heads, kv heads, ids): W below,
# at and over the block, no multiple of it, three blocks wide, blocks of
# two sizes, GQA groups of 8, packed ids, a padded length; and at blocks
# that hold strips (256: two of 128 rows) W of one block, of two and of no
# whole number of them, with and without ids
BANDS = [
    ("below_block", 256, 24, 64, 64, 2, 2, False),
    ("one_block", 256, 64, 64, 64, 2, 2, False),
    ("no_multiple", 320, 100, 64, 64, 2, 1, False),
    ("three_blocks", 384, 192, 64, 64, 2, 2, False),
    ("tall_q", 256, 70, 128, 64, 2, 2, False),
    ("wide_kv", 256, 70, 64, 128, 2, 2, False),
    ("groups_of_8", 256, 64, 64, 64, 8, 1, False),
    ("ids", 256, 80, 64, 64, 2, 2, True),
    ("padded", 200, 48, 64, 64, 2, 2, False),
    ("strips", 512, 256, 256, 256, 1, 1, False),
    ("one_key", 128, 1, 64, 64, 1, 1, False),
    ("whole", 128, 128, 64, 64, 1, 1, False),
    ("lower_strips", 768, 256, 256, 256, 2, 1, False),
    ("lower_strips_ids", 768, 256, 256, 256, 1, 1, True),
    ("lower_strips_two_blocks", 1024, 512, 256, 256, 1, 1, False),
    ("lower_strips_two_blocks_ids", 1024, 512, 256, 256, 1, 1, True),
    ("lower_square", 768, 300, 256, 256, 1, 1, False),
    ("lower_square_ids", 768, 300, 256, 256, 1, 1, True),
]


@pytest.mark.parametrize("path", ["fused", "split"])
@pytest.mark.parametrize(
    "seq,window,block_q,block_kv,hq,hkv,with_ids",
    [case[1:] for case in BANDS], ids=[case[0] for case in BANDS],
)
def test_band_matches_xla(
    rng, monkeypatch, path, seq, window, block_q, block_kv, hq, hkv,
    with_ids,
):
    if path == "split":
        monkeypatch.setattr(fa, "_VMEM_CAP", 1 << 10)
    assert fa.backward_path(
        seq, seq, 64, 64, block_q, block_kv, jnp.float32
    ) == path
    q, k, v = _qkv(rng, 1, seq, hq, hkv, 64)
    ids = None
    if with_ids:
        ids = jnp.asarray((np.arange(seq) // 90)[None, :], jnp.int32)
    got, want = _both(q, k, v, ids, window, block_q, block_kv)
    for g, w, name in zip(got, want, ("o", "dq", "dk", "dv")):
        np.testing.assert_allclose(
            g, w, atol=5e-4, rtol=5e-4, err_msg=name
        )


def test_a_window_of_one_key_more_or_less_is_another_answer(rng):
    """The comparison above is sharp: W + 1 and W - 1 both differ."""
    q, k, v = _qkv(rng, 1, 256, 2, 2, 64)
    kernel = lambda w: fa.mha(q, k, v, window=w, block_q=64, block_kv=64)
    plain = xla_attention(q, k, v, window=100)
    assert float(jnp.abs(kernel(100) - plain).max()) < 1e-4
    for off in (99, 101):
        assert float(jnp.abs(kernel(off) - plain).max()) > 1e-2
    # a window over the whole sequence is the causal mask
    np.testing.assert_allclose(
        fa.mha(q, k, v, window=256, block_q=64, block_kv=64),
        fa.mha(q, k, v, block_q=64, block_kv=64), atol=1e-6,
    )


def _by_hand(seq, block_q, block_kv, window):
    """Each block's class from its own pairs (no [seq, seq] array)."""
    counts = dict(dead=0, interior=0, diagonal=0, lower=0, both=0)
    live = np.zeros((seq // block_q, seq // block_kv), bool)
    for iq, q0 in enumerate(range(0, seq, block_q)):
        i = np.arange(q0, q0 + block_q)[:, None]
        for ik, k0 in enumerate(range(0, seq, block_kv)):
            if k0 > q0 + block_q or q0 - k0 > window + block_kv:
                counts["dead"] += 1     # far from the band: skip the work
                continue
            j = np.arange(k0, k0 + block_kv)[None, :]
            above, below = i < j, i - j >= window
            seen = ~above & ~below
            live[iq, ik] = seen.any()
            counts[
                "dead" if not seen.any() else
                "interior" if seen.all() else
                "both" if above.any() and below.any() else
                "diagonal" if above.any() else "lower"
            ] += 1
    return counts, live.sum(axis=1).max(), live.sum(axis=0).max()


@pytest.mark.parametrize("seq,window,block_q,block_kv,expected", [
    # Mellum2's: 32 diagonal and 31 lower (strips both), no interior, two
    # steps
    (32768, 1024, 1024, 1024, (961, 0, 32, 256, 31, 0, 2, 2, 256)),
    # Command A+'s: a window of four blocks
    (16384, 4096, 1024, 1024, (186, 42, 16, 256, 12, 0, 5, 5, 256)),
    (32768, 1024, 512, 512, (3907, 63, 64, 256, 62, 0, 3, 3, 256)),
    # no whole number of blocks: the lower edge keeps the masked square
    (4096, 1000, 512, 512, (43, 0, 8, 256, 13, 0, 3, 3, 0)),
    (1024, 100, 256, 256, (9, 0, 0, 0, 3, 4, 2, 2, 0)),
    (1024, 300, 128, 256, (16, 0, 8, 0, 8, 0, 3, 5, 0)),
])
def test_band_classes(seq, window, block_q, block_kv, expected):
    got = fa.block_classes(seq, seq, block_q, block_kv, True, window)
    assert isinstance(got, fa.BandClasses)
    counts, kv_steps, q_steps = _by_hand(seq, block_q, block_kv, window)
    assert {k: getattr(got, k) for k in counts} == counts
    assert (got.kv_steps, got.q_steps) == (kv_steps, q_steps)
    assert tuple(got) == expected
    nq, nk = seq // block_q, seq // block_kv
    for iq in range(0, nq, max(1, nq // 8)):
        for ik in range(nk):
            live, diagonal, lower = fa._block_edges(
                iq, ik, block_q, block_kv, window
            )
            i = np.arange(iq * block_q, (iq + 1) * block_q)[:, None]
            j = np.arange(ik * block_kv, (ik + 1) * block_kv)[None, :]
            seen = (i >= j) & (i - j < window)
            assert (bool(live), bool(live and not (diagonal or lower))) == (
                bool(seen.any()), bool(seen.all())
            )


@pytest.mark.parametrize("block,strip", [(1024, 256), (512, 256), (256, 128)])
@pytest.mark.parametrize("edge", ["diagonal", "lower"])
def test_an_edge_blocks_strips_hold_every_live_pair_once(block, strip, edge):
    """Over a block the diagonal crosses (row i sees j <= i), and over one
    the band's lower edge crosses at ``window == block`` (j > i), every
    live pair lies in exactly one tile, no 128-wide sub-tile of a tile is
    wholly dead, and the strips are ``n (n + 1) / 2`` of the block's ``n
    * n`` sub-tiles of ``strip`` rows: 10 of 16 at blocks of 1,024."""
    assert fa._strip_rows(block, block) == strip
    lower = edge == "lower"
    tiles = fa._tiles(block, block, strip, lower=lower)
    i, j = np.arange(block)[:, None], np.arange(block)[None, :]
    live = j > i if lower else j <= i
    worked = np.zeros((block, block), int)
    for rows, cols in tiles:
        worked[rows, cols] += 1
        for c in range(cols.start, cols.stop, 128):
            assert live[rows, c:c + 128].any()
    assert worked.max() == 1 and (worked[live] == 1).all()
    n = block // strip
    assert worked.sum() == n * (n + 1) // 2 * strip * strip
    if block == 1024:
        assert worked.sum() * 16 == 10 * block * block
    # the whole block where no strip is asked for, on either edge
    assert fa._tiles(block, block, 0, lower=lower) == [
        (slice(0, block), slice(0, block))
    ]


def _square_at_the_lower_edge(monkeypatch):
    """The kernels as they were: the builders take the same
    ``BandClasses`` with ``lower_strip`` 0."""
    classes = fa._band_classes
    monkeypatch.setattr(
        fa, "_band_classes",
        lambda *sizes: classes(*sizes)._replace(lower_strip=0),
    )


@pytest.mark.parametrize("path", ["fused", "split"])
@pytest.mark.parametrize("seq,window,with_ids", [
    (768, 256, False), (768, 256, True), (1024, 512, False),
])
def test_lower_strips_sum_what_the_masked_square_summed(
    rng, monkeypatch, path, seq, window, with_ids
):
    """A row of a lower-edge strip sums the terms it summed in the masked
    square (whose masked entries were exact zeros) in a reduction of
    another length: the outputs and the three cotangents agree to float32
    reassociation, 1e-5 absolute on values of order one (sums of at most
    512 float32 terms; a wrong or a missing column moves them by 1e-2 and
    more, ``test_a_window_of_one_key_more_or_less_is_another_answer``),
    and not bit for bit."""
    if path == "split":
        monkeypatch.setattr(fa, "_VMEM_CAP", 1 << 10)
    assert fa.backward_path(seq, seq, 64, 64, 256, 256, jnp.float32) == path
    assert fa.block_classes(seq, seq, 256, 256, True, window).lower_strip == 128
    q, k, v = _qkv(rng, 1, seq, 2, 1, 64)
    ids = None
    if with_ids:
        ids = jnp.asarray((np.arange(seq) // 90)[None, :], jnp.int32)
    strips, _ = _both(q, k, v, ids, window, 256, 256)
    _square_at_the_lower_edge(monkeypatch)
    assert fa.block_classes(seq, seq, 256, 256, True, window).lower_strip == 0
    square, _ = _both(q, k, v, ids, window, 256, 256)
    for got, want, name in zip(strips, square, ("o", "dq", "dk", "dv")):
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0, err_msg=name)


@pytest.mark.parametrize("seq,window,with_ids", [
    (768, 256, False), (768, 256, True), (1024, 512, False),
    (768, 300, False),
])
def test_strips_in_lockstep_give_the_loops_numbers(
    rng, monkeypatch, seq, window, with_ids
):
    """A banded block's strips advanced in turn are the strips one after
    another in another PROGRAM order: each strip's arithmetic keeps its
    order and the strips' adds to dk / dv theirs, so the forward and the
    one-pass backward give the loop's outputs bit for bit."""
    q, k, v = _qkv(rng, 1, seq, 2, 1, 64)
    ids = None
    if with_ids:
        ids = jnp.asarray((np.arange(seq) // 90)[None, :], jnp.int32)
    assert fa._advance(window) is fa._in_lockstep
    assert fa._advance(None) is fa._one_by_one
    in_turn, _ = _both(q, k, v, ids, window, 256, 256)
    monkeypatch.setattr(fa, "_in_lockstep", fa._one_by_one)
    looped, _ = _both(q, k, v, ids, window, 256, 256)
    for got, want, name in zip(in_turn, looped, ("o", "dq", "dk", "dv")):
        np.testing.assert_array_equal(got, want, err_msg=name)


def test_a_steps_strips_take_their_stages_in_turn():
    """What the lockstep is for, read in the kernel bodies' program order:
    ``_in_lockstep`` runs a stage of every tile it is given before any
    tile's next, a banded kernel hands it its block's strips, and a call
    without a window runs each tile to its end."""
    said = []

    def tile(name):
        for stage in range(2):
            said.append((name, stage))
            yield

    fa._advance(64)(tile(n) for n in "ab")
    assert said == [("a", 0), ("b", 0), ("a", 1), ("b", 1)]
    del said[:]
    fa._advance(None)(tile(n) for n in "ab")
    assert said == [("a", 0), ("a", 1), ("b", 0), ("b", 1)]
    # in a banded forward's body no two products in a row are one strip's
    # (q k^T of every strip, then p v of every strip); in a causal one a
    # strip's two stand together
    q = jnp.zeros((1, 768, 1, 64), jnp.float32)

    def first_q_products(window):
        fwd = lambda q: fa.mha(
            q, q, q, window=window, block_q=256, block_kv=256
        )
        return _dots_of_branches(jax.make_jaxpr(fwd)(q).jaxpr)

    assert ["qk", "qk", "pv", "pv"] in first_q_products(256)
    assert ["qk", "pv", "qk", "pv"] in first_q_products(None)
    assert ["qk", "qk", "pv", "pv"] not in first_q_products(None)


def _dots_of_branches(program):
    """For each branch of a forward's program that holds products (the
    kernel body's ``pl.when`` of a class): ``qk`` (contracts the heads'
    width, both operands' last axis) or ``pv`` of each, in program order."""
    found = []

    def walk(jaxpr):
        kinds = [
            "qk" if e.params["dimension_numbers"][0] == ((1,), (1,))
            else "pv"
            for e in jaxpr.eqns if e.primitive.name == "dot_general"
        ]
        if kinds:
            found.append(kinds)
        for e in jaxpr.eqns:
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)

    walk(program)
    return found


@pytest.mark.parametrize("seq,window,block_q,block_kv", [
    (2048, 256, 256, 256), (2048, 300, 128, 256), (2048, 300, 256, 128),
    (1024, 100, 256, 256),
])
def test_a_dead_step_on_either_side_names_a_live_block(
    seq, window, block_q, block_kv
):
    """Every step of the banded grids names a LIVE block of its row (kv
    inner) or column (q inner), each live block exactly once."""
    nq, nk = seq // block_q, seq // block_kv
    classes = fa.block_classes(seq, seq, block_q, block_kv, True, window)
    live = np.zeros((nq, nk), bool)
    for iq in range(nq):
        for ik in range(nk):
            live[iq, ik] = fa._block_edges(
                iq, ik, block_q, block_kv, window
            )[0]
    for iq in range(nq):
        named = [
            int(fa._last_live_kv(
                iq, fa._kv_of_step(iq, t, block_q, block_kv, window),
                block_q, block_kv, True, window,
            )) for t in range(classes.kv_steps)
        ]
        assert all(live[iq, ik] for ik in named)
        assert set(named) == set(np.flatnonzero(live[iq]))
        for ik in range(nk):          # a grid over the whole sequence too
            kv = int(fa._last_live_kv(iq, ik, block_q, block_kv, True, window))
            assert live[iq, kv] and (kv == ik) == bool(live[iq, ik])
    for ik in range(nk):
        named = [
            int(fa._first_live_q(
                fa._q_of_step(ik, t, nq, block_q, block_kv, window), ik, nq,
                block_q, block_kv, True, window,
            )) for t in range(classes.q_steps)
        ]
        assert all(live[iq, ik] for iq in named)
        assert set(named) == set(np.flatnonzero(live[:, ik]))


def test_without_a_window_the_text_is_what_it_was():
    """``window=None`` adds no argument, no mask and no index arithmetic:
    the jaxpr of a call and of its gradient is the one a call that never
    names ``window`` gives, and a banded call's differs."""
    q = jnp.zeros((1, 512, 2, 64), jnp.float32)

    def text(**kw):
        def loss(q, k, v):
            return jnp.sum(fa.mha(q, k, v, block_q=128, block_kv=128, **kw))

        return re.sub(
            r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(
                jax.grad(loss, argnums=(0, 1, 2))
            )(q, q, q))
        )

    assert text(window=None) == text()
    assert text(window=128) != text()
    # the band's grids are the band's: 2 of 4 kv steps a q block
    assert "grid=(1, 2, 4, 2)" in text(window=128)
    assert "grid=(1, 2, 4, 4)" in text()


def test_a_window_refuses_what_it_cannot_mean(rng):
    q, k, v = _qkv(rng, 1, 128, 1, 1, 64)
    with pytest.raises(ValueError, match="window needs causal"):
        fa.mha(q, k, v, causal=False, window=16)
    with pytest.raises(ValueError, match="window needs causal"):
        fa.mha(q[:, :64], k, v, window=16)
    with pytest.raises(ValueError, match="window needs causal"):
        fa.mha(q, k, v, window=0)
    with pytest.raises(ValueError, match="a window needs causal"):
        xla_attention(q, k, v, causal=False, window=16)
