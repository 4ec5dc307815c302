"""The flash kernels under a window (a band of the causal triangle) against
``xla_attention``'s band: forward, dq, dk and dv on both backward paths, at
shapes that keep every block class, and the classes counted by hand."""

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
# two sizes, GQA groups of 8, packed ids, a padded length
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
    # the cell's: 32 diagonal (strips), 31 lower, no interior, two steps
    (32768, 1024, 1024, 1024, (961, 0, 32, 256, 31, 0, 2, 2)),
    (32768, 1024, 512, 512, (3907, 63, 64, 256, 62, 0, 3, 3)),
    (4096, 1000, 512, 512, (43, 0, 8, 256, 13, 0, 3, 3)),
    (1024, 100, 256, 256, (9, 0, 0, 0, 3, 4, 2, 2)),
    (1024, 300, 128, 256, (16, 0, 8, 0, 8, 0, 3, 5)),
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
