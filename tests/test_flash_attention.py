"""Flash attention kernel vs XLA reference, fwd + grads, masks, GQA."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.attention import xla_attention
from dlrover_tpu.ops import flash_attention as fa


def _rand_qkv(rng, b, s, hq, hkv, d, dtype=jnp.float32):
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_fwd_matches_xla(rng, causal):
    q, k, v = _rand_qkv(rng, 2, 256, 4, 4, 64)
    out = fa.mha(q, k, v, causal=causal, block_q=128, block_kv=128)
    ref = xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_fwd_gqa(rng):
    q, k, v = _rand_qkv(rng, 1, 256, 8, 2, 64)
    out = fa.mha(q, k, v, causal=True, block_q=128, block_kv=128)
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_fwd_segment_mask(rng):
    b, s = 2, 256
    q, k, v = _rand_qkv(rng, b, s, 2, 2, 64)
    seg = jnp.asarray(
        rng.integers(0, 3, size=(b, s)).cumsum(axis=1) // 40, jnp.int32
    )
    out = fa.mha(
        q, k, v, causal=True, segment_ids=seg, block_q=128, block_kv=128
    )
    ref = xla_attention(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_fwd_unpadded_seq(rng):
    """Sequence not a multiple of the block: wrapper pads + masks."""
    q, k, v = _rand_qkv(rng, 1, 200, 2, 2, 64)
    out = fa.mha(q, k, v, causal=True, block_q=128, block_kv=128)
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
def test_grads_match_xla(rng, hq, hkv):
    q, k, v = _rand_qkv(rng, 1, 256, hq, hkv, 64)

    def loss_flash(q, k, v):
        return jnp.sum(
            fa.mha(q, k, v, causal=True, block_q=128, block_kv=128) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True) ** 2)

    g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            gf, gr, atol=5e-4, rtol=5e-4, err_msg=f"d{name}"
        )


def test_grads_with_segments(rng):
    b, s = 1, 256
    q, k, v = _rand_qkv(rng, b, s, 2, 2, 64)
    seg = jnp.asarray((np.arange(s) // 64)[None, :].repeat(b, 0), jnp.int32)

    def loss_flash(q):
        return jnp.sum(
            fa.mha(q, k, v, causal=True, segment_ids=seg,
                   block_q=128, block_kv=128)
        )

    def loss_ref(q):
        return jnp.sum(xla_attention(q, k, v, causal=True, segment_ids=seg))

    np.testing.assert_allclose(
        jax.jit(jax.grad(loss_flash))(q), jax.jit(jax.grad(loss_ref))(q),
        atol=5e-4, rtol=5e-4,
    )


def test_grads_fused_with_segments(rng):
    b, s = 1, 256
    q, k, v = _rand_qkv(rng, b, s, 2, 2, 64)
    seg = jnp.asarray((np.arange(s) // 64)[None, :].repeat(b, 0), jnp.int32)

    def loss_flash(q):
        return jnp.sum(
            fa.mha(q, k, v, causal=True, segment_ids=seg,
                   block_q=256, block_kv=256)
        )

    def loss_ref(q):
        return jnp.sum(xla_attention(q, k, v, causal=True, segment_ids=seg))

    np.testing.assert_allclose(
        jax.jit(jax.grad(loss_flash))(q), jax.jit(jax.grad(loss_ref))(q),
        atol=5e-4, rtol=5e-4,
    )


# -- the one-pass backward at several kv blocks -----------------------------

# (id, q heads, kv heads, d_qk, d_v, causal, segment ids, seq, block_q,
# block_kv): every feature the split pair serves, at 2 to 5 kv blocks
SEVERAL_KV_BLOCKS = [
    ("causal", 2, 2, 64, 64, True, False, 256, 64, 64),
    ("full", 2, 2, 64, 64, False, False, 256, 64, 64),
    ("gqa", 4, 2, 64, 64, True, False, 256, 64, 64),
    ("gqa_full", 4, 2, 64, 64, False, False, 256, 64, 64),
    ("latent_192_128", 2, 2, 192, 128, True, False, 256, 64, 64),
    ("segments", 2, 2, 64, 64, True, True, 256, 64, 64),
    ("segments_full", 2, 2, 64, 64, False, True, 256, 64, 64),
    ("unpadded", 2, 2, 64, 64, True, False, 200, 64, 64),
    ("unpadded_full", 2, 2, 64, 64, False, False, 200, 64, 64),
    ("wide_q_blocks", 2, 2, 64, 64, True, False, 256, 128, 64),
    ("wide_kv_blocks", 2, 2, 64, 64, True, True, 256, 64, 128),
    ("gqa_latent_segments_unpadded", 4, 2, 192, 128, True, True, 300, 128, 64),
    # blocks of 256 x 256: a diagonal block is two strips of 128 rows
    ("strips", 2, 2, 64, 64, True, False, 512, 256, 256),
    ("strips_gqa_latent", 4, 2, 192, 128, True, False, 512, 256, 256),
    ("strips_segments", 2, 2, 128, 128, True, True, 768, 256, 256),
    ("strips_unpadded", 2, 2, 64, 64, True, False, 500, 256, 256),
]

# the same columns at ONE kv block: no running state in the forward, no dq
# scratch in the backward; a block of 1024 is four strips of 256 rows
ONE_KV_BLOCK = [
    ("causal", 4, 4, 64, 64, True, False, 256, 256, 256),
    ("gqa_full", 4, 2, 64, 64, False, False, 256, 256, 256),
    ("four_strips", 2, 2, 64, 64, True, False, 1024, 1024, 1024),
    ("strips_gqa_latent", 4, 2, 192, 128, True, False, 512, 512, 512),
    ("strips_segments", 2, 2, 128, 128, True, True, 512, 512, 512),
    ("strips_unpadded", 2, 1, 128, 128, True, False, 300, 512, 512),
    ("square_block_too_small", 2, 2, 64, 64, True, True, 128, 128, 128),
]


def _case(rng, hq, hkv, d, d_v, segments, seq, dtype=jnp.float32):
    q = jnp.asarray(rng.normal(size=(2, seq, hq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(2, seq, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(2, seq, hkv, d_v)), dtype)
    seg = None
    if segments:
        seg = jnp.asarray((np.arange(seq) // 90)[None].repeat(2, 0), jnp.int32)
    return q, k, v, seg


def _assert_grads_match_xla(
    q, k, v, seg, causal, block_q, block_kv, label, tol=1e-3
):
    """The output elementwise, at the forward tests' limit, and the
    gradients of the sum of its squares within ``tol``."""
    def loss(attend):
        def sum_of_squares(q, k, v):
            out = attend(q, k, v)
            return jnp.sum(out ** 2), out

        # one program a side: op by op, each side compiles a hundred
        return jax.jit(jax.value_and_grad(
            sum_of_squares, argnums=(0, 1, 2), has_aux=True
        ))

    ((_, out), g_flash), ((_, ref), g_ref) = (
        loss(attend)(q, k, v) for attend in (
            lambda q, k, v: fa.mha(
                q, k, v, causal=causal, segment_ids=seg,
                block_q=block_q, block_kv=block_kv,
            ),
            lambda q, k, v: xla_attention(
                q, k, v, causal=causal, segment_ids=seg
            ),
        )
    )
    np.testing.assert_allclose(
        out, ref, atol=2e-5, rtol=2e-5, err_msg=f"o ({label})"
    )
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            gf, gr, atol=tol, rtol=tol, err_msg=f"d{name} ({label})"
        )


@pytest.mark.parametrize(
    "hq,hkv,d,d_v,causal,segments,seq,block_q,block_kv",
    [pytest.param(*c[1:], id=c[0]) for c in SEVERAL_KV_BLOCKS],
)
def test_grads_match_xla_fused_several_kv_blocks(
    rng, hq, hkv, d, d_v, causal, segments, seq, block_q, block_kv
):
    q, k, v, seg = _case(rng, hq, hkv, d, d_v, segments, seq)
    assert fa.backward_path(
        seq, seq, d, d_v, block_q, block_kv, q.dtype
    ) == "fused"
    _assert_grads_match_xla(
        q, k, v, seg, causal, block_q, block_kv, "one pass"
    )


@pytest.mark.parametrize(
    "hq,hkv,d,d_v,causal,segments,seq,block_q,block_kv",
    [pytest.param(*c[1:], id=c[0]) for c in ONE_KV_BLOCK],
)
def test_grads_match_xla_fused_single_kv_block(
    rng, hq, hkv, d, d_v, causal, segments, seq, block_q, block_kv
):
    """block_kv == (padded) seq routes through the fused one-pass backward
    kernel — the default-config path on the bench shapes."""
    q, k, v, seg = _case(rng, hq, hkv, d, d_v, segments, seq)
    assert fa._blocks_and_padding(seq, seq, block_q, block_kv)[3] == block_kv
    _assert_grads_match_xla(
        q, k, v, seg, causal, block_q, block_kv, "one kv block", tol=5e-4
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "hq,hkv,d,d_v,causal,segments,seq,block_q,block_kv",
    [pytest.param(*c[1:], id=c[0]) for c in SEVERAL_KV_BLOCKS],
)
def test_fused_several_kv_blocks_equals_the_split_pair_exactly(
    rng, hq, hkv, d, d_v, causal, segments, seq, block_q, block_kv, dtype
):
    """The same terms summed in the same order at the same precision: dq,
    dk and dv of the one pass are the split pair's to the last bit."""
    q, k, v, seg = _case(rng, hq, hkv, d, d_v, segments, seq, dtype)
    block_q, block_kv, sq_p, skv_p = fa._blocks_and_padding(
        seq, seq, block_q, block_kv
    )
    assert skv_p // block_kv > 1
    ids = jnp.zeros((2, seq), jnp.int32) if seg is None else seg
    ids = fa._pad_to(ids, sq_p, 1, value=-1)[:, None, :]
    q, k, v = (
        fa._pad_to(x.transpose(0, 2, 1, 3), sq_p, 2) for x in (q, k, v)
    )
    kw = dict(
        causal=causal, scale=d ** -0.5, block_q=block_q, block_kv=block_kv
    )
    kw["segments"] = seg is not None or sq_p != seq
    o, lse = fa._flash_fwd(q, k, v, ids, ids, **kw)
    do = jnp.asarray(rng.normal(size=o.shape), dtype)
    one_pass = fa._flash_bwd_fused(q, k, v, ids, ids, o, lse, do, **kw)
    split = fa._flash_bwd(q, k, v, ids, ids, o, lse, do, **kw)
    for got, want, name in zip(one_pass, split, ("dq", "dk", "dv")):
        assert got.dtype == want.dtype == dtype
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            err_msg=name,
        )


def _backward_kernels(seq, block, **patch):
    q = jnp.zeros((1, seq, 2, 64), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(fa.mha(q, k, v, block_q=block, block_kv=block))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    return text.count("pallas_call[")


def test_backward_of_four_kv_blocks_is_one_kernel(monkeypatch):
    """Forward + one backward kernel where the parent held forward + two;
    past the bound the split pair is back."""
    assert _backward_kernels(256, 256) == 2
    assert _backward_kernels(256, 64) == 2
    monkeypatch.setattr(fa, "_VMEM_CAP", 1 << 16)
    assert _backward_kernels(256, 256) == 2     # one kv block: no scratch
    assert _backward_kernels(256, 64) == 3


@pytest.mark.parametrize(
    "d,d_v,longest", [(128, 128, 43008), (192, 128, 20480)]
)
def test_backward_path_on_either_side_of_its_bound(d, d_v, longest):
    """The bound is the VMEM the kernel asks for, 64 MiB: bf16 at blocks
    of 1024 admits 42 kv blocks at head 128 and 20 at 192 / 128."""
    def path(seq, block=1024, dtype=jnp.bfloat16):
        return fa.backward_path(seq, seq, d, d_v, block, block, dtype)

    assert path(8192) == path(longest) == "fused"
    assert path(longest + 1024) == "split"
    assert fa._fused_bwd_vmem_bytes(
        longest, d, d_v, 1024, 1024, jnp.bfloat16
    ) <= fa._VMEM_CAP
    # one kv block keeps no dq scratch: fused at any length and width
    assert path(1 << 20, block=1 << 20) == "fused"
    # clamped and padded as mha does: 200 rows in blocks of 64 are 4 blocks
    assert fa._blocks_and_padding(200, 200, 64, 512) == (64, 256, 256, 256)
    assert path(200, block=512) == "fused"


def test_split_pair_still_correct_beyond_the_bound(rng, monkeypatch):
    monkeypatch.setattr(fa, "_VMEM_CAP", 1 << 16)
    q, k, v, seg = _case(rng, 4, 2, 192, 128, True, 200)
    assert fa.backward_path(200, 200, 192, 128, 64, 64, q.dtype) == "split"
    _assert_grads_match_xla(q, k, v, seg, True, 64, 64, "split")


# -- a block's class --------------------------------------------------------


def _classes_by_hand(sq, skv, block_q, block_kv):
    """Count from the mask itself: a block no row of which sees a column
    is dead, one every row of which sees every column interior."""
    seen = np.arange(sq)[:, None] >= np.arange(skv)[None, :]
    counts = {"dead": 0, "interior": 0, "diagonal": 0}
    for q0 in range(0, sq, block_q):
        for k0 in range(0, skv, block_kv):
            block = seen[q0:q0 + block_q, k0:k0 + block_kv]
            counts[
                "interior" if block.all() else
                "diagonal" if block.any() else "dead"
            ] += 1
    return counts


@pytest.mark.parametrize("seq,block_q,block_kv,causal,expected", [
    (8192, 1024, 1024, True, (28, 28, 8, 256)),    # JoyAI, the hybrid
    (4096, 1024, 1024, True, (6, 6, 4, 256)),      # OLMoE, Mixtral
    (1024, 1024, 1024, True, (0, 0, 1, 256)),      # GPT-2
    (8192, 1024, 1024, False, (0, 64, 0, 0)),
    (4096, 1024, 512, True, (12, 12, 8, 0)),       # not one size: the square
    (4096, 512, 1024, True, (12, 12, 8, 0)),
    (2048, 512, 512, True, (6, 6, 4, 256)),        # two strips
    (512, 256, 256, True, (1, 1, 2, 128)),
    (256, 128, 128, True, (1, 1, 2, 0)),           # no two strips of 128
    (200, 64, 512, True, (0, 0, 4, 0)),            # clamped and padded
])
def test_block_classes(seq, block_q, block_kv, causal, expected):
    got = fa.block_classes(seq, seq, block_q, block_kv, causal)
    assert tuple(got) == expected
    if causal:
        bq, bkv, sq_p, skv_p = fa._blocks_and_padding(
            seq, seq, block_q, block_kv
        )
        assert got._asdict() == {
            **_classes_by_hand(sq_p, skv_p, bq, bkv), "strip": got.strip
        }
        for iq in range(sq_p // bq):
            for ik in range(skv_p // bkv):
                dead, interior = fa._block_class(iq, ik, bq, bkv, True)
                rows = np.arange(iq * bq, (iq + 1) * bq)[:, None]
                cols = np.arange(ik * bkv, (ik + 1) * bkv)[None, :]
                assert dead == (not (rows >= cols).any())
                assert interior == bool((rows >= cols).all())


@pytest.mark.parametrize("seq,block_q,block_kv", [
    (8192, 1024, 1024), (4096, 1024, 512), (4096, 512, 1024),
    (1024, 1024, 1024),
])
def test_a_dead_step_names_a_block_that_is_already_there(
    seq, block_q, block_kv
):
    """kv inner (forward, dq): k and v stay on the q block's last live kv
    block.  q inner (one pass, dk / dv): q, do, o and lse stay on the kv
    block's first live q block.  A live step names its own."""
    nq, nk = seq // block_q, seq // block_kv
    live = [
        [not fa._block_class(iq, ik, block_q, block_kv, True)[0]
         for ik in range(nk)] for iq in range(nq)
    ]
    for iq in range(nq):
        for ik in range(nk):
            kv = int(fa._last_live_kv(iq, ik, block_q, block_kv, True))
            q = int(fa._first_live_q(iq, ik, nq, block_q, block_kv, True))
            if live[iq][ik]:
                assert (q, kv) == (iq, ik)
                continue
            assert kv == max(j for j in range(nk) if live[iq][j]) < ik
            assert q == min(i for i in range(nq) if live[i][ik]) > iq
            assert fa._last_live_kv(iq, ik, block_q, block_kv, False) == ik
            assert fa._first_live_q(iq, ik, nq, block_q, block_kv, False) == iq


@pytest.mark.parametrize("path", ["fused", "split"])
def test_more_keys_than_queries(rng, monkeypatch, path):
    """A kv block past the last q row is dead for every q block: its steps
    name the last q block there is, and its dk and dv are zero."""
    if path == "split":
        monkeypatch.setattr(fa, "_VMEM_CAP", 1 << 16)
    sq, skv, block = 128, 320, 64
    assert fa.backward_path(sq, skv, 64, 64, block, block, jnp.float32) == path
    nq, nk = sq // block, skv // block
    for ik in range(nk):
        assert 0 <= fa._first_live_q(0, ik, nq, block, block, True) < nq
    q = jnp.asarray(rng.normal(size=(1, sq, 2, 64)), jnp.float32)
    k, v = (
        jnp.asarray(rng.normal(size=(1, skv, 2, 64)), jnp.float32)
        for _ in range(2)
    )
    _assert_grads_match_xla(q, k, v, None, True, block, block, path)


@pytest.mark.parametrize("seq,with_ids,compared", [
    (256, False, False), (256, True, True), (200, False, True),
])
def test_the_segment_compare_is_built_only_where_it_can_bite(
    seq, with_ids, compared
):
    """No ids and no padded length: no kernel, forward or backward, holds
    a tile-shaped compare of ids (the causal mask is a ``ge``)."""
    import re

    q = jnp.zeros((1, seq, 2, 64), jnp.float32)
    ids = jnp.zeros((1, seq), jnp.int32) if with_ids else None

    def loss(q, k, v):
        return jnp.sum(
            fa.mha(q, k, v, segment_ids=ids, block_q=128, block_kv=128)
        )

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    assert text.count("pallas_call[") == 2
    tiles = [
        m for m in re.findall(r"bool\[(\d+),(\d+)\] = eq ", text)
        if min(map(int, m)) > 1
    ]
    assert bool(tiles) == compared
