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

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
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
        jax.grad(loss_flash)(q), jax.grad(loss_ref)(q), atol=5e-4, rtol=5e-4
    )


@pytest.mark.parametrize("hq,hkv,causal", [(4, 4, True), (4, 2, False)])
def test_grads_match_xla_fused_single_kv_block(rng, hq, hkv, causal):
    """block_kv == (padded) seq routes through the fused one-pass backward
    kernel — the default-config path on the bench shapes."""
    q, k, v = _rand_qkv(rng, 1, 256, hq, hkv, 64)

    def loss_flash(q, k, v):
        return jnp.sum(
            fa.mha(q, k, v, causal=causal, block_q=256, block_kv=256) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            gf, gr, atol=5e-4, rtol=5e-4, err_msg=f"d{name} (fused path)"
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
        jax.grad(loss_flash)(q), jax.grad(loss_ref)(q), atol=5e-4, rtol=5e-4
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
]


def _case(rng, hq, hkv, d, d_v, segments, seq, dtype=jnp.float32):
    q = jnp.asarray(rng.normal(size=(2, seq, hq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(2, seq, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(2, seq, hkv, d_v)), dtype)
    seg = None
    if segments:
        seg = jnp.asarray((np.arange(seq) // 90)[None].repeat(2, 0), jnp.int32)
    return q, k, v, seg


def _assert_grads_match_xla(q, k, v, seg, causal, block_q, block_kv, label):
    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v) ** 2)

    g_flash = jax.grad(loss(lambda q, k, v: fa.mha(
        q, k, v, causal=causal, segment_ids=seg,
        block_q=block_q, block_kv=block_kv,
    )), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(lambda q, k, v: xla_attention(
        q, k, v, causal=causal, segment_ids=seg
    )), argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            gf, gr, atol=1e-3, rtol=1e-3, err_msg=f"d{name} ({label})"
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
