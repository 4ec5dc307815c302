"""Quantization + grouped matmul kernel tests (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.ops import grouped_matmul as gmm
from dlrover_tpu.ops import quantization as qz


def test_quantize_dequantize_roundtrip(rng):
    x = jnp.asarray(rng.normal(size=(33, 77)) * 5.0, jnp.float32)
    q, scales = qz.quantize(x)
    assert q.dtype == jnp.int8
    out = qz.dequantize(q, scales, x.shape)
    # absmax/127 per 256-block: error bounded by scale/2 per block
    err = np.abs(np.asarray(out) - np.asarray(x))
    bound = np.abs(np.asarray(x)).max() / 127.0
    assert err.max() <= bound + 1e-6


@pytest.mark.slow  # long optimizer tracking loop
def test_q8_adam_tracks_fp32_adam(rng):
    """Quantized Adam should follow full-precision Adam closely on a quadratic."""
    dim = 8192  # above min_quant_size -> quantized path
    target = jnp.asarray(rng.normal(size=(dim,)), jnp.float32)
    params_q = {"w": jnp.zeros(dim, jnp.float32), "b": jnp.zeros(8, jnp.float32)}
    params_f = {"w": jnp.zeros(dim, jnp.float32), "b": jnp.zeros(8, jnp.float32)}

    opt_q = qz.q8_adam(learning_rate=0.05)
    opt_f = optax.adam(0.05)
    s_q, s_f = opt_q.init(params_q), opt_f.init(params_f)

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2) + jnp.sum(p["b"] ** 2)

    for _ in range(30):
        g_q = jax.grad(loss)(params_q)
        u_q, s_q = opt_q.update(g_q, s_q, params_q)
        params_q = optax.apply_updates(params_q, u_q)
        g_f = jax.grad(loss)(params_f)
        u_f, s_f = opt_f.update(g_f, s_f, params_f)
        params_f = optax.apply_updates(params_f, u_f)

    # quantized Adam must descend comparably to fp32 Adam (a few % per-step
    # state error is expected; divergence or stalls are not)
    loss_q, loss_f = float(loss(params_q)), float(loss(params_f))
    assert loss_q < 0.25 * dim, loss_q
    assert loss_q < 2.0 * loss_f + 1.0, (loss_q, loss_f)
    drift = jnp.abs(params_q["w"] - params_f["w"]).max()
    assert float(drift) < 0.25, float(drift)


def test_q8_adam_small_leaf_exact(rng):
    """Small leaves bypass quantization and match optax.adam exactly."""
    p = {"b": jnp.asarray(rng.normal(size=(16,)), jnp.float32)}
    g = {"b": jnp.asarray(rng.normal(size=(16,)), jnp.float32)}
    opt_q = qz.q8_adam(learning_rate=0.1)
    opt_f = optax.adam(0.1, eps_root=0.0)
    u_q, _ = opt_q.update(g, opt_q.init(p), p)
    u_f, _ = opt_f.update(g, opt_f.init(p), p)
    np.testing.assert_allclose(u_q["b"], u_f["b"], atol=1e-6, rtol=1e-5)


@pytest.mark.slow  # long optimizer tracking loop
def test_q4_adam_tracks_fp32_adam(rng):
    """4-bit moments: coarser than q8 but must still descend comparably
    (ref low_bit/functional.py q4 states)."""
    dim = 8192
    target = jnp.asarray(rng.normal(size=(dim,)), jnp.float32)
    params_q = {"w": jnp.zeros(dim, jnp.float32), "b": jnp.zeros(8, jnp.float32)}
    params_f = {"w": jnp.zeros(dim, jnp.float32), "b": jnp.zeros(8, jnp.float32)}

    opt_q = qz.q4_adam(learning_rate=0.05)
    opt_f = optax.adam(0.05)
    s_q, s_f = opt_q.init(params_q), opt_f.init(params_f)

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2) + jnp.sum(p["b"] ** 2)

    # 4-bit moments converge with a slower transient than q8 (15 levels of
    # momentum); the contract is sustained descent to near-convergence,
    # not per-step tracking.
    for _ in range(100):
        g_q = jax.grad(loss)(params_q)
        u_q, s_q = opt_q.update(g_q, s_q, params_q)
        params_q = optax.apply_updates(params_q, u_q)
        g_f = jax.grad(loss)(params_f)
        u_f, s_f = opt_f.update(g_f, s_f, params_f)
        params_f = optax.apply_updates(params_f, u_f)

    loss_q = float(loss(params_q))
    assert loss_q < 0.02 * dim, loss_q
    assert np.isfinite(loss_q)


def test_q4_adam_state_is_1_25_bytes_per_param():
    """The point of q4: moment containers pack two values per byte and
    scales ride 8 lanes — ~1.25 bytes/param of optimizer state."""
    dim = 65536
    p = {"w": jnp.zeros(dim, jnp.float32)}
    opt = qz.q4_adam(learning_rate=0.1)
    state = opt.init(p)
    m = state.m["w"]
    total = (m.q.size * m.q.dtype.itemsize
             + m.scales.size * m.scales.dtype.itemsize) * 2  # m and v
    assert total / dim <= 1.3, total / dim
    # nibble round-trip sanity
    import numpy as np2
    vals = jnp.asarray(np2.arange(-7, 8).repeat(18)[:qz.BLOCK], jnp.int32)
    packed = qz._pack_nibbles(vals[None, :])
    un = qz._unpack_nibbles_signed(packed)
    np.testing.assert_array_equal(un[0], np2.asarray(vals, np2.float32))


def test_q4_adam_small_leaf_exact(rng):
    p = {"b": jnp.asarray(rng.normal(size=(16,)), jnp.float32)}
    g = {"b": jnp.asarray(rng.normal(size=(16,)), jnp.float32)}
    opt_q = qz.q4_adam(learning_rate=0.1)
    opt_f = optax.adam(0.1, eps_root=0.0)
    u_q, _ = opt_q.update(g, opt_q.init(p), p)
    u_f, _ = opt_f.update(g, opt_f.init(p), p)
    # eps placement differs (we fold sqrt(1-b2) into the numerator; optax
    # rescales v before adding eps): agreement to ~1e-4 relative.
    np.testing.assert_allclose(u_q["b"], u_f["b"], atol=1e-5, rtol=1e-4)


def test_grouped_matmul_fwd(rng):
    e, k, m = 4, 64, 128
    sizes = jnp.asarray([256, 0, 128, 128], jnp.int32)
    n = int(sizes.sum())
    x = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(e, k, m)) * 0.1, jnp.float32)
    out = gmm.grouped_matmul(x, w, sizes, block_rows=128)
    ref = gmm.grouped_matmul_ref(x, w, sizes)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("k,m,tile_bytes,cap,fwd_tk,dx_tk,dw_tile", [
    (64, 64, None, None, 64, 64, (64, 64)),    # one tile per expert block
    # no more VMEM to ask for: fwd/dx tile M then K, dw tiles M and K
    (256, 384, 256 * 1024, 0, 256, 128, (128, 128)),
    # M off the lane grid: only K can be split, and without a cap it is
    (384, 200, 256 * 1024, 0, 128, 200, (128, 200)),
    # the same under the cap: the strip the budget would cut in three
    # stays whole, one contraction step, and so does the dw tile
    (384, 200, 256 * 1024, None, 384, 200, (384, 200)),
    # a cap that holds half of dw: cut along K where dy (m wide) is the
    # narrower operand to read twice ...
    (512, 256, 256 * 1024, 1792 * 1024, 512, 256, (256, 256)),
    # ... and along M where x (k wide) is
    (256, 512, 256 * 1024, 1792 * 1024, 256, 512, (256, 256)),
])
def test_grouped_matmul_grads(rng, monkeypatch, k, m, tile_bytes, cap,
                              fwd_tk, dx_tk, dw_tile):
    if tile_bytes is not None:
        monkeypatch.setattr(gmm, "_TILE_BYTES", tile_bytes)
    if cap is not None:
        monkeypatch.setattr(gmm, "_VMEM_CAP", cap)
    assert gmm.plan_tiles(k, m, False, False, jnp.float32).tk == fwd_tk
    assert gmm.plan_tiles(m, k, False, False, jnp.float32).tk == dx_tk
    assert gmm.plan_dw_tiles(k, m, False, False, jnp.float32)[:2] == dw_tile
    e = 3
    sizes = jnp.asarray([128, 256, 128], jnp.int32)
    n = int(sizes.sum())
    x = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(e, k, m)) * 0.1, jnp.float32)

    def loss_kernel(x, w):
        return jnp.sum(gmm.grouped_matmul(x, w, sizes, block_rows=128) ** 2)

    def loss_ref(x, w):
        return jnp.sum(gmm.grouped_matmul_ref(x, w, sizes) ** 2)

    gx_k, gw_k = jax.grad(loss_kernel, argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(gx_k, gx_r, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(gw_k, gw_r, atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("call", ["wo_forward", "wi_dx"])
def test_grouped_matmul_out_of_the_width_under_the_limit_it_asks_for(
    rng, monkeypatch, call
):
    """Command A+'s case in small: rows leave the expert width row-tiled,
    so the narrowest block of M is a native tile's 2,048 columns and the
    whole-K strip overflows the budget.  A cap that holds it to the byte
    keeps K whole (one contraction step, the limit asked for); one byte
    less splits K in two.  Both are the reference's numbers, forward and
    gradients, whether the call is ``wo``'s forward or the dx of ``wi``."""
    d, ff, e = 2048, 256, 3
    dtype = jnp.bfloat16
    monkeypatch.setattr(gmm, "_TILE_BYTES", 512 * 1024)
    need = gmm._strip_vmem_bytes(ff, d, dtype, 128)
    sizes = jnp.asarray([128, 256, 0], jnp.int32)
    n = int(sizes.sum()) + 128         # a dead block of the budget's slack
    if call == "wo_forward":
        k, m, tiled_in, tiled_out = ff, d, False, True
    else:
        k, m, tiled_in, tiled_out = d, ff, True, False
    x = jnp.asarray(rng.normal(size=(n, k)), dtype)
    w = jnp.asarray(rng.normal(size=(e, k, m)) * 0.05, dtype)
    dy = jnp.asarray(rng.normal(size=(n, m)), dtype)

    def kernel(x, w):
        rows = x.reshape(n, k // 128, 128) if tiled_in else x
        out = gmm.grouped_matmul(rows, w, sizes, 128, tiled_out, True)
        return out.reshape(n, m)

    want, ref_vjp = jax.vjp(
        lambda x, w: gmm.grouped_matmul_ref(x, w, sizes), x, w
    )
    for cap, steps in ((need, 1), (need - 1, 2)):
        monkeypatch.setattr(gmm, "_VMEM_CAP", cap)
        out_of = gmm.plan_tiles(ff, d, False, True, dtype)
        assert out_of == (ff // steps, d, need if steps == 1 else None)
        got, vjp = jax.vjp(kernel, x, w)
        for a, b in zip((got,) + vjp(dy), (want,) + ref_vjp(dy)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=0.05, rtol=0.02,
            )


def test_grouped_matmul_dw_is_the_same_numbers_whatever_the_tile(
    rng, monkeypatch
):
    """Every element of dw is the float32 sum over its expert's row blocks
    in the same order, rounded once: whole under the cap or in the six
    tiles of the default budget's rule, bit for bit."""
    k, m = 384, 256
    sizes = jnp.asarray([256, 0, 384, 128], jnp.int32)
    n = int(sizes.sum()) + 128     # a block of the budget's slack
    x = jnp.asarray(rng.normal(size=(n, k)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(4, k, m)) * 0.1, jnp.bfloat16)
    dy = jnp.asarray(rng.normal(size=(n, m)), jnp.bfloat16)
    monkeypatch.setattr(gmm, "_TILE_BYTES", 128 * 1024)

    def dw():
        _, vjp = jax.vjp(lambda w: gmm.grouped_matmul(x, w, sizes), w)
        return vjp(dy)[0]

    whole = gmm.plan_dw_tiles(k, m, False, False, jnp.bfloat16)
    assert (whole.tk, whole.tm) == (k, m) and whole.vmem_limit_bytes
    under_the_cap = dw()
    monkeypatch.setattr(gmm, "_VMEM_CAP", 0)
    assert gmm.plan_dw_tiles(k, m, False, False, jnp.bfloat16) == (
        128, 128, None
    )
    np.testing.assert_array_equal(
        np.asarray(under_the_cap, np.float32), np.asarray(dw(), np.float32)
    )


# (k, m, rows in tiled, rows out tiled) -> (tk, tm, asks for more VMEM), in
# bfloat16.  A gated expert layer's six forward/dx calls are two plans: wi /
# wg forward and wo's dx ("into" the expert width), wo forward and wi / wg's
# dx ("out of" it).  The four cells above the line keep the tiles they had
# before `plan_tiles` (their strips were whole); LFM2's and Nemotron's split
# K until PR 53 ((896, 2048) and (896, 1856)); Mixtral's wo still does.
_CELL_TILES = {
    "olmoe_into": ((2048, 1024, True, False), (2048, 1024, False)),
    "olmoe_out_of": ((1024, 2048, False, True), (1024, 2048, False)),
    "joyai_into": ((2048, 768, True, False), (2048, 768, False)),
    "joyai_out_of": ((768, 2048, False, True), (768, 2048, False)),
    "ling_into": ((2560, 768, False, False), (2560, 768, False)),
    "ling_out_of": ((768, 2560, False, False), (768, 2560, False)),
    "granite_into": ((4096, 768, True, False), (4096, 384, False)),
    "granite_out_of": ((768, 4096, False, True), (768, 2048, False)),
    "lfm2_into": ((2048, 1792, True, False), (2048, 896, False)),
    "nemotron_out_of": ((1856, 2688, False, False), (1856, 896, False)),
    "mixtral_into": ((4096, 14336, True, False), (4096, 512, False)),
    # Mellum2 (PR 54): rows of 2,304 = 18 lane tiles stay plain
    "mellum_into": ((2304, 896, False, False), (2304, 896, False)),
    "mellum_out_of": ((896, 2304, False, False), (896, 2304, False)),
    # ------------------------------------------------------------------
    "lfm2_out_of": ((1792, 2048, False, True), (1792, 2048, True)),
    "nemotron_into": ((2688, 1856, False, False), (2688, 1856, True)),
    "mixtral_out_of": ((14336, 4096, False, True), (1024, 2048, False)),
    # Command A+ (PR 58): into the width strips of 512 columns were always
    # whole; out of it a row-tiled block's narrowest width is 2,048 columns,
    # whose whole-K strip counts 38.0 MiB: K split in four (1024, 2048)
    # until the cap went from 32 to 40 MiB (PR 61)
    "command_a_into": ((4096, 4096, True, False), (4096, 512, False)),
    "command_a_out_of": ((4096, 4096, False, True), (4096, 2048, True)),
}


@pytest.mark.parametrize("call", sorted(_CELL_TILES))
def test_plan_tiles_at_the_cells_shapes(call):
    (k, m, x_tiled, out_tiled), (tk, tm, asks) = _CELL_TILES[call]
    plan = gmm.plan_tiles(k, m, x_tiled, out_tiled, jnp.bfloat16)
    assert (plan.tk, plan.tm) == (tk, tm)
    if not asks:
        # the default scoped limit, and no compiler parameters at all
        assert plan.vmem_limit_bytes is None
    else:
        # what the whole-K strip needs, twice over, and within the cap
        assert 2 * k * tm * 2 < plan.vmem_limit_bytes <= gmm._VMEM_CAP


# (k, m, x row-tiled, dy row-tiled) -> (tk, tm, asks for more VMEM), in
# bfloat16: the two dW plans of each grouped cell's expert layer, wi / wg's
# ("into" the expert width) and wo's ("out of" it), and Mixtral's under
# `grouped`.  Until PR 55 the default scoped VMEM's budget cut them into
# the tiles on the right; every M tile reads x again, every K tile dy.
_CELL_DW_TILES = {
    "mellum_into": ((2304, 896, False, False), (2304, 896, True)),    # 1x7
    "mellum_out_of": ((896, 2304, False, False), (896, 2304, True)),  # 1x2
    "lfm2_into": ((2048, 1792, True, False), (2048, 1792, True)),     # 1x7
    "lfm2_out_of": ((1792, 2048, False, True), (1792, 2048, True)),   # 7x1
    # 2688 x 1856 whole would take 40 MiB: three tiles along the side that
    # can be cut (1,856 is 14.5 lane tiles, a block's full extent)
    "nemotron_into": ((2688, 1856, False, False), (896, 1856, True)),  # 7x1
    "nemotron_out_of": ((1856, 2688, False, False), (1856, 896, True)),
    "olmoe_into": ((2048, 1024, True, False), (2048, 1024, True)),    # 1x2
    "olmoe_out_of": ((1024, 2048, False, True), (1024, 2048, True)),  # 2x1
    "granite_into": ((4096, 768, True, False), (4096, 768, True)),    # 1x3
    "granite_out_of": ((768, 4096, False, True), (768, 4096, True)),  # 2x2
    "joyai_into": ((2048, 768, True, False), (2048, 768, True)),      # 1x2
    "joyai_out_of": ((768, 2048, False, True), (768, 2048, True)),    # 2x1
    "ling_into": ((2560, 768, False, False), (2560, 768, True)),      # 1x2
    "ling_out_of": ((768, 2560, False, False), (768, 2560, True)),    # 1x2
    # fourteen tiles under the cap where the budget cut 56 (sixteen of
    # 2048 x 1792 under 32 MiB, until PR 61): rows of 4,096 row-tiled can
    # be halved, and then the wider tile of M re-reads less
    "mixtral_into": ((4096, 14336, True, False), (2048, 2048, True)),
    "mixtral_out_of": ((14336, 4096, False, True), (2048, 2048, True)),
    # Command A+ (PR 61): four tiles of 35.0 MiB where 32 MiB held eight
    # ([2048, 1024] / [1024, 2048]); the whole tile would count 100 MiB
    "command_a_into": ((4096, 4096, True, False), (2048, 2048, True)),
    "command_a_out_of": ((4096, 4096, False, True), (2048, 2048, True)),
    # a tile the default limit holds stays, and asks for nothing
    "preset": ((256, 512, False, False), (256, 512, False)),
}


@pytest.mark.parametrize("call", sorted(_CELL_DW_TILES))
def test_plan_dw_tiles_at_the_cells_shapes(call):
    (k, m, x_tiled, out_tiled), (tk, tm, asks) = _CELL_DW_TILES[call]
    plan = gmm.plan_dw_tiles(k, m, x_tiled, out_tiled, jnp.bfloat16)
    assert (plan.tk, plan.tm) == (tk, tm)
    if not asks:
        assert plan.vmem_limit_bytes is None
    else:
        # over the float32 accumulator and the output tile twice, and
        # within the cap
        assert 8 * tk * tm < plan.vmem_limit_bytes <= gmm._VMEM_CAP


@pytest.mark.parametrize("d,ff,tiled,cap,said", [
    (2304, 896, False, None, "into:1x1 out_of:1x1"),      # Mellum2
    (2304, 896, False, 0, "into:1x7 out_of:1x2"),         # ... until PR 55
    (2048, 1792, True, None, "into:1x1 out_of:1x1"),      # LFM2
    (2048, 1792, True, 0, "into:1x7 out_of:7x1"),
    (2688, 1856, False, None, "into:3x1 out_of:1x3"),     # Nemotron
    (2688, 1856, False, 0, "into:7x1 out_of:1x7"),
    (4096, 14336, True, None, "into:2x7 out_of:7x2"),     # Mixtral, `grouped`
    (4096, 4096, True, None, "into:2x2 out_of:2x2"),      # Command A+
    (4096, 4096, True, 32 << 20, "into:2x4 out_of:4x2"),  # ... until PR 61
])
def test_expert_dw_tiles_counts_a_layers_tiles(monkeypatch, d, ff, tiled,
                                               cap, said):
    if cap is not None:
        monkeypatch.setattr(gmm, "_VMEM_CAP", cap)
    assert gmm.expert_dw_tiles(d, ff, tiled, jnp.bfloat16) == said


@pytest.mark.parametrize("d,ff,gated,tiled,said", [
    (2048, 1024, True, True, "resident"),       # OLMoE
    (2048, 1792, True, True, "resident"),       # LFM2 (split_k:3/6 before)
    (2688, 1856, False, False, "resident"),     # Nemotron (split_k:2/4)
    (4096, 14336, True, True, "split_k:3/6"),   # Mixtral under `grouped`
    (4096, 4096, True, True, "resident"),       # Command A+ (split_k:3/6)
])
def test_expert_strips_counts_a_layers_calls(d, ff, gated, tiled, said):
    assert gmm.expert_strips(d, ff, gated, tiled, jnp.bfloat16) == said


def test_expert_strips_under_32_mib_split_command_a_plus(monkeypatch):
    """The cap PR 53 chose held every cell's strip but the one a row-tiled
    output makes 2,048 columns wide at K 4,096 (38.0 MiB counted)."""
    monkeypatch.setattr(gmm, "_VMEM_CAP", 32 << 20)
    assert gmm.plan_tiles(4096, 4096, False, True, jnp.bfloat16) == (
        1024, 2048, None
    )
    assert gmm.expert_strips(
        4096, 4096, True, True, jnp.bfloat16
    ) == "split_k:3/6"


def test_expert_strips_without_the_cap_reads_the_old_rule(monkeypatch):
    monkeypatch.setattr(gmm, "_VMEM_CAP", 0)
    assert gmm.expert_strips(
        2048, 1792, True, True, jnp.bfloat16
    ) == "split_k:3/6"
    assert gmm.expert_strips(
        2688, 1856, False, False, jnp.bfloat16
    ) == "split_k:2/4"


def test_grouped_matmul_empty_expert_grad(rng):
    """dw of an expert with zero rows must be exactly zero (not NaN)."""
    e, k, m = 3, 64, 64
    sizes = jnp.asarray([256, 0, 128], jnp.int32)
    n = int(sizes.sum())
    x = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(e, k, m)), jnp.float32)
    gw = jax.grad(
        lambda w: jnp.sum(gmm.grouped_matmul(x, w, sizes, block_rows=128))
    )(w)
    assert np.all(np.isfinite(np.asarray(gw)))
    np.testing.assert_array_equal(np.asarray(gw[1]), 0.0)
