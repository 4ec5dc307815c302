"""Compile for a described (not attached) TPU v5e what the chip must accept.

The TPU compiler ships with jaxlib's libtpu and compiles for a topology
that is only described (on-chip-measurement guide §2.3), so these run on
the CPU lane at no chip time: every Pallas kernel the tree can route to on
a TPU, at the widths ``chip_smoke.py`` runs them at.  Interpret mode lowers
a kernel to plain HLO and hides what Mosaic refuses (block shapes off the
(8, 128) tiling, scoped-VMEM overflow, unsupported shape casts), which is
how four of these kernels passed every CPU test while refused by the chip.
A compile that passes is not a chip run; ``chip_smoke.py`` is.
"""

import os
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dlrover_tpu.ops import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"v5e:2x2 topology cannot be described here: {e}")
    return topo.devices


@pytest.fixture
def compile_for_chip(v5e, monkeypatch):
    """``compile(fn, *avals, **static)`` for one described v5e device, with
    the kernels in compiled (not interpret) mode and the persistent cache
    off: a described-chip executable written there cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(backend, "interpret", lambda: False)
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    one_chip = SingleDeviceSharding(v5e[0])

    def compile_(fn, *avals, **static):
        args = [
            jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in avals
        ]
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        return jitted.lower(*args, **static).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    cc.reset_cache()


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


def _flash(block, window=None):
    from dlrover_tpu.ops import flash_attention as fa

    def loss(q, k, v, ids=None):
        out = fa.mha(
            q, k, v, causal=True, segment_ids=ids,
            block_q=block, block_kv=block, window=window,
        )
        return out.astype(F32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


def _sparse_flash(block):
    """Attention over a choice (ops/sparse_flash_attention.py): the forward
    and the backward its shapes take under the int8 mask (``backward_path``:
    one pass where dq over the sequence fits in VMEM, the split pair past
    it)."""
    from dlrover_tpu.ops import sparse_flash_attention as sfa

    def loss(q, k, v, mask):
        out, _ = sfa.mha(
            q, k, v, mask, scale=q.shape[-1] ** -0.5, block=block
        )
        return out.astype(F32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


def _index_choice(topk, rows):
    """The indexer's scores and the choice by counting, a block of query
    rows at a time (ops/index_select.py): plain XLA, no kernel."""
    from dlrover_tpu.ops import index_select

    return lambda q, k, w: index_select.choose_blocked(
        q, k, w, None, topk, rows
    )


def _flash_band_split(block, window):
    """The banded forward and the SPLIT backward pair (a length past the
    one-pass backward's bound would take it; here it is asked for)."""
    from dlrover_tpu.ops import flash_attention as fa

    def fn(q, k, v, do):
        b, _, s, _ = q.shape
        ids = jnp.zeros((b, 1, s), I32)
        static = dict(
            causal=True, scale=q.shape[-1] ** -0.5, block_q=block,
            block_kv=block, segments=False, window=window,
        )
        o, lse = fa._flash_fwd(q, k, v, ids, ids, **static)
        return fa._flash_bwd(q, k, v, ids, ids, o, lse, do, **static)

    return fn


def _norm_grad(name, with_bias):
    from dlrover_tpu.ops import fused_norm

    fn = getattr(fused_norm, name)

    def loss(x, *params):
        return fn(x, *params).astype(F32).sum()

    return jax.grad(loss, argnums=tuple(range(2 + with_bias)))


def _grouped_matmul(out_tiled=False, skip_dead=False):
    from dlrover_tpu.ops.grouped_matmul import grouped_matmul

    def loss(x, w, sizes):
        return grouped_matmul(
            x, w, sizes, 128, out_tiled, skip_dead
        ).astype(F32).sum()

    # value_and_grad keeps the forward kernel live beside dx and dw.
    return jax.value_and_grad(loss, argnums=(0, 1))


def _row_gather_sum():
    from dlrover_tpu.ops.row_gather_sum import gather_sum

    return gather_sum


def _row_gather_sum_live():
    """Under a share's plan: the last row is the zero row, and the pairs
    that name it are neither fetched nor added."""
    from dlrover_tpu.ops.row_gather_sum import gather_sum, live_pairs

    def fn(rows, index, *gates):
        zero_row, d = rows.shape[0] - 1, rows.shape[1] * rows.shape[2]
        live = live_pairs(index, zero_row, d, rows.dtype)
        return gather_sum(rows, index, *gates, live=live)

    return fn


def _row_gather_sum_padded(share):
    """Plain rows of whole lanes that are no whole tiles, padded at the
    kernel's door and the sums cut back; under a share the list is the one
    made for the padded width."""
    from dlrover_tpu.ops import row_gather_sum as rgs

    def fn(rows, index, *gates):
        live = None
        if share:
            width = rgs.padded_width(rows.shape[1], index.shape[1], rows.dtype)
            live = rgs.live_pairs(index, rows.shape[0] - 1, width, rows.dtype)
        return rgs.padded_gather_sum(rows, index, *gates, live=live)

    return fn


def _delta_rule():
    from dlrover_tpu.ops.gated_delta_rule import gated_delta_rule

    def loss(q, k, v, g, beta):
        return gated_delta_rule(q, k, v, g, beta)[0].astype(F32).sum()

    return jax.grad(loss, argnums=(0, 1, 2, 3, 4))


def _kda(exact=False):
    from dlrover_tpu.ops.kda import kda

    def loss(q, k, v, g, beta):
        return kda(q, k, v, g, beta, exact=exact)[0].astype(F32).sum()

    return jax.grad(loss, argnums=(0, 1, 2, 3, 4))


def _ssd(chunk=128):
    from dlrover_tpu.ops.ssd import ssd

    def loss(x, dt, a, b, c, d):
        return ssd(
            x, dt, a, b, c, d, chunk=chunk, impl="kernel"
        )[0].astype(F32).sum()

    return jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))


def _short_conv(offset, splits, l2_scales, has_bias):
    from dlrover_tpu.ops.short_conv import short_conv

    def loss(x, taps, bias=None):
        outs = short_conv(x, taps, bias, offset, splits, l2_scales)
        return sum(y.astype(F32).sum() for y in outs)

    # the backward rebuilds what it needs from x: the value keeps the
    # forward kernel in the program
    return jax.value_and_grad(
        loss, argnums=(0, 1, 2) if has_bias else (0, 1)
    )


def _gated_conv():
    from dlrover_tpu.ops.short_conv import gated_conv

    def loss(x, taps):
        return gated_conv(x, taps).astype(F32).sum()

    return jax.value_and_grad(loss, argnums=(0, 1))


def _quant_roundtrip():
    from dlrover_tpu.ops import quantization as qz

    return lambda x: qz.dequantize(*qz.quantize(x), x.shape)


def _adam_update(name):
    from dlrover_tpu.ops import quantization as qz

    opt = getattr(qz, name)(learning_rate=1e-3)

    def update(g, p):
        return opt.update({"w": g}, opt.init({"w": p}), {"w": p})

    return update


def _embed(name):
    from dlrover_tpu.embedding import kernels

    return getattr(kernels, name)


QKV_1P5B = ((16, 1024, 25, 64), BF16)      # GPT-2 1.5B, batch 16
QKV_LONG = ((4, 2048, 32, 128), BF16)      # head_dim 128, two kv blocks


def _flash_longest(seq, d, d_v=None):
    """One sequence of one head at the one-pass backward's bound."""
    qk = ((1, seq, 1, d), BF16)
    return [qk, qk, ((1, seq, 1, d_v or d), BF16)]


MELLUM_QKV = (
    [((1, 32768, 32, 128), BF16)] + [((1, 32768, 4, 128), BF16)] * 2
)
COMMAND_A_QKV = (
    [((1, 16384, 32, 128), BF16)] + [((1, 16384, 2, 128), BF16)] * 2
)
LEAF = ((1600, 6400), F32)                 # the 1.5B MLP wi kernel
CACHE = ((65536, 128), F32)

# (id, builder, avals, static kwargs, kernels expected in the program)
CASES = [
    ("flash_fwd_fused_bwd", lambda: _flash(1024), [QKV_1P5B] * 3, {}, 2),
    # several kv blocks: the forward and ONE backward kernel, its dq in a
    # whole-sequence VMEM scratch (PR 34; the split pair made three)
    ("flash_fused_bwd_two_kv_blocks", lambda: _flash(1024),
     [QKV_LONG] * 3, {}, 2),
    # JoyAI-LLM-Flash's latent attention: 2 x 8192 tokens, 32 heads, keys
    # 192 wide (1.5 lane groups) and values 128; eight kv blocks
    ("flash_latent_192_128", lambda: _flash(1024),
     [((2, 8192, 32, 192), BF16)] * 2 + [((2, 8192, 32, 128), BF16)], {}, 2),
    # the longest sequences `backward_path` calls fused (64 MiB of VMEM
    # asked for), and the first beyond: the split pair, three kernels
    ("flash_fused_bwd_longest_128", lambda: _flash(1024),
     _flash_longest(43008, 128), {}, 2),
    ("flash_fused_bwd_longest_192_128", lambda: _flash(1024),
     _flash_longest(20480, 192, 128), {}, 2),
    ("flash_split_bwd", lambda: _flash(1024),
     _flash_longest(44032, 128), {}, 3),
    # the strips of a diagonal block slice the segment ids too: packed
    # documents at JoyAI's widths, and a length padded to whole blocks
    ("flash_strips_with_ids", lambda: _flash(1024),
     [((2, 8192, 32, 192), BF16)] * 2 + [((2, 8192, 32, 128), BF16),
                                         ((2, 8192), I32)], {}, 2),
    ("flash_strips_padded", lambda: _flash(1024),
     [((4, 4000, 16, 128), BF16)] * 3, {}, 2),
    ("fused_layernorm", lambda: _norm_grad("fused_layernorm", True),
     [((16, 1024, 1600), BF16), ((1600,), F32), ((1600,), F32)], {}, 1),
    ("fused_rmsnorm", lambda: _norm_grad("fused_rmsnorm", False),
     [((4, 2048, 4096), BF16), ((4096,), F32)], {}, 1),
    ("grouped_matmul_wi", _grouped_matmul,
     [((40960, 1600), BF16), ((8, 1600, 3200), BF16), ((8,), I32)], {}, 3),
    ("grouped_matmul_wo", _grouped_matmul,
     [((40960, 3200), BF16), ((8, 3200, 1600), BF16), ((8,), I32)], {}, 3),
    # OLMoE-1B-7B: 64 groups, 131072 routed rows + 64 blocks of budget
    ("grouped_matmul_olmoe_wi", _grouped_matmul,
     [((139264, 2048), BF16), ((64, 2048, 1024), BF16), ((64,), I32)], {}, 3),
    ("grouped_matmul_olmoe_wo", _grouped_matmul,
     [((139264, 1024), BF16), ((64, 1024, 2048), BF16), ((64,), I32)], {}, 3),
    # the same with the d_model-wide rows row-tiled on their way in and out
    ("grouped_matmul_olmoe_wi_rows_tiled", _grouped_matmul,
     [((139264, 16, 128), BF16), ((64, 2048, 1024), BF16), ((64,), I32)],
     {}, 3),
    ("grouped_matmul_olmoe_wo_out_tiled", lambda: _grouped_matmul(True),
     [((139264, 1024), BF16), ((64, 1024, 2048), BF16), ((64,), I32)], {}, 3),
    # Mixtral under `grouped` (6 x 4096 tokens, 2 a token): K and M are
    # tiled, and a row-tiled side is tiled in whole native tiles
    ("grouped_matmul_mixtral_wi_rows_tiled", _grouped_matmul,
     [((50176, 32, 128), BF16), ((8, 4096, 14336), BF16), ((8,), I32)],
     {}, 3),
    ("grouped_matmul_mixtral_wo_out_tiled", lambda: _grouped_matmul(True),
     [((50176, 14336), BF16), ((8, 14336, 4096), BF16), ((8,), I32)], {}, 3),
    # JoyAI-LLM-Flash's share: 32 held experts of 768, a budget of 24,704
    # rows (1.25 x the expected 16,384 + a block an expert + the zero
    # block) whose dead blocks the kernels skip; rows tiled in and out
    ("grouped_matmul_share_wi_rows_tiled",
     lambda: _grouped_matmul(False, True),
     [((24704, 16, 128), BF16), ((32, 2048, 768), BF16), ((32,), I32)],
     {}, 3),
    ("grouped_matmul_share_wo_out_tiled", lambda: _grouped_matmul(True, True),
     [((24704, 768), BF16), ((32, 768, 2048), BF16), ((32,), I32)], {}, 3),
    ("row_gather_sum_share_weighted", _row_gather_sum,
     [((24704, 16, 128), BF16), ((16384, 8), I32), ((16384, 8), F32)], {}, 1),
    # the same handed the live pairs (an eighth of them in the cell)
    ("row_gather_sum_share_live_weighted", _row_gather_sum_live,
     [((24704, 16, 128), BF16), ((16384, 8), I32), ((16384, 8), F32)], {}, 1),
    ("row_gather_sum_share_live_plain", _row_gather_sum_live,
     [((24704, 16, 128), BF16), ((16384, 8), I32)], {}, 1),
    # a token's 8 of the 139264 rows fetched and summed, OLMoE's combine
    ("row_gather_sum_olmoe_weighted", _row_gather_sum,
     [((139264, 16, 128), BF16), ((16384, 8), I32), ((16384, 8), F32)], {}, 1),
    ("row_gather_sum_olmoe_plain", _row_gather_sum,
     [((139264, 16, 128), BF16), ((16384, 8), I32)], {}, 1),
    ("row_gather_sum_from_plain_rows", _row_gather_sum,
     [((139264, 2048), BF16), ((16384, 8), I32)], {}, 1),
    # Olmo-Hybrid-7B's linear layers: 2 x 8192 tokens, 30 heads of 96 / 192,
    # the forward kernel and the backward kernel
    ("delta_rule_olmo_hybrid", _delta_rule,
     [((2, 8192, 30, 96), BF16)] * 2 + [((2, 8192, 30, 192), BF16)]
     + [((2, 8192, 30), F32)] * 2, {}, 2),
    # Ling-3.0-flash's KDA layers: 2 x 8192 tokens, 32 heads of 128 / 128, a
    # float32 log decay a channel: the forward kernel and the backward kernel
    ("kda_ling_flash", _kda,
     [((2, 8192, 32, 128), BF16)] * 3 + [((2, 8192, 32, 128), F32)]
     + [((2, 8192, 32), F32)], {}, 2),
    # Solar-Open2's KDA layers: 1 x 16384 tokens, 64 heads of 128 / 128 under
    # a gate with no lower bound: the kernels in the form that is exact for
    # any g <= 0 (the pairs' decays cut by halves, ops/kda.py)
    ("kda_solar_open_exact", lambda: _kda(exact=True),
     [((1, 16384, 64, 128), BF16)] * 3 + [((1, 16384, 64, 128), F32)]
     + [((1, 16384, 64), F32)], {}, 2),
    # Nemotron-3-Nano's Mamba-2 layers: 2 x 8192 tokens, 64 heads of 64, a
    # 128-wide state, 8 groups: the forward kernel and the backward kernel
    ("ssd_nemotron_h", _ssd,
     [((2, 8192, 64, 64), BF16), ((2, 8192, 64), F32), ((64,), F32)]
     + [((2, 8192, 8, 128), BF16)] * 2 + [((64,), F32)], {}, 2),
    # Granite-4.0-H-Small's: 128 heads of 64 in ONE group, sixteen tiles of
    # 8 heads a (batch, chunk), at both chunks the configuration names
    ("ssd_granite_one_group", _ssd,
     [((2, 8192, 128, 64), BF16), ((2, 8192, 128), F32), ((128,), F32)]
     + [((2, 8192, 1, 128), BF16)] * 2 + [((128,), F32)], {}, 2),
    ("ssd_granite_one_group_chunk_256", lambda: _ssd(256),
     [((2, 8192, 128, 64), BF16), ((2, 8192, 128), F32), ((128,), F32)]
     + [((2, 8192, 1, 128), BF16)] * 2 + [((128,), F32)], {}, 2),
    # its share of the experts: ten rows of 4,096 a token fetched and
    # summed out of the 26,880 rows set aside for 9 of 72 experts, and the
    # grouped GEMMs over them
    ("row_gather_sum_granite_weighted", _row_gather_sum,
     [((26880, 32, 128), BF16), ((16384, 10), I32), ((16384, 10), F32)],
     {}, 1),
    ("row_gather_sum_granite_plain", _row_gather_sum,
     [((26880, 32, 128), BF16), ((16384, 10), I32)], {}, 1),
    # (grid steps of 256 tokens: their indices are no whole SMEM tiles, and
    # one SMEM block spans two steps)
    ("row_gather_sum_granite_live_weighted", _row_gather_sum_live,
     [((26880, 32, 128), BF16), ((16384, 10), I32), ((16384, 10), F32)],
     {}, 1),
    ("row_gather_sum_granite_live_plain", _row_gather_sum_live,
     [((26880, 32, 128), BF16), ((16384, 10), I32)], {}, 1),
    # Nemotron-3-Nano's and Ling-3.0-flash's shares: rows of 2,688 and 2,560
    # (21 and 20 lane tiles, which Mosaic refuses to slice) padded to 24 at
    # the kernel's door, out of budgets of 17,536 and 14,464 rows
    ("row_gather_sum_nemotron_padded_live_weighted",
     lambda: _row_gather_sum_padded(True),
     [((17536, 2688), BF16), ((16384, 6), I32), ((16384, 6), F32)], {}, 1),
    ("row_gather_sum_ling_padded_live_plain",
     lambda: _row_gather_sum_padded(True),
     [((14464, 2560), BF16), ((16384, 8), I32)], {}, 1),
    ("row_gather_sum_nemotron_padded_every_pair",
     lambda: _row_gather_sum_padded(False),
     [((17536, 2688), BF16), ((16384, 6), I32)], {}, 1),
    ("grouped_matmul_granite_wi_rows_tiled",
     lambda: _grouped_matmul(False, True),
     [((26880, 32, 128), BF16), ((9, 4096, 768), BF16), ((9,), I32)],
     {}, 3),
    ("grouped_matmul_granite_wo_out_tiled",
     lambda: _grouped_matmul(True, True),
     [((26880, 768), BF16), ((9, 768, 4096), BF16), ((9,), I32)], {}, 3),
    # the short convolutions at the cells' 2 x 8192 tokens, the forward
    # kernel and the backward kernel: Nemotron's 6,144 channels from column
    # 4,096 of in_proj's 10,304 with a bias, x | B | C written apart; the
    # hybrid's 384 of each of 30 heads' 576, q and k L2-normalised
    ("short_conv_nemotron_h",
     lambda: _short_conv(4096, (4096, 1024, 1024), None, True),
     [((2, 8192, 10304), BF16), ((4, 6144), BF16), ((6144,), BF16)], {}, 2),
    # Granite's 8,448 channels from column 8,192 of in_proj's 16,768
    ("short_conv_granite",
     lambda: _short_conv(8192, (8192, 128, 128), None, True),
     [((2, 8192, 16768), BF16), ((4, 8448), BF16), ((8448,), BF16)], {}, 2),
    ("short_conv_olmo_hybrid",
     lambda: _short_conv(0, (96, 96, 192), (96 ** -0.5, 1.0, None), False),
     [((2, 8192, 30, 576), BF16), ((4, 30, 384), BF16)], {}, 2),
    # LFM2-8B-A1B's cell, 4 x 8192 tokens: 32 query heads over 8 key/value
    # heads of 64 (half a lane group wide, eight kv blocks); its share's
    # grouped GEMMs at the expert width 1,792 (14 lane tiles) over a budget
    # of 42,112 rows for 8 of 32 experts, rows tiled in and out; a token's
    # 4 live rows of 2,048 fetched and summed
    ("flash_lfm2_gqa_32_over_8_of_64", lambda: _flash(1024),
     [((4, 8192, 32, 64), BF16)] + [((4, 8192, 8, 64), BF16)] * 2, {}, 2),
    ("grouped_matmul_lfm2_wi_rows_tiled",
     lambda: _grouped_matmul(False, True),
     [((42112, 16, 128), BF16), ((8, 2048, 1792), BF16), ((8,), I32)],
     {}, 3),
    ("grouped_matmul_lfm2_wo_out_tiled",
     lambda: _grouped_matmul(True, True),
     [((42112, 1792), BF16), ((8, 1792, 2048), BF16), ((8,), I32)], {}, 3),
    # Nemotron-3-Nano's share: 16 held ungated experts of 1,856 (14.5 lane
    # tiles: a block's full extent) at d_model 2,688, plain rows out of a
    # budget of 17,536.  wi's [2688, 1856] strip, as LFM2's wo [1792, 2048]
    # above, stays whole-K under a scoped-VMEM limit the call asks for
    # (`plan_tiles`), which Mosaic holds it to here
    ("grouped_matmul_nemotron_wi", lambda: _grouped_matmul(False, True),
     [((17536, 2688), BF16), ((16, 2688, 1856), BF16), ((16,), I32)], {}, 3),
    ("grouped_matmul_nemotron_wo", lambda: _grouped_matmul(False, True),
     [((17536, 1856), BF16), ((16, 1856, 2688), BF16), ((16,), I32)], {}, 3),
    ("row_gather_sum_lfm2_live_weighted", _row_gather_sum_live,
     [((42112, 16, 128), BF16), ((32768, 4), I32), ((32768, 4), F32)],
     {}, 1),
    # its gated short convolution's core: B | C | z of one [4, 8192, 6144]
    # projection, 3 taps, the forward kernel and the backward kernel
    ("gated_conv_lfm2", _gated_conv,
     [((4, 8192, 6144), BF16), ((3, 2048), BF16)], {}, 2),
    # Mellum2-12B-A2.5B's cell, 1 x 32768 tokens, 32 query heads over 4
    # key/value heads of 128: the full layers' kernels (32 kv blocks, the
    # one-pass backward's dq scratch 32 MiB), the banded ones under a window
    # of 1,024 keys at blocks of 1,024 and of 512 (two and three steps a
    # block's inner grid) with both backward paths; its share's grouped
    # GEMMs at the expert width 896 (7 lane tiles) and d_model 2,304 (18
    # lane tiles: plain rows) over a budget of 84,096 rows for 16 of 64
    # experts; a token's 8 rows of 2,304 padded to 24 lane tiles at the
    # fetch-and-sum kernel's door
    ("flash_mellum_full_32k", lambda: _flash(1024), MELLUM_QKV, {}, 2),
    ("flash_mellum_band_1024", lambda: _flash(1024, 1024), MELLUM_QKV, {}, 2),
    ("flash_mellum_band_512", lambda: _flash(512, 1024), MELLUM_QKV, {}, 2),
    ("flash_mellum_band_split", lambda: _flash_band_split(1024, 1024),
     [((1, 32, 32768, 128), BF16)] + [((1, 4, 32768, 128), BF16)] * 2
     + [((1, 32, 32768, 128), BF16)], {}, 3),
    ("grouped_matmul_mellum_wi", lambda: _grouped_matmul(False, True),
     [((84096, 2304), BF16), ((16, 2304, 896), BF16), ((16,), I32)], {}, 3),
    ("grouped_matmul_mellum_wo", lambda: _grouped_matmul(False, True),
     [((84096, 896), BF16), ((16, 896, 2304), BF16), ((16,), I32)], {}, 3),
    ("row_gather_sum_mellum_padded_live_weighted",
     lambda: _row_gather_sum_padded(True),
     [((84096, 2304), BF16), ((32768, 8), I32), ((32768, 8), F32)], {}, 1),
    # Command A+'s cell, 1 x 16384 tokens, 32 query heads over 2 key/value
    # heads of 128 (a group of 16): the full layer's kernels, the banded
    # ones under a window of 4,096 keys at blocks of 1,024 (a band five
    # blocks wide) with both backward paths; its share's grouped GEMMs at 8
    # experts of 4,096 x 4,096 over a budget of 11,392 rows, rows of 32 lane
    # tiles tiled in (wi) and out (wo): forward, dx and dW each way, under
    # the tiles ``test_the_plans_at_4096_by_4096`` holds
    ("flash_command_a_full_16k", lambda: _flash(1024), COMMAND_A_QKV, {}, 2),
    ("flash_command_a_band_4096", lambda: _flash(1024, 4096), COMMAND_A_QKV,
     {}, 2),
    ("flash_command_a_band_split", lambda: _flash_band_split(1024, 4096),
     [((1, 32, 16384, 128), BF16)] + [((1, 2, 16384, 128), BF16)] * 2
     + [((1, 32, 16384, 128), BF16)], {}, 3),
    ("grouped_matmul_command_a_wi_rows_tiled",
     lambda: _grouped_matmul(False, True),
     [((11392, 32, 128), BF16), ((8, 4096, 4096), BF16), ((8,), I32)],
     {}, 3),
    ("grouped_matmul_command_a_wo_out_tiled",
     lambda: _grouped_matmul(True, True),
     [((11392, 4096), BF16), ((8, 4096, 4096), BF16), ((8,), I32)], {}, 3),
    # GLM-5.2's cell: one sequence of 16,384 tokens, 16 heads of 256 / 256
    # under the int8 choice (forward and the ONE-PASS backward: 41.5 MiB of
    # VMEM by its own count, dq over the sequence resident; twice the tokens
    # keep the split pair: forward, dq, dk / dv); the choice itself, 32
    # indexer heads of 128, 2,048 keys a query; the share's grouped GEMMs at
    # 8 experts of 6,144 x 2,048 over a budget of 7,296 rows (1.5 x the
    # expected 4,096 + a block an expert + the zero block)
    ("sparse_flash_glm_16k", lambda: _sparse_flash(512),
     [((1, 16384, 16, 256), BF16)] * 3 + [((1, 16384, 16384), jnp.int8)],
     {}, 2),
    ("sparse_flash_glm_32k_split", lambda: _sparse_flash(512),
     [((1, 32768, 2, 256), BF16)] * 3 + [((1, 32768, 32768), jnp.int8)],
     {}, 3),
    ("index_choice_glm_16k", lambda: _index_choice(2048, 128),
     [((1, 16384, 32, 128), BF16), ((1, 16384, 128), BF16),
      ((1, 16384, 32), F32)], {}, 0),
    ("grouped_matmul_glm_wi_rows_tiled",
     lambda: _grouped_matmul(False, True),
     [((7296, 48, 128), BF16), ((8, 6144, 2048), BF16), ((8,), I32)],
     {}, 3),
    ("grouped_matmul_glm_wo_out_tiled",
     lambda: _grouped_matmul(True, True),
     [((7296, 2048), BF16), ((8, 2048, 6144), BF16), ((8,), I32)], {}, 3),
    ("quantize_dequantize", _quant_roundtrip, [LEAF], {}, 2),
    ("q8_adam", lambda: _adam_update("q8_adam"), [LEAF, LEAF], {}, 1),
    ("q4_adam", lambda: _adam_update("q4_adam"), [LEAF, LEAF], {}, 1),
    ("embed_gather", lambda: _embed("_gather"),
     [CACHE, ((4096,), I32)], {"mode": "pallas"}, 1),
    ("embed_scatter", lambda: _embed("_scatter"),
     [CACHE, ((4096,), I32), ((4096, 128), F32)], {"mode": "pallas"}, 1),
]


@pytest.mark.parametrize(
    "build,avals,static,kernels",
    [pytest.param(*case[1:], id=case[0]) for case in CASES],
)
def test_kernel_compiles_for_v5e(compile_for_chip, build, avals, static,
                                 kernels):
    text = compile_for_chip(build(), *avals, **static)
    assert text.count('custom_call_target="tpu_custom_call"') == kernels


def test_the_plans_at_4096_by_4096():
    """What the grouped GEMMs' plans choose for Command A+'s experts (bf16,
    rows of 32 lane tiles row-tiled): INTO the expert width whole-K strips
    of 512 columns, resident under the default limit; OUT OF it a tiled
    output's narrowest block is 2,048 columns, whose whole-K strip counts
    39,845,888 bytes (38.0 MiB): resident too, under a limit the call asks
    for, since ``_VMEM_CAP`` is 40 MiB (PR 61).  Under 32 MiB K was split in
    four (``tk`` 1024) and every row block of 128 rows streamed its expert's
    16 MiB strip again, ``split_k:3/6``.  Each weight gradient is four tiles
    of ``[2048, 2048]`` asking 36,700,160 where 32 MiB held eight
    (``[2048, 1024]`` / ``[1024, 2048]``, 19,136,512)."""
    from dlrover_tpu.ops import grouped_matmul as gmm

    into = gmm.plan_tiles(4096, 4096, True, False, BF16)
    out_of = gmm.plan_tiles(4096, 4096, False, True, BF16)
    assert (into.tk, into.tm, into.vmem_limit_bytes) == (4096, 512, None)
    assert (out_of.tk, out_of.tm, out_of.vmem_limit_bytes) == (
        4096, 2048, 39845888
    )
    assert out_of.vmem_limit_bytes <= gmm._VMEM_CAP == 40 * 2**20
    assert gmm.expert_strips(4096, 4096, True, True, BF16) == "resident"
    dw_into = gmm.plan_dw_tiles(4096, 4096, True, False, BF16)
    dw_out_of = gmm.plan_dw_tiles(4096, 4096, False, True, BF16)
    assert (dw_into.tk, dw_into.tm) == (2048, 2048)
    assert (dw_out_of.tk, dw_out_of.tm) == (2048, 2048)
    assert dw_into.vmem_limit_bytes == dw_out_of.vmem_limit_bytes == 36700160
    assert gmm.expert_dw_tiles(4096, 4096, True, BF16) == (
        "into:2x2 out_of:2x2"
    )


def test_chip_smoke_refuses_cpu(cpu_child_env):
    """No accelerator: a clear line, a non-zero exit, no result line."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=cpu_child_env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert "platform is 'cpu', not 'tpu'" in out.stdout + out.stderr
    assert '"ok": true' not in out.stdout


def test_compile_cache_has_one_placement_rule(tmp_path, monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` set: jax's own handling stands and no
    other directory is named.  Unset: ``<checkout>/.jax_cache``."""
    from dlrover_tpu.runtime import compile_cache

    writes = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: writes.append(name)
    )
    monkeypatch.setattr(compile_cache, "_listening", True)
    # In two parts: the tree keeps one literal site of this option's name,
    # the one in compile_cache.enable().
    cache_option = "jax_compilation_" + "cache_dir"

    monkeypatch.setattr(compile_cache, "_enabled_dir", None)
    monkeypatch.setenv(compile_cache.ENV_JAX_CACHE_DIR, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert cache_option not in writes

    monkeypatch.setattr(compile_cache, "_enabled_dir", None)
    monkeypatch.delenv(compile_cache.ENV_JAX_CACHE_DIR)
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.default_cache_dir() == fixed
    assert compile_cache.enable() == fixed
    assert cache_option in writes
