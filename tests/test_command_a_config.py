"""What Command A+'s configuration refuses and counts, on the CPU: the new
fields' bad values, the defaults that leave every other model as it was
(GPT-2's tree keeps its LayerNorm biases), the published order of kinds, the
published widths' parameter count against the benchmark file's arithmetic
to the parameter (the cut's 2,090,860,544 and the whole model's
218,254,802,944), the benchmark file against the catalog's config, the
cache key, the master's gauges for the new facts."""

import dataclasses
import json
import os

import jax
import pytest

from dlrover_tpu.models import command_a, layers
from dlrover_tpu.models.command_a import command_a_config
from dlrover_tpu.models.transformer import (
    FULL_ATTENTION,
    SLIDING_ATTENTION,
    TransformerConfig,
    TransformerLM,
    kernel_facts,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "command-a-plus-05-2026"
SMALL = dict(
    vocab_size=128, num_layers=4, d_model=32, num_heads=4, num_kv_heads=2,
    head_dim=8, d_ff=48, max_seq_len=32, moe_d_ff=16, num_experts=16,
    top_k=4, experts_held=4, shared_experts_held=1, sliding_window=8,
)
REDUCED = ["layer_types", "num_attention_heads", "num_experts",
           "num_hidden_layers", "num_key_value_heads", "num_shared_experts",
           "vocab_size"]


def config(**overrides):
    return command_a_config(**{**SMALL, **overrides})


def cell_file():
    with open(os.path.join(REPO, "benchmark", "configs", f"{NAME}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("overrides,message", [
    (dict(norm_placement="post"), "parallel_block is the pre-norm two-branch"),
    (dict(mtp_depth=1, layer_pattern=(), full_rope=True),
     "parallel_block is the pre-norm two-branch"),
    (dict(layer_pattern=("sliding_attention", "experts"), num_layers=2),
     "a one-branch kind has no second branch"),
    (dict(norm="rmsnorm"), "norm_use_bias=False leaves a LayerNorm's bias"),
    (dict(layer_pattern=()), "full_rope=False takes the rotation off"),
    (dict(layer_pattern=("full_attention",)), "BESIDE sliding_attention"),
    (dict(position="none"), "full_rope=False takes the rotation off"),
    (dict(rope_scaling="yarn", rope_scaling_factor=4.0,
          rope_original_max_position=16, rope_beta_fast=32.0,
          rope_beta_slow=1.0, rope_attention_factor=1.1),
     "rope_scaling is the full layers' rotation"),
    (dict(shared_expert_combine="mean"), "must be 'sum' or 'average'"),
    (dict(num_shared_experts=0, shared_experts_held=0),
     "must be 'sum' or 'average'"),
    (dict(shared_experts_held=3), "must divide num_shared_experts 4"),
    (dict(shared_experts_held=-1), "must divide num_shared_experts 4"),
    (dict(shared_expert_d_ff=64), "no shared_expert_d_ff"),
    (dict(sliding_window=0), "a sliding_attention layer needs sliding_window"),
    (dict(decode=True), "decode=True with a sliding_attention layer"),
    (dict(num_layers=6), "no whole number of periods of the 4-layer pattern"),
])
def test_bad_values_of_the_new_fields_raise(overrides, message):
    with pytest.raises(ValueError, match=message):
        config(**overrides)


def test_the_defaults_leave_every_other_model_as_it_was():
    from dlrover_tpu.models import attention, moe
    from dlrover_tpu.models.gpt2 import gpt2_config

    plain = TransformerConfig()
    assert not plain.parallel_block and plain.norm_use_bias
    assert plain.full_rope and plain.shared_expert_combine == "sum"
    assert plain.shared_experts_held == 0 and plain.norms_per_layer == 2
    assert plain.shared_expert_scale == 1.0
    assert plain.rotation() == layers.Rotation(10000.0)
    assert attention._by_kind(plain, FULL_ATTENTION) == {}
    facts = kernel_facts(plain, 64)
    assert (facts["block_form"], facts["block_norms"]) == ("serial", 2)
    # a summed shared expert is the one MLP it was, under no scale
    summed = TransformerConfig(
        num_experts=8, moe_dispatch="grouped", num_shared_experts=2,
        moe_d_ff=16,
    )
    layer = moe.from_config(summed)
    assert (layer.shared_d_ff, layer.shared_scale) == (32, 1.0)
    assert summed.resolved_shared_held == 2
    # GPT-2's LayerNorms keep their biases: its tree is the one it was
    gpt2 = gpt2_config(
        vocab_size=64, num_layers=2, d_model=32, num_heads=2, max_seq_len=16,
    )
    tree = jax.eval_shape(
        TransformerLM(gpt2).init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 8), "int32"),
    )["params"]
    assert sorted(tree["blocks"]) == ["attn", "ln_attn", "ln_mlp", "mlp"]
    for norm in (tree["blocks"]["ln_attn"], tree["blocks"]["ln_mlp"],
                 tree["ln_final"]):
        assert sorted(norm) == ["bias", "scale"]


def test_the_published_order_is_three_sliding_to_one_position_free_full():
    kinds = command_a.LAYER_TYPES
    assert len(kinds) == 32 and kinds.count(SLIDING_ATTENTION) == 24
    assert [i for i, k in enumerate(kinds) if k == FULL_ATTENTION] == list(
        range(3, 32, 4)
    )
    cfg = command_a_config()
    assert cfg.num_layers == 32 and cfg.num_scan_units == 8
    assert tuple(cfg.layer_kind(i) for i in range(32)) == kinds
    assert (cfg.num_sliding_layers, cfg.num_full_layers) == (24, 8)
    assert cfg.rotation(SLIDING_ATTENTION) == layers.Rotation(50000.0)
    assert cfg.rotation(FULL_ATTENTION) is None
    assert cfg.parallel_block and cfg.norms_per_layer == 1
    assert (cfg.norm, cfg.norm_use_bias, cfg.norm_eps) == (
        "layernorm", False, 1e-5
    )
    assert cfg.shared_expert_scale == 0.25
    assert (cfg.router_scoring, cfg.router_bias, cfg.moe_aux_weight) == (
        "sigmoid", False, 0.0
    )
    from dlrover_tpu.models.transformer import slot_name

    assert [slot_name(i, k) for i, k in enumerate(cfg.layer_pattern)] == [
        "sliding_0", "sliding_1", "sliding_2", "full_3"
    ]


def test_the_published_widths_count_what_the_file_counts():
    """The benchmark file's arithmetic, to the parameter."""
    from benchmark import build

    file = cell_file()
    cut = build.transformer_config(build.model_group(file), 16384)
    attn = 2 * 4096 * 32 * 128 + 2 * 4096 * 2 * 128
    expert, router = 3 * 4096 * 4096, 4096 * 128
    assert attn == 35_651_584 and expert == 50_331_648 and router == 524_288
    layer = attn + expert + 8 * expert + router
    assert layer == 489_160_704 and 4 * layer == 1_956_642_816
    table = 32768 * 4096
    assert table == 134_217_728
    assert cut.num_params() == 4 * layer + table == 2_090_860_544 == (
        file["num_params"]
    )
    assert "2,090,860,544" in file["reduced"]["num_hidden_layers"]["why"]
    # the whole model: 32 layers, 128 heads over 8, 128 + 4 experts, the
    # whole tied table: the name's 218B, and 25B a token
    whole_attn = 2 * 4096 * 16384 + 2 * 4096 * 1024
    assert whole_attn == 142_606_336
    whole = command_a_config().num_params()
    assert whole == 32 * (whole_attn + 132 * expert + router) + (
        262144 * 4096
    ) == 218_254_802_944
    active = 32 * (whole_attn + 12 * expert) + 262144 * 4096
    assert 24.9e9 < active < 25.0e9
    # the published prefix_dense_* keys build nothing: no layer is dense
    assert cut.first_k_dense == 0 and cut.d_ff == 16384
    assert cut.num_params() == dataclasses.replace(
        cut, d_ff=4096
    ).num_params()
    tree = jax.eval_shape(
        TransformerLM(config()).init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 8), "int32"),
    )["params"]
    assert not [k for k in tree if k.startswith("dense_")]
    assert "mlp" not in tree["blocks"]["full_3"]


def test_the_cells_kernels_at_16k_keep_every_strip_resident():
    """What the ``compile`` event says of the cell's kernels at 1 x 16,384
    tokens.  Since PR 61 the three grouped GEMMs OUT OF the expert width
    keep their whole-K strip resident under a limit they ask for (40 MiB
    holds its 38.0; ``split_k:3/6`` under 32), and each weight gradient is
    four tiles (eight).  ``tests/benchmark_suite``'s pinned reading of the
    same facts still holds PR 58's two strings (only a ``benchmark`` issue
    may re-pin them) and stops there, so the facts after them are held
    here: the parallel block's one norm and the two attention grids."""
    from benchmark import build

    cut = build.transformer_config(build.model_group(cell_file()), 16384)
    facts = kernel_facts(cut, 16384)
    assert facts["row_moves"] == "kernel_live"
    assert facts["gmm_strips"] == "resident"
    assert facts["gmm_dw_tiles"] == "into:2x2 out_of:2x2"
    assert (facts["block_form"], facts["block_norms"]) == ("parallel", 1)
    # a band five blocks of 1,024 wide: 70 live steps of a grid of 80
    band = facts["flash_blocks"]["sliding_attention"]
    assert (band["live"], band["grid"], band["live_share"]) == (70, 80, 0.875)
    assert facts["flash_blocks"]["full_attention"]["live"] == 136


def test_the_file_holds_every_key_of_the_catalog_s_config():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f) if r["name"] == NAME]
    file = cell_file()
    assert file["source"] == row["source_url"]
    reduced = file["reduced"]
    assert sorted(reduced) == REDUCED
    for key, published in row["config"].items():
        assert key in file, key
        if key in reduced:
            assert reduced[key]["published"] == published, key
            assert reduced[key]["run"] == file[key], key
            assert reduced[key]["why"]
        else:
            assert file[key] == published, key      # the nested group too
    # no width is cut
    for key in ("hidden_size", "intermediate_size", "head_dim",
                "num_experts_per_tok", "sliding_window", "rope_theta",
                "layer_norm_eps", "prefix_dense_intermediate_size"):
        assert key not in reduced
    assert file["num_attention_heads"] // file["num_key_value_heads"] == (
        128 // 8
    )
    assert tuple(file["layer_types"]) == command_a.LAYER_TYPES[:4]
    assert (file["router_experts"], file["shared_experts_published"]) == (
        128, 4
    )
    manifest = {
        c["name"]: c for c in json.load(
            open(os.path.join(REPO, "BENCHMARK.json"))
        )["configs"]
    }[NAME]
    assert sorted(manifest["reduced"]) == REDUCED
    assert manifest["source"] == row["source_url"]


def test_to_program_maps_to_fields_that_exist():
    from benchmark import build

    file = cell_file()
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    for field, key in file["to_program"].items():
        assert field in fields, field
        assert key in file, key
    assert set(file["program"]) <= fields
    for name in ("intermediate_size", "shared_expert_combination_strategy",
                 "full_attention", "sliding_window", "rotation", "router",
                 "norm", "embed_init_std", "attn_init_score_std"):
        assert name in file["assumed"], name
    deployment = file["deployment"]
    for said in ("sixteen chips share each layer", "go over 4", "over 16",
                 "the vocabulary over 8", "eight pipeline stages"):
        assert said in deployment, said
    assert "vision tower" in file["left_out"]
    cfg = build.transformer_config(build.model_group(file), 16384)
    want = command_a_config(
        num_layers=4, num_heads=32, num_kv_heads=2, experts_held=8,
        shared_experts_held=1, vocab_size=32768,
    )
    for field in ("d_model", "num_heads", "resolved_kv_heads",
                  "resolved_head_dim", "moe_d_ff", "num_experts",
                  "experts_held", "first_expert", "top_k", "norm_topk_prob",
                  "norm_eps", "norm", "norm_use_bias", "rope_theta",
                  "full_rope", "tie_embeddings", "use_bias", "layer_pattern",
                  "sliding_window", "num_shared_experts",
                  "shared_experts_held", "shared_expert_combine",
                  "parallel_block", "router_scoring", "router_bias",
                  "moe_aux_weight", "position", "activation", "moe_dispatch",
                  "logit_scale", "qk_norm", "d_ff", "first_k_dense"):
        assert getattr(cfg, field) == getattr(want, field), field
    assert (cfg.remat, cfg.attention_impl) == ("flash_only", "flash")
    assert cfg.shared_expert_scale == 0.25 and cfg.resolved_shared_d_ff == 4096
    facts = kernel_facts(cfg, 16384)
    assert (facts["block_form"], facts["block_norms"]) == ("parallel", 1)
    # a window of four blocks: 16 diagonal and 12 lower-edge blocks in
    # strips, 42 interior: 56 blocks' live pairs of 59.5 worked (of 64)
    band = facts["flash_blocks"]["sliding_attention"]
    assert (band["strip"], band["lower_strip"]) == (256, 256)
    assert band["tile_live_share"] == pytest.approx(56 / 59.5, abs=1e-4)
    assert "lower_strip" not in facts["flash_blocks"]["full_attention"]


def test_cache_key_covers_the_new_fields():
    from dlrover_tpu.runtime.compile_cache import train_cache_key

    def key(**kw):
        return train_cache_key(
            dataclasses.replace(config(), **kw), (1, 1, 1, 1, 1, 1),
            global_batch_size=8, seq_len=32,
        )

    keys = {
        key(), key(parallel_block=False), key(full_rope=True),
        key(shared_expert_combine="sum"), key(shared_experts_held=2),
        key(norm_use_bias=True),
    }
    assert len(keys) == 6


def test_the_master_renders_the_new_facts_as_gauges():
    from dlrover_tpu.master.speed_monitor import SpeedMonitor
    from dlrover_tpu.master.timeline import JobTimeline

    monitor = SpeedMonitor()
    monitor.record_health(
        "attn", 0, step=5, full_layers=1, sliding_layers=3, window=4096,
        full_rotation="none", sliding_rotation="rope", rotated_layers=3,
        full_score_bound=12.5, sliding_score_bound=7.5, score_bound=12.5,
    )
    monitor.record_moe(
        0, step=5, load="[0.5, 0.5]", experts=128, held=8, top_k=8,
        shared_held=1, shared_published=4, shared_scale=0.25,
    )
    attn, moe = monitor.health_ledger("attn"), monitor.health_ledger("moe")
    assert attn["rotated_layers"] == 3.0
    assert attn["full_layers"] + attn["sliding_layers"] == 4
    assert (moe["shared_held"], moe["shared_published"]) == (1.0, 4.0)
    assert moe["shared_scale"] == 0.25
    text = JobTimeline().render_metrics(speed_monitor=monitor)
    for name, value in (
        ("dlrover_attn_rotated_layers", "3"),
        ("dlrover_moe_shared_experts_held", "1"),
        ("dlrover_moe_shared_experts", "4"),
        ("dlrover_moe_shared_expert_scale", "0.25"),
    ):
        assert f"# TYPE {name} gauge" in text
        assert any(
            line.startswith(name + " ") and line.split()[1].startswith(value)
            for line in text.splitlines()
        ), name
    # an older trainer's events say none of it: no shared expert, summed
    older = SpeedMonitor()
    older.record_moe(0, step=1, load="[1.0]", experts=8, top_k=2)
    older.record_health("attn", 0, step=1, full_layers=2, sliding_layers=6)
    assert older.health_ledger("moe")["shared_scale"] == 1.0
    assert older.health_ledger("moe")["shared_held"] == 0.0
    assert older.health_ledger("attn")["rotated_layers"] == 0.0
