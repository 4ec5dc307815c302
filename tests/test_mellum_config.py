"""What Mellum2-12B-A2.5B's configuration refuses and counts, on the CPU:
the new kind and the new fields' bad values, the published order of kinds,
the published widths' parameter count against the benchmark file's
arithmetic to the parameter, YaRN's range and factor out of the five
published numbers, the benchmark file against the catalog's config, the
cache key, the master's gauges for the ``attn`` event."""

import dataclasses
import json
import math
import os

import pytest

from dlrover_tpu.models import layers, mellum
from dlrover_tpu.models.mellum import mellum_config
from dlrover_tpu.models.transformer import (
    FULL_ATTENTION,
    SLIDING_ATTENTION,
    TWO_BRANCH_KINDS,
    TransformerConfig,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SMALL = dict(
    vocab_size=128, num_layers=4, d_model=32, num_heads=4, num_kv_heads=2,
    head_dim=8, d_ff=48, max_seq_len=32, moe_d_ff=16, experts_held=16,
    sliding_window=8,
)


def config(**overrides):
    return mellum_config(**{**SMALL, **overrides})


def cell_file():
    with open(os.path.join(
        REPO, "benchmark", "configs", "mellum2-12b-a2.5b.json"
    )) as f:
        return json.load(f)


@pytest.mark.parametrize("overrides,message", [
    (dict(sliding_window=0), "a sliding_attention layer needs sliding_window"),
    (dict(decode=True), "decode=True with a sliding_attention layer"),
    (dict(attention_impl="ring"), "latent attention and ring attention know"),
    (dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
          v_head_dim=8), "latent attention and ring attention know"),
    (dict(rope_scaling="ntk"), "rope scaling must be '' or 'yarn'"),
    (dict(rope_scaling_factor=0.0), "yarn needs its five numbers"),
    (dict(rope_original_max_position=0), "yarn needs its five numbers"),
    (dict(rope_beta_fast=0.0), "yarn needs its five numbers"),
    (dict(rope_beta_slow=0.0), "yarn needs its five numbers"),
    (dict(rope_attention_factor=0.0), "yarn needs its five numbers"),
    (dict(num_layers=6), "no whole number of periods of the 4-layer pattern"),
    (dict(layer_pattern=("sliding_attention", "sliding"), num_layers=2),
     "layer_pattern kinds must be among"),
])
def test_bad_values_of_the_new_fields_raise(overrides, message):
    with pytest.raises(ValueError, match=message):
        config(**overrides)


def test_the_defaults_leave_every_other_model_as_it_was():
    from dlrover_tpu.models import attention

    plain = TransformerConfig()
    assert plain.sliding_window == 0 and plain.rope_scaling == ""
    assert plain.attn_init_score_std == 0.0     # the default initialisers
    assert plain.num_sliding_layers == 0 and plain.num_full_layers == 12
    assert SLIDING_ATTENTION in TWO_BRANCH_KINDS
    assert plain.rotation() == layers.Rotation(10000.0)
    # no window, no rotation, no statistics handed to Attention
    assert attention._by_kind(plain, FULL_ATTENTION) == {}
    yarn_only = TransformerConfig(
        position="rope", rope_scaling="yarn", rope_scaling_factor=4.0,
        rope_original_max_position=64, rope_beta_fast=32.0,
        rope_beta_slow=1.0, rope_attention_factor=1.1,
    )
    assert attention._by_kind(yarn_only, FULL_ATTENTION) == dict(
        window=0, rotation=yarn_only.rotation(), score_stats=False
    )


def test_the_published_order_is_three_sliding_to_one_full():
    kinds = mellum.LAYER_TYPES
    assert len(kinds) == 28 and kinds.count(SLIDING_ATTENTION) == 21
    assert [i for i, k in enumerate(kinds) if k == FULL_ATTENTION] == list(
        range(3, 28, 4)
    )
    cfg = mellum_config()
    assert cfg.num_layers == 28 and cfg.num_scan_units == 7
    assert tuple(cfg.layer_kind(i) for i in range(28)) == kinds
    assert (cfg.num_sliding_layers, cfg.num_full_layers) == (21, 7)
    cut = mellum_config(num_layers=8)
    assert (cut.num_sliding_layers, cut.num_full_layers) == (6, 2)
    from dlrover_tpu.models.transformer import slot_name

    assert [slot_name(i, k) for i, k in enumerate(cfg.layer_pattern)] == [
        "sliding_0", "sliding_1", "sliding_2", "full_3"
    ]


def test_each_kind_has_its_own_rotation():
    cfg = mellum_config()
    assert cfg.rotation(SLIDING_ATTENTION) == layers.Rotation(500000.0)
    full = cfg.rotation()
    assert (full.scaling, full.factor, full.original_len) == (
        "yarn", 16.0, 8192
    )
    # low, high and the factor out of the five published numbers
    assert layers.yarn_range(128, 500000.0, 8192, 32.0, 1.0) == (18, 35)
    assert full.attention_factor == 1.2772588722239782 == (
        0.1 * math.log(16.0) + 1.0
    )
    inv, factor = full.table(128)
    plain, one = cfg.rotation(SLIDING_ATTENTION).table(128)
    ratio = [float(r) for r in inv / plain]
    assert ratio[:19] == [1.0] * 19                  # columns 0-18 kept
    assert all(a > b for a, b in zip(ratio[18:35], ratio[19:36]))
    assert ratio[35:] == pytest.approx([1 / 16] * 29, rel=1e-6)
    assert (factor, one) == (1.2772588722239782, 1.0)


def test_the_published_widths_count_what_the_file_counts():
    """The benchmark file's arithmetic, to the parameter."""
    from benchmark import build

    file = cell_file()
    cut = build.transformer_config(build.model_group(file), 32768)
    attn = 9_437_184 + 2 * 1_179_648 + 9_437_184
    expert, router = 3 * 2304 * 896, 2304 * 64
    assert attn == 21_233_664 == 2 * 2304 * 4096 + 2 * 2304 * 512
    assert expert == 6_193_152 and router == 147_456
    layer = attn + 16 * expert + router
    assert layer == 120_471_552 and 8 * layer == 963_772_416
    head = 24576 * 2304
    assert 2 * head == 113_246_208
    assert cut.num_params() == 8 * layer + 2 * head == 1_077_018_624 == (
        file["num_params"]
    )
    assert "1,077,018,624" in file["reduced"]["num_hidden_layers"]["why"]
    # the whole model: 28 layers of 64 experts, the whole vocabulary
    whole = mellum_config().num_params()
    assert whole == 28 * (attn + 64 * expert + router) + 2 * 98304 * 2304
    assert 12.1e9 < whole < 12.2e9


def test_the_file_holds_every_key_of_the_catalog_s_config():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        (row,) = [
            r for r in map(json.loads, f)
            if r["name"] == "Mellum2-12B-A2.5B-Instruct"
        ]
    file = cell_file()
    assert file["source"] == row["source_url"]
    reduced = file["reduced"]
    assert sorted(reduced) == [
        "layer_types", "mlp_layer_types", "num_experts",
        "num_hidden_layers", "vocab_size",
    ]
    for key, published in row["config"].items():
        assert key in file, key
        if key in reduced:
            assert reduced[key]["published"] == published, key
            assert reduced[key]["run"] == file[key], key
            assert reduced[key]["why"]
        else:
            assert file[key] == published, key      # the nested group too
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "num_experts_per_tok", "sliding_window", "rope_parameters"):
        assert key not in reduced
    assert tuple(file["layer_types"]) == mellum.LAYER_TYPES[:8]
    # the flat keys to_program reads repeat the nested group
    full = file["rope_parameters"]["full_attention"]
    assert full["rope_type"] == file["rope_scaling"] == "yarn"
    assert (
        file["rope_theta"], file["yarn_factor"],
        file["yarn_original_max_position_embeddings"],
        file["yarn_beta_fast"], file["yarn_beta_slow"],
        file["yarn_attention_factor"],
    ) == (
        full["rope_theta"], full["factor"],
        full["original_max_position_embeddings"], full["beta_fast"],
        full["beta_slow"], full["attention_factor"],
    )
    assert file["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": file["rope_theta"]
    }


def test_to_program_maps_to_fields_that_exist():
    from benchmark import build

    file = cell_file()
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    for field, key in file["to_program"].items():
        assert field in fields, field
        assert key in file, key
    assert set(file["program"]) <= fields
    for name in ("norm_placement", "qk_norm", "sliding_window", "rotation",
                 "moe_aux"):
        assert name in file["assumed"], name
    assert "four chips share each layer" in file["deployment"]
    assert "two hosts" in file["deployment"]
    assert "multi-token head" in file["left_out"]
    cfg = build.transformer_config(build.model_group(file), 32768)
    want = mellum_config(num_layers=8, experts_held=16, vocab_size=24576)
    for field in ("d_model", "num_heads", "resolved_kv_heads",
                  "resolved_head_dim", "moe_d_ff", "num_experts",
                  "experts_held", "top_k", "norm_topk_prob", "norm_eps",
                  "rope_theta", "tie_embeddings", "use_bias", "layer_pattern",
                  "sliding_window", "rope_scaling", "rope_scaling_factor",
                  "rope_original_max_position", "rope_beta_fast",
                  "rope_beta_slow", "rope_attention_factor", "moe_aux_form",
                  "moe_aux_weight", "router_scoring", "position", "norm",
                  "activation", "moe_dispatch"):
        assert getattr(cfg, field) == getattr(want, field), field
    assert (cfg.remat, cfg.attention_impl) == ("flash_only", "flash")
    assert cfg.rotation() == want.rotation()


def test_cache_key_covers_the_new_fields():
    from dlrover_tpu.runtime.compile_cache import train_cache_key

    def key(**kw):
        return train_cache_key(
            dataclasses.replace(config(), **kw), (1, 1, 1, 1, 1, 1),
            global_batch_size=8, seq_len=32,
        )

    keys = {
        key(), key(sliding_window=9), key(rope_scaling=""),
        key(rope_scaling_factor=8.0), key(rope_attention_factor=1.0),
        key(rope_beta_fast=16.0), key(rope_beta_slow=2.0),
        key(rope_original_max_position=4096), key(attn_init_score_std=4.0),
    }
    assert len(keys) == 9


def test_the_master_renders_the_attn_event_as_gauges():
    from dlrover_tpu.master.speed_monitor import SpeedMonitor
    from dlrover_tpu.master.timeline import JobTimeline

    monitor = SpeedMonitor()
    monitor.record_health(
        "attn", 0, step=5, full_layers=2, sliding_layers=6, window=1024,
        full_score_bound=12.5, sliding_score_bound=7.5, score_bound=12.5,
    )
    monitor.record_health(
        "attn", 1, step=5, full_layers=2, sliding_layers=6, window=1024,
        full_score_bound=14.5, sliding_score_bound=6.5, score_bound=14.5,
        later_field=1,
    )
    ledger = monitor.health_ledger("attn")
    assert ledger["full_score_bound"] == 14.5
    assert ledger["sliding_score_bound"] == 7.5
    assert ledger["full_layers"] + ledger["sliding_layers"] == 8
    assert ledger["reporters"] == 2
    assert ledger["window"] == 1024 and "state_absmax" not in ledger
    text = JobTimeline().render_metrics(speed_monitor=monitor)
    for name, value in (
        ("dlrover_attn_full_score_bound", "14.5"),
        ("dlrover_attn_sliding_score_bound", "7.5"),
        ("dlrover_attn_window", "1024"),
        ("dlrover_attn_sliding_layers", "6"),
    ):
        assert f"# TYPE {name} gauge" in text
        assert any(
            line.startswith(name + " ") and line.split()[1].startswith(value)
            for line in text.splitlines()
        ), name
    monitor.record_health("attn", 1, step=6, full_score_bound=float("nan"))
    assert monitor.health_ledger("attn")["full_score_bound"] != (
        monitor.health_ledger("attn")["full_score_bound"]
    )
    assert SpeedMonitor().health_ledger("attn")["score_bound"] == 0.0
