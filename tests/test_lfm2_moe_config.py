"""What LFM2-8B-A1B's configuration refuses and counts, on the CPU: the new
kind and the new fields' bad values, the published order of kinds, the
published widths' parameter count against the benchmark file's arithmetic
to the parameter, the benchmark file against the catalog's config, the
cache key, the master's gauges for the ``conv`` event."""

import dataclasses
import json
import os

import pytest

from dlrover_tpu.models import lfm2_moe
from dlrover_tpu.models.lfm2_moe import lfm2_moe_config
from dlrover_tpu.models.transformer import (
    CONV,
    FULL_ATTENTION,
    TWO_BRANCH_KINDS,
    TransformerConfig,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SMALL = dict(
    vocab_size=128, num_layers=5, first_k_dense=1, d_model=32, num_heads=4,
    num_kv_heads=2, d_ff=48, max_seq_len=32, moe_d_ff=16, experts_held=8,
)


def config(**overrides):
    return lfm2_moe_config(**{**SMALL, **overrides})


def cell_file():
    with open(os.path.join(
        REPO, "benchmark", "configs", "lfm2-8b-a1b.json"
    )) as f:
        return json.load(f)


@pytest.mark.parametrize("overrides,message", [
    (dict(conv_kernel=1), "a conv layer needs conv_kernel >= 2 taps"),
    (dict(qk_norm="per_group"), "qk_norm must be False, True"),
    (dict(decode=True), "decode=True with a conv layer"),
    (dict(num_layers=6), "no whole number of periods of the 4-layer pattern"),
    (dict(layer_pattern=("conv", "experts"), num_layers=3),
     "every kind must be a mixer AND an MLP"),
    (dict(layer_pattern=("conv", "convolution"), num_layers=3),
     "layer_pattern kinds must be among"),
    (dict(router_scoring="softmax"), "router_bias corrects a sigmoid"),
])
def test_bad_values_of_the_new_fields_raise(overrides, message):
    with pytest.raises(ValueError, match=message):
        config(**overrides)


def test_the_defaults_leave_every_other_model_as_it_was():
    plain = TransformerConfig()
    assert plain.qk_norm is False and plain.router_norm_eps == 1e-20
    assert plain.num_conv_layers == 0 and CONV in TWO_BRANCH_KINDS
    assert plain.layer_kind(3) == FULL_ATTENTION


def test_the_published_order_is_three_conv_to_one_attention_from_layer_two():
    kinds = lfm2_moe.LAYER_TYPES
    assert len(kinds) == 24 and kinds.count(CONV) == 18
    assert [i for i, k in enumerate(kinds) if k == FULL_ATTENTION] == [
        2, 6, 10, 14, 18, 21
    ]
    assert lfm2_moe.TRUNK_PATTERN == (FULL_ATTENTION, CONV, CONV, CONV)
    # the program's numbering agrees with the published one, layer by
    # layer, for the default (two dense layers, the four whole periods) ...
    cfg = lfm2_moe_config()
    assert cfg.num_layers == 18 and cfg.num_scan_units == 4
    assert tuple(cfg.layer_kind(i) for i in range(18)) == kinds[:18]
    # ... and for the benchmark's cut, which starts at published layer 1
    cut = lfm2_moe_config(num_layers=17, first_k_dense=1)
    assert tuple(cut.layer_kind(i) for i in range(17)) == kinds[1:18]
    assert cut.num_conv_layers == 13 and cut.num_layers_of(FULL_ATTENTION) == 4
    # the last six published layers are no whole period
    assert kinds[18:] == (FULL_ATTENTION, CONV, CONV) * 2


def test_the_published_widths_count_what_the_file_counts():
    """The benchmark file's arithmetic, to the parameter."""
    from benchmark import build

    file = cell_file()
    cut = build.transformer_config(build.model_group(file), 8192)
    conv, attn = 16_783_360, 10_485_888
    expert = 3 * 2048 * 1792
    router = 2048 * 32 + 32
    assert cut._conv_mixer_params() == conv == 12_582_912 + 4_194_304 + 6_144
    assert attn == 4_194_304 + 2 * 1_048_576 + 4_194_304 + 128
    assert expert == 11_010_048 and router == 65_568
    period = attn + 3 * conv + 4 * (8 * expert + router)
    dense = conv + 3 * 2048 * 7168
    assert period == 413_419_776 and dense == 60_823_552
    assert cut.num_params() == 4 * period + dense + 16384 * 2048 == (
        1_748_057_088
    ) == file["num_params"]
    # the whole model: 22 expert layers of 32, two dense, 18 + 6 mixers
    whole = (
        22 * (32 * expert + router) + 18 * conv + 6 * attn
        + 2 * 3 * 2048 * 7168 + 65536 * 2048
    )
    assert 8.3e9 < whole < 8.4e9


def test_the_file_holds_every_key_of_the_catalog_s_config():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        (row,) = [
            r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B"
        ]
    file = cell_file()
    assert file["source"] == row["source_url"]
    reduced = file["reduced"]
    assert sorted(reduced) == [
        "layer_types", "num_dense_layers", "num_experts",
        "num_hidden_layers", "vocab_size",
    ]
    for key, published in row["config"].items():
        assert key in file, key
        if key in reduced:
            assert reduced[key]["published"] == published, key
            assert reduced[key]["run"] == file[key], key
            assert reduced[key]["why"]
        else:
            assert file[key] == published, key
    # no width among the reduced keys
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "conv_L_cache"):
        assert key not in reduced
    # the run's kinds are published layers 1 to 17
    assert tuple(file["layer_types"]) == lfm2_moe.LAYER_TYPES[1:18]


def test_to_program_maps_to_fields_that_exist():
    file = cell_file()
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    for field, key in file["to_program"].items():
        assert field in fields, field
        assert key in file, key
    assert set(file["program"]) <= fields
    for name in ("tie_word_embeddings", "qk_layernorm", "router_norm_eps",
                 "router_bias_rate", "conv_init"):
        assert name in file["assumed"], name
    assert "four chips share each layer" in file["deployment"]


def test_cache_key_covers_the_new_fields():
    from dlrover_tpu.runtime.compile_cache import train_cache_key

    def key(**kw):
        return train_cache_key(
            dataclasses.replace(config(), **kw), (1, 1, 1, 1, 1, 1),
            global_batch_size=8, seq_len=32,
        )

    keys = {
        key(), key(conv_kernel=4), key(qk_norm=True), key(qk_norm=False),
        key(router_norm_eps=1e-20),
    }
    assert len(keys) == 5


def test_the_master_renders_the_conv_event_as_gauges():
    from dlrover_tpu.master.speed_monitor import SpeedMonitor
    from dlrover_tpu.master.timeline import JobTimeline

    monitor = SpeedMonitor()
    monitor.record_health(
        "conv", 0, step=5, layers=13, gate_absmean=0.5,
        out_gate_absmean=0.75, out_absmax=2.5,
    )
    monitor.record_health(
        "conv", 1, step=5, layers=13, gate_absmean=0.25,
        out_gate_absmean=0.75, out_absmax=7.5, later_field=1,
    )
    ledger = monitor.health_ledger("conv")
    assert ledger["out_absmax"] == 7.5 and ledger["gate_absmean"] == 0.375
    assert ledger["layers"] == 13 and ledger["reporters"] == 2
    assert "chunk" not in ledger and "state_absmax" not in ledger
    text = JobTimeline().render_metrics(speed_monitor=monitor)
    for name, value in (
        ("dlrover_conv_out_absmax", "7.5"),
        ("dlrover_conv_out_gate_absmean", "0.75"),
        ("dlrover_conv_gate_absmean", "0.375"),
    ):
        assert f"# TYPE {name} gauge" in text
        assert any(
            line.startswith(name + " ") and line.split()[1].startswith(value)
            for line in text.splitlines()
        ), name
    # a value that is not a number on one replica must show
    monitor.record_health("conv", 1, step=6, layers=13, out_absmax=float("nan"))
    assert monitor.health_ledger("conv")["out_absmax"] != (
        monitor.health_ledger("conv")["out_absmax"]
    )
    # no reporter: the gauges read neutral
    assert SpeedMonitor().health_ledger("conv")["out_absmax"] == 0.0
