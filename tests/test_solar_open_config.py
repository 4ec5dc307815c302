"""What the configuration refuses and what it counts, the benchmark's file
against the catalog's config key for key and onto ``solar_open2_config``
field for field, and the master's rendering of the ``linear_attn`` event's
new attributes."""

import dataclasses
import json
import os

import jax.numpy as jnp
import pytest

from dlrover_tpu.models import solar_open
from dlrover_tpu.models.solar_open import solar_open2_config
from dlrover_tpu.models.transformer import (
    FULL_ATTENTION,
    LINEAR_ATTENTION,
    TransformerConfig,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILE = os.path.join(REPO, "benchmark", "configs", "solar-open2-250b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SMALL = dict(
    vocab_size=256, num_layers=4, d_model=64, num_heads=4, num_kv_heads=2,
    head_dim=16, d_ff=96, max_seq_len=48, linear_num_heads=4,
    linear_key_head_dim=16, linear_value_head_dim=16, linear_gate_rank=8,
    num_experts=32, top_k=4, moe_d_ff=32, experts_held=8,
)


def config(**overrides):
    return solar_open2_config(**{**SMALL, **overrides})


@pytest.mark.parametrize("overrides,message", [
    (dict(linear_decay_bound=-6.0), r"must lie in \(-5.5, 0\), got -6.0"),
    (dict(linear_decay_bound=0.5), r"must lie in \(-5.5, 0\), got 0.5"),
    (dict(linear_gate_rank=-1), "linear_gate_rank is the per-channel rule's"),
    (dict(linear_rule="delta"), "linear_gate_rank is the per-channel rule's"),
    (dict(attention_gate="per_channel"), "attention_gate is '' or 'head_wise'"),
    (dict(num_layers=6), "no whole number of periods of the 4-layer pattern"),
    (dict(decode=True), "decode=True with a linear_attention layer"),
])
def test_bad_combinations_of_the_new_fields_raise(overrides, message):
    with pytest.raises(ValueError, match=message):
        config(**overrides)


def test_an_elementwise_gate_is_grouped_query_attention_s_alone():
    from dlrover_tpu.models.ling_flash import ling_flash_config

    with pytest.raises(ValueError, match="grouped-query attention's alone"):
        ling_flash_config(attention_gate="elementwise")
    # on the plain path both granularities stand
    assert config(attention_gate="head_wise").attention_gate == "head_wise"
    assert TransformerConfig(attention_gate="elementwise").num_params() > (
        TransformerConfig().num_params()
    )


def test_the_defaults_leave_every_other_model_as_it_was():
    plain = TransformerConfig()
    assert plain.linear_gate_rank == 0 and plain.linear_decay_init_std == 0
    assert plain.linear_decay_bound == -5.0 and plain.attention_gate == ""
    from dlrover_tpu.models import linear_attention
    from dlrover_tpu.models.ling_flash import ling_flash_config

    ling = linear_attention.from_config(ling_flash_config())
    assert (ling.decay_bound, ling.gate_rank, ling.allow_neg_eigval) == (
        -5.0, 0, False
    )
    assert not ling.exact
    ours = linear_attention.from_config(solar_open2_config())
    assert (ours.decay_bound, ours.gate_rank, ours.allow_neg_eigval) == (
        0.0, 128, True
    )
    assert ours.exact and ours.num_heads == 64


def test_the_published_pattern_is_three_kda_to_one_gqa_from_layer_zero():
    kinds = [solar_open.published_kind(i) for i in range(48)]
    assert [i for i, k in enumerate(kinds) if k == FULL_ATTENTION] == list(
        range(0, 48, 4)
    )
    assert kinds.count(LINEAR_ATTENTION) == 36
    assert solar_open.TRUNK_PATTERN == tuple(kinds[:4]) == (
        (FULL_ATTENTION,) + (LINEAR_ATTENTION,) * 3
    )
    cfg = solar_open2_config()
    assert cfg.num_layers == 48 and cfg.num_scan_units == 12
    assert [cfg.layer_kind(i) for i in range(48)] == kinds
    assert cfg.first_k_dense == 0 and cfg.position == "none"


def test_the_published_widths_count_what_the_file_counts():
    """The benchmark file's arithmetic (its ``reduced`` says each term), and
    the whole model's against its name: 250B-A15B."""
    cut = solar_open2_config(
        num_layers=4, experts_held=20, vocab_size=24576
    )
    kda, gqa = 137_732_288, 109_051_904
    expert = 3 * 4096 * 1280
    outside = expert + 20 * expert + 4096 * 320
    assert cut._linear_mixer_params() == kda
    assert outside == 331_612_160
    assert cut.num_params() == (
        3 * kda + gqa + 4 * outside + 2 * 24576 * 4096
    ) == 2_050_024_000
    ten = dataclasses.replace(cut, experts_held=10)
    assert ten.num_params() == 2_050_024_000 - 4 * 10 * expert
    whole = solar_open2_config()
    assert abs(whole.num_params() / 250.3e9 - 1) < 0.005
    # a token meets 8 routed experts and the shared one
    active = dataclasses.replace(whole, experts_held=8).num_params()
    assert abs(active / 14.7e9 - 1) < 0.005


def test_the_file_holds_the_catalogs_config_key_for_key():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide in this install")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r["name"] == "Solar-Open2-250B"]
    with open(FILE) as f:
        file = json.load(f)
    assert file["source"] == row["source_url"]
    reduced = set(file["reduced"])
    assert reduced == {
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"
    }
    for key, value in row["config"].items():
        assert key in file, key
        if key in reduced:
            assert file["reduced"][key]["published"] == value, key
            assert file["reduced"][key]["run"] == file[key], key
        else:
            assert file[key] == value, key
    # no width is cut
    for key in ("hidden_size", "head_dim", "moe_intermediate_size",
                "num_experts_per_tok", "num_attention_heads",
                "num_key_value_heads", "linear_attn_config"):
        assert key not in reduced


def test_the_file_maps_onto_the_model_s_config_field_for_field():
    from benchmark import build

    with open(FILE) as f:
        file = json.load(f)
    model = build.model_group(file)
    got = build.transformer_config(model, file["run"]["seq_len"])
    # the published model cut as the file says, and what no publication
    # fixes (the program group's choices)
    chosen = (
        "param_dtype", "attention_impl", "remat", "moe_row_budget",
        "embed_init_std", "attn_init_score_std", "linear_decay_init_std",
    )
    want = solar_open2_config(
        num_layers=file["num_hidden_layers"],
        experts_held=file["n_routed_experts"],
        first_expert=file["first_expert"], vocab_size=file["vocab_size"],
        max_seq_len=file["run"]["seq_len"],
        **{k: getattr(got, k) for k in chosen},
    )
    assert got == want
    assert got.param_dtype == jnp.bfloat16 and got.remat == "flash_only"
    assert file["num_params"] == got.num_params()
    assert file["router_experts"] == got.num_experts == 320
    assert file["gqa_layers"] == [
        i for i in range(got.num_layers)
        if got.layer_kind(i) == FULL_ATTENTION
    ]
    # every published key the program reads is mapped or written under
    # program beside its reason
    lin = file["linear_attn_config"]
    assert (got.resolved_linear_heads, got.linear_key_head_dim,
            got.linear_value_head_dim, got.linear_conv_kernel) == (
        lin["num_heads"], lin["head_dim"], lin["head_dim"],
        lin["short_conv_kernel_size"],
    )
    assert got.linear_allow_neg_eigval is file["kda_allow_neg_eigval"]
    assert bool(got.linear_gate_rank) is not file["kda_use_full_proj"]
    assert bool(got.attention_gate) is file["use_gqa_gate"]
    assert (got.position != "none") is file["use_rope"]
    for key in ("kda_gate", "kda_low_rank", "kda_gate_bias", "gqa_gate",
                "router", "seeded_scales", "kda_initialisers"):
        assert key in file["assumed"], key


def test_cache_key_covers_the_new_fields():
    from dlrover_tpu.runtime.compile_cache import train_cache_key

    def key(**kw):
        return train_cache_key(
            dataclasses.replace(config(), **kw), (1, 1, 1, 1, 1, 1),
            global_batch_size=8, seq_len=32,
        )

    keys = {
        key(), key(linear_decay_bound=-5.0), key(linear_gate_rank=0),
        key(linear_allow_neg_eigval=False), key(attention_gate="head_wise"),
        key(attention_gate=""), key(linear_decay_init_std=2.0),
    }
    assert len(keys) == 7


def test_the_master_renders_the_new_attributes_as_gauges():
    from dlrover_tpu.master.speed_monitor import SpeedMonitor
    from dlrover_tpu.master.timeline import JobTimeline

    monitor = SpeedMonitor()
    monitor.record_health(
        "linear_attn", 0, step=5, layers=3, chunk=128, mean_alpha=0.8,
        mean_beta=1.0, state_absmax=2.5, rule="kda", min_alpha=0.25,
        g_min=-120.0, past_bound_share=0.03125,
    )
    monitor.record_health(
        "linear_attn", 1, step=5, layers=3, chunk=128, mean_alpha=0.6,
        mean_beta=1.0, state_absmax=7.5, rule="kda", min_alpha=0.125,
        g_min=-140.0, past_bound_share=0.0625,
    )
    ledger = monitor.health_ledger("linear_attn")
    assert ledger["g_min"] == -140.0 and ledger["past_bound_share"] == 0.0625
    text = JobTimeline().render_metrics(speed_monitor=monitor)
    for name, value in (
        ("dlrover_linear_attn_g_min", "-140"),
        ("dlrover_linear_attn_past_bound_share", "0.0625"),
    ):
        assert f"# TYPE {name} gauge" in text
        assert any(
            line.startswith(name + " ") and line.split()[1].startswith(value)
            for line in text.splitlines()
        ), name
    # a bounded gate's events carry neither: the gauges read neutral
    older = SpeedMonitor()
    older.record_health("linear_attn", 0, step=1, layers=6, chunk=128)
    assert older.health_ledger("linear_attn")["g_min"] == 0.0
    assert older.health_ledger("linear_attn")["past_bound_share"] == 0.0
