"""What Ling-3.0-flash's configuration refuses and counts, on the CPU: the
new fields' bad combinations, the dense prefix beside a layer pattern, the
published widths' parameter count against the benchmark file's arithmetic,
the cache key, the master's gauges for the events' new fields.  (The
benchmark file itself against the catalog is
``tests/benchmark_suite/test_benchmark_ling_flash.py``'s.)"""

import dataclasses

import pytest

from dlrover_tpu.models import ling_flash
from dlrover_tpu.models.ling_flash import ling_flash_config
from dlrover_tpu.models.transformer import (
    FULL_ATTENTION,
    LINEAR_ATTENTION,
    TransformerConfig,
)

SMALL = dict(
    vocab_size=128, num_layers=7, first_k_dense=1, d_model=32, num_heads=4,
    d_ff=48, max_seq_len=32, linear_num_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=8, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, num_experts=32, router_groups=4,
    router_topk_groups=2, top_k=4, moe_d_ff=16, experts_held=8,
)


def config(**overrides):
    return ling_flash_config(**{**SMALL, **overrides})


@pytest.mark.parametrize("overrides,message", [
    (dict(linear_rule="delta_net"), "linear_rule must be 'delta' or 'kda'"),
    (dict(linear_decay_bound=-6.0), r"must lie in \(-5.5, 0\), got -6.0"),
    (dict(linear_decay_bound=0.5), r"must lie in \(-5.5, 0\), got 0.5"),
    (dict(attention_gate="element_wise"), "attention_gate is '' or 'head_wise'"),
    (dict(router_groups=5), "router_groups 5 must divide num_experts 32"),
    (dict(router_topk_groups=5), "router_topk_groups 5 of them"),
    (dict(router_groups=8, router_topk_groups=1, top_k=8),
     "at least top_k 8 experts"),
    (dict(router_scoring="softmax", router_bias=False),
     "a group-limited choice is a sigmoid router's"),
    (dict(num_layers=8), "no whole number of periods of the 6-layer pattern"),
    (dict(layer_pattern=("linear_attention", "experts"), num_layers=3),
     "every kind must be a mixer AND an MLP"),
    (dict(kv_lora_rank=0), "latent attention needs kv_lora_rank"),
    (dict(decode=True), "decode=True with a linear_attention layer"),
    (dict(mtp_depth=1), "mtp_depth with a layer_pattern"),
])
def test_bad_combinations_of_the_new_fields_raise(overrides, message):
    with pytest.raises(ValueError, match=message):
        config(**overrides)


def test_an_elementwise_gate_on_latent_attention_raises():
    """Latent attention's gate is a head's; since PR 64 plain grouped-query
    attention takes either granularity (tests/test_solar_open_config.py)."""
    with pytest.raises(ValueError, match="latent attention's"):
        config(attention_gate="elementwise")
    assert TransformerConfig(attention_gate="head_wise").attention_gate


def test_the_defaults_leave_every_other_model_as_it_was():
    plain = TransformerConfig()
    assert plain.linear_rule == "delta" and plain.attention_gate == ""
    assert plain.router_groups == plain.router_topk_groups == 1
    assert plain.layer_kind(3) == FULL_ATTENTION
    assert plain.num_linear_layers == 0


def test_the_published_pattern_is_five_kda_to_one_latent_from_layer_two():
    kinds = [ling_flash.published_kind(i) for i in range(42)]
    assert [i for i, k in enumerate(kinds) if k == FULL_ATTENTION] == [
        5, 11, 17, 23, 29, 35, 41
    ]
    assert kinds.count(LINEAR_ATTENTION) == 35
    assert ling_flash.TRUNK_PATTERN == tuple(kinds[2:8]) == (
        (LINEAR_ATTENTION,) * 3 + (FULL_ATTENTION,) + (LINEAR_ATTENTION,) * 2
    )
    # the program's numbering agrees with the published one, layer by
    # layer, for the default (two dense layers, six periods) ...
    cfg = ling_flash_config()
    assert cfg.num_layers == 38 and cfg.num_scan_units == 6
    assert [cfg.layer_kind(i) for i in range(38)] == kinds[:38]
    # ... and for the benchmark's cut, which starts at published layer 1
    cut = ling_flash_config(num_layers=7, first_k_dense=1)
    assert [cut.layer_kind(i) for i in range(7)] == kinds[1:8]
    assert cut.num_linear_layers == 6


def test_the_published_widths_count_what_the_file_counts():
    """The benchmark file's arithmetic (its ``reduced`` says each term)."""
    cut = ling_flash_config(
        num_layers=7, first_k_dense=1, experts_held=32, vocab_size=19712
    )
    kda, latent = 63_049_888, 31_965_696
    expert = 3 * 2560 * 768
    outside = expert + 2560 * 512 + 512 + 32 * expert
    dense = kda + 3 * 2560 * 6144
    assert cut._linear_mixer_params() == kda
    assert outside == 195_953_152 and dense == 110_235_808
    assert cut.num_params() == (
        5 * (kda + outside) + (latent + outside) + dense
        + 2 * 19712 * 2560
    ) == 1_734_095_296
    # a published layer whole: no chip holds one at 4.8 bytes a parameter
    whole = kda + expert + 2560 * 512 + 512 + 512 * expert
    assert 3.08e9 < whole < 3.10e9
    # the default: two dense layers and six whole periods of the 42
    full = ling_flash_config()
    assert 111e9 < full.num_params() < 113e9


def test_cache_key_covers_the_new_fields():
    from dlrover_tpu.runtime.compile_cache import train_cache_key

    def key(**kw):
        return train_cache_key(
            dataclasses.replace(config(), **kw), (1, 1, 1, 1, 1, 1),
            global_batch_size=8, seq_len=32,
        )

    keys = {
        key(), key(linear_rule="delta"), key(linear_decay_bound=-4.0),
        key(attention_gate=""), key(router_groups=2),
        key(router_topk_groups=3), key(q_lora_rank=8),
    }
    assert len(keys) == 7


def test_the_master_renders_the_new_fields_as_gauges():
    from dlrover_tpu.master.speed_monitor import SpeedMonitor
    from dlrover_tpu.master.timeline import JobTimeline

    monitor = SpeedMonitor()
    monitor.record_health(
        "linear_attn", 0, step=5, layers=6, chunk=128, mean_alpha=0.8, mean_beta=0.5,
        state_absmax=2.5, rule="kda", min_alpha=0.25,
    )
    monitor.record_health(
        "linear_attn", 1, step=5, layers=6, chunk=128, mean_alpha=0.6, mean_beta=0.5,
        state_absmax=7.5, rule="kda", min_alpha=0.125,
    )
    assert monitor.health_ledger("linear_attn")["min_alpha"] == 0.125
    monitor.record_moe(
        0, step=5, experts=512, top_k=8, held=32, pairs_here=0.0625,
        tokens_here=0.25, groups=8, topk_group=4, load="[]",
    )
    ledger = monitor.moe_ledger()
    assert ledger["tokens_here"] == 0.25 and ledger["groups"] == 8
    text = JobTimeline().render_metrics(speed_monitor=monitor)
    for name, value in (
        ("dlrover_linear_attn_min_alpha", "0.125"),
        ("dlrover_moe_tokens_here", "0.25"),
        ("dlrover_moe_router_groups", "8"),
    ):
        assert f"# TYPE {name} gauge" in text
        assert any(
            line.startswith(name + " ") and line.split()[1].startswith(value)
            for line in text.splitlines()
        ), name
    # an older trainer's events carry neither: the gauges read neutral
    older = SpeedMonitor()
    older.record_health("linear_attn", 0, step=1, layers=6, chunk=128)
    older.record_moe(0, step=1, experts=8, top_k=2, load="[]")
    assert older.health_ledger("linear_attn")["min_alpha"] == 1.0
    assert older.moe_ledger()["tokens_here"] == 1.0
