"""A model family's declaration (``models/family.py``) against its row of
the master's table (``master/speed_monitor.HEALTH_KINDS``), one case a row,
no program built: what the row keeps is what the family's reading emits,
and every gauge the master rendered for the family before the table (PR 56)
is rendered, with the value the event carries."""

import numpy as np
import pytest

import reference_harness as harness
from dlrover_tpu.master.speed_monitor import HEALTH_KINDS, SpeedMonitor
from dlrover_tpu.master.timeline import JobTimeline
from dlrover_tpu.models import transformer

# kind -> (a tiny preset that has the family with every optional entry of
# its vector, the values a report would fetch for it)
CASES = {
    # a group-limited router told a share: the third entry, ``tokens_here``
    "moe": ("ling-3.0-flash-vl", lambda cfg: [
        np.concatenate([
            [3.25, 0.125], np.full(cfg.num_experts, 1 / cfg.num_experts),
            [0.0625, 1.5],
        ]),
        np.array([0.25, 0.03125, 0.75]),
    ]),
    "mtp": ("joyai-llm-flash", lambda cfg: [np.float32(9.5)]),
    # the per-channel rule under a gate without a bound: the fourth entry,
    # ``min_alpha``, the fifth and the sixth, ``g_min`` and
    # ``past_bound_share``
    "linear_attn": ("solar-open2-250b", lambda cfg: [
        np.array([0.875, 1.0, 3.5, 0.125, -96.0, 0.03125], np.float32)
    ]),
    "ssm": ("nemotron-3-nano-30b-a3b", lambda cfg: [
        np.array([0.75, 0.03125, 6.5], np.float32)
    ]),
    "conv": ("lfm2-8b-a1b", lambda cfg: [
        np.array([0.375, 0.625, 2.5], np.float32)
    ]),
    "attn": ("mellum2-12b-a2.5b", lambda cfg: [
        np.array([12.5, 7.5], np.float32)
    ]),
    # [chosen pairs, pairs seen, largest score, the layers' KL terms summed]
    "index": ("glm-5.2", lambda cfg: [
        np.array([1536.0, 6240.0, 9.5, 4.5], np.float32)
    ]),
}
# The gauges ``render_metrics`` wrote out for each family at PR 55, by hand.
GAUGES_BEFORE_THE_TABLE = {
    "moe": {
        "dlrover_moe_gate_entropy", "dlrover_moe_capacity_drop_fraction",
        "dlrover_moe_pad_share", "dlrover_moe_max_expert_load",
        "dlrover_moe_experts", "dlrover_moe_top_k", "dlrover_moe_reporters",
        "dlrover_moe_experts_held", "dlrover_moe_pairs_here",
        "dlrover_moe_tokens_here", "dlrover_moe_router_groups",
        "dlrover_moe_router_bias_absmax",
    },
    "mtp": {"dlrover_mtp_loss"},
    "linear_attn": {
        "dlrover_linear_attn_layers", "dlrover_linear_attn_chunk",
        "dlrover_linear_attn_mean_alpha", "dlrover_linear_attn_mean_beta",
        "dlrover_linear_attn_state_absmax", "dlrover_linear_attn_min_alpha",
        "dlrover_linear_attn_reporters",
    },
    "ssm": {
        "dlrover_ssm_layers", "dlrover_ssm_chunk", "dlrover_ssm_mean_decay",
        "dlrover_ssm_mean_dt", "dlrover_ssm_state_absmax",
        "dlrover_ssm_reporters",
    },
    "conv": {
        "dlrover_conv_gate_absmean", "dlrover_conv_out_gate_absmean",
        "dlrover_conv_out_absmax", "dlrover_conv_reporters",
    },
    "attn": {
        "dlrover_attn_window", "dlrover_attn_sliding_layers",
        "dlrover_attn_full_score_bound", "dlrover_attn_sliding_score_bound",
        "dlrover_attn_reporters",
    },
    "index": set(),
}
# ... and the ones a family's row has gained since, by the PR that added them.
GAUGES_SINCE = {
    # PR 58: the shared experts held, published and their scale; the layers
    # that rotate at all
    "moe": {
        "dlrover_moe_shared_experts_held", "dlrover_moe_shared_experts",
        "dlrover_moe_shared_expert_scale",
    },
    "attn": {"dlrover_attn_rotated_layers"},
    # PR 64: a gate without a lower bound
    "linear_attn": {
        "dlrover_linear_attn_g_min", "dlrover_linear_attn_past_bound_share",
    },
    # PR 62: the family itself
    "index": {
        "dlrover_index_layers", "dlrover_index_shared_layers",
        "dlrover_index_topk", "dlrover_index_selected_share",
        "dlrover_index_kl", "dlrover_index_score_absmax",
        "dlrover_index_reporters",
    },
}


def test_every_family_has_a_row_and_every_row_a_family():
    assert [f.event for f in transformer.FAMILIES] == list(HEALTH_KINDS)
    assert set(CASES) == set(HEALTH_KINDS) == set(GAUGES_BEFORE_THE_TABLE)


@pytest.mark.parametrize("kind", sorted(HEALTH_KINDS))
def test_a_row_keeps_what_the_family_emits_and_renders_its_gauges(kind):
    preset, fetched = CASES[kind]
    cfg = harness.preset(preset)[0]
    (family,) = [f for f in transformer.families(cfg) if f.event == kind]
    attrs = family.read(cfg, *fetched(cfg))
    row = HEALTH_KINDS[kind]
    kept = {
        attr for how in ("mean", "max", "min") for attr in row.get(how, ())
    }
    # a renamed attribute would be kept at its default and say nothing
    assert kept <= set(attrs), kept - set(attrs)
    assert family.absmax is None or family.absmax in attrs
    monitor = SpeedMonitor()
    if kind == "moe":
        monitor.record_moe(0, step=5, **attrs)
    else:
        monitor.record_health(kind, 0, step=5, **attrs)
    text = JobTimeline().render_metrics(speed_monitor=monitor)
    rendered = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, value = line.rsplit(" ", 1)
            rendered[name] = float(value)
    gauges = {name: attr for attr, name, _ in row["gauges"]}
    assert set(gauges) == GAUGES_BEFORE_THE_TABLE[kind] | GAUGES_SINCE.get(
        kind, set()
    )
    for name, attr in gauges.items():
        assert f"# TYPE {name} gauge" in text
        want = 1.0 if attr == "reporters" else float(attrs[attr])
        assert rendered[name] == pytest.approx(want, rel=1e-5), name
