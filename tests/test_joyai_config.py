"""JoyAI-LLM-Flash without a model built: what the configuration refuses,
and what the master does with the ``moe`` and ``mtp`` events.  (Many cases
and no compile: a file is one worker's, and the driver's workers take the
files with the most cases first.)"""

import pytest

from test_joyai_reference import config


@pytest.mark.parametrize("overrides,message", [
    (dict(experts_held=5), "must divide num_experts"),
    (dict(first_expert=2), "multiple of it"),
    (dict(moe_dispatch="einsum"), "dispatch='grouped' only"),
    (dict(experts_held=0, first_expert=0, moe_dispatch="einsum"),
     "routed by moe_dispatch='grouped' only"),
    (dict(decode=True), "trains only"),
    (dict(v_head_dim=0), "together"),
    (dict(position="learned"), "position='rope'"),
    (dict(first_k_dense=3), "must leave a trunk"),
    (dict(num_experts=0, experts_held=0, first_expert=0, router_bias=False,
          router_scoring="softmax", moe_dispatch="einsum"),
     "describe an expert layer"),
    # (since PR 44 a dense prefix goes ahead of a pattern of two-branch
    # kinds; what is refused is a prefix beside one-branch kinds)
    (dict(layer_pattern=("full_attention", "experts"), num_layers=3),
     "every kind must be a mixer AND an MLP"),
    (dict(mtp_depth=2), "mtp_depth must be 0 or 1"),
    (dict(router_scoring="softmax"), "router_bias corrects a sigmoid"),
    (dict(pipeline_stages=3, first_k_dense=1, num_layers=5),
     "does not divide"),
])
def test_bad_combinations_of_the_new_fields_raise(overrides, message):
    with pytest.raises(ValueError, match=message):
        config(**overrides)


def test_the_master_renders_the_share_the_bias_and_the_mtp_loss_as_gauges():
    from dlrover_tpu.master.speed_monitor import SpeedMonitor
    from dlrover_tpu.master.timeline import JobTimeline

    monitor = SpeedMonitor()
    common = dict(entropy=5.5, drop_fraction=0.0, experts=256, top_k=8,
                  load="[]", pad_share=0.1, max_expert_load=1.2)
    monitor.record_moe(0, step=5, held=32, pairs_here=0.124,
                       bias_absmax=0.004, later_attr="ignored", **common)
    monitor.record_moe(1, step=5, held=32, pairs_here=0.126,
                       bias_absmax=0.006, **common)
    monitor.record_health("mtp", 0, step=5, mtp_loss=10.0, weight=0.3)
    monitor.record_health("mtp", 1, step=5, mtp_loss=10.5)
    ledger = monitor.moe_ledger()
    assert ledger["held"] == 32 and ledger["experts"] == 256
    assert ledger["pairs_here"] == pytest.approx(0.125)
    assert ledger["bias_absmax"] == 0.006         # the largest replica's
    assert monitor.health_ledger("mtp")["mtp_loss"] == pytest.approx(10.25)
    text = JobTimeline().render_metrics(speed_monitor=monitor)
    for name, value in (
        ("dlrover_moe_experts_held", "32"),
        ("dlrover_moe_pairs_here", "0.125"),
        ("dlrover_moe_router_bias_absmax", "0.006"),
        ("dlrover_mtp_loss", "10.25"),
    ):
        assert f"# TYPE {name} gauge" in text
        assert any(
            line.startswith(name + " ") and line.split()[1].startswith(value)
            for line in text.splitlines()
        ), name
    # an older trainer's event (no share told) reads as every expert held
    older = SpeedMonitor()
    older.record_moe(0, step=1, **common)
    assert older.moe_ledger()["held"] == 256
    assert older.moe_ledger()["pairs_here"] == 1.0
    assert older.health_ledger("mtp")["mtp_loss"] == 0.0
