"""Nemotron-H without a model built: what the configuration refuses and
counts, which scan a trainer names, and what the master does with the
``ssm`` event.  (Many cases and no compile: a file is one worker's, and the
driver's workers take the files with the most cases first.)"""

import numpy as np
import pytest

from dlrover_tpu.models import nemotron_h
from dlrover_tpu.models.nemotron_h import nemotron_h_config
from dlrover_tpu.models.transformer import (
    ATTENTION, EXPERTS, TransformerConfig,
)
from test_nemotron_h_reference import config


def test_decode_with_an_ssm_layer_raises_naming_what_is_missing():
    with pytest.raises(ValueError, match=r"recurrent state \[H, P, N\]"):
        config(decode=True)
    with pytest.raises(ValueError, match="serving/decode.py"):
        config(decode=True)


@pytest.mark.parametrize("overrides,message", [
    (dict(ssm_num_heads=0), "an ssm layer needs"),
    (dict(ssm_groups=3), "ssm_groups dividing the heads"),
    (dict(ssm_impl="pallas"), "ssm_impl must be one of"),
    (dict(ssm_impl="kernel", ssm_head_dim=48), "side by side"),
    (dict(num_experts=0, router_scoring="softmax", router_bias=False,
          num_shared_experts=0, moe_dispatch="einsum"),
     "an 'experts' layer needs num_experts"),
    (dict(layer_pattern=("ssm", "mamba")), "layer_pattern kinds"),
    (dict(num_layers=10), "no whole number of periods"),
    (dict(mtp_depth=1), "mtp_depth with a layer_pattern"),
    (dict(first_k_dense=1), "first_k_dense"),
    (dict(position="alibi"), "position must be"),
    (dict(activation="relu"), "activation must be"),
])
def test_bad_combinations_of_the_new_fields_raise(overrides, message):
    with pytest.raises(ValueError, match=message):
        config(**overrides)


def test_the_published_widths_count_what_the_issue_counts():
    """ISSUE 37's arithmetic, from the program's own shapes (a layer's own
    norm and the final one are left out of ``num_params``, as ever)."""
    cfg = nemotron_h_config(
        num_layers=18, experts_held=16, vocab_size=16384
    )
    assert cfg._ssm_mixer_params() == 38_744_896 - 2688
    assert cfg.num_ssm_layers == 8
    assert cfg.num_layers_of(EXPERTS) == 8
    assert cfg.num_layers_of(ATTENTION) == 2
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256
    assert attn == 23_399_040 - 2688
    experts = 16 * 2 * 2688 * 1856 + 2 * 2688 * 3712 + 2688 * 128 + 128
    assert experts == 179_948_288 - 2688
    assert cfg.num_params() == (
        8 * (38_744_896 - 2688) + 2 * attn + 8 * experts + 2 * 16384 * 2688
    )
    assert cfg.num_params() == 1_884_426_624 - 19 * 2688
    whole = nemotron_h_config(
        num_layers=52, layer_pattern=nemotron_h.kinds(
            nemotron_h.PUBLISHED_PATTERN
        ),
    )
    assert 31.5e9 < whole.num_params() < 31.7e9
    letters = nemotron_h.PUBLISHED_PATTERN
    assert (letters.count("M"), letters.count("E"), letters.count("*")) == (
        23, 23, 6
    )
    # the run taken: published layers 34-42, the only whole run at 4 : 4 : 1
    assert letters[34:43] == nemotron_h.PERIOD
    runs = [len(run) + 1 for run in letters.split("*")[:-1]]
    # ... and the last nine layers close with an expert layer, not an ``*``
    assert runs == [6, 7, 7, 7, 7, 9] and letters.split("*")[-1] == "EMEMEMEME"


def test_a_model_without_such_a_layer_names_no_scan():
    from dlrover_tpu.models import transformer

    facts = transformer.kernel_facts(TransformerConfig(), 64)
    assert facts["ssm_scan"] == "none"
    assert transformer.kernel_facts(config(), 64)["ssm_scan"] == "xla"


@pytest.mark.parametrize("cap,said,dw_said", [
    # wi's [2688, 1856] strip (and the transposed wo's: its dx) overflows
    # the default scoped VMEM's budget and asks for more; the weight
    # gradient's whole tile would take 40 MiB and is cut in three along the
    # side that can be (1,856 is a block's full extent) ...
    (None, "resident", "into:3x1 out_of:1x3"),
    # ... and with nothing more to ask for K is cut in three: two of the
    # ungated layer's four forward/dx calls (the rule until PR 53), and the
    # weight gradients in seven (until PR 55)
    (0, "split_k:2/4", "into:7x1 out_of:1x7"),
])
def test_the_compile_event_names_the_gemm_strips(monkeypatch, cap, said,
                                                 dw_said):
    from dlrover_tpu.models import transformer
    from dlrover_tpu.ops import grouped_matmul

    if cap is not None:
        monkeypatch.setattr(grouped_matmul, "_VMEM_CAP", cap)
    facts = transformer.kernel_facts(nemotron_h_config(experts_held=16), 8192)
    assert facts["gmm_strips"] == said
    assert facts["gmm_dw_tiles"] == dw_said


@pytest.mark.parametrize("overrides,rows", [
    # the cell: 16 of 128 experts here, six rows of 2,688 a token.  21 lane
    # tiles are no whole native tiles, so the rows stay plain between the
    # gathers and the GEMMs and the fetch-and-sum kernel takes them padded
    # to 24, only the pairs that have a row here
    (dict(experts_held=16), "kernel_live_padded"),
    (dict(), "kernel_padded"),
    # float32 rows of 3,072: the slots and the output's 512-token blocks
    # pass the kernel's 12 MiB VMEM plan, so XLA's gather keeps them
    (dict(experts_held=16, dtype="float32"), "xla"),
    # the tiny reference model's rows of 64 are no whole lanes
    (None, "xla"),
])
def test_the_compile_event_names_the_row_moves(overrides, rows):
    from dlrover_tpu.models import transformer
    from dlrover_tpu.ops import row_gather_sum

    cfg = config() if overrides is None else nemotron_h_config(**overrides)
    assert transformer.kernel_facts(cfg, 8192)["row_moves"] == rows
    row = (cfg.d_model, cfg.top_k, cfg.dtype)
    # rows of whole tiles go row-tiled THROUGH THE GEMMS: these never do
    assert not row_gather_sum.kernel_fits(*row)
    assert row_gather_sum.padded_width(*row) == (
        3072 if rows.endswith("padded") else 0
    )


def test_the_master_renders_the_events_as_gauges():
    from dlrover_tpu.master.speed_monitor import SpeedMonitor
    from dlrover_tpu.master.timeline import JobTimeline

    monitor = SpeedMonitor()
    monitor.record_health(
        "ssm", 0, step=5, layers=8, chunk=128, mean_decay=0.8, mean_dt=0.02,
        state_absmax=2.5, later_attr="ignored",
    )
    monitor.record_health(
        "ssm", 1, step=5, layers=8, chunk=128, mean_decay=0.6, mean_dt=0.04,
        state_absmax=7.5,
    )
    ledger = monitor.health_ledger("ssm")
    assert ledger["reporters"] == 2 and ledger["layers"] == 8
    assert ledger["mean_decay"] == pytest.approx(0.7)
    assert ledger["state_absmax"] == 7.5          # the worst replica's
    text = JobTimeline().render_metrics(speed_monitor=monitor)
    for name, value in (
        ("dlrover_ssm_layers", "8"),
        ("dlrover_ssm_chunk", "128"),
        ("dlrover_ssm_mean_decay", "0.7"),
        ("dlrover_ssm_mean_dt", "0.03"),
        ("dlrover_ssm_state_absmax", "7.5"),
        ("dlrover_ssm_reporters", "2"),
    ):
        assert f"# TYPE {name} gauge" in text
        assert any(
            line.startswith(name + " ") and line.split()[1].startswith(value)
            for line in text.splitlines()
        ), name
    # a state that diverged on one replica shows as such, and the linear
    # layers' ledger is its own
    monitor.record_health("ssm", 1, step=10, state_absmax=float("nan"))
    assert np.isnan(monitor.health_ledger("ssm")["state_absmax"])
    assert monitor.health_ledger("linear_attn")["reporters"] == 0


def test_the_servicer_routes_the_event_to_the_ledger():
    import pickle

    from dlrover_tpu.master import messages as msg
    from dlrover_tpu.master.servicer import MasterServicer
    from dlrover_tpu.master.speed_monitor import HEALTH_KINDS, SpeedMonitor
    from dlrover_tpu.master.timeline import JobTimeline

    # by the kind's row of the one table, not by a branch of its own
    assert "ssm" in HEALTH_KINDS
    monitor = SpeedMonitor()
    servicer = MasterServicer(speed_monitor=monitor, timeline=JobTimeline())
    attrs = dict(step=5, layers=8, chunk=128, mean_decay=0.8, mean_dt=0.02,
                 state_absmax=2.5, heads=64, groups=8)
    wire = pickle.dumps(msg.Envelope(
        node_id=3,
        payload=msg.TelemetryEvents(3, (("ssm", "event", 0.0, 0.0, attrs),)),
    ))
    assert servicer.report(msg.safe_loads(wire)).success
    ledger = monitor.health_ledger("ssm")
    assert ledger["reporters"] == 1 and ledger["state_absmax"] == 2.5
