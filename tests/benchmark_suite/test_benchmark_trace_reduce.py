"""``benchmark/trace_reduce.py`` on numbers a reader can check by hand.

``recorded_trace.json`` is cut from this PR's own traced run of the GPT-2
1.5B step on one v5e chip (``extracted`` structure of ``trace_reduce``):
the last instructions of one step program, the idle gap up to the next
step's first instruction, the first instructions of that step, the rows of
the "XLA Modules" line for both steps, and the ``bench:<phase>`` host rows
that overlap the cut.  Times are nanoseconds on the trace's clock, moved so
that the cut starts near zero.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import trace_reduce as tr  # noqa: E402


def test_union_gaps_and_self_times_by_hand():
    rows = [
        ["while.1", "", 0, 100],        # a container ...
        ["fusion.1", "blocks/mlp/wo/dot_general", 10, 30],   # ... its children
        ["attn.2", "blocks/attn/pallas_call", 50, 40],
        ["fusion.9", "ln_final", 120, 30],                   # after a gap of 20
    ]
    merged = tr.union(tr.intervals(rows))
    assert merged == [[0, 100], [120, 150]]
    assert tr.length(merged) == 130
    assert tr.gaps(merged, 0, 160) == [[100, 120], [150, 160]]
    assert tr.self_times(rows) == [30.0, 30.0, 40.0, 30.0]
    assert tr.scope_seconds(rows, "pallas_call") == pytest.approx(40e-9)
    assert tr.scope_seconds(rows, r"mlp/wo") == pytest.approx(30e-9)
    assert tr.label(rows[1]) == "fusion.1@mlp/wo/dot_general"


def test_gap_attribution_by_hand():
    host = [["train_step", "", 90, 15], ["report", "", 105, 10],
            ["data_wait", "", 115, 20]]
    got = tr.attribute_gaps([[100, 120], [150, 160]], host)
    assert got == pytest.approx({
        "train_step": 5e-9, "report": 10e-9, "data_wait": 5e-9,
        "host_other": 10e-9,
    })


def test_exposed_collectives_by_hand():
    ops = [
        ["fusion.1", "", 0, 50],
        ["all-gather.3", "", 40, 30],       # 10 hidden under fusion.1
        ["all-reduce.7", "", 100, 20],      # fully exposed
        ["fusion.2", "", 125, 10],
    ]
    assert tr.exposed_collective_seconds(ops) == pytest.approx(40e-9)


def test_reduce_on_two_made_up_steps():
    dev = {
        "modules": [["jit__train_step(1)", "", 0, 100],
                    ["jit__train_step(1)", "", 110, 100],
                    ["jit_convert(2)", "", 215, 1]],
        "ops": [["fusion.1", "a/b", 0, 100], ["fusion.1", "a/b", 110, 90],
                ["fusion.2", "c", 200, 10]],
    }
    host = [["report", "", 95, 20]]
    got = tr.reduce({"devices": {"/device:TPU:0": dev}, "host": host},
                    "train_step")
    assert got["steps"] == 2 and got["chips"] == 1
    assert got["window_s"] == pytest.approx(210e-9)
    assert got["busy_s"] == pytest.approx(200e-9)
    assert got["step_device_ms"] == pytest.approx(100e-6)
    assert got["host_step_gap_ms"] == pytest.approx(10e-6)
    assert got["idle_gaps"] == [["report", pytest.approx(10e-9)]]
    assert got["device_ops"][0] == ["fusion.1@a/b", pytest.approx(190e-9)]
    assert tr.per_step(
        {"devices": {"d": dev}}, "train_step",
        lambda ops: tr.scope_seconds(ops, "a/b"),
    ) == [pytest.approx(100e-9), pytest.approx(90e-9)]


def test_scopes_from_compiled_text():
    text = """
  %fusion.350 = bf16[16,1024,6400]{2,1,0} fusion(%p0, %p1), kind=kOutput, calls=%fused.1, metadata={op_name="jit(_train_step)/jvp(TransformerLM)/while/body/blocks/mlp/wi/dot_general" source_file="x.py" source_line=3}
  ROOT %attn.15 = bf16[16,25,1024,64]{3,2,1,0} custom-call(%q, %k), custom_call_target="tpu_custom_call", metadata={op_name="jit(_train_step)/blocks/attn/pallas_call"}
  %copy.1 = f32[2]{0} copy(%x)
"""
    assert tr.scopes_from_hlo(text) == {
        "fusion.350":
            "jit(_train_step)/jvp(TransformerLM)/while/body/blocks/mlp/wi/"
            "dot_general",
        "attn.15": "jit(_train_step)/blocks/attn/pallas_call",
    }
    assert tr._op_name("%fusion.350 = bf16[16] fusion(...)") == "fusion.350"


# -- the recorded sample -------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def brute_busy(rows, lo, hi):
    """Busy nanoseconds by marking every nanosecond: slow and plain."""
    mark = bytearray(int(hi - lo))
    for _, _, start, dur in rows:
        a, b = int(max(start, lo) - lo), int(min(start + dur, hi) - lo)
        if b > a:
            mark[a:b] = b"\x01" * (b - a)
    return sum(mark)


def test_recorded_busy_share_gap_and_kernel(recorded):
    (dev,) = recorded["devices"].values()
    ops, host = dev["ops"], recorded["host"]
    want = recorded["by_hand"]
    lo = min(r[2] for r in ops)
    hi = max(r[2] + r[3] for r in ops)
    merged = tr.union(tr.intervals(ops))
    assert tr.length(merged) == brute_busy(ops, lo, hi)
    assert tr.length(merged) == want["busy_ns"]
    assert hi - lo == want["window_ns"]
    # the longest gap lies between the two step programs: from the first
    # one's last instruction (its module row ends some microseconds later)
    # to the small programs that place the next batch's weights
    idle = tr.gaps(merged, lo, hi)
    longest = max(idle, key=lambda g: g[1] - g[0])
    first, second = tr.step_rows(dev["modules"], "train_step")
    assert first[2] + first[3] - 20_000 <= longest[0]
    assert longest[1] <= second[2]
    assert longest[1] - longest[0] == want["step_gap_ns"]
    named = tr.attribute_gaps([longest], host)
    assert sum(named.values()) == pytest.approx(want["step_gap_ns"] * 1e-9)
    assert max(named, key=named.get) == want["step_gap_phase"]
    # a kernel's time: the flash kernels in the cut, by their scope
    assert tr.scope_seconds(ops, "pallas_call") == pytest.approx(
        want["pallas_ns"] * 1e-9
    )
    assert want["pallas_ns"] == sum(
        r[3] for r in ops if "pallas_call" in r[1]
    )
    got = tr.reduce(recorded, "train_step")
    assert got["busy_s"] == pytest.approx(want["busy_ns"] * 1e-9)
    assert got["host_step_gap_ms"] == pytest.approx(
        (second[2] - first[2] - first[3]) * 1e-6
    )
