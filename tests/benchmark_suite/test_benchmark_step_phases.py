"""The step program's six scope metrics (PR 35) on a recorded structure
written by hand: forward, recomputation, backward and optimizer split one
execution of the step program between them, ``head_loss_ms`` and
``step_unnamed_ms`` cut across the phases."""

import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import build, layers, trace_reduce  # noqa: E402
from benchmark.readers import scope_ms  # noqa: E402

STEP = "jit(_train_step)/"
FWD = STEP + "jvp(TransformerLM)/"
BWD = STEP + "transpose(jvp(TransformerLM))/"
BODY = "while/body/closed_call/"
# [instruction, op_name, start ns, duration ns], as the compiled text of a
# scanned trunk under ``flash_only`` names them (tests/test_step_scopes.py
# holds the forms against the program).
ROWS = [
    # the forward scan: 800 with 790 of children, so 10 of its own
    ["while.1", FWD + "while", 0, 800],
    ["fusion.1", FWD + BODY + "blocks/attn/qkv/dot_general", 0, 400],
    ["fusion.2", FWD + BODY + "blocks/mlp/wi/dot_general", 400, 300],
    ["fusion.3", FWD + "while/body/dynamic_update_slice", 700, 90],
    ["fusion.4", FWD + "ln_final/mul", 800, 50],
    ["fusion.5", FWD + "lm_head/dot_general", 850, 200],
    ["fusion.6", STEP + "jvp(loss)/reduce_sum", 1050, 60],
    ["fusion.7", STEP + "transpose(jvp(loss))/mul", 1110, 40],
    ["fusion.8", BWD + "lm_head/dot_general", 1150, 400],
    # jax.checkpoint's recomputation, inside the backward
    ["fusion.9", BWD + BODY + "checkpoint/rematted_computation/blocks/attn/"
     "qkv/dot_general", 1550, 400],
    # XLA's own: a forward instruction it runs again
    ["fusion.10.remat", FWD + BODY + "blocks/moe/moe._einsum_forward/"
     "dot_general", 1950, 100],
    ["fusion.11", BWD + BODY + "checkpoint/blocks/attn/qkv/dot_general",
     2050, 800],
    ["fusion.12", BWD + BODY + "checkpoint/blocks/mlp/wi/dot_general",
     2850, 500],
    ["fusion.13", BWD + "while/body/dynamic_slice", 3350, 30],
    ["fusion.14", STEP + "optimizer/update/mul", 3380, 300],
    # rematerialised inside the update: the update's, not the forward's
    ["fusion.15.remat", STEP + "optimizer/update/sqrt", 3680, 20],
    ["fusion.16", STEP + "optimizer/apply/add", 3700, 100],
    ["fusion.17", STEP + "grad_norm/reduce_sum", 3800, 50],
    # in no phase: an instruction the text gives no op_name, and a rope
    # table hoisted out of the differentiated function
    ["copy.1", "", 3850, 25],
    ["fusion.18", STEP + "blocks/attn/pow", 3875, 15],
]
IN_NO_PHASE = 25 + 15
MODULES = [["jit__train_step(1)", "", 0, 3890]]
TRACE = {"devices": {"/device:TPU:0": {"ops": ROWS, "modules": MODULES}},
         "host": []}


def without_scopes(row):
    """The row as the parent's program names it: no scope in train_lib."""
    name, op, start, dur = row
    for scope in ("optimizer/update/", "optimizer/apply/", "grad_norm/"):
        op = op.replace(scope, "")
    return [name, op.replace("jvp(loss)", "jvp()"), start, dur]


PARENT = {"devices": {"/device:TPU:0": {
    "ops": [without_scopes(r) for r in ROWS], "modules": MODULES,
}}, "host": []}
PHASES = ("forward_ms", "recompute_ms", "backward_ms", "optimizer_ms")
SIX = PHASES + ("head_loss_ms", "step_unnamed_ms")
CELLS = [
    "gpt2-1.5b.train_steady", "mixtral-8x7b.train_steady",
    "gpt2-1.5b.train_steady_x4", "olmoe-1b-7b.train_steady",
    "olmo-hybrid-7b.train_steady", "joyai-llm-flash.train_steady",
]


def ms(name, trace=TRACE):
    spec = layers.spec(name)
    assert spec["reader"] == "scope_ms"
    return scope_ms.read(
        {"trace": trace, "step_module": "train_step"}, spec["params"]
    )


@pytest.mark.parametrize("metric,nanoseconds", [
    # the scan's own 10, its two layers and its slice, the final norm, the
    # head and the loss's forward
    ("forward_ms", 10 + 400 + 300 + 90 + 50 + 200 + 60),
    # the checkpoint's marker, and the instruction named .remat
    ("recompute_ms", 400 + 100),
    # the loss's and the head's transposes, two layers, the scan's slice
    ("backward_ms", 40 + 400 + 800 + 500 + 30),
    # update (its own .remat with it), apply, the norm of the gradients
    ("optimizer_ms", 300 + 20 + 100 + 50),
    # final norm, head and loss forward; loss and head backward
    ("head_loss_ms", 50 + 200 + 60 + 40 + 400),
    # the scan's own time and its two slices, the copy with no op_name
    ("step_unnamed_ms", 10 + 90 + 30 + 25),
])
def test_each_metric_reads_its_rows(metric, nanoseconds):
    assert ms(metric) == pytest.approx(nanoseconds * 1e-6)


def test_the_four_phases_add_up_to_the_step():
    step = trace_reduce.reduce(TRACE, "train_step")["step_device_ms"]
    assert step == pytest.approx(3890e-6)
    assert sum(ms(name) for name in PHASES) == pytest.approx(
        step - IN_NO_PHASE * 1e-6
    )
    # and no row is in two of them
    for row in ROWS:
        label = f"{row[0]}@{row[1]}"
        hits = [n for n in PHASES if re.search(
            layers.spec(n)["params"]["match"], label
        )]
        assert len(hits) == (0 if row in ROWS[-2:] else 1), (label, hits)


def test_a_program_without_the_scopes_leaves_the_line_whole():
    """The parent's program: its update and its loss carry no name, so
    ``optimizer_ms`` finds nothing and is left out; the other five are
    there, the update's time now in no phase and unnamed."""
    assert ms("optimizer_ms", PARENT) is None
    entries = [m for m in build.manifest()["per_layer"] if m["name"] in SIX]
    line = layers.compute(
        {"per_layer": entries}, CELLS[0],
        {"trace": PARENT, "step_module": "train_step"},
    )
    assert set(line) == set(SIX) - {"optimizer_ms"}
    assert line["forward_ms"]["value"] == pytest.approx(1110e-6)
    assert line["recompute_ms"]["value"] == pytest.approx((500 + 20) * 1e-6)
    assert line["backward_ms"]["value"] == pytest.approx(1770e-6)
    assert line["head_loss_ms"]["value"] == pytest.approx(650e-6)
    assert line["step_unnamed_ms"]["value"] == pytest.approx(
        (155 + 300 + 20 + 100 + 50 + 60 + 40) * 1e-6
    )
    # no trace at all: nothing, and no error
    assert all(ms(name, None) is None for name in SIX)


@pytest.mark.parametrize("metric", SIX)
def test_the_five_cells_report_it(metric):
    """A superset check: a later cell joins the lists as a data change,
    with no edit here (JoyAI's did, PR 47)."""
    entry = {m["name"]: m for m in build.manifest()["per_layer"]}[metric]
    assert set(CELLS) <= set(entry["workloads"])
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "ms", "lower", "device_trace"
    )
    assert entry["layer"] == "step program"
    assert entry["moves"] == "tokens_per_s_chip"


@pytest.mark.parametrize("op_name,named", [
    # a state-space mixer's scopes and the layer's own norm carry a
    # layer's name (PR 47: the pattern knew no ``ssm`` and no bare ``ln``)
    (FWD + BODY + "period/mamba_0/ssm/in_proj/dot_general", True),
    (BWD + BODY + "checkpoint/period/mamba_0/ssm/scan/pallas_call", True),
    (FWD + BODY + "period/mamba_0/ssm/dt/softplus", True),
    (FWD + BODY + "period/mamba_0/ln/mul", True),
    (FWD + BODY + "blocks/ln_attn/mul", True),
    # a scan's own slices and an instruction with no op_name stay unnamed
    (FWD + "while/body/dynamic_slice", False),
    ("", False),
])
def test_step_unnamed_leaves_out_what_carries_a_layers_name(op_name, named):
    pattern = layers.spec("step_unnamed_ms")["params"]["match"]
    hit = re.search(pattern, f"fusion.1@{op_name}") is not None
    assert hit == (not named)
