"""The step program's names, held against the next change that adds work to
a step without one, and the lowered text of the steps that were.

The device trace names instructions only; the per-layer metrics of the step
program (``benchmark/layer_metrics/{forward,recompute,backward,optimizer,
head_loss,step_unnamed}_ms.json``) read each instruction's ``op_name`` out of
the compiled step's text.  So for every tiny preset the compiled step must
carry ``train_lib.STEP_SCOPES`` where the preset has the mechanism, every
matmul, convolution and custom call must lie under a layer's or a scope's
name, and the four phases must not overlap.

The pinned hashes of the presets' lowered steps are here as well (they
were ``tests/test_lowered_steps.py``'s, and are unedited): they read the
text of the lowering whose compiled form the cases above read, which
``reference_harness.lowered`` traces once a preset.

And what the ``compile`` event says of the compiled step's kernels
(``tests/test_compile_event.py``'s cases until PR 65): the flash backward's
path and the classes of its blocks, the form the short convolutions took,
and where the compilation's own seconds went, by its children.  A preset has
this ONE file, and its plain step ONE builder: where a case reads a preset's
event, the preset's step is a one-device trainer's
(``reference_harness.trained``), whose ``ShardedTrain`` the scopes' cases
and the pinned hashes lower; the other presets' steps are only lowered
(``reference_harness.built``).  Programs of their own have: ZeRO-1, two
microbatches and the int8 wires (other engines), ``xla`` attention and a
patched ``_VMEM_CAP`` (other kernels: faults that are structure), and
Nemotron's step at 128 tokens (the length at which its convolution is the
kernel's)."""

import dataclasses
import functools
import hashlib
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import reference_harness as harness  # noqa: E402
from benchmark import layers, trace_reduce  # noqa: E402
from dlrover_tpu.models.transformer import TransformerConfig  # noqa: E402
from dlrover_tpu.ops import flash_attention  # noqa: E402
from dlrover_tpu.trainer import train_lib  # noqa: E402

PHASES = ("forward_ms", "recompute_ms", "backward_ms", "optimizer_ms")
EVERY_STEP = {
    train_lib.OPTIMIZER_UPDATE, train_lib.OPTIMIZER_APPLY,
    train_lib.GRAD_NORM, train_lib.LOSS,
}
ZERO1 = {train_lib.OPTIMIZER_REDUCE, train_lib.OPTIMIZER_GATHER}
# Scopes the compiled text may lose: a sharding pin is no instruction (the
# collective the compiler puts there carries the name of the op whose result
# it moves, ``optimizer/apply`` for the gather), and the clip takes the same
# norm as ``grad_norm`` first, under ``optimizer/update``, of which equal
# computations the compiler keeps one.
MAY_VANISH = ZERO1 | {train_lib.GRAD_NORM}
# (preset, devices, ZeRO-1, microbatches): the five presets as their cells
# run them, and the mechanisms no one-chip cell has.
CASES = [
    ("gpt2-1.5b", 1, False, 1),
    ("mixtral-8x7b", 1, False, 1),
    ("olmoe-1b-7b", 1, False, 1),
    ("olmo-hybrid-7b", 1, False, 1),
    ("joyai-llm-flash", 1, False, 1),
    ("gpt2-1.5b", 2, True, 1),
    ("gpt2-1.5b", 1, False, 2),
    ("joyai-llm-flash", 2, True, 2),
]
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\s(dot|convolution|custom-call)\("
)


def pattern(metric):
    spec = layers.spec(metric)
    assert spec["reader"] == "scope_ms"
    return re.compile(spec["params"]["match"])


# The presets of which some case reads the ``compile`` event: their plain
# step is a trainer's, compiled once (a trainer also builds and runs the
# init program, which a preset that is only lowered or compiled need not).
TRAINED = {
    "gpt2-1.5b", "olmo-hybrid-7b", "mellum2-12b-a2.5b",
    "command-a-plus-05-2026", "nemotron-3-nano-30b-a3b",
}


def step_program(preset, devices=1, zero1=False, grad_accum=1, engine=()):
    """The preset's step (a ``ShardedTrain``), built once a process;
    ``engine``: further options of ``build_sharded_train``, as pairs."""
    model, seq, per_chip = harness.preset(preset)
    options = dict(engine)
    if zero1:
        options["zero1"] = True
    if grad_accum > 1:
        options["grad_accum"] = grad_accum
    if preset in TRAINED and devices == 1 and not options:
        return harness.trained(model, seq, per_chip)[1]
    return harness.built(
        model, batch=per_chip * devices * grad_accum, seq=seq,
        devices=devices, **options,
    )


def step_lowered(*case):
    """The preset's step, lowered once a process."""
    return harness.lowered(step_program(*case))


@functools.cache
def compiled_step(*case):
    """``(compiled text, {instruction: op_name}, lowered text with its
    locations)`` of ``step_lowered(*case)``."""
    lowered = step_lowered(*case)
    # a trainer's step is compiled already
    text = step_program(*case).compiled_step_text() or (
        lowered.compile().as_text()
    )
    return (
        text, trace_reduce.scopes_from_hlo(text),
        lowered.as_text(debug_info=True),
    )


def scopes_expected(preset, zero1, grad_accum):
    want = set(EVERY_STEP)
    if zero1:
        want |= ZERO1
    if grad_accum > 1:
        want.add(train_lib.GRAD_ACCUM)
    if preset == "joyai-llm-flash":
        want.add(train_lib.ROUTER_BIAS)
    return want


def holds(text, scope):
    """Whether ``text`` names ``scope``: as ``scope/`` outside the
    differentiated function, as ``jvp(scope)`` inside it."""
    return re.search(rf"(?<![\w.]){re.escape(scope)}[/)]", text) is not None


@pytest.mark.parametrize("preset,devices,zero1,grad_accum", CASES)
def test_the_step_scopes_are_where_the_mechanism_is(
    preset, devices, zero1, grad_accum
):
    _, scope_of, lowered = compiled_step(preset, devices, zero1, grad_accum)
    op_names = sorted(set(scope_of.values()))
    want = scopes_expected(preset, zero1, grad_accum)
    optimizer, head_loss = pattern("optimizer_ms"), pattern("head_loss_ms")
    for scope in train_lib.STEP_SCOPES:
        # what the program names, before any compiler has touched it
        assert holds(lowered, scope) == (scope in want), scope
        under = [op for op in op_names if holds(op, scope)]
        if scope not in want:
            assert not under, scope
        elif scope not in MAY_VANISH:
            assert under, scope
        # and what survives is read by the metric that is for it
        reader = head_loss if scope == train_lib.LOSS else optimizer
        assert all(reader.search(f"x@{op}") for op in under), scope


@pytest.mark.parametrize("engine,scope", [
    ((("reduce_quant", "int8"),), train_lib.OPTIMIZER_REDUCE),
    ((("overlap", True), ("allgather_quant", "int8")),
     train_lib.OPTIMIZER_GATHER),
])
def test_an_explicit_collective_keeps_its_scope(engine, scope):
    """No benchmarked cell reads ``optimizer/reduce`` or ``optimizer/gather``
    in a compiled ``op_name``: their pins are no instructions.  The int8
    wires (``parallel.overlap``'s gather, the quantized reduce-scatter) run
    their collectives themselves, inside the scope, and there the compiled
    text keeps it for ``optimizer_ms``."""
    _, scope_of, _ = compiled_step("gpt2-1.5b", 2, True, 1, engine)
    under = {op for op in scope_of.values() if holds(op, scope)}
    assert any("shard_map" in op for op in under), scope
    optimizer = pattern("optimizer_ms")
    assert all(optimizer.search(f"x@{op}") for op in under)


@pytest.mark.parametrize("preset,devices,zero1,grad_accum", CASES)
def test_no_matmul_is_unnamed(preset, devices, zero1, grad_accum):
    text, scope_of, _ = compiled_step(preset, devices, zero1, grad_accum)
    unnamed = pattern("step_unnamed_ms")
    seen = 0
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if not m:
            continue
        seen += 1
        label = f"{m.group(1)}@{scope_of.get(m.group(1), '')}"
        assert not unnamed.search(label), line[:300]
    assert seen


@pytest.mark.parametrize("preset,devices,zero1,grad_accum", CASES)
def test_the_phases_are_exclusive(preset, devices, zero1, grad_accum):
    _, scope_of, _ = compiled_step(preset, devices, zero1, grad_accum)
    phases = {name: pattern(name) for name in PHASES}
    counts = dict.fromkeys(PHASES, 0)
    for name, op in scope_of.items():
        hit = [p for p, rx in phases.items() if rx.search(f"{name}@{op}")]
        assert len(hit) <= 1, (name, op, hit)
        for p in hit:
            counts[p] += 1
    # every preset rematerialises (``flash_only``), so all four are met
    assert all(counts.values()), counts


# -- the lowered text of the steps that were ----------------------------------

# sha256 of the lowered step text (StableHLO; CPU; the benchmark's tiny
# presets; ``@name_<n>`` counters normalised) at the parent commit 04ce0df
# with PR 36's flash kernels, which every one of the four runs: no segment
# compare and no all-masked-row guards without ids or padding, an exact
# ``scale`` on the q tile, the forward of ONE kv block written straight out
# (all four were recorded anew; the other modules lower to what they did).
# A PR that changes these models' step on purpose records them anew.  PR 33
# left all four as they were: the flash kernels at ``d_qk == d_v``, the
# grouped GEMMs with every expert held (no dead blocks skipped), the router
# statistics without a share and the trunk without a dense prefix or an MTP
# module lower to what they lowered to.  PR 34 (the one-pass flash backward at
# several kv blocks) left three as they were: their tiny presets run ONE kv
# block (64 tokens in a block of 64), which lowers to the parent's kernel.
# ``olmo-hybrid-7b``'s preset sets blocks of 16 for its 64 tokens, four kv
# blocks: its two full layers' backward is now one kernel with a [64, 16]
# float32 dq scratch where it was two, so its text is recorded anew.  PR 51
# recorded ``olmo-hybrid-7b``'s anew and no other: the delta rule's kernels
# build ``(I + A)^-1`` on the rows that are not zero and advance a grid
# step's heads in lockstep (``ops/gated_delta_rule.py``), which is another
# kernel body and the same numbers; it read f0d80527...c1ed6ab5.  The other
# five texts (three here, two below) standing unedited is the proof that no
# other model's program moved.  PR 53 recorded ``olmoe-1b-7b``'s anew (and
# the two below): a grouped GEMM's forward/dx call of ONE contraction step
# writes the dot's result straight to its output block and keeps no float32
# accumulator (``ops/grouped_matmul.py`` ``_gmm_kernel``), another kernel
# body and the same numbers; it read 0d7f87bc...5623fd02.  With ``plan_tiles``
# alone (the tiles and the VMEM limit, the accumulator still there) all six
# texts stood unedited: no tiny preset's strip overflows the budget.  PR 55
# (the dW call's tile planned under a VMEM limit it asks for,
# ``plan_dw_tiles``) recorded none anew: every tiny preset's whole dW tile
# fits the default budget, keeps its tile and asks for nothing, so all six
# texts stand unedited, which is the proof that no small program moved.
LOWERED_AT_PARENT = {
    "gpt2-1.5b":
        "3fb5f6338781894bc6418780c92ff0224b12abbaddadeef5d7c79a740c9f4f92",
    "mixtral-8x7b":
        "a610499e04164995118fff59e041ffb9f8a82901625a1fddb1ebe83edd4790bb",
    "olmoe-1b-7b":
        "e4a8149a8e2cf5234188b2af863582c6a92a856d1b82420555f3a2a16ffcaa9a",
    "olmo-hybrid-7b":
        "d31949ba8911676ff7e5da8cb178c47edcc51ec0f2fba1b5676e1d934fc6a86c",
}


def lowered_step_text(preset):
    text = step_lowered(preset).as_text()
    return re.sub(r"@(\w+?)_\d+\b", r"@\1_N", text)


@pytest.mark.parametrize("preset", sorted(LOWERED_AT_PARENT))
def test_earlier_models_keep_their_lowered_step_text(preset):
    text = lowered_step_text(preset)
    assert hashlib.sha256(text.encode()).hexdigest() == (
        LOWERED_AT_PARENT[preset]
    )
    if preset != "olmo-hybrid-7b":
        assert "linear_attn" not in text and "delta" not in text
    # and none of them has met the DeepSeek-V3 family's parts
    for name in ("latent", "router_bias", "mtp", "moe_share_stats"):
        assert name not in text, name


# sha256 of the lowered step text of the two tiny presets that
# the table above does not hold (its ``lowered_step_text``): JoyAI-LLM-Flash's
# (latent attention, the sigmoid router, a share) and Nemotron's, whose text
# holds its scan kernels' grids and index maps (one tile a group).  Both are
# the texts since PR 45, which took the gather, its scatter and ``top_k``'s
# sort out of the sigmoid router (``models/moe.py::_gate``): at the parent
# 0ab4b77 they read 680dda30...835c48d (as at fed8b01: nothing else in the
# step had moved) and 1ac0af4f...e16b47b4 (PR 40's scan kernels); the
# presets that route by softmax or not at all, the four above and Granite's,
# kept the parent's texts (CHANGES.md, PR 45).  PR 53 recorded both anew for
# the grouped GEMMs' one-step body (the note above): they read
# 63af0acf...9706c9955 and 80d960d2...39bf378.  Whoever edits the router, the
# scan kernels or the grouped GEMMs next re-pins them.  PR 56 recorded the
# other four presets' texts at its parent ebf9455, BEFORE it moved the fold of
# the layers' sown statistics and the layers' construction behind each
# family's declaration (``models/family.py``): Granite's (the scan kernels at
# sixteen tiles a group, the four multipliers), Ling's (the per-channel delta
# rule, the group-limited router), LFM2's (the gated short convolution) and
# Mellum2's (the banded flash kernels, two rotations, ``attn_stats``).  All
# ten texts standing unedited after it is the CPU's certificate that the chip
# runs the programs it ran.
LATER_PRESETS_LOWERED = {
    "joyai-llm-flash":
        "c92d3a044315cdf4b513e023bed00f6fb542564c765d739e3971ef4b5ee5b0bf",
    "nemotron-3-nano-30b-a3b":
        "cedfb9810641b29ef3ef7d973511726f2eb95ded9dec29a377f2de3c46aea18c",
    "granite-4.0-h-small":
        "f5b5219e173325e666075902a50e7d675076e9d75f6792f38ec208ace70a5ec3",
    "ling-3.0-flash-vl":
        "32e2f1afb936167fe50c9c8145e1a6899033877334ad15a3c81e8d1968cd8df0",
    "lfm2-8b-a1b":
        "e7bc689f6c5100b1ca8b4f1ba6b06b0b5e8e6b2b3ad6251b7f902de9d9ef0364",
    "mellum2-12b-a2.5b":
        "9a4487fbfeccc099066a58a2f909ef0c77e5c58a72bb9845006caa9d2b8e8b67",
}


# PR 62 recorded GLM-5.2's (sparse attention over an indexer's choice: the
# count of 32 bits, the masked ``jax.numpy`` form at the preset's 64 tokens,
# the KL term's scan, the choice carried through the dense prefix, the
# period and the MTP module) and no other: the ten texts above stand unedited
# after ``LatentAttention``'s projections moved into two plain functions
# (``models/attention.py`` ``latent_qkv`` / ``latent_output``), which is the
# CPU's certificate that JoyAI's and Ling's chips run the programs they ran.
SPARSE_PRESETS_LOWERED = {
    "glm-5.2":
        "5c816a90aace175b5cd365b668699bcd9414ba5a6129bef595100d632848d2b5",
}


@pytest.mark.parametrize("preset", sorted(SPARSE_PRESETS_LOWERED))
def test_the_sparse_attention_preset_keeps_its_lowered_step_text(preset):
    text = lowered_step_text(preset)
    assert hashlib.sha256(text.encode()).hexdigest() == (
        SPARSE_PRESETS_LOWERED[preset]
    )
    # the names only this family's programs hold
    for name in ("index_stats", "select", "index_kl"):
        assert name in step_lowered(preset).as_text(debug_info=True), name


# PR 64 recorded Solar-Open2's (Kimi Delta Attention under the published
# gate, which has no lower bound: the rule's exact form, the triangle cut by
# halves, here as the chunked ``jax.numpy`` form at the preset's heads of
# 16; the two low-rank pairs under ``gates``; the element-wise gate on
# position-free grouped-query attention) and no other.  Ling's text above
# stands unedited after ``ops/kda.py``'s pair products moved into two
# functions a form and ``KimiDeltaAttention`` learnt the published gate,
# which is the CPU's certificate that Ling's chip runs the program it ran.
FREE_GATE_PRESETS_LOWERED = {
    "solar-open2-250b":
        "58c62fc0451fddaa117952e86cc385e9b0a197fdccc9a96a0eb4527daeb9bd23",
}


@pytest.mark.parametrize("preset", sorted(FREE_GATE_PRESETS_LOWERED))
def test_the_free_gate_preset_keeps_its_lowered_step_text(preset):
    text = lowered_step_text(preset)
    assert hashlib.sha256(text.encode()).hexdigest() == (
        FREE_GATE_PRESETS_LOWERED[preset]
    )
    # the names only this model's programs hold
    named = step_lowered(preset).as_text(debug_info=True)
    for name in ("linear_attn/gates", "attn/gate", "f_down", "g_up",
                 "softplus"):
        assert name in named, name
    assert "rope" not in named and "router_bias" not in named


@pytest.mark.parametrize("preset", sorted(LATER_PRESETS_LOWERED))
def test_the_multipliers_and_the_tiles_default_to_nothing(preset):
    """A config that names none of the four multipliers, and a scan whose
    group is one grid step, lower to the step they lowered to (the other
    four pinned texts are held above); so do the four presets recorded
    since."""
    cfg = TransformerConfig()
    assert (cfg.embed_scale, cfg.attention_scale, cfg.residual_scale,
            cfg.logit_scale) == (1.0, 0.0, 1.0, 1.0)
    text = lowered_step_text(preset)
    assert hashlib.sha256(text.encode()).hexdigest() == (
        LATER_PRESETS_LOWERED[preset]
    )


# -- what the ``compile`` event says of the compiled step ----------------------


def preset_model(preset, seq=None):
    """(the preset's model at ``seq`` tokens or its own, that length)."""
    return harness.preset(preset, seq)[:2]


def trainer_of(preset, seq=None, vmem_cap=None, xla_attention=False):
    """What ``reference_harness`` keys the preset's trainer by: (model,
    sequence length, sequences a step, patches), the flash kernels' VMEM
    bound at ``vmem_cap`` where given."""
    model, seq, per_chip = harness.preset(preset, seq)
    if xla_attention:
        model = dataclasses.replace(model, attention_impl="xla", remat="none")
    patches = ()
    if vmem_cap is not None:
        patches = ((flash_attention, "_VMEM_CAP", vmem_cap),)
    return model, seq, per_chip, patches


def compile_event(preset, seq=None, **how):
    """The attributes of the ``compile`` event of the preset's trainer."""
    return harness.compile_event(*trainer_of(preset, seq, **how))


@pytest.mark.parametrize("preset,blocks,vmem_cap,path,classes", [
    # one kv block: no dq scratch, and one diagonal block a (batch, head)
    ("gpt2-1.5b", 1, None, "fused", (0, 0, 1)),
    # several: dq in VMEM scratch; 6 dead, 6 interior, 4 diagonal
    ("olmo-hybrid-7b", 4, None, "fused", (6, 6, 4)),
    ("olmo-hybrid-7b", 4, 1 << 16, "split", (6, 6, 4)),   # past the bound
    ("gpt2-1.5b", 1, 1 << 16, "fused", (0, 0, 1)),
])
def test_compile_event_names_the_flash_backward(
    preset, blocks, vmem_cap, path, classes
):
    """The path and the blocks' classes are facts of the compiled step: the
    ``compile`` event names them, from the functions the dispatch asks
    (``xla`` attention: ``none``, and no blocks)."""
    model, seq = preset_model(preset)
    assert model.attention_impl == "flash"
    assert seq // min(seq, model.flash_block_kv) == blocks

    def flash_facts(**kw):
        event = compile_event(preset, vmem_cap=vmem_cap, **kw)
        return event["flash_backward"], event["flash_blocks"]

    strip = flash_attention.block_classes(
        seq, seq, model.flash_block_q, model.flash_block_kv, True
    ).strip
    assert flash_facts() == (path, dict(zip(
        ("dead", "interior", "diagonal", "strip"), (*classes, strip)
    )))
    if vmem_cap is None:
        assert flash_facts(xla_attention=True) == ("none", None)


@pytest.mark.parametrize("preset", [
    "mellum2-12b-a2.5b", "command-a-plus-05-2026",
])
def test_compile_event_names_the_banded_kernels_tiles(preset):
    """A model with windowed layers says three more facts of its BANDED
    kernels, under ``sliding_attention`` and nowhere else (the full layers'
    dict and a plain model's four counts above keep their keys): the rows
    of a lower-edge block's strips, the live pairs among the pairs the
    kernels' tiles work, and that a block's strips run in lockstep."""
    model, seq = preset_model(preset)
    blocks = compile_event(preset)["flash_blocks"]
    band, full = blocks["sliding_attention"], blocks["full_attention"]
    assert set(band) - set(full) == {
        "lower_strip", "tile_live_share", "lockstep"
    }
    assert not set(full) - set(band)
    block, window = model.flash_block_kv, model.sliding_window
    assert (seq, block, window) == (64, 16, 24)
    # blocks of 16 hold no strip: every live block is worked whole
    assert (band["strip"], band["lower_strip"]) == (0, 0)
    live_pairs = sum(min(i + 1, window) for i in range(seq))
    assert band["tile_live_share"] == live_pairs / (
        band["live"] * block * block
    )
    assert band["lockstep"] is True


@pytest.mark.parametrize("preset,seq,path", [
    # Nemotron-like: the tiny preset's layers on one whole lane tile of
    # tokens (x | B | C = 256 | 32 | 32 channels: whole row tiles)
    ("nemotron-3-nano-30b-a3b", 128, "kernel"),
    ("nemotron-3-nano-30b-a3b", None, "xla"),      # the preset's 64 tokens
    ("olmo-hybrid-7b", None, "xla"),
    ("gpt2-1.5b", None, "none"),
])
def test_compile_event_names_the_short_conv(preset, seq, path):
    """Beside ``test_compile_event_names_the_flash_backward``: which form
    the step's convolutions took is a fact of the compiled step."""
    assert compile_event(preset, seq)["short_conv"] == path


STAGES = ("trace", "lower", "backend", "analysis")


@pytest.mark.parametrize("preset,seq", [
    ("gpt2-1.5b", None),
    ("olmo-hybrid-7b", None),
    ("nemotron-3-nano-30b-a3b", 128),
])
def test_compile_event_names_its_parts(preset, seq):
    """The event's seconds are split where the work happens: four children,
    once each, that add up to them; the text pass beside them; and the one
    executable built, booked under ``compile.backend``."""
    events, _ = harness.trained(*trainer_of(preset, seq))
    (whole,) = [e for e in events if e[0] == "compile"]
    attrs = whole[4]
    assert whole[3] == pytest.approx(attrs["seconds"], abs=1e-5)
    assert attrs["id"] == "restart:0" and "parent" not in attrs
    children = {
        e[0]: e for e in events if e[4].get("parent") == "compile"
    }
    assert sorted(children) == sorted(
        f"compile.{stage}" for stage in (*STAGES, "text")
    )
    assert len([e for e in events if e[0] in children]) == 5   # once each
    for name, (_, kind, _, seconds, said) in children.items():
        assert kind == "span" and said["id"] == "restart:0"
        stage = name.split(".")[1]
        assert seconds == pytest.approx(attrs[f"{stage}_s"], abs=1e-3)
        if stage in ("trace", "lower", "backend"):
            assert said["fun_name"] == "_train_step"
    in_seconds = sum(attrs[f"{stage}_s"] for stage in STAGES)
    assert in_seconds == pytest.approx(
        attrs["seconds"], abs=max(0.05, 0.01 * attrs["seconds"])
    )
    assert in_seconds <= attrs["seconds"] and attrs["text_s"] > 0
    # the text pass comes after the seconds, not inside them
    text = children["compile.text"]
    assert text[2] >= whole[2] + whole[3] - 1e-3
    # the CPU keeps no persistent cache: compiled, nothing written
    assert attrs["cache"] == children["compile.backend"][4]["cache"] == "off"
    (built,) = [
        e for e in events if e[0] == "jax.compile"
        and e[4].get("parent") == "compile.backend"
    ]
    assert built[4]["fun_name"] == "jit(_train_step)"
    assert built[4]["id"] == "restart:0" and built[4]["cache"] == "off"
    assert built[3] <= children["compile.backend"][3]
    # start-up's own executables fall where they are built
    assert [
        e for e in events if e[0] == "jax.compile"
        and e[4].get("parent") == "startup.init"
    ]
