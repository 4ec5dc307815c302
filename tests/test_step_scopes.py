"""The step program's names, held against the next change that adds work to
a step without one.

The device trace names instructions only; the per-layer metrics of the step
program (``benchmark/layer_metrics/{forward,recompute,backward,optimizer,
head_loss,step_unnamed}_ms.json``) read each instruction's ``op_name`` out of
the compiled step's text.  So for every tiny preset the compiled step must
carry ``train_lib.STEP_SCOPES`` where the preset has the mechanism, every
matmul, convolution and custom call must lie under a layer's or a scope's
name, and the four phases must not overlap."""

import functools
import os
import re
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import build, layers, trace_reduce  # noqa: E402
from dlrover_tpu.models.transformer import TransformerLM  # noqa: E402
from dlrover_tpu.parallel import rules as lr  # noqa: E402
from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh  # noqa: E402
from dlrover_tpu.trainer import train_lib  # noqa: E402

PRESETS = os.path.join(REPO, "tests", "benchmark_suite", "presets")
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


@functools.lru_cache(maxsize=None)
def compiled_step(preset, devices, zero1, grad_accum, engine=()):
    """``(compiled text, {instruction: op_name}, lowered text with its
    locations)`` of the preset's step; ``engine``: further options of
    ``build_sharded_train``, as pairs."""
    cfg = build.load_json(os.path.join(PRESETS, f"{preset}.json"))
    seq = cfg["run"]["seq_len"]
    batch = cfg["run"]["sequences_per_chip"] * devices * grad_accum
    mesh = build_mesh(
        ParallelConfig(data=-1), devices=jax.devices()[:devices]
    )
    train = train_lib.build_sharded_train(
        TransformerLM(build.transformer_config(build.model_group(cfg), seq)),
        train_lib.make_optimizer("adafactor", learning_rate=1e-3),
        mesh, lr.DEFAULT_RULES, global_batch_size=batch, seq_len=seq,
        zero1=zero1, grad_accum=grad_accum, **dict(engine),
    )
    with train_lib.use_mesh(mesh):
        state = jax.eval_shape(train.init_fn, train_lib._ABSTRACT_KEY)
        lowered = train.step_fn.lower(state, train.batch_avals)
        text = lowered.compile().as_text()
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
