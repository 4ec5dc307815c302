"""What the ``compile`` event says of the compiled step's kernels, read off
``ElasticTrainer``s of the benchmark's tiny presets: the flash backward's
path and the classes of its blocks, the form the short convolutions took;
and where the compilation's own seconds went, by its children.
A preset's trainer is built and compiled once, whichever case asks first,
and its event kept (``reference_harness.compile_event``)."""

import dataclasses

import pytest

import reference_harness as harness
from dlrover_tpu.ops import flash_attention


def preset_model(preset, seq=None):
    """(the preset's model at ``seq`` tokens or its own, that length)."""
    return harness.preset(preset, seq)[:2]


def trainer_of(preset, seq=None, vmem_cap=None, xla_attention=False):
    """What ``reference_harness`` keys the preset's trainer by: (model,
    sequence length, patches), the flash kernels' VMEM bound at ``vmem_cap``
    where given."""
    model, seq = preset_model(preset, seq)
    if xla_attention:
        model = dataclasses.replace(model, attention_impl="xla", remat="none")
    patches = ()
    if vmem_cap is not None:
        patches = ((flash_attention, "_VMEM_CAP", vmem_cap),)
    return model, seq, patches


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
    events = harness.compile_events(*trainer_of(preset, seq))
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
