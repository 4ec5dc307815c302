"""What the Mellum2-12B-A2.5B cell brings to the benchmark: its program
against its file, its own plain reference against the repository's, the
arithmetic of its cost module by hand, and its readers on a recorded list of
op names.  (The file against the catalog is ``tests/test_mellum_config.py``'s;
the rehearsals of the cell through its new traffic file are
``test_benchmark_rehearsal.py``'s and ``test_benchmark_program_spans.py``'s,
which run every cell of the manifest.)  Membership assertions only: never a
list's last place or its whole content, so that the next cell to join a list
breaks nothing here."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import build, flops, flops_window_moe, layers  # noqa: E402
from benchmark.readers import (  # noqa: E402
    evidence_value,
    kernel_roofline_from,
    mfu_from,
    program_events,
    scope_ms,
)

NAME = "mellum2-12b-a2.5b"
CONFIG = os.path.join(REPO, "benchmark", "configs", f"{NAME}.json")
PRESET = os.path.join(HERE, "presets", f"{NAME}.json")
TRAFFIC = os.path.join(REPO, "benchmark", "traffic", "train_long_own_ref.json")
CELL = f"{NAME}.train_long"
MODULE = "flops_window_moe"
SEQ = 32768


def cell_model():
    return build.model_group(build.load_json(CONFIG))


def test_the_program_takes_the_configuration_and_the_traffic():
    from dlrover_tpu.models.moe import _share_row_budget
    from dlrover_tpu.ops import row_gather_sum

    config, traffic = build.load_json(CONFIG), build.load_json(TRAFFIC)
    assert (build.seq_len(config, traffic), build.global_batch(
        config, traffic, 1
    )) in ((32768, 1), (16384, 2))  # the cell, or the issue's one fallback
    assert config["run"] == traffic["run"]
    cfg = build.transformer_config(cell_model(), SEQ)
    assert cfg.num_params() == 1_077_018_624 == config["num_params"]
    assert (cfg.num_sliding_layers, cfg.num_full_layers) == (6, 2)
    assert cfg.max_seq_len == SEQ and cfg.num_scan_units == 2
    # the traffic file is train_steady_own_ref's but for one long sequence
    # and a check on one
    steady = build.load_json(os.path.join(
        REPO, "benchmark", "traffic", "train_steady_own_ref.json"
    ))
    differ = {"name", "what", "reference_sequences", "run", "rehearsal"}
    assert {k: v for k, v in traffic.items() if k not in differ} == {
        k: v for k, v in steady.items() if k not in differ
    }
    assert traffic["reference_sequences"] == 1
    assert traffic["scenario"] == "train_steady_own_ref"
    # the rows set aside for a layer's share at 1 x 32768 tokens: 1.25 x
    # 65,536 expected + a block of 128 an expert + the zero block
    assert _share_row_budget(SEQ * 8, 128, 16, 64, 1.25) == 84_096
    # rows of 2,304 are 18 lane tiles, no whole native tiles: padded to 24
    assert not row_gather_sum.kernel_fits(2304, 8, "bfloat16")
    assert row_gather_sum.padded_width(2304, 8, "bfloat16") == 3072
    assert 896 == 7 * 128 and cfg.resolved_moe_d_ff == 896


def test_the_preset_is_the_cell_in_small():
    config, preset = build.load_json(CONFIG), build.load_json(PRESET)
    assert set(preset) == set(config)
    for group in ("to_program", "trainer"):
        assert preset[group] == config[group]
    varies = ("param_dtype", "dtype", "moe_row_budget", "flash_block_q",
              "flash_block_kv")
    assert {
        k: v for k, v in preset["program"].items() if k not in varies
    } == {
        k: v for k, v in config["program"].items() if k not in varies
    }
    assert preset["reference_module"] == config["reference_module"]
    seq = build.seq_len(preset, build.load_json(TRAFFIC)["rehearsal"])
    cfg = build.transformer_config(build.model_group(preset), seq)
    # the same kinds: two periods, a window smaller than the sequence, YaRN
    # past a small original length, 16 of 64 experts, a sliced untied head
    assert cfg.num_scan_units == 2 and not cfg.tie_embeddings
    assert cfg.layer_pattern == ("sliding_attention",) * 3 + (
        "full_attention",
    )
    assert cfg.sliding_window < seq
    assert cfg.rope_scaling == "yarn"
    assert cfg.rope_original_max_position < seq
    assert (cfg.num_experts, cfg.resolved_experts_held) == (64, 16)


@pytest.fixture(scope="module")
def preset_case():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models.transformer import TransformerLM

    config = build.load_json(PRESET)
    model = build.model_group(config)
    seq = build.seq_len(config, {})
    rows = jnp.asarray(
        np.random.default_rng(3).integers(0, config["token_vocab"],
                                          (2, seq + 1)),
        jnp.int32,
    )
    lm = TransformerLM(build.transformer_config(model, seq))
    params = nn.meta.unbox(
        jax.jit(lm.init)(jax.random.PRNGKey(3), rows[:, :-1])
    )
    return model, params["params"], rows[:, :-1], rows[:, 1:]


def test_the_benchmarks_reference_agrees_with_the_repositorys(preset_case):
    import numpy as np

    from benchmark.references import mellum as ours
    from dlrover_tpu.models.references import mellum as theirs

    with open(ours.__file__) as a, open(theirs.__file__) as b:
        assert a.read() == b.read()        # one text in both places
    model, params, inputs, targets = preset_case
    exact = np.asarray(ours.token_nll(model, params, inputs, targets))
    np.testing.assert_allclose(
        theirs.token_nll(model, params, inputs, targets), exact, atol=2e-5
    )
    # what ``reference_tolerance`` is set against (PERF.md §6): the
    # reference with its router, its attention, or all of it, in bfloat16
    gaps = {
        mode: float(np.abs(np.asarray(ours.token_nll(
            model, params, inputs, targets, lowered=mode
        )) - exact).mean())
        for mode in ("router", "attention", "all")
    }
    assert 0 < gaps["router"] < gaps["all"]
    assert 0 < gaps["attention"] < gaps["all"]


def test_the_flops_of_a_token_by_hand():
    model = cell_model()
    assert flops_window_moe.layer_counts(model) == {
        "sliding_attention": 6, "full_attention": 2,
    }
    attn = 2 * 2304 * 4096 + 2 * 2304 * 512
    assert flops_window_moe.attention_projection_params(model) == attn
    assert flops_window_moe.pairs_here_per_token(model) == 2.0
    # live pairs: the triangle, and the band inside it
    assert flops_window_moe.live_pairs(SEQ) == SEQ * (SEQ + 1) / 2
    band = flops_window_moe.live_pairs(SEQ, 1024)
    assert band == 1024 * SEQ - 1024 * 1023 / 2 == 33_030_656
    assert flops_window_moe.live_pairs(512, 1024) == 512 * 513 / 2
    parts = flops_window_moe.flops_per_token_by_part(model, SEQ)
    pair = 6.0 * 32 * 2 * 128 / SEQ
    assert parts == {
        "attention_projections": 6.0 * 8 * attn,
        "full_attention": pair * 2 * SEQ * (SEQ + 1) / 2,
        "sliding_attention": pair * 6 * band,
        "routed_here": 6.0 * 8 * 2.0 * 3 * 2304 * 896,
        "router": 6.0 * 8 * 2304 * 64,
        "head": 6.0 * 24576 * 2304,
    }
    total = flops_window_moe.model_flops_per_token(model, SEQ)
    assert total == sum(parts.values())
    share = {k: v / total for k, v in parts.items()}
    # ISSUE 54's count: matmuls 327 M and scores and values 331 M forward
    # multiply-adds a token; attention's kernels about 47% of the step's
    # FLOPs (the full layers 38%, the six banded calls 9%)
    matmuls = total / 6 - (
        parts["full_attention"] + parts["sliding_attention"]
    ) / 6
    assert matmuls == pytest.approx(327e6, rel=5e-3)
    assert share["full_attention"] == pytest.approx(0.41, abs=0.01)
    assert share["sliding_attention"] == pytest.approx(0.076, abs=0.005)
    # were the band only masked and not skipped, the step's FLOPs
    masked = total + parts["full_attention"] * 3 - parts["sliding_attention"]
    assert 1.9 < masked / total < 2.3
    for missing in ("layer_pattern", "num_experts", "top_k",
                    "sliding_window"):
        with pytest.raises(KeyError):
            flops_window_moe.model_flops_per_token(
                {k: v for k, v in model.items() if k != missing}, SEQ
            )
    for other in ("gpt2-1.5b", "olmo-hybrid-7b", "joyai-llm-flash",
                  "lfm2-8b-a1b", "ling-3.0-flash-vl"):
        group = build.model_group(build.load_json(
            os.path.join(REPO, "benchmark", "configs", f"{other}.json")
        ))
        with pytest.raises(KeyError):
            flops_window_moe.model_flops_per_token(group, SEQ)


def test_the_kernel_costs_by_hand():
    model = cell_model()
    peak = build.peak_for("TPU v5 lite")
    band = flops_window_moe.band_flash_cost(model, SEQ, 1)
    full = flops_window_moe.full_flash_cost(model, SEQ, 1)
    assert band["flops"] == 7 * 2.0 * 33_030_656 * 128 * 32 * 6
    assert full["flops"] == 7 * 2.0 * (SEQ * (SEQ + 1) / 2) * 128 * 32 * 2
    row = 2.0 * SEQ * 128
    a_layer = (
        row * (2 * 32 + 2 * 4) + row * (4 * 32 + 4 * 4) + 2 * 4.0 * SEQ * 32
    )
    assert band["bytes"] == 6 * a_layer and full["bytes"] == 2 * a_layer
    assert flops.roofline_seconds(full, peak)["bound"] == "compute"
    assert flops.roofline_seconds(band, peak)["bound"] == "compute"
    # a banded layer's floor is a sixteenth of a full layer's
    assert (full["flops"] / 2) / (band["flops"] / 6) == pytest.approx(
        16.25, rel=1e-2
    )
    held = flops_window_moe.held_expert_matmul_cost(model, SEQ, 1)
    assert held["flops"] == 3 * 3 * 2.0 * 65536 * 2304 * 896 * 8
    weights = 2.0 * 16 * 3 * 2304 * 896
    acts = 2.0 * 65536 * (2 * 2304 + 3 * 896)
    assert held["bytes"] == 3 * (weights + acts) * 8


STEP = "jit(_train_step)/"
BACK = STEP + "transpose(jvp())/"
ROWS = [
    ["while.3", "", 0, 6000],
    ["fusion.1", STEP + "blocks/sliding_0/attn/query/dot_general", 0, 200],
    ["attn.1", STEP + "blocks/sliding_0/attn/pallas_call", 200, 100],
    ["attn.2", BACK + "blocks/sliding_2/attn/pallas_call", 300, 150],
    ["fusion.2", STEP + "blocks/sliding_1/attn/out/dot_general", 450, 50],
    ["attn.3", STEP + "blocks/full_3/attn/pallas_call", 500, 700],
    ["attn.4", BACK + "blocks/full_3/attn/pallas_call", 1200, 1300],
    ["fusion.3", STEP + "blocks/full_3/attn/key/dot_general", 2500, 100],
    ["fusion.4", STEP + "blocks/sliding_1/moe/router/dot_general", 2600, 30],
    ["gmm.1", STEP + "blocks/sliding_1/moe/gmm_wi/pallas_call", 2630, 300],
    ["gmm.2", BACK + "blocks/full_3/moe/gmm_wo/pallas_call", 2930, 100],
    ["fusion.5", STEP + "lm_head/dot_general", 3030, 250],
    # another model's attention is under neither slot name
    ["attn.5", STEP + "blocks/attn/pallas_call", 3300, 50],
]
TRACE = {"devices": {"/device:TPU:0": {
    "ops": ROWS[:-1], "modules": [["jit__train_step(1)", "", 0, 6000]],
}}, "host": []}
OTHER = {"devices": {"/device:TPU:0": {
    "ops": [ROWS[0 * 1], ROWS[-1]][1:] + [ROWS[0]],
    "modules": TRACE["devices"]["/device:TPU:0"]["modules"],
}}, "host": []}


def evidence(trace=TRACE, **more):
    return dict({
        "trace": trace, "step_module": "train_step", "model": cell_model(),
        "seq_len": SEQ, "sequences_per_chip": 1,
        "peak": build.peak_for("TPU v5 lite"),
    }, **more)


def test_the_scope_patterns_on_a_recorded_list_of_op_names():
    def ms(name, trace=TRACE):
        spec = layers.spec(name)
        assert spec["reader"] == "scope_ms"
        return scope_ms.read(evidence(trace), spec["params"])

    # projections and kernels, forward and transposed, of each kind
    assert ms("sliding_attn_ms") == pytest.approx(500e-6)
    assert ms("full_attn_ms") == pytest.approx(2100e-6)
    for name in ("sliding_attn_ms", "full_attn_ms"):
        assert ms(name, OTHER) is None


def test_the_rooflines_read_their_own_ops_against_their_own_cost():
    peak = build.peak_for("TPU v5 lite")
    model = cell_model()
    cases = (
        ("band_flash_roofline", "band_flash_cost", 250e-9),
        ("full_flash_roofline", "full_flash_cost", 2000e-9),
        ("window_moe_grouped_matmul_roofline", "held_expert_matmul_cost",
         400e-9),
    )
    for name, cost, seconds in cases:
        spec = layers.spec(name)
        assert spec["reader"] == "kernel_roofline_from", name
        assert spec["params"]["module"] == MODULE
        assert spec["params"]["cost"] == cost
        floor = flops.roofline_seconds(
            getattr(flops_window_moe, cost)(model, SEQ, 1), peak
        )["seconds"]
        assert kernel_roofline_from.read(
            evidence(), spec["params"]
        ) == pytest.approx(100 * floor / seconds), name
        params = spec["params"]
        assert kernel_roofline_from.read(evidence(OTHER), params) is None
        assert kernel_roofline_from.read(evidence(peak=None), params) is None
        assert kernel_roofline_from.read({}, params) is None
        older = dict(model, layer_pattern=["full_attention"])
        assert kernel_roofline_from.read(
            evidence(model=older), params
        ) is None


def test_the_step_mfu_counts_by_part_and_leaves_other_models_alone():
    spec = layers.spec("window_moe_step_mfu")
    assert spec["reader"] == "mfu_from" and spec["params"] == {
        "module": MODULE
    }
    model = cell_model()
    summary = {"tokens_per_s_chip": 18000.0}
    got = mfu_from.read(evidence(summary=summary), spec["params"])
    per_token = flops_window_moe.model_flops_per_token(model, SEQ)
    assert got == pytest.approx(per_token * 18000.0 / 197e12)
    assert 0.1 < got < 0.7
    for other in ("gpt2-1.5b", "olmo-hybrid-7b", "lfm2-8b-a1b"):
        group = build.model_group(build.load_json(
            os.path.join(REPO, "benchmark", "configs", f"{other}.json")
        ))
        assert mfu_from.read(
            evidence(summary=summary, model=group), spec["params"]
        ) is None, other
    assert mfu_from.read(evidence(), spec["params"]) is None   # no summary


def test_the_band_s_grid_and_the_scores_are_read_from_the_program_s_events():
    spec = layers.spec("band_grid_live_share")
    assert spec["reader"] == "evidence_value"
    blocks = {"sliding_attention": {"live": 63, "grid": 64,
                                    "live_share": 63 / 64}}
    assert evidence_value.read(
        {"compile": {"flash_blocks": blocks}}, spec["params"]
    ) == 63 / 64
    # a program whose compile event counts no kinds (the parent, another
    # model: four counts, or none) gives nothing
    for older in ({"dead": 6, "interior": 6, "diagonal": 4, "strip": 256},
                  None):
        assert evidence_value.read(
            {"compile": {"flash_blocks": older}}, spec["params"]
        ) is None
    assert evidence_value.read({}, spec["params"]) is None
    spec = layers.spec("attn_score_bound")
    assert spec["reader"] == "program_events"
    assert spec["params"] == {
        "name": "attn", "attr": "score_bound", "reduce": "max"
    }
    assert program_events.read({}, spec["params"]) is None


OWN = ("sliding_attn_ms", "full_attn_ms", "band_flash_roofline",
       "full_flash_roofline", "window_moe_grouped_matmul_roofline",
       "window_moe_step_mfu", "band_grid_live_share", "attn_score_bound")
JOINED = ("host_step_gap_ms", "step_s_worst_over_median",
          "tokens_per_s_chip_median_step", "data_wait_ms",
          "data_wait_span_ms", "step_device_ms", "device_idle_share",
          "peak_hbm_gib", "startup_to_mesh_s", "compile_trace_s",
          "compile_lower_s", "compile_backend_s", "compile_text_s",
          "startup_build_s", "startup_init_s", "forward_ms", "recompute_ms",
          "backward_ms", "optimizer_ms", "head_loss_ms", "step_unnamed_ms",
          "moe_pad_share", "moe_max_expert_load", "moe_pairs_here",
          "moe_row_move_ms", "moe_row_gather_ms", "moe_router_ms",
          "moe_dispatch_ms")
NOT_JOINED = ("router_bias_absmax", "conv_mixer_ms", "conv_core_roofline",
              "conv_moe_flash_roofline", "conv_moe_step_mfu", "step_mfu",
              "flash_roofline", "flash_attn_roofline",
              "pattern_flash_roofline", "latent_flash_roofline",
              "kda_latent_flash_roofline", "ssm_moe_flash_roofline",
              "held_grouped_matmul_roofline", "shared_expert_ms", "mtp_ms")
SETUP = ("startup_to_mesh_s", "compile_trace_s", "compile_lower_s",
         "compile_backend_s", "compile_text_s", "startup_build_s",
         "startup_init_s")


@pytest.mark.parametrize("name", OWN + JOINED)
def test_the_cell_is_in_the_list(name):
    entry = {m["name"]: m for m in build.manifest()["per_layer"]}[name]
    assert CELL in entry["workloads"]
    assert entry["moves"] == (
        "setup_s" if name in SETUP else "tokens_per_s_chip"
    )
    assert layers.spec(name)["name"] == name
    if name in OWN:
        assert entry["workloads"][0] == CELL
        if name.endswith("_roofline"):
            assert entry["unit"] == "%" and entry["layer"] == "kernels"


@pytest.mark.parametrize("name", NOT_JOINED)
def test_the_cell_is_not_in_a_list_whose_cost_or_pattern_is_anothers(name):
    entry = {m["name"]: m for m in build.manifest()["per_layer"]}[name]
    assert CELL not in entry["workloads"]


def test_the_cell_reports_the_rate_on_one_chip():
    e2e = {m["name"]: m for m in build.manifest()["end_to_end"]}
    assert CELL in e2e["tokens_per_s_chip"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    assert CELL not in e2e["save_stall_s"]["workloads"]
    cell = {w["name"]: w for w in build.manifest()["workloads"]}[CELL]
    assert cell["traffic"] == "train_long_own_ref" and cell["chips"] == 1
    assert cell["config"] == NAME
    assert "4,096 rows" in cell["why"] and "16,384" in cell["why"]
    reported = {m["name"] for m in layers.cell_entries(
        build.manifest(), CELL, "per_layer"
    )}
    assert set(OWN + JOINED) <= reported
    assert "compile_s" in reported
    cells = build.manifest()["workloads"]
    assert len(cells) >= 12
    assert sum(w["chips"] == 4 for w in cells) <= len(cells) // 4
