"""What the Command A+ cell brings to the benchmark: its program against its
file, its own plain reference against the repository's, the arithmetic of
its cost module by hand, and its readers on a recorded list of op names.
(The file against the catalog is ``tests/test_command_a_config.py``'s; the
rehearsals of the cell through its new traffic file are
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

from benchmark import (  # noqa: E402
    build,
    flops,
    flops_parallel_moe,
    flops_window_moe,
    layers,
)
from benchmark.readers import (  # noqa: E402
    evidence_value,
    kernel_roofline_from,
    mfu_from,
    scope_ms,
)

NAME = "command-a-plus-05-2026"
CONFIG = os.path.join(REPO, "benchmark", "configs", f"{NAME}.json")
PRESET = os.path.join(HERE, "presets", f"{NAME}.json")
TRAFFIC = os.path.join(REPO, "benchmark", "traffic", "train_16k_own_ref.json")
CELL = f"{NAME}.train_16k"
MODULE = "flops_parallel_moe"
SEQ = 16384


def cell_model():
    return build.model_group(build.load_json(CONFIG))


def test_the_program_takes_the_configuration_and_the_traffic():
    from dlrover_tpu.models.moe import _share_row_budget
    from dlrover_tpu.models.transformer import kernel_facts
    from dlrover_tpu.ops import row_gather_sum

    config, traffic = build.load_json(CONFIG), build.load_json(TRAFFIC)
    assert (build.seq_len(config, traffic), build.global_batch(
        config, traffic, 1
    )) in ((16384, 1), (8192, 1))   # the cell, or the issue's one fallback
    assert config["run"] == traffic["run"]
    cfg = build.transformer_config(cell_model(), SEQ)
    assert cfg.num_params() == 2_090_860_544 == config["num_params"]
    assert (cfg.num_sliding_layers, cfg.num_full_layers) == (3, 1)
    assert cfg.max_seq_len == SEQ and cfg.num_scan_units == 1
    # the traffic file is train_steady_own_ref's but for one 16k sequence
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
    # the rows set aside for a layer's share at 1 x 16384 tokens: 1.25 x
    # 8,192 expected + a block of 128 an expert + the zero block
    assert _share_row_budget(SEQ * 8, 128, 8, 128, 1.25) == 11_392
    # rows of 4,096 are 32 lane tiles, whole native tiles: the row kernel fits
    assert row_gather_sum.kernel_fits(4096, 8, "bfloat16")
    facts = kernel_facts(cfg, SEQ)
    assert facts["row_moves"] == "kernel_live"
    # what the plans choose at 4,096 x 4,096 (PERF.md §5): the three calls
    # OUT of the expert width split K; the weight gradients are eight tiles
    assert facts["gmm_strips"] == "split_k:3/6"
    assert facts["gmm_dw_tiles"] == "into:2x4 out_of:4x2"
    assert (facts["block_form"], facts["block_norms"]) == ("parallel", 1)
    # a band five blocks of 1,024 wide: 70 live steps of a grid of 80
    band = facts["flash_blocks"]["sliding_attention"]
    assert (band["live"], band["grid"], band["live_share"]) == (70, 80, 0.875)
    assert facts["flash_blocks"]["full_attention"]["live"] == 136


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
    # the same kinds: one period under one norm a layer, a window smaller
    # than the sequence, full layers without positions, 4 of 16 experts and
    # 1 of 4 shared experts held, a sliced tied head
    assert cfg.num_scan_units == 1 and cfg.tie_embeddings
    assert cfg.parallel_block and not cfg.norm_use_bias
    assert cfg.layer_pattern == ("sliding_attention",) * 3 + (
        "full_attention",
    )
    assert cfg.sliding_window < seq and not cfg.full_rope
    assert (cfg.num_experts, cfg.resolved_experts_held) == (16, 4)
    assert (cfg.num_shared_experts, cfg.resolved_shared_held) == (4, 1)
    assert cfg.shared_expert_scale == 0.25


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
    import jax
    import numpy as np

    from benchmark.references import command_a as ours
    from dlrover_tpu.models.references import command_a as theirs
    from dlrover_tpu.models.transformer import TransformerLM

    with open(ours.__file__) as a, open(theirs.__file__) as b:
        assert a.read() == b.read()        # one text in both places
    model, params, inputs, targets = preset_case
    exact = np.asarray(ours.token_nll(model, params, inputs, targets))
    # the program, built from the file as the worker builds it
    lm = TransformerLM(build.transformer_config(model, inputs.shape[1]))
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lm.apply)({"params": params}, inputs)
    logp = jax.nn.log_softmax(logits, axis=-1)
    got = -np.take_along_axis(
        np.asarray(logp), np.asarray(targets)[..., None], -1
    )[..., 0]
    np.testing.assert_allclose(got, exact, atol=1e-4)
    # what ``reference_tolerance`` is set against (PERF.md §6): the
    # reference with its router, its attention, or all of it, in bfloat16,
    # and the faults the builder's chip run hands the harness
    gaps = {
        mode: float(np.abs(np.asarray(ours.token_nll(
            model, params, inputs, targets, lowered=mode
        )) - exact).mean())
        for mode in ("router", "attention", "all")
    }
    assert 0 < gaps["router"] < gaps["all"]
    assert 0 < gaps["attention"] < gaps["all"]
    for wrong in ("sequential_block", "rope_on_full", "no_window"):
        other = np.asarray(ours.token_nll(
            model, params, inputs, targets, wrong=wrong
        ))
        assert float(np.abs(other - exact).mean()) > 3 * gaps["all"], wrong


def test_the_flops_of_a_token_by_hand():
    model = cell_model()
    assert flops_parallel_moe.layer_counts(model) == {
        "sliding_attention": 3, "full_attention": 1,
    }
    attn = 2 * 4096 * 4096 + 2 * 4096 * 256
    assert flops_window_moe.attention_projection_params(model) == attn
    assert flops_window_moe.pairs_here_per_token(model) == 0.5
    assert flops_parallel_moe.shared_experts_here(model) == 1
    expert = 3 * 4096 * 4096
    assert flops_parallel_moe.shared_params(model) == expert
    band = flops_parallel_moe.live_pairs(SEQ, 4096)
    assert band == 4096 * SEQ - 4096 * 4095 / 2 == 58_722_304
    parts = flops_parallel_moe.flops_per_token_by_part(model, SEQ)
    pair = 6.0 * 32 * 2 * 128 / SEQ
    assert parts == {
        "attention_projections": 6.0 * 4 * attn,
        "full_attention": pair * 1 * SEQ * (SEQ + 1) / 2,
        "sliding_attention": pair * 3 * band,
        "routed_here": 6.0 * 4 * 0.5 * expert,
        "router": 6.0 * 4 * 4096 * 128,
        "head": 6.0 * 32768 * 4096,
        "shared_here": 6.0 * 4 * expert,
    }
    total = flops_parallel_moe.model_flops_per_token(model, SEQ)
    assert total == sum(parts.values())
    # ISSUE 58's count: 4.417 GFLOP a token at 16,384, 72.4 TFLOP a step
    assert total == pytest.approx(4.417e9, rel=2e-4)
    assert total * SEQ == pytest.approx(72.4e12, rel=2e-3)
    share = {k: v / total for k, v in parts.items()}
    assert share["attention_projections"] == pytest.approx(0.19, abs=0.01)
    assert share["sliding_attention"] == pytest.approx(0.12, abs=0.01)
    assert share["full_attention"] == pytest.approx(0.09, abs=0.01)
    assert share["shared_here"] == pytest.approx(0.27, abs=0.01)
    assert share["routed_here"] == pytest.approx(0.14, abs=0.01)
    assert share["head"] == pytest.approx(0.18, abs=0.01)
    # the parts but the shared experts are flops_window_moe's, and with the
    # shared experts set to none the totals are equal
    theirs = flops_window_moe.flops_per_token_by_part(model, SEQ)
    assert {k: v for k, v in parts.items() if k != "shared_here"} == theirs
    assert total - parts["shared_here"] == pytest.approx(
        flops_window_moe.model_flops_per_token(model, SEQ), rel=1e-12
    )
    # held shared experts absent or 0: all the published ones
    assert flops_parallel_moe.shared_experts_here(
        dict(model, shared_experts_held=0)
    ) == 4
    for missing in ("layer_pattern", "num_experts", "top_k",
                    "sliding_window", "parallel_block",
                    "num_shared_experts"):
        with pytest.raises(KeyError):
            flops_parallel_moe.model_flops_per_token(
                {k: v for k, v in model.items()
                 if k not in (missing, "shared_experts_held")}, SEQ
            )
    for other in ("gpt2-1.5b", "olmo-hybrid-7b", "joyai-llm-flash",
                  "lfm2-8b-a1b", "mellum2-12b-a2.5b"):
        group = build.model_group(build.load_json(
            os.path.join(REPO, "benchmark", "configs", f"{other}.json")
        ))
        with pytest.raises(KeyError):
            flops_parallel_moe.model_flops_per_token(group, SEQ)


def test_the_appended_kernel_costs_count_this_model_by_hand():
    """``band_flash_roofline``, ``full_flash_roofline`` and
    ``window_moe_grouped_matmul_roofline`` read ``flops_window_moe``'s cost
    functions on this cell's model: each against a hand count."""
    model = cell_model()
    peak = build.peak_for("TPU v5 lite")
    band = flops_window_moe.band_flash_cost(model, SEQ, 1)
    full = flops_window_moe.full_flash_cost(model, SEQ, 1)
    assert band["flops"] == 7 * 2.0 * 58_722_304 * 128 * 32 * 3
    assert full["flops"] == 7 * 2.0 * (SEQ * (SEQ + 1) / 2) * 128 * 32 * 1
    row = 2.0 * SEQ * 128
    a_layer = (
        row * (2 * 32 + 2 * 2) + row * (4 * 32 + 4 * 2) + 2 * 4.0 * SEQ * 32
    )
    assert band["bytes"] == 3 * a_layer and full["bytes"] == a_layer
    assert flops.roofline_seconds(full, peak)["bound"] == "compute"
    assert flops.roofline_seconds(band, peak)["bound"] == "compute"
    # the ROUTED experts alone (the shared expert is plain matmuls under
    # moe/shared/, which the GEMMs' pattern does not match)
    held = flops_window_moe.held_expert_matmul_cost(model, SEQ, 1)
    assert held["flops"] == 3 * 3 * 2.0 * 8192 * 4096 * 4096 * 4
    weights = 2.0 * 8 * 3 * 4096 * 4096
    acts = 2.0 * 8192 * (2 * 4096 + 3 * 4096)
    assert held["bytes"] == 3 * (weights + acts) * 4


STEP = "jit(_train_step)/"
BACK = STEP + "transpose(jvp())/"
ROWS = [
    ["while.3", "", 0, 6000],
    ["fusion.0", STEP + "blocks/sliding_0/ln/reduce", 0, 40],
    ["fusion.1", STEP + "blocks/sliding_0/attn/query/dot_general", 40, 160],
    ["attn.1", STEP + "blocks/sliding_0/attn/pallas_call", 200, 100],
    ["attn.2", BACK + "blocks/sliding_2/attn/pallas_call", 300, 150],
    ["fusion.2", STEP + "blocks/sliding_1/attn/out/dot_general", 450, 50],
    ["attn.3", STEP + "blocks/full_3/attn/pallas_call", 500, 700],
    ["attn.4", BACK + "blocks/full_3/attn/pallas_call", 1200, 1300],
    ["fusion.3", STEP + "blocks/full_3/attn/key/dot_general", 2500, 100],
    ["fusion.4", STEP + "blocks/sliding_1/moe/router/dot_general", 2600, 30],
    ["gmm.1", STEP + "blocks/sliding_1/moe/gmm_wi/pallas_call", 2630, 300],
    ["gmm.2", BACK + "blocks/full_3/moe/gmm_wo/pallas_call", 2930, 100],
    ["fusion.5", STEP + "blocks/full_3/moe/shared/wi/dot_general", 3030, 250],
    ["fusion.6", BACK + "blocks/full_3/ln/mul", 3280, 20],
    # another model's norms and attention are under neither pattern
    ["fusion.7", STEP + "blocks/sliding_0/ln_attn/reduce", 3300, 50],
    ["attn.5", STEP + "blocks/attn/query/dot_general", 3350, 50],
]
TRACE = {"devices": {"/device:TPU:0": {
    "ops": ROWS[:-2], "modules": [["jit__train_step(1)", "", 0, 6000]],
}}, "host": []}
OTHER = {"devices": {"/device:TPU:0": {
    "ops": ROWS[-2:] + [ROWS[0]],
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

    assert ms("block_norm_ms") == pytest.approx(60e-6)
    assert ms("attn_proj_ms") == pytest.approx(310e-6)
    assert ms("sliding_attn_ms") == pytest.approx(460e-6)
    assert ms("full_attn_ms") == pytest.approx(2100e-6)
    assert ms("shared_expert_ms") == pytest.approx(250e-6)
    for name in ("block_norm_ms", "attn_proj_ms", "sliding_attn_ms",
                 "full_attn_ms", "shared_expert_ms"):
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
        floor = flops.roofline_seconds(
            getattr(flops_window_moe, cost)(model, SEQ, 1), peak
        )["seconds"]
        assert kernel_roofline_from.read(
            evidence(), spec["params"]
        ) == pytest.approx(100 * floor / seconds), name


def test_the_step_mfu_counts_by_part_and_leaves_other_models_alone():
    spec = layers.spec("parallel_moe_step_mfu")
    assert spec["reader"] == "mfu_from" and spec["params"] == {
        "module": MODULE
    }
    model = cell_model()
    summary = {"tokens_per_s_chip": 20000.0}
    got = mfu_from.read(evidence(summary=summary), spec["params"])
    per_token = flops_parallel_moe.model_flops_per_token(model, SEQ)
    assert got == pytest.approx(per_token * 20000.0 / 197e12)
    assert 0.1 < got < 0.7
    for other in ("gpt2-1.5b", "olmo-hybrid-7b", "lfm2-8b-a1b",
                  "mellum2-12b-a2.5b"):
        group = build.model_group(build.load_json(
            os.path.join(REPO, "benchmark", "configs", f"{other}.json")
        ))
        assert mfu_from.read(
            evidence(summary=summary, model=group), spec["params"]
        ) is None, other
    assert mfu_from.read(evidence(), spec["params"]) is None   # no summary


def test_the_band_s_grid_is_read_from_the_program_s_compile_event():
    spec = layers.spec("band_grid_live_share")
    blocks = {"sliding_attention": {"live": 70, "grid": 80,
                                    "live_share": 0.875}}
    assert evidence_value.read(
        {"compile": {"flash_blocks": blocks}}, spec["params"]
    ) == 0.875


OWN = ("parallel_moe_step_mfu", "block_norm_ms", "attn_proj_ms")
JOINED = ("sliding_attn_ms", "full_attn_ms", "band_flash_roofline",
          "full_flash_roofline", "window_moe_grouped_matmul_roofline",
          "band_grid_live_share", "attn_score_bound", "shared_expert_ms",
          "host_step_gap_ms", "step_s_worst_over_median",
          "tokens_per_s_chip_median_step", "data_wait_ms",
          "data_wait_span_ms", "step_device_ms", "device_idle_share",
          "peak_hbm_gib", "startup_to_mesh_s", "compile_trace_s",
          "compile_lower_s", "compile_backend_s", "compile_text_s",
          "startup_build_s", "startup_init_s", "forward_ms", "recompute_ms",
          "backward_ms", "optimizer_ms", "head_loss_ms", "step_unnamed_ms",
          "moe_pad_share", "moe_max_expert_load", "moe_pairs_here",
          "moe_row_move_ms", "moe_row_gather_ms", "moe_router_ms",
          "moe_dispatch_ms")
NOT_JOINED = ("window_moe_step_mfu", "router_bias_absmax", "conv_mixer_ms",
              "step_mfu", "flash_roofline", "flash_attn_roofline",
              "pattern_flash_roofline", "latent_flash_roofline",
              "held_grouped_matmul_roofline", "mtp_ms", "latent_proj_ms")
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
    assert cell["traffic"] == "train_16k_own_ref" and cell["chips"] == 1
    assert cell["config"] == NAME
    assert "1,024 rows" in cell["why"] and "4,096" in cell["why"]
    reported = {m["name"] for m in layers.cell_entries(
        build.manifest(), CELL, "per_layer"
    )}
    assert set(OWN + JOINED) <= reported
    assert "compile_s" in reported
    cells = build.manifest()["workloads"]
    assert len(cells) >= 13
    assert sum(w["chips"] == 4 for w in cells) <= len(cells) // 4
