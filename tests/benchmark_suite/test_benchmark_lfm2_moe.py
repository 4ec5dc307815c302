"""What the LFM2-8B-A1B cell brings to the benchmark: its program against
its file, its own plain reference against the repository's, the arithmetic
of its cost module by hand, and its readers on a recorded list of op names.
(The file against the catalog is ``tests/test_lfm2_moe_config.py``'s; the
rehearsals of the cell are ``test_benchmark_rehearsal.py``'s and
``test_benchmark_program_spans.py``'s, which run every cell of the
manifest.)  Membership assertions only: never a list's last place or its
whole content, so that the next cell to join a list breaks nothing here
(PERF.md §7 (7))."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import build, flops, flops_conv_moe, layers  # noqa: E402
from benchmark.readers import (  # noqa: E402
    kernel_roofline_from,
    mfu_from,
    program_events,
    scope_ms,
)

NAME = "lfm2-8b-a1b"
CONFIG = os.path.join(REPO, "benchmark", "configs", f"{NAME}.json")
PRESET = os.path.join(HERE, "presets", f"{NAME}.json")
CELL = f"{NAME}.train_steady"
MODULE = "flops_conv_moe"


def cell_model():
    return build.model_group(build.load_json(CONFIG))


def test_the_program_takes_the_configuration():
    from dlrover_tpu.models import lfm2_moe
    from dlrover_tpu.models.moe import _share_row_budget
    from dlrover_tpu.ops import row_gather_sum

    config = build.load_json(CONFIG)
    cfg = build.transformer_config(cell_model(), build.seq_len(config, {}))
    want = lfm2_moe.lfm2_moe_config(
        num_layers=17, first_k_dense=1, vocab_size=16384, experts_held=8,
    )
    for field in ("d_model", "num_heads", "resolved_kv_heads",
                  "resolved_head_dim", "d_ff", "moe_d_ff", "num_experts",
                  "experts_held", "first_expert", "top_k", "router_scoring",
                  "router_bias", "router_bias_rate", "router_norm_eps",
                  "norm_topk_prob", "routed_scaling_factor", "norm_eps",
                  "rope_theta", "tie_embeddings", "moe_dispatch",
                  "max_seq_len", "layer_pattern", "first_k_dense",
                  "position", "activation", "norm", "qk_norm", "conv_kernel",
                  "resolved_shared_d_ff", "norm_placement", "use_bias"):
        assert getattr(cfg, field) == getattr(want, field), field
    assert cfg.resolved_head_dim == 64 and cfg.resolved_shared_d_ff == 0
    assert cfg.num_scan_units == 4 and cfg.num_conv_layers == 13
    assert (cfg.remat, cfg.attention_impl) == ("flash_only", "flash")
    assert cfg.num_params() == 1_748_057_088 == config["num_params"]
    assert "1,748,057,088" in config["reduced"]["num_hidden_layers"]["why"]
    assert (config["run"]["seq_len"], build.global_batch(config, {}, 1)) in (
        (8192, 4), (8192, 2)    # the cell, or the issue's one fallback
    )
    # the rows set aside for an expert layer's share at 4 x 8192 tokens:
    # 1.25 x 32,768 expected + a block of 128 an expert + the zero block
    assert _share_row_budget(4 * 8192 * 4, 128, 8, 32, 1.25) == 42_112
    # rows of 2,048 are 16 lane tiles, 4 a token: the fetch-and-sum kernel
    assert row_gather_sum.kernel_fits(2048, 4, "bfloat16")
    # the expert width is whole lane tiles: 14 of them
    assert 1792 % 128 == 0 and cfg.resolved_moe_d_ff == 1792


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
    cfg = build.transformer_config(
        build.model_group(preset), build.seq_len(preset, {})
    )
    # the same kinds: a dense prefix, two periods, 8 of 32 experts, a
    # sliced tied head
    assert cfg.first_k_dense == 1 and cfg.num_scan_units == 2
    assert cfg.layer_pattern == ("full_attention", "conv", "conv", "conv")
    assert (cfg.num_experts, cfg.resolved_experts_held) == (32, 8)
    assert cfg.tie_embeddings and cfg.qk_norm == "per_head"


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


def test_a_lowered_reference_goes_through_the_workers_own_comparison(
    preset_case
):
    """``benchmark/lowered_control.py`` puts the reference, a precision
    lower, where ``check_reference`` applies the program: the distance the
    harness then reads is the lowered reference's own, and ``ok`` is its
    verdict under the file's limits."""
    import types

    import jax

    from benchmark import lowered_control
    from benchmark.scenarios import train_steady_own_ref
    from dlrover_tpu.models.transformer import TransformerLM
    from dlrover_tpu.parallel import rules
    from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh

    model, params, _, _ = preset_case
    config = build.load_json(PRESET)
    traffic = build.load_json(
        os.path.join(build.ROOT, "traffic", "train_steady_own_ref.json")
    )
    worker = train_steady_own_ref.Worker(
        config, traffic, 1, 3, 0.0, False, rehearsal=True
    )
    cfg = build.transformer_config(model, build.seq_len(config, {}))
    state = types.SimpleNamespace(params=params)
    worker.trainer = types.SimpleNamespace(
        model=TransformerLM(cfg), model_config=cfg,
        mesh=build_mesh(ParallelConfig(), jax.devices()[:1]),
        _rules=rules.DEFAULT_RULES, state=state,
        train=types.SimpleNamespace(init=lambda key: state),
    )
    program = worker.check_reference()
    assert program["ok"] and program["mean_abs_token_error"] < 1e-4
    (line,) = lowered_control.sweep(
        worker, [3], ("all", "conv"), say=lambda _: None
    )
    assert line["program"]["mean_abs_token_error"] == pytest.approx(
        program["mean_abs_token_error"]
    )
    # the core alone, lowered: the head is float32 either way, so what
    # the harness reads IS the reference's own distance
    assert line["conv"]["mean_abs_token_error"] == pytest.approx(
        line["direct"]["conv"], rel=1e-3
    )
    # wholly lowered: the harness takes the logits to float32 before the
    # loss, the reference's own loss is bfloat16 too
    through = line["all"]
    assert through["mean_abs_token_error"] > 100 * (
        program["mean_abs_token_error"]
    )
    assert line["direct"]["all"] > through["mean_abs_token_error"]
    limits = config["reference_tolerance"]
    assert through["ok"] == (
        through["mean_abs_token_error"] <= limits["mean_abs_token_nll"]
        and through["mean_loss_error"] <= limits["mean_nll"]
    )
    # the program stands where it stood, and in float32 it is itself here
    assert isinstance(worker.trainer.model, TransformerLM)
    again = lowered_control.control(worker, "float32")
    assert again["mean_abs_token_error"] < 1e-4 and again["ok"]


def test_the_benchmarks_reference_agrees_with_the_repositorys(preset_case):
    import numpy as np

    from benchmark.references import lfm2_moe as ours
    from dlrover_tpu.models.references import lfm2_moe as theirs

    with open(ours.__file__) as a, open(theirs.__file__) as b:
        assert a.read() == b.read()        # one text in both places
    model, params, inputs, targets = preset_case
    got = ours.forward(model, params, inputs, targets)
    exact = np.asarray(got["nll"])
    np.testing.assert_allclose(
        theirs.token_nll(model, params, inputs, targets), exact, atol=2e-5
    )
    # eight expert layers' counts over all 32 experts: 2 x 32 x 4 pairs each
    assert [int(c.sum()) for c in got["counts"]] == [256] * 8
    assert all(c.shape == (32,) for c in got["counts"])
    # what ``reference_tolerance`` is set against (PERF.md §6): the
    # reference with its router, its core, or all of it, in bfloat16
    gaps = {
        mode: float(np.abs(np.asarray(ours.token_nll(
            model, params, inputs, targets, lowered=mode
        )) - exact).mean())
        for mode in ("router", "conv", "all")
    }
    assert 0 < gaps["router"] < gaps["all"]
    assert 0 < gaps["conv"] < gaps["all"]


def test_the_flops_of_a_token_by_hand():
    model = cell_model()
    assert flops_conv_moe.layer_counts(model) == {
        "conv": 13, "full_attention": 4, "dense": 1, "experts": 16,
    }
    conv = 3 * 2048 * 2048 + 2048 * 2048
    assert flops_conv_moe.conv_projection_params(model) == conv == 16_777_216
    # q and out 2048 x 2048, k and v 2048 x 512
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert flops_conv_moe.attention_projection_params(model) == attn
    # of 4 pairs a token a quarter is routed here
    assert flops_conv_moe.pairs_here_per_token(model) == 1.0
    parts = flops_conv_moe.flops_per_token_by_part(model, 8192)
    assert parts == {
        "conv_projections": 6.0 * 13 * conv,
        "attention_projections": 6.0 * 4 * attn,
        # FOUR attention layers, 32 heads of 64, the causal half
        "attention": 0.5 * 6.0 * 4 * 32 * 8192 * (64 + 64),
        "dense_mlp": 6.0 * 1 * 3 * 2048 * 7168,
        "routed_here": 6.0 * 16 * 1.0 * 3 * 2048 * 1792,
        "router": 6.0 * 16 * 2048 * 32,
        "head": 6.0 * 16384 * 2048,
    }
    total = flops_conv_moe.model_flops_per_token(model, 8192)
    assert total == sum(parts.values())
    # ISSUE 52's count: 582 M forward multiply-adds a token
    assert total / 6 == pytest.approx(582e6, rel=2e-3)
    share = {k: v / total for k, v in parts.items()}
    # its shares: the thirteen mixers 37.5%, the held experts 30.3%, the
    # four attention layers 18.7%, the dense MLP 7.6%, the head 5.8%
    assert share["conv_projections"] == pytest.approx(0.375, abs=0.002)
    assert share["routed_here"] == pytest.approx(0.303, abs=0.002)
    assert share["attention_projections"] + share["attention"] == (
        pytest.approx(0.187, abs=0.002)
    )
    assert share["dense_mlp"] == pytest.approx(0.076, abs=0.002)
    assert share["head"] == pytest.approx(0.058, abs=0.002)
    # a model without these layers cannot be counted here
    for missing in ("layer_pattern", "num_experts", "top_k"):
        with pytest.raises(KeyError):
            flops_conv_moe.model_flops_per_token(
                {k: v for k, v in model.items() if k != missing}, 8192
            )
    for other in ("gpt2-1.5b", "olmo-hybrid-7b", "joyai-llm-flash",
                  "granite-4.0-h-small", "ling-3.0-flash-vl"):
        group = build.model_group(build.load_json(
            os.path.join(REPO, "benchmark", "configs", f"{other}.json")
        ))
        with pytest.raises(KeyError):
            flops_conv_moe.model_flops_per_token(group, 8192)


def test_the_kernel_costs_by_hand():
    model = cell_model()
    tokens = 4 * 8192
    peak = build.peak_for("TPU v5 lite")
    core = flops_conv_moe.conv_core_cost(model, 8192, 4)
    # ISSUE 52's floor a layer: forward reads 403 MB and writes 134,
    # backward reads 537 and writes 403
    a_layer = core["bytes"] / 13
    assert a_layer == tokens * 2048 * 2 * (3 + 1 + 4 + 3)
    assert a_layer == pytest.approx((403 + 134 + 537 + 403) * 1e6, rel=2e-3)
    floor = flops.roofline_seconds(core, peak)
    assert floor["bound"] == "memory"
    # 1.80 ms a layer at 819 GB/s, 23 ms over the thirteen
    assert floor["seconds"] == pytest.approx(13 * 1.803e-3, rel=2e-3)
    flash = flops_conv_moe.gqa_flash_cost(model, 8192, 4)
    assert flash["flops"] == 2.0 * 8192 * 8192 * 32 * 4 * 7 * 64 * 0.5 * 4
    row = 2.0 * tokens * 64
    assert flash["bytes"] == 4 * (
        row * (2 * 32 + 2 * 8) + row * (4 * 32 + 4 * 8) + 2 * 4.0 * tokens * 32
    )
    assert flops.roofline_seconds(flash, peak)["bound"] == "compute"
    # the held grouped GEMMs: 32,768 pairs here a layer, three matrices of
    # 2048 x 1792, three passes, sixteen expert layers
    held = flops_conv_moe.held_expert_matmul_cost(model, 8192, 4)
    assert held["flops"] == 3 * 3 * 2.0 * 32768 * 2048 * 1792 * 16
    weights = 2.0 * 8 * 3 * 2048 * 1792
    acts = 2.0 * 32768 * (2 * 2048 + 3 * 1792)
    assert held["bytes"] == 3 * (weights + acts) * 16
    assert flops.roofline_seconds(held, peak)["bound"] == "compute"


# a recorded ``op_name`` list of this model's step (names as the chip's
# trace has them: the slot, the part, the scope)
STEP = "jit(_train_step)/"
BACK = STEP + "transpose(jvp())/"
ROWS = [
    ["while.3", "", 0, 6000],
    ["fusion.1", STEP + "dense_0/conv/in_proj/dot_general", 0, 200],
    ["fusion.2", STEP + "blocks/conv_1/conv/core/mul", 200, 75],
    ["fusion.3", BACK + "blocks/conv_2/conv/core/mul", 275, 125],
    ["fusion.4", STEP + "blocks/conv_1/conv/core/reduce_max", 400, 25],
    ["fusion.5", STEP + "blocks/conv_3/conv/out_proj/dot_general", 425, 100],
    ["attn.1", STEP + "blocks/full_0/attn/pallas_call", 525, 400],
    ["fusion.6", STEP + "blocks/full_0/attn/q_norm/rsqrt", 925, 20],
    ["fusion.7", STEP + "blocks/full_0/attn/query/dot_general", 945, 75],
    ["fusion.8", STEP + "blocks/conv_1/moe/router/dot_general", 1020, 30],
    ["gmm.1", STEP + "blocks/conv_1/moe/gmm_wi/pallas_call", 1050, 300],
    ["gmm.2", BACK + "blocks/full_0/moe/gmm_wo/pallas_call", 1350, 100],
    ["fusion.9", STEP + "blocks/conv_1/moe/combine/gather", 1450, 150],
    ["fusion.10", STEP + "embed/attend/dot_general", 1600, 250],
    # another model's short convolutions are none of this cell's mixers
    ["fusion.11", STEP + "blocks/linear_0/linear_attn/conv/jit(_forward)/"
     "short_conv_fwd", 1850, 50],
]
TRACE = {"devices": {"/device:TPU:0": {
    "ops": ROWS[:-1], "modules": [["jit__train_step(1)", "", 0, 6000]],
}}, "host": []}
OTHER = {"devices": {"/device:TPU:0": {
    "ops": [ROWS[0], ROWS[-1], ROWS[-2]],
    "modules": TRACE["devices"]["/device:TPU:0"]["modules"],
}}, "host": []}


def evidence(trace=TRACE, **more):
    return dict({
        "trace": trace, "step_module": "train_step", "model": cell_model(),
        "seq_len": 8192, "sequences_per_chip": 4,
        "peak": build.peak_for("TPU v5 lite"),
    }, **more)


def test_the_scope_patterns_on_a_recorded_list_of_op_names():
    def ms(name, trace=TRACE):
        spec = layers.spec(name)
        assert spec["reader"] == "scope_ms"
        return scope_ms.read(evidence(trace), spec["params"])

    # the thirteen mixers whole: projections and core, forward and
    # transposed; the core alone, its statistics with it
    assert ms("conv_mixer_ms") == pytest.approx(525e-6)
    assert ms("conv_core_ms") == pytest.approx(225e-6)
    # the accepted metric this cell joins reads the same scopes here
    assert ms("moe_row_move_ms") == pytest.approx(150e-6)
    # a program with no such scope (the parent, another model) gives
    # nothing: ``linear_attn/conv`` is not ``conv/``
    for name in ("conv_mixer_ms", "conv_core_ms"):
        assert ms(name, OTHER) is None


def test_the_rooflines_read_their_own_ops_against_their_own_cost():
    peak = build.peak_for("TPU v5 lite")
    model = cell_model()
    cases = (
        # every op under conv/../core, kernel or not: both kernels, the
        # layout's transposes and the statistics (75 + 125 + 25)
        ("conv_core_roofline", "conv_core_cost", 225e-9),
        ("conv_moe_flash_roofline", "gqa_flash_cost", 400e-9),
        ("conv_moe_grouped_matmul_roofline", "held_expert_matmul_cost",
         400e-9),
    )
    for name, cost, seconds in cases:
        spec = layers.spec(name)
        assert spec["reader"] == "kernel_roofline_from", name
        assert spec["params"]["module"] == MODULE
        assert spec["params"]["cost"] == cost
        floor = flops.roofline_seconds(
            getattr(flops_conv_moe, cost)(model, 8192, 4), peak
        )["seconds"]
        assert kernel_roofline_from.read(
            evidence(), spec["params"]
        ) == pytest.approx(100 * floor / seconds), name
        # nothing to read: no such op, no peak, no trace, another model
        params = spec["params"]
        assert kernel_roofline_from.read(evidence(OTHER), params) is None
        assert kernel_roofline_from.read(evidence(peak=None), params) is None
        assert kernel_roofline_from.read({}, params) is None
        older = dict(model, layer_pattern=["full_attention"])
        assert kernel_roofline_from.read(
            evidence(model=older), params
        ) is None


def test_the_step_mfu_counts_by_part_and_leaves_other_models_alone():
    spec = layers.spec("conv_moe_step_mfu")
    assert spec["reader"] == "mfu_from" and spec["params"] == {
        "module": MODULE
    }
    model = cell_model()
    summary = {"tokens_per_s_chip": 17000.0}
    got = mfu_from.read(evidence(summary=summary), spec["params"])
    per_token = flops_conv_moe.model_flops_per_token(model, 8192)
    assert got == pytest.approx(per_token * 17000.0 / 197e12)
    assert 0.1 < got < 0.7
    for other in ("gpt2-1.5b", "olmo-hybrid-7b", "joyai-llm-flash",
                  "ling-3.0-flash-vl"):
        group = build.model_group(build.load_json(
            os.path.join(REPO, "benchmark", "configs", f"{other}.json")
        ))
        assert mfu_from.read(
            evidence(summary=summary, model=group), spec["params"]
        ) is None, other
    assert mfu_from.read(evidence(), spec["params"]) is None   # no summary


def test_the_core_s_largest_output_is_read_from_the_conv_event():
    spec = layers.spec("conv_out_absmax")
    assert spec["reader"] == "program_events"
    assert spec["params"] == {
        "name": "conv", "attr": "out_absmax", "reduce": "max"
    }
    # a program that books no such event (the parent) gives nothing
    assert program_events.read({}, spec["params"]) is None


OWN = ("conv_mixer_ms", "conv_core_ms", "conv_core_roofline",
       "conv_moe_flash_roofline",
       "conv_moe_grouped_matmul_roofline", "conv_moe_step_mfu",
       "conv_out_absmax")
JOINED = ("host_step_gap_ms", "step_s_worst_over_median",
          "tokens_per_s_chip_median_step", "data_wait_ms",
          "data_wait_span_ms", "step_device_ms", "device_idle_share",
          "peak_hbm_gib", "startup_to_mesh_s", "compile_trace_s",
          "compile_lower_s", "compile_backend_s", "compile_text_s",
          "startup_build_s", "startup_init_s", "forward_ms", "recompute_ms",
          "backward_ms", "optimizer_ms", "head_loss_ms", "step_unnamed_ms",
          "moe_pad_share", "moe_max_expert_load", "moe_pairs_here",
          "router_bias_absmax", "moe_row_move_ms", "moe_row_gather_ms",
          "moe_router_ms", "moe_dispatch_ms")
NOT_JOINED = ("short_conv_ms", "ssm_conv_ms", "linear_attn_ms",
              "step_mfu", "flash_roofline",
              "flash_attn_roofline", "grouped_matmul_roofline",
              "held_grouped_matmul_roofline", "shared_expert_ms",
              "kda_latent_flash_roofline", "kda_latent_moe_step_mfu",
              "latent_moe_step_mfu", "pattern_step_mfu", "ssm_ms", "mtp_ms")
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
        # a metric this cell brought lists the cells whose program has
        # what it reads: this one, and whoever joins later
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
    assert cell["traffic"] == "train_steady_own_ref" and cell["chips"] == 1
    assert cell["config"] == NAME
    assert "4,096 rows" in cell["why"] and "8,192" in cell["why"]
    reported = {m["name"] for m in layers.cell_entries(
        build.manifest(), CELL, "per_layer"
    )}
    assert set(OWN + JOINED) <= reported
    # compile_s lists no cells: every cell reports it
    assert "compile_s" in reported
    # eleven cells or more, and no more four-chip cells than a quarter
    cells = build.manifest()["workloads"]
    assert len(cells) >= 11
    assert sum(w["chips"] == 4 for w in cells) <= len(cells) // 4
