"""What the Solar-Open2 cell brings to the benchmark: its program against
its file, its own plain reference against the repository's, the arithmetic
of its cost module by hand, and its readers on a recorded list of op names.
(The file against the catalog is ``tests/test_solar_open_config.py``'s; the
rehearsals of the cell are ``test_benchmark_rehearsal.py``'s and
``test_benchmark_program_spans.py``'s, which run every cell of the
manifest.)  Membership assertions only: never a list's last place or its
whole content, so that the next cell to join a list breaks nothing here."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import (  # noqa: E402
    build,
    flops,
    flops_kda_gqa_moe,
    flops_kda_latent_moe,
    flops_latent_moe,
    layers,
)
from benchmark.readers import (  # noqa: E402
    kernel_roofline_from,
    mfu_from,
    program_events,
    scope_ms,
)

NAME = "solar-open2-250b"
CONFIG = os.path.join(REPO, "benchmark", "configs", f"{NAME}.json")
PRESET = os.path.join(HERE, "presets", f"{NAME}.json")
TRAFFIC = os.path.join(REPO, "benchmark", "traffic", "train_16k_own_ref.json")
CELL = f"{NAME}.train_16k"
MODULE = "flops_kda_gqa_moe"
SEQ = 16384
KDA_PROJ = 2 * 4096 * 8192 * 2 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
GQA_PROJ = 2 * 4096 * 8192 + 2 * 4096 * 1024 + 4096 * 8192


def cell_model():
    return build.model_group(build.load_json(CONFIG))


def test_the_program_takes_the_configuration_and_the_traffic():
    from dlrover_tpu.models.transformer import kernel_facts

    config, traffic = build.load_json(CONFIG), build.load_json(TRAFFIC)
    assert (build.seq_len(config, traffic), build.global_batch(
        config, traffic, 1
    )) == (SEQ, 1)
    assert config["run"] == traffic["run"]
    cfg = build.transformer_config(cell_model(), SEQ)
    assert cfg.num_params() == config["num_params"] == 2_050_024_000
    assert (cfg.num_full_layers, cfg.num_linear_layers) == (1, 3)
    assert cfg.max_seq_len == SEQ and cfg.num_scan_units == 1
    assert traffic["reference_sequences"] == 1
    assert traffic["scenario"] == "train_steady_own_ref"
    facts = kernel_facts(cfg, SEQ)
    # a gate without a bound: the rule's exact form, in the kernels
    assert facts["kda"] == "kernel_exact"
    assert facts["short_conv"] == "kernel"
    assert facts["flash_backward"] == "fused"
    # rows of 4,096 are whole tiles: the moves run in the row kernels, and
    # under the cell's share only the pairs that have a row here are fetched
    assert facts["row_moves"] == "kernel_live"
    assert facts["gmm_strips"] == "resident"


def test_the_preset_is_the_cell_in_small():
    config, preset = build.load_json(CONFIG), build.load_json(PRESET)
    assert set(preset) == set(config)
    for group in ("to_program", "trainer"):
        assert preset[group] == config[group]
    varies = ("moe_row_budget", "param_dtype", "linear_num_heads",
              "linear_key_head_dim", "linear_value_head_dim",
              "linear_gate_rank")
    assert {
        k: v for k, v in preset["program"].items() if k not in varies
    } == {
        k: v for k, v in config["program"].items() if k not in varies
    }
    assert preset["reference_module"] == config["reference_module"]
    seq = build.seq_len(preset, build.load_json(TRAFFIC)["rehearsal"])
    cfg = build.transformer_config(build.model_group(preset), seq)
    # the same kinds: one period of a gated GQA layer and three KDA layers
    # under the gate without a bound, a doubled beta, low-rank gates, 8 of
    # 32 experts held, an untied head, no positions
    assert cfg.num_scan_units == 1 and not cfg.tie_embeddings
    assert cfg.layer_pattern == ("full_attention",) + (
        "linear_attention",
    ) * 3
    assert cfg.linear_decay_bound == 0 and cfg.linear_allow_neg_eigval
    assert 0 < cfg.linear_gate_rank < cfg.d_model
    assert cfg.attention_gate == "elementwise" and cfg.position == "none"
    assert cfg.num_kv_heads < cfg.num_heads
    assert (cfg.num_experts, cfg.resolved_experts_held) == (32, 8)


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
    import jax.numpy as jnp
    import numpy as np

    from benchmark.references import solar_open as ours
    from dlrover_tpu.models.references import solar_open as theirs
    from dlrover_tpu.models.transformer import TransformerLM

    with open(ours.__file__) as a, open(theirs.__file__) as b:
        assert a.read() == b.read()        # one text in both places
    model, params, inputs, targets = preset_case
    exact = np.asarray(ours.token_nll(model, params, inputs, targets))
    # the program, built from the file as the worker builds it, in float32
    lm = TransformerLM(build.transformer_config(
        dict(model, dtype="float32"), inputs.shape[1]
    ))
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lm.apply)({"params": params}, inputs)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    got = -np.take_along_axis(
        np.asarray(logp), np.asarray(targets)[..., None], -1
    )[..., 0]
    np.testing.assert_allclose(got, exact, atol=1e-4)
    # what ``reference_tolerance`` is set against: the reference wholly in
    # bfloat16, and the faults the builder's chip run hands the harness
    lowered = float(np.abs(np.asarray(ours.token_nll(
        model, params, inputs, targets, lowered="all"
    )) - exact).mean())
    assert lowered > 1e-3
    for wrong in ("beta_not_doubled", "safe_gate", "no_gqa_gate",
                  "rope_on_gqa"):
        other = np.asarray(ours.token_nll(
            model, params, inputs, targets, wrong=wrong
        ))
        assert float(np.abs(other - exact).mean()) > 1e-3, wrong


def test_the_flops_of_a_token_by_hand():
    model = cell_model()
    parts = flops_kda_gqa_moe.flops_per_token_by_part(model, SEQ)
    assert flops_kda_gqa_moe.kda_projection_params(model) == KDA_PROJ
    assert flops_kda_gqa_moe.gqa_projection_params(model) == GQA_PROJ
    assert parts["kda_projections"] == 6.0 * 3 * KDA_PROJ
    assert parts["gqa_projections"] == 6.0 * GQA_PROJ
    # the rule on the yardstick Ling's cell stands on, at twice the heads
    assert parts["kda_rule"] == (
        3.0 * 3 * flops_kda_latent_moe.kda_rule_flops_per_token(model)
    )
    # scores and values over 128 each, 64 heads, the causal half
    assert parts["attention"] == 0.5 * 6.0 * 64 * SEQ * 256
    expert = 3 * 4096 * 1280
    assert flops_latent_moe.pairs_here_per_token(model) == 0.5
    assert parts["routed_here"] == 6.0 * 4 * 0.5 * expert
    assert parts["shared_experts"] == 6.0 * 4 * expert
    assert parts["router"] == 6.0 * 4 * 4096 * 320
    assert parts["heads"] == 6.0 * 24576 * 4096
    assert flops_kda_gqa_moe.model_flops_per_token(
        model, SEQ
    ) == pytest.approx(sum(parts.values()))
    # full-rank gates would count 2 x 4096 x 8192 a layer where the pairs
    # count 2 x 1,572,864
    full = flops_kda_gqa_moe.kda_projection_params(
        dict(model, linear_gate_rank=0)
    )
    assert full - KDA_PROJ == 2 * (4096 * 8192 - 1_572_864)
    head_wise = flops_kda_gqa_moe.gqa_projection_params(
        dict(model, attention_gate="head_wise")
    )
    assert GQA_PROJ - head_wise == 4096 * 64 * 127


def test_the_kernel_costs_by_hand():
    model = cell_model()
    peak = build.peak_for("TPU v5 lite")
    flash = flops_kda_gqa_moe.gqa_flash_cost(model, SEQ, 1)
    assert flash["flops"] == 2.0 * SEQ * SEQ * 64 * 7 * 128 * 0.5
    rows = 2.0 * SEQ * 128
    assert flash["bytes"] == (
        rows * (2 * 64 + 2 * 8) + rows * (4 * 64 + 4 * 8)
        + 2 * 4.0 * SEQ * 64
    )
    assert flops.roofline_seconds(flash, peak)["bound"] == "compute"
    # the rule's cost is the accepted one, read with this model group
    assert flops_kda_gqa_moe.kda_cost is flops_kda_latent_moe.kda_cost
    rule = flops_kda_gqa_moe.kda_cost(model, SEQ, 1)
    assert rule["flops"] == (
        3.0 * SEQ * flops_kda_latent_moe.kda_rule_flops_per_token(model) * 3
    )
    # and the held experts' grouped GEMMs: 8,192 pairs a layer expected
    held = flops_latent_moe.held_expert_matmul_cost(model, SEQ, 1)
    assert held["flops"] == 3 * 3 * 2.0 * 8192 * 4096 * 1280 * 4
    # another model has no GQA gate key to miss, but no KDA rank either:
    # the module reads Ling's group as full rank and a model without
    # linear layers not at all
    other = build.model_group(build.load_json(os.path.join(
        REPO, "benchmark", "configs", "gpt2-1.5b.json"
    )))
    with pytest.raises(KeyError):
        flops_kda_gqa_moe.model_flops_per_token(other, 1024)


STEP = "jit(_train_step)/"
FWD = STEP + "jvp(TransformerLM)/"
BACK = STEP + "transpose(jvp(TransformerLM))/"
ROWS = [
    ["while.3", "", 0, 9000],
    ["fusion.0", FWD + "blocks/full_0/attn/query/dot_general", 0, 40],
    ["attn.1", FWD + "blocks/full_0/attn/pallas_call", 40, 1000],
    ["fusion.1", FWD + "blocks/full_0/attn/gate/dot_general", 1040, 60],
    ["fusion.2", FWD + "blocks/full_0/attn/gate/mul", 1100, 10],
    ["attn.2", BACK + "blocks/full_0/attn/flash_bwd/pallas_call", 1110, 2500],
    ["fusion.3", BACK + "blocks/full_0/attn/gate/dot_general", 3610, 120],
    ["fusion.4", FWD + "blocks/linear_1/linear_attn/gates/dot_general", 3730,
     30],
    ["kda.1", FWD + "blocks/linear_1/linear_attn/kda/pallas_call", 3760, 300],
    ["kda.2", BACK + "blocks/linear_1/linear_attn/kda/pallas_call", 4060,
     600],
    ["gmm.1", FWD + "blocks/linear_2/moe/gmm_wi/pallas_call", 4660, 300],
    # another model's latent attention gate lies under attn/gate too
    ["fusion.9", FWD + "blocks/full_5/attn/q_b/dot_general", 6350, 50],
]
TRACE = {"devices": {"/device:TPU:0": {
    "ops": ROWS[:-1], "modules": [["jit__train_step(1)", "", 0, 9000]],
}}, "host": []}
OTHER = {"devices": {"/device:TPU:0": {
    "ops": ROWS[-1:] + [ROWS[0]],
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

    assert ms("attn_gate_ms") == pytest.approx((60 + 10 + 120) * 1e-6)
    assert ms("kda_gate_ms") == pytest.approx(30e-6)
    assert ms("linear_attn_ms") == pytest.approx(930e-6)
    assert ms("attn_gate_ms", OTHER) is None


def test_the_rooflines_read_their_own_ops_against_their_own_costs():
    peak = build.peak_for("TPU v5 lite")
    spec = layers.spec("gqa_gate_flash_roofline")
    assert spec["params"]["module"] == MODULE
    floor = flops.roofline_seconds(
        flops_kda_gqa_moe.gqa_flash_cost(cell_model(), SEQ, 1), peak
    )["seconds"]
    assert kernel_roofline_from.read(
        evidence(), spec["params"]
    ) == pytest.approx(100 * floor / 3500e-9)
    assert kernel_roofline_from.read(
        evidence(OTHER), spec["params"]
    ) is None
    # the accepted share of the rule's roofline reads this model group
    kda = layers.spec("kda_roofline")
    floor = flops.roofline_seconds(
        flops_kda_latent_moe.kda_cost(cell_model(), SEQ, 1), peak
    )["seconds"]
    assert kernel_roofline_from.read(
        evidence(), kda["params"]
    ) == pytest.approx(100 * floor / 900e-9)
    held = layers.spec("held_grouped_matmul_roofline")
    assert kernel_roofline_from.read(evidence(), held["params"]) is not None


def test_the_step_mfu_counts_by_part_and_leaves_other_models_alone():
    spec = layers.spec("kda_gqa_moe_step_mfu")
    assert spec["reader"] == "mfu_from" and spec["params"] == {
        "module": MODULE
    }
    model = cell_model()
    summary = {"tokens_per_s_chip": 16000.0}
    got = mfu_from.read(evidence(summary=summary), spec["params"])
    per_token = flops_kda_gqa_moe.model_flops_per_token(model, SEQ)
    assert got == pytest.approx(per_token * 16000.0 / 197e12)
    assert 0.1 < got < 0.7
    for other in ("gpt2-1.5b", "mixtral-8x7b", "joyai-llm-flash"):
        group = build.model_group(build.load_json(
            os.path.join(REPO, "benchmark", "configs", f"{other}.json")
        ))
        assert mfu_from.read(
            evidence(summary=summary, model=group), spec["params"]
        ) is None, other


def test_the_gate_s_numbers_are_read_from_the_program_s_events():
    event = ["linear_attn", "event", 0.0, 0.0, {
        "step": 12, "g_min": -120.0, "past_bound_share": 0.03,
        "state_absmax": 4.0,
    }]
    later = ["linear_attn", "event", 0.0, 0.0, {
        "step": 16, "g_min": -140.0, "past_bound_share": 0.02,
        "state_absmax": 5.0,
    }]
    # a program whose gate has a bound books neither attribute
    ling = ["linear_attn", "event", 0.0, 0.0, {
        "step": 12, "state_absmax": 3.0, "min_alpha": 0.2,
    }]
    found = {"spans": [event, later], "step_ids": [8, 12, 16],
             "window_steps": [8, 16]}
    want = {"kda_decay_min": -130.0, "kda_past_bound_share": 0.03,
            "delta_state_absmax": 5.0}
    for name, value in want.items():
        spec = layers.spec(name)
        assert spec["reader"] == "program_events"
        got = program_events.read(found, spec["params"])
        if got is not None:     # the window's steps as the reader finds them
            assert got == pytest.approx(value), name
        if name != "delta_state_absmax":
            assert program_events.read(
                dict(found, spans=[ling]), spec["params"]
            ) is None


OWN = ("kda_gqa_moe_step_mfu", "gqa_gate_flash_roofline", "attn_gate_ms",
       "kda_decay_min", "kda_past_bound_share")
JOINED = ("kda_roofline", "kda_gate_ms", "linear_attn_ms", "short_conv_ms",
          "delta_state_absmax", "held_grouped_matmul_roofline",
          "shared_expert_ms", "moe_router_ms", "moe_row_gather_ms",
          "moe_row_move_ms", "moe_pad_share", "moe_max_expert_load",
          "moe_pairs_here", "moe_dispatch_ms", "host_step_gap_ms",
          "step_s_worst_over_median", "tokens_per_s_chip_median_step",
          "data_wait_ms",
          "data_wait_span_ms", "step_device_ms", "device_idle_share",
          "peak_hbm_gib", "forward_ms", "recompute_ms", "backward_ms",
          "optimizer_ms", "head_loss_ms", "step_unnamed_ms",
          "startup_to_mesh_s", "compile_trace_s", "compile_lower_s",
          "compile_backend_s", "compile_text_s", "startup_build_s",
          "startup_init_s")
NOT_JOINED = ("router_bias_absmax", "latent_attn_ms", "latent_proj_ms",
              "kda_latent_flash_roofline", "kda_latent_moe_step_mfu",
              "delta_rule_roofline", "mtp_ms", "sliding_attn_ms",
              "full_attn_ms", "step_mfu", "flash_roofline",
              "sparse_attn_roofline", "attn_proj_ms")
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
    assert "16384" in cell["why"] and "KDA" in cell["why"]
    reported = {m["name"] for m in layers.cell_entries(
        build.manifest(), CELL, "per_layer"
    )}
    assert set(OWN + JOINED) <= reported
    assert "compile_s" in reported
    cells = build.manifest()["workloads"]
    assert len(cells) >= 15
    assert sum(w["chips"] == 4 for w in cells) <= len(cells) // 4
