"""What the GLM-5.2 cell brings to the benchmark: its program against its
file, its own plain reference against the repository's, the arithmetic of
its cost module by hand, and its readers on a recorded list of op names.
(The file against the catalog is ``tests/test_glm_dsa_config.py``'s; the
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
    flops_dsa_latent_moe,
    flops_latent_moe,
    layers,
)
from benchmark.readers import (  # noqa: E402
    kernel_roofline_from,
    mfu_from,
    program_events,
    scope_ms,
)

NAME = "glm-5.2"
CONFIG = os.path.join(REPO, "benchmark", "configs", f"{NAME}.json")
PRESET = os.path.join(HERE, "presets", f"{NAME}.json")
TRAFFIC = os.path.join(REPO, "benchmark", "traffic", "train_16k_own_ref.json")
CELL = f"{NAME}.train_16k"
MODULE = "flops_dsa_latent_moe"
SEQ = 16384
CHOSEN = 2048 * 2049 // 2 + (SEQ - 2048) * 2048        # 31,458,304
CAUSAL = SEQ * (SEQ + 1) // 2                           # 134,225,920


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
    assert cfg.num_params() == config["num_params"]
    assert (cfg.num_index_layers, cfg.num_reuse_layers) == (
        2 + cfg.mtp_depth, 3
    )
    assert cfg.max_seq_len == SEQ and cfg.num_scan_units == 1
    assert traffic["reference_sequences"] == 1
    assert traffic["scenario"] == "train_steady_own_ref"
    facts = kernel_facts(cfg, SEQ)
    assert facts["sparse_attention"] == "masked_kernel"
    # rows of 6,144 are whole native tiles, but a tile of tokens' 8 rows
    # each overflows the row kernel's VMEM plan: the moves run in XLA
    # (PERF.md §7, open inside the GLM step)
    assert facts["row_moves"] == "xla"
    # a query keeps 23.44 % of the pairs it may see
    assert CHOSEN / CAUSAL == pytest.approx(0.2344, abs=1e-4)


def test_the_preset_is_the_cell_in_small():
    config, preset = build.load_json(CONFIG), build.load_json(PRESET)
    assert set(preset) == set(config)
    for group in ("to_program", "trainer"):
        assert preset[group] == config[group]
    varies = ("moe_row_budget", "mtp_layer_kind", "dtype", "param_dtype")
    assert {
        k: v for k, v in preset["program"].items() if k not in varies
    } == {
        k: v for k, v in config["program"].items() if k not in varies
    }
    assert preset["reference_module"] == config["reference_module"]
    seq = build.seq_len(preset, build.load_json(TRAFFIC)["rehearsal"])
    cfg = build.transformer_config(build.model_group(preset), seq)
    # the same kinds: a dense layer that chooses, one period of three
    # reusing layers and a choosing one, a module that chooses, fewer keys
    # kept than the sequence has, 4 of 16 experts held, an untied head
    assert cfg.num_scan_units == 1 and not cfg.tie_embeddings
    assert cfg.layer_pattern == ("reuse_attention",) * 3 + (
        "index_attention",
    )
    assert cfg.first_k_dense == 1 and cfg.mtp_depth == 1
    assert cfg.index_topk < seq
    assert (cfg.num_experts, cfg.resolved_experts_held) == (16, 4)


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

    from benchmark.references import glm_dsa as ours
    from dlrover_tpu.models.references import glm_dsa as theirs
    from dlrover_tpu.models.transformer import TransformerLM

    with open(ours.__file__) as a, open(theirs.__file__) as b:
        assert a.read() == b.read()        # one text in both places
    model, params, inputs, targets = preset_case
    exact = np.asarray(ours.token_nll(model, params, inputs, targets))
    # the program, built from the file as the worker builds it (float32)
    lm = TransformerLM(build.transformer_config(model, inputs.shape[1]))
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lm.apply)({"params": params}, inputs)
    logp = jax.nn.log_softmax(logits, axis=-1)
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
    for wrong in ("dense", "window", "half_topk", "reuse_chooses", "no_relu"):
        other = np.asarray(ours.token_nll(
            model, params, inputs, targets, wrong=wrong
        ))
        assert float(np.abs(other - exact).mean()) > 1e-2, wrong


def test_the_flops_of_a_token_by_hand():
    model = cell_model()
    mtp = int(model["mtp_depth"])
    parts = flops_dsa_latent_moe.flops_per_token_by_part(model, SEQ)
    sibling = flops_latent_moe.flops_per_token_by_part(model, SEQ)
    assert flops_dsa_latent_moe.choosing_layers(model) == 2 + mtp
    assert flops_dsa_latent_moe.chosen_pairs(model, SEQ) == CHOSEN
    assert flops_dsa_latent_moe.indexer_params(model) == 9_371_648
    # attention on the chosen pairs: 16 heads, 256 + 256 wide, 5 + mtp layers
    assert parts["attention"] == pytest.approx(
        6.0 * (5 + mtp) * 16 * 512 * CHOSEN / SEQ
    )
    assert parts["attention"] / sibling["attention"] == pytest.approx(
        CHOSEN / SEQ / SEQ
    )
    assert parts["indexer_projections"] == 6.0 * (2 + mtp) * 9_371_648
    assert parts["index_scores"] == pytest.approx(
        6.0 * (2 + mtp) * 32 * 128 * CAUSAL / SEQ
    )
    same = set(sibling) - {"attention"}
    assert {k: parts[k] for k in same} == {k: sibling[k] for k in same}
    assert flops_dsa_latent_moe.model_flops_per_token(
        model, SEQ
    ) == pytest.approx(sum(parts.values()))
    # the step: the issue counted 53.6 TFLOP forward with the module
    step = SEQ * sum(parts.values()) / 3
    assert 40e12 < step < 60e12


def test_the_kernel_costs_by_hand():
    model = cell_model()
    mtp = int(model["mtp_depth"])
    peak = build.peak_for("TPU v5 lite")
    sparse = flops_dsa_latent_moe.sparse_flash_cost(model, SEQ, 1)
    assert sparse["flops"] == 2.0 * CHOSEN * 16 * (4 * 256 + 3 * 256) * (
        5 + mtp
    )
    whole = flops_latent_moe.latent_flash_cost(model, SEQ, 1)
    assert sparse["bytes"] == whole["bytes"]
    # a masked kernel over the causal triangle can read at most this share
    assert sparse["flops"] / whole["flops"] == pytest.approx(
        CHOSEN / (SEQ * SEQ / 2)
    )
    assert flops.roofline_seconds(sparse, peak)["bound"] == "compute"
    scores = flops_dsa_latent_moe.index_score_cost(model, SEQ, 1)
    assert scores["flops"] == 3 * 2.0 * 32 * 128 * CAUSAL * (2 + mtp)
    held = flops_dsa_latent_moe.held_expert_matmul_cost(model, SEQ, 1)
    assert held["flops"] == 3 * 3 * 2.0 * 4096 * 6144 * 2048 * (4 + mtp)
    # another model has no indexer: nothing to read
    other = build.model_group(build.load_json(os.path.join(
        REPO, "benchmark", "configs", "joyai-llm-flash.json"
    )))
    for fn in ("sparse_flash_cost", "index_score_cost"):
        with pytest.raises(KeyError):
            getattr(flops_dsa_latent_moe, fn)(other, 8192, 2)
    with pytest.raises(KeyError):
        flops_dsa_latent_moe.model_flops_per_token(other, 8192)


STEP = "jit(_train_step)/"
FWD = STEP + "jvp(TransformerLM)/"
BACK = STEP + "transpose(jvp(TransformerLM))/"
ROWS = [
    ["while.3", "", 0, 9000],
    ["fusion.0", FWD + "dense_0/attn/q_a/dot_general", 0, 40],
    ["fusion.1", FWD + "dense_0/attn/indexer/wq_b/dot_general", 40, 60],
    ["fusion.2", FWD + "dense_0/attn/indexer/rope/mul", 100, 10],
    ["fusion.3", FWD + "dense_0/attn/select/while/body/dot_general", 110, 300],
    ["fusion.4", FWD + "dense_0/attn/select/while/body/reduce", 410, 200],
    ["sparse.1", FWD + "dense_0/attn/sparse/pallas_call", 610, 1000],
    ["fusion.5", FWD + "dense_0/attn/index_kl/while/body/dot_general", 1610,
     700],
    ["sparse.2", FWD + "blocks/reuse_0/attn/sparse/pallas_call", 2310, 1000],
    ["sparse.3", BACK + "blocks/reuse_0/attn/sparse/pallas_call", 3310, 2500],
    ["fusion.6", BACK + "blocks/index_3/attn/index_kl/mul", 5810, 30],
    ["fusion.7", BACK + "blocks/index_3/attn/indexer/wk/dot_general", 5840,
     20],
    ["gmm.1", FWD + "blocks/reuse_1/moe/gmm_wi/pallas_call", 5860, 300],
    ["fusion.8", FWD + "mtp/block/attn/select/while/body/reduce", 6160, 100],
    # another model's latent attention is under none of the four scopes
    ["attn.5", FWD + "blocks/attn/pallas_call", 6300, 50],
    ["fusion.9", FWD + "blocks/attn/q_b/dot_general", 6350, 50],
]
TRACE = {"devices": {"/device:TPU:0": {
    "ops": ROWS[:-2], "modules": [["jit__train_step(1)", "", 0, 9000]],
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

    assert ms("indexer_ms") == pytest.approx(90e-6)
    assert ms("index_select_ms") == pytest.approx(600e-6)
    assert ms("sparse_attn_ms") == pytest.approx(4500e-6)
    assert ms("index_kl_ms") == pytest.approx(730e-6)
    # the four are disjoint and all lie under the layer's attention
    assert ms("latent_attn_ms") == pytest.approx(
        (90 + 600 + 4500 + 730 + 40) * 1e-6
    )
    assert ms("latent_proj_ms") == pytest.approx(40e-6)
    for name in ("indexer_ms", "index_select_ms", "sparse_attn_ms",
                 "index_kl_ms"):
        assert ms(name, OTHER) is None


def test_the_roofline_reads_its_own_ops_against_its_own_cost():
    spec = layers.spec("sparse_attn_roofline")
    floor = flops.roofline_seconds(
        flops_dsa_latent_moe.sparse_flash_cost(cell_model(), SEQ, 1),
        build.peak_for("TPU v5 lite"),
    )["seconds"]
    assert kernel_roofline_from.read(
        evidence(), spec["params"]
    ) == pytest.approx(100 * floor / 4500e-9)
    assert kernel_roofline_from.read(
        evidence(OTHER), spec["params"]
    ) is None
    # the accepted share of the whole triangle does not read these kernels'
    # cost: the cell is not in its list
    held = layers.spec("held_grouped_matmul_roofline")
    assert kernel_roofline_from.read(evidence(), held["params"]) is not None


def test_the_step_mfu_counts_by_part_and_leaves_other_models_alone():
    spec = layers.spec("dsa_latent_moe_step_mfu")
    assert spec["reader"] == "mfu_from" and spec["params"] == {
        "module": MODULE
    }
    model = cell_model()
    summary = {"tokens_per_s_chip": 8000.0}
    got = mfu_from.read(evidence(summary=summary), spec["params"])
    per_token = flops_dsa_latent_moe.model_flops_per_token(model, SEQ)
    assert got == pytest.approx(per_token * 8000.0 / 197e12)
    assert 0.1 < got < 0.7
    for other in ("gpt2-1.5b", "joyai-llm-flash", "ling-3.0-flash-vl",
                  "command-a-plus-05-2026"):
        group = build.model_group(build.load_json(
            os.path.join(REPO, "benchmark", "configs", f"{other}.json")
        ))
        assert mfu_from.read(
            evidence(summary=summary, model=group), spec["params"]
        ) is None, other


def test_the_index_event_s_numbers_are_read_from_the_program_s_events():
    event = ["index", "event", 0.0, 0.0, {
        "step": 12, "selected_share": 0.2344, "kl": 1.5, "score_absmax": 7.0,
    }]
    later = ["index", "event", 0.0, 0.0, {
        "step": 16, "selected_share": 0.2344, "kl": 1.25, "score_absmax": 9.0,
    }]
    found = {"spans": [event, later], "step_ids": [8, 12, 16],
             "window_steps": [8, 16]}
    want = {"index_selected_share": 0.2344, "index_kl": 1.375,
            "index_score_absmax": 9.0}
    for name, value in want.items():
        spec = layers.spec(name)
        assert spec["reader"] == "program_events"
        got = program_events.read(found, spec["params"])
        if got is not None:     # the window's steps as the reader finds them
            assert got == pytest.approx(value), name
        assert program_events.read(
            dict(found, spans=[]), spec["params"]
        ) is None


OWN = ("dsa_latent_moe_step_mfu", "indexer_ms", "index_select_ms",
       "sparse_attn_ms", "index_kl_ms", "sparse_attn_roofline",
       "index_selected_share", "index_kl", "index_score_absmax")
JOINED = ("latent_attn_ms", "latent_proj_ms", "shared_expert_ms",
          "held_grouped_matmul_roofline", "moe_pad_share",
          "moe_max_expert_load", "moe_pairs_here", "moe_row_move_ms",
          "moe_row_gather_ms", "moe_router_ms", "router_bias_absmax",
          "host_step_gap_ms", "step_s_worst_over_median",
          "tokens_per_s_chip_median_step", "data_wait_ms",
          "data_wait_span_ms", "step_device_ms", "device_idle_share",
          "peak_hbm_gib", "startup_to_mesh_s", "compile_trace_s",
          "compile_lower_s", "compile_backend_s", "compile_text_s",
          "startup_build_s", "startup_init_s", "forward_ms", "recompute_ms",
          "backward_ms", "optimizer_ms", "head_loss_ms", "step_unnamed_ms")
NOT_JOINED = ("moe_dispatch_ms", "latent_flash_roofline",
              "latent_moe_step_mfu", "kda_latent_flash_roofline",
              "sliding_attn_ms", "full_attn_ms", "step_mfu",
              "flash_roofline", "parallel_moe_step_mfu", "attn_score_bound")
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
        assert entry["workloads"] == [CELL]
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
    assert "2,048" in cell["why"] and "23.4 %" in cell["why"]
    reported = {m["name"] for m in layers.cell_entries(
        build.manifest(), CELL, "per_layer"
    )}
    assert set(OWN + JOINED) <= reported
    assert "compile_s" in reported
    mtp = bool(cell_model()["mtp_depth"])
    assert ("mtp_ms" in reported) == mtp
    cells = build.manifest()["workloads"]
    assert len(cells) >= 14
    assert sum(w["chips"] == 4 for w in cells) <= len(cells) // 4
