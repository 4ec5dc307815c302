"""What the JoyAI-LLM-Flash cell brings to the benchmark: its configuration
against the catalog, its own plain reference against the repository's, the
arithmetic of its cost module by hand, and its readers on recorded data."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import build, flops, flops_latent_moe, layers  # noqa: E402
from benchmark.readers import (  # noqa: E402
    kernel_roofline_from,
    mfu_from,
    program_events,
    scope_ms,
)

# ``config`` of the catalog's entry JoyAI-LLM-Flash (the model-configs
# guide's architectures.jsonl), as published.
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280,
}
CONFIG = os.path.join(REPO, "benchmark", "configs", "joyai-llm-flash.json")
PRESET = os.path.join(HERE, "presets", "joyai-llm-flash.json")
CELL = "joyai-llm-flash.train_steady"
MODULE = "flops_latent_moe"


def cell_model():
    return build.model_group(build.load_json(CONFIG))


def test_the_configuration_differs_from_the_catalog_in_what_it_says():
    config = build.load_json(CONFIG)
    assert set(CATALOG) <= set(config)
    differs = {k for k, v in CATALOG.items() if config[k] != v}
    assert differs == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert differs == set(config["reduced"])
    entry = {c["name"]: c for c in build.manifest()["configs"]}[
        "joyai-llm-flash"
    ]
    assert set(entry["reduced"]) == differs
    for key in differs:
        assert config["reduced"][key]["published"] == CATALOG[key]
        assert config["reduced"][key]["run"] == config[key]
    # the floors: four expert layers after the dense one, eight experts,
    # an eighth of the vocabulary (in whole lanes; ids from the eighth)
    assert config["num_hidden_layers"] >= 1 + 4
    assert config["n_routed_experts"] == 32 >= 8
    assert config["router_experts"] == CATALOG["n_routed_experts"] == 256
    assert config["token_vocab"] * 8 == CATALOG["vocab_size"]
    assert config["vocab_size"] == -(-config["token_vocab"] // 128) * 128
    assert "eight chips share each layer" in config["deployment"]
    model = cell_model()
    assert (model["num_experts"], model["experts_held"]) == (256, 32)
    assert model["first_expert"] == 0 and model["top_k"] == 8
    assert model["moe_d_ff"] == 768 and model["d_ff"] == 7168
    assert (model["q_lora_rank"], model["kv_lora_rank"]) == (1536, 512)
    assert (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
            model["v_head_dim"]) == (128, 64, 128)
    assert model["router_scoring"] == "sigmoid" and model["router_bias"]
    assert model["routed_scaling_factor"] == 2.5
    assert model["first_k_dense"] == 1 and model["mtp_depth"] == 1
    assert {"optimizer", "precision", "router_bias_rate", "mtp_weight",
            "rope_interleave", "mtp_concat_order", "sequence",
            "moe_row_budget", "balance_term"} <= set(config["assumed"])


def test_the_program_takes_the_configuration():
    from dlrover_tpu.models.joyai_llm_flash import joyai_llm_flash_config

    config = build.load_json(CONFIG)
    cfg = build.transformer_config(cell_model(), build.seq_len(config, {}))
    layers_run = config["num_hidden_layers"]
    want = joyai_llm_flash_config(
        num_layers=layers_run, vocab_size=16256, experts_held=32,
    )
    for field in ("d_model", "num_heads", "d_ff", "moe_d_ff", "q_lora_rank",
                  "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                  "v_head_dim", "num_experts", "experts_held", "first_expert",
                  "top_k", "router_scoring", "router_bias",
                  "router_bias_rate", "routed_scaling_factor",
                  "norm_topk_prob", "num_shared_experts", "first_k_dense",
                  "mtp_depth", "mtp_weight", "rope_theta", "norm_eps",
                  "tie_embeddings", "moe_dispatch", "max_seq_len"):
        assert getattr(cfg, field) == getattr(want, field), field
    assert cfg.num_scan_units == layers_run - 1 and cfg.latent_attention
    # the issue's arithmetic: attention 26,345,472 + two latent norms, an
    # expert 4,718,592, a layer outside its routed experts 31.59 M
    attn = 26_345_472 + 1536 + 512
    expert = 3 * 2048 * 768
    layer = attn + 33 * expert + 2048 * 256 + 256
    dense = attn + 3 * 2048 * 7168
    mtp = layer + 2 * 2048 * 2048 + 3 * 2048
    assert expert == 4_718_592
    assert cfg.num_params() == (
        (layers_run - 1) * layer + dense + mtp + 2 * 16256 * 2048
    )


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
    params = nn.meta.unbox(lm.init(jax.random.PRNGKey(3), rows[:, :-1]))
    return model, params["params"], rows[:, :-1], rows[:, 1:]


def test_the_benchmarks_reference_agrees_with_the_repositorys(preset_case):
    import numpy as np

    from benchmark.references import joyai_llm_flash as ours
    from dlrover_tpu.models.references import joyai_llm_flash as theirs

    model, params, inputs, targets = preset_case
    got = ours.forward(model, params, inputs, targets)
    want = theirs.forward(model, params, inputs, targets)
    # two float32 programs of one mathematics
    for key in ("nll", "mtp_nll"):
        np.testing.assert_allclose(got[key], want[key], atol=2e-5)
    np.testing.assert_allclose(
        ours.token_nll(model, params, inputs, targets), want["nll"], atol=2e-5
    )
    # three expert layers' counts over all 16 experts: 2 x 64 tokens x 4
    assert [int(c.sum()) for c in got["counts"]] == [512] * 3
    assert got["counts"][0].shape == (16,)


def test_a_lowered_reference_is_another_result(preset_case):
    """What ``reference_tolerance`` is set against (PERF.md §6): the
    reference with its router, or all of it, in bfloat16."""
    import numpy as np

    from benchmark.references import joyai_llm_flash as ours

    model, params, inputs, targets = preset_case
    exact = np.asarray(ours.token_nll(model, params, inputs, targets))
    gaps = {
        mode: float(np.abs(np.asarray(ours.token_nll(
            model, params, inputs, targets, lowered=mode
        )) - exact).mean())
        for mode in ("router", "all")
    }
    assert 0 < gaps["router"] < gaps["all"]


def test_the_flops_of_a_token_by_hand():
    model = cell_model()
    layers_run = int(model["num_layers"])
    expert_layers = layers_run - 1 + 1          # the trunk's and the MTP's
    attn_layers = layers_run + 1
    # q_a 2048 x 1536, q_b 1536 x 32 x 192, kv_a 2048 x 576,
    # kv_b 512 x 32 x 256, wo 4096 x 2048
    proj = 3_145_728 + 9_437_184 + 1_179_648 + 4_194_304 + 8_388_608
    assert proj == 26_345_472
    assert flops_latent_moe.latent_projection_params(model) == proj
    # of 8 pairs a token an eighth is routed here: one expert's worth
    assert flops_latent_moe.pairs_here_per_token(model) == 1.0
    parts = flops_latent_moe.flops_per_token_by_part(model, 8192)
    assert parts == {
        "latent_projections": 6.0 * attn_layers * proj,
        "attention": 6.0 * attn_layers * 32 * 8192 * (192 + 128),
        "dense_mlp": 6.0 * 3 * 2048 * 7168,
        "shared_experts": 6.0 * expert_layers * 4_718_592,
        "routed_here": 6.0 * expert_layers * 4_718_592,
        "router": 6.0 * expert_layers * 2048 * 256,
        "heads": 6.0 * 2 * 16256 * 2048,
        "eh_proj": 6.0 * 2 * 2048 * 2048,
    }
    assert flops_latent_moe.model_flops_per_token(model, 8192) == sum(
        parts.values()
    )
    # at 8192 tokens the attention's scores and values are over half
    assert 0.5 < parts["attention"] / sum(parts.values()) < 0.7
    # ``flops.py`` cannot count this model: it knows one head size
    with pytest.raises(KeyError):
        flops_latent_moe.model_flops_per_token(
            {k: v for k, v in model.items() if k != "kv_lora_rank"}, 8192
        )


def test_the_two_kernel_costs_by_hand():
    model = cell_model()
    attn_layers = int(model["num_layers"]) + 1
    expert_layers = int(model["num_layers"])
    cost = flops_latent_moe.latent_flash_cost(model, 8192, 2)
    # seven matmuls a head: QK^T, dQ, dK and QK^T again at 192; PV, dV, dP
    # at 128; 2 S^2 each, halved by the mask, 32 heads, 2 sequences
    per_layer = 2.0 * 8192 * 8192 * 32 * 2 * (4 * 192 + 3 * 128) * 0.5
    assert cost["flops"] == per_layer * attn_layers
    # bf16 q, k (192) and v, o (128) forward; those and do in, dq, dk, dv
    # out backward; float32 lse once each way
    tokens = 2 * 8192 * 32
    fwd = tokens * (2 * (2 * 192 + 2 * 128) + 4)
    bwd = tokens * (2 * (4 * 192 + 4 * 128) + 4)
    assert cost["bytes"] == (fwd + bwd) * attn_layers
    peak = build.peak_for("TPU v5 lite")
    assert flops.roofline_seconds(cost, peak)["bound"] == "compute"
    # padding v to 192 would be half again the PV, dV and dP work
    assert (4 * 192 + 3 * 192) / (4 * 192 + 3 * 128) == pytest.approx(7 / 6)
    held = flops_latent_moe.held_expert_matmul_cost(model, 8192, 2)
    # 16,384 pairs here a layer (131,072 chosen, an eighth), three
    # matrices of 2048 x 768, three passes
    assert held["flops"] == 3 * 3 * 2.0 * 16384 * 2048 * 768 * expert_layers
    weights = 2.0 * 32 * 3 * 2048 * 768
    acts = 2.0 * 16384 * (2 * 2048 + 3 * 768)
    assert held["bytes"] == 3 * (weights + acts) * expert_layers
    # counted over all the router chose it would read eight times higher
    everywhere = flops.expert_matmul_cost(
        dict(model, d_ff=768, num_layers=expert_layers), 8192, 2
    )
    assert everywhere["flops"] == 8 * held["flops"]


STEP = "jit(_train_step)/"
ROWS = [
    ["while.3", "", 0, 3000],
    ["fusion.1", STEP + "blocks/attn/q_a/dot_general", 0, 100],
    ["fusion.2", STEP + "blocks/attn/kv_b/dot_general", 100, 50],
    ["fusion.3", STEP + "blocks/attn/rope/concatenate", 150, 25],
    ["fusion.4", STEP + "blocks/attn/q_norm/mul", 175, 25],
    ["attn.2", STEP + "blocks/attn/pallas_call", 200, 400],
    ["attn.3", STEP + "transpose(jvp())/mtp/block/attn/pallas_call", 600, 200],
    ["fusion.5", STEP + "transpose(jvp())/blocks/attn/wo/dot_general",
     800, 75],
    ["fusion.6", STEP + "blocks/moe/shared/wi/dot_general", 875, 125],
    ["gmm.1", STEP + "blocks/moe/gmm_wi/pallas_call", 1000, 300],
    ["gmm.2", STEP + "transpose(jvp())/mtp/block/moe/gmm_wo/pallas_call",
     1300, 100],
    ["row_gather_sum.1", STEP + "blocks/moe/combine/jit(gather_sum)/"
     "row_gather_sum/pallas_call", 1400, 150],
    ["fusion.7", STEP + "mtp/proj/dot_general", 1550, 50],
    ["fusion.8", STEP + "mtp/head/dot_general", 1600, 250],
    ["fusion.9", STEP + "dense_0/mlp/wi/dot_general", 1850, 500],
]
TRACE = {"devices": {"/device:TPU:0": {
    "ops": ROWS, "modules": [["jit__train_step(1)", "", 0, 3000]],
}}, "host": []}
OTHER = {"devices": {"/device:TPU:0": {
    "ops": [ROWS[0], ROWS[14]],
    "modules": TRACE["devices"]["/device:TPU:0"]["modules"],
}}, "host": []}


def evidence(trace=TRACE, **more):
    return dict({
        "trace": trace, "step_module": "train_step", "model": cell_model(),
        "seq_len": 8192, "sequences_per_chip": 2,
        "peak": build.peak_for("TPU v5 lite"),
    }, **more)


def test_the_scope_readers_split_attention_the_shared_expert_and_the_module():
    def ms(name, trace=TRACE):
        spec = layers.spec(name)
        assert spec["reader"] == "scope_ms"
        return scope_ms.read(evidence(trace), spec["params"])

    # everything under attn/: 100 + 50 + 25 + 25 + 400 + 200 + 75
    assert ms("latent_attn_ms") == pytest.approx(875e-6)
    # the five projections and rope: not the norms, not the kernels
    assert ms("latent_proj_ms") == pytest.approx(250e-6)
    assert ms("shared_expert_ms") == pytest.approx(125e-6)
    # the module's kernel, its GEMM, its projection and its head
    assert ms("mtp_ms") == pytest.approx(600e-6)
    for name in ("latent_attn_ms", "latent_proj_ms", "shared_expert_ms",
                 "mtp_ms"):
        # a program with no such scope (the parent) gives nothing
        assert ms(name, OTHER) is None


def test_the_two_rooflines_read_their_own_ops_against_their_own_cost():
    flash = layers.spec("latent_flash_roofline")
    held = layers.spec("held_grouped_matmul_roofline")
    assert flash["reader"] == held["reader"] == "kernel_roofline_from"
    assert flash["params"]["module"] == held["params"]["module"] == MODULE
    peak = build.peak_for("TPU v5 lite")
    model = cell_model()
    floor = flops.roofline_seconds(
        flops_latent_moe.latent_flash_cost(model, 8192, 2), peak
    )["seconds"]
    # the kernels under attn/, the trunk's and the module's: 400 + 200
    assert kernel_roofline_from.read(
        evidence(), flash["params"]
    ) == pytest.approx(100 * floor / 600e-9)
    floor = flops.roofline_seconds(
        flops_latent_moe.held_expert_matmul_cost(model, 8192, 2), peak
    )["seconds"]
    # the GEMMs (300 + 100), not the fetch-and-sum kernel beside them
    assert kernel_roofline_from.read(
        evidence(), held["params"]
    ) == pytest.approx(100 * floor / 400e-9)
    # nothing to read: no such op, no peak, no trace, an older model group
    for params in (flash["params"], held["params"]):
        assert kernel_roofline_from.read(evidence(OTHER), params) is None
        assert kernel_roofline_from.read(evidence(peak=None), params) is None
        assert kernel_roofline_from.read({}, params) is None
        older = {k: v for k, v in model.items() if "lora" not in k}
        older.pop("num_experts")
        assert kernel_roofline_from.read(
            evidence(model=older), params
        ) is None


def test_latent_moe_step_mfu_counts_by_part_and_leaves_other_models_alone():
    spec = layers.spec("latent_moe_step_mfu")
    assert spec["reader"] == "mfu_from" and spec["params"] == {
        "module": MODULE
    }
    model = cell_model()
    summary = {"tokens_per_s_chip": 12000.0}
    got = mfu_from.read(evidence(summary=summary), spec["params"])
    per_token = flops_latent_moe.model_flops_per_token(model, 8192)
    assert got == pytest.approx(per_token * 12000.0 / 197e12)
    assert 0.2 < got < 0.7
    gpt2 = build.model_group(build.load_json(
        os.path.join(REPO, "benchmark", "configs", "gpt2-1.5b.json")
    ))
    assert mfu_from.read(
        evidence(summary=summary, model=gpt2), spec["params"]
    ) is None
    assert mfu_from.read(evidence(), spec["params"]) is None   # no summary
    assert mfu_from.read(
        evidence(summary=summary, peak=None), spec["params"]
    ) is None


def recorded_events():
    with open(os.path.join(HERE, "recorded_joyai_events.json")) as f:
        return json.load(f)


def test_the_event_readers_take_the_windows_share_and_bias():
    share = layers.spec("moe_pairs_here")
    bias = layers.spec("router_bias_absmax")
    assert share["reader"] == bias["reader"] == "program_events"
    # steps 5 and 10 lie in the window (5..12); 0 and 15 outside it
    assert program_events.read(
        recorded_events(), share["params"]
    ) == pytest.approx(0.1255)
    assert program_events.read(recorded_events(), bias["params"]) == 0.010
    # a program whose moe event has no such attribute (the parent's)
    older = recorded_events()
    for event in older["program_spans"]:
        event[4].pop("pairs_here", None)
        event[4].pop("bias_absmax", None)
    assert program_events.read(older, share["params"]) is None
    assert program_events.read(older, bias["params"]) is None
    # the accepted readers of the same event still read it
    assert program_events.read(
        recorded_events(), layers.spec("moe_pad_share")["params"]
    ) == pytest.approx(0.11)


def test_the_cell_joins_the_lists_the_issue_names_and_no_cost_of_flops_py():
    per_layer = {m["name"]: m for m in build.manifest()["per_layer"]}
    joined = {name for name, m in per_layer.items()
              if CELL in m.get("workloads", [])}
    own = {"latent_attn_ms", "latent_proj_ms", "latent_flash_roofline",
           "shared_expert_ms", "mtp_ms", "held_grouped_matmul_roofline",
           "latent_moe_step_mfu", "moe_pairs_here", "router_bias_absmax"}
    # membership only: the next metric or cell to join breaks nothing here
    assert joined >= own | {
        "host_step_gap_ms", "step_s_worst_over_median",
        "tokens_per_s_chip_median_step", "data_wait_ms",
        "data_wait_span_ms", "step_device_ms", "device_idle_share",
        "peak_hbm_gib", "startup_to_mesh_s", "moe_row_move_ms",
        "moe_pad_share", "moe_max_expert_load",
        # owed since PR 35, joined as a data change (PR 47)
        "forward_ms", "recompute_ms", "backward_ms", "optimizer_ms",
        "head_loss_ms", "step_unnamed_ms",
    }
    # not the metrics whose costs do not describe this model, nor the
    # dispatch's milliseconds, whose pattern would take in moe/shared/
    for name in ("step_mfu", "flash_roofline", "flash_attn_roofline",
                 "grouped_matmul_roofline", "moe_dispatch_ms"):
        assert CELL not in per_layer[name]["workloads"]
    for name in own:
        assert CELL in per_layer[name]["workloads"]
        assert per_layer[name]["moves"] == "tokens_per_s_chip"
        assert layers.spec(name)["name"] == name
    e2e = {m["name"]: m for m in build.manifest()["end_to_end"]}
    assert CELL in e2e["tokens_per_s_chip"]["workloads"]
    cell = {w["name"]: w for w in build.manifest()["workloads"]}[CELL]
    assert cell["traffic"] == "train_steady_own_ref" and cell["chips"] == 1
