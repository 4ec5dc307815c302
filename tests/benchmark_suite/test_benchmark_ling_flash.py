"""What the Ling-3.0-flash-VL cell brings to the benchmark: its
configuration against the catalog, its own plain reference against the
repository's, the arithmetic of its cost module by hand, and its readers on
a recorded list of op names.  Membership assertions only: never a list's
last place or its whole content, so that the next cell to join a list
breaks nothing here (PERF.md §7 (7))."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import (  # noqa: E402
    build, flops, flops_kda_latent_moe, flops_latent_moe, layers,
)
from benchmark.readers import (  # noqa: E402
    kernel_roofline_from,
    mfu_from,
    scope_ms,
)

NAME = "ling-3.0-flash-vl"
CONFIG = os.path.join(REPO, "benchmark", "configs", f"{NAME}.json")
PRESET = os.path.join(HERE, "presets", f"{NAME}.json")
CELL = f"{NAME}.train_steady"
MODULE = "flops_kda_latent_moe"
GUIDE = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {
    "num_hidden_layers": (42, 7), "first_k_dense_replace": (2, 1),
    "num_experts": (512, 32), "vocab_size": (157184, 19712),
}
# the catalog's numbers this file is held to where the guide is not on the
# machine (its whole ``config`` where it is)
PUBLISHED = {
    "hidden_size": 2560, "intermediate_size": 6144,
    "moe_intermediate_size": 768, "num_experts_per_tok": 8,
    "num_attention_heads": 32, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "head_dim": 128,
    "rope_theta": 6000000, "rms_norm_eps": 1e-06, "n_group": 8,
    "topk_group": 4, "routed_scaling_factor": 2.5, "layer_group_size": 6,
    "moe_shared_expert_intermediate_size": 768, "short_conv_kernel_size": 4,
    "kda_lower_bound": -5, "q_lora_rank": None, "kda_safe_gate": True,
    "gated_attention_proj_granularity_type": "head_wise",
    "score_function": "sigmoid", "moe_router_enable_expert_bias": True,
    "max_position_embeddings": 131072,
}


def cell_model():
    return build.model_group(build.load_json(CONFIG))


def test_every_key_of_the_catalogs_config_is_in_the_file_under_its_name():
    if not os.path.exists(GUIDE):
        pytest.skip("the model-configs guide is not on this machine")
    with open(GUIDE) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r["name"] == "Ling-3.0-flash-VL"]
    config = build.load_json(CONFIG)
    assert row["source_url"] == config["source"]
    assert set(row["config"]) <= set(config)
    differs = {k for k, v in row["config"].items() if config[k] != v}
    assert differs == set(REDUCED)
    assert {k: row["config"][k] for k in PUBLISHED} == PUBLISHED


def test_the_configuration_differs_from_the_catalog_in_what_it_says():
    config = build.load_json(CONFIG)
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert set(config["reduced"]) == set(REDUCED)
    entry = {c["name"]: c for c in build.manifest()["configs"]}[NAME]
    assert set(entry["reduced"]) == set(REDUCED)
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == config["source"]
    for key, (published, run) in REDUCED.items():
        assert config["reduced"][key]["published"] == published
        assert config["reduced"][key]["run"] == run == config[key]
        assert len(config["reduced"][key]["why"]) > 100
    # no width is among them
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    # the floors: a whole period and at least four layers after the dense
    # ones, eight experts or more, an eighth of the vocabulary
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] == (
        config["layer_group_size"]
    ) == 6 > 4
    assert config["num_experts"] == 32 >= 8
    assert config["router_experts"] == 512 and config["first_expert"] == 0
    assert config["token_vocab"] * 8 == 157184
    assert config["vocab_size"] % 128 == 0
    assert 0 <= config["vocab_size"] - config["token_vocab"] < 128
    # the vision tower's ids lie outside the slice
    assert config["video_patch_token"] > config["vocab_size"]
    assert "sixteen chips share each layer" in config["deployment"]
    assert "experts 0-31" in config["deployment"]
    assert "vocabulary over 8" in config["deployment"]
    assert "Nothing stands in for the absent chips" in config["deployment"]
    # no clamp in any layer run (published layers 1 to 7)
    assert not any(config["expert_swiglu_limit_list"][:34])
    assert not any(config["share_expert_swiglu_limit_list"][:34])
    assert {"norm_placement", "tie_word_embeddings", "kda_heads",
            "kda_qk_norm", "kda_gate", "kda_position", "kda_initialisers",
            "latent_attention", "group_score", "router_bias_rate",
            "optimizer", "precision", "remat", "moe_row_budget",
            "sequence"} <= set(config["assumed"])
    for left_out in ("vision tower", "MTP", "use_nGPT", "no clamp"):
        assert left_out in config["described_in"], left_out
    tol = config["reference_tolerance"]
    assert 0 < tol["mean_abs_token_nll"] < 0.05
    assert tol["first_step_loss"] == 0.1
    assert "memory_peak_bytes" in json.dumps(config["compiled_bytes"])


def test_the_program_takes_the_configuration():
    from dlrover_tpu.models import ling_flash
    from dlrover_tpu.models.moe import _share_row_budget
    from dlrover_tpu.ops import kda, row_gather_sum

    config = build.load_json(CONFIG)
    model = cell_model()
    cfg = build.transformer_config(model, build.seq_len(config, {}))
    want = ling_flash.ling_flash_config(
        num_layers=7, first_k_dense=1, vocab_size=19712, experts_held=32,
    )
    for field in ("d_model", "num_heads", "d_ff", "moe_d_ff",
                  "resolved_shared_d_ff", "num_experts", "experts_held",
                  "first_expert", "top_k", "router_scoring", "router_bias",
                  "router_bias_rate", "router_groups", "router_topk_groups",
                  "norm_topk_prob", "routed_scaling_factor", "norm_eps",
                  "rope_theta", "tie_embeddings", "moe_dispatch",
                  "max_seq_len", "layer_pattern", "first_k_dense",
                  "position", "activation", "norm", "q_lora_rank",
                  "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                  "v_head_dim", "attention_gate", "linear_rule",
                  "resolved_linear_heads", "linear_key_head_dim",
                  "linear_value_head_dim", "linear_conv_kernel",
                  "linear_decay_bound", "norm_placement"):
        assert getattr(cfg, field) == getattr(want, field), field
    assert cfg.layer_pattern == ling_flash.TRUNK_PATTERN
    assert cfg.num_scan_units == 1 and cfg.num_linear_layers == 6
    assert (cfg.remat, cfg.attention_impl) == ("flash_only", "flash")
    # the file's arithmetic (a layer's own norms left out, as ever)
    assert cfg.num_params() == 1_734_095_296
    assert "1,734,095,296" in config["reduced"]["num_hidden_layers"]["why"]
    # the rows set aside for an expert layer's share: 1.25 x 8,192 expected
    # + a block of 128 an expert + the zero block
    assert _share_row_budget(2 * 8192 * 8, 128, 32, 512, 1.25) == 14_464
    assert "14,464" in config["assumed"]["moe_row_budget"]
    # the rule's kernels take these widths; rows of 2,560 (20 lane tiles)
    # go through XLA's gather (PERF.md §6)
    assert kda.plan(cfg.linear_key_head_dim, cfg.linear_value_head_dim) == (
        "kernel"
    )
    assert not row_gather_sum.kernel_fits(2560, 8, "bfloat16")


def test_the_preset_is_the_cell_in_small():
    config, preset = build.load_json(CONFIG), build.load_json(PRESET)
    assert set(preset) == set(config)
    for group in ("to_program", "trainer"):
        assert preset[group] == config[group]
    varies = ("param_dtype", "dtype", "moe_row_budget", "layer_pattern",
              "flash_block_q", "flash_block_kv")
    assert {
        k: v for k, v in preset["program"].items() if k not in varies
    } == {
        k: v for k, v in config["program"].items() if k not in varies
    }
    assert preset["reference_module"] == config["reference_module"]
    cfg = build.transformer_config(
        build.model_group(preset), build.seq_len(preset, {})
    )
    assert cfg.first_k_dense == 1 and cfg.num_scan_units == 1
    assert cfg.layer_pattern == (
        "linear_attention", "full_attention", "linear_attention"
    )
    assert (cfg.router_groups, cfg.router_topk_groups) == (4, 2)


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

    from benchmark.references import ling_flash as ours
    from dlrover_tpu.models.references import ling_flash as theirs

    with open(ours.__file__) as a, open(theirs.__file__) as b:
        assert a.read() == b.read()        # one text in both places
    model, params, inputs, targets = preset_case
    got = ours.forward(model, params, inputs, targets)
    want = theirs.forward(model, params, inputs, targets)
    np.testing.assert_allclose(got["nll"], want["nll"], atol=2e-5)
    np.testing.assert_allclose(
        ours.token_nll(model, params, inputs, targets), want["nll"], atol=2e-5
    )
    # three expert layers' counts over all 32 experts: 2 x 32 x 4 pairs each
    assert [int(c.sum()) for c in got["counts"]] == [256] * 3
    assert all(c.shape == (32,) for c in got["counts"])


def test_a_lowered_reference_is_another_result(preset_case):
    """What ``reference_tolerance`` is set against (PERF.md §6): the
    reference with its router, its recurrence, or all of it, in bfloat16."""
    import numpy as np

    from benchmark.references import ling_flash as ours

    model, params, inputs, targets = preset_case
    exact = np.asarray(ours.token_nll(model, params, inputs, targets))
    gaps = {
        mode: float(np.abs(np.asarray(ours.token_nll(
            model, params, inputs, targets, lowered=mode
        )) - exact).mean())
        for mode in ("router", "rule", "all")
    }
    assert 0 < gaps["router"] < gaps["all"]
    assert 0 < gaps["rule"] < gaps["all"]


def test_the_flops_of_a_token_by_hand():
    model = cell_model()
    assert flops_kda_latent_moe.layer_counts(model) == {
        "linear_attention": 6, "full_attention": 1, "dense": 1, "experts": 6,
    }
    # q, k, v, decay, output gate, wo: six of 2560 x 4096; beta 2560 x 32
    kda = 6 * 2560 * 4096 + 2560 * 32
    assert flops_kda_latent_moe.kda_projection_params(model) == kda
    # q 2560 x 32 x 192, kv_a 2560 x 576, kv_b 512 x 32 x 256, wo, gate
    latent = (2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256
              + 4096 * 2560 + 2560 * 32)
    assert flops_kda_latent_moe.latent_projection_params(model) == latent
    # a chunk of 64 and a head of 128 / 128: K K^T and Q K^T (2 x 64^2 x
    # 128 each), the solve (64^2 x 256), three products with the state (2 x
    # 64 x 128^2 each), M U (2 x 64^2 x 128); a token's 64th, 32 heads
    rule = 32 * (4 * 4096 * 128 + 4096 * 256 + 6 * 64 * 16384
                 + 2 * 4096 * 128) / 64
    assert flops_kda_latent_moe.kda_rule_flops_per_token(model) == rule
    assert rule == 5_242_880
    # of 8 pairs a token a sixteenth is routed here
    assert flops_latent_moe.pairs_here_per_token(model) == 0.5
    parts = flops_kda_latent_moe.flops_per_token_by_part(model, 8192)
    assert parts == {
        "kda_projections": 6.0 * 6 * kda,
        "kda_rule": 3.0 * 6 * rule,
        "latent_projections": 6.0 * 1 * latent,
        # ONE latent layer, the causal half
        "attention": 0.5 * 6.0 * 1 * 32 * 8192 * (192 + 128),
        "dense_mlp": 6.0 * 1 * 3 * 2560 * 6144,
        "shared_experts": 6.0 * 6 * 3 * 2560 * 768,
        "routed_here": 6.0 * 6 * 0.5 * 3 * 2560 * 768,
        "router": 6.0 * 6 * 2560 * 512,
        "heads": 6.0 * 19712 * 2560,
    }
    total = flops_kda_latent_moe.model_flops_per_token(model, 8192)
    assert total == sum(parts.values())
    assert 3.7e9 < total < 3.8e9
    share = {k: v / total for k, v in parts.items()}
    # ISSUE 44's shares: the six KDA mixers about 60%, the one latent layer
    # 12%, the experts with the shared expert and router 10%, the dense MLP
    # and the head 8% each
    assert share["kda_projections"] + share["kda_rule"] == (
        pytest.approx(0.63, abs=0.02)
    )
    assert share["latent_projections"] + share["attention"] == (
        pytest.approx(0.12, abs=0.01)
    )
    assert (share["shared_experts"] + share["routed_here"]
            + share["router"]) == pytest.approx(0.10, abs=0.01)
    assert share["dense_mlp"] == pytest.approx(0.08, abs=0.01)
    assert share["heads"] == pytest.approx(0.08, abs=0.01)
    # a model without these layers cannot be counted here
    for missing in ("layer_pattern", "linear_key_head_dim", "kv_lora_rank"):
        with pytest.raises(KeyError):
            flops_kda_latent_moe.model_flops_per_token(
                {k: v for k, v in model.items() if k != missing}, 8192
            )


def test_the_kernel_costs_by_hand():
    model = cell_model()
    tokens = 2 * 8192
    peak = build.peak_for("TPU v5 lite")
    rule = flops_kda_latent_moe.kda_cost(model, 8192, 2)
    assert rule["flops"] == 3.0 * 5_242_880 * tokens * 6
    # a head's bf16 q, k, v (3 x 128) and o (128), float32 g (128) and beta
    # forward; those and do in, dq, dk, dv, dg, dbeta out backward; no
    # chunk-start state, no second forward
    fwd = 2 * 384 + 4 * 129 + 2 * 128
    bwd = (2 * 384 + 4 * 129 + 2 * 128) + (2 * 384 + 4 * 129)
    assert (fwd, bwd) == (1540, 2824)
    assert rule["bytes"] == (fwd + bwd) * 32 * tokens * 6
    # 113 FLOPs a byte against the chip's 240: bound by its bytes
    assert flops.roofline_seconds(rule, peak)["bound"] == "memory"
    # the flash kernels of the ONE latent layer: the accepted module's
    # count a layer, and a seventh of what it counts for seven layers
    flash = flops_kda_latent_moe.latent_flash_cost(model, 8192, 2)
    assert flash["flops"] == 2.0 * 8192 * 8192 * 32 * 2 * (
        4 * 192 + 3 * 128
    ) * 0.5
    seven = flops_latent_moe.latent_flash_cost(model, 8192, 2)
    assert seven["flops"] == 7 * flash["flops"]
    assert seven["bytes"] == 7 * flash["bytes"]
    # the held grouped GEMMs: the ACCEPTED cost reads this configuration's
    # work unedited (8,192 pairs here a layer, three matrices of 2560 x
    # 768, three passes, six expert layers)
    held = flops_latent_moe.held_expert_matmul_cost(model, 8192, 2)
    assert held["flops"] == 3 * 3 * 2.0 * 8192 * 2560 * 768 * 6
    weights = 2.0 * 32 * 3 * 2560 * 768
    acts = 2.0 * 8192 * (2 * 2560 + 3 * 768)
    assert held["bytes"] == 3 * (weights + acts) * 6


# a recorded ``op_name`` list of this model's step (names as the chip's
# trace has them: the slot, the part, the scope)
STEP = "jit(_train_step)/"
BACK = STEP + "transpose(jvp())/"
ROWS = [
    ["while.3", "", 0, 6000],
    ["fusion.1", STEP + "dense_0/linear_attn/qkv/qkv/dot_general", 0, 200],
    ["fusion.2", STEP + "blocks/linear_0/linear_attn/conv/jit(_forward)/"
     "short_conv_fwd", 200, 75],
    ["fusion.3", STEP + "blocks/linear_0/linear_attn/gates/dot_general",
     275, 50],
    ["fusion.4", STEP + "blocks/linear_0/linear_attn/gates/logistic",
     325, 25],
    ["kda_fwd.1", STEP + "blocks/linear_0/linear_attn/kda/jit(_forward)/"
     "kda_fwd", 350, 300],
    ["fusion.5", STEP + "blocks/linear_0/linear_attn/kda/transpose",
     650, 50],
    ["kda_bwd.1", BACK + "blocks/linear_2/linear_attn/kda/jit(_backward)/"
     "kda_bwd", 700, 600],
    ["fusion.6", BACK + "blocks/linear_2/linear_attn/gates/mul", 1300, 25],
    ["fusion.7", STEP + "blocks/linear_0/linear_attn/out_norm/rsqrt",
     1325, 60],
    ["fusion.8", STEP + "blocks/linear_0/linear_attn/wo/dot_general",
     1385, 100],
    ["attn.2", STEP + "blocks/full_3/attn/pallas_call", 1485, 400],
    ["fusion.9", STEP + "blocks/full_3/attn/q_b/dot_general", 1885, 75],
    ["fusion.10", STEP + "blocks/full_3/attn/gate/logistic", 1960, 20],
    ["fusion.11", STEP + "blocks/full_3/attn/kv_b/dot_general", 1980, 40],
    ["fusion.12", STEP + "blocks/linear_0/moe/router/dot_general", 2020, 30],
    ["fusion.13", STEP + "blocks/linear_0/moe/router/top_k", 2050, 90],
    ["fusion.14", STEP + "blocks/linear_0/moe/shared/wg/dot_general",
     2140, 125],
    ["gmm.1", STEP + "blocks/linear_0/moe/gmm_wi/pallas_call", 2265, 300],
    ["gmm.2", BACK + "blocks/full_3/moe/gmm_wo/pallas_call", 2565, 100],
    ["fusion.15", STEP + "blocks/linear_0/moe/combine/gather", 2665, 150],
    ["fusion.16", STEP + "lm_head/dot_general", 2815, 250],
]
TRACE = {"devices": {"/device:TPU:0": {
    "ops": ROWS, "modules": [["jit__train_step(1)", "", 0, 6000]],
}}, "host": []}
OTHER = {"devices": {"/device:TPU:0": {
    "ops": [ROWS[0], ROWS[-1]],
    "modules": TRACE["devices"]["/device:TPU:0"]["modules"],
}}, "host": []}


def evidence(trace=TRACE, **more):
    return dict({
        "trace": trace, "step_module": "train_step", "model": cell_model(),
        "seq_len": 8192, "sequences_per_chip": 2,
        "peak": build.peak_for("TPU v5 lite"),
    }, **more)


def test_the_scope_patterns_on_a_recorded_list_of_op_names():
    def ms(name, trace=TRACE):
        spec = layers.spec(name)
        assert spec["reader"] == "scope_ms"
        return scope_ms.read(evidence(trace), spec["params"])

    # the decay projection, beta and the safe gate, forward and transposed
    assert ms("kda_gate_ms") == pytest.approx(100e-6)
    # the router's projection and its group-limited choice
    assert ms("moe_router_ms") == pytest.approx(120e-6)
    # the accepted metrics this cell joins read the same scopes here:
    # everything under linear_attn/, the convolution, the latent layer's
    # ops and its projections (q_b, kv_b; not the kernel, not the gate)
    assert ms("linear_attn_ms") == pytest.approx(1485e-6)
    assert ms("short_conv_ms") == pytest.approx(75e-6)
    assert ms("latent_attn_ms") == pytest.approx(535e-6)
    assert ms("latent_proj_ms") == pytest.approx(115e-6)
    assert ms("shared_expert_ms") == pytest.approx(125e-6)
    assert ms("moe_row_gather_ms") == ms("moe_row_move_ms") == (
        pytest.approx(150e-6)
    )
    # a program with no such scope (the parent) gives nothing
    for name in ("kda_gate_ms", "moe_router_ms", "linear_attn_ms"):
        assert ms(name, OTHER) is None


def test_the_rooflines_read_their_own_ops_against_their_own_cost():
    peak = build.peak_for("TPU v5 lite")
    model = cell_model()
    cases = (
        # every op under linear_attn/../kda, kernel or not: the two kernels
        # and the transpose to heads-first (300 + 50 + 600)
        ("kda_roofline", flops_kda_latent_moe, "kda_cost", 950e-9),
        ("kda_latent_flash_roofline", flops_kda_latent_moe,
         "latent_flash_cost", 400e-9),
        # the accepted metric this cell joins, against the accepted module
        ("held_grouped_matmul_roofline", flops_latent_moe,
         "held_expert_matmul_cost", 400e-9),
    )
    for name, module, cost, seconds in cases:
        spec = layers.spec(name)
        assert spec["reader"] == "kernel_roofline_from", name
        assert spec["params"]["module"] == module.__name__.split(".")[-1]
        assert spec["params"]["cost"] == cost
        floor = flops.roofline_seconds(
            getattr(module, cost)(model, 8192, 2), peak
        )["seconds"]
        assert kernel_roofline_from.read(
            evidence(), spec["params"]
        ) == pytest.approx(100 * floor / seconds), name
        # nothing to read: no such op, no peak, no trace, another model
        params = spec["params"]
        assert kernel_roofline_from.read(evidence(OTHER), params) is None
        assert kernel_roofline_from.read(evidence(peak=None), params) is None
        assert kernel_roofline_from.read({}, params) is None
    older = {k: v for k, v in model.items() if not k.startswith("linear_")}
    for name in ("kda_roofline",):
        assert kernel_roofline_from.read(
            evidence(model=older), layers.spec(name)["params"]
        ) is None


def test_the_step_mfu_counts_by_part_and_leaves_other_models_alone():
    spec = layers.spec("kda_latent_moe_step_mfu")
    assert spec["reader"] == "mfu_from" and spec["params"] == {
        "module": MODULE
    }
    model = cell_model()
    summary = {"tokens_per_s_chip": 15000.0}
    got = mfu_from.read(evidence(summary=summary), spec["params"])
    per_token = flops_kda_latent_moe.model_flops_per_token(model, 8192)
    assert got == pytest.approx(per_token * 15000.0 / 197e12)
    assert 0.1 < got < 0.7
    for other in ("gpt2-1.5b", "olmo-hybrid-7b", "joyai-llm-flash",
                  "granite-4.0-h-small"):
        group = build.model_group(build.load_json(
            os.path.join(REPO, "benchmark", "configs", f"{other}.json")
        ))
        assert mfu_from.read(
            evidence(summary=summary, model=group), spec["params"]
        ) is None, other
    assert mfu_from.read(evidence(), spec["params"]) is None   # no summary
    assert mfu_from.read(
        evidence(summary=summary, peak=None), spec["params"]
    ) is None


OWN = ("kda_roofline", "kda_gate_ms", "moe_router_ms",
       "kda_latent_flash_roofline", "kda_latent_moe_step_mfu",
       "moe_row_gather_ms")
JOINED = ("host_step_gap_ms", "step_s_worst_over_median",
          "tokens_per_s_chip_median_step", "data_wait_ms",
          "data_wait_span_ms", "step_device_ms", "device_idle_share",
          "peak_hbm_gib", "startup_to_mesh_s", "forward_ms", "recompute_ms",
          "backward_ms", "optimizer_ms", "head_loss_ms", "step_unnamed_ms",
          "linear_attn_ms", "short_conv_ms", "delta_state_absmax",
          "latent_attn_ms", "latent_proj_ms", "shared_expert_ms",
          "moe_pad_share", "moe_max_expert_load",
          "moe_pairs_here", "router_bias_absmax",
          "held_grouped_matmul_roofline")
# (``moe_row_move_ms`` reads the same scopes as this cell's own
# ``moe_row_gather_ms``; ``tests/test_moe_row_moves.py`` holds its list of
# cells closed, PERF.md §7 (7), so the cell reports under a name of its own)
NOT_JOINED = ("moe_row_move_ms", "moe_dispatch_ms", "step_mfu", "flash_roofline",
              "flash_attn_roofline", "grouped_matmul_roofline",
              "expert_matmul_roofline", "latent_flash_roofline",
              "latent_moe_step_mfu", "pattern_flash_roofline",
              "pattern_step_mfu", "delta_rule_roofline", "mtp_ms",
              "ssm_ms", "ssd_roofline")


@pytest.mark.parametrize("name", OWN + JOINED)
def test_the_cell_is_in_the_list(name):
    entry = {m["name"]: m for m in build.manifest()["per_layer"]}[name]
    assert CELL in entry["workloads"]
    assert entry["moves"] == (
        "setup_s" if name == "startup_to_mesh_s" else "tokens_per_s_chip"
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
    assert "256 rows" in cell["why"] and "4,096" in cell["why"]
    reported = {m["name"] for m in layers.cell_entries(
        build.manifest(), CELL, "per_layer"
    )}
    assert set(OWN + JOINED) <= reported
    # compile_s lists no cells: every cell reports it
    assert "compile_s" in reported
