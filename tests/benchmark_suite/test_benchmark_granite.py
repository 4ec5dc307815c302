"""What the Granite-4.0-H-Small cell brings to the benchmark: its
configuration against the catalog, its own plain reference against the
repository's, the arithmetic of its cost module by hand, and its readers on
a recorded list of op names."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import (  # noqa: E402
    build, flops, flops_ssm_gated_moe, flops_ssm_moe, layers,
)
from benchmark.readers import (  # noqa: E402
    kernel_roofline_from,
    mfu_from,
    scope_ms,
)

PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# ``config`` of the catalog's entry granite-4.0-h-small (the model-configs
# guide's architectures.jsonl), as published.
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "layer_types": PERIOD * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 1536,
    "tie_word_embeddings": True, "vocab_size": 100352,
}
NAME = "granite-4.0-h-small"
CONFIG = os.path.join(REPO, "benchmark", "configs", f"{NAME}.json")
PRESET = os.path.join(HERE, "presets", f"{NAME}.json")
CELL = f"{NAME}.train_steady"
MODULE = "flops_ssm_gated_moe"


def cell_model():
    return build.model_group(build.load_json(CONFIG))


def test_the_catalog_here_is_the_guides():
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(guide):
        pytest.skip("the model-configs guide is not on this machine")
    with open(guide) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r["name"] == NAME]
    assert row["config"] == CATALOG
    assert row["source_url"] == build.load_json(CONFIG)["source"]


def test_the_configuration_differs_from_the_catalog_in_what_it_says():
    config = build.load_json(CONFIG)
    assert set(CATALOG) <= set(config)
    differs = {k for k, v in CATALOG.items() if config[k] != v}
    assert differs == {
        "num_hidden_layers", "layer_types", "num_local_experts", "vocab_size",
    }
    assert differs == set(config["reduced"])
    entry = {c["name"]: c for c in build.manifest()["configs"]}[NAME]
    assert set(entry["reduced"]) == differs
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == config["source"]
    for key in differs:
        assert config["reduced"][key]["published"] == CATALOG[key]
        assert config["reduced"][key]["run"] == config[key]
        assert len(config["reduced"][key]["why"]) > 100
    # the floors: a whole period of more than four layers, eight experts,
    # an eighth of the vocabulary (whole lanes as published)
    assert config["num_hidden_layers"] == len(PERIOD) == 10 > 4
    assert config["layer_types"] == PERIOD == CATALOG["layer_types"][:10]
    assert config["num_local_experts"] == 9 >= 8
    assert config["router_experts"] == CATALOG["num_local_experts"] == 72
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert config["vocab_size"] % 128 == 0
    assert "eight chips share each layer" in config["deployment"]
    assert "Nothing stands in for the absent chips" in config["deployment"]
    model = cell_model()
    assert (model["num_experts"], model["experts_held"]) == (72, 9)
    assert model["first_expert"] == 0 and model["top_k"] == 10
    assert model["moe_d_ff"] == 768 and model["shared_expert_d_ff"] == 1536
    assert (model["ssm_num_heads"], model["ssm_head_dim"],
            model["ssm_state_size"], model["ssm_groups"],
            model["ssm_conv_kernel"]) == (128, 64, 128, 1, 4)
    assert model["ssm_chunk"] in (128, 256)
    assert (model["num_heads"], model["num_kv_heads"],
            model["head_dim"]) == (32, 8, 128)
    # the four multipliers, under the program's names
    assert (model["embed_scale"], model["attention_scale"],
            model["residual_scale"], model["logit_scale"]) == (
        12, 0.0078125, 0.22, 1 / 16
    )
    assert model["logit_scale"] * CATALOG["logits_scaling"] == 1
    assert model["position"] == "none" and model["activation"] == "swiglu"
    assert model["router_scoring"] == "softmax" and model["norm_topk_prob"]
    assert model["tie_embeddings"] is True
    assert model["ssm_impl"] == "kernel" and model["remat"] == "flash_only"
    # Mamba's reference rescale of out_proj: 2 branches x 40 layers
    assert model["ssm_out_init_scale"] == pytest.approx(80 ** -0.5)
    # a published layer is two of the program's
    kinds = {"mamba": "ssm", "attention": "attention"}
    assert model["layer_pattern"] == [
        kind for t in PERIOD for kind in (kinds[t], "experts")
    ]
    assert model["num_layers"] == 2 * config["num_hidden_layers"] == 20
    assert {"optimizer", "precision", "head_dim", "time_step", "balance_term",
            "initialisers", "chunk", "sequence", "moe_row_budget",
            "remat"} <= set(config["assumed"])
    assert "128" in config["assumed"]["chunk"]
    assert "256" in config["assumed"]["chunk"]
    # the chip's verdict at one and at two sequences is on record
    bytes_ = config["compiled_bytes"]
    assert {"sequences_1", "sequences_2"} <= set(bytes_)
    tol = config["reference_tolerance"]
    assert 0 < tol["mean_abs_token_nll"] < 0.05
    assert tol["first_step_loss"] == 0.1


def test_the_program_takes_the_configuration():
    from dlrover_tpu.models import granite_moe_hybrid as granite
    from dlrover_tpu.models.granite_moe_hybrid import (
        granite_moe_hybrid_config,
    )

    config = build.load_json(CONFIG)
    cfg = build.transformer_config(cell_model(), build.seq_len(config, {}))
    want = granite_moe_hybrid_config(
        num_layers=20, vocab_size=12544, experts_held=9,
    )
    for field in ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
                  "moe_d_ff", "shared_expert_d_ff", "num_shared_experts",
                  "num_experts", "experts_held", "first_expert", "top_k",
                  "router_scoring", "router_bias", "norm_topk_prob",
                  "norm_eps", "tie_embeddings", "moe_dispatch", "max_seq_len",
                  "layer_pattern", "position", "activation", "norm",
                  "moe_aux_form", "moe_aux_weight", "embed_scale",
                  "attention_scale", "residual_scale", "logit_scale",
                  "ssm_num_heads", "ssm_head_dim", "ssm_state_size",
                  "ssm_groups", "ssm_conv_kernel", "ssm_dt_min",
                  "ssm_dt_max", "ssm_dt_floor"):
        assert getattr(cfg, field) == getattr(want, field), field
    assert cfg.layer_pattern == granite.kinds(config["layer_types"])
    assert cfg.num_scan_units == 1
    # ISSUE 39's arithmetic (a layer's own norms left out, as ever)
    assert cfg.num_params() + 21 * 4096 == 2_055_031_424
    # the rows set aside for an expert layer's share: 1.25 x 20,480 expected
    # + a block of 128 an expert + the zero block
    from dlrover_tpu.models.moe import _share_row_budget

    assert _share_row_budget(2 * 8192 * 10, 128, 9, 72, 1.25) == 26_880
    # both kernels this model's shapes had no room in now hold them
    from dlrover_tpu.ops import row_gather_sum, ssd

    assert ssd.heads_per_step(128, 64, 1, 128, cfg.ssm_chunk) == 8
    assert row_gather_sum.kernel_fits(4096, 10, "bfloat16")


def test_the_preset_is_the_cell_in_small():
    from dlrover_tpu.models import granite_moe_hybrid as granite

    config, preset = build.load_json(CONFIG), build.load_json(PRESET)
    assert set(preset) == set(config)
    for group in ("to_program", "trainer"):
        assert preset[group] == config[group]
    # the preset's period is four published layers, not ten: a period is
    # not scanned, and the rehearsals compile the cell three times
    varies = (
        "param_dtype", "moe_row_budget", "head_dim", "ssm_chunk",
        "num_layers", "layer_pattern",
    )
    assert {
        k: v for k, v in preset["program"].items() if k not in varies
    } == {
        k: v for k, v in config["program"].items() if k not in varies
    }
    assert preset["reference_module"] == config["reference_module"]
    assert preset["layer_types"] == ["mamba", "mamba", "attention", "mamba"]
    assert tuple(preset["program"]["layer_pattern"]) == granite.kinds(
        preset["layer_types"]
    )
    assert preset["program"]["num_layers"] == 2 * preset["num_hidden_layers"]
    cfg = build.transformer_config(
        build.model_group(preset), build.seq_len(preset, {})
    )
    # ONE group of sixteen heads: two tiles of eight where the kernel runs
    assert (cfg.ssm_num_heads, cfg.ssm_groups) == (16, 1)

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
        np.random.default_rng(3).integers(0, config["vocab_size"],
                                          (2, seq + 1)),
        jnp.int32,
    )
    lm = TransformerLM(build.transformer_config(model, seq))
    params = nn.meta.unbox(lm.init(jax.random.PRNGKey(3), rows[:, :-1]))
    return model, params["params"], rows[:, :-1], rows[:, 1:]


def test_the_benchmarks_reference_agrees_with_the_repositorys(preset_case):
    import numpy as np

    from benchmark.references import granite_moe_hybrid as ours
    from dlrover_tpu.models.references import granite_moe_hybrid as theirs

    with open(ours.__file__) as a, open(theirs.__file__) as b:
        assert a.read() == b.read()        # one text in both places
    model, params, inputs, targets = preset_case
    got = ours.forward(model, params, inputs, targets)
    want = theirs.forward(model, params, inputs, targets)
    np.testing.assert_allclose(got["nll"], want["nll"], atol=2e-5)
    np.testing.assert_allclose(
        ours.token_nll(model, params, inputs, targets), want["nll"], atol=2e-5
    )
    # a layer's balance term is about top_k where the router is even
    layers_here = len(model["layer_pattern"]) // 2
    assert layers_here * 3.5 < float(got["balance"]) < layers_here * 6.0


def test_a_lowered_reference_is_another_result(preset_case):
    """What ``reference_tolerance`` is set against (PERF.md §6): the
    reference with its router, its recurrence, or all of it, in bfloat16."""
    import numpy as np

    from benchmark.references import granite_moe_hybrid as ours

    model, params, inputs, targets = preset_case
    exact = np.asarray(ours.token_nll(model, params, inputs, targets))
    gaps = {
        mode: float(np.abs(np.asarray(ours.token_nll(
            model, params, inputs, targets, lowered=mode
        )) - exact).mean())
        for mode in ("router", "ssm", "all")
    }
    assert 0 < gaps["router"] < gaps["all"]
    assert 0 < gaps["ssm"] < gaps["all"]


def test_the_flops_of_a_token_by_hand():
    model = dict(cell_model(), ssm_chunk=256)
    # in_proj 4096 x 16,768, out_proj 8192 x 4096
    proj = 68_681_728 + 33_554_432
    assert flops_ssm_moe.ssm_projection_params(model) == proj
    # C B^T once for the ONE group (2 x 256 x 128) and, a head of 64, M X
    # (2 x 256 x 64), C S^T and B^T X (2 x 128 x 64 each)
    scan = 65_536 + 128 * (32_768 + 2 * 16_384)
    assert flops_ssm_moe.scan_flops_per_token(model) == scan == 8_454_144
    # of 10 pairs a token an eighth is routed here
    assert flops_ssm_moe.pairs_here_per_token(model) == 1.25
    parts = flops_ssm_gated_moe.flops_per_token_by_part(model, 8192)
    assert parts == {
        "ssm_projections": 6.0 * 9 * proj,
        "ssm_scan": 3.0 * 9 * scan,
        "attention_projections": 6.0 * 1 * 4096 * 128 * (64 + 16),
        "attention": 6.0 * 1 * 32 * 8192 * 2 * 128 * 0.5,
        # THREE matrices an expert and the shared expert
        "shared_experts": 6.0 * 10 * 3 * 4096 * 1536,
        "routed_here": 6.0 * 10 * 1.25 * 3 * 4096 * 768,
        "router": 6.0 * 10 * 4096 * 72,
        "dense_mlp": 0.0,
        "head": 6.0 * 12544 * 4096,
    }
    total = flops_ssm_gated_moe.model_flops_per_token(model, 8192)
    assert total == sum(parts.values())
    # ISSUE 39's shares: 8.35 GFLOP a token, the state-space projections
    # 66%, the shared expert 14%, the held pairs 8%, attention 6%, the head
    # 4%, the scan 2 to 3%
    assert 8.3e9 < total < 8.4e9
    share = {k: v / total for k, v in parts.items()}
    assert share["ssm_projections"] == pytest.approx(0.66, abs=0.01)
    assert share["shared_experts"] == pytest.approx(0.14, abs=0.01)
    assert share["routed_here"] == pytest.approx(0.08, abs=0.01)
    assert share["attention"] + share["attention_projections"] == (
        pytest.approx(0.06, abs=0.01)
    )
    assert share["head"] == pytest.approx(0.04, abs=0.005)
    assert 0.02 < share["ssm_scan"] < 0.03
    # what the ungated module would count for the same fields: two
    # matrices where there are three
    ungated = flops_ssm_moe.flops_per_token_by_part(model, 8192)
    assert parts["routed_here"] == 1.5 * ungated["routed_here"]
    assert parts["shared_experts"] == 1.5 * ungated["shared_experts"]
    assert parts["ssm_scan"] == ungated["ssm_scan"]
    # a model without these layers cannot be counted here
    for missing in ("ssm_num_heads", "layer_pattern", "shared_expert_d_ff"):
        with pytest.raises(KeyError):
            flops_ssm_gated_moe.model_flops_per_token(
                {k: v for k, v in model.items() if k != missing}, 8192
            )


def test_both_kernel_costs_by_hand():
    model = dict(cell_model(), ssm_chunk=256)
    tokens = 2 * 8192
    peak = build.peak_for("TPU v5 lite")
    # the scan's cost is the accepted module's, reading ONE group
    ssd = flops_ssm_moe.ssd_cost(model, 8192, 2)
    assert ssd["flops"] == 3.0 * 8_454_144 * tokens * 9
    # bf16 x, y (8192) and B, C (128 each), float32 dt (128) forward;
    # x, dy, dx, B, C, dB, dC and dt, ddt backward
    fwd = 2 * (2 * 8192 + 2 * 128) + 4 * 128
    bwd = 2 * (3 * 8192 + 4 * 128) + 4 * 2 * 128
    assert (fwd, bwd) == (33_792, 51_200)
    assert ssd["bytes"] == (fwd + bwd) * tokens * 9
    # at the published chunk of 256 the chunked form's products outweigh
    # its bytes (298 FLOPs a byte against the chip's 240); at 128 they
    # do not
    assert flops.roofline_seconds(ssd, peak)["bound"] == "compute"
    assert flops.roofline_seconds(
        flops_ssm_moe.ssd_cost(dict(model, ssm_chunk=128), 8192, 2), peak
    )["bound"] == "memory"
    # the flash kernels': k and v 8 heads wide, one layer
    flash = flops_ssm_moe.gqa_flash_cost(model, 8192, 2)
    assert flash["flops"] == 2.0 * 8192 * 8192 * 32 * 2 * 7 * 128 * 0.5
    row = 2.0 * tokens * 128
    assert flash["bytes"] == (
        row * (2 * 32 + 2 * 8) + row * (4 * 32 + 4 * 8)
        + 2 * 4.0 * tokens * 32
    )
    held = flops_ssm_gated_moe.gated_held_expert_matmul_cost(model, 8192, 2)
    # 20,480 pairs here a layer (163,840 chosen, an eighth), THREE matrices
    # of 4096 x 768, three passes, ten layers
    assert held["flops"] == 3 * 3 * 2.0 * 20480 * 4096 * 768 * 10
    weights = 2.0 * 9 * 3 * 4096 * 768
    acts = 2.0 * 20480 * (2 * 4096 + 3 * 768)
    assert held["bytes"] == 3 * (weights + acts) * 10
    assert flops.roofline_seconds(held, peak)["bound"] == "compute"
    # counted as the ungated module counts (two matrices) it reads a third
    # lower, and the shares' cost module of JoyAI agrees with this one
    ungated = flops_ssm_moe.relu2_expert_matmul_cost(model, 8192, 2)
    assert held["flops"] == 1.5 * ungated["flops"]


# a recorded ``op_name`` list of this model's step (names as the chip's
# trace has them: the slot, the part, the scope)
STEP = "jit(_train_step)/"
ROWS = [
    ["while.3", "", 0, 4000],
    ["fusion.1", STEP + "blocks/ssm_0/ssm/in_proj/dot_general", 0, 200],
    ["fusion.2", STEP + "blocks/ssm_0/ssm/conv/jit(_forward)/short_conv_fwd",
     200, 75],
    ["fusion.3", STEP + "blocks/ssm_0/ssm/dt/softplus", 275, 25],
    ["ssd_fwd.1", STEP + "blocks/ssm_0/ssm/scan/jit(_forward)/ssd_fwd",
     300, 100],
    ["ssd_bwd.1", STEP + "transpose(jvp())/blocks/ssm_2/ssm/scan/"
     "jit(_backward)/ssd_bwd", 400, 200],
    ["fusion.4", STEP + "transpose(jvp())/blocks/ssm_2/ssm/scan/reduce_sum",
     600, 50],
    ["fusion.5", STEP + "blocks/ssm_0/ssm/out_norm/rsqrt", 650, 60],
    ["fusion.6", STEP + "transpose(jvp())/blocks/ssm_0/ssm/out_norm/mul",
     710, 40],
    ["fusion.7", STEP + "transpose(jvp())/blocks/ssm_0/ssm/out_proj/"
     "dot_general", 750, 100],
    ["attn.2", STEP + "blocks/attention_10/attn/pallas_call", 850, 400],
    ["fusion.8", STEP + "blocks/attention_10/attn/query/dot_general",
     1250, 75],
    ["fusion.9", STEP + "blocks/experts_1/moe/shared/wg/dot_general",
     1325, 125],
    ["gmm.1", STEP + "blocks/experts_1/moe/gmm_wi/pallas_call", 1450, 300],
    ["gmm.2", STEP + "blocks/experts_1/moe/gmm_wg/pallas_call", 1750, 300],
    ["gmm.3", STEP + "transpose(jvp())/blocks/experts_3/moe/gmm_wo/"
     "pallas_call", 2050, 100],
    ["row_gather_sum.1", STEP + "blocks/experts_1/moe/combine/"
     "jit(gather_sum)/row_gather_sum/pallas_call", 2150, 150],
    ["fusion.10", STEP + "blocks/experts_1/residual/mul", 2300, 20],
    ["fusion.11", STEP + "embed/attend/dot_general", 2320, 250],
]
TRACE = {"devices": {"/device:TPU:0": {
    "ops": ROWS, "modules": [["jit__train_step(1)", "", 0, 4000]],
}}, "host": []}
OTHER = {"devices": {"/device:TPU:0": {
    "ops": [ROWS[0], ROWS[18]],
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

    # the gated norm's passes, forward and transposed, and nothing else
    assert ms("ssm_out_norm_ms") == pytest.approx(100e-6)
    # everything under ssm/: 200 + 75 + 25 + 100 + 200 + 50 + 60 + 40 + 100
    assert ms("ssm_ms") == pytest.approx(850e-6)
    assert ms("ssm_scan_ms") == pytest.approx(350e-6)
    assert ms("ssm_conv_ms") == pytest.approx(75e-6)
    assert ms("ssm_proj_ms") == pytest.approx(300e-6)
    assert ms("shared_expert_ms") == pytest.approx(125e-6)
    assert ms("moe_row_move_ms") == pytest.approx(150e-6)
    # a program with no such scope (the parent) gives nothing
    for name in ("ssm_out_norm_ms", "ssm_ms", "ssm_scan_ms"):
        assert ms(name, OTHER) is None


def test_the_rooflines_read_their_own_ops_against_their_own_cost():
    peak = build.peak_for("TPU v5 lite")
    model = cell_model()
    cases = (
        # the three grouped GEMMs (300 + 300 + 100), not the fetch-and-sum
        ("gated_held_grouped_matmul_roofline", flops_ssm_gated_moe,
         "gated_held_expert_matmul_cost", 700e-9),
        # the accepted metrics this cell joins, against the accepted module
        ("ssd_roofline", flops_ssm_moe, "ssd_cost", 350e-9),
        ("ssm_moe_flash_roofline", flops_ssm_moe, "gqa_flash_cost", 400e-9),
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
        older = {k: v for k, v in model.items() if not k.startswith("ssm_")}
        assert kernel_roofline_from.read(
            evidence(model=older), params
        ) is None


def test_the_step_mfu_counts_by_part_and_leaves_other_models_alone():
    spec = layers.spec("ssm_gated_moe_step_mfu")
    assert spec["reader"] == "mfu_from" and spec["params"] == {
        "module": MODULE
    }
    model = cell_model()
    summary = {"tokens_per_s_chip": 9000.0}
    got = mfu_from.read(evidence(summary=summary), spec["params"])
    per_token = flops_ssm_gated_moe.model_flops_per_token(model, 8192)
    assert got == pytest.approx(per_token * 9000.0 / 197e12)
    assert 0.2 < got < 0.7
    for other in ("gpt2-1.5b", "olmo-hybrid-7b", "joyai-llm-flash"):
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


OWN = ("ssm_gated_moe_step_mfu", "gated_held_grouped_matmul_roofline",
       "ssm_out_norm_ms")
JOINED = ("host_step_gap_ms", "step_s_worst_over_median",
          "tokens_per_s_chip_median_step", "data_wait_ms",
          "data_wait_span_ms", "step_device_ms", "device_idle_share",
          "peak_hbm_gib", "startup_to_mesh_s", "forward_ms", "recompute_ms",
          "backward_ms", "optimizer_ms", "head_loss_ms", "step_unnamed_ms",
          "moe_pad_share", "moe_max_expert_load", "moe_row_move_ms",
          "moe_pairs_here", "shared_expert_ms", "ssm_ms", "ssm_scan_ms",
          "ssm_conv_ms", "ssm_proj_ms", "ssm_state_absmax", "ssd_roofline",
          "ssm_moe_flash_roofline")
NOT_JOINED = ("moe_dispatch_ms", "step_mfu", "flash_roofline",
              "flash_attn_roofline", "grouped_matmul_roofline",
              "expert_matmul_roofline", "held_grouped_matmul_roofline",
              "relu2_grouped_matmul_roofline", "ssm_moe_step_mfu",
              "latent_flash_roofline", "pattern_flash_roofline",
              "delta_rule_roofline", "linear_attn_ms", "mtp_ms",
              "router_bias_absmax")


@pytest.mark.parametrize("name", OWN + JOINED)
def test_the_cell_is_in_the_list(name):
    """Membership only: never a list's last place or its whole content, so
    that the next cell to join a list breaks nothing here."""
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
    assert "2,276 rows" in cell["why"] and "18,204" in cell["why"]
    assert "more than its share" in cell["why"]
    reported = {m["name"] for m in layers.cell_entries(
        build.manifest(), CELL, "per_layer"
    )}
    assert set(OWN + JOINED) <= reported
