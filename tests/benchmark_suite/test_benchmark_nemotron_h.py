"""What the Nemotron-3-Nano cell brings to the benchmark: its configuration
against the catalog, its own plain reference against the repository's, the
arithmetic of its cost module by hand, and its readers on recorded data."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import build, flops, flops_ssm_moe, layers  # noqa: E402
from benchmark.readers import (  # noqa: E402
    kernel_roofline_from,
    mfu_from,
    program_events,
    scope_ms,
)

# ``config`` of the catalog's entry NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (the
# model-configs guide's architectures.jsonl), as published.
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
}
NAME = "nemotron-3-nano-30b-a3b"
CONFIG = os.path.join(REPO, "benchmark", "configs", f"{NAME}.json")
PRESET = os.path.join(HERE, "presets", f"{NAME}.json")
CELL = f"{NAME}.train_steady"
MODULE = "flops_ssm_moe"
PERIOD = "EMEMEMEM*"


def cell_model():
    return build.model_group(build.load_json(CONFIG))


def test_the_catalog_here_is_the_guides():
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(guide):
        pytest.skip("the model-configs guide is not on this machine")
    with open(guide) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [
        r for r in rows if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
    ]
    assert row["config"] == CATALOG
    assert row["source_url"] == build.load_json(CONFIG)["source"]


def test_the_configuration_differs_from_the_catalog_in_what_it_says():
    config = build.load_json(CONFIG)
    assert set(CATALOG) <= set(config)
    differs = {k for k, v in CATALOG.items() if config[k] != v}
    assert differs == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size",
    }
    assert differs == set(config["reduced"])
    entry = {c["name"]: c for c in build.manifest()["configs"]}[NAME]
    assert set(entry["reduced"]) == differs
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    for key in differs:
        assert config["reduced"][key]["published"] == CATALOG[key]
        assert config["reduced"][key]["run"] == config[key]
        assert len(config["reduced"][key]["why"]) > 100
    # the floors: whole periods and more than four layers, eight experts,
    # an eighth of the vocabulary (whole lanes as published)
    units = config["num_hidden_layers"] // len(PERIOD)
    assert units >= 1 and config["num_hidden_layers"] == 9 * units > 4
    assert config["hybrid_override_pattern"] == PERIOD * units
    assert config["pattern_period"] == PERIOD
    # the run taken is a run of the published order: its layers 34-42
    assert CATALOG["hybrid_override_pattern"][34:43] == PERIOD
    assert config["n_routed_experts"] == 16 >= 8
    assert config["router_experts"] == CATALOG["n_routed_experts"] == 128
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert config["vocab_size"] % 128 == 0
    assert "eight chips share each layer" in config["deployment"]
    assert "Nothing stands in for the absent chips" in config["deployment"]
    model = cell_model()
    assert (model["num_experts"], model["experts_held"]) == (128, 16)
    assert model["first_expert"] == 0 and model["top_k"] == 6
    assert model["moe_d_ff"] == 1856 and model["shared_expert_d_ff"] == 3712
    assert (model["ssm_num_heads"], model["ssm_head_dim"],
            model["ssm_state_size"], model["ssm_groups"],
            model["ssm_conv_kernel"], model["ssm_chunk"]) == (
        64, 64, 128, 8, 4, 128
    )
    assert (model["num_heads"], model["num_kv_heads"],
            model["head_dim"]) == (32, 2, 128)
    assert model["position"] == "none" and model["activation"] == "relu2"
    assert model["router_scoring"] == "sigmoid" and model["router_bias"]
    assert model["routed_scaling_factor"] == 2.5
    assert model["ssm_impl"] == "kernel" and model["remat"] == "flash_only"
    letters = {"E": "experts", "M": "ssm", "*": "attention"}
    assert model["layer_pattern"] == [letters[c] for c in PERIOD]
    assert {"optimizer", "precision", "position", "router_bias_rate",
            "balance_term", "initialisers", "embedding_scale", "expand",
            "sequence", "moe_row_budget", "remat"} <= set(config["assumed"])
    # U is the chip's largest: its verdict and U + 1's are on record
    bytes_ = config["compiled_bytes"]
    assert f"U_{units}" in bytes_ and f"U_{units + 1}" in bytes_
    assert "RESOURCE_EXHAUSTED" in bytes_[f"U_{units + 1}"]["verdict"]
    tol = config["reference_tolerance"]
    assert 0 < tol["mean_abs_token_nll"] < 0.02 and tol["first_step_loss"] == 0.1


def test_the_program_takes_the_configuration():
    from dlrover_tpu.models import nemotron_h
    from dlrover_tpu.models.nemotron_h import nemotron_h_config

    config = build.load_json(CONFIG)
    cfg = build.transformer_config(cell_model(), build.seq_len(config, {}))
    layers_run = config["num_hidden_layers"]
    want = nemotron_h_config(
        num_layers=layers_run, vocab_size=16384, experts_held=16,
    )
    for field in ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
                  "moe_d_ff", "shared_expert_d_ff", "num_shared_experts",
                  "num_experts", "experts_held", "first_expert", "top_k",
                  "router_scoring", "router_bias", "router_bias_rate",
                  "routed_scaling_factor", "norm_topk_prob", "norm_eps",
                  "tie_embeddings", "moe_dispatch", "max_seq_len",
                  "layer_pattern", "position", "activation", "norm",
                  "ssm_num_heads", "ssm_head_dim", "ssm_state_size",
                  "ssm_groups", "ssm_conv_kernel", "ssm_chunk", "ssm_dt_min",
                  "ssm_dt_max", "ssm_dt_floor"):
        assert getattr(cfg, field) == getattr(want, field), field
    assert cfg.layer_pattern == nemotron_h.kinds(PERIOD)
    assert cfg.num_scan_units == layers_run // 9
    # ISSUE 37's arithmetic (a layer's own norm left out, as ever): a
    # Mamba-2 layer 38,742,208 + its norm, attention 23,396,352, an expert
    # layer's share 179,945,600, embedding and head 88,080,384
    units = layers_run // 9
    unit = 4 * 38_742_208 + 4 * 179_945_600 + 23_396_352
    assert unit + 9 * 2688 == 898_171_776
    assert cfg.num_params() == units * unit + 2 * 16384 * 2688
    # the rows set aside for an expert layer's share: 1.25 x 12,288 expected
    # + a block of 128 an expert + the zero block
    from dlrover_tpu.models.moe import _share_row_budget

    assert _share_row_budget(2 * 8192 * 6, 128, 16, 128, 1.25) == 17_536


def test_the_preset_is_the_cell_in_small():
    config, preset = build.load_json(CONFIG), build.load_json(PRESET)
    assert set(preset) == set(config)
    for group in ("to_program", "trainer"):
        assert preset[group] == config[group]
    assert {
        k: v for k, v in preset["program"].items()
        if k not in ("param_dtype", "moe_row_budget")
    } == {
        k: v for k, v in config["program"].items()
        if k not in ("param_dtype", "moe_row_budget")
    }
    assert preset["reference_module"] == config["reference_module"]
    assert build.transformer_config(
        build.model_group(preset), build.seq_len(preset, {})
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
        np.random.default_rng(3).integers(0, config["vocab_size"],
                                          (2, seq + 1)),
        jnp.int32,
    )
    lm = TransformerLM(build.transformer_config(model, seq))
    params = nn.meta.unbox(lm.init(jax.random.PRNGKey(3), rows[:, :-1]))
    return model, params["params"], rows[:, :-1], rows[:, 1:]


def test_the_benchmarks_reference_agrees_with_the_repositorys(preset_case):
    import numpy as np

    from benchmark.references import nemotron_h as ours
    from dlrover_tpu.models.references import nemotron_h as theirs

    with open(ours.__file__) as a, open(theirs.__file__) as b:
        assert a.read() == b.read()        # one text in both places
    model, params, inputs, targets = preset_case
    got = ours.forward(model, params, inputs, targets)
    want = theirs.forward(model, params, inputs, targets)
    np.testing.assert_allclose(got["nll"], want["nll"], atol=2e-5)
    np.testing.assert_allclose(
        ours.token_nll(model, params, inputs, targets), want["nll"], atol=2e-5
    )
    # four expert layers' counts over all 16 experts: 2 x 64 tokens x 4
    assert [int(c.sum()) for c in got["counts"]] == [512] * 4
    assert got["counts"][0].shape == (16,)


def test_a_lowered_reference_is_another_result(preset_case):
    """What ``reference_tolerance`` is set against (PERF.md §6): the
    reference with its router, its recurrence, or all of it, in bfloat16."""
    import numpy as np

    from benchmark.references import nemotron_h as ours

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
    model = cell_model()
    units = int(model["num_layers"]) // 9
    ssm, experts, attn_layers = 4 * units, 4 * units, units
    # in_proj 2688 x 10,304, out_proj 4096 x 2688
    proj = 27_697_152 + 11_010_048
    assert flops_ssm_moe.ssm_projection_params(model) == proj
    # C B^T once a group (2 x 128 x 128 x 8) and, a head of 64, M X
    # (2 x 128 x 64), C S^T and B^T X (2 x 128 x 64 each)
    scan = 262_144 + 64 * (16_384 + 2 * 16_384)
    assert scan == 3_407_872
    assert flops_ssm_moe.scan_flops_per_token(model) == scan
    # of 6 pairs a token an eighth is routed here
    assert flops_ssm_moe.pairs_here_per_token(model) == 0.75
    parts = flops_ssm_moe.flops_per_token_by_part(model, 8192)
    assert parts == {
        "ssm_projections": 6.0 * ssm * proj,
        "ssm_scan": 3.0 * ssm * scan,
        "attention_projections": 6.0 * attn_layers * 2688 * 128 * (64 + 4),
        "attention": 6.0 * attn_layers * 32 * 8192 * 2 * 128 * 0.5,
        "shared_experts": 6.0 * experts * 2 * 2688 * 3712,
        "routed_here": 6.0 * experts * 0.75 * 2 * 2688 * 1856,
        "router": 6.0 * experts * 2688 * 128,
        "dense_mlp": 0.0,
        "head": 6.0 * 16384 * 2688,
    }
    total = flops_ssm_moe.model_flops_per_token(model, 8192)
    assert total == sum(parts.values())
    # ISSUE 37's shares at two units, forward a token: the state-space
    # layers 646 MFLOP (46%), the expert layers 445, attention 228, head 88
    forward = {k: v * 2 / 3.0 / units / 1e6 for k, v in parts.items()}
    assert forward["ssm_projections"] + forward["ssm_scan"] == pytest.approx(
        646.6, abs=0.1
    )
    assert (forward["shared_experts"] + forward["routed_here"]
            + forward["router"]) == pytest.approx(444.5, abs=0.1)
    assert (forward["attention_projections"]
            + forward["attention"]) == pytest.approx(227.8, abs=0.1)
    assert parts["head"] / 3.0 / 1e6 == pytest.approx(88.1, abs=0.1)
    if units == 2:
        assert 4.1e9 < total < 4.3e9
    # a model without these layers cannot be counted here
    for missing in ("ssm_num_heads", "layer_pattern", "shared_expert_d_ff"):
        with pytest.raises(KeyError):
            flops_ssm_moe.model_flops_per_token(
                {k: v for k, v in model.items() if k != missing}, 8192
            )


def test_the_three_kernel_costs_by_hand():
    model = cell_model()
    units = int(model["num_layers"]) // 9
    tokens = 2 * 8192
    peak = build.peak_for("TPU v5 lite")
    ssd = flops_ssm_moe.ssd_cost(model, 8192, 2)
    assert ssd["flops"] == 3.0 * 3_407_872 * tokens * 4 * units
    # bf16 x, y (4096) and B, C (1024 each), float32 dt (64) forward;
    # x, dy, dx, B, C, dB, dC and dt, ddt backward
    fwd = 2 * (2 * 4096 + 2 * 1024) + 4 * 64
    bwd = 2 * (3 * 4096 + 4 * 1024) + 4 * 2 * 64
    assert (fwd, bwd) == (20_736, 33_280)
    assert ssd["bytes"] == (fwd + bwd) * tokens * 4 * units
    # the scan is bound by memory: which is why it needs its kernel
    assert flops.roofline_seconds(ssd, peak)["bound"] == "memory"
    flash = flops_ssm_moe.gqa_flash_cost(model, 8192, 2)
    # seven matmuls a query head of 128, 2 S^2 each, halved by the mask
    assert flash["flops"] == (
        2.0 * 8192 * 8192 * 32 * 2 * 7 * 128 * 0.5 * units
    )
    row = 2.0 * tokens * 128
    fwd = row * (2 * 32 + 2 * 2) + 4.0 * tokens * 32
    bwd = row * (4 * 32 + 4 * 2) + 4.0 * tokens * 32
    assert flash["bytes"] == (fwd + bwd) * units
    assert flops.roofline_seconds(flash, peak)["bound"] == "compute"
    held = flops_ssm_moe.relu2_expert_matmul_cost(model, 8192, 2)
    # 12,288 pairs here a layer (98,304 chosen, an eighth), TWO matrices
    # of 2688 x 1856, three passes
    assert held["flops"] == 2 * 3 * 2.0 * 12288 * 2688 * 1856 * 4 * units
    weights = 2.0 * 16 * 2 * 2688 * 1856
    acts = 2.0 * 12288 * (2 * 2688 + 2 * 1856)
    assert held["bytes"] == 3 * (weights + acts) * 4 * units
    # counted over all the router chose (``flops.py``, which also knows no
    # layer without a mixer) it would read eight times higher
    everywhere = flops.expert_matmul_cost(
        dict(model, d_ff=1856, num_layers=4 * units), 8192, 2
    )
    assert everywhere["flops"] == 8 * held["flops"]


STEP = "jit(_train_step)/"
ROWS = [
    ["while.3", "", 0, 3000],
    ["fusion.1", STEP + "blocks/ssm_1/ssm/in_proj/dot_general", 0, 200],
    ["fusion.2", STEP + "blocks/ssm_1/ssm/in_proj/convert_element_type",
     200, 25],
    ["fusion.3", STEP + "blocks/ssm_1/ssm/conv/mul", 225, 75],
    ["fusion.4", STEP + "blocks/ssm_1/ssm/dt/softplus", 300, 25],
    ["ssd_fwd.1", STEP + "blocks/ssm_1/ssm/scan/jit(_forward)/ssd_fwd",
     325, 100],
    ["ssd_bwd.1", STEP + "transpose(jvp())/blocks/ssm_3/ssm/scan/"
     "jit(_backward)/ssd_bwd", 425, 200],
    ["fusion.5", STEP + "transpose(jvp())/blocks/ssm_3/ssm/scan/reduce_sum",
     625, 50],
    ["fusion.6", STEP + "blocks/ssm_1/ssm/out_norm/rsqrt", 675, 50],
    ["fusion.7", STEP + "transpose(jvp())/blocks/ssm_1/ssm/out_proj/"
     "dot_general", 725, 100],
    ["attn.2", STEP + "blocks/attention_8/attn/pallas_call", 825, 400],
    ["fusion.8", STEP + "blocks/attention_8/attn/query/dot_general",
     1225, 75],
    ["fusion.9", STEP + "blocks/experts_0/moe/shared/wi/dot_general",
     1300, 125],
    ["gmm.1", STEP + "blocks/experts_0/moe/gmm_wi/pallas_call", 1425, 300],
    ["gmm.2", STEP + "transpose(jvp())/blocks/experts_2/moe/gmm_wo/"
     "pallas_call", 1725, 100],
    ["row_gather_sum.1", STEP + "blocks/experts_0/moe/combine/"
     "jit(gather_sum)/row_gather_sum/pallas_call", 1825, 150],
    ["fusion.10", STEP + "lm_head/dot_general", 1975, 250],
]
TRACE = {"devices": {"/device:TPU:0": {
    "ops": ROWS, "modules": [["jit__train_step(1)", "", 0, 3000]],
}}, "host": []}
OTHER = {"devices": {"/device:TPU:0": {
    "ops": [ROWS[0], ROWS[16]],
    "modules": TRACE["devices"]["/device:TPU:0"]["modules"],
}}, "host": []}


def evidence(trace=TRACE, **more):
    return dict({
        "trace": trace, "step_module": "train_step", "model": cell_model(),
        "seq_len": 8192, "sequences_per_chip": 2,
        "peak": build.peak_for("TPU v5 lite"),
    }, **more)


def test_the_scope_readers_split_the_state_space_layers():
    def ms(name, trace=TRACE):
        spec = layers.spec(name)
        assert spec["reader"] == "scope_ms"
        return scope_ms.read(evidence(trace), spec["params"])

    # everything under ssm/: 200 + 25 + 75 + 25 + 100 + 200 + 50 + 50 + 100
    assert ms("ssm_ms") == pytest.approx(825e-6)
    # the kernels and the dD reduction beside them
    assert ms("ssm_scan_ms") == pytest.approx(350e-6)
    # the convolution's scope, not in_proj's convert_element_type
    assert ms("ssm_conv_ms") == pytest.approx(75e-6)
    assert ms("ssm_proj_ms") == pytest.approx(325e-6)
    assert ms("shared_expert_ms") == pytest.approx(125e-6)
    for name in ("ssm_ms", "ssm_scan_ms", "ssm_conv_ms", "ssm_proj_ms"):
        # a program with no such scope (the parent) gives nothing
        assert ms(name, OTHER) is None


def test_the_three_rooflines_read_their_own_ops_against_their_own_cost():
    peak = build.peak_for("TPU v5 lite")
    model = cell_model()
    cases = (
        # the scan's kernels AND what runs beside them under the scope
        ("ssd_roofline", "ssd_cost", 350e-9),
        # the kernel under attn/, not the projection
        ("ssm_moe_flash_roofline", "gqa_flash_cost", 400e-9),
        # the GEMMs (300 + 100), not the fetch-and-sum kernel beside them
        ("relu2_grouped_matmul_roofline", "relu2_expert_matmul_cost", 400e-9),
    )
    for name, cost, seconds in cases:
        spec = layers.spec(name)
        assert spec["reader"] == "kernel_roofline_from", name
        assert spec["params"]["module"] == MODULE
        assert spec["params"]["cost"] == cost
        floor = flops.roofline_seconds(
            getattr(flops_ssm_moe, cost)(model, 8192, 2), peak
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


def test_ssm_moe_step_mfu_counts_by_part_and_leaves_other_models_alone():
    spec = layers.spec("ssm_moe_step_mfu")
    assert spec["reader"] == "mfu_from" and spec["params"] == {
        "module": MODULE
    }
    model = cell_model()
    summary = {"tokens_per_s_chip": 16000.0}
    got = mfu_from.read(evidence(summary=summary), spec["params"])
    per_token = flops_ssm_moe.model_flops_per_token(model, 8192)
    assert got == pytest.approx(per_token * 16000.0 / 197e12)
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


def recorded_events():
    with open(os.path.join(HERE, "recorded_ssm_events.json")) as f:
        return json.load(f)


def test_the_event_reader_takes_the_windows_largest_state_entry():
    spec = layers.spec("ssm_state_absmax")
    assert spec["reader"] == "program_events"
    assert spec["params"] == {
        "name": "ssm", "attr": "state_absmax", "reduce": "max"
    }
    # steps 5 and 10 lie in the window (5..12); 0 and 15 outside it
    assert program_events.read(recorded_events(), spec["params"]) == 8.5
    # a program that books no such event (the parent's) gives nothing
    older = recorded_events()
    older["program_spans"] = [
        e for e in older["program_spans"] if e[0] != "ssm"
    ]
    assert program_events.read(older, spec["params"]) is None
    # the accepted readers of the moe event read this cell's too
    assert program_events.read(
        recorded_events(), layers.spec("moe_pairs_here")["params"]
    ) == pytest.approx(0.1255)
    assert program_events.read(
        recorded_events(), layers.spec("router_bias_absmax")["params"]
    ) == 0.010
    assert program_events.read(
        recorded_events(), layers.spec("moe_pad_share")["params"]
    ) == pytest.approx(0.09)
    # and the linear layers' reader finds nothing in it
    assert program_events.read(
        recorded_events(), layers.spec("delta_state_absmax")["params"]
    ) is None


OWN = ("ssm_ms", "ssm_scan_ms", "ssm_conv_ms", "ssm_proj_ms", "ssd_roofline",
       "ssm_moe_flash_roofline", "relu2_grouped_matmul_roofline",
       "ssm_moe_step_mfu", "ssm_state_absmax")
JOINED = ("host_step_gap_ms", "step_s_worst_over_median",
          "tokens_per_s_chip_median_step", "data_wait_ms",
          "data_wait_span_ms", "step_device_ms", "device_idle_share",
          "peak_hbm_gib", "startup_to_mesh_s", "moe_row_move_ms",
          "moe_pad_share", "moe_max_expert_load", "moe_pairs_here",
          "router_bias_absmax", "shared_expert_ms", "forward_ms",
          "recompute_ms", "backward_ms", "optimizer_ms", "head_loss_ms",
          "step_unnamed_ms")
NOT_JOINED = ("moe_dispatch_ms", "step_mfu", "flash_roofline",
              "flash_attn_roofline", "grouped_matmul_roofline",
              "expert_matmul_roofline", "held_grouped_matmul_roofline",
              "latent_flash_roofline", "pattern_flash_roofline",
              "delta_rule_roofline", "linear_attn_ms", "mtp_ms")


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
    assert cell["config"] == NAME and "768 rows" in cell["why"]
    reported = {m["name"] for m in layers.cell_entries(
        build.manifest(), CELL, "per_layer"
    )}
    assert set(OWN + JOINED) <= reported
    # the metrics that list no cells: every cell reports them
    assert {"compile_s", "process_to_window_s",
            "reference_check_s"} <= reported - set(OWN + JOINED)
