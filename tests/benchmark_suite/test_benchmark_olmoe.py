"""What the OLMoE-1B-7B cell brings to the benchmark: its configuration
against the catalog, its own plain reference against the repository's, the
arithmetic of its FLOPs by hand, and its readers on recorded data."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import build, flops, layers  # noqa: E402
from benchmark.readers import program_events, scope_ms  # noqa: E402

# ``config`` of the catalog's entry OLMoE-1B-7B-0125-Instruct (the
# model-configs guide's architectures.jsonl), as published.
CATALOG = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304,
}
CONFIG = os.path.join(REPO, "benchmark", "configs", "olmoe-1b-7b.json")
PRESET = os.path.join(HERE, "presets", "olmoe-1b-7b.json")


def test_the_configuration_holds_every_published_key_but_the_depth():
    config = build.load_json(CONFIG)
    differs = {k for k, v in CATALOG.items() if config.get(k) != v}
    assert differs == {"num_hidden_layers"} == set(config["reduced"])
    assert config["reduced"]["num_hidden_layers"]["published"] == 16
    assert config["num_hidden_layers"] == (
        config["reduced"]["num_hidden_layers"]["run"]
    )
    model = build.model_group(config)
    assert model["d_model"] // model["num_heads"] == model["head_dim"] == 128
    assert model["moe_dispatch"] == "grouped" and model["qk_norm"] is True
    assert model["norm_topk_prob"] is False
    assert "capacity_factor" not in model


def test_the_preset_is_finer_grained_than_mixtrals():
    preset = build.load_json(PRESET)
    assert preset["num_experts"] > 8 and preset["num_experts_per_tok"] > 2


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
        np.random.default_rng(3).integers(0, model["vocab_size"], (2, seq + 1)),
        jnp.int32,
    )
    lm = TransformerLM(build.transformer_config(model, seq))
    params = nn.meta.unbox(lm.init(jax.random.PRNGKey(3), rows[:, :-1]))
    return model, params["params"], rows[:, :-1], rows[:, 1:]


def test_the_benchmarks_reference_agrees_with_the_repositorys(preset_case):
    import numpy as np

    from benchmark.references import olmoe as ours
    from dlrover_tpu.models.references import olmoe as theirs

    model, params, inputs, targets = preset_case
    got = np.asarray(ours.token_nll(model, params, inputs, targets))
    want = np.asarray(theirs.token_nll(model, params, inputs, targets))
    # two float32 programs of one mathematics, summed in another order
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_lowered_reference_is_another_result(preset_case):
    """What ``reference_tolerance`` is set against (PERF.md §6): the
    reference with its router, or all of it, in bfloat16."""
    import numpy as np

    from benchmark.references import olmoe as ours

    model, params, inputs, targets = preset_case
    exact = np.asarray(ours.token_nll(model, params, inputs, targets))
    gaps = {
        mode: float(np.abs(np.asarray(ours.token_nll(
            model, params, inputs, targets, lowered=mode
        )) - exact).mean())
        for mode in ("router", "all")
    }
    assert 0 < gaps["router"] < gaps["all"]


def test_one_layers_flops_by_hand():
    layer = dict(build.model_group(build.load_json(CONFIG)), num_layers=1)
    # attention 4 x 2048 x 2048; 8 experts of 3 x 2048 x 1024; the router
    # 2048 x 64; the head 50304 x 2048
    per_layer = 4 * 2048 * 2048 + 8 * 3 * 2048 * 1024 + 2048 * 64
    assert per_layer == 67_239_936
    assert flops.matmul_params_per_token(layer) == per_layer + 103_022_592
    assert flops.model_flops_per_token(layer, 4096) == (
        6.0 * (per_layer + 103_022_592) + 12 * 16 * 128 * 4096
    )
    # 4 sequences of 4096: 131,072 routed pairs through three matmuls of
    # 2 x 2048 x 1024, forward, d-input and d-weight
    cost = flops.expert_matmul_cost(layer, 4096, 4)
    assert cost["flops"] == 3 * 3 * 2.0 * 131_072 * 2048 * 1024
    weights = 2.0 * 64 * 3 * 2048 * 1024
    acts = 2.0 * 131_072 * (2 * 2048 + 3 * 1024)
    assert cost["bytes"] == 3 * weights + 3 * acts
    # compute-bound on a v5e: 4.95 TFLOP / 197 TFLOP/s against 6.0 GB / 819
    peak = build.peak_for("TPU v5 lite")
    assert flops.roofline_seconds(cost, peak)["bound"] == "compute"


ROWS = [
    ["while.3", "", 0, 1000],
    ["fusion.7", "jit(_train_step)/blocks/moe/moe._grouped_forward/sort/cumsum",
     0, 100],
    ["gmm_wi.5", "jit(_train_step)/blocks/moe/moe._grouped_forward/gmm_wi/"
     "pallas_call", 100, 300],
    ["fusion.9", "jit(_train_step)/blocks/moe/moe._grouped_forward/scatter/"
     "gather", 400, 150],
    ["attn.2", "jit(_train_step)/blocks/attn/pallas_call", 550, 200],
    ["fusion.11", "jit(_train_step)/transpose(jvp())/blocks/moe/router/"
     "dot_general", 750, 50],
    ["fusion.12", "jit(_train_step)/blocks/ln_mlp/mul", 800, 100],
]
TRACE = {"devices": {"/device:TPU:0": {
    "ops": ROWS, "modules": [["jit__train_step(1)", "", 0, 1000]],
}}, "host": []}


def test_moe_dispatch_ms_is_the_moe_scopes_time_less_the_kernels():
    from benchmark import trace_reduce

    params = layers.spec("moe_dispatch_ms")["params"]
    assert trace_reduce.scope_seconds(
        ROWS, params["match"]
    ) == pytest.approx(300e-9)        # sort 100 + gather 150 + router 50
    evidence = {"trace": TRACE, "step_module": "train_step"}
    assert scope_ms.read(evidence, params) == pytest.approx(300e-6)
    # a program with no such scope (the parent) gives nothing
    other = {"devices": {"/device:TPU:0": {
        "ops": [ROWS[4], ROWS[6]], "modules": TRACE["devices"][
            "/device:TPU:0"]["modules"],
    }}, "host": []}
    assert scope_ms.read(dict(evidence, trace=other), params) is None


def test_the_two_kernel_shares_split_the_pallas_calls_by_scope():
    from benchmark import trace_reduce

    grouped = layers.spec("grouped_matmul_roofline")["params"]["match"]
    flash = layers.spec("flash_attn_roofline")["params"]["match"]
    assert trace_reduce.scope_seconds(ROWS, grouped) == pytest.approx(300e-9)
    assert trace_reduce.scope_seconds(ROWS, flash) == pytest.approx(200e-9)


def recorded_events():
    with open(os.path.join(HERE, "recorded_moe_events.json")) as f:
        return json.load(f)


def test_moe_counters_are_the_median_of_the_windows_events():
    evidence = recorded_events()
    load = layers.spec("moe_max_expert_load")["params"]
    # steps 5 and 10 lie in the window (5..12): 1.25 and 1.75; the events
    # of steps 0 and 15 (2.5 and 9.0) lie outside it
    assert program_events.read(evidence, load) == pytest.approx(1.5)
    pad = layers.spec("moe_pad_share")["params"]
    assert program_events.read(evidence, pad) == pytest.approx(0.0588)


def test_a_program_whose_events_lack_the_attribute_gives_nothing():
    evidence = recorded_events()
    for event in evidence["program_spans"]:
        event[4].pop("pad_share", None)
    pad = layers.spec("moe_pad_share")["params"]
    assert program_events.read(evidence, pad) is None
    assert program_events.read({}, pad) is None
