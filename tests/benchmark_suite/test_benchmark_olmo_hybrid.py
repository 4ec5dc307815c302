"""What the Olmo-Hybrid-7B cell brings to the benchmark: its configuration
against the catalog, its own plain reference against the repository's, the
arithmetic of its FLOPs by kind by hand, and its readers on recorded data."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import build, flops, flops_by_kind, layers  # noqa: E402
from benchmark.readers import (  # noqa: E402
    kernel_roofline_by_kind,
    mfu_by_kind,
    program_events,
    scope_ms,
)

PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# ``config`` of the catalog's entry Olmo-Hybrid-7B (the model-configs
# guide's architectures.jsonl), as published.
CATALOG = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}
CONFIG = os.path.join(REPO, "benchmark", "configs", "olmo-hybrid-7b.json")
PRESET = os.path.join(HERE, "presets", "olmo-hybrid-7b.json")
CELL = "olmo-hybrid-7b.train_steady"


def cell_model():
    return build.model_group(build.load_json(CONFIG))


def test_the_configuration_differs_from_the_catalog_in_what_it_says():
    config = build.load_json(CONFIG)
    differs = {k for k, v in CATALOG.items() if config.get(k) != v}
    assert differs == {"num_hidden_layers", "layer_types", "vocab_size"}
    assert differs == set(config["reduced"])
    entry = {c["name"]: c for c in build.manifest()["configs"]}[
        "olmo-hybrid-7b"
    ]
    assert set(entry["reduced"]) == differs
    # whole periods of the published order, the same quarter of the
    # vocabulary as of the depth
    assert config["layer_types"] == CATALOG["layer_types"][:8] == PERIOD * 2
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 8
    assert config["vocab_size"] * 4 == CATALOG["vocab_size"]
    model = cell_model()
    assert model["layer_pattern"] == PERIOD
    assert model["d_model"] // model["num_heads"] == model["head_dim"] == 128
    assert (model["linear_key_head_dim"], model["linear_value_head_dim"]) == (
        96, 192
    )
    assert config["linear_num_key_heads"] == config["linear_num_value_heads"]
    assert model["norm_placement"] == "post" and model["norm_eps"] == 1e-6
    assert model["linear_allow_neg_eigval"] is True
    assert {"norm_placement", "rope_theta", "optimizer", "sequence",
            "initialisers", "precision"} <= set(config["assumed"])


def test_the_program_takes_the_configuration():
    from dlrover_tpu.models.olmo_hybrid import olmo_hybrid_config

    config = build.load_json(CONFIG)
    cfg = build.transformer_config(cell_model(), build.seq_len(config, {}))
    want = olmo_hybrid_config(num_layers=8, vocab_size=25088)
    for field in ("layer_pattern", "d_model", "num_heads", "d_ff",
                  "linear_num_heads", "linear_key_head_dim",
                  "linear_value_head_dim", "linear_conv_kernel",
                  "linear_allow_neg_eigval", "norm_placement", "norm_eps",
                  "rope_theta", "qk_norm", "tie_embeddings", "max_seq_len"):
        assert getattr(cfg, field) == getattr(want, field), field
    assert cfg.num_scan_units == 2 and cfg.resolved_head_dim == 128
    # 1.858 B: two periods and a quarter of the vocabulary
    assert cfg.num_params() == 2 * (
        3 * (88_750_332 + 126_812_160) + 58_982_400 + 126_812_160
    ) + 2 * 25088 * 3840


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

    from benchmark.references import olmo_hybrid as ours
    from dlrover_tpu.models.references import olmo_hybrid as theirs

    model, params, inputs, targets = preset_case
    got = np.asarray(ours.token_nll(model, params, inputs, targets))
    want = np.asarray(theirs.token_nll(model, params, inputs, targets))
    # two float32 programs of one mathematics, summed in another order
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_lowered_reference_is_another_result(preset_case):
    """What ``reference_tolerance`` is set against (PERF.md §6): the
    reference with its delta rule, or all of it, in bfloat16."""
    import numpy as np

    from benchmark.references import olmo_hybrid as ours

    model, params, inputs, targets = preset_case
    exact = np.asarray(ours.token_nll(model, params, inputs, targets))
    gaps = {
        mode: float(np.abs(np.asarray(ours.token_nll(
            model, params, inputs, targets, lowered=mode
        )) - exact).mean())
        for mode in ("rule", "all")
    }
    assert 0 < gaps["rule"] < gaps["all"]


def test_one_linear_and_one_full_layers_flops_by_hand():
    model = cell_model()
    head = 25088 * 3840
    linear = dict(model, num_layers=1, layer_pattern=["linear_attention"])
    # q and k 2 x 3840 x 2880, v, gate and output 3 x 3840 x 5760, the two
    # gate projections 2 x 3840 x 30; SwiGLU 3 x 3840 x 11008
    mixer = 2 * 11_059_200 + 3 * 22_118_400 + 230_400
    assert flops_by_kind.linear_mixer_matmul_params(model) == mixer
    assert mixer == 88_704_000          # the mixer less taps, A, dt, scale
    assert flops_by_kind.matmul_params_per_token(linear) == (
        mixer + 126_812_160 + head
    )
    # the rule, a token, all 30 heads: per chunk of 64 and head 4 C^2 dk +
    # C^2 (dk + dv) + 6 C dk dv + 2 C^2 dv
    chunk = (4 * 4096 * 96 + 4096 * 288 + 6 * 64 * 96 * 192
             + 2 * 4096 * 192)
    assert chunk == 11_403_264
    assert flops_by_kind.delta_rule_flops_per_token(model) == 30 * chunk / 64
    assert flops_by_kind.model_flops_per_token(linear, 8192) == (
        6.0 * (mixer + 126_812_160 + head) + 3 * 30 * chunk / 64
    )
    full = dict(model, num_layers=1, layer_pattern=["full_attention"])
    assert flops_by_kind.matmul_params_per_token(full) == (
        4 * 3840 * 3840 + 126_812_160 + head
    )
    assert flops_by_kind.model_flops_per_token(full, 8192) == (
        flops.model_flops_per_token(dict(full, layer_pattern=[]), 8192)
    ) == 6.0 * (4 * 3840 * 3840 + 126_812_160 + head) + 12 * 30 * 128 * 8192
    # the cell: 6 linear and 2 full layers; the head is 5.5% of the weights
    assert flops_by_kind.layer_counts(model) == {
        "full_attention": 2, "linear_attention": 6,
    }
    weights = flops_by_kind.matmul_params_per_token(model)
    assert weights == 6 * (mixer + 126_812_160) + 2 * (
        58_982_400 + 126_812_160
    ) + head
    assert 0.054 < head / weights < 0.056
    # without a pattern the count is ``flops.py``'s
    gpt2 = build.model_group(build.load_json(
        os.path.join(REPO, "benchmark", "configs", "gpt2-1.5b.json")
    ))
    assert flops_by_kind.model_flops_per_token(gpt2, 1024) == (
        flops.model_flops_per_token(gpt2, 1024)
    )


def test_the_rules_cost_and_the_flash_cost_by_kind_by_hand():
    model = cell_model()
    cost = flops_by_kind.gated_delta_rule_cost(model, 8192, 2)
    # 2 x 8192 tokens are 256 chunks of 30 heads in each of 6 layers,
    # forward and twice that backward
    assert cost["flops"] == 3.0 * 256 * 30 * 11_403_264 * 6
    # a token and head: q, k 96 and v 192 in bf16, g and beta in float32,
    # o 192 in bf16 forward (1160 B); backward those and o, do in (1544)
    # and the five gradients out (776)
    assert cost["bytes"] == 16384.0 * 30 * (1160 + 1544 + 776) * 6
    peak = build.peak_for("TPU v5 lite")
    floor = flops.roofline_seconds(cost, peak)
    assert floor["bound"] == "memory" and 0.010 < floor["seconds"] < 0.016
    # the flash kernels of the TWO full layers, not of all eight
    two = flops_by_kind.flash_attention_cost(model, 8192, 2)
    eight = flops.flash_attention_cost(model, 8192, 2)
    assert two["flops"] * 4 == eight["flops"]
    assert two["bytes"] * 4 == eight["bytes"]
    assert two["flops"] == 7 * 2.0 * 8192 * 8192 * 128 * 30 * 2 * 0.5 * 2


STEP = "jit(_train_step)/"
ROWS = [
    ["while.3", "", 0, 2000],
    ["fusion.1", STEP + "blocks/linear_0/linear_attn/qkv/qkvg/dot_general",
     0, 300],
    ["fusion.2", STEP + "blocks/linear_0/linear_attn/conv/mul", 300, 50],
    ["fusion.3", STEP + "blocks/linear_0/linear_attn/delta_rule/while/body/"
     "dot_general", 350, 200],
    ["fusion.4", STEP + "transpose(jvp())/blocks/linear_1/linear_attn/"
     "delta_rule/dot_general", 550, 100],
    ["fusion.5", STEP + "transpose(jvp())/blocks/linear_1/linear_attn/conv/"
     "mul", 650, 25],
    ["attn.2", STEP + "blocks/full_3/attn/pallas_call", 700, 400],
    ["fusion.6", STEP + "blocks/full_3/attn/qkv/dot_general", 1100, 100],
    ["fusion.7", STEP + "blocks/linear_2/mlp/wi/dot_general", 1200, 500],
]
TRACE = {"devices": {"/device:TPU:0": {
    "ops": ROWS, "modules": [["jit__train_step(1)", "", 0, 2000]],
}}, "host": []}
OTHER = {"devices": {"/device:TPU:0": {
    "ops": [ROWS[0], ROWS[6], ROWS[8]],
    "modules": TRACE["devices"]["/device:TPU:0"]["modules"],
}}, "host": []}


def evidence(trace=TRACE, **more):
    return dict({
        "trace": trace, "step_module": "train_step", "model": cell_model(),
        "seq_len": 8192, "sequences_per_chip": 2,
        "peak": build.peak_for("TPU v5 lite"),
    }, **more)


def test_the_scope_readers_split_the_linear_layers_time():
    linear = layers.spec("linear_attn_ms")["params"]
    conv = layers.spec("short_conv_ms")["params"]
    assert layers.spec("linear_attn_ms")["reader"] == "scope_ms"
    # qkv 300 + conv 50 + rule 200 + 100 + conv 25; not the flash kernel,
    # whose scope also ends in ``attn/``... and is no linear layer's
    assert scope_ms.read(evidence(), linear) == pytest.approx(675e-6)
    assert scope_ms.read(evidence(), conv) == pytest.approx(75e-6)
    # a program with no such scope (the parent) gives nothing
    assert scope_ms.read(evidence(OTHER), linear) is None
    assert scope_ms.read(evidence(OTHER), conv) is None


def test_the_two_rooflines_read_their_own_ops_against_their_own_cost():
    rule = layers.spec("delta_rule_roofline")
    flash = layers.spec("pattern_flash_roofline")
    assert rule["reader"] == flash["reader"] == "kernel_roofline_by_kind"
    peak = build.peak_for("TPU v5 lite")
    model = cell_model()
    floor = flops.roofline_seconds(
        flops_by_kind.gated_delta_rule_cost(model, 8192, 2), peak
    )["seconds"]
    assert kernel_roofline_by_kind.read(
        evidence(), rule["params"]
    ) == pytest.approx(100 * floor / 300e-9)
    floor = flops.roofline_seconds(
        flops_by_kind.flash_attention_cost(model, 8192, 2), peak
    )["seconds"]
    # the kernel under ``full_3/attn/``, none under ``linear_attn/``
    assert kernel_roofline_by_kind.read(
        evidence(), flash["params"]
    ) == pytest.approx(100 * floor / 400e-9)
    # nothing to read: no such op, no peak, no trace, an older model group
    assert kernel_roofline_by_kind.read(
        evidence(OTHER), rule["params"]
    ) is None
    assert kernel_roofline_by_kind.read(
        evidence(peak=None), rule["params"]
    ) is None
    assert kernel_roofline_by_kind.read({}, rule["params"]) is None
    older = {k: v for k, v in model.items() if not k.startswith("linear_")}
    assert kernel_roofline_by_kind.read(
        evidence(model=older), rule["params"]
    ) is None


def test_pattern_step_mfu_counts_by_kind_and_leaves_other_models_alone():
    model = cell_model()
    summary = {"tokens_per_s_chip": 7000.0}
    got = mfu_by_kind.read(evidence(summary=summary), {})
    per_token = flops_by_kind.model_flops_per_token(model, 8192)
    assert got == pytest.approx(per_token * 7000.0 / 197e12)
    assert 0.3 < got < 0.5
    # counted as eight softmax layers the same rate would read higher
    assert got < flops.model_flops_per_token(model, 8192) * 7000.0 / 197e12
    plain = {k: v for k, v in model.items() if k != "layer_pattern"}
    assert mfu_by_kind.read(evidence(summary=summary, model=plain), {}) is None
    assert mfu_by_kind.read(evidence(), {}) is None          # no summary
    assert mfu_by_kind.read(evidence(summary=summary, peak=None), {}) is None


def recorded_events():
    with open(os.path.join(HERE, "recorded_linear_attn_events.json")) as f:
        return json.load(f)


def test_delta_state_absmax_is_the_largest_of_the_windows_events():
    spec = layers.spec("delta_state_absmax")
    assert spec["reader"] == "program_events"
    # steps 5 and 10 lie in the window (5..12): 2.25 and 3.5; the events of
    # steps 0 and 15 (9.5 and 40.0) lie outside it
    assert program_events.read(recorded_events(), spec["params"]) == 3.5
    older = recorded_events()
    older["program_spans"] = [
        e for e in older["program_spans"] if e[0] != "linear_attn"
    ]
    assert program_events.read(older, spec["params"]) is None


def test_the_cell_joins_the_lists_the_issue_names_and_no_roofline_of_flops_py():
    """The cell's OWN metrics and the nine every steady cell has, by
    membership: never a list's place in the file nor a closed set, so that
    the next metric or cell to join breaks nothing here."""
    per_layer = {m["name"]: m for m in build.manifest()["per_layer"]}
    joined = {name for name, m in per_layer.items()
              if CELL in m.get("workloads", [])}
    own = {"delta_rule_roofline", "linear_attn_ms", "short_conv_ms",
           "pattern_flash_roofline", "pattern_step_mfu",
           "delta_state_absmax"}
    assert joined >= own | {
        "host_step_gap_ms", "step_s_worst_over_median",
        "tokens_per_s_chip_median_step", "data_wait_ms",
        "data_wait_span_ms", "step_device_ms", "device_idle_share",
        "peak_hbm_gib", "startup_to_mesh_s",
    }
    for name in own:
        assert CELL in per_layer[name]["workloads"]
        assert per_layer[name]["moves"] == "tokens_per_s_chip"
        assert layers.spec(name)["name"] == name
    for name in ("step_mfu", "flash_roofline", "flash_attn_roofline"):
        assert CELL not in per_layer[name]["workloads"]
