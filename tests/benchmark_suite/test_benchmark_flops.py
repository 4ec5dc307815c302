"""``benchmark/flops.py`` against ``bench.py`` and against a count by hand."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import build, flops  # noqa: E402


def model_of(name):
    return build.model_group(build.load_json(
        os.path.join(REPO, "benchmark", "configs", f"{name}.json")
    ))


def test_gpt2_matches_bench_py():
    import jax.numpy as jnp

    import bench
    from dlrover_tpu.models.gpt2 import gpt2_config

    want = bench.flops_per_token(gpt2_config(
        "1.5b", max_seq_len=1024, param_dtype=jnp.bfloat16
    ))
    got = flops.model_flops_per_token(model_of("gpt2-1.5b"), 1024)
    # bench.py counts the position table (1024 x 1600 of 1.56e9 parameters)
    # as if it were a matmul; a lookup is not one.
    assert got == pytest.approx(want, rel=2e-3)
    assert got < want


def test_one_mixtral_layer_by_hand():
    d, ff, v, s = 4096, 14336, 32000, 4096
    attn = d * 32 * 128 + 2 * d * 8 * 128 + 32 * 128 * d       # q, k+v, out
    experts = 2 * 3 * d * ff                                    # 2 of 8
    router = d * 8
    head = v * d
    by_hand = 6 * (attn + experts + router + head) + 12 * 32 * 128 * s
    model = model_of("mixtral-8x7b")
    assert model["num_layers"] == 1 and model["top_k"] == 2
    assert flops.model_flops_per_token(model, s) == by_hand
    # all 8 experts would be four times the expert part
    dense = dict(model, top_k=8)
    assert flops.model_flops_per_token(dense, s) - by_hand == 6 * 6 * 3 * d * ff


def test_kernel_costs_by_hand():
    gpt2 = model_of("gpt2-1.5b")
    cost = flops.flash_attention_cost(gpt2, 1024, 16)
    assert cost["flops"] == 7 * 2 * 1024 * 1024 * 64 * 25 * 16 * 0.5 * 48
    mix = model_of("mixtral-8x7b")
    cost = flops.expert_matmul_cost(mix, 4096, 8)
    assert cost["flops"] == 3 * 3 * 2 * (8 * 4096 * 2) * 4096 * 14336
    peak = build.peak_for("TPU v5 lite")
    floor = flops.roofline_seconds(cost, peak)
    assert floor["bound"] == "compute"
    assert floor["seconds"] == pytest.approx(cost["flops"] / 197e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        build.peak_for("TPU v9 imaginary")
    with pytest.raises(SystemExit):
        build.peak_for("_source")
