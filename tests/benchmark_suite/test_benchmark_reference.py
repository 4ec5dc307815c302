"""The plain reference against ``TransformerLM`` at tiny sizes on the CPU."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import build, reference, traffic  # noqa: E402

PRESETS = os.path.join(REPO, "tests", "benchmark_suite", "presets")


def setup(name, **program):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.transformer import TransformerLM

    config = build.load_json(os.path.join(PRESETS, f"{name}.json"))
    model = build.model_group(config)
    model.update(attention_impl="xla", remat="none", dtype="float32",
                 **program)
    seq = config["run"]["seq_len"]
    rows = traffic.first_sequences(traffic.sample_fn(250, seq, 7), 2)
    lm = TransformerLM(build.transformer_config(model, seq))
    inputs, targets = jnp.asarray(rows["inputs"]), jnp.asarray(rows["targets"])
    params = nn.meta.unbox(lm.init(jax.random.PRNGKey(5), inputs)["params"])
    # break the symmetry of the zero biases and unit scales
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(6), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)
    ])
    logits, _ = lm.apply({"params": params}, inputs)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    got = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    return model, params, inputs, targets, np.asarray(got)


@pytest.mark.parametrize("name,program", [
    ("gpt2-1.5b", {}),
    ("gpt2-1.5b", {"scan_layers": False}),
    ("mixtral-8x7b", {}),
    ("mixtral-8x7b", {"capacity_factor": 0.5}),
])
def test_reference_agrees_with_the_program(name, program):
    model, params, inputs, targets, got = setup(name, **program)
    want = np.asarray(reference.token_nll(model, params, inputs, targets))
    assert want.shape == got.shape
    # float32 on both sides: what is left is the order of the sums.
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


def test_the_check_is_sharp_enough_for_a_dropped_expert_or_a_wrong_mask():
    model, params, inputs, targets, got = setup("mixtral-8x7b")
    top1 = dict(model, top_k=1)
    off = np.asarray(reference.token_nll(top1, params, inputs, targets))
    assert np.abs(off - got).mean() > 10 * 2e-4
    # a "mask" that lets the future in: reverse the sequence order of the
    # comparison instead of touching the reference
    future = np.asarray(reference.token_nll(
        model, params, inputs[:, ::-1], targets[:, ::-1]
    ))[:, ::-1]
    assert np.abs(future - got).mean() > 10 * 2e-4


def test_capacity_drops_are_exercised_at_the_tight_preset():
    """At capacity factor 0.5 tokens really are dropped, so the agreement
    above covers the dropping rule and not only the dropless case."""
    model, params, inputs, targets, got = setup(
        "mixtral-8x7b", capacity_factor=0.5
    )
    dropless = dict(model, capacity_factor=None)
    loose = np.asarray(reference.token_nll(dropless, params, inputs, targets))
    assert np.abs(loose - got).max() > 1e-3
