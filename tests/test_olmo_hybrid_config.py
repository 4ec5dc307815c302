"""Olmo-Hybrid without a model built: what the configuration refuses and
counts, the cache key's fields, and what the master does with the
``linear_attn`` event.  (Many cases and no compile: a file is one worker's,
and the driver's workers take the files with the most cases first.)"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.olmo_hybrid import olmo_hybrid_config
from dlrover_tpu.models.transformer import TransformerLM
from test_olmo_hybrid_reference import BATCH, SEQ, config


def test_decode_with_a_linear_layer_raises_naming_what_is_missing():
    with pytest.raises(ValueError, match="recurrent state"):
        config(decode=True)


@pytest.mark.parametrize("kwargs,message", [
    (dict(num_layers=6), "6 is no whole number of periods of the 4-layer"),
    (dict(num_layers=12, pipeline_stages=2),
     "pipeline_stages 2 does not divide the 3 periods"),
    (dict(layer_pattern=("full_attention", "sliding")), "sliding"),
    (dict(linear_key_head_dim=0), "linear_key_head_dim"),
    (dict(norm_placement="sandwich"), "sandwich"),
])
def test_a_pattern_that_does_not_fit_raises_with_the_numbers(kwargs, message):
    with pytest.raises(ValueError, match=message):
        config(**kwargs)


def test_the_published_widths_count_what_the_issue_counts():
    """No array is made: ``eval_shape`` of the program's own init at the
    published widths, one period, a 128-row vocabulary."""
    cfg = olmo_hybrid_config(num_layers=4, vocab_size=128, max_seq_len=64)
    shapes = jax.eval_shape(
        TransformerLM(cfg).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 64), jnp.int32),
    )["params"]

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    blocks = nn.meta.unbox(shapes)["blocks"]
    assert count(blocks["linear_0"]["linear_attn"]) == 88_750_332
    assert count(blocks["linear_0"]["mlp"]) == 126_812_160
    assert cfg._linear_mixer_params() == 88_750_332
    # attention without its two QK-norm scales, as num_params counts it
    attn = count(blocks["full_3"]["attn"]) - 2 * 3840
    assert attn == 4 * 3840 * 3840
    full = olmo_hybrid_config()
    assert full.num_params() == (
        24 * (88_750_332 + 126_812_160) + 8 * (attn + 126_812_160)
        + 2 * 100352 * 3840
    )
    assert 7.42e9 < full.num_params() < 7.44e9


def test_cache_key_covers_the_new_fields():
    from dlrover_tpu.runtime.compile_cache import train_cache_key

    def key(**kw):
        return train_cache_key(
            dataclasses.replace(config(), **kw), (1, 1, 1, 1, 1, 1),
            global_batch_size=BATCH, seq_len=SEQ,
        )

    keys = {
        key(),
        key(layer_pattern=("linear_attention", "full_attention")),
        key(linear_num_heads=2), key(linear_key_head_dim=8),
        key(linear_value_head_dim=16), key(linear_conv_kernel=2),
        key(linear_allow_neg_eigval=False), key(norm_placement="pre"),
        key(norm_eps=1e-5),
    }
    assert len(keys) == 9


def test_the_master_renders_the_events_as_gauges():
    from dlrover_tpu.master.speed_monitor import SpeedMonitor
    from dlrover_tpu.master.timeline import JobTimeline

    monitor = SpeedMonitor()
    monitor.record_health(
        "linear_attn", 0, step=5, layers=6, chunk=64, mean_alpha=0.8, mean_beta=1.0,
        state_absmax=2.5, later_attr="ignored",
    )
    monitor.record_health(
        "linear_attn", 1, step=5, layers=6, chunk=64, mean_alpha=0.6, mean_beta=1.2,
        state_absmax=7.5,
    )
    ledger = monitor.health_ledger("linear_attn")
    assert ledger["reporters"] == 2 and ledger["layers"] == 6
    assert ledger["mean_alpha"] == pytest.approx(0.7)
    assert ledger["state_absmax"] == 7.5          # the worst replica's
    text = JobTimeline().render_metrics(speed_monitor=monitor)
    for name, value in (
        ("dlrover_linear_attn_layers", "6"),
        ("dlrover_linear_attn_chunk", "64"),
        ("dlrover_linear_attn_mean_beta", "1.1"),
        ("dlrover_linear_attn_state_absmax", "7.5"),
        ("dlrover_linear_attn_reporters", "2"),
    ):
        assert f"# TYPE {name} gauge" in text
        assert any(
            line.startswith(name + " ") and line.split()[1].startswith(value)
            for line in text.splitlines()
        ), name
    # a state that diverged on one replica shows as such
    monitor.record_health("linear_attn", 1, step=10, state_absmax=float("nan"))
    assert np.isnan(monitor.health_ledger("linear_attn")["state_absmax"])


def test_a_state_that_is_not_finite_is_the_anomaly_a_loss_would_be():
    from dlrover_tpu.trainer.numeric_health import NumericHealthMonitor

    monitor = NumericHealthMonitor()
    assert monitor.check(1, 5.0, 1.0, state_absmax=3.0) == []
    (found,) = monitor.check(2, 5.0, 1.0, state_absmax=float("inf"))
    assert found.kind == "nan" and "state_absmax=inf" in found.detail
    # a poisoned reading stays out of the rolling statistics
    assert len(monitor._losses) == 1
    assert monitor.check(3, 5.0, 1.0) == []
