"""Granite-4.0-H's parts through the rest of the system, one small CPU test
each (its numerics against the reference are
``tests/test_granite_moe_hybrid_reference.py``'s): what the ``compile`` and
``ssm`` events of a fit say; the mixer's rescaled initialiser.  (What the
configuration refuses is ``tests/test_granite_moe_hybrid_config.py``'s; the
defaults that leave every other model's step as it was
``tests/test_step_scopes.py``'s.)"""

import numpy as np
import pytest

import reference_harness as harness
from dlrover_tpu.models.transformer import TransformerConfig
from test_granite_moe_hybrid_reference import config, params, tokens


def test_fit_books_the_scan_cut_and_the_row_moves(tmp_path, one_step_program):
    """Five steps at ``report_every=5`` through ``ElasticTrainer``: the
    ``compile`` event says the scan is the kernel's, eight heads a grid
    step, and which path a token's rows take; the ``ssm`` event carries
    the heads and the one group; a ``moe`` event beside it with nothing
    dropped; the loss is finite and the step traced once."""
    from dlrover_tpu.trainer import train_lib

    fit = harness.fit(
        config(
            ssm_impl="kernel", num_layers=6, experts_held=4,
            moe_row_budget=4.0,
        ),
        str(tmp_path), seq=32, steps=5, metrics_lag=0,
    )
    taken = fit["taken"]
    losses = {step: float(m["loss"]) for step, m in fit["seen"].items()}
    assert sorted(losses) == [1, 2, 3, 4, 5]
    assert all(np.isfinite(v) for v in losses.values())
    assert train_lib.trace_count("train_step") == 1
    (compiled,) = [e for e in taken if e[0] == "compile"]
    assert compiled[-1]["ssm_scan"] == "kernel"
    assert compiled[-1]["ssm_heads_per_step"] == 8
    assert compiled[-1]["ssm_tiles_per_group"] == 2
    assert compiled[-1]["row_moves"] == "xla"      # rows of 64 are no tile
    (ssm,) = [e for e in taken if e[0] == "ssm" and e[1] == "event"]
    assert ssm[4]["heads"] == 16 and ssm[4]["groups"] == 1
    assert ssm[4]["layers"] == 2 and ssm[4]["chunk"] == 16
    (moe,) = [e for e in taken if e[0] == "moe" and e[1] == "event"]
    assert moe[4]["drop_fraction"] == 0.0
    # XLA's gather fetches every one of a token's rows, the zero row too
    assert moe[4]["row_fetch_share"] == 1.0 > moe[4]["pairs_here"]


def test_the_mixers_output_projection_starts_rescaled_where_asked(
    params, tokens
):
    """``ssm_out_init_scale`` scales ``out_proj``'s initial std over lecun
    normal's and touches nothing else; 1 (the default) is lecun normal."""
    plain = params["blocks"]["ssm_0"]["ssm"]
    scaled = harness.init(
        config(ssm_out_init_scale=0.25), tokens[0]
    )["blocks"]["ssm_0"]["ssm"]
    fan_in = plain["out_proj"]["kernel"].shape[1]
    assert float(plain["out_proj"]["kernel"].std()) == pytest.approx(
        fan_in ** -0.5, rel=0.05
    )
    assert float(scaled["out_proj"]["kernel"].std()) == pytest.approx(
        0.25 * fan_in ** -0.5, rel=0.05
    )
    np.testing.assert_array_equal(
        scaled["in_proj"]["kernel"], plain["in_proj"]["kernel"]
    )
    assert TransformerConfig().ssm_out_init_scale == 1.0
