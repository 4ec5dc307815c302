"""Granite-4.0-H without a model built: what the configuration refuses and
counts, and what a trainer's ``compile`` event asks of the dispatch for
each model the program runs.  (Many cases and no compile: a file is one
worker's, and the driver's workers take the files with the most cases
first.)"""

import pytest

from dlrover_tpu.models.granite_moe_hybrid import granite_moe_hybrid_config
from dlrover_tpu.models.nemotron_h import nemotron_h_config
from dlrover_tpu.models.transformer import TransformerConfig
from test_granite_moe_hybrid_reference import config


@pytest.mark.parametrize("overrides,message", [
    (dict(ssm_impl="kernel", ssm_chunk=2048), "heads_per_step"),
    (dict(decode=True), "decode=True with an ssm layer"),
    (dict(experts_held=5), "must divide num_experts"),
])
def test_bad_combinations_raise(overrides, message):
    with pytest.raises(ValueError, match=message):
        config(**overrides)


def test_the_published_widths_count_what_the_issue_counts():
    """The whole model is 32 B; the cell's cut (one period, 9 of 72
    experts, an eighth of the vocabulary) 2,054,945,408 parameters without
    the layers' norms, the issue's 2,055,031,424 with them."""
    full = granite_moe_hybrid_config()
    assert 32.0e9 < full.num_params() < 32.5e9
    cut = granite_moe_hybrid_config(
        num_layers=20, experts_held=9, vocab_size=12544
    )
    assert cut._ssm_mixer_params() == 102_286_976
    assert cut.num_params() == 2_054_945_408
    assert cut.num_params() + 21 * 4096 == 2_055_031_424


@pytest.mark.parametrize("model,scan,heads,tiles,rows", [
    # the published widths: the fetch-and-sum takes ten rows of 4,096;
    # ONE group of 128 heads is sixteen grid steps of 8
    (lambda: granite_moe_hybrid_config(ssm_impl="kernel"), "kernel", 8, 16,
     "kernel"),
    (lambda: granite_moe_hybrid_config(ssm_impl="kernel", ssm_chunk=128),
     "kernel", 8, 16, "kernel"),
    (lambda: granite_moe_hybrid_config(), "xla", None, None, "kernel"),
    # the cell's cut: 9 of the 72 experts here, so most of a token's ten
    # pairs have no row here and the kernel is handed those that have
    (lambda: granite_moe_hybrid_config(
        ssm_impl="kernel", num_layers=20, experts_held=9, vocab_size=12544,
    ), "kernel", 8, 16, "kernel_live"),
    (lambda: TransformerConfig(
        d_model=2048, num_heads=16, num_experts=256, experts_held=32,
        top_k=8, moe_dispatch="grouped",
    ), "none", None, None, "kernel_live"),
    (lambda: TransformerConfig(
        d_model=2048, num_heads=16, num_experts=64, top_k=8,
        moe_dispatch="grouped",
    ), "none", None, None, "kernel"),
    # Nemotron-3-Nano's rows of 2,688 are no whole native tiles: the
    # fetch-and-sum takes them padded to 3,072 at its door (XLA's gather
    # and reduction until PR 46); a group of 8 heads is one grid step
    (lambda: nemotron_h_config(ssm_impl="kernel"), "kernel", 8, 1,
     "kernel_padded"),
    (lambda: nemotron_h_config(ssm_impl="kernel", experts_held=16),
     "kernel", 8, 1, "kernel_live_padded"),
    (lambda: TransformerConfig(), "none", None, None, "none"),
    (lambda: TransformerConfig(num_experts=8, moe_dispatch="einsum"),
     "none", None, None, "none"),
])
def test_the_compile_event_asks_what_the_dispatch_asks(
    model, scan, heads, tiles, rows
):
    from dlrover_tpu.models import transformer

    # none of the four reads the sequence's length
    facts = transformer.kernel_facts(model(), 4096)
    assert facts["ssm_scan"] == scan
    assert facts["ssm_heads_per_step"] == heads
    assert facts["ssm_tiles_per_group"] == tiles
    assert facts["row_moves"] == rows
