"""That the comparison of ``tests/test_glm_dsa_reference.py`` is sharp:
each fault, made on the reference's side, moves a token's loss past the
tolerance held there, and the reference computed in bfloat16 is another
result.  The same faults through the CELL's own comparison
(``check_reference`` at the configuration's ``reference_tolerance``) are
``tools/glm_dsa_control.py``'s, on the chip at the published widths."""

import numpy as np
import pytest

from dlrover_tpu.models.references import glm_dsa as ref
from test_glm_dsa_reference import CHECK, TOL, config, tokens, weights

WRONG = [w for w in ref.WRONG if w]


def test_every_fault_the_reference_can_make_is_tried():
    assert sorted(WRONG) == [
        "dense", "half_topk", "no_relu", "reuse_chooses", "window",
    ]
    with pytest.raises(ValueError, match="wrong must be one of"):
        ref.forward({}, {}, None, wrong="something_else")


@pytest.mark.parametrize("wrong", WRONG)
def test_the_check_is_sharp(wrong):
    """No choice, a window for a choice, half the keys, a reusing layer
    that chooses for itself, scores without the rectifier: each moves a
    token's loss past ten times the tolerance."""
    assert CHECK.nll_gap(
        config(), weights(), tokens(), wrong=wrong
    ) > 10 * TOL


def test_a_fault_changes_the_sets_it_should_and_no_other():
    cfg, params, toks = config(), weights(), tokens()
    exact = CHECK.reference("forward", cfg, params, toks)["masks"]

    def masks(wrong):
        return CHECK.reference(
            "forward", cfg, params, toks, wrong=wrong
        )["masks"]

    # a reusing layer that chooses for itself: layer 0's set stands, the
    # reusing layers' differ from it and from each other
    own = masks("reuse_chooses")
    assert (own[0] == exact[0]).all()
    assert all((own[i] != exact[i]).any() for i in (1, 2, 3))
    assert (own[1] != own[2]).any()
    # half the keys: a subset of no set in particular, half the size
    half = masks("half_topk")
    assert half[0].sum(-1).max() == cfg.index_topk // 2
    # the window: the most recent topk keys of every row
    window = masks("window")[0]
    seq = window.shape[-1]
    rows, cols = np.arange(seq)[:, None], np.arange(seq)[None, :]
    assert (window == ((cols <= rows) & (cols > rows - cfg.index_topk))).all()
    assert masks("dense")[0].sum() == window.shape[0] * seq * (seq + 1) // 2


def test_every_part_the_reference_can_lower_is_tried():
    assert ref.LOWERED == ("", "all", "rotation", "indexer")
    with pytest.raises(ValueError, match="lowered must be one of"):
        ref.forward({}, {}, None, lowered="router")


@pytest.mark.parametrize("lowered", [m for m in ref.LOWERED if m])
def test_the_reference_computed_lower_is_another_result(lowered):
    cfg, params, toks = config(), weights(), tokens()
    exact = CHECK.reference("token_nll", cfg, params, toks)
    other = CHECK.reference("token_nll", cfg, params, toks, lowered=lowered)
    # read at these sizes: 0.06 to 0.08 a token
    assert float(np.abs(other - exact).mean()) > 50 * TOL


def test_a_lowered_rotation_loses_the_positions_past_256():
    """What ``lowered="rotation"`` stands for: in bfloat16 position 257 is
    256, so the rotated columns of two neighbouring tokens are the same
    where float32 turns them apart; below 256 every position is itself and
    only the angles round."""
    x = np.ones((1, 1024, 8), np.float32)
    exact = np.asarray(ref.rope(x, 10000.0))
    low = np.asarray(ref.rope(x, 10000.0, ref.BF16))
    assert np.abs(low[0, :256] - exact[0, :256]).max() < 0.15
    assert (low[0, 257] == low[0, 256]).all()
    assert np.abs(exact[0, 257] - exact[0, 256]).max() > 0.5
    assert np.abs(low[0, 256:] - exact[0, 256:]).max() > 0.5


def test_a_lowered_indexer_chooses_on_bfloat16_scores():
    """``lowered="indexer"``: the scores the choice is made on are
    bfloat16 (8 bits), the trunk's precision stays float32."""
    cfg, params, toks = config(), weights(), tokens()
    exact = CHECK.reference("forward", cfg, params, toks)
    low = CHECK.reference("forward", cfg, params, toks, lowered="indexer")
    assert low["hidden"].dtype == np.float32
    assert any((a != b).any() for a, b in zip(low["masks"], exact["masks"]))
    q = np.ones((1, 2, 2, 4), ref.BF16)
    k = np.ones((1, 3, 4), ref.BF16)
    w = np.ones((1, 2, 2), ref.BF16)
    assert ref.index_scores(q, k, w).dtype == ref.BF16
    assert ref.index_scores(
        q.astype(np.float32), k.astype(np.float32), w.astype(np.float32)
    ).dtype == np.float32
