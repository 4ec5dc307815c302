"""LFM2's parts through the rest of the system, one small CPU test each:
the train step's first loss and its router-bias rule against the reference,
the ``conv``, ``moe`` and ``compile`` events' fields from the step's own
sown stats through the servicer to the master's ledger, the scopes the
benchmark reads, and that the parameters held are the parameters counted.
(Sizes and weights are ``tests/test_lfm2_moe_reference.py``'s:
``numerics``.)"""

import functools
import json

import jax
import numpy as np
import pytest

import reference_harness as harness
import test_lfm2_moe_reference as numerics
from dlrover_tpu.models import gated_conv
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.references import lfm2_moe as ref
from dlrover_tpu.models.transformer import TransformerLM
from dlrover_tpu.trainer import train_lib
from test_lfm2_moe_reference import config, params, tokens  # noqa: F401

SEQ, BATCH, VOCAB = 32, 8, numerics.VOCAB
SLOTS = ("full_0", "conv_1", "conv_2", "conv_3")
biases = harness.router_biases


# ONE step program for the file: the trainer's, which the first-loss case
# runs on the trainer's batch (``train_step`` is traced once)
pytestmark = pytest.mark.usefixtures("one_step_program")


def cell_config():
    """One period (a dense layer ahead of it) under the policy the cell
    runs."""
    return config(
        max_seq_len=SEQ, num_layers=5, attention_impl="flash",
        remat="flash_only", flash_block_q=8, flash_block_kv=8,
    )


@functools.cache
def one_period(seed=2):
    """Seeded weights of the one-period tree, the biases moved."""
    inputs = harness.tokens(1, BATCH, SEQ, VOCAB)[0]
    return harness.init(cell_config(), inputs, seed=seed, move=numerics.move)


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    """Ten steps at ``report_every=5``."""
    return harness.fit(
        cell_config(), str(tmp_path_factory.mktemp("lfm2")), seq=SEQ,
        batch=BATCH,
    )


def test_the_train_step_s_first_loss_and_bias_move_are_the_reference_s(
    fitted
):
    """The normal path: the trainer's compiled step under the policy the
    cell runs, on the trainer's batch.  Each expert layer's bias moves by
    the unchanged rule on that layer's own counts over ALL the experts."""
    cfg, params = cell_config(), one_period()
    tokens = harness.tokens(1, BATCH, SEQ, VOCAB)
    before = biases(params)
    new_state, metrics = harness.first_step(fitted["train"], params, tokens)
    want = numerics.CHECK.reference("forward", cfg, params, tokens)
    assert abs(float(metrics["loss"]) - float(want["nll"].mean())) <= 1e-4
    assert float(metrics["aux_loss"]) == 0.0
    moved = biases(new_state.params)
    assert sorted(moved) == sorted(
        f"blocks/{slot}/moe/router_bias" for slot in SLOTS
    )
    # the trunk's layers in order: period 0's four slots, then period 1's
    for layer, counts in enumerate(want["counts"]):
        name = f"blocks/{SLOTS[layer % 4]}/moe/router_bias"
        np.testing.assert_allclose(
            moved[name][layer // 4],
            ref.bias_rule(
                before[name][layer // 4], counts, cfg.router_bias_rate
            ),
            atol=1e-7, err_msg=name,
        )
    pairs, bias_absmax = np.asarray(metrics[moe_lib.SHARE_STATS_NAME])[:2]
    here = np.mean([float(c[8:16].sum() / c.sum()) for c in want["counts"]])
    assert pairs == pytest.approx(here, rel=1e-5) and bias_absmax > 0
    gate, out_gate, absmax = np.asarray(metrics[gated_conv.STATS_NAME])
    assert 0 < gate < 10 and 0 < out_gate < 10 and 0 < absmax < 1e3


def test_fit_books_the_conv_event_from_the_step_itself(fitted):
    """Ten steps at ``report_every=5``: two ``conv`` and two ``moe`` events
    carrying the step's own numbers, one ``compile`` event that says how
    the core runs; the servicer hands the ``conv`` event to the master's
    ledger."""
    from dlrover_tpu.master.speed_monitor import SpeedMonitor

    taken, seen = fitted["taken"], fitted["seen"]
    events = [e for e in taken if e[1] == "event"]
    (compiled,) = [e[-1] for e in taken if e[0] == "compile"]
    assert compiled["conv_core"] == "xla" and compiled["short_conv"] == "none"
    assert compiled["gmm_strips"] == "resident"
    assert compiled["gmm_dw_tiles"] == "into:1x1 out_of:1x1"
    conv = [e[4] for e in events if e[0] == "conv"]
    moe = [e[4] for e in events if e[0] == "moe"]
    assert [e["step"] for e in conv] == [5, 10] == [e["step"] for e in moe]
    for event in conv:
        vec = np.asarray(
            seen[event["step"]][gated_conv.STATS_NAME], np.float64
        )
        assert event["layers"] == 4 and "taps" not in event
        assert event["gate_absmean"] == pytest.approx(float(vec[0]))
        assert event["out_gate_absmean"] == pytest.approx(float(vec[1]))
        assert event["out_absmax"] == pytest.approx(float(vec[2]))
        assert "state_absmax" not in event
    for event in moe:
        assert event["experts"] == 32 and event["held"] == 8
        assert event["drop_fraction"] == 0.0 and event["groups"] == 1
        assert len(json.loads(event["load"])) == 32
    assert train_lib.trace_count("train_step") == 1
    # the event as it is shipped (unknown attributes and all) is what the
    # master's ledger takes
    monitor = SpeedMonitor()
    monitor.record_health("conv", 0, **conv[-1])
    ledger = monitor.health_ledger("conv")
    assert ledger["out_absmax"] == conv[-1]["out_absmax"]
    assert ledger["layers"] == 4


def test_the_compile_event_says_how_the_core_runs():
    from dlrover_tpu.models import transformer
    from dlrover_tpu.models.lfm2_moe import lfm2_moe_config
    from dlrover_tpu.models.transformer import TransformerConfig

    def facts(cfg, seq=SEQ):
        return transformer.kernel_facts(cfg, seq)

    assert facts(config())["conv_core"] == "xla"
    assert facts(TransformerConfig())["conv_core"] == "none"
    published = lfm2_moe_config(
        num_layers=17, first_k_dense=1, experts_held=8, vocab_size=16384
    )
    assert facts(published, 8192)["short_conv"] == "none"
    # tokens whole lane tiles and d whole row tiles: the Pallas form
    assert facts(published, 8192)["conv_core"] == "pallas"
    # a length that is no whole lane tiles: the written-out form
    assert facts(published, 8000)["conv_core"] == "xla"
    # rows of 2,048 are 16 lane tiles, 4 a token: the fetch-and-sum kernel
    # under a share of the experts
    assert facts(published, 8192)["row_moves"] == "kernel_live"
    # wo's [1792, 2048] strip and the transposed wi's and wg's, which the
    # default scoped VMEM would cut in two, stay whole under the limit the
    # calls ask for: none of a layer's six forward/dx GEMMs splits K
    assert facts(published, 8192)["gmm_strips"] == "resident"
    assert facts(TransformerConfig())["gmm_strips"] == "none"
    # the weight gradients' [2048, 1792] and [1792, 2048], seven tiles each
    # under the default scoped VMEM, are one tile under the limit they ask
    assert facts(published, 8192)["gmm_dw_tiles"] == (
        "into:1x1 out_of:1x1"
    )
    assert facts(TransformerConfig())["gmm_dw_tiles"] == "none"


def test_the_scopes_the_benchmark_reads_reach_the_compiled_text(tokens):
    cfg = config(num_layers=5)
    weights = one_period()
    text = jax.jit(
        lambda p, t: TransformerLM(cfg).apply({"params": p}, t)[0]
    ).lower(weights, tokens[0]).as_text(debug_info=True)
    for scope in (
        "conv/in_proj", "conv/core", "conv/out_proj", "attn/query",
        "attn/q_norm", "attn/k_norm", "attn/out", "moe/router",
    ):
        assert scope in text, scope
    assert "linear_attn" not in text and "moe/shared" not in text
    assert "top_k" not in text


def test_num_params_counts_what_is_held(params):
    cfg = config()
    held = sum(leaf.size for leaf in jax.tree.leaves(params))
    norms = sum(
        leaf.size
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
        if path[-2].key in ("ln_attn", "ln_mlp", "ln_final")
    )
    # the layer norms are the approximation num_params() always made; the
    # per-head norms' two scales a layer and the taps are counted
    assert cfg.num_params() == held - norms
    assert cfg.num_conv_layers == 7
