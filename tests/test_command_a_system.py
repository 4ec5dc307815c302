"""Command A+'s parts through the rest of the system, one small CPU test
each: the train step's first loss against the reference under the policy the
cell runs; the trainer's normal path (``ElasticTrainer``: ten steps, a Flash
Checkpoint save, a second trainer that restores it) with the ``compile``,
``attn`` and ``moe`` events' new facts from the step's own sown stats
through the servicer's route to the master's ledger; the scopes the
benchmark reads; that the parameters held are the parameters counted.
(Sizes and weights are ``tests/test_command_a_reference.py``'s:
``numerics``.)"""

import json
import re

import jax
import numpy as np
import pytest

import reference_harness as harness
import test_command_a_reference as numerics
from dlrover_tpu.models import attention as attention_lib
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.transformer import TransformerLM
from dlrover_tpu.trainer import train_lib
from test_command_a_reference import config, share, tokens  # noqa: F401

SEQ, BATCH, VOCAB = 32, 8, numerics.VOCAB
FLASH = dict(attention_impl="flash", flash_block_q=8, flash_block_kv=8)

# ONE step program for the file: the trainer's, which the first-loss case
# runs on the trainer's batch (``train_step`` is traced once)
pytestmark = pytest.mark.usefixtures("one_step_program")


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    """Ten steps of the preset's period under the policy the cell runs at
    ``report_every=5``, a checkpoint every 5; then a second trainer, as a
    restarted process builds it (the normal path's restore), that trains
    on to step 12."""
    directory = str(tmp_path_factory.mktemp("command_a"))
    cfg = config(max_seq_len=SEQ, remat="flash_only", **FLASH)
    first = harness.fit(cfg, directory, seq=SEQ, batch=BATCH, ckpt_every=5)
    second = harness.fit(
        cfg, directory, seq=SEQ, batch=BATCH, ckpt_every=5, steps=12, seed=1
    )
    return dict(
        first, cfg=cfg, restored=second["began"], final=second["ended"][0]
    )


def test_the_train_step_s_first_loss_is_the_reference_s(fitted):
    """The normal path: the trainer's compiled step under the policy the
    cell runs, both kinds through the flash kernels, both branches of the
    parallel block under ``flash_only``."""
    cfg = fitted["cfg"]
    params = share(cfg)
    tokens = harness.tokens(1, BATCH, SEQ, VOCAB)
    _, metrics = harness.first_step(fitted["train"], params, tokens)
    want = numerics.CHECK.reference("forward", cfg, params, tokens)
    assert abs(float(metrics["loss"]) - float(want["nll"].mean())) <= 1e-4
    assert float(metrics["aux_loss"]) == 0.0
    pairs = float(np.asarray(metrics[moe_lib.SHARE_STATS_NAME])[0])
    assert 0.1 < pairs < 0.5            # 4 of 16 experts held
    full, sliding = np.asarray(metrics[attention_lib.STATS_NAME])
    assert full > 0 and sliding > 0


def test_a_save_is_restored_by_the_next_trainer(fitted):
    """``ElasticTrainer`` with a checkpoint directory: the state saved at
    step 10 is the state the next trainer starts from, and it trains on."""
    assert fitted["restored"] == fitted["ended"]
    assert fitted["ended"][0] == 10
    assert fitted["final"] == 12
    saves = [e for e in fitted["taken"] if e[0] == "checkpoint"]
    assert [e[4]["step"] for e in saves] == [5, 10]


def test_fit_books_the_new_facts_from_the_step_itself(fitted):
    """Ten steps at ``report_every=5``: one ``compile`` event that says the
    block's form, two ``attn`` events that say each kind's rotation, two
    ``moe`` events that say the shared experts held, published and their
    scale; the master's ledger takes each as it is shipped."""
    from dlrover_tpu.master.speed_monitor import SpeedMonitor

    taken, seen = fitted["taken"], fitted["seen"]
    events = [e for e in taken if e[1] == "event"]
    (compiled,) = [e[-1] for e in taken if e[0] == "compile"]
    assert (compiled["block_form"], compiled["block_norms"]) == (
        "parallel", 1
    )
    blocks = compiled["flash_blocks"]
    assert sorted(blocks) == ["full_attention", "sliding_attention"]
    # 32 tokens in blocks of 8 under a window of 12
    band = blocks["sliding_attention"]
    assert (band["live"], band["dead"], band["grid"]) == (9, 7, 12)
    assert compiled["flash_backward"] == "fused"
    attn = [e[4] for e in events if e[0] == "attn"]
    moe = [e[4] for e in events if e[0] == "moe"]
    assert [e["step"] for e in attn] == [5, 10] == [e["step"] for e in moe]
    for event in attn:
        vec = np.asarray(
            seen[event["step"]][attention_lib.STATS_NAME], np.float64
        )
        assert (event["full_layers"], event["sliding_layers"]) == (1, 3)
        assert (event["full_rotation"], event["sliding_rotation"]) == (
            "none", "rope"
        )
        assert event["rotated_layers"] == 3 and event["window"] == 12
        assert event["full_score_bound"] == pytest.approx(float(vec[0]))
        assert event["sliding_score_bound"] == pytest.approx(float(vec[1]))
    for event in moe:
        assert event["experts"] == 16 and event["held"] == 4
        assert (event["shared_held"], event["shared_published"]) == (1, 4)
        assert event["shared_scale"] == 0.25
        assert event["drop_fraction"] == 0.0
        assert 0.1 < event["pairs_here"] < 0.5
        assert len(json.loads(event["load"])) == 16
    assert train_lib.trace_count("train_step") == 1
    # the events as they are shipped are what the master's ledger takes
    monitor = SpeedMonitor()
    monitor.record_health("attn", 0, **attn[-1])
    monitor.record_moe(0, **moe[-1])
    assert monitor.health_ledger("attn")["rotated_layers"] == 3.0
    ledger = monitor.health_ledger("moe")
    assert (ledger["shared_held"], ledger["shared_published"]) == (1.0, 4.0)
    assert ledger["shared_scale"] == 0.25 and ledger["held"] == 4.0


def test_a_rotating_sibling_books_what_it_is():
    """Mellum2's ``attn`` event says ``rope`` and ``yarn``; a summed shared
    expert says a scale of 1."""
    from dlrover_tpu.models.mellum import mellum_config
    from dlrover_tpu.models.joyai_llm_flash import joyai_llm_flash_config

    mellum = mellum_config(num_layers=8, experts_held=16, vocab_size=24576)
    event = attention_lib.FAMILY.read(mellum, [1.0, 2.0])
    assert (event["full_rotation"], event["sliding_rotation"]) == (
        "yarn", "rope"
    )
    assert event["rotated_layers"] == 8
    joyai = joyai_llm_flash_config()
    vec = np.zeros(2 + joyai.num_experts + 2)
    event = moe_lib.FAMILY.read(joyai, vec)
    assert (event["shared_held"], event["shared_published"]) == (1, 1)
    assert event["shared_scale"] == 1.0


def test_the_scopes_the_benchmark_reads_reach_the_compiled_text(tokens):
    cfg = config()
    weights = share(cfg)
    text = jax.jit(
        lambda p, t: TransformerLM(cfg).apply({"params": p}, t)[0]
    ).lower(weights, tokens[0]).as_text(debug_info=True)
    for scope in (
        "sliding_0/ln/", "full_3/ln/", "sliding_0/attn/query",
        "sliding_2/attn/out", "full_3/attn/key", "full_3/attn/out",
        "sliding_1/moe/router", "full_3/moe/router", "full_3/moe/shared/wi",
        "sliding_0/moe/shared/wo", "full_3/residual",
    ):
        assert scope in text, scope
    # the routed experts' GEMMs, as ``window_moe_grouped_matmul_roofline``
    # finds them
    assert re.search(r"full_3/moe/(.*/)?gmm_w[igo]/", text)
    assert "ln_attn" not in text and "ln_mlp" not in text


def test_num_params_counts_what_is_held():
    cfg = config()
    params = share(cfg)
    held = sum(leaf.size for leaf in jax.tree.leaves(params))
    norms = sum(
        leaf.size
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
        if path[-2].key in ("ln", "ln_final")
    )
    assert norms == 5 * 64              # one a layer and the final one
    assert cfg.num_params() == held - norms
    assert (cfg.num_sliding_layers, cfg.num_full_layers) == (3, 1)
