"""GLM-5.2's parts through the rest of the system, one small CPU test each:
the train step's first loss and ``L^I`` against the reference under the
policy the cell runs (the sparse kernels, ``flash_only``); the trainer's
normal path (``ElasticTrainer``: ten steps, a Flash Checkpoint save, a
second trainer that restores it) with the ``compile`` and ``index`` events'
facts from the step's own sown stats to the master's ledger; the scopes the
benchmark reads; what a model without an indexer does not import.  (Sizes
and weights are ``tests/test_glm_dsa_reference.py``'s: ``numerics``.)"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import reference_harness as harness
import test_glm_dsa_reference as numerics
from dlrover_tpu.models import sparse_attention, transformer
from dlrover_tpu.trainer import train_lib
from test_glm_dsa_reference import config, weights

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, BATCH, VOCAB = 128, 8, numerics.VOCAB
KERNELS = dict(attention_impl="flash", max_seq_len=SEQ, remat="flash_only")

# ONE step program for the file: the trainer's, which the first-loss case
# runs and the scopes' case lowers (``train_step`` is traced once)
pytestmark = pytest.mark.usefixtures("one_step_program")


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    """Ten steps of the small model at ``report_every=5``, a checkpoint
    every 5, then a second trainer that restores."""
    directory = str(tmp_path_factory.mktemp("glm_dsa"))
    cfg = config(**KERNELS)
    first = harness.fit(cfg, directory, seq=SEQ, batch=BATCH, ckpt_every=5)
    second = harness.fit(cfg, directory, seq=SEQ, batch=BATCH, ckpt_every=5)
    return dict(first, cfg=cfg, restored=second["began"])


def test_the_train_step_s_first_losses_are_the_reference_s(fitted):
    """The normal path: the trainer's compiled step under the policy the
    cell runs, on the trainer's batch; ``aux_loss`` is the indexers' terms
    alone."""
    cfg = fitted["cfg"]
    toks = harness.tokens(1, BATCH, SEQ, VOCAB)
    params = weights()
    _, metrics = harness.first_step(fitted["train"], params, toks)
    want = numerics.CHECK.reference("forward", cfg, params, toks)
    assert abs(float(metrics["loss"]) - float(want["nll"].mean())) <= 1e-4
    assert abs(
        float(metrics["mtp_loss"]) - float(want["mtp_nll"].mean())
    ) <= 1e-4
    assert abs(
        float(metrics["aux_loss"]) - float(sum(want["index_kl"]))
    ) <= 1e-4
    chosen, seen, absmax, kl = np.asarray(
        metrics[sparse_attention.STATS_NAME]
    )
    rows = np.minimum(np.arange(SEQ) + 1, cfg.index_topk).sum()
    assert chosen == 3 * BATCH * rows
    assert seen == 3 * BATCH * SEQ * (SEQ + 1) // 2
    assert kl == pytest.approx(float(sum(want["index_kl"])), abs=1e-4)
    assert absmax > 0


def test_the_trainer_trains_saves_and_restores_it(fitted):
    assert fitted["restored"] == fitted["ended"]
    assert fitted["ended"][0] == 10
    assert train_lib.trace_count("train_step") == 1
    losses = [float(fitted["seen"][s]["loss"]) for s in sorted(fitted["seen"])]
    assert all(np.isfinite(losses)) and len(losses) == 10
    # the indexers' leaves are ordinary leaves: saved, restored, and moved
    # by their own term
    moved = jax.tree_util.tree_map_with_path(
        lambda path, a, b: float(np.abs(a - b).max()),
        *fitted["params"],
    )
    flat = {
        jax.tree_util.keystr(p): v
        for p, v in jax.tree_util.tree_leaves_with_path(moved)
    }
    assert all(v > 0 for k, v in flat.items() if "indexer" in k)
    assert sum("indexer" in k for k in flat) == 3 * 5


def test_fit_books_the_index_event_from_the_step_itself(fitted):
    """One ``compile`` event that says the sparse attention's form, two
    ``index`` events whose numbers are the step's own; the master's ledger
    takes them and the numeric monitor is fed the largest score."""
    from dlrover_tpu.master.speed_monitor import SpeedMonitor

    cfg, taken, seen = fitted["cfg"], fitted["taken"], fitted["seen"]
    (compiled,) = [e[-1] for e in taken if e[0] == "compile"]
    assert compiled["sparse_attention"] == "masked_kernel"
    assert compiled["sparse_block"] == 128
    assert compiled["sparse_backward"] == "one_pass"
    assert compiled["index_select"] == "count32_rows128"
    assert compiled["index_mask_bytes"] == SEQ * SEQ
    events = [e[4] for e in taken if e[1] == "event" and e[0] == "index"]
    assert [e["step"] for e in events] == [5, 10]
    share = np.minimum(
        np.arange(SEQ) + 1, cfg.index_topk
    ).sum() / (SEQ * (SEQ + 1) // 2)
    for event in events:
        vec = np.asarray(
            seen[event["step"]][sparse_attention.STATS_NAME], np.float64
        )
        assert (event["index_layers"], event["shared_layers"]) == (3, 3)
        assert event["topk"] == cfg.index_topk
        assert event["selected_share"] == pytest.approx(share)
        assert event["score_absmax"] == pytest.approx(vec[2])
        assert event["kl"] == pytest.approx(vec[3] / 3)
    # the largest score is what the family feeds the numeric check with
    assert transformer.INDEX_FAMILY.absmax == "score_absmax"
    assert all(np.isfinite(e["score_absmax"]) for e in events)
    monitor = SpeedMonitor()
    monitor.record_health("index", 0, **events[-1])
    ledger = monitor.health_ledger("index")
    assert ledger["shared_layers"] == 3.0 and ledger["kl"] > 0


def test_the_scopes_the_benchmark_reads_reach_the_lowered_text(fitted):
    from benchmark import layers

    text = harness.lowered(fitted["train"]).as_text(debug_info=True)
    assert train_lib.trace_count("train_step") == 1
    for scope in (
        "dense_0/attn/indexer/wq_b", "dense_0/attn/indexer/wk",
        "dense_0/attn/indexer/k_norm", "dense_0/attn/indexer/weights_proj",
        "dense_0/attn/indexer/rope", "dense_0/attn/select/",
        "dense_0/attn/sparse/", "dense_0/attn/index_kl/",
        "index_3/attn/select/", "index_3/attn/index_kl/",
        "reuse_0/attn/sparse/", "reuse_0/attn/q_a", "mtp/block/attn/select/",
        "reuse_0/moe/shared/", "index_3/moe/router",
    ):
        assert scope in text, scope
    # a reusing layer neither scores nor selects nor adds a term
    for scope in ("indexer", "select", "index_kl"):
        assert f"reuse_0/attn/{scope}" not in text
    for metric, scope in (
        ("indexer_ms", "attn/indexer/"), ("index_select_ms", "attn/select/"),
        ("sparse_attn_ms", "attn/sparse/"), ("index_kl_ms", "attn/index_kl/"),
    ):
        assert layers.spec(metric)["params"]["match"] == scope


def test_a_model_without_an_indexer_imports_none_of_it():
    """Nothing new on the other cells' set-up path: the trainer, the
    benchmark's worker and the sibling family's model import neither the
    sparse attention's module nor its kernels nor the choice."""
    code = (
        "import sys\n"
        "from dlrover_tpu.trainer import elastic_trainer\n"
        "import benchmark.worker\n"
        "from dlrover_tpu.models.joyai_llm_flash import "
        "joyai_llm_flash_config\n"
        "from dlrover_tpu.models.transformer import TransformerLM, "
        "kernel_facts\n"
        "import jax, jax.numpy as jnp\n"
        "cfg = joyai_llm_flash_config(num_layers=2, d_model=32, num_heads=2,"
        " d_ff=48, q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,"
        " qk_rope_head_dim=8, v_head_dim=8, num_experts=4, top_k=2,"
        " moe_d_ff=16, vocab_size=128, dtype=jnp.float32)\n"
        "tokens = jnp.zeros((1, 16), jnp.int32)\n"
        "m = TransformerLM(cfg)\n"
        # traced, not run: what a walk of the model imports is the
        # property, and op by op the walk is twenty seconds of compiling
        "jax.eval_shape(lambda: m.apply("
        "m.init(jax.random.PRNGKey(0), tokens), tokens))\n"
        "assert kernel_facts(cfg, 16)['sparse_attention'] == 'none'\n"
        "bad = [n for n in sys.modules if n.endswith(('sparse_attention',"
        " 'ops.index_select', 'ops.sparse_flash_attention',"
        " 'models.glm_dsa', 'references.glm_dsa'))]\n"
        "assert not bad, bad\n"
        "print('clean')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO},
    )
    assert out.returncode == 0 and "clean" in out.stdout, out.stderr[-2000:]
