"""Ling-3.0-flash's parts through the rest of the system, one small CPU
test each: the train step's first loss and its router-bias rule against
the reference (512-less: 32 loads in 4 groups), the ``linear_attn``,
``moe`` and ``compile`` events' new fields from the step's own sown stats,
the scopes the benchmark reads, what ``flash_only`` keeps, and that a model
without a KDA layer imports and traces none of this.  (Sizes and weights
are ``tests/test_ling_flash_reference.py``'s: ``numerics``.)"""

import functools
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import reference_harness as harness
import test_ling_flash_reference as numerics
from dlrover_tpu.models import linear_attention
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.references import ling_flash as ref
from dlrover_tpu.models.transformer import TransformerLM
from dlrover_tpu.ops import kda as kda_lib
from dlrover_tpu.ops import remat_policy
from dlrover_tpu.trainer import train_lib
from test_ling_flash_reference import config, params, tokens  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, BATCH, VOCAB = 32, 8, numerics.VOCAB
biases = harness.router_biases


# ONE step program for the file: the trainer's, which the first-loss case
# runs on the trainer's batch (``train_step`` is traced once)
pytestmark = pytest.mark.usefixtures("one_step_program")


def cell_config():
    """The small model under the policy the cell runs (the flash and KDA
    outputs kept)."""
    return config(
        max_seq_len=SEQ, attention_impl="flash", remat="flash_only",
        flash_block_q=8, flash_block_kv=8,
    )


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    """Ten steps at ``report_every=5``, each report read as its step ends
    (``metrics_lag=0``)."""
    return harness.fit(
        cell_config(), str(tmp_path_factory.mktemp("ling")), seq=SEQ,
        batch=BATCH, metrics_lag=0,
    )


def test_the_train_step_s_first_loss_and_bias_move_are_the_reference_s(
    params, fitted
):
    """The normal path: the trainer's compiled step under the policy the
    cell runs, on the trainer's batch.  Each expert layer's bias moves by
    the unchanged rule on that layer's own counts over ALL the experts, in
    their groups."""
    cfg = cell_config()
    tokens = harness.tokens(1, BATCH, SEQ, VOCAB)
    before = biases(params)
    new_state, metrics = harness.first_step(fitted["train"], params, tokens)
    want = numerics.CHECK.reference("forward", cfg, params, tokens)
    assert abs(float(metrics["loss"]) - float(want["nll"].mean())) <= 1e-4
    assert float(metrics["aux_loss"]) == 0.0
    moved = biases(new_state.params)
    assert sorted(moved) == [
        "blocks/full_1/moe/router_bias", "blocks/linear_0/moe/router_bias",
        "blocks/linear_2/moe/router_bias",
    ]
    # the trunk's layers in order: linear_0, full_1, linear_2
    for slot, counts in zip(("linear_0", "full_1", "linear_2"), want["counts"]):
        name = f"blocks/{slot}/moe/router_bias"
        np.testing.assert_allclose(
            moved[name][0],
            ref.bias_rule(before[name][0], counts, cfg.router_bias_rate),
            atol=1e-7, err_msg=name,
        )
    pairs, bias_absmax, tokens_here = np.asarray(
        metrics[moe_lib.SHARE_STATS_NAME]
    )
    here = np.mean([float(c[8:16].sum() / c.sum()) for c in want["counts"]])
    assert pairs == pytest.approx(here, rel=1e-5)
    assert 0 < pairs < tokens_here <= 0.75 and bias_absmax > 0
    alpha, beta, absmax = linear_attention.split_stats(
        np.asarray(metrics[linear_attention.STATS_NAME])
    )
    min_alpha = float(np.asarray(metrics[linear_attention.STATS_NAME])[3])
    assert 0 < min_alpha <= alpha <= 1 and 0 < beta < 1 and 0 < absmax < 100


@pytest.mark.parametrize("metrics_lag", [0, 4])
def test_fit_books_the_new_fields_from_the_step_itself(
    metrics_lag, tmp_path, fitted
):
    """Ten steps at ``report_every=5``: two ``linear_attn`` and two ``moe``
    events carrying the step's own numbers, one ``compile`` event that
    says how the rule runs; one trace of the step and no second forward."""
    fit = fitted if not metrics_lag else harness.fit(
        cell_config(), str(tmp_path), seq=SEQ, batch=BATCH,
        metrics_lag=metrics_lag,
    )
    taken, seen = fit["taken"], fit["seen"]
    events = [e for e in taken if e[1] == "event"]
    compiled = [e[-1] for e in taken if e[0] == "compile"]
    if not metrics_lag:
        # (the second case is handed the first's program: nothing compiles)
        assert [e["kda"] for e in compiled] == ["xla"]
        assert compiled[0]["short_conv"] == "xla"
    linear = [e[4] for e in events if e[0] == "linear_attn"]
    moe = [e[4] for e in events if e[0] == "moe"]
    assert [e["step"] for e in linear] == [5, 10] == [e["step"] for e in moe]
    for event in linear:
        vec = np.asarray(
            seen[event["step"]][linear_attention.STATS_NAME], np.float64
        )
        assert event["rule"] == "kda" and event["layers"] == 3
        assert event["chunk"] == kda_lib.CHUNK == 128
        assert event["mean_alpha"] == pytest.approx(float(vec[0]))
        assert event["min_alpha"] == pytest.approx(float(vec[3]))
        assert 0 < event["min_alpha"] <= event["mean_alpha"] <= 1
        assert 0 < event["state_absmax"] < 1e3
    for event in moe:
        share = np.asarray(seen[event["step"]][moe_lib.SHARE_STATS_NAME])
        assert event["groups"] == 4 and event["topk_group"] == 2
        assert event["experts"] == 32 and event["held"] == 8
        assert event["pairs_here"] == pytest.approx(float(share[0]))
        assert event["tokens_here"] == pytest.approx(float(share[2]))
        assert event["pairs_here"] < event["tokens_here"] <= 0.75
        assert event["drop_fraction"] == 0.0
        assert len(json.loads(event["load"])) == 32
    assert train_lib.trace_count("train_step") == 1


def test_the_compile_event_says_which_rule_runs():
    from dlrover_tpu.models import transformer
    from dlrover_tpu.models.olmo_hybrid import olmo_hybrid_config
    from dlrover_tpu.models.transformer import TransformerConfig

    def facts(cfg, seq=SEQ):
        return transformer.kernel_facts(cfg, seq)

    assert facts(config())["kda"] == "xla"
    wide = config(
        linear_num_heads=2, linear_key_head_dim=128, linear_value_head_dim=128
    )
    assert facts(wide)["kda"] == "kernel"
    # the scalar rule's layers and a model without linear layers: none
    hybrid = olmo_hybrid_config(
        num_layers=4, d_model=32, num_heads=4, linear_num_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=16, vocab_size=128,
    )
    assert facts(hybrid)["kda"] == "none"
    assert facts(TransformerConfig())["kda"] == "none"
    # the convolution's row is [q | k | v] alone: at the published widths
    # on 8192 tokens it runs as the kernel, as the hybrid's does
    published = numerics.ling_flash_config(
        num_layers=7, first_k_dense=1, experts_held=32
    )
    assert facts(published, 8192)["short_conv"] == "kernel"
    assert facts(published, 8192)["kda"] == "kernel"
    # rows of 2,560 are 20 lane tiles: padded to 24 at the fetch-and-sum
    # kernel's door, and under the cell's share (32 of 512 experts) only
    # the pairs that have a row here are fetched
    assert facts(published, 8192)["row_moves"] == (
        "kernel_live_padded"
    )


def test_the_scopes_the_benchmark_reads_reach_the_compiled_text(tokens):
    cfg = config()
    weights = numerics.share(cfg)
    text = jax.jit(
        lambda p, t: TransformerLM(cfg).apply({"params": p}, t)[0]
    ).lower(weights, tokens[0]).as_text(debug_info=True)
    for scope in (
        "linear_attn/qkv", "linear_attn/conv", "linear_attn/gates",
        "linear_attn/kda", "linear_attn/out_norm", "linear_attn/wo",
        "attn/q_b", "attn/kv_a", "attn/kv_b", "attn/rope", "attn/gate",
        "attn/wo", "moe/router", "moe/shared",
    ):
        assert scope in text, scope
    assert "attn/q_a" not in text
    assert "linear_attn/delta_rule" not in text
    # the group limit is the router's work
    assert "moe/router" in text and "argmax" in text
    assert "top_k" not in text


def traced_gradient(cfg, inputs, targets):
    """The mean token loss's gradient, traced against the shapes of
    ``cfg``'s own init (no weight is made)."""
    model = TransformerLM(cfg)
    weights = jax.eval_shape(model.init, jax.random.PRNGKey(0), inputs)

    def loss(p):
        return harness.token_nll(model.apply(p, inputs)[0], targets).mean()

    return jax.make_jaxpr(jax.grad(loss))(weights)


@functools.cache
def gradient_program(remat, kernel_widths=True):
    """``traced_gradient`` of the small model under ``remat``: with heads of
    128 / 128, which take the kernels (a dense layer ahead of a trunk of
    one: two KDA layers), or as it is (heads of 16: the ``jax.numpy``
    form)."""
    widths = numerics.KDA_KERNEL_WIDTHS if kernel_widths else {}
    cfg = config(**widths, attention_impl="flash", remat=remat,
                 flash_block_q=8, flash_block_kv=8)
    return traced_gradient(cfg, *numerics.seeded()[0])


def test_flash_only_keeps_the_rule_s_output_under_its_own_name():
    kept = remat_policy.resolve("flash_only").saved_names
    assert {"kda_out", "kda_states", "delta_out"} <= set(kept)
    text = str(gradient_program("flash_only", kernel_widths=False))
    assert "name=kda_out" in text and "name=flash_out" in text
    # the states are the kernel's: heads of 16 run the ``jax.numpy`` form
    assert "name=kda_states" not in text
    text = str(gradient_program("flash_only"))
    assert "name=kda_out" in text and "name=kda_states" in text


@pytest.mark.parametrize("remat,runs", [("flash_only", 1), ("full", 2)])
def test_the_forward_kernel_runs_once_a_layer_where_its_states_are_kept(
    remat, runs
):
    """Under ``flash_only`` the layer's remat keeps both of the forward
    kernel's outputs the backward reads, so the replayed kernel has no live
    output and is dropped; a policy that keeps no names runs it twice."""
    calls = harness.pallas_calls(gradient_program(remat).jaxpr)
    assert calls.count("kda_bwd") == 2
    assert calls.count("kda_fwd") == 2 * runs


def test_a_model_with_the_scalar_rule_emits_neither_kda_name():
    """Only a program with a KDA layer changes: the hybrid's step keeps
    ``delta_out`` alone, as the chip chose for it (ops/remat_policy.py)."""
    from dlrover_tpu.models.olmo_hybrid import olmo_hybrid_config

    cfg = olmo_hybrid_config(
        num_layers=4, d_model=32, num_heads=4, linear_num_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=16, vocab_size=128,
        attention_impl="flash", remat="flash_only",
        flash_block_q=8, flash_block_kv=8,
    )
    text = str(traced_gradient(cfg, *harness.tokens(0, 2, 16, 128)))
    assert "name=delta_out" in text and "name=flash_out" in text
    assert "kda_out" not in text and "kda_states" not in text


def test_num_params_counts_what_is_held():
    cfg = config()
    weights = numerics.share(cfg)
    held = sum(leaf.size for leaf in jax.tree.leaves(weights))
    norms = sum(
        leaf.size
        for path, leaf in jax.tree_util.tree_leaves_with_path(weights)
        if path[-2].key in ("ln_attn", "ln_mlp", "ln_final")
    )
    # the layer norms are the approximation num_params() always made
    assert cfg.num_params() == held - norms
    assert cfg.num_linear_layers == 3
    assert [cfg.layer_kind(i) for i in range(4)] == [
        "linear_attention", "linear_attention", "full_attention",
        "linear_attention",
    ]


def test_a_model_without_a_kda_layer_imports_none_of_it():
    """Nothing new on the other cells' set-up path: the trainer, a dense
    model and the hybrid's scalar rule import neither ``ops/kda.py`` nor
    the model file."""
    code = (
        "import sys\n"
        "from dlrover_tpu.trainer import elastic_trainer\n"
        "import benchmark.worker\n"
        "from dlrover_tpu.models.olmo_hybrid import olmo_hybrid_config\n"
        "from dlrover_tpu.models.transformer import TransformerLM\n"
        "import jax, jax.numpy as jnp\n"
        "cfg = olmo_hybrid_config(num_layers=4, d_model=32, num_heads=4,"
        " linear_num_heads=4, linear_key_head_dim=8,"
        " linear_value_head_dim=16, vocab_size=128, dtype=jnp.float32)\n"
        "tokens = jnp.zeros((1, 16), jnp.int32)\n"
        "m = TransformerLM(cfg)\n"
        # traced, not run: what a walk of the model imports is the
        # property, and op by op the walk is twenty seconds of compiling
        "jax.eval_shape(lambda: m.apply("
        "m.init(jax.random.PRNGKey(0), tokens), tokens))\n"
        "bad = [n for n in sys.modules if n.endswith(('ops.kda',"
        " 'models.ling_flash', 'references.ling_flash'))]\n"
        "assert not bad, bad\n"
        "print('clean')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO},
    )
    assert out.returncode == 0 and "clean" in out.stdout, out.stderr[-2000:]
