"""Solar-Open2's parts through the rest of the system, one small CPU test
each: the train step's first loss against the reference, the
``linear_attn`` and ``compile`` events' new fields from the step's own sown
stats (``g_min``, ``past_bound_share``; which form of the rule runs), the
scopes the benchmark reads, what ``flash_only`` keeps of the exact-form
kernels, and that a model without such a layer imports none of this.
(Sizes and weights are ``tests/test_solar_open_reference.py``'s:
``numerics``.)"""

import functools
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import reference_harness as harness
import test_solar_open_reference as numerics
from dlrover_tpu.models import linear_attention
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.transformer import TransformerLM
from dlrover_tpu.ops import kda as kda_lib
from dlrover_tpu.trainer import train_lib
from test_solar_open_reference import config, params, tokens  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, BATCH, VOCAB = 32, 8, numerics.VOCAB


# ONE step program for the file: the trainer's, which the first-loss case
# runs on the trainer's batch (``train_step`` is traced once)
pytestmark = pytest.mark.usefixtures("one_step_program")


def cell_config():
    """The small model under the policy the cell runs (the flash and KDA
    outputs kept); its own init spreads the decay's bias past the split
    form's floor (the field shapes the init alone, not the step)."""
    return config(
        max_seq_len=SEQ, linear_decay_init_std=8.0, attention_impl="flash",
        remat="flash_only", flash_block_q=8, flash_block_kv=8,
    )


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    """Ten steps at ``report_every=5``."""
    return harness.fit(
        cell_config(), str(tmp_path_factory.mktemp("solar")), seq=SEQ,
        batch=BATCH,
    )


def test_the_train_step_s_first_loss_is_the_reference_s(params, fitted):
    """The normal path: the trainer's compiled step under the policy the
    cell runs, on the trainer's batch and the reference file's weights."""
    cfg = cell_config()
    tokens = harness.tokens(1, BATCH, SEQ, VOCAB)
    _, metrics = harness.first_step(fitted["train"], params, tokens)
    want = numerics.CHECK.reference("forward", cfg, params, tokens)
    assert abs(float(metrics["loss"]) - float(want["nll"].mean())) <= 1e-4
    assert float(metrics["aux_loss"]) == 0.0
    pairs = float(np.asarray(metrics[moe_lib.SHARE_STATS_NAME])[0])
    here = np.mean([float(c[8:16].sum() / c.sum()) for c in want["counts"]])
    assert pairs == pytest.approx(here, rel=1e-5) and 0 < pairs < 1
    vec = np.asarray(metrics[linear_attention.STATS_NAME], np.float64)
    alpha, beta, absmax = linear_attention.split_stats(vec)
    min_alpha, g_min, past = vec[3:]
    assert 0 < min_alpha <= alpha <= 1 and 0 < absmax < 100
    # beta is doubled, and the seeded gate goes past the split form's floor
    assert 0.5 < beta < 1.5
    assert g_min < 2 * kda_lib.SPLIT_FLOOR and 0.005 < past < 1


def test_fit_books_the_new_fields_from_the_step_itself(fitted):
    """Ten steps at ``report_every=5``: two ``linear_attn`` events carrying
    the step's own numbers, one ``compile`` event that says which form of
    the rule runs; one trace of the step."""
    taken, seen = fitted["taken"], fitted["seen"]
    events = [e for e in taken if e[1] == "event"]
    compiled = [e[-1] for e in taken if e[0] == "compile"]
    assert [e["kda"] for e in compiled] == ["xla_exact"]
    linear = [e[4] for e in events if e[0] == "linear_attn"]
    assert [e["step"] for e in linear] == [5, 10]
    for event in linear:
        vec = np.asarray(
            seen[event["step"]][linear_attention.STATS_NAME], np.float64
        )
        assert event["rule"] == "kda" and event["layers"] == 1
        assert event["chunk"] == kda_lib.CHUNK == 128
        assert event["min_alpha"] == pytest.approx(float(vec[3]))
        assert event["g_min"] == pytest.approx(float(vec[4]))
        assert event["past_bound_share"] == pytest.approx(float(vec[5]))
        assert event["g_min"] < kda_lib.SPLIT_FLOOR
        assert 0 < event["past_bound_share"] < 1
        assert 0.5 < event["mean_beta"] < 1.5
        assert 0 < event["state_absmax"] < 1e3
    assert train_lib.trace_count("train_step") == 1


def test_the_compile_event_says_which_form_of_the_rule_runs():
    from dlrover_tpu.models import transformer
    from dlrover_tpu.models.ling_flash import ling_flash_config

    def facts(cfg, seq=SEQ):
        return transformer.kernel_facts(cfg, seq)

    assert facts(config())["kda"] == "xla_exact"
    # a bounded gate takes the split form, whatever else the model is
    assert facts(config(linear_decay_bound=-5.0))["kda"] == "xla"
    published = numerics.solar_open2_config(
        num_layers=4, experts_held=20, vocab_size=24576,
        attention_impl="flash",
    )
    assert facts(published, 16384)["kda"] == "kernel_exact"
    assert facts(published, 16384)["short_conv"] == "kernel"
    assert facts(published, 16384)["flash_backward"] == "fused"
    # Ling's layers (the safe gate) run what they ran
    ling = ling_flash_config(num_layers=7, first_k_dense=1, experts_held=32)
    assert facts(ling, 8192)["kda"] == "kernel"


def test_the_scopes_the_benchmark_reads_reach_the_compiled_text(tokens):
    cfg = config()
    weights = numerics.share(cfg)
    text = jax.jit(
        lambda p, t: TransformerLM(cfg).apply({"params": p}, t)[0]
    ).lower(weights, tokens[0]).as_text(debug_info=True)
    for scope in (
        "linear_attn/qkv", "linear_attn/conv", "linear_attn/gates",
        "linear_attn/kda", "linear_attn/out_norm", "linear_attn/wo",
        "attn/query", "attn/key", "attn/value", "attn/gate", "attn/out",
        "moe/router", "moe/shared",
    ):
        assert scope in text, scope
    # the low-rank pairs are the gates', both of them; nothing rotates
    assert "linear_attn/g_proj" not in text and "attn/rope" not in text
    assert "softplus" in text or "log_plus_one" in text or "logistic" in text


@functools.cache
def gradient_program(remat):
    """The mean token loss's gradient of one KDA layer with heads of
    128 / 128 (the exact-form kernels), traced against the shapes of the
    config's own init (no weight is made)."""
    cfg = config(**numerics.CASES["kda_kernel_widths"], remat=remat,
                 attention_impl="flash", flash_block_q=8, flash_block_kv=8)
    inputs, targets = numerics.seeded()[0]
    model = TransformerLM(cfg)
    weights = jax.eval_shape(model.init, jax.random.PRNGKey(0), inputs)

    def loss(p):
        return harness.token_nll(model.apply(p, inputs)[0], targets).mean()

    return jax.make_jaxpr(jax.grad(loss))(weights)


@pytest.mark.parametrize("remat,runs", [("flash_only", 1), ("full", 2)])
def test_the_exact_forward_kernel_runs_once_where_its_states_are_kept(
    remat, runs
):
    program = gradient_program(remat)
    calls = harness.pallas_calls(program.jaxpr)
    assert calls.count("kda_bwd") == 1
    assert calls.count("kda_fwd") == runs
    if remat == "flash_only":
        assert "name=kda_out" in str(program)
        assert "name=kda_states" in str(program)


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
    assert cfg.num_linear_layers == 1 and cfg.num_full_layers == 1
    # a head-wise gate would hold a head's width less a head
    other = config(attention_gate="head_wise")
    assert cfg.num_params() - other.num_params() == (
        cfg.d_model * cfg.num_heads * (cfg.head_dim - 1)
    )


def test_a_model_without_such_a_layer_imports_none_of_it():
    """Nothing new on the other cells' set-up path: the trainer, the
    benchmark's worker and Ling's model import neither the model file nor
    its reference."""
    code = (
        "import sys\n"
        "from dlrover_tpu.trainer import elastic_trainer\n"
        "import benchmark.worker\n"
        "from dlrover_tpu.models.ling_flash import ling_flash_config\n"
        "from dlrover_tpu.models import transformer\n"
        "transformer.kernel_facts(ling_flash_config(), 8192)\n"
        "bad = [n for n in sys.modules if n.endswith(('models.solar_open',"
        " 'references.solar_open', 'flops_kda_gqa_moe'))]\n"
        "assert not bad, bad\n"
        "print('clean')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO},
    )
    assert out.returncode == 0 and "clean" in out.stdout, out.stderr[-2000:]
