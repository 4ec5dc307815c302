"""Mellum2's parts through the rest of the system, one small CPU test each:
the train step's first loss against the reference under the policy the
cell runs, the ``attn``, ``moe`` and ``compile`` events' fields from the
step's own sown stats through the servicer to the master's ledger, the
scopes the benchmark reads, what ``Attention`` refuses under a window, and
that the parameters held are the parameters counted.  (Sizes and weights
are ``tests/test_mellum_reference.py``'s: ``numerics``.)

The file's step program is the trainer's, traced once; its last case,
``test_fit_books_the_attn_event_under_accumulation``, needs the one other:
two microbatches a step are another step program."""

import json

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_harness as harness
import test_mellum_reference as numerics
from dlrover_tpu.models import attention as attention_lib
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models import transformer as transformer_lib
from dlrover_tpu.models.transformer import TransformerLM
from dlrover_tpu.trainer import train_lib
from test_mellum_reference import config, share, tokens  # noqa: F401

SEQ, BATCH, VOCAB = 32, 8, numerics.VOCAB
ONE_PERIOD = dict(num_layers=4)

# ONE step program for the file's cases, the trainer's, but for the case
# under accumulation (the file's last): two microbatches are another step
pytestmark = pytest.mark.usefixtures("one_step_program")


def cell_config():
    """One period under the policy the cell runs, both kinds through the
    flash kernels."""
    return config(
        max_seq_len=SEQ, attention_impl="flash", remat="flash_only",
        flash_block_q=8, flash_block_kv=8, **ONE_PERIOD
    )


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    """Ten steps of one period at ``report_every=5``."""
    return harness.fit(
        cell_config(), str(tmp_path_factory.mktemp("mellum")), seq=SEQ,
        batch=BATCH,
    )


def test_the_train_step_s_first_loss_is_the_reference_s(fitted):
    """The normal path: the trainer's compiled step, on its batch."""
    cfg = cell_config()
    params = share(cfg)
    tokens = harness.tokens(1, BATCH, SEQ, VOCAB)
    _, metrics = harness.first_step(fitted["train"], params, tokens)
    want = numerics.CHECK.reference("forward", cfg, params, tokens)
    assert abs(float(metrics["loss"]) - float(want["nll"].mean())) <= 1e-4
    assert float(metrics["aux_loss"]) == pytest.approx(
        cfg.moe_aux_weight * float(want["balance"]), rel=1e-4
    )
    pairs = float(np.asarray(metrics[moe_lib.SHARE_STATS_NAME])[0])
    assert 0.1 < pairs < 0.5            # 16 of 64 experts held
    full, sliding = np.asarray(metrics[attention_lib.STATS_NAME])
    assert full > 0 and sliding > 0


def test_the_score_bound_bounds_the_scores_and_carries_the_factor(rng):
    q = jnp.asarray(rng.normal(size=(2, 24, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 24, 2, 16)), jnp.float32)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q.reshape(2, 24, 2, 2, 16), k
    ) * 0.25
    exact = float(jnp.abs(scores).max())
    bound = float(attention_lib.score_bound(q, k, 0.25))
    assert exact <= bound <= 3 * exact
    assert float(attention_lib.score_bound(1.3 * q, 1.3 * k, 0.25)) == (
        pytest.approx(1.69 * bound, rel=1e-5)
    )


def test_fit_books_the_attn_event_from_the_step_itself(fitted):
    """Ten steps at ``report_every=5``: two ``attn`` and two ``moe`` events
    carrying the step's own numbers, one ``compile`` event that counts each
    kind's blocks; the servicer hands the ``attn`` event to the master's
    ledger."""
    from dlrover_tpu.master.speed_monitor import SpeedMonitor

    taken, seen = fitted["taken"], fitted["seen"]
    events = [e for e in taken if e[1] == "event"]
    (compiled,) = [e[-1] for e in taken if e[0] == "compile"]
    blocks = compiled["flash_blocks"]
    assert sorted(blocks) == ["full_attention", "sliding_attention"]
    # 32 tokens in blocks of 8: ten live causal blocks of sixteen; under a
    # window of 12 a q block sees itself and one or two before it
    assert blocks["full_attention"] == dict(
        live=10, interior=6, edge=4, dead=6, grid=16, strip=0,
        live_share=10 / 16, backward="fused",
    )
    band = blocks["sliding_attention"]
    assert (band["live"], band["dead"], band["grid"]) == (9, 7, 12)
    assert band["live_share"] == 0.75 and band["interior"] == 0
    # ... and, of the banded kernels alone: blocks of 8 hold no strip, so
    # the 318 live pairs of the band are worked as nine whole blocks
    assert (band["lower_strip"], band["lockstep"]) == (0, True)
    assert band["tile_live_share"] == 318 / (9 * 64)
    assert compiled["flash_backward"] == "fused"
    attn = [e[4] for e in events if e[0] == "attn"]
    moe = [e[4] for e in events if e[0] == "moe"]
    assert [e["step"] for e in attn] == [5, 10] == [e["step"] for e in moe]
    for event in attn:
        vec = np.asarray(
            seen[event["step"]][attention_lib.STATS_NAME], np.float64
        )
        assert (event["full_layers"], event["sliding_layers"]) == (1, 3)
        assert event["window"] == 12
        assert event["full_score_bound"] == pytest.approx(float(vec[0]))
        assert event["sliding_score_bound"] == pytest.approx(float(vec[1]))
        assert event["score_bound"] == pytest.approx(float(vec.max()))
    for event in moe:
        assert event["experts"] == 64 and event["held"] == 16
        assert event["drop_fraction"] == 0.0
        assert len(json.loads(event["load"])) == 64
    assert train_lib.trace_count("train_step") == 1
    # the event as it is shipped is what the master's ledger takes
    monitor = SpeedMonitor()
    monitor.record_health("attn", 0, **attn[-1])
    ledger = monitor.health_ledger("attn")
    assert ledger["score_bound"] == attn[-1]["score_bound"]
    assert ledger["full_layers"] + ledger["sliding_layers"] == 4


def test_a_model_without_windowed_layers_books_what_it_booked():
    """The ``compile`` event's ``flash_blocks`` keeps its four counts, and
    no ``attn_stats`` is sown."""
    from dlrover_tpu.models.transformer import TransformerConfig

    facts = transformer_lib.kernel_facts
    plain = TransformerConfig(attention_impl="flash")
    assert facts(plain, 4096)["flash_blocks"] == dict(
        dead=6, interior=6, diagonal=4, strip=256
    )
    published = numerics.mellum_config(
        num_layers=8, experts_held=16, vocab_size=24576,
        attention_impl="flash",
    )
    blocks = facts(published, 32768)["flash_blocks"]
    assert blocks["full_attention"]["live"] == 528
    band = blocks["sliding_attention"]
    assert (band["live"], band["grid"], band["backward"]) == (
        63, 64, "fused"
    )
    assert band["live_share"] == 63 / 64
    # the lower-edge blocks are strips as the diagonal ones: 10 of 16
    # sub-tiles each, 31.5 blocks' live pairs of 39.4 worked (of 51 while
    # the lower edge was a masked square); said of the banded kernels only
    assert (band["strip"], band["lower_strip"]) == (256, 256)
    assert band["tile_live_share"] == pytest.approx(0.8, abs=1e-4)
    assert band["lockstep"] is True
    assert set(band) - set(blocks["full_attention"]) == {
        "lower_strip", "tile_live_share", "lockstep"
    }
    # rows of 2,304 are 18 lane tiles: padded at the fetch-and-sum kernel's
    # door; the 896-wide strips stay whole-K
    assert facts(published, 32768)["row_moves"] == (
        "kernel_live_padded"
    )
    assert facts(published, 32768)["gmm_strips"] == "resident"
    # ... and each weight gradient is ONE tile under the limit its call asks
    # for (2304 x 896 was seven tiles of 128 lanes until PR 55, and read
    # every row block seven times)
    assert facts(published, 32768)["gmm_dw_tiles"] == (
        "into:1x1 out_of:1x1"
    )


def test_the_scopes_the_benchmark_reads_reach_the_compiled_text(tokens):
    cfg = config(**ONE_PERIOD)
    weights = share(cfg)
    text = jax.jit(
        lambda p, t: TransformerLM(cfg).apply({"params": p}, t)[0]
    ).lower(weights, tokens[0]).as_text(debug_info=True)
    for scope in (
        "sliding_0/attn/query", "sliding_2/attn/out", "full_3/attn/key",
        "full_3/attn/out", "sliding_1/moe/router", "full_3/moe/router",
    ):
        assert scope in text, scope
    assert "linear_attn" not in text and "moe/shared" not in text


def test_attention_refuses_a_window_where_nothing_holds_it(tokens):
    """``cached_attention`` keeps no ring of ``W`` rows, and a sequence
    split across chips splits the band."""
    x = jnp.zeros((1, 16, 32), jnp.float32)

    def layer(**kw):
        return attention_lib.Attention(
            num_heads=4, num_kv_heads=2, head_dim=8, window=4,
            dtype=jnp.float32, **kw
        )

    for kw in (dict(decode=True, cache_len=16), dict(attention_impl="ring")):
        with pytest.raises(ValueError, match="a window of 4 keys runs on"):
            layer(**kw).init(jax.random.PRNGKey(0), x)
    out, _ = layer().init_with_output(jax.random.PRNGKey(0), x)
    assert out.shape == x.shape


@pytest.mark.parametrize("score_std", [0.0, 1.0, 4.0])
def test_the_seeded_scores_spread_as_the_config_says(score_std):
    """``attn_init_score_std`` is the spread of the seeded ``q k^T /
    sqrt(head_dim)`` over unit rows; 0 leaves the default initialiser's
    kernels (whose fan-in counts the heads) bit for bit, and the values'
    and the output's kernels are the default's at every setting."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 48, 256), jnp.float32)

    def kernels(**kw):
        layer = attention_lib.Attention(
            num_heads=8, num_kv_heads=2, head_dim=64, dtype=jnp.float32,
            use_rope=False, **kw
        )
        params = nn.meta.unbox(layer.init(jax.random.PRNGKey(1), x))
        return params["params"]

    plain, seeded = kernels(), kernels(init_score_std=score_std)
    for name in ("value", "out"):
        assert (plain[name]["kernel"] == seeded[name]["kernel"]).all()
    same = bool((plain["query"]["kernel"] == seeded["query"]["kernel"]).all())
    assert same == (score_std == 0.0)
    if score_std:
        q = jnp.einsum("bsd,dhk->bshk", x, seeded["query"]["kernel"])
        k = jnp.einsum("bsd,dhk->bshk", x, seeded["key"]["kernel"])
        scores = jnp.einsum("bqhk,bshk->bhqs", q, jnp.repeat(k, 4, 2)) / 8.0
        assert float(scores.std()) == pytest.approx(score_std, rel=0.1)
        with pytest.raises(ValueError, match="the fused qkv kernel has one"):
            attention_lib.Attention(
                num_heads=4, num_kv_heads=4, head_dim=8,
                init_score_std=score_std,
            ).init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 32)))
    cfg = config(attn_init_score_std=score_std)
    assert attention_lib.from_config(cfg).init_score_std == score_std


def test_num_params_counts_what_is_held():
    cfg = config()
    params = share(cfg)
    held = sum(leaf.size for leaf in jax.tree.leaves(params))
    norms = sum(
        leaf.size
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
        if path[-2].key in ("ln_attn", "ln_mlp", "ln_final")
    )
    assert cfg.num_params() == held - norms
    assert (cfg.num_sliding_layers, cfg.num_full_layers) == (6, 2)


def test_fit_books_the_attn_event_under_accumulation(tmp_path):
    """Two microbatches a step: the score bounds are folded over them as
    over the layers, and the ``attn`` event is booked as at one (until PR
    56 the accumulating step dropped ``attn_stats``, and none was).  The
    file's second step program: the microbatch engine is another step."""
    # two rows a device, so that a step can be two microbatches
    fit = harness.fit(
        cell_config(), str(tmp_path), seq=SEQ, batch=2 * BATCH, grad_accum=2
    )
    assert fit["grad_accum"] == 2
    taken, seen = fit["taken"], fit["seen"]
    attn = [e[4] for e in taken if e[:2] == ("attn", "event")]
    assert [e["step"] for e in attn] == [5, 10]
    for event in attn:
        vec = np.asarray(
            seen[event["step"]][attention_lib.STATS_NAME], np.float64
        )
        assert np.isfinite(event["score_bound"])
        assert event["score_bound"] == pytest.approx(float(vec.max()))
        assert min(vec) > 0
    assert train_lib.trace_count("train_step") == 2
