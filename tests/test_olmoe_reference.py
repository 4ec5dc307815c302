"""OLMoE-1B-7B through the program against the plain reference
(``dlrover_tpu/models/references/olmoe.py``), at a small size on the CPU.

Seeded weights; every norm scale (the QK-norm's too) is moved off one, so
that a scale applied to the wrong axis or left out shows.  Tolerances, all
float32 against float32: the program and the reference order their sums
differently (fused QKV kernel, grouped rows, gathers), which moves a
token's loss by about 1e-6 and a gradient by about 1e-7 of its size; the
three mistakes this model invites (renormalised gates, no QK-norm, one
dropped (token, expert) pair) move a token's loss by 5e-3 to 1, so 2e-4
lies a factor of twenty or more from either.
"""

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_harness as harness
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.olmoe import olmoe_config
from dlrover_tpu.models.references import olmoe as reference
from dlrover_tpu.models.transformer import TransformerConfig, TransformerLM
from dlrover_tpu.ops.grouped_matmul import grouped_matmul

NLL_ATOL = 2e-4
LOSS_ATOL = 2e-5
# a gradient leaf against the reference's: |difference| <= GRAD_ATOL +
# GRAD_RTOL x the leaf's largest entry
GRAD_RTOL, GRAD_ATOL = 2e-4, 1e-7

SEQ, BATCH, VOCAB = 32, 2, 256


def config(**overrides):
    base = dict(
        vocab_size=VOCAB, num_layers=2, d_model=64, num_heads=4, d_ff=32,
        num_experts=8, top_k=4, max_seq_len=SEQ, dtype=jnp.float32,
        param_dtype=jnp.float32, attention_impl="xla", flash_block_q=16,
        flash_block_kv=16,
    )
    base.update(overrides)
    return olmoe_config(**base)


CHECK = harness.Harness(
    reference, loss_atol=LOSS_ATOL, grad_atol=GRAD_ATOL,
    grad_rtol=GRAD_RTOL,
)


def move(name, leaf, draw):
    """Every ``scale`` moved off one and the router sharpened so that the
    eight probabilities differ."""
    if name.endswith("['scale']"):
        return leaf * (1 + 0.3 * draw(leaf.shape))
    if "['router']" in name:
        return leaf * 4
    return leaf


@pytest.fixture(scope="module")
def tokens():
    return harness.tokens(11, BATCH, SEQ, VOCAB)


@pytest.fixture(scope="module")
def params(tokens):
    return harness.init(config(), tokens[0], seed=5, move=move)


IMPLS = pytest.mark.parametrize("attention_impl", ["xla", "flash"])


@IMPLS
def test_loss_and_every_gradient_match_the_reference(
    attention_impl, params, tokens
):
    cfg = config(attention_impl=attention_impl, remat=(
        "flash_only" if attention_impl == "flash" else "none"
    ))
    CHECK.loss_and_every_gradient_match(cfg, params, tokens)
    # the auxiliary term is in both: without it the losses part by this
    aux = CHECK.loss_and_grads(cfg, params, tokens)[1][1]
    assert float(aux) > 100 * LOSS_ATOL


@IMPLS
def test_token_nll_matches_the_reference(attention_impl, params, tokens):
    cfg = config(attention_impl=attention_impl)
    assert CHECK.nll_gap(cfg, params, tokens) <= NLL_ATOL


def test_the_train_step_s_first_loss_is_the_reference_s(params, tokens):
    """The normal path: ``build_sharded_train``'s compiled step."""
    cfg = config(attention_impl="flash", remat="flash_only")
    train = harness.built(cfg, batch=BATCH, seq=SEQ)
    _, metrics = harness.first_step(train, params, tokens)
    want = CHECK.reference("token_nll", cfg, params, tokens).mean()
    assert abs(float(metrics["loss"]) - float(want)) <= LOSS_ATOL


def _drop_one_pair(monkeypatch):
    """The program with one routed (token, expert) pair's row emptied."""
    plan_of = moe_lib._dispatch_plan

    def plan(*args):
        out = plan_of(*args)
        pairs = out["dest"].size
        first = jnp.argmax(out["row_pair"] < pairs)
        return dict(out, row_pair=out["row_pair"].at[first].set(pairs))

    monkeypatch.setattr(moe_lib, "_dispatch_plan", plan)
    return config()


@pytest.mark.parametrize("undo", [
    "renormalised_gates", "no_qk_norm", "one_dropped_pair",
    "top1_balance_loss",
])
def test_the_check_is_sharp(undo, params, tokens, monkeypatch):
    """Each of the model's departures from Mixtral, undone in the program,
    fails the comparison the tests above make."""
    if undo == "top1_balance_loss":
        cfg = config(moe_aux_form="top1")
        nll, aux, _ = CHECK.outputs(cfg, params, tokens)
        want = CHECK.reference("loss", cfg, params, tokens)
        assert abs(float(nll.mean() + aux) - float(want)) > 100 * LOSS_ATOL
        return
    if undo == "one_dropped_pair":
        # the plan is patched under a configuration the other cases run:
        # traced here and now (one program, and a new one), not taken from
        # what they kept
        cfg = _drop_one_pair(monkeypatch)
        got = jax.jit(functools.partial(harness.program_nll, cfg))(
            params, *tokens
        )
        want = CHECK.reference("token_nll", cfg, params, tokens)
        assert float(np.abs(got - want).max()) > 10 * NLL_ATOL
        return
    cfg = {
        "renormalised_gates": config(norm_topk_prob=True),
        "no_qk_norm": config(qk_norm=False),
    }[undo]
    assert CHECK.nll_gap(cfg, params, tokens) > 10 * NLL_ATOL


def test_prefill_then_cached_decode_agree_with_the_full_forward(
    params, tokens
):
    inputs = tokens[0]
    want, _ = reference.forward(config(), params, inputs)
    decoder = TransformerLM(config(decode=True))

    @jax.jit
    def decode(variables, ids, positions):
        return decoder.apply(
            variables, ids, positions=positions, mutable=["cache"]
        )

    prefill = 24
    (got, _), state = decode(
        {"params": params}, inputs[:, :prefill],
        jnp.arange(prefill)[None, :],
    )
    np.testing.assert_allclose(got, want[:, :prefill], atol=NLL_ATOL)
    for i in range(prefill, SEQ):
        (got, _), state = decode(
            {"params": params, "cache": state["cache"]}, inputs[:, i:i + 1],
            jnp.full((BATCH, 1), i),
        )
        np.testing.assert_allclose(got[:, 0], want[:, i], atol=NLL_ATOL)


def test_an_expert_with_no_rows_gets_a_zero_dw():
    """Groups 0 and 2 have rows, 1 and 3 none (3 is also where the rows of
    the static padding budget land): their dW is zero, not what the
    kernel's unvisited output block happened to hold."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((48, 16)), jnp.float32)
    x = x.at[32:].set(0)   # the budget's rows hold zeros, by the contract
    w = jnp.asarray(rng.standard_normal((4, 16, 8)), jnp.float32)
    sizes = jnp.asarray([16, 0, 16, 0], jnp.int32)

    def loss(x, w):
        return jnp.square(grouped_matmul(x, w, sizes, 8)).sum()

    dx, dw = jax.grad(loss, argnums=(0, 1))(x, w)
    assert np.isfinite(np.asarray(dw)).all()
    assert float(jnp.abs(dw[0]).min()) > 0 and float(jnp.abs(dw[2]).min()) > 0
    np.testing.assert_array_equal(dw[1], 0)
    np.testing.assert_array_equal(dw[3], 0)
    assert np.isfinite(np.asarray(dx)).all()


def test_grouped_stats_book_no_drop_and_the_row_budget(params, tokens):
    cfg = config()
    _, inter = TransformerLM(cfg).apply(
        {"params": params}, tokens[0], mutable=["intermediates"]
    )
    (vecs,) = jax.tree_util.tree_leaves(inter)
    pairs = BATCH * SEQ * cfg.top_k
    for vec in np.asarray(vecs).reshape(cfg.num_layers, -1):
        _, drop, load, pad_share, max_load = moe_lib.split_stats(vec)
        assert drop == 0.0
        budget = moe_lib._row_budget(pairs, 128, cfg.num_experts)
        np.testing.assert_allclose(pad_share, 1 - pairs / budget, rtol=1e-6)
        np.testing.assert_allclose(
            max_load, load.max() * cfg.num_experts, rtol=1e-6
        )
        assert max_load >= 1.0


# -- the models the program already ran are what they were ---------------------

TINY = dict(
    vocab_size=256, num_layers=2, d_model=64, num_heads=4, max_seq_len=32,
    dtype=jnp.float32, param_dtype=jnp.float32,
)
MIXTRAL = dict(
    TINY, num_kv_heads=2, d_ff=96, position="rope", norm="rmsnorm",
    activation="swiglu", use_bias=False, tie_embeddings=False, num_experts=8,
    top_k=2, rope_theta=1e6, moe_aux_weight=0.02,
)
ATTN = ["attn/key/kernel", "attn/out/kernel", "attn/query/kernel",
        "attn/value/kernel"]
MOE = ["moe/router/kernel", "moe/wg", "moe/wi", "moe/wo"]
# (configuration, leaves under ``blocks``, first loss and auxiliary term at
# the parent commit b216c33, CPU, float32)
EARLIER = {
    "gpt2": (TINY, [
        "attn/out/bias", "attn/out/kernel", "attn/qkv/bias",
        "attn/qkv/kernel", "ln_attn/bias", "ln_attn/scale", "ln_mlp/bias",
        "ln_mlp/scale", "mlp/wi/bias", "mlp/wi/kernel", "mlp/wo/bias",
        "mlp/wo/kernel",
    ], 5.566120624542236, 0.0),
    "mixtral": (MIXTRAL, ATTN + ["ln_attn/scale", "ln_mlp/scale"] + MOE,
                5.933447360992432, 0.3137377202510834),
    "mixtral_grouped": (
        dict(MIXTRAL, moe_dispatch="grouped"),
        ATTN + ["ln_attn/scale", "ln_mlp/scale"] + MOE,
        5.93068790435791, 0.3137036859989166,
    ),
}


@pytest.mark.parametrize("name", sorted(EARLIER))
def test_earlier_models_keep_their_trees_and_losses(name):
    kwargs, leaves, nll, aux = EARLIER[name]
    cfg = TransformerConfig(**kwargs)
    assert cfg.qk_norm is False and cfg.norm_topk_prob is True
    assert cfg.moe_aux_form == "top1"
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.integers(0, 256, (2, 33)), jnp.int32)
    tree = nn.meta.unbox(
        TransformerLM(cfg).init(jax.random.PRNGKey(0), rows[:, :-1])
    )["params"]
    found = sorted(
        "/".join(k.key for k in path)
        for path, _ in jax.tree_util.tree_leaves_with_path(tree["blocks"])
    )
    assert found == sorted(leaves)
    got_nll, got_aux, _ = harness.program_outputs(
        cfg, tree, rows[:, :-1], rows[:, 1:]
    )
    np.testing.assert_allclose(float(got_nll.mean()), nll, rtol=1e-6)
    np.testing.assert_allclose(float(got_aux), aux, rtol=1e-6)


def test_olmoe_keeps_its_tree_and_losses():
    """GPT-2's and Mixtral's are held above; OLMoE's first loss and
    auxiliary term at the parent commit 3e4dd89.  (From
    ``tests/test_lowered_steps.py``, whose pinned step texts are
    ``tests/test_step_scopes.py``'s since PR 48.)"""
    cfg = olmoe_config(
        vocab_size=256, num_layers=2, d_model=64, num_heads=4, d_ff=32,
        num_experts=8, top_k=4, max_seq_len=32, dtype=jnp.float32,
        param_dtype=jnp.float32,
    )
    assert cfg.layer_pattern == () and cfg.norm_placement == "pre"
    assert cfg.norm_eps == 1e-5 and cfg.num_scan_units == 2
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.integers(0, 256, (2, 33)), jnp.int32)
    tree = nn.meta.unbox(
        TransformerLM(cfg).init(jax.random.PRNGKey(0), rows[:, :-1])
    )["params"]
    found = sorted(
        "/".join(k.key for k in path)
        for path, _ in jax.tree_util.tree_leaves_with_path(tree["blocks"])
    )
    assert found == [
        "attn/k_norm/scale", "attn/out/kernel", "attn/q_norm/scale",
        "attn/qkv/kernel", "ln_attn/scale", "ln_mlp/scale",
        "moe/router/kernel", "moe/wg", "moe/wi", "moe/wo",
    ]
    logits, aux = TransformerLM(cfg).apply({"params": tree}, rows[:, :-1])
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, rows[:, 1:][..., None], -1)[..., 0]
    np.testing.assert_allclose(float(nll.mean()), 6.050836563110352, rtol=1e-6)
    np.testing.assert_allclose(float(aux), 0.0913332924246788, rtol=1e-6)


def test_cache_key_covers_the_new_fields():
    from dlrover_tpu.runtime.compile_cache import train_cache_key

    def key(**kw):
        return train_cache_key(
            dataclasses.replace(config(), **kw), (1, 1, 1, 1, 1, 1),
            global_batch_size=BATCH, seq_len=SEQ,
        )

    keys = {key(), key(qk_norm=False), key(norm_topk_prob=True),
            key(moe_aux_form="top1")}
    assert len(keys) == 4
