"""JoyAI-LLM-Flash (the DeepSeek-V3 family's layers) against its plain
reference, on the CPU at a small size with seeded weights, and each new
mechanism on its own: the flash kernels at ``d_qk != d_v``, the sigmoid
router and its bias, a chip's share of the experts and its row budget, the
grouped GEMM's dead blocks, the dense prefix, the MTP module.  What the
configuration refuses and counts is ``tests/test_joyai_system.py``'s."""

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_harness as harness
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.attention import xla_attention
from dlrover_tpu.models.joyai_llm_flash import joyai_llm_flash_config
from dlrover_tpu.models.references import joyai_llm_flash as ref
from dlrover_tpu.models.transformer import TransformerLM
from dlrover_tpu.ops import flash_attention as fa
from dlrover_tpu.ops import grouped_matmul as gmm

SEQ, BATCH, VOCAB = 32, 2, 256

SMALL = dict(
    vocab_size=VOCAB, num_layers=3, d_model=64, num_heads=4, d_ff=96,
    max_seq_len=SEQ, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, num_experts=16, top_k=4, moe_d_ff=32,
    experts_held=4, first_expert=4, moe_row_budget=2.0, rope_theta=1e4,
    dtype=jnp.float32, param_dtype=jnp.float32,
)

# -- the whole model against the reference ------------------------------------

# Tolerance: both sides are float32 under matmul precision "highest"; they
# differ in the order of sums (fused kernels, sorted rows, one head or one
# expert at a time), which moves a loss of ~6 by a few float32 ulps (1e-6)
# and a gradient entry of ~1 likewise.  1e-4 is a hundred times that and a
# hundredth of what any left-out term moves (a gate's scale, the shared
# expert, the rotary key, the MTP term: 1e-2 or more a token).
TOL = 1e-4
# b picks, it never weighs: no gradient reaches it
CHECK = harness.Harness(
    ref, loss_atol=TOL, grad_atol=TOL, grad_rtol=0.0,
    no_gradient=("router_bias",), may_be_zero=("ln_",),
)


def config(**overrides):
    return joyai_llm_flash_config(**{**SMALL, **overrides})


def move(name, leaf, draw):
    """Router biases that are not zero, so that the choice on ``s + b``
    differs from the choice on ``s``."""
    if "router_bias" in name:
        return 0.05 * draw(leaf.shape)
    return leaf


@functools.cache
def tokens():
    return harness.tokens(1, BATCH, SEQ, VOCAB)


@functools.cache
def weights(experts_held=4):
    """Seeded weights of a share of ``experts_held`` experts (0: of all);
    which share it is changes no shape."""
    cfg = config(experts_held=experts_held, first_expert=0)
    return harness.init(cfg, tokens()[0], move=move)


CASES = {
    "share": {},
    "whole": dict(experts_held=0, first_expert=0),
    "flash": dict(attention_impl="flash", flash_block_q=16, flash_block_kv=16),
    "last_share": dict(first_expert=12),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_matches_the_reference_in_float32(case):
    cfg = config(**CASES[case])
    params = weights(cfg.experts_held)
    _, (main, _, extra), _ = CHECK.loss_and_grads(cfg, params, tokens())
    want = CHECK.reference("forward", cfg, params, tokens())
    np.testing.assert_allclose(main, want["nll"], atol=TOL)
    np.testing.assert_allclose(extra, want["mtp_nll"], atol=TOL)
    CHECK.loss_and_every_gradient_match(cfg, params, tokens())
    # the plain call is the two values it always was
    plain = jax.jit(
        lambda p, i: TransformerLM(cfg).apply({"params": p}, i)
    )(params, tokens()[0])
    assert len(plain) == 2
    np.testing.assert_allclose(
        harness.token_nll(plain[0], tokens()[1]), main, atol=1e-6
    )


def test_the_reference_computed_lower_is_another_result():
    exact = CHECK.reference("token_nll", config(), weights(), tokens())
    for lowered, least in (("router", TOL), ("all", 100 * TOL)):
        other = CHECK.reference(
            "token_nll", config(), weights(), tokens(), lowered=lowered
        )
        assert float(np.abs(other - exact).mean()) > least, lowered


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all 4 shares of one layer, plus the shared
    expert counted once, are the uncut reference's layer."""
    total, held, d, width = 16, 4, 64, 32
    keys = jax.random.split(jax.random.PRNGKey(5), 8)
    n = jax.random.normal(keys[0], (BATCH, SEQ, d))
    whole = {
        "router": {"kernel": jax.random.normal(keys[1], (d, total))},
        "router_bias": 0.05 * jax.random.normal(keys[2], (total,)),
        "wi": 0.1 * jax.random.normal(keys[3], (total, d, width)),
        "wg": 0.1 * jax.random.normal(keys[4], (total, d, width)),
        "wo": 0.1 * jax.random.normal(keys[5], (total, width, d)),
        "shared": {
            name: {"kernel": 0.1 * jax.random.normal(key, shape)}
            for name, key, shape in (
                ("wi", keys[6], (d, width)), ("wg", keys[7], (d, width)),
                ("wo", keys[0], (width, d)),
            )
        },
    }
    fields = dict(
        num_experts=total, top_k=4, norm_topk_prob=True,
        routed_scaling_factor=2.5,
    )
    with jax.default_matmul_precision("highest"):
        shared = ref.swiglu(n, whole["shared"])
    harness.shares_add_up(
        ref, fields, n, whole, held,
        lambda first: moe_lib.MoEMlp(
            num_experts=total, d_ff=width, top_k=4, dispatch="grouped",
            scoring="sigmoid", router_bias=True, routed_scale=2.5,
            experts_held=held, first_expert=first, shared_d_ff=width,
            row_budget_multiple=4.0, dtype=jnp.float32, gmm_block_rows=8,
        ),
        shared, TOL,
    )


# -- the flash kernels at d_qk != d_v ----------------------------------------


@pytest.mark.parametrize("blocks", [(64, 64), (16, 16)], ids=["fused", "split"])
@pytest.mark.parametrize("widths", [(48, 32), (32, 48), (32, 32)])
def test_flash_takes_keys_and_values_of_two_widths(widths, blocks):
    """Forward and the three gradients against ``xla_attention``: the fused
    backward (one kv block) and the split dq / dkv kernels; equal widths
    are the case every other model runs."""
    d_qk, d_v = widths
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (2, 64, 4, d_qk))
    k = jax.random.normal(keys[1], (2, 64, 4, d_qk))
    v = jax.random.normal(keys[2], (2, 64, 4, d_v))
    w = jax.random.normal(keys[3], (2, 64, 4, d_v))

    def flash(q, k, v):
        return fa.mha(q, k, v, block_q=blocks[0], block_kv=blocks[1])

    with jax.default_matmul_precision("highest"):
        out, want = flash(q, k, v), xla_attention(q, k, v)
        got_grads = jax.grad(
            lambda *a: (flash(*a) * w).sum(), argnums=(0, 1, 2)
        )(q, k, v)
        want_grads = jax.grad(
            lambda *a: (xla_attention(*a) * w).sum(), argnums=(0, 1, 2)
        )(q, k, v)
    assert out.shape == (2, 64, 4, d_v)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, want_grad, like in zip(got_grads, want_grads, (q, k, v)):
        assert got.shape == like.shape
        np.testing.assert_allclose(got, want_grad, atol=5e-5)


# -- the router ----------------------------------------------------------------


def test_sigmoid_router_bias_picks_and_never_weighs():
    logits = jnp.log(jnp.asarray([[[0.9, 0.8, 0.3, 0.2, 0.1, 0.05]]])
                     / (1 - jnp.asarray([[[0.9, 0.8, 0.3, 0.2, 0.1, 0.05]]])))
    bias = jnp.asarray([0.0, -1.0, 0.0, 0.0, 0.6, 0.0])
    gates, idx, aux = moe_lib._gate(
        logits, 2, True, "top1", "sigmoid", bias, 2.5
    )
    # chosen on s + b: expert 0 (0.9) and expert 4 (0.1 + 0.6), not 1
    assert sorted(np.asarray(idx)[0, 0].tolist()) == [0, 4]
    by_expert = dict(zip(np.asarray(idx)[0, 0].tolist(),
                         np.asarray(gates)[0, 0].tolist()))
    # weighed by s alone, renormalised over the chosen, times 2.5
    assert by_expert[0] == pytest.approx(2.5 * 0.9 / 1.0, rel=1e-5)
    assert by_expert[4] == pytest.approx(2.5 * 0.1 / 1.0, rel=1e-5)
    assert float(aux) == 0.0
    # without the bias the choice is the two largest scores
    _, plain, _ = moe_lib._gate(logits, 2, True, "top1", "sigmoid", None, 2.5)
    assert sorted(np.asarray(plain)[0, 0].tolist()) == [0, 1]
    # no gradient to b, one to the logits
    d_logits, d_bias = jax.grad(
        lambda lg, b: moe_lib._gate(lg, 2, True, "top1", "sigmoid", b, 2.5)[
            0
        ][..., 0].sum(),
        argnums=(0, 1),
    )(logits, bias)
    assert not np.asarray(d_bias).any() and np.asarray(d_logits).any()
    # unnormalised: the scores as they are, times the scale
    raw, raw_idx, _ = moe_lib._gate(
        logits, 2, False, "top1", "sigmoid", None, 2.0
    )
    assert sorted(np.asarray(raw)[0, 0].tolist()) == pytest.approx([1.6, 1.8])


def test_the_bias_moves_by_the_sign_of_the_load_against_its_mean():
    load = jnp.asarray([[4.0, 1.0, 1.0, 2.0], [2.0, 2.0, 2.0, 2.0]])
    bias = jnp.asarray([[0.0, 0.1, -0.1, 0.0], [0.5, 0.5, 0.5, 0.5]])
    moved = moe_lib.bias_update(bias, load, 0.01)
    np.testing.assert_allclose(
        moved, [[-0.01, 0.11, -0.09, 0.0], [0.5, 0.5, 0.5, 0.5]], atol=1e-7
    )
    np.testing.assert_allclose(
        ref.bias_rule(bias[0], load[0], 0.01), moved[0], atol=1e-7
    )


# -- a share's row budget ------------------------------------------------------


def expert_layer(held, multiple, first=0, total=8, block=8):
    return moe_lib.MoEMlp(
        num_experts=total, d_ff=16, top_k=2, dispatch="grouped",
        scoring="sigmoid", experts_held=held, first_expert=first,
        row_budget_multiple=multiple, dtype=jnp.float32, gmm_block_rows=block,
    )


def test_a_pair_past_the_row_budget_is_dropped_and_counted():
    """A router that sends every token to the two experts held here: four
    times the expected share.  At a budget of the expected share the rest
    is dropped, ``drop_fraction`` says how much, and the result is the
    kept pairs' alone; at four times it nothing is dropped."""
    d, total, held = 32, 8, 2
    n = jax.random.normal(jax.random.PRNGKey(0), (2, 32, d))
    layer = expert_layer(held, 1.0)
    params = nn.meta.unbox(jax.jit(layer.init)(jax.random.PRNGKey(1), n))[
        "params"
    ]
    kernel = jnp.zeros((d, total)).at[:, :held].set(
        jnp.abs(params["router"]["kernel"][:, :held]) + 1.0
    )
    n = jnp.abs(n)                       # so that experts 0 and 1 always win
    params = dict(params, router={"kernel": kernel})
    pairs = 2 * 32 * 2
    results = {}
    for multiple in (1.0, 4.0):
        (out, _), sown = jax.jit(lambda p, n: expert_layer(
            held, multiple
        ).apply({"params": p}, n, mutable=["intermediates"]))(params, n)
        stats = sown["intermediates"]
        _, drop, load, pad, _ = moe_lib.split_stats(stats["moe_stats"][0])
        here = float(stats[moe_lib.SHARE_STATS_NAME][0][0])
        results[multiple] = (out, float(drop))
        assert here == pytest.approx(1.0)        # every pair is routed here
        assert float(load[:held].sum()) == pytest.approx(1.0)
    budget = moe_lib._share_row_budget(pairs, 8, held, total, 1.0)
    assert budget == (pairs // 4 // 8 + held) * 8 + 8
    kept = budget - 8                    # all but the zero block, no padding
    assert results[1.0][1] == pytest.approx(1.0 - kept / pairs)
    assert results[4.0][1] == 0.0
    # the budget ends inside expert 0's group: its first tokens' pairs are
    # computed, its last tokens' and all of expert 1's are not
    assert np.asarray(results[1.0][0][0, 0]).any()
    assert not np.asarray(results[1.0][0][1, -1]).any()
    assert np.asarray(results[4.0][0][1, -1]).any()


def test_the_plan_is_built_over_the_held_experts_alone():
    gate_idx = jnp.asarray([[0, 5], [4, 5], [7, 2], [5, 4]], jnp.int32)
    plan = moe_lib._dispatch_plan(gate_idx, 2, 2, 12, first=4, total=8)
    assert plan["padded"].tolist() == [2, 4]            # experts 4 and 5
    assert int(plan["here"]) == 5 and int(plan["kept"]) == 5
    # pairs routed elsewhere point at the last row, which no pair is given
    assert plan["dest"].tolist() == [[11, 2], [0, 3], [11, 11], [4, 1]]
    assert plan["row_pair"].tolist() == [2, 7, 1, 3, 6, 8, 8, 8, 8, 8, 8, 8]
    # all the experts held: the plan every dropless model had
    whole = moe_lib._dispatch_plan(gate_idx, 8, 2, 24)
    assert sorted(whole) == ["dest", "padded", "row_pair"]
    assert whole["padded"].sum() == 12
    assert whole["row_pair"][:4].tolist() == [0, 8, 5, 8]


@pytest.mark.parametrize("skip_dead", [True, False])
def test_dead_row_blocks_cost_no_matmul_and_come_out_zero(skip_dead):
    """Rows past the last group (a budget's slack) are zero in the output
    and in dx, and dw is the live rows' alone: skipped (a share's budget),
    or met with the last expert's weights as zeros (every expert held)."""
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    sizes = jnp.asarray([16, 0, 8], jnp.int32)
    x = jax.random.normal(keys[0], (48, 32))            # 24 live, 24 slack
    if not skip_dead:
        x = x.at[24:].set(0.0)      # unskipped slack has to hold zeros
    w = jax.random.normal(keys[1], (3, 32, 16))
    dy = jax.random.normal(keys[2], (48, 16))
    out, vjp = jax.vjp(
        lambda x, w: gmm.grouped_matmul(x, w, sizes, 8, False, skip_dead),
        x, w,
    )
    want = gmm.grouped_matmul_ref(x, w, sizes)
    np.testing.assert_allclose(out, want, atol=1e-4)
    assert not np.asarray(out[24:]).any()
    dx, dw = vjp(dy)
    want_dx, want_dw = jax.vjp(
        lambda x, w: gmm.grouped_matmul_ref(x, w, sizes), x, w
    )[1](dy)
    # (unskipped, a slack row's dx is its dy through the last expert's
    # weights: no token's gradient ever reads it)
    live_rows = slice(None) if skip_dead else slice(0, 24)
    np.testing.assert_allclose(dx[live_rows], want_dx[live_rows], atol=1e-4)
    np.testing.assert_allclose(dw, want_dw, atol=1e-4)
    assert not np.asarray(dw[1]).any()
    if not skip_dead:
        assert gmm._block_plan(sizes, 6, 8, False)[0].tolist() == [
            0, 0, 2, 2, 2, 2
        ]
        return
    # skipped blocks never read the slack: whatever it holds, dx is zero
    assert not np.asarray(dx[24:]).any()
    eob, live = gmm._block_plan(sizes, 6, 8, True)
    assert live.tolist() == [3] and eob.tolist() == [0, 0, 2, 2, 2, 2]


def test_trunk_without_the_scan_has_the_same_losses():
    cfg = config()
    inputs, targets = tokens()
    params = weights()
    listed = {k: v for k, v in params.items() if k != "blocks"}
    for i in range(2):
        listed[f"block_{i + 1}"] = jax.tree.map(
            lambda a: a[i], params["blocks"]
        )
    loose = dataclasses.replace(cfg, scan_layers=False)

    def logits(cfg, params):
        return jax.jit(
            lambda p, i: TransformerLM(cfg).apply({"params": p}, i)[0]
        )(params, inputs)

    want = logits(cfg, params)
    np.testing.assert_allclose(logits(loose, listed), want, atol=1e-5)
    np.testing.assert_allclose(
        ref.token_nll(dataclasses.asdict(loose), listed, inputs, targets),
        harness.token_nll(want, targets), atol=TOL,
    )
