"""Olmo-Hybrid through the program against the plain reference
(``dlrover_tpu/models/references/olmo_hybrid.py``), at a small size on the
CPU: the chunked delta rule against the token-by-token recurrence, the
patterned trunk against the reference's loop over layers.

The rule runs as its Pallas kernels in interpret mode
(``ops/backend.interpret``), forward and backward.

Seeded weights; every norm scale is moved off one.  Tolerances, all
float32 against float32: the chunked form orders its sums differently
from the recurrence and builds ``(I + A)^-1`` by products, which moves an
output by about 1e-6 of the largest; each piece of the layer undone
(doubling of beta, decay, k's norm, convolution, output gate, norm
placement) moves a token's loss by 1e-2 or more, so 2e-4 lies a factor of
ten or more from either.
"""

import dataclasses
import hashlib
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.olmo_hybrid import olmo_hybrid_config
from dlrover_tpu.models.references import olmo_hybrid as reference
from dlrover_tpu.models.transformer import TransformerConfig, TransformerLM
from dlrover_tpu.ops.gated_delta_rule import gated_delta_rule

NLL_ATOL = 2e-4
LOSS_ATOL = 2e-5
GRAD_RTOL, GRAD_ATOL = 2e-4, 1e-7

SEQ, BATCH, VOCAB = 144, 2, 256
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(**overrides):
    base = dict(
        vocab_size=VOCAB, num_layers=8, d_model=64, num_heads=4, d_ff=96,
        linear_num_heads=4, linear_key_head_dim=12, linear_value_head_dim=24,
        max_seq_len=SEQ, dtype=jnp.float32, param_dtype=jnp.float32,
        attention_impl="xla", flash_block_q=16, flash_block_kv=16,
    )
    base.update(overrides)
    return olmo_hybrid_config(**base)


# -- the rule ------------------------------------------------------------------


def rule_inputs(seed, length, neg, heads=3, dk=8, dv=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (BATCH, length, heads)
    q = jax.random.normal(keys[0], shape + (dk,))
    k = jax.random.normal(keys[1], shape + (dk,))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], shape + (dv,))
    g = -0.5 * jax.nn.softplus(jax.random.normal(keys[3], shape))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape)) * (2 if neg else 1)
    do = jax.random.normal(keys[5], shape + (dv,))
    return (q, k, v, g, beta), do


@pytest.mark.parametrize("neg", [True, False])
@pytest.mark.parametrize("length,chunk", [
    (64, 16), (64, 64), (100, 16), (100, 64), (200, 128),
])
def test_chunked_rule_is_the_recurrence(chunk, length, neg):
    """Outputs and every gradient; 100 and 200 are no multiples of their
    chunks, and every case but (64, 64) crosses a chunk boundary."""
    args, do = rule_inputs(length + chunk, length, neg)
    want = reference.delta_rule_recurrence(*args)
    got, _ = gated_delta_rule(*args, chunk=chunk)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 1e-5 * scale

    def loss(fn):
        return lambda *a: (fn(*a) * do).sum()

    got_grads = jax.grad(
        loss(lambda *a: gated_delta_rule(*a, chunk=chunk)[0]),
        argnums=(0, 1, 2, 3, 4),
    )(*args)
    want_grads = jax.grad(
        loss(reference.delta_rule_recurrence), argnums=(0, 1, 2, 3, 4)
    )(*args)
    for name, g, w in zip("q k v g beta".split(), got_grads, want_grads):
        assert float(jnp.abs(w).max()) > 0, name
        assert float(jnp.abs(g - w).max()) <= 2e-5 * float(
            jnp.abs(w).max()
        ), name


def test_keys_that_resemble_each_other_keep_their_digits():
    """Trained keys are alike (after SiLU most channels are positive), and
    with beta near 2 the chunk's ``(I + A)^-1`` is then ill-suited to a
    product of powers of ``A``: that form lost every digit here."""
    args, _ = rule_inputs(9, 256, True)
    q, k, v, g, beta = args
    k = k + 3.0 * jnp.ones_like(k[:1, :1, :1])
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    cos = jnp.einsum("bshk,bthk->bhst", k, k)
    assert float(cos.min()) > 0.6
    args = (q, k, v, 0.05 * g, 0.99 * jnp.full_like(beta, 2.0))
    want = reference.delta_rule_recurrence(*args)
    got, _ = gated_delta_rule(*args)          # the program's chunk, 128
    assert float(jnp.abs(got - want).max()) <= 1e-4 * float(
        jnp.abs(want).max()
    )


def test_state_absmax_is_the_largest_boundary_state():
    args, _ = rule_inputs(3, 96, True)
    q, k, v, g, beta = args
    _, got = gated_delta_rule(*args, chunk=16)
    # the recurrence's states at the chunk boundaries, by its own step
    state, top = jnp.zeros((BATCH, 3, 16, 8)), 0.0
    for t in range(96):
        alpha = jnp.exp(g[:, t])[..., None, None]
        bt = beta[:, t][..., None, None]
        kk = k[:, t][..., :, None] * k[:, t][..., None, :]
        state = alpha * (state - bt * state @ kk) + bt * (
            v[:, t][..., :, None] * k[:, t][..., None, :]
        )
        if (t + 1) % 16 == 0:
            top = max(top, float(jnp.abs(state).max()))
    np.testing.assert_allclose(float(got), top, rtol=1e-5)


def rule_grads(fn, args, do):
    return jax.grad(
        lambda *a: (fn(*a).astype(jnp.float32) * do).sum(),
        argnums=(0, 1, 2, 3, 4),
    )(*args)


# bfloat16 operands: W, V', M and the start states are rounded to 8 bits
# inside a chunk, which moves a gradient by 0.3-1.2% of its largest entry
# (read here, both lengths); a dropped term moves it by tens of percent.
KERNEL_RTOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("length,cotangent", [
    (128, "all"), (384, "all"), (300, "all"), (384, "last_chunk"),
])
def test_kernel_gradients_at_the_published_head_widths(
    length, cotangent, dtype
):
    """dk 96, dv 192, chunk 128: one chunk, three, and a length that is
    no whole number of chunks; every gradient against autodiff of the
    token-by-token recurrence on the same (rounded) operands.  With the
    cotangent on the last chunk alone, all that reaches the first chunk's
    tokens has crossed two chunk boundaries as the state's cotangent."""
    args, do = rule_inputs(length, length, True, heads=2, dk=96, dv=192)
    args = tuple(a.astype(dtype) for a in args[:3]) + args[3:]
    if cotangent == "last_chunk":
        do = do * (jnp.arange(length) >= 256)[None, :, None, None]
    in_f32 = tuple(a.astype(jnp.float32) for a in args)
    want_o = reference.delta_rule_recurrence(*in_f32)
    got_o, _ = gated_delta_rule(*args)
    assert got_o.dtype == dtype
    rtol = KERNEL_RTOL[dtype]
    assert float(jnp.abs(got_o - want_o).max()) <= rtol * float(
        jnp.abs(want_o).max()
    )
    got = rule_grads(lambda *a: gated_delta_rule(*a)[0], args, do)
    want = rule_grads(reference.delta_rule_recurrence, in_f32, do)
    for name, g, w in zip("q k v g beta".split(), got, want):
        assert g.dtype == (jnp.float32 if name in ("g", "beta") else dtype)
        first = jnp.abs(w[:, :128]).max()
        # (q_t reaches no output but its own token's)
        assert float(first) > 0 or (name, cotangent) == ("q", "last_chunk")
        assert float(jnp.abs(g - w).max()) <= rtol * float(
            jnp.abs(w).max()
        ), name
        # and the first chunk's own gradients, by their own size
        assert float(jnp.abs(g[:, :128] - w[:, :128]).max()) <= (
            rtol * float(first)
        ), name


def test_alike_keys_in_bfloat16_stay_with_the_recurrence():
    """The inverse's three-pass products (bfloat16 operands take that
    path; float32 operands multiply exactly) on keys at cosine 0.9 and
    beta 1.98: within the rounding of the operands, where one pass or
    the product of powers is not."""
    args, _ = rule_inputs(9, 256, True, heads=2, dk=96, dv=192)
    q, k, v, g, beta = args
    k = k + 0.3 * jnp.ones_like(k[:1, :1, :1])
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    cos = jnp.einsum("bshk,bthk->bhst", k, k)
    assert float(cos.min()) > 0.6
    args = tuple(a.astype(jnp.bfloat16) for a in (q, k, v)) + (
        0.05 * g, 0.99 * jnp.full_like(beta, 2.0),
    )
    want = reference.delta_rule_recurrence(
        *(a.astype(jnp.float32) for a in args)
    )
    got, _ = gated_delta_rule(*args)
    assert float(jnp.abs(got - want).max()) <= 3e-2 * float(
        jnp.abs(want).max()
    )


@pytest.mark.parametrize("chunk,dtype,message", [
    (96, jnp.float32, "power of two"),
    (8, jnp.bfloat16, "16 rows of a bfloat16 tile, got 8"),
])
def test_a_chunk_the_kernel_cannot_tile_raises_with_the_numbers(
    chunk, dtype, message
):
    args, _ = rule_inputs(1, 64, True)
    args = tuple(a.astype(dtype) for a in args[:3]) + args[3:]
    with pytest.raises(ValueError, match=message):
        gated_delta_rule(*args, chunk=chunk)


# -- the model -----------------------------------------------------------------


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.default_rng(11)
    rows = jnp.asarray(rng.integers(0, VOCAB, (BATCH, SEQ + 1)), jnp.int32)
    return rows[:, :-1], rows[:, 1:]


@pytest.fixture(scope="module")
def params(tokens):
    return init(config(), tokens)


def init(cfg, tokens):
    """The program's own init, then every norm scale moved off one."""
    tree = nn.meta.unbox(
        TransformerLM(cfg).init(jax.random.PRNGKey(5), tokens[0])
    )["params"]
    rng = np.random.default_rng(7)

    def move(path, leaf):
        if getattr(path[-1], "key", "").endswith("scale"):
            return leaf * jnp.asarray(
                1 + 0.3 * rng.standard_normal(leaf.shape), leaf.dtype
            )
        return leaf

    return jax.tree_util.tree_map_with_path(move, tree)


def program_nll(cfg, params, inputs, targets):
    logits, _ = TransformerLM(cfg).apply({"params": params}, inputs)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def program_loss(cfg, params, inputs, targets):
    return program_nll(cfg, params, inputs, targets).mean()


def nll_gap(cfg, params, tokens, undo=""):
    got = program_nll(cfg, params, *tokens)
    want = reference.token_nll(config(), params, *tokens, undo=undo)
    return float(jnp.abs(got - want).max())


IMPLS = pytest.mark.parametrize("attention_impl", ["xla", "flash"])


def test_the_tree_has_a_slot_per_position_stacked_over_the_periods(params):
    blocks = params["blocks"]
    assert sorted(blocks) == ["full_3", "linear_0", "linear_1", "linear_2"]
    for slot, tree in blocks.items():
        mixer = "attn" if slot == "full_3" else "linear_attn"
        assert sorted(tree) == sorted([mixer, "ln_attn", "ln_mlp", "mlp"])
        assert all(leaf.shape[0] == 2 for leaf in jax.tree.leaves(tree))
    assert sorted(blocks["linear_0"]["linear_attn"]) == [
        "A_log", "ab_kernel", "conv_kernel", "dt_bias", "out_norm_scale",
        "qkvg", "wo",
    ]


@IMPLS
def test_token_nll_matches_the_reference(attention_impl, params, tokens):
    cfg = config(attention_impl=attention_impl)
    assert nll_gap(cfg, params, tokens) <= NLL_ATOL


@IMPLS
def test_loss_and_every_gradient_match_the_reference(
    attention_impl, params, tokens
):
    cfg = config(attention_impl=attention_impl, remat=(
        "flash_only" if attention_impl == "flash" else "none"
    ))
    loss_and_every_gradient_match(cfg, params, tokens)


def test_loss_and_every_gradient_match_where_the_conv_kernel_runs():
    """Heads of 12 | 12 | 24 columns are no whole row tiles and 144 tokens
    no whole lane tile, so the cases above take the convolution's XLA form;
    256 tokens of heads of 16 | 16 | 32 are two token tiles of
    ``ops/short_conv.py``'s kernels (the tokens on the lanes; q and k one
    row tile each, normalised inside; v two)."""
    from dlrover_tpu.models import linear_attention
    from dlrover_tpu.ops import short_conv

    seq = 256
    rng = np.random.default_rng(13)
    rows = jnp.asarray(rng.integers(0, VOCAB, (BATCH, seq + 1)), jnp.int32)
    tokens = rows[:, :-1], rows[:, 1:]
    assert linear_attention.conv_path(seq, 4, 12, 24, 4) == "xla"
    assert linear_attention.conv_path(SEQ, 4, 16, 32, 4) == "xla"
    assert linear_attention.conv_path(seq, 4, 16, 32, 4) == "kernel"
    calls = []
    kernel = short_conv.short_conv
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(short_conv, "_TILE_TOKENS", 128)
        patch.setattr(
            short_conv, "short_conv",
            lambda *a: calls.append(a[0].shape) or kernel(*a),
        )
        cfg = config(
            attention_impl="flash", remat="flash_only", max_seq_len=seq,
            linear_key_head_dim=16, linear_value_head_dim=32,
        )
        loss_and_every_gradient_match(cfg, init(cfg, tokens), tokens)
    assert calls and set(calls) == {(BATCH, seq, 4, 96)}


def loss_and_every_gradient_match(cfg, params, tokens):
    got, got_grads = jax.value_and_grad(program_loss, argnums=1)(
        cfg, params, *tokens
    )
    want, want_grads = reference.loss_and_grads(cfg, params, *tokens)
    assert abs(float(got) - float(want)) <= LOSS_ATOL
    flat_got = jax.tree_util.tree_leaves_with_path(got_grads)
    flat_want = jax.tree_util.tree_leaves(want_grads)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        bound = GRAD_ATOL + GRAD_RTOL * float(jnp.abs(w).max())
        assert float(jnp.abs(g - w).max()) <= bound, jax.tree_util.keystr(path)
        assert float(jnp.abs(w).max()) > 0, jax.tree_util.keystr(path)


def test_the_unrolled_trunk_is_the_scanned_one(params, tokens):
    cfg = config(scan_layers=False)
    unrolled = {k: v for k, v in params.items() if k != "blocks"}
    for i in range(cfg.num_layers):
        unrolled[f"block_{i}"] = reference.layer_params(
            dataclasses.asdict(cfg), params, i
        )[1]
    got = program_nll(cfg, unrolled, *tokens)
    want = program_nll(config(), params, *tokens)
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert nll_gap(cfg, unrolled, tokens) <= NLL_ATOL


@pytest.mark.parametrize("parallel,devices", [
    (dict(data=1), 1), (dict(data=2, tensor=2), 4),
])
def test_the_train_step_s_first_loss_is_the_reference_s(
    parallel, devices, params, tokens
):
    """The normal path: ``build_sharded_train``'s compiled step, under the
    policy the cell runs (the rule's and the flash kernels' outputs kept);
    on one device, and with the batch over ``data`` and the heads over
    ``tensor``, where each device's kernels see its own rows and heads."""
    from dlrover_tpu.models import linear_attention
    from dlrover_tpu.parallel import rules as lr
    from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
    from dlrover_tpu.trainer import train_lib

    cfg = config(attention_impl="flash", remat="flash_only")
    train = train_lib.build_sharded_train(
        TransformerLM(cfg),
        train_lib.make_optimizer("adafactor", learning_rate=1e-3),
        build_mesh(
            ParallelConfig(**parallel), devices=jax.devices()[:devices]
        ),
        lr.DEFAULT_RULES, global_batch_size=BATCH, seq_len=SEQ,
    )
    state = train.init(jax.random.PRNGKey(0))
    state = state.replace(params=jax.tree.map(
        lambda new, old: jax.device_put(
            jnp.array(new, old.dtype, copy=True), old.sharding
        ), params, state.params,
    ))
    batch = {"inputs": np.asarray(tokens[0]), "targets": np.asarray(tokens[1])}
    _, metrics = train.step(state, train_lib.shard_batch(batch, train))
    want = reference.token_nll(cfg, params, *tokens).mean()
    assert abs(float(metrics["loss"]) - float(want)) <= LOSS_ATOL
    alpha, beta, absmax = linear_attention.split_stats(
        np.asarray(metrics[linear_attention.STATS_NAME])
    )
    assert 0 < alpha < 1 and 0 < beta < 2 and 0 < absmax < 100


@pytest.mark.parametrize("undo", [
    "beta_doubling", "decay", "k_norm", "conv", "out_gate", "post_norm",
])
def test_the_check_is_sharp(undo, params, tokens):
    """Each piece of the layer, undone on one side, fails the comparison
    the tests above make."""
    cfg = {
        "beta_doubling": config(linear_allow_neg_eigval=False),
        "post_norm": config(norm_placement="pre"),
    }.get(undo)
    if cfg is not None:      # the program has the switch: undo it there
        assert nll_gap(cfg, params, tokens) > 10 * NLL_ATOL
        # and the reference with the same piece undone agrees again
        assert nll_gap(cfg, params, tokens, undo=undo) <= NLL_ATOL
        return
    assert nll_gap(config(), params, tokens, undo=undo) > 10 * NLL_ATOL


def test_a_pipelined_trunk_is_the_scanned_one(tokens):
    """Two stages of one period each: the stage stack holds whole
    periods, and the logits are the plain scan's."""
    plain, piped = config(), config(pipeline_stages=2, num_microbatches=2)
    tree = nn.meta.unbox(
        TransformerLM(piped).init(jax.random.PRNGKey(5), tokens[0])
    )["params"]
    layers = tree["blocks"]["ticks"]["stages"]["layers"]
    assert sorted(layers) == ["full_3", "linear_0", "linear_1", "linear_2"]
    # [stage, period of the stage, ...] -> [period, ...]
    flat = dict(tree, blocks=jax.tree.map(
        lambda a: a.reshape(-1, *a.shape[2:]), layers
    ))
    got = program_nll(piped, tree, *tokens)
    want = program_nll(plain, flat, *tokens)
    np.testing.assert_allclose(got, want, atol=1e-4)


# -- what the configuration refuses ---------------------------------------------


def test_decode_with_a_linear_layer_raises_naming_what_is_missing():
    with pytest.raises(ValueError, match="recurrent state"):
        config(decode=True)


@pytest.mark.parametrize("kwargs,message", [
    (dict(num_layers=6), "6 is no whole number of periods of the 4-layer"),
    (dict(num_layers=12, pipeline_stages=2),
     "pipeline_stages 2 does not divide the 3 periods"),
    (dict(layer_pattern=("full_attention", "sliding")), "sliding"),
    (dict(linear_key_head_dim=0), "linear_key_head_dim"),
    (dict(norm_placement="sandwich"), "sandwich"),
])
def test_a_pattern_that_does_not_fit_raises_with_the_numbers(kwargs, message):
    with pytest.raises(ValueError, match=message):
        config(**kwargs)


def test_the_published_widths_count_what_the_issue_counts():
    """No array is made: ``eval_shape`` of the program's own init at the
    published widths, one period, a 128-row vocabulary."""
    cfg = olmo_hybrid_config(num_layers=4, vocab_size=128, max_seq_len=64)
    shapes = jax.eval_shape(
        TransformerLM(cfg).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 64), jnp.int32),
    )["params"]

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    blocks = nn.meta.unbox(shapes)["blocks"]
    assert count(blocks["linear_0"]["linear_attn"]) == 88_750_332
    assert count(blocks["linear_0"]["mlp"]) == 126_812_160
    assert cfg._linear_mixer_params() == 88_750_332
    # attention without its two QK-norm scales, as num_params counts it
    attn = count(blocks["full_3"]["attn"]) - 2 * 3840
    assert attn == 4 * 3840 * 3840
    full = olmo_hybrid_config()
    assert full.num_params() == (
        24 * (88_750_332 + 126_812_160) + 8 * (attn + 126_812_160)
        + 2 * 100352 * 3840
    )
    assert 7.42e9 < full.num_params() < 7.44e9


# -- the models the program already ran are what they were ---------------------

# sha256 of the lowered step text (StableHLO; CPU; the benchmark's tiny
# presets; ``@name_<n>`` counters normalised) at the parent commit 04ce0df
# with PR 36's flash kernels, which every one of the four runs: no segment
# compare and no all-masked-row guards without ids or padding, an exact
# ``scale`` on the q tile, the forward of ONE kv block written straight out
# (all four were recorded anew; the other modules lower to what they did).
# A PR that changes these models' step on purpose records them anew.  PR 33
# left all four as they were: the flash kernels at ``d_qk == d_v``, the
# grouped GEMMs with every expert held (no dead blocks skipped), the router
# statistics without a share and the trunk without a dense prefix or an MTP
# module lower to what they lowered to.  PR 34 (the one-pass flash backward at
# several kv blocks) left three as they were: their tiny presets run ONE kv
# block (64 tokens in a block of 64), which lowers to the parent's kernel.
# ``olmo-hybrid-7b``'s preset sets blocks of 16 for its 64 tokens, four kv
# blocks: its two full layers' backward is now one kernel with a [64, 16]
# float32 dq scratch where it was two, so its text is recorded anew.
LOWERED_AT_PARENT = {
    "gpt2-1.5b":
        "3fb5f6338781894bc6418780c92ff0224b12abbaddadeef5d7c79a740c9f4f92",
    "mixtral-8x7b":
        "a610499e04164995118fff59e041ffb9f8a82901625a1fddb1ebe83edd4790bb",
    "olmoe-1b-7b":
        "0d7f87bc882696205ed45766b521452c70b9eab6848047132852914d5623fd02",
    "olmo-hybrid-7b":
        "f0d80527a4cb2792ec44b3c973ef822f3582b8ba5d6d058e9850cd56c1ed6ab5",
}


def lowered_step_text(preset):
    from benchmark import build
    from dlrover_tpu.parallel import rules as lr
    from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
    from dlrover_tpu.trainer import train_lib

    cfg = build.load_json(os.path.join(
        REPO, "tests", "benchmark_suite", "presets", f"{preset}.json"
    ))
    seq, batch = cfg["run"]["seq_len"], cfg["run"]["sequences_per_chip"]
    mesh = build_mesh(ParallelConfig(data=-1), devices=jax.devices()[:1])
    train = train_lib.build_sharded_train(
        TransformerLM(
            build.transformer_config(build.model_group(cfg), seq)
        ),
        train_lib.make_optimizer("adafactor", learning_rate=1e-3),
        mesh, lr.DEFAULT_RULES, global_batch_size=batch, seq_len=seq,
    )
    state = jax.eval_shape(train.init_fn, train_lib._ABSTRACT_KEY)
    batch_shape = {
        k: jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        for k in ("inputs", "targets")
    }
    batch_shape["weights"] = jax.ShapeDtypeStruct((batch, seq), jnp.float32)
    with train_lib.use_mesh(mesh):
        text = train.step_fn.lower(state, batch_shape).as_text()
    return re.sub(r"@(\w+?)_\d+\b", r"@\1_N", text)


@pytest.mark.parametrize("preset", sorted(LOWERED_AT_PARENT))
def test_earlier_models_keep_their_lowered_step_text(preset):
    text = lowered_step_text(preset)
    assert hashlib.sha256(text.encode()).hexdigest() == (
        LOWERED_AT_PARENT[preset]
    )
    if preset != "olmo-hybrid-7b":
        assert "linear_attn" not in text and "delta" not in text
    # and none of them has met the DeepSeek-V3 family's parts
    for name in ("latent", "router_bias", "mtp", "moe_share_stats"):
        assert name not in text, name


@pytest.mark.parametrize("preset,blocks,vmem_cap,path,classes", [
    # one kv block: no dq scratch, and one diagonal block a (batch, head)
    ("gpt2-1.5b", 1, None, "fused", (0, 0, 1)),
    # several: dq in VMEM scratch; 6 dead, 6 interior, 4 diagonal
    ("olmo-hybrid-7b", 4, None, "fused", (6, 6, 4)),
    ("olmo-hybrid-7b", 4, 1 << 16, "split", (6, 6, 4)),   # past the bound
    ("gpt2-1.5b", 1, 1 << 16, "fused", (0, 0, 1)),
])
def test_compile_event_names_the_flash_backward(
    tap, monkeypatch, preset, blocks, vmem_cap, path, classes
):
    """The path and the blocks' classes are facts of the compiled step: the
    ``compile`` event names them, from the functions the dispatch asks
    (``xla`` attention: ``none``, and no blocks)."""
    from benchmark import build
    from dlrover_tpu.ops import flash_attention
    from dlrover_tpu.trainer import train_lib
    from dlrover_tpu.trainer.elastic_trainer import (
        ElasticTrainer, TrainerConfig,
    )

    if vmem_cap is not None:
        monkeypatch.setattr(flash_attention, "_VMEM_CAP", vmem_cap)
    cfg = build.load_json(os.path.join(
        REPO, "tests", "benchmark_suite", "presets", f"{preset}.json"
    ))
    seq = cfg["run"]["seq_len"]
    model = build.transformer_config(build.model_group(cfg), seq)
    assert model.attention_impl == "flash"
    assert seq // min(seq, model.flash_block_kv) == blocks

    def flash_facts(model):
        train_lib.reset_build_cache()
        tap.take()
        ElasticTrainer(model, TrainerConfig(
            global_batch_size=jax.device_count(), seq_len=seq,
            optimizer="adafactor", warmup_compile=True, ckpt_every=1000,
        ))
        (event,) = [e for e in tap.take() if e[0] == "compile"]
        return event[-1]["flash_backward"], event[-1]["flash_blocks"]

    strip = flash_attention.block_classes(
        seq, seq, model.flash_block_q, model.flash_block_kv, True
    ).strip
    assert flash_facts(model) == (path, dict(zip(
        ("dead", "interior", "diagonal", "strip"), (*classes, strip)
    )))
    if vmem_cap is None:
        assert flash_facts(
            dataclasses.replace(model, attention_impl="xla", remat="none")
        ) == ("none", None)


def test_olmoe_keeps_its_tree_and_losses():
    """GPT-2's and Mixtral's are held by ``tests/test_olmoe_reference.py``;
    OLMoE's first loss and auxiliary term at the parent commit 3e4dd89."""
    from dlrover_tpu.models.olmoe import olmoe_config

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

    keys = {
        key(),
        key(layer_pattern=("linear_attention", "full_attention")),
        key(linear_num_heads=2), key(linear_key_head_dim=8),
        key(linear_value_head_dim=16), key(linear_conv_kernel=2),
        key(linear_allow_neg_eigval=False), key(norm_placement="pre"),
        key(norm_eps=1e-5),
    }
    assert len(keys) == 9
