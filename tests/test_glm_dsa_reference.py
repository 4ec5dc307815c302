"""GLM-5.2 (the DeepSeek-V3 family's layers with DeepSeek-V3.2's sparse
attention and IndexShare) against its plain reference, on the CPU at a
small size with seeded weights, and each new mechanism on its own: the
choice (a count, not a sort; a planted tie), the attention over it (the
``jax.numpy`` form and the kernels), the KL term and the two detachments,
IndexShare across the dense prefix, two periods and the MTP module, the
shares of heads and experts.  What the configuration refuses and counts is
``tests/test_glm_dsa_config.py``'s."""

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_harness as harness
from dlrover_tpu.models import sparse_attention
from dlrover_tpu.models.attention import LatentAttention
from dlrover_tpu.models.glm_dsa import glm_dsa_config
from dlrover_tpu.models.references import glm_dsa as ref
from dlrover_tpu.models.references import joyai_llm_flash as joyai_ref
from dlrover_tpu.models.transformer import (
    INDEX_ATTENTION,
    REUSE_ATTENTION,
    TransformerLM,
)
from dlrover_tpu.ops import index_select
from dlrover_tpu.ops import sparse_flash_attention as sfa

SEQ, BATCH, VOCAB, TOPK = 32, 2, 256, 8

SMALL = dict(
    vocab_size=VOCAB, num_layers=5, first_k_dense=1, d_model=64, num_heads=4,
    d_ff=96, max_seq_len=SEQ, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_experts=16,
    top_k=4, moe_d_ff=32, experts_held=4, first_expert=4, moe_row_budget=2.0,
    rope_theta=1e4, index_n_heads=3, index_head_dim=16, index_topk=TOPK,
    attn_init_score_std=2.0, index_init_score_std=2.0,
    dtype=jnp.float32, param_dtype=jnp.float32,
)

# Tolerance: both sides are float32 under matmul precision "highest" and
# differ in the order of sums: a loss of ~6 moves by a few float32 ulps
# (2e-6 read).  1e-4 is fifty times that and a hundredth of what any fault
# of ``tests/test_glm_dsa_sharp.py`` moves.
TOL = 1e-4
CHECK = harness.Harness(
    ref, loss_atol=TOL, grad_atol=TOL, grad_rtol=0.0,
    no_gradient=("router_bias",), may_be_zero=("ln_",),
)


def config(**overrides):
    return glm_dsa_config(**{**SMALL, **overrides})


def move(name, leaf, draw):
    """Router biases that pick, and an indexer key norm whose bias shows."""
    if "router_bias" in name:
        return 0.05 * draw(leaf.shape)
    if "k_norm" in name and "bias" in name:
        return 0.1 * draw(leaf.shape)
    return leaf


@functools.cache
def tokens(seq=SEQ):
    return harness.tokens(1, BATCH, seq, VOCAB)


@functools.cache
def weights(**overrides):
    """Seeded weights of ``config(**overrides)``'s tree (which share of the
    experts a config holds changes no shape or value, nor does how many
    keys are kept or how long a sequence may be: ``_tree_of``).  Drawn once
    a trunk: a tree that differs from the default one in the MTP module
    alone is that tree without the module, or without the module's
    indexer."""
    cfg = config(**overrides)
    if overrides and set(overrides) <= {"mtp_depth", "mtp_layer_kind"}:
        tree = dict(weights())
        module = tree.pop("mtp")
        if cfg.mtp_depth:
            assert cfg.mtp_layer_kind == REUSE_ATTENTION
            attn = {
                k: v for k, v in module["block"]["attn"].items()
                if k != "indexer"
            }
            tree["mtp"] = dict(module, block=dict(module["block"], attn=attn))
        return tree
    return harness.init(cfg, tokens()[0], move=move)


@functools.cache
def choices(cfg, seq=SEQ, segments=False):
    """``(each attention layer's mask in the reference's order, each
    choosing layer's [chosen, seen, absmax, L^I])`` of the program."""
    params = weights(**_tree_of(cfg))
    inputs = tokens(seq)[0]
    segment_ids = _segments(seq) if segments else None

    def run(params):
        with jax.default_matmul_precision("highest"):
            return TransformerLM(cfg).apply(
                {"params": params}, inputs, segment_ids=segment_ids,
                next_tokens=tokens(seq)[1] if cfg.mtp_depth else None,
                mutable=["intermediates"],
                capture_intermediates=lambda m, method: isinstance(
                    m, sparse_attention.SparseLatentAttention
                ) and method == "__call__",
            )[1]["intermediates"]

    sown = jax.jit(run)(params)
    layers = list(_layers(sown, cfg.num_scan_units))
    masks = [np.asarray(l["attn"]["__call__"][0][1][0]) != 0 for l in layers]
    stats = [
        np.asarray(l["attn"]["index_stats"][0]) for l in layers
        if "index_stats" in l["attn"]
    ]
    return masks, stats


def _tree_of(cfg):
    """The overrides that shape ``cfg``'s parameter tree."""
    return {
        k: getattr(cfg, k) for k in (
            "num_layers", "mtp_depth", "mtp_layer_kind", "scan_layers",
            "layer_pattern",
        ) if getattr(cfg, k) != getattr(config(), k)
    }


def _segments(seq):
    """Two documents a row, cut at different places."""
    at = np.array([[seq // 3], [seq // 2]])
    return jnp.asarray((np.arange(seq)[None, :] >= at).astype(np.int32))


def _layers(sown, periods):
    def number(name):
        return int(name.rsplit("_", 1)[1])

    for name in sorted((k for k in sown if k.startswith("dense_")), key=number):
        yield sown[name]
    if "blocks" in sown:
        for period in range(periods):
            for slot in sorted(sown["blocks"], key=number):
                yield jax.tree.map(lambda a: a[period], sown["blocks"][slot])
    for name in sorted((k for k in sown if k.startswith("block_")), key=number):
        yield sown[name]
    if "mtp" in sown:
        yield sown["mtp"]["block"]


# -- the whole model against the reference ------------------------------------

# A case is a whole model walked forward and backward, so a configuration
# is there for a property no other has, and TWO models carry the four
# names: the choice shared across the dense prefix and across two periods
# into a module that chooses (nine layers: what ``share`` names holds in
# the first period of it), and the module that reuses a choice under the
# kernels (a kernel runs over a choice whoever made it; the module that
# chooses under the kernels is ``tests/test_glm_dsa_system.py``'s step).
_TWO_PERIODS = dict(num_layers=9)
_KERNELS_AND_REUSE = dict(
    mtp_layer_kind=REUSE_ATTENTION, attention_impl="flash", max_seq_len=128
)
NO_MTP = dict(mtp_depth=0, mtp_layer_kind="")
CASES = {
    "share": _TWO_PERIODS,
    "two_periods": _TWO_PERIODS,
    "mtp_reuses": _KERNELS_AND_REUSE,
    "kernels": _KERNELS_AND_REUSE,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_matches_the_reference_in_float32(case):
    """Per-token nll, the MTP module's, each choosing layer's ``L^I`` and
    every gradient: the main model's, the indexers', the module's."""
    cfg = config(**CASES[case])
    toks = tokens(cfg.max_seq_len)
    params = weights(**_tree_of(cfg))
    CHECK.loss_and_every_gradient_match(cfg, params, toks)
    _, (main, aux, extra), _ = CHECK.loss_and_grads(cfg, params, toks)
    want = CHECK.reference("forward", cfg, params, toks)
    np.testing.assert_allclose(main, want["nll"], atol=TOL)
    if cfg.mtp_depth:
        np.testing.assert_allclose(extra, want["mtp_nll"], atol=TOL)
    assert len(want["index_kl"]) == cfg.num_index_layers
    np.testing.assert_allclose(aux, sum(want["index_kl"]), atol=TOL)
    _, stats = choices(cfg, cfg.max_seq_len)
    np.testing.assert_allclose(
        [s[3] for s in stats], want["index_kl"], atol=TOL
    )
    assert all(float(kl) > 1e-3 for kl in want["index_kl"])
    if case == "kernels":
        calls = harness.pallas_calls(
            CHECK.gradient_program(cfg, params, toks).jaxpr
        )
        # forward, dq and dk / dv of every attention layer (one body for
        # the scanned period's four)
        assert len(calls) >= 3 * 3


def test_the_blocked_passes_walk_runs_of_rows_to_their_own_keys(monkeypatch):
    """The choice and the KL term walk the query rows block by block, each
    of four runs of blocks against the keys up to its own end: eight blocks
    of four rows here, the same losses and gradients."""
    monkeypatch.setattr(sparse_attention, "BLOCK_ROWS", 4)
    assert index_select.key_runs(SEQ, 4) == [
        (0, 2, 8), (2, 2, 16), (4, 2, 24), (6, 2, 32)
    ]
    assert index_select.key_runs(16384, 128)[0] == (0, 32, 4096)
    assert index_select.key_runs(96, 32) == [(0, 3, 96)]
    # a program no other case has compiled, of the layers that walk: a
    # dense and an expert layer, both choosing
    cfg = config(
        index_topk=6, num_layers=2, layer_pattern=(INDEX_ATTENTION,),
        **NO_MTP,
    )
    params = weights(**_tree_of(cfg))
    CHECK.loss_and_every_gradient_match(cfg, params, tokens())
    masks, _ = choices(cfg)
    want = CHECK.reference("forward", cfg, params, tokens())["masks"]
    assert all((a == b).all() for a, b in zip(masks, want))


@pytest.mark.parametrize("case", ["share", "two_periods", "mtp_reuses"])
def test_the_chosen_sets_are_the_reference_s_and_are_shared(case):
    """Float32: every layer's set IS the reference's; a reusing layer's IS
    the nearest choosing layer's before it, across the prefix-to-pattern
    boundary, across two periods and into the MTP module."""
    cfg = config(**CASES[case])
    seq = cfg.max_seq_len
    masks, stats = choices(cfg, seq)
    want = CHECK.reference(
        "forward", cfg, weights(**_tree_of(cfg)), tokens(seq)
    )["masks"]
    assert len(masks) == len(want) == cfg.num_layers + cfg.mtp_depth
    kinds = [cfg.layer_kind(i) for i in range(cfg.num_layers)] + (
        [cfg.mtp_layer_kind] if cfg.mtp_depth else []
    )
    assert kinds[:5] == [INDEX_ATTENTION] + [REUSE_ATTENTION] * 3 + [
        INDEX_ATTENTION
    ]
    last = None
    for kind, ours, theirs in zip(kinds, masks, want):
        assert (ours == theirs).all()
        if kind == REUSE_ATTENTION:
            assert (ours == last).all()
        else:
            assert last is None or (ours != last).any()
        last = ours
    # every query keeps min(t + 1, topk) keys, itself or earlier
    rows = np.minimum(np.arange(seq) + 1, TOPK)
    for ours in masks:
        assert (ours.sum(-1) == rows).all()
        assert not np.triu(ours, 1).any()
    for chosen, seen, _, _ in stats:
        assert chosen == BATCH * rows.sum()
        assert seen == BATCH * seq * (seq + 1) // 2


def test_a_planted_tie_goes_to_the_lower_key():
    """An indexer whose weights' projection is zero scores every key 0:
    program and reference both keep the FIRST ``topk`` keys of a row."""
    cfg = config(**NO_MTP)
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.zeros_like(leaf)
        if "weights_proj" in jax.tree_util.keystr(path) else leaf,
        weights(**_tree_of(cfg)),
    )
    want = CHECK.reference("forward", cfg, params, tokens())["masks"]
    first = np.tril(np.ones((SEQ, SEQ), bool)) & (
        np.arange(SEQ)[None, :] < TOPK
    )
    assert all((m == first[None]).all() for m in want)
    nll = CHECK.reference("token_nll", cfg, params, tokens())
    np.testing.assert_allclose(
        CHECK.nll(cfg, params, tokens()), nll, atol=TOL
    )
    # and the choice alone, ties among scores that are not all equal
    score = jnp.asarray([[3.0, 1.0, 1.0, 1.0, 2.0, 1.0, -0.0, 0.0]])
    valid = jnp.ones((1, 8), bool)
    for topk, keep in ((3, [0, 1, 4]), (4, [0, 1, 2, 4]), (7, range(7))):
        got = np.asarray(index_select.choose(score, valid, topk))[0]
        assert sorted(np.flatnonzero(got)) == sorted(keep), topk
        assert (got == np.asarray(ref.choose(score, valid, topk))[0]).all()


def test_the_choice_by_counting_is_the_choice_by_sorting():
    """Random float32 scores with repeated values, masked rows, rows with
    fewer valid keys than ``topk``."""
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    score = jnp.round(jax.random.normal(keys[0], (4, 64, 96)) * 4) / 4
    valid = jax.random.bernoulli(keys[1], 0.6, score.shape)
    valid = valid.at[:, :8].set(False).at[:, 8:16, 5:].set(False)
    for topk in (1, 7, 40):
        got = jax.jit(index_select.choose, static_argnums=2)(
            score, valid, topk
        )
        assert (np.asarray(got) == np.asarray(
            ref.choose(score, valid, topk)
        )).all()
        assert (np.asarray(got).sum(-1) == np.minimum(
            np.asarray(valid).sum(-1), topk
        )).all()


def test_in_bfloat16_the_sets_agree_at_the_margin():
    """A bfloat16 program picks other keys where scores lie closer than its
    rounding: the agreement is read and bounded, not assumed."""
    cfg = config(dtype=jnp.bfloat16)
    masks, _ = choices(cfg)
    want, _ = choices(config())
    shares = [
        float((a & b).sum() / b.sum()) for a, b in zip(masks, want)
    ]
    # read at these sizes: 0.95 to 0.99 in the first layer, 0.85 to 0.95
    # after four layers of a stream that bfloat16 has already moved
    assert min(shares) > 0.7 and shares[0] > 0.9, shares
    assert max(shares) < 1.0 or cfg.index_topk >= SEQ


# -- the two detachments -------------------------------------------------------


def test_each_loss_trains_its_own_parameters_and_no_other():
    """``d L_LM / d(indexer weights)`` is exactly zero, ``d L^I /
    d(everything outside the indexers)`` is exactly zero."""
    cfg, params, toks = config(), weights(), tokens()

    def parts(p):
        nll, aux, mtp = harness.program_outputs(cfg, p, *toks)
        return nll.mean() + cfg.mtp_weight * mtp.mean(), aux

    lm, kl = jax.jit(jax.jacrev(parts))(params)
    seen = {True: 0, False: 0}
    for (path, g_lm), g_kl in zip(
        jax.tree_util.tree_leaves_with_path(lm), jax.tree.leaves(kl)
    ):
        name = jax.tree_util.keystr(path)
        indexer = "indexer" in name
        seen[indexer] += 1
        if indexer:
            assert not np.asarray(g_lm).any(), name
            assert np.asarray(g_kl).any(), name
        else:
            assert not np.asarray(g_kl).any(), name
    # an indexer in the dense layer, the period's last and the module's
    assert seen[True] == 3 * 5 and seen[False] > 30


# -- IndexShare's parameters ---------------------------------------------------


def test_only_a_choosing_layer_holds_an_indexer_and_is_counted():
    cfg = config()
    params = weights()
    assert "indexer" in params["dense_0"]["attn"]
    assert "indexer" in params["mtp"]["block"]["attn"]
    for slot, layer in params["blocks"].items():
        assert ("indexer" in layer["attn"]) == slot.startswith("index"), slot
    one = sum(
        leaf.size for leaf in jax.tree.leaves(
            params["dense_0"]["attn"]["indexer"]
        )
    )
    every = config(layer_pattern=(INDEX_ATTENTION,))
    assert every.num_index_layers == 6 and cfg.num_index_layers == 3
    assert every.num_params() - cfg.num_params() == 3 * one
    held = sum(leaf.size for leaf in jax.tree.leaves(params))
    norms = sum(
        leaf.size for path, leaf in jax.tree_util.tree_leaves_with_path(params)
        if "ln_" in jax.tree_util.keystr(path)
    )
    assert cfg.num_params() == held - norms


# -- rows with few keys, and the model without a choice ------------------------


def test_where_every_key_is_kept_the_layer_is_dense_latent_attention():
    """Rows ``t < topk`` of a sparse layer are dense latent attention's;
    with ``index_topk >= T`` the whole model is the sibling family's block
    (JoyAI's reference at these sizes)."""
    cfg = config()
    n = jax.random.normal(jax.random.PRNGKey(7), (BATCH, SEQ, cfg.d_model))
    sparse = sparse_attention.from_config(cfg, INDEX_ATTENTION)
    dense = LatentAttention(**{
        f.name: getattr(sparse, f.name)
        for f in dataclasses.fields(LatentAttention)
        if f.name not in ("parent", "name")
    })
    # (a program each: op by op a layer is a compile for every operation)
    variables = jax.jit(sparse.init)(jax.random.PRNGKey(8), n)
    with jax.default_matmul_precision("highest"):
        got, index = jax.jit(sparse.apply)(variables, n)
        params = dict(nn.meta.unbox(variables["params"]))
        params.pop("indexer")
        want = jax.jit(dense.apply)({"params": params}, n)
    np.testing.assert_allclose(got[:, :TOPK], want[:, :TOPK], atol=1e-5)
    assert float(jnp.abs(got[:, TOPK:] - want[:, TOPK:]).max()) > 1e-2
    assert float(index.kl) > 0
    whole = config(index_topk=SEQ, scan_layers=False)
    params = weights(**_tree_of(whole))
    fields = dataclasses.asdict(whole)
    with jax.default_matmul_precision("highest"):
        sibling = jax.jit(functools.partial(joyai_ref.forward, fields))(
            params, *tokens()
        )
    nll, _, mtp = CHECK.outputs(whole, params, tokens())
    np.testing.assert_allclose(nll, sibling["nll"], atol=TOL)
    np.testing.assert_allclose(mtp, sibling["mtp_nll"], atol=TOL)


def test_no_key_of_another_document_is_chosen():
    cfg = config(**NO_MTP)
    masks, stats = choices(cfg, segments=True)
    seg = np.asarray(_segments(SEQ))
    same = seg[:, :, None] == seg[:, None, :]
    valid = same & np.tril(np.ones((SEQ, SEQ), bool))[None]
    for mask in masks:
        assert not (mask & ~same).any()
        assert (mask.sum(-1) == np.minimum(valid.sum(-1), TOPK)).all()
    assert all(s[1] == valid.sum() for s in stats)
    # the reference's choice on a layer's own inputs, with the ids
    n = jax.random.normal(jax.random.PRNGKey(9), (BATCH, SEQ, cfg.d_model))
    layer = sparse_attention.from_config(cfg, INDEX_ATTENTION)
    variables = jax.jit(layer.init)(jax.random.PRNGKey(10), n)
    with jax.default_matmul_precision("highest"):
        _, index = jax.jit(layer.apply)(variables, n, None, jnp.asarray(seg))
        p = nn.meta.unbox(variables["params"])
        _, _, _, c_q = ref.latent_qkv(dataclasses.asdict(cfg), n, p)
        want = ref.selection(
            dataclasses.asdict(cfg), n, c_q, p["indexer"], jnp.asarray(seg)
        )
    assert (np.asarray(index.mask != 0) == np.asarray(want)).all()


# -- the kernels alone ----------------------------------------------------------


@pytest.mark.parametrize("d,d_v", [(32, 32), (24, 16)])
def test_the_sparse_kernels_are_the_masked_softmax(d, d_v):
    """Forward, log-sum-exp and the three gradients of the kernels over a
    choice (two kv blocks, a dead block above the diagonal) against the
    ``jax.numpy`` form."""
    t, heads, topk = 256, 2, 40
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    q = jax.random.normal(keys[0], (BATCH, t, heads, d))
    k = jax.random.normal(keys[1], (BATCH, t, heads, d))
    v = jax.random.normal(keys[2], (BATCH, t, heads, d_v))
    mask, picked = index_select.choose_blocked(
        jax.random.normal(keys[3], (BATCH, t, 3, 8)),
        jax.random.normal(keys[4], (BATCH, t, 8)),
        jax.random.normal(keys[5], (BATCH, t, 3)), None, topk, 64,
    )
    assert float(picked[0]) == BATCH * np.minimum(
        np.arange(t) + 1, topk
    ).sum()
    scale = d ** -0.5

    def loss(fn, q, k, v):
        out, lse = fn(q, k, v)
        return (out ** 2).sum(), (out, lse)

    kernels = functools.partial(sfa.mha, mask=mask, scale=scale, block=128)
    plain = functools.partial(
        sparse_attention.masked_attention, mask=mask, scale=scale
    )
    with jax.default_matmul_precision("highest"):
        got, (out, lse) = jax.jit(jax.grad(
            functools.partial(loss, kernels), (0, 1, 2), has_aux=True
        ))(q, k, v)
        want, (out_w, lse_w) = jax.grad(
            functools.partial(loss, plain), (0, 1, 2), has_aux=True
        )(q, k, v)
    np.testing.assert_allclose(out, out_w, atol=1e-5)
    np.testing.assert_allclose(lse, lse_w, atol=1e-5)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5)
    assert sfa.block_size(16384, 512) == 512
    assert sfa.block_size(640, 512) == 128 and sfa.block_size(96, 512) == 0


def _late_mask(t, keys):
    """Each row's last ``keys`` keys: a row past the first block has no
    chosen key in the first blocks of its walk."""
    rows, cols = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (cols <= rows) & (cols > rows - keys)
    return jnp.broadcast_to(jnp.asarray(seen, jnp.int8), (BATCH, t, t))


@pytest.mark.parametrize("d,d_v,t,choice", [
    (64, 64, 384, "indexer"), (48, 32, 384, "indexer"),
    (64, 64, 384, "late"), (48, 32, 384, "late"),
    (64, 64, 128, "indexer"),       # one kv block: no dq scratch
])
def test_both_backward_paths_give_the_masked_softmax_gradients(
    d, d_v, t, choice
):
    """The one pass and the split pair on the same ``q, k, v, mask, do``
    (three kv blocks of 128, or one): dq, dk and dv of the one pass are the
    pair's, term for term, and both are the ``jax.numpy`` form's."""
    heads, topk, block = 2, 40, 128
    keys = jax.random.split(jax.random.PRNGKey(21), 7)
    q = jax.random.normal(keys[0], (BATCH, t, heads, d))
    k = jax.random.normal(keys[1], (BATCH, t, heads, d))
    v = jax.random.normal(keys[2], (BATCH, t, heads, d_v))
    do = jax.random.normal(keys[3], (BATCH, t, heads, d_v))
    if choice == "late":
        mask = _late_mask(t, topk)
    else:
        mask, _ = index_select.choose_blocked(
            jax.random.normal(keys[4], (BATCH, t, 3, 8)),
            jax.random.normal(keys[5], (BATCH, t, 8)),
            jax.random.normal(keys[6], (BATCH, t, 3)), None, topk, 64,
        )
    scale = d ** -0.5
    by_head = [x.transpose(0, 2, 1, 3) for x in (q, k, v, do)]
    with jax.default_matmul_precision("highest"):
        o, lse = sfa._flash_fwd(
            *by_head[:3], mask, scale=scale, block=block
        )
        one_pass, split = (
            impl(
                *by_head[:3], mask, o, lse, by_head[3], scale=scale,
                block=block,
            )
            for impl in (sfa._flash_bwd_one_pass, sfa._flash_bwd)
        )
        (_, lse_w), vjp = jax.vjp(
            functools.partial(
                sparse_attention.masked_attention, mask=mask, scale=scale
            ),
            q, k, v,
        )
        want = vjp((do, jnp.zeros_like(lse_w)))
    for one, pair, w in zip(one_pass, split, want):
        np.testing.assert_array_equal(one, pair)
        np.testing.assert_allclose(
            one.transpose(0, 2, 1, 3), w, atol=2e-5
        )
    # which of the two ``mha`` runs is the shapes' alone, and a step's
    # program holds the forward's call and that path's
    assert sfa.backward_path(t, d, d_v, block, q.dtype) == "one_pass"
    program = str(jax.make_jaxpr(jax.grad(
        lambda q: sfa.mha(q, k, v, mask, scale=scale, block=block)[0].sum()
    ))(q))
    assert program.count("pallas_call") == 2


def test_the_sparse_backward_path_on_either_side_of_its_bound(monkeypatch):
    """The bound is the VMEM the module asks for, 64 MiB: at 256-wide keys
    and values in bfloat16 and blocks of 512 the cell's 16,384 tokens take
    the one pass (41.5 MiB), twice that keep the pair (73.5)."""
    def path(seq, d=256, d_v=256, block=512, dtype=jnp.bfloat16):
        return sfa.backward_path(seq, d, d_v, block, dtype)

    assert path(16384) == "one_pass" and path(32768) == "split"
    assert sfa._one_pass_vmem_bytes(
        16384, 256, 256, 512, jnp.bfloat16
    ) == 83 << 19 <= sfa._VMEM_LIMIT
    # the longest that fits: dq takes 2 KiB a token beside 9.5 MiB
    assert path(27648) == "one_pass" and path(28160) == "split"
    # narrower keys and values (192 / 128 occupy 256 / 128 lanes) go further
    assert path(32768, 192, 128) == "split" != path(24576, 192, 128)
    # float32 blocks and a float32 dq output: 3 KiB a token beside 13 MiB
    assert path(16384, dtype=jnp.float32) == "one_pass"
    assert path(20480, dtype=jnp.float32) == "split"
    # one kv block keeps no dq across blocks: the one pass at any length
    assert path(1 << 20, block=1 << 20) == "one_pass"
    # past the bound ``mha`` runs the pair: three calls in the program
    monkeypatch.setattr(sfa, "_VMEM_LIMIT", 1 << 16)
    x = jnp.zeros((1, 256, 1, 32))
    program = str(jax.make_jaxpr(jax.grad(
        lambda q: sfa.mha(
            q, x, x, jnp.ones((1, 256, 256), jnp.int8), scale=1.0, block=128
        )[0].sum()
    ))(x))
    assert sfa.backward_path(256, 32, 32, 128, x.dtype) == "split"
    assert program.count("pallas_call") == 3


# -- the shares add up ----------------------------------------------------------


def test_the_head_shares_add_up_to_the_uncut_layer():
    """Four shares of the heads (``q_b``, ``kv_b``, ``wo`` by head; the
    latents, the indexer and so the choice whole on every chip): their
    outputs sum to the uncut reference's layer, and their ``sum_h A_h`` over
    4 x 2 heads, divided by 8, is the uncut ``p_t``."""
    heads, share = 8, 2
    cfg = config(num_heads=heads)
    fields = dataclasses.asdict(cfg)
    n = jax.random.normal(jax.random.PRNGKey(12), (BATCH, SEQ, cfg.d_model))
    whole_layer = sparse_attention.from_config(cfg, INDEX_ATTENTION)
    whole = nn.meta.unbox(
        jax.jit(whole_layer.init)(jax.random.PRNGKey(13), n)["params"]
    )

    def cut(first):
        heads_of = slice(first, first + share)
        part = dict(whole)
        part["q_b"] = {"kernel": whole["q_b"]["kernel"][:, heads_of]}
        part["kv_b"] = {"kernel": whole["kv_b"]["kernel"][:, heads_of]}
        part["wo"] = {"kernel": whole["wo"]["kernel"][heads_of]}
        return part

    with jax.default_matmul_precision("highest"):
        want, mask, term = ref.sparse_attention(
            fields, n, whole, INDEX_ATTENTION, None
        )
        q, k, v, c_q = ref.latent_qkv(fields, n, whole)
        _, p_whole = ref.attention_over(fields, q, k, v, mask)
        # the term alone, from the uncut target, is the layer's own
        assert float(ref.index_kl(
            fields, n, c_q, whole["indexer"], mask, p_whole
        )) == pytest.approx(float(term), abs=1e-6)
        layer = sparse_attention.from_config(
            config(num_heads=share), INDEX_ATTENTION
        )
        apply = jax.jit(lambda part: layer.apply({"params": part}, n))
        total, p_sum = 0.0, 0.0
        for first in range(0, heads, share):
            out, index = apply(cut(first))
            # what every chip computes alike is the same on every chip
            assert (np.asarray(index.mask != 0) == np.asarray(mask)).all()
            total = total + out
            q, k, v, _ = ref.latent_qkv(
                dict(fields, num_heads=share), n, cut(first)
            )
            _, p = ref.attention_over(fields, q, k, v, mask)
            p_sum = p_sum + share * p
    np.testing.assert_allclose(total, want, atol=1e-5)
    np.testing.assert_allclose(p_sum / heads, p_whole, atol=1e-6)


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """The routed parts of all 32 shares of one expert each, plus the
    shared expert counted once, are the uncut reference's layer."""
    from dlrover_tpu.models import moe as moe_lib

    total, held, d, width = 32, 1, 64, 32
    keys = jax.random.split(jax.random.PRNGKey(5), 8)
    n = jax.random.normal(keys[0], (BATCH, SEQ, d))
    whole = {
        "router": {"kernel": jax.random.normal(keys[1], (d, total))},
        "router_bias": 0.05 * jax.random.normal(keys[2], (total,)),
        "wi": jax.random.normal(keys[3], (total, d, width)) / 8,
        "wg": jax.random.normal(keys[4], (total, d, width)) / 8,
        "wo": jax.random.normal(keys[5], (total, width, d)) / 6,
        "shared": {
            name: {"kernel": jax.random.normal(key, shape) / 8}
            for name, key, shape in (
                ("wi", keys[6], (d, width)), ("wg", keys[7], (d, width)),
                ("wo", keys[0], (width, d)),
            )
        },
    }
    cfg = config(
        num_experts=total, experts_held=held, first_expert=0, top_k=4,
        moe_row_budget=8.0,
    )
    fields = dict(dataclasses.asdict(cfg), first_expert=0)
    with jax.default_matmul_precision("highest"):
        shared = ref.swiglu(n, whole["shared"])
    harness.shares_add_up(
        ref, fields, n, whole, held,
        lambda first: moe_lib.from_config(cfg).clone(first_expert=first),
        shared, atol=1e-5,
    )
