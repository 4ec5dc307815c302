"""Command A+'s model (a PARALLEL block under one bias-free LayerNorm; three
sliding-window attention layers that rotate to one full-attention layer
WITHOUT positions; a sigmoid router over a share of the experts beside
shared experts that are AVERAGED, of which a share is held) against its
plain reference, at one period of tiny widths on the CPU with seeded float32
weights: per-token loss, the loss and every gradient; the rotation's two
pairings; the sixteen chips' shares of one layer.  Each fault the comparison
must catch is ``tests/test_command_a_sharp.py``'s; the train step, the
events, a save and a restore ``tests/test_command_a_system.py``'s; what the
configuration refuses and counts ``tests/test_command_a_config.py``'s."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_harness as harness
from dlrover_tpu.models import attention as attention_lib
from dlrover_tpu.models import layers
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.command_a import command_a_config
from dlrover_tpu.models.references import command_a as ref

SEQ, BATCH, VOCAB = 40, 2, 256
# float32 on both sides under matmul precision "highest": what is left is
# the order of the sums (a head, a block of rows or an expert at a time).
TOL = 1e-4
CHECK = harness.Harness(ref, loss_atol=TOL, grad_atol=2e-5, grad_rtol=2e-4)

# ONE period of sliding x 3, full; heads of 16, four over two; a window of
# 12 keys (no multiple of the flash cases' blocks of 8, and wider than one);
# 16 experts of 32, 4 a token, 4 held from the second share on; 4 shared
# experts of 32 of which ONE is held, still divided by 4
SMALL = dict(
    vocab_size=VOCAB, num_layers=4, d_model=64, num_heads=4, num_kv_heads=2,
    head_dim=16, d_ff=96, max_seq_len=64, rope_theta=100.0, moe_d_ff=32,
    sliding_window=12, num_experts=16, top_k=4, experts_held=4,
    first_expert=4, shared_experts_held=1, moe_row_budget=4.0,
    dtype=jnp.float32, param_dtype=jnp.float32,
)
UNCUT = dict(experts_held=0, first_expert=0, shared_experts_held=0)


def config(**overrides):
    return command_a_config(**{**SMALL, **overrides})


def move(name, leaf, draw):
    """Queries and keys large enough that the softmax is peaked (a mask or
    a rotation then moves a token's loss), experts and routers large enough
    beside the mixers that a fault in the gates does, norm scales off 1."""
    if "['query']" in name or "['key']" in name:
        return leaf * 4.0
    if "['moe']" in name:
        return leaf * 4.0
    if name.endswith("['scale']"):
        return leaf + 0.3 * draw(leaf.shape)
    return leaf


@functools.cache
def seeded():
    """(tokens, weights of the uncut model)."""
    rows = harness.tokens(1, BATCH, SEQ, VOCAB)
    return rows, harness.init(config(**UNCUT), rows[0], move=move)


def held(params, cfg):
    """``params`` cut to ``cfg``'s share: the routed experts
    (``harness.held``) and the first ``shared_experts_held`` shared experts,
    which are runs of ``moe_d_ff`` columns of the one shared MLP."""
    width = cfg.resolved_shared_d_ff

    def cut(path, leaf):
        name = jax.tree_util.keystr(path)
        if "['shared']" not in name:
            return leaf
        if "['wo']" in name:                         # [..., width, d]
            return leaf[..., :width, :]
        return leaf[..., :width]                     # [..., d, width]

    return jax.tree_util.tree_map_with_path(cut, harness.held(params, cfg))


@functools.cache
def share(cfg):
    """The seeded weights cut to ``cfg``'s share."""
    return held(seeded()[1], cfg)


@pytest.fixture(scope="module")
def tokens():
    return seeded()[0]


CASES = {
    "share": {},
    "whole": UNCUT,
    # blocks of 8 under a window of 12: wider than a block, no multiple
    "flash": dict(
        attention_impl="flash", flash_block_q=8, flash_block_kv=8,
    ),
    # a window SMALLER than the block: one block carries both edges
    "flash_narrow": dict(
        attention_impl="flash", flash_block_q=8, flash_block_kv=8,
        sliding_window=5,
    ),
}
GRADIENTS = ("share", "flash")


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_matches_the_reference_in_float32(case, tokens):
    cfg = config(**CASES[case])
    weights = share(cfg)
    if case not in GRADIENTS:
        assert CHECK.nll_gap(cfg, weights, tokens) <= TOL
        return
    _, (main, aux, _), _ = CHECK.loss_and_grads(cfg, weights, tokens)
    want = CHECK.reference("forward", cfg, weights, tokens)
    np.testing.assert_allclose(main, want["nll"], atol=TOL)
    assert float(aux) == 0.0 == float(want["balance"])   # a sigmoid router
    CHECK.loss_and_every_gradient_match(cfg, weights, tokens)


def test_the_unrolled_trunk_is_the_scanned_one(tokens):
    cfg = config(scan_layers=False)
    weights = harness.init(cfg, tokens[0], seed=3, move=move)
    assert "block_3" in weights and "block_4" not in weights
    assert sorted(weights["block_0"]) == ["attn", "ln", "moe"]
    assert CHECK.nll_gap(cfg, weights, tokens) <= TOL


def test_the_tree_has_one_bias_free_norm_a_layer(tokens):
    weights = share(config())
    for slot in ("sliding_0", "sliding_1", "sliding_2", "full_3"):
        layer = weights["blocks"][slot]
        assert sorted(layer) == ["attn", "ln", "moe"]
        assert sorted(layer["ln"]) == ["scale"]
    assert sorted(weights["ln_final"]) == ["scale"]
    # ONE shared MLP, as wide as the shared experts held
    shared = weights["blocks"]["full_3"]["moe"]["shared"]
    assert shared["wi"]["kernel"].shape == (1, 64, 32)
    whole = seeded()[1]["blocks"]["full_3"]["moe"]["shared"]
    assert whole["wo"]["kernel"].shape == (1, 4 * 32, 64)


# -- the norm and the rotation --------------------------------------------------


def test_the_bias_free_layernorm_is_the_reference_s(rng):
    x = jnp.asarray(3.0 + 2.0 * rng.normal(size=(2, 5, 64)), jnp.float32)
    norm = layers.make_norm(
        "layernorm", jnp.float32, jnp.float32, "ln", epsilon=1e-5,
        use_bias=False,
    )
    params = nn.meta.unbox(norm.init(jax.random.PRNGKey(0), x))["params"]
    assert sorted(params) == ["scale"]
    scale = params["scale"] + jnp.asarray(rng.normal(size=64), jnp.float32)
    got = norm.apply({"params": {"scale": scale}}, x)
    np.testing.assert_allclose(
        got, ref.layer_norm(x, scale, 1e-5), atol=1e-6
    )
    assert abs(float(got.mean())) < 1.0      # the mean is taken out ...
    kept = ref.layer_norm(x, scale, 1e-5, wrong="layernorm_keeps_mean")
    assert float(jnp.abs(kept - got).max()) > 0.1     # ... an RMSNorm keeps it
    # the default builds the bias it always built (GPT-2's)
    biased = layers.make_norm("layernorm", jnp.float32, jnp.float32, "ln")
    assert sorted(
        nn.meta.unbox(biased.init(jax.random.PRNGKey(0), x))["params"]
    ) == ["bias", "scale"]


def test_rotate_half_is_the_published_pairing_under_the_permutation(rng):
    """The program turns columns ``(i, i + hd/2)``, the published
    ``rope_gptj`` columns ``(2i, 2i + 1)``: one fixed permutation of a
    head's columns apart, and scores do not see a permutation q and k
    share."""
    q = jnp.asarray(rng.normal(size=(2, 24, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 24, 2, 16)), jnp.float32)
    positions = jnp.arange(24)[None, :]
    halves = layers.rotary_embedding(
        q, k, positions, *layers.Rotation(100.0).table(16)
    )
    for ours, x in zip(halves, (q, k)):
        np.testing.assert_allclose(
            ref.to_published_pairing(ours),
            ref.rotate(ref.to_published_pairing(x), 100.0), atol=1e-5,
        )
    # the permutation: program column i -> 2i, i + 8 -> 2i + 1
    columns = ref.to_published_pairing(jnp.arange(16.0))
    assert [int(c) for c in columns] == [
        0, 8, 1, 9, 2, 10, 3, 11, 4, 12, 5, 13, 6, 14, 7, 15
    ]
    q_rot, k_rot = halves
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_rot[:, :, :2], k_rot)
    published = jnp.einsum(
        "bqhd,bkhd->bhqk",
        ref.rotate(ref.to_published_pairing(q[:, :, :2]), 100.0),
        ref.rotate(ref.to_published_pairing(k), 100.0),
    )
    np.testing.assert_allclose(scores, published, atol=1e-4)


def test_a_kind_without_rotation_stands_beside_one_that_rotates():
    cfg = config()
    assert cfg.rotation("sliding_attention") == layers.Rotation(100.0)
    assert cfg.rotation("full_attention") is None
    sliding = attention_lib.from_config(cfg, "sliding_attention")
    full = attention_lib.from_config(cfg, "full_attention")
    assert (sliding.use_rope, sliding.window) == (True, 12)
    assert (full.use_rope, full.window, full.rotation) == (False, 0, None)
    assert full.score_stats and sliding.score_stats
    assert attention_lib.rotation_of(cfg, "full_attention") == "none"
    assert attention_lib.rotation_of(cfg, "sliding_attention") == "rope"


# -- the shares ----------------------------------------------------------------


def test_the_sixteen_chips_shares_add_up_to_the_uncut_layer():
    """One layer on sixteen chips: the heads and the shared experts over 4
    (two query heads over one key/value head and ONE whole shared expert a
    share, its output over the PUBLISHED 4), the routed experts over 16 (two
    a chip).  The shares' partial sums, with the norm and the residual
    counted once, are the uncut reference's layer."""
    d, heads, kv, hd, width, experts, top_k = 32, 8, 4, 8, 16, 32, 4
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 16))

    def draw(*shape, scale=1.0):
        return scale * jax.random.normal(next(keys), shape)

    x = draw(BATCH, 24, d)
    gain = 1.0 + 0.3 * draw(d)
    whole = {
        "ln": {"scale": gain},
        "attn": {
            "query": {"kernel": draw(d, heads, hd, scale=0.6)},
            "key": {"kernel": draw(d, kv, hd, scale=0.6)},
            "value": {"kernel": draw(d, kv, hd, scale=0.3)},
            "out": {"kernel": draw(heads, hd, d, scale=0.3)},
        },
        "moe": {
            "router": {"kernel": draw(d, experts)},
            "wi": draw(experts, d, width, scale=0.2),
            "wg": draw(experts, d, width, scale=0.2),
            "wo": draw(experts, width, d, scale=0.2),
            "shared": {
                "wi": {"kernel": draw(d, 4 * width, scale=0.2)},
                "wg": {"kernel": draw(d, 4 * width, scale=0.2)},
                "wo": {"kernel": draw(4 * width, d, scale=0.2)},
            },
        },
    }
    fields = dict(
        num_experts=experts, top_k=top_k, norm_topk_prob=True,
        num_shared_experts=4, moe_d_ff=width, sliding_window=7,
        rope_theta=100.0, norm_eps=1e-5,
    )
    with jax.default_matmul_precision("highest"):
        n = ref.layer_norm(x, gain, 1e-5)
        want = (
            x + ref.attention(fields, n, whole["attn"], "band", True)
            + ref.expert_layer(fields, n, whole["moe"])[0]
        )
        # what every chip computes alike is counted once: norm, residual
        norm = layers.make_norm(
            "layernorm", jnp.float32, jnp.float32, "ln", use_bias=False
        )
        n_here = norm.apply({"params": whole["ln"]}, x)
        got = x
        for s in range(4):                      # heads and shared over 4
            attn = attention_lib.Attention(
                num_heads=heads // 4, num_kv_heads=kv // 4, head_dim=hd,
                rope_theta=100.0, window=7, dtype=jnp.float32,
            )
            h, g = slice(2 * s, 2 * s + 2), slice(s, s + 1)
            p = whole["attn"]
            got = got + attn.apply({"params": {
                "query": {"kernel": p["query"]["kernel"][:, h]},
                "key": {"kernel": p["key"]["kernel"][:, g]},
                "value": {"kernel": p["value"]["kernel"][:, g]},
                "out": {"kernel": p["out"]["kernel"][h]},
            }}, n_here)
        # a chip's layer with a shared expert and without, a program each
        # for all the chips of its form
        apply = {
            has_shared: harness.share_program(
                lambda first, has_shared=has_shared: moe_lib.MoEMlp(
                    num_experts=experts, d_ff=width, top_k=top_k,
                    dispatch="grouped", scoring="sigmoid", experts_held=2,
                    first_expert=first, row_budget_multiple=16.0,
                    shared_d_ff=width if has_shared else 0,
                    shared_scale=0.25, dtype=jnp.float32, gmm_block_rows=8,
                )
            ) for has_shared in (True, False)
        }
        pairs = 0.0
        for chip in range(16):                  # routed over 16
            first, cols = 2 * chip, slice(chip * width, (chip + 1) * width)
            part = {
                "router": whole["moe"]["router"],
                **{w: whole["moe"][w][first:first + 2]
                   for w in ("wi", "wg", "wo")},
            }
            has_shared = chip < 4               # chip s holds shared expert s
            if has_shared:
                shared = whole["moe"]["shared"]
                part["shared"] = {
                    "wi": {"kernel": shared["wi"]["kernel"][:, cols]},
                    "wg": {"kernel": shared["wg"]["kernel"][:, cols]},
                    "wo": {"kernel": shared["wo"]["kernel"][cols]},
                }
            (out, aux), sown = apply[has_shared](part, n_here, first)
            stats = sown["intermediates"]
            assert float(moe_lib.split_stats(stats["moe_stats"][0])[1]) == 0.0
            pairs += float(stats[moe_lib.SHARE_STATS_NAME][0][0])
            assert float(aux) == 0.0
            ours = ref.expert_layer(
                dict(fields, first_expert=first), n, part
            )[0]
            np.testing.assert_allclose(out, ours, atol=TOL)
            got = got + out
    np.testing.assert_allclose(got, want, atol=TOL)
    assert pairs == pytest.approx(1.0)
