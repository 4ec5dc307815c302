"""The models the program already ran are what they were: sha256 of the
lowered step text of the benchmark's tiny presets, pinned by the PRs that
changed a step on purpose, and OLMoE's tree and first losses.  (Moved here
whole from ``tests/test_olmo_hybrid_reference.py`` and
``tests/test_granite_moe_hybrid_reference.py`` by PR 42: the hashes are
those files', unedited.)"""

import hashlib
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.transformer import TransformerConfig, TransformerLM

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


# sha256 of the lowered step text of the two tiny presets that
# the table above does not hold (its ``lowered_step_text``): JoyAI-LLM-Flash's
# (latent attention, the sigmoid router, a share) and Nemotron's, whose text
# holds its scan kernels' grids and index maps (one tile a group).  Both are
# the texts since PR 45, which took the gather, its scatter and ``top_k``'s
# sort out of the sigmoid router (``models/moe.py::_gate``): at the parent
# 0ab4b77 they read 680dda30...835c48d (as at fed8b01: nothing else in the
# step had moved) and 1ac0af4f...e16b47b4 (PR 40's scan kernels); the
# presets that route by softmax or not at all, the four above and Granite's,
# kept the parent's texts (CHANGES.md, PR 45).  Whoever edits the router or
# the scan kernels next re-pins them.
LATER_PRESETS_LOWERED = {
    "joyai-llm-flash":
        "63af0acfb70a6311d09581ecbce514fc91fea6dfa3d15f6c04ad2fa9706c9955",
    "nemotron-3-nano-30b-a3b":
        "80d960d277046e5ad1b5448296088ed0d3696374b89f651e741461bf439bf378",
}


@pytest.mark.parametrize("preset", sorted(LATER_PRESETS_LOWERED))
def test_the_multipliers_and_the_tiles_default_to_nothing(preset):
    """A config that names none of the four multipliers, and a scan whose
    group is one grid step, lower to the step they lowered to (the other
    four pinned texts are held above)."""
    cfg = TransformerConfig()
    assert (cfg.embed_scale, cfg.attention_scale, cfg.residual_scale,
            cfg.logit_scale) == (1.0, 0.0, 1.0, 1.0)
    text = lowered_step_text(preset)
    assert hashlib.sha256(text.encode()).hexdigest() == (
        LATER_PRESETS_LOWERED[preset]
    )


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
