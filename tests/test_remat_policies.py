"""Remat-policy tests: registry, parity across all policies, and
recompute elision.

The round-4 perf work (PROFILE.md) saves the flash kernel's own outputs
(o, lse) as named remat targets so the backward replay drops the attention
forward recompute; the remat-policy subsystem (ops/remat_policy.py)
generalizes that into named save-only policies.
These tests pin down (a) gradient equivalence across every registered
policy, (b) what the registry and the config refuse, and (c) that the
named saveables actually exist in the jaxpr.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.gpt2 import gpt2_config
from dlrover_tpu.models.transformer import TransformerConfig, TransformerLM
from dlrover_tpu.ops import remat_policy as rp


def _tiny(remat: str, impl: str = "flash"):
    cfg = gpt2_config(
        "124m", num_layers=2, d_model=64, num_heads=2, vocab_size=128,
        max_seq_len=64, param_dtype=jnp.float32,
        remat=remat, attention_impl=impl,
        flash_block_q=32, flash_block_kv=32,
    )
    return TransformerLM(cfg), cfg


@functools.lru_cache(maxsize=None)
def _loss_and_grads(remat: str, impl: str = "flash"):
    # Cached: the parametrized parity sweep reuses the "none" reference
    # instead of re-tracing the same jit per test — each trace is seconds
    # of CPU compile time.
    model, cfg = _tiny(remat, impl)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), tokens)

    def loss(p):
        logits, aux = model.apply(p, tokens)
        return jnp.mean(logits.astype(jnp.float32) ** 2) + aux

    l, g = jax.jit(jax.value_and_grad(loss))(params)
    return l, g


@pytest.mark.parametrize("remat", ["flash_only", "flash_res"])
def test_flash_policies_match_attn_out_grads(remat):
    l_ref, g_ref = _loss_and_grads("attn_out")
    l, g = _loss_and_grads(remat)
    np.testing.assert_allclose(float(l), float(l_ref), rtol=1e-5)
    flat_ref = jax.tree_util.tree_leaves(g_ref)
    flat = jax.tree_util.tree_leaves(g)
    for a, b in zip(flat, flat_ref):
        np.testing.assert_allclose(
            np.asarray(a, np.float64), np.asarray(b, np.float64),
            rtol=2e-4, atol=2e-6,
        )


@pytest.mark.parametrize(
    "remat",
    [
        # flash_only recompiles the Pallas kernel in the bwd pass (~12s on
        # 1 core) and is already graded against attn_out grads below;
        # flash_res (~16s) likewise — attn_out stays the tier-1 witness
        # here.
        pytest.param(p, marks=pytest.mark.slow)
        if p in ("flash_only", "flash_res") else p
        for p in rp.available()
    ],
)
def test_every_registered_policy_matches_none_grads(remat):
    """Loss/grad parity for EVERY policy the registry knows against the
    no-remat baseline — the same harness as the pipeline parity tests,
    rtol 2e-3.

    Non-flash policies run under xla attention (the interpreted flash
    kernel dominates CPU compile time and adds nothing to a remat parity
    check); flash-name policies need the flash kernel's named residuals.
    """
    impl = "flash" if rp.resolve(remat).requires_flash else "xla"
    l_ref, g_ref = _loss_and_grads("none", impl)
    l, g = _loss_and_grads(remat, impl)
    np.testing.assert_allclose(float(l), float(l_ref), rtol=2e-3)
    for a, b in zip(
        jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(g_ref)
    ):
        np.testing.assert_allclose(
            np.asarray(a, np.float64), np.asarray(b, np.float64),
            rtol=2e-3, atol=1e-5,
        )


def test_registry_resolves_and_canonicalizes():
    assert len(rp.available()) == 8
    for name in rp.available():
        assert rp.resolve(name).name == name
    with pytest.raises(ValueError, match="remat must be one of"):
        rp.resolve("bogus_policy")
    # Flash-name policies are rejected under non-flash impls.
    with pytest.raises(ValueError, match="attention_impl='flash'"):
        rp.validate("flash_res", attention_impl="xla")
    with pytest.raises(ValueError, match="attention_impl='flash'"):
        TransformerConfig(remat="flash_only", attention_impl="xla")


@pytest.mark.parametrize(
    "remat", ["offload", "offload:attn_out,mlp_wo", "offlaod"]
)
def test_config_refuses_removed_and_unknown_remat_strings(remat):
    """The host-offload family went in PR 29 (the chip read its road at a
    fiftieth of what the cost model assumed): both of its spellings meet
    the registry's ordinary error, as any typo does."""
    with pytest.raises(ValueError, match="remat must be one of"):
        gpt2_config("124m", num_layers=2, remat=remat)


def test_named_saveables_present_in_jaxpr():
    """attn_out / mlp_out must be tagged in the traced program — otherwise
    the policies that name them silently save nothing."""
    model, cfg = _tiny("branch_out", "xla")
    tokens = jnp.zeros((2, 64), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)

    def loss(p):
        logits, aux = model.apply(p, tokens)
        return jnp.mean(logits.astype(jnp.float32) ** 2) + aux

    txt = str(jax.make_jaxpr(jax.grad(loss))(params))
    for name in ("attn_out", "mlp_out"):
        assert name in txt, f"checkpoint_name {name!r} missing from jaxpr"


def test_flash_res_names_present_in_jaxpr():
    """The custom_vjp fwd rule must emit the named saveables the policy keys
    on — if someone renames them the policy silently degrades to 'full'."""
    model, cfg = _tiny("flash_res")
    tokens = jnp.zeros((2, 64), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)

    def loss(p):
        logits, aux = model.apply(p, tokens)
        return jnp.mean(logits.astype(jnp.float32) ** 2) + aux

    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    txt = str(jaxpr)
    assert "flash_out" in txt and "flash_lse" in txt
