"""Remat-policy tests: registry, parity across all policies, offload
fallback, and recompute elision.

The round-4 perf work (PROFILE.md) saves the flash kernel's own outputs
(o, lse) as named remat targets so the backward replay drops the attention
forward recompute; the remat-policy subsystem (ops/remat_policy.py)
generalizes that into named, composable policies with host offload.
These tests pin down (a) gradient equivalence across every registered
policy, (b) the save-only fallback on backends without pinned host
memory, and (c) that the named saveables actually exist in the jaxpr.
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
    # (and the fallback test reuses "offload") instead of re-tracing the
    # same jit per test — each trace is seconds of CPU compile time.
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


_ALL_POLICIES = sorted(rp.available()) + ["offload:attn_out,mlp_wo"]


@pytest.mark.parametrize(
    "remat",
    [
        # flash_only recompiles the Pallas kernel in the bwd pass (~12s on
        # 1 core) and is already graded against attn_out grads below;
        # flash_res (~16s) likewise — attn_out stays the tier-1 witness
        # here.
        pytest.param(p, marks=pytest.mark.slow)
        if p in ("flash_only", "flash_res") else p
        for p in _ALL_POLICIES
    ],
)
def test_every_registered_policy_matches_none_grads(remat):
    """Loss/grad parity for EVERY policy the registry knows (plus a
    selective offload list) against the no-remat baseline — the same
    harness as the pipeline parity tests, rtol 2e-3.

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
    # Selective lists canonicalize to a stable order...
    assert rp.resolve("offload:mlp_wo,qkv_proj").name == (
        "offload:qkv_proj,mlp_wo"
    )
    # ...and the default name set folds back to the plain alias.
    assert rp.resolve("offload:mlp_wo,attn_out,qkv_proj").name == "offload"
    offload = rp.resolve("offload")
    assert offload.offload_names == ("qkv_proj", "attn_out", "mlp_wo")
    assert offload.recompute_fraction == 0.0
    assert offload.offload_bytes_per_token_layer == 5.0
    with pytest.raises(ValueError, match="unknown offload target"):
        rp.resolve("offload:nonsense")
    with pytest.raises(ValueError, match="remat must be one of"):
        rp.resolve("bogus_policy")
    # Flash-name policies are rejected under non-flash impls, selective
    # offload lists included.
    with pytest.raises(ValueError, match="attention_impl='flash'"):
        rp.validate("offload:flash_out", attention_impl="xla")
    with pytest.raises(ValueError, match="attention_impl='flash'"):
        TransformerConfig(remat="flash_only", attention_impl="xla")


def test_config_accepts_selective_offload_strings():
    cfg = gpt2_config("124m", num_layers=2, remat="offload:attn_out,mlp_wo")
    assert cfg.remat == "offload:attn_out,mlp_wo"
    with pytest.raises(ValueError, match="remat must be one of"):
        gpt2_config("124m", remat="offlaod")


def test_offload_without_pinned_host_raises(monkeypatch):
    """On a backend with no pinned_host memory kind an offload policy is
    refused with a sentence — keeping the names in HBM instead would run
    another memory plan under the offload policy's name.  Where the kind
    exists (this CPU backend has it) the policy's gradients are those of
    the un-rematerialized step."""
    monkeypatch.setattr(rp, "host_offload_supported", lambda device=None: False)
    with pytest.raises(ValueError, match="pinned_host"):
        rp.jax_policy("offload")
    assert rp.jax_policy("flash_res") is not None  # save-only: unaffected
    monkeypatch.undo()
    assert rp.host_offload_supported()
    l_off, g_off = _loss_and_grads("offload", "xla")
    l_ref, g_ref = _loss_and_grads("none", "xla")
    np.testing.assert_allclose(float(l_off), float(l_ref), rtol=2e-3)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_off), jax.tree_util.tree_leaves(g_ref)
    ):
        np.testing.assert_allclose(
            np.asarray(a, np.float64), np.asarray(b, np.float64),
            rtol=2e-3, atol=1e-5,
        )


def test_named_saveables_present_in_jaxpr():
    """qkv_proj / attn_out / mlp_out / mlp_wo must be tagged in the traced
    program — otherwise offload/selective policies silently save nothing."""
    model, cfg = _tiny("offload", "xla")
    tokens = jnp.zeros((2, 64), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)

    def loss(p):
        logits, aux = model.apply(p, tokens)
        return jnp.mean(logits.astype(jnp.float32) ** 2) + aux

    txt = str(jax.make_jaxpr(jax.grad(loss))(params))
    for name in ("qkv_proj", "attn_out", "mlp_out", "mlp_wo"):
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
