"""Named, composable remat policies — host offload as the headline.

The round-4 trace (PROFILE.md) showed the single-chip MFU gap is
recompute-bound: ``flash_only`` still re-runs the QKV forward (~4.7
ms/layer) and the out-projection (+29 ms/step) in the backward because
saving those activations OOMs HBM by 1.3 GB.  Host offload
(ref ATorch's ``selective_offloading_checkpoint``; TorchTitan treats the
AC strategy as a first-class perf axis) trades that recompute for
host<->HBM DMA instead: the named activations are ``device_put`` to
``pinned_host`` memory at forward time and fetched back for the backward.

This module is the registry that turns the ad-hoc remat strings into
:class:`RematPolicy` objects carrying

* the jax checkpoint policy (``jax_policy``); an offload policy raises on
  a backend without ``pinned_host`` memory (the CPU backend of the tests
  has it, so they run the real offload path);
* the accounting metadata ``auto/tune.py`` prices candidates with
  (HBM-resident activation bytes, recompute fraction, offloaded bytes).

Policy names accepted everywhere ``TransformerConfig.remat`` is:

* the registered names (``none``, ``full``, ``dots``, ``dots_no_batch``,
  ``attn_out``, ``branch_out``, ``flash_res``, ``flash_only``,
  ``offload``);
* ``offload:<name>[,<name>...]`` for a selective offload set drawn from
  :data:`OFFLOADABLE_NAMES` — e.g. ``offload:attn_out,mlp_wo``.  Names
  are canonicalized to a stable order so equal sets compare equal.

The saveable names are emitted by the model code via
``jax.ad_checkpoint.checkpoint_name``: ``qkv_proj`` (attention.py),
``attn_out`` / ``mlp_out`` (transformer.py Block), ``mlp_wo``
(transformer.py Mlp), ``flash_out`` / ``flash_lse``
(ops/flash_attention.py custom_vjp fwd — flash impl only).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Tuple, Union

import jax

OFFLOAD_SRC = "device"
OFFLOAD_DST = "pinned_host"

# bf16 bytes per token-layer of each named saveable, in residual-stream
# (d_model) multiples.  qkv_proj is the fused [B,S,H,3hd] projection.
SAVEABLE_BYTES: Dict[str, float] = {
    "qkv_proj": 3.0,
    "attn_out": 1.0,
    "mlp_out": 1.0,
    "mlp_wo": 1.0,
    "flash_out": 1.0,
    "flash_lse": 0.05,
}

# Fraction of the layer's forward matmul FLOPs whose backward recompute a
# saved/offloaded name eliminates.  The headline set (qkv_proj + attn_out
# + mlp_wo) sums to 1.0: with all three resident the backward re-executes
# no matmuls, so the default "offload" policy prices at recompute 0 —
# its cost is pure DMA, which is exactly the trade auto/tune.py arbitrates.
RECOMPUTE_AVOIDED: Dict[str, float] = {
    "qkv_proj": 0.45,
    "attn_out": 0.30,
    "mlp_out": 0.25,
    "mlp_wo": 0.25,
    "flash_out": 0.25,
    "flash_lse": 0.0,
}

# Canonical name order — also the bitmask order auto/tune.py uses to
# encode selective policies for the multihost choice broadcast.
OFFLOADABLE_NAMES: Tuple[str, ...] = tuple(SAVEABLE_BYTES)
DEFAULT_OFFLOAD_NAMES: Tuple[str, ...] = ("qkv_proj", "attn_out", "mlp_wo")
_FLASH_NAMES = frozenset(("flash_out", "flash_lse"))


@dataclasses.dataclass(frozen=True)
class RematPolicy:
    """One named policy: the jax checkpoint spec + accounting metadata."""

    name: str
    saved_names: Tuple[str, ...] = ()     # kept in HBM
    offload_names: Tuple[str, ...] = ()   # moved to pinned host memory
    builtin: str = ""  # attr name on jax.checkpoint_policies, if any
    # HBM-resident saved activation bytes per token-layer (bf16
    # residual-stream multiples) — offloaded names excluded by definition.
    hbm_act_per_token_layer: float = 1.0
    # Fraction of forward matmul FLOPs re-run in the backward.
    recompute_fraction: float = 1.0

    @property
    def requires_flash(self) -> bool:
        return any(
            n in _FLASH_NAMES for n in self.saved_names + self.offload_names
        )

    @property
    def offload_bytes_per_token_layer(self) -> float:
        return sum(SAVEABLE_BYTES[n] for n in self.offload_names)


_REGISTRY: Dict[str, RematPolicy] = {}


def register(policy: RematPolicy) -> RematPolicy:
    _REGISTRY[policy.name] = policy
    return policy


def available() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def _canonical_offload_names(names: Sequence[str]) -> Tuple[str, ...]:
    unknown = sorted(set(names) - set(OFFLOADABLE_NAMES))
    if unknown:
        raise ValueError(
            f"unknown offload target(s) {unknown}; offloadable names are "
            f"{list(OFFLOADABLE_NAMES)}"
        )
    if not names:
        raise ValueError("offload:<names> needs at least one name")
    return tuple(n for n in OFFLOADABLE_NAMES if n in set(names))


def offload_policy_name(names: Sequence[str]) -> str:
    """Canonical policy string for an offload name set."""
    canon = _canonical_offload_names(names)
    if canon == DEFAULT_OFFLOAD_NAMES:
        return "offload"
    return "offload:" + ",".join(canon)


def offload_policy(names: Sequence[str]) -> RematPolicy:
    canon = _canonical_offload_names(names)
    avoided = sum(RECOMPUTE_AVOIDED[n] for n in canon)
    recompute = 0.0 if avoided >= 1.0 - 1e-9 else 1.0 - avoided
    return RematPolicy(
        name=offload_policy_name(canon),
        offload_names=canon,
        # Only the scan carry stays resident; the named saveables live in
        # pinned host memory until the backward fetches them.
        hbm_act_per_token_layer=1.0,
        recompute_fraction=recompute,
    )


# ---- registered policies (accounting constants measured/estimated on
# v5e at bench shapes; see PROFILE.md) -----------------------------------
register(RematPolicy(
    "none", hbm_act_per_token_layer=12.0, recompute_fraction=0.0,
))
register(RematPolicy(
    "full", builtin="nothing_saveable",
    hbm_act_per_token_layer=1.0, recompute_fraction=1.0,
))
register(RematPolicy(
    "dots", builtin="checkpoint_dots",
    hbm_act_per_token_layer=8.0, recompute_fraction=0.3,
))
register(RematPolicy(
    "dots_no_batch", builtin="checkpoint_dots_with_no_batch_dims",
    hbm_act_per_token_layer=6.0, recompute_fraction=0.3,
))
register(RematPolicy(
    "attn_out", saved_names=("attn_out",),
    hbm_act_per_token_layer=2.0, recompute_fraction=0.85,
))
register(RematPolicy(
    "branch_out", saved_names=("attn_out", "mlp_out"),
    hbm_act_per_token_layer=3.0, recompute_fraction=0.7,
))
register(RematPolicy(
    "flash_res", saved_names=("attn_out", "flash_out", "flash_lse"),
    hbm_act_per_token_layer=3.05, recompute_fraction=0.55,
))
register(RematPolicy(
    "flash_only", saved_names=("flash_out", "flash_lse"),
    hbm_act_per_token_layer=2.05, recompute_fraction=0.7,
))
register(offload_policy(DEFAULT_OFFLOAD_NAMES))


def resolve(name: Union[str, RematPolicy]) -> RematPolicy:
    """Policy object for a remat string; raises ValueError when unknown."""
    if isinstance(name, RematPolicy):
        return name
    if name in _REGISTRY:
        return _REGISTRY[name]
    if name.startswith("offload:"):
        return offload_policy(
            [n.strip() for n in name[len("offload:"):].split(",") if n.strip()]
        )
    raise ValueError(
        f"remat must be one of {list(available())} or 'offload:<names>' "
        f"with names from {list(OFFLOADABLE_NAMES)}, got {name!r}"
    )


def validate(name: str, attention_impl: str = "xla") -> RematPolicy:
    """Resolve + check impl compatibility (flash-name policies need the
    flash kernel: under any other impl the flash_out/flash_lse names never
    exist in the jaxpr, the policy silently saves nothing (= remat "full")
    and accounting keyed on the remat string would be wrong)."""
    policy = resolve(name)
    if policy.requires_flash and attention_impl != "flash":
        raise ValueError(
            f"remat={policy.name!r} requires attention_impl='flash', got "
            f"{attention_impl!r}"
        )
    return policy


def host_offload_supported(device=None) -> bool:
    """True when the backend exposes a ``pinned_host`` memory kind."""
    device = device if device is not None else jax.devices()[0]
    return OFFLOAD_DST in {m.kind for m in device.addressable_memories()}


def jax_policy(
    policy: Union[str, RematPolicy],
) -> Optional[Callable]:
    """The ``jax.ad_checkpoint.checkpoint`` policy callable for a name.

    An offload policy on a backend without ``pinned_host`` memory raises:
    keeping the names in HBM instead would run another memory plan under
    the offload policy's name.
    """
    policy = resolve(policy)
    if policy.builtin:
        return getattr(jax.checkpoint_policies, policy.builtin)
    if not policy.saved_names and not policy.offload_names:
        return None  # "none": no checkpointing at all
    cp = jax.checkpoint_policies
    if policy.offload_names:
        if not host_offload_supported():
            raise ValueError(
                f"remat policy {policy.name!r} offloads "
                f"{list(policy.offload_names)} to {OFFLOAD_DST!r} memory, "
                f"which {jax.devices()[0].device_kind!r} does not expose; "
                "pick a save-only policy"
            )
        return cp.save_and_offload_only_these_names(
            names_which_can_be_saved=list(policy.saved_names),
            names_which_can_be_offloaded=list(policy.offload_names),
            offload_src=OFFLOAD_SRC,
            offload_dst=OFFLOAD_DST,
        )
    return cp.save_only_these_names(*policy.saved_names)
