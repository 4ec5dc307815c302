"""Named remat policies: which of a layer's activations the backward keeps.

The round-4 trace (PROFILE.md) showed the single-chip MFU gap is
recompute-bound, so what a layer saves is a first-class performance
choice (TorchTitan treats the AC strategy the same way).  This module is
the registry that turns the remat strings into :class:`RematPolicy`
objects carrying

* the jax checkpoint policy (``jax_policy``);
* the accounting metadata a cost model prices a policy with
  (HBM-resident activation bytes, recompute fraction).

``TransformerConfig.remat`` accepts the registered names: ``none``,
``full``, ``dots``, ``dots_no_batch``, ``attn_out``, ``branch_out``,
``flash_res``, ``flash_only``.

The saveable names are emitted by the model code via
``jax.ad_checkpoint.checkpoint_name``: ``attn_out`` / ``mlp_out``
(transformer.py Block), ``flash_out`` / ``flash_lse``
(ops/flash_attention.py custom_vjp fwd — flash impl only), ``delta_out``
(models/linear_attention.py: the gated delta rule's output; a model
without such a layer emits no such name, and its step is what it was),
``kda_out`` (the same file's ``KimiDeltaAttention``: the per-channel
rule's output) and ``kda_states`` (ops/kda.py custom_vjp fwd, the kernel
path only: the state each chunk starts from, which the backward kernel
reads),
``ssd_out`` (models/mamba2.py: the state-space scan's output, likewise
only where a model has such a layer),
``index_choice`` / ``index_kl_grads`` (models/sparse_attention.py),
``latent_k`` / ``latent_v`` (models/attention.py ``LatentAttention``: the
per-head keys and values rebuilt from the latent row; NO registered policy
keeps them).

What ``flash_only`` keeps of a sparse attention layer
(``models/sparse_attention.py``): the sparse kernels' output and rows under
the flash kernels' names, a choosing layer's choice (``index_choice``, the
int8 mask ``[B, T, T]``: the backward neither scores nor selects a second
time, and the layers that reuse the choice take it as their input) and the
gradient of its KL term, which the term's forward has already computed
(``index_kl_grads``: the indexer's ``q``, ``k`` and weights' cotangents,
``[B, T, 32 x 128]`` and less; the backward scales them).  A model without
such a layer emits neither name.

What ``flash_only`` keeps of a linear layer differs by rule, and the chip
chose each.  Both rules' backward kernels read the state each chunk starts
from, which the forward kernel writes beside its output; keeping those
states costs residency and no traffic, and spares the forward kernel's
second run under the layer's remat.

* The scalar rule (``ops/gated_delta_rule.py``, the hybrid): the output
  alone (``delta_out``).  Keeping the states too (no second forward) was
  the slower step on the chip, 1915.20 against 1907.73 ms, because the
  compiler then made room by recomputing two projections, and keeping
  neither let it pick slower layouts around the rule (PERF.md §6, PR 32).
* The per-channel rule (``ops/kda.py``, Ling): the output AND the states
  (``kda_out``, ``kda_states``; ``[B H, S / 128, dv, dk]``, 134 MB a layer
  at 2 x 8192 tokens and 32 heads of 128 / 128).  That step holds 7.35 of
  15.75 GiB, so nothing has to make room: the replayed forward kernel has
  no live output and is dropped, one run of it a layer instead of two.
  On the chip the step fell from 854.33 to 807.90 ms, six runs of 7.72 ms,
  no other instruction moved by more than 0.03 ms and the peak of memory
  stood where it was (PERF.md §6, PR 49).  Only a program with a KDA layer
  on the kernel path emits the name; the ``jax.numpy`` form of the rule
  (head widths that are no whole lane tiles) has no kernel to drop.

What ``flash_only`` keeps of a state-space (Mamba-2) layer is likewise the
scan's output alone (``ssd_out``, ``[B, S, H P]``): the scan's forward
kernel runs a second time in the backward for the chunk-start states
(``[B tiles, S / 128, N, 512]``, 134 MB a layer at 2 x 8192 tokens).
Keeping those too was refused by the chip's compiler at the benchmark's
depth ("Used 16.06G of 15.75G hbm"; PERF.md §6, PR 37).  The backward
kernel adds up ``dD`` and a group's ``dB``, ``dC`` itself: nothing of the
scan's cotangents is reduced by XLA but ``dD`` over the batch and a head's
lanes.

Neither layer's short convolution is kept: it runs a second time in the
backward, and the backward rebuilds its pre-activation a third time from
the projection's output (``ops/short_conv.py``: one Pallas pass each way
where the shape tiles, PERF.md §6, PR 38), which costs a pass where a kept
copy costs 201 / 377 MB a layer that neither step has.

What ``flash_only`` keeps of a latent-attention layer is the flash
kernel's output and log-sum-exp rows, as of any attention layer.  The
per-head k and v (``[B, S, H, 192 + 128]``, 335 MB a layer at 2 x 8192
tokens) are rebuilt in the backward from the 576-wide latent row: keeping
them too moved the step by -0.05% on the chip (PERF.md §6, PR 33), and the
memory buys a layer instead.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple, Union

import jax

_FLASH_NAMES = frozenset(("flash_out", "flash_lse"))


@dataclasses.dataclass(frozen=True)
class RematPolicy:
    """One named policy: the jax checkpoint spec + accounting metadata."""

    name: str
    saved_names: Tuple[str, ...] = ()     # kept in HBM
    builtin: str = ""  # attr name on jax.checkpoint_policies, if any
    # HBM-resident saved activation bytes per token-layer (bf16
    # residual-stream multiples).
    hbm_act_per_token_layer: float = 1.0
    # Fraction of forward matmul FLOPs re-run in the backward.
    recompute_fraction: float = 1.0

    @property
    def requires_flash(self) -> bool:
        return any(n in _FLASH_NAMES for n in self.saved_names)


_REGISTRY: Dict[str, RematPolicy] = {}


def register(policy: RematPolicy) -> RematPolicy:
    _REGISTRY[policy.name] = policy
    return policy


def available() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


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
# What the mixers' kernels wrote and their backward kernels read, as far as
# the chip chose it (the module's text): the flash kernel's output and
# log-sum-exp rows, the KDA rule's output and chunk-start states, and of the
# scalar rule and the state-space scan the output alone.
register(RematPolicy(
    "flash_only",
    saved_names=(
        "flash_out", "flash_lse", "delta_out", "kda_out", "kda_states",
        "ssd_out", "index_choice", "index_kl_grads",
    ),
    hbm_act_per_token_layer=2.05, recompute_fraction=0.7,
))


def resolve(name: Union[str, RematPolicy]) -> RematPolicy:
    """Policy object for a remat string; raises ValueError when unknown."""
    if isinstance(name, RematPolicy):
        return name
    if name in _REGISTRY:
        return _REGISTRY[name]
    raise ValueError(
        f"remat must be one of {list(available())}, got {name!r}"
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


def jax_policy(
    policy: Union[str, RematPolicy],
) -> Optional[Callable]:
    """The ``jax.ad_checkpoint.checkpoint`` policy callable for a name."""
    policy = resolve(policy)
    if policy.builtin:
        return getattr(jax.checkpoint_policies, policy.builtin)
    if not policy.saved_names:
        return None  # "none": no checkpointing at all
    return jax.checkpoint_policies.save_only_these_names(*policy.saved_names)
