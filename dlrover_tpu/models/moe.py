"""Mixture-of-experts layer with expert parallelism.

Counterpart of the reference's MoE stack
(ref ``atorch/atorch/modules/moe/moe_layer.py:22-611`` — ``_AllToAll`` token
dispatch, ``topk_gating.py``, ``grouped_gemm_moe.py:46``).

TPU-first design: the classic dense-dispatch MoE (Shazeer/mesh-TF lineage) —
gating produces a static-shaped dispatch tensor ``[B, S, E, C]`` and the token
shuffle is an einsum whose expert dim is sharded over the ``expert`` mesh
axis, so GSPMD inserts the a2a the reference writes by hand.  Everything is
static-shaped and MXU-friendly; the grouped-GEMM Pallas kernel
(``dlrover_tpu.ops.grouped_matmul``) is the drop-in upgrade for the expert
matmuls at larger expert counts.
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.models import layers
from dlrover_tpu.parallel import rules as lr


def _gate(logits: jax.Array, k: int):
    """Shared top-k gate: (gate_vals, gate_idx, aux_loss)."""
    e = logits.shape[-1]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)            # [B,S,k]
    # renormalize the chosen gates
    gate_vals = gate_vals / jnp.clip(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )
    # Load-balancing aux loss: mean prob * mean assignment per expert.
    top1_onehot = jax.nn.one_hot(gate_idx[..., 0], e, dtype=jnp.float32)
    density = jnp.mean(top1_onehot, axis=(0, 1))             # [E]
    density_proxy = jnp.mean(probs, axis=(0, 1))             # [E]
    aux_loss = jnp.sum(density * density_proxy) * (e ** 2) / k
    return gate_vals, gate_idx, aux_loss


def top_k_gating(
    logits: jax.Array, k: int, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k gating with per-expert capacity (Switch/GShard style).

    Returns ``(dispatch, combine, aux_loss)`` with
    ``dispatch: [B, S, E, C]`` bool-ish one-hot of (expert, slot) per token,
    ``combine: [B, S, E, C]`` gate-weighted dispatch, and the load-balancing
    auxiliary loss (ref ``topk_gating.py`` capability).
    """
    b, s, e = logits.shape
    gate_vals, gate_idx, aux_loss = _gate(logits, k)

    # Assign capacity slots expert-by-expert in token order.  Slots taken by
    # earlier choice ranks offset later ranks (`prior`), so a token picked
    # 2nd-choice never collides with one picked 1st-choice.
    dispatch = jnp.zeros((b, s, e, capacity), dtype=jnp.float32)
    combine = jnp.zeros((b, s, e, capacity), dtype=jnp.float32)
    prior = jnp.zeros((b, 1, e), dtype=jnp.float32)          # slots used so far
    for choice in range(k):
        idx = gate_idx[..., choice]                          # [B,S]
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)   # [B,S,E]
        # position of this token within its expert's queue
        pos = jnp.cumsum(onehot, axis=1) - onehot + prior    # [B,S,E]
        in_cap = pos < capacity
        onehot = onehot * in_cap
        prior = prior + onehot.sum(axis=1, keepdims=True)
        slot = jax.nn.one_hot(
            (pos * onehot).sum(-1).astype(jnp.int32), capacity, dtype=jnp.float32
        )                                                     # [B,S,C]
        d = onehot[..., None] * slot[..., None, :]            # [B,S,E,C]
        dispatch = dispatch + d
        combine = combine + d * gate_vals[..., choice][..., None, None]
    return dispatch, combine, aux_loss


def _router_entropy(router_logits: jax.Array) -> jax.Array:
    """Mean per-token entropy of the router distribution (nats)."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    return jnp.mean(-jnp.sum(probs * jnp.log(probs + 1e-9), axis=-1))


class MoEMlp(nn.Module):
    """Expert-parallel MLP with top-k routing.

    Dispatch paths:

    * ``"einsum"`` — classic dense capacity dispatch (Shazeer/mesh-TF
      lineage): static [B, S, E, C] tensors whose expert dim shards over the
      ``expert`` mesh axis, GSPMD inserting the a2a.  Tokens beyond an
      expert's capacity are dropped; capacity padding burns FLOPs.
    * ``"a2a"`` / ``"a2a_int8"`` — the einsum math with an EXPLICIT
      all-to-all wire leg (ref ``moe_layer.py`` ``_AllToAll``): under
      ``shard_map`` each expert shard exchanges its local batch chunks
      with every other expert-axis peer before the expert matmuls, and
      the inverse exchange routes results home before the combine.  The
      expert compute is elementwise over the batch dim, so the
      shuffle/unshuffle pair is semantically the identity — what it buys
      is control of the transport: ``"a2a_int8"`` rides
      :func:`~dlrover_tpu.parallel.quantized_collectives.quantized_all_to_all`
      (~(1 + 4/block) bytes/element vs 4 for ``"a2a"``'s fp32 wire, both
      legs, forward and backward).  With a unit expert axis both modes
      are exactly ``"einsum"`` (no wire → no-op, no quantization).
    * ``"grouped"`` — dropless megablocks-style dispatch through the Pallas
      grouped-matmul kernel (ref
      ``atorch/atorch/modules/moe/grouped_gemm_moe.py:46``): token-choices
      are sorted by expert and each expert's ragged row group runs as one
      grouped GEMM — no token drops, padding bounded by E x block rows
      instead of the capacity factor.  **Per-device only**: the kernel
      sees local rows, so it cannot shard over an expert mesh axis > 1 —
      that combination raises (see PROFILE.md round 19) rather than
      silently computing with the wrong experts; use an a2a/einsum mode
      under expert parallelism.

    Router observability: every forward ``sow``s a ``moe_stats`` vector
    ``[gate_entropy, drop_fraction, load_0..load_{E-1}]`` into the
    ``"intermediates"`` collection — a no-op (zero cost) unless the
    caller applies with ``mutable=["intermediates"]``, which is how the
    trainer harvests router health on the report cadence without
    touching the compiled step.
    """

    num_experts: int
    d_ff: int
    top_k: int = 2
    capacity_factor: float = 1.25
    activation: str = "swiglu"
    dtype: layers.Dtype = jnp.bfloat16
    param_dtype: layers.Dtype = jnp.float32
    dispatch: str = "einsum"        # "einsum" | "a2a" | "a2a_int8" | "grouped"
    gmm_block_rows: int = 128

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        b, s, d = x.shape
        e = self.num_experts

        router_logits = layers.DenseGeneral(
            e,
            kernel_axes=(lr.EMBED, None),
            dtype=jnp.float32,
            param_dtype=self.param_dtype,
            name="router",
        )(x.astype(jnp.float32))

        wi_shape = (e, d, self.d_ff)
        wi_axes = (lr.EXPERT, lr.EMBED, lr.MLP)
        wo = self.param(
            "wo",
            nn.with_logical_partitioning(
                layers.default_kernel_init, (lr.EXPERT, lr.MLP, lr.EMBED)
            ),
            (e, self.d_ff, d),
            self.param_dtype,
        ).astype(self.dtype)
        wi = self.param(
            "wi",
            nn.with_logical_partitioning(layers.default_kernel_init, wi_axes),
            wi_shape,
            self.param_dtype,
        ).astype(self.dtype)
        wg = None
        if self.activation == "swiglu":
            wg = self.param(
                "wg",
                nn.with_logical_partitioning(layers.default_kernel_init, wi_axes),
                wi_shape,
                self.param_dtype,
            ).astype(self.dtype)

        from dlrover_tpu.runtime.mesh import EXPERT_AXIS, mesh_axis_size

        ep = mesh_axis_size(EXPERT_AXIS)
        if self.dispatch == "grouped":
            if ep > 1:
                raise ValueError(
                    "dispatch='grouped' runs the per-device Pallas grouped-"
                    f"GEMM kernel and cannot shard over the {ep}-way "
                    f"{EXPERT_AXIS!r} mesh axis: the kernel only sees local "
                    "rows, so cross-device token groups would silently "
                    "multiply against the wrong experts.  Use dispatch="
                    "'einsum', 'a2a', or 'a2a_int8' under expert "
                    "parallelism (see PROFILE.md round 19)."
                )
            return self._grouped_forward(x, router_logits, wi, wg, wo)
        if self.dispatch not in ("einsum", "a2a", "a2a_int8"):
            raise ValueError(
                f"unknown MoE dispatch {self.dispatch!r}; expected one of "
                "'einsum', 'a2a', 'a2a_int8', 'grouped'"
            )
        if self.dispatch in ("a2a", "a2a_int8") and ep > 1:
            return self._a2a_forward(x, router_logits, wi, wg, wo, ep)
        # With a unit expert axis the a2a modes have no wire to ride —
        # they fall through to the (exactly equal) einsum path.
        return self._einsum_forward(x, router_logits, wi, wg, wo)

    # -- capacity einsum dispatch (EP-shardable) ------------------------------

    def _einsum_forward(self, x, router_logits, wi, wg, wo):
        b, s, d = x.shape
        e = self.num_experts
        capacity = max(1, int(self.capacity_factor * s * self.top_k / e))
        dispatch, combine, aux_loss = top_k_gating(
            router_logits, self.top_k, capacity
        )
        self._sow_router_stats(
            _router_entropy(router_logits),
            routed=dispatch.sum(axis=(0, 1, 3)),
            total=b * s * self.top_k,
        )
        dispatch = dispatch.astype(self.dtype)
        combine = combine.astype(self.dtype)

        # Token shuffle: expert dim sharded over the `expert` mesh axis —
        # this einsum IS the all-to-all under EP.
        expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, x.astype(self.dtype))
        expert_in = nn.with_logical_constraint(
            expert_in, (lr.EXPERT, lr.BATCH, None, lr.ACT_EMBED)
        )
        h = jnp.einsum("ebcd,edf->ebcf", expert_in, wi)
        if wg is not None:
            g = jnp.einsum("ebcd,edf->ebcf", expert_in, wg)
            h = nn.silu(g) * h
        else:
            h = nn.gelu(h)
        expert_out = jnp.einsum("ebcf,efd->ebcd", h, wo)
        expert_out = nn.with_logical_constraint(
            expert_out, (lr.EXPERT, lr.BATCH, None, lr.ACT_EMBED)
        )

        # Un-shuffle (second a2a) + weighted combine.
        out = jnp.einsum("bsec,ebcd->bsd", combine, expert_out)
        return out, aux_loss.astype(jnp.float32)

    # -- explicit all-to-all dispatch (shard_map) -----------------------------

    def _a2a_forward(self, x, router_logits, wi, wg, wo, ep):
        """Capacity dispatch with an EXPLICIT all-to-all wire (ref
        ``moe_layer.py`` ``_AllToAll``): each device routes a batch
        sub-chunk to ALL experts locally, then the dispatch a2a transposes
        expert-sharded ← batch-sharded (chunk for expert group ``r`` goes
        to expert-axis peer ``r``), the expert matmuls run on the local
        expert slice, and the inverse a2a routes results home for the
        combine.  Numerically this is :meth:`_einsum_forward` exactly —
        the slot assignment is independent per batch row, and the aux
        loss pmean-composes over equal chunks — up to int8 rounding when
        ``dispatch == "a2a_int8"`` puts the two legs on the quantized
        wire (~(1 + 4/block) bytes/element vs 4 fp32; both directions,
        forward and backward, see ``quantized_all_to_all``)."""
        from jax.sharding import PartitionSpec as P

        from dlrover_tpu.parallel.quantized_collectives import (
            quantized_all_to_all,
        )
        from dlrover_tpu.runtime.mesh import (
            EXPERT_AXIS, current_mesh, mesh_axis_size, shard_map_compat,
        )

        b, s, d = x.shape
        e, k = self.num_experts, self.top_k
        capacity = max(1, int(self.capacity_factor * s * k / e))
        int8 = self.dispatch == "a2a_int8"
        for axis in ("seq", "tensor"):
            if mesh_axis_size(axis) > 1:
                raise ValueError(
                    f"a2a dispatch does not compose with a {axis!r} mesh "
                    "axis > 1 yet; use dispatch='einsum' (GSPMD) there"
                )
        dp = mesh_axis_size("data") * mesh_axis_size("fsdp")
        if b % (dp * ep):
            raise ValueError(
                f"a2a dispatch splits the batch over data x expert: got "
                f"batch {b} not divisible by {dp} (data*fsdp) x {ep} "
                f"(expert)"
            )
        if e % ep:
            raise ValueError(
                f"num_experts {e} must divide by the {ep}-way expert axis"
            )
        batch_axes = ("data", "fsdp", EXPERT_AXIS)

        def wire(v, split_axis, concat_axis):
            if int8:
                return quantized_all_to_all(
                    v, EXPERT_AXIS,
                    split_axis=split_axis, concat_axis=concat_axis,
                )
            return jax.lax.all_to_all(
                v, EXPERT_AXIS, split_axis, concat_axis, tiled=True
            )

        def body(x_loc, logits_loc, *weights):
            wi_loc = weights[0]
            wg_loc = weights[1] if len(weights) == 3 else None
            wo_loc = weights[-1]
            # Slot assignment is per (batch row, expert) — identical on a
            # batch chunk to what the full batch computes.
            dispatch, combine, _ = top_k_gating(logits_loc, k, capacity)
            probs = jax.nn.softmax(logits_loc.astype(jnp.float32), axis=-1)
            # Exact global aux loss: pmean the densities BEFORE the
            # product (chunk means over equal chunks compose exactly).
            top1 = jax.nn.one_hot(
                jnp.argmax(probs, axis=-1), e, dtype=jnp.float32
            )
            density = jax.lax.pmean(
                jnp.mean(top1, axis=(0, 1)), batch_axes
            )
            proxy = jax.lax.pmean(
                jnp.mean(probs, axis=(0, 1)), batch_axes
            )
            aux = jnp.sum(density * proxy) * (e ** 2) / k
            entropy = jax.lax.pmean(
                jnp.mean(-jnp.sum(probs * jnp.log(probs + 1e-9), -1)),
                batch_axes,
            )
            routed = jax.lax.psum(
                dispatch.sum(axis=(0, 1, 3)), batch_axes
            )
            dispatch = dispatch.astype(self.dtype)
            combine = combine.astype(self.dtype)
            # Local dispatch to ALL experts: [E, b_chunk, C, D].
            expert_in = jnp.einsum(
                "bsec,bsd->ebcd", dispatch, x_loc.astype(self.dtype)
            )
            # Dispatch leg: expert-split, batch-concat — each peer keeps
            # its expert group's tokens from every batch chunk.
            expert_in = wire(expert_in, 0, 1)      # [E/ep, b_chunk*ep, C, D]
            h = jnp.einsum("ebcd,edf->ebcf", expert_in, wi_loc)
            if wg_loc is not None:
                g = jnp.einsum("ebcd,edf->ebcf", expert_in, wg_loc)
                h = nn.silu(g) * h
            else:
                h = nn.gelu(h)
            expert_out = jnp.einsum("ebcf,efd->ebcd", h, wo_loc)
            # Combine leg home: the exact inverse exchange.
            expert_out = wire(expert_out, 1, 0)    # [E, b_chunk, C, D]
            out = jnp.einsum("bsec,ebcd->bsd", combine, expert_out)
            return out, aux, entropy, routed

        bspec = P(batch_axes, None, None)
        espec = P(EXPERT_AXIS, None, None)
        args = [x, router_logits, wi] + ([wg] if wg is not None else [])
        args.append(wo)
        in_specs = tuple([bspec, bspec] + [espec] * (len(args) - 2))
        out, aux, entropy, routed = shard_map_compat(
            body, mesh=current_mesh(), in_specs=in_specs,
            out_specs=(bspec, P(), P(), P()),
        )(*args)
        self._sow_router_stats(entropy, routed, b * s * k)
        return out, aux.astype(jnp.float32)

    def _sow_router_stats(self, entropy, routed, total):
        """Book ``[entropy, drop_fraction, load_0..load_{E-1}]`` into the
        ``"intermediates"`` collection (no-op unless mutable)."""
        routed = routed.astype(jnp.float32)
        kept = routed.sum()
        drop = 1.0 - kept / max(1, total)
        load = routed / jnp.clip(kept, 1.0)
        self.sow(
            "intermediates", "moe_stats",
            jnp.concatenate([jnp.stack([entropy, drop]), load]),
        )

    # -- dropless grouped-GEMM dispatch ---------------------------------------

    def _grouped_forward(self, x, router_logits, wi, wg, wo):
        from jax.sharding import PartitionSpec as P

        from dlrover_tpu.ops.grouped_matmul import grouped_matmul
        from dlrover_tpu.runtime.mesh import shard_local

        b, s, d = x.shape
        e, k = self.num_experts, self.top_k
        block = self.gmm_block_rows

        # Routing and its statistics are global and plain XLA; only the
        # sort + grouped GEMMs below run per device.
        gate_vals, gate_idx, aux_loss = _gate(router_logits, k)
        counts = jnp.zeros((e,), jnp.int32).at[gate_idx.reshape(-1)].add(1)
        # Dropless: routed == total, so drop_fraction books as exactly 0.
        self._sow_router_stats(
            _router_entropy(router_logits), routed=counts, total=b * s * k
        )

        def local(x, gate_vals, gate_idx, *weights):
            """Sort this device's token-choices by expert and run each
            expert's ragged row group through the grouped GEMMs."""
            wi, wo = weights[0], weights[-1]
            wg = weights[1] if len(weights) == 3 else None
            b, s, d = x.shape
            n = b * s * k
            # Static row budget: every token-choice plus at most one
            # partial block of padding per expert, in whole kernel blocks.
            n_pad = ((n + block - 1) // block + e) * block

            x_flat = x.reshape(b * s, d).astype(self.dtype)
            experts_flat = gate_idx.reshape(n)                   # [N]
            gates_flat = gate_vals.reshape(n).astype(self.dtype)
            token_of_choice = jnp.arange(n, dtype=jnp.int32) // k

            # Stable sort by expert: each expert's choices become one
            # consecutive ragged group.
            order = jnp.argsort(experts_flat, stable=True)
            expert_sorted = experts_flat[order]
            src_token = token_of_choice[order]
            counts = jnp.zeros((e,), jnp.int32).at[experts_flat].add(1)
            padded = ((counts + block - 1) // block) * block     # group sizes
            group_starts = jnp.cumsum(padded) - padded
            count_starts = jnp.cumsum(counts) - counts
            rank = jnp.arange(n, dtype=jnp.int32) - count_starts[expert_sorted]
            dest = group_starts[expert_sorted] + rank            # [N] row slots

            rows = jnp.zeros((n_pad, d), self.dtype).at[dest].set(
                x_flat[src_token]
            )
            h = grouped_matmul(rows, wi, padded, block)
            if wg is not None:
                g = grouped_matmul(rows, wg, padded, block)
                h = nn.silu(g) * h
            else:
                h = nn.gelu(h)
            out_rows = grouped_matmul(h, wo, padded, block)

            weighted = out_rows[dest] * gates_flat[order][:, None]
            out = jnp.zeros((b * s, d), self.dtype).at[src_token].add(weighted)
            return out.reshape(b, s, d)

        # Tokens stay split over their batch and sequence axes (an MLP is
        # token-wise); the embed dim and the expert weights are whole on
        # every device, so peers on a tensor axis repeat each other's work.
        tokens = nn.logical_to_mesh_axes((lr.BATCH, lr.ACT_SEQ, None))
        weights = [wi] + ([wg] if wg is not None else []) + [wo]
        out = shard_local(
            local,
            in_specs=(tokens,) * 3 + (P(),) * len(weights),
            out_specs=tokens,
        )(x, gate_vals, gate_idx, *weights)
        return out, aux_loss.astype(jnp.float32)
