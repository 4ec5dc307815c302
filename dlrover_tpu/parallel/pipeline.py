"""SPMD pipeline parallelism: vmapped stages + rolling buffer over ``pipe``.

Capability ref: the reference's PiPPy-based pipeline compiler
(``atorch/atorch/modules/distributed_modules/compilers/pipe_compiler/
PipelineStage.py:1-989``, ``StageInterleaver.py``; RPC-driven 1F1B).  The TPU
redesign keeps the whole schedule *inside one jitted SPMD program* instead of
an RPC graph:

* the layer stack [L, ...] is reshaped to [S, L/S, ...] and the leading
  stage dim is sharded over the ``pipe`` mesh axis (logical axis
  ``STAGES``), so each pipe group holds exactly its stage's weights;
* a state buffer [S, microbatch, seq, embed] — also stage-sharded — holds
  the activation each stage is currently working on.  Every tick all stages
  run in parallel (``nn.vmap`` over the stage dim), then the buffer shifts
  one slot: XLA lowers the shift of a pipe-sharded dim to a
  ``collective-permute`` over ICI, which is exactly the reference's
  send/recv stage handoff without the RPC fabric;
* the tick loop is an ``nn.scan`` with broadcast params: microbatches
  stream in at stage 0, finished microbatches stream out of stage S-1,
  giving the GPipe/1F1B-family schedule with an S-1-tick bubble.  The
  backward pass generated by jax AD runs the same schedule in reverse
  (last stage first), i.e. fwd and bwd microbatches interleave exactly like
  1F1B, with per-stage remat bounding activation memory.

Degenerate case S=1 reduces to plain scan-over-layers semantics (same math,
same loss), which the parity test asserts.

On interleaving (the reference's ``StageInterleaver``) — MEASURED, not
argued (``tools/pipeline_account.py`` + PROFILE.md rounds 4-5): this
schedule's useful-work fraction M/(M+S-1) equals non-interleaved 1F1B
exactly (the reference's default schedule); the naive SPMD approximation
of interleaving (vS buffer slots, one shift per tick) is confirmed
strictly negative (ticks grow to M + vS - 1).  What DOES reproduce the
interleaved-1F1B bubble inside one SPMD program is the **circular
schedule** (``pipeline_interleave=v``, :class:`PipelinedBlocks` circular
branch): each device holds v layer chunks (chunk c on device c mod S)
and every microbatch makes v laps around the stage ring — vM + S - 1
ticks of one chunk-compute each, useful fraction vM/(vM+S-1) ==
interleaved 1F1B's, for BOTH the forward and the jax-AD-reversed
backward, at v x the stage-handoff traffic and v x the per-step weight
streaming.  The r4 claim that this needs a hand-staged custom_vjp was
wrong: circular scheduling gets the same bubble algebra out of a plain
scan.  Measured on the virtual mesh the best operating point is M = 2S:
beyond it per-tick fixed costs grow faster than the bubble shrinks, so
``num_microbatches`` is a searched knob (auto_tune) rather than a fixed
mitigation.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.parallel import rules as lr


class PipelinedBlocks(nn.Module):
    """Run the trunk's scanned units as ``pipeline_stages`` pipelined
    stages.

    ``block_cls`` is the module class the trunk stacks: the (possibly
    remat-wrapped) layer, or one period of the layer pattern
    (``config.num_scan_units`` of them make the trunk), with the
    ``carry=(x, aux), (positions, segment_ids) -> (carry, None)``
    signature shared with the non-pipelined scan path.
    """

    config: Any
    block_cls: Any

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        aux0: jax.Array,
        positions: Optional[jax.Array],
        segment_ids: Optional[jax.Array],
    ) -> Tuple[jax.Array, jax.Array]:
        cfg = self.config
        stages = cfg.pipeline_stages
        num_micro = cfg.num_microbatches or stages
        if cfg.num_scan_units % stages:
            raise ValueError(
                f"num_layers {cfg.num_layers} ({cfg.num_scan_units} "
                f"scanned units) not divisible by pipeline_stages {stages}"
            )
        if cfg.num_experts:
            raise NotImplementedError(
                "MoE aux loss accounting inside bubble ticks is not "
                "defined; pipeline_stages>1 requires num_experts=0"
            )
        batch = x.shape[0]
        if batch % num_micro:
            raise ValueError(
                f"batch {batch} not divisible by num_microbatches {num_micro}"
            )
        per_stage = cfg.num_scan_units // stages
        mb_size = batch // num_micro

        # One stage = a scan over its per_stage layers (params [per_stage,...]
        # on the LAYERS logical axis, unsharded by default under pipelining).
        class StageStack(nn.Module):
            config: Any
            block_cls: Any

            @nn.compact
            def __call__(self, x, positions, segment_ids):
                stack = nn.scan(
                    self.block_cls,
                    variable_axes={"params": 0},
                    split_rngs={"params": True},
                    in_axes=nn.broadcast,
                    length=per_stage,
                    metadata_params={nn.PARTITION_NAME: lr.LAYERS},
                )(self.config, name="layers")
                (x, _aux), _ = stack(
                    (x, jnp.zeros((), jnp.float32)), positions, segment_ids
                )
                return x

        def microbatch_feed(arr, name):
            """[num_micro+S-1, mb, ...] feed for the input or a side input.

            Side inputs return None when they broadcast over microbatches
            (leading dim 1 or absent) — the closure value is used unsliced
            then; the main input ``x`` is always microbatched (a batch of 1
            is still a real batch, not a broadcast).  Batch-shaped side
            inputs (packed-sequence positions/segment_ids) must travel with
            their microbatch through the stage buffer.
            """
            if arr is None or (name != "x" and arr.shape[0] == 1):
                return None
            if arr.shape[0] != batch:
                raise ValueError(
                    f"{name} leading dim {arr.shape[0]} must be 1 or match "
                    f"the batch {batch} under pipeline microbatching"
                )
            m = arr.reshape(num_micro, mb_size, *arr.shape[1:])
            pad = jnp.zeros((stages - 1, *m.shape[1:]), m.dtype)
            if name == "x":
                # Pin the feed layout (tick dim unsharded, microbatch over
                # the batch axes) so the per-tick dynamic-slice keeps the
                # sharding instead of replicating (involuntary remat).  The
                # bubble pad is pinned too — an unconstrained zeros
                # broadcast otherwise inherits a conflicting sharding from
                # its consumers and forces the same fallback.
                spec = (lr.GATHERED, lr.BATCH, lr.ACT_SEQ, lr.ACT_EMBED)
                m = nn.with_logical_constraint(m, spec)
                pad = nn.with_logical_constraint(pad, spec)
                return nn.with_logical_constraint(
                    jnp.concatenate([m, pad], axis=0), spec
                )
            return jnp.concatenate([m, pad], axis=0)

        if cfg.pipeline_interleave > 1 and not self.is_initializing():
            # Circular schedule: params were created by the plain path
            # below during init (identical [S, per_stage, ...] layout and
            # checkpoint format); apply hand-schedules the laps.
            return self._circular(x, positions, segment_ids), aux0

        pos_feed = microbatch_feed(positions, "positions")
        seg_feed = microbatch_feed(segment_ids, "segment_ids")

        # All stages computed in parallel: vmap over the leading stage dim,
        # params [S, per_stage, ...] with S on the STAGES (-> pipe) axis.
        # Side inputs are stage-mapped only when they travel per-microbatch.
        VStages = nn.vmap(
            StageStack,
            in_axes=(
                0,
                None if pos_feed is None else 0,
                None if seg_feed is None else 0,
            ),
            out_axes=0,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            metadata_params={nn.PARTITION_NAME: lr.STAGES},
        )

        def constrain_buf(buf):
            return nn.with_logical_constraint(
                buf, (lr.STAGES, lr.BATCH, lr.ACT_SEQ, lr.ACT_EMBED)
            )

        def shift(buf, head):
            """Rolling one-slot shift: stage s's slot takes stage s-1's value
            and ``head`` enters at stage 0.  XLA lowers the slice+concat on a
            pipe-sharded dim to a collective-permute (the stage handoff)."""
            return jnp.concatenate([head[None], buf[:-1]], axis=0)

        class PipeTick(nn.Module):
            """One pipeline tick: all stages compute, buffers shift one slot."""

            config: Any
            block_cls: Any

            @nn.compact
            def __call__(self, carry, inp):
                out_prev, pos_prev, seg_prev = carry
                state = shift(constrain_buf(out_prev), inp["x"])
                pos_state = (
                    positions if pos_feed is None
                    else shift(pos_prev, inp["pos"])
                )
                seg_state = (
                    segment_ids if seg_feed is None
                    else shift(seg_prev, inp["seg"])
                )
                out = VStages(self.config, self.block_cls, name="stages")(
                    constrain_buf(state), pos_state, seg_state
                )
                out = constrain_buf(out)
                new_carry = (
                    out,
                    None if pos_feed is None else pos_state,
                    None if seg_feed is None else seg_state,
                )
                # Microbatch m leaves the last stage at tick m + S - 1.
                return new_carry, out[-1]

        # Stream microbatches through T = num_micro + S - 1 ticks; the params
        # are broadcast (every tick reuses the same stage weights).
        feed = {"x": microbatch_feed(x, "x")}
        if pos_feed is not None:
            feed["pos"] = pos_feed
        if seg_feed is not None:
            feed["seg"] = seg_feed
        carry0 = (
            constrain_buf(jnp.zeros((stages, mb_size, *x.shape[1:]), x.dtype)),
            None if pos_feed is None else jnp.zeros(
                (stages, *pos_feed.shape[1:]), pos_feed.dtype
            ),
            None if seg_feed is None else jnp.zeros(
                (stages, *seg_feed.shape[1:]), seg_feed.dtype
            ),
        )
        ticks = nn.scan(
            PipeTick,
            variable_broadcast="params",
            split_rngs={"params": False},
            in_axes=0,
            out_axes=0,
            length=num_micro + stages - 1,
        )(cfg, self.block_cls, name="ticks")
        _, outs = ticks(carry0, feed)
        # Microbatch m leaves the last stage at tick m + S - 1.
        y = outs[stages - 1 :].reshape(batch, *x.shape[1:])
        return nn.with_logical_constraint(
            y, (lr.BATCH, lr.ACT_SEQ, lr.ACT_EMBED)
        ), aux0

    def _circular(
        self,
        x: jax.Array,
        positions: Optional[jax.Array],
        segment_ids: Optional[jax.Array],
    ) -> jax.Array:
        """Interleaved-1F1B-equivalent circular schedule (apply only).

        Chunk c (of S*v chunks, ``per_chunk = L/(S*v)`` layers each) lives
        on device c mod S; a microbatch makes v laps around the ring.  At
        tick t, device s computes the item fed at i = t - s, which is lap
        ``i // M`` of microbatch ``i % M`` — so each device selects its
        lap's layer window with one dynamic slice of its [per_stage, ...]
        params (per_stage = v * per_chunk, the SAME param layout the
        non-interleaved schedule creates, so checkpoints carry over).  An
        item leaving stage S-1 at tick e re-enters stage 0 at tick
        e + (M - S + 1) for its next lap, carried by a FIFO of that
        length.  Total ticks vM + S - 1 of one chunk-compute each ==
        useful fraction vM/(vM+S-1) — the interleaved-1F1B bubble, for
        the backward too (jax AD reverses the scan tick-for-tick).
        Match: ref ``StageInterleaver.py`` (virtual pipeline stages over
        RPC); here the handoff is the collective-permute the buffer shift
        lowers to.
        """
        cfg = self.config
        stages = cfg.pipeline_stages
        v = cfg.pipeline_interleave
        num_micro = cfg.num_microbatches or stages
        per_chunk = cfg.num_scan_units // (stages * v)
        batch = x.shape[0]
        mb_size = batch // num_micro
        total_ticks = v * num_micro + stages - 1
        queue_len = num_micro - stages + 1

        layers_tree = self.variables["params"]["ticks"]["stages"]["layers"]
        layers_tree = nn.meta.unbox(layers_tree)

        # Param re-layout: storage is stage-contiguous (device s holds
        # global layers [s*per_stage, (s+1)*per_stage) — the layout the
        # non-interleaved schedule and its checkpoints use), but the
        # circular schedule needs device s to hold global chunks
        # {s, S+s, ..., (v-1)S+s} so a microbatch's lap-ℓ visit to device
        # s applies global chunk ℓS+s.  The static gather below crosses
        # the STAGES-sharded dim (XLA lowers it to a weight all-to-all
        # over ICI, ~param_bytes/step fwd + the transpose scatter in bwd)
        # — the price of keeping ONE param/checkpoint layout across
        # interleave settings; measured in tools/pipeline_account.py.
        import numpy as np

        order = (
            np.arange(v)[None, :] * stages + np.arange(stages)[:, None]
        ).reshape(-1)

        def relayout(w):
            w = w.reshape(stages * v, per_chunk, *w.shape[2:])
            w = jnp.take(w, order, axis=0)
            return w.reshape(stages, v * per_chunk, *w.shape[2:])

        layers_tree = jax.tree.map(relayout, layers_tree)
        config, block_cls = self.config, self.block_cls

        class ChunkStack(nn.Module):
            """per_chunk consecutive layers, applied with sliced params."""

            length: int

            @nn.compact
            def __call__(self, x, positions, segment_ids):
                stack = nn.scan(
                    block_cls,
                    variable_axes={"params": 0},
                    split_rngs={"params": True},
                    in_axes=nn.broadcast,
                    length=self.length,
                    metadata_params={nn.PARTITION_NAME: lr.LAYERS},
                )(config, name="layers")
                (x, _aux), _ = stack(
                    (x, jnp.zeros((), jnp.float32)), positions, segment_ids
                )
                return x

        # Construct ONCE at this trace level: flax 0.10 forbids building a
        # Module at a deeper jax trace (inside the vmap/scan below) while a
        # parent module's method is live; ``.apply`` on a prebuilt instance
        # is fine at any depth.
        chunk_stack = ChunkStack(per_chunk)

        def chunk_apply(stage_layers, lap, x_mb, pos_mb, seg_mb):
            sliced = jax.tree.map(
                lambda w: lax.dynamic_slice_in_dim(
                    w, lap * per_chunk, per_chunk, axis=0
                ),
                stage_layers,
            )
            return chunk_stack.apply(
                {"params": {"layers": sliced}}, x_mb, pos_mb, seg_mb
            )

        def side_feed(arr, name):
            """[M, mb, ...] per-microbatch side input, or None for a
            broadcast side (used unsliced via closure)."""
            if arr is None or arr.shape[0] == 1:
                return None
            if arr.shape[0] != batch:
                raise ValueError(
                    f"{name} leading dim {arr.shape[0]} must be 1 or match "
                    f"the batch {batch} under pipeline microbatching"
                )
            return arr.reshape(num_micro, mb_size, *arr.shape[1:])

        pos_m = side_feed(positions, "positions")
        seg_m = side_feed(segment_ids, "segment_ids")

        buf_spec = (lr.STAGES, lr.BATCH, lr.ACT_SEQ, lr.ACT_EMBED)
        feed_spec = (lr.GATHERED, lr.BATCH, lr.ACT_SEQ, lr.ACT_EMBED)

        def constrain_buf(buf):
            return nn.with_logical_constraint(buf, buf_spec)

        # Fresh feed: microbatch t enters at tick t < M; later entries come
        # from the recirculation queue.
        fresh = x.reshape(num_micro, mb_size, *x.shape[1:])
        fresh = nn.with_logical_constraint(fresh, feed_spec)
        pad = nn.with_logical_constraint(
            jnp.zeros(
                (total_ticks - num_micro, *fresh.shape[1:]), fresh.dtype
            ),
            feed_spec,
        )
        fresh = nn.with_logical_constraint(
            jnp.concatenate([fresh, pad], axis=0), feed_spec
        )

        stage_ids = jnp.arange(stages)

        def tick(carry, fresh_t):
            buf, queue, t = carry
            entry = jnp.where(t // num_micro >= 1, queue[0], fresh_t)
            state = constrain_buf(
                jnp.concatenate([entry[None], buf[:-1]], axis=0)
            )
            item = t - stage_ids              # feed index per stage
            laps = jnp.clip(item // num_micro, 0, v - 1)
            mb = jnp.where(item >= 0, item % num_micro, 0)
            pos_in = positions if pos_m is None else jnp.take(
                pos_m, mb, axis=0
            )
            seg_in = segment_ids if seg_m is None else jnp.take(
                seg_m, mb, axis=0
            )
            out = jax.vmap(
                chunk_apply,
                in_axes=(
                    0, 0, 0,
                    None if pos_m is None else 0,
                    None if seg_m is None else 0,
                ),
            )(layers_tree, laps, state, pos_in, seg_in)
            out = constrain_buf(out)
            exit_val = out[-1]
            new_queue = jnp.concatenate(
                [queue[1:], exit_val[None]], axis=0
            )
            return (out, new_queue, t + 1), exit_val

        carry0 = (
            constrain_buf(
                jnp.zeros((stages, mb_size, *x.shape[1:]), x.dtype)
            ),
            nn.with_logical_constraint(
                jnp.zeros((queue_len, mb_size, *x.shape[1:]), x.dtype),
                feed_spec,
            ),
            jnp.zeros((), jnp.int32),
        )
        _, exits = lax.scan(tick, carry0, fresh, length=total_ticks)
        # Microbatch m's final (lap v-1) exit lands at tick
        # (v-1)M + m + S - 1: the last M exits, in microbatch order.
        y = exits[(v - 1) * num_micro + stages - 1:]
        y = y.reshape(batch, *x.shape[1:])
        return nn.with_logical_constraint(
            y, (lr.BATCH, lr.ACT_SEQ, lr.ACT_EMBED)
        )
