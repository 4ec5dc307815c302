"""Slotted/paged KV-cache decode programs on the training models.

The compiled substrate under :mod:`dlrover_tpu.serving.engine`: a fixed
pool of per-request cache *slots* plus three jitted programs that never
retrace in steady state —

* ``prefill(params, tokens[1, bucket], true_len, rng, temp, topk)`` — run
  one prompt (right-padded to a bucket width; pads are causally inert,
  see ``serving/bucketing.py``) through the decode-mode model with a
  fresh batch-1 cache, sample its first token from the logits at
  ``true_len - 1``, and hand back the filled cache row.  Retraces per
  bucket width only.
* ``insert(pool, row, slot)`` — dynamic-update-slice the prefilled row
  into the pool at a *traced* slot index (one program for every slot).
  Overwrites the slot's ENTIRE cache row, so a recycled slot can never
  leak a previous request's K/V.
* ``decode_step(params, pool, tokens[S], positions[S], rng, temps[S],
  topks[S])`` — advance ALL slots one token: per-slot positional cache
  writes (models/attention.py), per-slot sampling via vectorized
  temperature/top-k arrays.  ONE program regardless of which slots are
  live; free slots compute garbage the host ignores and the next
  ``insert`` overwrites.

Two optional layers ride the same programs:

* **Tensor parallelism** (``tp=ServeTPMesh``): params and the KV pool
  shard GSPMD-style over the mesh's ``tensor`` axis under
  ``serving/tp.py``'s Megatron rule table — per-device pool bytes fall
  as 1/tp, and the program memo keys on ``(logical_tp, physical_tp)``
  so a fleet resize that folds back to a seen width retraces nothing.
* **Speculative decoding** (:class:`SpecPrograms`): a draft model
  proposes γ greedy tokens in one scanned program; a verify program
  runs the γ+1-wide chunk through the target once and accepts the
  longest matching prefix plus one bonus token — lossless for greedy
  rows (bitwise the plain decode path), graceful n=0 fallback for
  sampled rows.

Programs are memoized process-wide by ``compile_cache.serve_cache_key``,
and :meth:`ServePrograms.aot_compile` lower+compiles all of them ahead of
the first request (AOT warm-start) — a second engine on the same key pays
zero trace and zero compile.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.models.transformer import TransformerConfig, TransformerLM
from dlrover_tpu.runtime import compile_cache
from dlrover_tpu.runtime.compile_cache import serve_cache_key
from dlrover_tpu.serving.tp import (
    SERVE_TP_RULES,
    ServeTPMesh,
    param_shardings,
    validate_tp_config,
)
from dlrover_tpu.trainer import train_lib

NEG_INF = -1e15

#: Speculative proposal-length ceiling: the verify chunk is ``γ+1`` wide
#: and must stay under the decode-mode flash-prefill threshold (16) so a
#: verify step never takes the position-0-only kernel path.
MAX_SPEC_TOKENS = 14


def decode_config(config: TransformerConfig) -> TransformerConfig:
    """The decode-mode twin of a training config: same param tree, KV
    cache enabled, training-only machinery (remat/pipeline) off.  The
    attention impl is PRESERVED for ``"xla"``/``"flash"`` — flash serves
    the bucketed prefill chunks (models/attention.py decode branch) —
    and only ``"ring"`` (no decode path) normalizes to ``"xla"``."""
    return dataclasses.replace(
        config,
        decode=True,
        attention_impl=(
            "xla" if config.attention_impl == "ring"
            else config.attention_impl
        ),
        remat="none",
        pipeline_stages=1,
        num_microbatches=0,
        pipeline_interleave=1,
    )


def sample_tokens(
    logits: jax.Array,
    rng: jax.Array,
    temps: jax.Array,
    topks: jax.Array,
    max_top_k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Vectorized per-row sampling: ``(tokens [N], logprobs [N])``.

    Per-row ``temps``/``topks`` make one compiled program serve every
    SamplingParams mix in the batch: ``temp == 0`` rows take the argmax
    (the temperature->0 limit, matching ``rl/generation.py``), ``topk > 0``
    rows filter below their k-th largest logit.  ``max_top_k`` is the
    STATIC ceiling on per-request k — the ``lax.top_k`` width the program
    is compiled for (O(V log kmax), not a full-vocab sort).

    Logprobs are of the *returned* token under the raw (unscaled,
    unfiltered) distribution — the same contract as the RL rollout path,
    so the two engines' outputs are directly comparable.
    """
    logits32 = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits32, axis=-1)
    scaled = logits32 / jnp.maximum(temps, 1e-6)[:, None]
    if max_top_k > 0:
        kmax = min(max_top_k, logits32.shape[-1])
        vals, _ = jax.lax.top_k(scaled, kmax)
        idx = jnp.clip(topks - 1, 0, kmax - 1)
        kth = jnp.take_along_axis(vals, idx[:, None], axis=-1)
        scaled = jnp.where(
            (topks[:, None] > 0) & (scaled < kth), NEG_INF, scaled
        )
    sampled = jax.random.categorical(rng, scaled, axis=-1)
    tokens = jnp.where(temps > 0.0, sampled, greedy).astype(jnp.int32)
    logp = jax.nn.log_softmax(logits32, axis=-1)
    logp = jnp.take_along_axis(logp, tokens[:, None], axis=-1)[:, 0]
    return tokens, logp


def _programs_key(
    config: TransformerConfig,
    slots: int,
    buckets: Tuple[int, ...],
    max_top_k: int,
    tp: Optional[ServeTPMesh],
) -> str:
    """The ONE spelling of a program set's memo key (used by both
    :func:`get_programs` and ``ServePrograms.__init__`` so they can
    never drift): the attention impl is the decode twin's (what the
    programs actually lower), and ``tp`` carries (logical, physical)."""
    twin = decode_config(config)
    return serve_cache_key(
        config,
        slots=slots,
        buckets=tuple(sorted(buckets)),
        max_top_k=max_top_k,
        attention_impl=twin.attention_impl,
        tp=(tp.logical_tp, tp.physical_tp) if tp is not None else (),
    )


class ServePrograms:
    """The jitted prefill/insert/decode triple for one (config, slots,
    buckets, max_top_k, tp) tuple.  Obtain through :func:`get_programs`
    so equal keys share traced programs and AOT executables."""

    def __init__(
        self,
        config: TransformerConfig,
        slots: int,
        buckets: Tuple[int, ...],
        max_top_k: int = 64,
        tp: Optional[ServeTPMesh] = None,
    ):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if not buckets:
            raise ValueError("at least one prefill bucket is required")
        buckets = tuple(sorted(int(b) for b in buckets))
        if buckets[0] < 1:
            raise ValueError(f"bucket widths must be >= 1, got {buckets}")
        self.config = decode_config(config)
        if buckets[-1] >= self.config.max_seq_len:
            raise ValueError(
                f"largest bucket {buckets[-1]} must leave decode room "
                f"inside max_seq_len {self.config.max_seq_len}"
            )
        if max_top_k < 0 or max_top_k > self.config.vocab_size:
            raise ValueError(
                f"max_top_k must be in [0, vocab_size], got {max_top_k}"
            )
        self.slots = slots
        self.buckets = buckets
        self.max_top_k = max_top_k
        self.tp = tp
        self.model = TransformerLM(self.config)
        self.cache_key = _programs_key(
            config, slots, buckets, max_top_k, tp
        )
        if tp is None:
            self._param_sh = self._pool_sh = self._row_sh = None
            self._prefill = jax.jit(self._prefill_impl)
            self._insert = jax.jit(self._insert_impl, donate_argnums=(0,))
            self._decode = jax.jit(self._decode_impl, donate_argnums=(1,))
        else:
            validate_tp_config(self.config, tp.logical_tp)
            example = jnp.zeros((1, 4), jnp.int32)
            self._param_sh = param_shardings(tp, self.model, example)
            # Abstract params (plain unboxed leaves) seed the pool/row
            # shape harvest without ever running a forward pass.
            import flax.linen as nn

            abstract_params = jax.eval_shape(
                lambda: nn.meta.unbox(
                    self.model.init(jax.random.PRNGKey(0), example)[
                        "params"
                    ]
                )
            )
            pool_struct = jax.eval_shape(
                lambda p: self._cache_shapes(p, self.slots),
                abstract_params,
            )
            row_struct = jax.eval_shape(
                lambda p: self._cache_shapes(p, 1), abstract_params
            )
            self._pool_sh = tp.pool_shardings(pool_struct)
            self._row_sh = tp.pool_shardings(row_struct)
            rep = tp.replicated()
            self._prefill = jax.jit(
                self._prefill_impl,
                in_shardings=(
                    self._param_sh, rep, rep, rep, rep, rep
                ),
                out_shardings=(self._row_sh, rep, rep),
            )
            self._insert = jax.jit(
                self._insert_impl,
                donate_argnums=(0,),
                in_shardings=(self._pool_sh, self._row_sh, rep),
                out_shardings=self._pool_sh,
            )
            self._decode = jax.jit(
                self._decode_impl,
                donate_argnums=(1,),
                in_shardings=(
                    self._param_sh, self._pool_sh,
                    rep, rep, rep, rep, rep,
                ),
                out_shardings=(self._pool_sh, rep, rep),
            )
        # AOT executables: {("prefill", bucket) | ("insert",) | ("decode",)
        # -> compiled}.  Populated by aot_compile; the jit path is the
        # fallback (first call traces lazily).
        self._aot: Dict[Tuple, Any] = {}

    def _trace_ctx(self):
        """Tracing context: under TP the model's logical-axis constraints
        need the mesh + rule table ambient (same contexts the trainer
        traces under); without TP this is free."""
        if self.tp is None:
            return contextlib.nullcontext()
        import flax.linen as nn

        stack = contextlib.ExitStack()
        stack.enter_context(train_lib.use_mesh(self.tp.mesh))
        stack.enter_context(nn.logical_axis_rules(SERVE_TP_RULES))
        return stack

    # -- cache pool -----------------------------------------------------------

    def _cache_shapes(self, params, batch: int):
        _, mutated = self.model.apply(
            {"params": params},
            jnp.zeros((batch, 1), jnp.int32),
            positions=jnp.zeros((batch, 1), jnp.int32),
            mutable=["cache"],
        )
        return mutated["cache"]

    def init_cache(self, params) -> Any:
        """A zeroed slot-pool cache pytree ([layers, slots, max_seq, H_kv,
        hd] per K/V leaf).  ``eval_shape`` keeps this allocation-only —
        no forward pass runs.  Under TP the pool lands pre-sharded on its
        heads axis."""
        shapes = jax.eval_shape(
            lambda p: self._cache_shapes(p, self.slots), params
        )
        if self.tp is None:
            return jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes
            )
        return jax.tree.map(
            lambda s, sh: jax.device_put(
                jnp.zeros(s.shape, s.dtype), sh
            ),
            shapes, self._pool_sh,
        )

    # -- placement ------------------------------------------------------------

    def place_params(self, params):
        """Lay a (host or differently-placed) param tree out under the
        programs' shardings — identity without TP.  Accepts boxed
        (``LogicallyPartitioned``) trees straight from ``model.init``;
        the shardings here are the serve fold's, not the boxes'."""
        if self.tp is None:
            return params
        params = nn.meta.unbox(params)
        return jax.tree.map(
            lambda p, s: jax.device_put(p, s), params, self._param_sh
        )

    def place_row(self, row):
        """Lay a prefilled cache row (possibly a host-numpy page streamed
        from a prefill replica) out under the pool's sharding."""
        if self.tp is None:
            return row
        return jax.tree.map(
            lambda leaf, s: jax.device_put(jnp.asarray(leaf), s),
            row, self._row_sh,
        )

    def pool_device_bytes(self, pool) -> int:
        """Max per-device bytes of ``pool`` (the whole pool without TP)."""
        if self.tp is not None:
            return self.tp.pool_device_bytes(pool)
        return sum(
            getattr(leaf, "nbytes", 0) for leaf in jax.tree.leaves(pool)
        )

    # -- traced programs ------------------------------------------------------

    def _prefill_impl(self, params, tokens, true_len, rng, temp, topk):
        train_lib.TRACE_COUNTS["serve_prefill"] += 1
        width = tokens.shape[1]
        (logits, _), mutated = self.model.apply(
            {"params": params},
            tokens,
            positions=jnp.arange(width)[None, :],
            mutable=["cache"],
        )
        # The next-token logits live at the LAST REAL position, not the
        # padded end — a traced gather, so one program serves every
        # true_len inside the bucket.
        last = jax.lax.dynamic_slice_in_dim(
            logits, true_len - 1, 1, axis=1
        )[:, 0]
        first, logp = sample_tokens(
            last, rng, temp, topk, self.max_top_k
        )
        return mutated["cache"], first, logp

    def _insert_impl(self, pool, row, slot):
        train_lib.TRACE_COUNTS["serve_insert"] += 1

        def put(pool_leaf, row_leaf):
            if pool_leaf.ndim < 2:
                # Per-layer scalars (the cache_index cursor) carry no
                # per-slot state — keep the pool's.
                return pool_leaf
            start = (0, slot) + (0,) * (pool_leaf.ndim - 2)
            return jax.lax.dynamic_update_slice(
                pool_leaf, row_leaf.astype(pool_leaf.dtype), start
            )

        return jax.tree.map(put, pool, row)

    def _decode_impl(self, params, pool, tokens, positions, rng, temps,
                     topks):
        train_lib.TRACE_COUNTS["serve_decode"] += 1
        (logits, _), mutated = self.model.apply(
            {"params": params, "cache": pool},
            tokens[:, None],
            positions=positions[:, None],
            mutable=["cache"],
        )
        next_tokens, logp = sample_tokens(
            logits[:, 0], rng, temps, topks, self.max_top_k
        )
        return mutated["cache"], next_tokens, logp

    # -- dispatch -------------------------------------------------------------

    def prefill(self, params, tokens, true_len, rng, temp, topk):
        fn = self._aot.get(("prefill", tokens.shape[1]), self._prefill)
        with self._trace_ctx():
            return fn(params, tokens, true_len, rng, temp, topk)

    def insert(self, pool, row, slot):
        fn = self._aot.get(("insert",), self._insert)
        with self._trace_ctx():
            return fn(pool, row, slot)

    def decode_step(self, params, pool, tokens, positions, rng, temps,
                    topks):
        fn = self._aot.get(("decode",), self._decode)
        with self._trace_ctx():
            return fn(params, pool, tokens, positions, rng, temps, topks)

    # -- AOT warm-start -------------------------------------------------------

    def aot_compile(self, params) -> float:
        """``lower().compile()`` every serving program ahead of the first
        request.  Returns the wall seconds spent; ``0.0`` means every
        program was already compiled (a warm start — the caller books it
        as a cached compile in the goodput ledger)."""
        t0 = time.perf_counter()
        compiled_any = False
        rng = jax.random.PRNGKey(0)
        one = jnp.ones((1,), jnp.float32)
        one_k = jnp.zeros((1,), jnp.int32)
        cache = None
        with self._trace_ctx():
            for bucket in self.buckets:
                key = ("prefill", bucket)
                if key in self._aot:
                    continue
                self._aot[key] = compile_cache.staged_compile(
                    self._prefill,
                    params, jnp.zeros((1, bucket), jnp.int32),
                    jnp.int32(bucket), rng, one, one_k,
                )
                compiled_any = True
            if ("insert",) not in self._aot or ("decode",) not in self._aot:
                cache = self.init_cache(params)
            if ("insert",) not in self._aot:
                # The batch-1 cache row a prefill produces: slot axis
                # sliced to width 1, per-layer scalars kept as-is.
                row = jax.tree.map(
                    lambda leaf: leaf[:, :1] if leaf.ndim >= 2 else leaf,
                    cache,
                )
                row = self.place_row(row)
                self._aot[("insert",)] = compile_cache.staged_compile(
                    self._insert, cache, row, jnp.int32(0),
                )
                compiled_any = True
            if ("decode",) not in self._aot:
                s = self.slots
                self._aot[("decode",)] = compile_cache.staged_compile(
                    self._decode,
                    params, cache,
                    jnp.zeros((s,), jnp.int32), jnp.zeros((s,), jnp.int32),
                    rng, jnp.ones((s,), jnp.float32),
                    jnp.zeros((s,), jnp.int32),
                )
                compiled_any = True
        return time.perf_counter() - t0 if compiled_any else 0.0


class SpecPrograms:
    """Speculative-decoding pair over two :class:`ServePrograms`:

    * ``propose(draft_params, draft_pool, tokens[S], positions[S])`` —
      the draft model greedily rolls γ tokens per slot inside ONE jitted
      ``lax.scan`` program (γ sequential draft steps, one dispatch),
      writing the draft's own KV pool as it goes.
    * ``verify(params, pool, chunk[S, γ+1], positions[S], rng, temps,
      topks)`` — the target model scores the whole chunk (current token
      + γ proposals) in one decode-mode apply; per slot the accepted
      length is the longest prefix where the draft matched the target's
      greedy argmax, plus one BONUS token from the target's own logits
      at the first divergence — so every verify emits ``n+1 ∈ [1, γ+1]``
      tokens and a greedy slot's token stream is bitwise the plain
      decode path's (lossless speculation).  Sampled rows (temp > 0)
      force ``n = 0`` and draw the bonus through the same
      ``sample_tokens`` contract as plain decode — speculation never
      changes a sampled distribution.

    Cache hygiene: verify writes K/V for all γ+1 chunk positions, but
    rejected positions are causally inert — ``cached_attention`` masks
    ``kpos <= q_position`` and the committed stream's next writes land
    exactly on (and overwrite) the stale rows, the same argument that
    makes prefill right-padding safe (serving/bucketing.py).
    """

    def __init__(
        self,
        target: ServePrograms,
        draft: ServePrograms,
        spec_tokens: int,
    ):
        if not 1 <= spec_tokens <= MAX_SPEC_TOKENS:
            raise ValueError(
                f"spec_tokens must be in [1, {MAX_SPEC_TOKENS}], got "
                f"{spec_tokens} (the γ+1-wide verify chunk must stay "
                "under the flash prefill threshold)"
            )
        if target.config.vocab_size != draft.config.vocab_size:
            raise ValueError(
                "draft and target must share a vocab: "
                f"{draft.config.vocab_size} != {target.config.vocab_size}"
            )
        if target.slots != draft.slots:
            raise ValueError(
                f"draft slots {draft.slots} != target slots {target.slots}"
            )
        t_tp = (target.tp.logical_tp, target.tp.physical_tp) \
            if target.tp else ()
        d_tp = (draft.tp.logical_tp, draft.tp.physical_tp) \
            if draft.tp else ()
        if t_tp != d_tp:
            raise ValueError(
                f"draft tp {d_tp} != target tp {t_tp}: the draft shares "
                "the TP decode path"
            )
        self.target = target
        self.draft = draft
        self.spec_tokens = spec_tokens
        self.cache_key = repr(
            ("spec", target.cache_key, draft.cache_key, spec_tokens)
        )
        if target.tp is None:
            self._propose = jax.jit(
                self._propose_impl, donate_argnums=(1,)
            )
            self._verify = jax.jit(
                self._verify_impl, donate_argnums=(1,)
            )
        else:
            rep = target.tp.replicated()
            self._propose = jax.jit(
                self._propose_impl,
                donate_argnums=(1,),
                in_shardings=(
                    draft._param_sh, draft._pool_sh, rep, rep
                ),
                out_shardings=(draft._pool_sh, rep),
            )
            self._verify = jax.jit(
                self._verify_impl,
                donate_argnums=(1,),
                in_shardings=(
                    target._param_sh, target._pool_sh,
                    rep, rep, rep, rep, rep,
                ),
                out_shardings=(
                    target._pool_sh, rep, rep, rep, rep
                ),
            )
        self._aot: Dict[Tuple, Any] = {}

    def _propose_impl(self, draft_params, draft_pool, tokens, positions):
        train_lib.TRACE_COUNTS["serve_draft"] += 1

        def body(carry, _):
            pool, tok, pos = carry
            (logits, _), mutated = self.draft.model.apply(
                {"params": draft_params, "cache": pool},
                tok[:, None],
                positions=pos[:, None],
                mutable=["cache"],
            )
            nxt = jnp.argmax(
                logits[:, 0].astype(jnp.float32), axis=-1
            ).astype(jnp.int32)
            return (mutated["cache"], nxt, pos + 1), nxt

        # One step more than is proposed: it writes the draft's K/V for
        # pγ, so a fully accepted round leaves no hole for the next to
        # attend over; its own proposal is discarded.
        (pool, _, _), proposed = jax.lax.scan(
            body, (draft_pool, tokens, positions), None,
            length=self.spec_tokens + 1,
        )
        return pool, jnp.transpose(proposed[:-1])  # [S, γ]

    def _verify_impl(self, params, pool, chunk, positions, rng, temps,
                     topks):
        train_lib.TRACE_COUNTS["serve_verify"] += 1
        s, width = chunk.shape  # width == γ + 1
        pos_grid = positions[:, None] + jnp.arange(width)[None, :]
        (logits, _), mutated = self.target.model.apply(
            {"params": params, "cache": pool},
            chunk,
            positions=pos_grid,
            mutable=["cache"],
        )
        logits32 = logits.astype(jnp.float32)
        target_greedy = jnp.argmax(logits32, axis=-1).astype(jnp.int32)
        proposals = chunk[:, 1:]  # [S, γ]
        match = (proposals == target_greedy[:, :-1]).astype(jnp.int32)
        # Longest matching prefix: cumprod kills everything after the
        # first mismatch.
        accepted = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # [S]
        greedy_row = temps <= 0.0
        accepted = jnp.where(greedy_row, accepted, 0)
        # The bonus token at the first divergence: the target's own
        # prediction for greedy rows, a real sample (same contract as
        # plain decode) for temp>0 rows — whose divergence point is
        # always chunk position 0.
        sampled0, _ = sample_tokens(
            logits32[:, 0], rng, temps, topks, self.target.max_top_k
        )
        bonus = jnp.take_along_axis(
            target_greedy, accepted[:, None], axis=1
        )[:, 0]
        bonus = jnp.where(greedy_row, bonus, sampled0)
        # emitted[i] = proposals[i] for i < n, bonus at i == n (host
        # reads emit_len = n+1 tokens; beyond that is junk).
        idx = jnp.arange(width)[None, :]
        prop_pad = jnp.concatenate(
            [proposals, jnp.zeros((s, 1), jnp.int32)], axis=1
        )
        emitted = jnp.where(
            idx < accepted[:, None], prop_pad, bonus[:, None]
        )
        logp_all = jax.nn.log_softmax(logits32, axis=-1)
        logps = jnp.take_along_axis(
            logp_all, emitted[..., None], axis=-1
        )[..., 0]
        emit_len = accepted + 1
        return mutated["cache"], emitted, emit_len, logps, accepted

    # -- dispatch -------------------------------------------------------------

    def propose(self, draft_params, draft_pool, tokens, positions):
        fn = self._aot.get(("propose",), self._propose)
        with self.target._trace_ctx():
            return fn(draft_params, draft_pool, tokens, positions)

    def verify(self, params, pool, chunk, positions, rng, temps, topks):
        fn = self._aot.get(("verify",), self._verify)
        with self.target._trace_ctx():
            return fn(params, pool, chunk, positions, rng, temps, topks)

    # -- AOT warm-start -------------------------------------------------------

    def aot_compile(self, params, draft_params) -> float:
        t0 = time.perf_counter()
        compiled_any = False
        s = self.target.slots
        tok = jnp.zeros((s,), jnp.int32)
        with self.target._trace_ctx():
            if ("propose",) not in self._aot:
                draft_pool = self.draft.init_cache(draft_params)
                self._aot[("propose",)] = compile_cache.staged_compile(
                    self._propose, draft_params, draft_pool, tok, tok,
                )
                compiled_any = True
            if ("verify",) not in self._aot:
                pool = self.target.init_cache(params)
                self._aot[("verify",)] = compile_cache.staged_compile(
                    self._verify,
                    params, pool,
                    jnp.zeros((s, self.spec_tokens + 1), jnp.int32), tok,
                    jax.random.PRNGKey(0),
                    jnp.zeros((s,), jnp.float32), tok,
                )
                compiled_any = True
        return time.perf_counter() - t0 if compiled_any else 0.0


# Process-wide program memo: equal serve keys share traced jit programs
# AND their AOT executables, so a rebuilt engine (elastic restart to the
# same shape, a TP re-fold back to a seen width, or the bench's
# warm-start leg) pays zero trace/compile.
_PROGRAMS: Dict[str, Any] = {}


def get_programs(
    config: TransformerConfig,
    slots: int,
    buckets: Tuple[int, ...],
    max_top_k: int = 64,
    tp: Optional[ServeTPMesh] = None,
) -> ServePrograms:
    key = _programs_key(config, slots, tuple(buckets), max_top_k, tp)
    programs = _PROGRAMS.get(key)
    if programs is None:
        programs = ServePrograms(config, slots, buckets, max_top_k, tp)
        _PROGRAMS[key] = programs
    return programs


def get_spec_programs(
    target: ServePrograms,
    draft: ServePrograms,
    spec_tokens: int,
) -> SpecPrograms:
    key = repr(("spec", target.cache_key, draft.cache_key, spec_tokens))
    programs = _PROGRAMS.get(key)
    if programs is None:
        programs = SpecPrograms(target, draft, spec_tokens)
        _PROGRAMS[key] = programs
    return programs


def clear_programs():
    """Test hook: drop the process-wide program memo."""
    _PROGRAMS.clear()
