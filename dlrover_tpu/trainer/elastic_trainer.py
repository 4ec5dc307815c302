"""ElasticTrainer: the reusable high-level training loop.

Capability ref: ``dlrover/trainer/torch/elastic/trainer.py:181-336``
(``ElasticTrainer.step`` keeps the global batch fixed via gradient
accumulation when the world shrinks) and the HF-style façade
``atorch/atorch/trainer/atorch_trainer.py:136`` (auto_accelerate + flash
checkpoint hooks around a training loop).

TPU redesign: under SPMD the *global* batch is a property of the compiled
program, not of the world — ``build_sharded_train(global_batch_size=...)``
keeps step semantics identical across elastic restarts by construction
(a smaller world recompiles with more per-device rows; no grad-accumulation
bookkeeping needed).  What remains for the façade is the glue every trainer
re-implements: strategy selection (manual or ``auto_tune``), sharded
init, checkpoint restore/save cadence, master step reporting, device
telemetry, and the crash/elastic-resume contract.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax
import numpy as np
import optax

from dlrover_tpu.common import faults, telemetry
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.retry import RetryError, RetryPolicy
from dlrover_tpu.models import transformer
from dlrover_tpu.models.transformer import TransformerConfig, TransformerLM
from dlrover_tpu.parallel import rules as lr
from dlrover_tpu.runtime import compile_cache, env as renv
from dlrover_tpu.runtime import virtual_mesh
from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
from dlrover_tpu.trainer import state_digest, train_lib
from dlrover_tpu.utils.profiler import pipeline_counters


# End-of-source sentinel of ``ElasticTrainer._waited``.
_NO_BATCH = object()
# The step metrics that are vectors, not scalars (the families' sown
# statistics): they stay on the device until a report reads them.
_STATS_KEYS = tuple(
    name for family in transformer.FAMILIES
    for name, fold in family.stats.items() if fold is not None
)

_PROCESS_START_BOOKED = False


def _book_process_start(restart: int):
    """``startup.runtime``: from this process's start, as the OS booked
    it, to its devices being listed — interpreter, imports and the
    accelerator runtime's start-up.  Once per process."""
    global _PROCESS_START_BOOKED
    started = telemetry.process_start_mono()
    if _PROCESS_START_BOOKED or started is None:
        return
    _PROCESS_START_BOOKED = True
    telemetry.event(
        "startup.runtime", duration_s=time.monotonic() - started,
        t_mono=started, restart_count=restart,
    )


@dataclasses.dataclass
class TrainerConfig:
    global_batch_size: int = 8
    seq_len: int = 128
    optimizer: str = "adamw"
    learning_rate: float = 1e-3
    # LR schedule (train_lib.make_schedule): warmup-linear, then cosine
    # decay when decay_steps > 0.
    warmup_steps: int = 0
    decay_steps: int = 0
    checkpoint_dir: str = ""
    ckpt_every: int = 100
    report_every: int = 5
    # Evaluation cadence: 0 disables periodic eval during fit().
    eval_every: int = 0
    eval_batches: int = 10
    auto_tune: bool = False
    ce_chunks: int = 0
    # Numeric health (trainer/numeric_health.py): anomalies ship to the
    # master with step reports, feeding the NumericAnomalyOperator.
    numeric_checks: bool = True
    # -- async step pipeline ------------------------------------------------
    # Deferred metrics: keep step metrics on device in a ring and
    # materialize them this many steps later with ONE blocking fetch
    # (flushed early at eval/checkpoint/end-of-fit).  0 = synchronous
    # legacy behavior: every report blocks on float(loss).
    metrics_lag: int = 0
    # Keep this many batches device-resident ahead of compute so the H2D
    # device_put of batch N+1 overlaps step N (data.loader.DevicePrefetcher).
    # 0 = place each batch synchronously on the step's critical path.
    prefetch_to_device: int = 0
    # -- restart-fast compile ----------------------------------------------
    # AOT lower().compile() the step at construction and report the wall
    # time to the master's goodput ledger (event "compile").
    warmup_compile: bool = False
    # -- microbatch engine --------------------------------------------------
    # Gradient accumulation: split the global batch into N microbatches
    # and lax.scan the fwd+bwd, accumulating grads on device
    # (train_lib.build_sharded_train).  Tokens/step is invariant in N; on
    # an elastic resize the effective N is recomputed from the reference
    # world below, so fewer chips -> more microbatches at ~constant
    # per-device activation HBM, same optimizer trajectory.
    grad_accum: int = 1
    # Accumulator dtype: "float32" (default; exact parity with the
    # full-batch step) or "bf16" (half the accumulator HBM; tolerance
    # documented in PROFILE.md).
    accum_dtype: str = "float32"
    # "int8" routes the deferred once-per-step DP gradient reduce through
    # the EQuARX-style quantized all-reduce; "none" = XLA's fp reduce.
    reduce_quant: str = "none"
    # ZeRO-1 cross-replica sharded weight update: optimizer state and the
    # parameter update sharded over the data axis, DP reduce lowered as
    # reduce-scatter + all-gather (optimizers/zero1.py).
    zero1: bool = False
    # Overlap engine (parallel/overlap.py, zero1 only): reduce-scatter
    # each microbatch's gradient inside the grad-accum scan and pipeline
    # the param all-gather per-bucket, so the zero1 wire hides under
    # compute structurally instead of by XLA-scheduler accident.
    overlap: bool = False
    # Collective bucket size for the overlap engine's wave schedule.
    overlap_bucket_mb: float = 4.0
    # "int8" routes the zero1 param re-replication all-gather through the
    # block-quantized wire format (quantized_collectives.
    # quantized_all_gather); "none" = full-precision all-gather.
    allgather_quant: str = "none"
    # -- silent data corruption ---------------------------------------------
    # Every N steps, digest the post-update train state on device
    # (trainer/state_digest.py) and queue it for the master's cross-replica
    # vote ledger; after the ZeRO-1 all-gather every DP replica holds
    # bitwise-identical state, so a minority digest pins the corrupting
    # host.  0 disables: no digest program is built, nothing is allocated.
    sdc_check_every: int = 0
    # -- device-time capture ---------------------------------------------------
    # Every N steps, wrap ONE step in a ``jax.profiler`` trace window and
    # emit the parsed per-phase device seconds as ``source="measured"``
    # timeline rows + a calibration event (utils/device_profile.py).  The
    # captured step pays one host<->device sync plus the trace write and
    # parse; 0 disables — no profiler object is built and the step path
    # allocates nothing.
    profile_every: int = 0
    # -- classified HBM accounting --------------------------------------------
    # Register the trainer's buffers (params / optimizer state / prefetch)
    # in the utils/memory_profile registry and ship one flat-attr
    # ``memory`` telemetry event per report tick: allocator bytes (or the
    # live-buffer nbytes fallback), per-pool classified bytes, the
    # compiled program's memory_analysis, and the measured-vs-modeled
    # bytes pairing for the master's calibration ledger.  Off (default):
    # the step path pays one attribute read and nothing else.
    memory_report: bool = False
    # World size ``grad_accum`` was chosen for; 0 = the world at first
    # construction.  Booked in checkpoint `extra` so a restore into a
    # different world recomputes N from the ORIGINAL reference pairing.
    grad_accum_ref_world: int = 0
    # -- virtual mesh ---------------------------------------------------------
    # Logical member count for elastic accounting (0 = jax.device_count()
    # at construction).  The VirtualMesh folds grad_accum_ref_world
    # logical submeshes onto this many members; ``apply_world_change``
    # moves it live without recompiling or restoring from storage.
    world: int = 0


class TrainerCallback:
    """Hook surface of the fit loop (ref ``atorch_trainer.py`` callbacks /
    the HF TrainerCallback contract it implements).  Subclass and override;
    every method is optional."""

    def on_train_begin(self, trainer: "ElasticTrainer"):
        pass

    def on_step_end(self, trainer: "ElasticTrainer", step: int,
                    metrics: Dict[str, Any]):
        pass

    def on_evaluate(self, trainer: "ElasticTrainer", step: int,
                    eval_metrics: Dict[str, float]):
        pass

    def on_checkpoint(self, trainer: "ElasticTrainer", step: int):
        pass

    def on_epoch_end(self, trainer: "ElasticTrainer", epoch: int):
        pass

    def on_train_end(self, trainer: "ElasticTrainer", step: int):
        pass


class ElasticTrainer:
    """Sharded training loop with flash checkpointing + master reporting.

    Usage::

        trainer = ElasticTrainer(model_config, TrainerConfig(...))
        trainer.fit(loader, max_steps=1000)
    """

    def __init__(
        self,
        model_config: TransformerConfig,
        config: TrainerConfig,
        parallel: Optional[ParallelConfig] = None,
        rules=None,
        optimizer: Optional[optax.GradientTransformation] = None,
        client=None,
        callbacks=None,
    ):
        self.config = config
        self.callbacks = list(callbacks or [])
        self.client = client if client is not None else renv.master_client()
        # From here on an open span is a ``dlrover:<name>`` row of whatever
        # profiler session is on.  The start-up spans share the resume's
        # identifier, so the agent's failure path and this trainer's
        # start-up read as one interval on the job timeline.
        telemetry.install_trace_annotations()
        restart = renv.restart_count()
        jax.devices()
        _book_process_start(restart)
        if config.auto_tune:
            from dlrover_tpu.auto import auto_tune

            tuned = auto_tune(
                model_config,
                global_batch_size=config.global_batch_size,
                seq_len=config.seq_len,
                optimizer=config.optimizer,
                max_measure=2,
            )
            model_config = tuned.model_config
            parallel = tuned.parallel
            logger.info("auto_tune picked %s", tuned.best.describe())
        self.model_config = model_config
        self.parallel = parallel or ParallelConfig(data=-1)
        with telemetry.span("startup.mesh", restart_count=restart):
            self.mesh = build_mesh(self.parallel)
        t_build = time.monotonic()
        self.model = TransformerLM(model_config)
        self.optimizer = optimizer or train_lib.make_optimizer(
            config.optimizer, learning_rate=config.learning_rate,
            warmup_steps=config.warmup_steps,
            decay_steps=config.decay_steps,
        )
        self.lr_schedule = train_lib.make_schedule(
            config.learning_rate, config.warmup_steps, config.decay_steps
        )
        self.numeric_monitor = None
        if config.numeric_checks:
            from dlrover_tpu.trainer.numeric_health import (
                NumericHealthMonitor,
            )

            self.numeric_monitor = NumericHealthMonitor()
        self.epoch = 0
        # Once a NaN/Inf is observed in the step scalars the live state is
        # poisoned; checkpoints taken after that point would be restored by
        # the master's RESTART_WORLD remediation and loop the failure.  The
        # flag resets on construction — the restart restores the last good
        # checkpoint into a fresh trainer.
        self._state_poisoned = False
        self._last_metrics = None
        # Deferred-metrics ring: (step, device_metrics) pairs awaiting the
        # single batched fetch in _flush_metrics.
        self._metrics_ring: List[Tuple[int, Dict[str, Any]]] = []
        # SDC sentry: lazily-built digest program (rebuilt when self.train
        # is) and (step, device_digest) pairs awaiting the report-cadence
        # ship — the fetch happens off the step's critical path.
        self._digest_fn = None
        self._digest_train = None
        self._pending_digests: List[Tuple[int, Any]] = []
        self._on_step: Optional[Callable[[int, Dict], None]] = None
        self._fit_max_steps = 0
        # Restart-fast compile, layer 1: persistent XLA cache so a restarted
        # process re-traces but skips compilation.
        compile_cache.maybe_enable()
        # Microbatch engine: resolve the effective grad_accum for THIS
        # world from the configured reference pairing (config.grad_accum @
        # grad_accum_ref_world, default: the current world), snapped to a
        # feasible divisor of the batch sharding.
        self._rules = rules if rules is not None else lr.DEFAULT_RULES
        self._world = max(1, config.world or jax.device_count())
        self._ref_accum = max(1, config.grad_accum)
        self._ref_world = config.grad_accum_ref_world or self._world
        # The virtual mesh: logical shape fixed at the reference world for
        # the life of the job, folded onto however many members are live.
        # grad_accum is the fold realized in time; the logical shape is
        # the resize-invariant bit of the compile-cache key.  The expert
        # plane (PR 19) is booked at the mesh's expert-axis size: expert
        # shards fold with the same s % P rule, and the logical expert
        # world rides train_cache_key via logical_shape.
        self._expert_world = self._mesh_expert_size()
        self.vmesh = virtual_mesh.VirtualMesh(
            self.mesh, logical_world=self._ref_world,
            physical_world=self._world,
            expert_logical=self._expert_world,
            expert_physical=self._expert_world,
        )
        # Live-resize plumbing: the prefetcher handle (for the drain) and
        # the fit loop's loader (for the sampler rebind).
        self._prefetcher = None
        self._active_loader = None
        # Sparse embedding plane (embedding/sharded.py), if the model has
        # one: its bucket→owner fold follows the dense world through every
        # resize/restore, and its booking rides the checkpoint ``extra``.
        self._embed_plane = None
        self._embed_dir = None
        # Device-time capture: None when off, so the step path pays one
        # attribute read and nothing else.
        self._device_profiler = None
        if config.profile_every > 0:
            from dlrover_tpu.utils.device_profile import DeviceProfiler

            self._device_profiler = DeviceProfiler(config.profile_every)
        self.grad_accum = self._resolve_grad_accum()
        if self.grad_accum != self._ref_accum:
            logger.info(
                "elastic grad_accum: %d (reference %d @ world %d -> world "
                "%d; tokens/step unchanged at %d)",
                self.grad_accum, self._ref_accum, self._ref_world,
                self._world,
                config.global_batch_size * config.seq_len,
            )
        # Layer 2: in-process program reuse.  Only config-built pieces are
        # representable in the key — a caller-supplied optimizer or rule
        # set could close over anything, so either one opts out.
        self._cacheable = optimizer is None and rules is None
        self.train = self._build_train()
        self._ckpt = None
        if config.checkpoint_dir:
            from dlrover_tpu.checkpoint import Checkpointer

            self._ckpt = Checkpointer(
                config.checkpoint_dir, local_saver=not renv.under_agent()
            )
            # The first save's one-time work (the arena made and its pages
            # touched, the staged programs compiled) needs only what the
            # state WILL be: on a thread from here, beside the step
            # program's compile, so that the first save is a later save.
            self._ckpt.prepare(
                self.train.abstract_state(), extra=self._accum_extra(),
                restart_count=restart,
            )
        # Model, optimizer and ``build_sharded_train``, up to ``compile``.
        telemetry.event(
            "startup.build", duration_s=time.monotonic() - t_build,
            t_mono=t_build, restart_count=restart,
        )
        if config.warmup_compile:
            before = compile_cache.stats()
            # The stages close before the ``compile`` event is recorded
            # (backdated, its duration what ``aot_compile`` returns), so
            # they are told their restart.
            t_compile = time.monotonic()
            compile_s = self.train.aot_compile(restart_count=restart)
            after = compile_cache.stats()
            # 0.0 means the build cache handed back an already-compiled
            # program — a zero-cost restart, recorded as a cache hit.  The
            # persistent_* counts say what the step program did to the
            # cross-process cache: a restarted trainer hits, never misses.
            # ``trace_s`` to ``analysis_s`` add up to ``seconds``;
            # ``text_s`` lies beside them.
            detail = {
                "seconds": round(compile_s, 6),
                "restart": renv.restart_count() > 0,
                "cached": compile_s == 0.0,
                "persistent_hits": after["hits"] - before["hits"],
                "persistent_misses": after["misses"] - before["misses"],
                **(self.train.compile_parts if compile_s else {}),
                "kernel_calls": self.train.kernel_calls,
                **transformer.kernel_facts(model_config, config.seq_len),
            }
            logger.info("compile warmup: %s", detail)
            telemetry.event(
                "compile", duration_s=compile_s, t_mono=t_compile,
                restart_count=restart, **detail,
            )
            if self.client is not None:
                self.client.report_event("compile", json.dumps(detail))
        with telemetry.span("startup.init", restart_count=restart):
            self.state = self.train.init(jax.random.PRNGKey(0))
        # Classified HBM accounting: None when off, so _report pays one
        # attribute read and nothing else (the same off-path contract as
        # the device profiler above).
        self._memory_registry = None
        if config.memory_report:
            from dlrover_tpu.utils import memory_profile

            self._memory_registry = memory_profile.registry()
            self._memory_registry.register(
                "params", "trainer.params", lambda: self.state.params
            )
            self._memory_registry.register(
                "opt_state", "trainer.opt_state",
                lambda: self.state.opt_state,
            )
            memory_profile.record_compiled_analysis(
                self._current_cache_key() or "",
                self.train.memory_analysis or {},
            )
        self.step = 0
        self._last_saved = 0
        if self._ckpt is not None:
            with telemetry.span("restore", restart_count=restart):
                restored_step, restored = self._ckpt.load_checkpoint(
                    shardings=self.train.state_shardings,
                    state_template=self.state,
                )
                if restored is not None:
                    self.state = self.train.adopt(restored)
            if restored is not None:
                self.step = restored_step
                # A restored step is NOT a step this world has committed:
                # shm restores (and another world's uncommitted files) are
                # exactly what elastic restarts resume from.  Leaving
                # _last_saved behind the current step makes the end-of-fit
                # persistence re-commit the state under THIS world.
                self._last_saved = -1
                logger.info(
                    "resumed from checkpoint at step %d", restored_step
                )
                self._adopt_checkpoint_accum(self._ckpt.last_extra)

    # -- read-only views (measurement harnesses, tools) -------------------------

    @property
    def checkpointing(self) -> bool:
        """Whether this trainer saves and restores (``checkpoint_dir``)."""
        return self._ckpt is not None

    @property
    def logical_axis_rules(self):
        """The logical-axis -> mesh-axis rule table the model is sharded
        by (``parallel/rules.py``)."""
        return self._rules

    # -- microbatch engine -----------------------------------------------------

    def _mesh_expert_size(self) -> int:
        """The mesh's expert-axis extent (1 when the axis is unit-sized
        or absent) — the expert plane's physical world."""
        names = tuple(getattr(self.mesh, "axis_names", ()))
        if "expert" not in names:
            return 1
        return int(self.mesh.devices.shape[names.index("expert")])

    def _dp_shards(self) -> int:
        """How many ways the batch dim splits on this mesh + rule table."""
        spec = train_lib.logical_sharding(
            self.mesh, self._rules, lr.BATCH
        ).spec
        return train_lib._batch_shard_count(
            self.mesh, spec[0] if spec else None
        )

    def _resolve_grad_accum(self) -> int:
        return self.vmesh.grad_accum_for(
            self._ref_accum, self.config.global_batch_size,
            self._dp_shards(),
        )

    def _build_train(
        self, grad_accum: Optional[int] = None
    ) -> train_lib.ShardedTrain:
        """Build (or cache-hit) the step program for ``grad_accum``
        microbatches (default: the current fold's)."""
        config = self.config
        accum = self.grad_accum if grad_accum is None else grad_accum
        cache_key = None
        if self._cacheable:
            cache_key = compile_cache.train_cache_key(
                self.model_config, self.mesh.devices.shape,
                global_batch_size=config.global_batch_size,
                seq_len=config.seq_len,
                ce_chunks=config.ce_chunks,
                optimizer=(
                    f"{config.optimizer}/lr={config.learning_rate!r}"
                    f"/warmup={config.warmup_steps}"
                    f"/decay={config.decay_steps}"
                ),
                grad_accum=accum,
                accum_dtype=config.accum_dtype,
                reduce_quant=config.reduce_quant,
                zero1=config.zero1,
                overlap=config.overlap,
                overlap_bucket_mb=config.overlap_bucket_mb,
                allgather_quant=config.allgather_quant,
                logical_shape=self.vmesh.logical_shape,
            )
        return train_lib.build_sharded_train(
            self.model, self.optimizer, self.mesh, self._rules,
            global_batch_size=config.global_batch_size,
            seq_len=config.seq_len,
            ce_chunks=config.ce_chunks,
            grad_accum=accum,
            accum_dtype=config.accum_dtype,
            reduce_quant=config.reduce_quant,
            zero1=config.zero1,
            overlap=config.overlap,
            overlap_bucket_mb=config.overlap_bucket_mb,
            allgather_quant=config.allgather_quant,
            cache_key=cache_key,
        )

    def attach_embedding_plane(self, plane, directory: str = None):
        """Bind a ``ShardedEmbeddingTable`` to the trainer's elasticity.

        From here on: the plane's bucket→owner booking rides every
        checkpoint's ``extra``; a live resize re-folds the plane alongside
        the dense state; a restore adopts the booked optimizer clocks and
        folds the plane onto the live world.  With ``directory`` set,
        every dense checkpoint also flushes the plane's delta export
        there (the preemption-drain leg — rows touched since the last
        export, under the integrity chain).

        If the trainer already restored a checkpoint before the attach
        (the normal construction order), the booking it carried is
        adopted now.
        """
        self._embed_plane = plane
        self._embed_dir = directory
        if self._ckpt is not None:
            self._adopt_embed_booking(self._ckpt.last_extra)

    def _adopt_embed_booking(self, extra):
        """Adopt a restored embed booking onto the LIVE world: clocks come
        from the booking, but the fold target is this trainer's current
        physical world — one reshard instead of a there-and-back through
        the save-time world."""
        plane = self._embed_plane
        if plane is None or not extra:
            return
        booking = extra.get("embed")
        if not booking:
            return
        booking = dict(booking)
        booking["world"] = self._world
        plane.adopt_booking(booking)

    def _accum_extra(self) -> Dict[str, Any]:
        """The microbatch-engine sidecar booked with every checkpoint."""
        extra = {
            "grad_accum": self.grad_accum,
            "grad_accum_ref": {
                "accum": self._ref_accum, "world": self._ref_world,
            },
            "accum_dtype": self.config.accum_dtype,
            "reduce_quant": self.config.reduce_quant,
            "zero1": self.config.zero1,
            "global_batch_size": self.config.global_batch_size,
            "world": self._world,
        }
        if self._embed_plane is not None:
            extra["embed"] = self._embed_plane.booking()
        return extra

    def _adopt_checkpoint_accum(self, extra: Dict[str, Any]):
        """Recompute grad_accum from the checkpoint's booked reference.

        The checkpoint carries the ORIGINAL (accum, world) pairing the run
        was launched with; a restore into a resized world derives N from
        that booking — not from whatever this process's config says — so
        every restart of the job lands on the same tokens/step-invariant
        schedule.  A changed N rebuilds the compiled program (state
        shardings are N-independent, so the restored state stays placed).
        """
        # The embed booking adopts regardless of the grad-accum outcome —
        # an unchanged microbatch schedule can still carry a plane whose
        # optimizer clocks moved.
        self._adopt_embed_booking(extra)
        ref = extra.get("grad_accum_ref") if extra else None
        if not ref:
            return
        booked = (int(ref.get("accum", 1)), int(ref.get("world", 0)))
        if booked[1] <= 0:
            return
        if booked == (self._ref_accum, self._ref_world):
            return
        self._ref_accum, self._ref_world = booked
        # The logical mesh is sized by the booked reference world — adopt
        # it so this process's virtual mesh (and program-family key)
        # matches every other member of the job.
        self.vmesh = virtual_mesh.VirtualMesh(
            self.mesh, logical_world=self._ref_world,
            physical_world=self._world,
            expert_logical=self._expert_world,
            expert_physical=self._expert_world,
        )
        resolved = self._resolve_grad_accum()
        if resolved == self.grad_accum:
            return
        logger.info(
            "checkpoint booked grad_accum reference %d @ world %d -> "
            "rebuilding with grad_accum=%d for world %d",
            booked[0], booked[1], resolved, self._world,
        )
        self.grad_accum = resolved
        self.train = self._build_train()

    # -- virtual mesh: live resize ---------------------------------------------

    def prewarm_worlds(
        self, worlds: Iterable[int], aot: bool = False
    ) -> Dict[int, int]:
        """Build the program family for every fold ``worlds`` implies, so
        a later ``apply_world_change`` to any of them is a pure build-
        cache hit (VirtualFlow's precompile-all-configurations move —
        cheap because every fold shares the logical shape and differs
        only in grad_accum).  ``aot=True`` additionally lowers+compiles
        each step program now; with it a resize to a warmed world
        performs ZERO traces and ZERO compiles.  Needs the in-process
        build cache (default optimizer and rules) to retain
        anything.  Returns ``{world: grad_accum}``."""
        out: Dict[int, int] = {}
        for world in worlds:
            vm = self.vmesh.with_world(int(world))
            accum = vm.grad_accum_for(
                self._ref_accum, self.config.global_batch_size,
                self._dp_shards(),
            )
            train = self._build_train(grad_accum=accum)
            if aot:
                train.aot_compile()
            out[int(world)] = accum
        return out

    def apply_world_change(
        self, new_world: int, reason: str = "scale"
    ) -> Dict[str, Any]:
        """Live re-layout to a resized world: no recompile, no restore.

        The graceful-resize path: the job world changed (a drained
        preemption, a scale plan) but THIS member survived, so its live
        state is authoritative — re-fold the virtual mesh onto the new
        member count in memory and keep stepping.  ``self.step`` is never
        rewound: the graceful path loses zero steps by construction.

        Retries ride the ``relayout.apply`` Faultline seam under a
        RetryPolicy; on exhaustion (or a member dying WITHOUT grace, when
        the re-layout source state is gone) the path degrades to the
        classic checkpoint restore, booked master-side as
        ``resizes_by_reason["relayout_failed"]``.

        Returns the booking detail (also shipped as a "relayout" node
        event + telemetry event): ok/fallback flags, worlds, fold,
        grad_accum, relayout seconds.
        """
        new_world = max(1, int(new_world))
        if new_world == self._world:
            return {
                "ok": True, "noop": True, "fallback": False,
                "old_world": self._world, "new_world": new_world,
            }
        # Barrier: the deferred-metrics ring references the pre-resize
        # program's outputs — flush under their own step attribution.
        self._flush_metrics()
        old_world = self._world
        t0 = time.perf_counter()
        policy = RetryPolicy(
            max_attempts=3, base_delay_s=0.05, max_delay_s=0.5,
            name="relayout.apply", quiet=True,
        )
        try:
            detail = policy.call(self._relayout, new_world)
        except RetryError as e:
            return self._relayout_fallback(new_world, reason, e)
        relayout_s = time.perf_counter() - t0
        detail.update(
            ok=True, fallback=False, old_world=old_world,
            new_world=new_world, reason=reason,
            relayout_s=round(relayout_s, 6),
        )
        logger.info(
            "live relayout: world %d -> %d (fold %d, grad_accum %d) in "
            "%.1f ms", old_world, new_world, detail["fold"],
            detail["grad_accum"], relayout_s * 1e3,
        )
        self._ship_relayout(detail, relayout_s)
        return detail

    def _relayout(self, new_world: int) -> Dict[str, Any]:
        """One re-layout attempt; commits only once everything succeeded,
        so a retried attempt always starts from a consistent trainer."""
        faults.fire(
            "relayout.apply", old_world=self._world, new_world=new_world,
        )
        vmesh = self.vmesh.with_world(new_world)
        accum = vmesh.grad_accum_for(
            self._ref_accum, self.config.global_batch_size,
            self._dp_shards(),
        )
        # Drain the prefetcher (generation token): device placements of
        # the old fold are dropped, host batches retained and re-placed.
        drained = (
            self._prefetcher.drain() if self._prefetcher is not None else 0
        )
        rebuilt = accum != self.grad_accum
        # A pure cache hit after prewarm_worlds — the logical shape in
        # the key never changed, only the fold's grad_accum did.
        train = self._build_train(grad_accum=accum) if rebuilt else self.train
        # In-memory re-layout of params/opt-state/RNG: PR 7's reshard
        # record mapping without the storage round-trip.  Transient cost:
        # one host copy of the state.
        state = train.adopt(
            virtual_mesh.relayout_state(self.state, train.state_shardings)
        )
        moves = len(self.vmesh.relayout_plan(new_world))
        # Re-fold an attached embedding plane onto the same new world.
        # Its seam fires before any owner mutates and migration inserts
        # before it removes, so a failure here aborts the attempt with
        # the plane intact (or duplicated, never short) for the retry.
        embed_moved = 0
        if self._embed_plane is not None:
            embed_moved = self._embed_plane.reshard(
                new_world
            )["moved_rows"]
        self.vmesh = vmesh
        self._world = new_world
        self.grad_accum = accum
        self.train = train
        self.state = state
        rebound = self._rebind_sampler(new_world)
        return {
            "fold": vmesh.fold, "grad_accum": accum,
            "drained_batches": drained, "rebuilt_program": rebuilt,
            "shard_moves": moves, "sampler_rebound": rebound,
            "embed_moved_rows": embed_moved,
            # Expert-plane booking: the per-process expert axis is
            # constant across a data-world resize, so the expert fold is
            # carried for the master's ledger (relayout_state above moved
            # the expert-sharded leaves bitwise along with the rest).
            "expert_world": vmesh.expert_physical,
            "expert_fold": vmesh.expert_fold,
        }

    def _relayout_fallback(
        self, new_world: int, reason: str, err: BaseException
    ) -> Dict[str, Any]:
        """Re-layout exhausted its retries: degrade to checkpoint restore
        (the same cycle an ungraceful member death forces — the live
        source state is unusable/gone, storage is the only truth)."""
        logger.error(
            "live relayout to world %d failed after retries (%s); "
            "degrading to checkpoint restore", new_world, err,
        )
        if self._ckpt is None:
            raise err
        old_world = self._world
        t0 = time.perf_counter()
        if self._prefetcher is not None:
            self._prefetcher.drain()
        self.vmesh = self.vmesh.with_world(new_world)
        self._world = new_world
        resolved = self._resolve_grad_accum()
        if resolved != self.grad_accum:
            self.grad_accum = resolved
            self.train = self._build_train()
        with telemetry.span("restore"):
            restored_step, restored = self._ckpt.load_checkpoint(
                shardings=self.train.state_shardings,
                state_template=self.state,
            )
        if restored is None:
            raise err
        self.state = self.train.adopt(restored)
        self.step = restored_step
        self._last_saved = -1
        self._adopt_checkpoint_accum(self._ckpt.last_extra)
        if (self._embed_plane is not None
                and self._embed_plane.world != new_world):
            # No embed booking rode this checkpoint — fold the live plane
            # onto the new world directly (its rows survived in host
            # memory; only ownership must follow the dense state).
            self._embed_plane.reshard(new_world)
        self._rebind_sampler(new_world)
        restore_s = time.perf_counter() - t0
        detail = {
            "ok": True, "fallback": True, "old_world": old_world,
            "new_world": new_world, "reason": reason,
            "relayout_s": round(restore_s, 6),
            "restored_step": restored_step,
            "grad_accum": self.grad_accum,
        }
        logger.warning(
            "relayout fallback: restored step %d from checkpoint in "
            "%.2f s", restored_step, restore_s,
        )
        self._ship_relayout(detail, restore_s)
        return detail

    def _rebind_sampler(self, new_world: int) -> bool:
        """Rebind the active loader's sampler onto the new physical world
        (its logical keying keeps the batch order invariant).  Lockstep
        and dynamic-sharding sources carry no rank binding — no-op."""
        loader = self._active_loader
        for candidate in (loader, getattr(loader, "source", None)):
            if candidate is not None and hasattr(candidate, "rebind_world"):
                candidate.rebind_world(num_replicas=new_world)
                return True
        return False

    def _ship_relayout(self, detail: Dict[str, Any], seconds: float):
        attrs = {k: v for k, v in detail.items() if k != "relayout_s"}
        telemetry.event("relayout", duration_s=seconds, **attrs)
        if self.client is not None:
            try:
                self.client.report_event("relayout", json.dumps(detail))
                telemetry.recorder().ship(self.client)
            except Exception as e:  # noqa: BLE001 — booking is best-effort
                logger.warning("relayout report failed: %s", e)

    # -- loop -----------------------------------------------------------------

    def train_step(self, batch: Dict[str, Any]):
        # The span times what the host observes of this step: H2D place +
        # dispatch, plus any backpressure XLA applies when the device falls
        # behind — exactly the per-node signal the master's step-skew
        # attribution compares across hosts.
        prof = self._device_profiler
        capturing = prof is not None and prof.arm(self.step + 1)
        t_span = time.monotonic()
        # The span is also the step's ``dlrover:step`` row in a profiler
        # trace: a host-side row, not a traced op, so the compiled step
        # program is untouched (no-retrace contract holds).
        with telemetry.span("step", step=self.step + 1):
            metrics = self._dispatch_step(batch)
        if capturing:
            self._finish_capture(t_span)
        self._last_metrics = metrics
        return metrics

    def _dispatch_step(self, batch: Dict[str, Any]):
        placed = train_lib.shard_batch(batch, self.train)
        t0 = time.perf_counter()
        try:
            self.state, metrics = self.train.step(self.state, placed)
        except Exception as e:
            # OOM forensics: before the process dies, write the
            # classified live-buffer table (who held the HBM) next to
            # the checkpoint dir.  Best-effort, then re-raise — the
            # postmortem must never mask the original error.
            from dlrover_tpu.utils import memory_profile

            if memory_profile.is_oom_error(e) and self.config.checkpoint_dir:
                memory_profile.dump_oom_postmortem(
                    self.config.checkpoint_dir, error=e,
                    cache_key=self._current_cache_key(),
                )
            raise
        self.step += 1
        pipeline_counters().record_dispatch(
            self.step, time.perf_counter() - t0
        )
        every = self.config.sdc_check_every
        if every > 0 and self.step % every == 0:
            # Booked inside the step span: the digest dispatch is part
            # of the step's host-observed cost at its check cadence.
            self._sdc_check()
        return metrics

    # -- device-time capture ---------------------------------------------------

    def _current_cache_key(self) -> str:
        """The live step program's compile-cache key — the calibration
        ledger's bucketing key.  Recomputed on demand: ``_build_train``
        also keys OTHER folds during prewarm/relayout, so nothing it
        stores could be trusted to describe the running program."""
        if not self._cacheable:
            return ""
        config = self.config
        return compile_cache.train_cache_key(
            self.model_config, self.mesh.devices.shape,
            global_batch_size=config.global_batch_size,
            seq_len=config.seq_len,
            ce_chunks=config.ce_chunks,
            optimizer=(
                f"{config.optimizer}/lr={config.learning_rate!r}"
                f"/warmup={config.warmup_steps}"
                f"/decay={config.decay_steps}"
            ),
            grad_accum=self.grad_accum,
            accum_dtype=config.accum_dtype,
            reduce_quant=config.reduce_quant,
            zero1=config.zero1,
            overlap=config.overlap,
            overlap_bucket_mb=config.overlap_bucket_mb,
            allgather_quant=config.allgather_quant,
            logical_shape=self.vmesh.logical_shape,
        )

    def _finish_capture(self, t_span: float):
        """Close the armed profiler window: block on the step's outputs so
        the device work lands inside the trace, then parse it and book the
        measured rows + calibration event.  Strictly best-effort — a
        failed window must never take the step down with it."""
        from dlrover_tpu.utils import device_profile

        # The capture sync is a deliberate host stall (the window must
        # close after the device finished) — book it as a host block so
        # the pipeline counters price what profiling costs the step loop.
        with pipeline_counters().host_block("profile-sync", steps=(self.step,)):
            try:
                jax.block_until_ready(self.state)
            except Exception as e:  # noqa: BLE001 — surface via the step
                logger.warning("device capture sync failed: %s", e)
        wall = time.monotonic() - t_span
        window = self._device_profiler.finish()
        if window is None:
            return
        # The modeled baseline for the SAME wall the window measured —
        # the calibration ratio compares like with like.
        rows = train_lib.microbatch_phase_plan(
            self.train.grad_accum, self.train.reduce_quant, wall,
            zero1=self.train.zero1, overlap=self.train.overlap,
        )
        device_profile.emit_measured_phases(
            window, step=self.step, t_span=t_span, wall_s=wall,
            modeled_rows=rows, cache_key=self._current_cache_key(),
        )

    # -- silent data corruption ------------------------------------------------

    def _sdc_check(self):
        """Digest the post-update state on device and queue it for the
        master's cross-replica vote (shipped on the report cadence).

        The ``sdc.flip`` chaos seam fires HOST-side here — never inside a
        traced function — so the drill corrupts one replica's live state
        without touching the compiled step program: trace purity and the
        zero-retrace contract both hold, and the corruption persists into
        every later step exactly like a real SDC event would.
        """
        try:
            faults.fire("sdc.flip", step=self.step)
        except faults.FaultInjected as e:
            logger.warning(
                "sdc.flip: flipping one mantissa bit in the live state (%s)",
                e,
            )
            self.state = state_digest.flip_mantissa_bit(self.state)
        if self._digest_fn is None or self._digest_train is not self.train:
            self._digest_fn = state_digest.build_digest_fn(self.train)
            self._digest_train = self.train
        with train_lib.use_mesh(self.train.mesh):
            value = self._digest_fn(self.state)
        self._pending_digests.append((self.step, value))

    def _batch_stream(self, loader: Iterable) -> Iterable:
        """Wrap ``loader`` in a DevicePrefetcher when configured, so batch
        N+1's H2D placement is issued before batch N is even handed to
        ``train_step`` (whose ``shard_batch`` then passes it through)."""
        if self.config.prefetch_to_device <= 0:
            self._prefetcher = None
            return self._waited(loader)
        from dlrover_tpu.data.loader import DevicePrefetcher

        # The handle is kept for apply_world_change's drain; place_fn
        # reads ``self.train`` at call time, so a post-resize re-place
        # lands under the new fold's program with no rebinding.
        self._prefetcher = DevicePrefetcher(
            loader,
            lambda batch: train_lib.shard_batch(batch, self.train),
            depth=self.config.prefetch_to_device,
        )
        return self._waited(self._prefetcher)

    def _waited(self, source: Iterable) -> Iterable:
        """``source``'s batches, with the wait for each one spanned
        (``data_wait``), whichever loader or prefetcher is underneath."""
        it = iter(source)
        while True:
            with telemetry.span("data_wait", step=self.step + 1):
                batch = next(it, _NO_BATCH)
            if batch is _NO_BATCH:
                return
            yield batch

    # -- deferred metrics ------------------------------------------------------

    def _flush_metrics(self):
        """Materialize the deferred-metrics ring with ONE blocking sync.

        Called every ``metrics_lag`` steps by the fit loop and forced at
        the pipeline barriers — evaluate, checkpoint, end-of-fit (a resize
        restart tears the trainer down through those same paths) — so no
        step's metrics outlive the state that produced them.  Each entry
        then flows through callbacks / reporting / numeric checks with its
        own step attribution, exactly as the synchronous loop would have.
        """
        if not self._metrics_ring:
            return
        ring, self._metrics_ring = self._metrics_ring, []
        steps = tuple(step for step, _ in ring)
        with pipeline_counters().host_block("metrics-flush", steps=steps):
            fetched = jax.device_get([
                {k: v for k, v in metrics.items() if k not in _STATS_KEYS}
                for _, metrics in ring
            ])
        for (step, device), host in zip(ring, fetched):
            host = {k: float(np.asarray(v)) for k, v in host.items()}
            for key in _STATS_KEYS:
                if key in device:
                    # A step's stats vectors (MoE router, linear
                    # attention) stay on the device: only a report reads
                    # them (``_report``).
                    host[key] = device[key]
            self._last_metrics = host
            if self._on_step is not None:
                self._on_step(step, host)
            self._dispatch("on_step_end", step, host)
            cfg = self.config
            if step % cfg.report_every == 0 or step == self._fit_max_steps:
                self._report(host, step=step)

    def _dispatch(self, hook: str, *args):
        for cb in self.callbacks:
            try:
                getattr(cb, hook)(self, *args)
            except Exception as e:  # noqa: BLE001 - one callback must not
                logger.warning("callback %s.%s failed: %s",
                               type(cb).__name__, hook, e)

    def current_lr(self, step: Optional[int] = None) -> float:
        """The LR the schedule prescribes at ``step`` (default: current)."""
        step = self.step if step is None else step
        if callable(self.lr_schedule):
            return float(self.lr_schedule(step))
        return float(self.lr_schedule)

    def evaluate(
        self,
        eval_loader: Iterable[Dict[str, Any]],
        max_batches: int = 0,
    ) -> Dict[str, float]:
        """Forward-only evaluation: mean loss + perplexity over the loader
        (ref ``atorch_trainer.py`` ``evaluate``/``prediction_loop``).

        Loss·tokens accumulate ON DEVICE across the loop; one blocking
        fetch at the end materializes both sums (a per-batch ``float()``
        would serialize host and device for the whole eval pass).
        """
        self._flush_metrics()
        t_eval = time.monotonic()
        weighted_loss = total_tokens = None  # device-resident accumulators
        batches = 0
        for batch in eval_loader:
            if max_batches and batches >= max_batches:
                break
            placed = train_lib.shard_batch(batch, self.train)
            metrics = self.train.eval_step(self.state, placed)
            weighted = metrics["loss"] * metrics["tokens"]
            if batches == 0:
                weighted_loss, total_tokens = weighted, metrics["tokens"]
            else:
                weighted_loss = weighted_loss + weighted
                total_tokens = total_tokens + metrics["tokens"]
            batches += 1
        if batches:
            with pipeline_counters().host_block(
                "eval-fetch", steps=(self.step,)
            ):
                fetched = jax.device_get(
                    {"loss": weighted_loss, "tokens": total_tokens}
                )
            total_tokens = float(np.asarray(fetched["tokens"]))
            mean_loss = (
                float(np.asarray(fetched["loss"])) / total_tokens
                if total_tokens else float("nan")
            )
        else:
            total_tokens, mean_loss = 0.0, float("nan")
        out = {
            "eval_loss": mean_loss,
            "eval_ppl": float(np.exp(min(mean_loss, 30.0))),
            "eval_tokens": total_tokens,
            "eval_batches": batches,
        }
        logger.info(
            "eval @ step %d: loss %.4f ppl %.2f (%d batches)",
            self.step, mean_loss, out["eval_ppl"], batches,
        )
        telemetry.event(
            "eval", duration_s=time.monotonic() - t_eval,
            step=self.step, batches=batches,
        )
        self._dispatch("on_evaluate", self.step, out)
        return out

    def fit(
        self,
        loader: Iterable[Dict[str, Any]],
        max_steps: int,
        on_step: Optional[Callable[[int, Dict], None]] = None,
        eval_loader: Optional[Iterable[Dict[str, Any]]] = None,
        epochs: int = 0,
    ) -> int:
        """Run until ``max_steps``; returns the final step.

        ``on_step(step, metrics)`` runs after every step (test hooks,
        custom logging); metrics values are still on device unless read.
        ``eval_loader`` + ``config.eval_every`` turn on periodic
        evaluation.  ``epochs > 0`` re-iterates ``loader`` that many times
        (resume-aware: a restored trainer continues counting from its
        restored step, and for a SIZED loader the epoch counter resumes at
        ``step // len(loader)``; an unsized generator cannot imply an
        epoch, so its counter restarts at 0).
        """
        cfg = self.config
        if epochs:
            # Single-use iterators (generators, list_iterator,
            # map/zip/filter, ...) are their own iterator and expose
            # __next__; containers don't.  (`iter(loader) is loader`
            # would be the textbook probe, but calling iter() consumes a
            # pass from stateful re-iterable loaders.)  Each is exhausted
            # after one pass, so the epoch counter would spin to N while
            # training a single epoch's worth of data.
            if hasattr(loader, "__next__"):
                raise ValueError(
                    f"fit(epochs={epochs}) needs a re-iterable loader, "
                    "got a one-shot iterator (pass a list, Dataset, or "
                    "ElasticDataLoader)"
                )
        t_start = time.monotonic()
        start_step = self.step
        steps_per_epoch = None
        if epochs and hasattr(loader, "__len__"):
            steps_per_epoch = max(1, len(loader))
            # Resume accounting: a restored step implies the epoch.
            self.epoch = self.step // steps_per_epoch
        self._on_step = on_step
        self._fit_max_steps = max_steps
        self._active_loader = loader  # apply_world_change's sampler rebind
        lag = max(0, cfg.metrics_lag)
        self._dispatch("on_train_begin")
        done = False
        epoch_iterations = max(1, epochs) if epochs else 1
        passes = 0
        while not done:
            # A resumed trainer can start at/past the epoch budget — check
            # BEFORE running a pass, not only after one completes.
            if epochs and self.epoch >= epoch_iterations:
                break
            batches_this_pass = 0
            for batch in self._batch_stream(loader):
                batches_this_pass += 1
                if self.step >= max_steps:
                    done = True
                    break
                metrics = self.train_step(batch)
                if lag:
                    # Pipelined: park the device metrics in the ring; they
                    # materialize (and drive callbacks/reporting with their
                    # own step attribution) ``lag`` steps later, in one
                    # batched fetch — the dispatch thread never blocks on
                    # the step it just enqueued.
                    self._metrics_ring.append((self.step, metrics))
                    if len(self._metrics_ring) >= lag:
                        self._flush_metrics()
                else:
                    if on_step is not None:
                        on_step(self.step, metrics)
                    self._dispatch("on_step_end", self.step, metrics)
                    if self.step % cfg.report_every == 0 or (
                        self.step == max_steps
                    ):
                        self._report(metrics)
                if cfg.eval_every and eval_loader is not None and (
                    self.step % cfg.eval_every == 0
                ):
                    self.evaluate(eval_loader, cfg.eval_batches)
                if self.step % cfg.ckpt_every == 0 or self.step == max_steps:
                    self.save_checkpoint()
            else:
                # Loader exhausted: an epoch boundary.
                passes += 1
                if epochs and passes > 1 and batches_this_pass == 0:
                    # A drained elastic loader (master-side epoch budget
                    # exhausted) or an empty per-host shard after a resize
                    # legitimately yields nothing — count the epoch and
                    # let the budget terminate, but say so: an exhausted
                    # iterator mistakenly passed here looks identical.
                    logger.warning(
                        "fit epoch pass %d yielded no batches (drained "
                        "dataset, empty shard, or a non-re-iterable "
                        "loader)", passes,
                    )
                self.epoch += 1
                self._dispatch("on_epoch_end", self.epoch)
                if epochs and self.epoch >= epoch_iterations:
                    done = True
                if not epochs:
                    done = True
        # End-of-fit barrier: drain whatever the ring still holds so the
        # final steps' metrics reach callbacks/reports before on_train_end.
        self._flush_metrics()
        if self._last_saved < self.step:
            # A restart can resume at (or past) max_steps with the newest
            # state only in a previous world's uncommitted files — persist
            # under THIS world before declaring done.
            self.save_checkpoint()
        elapsed = time.monotonic() - t_start
        tokens = (self.step - start_step) * cfg.global_batch_size * cfg.seq_len
        logger.info(
            "done: %d steps (%.1f tokens/s)", self.step,
            tokens / elapsed if elapsed > 0 else 0.0,
        )
        self._dispatch("on_train_end", self.step)
        if self.client is not None:
            try:
                telemetry.recorder().ship(self.client)
            except Exception as e:  # noqa: BLE001 - telemetry is best-effort
                logger.warning("final telemetry ship failed: %s", e)
        return self.step

    def _report(self, metrics: Dict[str, Any], step: Optional[int] = None):
        """Report ``metrics`` under ``step`` (default: the current step —
        the synchronous path; the deferred-metrics flush passes the ring
        entry's own step so lagged values keep correct attribution)."""
        cfg = self.config
        step = self.step if step is None else step
        loss = metrics["loss"]
        grad_norm = metrics.get("grad_norm")
        if isinstance(loss, jax.Array):
            # Synchronous mode's per-step blocking fetch — the "metrics"
            # block the pipeline counters tally as sync_block_count (and
            # the pipelined path never reaches: its flush hands host
            # floats in).  One device_get for both scalars.
            fetch = {"loss": loss}
            if grad_norm is not None:
                fetch["grad_norm"] = grad_norm
            with pipeline_counters().host_block("metrics", steps=(step,)):
                fetch = jax.device_get(fetch)
            loss = fetch["loss"]
            grad_norm = fetch.get("grad_norm")
        loss = float(loss)
        grad_norm = float(grad_norm) if grad_norm is not None else None
        logger.info(
            "step %d loss %.4f lr %.3g", step, loss, self.current_lr(step)
        )
        # Each family's health on the report cadence, as its own event; the
        # largest recurrent-state entry of any of them feeds the numeric
        # check, and one that is not a number stays so.
        absmaxes = [
            v for v in (
                self._report_family(family, metrics, step)
                for family in transformer.families(self.model_config)
            ) if v is not None
        ]
        state_absmax = None if not absmaxes else (
            float("nan") if any(v != v for v in absmaxes) else max(absmaxes)
        )
        anomalies = ()
        if self.numeric_monitor is not None:
            found = self.numeric_monitor.check(
                step, loss, grad_norm, state_absmax=state_absmax
            )
            if found:
                for a in found:
                    logger.error("numeric anomaly: %s", a.encode())
                anomalies = tuple(a.encode() for a in found)
                if any(a.kind == "nan" for a in found):
                    self._state_poisoned = True
        if self._memory_registry is not None:
            # Classified HBM snapshot on the report cadence, queued
            # BEFORE the ring ships below so it rides this report's
            # drain RPC.  Off path (memory_report=False) this branch is
            # the one attribute read.
            self._emit_memory_event(step)
        if self.client is not None:
            self.client.report_step(
                step,
                tokens=cfg.global_batch_size * cfg.seq_len
                * cfg.report_every,
                loss=loss,
                anomalies=anomalies,
            )
            # Piggyback the telemetry drain on the report cadence: one
            # extra RPC per report window, never per step.  Snapshot the
            # ring's drop count before ship() zeroes it — the pipeline
            # counters keep the worker-local lifetime tally.
            dropped = telemetry.recorder().dropped
            if dropped:
                pipeline_counters().record_dropped(dropped)
            telemetry.recorder().ship(self.client)
            if self._pending_digests:
                # Digest fetch + ship rides the same cadence: the uint32
                # scalars materialize here, off the step critical path.
                pending, self._pending_digests = self._pending_digests, []
                for dstep, value in pending:
                    self.client.report_digest(
                        dstep,
                        state_digest.format_digest(value),
                        check_every=cfg.sdc_check_every,
                    )
        from dlrover_tpu.agent.monitor import write_device_metrics

        write_device_metrics()

    def _report_family(self, family, metrics, step: int) -> Optional[float]:
        """``family``'s statistics of this step, if a report is due and the
        step handed them out, as the family's event (``models/family.py``:
        the declaration reads the vector and adds the layers' geometry).
        A sown vector is the one this step's program returned, so of the
        parameters the step ran with, ready when its loss is; it is fetched
        here, under the ``host_block`` of its metric's name.  Returns the
        attribute the family feeds the numeric check with, ``None`` where
        it names none or nothing was booked."""
        names = tuple(family.stats)
        values = [metrics.get(name) for name in names]
        if values[0] is None or step % self.config.report_every:
            return None
        if family.stats[names[0]] is not None:
            with pipeline_counters().host_block(names[0], steps=(step,)):
                values = jax.device_get(values)
        attrs = family.read(self.model_config, *values)
        telemetry.event(family.event, step=step, **attrs)
        return attrs.get(family.absmax)

    def _emit_memory_event(self, step: int):
        """One flat-attr ``memory`` event: allocator truth + classified
        pool bytes.  ``modeled_b`` is the shardings-derived param+opt
        model — the same quantity tune's est_hbm_gb books — so the
        master's calibration ratio measures what the shape model misses
        (temps, fragmentation, XLA slack)."""
        from dlrover_tpu.utils import memory_profile

        pools = self._memory_registry.pool_bytes()
        memory_profile.emit_memory_event(
            step=step,
            cache_key=self._current_cache_key(),
            modeled_b=pools["params"] + pools["opt_state"],
        )

    # -- checkpoint -----------------------------------------------------------

    def save_checkpoint(self):
        if self._ckpt is None:
            self._flush_metrics()
            return
        from dlrover_tpu.checkpoint import StorageType

        # The span is everything the training loop is blocked for: the
        # drain of what the device still had in flight, then the save.
        with telemetry.span("checkpoint", step=self.step) as span:
            with telemetry.span("checkpoint.drain"):
                # Checkpoint barrier: drain deferred metrics first, so (a)
                # every step committed by this save has already been
                # reported/attributed and (b) _healthy_to_save reads host
                # floats, not device arrays.
                self._flush_metrics()
                healthy = self._healthy_to_save()
            if healthy is False:
                logger.error(
                    "skipping checkpoint at step %d: state holds "
                    "non-finite values; waiting for the master's restart "
                    "remediation", self.step,
                )
                telemetry.event(
                    "checkpoint.skip", step=self.step, reason="non_finite"
                )
                return
            self._ckpt.save_checkpoint(
                self.step, self.state, StorageType.DISK,
                extra=self._accum_extra(),
            )
            # The first save says how long it was blocked for the arena's
            # preparation (``Checkpointer.prepare``): 0.0 where that work
            # was hidden behind the start.
            waited = self._ckpt.take_arena_wait()
            if span is not None and waited is not None:
                span.attrs["arena_wait_s"] = waited
        if self._embed_plane is not None and self._embed_dir is not None:
            # The plane's delta leg rides every dense checkpoint: rows
            # touched since the last export land under the integrity
            # chain, so a preemption after this point loses nothing.
            self._embed_plane.drain(self._embed_dir, self.step)
            self._embed_plane.emit_telemetry()
        self._last_saved = self.step
        self._dispatch("on_checkpoint", self.step)

    def _healthy_to_save(self) -> bool:
        """False when the live state is known (or found) non-finite.

        The monitor only samples on report cadence, so a NaN can land
        between reports; re-check the newest step's loss at save time —
        cheap (one scalar sync per checkpoint), and it closes the window
        where a poisoned state would be committed and later restored by
        the NumericAnomalyOperator's RESTART_WORLD remediation.
        """
        if self._state_poisoned:
            return False
        if self.numeric_monitor is not None and (
            self._last_metrics is not None
        ):
            # grad_norm too: the loss is computed on the PRE-update params,
            # so NaN gradients at the newest step poison the state while
            # its loss still reads finite.
            loss = float(self._last_metrics["loss"])
            grad_norm = self._last_metrics.get("grad_norm")
            grad_norm = (
                float(grad_norm) if grad_norm is not None else None
            )
            if not np.isfinite(loss) or (
                grad_norm is not None and not np.isfinite(grad_norm)
            ):
                self._state_poisoned = True
                # Ship the anomaly NOW: the skip path waits for the
                # master's restart remediation, which only fires on a
                # reported anomaly — a save-time-only detection (report
                # and checkpoint cadences misaligned) must not silently
                # block every future checkpoint with no restart coming.
                found = self.numeric_monitor.check(
                    self.step, loss, grad_norm
                )
                if self.client is not None:
                    self.client.report_step(
                        self.step, tokens=0, loss=loss,
                        anomalies=tuple(a.encode() for a in found),
                    )
                return False
        return True

    def close(self, wait: float = 120.0):
        if self._ckpt is not None:
            self._ckpt.wait(timeout=wait)
            self._ckpt.close()
