"""The process that holds the chip(s): builds the program's trainer from the
benchmark's data files, drives it, and records what happened.

Used in-process by the ``train_steady`` scenario and as the trainer the
agent starts (``python -m benchmark.worker``) by ``save_kill_resume``.
From the program it takes the system under test (``ElasticTrainer``,
``ElasticDataLoader``, ``DevicePrefetcher``, the checkpoint engine behind
``save_checkpoint``) and its spans and counters; the clock, the window,
the seed, the reference and the trace reduction are the benchmark's own.

Host phases.  The host is always in exactly one of ``data_wait`` (inside
``next()`` of the prefetcher), ``train_step`` (from the batch's hand-over
to the read of the step's loss), ``report`` (the trainer's report and
whatever follows the hook) and ``checkpoint`` (inside ``save_checkpoint``).
Each phase is a ``TraceAnnotation`` named ``bench:<phase>``, so that idle
gaps of the device can be named from the trace itself.
"""

from __future__ import annotations

import argparse
import logging
import math
import sys
import time
from typing import Any, Dict, List, Optional

from benchmark import build, readings, traffic as traffic_lib

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Done(Exception):
    """Raised from the step hook to leave ``fit`` at a step's end."""


class HostPhases:
    """The host's current phase as an open ``bench:<phase>`` annotation."""

    def __init__(self):
        self._ann = None

    def switch(self, name: Optional[str]):
        import jax

        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if name is not None:
            self._ann = jax.profiler.TraceAnnotation(f"bench:{name}")
            self._ann.__enter__()


class TimedBatches:
    """The prefetcher's batches, with the wait for each one timed."""

    def __init__(self, source, phases: HostPhases):
        self.source = source
        self.phases = phases
        self.waits: List[float] = []

    def __iter__(self):
        it = iter(self.source)
        try:
            while True:
                self.phases.switch("data_wait")
                t0 = time.monotonic()
                try:
                    batch = next(it)
                except StopIteration:
                    return
                self.waits.append(time.monotonic() - t0)
                self.phases.switch("train_step")
                yield batch
        finally:
            if hasattr(it, "close"):
                it.close()


class _LogTap(logging.Handler):
    """Counts the program's own log lines the benchmark reads."""

    def __init__(self):
        super().__init__()
        self.skipped_saves: List[str] = []

    def emit(self, record):
        msg = record.getMessage()
        if "skip memory save" in msg or "skipping checkpoint" in msg:
            self.skipped_saves.append(msg)


def device_info(devices) -> Dict[str, Any]:
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    ]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(max(peaks)),
    }


def require_devices(chips: int, rehearsal: bool):
    """The devices the cell runs on, or exit without a result line."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not rehearsal:
        print(
            f"benchmark: a TPU is required and jax reports platform "
            f"{devices[0].platform!r}; nothing measured", file=sys.stderr,
        )
        raise SystemExit(3)
    if len(devices) < chips:
        print(
            f"benchmark: the cell asks for {chips} chip(s) and jax reports "
            f"{len(devices)}; nothing measured", file=sys.stderr,
        )
        raise SystemExit(3)
    return devices[:chips]


class Worker:
    def __init__(self, config: Dict, traffic: Dict, chips: int, seed: int,
                 seconds: float, trace: bool,
                 rehearsal: bool = False, checkpoint_dir: str = "",
                 trace_dir: str = ""):
        self.config, self.traffic = config, traffic
        self.chips, self.seed = chips, int(seed)
        self.seconds, self.trace = float(seconds), bool(trace)
        self.rehearsal = rehearsal
        self.checkpoint_dir, self.trace_dir = checkpoint_dir, trace_dir
        self.model = build.model_group(config)
        self.seq_len = build.seq_len(config, traffic)
        self.global_batch = build.global_batch(config, traffic, chips)
        self.tokens_per_step = self.global_batch * self.seq_len
        self.phases = HostPhases()
        self.compile_ends: List[float] = []
        self.step_ends: List[float] = []
        self.step_ids: List[int] = []
        self.losses: Dict[int, float] = {}
        self.saves: List[Dict[str, Any]] = []
        self.log_tap = _LogTap()
        self.traced = False
        self.trainer = None
        self.compile_event = None
        self.startup_spans: List[Any] = []
        self.reference_check_s: Optional[float] = None
        self.batches: Optional[TimedBatches] = None

    # -- set-up ---------------------------------------------------------------

    def build_trainer(self):
        import jax

        from dlrover_tpu.common.log import default_logger
        from dlrover_tpu.runtime.mesh import ParallelConfig
        from dlrover_tpu.trainer.elastic_trainer import (
            ElasticTrainer,
            TrainerConfig,
        )

        self.devices = require_devices(self.chips, self.rehearsal)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        default_logger.addHandler(self.log_tap)
        knobs = dict(self.config.get("trainer", {}))
        knobs.update(self.traffic.get("trainer", {}))
        trainer_config = TrainerConfig(
            global_batch_size=self.global_batch,
            seq_len=self.seq_len,
            checkpoint_dir=self.checkpoint_dir,
            ckpt_every=int(self.traffic.get("ckpt_every", 10 ** 9)),
            # ``report_every`` stays the program's own: the step hook
            # fires for every step whatever the report cadence is.
            warmup_compile=True,
            # The benchmark wraps the program's DevicePrefetcher itself (to
            # time the wait for each batch); placed batches pass through
            # the trainer's own shard_batch untouched.
            prefetch_to_device=0,
            **knobs,
        )
        self.trainer = ElasticTrainer(
            build.transformer_config(self.model, self.seq_len),
            trainer_config,
            parallel=ParallelConfig(**self.traffic.get("mesh", {"data": -1})),
        )
        # The trainer's own ``compile`` event (the step program compiled,
        # or read back from the compile cache) and its start-up spans.
        # Read here, before the first report ships the ring to the master.
        from dlrover_tpu.common import telemetry

        events = telemetry.recorder().peek()
        self.compile_event = next(
            (e for e in reversed(events) if e[0] == "compile"), None,
        )
        self.startup_spans = [
            e for e in events if e[0].startswith("startup.")
        ]
        return self.trainer

    def _on_event(self, name, secs, **_):
        if name == COMPILE_EVENT:
            self.compile_ends.append(time.monotonic())

    def seed_state(self):
        """Weights from ``--seed``, made on the device in one jitted call
        (the program's own ``init``), replacing the trainer's fixed key."""
        import jax

        trainer = self.trainer
        trainer.state = None  # free the fixed-key state first
        trainer.state = trainer.train.init(jax.random.PRNGKey(self.seed))

    def sample_fn(self):
        vocab = int(self.config.get("token_vocab") or self.model["vocab_size"])
        return traffic_lib.sample_fn(vocab, self.seq_len, self.seed)

    def batch_stream(self):
        from dlrover_tpu.data.loader import DevicePrefetcher, ElasticDataLoader
        from dlrover_tpu.trainer import train_lib

        loader = ElasticDataLoader(
            self.sample_fn(), batch_size=self.global_batch
        )
        prefetcher = DevicePrefetcher(
            loader,
            lambda batch: train_lib.shard_batch(batch, self.trainer.train),
            depth=int(self.traffic.get("prefetch_to_device", 2)),
        )
        self.batches = TimedBatches(prefetcher, self.phases)
        return self.batches

    # -- correctness against the plain reference ------------------------------

    def timed_reference_check(self) -> Dict[str, Any]:
        """``check_reference``, timed around the whole call: the rows'
        making and the check's own compilations are the benchmark's too,
        and ``setup_s`` counts none of it (``readings.setup_parts``)."""
        t0 = time.monotonic()
        reference = self.check_reference()
        self.reference_check_s = time.monotonic() - t0
        return reference

    def check_reference(self) -> Dict[str, Any]:
        """Per-token loss of the program's forward (its kernels, its dtype,
        its sharding) against ``benchmark/reference.py`` on the first
        sequences of the first batch."""
        import flax.linen as nn
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmark import reference
        from dlrover_tpu.trainer import train_lib

        count = int(self.traffic.get("reference_sequences", 2))
        rows = traffic_lib.first_sequences(self.sample_fn(), count)
        trainer = self.trainer
        inputs, targets = jnp.asarray(rows["inputs"]), jnp.asarray(
            rows["targets"]
        )

        @jax.jit
        def program_nll(params, inputs, targets):
            logits, _ = trainer.model.apply({"params": params}, inputs)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]

        t0 = time.monotonic()
        with train_lib.use_mesh(trainer.mesh), nn.logical_axis_rules(
            trainer._rules
        ):
            got = np.asarray(program_nll(trainer.state.params, inputs, targets))
        params = jax.device_get(trainer.state.params) if (
            self.chips > 1
        ) else trainer.state.params
        want = np.asarray(
            reference.token_nll(self.model, params, inputs, targets)
        )
        tol = self.config["reference_tolerance"]
        token_err = float(np.abs(got - want).mean())
        mean_err = float(abs(got.mean() - want.mean()))
        return {
            "sequences": count,
            "program_loss": float(got.mean()),
            "reference_loss": float(want.mean()),
            "mean_abs_token_error": token_err,
            "mean_loss_error": mean_err,
            "finite": bool(np.isfinite(got).all() and np.isfinite(want).all()),
            "ok": bool(
                np.isfinite(got).all() and np.isfinite(want).all()
                and token_err <= tol["mean_abs_token_nll"]
                and mean_err <= tol["mean_nll"]
            ),
            "seconds": time.monotonic() - t0,
        }

    # -- the loop --------------------------------------------------------------

    def note_step(self, step: int, metrics) -> bool:
        """The hook of every step.  The host's read of a step's loss closes
        a reading; where the trainer defers its reads (``metrics_lag``) it
        hands over a block of steps at once, and only the newest of them,
        the one the trainer has just dispatched, closes the reading.
        Returns whether a reading closed."""
        self.losses[step] = float(metrics["loss"])
        if step < self.trainer.step:
            return False
        self.step_ends.append(time.monotonic())
        self.step_ids.append(step)
        self.phases.switch("report")
        return True

    def warm_index(self) -> Optional[int]:
        return readings.window_open_index(
            self.step_ends, self.step_ids, self.compile_ends,
            int(self.traffic.get("warm_steps", 3)),
        )

    def start_trace(self):
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)

    def stop_trace(self):
        import jax

        # Close the open phase so that its annotation lands in the trace.
        self.phases.switch("report")
        jax.profiler.stop_trace()
        self.traced = True

    def extract_trace(self) -> Dict[str, Any]:
        """The trace in the benchmark's plain structure, each instruction
        with the framework path the compiled step program gives it."""
        from benchmark import trace_reduce

        compiled = getattr(self.trainer.train, "_aot_step", None)
        scopes = trace_reduce.scopes_from_hlo(
            compiled.as_text() if compiled is not None else ""
        )
        return trace_reduce.extract(
            trace_reduce.find_xplane(self.trace_dir), scopes
        )

    def fit(self, hook, max_steps: int = 10 ** 9):
        trainer = self.trainer
        try:
            trainer.fit(self.batch_stream(), max_steps=max_steps, on_step=hook)
        except Done:
            pass
        finally:
            self.phases.switch(None)

    def evidence(self) -> Dict[str, Any]:
        """What the readers read, as plain JSON-able data."""
        from dlrover_tpu.utils.profiler import pipeline_counters

        return {
            "step_ids": self.step_ids,
            "step_ends": self.step_ends,
            "losses": [self.losses[k] for k in sorted(self.losses)],
            "compile_ends": self.compile_ends,
            "data_waits": self.batches.waits if self.batches else [],
            "saves": self.saves,
            "skipped_saves": self.log_tap.skipped_saves,
            "pipeline_counters": pipeline_counters().summary(),
            "compile_s": self.compile_event[3] if self.compile_event else None,
            "compile": self.compile_event[4] if self.compile_event else None,
            "startup_spans": self.startup_spans,
            "reference_check_s": self.reference_check_s,
            "device": device_info(self.devices),
            "tokens_per_step": self.tokens_per_step,
            "seq_len": self.seq_len,
            "sequences_per_chip": self.global_batch // self.chips,
            "chips": self.chips,
        }


def all_finite(values) -> bool:
    return all(math.isfinite(v) for v in values)


# -- the trainer the agent starts (save_kill_resume) ---------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config-file", required=True)
    ap.add_argument("--traffic-file", required=True)
    ap.add_argument("--scenario", required=True)
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--run-dir", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    import importlib

    scenario = importlib.import_module(f"benchmark.scenarios.{args.scenario}")
    return scenario.trainer_main(args)


if __name__ == "__main__":
    sys.exit(main())
