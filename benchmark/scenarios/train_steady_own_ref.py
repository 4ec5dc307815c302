"""``train_steady`` with the configuration's own plain reference.

The harness's ``Worker.check_reference`` calls ``benchmark.reference`` by
name, which knows GPT-2 and Mixtral.  A configuration whose layer that
reference does not describe names its own module under
``benchmark/references/`` (``reference_module`` in its file); this
scenario's worker calls that one.  Everything else, set-up, window,
readings and ``correct``, is ``train_steady``'s, line for line; the
scenario and its traffic file fold back into ``train_steady`` once
``worker.check_reference`` reads ``reference_module`` itself (PERF.md §7).
"""

from __future__ import annotations

import importlib
import os
import time
from typing import Any, Dict

from benchmark import build, layers, readings, traffic as traffic_lib
from benchmark import worker as worker_lib


class Worker(worker_lib.Worker):
    def check_reference(self) -> Dict[str, Any]:
        """Per-token loss of the program's forward (its kernels, its dtype,
        its sharding) against the configuration's ``reference_module`` on
        the first sequences of the first batch."""
        import flax.linen as nn
        import jax
        import jax.numpy as jnp
        import numpy as np

        from dlrover_tpu.trainer import train_lib

        reference = importlib.import_module(
            f"benchmark.references.{self.config['reference_module']}"
        )
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
        finite = bool(np.isfinite(got).all() and np.isfinite(want).all())
        return {
            "sequences": count,
            "reference_module": self.config["reference_module"],
            "program_loss": float(got.mean()),
            "reference_loss": float(want.mean()),
            "mean_abs_token_error": token_err,
            "mean_loss_error": mean_err,
            "finite": finite,
            "ok": bool(
                finite and token_err <= tol["mean_abs_token_nll"]
                and mean_err <= tol["mean_nll"]
            ),
            "seconds": time.monotonic() - t0,
        }


def run(ctx) -> Dict[str, Any]:
    w = Worker(
        ctx.config, ctx.traffic, ctx.chips, ctx.seed, ctx.seconds,
        ctx.trace, rehearsal=ctx.rehearsal,
        trace_dir=os.path.join(ctx.run_dir, "trace"),
    )
    w.build_trainer()
    w.seed_state()
    reference = w.check_reference()
    ctx.say({"reference": reference})
    trace_readings = int(ctx.traffic.get("trace_readings", 2))
    state = {"open": None, "close": None, "wait_from": 0, "trace_end": None}

    def hook(step, metrics):
        if not w.note_step(step, metrics):
            return
        i = len(w.step_ends) - 1
        if state["open"] is None:
            if w.warm_index() is not None:
                state["open"] = i
                state["wait_from"] = len(w.batches.waits)
            return
        if state["close"] is None:
            if readings.window_close_index(
                w.step_ends, state["open"], w.seconds
            ) is None:
                return
            state["close"] = i
            state["wait_to"] = len(w.batches.waits)
            if not w.trace:
                raise worker_lib.Done
            w.start_trace()
            state["trace_end"] = i + trace_readings
            return
        if i >= state["trace_end"]:
            w.stop_trace()
            raise worker_lib.Done

    w.fit(hook)
    summary = readings.summarize(
        w.step_ends, w.step_ids, w.compile_ends, w.losses, state["open"],
        state["close"], w.tokens_per_step, w.chips,
    )
    setup_s = w.step_ends[state["open"]] - ctx.t0
    evidence = w.evidence()
    ctx.say({
        "readings_s": summary["readings"],
        "steps_per_reading": summary["steps_per_reading"],
        "losses": [w.losses[k] for k in sorted(w.losses)],
        "window_steps": [w.step_ids[state["open"]], w.step_ids[state["close"]]],
        "tokens_per_s_chip_median_step":
            summary["tokens_per_s_chip_median_step"],
        "compile": evidence["compile"],
        "compile_events": len(w.compile_ends),
        "pipeline_counters": evidence["pipeline_counters"],
    })
    evidence.update(
        summary=summary,
        window_data_waits=w.batches.waits[
            state["wait_from"]: state["wait_to"]
        ],
        model=w.model,
        # A rehearsal's device has no published peak: the readers that
        # need one then find nothing to read.
        peak=None if ctx.rehearsal else build.peak_for(
            w.devices[0].device_kind
        ),
        step_module=ctx.traffic.get("step_module", ""),
    )
    device = evidence["device"]
    breakdown = None
    if w.trace:
        from benchmark import trace_reduce

        evidence["trace"] = w.extract_trace()
        reduced = trace_reduce.reduce(
            evidence["trace"], evidence["step_module"]
        )
        evidence["trace_reduced"] = reduced
        device = dict(
            device, busy_s=reduced["busy_s"], window_s=reduced["window_s"]
        )
        breakdown = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
        ctx.say({"trace": {
            k: v for k, v in reduced.items()
            if k not in ("device_ops", "idle_gaps")
        }})
    correct = bool(
        reference["ok"] and summary["ok"]
        and worker_lib.all_finite(w.losses.values())
        # The compiled step's own first loss (all sequences of the batch)
        # must lie where the reference's loss on the first sequences does:
        # both are means over thousands of tokens of one distribution.
        and abs(w.losses[1] - reference["reference_loss"])
        <= ctx.config["reference_tolerance"]["first_step_loss"]
    )
    return {
        "correct": correct,
        "attempted": summary["steps"],
        "failed": summary["failed"],
        "end_to_end": {
            "tokens_per_s_chip": summary["tokens_per_s_chip"],
            "setup_s": setup_s,
        },
        "per_layer": layers.compute(ctx.manifest, ctx.cell, evidence)
        if w.trace else {},
        "device": device,
        "breakdown": breakdown,
    }
