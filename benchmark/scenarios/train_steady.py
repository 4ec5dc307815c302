"""Steady training steps: the program's trainer, loader and prefetcher in
this process, no saves.

Before the window: build the trainer (the step program compiles, or comes
from the compile cache), make the weights from the seed, check the
program's forward against the plain reference, run warm steps.  The window
opens at the end of a step, once ``warm_steps`` whole steps have followed
the last compilation, and closes at the first step's end at or after
``--seconds``.  A traced run then traces ``trace_readings`` further readings,
so that the measured window is the same with and without the profiler.

``setup_s`` starts at the instant the trainer's mesh is built (the close of
the program's ``startup.mesh`` span) and ends at the window's first
instant, less the seconds of the benchmark's own reference check
(``readings.setup_parts``).  What is taken out, process start to the mesh
and the check, is on a line of its own in every run (``"setup"``) and among
the per-layer metrics of a traced one.
"""

from __future__ import annotations

import os
from typing import Any, Dict

from benchmark import build, layers, readings, worker as worker_lib


def run(ctx, worker_class=worker_lib.Worker) -> Dict[str, Any]:
    w = worker_class(
        ctx.config, ctx.traffic, ctx.chips, ctx.seed, ctx.seconds,
        ctx.trace, rehearsal=ctx.rehearsal,
        trace_dir=os.path.join(ctx.run_dir, "trace"),
    )
    w.build_trainer()
    w.seed_state()
    reference = w.timed_reference_check()
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
    setup = readings.setup_parts(
        ctx.t0, w.step_ends[state["open"]], w.startup_spans,
        w.reference_check_s,
    )
    ctx.say({"setup": setup})
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
        process_to_window_s=setup["process_to_window_s"],
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
            "setup_s": setup["setup_s"],
        },
        "per_layer": layers.compute(ctx.manifest, ctx.cell, evidence)
        if w.trace else {},
        "device": device,
        "breakdown": breakdown,
    }
