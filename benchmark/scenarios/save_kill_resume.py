"""Flash Checkpoint under way, then a kill and a resume in place.

This process (the harness) never touches JAX: it starts the program's own
launcher (``python -m dlrover_tpu.run --standalone``: master, agent), whose
agent starts the trainer, ``python -m benchmark.worker``, which holds the
chip.  Trainer and harness talk through one JSON-lines file in the run's
directory; clocks are ``time.monotonic()``, which Linux shares between
processes.

Phases of a run:

* set-up: the job starts, the step program compiles (or is read from the
  compile cache), the weights come from the seed, the reference check runs,
  and the trainer trains through its FIRST save, which creates the
  shared-memory arena and is no reading, waits until the agent has
  persisted it (16 s, longer than the steps to the next save, which the
  engine would otherwise skip as "shm busy"), and trains on to the step
  before the second save.  ``setup_s`` ends there.  It starts where this
  process does and leaves out three stretches (``readings.setup_parts``):
  the trainer's own process start to its mesh built (its
  ``startup.runtime`` and ``startup.mesh`` spans), the reference check,
  and that wait for the first persist, which this benchmark puts there
  and no job makes; the trainer's record hands all three over.  The
  launcher's, master's and agent's start stay in it, and the first save,
  blocked while it makes the cold arena: the program owns them.
* the window opens at that step's end, as the second save begins.  Every
  save inside it is a reading: the seconds the training loop is blocked in
  ``save_checkpoint``, on the host clock, around the call.
  ``save_stall_s`` is their mean: all the blocked seconds of the window
  over its saves.  The window closes at the first step's end at or after
  ``--seconds`` that lies ``kill_after_steps`` steps after a save, so it is
  a whole number of steps and every run loses the same work.
* a traced run then traces one more save cycle, up to the same point
  after the next save.
* the kill: the harness SIGKILLs the trainer when it sees the record of
  that step.  ``resume_s`` runs from that instant to the end of the first
  step of the trainer the agent starts in its place; it is part of neither
  ``setup_s`` nor the window.  It is printed on an earlier line only: its
  runs spread too widely for a bound (PERF.md, Open questions).
"""

from __future__ import annotations

import json
import os
import re
import shutil
import signal
import statistics
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from benchmark import build, layers, readings

RECORDS = "records.jsonl"
LAUNCHER_LOG = "launcher.log"
PERSIST_LINE = re.compile(r"persisted step (\d+) in ([\d.]+)s")


# -- the trainer (child of the agent; holds the chip) --------------------------


def _emit(path: str, record: Dict[str, Any]):
    with open(path, "a") as f:
        f.write(json.dumps(record) + "\n")
        f.flush()
        os.fsync(f.fileno())


def trainer_main(args) -> int:
    from benchmark import worker as worker_lib

    from dlrover_tpu.runtime import env as renv

    renv.initialize()
    config = build.load_json(args.config_file)
    traffic = build.load_json(args.traffic_file)
    if args.rehearsal:
        traffic.update(traffic.get("rehearsal", {}))
    records = os.path.join(args.run_dir, RECORDS)
    w = worker_lib.Worker(
        config, traffic, args.chips, args.seed, args.seconds,
        bool(args.trace), rehearsal=args.rehearsal,
        checkpoint_dir=os.path.join(args.run_dir, "ckpt"),
        trace_dir=os.path.join(args.run_dir, "trace"),
    )
    if renv.restart_count() > 0:
        return _resumed_trainer(w, records)
    return _first_trainer(
        w, records, os.path.join(args.run_dir, LAUNCHER_LOG), traffic
    )


def _wrap_save(w, digest_of):
    """Time ``save_checkpoint`` around the call, on the host clock, and
    digest the state that was saved right after it (outside the timing)."""
    from dlrover_tpu.common import telemetry

    trainer = w.trainer
    inner = trainer.save_checkpoint

    def save_checkpoint():
        if trainer._ckpt is None or trainer.step == 0:
            return inner()
        skipped_before = len(w.log_tap.skipped_saves)
        w.phases.switch("checkpoint")
        t0 = time.monotonic()
        inner()
        t1 = time.monotonic()
        w.phases.switch("report")
        spans = [
            e for e in telemetry.recorder().peek()
            if e[0] == "checkpoint" and e[4].get("step") == trainer.step
        ]
        w.saves.append({
            "step": trainer.step, "t0": t0, "t1": t1, "stall_s": t1 - t0,
            "span_s": spans[-1][3] if spans else None,
            "skipped": len(w.log_tap.skipped_saves) > skipped_before,
            "digest": digest_of(trainer.state),
        })

    trainer.save_checkpoint = save_checkpoint


def _digest_fn(w):
    from dlrover_tpu.trainer import state_digest

    fn = state_digest.build_digest_fn(w.trainer.train)

    def digest_of(state) -> str:
        from dlrover_tpu.trainer import train_lib

        with train_lib.use_mesh(w.trainer.mesh):
            return state_digest.format_digest(fn(state))

    return digest_of


def _wait_for_persist(log_path: str, step: int, limit_s: float = 120.0):
    """Block until the agent's saver has logged that ``step`` is on disk."""
    deadline = time.monotonic() + limit_s
    while time.monotonic() < deadline:
        with open(log_path, errors="replace") as f:
            if any(
                m and int(m.group(1)) == step
                for m in map(PERSIST_LINE.search, f)
            ):
                return
        time.sleep(0.1)
    raise SystemExit(
        f"benchmark: the agent did not persist step {step} in {limit_s} s"
    )


def _first_trainer(w, records: str, log_path: str,
                   traffic: Dict[str, Any]) -> int:
    from benchmark import worker as worker_lib

    every = int(traffic["ckpt_every"])
    kill_after = int(traffic["kill_after_steps"])
    w.build_trainer()
    w.seed_state()
    reference = w.timed_reference_check()
    _wrap_save(w, _digest_fn(w))
    state = {"open": None, "close": None, "kill": None}

    def at_kill_phase(step) -> bool:
        return bool(w.saves) and step - w.saves[-1]["step"] == kill_after

    def kill_point(i, step):
        state["kill"] = i
        _emit(records, {
            "kind": "kill_point", "pid": os.getpid(), "step": step,
            "t": w.step_ends[i], "evidence": _first_evidence(
                w, state, reference
            ),
        })

    def hook(step, metrics):
        w.note_step(step, metrics)
        i = len(w.step_ends) - 1
        if state["kill"] is not None:
            return  # training on until the harness kills this process
        if state["open"] is None:
            if step == every + 1:
                t_wait = time.monotonic()
                _wait_for_persist(log_path, every)
                state["persist_wait_s"] = time.monotonic() - t_wait
            if (step >= 2 * every and step % every == 0 and w.saves
                    and w.warm_index() is not None):
                state["open"] = i
            return
        if state["close"] is None:
            if (w.step_ends[i] - w.step_ends[state["open"]] >= w.seconds
                    and at_kill_phase(step)):
                state["close"] = i
                state["saves_in_window"] = len(w.saves)
                if w.trace:
                    w.start_trace()
                else:
                    kill_point(i, step)
            return
        if at_kill_phase(step) and len(w.saves) > state["saves_in_window"]:
            w.stop_trace()
            kill_point(i, step)

    w.fit(hook)
    return 0


def _first_evidence(w, state, reference) -> Dict[str, Any]:
    evidence = w.evidence()
    evidence["reference"] = reference
    # The first save makes the shared-memory arena: the seconds the loop
    # was blocked in it stay IN ``setup_s`` (the program owns them) and are
    # the part of it that wanders from run to run (PERF.md section 2).  The
    # wait for its persist is the harness's and goes OUT; a window cannot
    # open before that wait, so a record without it is a fault.
    evidence["first_save_cold_s"] = w.saves[0]["stall_s"]
    evidence["persist_wait_s"] = state["persist_wait_s"]
    evidence["window"] = {
        "open": state["open"], "close": state["close"],
        "saves": state.get("saves_in_window"),
    }
    if w.traced:
        from benchmark import trace_reduce

        evidence["trace_reduced"] = trace_reduce.reduce(
            w.extract_trace(), w.traffic.get("step_module", "")
        )
    return evidence


def _resumed_trainer(w, records: str) -> int:
    """The trainer the agent starts in the dead one's place: restore,
    first step, report, leave."""
    from benchmark import worker as worker_lib

    from dlrover_tpu.common import telemetry

    t_start = time.monotonic()
    w.build_trainer()
    trainer = w.trainer
    restore = [e for e in telemetry.recorder().peek() if e[0] == "restore"]
    restored_step = trainer.step
    digest = _digest_fn(w)(trainer.state)

    def hook(step, metrics):
        w.note_step(step, metrics)
        loss = w.losses[step]
        restart_wall = None
        try:
            timeline = trainer.client.get_timeline()
            for node_events in timeline.values():
                for e in node_events:
                    if e[0] == "restart":
                        restart_wall = e[2]
        except Exception as e:  # noqa: BLE001 - the metric is then left out
            print(f"benchmark: no timeline from the master: {e}", flush=True)
        _emit(records, {
            "kind": "resumed_first_step", "t": w.step_ends[-1],
            "step": step, "loss": loss, "restored_step": restored_step,
            "digest": digest, "trainer_start_t": t_start,
            "restore_s": restore[-1][3] if restore else None,
            "compile": w.compile_event[4] if w.compile_event else None,
            "recompile_s": w.compile_event[3] if w.compile_event else None,
            "restart_event_wall": restart_wall,
            "device": worker_lib.device_info(w.devices),
        })
        raise worker_lib.Done

    w.fit(hook)
    trainer.close()
    return 0


# -- the harness ----------------------------------------------------------------


def _read_records(path: str) -> List[Dict[str, Any]]:
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            if line.endswith("\n"):
                out.append(json.loads(line))
    return out


def _wait_for(path: str, kind: str, proc, deadline: float) -> Optional[Dict]:
    while time.monotonic() < deadline:
        for record in _read_records(path):
            if record["kind"] == kind:
                return record
        if proc.poll() is not None:
            return None
        time.sleep(0.02)
    return None


def run(ctx) -> Dict[str, Any]:
    every = int(ctx.traffic["ckpt_every"])
    sockets = tempfile.mkdtemp(prefix="bk")
    env = dict(os.environ)
    repo = os.path.dirname(build.ROOT)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (repo, env.get("PYTHONPATH", "")) if p
    )
    env["PYTHONUNBUFFERED"] = "1"
    env["DLROVER_TPU_SOCKET_DIR"] = sockets
    # Its own arena: a stale one under a shared tag would be restored at
    # step 0 of this run.
    env["DLROVER_TPU_JOB"] = f"bench{os.getpid()}s{ctx.seed}"
    records = os.path.join(ctx.run_dir, RECORDS)
    log_path = os.path.join(ctx.run_dir, LAUNCHER_LOG)
    cmd = [
        sys.executable, "-m", "dlrover_tpu.run", "--standalone",
        "--checkpoint-dir", os.path.join(ctx.run_dir, "ckpt"),
        "--monitor-interval", str(ctx.traffic.get("monitor_interval", 1)),
        "--",
        sys.executable, "-m", "benchmark.worker",
        "--config-file", ctx.config_file,
        "--traffic-file", ctx.traffic_file,
        "--scenario", ctx.traffic["scenario"],
        "--chips", str(ctx.chips), "--seed", str(ctx.seed),
        "--seconds", str(ctx.seconds), "--trace", str(int(ctx.trace)),
        "--run-dir", ctx.run_dir,
    ] + (["--rehearsal"] if ctx.rehearsal else [])
    limit = float(ctx.traffic.get("phase_limit_s", 900))
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            cmd, env=env, cwd=repo, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        try:
            kill = _wait_for(
                records, "kill_point", proc, time.monotonic() + limit
            )
            resumed = None
            if kill is not None:
                os.kill(kill["pid"], signal.SIGKILL)
                t_kill, wall_kill = time.monotonic(), time.time()
                resumed = _wait_for(
                    records, "resumed_first_step", proc,
                    time.monotonic() + limit,
                )
            try:
                proc.wait(timeout=180 if resumed else 5)
            except subprocess.TimeoutExpired:
                pass
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
            shutil.rmtree(sockets, ignore_errors=True)
            _unlink_arenas(env["DLROVER_TPU_JOB"])
    with open(log_path, errors="replace") as f:
        log_lines = f.read().splitlines()
    if kill is None or resumed is None:
        for line in log_lines[-60:]:
            print(f"launcher | {line[:300]}", file=sys.stderr)
        raise SystemExit(
            "benchmark: the job did not reach its "
            + ("kill point" if kill is None else "first resumed step")
        )
    return _assemble(
        ctx, kill, resumed, t_kill, wall_kill, log_lines, every
    )


def _unlink_arenas(job: str):
    """The agent unlinks its arena when the job succeeds; make sure."""
    for name in os.listdir("/dev/shm") if os.path.isdir("/dev/shm") else ():
        if job in name:
            try:
                os.unlink(os.path.join("/dev/shm", name))
            except OSError:
                pass


def _assemble(ctx, kill, resumed, t_kill, wall_kill, log_lines, every):
    ev = kill["evidence"]
    ends, ids = ev["step_ends"], ev["step_ids"]
    window = ev["window"]
    t_open, t_close = ends[window["open"]], ends[window["close"]]
    saves = ev["saves"]
    in_window = [s for s in saves if t_open <= s["t0"] and s["t1"] <= t_close]
    # Saves the cadence asked for inside the window: every ``every`` steps
    # from the step that opened it.  One that did not happen, or that the
    # engine skipped ("shm busy"), is a failed save.
    due = [
        s for s in range(ids[window["open"]], ids[window["close"]] + 1)
        if s % every == 0
    ]
    done = {s["step"] for s in in_window if not s["skipped"]}
    failed_saves = [s for s in due if s not in done]
    stalls = [s["stall_s"] for s in in_window if not s["skipped"]]
    last_save = saves[-1]
    compiles = sum(1 for c in ev["compile_ends"] if t_open < c <= t_close)
    resume_ok = (
        resumed["restored_step"] == last_save["step"]
        and resumed["digest"] == last_save["digest"]
        and resumed["loss"] != ev["losses"][0]
        and resumed["step"] == last_save["step"] + 1
    )
    import math

    correct = bool(
        ev["reference"]["ok"] and compiles == 0 and not failed_saves
        and all(math.isfinite(x) for x in ev["losses"])
        and math.isfinite(resumed["loss"]) and resume_ok and stalls
    )
    persists = [
        float(m.group(2)) for line in log_lines
        for m in [PERSIST_LINE.search(line)] if m
    ]
    setup = readings.setup_parts(
        ctx.t0, t_open, ev["startup_spans"], ev["reference_check_s"],
        ev["persist_wait_s"],
    )
    ctx.say({
        "setup": setup, "first_save_cold_s": ev["first_save_cold_s"],
        "compile": ev["compile"],
    })
    resume_s = resumed["t"] - t_kill
    respawn = None
    if resumed.get("restart_event_wall") is not None:
        respawn = resumed["restart_event_wall"] - wall_kill
    ctx.say({
        "reference": ev["reference"],
        "saves": saves, "persist_s": persists,
        "skipped_saves": ev["skipped_saves"],
        "step_readings_s": [b - a for a, b in zip(ends, ends[1:])],
        "step_ids": ids, "losses": ev["losses"],
        "window_steps": [ids[window["open"]], ids[window["close"]]],
        "window_s": t_close - t_open,
        "compiles_in_window": compiles,
        "kill": {"step": kill["step"], "last_save_step": last_save["step"]},
        "resumed": resumed, "resume_s": resume_s, "respawn_s": respawn,
        "seconds_kill_to_trainer_start": resumed["trainer_start_t"] - t_kill,
    })
    evidence = dict(
        ev, model=build.model_group(ctx.config), persist_s=persists,
        window_save_stalls=stalls,
        # Of the trainer's spans only ``startup_spans`` cross the records
        # file, and this process's recorder holds none: a metric of any
        # other span finds nothing here and is left out of the line.
        program_spans=[],
        process_to_window_s=setup["process_to_window_s"],
    )
    device = dict(ev["device"])
    device["memory_peak_bytes"] = max(
        device["memory_peak_bytes"], resumed["device"]["memory_peak_bytes"]
    )
    breakdown = None
    reduced = ev.get("trace_reduced")
    if reduced:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
    return {
        "correct": correct,
        "attempted": len(due) + 1,
        "failed": len(failed_saves) + (0 if resume_ok else 1),
        "end_to_end": {
            # All the seconds the loop was blocked in the window's saves
            # over their number: one slow save shows in it.
            "save_stall_s": statistics.fmean(stalls) if stalls else None,
            "setup_s": setup["setup_s"],
        },
        "per_layer": layers.compute(ctx.manifest, ctx.cell, evidence)
        if ctx.trace else {},
        "device": device,
        "breakdown": breakdown,
    }
