"""Goodput-under-chaos bench: scripted kill injection + goodput ledger.

The artifact behind BASELINE.json's north-star metric (goodput >= 90% under
injected preemption; reference method
``docs/tech_report/fault_tolerance_exps.md:145-210``): run elastic training
under the real master/agent stack, SIGKILL the trainer (process failure ->
agent restart-in-place) and the whole agent group (preemption -> relaunch)
on a schedule, and report the master SpeedMonitor's goodput ledger.

    python tools/goodput_bench.py --steps 400 --kill-every 60 --out GOODPUT.json
    python tools/goodput_bench.py --resize-drill --steps 120 --out DRILL.json
    python tools/goodput_bench.py --resize-drill --live-relayout --steps 80 \\
        --step-sleep 0.3 --drill-preempt-hit 10 --out RESIZE_LIVE.json
    python tools/goodput_bench.py --sdc-drill --steps 60 --step-sleep 0.2 \\
        --sdc-check-every 8 --out SDC.json

Its trainers run on the CPU (JAX_PLATFORMS=cpu in their environment): the
drills start several trainers on one host, a chip belongs to one process,
and what they exercise is the control plane.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _children(pid: int):
    try:
        with open(f"/proc/{pid}/task/{pid}/children") as f:
            return [int(p) for p in f.read().split()]
    except OSError:
        return []


def _bench_env(args) -> dict:
    """Child environment shared by the bench and the resize drill."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "DLROVER_TPU_SOCKET_DIR": os.path.join(args.workdir, "socks"),
        "DLROVER_TPU_JOB": f"goodput{os.getpid()}",
        "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    })
    # NO persistent compile cache here: this bench pins JAX_PLATFORMS=cpu,
    # and a process that hits CPU cache entries another process wrote gets
    # a corrupt deserialized executable (SIGSEGV/SIGABRT, or silently
    # garbage losses) — exactly what every elastic restart would do.  The
    # restart-speed lever stays a TPU-only story; CPU restarts just
    # re-trace.  jax reads its own env knob directly, bypassing the
    # runtime.compile_cache CPU gate, so scrub it too.
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)
    return env


def run_resize_drill(args) -> int:
    """Deterministic elastic-resize drill (2 hosts -> 1).

    Node 1's fault plan scripts a ``preempt.notice`` error at a fixed hit,
    so its ResourceMonitor "receives" the preemption warning at the same
    point every run; the agent drains (shm flush, master notice, trainer
    stop) and exits.  Node 0 re-rendezvouses alone and resumes from the
    cross-world reshard of the 2-host checkpoint.  Same plan + seed =>
    same drill.

    CPU backends cannot run multi-process XLA computations, so the drill
    sets ``DLROVER_TPU_SKIP_JAX_INIT=1``: each trainer computes in its
    own single-process jax world while rendezvous, data sharding and the
    checkpoint world stay genuinely 2-host (the agent's saver stamps the
    sealed world) — the n=2 -> m=1 reshard on resume is the real path.
    """
    from dlrover_tpu.common import faults
    from dlrover_tpu.common.storage import (
        CheckpointDirLayout,
        PosixDiskStorage,
    )
    from dlrover_tpu.master.job_master import JobMaster

    os.makedirs(args.workdir, exist_ok=True)
    ckpt = os.path.join(args.workdir, "ckpt")
    # Same plan + seed => same drill, which starts with NO checkpoint: a
    # previous run's committed steps would turn round 1 into a resume and
    # shift every "step N" in the fault plan.
    import shutil

    shutil.rmtree(ckpt, ignore_errors=True)
    master = JobMaster(
        num_nodes=2, min_nodes=1,
        heartbeat_timeout=8.0, max_relaunches=10**6,
    )
    master.CONTROL_LOOP_INTERVAL = 2.0
    port = master.start()

    base_env = _bench_env(args)
    base_env["DLROVER_TPU_SKIP_JAX_INIT"] = "1"
    drill_plan = f"preempt.notice:error@{args.drill_preempt_hit}"
    if args.fault_plan:
        drill_plan = f"{args.fault_plan};{drill_plan}"
    faults.parse_plan(drill_plan)  # fail fast on a typo'd base plan

    def spawn(node_id: int, plan: str):
        env = dict(base_env)
        if plan:
            env[faults.ENV_PLAN] = plan
            env[faults.ENV_SEED] = str(args.fault_seed)
        cmd = [
            sys.executable, "-m", "dlrover_tpu.run",
            "--master", f"localhost:{port}",
            "--nnodes", "1:2", "--node-id", str(node_id),
            "--max-restarts", "1000",
            "--monitor-interval", "0.5",
            "--heartbeat-interval", "2",
            "--save-at-breakpoint",
            "--checkpoint-dir", ckpt,
            "--", sys.executable,
            os.path.join(REPO, "examples", "train_lm.py"),
            "--steps", str(args.steps), "--ckpt-every", "10",
            "--checkpoint-dir", ckpt,
            "--layers", "1", "--d-model", "64", "--heads", "2",
            "--seq-len", "64", "--batch-size", "4",
            "--step-sleep", str(args.step_sleep),
        ]
        return subprocess.Popen(cmd, env=env, start_new_session=True)

    storage = PosixDiskStorage()
    layout = CheckpointDirLayout(ckpt)
    t_start = time.monotonic()
    survivor = spawn(0, args.fault_plan)
    victim = spawn(1, drill_plan)
    step_at_notice = -1
    restored_step = -1
    t_notice = None
    ok = False
    deadline = t_start + args.steps * max(args.step_sleep, 0.1) * 6 + 600
    while time.monotonic() < deadline:
        sm = master.speed_monitor
        if t_notice is None and sm.resize_ledger()["resizes"] > 0:
            t_notice = time.monotonic()
            step_at_notice = sm.global_step
            print(f"[drill] preemption notice at step {step_at_notice}",
                  flush=True)
        if victim is not None and victim.poll() is not None:
            # The drained host is gone for good: the drill never
            # reprovisions it — that's the resize.
            restored_step = layout.latest_step(storage)
            print(f"[drill] node 1 drained (rc {victim.returncode}); "
                  f"last committed step {restored_step}", flush=True)
            victim = None
        rc = survivor.poll()
        if rc is not None:
            if rc == 0:
                ok = True
                break
            time.sleep(args.reprovision_delay)
            survivor = spawn(0, args.fault_plan)
            continue
        time.sleep(0.5)
    for proc in (survivor, victim):
        if proc is not None and proc.poll() is None:
            try:
                os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
            except (ProcessLookupError, OSError):
                pass

    sm = master.speed_monitor
    resize = sm.resize_ledger()
    drain_s = max(
        (e[3] for e in master.timeline.spans(1, "drain")), default=0.0
    )
    steps_lost = (
        max(0, step_at_notice - restored_step)
        if step_at_notice >= 0 and restored_step >= 0 else -1
    )
    result = {
        "metric": "elastic resize drill (2 -> 1, scripted preemption)",
        "value": round(resize["resize_s_total"], 2),
        "unit": "seconds",
        "detail": {
            "completed": ok and sm.global_step >= args.steps,
            "final_step": sm.global_step,
            "target_steps": args.steps,
            "step_at_notice": step_at_notice,
            "restored_step": restored_step,
            "steps_lost": steps_lost,
            "drain_s": round(drain_s, 4),
            "resize_s": round(resize["resize_s_total"], 2),
            "resizes": resize["resizes"],
            "resizes_by_reason": resize["by_reason"],
            "goodput": round(sm.goodput(), 4),
            "fault_plan": drill_plan,
            "fault_seed": args.fault_seed,
            "fault_ledger": sm.fault_ledger(),
        },
    }
    master.stop()
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0 if result["detail"]["completed"] else 1


def run_live_relayout_drill(args) -> int:
    """Live virtual-mesh resize drill: relayout vs rebuild-restore.

    Three phases, one artifact (RESIZE_LIVE.json):

    A. **Live 2 -> 1**: both agents run ``--live-relayout``; node 1's
       scripted ``preempt.notice`` drains it, node 0's agent re-joins the
       rendezvous but KEEPS its trainer, which folds the virtual mesh onto
       itself in place (``apply_world_change``) — the master books the
       relayout (ms) in the resize ledger's ``by_kind``.  ``steps_lost``
       is 0 by construction when the survivor finishes with zero restarts
       (its step counter never rewinds).
    B. **Restore baseline**: the classic 2 -> 1 drill (same plan, same
       chaos point) on the legacy drain -> re-rendezvous -> checkpoint
       -restore path; its resize seconds are the denominator of the
       ``speedup_vs_restore`` headline (target: >= 10x).
    C. **Parity child**: an in-process 4 -> 2 -> 4 lockstep run
       (``--live-parity-child``) whose loss trajectory must match a
       never-resized reference step for step — the proof that a live
       relayout changes WHERE state lives, not what the program computes.
    """
    import copy
    import shutil

    from dlrover_tpu.common import faults
    from dlrover_tpu.master.job_master import JobMaster

    os.makedirs(args.workdir, exist_ok=True)

    # -- phase A: live 2 -> 1 (virtual-mesh fold, no restart) -----------------
    ckpt = os.path.join(args.workdir, "ckpt_live")
    shutil.rmtree(ckpt, ignore_errors=True)
    master = JobMaster(
        num_nodes=2, min_nodes=1,
        heartbeat_timeout=8.0, max_relaunches=10**6,
    )
    master.CONTROL_LOOP_INTERVAL = 2.0
    port = master.start()
    base_env = _bench_env(args)
    base_env["DLROVER_TPU_SKIP_JAX_INIT"] = "1"
    base_env["DLROVER_TPU_JOB"] = f"live{os.getpid()}"
    drill_plan = f"preempt.notice:error@{args.drill_preempt_hit}"
    faults.parse_plan(drill_plan)

    def spawn(node_id: int, plan: str = ""):
        env = dict(base_env)
        if plan:
            env[faults.ENV_PLAN] = plan
            env[faults.ENV_SEED] = str(args.fault_seed)
        cmd = [
            sys.executable, "-m", "dlrover_tpu.run",
            "--master", f"localhost:{port}",
            "--nnodes", "1:2", "--node-id", str(node_id),
            "--max-restarts", "1000",
            "--monitor-interval", "0.5",
            "--heartbeat-interval", "2",
            "--live-relayout",
            "--save-at-breakpoint",
            "--checkpoint-dir", ckpt,
            "--", sys.executable,
            os.path.join(REPO, "examples", "train_lm.py"),
            "--steps", str(args.steps), "--ckpt-every", "10",
            "--checkpoint-dir", ckpt,
            "--layers", "1", "--d-model", "64", "--heads", "2",
            "--seq-len", "64", "--batch-size", "4",
            "--step-sleep", str(args.step_sleep),
            "--ref-world", "2", "--live-relayout", "--lockstep-data",
        ]
        return subprocess.Popen(cmd, env=env, start_new_session=True)

    t_start = time.monotonic()
    survivor = spawn(0)
    victim = spawn(1, drill_plan)
    relayout_step = -1
    completed = False
    deadline = t_start + args.steps * max(args.step_sleep, 0.1) * 6 + 600
    while time.monotonic() < deadline:
        sm = master.speed_monitor
        if (
            relayout_step < 0
            and sm.resize_ledger()["by_reason"].get("relayout", 0) > 0
        ):
            relayout_step = sm.global_step
            print(f"[live] relayout booked at step {relayout_step}",
                  flush=True)
        if victim is not None and victim.poll() is not None:
            print(f"[live] node 1 drained (rc {victim.returncode})",
                  flush=True)
            victim = None
        rc = survivor.poll()
        if rc is not None:
            # No reprovision here: a survivor restart IS a drill failure
            # (the live path's whole point is that it never restarts).
            completed = rc == 0
            break
        time.sleep(0.5)
    for proc in (survivor, victim):
        if proc is not None and proc.poll() is None:
            try:
                os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
            except (ProcessLookupError, OSError):
                pass

    sm = master.speed_monitor
    resize = sm.resize_ledger()
    relayout_s = resize["by_kind"].get("relayout", 0.0)
    relayouts = resize["by_reason"].get("relayout", 0)
    fallbacks = resize["by_reason"].get("relayout_failed", 0)
    survivor_restarts = master.timeline.restart_count(0)
    live_completed = completed and sm.global_step >= args.steps
    # The survivor's step counter never rewinds unless it restarts, so a
    # zero-restart completed run lost zero steps to the resize.
    steps_lost = 0 if live_completed and survivor_restarts == 0 else -1
    live = {
        "completed": live_completed,
        "final_step": sm.global_step,
        "target_steps": args.steps,
        "relayout_step": relayout_step,
        "relayouts": relayouts,
        "relayout_fallbacks": fallbacks,
        "relayout_s": round(relayout_s, 4),
        "survivor_restarts": survivor_restarts,
        "steps_lost": steps_lost,
        "resizes_by_reason": resize["by_reason"],
        "resize_s_by_kind": {
            k: round(v, 4) for k, v in resize["by_kind"].items()
        },
        "goodput": round(sm.goodput(), 4),
        "fault_plan": drill_plan,
    }
    master.stop()
    print(f"[live] phase A done: {json.dumps(live)}", flush=True)

    # -- phase B: classic restore drill (the denominator) ---------------------
    b_args = copy.copy(args)
    b_args.out = os.path.join(args.workdir, "restore_drill.json")
    run_resize_drill(b_args)
    with open(b_args.out) as f:
        restore = json.load(f)["detail"]
    restore_resize_s = restore.get("resize_s", 0.0)

    # -- phase C: in-process 4 -> 2 -> 4 lockstep parity ----------------------
    parity_out = os.path.join(args.workdir, "live_parity.json")
    c_env = _bench_env(args)
    c_env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    c_env["DLROVER_TPU_JOB"] = f"parity{os.getpid()}"
    c_env.pop("DLROVER_TPU_SKIP_JAX_INIT", None)
    c_rc = subprocess.call(
        [sys.executable, os.path.abspath(__file__),
         "--live-parity-child", "--out", parity_out],
        env=c_env,
    )
    parity = {"ok": False, "rc": c_rc}
    if os.path.exists(parity_out):
        with open(parity_out) as f:
            parity = json.load(f)

    speedup = restore_resize_s / max(relayout_s, 1e-9)
    ok = (
        live_completed
        and steps_lost == 0
        and relayouts >= 1
        and fallbacks == 0
        and relayout_s > 0.0
        and restore_resize_s >= 10.0 * relayout_s
        and bool(parity.get("ok"))
    )
    result = {
        "metric": "live relayout vs restore-path resize",
        "value": round(relayout_s * 1000.0, 3),
        "unit": "ms (in-memory re-layout, vs restore seconds)",
        "detail": {
            "ok": ok,
            "live": live,
            "restore": {
                "completed": restore.get("completed"),
                "resize_s": restore_resize_s,
                "steps_lost": restore.get("steps_lost"),
                "drain_s": restore.get("drain_s"),
            },
            "speedup_vs_restore": round(speedup, 1),
            "parity": parity,
        },
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0 if ok else 1


def run_live_parity_child(args) -> int:
    """4 -> 2 -> 4 lockstep parity (in-process; spawned by the live drill).

    One trainer starts on a reference world of 4, folds to 2 at step 4,
    fans back to 4 at step 8; a second never-resized trainer consumes the
    identical batch stream.  Because programs compile against the logical
    mesh, tokens/step and the optimizer trajectory are resize-invariant —
    the only drift allowed is grad-accum fp reassociation (~1e-5 rel).
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("DLROVER_TPU_JOB", f"parity{os.getpid()}")
    os.environ.pop("DLROVER_TPU_SKIP_JAX_INIT", None)
    import numpy as np

    import jax
    from dlrover_tpu.models.transformer import TransformerConfig
    from dlrover_tpu.trainer.elastic_trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    steps = 12
    mc = TransformerConfig(
        num_layers=1, d_model=64, num_heads=2,
        vocab_size=256, max_seq_len=32,
    )
    rng = np.random.default_rng(0)
    batches = [
        {
            "inputs": rng.integers(0, 256, (16, 32), dtype=np.int32),
            "targets": rng.integers(0, 256, (16, 32), dtype=np.int32),
        }
        for _ in range(steps)
    ]

    def mk():
        return ElasticTrainer(
            mc,
            TrainerConfig(
                global_batch_size=16, seq_len=32,
                optimizer="sgd", learning_rate=1e-2,
                grad_accum=1, grad_accum_ref_world=4, world=4,
                report_every=1000, numeric_checks=False,
            ),
            client=None,
        )

    def losses_of(trainer, schedule):
        losses = []

        def on_step(step, metrics):
            losses.append(float(jax.device_get(metrics["loss"])))

        relayout_ms = []
        at = 0
        for world, until in schedule:
            if trainer.vmesh.physical_world != world:
                d = trainer.apply_world_change(world)
                if not d.get("ok") or d.get("fallback"):
                    raise RuntimeError(f"relayout failed: {d}")
                relayout_ms.append(round(d["relayout_s"] * 1000.0, 3))
            trainer.fit(iter(batches[at:until]), max_steps=until,
                        on_step=on_step)
            at = until
        return losses, relayout_ms

    resized = mk()
    prewarm = resized.prewarm_worlds([2, 4], aot=True)
    live, relayout_ms = losses_of(
        resized, [(4, 4), (2, 8), (4, steps)]
    )
    ref, _ = losses_of(mk(), [(4, steps)])
    rel_err = max(
        abs(a - b) / max(abs(b), 1e-9) for a, b in zip(live, ref)
    )
    res = {
        "ok": len(live) == steps and rel_err < 5e-5,
        "schedule": "4->2->4",
        "steps": steps,
        "max_rel_err": rel_err,
        "relayout_ms": relayout_ms,
        "prewarm_grad_accum": {str(k): v for k, v in prewarm.items()},
    }
    with open(args.out, "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res))
    return 0 if res["ok"] else 1


def run_sdc_drill(args) -> int:
    """Deterministic silent-data-corruption drill (3 hosts, 1 bitflip).

    Node 2's fault plan scripts one ``sdc.flip`` at a fixed digest check,
    so a single mantissa bit of its live train state flips at the same
    point every run.  The corrupted replica's state digest then diverges
    from the other two at every later check; the master's cross-replica
    vote (SpeedMonitor digest ledger -> SDCVoteOperator) pins the 2-vs-1
    minority, and after a persistent streak QUARANTINEs the host:
    blacklist + rendezvous ban + replacement request + world restart onto
    the last checkpoint.  The drill books detection latency in steps and
    verifies the vote fingered the right host, that post-restore digests
    are unanimous, and that the recovered loss trajectory tracks an
    uninjected reference run.

    ``--lockstep-data`` is load-bearing: with ``DLROVER_TPU_SKIP_JAX_INIT``
    each node is its own data replica, and the digests only agree when the
    replicas consume identical batches.
    """
    import shutil

    from dlrover_tpu.common import faults
    from dlrover_tpu.master.job_master import JobMaster

    os.makedirs(args.workdir, exist_ok=True)
    victim_id = 2
    flip_step = args.sdc_flip_hit * args.sdc_check_every
    drill_plan = f"sdc.flip:error@{args.sdc_flip_hit}"
    faults.parse_plan(drill_plan)

    def train_cmd(port: int, nnodes: str, node_id: int, ckpt: str):
        return [
            sys.executable, "-m", "dlrover_tpu.run",
            "--master", f"localhost:{port}",
            "--nnodes", nnodes, "--node-id", str(node_id),
            "--max-restarts", "1000",
            "--monitor-interval", "0.5",
            "--heartbeat-interval", "2",
            "--save-at-breakpoint",
            "--checkpoint-dir", ckpt,
            "--", sys.executable,
            os.path.join(REPO, "examples", "train_lm.py"),
            "--steps", str(args.steps), "--ckpt-every", "10",
            "--checkpoint-dir", ckpt,
            "--layers", "1", "--d-model", "64", "--heads", "2",
            "--seq-len", "64", "--batch-size", "4",
            "--step-sleep", str(args.step_sleep),
            "--sdc-check-every", str(args.sdc_check_every),
            "--lockstep-data",
        ]

    # -- phase 1: chaos run (3 nodes, node 2 flips one bit) -------------------
    ckpt = os.path.join(args.workdir, "ckpt_sdc")
    shutil.rmtree(ckpt, ignore_errors=True)
    master = JobMaster(
        num_nodes=3, min_nodes=2,
        heartbeat_timeout=8.0, max_relaunches=10**6,
    )
    master.CONTROL_LOOP_INTERVAL = 2.0
    port = master.start()
    base_env = _bench_env(args)
    base_env["DLROVER_TPU_SKIP_JAX_INIT"] = "1"
    base_env["DLROVER_TPU_JOB"] = f"sdc{os.getpid()}"

    def spawn(node_id: int, plan: str = ""):
        env = dict(base_env)
        if plan:
            env[faults.ENV_PLAN] = plan
            env[faults.ENV_SEED] = str(args.fault_seed)
        return subprocess.Popen(
            train_cmd(port, "2:3", node_id, ckpt),
            env=env, start_new_session=True,
        )

    t_start = time.monotonic()
    procs = {i: spawn(i) for i in range(victim_id)}
    procs[victim_id] = spawn(victim_id, drill_plan)
    quarantine_step = -1
    voted_node = -1
    t_first_mismatch = None
    t_quarantine = None
    mismatches_at_quarantine = -1
    survivors_done = set()
    failed = False
    deadline = t_start + args.steps * max(args.step_sleep, 0.1) * 8 + 900
    while time.monotonic() < deadline:
        sm = master.speed_monitor
        ledger = sm.sdc_ledger()
        if t_first_mismatch is None and ledger["mismatches"] > 0:
            t_first_mismatch = time.monotonic()
            print(f"[sdc] first digest mismatch at step {sm.global_step} "
                  f"(streaks {ledger['streaks']})", flush=True)
        quarantined = master.node_manager.quarantined()
        if t_quarantine is None and quarantined:
            t_quarantine = time.monotonic()
            quarantine_step = sm.global_step
            voted_node = next(iter(quarantined))
            mismatches_at_quarantine = ledger["mismatches"]
            print(f"[sdc] node {voted_node} quarantined at step "
                  f"{quarantine_step}: {quarantined[voted_node]}",
                  flush=True)
            # The banned host is gone for good — like a real corrupting
            # chip, it never re-joins; the drill reaps its process group.
            proc = procs.pop(victim_id, None)
            if proc is not None and proc.poll() is None:
                try:
                    os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
                except (ProcessLookupError, OSError):
                    pass
        for node_id in list(procs):
            rc = procs[node_id].poll()
            if rc is None:
                continue
            if rc == 0:
                survivors_done.add(node_id)
                del procs[node_id]
            elif node_id == victim_id:
                del procs[node_id]  # banned victim's exit code is moot
            else:
                failed = True
                print(f"[sdc] survivor {node_id} exited rc {rc}",
                      flush=True)
                del procs[node_id]
        if failed or len(survivors_done) >= 2:
            break
        time.sleep(0.5)
    for proc in procs.values():
        if proc.poll() is None:
            try:
                os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
            except (ProcessLookupError, OSError):
                pass

    sm = master.speed_monitor
    ledger = sm.sdc_ledger()
    chaos_losses = sm.recent_losses(5)
    completed = len(survivors_done) >= 2 and sm.global_step >= args.steps
    detect_steps = (
        quarantine_step - flip_step if quarantine_step >= 0 else -1
    )
    # Post-restore unanimity: once the corrupting host is out, every later
    # finalized vote must agree — zero mismatches after the quarantine.
    post_restore_mismatches = (
        ledger["mismatches"] - mismatches_at_quarantine
        if mismatches_at_quarantine >= 0 else -1
    )
    master.stop()

    # -- phase 2: uninjected reference run (loss-trajectory parity) -----------
    # Bitwise parity is out of reach (the restart rewinds the lockstep
    # sample stream), so the drill checks the recovered trajectory's tail
    # lands on the clean run's: same toy problem, same step count.
    ckpt_ref = os.path.join(args.workdir, "ckpt_ref")
    shutil.rmtree(ckpt_ref, ignore_errors=True)
    ref_master = JobMaster(
        num_nodes=1, heartbeat_timeout=8.0, max_relaunches=10**6
    )
    ref_master.CONTROL_LOOP_INTERVAL = 2.0
    ref_port = ref_master.start()
    ref_env = _bench_env(args)
    ref_env["DLROVER_TPU_SKIP_JAX_INIT"] = "1"
    ref_env["DLROVER_TPU_JOB"] = f"sdcref{os.getpid()}"
    ref = subprocess.Popen(
        train_cmd(ref_port, "1", 0, ckpt_ref),
        env=ref_env, start_new_session=True,
    )
    ref_deadline = time.monotonic() + args.steps * max(
        args.step_sleep, 0.1
    ) * 6 + 600
    ref_ok = False
    while time.monotonic() < ref_deadline:
        rc = ref.poll()
        if rc is not None:
            ref_ok = rc == 0
            break
        time.sleep(0.5)
    if ref.poll() is None:
        try:
            os.killpg(os.getpgid(ref.pid), signal.SIGKILL)
        except (ProcessLookupError, OSError):
            pass
    ref_losses = ref_master.speed_monitor.recent_losses(5)
    ref_master.stop()

    def _mean(samples):
        return (
            sum(v for _, v in samples) / len(samples) if samples else -1.0
        )

    loss_chaos, loss_ref = _mean(chaos_losses), _mean(ref_losses)
    loss_rel_err = (
        abs(loss_chaos - loss_ref) / max(abs(loss_ref), 1e-9)
        if loss_chaos >= 0 and loss_ref >= 0 else -1.0
    )
    ok = (
        completed
        and ref_ok
        and voted_node == victim_id
        and detect_steps >= 0
        and post_restore_mismatches == 0
        and 0.0 <= loss_rel_err < 0.25
    )
    result = {
        "metric": "SDC drill (bitflip -> vote -> quarantine -> restore)",
        "value": detect_steps,
        "unit": "steps from flip to quarantine",
        "detail": {
            "ok": ok,
            "completed": completed,
            "final_step": sm.global_step,
            "target_steps": args.steps,
            "flip_step": flip_step,
            "flipped_node": victim_id,
            "voted_node": voted_node,
            "quarantine_step": quarantine_step,
            "detect_steps": detect_steps,
            "detect_s": (
                round(t_quarantine - t_first_mismatch, 2)
                if t_quarantine and t_first_mismatch else -1.0
            ),
            "sdc_checks": ledger["checks"],
            "sdc_mismatches": ledger["mismatches"],
            "sdc_quarantines": ledger["quarantines"],
            "post_restore_mismatches": post_restore_mismatches,
            "loss_recovered": round(loss_chaos, 4),
            "loss_reference": round(loss_ref, 4),
            "loss_rel_err": round(loss_rel_err, 4),
            "reference_completed": ref_ok,
            "check_every": args.sdc_check_every,
            "fault_plan": drill_plan,
            "fault_seed": args.fault_seed,
        },
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--step-sleep", type=float, default=1.0,
                    help="per-step sleep standing in for real step compute "
                         "(a 1.5B TPU step is ~2s; the toy CPU step is ~ms)")
    ap.add_argument("--kill-every", type=float, default=150.0,
                    help="seconds between injected failures (TPU-VM spot "
                         "preemptions are minutes-to-hours apart; 150s is "
                         "far harsher than the north-star scenario)")
    ap.add_argument("--reprovision-delay", type=float, default=3.0,
                    help="simulated node re-provisioning time after a "
                         "group kill")
    ap.add_argument("--workdir", default="/tmp/dlrover_tpu_goodput")
    ap.add_argument("--out", default="GOODPUT.json")
    ap.add_argument("--target", type=float, default=0.9)
    ap.add_argument("--fault-plan", default="",
                    help="Faultline plan (DLROVER_TPU_FAULTS grammar, e.g. "
                         "'storage.write:error@3;rpc.report:delay=0.5@5'); "
                         "replaces the wall-clock SIGKILL scheduler with a "
                         "deterministic, seeded fault schedule so runs are "
                         "reproducible")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for --fault-plan probabilistic schedules")
    ap.add_argument("--resize-drill", action="store_true",
                    help="deterministic 2->1 elastic-resize drill: node 1 "
                         "gets a scripted preempt.notice fault, drains "
                         "gracefully (shm flush -> master notice -> exit), "
                         "and node 0's survivor world resumes from the "
                         "cross-world-restored checkpoint; reports drain_s "
                         "/ resize_s / steps_lost")
    ap.add_argument("--live-relayout", action="store_true",
                    help="virtual-mesh variant of the resize drill: both "
                         "agents run --live-relayout, the survivor folds "
                         "its logical mesh in place (ms) instead of "
                         "restarting into a checkpoint restore (s); also "
                         "runs the classic restore drill as the speedup "
                         "denominator and a 4->2->4 in-process lockstep "
                         "parity child; writes one combined artifact")
    ap.add_argument("--live-parity-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--drill-preempt-hit", type=int, default=20,
                    help="preempt.notice seam hit at which node 1's notice "
                         "fires (the monitor probes ~1/s, so this is "
                         "roughly seconds into the run)")
    ap.add_argument("--sdc-drill", action="store_true",
                    help="deterministic silent-data-corruption drill: 3 "
                         "nodes train in lockstep, node 2's sdc.flip seam "
                         "flips one mantissa bit of its live state, the "
                         "cross-replica digest vote pins the 2-vs-1 "
                         "minority and quarantines the host; reports "
                         "detect_steps + post-restore loss parity vs an "
                         "uninjected reference run")
    ap.add_argument("--sdc-check-every", type=int, default=16,
                    help="digest-check cadence handed to the trainers "
                         "(--sdc-check-every of examples/train_lm.py)")
    ap.add_argument("--sdc-flip-hit", type=int, default=1,
                    help="sdc.flip seam hit at which the victim's bit "
                         "flips (hit N = the N-th digest check, i.e. step "
                         "N * sdc-check-every)")
    args = ap.parse_args()
    if args.live_parity_child:
        return run_live_parity_child(args)
    if args.live_relayout:
        return run_live_relayout_drill(args)
    if args.resize_drill:
        return run_resize_drill(args)
    if args.sdc_drill:
        return run_sdc_drill(args)

    from dlrover_tpu.master.job_master import JobMaster

    os.makedirs(args.workdir, exist_ok=True)
    ckpt = os.path.join(args.workdir, "ckpt")
    # Injected failures are the point of this bench: the relaunch/restart
    # budget must never be the thing that ends the run.
    # heartbeat-interval 2s below: 8s = four missed beats, the detection
    # latency a silent SIGKILL pays (SIGTERM preemptions report instantly).
    master = JobMaster(
        num_nodes=1, heartbeat_timeout=8.0, max_relaunches=10**6
    )
    master.CONTROL_LOOP_INTERVAL = 2.0
    port = master.start()

    env = _bench_env(args)
    if args.fault_plan:
        # Validate up front (a typo'd plan must not burn a bench run) and
        # hand the schedule to every child; agents re-export it to their
        # trainer subprocesses, so one flag arms the whole process tree.
        from dlrover_tpu.common import faults

        faults.parse_plan(args.fault_plan)
        env[faults.ENV_PLAN] = args.fault_plan
        env[faults.ENV_SEED] = str(args.fault_seed)

    def spawn_agent():
        cmd = [
            sys.executable, "-m", "dlrover_tpu.run",
            "--master", f"localhost:{port}",
            "--nnodes", "1", "--node-id", "0",
            "--max-restarts", "1000",
            "--monitor-interval", "0.5",
            "--heartbeat-interval", "2",
            "--save-at-breakpoint",
            "--checkpoint-dir", ckpt,
            "--", sys.executable, os.path.join(REPO, "examples", "train_lm.py"),
            "--steps", str(args.steps), "--ckpt-every", "10",
            "--checkpoint-dir", ckpt,
            "--layers", "1", "--d-model", "64", "--heads", "2",
            "--seq-len", "64", "--batch-size", "4",
            "--step-sleep", str(args.step_sleep),
        ]
        return subprocess.Popen(cmd, env=env, start_new_session=True)

    t_start = time.monotonic()
    agent = spawn_agent()
    kills = []
    # Deterministic mode: injected faults come from the seeded plan, not
    # from this process's wall clock — disable the SIGKILL scheduler.
    next_kill = (
        float("inf") if args.fault_plan
        else time.monotonic() + args.kill_every
    )
    mode = 0
    while True:
        rc = agent.poll()
        if rc is not None:
            if rc == 0:
                break
            # Agent died from a group kill: reprovision after a delay.
            time.sleep(args.reprovision_delay)
            agent = spawn_agent()
            continue
        now = time.monotonic()
        if now >= next_kill and master.speed_monitor.global_step < args.steps - 20:
            next_kill = now + args.kill_every
            if mode == 0:
                # Process failure: kill the trainer only.
                trainers = [
                    c for c in _children(agent.pid)
                ]
                for pid in trainers:
                    try:
                        os.kill(pid, signal.SIGKILL)
                    except ProcessLookupError:
                        continue
                kills.append({"t": round(now - t_start, 1),
                              "kind": "trainer_sigkill"})
                print(f"[chaos] killed trainer(s) {trainers}", flush=True)
            else:
                # Preemption: kill the whole node group; harness relaunches.
                try:
                    os.killpg(os.getpgid(agent.pid), signal.SIGKILL)
                except ProcessLookupError:
                    pass
                kills.append({"t": round(now - t_start, 1),
                              "kind": "node_preemption"})
                print("[chaos] preempted node group", flush=True)
            mode ^= 1
        if now - t_start > args.steps * args.step_sleep * 6 + 600:
            print("goodput bench timed out", file=sys.stderr)
            break
        time.sleep(1.0)

    sm = master.speed_monitor
    total_s = time.monotonic() - t_start
    productive = sm._productive_s
    first = sm._first_step_time
    training_s = (time.time() - first) if first else total_s
    result = {
        "metric": "goodput under injected failures",
        "value": round(sm.goodput(), 4),
        "unit": "fraction",
        "vs_baseline": round(sm.goodput() / args.target, 4),
        "detail": {
            "goodput_total": round(sm.goodput(), 4),
            "goodput_training_phase": round(
                min(1.0, productive / training_s) if training_s > 0 else 0.0, 4
            ),
            "productive_s": round(productive, 1),
            "wall_s": round(total_s, 1),
            "final_step": sm.global_step,
            "target_steps": args.steps,
            "kills": kills,
            "fault_plan": args.fault_plan,
            "fault_ledger": sm.fault_ledger(),
            "completed": sm.global_step >= args.steps,
        },
    }
    master.stop()
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0 if result["detail"]["completed"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
