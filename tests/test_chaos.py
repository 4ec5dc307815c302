"""Dead-node chaos test: SIGKILL one of two agents mid-training; the
survivor must detect the broken world, requeue the dead host's shards,
re-form a 1-node world, resume from checkpoint and finish.

This is the TPU counterpart of the reference's pod-kill experiments
(ref ``docs/tech_report/fault_tolerance_exps.md:145-210``) exercising the
heartbeat-death path end-to-end: master ``check_heartbeats`` ->
``_handle_node_death`` (evict from rendezvous + ``recover_tasks``) ->
survivor ``world_changed`` -> membership restart -> smaller world seals.
"""

import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _agent_cmd(master_addr, node_id, ckpt_dir, steps):
    return [
        sys.executable, "-m", "dlrover_tpu.run",
        "--master", master_addr,
        "--nnodes", "1:2",
        "--node-id", str(node_id),
        "--max-restarts", "3",
        "--monitor-interval", "1",
        "--heartbeat-interval", "1",
        "--checkpoint-dir", ckpt_dir,
        "--", sys.executable, os.path.join(REPO, "examples", "train_lm.py"),
        "--steps", str(steps), "--ckpt-every", "4",
        "--checkpoint-dir", ckpt_dir,
        "--layers", "1", "--d-model", "64", "--heads", "2",
        "--seq-len", "64", "--batch-size", "4",
        "--step-sleep", "0.3",
    ]


@pytest.mark.slow
def test_sigkill_one_of_two_agents_survivor_recovers(tmp_path, cpu_child_env):
    from dlrover_tpu.common.storage import CheckpointDirLayout, PosixDiskStorage
    from dlrover_tpu.master.job_master import JobMaster

    ckpt_dir = str(tmp_path / "ckpt")
    steps = 24
    master = JobMaster(
        num_nodes=2, min_nodes=1, rdzv_waiting_timeout=3.0,
        heartbeat_timeout=5.0,
    )
    master.CONTROL_LOOP_INTERVAL = 1.0
    port = master.start()
    addr = f"localhost:{port}"

    env = cpu_child_env
    env.update(
        {
            "DLROVER_TPU_SOCKET_DIR": str(tmp_path / "socks"),
            "DLROVER_TPU_JOB": f"chaos{os.getpid()}",
            "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        }
    )
    env.pop("XLA_FLAGS", None)

    procs = {}
    try:
        for node_id in (0, 1):
            procs[node_id] = subprocess.Popen(
                _agent_cmd(addr, node_id, ckpt_dir, steps),
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
                start_new_session=True,  # killpg takes out agent + trainer
            )

        # Wait for the first committed checkpoint so the survivor has
        # something to resume from, then SIGKILL node 1's process group
        # (agent and trainer die silently — no failure report, no SIGTERM
        # persist; only heartbeat timeout can discover this).
        layout = CheckpointDirLayout(ckpt_dir)
        storage = PosixDiskStorage()
        deadline = time.monotonic() + 240
        while layout.latest_step(storage) < 4:
            assert time.monotonic() < deadline, "no checkpoint within 240s"
            assert procs[0].poll() is None, (
                procs[0].communicate(timeout=120)[0][-3000:]
            )
            assert procs[1].poll() is None, "agent 1 died prematurely"
            time.sleep(0.5)
        os.killpg(os.getpgid(procs[1].pid), signal.SIGKILL)
        procs[1].wait(timeout=10)

        out, _ = procs[0].communicate(timeout=240)
        assert procs[0].returncode == 0, out[-5000:]
        assert "membership changed" in out
        assert "resumed from checkpoint at step" in out
        assert layout.latest_step(storage) == steps

        # The master declared node 1 dead and relaunched (noop launcher ->
        # PENDING); its unfinished shards were requeued and completed by the
        # survivor (exhausted task queue lets the trainer reach `steps`).
        assert master.node_manager.statuses()[1] in ("pending", "dead")
    finally:
        for p in procs.values():
            if p.poll() is None:
                try:
                    os.killpg(os.getpgid(p.pid), signal.SIGKILL)
                except ProcessLookupError:
                    pass
        master.stop()
