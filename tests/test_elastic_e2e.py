"""End-to-end elastic launch tests: CLI -> standalone master -> agent ->
trainer subprocess, with crash-restart-resume.

Mirrors the reference's chaos validation (SURVEY.md §4/§5: kill process,
observe relaunch & resumed step — ``fault_tolerance_exps.md``).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_cli(tmp_path, child_env, extra_cli, extra_trainer, timeout=600):
    env = dict(child_env)
    env.update(
        {
            "DLROVER_TPU_SOCKET_DIR": str(tmp_path / "socks"),
            # Unique per test: the shm arena is named by job tag and outlives
            # processes, so two tests sharing a tag would see each other's
            # checkpoints.
            "DLROVER_TPU_JOB": f"e2e{os.getpid()}_{os.path.basename(tmp_path)}",
            "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        }
    )
    env.pop("XLA_FLAGS", None)
    cmd = (
        [sys.executable, "-m", "dlrover_tpu.run", "--standalone"]
        + extra_cli
        + ["--", sys.executable, os.path.join(REPO, "examples", "train_lm.py")]
        + extra_trainer
    )
    return subprocess.run(
        cmd, env=env, timeout=timeout, capture_output=True, text=True
    )


@pytest.mark.slow
def test_cli_standalone_training(tmp_path, cpu_child_env):
    ckpt_dir = str(tmp_path / "ckpt")
    result = _run_cli(
        tmp_path, cpu_child_env,
        ["--checkpoint-dir", ckpt_dir, "--monitor-interval", "1"],
        [
            "--steps", "8", "--ckpt-every", "4",
            "--checkpoint-dir", ckpt_dir,
            "--layers", "1", "--d-model", "64", "--heads", "2",
            "--seq-len", "64", "--batch-size", "4",
        ],
    )
    assert result.returncode == 0, result.stderr[-3000:]
    from dlrover_tpu.common.storage import CheckpointDirLayout, PosixDiskStorage

    assert CheckpointDirLayout(ckpt_dir).latest_step(PosixDiskStorage()) == 8


@pytest.mark.slow
def test_cli_crash_restart_resume(tmp_path, cpu_child_env):
    """Trainer crashes at step 6 (after the step-4 checkpoint); the agent
    restarts it in place; it resumes from step 4 and completes."""
    ckpt_dir = str(tmp_path / "ckpt")
    result = _run_cli(
        tmp_path, cpu_child_env,
        [
            "--checkpoint-dir", ckpt_dir, "--max-restarts", "2",
            "--monitor-interval", "1",
        ],
        [
            "--steps", "8", "--ckpt-every", "4",
            "--checkpoint-dir", ckpt_dir, "--fail-at-step", "6",
            "--layers", "1", "--d-model", "64", "--heads", "2",
            "--seq-len", "64", "--batch-size", "4",
        ],
    )
    assert result.returncode == 0, result.stderr[-3000:]
    combined = result.stdout + result.stderr
    assert "crashing at step 6" in combined
    assert "resumed from checkpoint at step 4" in combined
    from dlrover_tpu.common.storage import CheckpointDirLayout, PosixDiskStorage

    assert CheckpointDirLayout(ckpt_dir).latest_step(PosixDiskStorage()) == 8
