"""Stack-dump collector (ref ``datacollector/cuda_log_collector.py``):
the agent must be able to ask a live trainer WHERE it is stuck."""

import os
import subprocess
import sys
import time

import pytest

from dlrover_tpu.agent.stack_collector import (
    ENV_STACK_FILE,
    collect_stacks,
    install_stack_dump_handler,
)


@pytest.mark.slow  # spawns a python subprocess and polls it for seconds
def test_collect_stacks_from_live_process(tmp_path):
    path = str(tmp_path / "stacks.txt")
    child = subprocess.Popen(
        [sys.executable, "-c", (
            "import time\n"
            "from dlrover_tpu.agent.stack_collector import "
            "install_stack_dump_handler\n"
            "install_stack_dump_handler()\n"
            "def deep_in_training_step():\n"
            "    time.sleep(60)\n"
            "deep_in_training_step()\n"
        )],
        env={**os.environ, ENV_STACK_FILE: path,
             "PYTHONPATH": os.getcwd()},
    )
    try:
        deadline = time.monotonic() + 10
        while not os.path.exists(path) and time.monotonic() < deadline:
            time.sleep(0.05)
        time.sleep(0.3)  # let the handler registration land
        stacks = collect_stacks(child.pid, path, timeout_s=5.0)
        assert "deep_in_training_step" in stacks, stacks
        # a second collection reads only the NEW dump
        stacks2 = collect_stacks(child.pid, path, timeout_s=5.0)
        assert "deep_in_training_step" in stacks2
    finally:
        child.kill()
        child.wait(timeout=120)


def test_collect_stacks_dead_process(tmp_path):
    path = str(tmp_path / "stacks.txt")
    child = subprocess.Popen([sys.executable, "-c", "pass"])
    child.wait(timeout=120)
    assert collect_stacks(child.pid, path, timeout_s=0.5) == ""


def test_install_without_env_is_noop(monkeypatch):
    monkeypatch.delenv(ENV_STACK_FILE, raising=False)
    assert install_stack_dump_handler() is None
