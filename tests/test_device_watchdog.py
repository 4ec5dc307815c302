"""Device-init watchdog: a trainer that hangs below Python before its
first step (hung PJRT / device init) must be restarted and, when
the hang persists, failed — instead of heartbeating healthily forever.

VERDICT r4 #2b.  The reference's hang detection
(``check_training_hang_operator.py:26-60``) only covers the stepping
case; the pre-first-step window (device init) is not covered there.
"""

import os
import sys
import time

import pytest

from dlrover_tpu.agent.training_agent import (
    ElasticAgent,
    ElasticLaunchConfig,
    RunResult,
)
from dlrover_tpu.master.job_master import JobMaster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A "trainer" that simulates a wedged device init: alive, heartbeating at
# the process level, but never reaching a first step (no metrics write).
HANG_SCRIPT = "import time\ntime.sleep(3600)\n"

# A trainer whose device init is slow but healthy: writes the metrics
# file (the first-step evidence) after a delay, then exits cleanly.
SLOW_OK_SCRIPT = """
import json, os, time
time.sleep(1.0)
path = os.environ["DLROVER_TPU_METRICS_FILE"]
with open(path + ".tmp", "w") as f:
    json.dump({"device_mem_gb": 0.0, "timestamp": time.time()}, f)
os.replace(path + ".tmp", path)
time.sleep(1.0)
"""


@pytest.fixture(autouse=True)
def _isolated_dirs(monkeypatch, tmp_path):
    monkeypatch.setenv("DLROVER_TPU_SOCKET_DIR", str(tmp_path / "socks"))
    monkeypatch.setenv("DLROVER_TPU_JOB", f"wd{os.getpid()}_{tmp_path.name}")


def _agent(master_port, script, **cfg_kwargs):
    config = ElasticLaunchConfig(
        min_nodes=1, max_nodes=1,
        monitor_interval=0.2,
        heartbeat_interval=0.5,
        rdzv_timeout=30.0,
        **cfg_kwargs,
    )
    return ElasticAgent(
        config, [sys.executable, "-c", script],
        f"localhost:{master_port}", node_id=0,
    )


@pytest.mark.slow  # chaos test: hung-init restart cycles with real timeouts
def test_hung_device_init_restarts_then_fails():
    master = JobMaster(num_nodes=1, heartbeat_timeout=3600.0)
    port = master.start()
    agent = _agent(
        port, HANG_SCRIPT, device_init_timeout=1.5, max_restarts=1,
    )
    try:
        t0 = time.monotonic()
        result = agent.run()
        elapsed = time.monotonic() - t0
        # One watchdog fire -> restart; second fire -> budget exhausted ->
        # FAILED.  Without the watchdog this would hang the full 3600s.
        assert result == RunResult.FAILED
        assert elapsed < 60
        # The master heard the device-init-hang diagnosis.
        node = master.node_manager.ensure_node(0)
        assert "device-init-hang" in (node.error or "")
    finally:
        agent.shutdown()
        master.stop()


def test_slow_but_healthy_init_not_killed():
    """First-step evidence before the timeout latches the watchdog off."""
    master = JobMaster(num_nodes=1, heartbeat_timeout=3600.0)
    port = master.start()
    # The metrics write lands ~3 s after spawn, well inside 10 s.
    agent = _agent(
        port, SLOW_OK_SCRIPT, device_init_timeout=10.0, max_restarts=0,
    )
    try:
        result = agent.run()
        assert result == RunResult.SUCCEEDED
        assert agent._first_step_confirmed
    finally:
        agent.shutdown()
        master.stop()


def test_watchdog_disabled_by_zero():
    agent = ElasticAgent(
        ElasticLaunchConfig(device_init_timeout=0.0),
        ["true"], "localhost:1",
    )
    agent._worker_started_wallclock = time.time() - 10_000
    assert not agent._device_init_hung()
