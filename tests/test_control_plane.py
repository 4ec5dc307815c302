"""Master/agent control-plane tests over in-process localhost gRPC.

Mirrors the reference's test strategy (SURVEY.md §4: real agent against an
in-process master + servicer; multi-node behavior by simulating node ranks
joining the rendezvous manager directly).
"""

import time

import pytest

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.master import messages as msg
from dlrover_tpu.master.job_master import JobMaster
from dlrover_tpu.master.rdzv_manager import (
    ElasticTrainingRendezvousManager,
    NetworkCheckRendezvousManager,
)
from dlrover_tpu.master.speed_monitor import SpeedMonitor


@pytest.fixture(scope="module")
def master():
    m = JobMaster(port=0, num_nodes=2)
    m.start()
    yield m
    m.stop()


@pytest.fixture()
def client(master):
    c = MasterClient(f"localhost:{master.port}", node_id=0)
    yield c
    c.close()


def test_rendezvous_two_nodes(master, client):
    client2 = MasterClient(f"localhost:{master.port}", node_id=1)
    assert client.join_rendezvous(0, 4) == 0
    state = client.get_comm_world(0)
    assert state.world == {}  # still forming: only 1 of 2 nodes
    client2.join_rendezvous(1, 4)
    state = client.get_comm_world(0)
    assert state.world == {0: 4, 1: 4}
    assert state.round == 1
    state2 = client2.get_comm_world(1)
    assert state2.world == {0: 4, 1: 4}
    client2.close()


def test_dynamic_sharding_and_recovery(master, client):
    client.create_dataset(
        msg.DatasetShardParams(
            dataset_name="train", dataset_size=100, shard_size=30
        )
    )
    seen = []
    t1 = client.get_task("train")
    t2 = client.get_task("train")
    seen += [(t1.start, t1.end), (t2.start, t2.end)]
    client.report_task("train", t1.task_id, success=True)
    # node 0 dies with t2 in flight -> shard requeues
    master.task_manager.recover_tasks(0)
    t3 = client.get_task("train")
    assert (t3.start, t3.end) == (t2.start, t2.end)
    # drain the rest
    tasks = []
    while True:
        t = client.get_task("train")
        if t.empty:
            break
        tasks.append(t)
        client.report_task("train", t.task_id)
    client.report_task("train", t3.task_id)
    covered = sorted(seen + [(t.start, t.end) for t in tasks])
    assert covered[0][0] == 0 and covered[-1][1] == 100


def test_shard_checkpoint_roundtrip(master, client):
    client.create_dataset(
        msg.DatasetShardParams(
            dataset_name="ckpt_ds", dataset_size=60, shard_size=20
        )
    )
    t = client.get_task("ckpt_ds")  # one in flight
    ckpt = client.get_shard_checkpoint("ckpt_ds")
    assert "todo" in ckpt.content
    client.restore_shard_checkpoint(ckpt)
    # after restore, the in-flight shard is pending again
    starts = set()
    while True:
        task = client.get_task("ckpt_ds")
        if task.empty:
            break
        starts.add(task.start)
        client.report_task("ckpt_ds", task.task_id)
    assert t.start in starts


def test_kv_store_and_barrier(master, client):
    client.kv_put("rdzv/addr", b"10.0.0.1:1234")
    assert client.kv_get("rdzv/addr") == b"10.0.0.1:1234"
    assert client.kv_get("missing") is None
    assert client.kv_add("barrier/x") == 1
    assert client.kv_add("barrier/x") == 2


def test_step_reports_and_job_status(master, client):
    now = time.time()
    for i, step in enumerate([1, 2, 3, 4]):
        master.speed_monitor.collect_global_step(
            step, now + i * 1.0, tokens=1000
        )
    status = client.get_job_status()
    assert status.global_step == 4
    assert status.speed == pytest.approx(1.0, rel=0.2)


def test_failure_report_actions(master, client):
    action = client.report_failure("oom", exit_code=137, level="process")
    assert action == "restart"
    action = client.report_failure("host gone", exit_code=1, level="node")
    assert action == "relaunch"


def test_network_check_bisection():
    manager = NetworkCheckRendezvousManager()
    manager.update_rdzv_params(4, 4, 60.0, 1)
    for rank in range(4):
        manager.join_rendezvous(rank, 4)
    # round 0: pairs (0,1) (2,3)
    _, g0, w0 = manager.get_comm_world(0)
    _, g1, w1 = manager.get_comm_world(2)
    assert set(w0) == {0, 1} and set(w1) == {2, 3}
    assert g0 != g1
    # pair (2,3) fails its probe
    manager.report_network_status(0, True, 1.0)
    manager.report_network_status(1, True, 1.0)
    manager.report_network_status(2, False, 1.0)
    manager.report_network_status(3, False, 1.0)
    faults, reason = manager.check_fault_node()
    assert set(faults) == {2, 3}
    # round 1: each suspect paired with a healthy node to bisect
    groups = manager._group_nodes(1)
    for suspect in (2, 3):
        group = [g for g in groups if suspect in g][0]
        assert any(r in (0, 1) for r in group), group
    # after round 1, only node 3 still fails -> node 3 is the bad host
    manager.report_network_status(2, True, 1.0)
    manager.report_network_status(3, False, 1.0)
    faults, _ = manager.check_fault_node()
    assert faults == [3]


def test_straggler_detection():
    manager = NetworkCheckRendezvousManager()
    manager.update_rdzv_params(4, 4, 60.0, 1)
    for rank in range(4):
        manager.join_rendezvous(rank, 1)
        manager.get_comm_world(rank)
    times = {0: 1.0, 1: 1.1, 2: 0.9, 3: 5.0}
    for rank, t in times.items():
        manager.report_network_status(rank, True, t)
    assert manager.get_stragglers() == [3]


def test_rdzv_node_unit_rounding():
    """With node_unit=2, a 3-node waiting set seals a 2-node world."""
    manager = ElasticTrainingRendezvousManager()
    manager.update_rdzv_params(
        min_nodes=2, max_nodes=4, waiting_timeout=0.0, node_unit=2
    )
    for rank in range(3):
        manager.join_rendezvous(rank, 4)
    time.sleep(0.01)
    _, _, world = manager.get_comm_world(0)
    assert len(world) == 2


def test_speed_monitor_goodput():
    monitor = SpeedMonitor()
    t0 = time.time()
    monitor.collect_global_step(1, t0)
    monitor.collect_global_step(2, t0 + 1)
    assert monitor.no_progress_for() < 5
    assert 0.0 <= monitor.goodput() <= 1.0


def test_network_check_odd_healthy_pool_no_singleton():
    """ADVICE low: round>=1 grouping with an odd healthy pool must not
    strand the last node in a singleton/empty comm world."""
    from dlrover_tpu.master.rdzv_manager import NetworkCheckRendezvousManager

    mgr = NetworkCheckRendezvousManager()
    mgr._rdzv_nodes = {r: 1 for r in range(5)}
    for r in range(5):
        mgr._node_status[r] = True  # all healthy -> pool of 5, no suspects
    groups = mgr._group_nodes(check_round=1)
    covered = sorted(r for g in groups for r in g)
    assert covered == list(range(5))
    assert all(len(g) >= 2 for g in groups)


_AGENT_WITH_NETWORK_CHECK = r"""
import sys
from jax._src import xla_bridge
from dlrover_tpu import run as launcher
from dlrover_tpu.agent.training_agent import ElasticAgent

start_workers = ElasticAgent._start_workers

def spy(self):
    print("AGENT_BACKEND_LIVE_AT_SPAWN",
          xla_bridge.backends_are_initialized(), flush=True)
    return start_workers(self)

ElasticAgent._start_workers = spy
sys.exit(launcher.run([
    "--standalone", "--network-check", "--monitor-interval", "0.2", "--",
    sys.executable, "-c", "print('TRAINER_RAN', flush=True)",
]))
"""


def test_network_check_leaves_the_agent_off_the_chip(cpu_child_env):
    """A chip belongs to one process, and the agent spawns the trainer:
    with ``--network-check`` the probes run in a child that has exited by
    then, and the agent has initialised no JAX backend of its own."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(cpu_child_env, PYTHONPATH=repo)
    env.pop("XLA_FLAGS", None)  # one CPU device keeps the probe short
    out = subprocess.run(
        [sys.executable, "-c", _AGENT_WITH_NETWORK_CHECK],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "AGENT_BACKEND_LIVE_AT_SPAWN False" in out.stdout
    assert "TRAINER_RAN" in out.stdout
    # The probe did run, in the child: its verdict reached the agent log.
    assert "node check: golden digest" in out.stderr



def test_sync_service_barrier_and_cluster_version(master, client):
    client2 = MasterClient(f"localhost:{master.port}", node_id=1)
    assert client.join_sync("init", need=2) is False
    assert client2.join_sync("init", need=2) is True
    # Late (re-)join of a finished barrier passes immediately.
    assert client.join_sync("init", need=2) is True
    assert client.sync_finished("init")

    # Cluster version: global = min over reporters, gated on the expected
    # reporter count (one early reporter must not advance it alone).
    assert client.report_cluster_version(3, expected=2) == 0
    assert client2.report_cluster_version(2, expected=2) == 2
    assert client.get_cluster_version() == 2
    # A dead node must not hold the version back or wedge barriers.
    client.join_sync("resize", need=2)
    master._handle_node_death(1)
    assert client.sync_finished("resize")
    assert client.report_cluster_version(3, expected=1) == 3
    client2.close()


def test_paral_config_update_and_versioning(master, client):
    from dlrover_tpu.master import messages as msg

    base = client.get_paral_config()
    master.servicer.update_paral_config(
        msg.ParalConfig(global_batch_size=64, grad_accum=2)
    )
    updated = client.get_paral_config()
    assert updated.version == base.version + 1
    assert updated.global_batch_size == 64


def test_master_kill_restart_agents_rejoin_monotonic_round(tmp_path):
    """Satellite: kill the master (stop; only state_path survives), start
    a fresh one from the same state file, and have the agents re-join over
    the wire — the re-formed world's rendezvous round must be strictly
    greater than any round the dead master sealed, so agents can tell the
    re-join from a stale world."""
    path = str(tmp_path / "master_state.json")
    first = JobMaster(port=0, num_nodes=2, min_nodes=1, state_path=path)
    first.start()
    try:
        a0 = MasterClient(f"localhost:{first.port}", node_id=0)
        a1 = MasterClient(f"localhost:{first.port}", node_id=1)
        a0.join_rendezvous(0, 4)
        a1.join_rendezvous(1, 4)
        sealed = a0.get_comm_world(0)
        assert sealed.round == 1 and sealed.world == {0: 4, 1: 4}
        first._state_store.save(first)
        a0.close()
        a1.close()
    finally:
        first.stop()  # the kill: all in-memory state gone

    fresh = JobMaster(port=0, num_nodes=2, min_nodes=1, state_path=path)
    fresh.start()
    try:
        # Restore alone already keeps the counter monotonic...
        assert fresh.rdzv_managers["elastic-training"]._rdzv_round >= 1
        a0 = MasterClient(f"localhost:{fresh.port}", node_id=0)
        a1 = MasterClient(f"localhost:{fresh.port}", node_id=1)
        a0.join_rendezvous(0, 4)
        a1.join_rendezvous(1, 4)
        resealed = a0.get_comm_world(0)
        # ...and the agents' re-join seals a STRICTLY newer round.
        assert resealed.world == {0: 4, 1: 4}
        assert resealed.round > sealed.round
        a0.close()
        a1.close()
    finally:
        fresh.stop()
