"""The one span system: parents, shared identifiers, profiler rows, the
tap, and the spans placed where the save, the persist, the resume and the
wait for a batch happen."""

import importlib.util
import json
import logging
import os
import pickle
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.agent.training_agent import (
    ENV_RESTART_COUNT,
    ElasticAgent,
    ElasticLaunchConfig,
    RunResult,
)
from dlrover_tpu.checkpoint.checkpointer import Checkpointer, StorageType
from dlrover_tpu.common import telemetry
from dlrover_tpu.common.log import default_logger
from dlrover_tpu.common.telemetry import (
    TelemetryRecorder,
    events_to_chrome_trace,
)
from dlrover_tpu.master import messages as msg
from dlrover_tpu.master.job_master import JobMaster
from dlrover_tpu.master.servicer import MasterServicer
from dlrover_tpu.master.timeline import JobTimeline
from dlrover_tpu.models.gpt2 import gpt2_config
from dlrover_tpu.parallel import rules as lr
from dlrover_tpu.trainer import elastic_trainer
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer, TrainerConfig
from dlrover_tpu.utils.profiler import StepPipelineCounters

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCH, SEQ = 8, 16


@pytest.fixture(autouse=True)
def _own_sockets_and_arena(tmp_path, monkeypatch):
    monkeypatch.setenv("DLROVER_TPU_SOCKET_DIR", str(tmp_path / "socks"))
    monkeypatch.setenv("DLROVER_TPU_JOB", f"span{os.getpid()}")
    yield
    for name in os.listdir("/dev/shm"):
        if f"span{os.getpid()}" in name:
            os.unlink(os.path.join("/dev/shm", name))


def _trainer(tmp_path=None, **cfg):
    model_config = gpt2_config(
        "124m", num_layers=1, d_model=32, num_heads=2, vocab_size=64,
        max_seq_len=SEQ, param_dtype=jnp.float32,
    )
    cfg.setdefault("report_every", 2)
    cfg.setdefault("ckpt_every", 1000)
    if tmp_path is not None:
        cfg["checkpoint_dir"] = str(tmp_path / "ckpt")
    return ElasticTrainer(
        model_config,
        TrainerConfig(
            global_batch_size=BATCH, seq_len=SEQ, learning_rate=1e-2, **cfg
        ),
        client=None,
    )


def _batches(n):
    rng = np.random.default_rng(0)
    out = []
    for _ in range(n):
        toks = rng.integers(0, 64, size=(BATCH, SEQ + 1), dtype=np.int32)
        out.append({"inputs": toks[:, :-1].copy(),
                    "targets": toks[:, 1:].copy()})
    return out


def _state_bytes(state) -> int:
    return sum(
        int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(state)
    )


def _named(events, name, **attrs):
    return [
        e for e in events if e[0] == name
        and all(e[4].get(k) == v for k, v in attrs.items())
    ]


# -- the span itself -----------------------------------------------------------


def test_parent_and_id_survive_wire_timeline_and_chrome_export(
    tap, tmp_path, monkeypatch
):
    r = TelemetryRecorder(enabled=True, source="trainer")
    with r.span("checkpoint", step=7):
        with r.span("checkpoint.d2h") as d2h:
            d2h.attrs["bytes"] = 12
            r.event("note")
    with r.span("restore", restart_count=2):
        with r.span("restore.read"):
            pass
    drained = r.drain()
    by_name = {e[0]: e[4] for e in drained}
    assert "parent" not in by_name["checkpoint"]
    assert by_name["checkpoint"]["id"] == "step:7"
    assert by_name["checkpoint.d2h"]["parent"] == "checkpoint"
    assert by_name["checkpoint.d2h"]["id"] == "step:7"
    assert by_name["note"]["parent"] == "checkpoint.d2h"
    assert by_name["note"]["id"] == "step:7"
    assert by_name["restore.read"]["parent"] == "restore"
    assert by_name["restore.read"]["id"] == "restart:2"
    # A program the job compiles late, here the digest at the first
    # ``sdc_check_every``, is one ``jax.compile`` event of that step's.
    trainer = _trainer(sdc_check_every=2)
    tap.take()
    trainer.fit(_batches(3), max_steps=2)
    (late,) = [
        e for e in _named(tap.take(), "jax.compile")
        if e[4]["fun_name"] == "jit(_digest_tree)"
    ]
    assert late[1] == "span"
    assert late[3] == pytest.approx(late[4]["seconds"], abs=1e-6)
    assert late[4]["parent"] == "step" and late[4]["id"] == "step:2"
    assert late[4]["cache"] == "off"
    drained.append(late)

    timeline = JobTimeline()
    servicer = MasterServicer(timeline=timeline)
    wire = pickle.dumps(msg.Envelope(
        node_id=3, payload=msg.TelemetryEvents(3, tuple(drained)),
    ))
    assert servicer.report(msg.safe_loads(wire)).success
    merged = {e[0]: e[4] for e in timeline.events(3)[3]}
    assert merged["checkpoint.d2h"] == by_name["checkpoint.d2h"]
    assert merged["restore.read"]["id"] == "restart:2"
    assert merged["jax.compile"] == late[4]

    # the Perfetto export, as ``tools/job_timeline.py`` writes it
    dump, out = tmp_path / "events.json", tmp_path / "trace.json"
    dump.write_text(json.dumps(timeline.events()))
    spec = importlib.util.spec_from_file_location(
        "_job_timeline", os.path.join(REPO, "tools", "job_timeline.py")
    )
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    monkeypatch.setattr(sys, "argv", [
        "job_timeline.py", "--input", str(dump), "--out", str(out),
    ])
    assert tool.main() == 0
    exported = json.loads(out.read_text())
    assert exported == json.loads(json.dumps(
        events_to_chrome_trace(timeline.events())
    ))
    rows = {
        e["name"]: e for e in exported["traceEvents"]
        if e["ph"] in ("X", "i")
    }
    assert rows["checkpoint.d2h"]["args"]["parent"] == "checkpoint"
    assert rows["checkpoint.d2h"]["args"]["id"] == "step:7"
    assert rows["checkpoint.d2h"]["args"]["bytes"] == 12
    assert rows["note"]["args"]["parent"] == "checkpoint.d2h"
    assert rows["jax.compile"]["ph"] == "X"
    assert rows["jax.compile"]["args"]["id"] == "step:2"
    assert rows["jax.compile"]["args"]["fun_name"] == "jit(_digest_tree)"
    assert rows["jax.compile"]["dur"] == pytest.approx(late[3] * 1e6)


def test_parent_is_the_span_open_on_the_same_thread():
    r = TelemetryRecorder(enabled=True)
    inside = threading.Event()
    release = threading.Event()

    def other():
        with r.span("persist", step=4):
            inside.set()
            release.wait(5)
            with r.span("persist.copy"):
                pass

    t = threading.Thread(target=other)
    with r.span("step", step=9):
        t.start()
        assert inside.wait(5)
        with r.span("dispatch"):
            pass
        release.set()
        t.join()
    by_name = {e[0]: e[4] for e in r.drain()}
    assert by_name["dispatch"]["parent"] == "step"
    assert by_name["dispatch"]["id"] == "step:9"
    assert by_name["persist.copy"]["parent"] == "persist"
    assert by_name["persist.copy"]["id"] == "step:4"
    assert "parent" not in by_name["persist"]


def test_an_open_span_is_a_row_of_the_profiler_trace(tmp_path):
    r = TelemetryRecorder(enabled=True)
    r.annotate_with(jax.profiler.TraceAnnotation)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with r.span("checkpoint", step=1):
            with r.span("checkpoint.d2h"):
                jnp.ones((8, 8)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = [
        os.path.join(d, f) for d, _, fs in os.walk(tmp_path) for f in fs
        if f.endswith(".xplane.pb")
    ]
    rows = [
        e.name
        for plane in jax.profiler.ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith(telemetry.TRACE_PREFIX)
    ]
    assert sorted(rows) == ["dlrover:checkpoint", "dlrover:checkpoint.d2h"]
    # and the ring holds them as ever
    assert [e[0] for e in r.drain()] == ["checkpoint.d2h", "checkpoint"]


def test_a_recorder_without_a_factory_imports_no_jax():
    code = (
        "import sys\n"
        "from dlrover_tpu.common import telemetry\n"
        "r = telemetry.TelemetryRecorder(enabled=True, source='agent')\n"
        "with r.span('persist', step=1):\n"
        "    with r.span('persist.copy'):\n"
        "        pass\n"
        "assert [e[0] for e in r.drain()] == ['persist.copy', 'persist']\n"
        "assert 'jax' not in sys.modules, 'telemetry pulled jax in'\n"
    )
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


class _Client:
    def __init__(self):
        self.batches = []

    def report_telemetry(self, events, dropped=0):
        self.batches.append(list(events))


def test_a_tap_keeps_spans_across_a_ship():
    r = TelemetryRecorder(enabled=True)
    r.event("before")
    client = _Client()
    with r.open_tap() as held:
        with r.span("checkpoint", step=2):
            pass
        assert r.ship(client) == 2 and len(r) == 0
        r.event("compile", duration_s=1.0)
        assert [e[0] for e in held.take()] == ["checkpoint", "compile"]
        assert held.take() == []
        r.event("later")
        assert r.drain()[-1][0] == "later"  # the ring is its own reader's
        assert [e[0] for e in held.take()] == ["later"]
    r.event("after_close")
    assert held.take() == []
    # bounded: a tap nobody takes from keeps its newest events
    with r.open_tap(size=2) as small:
        for i in range(5):
            r.event("e", i=i)
        assert [e[4]["i"] for e in small.take()] == [3, 4]


def test_pipeline_event_log_is_a_window_and_the_totals_are_not():
    counters = StepPipelineCounters()
    counters.EVENT_WINDOW = 8
    counters.reset()
    for step in range(1, 21):
        counters.record_dispatch(step, 0.001)
        with counters.host_block("metrics", steps=(step,)):
            pass
    assert len(counters.events) == 8
    assert counters.events[-1].steps == (20,)
    summary = counters.summary()
    assert summary["dispatch_count"] == 20
    assert summary["host_block_count"] == summary["sync_block_count"] == 20


# -- the save --------------------------------------------------------------------


def test_a_save_is_split_where_the_work_happens(tmp_path, tap, small_pieces):
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None
    )
    trainer = _trainer(tmp_path, ckpt_every=2, metrics_lag=3)
    arena = trainer._ckpt._engine._shm
    inner = trainer.save_checkpoint
    staged, compiled_in = [], []

    def save_then_wait_for_the_persist():
        # The local saver holds the arena while it persists: a next save
        # that finds it held is skipped ("shm busy"), whatever the load.
        before = len(compiles)
        inner()
        compiled_in.append(len(compiles) - before)
        staged.append(dict(arena._staged))
        assert trainer._ckpt.wait(timeout=60)

    trainer.save_checkpoint = save_then_wait_for_the_persist
    # (a job's start hides the preparation; this one is too short to)
    preparing = trainer._ckpt._engine._preparing
    preparing.join(timeout=60)
    assert not preparing.is_alive()
    trainer.fit(_batches(6), max_steps=4)
    trainer.close()
    events = tap.take()
    size = _state_bytes(trainer.state)
    saves = _named(events, "checkpoint")
    assert [e[4]["step"] for e in saves] == [2, 4]
    for save in saves:
        group = save[4]["id"]
        children = [
            e for e in events
            if e[4].get("parent") == "checkpoint" and e[4].get("id") == group
        ]
        names = sorted(e[0] for e in children)
        # No save maps the arena or settles its pages: the trainer's
        # start did (``checkpoint.prepare``, below).
        assert names == [
            "checkpoint.d2h", "checkpoint.drain", "checkpoint.shm_write"
        ]
        assert sum(e[3] for e in children) <= save[3]
        (d2h,) = _named(children, "checkpoint.d2h")
        assert d2h[4]["bytes"] == size and d2h[4]["shards"] > 0
        assert d2h[4]["path"] == "staged" and d2h[4]["groups"] > 0
        assert d2h[4]["minflt"] >= 0
        assert 0 <= d2h[4]["arena_copy_s"] <= d2h[3]
        (write,) = _named(children, "checkpoint.shm_write")
        assert write[4]["bytes"] == size
        # what the device still had in flight is read inside the drain
        assert _named(events, "metrics-flush", parent="checkpoint.drain",
                      id=group)
    # The first save alone says how long it waited for the preparation.
    assert [("arena_wait_s" in e[4]) for e in saves] == [True, False]
    assert 0.0 <= saves[0][4]["arena_wait_s"] < 0.05
    # The arena is made, written once and settled at the trainer's start,
    # on the path a save would take, and the span says so.
    (prepare,) = _named(events, "checkpoint.prepare")
    assert prepare[4]["id"] == "restart:0" and "parent" not in prepare[4]
    assert prepare[4]["created"] is True and prepare[4]["minflt"] >= 0
    (arena_span,) = _named(events, "checkpoint.arena")
    assert arena_span[4]["created"] is True and arena_span[4]["ahead"] is True
    assert arena_span[4]["bytes"] == prepare[4]["bytes"] > size
    (settle,) = _named(events, "checkpoint.arena_settle")
    assert settle[4]["bytes"] == arena_span[4]["bytes"]
    for span in (arena_span, settle):
        assert span[4]["parent"] == "checkpoint.prepare"
        assert span[4]["id"] == "restart:0"
    assert not _named(events, "checkpoint.prepare_skipped")
    assert not _named(events, "checkpoint.skip")
    assert not _named(events, "checkpoint.d2h_fallback")
    # No save compiles or plans anything: the programs and pieces are the
    # preparation's.
    assert compiled_in == [0, 0]
    assert staged[0] and staged[1] == staged[0]
    # The staged programs are compiled inside ``checkpoint.prepare``:
    # three stages a program, one executable each.
    programs = sum(len(plan.programs) for plan in staged[0].values() if plan)
    assert prepare[4]["programs"] == programs
    for stage in ("trace", "lower", "backend"):
        spans = _named(events, f"compile.{stage}", fun_name="_flat_pieces")
        assert len(spans) == programs > 0
        assert all(e[4]["parent"] == "checkpoint.prepare"
                   and e[4]["id"] == "restart:0" for e in spans)
    built = [
        e for e in _named(events, "jax.compile", parent="compile.backend")
        if e[2] >= prepare[2] and e[2] + e[3] <= prepare[2] + prepare[3]
        and e[4]["id"] == "restart:0"
    ]
    assert len(built) >= programs
    assert not _named(events, "jax.compile", id="step:2")
    assert not _named(events, "jax.compile", id="step:4")
    assert sum(
        e[3] for e in events if e[4].get("parent") == "checkpoint.prepare"
    ) <= prepare[3]


def test_a_persist_has_its_four_children_and_says_persisted(tmp_path, tap):
    ckpt = Checkpointer(
        str(tmp_path / "ckpt"), host_index=0, num_hosts=1, local_saver=True
    )
    state = {"w": jnp.ones((64, 64)), "b": jnp.zeros((64,))}
    assert ckpt.save_checkpoint(5, state, StorageType.DISK)
    assert ckpt.wait(timeout=30)
    ckpt.close()
    events = tap.take()
    (persist,) = _named(events, "persist")
    assert persist[4]["step"] == 5 and persist[4]["id"] == "step:5"
    assert persist[4]["bytes"] == _state_bytes(state)
    children = [e for e in events if e[4].get("parent") == "persist"]
    assert {e[0] for e in children if e[1] == "span"} == {
        "persist.copy", "persist.crc", "persist.write", "persist.commit"
    }
    assert all(e[4]["id"] == "step:5" for e in children)
    assert sum(e[3] for e in children) <= persist[3]
    (said,) = _named(events, "persisted")
    assert said[1] == "event" and said[4]["step"] == 5
    assert said[4]["bytes"] == persist[4]["bytes"]


def test_the_agents_saver_records_into_the_recorder_it_is_given(tmp_path):
    from dlrover_tpu.checkpoint.engine import shm_name
    from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver
    from dlrover_tpu.checkpoint.shm_handler import SharedMemoryHandler

    agent = TelemetryRecorder(enabled=True, source="agent")
    saver = AsyncCheckpointSaver(
        str(tmp_path / "ckpt"), host_index=0, num_hosts=1, recorder=agent
    )
    arena = SharedMemoryHandler(shm_name(0))
    arena.save_state_dict({"w": np.ones(16, np.float32)}, step=6)
    with telemetry.recorder().open_tap() as process_wide:
        assert saver.save_step_checkpoint(6)
        assert not [e for e in process_wide.take() if "persist" in e[0]]
    saver.stop()
    arena.close(unlink=True)
    events = agent.drain()
    (persist,) = _named(events, "persist")
    assert persist[4]["src"] == "agent" and persist[4]["bytes"] == 64
    assert _named(events, "persisted", step=6)


class _Lines(logging.Handler):
    def __init__(self):
        super().__init__()
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


def test_a_save_against_a_held_lock_is_one_skip_event_and_a_log_line(
    tmp_path, tap
):
    ckpt = Checkpointer(
        str(tmp_path / "ckpt"), host_index=0, num_hosts=1, local_saver=True
    )
    lines = _Lines()
    default_logger.addHandler(lines)
    saver_lock = ckpt._engine._saver._lock
    held, release = threading.Event(), threading.Event()

    def hold():
        assert saver_lock.acquire(blocking=True)
        held.set()
        release.wait(10)
        saver_lock.release()

    holder = threading.Thread(target=hold)
    holder.start()
    try:
        assert held.wait(5)
        assert ckpt.save_checkpoint(3, {"w": jnp.ones(4)}) is False
    finally:
        release.set()
        holder.join()
        default_logger.removeHandler(lines)
        ckpt.close()
    (skip,) = _named(tap.take(), "checkpoint.skip")
    assert skip[1] == "event"
    assert skip[4]["step"] == 3 and skip[4]["reason"] == "shm_busy"
    assert any("skip memory save" in line for line in lines.lines)


def test_skips_and_persisted_steps_reach_the_master_gauges():
    timeline = JobTimeline()
    servicer = MasterServicer(timeline=timeline)
    events = (
        ("checkpoint.skip", "event", 0.0, 0.0,
         {"step": 14, "reason": "shm_busy"}),
        ("persisted", "event", 0.0, 0.0, {"step": 7}),
        ("persisted", "event", 0.0, 0.0, {"step": 14}),
        ("checkpoint.d2h_fallback", "event", 0.0, 0.0,
         {"step": 21, "reason": "hbm_headroom"}),
        ("checkpoint.prepare_skipped", "event", 0.0, 0.0,
         {"reason": "shm_busy", "restart_count": 1}),
    )
    wire = pickle.dumps(msg.Envelope(
        node_id=2, payload=msg.TelemetryEvents(2, events),
    ))
    assert servicer.report(msg.safe_loads(wire)).success
    text = timeline.render_metrics()
    assert "dlrover_checkpoint_skipped_total 1" in text
    assert "dlrover_checkpoint_d2h_fallback_total 1" in text
    assert "dlrover_checkpoint_prepare_skipped_total 1" in text
    assert 'dlrover_persisted_step{node="2"} 14' in text
    assert "# HELP dlrover_persisted_step " in text


# -- the trainer: batches, start-up, restore, read-only views -----------------------


@pytest.mark.parametrize("prefetch", [0, 2])
def test_the_wait_for_every_batch_is_spanned(prefetch, tap):
    trainer = _trainer(prefetch_to_device=prefetch)
    trainer.fit(_batches(6), max_steps=4)
    events = tap.take()
    waits = _named(events, "data_wait")
    assert [e[4]["step"] for e in waits][:4] == [1, 2, 3, 4]
    assert all(e[1] == "span" and e[4]["id"] == f"step:{e[4]['step']}"
               for e in waits)
    steps = _named(events, "step")
    assert [e[4]["id"] for e in steps] == [f"step:{n}" for n in (1, 2, 3, 4)]
    assert not [e for e in events if e[4].get("source") == "modeled"]


def test_start_up_and_restore_share_the_restart_identifier(
    tmp_path, tap, monkeypatch
):
    first = _trainer(tmp_path, ckpt_every=2)
    first.fit(_batches(4), max_steps=2)
    first.close()
    tap.take()
    monkeypatch.setenv(ENV_RESTART_COUNT, "3")
    monkeypatch.setattr(elastic_trainer, "_PROCESS_START_BOOKED", False)
    resumed = _trainer(tmp_path)
    assert resumed.step == 2
    resumed.close()
    events = tap.take()
    size = _state_bytes(resumed.state)
    for name in ("startup.runtime", "startup.mesh", "startup.build",
                 "startup.init", "restore"):
        (e,) = _named(events, name)
        assert e[1] == "span" and e[4]["restart_count"] == 3
        assert e[4]["id"] == "restart:3"
    (runtime,) = _named(events, "startup.runtime")
    (mesh,) = _named(events, "startup.mesh")
    # process start (the OS's) to devices listed ends where the mesh begins
    assert runtime[3] > 0 and runtime[2] + runtime[3] <= mesh[2] + 0.05
    (read,) = _named(events, "restore.read")
    (place,) = _named(events, "restore.place")
    assert read[4]["parent"] == place[4]["parent"] == "restore"
    assert read[4]["id"] == place[4]["id"] == "restart:3"
    assert read[4]["from"] == "shm" and read[4]["bytes"] == size
    (restore,) = _named(events, "restore")
    assert read[3] + place[3] <= restore[3]
    # once per process: a second trainer does not book the start again
    _trainer().close()
    assert not _named(tap.take(), "startup.runtime")


def test_a_restore_from_storage_says_so(tmp_path, tap):
    ckpt = Checkpointer(
        str(tmp_path / "ckpt"), host_index=0, num_hosts=1, local_saver=True
    )
    state = {"w": jnp.arange(32.0)}
    assert ckpt.save_checkpoint(9, state, StorageType.DISK)
    assert ckpt.wait(timeout=30)
    ckpt._engine._shm.close(unlink=True)
    tap.take()
    step, loaded = ckpt.load_checkpoint(state_template=state)
    ckpt.close()
    assert step == 9
    events = tap.take()
    (read,) = _named(events, "restore.read")
    assert read[4]["from"] == "storage" and read[4]["bytes"] == 32 * 4
    assert _named(events, "restore.place")


def test_trainer_says_whether_it_checkpoints(tmp_path):
    without = _trainer()
    assert without.checkpointing is False
    with_dir = _trainer(tmp_path)
    assert with_dir.checkpointing is True
    with_dir.close()


def test_trainer_hands_out_its_logical_axis_rules():
    assert _trainer().logical_axis_rules is lr.DEFAULT_RULES
    custom = tuple(lr.DEFAULT_RULES)
    model_config = gpt2_config(
        "124m", num_layers=1, d_model=32, num_heads=2, vocab_size=64,
        max_seq_len=SEQ, param_dtype=jnp.float32,
    )
    trainer = ElasticTrainer(
        model_config,
        TrainerConfig(global_batch_size=BATCH, seq_len=SEQ),
        rules=custom, client=None,
    )
    assert trainer.logical_axis_rules is custom


def test_the_compiled_step_program_hands_out_its_text():
    trainer = _trainer()
    assert trainer.train.compiled_step_text() == ""
    trainer.train.aot_compile()
    text = trainer.train.compiled_step_text()
    assert text.startswith("HloModule") and "op_name=" in text


def test_every_executable_is_booked_once_and_none_with_telemetry_off(tap):
    """One listener a process, however many trainers start in it; with
    telemetry off it books nothing and the staged compile gives the same
    program."""
    from dlrover_tpu.trainer import train_lib

    compiled = []

    def count(name, seconds, fun_name="", **_):
        if name == "/jax/core/compile/backend_compile_duration":
            compiled.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(count)
    recorder = telemetry.recorder()
    starts = []
    try:
        for recording in (True, True, False):
            train_lib.reset_build_cache()
            recorder.configure(enabled=recording)
            tap.take()
            del compiled[:]
            # every start from this one line: the compiled text names the
            # lines of its callers
            trainer = _trainer(warmup_compile=True)
            starts.append((trainer, tap.take(), list(compiled)))
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
        recorder.configure(enabled=True)
        train_lib.reset_build_cache()
    # a second listener would book every executable of the second start twice
    (first, *_), (second, events, built), (unseen, none, built_unseen) = starts
    booked = [e[4]["fun_name"] for e in _named(events, "jax.compile")]
    assert booked == built and booked.count("jit(_train_step)") == 1
    (whole,) = _named(events, "compile")
    assert whole[4]["cached"] is False and whole[4]["cache"] == "off"
    # off: nothing is booked, and the parts are still the trainer's to log
    assert none == [] and built_unseen.count("jit(_train_step)") == 1
    assert unseen.train.compile_parts["cache"] == "off"
    assert (
        unseen.train.compiled_step_text()
        == second.train.compiled_step_text()
        == first.train.compiled_step_text()
    )


def test_the_cache_outcome_is_the_executable_s_own_thread_s(tap):
    """What the chip says and the CPU cannot (its persistent cache stays
    off): jax's own sequence for a read from the cache, with an executable
    of another thread built in the middle of it."""
    from dlrover_tpu.runtime import compile_cache

    said = compile_cache._on_monitoring
    built = "/jax/core/compile/backend_compile_duration"
    before = compile_cache.stats()
    tap.take()
    said("/jax/compilation_cache/cache_hits")
    other = threading.Thread(
        target=said, args=(built, 0.25), kwargs={"fun_name": "jit(other)"}
    )
    other.start()
    other.join()
    said("/jax/compilation_cache/compile_time_saved_sec", 120.0)
    said("/jax/compilation_cache/cache_retrieval_time_sec", 1.5)
    said(built, 2.0, fun_name="jit(read_back)")
    said("/jax/compilation_cache/cache_misses")
    said(built, 3.0, fun_name="jit(written)")
    said(built, 4.0, fun_name="jit(uncached)")
    after = compile_cache.stats()
    assert (after["hits"] - before["hits"],
            after["misses"] - before["misses"]) == (1, 1)
    booked = {
        e[4]["fun_name"]: (e[3], {
            k: v for k, v in e[4].items() if k in ("cache", "retrieval_s")
        })
        for e in _named(tap.take(), "jax.compile")
    }
    assert booked == {
        "jit(other)": (0.25, {"cache": "off"}),
        "jit(read_back)": (2.0, {"cache": "hit", "retrieval_s": 1.5}),
        "jit(written)": (3.0, {"cache": "miss"}),
        "jit(uncached)": (4.0, {"cache": "off"}),
    }


# -- the agent's failure path ---------------------------------------------------------


def test_failure_and_restart_spans_share_the_restart_count():
    script = (
        "import os, sys; "
        f"sys.exit(1 if os.environ['{ENV_RESTART_COUNT}'] == '0' else 0)"
    )
    master = JobMaster(num_nodes=1, heartbeat_timeout=3600.0)
    port = master.start()
    agent = ElasticAgent(
        ElasticLaunchConfig(
            min_nodes=1, max_nodes=1, monitor_interval=0.1,
            heartbeat_interval=0.2, rdzv_timeout=30.0, max_restarts=2,
        ),
        [sys.executable, "-c", script], f"localhost:{port}", node_id=0,
    )
    # The heartbeat ships the agent's ring to the master every 0.2 s (and
    # the master drops a finished node's stream): the tap is how a reader
    # in this process sees all of it.
    try:
        with agent.telemetry.open_tap() as held:
            assert agent.run() == RunResult.SUCCEEDED
            events = held.take()
    finally:
        agent.shutdown()
        master.stop()
    names = [e[0] for e in events]
    order = ["process_exit", "failure.save", "failure.report",
             "restart.stacks", "restart", "restart.stop", "restart.spawn"]
    at = [names.index(n) for n in order]
    # spans record when they close: spawn closes after the rendezvous in it
    assert at == sorted(at)
    for name in order[1:]:
        (e,) = _named(events, name)
        assert e[4]["restart_count"] == 1 and e[4]["id"] == "restart:1"
        assert e[4]["src"] == "agent"
    rendezvous = _named(events, "rendezvous", parent="restart.spawn")
    assert len(rendezvous) == 1 and rendezvous[0][4]["id"] == "restart:1"
