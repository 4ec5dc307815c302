"""Measured device-time attribution, calibration loop, and the HTTP plane.

Covers the PR-14 observability stack end to end: the stdlib Chrome-trace
parser (``utils/device_profile``), the measured-row + calibration wire
emission, the master's :class:`CalibrationLedger` (EWMA math, servicer
routing, state-snapshot survival), ``auto/tune.apply_calibration``
re-ranking, the ``/metrics`` + ``/timeline`` + ``/healthz`` HTTP plane
(byte parity with the RPC render, seam-injected 503s), the
:class:`StepRegressionOperator` sentinel, and one real profiled CPU run
through :class:`ElasticTrainer` under the no-retrace contract.
"""

import gzip
import json
import os
import urllib.error
import urllib.request

import numpy as np
import pytest

import trace_asserts
from dlrover_tpu.common import faults, telemetry
from dlrover_tpu.master import messages as msg
from dlrover_tpu.master.calibration import CalibrationLedger
from dlrover_tpu.master.diagnosis import (
    ActionType,
    DiagnosisContext,
    StepRegressionOperator,
)
from dlrover_tpu.master.http_plane import MetricsHTTPServer
from dlrover_tpu.master.node_manager import NodeManager
from dlrover_tpu.master.servicer import MasterServicer
from dlrover_tpu.master.speed_monitor import SpeedMonitor
from dlrover_tpu.master.timeline import JobTimeline
from dlrover_tpu.utils import device_profile
from dlrover_tpu.utils.device_profile import (
    DeviceProfiler,
    DeviceWindow,
    emit_measured_phases,
    find_trace_file,
    modeled_kind_seconds,
    overlap_seconds,
    parse_device_trace,
)
from dlrover_tpu.utils.profiler import StepPipelineCounters


@pytest.fixture(autouse=True)
def _isolated(monkeypatch, tmp_path):
    """Unique job tag + socket dir per test (shared-shm hygiene), and no
    fault plan leaking across tests."""
    monkeypatch.setenv(
        "DLROVER_TPU_JOB", f"dp{os.getpid()}_{tmp_path.name}"
    )
    monkeypatch.setenv("DLROVER_TPU_SOCKET_DIR", str(tmp_path / "socks"))
    faults.reset()
    yield
    faults.reset()


# -- trace parsing ----------------------------------------------------------


def _meta(pid, name):
    return {"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": name}}


def _op(pid, name, ts, dur):
    return {"ph": "X", "pid": pid, "tid": 1, "name": name,
            "ts": ts, "dur": dur}


def _write_trace(path, events, gz=False):
    body = json.dumps({"traceEvents": events})
    if gz:
        with gzip.open(path, "wt") as f:
            f.write(body)
    else:
        with open(path, "w") as f:
            f.write(body)
    return str(path)


def test_parse_prefers_real_device_plane_over_host(tmp_path):
    # A TPU pid exists, so the /host:CPU plane (including an HLO-shaped
    # name living there) must not count.
    events = [
        _meta(1, "/device:TPU:0"),
        _meta(2, "/host:CPU"),
        _op(1, "dot.1", 0, 100),
        _op(1, "fusion.2", 100, 100),
        _op(1, "all-reduce.3", 150, 100),
        _op(2, "dot.99", 0, 500),
        _op(2, "PjitFunction(f)", 0, 500),
    ]
    path = _write_trace(tmp_path / "t.trace.json", events)
    w = parse_device_trace(path)
    assert w is not None
    assert w.op_count == 3
    assert w.seconds("compute") == pytest.approx(200e-6)
    assert w.seconds("collective") == pytest.approx(100e-6)
    assert w.device_total_s == pytest.approx(300e-6)
    # all-reduce [150,250] overlaps fusion [100,200] for 50us of its 100us.
    assert w.overlap_fraction == pytest.approx(0.5)


def test_parse_cpu_fallback_filters_host_scaffolding(tmp_path):
    # No accelerator pid: fall back to the CPU plane, but only HLO-shaped
    # rows count — host scaffolding, our own dlrover:* span rows, jit_*
    # and anonymous while/digit envelopes are all rejected.
    events = [
        _meta(7, "/host:CPU"),
        _op(7, "PjitFunction(f)", 0, 999),
        _op(7, "$profiler.py:91 start_trace", 0, 999),
        _op(7, "TfrtCpuExecutable::Execute", 0, 999),
        _op(7, "dlrover:step", 0, 999),
        _op(7, "jit_train_step", 0, 999),
        _op(7, "while.3", 0, 999),
        _op(7, "42", 0, 999),
        _op(7, "dot.4", 0, 50),
        _op(7, "broadcast_add_fusion", 50, 25),
        _op(7, "reduce-window", 75, 25),
    ]
    path = _write_trace(tmp_path / "t.trace.json", events)
    w = parse_device_trace(path)
    assert w is not None
    assert w.op_count == 3
    assert w.phases == {"compute": pytest.approx(100e-6)}
    assert w.overlap_fraction == 0.0  # no collectives -> nothing exposed


def test_parse_malformed_traces_degrade_to_none(tmp_path):
    junk = tmp_path / "junk.trace.json"
    junk.write_text("this is not json{{{")
    assert parse_device_trace(str(junk)) is None
    # Valid JSON but zero device ops is equally a no-window.
    empty = _write_trace(
        tmp_path / "empty.trace.json", [_meta(1, "/host:CPU")]
    )
    assert parse_device_trace(empty) is None
    assert parse_device_trace(str(tmp_path / "missing.trace.json")) is None


def test_find_trace_file_descends_and_gzip_roundtrips(tmp_path):
    # The profiler nests its output under plugins/profile/<ts>/.
    nest = tmp_path / "plugins" / "profile" / "2026_08_05"
    nest.mkdir(parents=True)
    events = [_meta(1, "/device:TPU:0"), _op(1, "dot.1", 0, 10)]
    _write_trace(nest / "host.trace.json.gz", events, gz=True)
    found = find_trace_file(str(tmp_path))
    assert found and found.endswith(".trace.json.gz")
    w = parse_device_trace(found)
    assert w is not None and w.op_count == 1


def test_overlap_seconds_merges_before_intersecting():
    compute = [(0.0, 1.0), (0.5, 2.0)]  # merges to (0, 2)
    collective = [(1.5, 3.0)]
    assert overlap_seconds(compute, collective) == pytest.approx(0.5)
    assert overlap_seconds([(0.0, 1.0)], [(2.0, 3.0)]) == 0.0
    assert overlap_seconds([], [(0.0, 1.0)]) == 0.0


def test_modeled_kind_seconds_maps_phase_plan_rows():
    rows = [
        {"phase": "accumulate", "dur": 0.3},
        {"phase": "reduce", "dur": 0.1},
        {"phase": "update", "dur": 0.05},
        {"phase": "warp_drive", "dur": 9.0},  # unknown phase: ignored
    ]
    out = modeled_kind_seconds(rows)
    assert out == {
        "compute": pytest.approx(0.35), "collective": pytest.approx(0.1),
    }


# -- measured-row + calibration emission ------------------------------------


def _drain_enabled_recorder():
    rec = telemetry.recorder()
    was = rec.enabled
    rec.configure(enabled=True)
    rec.drain()
    return rec, was


def test_emit_measured_phases_books_rows_and_calibration():
    rec, was = _drain_enabled_recorder()
    try:
        window = DeviceWindow(
            phases={"compute": 0.2, "collective": 0.1},
            overlap_fraction=0.5, device_total_s=0.3, op_count=3,
        )
        rows = emit_measured_phases(
            window, step=50, t_span=10.0, wall_s=0.35,
            modeled_rows=[
                {"phase": "accumulate", "dur": 0.25},
                {"phase": "reduce", "dur": 0.05},
            ],
            cache_key="abc123",
        )
        events = rec.drain()
    finally:
        rec.configure(enabled=was)
    assert rows == 2
    measured = [
        e for e in events if e[4].get("source") == "measured"
    ]
    assert [e[0] for e in measured] == ["compute", "collective"]
    for name, kind, _t, dur, attrs in measured:
        assert kind == "span"
        assert attrs["src"] == "device"
        assert attrs["step"] == 50
        assert attrs["overlap"] == pytest.approx(0.5)
    # Sequential layout: collective starts where compute ends.
    assert measured[0][3] == pytest.approx(0.2)
    assert measured[1][3] == pytest.approx(0.1)
    calib = [e for e in events if e[0] == "calibration"]
    assert len(calib) == 1
    attrs = calib[0][4]
    assert attrs["cache_key"] == "abc123"
    assert attrs["measured_compute"] == pytest.approx(0.2)
    assert attrs["modeled_compute"] == pytest.approx(0.25)
    assert attrs["measured_collective"] == pytest.approx(0.1)
    assert attrs["modeled_collective"] == pytest.approx(0.05)
    assert attrs["wall_s"] == pytest.approx(0.35)
    # The device rows render on their own Perfetto thread per node.
    trace = telemetry.events_to_chrome_trace({0: events})
    threads = {
        e["args"]["name"] for e in trace["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    assert "device" in threads


def test_emit_measured_phases_noop_when_recorder_disabled():
    rec = telemetry.recorder()
    was = rec.enabled
    rec.configure(enabled=False)
    try:
        window = DeviceWindow(
            phases={"compute": 1.0}, overlap_fraction=0.0,
            device_total_s=1.0, op_count=1,
        )
        assert emit_measured_phases(
            window, step=1, t_span=0.0, wall_s=1.0, modeled_rows=[],
        ) == 0
    finally:
        rec.configure(enabled=was)


def test_profiler_cadence_and_disable_latch(monkeypatch, tmp_path):
    off = DeviceProfiler(0)
    assert not off.wants(100)
    assert not off.arm(100)

    prof = DeviceProfiler(10, trace_dir=str(tmp_path))
    assert prof.wants(10) and prof.wants(20) and not prof.wants(11)

    import jax

    def _boom(*a, **k):
        raise RuntimeError("no profiler backend")

    monkeypatch.setattr(jax.profiler, "start_trace", _boom)
    assert not prof.arm(10)
    # Latched: the cadence no longer even wants a window.
    assert prof._disabled and not prof.wants(20)
    assert prof.finish() is None  # no open window -> harmless


# -- calibration ledger ------------------------------------------------------


def test_calibration_ledger_ewma_and_aggregate():
    ledger = CalibrationLedger()
    ledger.observe("k1", "compute", 2.0, 1.0)
    assert ledger.ratios("k1")["compute"] == pytest.approx(2.0)  # seeds
    ledger.observe("k1", "compute", 1.0, 1.0)
    # 2.0 + 0.3 * (1.0 - 2.0)
    assert ledger.ratios("k1")["compute"] == pytest.approx(1.7)
    # Non-positive sides carry no signal.
    ledger.observe("k1", "collective", 0.0, 1.0)
    ledger.observe("k1", "collective", 1.0, 0.0)
    assert "collective" not in ledger.ratios("k1")
    # Empty key buckets under "uncacheable".
    ledger.observe("", "compute", 3.0, 1.0)
    assert ledger.ratios("uncacheable")["compute"] == pytest.approx(3.0)
    assert len(ledger) == 2
    # Aggregate = mean over keys: (1.7 + 3.0) / 2.
    assert ledger.ratios()["compute"] == pytest.approx(2.35)
    assert ledger.observations("k1")["compute"] == 2


def test_calibration_ledger_state_roundtrip():
    ledger = CalibrationLedger()
    ledger.observe("k", "compute", 1.5, 1.0)
    ledger.observe("k", "collective", 2.0, 1.0)
    snap = json.loads(json.dumps(ledger.state()))  # must be JSON-able
    fresh = CalibrationLedger()
    fresh.restore(snap)
    assert fresh.ratios("k") == pytest.approx(ledger.ratios("k"))
    assert fresh.observations("k") == ledger.observations("k")
    # Empty snapshot is a no-op, not a wipe.
    fresh.restore({})
    assert fresh.ratios("k")["compute"] == pytest.approx(1.5)


def test_apply_calibration_reranks_comm_heavy_candidate():
    from dlrover_tpu.auto import tune
    from dlrover_tpu.runtime.mesh import ParallelConfig

    ledger = CalibrationLedger()
    # Measurement says collectives run 2x slower than the model prices.
    ledger.observe("k", "collective", 2.0, 1.0)

    a = tune.Candidate(ParallelConfig(), "none")
    a.est_step_time, a.est_comm_time = 1.0, 0.6   # comm-heavy: wins on paper
    b = tune.Candidate(ParallelConfig(), "none")
    b.est_step_time, b.est_comm_time = 1.05, 0.05
    rej = tune.Candidate(ParallelConfig(), "none")
    rej.rejected = "oom"

    assert a.est_step_time < b.est_step_time  # pre-calibration ranking
    tune.apply_calibration([a, b, rej], ledger)
    # a: 0.4 * 1.0 + 0.6 * 2.0 = 1.6; b: 1.0 + 0.05 * 2.0 = 1.10
    assert a.est_step_time == pytest.approx(1.6)
    assert b.est_step_time == pytest.approx(1.10)
    assert b.est_step_time < a.est_step_time  # ranking flipped
    assert b.est_comm_time == pytest.approx(0.1)
    assert rej.est_step_time == pytest.approx(float("inf"))
    # None / empty ledgers are no-ops.
    tune.apply_calibration([b], None)
    tune.apply_calibration([b], CalibrationLedger())
    assert b.est_step_time == pytest.approx(1.10)


# -- servicer routing --------------------------------------------------------


def _calibration_event(cache_key="key1"):
    return (
        "calibration", "point", 123.0, 0.0,
        {
            "step": 50, "cache_key": cache_key, "overlap": 0.5,
            "wall_s": 0.4, "device_total_s": 0.3,
            "measured_compute": 0.2, "modeled_compute": 0.1,
            "measured_collective": 0.1, "modeled_collective": 0.1,
        },
    )


def test_servicer_routes_calibration_events_and_dropped():
    timeline = JobTimeline()
    ledger = CalibrationLedger()
    servicer = MasterServicer(timeline=timeline, calibration=ledger)
    env = msg.Envelope(
        node_id=0, node_type="worker", job_name="local",
        payload=msg.TelemetryEvents(
            node_id=0, events=(_calibration_event(),), dropped=3,
        ),
    )
    servicer._report_telemetry(env)
    assert ledger.ratios("key1")["compute"] == pytest.approx(2.0)
    assert ledger.ratios("key1")["collective"] == pytest.approx(1.0)
    assert timeline.counter("telemetry_dropped") == 3
    text = servicer._get_metrics_text(None)
    assert 'dlrover_calibration_ratio{phase="compute"} 2' in text
    assert "dlrover_telemetry_dropped_total 3" in text
    assert "dlrover_perf_regressions_total 0" in text


def test_pipeline_counters_track_dropped_events():
    counters = StepPipelineCounters()
    counters.record_dropped(3)
    counters.record_dropped(0)   # no-op
    counters.record_dropped(-5)  # no-op
    assert counters.summary()["dropped_events"] == 3
    counters.reset()
    assert counters.summary()["dropped_events"] == 0


# -- HTTP plane --------------------------------------------------------------


def _http_get(port, path, timeout=5.0):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as resp:
        return resp.status, resp.read()


def test_http_plane_serves_metrics_timeline_healthz():
    timeline = JobTimeline()
    timeline.record(0, "step", kind="span", duration_s=0.1,
                    attrs={"step": 1})
    nodes = NodeManager(num_nodes=2)
    ledger = CalibrationLedger()
    ledger.observe("k", "compute", 1.5, 1.0)
    servicer = MasterServicer(
        timeline=timeline, node_manager=nodes,
        speed_monitor=SpeedMonitor(), calibration=ledger,
    )
    plane = MetricsHTTPServer(servicer, host="127.0.0.1", port=0)
    port = plane.start()
    try:
        # Byte parity with the RPC render path.
        status, body = _http_get(port, "/metrics")
        assert status == 200
        assert body == servicer._get_metrics_text(None).encode()
        assert b"dlrover_calibration_ratio" in body

        status, body = _http_get(port, "/timeline")
        trace = json.loads(body)
        assert any(
            e.get("name") == "step" for e in trace["traceEvents"]
        )

        status, body = _http_get(port, "/healthz")
        health = json.loads(body)
        assert health["ok"] is True and health["quarantined"] == []

        # Quarantine flips /healthz without touching anything else.
        nodes.quarantine(1, "sdc")
        health = json.loads(_http_get(port, "/healthz")[1])
        assert health["ok"] is False and health["quarantined"] == [1]

        with pytest.raises(urllib.error.HTTPError) as err:
            _http_get(port, "/teapot")
        assert err.value.code == 404
    finally:
        plane.stop()


def test_http_plane_seam_answers_503():
    servicer = MasterServicer(timeline=JobTimeline())
    plane = MetricsHTTPServer(servicer, host="127.0.0.1", port=0)
    port = plane.start()
    try:
        faults.configure("http.serve:error")
        with pytest.raises(urllib.error.HTTPError) as err:
            _http_get(port, "/metrics")
        assert err.value.code == 503
        faults.reset()
        status, _ = _http_get(port, "/metrics")
        assert status == 200
    finally:
        faults.reset()
        plane.stop()


def test_calibration_survives_master_state_snapshot(tmp_path):
    from dlrover_tpu.master.job_master import JobMaster

    state = str(tmp_path / "master_state.json")
    master = JobMaster(num_nodes=2, auto_scale=False, state_path=state)
    master.calibration.observe("k", "collective", 2.0, 1.0)
    master.calibration.observe("k", "compute", 1.2, 1.0)
    master._state_store.save(master)

    reborn = JobMaster(num_nodes=2, auto_scale=False, state_path=state)
    assert reborn._state_store.restore(reborn)
    assert reborn.calibration.ratios("k")["collective"] == pytest.approx(2.0)
    assert reborn.calibration.ratios("k")["compute"] == pytest.approx(1.2)
    text = reborn.servicer._get_metrics_text(None)
    assert 'dlrover_calibration_ratio{phase="collective"} 2' in text


# -- regression sentinel -----------------------------------------------------


class _FakeSpeedMonitor:
    def __init__(self):
        self.compiles = 0
        self.resizes = 0

    def compile_ledger(self):
        return {"compile_events": self.compiles}

    def resize_ledger(self):
        return {"resizes": self.resizes}


def test_step_regression_operator_fires_latches_and_resets():
    timeline = JobTimeline()
    sm = _FakeSpeedMonitor()
    op = StepRegressionOperator()
    ctx = DiagnosisContext(
        speed_monitor=sm, metrics=None, node_manager=None,
        timeline=timeline,
    )

    def steps(start, n, dur):
        for i in range(start, start + n):
            timeline.record(0, "step", kind="span", duration_s=dur,
                            attrs={"step": i})

    steps(1, 8, 0.1)
    assert op.observe(ctx) == []  # baseline frozen at 0.1
    steps(9, 8, 0.1)
    assert op.observe(ctx) == []  # steady state: no drift
    steps(17, 8, 0.2)
    actions = op.observe(ctx)
    assert len(actions) == 1
    assert actions[0].action == ActionType.REPORT
    assert "regressed" in actions[0].reason
    assert timeline.counter("perf_regressions") == 1
    # Latched: one report per generation, not one per tick.
    assert op.observe(ctx) == []
    assert timeline.counter("perf_regressions") == 1
    # A resize starts a new generation: relearn instead of alarming.
    sm.resizes = 1
    assert op.observe(ctx) == []
    assert op._baseline == pytest.approx(0.2)
    assert not op._fired


def test_step_regression_waits_for_a_full_window():
    timeline = JobTimeline()
    op = StepRegressionOperator()
    ctx = DiagnosisContext(
        speed_monitor=_FakeSpeedMonitor(), metrics=None,
        node_manager=None, timeline=timeline,
    )
    for i in range(1, 9):
        timeline.record(0, "step", kind="span", duration_s=0.1,
                        attrs={"step": i})
    assert op.observe(ctx) == []
    # Only 4 slow steps after baseline: window too short, stay silent.
    for i in range(9, 13):
        timeline.record(0, "step", kind="span", duration_s=0.5,
                        attrs={"step": i})
    assert op.observe(ctx) == []
    assert timeline.counter("perf_regressions") == 0


def test_regression_operator_without_timeline_is_silent():
    ctx = DiagnosisContext(
        speed_monitor=_FakeSpeedMonitor(), metrics=None,
        node_manager=None, timeline=None,
    )
    assert StepRegressionOperator().observe(ctx) == []


# -- tracelint: the HTTP plane's socket I/O is seam-covered -----------------


HTTP_PLANE_FIXTURE = """\
import socket
from dlrover_tpu.common import faults

def serve(port):
    faults.fire("http.serve", op="bind", port=port)
    return socket.create_connection(("127.0.0.1", port))
"""


def test_seam001_recognizes_http_serve_seam(tmp_path):
    from dlrover_tpu.analysis import run_paths
    from dlrover_tpu.analysis.rules.seams import known_seams

    assert "http.serve" in known_seams()
    (tmp_path / "master").mkdir()
    path = tmp_path / "master" / "plane.py"
    path.write_text(HTTP_PLANE_FIXTURE)
    report = run_paths(
        [str(path)], select=["SEAM001"], root=str(tmp_path)
    )
    assert report.findings == []
    # The same socket call WITHOUT the seam is a drillability gap.
    path.write_text(
        HTTP_PLANE_FIXTURE.replace(
            '    faults.fire("http.serve", op="bind", port=port)\n', ""
        )
    )
    report = run_paths(
        [str(path)], select=["SEAM001"], root=str(tmp_path)
    )
    assert [f.rule for f in report.findings] == ["SEAM001"]


# -- profiled CPU run through the trainer ------------------------------------


def _loader(batches, batch, seq, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(batches):
        toks = rng.integers(0, vocab, size=(batch, seq + 1), dtype=np.int32)
        yield {"inputs": toks[:, :-1], "targets": toks[:, 1:]}


@pytest.mark.slow  # real profiled train run + trace parse, ~12s; the
# parse/emit contract keeps its tier-1 witnesses on fixture traces.
def test_profiled_cpu_run_books_measured_rows(tmp_path):
    from dlrover_tpu.models.gpt2 import gpt2_config
    from dlrover_tpu.trainer.elastic_trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    model = gpt2_config(
        "124m", num_layers=1, d_model=64, num_heads=2,
        vocab_size=256, max_seq_len=32,
    )
    trainer = ElasticTrainer(
        model,
        TrainerConfig(
            global_batch_size=8, seq_len=32, learning_rate=1e-2,
            checkpoint_dir=str(tmp_path / "ckpt"),
            profile_every=2,
        ),
        client=None,
    )
    assert trainer._device_profiler is not None
    rec, was = _drain_enabled_recorder()
    try:
        loader = _loader(4, 8, 32)
        trainer.train_step(next(loader))  # step 1: pays the compile
        # Step 2 is a capture window; the TraceAnnotation + profiler
        # window must not retrace the compiled step program.
        with trace_asserts.assert_no_retrace("train_step"):
            trainer.train_step(next(loader))
            trainer.train_step(next(loader))
        events = rec.drain()
    finally:
        rec.configure(enabled=was)
        trainer.close()
    assert trainer._device_profiler.windows >= 1
    measured = [
        e for e in events if e[4].get("source") == "measured"
    ]
    assert measured, "a captured step must book measured phase rows"
    assert all(e[4].get("src") == "device" for e in measured)
    assert any(e[4].get("step") == 2 for e in measured)
    calib = [e for e in events if e[0] == "calibration"]
    assert calib
    attrs = calib[0][4]
    assert attrs["measured_compute"] > 0.0
    assert attrs["modeled_compute"] > 0.0
    assert attrs["cache_key"]  # cacheable tiny model -> a real key
    # The measured rows render on a distinct device track per node.
    trace = telemetry.events_to_chrome_trace({0: events})
    threads = {
        e["args"]["name"] for e in trace["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    assert "device" in threads


def test_profile_every_zero_constructs_nothing(tmp_path):
    # The default path must not even import device_profile: the knob off
    # means zero new objects on the step path.
    from dlrover_tpu.trainer.elastic_trainer import TrainerConfig

    assert TrainerConfig(
        global_batch_size=8, seq_len=32
    ).profile_every == 0
    prof = DeviceProfiler(0)
    assert not prof.wants(1) and not prof.arm(1)
