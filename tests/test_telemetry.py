"""Unified telemetry plane: recorder, wire format, job timeline, straggler
attribution, and the metrics exposition."""

import pickle
import threading
import time

import pytest

from dlrover_tpu.common import telemetry
from dlrover_tpu.common.telemetry import (
    TelemetryRecorder,
    events_to_chrome_trace,
)
from dlrover_tpu.master import messages as msg
from dlrover_tpu.master.diagnosis import (
    ActionType,
    DiagnosisContext,
    InferenceChain,
    StragglerOperator,
)
from dlrover_tpu.master.metrics import MetricsCollector
from dlrover_tpu.master.node_manager import NodeManager
from dlrover_tpu.master.servicer import MasterServicer
from dlrover_tpu.master.speed_monitor import SpeedMonitor
from dlrover_tpu.master.timeline import JobTimeline


def _recorder(**kw):
    kw.setdefault("enabled", True)
    kw.setdefault("ring_size", 256)
    return TelemetryRecorder(**kw)


# -- recorder ----------------------------------------------------------------


def test_span_nesting_and_attrs():
    r = _recorder(source="trainer")
    with r.span("outer", step=7):
        with r.span("inner", piece="a"):
            pass
    events = r.drain()
    # Inner exits (and records) first; both carry their attrs + src.
    assert [e[0] for e in events] == ["inner", "outer"]
    inner, outer = events
    assert inner[1] == "span" and inner[4]["piece"] == "a"
    assert outer[4]["step"] == 7
    assert inner[4]["src"] == outer[4]["src"] == "trainer"
    assert outer[3] >= inner[3] >= 0.0  # outer duration covers inner


def test_span_attrs_mutable_mid_span():
    r = _recorder()
    with r.span("rendezvous") as sp:
        sp.attrs["round"] = 3
    (event,) = r.drain()
    assert event[4]["round"] == 3


def test_span_records_error_kind_and_reraises():
    r = _recorder()
    with pytest.raises(ValueError):
        with r.span("step"):
            raise ValueError("boom")
    (event,) = r.drain()
    assert event[4]["error"] == "ValueError"


def test_event_duration_selects_kind():
    r = _recorder()
    r.event("restart")
    r.event("compile", duration_s=1.5)
    instant, timed = r.drain()
    assert instant[1] == "event" and instant[3] == 0.0
    assert timed[1] == "span" and timed[3] == 1.5


def test_event_t_mono_backdates():
    """Phases timed elsewhere (a capture window's measured device phases,
    a process's start as the OS booked it) are recorded afterwards but
    placed at caller-captured times."""
    r = _recorder()
    t0 = time.monotonic() - 2.5
    r.event("accumulate", duration_s=1.0, t_mono=t0, micro=0)
    r.event("accumulate", duration_s=1.0, t_mono=t0 + 1.0, micro=1)
    first, second = r.drain()
    assert first[1] == "span" and second[1] == "span"
    assert abs(second[2] - first[2] - 1.0) < 0.01
    assert first[2] < time.time() - 2.0  # backdated, not "now"


def test_reserved_attrs_rejected_with_clear_error():
    """Regression: attrs named after the span()/event() parameters used to
    surface as an opaque ``TypeError: got multiple values for argument`` —
    or, for ``duration_s`` arriving through a **dict, silently rebind the
    timing channel.  They are now rejected with a self-describing error."""
    r = _recorder()
    # `name` no longer binds the positional parameter (positional-only):
    # it reaches attrs and is rejected there with the reserved-name error.
    with pytest.raises(ValueError, match="reserved"):
        r.event("probe", **{"name": "matmul"})
    with pytest.raises(ValueError, match="reserved"):
        r.span("probe", **{"t_mono": 1.0})
    with pytest.raises(ValueError, match="reserved"):
        telemetry.event("probe", **{"name": "matmul", "host": "w0"})
    # A numeric duration_s kwarg IS the documented timing parameter (its
    # binding is indistinguishable from intent), but a non-numeric one is
    # an attr misrouted into the timing channel.
    with pytest.raises(TypeError, match="timing parameter"):
        r.event("probe", duration_s="slow")
    # The rejection fires even while disabled — a latent collision must not
    # hide until telemetry is switched on.
    off = _recorder(enabled=False)
    with pytest.raises(ValueError, match="reserved"):
        off.event("probe", **{"name": "x"})
    # Nothing landed in the ring, and legit reserved-free attrs still work.
    assert r.drain() == []
    r.event("probe", probe_duration_s=2.0, kind="block")
    (event,) = r.drain()
    assert event[4]["probe_duration_s"] == 2.0


def test_wall_clock_anchor():
    r = _recorder()
    r.event("tick")
    (event,) = r.drain()
    assert abs(event[2] - time.time()) < 5.0


def test_ring_bounded_under_threaded_churn():
    r = _recorder(ring_size=64)

    def hammer():
        for i in range(500):
            r.event("spin", i=i)

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(r) == 64
    assert r.dropped == 4 * 500 - 64
    assert r.drain() and len(r) == 0


def test_disabled_mode_allocates_nothing_per_event():
    r = _recorder(enabled=False)
    # span() hands out ONE cached null context — identity, not equality:
    # the disabled hot path must not allocate per call.
    assert r.span("a", x=1) is r.span("b") is telemetry._NULL_SPAN
    r.event("a", duration_s=2.0, x=1)
    with r.span("c"):
        pass
    assert len(r) == 0 and r.drain() == []
    # ... nor open a profiler row, nor keep a stack of open spans, nor feed
    # a tap: with an annotation factory installed the answer is the same.
    made = []
    r.annotate_with(made.append)
    with r.open_tap() as tap:
        assert r.span("step", step=1) is telemetry._NULL_SPAN
        with r.span("step", step=1):
            with r.span("checkpoint.d2h"):
                r.event("checkpoint.skip", step=1, reason="shm_busy")
        assert tap.take() == []
    assert made == [] and not hasattr(r._local, "stack")


def test_env_knobs(monkeypatch):
    monkeypatch.setenv(telemetry.ENV_ENABLE, "off")
    monkeypatch.setenv(telemetry.ENV_RING, "128")
    r = TelemetryRecorder()
    assert not r.enabled and r.ring_size == 128
    monkeypatch.setenv(telemetry.ENV_ENABLE, "1")
    assert TelemetryRecorder().enabled


def test_configure_resizes_preserving_newest():
    r = _recorder(ring_size=64)
    for i in range(64):
        r.event("e", i=i)
    r.configure(ring_size=16)
    kept = [e[4]["i"] for e in r.drain()]
    assert kept == list(range(48, 64))


class _FakeClient:
    def __init__(self):
        self.batches = []

    def report_telemetry(self, events, dropped=0):
        self.batches.append((list(events), dropped))


def test_ship_drains_events_and_dropped():
    r = _recorder(ring_size=16)
    client = _FakeClient()
    assert r.ship(client) == 0 and client.batches == []  # empty: no RPC
    for i in range(20):
        r.event("e", i=i)
    assert r.ship(client) == 16
    events, dropped = client.batches[0]
    assert len(events) == 16 and dropped == 4
    assert r.dropped == 0 and len(r) == 0


# -- wire round-trip through the servicer ------------------------------------


def test_wire_round_trip_through_servicer():
    """Trainer + agent recorders drain through pickled TelemetryEvents into
    a real servicer; the merged timeline holds both tiers' streams (the
    PR's acceptance shape: step/compile spans AND rendezvous/restart)."""
    trainer = _recorder(source="trainer")
    with trainer.span("step", step=1):
        pass
    trainer.event("compile", duration_s=2.5, cached=False)
    agent = _recorder(source="agent")
    with agent.span("rendezvous") as sp:
        sp.attrs["round"] = 0
    agent.event("restart", restart_count=1)

    timeline = JobTimeline()
    servicer = MasterServicer(timeline=timeline)
    for recorder in (trainer, agent):
        wire = pickle.dumps(msg.Envelope(
            node_id=5,
            payload=msg.TelemetryEvents(5, tuple(recorder.drain())),
        ))
        response = servicer.report(msg.safe_loads(wire))
        assert response.success, response.message

    names = {e[0] for e in timeline.events(5)[5]}
    assert {"step", "compile", "rendezvous", "restart"} <= names
    assert timeline.restart_count(5) == 1
    assert [e[3] for e in timeline.spans(5, "compile")] == [2.5]
    # src lanes survived the merge.
    sources = {e[4]["src"] for e in timeline.events(5)[5]}
    assert sources == {"trainer", "agent"}


def test_servicer_timeline_and_metrics_requests():
    timeline = JobTimeline()
    timeline.record(0, "step", kind="span", duration_s=0.1,
                    attrs={"step": 1})
    servicer = MasterServicer(
        speed_monitor=SpeedMonitor(), timeline=timeline
    )
    got = servicer.get(msg.Envelope(payload=msg.TimelineRequest()))
    assert got.success and 0 in got.payload
    text = servicer.get(msg.Envelope(payload=msg.MetricsRequest()))
    assert text.success and "dlrover_goodput" in text.payload
    # No timeline wired -> degrade, don't fail.
    bare = MasterServicer()
    assert bare.get(msg.Envelope(payload=msg.MetricsRequest())).payload == ""


def test_malformed_wire_events_do_not_drop_batch():
    timeline = JobTimeline()
    timeline.add_events(0, [
        ("good", "event", 0.0, 0.0, {}),
        "garbage",
        ("short",),
        ("also-good", "span", 1.0, 0.5, {"k": 1}),
    ])
    assert [e[0] for e in timeline.events(0)[0]] == ["good", "also-good"]


# -- embed ledger + gauges ---------------------------------------------------


def test_embed_event_routes_through_servicer_into_gauges():
    """An ``embed`` telemetry event lands in the speed monitor's embed
    ledger, and the ``dlrover_embed_*`` gauges render its snapshot."""
    sm = SpeedMonitor()
    timeline = JobTimeline()
    servicer = MasterServicer(speed_monitor=sm, timeline=timeline)
    attrs = {
        "world": 4, "rows_owned": 1200, "rows_owned_max": 400,
        "lookups": 50, "rows_fetched": 9000, "reshards": 2,
        "reshard_s": 0.75, "moved_rows": 300, "spill_bytes": 4096,
        "hit_rate": 0.8, "rows_per_s": 50_000.0,
        "unknown_future_attr": 1,  # engines may grow the event
    }
    wire = pickle.dumps(msg.Envelope(
        node_id=3,
        payload=msg.TelemetryEvents(
            3, (("embed", "event", 0.0, 0.0, attrs),)
        ),
    ))
    assert servicer.report(msg.safe_loads(wire)).success
    ledger = sm.embed_ledger()
    assert ledger["rows_owned"] == 1200 and ledger["reshards"] == 2
    assert ledger["hit_rate"] == pytest.approx(0.8)
    text = timeline.render_metrics(speed_monitor=sm)
    metrics = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, value = line.rsplit(" ", 1)
            metrics[key] = float(value)
    assert metrics["dlrover_embed_rows_owned"] == 1200
    assert metrics["dlrover_embed_rows_owned_max"] == 400
    assert metrics["dlrover_embed_cache_hit_rate"] == pytest.approx(0.8)
    assert metrics["dlrover_embed_lookups_total"] == 50
    assert metrics["dlrover_embed_rows_fetched_total"] == 9000
    assert metrics["dlrover_embed_reshards_total"] == 2
    assert metrics["dlrover_embed_reshard_seconds_total"] == (
        pytest.approx(0.75)
    )
    assert metrics["dlrover_embed_moved_rows_total"] == 300
    assert metrics["dlrover_embed_spill_bytes"] == 4096
    assert metrics["dlrover_embed_rows_per_s"] == 50_000


def test_instant_fault_events_route_into_counter_gauges():
    """Instant fault-plane events (retry, circuit_open, replica.death,
    process_exit, worker_start) bump timeline counters and render as
    HELP'd ``dlrover_*_total`` gauges — the TEL001 telemetry contract:
    no emitted event kind may die unrouted in the servicer."""
    sm = SpeedMonitor()
    timeline = JobTimeline()
    servicer = MasterServicer(speed_monitor=sm, timeline=timeline)
    kinds = ("retry", "circuit_open", "replica.death", "process_exit",
             "worker_start", "worker_start")
    wire = pickle.dumps(msg.Envelope(
        node_id=1,
        payload=msg.TelemetryEvents(
            1, tuple((k, "event", 0.0, 0.0, {}) for k in kinds)
        ),
    ))
    assert servicer.report(msg.safe_loads(wire)).success
    text = timeline.render_metrics(speed_monitor=sm)
    metrics = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, value = line.rsplit(" ", 1)
            metrics[key] = float(value)
    assert metrics["dlrover_retries_total"] == 1
    assert metrics["dlrover_circuit_opens_total"] == 1
    assert metrics["dlrover_replica_deaths_total"] == 1
    assert metrics["dlrover_worker_exits_total"] == 1
    assert metrics["dlrover_worker_starts_total"] == 2
    for name in ("dlrover_retries_total", "dlrover_worker_starts_total"):
        assert f"# HELP {name} " in text


def test_embed_ledger_newest_wins_max_aggregation_and_state():
    """Per-node snapshots are newest-wins; the fleet aggregate takes the
    max of plane-global counters (every reporter sees the same plane) and
    averages the per-reporter hit rate — and the ledger round-trips
    through the master-restart state snapshot."""
    sm = SpeedMonitor()
    sm.record_embed(0, rows_owned=100, hit_rate=0.5, reshards=1)
    sm.record_embed(0, rows_owned=150, hit_rate=0.6, reshards=2)  # newest
    sm.record_embed(1, rows_owned=149, hit_rate=0.8, reshards=2)
    ledger = sm.embed_ledger()
    assert ledger["reporters"] == 2 and ledger["embed_events"] == 3
    assert ledger["rows_owned"] == 150  # max, not sum: no double count
    assert ledger["reshards"] == 2
    assert ledger["hit_rate"] == pytest.approx(0.7)
    fresh = SpeedMonitor()
    fresh.restore_embed_state(sm.embed_state())
    assert fresh.embed_ledger() == ledger


def test_moe_event_routes_through_servicer_into_gauges():
    """A ``moe`` telemetry event lands in the speed monitor's router
    ledger, and the ``dlrover_moe_*`` gauges render its snapshot —
    including the per-expert load as a labeled gauge family."""
    sm = SpeedMonitor()
    timeline = JobTimeline()
    servicer = MasterServicer(speed_monitor=sm, timeline=timeline)
    attrs = {
        "step": 40, "entropy": 1.15, "drop_fraction": 0.03,
        "experts": 4, "top_k": 2,
        "load": "[0.26, 0.25, 0.25, 0.24]",
        "pad_share": 0.059, "max_expert_load": 1.04,
        "unknown_future_attr": 1,  # trainers may grow the event
    }
    wire = pickle.dumps(msg.Envelope(
        node_id=3,
        payload=msg.TelemetryEvents(
            3, (("moe", "event", 0.0, 0.0, attrs),)
        ),
    ))
    assert servicer.report(msg.safe_loads(wire)).success
    ledger = sm.moe_ledger()
    assert ledger["entropy"] == pytest.approx(1.15)
    assert ledger["drop_fraction"] == pytest.approx(0.03)
    assert ledger["experts"] == 4 and ledger["top_k"] == 2
    assert ledger["load"] == pytest.approx([0.26, 0.25, 0.25, 0.24])
    text = timeline.render_metrics(speed_monitor=sm)
    metrics = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, value = line.rsplit(" ", 1)
            metrics[key] = float(value)
    assert metrics["dlrover_moe_gate_entropy"] == pytest.approx(1.15)
    assert metrics["dlrover_moe_capacity_drop_fraction"] == (
        pytest.approx(0.03)
    )
    assert metrics["dlrover_moe_pad_share"] == pytest.approx(0.059)
    assert metrics["dlrover_moe_max_expert_load"] == pytest.approx(1.04)
    assert metrics["dlrover_moe_experts"] == 4
    assert metrics["dlrover_moe_top_k"] == 2
    assert metrics["dlrover_moe_reporters"] == 1
    assert metrics['dlrover_moe_expert_load{expert="0"}'] == (
        pytest.approx(0.26)
    )
    assert metrics['dlrover_moe_expert_load{expert="3"}'] == (
        pytest.approx(0.24)
    )
    # The labeled family still carries exactly one HELP/TYPE pair.
    assert text.count("# HELP dlrover_moe_expert_load") == 1
    assert text.count("# TYPE dlrover_moe_expert_load gauge") == 1


def test_moe_ledger_newest_wins_and_aggregates():
    """Per-node router snapshots are newest-wins; the aggregate averages
    entropy/drop/load across reporters and takes the max of the geometry
    fields (every replica trains the same model)."""
    sm = SpeedMonitor()
    sm.record_moe(0, step=10, entropy=1.0, drop_fraction=0.0,
                  experts=2, top_k=1, load=[0.5, 0.5])
    sm.record_moe(0, step=20, entropy=0.6, drop_fraction=0.1,
                  experts=2, top_k=1, load=[0.8, 0.2])  # newest
    sm.record_moe(1, step=18, entropy=0.4, drop_fraction=0.3,
                  experts=2, top_k=1, load=[0.6, 0.4])
    ledger = sm.moe_ledger()
    assert ledger["moe_events"] == 3 and ledger["reporters"] == 2
    assert ledger["step"] == 20
    assert ledger["entropy"] == pytest.approx(0.5)
    assert ledger["drop_fraction"] == pytest.approx(0.2)
    assert ledger["load"] == pytest.approx([0.7, 0.3])
    # A reporter with a stale-width load vector is excluded from the
    # elementwise mean, never crashes it.
    sm.record_moe(2, experts=2, top_k=1, load=[1.0])
    assert sm.moe_ledger()["load"] == pytest.approx([0.7, 0.3])


def test_plane_emit_telemetry_books_the_stats_snapshot():
    """``ShardedEmbeddingTable.emit_telemetry`` books one ``embed`` event
    whose attrs are exactly the stats the master's ledger consumes."""
    import numpy as np

    from dlrover_tpu.embedding import ShardedEmbeddingTable

    r = telemetry.recorder()
    was = r.enabled
    r.configure(enabled=True)
    r.drain()
    plane = ShardedEmbeddingTable(
        "tele", dim=4, num_buckets=8, world=2, learning_rate=0.1, seed=1
    )
    try:
        plane.lookup(np.arange(16, dtype=np.int64))
        plane.emit_telemetry(hit_rate=0.9)
        events = [e for e in r.drain() if e[0] == "embed"]
        assert len(events) == 1
        attrs = events[0][4]
        assert attrs["world"] == 2 and attrs["rows_owned"] == 16
        assert attrs["lookups"] == 1 and attrs["hit_rate"] == 0.9
        sm = SpeedMonitor()
        sm.record_embed(0, **attrs)  # the servicer's exact call shape
        assert sm.embed_ledger()["rows_owned"] == 16
    finally:
        plane.close()
        r.configure(enabled=was)


# -- chrome trace ------------------------------------------------------------


def test_chrome_trace_tracks_per_node_and_source():
    events = {
        0: [("step", "span", 10.0, 0.25, {"src": "trainer", "step": 1}),
            ("restart", "event", 11.0, 0.0, {"src": "agent"})],
        1: [("step", "span", 10.1, 0.30, {"src": "trainer", "step": 1})],
    }
    trace = events_to_chrome_trace(events)["traceEvents"]
    slices = [e for e in trace if e["ph"] == "X"]
    instants = [e for e in trace if e["ph"] == "i"]
    assert {e["pid"] for e in slices} == {0, 1}
    assert instants[0]["pid"] == 0
    # trainer and agent get distinct thread lanes within node 0.
    node0 = {e["tid"] for e in trace if e["pid"] == 0 and e["ph"] != "M"}
    assert len(node0) == 2
    step = next(e for e in slices if e["pid"] == 0)
    assert step["dur"] == pytest.approx(0.25e6)
    assert step["args"]["step"] == 1 and "src" not in step["args"]
    names = [e for e in trace if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in names)
    assert any(e["args"].get("name") == "agent" for e in names)


# -- skew attribution + straggler operator -----------------------------------


def _skewed_timeline(nodes=3, steps=12, slow_node=2, ratio=3.0):
    timeline = JobTimeline()
    for step in range(steps):
        for node in range(nodes):
            duration = 0.1 * ratio if node == slow_node else 0.1
            timeline.record(node, "step", kind="span", duration_s=duration,
                            attrs={"step": step})
    return timeline


def test_step_stats_and_slowest_histogram():
    timeline = _skewed_timeline()
    stats = timeline.step_stats()
    assert stats[2]["p50"] == pytest.approx(0.3)
    assert stats[0]["p95"] == pytest.approx(0.1)
    assert timeline.slowest_per_step() == {2: 12}
    assert timeline.steps_observed() == 12
    assert timeline.step_skew(2.0) == {2: 12}


def test_straggler_operator_reports_slow_node():
    ctx = DiagnosisContext(
        speed_monitor=SpeedMonitor(), metrics=None, node_manager=None,
        timeline=_skewed_timeline(),
    )
    actions = StragglerOperator().observe(ctx)
    assert len(actions) == 1
    action = actions[0]
    assert action.action == ActionType.REPORT
    assert action.node_id == 2
    assert "node 2" in action.reason and "straggler" in action.reason


def test_straggler_balanced_world_stays_quiet():
    timeline = JobTimeline()
    for step in range(20):
        for node in range(3):
            timeline.record(node, "step", kind="span",
                            duration_s=0.1 + 0.001 * node,
                            attrs={"step": step})
    ctx = DiagnosisContext(
        speed_monitor=SpeedMonitor(), metrics=None, node_manager=None,
        timeline=timeline,
    )
    assert StragglerOperator().observe(ctx) == []
    # And absent/None timeline disables the rule instead of raising.
    ctx.timeline = None
    assert StragglerOperator().observe(ctx) == []


def test_straggler_needs_persistent_evidence():
    # Below MIN_STEPS multi-node steps: no verdict yet.
    ctx = DiagnosisContext(
        speed_monitor=SpeedMonitor(), metrics=None, node_manager=None,
        timeline=_skewed_timeline(steps=StragglerOperator.MIN_STEPS - 1),
    )
    assert StragglerOperator().observe(ctx) == []


def test_straggler_registered_in_default_chain():
    assert any(
        isinstance(op, StragglerOperator)
        for op in InferenceChain().operators
    )


# -- metrics exposition ------------------------------------------------------


def test_render_metrics_overlap_fraction_gauge():
    """A calibration ledger that has observed a measured overlap fraction
    renders it as the ``dlrover_overlap_fraction`` gauge."""
    from dlrover_tpu.master.calibration import CalibrationLedger
    from dlrover_tpu.master.timeline import JobTimeline

    led = CalibrationLedger()
    led.observe("k1", "reduce_scatter", measured=0.9, modeled=1.0)
    led.observe_overlap("k1", 0.69)
    text = JobTimeline().render_metrics(calibration=led)
    assert "dlrover_overlap_fraction 0.69" in text
    # Never observed -> the gauge reads 0, not a stale or modeled value.
    bare = CalibrationLedger()
    bare.observe("k1", "reduce_scatter", measured=0.9, modeled=1.0)
    assert "dlrover_overlap_fraction 0" in JobTimeline().render_metrics(
        calibration=bare
    )


def test_render_metrics_goodput_matches_speed_monitor():
    sm = SpeedMonitor()
    now = time.time()
    for i in range(10):
        sm.collect_global_step(i + 1, now - (10 - i) * 1.0, tokens=100)
    sm.record_compile(4.2, restart=True)
    sm.record_anomaly(5, "nan@5:loss=nan")
    sm.record_anomaly(6, "loss_spike@6:loss=9.0")
    sm.record_serve(0, qps=20.0, p50_s=0.02, p95_s=0.08, occupancy=0.75,
                    slots=4, requests=50, tokens=800)
    timeline = _skewed_timeline()
    text = timeline.render_metrics(speed_monitor=sm)
    metrics = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, value = line.rsplit(" ", 1)
            metrics[key] = float(value)
    # Acceptance: exposition goodput within 1% of the ledger's own value.
    assert metrics["dlrover_goodput"] == pytest.approx(
        sm.goodput(), abs=0.01
    )
    assert metrics["dlrover_global_step"] == 10
    assert metrics["dlrover_compile_seconds_total"] == pytest.approx(4.2)
    assert metrics["dlrover_restart_compile_seconds_total"] == (
        pytest.approx(4.2)
    )
    assert metrics['dlrover_numeric_anomalies_recent{kind="nan"}'] == 1
    assert (
        metrics['dlrover_numeric_anomalies_recent{kind="loss_spike"}'] == 1
    )
    assert metrics['dlrover_step_time_seconds{node="2",quantile="0.50"}'] \
        == pytest.approx(0.3)
    assert metrics['dlrover_slowest_steps_total{node="2"}'] == 12
    # Serving-plane gauges come off the serve ledger.
    assert metrics["dlrover_serve_qps"] == pytest.approx(20.0)
    assert metrics['dlrover_serve_latency_seconds{quantile="0.5"}'] == (
        pytest.approx(0.02)
    )
    assert metrics['dlrover_serve_latency_seconds{quantile="0.95"}'] == (
        pytest.approx(0.08)
    )
    assert metrics["dlrover_serve_slot_occupancy"] == pytest.approx(0.75)
    assert metrics["dlrover_serve_requests_total"] == 50
    assert metrics["dlrover_serve_tokens_total"] == 800
    assert metrics["dlrover_serve_replicas"] == 1


def test_resize_seconds_split_by_kind_gauge_parity():
    """The resize ledger splits seconds by kind (restore vs relayout);
    the exposition's labeled ``dlrover_resize_seconds_total{kind=...}``
    lines must sum to the unlabeled total — open windows included, folded
    into the kind that opened them."""
    sm = SpeedMonitor()
    now = time.time()
    sm.collect_global_step(1, now, tokens=100)
    # A classic restore-path resize window, opened then closed by the
    # next step advance.
    sm.begin_resize(reason="preempt:1")
    time.sleep(0.01)
    sm.collect_global_step(2, now + 1.0, tokens=100)
    # Two live relayouts: one clean (ms-scale), one that fell back.
    sm.record_relayout(0.004)
    sm.record_relayout(1.5, ok=False)

    ledger = sm.resize_ledger()
    assert ledger["resizes"] == 3
    assert ledger["by_reason"]["preempt:1"] == 1
    assert ledger["by_reason"]["relayout"] == 1
    assert ledger["by_reason"]["relayout_failed"] == 1
    assert ledger["by_kind"]["relayout"] == pytest.approx(0.004)
    assert ledger["by_kind"]["restore"] >= 1.5
    assert ledger["open_kind"] == ""

    timeline = JobTimeline()
    text = timeline.render_metrics(speed_monitor=sm)
    metrics = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, value = line.rsplit(" ", 1)
            metrics[key] = float(value)
    labeled = (
        metrics['dlrover_resize_seconds_total{kind="restore"}']
        + metrics['dlrover_resize_seconds_total{kind="relayout"}']
    )
    assert labeled == pytest.approx(metrics["dlrover_resize_seconds_total"])
    assert metrics['dlrover_resize_seconds_total{kind="relayout"}'] == (
        pytest.approx(0.004)
    )
    assert metrics["dlrover_resizes_total"] == 3


def test_resize_open_window_folds_into_open_kind():
    """While a resize window is still open, its elapsed seconds appear in
    BOTH the unlabeled total and the opening kind's label — the parity
    invariant holds mid-resize, not just after the window closes."""
    sm = SpeedMonitor()
    now = time.time()
    sm.collect_global_step(1, now, tokens=100)
    sm.begin_resize(reason="scale", kind="restore")
    time.sleep(0.02)
    ledger = sm.resize_ledger()
    assert ledger["open_kind"] == "restore"
    assert ledger["resize_open_s"] > 0.0
    timeline = JobTimeline()
    text = timeline.render_metrics(speed_monitor=sm)
    metrics = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, value = line.rsplit(" ", 1)
            metrics[key] = float(value)
    labeled = (
        metrics['dlrover_resize_seconds_total{kind="restore"}']
        + metrics['dlrover_resize_seconds_total{kind="relayout"}']
    )
    # Both totals race the open window's clock; allow scheduler slop.
    assert labeled == pytest.approx(
        metrics["dlrover_resize_seconds_total"], abs=0.05
    )
    assert metrics['dlrover_resize_seconds_total{kind="restore"}'] > 0.0


def test_render_metrics_includes_node_manager_relaunches():
    timeline = JobTimeline()
    nm = NodeManager(num_nodes=2)
    nm._nodes[1].relaunch_count = 2
    text = timeline.render_metrics(node_manager=nm)
    assert 'dlrover_node_relaunch_count{node="1"} 2' in text


# -- eviction ----------------------------------------------------------------


def test_metrics_collector_evict():
    metrics = MetricsCollector()
    metrics.collect(0, 10.0, 1.0)
    metrics.collect(1, 90.0, 2.0, timestamp=time.time() - 1000)
    metrics.evict(1)
    assert metrics.latest(1) is None
    assert metrics.nodes() == [0]
    assert metrics.stale_nodes(300.0) == []
    metrics.evict(7)  # unknown node: no-op


def test_timeline_evict_node():
    timeline = _skewed_timeline()
    timeline.record(2, "restart")
    timeline.evict_node(2)
    assert timeline.nodes() == [0, 1]
    assert timeline.restart_count(2) == 0
    assert 2 not in timeline.step_skew(2.0)
    assert 2 not in timeline.step_stats()


def test_scale_down_evicts_observability_series():
    """Regression: a node_manager-driven departure (retire) must drop the
    node's metrics + timeline series via the master's transition hook."""
    from dlrover_tpu.master.job_master import JobMaster

    master = JobMaster(num_nodes=2, auto_scale=False)
    master.metrics.collect(1, 50.0, 4.0)
    master.timeline.record(1, "step", kind="span", duration_s=0.1,
                           attrs={"step": 3})
    assert master.metrics.latest(1) and master.timeline.nodes() == [1]
    master.node_manager.retire_node(1)
    assert master.metrics.latest(1) is None
    assert master.timeline.nodes() == []
    # The scaler's retire hook path clears series the same way.
    master.metrics.collect(0, 10.0, 1.0)
    master.timeline.record(0, "step", kind="span", duration_s=0.1,
                           attrs={"step": 4})
    master._handle_node_retired(0)
    assert master.metrics.latest(0) is None
    assert master.timeline.nodes() == []


# -- pipeline-counter folding ------------------------------------------------


def test_host_blocks_fold_into_module_recorder():
    from dlrover_tpu.utils.profiler import pipeline_counters

    recorder = telemetry.recorder()
    was_enabled = recorder.enabled
    recorder.configure(enabled=True)
    recorder.drain()
    try:
        with pipeline_counters().host_block("metrics-flush", steps=(3, 4)):
            pass
        pipeline_counters().record_place(0.002)
        events = recorder.drain()
    finally:
        recorder.configure(enabled=was_enabled)
    names = [e[0] for e in events]
    assert "metrics-flush" in names and "h2d" in names
    flush = events[names.index("metrics-flush")]
    assert flush[1] == "span" and flush[4]["steps"] == (3, 4)
    assert flush[4]["kind"] == "block"


def test_host_blocks_know_the_span_that_waited_for_them():
    from dlrover_tpu.utils.profiler import pipeline_counters

    recorder = telemetry.recorder()
    was_enabled = recorder.enabled
    recorder.configure(enabled=True)
    try:
        with recorder.open_tap() as tap:
            with telemetry.span("checkpoint", step=8):
                with telemetry.span("checkpoint.drain"):
                    with pipeline_counters().host_block(
                        "metrics-flush", steps=(7, 8)
                    ):
                        pass
            events = tap.take()
    finally:
        recorder.configure(enabled=was_enabled)
    (flush,) = [e for e in events if e[0] == "metrics-flush"]
    assert flush[4]["parent"] == "checkpoint.drain"
    assert flush[4]["id"] == "step:8"
