"""The readers of the program's own spans: ``program_spans`` (the
recorder's wire events) and ``trace_span`` (the ``dlrover:`` rows of the
run's trace), checked by hand on a small recorded list and end to end in a
traced rehearsal."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import build, layers  # noqa: E402
from benchmark.readers import program_spans, trace_span  # noqa: E402

from test_benchmark_rehearsal import run_cell  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
SPAN_METRICS = {
    m["name"]: m for m in build.manifest()["per_layer"]
    if layers.spec(m["name"])["reader"] in ("program_spans", "trace_span")
}


def recorded():
    with open(os.path.join(HERE, "recorded_spans.json")) as f:
        return json.load(f)


def test_window_steps_are_those_between_the_summarys_reading_ends():
    assert program_spans.window_steps(recorded()) == (5, 8)
    assert program_spans.window_steps({}) is None
    shifted = dict(recorded(), summary={"steps": 4, "window_s": 8.5})
    assert program_spans.window_steps(shifted) == (3, 6)


def test_data_wait_is_the_median_wait_of_the_windows_steps():
    params = layers.spec("data_wait_span_ms")["params"]
    # steps 5..8 wait 4, 2, 3, 9 ms: the median is 3.5 ms; the 40-70 ms
    # waits of steps 1, 4 and 9 lie outside the window.
    assert program_spans.read(recorded(), params) == pytest.approx(3.5)


def test_startup_to_mesh_is_the_first_trainers_runtime_plus_mesh():
    params = layers.spec("startup_to_mesh_s")["params"]
    assert program_spans.read(recorded(), params) == pytest.approx(11.75)


def test_a_program_without_the_spans_gives_nothing():
    older = dict(recorded(), program_spans=[
        e for e in recorded()["program_spans"] if e[0] == "step"
    ])
    for name in ("data_wait_span_ms", "startup_to_mesh_s"):
        assert program_spans.read(older, layers.spec(name)["params"]) is None
    # no run at all: the process's recorder is not consulted
    assert program_spans.spans_of({}) == []


def test_spans_come_from_this_process_where_no_one_hands_them_over():
    from dlrover_tpu.common import telemetry

    recorder = telemetry.TelemetryRecorder(enabled=True)
    with recorder.span("data_wait", step=5):
        pass
    evidence = {k: v for k, v in recorded().items() if k != "program_spans"}
    saved = telemetry._RECORDER
    telemetry._RECORDER = recorder
    try:
        value = program_spans.read(
            evidence, layers.spec("data_wait_span_ms")["params"]
        )
    finally:
        telemetry._RECORDER = saved
    assert value is not None and 0 <= value < 1000


def test_trace_span_reads_the_dlrover_rows_of_the_runs_trace(
    tmp_path, monkeypatch
):
    import jax

    from dlrover_tpu.common import telemetry

    recorder = telemetry.TelemetryRecorder(enabled=True)
    recorder.annotate_with(jax.profiler.TraceAnnotation)
    monkeypatch.setattr(trace_span, "run_trace_dir", lambda: str(tmp_path))
    params = layers.spec("save_d2h_s")["params"]
    assert trace_span.read({"trace_reduced": {"steps": 1}}, params) is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        with recorder.span("checkpoint", step=7):
            with recorder.span("checkpoint.d2h"):
                jax.numpy.ones((4, 4)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (ring_d2h, ring_save) = recorder.drain()
    value = trace_span.read({"trace_reduced": {"steps": 1}}, params)
    # the same interval on two clocks: the ring's and the profiler's
    assert value == pytest.approx(ring_d2h[3], abs=2e-3)
    assert value <= ring_save[3] + 2e-3
    # an untraced run has nothing to read, and an older program no row
    assert trace_span.read({}, params) is None
    assert trace_span.read(
        {"trace_reduced": {"steps": 1}}, {"name": "checkpoint.drain"}
    ) is None


def test_the_runs_trace_is_looked_for_where_run_py_puts_it():
    from benchmark import run

    assert trace_span.run_trace_dir() == os.path.join(
        run.RUNS_DIR, f"run{os.getpid()}", "trace"
    )


@pytest.mark.parametrize("cell,chips", sorted(
    {(c, w["chips"]) for m in SPAN_METRICS.values()
     for c in m["workloads"]
     for w in build.manifest()["workloads"] if w["name"] == c}
))
def test_a_traced_rehearsal_prints_the_span_metrics_names(cell, chips):
    proc = run_cell(cell, 1, chips)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    expected = {n for n, m in SPAN_METRICS.items() if cell in m["workloads"]}
    assert expected and expected <= set(line["metrics"])
    assert all(line["metrics"][n]["value"] is None for n in expected)
