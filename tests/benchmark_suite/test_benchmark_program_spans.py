"""The readers of the program's own spans: ``program_spans`` (the
recorder's wire events) and ``trace_span`` (the ``dlrover:`` rows of the
run's trace), checked by hand on a small recorded list and end to end in a
traced rehearsal; and what ``setup_s`` is made of, which starts at two of
those spans (``readings.setup_parts``).  Every check of a traced rehearsal
lives in this file, so that one worker runs each traced cell once."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import build, layers, readings  # noqa: E402
from benchmark.readers import program_spans, trace_span  # noqa: E402

from test_benchmark_rehearsal import (  # noqa: E402
    CELLS,
    check_result_line,
    manifest,
    run_cell,
)

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


@pytest.mark.parametrize("cell,chips", CELLS)
def test_traced_rehearsal_prints_one_result_line(cell, chips):
    """``test_benchmark_rehearsal.py``'s check on the traced run of every
    cell, here because this file's tests read the same runs."""
    check_result_line(cell, chips, 1)


# -- setup_s: from the mesh built to the window, less the reference check ------

# One cell a scenario, and records made up by hand: t0 of the process the
# command started, the program's wire events, the check's seconds as timed
# from outside, the harness's wait for the first persist where the scenario
# has one, and the instant the window opens.
SCENARIOS = {
    "train_steady": {
        "cell": "gpt2-1.5b.train_steady",
        "t0": 100.0, "window_open": 131.5, "check_s": 3.25, "wait_s": None,
        "spans": [
            ("startup.runtime", "span", 1.7e9, 12.5, {"restart_count": 0}),
            ("startup.mesh", "span", 1.7e9 + 12.5, 0.25, {}),
            ("compile", "span", 1.7e9 + 13.0, 6.0, {}),
        ],
        "to_mesh": 12.75, "setup_s": 31.5 - 12.75 - 3.25,
    },
    "train_steady_own_ref": {
        "cell": "olmoe-1b-7b.train_steady",
        "t0": 7.0, "window_open": 67.0, "check_s": 25.5, "wait_s": None,
        "spans": [
            ("startup.runtime", "span", 1.7e9, 17.75, {"restart_count": 0}),
            ("startup.mesh", "span", 1.7e9 + 17.75, 0.5, {}),
        ],
        "to_mesh": 18.25, "setup_s": 60.0 - 18.25 - 25.5,
    },
    # the trainer is a child of the launcher: its process starts seconds
    # after t0, its events cross a JSON file (tuples become lists), and the
    # resumed trainer's spans, later in the list, are not the first one's
    "save_kill_resume": {
        "cell": "gpt2-1.5b.save_kill_resume",
        "t0": 50.0, "window_open": 130.0, "check_s": 2.0, "wait_s": 16.5,
        "spans": json.loads(json.dumps([
            ("startup.runtime", "span", 1.7e9 + 4.0, 9.0,
             {"restart_count": 0}),
            ("startup.mesh", "span", 1.7e9 + 13.0, 0.125, {}),
            ("startup.runtime", "span", 1.7e9 + 140.0, 8.0,
             {"restart_count": 1}),
            ("startup.mesh", "span", 1.7e9 + 148.0, 0.25, {}),
        ])),
        # the launcher's, master's and agent's four seconds stay in; the
        # harness's own wait for the first persist goes out
        "to_mesh": 9.125, "setup_s": 80.0 - 9.125 - 2.0 - 16.5,
    },
}
PARTS = ("process_to_window_s", "startup_to_mesh_s", "reference_check_s")
STARTUP_SPANS = layers.spec("startup_to_mesh_s")["params"]["names"]


def parts_of(scenario):
    """The names of what a scenario takes out of ``setup_s``."""
    wait = SCENARIOS[scenario]["wait_s"] is not None
    return PARTS[1:] + (("persist_wait_s",) if wait else ())


def residual(setup, scenario):
    return abs(
        setup["process_to_window_s"] - setup["setup_s"]
        - sum(setup[k] for k in parts_of(scenario))
    )


def made_up(scenario):
    r = SCENARIOS[scenario]
    return readings.setup_parts(
        r["t0"], r["window_open"], r["spans"], r["check_s"], r["wait_s"]
    )


def check_window_less_mesh_less_check(scenario):
    r, got = SCENARIOS[scenario], made_up(scenario)
    assert got["setup_s"] == pytest.approx(r["setup_s"], abs=1e-9)
    assert got["startup_to_mesh_s"] == r["to_mesh"]
    assert got["reference_check_s"] == r["check_s"]
    # the old reading, to the digit
    assert got["process_to_window_s"] == r["window_open"] - r["t0"]
    # a slower start of the machine or a longer check moves the parts and
    # leaves setup_s where it was
    slow = [list(e) for e in r["spans"]]
    slow[0][3] += 2.7
    later = readings.setup_parts(
        r["t0"], r["window_open"] + 2.7 + 20.0, slow, r["check_s"] + 20.0,
        r["wait_s"],
    )
    assert later["setup_s"] == pytest.approx(got["setup_s"], abs=1e-9)
    assert later["process_to_window_s"] == pytest.approx(
        got["process_to_window_s"] + 22.7
    )
    # and so does a slower first persist, where the harness waits for one
    if r["wait_s"] is not None:
        assert got["persist_wait_s"] == r["wait_s"]
        slower = readings.setup_parts(
            r["t0"], r["window_open"] + 9.0, r["spans"], r["check_s"],
            r["wait_s"] + 9.0,
        )
        assert slower["setup_s"] == pytest.approx(got["setup_s"], abs=1e-9)


def check_the_parts_add_up(scenario):
    got = made_up(scenario)
    assert set(got) == {"process_to_window_s", "setup_s"} | set(
        parts_of(scenario)
    )
    # the per-layer metric of the name IS the reading (one definition: its
    # file's spans through its reader), so the identity holds between the
    # result lines of a run too
    assert got["startup_to_mesh_s"] == program_spans.read(
        {"program_spans": SCENARIOS[scenario]["spans"]},
        layers.spec("startup_to_mesh_s")["params"],
    )
    assert residual(got, scenario) < 1e-9


def check_no_mesh_span_fails_and_names_it(scenario):
    r = SCENARIOS[scenario]
    for missing in STARTUP_SPANS:
        spans = [e for e in r["spans"] if e[0] != missing]
        with pytest.raises(SystemExit) as failure:
            readings.setup_parts(
                r["t0"], r["window_open"], spans, r["check_s"], r["wait_s"]
            )
        # a message, not a number: nothing falls back to process start
        assert missing in str(failure.value)
        assert "nothing measured" in str(failure.value)
    # an event of that name that is no span (no seconds) is no span
    events = [(e[0], "event", e[2], 0.0, e[4]) for e in r["spans"]]
    with pytest.raises(SystemExit):
        readings.startup_to_mesh_s(events)


def check_the_rehearsal_prints_the_three_names(scenario):
    cell = SCENARIOS[scenario]["cell"]
    traffic = {w["name"]: w["traffic"] for w in manifest()["workloads"]}[cell]
    assert build.load_json(os.path.join(
        build.ROOT, "traffic", f"{traffic}.json"
    ))["scenario"] == scenario
    proc = run_cell(cell, 1, 1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(PARTS) | set(parts_of(scenario)) <= set(line["metrics"])
    notes = [json.loads(x) for x in proc.stdout.splitlines()[:-1]
             if x.startswith("{")]
    (setup,) = [n["setup"] for n in notes if "setup" in n]
    assert residual(setup, scenario) < 0.01
    assert all(setup[k] > 0 for k in setup)
    # the check is timed around the whole call: no shorter than the
    # check's own clock, which starts after its rows are made
    (reference,) = [n["reference"] for n in notes if "reference" in n]
    assert setup["reference_check_s"] >= reference["seconds"]


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
@pytest.mark.parametrize("check", [
    check_window_less_mesh_less_check, check_the_parts_add_up,
    check_no_mesh_span_fails_and_names_it,
    check_the_rehearsal_prints_the_three_names,
], ids=lambda f: f.__name__[len("check_"):])
def test_setup_is_read_from_the_mesh_to_the_window_less_the_check(
        scenario, check):
    check(scenario)
