"""Each scenario end to end on the CPU at the tiny presets.

A rehearsal runs the whole command (``python -m benchmark.run``), kernels
interpreted, and prints no number: every metric's value is null and the
line says ``"rehearsal": true``.  What is checked is the control flow and
the shape of the result line, never a speed.
"""

import functools
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LIMIT_S = 420


@functools.lru_cache(maxsize=None)
def run_cell(cell, trace, devices, rehearsal=True):
    """One run of the command; a worker runs each (cell, trace) once and
    every test that reads it shares the result."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = REPO
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    cmd = [sys.executable, "-m", "benchmark.run", "--workload", cell,
           "--seed", "3000000007", "--seconds", "1", "--trace", str(trace)]
    if rehearsal:
        cmd.append("--rehearsal")
    return subprocess.run(
        cmd, cwd=REPO, env=env, capture_output=True, text=True,
        timeout=LIMIT_S,
    )


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


CELLS = [(w["name"], w["chips"]) for w in manifest()["workloads"]]


def check_result_line(cell, chips, trace):
    proc = run_cell(cell, trace, chips)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) - {"breakdown"} == {
        "correct", "attempted", "failed", "metrics", "device", "rehearsal"
    }
    assert line["correct"] is True and line["failed"] == 0, (
        proc.stdout[-3000:]
    )
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    group = "per_layer" if trace else "end_to_end"
    allowed = {
        m["name"] for m in manifest()[group]
        if "workloads" not in m or cell in m["workloads"]
    }
    assert line["metrics"] and set(line["metrics"]) <= allowed
    if not trace:
        assert set(line["metrics"]) == allowed
    # no number from the CPU under a device metric's name
    assert all(m["value"] is None for m in line["metrics"].values())
    # the run's own readings are on an earlier line
    notes = [json.loads(x) for x in proc.stdout.splitlines()[:-1]
             if x.startswith("{")]
    assert any("readings_s" in n or "step_readings_s" in n for n in notes)


@pytest.mark.parametrize("cell,chips", CELLS)
def test_rehearsal_prints_one_result_line(cell, chips):
    """The untraced run of every cell.  The traced runs are checked in
    ``test_benchmark_program_spans.py``, the file that reads them for the
    span metrics too: one worker runs each traced rehearsal once."""
    check_result_line(cell, chips, 0)


def test_measuring_without_a_tpu_exits_non_zero_and_prints_no_result():
    cell = CELLS[0][0]
    proc = run_cell(cell, 0, 1, rehearsal=False)
    assert proc.returncode != 0
    assert "TPU is required" in proc.stderr
    assert not [x for x in proc.stdout.splitlines() if '"metrics"' in x]


def test_an_unknown_workload_exits_non_zero():
    proc = run_cell("no-such.cell", 0, 1)
    assert proc.returncode != 0 and not proc.stdout.strip()

