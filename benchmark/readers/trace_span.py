"""Seconds of a program span as the profiler saw it: the ``dlrover:<name>``
rows of the run's own trace (an open span of the program is a
``TraceAnnotation`` on the device trace's clock), the median over the
traced window.

The trace is the one the run wrote under its directory,
``.bench_runs/run<pid>/trace`` (``benchmark/run.py``); only a traced run
has one.  A program whose spans are not annotations (an older commit)
leaves no such row, and the metric is left out of the line.
"""

import os
import statistics

from benchmark import build, trace_reduce

# The program's ``telemetry.TRACE_PREFIX``; spelt out, since the reader also
# runs against programs that have no such name yet.
PREFIX = "dlrover:"


def run_trace_dir() -> str:
    return os.path.join(
        os.path.dirname(build.ROOT), ".bench_runs", f"run{os.getpid()}",
        "trace",
    )


def span_rows(path: str):
    """``[name, "", start_ns, dur_ns]`` of every ``dlrover:`` host row."""
    import jax

    rows = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            rows.extend(
                [e.name[len(PREFIX):], "", e.start_ns, e.duration_ns]
                for e in line.events if e.name.startswith(PREFIX)
            )
    return rows


def read(evidence, params):
    if not evidence.get("trace_reduced"):
        return None
    try:
        rows = span_rows(trace_reduce.find_xplane(run_trace_dir()))
    except FileNotFoundError:
        return None
    seconds = [r[3] * 1e-9 for r in rows if r[0] == params["name"]]
    if not seconds:
        return None
    return statistics.median(seconds) * params.get("scale", 1.0)
