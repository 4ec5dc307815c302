"""The arithmetic of a run's readings: pure functions on plain numbers.

A *reading* is a block of k consecutive steps closed by one host read of
their losses (k = 1 where the trainer reads every step's loss, k = the
trainer's ``metrics_lag`` where it defers them), timed on the host clock
from the end of the reading before it to its own end, and divided by k.
The window is a whole number of steps: it opens at a step's end and closes
at the first step's end at or after the asked length.  No step is cut, and
none is counted by a clock.  The throughput of a run is all the window's
tokens over all its seconds, so a stall inside the window shows in it.
The rate of the MEDIAN reading, which one slow step cannot move, and the
worst reading over the median stand beside it, so that a run that reads
low can be told apart: one stall, or every step slower.

Set-up is what a job's owner waits for and the program decides: from the
mesh built to the window's first instant, less what the benchmark puts
there itself and no user runs: its reference check and, in the save cell,
its wait for the first persist (``setup_parts``).
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence


def startup_to_mesh_s(spans: Sequence[Sequence]) -> float:
    """Seconds from the first trainer's process start, as the OS booked it,
    to its mesh built, as the per-layer metric of that name reads them from
    the program's wire events (name, kind, wall, seconds, attrs): its
    file's span names, through its reader.  A run whose program did not
    record them has no mesh instant, and no other instant stands in for
    it."""
    from benchmark import layers
    from benchmark.readers import program_spans

    params = layers.spec("startup_to_mesh_s")["params"]
    value = program_spans.read({"program_spans": spans}, params)
    if value is None:
        absent = [
            name for name in params["names"]
            if not any(e[0] == name and e[1] == "span" for e in spans)
        ]
        raise SystemExit(
            f"benchmark: the program recorded no {absent} span, so the "
            "instant its mesh was built is unknown; setup_s is read "
            "from there and from nowhere else; nothing measured"
        )
    return value


def setup_parts(
    t0: float, window_open: float, spans: Sequence[Sequence],
    reference_check_s: float, persist_wait_s: Optional[float] = None,
) -> Dict[str, float]:
    """``setup_s`` and what is taken out of it, for every scenario.

    ``process_to_window_s`` runs from ``t0``, the start of the process the
    command started, to the window's first instant.  Out of it go
    ``startup_to_mesh_s`` (interpreter, imports and the accelerator
    runtime's start, up to the mesh: the machine's, in two modes seconds
    apart on equal programs), ``reference_check_s`` (the benchmark's own
    check, timed around the whole call) and, in a scenario that has one,
    ``persist_wait_s`` (the seconds the harness holds the trainer until the
    agent has persisted the first save, so that the next save is a reading:
    no job waits there).  What stays is ``setup_s``: build, init, trace,
    lower, compile or cache read, seeding and the steps until the window
    opens and, where the trainer is a child of the program's launcher, the
    launcher's, master's and agent's start and the first save, which makes
    the arena.  The parts add up by construction."""
    process_to_window = window_open - t0
    parts = {
        "startup_to_mesh_s": startup_to_mesh_s(spans),
        "reference_check_s": reference_check_s,
    }
    if persist_wait_s is not None:
        parts["persist_wait_s"] = persist_wait_s
    return {
        "process_to_window_s": process_to_window, **parts,
        "setup_s": process_to_window - sum(parts.values()),
    }


def window_open_index(
    ends: Sequence[float], ids: Sequence[int],
    compile_ends: Sequence[float], warm_steps: int,
) -> Optional[int]:
    """Index of the reading whose end opens the window: the first with at
    least ``warm_steps`` whole steps since the last compilation before it.
    ``None`` while none qualifies.  ``ends[i]`` is the host-clock end of
    reading ``i`` and ``ids[i]`` the number of its last step; a reading
    covers the steps ``ids[i-1] + 1 .. ids[i]``."""
    for i, t in enumerate(ends):
        last_compile = max((c for c in compile_ends if c <= t), default=None)
        whole = sum(
            ids[j] - ids[j - 1] for j in range(1, i + 1)
            if last_compile is None or ends[j - 1] >= last_compile
        )
        if whole >= warm_steps:
            return i
    return None


def window_close_index(
    ends: Sequence[float], open_index: int, seconds: float
) -> Optional[int]:
    """Index of the first reading's end at or after ``seconds`` past the
    window's opening, and at least one reading after it."""
    t_open = ends[open_index]
    for i in range(open_index + 1, len(ends)):
        if ends[i] - t_open >= seconds:
            return i
    return None


def step_readings(
    ends: Sequence[float], ids: Sequence[int], open_index: int,
    close_index: int,
) -> List[float]:
    """Seconds per step of each reading inside the window: a reading of k
    steps closed by one read gives its seconds over k."""
    return [
        (ends[i] - ends[i - 1]) / (ids[i] - ids[i - 1])
        for i in range(open_index + 1, close_index + 1)
    ]


def compiles_in_window(
    compile_ends: Sequence[float], t_open: float, t_close: float
) -> int:
    return sum(1 for c in compile_ends if t_open < c <= t_close)


def tokens_per_s_chip(
    steps: int, window_s: float, tokens_per_step: int, chips: int
) -> float:
    """All the tokens of the window's steps / all its seconds / chips."""
    return tokens_per_step * steps / window_s / chips


def tokens_per_s_chip_median_step(
    readings: Sequence[float], tokens_per_step: int, chips: int
) -> float:
    """tokens of one global step / median seconds of a step / chips: the
    rate the run would have had if every step were its median step."""
    return tokens_per_step / statistics.median(readings) / chips


def worst_over_median(readings: Sequence[float]) -> float:
    return max(readings) / statistics.median(readings)


def summarize(
    ends: Sequence[float], ids: Sequence[int],
    compile_ends: Sequence[float], losses: Dict[int, float],
    open_index: int, close_index: int, tokens_per_step: int, chips: int,
) -> Dict[str, object]:
    """Everything a train cell derives from its series of reading ends.
    ``losses`` maps a step's number to its loss."""
    import math

    readings = step_readings(ends, ids, open_index, close_index)
    window_s = ends[close_index] - ends[open_index]
    steps = ids[close_index] - ids[open_index]
    compiles = compiles_in_window(
        compile_ends, ends[open_index], ends[close_index]
    )
    failed = sum(
        1 for step in range(ids[open_index] + 1, ids[close_index] + 1)
        if not math.isfinite(losses.get(step, float("nan")))
    )
    return {
        "readings": readings,
        "steps_per_reading": [
            ids[i] - ids[i - 1]
            for i in range(open_index + 1, close_index + 1)
        ],
        "steps": steps,
        "window_s": window_s,
        "compiles_in_window": compiles,
        "failed": failed,
        "median_step_s": statistics.median(readings),
        "tokens_per_s_chip": tokens_per_s_chip(
            steps, window_s, tokens_per_step, chips
        ),
        "tokens_per_s_chip_median_step": tokens_per_s_chip_median_step(
            readings, tokens_per_step, chips
        ),
        "step_s_worst_over_median": worst_over_median(readings),
        "ok": compiles == 0 and failed == 0,
    }
