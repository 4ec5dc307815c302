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
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence


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
