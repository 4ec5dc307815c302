"""A number out of the program's own spans (``common/telemetry.py``): the
trainer's, in this process.

``params.names`` are span names; ``params.reduce`` folds each name's
durations (``median`` / ``first``) and the names' results are added;
``params.scale`` converts the unit.  ``params.window`` keeps only the spans
of the window's steps (by their ``step`` attribute).

The spans are ``evidence["program_spans"]`` (wire events: name, kind,
wall, seconds, attrs) where a run hands them over; else whatever the
recorder of this process still holds.  Ahead of either go
``evidence["startup_spans"]``, the trainer's start-up spans, which the
worker keeps when it builds the trainer: the ring ships to the master with
the first report, and a trainer that is a child of the launcher hands
nothing else across.  A program without these spans (an older commit) gives
nothing, and the metric is left out of the line.
"""

import statistics

_FOLDS = {"median": statistics.median, "first": lambda values: values[0]}


def window_steps(evidence):
    """``(first, last)`` step of the window, from the run's own series:
    the pair of reading ends the summary's window was taken between."""
    summary = evidence.get("summary") or {}
    ids, ends = evidence.get("step_ids"), evidence.get("step_ends")
    if not ids or not ends or "window_s" not in summary:
        return None
    for i in range(len(ends)):
        for j in range(i + 1, len(ends)):
            if (ids[j] - ids[i] == summary["steps"]
                    and ends[j] - ends[i] == summary["window_s"]):
                return ids[i] + 1, ids[j]
    return None


def spans_of(evidence):
    kept = list(evidence.get("startup_spans") or [])
    if evidence.get("program_spans") is not None:
        rest = evidence["program_spans"]
    elif not evidence.get("step_ids"):
        rest = []  # no run to belong to
    else:
        from dlrover_tpu.common import telemetry

        rest = telemetry.recorder().peek()
    names = {e[0] for e in kept}
    return kept + [e for e in rest if e[0] not in names]


def read(evidence, params):
    spans = [e for e in spans_of(evidence) if e[1] == "span"]
    if params.get("window"):
        steps = window_steps(evidence)
        if steps is None:
            return None
        spans = [
            e for e in spans if steps[0] <= e[4].get("step", -1) <= steps[1]
        ]
    total = 0.0
    for name in params["names"]:
        seconds = [e[3] for e in spans if e[0] == name]
        if not seconds:
            return None
        total += _FOLDS[params.get("reduce", "median")](seconds)
    return total * params.get("scale", 1.0)
