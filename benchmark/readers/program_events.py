"""A number out of the program's own events (``common/telemetry.py``): the
trainer's, in this process.

``params.name`` is the event's name and ``params.attr`` the attribute to
read; ``params.reduce`` (``median`` / ``max``) folds the values of the
window's events (those whose ``step`` attribute lies in the window, as
``program_spans`` finds it), ``params.scale`` converts the unit.  A program
that books no such event, or none with that attribute (an older commit),
gives nothing, and the metric is left out of the line."""

import statistics

from benchmark.readers import program_spans

_FOLDS = {"median": statistics.median, "max": max}


def read(evidence, params):
    steps = program_spans.window_steps(evidence)
    if steps is None:
        return None
    values = [
        e[4][params["attr"]] for e in program_spans.spans_of(evidence)
        if e[0] == params["name"] and e[1] == "event"
        and params["attr"] in e[4]
        and steps[0] <= e[4].get("step", -1) <= steps[1]
    ]
    if not values:
        return None
    return float(_FOLDS[params.get("reduce", "median")](values)) * params.get(
        "scale", 1.0
    )
