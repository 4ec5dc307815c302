"""Milliseconds of a step in which a collective runs on a chip and no
compute does, median over the traced steps and chips."""

import statistics

from benchmark import trace_reduce


def read(evidence, params):
    trace = evidence.get("trace")
    if not trace or evidence.get("chips", 1) < 2:
        return None
    values = trace_reduce.per_step(
        trace, evidence.get("step_module", ""),
        trace_reduce.exposed_collective_seconds,
    )
    if not values:
        return None
    return 1e3 * statistics.median(values)
