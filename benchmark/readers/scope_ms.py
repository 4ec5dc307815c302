"""Milliseconds of one step the device spends in the ops of a scope.

Own device time (containers less their children) of the ops whose
``name@scope`` matches ``params.match`` (a regular expression; a negative
look-ahead leaves ops out, as the kernels under a scope), summed within
each execution of the step program, median over the traced steps and
chips.  A trace in which nothing matches (a program without that scope)
gives nothing, and the metric is left out of the line."""

import statistics

from benchmark import trace_reduce


def read(evidence, params):
    trace = evidence.get("trace")
    if not trace:
        return None
    seconds = [
        s for s in trace_reduce.per_step(
            trace, evidence.get("step_module", ""),
            lambda ops: trace_reduce.scope_seconds(ops, params["match"]),
        ) if s > 0
    ]
    if not seconds:
        return None
    return 1e3 * statistics.median(seconds)
