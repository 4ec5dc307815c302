"""A kernel's share of its roofline, in percent.

The least time the chip could take for one step's calls (``params.cost``
names the function of ``benchmark/flops.py`` that counts their operations
and bytes from shapes; the larger of FLOPs over the bf16 peak and bytes
over the HBM peak) over the time the device spent in the ops whose
``name@scope`` matches ``params.match``, median over the traced steps."""

import statistics

from benchmark import flops, trace_reduce


def read(evidence, params):
    trace = evidence.get("trace")
    if not trace or not evidence.get("peak"):
        return None
    seconds = [
        s for s in trace_reduce.per_step(
            trace, evidence.get("step_module", ""),
            lambda ops: trace_reduce.scope_seconds(ops, params["match"]),
        ) if s > 0
    ]
    if not seconds:
        return None
    cost = getattr(flops, params["cost"])(
        evidence["model"], evidence["seq_len"], evidence["sequences_per_chip"]
    )
    floor = flops.roofline_seconds(cost, evidence["peak"])["seconds"]
    return 100.0 * floor / statistics.median(seconds)
