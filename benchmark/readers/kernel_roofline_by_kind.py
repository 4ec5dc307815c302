"""A kernel's share of its roofline, in percent, for a model whose layers
differ in kind: ``readers/kernel_roofline.py`` with the cost taken from
``benchmark/flops_by_kind.py`` (``params.cost`` names the function), which
counts a kernel over the layers of its kind only.  A model without the
fields that cost function needs (an older program's configuration) gives
nothing, and the metric is left out of the line."""

import statistics

from benchmark import flops, flops_by_kind, trace_reduce


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
    try:
        cost = getattr(flops_by_kind, params["cost"])(
            evidence["model"], evidence["seq_len"],
            evidence["sequences_per_chip"],
        )
    except KeyError:
        return None
    if not cost["flops"]:
        return None
    floor = flops.roofline_seconds(cost, evidence["peak"])["seconds"]
    return 100.0 * floor / statistics.median(seconds)
