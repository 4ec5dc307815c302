"""A kernel's share of its roofline, in percent, with the cost taken from
the module ``params.module`` names under ``benchmark/`` (``params.cost``
the function in it): ``readers/kernel_roofline.py`` for any cost module,
so that a configuration brings its arithmetic as one module and its
metrics as data.  A model without the fields that cost function needs (an
older program's configuration) gives nothing, and the metric is left out
of the line."""

import importlib
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
    module = importlib.import_module(f"benchmark.{params['module']}")
    try:
        cost = getattr(module, params["cost"])(
            evidence["model"], evidence["seq_len"],
            evidence["sequences_per_chip"],
        )
    except KeyError:
        return None
    if not cost["flops"]:
        return None
    floor = flops.roofline_seconds(cost, evidence["peak"])["seconds"]
    return 100.0 * floor / statistics.median(seconds)
