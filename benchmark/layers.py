"""Per-layer metrics as data: ``layer_metrics/<name>.json`` names a reader
module under ``readers/`` and its parameters; the reader takes the metric
from the run's evidence (spans, counters, the trace).  A reader that finds
nothing to read returns ``None`` and the metric is left out of the line.
"""

from __future__ import annotations

import importlib
import os
from typing import Any, Dict, List

from benchmark import build


def cell_entries(manifest: Dict, cell: str, group: str) -> List[Dict]:
    return [
        m for m in manifest[group]
        if "workloads" not in m or cell in m["workloads"]
    ]


def spec(name: str) -> Dict[str, Any]:
    return build.load_json(
        os.path.join(build.ROOT, "layer_metrics", f"{name}.json")
    )


def compute(manifest: Dict, cell: str, evidence: Dict) -> Dict[str, Dict]:
    out = {}
    for entry in cell_entries(manifest, cell, "per_layer"):
        s = spec(entry["name"])
        reader = importlib.import_module(f"benchmark.readers.{s['reader']}")
        value = reader.read(evidence, s.get("params", {}))
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out
