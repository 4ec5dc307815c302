"""From the benchmark's data files to the program's objects.

A configuration file holds the published keys as they are run, a
``to_program`` map from the program's ``TransformerConfig`` fields to
those keys, and a ``program`` group of the settings no publication fixes
(dtype, kernel choice, remat).  Nothing here knows a configuration's or a
cell's name.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

ROOT = os.path.dirname(os.path.abspath(__file__))


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def manifest() -> Dict[str, Any]:
    return load_json(os.path.join(os.path.dirname(ROOT), "BENCHMARK.json"))


def model_group(config: Dict[str, Any]) -> Dict[str, Any]:
    """``TransformerConfig`` fields as plain numbers and strings."""
    model = {
        field: config[key] for field, key in config["to_program"].items()
    }
    model.update(config.get("program", {}))
    return model


def global_batch(config: Dict[str, Any], traffic: Dict[str, Any],
                 chips: int) -> int:
    run = {**config.get("run", {}), **traffic.get("run", {})}
    return int(run["sequences_per_chip"]) * chips


def seq_len(config: Dict[str, Any], traffic: Dict[str, Any]) -> int:
    run = {**config.get("run", {}), **traffic.get("run", {})}
    return int(run["seq_len"])


def transformer_config(model: Dict[str, Any], max_seq_len: int):
    import jax.numpy as jnp

    from dlrover_tpu.models.transformer import TransformerConfig

    kwargs = dict(model)
    for key in ("dtype", "param_dtype", "logits_dtype"):
        if key in kwargs:
            kwargs[key] = getattr(jnp, kwargs[key])
    kwargs.setdefault("max_seq_len", max_seq_len)
    return TransformerConfig(**kwargs)


def peak_for(kind: str) -> Dict[str, float]:
    """The published peaks of ``kind``; an unknown kind is an error."""
    table = load_json(os.path.join(ROOT, "peaks.json"))
    if kind.startswith("_") or kind not in table:
        raise SystemExit(
            f"benchmark: no published peaks for device kind {kind!r} in "
            "benchmark/peaks.json; nothing measured"
        )
    return table[kind]
