"""A number the run recorded itself: ``params.path`` walks the evidence,
``params.reduce`` (``median`` / ``max`` / ``mean``) folds a list, and
``params.scale`` converts the unit."""

import statistics

_FOLDS = {"median": statistics.median, "max": max, "mean": statistics.fmean}


def read(evidence, params):
    value = evidence
    for key in params["path"]:
        if not isinstance(value, dict) or value.get(key) is None:
            return None
        value = value[key]
    if isinstance(value, list):
        if not value:
            return None
        value = _FOLDS[params.get("reduce", "median")](value)
    return float(value) * params.get("scale", 1.0)
