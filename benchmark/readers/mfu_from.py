"""Model-FLOP utilisation with the FLOPs a token counted by the module
``params.module`` names under ``benchmark/`` (its
``model_flops_per_token(model, seq_len)``) x tokens/s/chip over the chip's
published bf16 peak: ``readers/mfu.py`` for any cost module.  A model
without the fields that module needs gives nothing."""

import importlib


def read(evidence, params):
    summary, peak = evidence.get("summary"), evidence.get("peak")
    model = evidence.get("model")
    if not summary or not peak or not model:
        return None
    module = importlib.import_module(f"benchmark.{params['module']}")
    try:
        per_token = module.model_flops_per_token(model, evidence["seq_len"])
    except KeyError:
        return None
    return (
        per_token * summary["tokens_per_s_chip"] / peak["bf16_flops_per_s"]
    )
