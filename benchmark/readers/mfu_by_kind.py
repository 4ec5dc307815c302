"""Model-FLOP utilisation with the FLOPs a token counted by layer kind
(``benchmark/flops_by_kind.py``: full-attention layers as ``flops.py``
counts them, linear-attention layers by their own projections and the
chunked delta rule) x tokens/s/chip over the chip's published bf16 peak.
Only a model with a ``layer_pattern`` is read: any other is ``mfu``'s."""

from benchmark import flops_by_kind


def read(evidence, params):
    summary, peak = evidence.get("summary"), evidence.get("peak")
    model = evidence.get("model") or {}
    if not summary or not peak or not model.get("layer_pattern"):
        return None
    per_token = flops_by_kind.model_flops_per_token(
        model, evidence["seq_len"]
    )
    return (
        per_token * summary["tokens_per_s_chip"] / peak["bf16_flops_per_s"]
    )
