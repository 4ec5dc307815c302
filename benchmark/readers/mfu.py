"""Model-FLOP utilisation: the benchmark's own FLOPs per token (recompute
not counted; a sparse MLP counts its ``top_k`` experts) x tokens/s/chip
over the chip's published bf16 peak."""

from benchmark import flops


def read(evidence, params):
    summary, peak = evidence.get("summary"), evidence.get("peak")
    if not summary or not peak:
        return None
    per_token = flops.model_flops_per_token(
        evidence["model"], evidence["seq_len"]
    )
    return (
        per_token * summary["tokens_per_s_chip"]
        / peak["bf16_flops_per_s"]
    )
