"""Benchmark: GPT-2 1.5B training throughput, tokens/sec/chip (BASELINE.json).

Runs the sharded train step on the attached TPU chip(s) and prints one JSON
line PER ENTRY (first line: the headline baseline config; further entries
exercise one knob each).  Every line names the device it ran on
(``platform``, ``device_kind``, ``device_count``).  Without a TPU it prints
one line saying so and exits non-zero: a number from another backend is
never printed under a device metric's name.  An entry that fails ends the
run with its traceback and a non-zero exit.  ``--max-entries N`` truncates
the sweep for budget-bound callers.

``vs_baseline`` compares hardware FLOPs utilization (HFU) against the
reference's best published HFU (Llama2-7B FSDP at 65.6% on A100,
`BASELINE.md` — the reference trains with activation checkpointing, so its
65.6% *includes* recompute FLOPs).  Comparing HFU to HFU is the
apples-to-apples form; the model-FLOPs view (MFU, recompute not counted) is
reported alongside in ``detail`` with its own ``vs_baseline_mfu``.
See PROFILE.md for the measured step breakdown behind the chosen config.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.utils.devices import device_fields

MODEL_SIZE = "1.5b"
SEQ_LEN = 1024
PER_CHIP_BATCH = 16     # measured fastest (24/32 spill or OOM, 8 underfills)
REMAT = "flash_only"    # measured fastest policy that fits (PROFILE.md):
                        # saves the flash kernel's o+lse so the backward
                        # skips the attention-forward recompute entirely
CE_CHUNKS = 0           # after the r3 kernel work the plain fused CE beats
                        # the chunked scan at this shape (PROFILE.md table)
WARMUP_STEPS = 2
MEASURE_STEPS = 10
# MoE sweep entry: iso-FLOP with the dense baseline — top_k experts of
# (dense d_ff / top_k) width activate per token, so the MLP matmul FLOPs
# per token match the dense entry exactly; the delta is routing + dispatch.
MOE_EXPERTS = 8
MOE_TOP_K = 2
MOE_CAPACITY = 1.25
REFERENCE_HFU = 0.656   # Llama2-7B FSDP, BASELINE.md best utilization claim

def flops_per_token(config) -> float:
    """Model FLOPs/token: 6*N matmul plus attention score/value FLOPs."""
    n = config.num_params()
    attn = 12 * config.num_layers * config.d_model * SEQ_LEN  # fwd+bwd qk+av
    return 6 * n + attn


def recompute_flops_per_token(config, remat: str) -> float:
    """Extra hardware FLOPs/token the backward re-executes under ``remat``.

    attn_out saves the post-projection attention output, so the backward
    re-runs per layer: the fused QKV projection, both MLP matmuls, and the
    attention forward (the out-projection forward is skipped).  This is what
    HFU counts on top of model FLOPs — the same accounting the reference's
    65.6% HFU uses for its activation-checkpointed runs.
    """
    if remat == "none":
        return 0.0
    d = config.d_model
    hd = config.resolved_head_dim * config.num_heads
    ff = config.resolved_d_ff
    qkv = 2 * d * 3 * hd
    wi = 2 * d * ff
    wo = 2 * ff * d
    attn_fwd = 4 * d * SEQ_LEN
    out_proj = 2 * hd * d
    per_layer = {
        "full": qkv + wi + wo + attn_fwd + out_proj,
        "attn_out": qkv + wi + wo + attn_fwd,
        # flash_only saves the attention kernel's o+lse: the backward skips
        # the attention forward entirely but re-runs the out-projection
        # (its output, attn_out, is not saved under this policy)
        "flash_only": qkv + wi + wo + out_proj,
        # flash_res saves attn_out too: out-projection recompute also gone
        "flash_res": qkv + wi + wo,
        # saved mlp_out additionally skips the wo forward recompute
        "branch_out": qkv + wi + attn_fwd,
        "dots": attn_fwd,
    }.get(remat, qkv + wi + wo + attn_fwd)
    return per_layer * config.num_layers


def _entry_metric(entry: str) -> str:
    if entry == "baseline":
        return "gpt2-1.5b tokens/sec/chip"
    return f"gpt2-1.5b tokens/sec/chip ({entry})"


# The sweep: each entry is one knob variation on the headline config.
# grad_accum=4 exercises the microbatch engine (scan overhead + deferred
# reduce) at identical global batch — the value SHOULD track baseline;
# the gap is the engine's real cost on this backend.  zero1 exercises the
# cross-replica sharded weight update (dp > 1: reduce-scatter + sharded
# update + all-gather; on a single chip it degrades to the baseline step).
BENCH_ENTRIES = (
    ("baseline", {"grad_accum": 1, "reduce_quant": "none"}),
    ("grad_accum=4", {"grad_accum": 4, "reduce_quant": "none"}),
    ("zero1", {"grad_accum": 4, "reduce_quant": "none", "zero1": True}),
    ("zero1+overlap", {"grad_accum": 4, "reduce_quant": "none",
                       "zero1": True, "overlap": True}),
    # MoE at the dense entry's activated FLOPs (MOE_* constants): value
    # SHOULD track baseline; the gap is routing + dispatch overhead.
    ("moe", {"grad_accum": 1, "reduce_quant": "none", "moe": True}),
)


def _tpu_bench(entry: str, grad_accum: int, reduce_quant: str,
               zero1: bool = False, overlap: bool = False,
               moe: bool = False) -> None:
    from dlrover_tpu.auto import est_comm_time, pick_grad_accum
    from dlrover_tpu.auto.tune import chip_specs
    from dlrover_tpu.models.gpt2 import gpt2_config
    from dlrover_tpu.models.transformer import TransformerLM
    from dlrover_tpu.parallel import rules as lr
    from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
    from dlrover_tpu.trainer import train_lib

    n_chips = len(jax.devices())
    config = gpt2_config(
        MODEL_SIZE,
        max_seq_len=SEQ_LEN,
        param_dtype=jnp.bfloat16,
        remat=REMAT,
        attention_impl="flash",
    )
    if moe:
        # Iso-FLOP with the dense baseline: top_k experts of
        # (dense d_ff / top_k) width per token, GSPMD einsum dispatch
        # (the expert axis is 1 on a single-chip bench).
        config = dataclasses.replace(
            config, num_experts=MOE_EXPERTS, top_k=MOE_TOP_K,
            capacity_factor=MOE_CAPACITY,
            d_ff=config.resolved_d_ff // MOE_TOP_K,
            moe_dispatch="einsum",
        )
    model = TransformerLM(config)
    parallel = ParallelConfig(data=-1, fsdp=1)
    mesh = build_mesh(parallel)
    # Single-chip 1.5B: adafactor keeps optimizer state sub-GB so params,
    # grads and activations fit HBM (the reference benches AdamW on 80GB
    # A100s; on 16GB v5e factored second moments are the idiomatic choice).
    opt = train_lib.make_optimizer("adafactor", learning_rate=1e-4)
    global_batch = PER_CHIP_BATCH * n_chips
    train = train_lib.build_sharded_train(
        model, opt, mesh, lr.DEFAULT_RULES,
        global_batch_size=global_batch, seq_len=SEQ_LEN,
        ce_chunks=CE_CHUNKS,
        grad_accum=grad_accum, reduce_quant=reduce_quant, zero1=zero1,
    )
    state = train.init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    tokens = rng.integers(
        0, config.vocab_size, size=(global_batch, SEQ_LEN + 1), dtype=np.int32
    )
    batch = train_lib.shard_batch(
        {"inputs": tokens[:, :-1].copy(), "targets": tokens[:, 1:].copy()},
        train,
    )

    for _ in range(WARMUP_STEPS):
        state, metrics = train.step(state, batch)
    # Reading the last step's loss on the host returns only when that step,
    # and so every step before it, has run; it is also the finite check.
    float(metrics["loss"])

    t0 = time.perf_counter()
    for _ in range(MEASURE_STEPS):
        state, metrics = train.step(state, batch)
    final_loss = float(metrics["loss"])
    dt = time.perf_counter() - t0

    tokens_per_step = global_batch * SEQ_LEN
    tokens_per_sec = tokens_per_step * MEASURE_STEPS / dt
    tokens_per_sec_chip = tokens_per_sec / n_chips

    # MoE FLOPs accounting uses the activated dense-equivalent shape
    # (num_params counts ALL experts; only top_k of them run per token).
    flops_cfg = config
    if moe:
        flops_cfg = dataclasses.replace(
            config, num_experts=0,
            d_ff=config.resolved_d_ff * config.top_k,
        )
    ftok = flops_per_token(flops_cfg)
    ftok_hw = ftok + recompute_flops_per_token(flops_cfg, REMAT)
    peak = chip_specs()[0] / 1e12
    mfu = tokens_per_sec_chip * ftok / 1e12 / peak
    hfu = tokens_per_sec_chip * ftok_hw / 1e12 / peak
    baseline_tokens_per_sec_chip = REFERENCE_HFU * peak * 1e12 / ftok

    detail = {
        "n_chips": n_chips,
        "global_batch": global_batch,
        "seq_len": SEQ_LEN,
        "remat": REMAT,
        "step_time_s": round(dt / MEASURE_STEPS, 4),
        "achieved_model_tflops_per_chip": round(
            tokens_per_sec_chip * ftok / 1e12, 2
        ),
        "achieved_hw_tflops_per_chip": round(
            tokens_per_sec_chip * ftok_hw / 1e12, 2
        ),
        "mfu": round(mfu, 4),
        "hfu": round(hfu, 4),
        "vs_baseline_basis": "hfu / reference_hfu (both count "
                             "activation-recompute FLOPs)",
        "vs_baseline_mfu": round(
            tokens_per_sec_chip / baseline_tokens_per_sec_chip, 4
        ),
        "loss": final_loss,
    }
    if grad_accum > 1:
        # Price the knob alongside the measurement: what the auto-tuner's
        # activation-memory model would pick here, and the modeled cost of
        # the deferred DP reduce on both wire formats.
        detail.update({
            "grad_accum": grad_accum,
            "reduce_quant": reduce_quant,
            "auto_pick_grad_accum": pick_grad_accum(
                config, parallel, global_batch, SEQ_LEN,
                remat=REMAT, optimizer="adafactor", zero1=zero1,
            ),
            "est_reduce_s_full": round(
                est_comm_time(config, parallel, "none"), 6
            ),
            "est_reduce_s_int8": round(
                est_comm_time(config, parallel, "int8"), 6
            ),
        })
    if moe:
        from dlrover_tpu.parallel.quantized_collectives import a2a_wire_bytes

        # Dispatch wire pricing next to the measurement: the per-device
        # capacity-padded expert tensor on both formats (what an expert
        # axis would move; PROFILE.md round 19's cost model).
        elems = int(
            config.capacity_factor * config.top_k
            * PER_CHIP_BATCH * SEQ_LEN * config.d_model
        )
        detail["moe"] = {
            "num_experts": config.num_experts,
            "top_k": config.top_k,
            "capacity_factor": config.capacity_factor,
            "dispatch": config.moe_dispatch,
            "iso_flop_dense_d_ff": config.resolved_d_ff * config.top_k,
            "a2a_wire_bytes_fp32": a2a_wire_bytes(elems, "none"),
            "a2a_wire_bytes_int8": a2a_wire_bytes(elems, "int8"),
        }
    if zero1:
        detail["zero1"] = bool(train.zero1)
        if overlap:
            # The overlap engine's bucket plan + the overlap-aware comm
            # pricing next to the measurement (PROFILE.md round 16).
            detail["overlap"] = bool(train.overlap)
            detail["overlap_plan"] = train.overlap_plan
            detail["est_comm_s_overlap"] = round(
                est_comm_time(config, parallel, reduce_quant,
                              overlap=True, grad_accum=grad_accum), 6
            )
        if train.zero1_stats:
            # The sharded-update memory story (opt-state MB/device before
            # vs after the data-axis split) — PROFILE.md's memory model.
            detail["zero1_stats"] = {
                k: round(v, 1) if isinstance(v, float) else v
                for k, v in train.zero1_stats.items()
            }
    out = {
        "metric": _entry_metric(entry),
        "value": round(tokens_per_sec_chip, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(hfu / REFERENCE_HFU, 4),
        **device_fields(),
        "detail": detail,
    }
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    args = argparse.ArgumentParser()
    args.add_argument(
        "--max-entries", type=int, default=0,
        help="run only the first N sweep entries (0 = all)",
    )
    opts = args.parse_args(argv)
    entries = BENCH_ENTRIES
    if opts.max_entries > 0:
        entries = entries[: opts.max_entries]
    device = device_fields()
    if device["platform"] != "tpu":
        print(
            f"bench.py measures a TPU and found platform "
            f"{device['platform']!r} ({device['device_kind']}); "
            "nothing measured"
        )
        return 1
    from dlrover_tpu.runtime import compile_cache

    compile_cache.enable()
    for entry, knobs in entries:
        _tpu_bench(entry, **knobs)
    return 0


if __name__ == "__main__":
    sys.exit(main())
