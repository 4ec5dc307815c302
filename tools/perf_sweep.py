"""Single-chip perf sweep for the GPT-2 1.5B bench configuration.

Usage:  python tools/perf_sweep.py remat=full batch=16 [steps=6] [trace=DIR]

Prints one JSON line per run: step time, tokens/s/chip, MFU, peak HBM.
Used to produce PROFILE.md; not part of the test suite.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

SEQ_LEN = 1024
REFERENCE_HFU = 0.656


def run(remat: str, batch: int, steps: int, opt_name: str, trace: str | None,
        attention_impl: str = "flash", ce_chunks: int = 0,
        block_q: int = 1024, block_kv: int = 1024) -> None:
    from dlrover_tpu.models.gpt2 import gpt2_config
    from dlrover_tpu.models.transformer import TransformerLM
    from dlrover_tpu.parallel import rules as lr
    from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
    from dlrover_tpu.trainer import train_lib
    from bench import flops_per_token
    from dlrover_tpu.auto.tune import chip_specs

    config = gpt2_config(
        "1.5b", max_seq_len=SEQ_LEN, param_dtype=jnp.bfloat16,
        remat=remat, attention_impl=attention_impl,
        flash_block_q=block_q, flash_block_kv=block_kv,
    )
    model = TransformerLM(config)
    mesh = build_mesh(ParallelConfig(data=-1, fsdp=1))
    opt = train_lib.make_optimizer(opt_name, learning_rate=1e-4)
    train = train_lib.build_sharded_train(
        model, opt, mesh, lr.DEFAULT_RULES,
        global_batch_size=batch, seq_len=SEQ_LEN, ce_chunks=ce_chunks,
    )
    state = train.init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, config.vocab_size, size=(batch, SEQ_LEN + 1),
                          dtype=np.int32)
    data = train_lib.shard_batch(
        {"inputs": tokens[:, :-1].copy(), "targets": tokens[:, 1:].copy()},
        train,
    )

    for _ in range(2):
        state, metrics = train.step(state, data)
    float(metrics["loss"])

    if trace:
        jax.profiler.start_trace(trace)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = train.step(state, data)
    float(metrics["loss"])
    dt = (time.perf_counter() - t0) / steps
    if trace:
        jax.profiler.stop_trace()

    tok_s = batch * SEQ_LEN / dt
    ftok = flops_per_token(config)
    peak = chip_specs()[0] / 1e12
    mfu = tok_s * ftok / 1e12 / peak
    base = REFERENCE_HFU * peak * 1e12 / ftok
    mem = jax.devices()[0].memory_stats() or {}
    print(json.dumps({
        "remat": remat, "batch": batch, "opt": opt_name, "ce": ce_chunks,
        "blocks": [block_q, block_kv],
        "step_s": round(dt, 4), "tok_s_chip": round(tok_s, 1),
        "mfu": round(mfu, 4), "vs_baseline": round(tok_s / base, 4),
        "peak_hbm_gb": round(mem.get("peak_bytes_in_use", 0) / 2**30, 2),
    }), flush=True)


if __name__ == "__main__":
    kv = dict(a.split("=", 1) for a in sys.argv[1:])
    run(
        remat=kv.get("remat", "full"),
        batch=int(kv.get("batch", 16)),
        steps=int(kv.get("steps", 6)),
        opt_name=kv.get("opt", "adafactor"),
        trace=kv.get("trace"),
        attention_impl=kv.get("attn", "flash"),
        ce_chunks=int(kv.get("ce", 0)),
        block_q=int(kv.get("bq", 1024)),
        block_kv=int(kv.get("bkv", 1024)),
    )
