"""Dump the optimized HLO of the bench train step to a file.

Usage: python tools/dump_hlo.py out=/tmp/step.hlo [remat=attn_out] [batch=16]
Fetches the optimized module text through the compiled-executable API (no
--xla_dump_to directory to pick the right module out of).
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")

SEQ_LEN = 1024


def main():
    kv = dict(a.split("=", 1) for a in sys.argv[1:])
    batch = int(kv.get("batch", 16))
    remat = kv.get("remat", "attn_out")
    out_path = kv.get("out", "/tmp/step.hlo")

    from dlrover_tpu.models.gpt2 import gpt2_config
    from dlrover_tpu.models.transformer import TransformerLM
    from dlrover_tpu.parallel import rules as lr
    from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
    from dlrover_tpu.trainer import train_lib

    config = gpt2_config(
        "1.5b", max_seq_len=SEQ_LEN, param_dtype=jnp.bfloat16,
        remat=remat, attention_impl="flash",
        flash_block_q=1024, flash_block_kv=1024,
    )
    model = TransformerLM(config)
    mesh = build_mesh(ParallelConfig(data=-1, fsdp=1))
    opt = train_lib.make_optimizer(kv.get("opt", "adafactor"),
                                   learning_rate=1e-4)
    train = train_lib.build_sharded_train(
        model, opt, mesh, lr.DEFAULT_RULES,
        global_batch_size=batch, seq_len=SEQ_LEN,
        ce_chunks=int(kv.get("ce", 0)),
    )
    state = train.init(jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((batch, SEQ_LEN), jnp.int32)
    weights = jax.ShapeDtypeStruct((batch, SEQ_LEN), jnp.float32)
    data = {"inputs": tokens, "targets": tokens, "weights": weights}
    lowered = train.step_fn.lower(state, data)
    txt = lowered.compile().as_text()
    with open(out_path, "w") as f:
        f.write(txt)
    print(f"wrote {len(txt)} bytes to {out_path}")


if __name__ == "__main__":
    main()
