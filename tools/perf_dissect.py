"""Decompose the bench step time: fwd / bwd / optimizer / CE / attention.

Usage: python tools/perf_dissect.py [batch=16] [remat=attn_out]
Prints one JSON line per phase.  Not part of the test suite.
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


def _sync(out):
    jax.block_until_ready(out)


def timed(fn, *args, steps=4):
    out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / steps


def main():
    kv = dict(a.split("=", 1) for a in sys.argv[1:])
    batch = int(kv.get("batch", 16))
    remat = kv.get("remat", "attn_out")

    from dlrover_tpu.models.gpt2 import gpt2_config
    from dlrover_tpu.models.transformer import TransformerLM
    from dlrover_tpu.parallel import rules as lr
    from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
    from dlrover_tpu.trainer import train_lib

    config = gpt2_config(
        "1.5b", max_seq_len=SEQ_LEN, param_dtype=jnp.bfloat16,
        remat=remat, attention_impl=kv.get("attn", "flash"),
    )
    model = TransformerLM(config)
    mesh = build_mesh(ParallelConfig(data=-1, fsdp=1))
    opt = train_lib.make_optimizer("adafactor", learning_rate=1e-4)
    train = train_lib.build_sharded_train(
        model, opt, mesh, lr.DEFAULT_RULES,
        global_batch_size=batch, seq_len=SEQ_LEN,
    )
    state = train.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, config.vocab_size, size=(batch, SEQ_LEN + 1),
                          dtype=np.int32)
    data = train_lib.shard_batch(
        {"inputs": tokens[:, :-1].copy(), "targets": tokens[:, 1:].copy()},
        train,
    )

    def report(name, secs):
        print(json.dumps({"phase": name, "time_s": round(secs, 4)}), flush=True)

    # full step (state is donated: thread it through the loop)
    state2, m = train.step(state, data)
    float(m["loss"])
    t0 = time.perf_counter()
    for _ in range(4):
        state2, m = train.step(state2, data)
    float(m["loss"])
    report("full_step", (time.perf_counter() - t0) / 4)
    del state2
    state = train.init(jax.random.PRNGKey(0))

    # forward-only loss (with CE)
    import flax.linen as nn

    def fwd_loss(params, batch):
        with nn.logical_axis_rules(list(lr.DEFAULT_RULES)):
            logits, aux = model.apply({"params": params}, batch["inputs"])
            ce, _ = train_lib.cross_entropy_loss(
                logits, batch["targets"], batch["weights"])
            return ce + aux

    with train_lib.use_mesh(mesh):
        f = jax.jit(fwd_loss)
        report("fwd_with_ce", timed(lambda: f(state.params, data)))

        # forward-only, scalar readout without CE (sum of logits)
        def fwd_sum(params, batch):
            with nn.logical_axis_rules(list(lr.DEFAULT_RULES)):
                logits, aux = model.apply({"params": params}, batch["inputs"])
                return logits.astype(jnp.float32).sum()
        f2 = jax.jit(fwd_sum)
        report("fwd_sum_logits", timed(lambda: f2(state.params, data)))

        # grad without optimizer
        g = jax.jit(lambda p, b: jax.grad(fwd_loss)(p, b))
        grads = g(state.params, data)
        jax.block_until_ready(grads)
        report("fwd_bwd_with_ce", timed(lambda: g(state.params, data)))

        # optimizer update alone
        def upd(grads, state):
            return state.apply_gradients(grads=grads)
        u = jax.jit(upd, donate_argnums=())
        report("opt_update", timed(lambda: u(grads, state)))


if __name__ == "__main__":
    main()
