"""Raw matmul MFU microbench at bench-model shapes (TPU).

python tools/perf_matmul.py  -> one JSON line per shape.
"""
import json
import time

import jax
import jax.numpy as jnp

PEAK = 197e12

SHAPES = [
    # (M, K, N)  tokens x in x out at GPT-2 1.5B shapes
    (16384, 1600, 1600),
    (16384, 1600, 6400),
#    (16384, 6400, 1600),
    (16384, 1600, 50304),
    (16384, 1536, 6144),   # lane-aligned control
    (8192, 1600, 6400),
    (32768, 1600, 6400),
]


def bench(m, k, n, steps=20):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (m, k), jnp.bfloat16)
    w = jax.random.normal(key, (k, n), jnp.bfloat16)

    w2 = jax.random.normal(key, (n, k), jnp.bfloat16)

    @jax.jit
    def f(x, w, w2):
        # ping-pong chain: every output feeds the next matmul entirely, so
        # nothing is dead-code-eliminated
        y = x
        for _ in range(4):
            y = jnp.dot(y, w, preferred_element_type=jnp.bfloat16)
            y = jnp.dot(y, w2, preferred_element_type=jnp.bfloat16) * 1e-2
        return y.sum()

    float(f(x, w, w2))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = f(x, w, w2)
    float(out)
    dt = (time.perf_counter() - t0) / steps
    flops = 8 * 2 * m * k * n
    return flops / dt / PEAK, dt


if __name__ == "__main__":
    for m, k, n in SHAPES:
        try:
            mfu, dt = bench(m, k, n)
            print(json.dumps({"shape": [m, k, n], "mfu": round(mfu, 3),
                              "time_s": round(dt, 5)}), flush=True)
        except Exception as e:  # noqa
            print(json.dumps({"shape": [m, k, n], "error": str(e)[:100]}),
                  flush=True)
