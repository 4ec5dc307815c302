"""Isolated experiment: QKV-projection lowering variants on the real chip.

PROFILE.md sink #2: the [1600, 25, 192] 3D kernel makes XLA lower the QKV
projection chain (fwd + bwd-recompute + dx + dW) to "convolution" window
emitters at 27-55% MXU.  r3 tried a plain 2D reshape and XLA algebraically
re-folded it.  This measures whether an optimization_barrier on the reshaped
operands pins the 2D lowering, vs. a Pallas matmul, before we commit to one.

The measured loop runs inside a single jit (lax.scan over ITERS iterations)
so per-dispatch host overhead does not pollute the numbers.

Run: python tools/qkv_experiment.py
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

B, S, D, H, HD3 = 16, 1024, 1600, 25, 192
N = H * HD3  # 4800
ITERS = 30


def _sync(out):
    jax.block_until_ready(out)


def scan_time(step, init, *args, n=3):
    """Time ITERS iterations of `step` folded into one jitted scan."""

    @jax.jit
    def many(init):
        def body(c, _):
            return step(c), None
        out, _ = jax.lax.scan(body, init, None, length=ITERS)
        return out

    out = many(init)
    _sync(out)
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        out = many(init)
        _sync(out)
        best = min(best, time.perf_counter() - t0)
    return best / ITERS


def hlo_ops(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    convs = txt.count("convolution(")
    dots = txt.count("dot(")
    return f"conv={convs} dot={dots}"


def run(name, proj, w):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, S, D), jnp.bfloat16)

    def loss(x, w):
        y = proj(x, w)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def fwd_step(x):
        y = proj(x, w)
        # fold output back to x's shape so the scan carry chains
        return y.reshape(B, S, -1)[..., :D] + x * 1e-6

    def grad_step(x):
        gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
        return x + gx * 1e-6 + jnp.sum(gw.astype(x.dtype)) * 0

    tf = scan_time(fwd_step, x)
    tg = scan_time(grad_step, x)
    fl_f = 2 * B * S * D * N
    fl_g = 3 * fl_f
    ops = hlo_ops(lambda x: jax.grad(loss, argnums=(0, 1))(x, w), x)
    print(f"{name:26s} fwd {tf*1e3:6.2f} ms ({fl_f/tf/1e12:6.1f} TF/s)  "
          f"grad {tg*1e3:6.2f} ms ({fl_g/tg/1e12:6.1f} TF/s)  [{ops}]")


def proj_3d(x, w):
    return jax.lax.dot_general(x, w, (((2,), (0,)), ((), ())))


def proj_2d_plain(x, w):
    y = jnp.dot(x.reshape(B * S, D), w.reshape(D, N))
    return y.reshape(B, S, H, HD3)


def proj_2d_barrier(x, w):
    x2 = jax.lax.optimization_barrier(x.reshape(B * S, D))
    w2 = jax.lax.optimization_barrier(w.reshape(D, N))
    y = jax.lax.optimization_barrier(jnp.dot(x2, w2))
    return y.reshape(B, S, H, HD3)


def proj_2d_barrier_w_only(x, w):
    w2 = jax.lax.optimization_barrier(w.reshape(D, N))
    y = jnp.dot(x.reshape(B * S, D), w2)
    return y.reshape(B, S, H, HD3)


if __name__ == "__main__":
    print(f"device: {jax.devices()[0].device_kind}")
    key = jax.random.PRNGKey(1)
    w3 = jax.random.normal(key, (D, H, HD3), jnp.bfloat16) * 0.02
    w2 = w3.reshape(D, N)
    # control: what can a clean 2D matmul of this size do in this harness
    run("control mm (2D in/out)",
        lambda x, w: jnp.dot(x.reshape(B * S, D), w), w2)
    run("dot_general 3D (current)", proj_3d, w3)
    run("2D reshape plain", proj_2d_plain, w3)
    run("2D + barrier x,w,y", proj_2d_barrier, w3)
    run("2D + barrier w only", proj_2d_barrier_w_only, w3)
