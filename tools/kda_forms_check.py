"""The per-channel delta rule's two forms on the chip, kernels alone
(``ops/kda.py``): the SPLIT form (every pair's decay split around its
sub-chunk's middle: Ling's, a gate bounded at -5) and the EXACT form (the
triangle cut by halves, any ``g <= 0``: Solar-Open2's), at one model's
shapes, each held to the float32 recurrence on a short prefix and timed.

    chiprun -- python tools/kda_forms_check.py --seq 16384 --heads 64

Prints one JSON line (the same under ``chiprun_out/kda_forms.json``): ms a
forward call and a backward call of each form from heads-first operands
(no transposes, no mixer), the largest difference of the two forms'
outputs and gradients under a bounded gate, and the exact form's distance
from the recurrence under a gate down to ``--g-min`` a token.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--g-min", type=float, default=-200.0)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--out", default="kda_forms.json")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.references import solar_open as reference
    from dlrover_tpu.ops import kda

    h, s, d = args.heads, args.seq, args.width
    n = s // kda.CHUNK
    keys = jax.random.split(jax.random.PRNGKey(0), 8)

    def unit(key):
        x = jax.random.normal(key, (h, s, d), jnp.float32)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = (unit(keys[0]) * d ** -0.5).astype(jnp.bfloat16)
    k = unit(keys[1]).astype(jnp.bfloat16)
    v = jax.random.normal(keys[2], (h, s, d), jnp.bfloat16)
    do = jax.random.normal(keys[3], (h, s, d), jnp.bfloat16)
    bounded = -5.0 * jax.nn.sigmoid(
        2.0 * jax.random.normal(keys[4], (h, s, d), jnp.float32)
    )
    # most channels slow, some tokens' far past the split form's floor
    free = args.g_min * jax.random.uniform(keys[5], (h, s, d)) ** 6 * (
        jax.random.uniform(keys[6], (h, s, d)) < 0.5
    )
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[7], (h, s)))
    beta = beta.reshape(h, n, 1, kda.CHUNK)

    def timed(fn, *a):
        out = jax.block_until_ready(fn(*a))
        start = time.perf_counter()
        for _ in range(args.calls):
            out = fn(*a)
        jax.block_until_ready(out)
        return out, (time.perf_counter() - start) / args.calls * 1e3

    line = {
        "device": jax.devices()[0].device_kind, "heads": h, "seq": s,
        "width": d, "chunk": kda.CHUNK,
    }
    outs = {}
    for name, exact, g in (
        ("split", False, bounded), ("exact", True, bounded),
        ("exact_free", True, free),
    ):
        (o, starts, top), fwd_ms = timed(
            lambda *a: kda._forward(*a, exact=exact), q, k, v, g, beta
        )
        grads, bwd_ms = timed(
            lambda *a: kda._backward(*a, exact=exact),
            q, k, v, g, beta, starts, do,
        )
        outs[name] = (o,) + tuple(grads)
        line[name] = {
            "forward_ms": fwd_ms, "backward_ms": bwd_ms,
            "state_absmax": float(top.max()),
            "finite": bool(all(
                jnp.isfinite(a.astype(jnp.float32)).all()
                for a in outs[name]
            )),
        }

    def gap(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.abs(a - b).max() / jnp.abs(b).max())

    line["exact_against_split"] = dict(zip(
        ("o", "dq", "dk", "dv", "dg", "dbeta"),
        (gap(a, b) for a, b in zip(outs["exact"], outs["split"])),
    ))
    # the float32 recurrence on the first 1,024 tokens of four heads
    short, few = 1024, 4

    def prefix(a):
        return jnp.moveaxis(a[:few, :short], 0, 1)[None].astype(jnp.float32)

    want = reference.kda_recurrence(
        prefix(q), prefix(k), prefix(v), prefix(free),
        prefix(beta.reshape(h, s)),
    )
    got = jnp.moveaxis(outs["exact_free"][0][:few, :short], 0, 1)[None]
    line["exact_free_against_recurrence"] = {
        "mean_abs": float(jnp.abs(got.astype(jnp.float32) - want).mean()),
        "mean_entry": float(jnp.abs(want).mean()),
        "g_min": float(free.min()),
        "past_floor_share": float((free < kda.SPLIT_FLOOR).mean()),
    }
    text = json.dumps(line)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", args.out), "w") as f:
        f.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
