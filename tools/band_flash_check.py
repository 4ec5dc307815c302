"""The flash kernels alone on the chip at one cell's attention shape: the
banded and the full kernels against a float32 ``highest`` band computed in
row blocks (forward and the three cotangents), a window of one key more or
less through the same comparison, and the calls' times.

    chiprun -- python tools/band_flash_check.py --out chiprun_out/band.json
    chiprun -- python tools/band_flash_check.py --cells mellum2 command-a \
        --blocks 1024 --out-dir chiprun_out/band

The second form runs both windowed cells' shapes one after another (a
file each), and beside the kernels as they are (``lockstep``: a banded
block's row strips advanced in turn) times the banded calls in the two
forms they had before (``--forms``; ``strips``: the strips one after
another, ``square``: the lower-edge block a masked square, the diagonal
one strips), each held to the first form's outputs: PR 60's verdict on
the lockstep in one command.

q, k and v are unit-variance bfloat16 (a peaked softmax: a key more or less
moves a row), the reference works on the same values in float32.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dlrover_tpu.ops import flash_attention as fa

ROWS = 2048
# the windowed cells' attention shapes: seq, heads, kv heads, window
CELLS = {
    "mellum2": dict(seq=32768, heads=32, kv_heads=4, window=1024),
    "command-a": dict(seq=16384, heads=32, kv_heads=2, window=4096),
}
FORMS = ("lockstep", "strips", "square")


def plain(q, k, v, window):
    """Float32 ``highest`` attention ``[B, S, H, D]`` in blocks of query
    rows, one head after another; ``window`` None: the causal triangle."""
    b, s, hq, d = q.shape
    group = hq // k.shape[2]
    qh = jnp.moveaxis(q.astype(jnp.float32), 2, 0)          # [H, B, S, D]
    kh = jnp.moveaxis(k.astype(jnp.float32), 2, 0)
    vh = jnp.moveaxis(v.astype(jnp.float32), 2, 0)
    rows = min(ROWS, s)
    j = jnp.arange(s)[None, :]

    def head(xs):
        q_h, g = xs
        k_h = jax.lax.dynamic_index_in_dim(kh, g, 0, False)
        v_h = jax.lax.dynamic_index_in_dim(vh, g, 0, False)

        @jax.checkpoint
        def block(xs):
            q_rows, first = xs
            i = first + jnp.arange(rows)[:, None]
            seen = i >= j
            if window is not None:
                seen = seen & (i - j < window)
            scores = jnp.einsum("bqd,bkd->bqk", q_rows, k_h) / math.sqrt(d)
            probs = jax.nn.softmax(
                jnp.where(seen[None], scores, -jnp.inf), axis=-1
            )
            return jnp.einsum("bqk,bkd->bqd", probs, v_h)

        blocks = jnp.moveaxis(q_h.reshape(b, s // rows, rows, d), 1, 0)
        out = jax.lax.map(block, (blocks, jnp.arange(0, s, rows)))
        return jnp.moveaxis(out, 0, 1).reshape(b, s, d)

    with jax.default_matmul_precision("highest"):
        out = jax.lax.map(head, (qh, jnp.arange(hq) // group))
    return jnp.moveaxis(out, 0, 2)                           # [B, S, H, D]


def both_ways(fn, q, k, v, do):
    o, vjp = jax.vjp(fn, q, k, v)
    return (o, *vjp(do.astype(o.dtype)))


@contextlib.contextmanager
def form(name):
    """The banded kernels as ``name`` says, for the calls traced inside:
    ``lockstep`` as they are, ``strips`` with a block's strips one after
    another, ``square`` with the lower-edge block a masked square too."""
    kept = fa._in_lockstep, fa._band_classes
    classes = fa._band_classes
    if name != "lockstep":
        fa._in_lockstep = fa._one_by_one
    if name == "square":
        fa._band_classes = (
            lambda *sizes: classes(*sizes)._replace(lower_strip=0)
        )
    try:
        yield
    finally:
        fa._in_lockstep, fa._band_classes = kept


def timed(fn, *args, repeats=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e3


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cells", nargs="+", choices=sorted(CELLS))
    parser.add_argument("--seq", type=int, default=32768)
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--kv-heads", type=int, default=4)
    parser.add_argument("--head-dim", type=int, default=128)
    parser.add_argument("--window", type=int, default=1024)
    parser.add_argument("--seed", type=int, default=2147486731)
    parser.add_argument("--blocks", type=int, nargs="+", default=[1024, 512])
    parser.add_argument("--forms", nargs="+", choices=FORMS,
                        default=list(FORMS))
    parser.add_argument("--repeats", type=int, default=20)
    parser.add_argument("--skip-check", action="store_true")
    parser.add_argument("--out", default="chiprun_out/band_flash_check.json")
    parser.add_argument("--out-dir", default="chiprun_out/band_flash_check")
    args = parser.parse_args()
    if not args.cells:
        return one_shape(args)
    for cell in args.cells:
        one_shape(argparse.Namespace(**{
            **vars(args), **CELLS[cell],
            "out": os.path.join(args.out_dir, f"{cell}.json"),
        }))


def one_shape(args):
    keys = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 4)
    shape = lambda h: (1, args.seq, h, args.head_dim)
    q = jax.random.normal(keys[0], shape(args.heads), jnp.bfloat16)
    k = jax.random.normal(keys[1], shape(args.kv_heads), jnp.bfloat16)
    v = jax.random.normal(keys[2], shape(args.kv_heads), jnp.bfloat16)
    do = jax.random.normal(keys[3], shape(args.heads), jnp.bfloat16)
    device = jax.devices()[0]
    result = {
        "device": {"platform": device.platform, "kind": device.device_kind},
        "shape": [1, args.seq, args.heads, args.kv_heads, args.head_dim],
        "window": args.window, "seed": args.seed, "check": {}, "ms": {},
        "forms": {},
    }

    def kernel(window, block):
        return functools.partial(
            fa.mha, window=window, block_q=block, block_kv=block
        )

    if not args.skip_check:
        for kind, window in (("band", args.window), ("full", None)):
            want = jax.jit(functools.partial(
                both_ways, functools.partial(plain, window=window)
            ))(q, k, v, do)
            want = [np.asarray(w, np.float32) for w in want]
            tries = [(window, args.blocks[0])]
            if window is not None:
                tries += [
                    (window, b) for b in args.blocks[1:]
                ] + [(window + 1, args.blocks[0]), (window - 1, args.blocks[0])]
            for w, block in tries:
                got = jax.jit(functools.partial(both_ways, kernel(w, block)))(
                    q, k, v, do
                )
                name = f"{kind}/window={w}/block={block}"
                result["check"][name] = {
                    part: {
                        "max_abs_err": float(np.abs(
                            np.asarray(g, np.float32) - r
                        ).max()),
                        "mean_abs_err": float(np.abs(
                            np.asarray(g, np.float32) - r
                        ).mean()),
                        "ref_absmax": float(np.abs(r).max()),
                    }
                    for part, g, r in zip(("o", "dq", "dk", "dv"), got, want)
                }
                print(name, json.dumps(result["check"][name]), flush=True)
            del want

    def times(window, block):
        fn = kernel(window, block)
        f_ms = timed(jax.jit(fn), q, k, v, repeats=args.repeats)
        fb_ms = timed(
            jax.jit(functools.partial(both_ways, fn)), q, k, v, do,
            repeats=args.repeats,
        )
        classes = fa.block_classes(
            args.seq, args.seq, block, block, True, window
        )
        return {
            "forward": f_ms, "forward_and_backward": fb_ms,
            "backward": fb_ms - f_ms, "classes": classes._asdict(),
            "backward_path": fa.backward_path(
                args.seq, args.seq, args.head_dim, args.head_dim,
                block, block, q.dtype,
            ),
        }

    for block in args.blocks:
        for kind, window in (("band", args.window), ("full", None)):
            name = f"{kind}/block={block}"
            result["ms"][name] = times(window, block)
            print(name, json.dumps(result["ms"][name]), flush=True)

    # the banded calls in each form, and how far each form's outputs lie
    # from the first's (the lockstep is the loop bit for bit; the strips
    # are the square to float32 reassociation under a bfloat16 output)
    block, first = args.blocks[0], None
    for name in args.forms:
        with form(name):
            said = times(args.window, block)
            said["tile_live_share"] = fa.band_tile_live_share(
                args.seq, args.seq, block, block, args.window
            )
            got = jax.jit(functools.partial(
                both_ways, kernel(args.window, block)
            ))(q, k, v, do)
        got = [np.asarray(g, np.float32) for g in got]
        first = first or got
        said["max_abs_diff_from_first"] = {
            part: float(np.abs(g - f).max())
            for part, g, f in zip(("o", "dq", "dk", "dv"), got, first)
        }
        result["forms"][f"{name}/block={block}"] = said
        print("form", name, json.dumps(said), flush=True)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"ok": True, "out": args.out}))


if __name__ == "__main__":
    main()
