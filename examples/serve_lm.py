"""Minimal serving example: continuous-batching decode on a toy LM.

Builds a small TransformerLM, AOT-warms the serving programs, then runs a
handful of mixed-length requests with per-request sampling params through
the continuous-batching engine and prints each result.

    JAX_PLATFORMS=cpu python examples/serve_lm.py --slots 4 --requests 8

It runs on the platform jax finds and prints which; ``chip_smoke.py`` drives
it on the chip at GPT-2 1.5B's widths (``--size 1.5b --param-dtype
bfloat16 --attention-impl flash``).
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="toy continuous-batching demo")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--size", default="",
                    help="a GPT-2 size of models/gpt2.py (124m ... 1.5b) at "
                         "its published widths, --layers/--max-seq-len "
                         "overriding. Default: a toy model from --vocab/"
                         "--d-model/--layers/--heads")
    ap.add_argument("--param-dtype", default="float32",
                    choices=("float32", "bfloat16"))
    ap.add_argument("--attention-impl", default="xla",
                    choices=("xla", "flash"),
                    help="flash serves prefill chunks of 16+ tokens "
                         "through the Pallas kernel")
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--max-seq-len", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def run(args) -> dict:
    """Build the model and engine, AOT-warm, serve the requests, print each
    result; returns what was built and served for a caller to check."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models.gpt2 import gpt2_config
    from dlrover_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from dlrover_tpu.rl.generation import SamplingParams
    from dlrover_tpu.serving import Request, ServingEngine
    from dlrover_tpu.utils.devices import device_fields

    print(f"device: {device_fields()}")
    param_dtype = getattr(jnp, args.param_dtype)
    if args.size:
        overrides = {
            "num_layers": args.layers, "max_seq_len": args.max_seq_len,
        }
        config = gpt2_config(
            args.size, param_dtype=param_dtype,
            attention_impl=args.attention_impl,
            **{k: v for k, v in overrides.items() if v is not None},
        )
    else:
        config = TransformerConfig(
            vocab_size=args.vocab, d_model=args.d_model,
            num_heads=args.heads, num_layers=args.layers or 2,
            d_ff=args.d_model * 2, max_seq_len=args.max_seq_len or 64,
            param_dtype=param_dtype, attention_impl=args.attention_impl,
        )
    # jit: eager init would run (and compile) one program per parameter.
    params = jax.jit(
        lambda rng: TransformerLM(config).init(
            rng, jnp.zeros((1, 4), jnp.int32)
        )["params"]
    )(jax.random.PRNGKey(args.seed))

    engine = ServingEngine(
        config, params, slots=args.slots, seed=args.seed
    )
    aot_s = engine.aot_compile()
    print(f"AOT warmup: {aot_s:.2f}s "
          f"(buckets {engine.buckets}, slots {args.slots})")

    rng = np.random.RandomState(args.seed)
    requests = []
    for i in range(args.requests):
        prompt = rng.randint(
            1, config.vocab_size, size=3 + (5 * i) % 13
        ).astype(np.int32)
        requests.append(Request(
            f"req{i}", prompt,
            SamplingParams(
                temperature=0.0 if i % 2 == 0 else 0.8,
                top_k=0 if i % 2 == 0 else 8,
                max_new_tokens=2 + (3 * i) % args.max_new,
            ),
        ))
    results = engine.run(requests)
    for req in requests:
        r = results[req.uid]
        print(f"{r.uid}: prompt[{len(r.prompt)}] -> "
              f"{r.tokens.tolist()} ({r.latency_s * 1e3:.1f} ms)")
    stats = engine.stats()
    print(f"stats: qps={stats['qps']:.1f} p50={stats['p50_s'] * 1e3:.1f}ms "
          f"p95={stats['p95_s'] * 1e3:.1f}ms "
          f"occupancy={stats['occupancy']:.2f}")
    return {
        "config": config, "params": params, "engine": engine,
        "requests": requests, "results": results, "aot_s": aot_s,
    }


def main() -> int:
    run(parse_args())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
