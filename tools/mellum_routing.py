"""How the seeded routers of the Mellum2 cell spread a 32,768-token
sequence, layer by layer, at several ``embed_init_std``: the program's own
forward at the published widths with the layers' ``moe_stats`` taken out
(the busiest held expert against the mean, the pairs routed here, the pairs
past the row budget).

    chiprun -- python tools/mellum_routing.py --stds 0.02 1 8
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from benchmark import build  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--stds", type=float, nargs="+", default=[0.02, 1.0, 8.0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[2147486731])
    ap.add_argument("--seq", type=int, default=32768)
    ap.add_argument(
        "--program", nargs="*", default=[], metavar="FIELD=NUMBER",
        help="other program fields to read it under "
        "(attn_init_score_std=4)",
    )
    ap.add_argument("--out", default="mellum_routing.json")
    ap.add_argument("--config", default=os.path.join(
        build.ROOT, "configs", "mellum2-12b-a2.5b.json"
    ))
    args = ap.parse_args(argv)
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models import moe as moe_lib
    from dlrover_tpu.models.transformer import TransformerLM

    config = build.load_json(args.config)
    model = build.model_group(config)
    model.update(
        (field, float(number))
        for field, number in (item.split("=") for item in args.program)
    )
    lines = []
    for std in args.stds:
        cfg = build.transformer_config(
            dict(model, embed_init_std=std), args.seq
        )
        lm = TransformerLM(cfg)
        for seed in args.seeds:
            rows = np.random.default_rng(seed).integers(
                0, cfg.vocab_size, (1, args.seq), dtype=np.int32
            )
            tokens = jnp.asarray(rows)
            params = jax.jit(lm.init)(
                jax.random.PRNGKey(seed % (2 ** 31)), tokens
            )["params"]
            params = nn.meta.unbox(params)

            @jax.jit
            def stats(params, tokens):
                _, sown = lm.apply(
                    {"params": params}, tokens, mutable=["intermediates"]
                )
                found = [
                    leaf.reshape(-1, leaf.shape[-1]) for path, leaf in
                    jax.tree_util.tree_leaves_with_path(sown)
                    if any(
                        getattr(k, "key", None) in (
                            "moe_stats", moe_lib.SHARE_STATS_NAME
                        ) for k in path
                    )
                ]
                return found

            found = [np.asarray(f, np.float64) for f in stats(params, tokens)]
            del params
            layers = []
            for vec in found:
                if vec.shape[-1] < 8:       # the share vectors
                    continue
                for row in vec:
                    _, drop, load, pad, busiest = moe_lib.split_stats(row)
                    layers.append({
                        "drop_fraction": float(drop),
                        "max_expert_load": float(busiest),
                        "pad_share": float(pad),
                        "load_max_over_mean_all": float(
                            load.max() / load.mean()
                        ),
                    })
            shares = [
                float(row[0]) for vec in found if vec.shape[-1] < 8
                for row in vec
            ]
            line = {
                "embed_init_std": std, "program": args.program,
                "seed": seed, "layers": layers,
                "pairs_here": shares,
                "worst_max_expert_load": max(
                    l["max_expert_load"] for l in layers
                ),
                "worst_drop_fraction": max(
                    l["drop_fraction"] for l in layers
                ),
            }
            print(json.dumps(line), flush=True)
            lines.append(line)
    out = os.path.join(os.path.dirname(build.ROOT), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, args.out), "w") as f:
        json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
