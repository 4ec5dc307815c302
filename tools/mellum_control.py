"""``benchmark/lowered_control.py`` for a configuration with an UNTIED head
and a reference that takes faults (Mellum2-12B-A2.5B): the comparison that
decides ``correct``, handed what it has to refuse.

    chiprun -- python tools/mellum_control.py --seeds <n> ...
    chiprun -- python tools/mellum_control.py --seeds <n> \
        --program embed_init_std=1 attn_init_score_std=4   # other seeded scales

``benchmark/lowered_control.py`` reads the head off the embedding and knows
the modes ``all`` / ``router`` / ``conv``; it cannot be edited here, so this
file stands beside it and uses its pieces (``standing_in``, ``Float32Program``
and the worker it builds).  For each seed, through the cell's own
``check_reference``: the program as it stands; the reference computed wholly
in bfloat16, with only its router, only its attention lowered; the float32
reference with a FAULT made (``no_window``, ``no_yarn``, ...), to read
whether the whole-model comparison catches it; the program with float32
activations.  The float32 reference's losses are computed once a seed.  One
JSON line a seed, the same under ``chiprun_out/``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from benchmark import build, lowered_control  # noqa: E402

CELL = "mellum2-12b-a2.5b.train_long"
LOWERED = ("all", "router", "attention")


class StandIn:
    """The reference where ``check_reference`` applies the program's model,
    a precision lower or with one fault: its logits through the untied
    head, in the head's precision."""

    def __init__(self, reference, model, lowered="", wrong=""):
        self.reference, self.model = reference, model
        self.lowered, self.wrong = lowered, wrong

    def apply(self, variables, inputs):
        import jax

        params = variables["params"]
        hidden = self.reference.forward(
            self.model, params, inputs, lowered=self.lowered,
            wrong=self.wrong,
        )["hidden"]
        with jax.default_matmul_precision("highest"):
            return self.head(params, hidden, hidden.dtype), None

    def head(self, params, hidden, dtype):
        """The final norm and the head, in ``dtype``: this model's RMSNorm
        and untied head (a sibling's script overrides it)."""
        return self.reference.rms_norm(
            hidden, params["ln_final"]["scale"],
            float(self.model["norm_eps"]), dtype,
        ) @ params["lm_head"]["kernel"].astype(dtype)


def main(argv=None, cell=CELL, stand_in=StandIn,
         faults=("no_window", "no_yarn"), out="mellum_control.json",
         doc=__doc__, lowered=LOWERED, readings=None) -> int:
    """``cell``, ``stand_in``, the default ``faults``, ``lowered`` modes and
    ``out``: a sibling configuration's script (``tools/command_a_control.py``)
    hands its own; ``readings(worker, reference, lowered modes)``: what
    more a sibling reads of a seed's weights, added to the seed's line."""
    ap = argparse.ArgumentParser(description=doc.splitlines()[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--lowered", nargs="*", default=list(lowered))
    ap.add_argument("--faults", nargs="*", default=list(faults))
    ap.add_argument("--float32", action="store_true")
    ap.add_argument("--out", default=out)
    ap.add_argument(
        "--program", nargs="*", default=[], metavar="FIELD=NUMBER",
        help="the configuration's program fields to read another way "
        "(seeded scales: embed_init_std=1 attn_init_score_std=4)",
    )
    args = ap.parse_args(argv)
    import jax.numpy as jnp
    import numpy as np

    from benchmark import traffic as traffic_lib
    from benchmark.scenarios import train_steady_own_ref

    manifest = build.manifest()
    cell = {w["name"]: w for w in manifest["workloads"]}[cell]
    file = {c["name"]: c for c in manifest["configs"]}[cell["config"]]["file"]
    root = os.path.dirname(build.ROOT)
    config = build.load_json(os.path.join(root, file))
    config["program"].update(
        (field, float(number))
        for field, number in (item.split("=") for item in args.program)
    )
    traffic = build.load_json(
        os.path.join(build.ROOT, "traffic", f"{cell['traffic']}.json")
    )
    reference = importlib.import_module(
        f"benchmark.references.{config['reference_module']}"
    )
    worker = train_steady_own_ref.Worker(
        config, traffic, int(cell["chips"]), args.seeds[0], 0.0, False
    )
    worker.build_trainer()
    exact, kept = reference.token_nll, {}

    def once_a_seed(model, params, inputs, targets):
        if worker.seed not in kept:
            kept.clear()
            kept[worker.seed] = exact(model, params, inputs, targets)
        return kept[worker.seed]

    reference.token_nll = once_a_seed
    keep = ("mean_abs_token_error", "mean_loss_error", "ok")
    lines = []
    for seed in args.seeds:
        worker.seed = int(seed)
        worker.seed_state()
        checks = {"program": worker.check_reference()}
        stand_ins = [(m, dict(lowered=m)) for m in args.lowered] + [
            (f, dict(wrong=f)) for f in args.faults
        ]
        for name, kw in stand_ins:
            with lowered_control.standing_in(
                worker, stand_in(reference, worker.model, **kw)
            ):
                checks[name] = worker.check_reference()
        if args.float32:
            checks["float32"] = lowered_control.control(worker, "float32")
        line = {
            "seed": int(seed),
            "reference_loss": checks["program"]["reference_loss"],
            "program_loss": checks["program"]["program_loss"],
        }
        if "all" in args.lowered:
            # the reference's OWN loss in bfloat16 (the comparison takes a
            # program's logits to float32 before the loss)
            rows = traffic_lib.first_sequences(
                worker.sample_fn(),
                int(worker.traffic.get("reference_sequences", 2)),
            )
            own = np.asarray(exact(
                worker.model, worker.trainer.state.params,
                jnp.asarray(rows["inputs"]), jnp.asarray(rows["targets"]),
                lowered="all",
            ))
            line["direct_all"] = float(
                np.abs(own - np.asarray(kept[worker.seed])).mean()
            )
        line.update({
            name: {k: check[k] for k in keep}
            for name, check in checks.items()
        })
        if readings is not None:
            # a seed's checks are not lost to what is read beside them
            try:
                line["readings"] = readings(worker, reference, args.lowered)
            except Exception as e:  # noqa: BLE001
                line["readings_error"] = repr(e)[:400]
        print(json.dumps(line), flush=True)
        lines.append(line)
    out = os.path.join(root, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, args.out), "w") as f:
        limits = {
            k: v for k, v in config["reference_tolerance"].items()
            if k != "why"
        }
        json.dump(
            {"limits": limits, "program": args.program, "seeds": lines},
            f, indent=1,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
