"""The comparison that decides ``correct``, handed what it has to refuse.

``python -m benchmark.lowered_control --workload <cell> --seeds <n> ...``,
from the root of a checkout, on the chip.  A ``reference_tolerance`` is set
between two readings: the program's distance from the configuration's plain
reference, and that reference's own distance from itself when it is computed
a precision lower (``lowered="all"``, or one part alone).  This file takes
both THROUGH the cell's own worker: the trainer is built as the cell builds
it, the weights are made from each seed, ``check_reference`` is called for
the program as it stands, and then again with a stand-in where the
program's model stands (:class:`LoweredReference`, :class:`Float32Program`),
so that each control's ``ok`` is the harness's own verdict under the limits
in the configuration's file.  Nothing is timed and no result line is
printed: one JSON line a seed, the same under ``chiprun_out/``.

It needs a reference module with ``forward(model, params, tokens,
lowered=)["hidden"]`` and ``rms_norm``, a tied head and a final norm
``ln_final`` (``benchmark/references/lfm2_moe.py``).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import json
import os
import sys
from typing import Any, Dict

from benchmark import build

MODES = ("all", "router", "conv", "float32")


class LoweredReference:
    """The reference at ``lowered`` where ``check_reference`` applies the
    program's model: its logits in the head's precision.  The comparison
    takes any program's logits to float32 before the loss, so the loss's
    own precision is the one part of ``lowered="all"`` it cannot be handed
    (``direct`` in a line is the reference's own loss at ``lowered``,
    beside it)."""

    def __init__(self, reference, model: Dict[str, Any], lowered: str):
        self.reference, self.model, self.lowered = reference, model, lowered

    def apply(self, variables, inputs):
        import jax

        params = variables["params"]
        hidden = self.reference.forward(
            self.model, params, inputs, lowered=self.lowered
        )["hidden"]
        dtype = hidden.dtype
        with jax.default_matmul_precision("highest"):
            logits = self.reference.rms_norm(
                hidden, params["ln_final"]["scale"],
                float(self.model["norm_eps"]), dtype,
            ) @ params["embed"]["embedding"].astype(dtype).T
        return logits, None


class Float32Program:
    """The program itself with float32 activations and whole-precision
    products over the weights as they are: what is left of its distance
    from the reference is not rounding."""

    def __init__(self, model_config):
        import jax.numpy as jnp

        from dlrover_tpu.models.transformer import TransformerLM

        self.model = TransformerLM(
            dataclasses.replace(model_config, dtype=jnp.float32)
        )

    def apply(self, variables, inputs):
        import jax

        with jax.default_matmul_precision("highest"):
            return self.model.apply(variables, inputs)


@contextlib.contextmanager
def standing_in(worker, model):
    program, worker.trainer.model = worker.trainer.model, model
    try:
        yield
    finally:
        worker.trainer.model = program


def control(worker, mode: str) -> Dict[str, Any]:
    """``worker.check_reference()`` with ``mode``'s stand-in as the
    program."""
    if mode == "float32":
        model = Float32Program(worker.trainer.model_config)
    else:
        model = LoweredReference(
            importlib.import_module(
                f"benchmark.references.{worker.config['reference_module']}"
            ),
            worker.model, mode,
        )
    with standing_in(worker, model):
        return worker.check_reference()


def direct(worker, modes) -> Dict[str, float]:
    """The reference's own per-token loss at each of ``modes`` against
    itself in float32, on the rows ``check_reference`` takes."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark import traffic as traffic_lib

    reference = importlib.import_module(
        f"benchmark.references.{worker.config['reference_module']}"
    )
    rows = traffic_lib.first_sequences(
        worker.sample_fn(), int(worker.traffic.get("reference_sequences", 2))
    )
    nll = {
        mode: np.asarray(reference.token_nll(
            worker.model, worker.trainer.state.params,
            jnp.asarray(rows["inputs"]), jnp.asarray(rows["targets"]),
            lowered=mode,
        )) for mode in ("",) + tuple(modes)
    }
    return {
        mode: float(np.abs(nll[mode] - nll[""]).mean()) for mode in modes
    }


def sweep(worker, seeds, modes, say=print):
    keep = ("mean_abs_token_error", "mean_loss_error", "ok")
    lines = []
    for seed in seeds:
        worker.seed = int(seed)
        worker.seed_state()
        checks = {"program": worker.check_reference()}
        checks.update({mode: control(worker, mode) for mode in modes})
        line = {
            "seed": int(seed),
            "reference_loss": checks["program"]["reference_loss"],
            "direct": direct(worker, [m for m in modes if m != "float32"]),
        }
        line.update({
            name: {k: check[k] for k in keep}
            for name, check in checks.items()
        })
        say(json.dumps(line))
        lines.append(line)
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--modes", nargs="+", choices=MODES, default=MODES[:3])
    args = ap.parse_args(argv)
    manifest = build.manifest()
    cell = {w["name"]: w for w in manifest["workloads"]}[args.workload]
    file = {c["name"]: c for c in manifest["configs"]}[cell["config"]]["file"]
    config = build.load_json(os.path.join(os.path.dirname(build.ROOT), file))
    traffic = build.load_json(
        os.path.join(build.ROOT, "traffic", f"{cell['traffic']}.json")
    )
    from benchmark.scenarios import train_steady_own_ref

    worker = train_steady_own_ref.Worker(
        config, traffic, int(cell["chips"]), args.seeds[0], 0.0, False
    )
    worker.build_trainer()
    lines = sweep(worker, args.seeds, args.modes)
    out = os.path.join(os.path.dirname(build.ROOT), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"lowered_control.{args.workload}.json"),
              "w") as f:
        limits = {
            k: v for k, v in config["reference_tolerance"].items()
            if k != "why"
        }
        json.dump({"limits": limits, "seeds": lines}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
