"""``train_steady`` with the configuration's own plain reference.

The harness's ``Worker.check_reference`` calls ``benchmark.reference`` by
name, which knows GPT-2 and Mixtral.  A configuration whose layer that
reference does not describe names its own module under
``benchmark/references/`` (``reference_module`` in its file); this
scenario's worker calls that one.  Everything else is ``train_steady``'s
own ``run`` with this worker: set-up, window, readings and ``correct``,
and ``setup_s`` from the mesh built to the window's first instant less the
reference check's seconds.  The scenario and its traffic file fold back
into ``train_steady`` once ``worker.check_reference`` reads
``reference_module`` itself (PERF.md §7).
"""

from __future__ import annotations

import importlib
import time
from typing import Any, Dict

from benchmark import traffic as traffic_lib, worker as worker_lib
from benchmark.scenarios import train_steady


class Worker(worker_lib.Worker):
    def check_reference(self) -> Dict[str, Any]:
        """Per-token loss of the program's forward (its kernels, its dtype,
        its sharding) against the configuration's ``reference_module`` on
        the first sequences of the first batch."""
        import flax.linen as nn
        import jax
        import jax.numpy as jnp
        import numpy as np

        from dlrover_tpu.trainer import train_lib

        reference = importlib.import_module(
            f"benchmark.references.{self.config['reference_module']}"
        )
        count = int(self.traffic.get("reference_sequences", 2))
        rows = traffic_lib.first_sequences(self.sample_fn(), count)
        trainer = self.trainer
        inputs, targets = jnp.asarray(rows["inputs"]), jnp.asarray(
            rows["targets"]
        )

        @jax.jit
        def program_nll(params, inputs, targets):
            logits, _ = trainer.model.apply({"params": params}, inputs)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]

        t0 = time.monotonic()
        with train_lib.use_mesh(trainer.mesh), nn.logical_axis_rules(
            trainer._rules
        ):
            got = np.asarray(program_nll(trainer.state.params, inputs, targets))
        params = jax.device_get(trainer.state.params) if (
            self.chips > 1
        ) else trainer.state.params
        want = np.asarray(
            reference.token_nll(self.model, params, inputs, targets)
        )
        tol = self.config["reference_tolerance"]
        token_err = float(np.abs(got - want).mean())
        mean_err = float(abs(got.mean() - want.mean()))
        finite = bool(np.isfinite(got).all() and np.isfinite(want).all())
        return {
            "sequences": count,
            "reference_module": self.config["reference_module"],
            "program_loss": float(got.mean()),
            "reference_loss": float(want.mean()),
            "mean_abs_token_error": token_err,
            "mean_loss_error": mean_err,
            "finite": finite,
            "ok": bool(
                finite and token_err <= tol["mean_abs_token_nll"]
                and mean_err <= tol["mean_nll"]
            ),
            "seconds": time.monotonic() - t0,
        }


def run(ctx) -> Dict[str, Any]:
    return train_steady.run(ctx, Worker)
