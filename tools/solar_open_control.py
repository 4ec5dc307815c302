"""``tools/mellum_control.py`` for the Solar-Open2 cell (Kimi Delta Attention
under a gate without a bound beside gated position-free GQA): the
comparison that decides ``correct``, handed what it has to refuse, and what
that comparison cannot see.

    chiprun -- python tools/solar_open_control.py --seeds <n> ...
    chiprun -- python tools/solar_open_control.py --seeds <n> \\
        --program linear_decay_init_std=3 embed_init_std=1   # other scales

For each seed, through the cell's own ``check_reference``: the program as it
stands; the reference computed wholly in bfloat16 (``all``) and with only
its rule (``rule``: the recurrence's inputs, decay, state and outputs) in
bfloat16; the float32 reference with a FAULT made (``beta_not_doubled``,
``safe_gate``: Ling's ``-5 sigmoid(.)``, ``no_gqa_gate``, ``rope_on_gqa``;
any of the reference's ``FAULTS`` by ``--faults``).  Then, on the same
weights and the cell's first sequence, what ``correct`` does not compare
(``readings``), layer by layer from the program's own sown statistics: each
KDA layer's mean decay, smallest mean decay of a channel, most negative log
decay (``g_min``), share of (token, head, channel) triples past the split
form's floor of -5.5 (``past_bound_share``), mean beta and largest state
entry; each expert layer's routed pairs here, busiest expert's load and
rows past the budget.  The loop, the arguments and the output are
``tools/mellum_control.py``'s.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import mellum_control  # noqa: E402

CELL = "solar-open2-250b.train_16k"
FAULTS = ("beta_not_doubled", "safe_gate", "no_gqa_gate", "rope_on_gqa")


class StandIn(mellum_control.StandIn):
    """The reference where ``check_reference`` applies the program's model;
    under ``all`` the final norm's statistics are bfloat16 too."""

    def head(self, params, hidden, dtype):
        stat = self.reference._dtypes(self.lowered)[3]
        return self.reference.rms_norm(
            hidden, params["ln_final"]["scale"],
            float(self.model["norm_eps"]), dtype, stat,
        ) @ params["lm_head"]["kernel"].astype(dtype)


def readings(worker, reference, lowered=()):
    """One forward of the program (its kernels, its dtype) with what its
    layers sow, in the model's order."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import traffic as traffic_lib
    from dlrover_tpu.models import linear_attention
    from dlrover_tpu.models import moe as moe_lib
    from dlrover_tpu.trainer import train_lib

    rows = traffic_lib.first_sequences(
        worker.sample_fn(), int(worker.traffic.get("reference_sequences", 2))
    )
    inputs = jnp.asarray(rows["inputs"])
    trainer = worker.trainer

    @jax.jit
    def program(params):
        _, sown = trainer.model.apply(
            {"params": params}, inputs, mutable=["intermediates"]
        )
        return sown["intermediates"]

    with train_lib.use_mesh(trainer.mesh), nn.logical_axis_rules(
        trainer._rules
    ):
        sown = program(trainer.state.params)
    out = {key: [] for key in (
        "mean_alpha", "min_alpha", "g_min", "past_bound_share", "mean_beta",
        "state_absmax", "moe_pairs_here", "moe_max_expert_load",
        "moe_drop_fraction",
    )}
    slots = sorted(sown["blocks"], key=lambda n: int(n.rsplit("_", 1)[1]))
    periods = int(trainer.model_config.num_scan_units)
    for period in range(periods):
        for slot in slots:
            layer = jax.tree.map(lambda a: a[period], sown["blocks"][slot])
            if "linear_attn" in layer:
                vec = np.asarray(
                    layer["linear_attn"][linear_attention.STATS_NAME][0],
                    np.float64,
                )
                for key, value in zip(
                    ("mean_alpha", "mean_beta", "state_absmax", "min_alpha",
                     "g_min", "past_bound_share"), vec,
                ):
                    out[key].append(float(value))
            _, drop, _, _, load = moe_lib.split_stats(
                np.asarray(layer["moe"]["moe_stats"][0])
            )
            out["moe_drop_fraction"].append(float(drop))
            out["moe_max_expert_load"].append(float(load))
            out["moe_pairs_here"].append(float(np.asarray(
                layer["moe"][moe_lib.SHARE_STATS_NAME][0]
            )[0]))
    return out


def main(argv=None) -> int:
    return mellum_control.main(
        argv, cell=CELL, stand_in=StandIn, faults=FAULTS,
        out="solar_open_control.json",
        doc=__doc__, lowered=("all", "rule"), readings=readings,
    )


if __name__ == "__main__":
    sys.exit(main())
