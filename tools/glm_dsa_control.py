"""``tools/mellum_control.py`` for the GLM-5.2 cell (sparse attention over an
indexer's choice): the comparison that decides ``correct``, handed what it
has to refuse, and what that comparison cannot see.

    chiprun -- python tools/glm_dsa_control.py --seeds <n> ...
    chiprun -- python tools/glm_dsa_control.py --seeds <n> \\
        --program attn_init_score_std=4 index_init_score_std=4  # other scales

For each seed, through the cell's own ``check_reference``: the program as it
stands; the reference computed wholly in bfloat16 (``all``), with only its
rotation (``rotation``: positions, angles, cos, sin) and only its indexer
(``indexer``: projections, scores, the sum over heads, so the choice) in
bfloat16; the float32 reference with a FAULT made (``dense``: no choice;
``window``: the most recent 2,048 keys; ``half_topk``; ``reuse_chooses``: a
reusing layer chooses for itself; ``no_relu``).  Then, on the same weights
and the cell's first sequence, what ``correct`` does not compare
(``readings``): the share of chosen (query, key) pairs on which the
program's choice and the float32 reference's AGREE, layer by layer; each
choosing layer's ``L^I`` and the MTP module's per-token loss against the
reference's; ``selected_share``, ``moe_pairs_here``, the busiest expert's
load and the rows past the budget; and the same agreement of sets for each
lowered reference.  The loop, the arguments and the output are
``tools/mellum_control.py``'s.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import mellum_control  # noqa: E402

CELL = "glm-5.2.train_16k"
FAULTS = ("dense", "window", "half_topk", "reuse_chooses", "no_relu")


def _layers(sown, periods):
    """The sown tree's layers in the reference's order: the dense prefix's,
    the trunk's period by period, the MTP module's; a scanned slot's leaves
    lose their leading (period) axis."""
    import jax

    def number(name):
        return int(name.rsplit("_", 1)[1])

    for name in sorted((k for k in sown if k.startswith("dense_")), key=number):
        yield sown[name]
    for period in range(periods):
        for slot in sorted(sown.get("blocks", {}), key=number):
            yield jax.tree.map(
                lambda a: a[period], sown["blocks"][slot]
            )
    if "mtp" in sown:
        yield sown["mtp"]["block"]


def readings(worker, reference, lowered=()):
    """One forward of the program (its kernels, its dtype) with what its
    layers sow and each attention layer's choice, against the reference's
    forward on the same weights, and the ``lowered`` references' choices
    against it."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import traffic as traffic_lib
    from dlrover_tpu.models import moe as moe_lib
    from dlrover_tpu.models.sparse_attention import SparseLatentAttention
    from dlrover_tpu.trainer import train_lib

    rows = traffic_lib.first_sequences(
        worker.sample_fn(), int(worker.traffic.get("reference_sequences", 2))
    )
    inputs, targets = jnp.asarray(rows["inputs"]), jnp.asarray(rows["targets"])
    trainer = worker.trainer
    mtp = bool(worker.model.get("mtp_depth"))

    @jax.jit
    def program(params):
        outs, sown = trainer.model.apply(
            {"params": params}, inputs, mutable=["intermediates"],
            capture_intermediates=lambda module, method: isinstance(
                module, SparseLatentAttention
            ) and method == "__call__",
            **({"next_tokens": targets} if mtp else {}),
        )
        nll = None
        if mtp:
            logp = jax.nn.log_softmax(outs[2].astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(
                logp[:, :-1], targets[:, 1:, None], -1
            )[..., 0]
        return nll, sown["intermediates"]

    def on_host(masks):
        """The layers' choices as numpy arrays, a shared one fetched once:
        nothing ``[T, T]`` stays on the device between two forwards."""
        fetched = {}
        return [
            fetched.setdefault(id(m), np.asarray(m) != 0) for m in masks
        ]

    def agreement(masks, exact):
        return [float((a & b).sum() / b.sum()) for a, b in zip(masks, exact)]

    want = reference.forward(
        worker.model, trainer.state.params, inputs, targets
    )
    exact = on_host(want.pop("masks"))
    out = {key: [] for key in (
        "selected_share", "score_absmax", "index_kl", "choice_agreement",
        "moe_pairs_here", "moe_max_expert_load", "moe_drop_fraction",
    )}
    out["index_kl_reference"] = [float(v) for v in want["index_kl"]]
    # the same agreement for each lowered reference: what the program's
    # float32 rotation and its float32 scores buy
    out["choice_agreement_lowered"] = {
        mode: agreement(on_host(reference.forward(
            worker.model, trainer.state.params, inputs, targets,
            lowered=mode,
        )["masks"]), exact) for mode in lowered
    }
    with train_lib.use_mesh(trainer.mesh), nn.logical_axis_rules(
        trainer._rules
    ):
        mtp_nll, sown = program(trainer.state.params)
    periods = int(worker.trainer.model_config.num_scan_units)
    for layer, mask in zip(_layers(sown, periods), exact):
        attn = layer["attn"]
        # (y, Index(mask, kl)) as the layer returned it
        out["choice_agreement"] += agreement(
            on_host([attn["__call__"][0][1][0]]), [mask]
        )
        if "index_stats" in attn:
            chosen, seen, absmax, kl = np.asarray(
                attn["index_stats"][0], np.float64
            )
            out["selected_share"].append(chosen / seen)
            out["score_absmax"].append(absmax)
            out["index_kl"].append(kl)
        if "moe" in layer:
            _, drop, _, _, load = moe_lib.split_stats(
                np.asarray(layer["moe"]["moe_stats"][0])
            )
            out["moe_drop_fraction"].append(float(drop))
            out["moe_max_expert_load"].append(float(load))
            out["moe_pairs_here"].append(float(np.asarray(
                layer["moe"][moe_lib.SHARE_STATS_NAME][0]
            )[0]))
    if mtp:
        out["mtp_nll_error"] = float(
            np.abs(np.asarray(mtp_nll) - np.asarray(want["mtp_nll"])).mean()
        )
    return out


def main(argv=None) -> int:
    return mellum_control.main(
        argv, cell=CELL, faults=FAULTS, out="glm_dsa_control.json",
        doc=__doc__, lowered=("all", "rotation", "indexer"),
        readings=readings,
    )


if __name__ == "__main__":
    sys.exit(main())
