"""Measured 1→n scaling curve for the sharded training step.

The paper's auto-scaling pillar needs a *measured* multi-device baseline,
not a modeled one: this module times the full sharded train step (ZeRO-1
update by default — the PR 8 hot path) on data-parallel submeshes of
n ∈ {1, 2, 4, 8} devices and reports tokens/s, parallel efficiency vs
n=1, and the comm fraction of the step (the reduce_scatter + allgather
rows of ``train_lib.microbatch_phase_plan`` — the same modeled spans the
trainer books inside the measured step span).

Weak scaling: the per-device batch is constant, so ideal tokens/s is
linear in n and ``efficiency = tokens_per_s(n) / (n · tokens_per_s(1))``.

Every point runs in this process on the devices it can see: each builds a
submesh over the first n devices, and counts above the visible device count
are dropped and named in ``truncated_from``.  The result names the platform
it ran on; a sweep on virtual CPU devices says how XLA:CPU shares host
cores, nothing about chips.

``python -m dlrover_tpu.utils.scaling`` prints the measurement as JSON.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Any, Dict, Optional, Sequence

DEFAULT_NS = (1, 2, 4, 8)


def _measure_point(
    n: int,
    *,
    per_device_batch: int = 4,
    seq_len: int = 32,
    steps: int = 3,
    zero1: bool = True,
    grad_accum: int = 1,
    reduce_quant: str = "none",
    profile: bool = True,
) -> Dict[str, Any]:
    """Time ``steps`` sharded train steps on an n-device data submesh.

    ``profile=True`` (default) additionally captures ONE extra step under
    a :class:`~dlrover_tpu.utils.device_profile.DeviceProfiler` window and
    reports ``comm_fraction`` from *measured* device collective seconds
    (``comm_source: "measured"``); when the capture fails or yields no
    collective ops, the modeled phase-plan rows price it instead
    (``comm_source: "modeled"``) — each point says which it got.
    """
    import jax
    import numpy as np

    from dlrover_tpu.models.gpt2 import gpt2_config
    from dlrover_tpu.models.transformer import TransformerLM
    from dlrover_tpu.parallel import rules as lr
    from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
    from dlrover_tpu.trainer import train_lib

    devices = jax.devices()[:n]
    mesh = build_mesh(ParallelConfig(data=n), devices=devices)
    config = gpt2_config(
        "124m", num_layers=2, d_model=64, num_heads=4,
        vocab_size=256, max_seq_len=seq_len,
    )
    model = TransformerLM(config)
    opt = train_lib.make_optimizer("sgd", learning_rate=1e-3)
    batch_size = per_device_batch * n
    train = train_lib.build_sharded_train(
        model, opt, mesh, lr.DEFAULT_RULES,
        global_batch_size=batch_size, seq_len=seq_len,
        grad_accum=grad_accum, reduce_quant=reduce_quant, zero1=zero1,
    )
    state = train.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = rng.integers(
        0, config.vocab_size, size=(batch_size, seq_len + 1), dtype=np.int32
    )
    batch = train_lib.shard_batch(
        {"inputs": toks[:, :-1], "targets": toks[:, 1:]}, train
    )
    # Warmup step pays the compile; the timed loop measures steady state.
    state, metrics = train.step(state, batch)
    jax.block_until_ready(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = train.step(state, batch)
    jax.block_until_ready(metrics["loss"])
    step_s = (time.perf_counter() - t0) / max(1, steps)
    loss = float(metrics["loss"])
    rows = train_lib.microbatch_phase_plan(
        train.grad_accum, reduce_quant, step_s, zero1=train.zero1
    )
    # n=1 has no data axis, hence no wire: the modeled "reduce" row is an
    # artifact of the shared phase plan there, not a comm cost.
    comm_s = 0.0 if n <= 1 else sum(
        r["dur"] for r in rows
        if r["phase"] in ("reduce_scatter", "allgather", "reduce")
    )
    comm_fraction = comm_s / step_s if step_s else 0.0
    comm_source = "modeled"
    if profile and n > 1:
        # One extra captured step: when the window parses, the comm
        # fraction comes from measured device collective seconds (share
        # of device op time, not a cost-model guess).
        from dlrover_tpu.utils import device_profile

        prof = device_profile.DeviceProfiler(profile_every=1)
        if prof.arm(0):
            state, metrics = train.step(state, batch)
            try:
                jax.block_until_ready(metrics["loss"])
            except Exception:  # noqa: BLE001 - capture is best-effort
                pass
            window = prof.finish()
            if window is not None and window.device_total_s > 0.0:
                comm_fraction = (
                    window.seconds("collective") / window.device_total_s
                )
                comm_source = "measured"
    return {
        "n": n,
        "step_s": step_s,
        "tokens_per_s": batch_size * seq_len / step_s if step_s else 0.0,
        "comm_fraction": comm_fraction,
        "comm_source": comm_source,
        "zero1": bool(train.zero1),
        "loss": loss,
        "ok": bool(np.isfinite(loss)),
    }


def _finish(points: list, source: str) -> Dict[str, Any]:
    """Attach efficiency-vs-n=1 and the human-readable table."""
    base = next((p for p in points if p["n"] == 1), None)
    base_tps = base["tokens_per_s"] if base else 0.0
    for p in points:
        ideal = base_tps * p["n"]
        p["efficiency"] = p["tokens_per_s"] / ideal if ideal else 0.0
    table = [f"{'n':>3} {'tokens/s':>12} {'speedup':>8} "
             f"{'efficiency':>10} {'comm%':>6} {'src':>9}"]
    for p in points:
        speedup = p["tokens_per_s"] / base_tps if base_tps else 0.0
        table.append(
            f"{p['n']:>3} {p['tokens_per_s']:>12.0f} {speedup:>8.2f} "
            f"{p['efficiency'] * 100:>9.1f}% "
            f"{p['comm_fraction'] * 100:>5.1f}% "
            f"{p.get('comm_source', 'modeled'):>9}"
        )
    return {
        "ok": all(p.get("ok") for p in points) and bool(points),
        "source": source,
        "ns": [p["n"] for p in points],
        "points": points,
        "table": table,
    }


def measure_scaling(
    ns: Sequence[int] = DEFAULT_NS, **point_kw: Any
) -> Dict[str, Any]:
    """The scaling block: tokens/s at each n, efficiency vs n=1, comm%.

    Returns ``{"ok": false, "cause": ...}`` instead of raising when no
    requested count fits the visible devices, so driver callers can
    attach the verdict as data.
    """
    import jax

    ns = sorted(set(int(n) for n in ns if n >= 1))
    if not ns:
        return {"ok": False, "cause": "empty ns", "points": []}
    devices = jax.devices()
    avail = [n for n in ns if n <= len(devices)]
    if not avail:
        return {
            "ok": False, "points": [],
            "cause": f"{len(devices)} device(s) < min(ns)={min(ns)}",
        }
    points = [_measure_point(n, **point_kw) for n in avail]
    out = _finish(
        points,
        source=f"in-process ({len(devices)} {devices[0].platform} devices)",
    )
    if avail != ns:
        out["truncated_from"] = list(ns)
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--ns", default="1,2,4,8",
                   help="comma-separated device counts")
    p.add_argument("--per-device-batch", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=32)
    p.add_argument("--steps", type=int, default=3)
    p.add_argument("--zero1", default="True",
                   help="True | False (sharded vs replicated update)")
    p.add_argument("--grad-accum", type=int, default=1)
    p.add_argument("--reduce-quant", default="none")
    p.add_argument("--profile", default="True",
                   help="True | False (capture one profiled step per "
                        "point for a measured comm_fraction)")
    args = p.parse_args(argv)
    ns = [int(x) for x in args.ns.split(",") if x.strip()]
    out = measure_scaling(
        ns,
        per_device_batch=args.per_device_batch,
        seq_len=args.seq_len,
        steps=args.steps,
        zero1=args.zero1 not in ("False", "false", "0"),
        grad_accum=args.grad_accum,
        reduce_quant=args.reduce_quant,
        profile=args.profile not in ("False", "false", "0"),
    )
    print(json.dumps(out), flush=True)
    return 0 if out.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
