"""Pipeline schedule accounting: settle interleaving with numbers.

VERDICT r3 #5 asked for a measurement where round 3 offered a docstring
argument (parallel/pipeline.py:28-37).  Two parts:

1. **Schedule simulator** — discrete per-(device, tick) accounting of four
   schedules over S stages, M microbatches, v interleave chunks (fwd work
   1 unit, bwd 2 units per microbatch-stage):
     * ``spmd``        — our all-slots-active scan (parallel/pipeline.py):
                         fwd M+S-1 ticks + bwd M+S-1 ticks, every device
                         busy every tick (bubble slots compute discarded
                         values), useful fraction M/(M+S-1);
     * ``gpipe``       — fwd drain then bwd drain, devices idle in bubbles:
                         same M/(M+S-1) useful fraction, less memory
                         headroom than 1F1B;
     * ``1f1b``        — the reference PipelineStage schedule
                         (ref ``pipe_compiler/PipelineStage.py``): same
                         bubble as GPipe, steady-state memory capped at S
                         in-flight microbatches;
     * ``1f1b_int``    — interleaved 1F1B (ref ``StageInterleaver.py``),
                         v chunks per device: bubble shrinks to
                         (S-1)/v ticks-equivalent at v x the stage-handoff
                         traffic;
   and the SPMD-interleaving variant the round-3 docstring rejected
   (``spmd_int``: per-tick work constant, ticks grow to M + vS - 1).

2. **Measured validation** — wall-clock of the real PipelinedBlocks train
   step on the virtual 8-device CPU mesh across (S, M) at fixed global
   work, compared against the simulator's predicted efficiency ratios.

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
           python tools/pipeline_account.py [--no-measure]
Prints one JSON document; paste the table into PROFILE.md.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, ".")


# ---------------------------------------------------------------------------
# 1. schedule simulator
# ---------------------------------------------------------------------------

FWD, BWD = 1.0, 2.0  # relative per-microbatch-stage work units


def sim_spmd(S: int, M: int, v: int = 1) -> dict:
    """All-slots-active SPMD scan: every tick every device computes one
    stage-slot (useful or bubble) — no idle ticks, bubbles burn compute.
    With v>1 virtual stages round-robin per device, per-tick device work
    is unchanged (1/v of the stage's layers x v slots) while the tick
    count grows to M + v*S - 1.  Work units: a fwd stage-slot costs FWD,
    its backward costs BWD (the generated backward mirrors the scan)."""
    total_work = (M + v * S - 1) * (FWD + BWD)
    useful_work = M * (FWD + BWD)
    return {
        "ticks": (M + v * S - 1) * (FWD + BWD),
        "useful_fraction": useful_work / total_work,
        "idle_fraction": 0.0,
        "wasted_compute_fraction": 1 - useful_work / total_work,
    }


def sim_gpipe(S: int, M: int) -> dict:
    """Fwd fill+drain then bwd fill+drain; devices idle in the bubbles."""
    span = (M + S - 1) * FWD + (M + S - 1) * BWD
    useful = M * (FWD + BWD)
    return {
        "ticks": span,
        "useful_fraction": useful / span,
        "idle_fraction": 1 - useful / span,
        "wasted_compute_fraction": 0.0,
    }


def sim_1f1b(S: int, M: int) -> dict:
    """Non-interleaved 1F1B: same critical path as GPipe ((S-1) fill +
    (S-1) drain around M steady (fwd+bwd) slots), but at most S in-flight
    microbatches of activations."""
    span = (S - 1) * (FWD + BWD) + M * (FWD + BWD)
    useful = M * (FWD + BWD)
    return {
        "ticks": span,
        "useful_fraction": useful / span,
        "idle_fraction": 1 - useful / span,
        "wasted_compute_fraction": 0.0,
        "in_flight_microbatches": min(S, M),
    }


def sim_1f1b_interleaved(S: int, M: int, v: int) -> dict:
    """Interleaved 1F1B: each device owns v non-contiguous chunks, so the
    fill/drain ramps shrink to (S-1)/v of a microbatch's full fwd/bwd —
    the device starts useful chunk work v x sooner."""
    span = (S - 1) / v * (FWD + BWD) + M * (FWD + BWD)
    useful = M * (FWD + BWD)
    return {
        "ticks": span,
        "useful_fraction": useful / span,
        "idle_fraction": 1 - useful / span,
        "wasted_compute_fraction": 0.0,
        "handoff_traffic_multiplier": v,
    }


def simulate(S: int, M: int, v: int = 2) -> dict:
    return {
        "spmd(ours)": sim_spmd(S, M),
        f"spmd_int(v={v})": sim_spmd(S, M, v=v),
        "gpipe": sim_gpipe(S, M),
        "1f1b(ref)": sim_1f1b(S, M),
        f"1f1b_int(v={v})": sim_1f1b_interleaved(S, M, v),
    }


# ---------------------------------------------------------------------------
# 2. measured validation on the virtual mesh
# ---------------------------------------------------------------------------


def measure(
    S: int, M: int, layers: int, steps: int = 3, v: int = 1
) -> tuple:
    """-> (step seconds, tokens/second) on the current mesh."""
    import jax
    import numpy as np

    from dlrover_tpu.models.gpt2 import gpt2_config
    from dlrover_tpu.models.transformer import TransformerLM
    from dlrover_tpu.parallel import rules as lr
    from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
    from dlrover_tpu.trainer import train_lib

    n = len(jax.devices())
    cfg = gpt2_config(
        "124m", num_layers=layers, d_model=128, num_heads=4,
        vocab_size=512, max_seq_len=128,
        pipeline_stages=S, num_microbatches=M if S > 1 else 0,
        pipeline_interleave=v,
    )
    # Hold the PER-MICROBATCH shape constant across M (4 rows per
    # microbatch x the data axis): otherwise shrinking microbatches mix
    # per-tick fixed costs into the bubble comparison.  Throughput is
    # normalized per token by the caller.
    batch = 4 * (n // S) * (M if S > 1 else 4)
    mesh = build_mesh(
        ParallelConfig(data=n // S, pipe=S), devices=jax.devices()
    )
    model = TransformerLM(cfg)
    opt = train_lib.make_optimizer("adamw", learning_rate=1e-3)
    train = train_lib.build_sharded_train(
        model, opt, mesh, lr.DEFAULT_RULES,
        global_batch_size=batch, seq_len=128,
    )
    state = train.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 512, size=(batch, 129), dtype=np.int32)
    data = train_lib.shard_batch(
        {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}, train
    )
    state, metrics = train.step(state, data)
    float(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = train.step(state, data)
    float(metrics["loss"])
    step_s = (time.perf_counter() - t0) / steps
    return step_s, batch * 128 / step_s  # (step time, tokens/s)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--no-measure", action="store_true")
    ap.add_argument("--layers", type=int, default=8)
    args = ap.parse_args()

    out = {"simulated": {}, "measured": {}}
    for S, M in [(4, 4), (4, 8), (4, 16), (4, 32), (8, 8), (8, 32)]:
        out["simulated"][f"S={S},M={M}"] = simulate(S, M)

    if not args.no_measure:
        import jax

        from dlrover_tpu.utils.devices import device_fields

        n = len(jax.devices())
        out["device"] = device_fields()
        rows = []
        base_s, base_tps = measure(1, 0, args.layers)
        for S in (2, 4):
            if n % S:
                continue
            for M in (S, 2 * S, 4 * S):
                t, tps = measure(S, M, args.layers)
                # pipe=S splits the layers S ways and the freed devices go
                # to data parallel, so total device-seconds are comparable;
                # per-TOKEN throughput vs pipe=1 exposes bubble + handoff
                # overhead, and the bubble model predicts its shape in M.
                predicted = (M + S - 1) / M
                rows.append({
                    "S": S, "M": M, "v": 1, "step_s": round(t, 4),
                    "tokens_per_s": round(tps, 0),
                    "pipe1_over_pipeS_throughput": round(base_tps / tps, 3),
                    "model_bubble_factor": round(predicted, 3),
                })
                # Circular (interleaved-1F1B-equivalent) schedule at the
                # same operating point, when the layer count allows v=2.
                if M >= S and args.layers % (S * 2) == 0:
                    tv, tpsv = measure(S, M, args.layers, v=2)
                    rows.append({
                        "S": S, "M": M, "v": 2, "step_s": round(tv, 4),
                        "tokens_per_s": round(tpsv, 0),
                        "pipe1_over_pipeS_throughput": round(
                            base_tps / tpsv, 3
                        ),
                        "model_bubble_factor": round(
                            (2 * M + S - 1) / (2 * M), 3
                        ),
                    })
        out["measured"] = {
            "pipe1_step_s": round(base_s, 4),
            "pipe1_tokens_per_s": round(base_tps, 0),
            "rows": rows,
        }

    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
