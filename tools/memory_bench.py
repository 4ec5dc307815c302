"""Memory-truth certification bench: measured bytes or no badge.

Certifies the classified HBM accounting plane
(``utils/memory_profile.py`` + ``master/memory_ledger.py``) from
**measured buffer bytes**, not the shape model it is meant to audit:

1. **model** — registry-measured params / opt-state pool bytes must match
   the shape-only model (``jax.eval_shape`` of the same init: dtypes and
   shapes, no device buffers) — the accounting itself is calibrated
   before it calibrates anything else.
2. **zero1** — the SAME config built at dp∈{1,2,4} (device subsets of one
   virtual 4-CPU world) with ``zero1=True``: measured per-device
   opt-state pool bytes must fall ~1/dp and match the build's own
   ``zero1_stats`` modeled bytes — sharding shows up in the *measured*
   numbers because ``per_device_nbytes`` prices the shard, not the
   global array.
3. **kv** — a ``ServingEngine`` at tp=1 vs tp=2: measured per-device KV
   pool bytes must fall ~1/tp.
4. **accum** — compiled ``memory_analysis()`` temp bytes for grad_accum=4
   under fp32 vs bf16 accumulators: the measured temp delta must equal
   the halved accumulator (``params_bytes / 2``) — XLA's own ledger
   certifies the knob, not the docstring.
5. **live** — an ``ElasticTrainer`` with ``memory_report=True`` runs real
   steps; ``memory`` telemetry events drain through the real
   ``MasterServicer`` routing into a ``MemoryLedger`` → ``dlrover_hbm_*``
   gauges render, the calibration ledger learns a measured-vs-modeled
   memory ratio, and a ``train_step`` trace-count pin holds zero
   steady-state retraces (the plane costs an attribute read, not a
   recompile).
6. **postmortem** — ``dump_oom_postmortem`` writes a classified top-N
   live-buffer table a human can read at 3am.

    python tools/memory_bench.py --out MEMORY.json

``evaluate_memory_gate`` is the ok-gate as a pure predicate, testable
without running the bench.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dlrover_tpu.utils.devices import (  # noqa: E402
    device_fields,
    virtual_cpu_devices,
)

#: Shape-model agreement for the registry's own accounting (leg 1) and
#: the zero1 modeled-vs-measured comparison: the only tolerated slack is
#: replicated scalar leaves (optimizer step counters) the shard model
#: does not bother pricing.
MODEL_RTOL = 0.05
#: The accumulator delta is bitwise-predictable (params_bytes / 2); the
#: tolerance absorbs layout padding only.
ACCUM_RTOL = 0.10


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--out", default="MEMORY.json")
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--seq-len", type=int, default=16)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--grad-accum", type=int, default=4,
                   help="microbatches for the accumulator-dtype leg")
    p.add_argument("--serve-slots", type=int, default=2)
    p.add_argument("--live-steps", type=int, default=4,
                   help="trainer steps for the live-plane leg")
    return p


def evaluate_memory_gate(result):
    """The MEMORY.json ok gate as a pure predicate: the registry's pool
    accounting matches the shape model, ZeRO-1 opt-state bytes measure
    ~1/dp (and match the build's own model), the serve KV pool measures
    ~1/tp, the bf16 accumulator's measured temp delta equals the halved
    accumulator, live memory events flow end-to-end into gauges and the
    calibration ledger with zero steady-state retraces, and the OOM
    postmortem table classifies its top rows."""
    def _rel(measured, modeled):
        return (abs(measured - modeled) / modeled
                if modeled > 0 else math.inf)

    po = result["param_opt"]
    z = result["zero1"]["legs"]
    z_meas = [leg["measured_opt_b"] for leg in z]
    kv = {leg["tp"]: leg["measured_kv_b"] for leg in result["kv"]["legs"]}
    ac = result["accum"]
    live = result["live"]
    pm = result["postmortem"]
    kv_ratio = (kv[1] / kv[2]) if kv.get(2, 0) > 0 else 0.0
    checks = {
        "params_match_shape_model": _rel(
            po["measured_params_b"], po["modeled_params_b"]
        ) <= MODEL_RTOL,
        "opt_state_matches_shape_model": _rel(
            po["measured_opt_b"], po["modeled_opt_b"]
        ) <= MODEL_RTOL,
        "zero1_opt_bytes_fall_with_dp": (
            all(a > b for a, b in zip(z_meas, z_meas[1:]))
            and z_meas[-1] > 0
            and z_meas[0] / z_meas[-1] >= 2.5
        ),
        "zero1_measured_matches_model": all(
            _rel(leg["measured_opt_b"], leg["modeled_opt_b"])
            <= 2 * MODEL_RTOL
            for leg in z if leg["modeled_opt_b"] > 0
        ),
        "kv_pool_falls_with_tp": 1.6 <= kv_ratio <= 2.6,
        "accum_bf16_halves_accumulator": (
            ac["temp_delta_b"] > 0
            and _rel(ac["temp_delta_b"], ac["accum_half_b"]) <= ACCUM_RTOL
        ),
        "live_events_flow": (
            live["events"] >= 2
            and live["ledger"]["bytes_in_use"] > 0
            and live["ledger"]["pool_params_b"] > 0
            and live["ledger"]["pool_opt_state_b"] > 0
        ),
        "live_gauges_render": live["gauges_rendered"],
        "calibration_learned_memory_ratio": (
            live["calibration_memory_ratio"] > 0
        ),
        "steady_state_no_retrace": live["retraces"] == 0,
        "postmortem_classified": (
            pm["rows"] >= 1 and pm["top_pool"] in pm["pools"]
        ),
    }
    failed = sorted(name for name, held in checks.items() if not held)
    return not failed, failed


def _model_config(args):
    from dlrover_tpu.models.gpt2 import gpt2_config

    return gpt2_config(
        "124m", num_layers=args.layers, d_model=args.d_model,
        num_heads=args.heads, vocab_size=args.vocab,
        max_seq_len=max(64, args.seq_len),
    )


def _build(args, dp: int, *, grad_accum: int = 1,
           accum_dtype: str = "float32", zero1: bool = False,
           optimizer: str = "adamw"):
    """One ShardedTrain over the first ``dp`` devices of the virtual
    world — the same config measured at different data widths."""
    import jax

    from dlrover_tpu.models.transformer import TransformerLM
    from dlrover_tpu.parallel import rules as lr
    from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
    from dlrover_tpu.trainer import train_lib

    mesh = build_mesh(
        ParallelConfig(data=dp), devices=jax.devices()[:dp]
    )
    model = TransformerLM(_model_config(args))
    opt = train_lib.make_optimizer(optimizer, learning_rate=1e-2)
    return train_lib.build_sharded_train(
        model, opt, mesh, lr.DEFAULT_RULES,
        global_batch_size=args.batch_size, seq_len=args.seq_len,
        grad_accum=grad_accum, accum_dtype=accum_dtype, zero1=zero1,
    )


def _shape_tree_nbytes(tree) -> int:
    """Bytes the SHAPE MODEL prices for a tree of ShapeDtypeStructs —
    no buffers exist; this is the modeled side of leg 1."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    return total


def run_param_opt_leg(args):
    """Registry-measured params/opt pools vs the shape-only model."""
    import jax

    from dlrover_tpu.utils import memory_profile as mp

    train = _build(args, dp=1)
    state = train.init(jax.random.PRNGKey(0))
    modeled = jax.eval_shape(train.init, jax.random.PRNGKey(0))

    reg = mp.BufferRegistry()
    reg.register("params", "bench.params", lambda: state.params)
    reg.register("opt_state", "bench.opt", lambda: state.opt_state)
    pools = reg.pool_bytes()
    return {
        "measured_params_b": pools["params"],
        "measured_opt_b": pools["opt_state"],
        "modeled_params_b": _shape_tree_nbytes(modeled.params),
        "modeled_opt_b": _shape_tree_nbytes(modeled.opt_state),
    }


def run_zero1_leg(args):
    """Measured per-device opt-state bytes across dp∈{1,2,4} under
    ZeRO-1: sharding must show up in the measured numbers."""
    import jax

    from dlrover_tpu.utils import memory_profile as mp

    legs = []
    for dp in (1, 2, 4):
        train = _build(args, dp=dp, zero1=True)
        state = train.init(jax.random.PRNGKey(0))
        measured = mp.tree_device_nbytes(state.opt_state)
        stats = train.zero1_stats or {}
        legs.append({
            "dp": dp,
            "measured_opt_b": measured,
            "modeled_opt_b": int(stats.get("bytes_per_device_after", 0)),
            "sharded_leaves": int(stats.get("sharded_leaves", 0)),
        })
    return {"legs": legs}


def run_kv_leg(args):
    """Measured per-device KV-pool bytes at tp=1 vs tp=2 through the
    engine's own registry registration."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from dlrover_tpu.serving.engine import ServingEngine
    from dlrover_tpu.utils import memory_profile as mp

    config = TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model,
        num_heads=args.heads, num_layers=args.layers,
        d_ff=args.d_model * 2, max_seq_len=max(64, args.seq_len),
    )
    params = TransformerLM(config).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]

    legs = []
    for tp in (1, 2):
        mp.registry().clear()
        engine = ServingEngine(
            config, params, slots=args.serve_slots,
            tp=tp, tp_devices=tp if tp > 1 else None,
        )
        pools = mp.registry().pool_bytes()
        legs.append({
            "tp": tp,
            "measured_kv_b": pools["kv_pool"],
            "measured_params_b": pools["params"],
        })
        del engine
    mp.registry().clear()
    return {"legs": legs}


def run_accum_leg(args):
    """XLA's compiled memory_analysis prices the grad-accum carry: the
    fp32→bf16 temp-bytes delta must equal the halved accumulator."""
    import jax

    from dlrover_tpu.utils import memory_profile as mp

    def temps(accum_dtype):
        train = _build(
            args, dp=1, grad_accum=args.grad_accum,
            accum_dtype=accum_dtype, optimizer="sgd",
        )
        train.aot_compile()
        state = train.init(jax.random.PRNGKey(0))
        params_b = mp.tree_device_nbytes(state.params)
        return (train.memory_analysis or {}).get("xla_temp_b", 0), params_b

    temp_f32, params_b = temps("float32")
    temp_bf16, _ = temps("bf16")
    return {
        "grad_accum": args.grad_accum,
        "temp_f32_b": temp_f32,
        "temp_bf16_b": temp_bf16,
        "temp_delta_b": temp_f32 - temp_bf16,
        "params_b": params_b,
        # The fp32 accumulator is one params-shaped tree; bf16 halves it,
        # so the measured temp delta should be params_b / 2.
        "accum_half_b": params_b // 2,
    }


def run_live_leg(args, tmpdir):
    """Real trainer steps with memory_report=True: events drain through
    the real servicer routing into MemoryLedger + calibration, gauges
    render, and the trace-count pin holds."""
    import jax

    from dlrover_tpu.common import telemetry
    from dlrover_tpu.master import messages as msg
    from dlrover_tpu.master.calibration import CalibrationLedger
    from dlrover_tpu.master.memory_ledger import MemoryLedger
    from dlrover_tpu.master.servicer import MasterServicer
    from dlrover_tpu.master.timeline import JobTimeline
    from dlrover_tpu.trainer import train_lib
    from dlrover_tpu.trainer.elastic_trainer import (
        ElasticTrainer,
        TrainerConfig,
    )
    from dlrover_tpu.utils import memory_profile as mp

    # The flash-ckpt shm arena outlives processes and is named by the job
    # tag: without a unique tag, a previous bench run's arena (already at
    # max_steps) satisfies the restore and fit() runs zero steps.
    os.environ["DLROVER_TPU_JOB"] = (
        f"membench{os.getpid()}_{os.path.basename(tmpdir)}"
    )
    os.environ["DLROVER_TPU_SOCKET_DIR"] = os.path.join(tmpdir, "socks")

    mp.registry().clear()
    recorder = telemetry.recorder()
    was_enabled = recorder.enabled
    recorder.configure(enabled=True)
    try:
        trainer = ElasticTrainer(
            _model_config(args),
            TrainerConfig(
                global_batch_size=args.batch_size, seq_len=args.seq_len,
                learning_rate=1e-2, report_every=1, memory_report=True,
                warmup_compile=True, checkpoint_dir=tmpdir,
                ckpt_every=10 ** 6,
            ),
            client=None,
        )

        import numpy as np

        def loader(n):
            rng = np.random.default_rng(0)
            for _ in range(n):
                toks = rng.integers(
                    0, args.vocab,
                    size=(args.batch_size, args.seq_len + 1),
                    dtype=np.int32,
                )
                yield {"inputs": toks[:, :-1], "targets": toks[:, 1:]}

        traces_before = train_lib.trace_count("train_step")
        trainer.fit(loader(args.live_steps + 2),
                    max_steps=args.live_steps)
        traces = train_lib.trace_count("train_step") - traces_before
        trainer.close()
    finally:
        recorder.configure(enabled=was_enabled)

    events = [ev for ev in recorder.drain() if ev[0] == "memory"]

    # Route the drained ring through the REAL servicer dispatch — the
    # same elif the production drain RPC hits.
    timeline = JobTimeline()
    memory_ledger = MemoryLedger()
    calibration = CalibrationLedger()
    servicer = MasterServicer(
        timeline=timeline, memory_ledger=memory_ledger,
        calibration=calibration,
    )
    servicer._report_telemetry(msg.Envelope(
        node_id=0, node_type="worker", job_name="bench",
        payload=msg.TelemetryEvents(
            node_id=0, events=tuple(events), dropped=0
        ),
    ))
    text = timeline.render_metrics(
        calibration=calibration, memory=memory_ledger
    )
    mp.registry().clear()
    return {
        "steps": args.live_steps,
        "events": len(events),
        "ledger": memory_ledger.ledger(),
        "gauges_rendered": (
            "dlrover_hbm_bytes_in_use" in text
            and 'dlrover_hbm_pool_bytes{pool="params"}' in text
        ),
        "calibration_memory_ratio": float(
            calibration.ratios().get("memory", 0.0)
        ),
        # Steady-state pin: the one trace the warmup compile pays is the
        # only one allowed; memory reporting must not retrace.
        "retraces": max(0, traces - 1),
    }


def run_postmortem_leg(args, tmpdir):
    """Classified OOM forensics table: registered pools dominate the
    top rows of the dump."""
    import jax

    from dlrover_tpu.utils import memory_profile as mp

    mp.registry().clear()
    train = _build(args, dp=1)
    state = train.init(jax.random.PRNGKey(0))
    mp.registry().register("params", "bench.params", lambda: state.params)
    mp.registry().register("opt_state", "bench.opt",
                           lambda: state.opt_state)
    path = mp.dump_oom_postmortem(
        tmpdir, error=RuntimeError("RESOURCE_EXHAUSTED: bench probe"),
        cache_key="bench", top_n=8,
    )
    mp.registry().clear()
    if path is None:
        return {"rows": 0, "top_pool": "", "pools": list(mp.POOLS)}
    with open(path) as f:
        dump = json.load(f)
    rows = dump.get("top", [])
    return {
        "rows": len(rows),
        "top_pool": rows[0]["pool"] if rows else "",
        "top_nbytes": rows[0]["nbytes"] if rows else 0,
        "pools": list(mp.POOLS),
    }


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    virtual_cpu_devices(4)

    import tempfile

    with tempfile.TemporaryDirectory() as tmpdir:
        result = {
            "config": {
                "layers": args.layers, "d_model": args.d_model,
                "heads": args.heads, "vocab": args.vocab,
                "seq_len": args.seq_len, "batch_size": args.batch_size,
                "grad_accum": args.grad_accum,
                "live_steps": args.live_steps,
            },
            "param_opt": run_param_opt_leg(args),
            "zero1": run_zero1_leg(args),
            "kv": run_kv_leg(args),
            "accum": run_accum_leg(args),
            "live": run_live_leg(args, tmpdir),
            "postmortem": run_postmortem_leg(args, tmpdir),
        }
    ok, failed = evaluate_memory_gate(result)
    result["ok"] = ok
    result["device"] = device_fields()
    result["failed_checks"] = failed
    z = result["zero1"]["legs"]
    kv = {leg["tp"]: leg["measured_kv_b"]
          for leg in result["kv"]["legs"]}
    result["headline"] = {
        "opt_bytes_dp1_over_dp4": round(
            z[0]["measured_opt_b"] / z[-1]["measured_opt_b"], 2
        ) if z[-1]["measured_opt_b"] else 0.0,
        "kv_bytes_tp1_over_tp2": round(
            kv[1] / kv[2], 2
        ) if kv.get(2) else 0.0,
        "accum_delta_vs_half_params": round(
            result["accum"]["temp_delta_b"]
            / result["accum"]["accum_half_b"], 3
        ) if result["accum"]["accum_half_b"] else 0.0,
        "calibration_memory_ratio": round(
            result["live"]["calibration_memory_ratio"], 3
        ),
        "live_retraces": result["live"]["retraces"],
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
