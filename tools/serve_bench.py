"""Serving bench: continuous vs static batching on the slotted decode engine.

The artifact behind SERVE.json: run the SAME mixed-length request trace
through two ServingEngine configurations sharing one set of compiled
programs —

* **continuous** — a freed KV-cache slot is refilled on the very next
  scheduler step (the serving plane's default);
* **static** — admission waits until the whole slot pool drains, so every
  batch runs as long as its longest member (the classic fixed-batch
  baseline).

and report tokens/s, request latency p50/p95 and slot occupancy for both,
plus the AOT warm-start story: the first engine pays the cold
``aot_compile`` (booked as a real compile in the SpeedMonitor ledger), the
second hits the process-wide program memo and books a CACHED compile —
the ledger the ``ok`` gate checks.

    python tools/serve_bench.py --slots 4 --requests 24 --out SERVE.json

``--fleet-drill`` runs the serving *survivability* drill instead (writes
SERVE_FLEET.json): the same trace goes through the RPC front door
(``ServeFrontend``) onto a ``ReplicaFleet``, then the drill kills a
replica mid-flight via the ``replica.death`` Faultline seam (zero lost
requests — every in-flight id resubmits onto survivors), measures a load
shed's fast-reject wall time against its budget, cancels a queued
request, hot-swaps the survivors' weights from a checkpoint between
decode steps (zero retrace, no slot drain) and finishes with a
scripted-corruption swap that must roll back and keep serving.

    python tools/serve_bench.py --fleet-drill --replicas 2

``--tp-drill`` certifies the tensor-parallel serving plane instead
(writes SERVE_TP.json), four phases:

1. **TP scaling** — the same greedy trace at tp ∈ {1, 2, 4}: greedy
   tokens must be IDENTICAL across widths, measured per-device KV-pool
   bytes must fall as 1/tp (addressable shards), and the compiled
   per-device decode program's cost (``Compiled.cost_analysis`` of the
   SPMD partition — what one device actually executes) must shrink
   monotonically.  ``device_bound_tokens_per_s`` projects the tp=1
   measured wall rate through that per-device cost ratio: on this box's
   serialized host devices wall time cannot show TP speedup, so the
   artifact reports BOTH and gates on the device-bound number.
2. **Disaggregated prefill** — a colocated fleet (mixed replicas) vs a
   prefill+decode split under the same admission ramp: the decode pool's
   decode-step p95 must be lower when prefill bubbles land elsewhere.
3. **Speculative decoding** — a 1-layer draft sliced from the target's
   own stacked blocks (later blocks damped toward pass-through so the
   draft is a faithful predictor) must clear the acceptance floor, beat
   plain decode tokens/s, and emit bitwise-identical greedy streams.
4. **TP fleet resize** — fold a live tp-logical-4 engine 4→2→4
   mid-serve; the fold back to the seen width must retrace NOTHING.

The drill serves fp32 activations (bf16's reduction error exceeds the
top-2 logit gap, so bf16 greedy near-ties flip for reasons unrelated to
TP) and a model small enough that decode is dispatch-bound — the regime
speculation targets:

    python tools/serve_bench.py --tp-drill --d-model 32 --vocab 64

Runs on the platform jax finds (set ``JAX_PLATFORMS=cpu`` for the CPU: the
comparison is about scheduling — both legs run the same compiled
programs) and names it in the ``device`` block of what it writes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from dlrover_tpu.utils.devices import (  # noqa: E402
    device_fields,
    virtual_cpu_devices,
)


def build_model(args):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    config = TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model, num_heads=args.heads,
        num_layers=args.layers, d_ff=args.d_model * 2,
        max_seq_len=args.max_seq_len,
    )
    params = TransformerLM(config).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    return config, params


def make_trace(args, greedy: bool = False, reserve: int = 0):
    """A deterministic mixed-length request trace: heterogeneous prompt
    widths (several buckets) AND heterogeneous decode lengths — the
    workload shape static batching is worst at.  ``greedy=True`` forces
    temperature 0 everywhere (bitwise-comparable legs); ``reserve``
    clamps decode lengths so bucket + new + reserve fits max_seq_len
    (speculation's verify-write headroom)."""
    import numpy as np

    from dlrover_tpu.rl.generation import SamplingParams
    from dlrover_tpu.serving.bucketing import pick_bucket

    rng = np.random.RandomState(args.seed)
    buckets = tuple(int(w) for w in args.buckets.split(","))
    prompt_lens = [int(w) for w in args.prompt_lens.split(",")]
    new_lens = [int(w) for w in args.new_lens.split(",")]
    trace = []
    for i in range(args.requests):
        p = prompt_lens[i % len(prompt_lens)]
        n = new_lens[i % len(new_lens)]
        n = max(1, min(
            n, args.max_seq_len - pick_bucket(p, buckets) - reserve
        ))
        prompt = rng.randint(1, args.vocab, size=p).astype(np.int32)
        # Greedy rows keep token counts identical across both legs; the
        # sampled rows exercise the vectorized per-request SamplingParams.
        sampling = SamplingParams(
            temperature=0.0 if greedy or i % 2 == 0 else 0.8,
            top_k=0 if greedy or i % 4 < 2 else 8,
            max_new_tokens=n,
        )
        trace.append((f"req{i:03d}", prompt, sampling))
    return trace


def run_leg(config, params, trace, args, static: bool):
    from dlrover_tpu.serving import Request, ServingEngine

    buckets = tuple(int(w) for w in args.buckets.split(","))
    engine = ServingEngine(
        config, params, slots=args.slots, buckets=buckets,
        seed=args.seed, static_batching=static,
    )
    warm_s = engine.aot_compile()
    requests = [
        Request(uid, prompt, sampling) for uid, prompt, sampling in trace
    ]
    t0 = time.perf_counter()
    results = engine.run(requests)
    wall_s = time.perf_counter() - t0
    stats = engine.stats()
    tokens = sum(len(r.tokens) for r in results.values())
    latencies = sorted(r.latency_s for r in results.values())

    def q(p):
        return latencies[min(len(latencies) - 1, int(p * len(latencies)))]

    return {
        "mode": "static" if static else "continuous",
        "aot_s": round(warm_s, 4),
        "wall_s": round(wall_s, 4),
        "requests": len(results),
        "tokens": tokens,
        "tokens_per_s": round(tokens / wall_s, 2) if wall_s > 0 else 0.0,
        "p50_s": round(q(0.50), 5),
        "p95_s": round(q(0.95), 5),
        "occupancy": round(stats["occupancy"], 4),
        "decode_steps": int(stats["steps"]),
    }


def evaluate_gate(continuous, static, n_requests, ledger):
    """The ok gate as a pure predicate: (ok, failed-check names).

    Kept out of ``main`` so the rc contract — exit 0 iff every check
    holds — is testable without running the bench (``test_tools_cli``).
    """
    checks = {
        "continuous_completed": continuous["requests"] == n_requests,
        "static_completed": static["requests"] == n_requests,
        "token_parity": continuous["tokens"] == static["tokens"],
        "throughput_wins":
            continuous["tokens_per_s"] > static["tokens_per_s"],
        "p95_wins": continuous["p95_s"] < static["p95_s"],
        "warm_start_free": static["aot_s"] == 0.0,
        "compile_memo_hit": ledger["cached_compiles"] >= 1,
    }
    failed = sorted(name for name, held in checks.items() if not held)
    return not failed, failed


def evaluate_fleet_gate(drill):
    """The ``--fleet-drill`` ok gate as a pure predicate (testable from
    ``test_tools_cli`` without running the drill): zero lost requests
    across the replica death, sub-budget shed reject, bounded recovery
    with post-death p95 back under the SLO, and a hot-swap that neither
    retraces nor drains — with the corrupted leg rolled back and still
    serving."""
    checks = {
        "all_accepted": drill["accepted"] == drill["submitted"],
        "death_fired": drill["deaths"] >= 1,
        "resubmitted": drill["resubmitted"] >= 1,
        "zero_lost": drill["lost"] == 0,
        "recovered_in_budget": drill["recovered"],
        "post_death_completions": drill["post_death_completions"] >= 1,
        "p95_recovered_under_slo":
            drill["p95_post_death_s"] <= drill["slo_p95_s"],
        "shed_rejected": drill["shed"]["rejected"],
        "shed_fast": drill["shed"]["reject_s"] < drill["shed"]["budget_s"],
        "cancel_honored": drill["shed"]["cancelled"],
        "backlog_drained": drill["shed"]["drained"],
        "swap_ok": drill["swap"]["ok"],
        "swap_zero_retrace": drill["swap"]["retraces"] == 0,
        "swap_no_drain": drill["swap"]["no_drain"],
        "rollback_on_corruption": (
            drill["swap_corrupt"]["rolled_back"]
            and not drill["swap_corrupt"]["ok"]
        ),
        "version_pinned_after_rollback":
            drill["swap_corrupt"]["version"] == drill["swap"]["version"],
        "serving_after_rollback": drill["swap_corrupt"]["served_after"],
    }
    failed = sorted(name for name, held in checks.items() if not held)
    return not failed, failed


def _quantile(values, p):
    values = sorted(values)
    if not values:
        return 0.0
    return values[min(len(values) - 1, int(p * len(values)))]


def evaluate_tp_gate(drill):
    """The ``--tp-drill`` ok gate as a pure predicate (testable from
    ``test_tools_cli`` without running the drill).

    TP legs: every width completes the trace with tokens bitwise equal
    to tp=1 greedy; measured per-device KV bytes and compiled per-device
    decode cost both shrink monotonically, KV within 15% of ideal 1/tp;
    zero retraces after the AOT warm-up.  Disaggregation: the split
    fleet's decode-step p95 beats the colocated fleet's under the same
    ramp, zero requests lost, every page streamed.  Speculation: the
    acceptance floor holds, spec beats plain tokens/s, greedy streams
    are bitwise identical.  Resize: the mid-serve fold back to a seen
    width completes everything and retraces nothing."""
    legs = drill["tp_legs"]
    first, last = legs[0], legs[-1]
    monotonic = all(
        b["kv_device_bytes"] < a["kv_device_bytes"]
        and b["device_flops_per_step"] < a["device_flops_per_step"]
        and b["device_bound_tokens_per_s"] > a["device_bound_tokens_per_s"]
        for a, b in zip(legs, legs[1:])
    )
    checks = {
        "tp_all_completed": all(leg["completed"] for leg in legs),
        "tp_greedy_parity": all(leg["greedy_parity"] for leg in legs),
        "tp_device_scaling_monotonic": monotonic,
        "tp_kv_bytes_near_ideal": (
            last["kv_device_bytes"] * last["tp"]
            <= first["kv_device_bytes"] * 1.15
        ),
        "tp_zero_steady_retrace": all(
            leg["steady_retraces"] == 0 for leg in legs
        ),
        "disagg_completed": drill["disagg"]["completed"],
        "disagg_zero_lost": drill["disagg"]["lost"] == 0,
        "disagg_pages_streamed": (
            drill["disagg"]["pages_streamed"]
            >= drill["disagg"]["requests"]
        ),
        "disagg_decode_p95_wins": (
            drill["disagg"]["decode_step_p95_s"]
            < drill["disagg"]["colocated_decode_step_p95_s"]
        ),
        "spec_acceptance_floor": (
            drill["spec"]["accept_rate"] >= drill["spec"]["accept_floor"]
        ),
        "spec_throughput_wins": (
            drill["spec"]["tokens_per_s"]
            > drill["spec"]["plain_tokens_per_s"]
        ),
        "spec_greedy_parity": drill["spec"]["greedy_parity"],
        "resize_completed": drill["resize"]["completed"],
        "resize_zero_retrace": drill["resize"]["warm_fold_retraces"] == 0,
    }
    failed = sorted(name for name, held in checks.items() if not held)
    return not failed, failed


SERVE_TRACE_KEYS = (
    "serve_prefill", "serve_insert", "serve_decode",
    "serve_draft", "serve_verify",
)


def _trace_delta(before):
    from dlrover_tpu.trainer import train_lib

    return sum(
        train_lib.TRACE_COUNTS[k] - before[k] for k in SERVE_TRACE_KEYS
    )


def _trace_snapshot():
    from dlrover_tpu.trainer import train_lib

    return {k: train_lib.TRACE_COUNTS[k] for k in SERVE_TRACE_KEYS}


def make_draft(config, params, draft_layers: int = 1, damp: float = 0.05):
    """A draft model carved out of the target itself: the first
    ``draft_layers`` of the scan-stacked blocks (sliced on the leading
    layer axis) sharing the target's embedding/head — plus a DAMPED copy
    of the target whose later blocks' output projections are scaled by
    ``damp``, pushing them toward residual pass-through.  The damped
    target is what both bench legs serve, so the draft is a faithful
    predictor (high acceptance) without any training in the loop."""
    import dataclasses as dc

    import jax
    import numpy as np

    damped = jax.tree_util.tree_map_with_path(
        lambda path, leaf: _damp_leaf(path, leaf, draft_layers, damp),
        params,
    )
    draft = dict(damped)
    draft["blocks"] = jax.tree.map(
        lambda leaf: leaf[:draft_layers], damped["blocks"]
    )
    draft_config = dc.replace(config, num_layers=draft_layers)
    return draft_config, draft, damped


def _damp_leaf(path, leaf, draft_layers: int, damp: float):
    import jax.numpy as jnp
    from jax.tree_util import keystr

    key = keystr(path)
    if "'blocks'" not in key:
        return leaf
    if "'out'" not in key and "'wo'" not in key:
        return leaf
    scale = jnp.ones((leaf.shape[0],) + (1,) * (leaf.ndim - 1),
                     leaf.dtype)
    scale = scale.at[draft_layers:].set(damp)
    return leaf * scale


def run_tp_drill(args, out_path: str) -> int:
    import jax

    from dlrover_tpu.master.speed_monitor import SpeedMonitor
    from dlrover_tpu.serving import ReplicaFleet, Request, ServingEngine

    config, params = build_model(args)
    import dataclasses as dc

    import flax.linen as nn
    import jax.numpy as jnp

    params = nn.meta.unbox(params)
    # fp32 activations for the drill: greedy parity across TP widths is
    # a reassociation-tolerance statement, and at bf16 the top-2 logit
    # gap routinely sits BELOW the bf16 reduction error, so near-ties
    # flip tokens for reasons that have nothing to do with TP.  fp32
    # pushes the reassociation error ~2^-14 under the gap, making the
    # argmax decisive and the parity check bitwise.
    config = dc.replace(config, dtype=jnp.float32)
    buckets = tuple(int(w) for w in args.buckets.split(","))
    widths = [int(w) for w in args.tp_widths.split(",")]
    n_devices = len(jax.devices())
    greedy_trace = make_trace(args, greedy=True)

    def requests_of(trace):
        return [Request(u, p, s) for u, p, s in trace]

    # -- phase 1: TP scaling legs -----------------------------------------
    legs = []
    baseline_tokens = None
    for tp in widths:
        if tp > n_devices:
            print(f"tp drill: skipping tp={tp} (> {n_devices} devices)",
                  file=sys.stderr)
            continue
        engine = ServingEngine(
            config, params, slots=args.slots, buckets=buckets,
            seed=args.seed, tp=tp if tp > 1 else 0, tp_devices=tp,
        )
        engine.aot_compile()
        steady = _trace_snapshot()
        t0 = time.perf_counter()
        results = engine.run(requests_of(greedy_trace))
        wall_s = time.perf_counter() - t0
        tokens = {u: r.tokens.tolist() for u, r in results.items()}
        if baseline_tokens is None:
            baseline_tokens = tokens
        cost = engine.programs._aot[("decode",)].cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        legs.append({
            "tp": tp,
            "completed": len(results) == len(greedy_trace),
            "greedy_parity": tokens == baseline_tokens,
            "tokens": sum(len(t) for t in tokens.values()),
            "wall_s": round(wall_s, 4),
            "wall_tokens_per_s": round(
                sum(len(t) for t in tokens.values()) / wall_s, 2
            ) if wall_s > 0 else 0.0,
            "kv_device_bytes": int(engine.kv_device_bytes()),
            "device_flops_per_step": float(cost.get("flops", 0.0)),
            "steady_retraces": _trace_delta(steady),
        })
    # Device-bound tokens/s: the tp=1 measured wall rate projected
    # through the measured per-device program cost ratio — what the wall
    # clock would show if each partition ran on its own device instead
    # of serialized host-platform devices (methodology in the artifact).
    base = legs[0]
    for leg in legs:
        ratio = (
            base["device_flops_per_step"] / leg["device_flops_per_step"]
            if leg["device_flops_per_step"] > 0 else 0.0
        )
        leg["device_bound_tokens_per_s"] = round(
            base["wall_tokens_per_s"] * ratio, 2
        )

    # -- phase 2: disaggregated prefill vs colocated under a ramp ---------
    def run_ramp(make_fleet):
        fleet, probe_engines = make_fleet()
        trace = make_trace(args, greedy=True)
        submitted = 0
        for i, (uid, prompt, sampling) in enumerate(trace):
            fleet.submit(Request(uid, prompt, sampling))
            submitted += 1
            # A ramp, not a batch: admissions keep landing while slots
            # are live, so colocated decode steps absorb prefill bubbles.
            fleet.step()
        for _ in range(args.recover_steps):
            if fleet.pending() == 0:
                break
            fleet.step()
        stats = fleet.stats()
        return {
            "requests": submitted,
            "completed": fleet.pending() == 0,
            "lost": submitted - len(fleet.results),
            "decode_step_p95_s": max(
                e.stats()["decode_step_p95_s"] for e in probe_engines
            ),
            "pages_streamed": int(stats["pages_streamed"]),
            "page_bytes_streamed": int(stats["page_bytes_streamed"]),
        }

    def colocated():
        fleet = ReplicaFleet(min_replicas=1)
        engines = [
            ServingEngine(config, params, slots=args.slots,
                          buckets=buckets, seed=args.seed + i)
            for i in range(2)
        ]
        for e in engines:
            fleet.add_replica(e)
        return fleet, engines

    def disaggregated():
        fleet = ReplicaFleet(min_replicas=1)
        pre = ServingEngine(config, params, slots=args.slots,
                            buckets=buckets, seed=args.seed,
                            role="prefill")
        dec = ServingEngine(config, params, slots=args.slots,
                            buckets=buckets, seed=args.seed + 1,
                            role="decode")
        fleet.add_replica(pre)
        fleet.add_replica(dec)
        return fleet, [dec]

    coloc = run_ramp(colocated)
    disagg = run_ramp(disaggregated)
    disagg["colocated_decode_step_p95_s"] = coloc["decode_step_p95_s"]

    # -- phase 3: speculative decoding ------------------------------------
    draft_config, draft_params, damped_params = make_draft(
        config, params, draft_layers=args.draft_layers,
        damp=args.draft_damp,
    )
    spec_trace = make_trace(args, greedy=True, reserve=args.spec_tokens)
    plain_eng = ServingEngine(
        config, damped_params, slots=args.slots, buckets=buckets,
        seed=args.seed,
    )
    plain_eng.aot_compile()
    t0 = time.perf_counter()
    plain_res = plain_eng.run(requests_of(spec_trace))
    plain_wall = time.perf_counter() - t0
    spec_eng = ServingEngine(
        config, damped_params, slots=args.slots, buckets=buckets,
        seed=args.seed, draft_config=draft_config,
        draft_params=draft_params, spec_tokens=args.spec_tokens,
    )
    spec_eng.aot_compile()
    t0 = time.perf_counter()
    spec_res = spec_eng.run(requests_of(spec_trace))
    spec_wall = time.perf_counter() - t0
    spec_stats = spec_eng.stats()
    plain_tokens = sum(len(r.tokens) for r in plain_res.values())
    spec_tokens_n = sum(len(r.tokens) for r in spec_res.values())
    spec = {
        "gamma": args.spec_tokens,
        "draft_layers": args.draft_layers,
        "accept_rate": round(spec_stats["spec_accept_rate"], 4),
        "accept_floor": args.accept_floor,
        "plain_tokens_per_s": round(plain_tokens / plain_wall, 2)
        if plain_wall > 0 else 0.0,
        "tokens_per_s": round(spec_tokens_n / spec_wall, 2)
        if spec_wall > 0 else 0.0,
        "plain_wall_s": round(plain_wall, 4),
        "wall_s": round(spec_wall, 4),
        "greedy_parity": {
            u: r.tokens.tolist() for u, r in plain_res.items()
        } == {u: r.tokens.tolist() for u, r in spec_res.items()},
        "proposed": int(spec_stats["spec_proposed"]),
        "accepted": int(spec_stats["spec_accepted"]),
    }

    # -- phase 4: TP fleet resize (fold mid-serve) ------------------------
    fold_to = max(w for w in widths if w > 1 and w <= n_devices) \
        if any(w > 1 for w in widths) else 1
    resize = {"completed": True, "warm_fold_retraces": 0,
              "logical_tp": fold_to}
    if fold_to > 1:
        eng = ServingEngine(
            config, params, slots=args.slots, buckets=buckets,
            seed=args.seed, tp=fold_to, tp_devices=fold_to,
        )
        half = max(1, fold_to // 2)
        trace = make_trace(args, greedy=True)
        mid = len(trace) // 2
        # Cold pass: run at the full width, fold to the narrow width
        # mid-serve and finish — this traces the narrow fold's programs.
        for uid, prompt, sampling in trace[:mid]:
            eng.submit(Request(uid, prompt, sampling))
        for _ in range(4):
            eng.step()
        eng.fold_tp(half)
        eng.drain()
        # Warm pass: both widths now live in the program memo; a fold
        # back mid-serve must hit it — zero retraces while serving.
        for uid, prompt, sampling in trace[mid:]:
            eng.submit(Request(f"warm-{uid}", prompt, sampling))
        for _ in range(4):
            eng.step()
        steady = _trace_snapshot()
        eng.fold_tp(fold_to)
        results = eng.drain()
        resize = {
            "completed": len(results) == len(trace),
            "warm_fold_retraces": _trace_delta(steady),
            "logical_tp": fold_to,
            "folds": [fold_to, half, fold_to],
        }

    # Master-side booking: the drill's serve ledger carries the new
    # gauges (spec acceptance, decode-step p95) end to end.
    sm = SpeedMonitor()
    sm.record_serve(0, **spec_eng.stats())
    ledger = sm.serve_ledger()

    drill = {
        "devices": n_devices,
        "tp_legs": legs,
        "disagg": disagg,
        "colocated": coloc,
        "spec": spec,
        "resize": resize,
        "serve_ledger": ledger,
        "methodology": (
            "wall_tokens_per_s is measured wall clock on serialized "
            "host-platform devices (no real parallel hardware here); "
            "device_flops_per_step is the compiled per-device SPMD "
            "partition's cost (Compiled.cost_analysis), and "
            "device_bound_tokens_per_s projects the measured tp=1 wall "
            "rate through that per-device cost ratio. kv_device_bytes "
            "is measured from addressable shards."
        ),
    }
    ok, failed_checks = evaluate_tp_gate(drill)
    value = (
        legs[-1]["device_bound_tokens_per_s"]
        / legs[0]["device_bound_tokens_per_s"]
        if legs and legs[0]["device_bound_tokens_per_s"] > 0 else 0.0
    )
    result = {
        "metric": (
            f"device-bound decode scaling, tp={legs[-1]['tp']} over tp=1"
        ),
        "value": round(value, 3),
        "unit": "x tokens/s",
        "device": device_fields(),
        "detail": {"ok": ok, "failed_checks": failed_checks, **drill},
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0 if ok else 1


def run_fleet_drill(args, out_path: str) -> int:
    import shutil
    import tempfile

    # Isolate the checkpoint shm/socket namespace like the test suite does.
    os.environ.setdefault("DLROVER_TPU_JOB", f"servefleet{os.getpid()}")
    os.environ.setdefault("DLROVER_TPU_SOCKET_DIR", tempfile.mkdtemp())

    import jax

    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver
    from dlrover_tpu.common import faults
    from dlrover_tpu.master import messages as msg
    from dlrover_tpu.master.speed_monitor import SpeedMonitor
    from dlrover_tpu.serving import ReplicaFleet, ServeFrontend, ServingEngine
    from dlrover_tpu.trainer import train_lib

    config, params = build_model(args)
    trace = make_trace(args)
    buckets = tuple(int(w) for w in args.buckets.split(","))

    # The hot-swap payload: a recognizably different param tree on disk,
    # saved through the real checkpoint path so the digest chain (crc
    # sidecars + shard crcs) is the one production restores verify.
    swap_step = 7
    ckpt_dir = tempfile.mkdtemp(prefix="serve_fleet_ckpt_")
    swapped_params = jax.tree.map(lambda x: x * 1.25, params)
    saver = AsyncCheckpointSaver(ckpt_dir, host_index=0, num_hosts=1)
    saver.set_world([0])
    saver.start()
    ckpt_engine = CheckpointEngine(
        ckpt_dir, host_index=0, num_hosts=1, agree_step_fn=lambda c: c
    )
    try:
        if not ckpt_engine.save_to_storage(
            swap_step, {"params": swapped_params}
        ) or not ckpt_engine.wait_saver(timeout=120):
            print("fleet drill: checkpoint save failed", file=sys.stderr)
            return 1

        fleet = ReplicaFleet(min_replicas=1)
        for i in range(args.replicas):
            fleet.add_replica(ServingEngine(
                config, params, slots=args.slots, buckets=buckets,
                seed=args.seed + i,
            ))
        frontend = ServeFrontend(
            fleet, max_pending=args.max_pending,
            default_deadline_s=args.deadline_s,
        )

        def submit(uid, prompt, sampling, deadline_s):
            return frontend.submit(msg.ServeSubmit(
                uid=uid, prompt=tuple(int(t) for t in prompt),
                max_new_tokens=sampling.max_new_tokens,
                temperature=sampling.temperature, top_k=sampling.top_k,
                deadline_s=deadline_s,
            ))

        # -- phase 1: failover. Kill the last replica on tick --kill-tick
        # (the seam fires once per replica per fleet step, registry
        # order), mid-flight, and require every accepted request to
        # complete anyway.
        tickets = [
            submit(uid, prompt, sampling, args.deadline_s)
            for uid, prompt, sampling in trace
        ]
        accepted = [t.uid for t in tickets if t.accepted]
        death_hit = (args.kill_tick - 1) * args.replicas + args.replicas
        faults.configure(f"replica.death:error@{death_hit}", seed=args.seed)
        deaths_before = fleet.deaths
        post_death_uids = set()
        death_wall = None
        steps = 0
        while fleet.pending() > 0 and steps < args.recover_steps:
            done_before = set(fleet.results)
            fleet.step()
            steps += 1
            if fleet.deaths > deaths_before and death_wall is None:
                death_wall = time.perf_counter()
            if death_wall is not None:
                post_death_uids |= set(fleet.results) - done_before
        faults.reset()
        recovered = fleet.pending() == 0
        recover_wall_s = (
            time.perf_counter() - death_wall if death_wall else 0.0
        )
        done = [
            uid for uid in accepted
            if frontend.poll(msg.ServePoll(uid=uid)).state == "done"
        ]
        lost = sorted(set(accepted) - set(done))
        post_lat = [fleet.results[u].latency_s for u in post_death_uids]
        p95_post = _quantile(post_lat, 0.95)

        # -- phase 2: backpressure. With a measured service rate and a
        # backlog, a tiny-deadline submit must fast-reject as a shed; a
        # queued request must be cancellable; the backlog must drain.
        backlog = []
        for i in range(3 * args.slots):
            uid, prompt, sampling = trace[i % len(trace)]
            backlog.append(f"bk{i:03d}")
            submit(backlog[-1], prompt, sampling, args.deadline_s)
        t0 = time.perf_counter()
        shed_ticket = submit("shedprobe", trace[0][1], trace[0][2], 1e-6)
        shed_reject_s = time.perf_counter() - t0
        cancel_status = frontend.cancel(msg.ServeCancel(uid=backlog[-1]))
        for _ in range(args.recover_steps):
            if fleet.pending() == 0:
                break
            fleet.step()
        drained = fleet.pending() == 0

        # -- phase 3: live hot-swap between decode steps. Two requests
        # hold live slots; the swap must neither retrace the three decode
        # programs nor free a slot.
        for i, uid in enumerate(("swap-a", "swap-b")):
            submit(uid, trace[i][1], trace[i][2], args.deadline_s)
        fleet.step()
        live_before = sum(
            len(r.engine._live_slots()) for r in fleet._replicas.values()
        )
        trace_keys = ("serve_prefill", "serve_insert", "serve_decode")
        counts_before = {k: train_lib.TRACE_COUNTS[k] for k in trace_keys}
        reports = [
            r.engine.swap_weights(ckpt_dir)
            for r in fleet._replicas.values()
        ]
        retraces = sum(
            train_lib.TRACE_COUNTS[k] - counts_before[k] for k in trace_keys
        )
        live_after = sum(
            len(r.engine._live_slots()) for r in fleet._replicas.values()
        )
        swap = {
            "ok": all(r["ok"] and not r["rolled_back"] for r in reports),
            "version": max((r["version"] for r in reports), default=0),
            "step": max((r["step"] for r in reports), default=-1),
            "seconds": round(sum(r["seconds"] for r in reports), 4),
            "retraces": int(retraces),
            "no_drain": live_before > 0 and live_after == live_before,
            "live_slots": live_before,
            "replicas_swapped": len(reports),
        }

        # -- phase 4: corrupted swap. The serve.swap seam flips one
        # mantissa bit after landing; the digest check must catch it,
        # roll back to the phase-3 weights, and keep serving.
        faults.configure("serve.swap:error@1", seed=args.seed)
        survivor = next(iter(fleet._replicas.values())).engine
        corrupt_report = survivor.swap_weights(ckpt_dir)
        faults.reset()
        submit("post-rollback", trace[0][1], trace[0][2], args.deadline_s)
        for _ in range(args.recover_steps):
            if fleet.pending() == 0:
                break
            fleet.step()
        served_after = (
            frontend.poll(msg.ServePoll(uid="post-rollback")).state == "done"
        )
        swap_corrupt = {
            "ok": bool(corrupt_report["ok"]),
            "rolled_back": bool(corrupt_report["rolled_back"]),
            "version": int(corrupt_report["version"]),
            "served_after": served_after,
        }

        # Book the drill into a master-side ledger exactly as the
        # servicer would, so the artifact carries the gauge view too.
        sm = SpeedMonitor()
        for i, rep in enumerate(reports + [corrupt_report]):
            sm.record_swap(
                i, version=rep["version"], ok=rep["ok"],
                rolled_back=rep["rolled_back"], seconds=rep["seconds"],
            )
        for i, replica in enumerate(fleet._replicas.values()):
            sm.record_serve(i, **replica.engine.stats())

        drill = {
            "submitted": len(tickets),
            "accepted": len(accepted),
            "deaths": fleet.deaths,
            "resubmitted": fleet.resubmitted,
            "lost": len(lost),
            "lost_uids": lost,
            "recovered": recovered,
            "recover_steps": steps,
            "recover_wall_s": round(recover_wall_s, 4),
            "post_death_completions": len(post_lat),
            "p95_post_death_s": round(p95_post, 5),
            "slo_p95_s": args.slo_p95_s,
            "shed": {
                "rejected": (
                    not shed_ticket.accepted
                    and shed_ticket.reason == "shed"
                ),
                "reason": shed_ticket.reason,
                "predicted_wait_s": round(
                    shed_ticket.predicted_wait_s, 5
                ),
                "reject_s": round(shed_reject_s, 5),
                "budget_s": args.shed_budget_s,
                "cancelled": cancel_status.state == "cancelled",
                "drained": drained,
            },
            "swap": swap,
            "swap_corrupt": swap_corrupt,
            "serve_ledger": sm.serve_ledger(),
        }
        ok, failed_checks = evaluate_fleet_gate(drill)
        result = {
            "metric": "requests lost to a mid-flight replica death",
            "value": len(lost),
            "unit": "requests",
            "device": device_fields(),
            "detail": {"ok": ok, "failed_checks": failed_checks, **drill},
        }
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
        print(json.dumps(result))
        return 0 if ok else 1
    finally:
        faults.reset()
        ckpt_engine._shm.close(unlink=True)
        saver.stop()
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(
        description="continuous- vs static-batching serving bench "
                    "(writes SERVE.json)"
    )
    ap.add_argument("--slots", type=int, default=4,
                    help="KV-cache slot pool size (the decode batch)")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--prompt-lens", default="5,9,14,27",
                    help="comma list the trace cycles prompt widths from")
    ap.add_argument("--new-lens", default="6,10,18,30",
                    help="comma list of per-request max_new_tokens")
    ap.add_argument("--buckets", default="16,32",
                    help="prefill bucket widths (one compiled program each)")
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--max-seq-len", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="",
                    help="artifact path (default SERVE.json, or "
                         "SERVE_FLEET.json under --fleet-drill)")
    drill = ap.add_argument_group("fleet drill (serving front door)")
    drill.add_argument("--fleet-drill", action="store_true",
                       help="run the survivability drill instead: RPC "
                            "front door + replica death failover + load "
                            "shed + live weight hot-swap w/ rollback "
                            "(writes SERVE_FLEET.json)")
    drill.add_argument("--replicas", type=int, default=2,
                       help="serving replicas behind the front door")
    drill.add_argument("--max-pending", type=int, default=64,
                       help="front-door bounded admission queue size")
    drill.add_argument("--deadline-s", type=float, default=30.0,
                       help="per-request deadline the shed test uses")
    drill.add_argument("--slo-p95-s", type=float, default=30.0,
                       help="post-death p95 latency must recover under "
                            "this SLO")
    drill.add_argument("--kill-tick", type=int, default=3,
                       help="fleet step on which the replica.death seam "
                            "kills the last replica")
    drill.add_argument("--recover-steps", type=int, default=512,
                       help="bounded recovery window (fleet steps)")
    drill.add_argument("--shed-budget-s", type=float, default=0.1,
                       help="a shed reject slower than this fails the "
                            "gate")
    tp = ap.add_argument_group("tp drill (tensor-parallel serving)")
    tp.add_argument("--tp-drill", action="store_true",
                    help="run the tensor-parallel serving drill instead: "
                         "TP scaling legs w/ greedy parity + per-device "
                         "cost, disaggregated prefill vs colocated, "
                         "speculative decoding, mid-serve TP fold "
                         "(writes SERVE_TP.json)")
    tp.add_argument("--tp-widths", default="1,2,4",
                    help="comma list of tensor-parallel widths to sweep")
    tp.add_argument("--spec-tokens", type=int, default=4,
                    help="draft tokens proposed per speculative step")
    tp.add_argument("--draft-layers", type=int, default=1,
                    help="target blocks sliced into the draft model")
    tp.add_argument("--draft-damp", type=float, default=0.05,
                    help="damping on post-draft block output projections "
                         "(pushes them toward pass-through)")
    tp.add_argument("--accept-floor", type=float, default=0.6,
                    help="speculative acceptance rate the gate requires")
    args = ap.parse_args()

    if args.tp_drill:
        virtual_cpu_devices(8)
        return run_tp_drill(args, args.out or "SERVE_TP.json")
    if args.fleet_drill:
        return run_fleet_drill(args, args.out or "SERVE_FLEET.json")
    args.out = args.out or "SERVE.json"
    from dlrover_tpu.master.speed_monitor import SpeedMonitor

    config, params = build_model(args)
    trace = make_trace(args)
    sm = SpeedMonitor()

    # Leg 1 (continuous) pays the cold AOT compile; leg 2 (static) hits
    # the process-wide program memo — the warm start an elastic serving
    # replica restart would see.  Both legs are booked in the compile
    # ledger exactly like a trainer's compile events.
    continuous = run_leg(config, params, trace, args, static=False)
    static = run_leg(config, params, trace, args, static=True)
    for leg in (continuous, static):
        sm.record_compile(leg["aot_s"], cached=leg["aot_s"] == 0.0)
    sm.record_serve(0, qps=0.0, p50_s=continuous["p50_s"],
                    p95_s=continuous["p95_s"],
                    occupancy=continuous["occupancy"],
                    slots=args.slots, requests=continuous["requests"],
                    tokens=continuous["tokens"])
    ledger = sm.compile_ledger()

    speedup = (
        continuous["tokens_per_s"] / static["tokens_per_s"]
        if static["tokens_per_s"] > 0 else 0.0
    )
    ok, failed_checks = evaluate_gate(
        continuous, static, len(trace), ledger
    )
    result = {
        "metric": "continuous-batching speedup over static batching",
        "value": round(speedup, 3),
        "unit": "x tokens/s",
        "device": device_fields(),
        "detail": {
            "ok": ok,
            "failed_checks": failed_checks,
            "continuous": continuous,
            "static": static,
            "speedup_tokens_per_s": round(speedup, 3),
            "p95_ratio": (
                round(static["p95_s"] / continuous["p95_s"], 3)
                if continuous["p95_s"] > 0 else 0.0
            ),
            "cold_aot_s": continuous["aot_s"],
            "warm_aot_s": static["aot_s"],
            "compile_ledger": ledger,
            "serve_ledger": sm.serve_ledger(),
            "slots": args.slots,
            "buckets": args.buckets,
            "requests": len(trace),
        },
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
