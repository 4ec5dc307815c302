"""MoE certification bench: elastic expert parallelism or no badge.

Certifies PR 19's expert-parallel MoE path (MOE.json) on the virtual CPU
mesh with four checks, each a measurement rather than an assertion about
code structure:

1. **throughput** — the MoE build (``E`` experts of width ``d_ff`` on a
   ``data x expert`` mesh, explicit all-to-all dispatch) must beat the
   dense iso-FLOP baseline: the dense model whose MLP carries the full
   expert parameter budget (``d_ff_dense = E * d_ff``) on the same device
   count.  Both models hold the same FF parameters; the MoE activates
   only ``top_k/E`` of them per token, and that sparsity must survive
   routing + dispatch overhead as measured tokens/s.
2. **wire** — the int8 dispatch wire (``quantized_all_to_all``: int8
   payload + fp32 block scales) must be strictly cheaper than the fp32
   wire at the bench's actual dispatch payload size
   (``cf * k * tokens_local * d_model`` elements), priced by the same
   :func:`a2a_wire_bytes` model ``auto/tune.py`` uses.
3. **resize** — two identical MoE trainers run ``--resize-steps`` lock-
   step steps; one then folds its world in half via
   ``apply_world_change`` (the live relayout path, expert plane booked
   via the virtual mesh's ``s % P`` fold).  Every expert-sharded param
   leaf must be BITWISE equal to the never-resized reference's.
4. **retrace** — the timed steps of both builds run under a
   ``train_step`` trace-count pin: zero steady-state retraces.

    python tools/moe_bench.py --out MOE.json

``evaluate_moe_gate`` is the ok-gate as a pure predicate, testable
without running the bench.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dlrover_tpu.utils.devices import (  # noqa: E402
    device_fields,
    virtual_cpu_devices,
)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--out", default="MOE.json")
    p.add_argument("--data", type=int, default=2,
                   help="data-axis extent of the MoE mesh (the dense "
                        "baseline runs pure-data on data*expert devices)")
    p.add_argument("--expert", type=int, default=4,
                   help="expert-axis extent of the MoE mesh")
    p.add_argument("--experts", type=int, default=8,
                   help="number of experts E (must divide by --expert)")
    p.add_argument("--top-k", type=int, default=2)
    p.add_argument("--capacity-factor", type=float, default=1.25)
    p.add_argument("--d-ff", type=int, default=128,
                   help="per-expert FF width; the dense baseline gets "
                        "E * this")
    p.add_argument("--dispatch", default="a2a_int8",
                   choices=("einsum", "a2a", "a2a_int8"),
                   help="MoE dispatch transport under the expert mesh")
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--seq-len", type=int, default=32)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--warmup-steps", type=int, default=2)
    p.add_argument("--timed-steps", type=int, default=6,
                   help="steps per build for the tokens/s leg (also the "
                        "zero-retrace pin window)")
    p.add_argument("--resize-steps", type=int, default=3,
                   help="lockstep steps before the mid-run fold in the "
                        "expert-state parity leg")
    return p


def evaluate_moe_gate(result):
    """The MOE.json ok gate as a pure predicate: MoE tokens/s strictly
    above the dense iso-FLOP baseline, int8 dispatch wire strictly
    cheaper than fp32 at the measured payload size, every expert-sharded
    leaf bitwise-identical to the never-resized reference after a
    mid-run fold, and zero steady-state retraces on either build."""
    checks = {
        "moe_tokens_per_s_beats_dense": (
            result["moe"]["tokens_per_s"] > result["dense"]["tokens_per_s"]
        ),
        "int8_dispatch_wire_cheaper": (
            result["wire"]["int8_bytes"] < result["wire"]["fp32_bytes"]
        ),
        "resize_expert_state_bitwise": (
            result["resize"]["expert_leaves"] >= 1
            and result["resize"]["bitwise_equal"]
        ),
        "steady_state_no_retrace": (
            result["moe"]["retraces"] == 0
            and result["dense"]["retraces"] == 0
        ),
    }
    failed = sorted(name for name, held in checks.items() if not held)
    return not failed, failed


def _config(args, moe: bool):
    from dlrover_tpu.models.gpt2 import gpt2_config

    kw = dict(
        num_layers=args.layers, d_model=args.d_model,
        num_heads=args.heads, vocab_size=args.vocab,
        max_seq_len=max(64, args.seq_len),
    )
    if moe:
        kw.update(
            num_experts=args.experts, top_k=args.top_k,
            capacity_factor=args.capacity_factor, d_ff=args.d_ff,
            moe_dispatch=args.dispatch,
        )
    else:
        # The iso-FLOP dense baseline: all E experts' FF width active for
        # every token (same parameter budget, E/top_k x the matmul work).
        kw.update(d_ff=args.experts * args.d_ff)
    return gpt2_config("124m", **kw)


def _build(args, moe: bool):
    from dlrover_tpu.models.transformer import TransformerLM
    from dlrover_tpu.parallel import rules as lr
    from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
    from dlrover_tpu.trainer import train_lib

    parallel = (
        ParallelConfig(data=args.data, expert=args.expert) if moe
        else ParallelConfig(data=args.data * args.expert)
    )
    mesh = build_mesh(parallel)
    model = TransformerLM(_config(args, moe))
    opt = train_lib.make_optimizer("sgd", learning_rate=1e-2)
    return train_lib.build_sharded_train(
        model, opt, mesh, lr.DEFAULT_RULES,
        global_batch_size=args.batch_size, seq_len=args.seq_len,
    )


def _batch(args, train, seed=0):
    import numpy as np

    from dlrover_tpu.trainer import train_lib

    rng = np.random.default_rng(seed)
    tokens = rng.integers(
        0, args.vocab, size=(args.batch_size, args.seq_len + 1),
        dtype=np.int32,
    )
    return train_lib.shard_batch(
        {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}, train
    )


def _measure_build(args, moe: bool):
    """Warmup + timed steps for one build, under a trace-count pin."""
    import jax

    from dlrover_tpu.trainer import train_lib

    train = _build(args, moe)
    state = train.init(jax.random.PRNGKey(0))
    batch = _batch(args, train)
    for _ in range(args.warmup_steps):
        state, metrics = train.step(state, batch)
    jax.block_until_ready(metrics["loss"])

    before = train_lib.trace_count("train_step")
    t0 = time.monotonic()
    for _ in range(args.timed_steps):
        state, metrics = train.step(state, batch)
    jax.block_until_ready(metrics["loss"])
    elapsed = time.monotonic() - t0
    retraces = train_lib.trace_count("train_step") - before

    tokens = args.batch_size * args.seq_len * args.timed_steps
    return {
        "moe": moe,
        "timed_steps": args.timed_steps,
        "step_s": elapsed / args.timed_steps,
        "tokens_per_s": tokens / elapsed if elapsed > 0 else 0.0,
        "loss": float(metrics["loss"]),
        "retraces": retraces,
    }


def _expert_leaves(state):
    """The expert-sharded param leaves (path contains the MoE module) as
    host arrays, keyed by path string."""
    import jax
    import numpy as np

    out = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(state.params)
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        if "moe" in name:
            out[name] = np.asarray(jax.device_get(leaf))
    return out


def _resize_trainer(args):
    from dlrover_tpu.runtime.mesh import ParallelConfig
    from dlrover_tpu.trainer.elastic_trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    world = args.data * args.expert
    return ElasticTrainer(
        _config(args, moe=True),
        TrainerConfig(
            global_batch_size=args.batch_size, seq_len=args.seq_len,
            optimizer="sgd", learning_rate=1e-2,
            world=world, grad_accum_ref_world=world,
            report_every=1000, numeric_checks=False,
        ),
        parallel=ParallelConfig(data=args.data, expert=args.expert),
        client=None,
    )


def _lm_batches(args, n, seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        t = rng.integers(
            0, args.vocab, size=(args.batch_size, args.seq_len + 1),
            dtype=np.int32,
        )
        out.append({"inputs": t[:, :-1], "targets": t[:, 1:]})
    return out


def run_resize_parity(args):
    """Lockstep MoE trainers; one folds its world in half mid-run via the
    live-relayout path.  Expert-sharded leaves must stay bitwise equal to
    the never-resized reference — the ``s % P`` expert fold moves bytes,
    never values."""
    steps = args.resize_steps
    batches = _lm_batches(args, steps)

    resized = _resize_trainer(args)
    reference = _resize_trainer(args)
    try:
        resized.fit(iter(batches), max_steps=steps)
        reference.fit(iter(batches), max_steps=steps)
        detail = resized.apply_world_change(
            max(1, (args.data * args.expert) // 2), reason="moe_bench"
        )
        got = _expert_leaves(resized.state)
        want = _expert_leaves(reference.state)
        bitwise = bool(got) and set(got) == set(want) and all(
            got[k].dtype == want[k].dtype
            and got[k].tobytes() == want[k].tobytes()
            for k in want
        )
        return {
            "steps": steps,
            "relayout_ok": bool(detail.get("ok")),
            "fallback": bool(detail.get("fallback")),
            "old_world": detail.get("old_world"),
            "new_world": detail.get("new_world"),
            "expert_world": detail.get("expert_world"),
            "expert_fold": detail.get("expert_fold"),
            "expert_leaves": len(want),
            "bitwise_equal": bitwise,
        }
    finally:
        resized.close()
        reference.close()


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.experts % args.expert:
        raise SystemExit(
            f"--experts {args.experts} must divide by --expert {args.expert}"
        )
    virtual_cpu_devices(args.data * args.expert)
    os.environ.setdefault("DLROVER_TPU_JOB", "moe_bench")

    from dlrover_tpu.parallel.quantized_collectives import a2a_wire_bytes

    dense = _measure_build(args, moe=False)
    moe = _measure_build(args, moe=True)

    # The per-device dispatch payload the expert all-to-all actually
    # moves: the capacity-padded expert tensor of the local batch chunk.
    tokens_local = args.batch_size * args.seq_len // (
        args.data * args.expert
    )
    elems = int(
        args.capacity_factor * args.top_k * tokens_local * args.d_model
    )
    wire = {
        "payload_elems": elems,
        "fp32_bytes": a2a_wire_bytes(elems, "none"),
        "int8_bytes": a2a_wire_bytes(elems, "int8"),
    }

    result = {
        "config": {
            "data": args.data, "expert": args.expert,
            "experts": args.experts, "top_k": args.top_k,
            "capacity_factor": args.capacity_factor,
            "d_ff_expert": args.d_ff,
            "d_ff_dense": args.experts * args.d_ff,
            "dispatch": args.dispatch,
            "layers": args.layers, "d_model": args.d_model,
            "seq_len": args.seq_len, "batch_size": args.batch_size,
        },
        "dense": dense,
        "moe": moe,
        "wire": wire,
        "resize": run_resize_parity(args),
    }
    ok, failed = evaluate_moe_gate(result)
    result["ok"] = ok
    result["device"] = device_fields()
    result["failed_checks"] = failed
    result["headline"] = {
        "tokens_per_s_moe": round(moe["tokens_per_s"], 2),
        "tokens_per_s_dense": round(dense["tokens_per_s"], 2),
        "speedup": round(
            moe["tokens_per_s"] / dense["tokens_per_s"], 3
        ) if dense["tokens_per_s"] > 0 else 0.0,
        "wire_bytes_ratio_int8": round(
            wire["int8_bytes"] / wire["fp32_bytes"], 4
        ),
        "resize_bitwise": result["resize"]["bitwise_equal"],
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
