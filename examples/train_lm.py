"""End-to-end elastic LM training example (nanoGPT-scale).

The TPU-native counterpart of the reference's flagship example
(ref ``examples/pytorch/nanogpt/train.py`` + ``dlrover-run``): launch with

    python -m dlrover_tpu.run --standalone -- python examples/train_lm.py \
        --steps 50 --checkpoint-dir /tmp/ckpt

Demonstrates the full loop through the reusable :class:`ElasticTrainer`
façade: agent rendezvous env, mesh + sharded train step (optionally
``--auto-tune``d), dynamic data sharding from the master, step reporting
(speed/goodput) + device telemetry, flash checkpointing every N steps, and
crash-resume (restart picks up from the latest checkpoint and the shard
stream continues where it left off).
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch-size", type=int, default=8,
                   help="GLOBAL batch size (constant across elasticity)")
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--size", default="",
                   help="a GPT-2 size of models/gpt2.py (124m, 355m, 774m, "
                        "1.5b) at its published widths; --layers/--d-model/"
                        "--heads/--vocab then override single fields. "
                        "Default: a toy model (2 layers, d_model 128)")
    p.add_argument("--vocab", type=int, default=None)
    p.add_argument("--layers", type=int, default=None)
    p.add_argument("--d-model", type=int, default=None)
    p.add_argument("--heads", type=int, default=None)
    p.add_argument("--param-dtype", default="float32",
                   choices=("float32", "bfloat16"),
                   help="dtype the parameters are stored in")
    p.add_argument("--report-every", type=int, default=5,
                   help="log the loss and report to the master every N "
                        "steps")
    p.add_argument("--checkpoint-dir", default="")
    p.add_argument("--ckpt-every", type=int, default=10)
    p.add_argument("--dataset-size", type=int, default=100000)
    p.add_argument("--fail-at-step", type=int, default=0,
                   help="test hook: crash at this step on first run")
    p.add_argument("--step-sleep", type=float, default=0.0,
                   help="test hook: slow steps down (chaos windows)")
    p.add_argument("--remat", default="none",
                   help="remat policy (ops/remat_policy.py): none, full, "
                        "dots, dots_no_batch, attn_out, branch_out, "
                        "flash_only, flash_res")
    p.add_argument("--auto-tune", action="store_true",
                   help="search mesh/remat strategy before training "
                        "(auto_accelerate equivalent)")
    p.add_argument("--optimizer", default="adamw",
                   help="adamw | adafactor | sgd | lion | q8_adam | agd")
    p.add_argument("--metrics-lag", type=int, default=0,
                   help="defer metrics materialization by N steps (one "
                        "batched device fetch per N steps; 0 = sync)")
    p.add_argument("--prefetch", type=int, default=0,
                   help="device-resident batches to keep ahead of compute "
                        "(H2D of batch N+1 overlaps step N; 0 = off)")
    p.add_argument("--warmup-compile", action="store_true",
                   help="AOT-compile the step at startup and report the "
                        "wall time to the master's goodput ledger")
    p.add_argument("--grad-accum", type=int, default=1,
                   help="microbatches per step: split the global batch "
                        "into N sequential microbatches and accumulate "
                        "gradients (same tokens/step, 1/N the activation "
                        "HBM; rescaled automatically on elastic resizes "
                        "so the optimizer trajectory is preserved)")
    p.add_argument("--accum-dtype", default="float32",
                   help="gradient accumulator dtype: float32 (default) | "
                        "bfloat16 (halves accumulator HBM, adds rounding "
                        "noise across microbatches)")
    p.add_argument("--reduce-quant", default="none",
                   help="wire format of the once-per-step deferred DP "
                        "gradient reduce: none (full precision) | int8 "
                        "(block-quantized EQuARX-style all-reduce; with "
                        "--zero1, a quantized reduce-scatter)")
    p.add_argument("--zero1", action="store_true",
                   help="ZeRO-1 cross-replica sharded weight update: "
                        "optimizer state + parameter update sharded over "
                        "the data axis (1/dp the opt-state HBM), DP "
                        "reduce lowered as reduce-scatter + all-gather")
    p.add_argument("--overlap", action="store_true",
                   help="overlap engine (requires --zero1): reduce-scatter "
                        "each microbatch's gradient inside the grad-accum "
                        "scan and pipeline the param all-gather in bucket "
                        "waves, so the zero1 wire hides under compute "
                        "structurally (parallel/overlap.py)")
    p.add_argument("--overlap-bucket-mb", type=float, default=4.0,
                   help="collective bucket size (MB of wire bytes) for the "
                        "overlap engine's wave schedule")
    p.add_argument("--allgather-quant", default="none",
                   help="wire format of the zero1 param re-replication "
                        "all-gather: none (full precision) | int8 "
                        "(block-quantized travelling shards)")
    p.add_argument("--attention-impl", default="xla",
                   choices=("xla", "flash", "ring"),
                   help="attention math: xla (einsum softmax), flash "
                        "(blocked Pallas fwd+bwd kernel), ring "
                        "(sequence-parallel blockwise)")
    p.add_argument("--flash-block-q", type=int, default=0,
                   help="flash attention query block size (0 = model "
                        "default)")
    p.add_argument("--flash-block-kv", type=int, default=0,
                   help="flash attention key/value block size (0 = model "
                        "default)")
    p.add_argument("--moe-experts", type=int, default=0,
                   help="mixture-of-experts: replace each block's MLP with "
                        "N routed experts (0 = dense). Expert params shard "
                        "over the mesh's 'expert' axis when one is present")
    p.add_argument("--moe-top-k", type=int, default=2,
                   help="experts each token is routed to")
    p.add_argument("--moe-capacity-factor", type=float, default=1.25,
                   help="per-expert slot budget as a multiple of the "
                        "balanced load (capacity = cf*S*k/E per batch row; "
                        "overflow tokens are dropped)")
    p.add_argument("--moe-dispatch", default="einsum",
                   choices=("einsum", "a2a", "a2a_int8", "grouped"),
                   help="expert dispatch transport: einsum (GSPMD one-hot "
                        "matmuls), a2a (explicit all-to-all exchange over "
                        "the expert axis), a2a_int8 (same wire, "
                        "block-quantized int8 payload), grouped (per-device "
                        "Pallas grouped GEMM; expert axis must be 1)")
    p.add_argument("--sdc-check-every", type=int, default=0,
                   help="silent-data-corruption sentry: every N steps, "
                        "digest the post-update train state on device and "
                        "ship it to the master's cross-replica vote ledger "
                        "(0 = off)")
    p.add_argument("--lockstep-data", action="store_true",
                   help="skip master data sharding so every node consumes "
                        "the identical sequential sample stream — required "
                        "for the SDC drill on CPU worlds, where each node "
                        "is its own data replica and digests only agree if "
                        "the replicas train on the same batches")
    p.add_argument("--ref-world", type=int, default=0,
                   help="logical member count the job was sized for "
                        "(virtual-mesh reference world). 0 = infer from "
                        "jax.device_count(); set explicitly in multi-agent "
                        "drills where each trainer is a 1-device world")
    p.add_argument("--live-relayout", action="store_true",
                   help="poll the master's node ledger and fold/fan the "
                        "virtual mesh in place when the live member count "
                        "changes (apply_world_change) instead of waiting "
                        "for a restart + checkpoint restore")
    p.add_argument("--timeline", default="",
                   help="write this process's telemetry (step/compile/"
                        "checkpoint spans) as a Chrome-trace JSON at exit "
                        "— open at https://ui.perfetto.dev")
    p.add_argument("--profile-every", type=int, default=0,
                   help="capture a jax.profiler trace window every N steps "
                        "and emit measured per-phase device rows next to "
                        "the modeled ones (0 = off; the captured step pays "
                        "one device sync + the trace parse)")
    return p.parse_args()


def main():
    args = parse_args()
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.common.log import default_logger as logger
    from dlrover_tpu.data.loader import (
        ElasticDataLoader,
        synthetic_lm_sample_fn,
    )
    from dlrover_tpu.data.sharding_client import ShardingClient
    from dlrover_tpu.models.gpt2 import gpt2_config
    from dlrover_tpu.runtime import env as renv
    from dlrover_tpu.trainer.elastic_trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    renv.initialize()
    client = renv.master_client()

    model_kw = dict(
        max_seq_len=args.seq_len,
        remat=args.remat,
        attention_impl=args.attention_impl,
        param_dtype=getattr(jnp, args.param_dtype),
    )
    # Without --size: the toy model.  With it: that size's own widths.
    toy = {} if args.size else dict(
        num_layers=2, d_model=128, num_heads=4, vocab_size=1024
    )
    for field, given in (
        ("num_layers", args.layers), ("d_model", args.d_model),
        ("num_heads", args.heads), ("vocab_size", args.vocab),
    ):
        if given is not None:
            model_kw[field] = given
        elif field in toy:
            model_kw[field] = toy[field]
    if args.flash_block_q:
        model_kw["flash_block_q"] = args.flash_block_q
    if args.flash_block_kv:
        model_kw["flash_block_kv"] = args.flash_block_kv
    if args.moe_experts:
        model_kw.update(
            num_experts=args.moe_experts,
            top_k=args.moe_top_k,
            capacity_factor=args.moe_capacity_factor,
            moe_dispatch=args.moe_dispatch,
        )
    cfg = gpt2_config(args.size or "124m", **model_kw)
    trainer = ElasticTrainer(
        cfg,
        TrainerConfig(
            global_batch_size=args.batch_size,
            seq_len=args.seq_len,
            optimizer=args.optimizer,
            learning_rate=1e-3,
            checkpoint_dir=args.checkpoint_dir,
            ckpt_every=args.ckpt_every,
            report_every=args.report_every,
            auto_tune=args.auto_tune,
            metrics_lag=args.metrics_lag,
            prefetch_to_device=args.prefetch,
            warmup_compile=args.warmup_compile,
            grad_accum=args.grad_accum,
            accum_dtype=args.accum_dtype,
            reduce_quant=args.reduce_quant,
            zero1=args.zero1,
            overlap=args.overlap,
            overlap_bucket_mb=args.overlap_bucket_mb,
            allgather_quant=args.allgather_quant,
            sdc_check_every=args.sdc_check_every,
            profile_every=args.profile_every,
            world=args.ref_world,
            grad_accum_ref_world=args.ref_world,
        ),
        client=client,
    )

    # Each host's loader produces its local slice of the global batch;
    # shard_batch assembles the global array from the per-process pieces.
    n_proc = max(1, jax.process_count())
    if args.batch_size % n_proc:
        raise ValueError(
            f"--batch-size {args.batch_size} must be divisible by the "
            f"{n_proc}-host world"
        )
    local_batch = args.batch_size // n_proc
    if client is not None and not args.lockstep_data:
        loader_source = ShardingClient(
            client,
            "train",
            dataset_size=args.dataset_size,
            shard_size=local_batch * 8,
            num_epochs=8,
            create=True,
        )
    else:
        loader_source = None
    loader = ElasticDataLoader(
        synthetic_lm_sample_fn(cfg.vocab_size, args.seq_len),
        batch_size=local_batch,
        source=loader_source,
    )

    # Live-relayout: watch the master's node ledger and fold/fan the
    # virtual mesh in place when the live member count changes.  Dead or
    # preempting members drop out of the "running" set; the survivor
    # re-lays-out state onto itself instead of restarting from storage.
    live_world = [trainer.vmesh.physical_world]

    def _poll_world(step):
        try:
            status = client.get_job_status()
        except Exception as e:  # noqa: BLE001 - master may be mid-resize
            logger.warning("live-relayout: job status poll failed: %s", e)
            return
        alive = sum(1 for s in status.nodes.values() if s == "running")
        if alive >= 1 and alive != live_world[0]:
            logger.info(
                "live-relayout: world %d -> %d at step %d",
                live_world[0], alive, step,
            )
            detail = trainer.apply_world_change(alive, reason="scale")
            if detail.get("ok"):
                live_world[0] = alive

    def on_step(step, metrics):
        if args.fail_at_step and step == args.fail_at_step:
            if renv.restart_count() == 0:
                logger.error("test hook: crashing at step %d", step)
                os._exit(17)
        if args.live_relayout and client is not None and step % 2 == 0:
            _poll_world(step)
        if args.step_sleep:
            time.sleep(args.step_sleep)

    trainer.fit(loader, max_steps=args.steps, on_step=on_step)
    trainer.close()
    if args.timeline:
        _write_timeline(args.timeline, client)
    return 0


def _write_timeline(path: str, client):
    """Dump the run's telemetry as a Chrome trace.

    With a master attached, its merged timeline covers every node (and
    already holds what this trainer shipped on report cadence); standalone
    runs fall back to this process's own ring.
    """
    import json

    from dlrover_tpu.common import telemetry
    from dlrover_tpu.common.log import default_logger as logger
    from dlrover_tpu.runtime import env as renv

    events = {}
    if client is not None:
        try:
            events = {
                int(n): list(evs)
                for n, evs in client.get_timeline().items()
            }
        except Exception as e:  # noqa: BLE001 - best-effort at exit
            logger.warning("timeline fetch from master failed: %s", e)
    local = telemetry.recorder().drain()
    if local or not events:
        events.setdefault(renv.node_id(), []).extend(local)
    trace = telemetry.events_to_chrome_trace(events)
    with open(path, "w") as f:
        json.dump(trace, f)
    logger.info(
        "timeline: %d events -> %s",
        sum(len(evs) for evs in events.values()), path,
    )


if __name__ == "__main__":
    sys.exit(main())
