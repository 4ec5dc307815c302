"""auto_tune: single-call strategy search — the auto_accelerate equivalent.

Capability ref: ``atorch/atorch/auto/accelerate.py:406-653`` (single call
finds + applies the best strategy), engine
``atorch/atorch/auto/engine/acceleration_engine.py:13-94`` (ANALYSE / TUNE /
DRYRUN task loop) and the BO searcher
``atorch/atorch/auto/engine/sg_algo/bayes_opt_sg.py``.

TPU redesign of the search: the reference must dry-run candidate strategies
because a CUDA strategy's cost is opaque until executed; under XLA the
strategy space is small and analytic — a strategy here is just
(mesh factorization x remat policy), everything else being sharding rules
that compose freely.  So instead of a Bayesian optimizer over measured
dry-runs we:

1. ANALYSE  — enumerate the legal mesh factorizations (divisibility of
   heads/seq/experts/layers) and remat policies;
2. PRUNE    — reject candidates whose static per-device memory estimate
   (params + grads + optimizer + activations by remat policy) exceeds the
   HBM budget, and rank the survivors with an analytic step-time model
   (MXU FLOPs + HBM traffic + ICI collective bytes);
3. DRYRUN   — measure a real train step for the top-k survivors only;
4. FINISH   — return the winning ``ParallelConfig`` + rules + model config.

Runs identically on a virtual CPU mesh (tests, the driver's 8-device dry
run) and on real TPU slices.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.models.transformer import TransformerConfig
from dlrover_tpu.ops import remat_policy as remat_policy_lib
from dlrover_tpu.runtime.mesh import ParallelConfig

# The one table of per-chip peaks, keyed by ``device_kind`` as jax reports
# it; ``bench.py`` reads the same rows.  A device that is not here is an
# error, never a default: a utilisation against a guessed peak means
# nothing.  Columns: peak bf16 FLOP/s, HBM B/s, HBM bytes, ICI B/s per
# link and direction.
# Sources: Google Cloud documentation, "TPU v5e" / "TPU v5p" / "TPU v4"
# system architecture pages.  The "cpu" row is not a device's peak: it
# only makes the model's ranking meaningful (relative, not absolute) on
# the virtual CPU mesh of the tests.
_CHIP_SPECS = {
    "tpu v5 lite": (197e12, 819e9, 16e9, 4.5e10),   # v5e
    "tpu v5e": (197e12, 819e9, 16e9, 4.5e10),
    "tpu v5p": (459e12, 2765e9, 95e9, 9e10),
    "tpu v4": (275e12, 1228e9, 32e9, 9e10),
    "cpu": (1e12, 100e9, 8e9, 1e10),
}

# Host-to-device B/s for a step's batch: an ASSUMPTION, measured on no
# chip.  Its one reader, ``est_h2d_time``, sits under a max() with the
# compute time, which a batch's 12 bytes a token never reach.
_HOST_TO_DEVICE_BW = 15e9


def chip_specs(device=None) -> Tuple[float, float, float, float]:
    """(peak bf16 FLOP/s, HBM B/s, HBM bytes, ICI B/s) of ``device``
    (default: the first device); raises for a kind not in the table."""
    device = device or jax.devices()[0]
    kind = device.device_kind.lower()
    if kind not in _CHIP_SPECS:
        raise ValueError(
            f"no peak figures for device_kind {device.device_kind!r} "
            f"(platform {device.platform!r}); add a sourced row to "
            "auto/tune.py _CHIP_SPECS"
        )
    return _CHIP_SPECS[kind]


@dataclasses.dataclass
class Candidate:
    parallel: ParallelConfig
    remat: str
    global_batch_size: int = 0   # 0 = the caller's requested batch
    # Widened knobs (PROFILE.md-proven; VERDICT r3 #9).  0/False sentinels
    # mean "model default" so old call sites keep their behavior.
    flash_block: Tuple[int, int] = (0, 0)   # (block_q, block_kv)
    ce_chunks: int = 0                       # 0 = unchunked CE
    microbatches: int = 0                    # 0 = pipe default
    quantized_dcn: bool = False              # int8 DCN collectives
    interleave: int = 0                      # 0/1 = plain; v>=2 circular
    fused_ln: bool = False                   # Pallas one-pass LN backward
    est_step_time: float = math.inf
    est_hbm_gb: float = math.inf
    # Backward recompute time, the accounting component the remat choice
    # moves (ops/remat_policy.py).  Exposed so tests (and operators
    # reading the candidate table) can see WHY a policy won.
    est_recompute_time: float = 0.0
    # Input-pipeline H2D time for the local batch slice.  With the device
    # prefetcher (data.loader.DevicePrefetcher) this OVERLAPS compute, so
    # it enters the step estimate under the same max() as compute/HBM
    # rather than as an additive term — exposed so the candidate table
    # shows when a shape is input-bound (t_h2d is the max).
    est_h2d_time: float = 0.0
    # Collective (ICI/DCN) traffic time — the component the calibration
    # ledger corrects separately from compute (apply_calibration).
    est_comm_time: float = 0.0
    measured_step_time: Optional[float] = None
    measured_tokens_per_sec: Optional[float] = None
    rejected: str = ""

    def describe(self) -> str:
        p = self.parallel
        axes = {
            "dp": p.data, "fsdp": p.fsdp, "tp": p.tensor,
            "sp": p.seq, "ep": p.expert, "pp": p.pipe,
        }
        live = ",".join(f"{k}={v}" for k, v in axes.items() if v not in (1,))
        batch = f" gbs={self.global_batch_size}" if self.global_batch_size else ""
        extras = ""
        if self.flash_block != (0, 0):
            extras += f" fb={self.flash_block[0]}x{self.flash_block[1]}"
        if self.ce_chunks:
            extras += f" ce={self.ce_chunks}"
        if self.microbatches:
            extras += f" mb={self.microbatches}"
        if self.interleave > 1:
            extras += f" il={self.interleave}"
        if self.fused_ln:
            extras += " fln"
        if self.quantized_dcn:
            extras += " q8dcn"
        return f"[{live or 'dp=1'} remat={self.remat}{batch}{extras}]"


@dataclasses.dataclass
class TuneResult:
    parallel: ParallelConfig
    model_config: TransformerConfig
    remat: str
    candidates: List[Candidate]
    global_batch_size: int = 0  # only set by search_batch=True
    ce_chunks: int = 0          # winner's CE chunking (search_kernels)
    quantized_dcn: bool = False  # winner's DCN transport choice

    @property
    def best(self) -> Candidate:
        return self.candidates[0]


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def _flash_factor(block_kv: int, seq_len: int) -> float:
    """Relative attention-kernel cost by kv block (PROFILE.md r3 table)."""
    if block_kv >= min(seq_len, 1024):
        return 1.0   # one kv block: the fused single-pass backward engages
    return 1.06 if block_kv >= 512 else 1.13


def _knob_space(
    config: TransformerConfig,
    seq_len: int,
    pipe: int,
    *,
    search_kernels: bool,
    multihost: bool,
) -> List[Dict]:
    """The per-mesh knob combinations (flash blocks x CE chunking x
    microbatches x DCN quantization) — the dimensions PROFILE.md measured
    as mattering, which the reference searches with its strategy library
    + BO (ref ``auto/engine/sg_algo/bayes_opt_sg.py``)."""
    if search_kernels and config.attention_impl == "flash":
        pads = 1 << max(seq_len - 1, 1).bit_length() if seq_len & (
            seq_len - 1
        ) else seq_len
        sizes = [b for b in (256, 512, 1024) if b <= pads]
        blocks = [(0, 0)] + [
            (bq, bkv) for bq in sizes for bkv in sizes
            if not (bq == bkv == sizes[-1])  # largest pair ~= default
        ]
    else:
        blocks = [(0, 0)]
    ce_options = [0, 16] if search_kernels else [0]
    fln = [False, True] if search_kernels else [False]
    if pipe > 1:
        micro = [pipe, 2 * pipe, 4 * pipe]
        # Circular interleave (parallel/pipeline.py _circular): v=2 cuts
        # the bubble fraction to (S-1)/(2M+S-1) at 2x handoff + weight
        # streaming; only legal when the chunks divide the layers.  Every
        # micro option already satisfies the M >= S wrap constraint.
        il = [0] + ([2] if config.num_layers % (pipe * 2) == 0 else [])
    else:
        micro = [0]
        il = [0]
    # The DCN knob is a kernel-level transport choice like flash blocks /
    # CE chunking: gate it on the same opt-in so estimate-only runs with
    # search_kernels=False never have their mesh ranking skewed by an
    # optimization no caller would apply.
    dcn = [False, True] if (search_kernels and multihost) else [False]
    return [
        {"flash_block": fb, "ce_chunks": ce, "microbatches": mb,
         "quantized_dcn": q, "interleave": v, "fused_ln": f}
        for fb in blocks for ce in ce_options for mb in micro for q in dcn
        for v in il for f in fln
    ]


def enumerate_candidates(
    config: TransformerConfig,
    n_devices: int,
    remat_policies: Sequence[str] = ("attn_out", "branch_out", "full"),
    max_tensor: int = 8,
    include_pipeline: bool = True,
    search_kernels: bool = False,
    seq_len: int = 0,
    multihost: bool = False,
) -> List[Candidate]:
    """All legal (mesh factorization x remat [x kernel knobs]) combinations.

    Legality (divisibility) mirrors the reference's strategy feasibility
    checks (ref ``atorch/auto/opt_lib``'s per-optimization
    ``applicable``): tensor and seq must divide the head count (Ulysses
    shards heads over seq x tensor inside attention), expert must divide
    the expert count, pipe must divide the layer count.

    ``search_kernels=True`` widens the space with the measured-impact knobs
    (flash block sizes, CE chunking, microbatch counts, quantized DCN
    collectives); the sampled-search fallback in :func:`auto_tune` keeps
    the widened space tractable.
    """
    heads = config.num_heads
    seq_len = seq_len or config.max_seq_len
    if search_kernels and config.attention_impl == "flash":
        # The remat policy is a searchable kernel-class knob like flash
        # blocks / CE chunking: widen with the flash residual policies
        # where the flash names exist.
        remat_policies = tuple(remat_policies) + tuple(
            r for r in ("flash_only", "flash_res")
            if r not in remat_policies
        )
    # Validate up front, identically on every host: a policy without a
    # broadcast code raising only on the hosts whose measured best uses it
    # would leave the others hung in broadcast_one_to_all.
    uncoded = []
    for r in remat_policies:
        try:
            _encode_remat(r)
        except ValueError:
            uncoded.append(r)
    if uncoded:
        raise ValueError(
            f"remat policies {uncoded} have no broadcast encoding; "
            "multihost choice broadcast would diverge"
        )
    candidates: List[Candidate] = []
    seen = set()
    for tensor in _divisors(n_devices):
        if tensor > max_tensor or heads % tensor:
            continue
        for seq in _divisors(n_devices // tensor):
            if seq > 1 and (heads % (seq * tensor) or config.max_seq_len % seq):
                continue
            for expert in _divisors(n_devices // (tensor * seq)):
                if expert > 1 and (
                    not config.num_experts or config.num_experts % expert
                ):
                    continue
                pipes = [1]
                if include_pipeline and not config.num_experts:
                    pipes += [
                        p
                        for p in _divisors(n_devices // (tensor * seq * expert))
                        if p > 1 and config.num_layers % p == 0
                    ]
                for pipe in pipes:
                    rest = n_devices // (tensor * seq * expert * pipe)
                    for fsdp in _divisors(rest):
                        data = rest // fsdp
                        key = (data, fsdp, pipe, expert, seq, tensor)
                        if key in seen:
                            continue
                        seen.add(key)
                        parallel = ParallelConfig(
                            data=data, fsdp=fsdp, pipe=pipe,
                            expert=expert, seq=seq, tensor=tensor,
                        )
                        knobs = _knob_space(
                            config, seq_len, pipe,
                            search_kernels=search_kernels,
                            multihost=multihost,
                        )
                        for remat in remat_policies:
                            for kn in knobs:
                                candidates.append(
                                    Candidate(parallel, remat, **kn)
                                )
    return candidates


def _estimate(
    cand: Candidate,
    config: TransformerConfig,
    global_batch_size: int,
    seq_len: int,
    optimizer: str,
    n_devices: int,
) -> None:
    """Fill est_hbm_gb / est_step_time with the analytic model.

    This is the XLA-era replacement for per-candidate dry-runs: FLOP and
    byte volumes are exact functions of shapes; only efficiency factors are
    folded constants (measured on v5e, PROFILE.md).
    """
    peak_flops, hbm_bw, hbm_bytes, ici_bw = chip_specs()
    policy = remat_policy_lib.resolve(cand.remat)
    p = cand.parallel
    n = config.num_params()
    tokens = global_batch_size * seq_len
    shard = p.fsdp * p.tensor * p.pipe * max(p.expert, 1)

    # ---- memory (per device) ----
    param_b = n * 2 / shard                       # bf16 params
    grad_b = n * 2 / shard
    opt_mult = {"adamw": 8.0, "adafactor": 0.2, "q8_adam": 2.2,
                "q4_adam": 1.25, "sgd": 4.0, "lion": 4.0}.get(optimizer, 8.0)
    opt_b = n * opt_mult / shard
    act_mult = policy.hbm_act_per_token_layer
    tokens_local = tokens / max(p.data * p.fsdp, 1) / max(p.seq, 1)
    act_b = (
        tokens_local * config.num_layers * config.d_model * 2 * act_mult
        / max(p.tensor, 1) / max(p.pipe, 1)
    )
    # transient working set (attention + MLP blocks)
    work_b = tokens_local * config.resolved_d_ff * 2 * 4 / max(p.tensor, 1)
    # Logits working set: unchunked CE materializes [tokens, vocab] fp32
    # (measured 3.3 GiB at bench shapes); chunking divides it.
    logits_b = (
        tokens_local * config.vocab_size * 4
        / max(cand.ce_chunks, 1) / max(p.tensor, 1)
    )
    total_b = (
        param_b + grad_b + opt_b + act_b + work_b + logits_b
    ) * 1.15  # frag pad
    cand.est_hbm_gb = total_b / 2**30
    if total_b > hbm_bytes * 0.92:
        cand.rejected = (
            f"est {cand.est_hbm_gb:.1f} GiB > {hbm_bytes * 0.92 / 2**30:.1f}"
        )
        return

    # ---- time ----
    ftok = 6 * n + 12 * config.num_layers * config.d_model * seq_len
    flops_dev = ftok * tokens / n_devices
    mxu_eff = 0.55  # measured sustained efficiency at bench shapes
    t_compute = flops_dev / (peak_flops * mxu_eff)
    # Backward recompute is SERIAL extra compute (the replay runs before
    # the grads that need it), so it is an additive term.  Forward FLOPs
    # are 1/3 of ftok.
    t_recompute = (
        flops_dev * policy.recompute_fraction / 3 / (peak_flops * mxu_eff)
    )
    # Flash block sizes: measured relative attention-kernel cost on v5e at
    # seq 1024 (PROFILE.md round 3 table; one-kv-block is fastest because
    # the fused single-pass backward engages).  Attention is ~20% of the
    # step at bench shapes.  The (0,0) sentinel means "the model config's
    # own blocks" and is priced from those — so when the config default is
    # sub-optimal an explicit block choice can genuinely win the ranking.
    if config.attention_impl == "flash":
        bq, bkv = cand.flash_block
        if (bq, bkv) == (0, 0):
            bq, bkv = config.flash_block_q, config.flash_block_kv
        flash_scale = 0.8 + 0.2 * _flash_factor(bkv, seq_len)
        t_compute *= flash_scale
        t_recompute *= flash_scale
    # Chunked CE re-runs the logits matmul per chunk boundary: measured
    # +-0.5% at bench shapes — time-neutral, memory is its real effect.
    if cand.ce_chunks:
        t_compute *= 1.005
    # Quantized DCN collectives pay for their bandwidth saving with
    # quantize/dequantize sweeps over the gradient tree (~3 extra HBM
    # passes of the sharded params) — the knob must not be a free win in
    # the estimate when it cannot be exercised by _measure.
    if cand.quantized_dcn:
        t_compute += 3 * (n * 2 / shard) / hbm_bw
    # HBM: weights stream fwd+bwd+update, activations twice
    t_hbm = (param_b * 6 + opt_b + act_b * 2) / hbm_bw
    # Fused LN backward (ops/fused_norm.py): the XLA LN-bwd fusions
    # re-read the layer activations ~once more than the one-pass
    # kernel does (PROFILE.md r4's 6.4 ms/layer sink).
    if cand.fused_ln:
        t_hbm -= act_b * 0.3 / hbm_bw
    # ICI: fsdp all-gather + reduce-scatter of params, dp grad all-reduce,
    # sp/ep all-to-alls of activations
    coll_b = 0.0
    if p.fsdp > 1:
        coll_b += 3 * n * 2 / shard * (p.fsdp - 1) / p.fsdp
    if p.data > 1:
        coll_b += 2 * n * 2 / shard * (p.data - 1) / p.data
    if p.seq > 1:
        coll_b += 4 * tokens_local * config.d_model * 2
    if p.expert > 1:
        if config.num_experts:
            # MoE a2a dispatch: the capacity-padded expert tensor rides
            # the expert ring twice per direction per layer, int8 wire
            # when the model asks for it (a2a_wire_bytes prices the
            # payload + block-scale format exactly).
            from dlrover_tpu.parallel.quantized_collectives import (
                a2a_wire_bytes,
            )

            quant = (
                "int8" if config.moe_dispatch == "a2a_int8" else "none"
            )
            elems = int(
                config.capacity_factor * config.top_k
                * tokens_local * config.d_model
            )
            coll_b += (
                4 * config.num_layers
                * a2a_wire_bytes(elems, quant)
                * (p.expert - 1) / p.expert
            )
        else:
            coll_b += 4 * tokens_local * config.d_model * 2
    if p.tensor > 1:
        coll_b += 4 * tokens_local * config.d_model * 2 * config.num_layers
    # DCN-crossing gradient traffic: int8-quantized collectives
    # (parallel/quantized_collectives.py) cut the bytes ~3.5x (int8
    # payload + fp scales vs bf16) at a small dequant-compute cost.  The
    # knob is only enumerated for multihost jobs, where the data-axis
    # gradient all-reduce is the traffic that rides DCN.
    if cand.quantized_dcn and p.data > 1:
        dcn_b = 2 * n * 2 / shard * (p.data - 1) / p.data
        coll_b -= dcn_b * (1 - 1 / 3.5)
    t_ici = coll_b / ici_bw
    # pipeline bubble: (S-1)/(T+S-1) idle fraction; more microbatches
    # shrink the bubble but below a per-microbatch floor the smaller
    # per-step matmuls lose MXU efficiency (searchable knob).
    bubble = 1.0
    if p.pipe > 1:
        micro = max(
            cand.microbatches or config.num_microbatches or p.pipe, p.pipe
        )
        v = max(cand.interleave, 1)
        # Circular interleave divides the bubble by v; the price is v x
        # weight streaming (each chunk's params re-read every lap) and
        # the per-step relayout all-to-all, folded in as extra HBM/ICI
        # time on the param bytes.
        bubble = 1 + (p.pipe - 1) / (v * micro)
        if v > 1:
            # param_b is already per-device bytes: no second /shard.
            t_hbm += (v - 1) * (param_b * 3) / hbm_bw
            t_ici += param_b / ici_bw
        rows_per_micro = tokens / seq_len / max(p.data * p.fsdp, 1) / micro
        if rows_per_micro < 1:
            cand.rejected = f"microbatches {micro} > local batch rows"
            return
    # H2D input placement: int32 inputs + targets (4 B each) and fp32
    # per-row weights amortized per token — ~12 B/token crossing the host
    # DMA link for the local slice.  The device prefetcher overlaps this
    # copy with the previous step's compute, so it shares the roofline
    # max() with compute/HBM instead of adding to the critical path; a
    # shape is only penalized when it is genuinely input-bound.
    t_h2d = tokens_local * 12 / _HOST_TO_DEVICE_BW
    cand.est_recompute_time = t_recompute
    cand.est_h2d_time = t_h2d
    cand.est_comm_time = t_ici * bubble
    cand.est_step_time = (
        max(t_compute, t_hbm, t_h2d) + t_recompute + t_ici
    ) * bubble


def pick_grad_accum(
    config: TransformerConfig,
    parallel: ParallelConfig,
    global_batch_size: int,
    seq_len: int,
    *,
    remat: str = "none",
    optimizer: str = "adamw",
    accum_dtype: str = "float32",
    hbm_bytes: Optional[float] = None,
    zero1: bool = False,
    calibration=None,
) -> int:
    """Smallest grad_accum N whose per-microbatch footprint fits HBM.

    Same memory model as ``_estimate``, split by what N divides: the
    activation/working/logits bytes scale with the microbatch (1/N) while
    params/grads/optimizer don't — and accumulation ADDS one params-sized
    accumulator (4 B/param fp32, 2 B bf16, sharded like the grads), so
    N=1 with no accumulator must also be priced (it wins whenever the
    full batch already fits).  Candidate Ns are the feasible divisors of
    the per-dp-shard batch, walked smallest-first; when nothing fits the
    largest feasible N is returned (the best the knob can do — the caller
    sees the estimate and can shrink the model or batch).

    ``zero1=True`` prices the ZeRO-1 sharded update: the optimizer-state
    bytes divide by the extra ``data``-axis factor (each replica keeps
    its 1/dp slice; params and grads stay as before — grads are consumed
    by the reduce-scatter, params re-gather to full size), so a config
    that is opt-state-bound can fit with a smaller N or none at all.

    ``calibration`` (a CalibrationLedger, optional) supplies the measured
    "memory" ratio — allocator bytes over the shape model, learned from
    trainers' classified HBM events — so the feasibility walk prices the
    model's blind spots (temps, fragmentation) instead of leaning on the
    0.92 margin alone.
    """
    _, _, hbm_default, _ = chip_specs()
    hbm = hbm_bytes if hbm_bytes is not None else hbm_default
    policy = remat_policy_lib.resolve(remat)
    p = parallel
    n = config.num_params()
    shard = p.fsdp * p.tensor * p.pipe * max(p.expert, 1)
    dp = max(p.data * p.fsdp, 1)
    opt_mult = {"adamw": 8.0, "adafactor": 0.2, "q8_adam": 2.2,
                "q4_adam": 1.25, "sgd": 4.0, "lion": 4.0}.get(optimizer, 8.0)
    opt_shard = shard * (max(p.data, 1) if zero1 else 1)
    # params + grads replicated over data; optimizer state 1/dp under zero1
    fixed_b = n * (2 + 2) / shard + n * opt_mult / opt_shard
    accum_b = n * (2 if accum_dtype in ("bf16", "bfloat16") else 4) / shard
    tokens_local = (
        global_batch_size * seq_len / dp / max(p.seq, 1)
    )
    act_b = (
        tokens_local * config.num_layers * config.d_model * 2
        * policy.hbm_act_per_token_layer
        / max(p.tensor, 1) / max(p.pipe, 1)
    )
    work_b = tokens_local * config.resolved_d_ff * 2 * 4 / max(p.tensor, 1)
    logits_b = tokens_local * config.vocab_size * 4 / max(p.tensor, 1)
    per_shard_rows = max(1, global_batch_size // dp)
    feasible = [
        N for N in range(1, per_shard_rows + 1)
        if global_batch_size % (dp * N) == 0
    ] or [1]
    mem_ratio = 1.0
    if calibration is not None:
        try:
            mem_ratio = float(calibration.ratios().get("memory", 1.0))
        except Exception:
            mem_ratio = 1.0
        mem_ratio = max(mem_ratio, 1e-6)
    for N in feasible:
        extra = accum_b if N > 1 else 0.0
        total = (fixed_b + extra + (act_b + work_b + logits_b) / N) * 1.15
        if total * mem_ratio <= hbm * 0.92:
            return N
    return feasible[-1]


# Default hidden share of the overlapped collective legs: the estimator's
# prior until a profiler capture books a *measured* overlap fraction into
# the calibration ledger (utils/device_profile.py -> master/calibration.py),
# at which point est_comm_time prices with the measured number instead.
OVERLAP_HIDDEN_DEFAULT = 0.7
# Per-bucket collective launch overhead (descriptor setup + barrier);
# what stops bucket_mb -> 0 from looking free in the estimate.
BUCKET_LAUNCH_S = 5e-6


def est_comm_time(
    config: TransformerConfig,
    parallel: ParallelConfig,
    reduce_quant: str = "none",
    *,
    overlap: bool = False,
    bucket_mb: float = 0.0,
    grad_accum: int = 1,
    calibration=None,
    moe_tokens_local: int = 0,
    moe_dispatch_quant: str = "none",
) -> float:
    """Seconds of *exposed* wire for the data-parallel gradient reduce.

    Modeled as its actual lowering — a reduce-scatter leg plus an
    all-gather leg, each moving ``n·2/shard·(dp-1)/dp`` bytes over ICI
    (the bandwidth-optimal ring; their sum equals the classic
    ``2·(dp-1)/dp`` all-reduce volume, so the full-precision price is
    unchanged).  The split matters for ``"int8"``: the quantized wire
    format applies to the reduce-scatter leg only (int8 payload + fp32
    block scales, ~3.5x fewer bytes than bf16) while the gather leg —
    under ZeRO-1 the updated *params* riding back — stays full precision;
    the quantize/dequantize passes add ~2 HBM sweeps over the sharded
    gradient tree.  Zero when data=1: there is no reduce to price.

    ``moe_tokens_local > 0`` additionally prices the MoE dispatch
    transport when the mesh has an expert axis: each MoE layer moves the
    capacity-padded expert tensor ``cf·k·tokens_local·d_model`` over the
    expert ring twice per direction (dispatch + combine, forward and
    backward — the all-to-all's adjoint is the inverse exchange on the
    same wire), with only ``(ep-1)/ep`` of the payload leaving the chip.
    ``moe_dispatch_quant="int8"`` prices the quantized wire format of
    ``quantized_all_to_all`` (int8 payload + fp32 block scales) via
    :func:`a2a_wire_bytes`.  The MoE legs are never hidden by the
    overlap engine — dispatch sits on the layer's critical path.

    ``overlap=True`` prices the overlap engine's schedule
    (``parallel/overlap.py``): the reduce-scatter runs once per
    microbatch (``grad_accum``× the leg bytes on the wire) but a
    ``hidden`` fraction of each leg rides under backward/forward compute,
    so only the exposed remainder enters the step's critical path — plus
    a fill/drain of one bucket at each end of the pipeline (the first
    bucket has no compute ahead of it, the last none behind) and a
    per-bucket launch overhead that keeps tiny buckets from looking
    free.  ``hidden`` starts at :data:`OVERLAP_HIDDEN_DEFAULT` and is
    replaced by the calibration ledger's *measured* overlap fraction
    (``ledger.overlap()``) as soon as profiler captures book one — the
    exposed-vs-hidden split is learned, not assumed.
    """
    _, hbm_bw, _, ici_bw = chip_specs()
    p = parallel
    ep = max(p.expert, 1)
    moe_t = 0.0
    if moe_tokens_local > 0 and config.num_experts and ep > 1:
        from dlrover_tpu.parallel.quantized_collectives import a2a_wire_bytes

        elems = int(
            config.capacity_factor * config.top_k
            * moe_tokens_local * config.d_model
        )
        leg = a2a_wire_bytes(elems, moe_dispatch_quant) * (ep - 1) / ep
        # dispatch + combine, forward + backward = 4 legs per MoE layer,
        # once per microbatch.
        moe_t = 4 * config.num_layers * max(1, grad_accum) * leg / ici_bw
    if p.data <= 1:
        return moe_t
    n = config.num_params()
    shard = p.fsdp * p.tensor * p.pipe * max(p.expert, 1)
    leg_b = n * 2 / shard * (p.data - 1) / p.data
    if reduce_quant == "int8":
        rs_t = leg_b / 3.5 / ici_bw       # quantized reduce-scatter leg
        sweep_t = 2 * (n * 2 / shard) / hbm_bw  # quant/dequant sweeps
    else:
        rs_t = leg_b / ici_bw
        sweep_t = 0.0
    ag_t = leg_b / ici_bw                 # full-precision gather leg
    if not overlap:
        return rs_t + ag_t + sweep_t + moe_t
    hidden = OVERLAP_HIDDEN_DEFAULT
    if calibration is not None:
        measured = getattr(calibration, "overlap", lambda: 0.0)()
        if measured > 0.0:
            hidden = min(float(measured), 0.95)
    accum = max(1, grad_accum)
    # Per-microbatch reduce-scatter: accum x the wire, (1 - hidden) of it
    # exposed.  The quant/dequant sweeps run per microbatch too, and HBM
    # sweeps contend with compute's own HBM traffic — kept fully exposed.
    rs_exposed = rs_t * accum * (1.0 - hidden)
    ag_exposed = ag_t * (1.0 - hidden)
    total_b = (n * 2 / shard) * (accum + 1)   # RS waves + AG wave
    if bucket_mb > 0:
        n_buckets = max(1, math.ceil(total_b / (bucket_mb * 1e6)))
        fill_drain = 2 * (bucket_mb * 1e6) / ici_bw
    else:
        n_buckets = accum + 1                 # one wave per collective
        fill_drain = rs_t + ag_t              # nothing pipelines
    return (
        rs_exposed + ag_exposed + sweep_t * accum
        + fill_drain + n_buckets * BUCKET_LAUNCH_S
        + moe_t
    )


def _measure(
    cand: Candidate,
    config: TransformerConfig,
    global_batch_size: int,
    seq_len: int,
    optimizer: str,
    devices,
    steps: int = 2,
) -> Optional[float]:
    """One real compile + ``steps`` timed steps for a finalist candidate."""
    from dlrover_tpu.parallel import rules as lr
    from dlrover_tpu.runtime.mesh import build_mesh
    from dlrover_tpu.trainer import train_lib

    overrides: Dict = dict(
        remat=cand.remat,
        pipeline_stages=cand.parallel.pipe,
        num_microbatches=(
            (cand.microbatches or cand.parallel.pipe)
            if cand.parallel.pipe > 1 else 0
        ),
        pipeline_interleave=max(cand.interleave, 1),
        fused_ln=cand.fused_ln,
    )
    if cand.flash_block != (0, 0):
        overrides["flash_block_q"] = cand.flash_block[0]
        overrides["flash_block_kv"] = cand.flash_block[1]
    model_cfg = dataclasses.replace(config, **overrides)
    from dlrover_tpu.models.transformer import TransformerLM

    try:
        mesh = build_mesh(cand.parallel, devices=devices)
        model = TransformerLM(model_cfg)
        opt = train_lib.make_optimizer(optimizer, learning_rate=1e-4)
        train = train_lib.build_sharded_train(
            model, opt, mesh, lr.DEFAULT_RULES,
            global_batch_size=global_batch_size, seq_len=seq_len,
            ce_chunks=cand.ce_chunks,
        )
        state = train.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        tokens = rng.integers(
            0, config.vocab_size,
            size=(global_batch_size, seq_len + 1), dtype=np.int32,
        )
        batch = train_lib.shard_batch(
            {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}, train
        )
        state, metrics = train.step(state, batch)  # compile + warm
        float(metrics["loss"])
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = train.step(state, batch)
        float(metrics["loss"])
        return (time.perf_counter() - t0) / steps
    except Exception as e:  # noqa: BLE001 - infeasible candidate, skip
        logger.warning("dry-run %s failed: %s", cand.describe(), str(e)[:200])
        cand.rejected = f"dryrun: {str(e)[:120]}"
        return None


def _cand_key(c: Candidate):
    p = c.parallel
    return (
        p.data, p.fsdp, p.pipe, p.expert, p.seq, p.tensor, c.remat,
        c.global_batch_size, c.flash_block, c.ce_chunks, c.microbatches,
        c.quantized_dcn, c.interleave, c.fused_ln,
    )


def _knob_neighbors(
    leaders: List[Candidate],
    config: TransformerConfig,
    seq_len: int,
    *,
    search_kernels: bool,
    multihost: bool,
) -> List[Candidate]:
    """All single-knob variations of the leaders (mesh axes held fixed)."""
    out: List[Candidate] = []
    for cand in leaders:
        space = _knob_space(
            config, seq_len, cand.parallel.pipe,
            search_kernels=search_kernels, multihost=multihost,
        )
        knob_values: Dict[str, set] = {}
        for kn in space:
            for key, value in kn.items():
                knob_values.setdefault(key, set()).add(value)
        for key, values in knob_values.items():
            for value in values:
                if getattr(cand, key) != value:
                    out.append(dataclasses.replace(
                        cand, **{key: value},
                        est_step_time=math.inf, est_hbm_gb=math.inf,
                        rejected="",
                    ))
    return out


def _encode_remat(name: str) -> int:
    """A policy's broadcast code: its place in the registry's sorted
    names, the same on every host of one version."""
    return remat_policy_lib.available().index(
        remat_policy_lib.resolve(name).name  # ValueError on garbage
    )


def _decode_remat(code: int) -> str:
    names = remat_policy_lib.available()
    if not 0 <= code < len(names):
        raise ValueError(
            f"broadcast remat code {code} unknown to this host "
            "(version skew between hosts?)"
        )
    return names[code]


def _broadcast_choice(best: Candidate, ranked: List[Candidate]) -> Candidate:
    """Make host 0's winning candidate the whole world's choice."""
    from jax.experimental import multihost_utils

    p = best.parallel
    # Silently encoding an unknown policy as -1 would make non-source
    # hosts decode it to their own local best — divergent compiled
    # programs hang the first collective.  _encode_remat fails loudly.
    key = np.asarray(
        [p.data, p.fsdp, p.pipe, p.expert, p.seq, p.tensor,
         _encode_remat(best.remat), best.global_batch_size,
         best.flash_block[0], best.flash_block[1], best.ce_chunks,
         best.microbatches, int(best.quantized_dcn), best.interleave,
         int(best.fused_ln)],
        np.int64,
    )
    agreed = multihost_utils.broadcast_one_to_all(key)
    if np.array_equal(agreed, key):
        return best
    parallel = ParallelConfig(
        data=int(agreed[0]), fsdp=int(agreed[1]), pipe=int(agreed[2]),
        expert=int(agreed[3]), seq=int(agreed[4]), tensor=int(agreed[5]),
    )
    remat = _decode_remat(int(agreed[6]))
    knobs = dict(
        global_batch_size=int(agreed[7]),
        flash_block=(int(agreed[8]), int(agreed[9])),
        ce_chunks=int(agreed[10]),
        microbatches=int(agreed[11]),
        quantized_dcn=bool(agreed[12]),
        interleave=int(agreed[13]),
        fused_ln=bool(agreed[14]),
    )
    for cand in ranked:
        if (
            cand.parallel == parallel and cand.remat == remat
            and all(getattr(cand, k) == v for k, v in knobs.items())
        ):
            return cand
    return Candidate(parallel, remat, **knobs)


def apply_calibration(candidates, ledger):
    """Measurement-correct ``est_*`` in place before ranking.

    ``ledger`` is a :class:`dlrover_tpu.master.calibration.CalibrationLedger`
    (or None — no-op): its aggregate ``ratios()`` carry the EWMA of
    measured/modeled device seconds per phase kind from profiler capture
    windows.  The estimator's collective component (``est_comm_time``)
    scales by the collective ratio and everything else by the compute
    ratio, so a cost model that (say) under-prices DCN traffic 2x stops
    ranking communication-heavy layouts above what the hardware actually
    runs faster.  Rejected candidates keep their sentinel estimates.
    """
    if ledger is None:
        return
    ratios = ledger.ratios()
    if not ratios:
        return
    r_compute = float(ratios.get("compute", 1.0))
    r_collective = float(ratios.get("collective", 1.0))
    r_memory = float(ratios.get("memory", 0.0))
    hbm_gb = chip_specs()[2] / 2**30
    for cand in candidates:
        if cand.rejected or not math.isfinite(cand.est_step_time):
            continue
        comm = min(cand.est_comm_time, cand.est_step_time)
        base = cand.est_step_time - comm
        cand.est_step_time = base * r_compute + comm * r_collective
        cand.est_comm_time = comm * r_collective
        if r_memory > 0.0:
            # Measured allocator-bytes-over-shape-model ratio: the
            # pruner re-judges the survivor on corrected bytes — a
            # config the blind 0.92 margin admitted can still be
            # rejected here once measurement says the model under-
            # prices real usage.
            cand.est_hbm_gb *= r_memory
            if cand.est_hbm_gb > hbm_gb * 0.92:
                cand.rejected = (
                    f"calibrated est_hbm {cand.est_hbm_gb:.1f} GiB > "
                    f"0.92 * {hbm_gb:.0f} GiB "
                    f"(memory ratio {r_memory:.2f})"
                )
                cand.est_step_time = math.inf


def auto_tune(
    config: TransformerConfig,
    *,
    global_batch_size: int,
    seq_len: int = 0,
    n_devices: int = 0,
    optimizer: str = "adamw",
    max_measure: int = 3,
    measure: bool = True,
    devices=None,
    include_pipeline: bool = True,
    search_batch: bool = False,
    search_kernels: bool = False,
    max_enumerate: int = 32768,
    calibration=None,
) -> TuneResult:
    """Find the best (ParallelConfig, remat) for ``config`` on this mesh.

    The single-call surface of the reference's
    ``auto_accelerate(model, optim_func, ...)``; returns a ``TuneResult``
    whose ``parallel``/``model_config`` plug straight into
    ``build_mesh`` + ``build_sharded_train``.

    ``search_batch=True`` additionally searches global batch sizes (1x/2x/
    4x the requested batch — the reference HyperParam tuner's knob) and
    ranks by estimated *throughput* instead of step time; the winner's
    batch lands on ``TuneResult.global_batch_size``.  Opt-in because a
    changed batch changes training semantics.

    ``search_kernels=True`` widens the space with the measured-impact
    kernel knobs (flash block sizes, CE chunking, pipeline microbatch
    counts, quantized DCN collectives — PROFILE.md's proven levers).  A
    space larger than ``max_enumerate`` falls back to seeded sampling plus
    single-knob neighborhood refinement of the estimator's leaders — the
    explore/exploit role the reference gives Bayesian optimization
    (``auto/engine/sg_algo/bayes_opt_sg.py``), deterministic here so every
    host enumerates the same space.
    """
    devices = list(devices if devices is not None else jax.devices())
    n_devices = n_devices or len(devices)
    devices = devices[:n_devices]
    seq_len = seq_len or config.max_seq_len

    base = enumerate_candidates(
        config, n_devices, include_pipeline=include_pipeline,
        search_kernels=search_kernels, seq_len=seq_len,
        multihost=jax.process_count() > 1,
    )
    sampled = len(base) > max_enumerate
    if sampled:
        rng = np.random.default_rng(0)  # identical sample on every host
        idx = rng.choice(len(base), size=max_enumerate, replace=False)
        logger.info(
            "auto_tune: sampling %d of %d candidates", max_enumerate,
            len(base),
        )
        base = [base[i] for i in sorted(idx)]
    if search_batch:
        candidates = []
        for mult in (1, 2, 4):
            for cand in base:
                candidates.append(
                    dataclasses.replace(
                        cand, global_batch_size=global_batch_size * mult
                    )
                )
    else:
        candidates = base
    for cand in candidates:
        _estimate(
            cand, config,
            cand.global_batch_size or global_batch_size,
            seq_len, optimizer, n_devices,
        )
    apply_calibration(candidates, calibration)

    def est_rank(c: Candidate) -> float:
        if not search_batch:
            return c.est_step_time
        batch = c.global_batch_size or global_batch_size
        # Throughput objective: bigger batches may take longer steps but
        # move more tokens.
        return -(batch * seq_len / c.est_step_time)

    feasible = sorted(
        (c for c in candidates if not c.rejected), key=est_rank
    )
    if sampled and feasible:
        # Refinement (the BO acquire step, deterministic): estimate every
        # single-knob neighbor of the estimator's leaders — a uniform
        # sample rarely contains the exact best knob combination.
        neighbors = _knob_neighbors(
            feasible[:8], config, seq_len,
            search_kernels=search_kernels,
            multihost=jax.process_count() > 1,
        )
        known = {_cand_key(c) for c in candidates}
        fresh = []
        for cand in neighbors:
            key = _cand_key(cand)
            if key not in known:
                known.add(key)
                fresh.append(cand)
        for cand in fresh:
            _estimate(
                cand, config,
                cand.global_batch_size or global_batch_size,
                seq_len, optimizer, n_devices,
            )
        apply_calibration(fresh, calibration)
        feasible = sorted(
            feasible + [c for c in fresh if not c.rejected], key=est_rank
        )
    if not feasible:
        raise ValueError(
            f"no feasible strategy for {n_devices} devices (all "
            f"{len(candidates)} candidates exceed memory); reduce batch or "
            f"model size"
        )
    logger.info(
        "auto_tune: %d candidates, %d feasible; top: %s",
        len(candidates), len(feasible),
        [c.describe() for c in feasible[:5]],
    )
    if measure:
        def _measure_key(c: Candidate):
            # quantized_dcn is a transport knob _measure cannot exercise
            # (the collective wiring lives in the Local-SGD layer):
            # est-twins differing only in it compile identical programs,
            # so measuring both wastes a finalist slot.
            key = list(_cand_key(c))
            key[-1] = False
            return tuple(key)

        measurable = []
        seen_keys = set()
        for cand in feasible:
            key = _measure_key(cand)
            if key not in seen_keys:
                seen_keys.add(key)
                measurable.append(cand)
        if search_batch:
            # Diversify finalists across batch sizes: the analytic model
            # favors the largest batch monotonically, so a top-k slice
            # would measure only 4x variants — one systematic estimator
            # error (e.g. a real-world OOM) would invalidate every
            # finalist at once with the safe batches never tried.
            finalists, seen_batches = [], set()
            for cand in measurable:
                if cand.global_batch_size not in seen_batches:
                    finalists.append(cand)
                    seen_batches.add(cand.global_batch_size)
                if len(finalists) >= max_measure:
                    break
            for cand in measurable:
                if len(finalists) >= max_measure:
                    break
                if cand not in finalists:
                    finalists.append(cand)
        else:
            finalists = measurable[:max_measure]
        for cand in finalists:
            batch = cand.global_batch_size or global_batch_size
            cand.measured_step_time = _measure(
                cand, config, batch, seq_len, optimizer, devices
            )
            if cand.measured_step_time:
                cand.measured_tokens_per_sec = (
                    batch * seq_len / cand.measured_step_time
                )
        measured = [
            c for c in finalists if c.measured_step_time is not None
        ]

        def measured_rank(c: Candidate) -> float:
            if search_batch:
                return -(c.measured_tokens_per_sec or 0.0)
            return c.measured_step_time

        ranked = sorted(measured, key=measured_rank) + [
            c for c in feasible if c not in measured
        ]
    else:
        ranked = feasible
    best = ranked[0]
    if jax.process_count() > 1:
        # Hosts measure wall-clock independently; near-ties can rank
        # differently per host, and divergent strategies compile mismatched
        # collectives (distributed hang).  Host 0's pick is authoritative —
        # and must ALSO lead `candidates`, or result.best would diverge
        # across hosts while result.parallel agrees.
        best = _broadcast_choice(best, ranked)
        ranked = [best] + [c for c in ranked if c is not best]
    logger.info(
        "auto_tune: selected %s (est %.3fs, measured %s)",
        best.describe(), best.est_step_time,
        f"{best.measured_step_time:.3f}s" if best.measured_step_time else "-",
    )
    cfg_overrides: Dict = dict(
        remat=best.remat,
        pipeline_stages=best.parallel.pipe,
        num_microbatches=(
            (best.microbatches or best.parallel.pipe)
            if best.parallel.pipe > 1 else 0
        ),
        pipeline_interleave=max(best.interleave, 1),
        fused_ln=best.fused_ln,
    )
    if best.flash_block != (0, 0):
        cfg_overrides["flash_block_q"] = best.flash_block[0]
        cfg_overrides["flash_block_kv"] = best.flash_block[1]
    model_cfg = dataclasses.replace(config, **cfg_overrides)
    return TuneResult(
        parallel=best.parallel,
        model_config=model_cfg,
        remat=best.remat,
        candidates=ranked,
        # 0 (the sentinel) whenever batch search was off: every candidate
        # then carries it.
        global_batch_size=best.global_batch_size,
        ce_chunks=best.ce_chunks,
        quantized_dcn=best.quantized_dcn,
    )
