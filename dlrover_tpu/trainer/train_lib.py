"""Sharded train-state construction and train-step compilation.

The TPU-native analogue of the reference's strategy *application* path
(ref ``atorch/atorch/auto/accelerate.py:406-653`` ``model_transform`` +
``atorch/atorch/distributed/distributed.py`` group setup): given a model, an
optimizer, a mesh and a rule table, produce a fully-sharded train state and a
compiled SPMD train step.  There is no module surgery — sharding falls out of
the logical annotations + rules, and XLA inserts every collective.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax.training import train_state as flax_train_state
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models import transformer
from dlrover_tpu.parallel import rules as lr
from dlrover_tpu.runtime import compile_cache


class TrainState(flax_train_state.TrainState):
    """step / params / opt_state / apply_fn / tx."""


# Retrace accounting: the staged python functions run ONLY while jax traces
# them, so counting their executions counts (re)traces.  The restart-fast
# compile path's contract — a second trainer with an identical (config,
# mesh-shape) performs zero retraces — is asserted against these.
TRACE_COUNTS: collections.Counter = collections.Counter()


def trace_count(name: str = "train_step") -> int:
    return TRACE_COUNTS[name]


def reset_trace_counts():
    TRACE_COUNTS.clear()


# The step program's own ``jax.named_scope``s: what the step does outside the
# model's modules.  A scope reaches an instruction's ``op_name`` and nothing
# else (the lowered text without locations is the same), and the device
# trace's per-layer metrics read it there; an op differentiated inside the
# loss function reads ``jvp(loss)`` and ``transpose(jvp(loss))``.  None of
# the names holds a layer's (``attn``, ``moe``, ``mtp``, ``scatter``,
# ``combine``, ``conv``), whose metrics must not pick them up.
OPTIMIZER = "optimizer"
OPTIMIZER_REDUCE = f"{OPTIMIZER}/reduce"  # ZeRO-1: gradients to their shards
OPTIMIZER_UPDATE = f"{OPTIMIZER}/update"  # ``tx.update``: clip and the rule
OPTIMIZER_APPLY = f"{OPTIMIZER}/apply"  # ``apply_updates``
OPTIMIZER_GATHER = f"{OPTIMIZER}/gather"  # ZeRO-1: parameters re-replicated
GRAD_NORM = "grad_norm"
ROUTER_BIAS = "router_bias"
LOSS = "loss"  # cross-entropy (the MTP term's too) and the scalars after it
GRAD_ACCUM = "grad_accum"  # the microbatch reshapes and the carry's sum
STEP_SCOPES = (
    OPTIMIZER_REDUCE, OPTIMIZER_UPDATE, OPTIMIZER_APPLY, OPTIMIZER_GATHER,
    GRAD_NORM, ROUTER_BIAS, LOSS, GRAD_ACCUM,
)


def use_mesh(mesh: Mesh):
    """Context entering the mesh for both tracing and execution."""
    from dlrover_tpu.runtime.mesh import activate_mesh

    return activate_mesh(mesh)


def make_schedule(
    learning_rate: float,
    warmup_steps: int = 0,
    decay_steps: int = 0,
):
    """The LR schedule ``make_optimizer`` installs — exposed so the trainer
    façade can log the live LR (``schedule(step)``) without re-deriving it."""
    if warmup_steps and not decay_steps:
        # Warmup-only: ramp to peak then hold (a cosine schedule here would
        # collapse to end_value one step after warmup).
        return optax.linear_schedule(
            init_value=0.0,
            end_value=learning_rate,
            transition_steps=max(1, warmup_steps),
        )
    if warmup_steps or decay_steps:
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=learning_rate,
            warmup_steps=max(1, warmup_steps),
            decay_steps=max(decay_steps, warmup_steps + 1),
            end_value=learning_rate * 0.1,
        )
    return learning_rate


def make_optimizer(
    name: str = "adamw",
    learning_rate: float = 3e-4,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
    warmup_steps: int = 0,
    decay_steps: int = 0,
    **kwargs,
) -> optax.GradientTransformation:
    schedule = make_schedule(learning_rate, warmup_steps, decay_steps)
    if name == "adamw":
        opt = optax.adamw(
            schedule, b1=b1, b2=b2, weight_decay=weight_decay, **kwargs
        )
    elif name == "adafactor":
        opt = optax.adafactor(schedule)
    elif name == "sgd":
        opt = optax.sgd(schedule, momentum=0.9)
    elif name == "lion":
        opt = optax.lion(schedule, weight_decay=weight_decay)
    elif name == "agd":
        # Stepwise-gradient-difference preconditioning (NeurIPS'23; ref
        # ``atorch/atorch/optimizers/agd.py``).
        from dlrover_tpu.optimizers.agd import agd

        opt = agd(
            schedule, b1=b1, b2=b2, weight_decay=weight_decay, **kwargs
        )
    elif name == "q8_adam":
        # 8-bit moments via the fused Pallas dequant->Adam->requant kernel
        # (ref ``atorch/atorch/optimizers/low_bit/``): ~2.5 bytes/param of
        # optimizer HBM instead of 8.
        from dlrover_tpu.ops.quantization import q8_adam

        opt = q8_adam(
            schedule, b1=b1, b2=b2, weight_decay=weight_decay, **kwargs
        )
    elif name == "q4_adam":
        # 4-bit packed moments (1.25 bytes/param; ref q4 states in
        # ``low_bit/functional.py``).
        from dlrover_tpu.ops.quantization import q4_adam

        opt = q4_adam(
            schedule, b1=b1, b2=b2, weight_decay=weight_decay, **kwargs
        )
    else:
        raise ValueError(f"unknown optimizer {name!r}")
    if grad_clip:
        opt = optax.chain(optax.clip_by_global_norm(grad_clip), opt)
    return opt


def cross_entropy_loss(
    logits: jax.Array,
    targets: jax.Array,
    weights: Optional[jax.Array] = None,
    z_loss: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Token-level softmax CE in fp32; returns (mean_loss, num_tokens)."""
    logits = logits.astype(jnp.float32)
    log_z = jax.scipy.special.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(
        logits, targets[..., None], axis=-1
    )[..., 0]
    loss = log_z - label_logits
    if z_loss:
        loss = loss + z_loss * jnp.square(log_z)
    if weights is None:
        weights = jnp.ones_like(loss)
    weights = weights.astype(jnp.float32)
    total_weight = jnp.maximum(weights.sum(), 1.0)
    return (loss * weights).sum() / total_weight, total_weight


def chunked_cross_entropy_loss(
    hidden: jax.Array,
    head: jax.Array,
    targets: jax.Array,
    weights: Optional[jax.Array] = None,
    *,
    num_chunks: int = 8,
    z_loss: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Softmax CE from final hidden states without full-logit materialization.

    Computes logits chunk-by-chunk over the sequence axis inside a
    rematerialized ``lax.scan``, so the [B, S, V] fp32 logits tensor (3.3 GB
    at the 1.5B bench shape) never lives in HBM — the backward recomputes
    each chunk's logits.  This is the fused/vocab-CE counterpart of the
    reference's fused cross-entropy kernels
    (ref ``atorch/atorch/modules/transformer/cross_entropy.py``), done the
    XLA way: a small scan + checkpoint instead of a custom kernel.

    Args:
      hidden: [B, S, D] final (normed) hidden states.
      head:   [V, D] output head — the tied embedding table, or lm_head
              kernel transposed.
      targets: [B, S] int labels.  weights: [B, S] or None.
    """
    b, s, d = hidden.shape
    if weights is None:
        weights = jnp.ones((b, s), jnp.float32)
    num_chunks = max(1, min(num_chunks, s))
    while s % num_chunks:
        num_chunks -= 1
    c = s // num_chunks
    xs = (
        hidden.reshape(b, num_chunks, c, d).swapaxes(0, 1),
        targets.reshape(b, num_chunks, c).swapaxes(0, 1),
        weights.reshape(b, num_chunks, c).swapaxes(0, 1),
    )

    def chunk_fn(carry, inp):
        x_c, t_c, w_c = inp
        logits = jnp.einsum(
            "bcd,vd->bcv",
            x_c.astype(head.dtype),
            head,
            preferred_element_type=jnp.float32,
        )
        log_z = jax.scipy.special.logsumexp(logits, axis=-1)
        label_logits = jnp.take_along_axis(
            logits, t_c[..., None], axis=-1
        )[..., 0]
        loss = log_z - label_logits
        if z_loss:
            loss = loss + z_loss * jnp.square(log_z)
        w = w_c.astype(jnp.float32)
        return (carry[0] + (loss * w).sum(), carry[1] + w.sum()), None

    (total, total_weight), _ = jax.lax.scan(
        jax.checkpoint(chunk_fn), (jnp.zeros(()), jnp.zeros(())), xs
    )
    total_weight = jnp.maximum(total_weight, 1.0)
    return total / total_weight, total_weight


def output_head(params: Dict[str, Any]) -> jax.Array:
    """[V, D] output projection from a TransformerLM param tree."""
    if "lm_head" in params:
        kernel = params["lm_head"]["kernel"]  # [D, V]
        if isinstance(kernel, nn.meta.AxisMetadata):
            kernel = kernel.value
        return kernel.T
    table = params["embed"]["embedding"]  # [V, D]
    if isinstance(table, nn.meta.AxisMetadata):
        table = table.value
    return table


@dataclasses.dataclass
class ShardedTrain:
    """A compiled SPMD training program bound to one mesh + rule table."""

    mesh: Mesh
    rules: Any
    state_shardings: Any
    batch_shardings: Any
    init_fn: Callable[..., TrainState]
    step_fn: Callable[..., Tuple[TrainState, Dict[str, jax.Array]]]
    eval_fn: Optional[Callable] = None
    # Abstract batch (ShapeDtypeStructs) matching step_fn's second arg —
    # what aot_compile lowers against without touching real data.
    batch_avals: Optional[Dict[str, jax.ShapeDtypeStruct]] = None
    # Microbatch-engine knobs the program was built with (introspection for
    # the trainer façade, trace tooling, and checkpoint `extra` booking).
    grad_accum: int = 1
    accum_dtype: str = "float32"
    reduce_quant: str = "none"
    # ZeRO-1 sharded weight update: True when the optimizer state and the
    # parameter update are sharded over the data axis (optimizers/zero1.py
    # spec derivation; inactive when the mesh has no data axis > 1).
    zero1: bool = False
    # Leaf counts + per-device bytes from the zero1 spec derivation —
    # what bench/PROFILE report as the replicated-vs-sharded memory model.
    zero1_stats: Optional[Dict[str, Any]] = None
    # Overlap engine (parallel/overlap.py): True when the program was built
    # with the scan-interior per-bucket reduce-scatter + per-bucket
    # all-gather staircase (requires zero1 with an active data axis).
    overlap: bool = False
    overlap_bucket_mb: float = 0.0
    # Re-replication wire format for the zero1 all-gather leg.
    allgather_quant: str = "none"
    # plan_buckets().describe() of the compiled bucket assignment.
    overlap_plan: Optional[Dict[str, Any]] = None
    # Canonical pytree statics the program was compiled against.  TrainState
    # metadata carries apply_fn/tx identities, and optax transforms compare
    # by function identity — so a state built by a DIFFERENT trainer whose
    # cache key aliased this program would retrace (jit) or be rejected
    # outright (AOT).  adopt() rebinds a state to these canonical statics.
    apply_fn: Optional[Callable] = None
    tx: Optional[optax.GradientTransformation] = None
    _aot_step: Optional[Callable] = None
    # Compiled program's memory_analysis() (flat xla_*_b bytes dict from
    # utils/memory_profile), captured by aot_compile where the backend
    # provides it — the compiler-side half of the HBM accounting plane.
    memory_analysis: Optional[Dict[str, int]] = None
    # Compiled Pallas kernels (``tpu_custom_call``) in the AOT step's
    # optimized program: 0 wherever the kernels ran in interpret mode, so
    # a chip run can tell that the step it timed holds the kernel it names.
    kernel_calls: Optional[int] = None
    # Where ``aot_compile``'s seconds went (``trace_s``, ``lower_s``,
    # ``backend_s``, ``analysis_s``), the text pass beside them
    # (``text_s``) and what the persistent cache did (``cache``, and on a
    # hit ``retrieval_s``); empty until it has compiled.
    compile_parts: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def init(self, rng: jax.Array) -> TrainState:
        with use_mesh(self.mesh):
            return self.init_fn(rng)

    def abstract_state(self) -> TrainState:
        """The state ``init`` will give, described: every leaf a
        ``jax.ShapeDtypeStruct`` under its ``state_shardings`` sharding.
        No array is made (``aot_compile`` traces the same ``init_fn``,
        so one of the two finds the other's trace)."""
        with use_mesh(self.mesh):
            shapes = jax.eval_shape(self.init_fn, _ABSTRACT_KEY)
        leaves, treedef = jax.tree_util.tree_flatten(shapes)
        shardings = jax.tree_util.tree_leaves(
            self.state_shardings,
            is_leaf=lambda x: isinstance(x, jax.sharding.Sharding),
        )
        return jax.tree_util.tree_unflatten(treedef, [
            jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sharding)
            for leaf, sharding in zip(leaves, shardings, strict=True)
        ])

    def adopt(self, state: TrainState) -> TrainState:
        """Rebind a state's static metadata (apply_fn/tx) to the identities
        this program was compiled with; array leaves are untouched."""
        if self.apply_fn is None:
            return state
        return state.replace(apply_fn=self.apply_fn, tx=self.tx)

    def step(self, state: TrainState, batch: Dict[str, jax.Array]):
        with use_mesh(self.mesh):
            fn = self._aot_step if self._aot_step is not None else self.step_fn
            return fn(state, batch)

    def eval_step(self, state: TrainState, batch: Dict[str, jax.Array]):
        """Forward-only loss on one batch -> {"loss", "tokens"}."""
        with use_mesh(self.mesh):
            return self.eval_fn(state, batch)

    def compiled_step_text(self) -> str:
        """The compiled step program as text (its instructions with their
        ``op_name`` metadata); empty before ``aot_compile``."""
        return self._aot_step.as_text() if self._aot_step is not None else ""

    def aot_compile(self, restart_count: Optional[int] = None) -> float:
        """``lower().compile()`` the train step before the first batch.

        Returns the wall seconds spent (the goodput ledger records it as
        compile time, not training time).  Subsequent ``step()`` calls run
        the compiled executable directly, so the jit dispatch path never
        retraces — and with the persistent compilation cache enabled the
        XLA compile inside is a disk hit on a post-restart world.

        The seconds are split where the work happens (``compile_parts``):
        ``compile.trace`` (the abstract state and the step's tracing),
        ``compile.lower``, ``compile.backend`` (the cache read or XLA's
        compile) and ``compile.analysis``; after them, and outside the
        seconds returned, ``compile.text`` walks the compiled text for
        ``kernel_calls``.  A trainer books the whole afterwards as its
        restart's ``compile`` event, whose seconds are the ones returned
        and which no open span can give (the text pass would lie inside
        it): with ``restart_count`` the spans, closed by then, carry that
        ``parent`` and restart.
        """
        if self._aot_step is not None or self.batch_avals is None:
            return 0.0
        from dlrover_tpu.utils import memory_profile

        parts = self.compile_parts = {}
        t0 = time.perf_counter()
        span_attrs = {} if restart_count is None else {
            "parent": "compile", "restart_count": restart_count,
        }
        named = dict(span_attrs, fun_name=self.step_fn.__name__)
        with use_mesh(self.mesh):
            with compile_cache.stage("trace", parts, **named):
                abstract_state = jax.eval_shape(self.init_fn, _ABSTRACT_KEY)
                traced = self.step_fn.trace(abstract_state, self.batch_avals)
            self._aot_step = compile_cache.compile_traced(
                traced, parts, **named
            )
        with compile_cache.stage("analysis", parts, **span_attrs):
            self.memory_analysis = memory_profile.compiled_memory_analysis(
                self._aot_step
            )
        seconds = time.perf_counter() - t0
        with compile_cache.stage("text", parts, **span_attrs):
            self.kernel_calls = self._aot_step.as_text().count(
                'custom_call_target="tpu_custom_call"'
            )
        return seconds


# Shape of a ``jax.random.PRNGKey``: eval_shape needs no real key, and
# building one inside the mesh context would run a program on the mesh's
# devices before anything is compiled for them.
_ABSTRACT_KEY = jax.ShapeDtypeStruct((2,), jnp.uint32)


def _sanitize_boxes(tree):
    """Drop sharding boxes whose axis names no longer match the value rank.

    Mirror-shaped optimizer states (Adam mu/nu) inherit valid metadata from
    the params, but factored states (adafactor v_row/v_col) change rank while
    optax's tree_map re-wraps them in the original boxes — strip those so they
    fall back to replicated.  Reads ``.value`` (not ``.unbox()``, which would
    apply the invalid constraint being checked for).
    """
    def fix(leaf):
        if isinstance(leaf, nn.meta.AxisMetadata):
            names = getattr(leaf, "names", ())
            value = getattr(leaf, "value", None)
            # Unbox when the boxed value is not a matching-rank array — e.g.
            # adafactor's factored rows/cols, or quantized-moment subtrees
            # (q8_adam) where the box wraps a whole (q, scales) pytree.
            if getattr(value, "ndim", None) != len(names):
                return value
        return leaf

    return jax.tree.map(
        fix, tree, is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata)
    )


def logical_sharding(
    mesh: Mesh, rules, *logical_axes: Optional[str]
) -> NamedSharding:
    """Map logical axis names -> NamedSharding via the rule table."""
    spec = nn.logical_to_mesh_axes(list(logical_axes), rules=list(rules))
    return NamedSharding(mesh, spec)


# In-process memo of compiled programs, keyed by
# ``runtime.compile_cache.train_cache_key``: a trainer rebuilt after an
# elastic resize back to an already-seen (config, mesh-shape) pair reuses
# the jitted functions — zero retraces, zero XLA compiles.
_BUILD_CACHE: Dict[str, ShardedTrain] = {}


def reset_build_cache():
    _BUILD_CACHE.clear()


_ACCUM_DTYPES = {
    "float32": jnp.float32,
    "fp32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "bf16": jnp.bfloat16,
}


def _batch_shard_count(mesh: Mesh, batch_spec_entry) -> int:
    """How many ways the batch dim is split (product of its mesh axes)."""
    if batch_spec_entry is None:
        return 1
    names = (
        batch_spec_entry
        if isinstance(batch_spec_entry, tuple)
        else (batch_spec_entry,)
    )
    out = 1
    for name in names:
        out *= dict(zip(mesh.axis_names, mesh.devices.shape))[name]
    return out


def _sown_vectors(sown, name: str) -> Optional[jax.Array]:
    """The float32 vectors the layers sowed under ``name`` into the
    ``"intermediates"`` of a forward pass, as ``[n, width]`` over the
    layers and whatever axes the layer scan and the sow stack; ``None``
    where no layer sowed one."""
    vectors = [
        leaf.reshape(-1, leaf.shape[-1])
        for path, leaf in jax.tree_util.tree_leaves_with_path(sown)
        if any(getattr(key, "key", None) == name for key in path)
    ]
    if not vectors:
        return None
    return jax.lax.stop_gradient(
        jnp.concatenate(vectors, axis=0).astype(jnp.float32)
    )


ROUTER_LOADS = "router_loads"


def _router_loads(sown) -> Dict[Tuple[str, ...], jax.Array]:
    """Each expert layer's own per-expert loads ``[..., E]`` (the ``load``
    of its ``moe_stats``), by the module path that sowed them: the same
    path holds the layer's ``router_bias`` in ``params``."""
    out = {}

    def walk(node, path):
        if not isinstance(node, dict):
            return
        if "moe_stats" in node:
            out[path] = moe_lib.split_stats(
                jax.lax.stop_gradient(node["moe_stats"][0])
            )[2]
        for key, child in node.items():
            walk(child, path + (key,))

    walk(dict(sown).get("intermediates", {}), ())
    return out


def _folded(families, vectors_of) -> Dict[str, Any]:
    """Each sown statistic of the ``families`` that ``vectors_of(name)``
    holds as ``[n, width]``, folded to the one vector a step hands out by
    its family's own fold (``models/family.py``), by metric name."""
    out = {}
    for family in families:
        for name, fold in family.stats.items():
            vectors = vectors_of(name) if fold is not None else None
            if vectors is not None:
                out[name] = fold(vectors)
    return out


def _layer_stats(families, sown, router_loads: bool = False) -> Dict[str, Any]:
    """What a step hands out of the vectors the layers of ``families``
    sowed, by metric name, each folded over the layers (``moe_stats``: the
    mean; ``linear_attn_stats``, ``ssm_stats``, ``conv_stats``: means, and
    the largest entry; ...).  Empty for a model whose layers sow none.
    ``router_loads`` adds each expert layer's own loads (``ROUTER_LOADS``,
    by module path) for the router-bias rule."""
    out = _folded(families, functools.partial(_sown_vectors, sown))
    if router_loads:
        out[ROUTER_LOADS] = _router_loads(sown)
    return out


def move_router_bias(old_params, new_params, loads, rate: float):
    """``new_params`` with every ``router_bias`` set to the OLD one moved
    by the balancing rule (``moe.bias_update``) on that layer's own loads
    of this step: whatever the optimizer made of the leaf (its gradient is
    zero, but weight decay or a parameter-scaled update need not be) is
    discarded.  ``loads``: ``ROUTER_LOADS`` of :func:`_layer_stats`."""
    def moved(old, new, path, load):
        if not path:
            return dict(new, router_bias=moe_lib.bias_update(
                old["router_bias"], load, rate
            ))
        key = path[0]
        return dict(new, **{key: moved(old[key], new[key], path[1:], load)})

    for path, load in loads.items():
        new_params = moved(old_params, new_params, path, load)
    return new_params


def build_sharded_train(
    model: nn.Module,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    rules,
    *,
    global_batch_size: int,
    seq_len: int,
    donate_state: bool = True,
    ce_chunks: int = 0,
    grad_accum: int = 1,
    accum_dtype: str = "float32",
    reduce_quant: str = "none",
    zero1: bool = False,
    overlap: bool = False,
    overlap_bucket_mb: float = 4.0,
    allgather_quant: str = "none",
    cache_key: Optional[str] = None,
) -> ShardedTrain:
    """Construct init/step functions jitted with mesh shardings.

    The batch dict is expected to hold int32 ``inputs`` and ``targets`` of
    shape [global_batch, seq_len] (plus optional fp ``weights``), laid out as
    jax.Arrays sharded batch-over-(data,fsdp) and seq-over-seq.

    ``grad_accum=N`` turns on the microbatch engine: the global batch is
    reshaped to [N, micro, seq] and a donated-carry ``lax.scan`` runs the
    forward+backward once per microbatch, accumulating gradients into an
    ``accum_dtype`` carry (fp32 default; "bf16" halves accumulator HBM at a
    documented tolerance cost) pinned to the params' sharding with
    ``with_sharding_constraint`` — XLA keeps the accumulator distributed
    and defers the data-parallel reduce to once per step instead of once
    per microbatch.  The loss is normalized by the GLOBAL token count (and
    the model aux loss by 1/N), so the accumulated gradient equals the
    full-batch gradient bitwise-up-to-reassociation: tokens/step and the
    optimizer trajectory are invariant in N, which is what lets the elastic
    trainer trade microbatches for devices on a resize.

    ``reduce_quant="int8"`` routes the once-per-step deferred gradient
    reduce through ``parallel.quantized_collectives.quantized_all_reduce``
    (EQuARX-shaped int8 wire format) over the ``data`` mesh axis via
    ``shard_map``.  Under GSPMD the per-microbatch grads arrive already
    globally summed, so on the data axis this runs the real quantized
    collective over data-replicated values — exercising the int8 wire path
    (and its quantization rounding) inside the compiled program; with
    ``data=1`` it is the identity.

    ``zero1=True`` turns on the cross-replica sharded weight update
    (ZeRO-1-for-XLA, arXiv:2004.13336): optimizer state is laid out with
    the ``data`` axis folded into each leaf's sharding
    (``optimizers.zero1``), and the step replaces ``apply_gradients`` with
    pin-grads-to-shard -> shard-local ``tx.update`` -> all-gather of the
    updated params.  GSPMD lowers the pin as a reduce-scatter (half the
    all-reduce wire) and the re-replication as an all-gather, and each
    replica pays 1/dp of the optimizer-state HBM and update FLOPs.  The
    update math is unchanged — parity with the replicated step holds to
    float-reassociation tolerance — so the knob composes freely with
    ``grad_accum`` and ``reduce_quant`` (whose int8 wire then runs as a
    per-shard quantized reduce-scatter with topology-aware ring/one-shot
    selection; the param all-gather stays full-precision).  A mesh with no
    ``data`` axis > 1 deactivates it silently.

    ``overlap=True`` (with ``zero1``) replaces the hope that "XLA's
    scheduler overlaps the reduce-scatter with the tail of the backward"
    with *structure* (``parallel.overlap``): gradients are reduce-scattered
    per microbatch inside the scan — reduce-scatter is linear, so
    accumulating the scattered shards equals scattering the accumulated
    gradient — and the scan carry shrinks to the 1/dp shard layout.
    Microbatch *i*'s reduce-scatter has no consumer in microbatch *i+1*'s
    backward, so the compiled program's dependence graph lets the wire
    hide under compute instead of leaving it to scheduler luck; the
    collectives issue in ~``overlap_bucket_mb``-MB bucket waves ordered by
    an ``optimization_barrier`` staircase, and the post-update param
    re-replication runs per-bucket the same way.  The trade: ``grad_accum``
    × the reduce-scatter wire bytes, hidden instead of exposed —
    ``auto.tune.est_comm_time`` prices it and ``tools/overlap_bench.py``
    certifies the measured overlap.  ``allgather_quant="int8"`` further
    routes the re-replication leg through
    ``quantized_collectives.quantized_all_gather`` (block-quantized
    travelling shards; quantization noise then does touch the replicated
    params, a documented tolerance).  Without an active ``data`` axis > 1
    or without ``zero1``, ``overlap`` deactivates silently, mirroring the
    ``zero1`` knob.

    ``cache_key`` (from ``runtime.compile_cache.train_cache_key``) opts into
    the in-process program memo: the caller asserts that equal keys mean an
    identical (model, optimizer, mesh-shape, batch) recipe, and gets back
    the previously-built ShardedTrain — no retrace, no recompile.  The memo
    compares mesh device layout too, so a resize to a genuinely different
    world never aliases.
    """
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
    if accum_dtype not in _ACCUM_DTYPES:
        raise ValueError(
            f"accum_dtype {accum_dtype!r} not in "
            f"{sorted(_ACCUM_DTYPES)}"
        )
    if reduce_quant not in ("none", "int8"):
        raise ValueError(
            f"reduce_quant {reduce_quant!r} must be 'none' or 'int8'"
        )
    if allgather_quant not in ("none", "int8"):
        raise ValueError(
            f"allgather_quant {allgather_quant!r} must be 'none' or 'int8'"
        )
    if cache_key is not None:
        cached = _BUILD_CACHE.get(cache_key)
        if cached is not None and (
            cached.mesh.devices.shape == mesh.devices.shape
            and list(cached.mesh.devices.flat) == list(mesh.devices.flat)
        ):
            logger.info("build_sharded_train: compile-cache hit (%d entries)",
                        len(_BUILD_CACHE))
            return cached
    rules = list(rules)
    dummy_tokens = jnp.zeros((global_batch_size, seq_len), jnp.int32)

    def _make_state(params, opt_state) -> TrainState:
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            apply_fn=model.apply,
            params=params,
            tx=optimizer,
            opt_state=opt_state,
        )

    def _init_boxed(rng) -> TrainState:
        # Used only under eval_shape to harvest sharding metadata: params stay
        # boxed so mirror-shaped optimizer states (Adam mu/nu) inherit specs.
        params = model.init(rng, dummy_tokens)["params"]
        return _make_state(params, optimizer.init(params))

    def _init(rng) -> TrainState:
        # The runtime state is fully unboxed (raw arrays): unbox applies the
        # logical sharding constraints, then the optimizer inits from plain
        # arrays so factored states (adafactor) get valid shapes.
        TRACE_COUNTS["init"] += 1
        params = nn.meta.unbox(model.init(rng, dummy_tokens)["params"])
        return _make_state(params, optimizer.init(params))

    with use_mesh(mesh), nn.logical_axis_rules(rules):
        abstract_state = jax.eval_shape(_init_boxed, _ABSTRACT_KEY)
        abstract_state = _sanitize_boxes(abstract_state)
        logical_specs = nn.get_partition_spec(abstract_state)
        state_shardings = nn.logical_to_mesh_sharding(
            logical_specs, mesh, rules
        )

    # ZeRO-1: re-shard the optimizer state (persistently, via the jitted
    # in/out shardings) and derive the transient grad/param shard specs
    # the update path pins through.  Shapes come from the eval_shape
    # harvest with the flax metadata boxes collapsed to plain leaves, so
    # the tree lines up 1:1 with the NamedSharding tree.
    mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    zero1_active = bool(zero1) and mesh_sizes.get("data", 1) > 1
    zero1_param_shardings = None
    zero1_opt_shardings = None
    zero1_stats = None
    # The init program keeps the replicated-update shardings: with the
    # non-partitionable threefry RNG the random bits depend on the layout
    # GSPMD picks, so compiling init against zero1 out-shardings would
    # yield DIFFERENT initial params than the replicated build (observed:
    # 0.37 max abs diff) and no parity could hold.  Init stays bitwise
    # identical; the opt state moves to its sharded layout via an explicit
    # (value-preserving) device_put right after.
    init_shardings = state_shardings
    if zero1_active:
        from dlrover_tpu.optimizers import zero1 as zero1_lib

        def _unbox(leaf):
            if isinstance(leaf, nn.meta.AxisMetadata):
                return leaf.value
            return leaf

        abstract_plain = jax.tree.map(
            _unbox, abstract_state,
            is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata),
        )
        zero1_opt_shardings, opt_stats = zero1_lib.shard_update_shardings(
            mesh, abstract_plain.opt_state, state_shardings.opt_state
        )
        zero1_param_shardings, _ = zero1_lib.shard_update_shardings(
            mesh, abstract_plain.params, state_shardings.params
        )
        state_shardings = state_shardings.replace(
            opt_state=zero1_opt_shardings
        )
        zero1_stats = opt_stats
        logger.info(
            "zero1 sharded update: dp=%d, %d/%d opt-state leaves sharded "
            "(%.1f -> %.1f MB/device)",
            opt_stats["dp"], opt_stats["sharded_leaves"],
            opt_stats["sharded_leaves"] + opt_stats["replicated_leaves"],
            opt_stats["bytes_per_device_before"] / 1e6,
            opt_stats["bytes_per_device_after"] / 1e6,
        )
    # Overlap needs the zero1 shard specs to scatter into; without them it
    # deactivates silently (same contract as the zero1 knob itself).
    overlap_active = bool(overlap) and zero1_active
    overlap_plan = None

    token_sharding = logical_sharding(mesh, rules, lr.BATCH, lr.ACT_SEQ)
    batch_shardings = {
        "inputs": token_sharding,
        "targets": token_sharding,
        "weights": token_sharding,
    }
    if grad_accum > 1:
        dp = _batch_shard_count(mesh, token_sharding.spec[0])
        if global_batch_size % (dp * grad_accum):
            raise ValueError(
                f"global_batch_size {global_batch_size} must be divisible "
                f"by dp*grad_accum = {dp}*{grad_accum} = {dp * grad_accum} "
                f"(each of the {grad_accum} microbatches must still split "
                f"over the {dp}-way batch sharding); pick a grad_accum "
                f"dividing {global_batch_size // dp}"
            )
    accum_jdt = _ACCUM_DTYPES[accum_dtype]
    micro_sharding = NamedSharding(
        mesh, PartitionSpec(None, *token_sharding.spec)
    )
    if overlap_active:
        from dlrover_tpu.parallel import overlap as overlap_lib

        overlap_plan = overlap_lib.plan_buckets(
            abstract_plain.params, overlap_bucket_mb,
            dtype_bytes=jnp.dtype(accum_jdt).itemsize,
        )
        logger.info(
            "overlap engine: %d bucket(s) of ~%.1f MB over %d grad leaves "
            "(scan-interior reduce-scatter%s, per-bucket all-gather%s)",
            overlap_plan.num_buckets, overlap_bucket_mb,
            overlap_plan.num_leaves,
            " [int8]" if reduce_quant == "int8" else "",
            " [int8]" if allgather_quant == "int8" else "",
        )

    # A model with routers, linear-attention or state-space layers hands
    # each layer's sown stats vector out of the step that computes it; any
    # other model's step is applied as ever.
    model_config = getattr(model, "config", None)
    families = (
        transformer.families(model_config)
        if isinstance(model_config, transformer.TransformerConfig) else ()
    )
    sows_stats = any(
        fold is not None
        for family in families for fold in family.stats.values()
    )
    # The DeepSeek-V3 family: a multi-token-prediction module whose
    # cross-entropy joins the loss, and router biases the step moves.
    mtp_weight = (
        float(getattr(model_config, "mtp_weight", 0.0))
        if getattr(model_config, "mtp_depth", 0) else 0.0
    )
    bias_rate = (
        float(getattr(model_config, "router_bias_rate", 0.0))
        if getattr(model_config, "router_bias", False) else 0.0
    )

    def _forward_sums(params, apply_fn, inputs, targets, weights):
        """One forward pass -> (weighted CE sum, token count, aux loss,
        layer statistics).  The last is ``_layer_stats`` of what the
        layers sowed (``moe_stats``, ``linear_attn_stats``, ``ssm_stats``,
        ``conv_stats``, ``attn_stats``),
        folded over
        layers and whatever axes the scan and the sow stack, under
        ``stop_gradient``; empty for a model that sows none.  A model with
        an MTP module is handed the targets as the next tokens, and the
        weighted sum of its cross-entropy (token ``i + 2`` from position
        ``i``, the last position masked) rides the statistics as
        ``mtp_ce_sum``."""
        kwargs = {"return_hidden": True} if ce_chunks else {}
        if mtp_weight:
            kwargs["next_tokens"] = targets
        variables = {"params": params}
        if sows_stats:
            outs, sown = apply_fn(
                variables, inputs, mutable=["intermediates"], **kwargs
            )
            stats = _layer_stats(
                families, sown, router_loads=bool(bias_rate)
            )
        else:
            outs = apply_fn(variables, inputs, **kwargs)
            stats = {}
        out, aux = outs[:2]

        def ce_of(out, targets, weights):
            if ce_chunks:
                return chunked_cross_entropy_loss(
                    out, output_head(params), targets, weights,
                    num_chunks=ce_chunks,
                )
            return cross_entropy_loss(out, targets, weights)

        with jax.named_scope(LOSS):
            ce, total_weight = ce_of(out, targets, weights)
            if mtp_weight:
                # position i predicts targets[i + 1]; the last has none
                mtp_ce, mtp_total = ce_of(
                    outs[2], jnp.roll(targets, -1, axis=1),
                    jnp.pad(weights[:, 1:], ((0, 0), (0, 1))),
                )
                stats["mtp_ce_sum"] = mtp_ce * mtp_total
                stats["mtp_tokens"] = mtp_total
            return ce * total_weight, total_weight, aux, stats

    def _q_reduce_scatter_leaf(leaf, z_sharding, full_sharding):
        """Route one gradient leaf's DP reduce through the int8 wire as a
        per-shard reduce-scatter: each member keeps only its update shard,
        so the quantized payload crosses the wire ONCE (the param
        all-gather after the update stays full precision — satellite: the
        int8 ratio applies to the reduce-scatter leg only)."""
        from dlrover_tpu.optimizers.zero1 import data_axis_dim
        from dlrover_tpu.parallel.quantized_collectives import (
            axis_crosses_dcn,
            quantized_all_reduce,
            quantized_reduce_scatter,
            select_reduce_algo,
        )
        from dlrover_tpu.runtime.mesh import shard_map_compat

        dp = mesh_sizes["data"]
        algo = select_reduce_algo(
            dp,
            payload_bytes=leaf.size * jnp.dtype(leaf.dtype).itemsize,
            crosses_dcn=axis_crosses_dcn(mesh, "data"),
        )
        dim = data_axis_dim(z_sharding.spec)
        if dim is None:
            # Unshardable leaf (scalar / no divisible dim): replicated
            # update, so it needs the full all-reduce.
            fn = shard_map_compat(
                lambda v: quantized_all_reduce(
                    v, "data", mean=True, algo=algo
                ),
                mesh=mesh, in_specs=full_sharding.spec,
                out_specs=full_sharding.spec,
            )
            return fn(leaf)
        fn = shard_map_compat(
            lambda v: quantized_reduce_scatter(
                v, "data", dim=dim, mean=True, algo=algo
            ),
            mesh=mesh, in_specs=full_sharding.spec,
            out_specs=z_sharding.spec,
        )
        return fn(leaf)

    if overlap_active:
        _z_param_leaves = jax.tree_util.tree_leaves(zero1_param_shardings)
        _full_param_leaves = jax.tree_util.tree_leaves(
            state_shardings.params
        )

        def _rs_grad_leaf(i, g):
            """Scatter one gradient leaf to its zero1 update shard (the
            scan-interior reduce-scatter; int8 when reduce_quant asks)."""
            z, full = _z_param_leaves[i], _full_param_leaves[i]
            if reduce_quant == "int8":
                return _q_reduce_scatter_leaf(g, z, full)
            return jax.lax.with_sharding_constraint(g, z)

        def _scatter_grads(grads):
            """Per-bucket reduce-scatter waves over the whole grad tree."""
            with jax.named_scope(OPTIMIZER_REDUCE):
                return overlap_lib.scheduled_leaf_map(
                    _rs_grad_leaf, grads, overlap_plan
                )

        def _ag_param_leaf(i, p):
            """Re-replicate one updated param leaf (optionally int8)."""
            from dlrover_tpu.optimizers.zero1 import data_axis_dim
            from dlrover_tpu.parallel.quantized_collectives import (
                axis_crosses_dcn,
                quantized_all_gather,
                select_reduce_algo,
            )
            from dlrover_tpu.runtime.mesh import shard_map_compat

            z, full = _z_param_leaves[i], _full_param_leaves[i]
            dim = data_axis_dim(z.spec)
            if allgather_quant == "int8" and dim is not None:
                algo = select_reduce_algo(
                    mesh_sizes["data"],
                    payload_bytes=(
                        p.size * jnp.dtype(p.dtype).itemsize
                        // mesh_sizes["data"]
                    ),
                    crosses_dcn=axis_crosses_dcn(mesh, "data"),
                )
                fn = shard_map_compat(
                    lambda v: quantized_all_gather(
                        v, "data", dim=dim, algo=algo
                    ),
                    mesh=mesh, in_specs=z.spec, out_specs=full.spec,
                )
                return fn(p)
            return jax.lax.with_sharding_constraint(p, full)

        def _replicate_params(new_params):
            """Per-bucket all-gather staircase: bucket b's re-replication
            is ordered before bucket b+1's, so its wire pipelines against
            the remaining buckets' update arithmetic instead of landing
            as one post-update wall."""
            return overlap_lib.scheduled_leaf_map(
                _ag_param_leaf, new_params, overlap_plan
            )

    def _apply_update(state: TrainState, grads, scattered: bool = False):
        """Optimizer update: replicated or ZeRO-1, each part under its
        ``STEP_SCOPES`` name.

        The zero1 path is the replicated one with three sharding pins
        around it: grads pinned to the update shards (GSPMD lowers the DP
        sum into a reduce-scatter — or the quantized collective runs it
        explicitly), params pinned likewise (a free local slice of the
        replicated copy), and the updated params pinned back to their
        replicated layout (the all-gather).  Same math, 1/dp of the
        update.  Without ``overlap`` the reduce-scatter/all-gather only
        overlap compute if XLA's scheduler happens to arrange it;
        ``scattered=True`` says the caller already ran the scan-interior
        per-bucket reduce-scatter (``parallel.overlap``), and the
        re-replication then rides the per-bucket staircase.
        """
        pin = jax.lax.with_sharding_constraint
        params = state.params
        if zero1_active:
            with jax.named_scope(OPTIMIZER_REDUCE):
                if reduce_quant == "int8" and not scattered:
                    grads = jax.tree.map(
                        _q_reduce_scatter_leaf, grads, zero1_param_shardings,
                        state_shardings.params,
                    )
                else:
                    # ``scattered``: already reduce-scattered inside the
                    # scan; re-pinning the shard layout is free and keeps
                    # the update shard-local.
                    grads = jax.tree.map(pin, grads, zero1_param_shardings)
                params = jax.tree.map(pin, params, zero1_param_shardings)
        # ``TrainState.apply_gradients`` taken apart, so that its two halves
        # carry their names.  A pin is no instruction: the collective the
        # compiler puts there carries the name of the op whose result it
        # moves (the gather reads ``optimizer/apply``).
        with jax.named_scope(OPTIMIZER_UPDATE):
            updates, new_opt_state = state.tx.update(
                grads, state.opt_state, params
            )
        with jax.named_scope(OPTIMIZER_APPLY):
            new_params = optax.apply_updates(params, updates)
        if zero1_active:
            with jax.named_scope(OPTIMIZER_GATHER):
                if overlap_active:
                    new_params = _replicate_params(new_params)
                else:
                    new_params = jax.tree.map(
                        pin, new_params, state_shardings.params
                    )
        with jax.named_scope(OPTIMIZER_APPLY):
            step = state.step + 1
        return state.replace(
            step=step, params=new_params, opt_state=new_opt_state
        )

    def _family_update(state, new_state, stats):
        """After the optimizer: each router bias moved by the balancing
        rule on this step's own loads (no gradient reaches it)."""
        if not bias_rate:
            return new_state
        with jax.named_scope(ROUTER_BIAS):
            return new_state.replace(params=move_router_bias(
                state.params, new_state.params, stats[ROUTER_LOADS],
                bias_rate,
            ))

    def _grad_norm(grads):
        with jax.named_scope(GRAD_NORM):
            return optax.global_norm(grads)

    def _family_metrics(stats):
        """The step's metrics out of ``_forward_sums``' statistics: the
        layers' vectors as they are, the MTP module's cross-entropy as
        ``mtp_loss``; the routers' loads stay inside the step."""
        out = {
            k: v for k, v in stats.items()
            if k not in (ROUTER_LOADS, "mtp_ce_sum", "mtp_tokens")
        }
        if "mtp_ce_sum" in stats:
            out["mtp_loss"] = stats["mtp_ce_sum"] / stats["mtp_tokens"]
        return out

    def _train_step(state: TrainState, batch: Dict[str, jax.Array]):
        TRACE_COUNTS["train_step"] += 1

        def loss_fn(params):
            ce_sum, total_weight, aux, stats = _forward_sums(
                params, state.apply_fn, batch["inputs"], batch["targets"],
                batch["weights"],
            )
            with jax.named_scope(LOSS):
                ce = ce_sum / total_weight
                loss = ce + aux
                if mtp_weight:
                    loss = loss + mtp_weight * (
                        stats["mtp_ce_sum"] / stats["mtp_tokens"]
                    )
            return loss, (ce, aux, total_weight, stats)

        grads, (ce, aux, total_weight, stats) = jax.grad(
            loss_fn, has_aux=True
        )(state.params)
        if overlap_active:
            # Per-bucket reduce-scatter waves directly off the backward:
            # each leaf's scatter depends only on that leaf's gradient, so
            # late-layer buckets can ride the wire while early layers are
            # still back-propagating.
            grads = _scatter_grads(grads)
        new_state = _apply_update(state, grads, scattered=overlap_active)
        new_state = _family_update(state, new_state, stats)
        metrics = {
            "loss": ce,
            "aux_loss": aux,
            "tokens": total_weight,
            "grad_norm": _grad_norm(grads),
            "step": new_state.step,
        }
        metrics.update(_family_metrics(stats))
        return new_state, metrics

    def _accum_train_step(state: TrainState, batch: Dict[str, jax.Array]):
        """grad_accum > 1: scan the forward+backward over microbatches.

        The scan carry (the accum_dtype gradient accumulator + scalar loss
        sums) is donated between iterations by XLA's scan lowering, so the
        accumulator costs ONE params-sized buffer regardless of N; the
        sharding constraint pins each accumulator leaf to its param's
        layout so no iteration gathers it.
        """
        TRACE_COUNTS["train_step"] += 1
        micro = global_batch_size // grad_accum

        def to_micro(name):
            arr = batch[name]
            arr = arr.reshape(grad_accum, micro, *arr.shape[1:])
            return jax.lax.with_sharding_constraint(arr, micro_sharding)

        with jax.named_scope(GRAD_ACCUM):
            xs = {k: to_micro(k) for k in ("inputs", "targets", "weights")}
        # The GLOBAL token count: known before the scan (weights are an
        # input), it normalizes every microbatch's CE-sum gradient so the
        # accumulated total equals the full-batch mean-CE gradient exactly
        # — not a mean-of-means, which would drift whenever microbatches
        # carry unequal token counts.
        with jax.named_scope(LOSS):
            w_total = jnp.maximum(
                batch["weights"].astype(jnp.float32).sum(), 1.0
            )
            # the MTP module's own count: every position but a row's last
            mtp_total = jnp.maximum(
                batch["weights"][:, 1:].astype(jnp.float32).sum(), 1.0
            )

        def micro_loss(params, mb):
            ce_sum, _w, aux, stats = _forward_sums(
                params, state.apply_fn, mb["inputs"], mb["targets"],
                mb["weights"],
            )
            # aux (model-internal regularizers) is a per-microbatch mean:
            # average it over N so its gradient scale matches full-batch.
            with jax.named_scope(LOSS):
                loss = ce_sum / w_total + aux / grad_accum
                if mtp_weight:
                    loss = loss + (
                        mtp_weight * stats["mtp_ce_sum"] / mtp_total
                    )
            return loss, (ce_sum, aux, stats)

        params_shardings = state_shardings.params
        # Overlap: the accumulator lives in the 1/dp zero1 shard layout
        # and every microbatch reduce-scatters into it (linearity of the
        # reduce makes scatter-then-accumulate equal accumulate-then-
        # scatter) — the wire rides inside the scan, where the NEXT
        # microbatch's backward has no dependence on it and can hide it.
        accum_shardings = (
            zero1_param_shardings if overlap_active else params_shardings
        )

        def pin(tree):
            return jax.tree.map(
                jax.lax.with_sharding_constraint, tree, accum_shardings
            )

        with jax.named_scope(GRAD_ACCUM):
            grads0 = pin(jax.tree.map(
                lambda p: jnp.zeros(p.shape, accum_jdt), state.params
            ))

        def accum(carry, mb):
            gacc, ce_acc, aux_acc = carry
            g, (ce_sum, aux, stats) = jax.grad(
                micro_loss, has_aux=True
            )(state.params, mb)
            if overlap_active:
                g = _scatter_grads(g)
            with jax.named_scope(GRAD_ACCUM):
                gacc = pin(jax.tree.map(
                    lambda a, gi: a + gi.astype(a.dtype), gacc, g
                ))
            # The microbatches' layer statistics stack as the scan's
            # output (empty, and no output, for a model that sows none).
            return (gacc, ce_acc + ce_sum, aux_acc + aux), stats

        (grads, ce_sum, aux_sum), stats = jax.lax.scan(
            accum, (grads0, jnp.zeros((), jnp.float32),
                    jnp.zeros((), jnp.float32)), xs
        )
        if (
            reduce_quant == "int8"
            and "data" in mesh.axis_names
            and not zero1_active
        ):
            # Deferred once-per-step reduce on the int8 wire format.  Under
            # GSPMD the scanned grads are already globally summed, so over
            # the data axis this all-reduces data-replicated values: the
            # real quantized collective (and its rounding) runs in-program;
            # exact identity when data=1.
            from dlrover_tpu.parallel.quantized_collectives import (
                quantized_all_reduce,
            )
            from dlrover_tpu.runtime.mesh import shard_map_compat

            def q_reduce(leaf, sharding):
                fn = shard_map_compat(
                    lambda v: quantized_all_reduce(v, "data", mean=True),
                    mesh=mesh, in_specs=sharding.spec,
                    out_specs=sharding.spec,
                )
                return fn(leaf)

            with jax.named_scope(OPTIMIZER_REDUCE):
                grads = jax.tree.map(q_reduce, grads, params_shardings)
        # Hand the optimizer grads in the params' dtype (bf16 accumulation
        # is a wire/HBM format, not an update format).
        with jax.named_scope(GRAD_ACCUM):
            grads = jax.tree.map(
                lambda g, p: g.astype(p.dtype), grads, state.params
            )
        new_state = _apply_update(state, grads, scattered=overlap_active)
        if ROUTER_LOADS in stats:
            # the step's loads are the microbatches' together
            stats[ROUTER_LOADS] = jax.tree.map(
                lambda load: load.mean(axis=0), stats[ROUTER_LOADS]
            )
        new_state = _family_update(state, new_state, stats)
        with jax.named_scope(LOSS):
            loss, aux_loss = ce_sum / w_total, aux_sum / grad_accum
        metrics = {
            "loss": loss,
            "aux_loss": aux_loss,
            "tokens": w_total,
            "grad_norm": _grad_norm(grads),
            "step": new_state.step,
        }
        # the families' vectors of the microbatches, folded as the layers'
        metrics.update(_folded(families, stats.get))
        if "mtp_ce_sum" in stats:
            metrics["mtp_loss"] = stats["mtp_ce_sum"].sum() / mtp_total
        return new_state, metrics

    if grad_accum > 1:
        _train_step = _accum_train_step  # noqa: F811 - explicit dispatch

    def _wrap_with_rules(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with nn.logical_axis_rules(rules):
                return fn(*args, **kwargs)
        return wrapped

    def _eval_step(state: TrainState, batch: Dict[str, jax.Array]):
        """Forward-only CE (the fit-loop's eval half; no state mutation)."""
        TRACE_COUNTS["eval_step"] += 1
        if ce_chunks:
            hidden, aux = state.apply_fn(
                {"params": state.params}, batch["inputs"], return_hidden=True
            )
            with jax.named_scope(LOSS):
                ce, total_weight = chunked_cross_entropy_loss(
                    hidden, output_head(state.params), batch["targets"],
                    batch["weights"], num_chunks=ce_chunks,
                )
        else:
            logits, aux = state.apply_fn(
                {"params": state.params}, batch["inputs"]
            )
            with jax.named_scope(LOSS):
                ce, total_weight = cross_entropy_loss(
                    logits, batch["targets"], batch["weights"]
                )
        return {"loss": ce, "aux_loss": aux, "tokens": total_weight}

    init_jit = jax.jit(
        _wrap_with_rules(_init), out_shardings=init_shardings
    )
    if zero1_active:
        _init_base = init_jit

        def init_jit(rng):  # noqa: F811 - zero1 wrapper over the base init
            state = _init_base(rng)
            return state.replace(
                opt_state=jax.device_put(
                    state.opt_state, zero1_opt_shardings
                )
            )
    step_jit = jax.jit(
        _wrap_with_rules(_train_step),
        in_shardings=(state_shardings, batch_shardings),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,) if donate_state else (),
    )
    eval_jit = jax.jit(
        _wrap_with_rules(_eval_step),
        in_shardings=(state_shardings, batch_shardings),
    )

    token_aval = jax.ShapeDtypeStruct(
        (global_batch_size, seq_len), jnp.int32
    )
    train = ShardedTrain(
        mesh=mesh,
        rules=rules,
        state_shardings=state_shardings,
        batch_shardings=batch_shardings,
        init_fn=init_jit,
        step_fn=step_jit,
        eval_fn=eval_jit,
        grad_accum=grad_accum,
        accum_dtype=accum_dtype,
        reduce_quant=reduce_quant,
        zero1=zero1_active,
        zero1_stats=zero1_stats,
        overlap=overlap_active,
        overlap_bucket_mb=overlap_bucket_mb if overlap_active else 0.0,
        allgather_quant=allgather_quant if overlap_active else "none",
        overlap_plan=(
            overlap_plan.describe() if overlap_plan is not None else None
        ),
        apply_fn=model.apply,
        tx=optimizer,
        batch_avals={
            "inputs": token_aval,
            "targets": token_aval,
            "weights": jax.ShapeDtypeStruct(
                (global_batch_size, seq_len), jnp.float32
            ),
        },
    )
    if cache_key is not None:
        _BUILD_CACHE[cache_key] = train
    return train


def elastic_grad_accum(
    ref_accum: int,
    ref_world: int,
    world: int,
    global_batch_size: int,
    dp: int,
) -> int:
    """Rescale grad_accum for a resized world, tokens/step invariant.

    The global batch (hence tokens/step and the optimizer trajectory) is a
    property of the compiled program, not the world — what a resize DOES
    change is the per-device working set.  Scaling N by ``ref_world /
    world`` keeps each microbatch's per-device rows (so activation HBM)
    ~constant: half the chips, twice the microbatches, same step
    semantics.  The target is snapped to the nearest feasible N (one that
    keeps every microbatch divisible over the ``dp``-way batch sharding),
    preferring the next LARGER feasible N so the reference per-microbatch
    HBM budget is never exceeded.
    """
    world = max(1, world)
    ref_world = max(1, ref_world) or world
    target = max(1, int(round(ref_accum * ref_world / world)))
    per_shard = max(1, global_batch_size // max(1, dp))
    feasible = [
        n for n in range(1, per_shard + 1)
        if global_batch_size % (max(1, dp) * n) == 0
    ]
    if not feasible:
        return 1
    larger = [n for n in feasible if n >= target]
    return min(larger) if larger else max(feasible)


# Modeled share of each zero1 collective leg the overlap engine hides
# under compute (parallel/overlap.py: scan-interior reduce-scatter, per-
# bucket all-gather staircase).  Starting points for the phase model; the
# calibration ledger's *measured* overlap fraction corrects them online
# (auto/tune.py apply_calibration) and tools/overlap_bench.py certifies
# the real number from device intervals.
OVERLAP_HIDDEN_RS = 0.75
OVERLAP_HIDDEN_AG = 0.5


def microbatch_phase_plan(
    grad_accum: int,
    reduce_quant: str,
    step_seconds: float,
    zero1: bool = False,
    overlap: bool = False,
) -> list:
    """Modeled accumulate/reduce/update breakdown of one microbatched step.

    The phases live inside ONE compiled XLA program, so the host cannot
    time them individually; this apportions the measured step wall time by
    the same cost model ``auto/tune.py`` prices the knobs with (reduce ~8%
    of the step on the fp32 wire, ~3% on int8 — the EQuARX ~2.6x byte
    ratio; update ~4%; the rest accumulates, split evenly over the N
    microbatches).  Rows are dicts ``{"phase", "micro", "t0", "dur"}``
    with times relative to step start — consumed by the trainer's
    ``profile_every`` calibration (the modeled side of measured/modeled)
    and by ``tools/trace_steps.py``'s per-microbatch table.

    ``zero1=True`` replaces the replicated reduce/update tail with the
    sharded-update phases: ``reduce_scatter``
    (half the all-reduce wire — est_comm_time's RS leg, where the int8
    format applies), ``shard_update`` (1/dp of the optimizer FLOPs) and
    ``allgather`` (the updated params riding back, full precision).  The
    reduce_scatter overlaps the last microbatch's backward and the
    allgather overlaps the next step's host work in the compiled program;
    the modeled rows keep them sequential inside the measured span so the
    timeline stays additive.

    ``overlap=True`` (zero1 only) models the overlap engine's schedule:
    only the *exposed* remainder of each collective leg is booked as its
    phase row (``1 - OVERLAP_HIDDEN_RS`` of the reduce-scatter, ``1 -
    OVERLAP_HIDDEN_AG`` of the allgather) — the hidden share rides under
    the accumulate rows, so the timeline stays additive and measured
    step-time attributions do not double-count wire seconds that device
    traces show hidden under backward compute.
    """
    if zero1:
        rs_frac = 0.015 if reduce_quant == "int8" else 0.04
        update_frac = 0.015
        ag_frac = 0.04
        if overlap:
            rs_frac *= 1.0 - OVERLAP_HIDDEN_RS
            ag_frac *= 1.0 - OVERLAP_HIDDEN_AG
        accum_total = step_seconds * (
            1.0 - rs_frac - update_frac - ag_frac
        )
        per_micro = accum_total / max(1, grad_accum)
        rows = [
            {
                "phase": "accumulate", "micro": i,
                "t0": i * per_micro, "dur": per_micro,
            }
            for i in range(grad_accum)
        ]
        t = accum_total
        for phase, frac in (
            ("reduce_scatter", rs_frac),
            ("shard_update", update_frac),
            ("allgather", ag_frac),
        ):
            rows.append({
                "phase": phase, "micro": -1,
                "t0": t, "dur": step_seconds * frac,
            })
            t += step_seconds * frac
        return rows
    reduce_frac = 0.03 if reduce_quant == "int8" else 0.08
    update_frac = 0.04
    accum_total = step_seconds * (1.0 - reduce_frac - update_frac)
    per_micro = accum_total / max(1, grad_accum)
    rows = []
    for i in range(grad_accum):
        rows.append({
            "phase": "accumulate", "micro": i,
            "t0": i * per_micro, "dur": per_micro,
        })
    rows.append({
        "phase": "reduce", "micro": -1,
        "t0": accum_total, "dur": step_seconds * reduce_frac,
    })
    rows.append({
        "phase": "update", "micro": -1,
        "t0": step_seconds * (1.0 - update_frac),
        "dur": step_seconds * update_frac,
    })
    return rows


def shard_batch(
    batch: Dict[str, Any], train: ShardedTrain
) -> Dict[str, jax.Array]:
    """Place a host-local numpy batch onto the mesh with the right layout.

    Single-host: ``batch`` holds the full global batch.  Multi-host: each
    host passes its *local* slice (global_batch / process_count rows — e.g.
    the rows its own shard stream produced) and the global array is
    assembled from the per-process pieces; ``jax.device_put`` of per-host
    *different* values would fail its cross-process equality check.

    ``weights`` (per-token loss weights) defaults to all-ones when absent so
    the batch pytree always matches the step's in_shardings.

    ``jax.device_put`` dispatches the H2D copy asynchronously, so calling
    this one batch ahead of consumption (``data.loader.DevicePrefetcher``)
    overlaps the copy with the previous step's compute.  A batch that is
    already device-resident with the right sharding passes through
    untouched — the trainer can hand prefetched batches back through this
    function without a second copy (and without logging a second "place"
    event to the pipeline counters).
    """
    out = {}
    placed_any = False
    t0 = time.perf_counter()
    if "weights" not in batch:
        batch = dict(batch)
        batch["weights"] = jnp.ones(
            batch["targets"].shape, jnp.float32
        )
    multihost = jax.process_count() > 1
    for key, value in batch.items():
        sharding = train.batch_shardings.get(
            key, train.batch_shardings["inputs"]
        )
        if isinstance(value, jax.Array) and value.sharding == sharding:
            out[key] = value  # already placed (prefetched) — passthrough
            continue
        placed_any = True
        if multihost:
            import numpy as np

            out[key] = jax.make_array_from_process_local_data(
                sharding, np.asarray(value)
            )
        else:
            out[key] = jax.device_put(value, sharding)
    if placed_any:
        from dlrover_tpu.utils.profiler import pipeline_counters

        pipeline_counters().record_place(time.perf_counter() - t0)
    return out
