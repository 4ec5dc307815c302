"""Restart-fast compile: persistent XLA cache + in-process program reuse.

Every elastic restart pays retrace + compile for a program that is, by
construction, identical to the one the previous world ran whenever the
(config, mesh-shape) pair is unchanged — the dominant goodput tax the
Flash-Checkpoint story leaves on the table.  Two layers remove it:

1. **Persistent XLA compilation cache** (cross-process): a restarted
   process re-traces but skips the XLA compile.  One rule places it: where
   ``JAX_COMPILATION_CACHE_DIR`` is set, jax's own handling of that
   variable is left alone and nothing here names another directory;
   otherwise the cache is ``<checkout>/.jax_cache`` — a fixed path, because
   the path is part of every entry's key and a directory that moves never
   hits.
2. **In-process ShardedTrain memo** (same-process restarts — e.g. a
   trainer rebuilt after a resize back to a previously-seen mesh shape):
   ``train_cache_key`` names the compiled program by everything that
   shapes it; ``trainer.train_lib.build_sharded_train`` memoizes on it so
   the second construction performs ZERO retraces.

What is left of a start is booked where it happens: ``staged_compile``
splits a compilation into tracing, lowering and the cache read or XLA
compile (spans ``compile.trace``, ``compile.lower``, ``compile.backend``),
and the module's one ``jax.monitoring`` listener books every executable
the process builds as a timed ``jax.compile`` event under the span, step
or restart it fell in.
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Any, Dict, Optional

from dlrover_tpu.common import telemetry
from dlrover_tpu.common.log import default_logger as logger

# jax's own variable: read by jax at import, never written here.
ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

# Opt-in override for the CPU-backend gate in ``maybe_enable``: on the CPU
# backend, a process that *hits* cache entries another process wrote gets a
# corrupt deserialized executable — SIGSEGV/SIGABRT inside the runtime, or
# worse, silently garbage losses (observed: 3.2e30 then NaN grads).  Elastic
# restarts are exactly that cross-process replay, so auto-enabling the cache
# on CPU turns every resume into a crash loop.  Set to "1" only for
# single-run cache-plumbing tests.
ENV_COMPILE_CACHE_CPU_OK = "DLROVER_TPU_COMPILE_CACHE_CPU_OK"

_enabled_dir: Optional[str] = None

# What jax's monitoring says of the persistent cache: ``hits`` is an
# executable read back from it, ``misses`` one compiled and written (what a
# restarted trainer reports to prove it compiled nothing).
_CACHE_SAID = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_counts: collections.Counter = collections.Counter()
# jax calls a listener on the thread that compiles.  ``said``: what the
# cache said on this thread since the thread's last executable, which is
# the next one's; ``built``: the outcome of the last one.
_thread = threading.local()
_listening = False


def _on_monitoring(event: str, seconds: float = 0.0, fun_name: str = "", **_):
    """The one listener, for jax's events and its durations alike.  Every
    executable the process builds is one timed ``jax.compile`` event, with
    the ``parent`` and ``id`` of the span it fell in and what the
    persistent cache did for it: ``cache`` (``hit``: read back, ``miss``:
    compiled and written, ``off``: compiled and nothing kept, as without a
    cache) and, on a hit, jax's own ``retrieval_s``.  jax's tracing and
    lowering durations are not booked: inner ``jit``s nest and would count
    twice, ``staged_compile``'s spans are the exclusive reading."""
    if event in _CACHE_SAID:
        _counts[_CACHE_SAID[event]] += 1
        _thread.said = {"cache": _CACHE_SAID[event]}
    elif event == _RETRIEVAL:
        getattr(_thread, "said", {})["retrieval_s"] = round(seconds, 6)
    elif event == _BACKEND_COMPILE:
        _thread.built = _thread.__dict__.pop("said", None) or {"cache": "off"}
        if telemetry.recorder().enabled:
            telemetry.event(
                "jax.compile", duration_s=seconds,
                t_mono=time.monotonic() - seconds, fun_name=fun_name,
                seconds=round(seconds, 6), **_thread.built,
            )


def listen():
    """Register ``_on_monitoring`` with jax, once a process."""
    global _listening
    if _listening:
        return
    import jax

    jax.monitoring.register_event_listener(_on_monitoring)
    jax.monitoring.register_event_duration_secs_listener(_on_monitoring)
    _listening = True


def stats() -> Dict[str, int]:
    """``hits`` and ``misses`` of the persistent cache since ``listen()``."""
    return {"hits": _counts["hit"], "misses": _counts["miss"]}


@contextlib.contextmanager
def stage(name: str, parts: Dict[str, Any], **attrs):
    """One stage of a compilation: the span ``compile.<name>``, whose
    attributes it yields (to no effect with telemetry off), and its seconds
    under ``parts["<name>_s"]``."""
    t0 = time.monotonic()
    with telemetry.span(f"compile.{name}", **attrs) as span:
        try:
            yield attrs if span is None else span.attrs
        finally:
            parts[f"{name}_s"] = round(time.monotonic() - t0, 6)


def compile_traced(traced, parts: Dict[str, Any], **attrs):
    """Lower and compile a traced program (``jitted.trace(...)``) as the
    spans ``compile.lower`` and ``compile.backend``; the latter closes
    with the cache's outcome (``_on_monitoring``), which ``parts`` holds
    too."""
    listen()
    with stage("lower", parts, **attrs):
        lowered = traced.lower()
    with stage("backend", parts, **attrs) as found:
        _thread.built = None
        compiled = lowered.compile()
        outcome = _thread.built or {"cache": "off"}
        found.update(outcome)
        parts.update(outcome)
    return compiled


def staged_compile(jitted, *args):
    """``jitted.lower(*args).compile()`` where the work happens: tracing,
    lowering and the cache read or XLA compile are the spans
    ``compile.trace``, ``compile.lower`` and ``compile.backend``, each with
    ``fun_name``, children of whatever span is open on the thread."""
    parts: Dict[str, Any] = {}
    with stage("trace", parts, fun_name=jitted.__name__):
        traced = jitted.trace(*args)
    return compile_traced(traced, parts, fun_name=jitted.__name__)


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``: fixed, derived from where this package
    lies, ignored by git.  Also where the embedding store builds its
    native library."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def enable() -> str:
    """Turn jax's persistent compilation cache on and return its directory.

    Idempotent.  Thresholds are dropped to zero so that every program is
    cached, the small ones a restart would otherwise recompile included.
    """
    global _enabled_dir
    if _enabled_dir is not None:
        return _enabled_dir
    import jax

    cache_dir = os.environ.get(ENV_JAX_CACHE_DIR, "")
    if not cache_dir:
        cache_dir = default_cache_dir()
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    listen()
    _enabled_dir = cache_dir
    logger.info("persistent compilation cache enabled at %s", cache_dir)
    return cache_dir


def enabled_dir() -> Optional[str]:
    return _enabled_dir


def maybe_enable() -> Optional[str]:
    """``enable()`` unless the backend is the CPU.

    XLA's persisted CPU executables do not survive cross-process reuse
    (deserialization yields crashing or silently wrong programs), and an
    elastic restart is precisely a second process reading the first one's
    entries.  ``ENV_COMPILE_CACHE_CPU_OK=1`` overrides for single-process
    cache-plumbing tests; ``enable()`` itself stays ungated.  Initialises
    the backend: call it from the process that owns the chip.
    """
    import jax

    # whether or not the cache comes on: the CPU path never calls enable()
    listen()
    if (
        os.environ.get(ENV_COMPILE_CACHE_CPU_OK, "") != "1"
        and jax.default_backend() == "cpu"
    ):
        logger.info(
            "persistent compile cache disabled on the CPU backend "
            "(cross-process executable reuse is unsound there; set %s=1 "
            "to force)", ENV_COMPILE_CACHE_CPU_OK,
        )
        return None
    return enable()


def train_cache_key(
    model_config,
    mesh_shape,
    *,
    global_batch_size: int,
    seq_len: int,
    ce_chunks: int = 0,
    optimizer: str = "",
    grad_accum: int = 1,
    accum_dtype: str = "float32",
    reduce_quant: str = "none",
    zero1: bool = False,
    overlap: bool = False,
    overlap_bucket_mb: float = 0.0,
    allgather_quant: str = "none",
    donate_state: bool = True,
    logical_shape=(),
) -> str:
    """Name the compiled train program by everything that shapes it.

    Two trainers with equal keys compile byte-identical programs: the
    model config dataclass fields, the mesh axis sizes (shape, not device
    objects — a restart's fresh Mesh over the same devices must hit), the
    batch geometry, the optimizer recipe, and the microbatch-engine knobs
    (grad_accum reshapes the whole step program; accum_dtype/reduce_quant
    change the accumulator and reduce lowering; zero1 reshards the whole
    optimizer update; the overlap-engine knobs move the zero1 collectives
    into the scan and re-bucket the wave schedule — aliasing any of them
    would hand a resized world the wrong executable).  ``donate_state``
    flips input/output buffer aliasing of the whole step program, so a
    donating and a non-donating build may not share an executable either.

    ``logical_shape`` is the virtual mesh's resize-INVARIANT bit
    (``VirtualMesh.logical_shape``: the per-process mesh scaled by the
    fixed logical world).  It does not vary across resizes — that is the
    point: the program family a job compiles is named by its logical
    geometry, and a live resize only moves between grad_accum folds of
    the same family, every one of which can be prewarmed and hit.
    """
    fields = tuple(sorted(
        (k, repr(v)) for k, v in vars(model_config).items()
    ))
    return repr((
        type(model_config).__name__, fields, tuple(mesh_shape),
        global_batch_size, seq_len, ce_chunks, optimizer,
        grad_accum, accum_dtype, reduce_quant, zero1,
        overlap, float(overlap_bucket_mb), allgather_quant,
        donate_state, tuple(logical_shape),
    ))


def serve_cache_key(
    model_config,
    mesh_shape=(),
    *,
    slots: int,
    buckets,
    max_top_k: int = 0,
    attention_impl: str = "",
    tp=(),
    spec: int = 0,
) -> str:
    """Name the serving program set by everything that shapes it.

    The serving analogue of :func:`train_cache_key`: the model config,
    the mesh axis sizes, the slot-pool size (decode batch shape), the
    prefill bucket widths (one prefill program each), and the static
    top-k ceiling (the ``lax.top_k`` width baked into the sampler).
    Equal keys mean a rebuilt engine — an elastic replica restart, or a
    second engine in-process — can reuse traced programs and AOT
    executables wholesale.

    The config fields already ride the key via ``vars``, but three knobs
    are carried EXPLICITLY so aliasing bugs cannot creep back in through
    config normalization (``decode_config`` rewrites the config before
    the programs see it):

    * ``attention_impl`` — the impl the decode-mode twin actually runs
      (flash and XLA prefill lower differently; colliding them in the
      process-wide ``_PROGRAMS`` memo would hand a flash engine an XLA
      executable or vice versa);
    * ``tp`` — ``(logical_tp, physical_tp)`` of the serve TP fold.  The
      logical width names the program FAMILY (stable across fleet
      resizes, mirroring ``train_cache_key(logical_shape=...)``); the
      physical width names the concrete fold, so re-folding back to a
      previously-seen width is a memo hit — zero retrace;
    * ``spec`` — the speculative-decode γ (proposal length); the verify
      program's chunk width is ``γ+1`` and must not alias plain decode.
    """
    fields = tuple(sorted(
        (k, repr(v)) for k, v in vars(model_config).items()
    ))
    return repr((
        "serve", type(model_config).__name__, fields, tuple(mesh_shape),
        slots, tuple(buckets), max_top_k,
        attention_impl, tuple(tp), int(spec),
    ))
