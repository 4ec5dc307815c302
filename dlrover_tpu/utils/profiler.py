"""Step profiler: per-op / per-module device-time breakdown + MFU.

Capability ref: ATorch's ``AProfiler``
(``atorch/atorch/utils/prof.py:38-823`` — per-module FLOPs/duration tables,
``print_model_profile``, ``compute_gpu_utilization``) and its trace parsing
(``utils/parse_trace_json.py``).

TPU redesign: modules are not instrumented with hooks (under jit they do not
exist at runtime) — instead one profiled window is captured with
``jax.profiler`` and the xplane-derived Chrome trace is parsed back into a
table keyed by the op's HLO metadata path (``.../blocks/attn/...``), which
recovers the module structure from the compiled program.  This is exactly
the workflow that produced PROFILE.md, packaged as a library.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import gzip
import json
import os
import re
import tempfile
import threading
import time
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

import jax

from dlrover_tpu.common import telemetry as _telemetry


@dataclasses.dataclass
class OpProfile:
    name: str
    time_s: float
    count: int
    detail: str = ""

    @property
    def module(self) -> str:
        """Module-ish path recovered from HLO metadata in ``detail``."""
        m = re.search(r'op_name="[^"]*?((?:[\w.]+/)*[\w.]+)"', self.detail)
        if not m:
            return _classify(self.name)
        path = m.group(1)
        # strip transform prefixes: jit(_train_step)/jvp(Model)/while/body/..
        parts = [
            p for p in path.split("/")
            if not re.match(r"(jit|jvp|transpose|while|body|closed_call|"
                            r"checkpoint|remat\d*)\b", p)
            and "(" not in p
        ]
        return "/".join(parts[:3]) if parts else _classify(self.name)


def _classify(op_name: str) -> str:
    for key, label in (
        ("attn", "attention-kernel"),
        ("convolution", "matmul"),
        ("dot", "matmul"),
        ("dynamic-update-slice", "grad-accumulate"),
        ("all-reduce", "collective"),
        ("all-gather", "collective"),
        ("all-to-all", "collective"),
        ("collective", "collective"),
        ("copy", "copy"),
        ("fusion", "fusion"),
    ):
        if key in op_name:
            return label
    return "other"


@dataclasses.dataclass
class StepProfile:
    steps: int
    wall_s: float
    device_total_s: float
    ops: List[OpProfile]

    def per_step(self) -> float:
        return self.device_total_s / max(self.steps, 1)

    def by_module(self) -> Dict[str, float]:
        table: Dict[str, float] = collections.defaultdict(float)
        for op in self.ops:
            table[op.module] += op.time_s
        return dict(sorted(table.items(), key=lambda kv: -kv[1]))

    def mfu(self, flops_per_step: float, peak_flops: float) -> float:
        step_s = self.per_step()
        return flops_per_step / (peak_flops * step_s) if step_s else 0.0

    def table(self, top: int = 20) -> str:
        """Human-readable profile (the ``print_model_profile`` analogue)."""
        lines = [
            f"device time/step: {self.per_step():.4f}s "
            f"(wall {self.wall_s:.2f}s over {self.steps} steps)",
            f"{'s/step':>10}  {'share':>6}  {'n':>5}  op / module",
        ]
        step_total = max(self.per_step(), 1e-12)
        for op in sorted(self.ops, key=lambda o: -o.time_s)[:top]:
            per = op.time_s / self.steps
            lines.append(
                f"{per:10.4f}  {per / step_total:6.1%}  "
                f"{op.count:5d}  {op.name}  [{op.module}]"
            )
        lines.append("-- by module --")
        for module, t in list(self.by_module().items())[:top]:
            per = t / self.steps
            lines.append(f"{per:10.4f}  {per / step_total:6.1%}  {module}")
        return "\n".join(lines)


def parse_chrome_trace(path: str, steps: int, wall_s: float) -> StepProfile:
    """Aggregate device-lane op durations from a jax profiler trace."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    pid_names = {
        e["pid"]: str(e["args"].get("name", ""))
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
        and "args" in e
    }
    device_pids = {
        pid for pid, name in pid_names.items()
        if "TPU" in name or "GPU" in name or "/device:" in name
    }
    dur: Dict[str, float] = collections.Counter()
    cnt: Dict[str, int] = collections.Counter()
    detail: Dict[str, str] = {}
    total = 0.0
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        name = e["name"]
        # Skip the envelope rows (whole-program and while-loop spans) so the
        # leaf table sums to the device time once, not 3x.
        if name.startswith("jit_") or re.fullmatch(r"while\.\d+|\d+", name):
            continue
        d = float(e.get("dur", 0)) / 1e6
        dur[name] += d
        cnt[name] += 1
        total += d
        if name not in detail:
            args = e.get("args", {})
            detail[name] = str(
                args.get("long_name") or args.get("tf_op") or ""
            )
    ops = [
        OpProfile(name, dur[name], cnt[name], detail.get(name, ""))
        for name in dur
    ]
    return StepProfile(
        steps=steps, wall_s=wall_s, device_total_s=total, ops=ops
    )


def find_trace_file(trace_dir: str) -> Optional[str]:
    hits = sorted(
        glob.glob(
            os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
        )
        + glob.glob(
            os.path.join(trace_dir, "**", "*.trace.json"), recursive=True
        )
    )
    return hits[-1] if hits else None


def capture(
    step_fn: Callable,
    args: Sequence,
    steps: int = 3,
    trace_dir: Optional[str] = None,
    sync: Optional[Callable] = None,
) -> StepProfile:
    """Profile ``steps`` invocations of a compiled step function.

    ``step_fn(*args)`` should return something whose first leaf can be
    fetched to synchronize (or pass an explicit ``sync(out)``).  Warm up
    (compile) before calling this.
    """
    trace_dir = trace_dir or tempfile.mkdtemp(prefix="dlrover_prof_")
    out = step_fn(*args)
    _sync(out, sync)
    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = step_fn(*args)
    _sync(out, sync)
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = find_trace_file(trace_dir)
    if path is None:
        return StepProfile(steps=steps, wall_s=wall, device_total_s=0.0, ops=[])
    return parse_chrome_trace(path, steps, wall)


def _sync(out, sync):
    if sync is not None:
        sync(out)
        return
    jax.block_until_ready(out)


# ---------------------------------------------------------------------------
# Host-side step-pipeline accounting
# ---------------------------------------------------------------------------
#
# The trace-based profiler above sees the DEVICE lanes; what it cannot see is
# whether the dispatch thread stayed ahead of the device.  These counters
# record the three host-side event kinds the async step pipeline cares about:
#
#   "place"    — a batch's H2D device_put was issued (train_lib.shard_batch)
#   "dispatch" — host time spent enqueueing one train step
#   "block"    — a blocking device->host sync (metrics fetch, eval fetch)
#
# The pipelined trainer's contract — at most one blocking sync per
# ``metrics_lag`` steps, and batch N+1 placed before step N's metrics are
# fetched — is asserted straight off the ordered event list.


@dataclasses.dataclass
class PipelineEvent:
    kind: str                 # "place" | "dispatch" | "block"
    label: str                # e.g. "h2d", "step", "metrics", "metrics-flush"
    t: float                  # perf_counter at event start
    duration_s: float = 0.0
    steps: Tuple[int, ...] = ()   # step(s) the event is attributed to


class StepPipelineCounters:
    """Ordered host-event log + aggregate counters for the step pipeline.

    A "block" with label ``"metrics"`` is a per-step synchronous fetch (the
    pre-pipeline behavior); label ``"metrics-flush"`` is the ring's batched
    fetch covering ``steps``.  ``sync_block_count`` therefore must read 0 in
    pipelined mode — the tier-1 assertion ``tools/trace_steps.py`` wraps.

    The event log is a window (the newest ``EVENT_WINDOW`` events, thousands
    of steps); the totals in ``summary()`` are counters kept for the life
    of the job.
    """

    EVENT_WINDOW = 65536

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        with getattr(self, "_lock", threading.Lock()):
            self._events: Deque[PipelineEvent] = collections.deque(
                maxlen=self.EVENT_WINDOW
            )
            self._block_counts: collections.Counter = collections.Counter()
            self.host_block_count = 0
            self.host_blocked_s = 0.0
            self.place_count = 0
            self.dispatch_count = 0
            self.dispatch_s = 0.0
            # Telemetry-ring overflow: events the bounded ring discarded
            # before a ship drained it (lifetime tally; the per-window
            # count also rides the wire to the master's
            # dlrover_telemetry_dropped_total gauge).
            self.dropped_events = 0

    @property
    def events(self) -> List[PipelineEvent]:
        """The event window, oldest first."""
        with self._lock:
            return list(self._events)

    @contextlib.contextmanager
    def host_block(self, label: str, steps: Sequence[int] = ()):
        t0 = time.perf_counter()
        # Host blocks are the pipeline's stalls — fold them into the job
        # timeline (and a profiler trace) so metrics-flush/eval-fetch
        # slices sit inside the span that waited for them.
        with _telemetry.span(label, kind="block", steps=tuple(steps)):
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                with self._lock:
                    self.host_block_count += 1
                    self.host_blocked_s += dt
                    self._block_counts[label] += 1
                    self._events.append(
                        PipelineEvent("block", label, t0, dt, tuple(steps))
                    )

    def record_place(self, duration_s: float = 0.0, label: str = "h2d"):
        with self._lock:
            index = self.place_count
            self.place_count += 1
            self._events.append(
                PipelineEvent("place", label, time.perf_counter(),
                              duration_s, (index,))
            )
        if duration_s > 0.0:
            _telemetry.event(label, duration_s=duration_s, kind="place",
                             batch=index)

    def record_dropped(self, count: int):
        if count <= 0:
            return
        with self._lock:
            self.dropped_events += count

    def record_dispatch(self, step: int, duration_s: float):
        with self._lock:
            self.dispatch_count += 1
            self.dispatch_s += duration_s
            self._events.append(
                PipelineEvent("dispatch", "step", time.perf_counter(),
                              duration_s, (step,))
            )

    # -- queries ------------------------------------------------------------

    def blocks(self, label: Optional[str] = None) -> List[PipelineEvent]:
        with self._lock:
            return [
                e for e in self._events
                if e.kind == "block" and (label is None or e.label == label)
            ]

    def sync_block_count(self) -> int:
        """Per-step synchronous fetches (the blocks pipelining eliminates)."""
        return len(self.blocks("metrics"))

    def sync_blocks_for_step(self, step: int) -> int:
        return sum(1 for e in self.blocks("metrics") if step in e.steps)

    def per_step_table(self) -> List[Dict]:
        """One row per dispatched step: host dispatch time vs attributed
        blocking time — the timeline ``tools/trace_steps.py`` dumps."""
        with self._lock:
            events = list(self._events)
        rows: Dict[int, Dict] = {}
        for e in events:
            if e.kind == "dispatch":
                row = rows.setdefault(e.steps[0], {
                    "step": e.steps[0], "dispatch_s": 0.0,
                    "blocked_s": 0.0, "sync_blocks": 0,
                })
                row["dispatch_s"] += e.duration_s
        for e in events:
            if e.kind != "block" or not e.steps:
                continue
            share = e.duration_s / len(e.steps)
            for step in e.steps:
                if step in rows:
                    rows[step]["blocked_s"] += share
                    if e.label == "metrics":
                        rows[step]["sync_blocks"] += 1
        return [rows[s] for s in sorted(rows)]

    def summary(self) -> Dict:
        with self._lock:
            return {
                "host_block_count": self.host_block_count,
                "host_blocked_s": self.host_blocked_s,
                "sync_block_count": self._block_counts["metrics"],
                "flush_block_count": self._block_counts["metrics-flush"],
                "place_count": self.place_count,
                "dispatch_count": self.dispatch_count,
                "dispatch_s": self.dispatch_s,
                "dropped_events": self.dropped_events,
            }


_PIPELINE_COUNTERS = StepPipelineCounters()


def pipeline_counters() -> StepPipelineCounters:
    """The process-wide step-pipeline counter instance."""
    return _PIPELINE_COUNTERS
