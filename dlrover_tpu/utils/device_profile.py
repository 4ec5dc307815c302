"""Measured device-time phase attribution for the step loop.

Everything the trainer's timeline emitted before this module was
**modeled**: ``train_lib.microbatch_phase_plan`` apportions the measured
step wall time by the same cost model ``auto/tune.py`` prices knobs with,
and stamps every row ``source="modeled"``.  This module closes the loop
with *measured* truth: every ``profile_every`` steps the trainer captures
one ``jax.profiler.trace`` window around a single step, this module parses
the Chrome-trace JSON the profiler writes (pure stdlib — no tensorboard
dependency) into per-phase **device** durations plus a compute-vs-
collective overlap fraction, and the trainer emits them as
``source="measured"`` rows (``src="device"``, so the Perfetto export grows
one extra device track per node) inside the same step span the modeled
rows live in.

The measured/modeled pairing also yields one ``"calibration"`` wire event
per captured window — per phase *kind* (compute/collective) measured and
modeled seconds keyed by the step program's cache key — which the master's
servicer routes into :class:`dlrover_tpu.master.calibration.CalibrationLedger`
and ``auto/tune.py`` reads back to measurement-correct its ``est_*``
ranking.

Capture discipline: the profiler window costs one host<->device sync per
captured step (the window must close after the device finished) plus the
trace write + parse — amortized to ~zero at sane cadences
(``profile_every >= 50``).  With ``profile_every == 0`` (the default)
nothing here is ever constructed and the step path allocates nothing.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
import shutil
import tempfile
from typing import Any, Dict, List, Optional, Sequence, Tuple

from dlrover_tpu.common import telemetry
from dlrover_tpu.common.log import default_logger as logger

# Modeled phase name (microbatch_phase_plan rows) -> phase kind.  The
# measured side classifies device ops into the same two kinds, so the
# calibration ratio compares like with like.
PHASE_KINDS: Dict[str, str] = {
    "accumulate": "compute",
    "update": "compute",
    "shard_update": "compute",
    "reduce": "collective",
    "reduce_scatter": "collective",
    "allgather": "collective",
}

#: Substrings that mark a device op as collective traffic (the same table
#: ``utils/profiler._classify`` routes through "collective").
_COLLECTIVE_KEYS = (
    "all-reduce", "all-gather", "all-to-all", "reduce-scatter",
    "collective", "psum", "ppermute",
)

#: HLO-ish op row: lowercase, digits, ``._-`` — matches ``dot.4``,
#: ``broadcast_add_fusion``, ``reduce-window``, ``all-reduce.3``; rejects
#: host scaffolding (``PjitFunction(f)``, ``$profiler.py:91 start_trace``,
#: ``TfrtCpuExecutable::Execute``).
_HLO_NAME = re.compile(r"^[a-z][a-z0-9._-]*$")

def _is_collective(op_name: str) -> bool:
    return any(key in op_name for key in _COLLECTIVE_KEYS)


def _collective_leg(op_name: str) -> Optional[str]:
    """The collective *leg* an op belongs to (first matching key,
    normalized to a metric-safe name) — e.g. ``all-reduce.3`` ->
    ``all_reduce``.  None for non-collective ops."""
    for key in _COLLECTIVE_KEYS:
        if key in op_name:
            return key.replace("-", "_")
    return None


def _is_device_op(name: str) -> bool:
    # The program's own spans (``dlrover:<name>``): host rows, never ops.
    if name.startswith(telemetry.TRACE_PREFIX):
        return False
    # Envelope rows (whole-program / while-loop spans) would double-count
    # the leaves; bare integers are XLA's anonymous envelope ids.
    if name.startswith("jit_") or re.fullmatch(r"while\.\d+|\d+", name):
        return False
    return bool(_HLO_NAME.match(name))


@dataclasses.dataclass
class DeviceWindow:
    """One parsed capture window: per-kind device seconds + overlap."""

    #: phase kind -> device seconds summed over the window's ops.
    phases: Dict[str, float]
    #: Fraction of collective device time that ran concurrently with
    #: compute (0.0 = fully exposed, 1.0 = fully hidden).
    overlap_fraction: float
    #: Total device op seconds in the window.
    device_total_s: float
    #: Device op rows counted (diagnostic).
    op_count: int = 0
    #: Per-collective-leg attribution: leg name (``all_reduce``,
    #: ``all_gather``, ``reduce_scatter``, ...) -> (device seconds,
    #: overlap fraction vs compute).  What the overlap bench books as the
    #: per-leg exposed-vs-hidden table.
    legs: Dict[str, Tuple[float, float]] = dataclasses.field(
        default_factory=dict
    )

    def seconds(self, kind: str) -> float:
        return self.phases.get(kind, 0.0)


def _merge_intervals(
    intervals: List[Tuple[float, float]],
) -> List[Tuple[float, float]]:
    if not intervals:
        return []
    intervals = sorted(intervals)
    out = [intervals[0]]
    for start, end in intervals[1:]:
        if start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def overlap_seconds(
    compute: List[Tuple[float, float]],
    collective: List[Tuple[float, float]],
) -> float:
    """Wall seconds where merged compute and collective intervals
    coincide — the numerator of the overlap fraction."""
    a, b = _merge_intervals(compute), _merge_intervals(collective)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def parse_device_trace(path: str) -> Optional[DeviceWindow]:
    """Parse one profiler-written Chrome trace into a :class:`DeviceWindow`.

    Pure stdlib (gzip + json), no tensorboard/xplane dependency.  Device
    lanes are the pids whose ``process_name`` metadata names a real
    accelerator (``TPU``/``GPU``/``/device:``); a CPU run has none, so the
    parser falls back to the ``/host:CPU`` plane where XLA:CPU books its op
    rows, filtered to HLO-shaped names so host scaffolding
    (``PjitFunction``, profiler internals, our own ``dlrover:*``
    span rows) never counts as device time.

    Returns ``None`` when the trace is unreadable or holds no device ops —
    the degrade-to-no-rows contract: a malformed window must cost the step
    loop nothing but the capture it already paid.
    """
    try:
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            data = json.load(f)
        events = data.get("traceEvents", [])
        if not isinstance(events, list):
            return None
    except (OSError, ValueError, EOFError) as e:
        logger.warning("device trace %s unparseable: %s", path, e)
        return None
    pid_names: Dict[Any, str] = {}
    for e in events:
        if (
            isinstance(e, dict) and e.get("ph") == "M"
            and e.get("name") == "process_name" and "args" in e
        ):
            pid_names[e.get("pid")] = str(e["args"].get("name", ""))
    device_pids = {
        pid for pid, name in pid_names.items()
        if "TPU" in name or "GPU" in name or "/device:" in name
    }
    if not device_pids:
        # XLA:CPU runs its ops inline on the host plane.
        device_pids = {
            pid for pid, name in pid_names.items() if "CPU" in name
        }
    phases: Dict[str, float] = {}
    compute_iv: List[Tuple[float, float]] = []
    collective_iv: List[Tuple[float, float]] = []
    leg_iv: Dict[str, List[Tuple[float, float]]] = {}
    leg_s: Dict[str, float] = {}
    total = 0.0
    ops = 0
    for e in events:
        if not isinstance(e, dict) or e.get("ph") != "X":
            continue
        if e.get("pid") not in device_pids:
            continue
        name = str(e.get("name", ""))
        if not _is_device_op(name):
            continue
        try:
            t0 = float(e.get("ts", 0.0)) / 1e6
            dur = float(e.get("dur", 0.0)) / 1e6
        except (TypeError, ValueError):
            continue
        if dur <= 0.0:
            continue
        leg = _collective_leg(name)
        kind = "collective" if leg else "compute"
        phases[kind] = phases.get(kind, 0.0) + dur
        if leg:
            collective_iv.append((t0, t0 + dur))
            leg_iv.setdefault(leg, []).append((t0, t0 + dur))
            leg_s[leg] = leg_s.get(leg, 0.0) + dur
        else:
            compute_iv.append((t0, t0 + dur))
        total += dur
        ops += 1
    if not ops:
        return None
    coll_total = phases.get("collective", 0.0)
    overlap = (
        overlap_seconds(compute_iv, collective_iv) / coll_total
        if coll_total > 0.0 else 0.0
    )
    legs = {
        leg: (
            leg_s[leg],
            min(1.0, overlap_seconds(compute_iv, ivs) / leg_s[leg]),
        )
        for leg, ivs in leg_iv.items()
        if leg_s[leg] > 0.0
    }
    return DeviceWindow(
        phases=phases,
        overlap_fraction=min(1.0, overlap),
        device_total_s=total,
        op_count=ops,
        legs=legs,
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


def modeled_kind_seconds(rows: Sequence[Dict[str, Any]]) -> Dict[str, float]:
    """Sum ``microbatch_phase_plan`` rows into per-phase-kind seconds."""
    out: Dict[str, float] = {}
    for row in rows:
        kind = PHASE_KINDS.get(str(row.get("phase", "")))
        if kind is None:
            continue
        out[kind] = out.get(kind, 0.0) + float(row.get("dur", 0.0))
    return out


class DeviceProfiler:
    """Cadenced ``jax.profiler`` capture windows around single steps.

    The trainer owns one instance when ``profile_every > 0`` and drives
    it from ``train_step``: :meth:`arm` starts a trace window when the
    step hits the cadence (returns whether it did), :meth:`finish` closes
    the window after the step's device work completed and hands back the
    parsed :class:`DeviceWindow` (or ``None`` on any failure — capture is
    strictly best-effort and must never take a step down with it).
    """

    def __init__(self, profile_every: int, trace_dir: str = ""):
        self.profile_every = max(0, int(profile_every))
        self._trace_root = trace_dir
        self._window_dir: Optional[str] = None
        self.windows = 0          # capture windows successfully parsed
        self.failed_windows = 0   # started but unparseable/failed windows
        self._disabled = False    # latched on a start_trace failure

    def wants(self, step: int) -> bool:
        return (
            not self._disabled
            and self.profile_every > 0
            and step % self.profile_every == 0
        )

    def arm(self, step: int) -> bool:
        """Open a trace window for ``step`` if the cadence says so."""
        if not self.wants(step):
            return False
        import jax

        trace_dir = tempfile.mkdtemp(
            prefix=f"dlrover_devprof_{step}_", dir=self._trace_root or None
        )
        try:
            jax.profiler.start_trace(trace_dir)
        except Exception as e:  # noqa: BLE001 - profiler backend missing
            # One loud latch, not one warning per cadence hit: a backend
            # that cannot trace today will not trace on the next window.
            logger.warning(
                "device profiler unavailable (%s); disabling capture", e
            )
            self._disabled = True
            shutil.rmtree(trace_dir, ignore_errors=True)
            return False
        self._window_dir = trace_dir
        return True

    def finish(self) -> Optional[DeviceWindow]:
        """Close the open window; parse it.  The caller must have blocked
        on the step's outputs first (the window only holds what the device
        finished before ``stop_trace``)."""
        if self._window_dir is None:
            return None
        trace_dir, self._window_dir = self._window_dir, None
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 - never fail the step
            logger.warning("device profiler stop failed: %s", e)
            self.failed_windows += 1
            shutil.rmtree(trace_dir, ignore_errors=True)
            return None
        try:
            path = find_trace_file(trace_dir)
            window = parse_device_trace(path) if path else None
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if window is None:
            self.failed_windows += 1
        else:
            self.windows += 1
        return window


def emit_measured_phases(
    window: DeviceWindow,
    *,
    step: int,
    t_span: float,
    wall_s: float,
    modeled_rows: Sequence[Dict[str, Any]],
    cache_key: str = "",
) -> int:
    """Book one capture window into the telemetry plane.

    Emits (a) one ``source="measured"`` phase row per phase kind the
    window observed — ``src="device"`` so ``events_to_chrome_trace``
    renders them on their own per-node device track, backdated via
    ``t_mono`` inside the measured step span — and (b) one
    ``"calibration"`` event carrying flat measured/modeled per-kind
    seconds for the master's :class:`CalibrationLedger`.  Returns the
    number of measured rows emitted (0 when the recorder is disabled).
    """
    if not telemetry.recorder().enabled:
        return 0
    modeled = modeled_kind_seconds(modeled_rows)
    rows = 0
    # Sequential layout inside the step span: compute first, collective
    # after — the real lanes overlap (that is what overlap_fraction
    # reports), but additive placement keeps the device track readable
    # (the modeled phase plan makes the same presentation choice).
    t = t_span
    for kind in ("compute", "collective"):
        seconds = window.seconds(kind)
        if seconds <= 0.0:
            continue
        telemetry.event(
            kind, duration_s=seconds, t_mono=t, step=step,
            source="measured", src="device",
            overlap=round(window.overlap_fraction, 4),
        )
        t += seconds
        rows += 1
    attrs: Dict[str, Any] = {
        "step": step,
        "cache_key": cache_key or "uncacheable",
        "overlap": round(window.overlap_fraction, 4),
        "wall_s": round(wall_s, 6),
        "device_total_s": round(window.device_total_s, 6),
    }
    for kind in ("compute", "collective"):
        attrs[f"measured_{kind}"] = round(window.seconds(kind), 6)
        attrs[f"modeled_{kind}"] = round(modeled.get(kind, 0.0), 6)
    # Per-leg split of the collective seconds (flat attrs — the wire
    # format is flat floats): which collective hid and which was exposed.
    for leg, (seconds, frac) in sorted(window.legs.items()):
        attrs[f"leg_{leg}_s"] = round(seconds, 6)
        attrs[f"leg_{leg}_overlap"] = round(frac, 4)
    telemetry.event("calibration", **attrs)
    return rows
