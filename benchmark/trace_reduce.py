"""From a profiler trace to numbers: the benchmark's own reduction.

``extract`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
a small plain structure (lists of ``[name, scope, start_ns, dur_ns]``);
``reduce`` and the helpers below work on that structure alone, so that the
arithmetic can be checked by hand on a recorded sample
(``tests/benchmark_suite``).

The structure::

    {"devices": {"<plane name>": {"ops": [[name, scope, start, dur], ...],
                                  "modules": [[name, "", start, dur], ...]}},
     "host": [[name, "", start, dur], ...]}      # the bench:<phase> rows

* ``ops`` are the events of the device plane's "XLA Ops" line: ``name`` is
  the HLO instruction, ``scope`` the framework path that made it (its
  ``named_scope``s).  Container instructions (``while``, ``conditional``)
  enclose their children on the same line, so an instruction's own time is
  its duration less its children's (``self_times``).
* busy time is the union of all op intervals: an instruction is running.
* a *step* is one execution of the step program on the "XLA Modules" line.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Any, Dict, Iterable, List, Sequence, Tuple

Row = Sequence  # [name, scope, start_ns, dur_ns]

COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
)
CONTAINER = re.compile(r"^(while|conditional|call)([.\d]*)$")
HOST_PREFIX = "bench:"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


HLO_NAME = re.compile(r"^%?([^\s=]+)")


def _op_name(text: str) -> str:
    """``fusion.12`` out of ``%fusion.12 = bf16[...] fusion(...)``."""
    return HLO_NAME.match(text).group(1) if text else text


HLO_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name=\"([^\"]*)\""
)


def scopes_from_hlo(text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` out of a compiled program's text: the
    framework path (``named_scope``s, module names) of every instruction
    that carries one.  The TPU's trace names instructions only."""
    out = {}
    for line in text.splitlines():
        m = HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def extract(path: str, scope_of: Dict[str, str] = None) -> Dict[str, Any]:
    """``scope_of`` maps an instruction's name to its framework path, for
    traces (the TPU's) whose events do not carry one."""
    import jax

    scope_of = scope_of or {}

    data = jax.profiler.ProfileData.from_file(path)
    out: Dict[str, Any] = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] = [
                        [_op_name(e.name),
                         scope_of.get(_op_name(e.name), ""),
                         e.start_ns, e.duration_ns]
                        for e in line.events
                    ]
                elif line.name == "XLA Modules":
                    dev["modules"] = [
                        [e.name, "", e.start_ns, e.duration_ns]
                        for e in line.events
                    ]
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name[len(HOST_PREFIX):], "", e.start_ns, e.duration_ns]
                    for e in line.events if e.name.startswith(HOST_PREFIX)
                )
    return out


# -- interval arithmetic --------------------------------------------------------


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Merged, sorted ``[start, end]`` intervals."""
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def length(merged: Iterable[Sequence[float]]) -> float:
    return sum(end - start for start, end in merged)


def clip(merged, lo: float, hi: float) -> List[List[float]]:
    return [
        [max(s, lo), min(e, hi)] for s, e in merged if e > lo and s < hi
    ]


def gaps(merged, lo: float, hi: float) -> List[List[float]]:
    """The parts of ``[lo, hi]`` that ``merged`` does not cover."""
    out, at = [], lo
    for start, end in clip(merged, lo, hi):
        if start > at:
            out.append([at, start])
        at = max(at, end)
    if hi > at:
        out.append([at, hi])
    return out


def intervals(rows: Iterable[Row]) -> List[Tuple[float, float]]:
    return [(r[2], r[2] + r[3]) for r in rows]


def self_times(rows: Sequence[Row]) -> List[float]:
    """Each row's duration less the rows it encloses (one line, nested)."""
    order = sorted(range(len(rows)), key=lambda i: (rows[i][2], -rows[i][3]))
    own = [float(r[3]) for r in rows]
    stack: List[int] = []
    for i in order:
        start, end = rows[i][2], rows[i][2] + rows[i][3]
        while stack and rows[stack[-1]][2] + rows[stack[-1]][3] <= start:
            stack.pop()
        if stack and end <= rows[stack[-1]][2] + rows[stack[-1]][3]:
            own[stack[-1]] -= rows[i][3]
        stack.append(i)
    return own


def short_scope(scope: str, parts: int = 3) -> str:
    """The last ``parts`` components of a framework path."""
    return "/".join([p for p in scope.split("/") if p][-parts:])


def label(row: Row) -> str:
    scope = short_scope(row[1])
    return f"{row[0]}@{scope}" if scope else str(row[0])


# -- the reduction --------------------------------------------------------------


def step_rows(modules: Sequence[Row], pattern: str) -> List[Row]:
    """Executions of the step program: module rows whose name matches
    ``pattern`` (a regular expression; empty: the module with most time)."""
    if pattern:
        rx = re.compile(pattern)
        return sorted((m for m in modules if rx.search(m[0])),
                      key=lambda m: m[2])
    by_name: Dict[str, float] = {}
    for m in modules:
        by_name[m[0]] = by_name.get(m[0], 0.0) + m[3]
    if not by_name:
        return []
    top = max(by_name, key=by_name.get)
    return sorted((m for m in modules if m[0] == top), key=lambda m: m[2])


def attribute_gaps(
    idle: Sequence[Sequence[float]], host: Sequence[Row]
) -> Dict[str, float]:
    """Seconds of device idleness by the host phase they fall in; what no
    phase covers is ``host_other``."""
    out: Dict[str, float] = {}
    for lo, hi in idle:
        left = hi - lo
        for name, _, start, dur in host:
            overlap = min(hi, start + dur) - max(lo, start)
            if overlap > 0:
                out[name] = out.get(name, 0.0) + overlap * 1e-9
                left -= overlap
        if left > 0:
            out["host_other"] = out.get("host_other", 0.0) + left * 1e-9
    return out


def scope_seconds(ops: Sequence[Row], pattern: str) -> float:
    """Own seconds of the ops whose ``name@scope`` matches ``pattern``."""
    rx = re.compile(pattern)
    own = self_times(ops)
    return sum(
        t for row, t in zip(ops, own) if rx.search(f"{row[0]}@{row[1]}")
    ) * 1e-9


def exposed_collective_seconds(ops: Sequence[Row]) -> float:
    """Seconds in which a collective runs on this chip and no other
    instruction does (containers aside)."""
    coll = union(
        (r[2], r[2] + r[3]) for r in ops if COLLECTIVE.search(r[0])
    )
    other = union(
        (r[2], r[2] + r[3]) for r in ops
        if not COLLECTIVE.search(r[0]) and not CONTAINER.match(r[0])
    )
    hidden = sum(
        length(clip(other, start, end)) for start, end in coll
    )
    return (length(coll) - hidden) * 1e-9


def reduce(extracted: Dict[str, Any], step_pattern: str = "") -> Dict[str, Any]:
    """Everything the readers and the result line take from one trace.

    The traced window is the span from the first to the last device op of
    any chip.  Per-step numbers are medians over the steps that lie whole
    inside it, on each chip, then averaged over the chips."""
    devices = extracted["devices"]
    host = extracted["host"]
    if not devices or not any(d["ops"] for d in devices.values()):
        return {
            "chips": len(devices), "busy_s": 0.0, "window_s": 0.0,
            "steps": 0, "step_device_ms": None, "host_step_gap_ms": None,
            "device_ops": [], "idle_gaps": [],
        }
    lo = min(r[2] for d in devices.values() for r in d["ops"])
    hi = max(r[2] + r[3] for d in devices.values() for r in d["ops"])
    busy, step_device, step_gap, n_steps = [], [], [], []
    op_time: Dict[str, float] = {}
    idle_by_phase: Dict[str, float] = {}
    for dev in devices.values():
        ops = dev["ops"]
        merged = union(intervals(ops))
        busy.append(length(merged) * 1e-9)
        for name, secs in attribute_gaps(gaps(merged, lo, hi), host).items():
            idle_by_phase[name] = idle_by_phase.get(name, 0.0) + secs
        for row, own in zip(ops, self_times(ops)):
            if not CONTAINER.match(row[0]):
                op_time[label(row)] = op_time.get(label(row), 0.0) + own * 1e-9
        steps = step_rows(dev["modules"], step_pattern)
        n_steps.append(len(steps))
        if steps:
            step_device.append(statistics.median(
                length(clip(merged, s[2], s[2] + s[3])) * 1e-6 for s in steps
            ))
        if len(steps) > 1:
            step_gap.append(statistics.median(
                (b[2] - (a[2] + a[3])) * 1e-6 for a, b in zip(steps, steps[1:])
            ))
    chips = len(devices)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(idle_by_phase.items(), key=lambda kv: -kv[1])[:10]
    return {
        "chips": chips,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / chips,
        "steps": min(n_steps) if n_steps else 0,
        "step_device_ms": (
            sum(step_device) / len(step_device) if step_device else None
        ),
        "host_step_gap_ms": (
            sum(step_gap) / len(step_gap) if step_gap else None
        ),
        "device_ops": [[k, v / chips] for k, v in top],
        "idle_gaps": [[k, v / chips] for k, v in idle],
    }


def per_step(extracted: Dict[str, Any], step_pattern: str, fn) -> List[float]:
    """``fn(ops_of_one_step)`` for every whole step of every chip."""
    out = []
    for dev in extracted["devices"].values():
        for s in step_rows(dev["modules"], step_pattern):
            lo, hi = s[2], s[2] + s[3]
            out.append(fn([
                r for r in dev["ops"] if r[2] >= lo and r[2] + r[3] <= hi
            ]))
    return out
