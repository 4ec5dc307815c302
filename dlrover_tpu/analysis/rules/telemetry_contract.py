"""TEL001: the telemetry plane's emit -> route -> render contract.

Telemetry here is a three-stage pipeline with three different owners:
workers emit events (``telemetry.event(...)``), the master servicer
routes them by name into ``SpeedMonitor`` ledgers or timeline counters
(``_report_telemetry`` / ``add_events``), and ``render_metrics`` exposes
the result as ``dlrover_*`` Prometheus gauges with HELP/TYPE.  Each
stage evolves separately, so the contract rots silently: an event kind
added worker-side lands in the timeline ring and nowhere else, a routed
kind whose emitter was deleted keeps its dead branch forever, a counter
bumped master-side never gets a gauge, and a renamed ``record_*`` method
turns the route into an ``AttributeError`` at job runtime.

Checks (all project-scope; each needs symbols from several modules):

* **unrouted instant event** — an *instant* ``telemetry.event("kind")``
  (no ``duration_s``/``t_mono``: pure occurrence, invisible on traces)
  whose literal kind has no route in any ``_report_telemetry`` /
  ``add_events``.  Timed events/spans are trace phases and exempt.
* **dead route** — a routed kind literal nothing in the tree emits.
* **gauge without HELP/TYPE** — a ``gauge("name", v)`` call in
  ``render_metrics`` with no help text and no explicit ``# HELP name``
  literal nearby.
* **orphan counter** — a ``timeline.bump("name")`` (or a routing-table
  value) with no rendered ``dlrover_<name>_total`` gauge.
* **SpeedMonitor surface drift** — a ``*.speed_monitor.m(...)`` call
  whose method ``m`` the ``SpeedMonitor`` class does not define, and
  conversely a ``SpeedMonitor.record_*`` method nothing calls.

Routing detection keys on the repo convention that routing functions
compare a variable literally named ``name`` against string constants
(``name == "fault"``, ``name in KINDS``, ``KINDS`` a module-level
dict/set literal of the routing module or of the module it imports the
name from).  When the tree has no routing function at all (single
-file lints, fixtures), the emit-side checks stay silent rather than
flagging every event in sight.

A model family books its event under the name its declaration carries
(``FAMILY = Family(event="ssm", ...)``, ``models/family.py``; the trainer
emits ``telemetry.event(family.event, ...)``, a kind no literal at the call
says), so a module-level ``Family(event="<kind>")`` counts as the emitter
of that instant kind.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from dlrover_tpu.analysis import jaxast
from dlrover_tpu.analysis.core import Finding, ProjectRule, register
from dlrover_tpu.analysis.project import ModuleInfo, ProjectContext

ROUTING_FUNCTIONS = {"_report_telemetry", "add_events"}
RENDER_FUNCTIONS = {"render_metrics"}
#: The record type whose ``event=`` keyword declares an emitted kind.
FAMILY_RECORD = "Family"


def _string_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _literal_strings(node: ast.AST) -> List[str]:
    """String constants of a tuple/set/list literal, or the literal keys
    (dict) / elements (set) of a container literal."""
    out: List[str] = []
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        for elt in node.elts:
            s = _string_const(elt)
            if s is not None:
                out.append(s)
    elif isinstance(node, ast.Dict):
        for key in node.keys:
            s = _string_const(key) if key is not None else None
            if s is not None:
                out.append(s)
    return out


def _is_telemetry_call(
    project: ProjectContext, info: ModuleInfo, qual: str, call: ast.Call
) -> Optional[str]:
    """"event"/"span"/"record" when ``call`` targets the telemetry API."""
    name = jaxast.call_name(call)
    if not name:
        return None
    bare = name.rsplit(".", 1)[-1]
    if bare not in ("event", "span", "record"):
        return None
    resolved = project.resolve(info.module, name)
    if resolved is not None:
        target_info, sym = resolved
        if sym == bare and target_info.module.split(".")[-1] == (
            "telemetry"
        ):
            return bare
    if "." in name:
        receiver = name.rsplit(".", 1)[0]
        if bare in ("event", "span") and "telemetry" in receiver:
            return bare
        if bare == "record" and "timeline" in receiver:
            return bare
    return None


@register
class TelemetryContract(ProjectRule):
    id = "TEL001"
    name = "telemetry-contract"
    description = (
        "telemetry event kind, counter, or gauge broken out of the "
        "emit->route->render pipeline"
    )

    def check_project(
        self, project: ProjectContext
    ) -> Iterator[Finding]:
        routes = self._routed_kinds(project)
        emits = self._emitted_kinds(project)
        if routes:
            yield from self._check_unrouted(routes, emits)
            yield from self._check_dead_routes(routes, emits)
        yield from self._check_gauges_and_counters(project, routes)
        yield from self._check_speed_monitor_surface(project)

    # -- route extraction ----------------------------------------------------

    def _routed_kinds(
        self, project: ProjectContext
    ) -> Dict[str, Tuple[ModuleInfo, ast.AST]]:
        """Routed kind literal -> (module, anchoring node)."""
        out: Dict[str, Tuple[ModuleInfo, ast.AST]] = {}
        for fname in sorted(ROUTING_FUNCTIONS):
            for info, _qual, fn in project.functions_named(fname):
                for node in ast.walk(fn):
                    if not isinstance(node, ast.Compare):
                        continue
                    for kind in self._kinds_of_compare(
                        project, info, node
                    ):
                        out.setdefault(kind, (info, node))
        return out

    @staticmethod
    def _kinds_of_compare(
        project: ProjectContext, info: ModuleInfo, node: ast.Compare
    ) -> List[str]:
        sides = [node.left] + list(node.comparators)
        if not any(
            isinstance(s, ast.Name) and s.id == "name" for s in sides
        ):
            return []
        has_eq = any(isinstance(o, ast.Eq) for o in node.ops)
        has_in = any(isinstance(o, ast.In) for o in node.ops)
        if not (has_eq or has_in):
            return []
        out: List[str] = []
        for other in sides:
            s = _string_const(other)
            if s is not None:
                out.append(s)
            elif isinstance(other, ast.Name) and other.id != "name":
                if has_in:
                    const = info.constants.get(other.id)
                    if const is None:
                        # a table the routing module imports by name
                        resolved = project.resolve(info.module, other.id)
                        if resolved is not None:
                            const = resolved[0].constants.get(resolved[1])
                    if const is not None:
                        out.extend(_literal_strings(const))
            elif has_in:
                out.extend(_literal_strings(other))
        return out

    # -- emission extraction -------------------------------------------------

    def _emitted_kinds(
        self, project: ProjectContext
    ) -> Dict[str, List[Tuple[ModuleInfo, str, ast.Call, bool]]]:
        """kind literal -> [(module, qualname, call, is_instant)]."""
        out: Dict[
            str, List[Tuple[ModuleInfo, str, ast.Call, bool]]
        ] = {}
        for mod in sorted(project.modules):
            info = project.modules[mod]
            for const in info.constants.values():
                # a family's declaration: the kind its reports book
                if not isinstance(const, ast.Call) or jaxast.call_name(
                    const
                ).rsplit(".", 1)[-1] != FAMILY_RECORD:
                    continue
                for kw in const.keywords:
                    literal = _string_const(kw.value)
                    if kw.arg == "event" and literal is not None:
                        out.setdefault(literal, []).append(
                            (info, "<module>", const, True)
                        )
            for qual in sorted(info.functions):
                fn = info.functions[qual]
                for node in jaxast.body_nodes(fn):
                    if not isinstance(node, ast.Call):
                        continue
                    kind_api = _is_telemetry_call(
                        project, info, qual, node
                    )
                    if kind_api is None:
                        continue
                    name_arg_index = 1 if kind_api == "record" else 0
                    if len(node.args) <= name_arg_index:
                        continue
                    literal = _string_const(node.args[name_arg_index])
                    if literal is None:
                        continue  # dynamic kind: out of linter scope
                    instant = (
                        kind_api == "event"
                        and len(node.args) == 1
                        and not any(
                            kw.arg in ("duration_s", "t_mono")
                            for kw in node.keywords
                        )
                    )
                    out.setdefault(literal, []).append(
                        (info, qual, node, instant)
                    )
        return out

    def _check_unrouted(self, routes, emits) -> Iterator[Finding]:
        for kind in sorted(emits):
            if kind in routes:
                continue
            for info, qual, call, instant in emits[kind]:
                if not instant:
                    continue
                yield info.ctx.finding(
                    self.id, call,
                    f"instant telemetry event {kind!r} emitted in {qual} "
                    "has no master-side route (_report_telemetry/"
                    "add_events) — it lands in the ring and vanishes",
                    symbol=f"event::{kind}",
                )

    def _check_dead_routes(self, routes, emits) -> Iterator[Finding]:
        for kind in sorted(routes):
            if kind in emits:
                continue
            info, node = routes[kind]
            yield info.ctx.finding(
                self.id, node,
                f"routed telemetry kind {kind!r} is emitted nowhere in "
                "the tree — dead route (delete it or restore the "
                "emitter)",
                symbol=f"route::{kind}",
            )

    # -- gauges + counters ---------------------------------------------------

    def _check_gauges_and_counters(
        self, project: ProjectContext, routes
    ) -> Iterator[Finding]:
        rendered: Set[str] = set()
        helped: Set[str] = set()
        gauge_calls: List[Tuple[ModuleInfo, str, ast.Call, str, bool]] = []
        render_seen = False
        for fname in sorted(RENDER_FUNCTIONS):
            for info, qual, fn in project.functions_named(fname):
                render_seen = True
                for node in ast.walk(fn):
                    s = _string_const(node)
                    if s is not None and s.startswith("# HELP "):
                        parts = s.split()
                        if len(parts) >= 3:
                            helped.add(parts[2])
                    if not isinstance(node, ast.Call):
                        continue
                    if jaxast.call_name(node) != "gauge" or not (
                        node.args
                    ):
                        continue
                    gname = _string_const(node.args[0])
                    if gname is None:
                        continue
                    rendered.add(gname)
                    help_arg = (
                        node.args[2] if len(node.args) >= 3 else None
                    )
                    for kw in node.keywords:
                        if kw.arg == "help_text":
                            help_arg = kw.value
                    # A dynamic help expression counts; an explicit ""
                    # (the gauge() default) does not.
                    has_help = help_arg is not None and (
                        _string_const(help_arg) != ""
                    )
                    gauge_calls.append(
                        (info, qual, node, gname, has_help)
                    )
        if not render_seen:
            return

        # HELP is per metric *name*, not per call: a labeled series
        # rides the HELP of the unlabeled call for the same name.
        helped |= {g for _i, _q, _n, g, has_help in gauge_calls if has_help}
        seen_nohelp: Set[str] = set()
        for info, qual, node, gname, has_help in gauge_calls:
            if gname in seen_nohelp:
                continue
            seen_nohelp.add(gname)
            if not has_help and gname not in helped:
                yield info.ctx.finding(
                    self.id, node,
                    f"gauge {gname!r} rendered in {qual} without "
                    "HELP/TYPE metadata — pass help_text or emit an "
                    "explicit # HELP/# TYPE pair",
                    symbol=f"gauge::{gname}",
                )

        for info, qual, call, counter in self._bump_literals(project):
            gauge_name = f"dlrover_{counter}_total"
            if gauge_name not in rendered and gauge_name not in helped:
                yield info.ctx.finding(
                    self.id, call,
                    f"counter {counter!r} bumped in {qual} but "
                    f"{gauge_name} is never rendered by render_metrics "
                    "— the increment is write-only",
                    symbol=f"counter::{counter}",
                )

    @staticmethod
    def _bump_literals(
        project: ProjectContext,
    ) -> List[Tuple[ModuleInfo, str, ast.Call, str]]:
        out: List[Tuple[ModuleInfo, str, ast.Call, str]] = []
        for mod in sorted(project.modules):
            info = project.modules[mod]
            for qual in sorted(info.functions):
                for node in jaxast.body_nodes(info.functions[qual]):
                    if not isinstance(node, ast.Call):
                        continue
                    name = jaxast.call_name(node)
                    if name.rsplit(".", 1)[-1] != "bump" or not (
                        node.args
                    ):
                        continue
                    arg = node.args[0]
                    literal = _string_const(arg)
                    if literal is not None:
                        out.append((info, qual, node, literal))
                    elif isinstance(arg, ast.Subscript):
                        table = jaxast.dotted_name(arg.value)
                        const = info.constants.get(table)
                        if isinstance(const, ast.Dict):
                            for value in const.values:
                                s = _string_const(value)
                                if s is not None:
                                    out.append((info, qual, node, s))
        return out

    # -- SpeedMonitor surface ------------------------------------------------

    def _check_speed_monitor_surface(
        self, project: ProjectContext
    ) -> Iterator[Finding]:
        monitors = list(project.classes_named("SpeedMonitor"))
        if not monitors:
            return
        minfo, mqual, _cls = monitors[0]
        methods = {
            qual.split(".")[-1]
            for qual in minfo.functions
            if qual.startswith(mqual + ".")
        }
        called: Set[str] = set()
        flagged: Set[str] = set()
        for mod in sorted(project.modules):
            info = project.modules[mod]
            for qual in sorted(info.functions):
                for node in jaxast.body_nodes(info.functions[qual]):
                    if not isinstance(node, ast.Call):
                        continue
                    name = jaxast.call_name(node)
                    parts = name.split(".")
                    if len(parts) < 2 or parts[-2] != "speed_monitor":
                        continue
                    method = parts[-1]
                    called.add(method)
                    if method not in methods and method not in flagged:
                        flagged.add(method)
                        yield info.ctx.finding(
                            self.id, node,
                            f"{qual} calls speed_monitor.{method}() but "
                            f"SpeedMonitor defines no such method — "
                            "AttributeError at route time",
                            symbol=f"speed_monitor::{method}",
                        )
        for method in sorted(methods):
            if method.startswith("record_") and method not in called:
                fn = minfo.functions[f"{mqual}.{method}"]
                yield minfo.ctx.finding(
                    self.id, fn,
                    f"SpeedMonitor.{method} is routed to by nothing — "
                    "orphan ledger intake (delete it or restore the "
                    "route)",
                    symbol=f"speed_monitor::orphan::{method}",
                )
