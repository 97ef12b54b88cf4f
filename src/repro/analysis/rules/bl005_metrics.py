"""BL005 — metric hygiene: names, kinds, and no gauge-sets in closures.

The :mod:`repro.obs.metrics` registry is get-or-create by name and a
name binds to exactly one kind — ``counter("x")`` after ``gauge("x")``
raises ``TypeError`` at runtime, *in whoever asks second*, which may be
a benchmark harness three modules away from the collision.  This rule
moves the whole contract to lint time:

* **Spans** — a literal ``span("a.b")`` (``Observability.span``)
  registers the histogram ``a.b_us``, so its name is held to the same
  grammar and joins the same kind table.
* **Grammar** — literal instrument names must be dotted
  ``component.metric`` (``sched.submitted``, ``kv.cow_faults``):
  lowercase, at least one dot, no uppercase/dashes/leading digits.
  Undotted names don't group in ``format()``'s procfs-style block and
  collide across components.  Non-literal names (f-strings, variables)
  are skipped — the grammar is only checkable for constants.
* **Kind collisions** — ``finalize`` joins every literal registration
  across all analyzed files and reports a name claimed as two kinds,
  pointing at the second claimant (the one that would raise).
* **Closure gauges** — ``gauge(...).set(...)`` inside a ``lambda`` or
  nested def captures the registry (and whatever the closure also
  holds: an engine, a pool) for as long as the callback lives, and
  races the mutation-site updates.  Per the metrics module's own
  design note, gauges are set at the mutation site only.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Tuple

from repro.analysis.engine import (FileContext, Finding, Project, Rule,
                                   register)
from repro.analysis.rules.common import calls_in

#: registration verbs -> instrument kind (a timed span is a histogram)
_KINDS = {"counter": "counter", "gauge": "gauge", "histogram": "histogram",
          "span": "histogram"}

#: what a verb appends to its literal name to make the instrument's name
_SUFFIX = {"span": "_us"}

#: component.metric grammar (underscored lowercase segments, >=1 dot)
_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")

def _literal_name(call: ast.Call) -> str:
    """The constant string name a registration call uses, or ''."""
    args = call.args
    if args and isinstance(args[0], ast.Constant) and \
            isinstance(args[0].value, str):
        return args[0].value
    return ""


def _verb(call: ast.Call) -> str:
    func = call.func
    return func.attr if isinstance(func, ast.Attribute) else \
        func.id if isinstance(func, ast.Name) else ""


def _kind_of(call: ast.Call) -> str:
    return _KINDS.get(_verb(call), "")


def _instrument_name(call: ast.Call) -> str:
    """The registered instrument's literal name (``span("a.b")``
    registers ``a.b_us``), or ''."""
    name = _literal_name(call)
    return name + _SUFFIX.get(_verb(call), "") if name else ""


def _registrations(ctx: FileContext) -> List[Tuple[str, str, int]]:
    """Every literal-name (name, kind, line) registration in a file."""
    out = []
    for call in calls_in(ctx.tree, *_KINDS):
        name = _instrument_name(call)
        if name:
            out.append((name, _kind_of(call), getattr(call, "lineno", 0)))
    return out


@register
class MetricHygiene(Rule):
    code = "BL005"
    title = "metric hygiene: dotted names, one kind per name, no gauge " \
            "mutation from closures"
    rationale = ("kind collisions raise at the second claimant at "
                 "runtime; undotted names break format() grouping; "
                 "closure gauges pin objects and race mutation sites")

    def visit(self, ctx: FileContext) -> List[Finding]:
        out: List[Finding] = []
        for call in calls_in(ctx.tree, *_KINDS):
            name = _instrument_name(call)
            if not name:
                continue        # dynamic name: grammar not checkable
            if not _NAME_RE.match(name):
                out.append(ctx.finding(
                    call, self.code,
                    f"metric name {name!r} is not component.metric "
                    "grammar (lowercase dotted segments); undotted "
                    "names collide across components and break "
                    "format() grouping"))
        # gauge mutation from inside a closure (a lambda, or a def
        # nested inside another function — module-level functions and
        # methods ARE the mutation sites and stay legal)
        for closure in self._closures(ctx.tree):
            for call in calls_in(closure, "set", "add"):
                func = call.func
                if isinstance(func, ast.Attribute) and \
                        isinstance(func.value, ast.Call) and \
                        _kind_of(func.value) == "gauge":
                    out.append(ctx.finding(
                        call, self.code,
                        "gauge mutated from inside a closure; set "
                        "gauges at the mutation site so retained "
                        "callbacks never pin the registry or race it"))
        return out

    @staticmethod
    def _closures(tree: ast.AST) -> List[ast.AST]:
        seen: dict = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Lambda):
                seen[id(node)] = node
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for sub in ast.walk(node):
                    if sub is not node and \
                            isinstance(sub, (ast.FunctionDef,
                                             ast.AsyncFunctionDef)):
                        seen[id(sub)] = sub
        return list(seen.values())

    def finalize(self, project: Project) -> List[Finding]:
        registry: Dict[str, List[Tuple[str, str, int]]] = {}
        for ctx in project.files:
            for name, kind, line in _registrations(ctx):
                registry.setdefault(name, []).append(
                    (kind, ctx.rel, line))
        out: List[Finding] = []
        for name, claims in sorted(registry.items()):
            first_kind, first_file, _ = claims[0]
            for kind, rel, line in claims[1:]:
                if kind == first_kind:
                    continue
                ctx = next((c for c in project.files if c.rel == rel),
                           None)
                out.append(Finding(
                    file=rel, line=line, col=0, rule=self.code,
                    message=(f"metric {name!r} registered as {kind} "
                             f"here but as {first_kind} in "
                             f"{first_file}; a name binds to exactly "
                             "one kind (the second claimant raises "
                             "TypeError at runtime)"),
                    snippet=(ctx.lines[line - 1].strip()
                             if ctx and line <= len(ctx.lines) else "")))
        return out
