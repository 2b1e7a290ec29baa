"""Observability-conformance analyzer (rules: unbalanced-span,
metric-name, label-name).

Span balance: `Tracer.span()` is a context manager; the ONLY form that
guarantees the end fires on every exception path — including across the
commit-worker thread boundary PR 5 parents explicitly — is
`with TRACER.span(...)`.  Any call to `.span(...)` that is not the
context expression of a `with` item (bare call, stored handle, manual
`__enter__`) is an unbalanced-span finding.

Metric names: every literal name passed to `TRACER.count/inc/observe`
and every literal span name must already satisfy the strict Prometheus
exposition rules PR 5's `validate_exposition()` enforces at scrape time
(`[a-zA-Z_:][a-zA-Z0-9_:]*`; label keywords `[a-zA-Z_][a-zA-Z0-9_]*`).
Runtime sanitization would *silently rename* a bad name, so the check is
static: the name a reader greps for must be the name exported.  Span
names additionally feed `span_<name>_seconds_total` families and pass
through the same gate.  A name passed as a plain variable (the wave
executor picks its one span's name from the plan) is read through the
module's assignments of string literals to that variable: every literal
it can hold is checked.
"""

from __future__ import annotations

import ast
import re

from .common import Finding, Module, dotted_name

# mirror utils/tracing.py's regexes (no import: these passes must run
# without the package's dependency closure)
_METRIC_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_NAME_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

TRACER_BASES = {"TRACER", "tracer", "_tracer"}
METRIC_METHODS = {"count", "inc", "inc_process", "observe"}


def _assigned_literals(tree: ast.AST) -> dict[str, set[str]]:
    """name -> every string literal the module assigns to it (`a = "x"`,
    `a = b = "x"`, `a, b = "x", "y"`)."""
    out: dict[str, set[str]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        for target in node.targets:
            pairs = [(target, node.value)]
            if isinstance(target, ast.Tuple) \
                    and isinstance(node.value, ast.Tuple):
                pairs = list(zip(target.elts, node.value.elts))
            for t, v in pairs:
                if isinstance(t, ast.Name) and isinstance(v, ast.Constant) \
                        and isinstance(v.value, str):
                    out.setdefault(t.id, set()).add(v.value)
    return out


class SpanAnalyzer:
    def __init__(self, modules: list[Module]):
        self.modules = modules

    def analyze(self) -> list[Finding]:
        findings: list[Finding] = []
        for mod in self.modules:
            findings.extend(self._check_module(mod))
        return findings

    def _check_module(self, mod: Module) -> list[Finding]:
        out: list[Finding] = []
        with_contexts: set[int] = set()   # id() of calls used as with-items
        literals = _assigned_literals(mod.tree)
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    with_contexts.add(id(item.context_expr))
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            target = self._tracer_method(node)
            if target is None:
                continue
            base, method = target
            if method == "span":
                if id(node) not in with_contexts:
                    out.append(Finding(
                        rule="unbalanced-span", path=mod.path,
                        qualname=self._span_name(node) or base,
                        detail=f"{base}.span not context-managed",
                        lineno=node.lineno,
                        message=f"{base}.span(...) outside a `with`: the "
                                "span end is not guaranteed on exception "
                                "paths"))
                self._check_name(node, mod, out, True, literals)
            elif method in METRIC_METHODS:
                self._check_name(node, mod, out, False, literals)
        return out

    @staticmethod
    def _tracer_method(call: ast.Call) -> tuple[str, str] | None:
        f = call.func
        if not isinstance(f, ast.Attribute):
            return None
        base = dotted_name(f.value)
        if base is None:
            return None
        last = base.split(".")[-1]
        if last in TRACER_BASES or base in TRACER_BASES:
            return last, f.attr
        return None

    @staticmethod
    def _span_name(call: ast.Call) -> str | None:
        if call.args and isinstance(call.args[0], ast.Constant) \
                and isinstance(call.args[0].value, str):
            return call.args[0].value
        return None

    def _check_name(self, call: ast.Call, mod: Module, out: list[Finding],
                    span: bool, literals: dict[str, set[str]]) -> None:
        name = self._span_name(call)
        names = [name] if name is not None else []
        if name is None and call.args and isinstance(call.args[0], ast.Name):
            names = sorted(literals.get(call.args[0].id, ()))
        for name in names:
            if _METRIC_NAME_RE.match(name):
                continue
            kind = "span" if span else "metric"
            out.append(Finding(
                rule="metric-name", path=mod.path, qualname=name,
                detail=f"invalid {kind} name {name!r}",
                lineno=call.lineno,
                message=f"{kind} name {name!r} fails the Prometheus name "
                        "rules (validate_exposition would only see a "
                        "silently sanitized alias)"))
        labels: list[str] = []
        for kw in call.keywords:
            if kw.arg is None:
                # **{...}: literal dict keys are checkable
                if isinstance(kw.value, ast.Dict):
                    labels.extend(
                        k.value for k in kw.value.keys
                        if isinstance(k, ast.Constant)
                        and isinstance(k.value, str))
            elif kw.arg not in ("n", "parent", "value"):
                labels.append(kw.arg)
        for label in labels:
            # keyword syntax already guarantees identifier shape; the
            # checkable surface is **{...} dicts and the reserved
            # double-underscore prefix Prometheus claims for itself
            if not _LABEL_NAME_RE.match(label) or label.startswith("__"):
                out.append(Finding(
                    rule="label-name", path=mod.path,
                    qualname=name or "?",
                    detail=f"invalid label {label!r}",
                    lineno=call.lineno,
                    message=f"label name {label!r} fails the Prometheus "
                            "label rules (reserved or malformed)"))
