"""kss-analyze: repo-native static analysis for the TPU scheduler
simulator (docs/static-analysis.md).

Three pure-AST analyzers over `kube_scheduler_simulator_tpu/`:

  * lock discipline  (tools/analysis/locks.py)  — lock-order inversions,
    self-deadlocks, blocking/device/serialize work under a lock;
  * device purity    (tools/analysis/purity.py) — per-pod Python loops,
    host syncs, and nondeterminism in the wave hot path;
  * observability    (tools/analysis/spans.py)  — span balance on all
    exception paths, static Prometheus name conformance.

plus the runtime lock-witness (tools/analysis/lockwitness.py) installed
by conftest.py under KSS_TPU_LOCK_WITNESS=1.

Entry points: `make analyze` / `python -m tools.analysis` (CLI), or
`run_analysis()` for tests and bench embedding.
"""

from __future__ import annotations

import os

from .common import Finding, filter_suppressed, load_modules  # noqa: F401

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_PACKAGE = "kube_scheduler_simulator_tpu"


def run_analysis(root: str | None = None,
                 package: str | None = None,
                 modules=None,
                 purity_roots=None,
                 swallow_modules=None) -> dict:
    """Run all four analyzers; returns
    {"findings": [Finding] (suppressions applied), "suppressed": int,
    "modules": int, "functions": int, "graph": CallGraph}."""
    from .callgraph import CallGraph
    from .locks import LockAnalyzer
    from .purity import PurityAnalyzer
    from .spans import SpanAnalyzer
    from .swallowed import SwallowedAnalyzer

    if modules is None:
        modules = load_modules(root or REPO_ROOT,
                               package or DEFAULT_PACKAGE)
    graph = CallGraph(modules)
    findings: list[Finding] = []
    lock_findings, lock_edges = LockAnalyzer(graph).analyze()
    findings.extend(lock_findings)
    findings.extend(PurityAnalyzer(graph, roots=purity_roots).analyze())
    findings.extend(SpanAnalyzer(modules).analyze())
    findings.extend(
        SwallowedAnalyzer(modules, hot_modules=swallow_modules).analyze())
    by_path = {m.path: m for m in modules}
    kept = filter_suppressed(findings, by_path)
    # stable order + dedup by fingerprint: one function repeating the
    # same violation on many lines (or reached through several transitive
    # paths) is ONE ratchetable fact, anchored at its first line
    seen: set[str] = set()
    uniq: list[Finding] = []
    for f in sorted(kept, key=lambda f: (f.path, f.lineno, f.rule,
                                         f.detail)):
        if f.fingerprint in seen:
            continue
        seen.add(f.fingerprint)
        uniq.append(f)
    return {
        "findings": uniq,
        "suppressed": len(findings) - len(kept),
        "modules": len(modules),
        "functions": len(graph.functions),
        "graph": graph,
        "lock_edges": lock_edges,
    }

