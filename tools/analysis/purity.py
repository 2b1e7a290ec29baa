"""Device-purity analyzer for the wave hot path (rules: pod-loop,
host-sync, nondeterminism).

Roots come from a small manifest (HOT_PATH_ROOTS below — the engine wave
entry, the whole replay module, the gang quorum slice, and the decode
chunk routing); every function reachable from them over the intra-repo
call graph is checked:

  * pod-loop — a Python `for` over a pod/node-sized iterable (pending,
    pods, nodes, or range(len(...)) of one).  The paper's whole point is
    the dense pod x node x plugin re-expression; a per-pod Python loop
    reintroduces the O(pods) interpreter serialization the fused wave
    removed.  Host-side loops that are *by design* (str building in
    decode, commit bookkeeping) are ratcheted or carry allow comments.
  * host-sync — `.item()`, `float()`, `int()`, `np.asarray()`/
    `np.array()` on a traced value forces a device->host transfer and a
    blocking sync inside the wave.  Statically "traced" is undecidable,
    so the rule fires on the syntactic forms inside *jitted* functions,
    and on `.item()` anywhere in the hot path.
  * nondeterminism — `time.*` / `random.*` / `np.random.*` inside
    jitted code: a traced clock or RNG bakes one trace-time value into
    the compiled executable, silently breaking replay determinism.
"""

from __future__ import annotations

import ast

from .callgraph import CallGraph
from .common import Finding, dotted_name

# the hot-path manifest: (module suffix, qualname-or-* ) roots
HOT_PATH_ROOTS: list[tuple[str, str]] = [
    ("framework.engine", "SchedulerEngine._schedule_wave"),
    ("framework.engine", "SchedulerEngine._profile_wave_run"),
    ("framework.engine", "SchedulerEngine._profile_wave_attempt"),
    ("framework.engine", "SchedulerEngine._device_wave"),
    ("framework.engine", "_WaveCommitter.on_chunk"),
    ("framework.engine", "_WaveCommitter._commit"),
    ("framework.replay", "*"),
    ("framework.gang", "quorum_slice"),
    ("store.decode", "decode_chunk_into"),
    ("store.decode", "decode_all_parallel"),
    # lazy materialization entry points (PR 9): the result-store read
    # path and the on-demand chunk routing serve API reads concurrently
    # with live waves — they must stay loop-free and host-sync-free too
    ("store.resultstore", "ResultStore.get_stored_result"),
    ("store.resultstore", "ResultStore.take_deferred"),
    ("store.resultstore", "_merge_snapshot"),
    ("store.lazy", "*"),
    ("store.reflector", "LazyReflections._drain"),
    ("store.reflector", "LazyReflections._apply"),
    # device-resident results (PR 10): the D2H entry points serve API
    # reads concurrently with live waves, and the device-side
    # attribution reduction runs per chunk inside the wave — both must
    # stay loop-free and host-sync-free (framework.replay is a root
    # already and covers _CompactChunks.materialize/_DeviceAttribution)
    ("store.native_decode", "decode_chunk_start"),
    ("store.native_decode", "decode_pod_fused"),
    # multi-session serving (PR 11): the session registry sits on every
    # routed request, concurrent with all sessions' live waves — lookup,
    # listing and the shared-shell stats must stay loop-free and
    # host-sync-free (the lock rules additionally watch the registry
    # lock package-wide: no engine wave, deep copy or blocking call may
    # run under SessionManager._mu)
    ("server.sessions", "SessionManager.get"),
    ("server.sessions", "SessionManager.list_sessions"),
    ("server.sessions", "SessionManager.stats"),
    ("server.sessions", "SimulationSession.touch"),
    ("server.sessions", "SimulationSession.register_stream"),
    ("server.sessions", "SimulationSession.unregister_stream"),
    # columnar data plane (PR 17): the node-table build/patch and the
    # column read surface run once per wave over up to 100k-node arrays
    # — a per-ROW Python loop here (columnar-row-loop below) undoes the
    # vectorization the columns exist for.  Bounded opaque-row fallbacks
    # iterate opaque_positions(), never the row arrays themselves.
    ("state.nodes", "build_node_table_columnar"),
    ("state.nodes", "patch_node_table_columnar"),
    ("state.compile", "_node_delta"),
    ("cluster.columnar", "NodeColumns.alloc_matrix"),
    ("cluster.columnar", "NodeColumns.extended_names"),
    ("cluster.columnar", "NodeColumns.allowed_pods"),
    ("cluster.columnar", "NodeColumns.unschedulable"),
    ("cluster.columnar", "_LabelRows.column"),
]

BIG_ITERABLES = {"pending", "pods", "nodes"}
HOST_SYNC_METHODS = {"item"}
HOST_SYNC_CALLS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}
NONDET_PREFIXES = ("time.", "random.", "np.random.", "numpy.random.")

# compact-host-sync: the replay's heavy per-chunk groups may be LIVE
# DEVICE arrays (device-resident results); an eager np.asarray /
# np.ascontiguousarray on one of these fields outside the materialization
# path silently re-introduces the in-wave D2H the residency design
# removed.  _CompactChunks.host()/materialize() (which route through
# parallel.mesh.gather_to_host on a generic value, not a field access)
# are the only sanctioned crossings.
COMPACT_FIELDS = {"packed", "raw8", "raw16", "raw32"}
COMPACT_SYNC_CALLS = HOST_SYNC_CALLS | {
    "np.ascontiguousarray", "numpy.ascontiguousarray", "jax.device_get"}

# columnar-row-loop: per-ROW arrays of the columnar banks
# (cluster/columnar.py) — one entry per stored object.  A Python `for`
# directly over one of these (or enumerate/zip/range(len(...)) of one)
# re-serializes O(rows) work the columns were built to vectorize.  The
# per-COLUMN dicts (res, label_cols, req) are ~dozens of entries and are
# deliberately NOT listed; neither are single-row subscripts like
# `taints[row]`.
COLUMNAR_ROW_ARRAYS = {"names", "rv", "uid", "created", "manifests",
                       "opaque", "deleted", "taints", "nonzero"}


def resolve_roots(graph: CallGraph,
                  roots: list[tuple[str, str]] | None = None) -> list[str]:
    keys: list[str] = []
    for mod_suffix, qual in roots or HOT_PATH_ROOTS:
        for key, info in graph.functions.items():
            modname = key.partition(":")[0]
            if not (modname == mod_suffix
                    or modname.endswith("." + mod_suffix)):
                continue
            if qual == "*" or info.qualname == qual:
                keys.append(key)
    return keys


class PurityAnalyzer:
    def __init__(self, graph: CallGraph,
                 roots: list[tuple[str, str]] | None = None):
        self.graph = graph
        self.root_keys = resolve_roots(graph, roots)
        self.reachable = graph.reachable(self.root_keys)

    def analyze(self) -> list[Finding]:
        findings: list[Finding] = []
        for key in sorted(self.reachable):
            info = self.graph.functions[key]
            findings.extend(self._check_function(info))
        return findings

    def _check_function(self, info) -> list[Finding]:
        out: list[Finding] = []
        jitted = info.jitted
        for node in ast.walk(info.node):
            if isinstance(node, (ast.For, ast.AsyncFor)):
                big = self._big_iterable(node.iter)
                if big:
                    out.append(Finding(
                        rule="pod-loop", path=info.module.path,
                        qualname=info.qualname, detail=f"for over {big}",
                        lineno=node.lineno,
                        message=f"Python for-loop over {big} in the wave "
                                "hot path (should be a fused tensor op)"))
                col = self._columnar_row_iterable(node.iter)
                if col:
                    out.append(Finding(
                        rule="columnar-row-loop", path=info.module.path,
                        qualname=info.qualname, detail=f"for over {col}",
                        lineno=node.lineno,
                        message=f"Python for-loop over columnar row array "
                                f"{col}: per-row work on the data plane "
                                "must be a vectorized numpy op (bounded "
                                "opaque-row fallbacks iterate "
                                "opaque_positions())"))
            elif isinstance(node, ast.Call):
                name = dotted_name(node.func) or ""
                last = name.split(".")[-1]
                if (name in COMPACT_SYNC_CALLS
                        and self._compact_field_arg(node)):
                    out.append(Finding(
                        rule="compact-host-sync", path=info.module.path,
                        qualname=info.qualname,
                        detail=f"{name}({self._compact_field_arg(node)})",
                        lineno=node.lineno,
                        message=f"{name} on a replay compact field outside "
                                "_CompactChunks.materialize: device-resident "
                                "chunks must cross D2H only through "
                                "cc.host()/materialize()"))
                if last in HOST_SYNC_METHODS and "." in name:
                    out.append(Finding(
                        rule="host-sync", path=info.module.path,
                        qualname=info.qualname, detail=f"{last}()",
                        lineno=node.lineno,
                        message=f"{name}() forces a device->host sync in "
                                "the wave hot path"))
                elif name in HOST_SYNC_CALLS and jitted:
                    out.append(Finding(
                        rule="host-sync", path=info.module.path,
                        qualname=info.qualname, detail=name,
                        lineno=node.lineno,
                        message=f"{name} on a traced value inside jitted "
                                "code materializes to host"))
                elif jitted and any(name.startswith(p)
                                    for p in NONDET_PREFIXES):
                    out.append(Finding(
                        rule="nondeterminism", path=info.module.path,
                        qualname=info.qualname, detail=name,
                        lineno=node.lineno,
                        message=f"{name}() inside jitted code bakes a "
                                "trace-time value into the executable"))
        return out

    @staticmethod
    def _compact_field_arg(call: ast.Call) -> str | None:
        """The `.packed`/`.raw*` attribute inside the call's arguments,
        if any (e.g. np.asarray(cc.packed[ci][:m]) -> "packed")."""
        for arg in call.args:
            for sub in ast.walk(arg):
                if (isinstance(sub, ast.Attribute)
                        and sub.attr in COMPACT_FIELDS):
                    return sub.attr
        return None

    def _columnar_row_iterable(self, it: ast.AST) -> str | None:
        """`x.names` / `enumerate(bank.rv)` / `range(len(cols.uid))` —
        an iteration over a per-row columnar array (attribute access
        only: bare names and single-row subscripts don't match)."""
        if (isinstance(it, ast.Attribute)
                and it.attr in COLUMNAR_ROW_ARRAYS):
            return dotted_name(it) or it.attr
        if isinstance(it, ast.Call):
            cname = dotted_name(it.func)
            if cname in ("range", "enumerate", "reversed", "sorted", "zip"):
                for arg in it.args:
                    inner = self._columnar_row_iterable(arg)
                    if inner:
                        return f"{cname}({inner})"
                for arg in it.args:
                    if (isinstance(arg, ast.Call)
                            and dotted_name(arg.func) == "len"
                            and arg.args):
                        inner = self._columnar_row_iterable(arg.args[0])
                        if inner:
                            return f"{cname}(len({inner}))"
        return None

    def _big_iterable(self, it: ast.AST) -> str | None:
        name = dotted_name(it)
        if name and name.split(".")[-1] in BIG_ITERABLES:
            return name
        if isinstance(it, ast.Call):
            cname = dotted_name(it.func)
            if cname in ("range", "enumerate", "reversed", "sorted", "zip"):
                for arg in it.args:
                    inner = self._big_iterable(arg)
                    if inner:
                        return f"{cname}({inner})"
                # range(len(pending)) shape
                for arg in it.args:
                    if (isinstance(arg, ast.Call)
                            and dotted_name(arg.func) == "len"
                            and arg.args):
                        inner = self._big_iterable(arg.args[0])
                        if inner:
                            return f"{cname}(len({inner}))"
        return None
