"""DefaultPreemption: the PostFilter extension point.

Capability parity with upstream DefaultPreemption as recorded by the
reference simulator (reference: simulator/scheduler/plugin/wrappedplugin.go
:550-583 records PostFilter; resultstore/store.go:439-458 stores
"preemption victim" at the nominated node and an empty entry for every
other evaluated node).  Algorithm follows upstream
pkg/scheduler/framework/plugins/defaultpreemption (v1.32):

  1. eligibility: preemptionPolicy "Never" never preempts;
  2. candidate nodes: only nodes whose Filter rejection is *resolvable* by
     removing pods — i.e. the first failing plugin is one whose verdict
     depends on the pods already on the node (NodeResourcesFit,
     PodTopologySpread, InterPodAffinity, and NodePorts).  Nodes rejected
     by node-property plugins (NodeName, NodeUnschedulable, NodeAffinity,
     TaintToleration) are UnschedulableAndUnresolvable upstream and are
     skipped; so are the nodes outside the pod's PreFilterResult, on
     which no Filter ran (upstream's absent-nodes status, "node(s) didn't
     satisfy plugin(s) [NodeAffinity]");
  3. per candidate node: a node that holds no lower-priority pod is no
     candidate, without a look (upstream SelectVictimsOnNode: "No
     preemption victims found for incoming pod"); otherwise dry-run with
     ALL lower-priority pods removed; if the pod then fits, reprieve
     victims most-important-first (priority desc, earlier creation
     first), keeping each one that still lets the pod fit — the rest are
     the victim set.  The first of those dry runs is made for every node
     at once, and only for the nodes that an EMPTY node's Fit check does
     not already refuse (`_screen`, below);
  4. candidate selection (upstream pickOneNodeForPreemption): fewest PDB
     violations first (PodDisruptionBudgets are storable even though they
     are outside the 7 synced GVRs — the real scheduler honors any PDBs
     present), then lowest highest-victim priority, then smallest
     priority sum, then fewest victims, then latest
     highest-priority-victim creation, then node order;
  5. execution: delete the victims, set the preemptor's
     status.nominatedNodeName.

The dry-run oracle re-runs the *same tensor kernels* as live scheduling
(compile_workload over the cluster minus the removed pods, one-pod
replay), so preemption verdicts can never drift from filter semantics.

The screen.  Step 3's "all lower-priority pods removed" hypothesis is one
compile_workload + one filter-only replay PER NODE when asked node by
node; asked once for the cluster minus EVERY node's potential victims it
is one of each over [1, N].  The two hypotheses differ, for node j, only
in the pods of OTHER nodes, so they agree on every plugin whose verdict
on node j reads nothing but node j and the pods on it (SCREEN_LOCAL_
PLUGINS).  A node one of those refuses in the screen is refused under its
own hypothesis too, and is out — exactly.  A node that passes the screen,
or that only a plugin outside the set refuses there, goes through the
per-node dry run and the reprieve loop as before.

Before that dry run, the static rule.  NodeResourcesFit refuses node n
when `requests > allocatable[n] - requested[n]` in some resource or
`num_pods[n] + 1 > allowed_pods[n]`, with requested, num_pods >= 0, and an
eviction hypothesis only ever lowers requested and num_pods: a node the
check refuses at requested = 0, num_pods = 0 is refused under every
hypothesis, the screen's included (NodeResourcesFit is in SCREEN_LOCAL_
PLUGINS).  That is noderesources.fit_refuses_empty over the host arrays
the failed pass compiled from (cw.host["fit"]): no upload, no scan, no
read-back.  Such a node leaves `lower_by_node` before the dry run, and the
dry run is made only if a node is left.  It moves from "screened out by
the dry run" to "screened out before it" and nothing else changes:
`potential`, and with it the candidate budget, are what they were, and so
are the candidates, the victims, the nominated node and evaluated_nodes.
The rule applies where NodeResourcesFit ran for the pod (enabled, and not
skipped); elsewhere no node is hopeless.  It reads the failed pass's node
table, as upstream's dry run reads the cycle's snapshot; the dry run reads
the store as it is now.

Documented divergences from upstream (also in docs/SEMANTICS.md):
candidate search starts at node 0 instead of a random offset, and the
terminating-victims eligibility check is skipped (the cluster model has
no graceful deletion).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ..utils.tracing import TRACER

# Plugins whose Filter rejection upstream reports as Unschedulable (the
# preemptible status); all other tensorized filters return
# UnschedulableAndUnresolvable upstream.
RESOLVABLE_PLUGINS = {
    "NodeResourcesFit",
    "PodTopologySpread",
    "InterPodAffinity",
    "NodePorts",
    # removing pods can free inline disks / CSI attachment slots
    "VolumeRestrictions",
    "NodeVolumeLimits",
}

# Filter plugins whose verdict on node j is a function of node j's own
# object and of the pods bound to node j, and of nothing else in the
# cluster — so removing pods from OTHER nodes cannot change it, which is
# what lets one batched dry run stand in for N per-node ones (module
# docstring, "The screen").  Stated conservatively: a plugin is here only
# if that is true of every branch of its Filter.
SCREEN_LOCAL_PLUGINS = frozenset({
    # allocatable of node j minus the requests of the pods on node j,
    # and the count of pods on node j against its pod capacity
    "NodeResourcesFit",
    # host ports in use by the pods on node j
    "NodePorts",
    # volumes attached by the pods on node j against node j's limits
    # (PVC / PV / CSINode objects are read, but no pod elsewhere)
    "NodeVolumeLimits",
    # node-property plugins: node j's name, spec.unschedulable, labels
    # and taints against the pod's own spec — no bound pod is read
    "NodeName",
    "NodeUnschedulable",
    "NodeAffinity",
    "TaintToleration",
})
# Left out on purpose: InterPodAffinity and PodTopologySpread (other
# nodes' pods by construction), VolumeRestrictions (ReadWriteOncePod is
# cluster-wide), VolumeBinding / VolumeZone (no bound pod is read, but
# their PreFilter state is the pod's claims against cluster objects and
# nothing is gained by trusting it here), and every custom plugin.

# upstream DefaultPreemptionArgs defaults
MIN_CANDIDATE_NODES_PERCENTAGE = 10
MIN_CANDIDATE_NODES_ABSOLUTE = 100

PLUGIN_NAME = "DefaultPreemption"


@dataclass
class PreemptionOutcome:
    nominated_node: str = ""            # "" == preemption failed
    victims: list[dict] = field(default_factory=list)
    evaluated_nodes: list[str] = field(default_factory=list)


def _priority(pod: dict) -> int:
    return int((pod.get("spec") or {}).get("priority") or 0)


def _creation(pod: dict) -> str:
    """Victim age for the tie-break ladder: upstream GetPodStartTime uses
    status.startTime when the kubelet set one, else creationTimestamp."""
    start = (pod.get("status") or {}).get("startTime")
    return start or (pod.get("metadata") or {}).get("creationTimestamp") or ""


def _pod_key(pod: dict) -> str:
    meta = pod.get("metadata") or {}
    return f"{meta.get('namespace') or 'default'}/{meta.get('name', '')}"


def _num_candidates(n_nodes: int,
                    pct: int = MIN_CANDIDATE_NODES_PERCENTAGE,
                    abs_: int = MIN_CANDIDATE_NODES_ABSOLUTE) -> int:
    n = max(n_nodes * pct // 100, abs_)
    return min(n, n_nodes)


def filter_pods_with_pdb_violation(pods: list[dict], pdbs: list[dict]
                                   ) -> tuple[list[dict], list[dict]]:
    """(violating, non-violating) split, upstream
    filterPodsWithPDBViolation semantics: each pod decrements every
    matching PDB's remaining disruptionsAllowed; once a budget goes
    negative, further matching pods (and that one) are violating."""
    from ..state.selectors import label_selector_matches

    allowed = [
        int(((pdb.get("status") or {}).get("disruptionsAllowed")) or 0)
        for pdb in pdbs
    ]
    violating, ok = [], []
    for pod in pods:
        meta = pod.get("metadata") or {}
        ns = meta.get("namespace") or "default"
        labels = {k: str(v) for k, v in (meta.get("labels") or {}).items()}
        is_violating = False
        for i, pdb in enumerate(pdbs):
            pdb_ns = (pdb.get("metadata") or {}).get("namespace") or "default"
            if pdb_ns != ns:
                continue
            selector = (pdb.get("spec") or {}).get("selector")
            # upstream filterPodsWithPDBViolation: "A PDB with a nil or
            # empty selector can't match anything" (unlike the eviction
            # API, where {} selects the namespace)
            if (not selector
                    or (not selector.get("matchLabels")
                        and not selector.get("matchExpressions"))
                    or not label_selector_matches(selector, labels)):
                continue
            allowed[i] -= 1
            if allowed[i] < 0:
                is_violating = True
        (violating if is_violating else ok).append(pod)
    return violating, ok


def first_fail_plugins(codes: np.ndarray, active_names: list[str]) -> list[str | None]:
    """Per node, the first filter plugin (upstream order) that rejected it,
    or None if the node passed or lies outside the pod's PreFilterResult
    (NOT_EVALUATED: no plugin ran there, and upstream gives such a node
    UnschedulableAndUnresolvable without an entry of its own, so it is
    neither a candidate nor an evaluated node).  codes: [F, N] over the
    ACTIVE filters."""
    if codes.ndim != 2 or not codes.shape[1]:
        return []
    if not active_names:
        return [None] * codes.shape[1]
    failed = np.asarray(codes) > 0
    first = np.where(failed.any(axis=0), failed.argmax(axis=0), -1)
    names = [*active_names, None]  # -1 -> None
    return [names[f] for f in first.tolist()]


class Preemptor:
    """Runs preemption for one unschedulable pod against live store state."""

    def __init__(self, store, plugin_config, extender_service=None,
                 reuse=None):
        """reuse: a NodeTableReuse of the pass that failed, so the first
        dry run patches that pass's node table instead of building one."""
        self.store = store
        self.plugin_config = plugin_config
        # webhook extenders with a preemptVerb participate in candidate
        # selection (upstream preemption callExtenders; the reference
        # proxies + records the round-trip, extender/service.go:45-85)
        self.extender_service = extender_service
        # DefaultPreemptionArgs from pluginConfig (upstream defaults
        # minCandidateNodesPercentage=10, minCandidateNodesAbsolute=100)
        args = (getattr(plugin_config, "args", None) or {}).get(
            "DefaultPreemption") or {}
        pct = args.get("minCandidateNodesPercentage")
        abs_ = args.get("minCandidateNodesAbsolute")
        # null -> default (upstream nil-pointer defaulting); an explicit 0
        # is valid ("use only the other knob") and must survive
        self.min_candidate_pct = (
            MIN_CANDIDATE_NODES_PERCENTAGE if pct is None else int(pct))
        self.min_candidate_abs = (
            MIN_CANDIDATE_NODES_ABSOLUTE if abs_ is None else int(abs_))
        self._fit_cache: dict = {}
        self._fit_cw = reuse
        self._nodes: list[dict] | None = None   # store snapshot, per preempt()
        self._pods_all: list[dict] | None = None
        self._volumes: dict | None = None

    # ------------------------------------------------------------ oracle

    def _dry_run(self, pod: dict, removed: frozenset[str]):
        """The filters' verdicts on `pod` over every node, with the pods in
        `removed` (ns/name keys) deleted from the cluster -> (cw, rr).

        Each hypothesis recompiles workload tensors (cheap numpy) but the
        jitted scan is shared via replay's content-keyed cache, so only the
        first hypothesis of a given shape pays an XLA compile."""
        from .replay import replay
        from ..state.compile import NodeTableReuse, compile_workload

        bound = [
            (p, p["spec"]["nodeName"]) for p in self._pods_all
            if (p.get("spec") or {}).get("nodeName") and _pod_key(p) not in removed
        ]
        cw = compile_workload(
            self._nodes, [pod], self.plugin_config, bound_pods=bound,
            volumes=self._volumes, reuse=self._fit_cw,
            namespaces=self._namespaces,
        )
        self._fit_cw = NodeTableReuse(cw)  # shared across hypotheses
        # host-resident: the oracle reads the single pod's codes right
        # below, so device residency would just add an unoverlapped
        # round-trip (plus an attribution reduction nobody consumes)
        # per hypothesis
        return cw, replay(cw, chunk=1, filter_only=True, device_resident=False)

    @staticmethod
    def _active_filters(cw, pod_idx: int = 0) -> list[tuple[int, str]]:
        """(row in the codes, name) of the filters that ran for the pod."""
        return [(f, name) for f, name in enumerate(cw.config.filters())
                if not cw.host["filter_skip"][name][pod_idx]]

    def _fits(self, pod: dict, node_name: str, removed: frozenset[str]) -> bool:
        """Would `pod` pass all Filter plugins on `node_name` with the pods
        in `removed` (set of ns/name keys) deleted from the cluster?"""
        cache_key = (node_name, removed)
        hit = self._fit_cache.get(cache_key)
        if hit is not None:
            return hit

        TRACER.count("preemption_fit_probes_total")
        with TRACER.span("preempt_probe"):
            cw, rr = self._dry_run(pod, removed)
        try:
            j = cw.node_table.names.index(node_name)
        except ValueError:
            return False
        if int(rr.prefilter_reject[0]) != 0:
            # PreFilter still rejects the pod in the hypothesis (e.g. the
            # ReadWriteOncePod holder is not among the removed victims)
            self._fit_cache[cache_key] = False
            return False
        active = [f for f, _ in self._active_filters(cw)]
        ok = bool((rr.codes_of(0)[active, j] == 0).all()) if active else True
        self._fit_cache[cache_key] = ok
        return ok

    @classmethod
    def _hopeless(cls, failed_pass, nodes) -> set[str]:
        """Those of `nodes` that NodeResourcesFit refuses the pod even when
        EMPTY (module docstring, "the static rule"), from the host arrays
        of failed_pass = (the pass's CompiledWorkload, the pod's row in
        it)."""
        from ..plugins.noderesources import NAME_FIT, fit_refuses_empty

        if failed_pass is None:
            return set()
        cw, pod_idx = failed_pass
        if NAME_FIT not in (
                name for _, name in cls._active_filters(cw, pod_idx)):
            return set()
        static, requests = cw.host["fit"]
        refused = fit_refuses_empty(static, requests[pod_idx])
        if not refused.any():
            return set()
        idx = cw.node_table.name_idx
        return {node for node in nodes
                if (j := idx.get(node)) is not None and refused[j]}

    def _dry_run_refused(self, pod: dict,
                         lower_by_node: dict[str, list[dict]]) -> set[str]:
        """The nodes of `lower_by_node` that cannot take `pod` even with
        all their lower-priority pods gone: ONE dry run of the cluster
        minus every node's potential victims, read through the plugins
        whose verdict on a node depends on that node alone (module
        docstring, "The screen")."""
        removed = frozenset(
            _pod_key(p) for lower in lower_by_node.values() for p in lower)
        TRACER.count("preemption_screen_dry_runs_total")
        with TRACER.span("preempt_screen_dry_run", nodes=len(lower_by_node)):
            cw, rr = self._dry_run(pod, removed)
            local = [f for f, name in self._active_filters(cw)
                     if name in SCREEN_LOCAL_PLUGINS]
            if not local:
                return set()
            refused = (np.asarray(rr.codes_of(0))[local] != 0).any(axis=0)
            out = {name for name, no in zip(cw.node_table.names,
                                            refused.tolist())
                   if no and name in lower_by_node}
        TRACER.count("preemption_screen_refused_nodes_total", len(out))
        return out

    def _screen(self, pod: dict, lower_by_node: dict[str, list[dict]],
                failed_pass) -> dict[str, list[dict]]:
        """`lower_by_node` (node -> its lower-priority pods) less the nodes
        that cannot take `pod` even with all of those gone, and less the
        pods a gang protects: first the static rule, on host arrays alone;
        the cluster's snapshot and the batched dry run only if it leaves a
        node (module docstring, "The screen")."""
        with TRACER.span("preempt_screen", nodes=len(lower_by_node)):
            hopeless = self._hopeless(failed_pass, lower_by_node)
            TRACER.count("preemption_static_refused_nodes_total",
                         len(hopeless))
            if len(hopeless) == len(lower_by_node):
                return {}
            self._snapshot_cluster()
            left = {}
            for node, lower in lower_by_node.items():
                if node in hopeless:
                    continue
                lower = [p for p in lower
                         if _pod_key(p) not in self._gang_protected]
                if lower:
                    left[node] = lower
            refused = self._dry_run_refused(pod, left) if left else ()
        return {node: lower for node, lower in left.items()
                if node not in refused}

    # ------------------------------------------------------------ algorithm

    def _snapshot_cluster(self) -> None:
        """What only a dry run or a candidate reads: the store's nodes,
        volumes, namespaces and PDBs as they are now, and the gang members
        that no preemption may evict."""
        from ..cluster.store import list_shared, volume_manifests
        from .gang import GangDirectory, preemption_protected

        def _shared(resource):
            # read-only snapshot, no per-object deep copies
            return list_shared(self.store, resource)

        self._nodes = _shared("nodes")
        self._volumes = volume_manifests(self.store)
        try:
            self._pdbs = _shared("poddisruptionbudgets")
        except KeyError:
            self._pdbs = []
        self._namespaces = _shared("namespaces")
        # gang quorum guard (docs/gang-scheduling.md): bound PodGroup
        # members whose eviction would drop a running group below its
        # minMember are never preemption victims
        self._gang_protected = preemption_protected(
            self._pods_all, GangDirectory(self.store))

    def preempt(self, pod: dict, failed: list[tuple[str, str | None]],
                failed_pass=None) -> PreemptionOutcome:
        """failed: (node name, first failing plugin or None) for every node
        evaluated in the failed scheduling cycle.  failed_pass: (that
        cycle's CompiledWorkload, the pod's row in it), whose host arrays
        the static rule reads; None: no node is taken for hopeless, and
        every node with a lower-priority pod is dry-run."""
        from ..cluster.store import list_shared

        self._fit_cache.clear()
        out = PreemptionOutcome(evaluated_nodes=[n for n, _ in failed])
        TRACER.count("preemption_attempts_total")
        # touched on every attempt, so that a reader of the counters can
        # tell "no node was screened out / probed" from "no such counter"
        for counter in ("preemption_static_refused_nodes_total",
                        "preemption_screen_dry_runs_total",
                        "preemption_screen_refused_nodes_total",
                        "preemption_fit_probes_total"):
            TRACER.count(counter, 0)

        if ((pod.get("spec") or {}).get("preemptionPolicy") or "") == "Never":
            return out

        pod_prio = _priority(pod)
        potential = [
            n for n, plugin in failed
            if plugin is not None and plugin in RESOLVABLE_PLUGINS
        ]
        if not potential:
            return out

        # from here on an attempt reads what it uses and no more: the
        # pods, for the nodes that hold a lower-priority one; the static
        # rule on those; the rest of the cluster only if a node is left
        self._pods_all = list_shared(self.store, "pods")
        by_node: dict[str, list[dict]] = {}
        for p in self._pods_all:
            nn = (p.get("spec") or {}).get("nodeName")
            if nn and _priority(p) < pod_prio:
                by_node.setdefault(nn, []).append(p)
        # a node without a lower-priority pod is no candidate (upstream's
        # early return); the rest are screened, and only the nodes the
        # screen cannot rule out are looked at one by one
        lower_by_node = {node: by_node[node] for node in potential
                         if node in by_node}
        if lower_by_node:
            lower_by_node = self._screen(pod, lower_by_node, failed_pass)
        if not lower_by_node:
            return out

        budget = _num_candidates(len(potential), self.min_candidate_pct,
                                 self.min_candidate_abs)
        candidates: list[tuple[str, list[dict], int]] = []
        for node in potential:
            if len(candidates) >= budget:
                break
            if node not in lower_by_node:
                continue
            found = self._victims_on(node, lower_by_node[node], pod)
            if found is not None:
                victims, violations = found
                candidates.append((node, victims, violations))
        if not candidates:
            return out

        if self.extender_service is not None:
            candidates = self._call_extenders(pod, candidates)
            if not candidates:
                return out

        node, victims = self._select(candidates)
        out.nominated_node = node
        out.victims = victims
        return out

    def _call_extenders(self, pod: dict,
                        candidates: list[tuple[str, list[dict], int]]
                        ) -> list[tuple[str, list[dict], int]]:
        """upstream preemption callExtenders: each preempt-capable extender
        receives ExtenderPreemptionArgs{Pod, NodeNameToVictims} and returns
        a (possibly narrowed) node->victims map — whose NumPDBViolations
        REPLACES the locally computed count, as upstream builds the final
        candidates from the extender's answer; an unignorable error aborts
        preemption.  Each round-trip is recorded into
        extender-preempt-result by the service's store."""
        def _pods_of(victims_obj) -> list:
            # the k8s extender/v1 Victims json tag is lowercase "pods";
            # accept the capitalized Go-field spelling too (as the
            # node-map and UID keys already do)
            v = victims_obj or {}
            return v.get("Pods") or v.get("pods") or []

        def _nv_of(victims_obj) -> int:
            v = victims_obj or {}
            return int(v.get("NumPDBViolations")
                       or v.get("numPDBViolations") or 0)

        node_to_victims: dict[str, dict] = {
            node: {"Pods": victims, "NumPDBViolations": violations}
            for node, victims, violations in candidates
        }
        order = [node for node, _, _ in candidates]
        for idx, ext in enumerate(self.extender_service.extenders):
            if not ext.preempt_verb or not node_to_victims:
                continue
            if not ext.is_interested(pod):
                continue
            args = {"Pod": pod, "NodeNameToVictims": node_to_victims}
            try:
                result = self.extender_service.handle("preempt", idx, args)
            except Exception:
                if ext.ignorable:
                    continue
                return []  # non-ignorable extender error aborts preemption
            # key-presence lookup: an explicit {} answer ("no candidate
            # may be preempted") must not read as "no opinion"
            from ..scheduler.extender import pick_field as _field

            ret = _field(result, "NodeNameToVictims", "nodeNameToVictims")
            if ret is None:
                # nodeCacheCapable contract: MetaVictims carry pod UIDs
                meta = _field(result, "NodeNameToMetaVictims",
                              "nodeNameToMetaVictims")
                if meta is None:
                    continue
                ret = {}
                for node, mv in meta.items():
                    olds = {}
                    for v in _pods_of(node_to_victims.get(node)):
                        vm = v.get("metadata") or {}
                        olds[vm.get("uid") or vm.get("name", "")] = v
                    pods = [
                        olds[m.get("UID") or m.get("uid") or ""]
                        for m in _pods_of(mv)
                        if (m.get("UID") or m.get("uid") or "") in olds
                    ]
                    ret[node] = {"Pods": pods,
                                 "NumPDBViolations": (mv or {}).get("NumPDBViolations")
                                 or (mv or {}).get("numPDBViolations") or 0}
            else:
                ret = {n: {"Pods": _pods_of(v), "NumPDBViolations": _nv_of(v)}
                       for n, v in ret.items()}
            node_to_victims = {
                n: v for n, v in ret.items() if n in node_to_victims
            }
        return [
            (n, _pods_of(node_to_victims[n]), _nv_of(node_to_victims[n]))
            for n in order if n in node_to_victims
        ]

    def _victims_on(self, node: str, lower: list[dict], pod: dict
                    ) -> tuple[list[dict], int] | None:
        """(minimal victim set on `node`, #PDB-violating victims), or None
        if removing every one of `lower` (the node's lower-priority pods
        that no gang protects) still doesn't make `pod` fit.

        PDB handling follows upstream SelectVictimsOnNode: split the
        potential victims into PDB-violating and non-violating, reprieve
        the violating ones FIRST (so budget-covered pods are preferred as
        the ones actually evicted), and count the violating pods that
        could not be reprieved."""
        all_removed = frozenset(_pod_key(p) for p in lower)
        if not self._fits(pod, node, all_removed):
            return None
        # reprieve most-important-first (upstream MoreImportantPod order)
        lower = sorted(
            lower, key=lambda p: (-_priority(p), _creation(p), _pod_key(p)))
        violating, non_violating = filter_pods_with_pdb_violation(
            lower, self._pdbs or [])
        removed = set(all_removed)
        victims: list[dict] = []
        violations = 0

        def reprieve(v: dict) -> bool:
            removed.discard(_pod_key(v))
            if not self._fits(pod, node, frozenset(removed)):
                removed.add(_pod_key(v))
                victims.append(v)
                return False
            return True

        for v in violating:
            if not reprieve(v):
                violations += 1
        for v in non_violating:
            reprieve(v)
        # keep victim list in MoreImportantPod order (execution + records)
        order = {_pod_key(p): i for i, p in enumerate(lower)}
        victims.sort(key=lambda p: order[_pod_key(p)])
        return victims, violations

    @staticmethod
    def _select(candidates: list[tuple[str, list[dict], int]]
                ) -> tuple[str, list[dict]]:
        """upstream pickOneNodeForPreemption: fewest PDB violations, then
        the victim-priority/count/age tie-break ladder."""

        def rank(c: tuple[str, list[dict], int]):
            _, victims, violations = c
            if not victims:  # no-victim candidates win their violation tier
                return (violations, 0, 0, 0, 0, _InvStr(""))
            prios = [_priority(v) for v in victims]
            top = max(prios)
            # later creation must rank first; _InvStr inverts string order
            latest = max(_creation(v) for v in victims if _priority(v) == top)
            return (violations, 1, top, sum(prios), len(victims), _InvStr(latest))

        best = min(range(len(candidates)), key=lambda i: (rank(candidates[i]), i))
        node, victims, _ = candidates[best]
        return node, victims


class _InvStr(str):
    """String with inverted ordering (later timestamps rank first)."""

    def __lt__(self, other):  # noqa: D105
        return str.__gt__(self, other)

    def __gt__(self, other):  # noqa: D105
        return str.__lt__(self, other)
