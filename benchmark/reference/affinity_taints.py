"""The plain reference for a trimmed profile of four plugins over a
heterogeneous cluster: TaintToleration, NodeAffinity, NodeResourcesFit and
NodeResourcesBalancedAllocation (BASELINE config 3's lineup), on nodes of
different capacities, labels and taints, for pods that differ in
requests, node-affinity terms and tolerations.

One pod and one node at a time, Python integers and float64, written from
upstream v1.32; it builds on `default_profile.py`'s helpers (quantities,
the Go encoder's rendering, the two arithmetics, NodeResourcesFit's Filter
and the LeastAllocated / BalancedAllocation scores, which it inherits) and
imports nothing of the program.  The interface is the one stated at
`default_profile.py`'s head (KEYS, ARITHMETICS,
ReferenceScheduler(nodes, bound_pods, arith).schedule_one(pod, annotate)).

The lineup is the configuration's posted profile, `PROFILE` below: a
multiPoint list with every default disabled.  Upstream runs the plugins of
an extension point in the order of that list, so Filter is TaintToleration,
NodeAffinity, NodeResourcesFit; a node's filter-result entry holds them in
that order and stops at the first that refuses (the framework's
RunFilterPlugins returns at the first non-success status).  The weights
are the list's.  There is no queue-sort, PostFilter, Reserve, Permit or
PreBind plugin in it: those maps are empty, and a pod no node takes is
left pending with every node's refusal and an empty postfilter-result.
Bind is DefaultBinder, which the simulator always runs.

  * TaintToleration (plugins/tainttoleration/taint_toleration.go).
    Filter: the first taint of the node, in the node's own order, whose
    effect is NoSchedule or NoExecute and which no toleration of the pod
    tolerates (v1helper.FindMatchingUntoleratedTaint) refuses the node:
    "node(s) had untolerated taint {<key>: <value>}".  A toleration
    tolerates a taint when its effect is empty or the taint's, its key is
    the taint's (an empty key with Exists: every key), and its operator is
    Exists, or Equal (the default) with the taint's value
    (ToleratesTaint).  PreScore "success".  Score: the number of the
    node's PreferNoSchedule taints that no toleration of the pod with an
    empty or PreferNoSchedule effect tolerates
    (getAllTolerationPreferNoSchedule, countIntolerableTaintsPreferNoSchedule);
    NormalizeScore is DefaultNormalizeScore reversed over the feasible
    nodes: 100 - 100 * count / max, 100 everywhere where max is 0.
  * NodeAffinity (plugins/nodeaffinity/node_affinity.go).  PreFilter: Skip
    (status "", no Filter entry) for a pod with neither nodeSelector nor
    required terms; else "success" and, as no covered term has
    matchFields, no PreFilterResult.  Filter: the required
    nodeSelectorTerms are ORed, a term's matchExpressions ANDed
    (In / NotIn / Exists / DoesNotExist on the node's labels; a term
    without expressions matches nothing); no match refuses with "node(s)
    didn't match Pod's node affinity/selector".  PreScore: Skip (status "",
    no score entry) for a pod without preferred terms.  Score: the sum of
    the weights of the preferred terms whose `preference` matches the
    node (a term of weight 0 is skipped); NormalizeScore is
    DefaultNormalizeScore over the feasible nodes: 100 * sum / max, 0
    everywhere where max is 0.
  * NodeResourcesFit and NodeResourcesBalancedAllocation as
    `default_profile.py` has them, here on nine capacities: Fit's reasons
    in upstream's order (Too many pods, Insufficient cpu, Insufficient
    memory); LeastAllocated over cpu and memory, weight 1 each; the
    balanced score 100 * (1 - |cpu fraction - memory fraction| / 2).  A
    node's ephemeral-storage is allocatable that no covered pod requests:
    it refuses nothing and scores nothing.

With one feasible node the framework skips scoring (prescore, score and
final maps empty).  Ties in the total go to the lowest node index (the
store's order, by name): the simulator's documented divergence from
upstream's random pick.

Anything else raises NotCovered: a nodeSelector, matchFields, Gt / Lt, a
topology spread constraint, an inter-pod term, volumes, host ports,
priorities, init containers, extended resources, node images, an
unschedulable node, a taint effect or toleration operator this file does
not know.
"""

from __future__ import annotations

from reference.default_profile import (  # noqa: F401  (the interface)
    ARITHMETICS, K_BIND, K_FILTER, K_FINAL, K_PERMIT, K_PERMIT_TIMEOUT,
    K_POSTFILTER, K_PREBIND, K_PREFILTER, K_PREFILTER_STATUS, K_PRESCORE,
    K_RESERVE, K_SCORE, K_SELECTED, KEYS, MAX_NODE_SCORE, Exact, NotCovered,
    marshal, quantity)
from reference.default_profile import ReferenceScheduler as _DefaultProfile

# the posted profile: (plugin, weight) in the multiPoint list's order
PROFILE = [("TaintToleration", 3), ("NodeAffinity", 2),
           ("NodeResourcesFit", 1), ("NodeResourcesBalancedAllocation", 1)]
PREFILTERS = ["NodeAffinity", "NodeResourcesFit"]

ERR_AFFINITY = "node(s) didn't match Pod's node affinity/selector"
_HARD_EFFECTS = ("NoSchedule", "NoExecute")
_EFFECTS = _HARD_EFFECTS + ("PreferNoSchedule",)
_POD_SPEC_KEYS = {"containers", "affinity", "tolerations", "nodeName"}


def untolerated_taint_message(key: str, value: str) -> str:
    return "node(s) had untolerated taint {%s: %s}" % (key, value)


def _expressions(term: dict) -> list[tuple[str, str, frozenset]]:
    if set(term) - {"matchExpressions"}:
        raise NotCovered(f"node selector term keys {sorted(term)}")
    out = []
    for e in term.get("matchExpressions") or []:
        op = e.get("operator")
        if op not in ("In", "NotIn", "Exists", "DoesNotExist"):
            raise NotCovered(f"node selector operator {op!r}")
        out.append((e["key"], op, frozenset(e.get("values") or [])))
    return out


def _term_matches(exprs: list, labels: dict[str, str]) -> bool:
    """nodeaffinity.NodeSelectorTerm: every expression holds; a term
    without any matches no node."""
    if not exprs:
        return False
    for key, op, values in exprs:
        has = key in labels
        if op == "In":
            ok = has and labels[key] in values
        elif op == "NotIn":
            ok = not has or labels[key] not in values
        elif op == "Exists":
            ok = has
        else:
            ok = not has
        if not ok:
            return False
    return True


def _tolerates(tol: dict, taint: tuple[str, str, str]) -> bool:
    key, value, effect = taint
    if tol["effect"] and tol["effect"] != effect:
        return False
    if tol["key"] and tol["key"] != key:
        return False
    if tol["operator"] == "Exists":
        return True
    return tol["value"] == value


class _Pod:
    __slots__ = ("name", "cpu", "mem", "required", "preferred", "tolerations")

    def __init__(self, manifest: dict):
        self.name = manifest["metadata"]["name"]
        spec = manifest.get("spec") or {}
        if set(spec) - _POD_SPEC_KEYS:
            raise NotCovered(f"pod spec keys {sorted(set(spec) - _POD_SPEC_KEYS)}")
        self.cpu = self.mem = 0
        for c in spec.get("containers") or []:
            if c.get("ports"):
                raise NotCovered("container ports")
            res = c.get("resources") or {}
            req = res.get("requests") or {}
            if set(res) - {"requests"} or set(req) - {"cpu", "memory"}:
                raise NotCovered(f"container resources {res}")
            c_cpu = quantity(req.get("cpu", "0"), milli=True)
            c_mem = quantity(req.get("memory", "0"), milli=False)
            if not c_cpu or not c_mem:  # the scoring path's non-zero defaults
                raise NotCovered("a container without cpu or memory request")
            self.cpu += c_cpu
            self.mem += c_mem
        aff = spec.get("affinity") or {}
        if set(aff) - {"nodeAffinity"}:
            raise NotCovered(f"affinity kinds {sorted(aff)}")
        na = aff.get("nodeAffinity") or {}
        req_key = "requiredDuringSchedulingIgnoredDuringExecution"
        pref_key = "preferredDuringSchedulingIgnoredDuringExecution"
        if set(na) - {req_key, pref_key}:
            raise NotCovered(f"nodeAffinity keys {sorted(na)}")
        # None: no required terms at all (PreFilter Skips)
        self.required = None
        if na.get(req_key) is not None:
            if set(na[req_key]) - {"nodeSelectorTerms"}:
                raise NotCovered(f"required keys {sorted(na[req_key])}")
            self.required = [_expressions(t)
                             for t in na[req_key].get("nodeSelectorTerms") or []]
        self.preferred = []
        for t in na.get(pref_key) or []:
            if set(t) - {"weight", "preference"}:
                raise NotCovered(f"preferred term keys {sorted(t)}")
            self.preferred.append((int(t.get("weight", 0)),
                                   _expressions(t.get("preference") or {})))
        self.tolerations = []
        for t in spec.get("tolerations") or []:
            if set(t) - {"key", "operator", "value", "effect"}:
                raise NotCovered(f"toleration keys {sorted(t)}")
            op = t.get("operator") or "Equal"
            effect = t.get("effect") or ""
            if op not in ("Equal", "Exists") or effect not in ("",) + _EFFECTS:
                raise NotCovered(f"toleration {t}")
            self.tolerations.append({"key": t.get("key") or "", "operator": op,
                                     "value": t.get("value") or "",
                                     "effect": effect})


class ReferenceScheduler(_DefaultProfile):
    """Cluster state plus `schedule_one`.  Initial pods arrive bound.
    Inherits `_bind`, `_fit_filter`, `_least_allocated` and
    `_resource_scores` (they read the arrays made here)."""

    def __init__(self, nodes: list[dict], bound_pods: list[dict],
                 arith=Exact):
        self.A = arith
        nodes = sorted(nodes, key=lambda n: n["metadata"]["name"])
        self.names = [n["metadata"]["name"] for n in nodes]
        self.labels, self.taints = [], []
        self.alloc_cpu, self.alloc_mem, self.allowed = [], [], []
        for n in nodes:
            spec, status = n.get("spec") or {}, n.get("status") or {}
            if spec.get("unschedulable"):
                raise NotCovered("unschedulable node")
            if status.get("images"):
                raise NotCovered("node images")
            taints = []
            for t in spec.get("taints") or []:
                if t.get("effect") not in _EFFECTS:
                    raise NotCovered(f"taint effect {t.get('effect')!r}")
                taints.append((t.get("key", ""), t.get("value") or "",
                               t["effect"]))
            self.taints.append(taints)
            alloc = status.get("allocatable") or {}
            if set(alloc) - {"cpu", "memory", "pods", "ephemeral-storage"}:
                raise NotCovered(f"allocatable {sorted(alloc)}")
            self.labels.append({k: str(v) for k, v in
                                (n["metadata"].get("labels") or {}).items()})
            self.alloc_cpu.append(arith.i(quantity(alloc["cpu"], milli=True)))
            self.alloc_mem.append(arith.i(quantity(alloc["memory"], milli=False)))
            self.allowed.append(quantity(alloc["pods"], milli=False))
        self.n = len(nodes)
        idx = {nm: j for j, nm in enumerate(self.names)}
        self.req_cpu = [0] * self.n
        self.req_mem = [0] * self.n
        self.num_pods = [0] * self.n
        self.assigned: list = []
        for m in bound_pods:
            self._bind(_Pod(m), idx[m["spec"]["nodeName"]])

    # ------------------------------------------------------------ plugins

    def _taint_filter(self, pod: _Pod, j: int) -> str | None:
        for taint in self.taints[j]:
            if taint[2] in _HARD_EFFECTS and not any(
                    _tolerates(t, taint) for t in pod.tolerations):
                return untolerated_taint_message(taint[0], taint[1])
        return None

    def _taint_score(self, soft: list[dict], j: int) -> int:
        return sum(1 for taint in self.taints[j]
                   if taint[2] == "PreferNoSchedule"
                   and not any(_tolerates(t, taint) for t in soft))

    def _affinity_filter(self, pod: _Pod, j: int) -> str | None:
        if any(_term_matches(exprs, self.labels[j]) for exprs in pod.required):
            return None
        return ERR_AFFINITY

    def _affinity_score(self, pod: _Pod, j: int) -> int:
        A, total = self.A, 0
        for weight, exprs in pod.preferred:
            if weight and _term_matches(exprs, self.labels[j]):
                total = A.i(total + weight)
        return total

    def _normalize(self, raw: list[int], reverse: bool) -> list[int]:
        """helper.DefaultNormalizeScore(MaxNodeScore, reverse)."""
        A, mx = self.A, max(raw)
        if mx == 0:
            return [MAX_NODE_SCORE if reverse else 0] * len(raw)
        out = [A.i(A.i(MAX_NODE_SCORE * s) // mx) for s in raw]
        return [MAX_NODE_SCORE - s for s in out] if reverse else out

    # -------------------------------------------------------------- cycle

    def schedule_one(self, manifest: dict, annotate: bool = True):
        """-> (annotations or None, selected node name or ""); binds."""
        A = self.A
        pod = _Pod(manifest)
        affinity_filters = pod.required is not None
        affinity_scores = bool(pod.preferred)
        filter_map: dict[str, dict[str, str]] = {}
        feasible: list[int] = []
        for j in range(self.n):
            entry = {}
            msg = self._taint_filter(pod, j)
            entry["TaintToleration"] = msg or "passed"
            if msg is None and affinity_filters:
                msg = self._affinity_filter(pod, j)
                entry["NodeAffinity"] = msg or "passed"
            if msg is None:
                msg = self._fit_filter(pod, j)
                entry["NodeResourcesFit"] = msg or "passed"
            if annotate:
                filter_map[self.names[j]] = entry
            if msg is None:
                feasible.append(j)

        prescore: dict[str, str] = {}
        score_map: dict[str, dict[str, str]] = {}
        final_map: dict[str, dict[str, str]] = {}
        selected = -1
        if len(feasible) == 1:
            selected = feasible[0]
        elif feasible:
            prescore = {name: "success" for name, _ in PROFILE}
            if not affinity_scores:
                prescore["NodeAffinity"] = ""
            soft = [t for t in pod.tolerations
                    if t["effect"] in ("", "PreferNoSchedule")]
            memo: dict = {}
            raw: dict[str, list[int]] = {name: [] for name, _ in PROFILE}
            for j in feasible:
                state = (self.req_cpu[j], self.req_mem[j], self.alloc_cpu[j],
                         self.alloc_mem[j])
                res = memo.get(state)
                if res is None:  # nodes in the same state score the same
                    res = memo[state] = self._resource_scores(pod, j)
                raw["NodeResourcesFit"].append(res[0])
                raw["NodeResourcesBalancedAllocation"].append(res[1])
                raw["TaintToleration"].append(self._taint_score(soft, j))
                raw["NodeAffinity"].append(self._affinity_score(pod, j))
            if not affinity_scores:
                del raw["NodeAffinity"]
            normed = dict(raw)
            normed["TaintToleration"] = self._normalize(
                raw["TaintToleration"], reverse=True)
            if affinity_scores:
                normed["NodeAffinity"] = self._normalize(
                    raw["NodeAffinity"], reverse=False)
            finals = {name: [A.i(v * weight) for v in normed[name]]
                      for name, weight in PROFILE if name in normed}
            totals = [0] * len(feasible)
            for values in finals.values():
                for i, v in enumerate(values):
                    totals[i] = A.i(totals[i] + v)
            selected = feasible[totals.index(max(totals))]  # lowest index wins
            if annotate:
                for i, j in enumerate(feasible):
                    score_map[self.names[j]] = {
                        name: str(values[i]) for name, values in raw.items()}
                    final_map[self.names[j]] = {
                        name: str(values[i]) for name, values in finals.items()}
        if selected >= 0:
            self._bind(pod, selected)
        node = self.names[selected] if selected >= 0 else ""
        if not annotate:
            return None, node
        status = {"NodeAffinity": "success" if affinity_filters else "",
                  "NodeResourcesFit": "success"}
        empty = marshal({})
        return {
            K_PREFILTER_STATUS: marshal(status),
            K_PREFILTER: empty,
            K_FILTER: marshal(filter_map),
            K_POSTFILTER: empty,
            K_PRESCORE: marshal(prescore),
            K_SCORE: marshal(score_map),
            K_FINAL: marshal(final_map),
            K_RESERVE: empty,
            K_PERMIT: empty,
            K_PERMIT_TIMEOUT: empty,
            K_PREBIND: empty,
            K_BIND: marshal({"DefaultBinder": "success"}
                            if selected >= 0 else {}),
            K_SELECTED: node,
        }, node
