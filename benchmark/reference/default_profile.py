"""The plain reference: the default kube-scheduler profile, one pod at a
time, one node at a time, in Python integers and float64.

It imports nothing of the program.  It is written from the upstream v1.32
plugins' semantics for exactly the object shapes the benchmark's
generators produce, and refuses (NotCovered) anything else, so a new
configuration cannot be "checked" by code that silently ignores half of
it.  Covered: NodeUnschedulable, NodeName, TaintToleration (nodes without
taints), NodeResourcesFit (LeastAllocated over cpu and memory, weight 1
each), InterPodAffinity (required pod-affinity terms with matchLabels and
an explicit namespace list; no anti-affinity, no preferred terms),
NodeResourcesBalancedAllocation, ImageLocality (nodes that list no
images), VolumeBinding (pods without volumes), and the PreFilter/PreScore
status maps of the whole default set.  The 13 result annotations are
rendered the way the reference simulator's Go encoder does: compact,
keys sorted.

Node index order is the store's listing order (sorted by name); ties in
the total score go to the lowest index, the documented divergence of this
simulator from upstream's random pick.

`arith` selects the arithmetic: EXACT is the configuration's stated one
(64-bit integers, float64).  NARROW32 is the control of the correctness
check: the same code in the nearest precision below (int32 wrap-around,
float32), the step that dropping x64 on the device would be.  It has to
come out as not equal.

What every file in reference/ gives the oracle child, which finds it by
the `reference` key of the configuration's file: KEYS (the result
annotations compared, the selected node last), ARITHMETICS (name ->
arithmetic, "exact" and the control's among them) and
ReferenceScheduler(nodes, bound_pods, arith) with
schedule_one(pod, annotate) -> (annotations or None, node).
"""

from __future__ import annotations

import json
import struct

PREFIX = "kube-scheduler-simulator.sigs.k8s.io/"
KEYS = [PREFIX + k for k in (
    "prefilter-result-status", "prefilter-result", "filter-result",
    "postfilter-result", "prescore-result", "score-result",
    "finalscore-result", "reserve-result", "permit-result",
    "permit-result-timeout", "prebind-result", "bind-result",
    "selected-node")]
(K_PREFILTER_STATUS, K_PREFILTER, K_FILTER, K_POSTFILTER, K_PRESCORE,
 K_SCORE, K_FINAL, K_RESERVE, K_PERMIT, K_PERMIT_TIMEOUT, K_PREBIND, K_BIND,
 K_SELECTED) = KEYS

MAX_NODE_SCORE = 100
# upstream v1.32 getDefaultPlugins (MultiPoint) order, per extension point
PREFILTERS = ["NodeAffinity", "NodePorts", "NodeResourcesFit",
              "VolumeRestrictions", "NodeVolumeLimits", "VolumeBinding",
              "VolumeZone", "PodTopologySpread", "InterPodAffinity"]
PRESCORERS = ["TaintToleration", "NodeAffinity", "NodeResourcesFit",
              "PodTopologySpread", "InterPodAffinity",
              "NodeResourcesBalancedAllocation"]
# (name, weight) of the scorers that run for the covered pods; NodeAffinity
# and PodTopologySpread skip a pod that carries no preference / constraint
SCORERS = [("TaintToleration", 3), ("NodeResourcesFit", 1),
           ("VolumeBinding", 1), ("InterPodAffinity", 2),
           ("NodeResourcesBalancedAllocation", 1), ("ImageLocality", 1)]
HARD_POD_AFFINITY_WEIGHT = 1


class NotCovered(Exception):
    """The object uses something this reference does not implement."""


class Exact:
    """The configuration's arithmetic: Python integers, float64."""
    name = "int64/float64"

    @staticmethod
    def i(x: int) -> int:
        return x

    @staticmethod
    def f(x: float) -> float:
        return x


class Narrow32:
    """The control's: two's-complement int32, float32."""
    name = "int32/float32"

    @staticmethod
    def i(x: int) -> int:
        return (x + 2 ** 31) % 2 ** 32 - 2 ** 31

    @staticmethod
    def f(x: float) -> float:
        return struct.unpack("f", struct.pack("f", x))[0]


ARITHMETICS = {"exact": Exact, "narrow32": Narrow32}


_SUFFIX = {"Ki": 2 ** 10, "Mi": 2 ** 20, "Gi": 2 ** 30, "Ti": 2 ** 40,
           "k": 10 ** 3, "M": 10 ** 6, "G": 10 ** 9, "T": 10 ** 12}


def quantity(s, milli: bool) -> int:
    """A Kubernetes quantity as an integer: milli-units for cpu, units
    (bytes, pods) otherwise."""
    s = str(s)
    scale = 1000 if milli else 1
    if s.endswith("m"):
        v = int(s[:-1])
        if not milli and v % 1000:
            raise NotCovered(f"fractional quantity {s!r}")
        return v if milli else v // 1000
    for suf, mult in _SUFFIX.items():
        if s.endswith(suf):
            return int(s[:-len(suf)]) * mult * scale
    return int(s) * scale


def marshal(obj) -> str:
    s = json.dumps(obj, sort_keys=True, separators=(",", ":"),
                   ensure_ascii=False)
    return (s.replace("<", "\\u003c").replace(">", "\\u003e")
            .replace("&", "\\u0026"))


def _pod_request(pod: dict) -> tuple[int, int]:
    """(milli-cpu, memory bytes) requested; the covered pods request both,
    so the scoring path's non-zero defaults (100m / 200Mi) never apply."""
    spec = pod.get("spec") or {}
    for k in ("initContainers", "overhead", "volumes", "nodeSelector",
              "tolerations", "topologySpreadConstraints", "schedulingGates",
              "priorityClassName", "priority"):
        if spec.get(k):
            raise NotCovered(f"pod spec.{k}")
    cpu = mem = 0
    for c in spec.get("containers") or []:
        for p in c.get("ports") or []:
            if p.get("hostPort"):
                raise NotCovered("hostPort")
        req = (c.get("resources") or {}).get("requests") or {}
        if set(req) - {"cpu", "memory"}:
            raise NotCovered(f"resource requests {sorted(req)}")
        c_cpu = quantity(req.get("cpu", "0"), milli=True)
        c_mem = quantity(req.get("memory", "0"), milli=False)
        if not c_cpu or not c_mem:
            raise NotCovered("a container without cpu or memory request")
        cpu += c_cpu
        mem += c_mem
    return cpu, mem


def _required_affinity_terms(pod: dict) -> list[dict]:
    aff = (pod.get("spec") or {}).get("affinity") or {}
    if set(aff) - {"podAffinity"}:
        raise NotCovered(f"affinity kinds {sorted(aff)}")
    pa = aff.get("podAffinity") or {}
    if set(pa) - {"requiredDuringSchedulingIgnoredDuringExecution"}:
        raise NotCovered("preferred pod affinity")
    terms = []
    for t in pa.get("requiredDuringSchedulingIgnoredDuringExecution") or []:
        if set(t) - {"labelSelector", "topologyKey", "namespaces"}:
            raise NotCovered(f"affinity term keys {sorted(t)}")
        sel = t.get("labelSelector") or {}
        if set(sel) - {"matchLabels"}:
            raise NotCovered("matchExpressions")
        ns = t.get("namespaces")
        terms.append({
            "key": t.get("topologyKey", ""),
            "match": {k: str(v) for k, v in
                      (sel.get("matchLabels") or {}).items()},
            # no namespace list: the owner's namespace (set by the caller)
            "namespaces": set(ns) if ns else None})
    return terms


class _Pod:
    __slots__ = ("name", "ns", "labels", "cpu", "mem", "terms")

    def __init__(self, manifest: dict):
        meta = manifest.get("metadata") or {}
        self.name = meta["name"]
        self.ns = meta.get("namespace") or "default"
        self.labels = {k: str(v) for k, v in (meta.get("labels") or {}).items()}
        self.cpu, self.mem = _pod_request(manifest)
        self.terms = _required_affinity_terms(manifest)
        for t in self.terms:
            if t["namespaces"] is None:
                t["namespaces"] = {self.ns}


def _term_matches(term: dict, target: _Pod) -> bool:
    if target.ns not in term["namespaces"]:
        return False
    return all(target.labels.get(k) == v for k, v in term["match"].items())


class ReferenceScheduler:
    """Cluster state plus `schedule_one`.  Initial pods arrive bound."""

    def __init__(self, nodes: list[dict], bound_pods: list[dict],
                 arith=Exact):
        self.A = arith
        nodes = sorted(nodes, key=lambda n: n["metadata"]["name"])
        self.names = [n["metadata"]["name"] for n in nodes]
        self.labels = []
        self.alloc_cpu, self.alloc_mem, self.allowed = [], [], []
        for n in nodes:
            spec, status = n.get("spec") or {}, n.get("status") or {}
            if spec.get("taints") or spec.get("unschedulable"):
                raise NotCovered("tainted or unschedulable node")
            if status.get("images"):
                raise NotCovered("node images")
            alloc = status.get("allocatable") or {}
            if set(alloc) - {"cpu", "memory", "pods"}:
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
        self.assigned: list[tuple[_Pod, int]] = []
        for m in bound_pods:
            self._bind(_Pod(m), idx[m["spec"]["nodeName"]])

    def _bind(self, pod: _Pod, j: int) -> None:
        A = self.A
        self.req_cpu[j] = A.i(self.req_cpu[j] + A.i(pod.cpu))
        self.req_mem[j] = A.i(self.req_mem[j] + A.i(pod.mem))
        self.num_pods[j] += 1
        self.assigned.append((pod, j))

    # ------------------------------------------------------------ plugins

    def _fit_filter(self, pod: _Pod, j: int) -> str | None:
        A = self.A
        reasons = []
        if self.num_pods[j] + 1 > self.allowed[j]:
            reasons.append("Too many pods")
        if A.i(pod.cpu) > A.i(self.alloc_cpu[j] - self.req_cpu[j]):
            reasons.append("Insufficient cpu")
        if A.i(pod.mem) > A.i(self.alloc_mem[j] - self.req_mem[j]):
            reasons.append("Insufficient memory")
        return ", ".join(reasons) if reasons else None

    def _affinity_state(self, pod: _Pod):
        """PreFilter: per required term, matching existing pods per domain
        value of the term's key, and their total over keyed nodes."""
        state = []
        for term in pod.terms:
            counts: dict[str, int] = {}
            total = 0
            for other, j in self.assigned:
                val = self.labels[j].get(term["key"])
                if val is not None and _term_matches(term, other):
                    counts[val] = counts.get(val, 0) + 1
                    total += 1
            state.append((term, counts, total))
        self_ok = all(_term_matches(t, pod) for t in pod.terms)
        return state, self_ok

    def _affinity_filter(self, state, self_ok: bool, j: int) -> str | None:
        lab = self.labels[j]
        if all(lab.get(t["key"]) is not None
               and counts.get(lab[t["key"]], 0) > 0 for t, counts, _ in state):
            return None
        # the first pod of a series: nothing matches anywhere, the pod
        # matches its own terms, and the node carries every term's key
        if (not any(total for _, _, total in state) and self_ok
                and all(t["key"] in lab for t, _, _ in state)):
            return None
        return "node(s) didn't match pod affinity rules"

    def _affinity_symmetry(self, pod: _Pod) -> dict[tuple[str, str], int]:
        """PreScore: existing pods' REQUIRED affinity terms that the
        incoming pod matches weigh hardPodAffinityWeight on their domain."""
        sym: dict[tuple[str, str], int] = {}
        for other, j in self.assigned:
            for term in other.terms:
                val = self.labels[j].get(term["key"])
                if val is not None and _term_matches(term, pod):
                    k = (term["key"], val)
                    sym[k] = sym.get(k, 0) + HARD_POD_AFFINITY_WEIGHT
        return sym

    def _least_allocated(self, requested: int, capacity: int) -> int:
        A = self.A
        if capacity == 0 or requested > capacity:
            return 0
        return A.i(A.i(A.i(capacity - requested) * MAX_NODE_SCORE) // capacity)

    def _resource_scores(self, pod: _Pod, j: int) -> tuple[int, int]:
        """(NodeResourcesFit, NodeResourcesBalancedAllocation) raw scores
        of node j with the pod on it."""
        A = self.A
        cpu = A.i(self.req_cpu[j] + A.i(pod.cpu))
        mem = A.i(self.req_mem[j] + A.i(pod.mem))
        fit_total = fit_w = 0
        fracs = []
        for r, cap in ((cpu, self.alloc_cpu[j]), (mem, self.alloc_mem[j])):
            if cap <= 0:
                continue  # a resource the node does not offer drops out
            fit_total += self._least_allocated(r, cap)
            fit_w += 1
            fracs.append(min(A.f(A.f(float(r)) / A.f(float(cap))), 1.0))
        std = A.f(abs(A.f(fracs[0] - fracs[1])) / 2.0) if len(fracs) == 2 else 0.0
        return (fit_total // fit_w if fit_w else 0,
                int(A.f(A.f(1.0 - std) * MAX_NODE_SCORE)))

    def _raw_scores(self, pod: _Pod, j: int, sym, memo: dict) -> dict[str, int]:
        # nodes in the same state score the same: worked out once per cycle
        state = (self.req_cpu[j], self.req_mem[j], self.alloc_cpu[j],
                 self.alloc_mem[j])
        res = memo.get(state)
        if res is None:
            res = memo[state] = self._resource_scores(pod, j)
        lab = self.labels[j]
        return {
            "TaintToleration": 0,
            "NodeResourcesFit": res[0],
            "VolumeBinding": 0,
            "InterPodAffinity": sum(d for (k, v), d in sym.items()
                                    if lab.get(k) == v),
            "NodeResourcesBalancedAllocation": res[1],
            "ImageLocality": 0,
        }

    @staticmethod
    def _normalize(name: str, raw: list[int]) -> list[int]:
        if name == "TaintToleration":  # reversed: fewest intolerable first
            mx = max(raw)
            return [MAX_NODE_SCORE - (s * MAX_NODE_SCORE // mx if mx else 0)
                    for s in raw]
        if name == "InterPodAffinity":
            mn, mx = min(raw), max(raw)
            return [int(MAX_NODE_SCORE * (float(s - mn) / float(mx - mn)))
                    if mx > mn else 0 for s in raw]
        return list(raw)  # no ScoreExtensions

    # -------------------------------------------------------------- cycle

    def schedule_one(self, manifest: dict, annotate: bool = True):
        """-> (annotations or None, selected node name or ""); binds."""
        pod = _Pod(manifest)
        affinity_on = bool(pod.terms)
        state = self_ok = None
        if affinity_on:
            state, self_ok = self._affinity_state(pod)
        filter_map: dict[str, dict[str, str]] = {}
        feasible: list[int] = []
        for j in range(self.n):
            msg = self._fit_filter(pod, j)
            fit_msg = msg
            if msg is None and affinity_on:
                msg = self._affinity_filter(state, self_ok, j)
            if annotate:  # filters run in order and stop at the first failure
                entry = {"NodeUnschedulable": "passed", "NodeName": "passed",
                         "TaintToleration": "passed",
                         "NodeResourcesFit": fit_msg or "passed"}
                if fit_msg is None and affinity_on:
                    entry["InterPodAffinity"] = msg or "passed"
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
            prescore = {nm: ("" if nm in ("NodeAffinity", "PodTopologySpread")
                             else "success") for nm in PRESCORERS}
            sym = self._affinity_symmetry(pod)
            memo: dict = {}
            raws = [self._raw_scores(pod, j, sym, memo) for j in feasible]
            totals = [0] * len(feasible)
            finals = {}
            for name, weight in SCORERS:
                normed = self._normalize(name, [r[name] for r in raws])
                finals[name] = [v * weight for v in normed]
                for i, v in enumerate(finals[name]):
                    totals[i] += v
            best = max(totals)
            selected = feasible[totals.index(best)]  # lowest index wins
            if annotate:
                for i, j in enumerate(feasible):
                    score_map[self.names[j]] = {
                        nm: str(raws[i][nm]) for nm, _ in SCORERS}
                    final_map[self.names[j]] = {
                        nm: str(finals[nm][i]) for nm, _ in SCORERS}
        if selected >= 0:
            self._bind(pod, selected)
        node = self.names[selected] if selected >= 0 else ""
        if not annotate:
            return None, node
        status = {nm: "" for nm in PREFILTERS}
        status["NodeResourcesFit"] = "success"
        if affinity_on:
            status["InterPodAffinity"] = "success"
        bound = {"VolumeBinding": "success"} if selected >= 0 else {}
        empty = marshal({})
        return {
            K_PREFILTER_STATUS: marshal(status),
            K_PREFILTER: empty,
            K_FILTER: marshal(filter_map),
            K_POSTFILTER: empty,
            K_PRESCORE: marshal(prescore),
            K_SCORE: marshal(score_map),
            K_FINAL: marshal(final_map),
            K_RESERVE: marshal(bound),
            K_PERMIT: empty,
            K_PERMIT_TIMEOUT: empty,
            K_PREBIND: marshal(bound),
            K_BIND: marshal({"DefaultBinder": "success"}
                            if selected >= 0 else {}),
            K_SELECTED: node,
        }, node
