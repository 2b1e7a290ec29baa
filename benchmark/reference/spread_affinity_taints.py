"""The plain reference for BASELINE config 4's profile: `affinity_taints.py`'s
four plugins (TaintToleration, NodeAffinity, NodeResourcesFit,
NodeResourcesBalancedAllocation: imported, not copied) plus
PodTopologySpread, over a heterogeneous cluster, for pods that carry a
`DoNotSchedule` constraint over zones and a `ScheduleAnyway` constraint over
hostnames beside their node-affinity terms and tolerations.

One pod and one node at a time, Python integers and float64, written from
upstream v1.32 pkg/scheduler/framework/plugins/podtopologyspread
(`common.go`, `filtering.go`, `scoring.go`); it imports nothing of the
program.  The interface is the one stated at `default_profile.py`'s head
(KEYS, ARITHMETICS, ReferenceScheduler(nodes, bound_pods,
arith).schedule_one(pod, annotate)).

The lineup is the configuration's posted profile, `PROFILE` below, in
upstream's default plugin order: Filter is TaintToleration, NodeAffinity,
NodeResourcesFit, PodTopologySpread, and a node's filter-result entry stops
at the first that refuses; PodTopologySpread scores at weight 2.

PodTopologySpread, as upstream runs it (the node inclusion policies are on
by default since v1.26; every covered constraint has the defaults,
`nodeAffinityPolicy: Honor`, `nodeTaintsPolicy: Ignore`):

  * PreFilter (calPreFilterState).  Skip (status "", no Filter entry) for
    a pod without a `DoNotSchedule` constraint.  Otherwise, for every
    node that carries the topology key of EVERY `DoNotSchedule`
    constraint of the pod (nodeLabelsMatchSpreadConstraints) and, per
    constraint, passes its inclusion policies
    (matchNodeInclusionPolicies: Honor asks that the node match the
    INCOMING pod's required node affinity), the constraint's
    TpValueToMatchNum[value of the node's key] grows by the number of the
    node's pods in the incoming pod's namespace whose labels match the
    constraint's selector (countPodsMatchSelector; no covered pod is
    terminating); a value enters the map with 0.  Upstream counts BY
    NODE: a pod on a node that the policies leave out is not counted,
    even where another node of its zone is kept.  The minimum is over the
    map's values (the critical path), and 0 where the map has fewer
    entries than minDomains (nil: 1, so an empty map reads 0).
  * Filter.  In the pod's order of constraints, the first that fails
    decides: a node without the key refuses with "node(s) didn't match
    pod topology spread constraints (missing required label)"; otherwise
    matchNum (the map's entry for the node's value, 0 where there is
    none) + selfMatch (1 where the pod's own labels match the selector) -
    minMatchNum > maxSkew refuses with "node(s) didn't match pod topology
    spread constraints".
  * PreScore, over the FILTERED nodes.  Skip (status "", no score entry)
    for a pod without a `ScheduleAnyway` constraint.  A filtered node that
    lacks the key of any such constraint is ignored.  Per constraint the
    topologyNormalizingWeight is math.Log(sz + 2): for the key
    kubernetes.io/hostname sz is the number of filtered nodes less the
    ignored ones; for any other key, the number of distinct values of
    the key among the filtered nodes that are not ignored, and
    TopologyPairToPodCounts is then counted for those values over every
    node of the cluster that carries every scored key and passes the
    constraint's inclusion policies.
  * Score: 0 for an ignored node; else the sum over the constraints of
    scoreForCount(cnt, maxSkew, weight) = cnt * weight + (maxSkew - 1),
    math.Round'ed, cnt being the node's OWN matching pods for the
    hostname key (counted in Score from nodeInfo.Pods, whatever the
    policies say) and the pair's count otherwise.
  * NormalizeScore: over the filtered nodes that are not ignored,
    100 * (max + min - score) / max in integers, 100 everywhere where max
    is 0; an ignored node gets 0.

With one feasible node the framework skips scoring.  Ties in the total go
to the lowest node index, the simulator's documented divergence.

Anything else raises NotCovered: matchLabelKeys, minDomains, a
`nodeAffinityPolicy` or `nodeTaintsPolicy` on the constraint,
matchExpressions in a constraint's selector, a constraint without
labelSelector, more constraints than four, and all that
`affinity_taints.py` refuses (nodeSelector, matchFields, inter-pod terms,
volumes, ports, ...).  What it cannot see: system-default constraints (the
covered pods carry their own), a taint policy of Honor
(`node_inclusion.py` covers that, for one constraint), and a
`ScheduleAnyway` constraint over a key that is not the hostname is
written here but met by no benchmark cell.

`scoreForCount` and `topologyNormalizingWeight` are written from memory of
v1.32's `scoring.go`, not read from it here; Python's `math.log` stands
for Go's `math.Log` (both are the platform's correctly-rounded-or-nearly
libm double logarithm).
"""

from __future__ import annotations

import math

from reference.affinity_taints import (  # noqa: F401  (the interface)
    ARITHMETICS, K_BIND, K_FILTER, K_FINAL, K_PERMIT, K_PERMIT_TIMEOUT,
    K_POSTFILTER, K_PREBIND, K_PREFILTER, K_PREFILTER_STATUS, K_PRESCORE,
    K_RESERVE, K_SCORE, K_SELECTED, KEYS, MAX_NODE_SCORE, Exact, NotCovered,
    _term_matches, marshal)
from reference.affinity_taints import ReferenceScheduler as _FourPlugins
from reference.affinity_taints import _Pod as _PlainPod

# the posted profile: (plugin, weight) in the multiPoint list's order,
# which is upstream's default order
PROFILE = [("TaintToleration", 3), ("NodeAffinity", 2),
           ("NodeResourcesFit", 1), ("PodTopologySpread", 2),
           ("NodeResourcesBalancedAllocation", 1)]
NAME = "PodTopologySpread"
ERR_SKEW = "node(s) didn't match pod topology spread constraints"
ERR_MISSING_LABEL = ("node(s) didn't match pod topology spread constraints "
                     "(missing required label)")
HOSTNAME = "kubernetes.io/hostname"     # v1.LabelHostname
MAX_INT32 = 2 ** 31 - 1                 # newCriticalPaths' initial MatchNum
MIN_DOMAINS = 1                         # a nil minDomains
MAX_CONSTRAINTS = 4
_CONSTRAINT_KEYS = {"maxSkew", "topologyKey", "whenUnsatisfiable",
                    "labelSelector"}


def _go_round(x: float) -> int:
    """math.Round for x >= 0: half away from zero."""
    t = math.trunc(x)
    return t + (1 if x - t >= 0.5 else 0)


def _constraints(manifest: dict) -> tuple[list[dict], list[dict]]:
    """-> (the pod's DoNotSchedule constraints, its ScheduleAnyway ones),
    each in the pod's order (filterTopologySpreadConstraints)."""
    raw = (manifest.get("spec") or {}).get("topologySpreadConstraints") or []
    if len(raw) > MAX_CONSTRAINTS:
        raise NotCovered(f"{len(raw)} topology spread constraints")
    hard, soft = [], []
    for c in raw:
        if set(c) - _CONSTRAINT_KEYS:
            raise NotCovered(
                f"constraint keys {sorted(set(c) - _CONSTRAINT_KEYS)}")
        action = c.get("whenUnsatisfiable", "DoNotSchedule")
        if action not in ("DoNotSchedule", "ScheduleAnyway"):
            raise NotCovered(f"whenUnsatisfiable {action}")
        sel = c.get("labelSelector")
        if sel is None:
            raise NotCovered("a constraint without labelSelector")
        if set(sel) - {"matchLabels"}:
            raise NotCovered("matchExpressions")
        one = {"key": c["topologyKey"], "max_skew": int(c["maxSkew"]),
               "match": {k: str(v) for k, v in
                         (sel.get("matchLabels") or {}).items()}}
        (hard if action == "DoNotSchedule" else soft).append(one)
    return hard, soft


def _selects(match: dict[str, str], labels: dict[str, str]) -> bool:
    return all(labels.get(k) == v for k, v in match.items())


class _Pod(_PlainPod):
    __slots__ = ("ns", "labels", "hard", "soft")

    def __init__(self, manifest: dict):
        meta = manifest.get("metadata") or {}
        self.ns = meta.get("namespace") or "default"
        self.labels = {k: str(v) for k, v in (meta.get("labels") or {}).items()}
        self.hard, self.soft = _constraints(manifest)
        # what is left of the manifest is affinity_taints' pod
        spec = dict(manifest.get("spec") or {})
        spec.pop("topologySpreadConstraints", None)
        super().__init__(dict(manifest, spec=spec))


class ReferenceScheduler(_FourPlugins):
    """affinity_taints.py's cluster state and four plugins; the spread
    constraints and the cycle are this file's."""

    def __init__(self, nodes: list[dict], bound_pods: list[dict],
                 arith=Exact):
        super().__init__(nodes, [], arith)
        idx = {nm: j for j, nm in enumerate(self.names)}
        for m in bound_pods:
            self._bind(_Pod(m), idx[m["spec"]["nodeName"]])

    # --------------------------------------------------- PodTopologySpread

    def _included(self, pod: _Pod, j: int) -> bool:
        """matchNodeInclusionPolicies under the defaults: Honor the
        incoming pod's required node affinity, Ignore the taints."""
        return pod.required is None or any(
            _term_matches(exprs, self.labels[j]) for exprs in pod.required)

    def _matching_on(self, pod: _Pod, match: dict[str, str]) -> list[int]:
        """countPodsMatchSelector for every node: the bound pods in the
        incoming pod's namespace whose labels match."""
        out = [0] * self.n
        for other, j in self.assigned:
            if other.ns == pod.ns and _selects(match, other.labels):
                out[j] += 1
        return out

    def _spread_filter(self, pod: _Pod):
        """-> check(j) over this cycle's PreFilter state, or None where
        PreFilter returns Skip."""
        if not pod.hard:
            return None
        A = self.A
        keyed = [all(c["key"] in self.labels[j] for c in pod.hard)
                 for j in range(self.n)]
        included = [keyed[j] and self._included(pod, j) for j in range(self.n)]
        state = []
        for c in pod.hard:
            on_node = self._matching_on(pod, c["match"])
            match_num: dict[str, int] = {}
            for j in range(self.n):
                if included[j]:
                    value = self.labels[j][c["key"]]
                    match_num[value] = A.i(match_num.get(value, 0) + on_node[j])
            min_match = min(match_num.values(), default=MAX_INT32)
            if len(match_num) < MIN_DOMAINS:
                min_match = 0
            state.append((c, match_num, min_match,
                          int(_selects(c["match"], pod.labels))))

        def check(j: int) -> str | None:
            for c, match_num, min_match, self_match in state:
                value = self.labels[j].get(c["key"])
                if value is None:
                    return ERR_MISSING_LABEL
                skew = A.i(match_num.get(value, 0) + self_match - min_match)
                if skew > c["max_skew"]:
                    return ERR_SKEW
            return None

        return check

    def _spread_scores(self, pod: _Pod, feasible: list[int]):
        """PreScore, Score and NormalizeScore over the filtered nodes ->
        (raw, normalized), aligned with `feasible`; None where PreScore
        returns Skip."""
        if not pod.soft:
            return None
        A = self.A
        keyed = [all(c["key"] in self.labels[j] for c in pod.soft)
                 for j in range(self.n)]
        live = [j for j in feasible if keyed[j]]      # not ignored
        state = []
        for c in pod.soft:
            on_node = self._matching_on(pod, c["match"])
            if c["key"] == HOSTNAME:
                pairs, size = None, len(live)
            else:
                pairs = {self.labels[j][c["key"]]: 0 for j in live}
                for j in range(self.n):
                    value = self.labels[j].get(c["key"])
                    if (keyed[j] and self._included(pod, j)
                            and value in pairs):
                        pairs[value] = A.i(pairs[value] + on_node[j])
                size = len(pairs)
            # topologyNormalizingWeight
            state.append((c, on_node, pairs, A.f(math.log(A.f(float(size + 2))))))
        raw = []
        for j in feasible:
            if not keyed[j]:
                raw.append(0)
                continue
            score = 0.0
            for c, on_node, pairs, weight in state:
                cnt = (on_node[j] if pairs is None
                       else pairs[self.labels[j][c["key"]]])
                # scoreForCount
                score = A.f(score + A.f(A.f(A.f(float(cnt)) * weight)
                                        + A.f(float(c["max_skew"] - 1))))
            raw.append(A.i(_go_round(score)))
        scored = [s for s, j in zip(raw, feasible) if keyed[j]]
        mn, mx = min(scored, default=0), max(scored, default=0)
        normed = []
        for s, j in zip(raw, feasible):
            if not keyed[j]:
                normed.append(0)
            elif mx == 0:
                normed.append(MAX_NODE_SCORE)
            else:
                normed.append(A.i(A.i(MAX_NODE_SCORE * A.i(mx + mn - s)) // mx))
        return raw, normed

    # -------------------------------------------------------------- cycle

    def schedule_one(self, manifest: dict, annotate: bool = True):
        """-> (annotations or None, selected node name or ""); binds."""
        A = self.A
        pod = _Pod(manifest)
        affinity_filters = pod.required is not None
        affinity_scores = bool(pod.preferred)
        spread = self._spread_filter(pod)
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
            if msg is None and spread is not None:
                msg = spread(j)
                entry[NAME] = msg or "passed"
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
            spread_scores = self._spread_scores(pod, feasible)
            prescore = {name: "success" for name, _ in PROFILE}
            if not affinity_scores:
                prescore["NodeAffinity"] = ""
            if spread_scores is None:
                prescore[NAME] = ""
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
            normed = dict(raw)
            normed["TaintToleration"] = self._normalize(
                raw["TaintToleration"], reverse=True)
            if affinity_scores:
                normed["NodeAffinity"] = self._normalize(
                    raw["NodeAffinity"], reverse=False)
            else:
                del raw["NodeAffinity"], normed["NodeAffinity"]
            if spread_scores is None:
                del raw[NAME], normed[NAME]
            else:
                raw[NAME], normed[NAME] = spread_scores
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
                  "NodeResourcesFit": "success",
                  NAME: "success" if spread is not None else ""}
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
