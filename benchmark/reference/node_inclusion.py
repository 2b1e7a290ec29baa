"""The plain reference for deployments whose nodes differ: tainted nodes
beside plain ones, and pods spread over a topology key with the node
inclusion policies (SchedulingWithNodeInclusionPolicy).

One pod and one node at a time, Python integers and float64; imports
`default_profile.py`'s helpers and `antiaffinity.py`'s rendering of a
refusal, and nothing of the program.  The interface is the one stated at
`default_profile.py`'s head (KEYS, ARITHMETICS,
ReferenceScheduler(nodes, bound_pods, arith).schedule_one(pod, annotate)).

What it adds to those two, from upstream v1.32:

  * TaintToleration (plugins/tainttoleration/taint_toleration.go).
    Filter: the FIRST taint of the node, in the node's own order, whose
    effect is NoSchedule or NoExecute and which the pod does not tolerate
    refuses the node: "node(s) had untolerated taint {<key>: <value>}" (a
    taint without a value renders the empty string).  The covered pods
    carry no toleration, so every such taint is untolerated.  PreScore
    ("success") and Score over the feasible nodes: the count of
    intolerable PreferNoSchedule taints, 0 on every covered node, and
    DefaultNormalizeScore reversed (`default_profile._normalize`: 100
    everywhere where the maximum is 0).  It is the 3rd Filter plugin: a
    tainted node's entry is NodeUnschedulable, NodeName "passed", then
    the message, and nothing after it.
  * PodTopologySpread (plugins/podtopologyspread/{common,filtering,
    scoring}.go) for a pod with ONE DoNotSchedule constraint.
    PreFilter (calPreFilterState): status "success", no PreFilterResult.
    A node takes part when it carries the constraint's topology key and
    passes the constraint's inclusion policies
    (matchNodeInclusionPolicies): `nodeAffinityPolicy` Honor, the
    default, asks that the node match the pod's nodeSelector and required
    node affinity (the covered pods have neither: every node matches);
    `nodeTaintsPolicy` Honor asks that the node carry no untolerated
    NoSchedule / NoExecute taint, Ignore, the default, asks nothing.  For
    every topology pair (key, value) of a node that takes part,
    TpPairToMatchNum is the count, over those nodes, of the bound pods in
    the incoming pod's namespace that match the constraint's selector
    (countPodsMatchSelector; no covered pod is terminating); the minimum
    (the critical path) is over those pairs, and math.MaxInt32 where
    there is none.  Upstream counts by NODE: a pod on a node that does
    not take part is not counted even where another node of its domain
    does; this reference counts the same way.
    Filter, on every node TaintToleration and NodeResourcesFit passed
    (it is the 11th Filter plugin of the default set, the 5th that does
    not Skip here): a node without the key refuses with
    "node(s) didn't match pod topology spread constraints (missing
    required label)"; otherwise matchNum (the node's pair's count, 0 for
    a pair that is not in the map: a node that takes no part) + selfMatch
    (1 where the pod's own labels match the selector) - minMatchNum >
    maxSkew refuses with "node(s) didn't match pod topology spread
    constraints".  minDomains is not covered (nil: 1 domain is enough).
    PreScore: a pod without a ScheduleAnyway constraint Skips (status "",
    no score entry), as in `default_profile.py`.
  * both refusals through `antiaffinity.py`'s `run_filters` / `render`:
    the score maps and the normalisation are over the feasible nodes
    only, and a pod no node takes is left pending with a postfilter-result
    that lists every refused node with an empty map.

A pod without constraints takes `antiaffinity.py`'s cycle with the taints'
Filter in it.  Anything else raises NotCovered: a toleration on the pod,
PreferNoSchedule taints, a taint effect this file does not know,
`whenUnsatisfiable: ScheduleAnyway`, more than one constraint,
matchExpressions, a constraint without labelSelector, matchLabelKeys,
minDomains, `nodeAffinityPolicy: Ignore`, and all that its parents refuse
(nodeSelector, node affinity, volumes, ports, priorities, ...).
"""

from __future__ import annotations

from reference.antiaffinity import (  # noqa: F401  (the interface)
    ARITHMETICS, KEYS, PREFILTERS, PRESCORERS, SCORERS, Exact, NotCovered,
    _passes, render, run_filters)
from reference.antiaffinity import ReferenceScheduler as _Refusals
from reference.antiaffinity import _Pod as _PlainPod

ERR_SKEW = "node(s) didn't match pod topology spread constraints"
ERR_MISSING_LABEL = ("node(s) didn't match pod topology spread constraints "
                     "(missing required label)")
MAX_INT32 = 2 ** 31 - 1   # newCriticalPaths' initial MatchNum

_DO_NOT_SCHEDULE = ("NoSchedule", "NoExecute")
_CONSTRAINT_KEYS = {"maxSkew", "topologyKey", "whenUnsatisfiable",
                    "labelSelector", "nodeAffinityPolicy", "nodeTaintsPolicy"}


def untolerated_taint_message(key: str, value: str) -> str:
    return "node(s) had untolerated taint {%s: %s}" % (key, value)


def _node_taints(node: dict) -> list[tuple[str, str]]:
    """The node's (key, value) taints, in its own order; every covered one
    has an effect that Filter looks at."""
    taints = []
    for t in (node.get("spec") or {}).get("taints") or []:
        effect = t.get("effect") or ""
        if effect not in _DO_NOT_SCHEDULE:
            raise NotCovered(f"taint effect {effect!r}")
        taints.append((t.get("key", ""), t.get("value") or ""))
    return taints


def _selects(match: dict[str, str], labels: dict[str, str]) -> bool:
    return all(labels.get(k) == v for k, v in match.items())


def _constraint(manifest: dict) -> dict | None:
    """The pod's one DoNotSchedule constraint, or None for a pod without
    topologySpreadConstraints."""
    raw = (manifest.get("spec") or {}).get("topologySpreadConstraints") or []
    if not raw:
        return None
    if len(raw) > 1:
        raise NotCovered(f"{len(raw)} topology spread constraints")
    c = raw[0]
    if set(c) - _CONSTRAINT_KEYS:
        raise NotCovered(f"constraint keys {sorted(set(c) - _CONSTRAINT_KEYS)}")
    if c.get("whenUnsatisfiable", "DoNotSchedule") != "DoNotSchedule":
        raise NotCovered(f"whenUnsatisfiable {c['whenUnsatisfiable']}")
    if (c.get("nodeAffinityPolicy") or "Honor") != "Honor":
        raise NotCovered("nodeAffinityPolicy Ignore")
    taints_policy = c.get("nodeTaintsPolicy") or "Ignore"
    if taints_policy not in ("Honor", "Ignore"):
        raise NotCovered(f"nodeTaintsPolicy {taints_policy}")
    sel = c.get("labelSelector")
    if sel is None:
        raise NotCovered("a constraint without labelSelector")
    if set(sel) - {"matchLabels"}:
        raise NotCovered("matchExpressions")
    return {"key": c["topologyKey"], "max_skew": int(c["maxSkew"]),
            "match": {k: str(v) for k, v in
                      (sel.get("matchLabels") or {}).items()},
            "honor_taints": taints_policy == "Honor"}


class _Pod(_PlainPod):
    __slots__ = ("constraint",)

    def __init__(self, manifest: dict):
        self.constraint = _constraint(manifest)
        # what is left of the manifest is antiaffinity's pod (a toleration
        # or a nodeSelector on it is refused there)
        spec = dict(manifest.get("spec") or {})
        spec.pop("topologySpreadConstraints", None)
        super().__init__(dict(manifest, spec=spec))


class ReferenceScheduler(_Refusals):
    """antiaffinity.py's cluster state, resource plugins and refusals; the
    taints, the spread constraint and the cycle are this file's."""

    def __init__(self, nodes: list[dict], bound_pods: list[dict],
                 arith=Exact):
        # the parents refuse a tainted node: they see the nodes without
        # their taints, which this file keeps, in the parents' node order
        plain = [dict(n, spec={k: v for k, v in (n.get("spec") or {}).items()
                               if k != "taints"}) for n in nodes]
        super().__init__(plain, [], arith)
        taints = {n["metadata"]["name"]: _node_taints(n) for n in nodes}
        self.taints = [taints[nm] for nm in self.names]
        idx = {nm: j for j, nm in enumerate(self.names)}
        for m in bound_pods:
            self._bind(_Pod(m), idx[m["spec"]["nodeName"]])

    # ----------------------------------------------------- TaintToleration

    def _taint_filter(self, j: int) -> str | None:
        """No covered pod tolerates anything: the first NoSchedule /
        NoExecute taint is the first untolerated one."""
        if not self.taints[j]:
            return None
        return untolerated_taint_message(*self.taints[j][0])

    # --------------------------------------------------- PodTopologySpread

    def _spread_filter(self, pod: _Pod):
        """-> check(j) over this cycle's PreFilter state, or None where
        upstream's PreFilter returns Skip (no DoNotSchedule constraint)."""
        c = pod.constraint
        if c is None:
            return None
        key = c["key"]

        def takes_part(j: int) -> bool:
            if key not in self.labels[j]:
                return False
            return not (c["honor_taints"] and self.taints[j])

        match_num: dict[str, int] = {
            self.labels[j][key]: 0 for j in range(self.n) if takes_part(j)}
        for other, j in self.assigned:
            if (takes_part(j) and other.ns == pod.ns
                    and _selects(c["match"], other.labels)):
                match_num[self.labels[j][key]] += 1
        min_match = min(match_num.values(), default=MAX_INT32)
        self_match = int(_selects(c["match"], pod.labels))

        def check(j: int) -> str | None:
            value = self.labels[j].get(key)
            if value is None:
                return ERR_MISSING_LABEL
            if match_num.get(value, 0) + self_match - min_match > c["max_skew"]:
                return ERR_SKEW
            return None

        return check

    # -------------------------------------------------------------- cycle

    def schedule_one(self, manifest: dict, annotate: bool = True):
        """-> (annotations or None, selected node name or ""); binds."""
        pod = _Pod(manifest)
        plugins = [("NodeUnschedulable", _passes), ("NodeName", _passes),
                   ("TaintToleration", self._taint_filter),
                   ("NodeResourcesFit", lambda j: self._fit_filter(pod, j))]
        spread = self._spread_filter(pod)
        if spread is not None:
            plugins.append(("PodTopologySpread", spread))
        interpod = self._interpod_filter(pod)
        if interpod is not None:
            plugins.append(("InterPodAffinity", interpod))
        filter_map: dict[str, dict[str, str]] = {}
        feasible: list[int] = []
        for j in range(self.n):
            entry, ok = run_filters(plugins, j)
            filter_map[self.names[j]] = entry
            if ok:
                feasible.append(j)

        prescore: dict[str, str] = {}
        score_map: dict[str, dict[str, str]] = {}
        final_map: dict[str, dict[str, str]] = {}
        selected = -1
        if len(feasible) == 1:
            selected = feasible[0]
        elif feasible:
            # PodTopologySpread's PreScore Skips: no ScheduleAnyway constraint
            prescore = {nm: ("" if nm in ("NodeAffinity", "PodTopologySpread")
                             else "success") for nm in PRESCORERS}
            sym = self._affinity_symmetry(pod)
            memo: dict = {}
            # TaintToleration's raw score is 0 on every feasible node (no
            # PreferNoSchedule taint is covered), as _raw_scores has it
            raws = [self._raw_scores(pod, j, sym, memo) for j in feasible]
            totals = [0] * len(feasible)
            finals = {}
            for name, weight in SCORERS:
                normed = self._normalize(name, [r[name] for r in raws])
                finals[name] = [v * weight for v in normed]
                for i, v in enumerate(finals[name]):
                    totals[i] += v
            selected = feasible[totals.index(max(totals))]  # lowest index wins
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
        if spread is not None:
            status["PodTopologySpread"] = "success"
        if interpod is not None:
            status["InterPodAffinity"] = "success"
        return render(status, filter_map, prescore, score_map, final_map,
                      node), node
