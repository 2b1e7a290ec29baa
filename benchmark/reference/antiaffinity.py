"""The plain reference for deployments in which a Filter says no: the
default profile with required pod ANTI-affinity, and the rendering of a
refusal that every later configuration with refusals reuses.

One pod and one node at a time, Python integers and float64; imports
`default_profile.py`'s helpers and nothing of the program.  The interface
is the one stated at that file's head (KEYS, ARITHMETICS,
ReferenceScheduler(nodes, bound_pods, arith).schedule_one(pod, annotate)).

What it adds to the default profile's reference, from the upstream v1.32
`interpodaffinity` plugin:

  * PreFilter: the plugin is skipped (status "", no Filter entry) only
    when the pod has no required term of either kind AND no existing pod's
    required anti-affinity term matches it; otherwise "success";
  * Filter, three checks in upstream's order, the first that fails gives
    the message:
      1. the pod's required affinity       "node(s) didn't match pod affinity rules"
      2. the pod's required anti-affinity  "node(s) didn't match pod anti-affinity rules"
         (an existing pod that matches one of the pod's anti terms sits
         in the node's domain of that term's topology key)
      3. existing pods' anti-affinity      "node(s) didn't satisfy existing pods anti-affinity rules"
         (an existing pod with a required anti term that matches the
         incoming pod sits in a domain this node belongs to, by any label);
  * PreScore / Score: required anti-affinity terms weigh nothing; the
    plugin still scores (0 everywhere here) as it does in
    default_profile.py;
  * how a refusal is rendered (`run_filters`, `render`): in a node's
    filter-result entry the plugins before the refusing one say "passed",
    the refusing one gives its message, and there is none after it; the
    score and finalscore maps, and the normalisation's minimum and
    maximum, are over the feasible nodes only; ties go to the lowest
    feasible index; exactly one feasible node is selected without scoring;
  * no feasible node: DefaultPreemption runs, and where no pod has a lower
    priority (every covered pod has none) it finds no victim: the pod
    stays unbound, selected-node is "", postfilter-result lists every node
    that was refused with an empty map (the reference simulator's
    AddPostFilterResult writes a message only for a nominated node),
    and there is no score, reserve, prebind or bind entry.

Anything else raises NotCovered: preferred terms, matchExpressions,
namespaceSelector, matchLabelKeys / mismatchLabelKeys, node affinity, more
than one required affinity term (upstream counts an existing pod only if
it matches ALL of them; the per-term form here is exact for one), and all
that default_profile.py refuses (taints, volumes, ports, priorities, ...).
"""

from __future__ import annotations

from reference.default_profile import (  # noqa: F401  (the interface)
    ARITHMETICS, K_BIND, K_FILTER, K_FINAL, K_PERMIT, K_PERMIT_TIMEOUT,
    K_POSTFILTER, K_PREBIND, K_PREFILTER, K_PREFILTER_STATUS, K_PRESCORE,
    K_RESERVE, K_SCORE, K_SELECTED, KEYS, PREFILTERS, PRESCORERS, SCORERS,
    Exact, NotCovered, _pod_request, _term_matches, marshal)
from reference.default_profile import ReferenceScheduler as _DefaultProfile

ERR_AFFINITY = "node(s) didn't match pod affinity rules"
ERR_ANTI_AFFINITY = "node(s) didn't match pod anti-affinity rules"
ERR_EXISTING_ANTI = "node(s) didn't satisfy existing pods anti-affinity rules"

_KINDS = ("podAffinity", "podAntiAffinity")
_REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"


def _required_terms(manifest: dict, kind: str, owner_ns: str) -> list[dict]:
    aff = (manifest.get("spec") or {}).get("affinity") or {}
    if set(aff) - set(_KINDS):
        raise NotCovered(f"affinity kinds {sorted(aff)}")
    group = aff.get(kind) or {}
    if set(group) - {_REQUIRED}:
        raise NotCovered(f"preferred {kind} terms")
    terms = []
    for t in group.get(_REQUIRED) or []:
        if set(t) - {"labelSelector", "topologyKey", "namespaces"}:
            raise NotCovered(f"affinity term keys {sorted(t)}")
        sel = t.get("labelSelector") or {}
        if set(sel) - {"matchLabels"}:
            raise NotCovered("matchExpressions")
        terms.append({
            "key": t.get("topologyKey", ""),
            "match": {k: str(v) for k, v in
                      (sel.get("matchLabels") or {}).items()},
            # no namespace list: the owner's namespace
            "namespaces": set(t.get("namespaces") or [owner_ns])})
    return terms


class _Pod:
    __slots__ = ("name", "ns", "labels", "cpu", "mem", "terms", "anti")

    def __init__(self, manifest: dict):
        meta = manifest.get("metadata") or {}
        self.name = meta["name"]
        self.ns = meta.get("namespace") or "default"
        self.labels = {k: str(v) for k, v in (meta.get("labels") or {}).items()}
        self.cpu, self.mem = _pod_request(manifest)
        self.terms = _required_terms(manifest, "podAffinity", self.ns)
        self.anti = _required_terms(manifest, "podAntiAffinity", self.ns)
        if len(self.terms) > 1:
            raise NotCovered("more than one required pod affinity term")


def run_filters(plugins, j: int) -> tuple[dict[str, str], bool]:
    """One node's filter-result entry.  `plugins` is [(name, check)] in the
    profile's order, check(j) -> a message or None; the framework stops at
    the first refusal, so the entry ends there.  -> (entry, feasible)."""
    entry: dict[str, str] = {}
    for name, check in plugins:
        msg = check(j)
        if msg is not None:
            entry[name] = msg
            return entry, False
        entry[name] = "passed"
    return entry, True


def render(status: dict, filter_map: dict, prescore: dict, score_map: dict,
           final_map: dict, node: str) -> dict[str, str]:
    """The 13 annotations of one cycle.  `node` is "" where no node was
    feasible: the PostFilter result then lists every refused node, and the
    cycle ends without a reserve, prebind or bind entry."""
    bound = {"VolumeBinding": "success"} if node else {}
    postfilter = {} if node else {nm: {} for nm in filter_map}
    empty = marshal({})
    return {
        K_PREFILTER_STATUS: marshal(status),
        K_PREFILTER: empty,
        K_FILTER: marshal(filter_map),
        K_POSTFILTER: marshal(postfilter),
        K_PRESCORE: marshal(prescore),
        K_SCORE: marshal(score_map),
        K_FINAL: marshal(final_map),
        K_RESERVE: marshal(bound),
        K_PERMIT: empty,
        K_PERMIT_TIMEOUT: empty,
        K_PREBIND: marshal(bound),
        K_BIND: marshal({"DefaultBinder": "success"} if node else {}),
        K_SELECTED: node,
    }


def _passes(j: int) -> None:
    return None


class ReferenceScheduler(_DefaultProfile):
    """default_profile's cluster state and resource plugins; the cycle and
    the InterPodAffinity filter are this file's."""

    def __init__(self, nodes: list[dict], bound_pods: list[dict],
                 arith=Exact):
        super().__init__(nodes, [], arith)
        idx = {nm: j for j, nm in enumerate(self.names)}
        for m in bound_pods:
            self._bind(_Pod(m), idx[m["spec"]["nodeName"]])

    # ---------------------------------------------------- InterPodAffinity

    def _anti_counts(self, pod: _Pod, own: bool) -> dict[tuple[str, str], int]:
        """Per (topology key, value) of the node an existing pod sits on:
        own=True, the existing pods that match one of the incoming pod's
        anti terms; own=False, the existing pods' anti terms that match the
        incoming pod."""
        counts: dict[tuple[str, str], int] = {}
        for other, j in self.assigned:
            owner, target = (pod, other) if own else (other, pod)
            for term in owner.anti:
                val = self.labels[j].get(term["key"])
                if val is not None and _term_matches(term, target):
                    counts[term["key"], val] = counts.get((term["key"], val), 0) + 1
        return counts

    def _interpod_filter(self, pod: _Pod):
        """-> check(j) over this cycle's PreFilter state, or None where
        upstream's PreFilter returns Skip."""
        existing = self._anti_counts(pod, own=False)
        if not (pod.terms or pod.anti or existing):
            return None
        state = self_ok = None
        if pod.terms:
            state, self_ok = self._affinity_state(pod)
        own = self._anti_counts(pod, own=True)

        def check(j: int) -> str | None:
            lab = self.labels[j]
            if pod.terms and self._affinity_filter(state, self_ok, j):
                return ERR_AFFINITY
            if any(own.get((t["key"], lab[t["key"]]), 0) > 0
                   for t in pod.anti if t["key"] in lab):
                return ERR_ANTI_AFFINITY
            if any(existing.get(kv, 0) > 0 for kv in lab.items()):
                return ERR_EXISTING_ANTI
            return None

        return check

    # -------------------------------------------------------------- cycle

    def schedule_one(self, manifest: dict, annotate: bool = True):
        """-> (annotations or None, selected node name or ""); binds."""
        pod = _Pod(manifest)
        plugins = [("NodeUnschedulable", _passes), ("NodeName", _passes),
                   ("TaintToleration", _passes),
                   ("NodeResourcesFit", lambda j: self._fit_filter(pod, j))]
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
        if interpod is not None:
            status["InterPodAffinity"] = "success"
        return render(status, filter_map, prescore, score_map, final_map,
                      node), node
