"""The plain reference for deployments whose nodes come and go between
pods, with a standing pod that fits nowhere: scheduler_perf's
SchedulingWithMixedChurn.

One pod and one node at a time, Python integers and float64; imports
`default_profile.py`'s and `antiaffinity.py`'s helpers and nothing of the
program.  The interface is the one stated at `default_profile.py`'s head.

What the oracle child hands a reference is the initial nodes, the initial
pods and then the measured pods, one `schedule_one` each, in queue order.
The churn is replayed from `nodes.churn` (generators/
scheduler_perf_churn.py): before its i-th `schedule_one` this reference
does what the client did before creating the i-th measured pod — tick i:

  * the churn node of tick i-1 leaves the cluster and the one of tick i
    joins it.  Node index order is the store's, by name, so the cluster
    is re-sorted; a churn node may sort anywhere;
  * the churn pod of tick i is scheduled.  It has to fit no node (this
    reference covers the source's shape, and raises NotCovered for a
    churn pod that fits): every node's filter-result entry ends at
    NodeResourcesFit with its reasons, upstream's fitsRequest order —
    "Too many pods", "Insufficient cpu", "Insufficient memory" — joined
    by ", ".  It has a priority, so DefaultPreemption looks at every
    node: one that holds no pod of lower priority is no candidate, and on
    the others the pod must still not fit with every such pod gone
    (else NotCovered: a preemption that finds a candidate deletes pods,
    which is another deployment).  So nothing is nominated, and
    postfilter-result lists every node with an empty map (the reference
    simulator's AddPostFilterResult writes a message only for a
    nominated node).  The pod stays pending and changes nothing;
  * the churn service is of no consequence: nothing here selects on it.

The measured pod is then scheduled over the cluster as it stands: the
churn node is refused (NodeResourcesFit, its reasons as above), the rest
is default_profile's scoring over the feasible nodes only, rendered by
antiaffinity's `run_filters` / `render`; ties go to the first feasible
node in name order.

`churn_results` keeps the churn pods' annotations where `render_churn` is
set (a test that compares them; the oracle child leaves it off).

Anything else raises NotCovered, as in the files this one imports.
"""

from __future__ import annotations

from reference.antiaffinity import render, run_filters
from reference.default_profile import (  # noqa: F401  (the interface)
    ARITHMETICS, KEYS, PREFILTERS, PRESCORERS, SCORERS, Exact, NotCovered,
    _Pod, _pod_request, quantity)
from reference.default_profile import ReferenceScheduler as _DefaultProfile


def _passes(j: int) -> None:
    return None


class _ChurnPod:
    """A pod with a priority and requests, and nothing else that matters."""

    __slots__ = ("name", "cpu", "mem", "priority")

    def __init__(self, manifest: dict):
        spec = dict(manifest.get("spec") or {})
        if spec.get("preemptionPolicy") or spec.get("nodeName"):
            raise NotCovered("a churn pod with a preemptionPolicy or a node")
        self.priority = int(spec.pop("priority", 0))
        self.name = manifest["metadata"]["name"]
        self.cpu, self.mem = _pod_request({"spec": spec})


class ReferenceScheduler(_DefaultProfile):
    """default_profile's cluster state and resource plugins; the churn,
    the refusals and the cycle are this file's."""

    def __init__(self, nodes: list[dict], bound_pods: list[dict],
                 arith=Exact):
        super().__init__(list(nodes), bound_pods, arith)
        self.churn = getattr(nodes, "churn", None)
        self.order = list(range(self.n))  # indices in the store's order
        self.slot: int | None = None      # where the churn node lives
        self.ticks = 0
        self.render_churn = False
        self.churn_results: dict[str, dict[str, str]] = {}

    # -------------------------------------------------------------- churn

    def _swap_churn_node(self, node: dict) -> None:
        spec, status = node.get("spec") or {}, node.get("status") or {}
        if spec.get("taints") or spec.get("unschedulable") or status.get("images"):
            raise NotCovered("a churn node with taints, images or unschedulable")
        alloc = status.get("allocatable") or {}
        if set(alloc) - {"cpu", "memory", "pods"}:
            raise NotCovered(f"allocatable {sorted(alloc)}")
        A = self.A
        row = {
            "names": node["metadata"]["name"],
            "labels": {k: str(v) for k, v in
                       (node["metadata"].get("labels") or {}).items()},
            # a resource the node does not state it does not offer
            "alloc_cpu": A.i(quantity(alloc.get("cpu", "0"), milli=True)),
            "alloc_mem": A.i(quantity(alloc.get("memory", "0"), milli=False)),
            "allowed": quantity(alloc.get("pods", "0"), milli=False),
            "req_cpu": 0, "req_mem": 0, "num_pods": 0}
        if self.slot is None:
            self.slot = self.n
            self.n += 1
            for field, value in row.items():
                getattr(self, field).append(value)
        else:
            if self.num_pods[self.slot]:
                raise NotCovered("a churn node that leaves with pods on it")
            for field, value in row.items():
                getattr(self, field)[self.slot] = value
        self.order = sorted(range(self.n), key=self.names.__getitem__)

    def _fit_plugins(self, pod):
        return [("NodeUnschedulable", _passes), ("NodeName", _passes),
                ("TaintToleration", _passes),
                ("NodeResourcesFit", lambda j: self._fit_filter(pod, j))]

    def _fits_emptied(self, pod: _ChurnPod, j: int) -> bool:
        """NodeResourcesFit on node j with every pod on it gone."""
        A = self.A
        return not (1 > self.allowed[j]
                    or A.i(pod.cpu) > self.alloc_cpu[j]
                    or A.i(pod.mem) > self.alloc_mem[j])

    def schedule_churn_pod(self, manifest: dict, annotate: bool = True):
        """-> (annotations or None, ""): the pod fits nowhere, preemption
        finds no candidate, nothing changes."""
        pod = _ChurnPod(manifest)
        plugins = self._fit_plugins(pod)
        filter_map: dict[str, dict[str, str]] = {}
        for j in self.order:
            entry, ok = run_filters(plugins, j)
            if ok:
                raise NotCovered(f"churn pod {pod.name} fits a node")
            filter_map[self.names[j]] = entry
        # DefaultPreemption: every bound pod here has priority 0 (the
        # covered pods carry none), so a node is looked at only if the
        # churn pod's priority is above that and the node holds a pod
        if pod.priority > 0:
            for j in self.order:
                if self.num_pods[j] and self._fits_emptied(pod, j):
                    raise NotCovered("a preemption that finds a candidate")
        if not annotate:
            return None, ""
        status = {nm: "" for nm in PREFILTERS}
        status["NodeResourcesFit"] = "success"
        return render(status, filter_map, {}, {}, {}, ""), ""

    def _tick(self) -> None:
        node, pod, _service = self.churn.trio(self.ticks)
        self.ticks += 1
        self._swap_churn_node(node)
        anns, _ = self.schedule_churn_pod(pod, annotate=self.render_churn)
        if anns is not None:
            self.churn_results[pod["metadata"]["name"]] = anns

    # -------------------------------------------------------------- cycle

    def schedule_one(self, manifest: dict, annotate: bool = True):
        """-> (annotations or None, selected node name or ""); binds.  The
        churn's next tick goes first."""
        if self.churn is not None:
            self._tick()
        pod = _Pod(manifest)
        if pod.terms:
            raise NotCovered("pod affinity under churn")
        plugins = self._fit_plugins(pod)
        filter_map: dict[str, dict[str, str]] = {}
        feasible: list[int] = []
        for j in self.order:
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
            memo: dict = {}
            raws = [self._raw_scores(pod, j, {}, memo) for j in feasible]
            totals = [0] * len(feasible)
            finals = {}
            for name, weight in SCORERS:
                normed = self._normalize(name, [r[name] for r in raws])
                finals[name] = [v * weight for v in normed]
                for i, v in enumerate(finals[name]):
                    totals[i] += v
            selected = feasible[totals.index(max(totals))]  # first in order
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
        return render(status, filter_map, prescore, score_map, final_map,
                      node), node
