"""DefaultPreemption's batched screen against the per-node dry run it
stands in for (framework/preemption.py, "The screen").

`_per_node_preempt` below is the candidate search as it was before the
screen: every node whose refusal is resolvable gets its own dry run, also
a node that holds no lower-priority pod.  On seeded clusters in which some
nodes do admit the preemptor once their lower-priority pods are gone, both
searches must give the same candidates' outcome: nominated node, victims,
evaluated nodes — and, through the engine, the same deletions, the same
status.nominatedNodeName and the same postfilter-result bytes.  One
cluster's refusal is cross-node (required anti-affinity over a zone whose
matching pods sit on other nodes): the screen, which removes every node's
victims at once, must not decide it, and hands it to the per-node probe.

Since PR 33 the screen starts with a static rule (a node too small even
when EMPTY leaves before the batched dry run).  The same outcome must come
out three ways: the per-node search, the screen without the rule (PR 32's)
and the screen with it — on clusters that mix hopeless nodes, nodes only
the dry run refuses and real candidates, and on one with more candidates
than the budget, where a rule that shrank `potential` would pick another
node.
"""

from __future__ import annotations

import copy
import json

import numpy as np
import pytest

from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
from kube_scheduler_simulator_tpu.framework import preemption as pre
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.store import annotations as ann
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

ZONE = "topology.kubernetes.io/zone"
PROBES, SCREENED = ("preemption_fit_probes_total",
                    "preemption_screen_refused_nodes_total")
STATIC, DRY_RUNS = ("preemption_static_refused_nodes_total",
                    "preemption_screen_dry_runs_total")


_PREEMPT = pre.Preemptor.preempt   # a test patches a spy over it, per run


def _per_node_preempt(self, pod, failed):
    """Preemptor.preempt as of PR 31: no early return, no screen."""
    from kube_scheduler_simulator_tpu.cluster.store import list_shared
    from kube_scheduler_simulator_tpu.framework.gang import (
        GangDirectory, preemption_protected)

    self._fit_cache.clear()
    self._nodes = list_shared(self.store, "nodes")
    self._pods_all = list_shared(self.store, "pods")
    self._volumes = {
        "pvcs": list_shared(self.store, "persistentvolumeclaims"),
        "pvs": list_shared(self.store, "persistentvolumes"),
        "storageclasses": list_shared(self.store, "storageclasses")}
    self._pdbs = list_shared(self.store, "poddisruptionbudgets")
    self._namespaces = list_shared(self.store, "namespaces")
    self._gang_protected = preemption_protected(
        self._pods_all, GangDirectory(self.store))
    out = pre.PreemptionOutcome(evaluated_nodes=[n for n, _ in failed])
    if ((pod.get("spec") or {}).get("preemptionPolicy") or "") == "Never":
        return out
    prio = pre._priority(pod)
    potential = [n for n, plugin in failed
                 if plugin is not None and plugin in pre.RESOLVABLE_PLUGINS]
    by_node: dict[str, list[dict]] = {}
    for p in self._pods_all:
        nn = (p.get("spec") or {}).get("nodeName")
        if nn:
            by_node.setdefault(nn, []).append(p)
    budget = pre._num_candidates(len(potential), self.min_candidate_pct,
                                 self.min_candidate_abs)
    candidates = []
    for node in potential:
        if len(candidates) >= budget:
            break
        lower = [p for p in by_node.get(node, [])
                 if pre._priority(p) < prio
                 and pre._pod_key(p) not in self._gang_protected]
        found = self._victims_on(node, lower, pod)
        if found is not None:
            candidates.append((node, *found))
    if not candidates:
        return out
    out.nominated_node, out.victims = self._select(candidates)
    return out


def _node(name: str, cpu: int, zone: str) -> dict:
    return {"metadata": {"name": name, "labels": {ZONE: zone}},
            "status": {"allocatable": {"cpu": str(cpu), "memory": "16Gi",
                                       "pods": "8"}}}


def _pod(name: str, cpu_m: int, prio: int, node: str | None = None,
         labels: dict | None = None, port: int | None = None,
         created: str = "2024-01-01T00:00:00Z") -> dict:
    c = {"name": "c", "resources": {"requests": {"cpu": f"{cpu_m}m",
                                                 "memory": "256Mi"}}}
    if port:
        c["ports"] = [{"containerPort": port, "hostPort": port}]
    p = {"metadata": {"name": name, "namespace": "default",
                      "labels": labels or {}, "creationTimestamp": created},
         "spec": {"priority": prio, "containers": [c]}}
    if node:
        p["spec"]["nodeName"] = node
    return p


def _cluster(seed: int) -> tuple[list[dict], list[dict], dict]:
    """Nodes of 2-8 CPU in three zones, each holding 0-4 pods of priority
    0-60 (some on a host port), and a preemptor of priority 50 that fits
    no node as the cluster stands but fits some once lower pods go."""
    rng = np.random.default_rng(seed)
    nodes, bound = [], []
    for j in range(int(rng.integers(9, 14))):
        cpu = int(rng.integers(2, 9))
        nodes.append(_node(f"n{j:02d}", cpu, f"z{j % 3}"))
        left = cpu * 1000
        for i in range(int(rng.integers(0, 5))):
            want = int(rng.integers(2, 9)) * 250
            if want > left:
                break
            left -= want
            bound.append(_pod(
                f"b{j:02d}-{i}", want, int(rng.choice([0, 10, 20, 60])),
                node=f"n{j:02d}",
                port=8080 if rng.random() < 0.2 and i == 0 else None,
                created=f"2024-01-01T00:00:{int(rng.integers(10, 59))}Z"))
        if left >= 3000:  # no room for the preemptor anywhere as it stands
            bound.append(_pod(f"f{j:02d}", left - 500, 60, node=f"n{j:02d}"))
    preemptor = _pod("preemptor", 3000, 50,
                     port=8080 if seed % 2 else None)
    return nodes, bound, preemptor


def _anti_affinity_cluster() -> tuple[list[dict], list[dict], dict]:
    """Zone a = {a0, a1}, zone b = {b0}.  The preemptor (priority 50) has
    required anti-affinity to app=x over the zone.  a0 holds a low x pod
    and a big low pod; a1 holds a low x pod; b0 holds a high pod that
    fills it.  On a0, with a0's own pods gone, a1's x pod still refuses
    the zone: only removing BOTH nodes' pods (the screen's hypothesis)
    lets a0 pass, so the screen cannot rule a0 in or out."""
    nodes = [_node("a0", 4, "a"), _node("a1", 4, "a"), _node("b0", 4, "b")]
    bound = [_pod("x-a0", 500, 0, "a0", {"app": "x"}),
             _pod("big-a0", 3000, 0, "a0"),
             _pod("x-a1", 500, 0, "a1", {"app": "x"}),
             _pod("big-a1", 3000, 0, "a1"),
             _pod("full-b0", 3500, 90, "b0")]
    preemptor = _pod("preemptor", 2000, 50)
    preemptor["spec"]["affinity"] = {"podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [{
            "labelSelector": {"matchLabels": {"app": "x"}},
            "topologyKey": ZONE}]}}
    return nodes, bound, preemptor


def _run(nodes, bound, preemptor, per_node: bool, monkeypatch,
         static_rule: bool = True, cfg: PluginSetConfig | None = None):
    """One engine pass over the preemptor -> what preemption did.
    static_rule=False: the screen as of PR 32, every node with a
    lower-priority pod in the batched dry run (no failed pass is handed
    to the search, so it takes no node for hopeless)."""
    store = ObjectStore()
    for n in nodes:
        store.create("nodes", copy.deepcopy(n))
    for p in bound:
        store.create("pods", copy.deepcopy(p))
    store.create("pods", copy.deepcopy(preemptor))
    engine = SchedulerEngine(store, plugin_config=cfg)
    outcomes = []
    search = _per_node_preempt if per_node else _PREEMPT

    def spy(self, pod, failed, failed_pass=None):
        if per_node or not static_rule:
            out = search(self, pod, failed)
        else:
            assert failed_pass is not None, "the engine handed no pass over"
            out = search(self, pod, failed, failed_pass=failed_pass)
        outcomes.append((out.nominated_node,
                         [pre._pod_key(v) for v in out.victims],
                         list(out.evaluated_nodes)))
        return out

    monkeypatch.setattr(pre.Preemptor, "preempt", spy)
    before = TRACER.counter_totals()
    engine.schedule_pending()
    after = TRACER.counter_totals()
    engine.close()
    pods = {p["metadata"]["name"]: p for p in store.list("pods")[0]}
    me = pods["preemptor"]
    return {
        "outcomes": outcomes,
        "left": sorted(pods),
        "bound_to": me["spec"].get("nodeName"),
        "nominated": (me.get("status") or {}).get("nominatedNodeName"),
        "postfilter": (me["metadata"].get("annotations") or {}).get(
            ann.POST_FILTER_RESULT),
        "probes": after.get(PROBES, 0) - before.get(PROBES, 0),
        "screened": after.get(SCREENED, 0) - before.get(SCREENED, 0),
        "static": after.get(STATIC, 0) - before.get(STATIC, 0),
        "dry_runs": after.get(DRY_RUNS, 0) - before.get(DRY_RUNS, 0),
    }


def _same(a: dict, b: dict) -> None:
    for k in ("outcomes", "left", "bound_to", "nominated", "postfilter"):
        assert a[k] == b[k], k


@pytest.mark.parametrize("seed", [3, 8, 21, 34, 55, 89])
def test_screen_equals_the_per_node_search(seed, monkeypatch):
    nodes, bound, preemptor = _cluster(seed)
    new = _run(nodes, bound, preemptor, False, monkeypatch)
    old = _run(nodes, bound, preemptor, True, monkeypatch)
    _same(new, old)
    first = new["outcomes"][0]
    assert first[0], "no node admits the preemptor: the cluster tests nothing"
    assert first[1], "a candidate without victims"
    # the victims went and the retry wave bound the preemptor there
    assert new["bound_to"] == first[0]
    assert not {v.split("/")[1] for v in first[1]} & set(new["left"])
    # the screen did work the per-node search did a node at a time
    assert new["probes"] < old["probes"]
    assert old["screened"] == 0


def _mixed_cluster(seed: int) -> tuple[list[dict], list[dict], dict]:
    """For a preemptor of 3 CPU and priority 50, in shuffled node order:
    hopeless nodes (1-2 CPU, a low pod each), nodes only the dry run
    refuses (4-6 CPU, a priority-90 pod that leaves less than 3 CPU and a
    low pod beside it), real candidates (4-8 CPU filled by low pods of
    mixed priorities) and nodes without a lower-priority pod."""
    rng = np.random.default_rng(seed)
    kinds = (["hopeless"] * int(rng.integers(2, 5))
             + ["dry-run"] * int(rng.integers(2, 4))
             + ["candidate"] * int(rng.integers(2, 5))
             + ["no-lower"] * int(rng.integers(1, 3)))
    rng.shuffle(kinds)
    nodes, bound = [], []
    for j, kind in enumerate(kinds):
        name = f"n{j:02d}"
        stamp = f"2024-01-01T00:00:{int(rng.integers(10, 59))}Z"
        if kind == "hopeless":
            cpu = int(rng.integers(1, 3))
            bound.append(_pod(f"low-{j}", 500, int(rng.choice([0, 10])),
                              node=name, created=stamp))
        elif kind == "dry-run":
            cpu = int(rng.integers(4, 7))
            bound.append(_pod(f"high-{j}", (cpu - 2) * 1000, 90, node=name))
            bound.append(_pod(f"low-{j}", 1500, 0, node=name, created=stamp))
        elif kind == "candidate":
            cpu = int(rng.integers(4, 9))
            left = cpu * 1000
            for i in range(int(rng.integers(1, 4))):
                want = left if i == 2 else int(rng.integers(2, 5)) * 500
                want = min(want, left)
                if not want:
                    break
                left -= want
                bound.append(_pod(f"low-{j}-{i}", want,
                                  int(rng.choice([0, 10, 20])), node=name,
                                  created=stamp))
            if left:  # no room as it stands
                bound.append(_pod(f"fill-{j}", left, 20, node=name))
        else:
            cpu = 4
            bound.append(_pod(f"high-{j}", 3500, 90, node=name))
        nodes.append(_node(name, cpu, f"z{j % 3}"))
    return nodes, bound, _pod("preemptor", 3000, 50)


@pytest.mark.parametrize("seed", [1, 2, 5, 13, 2147483777])
def test_static_rule_dry_run_and_per_node_search_agree(seed, monkeypatch):
    nodes, bound, preemptor = _mixed_cluster(seed)
    new = _run(nodes, bound, preemptor, False, monkeypatch)
    pr32 = _run(nodes, bound, preemptor, False, monkeypatch, static_rule=False)
    old = _run(nodes, bound, preemptor, True, monkeypatch)
    _same(new, pr32)
    _same(new, old)
    assert new["outcomes"][0][0] and new["outcomes"][0][1]
    # every kind of node was there, and each went where it should: the
    # hopeless ones never reached the dry run, which refused the rest
    hopeless = sum(1 for n in nodes
                   if int(n["status"]["allocatable"]["cpu"]) < 3)
    assert hopeless and new["static"] == hopeless
    assert new["screened"] > 0 and new["dry_runs"] == 1
    assert pr32["static"] == 0
    assert pr32["screened"] == new["static"] + new["screened"]
    assert new["probes"] == pr32["probes"] < old["probes"]


def _over_budget_cluster() -> tuple[list[dict], list[dict], dict]:
    """25 nodes, every one refused by Fit as it stands, so `potential` is
    25 and a budget of 20% is 5 candidates.  In node order: 10 hopeless
    (2 CPU), 3 only the dry run refuses, 6 candidates whose lone victim
    has priority 40, 40, 40, 40, 10, 0, then 6 without a lower pod.  With
    5 candidates the fifth wins (lowest victim priority of those looked
    at); had the hopeless nodes left `potential`, the budget would be 3
    and the first would win; a search blind to the budget takes the
    sixth."""
    nodes, bound = [], []
    for j in range(25):
        name = f"n{j:02d}"
        nodes.append(_node(name, 2 if j < 10 else 4, f"z{j % 3}"))
        if j < 10:
            bound.append(_pod(f"low-{j}", 1000, 0, node=name))
        elif j < 13:
            bound.append(_pod(f"high-{j}", 2000, 90, node=name))
            bound.append(_pod(f"low-{j}", 1500, 0, node=name))
        elif j < 19:
            bound.append(_pod(f"victim-{j}", 3500,
                              (40, 40, 40, 40, 10, 0)[j - 13], node=name))
        else:
            bound.append(_pod(f"high-{j}", 3500, 90, node=name))
    return nodes, bound, _pod("preemptor", 3000, 50)


def test_hopeless_nodes_still_count_toward_the_candidate_budget(monkeypatch):
    nodes, bound, preemptor = _over_budget_cluster()
    cfg = PluginSetConfig(args={"DefaultPreemption": {
        "minCandidateNodesPercentage": 20, "minCandidateNodesAbsolute": 1}})
    runs = [_run(nodes, bound, preemptor, per_node, monkeypatch,
                 static_rule=rule, cfg=cfg)
            for per_node, rule in ((False, True), (False, False), (True, True))]
    new, pr32, old = runs
    _same(new, pr32)
    _same(new, old)
    assert new["outcomes"][0][:2] == ("n17", ["default/victim-17"])
    assert len(new["outcomes"][0][2]) == 25
    assert (new["static"], new["screened"], new["dry_runs"]) == (10, 3, 1)
    assert (pr32["static"], pr32["screened"], pr32["dry_runs"]) == (0, 13, 1)
    # five candidates looked at, one probe to admit each and one to find
    # its lone victim cannot be reprieved
    assert new["probes"] == pr32["probes"] == 10


def test_some_seed_screens_a_node_out(monkeypatch):
    """The property above is not vacuous: over the seeds, the screen rules
    nodes out (nodes too small even when emptied)."""
    total = 0
    for seed in (3, 8, 21):
        nodes, bound, preemptor = _cluster(seed)
        total += _run(nodes, bound, preemptor, False, monkeypatch)["screened"]
    assert total > 0


def test_a_cross_node_refusal_falls_through_to_the_probe(monkeypatch):
    nodes, bound, preemptor = _anti_affinity_cluster()
    new = _run(nodes, bound, preemptor, False, monkeypatch)
    old = _run(nodes, bound, preemptor, True, monkeypatch)
    _same(new, old)
    # a0 and a1 are refused by InterPodAffinity, which the screen does not
    # read: nothing is screened out, both are probed, neither is a candidate
    # (the other node's x pod stays); b0 holds no lower pod: no look at all
    assert new["screened"] == 0 and new["probes"] == 2
    assert new["outcomes"][0][0] == "" and new["nominated"] is None
    assert set(json.loads(new["postfilter"])) == {"a0", "a1", "b0"}


def test_a_node_without_a_lower_priority_pod_is_never_probed(monkeypatch):
    """Upstream's early return: the per-node search probes such a node and
    is refused again; the new search does not look."""
    nodes = [_node("n0", 4, "a"), _node("n1", 4, "a")]
    bound = [_pod("hi-0", 3000, 90, "n0"), _pod("hi-1", 3000, 90, "n1")]
    preemptor = _pod("preemptor", 2000, 50)
    new = _run(nodes, bound, preemptor, False, monkeypatch)
    old = _run(nodes, bound, preemptor, True, monkeypatch)
    _same(new, old)
    assert (new["probes"], new["screened"]) == (0, 0)
    assert old["probes"] == 2


def test_first_fail_plugins_vectorised():
    codes = np.array([[0, 1, 0, 0], [0, 2, 3, 0], [5, 0, 1, 0]])
    assert pre.first_fail_plugins(codes, ["A", "B", "C"]) == ["C", "A", "B", None]
    assert pre.first_fail_plugins(codes[:0], []) == [None] * 4
    assert pre.first_fail_plugins(np.zeros((3, 0)), ["A", "B", "C"]) == []


def test_screen_local_plugins_are_resolvable_or_node_properties():
    """The local set holds no cross-node plugin."""
    assert not pre.SCREEN_LOCAL_PLUGINS & {
        "InterPodAffinity", "PodTopologySpread", "VolumeRestrictions",
        "VolumeBinding", "VolumeZone"}
    assert {"NodeResourcesFit", "NodePorts", "NodeVolumeLimits"} \
        <= pre.SCREEN_LOCAL_PLUGINS & pre.RESOLVABLE_PLUGINS
