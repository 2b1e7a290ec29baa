"""The scan against the sequential reference, workloads x routes.

The device evaluates a queue one way, the sequential scan
(framework/replay.py), and that scan has routes: a pass of one chunk is
ONE call over the packed buffers, a longer pass runs chunk after chunk
over leaves, a mesh shards the node axis, and under the engine the commit
is streamed by the chunk worker (over a mesh too) or made in a post-pass.  Every case here
takes one of the suite's hard workloads (the ones the speculative rounds'
suites were built around, moved here when the rounds were deleted: PR 54)
down one route and holds every pod to reference_impl/sequential.py byte
for byte: the binding, all 13 result annotations and the result history.
"""

from __future__ import annotations

import dataclasses
import functools
import json

import jax.numpy as jnp
import pytest

from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
from kube_scheduler_simulator_tpu.framework import engine as engine_mod
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.framework.gang import POD_GROUP_LABEL
from kube_scheduler_simulator_tpu.framework.replay import replay
from kube_scheduler_simulator_tpu.models.workloads import (
    make_gang_workload, make_nodes, make_pods, make_slot_pinned_workload)
from kube_scheduler_simulator_tpu.parallel.mesh import make_mesh
from kube_scheduler_simulator_tpu.plugins.coscheduling import (
    Coscheduling, ensure_podgroup_resource)
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.reference_impl.sequential import (
    SequentialScheduler)
from kube_scheduler_simulator_tpu.state.compile import compile_workload
from kube_scheduler_simulator_tpu.store import annotations as ann
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result
from kube_scheduler_simulator_tpu.utils import faults
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

from test_prefilter_result import NODES as PF_NODES, SAFE_CFG as PF_CFG
from test_prefilter_result import _queue as _pf_queue
from test_wave_path_table import _route

RELAXED = ["NodeResourcesFit", "NodeResourcesBalancedAllocation"]
SAFE_CFG = RELAXED + ["NodeAffinity", "TaintToleration"]
COUPLED_CFG = SAFE_CFG + ["PodTopologySpread"]


@dataclasses.dataclass
class Workload:
    nodes: list
    pods: list
    enabled: list
    namespaces: tuple = ()
    # the score columns forced onto the widest tier (a compile-proven
    # beyond-int32 bound takes this route; nothing small does by itself)
    wide_i64: bool = False
    # PodGroups: the engine's vectorized quorum pass admits or parks them
    # and the gang plugin's QueueSort orders the queue; the reference
    # knows no gang, and is asked in the queue's order (see _gang)
    podgroups: tuple = ()


# ---------------------------------------------------------------- workloads

def _contention():
    """2 nodes, many pods: every pod changes what the next one sees."""
    return Workload(make_nodes(2, seed=3), make_pods(30, seed=4), RELAXED)


def _tie_scores():
    """Identical nodes x identical pods: every node ties on every score,
    so selection rides the argmax first-max tie-break."""
    nodes = [{"metadata": {"name": f"tie-{i}"},
              "status": {"allocatable": {"cpu": "8", "memory": "16Gi",
                                         "pods": "20"}}} for i in range(6)]
    pods = [{"metadata": {"name": f"twin-{i:02d}", "namespace": "default"},
             "spec": {"containers": [{
                 "name": "c",
                 "resources": {"requests": {"cpu": "500m",
                                            "memory": "1Gi"}}}]}}
            for i in range(18)]
    return Workload(nodes, pods, RELAXED)


def _label_coupled(interpod: bool):
    """BASELINE configs 4 / 5's plugin sets: a bound pod changes the
    evaluation of every later pod its selectors see."""
    nodes = make_nodes(20, seed=13, taint_fraction=0.2)
    pods = make_pods(48, seed=14, with_affinity=True, with_tolerations=True,
                     with_spread=True, with_interpod=interpod)
    return Workload(nodes, pods, COUPLED_CFG
                    + (["InterPodAffinity"] if interpod else []))


def _nodeports():
    """hostPort contention: a bind occupies the port on its node only."""
    pods = [{"metadata": {"name": f"hp-{i}", "namespace": "default"},
             "spec": {"containers": [{
                 "name": "c",
                 "resources": {"requests": {"cpu": "100m"}},
                 "ports": [{"hostPort": 8000 + (i % 3),
                            "protocol": "TCP"}]}]}} for i in range(18)]
    return Workload(make_nodes(6, seed=7), pods,
                    ["NodeResourcesFit", "NodePorts"])


def _namespace_selector():
    """A cross-namespace required anti-affinity via namespaceSelector: p1
    may not share a zone with p0, which the namespace manifests alone
    tell."""
    def node(name, zone, cpu):
        return {"metadata": {"name": name, "labels":
                             {"topology.kubernetes.io/zone": zone,
                              "kubernetes.io/hostname": name}},
                "status": {"allocatable": {"cpu": cpu, "memory": "8Gi",
                                           "pods": "10"}}}

    nodes = [node("n0", "A", "300m"), node("n1", "A", "4"),
             node("n2", "B", "4")]
    namespaces = ({"metadata": {"name": "a", "labels": {"team": "x"}}},
                  {"metadata": {"name": "b", "labels": {"team": "y"}}})
    p0 = {"metadata": {"name": "p0", "namespace": "a",
                       "labels": {"app": "x"}},
          "spec": {"containers": [{"name": "c", "resources":
                                   {"requests": {"cpu": "200m"}}}]}}
    p1 = {"metadata": {"name": "p1", "namespace": "b",
                       "labels": {"app": "y"}},
          "spec": {"containers": [{"name": "c", "resources":
                                   {"requests": {"cpu": "1"}}}],
                   "affinity": {"podAntiAffinity": {
                       "requiredDuringSchedulingIgnoredDuringExecution": [{
                           "labelSelector": {"matchLabels": {"app": "x"}},
                           "namespaceSelector": {},
                           "topologyKey": "topology.kubernetes.io/zone"}]}}}}
    return Workload(nodes, [p0, p1], ["NodeResourcesFit", "InterPodAffinity"],
                    namespaces=namespaces)


def _wide_i64():
    """Pinned and broad pods on the widest score tier: the raw columns
    travel as int64 and nothing may narrow them on the way."""
    nodes, pinned = make_slot_pinned_workload(20, 16, seed=81)
    pods = pinned[:10] + make_pods(8, seed=82) + pinned[10:]
    return Workload(nodes, pods, RELAXED, wide_i64=True)


def _gang():
    """An admitted group and a below-quorum group (one member fits
    nowhere, the others park) among plain pods.  A parked member holds
    its node as an assumed bind, so every later pod sees the state the
    reference's plain binds leave."""
    pgs, gpods = make_gang_workload(2, 3, seed=12)
    for p in gpods:
        if p["metadata"]["name"] == "gang-0001-member-000":
            p["spec"]["containers"][0]["resources"]["requests"]["cpu"] = "9999"
    return Workload(make_nodes(8, seed=11), make_pods(10, seed=13) + gpods,
                    ["NodeResourcesFit"], podgroups=tuple(pgs))


def _prefilter_queue():
    """Every branch of upstream's NodeAffinity.PreFilter in one queue
    (tests/test_prefilter_result.py)."""
    return Workload(PF_NODES, _pf_queue(), PF_CFG)


def _forty_mixed():
    """Forty pods of differing node-affinity terms and tolerations:
    NodeAffinity's rows as scan arguments."""
    nodes = make_nodes(24, seed=9, taint_fraction=0.2)
    pods = make_pods(40, seed=10, with_affinity=True, with_tolerations=True)
    assert any("affinity" in p["spec"] for p in pods)
    return Workload(nodes, pods, SAFE_CFG)


def _slot_pinned():
    """Every pod pinned to a slot of 2 of 12 nodes: sparse feasibility."""
    nodes, pods = make_slot_pinned_workload(24, 12, seed=41)
    return Workload(nodes, pods, RELAXED + ["NodeAffinity"])


# name -> (builder, the node-shard counts its node count divides)
WORKLOADS = {
    "contention": (_contention, (2,)),
    "tie_scores": (_tie_scores, (2,)),
    "label_coupled": (lambda: _label_coupled(False), (2, 4)),
    "label_coupled_interpod": (lambda: _label_coupled(True), (2, 4)),
    "nodeports": (_nodeports, (2,)),
    "namespace_selector": (_namespace_selector, ()),
    "wide_i64": (_wide_i64, (2, 4)),
    "gang_parked": (_gang, (2, 4)),
    "prefilter_queue": (_prefilter_queue, (2,)),
    "forty_mixed": (_forty_mixed, (2, 4)),
    "slot_pinned": (_slot_pinned, (2, 4)),
}
# the gang cut lives in the engine's commit; a bare replay has no quorum
REPLAY_ROUTES = {
    name: ("packed", "leaves") + tuple(f"mesh{m}" for m in meshes)
    for name, (_b, meshes) in WORKLOADS.items() if name != "gang_parked"}
ENGINE_ROUTES = {
    name: ("streamed", "post_pass") + (
        ("streamed_mesh2",) if 2 in meshes else ())
    for name, (_b, meshes) in WORKLOADS.items()}
CASES = [(name, route) for name in WORKLOADS
         for route in REPLAY_ROUTES.get(name, ()) + ENGINE_ROUTES[name]]


@functools.lru_cache(maxsize=None)
def _workload(name: str) -> Workload:
    return WORKLOADS[name][0]()


@functools.lru_cache(maxsize=None)
def _reference(name: str):
    """[(the 13 annotations, selected node index or -1)] in queue order."""
    w = _workload(name)
    return SequentialScheduler(
        w.nodes, w.pods, PluginSetConfig(enabled=list(w.enabled)),
        namespaces=list(w.namespaces)).schedule_all()


def _force_i64(cw):
    cw.host["score_dtypes"] = tuple("i64" for _ in cw.config.scorers())
    return cw


# ------------------------------------------------------------------- routes

def _replayed(w: Workload, route: str):
    """-> [(annotations, node name or "")] of a bare replay down `route`."""
    cw = compile_workload(w.nodes, w.pods,
                          PluginSetConfig(enabled=list(w.enabled)),
                          namespaces=list(w.namespaces))
    if w.wide_i64:
        _force_i64(cw)
    many = max(len(w.pods) // 4, 1)        # four chunks or more
    TRACER.reset()
    if route == "packed":
        rr = replay(cw)
    elif route == "leaves":
        rr = replay(cw, chunk=many)
    else:
        rr = replay(cw, chunk=many, mesh=make_mesh(int(route[4:]), dp=1))
    assert (_route("packed"), _route("leaves")) == (
        (1, 0) if route == "packed" else (0, 1))
    if w.wide_i64:
        assert rr._compact.raw32, "the i64 tier pools its scorers in raw32"
        assert all(jnp.asarray(a).dtype == jnp.int64
                   for a in rr._compact.raw32)
    return [(decode_pod_result(rr, i), rr.selected_node_name(i))
            for i in range(len(w.pods))]


def _served(w: Workload, streamed: bool, monkeypatch, chunk: int = 8,
            mesh=None):
    """-> ({pod: (annotations, spec.nodeName or "")}, parked, the
    queue's order by pod name) of the engine's pass over the workload."""
    if w.wide_i64:
        real = engine_mod.compile_workload
        monkeypatch.setattr(engine_mod, "compile_workload",
                            lambda *a, **kw: _force_i64(real(*a, **kw)))
    custom = {"Coscheduling": Coscheduling()} if w.podgroups else {}
    store = ObjectStore()
    if w.podgroups:
        ensure_podgroup_resource(store)
        for pg in w.podgroups:
            store.create("podgroups", pg)
    for ns in w.namespaces:
        store.create("namespaces", ns)
    for n in w.nodes:
        store.create("nodes", n)
    engine = SchedulerEngine(store, plugin_config=PluginSetConfig(
        enabled=list(w.enabled) + list(custom), custom=custom),
        chunk=chunk, pipeline_commit=streamed, mesh=mesh)
    assert (engine._wave_plan(frozenset(custom)).commit == "streamed") \
        is streamed
    for p in w.pods:
        store.create("pods", p)
    order = [p["metadata"]["name"] for p in engine.pending_pods()]
    engine.schedule_pending()
    out = {}
    for p in store.list("pods")[0]:
        meta = p["metadata"]
        out[meta["name"]] = (dict(meta.get("annotations") or {}),
                             p["spec"].get("nodeName") or "")
    parked = {key: rec.node for key, rec in engine.gang_parked.items()}
    engine.close()
    return out, parked, order


def _assert_served_equals_reference(w, served, parked, want, pods=None):
    names = [n["metadata"]["name"] for n in w.nodes]
    for pod, (anns, sel) in zip(pods or w.pods, want):
        meta = pod["metadata"]
        got, node = served[meta["name"]]
        where = names[sel] if sel >= 0 else ""
        key = (meta.get("namespace", "default"), meta["name"])
        if key in parked:
            # below quorum: decided and its node assumed, neither bound
            # nor annotated until its group is whole or times out
            assert (node, parked[key], got) == ("", where, {}), meta["name"]
            continue
        assert node == where, meta["name"]
        history = json.loads(got[ann.RESULT_HISTORY])
        assert len(history) == 1
        for k, v in anns.items():
            if k in _PERMIT_KEYS and POD_GROUP_LABEL in (
                    meta.get("labels") or {}):
                continue
            assert got[k] == v, (meta["name"], k)
            assert history[0][k] == v, (meta["name"], k)
        assert set(got) == set(anns) | {ann.RESULT_HISTORY}, meta["name"]


# a gang member's Permit entries are the gang plugin's own (wait /
# success and the group's timeout), which the reference does not model
_PERMIT_KEYS = {ann.PERMIT_STATUS_RESULT, ann.PERMIT_TIMEOUT_RESULT}


@pytest.mark.parametrize("name,route", CASES,
                         ids=[f"{n}-{r}" for n, r in CASES])
def test_route_equals_the_sequential_reference(name, route, monkeypatch):
    w, want = _workload(name), _reference(name)
    if route in ENGINE_ROUTES[name]:
        TRACER.reset()
        served, parked, order = _served(
            w, route != "post_pass", monkeypatch,
            mesh=make_mesh(2, dp=1) if route == "streamed_mesh2" else None)
        assert (_route("leaves") > 0) is (
            route == "streamed_mesh2" or len(w.pods) > 8)
        assert bool(parked) is bool(w.podgroups)
        pods = w.pods
        if w.podgroups:
            by_name = {p["metadata"]["name"]: p for p in w.pods}
            pods = [by_name[n] for n in order]
            want = SequentialScheduler(w.nodes, pods, PluginSetConfig(
                enabled=list(w.enabled))).schedule_all()
        assert [p["metadata"]["name"] for p in pods] == order
        _assert_served_equals_reference(w, served, parked, want, pods)
        return
    names = [n["metadata"]["name"] for n in w.nodes]
    for i, ((got, node), (anns, sel)) in enumerate(
            zip(_replayed(w, route), want)):
        assert node == (names[sel] if sel >= 0 else ""), (i, route)
        for k, v in anns.items():
            assert got[k] == v, (i, k, route)


# --------------------------------- a batch pass is one call from its first

@pytest.mark.parametrize("streamed", [True, False],
                         ids=["streamed", "post_pass"])
@pytest.mark.parametrize("count", [8, 9, 30, 32])
def test_a_batch_pass_is_one_packed_call_from_its_first_pass(
        count, streamed, monkeypatch):
    """A pass of 8-512 pods on a profile without a PostFilter, on a
    cluster with room (every pod fits everywhere): ONE packed call, one
    decision fetch, nothing over leaves, from the session's first pass,
    under either commit; every pod as the reference decides it."""
    w = Workload(make_nodes(16, seed=31), make_pods(count, seed=32), RELAXED)
    want = SequentialScheduler(
        w.nodes, w.pods, PluginSetConfig(enabled=RELAXED)).schedule_all()
    TRACER.reset()
    served, parked, _ = _served(w, streamed, monkeypatch, chunk=64)
    counters = TRACER.counter_totals()
    assert (_route("packed"), _route("leaves")) == (1, 0)
    assert counters["decision_fetch_transfers_total"] == 1
    assert counters.get("commit_stream_waves_total", 0) == int(streamed)
    assert counters.get("wave_retries_total", 0) == 0
    _assert_served_equals_reference(w, served, parked, want)
    assert all(node for _a, node in served.values())  # everything bound


# ------------------------------------------- a fault in the middle of a wave

@pytest.mark.parametrize("nth", [1, 2])
def test_mid_wave_dispatch_fault_retries_the_suffix(nth, monkeypatch):
    """A transient fault at a chunk's dispatch (the first chunk's: nothing
    committed; the second's: the first chunk's commit stands): the
    uncommitted suffix retries, recompiled against the store as it then
    is, and every pod is still what the reference decides."""
    w = Workload(make_nodes(10, seed=21),
                 make_pods(30, seed=22, with_affinity=True),
                 RELAXED + ["NodeAffinity"])
    want = SequentialScheduler(
        w.nodes, w.pods, PluginSetConfig(enabled=list(w.enabled))
    ).schedule_all()
    TRACER.reset()
    plan = faults.FaultPlan([
        faults.FaultRule("replay.scan_dispatch", nth=nth, error="runtime"),
    ], seed=7)
    with faults.armed(plan):
        served, parked, _ = _served(w, True, monkeypatch)
    assert plan.stats()["rules"][0]["trips"] == 1, "the fault never fired"
    assert TRACER.counter_totals().get("wave_retries_total", 0) >= 1
    _assert_served_equals_reference(w, served, parked, want)


# ------------------------------------------------ the history of two waves

@pytest.mark.parametrize("streamed", [True, False],
                         ids=["streamed", "post_pass"])
def test_result_history_across_waves_equals_the_reference(streamed,
                                                          monkeypatch):
    """Pods that fit nowhere in the first wave and bind in the second,
    after nodes arrived: each carries two history records, the first
    wave's and the second's, byte for byte the reference's two answers
    (the second over the first wave's binds)."""
    small = [{"metadata": {"name": f"small-{i}"},
              "status": {"allocatable": {"cpu": "4", "memory": "16Gi",
                                         "pods": "6"}}} for i in range(2)]
    more = make_nodes(4, seed=62)
    pods = make_pods(40, seed=63)
    cfg = PluginSetConfig(enabled=RELAXED)
    first = SequentialScheduler(small, pods, cfg).schedule_all()
    names = [n["metadata"]["name"] for n in small]
    bound = [(p, names[sel]) for p, (_a, sel) in zip(pods, first) if sel >= 0]
    left = [p for p, (_a, sel) in zip(pods, first) if sel < 0]
    assert bound and left
    second = SequentialScheduler(small + more, left, cfg,
                                 bound_pods=bound).schedule_all()
    assert all(sel >= 0 for _a, sel in second)

    store = ObjectStore()
    for n in small:
        store.create("nodes", n)
    for p in pods:
        store.create("pods", p)
    engine = SchedulerEngine(store, plugin_config=cfg, chunk=8,
                             pipeline_commit=streamed)
    assert engine.schedule_pending() == len(bound)
    for n in more:
        store.create("nodes", n)
    assert engine.schedule_pending() == len(left)
    engine.close()
    once = {p["metadata"]["name"]: a for p, (a, sel) in zip(pods, first)}
    twice = {p["metadata"]["name"]: a for p, (a, _s) in zip(left, second)}
    for p in store.list("pods")[0]:
        name = p["metadata"]["name"]
        history = json.loads(p["metadata"]["annotations"][ann.RESULT_HISTORY])
        assert history == [once[name]] + (
            [twice[name]] if name in twice else []), name
        latest = twice.get(name, once[name])
        for k, v in latest.items():
            assert p["metadata"]["annotations"][k] == v, (name, k)
