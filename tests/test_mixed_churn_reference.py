"""The program against benchmark/reference/mixed_churn.py, the plain
reference that replays a churn op (PR 32): clusters shaped like
`sched_perf_mixed_churn_5k` at tens of nodes, served over HTTP the way
benchmark/drivers/closed_loop_churn.py drives them — before every measured
pod one tick: the previous churn node / pod / service deleted, the next
three created, the churn pod awaited and read in full.

  * every measured pod's 13 annotations + spec.nodeName AND every churn
    pod's 13 annotations, byte for byte, over 9 cycles; the same
    reference in int32/float32 (the control) differs;
  * what the served path did on the way: two passes of one pod a cycle
    (the parked churn pod in no later pass), no per-node preemption probe,
    one screen a cycle once a measured pod is bound, and in it no dry run:
    every node that holds a pod is too small for the churn pod even when
    empty (the static rule, PR 33), so the cycle's compile_workload calls
    are its two passes' own;
  * the reference by itself: the churn node may sort anywhere, and what it
    refuses (NotCovered).
"""

from __future__ import annotations

import copy
import json
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))

from generators.scheduler_perf_churn import generate  # noqa: E402
from reference import mixed_churn as ref  # noqa: E402
from reference.default_profile import Narrow32, NotCovered  # noqa: E402

from kube_scheduler_simulator_tpu.config.config import SimulatorConfiguration  # noqa: E402
from kube_scheduler_simulator_tpu.server.di import DIContainer  # noqa: E402
from kube_scheduler_simulator_tpu.server.server import SimulatorServer  # noqa: E402
from kube_scheduler_simulator_tpu.utils.tracing import TRACER  # noqa: E402

PARAMS = json.loads(
    (BENCH / "configs/sched_perf_mixed_churn_5k.json").read_text())["parameters"]
RESOURCE = {"Node": "nodes", "Pod": "pods", "Service": "services"}
COUNTERS = ("scheduling_work_passes_total", "scheduling_pass_pods_total",
            "preemption_attempts_total", "preemption_fit_probes_total",
            "pods_unschedulable_parked_total", "node_table_builds_total",
            "preemption_static_refused_nodes_total",
            "preemption_screen_dry_runs_total",
            "preemption_screen_refused_nodes_total")
FIT = ("Too many pods, Insufficient cpu, Insufficient memory")


def _deployment(nodes: int, seed: int, initial: int = 0):
    return generate(dict(PARAMS, nodes=nodes, initial_pods=dict(
        PARAMS["initial_pods"], count=initial)), seed)


def _req(port, method, path, body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}", data=data,
                                 method=method)
    if data is not None:
        req.add_header("Content-Type", "application/json")
    while True:
        try:
            with urllib.request.urlopen(req, timeout=300) as r:
                return r.status, json.loads(r.read() or b"null")
        except urllib.error.HTTPError as e:
            if e.code != 429:  # shed by the autopilot after a slow first pass
                raise
            time.sleep(0.2)


def _obj_path(obj: dict) -> str:
    meta = obj["metadata"]
    ns = f"/{meta['namespace']}" if "namespace" in meta else ""
    return f"/api/v1/{RESOURCE[obj['kind']]}{ns}/{meta['name']}"


def _read_decided(port: int, ns: str, name: str) -> dict:
    deadline = time.time() + 120
    while True:
        _, got = _req(port, "GET", f"/api/v1/pods/{ns}/{name}")
        annos = got["metadata"].get("annotations") or {}
        decided = got["spec"].get("nodeName") or any(
            c.get("reason") == "Unschedulable"
            for c in (got.get("status") or {}).get("conditions") or [])
        if decided and all(k in annos for k in ref.KEYS):
            return got
        assert time.time() < deadline, f"{name} not decided"
        time.sleep(0.02)


def _serve(dep, cycles: int):
    """-> (measured pods as read, churn pods as read, the measured pods'
    manifests, the counters' growth)."""
    srv = SimulatorServer(DIContainer(SimulatorConfiguration(port=0)), port=0)
    srv.start(block=False)
    measured, churned, pods, live = [], [], [], []
    try:
        path = "/api/v1/import?ignoreSchedulerConfiguration=true"
        assert _req(srv.port, "POST", path, {"namespaces": dep.namespaces,
                                             "nodes": dep.nodes})[0] == 200
        assert _req(srv.port, "POST", path, {"pods": dep.initial_pods})[0] == 200
        before = TRACER.counter_totals()
        spans_before = TRACER.snapshot()["spans"]
        rebuilds_before = TRACER.labeled_totals(
            "bound_carry_rebuilds_total", "reason")
        for k in range(cycles):
            for obj in live:
                assert _req(srv.port, "DELETE", _obj_path(obj))[0] == 200
            live = dep.nodes.churn.trio(k)
            for obj in live:
                assert _req(srv.port, "POST",
                            f"/api/v1/{RESOURCE[obj['kind']]}", obj)[0] == 201
            cp = live[1]["metadata"]
            churned.append(_read_decided(srv.port, cp["namespace"], cp["name"]))
            pod = dep.measured_pod()
            pods.append(pod)
            assert _req(srv.port, "POST", "/api/v1/pods", pod)[0] == 201
            measured.append(_read_decided(
                srv.port, pod["metadata"]["namespace"], pod["metadata"]["name"]))
        after = TRACER.counter_totals()
        spans_after = TRACER.snapshot()["spans"]
        rebuilds_after = TRACER.labeled_totals(
            "bound_carry_rebuilds_total", "reason")
        services = _req(srv.port, "GET", "/api/v1/services")[1]["items"]
        initial_on = [
            _req(srv.port, "GET", f"/api/v1/pods/{p['metadata']['namespace']}"
                 f"/{p['metadata']['name']}")[1]["spec"]["nodeName"]
            for p in dep.initial_pods]
    finally:
        srv.shutdown()
    growth = {k: after.get(k, 0) - before.get(k, 0) for k in COUNTERS}
    for name in ("compile_workload", "preempt_screen",
                 "preempt_screen_dry_run", "replay_and_decode_stream"):
        growth[f"span:{name}"] = (
            (spans_after.get(name) or {}).get("count", 0)
            - (spans_before.get(name) or {}).get("count", 0))
    growth["uncarried"] = (rebuilds_after.get("uncarried", 0)
                           - rebuilds_before.get("uncarried", 0))
    # the nodes that held a pod when cycle k's churn pod was tried
    growth["holding"] = sum(
        len(set(initial_on) | {m["spec"]["nodeName"] for m in measured[:k]})
        for k in range(cycles))
    assert [s["metadata"]["name"] for s in services] == [
        live[2]["metadata"]["name"]], "one churn service lives at a time"
    return measured, churned, pods, growth


def _differing(measured, churned, dep, pods, arith) -> int:
    oracle = ref.ReferenceScheduler(dep.nodes, dep.initial_pods, arith)
    oracle.render_churn = True
    differing = 0
    for got, pod in zip(measured, pods):
        want, node = oracle.schedule_one(pod)
        differing += (got["spec"].get("nodeName") or "") != node
        differing += sum(got["metadata"]["annotations"][k] != want[k]
                         for k in ref.KEYS)
    for got in churned:
        want = oracle.churn_results[got["metadata"]["name"]]
        differing += bool(got["spec"].get("nodeName"))
        differing += sum(got["metadata"]["annotations"][k] != want[k]
                         for k in ref.KEYS)
    return differing


@pytest.mark.parametrize("seed,initial", [(32, 0), (2147483777, 6)])
def test_served_under_churn_equals_the_reference(seed, initial):
    n, cycles = 40, 9
    dep = _deployment(n, seed, initial)
    measured, churned, pods, growth = _serve(dep, cycles)
    assert _differing(measured, churned, dep, pods, ref.Exact) == 0
    fresh = _deployment(n, seed, initial)  # the control draws the churn anew
    assert _differing(measured, churned, fresh, pods, Narrow32) > 0, \
        "the control passed"
    for k, got in enumerate(churned):
        annos = got["metadata"]["annotations"]
        assert not got["spec"].get("nodeName")
        assert not (got.get("status") or {}).get("nominatedNodeName")
        filt = json.loads(annos[ref.KEYS[2]])
        node_k = dep.nodes.churn.trio(k)[0]["metadata"]["name"]
        assert len(filt) == n + 1
        assert filt[node_k]["NodeResourcesFit"] == FIT
        assert {e["NodeResourcesFit"] for nm, e in filt.items()
                if nm != node_k} == {"Insufficient cpu"}
        assert json.loads(annos[ref.KEYS[3]]) == {nm: {} for nm in filt}
        assert annos[ref.KEYS[-1]] == ""
    for k, got in enumerate(measured):
        annos = got["metadata"]["annotations"]
        filt = json.loads(annos[ref.KEYS[2]])
        node_k = dep.nodes.churn.trio(k)[0]["metadata"]["name"]
        assert filt[node_k]["NodeResourcesFit"] == FIT
        assert set(json.loads(annos[ref.KEYS[5]])) == set(filt) - {node_k}
        assert got["spec"]["nodeName"] in filt
    # two passes of one pod a cycle: the parked churn pod rides in no later
    # pass; one preemption attempt a cycle and not one per-node dry run;
    # every tick's first pass builds the node table anew
    assert growth["scheduling_work_passes_total"] == 2 * cycles
    assert growth["scheduling_pass_pods_total"] == 2 * cycles
    assert growth["preemption_attempts_total"] == cycles
    assert growth["preemption_fit_probes_total"] == 0
    assert growth["pods_unschedulable_parked_total"] == cycles
    assert growth["node_table_builds_total"] >= cycles
    # PostFilter: the screen's span opens on every attempt that has a node
    # holding a pod; the static rule takes every such node (9 CPU fits no
    # 4-CPU node), so no dry run: no third compile_workload, no filter-only
    # replay, no throw-away carry
    with_candidates = cycles if initial else cycles - 1
    assert growth["span:preempt_screen"] == with_candidates
    assert growth["preemption_static_refused_nodes_total"] == growth["holding"] > 0
    assert growth["preemption_screen_dry_runs_total"] == 0
    assert growth["preemption_screen_refused_nodes_total"] == 0
    assert growth["span:preempt_screen_dry_run"] == 0
    assert growth["span:compile_workload"] == 2 * cycles
    assert growth["span:replay_and_decode_stream"] == 2 * cycles
    assert growth["uncarried"] == 0


# ---- the reference by itself ---------------------------------------------

def _tiny(seed: int = 5):
    dep = _deployment(6, seed)
    return dep, ref.ReferenceScheduler(dep.nodes, dep.initial_pods)


def test_reference_replays_one_tick_per_pod_and_keeps_one_churn_node():
    dep, sched = _tiny()
    sched.render_churn = True
    for k in range(3):
        anns, node = sched.schedule_one(dep.measured_pod())
        filt = json.loads(anns[ref.KEYS[2]])
        churn_node = dep.nodes.churn.trio(k)[0]["metadata"]["name"]
        assert len(filt) == 7 and churn_node in filt and node != churn_node
        assert sched.ticks == k + 1 and len(sched.churn_results) == k + 1
    assert sched.n == 7


def test_reference_sorts_a_churn_node_wherever_its_name_falls():
    """`node-churn-` happens to sort first; a template that sorts last
    must still take the tie-break position its name gives it."""
    params = copy.deepcopy(PARAMS)
    params["churn"]["templates"][0]["metadata"]["generateName"] = "zz-churn-"
    params["churn"]["templates"][0]["status"]["allocatable"] = \
        copy.deepcopy(params["node_template"]["status"]["allocatable"])
    dep = generate(dict(params, nodes=3), 5)
    sched = ref.ReferenceScheduler(dep.nodes, dep.initial_pods)
    pod = dep.nodes.churn.trio(0)[1]
    sched._swap_churn_node(dep.nodes.churn.trio(0)[0])
    assert [sched.names[j] for j in sched.order] == sorted(sched.names)
    assert sched.names[sched.order[-1]].startswith("zz-churn-")
    # every node is empty and equal: the first in name order wins, not the slot
    anns, _ = sched.schedule_churn_pod(pod)
    sched.churn = None
    _, node = sched.schedule_one(dep.measured_pod())
    assert node == sorted(sched.names)[0]


def test_not_covered_a_churn_pod_that_fits():
    dep, sched = _tiny()
    pod = copy.deepcopy(dep.nodes.churn.trio(0)[1])
    pod["spec"]["containers"][0]["resources"]["requests"]["cpu"] = "1"
    with pytest.raises(NotCovered):
        sched.schedule_churn_pod(pod)


def test_not_covered_a_preemption_that_finds_a_candidate():
    """A pod of priority 10 that fits an EMPTIED node but not the node as
    it stands would evict: another deployment."""
    dep, sched = _tiny()
    for _ in range(6 * 40):  # 40 x 100m on every node: 4 CPU taken
        sched.churn = None
        sched.schedule_one(dep.measured_pod())
    pod = copy.deepcopy(dep.nodes.churn.trio(0)[1])
    pod["spec"]["containers"][0]["resources"]["requests"]["cpu"] = "3"
    with pytest.raises(NotCovered):
        sched.schedule_churn_pod(pod)


def test_not_covered_a_churn_node_that_leaves_with_pods():
    params = copy.deepcopy(PARAMS)
    params["churn"]["templates"][0]["status"]["allocatable"] = {
        "cpu": "64", "memory": "512Gi", "pods": "110"}
    dep = generate(dict(params, nodes=3), 5)
    sched = ref.ReferenceScheduler(dep.nodes, dep.initial_pods)
    sched._swap_churn_node(dep.nodes.churn.trio(0)[0])
    sched._bind(ref._Pod(dep.measured_pod()), sched.slot)
    with pytest.raises(NotCovered):
        sched._swap_churn_node(dep.nodes.churn.trio(1)[0])


def test_churn_names_come_from_a_stream_of_their_own():
    a, b = _deployment(8, 77), _deployment(8, 77)
    a.nodes.churn.trio(20)  # draw churn first on one side only
    assert [n["metadata"]["name"] for n in a.nodes] == \
        [n["metadata"]["name"] for n in b.nodes]
    assert a.measured_pod() == b.measured_pod()
    assert a.nodes.churn.trio(3) == b.nodes.churn.trio(3)
    assert json.loads(json.dumps(a.nodes)) == list(a.nodes)
    kinds = [o["kind"] for o in a.nodes.churn.trio(0)]
    assert kinds == ["Node", "Pod", "Service"]
    assert all(n["metadata"]["name"] < a.nodes[0]["metadata"]["name"][:9]
               for n in [a.nodes.churn.trio(0)[0]])
