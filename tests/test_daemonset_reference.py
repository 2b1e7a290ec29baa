"""The program against benchmark/reference/daemonset.py, the plain
reference for pods that name their node (PR 38): clusters shaped like
`sched_perf_daemonset_15k` (one node created by name among `node-default`
nodes, every measured pod pinned to it by `matchFields: metadata.name`) at
tens of nodes, served over HTTP the way benchmark/drivers/closed_loop.py
drives them.

  * a run of pods onto the one node, each read in full: all 13 annotations
    + spec.nodeName byte for byte, prefilter-result naming the node,
    filter-result holding that node's entry and no other, the score maps
    empty; the same reference in int32/float32 (the control) differs;
  * the case where that node refuses (its pod capacity used up): the pod
    stays pending, Unschedulable, one filter-result entry ending at the
    refusal, one postfilter-result entry;
  * what the served path counted on the way: one narrowed pod and one
    considered node a pass, one scan compiled for all of them;
  * the reference by itself: what it refuses (NotCovered), and that it
    imports nothing of the program.
"""

from __future__ import annotations

import copy
import json
import sys
import time
import urllib.request
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))

from generators.scheduler_perf_named_node import generate  # noqa: E402
from reference import daemonset as ref  # noqa: E402
from reference.default_profile import Narrow32, NotCovered  # noqa: E402

from kube_scheduler_simulator_tpu.config.config import SimulatorConfiguration  # noqa: E402
from kube_scheduler_simulator_tpu.server.di import DIContainer  # noqa: E402
from kube_scheduler_simulator_tpu.server.server import SimulatorServer  # noqa: E402
from kube_scheduler_simulator_tpu.utils.tracing import TRACER  # noqa: E402

CONFIG = json.loads(
    (BENCH / "configs/sched_perf_daemonset_15k.json").read_text())
PARAMS = CONFIG["parameters"]
NAMED = PARAMS["named_node"]["metadata"]["name"]
(K_STATUS, K_PREFILTER, K_FILTER, K_POSTFILTER, K_PRESCORE, K_SCORE,
 K_FINAL) = ref.KEYS[:7]
K_SELECTED = ref.KEYS[-1]
COUNTERS = ("prefilter_narrowed_pods_total", "prefilter_considered_nodes_total",
            "filter_rejected_nodes_total", "scheduling_waves_total")


def _deployment(nodes: int, seed: int, named_pods: str | None = None):
    params = copy.deepcopy(PARAMS)
    params["nodes"] = nodes
    if named_pods is not None:
        for group in ("capacity", "allocatable"):
            params["named_node"]["status"][group]["pods"] = named_pods
    return generate(params, seed)


def _req(port, method, path, body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}", data=data,
                                 method=method)
    if data is not None:
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=300) as r:
        return r.status, json.loads(r.read() or b"null")


def _decided(pod: dict) -> bool:
    if pod["spec"].get("nodeName"):
        return True
    return any(c.get("type") == "PodScheduled" and c.get("reason") == "Unschedulable"
               for c in (pod.get("status") or {}).get("conditions") or [])


def _serve(dep, pods: list[dict]) -> tuple[list[dict], dict]:
    """The pods created one at a time against a stock server, each read in
    full -> (pods as read, the counters' growth over the run)."""
    srv = SimulatorServer(DIContainer(SimulatorConfiguration(port=0)), port=0)
    srv.start(block=False)
    served = []
    try:
        path = "/api/v1/import?ignoreSchedulerConfiguration=true"
        assert _req(srv.port, "POST", path, {"namespaces": dep.namespaces,
                                             "nodes": dep.nodes})[0] == 200
        before = TRACER.counter_totals()
        for pod in pods:
            ns, name = pod["metadata"]["namespace"], pod["metadata"]["name"]
            assert _req(srv.port, "POST", "/api/v1/pods", pod)[0] == 201
            deadline = time.time() + 120
            while True:
                _, got = _req(srv.port, "GET", f"/api/v1/pods/{ns}/{name}")
                annos = got["metadata"].get("annotations") or {}
                if _decided(got) and all(k in annos for k in ref.KEYS):
                    break
                assert time.time() < deadline, f"{name} not decided"
                time.sleep(0.02)
            served.append(got)
        # the commit counts after the pod is readable: let it catch up
        deadline = time.time() + 10
        while True:
            after = TRACER.counter_totals()
            if (after.get(COUNTERS[0], 0) - before.get(COUNTERS[0], 0)
                    >= len(pods) or time.time() > deadline):
                break
            time.sleep(0.01)
    finally:
        srv.shutdown()
    return served, {k: after.get(k, 0) - before.get(k, 0) for k in COUNTERS}


def _differing(served: list[dict], dep, pods: list[dict], arith) -> int:
    oracle = ref.ReferenceScheduler(dep.nodes, dep.initial_pods, arith)
    differing = 0
    for got, pod in zip(served, pods):
        want, node = oracle.schedule_one(pod)
        differing += (got["spec"].get("nodeName") or "") != node
        differing += sum(got["metadata"]["annotations"][k] != want[k]
                         for k in ref.KEYS)
    return differing


@pytest.mark.parametrize("seed", [38, 2147483777])
def test_served_onto_the_named_node_and_past_its_capacity(seed):
    """40 node-default nodes + the named one, whose pod capacity is cut to
    6: six pods land on it, the seventh is refused by it and by no other."""
    dep = _deployment(40, seed, named_pods="6")
    assert len(dep.nodes) == 41 and dep.nodes[-1]["metadata"]["name"] == NAMED
    pods = [dep.measured_pod() for _ in range(7)]
    served, grew = _serve(dep, pods)
    assert _differing(served, dep, pods, ref.Exact) == 0
    assert _differing(served, dep, pods, Narrow32) > 0, "the control passed"

    assert [p["spec"].get("nodeName") for p in served] == [NAMED] * 6 + [None]
    for got in served:
        anns = got["metadata"]["annotations"]
        assert json.loads(anns[K_PREFILTER]) == {"NodeAffinity": [NAMED]}
        assert json.loads(anns[K_STATUS])["NodeAffinity"] == "success"
        assert list(json.loads(anns[K_FILTER])) == [NAMED]
        # one feasible node at most: upstream's short cut, no scoring
        assert anns[K_PRESCORE] == anns[K_SCORE] == anns[K_FINAL] == "{}"
    bound = json.loads(served[0]["metadata"]["annotations"][K_FILTER])[NAMED]
    assert bound == {nm: "passed" for nm in (
        "NodeUnschedulable", "NodeName", "TaintToleration", "NodeAffinity",
        "NodeResourcesFit")}
    last = served[-1]["metadata"]["annotations"]
    assert json.loads(last[K_FILTER])[NAMED]["NodeResourcesFit"] == "Too many pods"
    assert json.loads(last[K_POSTFILTER]) == {NAMED: {}}
    assert last[K_SELECTED] == ""

    # one pod a pass, narrowed to the one node; the one refusal is the
    # last pod's; the queue compiled one scan and DefaultPreemption's look
    # at the refused node none of its own
    assert grew["prefilter_narrowed_pods_total"] == 7
    assert grew["prefilter_considered_nodes_total"] == 7
    assert grew["filter_rejected_nodes_total"] == 1
    assert grew["scheduling_waves_total"] == 7


def test_generator_keeps_the_seeded_names_of_the_plain_deployment():
    from generators.scheduler_perf import generate as plain

    dep = _deployment(12, 5)
    same = plain(dict(PARAMS, nodes=12), 5)
    assert [n["metadata"]["name"] for n in dep.nodes[:-1]] == [
        n["metadata"]["name"] for n in same.nodes]
    assert dep.nodes[-1] == PARAMS["named_node"]
    assert dep.initial_pods == [] and dep.measured_namespace == "default"
    pod = dep.measured_pod()
    assert pod["metadata"]["name"].startswith("daemonset-")
    assert ref.named_node(pod) == NAMED


def test_configuration_states_the_source_and_its_one_cut():
    assert CONFIG["reduced"] == ["measurePods"]
    assert PARAMS["nodes"] == PARAMS["initNodes"] == 15000
    assert PARAMS["initial_pods"]["count"] == 0
    assert len(CONFIG["source"]) <= 200
    # room for the source's 30,000 pods of 100m / 500Mi
    cap = PARAMS["named_node"]["status"]["allocatable"]
    assert int(cap["pods"]) >= 30000 and int(cap["cpu"]) * 10 >= 30000
    assert int(cap["memory"][:-2]) * 1024 // 500 >= 30000


def _pod_with(mutate):
    pod = _deployment(3, 1).measured_pod()
    mutate(pod)
    return pod


def _terms(pod):
    return pod["spec"]["affinity"]["nodeAffinity"][
        "requiredDuringSchedulingIgnoredDuringExecution"]["nodeSelectorTerms"]


NOT_COVERED = {
    "two_terms": lambda p: _terms(p).append(copy.deepcopy(_terms(p)[0])),
    "two_requirements": lambda p: _terms(p)[0]["matchFields"].append(
        copy.deepcopy(_terms(p)[0]["matchFields"][0])),
    "two_values": lambda p: _terms(p)[0]["matchFields"][0]["values"].append("x"),
    "not_in": lambda p: _terms(p)[0]["matchFields"][0].update(operator="NotIn"),
    "another_field": lambda p: _terms(p)[0]["matchFields"][0].update(
        key="metadata.namespace"),
    "label_expression_beside_the_field": lambda p: _terms(p)[0].update(
        matchExpressions=[{"key": "a", "operator": "Exists"}]),
    "preferred_terms": lambda p: p["spec"]["affinity"]["nodeAffinity"].update(
        preferredDuringSchedulingIgnoredDuringExecution=[]),
    "pod_affinity_beside_it": lambda p: p["spec"]["affinity"].update(
        podAntiAffinity={}),
    "node_selector": lambda p: p["spec"].update(nodeSelector={"a": "b"}),
    "a_name_that_is_no_node": lambda p: _terms(p)[0]["matchFields"][0].update(
        values=["gone"]),
}


@pytest.mark.parametrize("case", sorted(NOT_COVERED))
def test_reference_refuses_what_it_does_not_implement(case):
    dep = _deployment(3, 1)
    sched = ref.ReferenceScheduler(dep.nodes, [], ref.Exact)
    with pytest.raises(NotCovered):
        sched.schedule_one(_pod_with(NOT_COVERED[case]))


def test_reference_imports_nothing_of_the_program():
    src = (BENCH / "reference" / "daemonset.py").read_text()
    assert "kube_scheduler_simulator_tpu" not in src
    assert set(ref.ARITHMETICS) >= {"exact", "narrow32"}
    assert len(ref.KEYS) == 13
