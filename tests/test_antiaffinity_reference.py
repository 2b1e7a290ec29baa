"""The program against benchmark/reference/antiaffinity.py, the plain
reference that can render a refusal (PR 30): clusters shaped like
`sched_perf_antiaffinity_5k` (a hostname a node, one color=green pod a
host, two namespaces) at tens of nodes.

  * served one pod at a time over HTTP from a fifth full to the last free
    hostname and one pod past it: all 13 annotations + spec.nodeName byte
    for byte, the Unschedulable outcome included; the same reference in
    int32/float32 (the control) differs;
  * one scan over the whole queue: the device carry's bind decides the
    next pod's Filter;
  * each of InterPodAffinity's three checks firing first, so upstream's
    order and messages are pinned;
  * what the reference refuses (NotCovered), case by case;
  * `filter_rejected_nodes_total` and `decode_filter_failed_entries_total`
    rise by nodes - feasible for a pod with refusals and by 0 without.
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

from generators.scheduler_perf_unique_label import generate  # noqa: E402
from reference import antiaffinity as ref  # noqa: E402
from reference.default_profile import Narrow32, NotCovered  # noqa: E402

from kube_scheduler_simulator_tpu.config.config import SimulatorConfiguration  # noqa: E402
from kube_scheduler_simulator_tpu.framework.replay import replay  # noqa: E402
from kube_scheduler_simulator_tpu.server.di import DIContainer  # noqa: E402
from kube_scheduler_simulator_tpu.server.server import SimulatorServer  # noqa: E402
from kube_scheduler_simulator_tpu.state.compile import compile_workload  # noqa: E402
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result  # noqa: E402
from kube_scheduler_simulator_tpu.utils.tracing import TRACER  # noqa: E402

PARAMS = json.loads(
    (BENCH / "configs/sched_perf_antiaffinity_5k.json").read_text())["parameters"]
HOST, ZONE = "kubernetes.io/hostname", "topology.kubernetes.io/zone"
REJECTED, RENDERED = ("filter_rejected_nodes_total",
                      "decode_filter_failed_entries_total")


def _deployment(nodes: int, initial: int, seed: int):
    return generate(dict(PARAMS, nodes=nodes, initial_pods=dict(
        PARAMS["initial_pods"], count=initial)), seed)


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


def _serve(dep, pods: list[dict]) -> tuple[list[dict], list[dict]]:
    """The pods created one at a time against a stock server, each read in
    full -> (pods as read, the two counters' growth per pod)."""
    srv = SimulatorServer(DIContainer(SimulatorConfiguration(port=0)), port=0)
    srv.start(block=False)
    served, growth = [], []
    try:
        path = "/api/v1/import?ignoreSchedulerConfiguration=true"
        assert _req(srv.port, "POST", path, {"namespaces": dep.namespaces,
                                             "nodes": dep.nodes})[0] == 200
        assert _req(srv.port, "POST", path, {"pods": dep.initial_pods})[0] == 200
        for pod in pods:
            before = TRACER.counter_totals()
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
            # the commit counts its refusals after the pod is readable:
            # give it a moment to catch up with what the read rendered
            while True:
                after = TRACER.counter_totals()
                if (after.get(REJECTED, 0) - before.get(REJECTED, 0)
                        >= after.get(RENDERED, 0) - before.get(RENDERED, 0)
                        or time.time() > deadline):
                    break
                time.sleep(0.01)
            served.append(got)
            growth.append({k: after.get(k, 0) - before.get(k, 0)
                           for k in (REJECTED, RENDERED)})
    finally:
        srv.shutdown()
    return served, growth


def _differing(served: list[dict], dep, pods: list[dict], arith) -> int:
    oracle = ref.ReferenceScheduler(dep.nodes, dep.initial_pods, arith)
    differing = 0
    for got, pod in zip(served, pods):
        want, node = oracle.schedule_one(pod)
        differing += (got["spec"].get("nodeName") or "") != node
        differing += sum(got["metadata"]["annotations"][k] != want[k]
                         for k in ref.KEYS)
    return differing


@pytest.mark.parametrize("seed", [30, 2147483777])
def test_served_to_the_last_free_hostname_and_past_it(seed):
    n, initial = 20, 4
    dep = _deployment(n, initial, seed)
    pods = [dep.measured_pod() for _ in range(n - initial + 1)]
    served, growth = _serve(dep, pods)
    assert _differing(served, dep, pods, ref.Exact) == 0
    assert _differing(served, dep, pods, Narrow32) > 0, "the control passed"
    # one pod a hostname, the last pod refused everywhere and left pending
    taken = [p["spec"].get("nodeName") for p in served]
    assert len(set(taken[:-1])) == n - initial and taken[-1] is None
    last = served[-1]["metadata"]["annotations"]
    assert json.loads(last[ref.K_POSTFILTER]) == {
        nd["metadata"]["name"]: {} for nd in dep.nodes}
    assert last[ref.K_SELECTED] == "" and last[ref.K_SCORE] == "{}"
    # a refused node's entry ends at the refusal; the score maps hold the rest
    first = served[0]["metadata"]["annotations"]
    refused = {p["spec"]["nodeName"] for p in dep.initial_pods}
    filt = json.loads(first[ref.K_FILTER])
    assert {nm for nm, e in filt.items()
            if e.get("InterPodAffinity") == ref.ERR_ANTI_AFFINITY} == refused
    assert set(json.loads(first[ref.K_SCORE])) == set(filt) - refused
    # pod k is refused by the initial pods' hosts and the k hosts taken since
    for k, g in enumerate(growth):
        assert g == {REJECTED: initial + k, RENDERED: initial + k}, (k, g)


def test_one_scan_over_the_queue_equals_the_reference():
    """All pods in one pass: the carry's bind_update, not the next pass's
    host build, takes the hostname away from the next pod."""
    dep = _deployment(24, 5, 47)
    pods = [dep.measured_pod() for _ in range(19)]
    # the store lists nodes by name: that is the index order of the tie-break
    nodes = sorted(dep.nodes, key=lambda nd: nd["metadata"]["name"])
    cw = compile_workload(
        nodes, pods, None, namespaces=dep.namespaces,
        bound_pods=[(p, p["spec"]["nodeName"]) for p in dep.initial_pods])
    rr = replay(cw, chunk=8)
    oracle = ref.ReferenceScheduler(dep.nodes, dep.initial_pods)
    for i, pod in enumerate(pods):
        want, node = oracle.schedule_one(pod)
        got = decode_pod_result(rr, i)
        assert cw.node_table.names[int(rr.selected[i])] == node
        assert int(rr.feasible_count[i]) == 19 - i
        for k in ref.KEYS:
            assert got[k] == want[k], (i, k)


# ---- the three checks, each firing first ---------------------------------

def _node(name: str, zone: str) -> dict:
    nd = copy.deepcopy(PARAMS["node_template"])
    nd["metadata"] = {"name": name, "labels": {HOST: name, ZONE: zone}}
    return nd


def _term(color: str, key: str) -> dict:
    return {"labelSelector": {"matchLabels": {"color": color}},
            "topologyKey": key, "namespaces": ["sched-0", "sched-1"]}


def _pod(name: str, ns: str, color: str, affinity: dict | None,
         node: str | None = None) -> dict:
    pod = copy.deepcopy(PARAMS["measured_pods"]["template"])
    pod["metadata"] = {"name": name, "namespace": ns, "labels": {"color": color}}
    pod["spec"].pop("affinity")
    if affinity:
        pod["spec"]["affinity"] = {
            kind: {"requiredDuringSchedulingIgnoredDuringExecution": terms}
            for kind, terms in affinity.items()}
    if node:
        pod["spec"]["nodeName"] = node
    return pod


# the cluster: zone a = {n0, n1}, zone b = {n2, n3}.  n0 runs a green pod
# with anti-affinity to green by hostname; n2 runs a red pod without terms.
_NODES = [_node("n0", "a"), _node("n1", "a"), _node("n2", "b"), _node("n3", "b")]
_BOUND = [_pod("g0", "sched-0", "green", {"podAntiAffinity": [_term("green", HOST)]}, "n0"),
          _pod("r0", "sched-0", "red", None, "n2")]
_CHECKS = {
    # affinity to red by zone fails in zone a; on n0 checks 2 and 3 fail too
    "own_affinity": (_pod("q", "sched-1", "green", {
        "podAffinity": [_term("red", ZONE)],
        "podAntiAffinity": [_term("green", HOST)]}),
        {"n0": ref.ERR_AFFINITY, "n1": ref.ERR_AFFINITY, "n2": "passed"}),
    # no affinity term: on n0 check 2 fails before check 3
    "own_anti_affinity": (_pod("q", "sched-1", "green", {
        "podAntiAffinity": [_term("green", HOST)]}),
        {"n0": ref.ERR_ANTI_AFFINITY, "n1": "passed"}),
    # the pod's own anti term selects blue, which nothing is: only g0's term bites
    "existing_pods_anti_affinity": (_pod("q", "sched-1", "green", {
        "podAntiAffinity": [_term("blue", HOST)]}),
        {"n0": ref.ERR_EXISTING_ANTI, "n1": "passed"}),
    # no term of its own at all: PreFilter still runs, g0's term matches it
    "existing_only": (_pod("q", "sched-1", "green", None),
                      {"n0": ref.ERR_EXISTING_ANTI, "n3": "passed"}),
}


@pytest.mark.parametrize("case", sorted(_CHECKS))
def test_each_check_fires_first_with_its_message(case):
    pod, expect = _CHECKS[case]
    cw = compile_workload(
        _NODES, [pod], None,
        bound_pods=[(p, p["spec"]["nodeName"]) for p in _BOUND],
        namespaces=[{"metadata": {"name": ns}} for ns in ("sched-0", "sched-1")])
    got = decode_pod_result(replay(cw), 0)
    want, node = ref.ReferenceScheduler(_NODES, _BOUND).schedule_one(pod)
    for k in ref.KEYS:
        assert got[k] == want[k], k
    filt = json.loads(want[ref.K_FILTER])
    for name, message in expect.items():
        assert filt[name]["InterPodAffinity"] == message, (name, filt[name])
        # the entry ends at the refusal; a feasible node is scored
        assert (name in json.loads(want[ref.K_SCORE])) == (message == "passed")
    assert json.loads(want[ref.K_PREFILTER_STATUS])["InterPodAffinity"] == "success"


def test_prefilter_skips_a_pod_no_term_concerns():
    """Upstream's rule (the program's is coarser, docs/SEMANTICS.md): no
    term of its own and no existing anti term that matches it -> Skip."""
    pod = _pod("q", "sched-1", "red", None)
    want, node = ref.ReferenceScheduler(_NODES, _BOUND).schedule_one(pod)
    assert json.loads(want[ref.K_PREFILTER_STATUS])["InterPodAffinity"] == ""
    assert all("InterPodAffinity" not in e
               for e in json.loads(want[ref.K_FILTER]).values())
    assert node in {"n1", "n3"}


# ---- what the reference refuses ------------------------------------------

def _edited(edit) -> dict:
    pod = _pod("q", "sched-1", "green", {"podAntiAffinity": [_term("green", HOST)]})
    edit(pod["spec"])
    return pod


def _anti_term(spec: dict) -> dict:
    return spec["affinity"]["podAntiAffinity"][
        "requiredDuringSchedulingIgnoredDuringExecution"][0]


_NOT_COVERED = {
    "preferred_anti_affinity": lambda s: s["affinity"]["podAntiAffinity"].update(
        preferredDuringSchedulingIgnoredDuringExecution=[
            {"weight": 1, "podAffinityTerm": _term("green", HOST)}]),
    "preferred_affinity": lambda s: s["affinity"].update(podAffinity={
        "preferredDuringSchedulingIgnoredDuringExecution": [
            {"weight": 1, "podAffinityTerm": _term("green", HOST)}]}),
    "node_affinity": lambda s: s["affinity"].update(nodeAffinity={}),
    "match_expressions": lambda s: _anti_term(s)["labelSelector"].update(
        matchExpressions=[{"key": "color", "operator": "Exists"}]),
    "namespace_selector": lambda s: _anti_term(s).update(namespaceSelector={}),
    "match_label_keys": lambda s: _anti_term(s).update(matchLabelKeys=["color"]),
    "mismatch_label_keys": lambda s: _anti_term(s).update(mismatchLabelKeys=["color"]),
    "two_affinity_terms": lambda s: s["affinity"].update(podAffinity={
        "requiredDuringSchedulingIgnoredDuringExecution": [
            _term("red", ZONE), _term("green", ZONE)]}),
    "tolerations": lambda s: s.update(tolerations=[{"operator": "Exists"}]),
    "host_port": lambda s: s["containers"][0]["ports"][0].update(hostPort=80),
}


@pytest.mark.parametrize("case", sorted(_NOT_COVERED))
def test_not_covered(case):
    pod = _edited(_NOT_COVERED[case])
    sched = ref.ReferenceScheduler(_NODES, _BOUND)
    with pytest.raises(NotCovered):
        sched.schedule_one(pod)
    with pytest.raises(NotCovered):  # a bound pod is read the same way
        ref.ReferenceScheduler(_NODES, _BOUND + [dict(pod, spec=dict(
            pod["spec"], nodeName="n1"))])


def test_not_covered_tainted_node():
    tainted = copy.deepcopy(_NODES)
    tainted[1]["spec"] = {"taints": [{"key": "k", "effect": "NoSchedule"}]}
    with pytest.raises(NotCovered):
        ref.ReferenceScheduler(tainted, _BOUND)


# ---- the counters, where no Filter says no --------------------------------

def test_counters_do_not_move_without_a_refusal():
    dep = _deployment(12, 0, 5)
    pod = dep.measured_pod()
    pod["spec"].pop("affinity")
    served, growth = _serve(dep, [pod])
    assert served[0]["spec"]["nodeName"]
    assert growth == [{REJECTED: 0, RENDERED: 0}]
    assert {REJECTED, RENDERED} <= set(TRACER.counter_totals())
