"""The program against benchmark/reference/spread_affinity_taints.py, the
plain reference for BASELINE config 4's five-plugin profile (PR 52):
`baseline_c4_queue_5k` at 40 nodes over 4 zones, ssd / hdd halves, a
tainted pool, and a queue of 60 differing pods of which six in ten carry a
`DoNotSchedule` constraint over zones and a `ScheduleAnyway` one over
hostnames.

  * served in bursts over HTTP under the POSTED profile: all 13
    annotations + spec.nodeName byte for byte; the same reference in
    int32/float32 (the control) differs;
  * one pass over the whole queue, in chunks and as one packed call,
    against the same reference;
  * a zone of which the pod's node affinity excludes a part: upstream
    counts the zone's pods BY NODE, on the nodes the pod's required term
    keeps, and so do the reference and the program (the parent's
    plugins/topologyspread.py folded by domain once for all pods and
    refuses the node this test sees pass);
  * a `ScheduleAnyway` pod whose feasible set is not the whole cluster:
    the weight is log(feasible nodes + 2), per pod, and `maxSkew - 1` is
    added per constraint (the parent took log(all domains + 2) at build
    time and added nothing);
  * the group axis: a pass of other count groups, keys or inclusion specs
    on the same buckets has the same scan key and compiles nothing, on
    every route; the rows come from the node table's memo;
  * what the reference refuses, that reference and generator import
    nothing of the program, and that the generator is the seed's function.
"""

from __future__ import annotations

import copy
import json
import math
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))

from generators import baseline_mixed, baseline_mixed_spread  # noqa: E402
from reference import spread_affinity_taints as ref  # noqa: E402
from reference.default_profile import Narrow32, NotCovered  # noqa: E402

from kube_scheduler_simulator_tpu.config.config import SimulatorConfiguration  # noqa: E402
from kube_scheduler_simulator_tpu.framework.replay import (  # noqa: E402
    _workload_scan_key, replay)
from kube_scheduler_simulator_tpu.scheduler.convert import parse_plugin_set  # noqa: E402
from kube_scheduler_simulator_tpu.server.di import DIContainer  # noqa: E402
from kube_scheduler_simulator_tpu.server.server import SimulatorServer  # noqa: E402
from kube_scheduler_simulator_tpu.state.compile import (  # noqa: E402
    ARG_STATICS, compile_workload)
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result  # noqa: E402
from kube_scheduler_simulator_tpu.utils.tracing import TRACER  # noqa: E402

replay_mod = sys.modules["kube_scheduler_simulator_tpu.framework.replay"]
CONFIG = json.loads((BENCH / "configs/baseline_c4_queue_5k.json").read_text())
PARAMS = CONFIG["parameters"]
PROFILE = PARAMS["scheduler_configuration"]
CFG = parse_plugin_set(PROFILE)
(K_STATUS, K_PREFILTER, K_FILTER, K_POSTFILTER, K_PRESCORE, K_SCORE,
 K_FINAL) = ref.KEYS[:7]
HOST, ZONE = "kubernetes.io/hostname", "topology.kubernetes.io/zone"
NAME = "PodTopologySpread"
NODES, ZONES, PODS, BURST = 40, 4, 60, 15
SEED = 2147483777


def _deployment(seed: int = SEED, initial: int = 0):
    params = copy.deepcopy(PARAMS)
    params["nodes"] = NODES
    params["node_shape"]["zones"] = ZONES
    params["initial_pods"]["count"] = initial
    return baseline_mixed_spread.generate(params, seed)


def _req(port, method, path, body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}", data=data,
                                 method=method)
    if data is not None:
        req.add_header("Content-Type", "application/json")
    deadline = time.time() + 300
    while True:
        try:
            with urllib.request.urlopen(req, timeout=300) as r:
                return r.status, json.loads(r.read() or b"null")
        except urllib.error.HTTPError as e:
            # the autopilot sheds workload POSTs after a pass over its 2 s
            # target (a first compile under the other workers' load): ask
            # again, as the API says
            if e.code != 429 or time.time() > deadline:
                raise
            time.sleep(0.25)


def _counter(name: str) -> float:
    return TRACER.summary()["counters"].get(name, 0)


def _labeled(name: str) -> dict:
    series = TRACER.snapshot()["labeled_counters"].get(name, [])
    out: dict = {}
    for s in series:
        key = next(v for k, v in s["labels"].items() if k != "session")
        out[key] = out.get(key, 0) + s["value"]
    return out


def _decided(pod: dict) -> bool:
    if pod["spec"].get("nodeName"):
        return True
    return any(c.get("type") == "PodScheduled" and c.get("reason") == "Unschedulable"
               for c in (pod.get("status") or {}).get("conditions") or [])


def _serve_in_bursts(dep, pods: list[dict]) -> tuple[list[dict], dict, dict]:
    """The pods created BURST at a time against a server under the posted
    profile (each burst's creates back to back, so that the loop's window
    takes them as passes of several pods, in the order they were created),
    the next burst once the last is decided; each pod read in full ->
    (pods as read, the profile as read back, what the engine counted)."""
    srv = SimulatorServer(DIContainer(SimulatorConfiguration(port=0)), port=0)
    srv.start(block=False)
    served = []
    try:
        path = "/api/v1/import?ignoreSchedulerConfiguration=true"
        assert _req(srv.port, "POST", path, {"nodes": dep.nodes})[0] == 200
        if dep.initial_pods:
            assert _req(srv.port, "POST", path,
                        {"pods": dep.initial_pods})[0] == 200
        assert _req(srv.port, "POST", "/api/v1/schedulerconfiguration",
                    PROFILE)[0] == 202
        _, read_back = _req(srv.port, "GET", "/api/v1/schedulerconfiguration")
        base = {name: _counter(name) for name in (
            "scheduling_waves_total", "scheduling_pass_pods_total")}
        for lo in range(0, len(pods), BURST):
            burst = pods[lo:lo + BURST]
            for pod in burst:
                assert _req(srv.port, "POST", "/api/v1/pods", pod)[0] == 201
            for pod in burst:
                ns, name = pod["metadata"]["namespace"], pod["metadata"]["name"]
                deadline = time.time() + 240
                while True:
                    _, got = _req(srv.port, "GET", f"/api/v1/pods/{ns}/{name}")
                    annos = got["metadata"].get("annotations") or {}
                    if _decided(got) and all(k in annos for k in ref.KEYS):
                        break
                    assert time.time() < deadline, f"{name} not decided"
                    time.sleep(0.02)
                served.append(got)
        counted = {name: _counter(name) - v for name, v in base.items()}
    finally:
        srv.shutdown()
    return served, read_back, counted


def _differing(got_of, dep, pods: list[dict], arith) -> int:
    oracle = ref.ReferenceScheduler(dep.nodes, dep.initial_pods, arith)
    differing = 0
    for i, pod in enumerate(pods):
        want, node = oracle.schedule_one(pod)
        annos, placed = got_of(i)
        differing += sum(annos.get(k) != want[k] for k in ref.KEYS)
        differing += placed != node
    return differing


# ---- the served path ------------------------------------------------------

@pytest.fixture(scope="module")
def served_run():
    dep = _deployment()
    pods = [dep.measured_pod() for _ in range(PODS)]
    served, read_back, counted = _serve_in_bursts(dep, pods)
    return dep, pods, served, read_back, counted


def _served_of(served):
    def got_of(i):
        return (served[i]["metadata"]["annotations"],
                served[i]["spec"].get("nodeName") or "")
    return got_of


def test_served_in_bursts_under_the_posted_profile_byte_for_byte(served_run):
    dep, pods, served, _, _ = served_run
    assert _differing(_served_of(served), dep, pods, ref.Exact) == 0


def test_the_control_differs(served_run):
    dep, pods, served, _, _ = served_run
    assert _differing(_served_of(served), dep, pods, Narrow32) > 0


def test_the_profile_took_and_the_passes_held_several_pods(served_run):
    _, _, _, read_back, counted = served_run
    lineup = read_back["profiles"][0]["plugins"]["multiPoint"]["enabled"]
    assert [(p["name"], p["weight"]) for p in lineup] == ref.PROFILE
    assert counted["scheduling_pass_pods_total"] == PODS
    assert counted["scheduling_waves_total"] < PODS      # passes of several


def test_what_the_comparison_covered_is_what_the_cell_is_for(served_run):
    """Pods with and without constraints, with and without the ssd term;
    a constrained pod's entry runs four Filter plugins and its score maps
    carry PodTopologySpread at weight 2."""
    dep, pods, served, _, _ = served_run
    seen = set()
    for pod, got in zip(pods, served):
        annos = got["metadata"]["annotations"]
        spread = "topologySpreadConstraints" in pod["spec"]
        picky = "affinity" in pod["spec"]
        seen.add((spread, picky))
        status = json.loads(annos[K_STATUS])
        assert status == {"NodeAffinity": "success" if picky else "",
                          "NodeResourcesFit": "success",
                          NAME: "success" if spread else ""}
        filt = json.loads(annos[K_FILTER])
        passed = [e for e in filt.values()
                  if all(v == "passed" for v in e.values())]
        assert passed and all((NAME in e) == spread for e in passed)
        scores = json.loads(annos[K_SCORE])
        if len(passed) > 1:
            assert json.loads(annos[K_PRESCORE])[NAME] == (
                "success" if spread else "")
            assert all((NAME in e) == spread for e in scores.values())
            if spread:
                finals = json.loads(annos[K_FINAL])
                assert all(int(e[NAME]) % 2 == 0 and 0 <= int(e[NAME]) <= 200
                           for e in finals.values())
        assert annos[K_PREFILTER] == annos[K_POSTFILTER] == "{}"
    assert len(seen) == 4, seen


# ---- one pass, and the rounds ---------------------------------------------

def _compiled(dep, pods, **kw):
    nodes = sorted(dep.nodes, key=lambda n: n["metadata"]["name"])
    return compile_workload(
        nodes, pods, CFG,
        bound_pods=[(p, p["spec"]["nodeName"]) for p in dep.initial_pods], **kw)


def _replayed_of(cw, rr):
    names = cw.node_table.names

    def got_of(i):
        sel = int(rr.selected[i])
        return decode_pod_result(rr, i), names[sel] if sel >= 0 else ""
    return got_of


ROUTES = {
    "sequential_scan": lambda cw: replay(cw, chunk=16),
    "packed_one_chunk": lambda cw: replay(cw, device_resident=True),
}


@pytest.mark.parametrize("route", list(ROUTES))
def test_one_pass_over_the_whole_queue(route):
    """The carry's per-node counts, advanced by the binds of the pass
    itself, on every route; 20 pods already on the cluster."""
    dep = _deployment(seed=3000000019, initial=20)
    pods = [dep.measured_pod() for _ in range(PODS)]
    cw = _compiled(dep, pods)
    rr = ROUTES[route](cw)
    assert _differing(_replayed_of(cw, rr), dep, pods, ref.Exact) == 0
    assert _differing(_replayed_of(cw, rr), dep, pods, Narrow32) > 0


# ---- counted by node -------------------------------------------------------

def _node(name: str, zone: str, disk: str, labels: dict | None = None) -> dict:
    return {"apiVersion": "v1", "kind": "Node",
            "metadata": {"name": name, "labels": dict(
                {HOST: name, ZONE: zone, "disktype": disk}, **labels or {})},
            "spec": {},
            "status": {"allocatable": {"cpu": "64000m", "memory": str(1 << 38),
                                       "ephemeral-storage": str(1 << 39),
                                       "pods": "110"},
                       "conditions": [{"type": "Ready", "status": "True"}]}}


def _pod(name: str, ssd: bool = False, zone_skew: int | None = None,
         host_skew: int | None = None, app: str = "app-0",
         soft_key: str = HOST) -> dict:
    pod = {"apiVersion": "v1", "kind": "Pod",
           "metadata": {"name": name, "namespace": "default",
                        "labels": {"app": app, "tier": "web"}},
           "spec": {"containers": [{
               "name": "main", "image": "registry.k8s.io/pause:3.9",
               "resources": {"requests": {"cpu": "100m",
                                          "memory": str(128 << 20)}}}]}}
    if ssd:
        pod["spec"]["affinity"] = {"nodeAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": {
                "nodeSelectorTerms": [{"matchExpressions": [
                    {"key": "disktype", "operator": "In", "values": ["ssd"]}]}]}}}
    constraints = []
    sel = {"matchLabels": {"app": app}}
    if zone_skew is not None:
        constraints.append({"maxSkew": zone_skew, "topologyKey": ZONE,
                            "whenUnsatisfiable": "DoNotSchedule",
                            "labelSelector": sel})
    if host_skew is not None:
        constraints.append({"maxSkew": host_skew, "topologyKey": soft_key,
                            "whenUnsatisfiable": "ScheduleAnyway",
                            "labelSelector": sel})
    if constraints:
        pod["spec"]["topologySpreadConstraints"] = constraints
    return pod


def _bound(name: str, node: str, **kw) -> dict:
    pod = _pod(name, **kw)
    pod["spec"]["nodeName"] = node
    return pod


def _scan_against_reference(nodes, pods, bound=()):
    """-> per pod the reference's annotations, each equal to the program's."""
    ordered = sorted(nodes, key=lambda nd: nd["metadata"]["name"])
    cw = compile_workload(ordered, pods, CFG, bound_pods=[
        (p, p["spec"]["nodeName"]) for p in bound])
    rr = replay(cw, chunk=8)
    oracle = ref.ReferenceScheduler(nodes, list(bound))
    wants = []
    for i, pod in enumerate(pods):
        want, node = oracle.schedule_one(pod)
        got = decode_pod_result(rr, i)
        sel = int(rr.selected[i])
        assert (cw.node_table.names[sel] if sel >= 0 else "") == node, i
        for k in ref.KEYS:
            assert got[k] == want[k], (i, k, got[k], want[k])
        wants.append(want)
    return wants


@pytest.mark.parametrize("ssd, a0", [(True, "passed"), (False, ref.ERR_SKEW)])
def test_a_zone_of_which_the_node_affinity_excludes_a_part(ssd, a0):
    """Zone a = {a0 ssd, a1 hdd}, zone b = {b0 ssd}; one matching pod sits
    on the hdd node a1.  For a pod with `disktype In [ssd]` upstream leaves
    a1 out of the COUNT as well as out of the minimum (nodeAffinityPolicy
    Honor): zone a counts 0 and a0 passes.  For a pod without the term a1
    counts: zone a holds 1, zone b 0, skew 1 + 1 - 0 > 1 refuses a0.  A
    program that folds the zone's count once for all pods says `refused`
    for both."""
    nodes = [_node("a0", "a", "ssd"), _node("a1", "a", "hdd"),
             _node("b0", "b", "ssd")]
    want, = _scan_against_reference(
        nodes, [_pod("new", ssd=ssd, zone_skew=1)],
        [_bound("old", "a1", zone_skew=1)])
    filt = json.loads(want[K_FILTER])
    assert filt["a0"][NAME] == a0
    assert filt["b0"][NAME] == "passed"
    if ssd:
        assert filt["a1"] == {
            "TaintToleration": "passed",
            "NodeAffinity": "node(s) didn't match Pod's node affinity/selector"}


def test_a_node_without_the_zone_key_is_refused_and_is_not_counted_on():
    """A node without the key refuses with the missing-label message, and
    a matching pod on it is counted in no zone."""
    nodes = [_node("a0", "a", "ssd"), _node("b0", "b", "ssd"),
             _node("x0", "a", "ssd")]
    del nodes[2]["metadata"]["labels"][ZONE]
    want, = _scan_against_reference(
        nodes, [_pod("new", zone_skew=1)], [_bound("old", "x0")])
    filt = json.loads(want[K_FILTER])
    assert filt["x0"][NAME] == ref.ERR_MISSING_LABEL
    assert filt["a0"][NAME] == filt["b0"][NAME] == "passed"


# ---- scored from the feasible set ------------------------------------------

def test_the_weight_follows_each_pods_feasible_set():
    """Six nodes, three ssd; n0 (ssd) holds two matching pods, n1 (ssd)
    one.  A pod without the ssd term is feasible on 6 nodes: the hostname
    constraint's weight is log(6 + 2) and n0 scores
    round(2 * log 8 + (3 - 1)) = 6.  The next pod carries the term, is
    feasible on 3: log(3 + 2), and n0, which holds the same two matching
    pods still (the first pod went elsewhere), scores
    round(2 * log 5 + 2) = 5.  The parent scored both round(2 * log(6 +
    2)) = 4."""
    nodes = [_node(f"n{i}", "ab"[i % 2], "ssd" if i < 3 else "hdd")
             for i in range(6)]
    bound = [_bound("o0", "n0"), _bound("o1", "n0"), _bound("o2", "n1")]
    wide, narrow = _scan_against_reference(
        nodes, [_pod("wide", host_skew=3), _pod("narrow", ssd=True, host_skew=3)],
        bound)
    on_node = {"n0": 2, "n1": 1}
    for want, feasible in ((wide, 6), (narrow, 3)):
        scores = json.loads(want[K_SCORE])
        assert len(scores) == feasible
        weight = math.log(feasible + 2)
        for node, entry in scores.items():
            # count * weight + (maxSkew - 1), rounded
            assert entry[NAME] == str(round(
                on_node.get(node, 0) * weight + 2)), (node, feasible)
        assert json.loads(want[K_PRESCORE])[NAME] == "success"
        # PreFilter Skips: no DoNotSchedule constraint
        assert json.loads(want[K_STATUS])[NAME] == ""
        # the pod joins its node's count for the next pod
        took = want[ref.KEYS[-1]]
        on_node[took] = on_node.get(took, 0) + 1
    assert json.loads(wide[K_SCORE])["n0"][NAME] == "6"
    assert json.loads(narrow[K_SCORE])["n0"][NAME] == "5"
    # fewest matching pods first: 100 * (max + min - s) / max, weight 2
    finals = json.loads(narrow[K_FINAL])
    low = min(int(e[NAME]) for e in json.loads(narrow[K_SCORE]).values())
    assert finals["n0"][NAME] == str(2 * (100 * (5 + low - 5) // 5))


def test_a_node_without_the_scored_key_is_ignored():
    """`ScheduleAnyway` over a key that is not the hostname: racks r0 =
    {n0, n1}, r1 = {n2}; n3 carries no rack label.  A feasible node that
    lacks the scored key scores 0 and is left out of minimum, maximum and
    the weight's size; the weight is log(distinct racks among the scored
    nodes + 2) and a rack's count is folded over its nodes."""
    rack = "example.com/rack"
    nodes = [_node(f"n{i}", "a", "ssd", {rack: r})
             for i, r in enumerate(("r0", "r0", "r1"))]
    nodes.append(_node("n3", "a", "ssd"))
    want, = _scan_against_reference(
        nodes, [_pod("new", host_skew=2, soft_key=rack)],
        [_bound("o0", "n0"), _bound("o1", "n1"), _bound("o2", "n3")])
    scores, finals = json.loads(want[K_SCORE]), json.loads(want[K_FINAL])
    assert scores["n3"][NAME] == "0" and finals["n3"][NAME] == "0"
    weight = math.log(2 + 2)
    assert scores["n0"][NAME] == scores["n1"][NAME] == str(round(2 * weight + 1))
    assert scores["n2"][NAME] == "1"
    assert finals["n2"][NAME] == "200"


# ---- the group axis --------------------------------------------------------

def _executables() -> dict:
    return {key: fn._cache_size()
            for key, fn in replay_mod._SCAN_CACHE._entries.items()
            if hasattr(fn, "_cache_size")}


def _three(apps: tuple[str, str, str], ssd: bool) -> list[dict]:
    return [_pod(f"p-{apps[0]}", ssd=ssd, zone_skew=5, host_skew=3, app=apps[0]),
            _pod(f"p-{apps[1]}", zone_skew=5, host_skew=3, app=apps[1]),
            _pod(f"p-{apps[2]}", ssd=not ssd, app=apps[2])]


@pytest.mark.parametrize("route", list(ROUTES))
def test_a_pass_of_other_groups_has_the_same_key_and_compiles_nothing(route):
    """Two passes of three pods on one node table whose count groups
    (other apps: other selectors), constrained pods and inclusion specs
    differ: PodTopologySpread's statics are arguments on padded axes, so
    the scan-cache key is the same, the second pass builds no executable,
    and each is the reference's byte for byte."""
    assert NAME in ARG_STATICS
    dep = _deployment(seed=7)
    nodes = sorted(dep.nodes, key=lambda n: n["metadata"]["name"])
    first = compile_workload(nodes, _three(("app-1", "app-2", "app-3"), True),
                             CFG)
    ROUTES[route](first)
    before, rebuckets = _executables(), _labeled("spread_axis_rebuckets_total")
    second = compile_workload(nodes, _three(("app-7", "app-8", "app-7"), False),
                              CFG, reuse=first)
    assert second.node_table is first.node_table
    assert second.host["_statics_fp"] == first.host["_statics_fp"]
    assert _workload_scan_key(second, 4) == _workload_scan_key(first, 4)
    a, b = (cw.arg_statics()[NAME] for cw in (first, second))
    assert [x.shape for x in a] == [x.shape for x in b]
    assert int(a.group_key.shape[0]) == 8       # twice the bucket of 3 pods
    rr = ROUTES[route](second)
    assert _executables() == before
    assert _labeled("spread_axis_rebuckets_total") == rebuckets
    oracle = ref.ReferenceScheduler(dep.nodes, [])
    for i, pod in enumerate(second.pods):
        want, _ = oracle.schedule_one(pod)
        got = decode_pod_result(rr, i)
        assert all(got[k] == want[k] for k in ref.KEYS), i


def test_the_rows_come_from_the_node_tables_memo():
    """A topology key's domain row and an inclusion spec's row are built
    the first time a node table meets them, and never again; what the
    policies leave out is counted per constrained pod."""
    dep = _deployment(seed=11)
    nodes = sorted(dep.nodes, key=lambda n: n["metadata"]["name"])
    hdd = sum(n["metadata"]["labels"]["disktype"] == "hdd" for n in nodes)
    built0, out0 = (_labeled("spread_rows_built_total"),
                    _counter("spread_excluded_nodes_total"))
    first = compile_workload(nodes, _three(("app-1", "app-2", "app-3"), True),
                             CFG)
    built1, out1 = (_labeled("spread_rows_built_total"),
                    _counter("spread_excluded_nodes_total"))
    assert built1.get("dom_idx", 0) - built0.get("dom_idx", 0) == 2
    assert built1.get("eligible", 0) - built0.get("eligible", 0) == 1
    # one pod carries both the ssd term and constraints; the pod with the
    # term alone and the pod with constraints alone leave nothing out
    assert out1 - out0 == hdd
    compile_workload(nodes, _three(("app-4", "app-5", "app-6"), True), CFG,
                     reuse=first)
    assert _labeled("spread_rows_built_total") == built1
    assert _counter("spread_excluded_nodes_total") - out1 == hdd
    st = first.arg_statics()[NAME]
    assert st.dom_idx.shape == (2, NODES) and st.dom_iota.shape == (8,)
    assert sorted(map(bool, st.is_hostname)) == [False, True]
    # hostnames: one node a domain; the zones are folded
    assert [bool(i) for h, i in zip(st.is_hostname, st.is_ident)
            if bool(h)] == [True]
    assert [bool(i) for h, i in zip(st.is_hostname, st.is_ident)
            if not bool(h)] == [False]
    assert first.init_carry[NAME].shape == (8, NODES)


@pytest.mark.parametrize("name", [
    "spread_axis_rebuckets_total", "spread_rows_built_total",
    "spread_excluded_nodes_total"])
def test_the_counters_have_their_lines(name):
    from kube_scheduler_simulator_tpu.utils import tracing

    assert name in tracing._HELP
    docs = (BENCH.parent / "docs" / "metrics.md").read_text()
    assert f"`{name}" in docs


# ---- the reference by itself ------------------------------------------------

def _constrained(**constraint) -> dict:
    pod = _pod("x", zone_skew=1)
    c, = pod["spec"]["topologySpreadConstraints"]
    c.update(constraint)
    for k in [k for k, v in c.items() if v is None]:
        del c[k]
    return pod


@pytest.mark.parametrize("case, pod", [
    ("matchLabelKeys", _constrained(matchLabelKeys=["app"])),
    ("minDomains", _constrained(minDomains=2)),
    ("nodeAffinityPolicy", _constrained(nodeAffinityPolicy="Ignore")),
    ("nodeTaintsPolicy", _constrained(nodeTaintsPolicy="Honor")),
    ("matchExpressions", _constrained(labelSelector={"matchExpressions": [
        {"key": "app", "operator": "Exists"}]})),
    ("no labelSelector", _constrained(labelSelector=None)),
    ("five constraints", dict(_pod("x"), spec=dict(
        _pod("x")["spec"], topologySpreadConstraints=(
            _pod("x", zone_skew=1)["spec"]["topologySpreadConstraints"] * 5)))),
    ("a nodeSelector", dict(_pod("x"), spec=dict(
        _pod("x")["spec"], nodeSelector={"disktype": "ssd"}))),
])
def test_what_the_reference_refuses(case, pod):
    oracle = ref.ReferenceScheduler([_node("n0", "a", "ssd")], [])
    oracle.schedule_one(_pod("fine", ssd=True, zone_skew=5, host_skew=3))
    with pytest.raises(NotCovered):
        oracle.schedule_one(pod)


def test_reference_and_generator_import_nothing_of_the_program():
    for path in (BENCH / "reference/spread_affinity_taints.py",
                 BENCH / "generators/baseline_mixed_spread.py"):
        imported = {w for line in path.read_text().splitlines()
                    if line.startswith(("import ", "from "))
                    for w in line.replace(".", " ").split()}
        assert not imported & {"kube_scheduler_simulator_tpu", "numpy", "jax"}, path


def test_the_generator_is_the_seed_s_function_and_draws_the_sources_shares():
    a, b, c = _deployment(SEED, 10), _deployment(SEED, 10), _deployment(SEED + 1, 10)
    assert a.nodes == b.nodes and a.initial_pods == b.initial_pods
    pods = [a.measured_pod() for _ in range(1000)]
    assert pods[:20] == [b.measured_pod() for _ in range(20)]
    assert a.nodes != c.nodes
    assert a.nodes == b.nodes and a.initial_pods == b.initial_pods
    # the nodes are baseline_mixed's own: the constraint draw moves none
    params = copy.deepcopy(PARAMS)
    params.update(nodes=NODES)
    params["node_shape"]["zones"] = ZONES
    assert baseline_mixed.generate(params, SEED).nodes == a.nodes
    spread = [p for p in pods if "topologySpreadConstraints" in p["spec"]]
    assert 0.55 < len(spread) / len(pods) < 0.65
    both = [p for p in spread if "affinity" in p["spec"]]
    assert 0.25 < len(both) / len(pods) < 0.35
    for p in spread:
        zone, host = p["spec"]["topologySpreadConstraints"]
        app = p["metadata"]["labels"]["app"]
        assert zone == {"maxSkew": 5, "topologyKey": ZONE,
                        "whenUnsatisfiable": "DoNotSchedule",
                        "labelSelector": {"matchLabels": {"app": app}}}
        assert host == {"maxSkew": 3, "topologyKey": HOST,
                        "whenUnsatisfiable": "ScheduleAnyway",
                        "labelSelector": {"matchLabels": {"app": app}}}
