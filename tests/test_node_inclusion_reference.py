"""The program against benchmark/reference/node_inclusion.py, the plain
reference for a cluster whose nodes differ (PR 43): clusters shaped like
`sched_perf_nodeinclusion_5k` (four plain nodes to one tainted
`foo:NoSchedule`, a hostname a node, every pod spread one a hostname with
`nodeTaintsPolicy: Honor`) at 40 + 10 nodes.

  * served one pod at a time over HTTP through the first round and into
    the second: all 13 annotations + spec.nodeName byte for byte, ten
    entries that end at TaintToleration beside k that end at
    PodTopologySpread beside entries that pass in one filter-result; the
    41st pod starts the second round; the same reference in int32/float32
    (the control) differs;
  * the same pods with `nodeTaintsPolicy` left at its default, Ignore: the
    tainted hostnames stay in the minimum at 0, the 41st pod is refused
    by all 50 nodes and left Unschedulable, in reference and program alike;
  * what the served path counted on the way, by plugin;
  * one scan over the whole queue: the device carry's bind decides the
    next pod's skew check;
  * the missing-label message, and a zone-sized domain of which a part is
    excluded, where upstream counts by node: the reference and, since
    PR 52, the program (it folded counts by domain until then);
  * what the reference refuses (NotCovered), that it imports nothing of
    the program, and that the generator is the seed's function.
"""

from __future__ import annotations

import copy
import json
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))

from generators import scheduler_perf, scheduler_perf_node_pools  # noqa: E402
from reference import node_inclusion as ref  # noqa: E402
from reference.default_profile import Narrow32, NotCovered  # noqa: E402

from kube_scheduler_simulator_tpu.config.config import SimulatorConfiguration  # noqa: E402
from kube_scheduler_simulator_tpu.framework.replay import replay  # noqa: E402
from kube_scheduler_simulator_tpu.server.di import DIContainer  # noqa: E402
from kube_scheduler_simulator_tpu.server.server import SimulatorServer  # noqa: E402
from kube_scheduler_simulator_tpu.state.compile import compile_workload  # noqa: E402
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result  # noqa: E402
from kube_scheduler_simulator_tpu.utils.tracing import TRACER  # noqa: E402

CONFIG = json.loads(
    (BENCH / "configs/sched_perf_nodeinclusion_5k.json").read_text())
PARAMS = CONFIG["parameters"]
HOST, ZONE = "kubernetes.io/hostname", "topology.kubernetes.io/zone"
(K_STATUS, K_PREFILTER, K_FILTER, K_POSTFILTER, K_PRESCORE, K_SCORE,
 K_FINAL) = ref.KEYS[:7]
K_SELECTED = ref.KEYS[-1]
TAINT_MSG = "node(s) had untolerated taint {foo: }"
PLAIN, TAINTED = 40, 10
SEED = 2147483777


def _deployment(seed: int = SEED, nodes: int = PLAIN + TAINTED,
                policy: str | None = "Honor"):
    """The configuration at `nodes` nodes; `policy` None leaves
    nodeTaintsPolicy out of the constraint (the API's default, Ignore)."""
    params = copy.deepcopy(PARAMS)
    params["nodes"] = nodes
    if policy is None:
        for group in ("initial_pods", "measured_pods"):
            c, = params[group]["template"]["spec"]["topologySpreadConstraints"]
            del c["nodeTaintsPolicy"]
    return scheduler_perf_node_pools.generate(params, seed)


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


def _decided(pod: dict) -> bool:
    if pod["spec"].get("nodeName"):
        return True
    return any(c.get("type") == "PodScheduled" and c.get("reason") == "Unschedulable"
               for c in (pod.get("status") or {}).get("conditions") or [])


def _rejects() -> dict[str, float]:
    return TRACER.labeled_totals("plugin_filter_rejects_total", "plugin")


def _serve(dep, pods: list[dict]) -> tuple[list[dict], list[dict]]:
    """The pods created one at a time against a stock server, each read in
    full -> (pods as read, per pod the growth of the refusals by plugin)."""
    srv = SimulatorServer(DIContainer(SimulatorConfiguration(port=0)), port=0)
    srv.start(block=False)
    served, growth = [], []
    try:
        path = "/api/v1/import?ignoreSchedulerConfiguration=true"
        assert _req(srv.port, "POST", path, {"namespaces": dep.namespaces,
                                             "nodes": dep.nodes})[0] == 200
        for pod in pods:
            before = _rejects()
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
            # the attribution is recorded under wave_finish, after the pod
            # is readable: give it a moment to catch up (every pod here is
            # refused by the tainted nodes at least)
            while True:
                after = _rejects()
                if after != before or time.time() > deadline:
                    break
                time.sleep(0.01)
            served.append(got)
            growth.append({k: after.get(k, 0) - before.get(k, 0)
                           for k in after if after.get(k, 0) != before.get(k, 0)})
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


def _ends_at(filter_result: str) -> dict[str, set[str]]:
    """filter-result -> {message or "passed": the nodes whose entry ends so}."""
    out: dict[str, set[str]] = {}
    for node, entry in json.loads(filter_result).items():
        # the encoder sorts an entry's keys: the refusal, where there is
        # one, is the one value that is not "passed"
        refusal = [v for v in entry.values() if v != "passed"]
        assert len(refusal) <= 1, entry
        out.setdefault(refusal[0] if refusal else "passed", set()).add(node)
    return out


# ---- the served path -----------------------------------------------------

@pytest.fixture(scope="module")
def honor_run():
    dep = _deployment()
    pods = [dep.measured_pod() for _ in range(PLAIN + 5)]
    served, growth = _serve(dep, pods)
    return dep, pods, served, growth


def test_served_byte_for_byte_through_the_first_round_and_into_the_second(honor_run):
    dep, pods, served, _ = honor_run
    assert _differing(served, dep, pods, ref.Exact) == 0


def test_the_control_differs(honor_run):
    dep, pods, served, _ = honor_run
    assert _differing(served, dep, pods, Narrow32) > 0, "the control passed"


def test_two_refusal_families_in_one_filter_result(honor_run):
    dep, _, served, _ = honor_run
    tainted = {n["metadata"]["name"] for n in dep.nodes
               if n["spec"].get("taints")}
    assert len(tainted) == TAINTED
    taken: set[str] = set()
    for k, got in enumerate(served[:PLAIN]):
        annos = got["metadata"]["annotations"]
        ends = _ends_at(annos[K_FILTER])
        assert ends[TAINT_MSG] == tainted
        assert ends.get(ref.ERR_SKEW, set()) == taken and len(taken) == k
        assert len(ends["passed"]) == PLAIN - k
        # a tainted node's entry ends at the 3rd plugin, a taken one's at
        # PodTopologySpread; the score maps hold the feasible nodes only
        filt = json.loads(annos[K_FILTER])
        assert list(filt[min(tainted)]) == [
            "NodeName", "NodeUnschedulable", "TaintToleration"]
        if taken:
            assert filt[min(taken)]["PodTopologySpread"] == ref.ERR_SKEW
            assert filt[min(taken)]["NodeResourcesFit"] == "passed"
        if k < PLAIN - 1:  # one feasible node is selected without scoring
            assert set(json.loads(annos[K_SCORE])) == ends["passed"]
        assert json.loads(annos[K_STATUS])["PodTopologySpread"] == "success"
        assert annos[K_PREFILTER] == "{}" and annos[K_POSTFILTER] == "{}"
        taken.add(got["spec"]["nodeName"])
    assert not taken & tainted and len(taken) == PLAIN


def test_the_41st_pod_starts_the_second_round(honor_run):
    dep, _, served, _ = honor_run
    first_round = {p["spec"]["nodeName"] for p in served[:PLAIN]}
    for k, got in enumerate(served[PLAIN:]):
        ends = _ends_at(got["metadata"]["annotations"][K_FILTER])
        # the minimum over the 40 eligible hostnames is 1 now: a hostname
        # refuses only once it holds two
        assert len(ends.get(ref.ERR_SKEW, set())) == k
        assert len(ends[TAINT_MSG]) == TAINTED
        assert got["spec"]["nodeName"] in first_round
    second = [p["spec"]["nodeName"] for p in served[PLAIN:]]
    assert len(set(second)) == len(second) == 5


def test_refusals_counted_by_plugin_on_the_served_path(honor_run):
    _, _, _, growth = honor_run
    for k, g in enumerate(growth):
        want = {"TaintToleration": TAINTED}
        if k % PLAIN:
            want["PodTopologySpread"] = k % PLAIN
        assert g == want, (k, g)


def test_policy_ignore_leaves_the_41st_pod_unschedulable():
    dep = _deployment(policy=None)
    pods = [dep.measured_pod() for _ in range(PLAIN + 1)]
    served, _ = _serve(dep, pods)
    assert _differing(served, dep, pods, ref.Exact) == 0
    assert all(p["spec"].get("nodeName") for p in served[:PLAIN])
    last = served[-1]
    annos = last["metadata"]["annotations"]
    assert not last["spec"].get("nodeName") and annos[K_SELECTED] == ""
    ends = _ends_at(annos[K_FILTER])
    assert {m: len(v) for m, v in ends.items()} == {
        TAINT_MSG: TAINTED, ref.ERR_SKEW: PLAIN}
    assert json.loads(annos[K_POSTFILTER]) == {
        nd["metadata"]["name"]: {} for nd in dep.nodes}
    assert annos[K_SCORE] == "{}"
    # the reference alone says the same of the same pods under Honor: bound
    honor = _deployment()
    oracle = ref.ReferenceScheduler(honor.nodes, honor.initial_pods)
    nodes = [oracle.schedule_one(honor.measured_pod(), annotate=False)[1]
             for _ in range(PLAIN + 1)]
    assert all(nodes) and nodes[-1] in nodes[:PLAIN]


# ---- one scan, and the cases the deployment does not hold ----------------

def _replayed(nodes: list[dict], pods: list[dict], bound: list[dict]):
    # the store lists nodes by name: that is the index order of the tie-break
    ordered = sorted(nodes, key=lambda nd: nd["metadata"]["name"])
    cw = compile_workload(ordered, pods, None, namespaces=[],
                          bound_pods=[(p, p["spec"]["nodeName"]) for p in bound])
    return cw, replay(cw, chunk=8)


def _assert_scan_equals_reference(nodes, pods, bound=()):
    cw, rr = _replayed(nodes, pods, list(bound))
    oracle = ref.ReferenceScheduler(nodes, list(bound))
    for i, pod in enumerate(pods):
        want, node = oracle.schedule_one(pod)
        got = decode_pod_result(rr, i)
        sel = int(rr.selected[i])
        assert (cw.node_table.names[sel] if sel >= 0 else "") == node, i
        for k in ref.KEYS:
            assert got[k] == want[k], (i, k)
    return rr


def test_one_scan_over_the_queue_equals_the_reference():
    """All pods in one pass: the carry's bind_update, not the next pass's
    host build, takes the hostname away from the next pod, and lifts the
    minimum when the round is full."""
    dep = _deployment(seed=47, nodes=25)
    rr = _assert_scan_equals_reference(
        dep.nodes, [dep.measured_pod() for _ in range(23)])
    assert [int(x) for x in rr.feasible_count] == (
        list(range(20, 0, -1)) + [20, 19, 18])


def test_initial_pods_are_counted_where_they_sit():
    """The rehearsal's shape: bound pods of the measured kind on plain
    nodes take their hostnames out of the first round."""
    params = dict(copy.deepcopy(PARAMS), nodes=25)
    params["initial_pods"]["count"] = 8
    dep = scheduler_perf_node_pools.generate(params, 5)
    assert len({p["spec"]["nodeName"] for p in dep.initial_pods}) == 8
    rr = _assert_scan_equals_reference(
        dep.nodes, [dep.measured_pod() for _ in range(14)], dep.initial_pods)
    assert [int(x) for x in rr.feasible_count[:3]] == [12, 11, 10]


def _node(name: str, labels: dict, tainted: bool = False) -> dict:
    nd = copy.deepcopy(PARAMS["node_pools"][1 if tainted else 0]["template"])
    nd["metadata"] = {"name": name, "labels": labels}
    return nd


def _pod(name: str, key: str = HOST, **constraint) -> dict:
    pod = copy.deepcopy(PARAMS["measured_pods"]["template"])
    pod["metadata"] = {"name": name, "namespace": "default",
                       "labels": {"foo": ""}}
    c, = pod["spec"]["topologySpreadConstraints"]
    c["topologyKey"] = key
    c.update(constraint)
    for k in [k for k, v in c.items() if v is None]:
        del c[k]
    return pod


def test_a_node_without_the_key_refuses_with_the_missing_label_message():
    nodes = [_node("n0", {HOST: "n0", ZONE: "a"}), _node("n1", {HOST: "n1"}),
             _node("n2", {HOST: "n2", ZONE: "b"}, tainted=True)]
    pods = [_pod(f"p{i}", key=ZONE) for i in range(3)]
    _assert_scan_equals_reference(nodes, pods)
    oracle = ref.ReferenceScheduler(nodes, [])
    want, node = oracle.schedule_one(pods[0])
    filt = json.loads(want[K_FILTER])
    assert filt["n1"]["PodTopologySpread"] == ref.ERR_MISSING_LABEL
    assert filt["n2"] == {"NodeName": "passed", "NodeUnschedulable": "passed",
                          "TaintToleration": TAINT_MSG}
    assert node == "n0"  # one feasible node: selected without scoring
    assert want[K_SCORE] == "{}" and want[K_PRESCORE] == "{}"


def test_upstream_counts_by_node_in_a_partly_excluded_domain():
    """Zone a = {a0 plain, a1 tainted}, zone b = {b0 plain}; one matching
    pod already on the TAINTED a1.  Under Honor upstream leaves a1 out of
    the count as well as out of the minimum (calPreFilterState walks nodes,
    not domains): zone a counts 0, the pod may go to a0.  Under Ignore a1
    counts: zone a holds 1, zone b 0, and a0 is refused for the skew.  The
    program keeps its counts by node and folds them per pod and slot over
    the nodes the slot counts on (plugins/topologyspread.py _fold), so it
    says the same in both cases; until PR 52 it folded by domain once for
    all pods and failed the Honor case by construction."""
    nodes = [_node("a0", {HOST: "a0", ZONE: "a"}),
             _node("a1", {HOST: "a1", ZONE: "a"}, tainted=True),
             _node("b0", {HOST: "b0", ZONE: "b"})]
    bound = _pod("old", key=ZONE)
    bound["spec"]["nodeName"] = "a1"

    def filter_of(policy):
        oracle = ref.ReferenceScheduler(nodes, [bound])
        want, node = oracle.schedule_one(_pod("new", key=ZONE,
                                              nodeTaintsPolicy=policy))
        return {n: m for m, ns in _ends_at(want[K_FILTER]).items()
                for n in ns}, node

    honor, node = filter_of("Honor")
    assert honor == {"a0": "passed", "a1": TAINT_MSG, "b0": "passed"}
    assert node == "a0"  # equal scores, the lowest index
    ignore, node = filter_of(None)
    assert ignore == {"a0": ref.ERR_SKEW, "a1": TAINT_MSG, "b0": "passed"}
    assert node == "b0"
    # the program, on both
    for policy in ("Honor", None):
        _assert_scan_equals_reference(
            nodes, [_pod("new", key=ZONE, nodeTaintsPolicy=policy)], [bound])
    # the same cluster spread over hostnames
    by_host = _pod("old")
    by_host["spec"]["nodeName"] = "a1"
    _assert_scan_equals_reference(
        nodes, [_pod(f"h{i}") for i in range(3)], [by_host])


# ---- the reference by itself ---------------------------------------------

def _with(pod: dict, **spec) -> dict:
    return dict(pod, spec=dict(pod["spec"], **spec))


@pytest.mark.parametrize("case, make", [
    ("a toleration on the pod", lambda p: _with(p, tolerations=[
        {"key": "foo", "operator": "Exists", "effect": "NoSchedule"}])),
    ("ScheduleAnyway", lambda p: _pod("x", whenUnsatisfiable="ScheduleAnyway")),
    ("two constraints", lambda p: _with(p, topologySpreadConstraints=(
        p["spec"]["topologySpreadConstraints"] * 2))),
    ("matchExpressions", lambda p: _pod("x", labelSelector={
        "matchExpressions": [{"key": "foo", "operator": "Exists"}]})),
    ("matchLabelKeys", lambda p: _pod("x", matchLabelKeys=["foo"])),
    ("minDomains", lambda p: _pod("x", minDomains=3)),
    ("nodeAffinityPolicy Ignore", lambda p: _pod("x", nodeAffinityPolicy="Ignore")),
    ("no labelSelector", lambda p: _pod("x", labelSelector=None)),
    ("a nodeSelector", lambda p: _with(p, nodeSelector={"a": "b"})),
])
def test_what_the_reference_refuses(case, make):
    dep = _deployment(nodes=5)
    oracle = ref.ReferenceScheduler(dep.nodes, [])
    oracle.schedule_one(_pod("fine"))
    with pytest.raises(NotCovered):
        oracle.schedule_one(make(_pod("x")))


def test_the_reference_refuses_a_prefer_no_schedule_taint():
    node = _node("n0", {HOST: "n0"}, tainted=True)
    node["spec"]["taints"][0]["effect"] = "PreferNoSchedule"
    with pytest.raises(NotCovered):
        ref.ReferenceScheduler([node], [])


def test_the_reference_and_the_generator_import_nothing_of_the_program():
    code = ("import sys; sys.path.insert(0, %r); "
            "import reference.node_inclusion, generators.scheduler_perf_node_pools; "
            "bad = [m for m in sys.modules if m.startswith(('kube_scheduler', 'jax', 'numpy'))]; "
            "assert not bad, bad" % str(BENCH))
    subprocess.run([sys.executable, "-c", code], check=True)


def test_the_control_alone_refuses_every_node():
    dep = _deployment()
    want, node = ref.ReferenceScheduler(dep.nodes, [], Narrow32).schedule_one(
        dep.measured_pod())
    ends = _ends_at(want[K_FILTER])
    assert node == "" and {m: len(v) for m, v in ends.items()} == {
        TAINT_MSG: TAINTED, "Insufficient memory": PLAIN}


# ---- the generator -------------------------------------------------------

def test_the_generator_is_the_seeds_function_and_keeps_the_ratio():
    a, b = _deployment(seed=7), _deployment(seed=7)
    assert a.nodes == b.nodes and a.measured_pod() == b.measured_pod()
    assert _deployment(seed=8).nodes != a.nodes
    for nodes, want in ((50, (40, 10)), (600, (480, 120)), (7, (5, 2)),
                        (5000, (4000, 1000))):
        assert tuple(scheduler_perf_node_pools.pool_counts(
            PARAMS["node_pools"], nodes)) == want
    names = [n["metadata"]["name"] for n in a.nodes]
    assert len(set(names)) == 50
    assert all(n["metadata"]["labels"][HOST] == n["metadata"]["name"]
               for n in a.nodes)
    # the pools do not interleave in the store's (sorted) order
    ordered = sorted(a.nodes, key=lambda n: n["metadata"]["name"])
    assert [bool(n["spec"].get("taints")) for n in ordered] == (
        [False] * PLAIN + [True] * TAINTED)
    assert a.initial_pods == [] and a.measured_namespace == "default"


def test_the_plain_pool_draws_basic_5ks_first_names():
    basic = json.loads(
        (BENCH / "configs/sched_perf_basic_5k.json").read_text())["parameters"]
    seed = 3000000019
    want = scheduler_perf.generate(dict(basic, nodes=PLAIN, initial_pods=dict(
        basic["initial_pods"], count=0)), seed)
    got = _deployment(seed=seed)
    assert ([n["metadata"]["name"] for n in got.nodes[:PLAIN]]
            == [n["metadata"]["name"] for n in want.nodes])
    assert all(n["metadata"]["name"].startswith("taint-node-")
               for n in got.nodes[PLAIN:])


def test_the_configuration_states_its_cut_and_its_source():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry, = [c for c in bench["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == CONFIG["reduced"] == ["measurePods"]
    assert entry["source"] == CONFIG["source"]
    assert "SchedulingWithNodeInclusionPolicy" in entry["source"]
    assert CONFIG["architecture"] is None
    assert [p["count"] for p in PARAMS["node_pools"]] == [4000, 1000]
    assert PARAMS["nodes"] == 5000 and PARAMS["initial_pods"]["count"] == 0
    cell, = [w for w in bench["workloads"] if w["config"] == CONFIG["name"]]
    assert cell == dict(cell, name="nodeinclusion_5k.interactive",
                        traffic="interactive", chips=1)
