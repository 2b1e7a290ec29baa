"""The program against benchmark/reference/affinity_taints.py, the plain
reference for BASELINE config 3's four-plugin profile on a cluster whose
nodes AND pods differ (PR 46): `baseline_c3_1k` at 40 nodes, 30 bound pods
and 60 measured pods.

  * served one pod at a time over HTTP under the POSTED profile (a pass
    of one pod: the sequential scan's one call and the streaming commit,
    row 9 of docs/wave-pipeline.md's table): all 13 annotations +
    spec.nodeName byte for byte, among them a pod that tolerates the dedicated pool and one
    that does not, each against a tainted node's entry, and a
    PreferNoSchedule taint's score; the same reference in int32/float32
    (the control) differs;
  * the posted profile read back, names and weights;
  * one pass over the whole queue against the same reference;
  * that the reference and the generator import nothing of the program,
    and that the generator is the seed's function.
"""

from __future__ import annotations

import copy
import json
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))

from generators import baseline_mixed  # noqa: E402
from reference import affinity_taints as ref  # noqa: E402
from reference.default_profile import Narrow32  # noqa: E402

from kube_scheduler_simulator_tpu.config.config import SimulatorConfiguration  # noqa: E402
from kube_scheduler_simulator_tpu.framework.replay import replay  # noqa: E402
from kube_scheduler_simulator_tpu.scheduler.convert import parse_plugin_set  # noqa: E402
from kube_scheduler_simulator_tpu.server.di import DIContainer  # noqa: E402
from kube_scheduler_simulator_tpu.server.server import SimulatorServer  # noqa: E402
from kube_scheduler_simulator_tpu.state.compile import compile_workload  # noqa: E402
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result  # noqa: E402
from kube_scheduler_simulator_tpu.utils.tracing import TRACER  # noqa: E402

CONFIG = json.loads((BENCH / "configs/baseline_c3_1k.json").read_text())
PARAMS = CONFIG["parameters"]
PROFILE = PARAMS["scheduler_configuration"]
(K_STATUS, K_PREFILTER, K_FILTER, K_POSTFILTER, K_PRESCORE, K_SCORE,
 K_FINAL) = ref.KEYS[:7]
NODES, INITIAL, PODS = 40, 30, 60
SEED = 2147483777
TAINT_MSG = ref.untolerated_taint_message("dedicated", "batch")


def _deployment(seed: int = SEED):
    params = copy.deepcopy(PARAMS)
    params["nodes"] = NODES
    params["initial_pods"]["count"] = INITIAL
    return baseline_mixed.generate(params, seed)


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


def _serve(dep, pods: list[dict]) -> tuple[list[dict], dict, dict]:
    """The pods created one at a time against a server under the posted
    profile, each read in full -> (pods as read, the profile as read back,
    what the engine counted)."""
    srv = SimulatorServer(DIContainer(SimulatorConfiguration(port=0)), port=0)
    srv.start(block=False)
    served = []
    try:
        path = "/api/v1/import?ignoreSchedulerConfiguration=true"
        assert _req(srv.port, "POST", path, {"nodes": dep.nodes})[0] == 200
        assert _req(srv.port, "POST", path, {"pods": dep.initial_pods})[0] == 200
        assert _req(srv.port, "POST", "/api/v1/schedulerconfiguration",
                    PROFILE)[0] == 202
        _, read_back = _req(srv.port, "GET", "/api/v1/schedulerconfiguration")
        base = {name: _counter(name) for name in (
            "commit_stream_waves_total", "scheduling_waves_total")}
        for pod in pods:
            ns, name = pod["metadata"]["namespace"], pod["metadata"]["name"]
            assert _req(srv.port, "POST", "/api/v1/pods", pod)[0] == 201
            deadline = time.time() + 120
            while True:
                _, got = _req(srv.port, "GET", f"/api/v1/pods/{ns}/{name}")
                annos = got["metadata"].get("annotations") or {}
                if got["spec"].get("nodeName") and all(k in annos for k in ref.KEYS):
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


def test_served_under_the_posted_profile_byte_for_byte():
    dep = _deployment()
    pods = [dep.measured_pod() for _ in range(PODS)]
    served, read_back, counted = _serve(dep, pods)

    def got_of(i):
        return (served[i]["metadata"]["annotations"],
                served[i]["spec"].get("nodeName") or "")

    assert _differing(got_of, dep, pods, ref.Exact) == 0
    assert _differing(got_of, dep, pods, Narrow32) > 0      # the control
    # the profile took, and it is row 9 that served: a streamed commit a
    # pass, whatever the pass held
    lineup = read_back["profiles"][0]["plugins"]["multiPoint"]["enabled"]
    assert [(p["name"], p["weight"]) for p in lineup] == [
        ("TaintToleration", 3), ("NodeAffinity", 2), ("NodeResourcesFit", 1),
        ("NodeResourcesBalancedAllocation", 1)]
    passes = counted["scheduling_waves_total"]
    assert PODS // 2 < passes <= PODS
    assert counted["commit_stream_waves_total"] == passes

    # what the comparison covered is what the cell is for
    pool = {n["metadata"]["name"] for n in dep.nodes if any(
        t["effect"] == "NoSchedule" for t in n["spec"].get("taints") or [])}
    soft = {n["metadata"]["name"] for n in dep.nodes if any(
        t["effect"] == "PreferNoSchedule" for t in n["spec"].get("taints") or [])}
    assert pool and soft
    seen = set()
    for pod, got in zip(pods, served):
        annos = got["metadata"]["annotations"]
        filt = json.loads(annos[K_FILTER])
        tolerant = "tolerations" in pod["spec"]
        picky = "affinity" in pod["spec"]
        seen.add((picky, tolerant))
        for name in pool:   # a tainted node: tolerated, or refused there
            if tolerant:
                assert filt[name]["TaintToleration"] == "passed"
            else:
                assert filt[name] == {"TaintToleration": TAINT_MSG}
        assert all(("NodeAffinity" in e) == picky
                   for nm, e in filt.items() if tolerant or nm not in pool)
        finals = json.loads(annos[K_FINAL])
        for name in set(finals) & soft:   # PreferNoSchedule, reversed
            assert json.loads(annos[K_SCORE])[name]["TaintToleration"] == "1"
            assert finals[name]["TaintToleration"] == "0"
        assert json.loads(annos[K_STATUS]) == {
            "NodeAffinity": "success" if picky else "",
            "NodeResourcesFit": "success"}
        assert annos[K_PREFILTER] == annos[K_POSTFILTER] == "{}"
    assert len(seen) == 4, seen
    assert any(got["spec"]["nodeName"] in pool for got in served), \
        "no tolerating pod landed on the dedicated pool"


def test_one_pass_over_the_whole_queue():
    dep = _deployment(seed=3000000019)
    pods = [dep.measured_pod() for _ in range(PODS)]
    nodes = sorted(dep.nodes, key=lambda n: n["metadata"]["name"])
    cw = compile_workload(
        nodes, pods, parse_plugin_set(PROFILE),
        bound_pods=[(p, p["spec"]["nodeName"]) for p in dep.initial_pods])
    rr = replay(cw, chunk=16)
    names = cw.node_table.names

    def got_of(i):
        sel = int(rr.selected[i])
        return decode_pod_result(rr, i), names[sel] if sel >= 0 else ""

    assert _differing(got_of, dep, pods, ref.Exact) == 0
    assert _differing(got_of, dep, pods, Narrow32) > 0


def test_reference_and_generator_import_nothing_of_the_program():
    for path in (BENCH / "reference/affinity_taints.py",
                 BENCH / "generators/baseline_mixed.py",
                 BENCH / "drivers/closed_loop_profile.py"):
        imported = {w for line in path.read_text().splitlines()
                    if line.startswith(("import ", "from "))
                    for w in line.replace(".", " ").split()}
        assert not imported & {"kube_scheduler_simulator_tpu", "numpy", "jax"}, path


def test_the_generator_is_the_seed_s_function():
    a, b, c = _deployment(SEED), _deployment(SEED), _deployment(SEED + 1)
    assert a.nodes == b.nodes and a.initial_pods == b.initial_pods
    assert [a.measured_pod() for _ in range(20)] == [
        b.measured_pod() for _ in range(20)]
    assert a.nodes != c.nodes
    # more measured pods never move a node or an initial pod
    for _ in range(50):
        a.measured_pod()
    assert a.nodes == b.nodes and a.initial_pods == b.initial_pods
    # every initial pod sits where the profile's filters accept it
    by_name = {n["metadata"]["name"]: n for n in a.nodes}
    for pod in a.initial_pods:
        node = by_name[pod["spec"]["nodeName"]]
        if "affinity" in pod["spec"]:
            assert node["metadata"]["labels"]["disktype"] == "ssd"
        if any(t["effect"] == "NoSchedule"
               for t in node["spec"].get("taints") or []):
            assert pod["spec"].get("tolerations")
