"""The program against benchmark/reference/csi_volumes.py, the plain
reference of the volume family for bound claims on CSI PVs (PR 36):
clusters shaped like `sched_perf_csipvs_5k` at tens of nodes, served over
HTTP the way benchmark/drivers/closed_loop_volumes.py drives them — the
CSINodes and the initial pods' PVs and claims imported after the bound
pods, then for every measured pod its PV, its claim and the pod.

  * every measured pod's 13 annotations + spec.nodeName, byte for byte,
    over 9 cycles, WITH THE CSINODE COUNT SET TO 1: every node that holds
    a pod refuses the next one with "node(s) exceed max volume count", so
    the rendering of a NodeVolumeLimits refusal is held too; the same
    reference in int32/float32 (the control) differs;
  * at the source's count of 39 no node refuses and every node's entry
    carries NodeVolumeLimits and VolumeBinding "passed";
  * what the served path did on the way: one pass a pod, every manifest
    parsed and every bound row walked once a pass, one scan compiled for
    all of them (a PV created between two passes is no new executable);
  * the reference by itself: what it refuses (NotCovered), and that it
    imports nothing of the program.
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

from generators.scheduler_perf_volumes import generate  # noqa: E402
from reference import csi_volumes as ref  # noqa: E402
from reference.default_profile import Narrow32, NotCovered  # noqa: E402

from kube_scheduler_simulator_tpu.config.config import SimulatorConfiguration  # noqa: E402
from kube_scheduler_simulator_tpu.server.di import DIContainer  # noqa: E402
from kube_scheduler_simulator_tpu.server.server import SimulatorServer  # noqa: E402
from kube_scheduler_simulator_tpu.utils.tracing import TRACER  # noqa: E402

PARAMS = json.loads(
    (BENCH / "configs/sched_perf_csipvs_5k.json").read_text())["parameters"]
K_STATUS, K_FILTER, K_SCORE = ref.KEYS[0], ref.KEYS[2], ref.KEYS[5]
IMPORT = "/api/v1/import?ignoreSchedulerConfiguration=true"


def _deployment(nodes: int, seed: int, initial: int, count: int = 39):
    params = copy.deepcopy(PARAMS)
    params.update(nodes=nodes)
    params["initial_pods"]["count"] = initial
    params["volumes"]["csinode"]["count"] = count
    return generate(params, seed)


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


def _counts() -> dict:
    out = TRACER.counter_totals()
    for name, label in (("scan_compile_cache_total", "result"),
                        ("volume_manifests_parsed_total", "kind"),
                        ("volume_axis_rebuckets_total", "axis")):
        for value, n in TRACER.labeled_totals(name, label).items():
            out[f"{name}:{value}"] = n
    return out


def _serve(dep, cycles: int):
    """-> (measured pods as read, their manifests, the counters' growth)."""
    srv = SimulatorServer(DIContainer(SimulatorConfiguration(port=0)), port=0)
    srv.start(block=False)
    vols = dep.nodes.volumes
    measured, pods = [], []
    try:
        p = srv.port
        assert _req(p, "POST", IMPORT, {"namespaces": dep.namespaces,
                                        "nodes": dep.nodes})[0] == 200
        assert _req(p, "POST", IMPORT, {"pods": dep.initial_pods})[0] == 200
        assert _req(p, "GET", "/api/v1/csinodes")[1]["items"] == []
        assert _req(p, "POST", IMPORT, {
            "pvcs": [pvc for _, pvc in vols.initial],
            "pvs": [pv for pv, _ in vols.initial],
            "csinodes": vols.csinodes})[0] == 200
        assert len(_req(p, "GET", "/api/v1/csinodes")[1]["items"]) == len(dep.nodes)
        before = _counts()
        for _ in range(cycles):
            pod = dep.measured_pod()
            pv, pvc = vols.of(pod["metadata"]["name"])
            assert _req(p, "POST", "/api/v1/persistentvolumes", pv)[0] == 201
            assert _req(p, "POST", "/api/v1/persistentvolumeclaims", pvc)[0] == 201
            assert _req(p, "POST", "/api/v1/pods", pod)[0] == 201
            pods.append(pod)
            measured.append(_read_decided(
                p, pod["metadata"]["namespace"], pod["metadata"]["name"]))
        after = _counts()
    finally:
        srv.shutdown()
    return measured, pods, {k: after[k] - before.get(k, 0) for k in after}


def _differing(measured, dep, pods, arith) -> int:
    oracle = ref.ReferenceScheduler(dep.nodes, dep.initial_pods, arith)
    differing = 0
    for got, pod in zip(measured, pods):
        want, node = oracle.schedule_one(pod)
        differing += (got["spec"].get("nodeName") or "") != node
        differing += sum(got["metadata"]["annotations"][k] != want[k]
                         for k in ref.KEYS)
    return differing


@pytest.mark.parametrize("seed", [36, 2147483777])
def test_served_with_a_limit_of_one_equals_the_reference(seed):
    n, initial, cycles = 40, 28, 9
    dep = _deployment(n, seed, initial, count=1)
    measured, pods, growth = _serve(dep, cycles)
    assert _differing(measured, dep, pods, ref.Exact) == 0
    assert _differing(measured, dep, pods, Narrow32) > 0, "the control passed"
    holding = {p["spec"]["nodeName"] for p in dep.initial_pods}
    for k, got in enumerate(measured):
        annos = got["metadata"]["annotations"]
        filt = json.loads(annos[K_FILTER])
        assert len(filt) == n
        refused = {nm for nm, e in filt.items()
                   if e.get("NodeVolumeLimits") == ref.ERR_MAX_VOLUME_COUNT}
        # every node with a volume attached refuses, with the message, and
        # its entry ends there; the others go on to VolumeBinding
        assert refused == holding and len(refused) == initial + k
        for nm, e in filt.items():
            assert e["NodeResourcesFit"] == "passed"
            if nm in refused:
                assert "VolumeBinding" not in e
            else:
                assert e["NodeVolumeLimits"] == e["VolumeBinding"] == "passed"
        assert set(json.loads(annos[K_SCORE])) == set(filt) - refused
        status = json.loads(annos[K_STATUS])
        assert status["NodeVolumeLimits"] == status["VolumeBinding"] == "success"
        assert status["VolumeRestrictions"] == status["VolumeZone"] == ""
        assert got["spec"]["nodeName"] not in holding
        holding.add(got["spec"]["nodeName"])
    _assert_one_pass_a_pod(growth, cycles, initial, n)


def test_served_at_the_source_s_limit_refuses_no_node():
    n, initial, cycles = 30, 30, 8
    dep = _deployment(n, 7, initial)
    measured, pods, growth = _serve(dep, cycles)
    assert _differing(measured, dep, pods, ref.Exact) == 0
    for got in measured:
        filt = json.loads(got["metadata"]["annotations"][K_FILTER])
        assert len(filt) == n
        assert all(e["NodeVolumeLimits"] == e["VolumeBinding"] == "passed"
                   for e in filt.values())
    _assert_one_pass_a_pod(growth, cycles, initial, n)


def _assert_one_pass_a_pod(growth, cycles, initial, n):
    assert growth["scheduling_work_passes_total"] == cycles
    assert growth["scheduling_pass_pods_total"] == cycles
    # the session's first pass parses every manifest and resolves every
    # bound pod's row (the volume carry's resync); each later one its own
    # PV and claim and the row of the pod the pass before bound
    assert growth["volume_manifests_parsed_total:pv"] == initial + cycles
    assert growth["volume_manifests_parsed_total:pvc"] == initial + cycles
    assert growth["volume_manifests_parsed_total:csinode"] == n
    assert growth["volume_bound_rows_walked_total"] == initial + cycles - 1
    # one executable for all of them: the PVs are arguments, their axis padded
    # (none where an earlier test of this process left the same scan)
    assert growth["scan_compile_cache_total:miss"] <= 1
    assert (growth["scan_compile_cache_total:miss"]
            + growth["scan_compile_cache_total:hit"]) == cycles
    assert growth["volume_axis_rebuckets_total:pv"] == 0
    assert growth["volume_axis_rebuckets_total:csi"] == 0
    assert growth["volume_static_args_bytes_total"] > 0


# ---- the reference by itself ---------------------------------------------

def _tiny(seed: int = 5, count: int = 39):
    dep = _deployment(6, seed, 3, count)
    return dep, ref.ReferenceScheduler(dep.nodes, dep.initial_pods)


def test_reference_imports_nothing_of_the_program():
    for name in ("csi_volumes", "default_profile", "antiaffinity"):
        src = (BENCH / "reference" / f"{name}.py").read_text()
        assert "kube_scheduler_simulator_tpu" not in src.replace(
            "kube-scheduler-simulator", "")
        assert "import jax" not in src and "import numpy" not in src
    src = (BENCH / "generators/scheduler_perf_volumes.py").read_text()
    assert "kube_scheduler_simulator_tpu" not in src


def test_reference_creates_the_pod_s_volumes_before_the_pod():
    dep, sched = _tiny()
    assert len(sched.pvs) == len(sched.pvcs) == 3
    pod = dep.measured_pod()
    anns, node = sched.schedule_one(pod)
    assert len(sched.pvs) == len(sched.pvcs) == 4
    pv, _ = dep.nodes.volumes.of(pod["metadata"]["name"])
    j = sched.names.index(node)
    assert f"ebs.csi.aws.com/{pv['metadata']['name']}" in sched.attached[j]
    assert json.loads(anns[K_FILTER])[node]["VolumeBinding"] == "passed"


def test_reference_counts_a_shared_volume_once():
    """Two pods that mount one claim on one node attach one volume."""
    dep, sched = _tiny(count=1)
    first = dep.measured_pod()
    _, node = sched.schedule_one(first)
    second = dep.measured_pod()
    second["spec"]["volumes"] = copy.deepcopy(first["spec"]["volumes"])
    anns, _ = sched.schedule_one(second)
    filt = json.loads(anns[K_FILTER])
    # second's own claim is still created, but it mounts the first's: on
    # the first's node nothing new is attached, so the limit of 1 holds
    assert filt[node]["NodeVolumeLimits"] == "passed"
    assert sum(e.get("NodeVolumeLimits") == ref.ERR_MAX_VOLUME_COUNT
               for e in filt.values()) == 3


def test_reference_a_node_without_a_csinode_has_no_limit():
    dep = _deployment(6, 5, 3, count=1)
    dep.nodes.volumes.csinodes.pop()
    sched = ref.ReferenceScheduler(dep.nodes, dep.initial_pods)
    assert sum(not lim for lim in sched.limits) == 1


@pytest.mark.parametrize("breakage", [
    "rwop", "unbound", "no_annotation", "inline", "affinity", "zone",
    "no_csi", "missing_claim", "pod_affinity"])
def test_not_covered(breakage):
    dep, sched = _tiny()
    pod = dep.measured_pod()
    pv, pvc = dep.nodes.volumes.of(pod["metadata"]["name"])
    if breakage == "rwop":
        pvc["spec"]["accessModes"] = ["ReadWriteOncePod"]
    elif breakage == "unbound":
        pvc["spec"].pop("volumeName")
    elif breakage == "no_annotation":
        pvc["metadata"]["annotations"] = {}
    elif breakage == "inline":
        pod["spec"]["volumes"].append(
            {"name": "d", "awsElasticBlockStore": {"volumeID": "v"}})
    elif breakage == "affinity":
        pv["spec"]["nodeAffinity"] = {"required": {"nodeSelectorTerms": []}}
    elif breakage == "zone":
        pv["metadata"]["labels"] = {"topology.kubernetes.io/zone": "a"}
    elif breakage == "no_csi":
        pv["spec"].pop("csi")
    elif breakage == "missing_claim":
        pod["spec"]["volumes"][0]["persistentVolumeClaim"]["claimName"] = "x"
    elif breakage == "pod_affinity":
        pod["spec"]["affinity"] = {"podAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "labelSelector": {"matchLabels": {"a": "b"}},
                "topologyKey": "kubernetes.io/hostname"}]}}
    with pytest.raises(NotCovered):
        sched.schedule_one(pod)
