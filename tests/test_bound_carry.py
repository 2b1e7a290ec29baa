"""The bound pods' rows carried from pass to pass (state/boundcarry.py):
a carry brought up to date by the store's deltas gives compile_workload
the same bytes as a build from scratch on the same store, per plugin
family; what it cannot follow is rebuilt and counted; a steady pass builds
as many rows as the last pass bound, however many are bound; and an
envelope-shaped cluster served over HTTP reads back the sequential
oracle's annotations."""

from __future__ import annotations

import json
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from kube_scheduler_simulator_tpu.cluster.store import ObjectStore, list_shared
from kube_scheduler_simulator_tpu.config.config import SimulatorConfiguration
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.reference_impl.sequential import SequentialScheduler
from kube_scheduler_simulator_tpu.server.di import DIContainer
from kube_scheduler_simulator_tpu.server.server import SimulatorServer
from kube_scheduler_simulator_tpu.state.boundcarry import BoundCarry, BoundFeed
from kube_scheduler_simulator_tpu.state.compile import (
    NodeTableReuse, compile_workload)
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

ZONES = ("z0", "z1", "z2")


def _node(j):
    return {"apiVersion": "v1", "kind": "Node",
            "metadata": {"name": f"n{j:03d}", "labels": {
                "kubernetes.io/hostname": f"n{j:03d}",
                "topology.kubernetes.io/zone": ZONES[j % 3]}},
            "spec": {},
            "status": {"allocatable": {"cpu": "16", "memory": "64Gi", "pods": "110"},
                       "capacity": {"cpu": "16", "memory": "64Gi", "pods": "110"}}}


def _plain(rng, name):
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": "default",
                         "labels": {"app": f"a{int(rng.integers(3))}"}},
            "spec": {"containers": [{"name": "c", "resources": {"requests": {
                "cpu": f"{int(rng.integers(1, 5)) * 100}m", "memory": "64Mi"}}}]}}


def _term(rng):
    return {"topologyKey": ("topology.kubernetes.io/zone",
                            "kubernetes.io/hostname")[int(rng.integers(2))],
            "labelSelector": {"matchLabels": {"app": f"a{int(rng.integers(3))}"}}}


def _with_terms(rng, name):
    pod = _plain(rng, name)
    aff = {}
    if rng.random() < 0.7:
        aff["podAffinity"] = {
            "requiredDuringSchedulingIgnoredDuringExecution": [_term(rng)],
            "preferredDuringSchedulingIgnoredDuringExecution": [
                {"weight": int(rng.choice([1, 50])), "podAffinityTerm": _term(rng)}]}
    if rng.random() < 0.5:
        anti = dict(_term(rng), topologyKey="kubernetes.io/hostname")
        if rng.random() < 0.5:
            # resolved against the namespace manifests of the pass
            anti["namespaceSelector"] = {"matchLabels": {"team": "blue"}}
        aff["podAntiAffinity"] = {
            "requiredDuringSchedulingIgnoredDuringExecution": [anti]}
    if aff:
        pod["spec"]["affinity"] = aff
    return pod


def _with_ports(rng, name):
    pod = _plain(rng, name)
    if rng.random() < 0.7:
        port = {"containerPort": 80, "hostPort": 8000 + int(rng.integers(4))}
        if rng.random() < 0.4:
            port["hostIP"] = f"10.0.0.{int(rng.integers(2))}"
        pod["spec"]["containers"][0]["ports"] = [port]
    return pod


def _with_volumes(rng, name):
    pod = _plain(rng, name)
    vols = []
    if rng.random() < 0.6:
        vols.append({"name": "data", "persistentVolumeClaim": {
            "claimName": f"claim{int(rng.integers(6))}"}})
    if rng.random() < 0.3:
        vols.append({"name": "disk", "gcePersistentDisk": {
            "pdName": f"pd{int(rng.integers(3))}", "readOnly": bool(rng.random() < 0.5)}})
    if vols:
        pod["spec"]["volumes"] = vols
    return pod


def _with_spread(rng, name):
    pod = _plain(rng, name)
    if rng.random() < 0.7:
        pod["spec"]["topologySpreadConstraints"] = [{
            "maxSkew": 1, "topologyKey": "topology.kubernetes.io/zone",
            "whenUnsatisfiable": ("DoNotSchedule", "ScheduleAnyway")[int(rng.integers(2))],
            "labelSelector": {"matchLabels": {"app": f"a{int(rng.integers(3))}"}}}]
    return pod


def _volume_objects():
    sc = {"apiVersion": "storage.k8s.io/v1", "kind": "StorageClass",
          "metadata": {"name": "local"}, "provisioner": "kubernetes.io/no-provisioner",
          "volumeBindingMode": "WaitForFirstConsumer"}
    pvs, pvcs = [], []
    for i in range(6):
        pvs.append({"apiVersion": "v1", "kind": "PersistentVolume",
                    "metadata": {"name": f"pv{i}"},
                    "spec": {"capacity": {"storage": f"{i + 1}Gi"},
                             "accessModes": ["ReadWriteOnce"],
                             "storageClassName": "local"}})
        pvcs.append({"apiVersion": "v1", "kind": "PersistentVolumeClaim",
                     "metadata": {"name": f"claim{i}", "namespace": "default"},
                     "spec": {"accessModes": ["ReadWriteOncePod" if i == 0
                                              else "ReadWriteOnce"],
                              "storageClassName": "local",
                              "resources": {"requests": {"storage": "1Gi"}}}})
    return sc, pvs, pvcs


FAMILIES = {"plain": _plain, "affinity_terms": _with_terms, "host_ports": _with_ports,
            "pvc_volumes": _with_volumes, "spread": _with_spread}


def _rebuilds():
    return {k: v for k, v in TRACER.labeled_totals(
        "bound_carry_rebuilds_total", "reason").items() if k != "uncarried"}


def _leaves(cw):
    out = []
    for part in ("xs", "statics", "init_carry"):
        tree = getattr(cw, part)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        out.append((part, str(treedef), [
            (np.asarray(x).dtype.str, np.asarray(x).shape, np.asarray(x).tobytes())
            for x in leaves]))
    return out


@pytest.mark.parametrize("family", list(FAMILIES))
def test_carried_build_equals_a_build_from_scratch(family):
    make = FAMILIES[family]
    rng = np.random.default_rng(sum(map(ord, family)))
    store = ObjectStore()
    for j in range(12):
        store.create("nodes", _node(j))
    store.create("namespaces", {"apiVersion": "v1", "kind": "Namespace",
                                "metadata": {"name": "default"}})
    sc, pvs, pvcs = _volume_objects()
    store.create("storageclasses", sc)
    for pv in pvs:
        store.create("persistentvolumes", pv)
    for pvc in pvcs:
        store.create("persistentvolumeclaims", pvc)
    serial = iter(range(10 ** 6))

    def bind_one():
        pod = make(rng, f"b{next(serial):04d}")
        pod["spec"]["nodeName"] = (f"n{int(rng.integers(12)):03d}"
                                   if rng.random() < 0.95 else "no-such-node")
        store.create("pods", pod)

    def bound_names():
        return [p["metadata"]["name"] for p in list_shared(store, "pods")
                if p["spec"].get("nodeName")]

    for _ in range(25):
        bind_one()
    cfg = PluginSetConfig()
    carry = BoundCarry(BoundFeed(store))
    reuse = None
    want_rebuilds = {}
    for step in range(9):
        what = None
        if step:
            for _ in range(int(rng.integers(0, 4))):
                bind_one()
            names = bound_names()
            for name in rng.choice(names, size=min(2, len(names)), replace=False):
                store.delete("pods", str(name), "default")
            names = bound_names()
            # a label change and a bare resourceVersion change of bound pods
            pod = store.get("pods", str(rng.choice(names)), "default")
            pod["metadata"]["labels"] = {"app": f"a{int(rng.integers(3))}"}
            store.update("pods", pod)
            pod = store.get("pods", str(rng.choice(names)), "default")
            pod["metadata"].setdefault("annotations", {})["touched"] = str(step)
            store.update("pods", pod)
        if step == 4:
            store.create("nodes", _node(12))
            what = "nodes"
        if step == 6:
            ns = store.get("namespaces", "default")
            ns["metadata"]["labels"] = {"team": "blue"}
            store.update("namespaces", ns)
            if family == "affinity_terms":
                what = "namespaces"
        if step == 7:
            # a resource name no bound pod requested before
            pod = make(rng, f"b{next(serial):04d}")
            pod["spec"]["containers"][0]["resources"]["requests"]["example.com/gpu"] = "1"
            pod["spec"]["nodeName"] = "n001"
            store.create("pods", pod)
            what = "schema"
        if step == 0:
            what = "first"
        if what:
            want_rebuilds[what] = want_rebuilds.get(what, 0) + 1
        before = _rebuilds()

        nodes = list_shared(store, "nodes")
        queue = [make(rng, f"q{step}-{i}") for i in range(3)]
        volumes = {"pvcs": list_shared(store, "persistentvolumeclaims"),
                   "pvs": list_shared(store, "persistentvolumes"),
                   "storageclasses": list_shared(store, "storageclasses")}
        namespaces = list_shared(store, "namespaces")
        carried = compile_workload(nodes, queue, cfg, bound_carry=carry,
                                   volumes=volumes, reuse=reuse,
                                   namespaces=namespaces)
        reuse = NodeTableReuse(carried)
        got = {k: v - before.get(k, 0) for k, v in _rebuilds().items()
               if v - before.get(k, 0)}
        assert got == ({what: 1} if what else {}), (step, got)

        bound = [(p, p["spec"]["nodeName"]) for p in list_shared(store, "pods")
                 if p["spec"].get("nodeName")]
        assert carry.n == len(bound)
        scratch = compile_workload(nodes, queue, cfg, bound_pods=bound,
                                   volumes=volumes, namespaces=namespaces)
        for (part, tree_a, leaves_a), (_, tree_b, leaves_b) in zip(
                _leaves(carried), _leaves(scratch)):
            assert tree_a == tree_b, (step, part)
            for i, (a, b) in enumerate(zip(leaves_a, leaves_b)):
                assert a == b, (step, part, i, a[:2], b[:2])
    assert family != "affinity_terms" or "namespaces" in want_rebuilds
    carry.close()


@pytest.mark.parametrize("n_bound", [300, 3000])
def test_a_steady_pass_builds_the_rows_the_last_pass_bound(n_bound):
    rng = np.random.default_rng(n_bound)
    store = ObjectStore()
    for j in range(40):
        store.create("nodes", _node(j))
    for i in range(n_bound):
        pod = _plain(rng, f"b{i:05d}")
        pod["spec"]["containers"][0]["resources"]["requests"] = {"cpu": "10m"}
        pod["spec"]["nodeName"] = f"n{i % 40:03d}"
        store.create("pods", pod)
    engine = SchedulerEngine(store, chunk=16)
    built = []
    for step, arrivals in enumerate((3, 2, 4, 1)):
        for i in range(arrivals):
            store.create("pods", _plain(rng, f"q{step}-{i}"))
        before = TRACER.counter_totals()
        assert engine.schedule_pending() == arrivals
        after = TRACER.counter_totals()
        built.append(int(after["bound_rows_built_total"]
                         - before.get("bound_rows_built_total", 0)))
        carried = int(after.get("bound_rows_carried_total", 0)
                      - before.get("bound_rows_carried_total", 0))
        assert built[-1] + carried == n_bound + sum((3, 2, 4, 1)[:step])
    engine.close()
    # the first pass builds every row; each later one, what the last bound
    assert built == [n_bound, 3, 2, 4]


def _req(port, method, path, body=None):
    r = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method=method,
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json"})
    deadline = time.time() + 60
    while True:
        try:
            with urllib.request.urlopen(r, timeout=60) as resp:
                raw = resp.read()
                return resp.status, json.loads(raw) if raw else None
        except urllib.error.HTTPError as e:
            # shed by the autopilot after a slow first pass: retry, as the
            # API asks (a loaded machine under six workers)
            if e.code != 429 or time.time() > deadline:
                raise
            time.sleep(0.25)


def test_envelope_shaped_cluster_served_over_http_equals_the_oracle():
    rng = np.random.default_rng(26)
    nodes = [_node(j) for j in range(50)]
    perm = rng.permutation(50)
    bound = []
    for i in range(50 * 30 - 10):           # 40 nodes hold 30 pods, 10 hold 29
        pod = _plain(rng, f"b{i:05d}")
        pod["spec"]["nodeName"] = nodes[perm[i % 50]]["metadata"]["name"]
        bound.append(pod)
    queue = [_plain(rng, f"q{i}") for i in range(4)]
    srv = SimulatorServer(DIContainer(SimulatorConfiguration(port=0)), port=0)
    srv.start(block=False)
    rebuilds0, totals0 = _rebuilds(), TRACER.counter_totals()
    try:
        path = "/api/v1/import?ignoreSchedulerConfiguration=true"
        assert _req(srv.port, "POST", path, {"nodes": nodes})[0] == 200
        assert _req(srv.port, "POST", path, {"pods": bound})[0] == 200
        served = []
        for pod in queue:
            assert _req(srv.port, "POST", "/api/v1/pods", pod)[0] == 201
            deadline = time.time() + 120
            while True:
                _, got = _req(srv.port, "GET",
                              f"/api/v1/pods/default/{pod['metadata']['name']}")
                annos = got["metadata"].get("annotations") or {}
                if got["spec"].get("nodeName") and len(annos) >= 13:
                    break
                assert time.time() < deadline, "not decided"
                time.sleep(0.05)
            served.append(got)
        _, metrics = _req(srv.port, "GET", "/api/v1/metrics")
    finally:
        srv.shutdown()
    oracle = SequentialScheduler(
        nodes, queue, PluginSetConfig(),
        bound_pods=[(p, p["spec"]["nodeName"]) for p in bound])
    for got, (want, selected) in zip(served, oracle.schedule_all()):
        assert len(want) == 13
        assert got["spec"]["nodeName"] == oracle.names[selected]
        for key, value in want.items():
            assert got["metadata"]["annotations"][key] == value, key
    # the carry's counters, on /api/v1/metrics and as this server moved them:
    # one full build (the session's first pass), then one row a pass
    assert "bound_carry_rebuilds_total" in metrics["labeled_counters"]
    assert metrics["gauges"]["bound_pods"] == len(bound) + len(queue) - 1
    assert {k: v - rebuilds0.get(k, 0) for k, v in _rebuilds().items()
            if v - rebuilds0.get(k, 0)} == {"first": 1}
    totals = TRACER.counter_totals()
    built = totals["bound_rows_built_total"] - totals0.get("bound_rows_built_total", 0)
    assert len(bound) <= built <= len(bound) + 2 * len(queue)
    assert metrics["counters"]["bound_rows_carried_total"] >= 3 * len(bound)
