"""Lazy annotation materialization (store/lazy.py): byte parity with
eager mode across decoder rungs and wave shapes, exactly-once chunk
decode under concurrent cold reads, and the flight-recorder taps.

The parity rule (docs/wave-pipeline.md lazy-decode stage): whatever a
reader observes — pod annotations, result-history, bind order, parked
gangs — must be bit-identical between the default lazy mode, eager
decode (the degradation ladder's bottom rung, pinned on the engines
built here by `residency_floor=2`), and lazy over the pure-Python
decoder rung (KSS_TPU_DISABLE_NATIVE=1), including pods nobody reads
until after a later wave has overwritten their result-store entry.
"""

from __future__ import annotations

import copy
import json
import queue as queue_mod
import threading

import pytest

from kube_scheduler_simulator_tpu.cluster.store import ObjectStore, list_shared
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.models.workloads import (
    make_gang_workload, make_nodes, make_pods)
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.store import annotations as ann
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

ENABLED = ["NodeResourcesFit", "NodeResourcesBalancedAllocation",
           "NodeAffinity", "TaintToleration", "VolumeBinding"]


def _mode(monkeypatch, mode: str) -> int:
    """-> the rung to pin on the mode's engines (residency_floor)."""
    monkeypatch.delenv("KSS_TPU_DISABLE_NATIVE", raising=False)
    if mode == "lazy_python":
        monkeypatch.setenv("KSS_TPU_DISABLE_NATIVE", "1")
    else:
        assert mode in ("lazy", "eager")
    return 2 if mode == "eager" else 0


def _mixed_workload():
    """Plain + affinity/toleration pods, taints, host score columns AND
    two prefilter-rejected pods (missing PVC) mid-queue — the shapes the
    chunk decode special-cases (tests/test_chunk_decode.py recipe)."""
    nodes = make_nodes(18, seed=3, taint_fraction=0.3)
    pods = make_pods(50, seed=4, with_affinity=True, with_tolerations=True)
    for j, at in enumerate((7, 33)):
        pods.insert(at, {
            "metadata": {"name": f"pvc-pod-{j}", "namespace": "default"},
            "spec": {
                "containers": [{"name": "c", "resources": {
                    "requests": {"cpu": "100m"}}}],
                "volumes": [{"name": "v", "persistentVolumeClaim": {
                    "claimName": f"missing-{j}"}}],
            },
        })
    for i, p in enumerate(pods):
        p["spec"]["priority"] = (i % 3) * 100
    return nodes, pods


def _run_wave(nodes, pods, pipeline=True, chunk=16, rung=0):
    """Schedule once on the given rung; -> (engine, store, bound,
    bind_order)."""
    store = ObjectStore()
    for n in nodes:
        store.create("nodes", copy.deepcopy(n))
    for p in pods:
        store.create("pods", copy.deepcopy(p))
    q = store.watch("pods")
    engine = SchedulerEngine(store, plugin_config=PluginSetConfig(
        enabled=list(ENABLED)), chunk=chunk, pipeline_commit=pipeline,
        residency_floor=rung)
    bound = engine.schedule_pending()
    bind_order, seen = [], set()
    while True:
        try:
            _rv, event_type, obj = q.get_nowait()
        except queue_mod.Empty:
            break
        name = obj["metadata"]["name"]
        if (event_type == "MODIFIED"
                and (obj.get("spec") or {}).get("nodeName")
                and name not in seen):
            seen.add(name)
            bind_order.append(name)
    store.unwatch("pods", q)
    return engine, store, bound, bind_order


def _read_all(store) -> dict[str, dict]:
    return {p["metadata"]["name"]: p["metadata"].get("annotations") or {}
            for p in store.list("pods")[0]}


def _assert_same(anns_a: dict, anns_b: dict, what: str) -> None:
    assert anns_a.keys() == anns_b.keys()
    for name in anns_a:
        for key in set(anns_a[name]) | set(anns_b[name]):
            assert anns_a[name].get(key) == anns_b[name].get(key), (
                f"pod {name} key {key} diverged ({what})")


@pytest.mark.parametrize("pipeline", [True, False])
def test_lazy_eager_parity_mixed_wave(monkeypatch, pipeline):
    """Lazy (native), lazy (pure-Python rung) and eager runs of the
    same mixed wave — prefilter rejects included — are byte-identical
    in annotations, result-history, bind count and bind order, on both
    the streaming-commit and sequential post-pass paths."""
    nodes, pods = _mixed_workload()
    results = {}
    for mode in ("lazy", "eager", "lazy_python"):
        engine, store, bound, order = _run_wave(
            nodes, pods, pipeline=pipeline, rung=_mode(monkeypatch, mode))
        if mode.startswith("lazy"):
            # deferral really happened: shared reads see no annotations
            assert not any((p["metadata"].get("annotations") or {})
                           for p in list_shared(store, "pods"))
            reg = engine.reflector._lazy
            assert reg is not None and reg.pending_count() == len(pods)
        results[mode] = (bound, order, _read_all(store))
        if mode.startswith("lazy"):
            assert engine.reflector._lazy.pending_count() == 0
    b0, o0, a0 = results["eager"]
    for mode in ("lazy", "lazy_python"):
        b, o, a = results[mode]
        assert b == b0 and o == o0
        _assert_same(a, a0, f"{mode} vs eager")
    # the rejected pods took the early-out in every mode
    for j in range(2):
        assert a0[f"pvc-pod-{j}"][ann.FILTER_RESULT] == "{}"


def test_lazy_gang_wave_parity(monkeypatch):
    """Gang waves defer too: an admitted gang, a below-quorum (parked)
    gang and plain pods produce identical annotations (permit-result /
    permit-result-timeout included), bind order and parked set between
    lazy and eager runs of the streaming gang-atomic commit."""
    from kube_scheduler_simulator_tpu.framework.gang import POD_GROUP_LABEL
    from kube_scheduler_simulator_tpu.plugins.coscheduling import (
        Coscheduling, ensure_podgroup_resource)

    nodes = make_nodes(14, seed=21, taint_fraction=0.2)
    pgs, gpods = make_gang_workload(3, 5, seed=22)
    for p in gpods:
        if (p["metadata"]["labels"][POD_GROUP_LABEL] == "gang-0001"
                and p["metadata"]["name"].endswith(("003", "004"))):
            p["spec"]["containers"][0]["resources"]["requests"]["cpu"] = \
                "9999999m"
    plain = make_pods(30, seed=23, with_affinity=True, with_tolerations=True)

    def run(rung):
        store = ObjectStore()
        ensure_podgroup_resource(store)
        for n in nodes:
            store.create("nodes", copy.deepcopy(n))
        for pg in pgs:
            store.create("podgroups", copy.deepcopy(pg))
        for p in gpods + plain:
            store.create("pods", copy.deepcopy(p))
        cfg = PluginSetConfig(
            enabled=["NodeResourcesFit", "NodeAffinity", "TaintToleration",
                     "Coscheduling"],
            custom={"Coscheduling": Coscheduling()},
        )
        engine = SchedulerEngine(store, plugin_config=cfg, chunk=8,
                                 residency_floor=rung)
        bound = engine.schedule_pending()
        parked = sorted(engine.gang_parked)
        return bound, parked, _read_all(store)

    bound_l, parked_l, anns_l = run(_mode(monkeypatch, "lazy"))
    bound_e, parked_e, anns_e = run(_mode(monkeypatch, "eager"))
    assert bound_l == bound_e
    assert parked_l == parked_e and len(parked_l) == 3
    _assert_same(anns_l, anns_e, "lazy vs eager gang wave")


def test_unread_pods_survive_later_wave_overwrite(monkeypatch):
    """A pod scheduled by wave 1 and RE-scheduled by wave 2 before
    anyone reads it materializes both records in order: annotations =
    wave 2's bytes, result-history = [wave-1 record, wave-2 record] —
    exactly what eager mode wrote."""
    nodes = [{"metadata": {"name": "n1"},
              "status": {"allocatable": {"cpu": "2", "memory": "4Gi",
                                         "pods": "10"}}}]
    pods = [{"metadata": {"name": f"p{i}"}, "spec": {"containers": [
        {"name": "c", "resources": {"requests": {"cpu": "1",
                                                 "memory": "1Gi"}}}]}}
            for i in range(4)]
    extra_node = {"metadata": {"name": "n2"},
                  "status": {"allocatable": {"cpu": "8", "memory": "16Gi",
                                             "pods": "10"}}}

    def run(rung):
        store = ObjectStore()
        for n in nodes:
            store.create("nodes", copy.deepcopy(n))
        for p in pods:
            store.create("pods", copy.deepcopy(p))
        engine = SchedulerEngine(store, plugin_config=PluginSetConfig(
            enabled=["NodeResourcesFit",
                     "NodeResourcesBalancedAllocation"]),
            residency_floor=rung)
        b1 = engine.schedule_pending()   # capacity for 2: rest pending
        store.create("nodes", copy.deepcopy(extra_node))
        b2 = engine.schedule_pending()   # retried pods get a 2nd record
        return store, b1, b2

    store_l, b1_l, b2_l = run(_mode(monkeypatch, "lazy"))
    store_e, b1_e, b2_e = run(_mode(monkeypatch, "eager"))
    assert (b1_l, b2_l) == (b1_e, b2_e) and b2_l > 0
    anns_l, anns_e = _read_all(store_l), _read_all(store_e)
    _assert_same(anns_l, anns_e, "overwrite-before-read")
    # the retried pods carry BOTH wave records, oldest first
    multi = [n for n, a in anns_e.items()
             if len(json.loads(a.get(ann.RESULT_HISTORY, "[]"))) >= 2]
    assert multi, "expected at least one pod with a two-record history"


def test_concurrent_first_reads_decode_each_chunk_once(monkeypatch):
    """The multi-thread first-read soak: many concurrent cold readers
    across several chunks; every read returns eager-identical bytes and
    each chunk decodes EXACTLY once (one decode_lazy span per chunk —
    concurrent readers of a chunk wait on the owner instead of decoding
    again)."""
    nodes, pods = _mixed_workload()
    _, store_e, _, _ = _run_wave(nodes, pods,
                                 rung=_mode(monkeypatch, "eager"))
    baseline = _read_all(store_e)

    _mode(monkeypatch, "lazy")
    engine, store, _, _ = _run_wave(nodes, pods, chunk=16)
    n_chunks = (len(pods) + 15) // 16
    TRACER.reset()

    names = [p["metadata"]["name"] for p in list_shared(store, "pods")]
    errors: list = []
    results: dict[str, dict] = {}
    res_mu = threading.Lock()
    start = threading.Barrier(8)

    def reader(k):
        try:
            start.wait()
            # stripe across the queue so every chunk gets concurrent
            # cold readers from several threads
            for name in names[k::2]:
                a = store.get("pods", name, "default")["metadata"] \
                    .get("annotations") or {}
                with res_mu:
                    prev = results.setdefault(name, a)
                assert prev == a
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=reader, args=(k % 2,))
               for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    for name, a in results.items():
        for key in baseline[name]:
            assert a.get(key) == baseline[name][key], (name, key)
    spans = TRACER.summary()["spans"]
    assert spans.get("decode_lazy", {}).get("count") == n_chunks, (
        f"expected exactly {n_chunks} chunk decodes, got "
        f"{spans.get('decode_lazy')}")


def test_lazy_flight_recorder_taps(monkeypatch):
    """decode_on_demand_total{result=hit|miss}, the cold first-read
    histogram and the decode_lazy span all record, and the exposition
    stays strictly valid."""
    from kube_scheduler_simulator_tpu.utils.tracing import validate_exposition

    nodes, pods = _mixed_workload()
    _mode(monkeypatch, "lazy")
    engine, store, _, _ = _run_wave(nodes, pods, chunk=16)
    TRACER.reset()
    store.get("pods", pods[0]["metadata"]["name"], "default")   # cold
    store.list("pods")  # drains the rest: chunk-mates are warm hits
    snap = TRACER.snapshot()
    od = {tuple(sorted(s["labels"].items())): s["value"]
          for s in snap["labeled_counters"]["decode_on_demand_total"]}
    assert od[(("result", "miss"),)] >= 1
    assert od[(("result", "hit"),)] >= 1
    hist = snap["histograms"]["lazy_decode_cold_read_seconds"]
    assert hist["series"][0]["count"] >= 1
    assert "decode_lazy" in snap["spans"]
    validate_exposition(TRACER.prometheus_text())


def test_export_and_dump_carry_deferred_annotations(monkeypatch):
    """Snapshot fidelity: dump() (the reset/export surface) drains the
    deferred write-backs, so the snapshot carries the same annotation
    bytes an eager wave would have written."""
    nodes, pods = _mixed_workload()
    _mode(monkeypatch, "lazy")
    engine, store, _, _ = _run_wave(nodes, pods)
    assert engine.reflector._lazy.pending_count() == len(pods)
    snap = store.dump()
    assert engine.reflector._lazy.pending_count() == 0
    annotated = sum(
        1 for obj in snap["pods"].values()
        if (obj["metadata"].get("annotations") or {}).get(ann.SELECTED_NODE)
        is not None)
    assert annotated == len(pods)


def test_unsealed_wave_records_never_stall_readers():
    """A record queued by a still-streaming wave (unsealed LazyWave) is
    SKIPPED by drains — a GET or watch-pump flush mid-wave returns
    immediately instead of blocking until the replay finishes — and
    lands on the first flush after the seal."""
    from kube_scheduler_simulator_tpu.store.reflector import LazyReflections

    store = ObjectStore()
    store.create("pods", {"metadata": {"name": "p"},
                          "spec": {"containers": [{"name": "c"}]}})
    uid = store.get("pods", "p")["metadata"]["uid"]

    class _Part:  # DeferredResult stand-in backed by an unsealed wave
        def __init__(self):
            self.sealed = False

        def ready(self):
            return self.sealed

        def result_set(self):
            assert self.sealed, "materialized before the wave sealed"
            return {ann.SELECTED_NODE: "n1"}

    part = _Part()
    reg = LazyReflections(store)
    reg.add("default", "p", uid, [part])
    reg.flush("pods", "p", "default")        # mid-wave: must not block
    assert reg.pending_count() == 1          # record survived, unapplied
    reg.flush("pods")                        # whole-resource: same
    assert reg.pending_count() == 1
    part.sealed = True                       # wave seals
    reg.flush("pods")
    assert reg.pending_count() == 0
    a = store.get("pods", "p")["metadata"].get("annotations") or {}
    assert a.get(ann.SELECTED_NODE) == "n1"


def test_deleted_pod_drops_deferred_records(monkeypatch):
    """Deleting a pod discards its deferred records (they stop pinning
    the wave's replay buffers) without disturbing its neighbors."""
    nodes, pods = _mixed_workload()
    _mode(monkeypatch, "lazy")
    engine, store, _, _ = _run_wave(nodes, pods)
    reg = engine.reflector._lazy
    n0 = reg.pending_count()
    victim = pods[5]["metadata"]["name"]
    store.delete("pods", victim, "default")
    assert reg.pending_count() == n0 - 1
    # neighbors still materialize fine
    a = store.get("pods", pods[6]["metadata"]["name"],
                  "default")["metadata"].get("annotations") or {}
    assert ann.SELECTED_NODE in a
