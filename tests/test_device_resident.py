"""Device-resident replay results (framework/replay.py device-residency
stage): decision-only in-wave fetch, on-demand D2H materialization.

The parity rule extends PR 9's (docs/wave-pipeline.md): whatever a
reader observes — pod annotations, result-history, bind order,
attribution tallies — must be bit-identical across the three residency
rungs: the device-resident default, host-resident (lazy decode,
in-wave host fetch — the PR 9 behavior) and eager decode, the
degradation ladder's two lower rungs, pinned on the engines built here
by `residency_floor` — including waves run on a mesh and chunks spilled
to host by the KSS_TPU_DEVICE_RESULT_BUDGET_MB budget.
"""

from __future__ import annotations

import copy
import queue as queue_mod
import sys
import threading

import numpy as np
import pytest

from kube_scheduler_simulator_tpu.cluster.store import ObjectStore, list_shared
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.framework.replay import (
    _DEVICE_BUDGET, plugin_attribution, replay)
from kube_scheduler_simulator_tpu.models.workloads import (
    baseline_config, make_nodes, make_pods)
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.state.compile import compile_workload
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

ENABLED = ["NodeResourcesFit", "NodeResourcesBalancedAllocation",
           "NodeAffinity", "TaintToleration", "PodTopologySpread"]

replay_mod = sys.modules["kube_scheduler_simulator_tpu.framework.replay"]


# the ladder's rungs, as SchedulerEngine(residency_floor=) takes them
RUNGS = {"device": 0, "host": 1, "eager": 2}


@pytest.fixture(autouse=True)
def _no_budget(monkeypatch):
    monkeypatch.delenv("KSS_TPU_DEVICE_RESULT_BUDGET_MB", raising=False)


def _mixed_workload():
    """Taints, affinity/toleration pods, host score columns (spread) and
    two prefilter-rejected pods mid-queue — the chunk-decode special
    cases (tests/test_lazy_decode.py recipe; 16 nodes so an 8-way mesh
    divides the node axis)."""
    nodes = make_nodes(16, seed=3, taint_fraction=0.3)
    pods = make_pods(50, seed=4, with_affinity=True, with_tolerations=True,
                     with_spread=True)
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


def _run_wave(nodes, pods, mode="device", pipeline=True, chunk=16,
              mesh=None):
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
        mesh=mesh, residency_floor=RUNGS[mode])
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


# ----------------------------------------------------- three-rung parity


@pytest.mark.parametrize("pipeline", [True, False])
def test_three_rung_byte_parity(monkeypatch, pipeline):
    """Device-resident (default), host-resident-lazy and eager runs of
    the same mixed wave are byte-identical in annotations,
    result-history, bind count and bind order — streaming commit and
    sequential post-pass both."""
    nodes, pods = _mixed_workload()
    results = {}
    for mode in ("device", "host", "eager"):
        TRACER.reset()
        engine, store, bound, order = _run_wave(nodes, pods, mode,
                                                pipeline=pipeline)
        if mode == "device":
            # residency really happened: the wave itself moved only
            # decision rows, and chunks are registered with the budget
            wave_bytes = TRACER.summary()["counters"].get(
                "wave_d2h_bytes_total", 0)
            assert _DEVICE_BUDGET.retained_chunks() > 0
            assert wave_bytes < 64 * len(pods) + 4096, wave_bytes
        results[mode] = (bound, order, _read_all(store))
    b0, o0, a0 = results["eager"]
    for mode in ("device", "host"):
        b, o, a = results[mode]
        assert b == b0 and o == o0
        _assert_same(a, a0, f"{mode} vs eager")


def test_mesh_sharded_wave_parity(monkeypatch):
    """A device-resident wave run on an 8-virtual-device mesh (node axis
    sharded) reads back bit-identical to the eager unsharded wave — the
    cold read's materialization gathers the shards."""
    from kube_scheduler_simulator_tpu.parallel.mesh import make_mesh

    nodes, pods = _mixed_workload()
    _, store_e, bound_e, _ = _run_wave(nodes, pods, "eager")
    baseline = _read_all(store_e)

    mesh = make_mesh(8, dp=1)
    engine, store, bound, _ = _run_wave(nodes, pods, mesh=mesh)
    assert bound == bound_e
    _assert_same(_read_all(store), baseline, "mesh device-resident vs eager")


def test_replay_level_mesh_attribution_parity(monkeypatch):
    """plugin_attribution over a mesh-sharded device-resident replay
    equals the host tally of a host-resident replay — the jit'd
    reduction's cross-shard sums ride GSPMD collectives."""
    from kube_scheduler_simulator_tpu.parallel.mesh import make_mesh

    nodes, pods = _mixed_workload()
    cfg = PluginSetConfig(enabled=list(ENABLED))
    cw = compile_workload(nodes, pods, cfg)
    rr_mesh = replay(cw, chunk=16, mesh=make_mesh(8, dp=1))
    att_mesh = plugin_attribution(rr_mesh)
    rr_host = replay(cw, chunk=16, device_resident=False)
    att_host = plugin_attribution(rr_host)
    assert att_mesh == att_host
    # and the device fold really was the source: no chunk materialized
    assert all(rr_mesh._compact.is_device(ci)
               for ci in range(len(rr_mesh._compact.packed)))


def test_attribution_device_fold_matches_host_tally(monkeypatch):
    """The on-device reduction (limb-recombined score sums, bitmap-fed
    host columns) is bit-identical to the host tally over the same
    replay values, and computing it never materializes a chunk."""
    nodes, pods = _mixed_workload()
    cfg = PluginSetConfig(enabled=list(ENABLED))
    cw = compile_workload(nodes, pods, cfg)
    rr = replay(cw, chunk=16)
    cc = rr._compact
    assert any(a is not None for a in cc.att)
    att_dev = plugin_attribution(rr)
    assert all(cc.is_device(ci) for ci in range(len(cc.packed)))
    # force the host tally over the SAME result: drop the device sums
    cc.att = [None] * len(cc.att)
    att_host = plugin_attribution(rr)
    assert att_dev == att_host


# ------------------------------------------------- width-tier re-runs


def test_width_tier_rerun_with_device_chunks(monkeypatch):
    """An injected score-width overflow re-runs the scan wider while the
    first tier's chunks were retained on device; the final result's
    annotations stay identical to pure Python and the first tier's
    retained chunks release their budget accounting."""
    nodes, pods, cfg = baseline_config(4, scale=0.02, seed=11)
    cw = compile_workload(nodes, pods, cfg)

    real_fetch = replay_mod._fetch_decisions
    state = {"fired": False, "count": 0}

    def inject_overflow(out_dev, att):
        c = real_fetch(out_dev, att)
        state["count"] += 1
        if not state["fired"] and state["count"] == 3:
            c["raw_overflow"] = np.asarray(True)
            state["fired"] = True
        return c

    monkeypatch.setattr(replay_mod, "_fetch_decisions", inject_overflow)
    before = TRACER.summary()["counters"].get("replay_width_retries_total", 0)
    retained0 = _DEVICE_BUDGET.retained_chunks()
    rr = replay(cw, chunk=32)
    retries = TRACER.summary()["counters"].get(
        "replay_width_retries_total", 0) - before
    assert retries >= 1, "no width retry triggered"
    import gc

    gc.collect()  # the abandoned first-tier compact drops its entries
    final_chunks = len(rr._compact.packed)
    assert _DEVICE_BUDGET.retained_chunks() - retained0 <= final_chunks

    out = [decode_pod_result(rr, i) for i in range(len(pods))]
    monkeypatch.setenv("KSS_TPU_DISABLE_NATIVE", "1")
    try:
        pure = [decode_pod_result(rr, i) for i in range(len(pods))]
    finally:
        monkeypatch.delenv("KSS_TPU_DISABLE_NATIVE")
    assert out == pure


# -------------------------------------------------- concurrent cold reads


def test_concurrent_cold_reads_one_d2h_per_chunk(monkeypatch):
    """8-thread cold-read soak over a device-resident wave: every read
    returns eager-identical bytes, and each chunk crosses the
    host/device boundary EXACTLY once (one d2h_fetch span per chunk;
    concurrent readers wait on the materialize owner)."""
    nodes, pods = _mixed_workload()
    _, store_e, _, _ = _run_wave(nodes, pods, "eager")
    baseline = _read_all(store_e)

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
    assert spans.get("d2h_fetch", {}).get("count") == n_chunks, (
        f"expected exactly {n_chunks} chunk materializations, got "
        f"{spans.get('d2h_fetch')}")
    assert spans.get("decode_lazy", {}).get("count") == n_chunks


# ------------------------------------------------------- retention budget


def test_spill_then_read_round_trip(monkeypatch):
    """KSS_TPU_DEVICE_RESULT_BUDGET_MB=0 spills every retained chunk to
    host on the background writer; reads after the spill return the
    eager bytes, and the spill taps record."""
    nodes, pods = _mixed_workload()
    _, store_e, _, _ = _run_wave(nodes, pods, "eager")
    baseline = _read_all(store_e)

    monkeypatch.setenv("KSS_TPU_DEVICE_RESULT_BUDGET_MB", "0")
    TRACER.reset()
    engine, store, _, _ = _run_wave(nodes, pods, chunk=16)
    _DEVICE_BUDGET.drain()
    counters = TRACER.summary()["counters"]
    assert counters.get("device_chunks_spilled_total", 0) >= 1
    snap = TRACER.snapshot()
    assert snap["gauges"].get("device_chunks_retained") == 0
    # spilled chunks are plain host chunks now: reads bit-identical,
    # and cold reads do NOT pay (or count) an on-demand D2H
    _assert_same(_read_all(store), baseline, "spill round-trip vs eager")
    assert "d2h_fetch" not in TRACER.summary()["spans"]


def test_budget_taps_and_exposition(monkeypatch):
    """The d2h taps (bytes counter + latency histogram + span) record on
    a cold read of a device-resident wave, the retained gauge tracks,
    and the exposition stays strictly valid."""
    from kube_scheduler_simulator_tpu.utils.tracing import validate_exposition

    nodes, pods = _mixed_workload()
    engine, store, _, _ = _run_wave(nodes, pods, chunk=16)
    TRACER.reset()
    store.get("pods", pods[0]["metadata"]["name"], "default")   # cold
    counters = TRACER.summary()["counters"]
    assert counters.get("d2h_on_demand_bytes_total", 0) > 0
    snap = TRACER.snapshot()
    assert snap["histograms"]["d2h_on_demand_seconds"]["series"][0]["count"] >= 1
    assert "d2h_fetch" in snap["spans"]
    assert "device_chunks_retained" in snap["gauges"]
    validate_exposition(TRACER.prometheus_text())


# -------------------------------------------------------- scan-cache LRU


def test_scan_cache_lru_alternating_shapes(monkeypatch):
    """_SCAN_CACHE is LRU, not insertion-order FIFO: two alternating
    workload shapes at capacity keep their compiled scans while a third
    evicts only the least-recently-USED entry."""
    nodes = make_nodes(4, seed=1)
    pods = make_pods(6, seed=2)
    cfg = PluginSetConfig(enabled=["NodeResourcesFit"])
    cw = compile_workload(nodes, pods, cfg)

    cache = replay_mod._SCAN_CACHE
    monkeypatch.setattr(cache, "max_entries", 2)
    saved = dict(cache._entries)
    cache._entries.clear()
    try:
        from kube_scheduler_simulator_tpu.framework.replay import _scan_for

        a = _scan_for(cw, chunk=2)   # shape A
        b = _scan_for(cw, chunk=3)   # shape B — cache full
        assert _scan_for(cw, chunk=2) is a   # hit moves A to recent end
        c = _scan_for(cw, chunk=4)   # evicts B (LRU), NOT A
        assert _scan_for(cw, chunk=2) is a, \
            "LRU must keep the just-hit entry on eviction"
        assert _scan_for(cw, chunk=4) is c
        assert _scan_for(cw, chunk=3) is not b, "B was the LRU victim"
    finally:
        cache._entries.clear()
        cache._entries.update(saved)


def test_scan_cache_interleave_beyond_capacity(monkeypatch):
    """_SCAN_CACHE_MAX+1 interleaved shapes: the hot alternating pair
    survives a full interleave cycle (the FIFO behavior this replaces
    evicted whichever entry was INSERTED first, recompiling the hot
    shapes every pass)."""
    nodes = make_nodes(4, seed=1)
    pods = make_pods(6, seed=2)
    cfg = PluginSetConfig(enabled=["NodeResourcesFit"])
    cw = compile_workload(nodes, pods, cfg)

    cache = replay_mod._SCAN_CACHE
    monkeypatch.setattr(cache, "max_entries", 3)
    saved = dict(cache._entries)
    cache._entries.clear()
    try:
        from kube_scheduler_simulator_tpu.framework.replay import _scan_for

        hot = [_scan_for(cw, chunk=2), _scan_for(cw, chunk=3)]
        for cold_chunk in (4, 5, 6, 7):  # max_entries+1 shapes total
            # touch the hot pair, then one cold shape — the cold shapes
            # must evict each other, never the just-touched pair
            assert _scan_for(cw, chunk=2) is hot[0]
            assert _scan_for(cw, chunk=3) is hot[1]
            _scan_for(cw, chunk=cold_chunk)
        assert _scan_for(cw, chunk=2) is hot[0]
        assert _scan_for(cw, chunk=3) is hot[1]
    finally:
        cache._entries.clear()
        cache._entries.update(saved)
