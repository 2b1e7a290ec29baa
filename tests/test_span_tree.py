"""The span tree of a served pass and the counters beside it
(docs/metrics.md "The span tree of a pass"): wave and its children, the
compile_workload phases, queue wait, JAX compile events by function, GC
pauses, the loop's and the server's spans, and the same spans as kss:
TraceMe events in a profile."""

from __future__ import annotations

import gc
import json
import sys
import threading
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.state.compile import BUILD_SPAN_PLUGINS
from kube_scheduler_simulator_tpu.utils import hostevents
from kube_scheduler_simulator_tpu.utils.tracing import (
    TRACER, validate_exposition)

WAVE_CHILDREN = ("wave_setup", "compile_workload", "replay_and_decode_stream",
                 "commit_and_reflect", "wave_finish")


def _wave(plugin_config=None, nodes=50, pods=12, seed=31):
    """One engine pass over a fresh store -> (engine, TRACER.snapshot())."""
    hostevents.install()
    store = ObjectStore()
    for n in make_nodes(nodes, seed=seed):
        store.create("nodes", n)
    for p in make_pods(pods, seed=seed + 1):
        store.create("pods", p)
    engine = SchedulerEngine(store, plugin_config=plugin_config, chunk=16)
    # what a process's first wave imports between two children (~50 ms,
    # once: _needs_host_path's) is no part of a pass, and the children
    # are held to 95% of a wave that can be as short as 1.5 s here
    import kube_scheduler_simulator_tpu.scheduler.debuggable  # noqa: F401
    TRACER.reset()
    engine.schedule_pending()
    snap = TRACER.snapshot()
    engine.close()
    return engine, snap


def _seconds(snap, names):
    return sum(snap["spans"].get(n, {}).get("total_seconds", 0.0)
               for n in names)


@pytest.fixture(scope="module")
def default_wave():
    """The default profile (every default plugin; the sequential scan)."""
    return _wave()


def test_wave_children_cover_the_sequential_wave(default_wave):
    _, snap = default_wave
    spans = snap["spans"]
    assert spans["wave"]["count"] == 1
    assert _seconds(snap, WAVE_CHILDREN) >= 0.95 * _seconds(snap, ["wave"])
    # the two seams of the sequential scan, and the root's attrs
    assert spans["scan_dispatch"]["count"] >= 1
    assert spans["decision_fetch"]["count"] >= 1
    wave = [e for e in TRACER.events(4096) if e["name"] == "wave"]
    assert wave and wave[-1]["pods"] == 12 and wave[-1]["nodes"] == 50
    assert snap["counters"]["scheduling_work_passes_total"] == 1
    assert snap["counters"]["scheduling_pass_pods_total"] == 12


def test_every_default_plugin_yields_its_build_span(default_wave):
    engine, snap = default_wave
    enabled = set(engine.plugin_config.active_plugins())
    built = [p for p in BUILD_SPAN_PLUGINS if p in enabled]
    assert len(built) >= 10, built  # the default profile enables them all
    for plugin in built:
        assert snap["spans"][f"cw_build_{plugin}"]["count"] == 1, plugin
    phases = ["cw_bound_delta", "cw_schema", "cw_node_table", "cw_core",
              "cw_volume_table", "cw_finish"] + [f"cw_build_{p}" for p in built]
    for name in phases:
        assert name in snap["spans"], name
    assert _seconds(snap, phases) >= 0.9 * _seconds(snap, ["compile_workload"])
    # the pass's one upload site, inside cw_finish
    assert snap["spans"]["cw_upload"]["count"] == 1
    assert _seconds(snap, ["cw_upload"]) <= _seconds(snap, ["cw_finish"])


def test_jax_listener_counts_one_backend_compile_per_fresh_function():
    import jax

    hostevents.install()

    @jax.jit
    def fresh_fn_for_the_listener_test(x):
        return x * 3 + 1

    x = np.arange(5, dtype=np.int32)
    key = "jax_compile_events_total{stage=backend_compile}"
    fun = ("jax_compiles_by_function_total{"
           "fun=jit(fresh_fn_for_the_listener_test),span=listener_test}")
    before = TRACER.counter_totals()
    t0 = hostevents.thread_compile_seconds()
    with TRACER.span("listener_test"):
        fresh_fn_for_the_listener_test(x)
    first = TRACER.counter_totals()
    assert first.get(key, 0) - before.get(key, 0) == 1
    assert first.get(fun, 0) - before.get(fun, 0) == 1
    assert hostevents.thread_compile_seconds() > t0
    for stage in ("trace", "lower", "backend_compile"):
        k = f"jax_compile_seconds_total{{stage={stage}}}"
        assert first.get(k, 0) > before.get(k, 0), stage
    with TRACER.span("listener_test"):
        fresh_fn_for_the_listener_test(x)
    second = TRACER.counter_totals()
    assert second.get(key, 0) == first.get(key, 0)  # zero on the second call
    assert second.get(fun, 0) == first.get(fun, 0)


def test_jax_listener_caps_function_labels(monkeypatch):
    monkeypatch.setattr(hostevents, "_fun_labels", set())
    names = [f"jit(f{i})" for i in range(hostevents.MAX_FUN_LABELS)]
    assert [hostevents._fun_label(n) for n in names] == names
    assert hostevents._fun_label("jit(one_too_many)") == "other"
    assert hostevents._fun_label(names[3]) == names[3]  # known names stay
    assert hostevents._fun_label(None) == "other"


def test_gc_hook_counts_a_full_collection():
    hostevents.install()
    before = TRACER.counter_totals()
    gc.collect(2)
    after = TRACER.counter_totals()
    n = "gc_collections_total{generation=2}"
    s = "gc_pause_seconds_total{generation=2}"
    assert after.get(n, 0) - before.get(n, 0) >= 1
    assert after.get(s, 0) > before.get(s, 0)
    # ... and each full collection is a gc_gen2 span on the timeline
    assert TRACER.snapshot()["spans"]["gc_gen2"]["count"] >= 1


def test_queue_wait_after_a_held_debounce():
    from kube_scheduler_simulator_tpu.server.di import SchedulingLoop

    store = ObjectStore()
    store.create("nodes", make_nodes(1, seed=41)[0])
    engine = SchedulerEngine(
        store, plugin_config=PluginSetConfig(enabled=["NodeResourcesFit"]))
    # the cap is far off: what holds the window for 0.3 s is a writer
    # still in flight, as an import's handler is while it creates pods
    loop = SchedulingLoop(store, engine, window_cap=30.0)
    TRACER.reset()
    loop.start()
    try:
        pod = make_pods(1, seed=42)[0]
        with loop.writer_in_flight():
            store.create("pods", pod)
            deadline = time.time() + 60
            while not any(s["name"] == "loop_debounce"
                          for s in TRACER.open_spans()):
                assert time.time() < deadline, "no window opened"
                time.sleep(0.005)
            time.sleep(0.3)
        meta = pod["metadata"]
        deadline = time.time() + 60
        while time.time() < deadline:
            got = store.get("pods", meta["name"], meta.get("namespace"))
            if (got.get("spec") or {}).get("nodeName"):
                break
            time.sleep(0.02)
        else:
            pytest.fail("the loop never bound the pod")
        # the counters land at wave START, the bind later in the same wave
        c = TRACER.snapshot()["counters"]
    finally:
        loop.stop()
        engine.close()
    assert c["queue_wait_pods_total"] == 1
    assert 0.3 <= c["queue_wait_seconds_total"] < 5.0
    assert c["queue_wait_oldest_seconds_total"] == c["queue_wait_seconds_total"]
    assert c["scheduling_work_passes_total"] == 1
    assert c["scheduling_pass_pods_total"] == 1
    spans = TRACER.snapshot()["spans"]
    assert spans["loop_debounce"]["total_seconds"] >= 0.3
    assert spans["loop_idle"]["count"] >= 1
    assert not engine._arrivals  # the stamp was taken by the wave


def test_profile_holds_the_spans_as_kss_tracemes(tmp_path):
    """With a profile running, every program span is also a kss:<name>
    TraceMe in the .xplane.pb, and benchmark/lib/xplane_spans.py finds
    them there (the CPU backend has no device plane: all of it is idle)."""
    from jax.profiler import ProfileData

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "benchmark"))
    from lib import xplane_spans

    store = ObjectStore()
    for n in make_nodes(5, seed=51):
        store.create("nodes", n)
    for p in make_pods(3, seed=52):
        store.create("pods", p)
    engine = SchedulerEngine(
        store, plugin_config=PluginSetConfig(enabled=["NodeResourcesFit"]))
    TRACER.start_xla_profile(str(tmp_path), python_tracer=False)
    try:
        assert TRACER.profiling
        with TRACER.span("loop_pass"):
            engine.schedule_pending()
    finally:
        TRACER.stop_xla_profile()
        engine.close()
    assert TRACER._annotate is None
    pd = ProfileData.from_file(str(xplane_spans.find_xplane(tmp_path)))
    names = {e.name for plane in pd.planes for ln in plane.lines
             for e in ln.events if e.name.startswith("kss:")}
    assert {"kss:compile_workload", "kss:wave", "kss:cw_core",
            "kss:cw_bound_delta", "kss:scan_dispatch"} <= names, names
    red = xplane_spans.reduce_spans(pd)
    assert red["kss_events"] >= 10
    by_span = dict(red["idle_by_span"])
    assert "loop_pass" not in by_span or by_span["loop_pass"] < red["idle_s"]
    assert red["idle_in_spans_s"] == pytest.approx(red["idle_s"], rel=1e-6)


@pytest.fixture(scope="module")
def live_server():
    from kube_scheduler_simulator_tpu.config.config import (
        SimulatorConfiguration)
    from kube_scheduler_simulator_tpu.server.di import DIContainer
    from kube_scheduler_simulator_tpu.server.server import SimulatorServer

    di = DIContainer(SimulatorConfiguration(port=0), start_scheduler=True)
    srv = SimulatorServer(di, port=0)
    srv.start(block=False)
    yield di, f"http://127.0.0.1:{srv.port}"
    srv.shutdown()


def _http(base, method, path, body=None):
    req = urllib.request.Request(
        base + path, method=method,
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        raw = r.read()
        return r.status, json.loads(raw) if raw else None


def test_served_requests_and_the_watch_stream_have_spans(live_server):
    _, base = live_server
    TRACER.reset()
    got = threading.Event()

    def watch():
        with urllib.request.urlopen(base + "/api/v1/listwatchresources",
                                    timeout=20) as resp:
            if resp.read1(65536):
                got.set()

    t = threading.Thread(target=watch, daemon=True)
    t.start()
    _http(base, "POST", "/api/v1/import",
          {"nodes": make_nodes(2, seed=61), "pods": []})
    pod = make_pods(1, seed=62)[0]
    _http(base, "POST", "/api/v1/pods", pod)
    meta = pod["metadata"]
    ns = meta.get("namespace") or "default"
    # until the loop has bound the pod: no wave may outlive this module
    deadline = time.time() + 120
    while True:
        _, got_pod = _http(base, "GET", f"/api/v1/pods/{ns}/{meta['name']}")
        if (got_pod.get("spec") or {}).get("nodeName"):
            break
        assert time.time() < deadline, "the loop never bound the pod"
        time.sleep(0.05)
    assert got.wait(20), "the watch stream delivered nothing"
    t.join(timeout=5)
    snap = TRACER.snapshot()
    for name in ("http_import", "http_pod_create", "http_pod_read",
                 "watch_write", "watch_flush", "watch_encode", "watch_send",
                 "http_encode", "http_send"):
        assert snap["spans"][name]["count"] >= 1, name
    assert snap["counters"]["watch_bytes_sent_total"] > 0
    # the create's span carries the request's trace id
    ev = [e for e in TRACER.events(4096) if e["name"] == "http_pod_create"]
    assert ev and ev[-1].get("trace_id", "").startswith("t-")


def _watch_until_bound(base, name, ready, done):
    """Hold a watch stream open until `name` shows bound on it."""
    with urllib.request.urlopen(base + "/api/v1/listwatchresources",
                                timeout=240) as resp:
        ready.set()
        buf = b""
        while not done.is_set():
            buf += resp.read1(1 << 16)
            if (b'"name": "%s"' % name.encode()) in buf \
                    and b'"nodeName": "' in buf.rpartition(
                        b'"name": "%s"' % name.encode())[2]:
                done.set()


def test_the_served_tree_past_the_commit(live_server, tmp_path):
    """docs/metrics.md "The decision's way out": the pump's and the
    handlers' new spans hang where the tree says, all of them are kss:
    TraceMe events in a profile, and the two stretches timed across
    threads are ring and aggregate only."""
    from jax.profiler import ProfileData

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "benchmark"))
    from lib import xplane_spans

    _, base = live_server
    _http(base, "POST", "/api/v1/import",
          {"nodes": make_nodes(2, seed=61), "pods": []})
    pod = make_pods(1, seed=63)[0]
    name = pod["metadata"]["name"] = "past-the-commit"
    ns = pod["metadata"].setdefault("namespace", "default")
    ready, done = threading.Event(), threading.Event()
    t = threading.Thread(target=_watch_until_bound,
                         args=(base, name, ready, done), daemon=True)
    t.start()
    # every wait below is on an event (the stream is open, the decision
    # is on it, the spans are recorded); the clocks are backstops sized
    # for a first pass that compiles beside five other xdist workers
    assert ready.wait(120)
    seen = {e["span_id"] for e in TRACER.events(4096)}
    TRACER.start_xla_profile(str(tmp_path), python_tracer=False)
    try:
        _http(base, "POST", "/api/v1/pods", pod)
        assert done.wait(240), "the decision never showed on the stream"
        _http(base, "GET", f"/api/v1/pods/{ns}/{name}")
        # a request's span closes after its last byte, the pump notes
        # the delivery after its write returns, the wave's tail runs on
        # after the commit: the client is back before they are recorded
        last_in = {"http_pod_read", "wave", "decision_delivery",
                   "decision_to_read"}
        deadline = time.time() + 120
        while True:
            missing = last_in - {e["name"] for e in TRACER.events(4096)
                                 if e["span_id"] not in seen}
            if not missing:
                break
            assert time.time() < deadline, f"never recorded: {missing}"
            time.sleep(0.01)
    finally:
        TRACER.stop_xla_profile()
        t.join(timeout=5)
    evs = [e for e in TRACER.events(4096) if e["span_id"] not in seen]
    by_id = {e["span_id"]: e for e in evs}

    def parents(child):
        return {by_id[e["parent_id"]]["name"] if e["parent_id"] in by_id
                else None for e in evs if e["name"] == child}

    assert parents("watch_encode") == {"watch_write"}
    assert parents("watch_send") == {"watch_write"}
    assert parents("watch_flush") == {None}  # a root on the pump's thread
    assert {"http_pod_create", "http_pod_read"} <= parents("http_encode")
    assert parents("http_encode") == parents("http_send")
    # the reflector's write-back (PR 45): under whichever reader drained
    # the pod's record, the pump ahead of an event or the GET's handler
    # (none where the stream's four-a-second drain took this one pod's
    # record first: its batched write has no span, and
    # tests/test_decision_delivery.py holds that a reader's has)
    assert parents("reflect_write_back") <= {"watch_flush", "http_pod_read"}
    # the two retroactive spans: one each, roots, the wave's trace id
    wave = [e for e in evs if e["name"] == "wave"][-1]
    for retro in ("decision_delivery", "decision_to_read"):
        got = [e for e in evs if e["name"] == retro]
        assert len(got) == 1 and got[0]["parent_id"] is None, (retro, got)
        assert got[0]["trace_id"] == wave["trace_id"]
        assert got[0]["session"] == "default"
    read = [e for e in evs if e["name"] == "http_pod_read"][-1]
    assert read["trace_id"] == wave["trace_id"]
    pd = ProfileData.from_file(str(xplane_spans.find_xplane(tmp_path)))
    kss = {e.name for plane in pd.planes for ln in plane.lines
           for e in ln.events if e.name.startswith("kss:")}
    assert {"kss:watch_flush", "kss:watch_write", "kss:watch_encode",
            "kss:watch_send", "kss:http_encode", "kss:http_send",
            "kss:http_pod_read"} <= kss, kss
    assert not {"kss:decision_delivery", "kss:decision_to_read"} & kss


def test_profile_route_takes_python_tracer_false(live_server, monkeypatch):
    import jax

    _, base = live_server
    seen = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: seen.append(kw))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    _http(base, "POST", "/api/v1/profile", {"action": "start"})
    _http(base, "POST", "/api/v1/profile", {"action": "stop"})
    _http(base, "POST", "/api/v1/profile",
          {"action": "start", "pythonTracer": False})
    _http(base, "POST", "/api/v1/profile", {"action": "stop"})
    assert seen[0] == {}  # the default stays as it was
    assert seen[1]["profiler_options"].python_tracer_level == 0


def test_exposition_stays_valid_with_the_new_labeled_families(default_wave):
    hostevents.install()
    gc.collect(2)
    import jax

    jax.jit(lambda x: x - 7)(np.arange(3))  # at least one compile event
    families = validate_exposition(TRACER.prometheus_text())
    by_fun = families["kss_tpu_jax_compiles_by_function_total"]
    assert by_fun["type"] == "counter"
    assert all({"fun", "span"} <= set(labels)
               for _n, labels, _v in by_fun["samples"])
    stages = {labels["stage"] for _n, labels, _v in
              families["kss_tpu_jax_compile_events_total"]["samples"]}
    assert {"trace", "lower", "backend_compile"} <= stages
    assert any(labels.get("generation") == "2" for _n, labels, _v in
               families["kss_tpu_gc_pause_seconds_total"]["samples"])


BACKEND_COMPILES = "jax_compile_events_total{stage=backend_compile}"


def _one_pod(name, affinity_terms, node_name=None):
    pod = {"apiVersion": "v1", "kind": "Pod",
           "metadata": {"name": name, "namespace": "default",
                        "labels": {"color": "blue"}},
           "spec": {"containers": [{"name": "c", "resources": {
               "requests": {"cpu": "100m", "memory": "64Mi"}}}]}}
    if affinity_terms:
        pod["spec"]["affinity"] = {"podAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "topologyKey": "topology.kubernetes.io/zone",
                "labelSelector": {"matchLabels": {"color": "blue"}}}]}}
    if node_name:
        pod["spec"]["nodeName"] = node_name
        pod["status"] = {"phase": "Running"}
    return pod


@pytest.mark.parametrize("affinity_terms,prefilled", [(False, 0), (True, 7)],
                         ids=["no_terms", "required_affinity"])
def test_a_steady_pass_builds_no_xla_executable(affinity_terms, prefilled):
    """One pod a pass onto a cluster whose bound set grows by one a pass
    (the interactive cells' traffic): after the first pass nothing that
    reaches the device has a shape that depends on the bound-pod count.
    (The cases start from different bound counts, so neither finds the
    other's executables in the process's jit cache.)"""
    hostevents.install()
    store = ObjectStore()
    nodes = make_nodes(12, seed=5)
    for n in nodes:
        store.create("nodes", n)
    for b in range(prefilled):
        store.create("pods", _one_pod(
            f"bound-{b}", affinity_terms,
            node_name=nodes[b % len(nodes)]["metadata"]["name"]))
    engine = SchedulerEngine(store, chunk=16)

    def one_pass(i):
        store.create("pods", _one_pod(f"steady-{i}", affinity_terms))
        engine.schedule_pending()
        assert store.get("pods", f"steady-{i}", "default")["spec"].get(
            "nodeName"), i
        return TRACER.counter_totals()

    try:
        warm = one_pass(0)
        seen = {e["span_id"] for e in TRACER.events(4096)}
        for i in (1, 2, 3):
            after = one_pass(i)
        events = [e for e in TRACER.events(4096) if e["span_id"] not in seen]
    finally:
        engine.close()
    # cw_bound_delta (the carry finds and applies what the last pass
    # bound: one row) sits under compile_workload in every pass
    cw_ids = {e["span_id"] for e in events if e["name"] == "compile_workload"}
    deltas = [e for e in events if e["name"] == "cw_bound_delta"]
    assert len(cw_ids) == 3 and len(deltas) == 6
    assert all(e["parent_id"] in cw_ids for e in deltas)
    # cw_upload, the pass's one host-to-device site, under cw_finish
    finish_ids = {e["span_id"] for e in events if e["name"] == "cw_finish"}
    uploads = [e for e in events if e["name"] == "cw_upload"]
    assert len(uploads) == 3
    assert all(e["parent_id"] in finish_ids for e in uploads)
    assert after["bound_rows_built_total"] - warm["bound_rows_built_total"] == 3
    assert (after["bound_rows_carried_total"]
            - warm.get("bound_rows_carried_total", 0)) == 3 * prefilled + 0 + 1 + 2
    grown = {k: v - warm.get(k, 0) for k, v in after.items()
             if k.startswith("jax_compiles_by_function_total")
             and v != warm.get(k, 0)}
    assert not grown, grown
    assert after.get(BACKEND_COMPILES, 0) == warm.get(BACKEND_COMPILES, 0)
