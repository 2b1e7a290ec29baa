"""Fault injection + wave failure protocol (docs/fault-injection.md).

Covers the deterministic seam layer (utils/faults.py), the engine's
wave failure protocol (uncommitted-suffix retry, the device->host->eager
degradation ladder with probe recovery, compile quarantine), the decode
failure visibility/heal satellite, the interruptible retry backoff, and
the session create/evict seams.  The tier-2 chaos suite
(tests/test_chaos.py, `make chaos`) composes all of this concurrently;
these tests pin each mechanism in isolation.
"""

from __future__ import annotations

import json
import threading
import time

import jax
import pytest

from kube_scheduler_simulator_tpu.cluster.store import Conflict, ObjectStore
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.utils import faults
from kube_scheduler_simulator_tpu.utils.faults import (
    FaultPlan, FaultRule, InjectedFault, classify_fault, fault_point,
)
from kube_scheduler_simulator_tpu.utils.retry import (
    RetryAborted, RetryTimeout, retry_with_exponential_backoff,
)
from kube_scheduler_simulator_tpu.utils.tracing import TRACER


def _counter(name: str, **labels) -> float:
    snap = TRACER.snapshot()
    if not labels:
        return (snap.get("counters") or {}).get(name, 0)
    for e in (snap.get("labeled_counters") or {}).get(name, []):
        if all(e["labels"].get(k) == v for k, v in labels.items()):
            return e["value"]
    return 0


def _cluster(n_nodes=3, n_pods=20):
    s = ObjectStore()
    for i in range(n_nodes):
        s.create("nodes", {
            "metadata": {"name": f"n{i}"},
            "status": {"allocatable": {"cpu": "8", "memory": "16Gi",
                                       "pods": "110"}}})
    for i in range(n_pods):
        s.create("pods", {
            "metadata": {"name": f"p{i:03d}", "namespace": "default"},
            "spec": {"containers": [{"name": "c", "resources": {
                "requests": {"cpu": "100m", "memory": "64Mi"}}}]}})
    return s


def _engine(store, chunk=8, **kw):
    eng = SchedulerEngine(store, chunk=chunk, **kw)
    eng._retry_sleep = lambda _d: None  # no real backoff in tests
    return eng


def _state(store):
    out = {}
    for p in store.list("pods")[0]:
        meta = p["metadata"]
        out[meta["name"]] = ((p.get("spec") or {}).get("nodeName"),
                             dict(meta.get("annotations") or {}))
    return out


def _reference(n_nodes=3, n_pods=20, chunk=8, **kw):
    s = _cluster(n_nodes, n_pods)
    assert _engine(s, chunk, **kw).schedule_pending() == n_pods
    return _state(s)


# ------------------------------------------------------------ plan core


def test_plan_is_deterministic_per_seed():
    def trips(seed):
        plan = FaultPlan([FaultRule("decode.chunk", p=0.3, times=None)],
                         seed=seed)
        hits = []
        for i in range(200):
            try:
                with faults.armed(plan):
                    fault_point("decode.chunk")
            except InjectedFault:
                hits.append(i)
        return hits

    assert trips(7) == trips(7)
    assert trips(7) != trips(8)
    assert trips(7)  # p=0.3 over 200 hits: fires


def test_nth_trips_exactly_once_and_times_bounds():
    plan = FaultPlan([FaultRule("decode.chunk", nth=3)], seed=0)
    fired = []
    with faults.armed(plan):
        for i in range(1, 8):
            try:
                fault_point("decode.chunk")
            except InjectedFault:
                fired.append(i)
    assert fired == [3]
    stats = plan.stats()["rules"][0]
    assert (stats["hits"], stats["trips"]) == (7, 1)


def test_session_filter_scopes_rules():
    plan = FaultPlan([FaultRule("decode.chunk", nth=1,
                                sessions=["tenant-a"])], seed=0)
    with faults.armed(plan):
        fault_point("decode.chunk")  # unscoped hit: no match, no count
        with TRACER.session_scope("tenant-b"):
            fault_point("decode.chunk")
        with TRACER.session_scope("tenant-a"):
            with pytest.raises(InjectedFault):
                fault_point("decode.chunk")


def test_plan_from_env_and_validation(monkeypatch):
    doc = {"seed": 9, "rules": [
        {"seam": "replay.scan_dispatch", "nth": 2, "error": "memory"}]}
    monkeypatch.setenv("KSS_TPU_FAULT_PLAN", json.dumps(doc))
    plan = FaultPlan.from_env()
    assert plan.seed == 9 and plan.rules[0].error == "memory"
    monkeypatch.delenv("KSS_TPU_FAULT_PLAN")
    assert FaultPlan.from_env() is None
    with pytest.raises(ValueError, match="unknown fault seam"):
        FaultRule("not.a.seam", nth=1)
    with pytest.raises(ValueError, match="error type"):
        FaultRule("decode.chunk", nth=1, error="kaboom")
    with pytest.raises(ValueError, match="exactly one"):
        FaultRule("decode.chunk")


def test_unarmed_fault_point_is_noop():
    assert faults.current_plan() is None
    for seam in faults.SEAMS:
        fault_point(seam)  # no plan: must never raise


def test_classification():
    assert classify_fault(faults.InjectedRuntimeFault("x")) == "transient"
    assert classify_fault(faults.InjectedOOM("x")) == "structural"
    assert classify_fault(MemoryError()) == "structural"
    # what the installed runtime raises when HBM runs out (the class is
    # matched by isinstance: its name changed across JAX releases)
    assert classify_fault(jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm"
    )) == "structural"
    assert classify_fault(jax.errors.JaxRuntimeError(
        "INTERNAL: stream did not block host until done")) == "transient"
    assert classify_fault(RuntimeError()) == "transient"
    assert classify_fault(RetryTimeout()) == "fatal"
    assert classify_fault(KeyboardInterrupt()) == "fatal"


# ------------------------------------------------- wave failure protocol


def test_transient_scan_fault_retries_suffix_bit_identical():
    ref = _reference()
    s = _cluster()
    eng = _engine(s)
    before = _counter("wave_retries_total")
    plan = FaultPlan([FaultRule("replay.scan_dispatch", nth=2,
                                error="runtime")], seed=1)
    with faults.armed(plan):
        assert eng.schedule_pending() == 20
    assert plan.stats()["rules"][0]["trips"] == 1
    assert _counter("wave_retries_total") > before
    assert _counter("wave_faults_total", seam="replay.scan_dispatch",
                    action="retried") >= 1
    assert _state(s) == ref  # bit-identical to the fault-free run


def test_transient_fetch_fault_retries_bit_identical():
    ref = _reference()
    s = _cluster()
    eng = _engine(s)
    plan = FaultPlan([FaultRule("replay.decision_fetch", nth=2,
                                error="io")], seed=1)
    with faults.armed(plan):
        assert eng.schedule_pending() == 20
    assert _state(s) == ref


def test_retry_suffix_aligns_with_filtered_pending():
    """The retry suffix indexes the attempt's FILTERED pending list
    (scheduling gates, excludes, gang prescreen drop pods before the
    commit watermark is cut) — a fault + gated pods must not shift the
    suffix onto the wrong pods."""
    def cluster_with_gated():
        s = _cluster()
        for i in (2, 9):  # gated pods interleaved in queue order
            p = s.get("pods", f"p{i:03d}", "default")
            p["spec"]["schedulingGates"] = [{"name": "hold"}]
            s.update("pods", p)
        return s

    ref_s = cluster_with_gated()
    assert _engine(ref_s).schedule_pending() == 18
    ref = _state(ref_s)
    s = cluster_with_gated()
    eng = _engine(s)
    plan = FaultPlan([FaultRule("replay.scan_dispatch", nth=2,
                                error="runtime")], seed=3)
    with faults.armed(plan):
        assert eng.schedule_pending() == 18
    assert plan.stats()["rules"][0]["trips"] == 1
    assert _state(s) == ref


def test_structural_fault_steps_down_ladder_losslessly():
    ref = _reference()
    s = _cluster()
    eng = _engine(s)
    plan = FaultPlan([FaultRule("replay.scan_dispatch", nth=1,
                                error="memory")], seed=1)
    with faults.armed(plan):
        assert eng.schedule_pending() == 20
    assert eng.result_mode() == "host_resident"
    assert _counter("wave_degradations_total",
                    **{"from": "device_resident",
                       "to": "host_resident"}) >= 1
    assert _state(s) == ref  # the rungs are parity gates: lossless


def test_double_structural_fault_reaches_eager():
    ref = _reference()
    s = _cluster()
    eng = _engine(s)
    plan = FaultPlan([
        FaultRule("replay.scan_dispatch", nth=1, error="memory"),
        FaultRule("replay.scan_dispatch", nth=2, error="memory"),
    ], seed=1)
    with faults.armed(plan):
        assert eng.schedule_pending() == 20
    assert eng.result_mode() == "eager_decode"
    assert _state(s) == ref


def test_probe_recovery_steps_back_up(monkeypatch):
    monkeypatch.setenv("KSS_TPU_DEGRADE_PROBE_WAVES", "2")
    s = _cluster(n_pods=6)
    eng = _engine(s)
    plan = FaultPlan([FaultRule("replay.scan_dispatch", nth=1,
                                error="memory")], seed=1)
    with faults.armed(plan):
        assert eng.schedule_pending() == 6
    # one clean wave at the degraded rung so far: still degraded
    assert eng.result_mode() == "host_resident"
    # the second clean wave reaches the probe threshold -> step back up
    s.create("pods", {
        "metadata": {"name": "late", "namespace": "default"},
        "spec": {"containers": [{"name": "c", "resources": {
            "requests": {"cpu": "100m", "memory": "64Mi"}}}]}})
    assert eng.schedule_pending() == 1
    assert eng.result_mode() == "device_resident"
    assert _counter("wave_degradations_total",
                    **{"from": "host_resident",
                       "to": "device_resident"}) >= 1


def test_floor_caps_recovery(monkeypatch):
    eng = _engine(_cluster(n_pods=2), residency_floor=1)
    assert eng.result_mode() == "host_resident" and not eng.degraded
    assert eng._degrade("test") is True
    assert eng.result_mode() == "eager_decode" and eng.degraded
    monkeypatch.setenv("KSS_TPU_DEGRADE_PROBE_WAVES", "1")
    eng._wave_recovered_ok()
    # recovery lands on the floor, never above it
    assert eng.result_mode() == "host_resident" and not eng.degraded
    eng._wave_recovered_ok()
    assert eng.result_mode() == "host_resident"


def test_retries_exhausted_aborts_with_committed_prefix_standing(monkeypatch):
    """The _WaveCommitter.abort() baseline the protocol must not
    regress: a mid-stream replay failure leaves committed binds
    standing, lands NO binds after the failure, and the leftover pods
    reschedule cleanly on the next wave."""
    monkeypatch.setenv("KSS_TPU_WAVE_MAX_RETRIES", "0")
    s = _cluster()
    eng = _engine(s)
    # every fetch past the first fails: with retries disabled the wave
    # aborts on the first fault
    plan = FaultPlan([FaultRule("replay.decision_fetch", p=1.0, times=None,
                                nth=None)], seed=1)
    before_aborts = _counter("wave_faults_total",
                             seam="replay.decision_fetch", action="aborted")
    with faults.armed(plan):
        with pytest.raises(InjectedFault):
            eng.schedule_pending()
    assert _counter("wave_faults_total", seam="replay.decision_fetch",
                    action="aborted") > before_aborts
    # committed binds stand and form a PREFIX of pod order — nothing
    # lands after the failure point (abort drops queued chunks)
    state = _state(s)
    bound = sorted(n for n, (node, _a) in state.items() if node)
    all_names = sorted(state)
    assert bound == all_names[:len(bound)]
    # the leftover pods reschedule cleanly on the next (fault-free) wave
    monkeypatch.setenv("KSS_TPU_WAVE_MAX_RETRIES", "3")
    assert eng.schedule_pending() == 20 - len(bound)
    assert _state(s) == _reference()


def test_transient_fault_after_full_commit_keeps_bind_count():
    """An empty uncommitted suffix (every pod committed, the fault hit
    post-commit work like the reflect drain) must not abort a
    fully-committed wave: the retry settles immediately and the wave
    returns its bind count."""
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig

    s = _cluster()
    # a postfilter-free config keeps the STREAMING committer on — the
    # path whose finish()-time reflect drain this test poisons
    eng = SchedulerEngine(s, chunk=8, plugin_config=PluginSetConfig(
        enabled=["NodeResourcesFit", "NodeAffinity"]))
    eng._retry_sleep = lambda _d: None
    assert eng._wave_plan().commit == "streamed"
    real = eng.reflector.reflect_batch
    calls = {"n": 0}

    def poisoned(items):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("post-commit drain poison")
        return real(items)

    eng.reflector.reflect_batch = poisoned
    before = _counter("wave_retries_total")
    assert eng.schedule_pending() == 20  # binds counted, no crash
    assert _counter("wave_retries_total") > before
    assert all(node for node, _a in _state(s).values())


def test_compile_quarantine_contains_key_not_process():
    from kube_scheduler_simulator_tpu.framework.replay import (
        CompileQuarantined, _ScanCacheRegistry)

    reg = _ScanCacheRegistry()
    calls = {"n": 0}

    def bad_builder():
        calls["n"] += 1
        raise RuntimeError("injected compile failure")

    for _ in range(2):  # first failures are transient: builder re-runs
        with pytest.raises(RuntimeError):
            reg.get_or_build(("shape-a",), bad_builder)
    assert calls["n"] == 2
    # 2 consecutive failures: the KEY is quarantined — fail-fast, no
    # third doomed compile
    with pytest.raises(CompileQuarantined):
        reg.get_or_build(("shape-a",), bad_builder)
    assert calls["n"] == 2
    assert reg.stats()["quarantined"] == 1
    # other keys (other sessions' shapes) are unaffected
    assert reg.get_or_build(("shape-b",), lambda: "jit-b") == "jit-b"
    # expiry re-admits the build; success clears the failure history
    with reg._mu:
        reg._failed[("shape-a",)][1] = 0.0
    assert reg.get_or_build(("shape-a",), lambda: "jit-a") == "jit-a"
    assert reg.stats()["quarantined"] == 0
    assert reg.get_or_build(("shape-a",), bad_builder) == "jit-a"  # cached


# --------------------------------------------------- decode heal satellite


def test_decode_fault_is_visible_and_heals_on_reread():
    # eager reference bytes for the same workload
    ref = _reference(residency_floor=2)
    s = _cluster()
    eng = _engine(s)
    assert eng.schedule_pending() == 20  # lazy: decode deferred to read
    before = _counter("decode_failures_total", path="native_chunk") \
        + _counter("decode_failures_total", path="python")
    plan = FaultPlan([FaultRule("decode.chunk", nth=1, error="runtime")],
                     seed=1)
    with faults.armed(plan):
        with pytest.raises(InjectedFault):
            _state(s)  # first read surfaces the fault...
        healed = _state(s)  # ...and the re-read heals it
    after = _counter("decode_failures_total", path="native_chunk") \
        + _counter("decode_failures_total", path="python")
    assert after > before  # the failure was counted, not silent
    assert healed == ref  # chunk-mates unpoisoned, bytes identical


# -------------------------------------------------- reflector + retry stop


def test_injected_write_conflicts_heal_under_backoff():
    from kube_scheduler_simulator_tpu.store import annotations as ann
    from kube_scheduler_simulator_tpu.store.reflector import StoreReflector
    from kube_scheduler_simulator_tpu.store.resultstore import ResultStore

    s = ObjectStore()
    s.create("pods", {"metadata": {"name": "p", "namespace": "default"},
                      "spec": {}})
    rs = ResultStore()
    rs.add_selected_node("default", "p", "n1")
    refl = StoreReflector(s, sleep=lambda _t: None)
    refl.add_result_store(rs, "k")
    plan = FaultPlan([FaultRule("reflector.write_back", p=1.0, times=3,
                                error="conflict")], seed=1)
    with faults.armed(plan):
        refl.reflect("default", "p")
    pod = s.get("pods", "p", "default")
    assert pod["metadata"]["annotations"][ann.SELECTED_NODE] == "n1"


def test_reflect_batch_fault_degrades_to_per_pod_path():
    from kube_scheduler_simulator_tpu.store import annotations as ann
    from kube_scheduler_simulator_tpu.store.reflector import StoreReflector
    from kube_scheduler_simulator_tpu.store.resultstore import ResultStore

    s = ObjectStore()
    for n in ("a", "b"):
        s.create("pods", {"metadata": {"name": n, "namespace": "default"},
                          "spec": {}})
    rs = ResultStore()
    for n in ("a", "b"):
        rs.add_selected_node("default", n, f"n-{n}")
    refl = StoreReflector(s, sleep=lambda _t: None)
    refl.add_result_store(rs, "k")
    before = _counter("wave_faults_total", seam="reflector.write_back",
                      action="batch_fallback")
    plan = FaultPlan([FaultRule("reflector.write_back", nth=1,
                                error="runtime")], seed=1)
    with faults.armed(plan):
        refl.reflect_batch([("default", "a", None), ("default", "b", None)])
    assert _counter("wave_faults_total", seam="reflector.write_back",
                    action="batch_fallback") > before
    for n in ("a", "b"):
        pod = s.get("pods", n, "default")
        assert pod["metadata"]["annotations"][ann.SELECTED_NODE] == f"n-{n}"


def test_retry_stop_event_interrupts_backoff_fast():
    stop = threading.Event()
    calls = {"n": 0}

    def never_done():
        calls["n"] += 1
        return False, None

    threading.Timer(0.05, stop.set).start()
    t0 = time.monotonic()
    with pytest.raises(RetryAborted):
        retry_with_exponential_backoff(never_done, stop=stop)
    # the full schedule sleeps ~36s; the stop wakes it immediately
    assert time.monotonic() - t0 < 5.0
    assert calls["n"] >= 1


def test_reflector_teardown_interrupts_inflight_backoff():
    """Satellite regression: eviction/shutdown must not ride out the
    ~36s backoff of a conflicting write."""
    from kube_scheduler_simulator_tpu.store.reflector import StoreReflector
    from kube_scheduler_simulator_tpu.store.resultstore import ResultStore

    class ConflictStore(ObjectStore):
        def update(self, resource, obj, **kwargs):
            raise Conflict("always")

    s = ConflictStore()
    s.create("pods", {"metadata": {"name": "p", "namespace": "default"},
                      "spec": {}})
    rs = ResultStore()
    rs.add_selected_node("default", "p", "n1")
    refl = StoreReflector(s)  # REAL sleeps: the stop must interrupt them
    refl.add_result_store(rs, "k")
    errs: list = []

    def run():
        try:
            refl.reflect("default", "p")
        except BaseException as e:  # noqa: BLE001 — asserted below
            errs.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    time.sleep(0.15)  # let it enter the backoff
    t0 = time.monotonic()
    refl.stop_event.set()
    t.join(timeout=5)
    assert not t.is_alive(), "reflect rode out the backoff past teardown"
    assert time.monotonic() - t0 < 2.0
    assert errs and isinstance(errs[0], RetryAborted)


# ------------------------------------------------------- session seams


def test_session_create_fault_releases_reservation():
    from kube_scheduler_simulator_tpu.server.sessions import SessionManager

    mgr = SessionManager(max_sessions=4, idle_ttl=0, start_scheduler=False)
    try:
        plan = FaultPlan([FaultRule("session.create", nth=1,
                                    error="runtime")], seed=1)
        with faults.armed(plan):
            with pytest.raises(InjectedFault):
                mgr.create("s1")
            sess = mgr.create("s1")  # the reservation was released
        assert sess.id == "s1"
        assert {s["id"] for s in mgr.list_sessions()} == {"default", "s1"}
    finally:
        mgr.shutdown()


def test_session_evict_fault_counted_not_wedging():
    from kube_scheduler_simulator_tpu.server.sessions import SessionManager

    mgr = SessionManager(max_sessions=4, idle_ttl=0, start_scheduler=False)
    try:
        mgr.create("s1")
        before = _counter("session_teardown_failures_total",
                          reason="explicit")
        plan = FaultPlan([FaultRule("session.evict", nth=1,
                                    error="runtime")], seed=1)
        with faults.armed(plan):
            mgr.delete("s1")  # teardown fault: counted, not raised
        assert _counter("session_teardown_failures_total",
                        reason="explicit") > before
        assert {s["id"] for s in mgr.list_sessions()} == {"default"}
        mgr.create("s1")  # admission still works
    finally:
        mgr.shutdown()


def test_sessions_surface_degraded_mode():
    from kube_scheduler_simulator_tpu.server.sessions import SessionManager

    mgr = SessionManager(max_sessions=4, idle_ttl=0, start_scheduler=False)
    try:
        info = mgr.default.info()
        assert info["resultMode"] == "device_resident"
        assert info["degraded"] is False
        mgr.default.di.engine._degrade("test")
        info = mgr.default.info()
        assert info["resultMode"] == "host_resident"
        assert info["degraded"] is True
    finally:
        mgr.shutdown()


# --------------------------------------------------------------- taps


def test_fault_taps_are_valid_exposition():
    from kube_scheduler_simulator_tpu.utils.tracing import validate_exposition

    s = _cluster(n_pods=4)
    eng = _engine(s)
    plan = FaultPlan([FaultRule("replay.scan_dispatch", nth=1,
                                error="runtime")], seed=1)
    with faults.armed(plan):
        eng.schedule_pending()
    text = TRACER.prometheus_text()
    assert "wave_retries_total" in text
    assert "fault_injected_total" in text
    validate_exposition(text)  # raises on any conformance violation
