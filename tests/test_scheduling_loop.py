"""The scheduling loop's batching window (server/di.py SchedulingLoop):
it opens at the first pending pod's ADDED event and closes when arrivals
have settled (no workload-submitting request in flight and a quiet
interval since the last ADDED) or at its cap, whichever comes first.

Every loop here gets a cap far longer than the shipped 50 ms, so a
regression to a fixed sleep fails loudly instead of by 50 ms."""

from __future__ import annotations

import copy
import json
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
from kube_scheduler_simulator_tpu.control import CONTROLS
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.server import di as di_module
from kube_scheduler_simulator_tpu.server.di import SchedulingLoop
from kube_scheduler_simulator_tpu.server.sessions import DEFAULT_SESSION
from kube_scheduler_simulator_tpu.utils.tracing import TRACER


@pytest.fixture()
def rig():
    """A started loop over a fresh store, built by rig(cap, nodes=...);
    stopped and closed when the test ends."""
    made = []

    def build(cap: float, nodes: int = 2):
        store = ObjectStore()
        for n in make_nodes(nodes, seed=71):
            store.create("nodes", n)
        engine = SchedulerEngine(
            store, plugin_config=PluginSetConfig(enabled=["NodeResourcesFit"]))
        loop = SchedulingLoop(store, engine, window_cap=cap)
        TRACER.reset()
        loop.start()
        made.append((loop, engine))
        return store, engine, loop

    yield build
    for loop, engine in made:
        loop.stop()
        # a pass still running counts its wave when it ends: not into the
        # next test's freshly reset tracer
        loop._thread.join(timeout=60)
        engine.close()


def _wait(cond, what: str, timeout: float = 60.0):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            pytest.fail(what)
        time.sleep(0.01)


def _bound(store, pods) -> bool:
    return all(
        (store.get("pods", p["metadata"]["name"],
                   p["metadata"].get("namespace")).get("spec") or {})
        .get("nodeName") for p in pods)


def _counter(name: str) -> float:
    return TRACER.counter_totals().get(name, 0)


def _closed() -> dict:
    """loop_window_closed_total by reason, whatever session label rides."""
    return TRACER.labeled_totals("loop_window_closed_total", "reason")


def _window_open() -> bool:
    return any(s["name"] == "loop_debounce" for s in TRACER.open_spans())


def test_a_lone_pod_starts_its_pass_when_arrivals_settle(rig):
    store, _engine, _loop = rig(cap=0.5)
    pods = make_pods(1, seed=72)
    store.create("pods", pods[0])
    _wait(lambda: _bound(store, pods), "the loop never bound the pod")
    c = TRACER.counter_totals()
    assert c["queue_wait_pods_total"] == 1
    assert c["queue_wait_seconds_total"] < 0.1  # nowhere near the 0.5 s cap
    assert _closed() == {"settled": 1}
    assert c["scheduling_work_passes_total"] == 1


def test_a_writer_in_flight_holds_the_window_until_it_leaves(rig):
    store, _engine, loop = rig(cap=30.0)
    pods = make_pods(3, seed=73)
    with loop.writer_in_flight():
        store.create("pods", pods[0])
        _wait(_window_open, "the first ADDED event opened no window")
        for p in pods[1:]:
            time.sleep(0.1)  # many quiet intervals apart
            store.create("pods", p)
        time.sleep(0.1)
        assert _window_open()
        assert _counter("scheduling_waves_total") == 0
    _wait(lambda: _bound(store, pods), "the loop never bound the pods")
    c = TRACER.counter_totals()
    assert c["scheduling_work_passes_total"] == 1  # ONE batched workload
    assert c["scheduling_pass_pods_total"] == 3
    assert 0.3 <= c["queue_wait_oldest_seconds_total"] < 10.0
    assert _closed() == {"settled": 1}


def test_a_writer_that_never_leaves_is_cut_off_at_the_cap(rig):
    store, _engine, loop = rig(cap=0.3)
    pods = make_pods(1, seed=74)
    with loop.writer_in_flight():
        store.create("pods", pods[0])
        _wait(lambda: _bound(store, pods), "the cap never closed the window")
        c = TRACER.counter_totals()
    assert _closed() == {"cap": 1}
    assert 0.3 <= c["queue_wait_seconds_total"] < 5.0
    assert TRACER.snapshot()["spans"]["loop_debounce"]["total_seconds"] >= 0.3


def test_a_burst_is_one_pass_with_no_empty_pass_after_it(rig, monkeypatch):
    # a quiet interval no loaded test machine can stretch a create over:
    # what is under test is when _wake is cleared, not the constant
    monkeypatch.setattr(di_module, "QUIET_S", 0.25)
    store, _engine, _loop = rig(cap=30.0)
    pods = make_pods(5, seed=75)
    for p in pods:
        store.create("pods", p)
    _wait(lambda: _bound(store, pods), "the loop never bound the burst")
    # a stray wake-up would open a window at once and pass after QUIET_S
    time.sleep(0.6)
    c = TRACER.counter_totals()
    assert c["scheduling_work_passes_total"] == 1
    assert c["scheduling_pass_pods_total"] == 5
    assert c["scheduling_waves_total"] == 1  # the later ADDEDs bought no pass
    assert _closed() == {"settled": 1}
    assert TRACER.snapshot()["spans"]["loop_debounce"]["count"] == 1


def test_a_pod_created_during_a_pass_gets_a_pass_of_its_own(rig):
    store, engine, _loop = rig(cap=0.5)
    listed, release = threading.Event(), threading.Event()
    schedule_pending = engine.schedule_pending

    def hold_after_the_first_pass():
        n = schedule_pending()
        if not listed.is_set():
            # the pass has taken its list and bound it; it is still the
            # running pass as far as the loop can tell
            listed.set()
            assert release.wait(30)
        return n

    engine.schedule_pending = hold_after_the_first_pass
    first, late = make_pods(2, seed=76)
    store.create("pods", first)
    assert listed.wait(60), "the first pass never ran"
    store.create("pods", late)  # lands after _wake was cleared
    time.sleep(0.05)
    release.set()
    _wait(lambda: _bound(store, [first, late]), "the late pod was stranded")
    c = TRACER.counter_totals()
    assert c["scheduling_work_passes_total"] == 2
    assert c["scheduling_pass_pods_total"] == 2
    assert c["queue_wait_pods_total"] == 2
    assert _closed() == {"settled": 2}


def test_stop_during_an_open_window_returns_at_once(rig):
    store, _engine, loop = rig(cap=30.0)
    with loop.writer_in_flight():
        store.create("pods", make_pods(1, seed=77)[0])
        _wait(_window_open, "the ADDED event opened no window")
        t0 = time.monotonic()
        loop.stop()
        loop._thread.join(timeout=5)
        assert not loop._thread.is_alive()
        assert time.monotonic() - t0 < 2.0
    c = TRACER.counter_totals()
    assert c.get("scheduling_waves_total", 0) == 0  # no pass on the way out
    assert _closed() == {}


def test_concurrent_writers_strand_no_pod_and_all_leave(rig):
    """More writers than cores under a 10 us switch interval: a lost
    update on the in-flight count would hold every later window to its
    cap (30 s: the test would time out) or let it go negative."""
    store, _engine, loop = rig(cap=30.0, nodes=4)
    pods = make_pods(32, seed=78)
    errors = []

    def writer(mine):
        try:
            for p in mine:
                with loop.writer_in_flight():
                    store.create("pods", p)
        except Exception as e:  # surfaced below, on the test's thread
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(pods[i::16],))
               for i in range(16)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    # (well inside the cap: a few passes of pod counts the CPU backend
    # compiles for one by one)
    _wait(lambda: _bound(store, pods), "a pod was stranded", timeout=25.0)
    assert loop._in_flight == 0
    assert _counter("scheduling_pass_pods_total") == 32
    assert "cap" not in _closed()


# ------------------------------------------------ through the HTTP server


@pytest.fixture()
def served():
    from kube_scheduler_simulator_tpu.config.config import (
        SimulatorConfiguration)
    from kube_scheduler_simulator_tpu.server.di import DIContainer
    from kube_scheduler_simulator_tpu.server.server import SimulatorServer

    CONTROLS.reset()
    di = DIContainer(SimulatorConfiguration(port=0))
    # the shipped cap is 50 ms: a window that a leaked writer holds open
    # must show as seconds, not as 50 ms
    di.scheduling_loop.window_cap = 30.0
    srv = SimulatorServer(di, port=0)
    srv.start(block=False)
    yield di, f"http://127.0.0.1:{srv.port}"
    srv.shutdown()
    CONTROLS.reset()


def _post(base: str, path: str, data: bytes) -> int:
    req = urllib.request.Request(
        base + path, data=data, method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            r.read()
            return r.status
    except urllib.error.HTTPError as e:
        e.read()
        return e.code


def _shed(di, base, pod):
    CONTROLS.set_shed(DEFAULT_SESSION, True, 7)
    try:
        return _post(base, "/api/v1/pods", json.dumps(pod).encode())
    finally:
        CONTROLS.set_shed(DEFAULT_SESSION, False)


def _conflict(di, base, pod):
    body = json.dumps(pod).encode()
    assert _post(base, "/api/v1/pods", body) == 201
    return _post(base, "/api/v1/pods", body)


def _bad_json(di, base, pod):
    return _post(base, "/api/v1/pods", b"{not json")


def _bad_import(di, base, pod):
    return _post(base, "/api/v1/import", b'{"pods": 7}')


@pytest.mark.parametrize("refused, status", [
    (_shed, 429), (_conflict, 409), (_bad_json, 400), (_bad_import, 500)],
    ids=["shed_429", "conflict_409", "bad_json_400", "import_500"])
def test_a_refused_post_leaves_no_writer_in_flight(served, refused, status):
    di, base = served
    TRACER.reset()
    loop = di.scheduling_loop
    assert _post(base, "/api/v1/nodes",
                 json.dumps(make_nodes(1, seed=79)[0]).encode()) == 201
    first, second = make_pods(2, seed=80)
    assert refused(di, base, copy.deepcopy(first)) == status
    # the handler leaves writer_in_flight() after the response is on the
    # wire: the client can be back here a moment before it has
    _wait(lambda: loop._in_flight == 0, "the refused writer never left",
          timeout=5.0)
    # ... so the next pod's window closes settled, not at the 30 s cap
    assert _post(base, "/api/v1/pods", json.dumps(second).encode()) == 201
    _wait(lambda: _bound(di.store, [second]), "the loop never bound the pod")
    c = TRACER.counter_totals()
    assert loop._in_flight == 0
    assert "cap" not in _closed() and _closed()["settled"] >= 1
    assert c["queue_wait_oldest_seconds_total"] < 5.0
