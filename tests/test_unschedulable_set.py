"""The scheduling loop's unschedulable set (framework/unschedulable.py,
server/di.py): a pod a pass marked Unschedulable is parked, is in no later
pass, and comes back when a cluster event has moved it and its backoff has
run out, or on the 5-minute flush.  The set by itself runs on a clock the
test turns; the loop tests run the real thing with a short backoff."""

from __future__ import annotations

import time

import pytest

from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.framework.unschedulable import (
    FLUSH_AFTER_S, UnschedulablePods)
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.scheduler.service import SchedulerService
from kube_scheduler_simulator_tpu.server.di import SchedulingLoop
from kube_scheduler_simulator_tpu.utils.tracing import TRACER


class Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self) -> float:
        return self.t


def _node(name: str, cpu: str = "4") -> dict:
    return {"metadata": {"name": name},
            "status": {"allocatable": {"cpu": cpu, "memory": "32Gi",
                                       "pods": "110"}}}


def _pod(name: str, cpu: str = "100m", prio: int = 0, uid: str | None = None,
         node: str | None = None) -> dict:
    p = {"metadata": {"name": name, "namespace": "default"},
         "spec": {"priority": prio, "containers": [{
             "name": "c", "resources": {"requests": {"cpu": cpu,
                                                     "memory": "500Mi"}}}]}}
    if uid:
        p["metadata"]["uid"] = uid
    if node:
        p["spec"]["nodeName"] = node
    return p


def _requeued() -> dict:
    return TRACER.labeled_totals("pods_requeued_total", "reason")


# ---- the set on a turned clock --------------------------------------------

def test_parked_until_an_event_and_the_backoff():
    clock = Clock()
    q = UnschedulablePods(clock)
    TRACER.reset()
    q.park(_pod("p", uid="u1"), 1.0, 10.0, seq_at_start=q.move_seq)
    assert q.parked_uids() == {("default", "p"): "u1"} and len(q) == 1
    assert q.due_in() == pytest.approx(FLUSH_AFTER_S)
    clock.t += 0.25
    assert not q.note("services", "ADDED", {})           # no moving kind
    assert not q.note("nodes", "DELETED", _node("n9"))   # frees nothing
    assert q.note("nodes", "ADDED", _node("n9"))
    assert q.due_in() == pytest.approx(0.75)  # moved: due when the backoff ends
    assert q.release_due() == 0
    clock.t += 0.75
    assert q.release_due() == 1 and len(q) == 0
    assert _requeued() == {"backoff": 1}
    assert TRACER.counter_totals()["pods_unschedulable_parked_total"] == 1


def test_an_event_after_the_backoff_requeues_at_once():
    clock = Clock()
    q = UnschedulablePods(clock)
    TRACER.reset()
    q.park(_pod("p"), 1.0, 10.0, seq_at_start=q.move_seq)
    clock.t += 5
    assert q.release_due() == 0  # backoff over, but nothing has changed
    assert q.note("persistentvolumeclaims", "MODIFIED", {})
    assert q.due_in() <= 0 and q.release_due() == 1
    assert _requeued() == {"event": 1}


def test_the_backoff_doubles_per_attempt_up_to_the_maximum():
    clock = Clock()
    q = UnschedulablePods(clock)
    waits = []
    for _ in range(6):
        q.park(_pod("p"), 1.0, 10.0, seq_at_start=q.move_seq)
        q.note("nodes", "MODIFIED", _node("n0"))
        waits.append(q.due_in())
        clock.t += waits[-1]
        assert q.release_due() == 1
    assert waits == pytest.approx([1, 2, 4, 8, 10, 10])
    # a pod that went away and came back under the same name starts anew
    q.note("pods", "DELETED", _pod("p"))
    q.park(_pod("p"), 1.0, 10.0, seq_at_start=q.move_seq)
    q.note("nodes", "ADDED", _node("n1"))
    assert q.due_in() == pytest.approx(1)


def test_the_flush_brings_a_pod_back_that_nothing_moved():
    clock = Clock()
    q = UnschedulablePods(clock)
    TRACER.reset()
    q.park(_pod("p"), 1.0, 10.0, seq_at_start=q.move_seq)
    clock.t += FLUSH_AFTER_S - 1
    assert q.release_due() == 0
    clock.t += 1
    assert q.release_due() == 1
    assert _requeued() == {"flush": 1}


def test_an_event_during_the_pass_parks_the_pod_as_moved():
    """upstream's moveRequestCycle: the node that arrived while the pass
    ran was not in its cluster."""
    clock = Clock()
    q = UnschedulablePods(clock)
    seq = q.move_seq
    q.park(_pod("other"), 1.0, 10.0, seq_at_start=seq)
    q.note("nodes", "ADDED", _node("late"))  # lands mid-pass
    q.park(_pod("p"), 1.0, 10.0, seq_at_start=seq)
    assert q.due_in() == pytest.approx(1)
    clock.t += 1
    assert q.release_due() == 2


def test_pod_events_own_update_scheduler_writes_delete_and_bind():
    clock = Clock()
    q = UnschedulablePods(clock)
    pod = _pod("p", uid="u1")
    q.park(pod, 1.0, 10.0, seq_at_start=q.move_seq)
    clock.t += 2
    # what the scheduler writes is no reason to try again
    marked = dict(pod, status={"conditions": [{
        "type": "PodScheduled", "reason": "Unschedulable"}]})
    marked["metadata"] = dict(pod["metadata"], annotations={
        "kube-scheduler-simulator.sigs.k8s.io/filter-result": "{}"})
    assert not q.note("pods", "MODIFIED", marked) and q.release_due() == 0
    # a bound pod deleted frees room: every parked pod moves
    assert q.note("pods", "DELETED", _pod("victim", node="n0"))
    assert q.release_due() == 1
    # the owner's change of the pod's own spec moves that pod
    q.park(pod, 1.0, 10.0, seq_at_start=q.move_seq)
    clock.t += 5
    smaller = _pod("p", cpu="50m", uid="u1")
    assert q.note("pods", "MODIFIED", smaller) and q.release_due() == 1
    # deleted, bound elsewhere, or replaced by another pod of the name
    for event, obj in (("DELETED", pod), ("MODIFIED", _pod("p", uid="u1", node="n0")),
                       ("ADDED", _pod("p", uid="u2"))):
        q.park(pod, 1.0, 10.0, seq_at_start=q.move_seq)
        q.note("pods", event, obj)
        assert len(q) == 0, event


# ---- the loop and the engine ----------------------------------------------

@pytest.fixture()
def rig():
    made = []

    def build(nodes: int = 2, backoff=(0.3, 0.6)):
        store = ObjectStore()
        for j in range(nodes):
            store.create("nodes", _node(f"n{j}"))
        engine = SchedulerEngine(store, plugin_config=PluginSetConfig(
            enabled=["NodeResourcesFit", "DefaultPreemption"]))
        engine.pod_backoff_s = backoff
        loop = SchedulingLoop(store, engine, window_cap=0.05)
        TRACER.reset()
        loop.start()
        made.append((loop, engine))
        return store, engine, loop

    yield build
    for loop, engine in made:
        loop.stop()
        loop._thread.join(timeout=60)
        engine.close()


def _wait(cond, what: str, timeout: float = 60.0):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            pytest.fail(what)
        time.sleep(0.01)


def _get(store, name):
    return store.get("pods", name, "default")


def _marked(store, name) -> bool:
    return any(c.get("reason") == "Unschedulable" for c in
               (_get(store, name).get("status") or {}).get("conditions") or [])


def _count(name: str) -> float:
    return TRACER.counter_totals().get(name, 0)


def test_parked_after_the_mark_and_absent_from_the_next_pass(rig):
    store, engine, loop = rig()
    store.create("pods", _pod("big", cpu="9", prio=10))
    _wait(lambda: _marked(store, "big") and len(loop.unschedulable) == 1,
          "the pod was never marked and parked")
    assert _count("scheduling_pass_pods_total") == 1
    store.create("pods", _pod("small"))
    _wait(lambda: _get(store, "small")["spec"].get("nodeName"), "small unbound")
    # the parked pod was not taken along: a pass of one, no second attempt
    assert _count("scheduling_work_passes_total") == 2
    assert _count("scheduling_pass_pods_total") == 2
    assert _count("preemption_attempts_total") == 1
    assert _count("pods_unschedulable_parked_total") == 1
    assert len(loop.unschedulable) == 1
    # a direct call is the caller's own queue: it takes every pending pod
    # (once the loop's pass, whose tail runs on after the bind shows, has
    # left queued_by: inside it the engine's queue is the loop's)
    _wait(lambda: engine._queue is None, "the loop's pass never ended")
    assert [p["metadata"]["name"] for p in engine.pending_pods()] == ["big"]


def test_back_on_a_node_add_after_the_backoff(rig):
    store, engine, loop = rig(backoff=(0.5, 1.0))
    store.create("pods", _pod("big", cpu="9", prio=10))
    _wait(lambda: len(loop.unschedulable) == 1, "never parked")
    t0 = time.monotonic()
    store.create("nodes", _node("huge", cpu="16"))  # inside the backoff
    _wait(lambda: _get(store, "big")["spec"].get("nodeName") == "huge",
          "the pod did not come back on the node add")
    assert time.monotonic() - t0 >= 0.3, "the backoff was not waited out"
    assert TRACER.labeled_totals("pods_requeued_total", "reason") == {"backoff": 1}
    assert len(loop.unschedulable) == 0


def test_back_on_the_flush(rig, monkeypatch):
    from kube_scheduler_simulator_tpu.framework import unschedulable as mod

    monkeypatch.setattr(mod, "FLUSH_AFTER_S", 0.4)
    store, engine, loop = rig()
    store.create("pods", _pod("big", cpu="9", prio=10))
    _wait(lambda: _count("pods_unschedulable_parked_total") == 2,
          "the flush never brought the pod back for a second try")
    assert TRACER.labeled_totals("pods_requeued_total", "reason") == {"flush": 1}
    assert _count("scheduling_pass_pods_total") == 2


def test_a_nominated_preemptor_is_still_retried(rig):
    """Preemption nominates a node: the pod keeps its retry wave and binds
    in the same pass; it is never parked."""
    store, engine, loop = rig(nodes=1)
    store.create("pods", _pod("low", cpu="3"))
    _wait(lambda: _get(store, "low")["spec"].get("nodeName"), "low unbound")
    store.create("pods", _pod("high", cpu="3", prio=100))
    _wait(lambda: _get(store, "high")["spec"].get("nodeName") == "n0",
          "the preemptor never bound")
    assert _count("pods_unschedulable_parked_total") == 0
    assert len(loop.unschedulable) == 0
    assert [p["metadata"]["name"] for p in store.list("pods")[0]] == ["high"]


def test_the_posted_configuration_sets_the_backoff():
    store = ObjectStore()
    engine = SchedulerEngine(store)
    svc = SchedulerService(engine)
    assert engine.pod_backoff_s == (1.0, 10.0)
    cfg = svc.get_config()
    cfg["podInitialBackoffSeconds"], cfg["podMaxBackoffSeconds"] = 2, 30
    svc.restart_scheduler(cfg)
    assert engine.pod_backoff_s == (2.0, 30.0)
    svc.reset_scheduler()
    assert engine.pod_backoff_s == (1.0, 10.0)
    engine.close()
