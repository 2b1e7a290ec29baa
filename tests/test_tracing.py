"""Tracing/metrics subsystem: span aggregation, counters, Prometheus
exposition, engine instrumentation, HTTP endpoints."""

import json
import threading
import time
import urllib.request

import pytest

from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods
from kube_scheduler_simulator_tpu.utils import tracing
from kube_scheduler_simulator_tpu.utils.tracing import (
    TRACER, Tracer, validate_exposition)


def test_tracer_spans_and_counters():
    t = Tracer()
    with t.span("phase", pods=3):
        pass
    with t.span("phase"):
        pass
    t.count("things_total", 5)
    s = t.summary()
    assert s["spans"]["phase"]["count"] == 2
    assert s["spans"]["phase"]["total_seconds"] >= 0
    assert s["counters"]["things_total"] == 5
    text = t.prometheus_text()
    assert "kss_tpu_things_total 5" in text
    assert "kss_tpu_span_phase_count 2" in text
    assert t.events()[-1]["name"] == "phase"
    t.reset()
    assert t.summary() == {"spans": {}, "counters": {}}


def test_event_ring_counts_drops():
    """A full span ring must not lose its tail silently: every evicted
    event counts in tracer_events_dropped_total, surfaced by summary()
    and the Prometheus exposition."""
    t = Tracer(capacity=4)
    for _ in range(4):
        with t.span("s"):
            pass
    assert "tracer_events_dropped_total" not in t.summary()["counters"]
    for _ in range(3):
        with t.span("s"):
            pass
    assert t.summary()["counters"]["tracer_events_dropped_total"] == 3
    assert "kss_tpu_tracer_events_dropped_total 3" in t.prometheus_text()
    assert len(t.events(limit=100)) == 4  # the ring itself stays bounded


def test_gauge_session_scope_and_labels():
    """Gauges honor the session scope (mirrored into the per-session
    snapshot view) and accept labels (the HBM sampler's per-device
    series), folding the active session label in like inc() does."""
    from kube_scheduler_simulator_tpu.utils.tracing import validate_exposition

    t = Tracer()
    t.gauge("plain_g", 7)
    with t.session_scope("sa"):
        t.gauge("scoped_g", 3)
        t.gauge("labeled_g", 11, device="0")
    with t.session_scope("sb"):
        t.gauge("scoped_g", 5)
    snap = t.snapshot()
    assert snap["gauges"]["plain_g"] == 7
    assert snap["gauges"]["scoped_g"] == 5  # last write wins aggregate
    assert snap["labeled_gauges"]["labeled_g"] == [
        {"labels": {"device": "0", "session": "sa"}, "value": 11}]
    sa = t.snapshot(session="sa")
    assert sa["gauges"]["scoped_g"] == 3
    assert sa["gauges"]["labeled_g"] == 11
    assert sa["labeled_gauges"]["labeled_g"][0]["value"] == 11
    sb = t.snapshot(session="sb")
    assert sb["gauges"] == {"scoped_g": 5}
    assert "labeled_g" not in sb["labeled_gauges"]
    # one family per gauge name even when plain + labeled series mix
    t.gauge("labeled_g", 20)
    fams = validate_exposition(t.prometheus_text())
    assert fams["kss_tpu_labeled_g"]["type"] == "gauge"
    assert len(fams["kss_tpu_labeled_g"]["samples"]) == 2


def test_open_spans_listed_while_open():
    """What is open is listed while it is open, without the fields the
    watch keeps for itself; the old time_split (six spans' wall seconds
    summed as "host", the replay span called the "device window") is
    gone from the snapshot."""
    t = Tracer()
    with t.span("wave"):
        with t.span("inner"):
            open_now = t.open_spans()
    names = [s["name"] for s in open_now]
    assert names == ["wave", "inner"]
    assert all(s["seconds_so_far"] >= 0 for s in open_now)
    assert set(open_now[0]) == {"name", "span_id", "parent_id", "tid",
                                "seconds_so_far"}
    assert t.open_spans() == []
    snap = t.snapshot()
    assert snap["spans"]["wave"]["count"] == 1
    # one clock a span: no CPU reading rides the event or the aggregate
    assert "cpu_seconds" not in snap["spans"]["wave"]
    assert "cpu" not in t.events()[-1]
    assert "time_split" not in snap
    assert "span_cpu_seconds_total" not in snap["labeled_counters"]
    validate_exposition(t.prometheus_text())


@pytest.mark.parametrize("stand_s, hooked", [(0.0, False), (0.06, True)])
def test_stall_hook_gets_the_spans_that_stood(monkeypatch, stand_s, hooked):
    """span() makes one comparison when it closes: a span that stood
    for STALL_S is handed to the hook, with its event as the ring has
    it, outside the tracer's lock; a stretch timed elsewhere
    (record_span) never is."""
    monkeypatch.setattr(tracing, "STALL_S", 0.05)
    t = Tracer()
    got = []

    def hook(event):
        assert t._lock.acquire(blocking=False), "called under the lock"
        t._lock.release()
        got.append(event)

    t.set_stall_hook(hook)
    with t.session_scope("sa"), t.span("outer"):
        with t.span("standing", pods=3) as sp:
            time.sleep(stand_s)
    t.record_span("elsewhere", time.perf_counter() - 1.0, 1.0)
    if not hooked:
        assert got == []
        return
    [ev] = [e for e in got if e["name"] == "standing"]
    assert ev is t.events()[-3] and ev["span_id"] == sp.id
    assert ev["seconds"] >= stand_s and ev["session"] == "sa"
    assert ev["pods"] == 3
    assert [e["name"] for e in got] == ["standing", "outer"]


def test_open_since_and_open_chain():
    """The watch's view of what is open: only spans of an age, with the
    thread and the perf_counter start the public list leaves out; and a
    closed span's ancestors by name, nearest first."""
    t = Tracer()
    with t.span("loop_pass"):
        with t.span("wave") as wave:
            time.sleep(0.03)
            with t.span("young") as young:
                old = t.open_since(0.02)
                assert [s["name"] for s in old] == ["loop_pass", "wave"]
                assert old[1]["ident"] == threading.get_ident()
                assert old[1]["t0_perf"] <= time.perf_counter() - 0.02
                assert t.open_since(60.0) == []
                assert t.open_chain(young.parent_id) == ["wave", "loop_pass"]
            assert t.open_chain(young.id) == []  # closed: in no chain
    assert t.open_chain(wave.id) == [] and t.open_chain(None) == []


def test_long_spans_are_held_past_the_ring():
    """Spans of LONG_S and more are kept a second time: subtree_events
    still returns them after short spans have rolled the main ring
    over, each held event once, and says that the ring rolled."""
    t = Tracer(capacity=64)
    with t.span("first_pass") as top:
        since = round(time.perf_counter() - t._perf_epoch, 6) - 1e-3
        with t.span("tiny"):
            pass
        with t.span("slow_build"):
            time.sleep(2 * tracing.LONG_S)
        held, rolled = t.subtree_events(since)
        assert not rolled
        assert sorted(e["name"] for e in held) == ["slow_build", "tiny"]
        for _ in range(70):
            with t.span("pump", parent=0):
                pass
        held, rolled = t.subtree_events(since)
    assert rolled
    names = [e["name"] for e in held]
    assert names.count("slow_build") == 1 and "tiny" not in names
    assert [e for e in held if e["name"] == "slow_build"][0][
        "parent_id"] == top.id
    t.reset()
    assert t.subtree_events(0.0) == ([], False)


def test_first_spans_of_two_threads_take_two_tids(monkeypatch):
    """With the black box off a thread's first _tid() call is the one
    at its first span's close: it is made under the tracer's lock, so
    threads that close their first spans together get ids of their
    own."""
    monkeypatch.setattr(tracing, "BLACKBOX_OPEN_SPANS", False)
    t = Tracer()
    go = threading.Barrier(8)

    def first_span():
        go.wait()
        with t.span("first"):
            pass

    threads = [threading.Thread(target=first_span) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert sorted(e["tid"] for e in t.events()) == list(range(1, 9))


def test_engine_emits_spans_and_counts():
    TRACER.reset()
    store = ObjectStore()
    engine = SchedulerEngine(store)
    for n in make_nodes(2, seed=70):
        store.create("nodes", n)
    for p in make_pods(3, seed=71):
        store.create("pods", p)
    engine.schedule_pending()
    s = TRACER.summary()
    for span in ("compile_workload", "replay_and_decode_stream",
                 "commit_and_reflect"):
        assert s["spans"][span]["count"] >= 1, span
    assert s["counters"]["pods_scheduled_total"] == 3
    assert s["counters"]["scheduling_waves_total"] >= 1


def test_metrics_http_endpoints():
    from kube_scheduler_simulator_tpu.config.config import SimulatorConfiguration
    from kube_scheduler_simulator_tpu.server.di import DIContainer
    from kube_scheduler_simulator_tpu.server.server import SimulatorServer

    di = DIContainer(SimulatorConfiguration(port=0), start_scheduler=False)
    srv = SimulatorServer(di, port=0)
    srv.start(block=False)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(base + "/api/v1/metrics", timeout=10) as r:
            s = json.load(r)
            assert "spans" in s and "counters" in s
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            r.read()
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            assert r.status == 200
        # scheduler loop not started -> not ready
        try:
            urllib.request.urlopen(base + "/readyz", timeout=10)
            assert False, "expected 503"
        except urllib.error.HTTPError as e:
            assert e.code == 503
        req = urllib.request.Request(
            base + "/api/v1/profile", data=json.dumps({"action": "nope"}).encode(),
            method="POST", headers={"Content-Type": "application/json"})
        try:
            urllib.request.urlopen(req, timeout=10)
            assert False, "expected 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400
    finally:
        srv.shutdown()
