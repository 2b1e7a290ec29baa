"""The decision's way out (docs/metrics.md): the engine's commit stamp,
the watch stream's `decision_delivery`, the first read's
`decision_to_read`, and the one trace id from `http_pod_create` to
`http_pod_read`.  Served over HTTP on the CPU backend, 6 nodes."""

from __future__ import annotations

import json
import socket
import threading
import time
import urllib.request

import pytest

from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods
from kube_scheduler_simulator_tpu.services.resourcewatcher import (
    DecisionStamps, StreamWriter)
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

CYCLE_SPANS = ("http_pod_create", "wave", "decision_delivery",
               "decision_to_read", "http_pod_read")


@pytest.fixture(scope="module")
def served():
    from kube_scheduler_simulator_tpu.config.config import (
        SimulatorConfiguration)
    from kube_scheduler_simulator_tpu.server.di import DIContainer
    from kube_scheduler_simulator_tpu.server.server import SimulatorServer

    di = DIContainer(SimulatorConfiguration(port=0), start_scheduler=True)
    srv = SimulatorServer(di, port=0)
    srv.start(block=False)
    base = f"http://127.0.0.1:{srv.port}"
    _http(base, "POST", "/api/v1/import",
          {"nodes": make_nodes(6, seed=71), "pods": []})
    # the first pass compiles for seconds and may trip the autopilot's
    # 2 s target: pay it here, and let a shed lapse before the tests
    with Watch(srv.port) as w:
        _cycle(base, w, _pod("warm-0"))
    yield di, base, srv.port
    srv.shutdown()


def _http(base, method, path, body=None, headers=None, patience=60.0):
    """One request; a 429 (the autopilot sheds after a slow pass) is
    retried as the API asks."""
    deadline = time.time() + patience
    while True:
        req = urllib.request.Request(
            base + path, method=method,
            data=json.dumps(body).encode() if body is not None else None,
            headers={"Content-Type": "application/json", **(headers or {})})
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                raw = r.read()
                return r.status, json.loads(raw) if raw else None
        except urllib.error.HTTPError as e:
            if e.code != 429 or time.time() > deadline:
                raise
            time.sleep(0.25)


def _pod(name, cpu="100m"):
    pod = make_pods(1, seed=72)[0]
    pod["metadata"] = {"name": name, "namespace": "default"}
    pod["spec"]["containers"][0]["resources"] = {
        "requests": {"cpu": cpu, "memory": "64Mi"}}
    return pod


def _decision(obj):
    if (obj.get("spec") or {}).get("nodeName"):
        return obj["spec"]["nodeName"]
    for c in (obj.get("status") or {}).get("conditions") or ():
        if c.get("reason") == "Unschedulable":
            return ""
    return None


class Watch:
    """GET /api/v1/listwatchresources held open on a raw socket; the
    events parsed as they come (one HTTP chunk each)."""

    def __init__(self, port):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=120)
        self.sock.sendall(b"GET /api/v1/listwatchresources HTTP/1.1\r\n"
                          b"Host: 127.0.0.1\r\n\r\n")
        self.f = self.sock.makefile("rb")
        assert b" 200 " in self.f.readline()
        while self.f.readline() not in (b"\r\n", b""):
            pass
        self.cond = threading.Condition()
        self.decided: dict[str, str] = {}
        self._t = threading.Thread(target=self._read, daemon=True)
        self._t.start()

    def _read(self):
        try:
            while True:
                size = int(self.f.readline().strip() or b"0", 16)
                if not size:
                    return
                ev = json.loads(self.f.read(size))
                self.f.read(2)
                if ev["kind"] != "Pod" or ev["eventType"] == "DELETED":
                    continue
                node = _decision(ev["obj"])
                if node is not None:
                    with self.cond:
                        self.decided.setdefault(
                            ev["obj"]["metadata"]["name"], node)
                        self.cond.notify_all()
        except (OSError, ValueError):
            pass

    def wait(self, name, timeout=120.0):
        with self.cond:
            assert self.cond.wait_for(lambda: name in self.decided, timeout), \
                f"no decision for {name} on the stream"
            return self.decided[name]

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self.sock.close()
        self._t.join(timeout=5)


def _cycle(base, watch, pod, trace_id=None):
    """POST, wait for the decision on the stream, GET in full: the
    benchmark's interactive cycle.  -> (t0, t1) on perf_counter, which
    is the tracer's clock (the server runs in this process)."""
    name = pod["metadata"]["name"]
    t0 = time.perf_counter()
    _http(base, "POST", "/api/v1/pods", pod,
          headers={"X-KSS-Trace-Id": trace_id} if trace_id else None)
    watch.wait(name)
    _http(base, "GET", f"/api/v1/pods/default/{name}")
    return t0, time.perf_counter()


LAST_IN = ("wave", "http_pod_read", "decision_delivery", "decision_to_read")


def _trace(base, trace_id):
    """The spans under one trace id, in time order.  A request's span
    closes after its last byte is written, the delivery is noted by the
    pump thread after its write returns, and the wave's tail runs on
    after the commit, so the client can be back before any of them is
    in the ring: ask until they are."""
    deadline = time.time() + 10
    while True:
        _, doc = _http(base, "GET", f"/api/v1/trace?trace_id={trace_id}")
        xs = sorted((e for e in doc["traceEvents"] if e["ph"] == "X"),
                    key=lambda e: e["ts"])
        names = {e["name"] for e in xs}
        if set(LAST_IN) <= names or time.time() > deadline:
            return xs
        time.sleep(0.01)


def _count(name, at_least=0):
    """A span's count in the aggregates, once it has reached `at_least`
    (the pump thread notes a delivery after the client has seen it)."""
    deadline = time.time() + 10
    while True:
        n = TRACER.snapshot()["spans"].get(name, {}).get("count", 0)
        if n >= at_least or time.time() > deadline:
            return n
        time.sleep(0.01)


def _ring(names, trace_id=None):
    return [e for e in TRACER.events(4096) if e["name"] in names
            and (trace_id is None or e.get("trace_id") == trace_id)]


def test_one_trace_id_from_create_to_read(served):
    di, base, port = served
    before = TRACER.counter_totals().get("queue_wait_oldest_seconds_total", 0)
    with Watch(port) as w:
        t0, t1 = _cycle(base, w, _pod("traced-0"), trace_id="t-way-out-0")
    queue_wait = TRACER.counter_totals()[
        "queue_wait_oldest_seconds_total"] - before
    xs = _trace(base, "t-way-out-0")
    names = [e["name"] for e in xs]
    # one cycle's spans under the POST's id, in time order
    firsts = [names.index(n) for n in CYCLE_SPANS]
    assert firsts == sorted(firsts), names
    assert names.count("decision_delivery") == 1
    assert names.count("decision_to_read") == 1
    by = {n: next(e for e in xs if e["name"] == n) for n in CYCLE_SPANS}
    # the decision's own drain, encode and send carry the id and lie
    # inside decision_delivery, which closes as the send returns
    dd = by["decision_delivery"]
    dd_end = dd["ts"] + dd["dur"]
    for child in ("watch_flush", "watch_write", "watch_encode", "watch_send"):
        inside = [e for e in xs if e["name"] == child
                  and dd["ts"] <= e["ts"] <= dd_end]
        assert inside, (child, dd, names)
    sends = [e for e in xs if e["name"] == "watch_send"]
    assert any(abs(e["ts"] + e["dur"] - dd_end) <= 50 for e in sends), \
        (dd, sends)
    assert {"http_encode", "http_send"} <= set(names)
    # the delivery ends where the turn-around starts; the turn-around
    # ends where the read starts
    dr, rd = by["decision_to_read"], by["http_pod_read"]
    assert abs(dd["ts"] + dd["dur"] - dr["ts"]) <= 2
    assert dr["ts"] + dr["dur"] <= rd["ts"] + 2
    # what the program's clocks account for starts inside the cycle on
    # the client's clock (this process's perf_counter is the tracer's)
    # and covers most of it.  Clipped where stretches run beside each
    # other or past the client: the queue wait begins while the create's
    # response is still being written, the delivery at the commit, before
    # the wave's tail, which can outlast so short a cycle; and a
    # request's span closes after the client has its last byte.
    ring = {e["name"]: e for e in _ring(CYCLE_SPANS, "t-way-out-0")}
    lo, hi = t0 - TRACER._perf_epoch, t1 - TRACER._perf_epoch
    wave = ring["wave"]
    ivals = [(wave["ts"] - queue_wait, wave["ts"])] + [
        (ring[n]["ts"], ring[n]["ts"] + ring[n]["seconds"])
        for n in CYCLE_SPANS]
    assert all(lo - 1e-5 <= a <= hi for a, _ in ivals), (lo, hi, ivals)
    for n in ("decision_delivery", "decision_to_read"):  # before the GET
        assert ring[n]["ts"] + ring[n]["seconds"] <= hi + 1e-5, (n, hi, ring[n])
    covered, upto = 0.0, lo
    for a, b in sorted(ivals):
        covered += max(min(b, hi) - max(a, upto), 0.0)
        upto = max(upto, min(b, hi))
    assert 0.5 * (t1 - t0) <= covered <= t1 - t0 + 1e-5, (covered, t1 - t0)
    assert wave["ts"] <= ring["decision_delivery"]["ts"] \
        <= wave["ts"] + wave["seconds"]
    assert not len(di.decisions)  # popped by the read


def test_two_streams_deliver_a_decision_once(served):
    di, base, port = served
    n0, r0 = _count("decision_delivery"), _count("decision_to_read")
    with Watch(port) as w1, Watch(port) as w2:
        for i in range(3):
            _cycle(base, w1, _pod(f"twice-{i}"))
            w2.wait(f"twice-{i}")
    assert _count("decision_delivery", n0 + 3) == n0 + 3
    assert _count("decision_to_read", r0 + 3) == r0 + 3
    time.sleep(0.1)  # a second delivery would have come by now
    assert _count("decision_delivery") == n0 + 3
    assert not len(di.decisions)


def test_an_unschedulable_mark_is_a_decision(served):
    di, base, port = served
    n0 = _count("decision_delivery")
    with Watch(port) as w:
        _http(base, "POST", "/api/v1/pods", _pod("too-big", cpu="4000"),
              headers={"X-KSS-Trace-Id": "t-way-out-mark"})
        assert w.wait("too-big") == ""
        _http(base, "GET", "/api/v1/pods/default/too-big")
    assert _count("decision_delivery", n0 + 1) == n0 + 1
    names = [e["name"] for e in _trace(base, "t-way-out-mark")]
    assert "decision_delivery" in names and "decision_to_read" in names
    # ... and a DELETE forgets a decision nobody read
    with Watch(port) as w:
        _http(base, "POST", "/api/v1/pods", _pod("too-big-2", cpu="4000"))
        w.wait("too-big-2")
    assert ("default", "too-big-2") in di.decisions._ents
    for name in ("too-big", "too-big-2"):
        _http(base, "DELETE", f"/api/v1/pods/default/{name}")
    deadline = time.time() + 10
    while len(di.decisions) and time.time() < deadline:
        time.sleep(0.01)  # the loop's watch thread sees the DELETED event
    assert not len(di.decisions)


def test_watch_write_is_its_two_children(served):
    _, base, port = served
    s0 = TRACER.snapshot()["spans"]

    def grown(name):
        s1 = TRACER.snapshot()["spans"]
        return (s1[name]["total_seconds"]
                - s0.get(name, {}).get("total_seconds", 0.0))

    with Watch(port) as w:
        # events of the size the benchmark's are (megabytes of result
        # annotations at 5,000 nodes): beside them the two child spans'
        # own bookkeeping, ~10 us each, is nothing
        for i in range(3):
            pod = _pod(f"split-{i}")
            pod["metadata"]["annotations"] = {"pad": "x" * 2_000_000}
            _cycle(base, w, pod)
    assert grown("watch_encode") + grown("watch_send") \
        >= 0.95 * grown("watch_write")
    assert grown("watch_encode") + grown("watch_send") <= grown("watch_write")


def test_a_decode_for_the_watch_sits_under_watch_flush(served):
    """With a stream open the pump's drain runs the pod's deferred
    decode before the decision leaves: the decode_lazy span it causes has
    watch_flush for its parent, on the pump's thread, under the wave's
    trace id."""
    _, base, port = served
    seen = {e["span_id"] for e in TRACER.events(4096)}
    with Watch(port) as w:
        for i in range(4):
            _cycle(base, w, _pod(f"lazy-{i}"), trace_id=f"t-way-out-lazy-{i}")
    evs = [e for e in TRACER.events(4096) if e["span_id"] not in seen]
    by_id = {e["span_id"]: e for e in evs}
    decodes = [e for e in evs if e["name"] == "decode_lazy"]
    assert decodes, "no deferred decode ran: the served path changed"
    for d in decodes:
        # (parent None: the stream's four-a-second drain of what a wave
        # still in flight made the per-event drain skip; it has no span)
        parent = by_id.get(d["parent_id"])
        if parent is None:
            continue
        assert parent["name"] in ("watch_flush", "http_pod_read"), (d, parent)
        assert d["tid"] == parent["tid"]
        assert d.get("trace_id") == parent.get("trace_id")
    assert any(by_id[d["parent_id"]]["name"] in ("watch_flush",
                                                 "http_pod_read")
               for d in decodes if d["parent_id"] in by_id)


def test_the_write_back_is_a_span_beside_the_decode(served):
    """The stretch between the decode's end and the encode's start has a
    span of its own (PR 45): `reflect_write_back`, a sibling of
    `decode_lazy` under whichever reader drained the pod's record, on
    that reader's thread, after the decode and before its parent's
    encode."""
    _, base, port = served
    seen = {e["span_id"] for e in TRACER.events(4096)}
    with Watch(port) as w:
        for i in range(4):
            _cycle(base, w, _pod(f"wb-{i}"), trace_id=f"t-way-out-wb-{i}")
    evs = [e for e in TRACER.events(4096) if e["span_id"] not in seen]
    by_id = {e["span_id"]: e for e in evs}
    backs = [e for e in evs if e["name"] == "reflect_write_back"]
    # (a record the stream's four-a-second drain took went through the
    # batched write and has no such span)
    assert backs, "no decided pod's read wrote anything back"
    under = set()
    for b in backs:
        parent = by_id.get(b["parent_id"])
        if parent is None:  # the stream's four-a-second drain: no span
            continue
        under.add(parent["name"])
        assert parent["name"] in ("watch_flush", "http_pod_read"), (b, parent)
        assert b["tid"] == parent["tid"]
        kids = [e for e in evs if e["parent_id"] == parent["span_id"]]
        for d in kids:
            if d["name"] == "decode_lazy":
                assert d["ts"] + d["seconds"] <= b["ts"] + 1e-4, (d, b)
            if d["name"] in ("http_encode", "watch_encode"):
                assert b["ts"] + b["seconds"] <= d["ts"] + 1e-4, (b, d)
    assert under, "no write-back ran under a reader's span"
    # the four children of the read and of the pump's write are where
    # they were
    for child, parent in (("http_encode", "http_pod_read"),
                          ("http_send", "http_pod_read"),
                          ("watch_encode", "watch_write"),
                          ("watch_send", "watch_write")):
        assert parent in {by_id[e["parent_id"]]["name"] for e in evs
                          if e["name"] == child and e["parent_id"] in by_id}


# ------------------------------------------------- the map, on its own

def _bound(name, node="n0"):
    return {"metadata": {"name": name, "namespace": "default"},
            "spec": {"nodeName": node}}


def test_the_map_stays_under_its_cap_with_no_reader():
    stamps = DecisionStamps(cap=8)
    for i in range(40):
        stamps.stamp([("default", f"p{i}")])
    assert len(stamps) == 8
    assert set(stamps._ents) == {("default", f"p{i}") for i in range(32, 40)}
    # a delivered entry nobody reads is capped like any other
    sink = StreamWriter(lambda data: None, decisions=stamps)
    assert sink.send("Pod", "MODIFIED", _bound("p39"))
    stamps.stamp([("default", f"q{i}") for i in range(20)])
    assert len(stamps) == 8 and ("default", "p39") not in stamps._ents


def test_only_an_event_that_carries_the_decision_closes_it():
    TRACER.reset()
    stamps = DecisionStamps()
    sink = StreamWriter(lambda data: None, decisions=stamps)
    with TRACER.trace_scope("t-unit"):
        stamps.stamp([("default", "a")])
    pending = {"metadata": {"name": "a", "namespace": "default"}, "spec": {}}
    assert sink.send("Pod", "ADDED", pending)  # still in a queue: not it
    assert "decision_delivery" not in TRACER.snapshot()["spans"]
    with TRACER.session_scope("s"):  # as a session's pump thread is
        assert sink.send("Pod", "MODIFIED", _bound("a"))
        assert sink.send("Pod", "MODIFIED", _bound("a"))  # the reflect's
    spans = TRACER.snapshot()["spans"]
    assert spans["decision_delivery"]["count"] == 1
    assert TRACER.snapshot(session="s")["spans"][
        "decision_delivery"]["count"] == 1
    ev = [e for e in TRACER.events() if e["name"] == "decision_delivery"][0]
    assert ev["trace_id"] == "t-unit" and ev["session"] == "s"
    # a read with no stream: the entry goes, no turn-around is recorded
    stamps.stamp([("default", "b")])
    assert stamps.first_read("default", "b", time.perf_counter()) is None
    assert stamps.first_read("default", "a", time.perf_counter()) == "t-unit"
    assert not len(stamps)
    assert TRACER.snapshot()["spans"]["decision_to_read"]["count"] == 1
    # a broken stream delivers nothing
    stamps.stamp([("default", "c")])

    def broken(data):
        raise BrokenPipeError

    assert not StreamWriter(broken, decisions=stamps).send(
        "Pod", "MODIFIED", _bound("c"))
    assert stamps._ents[("default", "c")].t_delivered is None
    # ... and a client that is back before the pump thread has said so
    # still gets both stretches, the turn-around clipped at nothing
    d = stamps.undelivered(_bound("c"))
    assert stamps.first_read("default", "c", time.perf_counter()) is None
    stamps.delivered(d, time.perf_counter())
    stamps.delivered(d, time.perf_counter())  # a second stream: too late
    spans = TRACER.snapshot()["spans"]
    assert spans["decision_delivery"]["count"] == 2
    assert spans["decision_to_read"]["count"] == 2
    assert not len(stamps)
