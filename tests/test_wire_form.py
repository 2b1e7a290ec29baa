"""The result's wire form is made once (utils/wireform.py, PR 45).

A heavy annotation value's JSON-escaped bytes are made where the value is
rendered (the native codec, beside the blob) or, for what comes without,
at the reflector's write-back; the pod's GET and its event on every
watch stream splice them.  The bytes on the wire are json.dumps's own,
always.  Served over HTTP on the CPU backend, waves run by hand; the
threshold is lowered so that a dozen nodes' entries are heavy."""

from __future__ import annotations

import json
import os
import queue
import socket
import threading
import time
import urllib.request

import pytest

from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
from kube_scheduler_simulator_tpu.framework.replay import replay
from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods
from kube_scheduler_simulator_tpu.native import get_lib
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.services.resourcewatcher import StreamWriter
from kube_scheduler_simulator_tpu.state.compile import compile_workload
from kube_scheduler_simulator_tpu.store.decode import (
    decode_chunk_into, decode_pod_result)
from kube_scheduler_simulator_tpu.store.lazy import LazyWave
from kube_scheduler_simulator_tpu.store import reflector
from kube_scheduler_simulator_tpu.store.reflector import LazyReflections
from kube_scheduler_simulator_tpu.utils import wireform
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

from test_chunk_decode import _localize_ndarrays

native = pytest.mark.skipif(get_lib() is None, reason="no native toolchain")

HEAVY = 400  # the tests' threshold: a dozen nodes' entries, no status map


@pytest.fixture(autouse=True)
def forms(monkeypatch):
    """A registry of the test's own and a threshold a small cluster
    reaches; both are read where they are used, so the server thread and
    the codec see them."""
    reg = wireform.WireForms()
    monkeypatch.setattr(wireform, "WIRE_FORMS", reg)
    monkeypatch.setattr(wireform, "WIRE_MIN_LEN", HEAVY)
    # as at 5,000 nodes, where a record alone is past the annotation
    # limit: no result-history, the heavy values are the three blobs
    monkeypatch.setattr(reflector, "RESULT_HISTORY_LIMIT", HEAVY)
    return reg


@pytest.fixture(scope="module")
def served():
    """A server whose waves the test runs itself: nothing is decided
    behind its back."""
    from kube_scheduler_simulator_tpu.config.config import (
        SimulatorConfiguration)
    from kube_scheduler_simulator_tpu.server.di import DIContainer
    from kube_scheduler_simulator_tpu.server.server import SimulatorServer

    di = DIContainer(SimulatorConfiguration(port=0), start_scheduler=False)
    srv = SimulatorServer(di, port=0)
    srv.start(block=False)
    for node in make_nodes(12, seed=81):
        di.store.create("nodes", node)
    yield di, srv.port
    srv.shutdown()


@pytest.fixture(autouse=True)
def no_pod_left_behind(request):
    """A wave takes every pending pod: each test starts on none."""
    yield
    if "served" in request.fixturenames:
        di, _ = request.getfixturevalue("served")
        for pod in di.store.list("pods", copy_objects=False)[0]:
            di.store.delete("pods", pod["metadata"]["name"],
                            pod["metadata"]["namespace"])


def _raw(port, method, path, body=None, patience=60.0) -> bytes:
    """One request's body as it came; a 429 (the autopilot sheds
    workload POSTs after a pass that compiled for seconds) is retried
    as the API asks."""
    deadline = time.time() + patience
    while True:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", method=method,
            data=json.dumps(body).encode() if body is not None else None,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                raw = r.read()
                assert int(r.headers["Content-Length"]) == len(raw)
                return raw
        except urllib.error.HTTPError as e:
            if e.code != 429 or time.time() > deadline:
                raise
            time.sleep(0.25)


def _pod(name, cpu="100m"):
    pod = make_pods(1, seed=82)[0]
    pod["metadata"] = {"name": name, "namespace": "default"}
    pod["spec"]["containers"][0]["resources"] = {
        "requests": {"cpu": cpu, "memory": "64Mi"}}
    return pod


def _counts() -> dict:
    """The four series of the two body counters, the forms made and the
    forms evicted."""
    out = {}
    for how in ("spliced", "full"):
        for consumer, v in TRACER.labeled_totals(
                f"pod_bodies_{how}_total", "consumer").items():
            out[how, consumer] = v
    for origin, v in TRACER.labeled_totals(
            "wire_forms_made_total", "origin").items():
        out["made", origin] = v
    for state, v in TRACER.labeled_totals(
            "wire_forms_evicted_total", "state").items():
        out["evicted", state] = v
    return out


def _grown(before: dict) -> dict:
    after = _counts()
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


def _old_event(kind, event_type, obj) -> bytes:
    return json.dumps({"kind": kind, "eventType": event_type,
                       "obj": obj}).encode()


def _sent(obj, event_type="MODIFIED") -> bytes:
    """The bytes one stream puts on its socket for obj's event."""
    out: list[bytes] = []
    assert StreamWriter(out.append).send("Pod", event_type, obj)
    return b"".join(out)


# --- (a) the bytes are json.dumps's, whichever rung rendered the values ---

def _nodes(content):
    nodes = make_nodes(12, seed=83, taint_fraction=0.5)
    if content == "plain":
        return nodes
    # node names (keys of all three blobs) and taints (TaintToleration's
    # message carries key and value) that json.dumps has to escape
    marks = {"escapes": ['qu"ote', "back\\slash", "ta\tb", "del\x7fete",
                         "new\nline", "<html&>"],
             "non_ascii": ['nœud-"é"', "ノード",
                           "back\\slash", "emoji-\U0001f600"]}[content]
    for node, mark in zip(nodes, marks):
        node["metadata"]["name"] = f"{mark}-{node['metadata']['name']}"
    for node, mark in zip(nodes[::-1], marks):
        node.setdefault("spec", {})["taints"] = [
            {"key": f"k-{mark}", "value": f"v-{mark}", "effect": "NoSchedule"}]
    return nodes


_RR: dict = {}


def _replayed(content):
    if content not in _RR:
        pods = make_pods(5, seed=84, with_affinity=True)
        cw = compile_workload(_nodes(content), pods, PluginSetConfig())
        _RR[content] = replay(cw, chunk=4)
    return _RR[content]


def _render(rung, rr, i, monkeypatch) -> dict:
    if rung == "native_chunk":
        sink: list = [None]
        decode_chunk_into(rr, i, i + 1, sink, base=i)  # a range of one
        return sink[0]
    if rung == "python":
        monkeypatch.setenv("KSS_TPU_DISABLE_NATIVE", "1")
        try:
            return decode_pod_result(rr, i)
        finally:
            monkeypatch.delenv("KSS_TPU_DISABLE_NATIVE")
    return decode_pod_result(rr, i)  # the fused per-pod call


@native
@pytest.mark.parametrize("content", ["plain", "escapes", "non_ascii"])
@pytest.mark.parametrize("rung", ["native_chunk", "fused", "python"])
def test_get_and_event_bytes_are_json_dumps(served, monkeypatch, rung, content):
    """GET body == json.dumps(store.get(...)).encode() and the stream's
    bytes == the old event encoding, for every decoder rung and for
    values json.dumps has to escape; both bodies are SPLICED (the
    counters say so: equality alone would pass through the fall-back).
    Non-ASCII keys: the codec makes no form (its context is not all
    ASCII), the write-back does, by Python's own encoder."""
    di, port = served
    rr = _replayed(content)
    before = _counts()
    # a pod that was scored: all three blobs
    i = max(range(rr.cw.n_pods), key=lambda i: int(rr.feasible_count[i]))
    anns = _render(rung, rr, i, monkeypatch)
    heavy = [v for v in anns.values() if wireform.is_heavy(v)]
    assert len(heavy) == 3, "filter-, score- and finalscore-result"
    if content == "escapes":
        assert any("\\\\" in v and '\\"' in v for v in heavy)
    name = f"bytes-{rung}-{content}".replace("_", "-")
    stored = di.store.create("pods", _pod(name))
    di.reflector.lazy_pending().add(
        "default", name, stored["metadata"]["uid"], [anns])

    raw = _raw(port, "GET", f"/api/v1/pods/default/{name}")
    want = di.store.get("pods", name, "default")
    assert raw == json.dumps(want).encode()
    assert json.loads(raw)["metadata"]["annotations"].items() >= anns.items()
    obj = di.store.get("pods", name, "default", copy_object=False)
    assert _sent(obj) == _old_event("Pod", "MODIFIED", obj)

    origin = ("native" if rung != "python" and content != "non_ascii"
              else "python")  # the write-back makes what came without
    assert _grown(before) == {("made", origin): 1, ("spliced", "read"): 1,
                              ("spliced", "watch"): 1}
    for v in heavy:
        assert bytes(wireform.WIRE_FORMS.get(v)) == json.dumps(v).encode()


def test_what_carries_nothing_heavy_is_encoded_as_ever(served):
    """Every other response takes the fall-through: no parts, no count."""
    di, port = served
    before = _counts()
    for obj in (None, [1, 2], {"items": [{"metadata": {"annotations": {
            "big": "x" * 4 * HEAVY}}}]}, {"metadata": {"annotations": None}},
            {"metadata": {"annotations": {"small": "x", "n": 7}}}):
        assert wireform.body_parts(obj, "read") is None
    name = "nothing-heavy"
    di.store.create("pods", _pod(name))
    assert _raw(port, "GET", f"/api/v1/pods/default/{name}") == json.dumps(
        di.store.get("pods", name, "default")).encode()
    assert _raw(port, "GET", "/api/v1/nodes").startswith(b'{"items": [')
    assert _grown(before) == {}


def test_a_heavy_value_without_a_form_is_escaped_by_the_consumer(served):
    """A value nobody made a form for (a user's own annotation, PUT over
    HTTP) is encoded as today, beside spliced ones, and the body counts
    as full."""
    di, port = served
    name = "half-kept"
    kept, loose = 'k"' * HEAVY, 'l\\' * HEAVY
    pod = _pod(name)
    pod["metadata"]["annotations"] = {"a": kept, "m": "small", "z": loose}
    di.store.create("pods", pod, owned=True)
    wireform.WIRE_FORMS.keep(kept, json.dumps(kept).encode())
    before = _counts()
    obj = di.store.get("pods", name, "default", copy_object=False)
    assert b"".join(wireform.body_parts(obj, "read")) \
        == json.dumps(obj).encode()
    assert _grown(before) == {("full", "read"): 1}


# --- (b) exactly once --------------------------------------------------

class _Stream:
    """GET /api/v1/listwatchresources on a raw socket: the raw bytes of
    every pod event, by pod name."""

    def __init__(self, port):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=60)
        self.sock.sendall(b"GET /api/v1/listwatchresources HTTP/1.1\r\n"
                          b"Host: 127.0.0.1\r\n\r\n")
        self.f = self.sock.makefile("rb")
        assert b" 200 " in self.f.readline()
        while self.f.readline() not in (b"\r\n", b""):
            pass
        self.cond = threading.Condition()
        self.events: list[tuple[str, bytes]] = []
        self._t = threading.Thread(target=self._read, daemon=True)
        self._t.start()

    def _read(self):
        try:
            while True:
                size = int(self.f.readline().strip() or b"0", 16)
                if not size:
                    return
                raw = self.f.read(size)
                self.f.read(2)
                ev = json.loads(raw)
                if ev["kind"] == "Pod":
                    with self.cond:
                        self.events.append(
                            (ev["obj"]["metadata"]["name"], raw))
                        self.cond.notify_all()
        except (OSError, ValueError):
            pass

    def annotated(self, name, timeout=60.0) -> bytes:
        """The raw event that brought `name`'s result annotations."""
        def found():
            return [raw for n, raw in self.events
                    if n == name and b"selected-node" in raw]
        with self.cond:
            assert self.cond.wait_for(found, timeout), \
                f"no result for {name} on the stream"
            return found()[-1]

    def close(self):
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self.sock.close()
        self._t.join(timeout=5)


@native
def test_one_form_a_value_three_spliced_bodies(served, forms):
    """One decided pod, read by a GET and by two watch streams: its
    heavy values get one wire form each (one result's worth, by the
    codec) and all three bodies splice them."""
    di, port = served
    streams = [_Stream(port), _Stream(port)]
    try:
        before, kept = _counts(), len(forms)
        name = "exactly-once"
        _raw(port, "POST", "/api/v1/pods", _pod(name))
        assert di.engine.schedule_pending() == 1
        events = [s.annotated(name) for s in streams]
        raw = _raw(port, "GET", f"/api/v1/pods/default/{name}")
    finally:
        for s in streams:
            s.close()
    pod = json.loads(raw)
    assert pod["spec"]["nodeName"]
    heavy = [v for v in pod["metadata"]["annotations"].values()
             if wireform.is_heavy(v)]
    assert len(heavy) == 3
    assert len(forms) - kept == len(heavy)
    assert _grown(before) == {("made", "native"): 1, ("spliced", "read"): 1,
                              ("spliced", "watch"): 2}
    stored = di.store.get("pods", name, "default")
    assert raw == json.dumps(stored).encode()
    assert events[0] == events[1] == _old_event("Pod", "MODIFIED", stored)


# --- (c) supersession --------------------------------------------------

@native
def test_a_superseded_pod_is_served_with_its_own_bytes(served):
    """The kept bytes belong to the VALUE: a PUT that edits an
    annotation, a second wave's write-back and a recreation under the
    same name are each served as json.dumps would serve them, and never
    with what was kept for the pod before."""
    di, port = served
    name, path = "superseded", "/api/v1/pods/default/superseded"

    def served_now():
        raw = _raw(port, "GET", path)
        stored = di.store.get("pods", name, "default")
        assert raw == json.dumps(stored).encode()
        obj = di.store.get("pods", name, "default", copy_object=False)
        assert _sent(obj) == _old_event("Pod", "MODIFIED", obj)
        return json.loads(raw)

    # wave 1: nowhere to go (the filter-result is heavy all the same)
    _raw(port, "POST", "/api/v1/pods", _pod(name, cpu="9999"))
    assert di.engine.schedule_pending() == 0
    first = served_now()
    anns1 = first["metadata"]["annotations"]
    key = next(k for k, v in anns1.items() if wireform.is_heavy(v))
    assert not first["spec"].get("nodeName")

    # a user's PUT edits the heavy annotation
    edited = json.loads(json.dumps(first))
    edited["metadata"]["annotations"][key] = \
        anns1[key].replace("Insufficient", "EDITED-BY-A-USER")
    assert edited["metadata"]["annotations"][key] != anns1[key]
    _raw(port, "PUT", path, edited)
    second = served_now()
    assert "EDITED-BY-A-USER" in second["metadata"]["annotations"][key]
    assert second["metadata"]["resourceVersion"] \
        != first["metadata"]["resourceVersion"]

    # wave 2: a node large enough appears, the pod is decided again and
    # its write-back replaces the annotations
    big = make_nodes(1, seed=85)[0]
    big["metadata"]["name"] = "big-enough"
    big["metadata"].setdefault("labels", {})["kubernetes.io/hostname"] = \
        "big-enough"
    big["status"]["allocatable"] = dict(
        big["status"]["allocatable"], cpu="20000", memory="4096Gi")
    big["status"]["capacity"] = dict(big["status"]["allocatable"])
    di.store.create("nodes", big)
    try:
        assert di.engine.schedule_pending() == 1
        third = served_now()
        assert third["spec"]["nodeName"] == "big-enough"
        anns3 = third["metadata"]["annotations"]
        assert "EDITED-BY-A-USER" not in anns3[key]
        assert "big-enough" in anns3[key] and anns3[key] != anns1[key]

        # deleted and created again under the name: a pod with no result
        _raw(port, "DELETE", path)
        _raw(port, "POST", "/api/v1/pods", _pod(name))
        fresh = served_now()
        assert not (fresh["metadata"].get("annotations") or {})
        assert fresh["metadata"]["uid"] != third["metadata"]["uid"]
    finally:
        di.store.delete("nodes", "big-enough")


# --- (d) what the registry keeps and what it lets go --------------------

def _write_back(di, name, i):
    """A pod whose three heavy values come without a form: the
    write-back makes them (origin python)."""
    stored = di.store.create("pods", _pod(name))
    anns = {f"result-{k}": f"{i}{k}" * (4 * HEAVY) for k in "abc"}
    di.reflector.lazy_pending().add(
        "default", name, stored["metadata"]["uid"], [anns])
    di.store.materialize_reads("pods", name, "default")


def _event(di, name) -> None:
    """The pod's reflect event as one stream builds it, byte for byte."""
    obj = di.store.get("pods", name, "default", copy_object=False)
    assert _sent(obj) == _old_event("Pod", "MODIFIED", obj)


def test_past_the_cap_the_oldest_go_and_their_reads_are_full(
        served, monkeypatch):
    """More written-back pods than the registry pins at rest: the forms
    an event has SPLICED go, oldest first, and their pods are still
    served byte for byte and count as full; the forms no event has used
    stay, however young the ones that came after, and splice.  Past the
    ceiling for forms nobody uses a write-back makes none, and evicts
    none to make one."""
    di, port = served
    # what one pod pins: its three values and their forms
    _write_back(di, "capped-probe", 9)
    per_pod, per_pod_forms = wireform.WIRE_FORMS.pinned_bytes, 3
    assert len(wireform.WIRE_FORMS) == per_pod_forms
    # at rest two pods and a half; unread, four
    reg = wireform.WireForms(cap_bytes=int(2.5 * per_pod),
                             unread_ceiling_bytes=int(4.1 * per_pod))
    monkeypatch.setattr(wireform, "WIRE_FORMS", reg)
    names = [f"capped-{i}" for i in range(7)]
    before = _counts()
    for i, name in enumerate(names[:3]):
        _write_back(di, name, i)
    for name in names[:2]:
        _event(di, name)  # pods 0 and 1 are on the stream: spliced
    assert reg.unread_bytes == per_pod and reg.pinned_bytes == 3 * per_pod
    for i, name in enumerate(names[3:5], 3):
        _write_back(di, name, i)
    # 5 pods' worth is past the cap: both spliced pods' forms went, and
    # the three unread pods' stay though they alone pass it
    assert len(reg) == 3 * per_pod_forms
    assert reg.unread_bytes == reg.pinned_bytes == 3 * per_pod
    for i, name in enumerate(names[5:], 5):
        _write_back(di, name, i)
    # pod 5 fits under the ceiling, pod 6 does not: no form, none evicted
    assert len(reg) == 4 * per_pod_forms and reg.room() < per_pod
    assert _grown(before) == {
        ("made", "python"): 6, ("spliced", "watch"): 2,
        ("evicted", "spliced"): 2 * per_pod_forms}
    before = _counts()
    for name in names:
        assert _raw(port, "GET", f"/api/v1/pods/default/{name}") \
            == json.dumps(di.store.get("pods", name, "default")).encode()
    assert _grown(before) == {("full", "read"): 3, ("spliced", "read"): 4}


def test_the_registry_is_keyed_by_identity_and_bounded():
    reg = wireform.WireForms(cap_bytes=100, unread_ceiling_bytes=100)
    a = "a" * 20
    b = "".join(["a"] * 20)  # equal, another object
    assert a == b and a is not b
    reg.keep(a, b'"' + a.encode() + b'"')
    assert reg.get(a) == b'"aaaaaaaaaaaaaaaaaaaa"' and reg.get(b) is None
    reg.keep(a, b'"again"')  # the same value again: replaced, not doubled
    assert len(reg) == 1 and reg.pinned_bytes == 20 + 7
    reg.keep(b, b"x" * 60)
    assert reg.get(a) is None and reg.get(b) == b"x" * 60  # oldest first
    reg.keep("c" * 200, b"")  # larger than the ceiling itself: nothing stays
    assert len(reg) == 0 and reg.pinned_bytes == 0 == reg.unread_bytes


def test_spliced_forms_go_first_and_unread_ones_only_at_the_ceiling():
    """The two tables of the registry: past the cap a spliced form goes
    before any unread one, whatever their ages; unread forms alone may
    pass the cap and go, oldest first, at their ceiling; a form kept
    with `spare_unread` never pushes one out."""
    reg = wireform.WireForms(cap_bytes=100, unread_ceiling_bytes=160)
    old, mid, young, late = ("o" * 30, "m" * 30, "y" * 30, "l" * 30)
    before = _counts()
    for v in (old, mid, young):
        assert reg.keep(v, b"w" * 10)
    assert reg.pinned_bytes == reg.unread_bytes == 120, "unread: past the cap"
    reg.spliced([mid, "a value the registry never saw"])
    assert reg.unread_bytes == 80 and reg.get(mid) == b"w" * 10
    assert reg.room() == 80 and reg.decode_budget() == 100
    reg.keep(late, b"w" * 10)  # 160 in all: the spliced one goes, not `old`
    assert reg.get(mid) is None and reg.get(old) and reg.get(young)
    assert reg.pinned_bytes == reg.unread_bytes == 120
    assert not reg.keep("s" * 30, b"w" * 11, spare_unread=True)  # 161
    assert reg.keep("s" * 30, b"w" * 10, spare_unread=True)      # 160: fits
    assert reg.room() == 0 and reg.decode_budget() == 100
    reg.keep("n" * 30, b"w" * 10)  # at the ceiling the oldest unread goes
    assert reg.get(old) is None and reg.get(young) and len(reg) == 4
    assert _grown(before) == {("evicted", "spliced"): 1,
                              ("evicted", "unread"): 1}


def test_the_registry_keeps_its_books_under_racing_producers_and_pumps():
    """Producers keeping, pumps splicing and readers looking up at once,
    more threads than cores and a short switch interval: the two byte
    counts are the tables' own sums at the end (a lost update would
    break them), no bound was passed, and a get() never returned another
    value's form."""
    import sys

    reg = wireform.WireForms(cap_bytes=6_000, unread_ceiling_bytes=9_000)
    errors: list = []
    stop = time.time() + 1.5

    def actor(k):
        n = 0
        try:
            while time.time() < stop:
                n += 1
                value = f"{k}-{n}-" + "v" * (50 + n % 40)
                wire = b"w" + value.encode()
                reg.keep(value, wire, spare_unread=bool(n % 3 == 0))
                got = reg.get(value)
                if got is not None and got != wire:
                    errors.append((value, got))
                if n % 2:
                    reg.spliced([value])
                if not (reg.unread_bytes <= 9_000 + 200):
                    errors.append(("unread", reg.unread_bytes))
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=actor, args=(k,), daemon=True)
                   for k in range(min(32, 2 * (os.cpu_count() or 4)))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(was)
    assert not errors, errors[:3]
    size = lambda ents: sum(len(v) + len(w) for v, w in ents.values())  # noqa: E731
    assert reg.unread_bytes == size(reg._unread) <= 9_000
    assert reg.pinned_bytes == size(reg._unread) + size(reg._spliced)
    assert reg.pinned_bytes <= 9_000


def test_a_result_counts_once_and_a_batch_stops_at_its_budget(monkeypatch):
    """The write-back makes what came without, counts the result under
    the origin of its first form, and makes no more than the registry
    has room for: it stops there, and no unread form goes for one."""
    brought, late, alone = "b" * HEAVY, "l" * HEAVY, "a" * HEAVY
    wireform.WIRE_FORMS.keep(brought, json.dumps(brought).encode())
    before = _counts()
    kept = wireform.make_missing([brought, "small", late])
    assert kept == 2 * HEAVY + 2
    assert bytes(wireform.WIRE_FORMS.get(late)) == json.dumps(late).encode()
    assert _grown(before) == {}, "the codec's result, counted there"
    assert wireform.make_missing([alone]) and _grown(before) == {
        ("made", "python"): 1}
    # room for one value and its form, and a little
    one = 2 * (HEAVY + 1) + 2
    reg = wireform.WireForms(cap_bytes=one, unread_ceiling_bytes=one + HEAVY)
    monkeypatch.setattr(wireform, "WIRE_FORMS", reg)
    before = _counts()
    more = ["m" * HEAVY + str(i) for i in range(3)]
    assert wireform.make_missing(more) == one
    assert [reg.get(v) is not None for v in more] == [True, False, False]
    assert wireform.make_missing(more[1:]) == 0 and len(reg) == 1
    assert _grown(before) == {("made", "python"): 1}, "nothing evicted"


class _Part:
    """What the reflector defers for a lazy result: ready, and rendered
    on first use."""

    def __init__(self, wave, i):
        self.wave, self.i = wave, i

    def ready(self):
        return True

    def result_set(self):
        return self.wave.get(self.i)


_BURST: dict = {}


def _burst_rr(chunk):
    if chunk not in _BURST:
        cw = compile_workload(make_nodes(12, seed=88, taint_fraction=0.3),
                              make_pods(30, seed=89, with_affinity=True),
                              PluginSetConfig())
        _BURST[chunk] = replay(cw, chunk=chunk)
    return _BURST[chunk]


@native
@pytest.mark.parametrize("passes", [1, 2])
def test_a_burst_is_escaped_once_and_every_event_splices(monkeypatch, passes):
    """30 results a cycle in the pump's order: the decode call(s), 30
    write-backs, and only then the 30 reflect events (the store's queue
    holds every bind before the first of them).  The registry is sized
    to the burst as the constants are to the chip's (the cap a burst and
    a half, the ceiling three), and the burst is 2.5 times what the old
    cap of four or five results held.  Every form is the codec's, every
    event splices, no unread form is evicted: not by the write-backs,
    not by a second pass's decode call that arrives before the first's
    events are built (passes=2: the split window), and the read of a pod
    of the first pass still splices.  The next cycle's burst pushes out
    what was spliced, and nothing else."""
    rr = _burst_rr(32 // passes)
    store = ObjectStore()
    store.add_read_hook(pending := LazyReflections(store))

    def cycle(c):
        wave = LazyWave(rr, sealed=True)
        names = [f"burst-{passes}-{c}-{i}" for i in range(30)]
        for i, name in enumerate(names):
            stored = store.create("pods", _pod(name))
            pending.add("default", name, stored["metadata"]["uid"],
                        [_Part(wave, i)])
        for name in names:  # the pump meets 30 bind events: 30 flushes
            store.materialize_reads("pods", name, "default")
        for name in names:  # and then their 30 reflect events
            obj = store.get("pods", name, "default", copy_object=False)
            assert _sent(obj) == _old_event("Pod", "MODIFIED", obj)
        pod = store.get("pods", names[3], "default")  # the cycle's read
        assert b"".join(wireform.body_parts(pod, "read")) \
            == json.dumps(pod).encode()

    cycle(0)  # under the fixture's registry: what one burst pins
    burst = wireform.WIRE_FORMS.pinned_bytes
    old_cap = burst * 32 // 83  # 32 MiB beside a burst of 83 MB
    assert burst > 2.5 * old_cap
    reg = wireform.WireForms(cap_bytes=burst * 3 // 2,
                             unread_ceiling_bytes=3 * burst)
    monkeypatch.setattr(wireform, "WIRE_FORMS", reg)
    before = _counts()
    cycle(1)
    assert reg.pinned_bytes == burst and reg.unread_bytes == 0
    assert _grown(before) == {("made", "native"): 30,
                              ("spliced", "watch"): 30,
                              ("spliced", "read"): 1}
    before, forms = _counts(), len(reg)
    cycle(2)
    # down to the cap and no further: half of cycle 1's forms are left
    assert burst < reg.pinned_bytes <= burst * 3 // 2
    assert reg.unread_bytes == 0 and forms < len(reg) < 2 * forms
    assert _grown(before) == {("made", "native"): 30,
                              ("spliced", "watch"): 30,
                              ("spliced", "read"): 1,
                              ("evicted", "spliced"): 2 * forms - len(reg)}


# --- (e) a read and a pump racing on one pod ---------------------------

_ACTOR_JOBS: queue.SimpleQueue = queue.SimpleQueue()
_ACTORS: list = []


def _run_on_actors(jobs, timeout=60):
    """Each (fn, *args) of `jobs` on a daemon thread of its own, all at
    once; the threads are started on first use and kept for the process
    (test_racing_read_and_pump_soak says why)."""
    def serve():
        while True:
            fn, args, done = _ACTOR_JOBS.get()
            try:
                fn(*args)
            finally:
                done.release()

    while len(_ACTORS) < len(jobs):
        _ACTORS.append(threading.Thread(target=serve, daemon=True))
        _ACTORS[-1].start()
    done = threading.Semaphore(0)
    for fn, *args in jobs:
        _ACTOR_JOBS.put((fn, args, done))
    for _ in jobs:
        assert done.acquire(timeout=timeout), "an actor did not finish"


@native
def test_racing_read_and_pump_soak():
    """A GET's handler and a stream's pump reach one decided pod at once,
    in both orders, four pods at a time: one of the two drains (one
    decode, one write-back: the resourceVersion moves once), the other
    waits, and both put json.dumps's bytes on their sockets with every
    heavy value spliced.  Runs under ThreadSanitizer too
    (tests/test_native_tsan.py): the codec hands out the wire forms
    beside the blobs now.

    The eight actors are threads that never exit.  A thread that exits
    leaves its dynamic TLS block (the codec's thread_local scratch) to be
    freed by whichever thread trims glibc's stack cache next
    (_dl_deallocate_tls, from a sixth exited thread on); nothing TSan
    can see orders the two, and it reported that free against the dead
    thread's last write to its scratch when this soak started eight
    threads a round."""
    rr = replay(compile_workload(make_nodes(12, seed=86, taint_fraction=0.3),
                                 make_pods(4, seed=87, with_affinity=True),
                                 PluginSetConfig()), chunk=1)
    if os.environ.get("KSS_TPU_TSAN_LOCALIZE") == "1":
        _localize_ndarrays(rr)
    store = ObjectStore()
    store.add_read_hook(pending := LazyReflections(store))
    errors: list = []
    before = _counts()
    rounds = 6
    for r in range(rounds):
        wave = LazyWave(rr, sealed=True)  # cold again: a decode a round
        gate = threading.Barrier(8)
        rvs = {}

        def reader(name, late):
            try:
                gate.wait(timeout=30)
                time.sleep(0.002 * late)
                pod = store.get("pods", name, "default")
                body = b"".join(wireform.body_parts(pod, "read"))
                if body != json.dumps(pod).encode():
                    errors.append(f"{name}: the GET's bytes differ")
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

        def pump(name, late):
            try:
                gate.wait(timeout=30)
                time.sleep(0.002 * late)
                store.materialize_reads("pods", name, "default")
                obj = store.get("pods", name, "default", copy_object=False)
                if _sent(obj) != _old_event("Pod", "MODIFIED", obj):
                    errors.append(f"{name}: the event's bytes differ")
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

        jobs = []
        for i in range(4):
            name = f"race-{r}-{i}"
            stored = store.create("pods", _pod(name))
            rvs[name] = int(stored["metadata"]["resourceVersion"])
            pending.add("default", name, stored["metadata"]["uid"],
                        [_Part(wave, i)])
            # who is late alternates by pod and by round
            late = (i + r) % 2
            jobs += [(reader, name, late), (pump, name, 1 - late)]
        _run_on_actors(jobs)
        assert not errors, errors[:3]
        wrote = sorted(
            int(store.get("pods", n, "default", copy_object=False)
                ["metadata"]["resourceVersion"]) for n in rvs)
        # four creates, then four write-backs and no fifth
        assert wrote == list(range(max(rvs.values()) + 1,
                                   max(rvs.values()) + 5)), (rvs, wrote)
    grown = _grown(before)
    assert grown == {("made", "native"): 4 * rounds,
                     ("spliced", "read"): 4 * rounds,
                     ("spliced", "watch"): 4 * rounds}, grown
