"""The stall record (utils/blackbox.py, docs/metrics.md "Waiting and
working; the stall record"): a span that closes after STALL_S is kept by
the black box with what tells waiting from working, and a cause.

STALL_S, the watch's tick and its lateness limit are lowered here so
that a case takes ~0.2 s; the causes are made for real (a sleep, a spin,
a C call that holds the GIL, a collection with a slow finalizer) except
`compile`, which this machine cannot be made to produce on demand in
0.2 s: that goes through the classifier on synthetic readings, with the
order of the causes and `unseen`.
"""

import ctypes
import gc
import glob
import json
import time
import urllib.request

import pytest

from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods
from kube_scheduler_simulator_tpu.utils import blackbox, hostevents, tracing
from kube_scheduler_simulator_tpu.utils.blackbox import (
    BLACKBOX, TELEMETRY, classify_stall, validate_dump)
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

STALL = 0.15
LONG = 0.2  # what a case's span stands for: over STALL, under two of it


@pytest.fixture
def watch(monkeypatch):
    """The black box's watch on its real thread, at a tick of 20 ms."""
    monkeypatch.setattr(tracing, "STALL_S", STALL)
    monkeypatch.setattr(blackbox, "WATCH_S", 0.02)
    monkeypatch.setattr(blackbox, "LATE_S", 0.01)
    hostevents.install()
    TRACER.reset()
    BLACKBOX.reset()
    TELEMETRY.start(interval=0, device=False)
    # the first tick has come: from here a late one is a finding
    deadline = time.time() + 5
    while not TRACER.summary()["counters"].get("watchdog_ticks_total"):
        assert time.time() < deadline, "the watch never ticked"
        time.sleep(0.005)
    yield
    TELEMETRY.stop()
    BLACKBOX.owns_device = True
    blackbox.set_enabled(True)
    BLACKBOX.reset()
    TRACER.reset()


def _spin(seconds: float) -> None:
    # on the thread's own CPU clock: a loaded machine stretches the
    # wall time, not the work
    t0 = time.thread_time()
    while time.thread_time() - t0 < 2 * seconds:
        pass


def _sleep(seconds: float) -> None:
    # long enough for the watch to come by while it stands, on a
    # loaded machine too
    time.sleep(2 * seconds)


def _hold_gil(seconds: float) -> None:
    # PyDLL calls keep the GIL: no Python thread runs, the watch neither
    usleep = ctypes.PyDLL(None).usleep
    usleep.argtypes, usleep.restype = [ctypes.c_uint], ctypes.c_int
    # three times as long as the others stand: on a loaded machine the
    # span's wall time grows by what the thread waits for a core, and
    # the hold has to stay over half of it
    usleep(int(3 * seconds * 1e6))


class _SlowToDie:
    def __init__(self, seconds):
        self.me, self.seconds = self, seconds

    def __del__(self):
        time.sleep(self.seconds)


def _collect(seconds: float) -> None:
    _SlowToDie(seconds)  # a cycle: only a collection finds it
    gc.collect()


def _counters() -> dict:
    return TRACER.counter_totals()


@pytest.mark.parametrize("cause, stand, readings", [
    ("blocked", _sleep, None),
    ("on_cpu", _spin, None),
    ("process_stopped", _hold_gil, None),
    ("gc", _collect, None),
    # the classifier alone, on readings of a 2 s span
    ("compile", None, {"compile_s": 1.2, "since_s": 1.6, "cpu_since_s": 1.5}),
    ("gc", None, {"gc_s": 1.0, "late_s": 1.9}),
    # half of the stretch the watch saw, not of the span
    ("on_cpu", None, {"since_s": 1.2, "cpu_since_s": 0.6, "late_s": 1.0}),
    ("process_stopped", None, {"late_s": 1.0, "since_s": 1.5,
                               "cpu_since_s": 0.1}),
    ("blocked", None, {"compile_s": 0.9, "gc_s": 0.9, "late_s": 0.9,
                       "since_s": 1.5, "cpu_since_s": 0.7}),
    # nobody read the thread's CPU clock, or only over the span's last
    # stretch: waiting cannot be told from working
    ("unseen", None, {"gc_s": 0.3}),
    ("unseen", None, {"since_s": 0.9, "cpu_since_s": 0.9}),
    ("process_stopped", None, {"late_s": 1.7}),
])
def test_stall_cause(watch, capfd, cause, stand, readings):
    if stand is None:
        assert classify_stall(2.0, **readings) == cause
        return
    gc.collect()
    with TRACER.session_scope("s1"), TRACER.trace_scope("t-9"):
        with TRACER.span("outer"):
            with TRACER.span("standing"):
                with TRACER.span("kid"):
                    pass
                stand(LONG)
    [rec] = BLACKBOX.stalls()
    if (cause == "on_cpu" and rec["cause"] == "blocked"
            and rec["readings"]["cpu_since_s"]
            < rec["readings"]["since_s"] / 2):
        # beside five other workers the spin can get less than half a
        # core: runnable, not running, and the record says what it read
        cause = "blocked"
    assert rec["span"] == "standing" and rec["cause"] == cause
    assert rec["seconds"] >= LONG and rec["ancestors"] == ["outer"]
    assert rec["session"] == "s1" and rec["trace_id"] == "t-9"
    assert rec["descendants"]["kid"]["count"] == 1
    r = rec["readings"]
    # a span reads one clock: the watch read its thread's CPU clock from
    # outside when it first saw it stand, a tick or two after it opened
    assert "cpu" not in rec
    if cause == "process_stopped":
        # the watch was held too: it saw the span's last moment or none
        assert r.get("since_s", 0.0) < rec["seconds"] / 4
    elif "since_s" in r:
        # how soon the watch came by is the machine's (beside five other
        # workers it can be a second late), not the record's: `blocked`
        # and `on_cpu` say by their cause that it saw half the span, and
        # a collection is told by its own seconds, seen or not
        assert 0 < r["since_s"] < rec["seconds"]
    if cause == "on_cpu":
        # the spin holds the GIL but for a switch interval at a time
        assert r["cpu_since_s"] >= r["since_s"] / 2
        assert r["process_cpu_since_s"] >= r["cpu_since_s"] - 0.01
    elif cause == "blocked" and stand is _sleep:
        assert r["cpu_since_s"] < r["since_s"] / 4
    if cause == "process_stopped":
        assert r["late_s"] >= rec["seconds"] / 2
        assert _counters()["process_late_seconds_total"] >= LONG / 2
    if cause == "gc":
        assert r["gc_s"] >= rec["seconds"] / 2
    if cause == "blocked" and stand is _sleep:
        # the watch noted it while it stood: this thread's stack first,
        # in the frame that slept
        assert STALL <= rec["noted_after_s"] < rec["seconds"]
        assert "test_stall_record.py" in rec["stacks"][0]["frames"][0]
        assert r["since_s"] > 0 and r["state"] == "S"
    c = _counters()
    assert c[f"span_stalls_total{{cause={cause},session=s1,span=standing}}"] == 1
    assert c["span_stall_seconds_total"] == pytest.approx(rec["seconds"],
                                                          abs=1e-5)
    err = capfd.readouterr().err
    assert f"kss-tpu stall: span=standing seconds={rec['seconds']:.3f}" in err
    assert f"cause={cause}" in err


def test_a_span_that_beats_the_late_watch_reads_its_lateness(monkeypatch):
    """A stopped process is let run again: the span's thread may close
    the span before the watch has woken and written its late tick.  The
    record then reads how far overdue the watch's wait is."""
    monkeypatch.setattr(tracing, "STALL_S", STALL)
    BLACKBOX.reset()
    try:
        with TRACER.span("standing"):
            time.sleep(LONG)
            # no watch thread here: its wait, as if due since the start
            BLACKBOX.watch_due = time.perf_counter() - 0.9 * LONG
        [rec] = BLACKBOX.stalls()
        assert rec["cause"] == "process_stopped"
        assert rec["readings"]["late_s"] >= 0.9 * LONG
        assert "stacks" not in rec  # nobody saw it standing
    finally:
        BLACKBOX.reset()
        TRACER.reset()


def test_a_stalled_child_does_not_count_its_ancestors(watch, capfd):
    with TRACER.span("grandparent"):
        with TRACER.span("wave"):
            with TRACER.span("child"):
                time.sleep(LONG)
    [rec] = BLACKBOX.stalls()
    assert rec["span"] == "child"
    assert rec["ancestors"] == ["wave", "grandparent"]
    # the ancestors are named in the child's record as they close, each
    # with its own subtree by name; they count in neither counter
    closed = {a["span"]: a for a in rec["ancestors_closed"]}
    assert sorted(closed) == ["grandparent", "wave"]
    assert set(closed["grandparent"]["descendants"]) == {"wave", "child"}
    assert closed["wave"]["descendants"]["child"] == {
        "count": 1, "seconds": rec["seconds"]}
    c = _counters()
    stalls = {k: v for k, v in c.items() if k.startswith("span_stalls_total")}
    assert stalls == {"span_stalls_total{cause=blocked,span=child}": 1}
    assert c["span_stall_seconds_total"] == pytest.approx(rec["seconds"],
                                                          abs=1e-5)
    assert "span=wave" in capfd.readouterr().err
    # a parent that stands on its own beside a stalled child is a stall
    with TRACER.span("parent2"):
        with TRACER.span("child2"):
            time.sleep(LONG)
        time.sleep(2 * LONG)
    assert [r["span"] for r in BLACKBOX.stalls()] == ["child", "child2",
                                                      "parent2"]


@pytest.mark.parametrize("name", sorted(blackbox.EXEMPT))
def test_spans_that_wait_by_design_never_count(watch, name):
    """loop_idle waits for work and http_import for the applier's pool:
    neither is a stall, and neither covers its parent as one."""
    assert blackbox.EXEMPT == {"loop_idle", "http_import"}
    with TRACER.span(name):
        time.sleep(LONG)
    with TRACER.span("short"):
        time.sleep(STALL / 3)
    assert BLACKBOX.stalls() == []
    c = _counters()
    assert c["span_stall_seconds_total"] == 0
    assert c["process_late_seconds_total"] >= 0
    assert not [k for k in c if k.startswith("span_stalls_total")]


def test_record_outlives_the_rings(watch, monkeypatch, tmp_path):
    """The bundle is schema-valid and written like a wave abort's; the
    record keeps its subtree by name after the tracer's ring has rolled,
    and later bundles of other reasons do not push it out."""
    monkeypatch.setenv("KSS_TPU_BLACKBOX_DIR", str(tmp_path))
    BLACKBOX.wave_start(None, pods=1)
    with TRACER.span("first_pass"):
        for _ in range(3):
            with TRACER.span("build"):
                with TRACER.span("row"):
                    pass
        time.sleep(LONG)
    [doc] = BLACKBOX.stall_dumps()
    validate_dump(doc)
    assert doc["reason"] == "stall" and doc["stall"]["span"] == "first_pass"
    assert doc["counter_deltas"]["span_stalls_total"
                                 "{cause=blocked,span=first_pass}"] == 1
    [path] = glob.glob(str(tmp_path / "blackbox-*-stall.json"))
    assert doc["path"] == path
    with open(path, encoding="utf-8") as fh:
        assert json.load(fh)["stall"]["cause"] == "blocked"
    for _ in range(TRACER._events.maxlen + 8):  # the ring rolls over
        with TRACER.span("served"):
            pass
    assert not [e for e in TRACER.events(10 ** 6)
                if e["name"] == "first_pass"]
    for _ in range(BLACKBOX._dumps.maxlen + 2):
        BLACKBOX.dump("wave_abort")
    [rec] = BLACKBOX.stalls()
    assert {k: v["count"] for k, v in rec["descendants"].items()} == {
        "build": 3, "row": 3}
    assert BLACKBOX.stalls(session="someone-else") == []
    assert TRACER.counter_totals()["blackbox_dumps_total{reason=stall}"] == 1


def test_long_children_survive_a_ring_that_rolls_inside_the_span(watch):
    """A session's first pass: the watch streams' thousands of short
    spans roll the ring over before the pass closes.  What stood for
    LONG_S is held a second time and is in the record; the record says
    that the short ones from before are lost."""
    with TRACER.span("first_pass"):
        with TRACER.span("tiny"):
            pass
        with TRACER.span("slow_build"):
            time.sleep(2 * tracing.LONG_S)
        for _ in range(TRACER._events.maxlen + 8):
            with TRACER.span("pump", parent=0):
                pass
        time.sleep(LONG)
    [rec] = BLACKBOX.stalls()
    assert rec["span"] == "first_pass" and rec["short_descendants_lost"]
    assert rec["descendants"]["slow_build"]["count"] == 1
    assert "tiny" not in rec["descendants"]


def test_blackbox_off_keeps_nothing(watch):
    blackbox.set_enabled(False)
    ticks = _counters().get("watchdog_ticks_total", 0)
    with TRACER.span("standing") as sp:
        time.sleep(LONG)
    assert sp.seconds >= LONG
    assert BLACKBOX.stalls() == [] and BLACKBOX.stall_dumps() == []
    c = _counters()
    assert c["span_stall_seconds_total"] == 0
    assert c.get("watchdog_ticks_total", 0) <= ticks + 1  # the watch is off


def test_telemetry_thread_outlives_every_leg_turned_off(monkeypatch):
    """The HBM and history legs off and the black box turned off at run
    time: the thread's wait has no leg to be due for.  It must not take
    an infinite timeout (Event.wait raises OverflowError on one), and
    the watch runs again once the black box is back on."""
    monkeypatch.setattr(blackbox, "WATCH_S", 0.02)
    monkeypatch.setattr(blackbox, "OFF_S", 0.05)
    monkeypatch.setattr(blackbox._history, "enabled", lambda: False)
    TRACER.reset()
    TELEMETRY.start(interval=0, device=False)
    try:
        blackbox.set_enabled(False)
        time.sleep(0.1)  # a few waits with nothing due
        assert TELEMETRY._thread.is_alive()
        ticks = _counters().get("watchdog_ticks_total", 0)
        blackbox.set_enabled(True)
        deadline = time.time() + 5
        while _counters().get("watchdog_ticks_total", 0) < ticks + 2:
            assert time.time() < deadline, "the watch did not come back"
            time.sleep(0.01)
    finally:
        TELEMETRY.stop()
        BLACKBOX.owns_device = True
        blackbox.set_enabled(True)
        TRACER.reset()


def test_first_pass_is_listed_by_debug_dump(monkeypatch):
    """A served session's first pass compiles its scan and stands for
    seconds: GET /api/v1/debug/dump lists its record, with the pass's
    subtree by name, next to the recent dumps."""
    from kube_scheduler_simulator_tpu.config.config import (
        SimulatorConfiguration)
    from kube_scheduler_simulator_tpu.server.di import DIContainer
    from kube_scheduler_simulator_tpu.server.server import SimulatorServer

    monkeypatch.setattr(tracing, "STALL_S", STALL)
    BLACKBOX.reset()
    srv = SimulatorServer(DIContainer(SimulatorConfiguration(port=0)), port=0)
    srv.start(block=False)
    base = f"http://127.0.0.1:{srv.port}"

    def call(method, path, body=None):
        req = urllib.request.Request(
            base + path, method=method,
            data=None if body is None else json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read() or b"null")

    try:
        for n in make_nodes(3, seed=5):
            call("POST", "/api/v1/nodes", n)
        [pod] = make_pods(1, seed=6)
        call("POST", "/api/v1/pods", pod)
        name = pod["metadata"]["name"]
        deadline = time.time() + 60
        while not (call("GET", f"/api/v1/pods/default/{name}").get("spec")
                   or {}).get("nodeName"):
            assert time.time() < deadline, "the pod was never bound"
            time.sleep(0.05)
        out = call("GET", "/api/v1/debug/dump")
        assert "recent" in out
        stalls = out["stalls"]
        assert stalls, "the first pass left no stall record"
        in_pass = [s for s in stalls
                   if "wave" in [s["span"], *s["ancestors"]]]
        assert in_pass and all(s["session"] == "default" for s in in_pass)
        # the whole pass by name: in the wave's own record, or where a
        # child stood for most of it, in what that child's record says
        # of its ancestors
        waves = [s for s in in_pass if s["span"] == "wave"] + [
            a for s in in_pass for a in s["ancestors_closed"]
            if a["span"] == "wave"]
        assert "compile_workload" in waves[0]["descendants"]
        metrics = call("GET", "/api/v1/metrics")
        assert metrics["counters"]["span_stall_seconds_total"] >= STALL
        assert metrics["counters"]["process_late_seconds_total"] >= 0
        assert call("GET", "/api/v1/sessions/default/debug/dump")["stalls"]
    finally:
        srv.shutdown()
        BLACKBOX.reset()
