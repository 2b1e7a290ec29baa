"""DI container: construct all services once, wire dependencies.

Capability parity with the reference DI container (reference:
simulator/server/di/di.go:39-78): scheduler service, snapshot, reset,
resource watcher, resource applier, and — conditionally on config flags —
the one-shot importer, syncer, and replayer.  Extra here: the scheduling
loop thread, which replaces the reference's separate debuggable-scheduler
container by running the tensor engine in-process whenever pods await
scheduling.

Multi-session serving (server/sessions.py): a DIContainer IS the
per-session context — everything it owns (store, reflector, engine,
result store, scheduling loop, service set) is private to one simulated
cluster.  What it does NOT own is shared process-wide by design: the
compiled-scan registry (framework/replay._SCAN_CACHE — sessions at the
same workload shape reuse one XLA executable) and the device-result
retention budget (framework/replay._DEVICE_BUDGET — one
KSS_TPU_DEVICE_RESULT_BUDGET_MB pool split into per-session shares).
The `session` argument stamps the engine so waves record under that
session's tracer scope.
"""

from __future__ import annotations

import contextlib
import math
import threading
import time
import traceback

from ..utils.tracing import TRACER

from ..cluster.store import ADDED, DELETED, MODIFIED, ObjectStore
from ..config.config import SimulatorConfiguration
from ..framework.engine import SchedulerEngine
from ..framework.unschedulable import MOVING_RESOURCES, UnschedulablePods
from ..scenario.runner import ScenarioService
from ..scheduler.service import SchedulerService
from ..services.importer import OneShotImporter
from ..services.recorder import RecorderService
from ..services.replayer import ReplayerService
from ..services.reset import ResetService
from ..services.resourceapplier import ResourceApplier
from ..services.resourcewatcher import DecisionStamps, ResourceWatcherService
from ..services.snapshot import SnapshotService
from ..services.syncer import SyncerService
from ..store.reflector import StoreReflector
from ..utils.heap import settle_heap


# The batching window of the scheduling loop (docs/how-it-works.md).
# WINDOW_CAP_S is the longest a pending pod waits for a burst to finish
# arriving, whoever is still writing.  QUIET_S is how long after the last
# pending pod's ADDED event, with no workload-submitting request in
# flight, the burst counts as over: above every gap between ADDED events
# measured inside one writer's burst on the chip's host (the applier:
# 0.19 ms a pod, longest of 6,000 gaps 1.5 ms; a script's POSTs one after
# another: 0.94 ms, longest 1.3 ms; PERF.md section 6, PR 27), and a
# fiftieth of the ~90 ms pass it saves.
WINDOW_CAP_S = 0.05
QUIET_S = 0.002


class SchedulingLoop:
    """Watches pod events and runs scheduling waves for pending pods —
    the in-process analogue of the always-running debuggable-scheduler
    container.  Batches so a burst of creates compiles as ONE tensor
    workload instead of one compile per pod: the first pending pod's
    ADDED event opens a window, and the pass starts when the window
    closes —

      settled  no workload-submitting request of this session is in
               flight (writer_in_flight(), entered by the HTTP handler
               around every sheddable POST) and no pending pod has
               arrived for QUIET_S; or
      cap      window_cap seconds after the window opened, whatever is
               still in flight.

    Writers that are not HTTP requests (syncer, replayer, importer,
    scenarios, cmd/scheduler's remote store) never count as in flight:
    for them the quiet interval alone batches, and once a burst is under
    way the running pass is the window of the next.  Each close counts
    loop_window_closed_total{reason}.

    A pod a pass marked Unschedulable waits in the loop's unschedulable
    set (framework/unschedulable.py) and is in no later pass until a
    cluster event moves it and its backoff has run out, or the 5-minute
    flush comes: the loop feeds the set from the store's watches (pods,
    and the kinds whose events move pods) and wakes when a parked pod
    comes due, as it wakes for a new one."""

    def __init__(self, store: ObjectStore, engine: SchedulerEngine,
                 window_cap: float = WINDOW_CAP_S):
        self.store = store
        self.engine = engine
        self.window_cap = window_cap
        # guards the three fields below; notified on an arrival that
        # opens a window, on the last writer leaving, and on stop
        self._cond = threading.Condition()
        self._wake = False  # an arrival (or kick) no pass has taken yet
        self._in_flight = 0
        self._last_arrival = 0.0  # time.monotonic() of the last pending ADDED
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._q = None
        self.unschedulable = UnschedulablePods()
        self._moving_qs: dict[str, object] = {}
        # last wave crash ({time, error, traceback}) — the loop survives
        # engine exceptions, but a silently wedged loop is unobservable;
        # /readyz surfaces this and scheduling_loop_crashes_total counts
        self.last_crash: dict | None = None
        self._heap_settled = False

    def start(self):
        self._q = self.store.watch("pods")
        threading.Thread(target=self._watch, daemon=True).start()
        for resource in MOVING_RESOURCES:
            q = self._moving_qs[resource] = self.store.watch(resource)
            threading.Thread(target=self._watch_moving, args=(resource, q),
                             daemon=True).start()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def stop(self):
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        if self._q is not None:
            self.store.unwatch("pods", self._q)
            self._q.put(None)
        for resource, q in self._moving_qs.items():
            self.store.unwatch(resource, q)
            q.put(None)

    def kick(self):
        with self._cond:
            self._wake = True
            self._cond.notify_all()

    @contextlib.contextmanager
    def writer_in_flight(self):
        """Held by a request that may still create pending pods: the
        window stays open (up to its cap) until the last one leaves."""
        with self._cond:
            self._in_flight += 1
        try:
            yield
        finally:
            with self._cond:
                self._in_flight -= 1
                if not self._in_flight:
                    self._cond.notify_all()

    def _watch(self):
        while not self._stop.is_set():
            ev = self._q.get()
            if ev is None:
                return
            _, event_type, obj = ev
            self._note("pods", event_type, obj)
            if event_type == ADDED and not ((obj.get("spec") or {}).get("nodeName")):
                # where the store hands a pending pod to the loop: the
                # stamp the wave's queue_wait_* counters measure from
                self.engine.note_arrival(obj)
                with self._cond:
                    self._last_arrival = time.monotonic()
                    if not self._wake:
                        # an open window reads _last_arrival when its
                        # quiet deadline comes; only the arrival that
                        # opens one has a sleeper to wake
                        self._wake = True
                        self._cond.notify_all()
            elif event_type == DELETED:
                self.engine.forget_arrival(obj)

    def _watch_moving(self, resource: str, q) -> None:
        """Events of a kind that can help an Unschedulable pod."""
        while not self._stop.is_set():
            ev = q.get()
            if ev is None:
                return
            self._note(resource, ev[1], ev[2])

    def _note(self, resource: str, event_type: str, obj: dict) -> None:
        if self.unschedulable.note(resource, event_type, obj):
            with self._cond:  # the idle loop reckons its sleep anew
                self._cond.notify_all()

    def _await_work(self) -> None:
        """Sleep until a pending pod has arrived, a parked pod has come
        due (it is then pending again, and the wake-up is its), or stop."""
        with self._cond:
            while not (self._wake or self._stop.is_set()):
                due_in = self.unschedulable.due_in()
                if due_in is not None and due_in <= 0:
                    self._wake = bool(self.unschedulable.release_due())
                else:
                    self._cond.wait(due_in)

    def _run(self):
        # loop_idle / loop_debounce / loop_pass cover this thread end to
        # end: under a profile every instant of it carries a kss: span
        with TRACER.session_scope(getattr(self.engine, "session", None)):
            while True:
                with TRACER.span("loop_idle"):
                    self._await_work()
                with TRACER.span("loop_debounce"):
                    reason = self._hold_window()
                if reason is None:
                    return
                TRACER.inc("loop_window_closed_total", reason=reason)
                with TRACER.span("loop_pass"):
                    self._pass()

    def _hold_window(self) -> str | None:
        """Sleep until the open window closes and say why ("settled" or
        "cap"; None: the loop was stopped).  _wake is cleared HERE, at the
        close, just before the pass lists the pending pods: every arrival
        up to now is in that list, and one that lands later sets _wake
        again and gets a pass of its own — cleared any earlier, a burst's
        later arrivals would buy a second window and an empty pass."""
        cap_at = time.monotonic() + self.window_cap
        with self._cond:
            while not self._stop.is_set():
                now = time.monotonic()
                settled_at = (math.inf if self._in_flight
                              else self._last_arrival + QUIET_S)
                if now >= min(settled_at, cap_at):
                    self._wake = False
                    return "settled" if now >= settled_at else "cap"
                # woken early only by the last writer leaving or stop
                self._cond.wait(min(settled_at, cap_at) - now)
        return None

    def _pass(self):
        try:
            # a pass that a new pod woke takes the parked pods that have
            # come due meanwhile with it
            self.unschedulable.release_due()
            with self.engine.queued_by(self.unschedulable):
                n_bound = self.engine.schedule_pending()
            if n_bound and not self._heap_settled:
                # the session's first pass built the rows of every bound
                # pod and traced its scan: all of it is kept, none of it
                # garbage (utils/heap.py)
                self._heap_settled = True
                settle_heap()
        except Exception as e:  # keep the loop alive like a crashed-and-restarted pod
            tb = traceback.format_exc()
            self.last_crash = {
                "time": time.time(),
                "error": f"{type(e).__name__}: {e}",
                "traceback": tb,
            }
            session = getattr(self.engine, "session", None)
            if session is not None:
                TRACER.inc("scheduling_loop_crashes_total",
                           session=session)
            else:
                TRACER.count("scheduling_loop_crashes_total")
            traceback.print_exc()


class DIContainer:
    def __init__(self, cfg: SimulatorConfiguration | None = None,
                 source_store: ObjectStore | None = None,
                 start_scheduler: bool = True,
                 session: str | None = None):
        self.session = session
        self.cfg = cfg or SimulatorConfiguration()
        self.store = ObjectStore(
            extra_resources=getattr(self.cfg, "extra_resources", None))
        # extra GVRs ride the same watch/record/sync surface as the
        # built-in seven (DEFAULT_GVRS + config extraResources)
        from ..cluster.store import DEFAULT_GVRS

        extra_gvrs = [
            spec["resource"]
            for spec in getattr(self.cfg, "extra_resources", None) or []
            if spec.get("resource") not in DEFAULT_GVRS
        ]
        self._gvrs = list(DEFAULT_GVRS) + extra_gvrs
        self.applier = ResourceApplier(self.store)
        self.reflector = StoreReflector(self.store)
        self.engine = SchedulerEngine(self.store, reflector=self.reflector)
        self.engine.session = session
        # the decision's way out (docs/metrics.md): stamped by the engine
        # at the commit, closed by this session's watch streams and its
        # pod reads
        self.decisions = DecisionStamps()
        self.engine.decisions = self.decisions
        initial_scheduler_cfg = self.cfg.initial_scheduler_config()
        self.scheduler_service = SchedulerService(self.engine, initial_scheduler_cfg)
        self.snapshot_service = SnapshotService(self.store, self.scheduler_service)
        self.scenario_service = ScenarioService(self.store, self.engine)
        self.reset_service = ResetService(self.store, self.scheduler_service)
        self.watcher_service = ResourceWatcherService(self.store,
                                                      resources=self._gvrs)

        self.importer = None
        self.syncer = None
        self.replayer = None
        self.recorder = None
        if ((self.cfg.external_import_enabled or self.cfg.resource_sync_enabled)
                and source_store is None and self.cfg.kube_config):
            # the reference builds a client-go config from the kubeConfig
            # field for import/sync sources (config.go:94-98); here that
            # is a real-apiserver REST client (or a simulator URL —
            # connect_source probes)
            from ..cluster.kubeapi import connect_source

            source_store = connect_source(self.cfg.kube_config)
            self._owned_source = source_store
        if self.cfg.external_import_enabled:
            if source_store is None:
                raise ValueError("externalImportEnabled requires a source "
                                 "cluster (kubeConfig or source_store)")
            self.importer = OneShotImporter(source_store, self.applier,
                                            resources=self._gvrs)
        if self.cfg.resource_sync_enabled:
            if source_store is None:
                raise ValueError("resourceSyncEnabled requires a source "
                                 "cluster (kubeConfig or source_store)")
            self.syncer = SyncerService(source_store, self.applier,
                                        resources=self._gvrs)
        if self.cfg.replayer_enabled:
            self.replayer = ReplayerService(self.applier, self.cfg.record_file_path)

        self.scheduling_loop = SchedulingLoop(self.store, self.engine)
        if start_scheduler:
            self.scheduling_loop.start()

    def new_recorder(self, path: str, flush_interval: float = 5.0) -> RecorderService:
        self.recorder = RecorderService(self.store, path, flush_interval,
                                        resources=self._gvrs)
        return self.recorder

    def shutdown(self):
        # interrupt any in-flight write-back/bind backoff FIRST: the
        # retry schedule sleeps up to ~36s and eviction must not ride it
        # out (utils/retry.py stop; the aborted write surfaces as
        # RetryAborted to its wave, which teardown tolerates)
        self.reflector.stop_event.set()
        self.scheduling_loop.stop()
        if self.syncer:
            self.syncer.stop()
        if self.recorder:
            self.recorder.stop()
        src = getattr(self, "_owned_source", None)
        if src is not None:
            # a source THIS container dialed from cfg.kube_config — release
            # its watch threads/sockets (callers own any source they pass)
            if hasattr(src, "close"):
                src.close()
            elif hasattr(src, "stop"):
                src.stop()
