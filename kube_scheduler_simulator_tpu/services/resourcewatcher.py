"""Resource watcher: server-push of cluster changes to clients.

Capability parity with the reference resource watcher (reference:
simulator/resourcewatcher/resourcewatcher.go): for the 7 resource kinds
(:22-30 targetResources), starts a list (emitting initial ADDED events for
objects newer than the client's lastResourceVersion) + watch stream per
kind (:61-120), JSON-encoding every event onto one shared HTTP response
stream through a locked stream writer (reference:
streamwriter/streamwriter.go:41-49).  The wire format matches the
reference's WatchEvent: {"kind": "<Kind>", "eventType": "<TYPE>",
"obj": {...}} streamed as concatenated JSON objects.
"""

from __future__ import annotations

import json
import threading
import time
from itertools import islice

from ..cluster.store import ObjectStore, RESOURCES, ADDED, DEFAULT_GVRS
from ..utils import wireform
from ..utils.tracing import TRACER

# wire protocol: per-kind *LastResourceVersion query params a client passes
# to resume (reference: server/handler/watcher.go:23-45 form values)
WATCH_PARAMS = {
    "pods": "podsLastResourceVersion",
    "nodes": "nodesLastResourceVersion",
    "persistentvolumes": "pvsLastResourceVersion",
    "persistentvolumeclaims": "pvcsLastResourceVersion",
    "storageclasses": "scsLastResourceVersion",
    "priorityclasses": "pcsLastResourceVersion",
    "namespaces": "namespaceLastResourceVersion",
}


def _carries_decision(obj: dict) -> bool:
    """A bind (spec.nodeName) or the Unschedulable mark: what a watcher
    takes for the scheduler's decision."""
    if (obj.get("spec") or {}).get("nodeName"):
        return True
    return any(c.get("reason") == "Unschedulable"
               for c in (obj.get("status") or {}).get("conditions") or ())


class Decision:
    """One pod's decision on its way out: committed by the engine at
    `t_commit`, delivered when the first stream's write of it returned,
    read when the pod's first GET reached its handler."""

    __slots__ = ("t_commit", "trace_id", "t_delivered", "t_read")

    def __init__(self, t_commit: float, trace_id: str | None):
        self.t_commit = t_commit  # time.perf_counter(), like the others
        self.trace_id = trace_id  # the deciding wave's
        self.t_delivered: float | None = None
        self.t_read: float | None = None  # a read that beat `delivered`


class DecisionStamps:
    """One session's decisions on their way out to its clients
    (docs/metrics.md "The decision's way out").

    The engine stamps a pod when it writes the bind or the Unschedulable
    mark, BEFORE the store publishes the event; when the first watch
    stream's socket write of an event that carries the decision returns,
    the span `decision_delivery` closes; the pod's first GET closes
    `decision_to_read` and pops the entry.
    Both spans are recorded after the fact (TRACER.record_span) under
    the trace id of the wave that decided.

    Owned by the session's DIContainer and keyed (namespace, name).
    Bounded: an entry goes at the pod's first read, at its DELETE (the
    scheduling loop's watch thread sees every one, a reset's too), and
    oldest first past `cap` (clients that never watch or never read)."""

    CAP = 8192

    def __init__(self, cap: int = CAP):
        self._cap = cap
        self._mu = threading.Lock()
        self._ents: dict[tuple[str, str], Decision] = {}

    def __len__(self) -> int:
        return len(self._ents)

    @staticmethod
    def _span(name: str, d: Decision, t0: float, t1: float) -> None:
        # closed on a pump's or a handler's thread: both record under
        # their session's scope
        attrs = {} if d.trace_id is None else {"trace_id": d.trace_id}
        TRACER.record_span(name, t0, t1 - t0,
                           session=TRACER.current_session(), **attrs)

    def stamp(self, keys) -> None:
        """The engine is about to write these pods' decisions: one clock
        read for the batch, one dict store a pod."""
        now, trace_id = time.perf_counter(), TRACER.current_trace()
        with self._mu:
            ents = self._ents
            for key in keys:
                ents.pop(key, None)  # a re-decided pod is the youngest
                ents[key] = Decision(now, trace_id)
            over = len(ents) - self._cap
            if over > 0:
                for key in list(islice(ents, over)):
                    del ents[key]

    def undelivered(self, obj: dict) -> Decision | None:
        """The pod's decision if it is stamped, on no stream yet, and
        `obj` carries it."""
        meta = obj.get("metadata") or {}
        d = self._ents.get((meta.get("namespace") or "default",
                            meta.get("name", "")))
        if (d is None or d.t_delivered is not None
                or not _carries_decision(obj)):
            return None
        return d

    def delivered(self, d: Decision, now: float) -> None:
        """A stream's write of the decision returned at `now`: of
        several streams the first to say so closes the stretch."""
        with self._mu:
            if d.t_delivered is not None:
                return
            t_read = d.t_read
            if t_read is not None:
                # the client was back before this thread was: the bytes
                # were on the wire by then, whatever this clock read says
                now = min(now, t_read)
            d.t_delivered = now
        self._span("decision_delivery", d, d.t_commit, now)
        if t_read is not None:
            self._span("decision_to_read", d, now, t_read)

    def first_read(self, namespace: str | None, name: str,
                   now: float) -> str | None:
        """A GET of the pod reached its handler at `now`: pop the entry;
        -> the deciding wave's trace id (None: no entry)."""
        with self._mu:
            d = self._ents.pop((namespace or "default", name), None)
            if d is None:
                return None
            t_delivered = d.t_delivered
            if t_delivered is None:
                # no stream has said it delivered: one that is writing
                # right now closes both stretches when it does
                d.t_read = now
        if t_delivered is not None:
            self._span("decision_to_read", d, t_delivered, now)
        return d.trace_id

    def forget(self, namespace: str | None, name: str) -> None:
        """The pod was deleted: nobody will watch for or read this
        decision."""
        with self._mu:
            self._ents.pop((namespace or "default", name), None)


class StreamWriter:
    """Serialises concurrent event writes onto one response stream
    (reference: streamwriter/streamwriter.go)."""

    def __init__(self, write, flush=None,
                 decisions: DecisionStamps | None = None):
        self._write = write
        self._flush = flush
        self._lock = threading.Lock()
        self.decisions = decisions

    def decision_of(self, kind: str, event_type: str,
                    obj: dict) -> Decision | None:
        """The stamped decision this event would be the first to deliver
        (DecisionStamps.undelivered), if any."""
        if (self.decisions is None or kind != "Pod"
                or event_type == "DELETED"):
            return None
        return self.decisions.undelivered(obj)

    def send(self, kind: str, event_type: str, obj: dict) -> bool:
        decision = self.decision_of(kind, event_type, obj)
        # the decision's event leaves under the trace id of its wave
        with TRACER.trace_scope(decision and decision.trace_id), \
                TRACER.span("watch_write"):
            # lazy columnar rows (cluster/columnar.LazyManifest) must be
            # materialized explicitly: json's C encoder walks dict storage
            # directly, bypassing the subclass's lazy-read overrides
            with TRACER.span("watch_encode"):
                fill = getattr(obj, "fill", None)
                if fill is not None:
                    fill()
                # the same bytes either way: where obj carries values
                # whose escaped bytes are kept (utils/wireform.py) they
                # are spliced into the event, not escaped again
                parts = wireform.body_parts(obj, "watch")
                if parts is None:
                    data = json.dumps({"kind": kind, "eventType": event_type,
                                       "obj": obj}).encode()
                else:
                    head = json.dumps({"kind": kind,
                                       "eventType": event_type})[:-1]
                    data = b"".join(
                        [head.encode(), b', "obj": ', *parts, b"}"])
            with TRACER.span("watch_send"), self._lock:
                try:
                    self._write(data)
                    if self._flush:
                        self._flush()
                    sent_at = time.perf_counter()
                except (BrokenPipeError, ConnectionError, OSError):
                    sent_at = None
            if sent_at is None:
                return False
            if decision is not None:
                self.decisions.delivered(decision, sent_at)
            TRACER.count("watch_bytes_sent_total", len(data))
            return True


class ResourceWatcherService:
    def __init__(self, store: ObjectStore, resources: list[str] | None = None):
        self.store = store
        self.resources = resources or list(DEFAULT_GVRS)

    def list_watch(self, stream: StreamWriter, last_resource_versions: dict[str, int] | None,
                   stop: threading.Event) -> None:
        """Blocks until the client disconnects or stop is set.

        last_resource_versions: per-resource rv the client has already
        seen (the reference takes one *LastResourceVersion form value per
        kind, handler/watcher.go:23-45); 0/absent means full initial list.
        """
        lrv = last_resource_versions or {}
        registry = getattr(self.store, "resources", RESOURCES)
        queues = {}
        for resource in self.resources:
            kind, _ = registry[resource]
            since = int(lrv.get(resource, 0))
            if since == 0:
                # initial listing, then watch from the listing's rv — NOT
                # from 0, which would replay the event ring buffer on top
                # of the listing and double-deliver every object.  Events
                # racing in between are > list_rv and still buffered, so
                # nothing is lost.
                # shared manifests: send() serializes, never mutates.
                # Deferred lazy annotations (store/lazy.py) are drained
                # first so the initial listing carries the same bytes a
                # copying read would
                flush = getattr(self.store, "materialize_reads", None)
                if flush is not None:
                    flush(resource)
                items, list_rv = self.store.list(resource,
                                                 copy_objects=False)
                q = self.store.watch(resource, since_rv=list_rv)
                queues[resource] = q
                for obj in items:
                    if not stream.send(kind, ADDED, obj):
                        self._cleanup(queues)
                        return
            else:
                q = self.store.watch(resource, since_rv=since)
                queues[resource] = q

        threads = []
        dead = threading.Event()

        # the pump threads record under the session whose streams they
        # feed, as its handlers and its loop do: the caller's scope
        session = TRACER.current_session()

        def pump(resource, q):
            kind, _ = registry[resource]
            flush = (getattr(self.store, "materialize_reads", None)
                     if resource == "pods" else None)
            with TRACER.session_scope(session):
                while not (stop.is_set() or dead.is_set()):
                    ev = q.get()
                    if ev is None:
                        return
                    _, event_type, obj = ev
                    if flush is not None and event_type != "DELETED":
                        # a watch client is a reader: drain this pod's
                        # deferred annotations (no-op when none pending)
                        # so the reflect MODIFIED event follows this one
                        # and the client converges on the eager path's
                        # stream.  Ahead of a decision's event the drain
                        # is on the decision's way out: the same trace id
                        # as its send
                        decision = stream.decision_of(kind, event_type, obj)
                        meta = obj.get("metadata") or {}
                        with TRACER.trace_scope(
                                decision and decision.trace_id), \
                                TRACER.span("watch_flush"):
                            flush("pods", meta.get("name"),
                                  meta.get("namespace"))
                    if not stream.send(kind, event_type, obj):
                        dead.set()
                        return

        for resource, q in queues.items():
            t = threading.Thread(target=pump, args=(resource, q), daemon=True)
            t.start()
            threads.append(t)
        if "pods" in queues and hasattr(self.store, "materialize_reads"):
            # convergence for watch-only clients: a record queued by a
            # still-streaming wave is SKIPPED by the per-event flush
            # (never stall the stream on an in-flight replay), and the
            # wave emits no further event once it seals — so while this
            # connection is open, periodically drain whatever became
            # ready; the resulting reflect MODIFIED events reach the
            # stream like eager mode's wave-end write-backs would
            def laggard():
                while not (stop.is_set() or dead.is_set()):
                    if stop.wait(0.25) or dead.is_set():
                        return
                    try:
                        self.store.materialize_reads("pods")
                    except Exception:
                        pass  # observability of the flush, not the stream

            t = threading.Thread(target=laggard, daemon=True)
            t.start()
            threads.append(t)
        while not (stop.is_set() or dead.is_set()):
            stop.wait(0.2)
        for resource, q in queues.items():
            self.store.unwatch(resource, q)
            q.put(None)
        for t in threads:
            t.join(timeout=1)

    def _cleanup(self, queues):
        for resource, q in queues.items():
            self.store.unwatch(resource, q)
