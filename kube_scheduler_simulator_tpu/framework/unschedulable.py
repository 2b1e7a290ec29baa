"""The scheduling loop's unschedulable set: where a pod waits after a pass
marked it Unschedulable, and what brings it back.

Upstream's scheduling queue (pkg/scheduler/backend/queue, v1.32) keeps
such a pod in `unschedulablePods` until something happens that could help
it, then in `backoffQ` until its backoff has run out, and only then hands
it to a scheduling cycle again.  Without that, an Unschedulable pod rides
along in every later pass: each pass is one pod longer (another scan
shape) and runs the pod's PostFilter again.  This is that queue as far as
a batched pass needs it:

  parked    after the pass that marked it.  Inside `SchedulerEngine.
            queued_by(this set)` a pass leaves parked pods out of its
            pending list and parks the ones it leaves Unschedulable;
  moved     by a cluster event upstream's queue moves pods on — a node
            added or updated, a bound pod deleted, a PersistentVolume,
            PersistentVolumeClaim, StorageClass or CSINode added or updated, the
            pod's own spec or labels changed.  The queueing hints of the
            single plugins are not modelled: such an event moves every
            parked pod.  What the scheduler itself writes to a pod (the
            Unschedulable condition, the result annotations) is no event;
  due       when it was moved AND its backoff has run out: attempt k of a
            pod backs off podInitialBackoffSeconds * 2^(k-1), at most
            podMaxBackoffSeconds, counted from the attempt's end — or,
            moved or not, FLUSH_AFTER_S after it was parked (upstream's
            podMaxInUnschedulablePodsDuration, 5 minutes).

A pod that preemption nominated a node for is never parked: it keeps the
retry wave of the pass that nominated it.  The set is fed by the
scheduling loop's watch threads (server/di.py) and read by the engine's
pass; one lock guards it.  `pods_requeued_total{reason}` says why a pod
came back: event (it was moved with its backoff already over), backoff
(it was moved and had to wait the backoff out), flush.
"""

from __future__ import annotations

import threading
import time

from ..utils.tracing import TRACER
from .pending import _key as pod_key

FLUSH_AFTER_S = 300.0

_RESULT_PREFIX = "kube-scheduler-simulator.sigs.k8s.io/"
# resources whose ADDED / MODIFIED events move the parked pods
MOVING_RESOURCES = ("nodes", "persistentvolumes", "persistentvolumeclaims",
                    "storageclasses", "csinodes")


def _own_fields(pod: dict) -> tuple:
    """What of a pod is its owner's: the scheduler writes status and the
    result annotations, and neither is a reason to try the pod again."""
    meta = pod.get("metadata") or {}
    annotations = {k: v for k, v in (meta.get("annotations") or {}).items()
                   if not k.startswith(_RESULT_PREFIX)}
    return (pod.get("spec"), meta.get("labels"), annotations)


class _Parked:
    __slots__ = ("uid", "own", "since", "backoff_until", "moved", "waited")

    def __init__(self, uid, own, since, backoff_until, moved):
        self.uid, self.own = uid, own
        self.since, self.backoff_until = since, backoff_until
        self.moved = moved    # an event asked for another try
        self.waited = False   # ... while the backoff was still running


class UnschedulablePods:
    def __init__(self, clock=time.monotonic):
        self.clock = clock
        self._lock = threading.Lock()
        self._parked: dict[tuple[str, str], _Parked] = {}
        # failed attempts so far, for as long as the pod is pending
        self._attempts: dict[tuple[str, str], int] = {}
        # how many moving events there have been: a pass remembers the
        # count it started at, and a pod it parks after a later event is
        # parked as moved (upstream's moveRequestCycle)
        self.move_seq = 0

    def __len__(self) -> int:
        return len(self._parked)

    # ------------------------------------------------------- the pass's side

    def parked_uids(self) -> dict[tuple[str, str], str | None]:
        """key -> uid of the pods a pass must not take."""
        with self._lock:
            return {k: e.uid for k, e in self._parked.items()}

    def park(self, pod: dict, initial_s: float, max_s: float,
             seq_at_start: int | None = None) -> None:
        """The pass that started at move_seq == seq_at_start left `pod`
        Unschedulable."""
        key = pod_key(pod)
        now = self.clock()
        with self._lock:
            attempts = self._attempts[key] = self._attempts.get(key, 0) + 1
            backoff = min(initial_s * 2 ** (attempts - 1), max_s)
            moved = seq_at_start is not None and seq_at_start != self.move_seq
            self._parked[key] = _Parked(
                (pod.get("metadata") or {}).get("uid"), _own_fields(pod),
                now, now + backoff, moved)
        TRACER.count("pods_unschedulable_parked_total")

    # ------------------------------------------------------- the loop's side

    def note(self, resource: str, event_type: str, obj: dict) -> bool:
        """One store event -> True when a parked pod may now come due
        sooner than the loop last reckoned."""
        if not self._parked and not self._attempts:
            return False  # the common case: nothing waits, nothing to keep
        if resource == "pods":
            return self._note_pod(event_type, obj)
        if resource in MOVING_RESOURCES and event_type != "DELETED":
            return self._move_all()
        return False

    def _note_pod(self, event_type: str, pod: dict) -> bool:
        key = pod_key(pod)
        bound = bool((pod.get("spec") or {}).get("nodeName"))
        with self._lock:
            entry = self._parked.get(key)
            uid = (pod.get("metadata") or {}).get("uid")
            if event_type != "MODIFIED" or bound or (
                    entry is not None and entry.uid != uid):
                # gone, bound by someone else, or another pod of the name
                self._parked.pop(key, None)
                self._attempts.pop(key, None)
                entry = None
            elif entry is not None:
                own = _own_fields(pod)
                if own != entry.own:
                    entry.own = own
                    return self._move([entry])
                return False
        if event_type == "DELETED" and bound:
            return self._move_all()  # a bound pod went: room somewhere
        return False

    def _move_all(self) -> bool:
        with self._lock:
            self.move_seq += 1
            return self._move(list(self._parked.values()))

    def _move(self, entries) -> bool:
        now = self.clock()
        for e in entries:
            if not e.moved:
                e.moved = True
                e.waited = e.backoff_until > now
        return bool(entries)

    def _due_at(self, e: _Parked) -> float:
        flush_at = e.since + FLUSH_AFTER_S
        return min(e.backoff_until, flush_at) if e.moved else flush_at

    def due_in(self) -> float | None:
        """Seconds until the first parked pod comes due (<= 0: one is);
        None when nothing is parked."""
        with self._lock:
            if not self._parked:
                return None
            return min(map(self._due_at, self._parked.values())) - self.clock()

    def release_due(self) -> int:
        """Hand the pods that are due back to the pending list -> how many."""
        now = self.clock()
        released = []
        with self._lock:
            for key, e in list(self._parked.items()):
                if self._due_at(e) <= now:
                    del self._parked[key]
                    released.append(
                        "flush" if not e.moved or e.backoff_until > now
                        else "backoff" if e.waited else "event")
        for reason in released:
            TRACER.inc("pods_requeued_total", reason=reason)
        return len(released)
