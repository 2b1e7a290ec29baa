"""Snapshot export/import of whole cluster state as one JSON document.

Capability parity with the reference snapshot service
(reference: simulator/snapshot/snapshot.go):

  * ResourcesForSnap: Pods, Nodes, PVs, PVCs, StorageClasses,
    PriorityClasses, Namespaces + SchedulerConfig (:32-53);
  * Snap(): parallel list in the reference (semaphored errgroup, :103-136)
    — here a single pass over the in-memory store (listing is O(objects));
  * Load(): restart scheduler with the snapshot's config first, then apply
    in dependency order — namespaces barrier, then {priorityclasses,
    storageclasses, pvcs, nodes, pods} barrier, then pvs with bound-PV
    claimRef UID re-resolution (:154-192, :439-470);
  * immutable fields stripped on load; `system-` PriorityClasses and
    `kube-*`/`default` namespaces excluded on both snap and load
    (:541-563);
  * options IgnoreErr and IgnoreSchedulerConfiguration (:89-100).
"""

from __future__ import annotations

import copy
from dataclasses import dataclass

from ..cluster.store import AlreadyExists, ApiError, ObjectStore
from ..utils.errgroup import SemaphoredErrGroup
from ..utils.heap import settle_heap

# JSON field -> store resource, in the apply order of the reference's Load
_FIELDS = [
    ("namespaces", "namespaces"),
    ("priorityClasses", "priorityclasses"),
    ("storageClasses", "storageclasses"),
    ("pvcs", "persistentvolumeclaims"),
    ("nodes", "nodes"),
    ("pods", "pods"),
    ("pvs", "persistentvolumes"),
]


@dataclass
class SnapshotOptions:
    ignore_err: bool = False
    ignore_scheduler_configuration: bool = False


def _ignored_namespace(name: str) -> bool:
    return name.startswith("kube-") or name == "default"


def _ignored_priority_class(name: str) -> bool:
    return name.startswith("system-")


class SnapshotService:
    def __init__(self, store: ObjectStore, scheduler_service):
        self.store = store
        self.scheduler = scheduler_service

    def snap(self, options: SnapshotOptions | None = None) -> dict:
        """One JSON-able dict of the whole cluster.  The manifests are
        SHARED with the store (callers serialize or re-apply via load(),
        which copies) — do not mutate them.

        With ignore_err, a failing kind degrades to an empty list instead
        of failing the snapshot (reference snapshot.go:221-227 per-list
        IgnoreErr handling)."""
        from ..cluster.store import list_shared

        opts = options or SnapshotOptions()
        # the export must carry deferred lazy annotations (store/lazy.py)
        # and full bytes for lazy columnar rows, even though the
        # shared-manifest listing below skips read hooks
        flush = getattr(self.store, "materialize_reads", None)
        if flush is not None:
            flush()
        out: dict = {}
        for field, resource in _FIELDS + self._extra_fields():
            try:
                items = list_shared(self.store, resource)
            except Exception:
                if not opts.ignore_err:
                    raise
                items = []
            if resource == "namespaces":
                items = [i for i in items if not _ignored_namespace(i["metadata"]["name"])]
            if resource == "priorityclasses":
                items = [i for i in items if not _ignored_priority_class(i["metadata"]["name"])]
            out[field] = items
        out["schedulerConfig"] = self.scheduler.get_config()
        return out

    # the reference snapshots the fixed ResourcesForSnap list; a store
    # with registered extra GVRs exports/loads them too, keyed by their
    # plural resource name (they have no dependency edges, so they ride
    # the last apply group)
    _CORE = {r for _, r in _FIELDS} | {"poddisruptionbudgets"}

    def _extra_fields(self) -> list[tuple[str, str]]:
        known = getattr(self.store, "resources", None) or {}
        return [(r, r) for r in known if r not in self._CORE]

    def load(self, snapshot: dict, options: SnapshotOptions | None = None) -> None:
        opts = options or SnapshotOptions()
        if not opts.ignore_scheduler_configuration:
            cfg = snapshot.get("schedulerConfig")
            self.scheduler.restart_scheduler(cfg)

        errors: list[str] = []

        def apply(resource: str, obj: dict):
            obj = copy.deepcopy(obj)
            meta = obj.setdefault("metadata", {})
            for f in ("uid", "resourceVersion", "creationTimestamp"):
                meta.pop(f, None)
            if resource == "persistentvolumes":
                # re-resolve bound PV claim UIDs against the freshly
                # created PVCs (reference: snapshot.go:439-470)
                claim = (obj.get("spec") or {}).get("claimRef")
                if claim:
                    try:
                        pvc = self.store.get(
                            "persistentvolumeclaims", claim.get("name", ""),
                            claim.get("namespace"),
                        )
                        claim["uid"] = pvc["metadata"]["uid"]
                    except ApiError:
                        claim.pop("uid", None)
            try:
                self.store.create(resource, obj)
            except AlreadyExists:
                pass
            except ApiError as e:
                if not opts.ignore_err:
                    raise
                errors.append(str(e))

        # the reference's barrier structure (snapshot.go:154-192):
        # namespaces ∥ → {pcs, scs, pvcs, nodes, pods} ∥ → pvs (which
        # re-resolve PVC UIDs, so PVCs must exist first), each group a
        # bounded-parallel fan-out
        # snapshot fields for GVRs the target store has not registered:
        # infer and register (kind/apiVersion from the objects themselves,
        # like store.restore), so loading a snapshot from an
        # extraResources-configured simulator never silently drops data
        known_fields = {f for f, _ in _FIELDS} | {"schedulerConfig"}
        register = getattr(self.store, "register_resource", None)
        for fld, objs in snapshot.items():
            if (fld in known_fields
                    or fld in getattr(self.store, "resources", {})
                    or not isinstance(objs, list) or not objs
                    or register is None):
                continue
            first = objs[0] or {}
            register(fld, first.get("kind") or fld.capitalize(),
                     namespaced=bool((first.get("metadata") or {}).get("namespace")),
                     api_version=first.get("apiVersion") or "v1")
        extra_fields = self._extra_fields()
        groups = [
            {"namespaces"},
            {"priorityclasses", "storageclasses", "persistentvolumeclaims",
             "nodes", "pods"},
            {"persistentvolumes"} | {r for _, r in extra_fields},
        ]
        for group in groups:
            eg = SemaphoredErrGroup()
            for field, resource in _FIELDS + extra_fields:
                if resource not in group:
                    continue
                for obj in snapshot.get(field) or []:
                    name = (obj.get("metadata") or {}).get("name", "")
                    if resource == "namespaces" and _ignored_namespace(name):
                        continue
                    if resource == "priorityclasses" and _ignored_priority_class(name):
                        continue
                    eg.go(apply, resource, obj)
            eg.wait()
        # what was loaded stays for the session's life: keep the full
        # collections of later passes from walking it (utils/heap.py)
        settle_heap()
