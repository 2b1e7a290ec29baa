"""The volume family's state, carried from pass to pass.

compile_workload used to rebuild the whole of it every pass: every PV,
claim and CSINode manifest parsed again, a fresh [V, N] array filled, and
every bound pod with a volume resolved pod -> claim -> PV once per plugin;
for a cluster that gained one PV, one claim and one bound pod since the
last pass.  A VolumeCarry holds

  the VolumeTable   PVInfo / PVCInfo / StorageClassInfo rows and the
                    arrays made of them (pv_node_ok [V, N], pv_cap,
                    pv_claimed0, csi_limits), patched by the store's watch
                    events on the four volume kinds (VolumeFeed): a PV or
                    claim event parses that one manifest and patches its
                    one row, a CSINode event its node's entries, a
                    StorageClass event that changes a class re-resolves
                    the claims
  the bound rows    per bound pod with a volume (BoundCarry's volume rows)
                    what the three builds derive of it: its CSI volumes
                    (NodeVolumeLimits), its inline disks and RWOP claims
                    (VolumeRestrictions), whether it has an unbound
                    WaitForFirstConsumer claim to replay (VolumeBinding).
                    A row is resolved when its pod changes or a claim or
                    PV it names does (claim key -> rows, PV name -> claims)
  their aggregates  on_node [N, C], used_any / used_rw [N, D] and the RWOP
                    claims in use, as persistent arrays patched on bind and
                    unbind (NodeSlots: reference counts, so a volume two
                    pods share on a node stays when one of them leaves)

so a pass builds only its pending pods' xs against them.

The two cluster-sized arrays, pv_node_ok [V, N] and on_node [N, C] (csi's
plane), also stay on the DEVICE from pass to pass (state/resident.py:
pv_ok_dev, on_node_dev).  The numpy arrays here remain the truth; every
site that writes one tells its journal where (_patch_pv: rows moved, a
row written or cleared; _lay_pvs: its gather; NodeSlots.add / sub /
_release: cells), and the pass's upload sends the patch and not the
array.  What a journal cannot say drops the device copy and the next
pass uploads whole: a resync (_seed), another node table (_derive),
another set of drivers (_place_rows), a bucket that grew (_lay_pvs to
another extent, NodeSlots._reserve: seen as another shape at the upload),
more written than a payload holds.

The V axis is the store's key order (PV names sorted), as a listing gives
it: VolumeBinding breaks ties between equal-capacity PVs by lowest index,
so the order is observable, and a PV created mid-table is inserted there
(one slice move of the arrays).  The C, D and R axes hold the bound pods'
identities first, in the order they were resolved (a freed slot is filled
by the last one), and the pending pods' new ones after; the kernels
reduce over these axes (any / sum), so their order cannot show.

What depends on the node table follows the node table: pv_node_ok's
columns, csi_limits' entries and the aggregates are per node index, and
are derived again from the carried rows, not from manifests, when the
table is another one (volume_carry_rebuilds_total{reason="nodes"}).

compile_workload without a carry (preemption's fit oracle, the sequential
oracle's build_volume_table, tests) seeds a throw-away one from the lists
it is handed (carry_of_lists, reason="uncarried"): the scratch build IS an
empty carry seeded from lists, one code path for both.  A carried state
gives the same CompiledWorkload leaves, up to the order of C, D and R, as
a scratch build on the same store (tests/test_volume_carry.py).
"""

from __future__ import annotations

import bisect
import ctypes
import queue

import numpy as np

# modules, not names: a plugin module imported first imports this package
# while it is itself half initialised (state/boundcarry.py does the same)
from ..cluster.store import VOLUME_KINDS, obj_key
from ..plugins import nodevolumelimits, volumebinding, volumerestrictions
from ..utils.tracing import TRACER
from . import volumes as vol
from .boundcarry import _RESYNC_BACKLOG, BoundCarry
from .resident import CellsResident, RowsResident
from .nodes import NodeTable
from .selectors import node_selector_matches

# PV events of one pass applied to the [V, N] array by slice moves; past
# this (an import), the rows are gathered into a new array once
_IN_PLACE_MAX = 4


class VolumeFeed:
    """The four volume kinds of a store and what became of their objects
    since the last drain, from the store's own watch events (as BoundFeed
    is for pods).  One consumer: the engine's pass."""

    def __init__(self, store):
        self.store = store
        self._qs: dict | None = None

    def drain(self):
        """-> ("resync", {kind: {store key: manifest}}) with every object,
        the first time and after a backlog it was cheaper to drop; else
        ("delta", {kind: {store key: newest manifest | None}}): created or
        changed, and deleted (None), since the last drain."""
        if self._qs is None or any(q.qsize() > _RESYNC_BACKLOG
                                   for _, q in self._qs.values()):
            self.close()
            self._qs, listed = {}, {}
            for kind, resource in VOLUME_KINDS:
                namespaced = self.store.resources[resource][1]
                items, _rv, q = self.store.list_and_watch(resource)
                self._qs[kind] = (namespaced, q)
                listed[kind] = {obj_key(obj, namespaced): obj
                                for obj in items}
            return "resync", listed
        changes: dict[str, dict] = {}
        for kind, (namespaced, q) in self._qs.items():
            while True:
                try:
                    _rv, event_type, obj = q.get_nowait()
                except queue.Empty:
                    break
                changes.setdefault(kind, {})[obj_key(obj, namespaced)] = (
                    None if event_type == "DELETED" else obj)
        return "delta", changes

    def close(self) -> None:
        if self._qs is not None:
            for (kind, resource) in VOLUME_KINDS:
                self.store.unwatch(resource, self._qs[kind][1])
            self._qs = None


class NodeSlots:
    """Identities (a CSI volume, an inline disk, an RWOP claim) of the
    bound pods, interned as dense slots 0..n-1 of an axis, with a tag a
    slot (the CSI volume's driver index, the disk's strict flag) and, per
    plane, the [N, extent] bitmap of the nodes that hold it.  Reference
    counted: per slot the rows that name it, per (slot, node, plane) the
    rows that put it there, so a volume on a node by two pods counts once
    and stays when one of them leaves.  A slot nobody names is filled by
    the last one: the slots stay dense, and columns past n stay clear.
    `wrote(node, slot)` is told every cell of a plane that changes."""

    def __init__(self, planes: int, n_nodes: int = 0, wrote=None):
        self._wrote = wrote or (lambda j, s: None)
        self.slot: dict = {}                    # identity -> slot
        self._idents: list = []                 # slot -> identity
        self._refs: list[int] = []              # slot -> rows naming it
        self._held: list[dict] = []             # slot -> {node: [rows a plane]}
        self.tags = np.zeros(0, dtype=np.int32)
        self._bits = [np.zeros((n_nodes, 0), dtype=bool) for _ in range(planes)]

    @property
    def n(self) -> int:
        return len(self._idents)

    def _reserve(self, extent: int) -> None:
        cap = self.tags.shape[0]
        if extent <= cap:
            return
        # an extent asked for is a bucket or a count: doubling follows the
        # buckets exactly, so the padded plane is handed over whole
        new = max(extent, 2 * cap)
        tags = np.zeros(new, dtype=np.int32)
        tags[:cap] = self.tags
        self.tags = tags
        for k, b in enumerate(self._bits):
            grown = np.zeros((b.shape[0], new), dtype=bool)
            grown[:, :cap] = b
            self._bits[k] = grown

    def add(self, ident, j: int | None, planes=(), tag: int = 0) -> None:
        """One row names `ident`; on node j (None: a node the table does
        not know) it sets the planes flagged in `planes`."""
        s = self.slot.get(ident)
        if s is None:
            s = self.slot[ident] = len(self._idents)
            self._idents.append(ident)
            self._refs.append(0)
            self._held.append({})
            self._reserve(s + 1)
            self.tags[s] = tag
        self._refs[s] += 1
        if j is None:
            return
        held = self._held[s].get(j)
        if held is None:
            held = self._held[s][j] = [0] * len(self._bits)
        for k, flagged in enumerate(planes):
            if flagged:
                held[k] += 1
                if held[k] == 1:
                    self._bits[k][j, s] = True
                    self._wrote(j, s)

    def sub(self, ident, j: int | None, planes=()) -> None:
        """Take back one add() with the same arguments."""
        s = self.slot[ident]
        if j is not None:
            held = self._held[s][j]
            for k, flagged in enumerate(planes):
                if flagged:
                    held[k] -= 1
                    if not held[k]:
                        self._bits[k][j, s] = False
                        self._wrote(j, s)
            if not any(held):
                del self._held[s][j]
        self._refs[s] -= 1
        if not self._refs[s]:
            self._release(s)

    def _release(self, s: int) -> None:
        last = len(self._idents) - 1
        del self.slot[self._idents[s]]
        if s != last:
            self._idents[s] = self._idents[last]
            self.slot[self._idents[s]] = s
            self._refs[s], self._held[s] = self._refs[last], self._held[last]
            self.tags[s] = self.tags[last]
            for b in self._bits:
                b[:, s] = b[:, last]
                b[:, last] = False
            # column s was clear (nobody named it): the cells that changed
            # are the last slot's, in both columns
            for j in self._held[s]:
                self._wrote(j, s)
                self._wrote(j, last)
        self._idents.pop()
        self._refs.pop()
        self._held.pop()
        self.tags[last] = 0

    def plane(self, k: int, extent: int) -> np.ndarray:
        """[N, extent] bool: plane k over the first `extent` slots (the
        carry's own array: not the caller's to change)."""
        self._reserve(extent)
        b = self._bits[k]
        return b if b.shape[1] == extent else b[:, :extent]


class _Row:
    """What the family's builds derive of one bound pod."""

    __slots__ = ("pod", "node_name", "j", "claims", "csi", "csi_in", "disks",
                 "rwop", "wffc")


def _unlink(index: dict, at, member) -> None:
    """Take `member` out of index[at], and an emptied set out of the index."""
    members = index.get(at)
    if members is not None:
        members.discard(member)
        if not members:
            del index[at]


def _shift_rows(a: np.ndarray, i: int, v: int, by: int) -> None:
    """a[i + by:v + by] = a[i:v] along the first axis of a C-contiguous
    array, source and target overlapping: one memmove (numpy copies the
    source first, which for the [V, N] array is most of the move's time)."""
    if not (a.flags.c_contiguous and 0 <= i <= v
            and 0 <= i + by and v + by <= a.shape[0]):
        raise ValueError(f"rows {i}:{v} by {by} of {a.shape}")
    row = a.strides[0]
    ctypes.memmove(a.ctypes.data + (i + by) * row, a.ctypes.data + i * row,
                   (v - i) * row)


def _parse_csinode(cn: dict) -> tuple[str, tuple]:
    """-> (node name, ((driver, count), ...)) of the drivers that publish
    a count."""
    out = []
    for drv in ((cn.get("spec") or {}).get("drivers")) or []:
        count = (drv.get("allocatable") or {}).get("count")
        if count is not None:
            out.append((drv.get("name", ""), int(count)))
    return (cn.get("metadata") or {}).get("name", ""), tuple(out)


class VolumeCarry:
    def __init__(self, feed: VolumeFeed | None = None,
                 listed: dict | None = None):
        """feed: the store's events, drained every pass; or `listed`, the
        {kind: {key: manifest}} a throw-away carry is seeded from."""
        self.feed = feed
        # axis -> the padded extent the V and C axes had in this carry's
        # last pass (state/compile.py _count_rebuckets)
        self.axes: dict[str, int] = {}
        self._table: NodeTable | None = None
        self._name_idx = None
        self._bound: BoundCarry | None = None
        self._touched: set = set()      # the bound carry's journal, once followed
        # the two cluster-sized arrays as the device holds them
        # (state/resident.py): vt.pv_node_ok and csi's plane, each with the
        # journal of what this carry wrote to it since the last pass.  A
        # throw-away carry has no next pass: compile_workload makes
        # nothing of it resident, and a journal without a device copy
        # records nothing
        self.pv_ok_dev = RowsResident()
        self.on_node_dev = CellsResident()
        self._seed(listed or {})

    # ---------------------------------------------------- the parsed rows

    def _seed(self, listed: dict) -> None:
        """Every object again, from {kind: {key: manifest}} in the order a
        listing gives them: today's full parse, counted.  The arrays and
        the bound rows follow in advance()."""
        self._class_src = dict(listed.get("storageclasses") or {})
        classes, default = vol.parse_storage_classes(
            list(self._class_src.values()))
        pvs = listed.get("pvs") or {}
        self._pv_keys = list(pvs)
        self.vt = vol.VolumeTable(
            pvcs={}, pvs=[vol._parse_pv(pv) for pv in pvs.values()],
            pv_index={}, classes=classes, default_class=default,
            pv_node_ok=np.zeros((0, 0), dtype=bool),
            pv_cap=np.zeros(0, dtype=np.int64),
            pv_claimed0=np.ones(0, dtype=bool), csi_limits={})
        TRACER.inc("volume_manifests_parsed_total", len(pvs), kind="pv")
        self._pvc_src = dict(listed.get("pvcs") or {})
        self._parse_claims()
        self._csi_rows = {key: _parse_csinode(cn) for key, cn in
                          (listed.get("csinodes") or {}).items()}
        TRACER.inc("volume_manifests_parsed_total", len(self._csi_rows),
                   kind="csinode")
        self._table = None              # nothing derived yet
        self._forget_rows()
        self.pv_ok_dev.drop("resync")
        self.on_node_dev.drop("resync")

    def _parse_claims(self) -> None:
        vt = self.vt
        vt.pvcs = {key: vol._parse_pvc(pvc, vt.classes, vt.default_class)
                   for key, pvc in self._pvc_src.items()}
        TRACER.inc("volume_manifests_parsed_total", len(vt.pvcs), kind="pvc")
        self._claims_of_pv: dict[str, set] = {}
        for key, info in vt.pvcs.items():
            if info.volume_name:
                self._claims_of_pv.setdefault(info.volume_name, set()).add(key)

    def _forget_rows(self) -> None:
        self._rows: dict = {}                   # bound pod key -> _Row
        self._rows_of_claim: dict[str, set] = {}
        self._dirty: set = set()                # rows whose claim or PV changed
        self._wffc: set = set()                 # rows VolumeBinding replays
        self._all_rows = True                   # read every row of the bound carry

    # ------------------------------------------------- a pass, in order

    def advance(self, table: NodeTable, bound: BoundCarry) -> vol.VolumeTable:
        """Once a pass, after the node table and the bound carry are this
        pass's: the table brought up to date, then the bound rows that
        changed, or whose claim or PV did, resolved."""
        vt = self.table_on(table)
        self._follow(bound)
        return vt

    def table_on(self, table: NodeTable) -> vol.VolumeTable:
        """Apply what the store did to the four kinds since the last pass
        and follow the node table -> the table (the carry's own: the next
        pass patches it)."""
        kind, changes = (self.feed.drain() if self.feed is not None
                         else ("delta", {}))
        if kind == "resync":
            self._seed(changes)
            TRACER.inc("volume_carry_rebuilds_total", reason="resync")
        if table is not self._table:
            if self._table is not None and (
                    self.vt.pvs or self._csi_rows or self._rows):
                TRACER.inc("volume_carry_rebuilds_total", reason="nodes")
            self._derive(table)
        if kind == "delta" and changes:
            self._patch(changes)
        TRACER.gauge("volume_table_pvs", len(self.vt.pvs))
        return self.vt

    def _derive(self, table: NodeTable) -> None:
        """Everything that is per node index, from the carried rows."""
        self._table, self._name_idx = table, table.name_idx
        self.pv_ok_dev.drop("nodes")
        self._lay_pvs(self._pv_keys, self.vt.pvs, None)
        limits = self.vt.csi_limits = {}
        self._driver_refs: dict[str, int] = {}
        for row in self._csi_rows.values():
            self._set_limits(row, +1)
        self._drivers = tuple(sorted(limits))
        self._place_rows("nodes")

    def _patch(self, changes: dict) -> None:
        classes = changes.get("storageclasses")
        if classes:
            self._patch_classes(classes)
        for key, pvc in (changes.get("pvcs") or {}).items():
            self._patch_pvc(key, pvc)
        if changes.get("pvs"):
            self._patch_pvs(changes["pvs"])
        for key, cn in (changes.get("csinodes") or {}).items():
            self._set_limits(self._csi_rows.pop(key, None), -1)
            if cn is not None:
                row = self._csi_rows[key] = _parse_csinode(cn)
                TRACER.inc("volume_manifests_parsed_total", kind="csinode")
                self._set_limits(row, +1)
        drivers = tuple(sorted(self.vt.csi_limits))
        if drivers != self._drivers:
            # the C axis holds the volumes of drivers with a limit, tagged
            # by the driver's index: another set of drivers, another axis
            self._drivers = drivers
            TRACER.inc("volume_carry_rebuilds_total", reason="drivers")
            self._place_rows("drivers")

    # ------------------------------------------------- classes and claims

    def _patch_classes(self, changes: dict) -> None:
        for key, sc in changes.items():
            if sc is None:
                self._class_src.pop(key, None)
            else:
                self._class_src[key] = sc
        vt = self.vt
        classes, default = vol.parse_storage_classes(
            [self._class_src[key] for key in sorted(self._class_src)])
        if (classes, default) == (vt.classes, vt.default_class):
            return
        # a claim's class is resolved against the default, and a row's
        # unbound claims against the classes: all of them again
        vt.classes, vt.default_class = classes, default
        self._parse_claims()
        self._dirty.update(self._rows)
        TRACER.inc("volume_carry_rebuilds_total", reason="classes")

    def _patch_pvc(self, key: str, pvc: dict | None) -> None:
        vt = self.vt
        old = vt.pvcs.pop(key, None)
        if old is not None and old.volume_name:
            _unlink(self._claims_of_pv, old.volume_name, key)
        if pvc is None:
            self._pvc_src.pop(key, None)
        else:
            self._pvc_src[key] = pvc
            info = vt.pvcs[key] = vol._parse_pvc(pvc, vt.classes,
                                                 vt.default_class)
            TRACER.inc("volume_manifests_parsed_total", kind="pvc")
            if info.volume_name:
                self._claims_of_pv.setdefault(info.volume_name, set()).add(key)
        self._dirty.update(self._rows_of_claim.get(key, ()))

    # ------------------------------------------------------------- PVs

    def _lay_pvs(self, keys: list, infos: list, src: np.ndarray | None) -> None:
        """The V axis over `infos` in this order, in new arrays: row i is
        the old row src[i], or derived from infos[i] where src[i] < 0
        (src None: every row)."""
        vt, table = self.vt, self._table
        v, n = len(infos), table.n
        extent = vol.axis_bucket(v)
        if src is not None and extent == vt.pv_node_ok.shape[0]:
            self.pv_ok_dev.relaid(src, v)
        else:
            self.pv_ok_dev.drop("bucket")
        # a row past v is no PV anyone can claim: claimed, of capacity 0,
        # OK on no node
        ok = np.zeros((extent, n), dtype=bool)
        if src is None:
            fresh = range(v)
        else:
            kept = src >= 0
            ok[:v][kept] = vt.pv_node_ok[src[kept]]
            fresh = np.flatnonzero(~kept).tolist()
        # a PV without nodeAffinity is one True row, not a walk of N nodes
        ok[np.asarray([i for i in fresh if infos[i].node_affinity is None],
                      dtype=np.intp)] = True
        vt.pv_node_ok = ok
        for i in fresh:
            if infos[i].node_affinity is not None:
                self._walk_nodes(i, infos[i])
        vt.pv_cap = np.zeros(extent, dtype=np.int64)
        vt.pv_cap[:v] = [pv.capacity for pv in infos]
        vt.pv_claimed0 = np.ones(extent, dtype=bool)
        vt.pv_claimed0[:v] = [pv.claim_ref is not None for pv in infos]
        self._pv_keys, vt.pvs = keys, infos
        vt.pv_index = {pv.name: i for i, pv in enumerate(infos)}

    def _walk_nodes(self, i: int, pv: vol.PVInfo) -> None:
        table, ok = self._table, self.vt.pv_node_ok
        for j in range(table.n):
            ok[i, j] = node_selector_matches(
                pv.node_affinity, table.labels[j], table.names[j])

    def _patch_pvs(self, changes: dict) -> None:
        vt = self.vt
        v = len(vt.pvs)
        for key, pv in changes.items():
            # a feed's key is the PV's name
            self._dirty_claims_of(key)
            v += (pv is not None) - (key in vt.pv_index)
        if (len(changes) <= _IN_PLACE_MAX
                and vol.axis_bucket(v) == vt.pv_cap.shape[0]):
            moved = [self._patch_pv(key, changes[key]) for key in sorted(changes)]
            if any(moved):
                vt.pv_index = dict(zip(self._pv_keys, range(len(self._pv_keys))))
            return
        rows = {key: (info, i)
                for i, (key, info) in enumerate(zip(self._pv_keys, vt.pvs))}
        for key, pv in changes.items():
            if pv is None:
                rows.pop(key, None)
            else:
                rows[key] = (self._parsed_pv(pv), -1)
        keys = sorted(rows)
        self._lay_pvs(keys, [rows[key][0] for key in keys],
                      np.asarray([rows[key][1] for key in keys], dtype=np.int64))

    def _parsed_pv(self, pv: dict) -> vol.PVInfo:
        TRACER.inc("volume_manifests_parsed_total", kind="pv")
        return vol._parse_pv(pv)

    def _patch_pv(self, key: str, pv: dict | None) -> bool:
        """One PV in place, the arrays' bucket holding -> whether rows
        moved (the indices past it are others now)."""
        vt, keys = self.vt, self._pv_keys
        arrays = ((vt.pv_node_ok, False), (vt.pv_cap, 0), (vt.pv_claimed0, True))
        v = len(keys)
        i = bisect.bisect_left(keys, key)
        present = i < v and keys[i] == key
        if pv is None:
            if present:
                del keys[i], vt.pvs[i]
                for a, padding in arrays:
                    _shift_rows(a, i + 1, v, -1)
                    a[v - 1] = padding
                self.pv_ok_dev.moved(i + 1, v, -1)
                self.pv_ok_dev.wrote(v - 1, cleared=True)
            return present
        info = self._parsed_pv(pv)
        if present:
            vt.pvs[i] = info
        else:
            # the store's order: one slice move of the [V, N] array
            keys.insert(i, key)
            vt.pvs.insert(i, info)
            for a, _ in arrays:
                _shift_rows(a, i, v, +1)
            self.pv_ok_dev.moved(i, v, +1)
        self.pv_ok_dev.wrote(i)
        vt.pv_cap[i] = info.capacity
        vt.pv_claimed0[i] = info.claim_ref is not None
        if info.node_affinity is None:
            vt.pv_node_ok[i] = True
        else:
            self._walk_nodes(i, info)
        return not present

    def _dirty_claims_of(self, pv_name: str) -> None:
        for claim in self._claims_of_pv.get(pv_name, ()):
            self._dirty.update(self._rows_of_claim.get(claim, ()))

    # -------------------------------------------------------- CSINodes

    def _set_limits(self, row: tuple | None, sign: int) -> None:
        """Write (+1) or take back (-1) one CSINode's counts."""
        if row is None:
            return
        j = self._name_idx.get(row[0])
        if j is None:
            return
        limits, refs = self.vt.csi_limits, self._driver_refs
        for driver, count in row[1]:
            if sign > 0:
                if driver not in limits:
                    limits[driver] = np.full(self._table.n, -1, dtype=np.int64)
                limits[driver][j] = count
                refs[driver] = refs.get(driver, 0) + 1
            else:
                limits[driver][j] = -1
                refs[driver] -= 1
                if not refs[driver]:    # no node publishes a count for it
                    del limits[driver], refs[driver]

    # ------------------------------------------------- the bound pods' rows

    def _place_rows(self, why: str) -> None:
        """The three aggregates again from the resolved rows: another node
        table (why: "nodes"), or another set of drivers with a limit
        ("drivers")."""
        n = self._table.n
        self._d_idx = {d: i for i, d in enumerate(self._drivers)}
        self.on_node_dev.drop(why)
        # (driver, handle); tag: driver index
        self.csi = NodeSlots(1, n, wrote=self.on_node_dev.wrote)
        self.disks = NodeSlots(2, n)    # inline disk; planes any, rw; tag: strict
        self.rwops = NodeSlots(0)       # RWOP claim key
        for key in sorted(self._rows):
            self._apply(self._rows[key], +1)

    def _follow(self, bound: BoundCarry) -> None:
        dirty, self._dirty = self._dirty, set()
        if bound is not self._bound or self._all_rows:
            self._bound, self._all_rows = bound, False
            if self.feed is not None:
                bound.volume_journal = self._touched
            keys = set(bound.volume_keys()).union(self._rows)
        else:
            keys = dirty.union(self._touched)
        self._touched.clear()
        for key in sorted(keys):
            new = bound.volume_row(key)
            row = self._rows.get(key)
            if row is not None:
                if (new is not None and new[0] is row.pod
                        and new[1] == row.node_name and key not in dirty):
                    continue
                self._apply(row, -1)
                for claim in row.claims:
                    _unlink(self._rows_of_claim, claim, key)
                del self._rows[key]
                self._wffc.discard(key)
            if new is not None:
                row = self._rows[key] = self._resolve(*new)
                for claim in row.claims:
                    self._rows_of_claim.setdefault(claim, set()).add(key)
                if row.wffc:
                    self._wffc.add(key)
                self._apply(row, +1)

    def _resolve(self, pod: dict, node_name: str) -> _Row:
        """pod -> claim -> PV, once for the three builds."""
        vt = self.vt
        row = _Row()
        row.pod, row.node_name = pod, node_name
        row.claims = vol.pod_pvc_keys(pod)
        row.csi = nodevolumelimits.pod_csi_volumes(vt, pod)
        row.disks = volumerestrictions.pod_inline_disks(pod)
        row.rwop = volumerestrictions.pod_rwop_keys(vt, pod)
        # prime_claims replays, in bound order, the rows with an unbound
        # WaitForFirstConsumer claim; a row of bound claims is a no-op there
        reject, _, unbound = volumebinding.classify_pod(vt, pod)
        row.wffc = reject is None and bool(unbound)
        TRACER.count("volume_bound_rows_walked_total")
        return row

    def _apply(self, row: _Row, sign: int) -> None:
        """Add (+1) what a row gives the aggregates, under this node table
        and these drivers, or take back (-1) what it gave."""
        if sign > 0:
            row.j = self._name_idx.get(row.node_name)
            d_idx = self._d_idx
            # a volume of a driver without a limit is irrelevant to the filter
            row.csi_in = [(v, d_idx[v[0]]) for v in row.csi if v[0] in d_idx]
            for v, d in row.csi_in:
                self.csi.add(v, row.j, (True,), tag=d)
            for ident, ro in row.disks:
                self.disks.add(ident, row.j, (True, not ro),
                               tag=ident[0] == "aws")
            for key in row.rwop:
                self.rwops.add(key, None)
        else:
            for v, _ in row.csi_in:
                self.csi.sub(v, row.j, (True,))
            for ident, ro in row.disks:
                self.disks.sub(ident, row.j, (True, not ro))
            for key in row.rwop:
                self.rwops.sub(key, None)

    # ------------------------------------------------------------ reads

    def wffc_rows(self) -> list[tuple[dict, str]]:
        """(pod, node name) of the bound pods with an unbound
        WaitForFirstConsumer claim, in bound order: what prime_claims
        replays."""
        return [(self._rows[key].pod, self._rows[key].node_name)
                for key in sorted(self._wffc)]

    def close(self) -> None:
        if self.feed is not None:
            self.feed.close()


def carry_of_lists(volumes: dict | None) -> VolumeCarry:
    """A throw-away carry over given manifest lists ({"pvcs": [...],
    "pvs": [...], "storageclasses": [...], "csinodes": [...]}): what
    compile_workload and build_volume_table build when they are handed
    lists.  PVs, classes and CSINodes are keyed by position (the V axis is
    the list's order), claims by namespace/name (the last of a name wins,
    as in a dict)."""
    volumes = volumes or {}
    carry = VolumeCarry(listed={
        "pvcs": {vol._key(pvc): pvc for pvc in volumes.get("pvcs") or ()},
        **{kind: dict(enumerate(volumes.get(kind) or ()))
           for kind in ("pvs", "storageclasses", "csinodes")}})
    TRACER.inc("volume_carry_rebuilds_total", reason="uncarried")
    return carry
