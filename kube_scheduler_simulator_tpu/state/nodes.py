"""Columnar node table.

Parses a list of Node manifests (plain dicts, same shape the reference
handles as unstructured objects via client-go) into dense numpy arrays +
per-node label/taint structures.  This is the host-side half of the state
split: label/taint *structure* is static during a replay, so it lives here
and gets baked into dense match arrays by compile.py; the *resource
accumulators* become the device-side carry.

Reference behavior mirrored: the scheduler sees allocatable via
NodeInfo.Allocatable; pods-per-node via AllowedPodNumber; unschedulable
nodes are filtered by the NodeUnschedulable plugin (tolerated by pods that
tolerate the node.kubernetes.io/unschedulable:NoSchedule taint).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from types import MappingProxyType

import numpy as np

from .resources import ResourceSchema
from ..utils.tracing import TRACER

NO_SCHEDULE = "NoSchedule"
PREFER_NO_SCHEDULE = "PreferNoSchedule"
NO_EXECUTE = "NoExecute"


class NodeDerived:
    """What a build derives from a node table alone, or from the table
    and a hashable fragment of a pod's spec, computed on first use and
    kept for the table's life.  A table is an immutable snapshot: every
    node change makes a new NodeTable (build_node_table*,
    patch_node_table*) and with it an empty memo, so an entry is valid
    as long as it can be reached.  Two threads compiling against one
    table may compute an entry twice; both get equal values.

    Kinds (the `kind` label of node_derived_{hits,misses,evictions}_total):
    image_states, taint_max, name_idx (one value a table); image_row,
    taint_rows, spread_eligible (the [N] nodes a spread constraint's
    inclusion policies keep, with how many they leave out), dom_idx,
    affinity_required (NodeAffinity's
    [N] match row of a nodeSelector + required terms), affinity_term (the
    [N] match row of one preferred term, whatever its weight) (one value a
    fragment, at most ROW_CAP a kind, least recently used out);
    statics_device (one
    generation: the last pass's uploaded statics under their digest);
    codec_ctx (one generation: the native codec's context under the
    profile's lineup, weights, schema and custom message tables,
    store/native_decode.py shared_context; None where the LUTs cannot
    express the lineup).
    Arrays are handed out read-only: every consumer copies them into its
    own [P, N] block.  `swap` is no memo: it keeps one note a kind from
    pass to pass on this table (what the last pass's padded affinity axes
    were) and counts nothing."""

    ROW_CAP = 256

    __slots__ = ("_values", "_rows", "_notes", "_lock")

    def __init__(self):
        self._values: dict[str, object] = {}
        self._rows: dict[str, OrderedDict] = {}
        self._notes: dict[str, object] = {}
        self._lock = threading.Lock()

    def swap(self, kind: str, note):
        """Leave `note` for the next pass on this table -> the last pass's
        (None on the table's first pass)."""
        last, self._notes[kind] = self._notes.get(kind), note
        return last

    def once(self, kind: str, make):
        """The table's one value of `kind`."""
        if kind in self._values:
            TRACER.inc("node_derived_hits_total", kind=kind)
            return self._values[kind]
        TRACER.inc("node_derived_misses_total", kind=kind)
        value = self._values[kind] = _frozen(make())
        return value

    def row(self, kind: str, fragment, make):
        """The value of `kind` for one hashable fragment of a pod's spec."""
        with self._lock:
            rows = self._rows.setdefault(kind, OrderedDict())
            value = rows.get(fragment)
            if value is not None:
                rows.move_to_end(fragment)
        if value is not None:
            TRACER.inc("node_derived_hits_total", kind=kind)
            return value
        TRACER.inc("node_derived_misses_total", kind=kind)
        value = _frozen(make())
        with self._lock:
            rows[fragment] = value
            evicted = len(rows) - self.ROW_CAP
            for _ in range(evicted):
                rows.popitem(last=False)
        if evicted > 0:
            TRACER.inc("node_derived_evictions_total", evicted, kind=kind)
        return value

    def generation(self, kind: str, key, make):
        """One generation of `kind`: the value made under `key`, until a
        call with another key replaces it."""
        held = self._values.get(kind)
        if held is not None and held[0] == key:
            TRACER.inc("node_derived_hits_total", kind=kind)
            return held[1]
        TRACER.inc("node_derived_misses_total", kind=kind)
        if held is not None:
            TRACER.inc("node_derived_evictions_total", kind=kind)
        value = make()
        self._values[kind] = (key, value)
        return value


def _frozen(value):
    """numpy arrays (alone or in a tuple) read-only, a dict behind a
    read-only proxy; anything else as it is."""
    if isinstance(value, np.ndarray):
        value.flags.writeable = False
    elif isinstance(value, tuple):
        for v in value:
            _frozen(v)
    elif isinstance(value, dict):
        return MappingProxyType(value)
    return value


@dataclass
class NodeTable:
    names: list[str]
    allocatable: np.ndarray        # [N, R] int64
    allowed_pods: np.ndarray       # [N]    int64
    initial_requested: np.ndarray  # [N, R] int64 (from already-bound pods)
    initial_nonzero: np.ndarray    # [N, 2] int64
    initial_num_pods: np.ndarray   # [N]    int64
    # per-node label dicts / taint tuple lists: a plain list from the
    # manifest build, or a lazy columnar sequence (_LabelRows/_TaintRows,
    # cluster/columnar.py) that synthesizes rows on demand — consumers
    # index/iterate either
    labels: "list[dict[str, str]]"
    taints: "list[list[tuple[str, str, str]]]"
    unschedulable: np.ndarray      # [N] bool
    # the node-derived memo: empty on every new table
    derived: NodeDerived = field(default_factory=NodeDerived, repr=False,
                                 compare=False)

    @property
    def n(self) -> int:
        return len(self.names)

    @property
    def name_idx(self) -> "MappingProxyType[str, int]":
        """node name -> row, for the builders that place bound pods."""
        return self.derived.once(
            "name_idx", lambda: {name: j for j, name in enumerate(self.names)})

    @property
    def max_taints(self) -> int:
        """The longest taint list of any node: TaintToleration's filter
        code and raw score are bounded by it."""
        return self.derived.once(
            "taint_max", lambda: max((len(t) for t in self.taints), default=0))

    def domain_row(self, key: str) -> tuple[np.ndarray, int]:
        """(the [N] int32 domain index of every node under topology key
        `key`, -1 where a node lacks the label; the number of domains),
        domains numbered in node order.  PodTopologySpread's count groups
        and InterPodAffinity's terms index the same row."""
        def make():
            TRACER.inc("spread_rows_built_total", kind="dom_idx")
            labels = self.labels
            vals: dict[str, int] = {}
            row = np.full(self.n, -1, dtype=np.int32)
            for j in range(self.n):
                v = labels[j].get(key)
                if v is not None:
                    row[j] = vals.setdefault(v, len(vals))
            return row, len(vals)

        return self.derived.row("dom_idx", key, make)

    @property
    def label_index(self):
        """Lazy columnar label index for vectorized selector matching
        (state/selectors.LabelIndex); cached on the table."""
        idx = getattr(self, "_label_index", None)
        if idx is None:
            from .selectors import LabelIndex

            idx = LabelIndex(self.labels, self.names)
            object.__setattr__(self, "_label_index", idx)
        return idx


def build_node_table(nodes: list[dict], schema: ResourceSchema) -> NodeTable:
    n = len(nodes)
    names: list[str] = []
    allocatable = np.zeros((n, schema.n), dtype=np.int64)
    allowed = np.full(n, 110, dtype=np.int64)  # kubelet default max-pods
    labels: list[dict[str, str]] = []
    taints: list[list[tuple[str, str, str]]] = []
    unsched = np.zeros(n, dtype=bool)

    for i, node in enumerate(nodes):
        meta = node.get("metadata") or {}
        name = meta.get("name", f"node-{i}")
        names.append(name)
        status = node.get("status") or {}
        alloc = status.get("allocatable") or {}
        allocatable[i] = schema.parse_map(alloc)
        if "pods" in alloc:
            allowed[i] = int(float(alloc["pods"]))
        lab = {k: str(v) for k, v in (meta.get("labels") or {}).items()}
        # kubernetes.io/hostname is implicit on real nodes; KWOK sets it too.
        lab.setdefault("kubernetes.io/hostname", name)
        labels.append(lab)
        spec = node.get("spec") or {}
        taints.append([
            (t.get("key", ""), str(t.get("value", "")), t.get("effect", NO_SCHEDULE))
            for t in spec.get("taints") or []
        ])
        unsched[i] = bool(spec.get("unschedulable", False))

    return NodeTable(
        names=names,
        allocatable=allocatable,
        allowed_pods=allowed,
        initial_requested=np.zeros((n, schema.n), dtype=np.int64),
        initial_nonzero=np.zeros((n, 2), dtype=np.int64),
        initial_num_pods=np.zeros(n, dtype=np.int64),
        labels=labels,
        taints=taints,
        unschedulable=unsched,
    )


def _parse_node_row(node: dict, name: str, schema: ResourceSchema):
    """One node manifest -> (alloc_row, allowed, labels, taints, unsched)
    — the same parse build_node_table does per row, for the columnar
    opaque-row fallback and the delta patch."""
    meta = node.get("metadata") or {}
    status = node.get("status") or {}
    alloc = status.get("allocatable") or {}
    row = schema.parse_map(alloc)
    allowed = int(float(alloc["pods"])) if "pods" in alloc else 110
    lab = {k: str(v) for k, v in (meta.get("labels") or {}).items()}
    lab.setdefault("kubernetes.io/hostname", name)
    spec = node.get("spec") or {}
    taints = [
        (t.get("key", ""), str(t.get("value", "")), t.get("effect", NO_SCHEDULE))
        for t in spec.get("taints") or []
    ]
    return row, allowed, lab, taints, bool(spec.get("unschedulable", False))


def build_node_table_columnar(cols, schema: ResourceSchema) -> NodeTable:
    """NodeTable from a columnar view (cluster/columnar.NodeColumns):
    the numeric surface is gathered vectorized from the bank columns and
    labels/taints stay lazy sequences over the captured column refs — no
    per-node Python loop except for OPAQUE rows (sync faults), which are
    re-parsed from their manifests and patched in as overrides."""
    n = cols.n
    allocatable = cols.alloc_matrix(schema.columns)
    allowed = cols.allowed_pods().copy()
    unsched = cols.unschedulable()
    labels = cols.label_rows()
    taints = cols.taint_rows()
    lab_over: dict[int, dict] = {}
    taint_over: dict[int, list] = {}
    for pos in cols.opaque_positions():
        pos = int(pos)
        row, a, lab, tnt, us = _parse_node_row(
            cols.row_manifest(pos), cols.names[pos], schema)
        allocatable[pos] = row
        allowed[pos] = a
        unsched[pos] = us
        lab_over[pos] = lab
        taint_over[pos] = tnt
    if lab_over:
        labels = labels.with_overrides(lab_over)
        taints = taints.with_overrides(taint_over)
    return NodeTable(
        names=list(cols.names),
        allocatable=allocatable,
        allowed_pods=allowed,
        initial_requested=np.zeros((n, schema.n), dtype=np.int64),
        initial_nonzero=np.zeros((n, 2), dtype=np.int64),
        initial_num_pods=np.zeros(n, dtype=np.int64),
        labels=labels,
        taints=taints,
        unschedulable=unsched,
    )


def patch_node_table(table: NodeTable, nodes: list[dict],
                     changed: "np.ndarray", schema: ResourceSchema) -> NodeTable:
    """Delta path, dict source: same node names in the same order, only
    `changed` positions' manifests differ — re-parse those rows into
    copies of the previous wave's arrays instead of rebuilding all N.
    Returns a NEW NodeTable (tables are immutable snapshots; replay
    buffers may still pin the old one)."""
    allocatable = table.allocatable.copy()
    allowed = table.allowed_pods.copy()
    unsched = table.unschedulable.copy()
    labels = list(table.labels)
    taints = list(table.taints)
    for i in changed:
        i = int(i)
        name = (nodes[i].get("metadata") or {}).get("name", f"node-{i}")
        row, a, lab, tnt, us = _parse_node_row(nodes[i], name, schema)
        allocatable[i] = row
        allowed[i] = a
        unsched[i] = us
        labels[i] = lab
        taints[i] = tnt
    return NodeTable(
        names=table.names,
        allocatable=allocatable,
        allowed_pods=allowed,
        # always zeros at build time; compile copies before priming
        initial_requested=table.initial_requested,
        initial_nonzero=table.initial_nonzero,
        initial_num_pods=table.initial_num_pods,
        labels=labels,
        taints=taints,
        unschedulable=unsched,
    )


def patch_node_table_columnar(table: NodeTable, cols,
                              changed: "np.ndarray",
                              schema: ResourceSchema) -> NodeTable:
    """Delta path, columnar source: gather only the changed rows from
    the current bank columns into copies of the previous wave's arrays.
    Labels/taints for changed rows come in as overrides over the OLD
    lazy sequences (whose captured column refs predate the update's
    copy-on-write)."""
    allocatable = table.allocatable.copy()
    allowed = table.allowed_pods.copy()
    unsched = table.unschedulable.copy()
    rows = cols.rows[changed]
    bank = cols.bank
    for j, rname in enumerate(schema.columns):
        col = bank.res.get(rname)
        allocatable[changed, j] = col[rows] if col is not None else 0
    allowed[changed] = bank.allowed_pods[rows]
    unsched[changed] = bank.unschedulable[rows]
    fresh_labels = cols.label_rows()
    fresh_taints = cols.taint_rows()
    lab_over: dict[int, dict] = {}
    taint_over: dict[int, list] = {}
    opaque = set(int(p) for p in cols.opaque_positions())
    for pos in changed:
        pos = int(pos)
        if pos in opaque:
            row, a, lab, tnt, us = _parse_node_row(
                cols.row_manifest(pos), cols.names[pos], schema)
            allocatable[pos] = row
            allowed[pos] = a
            unsched[pos] = us
            lab_over[pos] = lab
            taint_over[pos] = tnt
        else:
            lab_over[pos] = fresh_labels[pos]
            taint_over[pos] = fresh_taints[pos]
    labels = (table.labels.with_overrides(lab_over)
              if hasattr(table.labels, "with_overrides")
              else _list_with(table.labels, lab_over))
    taints = (table.taints.with_overrides(taint_over)
              if hasattr(table.taints, "with_overrides")
              else _list_with(table.taints, taint_over))
    return NodeTable(
        names=table.names,
        allocatable=allocatable,
        allowed_pods=allowed,
        initial_requested=table.initial_requested,
        initial_nonzero=table.initial_nonzero,
        initial_num_pods=table.initial_num_pods,
        labels=labels,
        taints=taints,
        unschedulable=unsched,
    )


def _list_with(seq, overrides: dict[int, object]) -> list:
    out = list(seq)
    for i, v in overrides.items():
        out[i] = v
    return out
