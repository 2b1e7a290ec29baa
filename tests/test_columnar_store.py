"""Golden byte-parity: the columnar data plane vs the dict baseline.

The ColumnarStatusStore (cluster/columnar.py) backs nodes/pods with
numpy hot-field columns while the dict CRUD/watch/dump surface stays a
compat shim — these tests pin the shim to the PRE-columnar store
byte-for-byte.  Every suite runs the same operation sequence against a
columnar store (KSS_TPU_COLUMNAR=1, the default) and a dict-baseline
store (KSS_TPU_COLUMNAR=0) with uuid/time pinned, and compares the raw
`json.dumps` bytes (insertion order included) of every read surface:
get, list, watch events, dump, snapshot export.  The chaos seam
`store.columnar_sync` proves a mid-sync fault leaves the shim
consistent: the row goes opaque, the manifest stays authoritative, and
the columnar node-table build re-parses it (docs/data-plane.md).

Uid pinning: lazy rows draw their uid on FIRST READ, the eager path at
create — so each store runs its ops as a phase with the pinned uuid
counter reset at the phase start, and materializes its lazy rows in row
order (materialize_reads) so both phases assign uid k to the same
logical object.
"""

from __future__ import annotations

import itertools
import json
import time
import uuid

import numpy as np
import pytest

from kube_scheduler_simulator_tpu.cluster.columnar import LazyManifest
from kube_scheduler_simulator_tpu.cluster.store import ObjectStore, list_shared
from kube_scheduler_simulator_tpu.models.workloads import (
    make_nodes_columnar, make_pods_columnar)
from kube_scheduler_simulator_tpu.utils import faults
from kube_scheduler_simulator_tpu.utils.faults import (
    FaultPlan, FaultRule)


class _UuidPin:
    def __init__(self):
        self.reset()

    def reset(self):
        self._c = itertools.count()

    def __call__(self):
        return f"00000000-0000-4000-8000-{next(self._c):012d}"


@pytest.fixture
def pin(monkeypatch):
    """Pin uuid.uuid4 (resettable counter) and the store's
    creationTimestamp clock so both stores stamp identical bytes for
    identical per-phase operation sequences."""
    p = _UuidPin()
    monkeypatch.setattr(uuid, "uuid4", p)
    monkeypatch.setattr(time, "gmtime", lambda *a: time.struct_time(
        (2026, 1, 1, 0, 0, 0, 3, 1, 0)))
    return p


def make_store(monkeypatch, columnar: bool) -> ObjectStore:
    monkeypatch.setenv("KSS_TPU_COLUMNAR", "1" if columnar else "0")
    store = ObjectStore()
    monkeypatch.delenv("KSS_TPU_COLUMNAR")
    return store


def raw(obj) -> str:
    """Raw (insertion-ordered) JSON bytes of a possibly-lazy manifest,
    materialized the way real serializers must (json's C encoder walks
    dict storage, bypassing LazyManifest's overrides)."""
    LazyManifest.ensure(obj)
    return json.dumps(obj)


def load_population(s: ObjectStore, n_nodes=40, n_pods=25):
    s.load_columnar("nodes", make_nodes_columnar(
        n_nodes, seed=3, taint_fraction=0.2, unschedulable_fraction=0.1))
    s.load_columnar("pods", make_pods_columnar(
        n_pods, seed=4, with_affinity=True))


def load_both(pin, monkeypatch, materialize=True, **kw):
    """(columnar store, dict store) holding the same generated
    population, uid-aligned: each load runs as its own pinned phase, and
    the columnar store materializes its lazy rows in row order — the
    same order the dict store's eager fallback created them."""
    a = make_store(monkeypatch, True)
    pin.reset()
    load_population(a, **kw)
    if materialize:
        a.materialize_reads()
    b = make_store(monkeypatch, False)
    pin.reset()
    load_population(b, **kw)
    return a, b


NODE = {
    "metadata": {"name": "crud-node", "labels": {"zone": "z1"}},
    "spec": {"taints": [{"key": "k", "value": "v", "effect": "NoSchedule"}]},
    "status": {"allocatable": {"cpu": "8000m", "memory": "1073741824",
                               "example.com/gpu": "4", "pods": "110"}},
}
POD = {
    "metadata": {"name": "crud-pod", "labels": {"app": "a0"}},
    "spec": {"containers": [{"name": "c", "resources": {
        "requests": {"cpu": "250m", "memory": "2097152"}}}]},
}


def crud_sequence(s: ObjectStore) -> None:
    """The golden op sequence: create, update, delete, re-create."""
    s.create("nodes", json.loads(json.dumps(NODE)))
    s.create("pods", json.loads(json.dumps(POD)))
    nd = s.get("nodes", "crud-node")
    nd["status"]["allocatable"]["cpu"] = "16000m"
    nd["metadata"]["labels"]["zone"] = "z2"
    s.update("nodes", nd)
    s.delete("pods", "crud-pod")
    s.create("pods", json.loads(json.dumps(POD)))


def test_crud_surface_byte_parity(pin, monkeypatch):
    a = make_store(monkeypatch, True)
    b = make_store(monkeypatch, False)
    qa, qb = a.watch("nodes"), b.watch("nodes")
    for s in (a, b):
        pin.reset()
        crud_sequence(s)
    assert raw(a.get("nodes", "crud-node")) == raw(b.get("nodes", "crud-node"))
    assert raw(a.get("pods", "crud-pod")) == raw(b.get("pods", "crud-pod"))
    la, rva = a.list("nodes")
    lb, rvb = b.list("nodes")
    assert rva == rvb and [raw(o) for o in la] == [raw(o) for o in lb]
    assert raw(a.dump()) == raw(b.dump())
    # identical watch streams, rv for rv
    ev_a = [qa.get_nowait() for _ in range(qa.qsize())]
    ev_b = [qb.get_nowait() for _ in range(qb.qsize())]
    assert ([(rv, t, raw(o)) for rv, t, o in ev_a]
            == [(rv, t, raw(o)) for rv, t, o in ev_b])


def test_lazy_rows_byte_identical_to_eager_path(pin, monkeypatch):
    """load_columnar's LAZY rows must synthesize the same bytes — raw
    insertion order included — the eager fallback stores."""
    a, b = load_both(pin, monkeypatch)
    for resource in ("nodes", "pods"):
        la, rva = a.list(resource)
        lb, rvb = b.list(resource)
        assert rva == rvb
        assert [raw(o) for o in la] == [raw(o) for o in lb]
    assert (raw(a.get("nodes", "node-00007"))
            == raw(b.get("nodes", "node-00007")))
    assert (raw(a.get("pods", "pod-00003"))
            == raw(b.get("pods", "pod-00003")))
    assert raw(a.dump()) == raw(b.dump())


def test_watch_events_from_bulk_load_match_eager(pin, monkeypatch):
    a = make_store(monkeypatch, True)
    b = make_store(monkeypatch, False)
    qa, qb = a.watch("nodes"), b.watch("nodes")
    pin.reset()
    a.load_columnar("nodes", make_nodes_columnar(12, seed=3))
    a.materialize_reads()
    pin.reset()
    b.load_columnar("nodes", make_nodes_columnar(12, seed=3))
    ev_a = [qa.get_nowait() for _ in range(qa.qsize())]
    ev_b = [qb.get_nowait() for _ in range(qb.qsize())]
    assert len(ev_a) == 12
    assert ([(rv, t, raw(o)) for rv, t, o in ev_a]
            == [(rv, t, raw(o)) for rv, t, o in ev_b])


def test_update_and_delete_of_lazy_rows(pin, monkeypatch):
    """Mutating a lazy row (update / delete / re-create) keeps the shim
    on the dict baseline: rv sequencing, tombstoned reads, final bytes."""
    a, b = load_both(pin, monkeypatch)
    for s in (a, b):
        pin.reset()
        nd = s.get("nodes", "node-00003")
        nd["status"]["allocatable"]["cpu"] = "123000m"
        s.update("nodes", nd)
        s.delete("nodes", "node-00005")
        s.create("nodes", {"metadata": {"name": "node-00005"},
                           "status": {"allocatable": {"cpu": "1000m",
                                                      "pods": "10"}}})
        with pytest.raises(Exception):
            s.get("nodes", "node-00099")
    la, rva = a.list("nodes")
    lb, rvb = b.list("nodes")
    assert rva == rvb
    assert [raw(o) for o in la] == [raw(o) for o in lb]
    # re-created row carries a fresh rv, identical on both sides
    assert (a.get("nodes", "node-00005")["metadata"]["resourceVersion"]
            == b.get("nodes", "node-00005")["metadata"]["resourceVersion"])


def test_materialize_reads_fills_lazy_rows(pin, monkeypatch):
    """The read-hook flush surface: shared (no-copy) listings hand out
    lazy rows whose dict storage is EMPTY until filled — json's C
    encoder would serialize {}.  materialize_reads() is the documented
    pre-serialization flush and must leave the shared objects carrying
    full bytes."""
    a, b = load_both(pin, monkeypatch, materialize=False)
    sa = list_shared(a, "nodes")
    lazy = [o for o in sa if type(o) is LazyManifest and not dict.__len__(o)]
    assert lazy, "expected unfilled lazy rows before the flush"
    assert json.dumps(lazy[0]) == "{}"  # the bypass materialize guards
    pin.reset()
    a.materialize_reads()
    assert all(dict.__len__(o) for o in list_shared(a, "nodes"))
    assert ([json.dumps(o) for o in list_shared(a, "nodes")]
            == [raw(o) for o in list_shared(b, "nodes")])


def test_snapshot_export_byte_parity(pin, monkeypatch):
    from kube_scheduler_simulator_tpu.services.snapshot import SnapshotService

    class _Sched:
        def get_config(self):
            return {"profiles": []}

    a, b = load_both(pin, monkeypatch, n_nodes=15, n_pods=10)
    # snap() returns SHARED manifests; its materialize_reads() pass must
    # fill every lazy row, so callers' direct json.dumps is byte-safe
    snap_a = SnapshotService(a, _Sched()).snap()
    snap_b = SnapshotService(b, _Sched()).snap()
    assert json.dumps(snap_a) == json.dumps(snap_b)


def test_columnar_off_pins_dict_baseline(monkeypatch):
    s = make_store(monkeypatch, False)
    assert not s._banks
    n = s.load_columnar("nodes", make_nodes_columnar(8, seed=1))
    assert n == 8
    assert all(type(o) is dict for o in list_shared(s, "nodes"))


def test_columnar_sync_fault_leaves_shim_consistent(pin, monkeypatch):
    """A fault injected at the store.columnar_sync seam mid-update must
    never surface to the writer: the row goes opaque, the manifest stays
    authoritative, and every read surface — including the columnar
    node-table build — matches the dict baseline."""
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
    from kube_scheduler_simulator_tpu.state.compile import compile_workload

    a, b = load_both(pin, monkeypatch, n_nodes=20, n_pods=5)

    def edit(s):
        pin.reset()
        nd = s.get("nodes", "node-00004")
        nd["status"]["allocatable"]["cpu"] = "99000m"
        s.update("nodes", nd)

    plan = FaultPlan([FaultRule("store.columnar_sync", nth=1)], seed=0)
    with faults.armed(plan):
        edit(a)
    edit(b)
    assert plan.stats()["rules"][0]["trips"] == 1
    bank = a._banks["nodes"]
    assert bank.opaque[bank.row_of["node-00004"]]
    # shim byte-parity survives the faulted sync
    assert (raw(a.get("nodes", "node-00004"))
            == raw(b.get("nodes", "node-00004")))
    assert raw(a.dump()) == raw(b.dump())
    # the columnar build re-parses the opaque row's manifest: identical
    # allocatable to the dict-path build
    cfg = PluginSetConfig(enabled=["NodeResourcesFit"])
    na, _ = a.list("nodes", copy_objects=False)
    nb, _ = b.list("nodes", copy_objects=False)
    pa, _ = a.list("pods", copy_objects=False)
    cw_a = compile_workload(na, list(pa), cfg,
                            pod_columns=getattr(pa, "columns", None))
    cw_b = compile_workload([dict(o) for o in nb], list(pa), cfg)
    assert list(cw_a.node_table.names) == list(cw_b.node_table.names)
    assert np.array_equal(cw_a.node_table.allocatable,
                          cw_b.node_table.allocatable)
    row = list(cw_a.node_table.names).index("node-00004")
    cpu_col = list(cw_a.schema.columns).index("cpu")
    assert cw_a.node_table.allocatable[row, cpu_col] == 99000


# ------------------------------------------- the compile stage on the plane


def _tree_equal(a, b) -> bool:
    import jax

    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    return ta == tb and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


_WAVE_PLUGINS = ["NodeResourcesFit", "NodeResourcesBalancedAllocation",
                 "TaintToleration"]


def test_columnar_compile_and_binds_match_dict_plane(monkeypatch):
    """The workload compiled from the store's column banks is the one
    compiled from the same rows as plain dicts, tensor for tensor, and a
    wave over either plane binds every pod to the same node."""
    import copy

    from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
    from kube_scheduler_simulator_tpu.state.compile import compile_workload

    cfg = PluginSetConfig(enabled=list(_WAVE_PLUGINS))
    a = make_store(monkeypatch, True)
    a.load_columnar("nodes", make_nodes_columnar(48, seed=5,
                                                 taint_fraction=0.1))
    a.load_columnar("pods", make_pods_columnar(20, seed=6))
    nodes, _ = a.list("nodes", copy_objects=False)
    pods, _ = a.list("pods", copy_objects=False)
    assert getattr(nodes, "columns", None) is not None
    dict_nodes = [copy.deepcopy(o) for o in nodes]
    dict_pods = [copy.deepcopy(o) for o in pods]
    cw_c = compile_workload(nodes, pods, cfg,
                            pod_columns=getattr(pods, "columns", None))
    cw_d = compile_workload(dict_nodes, dict_pods, cfg)
    assert list(cw_c.node_table.names) == list(cw_d.node_table.names)
    assert np.array_equal(cw_c.node_table.allocatable,
                          cw_d.node_table.allocatable)
    for part in ("statics", "xs", "init_carry"):
        assert _tree_equal(getattr(cw_c, part), getattr(cw_d, part)), part

    b = make_store(monkeypatch, False)
    for nd in dict_nodes:
        b.create("nodes", copy.deepcopy(nd))
    for p in dict_pods:
        b.create("pods", copy.deepcopy(p))

    def binds(s):
        assert SchedulerEngine(s, plugin_config=cfg,
                               chunk=8).schedule_pending() == len(dict_pods)
        return {p["metadata"]["name"]: p["spec"]["nodeName"]
                for p in s.list("pods")[0]}

    assert binds(a) == binds(b)


@pytest.mark.parametrize("columnar", [True, False], ids=["columnar", "dict"])
def test_node_table_reused_then_patched_never_rebuilt(monkeypatch, columnar):
    """Between waves the node table is built once: a wave over an
    unchanged node set reuses it, and a wave after a bounded node change
    patches exactly the changed rows (docs/data-plane.md)."""
    from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
    from kube_scheduler_simulator_tpu.models.workloads import make_pods
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
    from kube_scheduler_simulator_tpu.utils.tracing import TRACER

    s = make_store(monkeypatch, columnar)
    s.load_columnar("nodes", make_nodes_columnar(48, seed=5))
    s.load_columnar("pods", make_pods_columnar(8, seed=6))
    engine = SchedulerEngine(
        s, plugin_config=PluginSetConfig(enabled=list(_WAVE_PLUGINS)), chunk=8)

    def wave(prefix):
        """Counter deltas of one wave over 4 new pods."""
        for i, p in enumerate(make_pods(4, seed=97)):
            p["metadata"]["name"] = f"{prefix}-{i}"
            s.create("pods", p)
        before = dict(TRACER.summary()["counters"])
        assert engine.schedule_pending() == 4
        after = TRACER.summary()["counters"]
        return {k: after.get(k, 0) - before.get(k, 0) for k in (
            "node_table_builds_total", "node_table_reuse_total",
            "node_table_delta_patches_total", "node_table_delta_rows_total")}

    assert engine.schedule_pending() == 8      # the one build
    unchanged = wave("again")
    assert unchanged["node_table_builds_total"] == 0
    assert unchanged["node_table_reuse_total"] >= 1
    touched = 3
    for i in range(touched):
        nd = s.get("nodes", f"node-{i:05d}")
        nd["metadata"].setdefault("labels", {})["kss.io/touched"] = "y"
        s.update("nodes", nd)
    patched = wave("after-touch")
    assert patched["node_table_builds_total"] == 0
    assert patched["node_table_delta_patches_total"] >= 1
    assert patched["node_table_delta_rows_total"] == touched
