"""The volume family's state carried from pass to pass
(state/volumecarry.py): a carry brought up to date by the store's events
on the four volume kinds, and by the bound carry's changed rows, gives
compile_workload the same leaves as a build from scratch on the same store
(the C, D and R axes up to their order, which no kernel can see) and the
same replayed decisions and annotations; what it cannot follow row by row
is rebuilt and counted by reason; a steady pass parses the manifests and
resolves the rows that changed, however many there are; and a closed
engine leaves no watcher behind."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from test_volume_axes import DRIVER, _bench_node, _csinode
from test_volumes import node, pod, pv, pvc, sc

from kube_scheduler_simulator_tpu.cluster.store import (
    VOLUME_KINDS, ObjectStore, list_shared, volume_manifests)
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.framework.replay import replay
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.reference_impl.sequential import SequentialScheduler
from kube_scheduler_simulator_tpu.state import boundcarry, volumecarry
from kube_scheduler_simulator_tpu.state.boundcarry import BoundCarry, BoundFeed
from kube_scheduler_simulator_tpu.state.compile import (
    NodeTableReuse, compile_workload)
from kube_scheduler_simulator_tpu.state.nodes import build_node_table
from kube_scheduler_simulator_tpu.state.resources import ResourceSchema
from kube_scheduler_simulator_tpu.state.volumecarry import (
    NodeSlots, VolumeCarry, VolumeFeed)
from kube_scheduler_simulator_tpu.state.volumes import build_volume_table
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result
from kube_scheduler_simulator_tpu.utils import tracing
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

VOL_CFG = PluginSetConfig(enabled=[
    "NodeResourcesFit", "VolumeRestrictions", "NodeVolumeLimits",
    "VolumeBinding", "VolumeZone"])
LOCAL = "kubernetes.io/no-provisioner"
ZONE = "topology.kubernetes.io/zone"
GCE = {"name": "d", "gcePersistentDisk": {"pdName": "pd0"}}
AWS_RO = {"name": "e", "awsElasticBlockStore": {"volumeID": "vol-1",
                                                "readOnly": True}}


def _csi_pv(name: str, claim: str, handle: str, driver: str = DRIVER) -> dict:
    return pv(name, claim_ref=claim, modes=("ReadWriteOnce", "ReadWriteOncePod"),
              csi={"driver": driver, "volumeHandle": handle})


def _seed(store: ObjectStore) -> None:
    """Bound and unbound WaitForFirstConsumer claims, equal-capacity PVs
    that tie, PVs with node affinity, an RWOP claim, inline disks, a CSI
    volume on one node by two pods, CSINodes with and without a count."""
    for j in range(8):
        store.create("nodes", node(f"n{j}", labels={ZONE: f"z{j % 2}"}))
    store.create("storageclasses", sc("local", provisioner=LOCAL))
    store.create("storageclasses", sc("fast", topo_zones=["z0"]))
    store.create("storageclasses", sc("imm", wffc=False))
    for j in range(6):
        store.create("csinodes", _csinode(f"n{j}", 2))
    store.create("csinodes", {                 # a driver without a count
        "apiVersion": "storage.k8s.io/v1", "kind": "CSINode",
        "metadata": {"name": "n6"},
        "spec": {"drivers": [{"name": DRIVER, "nodeID": "n6"}]}})
    for i in range(6):
        store.create("persistentvolumes", _csi_pv(f"pv-b{i}", f"c-b{i}", f"h{i}"))
        store.create("persistentvolumeclaims",
                     pvc(f"c-b{i}", sc="", volume_name=f"pv-b{i}"))
    for i in range(5):   # pv-w0..3 tie at 1Gi; two of them pinned to hosts
        hosts = (["n1"], ["n2", "n3"])[i] if i < 2 else None
        store.create("persistentvolumes", pv(
            f"pv-w{i}", capacity="2Gi" if i == 4 else "1Gi", sc="local",
            node_affinity_hosts=hosts))
    for i in range(6):
        store.create("persistentvolumeclaims", pvc(f"c-w{i}", sc="local"))
    store.create("persistentvolumes", _csi_pv("pv-r", "c-rwop", "hr"))
    store.create("persistentvolumeclaims", pvc(
        "c-rwop", sc="", volume_name="pv-r", modes=("ReadWriteOncePod",)))
    store.create("persistentvolumes", pv(
        "pv-zone", claim_ref="c-zone", labels={ZONE: "z0"}))
    store.create("persistentvolumeclaims",
                 pvc("c-zone", sc="", volume_name="pv-zone"))
    store.create("persistentvolumeclaims", pvc("c-fast", sc="fast"))
    store.create("persistentvolumeclaims", pvc("c-imm", sc="imm"))
    store.create("persistentvolumeclaims", pvc("c-late", sc="local"))
    for p in (
            pod("b0", pvcs=["c-b0"], node_name="n0"),
            pod("b1", pvcs=["c-b1"], node_name="n0"),
            pod("b2", pvcs=["c-b2"], node_name="n1"),     # one volume, one
            pod("b3", pvcs=["c-b2"], node_name="n1"),     # node, two pods
            pod("b4", pvcs=["c-b3"], node_name="no-such-node"),
            pod("b5", volumes=[GCE], node_name="n2"),
            pod("b6", volumes=[AWS_RO], node_name="n2"),
            pod("b7", pvcs=["c-rwop"], node_name="n3"),
            pod("b8", pvcs=["c-w0"], node_name="n1"),     # prime_claims
            pod("b9", pvcs=["c-w1", "c-late"], node_name="n4"),   # replays
            pod("b10", pvcs=["c-nowhere"], node_name="n5")):
        store.create("pods", p)


def _queue(tag: str) -> list[dict]:
    return [pod(f"q-{tag}-b4", pvcs=["c-b4"]),
            pod(f"q-{tag}-w2", pvcs=["c-w2"]),
            pod(f"q-{tag}-w3", pvcs=["c-w3", "c-w4"]),
            pod(f"q-{tag}-rwop", pvcs=["c-rwop"]),
            pod(f"q-{tag}-gce", volumes=[GCE]),
            pod(f"q-{tag}-aws", volumes=[AWS_RO, {
                "name": "f", "gcePersistentDisk": {"pdName": "pd9"}}]),
            pod(f"q-{tag}-fast", pvcs=["c-fast"]),
            pod(f"q-{tag}-imm", pvcs=["c-imm"]),
            pod(f"q-{tag}-gone", pvcs=["c-nowhere"]),
            pod(f"q-{tag}-zone", pvcs=["c-zone"]),
            pod(f"q-{tag}-shared", pvcs=["c-b2", "c-b5"]),
            pod(f"q-{tag}-plain")]


# ---- comparing a carried build with a scratch build -------------------------

def _np(tree):
    return jax.tree.map(np.asarray, tree)


def _sorted_columns(arrays: list[tuple[np.ndarray, int]]):
    """Arrays that share one axis (array, which axis), that axis put into
    the order of its columns' contents."""
    if not arrays[0][0].shape[arrays[0][1]]:
        return [a for a, _ in arrays]
    cols = [np.moveaxis(a, axis, 0).reshape(a.shape[axis], -1)
            for a, axis in arrays]
    keys = [b"".join(c[i].tobytes() for c in cols)
            for i in range(cols[0].shape[0])]
    order = sorted(range(len(keys)), key=keys.__getitem__)
    return [np.take(a, order, axis=axis) for a, axis in arrays]


def _canonical(cw) -> dict:
    """The workload's leaves with the C, D and R axes in canonical order:
    slots are interchangeable (the kernels reduce over them)."""
    st, xs, carry = _np(cw.statics), _np(cw.xs), _np(cw.init_carry)
    lim_s, lim_x, lim_c = (t.pop("NodeVolumeLimits") for t in (st, xs, carry))
    res_s, res_x, res_c = (t.pop("VolumeRestrictions") for t in (st, xs, carry))
    parts = {
        "C": _sorted_columns([(lim_s.driver_onehot, 0), (lim_x.pod_vols, 1),
                              (lim_c.on_node, 1)]),
        "D": _sorted_columns([(res_s.strict, 0), (res_x.w_any, 1),
                              (res_x.w_rw, 1), (res_c.used_any, 1),
                              (res_c.used_rw, 1)]),
        "R": _sorted_columns([(res_x.rwop, 1), (res_c.rwop_used, 0)]),
        "rest": jax.tree.leaves((st, xs, carry, lim_s.limits, lim_x.filter_skip,
                                 res_x.filter_skip)),
        "tree": [str(jax.tree.structure(t)) for t in (cw.statics, cw.xs,
                                                      cw.init_carry)],
    }
    return parts


def _assert_same_leaves(carried, scratch, where) -> None:
    a, b = _canonical(carried), _canonical(scratch)
    assert a["tree"] == b["tree"], where
    for part in ("C", "D", "R", "rest"):
        assert len(a[part]) == len(b[part]), (where, part)
        for i, (x, y) in enumerate(zip(a[part], b[part])):
            assert x.dtype == y.dtype and x.shape == y.shape, (
                where, part, i, x.shape, y.shape)
            assert x.tobytes() == y.tobytes(), (where, part, i)
    ta, tb = carried.host["volume_table"], scratch.host["volume_table"]
    assert ta.pvs == tb.pvs and ta.pv_index == tb.pv_index, where
    assert ta.pvcs == tb.pvcs and ta.classes == tb.classes, where
    assert ta.default_class == tb.default_class, where
    assert sorted(ta.csi_limits) == sorted(tb.csi_limits), where
    for d in ta.csi_limits:
        assert (ta.csi_limits[d] == tb.csi_limits[d]).all(), (where, d)
    assert carried.host.get("prefilter_reject") == scratch.host.get(
        "prefilter_reject"), where


def _decoded(cw) -> list[dict]:
    rr = replay(cw, chunk=4)
    return [decode_pod_result(rr, i) for i in range(cw.n_pods)]


def _reasons() -> dict:
    return {k: v for k, v in TRACER.labeled_totals(
        "volume_carry_rebuilds_total", "reason").items() if k != "uncarried"}


def _moved(before: dict, after: dict) -> dict:
    return {k: int(v - before.get(k, 0)) for k, v in after.items()
            if v - before.get(k, 0)}


def _parsed() -> dict:
    return TRACER.labeled_totals("volume_manifests_parsed_total", "kind")


def _walked() -> int:
    return int(TRACER.counter_totals().get("volume_bound_rows_walked_total", 0))


class _Session:
    """A store and the two carries a served session keeps over it."""

    def __init__(self):
        self.store = ObjectStore()
        _seed(self.store)
        self.bound = BoundCarry(BoundFeed(self.store))
        self.volumes = VolumeCarry(VolumeFeed(self.store))
        self.reuse = None
        self.passes = 0

    def close(self) -> None:
        self.bound.close()
        self.volumes.close()

    def a_pass(self, decide: bool = False):
        """Compile the carried and the scratch workload of one queue on
        the store as it is, compare them -> (what the carried compile
        counted: rebuild reasons, manifests parsed by kind, rows
        resolved)."""
        self.passes += 1
        nodes = list_shared(self.store, "nodes")
        queue = _queue(str(self.passes))
        before = _reasons(), _parsed(), _walked()
        carried = compile_workload(
            nodes, queue, VOL_CFG, bound_carry=self.bound,
            volume_carry=self.volumes, reuse=self.reuse)
        counted = (_moved(before[0], _reasons()), _moved(before[1], _parsed()),
                   _walked() - before[2])
        self.reuse = NodeTableReuse(carried)
        bound = [(p, p["spec"]["nodeName"]) for p in list_shared(self.store, "pods")
                 if p["spec"].get("nodeName")]
        scratch = compile_workload(nodes, queue, VOL_CFG, bound_pods=bound,
                                   volumes=volume_manifests(self.store))
        _assert_same_leaves(carried, scratch, self.passes)
        if decide:
            got = _decoded(carried)
            assert got == _decoded(scratch)
            self.decided = got
        return counted


@pytest.fixture()
def session():
    s = _Session()
    yield s
    s.close()


# ---- the events a carry follows ---------------------------------------------
# name -> (what happens to the store between two passes,
#          rebuild reasons the next pass counts,
#          manifests it may parse at most {kind: n}, rows it may resolve at most)

def _update(store, resource, name, change, ns=None):
    obj = store.get(resource, name, ns) if ns else store.get(resource, name)
    change(obj)
    store.update(resource, obj)


def _pv_created(name):
    def do(store):
        store.create("persistentvolumes", pv(name, sc="local"))
    return do


def _pv_grows(store):          # a tie broken: pv-w2 is no longer the smallest
    _update(store, "persistentvolumes", "pv-w2",
            lambda o: o["spec"]["capacity"].update(storage="3Gi"))


def _pv_pinned(store):         # nodeAffinity appears on a PV that had none
    _update(store, "persistentvolumes", "pv-w3", lambda o: o["spec"].update(
        nodeAffinity=pv("x", node_affinity_hosts=["n5"])["spec"]["nodeAffinity"]))


def _pv_of_a_bound_pod_changes(store):    # b0's volume becomes another driver's
    _update(store, "persistentvolumes", "pv-b0",
            lambda o: o["spec"]["csi"].update(driver="other.csi.io"))


def _claim_created(store):     # the claim b10 and a queue pod name appears
    store.create("persistentvolumeclaims", pvc("c-nowhere", sc="local"))


def _claim_bound_after_its_pod(store):    # b9's second claim gets its PV
    store.create("persistentvolumes", _csi_pv("pv-late", "c-late", "hl"))
    _update(store, "persistentvolumeclaims", "c-late",
            lambda o: o["spec"].update(volumeName="pv-late"), ns="default")


def _claim_becomes_rwop(store):           # b0's claim: the R axis gains a slot
    _update(store, "persistentvolumeclaims", "c-b0",
            lambda o: o["spec"].update(accessModes=["ReadWriteOncePod"]),
            ns="default")


def _class_turns_immediate(store):
    _update(store, "storageclasses", "local",
            lambda o: o.update(volumeBindingMode="Immediate"))


def _default_class_appears(store):
    store.create("storageclasses", sc("std", provisioner=LOCAL, default=True))
    store.create("persistentvolumeclaims", pvc("c-w5b"))     # takes the default


def _class_touched(store):     # nothing a claim resolves against changes
    _update(store, "storageclasses", "fast",
            lambda o: o["metadata"].setdefault("labels", {}).update(a="b"))


def _csinode_count(store):
    _update(store, "csinodes", "n0", lambda o: o["spec"]["drivers"][0][
        "allocatable"].update(count=1))


def _csinode_second_driver(store):
    _update(store, "csinodes", "n1", lambda o: o["spec"]["drivers"].append(
        {"name": "other.csi.io", "nodeID": "n1", "allocatable": {"count": 1}}))
    store.create("persistentvolumes", _csi_pv(
        "pv-o", "c-o", "ho", driver="other.csi.io"))
    store.create("persistentvolumeclaims", pvc("c-o", sc="", volume_name="pv-o"))
    store.create("pods", pod("b-o", pvcs=["c-o"], node_name="n1"))


def _csinodes_all_deleted(store):
    for j in range(7):
        store.delete("csinodes", f"n{j}")


def _pod_bound(store):
    store.create("pods", pod("b-new", pvcs=["c-b4", "c-w5"], volumes=[GCE],
                             node_name="n2"))


def _pod_rebound_elsewhere(store):
    store.delete("pods", "b1", "default")
    store.create("pods", pod("b1", pvcs=["c-b1"], node_name="n3"))


def _node_swapped(store):
    store.delete("nodes", "n1")
    store.create("nodes", node("n1b", labels={ZONE: "z1"}))
    store.create("csinodes", _csinode("n1b", 1))


def _node_relabelled(store):
    _update(store, "nodes", "n2", lambda o: o["metadata"]["labels"].update(
        {ZONE: "z0"}))


def _bucket_outgrown(store):   # 13 PVs -> 73: a new array, rows gathered
    for i in range(60):
        store.create("persistentvolumes", pv(f"pv-m{i:02d}", sc="local",
                                             capacity="5Gi"))


def _many_deleted(store):
    for name in ("pv-b1", "pv-w0", "pv-w2", "pv-w4", "pv-zone", "pv-r"):
        store.delete("persistentvolumes", name)


def _deleted(resource, name, ns=None):
    def do(store):
        store.delete(resource, name, ns) if ns else store.delete(resource, name)
    return do


EVENTS = {
    "nothing": (lambda store: None, {}, {}, 0),
    "pv_created_before_every_name": (_pv_created("a-first"), {}, {"pv": 1}, 0),
    "pv_created_between_names": (_pv_created("pv-w1b"), {}, {"pv": 1}, 0),
    "pv_created_after_every_name": (_pv_created("zz-last"), {}, {"pv": 1}, 0),
    "pv_updated_breaks_a_tie": (_pv_grows, {}, {"pv": 1}, 0),
    "pv_gains_node_affinity": (_pv_pinned, {}, {"pv": 1}, 0),
    "pv_of_a_bound_pod_changes": (_pv_of_a_bound_pod_changes, {}, {"pv": 1}, 1),
    "pv_of_a_bound_pod_deleted": (
        _deleted("persistentvolumes", "pv-b2"), {}, {}, 2),
    "pv_with_affinity_deleted": (
        _deleted("persistentvolumes", "pv-w0"), {}, {}, 0),
    "many_pvs_deleted": (_many_deleted, {}, {}, 2),
    "bucket_outgrown": (_bucket_outgrown, {}, {"pv": 60}, 0),
    "claim_created": (_claim_created, {}, {"pvc": 1}, 1),
    "claim_bound_after_its_pod": (
        _claim_bound_after_its_pod, {}, {"pv": 1, "pvc": 1}, 1),
    "claim_becomes_rwop": (_claim_becomes_rwop, {}, {"pvc": 1}, 1),
    "claim_of_two_bound_pods_deleted": (
        _deleted("persistentvolumeclaims", "c-b2", "default"), {}, {}, 2),
    "class_turns_immediate": (
        _class_turns_immediate, {"classes": 1}, {"pvc": 17}, 11),
    "class_deleted": (
        _deleted("storageclasses", "local"), {"classes": 1}, {"pvc": 17}, 11),
    "default_class_appears": (
        _default_class_appears, {"classes": 1}, {"pvc": 18}, 11),
    "class_touched_changes_nothing": (_class_touched, {}, {}, 0),
    "csinode_count_changes": (_csinode_count, {}, {"csinode": 1}, 0),
    "csinode_deleted": (_deleted("csinodes", "n0"), {}, {}, 0),
    "csinode_second_driver": (
        _csinode_second_driver, {"drivers": 1}, {"csinode": 1, "pv": 1, "pvc": 1}, 1),
    "csinodes_all_deleted": (_csinodes_all_deleted, {"drivers": 1}, {}, 0),
    "pod_bound": (_pod_bound, {}, {}, 1),
    "one_of_two_pods_on_a_volume_deleted": (
        _deleted("pods", "b3", "default"), {}, {}, 0),
    "pod_with_a_volume_rebound_elsewhere": (_pod_rebound_elsewhere, {}, {}, 1),
    "pod_with_an_inline_disk_deleted": (
        _deleted("pods", "b5", "default"), {}, {}, 0),
    "pod_with_an_unbound_claim_deleted": (
        _deleted("pods", "b8", "default"), {}, {}, 0),
    "pod_with_the_rwop_claim_deleted": (
        _deleted("pods", "b7", "default"), {}, {}, 0),
    "node_swapped": (_node_swapped, {"nodes": 1}, {"csinode": 1}, 0),
    "node_relabelled": (_node_relabelled, {"nodes": 1}, {}, 0),
}


@pytest.mark.parametrize("event", list(EVENTS))
def test_carried_state_equals_a_scratch_build_after(event, session):
    happen, reasons, may_parse, may_resolve = EVENTS[event]
    first = session.a_pass()
    assert first[0] == {"resync": 1}
    assert first[1] == {"pv": 13, "pvc": 17, "csinode": 7} and first[2] == 11
    happen(session.store)
    got_reasons, parsed, resolved = session.a_pass(decide=True)
    assert got_reasons == reasons
    assert parsed == may_parse and resolved <= may_resolve, (parsed, resolved)
    # and the pass after it is a steady one: nothing parsed, nothing resolved
    assert session.a_pass() == ({}, {}, 0)


def test_the_second_of_two_pods_on_a_volume_frees_its_slot(session):
    session.a_pass()
    assert session.volumes.csi.n == 5                  # h0 h1 h2 h3 hr
    session.store.delete("pods", "b3", "default")
    session.a_pass()
    assert session.volumes.csi.n == 5                  # b2 still holds h2
    session.store.delete("pods", "b2", "default")
    session.store.delete("pods", "b0", "default")
    session.a_pass(decide=True)
    assert session.volumes.csi.n == 3
    assert sorted(session.volumes.csi.slot.values()) == [0, 1, 2]


def test_carried_decisions_equal_the_sequential_oracle(session):
    session.a_pass()
    _pv_created("pv-w1b")(session.store)
    _claim_bound_after_its_pod(session.store)
    session.a_pass(decide=True)
    nodes = list_shared(session.store, "nodes")
    bound = [(p, p["spec"]["nodeName"]) for p in list_shared(session.store, "pods")
             if p["spec"].get("nodeName")]
    seq = SequentialScheduler(
        nodes, _queue(str(session.passes)),
        PluginSetConfig(enabled=list(VOL_CFG.enabled)),
        volumes=volume_manifests(session.store), bound_pods=bound).schedule_all()
    assert session.decided == [a for a, _ in seq]


def test_a_backlog_past_the_limit_is_a_resync(session, monkeypatch):
    session.a_pass()
    monkeypatch.setattr(volumecarry, "_RESYNC_BACKLOG", 3)
    for i in range(5):
        _pv_created(f"pv-x{i}")(session.store)
    reasons, parsed, resolved = session.a_pass(decide=True)
    assert reasons == {"resync": 1}
    assert parsed == {"pv": 18, "pvc": 17, "csinode": 7} and resolved == 11
    assert session.a_pass() == ({}, {}, 0)


def test_build_volume_table_is_a_carry_seeded_from_lists():
    store = ObjectStore()
    _seed(store)
    nodes = list_shared(store, "nodes")
    table = build_node_table(nodes, ResourceSchema.discover([], nodes))
    vols = volume_manifests(store)
    vols["pvs"] = vols["pvs"][::-1]            # a list's order is the V axis
    before = TRACER.labeled_totals("volume_carry_rebuilds_total", "reason")
    vt = build_volume_table(table, vols["pvcs"], vols["pvs"],
                            vols["storageclasses"], vols["csinodes"])
    after = TRACER.labeled_totals("volume_carry_rebuilds_total", "reason")
    assert _moved(before, after) == {"uncarried": 1}
    names = [p["metadata"]["name"] for p in vols["pvs"]]
    assert [p.name for p in vt.pvs] == names
    assert vt.pv_index == {nm: i for i, nm in enumerate(names)}
    assert vt.pv_node_ok.shape == (64, 8) and vt.pv_cap.shape == (64,)
    i = vt.pv_index["pv-w1"]                   # pinned to n2, n3
    assert vt.pv_node_ok[i].tolist() == [j in (2, 3) for j in range(8)]
    assert vt.pv_node_ok[vt.pv_index["pv-w2"]].all()
    assert not vt.pv_node_ok[13:].any() and vt.pv_claimed0[13:].all()
    assert vt.pv_claimed0[:13].tolist() == [
        nm.startswith(("pv-b", "pv-r", "pv-zone")) for nm in names]
    assert vt.pv_cap[vt.pv_index["pv-w4"]] == 2 << 30
    assert sorted(vt.csi_limits) == [DRIVER]
    assert vt.csi_limits[DRIVER].tolist() == [2] * 6 + [-1, -1]
    assert len(vt.pvcs) == 17 and vt.pvcs["default/c-rwop"].volume_name == "pv-r"


def test_node_slots_count_references_and_stay_dense():
    slots = NodeSlots(2, n_nodes=3)
    slots.add("a", 0, (True, True), tag=1)
    slots.add("a", 0, (True, False), tag=1)    # a second pod, read-only
    slots.add("b", 1, (True, False))
    slots.add("c", None, (True, True), tag=1)  # on a node the table lacks
    assert slots.n == 3 and slots.slot == {"a": 0, "b": 1, "c": 2}
    assert slots.plane(0, 3).tolist() == [[True, False, False],
                                          [False, True, False], [False] * 3]
    slots.sub("a", 0, (True, True))            # the writer leaves: any stays
    assert slots.plane(0, 3)[0, 0] and not slots.plane(1, 3)[0, 0]
    slots.sub("a", 0, (True, False))           # nobody names a: c takes slot 0
    assert slots.n == 2 and slots.slot == {"c": 0, "b": 1}
    assert slots.tags[:3].tolist() == [1, 0, 0]
    assert not slots.plane(0, 4)[:, 0].any() and not slots.plane(0, 4)[:, 2:].any()
    assert slots.plane(0, 64).shape == (3, 64)


# ---- through the engine -----------------------------------------------------

class _Served:
    """A store and its engine; every pod brings a PV and a claim, PV names
    in no order (the cell's generateName), so a new PV lands mid-table."""

    def __init__(self, n_nodes: int, initial: int):
        self.store = ObjectStore()
        self.rng = np.random.default_rng(initial)
        self.names = [f"node-{i:03d}" for i in range(n_nodes)]
        for nm in self.names:
            self.store.create("nodes", _bench_node(nm))
            self.store.create("csinodes", _csinode(nm, 39))
        self.k = 0
        for i in range(initial):
            self.add_pod(node_name=self.names[i % n_nodes])
        self.engine = SchedulerEngine(self.store)

    def add_pod(self, node_name=None) -> str:
        k, self.k = self.k, self.k + 1
        name = f"pv-{int(self.rng.integers(1 << 30)):08x}"
        self.store.create("persistentvolumes", pv(
            name, claim_ref=f"pvc-{k}", modes=("ReadOnlyMany",),
            csi={"driver": DRIVER, "volumeHandle": name}))
        self.store.create("persistentvolumeclaims", pvc(
            f"pvc-{k}", sc="", volume_name=name, modes=("ReadOnlyMany",)))
        self.store.create("pods", pod(f"pod-{k}", pvcs=[f"pvc-{k}"],
                                      node_name=node_name))
        return f"pod-{k}"

    def one_pass(self):
        name = self.add_pod()
        before = _reasons(), _parsed(), _walked()
        assert self.engine.schedule_pending() == 1
        assert self.store.get("pods", name, "default")["spec"].get("nodeName")
        return (_moved(before[0], _reasons()), _moved(before[1], _parsed()),
                _walked() - before[2])


@pytest.mark.parametrize("initial", [30, 300])
def test_a_steady_pass_parses_two_manifests_and_resolves_one_row(initial):
    served = _Served(n_nodes=11, initial=initial)
    try:
        first = served.one_pass()
        assert first == ({"resync": 1}, {"pv": initial + 1, "pvc": initial + 1,
                                         "csinode": 11}, initial)
        for _ in range(3):
            # the pass's own PV and claim; the row of the pod bound last
            assert served.one_pass() == ({}, {"pv": 1, "pvc": 1}, 1)
        vt = served.engine._volume_carry.vt
        assert [p.name for p in vt.pvs] == sorted(p.name for p in vt.pvs)
    finally:
        served.engine.close()


def test_a_closed_engine_leaves_no_watcher_on_the_four_kinds():
    served = _Served(n_nodes=3, initial=2)
    served.one_pass()
    watchers = served.store._watchers
    assert all(len(watchers[resource]) == 1 for _, resource in VOLUME_KINDS)
    served.engine.close()
    assert all(watchers[resource] == [] for _, resource in VOLUME_KINDS)
    assert watchers["pods"] == []
    # and an engine used again after close() seeds again
    assert served.one_pass()[0] == {"resync": 1}
    served.engine.close()


def test_a_remote_store_without_a_watch_gets_lists(monkeypatch):
    """No list_and_watch (the remote client): the pass lists the four
    kinds and the builds run on a throw-away carry."""
    served = _Served(n_nodes=3, initial=2)
    monkeypatch.setattr(served.engine, "_bound_pod_carry", lambda: None)
    before = TRACER.labeled_totals("volume_carry_rebuilds_total", "reason")
    name = served.add_pod()
    assert served.engine.schedule_pending() == 1
    assert served.store.get("pods", name, "default")["spec"].get("nodeName")
    after = TRACER.labeled_totals("volume_carry_rebuilds_total", "reason")
    assert _moved(before, after) == {"uncarried": 1}
    assert served.engine._volume_carry is None
    served.engine.close()


def test_the_counter_has_its_line_in_the_docs():
    from pathlib import Path

    docs = Path(__file__).resolve().parent.parent / "docs"
    assert "`volume_carry_rebuilds_total" in (docs / "metrics.md").read_text()
    assert "volume_carry_rebuilds_total" in tracing._HELP
    assert boundcarry._RESYNC_BACKLOG == volumecarry._RESYNC_BACKLOG
