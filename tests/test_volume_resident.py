"""The volume family's two cluster-sized arrays stay on the device
(state/resident.py): a carried session's pv_node_ok [V, N] and on_node
[N, C] are patched by row and by cell from the journal of what the volume
carry wrote to its host arrays, and sent whole only where the journal
cannot say what happened.  Held here: after every pass the device arrays
equal the host arrays and the workload's leaves equal a scratch build's;
which events patch and which upload whole, by reason; a throw-away carry
keeps nothing; nothing is donated, so an earlier pass's workload replays
as before; a mesh shards the resident leaves like any other; and after
the first patched pass nothing compiles."""

from __future__ import annotations

from pathlib import Path

import jax
import numpy as np
import pytest

from test_volume_axes import _Cluster
from test_volume_carry import (
    VOL_CFG, _assert_same_leaves, _bucket_outgrown, _claim_bound_after_its_pod,
    _csi_pv, _csinode_second_driver, _decoded, _deleted, _moved, _node_swapped,
    _pv_created, _pv_pinned, _queue, _Session)
from test_volumes import node, pod, pvc

from kube_scheduler_simulator_tpu.cluster.store import (
    list_shared, volume_manifests)
from kube_scheduler_simulator_tpu.framework.replay import replay
from kube_scheduler_simulator_tpu.parallel.mesh import make_mesh
from kube_scheduler_simulator_tpu.state import resident, volumecarry
from kube_scheduler_simulator_tpu.state.compile import (
    NodeTableReuse, compile_workload)
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result
from kube_scheduler_simulator_tpu.utils import hostevents, tracing
from kube_scheduler_simulator_tpu.utils.tracing import TRACER


def _uploads() -> dict:
    return TRACER.labeled_totals("volume_resident_uploads_total", "reason")


def _patches() -> int:
    return int(TRACER.counter_totals().get("volume_resident_patches_total", 0))


class _Carried(_Session):
    def step(self):
        """One pass of the carried session beside a scratch build on the
        same store -> (the carried workload, whole uploads by reason,
        arrays patched)."""
        self.passes += 1
        nodes = list_shared(self.store, "nodes")
        queue = _queue(str(self.passes))
        before = _uploads(), _patches()
        cw = compile_workload(
            nodes, queue, VOL_CFG, bound_carry=self.bound,
            volume_carry=self.volumes, reuse=self.reuse)
        after = _uploads(), _patches()
        counted = _moved(before[0], after[0]), after[1] - before[1]
        self.reuse = NodeTableReuse(cw)
        bound = [(p, p["spec"]["nodeName"])
                 for p in list_shared(self.store, "pods")
                 if p["spec"].get("nodeName")]
        scratch = compile_workload(nodes, queue, VOL_CFG, bound_pods=bound,
                                   volumes=volume_manifests(self.store))
        # the scratch build's throw-away carry keeps and counts nothing
        assert (_uploads(), _patches()) == after
        _assert_same_leaves(cw, scratch, self.passes)
        self.assert_device_is_host(cw)
        return cw, *counted

    def assert_device_is_host(self, cw) -> None:
        vc = self.volumes
        ok = cw.statics["VolumeBinding"].pv_node_ok
        on = cw.init_carry["NodeVolumeLimits"].on_node
        assert ok is vc.pv_ok_dev.dev and on is vc.on_node_dev.dev
        assert isinstance(ok, jax.Array) and isinstance(on, jax.Array)
        assert np.asarray(ok).tobytes() == vc.vt.pv_node_ok.tobytes()
        assert ok.shape == vc.vt.pv_node_ok.shape
        host = vc.csi.plane(0, on.shape[1])
        assert on.shape == host.shape
        assert np.array_equal(np.asarray(on), host)


@pytest.fixture()
def session():
    s = _Carried()
    yield s
    s.close()


def _many_pvs(n):
    def do(store):
        for i in range(n):
            _pv_created(f"pv-n{i}")(store)
    return do


def _bound_csi_pods(names_nodes):
    """A CSI PV, its claim and a pod that holds it on a node, each."""
    def do(store):
        for name, node_name in names_nodes:
            store.create("persistentvolumes",
                         _csi_pv(f"pv-{name}", f"c-{name}", f"h-{name}"))
            store.create("persistentvolumeclaims",
                         pvc(f"c-{name}", sc="", volume_name=f"pv-{name}"))
            store.create("pods", pod(f"b-{name}", pvcs=[f"c-{name}"],
                                     node_name=node_name))
    return do


def _pods_deleted(*names):
    def do(store):
        for name in names:
            store.delete("pods", name, "default")
    return do


def _pod_bound_on(claim, node_name):
    def do(store):
        store.create("pods", pod(f"b-{claim}-{node_name}", pvcs=[claim],
                                 node_name=node_name))
    return do


def _node_added(store):
    store.create("nodes", node("n8", labels={"topology.kubernetes.io/zone": "z0"}))


# name -> (what happens to the store between two passes, the arrays the
#          next pass sends whole {reason: n}, the arrays it patches)
EVENTS = {
    "nothing": (lambda store: None, {}, 0),
    "pv_inserted_first": (_pv_created("a-first"), {}, 1),
    "pv_inserted_mid_table": (_pv_created("pv-w1b"), {}, 1),
    "pv_inserted_last": (_pv_created("zz-last"), {}, 1),
    "pv_deleted": (_deleted("persistentvolumes", "pv-w0"), {}, 1),
    "pv_of_two_bound_pods_deleted": (
        _deleted("persistentvolumes", "pv-b2"), {}, 2),
    "pv_replaced_in_place": (_pv_pinned, {}, 1),
    "pv_created_and_its_claim_bound": (_claim_bound_after_its_pod, {}, 2),
    "five_pvs_at_once_are_laid_out_again": (_many_pvs(5), {}, 1),
    "nine_pvs_at_once_overflow_the_rows": (_many_pvs(9), {"overflow": 1}, 0),
    "v_bucket_outgrown": (_bucket_outgrown, {"bucket": 1}, 0),
    "v_and_c_buckets_outgrown": (
        _bound_csi_pods([(f"m{i:02d}", f"n{i % 8}") for i in range(60)]),
        {"bucket": 2}, 0),
    "node_added": (_node_added, {"nodes": 2}, 0),
    "node_removed": (_deleted("nodes", "n7"), {"nodes": 2}, 0),
    "node_swapped": (_node_swapped, {"nodes": 2}, 0),
    "pod_bound": (_pod_bound_on("c-b4", "n2"), {}, 1),
    "pod_unbound": (_pods_deleted("b1"), {}, 1),
    "one_of_two_pods_on_a_volume_unbound": (_pods_deleted("b3"), {}, 0),
    "both_pods_on_a_volume_unbound": (_pods_deleted("b2", "b3"), {}, 1),
    "a_second_pod_on_a_volume_on_another_node": (
        _pod_bound_on("c-b0", "n5"), {}, 1),
    "drivers_change": (_csinode_second_driver, {"drivers": 1}, 1),
}


@pytest.mark.parametrize("event", list(EVENTS))
def test_device_arrays_equal_host_arrays_after(event, session):
    happen, uploads, patched = EVENTS[event]
    _, first, _ = session.step()
    assert first == {"first": 2}
    happen(session.store)
    _, got_uploads, got_patched = session.step()
    assert (got_uploads, got_patched) == (uploads, patched)
    # the pass after it is a steady one: nothing sent whole, nothing patched
    assert session.step()[1:] == ({}, 0)


def test_a_freed_slot_is_filled_by_the_last(session):
    session.step()
    csi = session.volumes.csi
    last = csi._idents[csi.n - 1]
    freed = csi.slot[("ebs.csi.aws.com", "h0")]
    assert freed == 0 and csi.slot[last] == csi.n - 1
    _pods_deleted("b0")(session.store)
    assert session.step()[1:] == ({}, 1)
    assert csi.slot[last] == 0 and ("ebs.csi.aws.com", "h0") not in csi.slot
    # the column that moved is on the nodes it was on, the last one clear
    on = np.asarray(session.volumes.on_node_dev.dev)
    assert on[:, 0].any() and not on[:, csi.n:].any()


def test_more_cells_than_a_patch_holds_are_sent_whole(session, monkeypatch):
    monkeypatch.setattr(resident, "CELLS_MAX", 2)
    session.step()
    for claim, node_name in (("c-b4", "n2"), ("c-b5", "n3"), ("c-b0", "n5")):
        _pod_bound_on(claim, node_name)(session.store)
    assert session.step()[1:] == ({"overflow": 1}, 0)
    _pod_bound_on("c-b1", "n6")(session.store)
    assert session.step()[1:] == ({}, 1)


def test_a_resync_sends_both_whole(session, monkeypatch):
    session.step()
    monkeypatch.setattr(volumecarry, "_RESYNC_BACKLOG", 3)
    _many_pvs(5)(session.store)
    assert session.step()[1:] == ({"resync": 2}, 0)
    assert session.step()[1:] == ({}, 0)


def test_events_of_several_passes_compose(session):
    """Inserts, a delete and a replacement in one pass, over slots that
    are freed and taken again."""
    session.step()
    for name in ("a-first", "pv-w1b"):
        _pv_created(name)(session.store)
    _deleted("persistentvolumes", "pv-w0")(session.store)
    _pv_pinned(session.store)
    _pods_deleted("b0", "b7")(session.store)
    _pod_bound_on("c-b4", "n2")(session.store)
    assert session.step()[1:] == ({}, 2)
    _deleted("persistentvolumes", "a-first")(session.store)
    _pod_bound_on("c-b0", "n0")(session.store)
    assert session.step()[1:] == ({}, 2)


def test_an_earlier_pass_s_workload_replays_as_before(session):
    """Nothing is donated: the generation a workload holds is its own."""
    first, _, _ = session.step()
    before = _decoded(first)
    held = (np.asarray(first.statics["VolumeBinding"].pv_node_ok).copy(),
            np.asarray(first.init_carry["NodeVolumeLimits"].on_node).copy())
    _pv_created("a-first")(session.store)
    _pods_deleted("b0")(session.store)
    second, _, patched = session.step()
    assert patched == 2
    assert _decoded(second) and _decoded(first) == before
    assert np.array_equal(first.statics["VolumeBinding"].pv_node_ok, held[0])
    assert np.array_equal(first.init_carry["NodeVolumeLimits"].on_node, held[1])
    # and the carry's arrays survived the replay's donated scan
    session.assert_device_is_host(second)


def test_a_mesh_shards_the_resident_leaves_like_any_other(session):
    session.step()
    _pv_created("pv-w1b")(session.store)
    _pod_bound_on("c-b4", "n2")(session.store)
    cw, _, patched = session.step()
    assert patched == 2
    plain = replay(cw, chunk=4)
    sharded = replay(cw, chunk=4, mesh=make_mesh(8, dp=1))
    for i in range(cw.n_pods):
        assert decode_pod_result(sharded, i) == decode_pod_result(plain, i)
    session.assert_device_is_host(cw)


def test_a_throw_away_carry_keeps_nothing():
    s = _Carried()
    try:
        nodes = list_shared(s.store, "nodes")
        before = _uploads(), _patches()
        cw = compile_workload(nodes, _queue("x"), VOL_CFG,
                              volumes=volume_manifests(s.store))
        assert (_uploads(), _patches()) == before
        assert isinstance(cw.statics["VolumeBinding"].pv_node_ok, jax.Array)
    finally:
        s.close()


# ---- through the engine -----------------------------------------------------

@pytest.fixture()
def cluster():
    c = _Cluster(n_nodes=7, initial=9)
    yield c
    c.engine.close()


def test_a_cycle_of_the_cell_s_shape_patches_both_and_compiles_nothing(cluster):
    """A PV, its claim and a pod a cycle (benchmark/drivers/
    closed_loop_volumes.py): the PV's row is inserted, the pod bound last
    cycle is one cell."""
    hostevents.install()

    def cycle():
        before = (_uploads(), _patches(), TRACER.counter_totals().get(
            "jax_compile_events_total", 0))
        _, got = cluster.one_pass()
        assert got["spec"].get("nodeName")
        return (_moved(before[0], _uploads()), _patches() - before[1],
                TRACER.counter_totals().get("jax_compile_events_total", 0)
                - before[2])

    uploads, patched, _ = cycle()
    assert (uploads, patched) == ({"first": 2}, 0)
    uploads, patched, _ = cycle()             # the first patched pass
    assert (uploads, patched) == ({}, 2)
    for _ in range(3):
        assert cycle() == ({}, 2, 0)


@pytest.mark.parametrize("name", ["volume_resident_patches_total",
                                  "volume_resident_uploads_total"])
def test_the_counters_have_their_lines(name):
    docs = Path(__file__).resolve().parent.parent / "docs"
    assert f"`{name}" in (docs / "metrics.md").read_text()
    assert "Resident and patched" in (docs / "wave-pipeline.md").read_text()
    assert name in tracing._HELP
