"""The volume family's padded axes and argument statics (PR 36).

  * `axis_bucket`: the power of two at or above the count, at least 64; an
    empty axis stays empty;
  * padding changes no result: seeded clusters with bound CSI claims under
    CSINode limits AND unbound WaitForFirstConsumer claims that compete for
    fewer PVs than there are claims, replayed padded and with the padding
    taken out (`axis_bucket` = identity), equal in every annotation of every
    pod and equal to the sequential oracle: a padded PV is never claimed, a
    padded CSI slot never counted;
  * a PV, a claim and a CSI volume created between two passes change no
    scan-cache key while the padded axes hold them (no miss, no re-bucket),
    and the pass that outgrows a bucket misses once and counts one re-bucket
    an axis; a CSINode changed between two passes compiles nothing either;
  * the counters and the gauge of the family's pass read what
    docs/metrics.md says, and have their lines there and in the tracer's
    help table.
"""

from __future__ import annotations

import random
from pathlib import Path

import jax
import numpy as np
import pytest

from test_csi_volumes_reference import _counts
from test_volumes import node, pod, pv, pvc, sc

from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.framework.replay import replay
from kube_scheduler_simulator_tpu.plugins import nodevolumelimits
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.reference_impl.sequential import SequentialScheduler
from kube_scheduler_simulator_tpu.state import volumes as volumes_mod
from kube_scheduler_simulator_tpu.state.compile import ARG_STATICS, compile_workload
from kube_scheduler_simulator_tpu.state.volumes import AXIS_FLOOR, axis_bucket
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result
from kube_scheduler_simulator_tpu.utils import tracing
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

DOCS = Path(__file__).resolve().parent.parent / "docs"
DRIVER = "ebs.csi.aws.com"
COUNTERS = ("volume_manifests_parsed_total", "volume_bound_rows_walked_total",
            "volume_axis_rebuckets_total", "volume_static_args_bytes_total",
            "volume_table_pvs")
VOL_CFG = PluginSetConfig(enabled=[
    "NodeResourcesFit", "VolumeRestrictions", "NodeVolumeLimits",
    "VolumeBinding", "VolumeZone"])


@pytest.mark.parametrize("n, extent", [
    (0, 0), (1, 64), (63, 64), (64, 64), (65, 128), (5000, 8192),
    (6000, 8192), (8192, 8192), (8193, 16384)])
def test_axis_bucket(n, extent):
    assert axis_bucket(n) == extent
    assert AXIS_FLOOR == 64


# ---- padding changes no result ---------------------------------------------

def _csinode(name: str, count: int) -> dict:
    return {"apiVersion": "storage.k8s.io/v1", "kind": "CSINode",
            "metadata": {"name": name},
            "spec": {"drivers": [{"name": DRIVER, "nodeID": name,
                                  "allocatable": {"count": count}}]}}


def _seeded_cluster(seed: int):
    """-> (nodes, bound pods, pending pods, volumes).  Bound CSI claims
    under a limit of 2 a node; a WaitForFirstConsumer class without a
    provisioner whose 7 unbound claims compete for 4 PVs, two of them
    pinned to hosts: the claims that come too late find nothing, whatever
    the padding holds."""
    rnd = random.Random(seed)
    names = [f"n{i}" for i in range(6)]
    nodes = [node(nm) for nm in names]
    vols = {"pvcs": [], "pvs": [], "csinodes": [
        _csinode(nm, 2) for nm in names[:5]],
        "storageclasses": [sc("local", provisioner="kubernetes.io/no-provisioner")]}
    bound, pending = [], []

    def csi_claim(i: int) -> str:
        vols["pvs"].append(pv(f"pv-b{i}", claim_ref=f"c-b{i}",
                              csi={"driver": DRIVER, "volumeHandle": f"h{i}"}))
        vols["pvcs"].append(pvc(f"c-b{i}", sc="", volume_name=f"pv-b{i}"))
        return f"c-b{i}"

    for i in range(5):   # already attached: up to two on a node
        bound.append(pod(f"bound-{i}", pvcs=[csi_claim(i)],
                         node_name=rnd.choice(names[:3])))
    for i in range(4):   # what the unbound claims compete for
        hosts = [rnd.choice(names)] if i < 2 else None
        vols["pvs"].append(pv(f"pv-w{i}", capacity=rnd.choice(["1Gi", "2Gi"]),
                              sc="local", node_affinity_hosts=hosts))
    for i in range(12):
        if i % 2 == 0 or i > 9:
            vols["pvcs"].append(pvc(f"c-w{i}", sc="local"))
            pending.append(pod(f"p{i}", pvcs=[f"c-w{i}"]))
        else:
            pending.append(pod(f"p{i}", pvcs=[csi_claim(100 + i)]))
    rnd.shuffle(pending)
    return nodes, bound, pending, vols


def _decoded(nodes, bound, pending, vols):
    cw = compile_workload(nodes, pending, VOL_CFG, volumes=vols,
                          bound_pods=[(p, p["spec"]["nodeName"]) for p in bound])
    rr = replay(cw, chunk=4)
    return cw, [decode_pod_result(rr, i) for i in range(len(pending))]


@pytest.mark.parametrize("seed", [3, 36, 2147483777])
def test_padded_equals_unpadded_and_the_oracle(seed, monkeypatch):
    nodes, bound, pending, vols = _seeded_cluster(seed)
    cw, padded = _decoded(nodes, bound, pending, vols)
    v, c = len(vols["pvs"]), sum("c-b" in k["metadata"]["name"]
                                 for k in vols["pvcs"])
    binding, limits = cw.statics["VolumeBinding"], cw.statics["NodeVolumeLimits"]
    assert binding.pv_cap.shape == (64,) and binding.pv_node_ok.shape == (64, 6)
    assert limits.driver_onehot.shape == (64, 1) and v < 64 and c < 64
    # what the padding holds: claimed PVs of capacity 0 that are OK on no
    # node; CSI slots on no node, of no driver and in no pod
    assert not np.asarray(binding.pv_cap)[v:].any()
    assert not np.asarray(binding.pv_node_ok)[v:].any()
    assert np.asarray(cw.init_carry["VolumeBinding"].claimed)[v:].all()
    assert not np.asarray(cw.xs["VolumeBinding"].want)[:, :, v:].any()
    assert not np.asarray(limits.driver_onehot)[c:].any()
    assert not np.asarray(cw.init_carry["NodeVolumeLimits"].on_node)[:, c:].any()
    assert not np.asarray(cw.xs["NodeVolumeLimits"].pod_vols)[:, c:].any()

    monkeypatch.setattr(volumes_mod, "axis_bucket", lambda n: n)
    monkeypatch.setattr(nodevolumelimits, "axis_bucket", lambda n: n)
    cw_exact, exact = _decoded(nodes, bound, pending, vols)
    assert cw_exact.statics["VolumeBinding"].pv_cap.shape == (v,)
    assert cw_exact.statics["NodeVolumeLimits"].driver_onehot.shape == (c, 1)
    assert padded == exact

    seq = SequentialScheduler(
        nodes, pending, PluginSetConfig(enabled=list(VOL_CFG.enabled)),
        volumes=vols, bound_pods=[(p, p["spec"]["nodeName"]) for p in bound],
    ).schedule_all()
    assert padded == [a for a, _ in seq]
    # the cluster is what the docstring says: some claim found no PV
    assert any('"VolumeBinding":"node(s) didn\'t find available' in
               a["kube-scheduler-simulator.sigs.k8s.io/filter-result"]
               for a in padded)


# ---- volume objects between passes and the scan cache -----------------------

def _growth(before: dict, after: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after}


def _bench_node(name: str) -> dict:
    return {"apiVersion": "v1", "kind": "Node", "metadata": {"name": name},
            "status": {"allocatable": {"cpu": "4", "memory": "32Gi",
                                       "pods": "110"}}}


class _Cluster:
    """A store and its engine; every pod brings a PV and a claim of its
    own, as benchmark/drivers/closed_loop_volumes.py creates them."""

    def __init__(self, n_nodes: int, initial: int, count: int = 39):
        self.store = ObjectStore()
        self.names = [f"node-{i:03d}" for i in range(n_nodes)]
        for nm in self.names:
            self.store.create("nodes", _bench_node(nm))
            self.store.create("csinodes", _csinode(nm, count))
        self.k = 0
        for i in range(initial):
            self.add_pod(node_name=self.names[i % n_nodes])
        self.engine = SchedulerEngine(self.store)

    def add_pod(self, node_name: str | None = None) -> str:
        k, self.k = self.k, self.k + 1
        self.store.create("persistentvolumes", pv(
            f"pv-{k}", claim_ref=f"pvc-{k}", modes=("ReadOnlyMany",),
            csi={"driver": DRIVER, "volumeHandle": f"pv-{k}"}))
        self.store.create("persistentvolumeclaims", pvc(
            f"pvc-{k}", sc="", volume_name=f"pv-{k}", modes=("ReadOnlyMany",)))
        self.store.create("pods", pod(f"pod-{k}", pvcs=[f"pvc-{k}"],
                                      node_name=node_name))
        return f"pod-{k}"

    def one_pass(self) -> tuple[dict, dict]:
        """Create one pod with its volumes, run the pass -> (the counters'
        growth, the pod as stored)."""
        name = self.add_pod()
        before = _counts()
        self.engine.schedule_pending()
        return _growth(before, _counts()), self.store.get("pods", name, "default")


@pytest.fixture()
def cluster():
    made = []

    def make(*a, **kw):
        made.append(_Cluster(*a, **kw))
        return made[-1]

    yield make
    for c in made:
        c.engine.close()


def test_a_new_pv_is_no_new_executable_until_the_bucket_is_outgrown(cluster):
    c = cluster(n_nodes=23, initial=61)
    # V and C are 62, 63, 64 (the bucket of 64 holds), then 65: 128
    first, _ = c.one_pass()
    assert first["volume_axis_rebuckets_total:pv"] == 0   # no last pass yet
    for v in (63, 64):
        g, got = c.one_pass()
        assert got["spec"].get("nodeName")
        assert TRACER.snapshot()["gauges"]["volume_table_pvs"] == v
        assert g["scan_compile_cache_total:hit"] == 1
        assert g.get("scan_compile_cache_total:miss", 0) == 0
        assert g["volume_axis_rebuckets_total:pv"] == 0
        assert g["volume_axis_rebuckets_total:csi"] == 0
    g, got = c.one_pass()
    assert got["spec"].get("nodeName")
    assert g["scan_compile_cache_total:miss"] == 1
    assert g.get("scan_compile_cache_total:hit", 0) == 0
    assert g["volume_axis_rebuckets_total:pv"] == 1
    assert g["volume_axis_rebuckets_total:csi"] == 1
    g, _ = c.one_pass()    # 66 of 128: the new bucket holds
    assert g["scan_compile_cache_total:hit"] == 1
    assert g.get("scan_compile_cache_total:miss", 0) == 0
    assert g["volume_axis_rebuckets_total:pv"] == 0


def test_a_changed_csinode_is_no_new_executable_and_is_read(cluster):
    c = cluster(n_nodes=5, initial=5)      # one attached volume a node
    c.one_pass()
    g, got = c.one_pass()
    assert got["spec"].get("nodeName") and g["scan_compile_cache_total:hit"] == 1
    # every node's limit down to what it holds: the next pod fits nowhere
    for nm in c.names:
        held = sum(p["spec"].get("nodeName") == nm
                   for p in c.store.list("pods")[0])
        cn = c.store.get("csinodes", nm)
        cn["spec"]["drivers"][0]["allocatable"]["count"] = held
        c.store.update("csinodes", cn)
    g, got = c.one_pass()
    assert not got["spec"].get("nodeName")
    assert "exceed max volume count" in got["metadata"]["annotations"][
        "kube-scheduler-simulator.sigs.k8s.io/filter-result"]
    assert g["scan_compile_cache_total:hit"] == 1
    assert g.get("scan_compile_cache_total:miss", 0) == 0


def test_the_family_s_counters_read_the_pass(cluster):
    n, initial = 7, 9
    c = cluster(n_nodes=n, initial=initial)
    g, _ = c.one_pass()
    assert g["volume_manifests_parsed_total:pv"] == initial + 1
    assert g["volume_manifests_parsed_total:pvc"] == initial + 1
    assert g["volume_manifests_parsed_total:csinode"] == n
    assert g["volume_bound_rows_walked_total"] == initial
    assert TRACER.snapshot()["gauges"]["volume_table_pvs"] == initial + 1
    # pv_node_ok [64, n] + driver_onehot [64, 1] bools, pv_cap [64] +
    # limits [n, 1] int64s, VolumeRestrictions' strict [0]; and, since the
    # pass's buffers have one layout whether pv_node_ok is sent whole or
    # patched (PR 44), the payload of a patch that writes nothing: src
    # [64] and rows [8] int32s, 8 rows of n bools
    payload = 4 * 64 + 4 * 8 + 8 * n
    assert g["volume_static_args_bytes_total"] == (
        64 * n + 64 + 8 * 64 + 8 * n + payload)
    # the second pass of a session is a patch (state/volumecarry.py): its
    # own PV and claim parsed, the row of the pod bound last resolved
    g2, _ = c.one_pass()
    assert g2["volume_manifests_parsed_total:pv"] == 1
    assert g2["volume_manifests_parsed_total:pvc"] == 1
    assert g2.get("volume_manifests_parsed_total:csinode", 0) == 0
    assert g2["volume_bound_rows_walked_total"] == 1
    assert TRACER.snapshot()["gauges"]["volume_table_pvs"] == initial + 2
    # ... and pv_node_ok stays on the device (state/resident.py): what
    # travels in its place is src [64] and rows [8] int32s, 8 fresh rows
    assert g2["volume_static_args_bytes_total"] == (
        64 + 8 * 64 + 8 * n + payload)


def test_argument_statics_are_the_family_s_and_travel_with_the_pass():
    nodes, bound, pending, vols = _seeded_cluster(5)
    cw = compile_workload(nodes, pending, VOL_CFG, volumes=vols)
    family = {"VolumeRestrictions", "NodeVolumeLimits", "VolumeBinding"}
    # ... and NodeAffinity's match rows and PodTopologySpread's count
    # groups, which change with the queue
    assert set(ARG_STATICS) == family | {"NodeAffinity", "PodTopologySpread"}
    assert family <= set(cw.arg_statics()) <= set(ARG_STATICS)
    assert all(isinstance(leaf, jax.Array)
               for leaf in jax.tree.leaves(cw.arg_statics()))
    # another cluster's volumes, the same shapes: the same scan
    from kube_scheduler_simulator_tpu.framework.replay import _workload_scan_key
    nodes2, _, pending2, vols2 = _seeded_cluster(6)
    cw2 = compile_workload(nodes2, pending2, VOL_CFG, volumes=vols2)
    assert _workload_scan_key(cw, 4) == _workload_scan_key(cw2, 4)


@pytest.mark.parametrize("name", COUNTERS)
def test_every_new_counter_has_its_line_in_the_docs(name):
    assert f"`{name}" in (DOCS / "metrics.md").read_text(), name
    assert name in tracing._HELP
