"""A pass's xs, carry and statics reach the device through one site
(state/compile.py upload_tree, PR 35).

Every build hands numpy leaves; compile_workload reads the decoder's flags
and the scan-cache key's digest off those host bytes and then uploads the
trees once: one contiguous buffer per dtype, one jitted dispatch that
slices them apart.  Held here: what reaches the device is, leaf for leaf,
what a jnp.asarray of the same numpy leaf gives (bytes, shape, dtype,
weak type), the scan-cache key does not see the route, nothing under
compile_workload converts a device array to numpy or uploads a leaf on
its own, a steady pass makes at most five transfers, and the workload's
init_carry survives the donated scan.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kube_scheduler_simulator_tpu.config.config import SimulatorConfiguration
from kube_scheduler_simulator_tpu.framework.replay import (
    _workload_scan_key, replay)
from kube_scheduler_simulator_tpu.models.workloads import (
    baseline_config, make_nodes, make_pods)
from kube_scheduler_simulator_tpu.plugins.custom import CustomPlugin
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.server.sessions import SessionManager
from kube_scheduler_simulator_tpu.state.compile import (
    compile_workload, upload_tree)
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

from test_scan_prepare import _FetchSpy
from test_volumes import node, pod, pv, pvc, sc

compile_mod = sys.modules["kube_scheduler_simulator_tpu.state.compile"]


class _OddNodesCostMore(CustomPlugin):
    name = "OddNodesCostMore"
    default_weight = 2

    def filter(self, pod, node):
        idx = int(node["metadata"]["name"].rsplit("-", 1)[1])
        return "node 3 is taken" if idx == 3 else None

    def score(self, pod, node):
        return int(node["metadata"]["name"].rsplit("-", 1)[1]) % 2


def _volumes_and_ports():
    """The default profile over PVCs (bound, WFFC, one missing: a
    PreFilter reject, so xs carries force_unsched), an inline disk and
    hostPorts, with bound pods that hold some of each."""
    zones = {"topology.kubernetes.io/zone": "z1"}
    nodes = [node("n1", zones), node("n2", zones),
             node("n3", {"topology.kubernetes.io/zone": "z2"})]
    volumes = {
        "pvcs": [pvc("bound", sc="", volume_name="pv-b"),
                 pvc("late", sc="wffc"), pvc("held", sc="wffc")],
        "pvs": [pv("pv-b", claim_ref="bound", labels=zones,
                   csi={"driver": "ebs.csi.aws.com", "volumeHandle": "h1"}),
                pv("pv-w1", sc="wffc", capacity="2Gi"),
                pv("pv-w2", sc="wffc", capacity="1Gi")],
        "storageclasses": [sc("wffc", provisioner="kubernetes.io/no-provisioner")],
        "csinodes": [{
            "apiVersion": "storage.k8s.io/v1", "kind": "CSINode",
            "metadata": {"name": "n1"},
            "spec": {"drivers": [{"name": "ebs.csi.aws.com",
                                  "allocatable": {"count": 2}}]}}],
    }
    port = [{"containerPort": 80, "hostPort": 8080}]
    disk = {"name": "d", "gcePersistentDisk": {"pdName": "disk-1"}}
    pods = [pod("p-bound", pvcs=["bound"]), pod("p-late", pvcs=["late"]),
            pod("p-missing", pvcs=["nowhere"]), pod("p-disk", volumes=[disk]),
            pod("p-port"), pod("p-plain")]
    pods[4]["spec"]["containers"][0]["ports"] = port
    held = pod("b-held", pvcs=["held"], volumes=[disk], node_name="n2")
    held["spec"]["containers"][0]["ports"] = port
    return dict(nodes=nodes, pods=pods, config=PluginSetConfig(),
                bound_pods=[(held, "n2")], volumes=volumes)


def _custom():
    return dict(
        nodes=make_nodes(6, seed=20), pods=make_pods(5, seed=21),
        config=PluginSetConfig(
            enabled=["NodeResourcesFit", "NodeAffinity", "OddNodesCostMore"],
            custom={"OddNodesCostMore": _OddNodesCostMore()}))


def _baseline(idx, scale):
    nodes, pods, cfg = baseline_config(idx, scale=scale, seed=0)
    return dict(nodes=nodes, pods=pods, config=cfg)


# the parity suite's three constraint profiles (tests/test_parity.py), then
# what they leave out: the volume family and NodePorts with work to do, and
# a custom plugin's rows
WORKLOADS = {
    "affinity_taints": lambda: _baseline(3, 0.02),
    "spread": lambda: _baseline(4, 0.01),
    "interpod": lambda: _baseline(5, 0.01),
    "volumes_ports": _volumes_and_ports,
    "custom": _custom,
}


@pytest.fixture(params=list(WORKLOADS))
def workload(request):
    return WORKLOADS[request.param]()


def _per_leaf(tree):
    """The route the parent took: one jnp.asarray a numpy leaf."""
    return jax.tree.map(
        lambda leaf: jnp.asarray(leaf)
        if isinstance(leaf, (np.ndarray, np.generic)) else leaf, tree)


def _compile_recording(monkeypatch, kwargs, route=upload_tree):
    """compile_workload, with every tree handed to the upload site kept."""
    handed = []

    def recording(tree):
        handed.append(tree)
        return route(tree)

    with monkeypatch.context() as patch:
        patch.setattr(compile_mod, "upload_tree", recording)
        cw = compile_workload(**kwargs)
    return cw, handed


def _assert_same_leaf(got, want, where):
    if not isinstance(want, jax.Array):
        assert got is want or got == want, where
        return
    assert isinstance(got, jax.Array), where
    assert got.shape == want.shape, where
    assert got.dtype == want.dtype, where
    assert got.weak_type == want.weak_type, where
    assert got.committed == want.committed, where
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes(), where


# ------------------------------------------------- what reaches the device


def test_uploaded_trees_equal_the_per_leaf_route(monkeypatch, workload):
    cw, handed = _compile_recording(monkeypatch, workload)
    # the closure statics' tree (a fresh node table: its one generation is
    # made here), then the pass's own, the argument statics with it
    assert len(handed) == 2
    statics, (xs, init_carry, arg_statics) = handed
    assert set(arg_statics) <= set(compile_mod.ARG_STATICS)
    assert not set(statics) & set(compile_mod.ARG_STATICS)
    statics = {**statics, **arg_statics}
    for tree in handed:
        for leaf in jax.tree.leaves(tree):
            assert not isinstance(leaf, jax.Array), (
                "a build handed a device array")
    for name, got_tree, host_tree in (("statics", cw.statics, statics),
                                      ("xs", cw.xs, xs),
                                      ("init_carry", cw.init_carry, init_carry)):
        want_tree = _per_leaf(host_tree)
        assert jax.tree.structure(got_tree) == jax.tree.structure(want_tree)
        got = jax.tree_util.tree_flatten_with_path(got_tree)[0]
        for (path, leaf), want in zip(got, jax.tree.leaves(want_tree)):
            _assert_same_leaf(leaf, want, f"{name}{jax.tree_util.keystr(path)}")
    assert jax.tree.leaves(cw.xs), "the workload has no xs"
    assert all(isinstance(leaf, jax.Array)
               for leaf in jax.tree.leaves((cw.xs, cw.init_carry)))


def test_scan_key_does_not_see_the_route(monkeypatch, workload):
    cw, _ = _compile_recording(monkeypatch, workload)
    old, _ = _compile_recording(monkeypatch, workload, route=_per_leaf)
    assert _workload_scan_key(cw, 16) == _workload_scan_key(old, 16)
    assert cw.host["_statics_fp"] == old.host["_statics_fp"]


def test_upload_tree_leaf_kinds():
    """Scalars, empty and strided leaves, a dtype of one leaf, and what is
    no array at all."""
    strided = np.arange(24, dtype=np.int64).reshape(4, 6)[:, ::2]
    tree = {
        "scalar": np.int64(-7), "f": np.float64(0.1) * np.arange(3),
        "empty": np.zeros((2, 0), dtype=bool), "strided": strided,
        "fortran": np.asfortranarray(np.arange(6, dtype=np.int32).reshape(2, 3)),
        "flags": np.array([True, False, True]), "u16": np.arange(5, dtype=np.uint16),
        "n_groups": 3, "none": None, "device": jnp.arange(2),
        "big": np.array([np.iinfo(np.int64).min, np.iinfo(np.int64).max]),
    }
    got = upload_tree(tree)
    want = _per_leaf(tree)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for key in tree:
        _assert_same_leaf(got[key], want[key], key)
    assert got["device"] is tree["device"]
    assert upload_tree({"n": 1, "s": "x"}) == {"n": 1, "s": "x"}


@pytest.mark.parametrize("shape", [(3,), (4, 5), (2, 0)])
def test_a_device_leaf_stays_and_is_not_packed(monkeypatch, shape):
    """A leaf that is on the device already (a carried session's resident
    arrays, state/resident.py) is returned as the same object, and the
    packed buffers hold the numpy leaves' bytes alone."""
    resident = jnp.ones(shape, dtype=bool)
    tree = {"kept": resident, "flags": np.array([True, False]),
            "rows": np.arange(6, dtype=np.int32).reshape(2, 3)}
    sent = []
    real_put = jax.device_put

    def recording_put(bufs, *a, **kw):
        sent.append(bufs)
        return real_put(bufs, *a, **kw)

    monkeypatch.setattr(jax, "device_put", recording_put)
    before = TRACER.counter_totals().get("workload_h2d_transfers_total", 0)
    got = upload_tree(tree)
    assert got["kept"] is resident
    assert len(sent) == 1
    assert {dt: buf.nbytes for dt, buf in sent[0].items()} == {
        "bool": 2, "int32": 24}
    assert TRACER.counter_totals()["workload_h2d_transfers_total"] - before == 2
    np.testing.assert_array_equal(np.asarray(got["rows"]), tree["rows"])
    # a tree of device leaves alone sends nothing
    assert upload_tree({"kept": resident})["kept"] is resident
    assert len(sent) == 1


# ------------------------------------------- nothing is read back, one site


def test_compile_workload_reads_no_device_array_and_uploads_no_leaf(
        monkeypatch, workload):
    compile_workload(**workload)               # compile the unpack outside
    spy = _FetchSpy(monkeypatch)
    calls = {"asarray": 0, "device_put": 0}
    for fn_name in ("asarray", "array"):
        real = getattr(jnp, fn_name)

        def counted(*a, _real=real, **kw):
            calls["asarray"] += 1
            return _real(*a, **kw)

        monkeypatch.setattr(jnp, fn_name, counted)
    real_put = jax.device_put

    def counted_put(*a, **kw):
        calls["device_put"] += 1
        return real_put(*a, **kw)

    monkeypatch.setattr(jax, "device_put", counted_put)
    cw = compile_workload(**workload)
    assert spy.by_span == {}, "compile_workload fetched a device array"
    assert calls["asarray"] == 0, "a leaf was uploaded on its own"
    assert calls["device_put"] == 2          # the statics' tree, the pass's
    # the decoder's flags are the builds' host arrays
    for flags in (cw.host["filter_skip"], cw.host["score_skip"]):
        assert all(type(v) is np.ndarray for v in flags.values())
    for part in cw.host.get("tsp_ignore", ()):
        assert type(part) is np.ndarray
    # the spy does see a fetch that is meant to happen
    np.asarray(jax.tree.leaves(cw.xs)[0])
    assert spy.by_span == {None: 1}


def test_steady_pass_makes_at_most_five_transfers():
    """One pod a pass in a served session under the default profile: the
    first pass uploads the statics too, every later one only its xs and
    carry, a buffer a dtype."""
    mgr = SessionManager(cfg=SimulatorConfiguration(port=0),
                         start_scheduler=False, idle_ttl=0, max_sessions=2)
    try:
        sess = mgr.create("h2d")
        for n in make_nodes(8, seed=31):
            sess.di.store.create("nodes", n)

        def counters():
            return TRACER.snapshot(session="h2d")["counters"]

        def upload_spans():
            return TRACER.snapshot(session="h2d")["spans"].get(
                "cw_upload", {}).get("count", 0)

        rises = []
        for p in make_pods(3, seed=32):
            before, spans = counters(), upload_spans()
            sess.di.store.create("pods", p)
            assert sess.di.engine.schedule_pending() == 1
            after = counters()
            assert after["scheduling_work_passes_total"] - before.get(
                "scheduling_work_passes_total", 0) == 1
            assert upload_spans() - spans == 1
            rises.append(after["workload_h2d_transfers_total"]
                         - before.get("workload_h2d_transfers_total", 0))
        assert rises[0] > rises[1], rises      # the statics, once a table
        assert 1 <= rises[1] <= 5 and rises[2] == rises[1], rises
    finally:
        mgr.shutdown()


# -------------------------------------------------- the carry survives


def test_replay_twice_on_one_uploaded_workload(workload):
    """The unpack's outputs are the workload's own buffers: the donated
    scan must take a copy of init_carry, not them."""
    cw = compile_workload(**workload)
    n = len(workload["pods"])
    carry0 = [np.asarray(leaf).copy() for leaf in jax.tree.leaves(cw.init_carry)]
    xs0 = [np.asarray(leaf).copy() for leaf in jax.tree.leaves(cw.xs)]
    first = replay(cw, chunk=16, device_resident=True)
    second = replay(cw, chunk=16, device_resident=True)
    np.testing.assert_array_equal(first.selected, second.selected)
    assert ([decode_pod_result(first, i) for i in range(n)]
            == [decode_pod_result(second, i) for i in range(n)])
    for leaf, want in zip(jax.tree.leaves(cw.init_carry), carry0):
        np.testing.assert_array_equal(np.asarray(leaf), want)
    for leaf, want in zip(jax.tree.leaves(cw.xs), xs0):
        np.testing.assert_array_equal(np.asarray(leaf), want)
