"""A pass's xs, carry and statics reach the device through one site
(state/compile.py over state/packed.py pack_tree, PR 35), and stay as they
were sent (PR 44).

Every build hands numpy leaves; compile_workload reads the decoder's flags
and the scan-cache key's digest off those host bytes and then uploads the
trees once: one contiguous buffer per dtype.  The sequential scan takes
those buffers as they are and slices them apart inside its own executable
(framework/replay.py _packed_scan_for); whoever asks the workload for
leaves (a mesh, the speculative rounds, the host-interleaved path) gets
them unpacked on first access by one jitted dispatch.  Held here: what
reaches the device is, leaf for leaf, what a jnp.asarray of the same numpy
leaf gives (bytes, shape, dtype, weak type), the scan-cache key does not
see the route, nothing under compile_workload converts a device array to
numpy or uploads a leaf on its own, a steady pass makes at most five
transfers and a dozen dispatches, the packed route's result (a pass of one
chunk) and a many-chunk pass's over the lazily unpacked leaves are
byte-equal to a hand-held workload's, and no replay consumes the
workload's packed carry.
"""

from __future__ import annotations

import dataclasses
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
from kube_scheduler_simulator_tpu.state.compile import compile_workload
from kube_scheduler_simulator_tpu.state.packed import pack_tree, upload_tree
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

from test_scan_prepare import _FetchSpy, _routes
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


def _compile_recording(monkeypatch, kwargs):
    """compile_workload, with every tree handed to the upload site kept:
    the closure statics' (a fresh node table: its one generation is made
    here), then the pass's own (xs, carry, argument statics, skip masks)."""
    handed = []

    def recording(route):
        def record(tree):
            handed.append(tree)
            return route(tree)
        return record

    with monkeypatch.context() as patch:
        patch.setattr(compile_mod, "upload_tree", recording(upload_tree))
        patch.setattr(compile_mod, "pack_tree", recording(pack_tree))
        cw = compile_workload(**kwargs)
    return cw, handed


def _as_leaves(cw, handed):
    """The same workload as the parent held it: one jnp.asarray a numpy
    leaf, nothing packed."""
    statics, (xs, init_carry, arg_statics, _masks) = handed
    return dataclasses.replace(
        cw, xs=_per_leaf(xs), init_carry=_per_leaf(init_carry),
        statics=_per_leaf({**statics, **arg_statics}))


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
    assert len(handed) == 2
    statics, (xs, init_carry, arg_statics, _masks) = handed
    assert cw.packed is not None and cw.__dict__["_xs"] is None, (
        "compile_workload unpacked what it uploaded")
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
    cw, handed = _compile_recording(monkeypatch, workload)
    old = _as_leaves(cw, handed)
    assert old.packed is None
    key = _workload_scan_key(cw, 16)
    assert cw.__dict__["_xs"] is None, "the key unpacked the workload"
    assert key == _workload_scan_key(old, 16)
    cw.xs                                   # unpacked: the key stays
    assert _workload_scan_key(cw, 16) == key


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


@pytest.mark.parametrize("shape, own", [
    ((16, 4096), True), ((64, 5000), True), ((32, 5000, 2), True),
    ((8, 20000), False),        # a resident payload's rows (ROWS_MAX)
    ((64, 1000), False),        # many rows, short ones
    ((65536,), False)])
def test_a_leaf_of_many_long_rows_travels_as_a_buffer_of_its_own(shape, own):
    """Cutting [64, 5000] out of a flat buffer is a relayout a row in the
    consumer's executable (state/packed.py): such a leaf is sent in the
    same device_put as the buffers, stands in the tree as the device
    array it is, and is a copy of the host's bytes; every other leaf is
    packed as ever."""
    from kube_scheduler_simulator_tpu.state.packed import Packed

    big = (np.arange(np.prod(shape)) % 7).astype(np.int32).reshape(shape)
    tree = {"big": big, "flags": np.array([True, False]),
            "rows": np.arange(6, dtype=np.int32).reshape(2, 3)}
    before = TRACER.counter_totals().get("workload_h2d_transfers_total", 0)
    packed = pack_tree(tree)
    sent = TRACER.counter_totals()["workload_h2d_transfers_total"] - before
    leaf = packed.tree["big"]
    assert isinstance(leaf, Packed) != own
    assert isinstance(packed.tree["rows"], Packed)
    assert sent == (3 if own else 2)
    assert packed.bufs["int32"].size == 6 + (0 if own else big.size)
    got = packed.take(packed.tree)
    want = _per_leaf(tree)
    for key in tree:
        _assert_same_leaf(got[key], want[key], key)
    if own:
        assert got["big"] is leaf
        big[0] = -1                         # the host goes on writing
        assert int(np.asarray(leaf).min()) == 0


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


# ------------------------------- the packed route equals the leaves route


def _result_bytes(rr) -> dict:
    """Everything a ReplayResult holds of the scan, as bytes: the decision
    rows, every compact tensor of every chunk (pad rows too), the
    attribution sums that rode the fetch."""
    cc = rr._compact
    out = {name: np.asarray(getattr(rr, name)).tobytes()
           for name in ("selected", "feasible_count", "prefilter_reject")}
    for group in cc.GROUPS:
        for ci, chunk in enumerate(getattr(cc, group)):
            a = np.asarray(chunk)
            out[f"{group}[{ci}]"] = (a.shape, str(a.dtype), a.tobytes())
    for ci, att in enumerate(cc.att):
        for name, a in sorted((att or {}).items()):
            out[f"att[{ci}].{name}"] = (a.shape, str(a.dtype), a.tobytes())
    return out


def _both_routes(cw, **how):
    """replay(cw) as compile_workload made it (over its packed buffers
    where the pass is one chunk, else over leaves unpacked once), then
    over the same workload held as leaves from the start -> the two
    results' bytes."""
    assert cw.packed is not None and cw.__dict__["_xs"] is None
    one_chunk = cw.n_pods <= how["chunk"]
    before = _routes()
    made = replay(cw, **how)
    after = _routes()
    took = {r: after.get(r, 0) - before.get(r, 0) for r in ("packed", "leaves")}
    if one_chunk:
        assert cw.__dict__["_xs"] is None, "the packed route unpacked"
        assert took["packed"] >= 1 and took["leaves"] == 0
    else:
        assert cw.__dict__["_xs"] is not None
        assert took["leaves"] >= 1 and took["packed"] == 0
    as_leaves = dataclasses.replace(cw)
    assert as_leaves.packed is None
    leaves = replay(as_leaves, **how)
    assert _routes().get("leaves", 0) - after.get("leaves", 0) >= 1
    assert _routes().get("packed", 0) == after.get("packed", 0)
    return _result_bytes(made), _result_bytes(leaves)


# (pods in the pass, chunk).  One chunk, the packed route: one pod; a whole
# chunk of two; the fixture's five pods.  More chunks, where a packed
# workload's leaves are unpacked once: a carried chunk and a last, short
# one with a pad row; four carried chunks on the grid
CHUNKINGS = {"p=1": (1, 4), "p=chunk": (2, 2), "p=5,chunk=512": (5, 512),
             "p=chunk+1": (3, 2), "p=2chunk+3": (5, 1)}


@pytest.mark.parametrize("chunking", list(CHUNKINGS))
def test_packed_route_equals_the_leaves_route(workload, chunking):
    p, chunk = CHUNKINGS[chunking]
    assert len(workload["pods"]) >= p
    cw = compile_workload(**{**workload, "pods": workload["pods"][:p]})
    packed, leaves = _both_routes(cw, chunk=chunk, device_resident=True)
    assert any(key.startswith("att[") for key in packed)
    assert packed.keys() == leaves.keys()
    for key in packed:
        assert packed[key] == leaves[key], key


def test_packed_route_equals_the_leaves_route_fetched_whole(workload):
    """The host-resident rung: no attribution reduction in the executable,
    every compact tensor fetched."""
    cw = compile_workload(**workload)
    packed, leaves = _both_routes(cw, chunk=512, device_resident=False)
    assert not any(key.startswith("att[") for key in packed)
    assert packed == leaves


# -------------------------------------------------- the carry survives


@pytest.mark.parametrize("chunk", [512, 2])
def test_replay_twice_on_one_uploaded_workload(monkeypatch, workload, chunk):
    """The pass's buffers are the workload's own and nothing is donated
    them: one chunk cuts its carry out of them inside the scan; chunks of
    two read leaves unpacked from them once, and donate a copy."""
    cw, (_, (xs, init_carry, _args, _masks)) = _compile_recording(
        monkeypatch, workload)
    n = len(workload["pods"])
    first = replay(cw, chunk=chunk, device_resident=True)
    second = replay(cw, chunk=chunk, device_resident=True)
    assert (cw.__dict__["_xs"] is None) == (n <= chunk)
    np.testing.assert_array_equal(first.selected, second.selected)
    assert ([decode_pod_result(first, i) for i in range(n)]
            == [decode_pod_result(second, i) for i in range(n)])
    assert _result_bytes(first) == _result_bytes(second)
    for buf in cw.packed.bufs.values():
        assert not buf.is_deleted()
    # ... and what they hold is what was handed to the upload
    for got, want in ((cw.init_carry, init_carry), (cw.xs, xs)):
        for leaf, host in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(leaf), host)
