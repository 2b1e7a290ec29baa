"""scan_prepare asks the device nothing (framework/replay.py, PR 29), and
since PR 44 hands it nothing either where the scan takes the pass's
buffers as compile_workload uploaded them.

The scan-cache key is built from array metadata (a leaf's own, or the
packed layout's, which says the same) and from a digest that
compile_workload takes over the statics' host bytes before it uploads
them; the first chunk's carry is cut out of the pass's buffers inside the
scan's executable, and where the workload is held as leaves (a mesh, the
speculative rounds) the carry copy is one jitted dispatch.  Held here: the
digest is the one the fetch-and-hash fallback computes and is as
discriminating, the key is the same on both routes, nothing in
scan_prepare converts a device array to numpy, the workload's own
init_carry survives the donated scan on either route and under the
width-tier rerun, a steady one-pod pass is two dispatches over the packed
route, a mesh, the speculative rounds or a pass of many chunks read leaves
that are unpacked once, and the counter that says which way a key's digest
came.
"""

from __future__ import annotations

import copy
import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kube_scheduler_simulator_tpu.framework.replay import (
    _copy_carry, _statics_fingerprint, _workload_scan_key, replay)
from kube_scheduler_simulator_tpu.models.workloads import (
    baseline_config, make_nodes, make_pods)
from kube_scheduler_simulator_tpu.parallel.mesh import make_mesh
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.server.sessions import SessionManager
from kube_scheduler_simulator_tpu.config.config import SimulatorConfiguration
from kube_scheduler_simulator_tpu.state.compile import (
    compile_workload, split_statics, statics_digest)
from kube_scheduler_simulator_tpu.state.packed import upload_tree
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result
from kube_scheduler_simulator_tpu.utils import hostevents
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

replay_mod = sys.modules["kube_scheduler_simulator_tpu.framework.replay"]
packed_mod = sys.modules["kube_scheduler_simulator_tpu.state.packed"]

# the parity suite's three constraint profiles (tests/test_parity.py):
# NodeAffinity + taints, + PodTopologySpread, + InterPodAffinity
PROFILES = [(3, 0.02), (4, 0.01), (5, 0.01)]


def _cw(idx: int, scale: float, seed: int = 0):
    nodes, pods, cfg = baseline_config(idx, scale=scale, seed=seed)
    return compile_workload(nodes, pods, cfg), (nodes, pods, cfg)


def _without_digest(cw, **changes):
    """The same workload as a tool would hand-build it: no digest."""
    host = {k: v for k, v in cw.host.items() if k != "_statics_fp"}
    return dataclasses.replace(cw, host=host, **changes)


def _statics_counts() -> dict[str, float]:
    return TRACER.labeled_totals("scan_key_statics_total", "source")


# ------------------------------------------------------------ the digest


@pytest.mark.parametrize("idx,scale", PROFILES)
def test_digest_from_host_bytes_is_the_fetched_one(idx, scale):
    cw, _ = _cw(idx, scale)
    given = cw.host["_statics_fp"]
    assert all(isinstance(leaf, jax.Array)
               for leaf in jax.tree.leaves(cw.statics)
               if hasattr(leaf, "shape")), "statics must be uploaded"
    before = _statics_counts()
    bare = _without_digest(cw)
    assert _statics_fingerprint(bare) == given
    # the fallback fetched once and keeps what it found
    assert bare.host["_statics_fp"] == given
    assert _statics_fingerprint(bare) == given
    assert _statics_fingerprint(cw) == given
    after = _statics_counts()
    assert after.get("fetched", 0) - before.get("fetched", 0) == 1
    assert after.get("host", 0) - before.get("host", 0) == 2
    # equal statics from a second compile share the key
    again, _ = _cw(idx, scale)
    assert _workload_scan_key(again, 16) == _workload_scan_key(cw, 16)


@pytest.mark.parametrize("idx,scale", PROFILES)
def test_key_is_the_same_on_both_routes(idx, scale):
    """The packed layout carries the paths, shapes and dtypes that the
    leaves would: a cached executable is shared by the same workloads."""
    cw, _ = _cw(idx, scale)
    assert cw.packed is not None
    key = _workload_scan_key(cw, 16)
    assert cw.__dict__["_xs"] is None, "the key unpacked the workload"
    as_leaves = dataclasses.replace(cw)
    assert as_leaves.packed is None
    assert _workload_scan_key(as_leaves, 16) == key
    assert _workload_scan_key(cw, 16) == key    # unpacked by now: the same


def _core_variants(core):
    """(what differs, the core static) — each differs from `core` in
    exactly one of bytes / dtype / shape."""
    alloc = np.asarray(core.allocatable)
    one_byte = alloc.copy()
    one_byte.view(np.uint8)[0] ^= 1
    yield "byte", core._replace(allocatable=one_byte)
    # same bytes, same shape, another dtype
    yield "dtype", core._replace(allocatable=alloc.view(np.uint64))
    # same bytes, same dtype, another shape
    yield "shape", core._replace(allocatable=alloc.reshape(alloc.shape[::-1]))


@pytest.mark.parametrize("idx,scale", PROFILES)
def test_one_static_byte_dtype_or_shape_changes_the_key(idx, scale):
    cw, _ = _cw(idx, scale)
    key = _workload_scan_key(cw, 16)
    seen = {key[0]}
    for what, core in _core_variants(cw.statics["core"]):
        statics = {**cw.statics, "core": core}
        # from host bytes, as compile_workload digests them ...
        fp_host = statics_digest(split_statics(statics)[0])
        # ... and fetched back from the uploaded copy, as the fallback does
        other = _without_digest(cw, statics=upload_tree(statics))
        other_key = _workload_scan_key(other, 16)
        assert other_key[0] == fp_host, what
        assert other_key != key, what
        assert other_key[1:] == key[1:], what   # only the digest moved
        assert fp_host not in seen, what
        seen.add(fp_host)


def test_a_changed_node_changes_the_digest_compile_workload_sets():
    nodes, pods, cfg = baseline_config(3, scale=0.02)
    a = compile_workload(nodes, pods, cfg)
    nodes2 = copy.deepcopy(nodes)
    nodes2[0]["status"]["allocatable"]["cpu"] = "3"
    b = compile_workload(nodes2, pods, cfg)
    assert a.host["_statics_fp"] != b.host["_statics_fp"]
    assert _workload_scan_key(a, 16) != _workload_scan_key(b, 16)


def test_key_shapes_read_metadata_as_numpy_would():
    """The key's shapes entry says what the parent's np.asarray walk
    said, leaf for leaf."""
    cw, _ = _cw(5, 0.01)
    shapes = _workload_scan_key(cw, 16)[2]
    want = tuple(
        (str(path), tuple(np.shape(leaf)), str(np.asarray(leaf).dtype))
        for tree in (cw.xs, cw.init_carry, cw.arg_statics())
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0])
    assert shapes == want
    assert replay_mod._leaf_sig(7) == ((), str(np.asarray(7).dtype))
    assert replay_mod._leaf_sig(True) == ((), "bool")


# ----------------------------------------- nothing waits on the device


class _FetchSpy:
    """Counts conversions of jax Arrays to numpy, by the tracer span they
    happen under.  np.asarray reaches a CPU-backend array through the
    buffer protocol, which cannot be patched, so numpy's own entry points
    are wrapped; the array type's _value is what int(), bool(), item()
    and, on an accelerator, __array__ go through."""

    def __init__(self, monkeypatch):
        self.by_span: dict[str | None, int] = {}
        for fn_name in ("asarray", "array", "ascontiguousarray"):
            monkeypatch.setattr(np, fn_name,
                                self._wrap(getattr(np, fn_name)))
        arr_type = type(jnp.zeros(1))
        value = arr_type._value

        def spied_value(arr):
            self._note()
            return value.fget(arr)

        monkeypatch.setattr(arr_type, "_value", property(spied_value))

    def _note(self):
        span = TRACER.current_span_name()
        self.by_span[span] = self.by_span.get(span, 0) + 1

    def _wrap(self, fn):
        def spied(a, *args, **kw):
            if isinstance(a, jax.Array):
                self._note()
            return fn(a, *args, **kw)
        return spied


@pytest.mark.parametrize("idx,scale", PROFILES)
def test_scan_prepare_converts_no_device_array(monkeypatch, idx, scale):
    cw, _ = _cw(idx, scale)
    warm, _ = _cw(idx, scale)
    replay(warm, chunk=16, device_resident=True)   # compile outside the spy
    bare = _without_digest(warm)
    spy = _FetchSpy(monkeypatch)
    key = _workload_scan_key(cw, 16)
    assert spy.by_span == {}, "the key fetched a device array"
    rr = replay(cw, chunk=16, device_resident=True)
    assert rr.scheduled >= 0
    assert spy.by_span.get("scan_prepare", 0) == 0, spy.by_span
    assert spy.by_span.get("decision_fetch", 0) > 0, (
        "the spy must see the fetches that are meant to happen")
    # and it does see the fallback's read-backs
    spy.by_span.clear()
    assert _workload_scan_key(bare, 16) == key
    assert spy.by_span.get(None, 0) == sum(
        isinstance(leaf, jax.Array)
        for leaf in jax.tree.leaves(warm.closure_statics()))


# -------------------------------------------------- the carry survives


def test_copy_carry_is_a_real_copy():
    """A jitted identity forwards its input buffers; the copy must not,
    or the scan's donation would take the workload's init_carry."""
    cw, _ = _cw(5, 0.01)
    copied = _copy_carry(cw.init_carry)
    assert jax.tree.structure(copied) == jax.tree.structure(cw.init_carry)
    for src, dst in zip(jax.tree.leaves(cw.init_carry),
                        jax.tree.leaves(copied)):
        assert dst is not src
        assert dst.shape == src.shape and dst.dtype == src.dtype
        if src.size:
            assert dst.unsafe_buffer_pointer() != src.unsafe_buffer_pointer()
        want = np.asarray(src)
        dst.delete()                      # what donation does to the copy
        np.testing.assert_array_equal(np.asarray(src), want)


def _decoded(rr, n):
    return [decode_pod_result(rr, i) for i in range(n)]


@pytest.mark.parametrize("idx,scale", PROFILES)
def test_replay_twice_on_one_workload(idx, scale):
    hostevents.install()
    cw, (_, pods, _) = _cw(idx, scale, seed=3)
    carry0 = [np.asarray(leaf).copy() for leaf in jax.tree.leaves(cw.init_carry)]
    first = replay(cw, chunk=16, device_resident=True)
    compiles = TRACER.counter_totals().get("jax_compile_events_total", 0)
    second = replay(cw, chunk=16, device_resident=True)
    assert TRACER.counter_totals().get(
        "jax_compile_events_total", 0) == compiles, (
        "the second replay of the same shapes compiled something")
    np.testing.assert_array_equal(first.selected, second.selected)
    assert _decoded(first, len(pods)) == _decoded(second, len(pods))
    for leaf, want in zip(jax.tree.leaves(cw.init_carry), carry0):
        np.testing.assert_array_equal(np.asarray(leaf), want)


@pytest.mark.parametrize("route,chunk,nth", [
    ("packed", 4096, 2), ("many_chunks", 32, 3), ("leaves", 32, 3)])
def test_width_tier_rerun_replays_the_same_init_carry(monkeypatch, route,
                                                      chunk, nth):
    """The wider rerun starts from cw.init_carry again: over the packed
    buffers (a pass of one chunk) the first tier cut its carry out of
    buffers that nothing donates; over leaves (a pass of many chunks,
    unpacked once; a hand-held workload) the first tier's scan was donated
    a copy."""
    cw, (_, pods, _) = _cw(4, 0.02, seed=11)
    if route == "leaves":
        cw = dataclasses.replace(cw)
    assert (cw.packed is not None) == (route != "leaves")
    plain = _decoded(replay(cw, chunk=chunk, device_resident=True), len(pods))
    real_fetch = replay_mod._fetch_decisions
    state = {"count": 0}

    def inject_overflow(out_dev, att):
        c = real_fetch(out_dev, att)
        state["count"] += 1
        if state["count"] == nth:
            c["raw_overflow"] = np.asarray(True)
        return c

    monkeypatch.setattr(replay_mod, "_fetch_decisions", inject_overflow)
    state["count"] = 1 if route == "packed" else 0
    before = TRACER.counter_totals().get("replay_width_retries_total", 0)
    rr = replay(cw, chunk=chunk, device_resident=True)
    assert TRACER.counter_totals().get(
        "replay_width_retries_total", 0) - before >= 1
    assert _decoded(rr, len(pods)) == plain
    if route != "leaves":
        assert (cw.__dict__["_xs"] is None) == (route == "packed")
        assert not any(b.is_deleted() for b in cw.packed.bufs.values())
    for leaf in jax.tree.leaves(cw.init_carry):
        np.asarray(leaf)                  # not deleted by either donation


# ------------------------------------------------ the route, the dispatches


def _routes() -> dict[str, float]:
    return TRACER.labeled_totals("replay_route_total", "route")


class _UnpackSpy:
    """Counts the dispatches that hand a packed workload's leaves out as
    device arrays of their own (state/packed.py PackedPass.take)."""

    def __init__(self, monkeypatch):
        self.calls = 0
        real = packed_mod._unpack

        def counted(*args):
            self.calls += 1
            return real(*args)

        monkeypatch.setattr(packed_mod, "_unpack", counted)


def _spec_workload():
    nodes = make_nodes(24, seed=9, taint_fraction=0.2)
    pods = make_pods(20, seed=10, with_affinity=True, with_tolerations=True)
    cfg = PluginSetConfig(enabled=[
        "NodeResourcesFit", "NodeResourcesBalancedAllocation",
        "NodeAffinity", "TaintToleration", "NodeUnschedulable", "NodeName"])
    return compile_workload(nodes, pods, cfg), pods


@pytest.mark.parametrize("how", ["mesh", "many_chunks"])
def test_a_mesh_or_many_chunks_read_leaves_unpacked_once(monkeypatch, how):
    cw, pods = _spec_workload()
    base = _decoded(replay(cw, chunk=64), len(pods))
    assert cw.__dict__["_xs"] is None

    def run():
        if how == "mesh":
            return replay(cw, chunk=8, mesh=make_mesh(8, dp=1))
        return replay(cw, chunk=8)

    spy = _UnpackSpy(monkeypatch)
    before = _routes()
    first = run()
    assert spy.calls == 1, "the workload's leaves are one dispatch"
    assert cw.__dict__["_xs"] is not None
    second = run()
    assert spy.calls == 1, "unpacked again"
    after = _routes()
    assert after.get("leaves", 0) - before.get("leaves", 0) == 2
    assert after.get("packed", 0) == before.get("packed", 0)
    assert _decoded(first, len(pods)) == base == _decoded(second, len(pods))
    # and the one-chunk scan of the same workload still takes it packed
    assert _decoded(replay(cw, chunk=64), len(pods)) == base
    assert _routes().get("packed", 0) - after.get("packed", 0) == 1
    assert spy.calls == 1


def test_steady_one_pod_pass_is_two_dispatches_over_the_packed_route():
    """One pod a pass in a served session under the default profile: the
    pass's one transfer and the chunk's one call, where the parent made
    ~60 (an unpack, a carry copy, two mask uploads, 45 slices of xs, ...)."""
    mgr = SessionManager(cfg=SimulatorConfiguration(port=0),
                         start_scheduler=False, idle_ttl=0, max_sessions=2)
    try:
        sess = mgr.create("dispatches")
        for n in make_nodes(8, seed=41):
            sess.di.store.create("nodes", n)

        def counts():
            snap = TRACER.snapshot(session="dispatches")
            routes = {"packed": 0.0, "leaves": 0.0}
            for series in TRACER.snapshot()["labeled_counters"].get(
                    "replay_route_total", []):
                if series["labels"].get("session") == "dispatches":
                    routes[series["labels"]["route"]] += series["value"]
            return (snap["counters"].get("pass_device_dispatches_total", 0),
                    snap["counters"].get("scheduling_work_passes_total", 0),
                    routes)

        rises = []
        for pod in make_pods(3, seed=42):
            before = counts()
            sess.di.store.create("pods", pod)
            assert sess.di.engine.schedule_pending() == 1
            after = counts()
            assert after[1] - before[1] == 1
            assert after[2]["packed"] - before[2]["packed"] == 1
            assert after[2]["leaves"] == 0
            rises.append(after[0] - before[0])
        assert rises[0] > rises[1], rises      # the statics, once a table
        assert rises[1] == rises[2] == 2, rises
        assert rises[0] <= 12, rises
    finally:
        mgr.shutdown()


# ------------------------------------------------------------ the counter


def test_served_session_counts_host_digests_only():
    """One pod a pass in a served session: every pass's key takes the
    digest compile_workload made; nothing is fetched."""
    mgr = SessionManager(cfg=SimulatorConfiguration(port=0),
                         start_scheduler=False, idle_ttl=0, max_sessions=2)
    try:
        sess = mgr.create("scan-key")
        for n in make_nodes(8, seed=21):
            sess.di.store.create("nodes", n)
        pods = make_pods(4, seed=22)

        def counts():
            out = {"host": 0.0, "fetched": 0.0}
            for s in TRACER.snapshot()["labeled_counters"].get(
                    "scan_key_statics_total", []):
                if s["labels"].get("session") == "scan-key":
                    out[s["labels"]["source"]] += s["value"]
            return out

        for i, pod in enumerate(pods):
            before = counts()
            passes = TRACER.snapshot(session="scan-key")["counters"].get(
                "scheduling_work_passes_total", 0)
            sess.di.store.create("pods", pod)
            assert sess.di.engine.schedule_pending() == 1
            after = counts()
            assert TRACER.snapshot(session="scan-key")["counters"][
                "scheduling_work_passes_total"] - passes == 1
            assert after["host"] - before["host"] == 1, (i, before, after)
            assert after["fetched"] == 0
    finally:
        mgr.shutdown()


def test_hand_built_workload_counts_fetched():
    cw, _ = _cw(3, 0.02)
    bare = _without_digest(cw)
    before = _statics_counts()
    replay(bare, chunk=16)
    after = _statics_counts()
    assert after.get("fetched", 0) - before.get("fetched", 0) == 1
    assert after.get("host", 0) == before.get("host", 0)
    assert bare.host["_statics_fp"] == cw.host["_statics_fp"]
