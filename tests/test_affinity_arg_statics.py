"""NodeAffinity's match rows are ARGUMENTS of the jitted scan, not closure
constants (state/compile.py ARG_STATICS, plugins/affinity.py): another
pod's terms are the same scan-cache key and the same executable on every
route, the U / V axes are padded, the rows come from the node table's
memo, and every annotation is byte for byte the sequential reference's."""

from __future__ import annotations

import copy
import dataclasses
import sys

import jax
import numpy as np
import pytest

from kube_scheduler_simulator_tpu.framework.replay import (
    _workload_scan_key, replay)
from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.reference_impl.sequential import (
    SequentialScheduler)
from kube_scheduler_simulator_tpu.state.compile import (
    ARG_STATICS, compile_workload, split_statics, statics_digest)
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

replay_mod = sys.modules["kube_scheduler_simulator_tpu.framework.replay"]
C3 = ["NodeResourcesFit", "NodeResourcesBalancedAllocation", "NodeAffinity",
      "TaintToleration"]
CFG = PluginSetConfig(enabled=C3)
TYPE = "node.kubernetes.io/instance-type"


def _pod(name: str, required: list[str] | None = None,
         preferred: list[tuple[int, str]] = (), selector: dict | None = None):
    pod = copy.deepcopy(make_pods(1, seed=5)[0])
    pod["metadata"]["name"] = name
    aff = {}
    if required:
        aff["requiredDuringSchedulingIgnoredDuringExecution"] = {
            "nodeSelectorTerms": [{"matchExpressions": [
                {"key": "disktype", "operator": "In", "values": required}]}]}
    if preferred:
        aff["preferredDuringSchedulingIgnoredDuringExecution"] = [
            {"weight": w, "preference": {"matchExpressions": [
                {"key": TYPE, "operator": "In", "values": [t]}]}}
            for w, t in preferred]
    if aff:
        pod["spec"]["affinity"] = {"nodeAffinity": aff}
    if selector:
        pod["spec"]["nodeSelector"] = selector
    return pod


def _labeled(name: str) -> dict:
    series = TRACER.snapshot()["labeled_counters"].get(name, [])
    out: dict = {}
    for s in series:
        key = next(v for k, v in s["labels"].items() if k != "session")
        out[key] = out.get(key, 0) + s["value"]
    return out


def _counter(name: str) -> int:
    return TRACER.summary()["counters"].get(name, 0)


def _executables() -> dict:
    """Every cached scan callable and how many programs jax compiled for
    it: a new entry is a new key, a grown size a new executable."""
    return {key: fn._cache_size()
            for key, fn in replay_mod._SCAN_CACHE._entries.items()
            if hasattr(fn, "_cache_size")}


# ------------------------------------------------ one key, one executable

NODES = make_nodes(40, seed=3, taint_fraction=0.1)

# two passes of the same length whose pods differ in node-affinity terms
# alone: required and preferred, a nodeSelector, none at all
ONE_A = [_pod("a", ["ssd"], [(7, "type-1")])]
ONE_B = [_pod("b", ["hdd"], [(93, "type-2")], selector={"disktype": "hdd"})]
ONE_C = [_pod("c")]
THREE_A = [_pod("a0", ["ssd"], [(7, "type-1")]), _pod("a1"),
           _pod("a2", ["hdd"])]
THREE_B = [_pod("b0"), _pod("b1", ["hdd"], [(50, "type-3")]),
           _pod("b2", ["ssd", "hdd"])]


def _run_packed(cw):
    assert cw.packed is not None
    return replay(cw, device_resident=True)


def _run_leaves(cw):
    # a workload compile_workload did not make: its trees are leaves
    bare = dataclasses.replace(cw)
    assert bare.packed is None
    return replay(bare, device_resident=True)


def _run_chunked(cw):
    return replay(cw, chunk=2, device_resident=True)


ROUTES = [
    ("packed_one_chunk", _run_packed, (ONE_A, ONE_B, ONE_C), "packed"),
    ("leaves", _run_leaves, (ONE_A, ONE_B, ONE_C), "leaves"),
    ("sequential_chunks", _run_chunked, (THREE_A, THREE_B), "leaves"),
]


@pytest.mark.parametrize("name,run,passes,route", ROUTES,
                         ids=[r[0] for r in ROUTES])
def test_other_terms_are_the_same_key_and_executable(name, run, passes, route):
    cws = [compile_workload(NODES, pods, CFG) for pods in passes]
    keys = {_workload_scan_key(cw, len(cw.pods)) for cw in cws}
    assert len(keys) == 1, "another pod's terms moved the scan-cache key"
    assert len({cw.host["_statics_fp"] for cw in cws}) == 1
    TRACER.reset()
    first = run(cws[0])
    assert _labeled("replay_route_total") == {route: 1}
    held = _executables()
    misses = replay_mod._SCAN_CACHE.misses
    results = [first] + [run(cw) for cw in cws[1:]]
    assert replay_mod._SCAN_CACHE.misses == misses, "a new scan-cache key"
    assert _executables() == held, "a new executable"
    # ... and each pass is its own pods' answer
    for pods, rr in zip(passes, results):
        want = SequentialScheduler(NODES, pods, CFG).schedule_all()
        for i, (anns, _sel) in enumerate(want):
            got = decode_pod_result(rr, i)
            assert {k: got[k] for k in anns} == anns, (name, pods[i])


def test_the_rows_are_argument_statics_and_not_the_digest_s():
    assert "NodeAffinity" in ARG_STATICS
    a, b = (compile_workload(NODES, pods, CFG) for pods in (ONE_A, ONE_B))
    for cw in (a, b):
        assert set(cw.arg_statics()) == {"NodeAffinity"}
        assert "NodeAffinity" not in cw.closure_statics()
        assert all(isinstance(leaf, jax.Array)
                   for leaf in jax.tree.leaves(cw.arg_statics()))
    rows_a, rows_b = (np.asarray(cw.arg_statics()["NodeAffinity"].req_rows)
                      for cw in (a, b))
    assert rows_a.shape == rows_b.shape and (rows_a != rows_b).any()
    # as closure constants they were the digest's: the parent's key
    both = [statics_digest({**split_statics(cw.statics)[0],
                            "NodeAffinity": jax.tree.map(
                                np.asarray, cw.arg_statics()["NodeAffinity"])})
            for cw in (a, b)]
    assert both[0] != both[1]


# ----------------------------------------------------- padding, and row 0

# U and V follow the pass's pod axis, not how many of its pods share a
# spec (since PR 50): twice the bucket of the pod count, at most 64, so
# that a burst's passes have one layout a bucket
@pytest.mark.parametrize("pods,u,v", [
    ([_pod("p")], 2, 2),
    ([_pod("p", ["ssd"], [(5, "type-0")])], 2, 2),
    ([_pod("p", ["ssd"]), _pod("q", ["hdd"], [(5, "type-0")])], 4, 4),
    ([_pod("p", ["ssd"], [(1, "type-0")]), _pod("q", ["hdd"], [(2, "type-0")]),
      _pod("r", ["ssd", "hdd"], [(3, "type-1")])], 8, 8),
    ([_pod(f"p{i}", None, [(i + 1, "type-0")]) for i in range(5)], 16, 16),
    ([_pod(f"p{i}", None, [(i + 1, "type-0")]) for i in range(40)], 64, 64),
    # more distinct specs than the floor's cap holds: the next power of two
    ([_pod(f"p{i}", None, [(i + 1, "type-0")]) for i in range(70)], 64, 128),
], ids=["no_terms", "one_spec", "two_required", "three_each", "five_preferred",
        "forty_preferred", "seventy_preferred"])
def test_axes_are_padded_and_row_0_keeps_its_meaning(pods, u, v):
    cw = compile_workload(NODES, pods, CFG)
    st = jax.tree.map(np.asarray, cw.arg_statics()["NodeAffinity"])
    xs = jax.tree.map(np.asarray, cw.xs["NodeAffinity"])
    n = len(NODES)
    assert st.req_rows.shape == (u, n) and st.req_rows.dtype == np.bool_
    assert st.pref_rows.shape == (v, n) and st.pref_rows.dtype == np.int32
    assert st.req_rows[0].all() and not st.pref_rows[0].any()
    # a pod without terms gathers row 0 and skips; nothing gathers a pad row
    specs_r = len({i for i in xs.req_idx if i})
    specs_v = len({i for i in xs.pref_idx if i})
    assert xs.req_idx.max(initial=0) == specs_r < u
    assert xs.pref_idx.max(initial=0) == specs_v < v
    for i, pod in enumerate(pods):
        aff = (pod["spec"].get("affinity") or {}).get("nodeAffinity") or {}
        assert bool(xs.filter_skip[i]) == (
            "requiredDuringSchedulingIgnoredDuringExecution" not in aff)
        assert bool(xs.score_skip[i]) == (
            "preferredDuringSchedulingIgnoredDuringExecution" not in aff)
        assert (xs.req_idx[i] == 0) == bool(xs.filter_skip[i])
        assert (xs.pref_idx[i] == 0) == bool(xs.score_skip[i])


def test_a_pass_that_outgrows_an_axis_is_counted():
    nodes = make_nodes(12, seed=8)
    TRACER.reset()
    first = compile_workload(nodes, [_pod("p", ["ssd"])], CFG)
    assert _labeled("affinity_axis_rebuckets_total") == {"req": 0, "pref": 0}
    compile_workload(nodes, [_pod("q", ["hdd"], [(4, "type-1")])], CFG,
                     reuse=first)
    assert _labeled("affinity_axis_rebuckets_total") == {"req": 0, "pref": 0}
    # two pods in one pass: the pod axis is 2 rows, the floor of U and V
    # 4, another layout (the pod axis's own: pod_axis_rebuckets_total)
    compile_workload(nodes, [_pod("r", ["ssd"]), _pod("s", ["hdd"])], CFG,
                     reuse=first)
    assert _labeled("affinity_axis_rebuckets_total") == {"req": 1, "pref": 1}
    # ... and back: another layout again
    compile_workload(nodes, [_pod("t")], CFG, reuse=first)
    assert _labeled("affinity_axis_rebuckets_total") == {"req": 2, "pref": 2}
    # within one pod axis the specs a pass holds move nothing, up to the
    # floor: one pod with two required specs' worth of rows is U 2 still
    compile_workload(nodes, [_pod("u", ["ssd", "hdd"], [(7, "type-0")])],
                     CFG, reuse=first)
    assert _labeled("affinity_axis_rebuckets_total") == {"req": 2, "pref": 2}


# ------------------------------------------------------- rows from the memo

def test_a_spec_seen_before_on_this_table_walks_no_node():
    nodes = make_nodes(30, seed=4)
    TRACER.reset()
    first = compile_workload(nodes, [_pod("p", ["ssd"], [(9, "type-1")])], CFG)
    assert _counter("affinity_rows_built_total") == 2   # required + one term
    # the same required spec; the same term under another weight
    again = compile_workload(nodes, [_pod("q", ["ssd"], [(71, "type-1")])],
                             CFG, reuse=first)
    assert again.node_table is first.node_table
    assert _counter("affinity_rows_built_total") == 2
    pref = np.asarray(again.arg_statics()["NodeAffinity"].pref_rows)
    assert set(np.unique(pref[1])) <= {0, 71} and (pref[1] == 71).any()
    # another term is one more row; a changed node a new table, all anew
    compile_workload(nodes, [_pod("r", ["ssd"], [(9, "type-2")])], CFG,
                     reuse=first)
    assert _counter("affinity_rows_built_total") == 3
    moved = copy.deepcopy(nodes)
    moved[0]["metadata"]["labels"]["disktype"] = "nvme"
    compile_workload(moved, [_pod("s", ["ssd"], [(9, "type-1")])], CFG)
    assert _counter("affinity_rows_built_total") == 5


# ------------------------------------------------------------- the records

@pytest.mark.parametrize("name", ["affinity_axis_rebuckets_total",
                                  "affinity_rows_built_total"])
def test_every_new_counter_has_its_line_in_the_docs(name):
    from pathlib import Path

    from kube_scheduler_simulator_tpu.utils import tracing

    docs = Path(__file__).resolve().parent.parent / "docs"
    assert f"`{name}" in (docs / "metrics.md").read_text(), name
    assert name in tracing._HELP
