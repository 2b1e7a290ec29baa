"""The decision fetch is one transfer over the packed route
(framework/replay.py, PR 49).

The packed scan's executable lays the pass's decision fields and every
leaf of the attribution sums end to end into ONE int32 buffer
(_pack_row), and _fetch_decisions pulls that buffer with one np.asarray
and cuts it by the executable's static layout (_cut_row), where it pulled
a device array a field: nine or ten blocking round trips a pass for under
2.2 KB.  Held here: what the fetch hands on is, key for key, in dtype,
shape and value what it hands on over leaves for the same workload, under
every shape an attribution plan can take; the executable returns five
buffers; a served one-pod pass counts one transfer
(decision_fetch_transfers_total) and a pass over leaves a field apiece;
the row's cut is exact for every width a field can have.  Byte order on
the chip is chip_smoke.py's to vouch for (wave A's byte parity), not a CPU
test's.
"""

from __future__ import annotations

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kube_scheduler_simulator_tpu.config.config import SimulatorConfiguration
from kube_scheduler_simulator_tpu.framework.pipeline import CompactOut
from kube_scheduler_simulator_tpu.framework.replay import replay
from kube_scheduler_simulator_tpu.models.workloads import (
    baseline_config, make_nodes, make_pods)
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.server.sessions import SessionManager
from kube_scheduler_simulator_tpu.state.compile import compile_workload
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

replay_mod = sys.modules["kube_scheduler_simulator_tpu.framework.replay"]

DECISION_FIELDS = ("selected", "feasible_count", "prefilter_reject",
                   "raw_overflow")


def _baseline(idx, scale, pods=None):
    def make():
        nodes, queue, cfg = baseline_config(idx, scale=scale, seed=7)
        return nodes, queue[:pods], cfg
    return make


def _plain(n_nodes, n_pods, enabled):
    def make():
        return (make_nodes(n_nodes, seed=5, taint_fraction=0.2),
                make_pods(n_pods, seed=6, with_affinity=True,
                          with_tolerations=True),
                None if enabled is None else PluginSetConfig(enabled=enabled))
    return make


_DEFAULT_LEAVES = {"f_rejects", "f_evaluated", "s_evaluated", "s_sums",
                   "feas_packed"}

# (workload, width tier, the attribution leaves the row must hold)
CASES = {
    # the file's BASELINE configs (tests/test_scan_prepare.py PROFILES)
    "baseline3": (_baseline(3, 0.02), None, None),
    "baseline4": (_baseline(4, 0.01), None, None),
    "baseline5": (_baseline(5, 0.01), None, None),
    # a chunk of ONE pod (every served pass), the default profile: its
    # host-scored columns want the feasibility bitmap; 13 nodes are no
    # multiple of 8 or 32
    "one_pod_default": (_plain(13, 1, None), None, _DEFAULT_LEAVES),
    "several_pods_default": (_plain(37, 5, None), None, _DEFAULT_LEAVES),
    # device-scored narrow columns alone: per-pod int32 row sums
    "narrow_sums": (_plain(21, 3, ["NodeResourcesFit",
                                   "NodeResourcesBalancedAllocation"]),
                    None,
                    {"f_rejects", "f_evaluated", "s_evaluated", "s_sums"}),
    # the wide tier pools every device column in raw32: limb triples
    "wide_limbs": (_plain(21, 3, ["NodeResourcesFit",
                                  "NodeResourcesBalancedAllocation"]),
                   "i32",
                   {"f_rejects", "f_evaluated", "s_evaluated", "s_limbs"}),
    "wide_one_pod_baseline4": (_baseline(4, 0.01, pods=1), "i32", None),
    # scorers and no filter: no filter counts
    "scorer_only": (_plain(9, 2, ["NodeResourcesBalancedAllocation"]), None,
                    {"s_evaluated", "s_sums"}),
    # host-scored columns alone: the bitmap and nothing else
    "bitmap_only": (_plain(13, 3, ["ImageLocality"]), None, {"feas_packed"}),
    # neither a filter nor a scorer: no attribution at all
    "no_attribution": (_plain(11, 2, []), None, set()),
}


def _both_routes(case):
    """-> (_fetch_decisions' chunk over the packed route, over leaves)
    for one pass of one chunk of the same workload."""
    make, wide, _ = CASES[case]
    nodes, pods, cfg = make()
    cw = (compile_workload(nodes, pods) if cfg is None
          else compile_workload(nodes, pods, cfg))
    assert cw.packed is not None
    plan = replay_mod._compact_plan(cw, wide)
    dispatch, _ = replay_mod._packed_dispatch(cw, 1, wide, True, *plan)
    _, out, att = dispatch(None, 0, cw.n_pods)
    assert isinstance(out, replay_mod._PackedOut) and att is None
    packed = replay_mod._fetch_decisions(out, att)

    held = dataclasses.replace(cw)     # a hand-held workload: leaves
    assert held.packed is None
    dispatch, carry = replay_mod._leaves_dispatch(
        held, cw.pod_axis, 1, None, wide, True, *plan)
    _, out, att = dispatch(carry, 0, cw.n_pods)
    assert isinstance(out, CompactOut)
    return packed, replay_mod._fetch_decisions(out, att), cw


def _same(a, b, what):
    assert isinstance(a, np.ndarray) and isinstance(b, np.ndarray), what
    assert a.dtype == b.dtype, (what, a.dtype, b.dtype)
    assert a.shape == b.shape, (what, a.shape, b.shape)
    np.testing.assert_array_equal(a, b, err_msg=what)
    assert a.flags.c_contiguous, what


@pytest.mark.parametrize("case", list(CASES))
def test_the_row_hands_on_what_the_leaves_do(case):
    packed, leaves, cw = _both_routes(case)
    assert set(packed) == set(leaves)
    for f in DECISION_FIELDS:
        _same(packed[f], leaves[f], f)
        # a row a row of the pass's pod axis: its bucket (since PR 50),
        # the pad rows unbound on both routes
        assert packed[f].shape == (cw.pod_axis,)
    assert (packed["selected"][cw.n_pods:] == -1).all()
    want = CASES[case][2]
    if want is not None:
        assert set(leaves.get("att", ())) == want
    assert ("att" in packed) == ("att" in leaves)
    if "att" in leaves:
        assert set(packed["att"]) == set(leaves["att"])
        for k, v in leaves["att"].items():
            _same(packed["att"][k], v, k)
    # the row is what travelled: the fields' own bytes, a narrow field
    # padded to whole words (at most 3 B each for the flags and the bitmap)
    assert 0 <= packed["_d2h_bytes"] - leaves["_d2h_bytes"] <= 6
    assert packed["_d2h_bytes"] % 4 == 0


@pytest.mark.parametrize("case", ["one_pod_default", "no_attribution",
                                  "wide_limbs"])
def test_the_packed_executable_returns_five_buffers(case):
    make, wide, _ = CASES[case]
    nodes, pods, cfg = make()
    cw = (compile_workload(nodes, pods) if cfg is None
          else compile_workload(nodes, pods, cfg))
    pack_mode, score_dtypes, score_cols = replay_mod._compact_plan(cw, wide)
    scan, args = replay_mod._packed_scan_for(
        cw, 1, pack_mode, score_dtypes, wide,
        replay_mod._att_plan(cw, pack_mode, score_cols))
    outs = scan(*args)
    assert len(outs) == 5 and all(isinstance(a, jax.Array) for a in outs)
    p, n = cw.pod_axis, cw.n_nodes
    assert [a.shape[0] for a in outs[:4]] == [p] * 4
    assert outs[0].shape == (p, n)           # the heavy four stay whole
    row = outs[4]
    assert row.ndim == 1 and row.dtype == jnp.int32
    # the layout rides with the cached executable and accounts for the
    # row to the word
    layout = scan.row_layout
    assert [k for k, _, _ in layout[:4]] == list(DECISION_FIELDS)
    words = sum(-(-int(np.prod(shape)) * np.dtype(dt).itemsize // 4)
                for _, dt, shape in layout)
    assert row.shape == (words,)
    again, _ = replay_mod._packed_scan_for(
        cw, 1, pack_mode, score_dtypes, wide,
        replay_mod._att_plan(cw, pack_mode, score_cols))
    assert again is scan, "a second pass of the same shapes built again"


def _field(dtype, shape, seed):
    rng = np.random.default_rng(seed)
    if dtype == "bool":
        return rng.integers(0, 2, shape).astype(bool)
    info = np.iinfo(dtype)
    return rng.integers(info.min, info.max, shape, dtype=dtype,
                        endpoint=True)


@pytest.mark.parametrize("chunk,n8", [(1, 1), (1, 3), (3, 5), (2, 1876),
                                      (5, 4)])
def test_pack_and_cut_are_exact_for_every_width(chunk, n8):
    """Every dtype a field can have, sizes that are no whole words, the
    extreme values: nothing rounded, nothing shifted into a neighbour."""
    out = CompactOut(
        packed_filter=None, raw8=None, raw16=None, raw32=None,
        raw_overflow=_field("bool", (chunk,), 1),
        selected=_field("int32", (chunk,), 2),
        feasible_count=_field("int32", (chunk,), 3),
        prefilter_reject=_field("int32", (chunk,), 4))
    att = {"f_rejects": _field("int64", (3,), 5),
           "f_evaluated": _field("int64", (3,), 6),
           "s_evaluated": _field("int32", (2,), 7),
           "s_limbs": _field("int32", (chunk, 2, 3), 8),
           "s_sums": _field("int32", (chunk, 1), 9),
           "feas_packed": _field("uint8", (chunk, n8), 10),
           "an_int16": _field("int16", (chunk, 3), 11)}
    att["f_rejects"][0] = np.iinfo(np.int64).min
    att["f_evaluated"][-1] = np.iinfo(np.int64).max
    layout = replay_mod._row_layout(out, att)
    row = np.asarray(jax.jit(replay_mod._pack_row)(
        jax.tree.map(jnp.asarray, out), jax.tree.map(jnp.asarray, att)))
    assert row.dtype == np.int32 and row.ndim == 1
    c = replay_mod._cut_row(row, layout)
    for f in DECISION_FIELDS:
        _same(c[f], getattr(out, f), f)
    assert set(c["att"]) == set(att)
    for k, v in att.items():
        _same(c["att"][k], v, k)


# ------------------------------------------------------------ the counter


def _transfers(session=None) -> float:
    if session is None:
        return TRACER.counter_totals().get(
            "decision_fetch_transfers_total", 0)
    return TRACER.snapshot(session=session)["counters"].get(
        "decision_fetch_transfers_total", 0)


def test_a_served_one_pod_pass_is_one_transfer():
    """One pod a pass in a served session under the default profile: the
    decision fetch pulls ONE device array, where it pulled nine or ten."""
    mgr = SessionManager(cfg=SimulatorConfiguration(port=0),
                         start_scheduler=False, idle_ttl=0, max_sessions=2)
    try:
        sess = mgr.create("one-transfer")
        for n in make_nodes(8, seed=41):
            sess.di.store.create("nodes", n)
        for pod in make_pods(3, seed=42):
            before = _transfers("one-transfer")
            sess.di.store.create("pods", pod)
            assert sess.di.engine.schedule_pending() == 1
            assert _transfers("one-transfer") - before == 1
    finally:
        mgr.shutdown()


@pytest.mark.parametrize("route", ["many_chunks", "leaves"])
def test_a_pass_over_leaves_counts_a_transfer_a_field(route):
    nodes, pods, cfg = baseline_config(4, scale=0.01, seed=3)
    cw = compile_workload(nodes, pods, cfg)
    chunk = 16 if route == "many_chunks" else 4096
    if route == "leaves":
        cw = dataclasses.replace(cw)
    chunks = -(-cw.n_pods // chunk)
    before = _transfers()
    rr = replay(cw, chunk=chunk, device_resident=True)
    leaves = len(rr._compact.att[0])
    assert leaves >= 3
    assert _transfers() - before == chunks * (len(DECISION_FIELDS) + leaves)
    # ... and the same workload as ONE chunk over the buffers: one
    if route == "many_chunks":
        before = _transfers()
        replay(cw, chunk=4096, device_resident=True)
        assert _transfers() - before == 1


def test_host_resident_packed_pass_cuts_the_same_row():
    """The host-resident rung of a one-chunk pass takes the packed
    executable too (its row holds the decision fields alone): the full
    fetch hands on the heavy four and the fields as it did."""
    nodes, pods, cfg = baseline_config(3, scale=0.02, seed=5)
    cw = compile_workload(nodes, pods, cfg)
    a = replay(cw, chunk=4096, device_resident=False)
    b = replay(dataclasses.replace(cw), chunk=4096, device_resident=False)
    for f in ("selected", "feasible_count", "prefilter_reject"):
        _same(getattr(a, f), getattr(b, f), f)
    for g in ("packed", "raw8", "raw16", "raw32"):
        _same(a._compact.host(g, 0), b._compact.host(g, 0), g)
    assert a._compact.att == b._compact.att == [None]
