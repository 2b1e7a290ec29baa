"""Speculative default wave vs the sequential scan baseline
(KSS_TPU_SPECULATIVE=0): engine-level golden byte-identity — annotation
bytes, bind order, result history, parked gangs — plus the PR 12
composition (mid-round fault -> uncommitted-suffix retry) and the
contention scan-fallback (docs/wave-pipeline.md speculative-wave
stage), and the way out of rounds that do not accept: a one-chunk pass
whose first round collapses starts again as the packed scan, and the
rounds are tried again once the queue's feasible share has halved."""

from __future__ import annotations

import json

import pytest

from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.models.workloads import (
    make_nodes, make_pods, make_slot_pinned_workload)
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

from test_wave_path_table import _route  # noqa: E402

DEFAULT_ENABLED = [
    "NodeResourcesFit", "NodeResourcesBalancedAllocation", "NodeAffinity",
    "TaintToleration", "PodTopologySpread",
]


def _run_wave(nodes, pods, enabled, monkeypatch, speculative: bool,
              chunk: int = 16, pgs=(), custom=None, env=(), **engine_kw):
    """One engine pass; returns (state, bind_order, parked) where state
    maps pod name -> (nodeName, ALL annotations — result history
    included)."""
    monkeypatch.setenv("KSS_TPU_SPECULATIVE", "1" if speculative else "0")
    for k, v in env:
        monkeypatch.setenv(k, v)
    store = ObjectStore()
    if pgs:
        from kube_scheduler_simulator_tpu.plugins.coscheduling import (
            ensure_podgroup_resource)

        ensure_podgroup_resource(store)
        for pg in pgs:
            store.create("podgroups", pg)
    for n in nodes:
        store.create("nodes", n)
    for p in pods:
        store.create("pods", p)
    engine = SchedulerEngine(store, plugin_config=PluginSetConfig(
        enabled=list(enabled), custom=dict(custom or {})), chunk=chunk,
        **engine_kw)

    # bind ORDER: every bind funnels through _commit_pod_batch on the
    # batched paths and _bind on the post-pass/gang-release paths
    order: list[tuple[str, str, str]] = []
    orig_batch = engine._commit_pod_batch
    orig_bind = engine._bind

    def batch_spy(items):
        order.extend((ns, name, node) for ns, name, node in items if node)
        return orig_batch(items)

    def bind_spy(ns, name, node):
        order.append((ns, name, node))
        return orig_bind(ns, name, node)

    engine._commit_pod_batch = batch_spy
    engine._bind = bind_spy
    engine.schedule_pending()
    state = {}
    for p in store.list("pods")[0]:
        meta = p.get("metadata") or {}
        state[meta.get("name", "")] = (
            (p.get("spec") or {}).get("nodeName"),
            dict(meta.get("annotations") or {}))
    parked = sorted(engine.gang_parked)
    engine.close()
    return state, order, parked


def _assert_identical(a, b):
    sa, oa, pa = a
    sb, ob, pb = b
    diff = sorted(k for k in sb if sb[k] != sa.get(k))
    assert sa == sb, f"state diverged at {diff[:4]}"
    assert oa == ob, "bind order diverged"
    assert pa == pb, "parked gang set diverged"


def test_default_wave_is_speculative_and_byte_identical(monkeypatch):
    """The flagship parity gate: the DEFAULT wave (speculative) against
    KSS_TPU_SPECULATIVE=0, on the broad default workload (label-coupled
    spread constraints active — the dense eval + contention controller
    path)."""
    nodes = make_nodes(12, seed=5, taint_fraction=0.2)
    pods = make_pods(40, seed=6, with_affinity=True, with_tolerations=True,
                     with_spread=True)
    TRACER.reset()
    spec = _run_wave(nodes, pods, DEFAULT_ENABLED, monkeypatch, True)
    assert TRACER.summary()["counters"].get("speculative_rounds_total", 0) > 0
    seq = _run_wave(nodes, pods, DEFAULT_ENABLED, monkeypatch, False)
    _assert_identical(spec, seq)


def test_tie_score_pods_bind_identically(monkeypatch):
    """Identical nodes x identical pods: every node ties on every score,
    so selection rides the argmax first-max tie-break — pinned to be
    bit-identical between the batched rounds and the scan."""
    nodes = []
    for i in range(6):
        nodes.append({"metadata": {"name": f"tie-{i}"},
                      "status": {"allocatable": {"cpu": "8", "memory": "16Gi",
                                                 "pods": "20"}}})
    pods = [{"metadata": {"name": f"twin-{i:02d}", "namespace": "default"},
             "spec": {"containers": [{
                 "name": "c",
                 "resources": {"requests": {"cpu": "500m",
                                            "memory": "1Gi"}}}]}}
            for i in range(18)]
    enabled = ["NodeResourcesFit", "NodeResourcesBalancedAllocation"]
    spec = _run_wave(nodes, pods, enabled, monkeypatch, True, chunk=8)
    seq = _run_wave(nodes, pods, enabled, monkeypatch, False, chunk=8)
    _assert_identical(spec, seq)
    assert all(s[0] for s in spec[0].values())  # everything bound


def test_gang_wave_with_parked_members_matches_sequential(monkeypatch):
    """Gangs through the speculative stream: an admitted group and a
    below-quorum group (one member infeasible) — admission, parking and
    annotation bytes identical to the scan baseline."""
    from kube_scheduler_simulator_tpu.models.workloads import (
        make_gang_workload)
    from kube_scheduler_simulator_tpu.plugins.coscheduling import Coscheduling

    nodes = make_nodes(8, seed=11)
    pgs, gpods = make_gang_workload(2, 3, seed=12)
    # park gang-0001: one member requests more cpu than any node has
    for p in gpods:
        if p["metadata"]["name"] == "gang-0001-member-000":
            p["spec"]["containers"][0]["resources"]["requests"]["cpu"] = "9999"
    pods = make_pods(10, seed=13) + gpods
    enabled = ["NodeResourcesFit", "Coscheduling"]

    def run(spec_on):
        return _run_wave(nodes, pods, enabled, monkeypatch, spec_on,
                         chunk=8, pgs=pgs,
                         custom={"Coscheduling": Coscheduling()})

    spec = run(True)
    seq = run(False)
    _assert_identical(spec, seq)
    assert spec[2], "below-quorum gang should have parked members"
    bound_gang0 = [n for n, (node, _a) in spec[0].items()
                   if n.startswith("gang-0000-") and node]
    assert len(bound_gang0) == 3, "admitted gang must bind whole"


def test_mid_round_fault_retries_suffix_and_stays_identical(monkeypatch):
    """PR 12 composition: a transient fault at the speculative.round
    seam mid-wave — committed round chunks stand, the uncommitted
    suffix retries recompiled against current store state, and the
    final state is byte-identical to the fault-free run."""
    from kube_scheduler_simulator_tpu.utils import faults

    nodes = make_nodes(10, seed=21)
    pods = make_pods(30, seed=22, with_affinity=True)
    enabled = ["NodeResourcesFit", "NodeResourcesBalancedAllocation",
               "NodeAffinity"]
    clean = _run_wave(nodes, pods, enabled, monkeypatch, True, chunk=8)
    TRACER.reset()
    plan = faults.FaultPlan([
        faults.FaultRule("speculative.round", nth=2, error="runtime"),
    ], seed=7)
    with faults.armed(plan):
        faulted = _run_wave(nodes, pods, enabled, monkeypatch, True, chunk=8)
    assert plan.stats()["rules"][0]["trips"] == 1, "fault never fired"
    counters = TRACER.summary()["counters"]
    assert counters.get("wave_retries_total", 0) >= 1
    _assert_identical(faulted, clean)


def test_contended_wave_falls_back_to_scan_and_matches(monkeypatch):
    """Broad feasibility collapses byte-exact acceptance: the contention
    controller must hand the wave to the sequential chunked scan (the
    fallback tap fires) and results stay byte-identical."""
    nodes = make_nodes(16, seed=31)
    pods = make_pods(60, seed=32)  # every pod fits everywhere
    enabled = ["NodeResourcesFit", "NodeResourcesBalancedAllocation"]
    TRACER.reset()
    spec = _run_wave(nodes, pods, enabled, monkeypatch, True, chunk=16)
    fallbacks = sum(TRACER.labeled_totals(
        "speculative_fallbacks_total", "session").values())
    assert fallbacks >= 1, "contended wave never engaged the scan fallback"
    seq = _run_wave(nodes, pods, enabled, monkeypatch, False, chunk=16)
    _assert_identical(spec, seq)


RELAXED = ["NodeResourcesFit", "NodeResourcesBalancedAllocation"]


def _labeled(name, label="session"):
    return sum(TRACER.labeled_totals(name, label).values())


@pytest.mark.parametrize("streamed", [True, False],
                         ids=["streamed", "post_pass"])
@pytest.mark.parametrize("count", [8, 9, 30, 32])
def test_collapsed_first_round_starts_again_as_the_packed_scan(
        count, streamed, monkeypatch):
    """A one-chunk pass of pods that fit everywhere: its first round
    keeps one pod, so the stream ends there and the SAME pass runs as the
    sequential scan's one packed call (one dispatch, one fetch), under
    either commit; byte-identical to KSS_TPU_SPECULATIVE=0."""
    nodes = make_nodes(16, seed=31)
    pods = make_pods(count, seed=32)
    kw = dict(chunk=64, pipeline_commit=streamed)
    TRACER.reset()
    spec = _run_wave(nodes, pods, RELAXED, monkeypatch, True, **kw)
    counters = TRACER.counter_totals()
    assert counters["speculative_rounds_total"] == 1
    assert _labeled("speculative_fallbacks_total") == 1
    assert _labeled("speculative_accepted_total") == 0
    # (16 nodes are inside the candidate cap, so the rounds start dense, at
    # the ladder's bottom rung: a first round of eight)
    assert _labeled("speculative_rolled_back_total") == 8
    assert (_route("leaves"), _route("packed")) == (1, 1)
    assert counters["decision_fetch_transfers_total"] == 1
    assert counters.get("commit_stream_waves_total", 0) == int(streamed)
    assert counters.get("wave_retries_total", 0) == 0
    seq = _run_wave(nodes, pods, RELAXED, monkeypatch, False, **kw)
    _assert_identical(spec, seq)
    assert all(s[0] for s in spec[0].values())  # everything bound


def test_a_pass_of_fewer_than_eight_has_no_round_to_gain_from(monkeypatch):
    """Seven pods that fit everywhere: a round of them could be no
    evidence and would cost what the scan of all seven does, so the pass
    is the packed scan's one call as a pass of one pod is (since PR 52;
    until then three low rounds and the stream's own scan over leaves);
    no round runs, none is declined, and the session keeps no record."""
    from kube_scheduler_simulator_tpu.control import CONTROLS

    nodes = make_nodes(16, seed=31)
    pods = make_pods(7, seed=32)
    TRACER.reset()
    spec = _run_wave(nodes, pods, RELAXED, monkeypatch, True, chunk=64)
    assert TRACER.counter_totals()["speculative_rounds_total"] == 0
    assert _labeled("speculative_fallbacks_total") == 0
    assert _labeled("speculative_declined_passes_total") == 0
    assert (_route("leaves"), _route("packed")) == (0, 1)
    assert CONTROLS.spec_collapsed(
        None, PluginSetConfig(enabled=list(RELAXED)).signature()) is None
    seq = _run_wave(nodes, pods, RELAXED, monkeypatch, False, chunk=64)
    _assert_identical(spec, seq)


@pytest.mark.parametrize("seam, nth", [("speculative.round", 1),
                                       ("replay.scan_dispatch", 1),
                                       ("replay.scan_dispatch", 2)],
                         ids=["round", "round_dispatch", "restart_dispatch"])
def test_fault_around_the_restart_retries_and_stays_identical(
        seam, nth, monkeypatch):
    """A transient fault before the collapsing round, in it, and at the
    restarted pass's one dispatch (the seam's second visit): nothing was
    committed, the whole pass retries, and the result is byte-identical
    to the fault-free run."""
    from kube_scheduler_simulator_tpu.control import CONTROLS
    from kube_scheduler_simulator_tpu.utils import faults

    nodes = make_nodes(16, seed=31)
    pods = make_pods(30, seed=32)
    clean = _run_wave(nodes, pods, RELAXED, monkeypatch, True, chunk=64)
    CONTROLS.reset()
    TRACER.reset()
    plan = faults.FaultPlan([faults.FaultRule(seam, nth=nth,
                                              error="runtime")], seed=7)
    with faults.armed(plan):
        faulted = _run_wave(nodes, pods, RELAXED, monkeypatch, True, chunk=64)
    assert plan.stats()["rules"][0]["trips"] == 1, "fault never fired"
    assert TRACER.counter_totals().get("wave_retries_total", 0) >= 1
    _assert_identical(faulted, clean)


def test_rounds_are_tried_again_once_the_feasible_share_has_halved(
        monkeypatch):
    """(3) of the mechanism.  A session whose rounds collapsed on a
    relaxed burst (every node feasible) declines its next batch pass; that
    pass is of slot-pinned pods (2 feasible nodes of 24), so its scan
    reads a median share under half the remembered one, the record is
    cleared (speculative_retries_total 1), and the pass after it runs the
    rounds, which accept every pod.  Byte-identical to the sequential
    scan throughout."""
    from kube_scheduler_simulator_tpu.control import CONTROLS

    nodes, pinned = make_slot_pinned_workload(24, 24, seed=41)
    relaxed = make_pods(12, seed=42)
    enabled = RELAXED + ["NodeAffinity"]
    bursts = [relaxed, pinned[:12], pinned[12:]]

    def run(spec_on):
        monkeypatch.setenv("KSS_TPU_SPECULATIVE", "1" if spec_on else "0")
        CONTROLS.reset()
        TRACER.reset()
        store = ObjectStore()
        for n in nodes:
            store.create("nodes", n)
        engine = SchedulerEngine(store, plugin_config=PluginSetConfig(
            enabled=list(enabled)), chunk=64)
        engine.session = "retry-test"
        seen = []
        for burst in bursts:
            for p in burst:
                store.create("pods", p)
            assert engine.schedule_pending() == len(burst)
            seen.append((
                TRACER.counter_totals().get("speculative_rounds_total", 0),
                _labeled("speculative_declined_passes_total"),
                _labeled("speculative_retries_total"),
                _labeled("speculative_accepted_total")))
        state = {p["metadata"]["name"]: (
            p["spec"].get("nodeName"), dict(p["metadata"]["annotations"]))
            for p in store.list("pods")[0]}
        engine.close()
        return state, seen

    spec, seen = run(True)
    # rounds, declined, retries, accepted after each burst
    assert seen[0] == (1, 0, 0, 0)          # collapsed, restarted
    assert seen[1] == (1, 1, 1, 0)          # declined; share 2/24 <= 1/2
    assert seen[2] == (3, 1, 1, 12), seen   # rounds of 8 and 4: all kept
    seq, _ = run(False)
    assert spec == seq
    assert all(node for node, _a in spec.values())


def test_a_probe_waits_for_a_bucket_the_rounds_have_run_on(monkeypatch):
    """The rounds are tried again on executables that exist.  A session
    whose rounds collapsed on a burst of 12 (the bucket of 16) and whose
    declined burst of 20 pinned pods (the bucket of 32) asked for them
    again serves its next burst on the bucket of 32 declined too, since no
    round of the session ran there and a probe would compile that bucket's
    executables first; the burst of 12 after it is the probe, and its
    rounds accept every pod.  One ask, not one a declined pass."""
    from kube_scheduler_simulator_tpu.control import CONTROLS

    nodes, pinned = make_slot_pinned_workload(50, 48, seed=41)
    enabled = RELAXED + ["NodeAffinity"]
    bursts = [make_pods(12, seed=42), pinned[:20], pinned[20:38],
              pinned[38:50]]

    def run(spec_on):
        monkeypatch.setenv("KSS_TPU_SPECULATIVE", "1" if spec_on else "0")
        CONTROLS.reset()
        TRACER.reset()
        store = ObjectStore()
        for n in nodes:
            store.create("nodes", n)
        engine = SchedulerEngine(store, plugin_config=PluginSetConfig(
            enabled=list(enabled)), chunk=64)
        engine.session = "probe-test"
        seen = []
        for burst in bursts:
            for p in burst:
                store.create("pods", p)
            assert engine.schedule_pending() == len(burst)
            seen.append((
                TRACER.counter_totals().get("speculative_rounds_total", 0),
                _labeled("speculative_declined_passes_total"),
                _labeled("speculative_retries_total"),
                _labeled("speculative_accepted_total")))
        state = {p["metadata"]["name"]: (
            p["spec"].get("nodeName"), dict(p["metadata"]["annotations"]))
            for p in store.list("pods")[0]}
        engine.close()
        return state, seen

    spec, seen = run(True)
    # rounds, declined, retries, accepted after each burst
    assert seen[0] == (1, 0, 0, 0)          # bucket 16: collapsed
    assert seen[1] == (1, 1, 1, 0)          # bucket 32: declined, asks
    assert seen[2] == (1, 2, 1, 0)          # bucket 32: no round ran there
    assert seen[3] == (3, 2, 1, 12), seen   # bucket 16: the probe, all kept
    seq, _ = run(False)
    assert spec == seq
    assert all(node for node, _a in spec.values())


def test_a_probe_that_collapses_records_no_more_than_what_asked_for_it():
    """The record of a queue whose passes' medians move between two levels
    (half its pods pinned to half the nodes): the probe's own median may
    be the high one again, and the record is the share that asked all the
    same, so the session asks once and not at every turn."""
    from kube_scheduler_simulator_tpu.control import CONTROLS

    CONTROLS.reset()
    s, prof = "floor-test", ("profile",)
    assert not CONTROLS.spec_declines(s, prof, 16)
    CONTROLS.note_spec_rounds(s, prof, 16, probe=True)
    CONTROLS.note_spec_collapsed(s, prof, 0.9)
    assert CONTROLS.spec_declines(s, prof, 16)
    assert CONTROLS.spec_declines(s, prof, 32)
    assert not CONTROLS.spec_declines(s, ("another",), 16)
    assert not CONTROLS.spec_recheck(s, 0.5)        # 2 * 0.5 > 0.9
    assert CONTROLS.spec_recheck(s, 0.45)
    assert not CONTROLS.spec_recheck(s, 0.4)        # asked already
    # the record stands for a bucket no round of the session ran on ...
    assert CONTROLS.spec_declines(s, prof, 32)
    assert CONTROLS.spec_collapsed(s, prof) == 0.9
    # ... a pass of more than one chunk is no probe and clears nothing ...
    CONTROLS.note_spec_rounds(s, prof, 512, probe=False)
    assert CONTROLS.spec_declines(s, prof, 32)
    # ... and the bucket of 16 is the probe's
    assert not CONTROLS.spec_declines(s, prof, 16)
    CONTROLS.note_spec_rounds(s, prof, 16, probe=True)
    assert CONTROLS.spec_collapsed(s, prof) is None
    CONTROLS.note_spec_collapsed(s, prof, 0.9)
    assert CONTROLS.spec_collapsed(s, prof) == 0.45
    assert not CONTROLS.spec_recheck(s, 0.45)
    assert CONTROLS.spec_recheck(s, 0.22)
    # rounds that were no probe leave a later collapse its own share
    CONTROLS.reset()
    CONTROLS.note_spec_rounds(s, prof, 16, probe=True)
    CONTROLS.note_spec_collapsed(s, prof, 0.3)
    assert CONTROLS.spec_collapsed(s, prof) == 0.3
    CONTROLS.reset()


def test_sparse_candidate_eval_through_engine(monkeypatch):
    """KSS_TPU_SPECULATIVE_CANDIDATES pins a small candidate cap so the
    sparse score/select tail actually runs (slot-pinned pods: 2 feasible
    nodes each) — engine results byte-identical to the scan baseline."""
    nodes, pods = make_slot_pinned_workload(24, 12, seed=41)
    enabled = ["NodeResourcesFit", "NodeResourcesBalancedAllocation",
               "NodeAffinity"]
    env = (("KSS_TPU_SPECULATIVE_CANDIDATES", "4"),)
    TRACER.reset()
    spec = _run_wave(nodes, pods, enabled, monkeypatch, True, chunk=8,
                     env=env)
    accepted = sum(TRACER.labeled_totals(
        "speculative_accepted_total", "session").values())
    assert accepted == 24, "slot workload should accept every pod"
    seq = _run_wave(nodes, pods, enabled, monkeypatch, False, chunk=8,
                    env=env)
    _assert_identical(spec, seq)
    assert all(s[0] for s in spec[0].values())


def test_accept_rate_surfaces_per_session(monkeypatch):
    """The speculative_commit_rates surface /api/v1/sessions and
    `bench --serve` report: accepted/rolledBack per session label."""
    from kube_scheduler_simulator_tpu.server.sessions import (
        speculative_commit_rates)

    nodes, pods = make_slot_pinned_workload(12, 8, seed=51)
    monkeypatch.setenv("KSS_TPU_SPECULATIVE", "1")
    store = ObjectStore()
    for n in nodes:
        store.create("nodes", n)
    for p in pods:
        store.create("pods", p)
    engine = SchedulerEngine(store, plugin_config=PluginSetConfig(
        enabled=["NodeResourcesFit", "NodeAffinity"]), chunk=8)
    engine.session = "rate-test"
    TRACER.reset()
    engine.schedule_pending()
    rates = speculative_commit_rates(TRACER)
    assert "rate-test" in rates, rates
    ent = rates["rate-test"]
    assert ent["accepted"] == 12
    assert ent["acceptRate"] == pytest.approx(
        ent["accepted"] / (ent["accepted"] + ent["rolledBack"]))
    engine.close()


def test_result_history_across_waves_identical(monkeypatch):
    """Two waves over the same pods (second wave re-schedules after a
    delete/recreate) — the RESULT_HISTORY annotation accumulates
    byte-identically on both paths."""
    from kube_scheduler_simulator_tpu.store import annotations as ann

    nodes = make_nodes(6, seed=61)
    base_pods = make_pods(10, seed=62)

    def run(spec_on):
        monkeypatch.setenv("KSS_TPU_SPECULATIVE", "1" if spec_on else "0")
        store = ObjectStore()
        for n in nodes:
            store.create("nodes", n)
        engine = SchedulerEngine(store, plugin_config=PluginSetConfig(
            enabled=["NodeResourcesFit",
                     "NodeResourcesBalancedAllocation"]), chunk=4)
        for p in base_pods:
            store.create("pods", p)
        engine.schedule_pending()
        # unbind and re-run: the second wave's records append to history
        for p in store.list("pods", copy_objects=False)[0][:]:
            name = p["metadata"]["name"]
            store.delete("pods", name, "default")
        for p in base_pods:
            store.create("pods", p)
        engine.schedule_pending()
        hist = {}
        for p in store.list("pods")[0]:
            anns = (p["metadata"].get("annotations") or {})
            hist[p["metadata"]["name"]] = anns.get(ann.RESULT_HISTORY)
        engine.close()
        return hist

    spec, seq = run(True), run(False)
    assert spec == seq
    assert all(h and len(json.loads(h)) >= 1 for h in spec.values())
