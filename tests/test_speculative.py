"""Speculative dp-batch scheduling: bit-parity with the sequential scan
and the CPU oracle (parallel/speculative.py exactness argument)."""

from __future__ import annotations

import numpy as np
import pytest

from kube_scheduler_simulator_tpu.framework.replay import replay
from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods
from kube_scheduler_simulator_tpu.parallel.mesh import make_mesh
from kube_scheduler_simulator_tpu.parallel.speculative import (
    SAFE_SPECULATIVE, replay_speculative, speculation_ok)
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.state.compile import compile_workload
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result

SAFE_CFG = ["NodeResourcesFit", "NodeResourcesBalancedAllocation",
            "NodeAffinity", "TaintToleration"]


def _workload(n_nodes=24, n_pods=60, seed=9):
    # tight capacity so pods contend for the same nodes — the acceptance
    # walk must actually cut batches, not rubber-stamp them
    nodes = make_nodes(n_nodes, seed=seed, taint_fraction=0.2)
    pods = make_pods(n_pods, seed=seed + 1, with_affinity=True,
                     with_tolerations=True)
    return nodes, pods


def test_speculation_ok_classifier():
    assert speculation_ok(PluginSetConfig(enabled=SAFE_CFG))
    # label-coupled plugins qualify WITH manifests (interaction rule),
    # node-local NodePorts under the dirty-node rule alone
    assert speculation_ok(PluginSetConfig(
        enabled=SAFE_CFG + ["PodTopologySpread"]))
    assert speculation_ok(PluginSetConfig(
        enabled=SAFE_CFG + ["InterPodAffinity"]))
    assert speculation_ok(PluginSetConfig(enabled=["NodePorts"]))
    # the volume family's cluster-wide PV/PVC bind state stays excluded
    assert not speculation_ok(PluginSetConfig(
        enabled=SAFE_CFG + ["VolumeBinding"]))
    assert not speculation_ok(PluginSetConfig(
        enabled=SAFE_CFG + ["VolumeRestrictions"]))


@pytest.mark.parametrize("dp,batch", [(1, 4), (2, 8), (4, 16)])
def test_speculative_matches_scan(dp, batch):
    nodes, pods = _workload()
    cfg = PluginSetConfig(enabled=SAFE_CFG)
    cw = compile_workload(nodes, pods, cfg)
    base = replay(cw, chunk=16)

    cw2 = compile_workload(nodes, pods, cfg)
    mesh = make_mesh(dp * 2, dp=dp) if dp > 1 else None
    rr, stats = replay_speculative(cw2, mesh, batch=batch)

    np.testing.assert_array_equal(rr.selected, base.selected)
    np.testing.assert_array_equal(rr.feasible_count, base.feasible_count)
    assert stats["rounds"] >= (len(pods) + batch - 1) // batch
    # full annotation byte-parity, not just selections
    for i in range(len(pods)):
        a = decode_pod_result(rr, i)
        b = decode_pod_result(base, i)
        assert a == b, f"pod {i}"


def test_speculative_under_contention_still_exact():
    """2 nodes, many pods: almost every batch is cut at the first
    interference; parity must survive the worst acceptance pattern."""
    nodes = make_nodes(2, seed=3)
    pods = make_pods(30, seed=4)
    cfg = PluginSetConfig(enabled=["NodeResourcesFit",
                                   "NodeResourcesBalancedAllocation"])
    base = replay(compile_workload(nodes, pods, cfg), chunk=8)
    rr, stats = replay_speculative(compile_workload(nodes, pods, cfg),
                                   None, batch=8)
    np.testing.assert_array_equal(rr.selected, base.selected)
    assert stats["mean_accept"] < 8  # contention actually cut batches


def test_speculative_oracle_parity():
    from kube_scheduler_simulator_tpu.reference_impl.sequential import (
        SequentialScheduler)

    nodes, pods = _workload(n_nodes=12, n_pods=24, seed=21)
    cfg = PluginSetConfig(enabled=SAFE_CFG)
    oracle = SequentialScheduler(nodes, pods, cfg).schedule_all()
    rr, _ = replay_speculative(compile_workload(nodes, pods, cfg),
                               None, batch=6)
    for i, (sa, _sel) in enumerate(oracle):
        da = decode_pod_result(rr, i)
        for key, v in sa.items():
            assert da[key] == v, f"pod {i} {key}"


def test_engine_uses_speculative_path_with_dp_mesh(monkeypatch):
    from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
    from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
    from kube_scheduler_simulator_tpu.utils.tracing import TRACER

    nodes, pods = _workload(n_nodes=16, n_pods=24, seed=31)
    mesh = make_mesh(4, dp=2)

    def run(mesh_arg):
        store = ObjectStore()
        for n in nodes:
            store.create("nodes", n)
        for p in pods:
            store.create("pods", p)
        eng = SchedulerEngine(store, plugin_config=PluginSetConfig(
            enabled=SAFE_CFG), mesh=mesh_arg, chunk=16)
        eng.schedule_pending()
        out, _ = store.list("pods")
        return {(p["metadata"]["name"]): (
            p["spec"].get("nodeName"),
            (p["metadata"].get("annotations") or {}).get(
                "kube-scheduler-simulator.sigs.k8s.io/finalscore-result"))
            for p in out}

    TRACER.reset()
    spec_out = run(mesh)
    spans = TRACER.summary()["spans"]
    assert "speculative_round" in spans, sorted(spans)
    # the sequential-scan parity baseline (KSS_TPU_SPECULATIVE=0)
    monkeypatch.setenv("KSS_TPU_SPECULATIVE", "0")
    base_out = run(None)
    assert spec_out == base_out


def test_point_enabled_unsafe_plugin_blocks_speculation():
    """point_enabled can add a plugin cfg.enabled never lists; the gate
    must look at the ACTIVE set (review finding: a point-enabled coupled
    plugin silently corrupted speculative state).  VolumeBinding is the
    representative excluded plugin now that spread/interpod qualify."""
    cfg = PluginSetConfig(enabled=["NodeResourcesFit"],
                          point_enabled={"filter": ["VolumeBinding"]})
    assert not speculation_ok(cfg)
    # and a point-enabled LABEL_COUPLED plugin without manifests
    cfg2 = PluginSetConfig(enabled=["NodeResourcesFit"],
                           point_enabled={"score": ["PodTopologySpread"]})
    assert not speculation_ok(cfg2, have_manifests=False)
    assert speculation_ok(cfg2, have_manifests=True)


def test_init_carry_survives_speculative_replay():
    """commit() donates its carry; the workload's init_carry must be
    copied first so the SAME cw can replay again (review finding)."""
    nodes, pods = _workload(n_nodes=8, n_pods=10, seed=41)
    cfg = PluginSetConfig(enabled=SAFE_CFG)
    cw = compile_workload(nodes, pods, cfg)
    rr1, _ = replay_speculative(cw, None, batch=4)
    rr2, _ = replay_speculative(cw, None, batch=4)  # reuses cw.init_carry
    np.testing.assert_array_equal(rr1.selected, rr2.selected)
    base = replay(cw, chunk=4)  # the scan also reuses it
    np.testing.assert_array_equal(rr1.selected, base.selected)


COUPLED_CFG = SAFE_CFG + ["PodTopologySpread"]


def _coupled_workload(n_nodes=20, n_pods=48, seed=13, interpod=False):
    nodes = make_nodes(n_nodes, seed=seed, taint_fraction=0.2)
    pods = make_pods(n_pods, seed=seed + 1, with_affinity=True,
                     with_tolerations=True, with_spread=True,
                     with_interpod=interpod)
    return nodes, pods


@pytest.mark.parametrize("interpod", [False, True])
def test_speculative_label_coupled_matches_scan(interpod):
    """Configs 4/5 plugin sets (spread / interpod) under the interaction
    rule: byte-parity with the scan down to full annotations."""
    nodes, pods = _coupled_workload(interpod=interpod)
    cfg = PluginSetConfig(enabled=COUPLED_CFG
                          + (["InterPodAffinity"] if interpod else []))
    assert speculation_ok(cfg)
    base = replay(compile_workload(nodes, pods, cfg), chunk=16)
    rr, stats = replay_speculative(compile_workload(nodes, pods, cfg),
                                   None, batch=8, pods=pods)
    np.testing.assert_array_equal(rr.selected, base.selected)
    for i in range(len(pods)):
        assert decode_pod_result(rr, i) == decode_pod_result(base, i), i
    # interactions must actually cut batches on this workload (app-group
    # selectors overlap), or the rule is vacuous
    assert stats["mean_accept"] < stats["batch"]


def test_speculative_label_coupled_oracle_parity():
    from kube_scheduler_simulator_tpu.reference_impl.sequential import (
        SequentialScheduler)

    nodes, pods = _coupled_workload(n_nodes=10, n_pods=20, seed=29,
                                    interpod=True)
    cfg = PluginSetConfig(enabled=COUPLED_CFG + ["InterPodAffinity"])
    oracle = SequentialScheduler(nodes, pods, cfg).schedule_all()
    rr, _ = replay_speculative(compile_workload(nodes, pods, cfg),
                               None, batch=6, pods=pods)
    for i, (sa, _sel) in enumerate(oracle):
        da = decode_pod_result(rr, i)
        for key, v in sa.items():
            assert da[key] == v, f"pod {i} {key}"


def test_speculative_nodeports_exact():
    """NodePorts rides the dirty-node rule: port conflicts are node-local
    and monotone; parity with the scan under hostPort contention."""
    nodes = make_nodes(6, seed=7)
    pods = []
    for i in range(18):
        p = {"metadata": {"name": f"hp-{i}", "namespace": "default"},
             "spec": {"containers": [{
                 "name": "c",
                 "resources": {"requests": {"cpu": "100m"}},
                 "ports": [{"hostPort": 8000 + (i % 3),
                            "protocol": "TCP"}]}]}}
        pods.append(p)
    cfg = PluginSetConfig(enabled=["NodeResourcesFit", "NodePorts"])
    assert speculation_ok(cfg)
    base = replay(compile_workload(nodes, pods, cfg), chunk=8)
    rr, _ = replay_speculative(compile_workload(nodes, pods, cfg),
                               None, batch=6)
    np.testing.assert_array_equal(rr.selected, base.selected)
    for i in range(len(pods)):
        assert decode_pod_result(rr, i) == decode_pod_result(base, i), i


def test_label_coupled_requires_manifests():
    nodes, pods = _coupled_workload(n_nodes=6, n_pods=6)
    cfg = PluginSetConfig(enabled=COUPLED_CFG)
    assert not speculation_ok(cfg, have_manifests=False)
    with pytest.raises(ValueError):
        replay_speculative(compile_workload(nodes, pods, cfg), None, batch=4)


def test_namespace_selector_interaction_detected():
    """Review counterexample: a cross-namespace required anti-affinity via
    namespaceSelector must register as an interaction (the hand-rolled
    term extraction missed it; the oracle now reuses
    plugins/interpod.effective_terms with the namespace manifests)."""
    def node(name, zone, cpu):
        return {"metadata": {"name": name, "labels":
                             {"topology.kubernetes.io/zone": zone,
                              "kubernetes.io/hostname": name}},
                "status": {"allocatable": {"cpu": cpu, "memory": "8Gi",
                                           "pods": "10"}}}

    nodes = [node("n0", "A", "300m"), node("n1", "A", "4"),
             node("n2", "B", "4")]
    namespaces = [{"metadata": {"name": "a", "labels": {"team": "x"}}},
                  {"metadata": {"name": "b", "labels": {"team": "y"}}}]
    p0 = {"metadata": {"name": "p0", "namespace": "a",
                       "labels": {"app": "x"}},
          "spec": {"containers": [{"name": "c", "resources":
                                   {"requests": {"cpu": "200m"}}}]}}
    p1 = {"metadata": {"name": "p1", "namespace": "b",
                       "labels": {"app": "y"}},
          "spec": {"containers": [{"name": "c", "resources":
                                   {"requests": {"cpu": "1"}}}],
                   "affinity": {"podAntiAffinity": {
                       "requiredDuringSchedulingIgnoredDuringExecution": [{
                           "labelSelector": {"matchLabels": {"app": "x"}},
                           "namespaceSelector": {},
                           "topologyKey": "topology.kubernetes.io/zone"}]}}}}
    pods = [p0, p1]
    cfg = PluginSetConfig(enabled=["NodeResourcesFit", "InterPodAffinity"])
    base = replay(compile_workload(nodes, pods, cfg, namespaces=namespaces),
                  chunk=2)
    rr, stats = replay_speculative(
        compile_workload(nodes, pods, cfg, namespaces=namespaces),
        None, batch=2, pods=pods, namespaces=namespaces)
    np.testing.assert_array_equal(rr.selected, base.selected)
    for i in range(2):
        assert decode_pod_result(rr, i) == decode_pod_result(base, i), i
    # the interaction must have cut the first batch to 1
    assert stats["rounds"] == 2 and stats["mean_accept"] == 1.0


def test_sparse_tail_mixed_with_dense_fallback_rounds(monkeypatch):
    """KSS_TPU_SPECULATIVE_CANDIDATES below the cluster size engages the
    sparse score/select tail; pods whose feasible set exceeds the cap
    must push their round onto the dense eval — BOTH kinds of round in
    one stream, byte-identical to the scan."""
    from kube_scheduler_simulator_tpu.models.workloads import (
        make_slot_pinned_workload)

    monkeypatch.setenv("KSS_TPU_SPECULATIVE_CANDIDATES", "4")
    nodes, pinned = make_slot_pinned_workload(20, 16, seed=71)
    broad = make_pods(10, seed=72)  # feasible on ~all 16 nodes ( > 4 )
    pods = pinned[:10] + broad + pinned[10:]
    cfg = PluginSetConfig(enabled=["NodeResourcesFit",
                                   "NodeResourcesBalancedAllocation",
                                   "NodeAffinity"])
    base = replay(compile_workload(nodes, pods, cfg), chunk=8)
    rr, stats = replay_speculative(compile_workload(nodes, pods, cfg),
                                   None, batch=8)
    np.testing.assert_array_equal(rr.selected, base.selected)
    np.testing.assert_array_equal(rr.feasible_count, base.feasible_count)
    for i in range(len(pods)):
        assert decode_pod_result(rr, i) == decode_pod_result(base, i), i


@pytest.mark.parametrize("queue,probes_built,wide_rounds,ends_narrow", [
    ("broad", False, "all", False),
    ("pinned", True, "none", True),
    ("pinned+broad+pinned", True, "some", True)])
def test_the_sparse_probe_follows_the_last_round(
        monkeypatch, queue, probes_built, wide_rounds, ends_narrow):
    """A round runs the sparse probe only where the session's last round
    kept every feasible set inside the candidate cap: a queue of broad
    pods never builds the probe's executable, a queue of slot-pinned pods
    runs its first round dense and the rest sparse, a mixed one follows
    the queue both ways; every pod byte-identical to the scan, and the
    next stream of the session starts where the last round left off."""
    from kube_scheduler_simulator_tpu.control import CONTROLS
    from kube_scheduler_simulator_tpu.models.workloads import (
        make_slot_pinned_workload)
    from kube_scheduler_simulator_tpu.parallel import speculative
    from kube_scheduler_simulator_tpu.utils.tracing import TRACER

    monkeypatch.setenv("KSS_TPU_SPECULATIVE_CANDIDATES", "4")
    CONTROLS.reset()
    TRACER.reset()
    built = []
    build = speculative._sparse_round_fn
    monkeypatch.setattr(
        speculative, "_sparse_round_fn",
        lambda *a, **kw: built.append(a[2]) or build(*a, **kw))
    nodes, pinned = make_slot_pinned_workload(20, 16, seed=71)
    broad = make_pods(12, seed=72)  # feasible on ~all 16 nodes ( > 4 )
    pods = {"broad": broad, "pinned": pinned,
            "pinned+broad+pinned": pinned[:10] + broad + pinned[10:]}[queue]
    cfg = PluginSetConfig(enabled=["NodeResourcesFit",
                                   "NodeResourcesBalancedAllocation",
                                   "NodeAffinity"])
    base = replay(compile_workload(nodes, pods, cfg), chunk=8)
    rr, stats = replay_speculative(compile_workload(nodes, pods, cfg),
                                   None, batch=4)
    for i in range(len(pods)):
        assert decode_pod_result(rr, i) == decode_pod_result(base, i), i
    counters = TRACER.summary()["counters"]
    wide = counters.get("speculative_wide_rounds_total", 0)
    assert bool(built) is probes_built, built
    assert wide == {"all": stats["rounds"], "none": 0}.get(wide_rounds, wide)
    assert 0 < wide < stats["rounds"] or wide_rounds != "some", (wide, stats)
    assert CONTROLS.spec_narrow(None) is ends_narrow
    # the session's next stream starts as the last round ended
    del built[:]
    replay_speculative(compile_workload(nodes, pods[:4], cfg), None, batch=4)
    assert bool(built) is (ends_narrow and queue != "broad"), built
    CONTROLS.reset()


def test_wide_i64_tier_keeps_width_through_the_stream(monkeypatch):
    """Compile-proven i64 scores skip straight to the widest tier: the
    stream's eval must receive the tier STRING (review finding: a
    bool(wide) coercion disabled overflow detection and stacked the
    i64 tier's raw32 as int32) and the chunk-grid buffers must hold
    int64 — byte parity with the equally-forced scan, through both
    accumulator rounds (mixed acceptance) and direct-ingest rounds."""
    from kube_scheduler_simulator_tpu.models.workloads import (
        make_slot_pinned_workload)

    monkeypatch.setenv("KSS_TPU_SPECULATIVE_CANDIDATES", "4")
    nodes, pinned = make_slot_pinned_workload(20, 16, seed=81)
    pods = pinned[:10] + make_pods(8, seed=82) + pinned[10:]
    cfg = PluginSetConfig(enabled=["NodeResourcesFit",
                                   "NodeResourcesBalancedAllocation"])

    def force_i64(cw):
        cw.host["score_dtypes"] = tuple(
            "i64" for _ in cw.config.scorers())
        return cw

    base = replay(force_i64(compile_workload(nodes, pods, cfg)), chunk=8)
    rr, _ = replay_speculative(force_i64(compile_workload(nodes, pods, cfg)),
                               None, batch=8)
    assert rr._compact.raw32, "i64 tier must pool scorers into raw32"
    import jax.numpy as jnp
    for a in rr._compact.raw32:
        assert jnp.asarray(a).dtype == jnp.int64, a.dtype
    np.testing.assert_array_equal(rr.selected, base.selected)
    for i in range(len(pods)):
        assert decode_pod_result(rr, i) == decode_pod_result(base, i), i


def test_adaptive_batch_ladder_stays_exact():
    """batch=None engages the adaptive ladder (grow on full accept,
    shrink on early cuts); results stay bit-identical to the scan."""
    nodes, pods = _coupled_workload(n_nodes=24, n_pods=80, seed=51)
    cfg = PluginSetConfig(enabled=COUPLED_CFG)
    base = replay(compile_workload(nodes, pods, cfg), chunk=16)
    rr, stats = replay_speculative(compile_workload(nodes, pods, cfg),
                                   None, pods=pods)
    assert stats["adaptive"]
    np.testing.assert_array_equal(rr.selected, base.selected)
    for i in range(len(pods)):
        assert decode_pod_result(rr, i) == decode_pod_result(base, i), i


def test_adaptive_ladder_climbs_on_sparse_feasibility():
    """Disjoint feasible sets (per-node affinity pins) fully accept every
    round, so the ladder must actually climb its rungs (review finding:
    the climb condition was computed after `lo` moved and never fired)."""
    nodes = make_nodes(80, seed=61)
    pods = []
    for i in range(80):
        pods.append({
            "metadata": {"name": f"pin-{i:03d}", "namespace": "default"},
            "spec": {
                "containers": [{"name": "c", "resources":
                                {"requests": {"cpu": "100m"}}}],
                "affinity": {"nodeAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": {
                        "nodeSelectorTerms": [{"matchExpressions": [{
                            "key": "kubernetes.io/hostname",
                            "operator": "In",
                            "values": [f"node-{i:05d}"]}]}]}}},
            }})
    cfg = PluginSetConfig(enabled=["NodeResourcesFit", "NodeAffinity"])
    base = replay(compile_workload(nodes, pods, cfg), chunk=16)
    rr, stats = replay_speculative(compile_workload(nodes, pods, cfg), None)
    np.testing.assert_array_equal(rr.selected, base.selected)
    # the x4 ladder must actually climb off its bottom rung (8 -> 32)
    assert max(stats["round_batches"]) > stats["round_batches"][0], stats
    assert stats["round_batches"][:2] == [8, 32], stats["round_batches"]
    assert stats["accepted_first_try"] == stats["rounds"]
    assert stats["fallback_at"] is None and stats["accept_rate"] == 1.0
