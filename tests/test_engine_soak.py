"""Randomized end-to-end engine soak: random nodes/pods, config
churn (weights, point overrides, profiles), repeated waves — asserting the
invariants that hold regardless of workload:

  * schedule_pending never raises;
  * every bound pod's node exists and its filter-result shows no failure
    message for the chosen node;
  * every annotation blob parses as JSON with the exact key set;
  * unschedulable pods carry the PodScheduled=False condition;
  * node capacity is never exceeded by the bound set.
"""

import json

import numpy as np
import pytest

from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods
from kube_scheduler_simulator_tpu.scheduler.service import SchedulerService
from kube_scheduler_simulator_tpu.store import annotations as ann
from kube_scheduler_simulator_tpu.utils.quantity import parse_quantity

ALL_KEYS = {
    ann.PRE_FILTER_STATUS_RESULT, ann.PRE_FILTER_RESULT, ann.FILTER_RESULT,
    ann.POST_FILTER_RESULT, ann.PRE_SCORE_RESULT, ann.SCORE_RESULT,
    ann.FINAL_SCORE_RESULT, ann.RESERVE_RESULT, ann.PERMIT_STATUS_RESULT,
    ann.PERMIT_TIMEOUT_RESULT, ann.PRE_BIND_RESULT, ann.BIND_RESULT,
}


def check_invariants(store: ObjectStore):
    nodes = {n["metadata"]["name"]: n for n in store.list("nodes")[0]}
    used = {n: [0.0, 0.0, 0] for n in nodes}  # cpu, mem, pods
    for p in store.list("pods")[0]:
        meta, spec = p["metadata"], p.get("spec") or {}
        anns = meta.get("annotations") or {}
        nn = spec.get("nodeName")
        scheduled_keys = ALL_KEYS & set(anns)
        for k in scheduled_keys:
            v = anns[k]
            parsed = json.loads(v)
            assert isinstance(parsed, dict), k
        if nn:
            assert nn in nodes, f"bound to unknown node {nn}"
            fr = json.loads(anns.get(ann.FILTER_RESULT, "{}"))
            for plugin, msg in (fr.get(nn) or {}).items():
                assert msg == "passed", (
                    f"{meta['name']} bound to {nn} but {plugin} said {msg!r}")
            for c in spec.get("containers") or []:
                req = (c.get("resources") or {}).get("requests") or {}
                used[nn][0] += parse_quantity(req.get("cpu", "0"))
                used[nn][1] += parse_quantity(req.get("memory", "0"))
            used[nn][2] += 1
        else:
            conds = (p.get("status") or {}).get("conditions") or []
            if anns:  # a pod the scheduler actually looked at
                assert any(c.get("type") == "PodScheduled"
                           and c.get("status") == "False" for c in conds), (
                    f"{meta['name']} unbound without Unschedulable condition")
    for n, (cpu, mem, cnt) in used.items():
        alloc = (nodes[n].get("status") or {}).get("allocatable") or {}
        assert cpu <= parse_quantity(alloc.get("cpu", "0")) + 1e-9, n
        assert mem <= parse_quantity(alloc.get("memory", "0")) + 1e-9, n
        assert cnt <= int(alloc.get("pods", "110")), n


@pytest.mark.parametrize("seed", [31, 67])
def test_engine_soak(seed):
    rng = np.random.default_rng(seed)
    store = ObjectStore()
    for n in make_nodes(int(rng.integers(6, 14)), seed=seed,
                        taint_fraction=0.25):
        store.create("nodes", n)
    engine = SchedulerEngine(store)
    svc = SchedulerService(engine)

    for round_ in range(4):
        pods = make_pods(int(rng.integers(4, 14)), seed=seed * 10 + round_,
                         with_affinity=True, with_tolerations=True,
                         with_spread=True,
                         with_interpod=bool(round_ % 2))
        for p in pods:
            p["metadata"]["name"] = f"r{round_}-{p['metadata']['name']}"
            p["spec"]["priority"] = int(rng.integers(0, 3)) * 50
            store.create("pods", p)

        if round_ == 1:
            cfg = svc.get_config()
            cfg["profiles"][0]["plugins"] = {
                "score": {"disabled": [{"name": "TaintToleration"}]},
                "filter": {"disabled": [{"name": "PodTopologySpread"}]},
            }
            svc.restart_scheduler(cfg)
        elif round_ == 2:
            cfg = svc.get_config()
            cfg["profiles"][0]["plugins"] = {}
            cfg["profiles"][0]["pluginConfig"] = [
                {"name": "NodeResourcesFit",
                 "args": {"scoringStrategy": {"type": "MostAllocated"}}}]
            svc.restart_scheduler(cfg)

        engine.schedule_pending()
        check_invariants(store)

        # random deletions free capacity for the next round
        bound = [p for p in store.list("pods")[0]
                 if (p.get("spec") or {}).get("nodeName")]
        rng.shuffle(bound)
        for p in bound[: len(bound) // 3]:
            store.delete("pods", p["metadata"]["name"],
                         p["metadata"].get("namespace"))
    # final wave picks up any pods that became schedulable after deletes
    engine.schedule_pending()
    check_invariants(store)


@pytest.mark.parametrize("seed", [3, 11])
def test_engine_soak_dp_mesh(seed):
    """The soak's config churn / priority mix / deletion rounds, run on a
    dp>1 mesh: the scan shards the node axis, replicates over dp, and
    must land in the same invariant-clean state as the unsharded engine
    on an identical store."""
    from kube_scheduler_simulator_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(seed)
    nodes = make_nodes(int(rng.integers(6, 14)), seed=seed,
                       taint_fraction=0.25)
    pod_rounds = [
        make_pods(int(rng.integers(4, 14)), seed=seed * 10 + r,
                  with_affinity=True, with_tolerations=True, with_spread=True)
        for r in range(3)
    ]

    def run(mesh):
        store = ObjectStore()
        for n in nodes:
            store.create("nodes", n)
        engine = SchedulerEngine(store, mesh=mesh, chunk=16)
        for r, pods in enumerate(pod_rounds):
            for p in pods:
                q = {"metadata": dict(p["metadata"]), "spec": dict(p["spec"])}
                q["metadata"]["name"] = f"r{r}-{p['metadata']['name']}"
                store.create("pods", q)
            engine.schedule_pending()
            check_invariants(store)
        return {p["metadata"]["name"]: (p.get("spec") or {}).get("nodeName")
                for p in store.list("pods")[0]}

    mesh_out = run(make_mesh(4, dp=2))
    base_out = run(None)
    assert mesh_out == base_out


@pytest.mark.parametrize("seed", [5, 23])
def test_engine_soak_streaming_commit(seed):
    """The randomized soak on the chunk-pipelined commit path: a
    no-postfilter lineup with chunk=8 forces multi-chunk streaming waves
    (the commit worker runs while the device scans), across creation /
    priority-churn / deletion rounds.  End state must satisfy the same
    invariants as the sequential engine, and a pipelined run must land
    the exact same placement as a sequential run of the same rounds."""
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig

    rng = np.random.default_rng(seed)
    nodes = make_nodes(int(rng.integers(6, 14)), seed=seed,
                       taint_fraction=0.25)
    pod_rounds = []
    for r in range(3):
        pods = make_pods(int(rng.integers(8, 20)), seed=seed * 10 + r,
                         with_affinity=True, with_tolerations=True,
                         with_spread=True)
        for p in pods:
            p["spec"]["priority"] = int(rng.integers(0, 3)) * 50
        pod_rounds.append(pods)
    cfg_kw = dict(enabled=[
        "NodeResourcesFit", "NodeResourcesBalancedAllocation",
        "NodeAffinity", "TaintToleration", "PodTopologySpread",
    ])

    def run(pipeline):
        store = ObjectStore()
        for n in nodes:
            store.create("nodes", n)
        engine = SchedulerEngine(
            store, plugin_config=PluginSetConfig(**cfg_kw), chunk=8,
            pipeline_commit=pipeline)
        plan = engine._wave_plan()
        assert (plan.commit == "streamed") == pipeline
        for r, pods in enumerate(pod_rounds):
            for p in pods:
                q = {"metadata": dict(p["metadata"]), "spec": dict(p["spec"])}
                q["metadata"]["name"] = f"r{r}-{p['metadata']['name']}"
                store.create("pods", q)
            engine.schedule_pending()
            check_invariants(store)
            # deterministic deletions free capacity for the next round
            bound = sorted(
                p["metadata"]["name"] for p in store.list("pods")[0]
                if (p.get("spec") or {}).get("nodeName"))
            for name in bound[: len(bound) // 3]:
                store.delete("pods", name, "default")
        engine.schedule_pending()
        check_invariants(store)
        return {p["metadata"]["name"]: (p.get("spec") or {}).get("nodeName")
                for p in store.list("pods")[0]}

    assert run(True) == run(False)
