"""Golden annotation fixtures: exact JSON strings for a hand-computed
cluster, pinning the wire format itself (the parity suite only proves
the tensor path and the sequential oracle agree with EACH OTHER).

Hand-derivation (upstream v1.32 semantics):
  node-a 2cpu/4Gi, node-b 4cpu/8Gi; pod requests 1cpu/2Gi.
  NodeResourcesFit LeastAllocated = mean over resources of
    (allocatable-requested)*100/allocatable -> a: (50+50)/2=50,
    b: (75+75)/2=75.
  BalancedAllocation: cpu/mem fractions equal on both -> std 0 -> 100.
  Scores marshal as strconv.FormatInt strings (store.go:474,501); maps
  marshal compact with sorted keys (Go encoding/json).
"""

import json

from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.store import annotations as ann

GOLDEN = {
    ann.PRE_FILTER_STATUS_RESULT: '{"NodeResourcesFit":"success"}',
    ann.PRE_FILTER_RESULT: "{}",
    ann.FILTER_RESULT:
        '{"node-a":{"NodeResourcesFit":"passed"},"node-b":{"NodeResourcesFit":"passed"}}',
    ann.POST_FILTER_RESULT: "{}",
    ann.PRE_SCORE_RESULT:
        '{"NodeResourcesBalancedAllocation":"success","NodeResourcesFit":"success"}',
    ann.SCORE_RESULT:
        '{"node-a":{"NodeResourcesBalancedAllocation":"100","NodeResourcesFit":"50"},'
        '"node-b":{"NodeResourcesBalancedAllocation":"100","NodeResourcesFit":"75"}}',
    ann.FINAL_SCORE_RESULT:
        '{"node-a":{"NodeResourcesBalancedAllocation":"100","NodeResourcesFit":"50"},'
        '"node-b":{"NodeResourcesBalancedAllocation":"100","NodeResourcesFit":"75"}}',
    ann.RESERVE_RESULT: "{}",
    ann.PERMIT_STATUS_RESULT: "{}",
    ann.PERMIT_TIMEOUT_RESULT: "{}",
    ann.PRE_BIND_RESULT: "{}",
    ann.BIND_RESULT: '{"DefaultBinder":"success"}',
    ann.SELECTED_NODE: "node-b",
}


def test_golden_annotation_strings():
    store = ObjectStore()
    store.create("nodes", {"metadata": {"name": "node-a"},
                           "status": {"allocatable": {"cpu": "2", "memory": "4Gi",
                                                      "pods": "10"}}})
    store.create("nodes", {"metadata": {"name": "node-b"},
                           "status": {"allocatable": {"cpu": "4", "memory": "8Gi",
                                                      "pods": "10"}}})
    engine = SchedulerEngine(store)
    engine.set_plugin_config(PluginSetConfig(
        enabled=["NodeResourcesFit", "NodeResourcesBalancedAllocation"]))
    store.create("pods", {"metadata": {"name": "p1"}, "spec": {"containers": [
        {"name": "c", "resources": {"requests": {"cpu": "1", "memory": "2Gi"}}}]}})
    assert engine.schedule_pending() == 1

    anns = store.get("pods", "p1", "default")["metadata"]["annotations"]
    for key, want in GOLDEN.items():
        assert anns[key] == want, f"{key}\n  got:  {anns[key]}\n  want: {want}"

    # result-history holds exactly these blobs as its first record
    hist = json.loads(anns[ann.RESULT_HISTORY])
    assert len(hist) == 1
    for key, want in GOLDEN.items():
        assert hist[0][key] == want, f"history {key}"


def test_golden_unschedulable_filter_message():
    """Infeasible pod records the upstream Insufficient-cpu message and
    an empty selected-node."""
    store = ObjectStore()
    store.create("nodes", {"metadata": {"name": "node-a"},
                           "status": {"allocatable": {"cpu": "2", "memory": "4Gi",
                                                      "pods": "10"}}})
    engine = SchedulerEngine(store)
    engine.set_plugin_config(PluginSetConfig(enabled=["NodeResourcesFit"]))
    store.create("pods", {"metadata": {"name": "big"}, "spec": {"containers": [
        {"name": "c", "resources": {"requests": {"cpu": "16", "memory": "2Gi"}}}]}})
    assert engine.schedule_pending() == 0
    anns = store.get("pods", "big", "default")["metadata"]["annotations"]
    fr = json.loads(anns[ann.FILTER_RESULT])
    assert fr["node-a"]["NodeResourcesFit"] == "Insufficient cpu"
    assert anns[ann.SELECTED_NODE] == ""


def _schedule(nodes, pods, enabled, weights=None):
    store = ObjectStore()
    for n in nodes:
        store.create("nodes", n)
    engine = SchedulerEngine(store)
    engine.set_plugin_config(PluginSetConfig(enabled=enabled,
                                             weights=weights or {}))
    for p in pods:
        store.create("pods", p)
    engine.schedule_pending()
    return {p["metadata"]["name"]:
            p["metadata"].get("annotations", {})
            for p in store.list("pods")[0]}


def _assert_golden(anns: dict, golden: dict):
    for key, want in golden.items():
        assert anns[key] == want, f"{key}\n  got:  {anns[key]}\n  want: {want}"


# Integer-division rounding, hand-derived from upstream v1.32 semantics
# (noderesources/least_allocated + balanced_allocation, int64 math):
#   node-a 4cpu/8Gi, node-b 2cpu/4Gi; pod requests 1cpu/1Gi.
#   LeastAllocated per resource = (allocatable-req)*100/allocatable, int64 div:
#     a.cpu (4000-1000)*100/4000 = 75
#     a.mem 7516192768*100/8589934592 = 87.4999... -> 87   (the rounding case)
#     a = (75+87)/2 = 81
#     b.cpu (2000-1000)*100/2000 = 50;  b.mem 3Gi*100/4Gi = 75 exact
#     b = (50+75)/2 = 125/2 -> 62                           (odd-sum division)
#   BalancedAllocation (2 resources): std = |f_cpu - f_mem|/2,
#   score = int64((1-std)*100):
#     a: |0.25-0.125|/2 = 0.0625 -> 93.75 -> 93
#     b: |0.5-0.25|/2   = 0.125  -> 87.5  -> 87
#   Totals: a 81+93=174 > b 62+87=149 -> node-a selected.
GOLDEN_ROUNDING = {
    ann.PRE_FILTER_STATUS_RESULT: '{"NodeResourcesFit":"success"}',
    ann.PRE_FILTER_RESULT: "{}",
    ann.FILTER_RESULT:
        '{"node-a":{"NodeResourcesFit":"passed"},"node-b":{"NodeResourcesFit":"passed"}}',
    ann.PRE_SCORE_RESULT:
        '{"NodeResourcesBalancedAllocation":"success","NodeResourcesFit":"success"}',
    ann.SCORE_RESULT:
        '{"node-a":{"NodeResourcesBalancedAllocation":"93","NodeResourcesFit":"81"},'
        '"node-b":{"NodeResourcesBalancedAllocation":"87","NodeResourcesFit":"62"}}',
    ann.FINAL_SCORE_RESULT:
        '{"node-a":{"NodeResourcesBalancedAllocation":"93","NodeResourcesFit":"81"},'
        '"node-b":{"NodeResourcesBalancedAllocation":"87","NodeResourcesFit":"62"}}',
    ann.BIND_RESULT: '{"DefaultBinder":"success"}',
    ann.SELECTED_NODE: "node-a",
}


def test_golden_integer_division_rounding():
    anns = _schedule(
        nodes=[
            {"metadata": {"name": "node-a"},
             "status": {"allocatable": {"cpu": "4", "memory": "8Gi", "pods": "10"}}},
            {"metadata": {"name": "node-b"},
             "status": {"allocatable": {"cpu": "2", "memory": "4Gi", "pods": "10"}}},
        ],
        pods=[{"metadata": {"name": "p1"}, "spec": {"containers": [
            {"name": "c", "resources": {"requests": {"cpu": "1", "memory": "1Gi"}}}]}}],
        enabled=["NodeResourcesFit", "NodeResourcesBalancedAllocation"],
    )
    _assert_golden(anns["p1"], GOLDEN_ROUNDING)


# TaintToleration, hand-derived from upstream v1.32 semantics
# (tainttoleration.go + helper.DefaultNormalizeScore reverse=true, weight 3):
#   node-a PreferNoSchedule dedicated=gpu (intolerable but not filtering),
#   node-b untainted, node-c NoSchedule dedicated=gpu (filters the pod).
#   Raw score = count of intolerable PreferNoSchedule taints: a=1, b=0.
#   Reverse-normalize over feasible nodes, max=1:
#     a: 100 - 100*1/1 = 0;  b: 100 - 100*0/1 = 100
#   finalscore = normalized x weight(3): a "0", b "300"; raw score-result
#   keeps the UN-normalized counts ("1"/"0") per AddScoreResult.
GOLDEN_TAINTS = {
    ann.PRE_FILTER_STATUS_RESULT: "{}",
    ann.PRE_FILTER_RESULT: "{}",
    ann.FILTER_RESULT:
        '{"node-a":{"TaintToleration":"passed"},'
        '"node-b":{"TaintToleration":"passed"},'
        '"node-c":{"TaintToleration":'
        '"node(s) had untolerated taint {dedicated: gpu}"}}',
    ann.PRE_SCORE_RESULT: '{"TaintToleration":"success"}',
    ann.SCORE_RESULT:
        '{"node-a":{"TaintToleration":"1"},"node-b":{"TaintToleration":"0"}}',
    ann.FINAL_SCORE_RESULT:
        '{"node-a":{"TaintToleration":"0"},"node-b":{"TaintToleration":"300"}}',
    ann.BIND_RESULT: '{"DefaultBinder":"success"}',
    ann.SELECTED_NODE: "node-b",
}


def test_golden_taint_reverse_normalize_weight():
    anns = _schedule(
        nodes=[
            {"metadata": {"name": "node-a"},
             "spec": {"taints": [{"key": "dedicated", "value": "gpu",
                                  "effect": "PreferNoSchedule"}]},
             "status": {"allocatable": {"cpu": "4", "memory": "8Gi", "pods": "10"}}},
            {"metadata": {"name": "node-b"},
             "status": {"allocatable": {"cpu": "4", "memory": "8Gi", "pods": "10"}}},
            {"metadata": {"name": "node-c"},
             "spec": {"taints": [{"key": "dedicated", "value": "gpu",
                                  "effect": "NoSchedule"}]},
             "status": {"allocatable": {"cpu": "4", "memory": "8Gi", "pods": "10"}}},
        ],
        pods=[{"metadata": {"name": "p1"},
               "spec": {"containers": [{"name": "c"}]}}],
        enabled=["TaintToleration"],
    )
    _assert_golden(anns["p1"], GOLDEN_TAINTS)


# NodeAffinity preferred terms, hand-derived from upstream v1.32 semantics
# (node_affinity.go Score = sum of matching preferred-term weights;
# NormalizeScore = DefaultNormalizeScore reverse=false; plugin weight 2):
#   node-a disk=ssd, node-b disk=hdd; preferred terms weight 5 (ssd) and
#   3 (hdd); required term disk In [ssd,hdd] matches both (keeps PreFilter
#   from skipping).  Raw: a=5, b=3; normalize max=5: a=100, b=100*3/5=60;
#   x2 -> "200"/"120".
GOLDEN_AFFINITY = {
    ann.PRE_FILTER_STATUS_RESULT: '{"NodeAffinity":"success"}',
    ann.PRE_FILTER_RESULT: "{}",
    ann.FILTER_RESULT:
        '{"node-a":{"NodeAffinity":"passed"},"node-b":{"NodeAffinity":"passed"}}',
    ann.PRE_SCORE_RESULT: '{"NodeAffinity":"success"}',
    ann.SCORE_RESULT:
        '{"node-a":{"NodeAffinity":"5"},"node-b":{"NodeAffinity":"3"}}',
    ann.FINAL_SCORE_RESULT:
        '{"node-a":{"NodeAffinity":"200"},"node-b":{"NodeAffinity":"120"}}',
    ann.BIND_RESULT: '{"DefaultBinder":"success"}',
    ann.SELECTED_NODE: "node-a",
}


def test_golden_node_affinity_preferred_weights():
    affinity = {"nodeAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": {
            "nodeSelectorTerms": [{"matchExpressions": [
                {"key": "disk", "operator": "In", "values": ["ssd", "hdd"]}]}]},
        "preferredDuringSchedulingIgnoredDuringExecution": [
            {"weight": 5, "preference": {"matchExpressions": [
                {"key": "disk", "operator": "In", "values": ["ssd"]}]}},
            {"weight": 3, "preference": {"matchExpressions": [
                {"key": "disk", "operator": "In", "values": ["hdd"]}]}},
        ]}}
    anns = _schedule(
        nodes=[
            {"metadata": {"name": "node-a", "labels": {"disk": "ssd"}},
             "status": {"allocatable": {"cpu": "4", "memory": "8Gi", "pods": "10"}}},
            {"metadata": {"name": "node-b", "labels": {"disk": "hdd"}},
             "status": {"allocatable": {"cpu": "4", "memory": "8Gi", "pods": "10"}}},
        ],
        pods=[{"metadata": {"name": "p1"},
               "spec": {"containers": [{"name": "c"}], "affinity": affinity}}],
        enabled=["NodeAffinity"],
    )
    _assert_golden(anns["p1"], GOLDEN_AFFINITY)


def test_pipelined_commit_parity_with_sequential_postpass():
    """The chunk-pipelined commit (engine pipeline_commit=True, the
    default) must be indistinguishable from the sequential post-pass:
    bit-identical annotations (including result-history), the same bind
    count, and the same bind order as observed by watch subscribers —
    chunk=16 over ~7 chunks so the commit worker genuinely runs while
    later chunks stream in, with a priority mix so queue order matters."""
    import copy
    import queue as queue_mod

    from kube_scheduler_simulator_tpu.models.workloads import make_nodes, make_pods

    nodes = make_nodes(20, seed=7, taint_fraction=0.2)
    pods = make_pods(110, seed=8, with_affinity=True, with_tolerations=True,
                     with_spread=True)
    for i, p in enumerate(pods):
        p["spec"]["priority"] = (i % 3) * 100
    cfg_kw = dict(enabled=[
        "NodeResourcesFit", "NodeResourcesBalancedAllocation", "NodeAffinity",
        "TaintToleration", "PodTopologySpread",
    ])

    def run(pipeline):
        store = ObjectStore()
        for n in nodes:
            store.create("nodes", copy.deepcopy(n))
        for p in pods:
            store.create("pods", copy.deepcopy(p))
        q = store.watch("pods")
        engine = SchedulerEngine(store, plugin_config=PluginSetConfig(**cfg_kw),
                                 chunk=16, pipeline_commit=pipeline)
        assert (engine._wave_plan().commit == "streamed") == pipeline
        bound = engine.schedule_pending()
        bind_order, seen = [], set()
        while True:
            try:
                _rv, event_type, obj = q.get_nowait()
            except queue_mod.Empty:
                break
            name = obj["metadata"]["name"]
            if (event_type == "MODIFIED"
                    and (obj.get("spec") or {}).get("nodeName")
                    and name not in seen):
                seen.add(name)
                bind_order.append(name)
        store.unwatch("pods", q)
        anns = {p["metadata"]["name"]: p["metadata"].get("annotations") or {}
                for p in store.list("pods")[0]}
        return bound, bind_order, anns

    bound_p, order_p, anns_p = run(True)
    bound_s, order_s, anns_s = run(False)
    assert bound_p == bound_s
    assert order_p == order_s
    assert anns_p.keys() == anns_s.keys()
    for name in anns_s:
        for key in set(anns_s[name]) | set(anns_p[name]):
            # resourceVersion never appears in annotations, so exact
            # string equality holds for every blob INCLUDING the
            # result-history append
            assert anns_p[name].get(key) == anns_s[name].get(key), (
                f"pod {name} key {key} diverged between pipelined and "
                "sequential commit")


def test_gang_pipelined_commit_parity_with_sequential_postpass():
    """The parity gate extended to gang scheduling
    (docs/gang-scheduling.md): a mixed wave of PodGroups (one admitted,
    one below quorum), gang-labeled pods and plain pods must produce
    bit-identical annotations (permit-result / permit-result-timeout /
    result-history included), the same bind count, the same bind order
    AND the same parked set between pipeline_commit=True (gang-boundary
    streaming cuts, chunk=8 so gangs of 5 straddle chunks) and False
    (the sequential post-pass with the same vectorized quorum pass)."""
    import copy
    import queue as queue_mod

    from kube_scheduler_simulator_tpu.framework.gang import POD_GROUP_LABEL
    from kube_scheduler_simulator_tpu.models.workloads import (
        make_gang_workload, make_nodes, make_pods)
    from kube_scheduler_simulator_tpu.plugins.coscheduling import (
        Coscheduling, ensure_podgroup_resource)

    nodes = make_nodes(14, seed=21, taint_fraction=0.2)
    pgs, gpods = make_gang_workload(3, 5, seed=22)
    for p in gpods:
        # one gang below quorum: two members infeasible
        if (p["metadata"]["labels"][POD_GROUP_LABEL] == "gang-0001"
                and p["metadata"]["name"].endswith(("003", "004"))):
            p["spec"]["containers"][0]["resources"]["requests"]["cpu"] = \
                "9999999m"
    plain = make_pods(40, seed=23, with_affinity=True, with_tolerations=True)
    for i, p in enumerate(plain):
        p["spec"]["priority"] = (i % 3) * 100

    def run(pipeline):
        store = ObjectStore()
        ensure_podgroup_resource(store)
        for n in nodes:
            store.create("nodes", copy.deepcopy(n))
        for pg in pgs:
            store.create("podgroups", copy.deepcopy(pg))
        for p in gpods + plain:
            store.create("pods", copy.deepcopy(p))
        q = store.watch("pods")
        cfg = PluginSetConfig(
            enabled=["NodeResourcesFit", "NodeResourcesBalancedAllocation",
                     "NodeAffinity", "TaintToleration", "Coscheduling"],
            custom={"Coscheduling": Coscheduling()},
        )
        engine = SchedulerEngine(store, plugin_config=cfg, chunk=8,
                                 pipeline_commit=pipeline)
        bound = engine.schedule_pending()
        bind_order, seen = [], set()
        while True:
            try:
                _rv, event_type, obj = q.get_nowait()
            except queue_mod.Empty:
                break
            name = obj["metadata"]["name"]
            if (event_type == "MODIFIED"
                    and (obj.get("spec") or {}).get("nodeName")
                    and name not in seen):
                seen.add(name)
                bind_order.append(name)
        store.unwatch("pods", q)
        anns = {p["metadata"]["name"]: p["metadata"].get("annotations") or {}
                for p in store.list("pods")[0]}
        parked = sorted(k for k in engine.gang_parked)
        return bound, bind_order, anns, parked

    bound_p, order_p, anns_p, parked_p = run(True)
    bound_s, order_s, anns_s, parked_s = run(False)
    assert bound_p == bound_s
    assert order_p == order_s
    assert parked_p == parked_s and len(parked_p) == 3
    assert anns_p.keys() == anns_s.keys()
    for name in anns_s:
        for key in set(anns_s[name]) | set(anns_p[name]):
            assert anns_p[name].get(key) == anns_s[name].get(key), (
                f"pod {name} key {key} diverged between pipelined and "
                "sequential gang commit")
