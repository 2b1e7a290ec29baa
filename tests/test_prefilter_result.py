"""PreFilterResult: NodeAffinity's PreFilter narrows the pass to the nodes a
pod names by `matchFields: metadata.name In [...]`, Filter runs on those
alone, and the names are recorded in the prefilter-result annotation.

Every branch of upstream v1.32 `NodeAffinity.PreFilter` (docs/SEMANTICS.md,
"PreFilterResult") against reference_impl/sequential.py: all 13
annotations byte for byte (tests/test_scan_parity_matrix.py runs the same
queue over every route of the scan).
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from kube_scheduler_simulator_tpu.cluster.store import NotFound, ObjectStore
from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
from kube_scheduler_simulator_tpu.framework.pipeline import (
    NOT_EVALUATED, PACK_MODES)
from kube_scheduler_simulator_tpu.framework.replay import (
    filter_rejected_rows, plugin_attribution, replay)
from kube_scheduler_simulator_tpu.plugins import affinity
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.reference_impl.sequential import (
    SequentialScheduler)
from kube_scheduler_simulator_tpu.state.compile import compile_workload
from kube_scheduler_simulator_tpu.store import annotations as ann
from kube_scheduler_simulator_tpu.store.decode import (
    decode_all_parallel, decode_pod_result)
from kube_scheduler_simulator_tpu.utils.tracing import TRACER

N_PLAIN = 9
SAFE_CFG = ["NodeResourcesFit", "NodeResourcesBalancedAllocation",
            "NodeAffinity", "TaintToleration", "NodeName",
            "NodeUnschedulable"]
ERR_AFFINITY = "node(s) didn't match Pod's node affinity/selector"


def node(name, cpu="4", labels=None):
    res = {"cpu": cpu, "memory": "32Gi", "pods": "110"}
    return {"apiVersion": "v1", "kind": "Node",
            "metadata": {"name": name, "labels": dict(labels or {})},
            "spec": {}, "status": {"allocatable": res, "capacity": dict(res)}}


def pod(name, terms=None, cpu="100m", priority=0, node_name=None,
        node_selector=None):
    spec = {"priority": priority, "containers": [{
        "name": "c", "resources": {"requests": {"cpu": cpu,
                                                "memory": "500Mi"}}}]}
    if terms is not None:
        spec["affinity"] = {"nodeAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": {
                "nodeSelectorTerms": terms}}}
    if node_selector:
        spec["nodeSelector"] = node_selector
    p = {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": name, "namespace": "default"},
         "spec": spec, "status": {}}
    if node_name:
        spec["nodeName"] = node_name
        p["status"]["phase"] = "Running"
    return p


def name_in(*values):
    return {"key": "metadata.name", "operator": "In", "values": list(values)}


def fields(*reqs):
    return {"matchFields": list(reqs)}


NODES = [node(f"n{i}", labels={"even": str(i % 2 == 0).lower()})
         for i in range(N_PLAIN)] + [node("named")]

# name -> (required terms, the prefilter-result entry or None, the nodes
# Filter runs on or None for all, the selected node or "" (None: any))
BRANCHES = {
    "one_name": ([fields(name_in("named"))], ["named"], ["named"], "named"),
    "two_names_one_requirement":
        ([fields(name_in("n3", "n1"))], ["n1", "n3"], ["n1", "n3"], None),
    "two_terms_union":
        ([fields(name_in("n1")), fields(name_in("n2"))],
         ["n1", "n2"], ["n1", "n2"], None),
    "two_requirements_intersection":
        ([fields(name_in("n1", "n2"), name_in("n2", "n3"))],
         ["n2"], ["n2"], "n2"),
    "term_without_the_field":
        ([fields(name_in("n1")),
          {"matchExpressions": [{"key": "even", "operator": "In",
                                 "values": ["true"]}]}],
         None, None, None),
    "name_that_is_no_node":
        ([fields(name_in("gone"))], ["gone"], [], ""),
    "one_name_of_two_is_no_node":
        ([fields(name_in("gone", "n4"))], ["gone", "n4"], ["n4"], "n4"),
    "label_expression_beside_the_field":
        ([{"matchFields": [name_in("n1", "n2")],
           "matchExpressions": [{"key": "even", "operator": "In",
                                 "values": ["true"]}]}],
         ["n1", "n2"], ["n1", "n2"], "n2"),
    "another_field_key":
        ([fields({"key": "metadata.namespace", "operator": "In",
                  "values": ["n1"]})], None, None, ""),
    "not_in_is_no_name":
        ([fields({"key": "metadata.name", "operator": "NotIn",
                  "values": ["n0"]})], None, None, None),
}


def _queue():
    pods = [pod(name, terms) for name, (terms, *_) in BRANCHES.items()]
    pods.insert(3, pod("plain"))
    pods.append(pod("conflict", [fields(name_in("n1"), name_in("n2"))]))
    pods.append(pod("too_big", [fields(name_in("named"))], cpu="5"))
    return pods


def _scan(cfg):
    return replay(compile_workload(NODES, _queue(), cfg), chunk=4)


def test_every_branch_matches_the_sequential_reference():
    rr = _scan(None)   # the default profile
    pods = _queue()
    oracle = SequentialScheduler(NODES, pods, None).schedule_all()
    decoded = decode_all_parallel(rr)
    for i, (want, sel) in enumerate(oracle):
        who = pods[i]["metadata"]["name"]
        for key, value in want.items():
            assert decoded[i][key] == value, (who, key)
        assert int(rr.selected[i]) == sel, who


@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_branch_is_what_upstream_prefilter_returns(branch):
    """Not only equal to the reference: the annotation itself, against
    upstream's rules written out per branch."""
    terms, names, considered, selected = BRANCHES[branch]
    rr = replay(compile_workload(NODES, [pod("p", terms)]))
    got = decode_pod_result(rr, 0)
    assert json.loads(got[ann.PRE_FILTER_RESULT]) == (
        {} if names is None else {"NodeAffinity": names})
    status = json.loads(got[ann.PRE_FILTER_STATUS_RESULT])
    assert status["NodeAffinity"] == "success"
    filt = json.loads(got[ann.FILTER_RESULT])
    all_nodes = sorted(n["metadata"]["name"] for n in NODES)
    assert sorted(filt) == (all_nodes if considered is None
                            else sorted(considered))
    if selected is not None:
        assert got[ann.SELECTED_NODE] == selected
    else:
        assert got[ann.SELECTED_NODE] in (considered or all_nodes)
    if considered is not None:
        # a node outside the result is neither refused nor counted
        assert int(filter_rejected_rows(rr, 0, 1)[0]) == (
            len(considered) - int(rr.feasible_count[0]))
        assert int(rr.cw.host["considered_count"][0]) == len(considered)
    if branch == "another_field_key":
        # upstream: the field selector asks for a field a node does not
        # have, so the term matches no node; every node is asked and refuses
        assert all(e["NodeAffinity"] == ERR_AFFINITY for e in filt.values())


def test_conflicting_requirements_reject_in_prefilter():
    """Each term's intersection empty: UnschedulableAndUnresolvable
    "pod affinity terms conflict", and the cycle ends before Filter."""
    pods = [pod("p", [fields(name_in("n1"), name_in("n2"))])]
    rr = replay(compile_workload(NODES, pods))
    got = decode_pod_result(rr, 0)
    assert json.loads(got[ann.PRE_FILTER_STATUS_RESULT]) == {
        "NodeAffinity": affinity.ERR_CONFLICT}
    assert got[ann.PRE_FILTER_RESULT] == got[ann.FILTER_RESULT] == "{}"
    assert got[ann.SELECTED_NODE] == "" and int(rr.selected[0]) == -1
    assert int(filter_rejected_rows(rr, 0, 1)[0]) == 0
    want, _ = SequentialScheduler(NODES, pods).schedule_all()[0]
    assert got == want


def test_not_evaluated_is_told_apart_from_refused_in_both_layouts():
    pods = [pod("narrow", [fields(name_in("named"))], cpu="5"),
            pod("plain", cpu="5")]
    cw = compile_workload(NODES, pods)
    rr = replay(cw)
    named = cw.node_table.names.index("named")
    f = len(cw.config.filters())
    _, code_bits, _ = PACK_MODES[rr._compact.pack_mode]
    words = np.asarray(rr._compact.host("packed", 0)).astype(np.int64)
    ffp = words >> code_bits
    outside = np.arange(cw.n_nodes) != named
    assert (ffp[0, outside] == f + 1).all() and (words[0, outside]
                                                 & ((1 << code_bits) - 1) == 0).all()
    assert 0 < ffp[0, named] <= f            # the named node: refused
    assert ((ffp[1] > 0) & (ffp[1] <= f)).all()   # the plain pod: all refused
    codes = rr.codes_of(0)
    assert (codes[:, outside] == NOT_EVALUATED).all()
    assert (codes[:, named] >= 0).all() and codes[:, named].any()
    assert not rr.feasible_of(0).any()
    # what the wave counts: 1 refusal for the narrowed pod, N for the plain
    assert filter_rejected_rows(rr, 0, 2).tolist() == [1, cw.n_nodes]
    att = plugin_attribution(rr)
    fit = att["filter"]["NodeResourcesFit"]
    assert fit["rejects"] == 1 + cw.n_nodes
    assert fit["evaluated"] == 1 + cw.n_nodes
    assert att["filter"]["NodeAffinity"] == {"evaluated": 1, "rejects": 0}


def test_names_differ_from_pod_to_pod_and_the_statics_do_not():
    """A DaemonSet's rollout names another node in every pod.  The names
    travel as an xs leaf; where the names say all there is to say (the
    controller's form) the match row is the identity row, so the closure
    statics, and with them the compiled scan, are the same pass after
    pass."""
    def one(target):
        return compile_workload(NODES, [pod("p", [fields(name_in(target))])])

    a, b = one("n1"), one("n7")
    assert a.host["_statics_fp"] == b.host["_statics_fp"]
    assert a.xs["NodeAffinity"].pf_nodes.shape == (1, 1)
    assert (int(a.xs["NodeAffinity"].pf_nodes[0, 0])
            != int(b.xs["NodeAffinity"].pf_nodes[0, 0]))
    assert int(a.xs["NodeAffinity"].req_idx[0]) == 0
    # a label expression beside the field still needs its own row
    c = compile_workload(NODES, [pod("p", BRANCHES[
        "label_expression_beside_the_field"][0])])
    assert int(c.xs["NodeAffinity"].req_idx[0]) == 1
    # and a queue without a named pod carries no column at all
    d = compile_workload(NODES, [pod("p")])
    assert d.xs["NodeAffinity"].pf_nodes.shape == (1, 0)
    assert "prefilter_json" not in d.host


def _engine(*objects):
    s = ObjectStore()
    for n in NODES[:3] + [node("named", cpu="1")]:
        s.create("nodes", n)
    for o in objects:
        s.create("pods", o)
    return s, SchedulerEngine(s)


def test_refused_by_the_named_node_is_unschedulable_with_one_entry():
    s, engine = _engine(pod("big", [fields(name_in("named"))], cpu="2"))
    before = {k: TRACER.counter_totals().get(k, 0) for k in (
        "prefilter_narrowed_pods_total", "prefilter_considered_nodes_total",
        "filter_rejected_nodes_total")}
    assert engine.schedule_pending() == 0
    p = s.get("pods", "big")
    assert not p["spec"].get("nodeName")
    anns = p["metadata"]["annotations"]
    assert json.loads(anns[ann.FILTER_RESULT]) == {"named": {
        "NodeName": "passed", "NodeUnschedulable": "passed",
        "TaintToleration": "passed", "NodeAffinity": "passed",
        "NodeResourcesFit": "Insufficient cpu"}}
    assert json.loads(anns[ann.PRE_FILTER_RESULT]) == {"NodeAffinity": ["named"]}
    # DefaultPreemption looked at the one evaluated node and at no other
    assert json.loads(anns[ann.POST_FILTER_RESULT]) == {"named": {}}
    after = TRACER.counter_totals()
    assert after["prefilter_narrowed_pods_total"] - before[
        "prefilter_narrowed_pods_total"] == 1
    assert after["prefilter_considered_nodes_total"] - before[
        "prefilter_considered_nodes_total"] == 1
    assert after["filter_rejected_nodes_total"] - before[
        "filter_rejected_nodes_total"] == 1


def test_preemptor_evicts_on_its_named_node_and_nowhere_else():
    """The named node holds a victim; every other node holds a
    lower-priority pod too and would be a candidate by its own state, but
    no Filter ran there (upstream: UnschedulableAndUnresolvable for the
    absent nodes), so none is evaluated, screened or nominated."""
    s, engine = _engine(
        pod("victim", cpu="800m", node_name="named"),
        pod("bystander0", cpu="3900m", node_name="n0"),
        pod("bystander1", cpu="3900m", node_name="n1"),
        pod("pri", [fields(name_in("named"))], cpu="500m", priority=10))
    assert engine.schedule_pending() == 1
    with pytest.raises(NotFound):
        s.get("pods", "victim")
    assert s.get("pods", "bystander0") and s.get("pods", "bystander1")
    p = s.get("pods", "pri")
    assert p["spec"]["nodeName"] == "named"
    first = json.loads(p["metadata"]["annotations"][ann.RESULT_HISTORY])[0]
    assert json.loads(first[ann.POST_FILTER_RESULT]) == {
        "named": {"DefaultPreemption": "preemption victim"}}
    assert json.loads(first[ann.FILTER_RESULT]).keys() == {"named"}
