"""InterPodAffinity build with bound pods: the queue rows of the term table
(xs) and the carry primed with the bound rows, against a plain loop over
(bound pod, term) written here, and the annotations against the sequential
oracle.  Seeded workloads with required / preferred [anti-]affinity terms
of several weights, nodes lacking a term's topology key, a bound pod on a
node the table doesn't know, and the same term twice on one pod."""

import json

import numpy as np
import pytest

from kube_scheduler_simulator_tpu.framework.replay import replay
from kube_scheduler_simulator_tpu.plugins.interpod import effective_terms
from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
from kube_scheduler_simulator_tpu.reference_impl.sequential import SequentialScheduler
from kube_scheduler_simulator_tpu.state.compile import compile_workload
from kube_scheduler_simulator_tpu.state.selectors import label_selector_matches
from kube_scheduler_simulator_tpu.store.decode import decode_pod_result

CFG = PluginSetConfig(enabled=["NodeResourcesFit", "InterPodAffinity"])
KEYS = ("zone", "rack", "kubernetes.io/hostname")
KINDS = (("req_aff", "podAffinity", False), ("req_anti", "podAntiAffinity", False),
         ("pref_aff", "podAffinity", True), ("pref_anti", "podAntiAffinity", True))


def _nodes(rng, n):
    out = []
    for j in range(n):
        labels = {"kubernetes.io/hostname": f"n{j}", "zone": f"z{j % 3}"}
        if rng.random() < 0.6:   # the others lack the rack key: dom_idx < 0
            labels["rack"] = f"r{j % 2}"
        out.append({
            "apiVersion": "v1", "kind": "Node",
            "metadata": {"name": f"n{j}", "labels": labels}, "spec": {},
            "status": {"allocatable": {"cpu": "16", "memory": "64Gi", "pods": "110"},
                       "capacity": {"cpu": "16", "memory": "64Gi", "pods": "110"}}})
    return out


def _term(rng):
    return {"topologyKey": KEYS[int(rng.integers(len(KEYS)))],
            "labelSelector": {"matchLabels": {"app": f"a{int(rng.integers(3))}"}}}


def _pod(rng, name, term_share):
    pod = {"apiVersion": "v1", "kind": "Pod",
           "metadata": {"name": name, "namespace": "default",
                        "labels": {"app": f"a{int(rng.integers(3))}"}},
           "spec": {"containers": [{"name": "c", "resources": {
               "requests": {"cpu": "100m"}}}]}}
    affinity = {}
    for _, field, preferred in KINDS:
        if rng.random() >= term_share:
            continue
        terms = [_term(rng) for _ in range(int(rng.integers(1, 3)))]
        if rng.random() < 0.4:
            terms.append(dict(terms[0]))   # the same term twice: multiplicity 2
        group = affinity.setdefault(field, {})
        if preferred:
            group["preferredDuringSchedulingIgnoredDuringExecution"] = [
                {"weight": int(rng.choice([1, 7, 50, 100])), "podAffinityTerm": t}
                for t in terms]
        else:
            # required anti-affinity only over the narrow hostname domain, or
            # most seeds end with every queue pod unschedulable
            if field == "podAntiAffinity":
                terms = [dict(t, topologyKey="kubernetes.io/hostname") for t in terms]
            group["requiredDuringSchedulingIgnoredDuringExecution"] = terms
    if affinity:
        pod["spec"]["affinity"] = affinity
    return pod


def _workload(seed, n_nodes=9, n_queue=5, n_bound=14, term_share=0.45,
              queue_term_share=None):
    rng = np.random.default_rng(seed)
    nodes = _nodes(rng, n_nodes)
    queue = [_pod(rng, f"q{i}", term_share if queue_term_share is None
                  else queue_term_share) for i in range(n_queue)]
    bound = [(_pod(rng, f"b{i}", term_share), f"n{int(rng.integers(n_nodes))}")
             for i in range(n_bound)]
    if bound:
        bound[len(bound) // 2] = (bound[len(bound) // 2][0], "no-such-node")
    return nodes, queue, bound


def _loop_reference(nodes, queue, bound):
    """What compile_workload must hand the scan for InterPodAffinity, one
    scalar update at a time: terms interned over the queue pods, then the
    bound pods' own in sorted order, one row per pod, the bound rows added at (term, the domain of the
    pod's node) and read back at every node of that domain."""
    pods = queue + [bp for bp, _ in bound]
    term_ids, term_list = {}, []

    def terms_of(pod):
        return {kind: [((t.get("topologyKey", ""),
                         json.dumps(t.get("labelSelector"), sort_keys=True),
                         tuple(t.get("namespaces") or ())), t, w)
                       for t, w in effective_terms(pod, field, preferred, None)]
                for kind, field, preferred in KINDS}

    keyed = [terms_of(pod) for pod in pods]
    # the queue's terms in the order met, then the terms only bound pods
    # carry, sorted: no bound pod's position moves a term id
    in_order = [kt for e in keyed[:len(queue)] for kind, _, _ in KINDS
                for kt in e[kind]]
    bound_only = sorted((kt for e in keyed[len(queue):] for kind, _, _ in KINDS
                         for kt in e[kind]), key=lambda kt: kt[0])
    for key, t, _ in in_order + bound_only:
        if key not in term_ids:
            term_ids[key] = len(term_list)
            term_list.append(t)
    per_pod = [{kind: [(term_ids[key], w) for key, _, w in e[kind]]
                for kind, _, _ in KINDS} for e in keyed]
    t_count = max(len(term_list), 1)
    rows = {name: [[0] * t_count for _ in pods]
            for name in ("t_matches", "req_aff", "req_anti", "pref_aff", "pref_anti")}
    for i, pod in enumerate(pods):
        labels = {k: str(v) for k, v in pod["metadata"]["labels"].items()}
        for t_id, t in enumerate(term_list):
            rows["t_matches"][i][t_id] = int(
                pod["metadata"]["namespace"] in t["namespaces"]
                and label_selector_matches(t.get("labelSelector"), labels))
        for kind in ("req_aff", "req_anti", "pref_aff", "pref_anti"):
            for t_id, w in per_pod[i][kind]:
                rows[kind][i][t_id] += w
    any_anti = any(e["req_anti"] for e in per_pod)
    q = len(queue)
    xs = {
        "t_matches": [[bool(v) for v in r] for r in rows["t_matches"][:q]],
        "h_req_aff": rows["req_aff"][:q], "h_req_anti": rows["req_anti"][:q],
        "h_pref_aff_w": rows["pref_aff"][:q], "h_pref_anti_w": rows["pref_anti"][:q],
        "self_ok": [all(rows["t_matches"][i][t_id] for t_id, _ in per_pod[i]["req_aff"])
                    for i in range(q)],
        "filter_skip": [not any_anti and not per_pod[i]["req_aff"]
                        and not per_pod[i]["req_anti"] for i in range(q)],
    }
    node_labels = [n["metadata"]["labels"] for n in nodes]
    name_idx = {n["metadata"]["name"]: j for j, n in enumerate(nodes)}
    carry = {name: [[0] * len(nodes) for _ in range(t_count)]
             for name in ("matched", "have_req_anti", "have_req_aff",
                          "sym_pref_aff", "sym_pref_anti")}
    matched_total = [0] * t_count
    for bi, (_, node_name) in enumerate(bound):
        j = name_idx.get(node_name)
        if j is None:
            continue
        i = q + bi
        for t_id, t in enumerate(term_list):
            domain = node_labels[j].get(t["topologyKey"])
            if domain is None:
                continue
            matched_total[t_id] += rows["t_matches"][i][t_id]
            for n, labels in enumerate(node_labels):
                if labels.get(t["topologyKey"]) != domain:
                    continue
                carry["matched"][t_id][n] += rows["t_matches"][i][t_id]
                carry["have_req_anti"][t_id][n] += rows["req_anti"][i][t_id]
                carry["have_req_aff"][t_id][n] += rows["req_aff"][i][t_id]
                carry["sym_pref_aff"][t_id][n] += rows["pref_aff"][i][t_id]
                carry["sym_pref_anti"][t_id][n] += rows["pref_anti"][i][t_id]
    carry["matched_total"] = matched_total
    return xs, carry, t_count


WORKLOADS = {
    "seed11": dict(seed=11),
    "seed12": dict(seed=12),
    "seed13_every_pod_has_terms": dict(seed=13, term_share=1.0),
    "seed14_many_bound": dict(seed=14, n_nodes=6, n_queue=3, n_bound=40),
    "seed15_no_bound": dict(seed=15, n_bound=0),
    "seed16_no_terms": dict(seed=16, term_share=0.0),
    # the queue's PreFilter Skip is lost to the bound pods' anti-affinity
    "seed17_terms_on_bound_only": dict(seed=17, queue_term_share=0.0),
}


@pytest.fixture(scope="module", params=list(WORKLOADS), ids=list(WORKLOADS))
def compiled(request):
    nodes, queue, bound = _workload(**WORKLOADS[request.param])
    return nodes, queue, bound, compile_workload(nodes, queue, CFG, bound_pods=bound)


XS_DTYPES = {"t_matches": bool, "h_req_aff": np.int32, "h_req_anti": np.int32,
             "h_pref_aff_w": np.int64, "h_pref_anti_w": np.int64,
             "self_ok": bool, "filter_skip": bool}


def test_xs_and_primed_carry_equal_the_plain_loop(compiled):
    nodes, queue, bound, cw = compiled
    want_xs, want_carry, t_count = _loop_reference(nodes, queue, bound)
    # T >= 3 wherever a pod has terms; the placeholder 1 where none has
    assert t_count >= 3 or not any(
        "affinity" in p["spec"] for p in queue + [bp for bp, _ in bound])
    xs = cw.xs["InterPodAffinity"]
    assert xs._fields == tuple(XS_DTYPES)
    for field, want in want_xs.items():
        got = np.asarray(getattr(xs, field))
        assert got.dtype == XS_DTYPES[field], field
        # the pod axis is the queue's bucket (state/compile.py
        # pod_axis_bucket, since PR 50): the rows past the pods are zeros
        assert got.shape == ((cw.pod_axis,) if field in ("self_ok", "filter_skip")
                             else (cw.pod_axis, t_count)), field
        assert not got[len(queue):].any(), field
        np.testing.assert_array_equal(got[:len(queue)], np.asarray(want),
                                      err_msg=field)
    carry = cw.init_carry["InterPodAffinity"]
    assert set(carry._fields) == set(want_carry)
    for field, want in want_carry.items():
        got = np.asarray(getattr(carry, field))
        assert got.dtype == np.int32, field
        np.testing.assert_array_equal(got, np.asarray(want), err_msg=field)


def test_annotations_equal_the_sequential_oracle(compiled):
    nodes, queue, bound, cw = compiled
    seq = SequentialScheduler(nodes, queue, CFG, bound_pods=bound).schedule_all()
    rr = replay(cw, chunk=8)
    for i, (want, selected) in enumerate(seq):
        assert int(rr.selected[i]) == selected, f"pod {i} selected"
        got = decode_pod_result(rr, i)
        for k in want:
            assert got[k] == want[k], f"pod {i} {k}"
