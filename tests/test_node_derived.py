"""The node-derived memo on the NodeTable (state/nodes.py NodeDerived, PR 31).

What a build derives from the node table alone, or from the table and a
hashable fragment of a pod's spec (image names, tolerations, a topology
key, the statics' digest), is computed on first use and kept on the table
object.  Held here:

  * a pass on a warm table is byte-equal (dtype, shape, bytes) to a build
    from scratch on a fresh table, for the four benchmark generators;
  * every node change (taint, label, status.images, name, a node added or
    removed, the delta-patch path) makes a new table with an empty memo;
  * the three counters: first pass all misses, steady pass all hits, an
    eviction past the cap is counted and the evicted row is rebuilt equal;
  * what the memo hands out is read-only;
  * an equal statics digest hands back the identical device arrays;
  * PodTopologySpread and InterPodAffinity index one domain row a key.
"""

from __future__ import annotations

import copy
import dataclasses
import json
import sys
import threading
from pathlib import Path

import jax
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))

from generators import scheduler_perf, scheduler_perf_unique_label  # noqa: E402

from kube_scheduler_simulator_tpu.config.config import SimulatorConfiguration  # noqa: E402
from kube_scheduler_simulator_tpu.plugins import imagelocality  # noqa: E402
from kube_scheduler_simulator_tpu.server.sessions import SessionManager  # noqa: E402
from kube_scheduler_simulator_tpu.state.compile import (  # noqa: E402
    compile_workload, split_statics)
from kube_scheduler_simulator_tpu.state.nodes import NodeDerived  # noqa: E402
from kube_scheduler_simulator_tpu.utils.tracing import TRACER  # noqa: E402

GENERATORS = {"scheduler_perf": scheduler_perf,
              "scheduler_perf_unique_label": scheduler_perf_unique_label}
CONFIGS = ["sched_perf_basic_5k", "sched_perf_podaffinity_5k",
           "envelope_5k", "sched_perf_antiaffinity_5k"]
ZONE = "topology.kubernetes.io/zone"
COUNTERS = {"hits": "node_derived_hits_total",
            "misses": "node_derived_misses_total",
            "evictions": "node_derived_evictions_total"}

TOLERATIONS = [
    None,
    [{"key": "dedicated", "operator": "Equal", "value": "batch",
      "effect": "NoSchedule"}],
    [{"operator": "Exists"}],
]
IMAGE_SETS = [["registry.k8s.io/pause:3.10"],
              ["nginx", "busybox:1.36"],
              ["busybox:1.36"]]


def _deployment(config: str, nodes: int = 200, initial: int = 40, seed: int = 31):
    conf = json.loads((BENCH / f"configs/{config}.json").read_text())
    params = dict(conf["parameters"], nodes=nodes)
    params["initial_pods"] = dict(params["initial_pods"], count=initial)
    dep = GENERATORS[conf["generator"]].generate(params, seed)
    # the generators' nodes carry neither taints nor images: give some of
    # them both, so that the memoised rows are not all zeros
    for j, node in enumerate(dep.nodes):
        node["metadata"]["resourceVersion"] = "1"
        if j % 7 == 0:
            node["spec"]["taints"] = [
                {"key": "dedicated", "value": "batch", "effect": "NoSchedule"}]
        if j % 5 == 0:
            node["spec"].setdefault("taints", []).append(
                {"key": "slow", "value": "disk", "effect": "PreferNoSchedule"})
        if j % 3 == 0:
            node["status"]["images"] = [
                {"names": ["registry.k8s.io/pause:3.10"], "sizeBytes": 300 << 20},
                {"names": ["nginx:latest"], "sizeBytes": (40 + j) << 20}]
        if j % 4 == 0:
            node["status"].setdefault("images", []).append(
                {"names": ["busybox:1.36"], "sizeBytes": 700 << 20})
    return dep


def _bound(dep):
    return [(p, p["spec"]["nodeName"]) for p in dep.initial_pods]


def _varied(dep, k: int) -> dict:
    """The k-th measured pod with one of 3 tolerations and 3 image sets."""
    pod = dep.measured_pod()
    tols = TOLERATIONS[k % 3]
    if tols is not None:
        pod["spec"]["tolerations"] = copy.deepcopy(tols)
    pod["spec"]["containers"] = [
        dict(pod["spec"]["containers"][0], name=f"c{i}", image=image)
        for i, image in enumerate(IMAGE_SETS[(k // 3) % 3])]
    return pod


def _counts() -> dict[tuple[str, str], float]:
    return {(what, kind): v for what, name in COUNTERS.items()
            for kind, v in TRACER.labeled_totals(name, "kind").items()}


def _delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] - before.get(k, 0)}


def _same(a, b, path="cw"):
    """Byte equality of two build results: arrays by dtype, shape and
    bytes; containers and dataclasses field by field (the memo itself is
    not part of a result)."""
    if isinstance(a, NodeDerived):
        return
    if isinstance(a, (np.ndarray, np.generic, jax.Array)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, (
            path, a.dtype, b.dtype, a.shape, b.shape)
        assert a.tobytes() == b.tobytes(), path
    elif isinstance(a, dict) or hasattr(a, "keys"):
        assert list(a.keys()) == list(b.keys()), path
        for k in a.keys():
            _same(a[k], b[k], f"{path}[{k!r}]")
    elif dataclasses.is_dataclass(a) and not isinstance(a, type):
        assert type(a) is type(b), path
        for f in dataclasses.fields(a):
            _same(getattr(a, f.name), getattr(b, f.name), f"{path}.{f.name}")
    elif hasattr(a, "_fields"):          # NamedTuple pytrees
        assert type(a) is type(b), path
        for name in a._fields:
            _same(getattr(a, name), getattr(b, name), f"{path}.{name}")
    elif isinstance(a, (list, tuple)) or (
            hasattr(a, "__len__") and hasattr(a, "__getitem__")
            and not isinstance(a, (str, bytes))):
        assert len(a) == len(b), path
        for i in range(len(a)):
            _same(a[i], b[i], f"{path}[{i}]")
    else:
        assert a == b, (path, a, b)


def _same_workload(warm, cold):
    _same(warm.xs, cold.xs, "xs")
    _same(warm.statics, cold.statics, "statics")
    _same(warm.init_carry, cold.init_carry, "init_carry")
    assert warm.host.keys() == cold.host.keys()
    for k in warm.host:
        if k == "volume_table":
            _same(vars(warm.host[k]), vars(cold.host[k]), "host.volume_table")
        elif k != "schema":
            _same(warm.host[k], cold.host[k], f"host[{k!r}]")
    assert warm.schema.columns == cold.schema.columns


def _cold(dep, pods):
    """The build from scratch: fresh manifests, a fresh table, no reuse."""
    return compile_workload(copy.deepcopy(dep.nodes), pods,
                            bound_pods=_bound(dep), namespaces=dep.namespaces)


# --------------------------------------- (a) warm == cold, byte for byte


@pytest.mark.parametrize("config", CONFIGS)
def test_warm_one_pod_passes_equal_a_build_from_scratch(config):
    dep = _deployment(config)
    prev = compile_workload(dep.nodes, [dep.measured_pod()],
                            bound_pods=_bound(dep), namespaces=dep.namespaces)
    table = prev.node_table
    for i in range(5):
        pods = [dep.measured_pod()]
        before = _counts()
        warm = compile_workload(dep.nodes, pods, bound_pods=_bound(dep),
                                namespaces=dep.namespaces, reuse=prev)
        moved = _delta(before, _counts())
        assert warm.node_table is table
        assert not [k for k in moved if k[0] != "hits"], (i, moved)
        assert moved[("hits", "name_idx")] == 4, moved
        _same_workload(warm, _cold(dep, pods))
        prev = warm


@pytest.mark.parametrize("config", CONFIGS)
def test_warm_twenty_pod_pass_equals_a_build_from_scratch(config):
    dep = _deployment(config)
    first = [_varied(dep, k) for k in range(20)]
    prev = compile_workload(dep.nodes, first, bound_pods=_bound(dep),
                            namespaces=dep.namespaces)
    pods = [_varied(dep, k + 1) for k in range(20)]
    before = _counts()
    warm = compile_workload(dep.nodes, pods, bound_pods=_bound(dep),
                            namespaces=dep.namespaces, reuse=prev)
    moved = _delta(before, _counts())
    assert warm.node_table is prev.node_table
    assert moved[("hits", "taint_rows")] == 20
    assert moved[("hits", "image_row")] == 20
    assert not [k for k in moved if k[0] == "evictions"], moved
    assert ("misses", "taint_rows") not in moved
    assert ("misses", "image_row") not in moved
    _same_workload(warm, _cold(dep, pods))


# -------------------------------- (b) a node change is a new, empty memo


def _change_taint(nodes):
    nodes[3]["spec"]["taints"] = [
        {"key": "dedicated", "value": "other", "effect": "NoSchedule"}]
    nodes[3]["metadata"]["resourceVersion"] = "2"


def _change_label(nodes):
    nodes[4]["metadata"].setdefault("labels", {})[ZONE] = "zone9"
    nodes[4]["metadata"]["resourceVersion"] = "2"


def _change_images(nodes):
    nodes[5]["status"]["images"] = [
        {"names": ["busybox:1.36"], "sizeBytes": 900 << 20}]
    nodes[5]["metadata"]["resourceVersion"] = "2"


def _change_name(nodes):
    nodes[6]["metadata"]["name"] += "-renamed"


def _add_node(nodes):
    node = copy.deepcopy(nodes[0])
    node["metadata"]["name"] = "zz-added"
    node["metadata"].get("labels", {}).pop("kubernetes.io/hostname", None)
    nodes.append(node)


def _remove_node(nodes):
    del nodes[9]


CHANGES = {"taint": (_change_taint, True), "label": (_change_label, True),
           "images": (_change_images, True), "name": (_change_name, False),
           "added": (_add_node, False), "removed": (_remove_node, False)}


@pytest.mark.parametrize("change", sorted(CHANGES))
def test_a_node_change_starts_an_empty_memo(change):
    dep = _deployment("sched_perf_podaffinity_5k", nodes=60, initial=20)
    mutate, patched = CHANGES[change]
    pod = _varied(dep, 1)
    prev = compile_workload(dep.nodes, [pod], namespaces=dep.namespaces)
    nodes = copy.deepcopy(dep.nodes)
    mutate(nodes)
    patches = TRACER.counter_totals().get("node_table_delta_patches_total", 0)
    before = _counts()
    cw = compile_workload(nodes, [pod], namespaces=dep.namespaces, reuse=prev)
    moved = _delta(before, _counts())
    assert cw.node_table is not prev.node_table
    assert cw.node_table.derived is not prev.node_table.derived
    assert (TRACER.counter_totals().get("node_table_delta_patches_total", 0)
            - patches) == (1 if patched else 0)
    # nothing was served from the old table: every kind missed once, and
    # the only hits are name_idx's three later builders
    assert {k: v for k, v in moved.items() if k[0] == "hits"} == {
        ("hits", "name_idx"): 3}, moved
    for kind in ("image_states", "image_row", "taint_rows", "taint_max",
                 "dom_idx", "name_idx", "statics_device"):
        assert moved[("misses", kind)] == 1, (kind, moved)
    _same_workload(cw, compile_workload(copy.deepcopy(nodes), [pod],
                                        namespaces=dep.namespaces))


def test_served_delta_patch_starts_an_empty_memo():
    """The columnar store's delta-patch path (patch_node_table_columnar):
    a node updated between two passes of a served session."""
    dep = _deployment("sched_perf_antiaffinity_5k", nodes=40, initial=8)
    mgr = SessionManager(cfg=SimulatorConfiguration(port=0),
                         start_scheduler=False, idle_ttl=0, max_sessions=2)
    try:
        sess = mgr.create("node-derived")
        store, engine = sess.di.store, sess.di.engine
        for ns in dep.namespaces:
            store.create("namespaces", ns)
        for obj in dep.nodes:
            obj["metadata"].pop("resourceVersion")
            store.create("nodes", obj)
        for obj in dep.initial_pods:
            store.create("pods", obj)

        def one_pass():
            store.create("pods", dep.measured_pod())
            before = _counts()
            assert engine.schedule_pending() == 1
            return _delta(before, _counts())

        one_pass()
        table = engine._last_cw.node_table
        steady = one_pass()
        assert engine._last_cw.node_table is table
        assert not [k for k in steady if k[0] != "hits"], steady
        # (the volume carry derives from name_idx on a new table only)
        assert sum(steady.values()) == 8, steady

        node = copy.deepcopy(store.get("nodes", dep.nodes[2]["metadata"]["name"]))
        node["spec"]["taints"] = [
            {"key": "dedicated", "value": "batch", "effect": "NoSchedule"}]
        patches = TRACER.counter_totals().get("node_table_delta_patches_total", 0)
        store.update("nodes", node)
        after_change = one_pass()
        assert TRACER.counter_totals()[
            "node_table_delta_patches_total"] - patches == 1
        patched = engine._last_cw.node_table
        assert patched is not table and patched.derived is not table.derived
        assert {k: v for k, v in after_change.items() if k[0] == "hits"} == {
            ("hits", "name_idx"): 3}, after_change
        j = patched.names.index(node["metadata"]["name"])
        assert len(table.taints[j]) == 0 and len(patched.taints[j]) == 1
        (old_code, _), = table.derived._rows["taint_rows"].values()
        (new_code, _), = patched.derived._rows["taint_rows"].values()
        assert old_code[j] == 0 and new_code[j] == 1
        assert one_pass() == steady
    finally:
        mgr.shutdown()


# ------------------------------------------------------ (c) the counters


def test_first_pass_misses_steady_pass_hits():
    dep = _deployment("sched_perf_antiaffinity_5k", nodes=50, initial=10)
    before = _counts()
    prev = compile_workload(dep.nodes, [dep.measured_pod()],
                            bound_pods=_bound(dep), namespaces=dep.namespaces)
    first = _delta(before, _counts())
    assert first == {
        ("misses", "image_states"): 1, ("misses", "image_row"): 1,
        ("misses", "taint_rows"): 1, ("misses", "taint_max"): 1,
        ("misses", "dom_idx"): 1, ("misses", "name_idx"): 1,
        ("misses", "statics_device"): 1, ("hits", "name_idx"): 3}
    steady = {("hits", "image_row"): 1, ("hits", "taint_rows"): 1,
              ("hits", "taint_max"): 1, ("hits", "dom_idx"): 1,
              ("hits", "name_idx"): 4, ("hits", "statics_device"): 1}
    for _ in range(3):
        before = _counts()
        prev = compile_workload(dep.nodes, [dep.measured_pod()],
                                bound_pods=_bound(dep),
                                namespaces=dep.namespaces, reuse=prev)
        assert _delta(before, _counts()) == steady


def test_eviction_past_the_cap_is_counted_and_rebuilt_equal(monkeypatch):
    monkeypatch.setattr(NodeDerived, "ROW_CAP", 4)
    dep = _deployment("sched_perf_basic_5k", nodes=30, initial=5)
    pods = []
    for k in range(6):
        pod = dep.measured_pod()
        pod["spec"]["tolerations"] = [
            {"key": "dedicated", "operator": "Equal", "value": f"v{k}",
             "effect": "NoSchedule"}]
        pods.append(pod)
    pods[5]["spec"]["tolerations"][0]["value"] = "batch"
    prev = compile_workload(dep.nodes, pods[:1], bound_pods=_bound(dep))
    first_rows = [np.asarray(a).copy() for a in prev.xs["TaintToleration"]]
    before = _counts()
    cw = compile_workload(dep.nodes, pods, bound_pods=_bound(dep), reuse=prev)
    moved = _delta(before, _counts())
    assert moved[("misses", "taint_rows")] == 5      # pods[0]'s row is warm
    assert moved[("hits", "taint_rows")] == 1
    assert moved[("evictions", "taint_rows")] == 2   # 6 rows, 4 kept
    assert ("evictions", "image_row") not in moved
    assert len(cw.node_table.derived._rows["taint_rows"]) == 4
    # the least recently used row went first: pods[0]'s is built again
    before = _counts()
    again = compile_workload(dep.nodes, pods[:1], bound_pods=_bound(dep),
                             reuse=cw)
    moved = _delta(before, _counts())
    assert moved[("misses", "taint_rows")] == 1
    assert moved[("evictions", "taint_rows")] == 1
    for want, got in zip(first_rows, again.xs["TaintToleration"]):
        np.testing.assert_array_equal(want, np.asarray(got))
    # distinct tolerations gave distinct rows: only pods[5] tolerates the
    # nodes' dedicated=batch taint
    code = np.asarray(cw.xs["TaintToleration"].filter_code)
    assert code[4].any() and not code[5].any()
    _same_workload(cw, _cold(dep, pods))


def test_two_threads_on_one_table_keep_the_memo_whole():
    """More threads than the cap has rows, a short switch interval: every
    lookup returns its own fragment's value and the cap holds."""
    derived = NodeDerived()
    errors: list = []
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def worker(seed: int):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(3000):
                k = int(rng.integers(0, 3 * NodeDerived.ROW_CAP))
                row = derived.row("dom_idx", k, lambda: np.full(4, k))
                assert int(row[0]) == k and not row.flags.writeable
        except Exception as e:  # noqa: BLE001 - reported by the assert below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(16)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert len(derived._rows["dom_idx"]) <= NodeDerived.ROW_CAP


# ---------------------------------------------------- (d) read-only rows


def test_memoised_values_are_read_only():
    dep = _deployment("sched_perf_podaffinity_5k", nodes=30, initial=5)
    cw = compile_workload(dep.nodes, [_varied(dep, 1)], bound_pods=_bound(dep),
                          namespaces=dep.namespaces)
    table = cw.node_table
    row, n_domains = table.domain_row(ZONE)
    assert n_domains == 1 and row.dtype == np.int32
    with pytest.raises(ValueError):
        row[0] = 7
    derived = table.derived
    (crow, prow), = derived._rows["taint_rows"].values()
    for arr in (crow, prow, *derived._rows["image_row"].values()):
        with pytest.raises(ValueError):
            arr[0] = 1
    with pytest.raises(TypeError):
        table.name_idx["intruder"] = 0
    with pytest.raises(TypeError):
        derived._values["image_states"]["intruder"] = (0, set())
    # the consumers copied: a workload's own blocks stay writable copies
    host_rows = cw.host["static_score_rows"]
    kept = prow.copy()
    host_rows["TaintToleration"][0, :] = 5
    np.testing.assert_array_equal(prow, kept)
    assert not np.shares_memory(host_rows["TaintToleration"], prow)
    assert not np.shares_memory(host_rows[imagelocality.NAME],
                                next(iter(derived._rows["image_row"].values())))


# -------------------------------------------- (e) the statics' device copies


def test_equal_digest_hands_back_the_same_device_arrays():
    dep = _deployment("sched_perf_antiaffinity_5k", nodes=40, initial=10)
    kw = dict(bound_pods=_bound(dep), namespaces=dep.namespaces)
    a = compile_workload(dep.nodes, [dep.measured_pod()], **kw)
    b = compile_workload(dep.nodes, [dep.measured_pod()], reuse=a, **kw)
    assert b.host["_statics_fp"] == a.host["_statics_fp"]
    # the closure statics; the argument statics (the volume family's) go
    # with xs and the carry
    la, lb = (jax.tree.leaves(split_statics(cw.statics)[0]) for cw in (a, b))
    assert len(la) == len(lb) and any(isinstance(x, jax.Array) for x in la)
    assert all(x is y for x, y in zip(la, lb))
    # xs, the carry and the argument statics are never kept: each pass
    # uploads its own
    assert a.arg_statics()
    assert not any(x is y for x, y in zip(
        jax.tree.leaves((a.init_carry, a.arg_statics())),
        jax.tree.leaves((b.init_carry, b.arg_statics()))))
    # a pod whose nodeSelector adds a NodeAffinity row does not change the
    # digest (the rows are argument statics, tests/test_affinity_arg_statics.py)
    # ...
    picky = dep.measured_pod()
    picky["spec"]["nodeSelector"] = {"kubernetes.io/os": "linux"}
    e = compile_workload(dep.nodes, [picky], reuse=b, **kw)
    assert e.host["_statics_fp"] == a.host["_statics_fp"]
    # ... nor does one whose spread constraint adds a dom_idx row (argument
    # statics too, since PR 52: tests/test_spread_affinity_taints_reference.py)
    spread = dep.measured_pod()
    spread["spec"]["topologySpreadConstraints"] = [{
        "maxSkew": 1, "topologyKey": ZONE,
        "whenUnsatisfiable": "ScheduleAnyway",
        "labelSelector": {"matchLabels": {"no": "pod"}}}]
    f = compile_workload(dep.nodes, [spread], reuse=b, **kw)
    assert f.host["_statics_fp"] == a.host["_statics_fp"]
    # ... one whose inter-pod term adds a dom_idx row over another key does
    other = dep.measured_pod()
    other["spec"]["affinity"] = {"podAffinity": {
        "preferredDuringSchedulingIgnoredDuringExecution": [{
            "weight": 1, "podAffinityTerm": {
                "topologyKey": ZONE,
                "labelSelector": {"matchLabels": {"no": "pod"}}}}]}}
    before = _counts()
    c = compile_workload(dep.nodes, [other], reuse=b, **kw)
    moved = _delta(before, _counts())
    assert c.node_table is a.node_table
    assert c.host["_statics_fp"] != a.host["_statics_fp"]
    assert moved[("misses", "statics_device")] == 1
    assert moved[("evictions", "statics_device")] == 1
    arrays = [x for x in la if isinstance(x, jax.Array)]
    assert not any(x is y for x in jax.tree.leaves(c.statics) for y in arrays)
    _same_workload(c, _cold(dep, [other]))
    # one generation only: the first digest is uploaded again
    d = compile_workload(dep.nodes, [dep.measured_pod()], reuse=c, **kw)
    assert d.host["_statics_fp"] == a.host["_statics_fp"]
    assert not any(x is y for x, y in zip(jax.tree.leaves(d.statics), la)
                   if isinstance(x, jax.Array))


# ------------------------------------------------- (f) one row a topology key


def test_topologyspread_and_interpod_share_one_domain_row():
    dep = _deployment("sched_perf_podaffinity_5k", nodes=30, initial=5)
    for j, node in enumerate(dep.nodes):
        node["metadata"]["labels"][ZONE] = f"zone{j % 3}"
    pod = dep.measured_pod()
    pod["spec"]["topologySpreadConstraints"] = [{
        "maxSkew": 1, "topologyKey": ZONE, "whenUnsatisfiable": "DoNotSchedule",
        "labelSelector": {"matchLabels": {"color": "blue"}}}]
    before = _counts()
    cw = compile_workload(dep.nodes, [pod], bound_pods=_bound(dep),
                          namespaces=dep.namespaces)
    moved = _delta(before, _counts())
    # PodTopologySpread builds first and misses; InterPodAffinity's term
    # over the same key is served the same row
    assert moved[("misses", "dom_idx")] == 1 and moved[("hits", "dom_idx")] == 1
    table = cw.node_table
    row, n_domains = table.domain_row(ZONE)
    assert table.domain_row(ZONE)[0] is row and n_domains == 3
    assert list(table.derived._rows["dom_idx"]) == [ZONE]
    st = cw.statics["PodTopologySpread"]
    np.testing.assert_array_equal(np.asarray(st.dom_idx)[0], row)
    # one row a KEY, on a padded axis: the pad row keys no node
    assert np.asarray(st.dom_idx).shape == (2, table.n)
    assert (np.asarray(st.dom_idx)[1] == -1).all()
    assert int(np.asarray(st.group_key)[0]) == 0
    assert not bool(np.asarray(st.is_ident)[0])
    np.testing.assert_array_equal(
        np.asarray(cw.statics["InterPodAffinity"].dom_idx)[0], row)
    # domains are numbered in node order of first appearance
    want, seen = [], {}
    for labels in table.labels:
        want.append(seen.setdefault(labels[ZONE], len(seen)))
    assert row.tolist() == want
