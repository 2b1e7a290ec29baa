"""PostFilter's static rule against the kernel it restates
(plugins/noderesources.py): `fit_refuses_empty`, in host numpy over the
arrays compile_workload hands over in cw.host["fit"], equals
`fit_filter(...) != 0` on a CoreCarry of zeros, node for node — and, since
the carry only ever takes room away, a node it refuses is refused on the
pass's real carry too.  `Preemptor._hopeless` is that mask as the names of
the nodes asked about, and empty where NodeResourcesFit did not run for
the pod."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from kube_scheduler_simulator_tpu.framework.preemption import Preemptor
from kube_scheduler_simulator_tpu.plugins.base import CoreCarry
from kube_scheduler_simulator_tpu.plugins.noderesources import (
    FitPodXS, fit_filter, fit_refuses_empty)
from kube_scheduler_simulator_tpu.plugins.registry import (
    PluginSetConfig, default_plugin_names)
from kube_scheduler_simulator_tpu.state.compile import compile_workload

GPU = "example.com/gpu"


def _node(name: str, cpu, mem="16Gi", pods="8", **extended) -> dict:
    alloc = {"cpu": str(cpu), "memory": mem, "pods": str(pods)}
    alloc.update({k.replace("__", "/").replace("_", "."): str(v)
                  for k, v in extended.items()})
    return {"metadata": {"name": name}, "status": {"allocatable": alloc}}


def _pod(name: str, node: str | None = None, **requests) -> dict:
    req = {k.replace("__", "/").replace("_", "."): str(v)
           for k, v in requests.items()}
    p = {"metadata": {"name": name, "namespace": "default"},
         "spec": {"containers": [{"name": "c",
                                  "resources": {"requests": req}}]}}
    if node:
        p["spec"]["nodeName"] = node
    return p


def _seeded_nodes(seed: int) -> list[dict]:
    """Nodes of 1-16 CPU and 1-32 Gi, 0-12 pods; a third with GPUs, a
    third with FPGAs."""
    rng = np.random.default_rng(seed)
    nodes = []
    for j in range(int(rng.integers(12, 24))):
        ext = {}
        if j % 3 == 0:
            ext["example_com__gpu"] = int(rng.integers(0, 5))
        if j % 3 == 1:
            ext["vendor_io__fpga"] = int(rng.integers(1, 3))
        nodes.append(_node(f"n{j:02d}", int(rng.integers(1, 17)),
                           f"{int(rng.integers(1, 33))}Gi",
                           int(rng.integers(0, 13)), **ext))
    return nodes


def _fill(nodes: list[dict], seed: int) -> list[tuple[dict, str]]:
    """1-CPU bound pods, two or three to a node: more than a 1-CPU node
    can hold, so some nodes are overcommitted."""
    rng = np.random.default_rng(seed + 1)
    return [(_pod(f"b{j}-{i}", n["metadata"]["name"], cpu=1, memory="1Gi"),
             n["metadata"]["name"])
            for j, n in enumerate(nodes) for i in range(int(rng.integers(2, 4)))]


PODS = [
    _pod("small", cpu="500m", memory="512Mi"),
    _pod("wide", cpu=9, memory="1Gi"),
    _pod("deep", cpu=1, memory="24Gi"),
    _pod("nothing"),                                   # the zero-request pod
    _pod("gpu", cpu=1, example_com__gpu=2),
    _pod("fpga", cpu=12, vendor_io__fpga=1),
    _pod("both", example_com__gpu=1, vendor_io__fpga=3),
]

CASES = {
    "mixed-sizes": ({}, 7),
    "other-seed": ({}, 2147483777),
    "ignored-resource": ({"ignoredResources": [GPU]}, 11),
    "ignored-group": ({"ignoredResourceGroups": ["vendor.io"]}, 13),
    "ignored-both": ({"ignoredResources": [GPU],
                      "ignoredResourceGroups": ["vendor.io"]}, 17),
    # cpu is native: naming it changes nothing (upstream skips only
    # extended resources)
    "ignored-native": ({"ignoredResources": ["cpu"]}, 19),
}


def _on_device(cw, i: int, carry: CoreCarry) -> np.ndarray:
    core = cw.xs["core"]
    pod = FitPodXS(requests=core.requests[i], nonzero=core.nonzero[i])
    return np.asarray(fit_filter(cw.statics["core"], pod, carry)) != 0


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_host_rule_is_fit_filter_on_a_zero_carry(case):
    fit_args, seed = CASES[case]
    nodes = _seeded_nodes(seed)
    cfg = PluginSetConfig(args={"NodeResourcesFit": fit_args})
    cw = compile_workload(nodes, PODS, cfg, bound_pods=_fill(nodes, seed))
    static, requests = cw.host["fit"]
    assert isinstance(static.allocatable, np.ndarray), "read from the device"
    assert (static.allowed_pods == 0).any() or case != "mixed-sizes"
    real = cw.init_carry["core"]
    zero = CoreCarry(*(jnp.zeros_like(a) for a in real))
    assert (np.asarray(real.requested) > static.allocatable).any(), \
        "no overcommitted node: the cluster tests less than it says"
    seen = set()
    for i, pod in enumerate(PODS):
        rule = fit_refuses_empty(static, requests[i])
        assert rule.dtype == bool and rule.shape == (len(nodes),)
        np.testing.assert_array_equal(rule, _on_device(cw, i, zero),
                                      err_msg=pod["metadata"]["name"])
        # the proof's other half: what the empty node refuses, the node
        # as it stands refuses
        assert not (rule & ~_on_device(cw, i, real)).any()
        names = {n for n, no in zip(cw.node_table.names, rule) if no}
        assert Preemptor._hopeless((cw, i), cw.node_table.names) == names
        seen.add((bool(rule.any()), bool((~rule).any())))
    assert seen >= {(True, True)}, "every verdict alike: nothing is told apart"


def test_a_zero_request_pod_is_refused_only_by_the_pod_count():
    nodes = [_node("full", 0, "0", 0), _node("tiny", 0, "0", 1),
             _node("roomy", 8)]
    cw = compile_workload(nodes, [_pod("nothing"), _pod("some", cpu=1)])
    static, requests = cw.host["fit"]
    assert fit_refuses_empty(static, requests[0]).tolist() == [True, False, False]
    assert fit_refuses_empty(static, requests[1]).tolist() == [True, True, False]


def test_an_extended_resource_no_node_advertises_refuses_every_node():
    nodes = [_node("a", 8), _node("b", 8, example_com__gpu=1)]
    pods = [_pod("fpga", vendor_io__fpga=1)]
    cw = compile_workload(nodes, pods)
    assert Preemptor._hopeless((cw, 0), ["a", "b", "gone"]) == {"a", "b"}
    ignoring = PluginSetConfig(args={"NodeResourcesFit": {
        "ignoredResourceGroups": ["vendor.io"]}})
    cw = compile_workload(nodes, pods, ignoring)
    assert Preemptor._hopeless((cw, 0), cw.node_table.names) == set()


def test_no_node_is_hopeless_where_fit_does_not_run():
    nodes = [_node("tiny", 1), _node("roomy", 16)]
    pods = [_pod("wide", cpu=9)]
    cw = compile_workload(nodes, pods)
    assert Preemptor._hopeless((cw, 0), cw.node_table.names) == {"tiny"}
    off = PluginSetConfig(enabled=[
        n for n in default_plugin_names() if n != "NodeResourcesFit"])
    cw = compile_workload(nodes, pods, off)
    assert "NodeResourcesFit" not in cw.config.filters()
    static, requests = cw.host["fit"]   # the arrays are there all the same
    assert fit_refuses_empty(static, requests[0]).tolist() == [True, False]
    assert Preemptor._hopeless((cw, 0), cw.node_table.names) == set()
    at_filter = PluginSetConfig(point_disabled={"filter": {"NodeResourcesFit"}})
    cw = compile_workload(nodes, pods, at_filter)
    assert Preemptor._hopeless((cw, 0), cw.node_table.names) == set()
    assert Preemptor._hopeless(None, ["tiny"]) == set()
