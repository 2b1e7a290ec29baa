"""The step with a PreFilterResult compiles for the chip at the size
`sched_perf_daemonset_15k` runs it: 15,001 nodes under the default profile,
one pass that holds a narrowed pod (the considered-nodes mask from its
`pf_nodes` row, the not-evaluated word in the packed layout) and an
un-narrowed one.  Compiled here for a DESCRIBED v5e (the TPU's compiler is
installed, no chip is attached), as tests/test_tpu_compile_volumes.py does
for the volume family: what the chip's compiler refuses shows without chip
time.  A compile that passes is not a chip run: no result and no time is
read.  Since PR 44 also the executable the cell actually calls: the scan
over the pass's packed buffers (framework/replay.py _packed_scan_for),
with every leaf cut out of its dtype's buffer, the carry with them, and
the attribution reduction inside it; since PR 49 also the packing of the
decision fields and the attribution sums into one int32 row.

The topology is described inside a fixture (never at import time: only one
process may load the TPU's library, and every xdist worker imports every
test file), and the test is skipped where it cannot be described.
"""

from __future__ import annotations

import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from kube_scheduler_simulator_tpu.framework.pipeline import build_step
import sys

from kube_scheduler_simulator_tpu.framework.replay import _compact_plan
from kube_scheduler_simulator_tpu.state.compile import (
    compile_workload, split_statics)

replay_mod = sys.modules["kube_scheduler_simulator_tpu.framework.replay"]

N = 15000  # + the named node


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _node(name: str) -> dict:
    res = {"pods": "110", "cpu": "4", "memory": "32Gi"}
    return {"apiVersion": "v1", "kind": "Node", "metadata": {"name": name},
            "spec": {}, "status": {"capacity": res, "allocatable": dict(res)}}


def _pod(name: str, node: str | None) -> dict:
    spec: dict = {"containers": [{"name": "pause", "image": "pause", "resources": {
        "requests": {"cpu": "100m", "memory": "500Mi"}}}]}
    if node is not None:
        spec["affinity"] = {"nodeAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": {
                "nodeSelectorTerms": [{"matchFields": [{
                    "key": "metadata.name", "operator": "In",
                    "values": [node]}]}]}}}
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": "default"}, "spec": spec}


def test_narrowed_step_compiles_for_v5e_at_15001_nodes(one_chip,
                                                       no_persistent_cache):
    nodes = [_node(f"node-{i:05d}") for i in range(N)]
    nodes.append(_node("scheduler-perf-node"))
    cw = compile_workload(nodes, [_pod("narrowed", "scheduler-perf-node"),
                                  _pod("plain", None)])
    assert cw.n_nodes == N + 1
    assert cw.xs["NodeAffinity"].pf_nodes.shape == (2, 1)
    pack_mode, score_dtypes, _ = _compact_plan(cw, None)
    # the closure statics as host constants (a described device holds no
    # array); xs, carry and the argument statics as shapes on the chip
    closure, args = split_statics(cw.statics)
    closure = jax.tree.map(np.asarray, closure)

    def scan_chunk(carry, xs, arg_statics):
        view = SimpleNamespace(
            config=cw.config, n_nodes=cw.n_nodes, schema=cw.schema,
            statics={**jax.tree.map(jnp.asarray, closure), **arg_statics})
        step = build_step(view, out_mode="compact", pack_mode=pack_mode,
                          score_dtypes=score_dtypes)
        return jax.lax.scan(step, carry, xs)

    placed = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (cw.init_carry, cw.xs, args))
    compiled = jax.jit(scan_chunk).lower(*placed).compile()
    # nothing of the step is [K, N] wider than its inputs: the mask is one
    # compare against the row's one index
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * (N + 1) * 8


def test_packed_scan_compiles_for_v5e_at_15001_nodes(one_chip,
                                                     no_persistent_cache,
                                                     monkeypatch):
    """A pass of three pods, one chunk: every leaf cut out of its dtype's
    buffer (the int64 one in its emulated layout), the carry with them,
    the scan and the attribution reduction in one executable."""
    nodes = [_node(f"node-{i:05d}") for i in range(N)]
    nodes.append(_node("scheduler-perf-node"))
    cw = compile_workload(nodes, [_pod("narrowed", "scheduler-perf-node"),
                                  _pod("plain", None),
                                  _pod("too", "scheduler-perf-node")])
    assert cw.packed is not None and set(cw.packed.bufs) >= {"bool", "int64"}
    # the closure statics as host constants (a described device holds no
    # array), made jax constants where the step is traced
    cw.statics = jax.tree.map(np.asarray, cw.closure_statics())
    with_args = replay_mod._SlimWorkload.with_args

    def traced_constants(self, arg_statics):
        view = with_args(self, arg_statics)
        view.statics = {**jax.tree.map(jnp.asarray, self.statics),
                        **arg_statics}
        return view

    monkeypatch.setattr(replay_mod._SlimWorkload, "with_args",
                        traced_constants)
    pack_mode, score_dtypes, score_cols = _compact_plan(cw, None)
    scan, (bufs, rest) = replay_mod._packed_scan_for(
        cw, 1, pack_mode, score_dtypes, None,
        replay_mod._att_plan(cw, pack_mode, score_cols))
    assert not rest
    placed = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), bufs)
    compiled = scan.fn.lower(placed, rest).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * (N + 1) * 8
    # five output buffers since PR 49: the four heavy tensors and the one
    # int32 row of everything the pass fetches (its int64 filter counts
    # bitcast to words in their emulated layout, the feasibility bitmap's
    # 1,876 bytes a pod to 469)
    heavy_and_row = jax.tree.leaves(compiled.out_info)
    assert len(heavy_and_row) == 5
    words = sum(-(-int(np.prod(shape)) * np.dtype(dt).itemsize // 4)
                for _, dt, shape in scan.row_layout)
    assert (heavy_and_row[4].shape, heavy_and_row[4].dtype) == (
        (words,), jnp.int32)
