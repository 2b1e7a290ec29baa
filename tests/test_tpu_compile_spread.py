"""The step with a live spread constraint and a tainted node table compiles
for the chip at the size `sched_perf_nodeinclusion_5k` runs it: 4,000 plain
and 1,000 tainted nodes under the default profile, pods spread one a
hostname with `nodeTaintsPolicy: Honor`: the skew check's `int64` `_BIG`
minima, the per-slot `[P, MC, N]` eligibility, the per-bind `counts [C, N]`
update, and TaintToleration's filter codes with taints in them.  Compiled
here for a DESCRIBED v5e (the TPU's compiler is installed, no chip is
attached), as tests/test_tpu_compile_volumes.py and
test_tpu_compile_daemonset.py do for their families: what the chip's
compiler refuses (PR 36 met an int64 `dot`) shows without chip time.  A
compile that passes is not a chip run: no result and no time is read.

The topology is described inside a fixture (never at import time: only one
process may load the TPU's library, and every xdist worker imports every
test file), and the tests are skipped where it cannot be described.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))

from generators.scheduler_perf_node_pools import generate  # noqa: E402

from kube_scheduler_simulator_tpu.framework.pipeline import build_step  # noqa: E402
from kube_scheduler_simulator_tpu.framework.replay import _compact_plan  # noqa: E402
from kube_scheduler_simulator_tpu.plugins import topologyspread  # noqa: E402
from kube_scheduler_simulator_tpu.state.compile import (  # noqa: E402
    compile_workload, split_statics)

PARAMS = json.loads(
    (BENCH / "configs/sched_perf_nodeinclusion_5k.json").read_text())["parameters"]
N, TAINTED, MC = 5000, 1000, topologyspread.MAX_CONSTRAINTS


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


@pytest.fixture(scope="module")
def workload():
    """The cell's cluster at its own size, a few hostnames taken, and one
    pass of two measured pods."""
    dep = generate(PARAMS, 2147483777)
    nodes = sorted(dep.nodes, key=lambda nd: nd["metadata"]["name"])
    bound = [(dep.measured_pod(), nodes[j]["metadata"]["name"])
             for j in range(3)]
    pods = [dep.measured_pod(), dep.measured_pod()]
    return compile_workload(nodes, pods, None, bound_pods=bound)


def _placed(sharding, *trees):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        trees)


def test_the_build_is_live_at_the_cells_size(workload):
    cw = workload
    assert cw.n_nodes == N
    xs = cw.xs["PodTopologySpread"]
    # Honor is a non-default inclusion policy: the eligibility has a slot axis
    assert xs.eligible.shape == (2, MC, N)
    assert int(np.asarray(xs.eligible)[0, 0].sum()) == N - TAINTED
    assert not np.asarray(xs.filter_skip).any()
    assert np.asarray(xs.score_skip).all()
    static = cw.statics["PodTopologySpread"]
    assert np.asarray(static.dom_idx).shape == (1, N)       # one count group
    assert int(np.asarray(cw.init_carry["PodTopologySpread"]).sum()) == 3
    code = np.asarray(cw.xs["TaintToleration"].filter_code)
    assert code.shape == (2, N) and int((code > 0).sum()) == 2 * TAINTED


def test_spread_step_compiles_for_v5e_at_5000_nodes(workload, one_chip,
                                                    no_persistent_cache):
    cw = workload
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

    compiled = jax.jit(scan_chunk).lower(
        *_placed(one_chip, cw.init_carry, cw.xs, args)).compile()
    # the step's temporaries are [N] rows (4.1 MB at this size), nothing
    # [N, N]: the hostname domains are compared row against column value
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 20


def test_spread_kernels_compile_for_v5e_alone(workload, one_chip,
                                              no_persistent_cache):
    """The Filter's skew check (int64 minima over the eligible keyed
    nodes), the score with its normalisation and the bind's elementwise
    carry update, without the rest of the step: a refusal here names the
    family, not the whole step."""
    cw = workload
    static = jax.tree.map(np.asarray, cw.statics["PodTopologySpread"])
    pod = jax.tree.map(lambda a: a[0], cw.xs["PodTopologySpread"])
    counts = cw.init_carry["PodTopologySpread"]

    def kernels(pod, counts, sel):
        st = topologyspread.SpreadStatic(
            dom_idx=jnp.asarray(static.dom_idx), n_groups=static.n_groups)
        code = topologyspread.filter_kernel(st, pod, counts)
        raw, ignored = topologyspread.score_kernel(st, pod, counts)
        normed = topologyspread.normalize(raw, ignored, code == 0)
        return code, normed, topologyspread.bind_update(st, pod, counts, sel)

    sel = jax.ShapeDtypeStruct((), jnp.int32)
    compiled = jax.jit(kernels).lower(
        *_placed(one_chip, pod, counts, sel)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 20
