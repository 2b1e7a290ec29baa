"""The step with a live spread constraint and a tainted node table compiles
for the chip at the size `sched_perf_nodeinclusion_5k` runs it: 4,000 plain
and 1,000 tainted nodes under the default profile, pods spread one a
hostname with `nodeTaintsPolicy: Honor`: the skew check's `int64` `_BIG`
minima, the inclusion rows `[E, N]` a slot gathers, the fold of the
per-node `counts [C, N]` by domain (a `[Dp, N]` one-hot, the identity for
hostnames), the per-bind update, and TaintToleration's filter codes with
taints in them.  Compiled
here for a DESCRIBED v5e (the TPU's compiler is installed, no chip is
attached), as tests/test_tpu_compile_volumes.py and
test_tpu_compile_daemonset.py do for their families: what the chip's
compiler refuses (PR 36 met an int64 `dot`) shows without chip time.  A
compile that passes is not a chip run: no result and no time is read.

The topology is described inside a fixture (never at import time: only one
process may load the TPU's library, and every xdist worker imports every
test file), and the tests are skipped where it cannot be described.

Since PR 52 also BASELINE config 4's pass as `baseline_c4_queue_5k` serves
it: 30 differing pods on a bucket of 32 rows over 5,000 mixed nodes, two
constraints a pod (a zone's 8 domains folded through the `[Dp, N]`
one-hot, hostnames as the identity), the `ScheduleAnyway` weight gathered
from the float64 table by the feasible count, 64 count groups in the
carry; as the sequential scan and as the speculative rounds' dense
evaluation of 8 pods at once.
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

from generators import baseline_mixed_spread  # noqa: E402
from generators.scheduler_perf_node_pools import generate  # noqa: E402

from kube_scheduler_simulator_tpu.framework.pipeline import build_step  # noqa: E402
from kube_scheduler_simulator_tpu.framework.replay import (  # noqa: E402
    _att_plan, _compact_plan, _packed_scan_for)
from kube_scheduler_simulator_tpu.plugins import topologyspread  # noqa: E402
from kube_scheduler_simulator_tpu.scheduler.convert import parse_plugin_set  # noqa: E402
from kube_scheduler_simulator_tpu.state.compile import (  # noqa: E402
    compile_workload, split_statics)
from kube_scheduler_simulator_tpu.state.packed import Packed  # noqa: E402

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
    static = cw.statics["PodTopologySpread"]
    # Honor is a non-default inclusion policy: the slot gathers a row of
    # its own, which leaves the tainted pool out
    assert xs.elig_idx.shape == (2, MC)
    row = int(np.asarray(xs.elig_idx)[0, 0])
    assert row == 1 and np.asarray(static.elig_rows).shape == (4, N)
    assert int(np.asarray(static.elig_rows)[row].sum()) == N - TAINTED
    assert np.asarray(static.elig_rows)[0].all()
    assert not np.asarray(xs.filter_skip).any()
    assert np.asarray(xs.score_skip).all()
    # one topology key on the padded key axis, hostnames: the identity
    assert np.asarray(static.dom_idx).shape == (2, N)
    assert np.asarray(static.is_hostname).tolist() == [True, False]
    assert bool(np.asarray(static.is_ident)[0])
    assert np.asarray(static.dom_iota).shape == (8,)
    # the carry counts by NODE, on the padded group axis
    assert np.asarray(cw.init_carry["PodTopologySpread"]).shape == (4, N)
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

    def kernels(static, pod, counts, sel):
        code = topologyspread.filter_kernel(static, pod, counts)
        raw, ignored = topologyspread.score_kernel(static, pod, counts,
                                                   code == 0)
        normed = topologyspread.normalize(raw, ignored, code == 0)
        return code, normed, topologyspread.bind_update(static, pod, counts,
                                                        sel)

    sel = jax.ShapeDtypeStruct((), jnp.int32)
    compiled = jax.jit(kernels).lower(
        *_placed(one_chip, static, pod, counts, sel)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 20


# ------------------------------------------------- BASELINE config 4's pass


@pytest.fixture(scope="module")
def config4_pass():
    """One burst of `baseline_c4_queue_5k.rollout30_profile` at the cell's
    own size, 60 pods of the queue already bound."""
    params = json.loads(
        (BENCH / "configs/baseline_c4_queue_5k.json").read_text())["parameters"]
    dep = baseline_mixed_spread.generate(params, 2147483777)
    nodes = sorted(dep.nodes, key=lambda nd: nd["metadata"]["name"])
    bound = [(dep.measured_pod(), nodes[7 * j]["metadata"]["name"])
             for j in range(60)]
    pods = [dep.measured_pod() for _ in range(30)]
    return compile_workload(
        nodes, pods, parse_plugin_set(params["scheduler_configuration"]),
        bound_pods=bound)


def test_config4_build_is_on_its_buckets(config4_pass):
    cw = config4_pass
    assert cw.n_nodes == N and cw.pod_axis == 32
    st = cw.arg_statics()["PodTopologySpread"]
    assert st.dom_idx.shape == (2, N) and st.elig_rows.shape == (64, N)
    assert st.group_key.shape == (64,) and st.dom_iota.shape == (8,)
    assert st.log_table.shape == (N + 1,)
    assert cw.init_carry["PodTopologySpread"].shape == (64, N)
    xs = cw.xs["PodTopologySpread"]
    assert xs.pm.shape == (32, 64) and xs.elig_idx.shape == (32, MC)
    constrained = int((~np.asarray(xs.filter_skip)[:30]).sum())
    assert 10 <= constrained <= 26
    # the leaves of many long rows are arguments of the scan, not slices
    # of the pass's flat buffers (state/packed.py: a relayout a row in the
    # scan's executable, 61.6 s of compile against 19.4)
    own = [leaf for leaf in jax.tree.leaves(
        cw.packed.tree[:3], is_leaf=lambda x: isinstance(x, Packed))
        if not isinstance(leaf, Packed)]
    assert sorted(leaf.shape for leaf in own) == (
        [(32, N)] * 2 + [(64, N)] * 4)


@pytest.mark.parametrize("route", ["sequential_scan", "dense_round_of_8",
                                   "packed_scan"])
def test_config4_pass_compiles_for_v5e_at_5000_nodes(route, config4_pass,
                                                     one_chip,
                                                     no_persistent_cache):
    cw = config4_pass
    pack_mode, score_dtypes, score_cols = _compact_plan(cw, None)
    if route == "packed_scan":
        # the served pass's ONE call, as SchedulerEngine._device_wave asks
        # for it (a pass of one chunk is not unrolled)
        scan, args = _packed_scan_for(
            cw, 1, pack_mode, score_dtypes, None,
            _att_plan(cw, pack_mode, score_cols))
        compiled = scan.lower(*_placed(one_chip, *args)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20
        return
    closure, args = split_statics(cw.statics)
    closure = jax.tree.map(np.asarray, closure)

    def step_of(arg_statics):
        view = SimpleNamespace(
            config=cw.config, n_nodes=cw.n_nodes, schema=cw.schema,
            statics={**jax.tree.map(jnp.asarray, closure), **arg_statics})
        return build_step(view, out_mode="compact", pack_mode=pack_mode,
                          score_dtypes=score_dtypes)

    if route == "sequential_scan":
        def run(carry, xs, arg_statics):
            return jax.lax.scan(step_of(arg_statics), carry, xs)
        xs = cw.xs
    else:
        def run(carry, xs, arg_statics):
            step = step_of(arg_statics)
            return jax.vmap(lambda c, sl: step(c, sl)[1],
                            in_axes=(None, 0))(carry, xs)
        xs = jax.tree.map(lambda a: a[:8], cw.xs)

    compiled = jax.jit(run).lower(
        *_placed(one_chip, cw.init_carry, xs, args)).compile()
    # [Dp, N] one-hots and [N] rows a pod: tens of MB at most for 8 pods
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20
