"""The volume family's kernels compile for the chip at the widths
`sched_perf_csipvs_5k` runs them (N = 5,000 nodes, V = C = 8,192 padded
PVs / CSI volumes): compiled here for a DESCRIBED v5e (the TPU's compiler is
installed, no chip is attached), which is where what the chip's compiler
refuses shows without chip time.  PR 36 met one such refusal on the chip:
NodeVolumeLimits counted per-driver volumes with an int64 matmul, and the
TPU compiler's X64 rewriting has no 64-bit dot ("UNIMPLEMENTED ... dot").
A compile that passes is not a chip run: no result and no time is read.

The topology is described inside a fixture (never at import time: only one
process may load the TPU's library, and every xdist worker imports every
test file), and the tests are skipped where it cannot be described.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from kube_scheduler_simulator_tpu.plugins import nodevolumelimits, volumebinding
from kube_scheduler_simulator_tpu.state import resident
from kube_scheduler_simulator_tpu.state.packed import pack_tree

N, V, C, D = 5000, 8192, 8192, 1


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


_s = jax.ShapeDtypeStruct


def _compile(fn, sharding, *trees):
    placed = jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct(sd.shape, sd.dtype, sharding=sharding),
        trees)
    return jax.jit(fn).lower(*placed).compile()


def test_node_volume_limits_kernels_compile_for_v5e(one_chip, no_persistent_cache):
    static = nodevolumelimits.LimitsStatic(
        driver_onehot=_s((C, D), jnp.bool_), limits=_s((N, D), jnp.int64))
    xs = nodevolumelimits.LimitsXS(
        pod_vols=_s((C,), jnp.bool_), filter_skip=_s((), jnp.bool_))
    carry = nodevolumelimits.LimitsCarry(on_node=_s((N, C), jnp.bool_))

    def step(static, xs, carry, selected):
        code = nodevolumelimits.filter_kernel(static, xs, carry)
        return code, nodevolumelimits.bind_update(xs, carry, selected)

    compiled = _compile(step, one_chip, static, xs, carry, _s((), jnp.int32))
    # the per-driver counts are a fused masked sum: no [N, C] int64 copy
    assert compiled.memory_analysis().temp_size_in_bytes < N * C * 8


def test_volume_binding_kernels_compile_for_v5e(one_chip, no_persistent_cache):
    k = 1   # one unbound WaitForFirstConsumer claim: the greedy matcher runs
    static = volumebinding.BindingStatic(
        pv_cap=_s((V,), jnp.int64), pv_node_ok=_s((V, N), jnp.bool_))
    xs = volumebinding.BindingXS(
        bound_code=_s((N,), jnp.int32), want=_s((k, V), jnp.bool_),
        active=_s((k,), jnp.bool_), provision_ok=_s((k, N), jnp.bool_),
        filter_skip=_s((), jnp.bool_))
    carry = volumebinding.BindingCarry(claimed=_s((V,), jnp.bool_))

    def step(static, xs, carry, selected):
        code = volumebinding.filter_kernel(static, xs, carry)
        return code, volumebinding.bind_update(static, xs, carry, selected)

    _compile(step, one_chip, static, xs, carry, _s((), jnp.int32))


@pytest.mark.parametrize("kept, shape", [
    (resident.RowsResident, (V, N)),      # pv_node_ok: old[src], fresh rows set
    (resident.CellsResident, (N, C)),     # on_node: cells set
])
def test_the_resident_patches_compile_for_v5e(kept, shape, one_chip,
                                              no_persistent_cache):
    """state/resident.py's two patches at the cell's shapes: a new array
    of the old one's size and nothing beside it (no donation, no
    cluster-sized temporary)."""
    like = _s(shape, jnp.bool_)
    # the payload as it rides in a pass's packed buffers (here alone in
    # them): the patch cuts it out inside its own executable
    payload = kept()._payload(np.zeros(shape, np.bool_))
    packed = pack_tree(payload)
    run, bufs = resident._patch_program(
        kept.patch_fn, packed.layout, type(payload),
        tuple(leaf.k for leaf in packed.tree))
    compiled = _compile(run, one_chip, like, bufs)
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == V * N
    assert memory.alias_size_in_bytes == 0
    assert memory.temp_size_in_bytes < V * N
