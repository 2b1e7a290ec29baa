"""Multi-chip scale-out: shard the node axis over a device mesh.

The reference scales Filter/Score across nodes with 16 goroutines inside
one process (SURVEY.md §2.6); there is no distributed backend to mirror.
The TPU-native scale-out instead follows the scaling-book recipe: pick a
mesh, annotate shardings, let XLA insert the collectives.

Axes:
  "nodes" — the cluster-node axis (the domain's sequence length; SURVEY.md
            §5 long-context note).  All [N]-shaped and [.., N] tensors are
            sharded over it; per-node filter/score math is embarrassingly
            parallel, and the only cross-shard traffic XLA must insert is
            the argmax/max/min reductions of host selection and score
            normalization (all-reduce over ICI).
  "dp"    — pod-batch axis.  Scheduling is sequential across pods (each
            bind mutates state), but scoring a *batch* of queued pods
            against the same frozen state is pure fan-out; batched_step
            vmaps over the batch and shards it over "dp".  The scan
            replicates over it.

Domain-count carries (counts[C, D], interpod [T, D]) are small and stay
replicated; their scatter updates are cheap everywhere.

This module is exercised single-host with N virtual CPU devices
(--xla_force_host_platform_device_count) and by the driver's
dryrun_multichip; on real multi-chip hardware the same code lays the node
axis over ICI unchanged — that is the point of jax.sharding.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..framework.pipeline import build_step
from ..state.compile import CompiledWorkload


def make_mesh(n_devices: int | None = None, dp: int = 1) -> Mesh:
    devices = jax.devices()
    n = n_devices or len(devices)
    if n > len(devices):
        raise ValueError(f"asked for {n} devices, only {len(devices)} present")
    if dp < 1:
        raise ValueError(f"dp must be >= 1, got {dp}")
    if n % dp:
        # the reshape below would otherwise fail with an opaque numpy
        # shape error (or, for a floor-divided node count, silently drop
        # devices off the mesh) — name the actual constraint instead
        raise ValueError(
            f"n_devices ({n}) must divide evenly by dp ({dp}): a "
            f"(dp={dp}) x (nodes={n}/{dp}) mesh is not integral — pick a "
            f"dp that divides the device count")
    nodes = n // dp
    arr = np.array(devices[:n]).reshape(dp, nodes)
    return Mesh(arr, axis_names=("dp", "nodes"))


def _node_axis_spec(x, n_nodes: int, skip_leading: bool):
    """PartitionSpec sharding the node axis over "nodes".

    skip_leading: xs tensors carry the pod axis first — it must never be
    mistaken for the node axis even when n_pods == n_nodes.  Domain axes D
    equal to N only happen for hostname topology keys, where domains ARE
    nodes, so sharding them is correct.
    """
    if not hasattr(x, "ndim"):
        return P()
    spec: list[Any] = [None] * x.ndim
    for d in range(1 if skip_leading else 0, x.ndim):
        if x.shape[d] == n_nodes:
            spec[d] = "nodes"
            break  # shard one axis only
    return P(*spec)


def gather_to_host(x) -> np.ndarray:
    """One replay output as a contiguous C-order host array — the single
    device->host crossing for device-resident results (framework/replay.py
    `_CompactChunks.materialize`).  Sharded arrays (a wave run on a mesh)
    gather their node-axis shards here, and accelerator fetches that
    arrive with device strides are re-laid C-order because the native
    codec walks raw pointers assuming C layout."""
    return np.ascontiguousarray(np.asarray(x))


def can_shard(n_nodes: int, mesh: Mesh | None) -> bool:
    """Whether shard_workload accepts this node count on this mesh — the
    single divisibility predicate shared with callers that degrade to an
    unsharded replay instead of erroring (the engine's live waves: a real
    cluster's node count need not divide the mesh)."""
    if mesh is None:
        return False
    shards = mesh.shape.get("nodes", 1)
    return shards <= 1 or n_nodes % shards == 0


def shard_workload(cw: CompiledWorkload, mesh: Mesh) -> CompiledWorkload:
    """A copy of `cw` with statics/xs/carry placed node-axis-sharded over
    the mesh (the input workload is left untouched so unsharded replays of
    the same object stay genuinely unsharded)."""
    import dataclasses

    n = cw.n_nodes
    shards = mesh.shape.get("nodes", 1)
    if shards > 1 and n % shards:
        raise ValueError(
            f"node axis ({n}) must divide evenly across the mesh's "
            f"'nodes' extent ({shards}); pick a divisor shard count")

    def place(skip_leading):
        def f(x):
            if not hasattr(x, "ndim"):
                return x
            return jax.device_put(x, NamedSharding(mesh, _node_axis_spec(x, n, skip_leading)))

        return f

    return dataclasses.replace(
        cw,
        statics=jax.tree.map(place(False), cw.statics),
        xs=jax.tree.map(place(True), cw.xs),
        init_carry=jax.tree.map(place(False), cw.init_carry),
    )


def sharded_step(cw: CompiledWorkload, mesh: Mesh | None = None):
    """jit the fused scheduling step with node-sharded inputs.

    GSPMD propagates the input shardings laid down by shard_workload:
    elementwise/gather work stays local to each node shard; the
    feasible-count sum, normalize max/min and select argmax lower to
    all-reduces over the "nodes" axis.  (mesh is accepted for symmetry
    with shard_workload; placement travels with the arrays.)
    """
    step = build_step(cw)
    return jax.jit(step)


def batched_step(cw: CompiledWorkload, mesh: Mesh | None = None):
    """Batched what-if evaluation: score a pod minibatch against one
    frozen state, binding nothing.  Returns f(carry, xs_batch) -> StepOut
    batch; used by the dp shard of the multi-chip dry run
    (__graft_entry__.py).

    With a mesh, the minibatch axis is explicitly placed over "dp" (and
    inner node axes over "nodes") before the call, so each dp slice of the
    mesh evaluates its own pods against the replicated-carry state.
    """
    step = build_step(cw)
    n = cw.n_nodes

    def eval_only(carry, sl):
        _, out = step(carry, sl)
        return out

    batched = jax.jit(jax.vmap(eval_only, in_axes=(None, 0)))
    if mesh is None:
        return batched

    def place_batch(x):
        if not hasattr(x, "ndim") or x.ndim == 0:
            return x
        inner = _node_axis_spec(x[0], n, skip_leading=False)
        return jax.device_put(x, NamedSharding(mesh, P("dp", *inner)))

    def run(carry, xs_batch):
        return batched(carry, jax.tree.map(place_batch, xs_batch))

    return run


def initialize_distributed(coordinator_address: str | None = None,
                           num_processes: int | None = None,
                           process_id: int | None = None) -> None:
    """Multi-host entry: start the JAX distributed runtime so
    jax.devices() returns the GLOBAL device set, after which make_mesh
    lays the "nodes" axis across hosts unchanged — XLA's collectives
    ride ICI within a slice and DCN across slices (the scaling-book
    recipe; the reference has no distributed backend to mirror,
    SURVEY.md §2.6/§5).

    All arguments default from the standard JAX environment
    (JAX_COORDINATOR_ADDRESS / processes / id set by the launcher);
    call once per process before any jax computation."""
    kwargs: dict = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)
