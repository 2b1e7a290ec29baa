"""Plugin kernel protocol.

A *plugin* in the reference is a Go object implementing some of the 12
scheduling-framework extension points, wrapped by the recording shim
(reference: simulator/scheduler/plugin/wrappedplugin.go:253-364).  Here a
plugin is a module of pure tensor kernels evaluated over ALL nodes at once:

    filter_kernel(static, pod_xs, carry)  -> codes  [N] int32  (0 == pass)
    score_kernel (static, pod_xs, carry)  -> raw    [N] int64
    normalize    (raw, feasible)          -> normed [N] int64   (ScoreExtensions)
    bind_update  (static, pod_xs, own_carry, sel)   -> own_carry

plus a host-side `build()` that precompiles the workload into the static /
per-pod arrays, and `decode_filter()` that maps a failure code back to the
exact status message the reference would have recorded
(e.g. "Insufficient cpu", wrappedplugin.go:523-548 records
status.Message(); pass records "passed", resultstore/store.go:27-28).

The scheduling cycle composes these python-side at trace time, so XLA sees
one fused program per pod step; there is no plugin dispatch on device.

A PreFilter that narrows the cycle (upstream framework.PreFilterResult:
"run Filter on these node names only") hands the framework, per pod, the
names as a row of node indices: its xs carries a leaf `pf_nodes`
([P, K] int32, `prefilter_rows`), and its build leaves the names
themselves in host_out["prefilter_result"][plugin] for the annotation.
The framework intersects the plugins' rows (PreFilterResult.Merge) in
framework/pipeline.py `considered_nodes`.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

MAX_NODE_SCORE = 100  # upstream framework.MaxNodeScore

# a `pf_nodes` row: PF_ALL in every slot = the plugin returned no
# PreFilterResult for the pod (upstream AllNodes()); else node indices,
# padded with PF_PAD (a name that is no node leaves only padding)
PF_ALL = -2
PF_PAD = -1


def prefilter_rows(names_by_pod: list, table) -> np.ndarray:
    """Per-pod PreFilterResult node names -> the [P, K] int32 `pf_nodes`
    leaf.  names_by_pod[i] is None (all nodes) or an iterable of node
    names; a name the node table (state/nodes.py NodeTable) does not hold
    is dropped, as upstream's findNodesThatFitPod drops it.  K is 0 when
    no pod is narrowed (the step then has no considered-nodes work at
    all), else the power of two >= the longest row: a queue that names
    one node a pod is one shape whatever the names are.  (Where K
    happens to equal the node count, the shape rule that finds a leaf's
    node axis by its extent — parallel/mesh.py — takes this one for
    node-sized: the mesh then shards it, which changes no value.)"""
    if all(names is None for names in names_by_pod):
        return np.zeros((len(names_by_pod), 0), dtype=np.int32)
    name_idx = table.name_idx
    rows = [None if names is None else
            sorted(j for j in (name_idx.get(nm) for nm in names)
                   if j is not None)
            for names in names_by_pod]
    longest = max(len(r) for r in rows if r is not None)
    k = 1 << max(longest - 1, 0).bit_length()
    out = np.full((len(rows), k), PF_ALL, dtype=np.int32)
    for i, r in enumerate(rows):
        if r is not None:
            out[i] = PF_PAD
            out[i, :len(r)] = r
    return out


class CoreCarry(NamedTuple):
    """Shared device-side mutable cluster state (the scan carry core).

    Mirrors upstream NodeInfo accumulators: Requested (actual requests, the
    Filter path), NonZeroRequested (scoring path, 100m/200Mi defaults) and
    the pod count.
    """

    requested: jnp.ndarray   # [N, R] int64
    nonzero: jnp.ndarray     # [N, 2] int64  (cpu milli, memory bytes)
    num_pods: jnp.ndarray    # [N] int64


def default_normalize_score(raw, feasible, reverse: bool):
    """upstream helper.DefaultNormalizeScore (int64 exact), computed over
    the feasible-node subset only (the framework only scores nodes that
    passed all filters)."""
    raw = raw.astype(jnp.int64)
    masked = jnp.where(feasible, raw, 0)
    max_count = jnp.max(masked)
    safe_max = jnp.maximum(max_count, 1)
    scaled = raw * MAX_NODE_SCORE // safe_max
    if reverse:
        scaled = MAX_NODE_SCORE - scaled
        # maxCount == 0: all scores set to maxPriority
        return jnp.where(max_count == 0, jnp.int64(MAX_NODE_SCORE), scaled)
    return jnp.where(max_count == 0, raw, scaled)
