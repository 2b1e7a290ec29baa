"""VolumeBinding tensor kernels.

Upstream v1.32 `volumebinding`:

* PreFilter: Skip when the pod has no PVC volumes; rejects the pod
  outright (UnschedulableAndUnresolvable) when a PVC is missing, when an
  unbound PVC's StorageClass uses Immediate binding ("pod has unbound
  immediate PersistentVolumeClaims"), or when the StorageClass doesn't
  exist — those become compile-time per-pod rejects here (the recording
  shim writes the status into prefilter-result-status, reference:
  simulator/scheduler/plugin/wrappedplugin.go:491-518).
* Filter (FindPodVolumes): a node fails with
    - "node(s) had volume node affinity conflict" when a *bound* PVC's PV
      has a node affinity not matching the node,
    - "node(s) didn't find available persistent volumes to bind" when some
      unbound WaitForFirstConsumer PVC can neither claim an existing
      matching PV nor be dynamically provisioned on the node,
    - "node(s) unavailable due to one or more pvc(s) bound to non-existent
      pv(s)" when a bound PVC references a PV that doesn't exist;
  both of the first two reasons can be reported together (the status
  message joins them), which is why codes are a bitmask.
* Reserve/PreBind assume + bind the chosen PVs; Score exists but returns 0
  with the VolumeCapacityPriority feature gate off (the default).

Tensorization: bound-PV node-affinity conflicts and the PreFilter rejects
are static per pod (the simulator runs no PV controller, exactly like the
reference's KWOK cluster) and precompile to host masks.  The *dynamic*
part is PV claiming: pods with unbound WFFC PVCs consume matching PVs as
they bind, so the carry is `claimed[V]` and the Filter runs upstream's
greedy findMatchingVolume on device — per PVC slot k (static unroll,
K = max unbound PVCs per pod), pick per node the smallest-capacity
available matching PV (argmin ties -> lowest PV index; upstream iterates
an unordered map, so its tie order is unspecified — ours is deterministic
and mirrored by the sequential oracle), exclude it from later slots, and
fall back to checking the StorageClass' allowedTopologies for dynamic
provisioning when no PV matches.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from ..state.volumes import (
    NO_PROVISIONER,
    VolumeTable,
    allowed_topologies_match,
    pod_pvc_keys,
    pv_matches_claim,
)

NAME = "VolumeBinding"
ERR_NODE_CONFLICT = "node(s) had volume node affinity conflict"
ERR_BIND_CONFLICT = "node(s) didn't find available persistent volumes to bind"
ERR_PV_NOT_EXIST = (
    "node(s) unavailable due to one or more pvc(s) bound to non-existent pv(s)"
)
ERR_UNBOUND_IMMEDIATE = "pod has unbound immediate PersistentVolumeClaims"

# filter code bitmask
CODE_NODE_CONFLICT = 1
CODE_BIND_CONFLICT = 2
CODE_PV_NOT_EXIST = 4


def decode_filter(code: int, node_idx: int, aux) -> str:
    if code & CODE_PV_NOT_EXIST:
        return ERR_PV_NOT_EXIST
    parts = []
    if code & CODE_NODE_CONFLICT:
        parts.append(ERR_NODE_CONFLICT)
    if code & CODE_BIND_CONFLICT:
        parts.append(ERR_BIND_CONFLICT)
    return ", ".join(parts)


class BindingStatic(NamedTuple):
    """V is padded (state/volumes.py axis_bucket); a scan ARGUMENT, not a
    closure constant (state/compile.py ARG_STATICS)."""
    pv_cap: jnp.ndarray       # [V] int64
    pv_node_ok: jnp.ndarray   # [V, N] bool


class BindingXS(NamedTuple):
    bound_code: jnp.ndarray    # [P, N] int32 (static: node-conflict / pv-missing bits)
    want: jnp.ndarray          # [P, K, V] bool
    active: jnp.ndarray        # [P, K] bool
    provision_ok: jnp.ndarray  # [P, K, N] bool
    filter_skip: jnp.ndarray   # [P] bool


class BindingCarry(NamedTuple):
    claimed: jnp.ndarray       # [V] bool


def classify_pod(vt: VolumeTable, pod: dict):
    """-> (reject_msg | None, bound_pv_idx list, unbound PVCInfo list).

    reject_msg is the upstream PreFilter UnschedulableAndUnresolvable
    message ('' when none); missing-PVC rejects belong to
    VolumeRestrictions, whose PreFilter runs first and does the same
    lister lookup (see compile.py)."""
    bound: list[int] = []
    unbound = []
    for key in pod_pvc_keys(pod):
        pvc = vt.pvcs.get(key)
        if pvc is None:
            name = key.split("/", 1)[1]
            return f'persistentvolumeclaim "{name}" not found', [], []
        if pvc.volume_name:
            bound.append(vt.pv_index.get(pvc.volume_name, -1))
            continue
        sc = vt.classes.get(pvc.storage_class or "")
        if sc is None:
            return (
                f'storageclass.storage.k8s.io "{pvc.storage_class}" not found',
                [], [],
            )
        if not sc.wait_for_first_consumer:
            return ERR_UNBOUND_IMMEDIATE, [], []
        unbound.append(pvc)
    return None, bound, unbound


def prime_claims(vt: VolumeTable, bound_pods, name_idx: dict[str, int]) -> np.ndarray:
    """claimed[V] with already-bound pods' WFFC claims re-applied.

    Pods bound in an earlier wave claimed PVs on device, but the store's
    PVC manifests still show volumeName="" (the simulator runs no PV
    controller), so on recompile each bound pod's greedy choice is
    re-derived host-side — same deterministic rule (smallest capacity,
    lowest index), in bound_pods order."""
    claimed = vt.pv_claimed0.copy()
    for bp, node_name in bound_pods or []:
        j = name_idx.get(node_name)
        if j is None:
            continue
        reject, _, unbound = classify_pod(vt, bp)
        if reject is not None or not unbound:
            continue
        chosen: set[int] = set()
        for pvc in unbound:
            best = None
            for vi, pv in enumerate(vt.pvs):
                if claimed[vi] or vi in chosen or not vt.pv_node_ok[vi, j]:
                    continue
                if not pv_matches_claim(pv, pvc):
                    continue
                if best is None or pv.capacity < vt.pvs[best].capacity:
                    best = vi
            if best is not None:
                chosen.add(best)
        for vi in chosen:
            claimed[vi] = True
    return claimed


def build(vt: VolumeTable, table, pods: list[dict], bound_pods=None):
    """-> (BindingStatic, BindingXS, BindingCarry, reject list[str | None]).

    bound_pods: the bound pods prime_claims replays; compile_workload
    hands the ones with an unbound WaitForFirstConsumer claim
    (state/volumecarry.py wffc_rows), the others being no-ops there."""
    # V is the table's padded extent (state/volumes.py axis_bucket): the
    # rows past n_pvs are wanted by no claim and start out claimed
    p, n, v = len(pods), table.n, vt.pv_cap.shape[0]
    ks: list[int] = []
    classified = []
    for pod in pods:
        reject, bound, unbound = classify_pod(vt, pod)
        classified.append((reject, bound, unbound))
        ks.append(len(unbound))
    k_max = max(ks, default=0)

    any_bound = any(bound for _, bound, _ in classified)
    # compact [P, 1] when no pod has bound PVCs (the kernel's output
    # broadcasts against the [N]-shaped bind-conflict mask)
    bound_code = np.zeros((p, n if any_bound else 1), dtype=np.int32)
    want = np.zeros((p, k_max, v), dtype=bool)
    active = np.zeros((p, k_max), dtype=bool)
    provision_ok = np.zeros((p, k_max, n), dtype=bool)
    skip = np.ones(p, dtype=bool)
    rejects: list[str | None] = []

    for i, pod in enumerate(pods):
        reject, bound, unbound = classified[i]
        rejects.append(reject)
        if reject is not None:
            continue
        if pod_pvc_keys(pod):
            skip[i] = False
        for b in bound:
            if b < 0:
                bound_code[i, :] |= CODE_PV_NOT_EXIST
            else:
                bound_code[i, :] |= np.where(
                    vt.pv_node_ok[b], 0, CODE_NODE_CONFLICT
                ).astype(np.int32)
        for k, pvc in enumerate(unbound):
            active[i, k] = True
            for vi, pv in enumerate(vt.pvs):
                want[i, k, vi] = pv_matches_claim(pv, pvc)
            sc = vt.classes[pvc.storage_class or ""]
            if sc.provisioner and sc.provisioner != NO_PROVISIONER:
                for j in range(n):
                    provision_ok[i, k, j] = allowed_topologies_match(
                        sc, table.labels[j]
                    )

    # numpy, xs and carry too: compile_workload reads its flags and the
    # digest off the host bytes, then uploads once (pack_tree)
    static = BindingStatic(
        pv_cap=np.asarray(vt.pv_cap), pv_node_ok=np.asarray(vt.pv_node_ok)
    )
    xs = BindingXS(
        bound_code=bound_code,
        want=want,
        active=active,
        provision_ok=provision_ok,
        filter_skip=skip,
    )
    carry = BindingCarry(
        claimed=prime_claims(vt, bound_pods, table.name_idx))
    return static, xs, carry, rejects


_I64_MAX = np.iinfo(np.int64).max


def _greedy_choices(static: BindingStatic, sl: BindingXS, claimed: jnp.ndarray):
    """Per-node greedy matching over the pod's K unbound-PVC slots.

    -> (bindfail [N] bool, chosen [V, N] bool: PV v statically claimed when
    this pod lands on node n)."""
    v, n = static.pv_node_ok.shape
    k_max = sl.want.shape[0]
    chosen = jnp.zeros((v, n), dtype=bool)
    bindfail = jnp.zeros(n, dtype=bool)
    for k in range(k_max):
        if v > 0:
            cand = (
                sl.want[k][:, None] & (~claimed)[:, None] & ~chosen
                & static.pv_node_ok
            )
            cap = jnp.where(cand, static.pv_cap[:, None], _I64_MAX)
            pick = jnp.argmin(cap, axis=0)                     # first min == lowest idx
            has = jnp.take_along_axis(cand, pick[None, :], axis=0)[0]
            use = sl.active[k] & has
            chosen = chosen | ((jnp.arange(v)[:, None] == pick[None, :]) & use[None, :])
        else:
            has = jnp.zeros(n, dtype=bool)
        ok_k = has | sl.provision_ok[k]
        bindfail = bindfail | (sl.active[k] & ~ok_k)
    return bindfail, chosen


def filter_kernel(static: BindingStatic, sl: BindingXS, carry: BindingCarry) -> jnp.ndarray:
    bindfail, _ = _greedy_choices(static, sl, carry.claimed)
    return (sl.bound_code | jnp.where(bindfail, CODE_BIND_CONFLICT, 0)).astype(jnp.int32)


def bind_update(static: BindingStatic, sl: BindingXS, carry: BindingCarry,
                selected: jnp.ndarray) -> BindingCarry:
    """Claim the PVs the greedy matcher picked on the selected node."""
    v = static.pv_cap.shape[0]
    if v == 0 or sl.want.shape[0] == 0:
        return carry
    _, chosen = _greedy_choices(static, sl, carry.claimed)
    col = jnp.take(chosen, jnp.clip(selected, 0), axis=1)
    return BindingCarry(claimed=carry.claimed | jnp.where(selected >= 0, col, False))


def score_kernel(n_nodes: int) -> jnp.ndarray:
    """VolumeCapacityPriority is off by default: Score returns 0."""
    return jnp.zeros(n_nodes, dtype=jnp.int64)
