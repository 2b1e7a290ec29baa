"""NodeVolumeLimits (CSI) tensor kernels.

Upstream v1.32 `nodevolumelimits.CSILimits`: Filter fails a node when
attaching the pod's CSI volumes would push any driver's unique-volume
count on that node over the CSINode-reported allocatable limit — status
"node(s) exceed max volume count".  Nodes with no CSINode object or no
limit for the driver are never failed.  PreFilter returns Skip when the
pod has no PVC-backed volumes.

Tensorization: CSI volumes (driver, volumeHandle) over PVC-bound PVs are
interned as c-slots with a driver id; the carry tracks the per-node
unique-volume bitmap `on_node[N, C]` (a volume shared by two pods counts
once, matching upstream's unique-volume semantics).  Per-driver counts are
derived with one masked sum against the driver one-hot.

The limits are the cluster's CSINode objects (`csinodes`, a stored kind:
cluster/store.py), one per node, `spec.drivers[].allocatable.count`; the
engine hands them to compile_workload with the PVs and claims, so a
served pass refuses a node exactly as a direct caller's does.  A node
without a CSINode, or a driver without a count, has no limit.

Divergence (documented, docs/SEMANTICS.md): volumes a pod acquires
through dynamic WaitForFirstConsumer provisioning
(plugins/volumebinding.py) have no PV at evaluation time and are not
counted against later pods; in-tree volumes are not translated to their
CSI drivers (a CSINode's migrated-plugins annotation is not read), and
inline ephemeral CSI volumes are not modeled; a node's
`attachable-volumes-*` allocatable is not a fallback for a missing
CSINode count (upstream dropped that fallback in v1.29).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from ..state.volumes import VolumeTable, axis_bucket, pod_pvc_keys

NAME = "NodeVolumeLimits"
ERR_MAX_VOLUME_COUNT = "node(s) exceed max volume count"


class LimitsStatic(NamedTuple):
    """C is padded (state/volumes.py axis_bucket); a scan ARGUMENT, not a
    closure constant (state/compile.py ARG_STATICS)."""
    driver_onehot: jnp.ndarray  # [C, D] bool
    limits: jnp.ndarray         # [N, D] int64 (-1 = unlimited)


class LimitsXS(NamedTuple):
    pod_vols: jnp.ndarray       # [P, C] bool
    filter_skip: jnp.ndarray    # [P] bool


class LimitsCarry(NamedTuple):
    on_node: jnp.ndarray        # [N, C] bool


def pod_csi_volumes(vt: VolumeTable, pod: dict) -> list[tuple[str, str]]:
    """(driver, handle) for each CSI volume reached through a bound PVC."""
    out = []
    for key in pod_pvc_keys(pod):
        pvc = vt.pvcs.get(key)
        if pvc is None or not pvc.volume_name:
            continue
        i = vt.pv_index.get(pvc.volume_name)
        if i is None:
            continue
        pv = vt.pvs[i]
        if pv.csi_driver and pv.csi_handle:
            out.append((pv.csi_driver, pv.csi_handle))
    return out


def build(vt: VolumeTable, table, pods: list[dict], bound):
    """-> (LimitsStatic, LimitsXS, LimitsCarry).  With no CSINode-published
    limits every dimension is 0 and the kernel can never fail a node.

    bound: the bound pods' CSI volumes of drivers with a limit, as the
    volume carry holds them (state/volumecarry.py NodeSlots): the first
    slots of the C axis, a slot's driver index its tag, the nodes that
    hold it the one plane; the pending pods' new volumes follow.  The
    kernel sums over C, so the order of the axis cannot show."""
    drivers = sorted(vt.csi_limits)
    d_idx = {d: i for i, d in enumerate(drivers)}
    new: dict[tuple[str, str], int] = {}

    def c_of(vol: tuple[str, str]) -> int | None:
        if vol[0] not in d_idx:
            return None  # unlimited driver: irrelevant to the filter
        c = bound.slot.get(vol)
        if c is None:
            c = new.setdefault(vol, bound.n + len(new))
        return c

    pod_slots = [[c for c in map(c_of, pod_csi_volumes(vt, pod))
                  if c is not None] for pod in pods]

    p, n = len(pods), table.n
    # the C axis is padded (state/volumes.py axis_bucket): a slot past the
    # interned volumes is on no node, of no driver and in no pod
    nc, ndrv = axis_bucket(bound.n + len(new)), len(drivers)
    pod_vols = np.zeros((p, nc), dtype=bool)
    skip = np.ones(p, dtype=bool)
    for i, pod in enumerate(pods):
        if pod_pvc_keys(pod):
            skip[i] = False  # upstream Skips only pods with no PVC volumes
        pod_vols[i, pod_slots[i]] = True

    onehot = np.zeros((nc, ndrv), dtype=bool)
    onehot[np.arange(bound.n), bound.tags[:bound.n]] = True
    for vol, c in new.items():
        onehot[c, d_idx[vol[0]]] = True
    limits = np.stack([vt.csi_limits[d] for d in drivers], axis=1) if drivers else \
        np.zeros((n, 0), dtype=np.int64)

    # numpy, xs and carry too: compile_workload reads its flags and the
    # digest off the host bytes, then uploads once (pack_tree)
    static = LimitsStatic(driver_onehot=onehot, limits=limits)
    xs = LimitsXS(pod_vols=pod_vols, filter_skip=skip)
    carry = LimitsCarry(on_node=bound.plane(0, nc))
    return static, xs, carry


def _per_driver(vols: jnp.ndarray, onehot: jnp.ndarray) -> jnp.ndarray:
    """[N, C] bool volumes -> [N, D] int64 counts per driver.  A masked
    sum, not `vols @ onehot` in int64: the TPU compiler has no 64-bit
    dot (its X64 rewriting refuses one), and D is a handful of drivers.
    A count is at most C, so int32 holds it."""
    counts = jnp.sum(vols[:, :, None] & onehot[None, :, :], axis=1,
                     dtype=jnp.int32)
    return counts.astype(jnp.int64)


def filter_kernel(static: LimitsStatic, sl: LimitsXS, carry: LimitsCarry) -> jnp.ndarray:
    """[N] int32: 1 where a driver limit would be exceeded."""
    existing = _per_driver(carry.on_node, static.driver_onehot)       # [N, D]
    new = _per_driver(sl.pod_vols[None, :] & ~carry.on_node,
                      static.driver_onehot)                           # [N, D]
    # upstream checks only drivers the pod ADDS volumes for (returns nil
    # when len(newVolumes) == 0), so a node already over its limit still
    # accepts pods that bring nothing new for that driver
    over = (static.limits >= 0) & (new > 0) & (existing + new > static.limits)
    return jnp.any(over, axis=1).astype(jnp.int32)


def bind_update(sl: LimitsXS, carry: LimitsCarry, selected: jnp.ndarray) -> LimitsCarry:
    n = carry.on_node.shape[0]
    onehot = (jnp.arange(n) == selected)[:, None]
    return LimitsCarry(on_node=carry.on_node | (onehot & sl.pod_vols[None, :]))
