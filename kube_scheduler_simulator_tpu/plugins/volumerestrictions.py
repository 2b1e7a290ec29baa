"""VolumeRestrictions tensor kernels.

Upstream v1.32 `volumerestrictions` has two jobs:

* **Inline-disk conflicts** (Filter): two pods on one node may not use the
  same GCEPersistentDisk / RBD / ISCSI volume unless both mount it
  read-only; the same AWSElasticBlockStore conflicts regardless of
  read-only.  Failure status: "node(s) had no available disk".
* **ReadWriteOncePod** (PreFilter): a pod using a PVC with the
  ReadWriteOncePod access mode is rejected outright — all nodes — when any
  other pod already uses that PVC, with status "node has pod using
  PersistentVolumeClaim with the same name and ReadWriteOncePod access
  mode".  PreFilter returns Skip when the pod has neither kind of volume.

Tensorization: inline volume identities are interned as d-slots with a
per-slot `strict` flag (AWS EBS: conflicts even read-only-vs-read-only);
the carry tracks per-node `used_any[d]` / `used_rw[d]`.  RWOP PVCs are
interned as r-slots with a *cluster-wide* (not per-node) `rwop_used[r]`
carry — the PreFilter conflict is global, which is why the step function
exposes it as a prefilter-reject output rather than a per-node filter
code (the recording shim writes the status into prefilter-result-status,
reference: simulator/scheduler/plugin/wrappedplugin.go:491-518, and the
cycle aborts before Filter).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from ..state.volumes import READ_WRITE_ONCE_POD, VolumeTable, pod_pvc_keys

NAME = "VolumeRestrictions"
ERR_DISK_CONFLICT = "node(s) had no available disk"
ERR_RWOP_CONFLICT = (
    "node has pod using PersistentVolumeClaim with the same name and "
    "ReadWriteOncePod access mode"
)


class RestrictionsStatic(NamedTuple):
    strict: jnp.ndarray       # [D] bool: conflicts even when both read-only


class RestrictionsXS(NamedTuple):
    w_any: jnp.ndarray        # [P, D] bool: pod uses disk d
    w_rw: jnp.ndarray         # [P, D] bool: pod uses disk d NOT read-only
    rwop: jnp.ndarray         # [P, R] bool: pod uses RWOP PVC r
    filter_skip: jnp.ndarray  # [P] bool


class RestrictionsCarry(NamedTuple):
    used_any: jnp.ndarray     # [N, D] bool
    used_rw: jnp.ndarray      # [N, D] bool
    rwop_used: jnp.ndarray    # [R] bool — cluster-wide


def pod_inline_disks(pod: dict) -> list[tuple[tuple, bool]]:
    """(identity, read_only) for each restricted inline volume.

    identity[0] is the source kind; 'aws' identities are strict."""
    out = []
    for vol in ((pod.get("spec") or {}).get("volumes")) or []:
        gce = vol.get("gcePersistentDisk")
        if gce and gce.get("pdName"):
            out.append((("gce", gce["pdName"]), bool(gce.get("readOnly"))))
        aws = vol.get("awsElasticBlockStore")
        if aws and aws.get("volumeID"):
            out.append((("aws", aws["volumeID"]), bool(aws.get("readOnly"))))
        rbd = vol.get("rbd")
        if rbd and rbd.get("image"):
            mons = tuple(sorted(rbd.get("monitors") or []))
            out.append((
                ("rbd", mons, rbd.get("pool", "rbd"), rbd["image"]),
                bool(rbd.get("readOnly")),
            ))
        iscsi = vol.get("iscsi")
        if iscsi and iscsi.get("iqn"):
            out.append((
                ("iscsi", iscsi.get("targetPortal", ""), iscsi["iqn"],
                 str(iscsi.get("lun", 0))),
                bool(iscsi.get("readOnly")),
            ))
    return out


def pod_rwop_keys(vt: VolumeTable, pod: dict) -> list[str]:
    out = []
    for key in pod_pvc_keys(pod):
        pvc = vt.pvcs.get(key)
        if pvc is not None and READ_WRITE_ONCE_POD in pvc.access_modes:
            out.append(key)
    return out


def build(vt: VolumeTable, table, pods: list[dict], disks, rwops):
    """-> (RestrictionsStatic, RestrictionsXS, RestrictionsCarry).

    disks, rwops: the bound pods' inline disks and RWOP claims as the
    volume carry holds them (state/volumecarry.py NodeSlots): they are the
    first slots of the D and R axes, their strict flags the tags, the
    nodes that hold a disk the two planes (any, rw), and every bound RWOP
    claim is in use; the pending pods' new identities follow.  The kernels
    reduce over D and R (any), so the order of these axes cannot show."""
    new_disks: dict[tuple, int] = {}
    new_rwops: dict[str, int] = {}

    def d_of(ident: tuple) -> int:
        d = disks.slot.get(ident)
        if d is None:
            d = new_disks.setdefault(ident, disks.n + len(new_disks))
        return d

    def r_of(key: str) -> int:
        r = rwops.slot.get(key)
        if r is None:
            r = new_rwops.setdefault(key, rwops.n + len(new_rwops))
        return r

    pod_disks = [[(d_of(ident), ro) for ident, ro in pod_inline_disks(pod)]
                 for pod in pods]
    pod_rwops = [[r_of(key) for key in pod_rwop_keys(vt, pod)] for pod in pods]

    p = len(pods)
    nd, nr = disks.n + len(new_disks), rwops.n + len(new_rwops)
    w_any = np.zeros((p, nd), dtype=bool)
    w_rw = np.zeros((p, nd), dtype=bool)
    rwop = np.zeros((p, nr), dtype=bool)
    skip = np.ones(p, dtype=bool)
    for i in range(p):
        # upstream PreFilter: Skip unless the pod has an inline restricted
        # volume (needsRestrictionsCheck) or a ReadWriteOncePod PVC
        if pod_disks[i] or pod_rwops[i]:
            skip[i] = False
        for d, ro in pod_disks[i]:
            w_any[i, d] = True
            if not ro:
                w_rw[i, d] = True
        rwop[i, pod_rwops[i]] = True

    strict = np.zeros(nd, dtype=bool)
    strict[:disks.n] = disks.tags[:disks.n]
    strict[disks.n:] = [ident[0] == "aws" for ident in new_disks]

    # numpy, xs and carry too: compile_workload reads its flags and the
    # digest off the host bytes, then uploads once (pack_tree)
    static = RestrictionsStatic(strict=strict)
    xs = RestrictionsXS(
        w_any=w_any, w_rw=w_rw,
        rwop=rwop, filter_skip=skip,
    )
    carry = RestrictionsCarry(
        used_any=disks.plane(0, nd), used_rw=disks.plane(1, nd),
        rwop_used=np.arange(nr) < rwops.n,
    )
    return static, xs, carry


def prefilter_reject(sl: RestrictionsXS, carry: RestrictionsCarry) -> jnp.ndarray:
    """scalar int32: 1 when this pod's RWOP PVC is already in use."""
    return jnp.any(sl.rwop & carry.rwop_used).astype(jnp.int32)


def filter_kernel(static: RestrictionsStatic, sl: RestrictionsXS,
                  carry: RestrictionsCarry) -> jnp.ndarray:
    """[N] int32: 1 where an inline disk conflicts."""
    # conflict: volume already on node with a writer, we write to a volume
    # already on the node, or a strict (EBS) volume appears on both sides
    c = (
        jnp.any(sl.w_any[None, :] & carry.used_rw, axis=1)
        | jnp.any(sl.w_rw[None, :] & carry.used_any, axis=1)
        | jnp.any((sl.w_any & static.strict)[None, :] & carry.used_any, axis=1)
    )
    return jnp.where(c, 1, 0).astype(jnp.int32)


def bind_update(sl: RestrictionsXS, carry: RestrictionsCarry,
                selected: jnp.ndarray) -> RestrictionsCarry:
    n = carry.used_any.shape[0]
    onehot = (jnp.arange(n) == selected)[:, None]
    did_bind = selected >= 0
    return RestrictionsCarry(
        used_any=carry.used_any | (onehot & sl.w_any[None, :]),
        used_rw=carry.used_rw | (onehot & sl.w_rw[None, :]),
        rwop_used=carry.rwop_used | (did_bind & sl.rwop),
    )


def sequential_disk_conflict(wanted, existing) -> bool:
    """Scalar oracle of the inline-disk rule (parity checks)."""
    for wid, wro in wanted:
        for eid, ero in existing:
            if wid != eid:
                continue
            if wid[0] == "aws" or not (wro and ero):
                return True
    return False
