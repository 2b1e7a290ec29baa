"""VolumeZone tensor kernel.

Upstream v1.32 `volumezone`: Filter fails a node when some PVC's bound PV
carries a zone/region topology label whose (comma-separated) value set
does not contain the node's value for that label — status
"node(s) had no available volume zone".  PreFilter returns Skip when the
pod has no PVC volumes (so the shim records "" in
prefilter-result-status; reference recording shim:
simulator/scheduler/plugin/wrappedplugin.go:491-518).

PV zone labels and node labels are both static during a replay, so the
whole plugin compiles to a per-pod [N] code mask evaluated on the host
(state/volumes.py) — the device kernel is a table lookup.  Unbound PVCs
whose StorageClass is WaitForFirstConsumer are skipped (VolumeBinding owns
them); unbound immediate-binding PVCs never reach this Filter because
VolumeBinding's PreFilter already rejected the pod.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from ..state.volumes import ZONE_LABELS, VolumeTable, pod_pvc_keys

NAME = "VolumeZone"
ERR_VOLUME_ZONE_CONFLICT = "node(s) had no available volume zone"


class VolumeZoneXS(NamedTuple):
    codes: jnp.ndarray        # [P, N] int32 (0 pass, 1 zone conflict)
    filter_skip: jnp.ndarray  # [P] bool


def _zone_conflict(vt: VolumeTable, node_labels: dict[str, str], pv_labels) -> bool:
    for key in ZONE_LABELS:
        if key not in pv_labels:
            continue
        allowed = {z.strip() for z in str(pv_labels[key]).split(",")}
        if node_labels.get(key) not in allowed:
            return True
    return False


def pod_zone_codes(vt: VolumeTable, node_labels_list, pod: dict) -> np.ndarray | None:
    """[N] int32 codes for one pod, or None when the plugin Skips."""
    keys = pod_pvc_keys(pod)
    if not keys:
        return None
    n = len(node_labels_list)
    codes = np.zeros(n, dtype=np.int32)
    relevant = False
    for key in keys:
        pvc = vt.pvcs.get(key)
        if pvc is None or not pvc.volume_name:
            # missing PVC / unbound: VolumeBinding's PreFilter owns the
            # rejection; nothing zone-specific to check here
            continue
        i = vt.pv_index.get(pvc.volume_name)
        if i is None:
            continue
        labels = vt.pvs[i].labels
        if not any(k in labels for k in ZONE_LABELS):
            continue
        relevant = True
        for j, nl in enumerate(node_labels_list):
            if _zone_conflict(vt, nl, labels):
                codes[j] = 1
    # upstream PreFilter: Skip unless some bound PV carries a zone label
    # (len(podPVTopologies) == 0 -> Skip)
    return codes if relevant else None


def build(vt: VolumeTable, table, pods: list[dict]) -> VolumeZoneXS:
    p, n = len(pods), table.n
    per_pod: dict[int, np.ndarray] = {}
    skip = np.ones(p, dtype=bool)
    for i, pod in enumerate(pods):
        c = pod_zone_codes(vt, table.labels, pod)
        if c is not None:
            per_pod[i] = c
            skip[i] = False
    # compact [P, 1] when every pod Skips — the kernel output broadcasts
    # (pipeline broadcasts filter codes to [N]); avoids a P x N tensor for
    # volume-free workloads
    if not per_pod:
        codes = np.zeros((p, 1), dtype=np.int32)
    else:
        codes = np.zeros((p, n), dtype=np.int32)
        for i, c in per_pod.items():
            codes[i] = c
    return VolumeZoneXS(codes=codes, filter_skip=skip)


def filter_kernel(sl: VolumeZoneXS) -> jnp.ndarray:
    return sl.codes
