"""Columnar volume state: PVCs, PVs, StorageClasses, CSINodes.

Host-side half of the volume-plugin state split (same design as nodes.py):
PV/PVC/StorageClass *structure* is static during a replay — the simulator
has no PV controller, exactly like the reference's KWOK cluster runs no
volume controllers — so all manifest parsing, selector matching and PV
node-affinity evaluation happens once, producing dense numpy arrays: once
a manifest, not once a pass.  This module holds the parsed rows' types,
the parsers of one manifest and the table they fill; state/volumecarry.py
keeps that table from pass to pass and patches it from the store's events
(a served session), or seeds it from lists and throws it away
(build_volume_table, compile_workload(volumes=...)): one builder.
The only *dynamic* volume state is which PVs get claimed as pods with
unbound WaitForFirstConsumer PVCs bind during the replay; that is the
device-side carry of plugins/volumebinding.py.

Semantics follow upstream k8s.io/kubernetes v1.32 (pin:
/root/reference/simulator/go.mod:59) pkg/scheduler/framework/plugins/
{volumebinding,volumezone,volumerestrictions,nodevolumelimits} and
pkg/controller/volume/persistentvolume (findMatchingVolume match rules).
The reference simulator exercises these plugins through the real scheduler
(reference: simulator/scheduler/plugin/plugins.go:25-85 wraps every
in-tree plugin, including the volume family).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .nodes import NodeTable
from .selectors import label_selector_matches
from ..utils.quantity import parse_quantity

# PVC annotation predating spec.storageClassName (still honored upstream)
BETA_STORAGE_CLASS_ANN = "volume.beta.kubernetes.io/storage-class"
DEFAULT_CLASS_ANN = "storageclass.kubernetes.io/is-default-class"
NO_PROVISIONER = "kubernetes.io/no-provisioner"
WAIT_FOR_FIRST_CONSUMER = "WaitForFirstConsumer"
READ_WRITE_ONCE_POD = "ReadWriteOncePod"

# upstream volumezone.topologyLabels
ZONE_LABELS = (
    "failure-domain.beta.kubernetes.io/zone",
    "failure-domain.beta.kubernetes.io/region",
    "topology.kubernetes.io/zone",
    "topology.kubernetes.io/region",
)


@dataclass
class StorageClassInfo:
    name: str
    provisioner: str
    wait_for_first_consumer: bool
    allowed_topologies: list[dict] | None  # v1.TopologySelectorTerm list


@dataclass
class PVInfo:
    name: str
    capacity: int                  # bytes of .spec.capacity.storage
    storage_class: str
    access_modes: frozenset[str]
    claim_ref: str | None          # "ns/name" of pre-bound / bound PVC
    labels: dict[str, str]
    node_affinity: dict | None     # .spec.nodeAffinity.required (NodeSelector)
    csi_driver: str | None
    csi_handle: str | None


@dataclass
class PVCInfo:
    key: str                       # "ns/name"
    storage_class: str | None      # resolved (default class applied); None = missing PVC
    volume_name: str               # bound PV name or ""
    access_modes: frozenset[str]
    request: int                   # bytes requested
    selector: dict | None


# smallest non-empty extent of a padded volume axis
AXIS_FLOOR = 64


def axis_bucket(n: int) -> int:
    """The padded extent of a volume axis that holds n entries: the V axis
    (PVs in the cluster) and the C axis (distinct CSI volumes among bound
    and pending pods) are array axes of the family's scan arguments, xs
    and carry, so an extent that followed the count would be a new
    executable with every PV created.  0 stays 0 (a cluster without
    volumes keeps its empty axes and the kernels' static no-volume
    branches); else the power of two >= n, at least AXIS_FLOOR."""
    if n <= 0:
        return 0
    return max(AXIS_FLOOR, 1 << (n - 1).bit_length())


@dataclass
class VolumeTable:
    pvcs: dict[str, PVCInfo]
    pvs: list[PVInfo]
    pv_index: dict[str, int]
    classes: dict[str, StorageClassInfo]
    default_class: str | None
    # The three PV arrays are padded along V to axis_bucket(n_pvs): a row
    # past n_pvs is no PV anyone can claim (claimed, of capacity 0, OK on
    # no node).
    # dense, [V, N]: PV node-affinity evaluated against every node
    pv_node_ok: np.ndarray
    pv_cap: np.ndarray             # [V] int64
    pv_claimed0: np.ndarray        # [V] bool (claimRef set at compile time)
    # CSINode limits: driver name -> [N] int64 (-1 = no limit on that node)
    csi_limits: dict[str, np.ndarray]

    @property
    def n_pvs(self) -> int:
        return len(self.pvs)


def _meta(obj: dict) -> dict:
    return obj.get("metadata") or {}


def _key(obj: dict) -> str:
    m = _meta(obj)
    return f"{m.get('namespace') or 'default'}/{m.get('name', '')}"


def parse_storage_classes(scs: list[dict]) -> tuple[dict[str, StorageClassInfo], str | None]:
    classes: dict[str, StorageClassInfo] = {}
    default = None
    for sc in scs or []:
        name = _meta(sc).get("name", "")
        info = StorageClassInfo(
            name=name,
            provisioner=sc.get("provisioner", NO_PROVISIONER),
            wait_for_first_consumer=(
                sc.get("volumeBindingMode") == WAIT_FOR_FIRST_CONSUMER
            ),
            allowed_topologies=sc.get("allowedTopologies") or None,
        )
        classes[name] = info
        if (_meta(sc).get("annotations") or {}).get(DEFAULT_CLASS_ANN) == "true":
            default = name
    return classes, default


def _parse_pv(pv: dict) -> PVInfo:
    meta = _meta(pv)
    spec = pv.get("spec") or {}
    cap = int(parse_quantity((spec.get("capacity") or {}).get("storage", "0")))
    claim = spec.get("claimRef")
    claim_ref = None
    if claim and claim.get("name"):
        claim_ref = f"{claim.get('namespace') or 'default'}/{claim['name']}"
    csi = spec.get("csi") or {}
    affinity = ((spec.get("nodeAffinity") or {}).get("required")) or None
    return PVInfo(
        name=meta.get("name", ""),
        capacity=cap,
        storage_class=spec.get("storageClassName") or "",
        access_modes=frozenset(spec.get("accessModes") or []),
        claim_ref=claim_ref,
        labels={k: str(v) for k, v in (meta.get("labels") or {}).items()},
        node_affinity=affinity,
        csi_driver=csi.get("driver"),
        csi_handle=csi.get("volumeHandle"),
    )


def _parse_pvc(pvc: dict, classes: dict[str, StorageClassInfo],
               default_class: str | None) -> PVCInfo:
    meta = _meta(pvc)
    spec = pvc.get("spec") or {}
    sc = spec.get("storageClassName")
    if sc is None:
        sc = (meta.get("annotations") or {}).get(BETA_STORAGE_CLASS_ANN)
    if sc is None:
        # upstream GetDefaultClass: nil class on the PVC resolves to the
        # cluster default StorageClass (retroactive default assignment)
        sc = default_class if default_class is not None else ""
    req = int(parse_quantity(
        ((spec.get("resources") or {}).get("requests") or {}).get("storage", "0")
    ))
    return PVCInfo(
        key=_key(pvc),
        storage_class=sc,
        volume_name=spec.get("volumeName") or "",
        access_modes=frozenset(spec.get("accessModes") or []),
        request=req,
        selector=spec.get("selector"),
    )


def build_volume_table(
    node_table: NodeTable,
    pvcs: list[dict] | None,
    pvs: list[dict] | None,
    storage_classes: list[dict] | None,
    csinodes: list[dict] | None,
) -> VolumeTable:
    """The table of given manifest lists on a node table, the V axis in
    the PV list's order: a throw-away volume carry seeded from the lists
    (state/volumecarry.py; the carried table of a served session is the
    same code fed by the store's events)."""
    from .volumecarry import carry_of_lists

    return carry_of_lists({
        "pvcs": pvcs, "pvs": pvs, "storageclasses": storage_classes,
        "csinodes": csinodes}).table_on(node_table)


def empty_volume_table(node_table: NodeTable) -> VolumeTable:
    return build_volume_table(node_table, None, None, None, None)


# ---------------------------------------------------------------------------
# pod-side volume extraction (shared by the tensor builders and the
# sequential oracle)

def pod_pvc_names(pod: dict) -> list[str]:
    """claimNames of the pod's persistentVolumeClaim volumes, in order."""
    out = []
    for vol in ((pod.get("spec") or {}).get("volumes")) or []:
        pvc = vol.get("persistentVolumeClaim")
        if pvc and pvc.get("claimName"):
            out.append(pvc["claimName"])
    return out


def pod_pvc_keys(pod: dict) -> list[str]:
    ns = _meta(pod).get("namespace") or "default"
    return [f"{ns}/{name}" for name in pod_pvc_names(pod)]


def pv_matches_claim(pv: PVInfo, pvc: PVCInfo) -> bool:
    """Static-provisioning match, upstream findMatchingVolume rules:
    storage class equal, access modes a superset, capacity sufficient,
    label selector satisfied, and claimRef (if set) naming this claim."""
    if pv.storage_class != (pvc.storage_class or ""):
        return False
    if not pvc.access_modes <= pv.access_modes:
        return False
    if pv.capacity < pvc.request:
        return False
    if pvc.selector is not None and not label_selector_matches(pvc.selector, pv.labels):
        return False
    if pv.claim_ref is not None and pv.claim_ref != pvc.key:
        return False
    return True


def topology_term_matches(term: dict, labels: dict[str, str]) -> bool:
    """v1.TopologySelectorTerm: AND over matchLabelExpressions, each
    requiring label[key] in values (upstream MatchTopologySelectorTerms)."""
    for expr in term.get("matchLabelExpressions") or []:
        key = expr.get("key", "")
        if key not in labels or labels[key] not in (expr.get("values") or []):
            return False
    return True


def allowed_topologies_match(sc: StorageClassInfo, labels: dict[str, str]) -> bool:
    if not sc.allowed_topologies:
        return True
    return any(topology_term_matches(t, labels) for t in sc.allowed_topologies)
