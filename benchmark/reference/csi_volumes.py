"""The plain reference for deployments whose pods mount PersistentVolumeClaims
bound to CSI PersistentVolumes, on nodes whose CSINodes publish attach
limits: scheduler_perf's SchedulingCSIPVs.

One pod and one node at a time, Python integers and float64; imports
`default_profile.py`'s and `antiaffinity.py`'s helpers and nothing of the
program.  The interface is the one stated at `default_profile.py`'s head.

What the oracle child hands a reference is the initial nodes, the initial
pods and then the measured pods, one `schedule_one` each, in queue order.
The cluster's volume objects are read from `nodes.volumes` (generators/
scheduler_perf_volumes.py): the CSINodes and the initial pods' PVs and
claims exist from the start; before its `schedule_one` of a measured pod
this reference creates that pod's PV and claim (`nodes.volumes.of(name)`),
as the client did before it created the pod.

What it adds to the default profile's reference, from the upstream v1.32
plugins (pkg/scheduler/framework/plugins/{volumerestrictions,
nodevolumelimits, volumebinding, volumezone}), for a pod whose volumes are
all `persistentVolumeClaim`:

  * VolumeRestrictions.  PreFilter looks every claim up (a missing claim
    would reject the pod; NotCovered here) and returns Skip unless the pod
    has a restricted inline volume or a ReadWriteOncePod claim: status "",
    no Filter entry.  Both of those are NotCovered.
  * NodeVolumeLimits (CSILimits).  PreFilter: a pod with a claim is not
    skipped, status "success".  Filter, per node: the pod's NEW volumes are
    its claims' PVs with a `csi` source, each named `<driver>/<handle>`;
    none -> passed.  The node's limits are its CSINode's drivers with an
    `allocatable.count`; none -> passed.  The ATTACHED volumes are the
    unique names over every pod on the node; a new volume that is already
    attached is not new.  For every driver the pod still adds volumes for,
    attached + new over the driver's count refuses the node:
    "node(s) exceed max volume count".
  * VolumeBinding.  PreFilter: a pod with claims is not skipped, status
    "success" (an unbound claim is another deployment: NotCovered).
    Filter, per node, for bound claims: the claim's PV does not exist ->
    "node(s) unavailable due to one or more pvc(s) bound to non-existent
    pv(s)"; its node affinity does not match -> a conflict (a PV with node
    affinity is NotCovered); else passed.  Reserve and PreBind say
    "success" for a bound pod, Score is 0 and there is no PreScore entry,
    as in default_profile.py.
  * VolumeZone.  PreFilter returns Skip unless a bound PV carries a zone
    or region label (NotCovered): status "", no Filter entry.

The Filter plugins run in the profile's order and stop at a node's first
refusal (antiaffinity's `run_filters`); the maps and the normalisation are
over the feasible nodes only, ties go to the first feasible node in name
order, and a pod with no feasible node is rendered by antiaffinity's
`render` (every covered pod has priority 0, so preemption finds nothing).

Anything else raises NotCovered: an unbound or ReadWriteOncePod claim, an
inline or ephemeral volume, a PV without a `csi` source, with node
affinity or with zone labels, pod affinity, and all that
default_profile.py refuses.  A node's `attachable-volumes-*` allocatable
is read and set aside: no covered pod requests it, and since v1.29 the
CSINode alone carries the limit.
"""

from __future__ import annotations

from reference.antiaffinity import render, run_filters
from reference.default_profile import (  # noqa: F401  (the interface)
    ARITHMETICS, KEYS, PREFILTERS, PRESCORERS, SCORERS, Exact, NotCovered, _Pod)
from reference.default_profile import ReferenceScheduler as _DefaultProfile

ERR_MAX_VOLUME_COUNT = "node(s) exceed max volume count"
ERR_PV_NOT_EXIST = ("node(s) unavailable due to one or more pvc(s) bound to "
                    "non-existent pv(s)")
BIND_COMPLETED = "pv.kubernetes.io/bind-completed"
ATTACHABLE_PREFIX = "attachable-volumes-"
ZONE_LABELS = ("failure-domain.beta.kubernetes.io/zone",
               "failure-domain.beta.kubernetes.io/region",
               "topology.kubernetes.io/zone", "topology.kubernetes.io/region")


def _passes(j: int) -> None:
    return None


def _without_attachable(node: dict) -> dict:
    """The node with its attachable-volumes-* allocatable set aside."""
    status = dict(node.get("status") or {})
    status["allocatable"] = {
        k: v for k, v in (status.get("allocatable") or {}).items()
        if not k.startswith(ATTACHABLE_PREFIX)}
    return {**node, "status": status}


def _claim_names(manifest: dict) -> list[str]:
    names = []
    for vol in (manifest.get("spec") or {}).get("volumes") or []:
        if set(vol) - {"name", "persistentVolumeClaim"}:
            raise NotCovered(f"volume kinds {sorted(set(vol) - {'name'})}")
        names.append(vol["persistentVolumeClaim"]["claimName"])
    return names


class _VolumePod(_Pod):
    """default_profile's pod (which refuses volumes) plus its claims."""

    __slots__ = ("claims",)

    def __init__(self, manifest: dict):
        self.claims = _claim_names(manifest)
        spec = {k: v for k, v in manifest["spec"].items() if k != "volumes"}
        super().__init__({**manifest, "spec": spec})
        if self.terms:
            raise NotCovered("pod affinity beside volumes")


class ReferenceScheduler(_DefaultProfile):
    """default_profile's cluster state and resource plugins; the volume
    objects, the volume family and the cycle are this file's."""

    def __init__(self, nodes: list[dict], bound_pods: list[dict],
                 arith=Exact):
        super().__init__([_without_attachable(n) for n in nodes], [], arith)
        self.volumes = getattr(nodes, "volumes", None)
        idx = {nm: j for j, nm in enumerate(self.names)}
        self.pvs: dict[str, str] = {}               # PV name -> driver/handle
        self.pvcs: dict[tuple[str, str], str] = {}  # (ns, name) -> PV name
        self.limits: list[dict[str, int]] = [{} for _ in range(self.n)]
        self.attached: list[set[str]] = [set() for _ in range(self.n)]
        if self.volumes is not None:
            for cn in self.volumes.csinodes:
                self.create_csinode(cn, idx)
            for pv, pvc in self.volumes.initial:
                self.create_pv(pv)
                self.create_pvc(pvc)
        for m in bound_pods:
            self._bind(_VolumePod(m), idx[m["spec"]["nodeName"]])

    # ---------------------------------------------------- cluster objects

    def create_csinode(self, cn: dict, idx: dict[str, int]) -> None:
        j = idx.get(cn["metadata"]["name"])
        if j is None:
            return  # a CSINode of no node limits nothing
        for drv in (cn.get("spec") or {}).get("drivers") or []:
            count = (drv.get("allocatable") or {}).get("count")
            if count is not None:
                self.limits[j][drv["name"]] = int(count)

    def create_pv(self, pv: dict) -> None:
        spec = pv.get("spec") or {}
        if spec.get("nodeAffinity"):
            raise NotCovered("a PV with node affinity")
        if set((pv["metadata"].get("labels") or {})) & set(ZONE_LABELS):
            raise NotCovered("a PV with zone labels")
        csi = spec.get("csi") or {}
        if not csi.get("driver") or not csi.get("volumeHandle"):
            raise NotCovered("a PV without a csi source")
        self.pvs[pv["metadata"]["name"]] = f"{csi['driver']}/{csi['volumeHandle']}"

    def create_pvc(self, pvc: dict) -> None:
        meta, spec = pvc["metadata"], pvc.get("spec") or {}
        if "ReadWriteOncePod" in (spec.get("accessModes") or []):
            raise NotCovered("a ReadWriteOncePod claim")
        if (not spec.get("volumeName")
                or BIND_COMPLETED not in (meta.get("annotations") or {})):
            raise NotCovered("an unbound claim")
        self.pvcs[meta.get("namespace") or "default", meta["name"]] = \
            spec["volumeName"]

    # ------------------------------------------------------------ plugins

    def _claimed_pvs(self, pod: _VolumePod) -> list[str]:
        """The PV name behind each of the pod's claims."""
        out = []
        for claim in pod.claims:
            if (pod.ns, claim) not in self.pvcs:
                raise NotCovered(f"claim {claim} does not exist")
            out.append(self.pvcs[pod.ns, claim])
        return out

    def _limits_filter(self, pv_names: list[str], j: int) -> str | None:
        A = self.A
        new = {self.pvs[nm] for nm in pv_names if nm in self.pvs}
        if not new or not self.limits[j]:
            return None
        new -= self.attached[j]
        for driver in {v.split("/", 1)[0] for v in new}:
            limit = self.limits[j].get(driver)
            if limit is None:
                continue
            attached = sum(1 for v in self.attached[j]
                           if v.split("/", 1)[0] == driver)
            adds = sum(1 for v in new if v.split("/", 1)[0] == driver)
            if A.i(attached + adds) > A.i(limit):
                return ERR_MAX_VOLUME_COUNT
        return None

    def _binding_filter(self, pv_names: list[str], j: int) -> str | None:
        if any(nm not in self.pvs for nm in pv_names):
            return ERR_PV_NOT_EXIST
        return None  # no covered PV has node affinity

    def _bind(self, pod, j: int) -> None:
        super()._bind(pod, j)
        for nm in self._claimed_pvs(pod):
            if nm in self.pvs:
                self.attached[j].add(self.pvs[nm])

    # -------------------------------------------------------------- cycle

    def schedule_one(self, manifest: dict, annotate: bool = True):
        """-> (annotations or None, selected node name or ""); binds.  The
        pod's own PV and claim are created first."""
        pod = _VolumePod(manifest)
        if self.volumes is not None and pod.claims:
            pv, pvc = self.volumes.of(pod.name)
            self.create_pv(pv)
            self.create_pvc(pvc)
        pv_names = self._claimed_pvs(pod)
        plugins = [("NodeUnschedulable", _passes), ("NodeName", _passes),
                   ("TaintToleration", _passes),
                   ("NodeResourcesFit", lambda j: self._fit_filter(pod, j))]
        if pod.claims:
            plugins += [
                ("NodeVolumeLimits", lambda j: self._limits_filter(pv_names, j)),
                ("VolumeBinding", lambda j: self._binding_filter(pv_names, j))]
        filter_map: dict[str, dict[str, str]] = {}
        feasible: list[int] = []
        for j in range(self.n):
            entry, ok = run_filters(plugins, j)
            filter_map[self.names[j]] = entry
            if ok:
                feasible.append(j)

        prescore: dict[str, str] = {}
        score_map: dict[str, dict[str, str]] = {}
        final_map: dict[str, dict[str, str]] = {}
        selected = -1
        if len(feasible) == 1:
            selected = feasible[0]
        elif feasible:
            prescore = {nm: ("" if nm in ("NodeAffinity", "PodTopologySpread")
                             else "success") for nm in PRESCORERS}
            memo: dict = {}
            raws = [self._raw_scores(pod, j, {}, memo) for j in feasible]
            totals = [0] * len(feasible)
            finals = {}
            for name, weight in SCORERS:
                normed = self._normalize(name, [r[name] for r in raws])
                finals[name] = [v * weight for v in normed]
                for i, v in enumerate(finals[name]):
                    totals[i] += v
            selected = feasible[totals.index(max(totals))]  # first in order
            if annotate:
                for i, j in enumerate(feasible):
                    score_map[self.names[j]] = {
                        nm: str(raws[i][nm]) for nm, _ in SCORERS}
                    final_map[self.names[j]] = {
                        nm: str(finals[nm][i]) for nm, _ in SCORERS}
        if selected >= 0:
            self._bind(pod, selected)
        node = self.names[selected] if selected >= 0 else ""
        if not annotate:
            return None, node
        status = {nm: "" for nm in PREFILTERS}
        status["NodeResourcesFit"] = "success"
        if pod.claims:
            status["NodeVolumeLimits"] = status["VolumeBinding"] = "success"
        return render(status, filter_map, prescore, score_map, final_map,
                      node), node
