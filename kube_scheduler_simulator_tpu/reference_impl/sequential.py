"""Sequential CPU reference scheduler — the parity oracle.

A deliberately *scalar* reimplementation of the scheduling cycle in the
style of the Go reference (one pod at a time, per-node loops, per-plugin
calls — SURVEY.md §3.2), sharing nothing with the tensor engine except the
static selector-matching helpers.  Its annotations must be bit-identical
to store/decode.py over framework/replay.py — that is the correctness gate
of PARITY.md ("The parity protocol"; parity_gate.py streams it).

Semantics sources are the same as the tensor kernels' (upstream v1.32
plugins; recording shim reference:
simulator/scheduler/plugin/wrappedplugin.go); the deterministic
lowest-index tie-break divergence is applied here identically.
"""

from __future__ import annotations

import json
import math

from ..plugins.registry import PluginSetConfig
from ..state.nodes import build_node_table, PREFER_NO_SCHEDULE
from ..state.resources import CPU, MEMORY, ResourceSchema, pod_resource_request
from ..state.selectors import (
    label_selector_matches,
    node_selector_matches,
    node_selector_term_matches,
    tolerations_tolerate,
)
from ..store import annotations as ann

MAX_NODE_SCORE = 100


def _meta(pod):
    return pod.get("metadata") or {}


def _spec(pod):
    return pod.get("spec") or {}


class SequentialScheduler:
    def __init__(self, nodes, pods, config: PluginSetConfig | None = None, bound_pods=None,
                 volumes=None, namespaces=None):
        from ..state.volumes import build_volume_table

        self.config = config or PluginSetConfig()
        self.pods = pods
        self.node_manifests = nodes
        # namespace manifests back InterPodAffinity namespaceSelector
        # resolution (interpod.effective_terms)
        self.namespaces = namespaces or []
        self._term_cache: dict = {}
        self.schema = ResourceSchema.discover(pods + [bp for bp, _ in (bound_pods or [])], nodes)
        self.table = build_node_table(nodes, self.schema)
        volumes = volumes or {}
        # manifest parsing (VolumeTable) is shared with the tensor side;
        # the *scheduling logic* below is independently scalar
        self.vt = build_volume_table(
            self.table, volumes.get("pvcs"), volumes.get("pvs"),
            volumes.get("storageclasses"), volumes.get("csinodes"),
        )
        from ..plugins.volumebinding import prime_claims

        self.pv_claimed = list(prime_claims(
            self.vt, bound_pods or [],
            {nm: j for j, nm in enumerate(self.table.names)},
        ))
        self._added_affinity = (self.config.args.get("NodeAffinity") or {}).get(
            "addedAffinity") or {}
        from ..plugins.noderesources import fit_ignored_mask

        self._fit_ignored = fit_ignored_mask(
            self.schema, self.config.args.get("NodeResourcesFit"))
        self.labels = self.table.labels
        self.names = self.table.names
        self.n = self.table.n
        self.requested = [row.copy() for row in self.table.allocatable * 0]
        self.nonzero = [[0, 0] for _ in range(self.n)]
        self.num_pods = [0] * self.n
        self.assigned: list[tuple[dict, int]] = []  # (pod manifest, node idx)
        self._image_states = None  # lazy ImageLocality node-image index
        self._name_idx = {nm: j for j, nm in enumerate(self.names)}
        for bp, node_name in bound_pods or []:
            j = self._name_idx.get(node_name)
            if j is None:
                continue
            r, nz = pod_resource_request(bp, self.schema)
            self.requested[j] = self.requested[j] + r
            self.nonzero[j][0] += int(nz[0])
            self.nonzero[j][1] += int(nz[1])
            self.num_pods[j] += 1
            self.assigned.append((bp, j))

    # ---------------- per-plugin filter/score ---------------------------

    def _filter(self, name, pod, req, j) -> str | None:
        """None == pass, else failure message."""
        if self.config.is_custom(name):
            return self.config.custom[name].filter(pod, self.node_manifests[j])
        if name == "NodeResourcesFit":
            reasons = []
            if self.num_pods[j] + 1 > self.table.allowed_pods[j]:
                reasons.append("Too many pods")
            if any(req):  # zero-request pods only face the pod-count check
                alloc = self.table.allocatable[j]
                free = alloc - self.requested[j]
                for r, col in enumerate(self.schema.columns):
                    if req[r] > free[r] and not self._fit_ignored[r]:
                        reasons.append(f"Insufficient {col}")
            return ", ".join(reasons) if reasons else None
        if name == "NodeAffinity":
            spec = _spec(pod)
            sel = spec.get("nodeSelector") or {}
            required = (((spec.get("affinity") or {}).get("nodeAffinity")) or {}).get(
                "requiredDuringSchedulingIgnoredDuringExecution"
            )
            ok = all(self.labels[j].get(k) == str(v) for k, v in sel.items())
            if ok and required:
                ok = node_selector_matches(required, self.labels[j], self.names[j])
            added_req = self._added_affinity.get(
                "requiredDuringSchedulingIgnoredDuringExecution")
            if ok and added_req:
                ok = node_selector_matches(added_req, self.labels[j], self.names[j])
            return None if ok else "node(s) didn't match Pod's node affinity/selector"
        if name == "TaintToleration":
            tols = _spec(pod).get("tolerations") or []
            for key, value, eff in self.table.taints[j]:
                if eff == PREFER_NO_SCHEDULE:
                    continue
                if not tolerations_tolerate(tols, key, value, eff):
                    return "node(s) had untolerated taint {%s: %s}" % (key, value)
            return None
        if name == "NodeUnschedulable":
            if not self.table.unschedulable[j]:
                return None
            tols = _spec(pod).get("tolerations") or []
            if tolerations_tolerate(tols, "node.kubernetes.io/unschedulable", "", "NoSchedule"):
                return None
            return "node(s) were unschedulable"
        if name == "NodeName":
            want = _spec(pod).get("nodeName") or ""
            return None if (not want or want == self.names[j]) else "node(s) didn't match the requested node name"
        if name == "NodePorts":
            from ..plugins import ports as portsmod

            wanted = portsmod.pod_host_ports(pod)
            existing = [
                t for ap, aj in self.assigned if aj == j
                for t in portsmod.pod_host_ports(ap)
            ]
            if portsmod.sequential_conflict(wanted, existing):
                return portsmod.ERR_NODE_PORTS
            return None
        if name == "PodTopologySpread":
            return self._spread_filter(pod, j)
        if name == "InterPodAffinity":
            return self._interpod_filter(pod, j)
        if name == "VolumeRestrictions":
            from ..plugins import volumerestrictions as vr

            wanted = vr.pod_inline_disks(pod)
            existing = [
                t for ap, aj in self.assigned if aj == j
                for t in vr.pod_inline_disks(ap)
            ]
            if vr.sequential_disk_conflict(wanted, existing):
                return vr.ERR_DISK_CONFLICT
            return None
        if name == "NodeVolumeLimits":
            return self._volume_limits_filter(pod, j)
        if name == "VolumeBinding":
            from ..plugins import volumebinding as vb

            code = self._vb_filter_code(pod, j)
            return vb.decode_filter(code, j, None) if code else None
        if name == "VolumeZone":
            return self._volume_zone_filter(pod, j)
        raise ValueError(name)

    # ---------------- volume plugins (scalar) ---------------------------

    def _pod_pvcs(self, pod):
        from ..state.volumes import pod_pvc_keys

        return pod_pvc_keys(pod)

    def _volume_zone_filter(self, pod, j) -> str | None:
        from ..plugins.volumezone import ERR_VOLUME_ZONE_CONFLICT
        from ..state.volumes import ZONE_LABELS

        for key in self._pod_pvcs(pod):
            pvc = self.vt.pvcs.get(key)
            if pvc is None or not pvc.volume_name:
                continue
            vi = self.vt.pv_index.get(pvc.volume_name)
            if vi is None:
                continue
            labels = self.vt.pvs[vi].labels
            for zk in ZONE_LABELS:
                if zk not in labels:
                    continue
                allowed = {z.strip() for z in str(labels[zk]).split(",")}
                if self.labels[j].get(zk) not in allowed:
                    return ERR_VOLUME_ZONE_CONFLICT
        return None

    def _volume_limits_filter(self, pod, j) -> str | None:
        from ..plugins.nodevolumelimits import ERR_MAX_VOLUME_COUNT, pod_csi_volumes

        if not self.vt.csi_limits:
            return None
        on_node: set[tuple[str, str]] = set()
        for ap, aj in self.assigned:
            if aj == j:
                on_node.update(pod_csi_volumes(self.vt, ap))
        new = set(pod_csi_volumes(self.vt, pod)) - on_node
        # only drivers the pod adds NEW volumes for are checked (upstream
        # returns nil when newVolumes is empty)
        for drv in {d for d, _ in new}:
            limits = self.vt.csi_limits.get(drv)
            if limits is None or limits[j] < 0:
                continue
            cnt = sum(1 for d, _ in on_node | new if d == drv)
            if cnt > limits[j]:
                return ERR_MAX_VOLUME_COUNT
        return None

    def _vb_classified(self, pod):
        from ..plugins.volumebinding import classify_pod

        key = id(pod)
        got = self._cycle.get(("vb", key))
        if got is None:
            got = classify_pod(self.vt, pod)
            self._cycle[("vb", key)] = got
        return got

    def _vb_filter_code(self, pod, j) -> int:
        """Bitmask mirroring plugins/volumebinding.filter_kernel, computed
        scalar-style: bound-PV affinity/existence + greedy matching of
        unbound WFFC claims (smallest capacity, lowest index, excluding
        claims made by earlier-bound pods and earlier slots of this pod)."""
        from ..plugins.volumebinding import (
            CODE_BIND_CONFLICT, CODE_NODE_CONFLICT, CODE_PV_NOT_EXIST,
        )
        from ..state.volumes import NO_PROVISIONER, allowed_topologies_match

        _, bound, unbound = self._vb_classified(pod)
        code = 0
        for b in bound:
            if b < 0:
                code |= CODE_PV_NOT_EXIST
            elif not self.vt.pv_node_ok[b, j]:
                code |= CODE_NODE_CONFLICT
        chosen: set[int] = set()
        for pvc in unbound:
            vi = self._vb_pick(pvc, j, chosen)
            if vi is not None:
                chosen.add(vi)
                continue
            sc = self.vt.classes[pvc.storage_class or ""]
            can_provision = (
                sc.provisioner and sc.provisioner != NO_PROVISIONER
                and allowed_topologies_match(sc, self.labels[j])
            )
            if not can_provision:
                code |= CODE_BIND_CONFLICT
        return code

    def _vb_pick(self, pvc, j, chosen: set[int]) -> int | None:
        from ..state.volumes import pv_matches_claim

        best = None
        for vi, pv in enumerate(self.vt.pvs):
            if self.pv_claimed[vi] or vi in chosen:
                continue
            if not self.vt.pv_node_ok[vi, j]:
                continue
            if not pv_matches_claim(pv, pvc):
                continue
            if best is None or pv.capacity < self.vt.pvs[best].capacity:
                best = vi
        return best

    def _vb_bind(self, pod, j) -> None:
        """Claim the PVs the greedy matcher picks on the bound node."""
        _, _, unbound = self._vb_classified(pod)
        chosen: set[int] = set()
        for pvc in unbound:
            vi = self._vb_pick(pvc, j, chosen)
            if vi is not None:
                chosen.add(vi)
        for vi in chosen:
            self.pv_claimed[vi] = True

    def _prefilter_reject(self, pod):
        """-> (plugin name, message) of the first PreFilter reject in
        config order, or None (upstream RunPreFilterPlugins stops at the
        first non-success status)."""
        from ..plugins.volumerestrictions import ERR_RWOP_CONFLICT, pod_rwop_keys

        for name in self.config.prefilters():
            if name == "NodeAffinity":
                names = self._affinity_node_names(pod)
                if names is not None and not names:
                    return name, "pod affinity terms conflict"
            elif name == "VolumeRestrictions":
                for key in self._pod_pvcs(pod):
                    if key not in self.vt.pvcs:
                        pvc_name = key.split("/", 1)[1]
                        return name, f'persistentvolumeclaim "{pvc_name}" not found'
                mine = set(pod_rwop_keys(self.vt, pod))
                if mine:
                    for ap, _ in self.assigned:
                        if mine & set(pod_rwop_keys(self.vt, ap)):
                            return name, ERR_RWOP_CONFLICT
            elif name == "VolumeBinding":
                reject, _, _ = self._vb_classified(pod)
                if reject is not None:
                    return name, reject
        return None

    @staticmethod
    def _affinity_node_names(pod) -> set[str] | None:
        """upstream v1.32 NodeAffinity.PreFilter: the node names the pod's
        required terms pin it to — per term the intersection of the value
        sets of its `metadata.name In` field requirements, over terms the
        union — or None where a term has no such requirement (or there is
        no term): every node stays eligible.  An empty set: the terms
        conflict."""
        required = (((_spec(pod).get("affinity") or {}).get("nodeAffinity"))
                    or {}).get("requiredDuringSchedulingIgnoredDuringExecution")
        terms = (required or {}).get("nodeSelectorTerms") or []
        if not terms:
            return None
        union: set[str] = set()
        for term in terms:
            pinned = [set(r.get("values") or [])
                      for r in term.get("matchFields") or []
                      if r.get("key") == "metadata.name"
                      and r.get("operator") == "In"]
            if not pinned:
                return None
            union |= set.intersection(*pinned)
        return union

    def _prefilter_results(self, pod) -> dict[str, list[str]]:
        """plugin -> the sorted node names of the PreFilterResult it
        returns for the pod (upstream's order is a set's; sorted here,
        docs/SEMANTICS.md).  Only NodeAffinity returns one."""
        out = {}
        if "NodeAffinity" in self.config.prefilters():
            names = self._affinity_node_names(pod)
            if names:
                out["NodeAffinity"] = sorted(names)
        return out

    def _filter_skip(self, name, pod) -> bool:
        if name == "NodePorts":
            from ..plugins.ports import pod_host_ports

            return not pod_host_ports(pod)
        if name == "NodeAffinity":
            spec = _spec(pod)
            req = (((spec.get("affinity") or {}).get("nodeAffinity")) or {}).get(
                "requiredDuringSchedulingIgnoredDuringExecution"
            )
            return (not spec.get("nodeSelector") and not req
                    and not self._added_affinity.get(
                        "requiredDuringSchedulingIgnoredDuringExecution"))
        if name == "PodTopologySpread":
            cs = _spec(pod).get("topologySpreadConstraints") or []
            return not any(c.get("whenUnsatisfiable", "DoNotSchedule") == "DoNotSchedule" for c in cs)
        if name == "InterPodAffinity":
            return self._interpod_filter_skip(pod)
        if name == "VolumeRestrictions":
            from ..plugins.volumerestrictions import pod_inline_disks, pod_rwop_keys

            return not pod_inline_disks(pod) and not pod_rwop_keys(self.vt, pod)
        if name in ("NodeVolumeLimits", "VolumeBinding"):
            return not self._pod_pvcs(pod)
        if name == "VolumeZone":
            from ..state.volumes import ZONE_LABELS

            for key in self._pod_pvcs(pod):
                pvc = self.vt.pvcs.get(key)
                if pvc is None or not pvc.volume_name:
                    continue
                vi = self.vt.pv_index.get(pvc.volume_name)
                if vi is not None and any(
                    zk in self.vt.pvs[vi].labels for zk in ZONE_LABELS
                ):
                    return False
            return True
        return False

    def _score_skip(self, name, pod) -> bool:
        if name == "NodeAffinity":
            pref = (((_spec(pod).get("affinity") or {}).get("nodeAffinity")) or {}).get(
                "preferredDuringSchedulingIgnoredDuringExecution"
            )
            return not pref and not self._added_affinity.get(
                "preferredDuringSchedulingIgnoredDuringExecution")
        if name == "PodTopologySpread":
            cs = _spec(pod).get("topologySpreadConstraints") or []
            return not any(c.get("whenUnsatisfiable", "DoNotSchedule") == "ScheduleAnyway" for c in cs)
        return False

    def _resource_active(self, rname: str, req, alloc: int) -> bool:
        """Upstream resource_allocation.go skips resources with zero
        allocatable, and calculateResourceAllocatableRequest bypasses
        scalar (extended) resources the pod does not request."""
        if alloc <= 0:
            return False
        from ..plugins.fitscoring import NATIVE_RESOURCES

        if rname in NATIVE_RESOURCES:
            return True
        if rname in self.schema.columns:
            return int(req[self.schema.columns.index(rname)]) > 0
        return False

    def _req_alloc_for(self, rname: str, req, nz, j,
                       use_requested: bool = False) -> tuple[int, int]:
        """(requested incl. this pod, allocatable) for one scored resource;
        cpu/memory use the non-zero accumulators unless use_requested
        (upstream useRequested=true for RequestedToCapacityRatio), others
        always raw requests."""
        if rname == "cpu":
            if use_requested:
                return int(self.requested[j][CPU]) + int(req[CPU]), int(self.table.allocatable[j][CPU])
            return self.nonzero[j][0] + int(nz[0]), int(self.table.allocatable[j][CPU])
        if rname == "memory":
            if use_requested:
                return int(self.requested[j][MEMORY]) + int(req[MEMORY]), int(self.table.allocatable[j][MEMORY])
            return self.nonzero[j][1] + int(nz[1]), int(self.table.allocatable[j][MEMORY])
        if rname in self.schema.columns:
            c = self.schema.columns.index(rname)
            return int(self.requested[j][c]) + int(req[c]), int(self.table.allocatable[j][c])
        return 0, 0

    def _score(self, name, pod, req, nz, j) -> int:
        if self.config.is_custom(name):
            return int(self.config.custom[name].score(pod, self.node_manifests[j]))
        if name == "NodeResourcesFit":
            from ..plugins.fitscoring import (
                REQUESTED_TO_CAPACITY_RATIO, parse_fit_strategy, score_resource)

            strategy = parse_fit_strategy(self.config.args.get(name))
            rtcr = strategy.stype == REQUESTED_TO_CAPACITY_RATIO
            total, wsum = 0, 0
            for rname, w in strategy.resources:
                r, alloc = self._req_alloc_for(rname, req, nz, j,
                                               use_requested=rtcr)
                if not self._resource_active(rname, req, alloc):
                    continue  # excluded from the weight sum too
                s = score_resource(strategy, r, alloc)
                if rtcr and s <= 0:
                    continue  # RTCR drops zero-score resources entirely
                total += s * w
                wsum += w
            if wsum <= 0:
                return 0
            if rtcr:  # math.Round: half away from zero (non-negative here)
                return (2 * total + wsum) // (2 * wsum)
            return total // wsum
        if name == "NodeResourcesBalancedAllocation":
            from ..plugins.fitscoring import balanced_std, parse_balanced_resources

            fracs = []
            for rname in parse_balanced_resources(self.config.args.get(name)):
                r, alloc = self._req_alloc_for(rname, req, nz, j)
                if not self._resource_active(rname, req, alloc):
                    continue
                fracs.append(min(float(r) / float(alloc), 1.0))
            return int((1.0 - balanced_std(fracs)) * MAX_NODE_SCORE)
        if name == "NodeAffinity":
            pref = (((_spec(pod).get("affinity") or {}).get("nodeAffinity")) or {}).get(
                "preferredDuringSchedulingIgnoredDuringExecution"
            ) or []
            pref = pref + (self._added_affinity.get(
                "preferredDuringSchedulingIgnoredDuringExecution") or [])
            s = 0
            for term in pref:
                if node_selector_term_matches(term.get("preference") or {}, self.labels[j], self.names[j]):
                    s += int(term.get("weight", 0))
            return s
        if name == "TaintToleration":
            tols = [
                t
                for t in (_spec(pod).get("tolerations") or [])
                if (t.get("effect") or "") in ("", PREFER_NO_SCHEDULE)
            ]
            cnt = 0
            for key, value, eff in self.table.taints[j]:
                if eff == PREFER_NO_SCHEDULE and not tolerations_tolerate(
                    tols, key, value, PREFER_NO_SCHEDULE
                ):
                    cnt += 1
            return cnt
        if name == "PodTopologySpread":
            return self._spread_score(pod, j)
        if name == "InterPodAffinity":
            return self._interpod_score(pod, j)
        if name == "VolumeBinding":
            return 0  # VolumeCapacityPriority off: scorer nil -> 0
        if name == "ImageLocality":
            from ..plugins import imagelocality

            row = self._cycle.get("image_row")
            if row is None:
                if self._image_states is None:
                    self._image_states = imagelocality.node_image_states(self.node_manifests)
                row = imagelocality.score_for(pod, self._image_states, self.n)
                self._cycle["image_row"] = row
            return int(row[j])
        raise ValueError(name)

    def _normalize(self, name, scores: dict[int, int], pod) -> dict[int, int]:
        if self.config.is_custom(name):
            plugin = self.config.custom[name]
            if getattr(plugin, "has_normalize", False):
                # upstream passes the feasible nodes' NodeScoreList in
                # node order (wrappedplugin.go:388-415 wraps out-of-tree
                # ScoreExtensions identically to in-tree ones)
                idx = sorted(scores)
                vals = list(plugin.normalize([int(scores[j]) for j in idx]))
                return {j: int(v) for j, v in zip(idx, vals)}
            return dict(scores)
        if name in ("NodeResourcesFit", "NodeResourcesBalancedAllocation", "ImageLocality",
                    "VolumeBinding"):
            return dict(scores)  # no ScoreExtensions
        if name in ("NodeAffinity", "TaintToleration"):
            reverse = name == "TaintToleration"
            mx = max(scores.values(), default=0)
            if mx == 0:
                if reverse:
                    return {j: MAX_NODE_SCORE for j in scores}
                return dict(scores)
            out = {}
            for j, s in scores.items():
                v = s * MAX_NODE_SCORE // mx
                out[j] = MAX_NODE_SCORE - v if reverse else v
            return out
        if name == "PodTopologySpread":
            return self._spread_normalize(scores, pod)
        if name == "InterPodAffinity":
            mn = min(scores.values(), default=0)
            mx = max(scores.values(), default=0)
            diff = mx - mn
            out = {}
            for j, s in scores.items():
                out[j] = int(MAX_NODE_SCORE * (float(s - mn) / float(diff))) if diff > 0 else 0
            return out
        raise ValueError(name)

    # ---------------- PodTopologySpread helpers -------------------------

    def _spread_constraints(self, pod, hard: bool):
        from ..plugins.topologyspread import effective_constraints

        out = []
        for c in effective_constraints(pod):
            is_hard = c.get("whenUnsatisfiable", "DoNotSchedule") == "DoNotSchedule"
            if is_hard == hard:
                out.append(c)
        return out

    def _count_by_node(self, ns: str, selector) -> dict[int, int]:
        """Existing pods matching (ns, selector) per NODE — computed once
        per scheduling cycle and selector, like upstream's
        countPodsMatchSelector over each nodeInfo.Pods."""
        memo = self._cycle.setdefault("spread_per_node", {})
        mk = (ns, json.dumps(selector, sort_keys=True))
        counts = memo.get(mk)
        if counts is None:
            counts = memo[mk] = {}
            for ap, aj in self.assigned:
                if (_meta(ap).get("namespace") or "default") != ns:
                    continue
                lab = {k: str(v) for k, v in (_meta(ap).get("labels") or {}).items()}
                if label_selector_matches(selector, lab):
                    counts[aj] = counts.get(aj, 0) + 1
        return counts

    def _spread_keyed(self, constraints) -> list[bool]:
        """Per node: does it carry every topology key of `constraints`
        (upstream nodeLabelsMatchSpreadConstraints)."""
        keys = [c.get("topologyKey", "") for c in constraints]
        return [all(k in self.labels[j] for k in keys) for j in range(self.n)]

    def _eligible_nodes(self, pod, c=None):
        """Per-constraint node inclusion (upstream matchNodeInclusionPolicies):
        nodeAffinityPolicy Honor (default) applies the pod's nodeSelector +
        required node affinity; nodeTaintsPolicy Honor (default Ignore)
        additionally excludes nodes with untolerated NoSchedule/NoExecute
        taints."""
        spec = _spec(pod)
        aff_policy = (c or {}).get("nodeAffinityPolicy") or "Honor"
        taint_policy = (c or {}).get("nodeTaintsPolicy") or "Ignore"
        sel = spec.get("nodeSelector") or {} if aff_policy == "Honor" else {}
        req = ((((spec.get("affinity") or {}).get("nodeAffinity")) or {}).get(
            "requiredDuringSchedulingIgnoredDuringExecution"
        ) if aff_policy == "Honor" else None)
        tols = spec.get("tolerations") or []
        out = []
        for j in range(self.n):
            ok = all(self.labels[j].get(k) == str(v) for k, v in sel.items()) if sel else True
            if ok and req:
                ok = node_selector_matches(req, self.labels[j], self.names[j])
            if ok and taint_policy == "Honor":
                from ..state.selectors import has_untolerated_do_not_schedule_taint

                ok = not has_untolerated_do_not_schedule_taint(
                    self.table.taints[j], tols)
            out.append(ok)
        return out

    def _spread_prefilter_state(self, pod) -> list[dict]:
        """Per-cycle state for the DoNotSchedule constraints (upstream
        preFilterState: TpPairToMatchNum counted BY NODE, over the nodes
        that pass the constraint's inclusion policies and carry every
        DoNotSchedule key, + critical-path min)."""
        if "spread_filter" in self._cycle:
            return self._cycle["spread_filter"]
        ns = _meta(pod).get("namespace") or "default"
        pod_labels = {k: str(v) for k, v in (_meta(pod).get("labels") or {}).items()}
        state = []
        hard = self._spread_constraints(pod, hard=True)
        keyed = self._spread_keyed(hard)
        for c in hard:
            eligible = self._eligible_nodes(pod, c)
            key = c.get("topologyKey", "")
            sel = c.get("labelSelector")
            per_node = self._count_by_node(ns, sel)
            counts: dict[str, int] = {}
            for k in range(self.n):
                if eligible[k] and keyed[k]:
                    val = self.labels[k][key]
                    counts[val] = counts.get(val, 0) + per_node.get(k, 0)
            min_match = min(counts.values(), default=None)
            md = c.get("minDomains")
            if md is not None and 0 < len(counts) < int(md):
                # upstream getMinMatchNum: fewer (but nonzero — a zero-
                # domain key errors upstream and the constraint is
                # skipped) counted domains than minDomains -> the global
                # minimum is treated as 0
                min_match = 0
            state.append({
                "key": key,
                "max_skew": int(c.get("maxSkew", 1)),
                "self_match": 1 if label_selector_matches(sel, pod_labels) else 0,
                "counts": counts,
                "min_match": min_match,  # None: no counted domain -> pass
            })
        self._cycle["spread_filter"] = state
        return state

    def _spread_prescore_state(self, pod) -> dict:
        """Per-cycle state for the ScheduleAnyway constraints (upstream
        PreScore over the cycle's FILTERED nodes, self._cycle["feasible"]):
        the ignored nodes (lacking a scored key), and per constraint the
        counts (the node's own for the hostname key, by topology pair over
        the counted nodes otherwise) and topologyNormalizingWeight."""
        if "spread_score" in self._cycle:
            return self._cycle["spread_score"]
        ns = _meta(pod).get("namespace") or "default"
        soft = self._spread_constraints(pod, hard=False)
        keyed = self._spread_keyed(soft)
        live = [j for j in self._cycle["feasible"] if keyed[j]]
        constraints = []
        for c in soft:
            key = c.get("topologyKey", "")
            per_node = self._count_by_node(ns, c.get("labelSelector"))
            if key == "kubernetes.io/hostname":
                pairs = None
                size = len(live)
            else:
                eligible = self._eligible_nodes(pod, c)
                pairs = {self.labels[j][key]: 0 for j in live}
                for k in range(self.n):
                    if keyed[k] and eligible[k] and self.labels[k][key] in pairs:
                        pairs[self.labels[k][key]] += per_node.get(k, 0)
                size = len(pairs)
            constraints.append({
                "key": key,
                "max_skew": int(c.get("maxSkew", 1)),
                "per_node": per_node,
                "pairs": pairs,
                "weight": math.log(float(size + 2)),
            })
        state = {"keyed": keyed, "constraints": constraints}
        self._cycle["spread_score"] = state
        return state

    def _spread_filter(self, pod, j) -> str | None:
        for c in self._spread_prefilter_state(pod):
            val = self.labels[j].get(c["key"])
            if val is None:
                return "node(s) didn't match pod topology spread constraints (missing required label)"
            if c["min_match"] is None:
                # upstream minMatchNum stays MaxInt when no counted domain
                # exists -> skew is negative -> the constraint passes
                continue
            skew = c["counts"].get(val, 0) + c["self_match"] - c["min_match"]
            if skew > c["max_skew"]:
                return "node(s) didn't match pod topology spread constraints"
        return None

    def _spread_score(self, pod, j) -> int:
        state = self._spread_prescore_state(pod)
        if not state["keyed"][j]:
            return 0  # ignored node
        total = 0.0
        for c in state["constraints"]:
            if c["pairs"] is None:
                cnt = c["per_node"].get(j, 0)
            else:
                cnt = c["pairs"][self.labels[j][c["key"]]]
            # upstream scoreForCount
            total += float(cnt) * c["weight"] + float(c["max_skew"] - 1)
        return int(math.floor(total + 0.5))

    def _spread_ignored(self, pod, j) -> bool:
        return not self._spread_prescore_state(pod)["keyed"][j]

    def _spread_normalize(self, scores: dict[int, int], pod) -> dict[int, int]:
        scored = {j: s for j, s in scores.items() if not self._spread_ignored(pod, j)}
        mx = max(scored.values(), default=0)
        mn = min(scored.values(), default=0)
        out = {}
        for j, s in scores.items():
            if self._spread_ignored(pod, j):
                out[j] = 0
            elif mx == 0:
                out[j] = MAX_NODE_SCORE
            else:
                out[j] = MAX_NODE_SCORE * (mx + mn - s) // mx
        return out

    # ---------------- InterPodAffinity helpers --------------------------

    def _pod_terms(self, pod, field, preferred):
        """Normalized terms (matchLabelKeys merged, namespaces resolved) —
        the same interpod.effective_terms the tensor build uses.  Memoized
        per pod object: terms and the namespace list are fixed for this
        scheduler's lifetime, and the per-cycle loops call this for every
        queue + assigned pod."""
        key = (id(pod), field, preferred)
        hit = self._term_cache.get(key)
        if hit is None:
            from ..plugins.interpod import effective_terms

            hit = effective_terms(pod, field, preferred, self.namespaces)
            self._term_cache[key] = hit
        return hit

    def _term_matches_pod(self, term, owner_ns, target_pod) -> bool:
        # a resolved-but-EMPTY namespace set matches nothing (upstream:
        # a namespaceSelector matching no namespace selects no pods);
        # only a term lacking the key falls back to the owner namespace
        nss = term.get("namespaces")
        if nss is None:
            nss = [owner_ns]
        tns = _meta(target_pod).get("namespace") or "default"
        if tns not in nss:
            return False
        lab = {k: str(v) for k, v in (_meta(target_pod).get("labels") or {}).items()}
        return label_selector_matches(term.get("labelSelector"), lab)

    def _interpod_filter_skip(self, pod) -> bool:
        if self._pod_terms(pod, "podAffinity", False) or self._pod_terms(pod, "podAntiAffinity", False):
            return False
        # coarse workload-level check, mirrored by the tensor engine: no
        # pod anywhere in the workload carries required anti-affinity
        for p in self.pods + [ap for ap, _ in self.assigned]:
            if self._pod_terms(p, "podAntiAffinity", False):
                return False
        return True

    def _term_counts_by_domain(self, term, owner_ns) -> tuple[dict[str, int], int]:
        """(matching existing pods per domain value of the term's key,
        total over keyed nodes) — per-cycle PreFilter-style precompute."""
        key = term.get("topologyKey", "")
        counts: dict[str, int] = {}
        total = 0
        for ap, aj in self.assigned:
            val = self.labels[aj].get(key)
            if val is None:
                continue
            if self._term_matches_pod(term, owner_ns, ap):
                counts[val] = counts.get(val, 0) + 1
                total += 1
        return counts, total

    def _interpod_filter_state(self, pod) -> dict:
        """Per-cycle state (upstream preFilterState: affinityCounts,
        antiAffinityCounts, existingAntiAffinityCounts)."""
        if "interpod_filter" in self._cycle:
            return self._cycle["interpod_filter"]
        ns = _meta(pod).get("namespace") or "default"
        aff_terms = self._pod_terms(pod, "podAffinity", False)
        anti_terms = self._pod_terms(pod, "podAntiAffinity", False)
        aff = [(t, *self._term_counts_by_domain(t, ns)) for t, _ in aff_terms]
        anti = [(t, self._term_counts_by_domain(t, ns)[0]) for t, _ in anti_terms]
        existing_anti: dict[tuple[str, str], int] = {}
        for ap, aj in self.assigned:
            ans = _meta(ap).get("namespace") or "default"
            for term, _ in self._pod_terms(ap, "podAntiAffinity", False):
                key = term.get("topologyKey", "")
                val = self.labels[aj].get(key)
                if val is None or not self._term_matches_pod(term, ans, pod):
                    continue
                existing_anti[(key, val)] = existing_anti.get((key, val), 0) + 1
        pod_self = {"metadata": _meta(pod)}
        state = {
            "aff": aff,
            "anti": anti,
            "existing_anti": existing_anti,
            "self_ok": all(self._term_matches_pod(t, ns, pod_self) for t, _ in aff_terms),
        }
        self._cycle["interpod_filter"] = state
        return state

    def _interpod_filter(self, pod, j) -> str | None:
        st = self._interpod_filter_state(pod)
        # 1. required affinity
        if st["aff"]:
            all_ok = all(
                (val := self.labels[j].get(term.get("topologyKey", ""))) is not None
                and counts.get(val, 0) > 0
                for term, counts, _ in st["aff"]
            )
            if not all_ok:
                # first-pod-in-series escape: no existing pod (on a keyed
                # node) matches any term, the pod matches its own terms,
                # and the node has all term keys
                any_match_anywhere = any(total > 0 for _, _, total in st["aff"])
                node_has_keys = all(
                    term.get("topologyKey", "") in self.labels[j] for term, _, _ in st["aff"]
                )
                if not (not any_match_anywhere and st["self_ok"] and node_has_keys):
                    return "node(s) didn't match pod affinity rules"
        # 2. required anti-affinity
        for term, counts in st["anti"]:
            val = self.labels[j].get(term.get("topologyKey", ""))
            if val is not None and counts.get(val, 0) > 0:
                return "node(s) didn't match pod anti-affinity rules"
        # 3. existing pods' required anti-affinity vs this pod
        for (key, val), cnt in st["existing_anti"].items():
            if cnt > 0 and self.labels[j].get(key) == val:
                return "node(s) didn't satisfy existing pods anti-affinity rules"
        return None

    def _interpod_score_state(self, pod) -> dict:
        if "interpod_score" in self._cycle:
            return self._cycle["interpod_score"]
        ns = _meta(pod).get("namespace") or "default"
        own = []
        for term, w in self._pod_terms(pod, "podAffinity", True):
            counts, _ = self._term_counts_by_domain(term, ns)
            own.append((term.get("topologyKey", ""), counts, w))
        for term, w in self._pod_terms(pod, "podAntiAffinity", True):
            counts, _ = self._term_counts_by_domain(term, ns)
            own.append((term.get("topologyKey", ""), counts, -w))
        hard_w = int((self.config.args.get("InterPodAffinity") or {})
                     .get("hardPodAffinityWeight") or 1)
        sym: dict[tuple[str, str], int] = {}
        for ap, aj in self.assigned:
            ans = _meta(ap).get("namespace") or "default"
            for term, w, sign in (
                [(t, w, 1) for t, w in self._pod_terms(ap, "podAffinity", True)]
                + [(t, w, -1) for t, w in self._pod_terms(ap, "podAntiAffinity", True)]
                + [(t, hard_w, 1) for t, _ in self._pod_terms(ap, "podAffinity", False)]
            ):
                key = term.get("topologyKey", "")
                val = self.labels[aj].get(key)
                if val is None or not self._term_matches_pod(term, ans, pod):
                    continue
                sym[(key, val)] = sym.get((key, val), 0) + sign * w
        state = {"own": own, "sym": sym}
        self._cycle["interpod_score"] = state
        return state

    def _interpod_score(self, pod, j) -> int:
        st = self._interpod_score_state(pod)
        score = 0
        for key, counts, w in st["own"]:
            val = self.labels[j].get(key)
            if val is not None:
                score += w * counts.get(val, 0)
        for (key, val), delta in st["sym"].items():
            if self.labels[j].get(key) == val:
                score += delta
        return score

    # ---------------- the cycle -----------------------------------------

    def schedule_one(self, pod) -> tuple[dict[str, str], int]:
        """-> (annotations, selected node idx or -1); binds on success."""
        cfg = self.config
        self._cycle = {}  # per-cycle PreFilter/PreScore state cache
        req, nz = pod_resource_request(pod, self.schema)

        reject = self._prefilter_reject(pod)
        if reject is not None:
            rej_name, rej_msg = reject
            pf: dict[str, str] = {}
            for nm in cfg.prefilters():
                if nm == rej_name:
                    pf[nm] = rej_msg
                    break
                pf[nm] = "" if self._filter_skip(nm, pod) else ann.SUCCESS_MESSAGE
            empty = ann.marshal({})
            # results returned before the rejecting plugin are on record
            returned = {nm: names
                        for nm, names in self._prefilter_results(pod).items()
                        if nm in pf and nm != rej_name}
            return {
                ann.PRE_FILTER_STATUS_RESULT: ann.marshal(pf),
                ann.PRE_FILTER_RESULT: ann.marshal(returned),
                ann.FILTER_RESULT: empty,
                ann.POST_FILTER_RESULT: empty,
                ann.PRE_SCORE_RESULT: empty,
                ann.SCORE_RESULT: empty,
                ann.FINAL_SCORE_RESULT: empty,
                ann.RESERVE_RESULT: empty,
                ann.PERMIT_STATUS_RESULT: empty,
                ann.PERMIT_TIMEOUT_RESULT: empty,
                ann.PRE_BIND_RESULT: empty,
                ann.BIND_RESULT: empty,
                ann.SELECTED_NODE: "",
            }, -1

        prefilter_status = {
            name: ("" if self._filter_skip(name, pod) else ann.SUCCESS_MESSAGE)
            for name in cfg.prefilters()
        }

        active = [n for n in cfg.filters() if not self._filter_skip(n, pod)]
        filter_map: dict[str, dict[str, str]] = {}
        feasible: list[int] = []
        # upstream findNodesThatFitPod: with a PreFilterResult, Filter runs
        # on the nodes it names (those that exist) and on no other; several
        # plugins' results intersect (PreFilterResult.Merge)
        narrowed = self._prefilter_results(pod)
        considered = range(self.n)
        if narrowed:
            merged = set.intersection(*(set(v) for v in narrowed.values()))
            considered = sorted(self._name_idx[nm] for nm in merged
                                if nm in self._name_idx)
        for j in considered:
            entry = {}
            ok = True
            for name in active:
                msg = self._filter(name, pod, req, j)
                if msg is None:
                    entry[name] = ann.PASSED_FILTER_MESSAGE
                else:
                    entry[name] = msg
                    ok = False
                    break
            if entry:
                filter_map[self.names[j]] = entry
            if ok:
                feasible.append(j)

        prescore: dict[str, str] = {}
        score_map: dict[str, dict[str, str]] = {}
        final_map: dict[str, dict[str, str]] = {}
        selected = -1
        if len(feasible) == 1:
            selected = feasible[0]
        elif len(feasible) > 1:
            # what upstream's PreScore plugins are handed: the filtered nodes
            self._cycle["feasible"] = feasible
            for name in cfg.prescorers():
                prescore[name] = "" if self._score_skip(name, pod) else ann.SUCCESS_MESSAGE
            totals = {j: 0 for j in feasible}
            for name in cfg.scorers():
                if self._score_skip(name, pod):
                    continue
                raw = {j: self._score(name, pod, req, nz, j) for j in feasible}
                normed = self._normalize(name, raw, pod)
                w = cfg.weight(name)
                for j in feasible:
                    score_map.setdefault(self.names[j], {})[name] = str(raw[j])
                    final = normed[j] * w
                    final_map.setdefault(self.names[j], {})[name] = str(final)
                    totals[j] += final
            best = max(totals.values())
            selected = min(j for j, t in totals.items() if t == best)

        if selected >= 0:
            self.requested[selected] = self.requested[selected] + req
            self.nonzero[selected][0] += int(nz[0])
            self.nonzero[selected][1] += int(nz[1])
            self.num_pods[selected] += 1
            self.assigned.append((pod, selected))
            if "VolumeBinding" in self.config.enabled and self._pod_pvcs(pod):
                self._vb_bind(pod, selected)

        vb_on = (
            "VolumeBinding" in self.config.enabled
            and not self.config.is_custom("VolumeBinding")
        )
        reserve_map = (
            {"VolumeBinding": ann.SUCCESS_MESSAGE} if selected >= 0 and vb_on else {}
        )

        annotations = {
            ann.PRE_FILTER_STATUS_RESULT: ann.marshal(prefilter_status),
            ann.PRE_FILTER_RESULT: ann.marshal(narrowed),
            ann.FILTER_RESULT: ann.marshal(filter_map),
            ann.POST_FILTER_RESULT: ann.marshal({}),
            ann.PRE_SCORE_RESULT: ann.marshal(prescore),
            ann.SCORE_RESULT: ann.marshal(score_map),
            ann.FINAL_SCORE_RESULT: ann.marshal(final_map),
            ann.RESERVE_RESULT: ann.marshal(reserve_map),
            ann.PERMIT_STATUS_RESULT: ann.marshal({}),
            ann.PERMIT_TIMEOUT_RESULT: ann.marshal({}),
            ann.PRE_BIND_RESULT: ann.marshal(reserve_map),
            ann.BIND_RESULT: ann.marshal(
                {"DefaultBinder": ann.SUCCESS_MESSAGE} if selected >= 0 else {}
            ),
            ann.SELECTED_NODE: self.names[selected] if selected >= 0 else "",
        }
        return annotations, selected

    def schedule_all(self):
        results = []
        for pod in self.pods:
            results.append(self.schedule_one(pod))
        return results
