"""scheduler_perf deployments whose pods each mount a volume of their own.

Upstream's `createPods` takes `persistentVolumeTemplatePath` and
`persistentVolumeClaimTemplatePath` (SchedulingCSIPVs, SchedulingInTreePVs,
...): for every pod the harness creates one PersistentVolume and one
PersistentVolumeClaim, bound to each other, and then the pod, which mounts
the claim as its one volume `vol`.  Its `createNodes` takes a
`nodeAllocatableStrategy`: an allocatable key on every node and one CSINode
a node that publishes a driver's attach limit.  This generator is
`scheduler_perf.py`'s Deployment plus those objects:

  * `parameters.volumes`: `pv_template`, `pvc_template`, `volume_name`,
    and `csinode` (`driver`, `count`, `migrated_plugins`);
  * every pod drawn, initial or measured, gets a PV and a PVC stamped from
    the templates: the PV's `claimRef` names the claim and its
    `csi.volumeHandle` is the PV's own name; the claim's `volumeName` names
    the PV; the pod's `spec.volumes` is the one claim;
  * `deployment.nodes.volumes` holds them: `.csinodes` (one a node),
    `.initial` ((pv, pvc) per initial pod, in the pods' order) and
    `.of(pod name)` -> (pv, pvc) of any pod drawn so far.

The volumes hang on `deployment.nodes` because that list and the initial
pods are all the oracle child hands a reference (lib/oracle_child.py), and
a measured pod's pair is filed under the pod's name when the pod is drawn
(`measured_pod()`), which both the driver and the oracle child do once per
pod: a reference finds there what the client created before the pod.  The
list itself is the initial nodes and nothing else, and encodes as a plain
list.

PV and PVC names come from a random stream of their own, so a seed gives
the same nodes, initial placement and pod names here as in
`scheduler_perf.py`.  Nothing here imports the program.
"""

from __future__ import annotations

import random

from generators.scheduler_perf import Deployment, _Names, _stamp


class Volumes:
    def __init__(self, spec: dict, seed: int):
        self.spec = spec
        self._names = _Names(random.Random(f"{seed}:volumes"))
        self.csinodes: list[dict] = []
        self.initial: list[tuple[dict, dict]] = []
        self._by_pod: dict[str, tuple[dict, dict]] = {}

    def csinode_for(self, node_name: str) -> dict:
        c = self.spec["csinode"]
        return {
            "apiVersion": "storage.k8s.io/v1", "kind": "CSINode",
            "metadata": {
                "name": node_name,
                "annotations": {
                    "storage.alpha.kubernetes.io/migrated-plugins":
                        ",".join(c.get("migrated_plugins") or [])}},
            "spec": {"drivers": [{
                "name": c["driver"], "nodeID": node_name,
                "allocatable": {"count": int(c["count"])}}]}}

    def attach(self, pod: dict) -> tuple[dict, dict]:
        """Draw the pod's PV and PVC, bound to each other, and mount the
        claim; -> (pv, pvc)."""
        ns = pod["metadata"]["namespace"]
        pv_t, pvc_t = self.spec["pv_template"], self.spec["pvc_template"]
        pv = _stamp(pv_t, self._names.make(pv_t["metadata"]["generateName"]), None)
        pvc = _stamp(pvc_t, self._names.make(pvc_t["metadata"]["generateName"]), ns)
        pv["spec"]["csi"]["volumeHandle"] = pv["metadata"]["name"]
        pv["spec"]["claimRef"] = {
            "apiVersion": "v1", "kind": "PersistentVolumeClaim",
            "namespace": ns, "name": pvc["metadata"]["name"]}
        pvc["spec"]["volumeName"] = pv["metadata"]["name"]
        pod["spec"]["volumes"] = [{
            "name": self.spec["volume_name"],
            "persistentVolumeClaim": {"claimName": pvc["metadata"]["name"]}}]
        self._by_pod[pod["metadata"]["name"]] = (pv, pvc)
        return pv, pvc

    def of(self, pod_name: str) -> tuple[dict, dict]:
        return self._by_pod[pod_name]


class NodesWithVolumes(list):
    """The initial nodes; `.volumes` is what the pods mount and what
    limits the nodes."""

    volumes: Volumes


class VolumeDeployment(Deployment):
    def __init__(self, params: dict, seed: int):
        super().__init__(params, seed)
        vols = Volumes(params["volumes"], seed)
        vols.csinodes = [vols.csinode_for(n["metadata"]["name"])
                         for n in self.nodes]
        vols.initial = [vols.attach(pod) for pod in self.initial_pods]
        self.nodes = NodesWithVolumes(self.nodes)
        self.nodes.volumes = vols

    def measured_pod(self) -> dict:
        pod = super().measured_pod()
        self.nodes.volumes.attach(pod)
        return pod


def generate(params: dict, seed: int) -> VolumeDeployment:
    return VolumeDeployment(params, seed)
