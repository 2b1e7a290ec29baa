"""A closed loop of ONE client whose every pod brings a volume of its own:
what scheduler_perf's `createPods` does for one pod with
`persistentVolumeTemplatePath` / `persistentVolumeClaimTemplatePath`, then
the read `closed_loop.py` makes.

A cycle:
  1. POST the pod's PersistentVolume, POST its PersistentVolumeClaim (bound
     to each other), POST the pod that mounts the claim, each acknowledged
     before the next is sent;
  2. wait on the watch stream for the pod's decision, GET it in full (asked
     again while its annotations are not there yet).

The sample is t0 = the PV's POST -> t1 = the pod's full result in hand;
`pods` is 1 a cycle.  A POST answered 429 (PV, claim or pod) is retried
after its Retry-After and counted (`shed`).  Well formed means, besides
`read_pod`'s checks: the pod's filter-result holds every node of the
cluster, and every node's entry that no refusal ended (an entry stops at
the plugin that refused the node) carries NodeVolumeLimits and
VolumeBinding (the volume family ran against the claim; it did not Skip).
A read that is not so is the cycle's `problem`; it is looked at after t1.  Every body is encoded before the
window opens.

Before its first cycle (in the warm-up, so in `setup_s`) the driver asks
the server for its CSINodes, which a server that does not store the kind
answers 404: such a server's NodeVolumeLimits could never see a limit, and
the run ends there.  Then it imports what run.py's two imports cannot
carry: the CSINodes and the initial pods' PVs and claims
(`deployment.nodes.volumes`), in one POST /api/v1/import.  The initial
pods are bound by then and nothing is pending, so no pass runs before the
volumes are there.

Parameters (the traffic file's `parameters`): `burst` must be 1 and
`submit` "create"; `read` as in closed_loop.py.
"""

from __future__ import annotations

import json
import time

from lib.client import check

IMPORT_PATH = "/api/v1/import?ignoreSchedulerConfiguration=true"
READ_RETRY_S = 0.02
READ_PATIENCE_S = 10.0
_FILTER = "kube-scheduler-simulator.sigs.k8s.io/filter-result"
_FAMILY = ("NodeVolumeLimits", "VolumeBinding")


class Driver:
    def __init__(self, params: dict, deployment, seed: int):
        self.burst = int(params["burst"])
        if self.burst != 1 or params["submit"] != "create":
            raise ValueError("one measured pod a cycle, created alone")
        self.dep = deployment
        self.volumes = deployment.nodes.volumes
        self.n_nodes = len(deployment.nodes)
        self.namespace = deployment.measured_namespace
        self.names: list[list[str]] = []   # measured pods, per cycle
        # per cycle: the PV's, the claim's and the pod's body, in that order
        self.bodies: list[tuple[bytes, bytes, bytes]] = []
        self._initial: bytes | None = json.dumps({
            "pvcs": [pvc for _, pvc in self.volumes.initial],
            "pvs": [pv for pv, _ in self.volumes.initial],
            "csinodes": self.volumes.csinodes}).encode()

    def provision(self, cycles: int, burst: int | None = None) -> None:
        for _ in range(cycles):
            pod = self.dep.measured_pod()
            name = pod["metadata"]["name"]
            pv, pvc = self.volumes.of(name)
            self.names.append([name])
            self.bodies.append(tuple(json.dumps(o).encode()
                                     for o in (pv, pvc, pod)))

    def _import_initial(self, client, deadline: float) -> None:
        code, raw = client.raw("GET", "/api/v1/csinodes")
        check(code == 200, f"GET /api/v1/csinodes -> {code}: the server "
              f"does not store CSINodes ({raw[:120]!r})")
        client.submit(IMPORT_PATH, self._initial, deadline)
        self._initial = None

    def _family_problem(self, pod: dict, name: str) -> str | None:
        entries = json.loads(pod["metadata"]["annotations"][_FILTER])
        if len(entries) != self.n_nodes:
            return (f"pod {name}: filter-result holds {len(entries)} nodes, "
                    f"the cluster {self.n_nodes}")
        passed = [e for e in entries.values()
                  if all(v == "passed" for v in e.values())]
        for plugin in _FAMILY:
            missing = sum(plugin not in e for e in passed)
            if missing:
                return (f"pod {name}: {missing} nodes' filter-result entries "
                        f"lack {plugin}")
        return None

    def cycle(self, k: int, client, watch, keys: list[str], read_pod,
              deadline: float) -> dict:
        if self._initial is not None:
            self._import_initial(client, deadline)
        if k >= len(self.bodies):  # never inside a window that was sized right
            self.provision(k + 1 - len(self.bodies))
        names = self.names[k]
        pv, pvc, pod_body = self.bodies[k]
        t0 = time.time()
        shed = client.submit("/api/v1/persistentvolumes", pv, deadline)
        shed += client.submit("/api/v1/persistentvolumeclaims", pvc, deadline)
        t_post = time.time()
        shed += client.submit("/api/v1/pods", pod_body, deadline)
        t_ack = time.time()
        watch.wait_decided(names, deadline)
        t_dec = time.time()
        retries = 0
        while True:
            pod, read_s, problem = read_pod(client, self.namespace, names[0],
                                            keys)
            if (problem is None or "lacks annotation" not in problem
                    or time.time() - t_dec > READ_PATIENCE_S):
                break
            retries += 1
            time.sleep(READ_RETRY_S)
        t1 = time.time()
        if problem is None:
            problem = self._family_problem(pod, names[0])
        return {"k": k, "t0": t0, "t_ack": t_ack, "submit_s": t_ack - t_post,
                "t_decided": t_dec, "t1": t1, "read_s": read_s, "shed": shed,
                "pods": 1, "read": names[0], "read_retries": retries,
                "problem": problem, "volumes_s": t_post - t0}
