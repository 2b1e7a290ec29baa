"""A closed loop of ONE client under churn: before every measured pod, one
tick of the deployment's churn op, then the pod as `closed_loop.py` sends
it.

A cycle:
  1. the tick (`mode: recreate`, `number: 1`): DELETE the objects of the
     tick before (in the op's order), POST the next ones (in the op's
     order), each request answered before the next is sent; wait on the
     watch stream for the churn POD's decision and read it in full.  The
     deployment's churn pod fits no node, so well formed means: marked
     Unschedulable, no spec.nodeName, no status.nominatedNodeName, all 13
     result annotations present and parsing, and a filter-result entry for
     every node of the cluster (the initial nodes + the churn node).  A
     read that is not so is the cycle's `problem`;
  2. POST one measured pod, wait for its decision on the watch stream, GET
     it in full (asked again while its annotations are not there yet).

The sample is t0 = the tick's first request -> t1 = the measured pod's
full result in hand; `pods` is 1 a cycle (the measured pod: the churn pod
is load, and is deleted a cycle later, so it is not in `names`, which
run.py reads back after the window).  A POST answered 429 is retried after
its Retry-After and counted (`shed`); a DELETE is not sheddable.  Every
body is encoded before the window opens.

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
_RESOURCE = {"Node": "nodes", "Pod": "pods", "Service": "services"}
_FILTER = "kube-scheduler-simulator.sigs.k8s.io/filter-result"


def _path(obj: dict) -> tuple[str, str]:
    """(collection path, object path) of a churn object."""
    res = _RESOURCE[obj["kind"]]
    meta = obj["metadata"]
    ns = f"/{meta['namespace']}" if "namespace" in meta else ""
    return f"/api/v1/{res}", f"/api/v1/{res}{ns}/{meta['name']}"


class Driver:
    def __init__(self, params: dict, deployment, seed: int):
        self.burst = int(params["burst"])
        if self.burst != 1 or params["submit"] != "create":
            raise ValueError("one measured pod a cycle, created alone")
        self.dep = deployment
        self.churn = deployment.nodes.churn
        self.n_nodes = len(deployment.nodes) + 1  # + the churn node
        self.namespace = deployment.measured_namespace
        self.names: list[list[str]] = []   # measured pods, per cycle
        self.bodies: list[bytes] = []
        # per cycle: [(collection path, object path, body)] in the op's order
        self.ticks: list[list[tuple[str, str, bytes]]] = []
        self.churn_pods: list[tuple[str, str]] = []   # (namespace, name)
        self.live: list[tuple[str, str, bytes]] = []  # what the last tick made

    def provision(self, cycles: int, burst: int | None = None) -> None:
        for _ in range(cycles):
            pod = self.dep.measured_pod()
            self.names.append([pod["metadata"]["name"]])
            self.bodies.append(json.dumps(pod).encode())
            trio = self.churn.trio(len(self.ticks))
            self.ticks.append([(*_path(o), json.dumps(o).encode()) for o in trio])
            cp = next(o for o in trio if o["kind"] == "Pod")["metadata"]
            self.churn_pods.append((cp["namespace"], cp["name"]))

    def _read_full(self, client, read_pod, ns: str, name: str, keys,
                   since: float):
        """GET until the annotations are there -> (pod, seconds, problem,
        retries): the decision is on the stream as soon as the pod is
        marked; its 13 annotations follow when the pass has sealed them."""
        retries = 0
        while True:
            pod, read_s, problem = read_pod(client, ns, name, keys)
            if (problem is None or "lacks annotation" not in problem
                    or time.time() - since > READ_PATIENCE_S):
                return pod, read_s, problem, retries
            retries += 1
            time.sleep(READ_RETRY_S)

    def _churn_pod_problem(self, pod: dict, name: str) -> str | None:
        if (pod.get("spec") or {}).get("nodeName"):
            return f"churn pod {name} was bound"
        if (pod.get("status") or {}).get("nominatedNodeName"):
            return f"churn pod {name} was given a nominated node"
        n = len(json.loads(pod["metadata"]["annotations"][_FILTER]))
        if n != self.n_nodes:
            return (f"churn pod {name}: filter-result holds {n} nodes, the "
                    f"cluster {self.n_nodes}")
        return None

    def cycle(self, k: int, client, watch, keys: list[str], read_pod,
              deadline: float) -> dict:
        if k >= len(self.bodies):  # never inside a window that was sized right
            self.provision(k + 1 - len(self.bodies))
        names = self.names[k]
        ns, churn_pod = self.churn_pods[k]
        t0 = time.time()
        # ---- the tick
        for _, path, _ in self.live:
            code, raw = client.raw("DELETE", path)
            check(code == 200, f"DELETE {path} -> {code}: {raw[:200]!r}")
        self.live, shed = [], 0
        for made in self.ticks[k]:
            shed += client.submit(made[0], made[2], deadline)
            self.live.append(made)
        watch.wait_decided([churn_pod], deadline)
        pod, _, problem, retries = self._read_full(
            client, read_pod, ns, churn_pod, keys, time.time())
        if problem is None:
            problem = self._churn_pod_problem(pod, churn_pod)
        # ---- the measured pod
        t_post = time.time()
        shed += client.submit("/api/v1/pods", self.bodies[k], deadline)
        t_ack = time.time()
        watch.wait_decided(names, deadline)
        t_dec = time.time()
        _, read_s, measured_problem, more = self._read_full(
            client, read_pod, self.namespace, names[0], keys, t_dec)
        t1 = time.time()
        return {"k": k, "t0": t0, "t_ack": t_ack, "submit_s": t_ack - t_post,
                "t_decided": t_dec, "t1": t1, "read_s": read_s, "shed": shed,
                "pods": 1, "read": names[0], "read_retries": retries + more,
                "problem": problem or measured_problem,
                "tick_s": t_post - t0}
