"""A closed loop of ONE client: submit a burst of measured pods, learn
from the watch stream that every one of them is decided, read one of them
in full (all 13 result annotations), then submit the next burst.

Parameters (the traffic file's `parameters`):
  burst       pods per cycle
  submit      "import": one POST /api/v1/import with the whole burst (a
              ReplicaSet scale-up); "create": POST /api/v1/pods, one pod
  read        which pod of the burst is read in full: "seeded" draws it
              from the run's seed
  think_s     (optional) the client's pause after a cycle, before the
              next submit; outside the cycle's time

A request answered 429 is retried after its Retry-After, and the cycle's
time counts from the first attempt.  Every request body is encoded
before the window opens.
"""

from __future__ import annotations

import json
import random
import time

IMPORT_PATH = "/api/v1/import?ignoreSchedulerConfiguration=true"
READ_RETRY_S = 0.02
READ_PATIENCE_S = 10.0


class Driver:
    def __init__(self, params: dict, deployment, seed: int):
        self.burst = int(params["burst"])
        self.submit = params["submit"]
        self.think_s = float(params.get("think_s", 0.0))
        if self.submit == "create" and self.burst != 1:
            raise ValueError("submit=create sends one pod per cycle")
        self.dep = deployment
        self.rng = random.Random(f"{seed}:read")
        self.names: list[list[str]] = []   # per provisioned cycle
        self.bodies: list[bytes] = []
        self.namespace = deployment.measured_namespace

    def provision(self, cycles: int, burst: int | None = None) -> None:
        """Draw and encode the bodies of `cycles` more cycles (of `burst`
        pods: the warm-up's smaller shapes; the mix's own by default)."""
        for _ in range(cycles):
            pods = [self.dep.measured_pod() for _ in range(burst or self.burst)]
            self.names.append([p["metadata"]["name"] for p in pods])
            body = {"pods": pods} if self.submit == "import" else pods[0]
            self.bodies.append(json.dumps(body).encode())

    def cycle(self, k: int, client, watch, keys: list[str], read_pod,
              deadline: float) -> dict:
        if k >= len(self.bodies):  # never inside a window that was sized right
            self.provision(k + 1 - len(self.bodies))
        names = self.names[k]
        pick = names[self.rng.randrange(len(names))]
        path = IMPORT_PATH if self.submit == "import" else "/api/v1/pods"
        t0 = time.time()
        shed = client.submit(path, self.bodies[k], deadline)
        t_ack = time.time()
        watch.wait_decided(names, deadline)
        t_dec = time.time()
        # the decision is on the stream as soon as the pod is bound; its 13
        # annotations follow when the pass has sealed its results.  A user
        # who wants the result asks again until it is there: the time counts
        retries = 0
        while True:
            _, read_s, problem = read_pod(client, self.namespace, pick, keys)
            if (problem is None or "lacks annotation" not in problem
                    or time.time() - t_dec > READ_PATIENCE_S):
                break
            retries += 1
            time.sleep(READ_RETRY_S)
        t1 = time.time()
        if self.think_s:
            time.sleep(self.think_s)
        return {"k": k, "t0": t0, "t_ack": t_ack, "submit_s": t_ack - t0, "t_decided": t_dec,
                "t1": t1, "read_s": read_s, "shed": shed, "pods": len(names),
                "read": pick, "read_retries": retries, "problem": problem}
