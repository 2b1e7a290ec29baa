"""scheduler_perf deployments with a `churn` op: objects that come and go
beside the measured pods.

Upstream's `churn` op (`mode: recreate`, `number: 1`) creates one object
from each of its templates, deletes them, creates the next ones, for as
long as the workload runs.  This generator is `scheduler_perf.py`'s
Deployment plus the endless seeded supply of those objects:
`parameters.churn.templates` are the op's templates in its order, and
`deployment.nodes.churn.trio(i)` is the i-th set drawn from them (here a
node, a pod and a service: a trio), stamped the way measured pods are.

The churn hangs on `deployment.nodes` because that list and the initial
pods are all the oracle child hands a reference (lib/oracle_child.py): a
reference that replays the churn finds it there.  The list itself is the
initial nodes and nothing else, and encodes as a plain list.

Churn names come from a random stream of their own, so a seed gives the
same nodes, initial placement and measured pods here as in
`scheduler_perf.py`, however many trios are drawn.  Nothing here imports
the program.
"""

from __future__ import annotations

import random

from generators.scheduler_perf import Deployment, _Names, _stamp


class Churn:
    def __init__(self, spec: dict, seed: int):
        self.mode, self.number = spec["mode"], int(spec["number"])
        if self.mode != "recreate" or self.number != 1:
            raise ValueError("only `mode: recreate`, `number: 1` is generated")
        self.namespace = spec.get("namespace") or "default"
        self.templates = spec["templates"]
        self._names = _Names(random.Random(f"{seed}:churn"))
        self._trios: list[list[dict]] = []

    def trio(self, i: int) -> list[dict]:
        """The objects of tick i, one per template, in the op's order."""
        while len(self._trios) <= i:
            self._trios.append([
                _stamp(t, self._names.make(t["metadata"]["generateName"]),
                       None if t["kind"] == "Node" else self.namespace)
                for t in self.templates])
        return self._trios[i]


class NodesWithChurn(list):
    """The initial nodes; `.churn` is what will come and go beside them."""

    churn: Churn


class ChurnDeployment(Deployment):
    def __init__(self, params: dict, seed: int):
        super().__init__(params, seed)
        self.nodes = NodesWithChurn(self.nodes)
        self.nodes.churn = Churn(params["churn"], seed)


def generate(params: dict, seed: int) -> ChurnDeployment:
    return ChurnDeployment(params, seed)
