"""scheduler_perf deployments with one more node, created by name.

Upstream's `createNodes` takes a `nodeTemplatePath` with a fixed
`metadata.name` (`node-with-name.yaml` in SchedulingDaemonset: one node,
`scheduler-perf-node`, that every measured pod names in its node
affinity).  This generator is `scheduler_perf.py`'s Deployment with that
one addition: `parameters.named_node` is the node's whole manifest, name
included, and is appended as it stands.

The node is added after the seeded draws, so a seed gives the same names,
node order and initial placement for the other nodes here as in
`scheduler_perf.py`; the named node's place in the server's (sorted) node
order is wherever its name falls among the drawn ones.  Nothing here
imports the program.
"""

from __future__ import annotations

import copy

from generators.scheduler_perf import Deployment


class NamedNodeDeployment(Deployment):
    def __init__(self, params: dict, seed: int):
        super().__init__(params, seed)
        self.nodes.append(copy.deepcopy(params["named_node"]))


def generate(params: dict, seed: int) -> NamedNodeDeployment:
    return NamedNodeDeployment(params, seed)
