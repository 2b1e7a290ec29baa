"""scheduler_perf deployments whose nodes carry a label unique per node.

Upstream's `createNodes` takes a `uniqueNodeLabelStrategy` (`labelKey:
kubernetes.io/hostname` in SchedulingPodAntiAffinity): every node gets the
key with a value no other node has.  This generator is
`scheduler_perf.py`'s Deployment with that one addition, stated in the
manifest's `metadata.labels` instead of left to whatever a server defaults:
`parameters.unique_node_label` names the key, and the value is the node's
own name (which value upstream draws is not part of the source's shape;
the configuration lists it under `assumed`).

The label is written after the seeded draws, so a seed gives the same
names, node order and initial placement here as in `scheduler_perf.py`.
Nothing here imports the program.
"""

from __future__ import annotations

from generators.scheduler_perf import Deployment


class UniqueLabelDeployment(Deployment):
    def __init__(self, params: dict, seed: int):
        super().__init__(params, seed)
        key = params["unique_node_label"]
        for node in self.nodes:
            meta = node["metadata"]
            meta["labels"] = {**(meta.get("labels") or {}), key: meta["name"]}


def generate(params: dict, seed: int) -> UniqueLabelDeployment:
    return UniqueLabelDeployment(params, seed)
