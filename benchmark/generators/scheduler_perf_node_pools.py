"""scheduler_perf deployments whose nodes come from more than one template.

Upstream's workload templates may hold several `createNodes` ops, each
with its own `nodeTemplatePath` and count (SchedulingWithNodeInclusionPolicy:
`$normalNodes` of node-default, then `$taintNodes` of node-with-taint).
This generator is `scheduler_perf.py`'s Deployment with the pools stated
as data: `parameters.node_pools` is a list of `{"count", "template"}` in
the source's order, and `parameters.unique_node_label`, as in
`scheduler_perf_unique_label.py`, names a label key that every node gets
with its own name for value.

`parameters.nodes` is the cluster's size.  Where an `override` makes it
smaller than the pools' counts add up to (the rehearsal's and the tests'
small sizes), every pool shrinks by the same factor, so the source's
ratio stays (4 : 1 gives 40 + 10 of 50); the last pool takes what
rounding leaves.

The first pool's names are drawn first, then the next pool's, all from
the one seeded stream: a seed gives the first pool the names
`scheduler_perf.py` gives its first nodes.  Initial pods, where a size
override asks for any, are drawn after the first pool and sit on its
nodes alone (initial pod i on node perm[i mod n0]).  Nothing here imports
the program.
"""

from __future__ import annotations

from generators.scheduler_perf import Deployment, _stamp


def pool_counts(pools: list[dict], nodes: int) -> list[int]:
    """The pools' counts at a cluster of `nodes`, in the pools' ratio."""
    full = [int(p["count"]) for p in pools]
    if nodes == sum(full):
        return full
    counts = [c * nodes // sum(full) for c in full[:-1]]
    return counts + [nodes - sum(counts)]


class NodePoolsDeployment(Deployment):
    def __init__(self, params: dict, seed: int):
        pools = params["node_pools"]
        counts = pool_counts(pools, int(params["nodes"]))
        super().__init__(dict(params, nodes=counts[0],
                              node_template=pools[0]["template"]), seed)
        for pool, count in zip(pools[1:], counts[1:]):
            prefix = pool["template"]["metadata"]["generateName"]
            self.nodes += [_stamp(pool["template"], self._names.make(prefix), None)
                           for _ in range(count)]
        key = params["unique_node_label"]
        for node in self.nodes:
            meta = node["metadata"]
            meta["labels"] = {**(meta.get("labels") or {}), key: meta["name"]}


def generate(params: dict, seed: int) -> NodePoolsDeployment:
    return NodePoolsDeployment(params, seed)
