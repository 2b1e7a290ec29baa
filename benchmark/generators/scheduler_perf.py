"""Clusters and pods of upstream scheduler_perf workloads, from a seed.

One generator for every scheduler_perf deployment whose shape is "N nodes
of one template, I initial pods of one template, measured pods of one
template": the templates and counts are the configuration file's
`parameters`.  The seed draws identities (names), the order of the node
list and the placement of the initial pods; it never changes a count or a
width, so every seed is the same amount of work:

  * node and pod names get a 5-character suffix the way the apiserver's
    generateName does, unique per kind;
  * initial pod i sits on node perm[i mod N] for a seeded permutation, so
    every seed loads the nodes with the same histogram of pod counts.

Nothing here imports the program.
"""

from __future__ import annotations

import copy
import random

_ALPHABET = "bcdfghjklmnpqrstvwxz2456789"  # the apiserver's generateName set


class _Names:
    def __init__(self, rng: random.Random):
        self.rng, self.seen = rng, set()

    def make(self, prefix: str) -> str:
        while True:
            name = prefix + "".join(self.rng.choices(_ALPHABET, k=5))
            if name not in self.seen:
                self.seen.add(name)
                return name


def _stamp(template: dict, name: str, namespace: str | None) -> dict:
    obj = copy.deepcopy(template)
    meta = obj.setdefault("metadata", {})
    meta.pop("generateName", None)
    meta["name"] = name
    if namespace is not None:
        meta["namespace"] = namespace
    return obj


class Deployment:
    """The initial cluster, and an endless seeded supply of measured pods."""

    def __init__(self, params: dict, seed: int):
        # independent streams: more measured pods never move a node name
        self._names = _Names(random.Random(f"{seed}:names"))
        place = random.Random(f"{seed}:placement")
        self.params = params
        n = int(params["nodes"])
        node_t = params["node_template"]
        prefix = node_t["metadata"]["generateName"]
        self.nodes = []
        for _ in range(n):
            node = _stamp(node_t, self._names.make(prefix), None)
            if params.get("node_labels"):
                node["metadata"]["labels"] = dict(params["node_labels"])
            self.nodes.append(node)
        self.namespaces = [
            {"apiVersion": "v1", "kind": "Namespace", "metadata": {"name": ns}}
            for ns in params.get("namespaces") or []]
        init = params["initial_pods"]
        perm = list(range(n))
        place.shuffle(perm)
        self.initial_pods = []
        for i in range(int(init["count"])):
            pod = _stamp(init["template"],
                         self._names.make(init["template"]["metadata"]["generateName"]),
                         init["namespace"])
            pod["spec"]["nodeName"] = self.nodes[perm[i % n]]["metadata"]["name"]
            self.initial_pods.append(pod)
        self._measured = params["measured_pods"]
        self.measured_namespace = self._measured["namespace"]

    def measured_pod(self) -> dict:
        """The next measured pod (pending: no nodeName)."""
        t = self._measured["template"]
        return _stamp(t, self._names.make(t["metadata"]["generateName"]),
                      self._measured["namespace"])


def generate(params: dict, seed: int) -> Deployment:
    return Deployment(params, seed)
