"""BASELINE config 3's heterogeneous cluster and queue, from a seed.

The source is the build's own published target: BASELINE.json configs[2]
("5k pods / 1k nodes, + NodeAffinity + TaintToleration filter/score"),
whose shapes the program's models/workloads.py BASELINE_CONFIGS[3] +
make_nodes + make_pods define.  This file states the same DISTRIBUTION as
data (the configuration file's `parameters.node_shape` / `pod_shape`) and
draws from it with plain `random`: the draws are the benchmark's own, not
numpy's streams of make_pods, and nothing here imports the program.

  nodes   capacity cpu x memory, each the base times one of
          `capacity_factors` (0.5 / 1 / 1 / 2: nine capacities), 110 pods;
          zone i mod `zones` and its region, one of `instance_types`
          instance types, disktype ssd with `ssd_fraction`; with
          `taint_fraction` the NoSchedule taint, else with `taint_fraction`
          again the PreferNoSchedule one
  pods    one of `apps` app labels, tier web / backend, one container that
          requests one of 5 cpu x 5 memory sizes; with `affinity_fraction`
          a required `disktype In [ssd]` term AND one preferred
          instance-type term of a weight in `weight_range`; with
          `toleration_fraction` the toleration of the NoSchedule taint

Every pod of a run, initial or measured, is a draw of that one
distribution.  `initial_pods.count` of them arrive bound: pod i sits on
the first node, walking a seeded permutation of the nodes from a seeded
start, that its required term, the node's NoSchedule taint against its
toleration, and NodeResourcesFit (cpu, memory, pod count) accept.  The
measured pods are an endless seeded supply, so consecutive pods differ in
requests, app, affinity and toleration.

Independent streams a kind (names, nodes, initial pods, placement,
measured pods): more measured pods never move a node.  The seed draws
identities and who gets which shape; it never changes a count, so every
seed is the same amount of work up to the distribution's own variance.
"""

from __future__ import annotations

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


def _node(shape: dict, i: int, name: str, rng: random.Random) -> dict:
    zone = i % int(shape["zones"])
    cpu = int(int(shape["cpu_milli"]) * rng.choice(shape["capacity_factors"]))
    mem = int(int(shape["memory_bytes"]) * rng.choice(shape["capacity_factors"]))
    node = {
        "apiVersion": "v1", "kind": "Node",
        "metadata": {"name": name, "labels": {
            "kubernetes.io/hostname": name,
            "topology.kubernetes.io/zone": f"zone-{zone}",
            "topology.kubernetes.io/region": f"region-{zone // 4}",
            "node.kubernetes.io/instance-type":
                f"type-{rng.randrange(int(shape['instance_types']))}",
            "disktype": "ssd" if rng.random() < shape["ssd_fraction"] else "hdd",
        }},
        "spec": {},
        "status": {
            "allocatable": {
                "cpu": f"{cpu}m", "memory": str(mem),
                "ephemeral-storage": str(int(shape["ephemeral_storage_bytes"])),
                "pods": str(int(shape["pods"]))},
            "conditions": [{"type": "Ready", "status": "True"}]},
    }
    if rng.random() < shape["taint_fraction"]:
        node["spec"]["taints"] = [dict(shape["noschedule_taint"])]
    elif rng.random() < shape["taint_fraction"]:
        node["spec"]["taints"] = [dict(shape["prefer_taint"])]
    return node


def _pod(shape: dict, name: str, namespace: str, rng: random.Random) -> dict:
    app = f"app-{rng.randrange(int(shape['apps']))}"
    cpu = rng.choice(shape["cpu_milli"])
    mem = rng.choice(shape["memory_mib"]) << 20
    pod = {
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": name, "namespace": namespace, "labels": {
            "app": app,
            "tier": "web" if rng.random() < shape["web_fraction"] else "backend"}},
        "spec": {"containers": [{
            "name": "main", "image": shape["image"],
            "resources": {"requests": {"cpu": f"{cpu}m", "memory": str(mem)}}}]},
    }
    if rng.random() < shape["affinity_fraction"]:
        lo, hi = shape["weight_range"]
        pod["spec"]["affinity"] = {"nodeAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": {
                "nodeSelectorTerms": [{"matchExpressions": [
                    {"key": "disktype", "operator": "In", "values": ["ssd"]}]}]},
            "preferredDuringSchedulingIgnoredDuringExecution": [{
                "weight": rng.randint(int(lo), int(hi)),
                "preference": {"matchExpressions": [{
                    "key": "node.kubernetes.io/instance-type",
                    "operator": "In",
                    "values": [f"type-{rng.randrange(int(shape['instance_types']))}"],
                }]}}]}}
    if rng.random() < shape["toleration_fraction"]:
        pod["spec"]["tolerations"] = [dict(shape["toleration"])]
    return pod


def _quantity(s: str) -> int:
    return int(s[:-1]) if s.endswith("m") else int(s)


class _Placer:
    """Where an initial pod sits: what the three Filter plugins of the
    profile that can refuse here would accept, on the empty cluster and
    every pod placed before."""

    def __init__(self, nodes: list[dict], rng: random.Random):
        self.rng = rng
        self.order = list(range(len(nodes)))
        rng.shuffle(self.order)
        self.names = [n["metadata"]["name"] for n in nodes]
        self.ssd = [n["metadata"]["labels"]["disktype"] == "ssd" for n in nodes]
        self.closed = [any(t["effect"] == "NoSchedule"
                           for t in n["spec"].get("taints") or [])
                       for n in nodes]
        alloc = [n["status"]["allocatable"] for n in nodes]
        self.free = [[_quantity(a["cpu"]), int(a["memory"]), int(a["pods"])]
                     for a in alloc]

    def place(self, pod: dict) -> str:
        spec = pod["spec"]
        req = spec["containers"][0]["resources"]["requests"]
        cpu, mem = _quantity(req["cpu"]), int(req["memory"])
        wants_ssd = "affinity" in spec
        tolerates = bool(spec.get("tolerations"))
        start = self.rng.randrange(len(self.order))
        for step in range(len(self.order)):
            j = self.order[(start + step) % len(self.order)]
            free = self.free[j]
            if ((wants_ssd and not self.ssd[j])
                    or (self.closed[j] and not tolerates)
                    or free[0] < cpu or free[1] < mem or free[2] < 1):
                continue
            free[0] -= cpu
            free[1] -= mem
            free[2] -= 1
            return self.names[j]
        raise ValueError(f"no node takes initial pod {pod['metadata']['name']}")


class Deployment:
    """The initial cluster, and an endless seeded supply of measured pods."""

    def __init__(self, params: dict, seed: int):
        self.params = params
        self._names = _Names(random.Random(f"{seed}:names"))
        node_rng = random.Random(f"{seed}:nodes")
        n = int(params["nodes"])
        self.nodes = [_node(params["node_shape"], i, self._names.make("node-"),
                            node_rng) for i in range(n)]
        self.namespaces: list[dict] = []
        self._pod_shape = params["pod_shape"]
        init = params["initial_pods"]
        init_rng = random.Random(f"{seed}:initial")
        placer = _Placer(self.nodes, random.Random(f"{seed}:placement"))
        self.initial_pods = []
        for _ in range(int(init["count"])):
            pod = _pod(self._pod_shape, self._names.make("pod-"),
                       init["namespace"], init_rng)
            pod["spec"]["nodeName"] = placer.place(pod)
            self.initial_pods.append(pod)
        self.measured_namespace = params["measured_pods"]["namespace"]
        self._measured_rng = random.Random(f"{seed}:measured")
        # what the driver posts before its first cycle
        self.scheduler_configuration = params["scheduler_configuration"]

    def measured_pod(self) -> dict:
        """The next measured pod (pending: no nodeName): the next draw."""
        return _pod(self._pod_shape, self._names.make("pod-"),
                    self.measured_namespace, self._measured_rng)


def generate(params: dict, seed: int) -> Deployment:
    return Deployment(params, seed)
