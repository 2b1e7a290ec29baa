"""The control of the correctness check for the configuration
`baseline_c4_queue_5k` (reference/spread_affinity_taints.py on the EMPTY
cluster, the queue 30 pods a burst), as test_control_baseline_c3_queue.py
is for `baseline_c3_queue_1k`: the reference in the nearest precision
below the configuration's (int32/float32 for int64/float64), put in the
program's place, has to come out as NOT equal, and the reference against
itself as equal.  Pure Python, no server, no JAX.

    python3 -m pytest benchmark/tests/test_control_baseline_c4_queue.py
    python3 benchmark/tests/test_control_baseline_c4_queue.py --at-size   # 5,000 nodes

In int32 every node's memory (128 / 256 / 512 Gi) wraps to 0, so
NodeResourcesFit refuses every node the two plugins before it have not
refused, and PodTopologySpread is never asked.  The number compared is
the count of differing values among the checked pods' 13 annotations +
spec.nodeName; its limit is 0.  The pods are replayed as the queue they
are: 60 of them, two bursts of 30, each scheduled onto what the pods
before it left.

A second control is this configuration's own: the same reference with
PodTopologySpread's PreFilter counting a zone's pods BY DOMAIN (every
node of the zone, whatever the incoming pod's node affinity says of it),
which is what the program did before PR 52.  On this deployment it has to
differ from upstream's count by node: if it did not, the cell would not
see the case it was added for.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

from generators.baseline_mixed_spread import generate  # noqa: E402
from reference.default_profile import Narrow32  # noqa: E402
from reference.spread_affinity_taints import (  # noqa: E402
    KEYS, PROFILE, Exact, ReferenceScheduler)

CONFIG = "baseline_c4_queue_5k"
SEEDS = (11, 2147483777, 3000000019)
BURST = 30


def _config() -> dict:
    return json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())


class CountsByDomain(ReferenceScheduler):
    """The reference with one thing changed: a node that the incoming
    pod's required node affinity leaves out still adds its pods to its
    zone's count (the minimum stays over the zones of the nodes kept)."""

    def _spread_filter(self, pod):
        check = super()._spread_filter(pod)
        if check is None:
            return None
        keyed = [all(c["key"] in self.labels[j] for c in pod.hard)
                 for j in range(self.n)]
        state = []
        for c in pod.hard:
            on_node = self._matching_on(pod, c["match"])
            kept = {self.labels[j][c["key"]] for j in range(self.n)
                    if keyed[j] and self._included(pod, j)}
            whole: dict[str, int] = {}
            for j in range(self.n):
                if keyed[j]:
                    value = self.labels[j][c["key"]]
                    whole[value] = whole.get(value, 0) + on_node[j]
            low = min((whole[v] for v in kept), default=0)
            state.append((c, whole, low,
                          int(all(pod.labels.get(k) == v
                                  for k, v in c["match"].items()))))

        def by_domain(j: int):
            for c, whole, low, self_match in state:
                value = self.labels[j].get(c["key"])
                if value is None:
                    return check(j)
                if whole.get(value, 0) + self_match - low > c["max_skew"]:
                    return ("node(s) didn't match pod topology spread "
                            "constraints")
            return None

        return by_domain


def differing_values(seed: int, nodes: int | None, pods: int, arith,
                     other_cls=ReferenceScheduler,
                     pod_shape: dict | None = None) -> tuple[int, int]:
    """-> (differing, compared) between the exact reference and `other_cls`
    computed in `arith`, over the queue's first `pods` pods."""
    params = _config()["parameters"]
    if nodes is not None:
        params = dict(params, nodes=nodes)
    if pod_shape is not None:
        params = dict(params, pod_shape=dict(params["pod_shape"], **pod_shape))
    dep = generate(params, seed)
    assert dep.initial_pods == []
    sound = ReferenceScheduler(dep.nodes, dep.initial_pods, Exact)
    other = other_cls(dep.nodes, dep.initial_pods, arith)
    differing = compared = 0
    for _ in range(pods):
        pod = dep.measured_pod()
        a, node_a = sound.schedule_one(pod)
        b, node_b = other.schedule_one(pod)
        differing += sum(a[k] != b[k] for k in KEYS) + (node_a != node_b)
        compared += len(KEYS) + 1
    return differing, compared


def test_control_fails_and_sound_passes():
    for seed in SEEDS:
        sound, n = differing_values(seed, 200, 2 * BURST, Exact)
        control, _ = differing_values(seed, 200, 2 * BURST, Narrow32)
        assert sound == 0, (seed, sound)
        assert control > 0, (seed, "the control passed the check")


def test_counting_by_domain_differs_on_this_deployment():
    """Tight constraints (maxSkew 1, two apps) over 40 nodes: the zones'
    counts by node and by domain part within the first bursts."""
    tight = {"apps": 2, "spread_constraints": [
        dict(c, maxSkew=1) for c in
        _config()["parameters"]["pod_shape"]["spread_constraints"]]}
    for seed in SEEDS:
        differing, _ = differing_values(seed, 40, 4 * BURST, Exact,
                                        CountsByDomain, tight)
        assert differing > 0, (seed, "counting by domain passed the check")


def test_the_configuration_is_baseline_c3_s_shapes_at_5000_nodes_plus_spread():
    """Not one number of a node's or a pod's shape differs from
    baseline_c3_queue_1k's file; what is added is the source's: 5,000
    nodes, the constraint draw and the fifth plugin."""
    c4 = _config()
    c3 = json.loads((BENCH / "configs" / "baseline_c3_queue_1k.json").read_text())
    assert c4["parameters"]["nodes"] == 5000
    assert c4["parameters"]["node_shape"] == c3["parameters"]["node_shape"]
    shape = dict(c4["parameters"]["pod_shape"])
    assert shape.pop("spread_fraction") == 0.6
    assert shape.pop("spread_constraints") == [
        {"maxSkew": 5, "topologyKey": "topology.kubernetes.io/zone",
         "whenUnsatisfiable": "DoNotSchedule"},
        {"maxSkew": 3, "topologyKey": "kubernetes.io/hostname",
         "whenUnsatisfiable": "ScheduleAnyway"}]
    assert shape == c3["parameters"]["pod_shape"]
    assert c4["parameters"]["initial_pods"] == {"count": 0,
                                                "namespace": "default"}
    lineup = c4["parameters"]["scheduler_configuration"]["profiles"][0][
        "plugins"]["multiPoint"]["enabled"]
    assert [(p["name"], p["weight"]) for p in lineup] == PROFILE
    assert c4["reduced"] == ["delivery"]
    assert set(c4["guarantees"]) == set(c3["guarantees"])
    assert (c4["generator"], c4["reference"]) == (
        "baseline_mixed_spread", "spread_affinity_taints")
    assert len(c4["source"]) <= 200 and c4["source"].endswith("as one queue")


def test_six_pods_in_ten_are_constrained_and_three_also_carry_the_ssd_term():
    dep = generate(dict(_config()["parameters"], nodes=40), SEEDS[1])
    pods = [dep.measured_pod() for _ in range(2000)]
    spread = [p for p in pods if "topologySpreadConstraints" in p["spec"]]
    both = [p for p in spread if "affinity" in p["spec"]]
    assert 0.57 < len(spread) / len(pods) < 0.63
    assert 0.27 < len(both) / len(pods) < 0.33
    groups = {(p["metadata"]["labels"]["app"], c["topologyKey"])
              for p in pods[:BURST] if "topologySpreadConstraints" in p["spec"]
              for c in p["spec"]["topologySpreadConstraints"]}
    assert 16 <= len(groups) <= 36, len(groups)


if __name__ == "__main__":
    at_size = "--at-size" in sys.argv
    for seed in SEEDS + (4242424242,):
        nodes = None if at_size else 200
        s, n = differing_values(seed, nodes, 2 * BURST, Exact)
        c, _ = differing_values(seed, nodes, 2 * BURST, Narrow32)
        print(f"{CONFIG} seed {seed} nodes {5000 if at_size else 200}: differing "
              f"values sound {s}/{n} (limit 0), control int32/float32 {c}/{n}")
        assert s == 0 and c > 0
