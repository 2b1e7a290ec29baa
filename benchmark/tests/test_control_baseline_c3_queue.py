"""The control of the correctness check for the configuration
`baseline_c3_queue_1k` (reference/affinity_taints.py on the EMPTY
cluster, the queue 30 pods a burst), as test_control_baseline_c3.py is for
`baseline_c3_1k`: the reference in the nearest precision below the
configuration's (int32/float32 for int64/float64), put in the program's
place, has to come out as NOT equal, and the reference against itself as
equal.  Pure Python, no server, no JAX.

    python3 -m pytest benchmark/tests/test_control_baseline_c3_queue.py
    python3 benchmark/tests/test_control_baseline_c3_queue.py --at-size   # 1,000 nodes

In int32 every node's memory (128 / 256 / 512 Gi) wraps to 0, so
NodeResourcesFit refuses every node the two plugins before it have not
refused: on the empty cluster too, where the sound reference refuses no
node for its resources.  The number compared is the count of differing
values among the checked pods' 13 annotations + spec.nodeName; its limit
is 0.  The pods are replayed as the queue they are: 60 of them, two
bursts of 30, each scheduled onto what the pods before it left.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

from generators.baseline_mixed import generate  # noqa: E402
from reference.affinity_taints import (  # noqa: E402
    KEYS, Exact, ReferenceScheduler)
from reference.default_profile import Narrow32  # noqa: E402

CONFIG = "baseline_c3_queue_1k"
SEEDS = (11, 2147483777, 3000000019)
BURST = 30


def _config() -> dict:
    return json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())


def differing_values(seed: int, nodes: int | None, pods: int,
                     arith) -> tuple[int, int]:
    """-> (differing, compared) between the exact reference and the same
    reference computed in `arith`, over the queue's first `pods` pods."""
    params = _config()["parameters"]
    if nodes is not None:
        params = dict(params, nodes=nodes)
    dep = generate(params, seed)
    assert dep.initial_pods == []
    sound = ReferenceScheduler(dep.nodes, dep.initial_pods, Exact)
    other = ReferenceScheduler(dep.nodes, dep.initial_pods, arith)
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


def test_the_configuration_is_baseline_c3_s_on_an_empty_cluster():
    """Not one number of a shape differs from baseline_c3_1k's file; the
    cluster starts empty; the guarantees are no weaker."""
    queue = _config()
    base = json.loads((BENCH / "configs" / "baseline_c3_1k.json").read_text())
    for key in ("nodes", "node_shape", "pod_shape", "scheduler_configuration",
                "measured_pods"):
        assert queue["parameters"][key] == base["parameters"][key], key
    assert queue["parameters"]["initial_pods"] == {
        "count": 0, "namespace": "default"}
    assert queue["guarantees"] == base["guarantees"]
    assert (queue["generator"], queue["reference"]) == (
        base["generator"], base["reference"])
    assert len(queue["source"]) <= 200 and queue["source"].endswith("as one queue")


def test_the_first_burst_is_placed_pod_after_pod():
    """What the empty cluster does to a batch: evaluated against the
    cluster as it stands before the burst, pods of a kind all score the
    same largest nodes highest; the sound reference places them one after
    another, so every bind moves the next pod's scores and the burst
    spreads (25 / 23 / 26 distinct nodes for the first three bursts of 30
    at 1,000 nodes, seed 2147483777).  A batch that binds all 30 on the
    pre-burst scores would fail the placement check."""
    dep = generate(dict(_config()["parameters"], nodes=200), SEEDS[0])
    sched = ReferenceScheduler(dep.nodes, [], Exact)
    placed = [sched.schedule_one(dep.measured_pod(), annotate=False)[1]
              for _ in range(BURST)]
    assert all(placed)
    assert len(set(placed)) > BURST // 2, placed


if __name__ == "__main__":
    at_size = "--at-size" in sys.argv
    for seed in SEEDS + (4242424242,):
        nodes = None if at_size else 200
        s, n = differing_values(seed, nodes, 2 * BURST, Exact)
        c, _ = differing_values(seed, nodes, 2 * BURST, Narrow32)
        print(f"{CONFIG} seed {seed} nodes {1000 if at_size else 200}: differing "
              f"values sound {s}/{n} (limit 0), control int32/float32 {c}/{n}")
        assert s == 0 and c > 0
