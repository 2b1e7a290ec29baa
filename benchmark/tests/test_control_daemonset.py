"""The control of the correctness check for reference/daemonset.py, as
test_control.py is for the default profile: the reference in the nearest
precision below the configuration's (int32/float32 for int64/float64),
put in the program's place, has to come out as NOT equal — and the
reference against itself as equal.  Pure Python, no server, no JAX.

    python3 -m pytest benchmark/tests/test_control_daemonset.py
    python3 benchmark/tests/test_control_daemonset.py --at-size   # 15,001 nodes

In int32 the named node's 16,000Gi wraps to 0 (16,000 x 2**30 is a
multiple of 2**32), so NodeResourcesFit refuses the one node Filter runs
on ("Insufficient memory") and every pod stays pending where the exact
reference binds it.  The number compared is the count of differing values
among the checked pods' 13 annotations + spec.nodeName; its limit is 0.
"""

from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

from generators.scheduler_perf_named_node import generate  # noqa: E402
from reference.daemonset import KEYS, Exact, ReferenceScheduler  # noqa: E402
from reference.default_profile import Narrow32  # noqa: E402

CONFIG = "sched_perf_daemonset_15k"
SEEDS = (11, 2147483777, 3000000019)
K_PREFILTER, K_FILTER, K_POSTFILTER, K_SCORE = KEYS[1], KEYS[2], KEYS[3], KEYS[5]
NAMED = "scheduler-perf-node"


def _deployment(seed: int, nodes: int | None):
    params = copy.deepcopy(json.loads(
        (BENCH / "configs" / f"{CONFIG}.json").read_text())["parameters"])
    if nodes is not None:
        params["nodes"] = nodes
    return generate(params, seed)


def differing_values(seed: int, nodes: int | None, pods: int, arith) -> tuple[int, int]:
    """-> (differing, compared) between the exact reference and the same
    reference computed in `arith`, over `pods` measured pods."""
    dep = _deployment(seed, nodes)
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
        sound, n = differing_values(seed, 40, 16, Exact)
        control, _ = differing_values(seed, 40, 16, Narrow32)
        assert sound == 0, (seed, sound)
        assert control > 0, (seed, "the control passed the check")


def test_the_sound_reference_narrows_and_the_control_refuses():
    """What the control is compared with is not vacuous: the exact
    reference names the node, asks it alone and binds there; the control
    asks it alone too and is refused by it."""
    dep = _deployment(SEEDS[0], 40)
    pods = [dep.measured_pod() for _ in range(3)]
    sound = ReferenceScheduler(dep.nodes, [], Exact)
    control = ReferenceScheduler(dep.nodes, [], Narrow32)
    for pod in pods:
        anns, node = sound.schedule_one(pod)
        assert node == NAMED
        assert json.loads(anns[K_PREFILTER]) == {"NodeAffinity": [NAMED]}
        assert set(json.loads(anns[K_FILTER])) == {NAMED}
        assert anns[K_SCORE] == anns[K_POSTFILTER] == "{}"
        anns, node = control.schedule_one(pod)
        assert node == ""
        assert json.loads(anns[K_FILTER])[NAMED]["NodeResourcesFit"] == \
            "Insufficient memory"
        assert json.loads(anns[K_POSTFILTER]) == {NAMED: {}}


if __name__ == "__main__":
    nodes = None if "--at-size" in sys.argv else 40
    for seed in SEEDS + (4242424242,):
        s, n = differing_values(seed, nodes, 16, Exact)
        c, _ = differing_values(seed, nodes, 16, Narrow32)
        print(f"{CONFIG} seed {seed} nodes {(nodes or 15000) + 1}: differing "
              f"values sound {s}/{n} (limit 0), control int32/float32 {c}/{n}")
        assert s == 0 and c > 0
