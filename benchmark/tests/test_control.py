"""The control of the correctness check: the reference in the nearest
precision below the configuration's (int32/float32 for int64/float64),
put in the program's place, has to come out as NOT equal — and the
reference against itself as equal.  Pure Python, no server, no JAX.

    python3 -m pytest benchmark/tests/test_control.py
    python3 benchmark/tests/test_control.py --at-size   # 5,000 nodes, 3+ seeds

The number compared is the count of differing values among the checked
pods' 13 annotations + spec.nodeName; its limit is 0 (an exact
comparison).  Sound readings are 0 by construction of the comparison;
the control's smallest reading is what PERF.md section 2 records.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

from generators.scheduler_perf import generate  # noqa: E402
from reference.default_profile import (  # noqa: E402
    KEYS, Exact, Narrow32, ReferenceScheduler)

CONFIGS = ["sched_perf_basic_5k", "sched_perf_podaffinity_5k"]


def differing_values(config: str, seed: int, nodes: int | None,
                     pods: int, arith) -> tuple[int, int]:
    """-> (differing, compared) between the exact reference and the same
    reference computed in `arith`, over `pods` measured pods."""
    params = json.loads((BENCH / "configs" / f"{config}.json").read_text())["parameters"]
    if nodes is not None:
        params = dict(params, nodes=nodes, initial_pods=dict(
            params["initial_pods"], count=max(nodes // 5, 1)))
    dep = generate(params, seed)
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
    for config in CONFIGS:
        for seed in (11, 2147483777, 3000000019):
            sound, n = differing_values(config, seed, 200, 16, Exact)
            control, _ = differing_values(config, seed, 200, 16, Narrow32)
            assert sound == 0, (config, seed, sound)
            assert control > 0, (config, seed, "the control passed the check")


if __name__ == "__main__":
    nodes = None if "--at-size" in sys.argv else 200
    for config in CONFIGS:
        for seed in (11, 2147483777, 3000000019, 4242424242):
            s, n = differing_values(config, seed, nodes, 16, Exact)
            c, _ = differing_values(config, seed, nodes, 16, Narrow32)
            print(f"{config} seed {seed} nodes {nodes or 5000}: differing values "
                  f"sound {s}/{n} (limit 0), control int32/float32 {c}/{n}")
            assert s == 0 and c > 0
