"""The control of the correctness check for reference/mixed_churn.py, as
test_control.py is for the default profile: the reference in the nearest
precision below the configuration's (int32/float32 for int64/float64),
put in the program's place, has to come out as NOT equal — and the
reference against itself as equal.  Pure Python, no server, no JAX.

    python3 -m pytest benchmark/tests/test_control_mixed_churn.py
    python3 benchmark/tests/test_control_mixed_churn.py --at-size   # 5,000 nodes

In int32 the nodes' 32Gi wraps to 0, so no node offers memory: every
measured pod is refused everywhere ("Insufficient memory") and stays
pending, where the exact reference binds it.  The churn pod is refused
everywhere on both sides, with different reasons.  The number compared is
the count of differing values among the checked pods' 13 annotations +
spec.nodeName; its limit is 0.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

from generators.scheduler_perf_churn import generate  # noqa: E402
from reference.default_profile import Narrow32  # noqa: E402
from reference.mixed_churn import KEYS, Exact, ReferenceScheduler  # noqa: E402

CONFIG = "sched_perf_mixed_churn_5k"
SEEDS = (11, 2147483777, 3000000019)
K_FILTER, K_POSTFILTER, K_SCORE = KEYS[2], KEYS[3], KEYS[5]


def _deployment(seed: int, nodes: int | None):
    params = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())["parameters"]
    if nodes is not None:
        params = dict(params, nodes=nodes)
    return generate(params, seed)


def differing_values(seed: int, nodes: int | None, pods: int, arith) -> tuple[int, int]:
    """-> (differing, compared) between the exact reference and the same
    reference computed in `arith`, over `pods` measured pods and the churn
    pods of their ticks."""
    dep, dep_other = _deployment(seed, nodes), _deployment(seed, nodes)
    sound = ReferenceScheduler(dep.nodes, dep.initial_pods, Exact)
    other = ReferenceScheduler(dep_other.nodes, dep_other.initial_pods, arith)
    sound.render_churn = other.render_churn = True
    differing = compared = 0
    for _ in range(pods):
        pod = dep.measured_pod()
        a, node_a = sound.schedule_one(pod)
        b, node_b = other.schedule_one(pod)
        differing += sum(a[k] != b[k] for k in KEYS) + (node_a != node_b)
        compared += len(KEYS) + 1
    for name, a in sound.churn_results.items():
        differing += sum(a[k] != other.churn_results[name][k] for k in KEYS)
        compared += len(KEYS)
    return differing, compared


def test_control_fails_and_sound_passes():
    for seed in SEEDS:
        sound, n = differing_values(seed, 200, 16, Exact)
        control, _ = differing_values(seed, 200, 16, Narrow32)
        assert sound == 0, (seed, sound)
        assert control > 0, (seed, "the control passed the check")


def test_the_sound_reference_renders_the_churn():
    """What the control is compared with is not vacuous: the sound side
    refuses the churn node for every measured pod and every node for the
    churn pod, and scores the rest."""
    dep = _deployment(SEEDS[0], 200)
    sched = ReferenceScheduler(dep.nodes, dep.initial_pods, Exact)
    sched.render_churn = True
    for k in range(3):
        anns, node = sched.schedule_one(dep.measured_pod())
        churn_node, churn_pod, _ = dep.nodes.churn.trio(k)
        gone = dep.nodes.churn.trio(k - 1)[0]["metadata"]["name"] if k else None
        filt = json.loads(anns[K_FILTER])
        name = churn_node["metadata"]["name"]
        assert len(filt) == 201 and gone not in filt
        assert filt[name]["NodeResourcesFit"] == \
            "Too many pods, Insufficient cpu, Insufficient memory"
        assert set(json.loads(anns[K_SCORE])) == set(filt) - {name}
        assert anns[K_POSTFILTER] == "{}" and node and node != name
        stuck = sched.churn_results[churn_pod["metadata"]["name"]]
        refused = json.loads(stuck[K_FILTER])
        assert set(refused) == set(filt)
        assert {e["NodeResourcesFit"] for nm, e in refused.items()
                if nm != name} == {"Insufficient cpu"}
        assert json.loads(stuck[K_POSTFILTER]) == {nm: {} for nm in filt}
        assert stuck[KEYS[-1]] == "" and stuck[K_SCORE] == "{}"


if __name__ == "__main__":
    nodes = None if "--at-size" in sys.argv else 200
    for seed in SEEDS + (4242424242,):
        s, n = differing_values(seed, nodes, 16, Exact)
        c, _ = differing_values(seed, nodes, 16, Narrow32)
        print(f"{CONFIG} seed {seed} nodes {nodes or 5000}: differing values "
              f"sound {s}/{n} (limit 0), control int32/float32 {c}/{n}")
        assert s == 0 and c > 0
