"""The control of the correctness check for reference/node_inclusion.py,
as test_control_antiaffinity.py is for reference/antiaffinity.py: the
reference in the nearest precision below the configuration's
(int32/float32 for int64/float64), put in the program's place, has to
come out as NOT equal — and the reference against itself as equal.  Pure
Python, no server, no JAX.

    python3 -m pytest benchmark/tests/test_control_nodeinclusion.py
    python3 benchmark/tests/test_control_nodeinclusion.py --at-size   # 5,000 nodes

In int32 the plain nodes' 32Gi wraps to 0, so NodeResourcesFit refuses
every node TaintToleration has not refused already and every pod takes
the reference's "no feasible node" outcome: the control also holds that
outcome to rendering something (every node refused, by one of two
plugins, the PostFilter map) instead of raising.  The number compared is
the count of differing values among the checked pods' 13 annotations +
spec.nodeName; its limit is 0.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

from generators.scheduler_perf_node_pools import generate  # noqa: E402
from reference.default_profile import (  # noqa: E402
    K_FILTER, K_POSTFILTER, K_SCORE, Narrow32)
from reference.node_inclusion import (  # noqa: E402
    ERR_SKEW, KEYS, Exact, ReferenceScheduler, untolerated_taint_message)

CONFIG = "sched_perf_nodeinclusion_5k"
SEEDS = (11, 2147483777, 3000000019)
TAINT_MSG = untolerated_taint_message("foo", "")


def _deployment(seed: int, nodes: int | None):
    params = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())["parameters"]
    if nodes is not None:
        params = dict(params, nodes=nodes)
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
        sound, n = differing_values(seed, 200, 16, Exact)
        control, _ = differing_values(seed, 200, 16, Narrow32)
        assert sound == 0, (seed, sound)
        assert control > 0, (seed, "the control passed the check")


def test_the_sound_reference_renders_both_refusals():
    """What the control is compared with is not vacuous: the sound side
    refuses exactly the tainted nodes and the taken hostnames, each with
    its own plugin's message, and scores the rest."""
    dep = _deployment(SEEDS[0], 200)
    sched = ReferenceScheduler(dep.nodes, dep.initial_pods, Exact)
    tainted = {n["metadata"]["name"] for n in dep.nodes
               if n["spec"].get("taints")}
    assert len(tainted) == 40
    taken: set[str] = set()
    for _ in range(3):
        anns, node = sched.schedule_one(dep.measured_pod())
        filt = json.loads(anns[K_FILTER])
        assert {nm for nm, e in filt.items()
                if e.get("TaintToleration") == TAINT_MSG} == tainted
        assert {nm for nm, e in filt.items()
                if e.get("PodTopologySpread") == ERR_SKEW} == taken
        assert all("NodeResourcesFit" not in filt[nm] for nm in tainted)
        assert set(json.loads(anns[K_SCORE])) == set(filt) - tainted - taken
        assert anns[K_POSTFILTER] == "{}" and node not in tainted | taken
        taken.add(node)


if __name__ == "__main__":
    nodes = None if "--at-size" in sys.argv else 200
    for seed in SEEDS + (4242424242,):
        s, n = differing_values(seed, nodes, 16, Exact)
        c, _ = differing_values(seed, nodes, 16, Narrow32)
        print(f"{CONFIG} seed {seed} nodes {nodes or 5000}: differing values "
              f"sound {s}/{n} (limit 0), control int32/float32 {c}/{n}")
        assert s == 0 and c > 0
