"""The control of the correctness check for reference/affinity_taints.py,
as test_control_nodeinclusion.py is for reference/node_inclusion.py: the
reference in the nearest precision below the configuration's
(int32/float32 for int64/float64), put in the program's place, has to
come out as NOT equal — and the reference against itself as equal.  Pure
Python, no server, no JAX.

    python3 -m pytest benchmark/tests/test_control_baseline_c3.py
    python3 benchmark/tests/test_control_baseline_c3.py --at-size   # 1,000 nodes, 3,000 pods

In int32 every node's memory (128 / 256 / 512 Gi) wraps to 0, so
NodeResourcesFit refuses every node the two plugins before it have not
refused and every pod takes the reference's "no feasible node" outcome:
the control also holds that outcome to rendering something (every node
refused, by one of three plugins, empty score maps) instead of raising.
The number compared is the count of differing values among the checked
pods' 13 annotations + spec.nodeName; its limit is 0.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

from generators.baseline_mixed import generate  # noqa: E402
from reference.affinity_taints import (  # noqa: E402
    ERR_AFFINITY, KEYS, Exact, NotCovered, ReferenceScheduler,
    untolerated_taint_message)
from reference.default_profile import (  # noqa: E402
    K_FILTER, K_FINAL, K_PREFILTER_STATUS, K_PRESCORE, K_SCORE, Narrow32)

CONFIG = "baseline_c3_1k"
SEEDS = (11, 2147483777, 3000000019)
TAINT_MSG = untolerated_taint_message("dedicated", "batch")


def _deployment(seed: int, nodes: int | None, initial: int | None):
    params = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())["parameters"]
    if nodes is not None:
        params = dict(params, nodes=nodes,
                      initial_pods=dict(params["initial_pods"], count=initial))
    return generate(params, seed)


def differing_values(seed: int, nodes: int | None, pods: int, arith,
                     initial: int | None = 120) -> tuple[int, int]:
    """-> (differing, compared) between the exact reference and the same
    reference computed in `arith`, over `pods` measured pods."""
    dep = _deployment(seed, nodes, initial)
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


def test_the_sound_reference_renders_the_lineup():
    """What the control is compared with is not vacuous: the sound side
    refuses the dedicated pool for a pod without the toleration and the
    hdd nodes for a pod with the ssd term, each at its own plugin and
    nothing after it, and scores the rest under the profile's names."""
    dep = _deployment(SEEDS[0], 200, 120)
    sched = ReferenceScheduler(dep.nodes, dep.initial_pods, Exact)
    pool = {n["metadata"]["name"] for n in dep.nodes
            if any(t["effect"] == "NoSchedule" for t in n["spec"].get("taints") or [])}
    soft = {n["metadata"]["name"] for n in dep.nodes
            if any(t["effect"] == "PreferNoSchedule"
                   for t in n["spec"].get("taints") or [])}
    hdd = {n["metadata"]["name"] for n in dep.nodes
           if n["metadata"]["labels"]["disktype"] == "hdd"}
    assert pool and soft and hdd
    kinds = set()
    for _ in range(40):
        pod = dep.measured_pod()
        picky = "affinity" in pod["spec"]
        tolerant = "tolerations" in pod["spec"]
        kinds.add((picky, tolerant))
        anns, node = sched.schedule_one(pod)
        filt = json.loads(anns[K_FILTER])
        assert set(filt) == {n["metadata"]["name"] for n in dep.nodes}
        closed = set() if tolerant else pool
        assert {nm for nm, e in filt.items()
                if e == {"TaintToleration": TAINT_MSG}} == closed
        wrong = (hdd - closed) if picky else set()
        assert {nm for nm, e in filt.items()
                if e.get("NodeAffinity") == ERR_AFFINITY} == wrong
        assert all("NodeResourcesFit" not in filt[nm] for nm in closed | wrong)
        assert all(("NodeAffinity" in e) == picky
                   for nm, e in filt.items() if nm not in closed)
        status = json.loads(anns[K_PREFILTER_STATUS])
        assert status == {"NodeAffinity": "success" if picky else "",
                          "NodeResourcesFit": "success"}
        scores, finals = json.loads(anns[K_SCORE]), json.loads(anns[K_FINAL])
        assert set(scores) == set(finals) == set(filt) - closed - wrong
        names = {"TaintToleration", "NodeResourcesFit",
                 "NodeResourcesBalancedAllocation"} | (
                     {"NodeAffinity"} if picky else set())
        assert all(set(e) == names for e in scores.values())
        assert json.loads(anns[K_PRESCORE])["NodeAffinity"] == (
            "success" if picky else "")
        # a PreferNoSchedule taint nobody tolerates scores 1, and reversed 0
        for nm in set(scores) & soft:
            assert scores[nm]["TaintToleration"] == "1"
            assert finals[nm]["TaintToleration"] == "0"
        for nm in set(scores) - soft:
            assert finals[nm]["TaintToleration"] == "300"
        if picky:
            assert max(int(e["NodeAffinity"]) for e in finals.values()) == 200
        assert node in scores
    assert len(kinds) == 4, kinds


def test_what_the_reference_does_not_cover_is_refused():
    dep = _deployment(SEEDS[0], 20, 0)
    sched = ReferenceScheduler(dep.nodes, [], Exact)
    for change in ({"nodeSelector": {"disktype": "ssd"}},
                   {"topologySpreadConstraints": [{"maxSkew": 1}]},
                   {"affinity": {"podAntiAffinity": {}}},
                   {"affinity": {"nodeAffinity": {
                       "requiredDuringSchedulingIgnoredDuringExecution": {
                           "nodeSelectorTerms": [{"matchFields": []}]}}}}):
        pod = dep.measured_pod()
        pod["spec"].update(change)
        try:
            sched.schedule_one(pod)
        except NotCovered:
            continue
        raise AssertionError(f"{change} was scheduled")


if __name__ == "__main__":
    at_size = "--at-size" in sys.argv
    for seed in SEEDS + (4242424242,):
        args = (None, 16) if at_size else (200, 16)
        s, n = differing_values(seed, *args, Exact, initial=None if at_size else 120)
        c, _ = differing_values(seed, *args, Narrow32, initial=None if at_size else 120)
        print(f"{CONFIG} seed {seed} nodes {1000 if at_size else 200}: differing "
              f"values sound {s}/{n} (limit 0), control int32/float32 {c}/{n}")
        assert s == 0 and c > 0
