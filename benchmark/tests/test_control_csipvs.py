"""The control of the correctness check for reference/csi_volumes.py, as
test_control.py is for the default profile: the reference in the nearest
precision below the configuration's (int32/float32 for int64/float64),
put in the program's place, has to come out as NOT equal — and the
reference against itself as equal.  Pure Python, no server, no JAX.

    python3 -m pytest benchmark/tests/test_control_csipvs.py
    python3 benchmark/tests/test_control_csipvs.py --at-size   # 5,000 nodes

In int32 the nodes' 32Gi wraps to 0, so NodeResourcesFit refuses every
node ("Insufficient memory") before the volume family is asked, and the
pod stays pending where the exact reference binds it.  The number compared
is the count of differing values among the checked pods' 13 annotations +
spec.nodeName; its limit is 0.
"""

from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

from generators.scheduler_perf_volumes import generate  # noqa: E402
from reference.csi_volumes import (  # noqa: E402
    ERR_MAX_VOLUME_COUNT, KEYS, Exact, ReferenceScheduler)
from reference.default_profile import Narrow32  # noqa: E402

CONFIG = "sched_perf_csipvs_5k"
SEEDS = (11, 2147483777, 3000000019)
K_STATUS, K_FILTER, K_SCORE = KEYS[0], KEYS[2], KEYS[5]


def _deployment(seed: int, nodes: int | None, count: int | None = None):
    params = copy.deepcopy(json.loads(
        (BENCH / "configs" / f"{CONFIG}.json").read_text())["parameters"])
    if nodes is not None:
        params["nodes"] = nodes
        params["initial_pods"]["count"] = nodes
    if count is not None:
        params["volumes"]["csinode"]["count"] = count
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


def test_the_sound_reference_runs_the_family():
    """What the control is compared with is not vacuous: at the source's
    limit every node's entry carries the family's two Filter plugins; at a
    limit of 1 exactly the nodes that hold a volume refuse."""
    dep = _deployment(SEEDS[0], 200)
    sched = ReferenceScheduler(dep.nodes, dep.initial_pods, Exact)
    anns, node = sched.schedule_one(dep.measured_pod())
    filt = json.loads(anns[K_FILTER])
    assert len(filt) == 200 and node
    assert all(e["NodeVolumeLimits"] == e["VolumeBinding"] == "passed"
               for e in filt.values())
    status = json.loads(anns[K_STATUS])
    assert status["NodeVolumeLimits"] == status["VolumeBinding"] == "success"
    assert status["VolumeRestrictions"] == status["VolumeZone"] == ""

    dep = _deployment(SEEDS[1], 50, count=1)
    dep.initial_pods[:] = dep.initial_pods[:30]
    sched = ReferenceScheduler(dep.nodes, dep.initial_pods, Exact)
    holding = {p["spec"]["nodeName"] for p in dep.initial_pods}
    for k in range(3):
        anns, node = sched.schedule_one(dep.measured_pod())
        filt = json.loads(anns[K_FILTER])
        refused = {nm for nm, e in filt.items()
                   if e.get("NodeVolumeLimits") == ERR_MAX_VOLUME_COUNT}
        assert refused == holding and len(holding) == 30 + k
        assert all("VolumeBinding" not in filt[nm] for nm in refused)
        assert set(json.loads(anns[K_SCORE])) == set(filt) - refused
        assert node not in holding
        holding.add(node)


if __name__ == "__main__":
    nodes = None if "--at-size" in sys.argv else 200
    for seed in SEEDS + (4242424242,):
        s, n = differing_values(seed, nodes, 16, Exact)
        c, _ = differing_values(seed, nodes, 16, Narrow32)
        print(f"{CONFIG} seed {seed} nodes {nodes or 5000}: differing values "
              f"sound {s}/{n} (limit 0), control int32/float32 {c}/{n}")
        assert s == 0 and c > 0
