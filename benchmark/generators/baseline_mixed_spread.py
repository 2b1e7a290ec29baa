"""BASELINE config 4's heterogeneous cluster and queue, from a seed:
`baseline_mixed.py`'s nodes and pods (config 3's distribution, which
config 4 shares field for field) plus the source's PodTopologySpread draw.

The source is BASELINE.json configs[3] ("10k pods / 5k nodes, +
PodTopologySpread (zone/hostname spread constraints)"), whose shapes the
program's models/workloads.py BASELINE_CONFIGS[4] + make_nodes + make_pods
(with_affinity, with_tolerations, with_spread) define.  After the
toleration, as make_pods draws them, a pod carries with
`pod_shape.spread_fraction` the constraints of `pod_shape.spread_constraints`
(the source: maxSkew 5 over topology.kubernetes.io/zone, DoNotSchedule, and
maxSkew 3 over kubernetes.io/hostname, ScheduleAnyway), each with the
selector `app = <the pod's own app label>`.  The draws are the benchmark's
own (plain `random`, the measured pods' own stream): a pod's other fields
are what `baseline_mixed.py` draws for it, then one more draw decides the
constraints, so more constrained pods never move a node.  Nothing here
imports the program.
"""

from __future__ import annotations

import random

from generators import baseline_mixed


def _with_spread(pod: dict, shape: dict, rng: random.Random) -> dict:
    if rng.random() < shape["spread_fraction"]:
        app = pod["metadata"]["labels"]["app"]
        pod["spec"]["topologySpreadConstraints"] = [
            dict(c, labelSelector={"matchLabels": {"app": app}})
            for c in shape["spread_constraints"]]
    return pod


class Deployment(baseline_mixed.Deployment):
    """`baseline_mixed.py`'s deployment whose every pod, initial or
    measured, makes the constraint draw after its own fields."""

    def __init__(self, params: dict, seed: int):
        super().__init__(params, seed)
        shape = params["pod_shape"]
        # the initial pods' constraint draws: a stream of their own, so
        # the parent's draws (and the placement) stay what they are
        spread_rng = random.Random(f"{seed}:initial-spread")
        for pod in self.initial_pods:
            _with_spread(pod, shape, spread_rng)

    def measured_pod(self) -> dict:
        """The next measured pod (pending: no nodeName): the next draw of
        `baseline_mixed.py`'s fields, then the constraint draw."""
        return _with_spread(super().measured_pod(), self._pod_shape,
                            self._measured_rng)


def generate(params: dict, seed: int) -> Deployment:
    return Deployment(params, seed)
