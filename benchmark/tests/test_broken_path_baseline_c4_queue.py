"""`correct` comes out false when the server counts a zone's pods BY
DOMAIN, as the program did before PR 52: the broken path for the cell
`baseline_c4_queue_5k.rollout30_profile` (reference/spread_affinity_taints.py,
driver drivers/closed_loop_profile_burst.py), as
test_broken_path_baseline_c3_queue.py is for the four-plugin queue.

test_run_on_a_program_that_folds_by_domain (slow: two server runs on the
CPU backend, ~2.5 min): skips the harness's look for a chip (platform
"cpu") and drives the cell at 40 nodes over 4 zones under the posted
five-plugin profile, the 29 smaller bursts first: once as it is
(`correct` true), once with the SERVER CHILD's PodTopologySpread Filter
replaced, through a `sitecustomize` on the child's PYTHONPATH, by one
that adds every node of a zone to the zone's count whatever the incoming
pod's node affinity says of the node (the minimum stays over the nodes
kept): `correct` false, by the annotation limit, the placement limit or
both, and by no other.  The run's constraints are tightened (maxSkew 1,
two apps) so that the two counts part within the warm-up's bursts; the
configuration's own (maxSkew 5, 20 apps) part over thousands of pods.

    python3 -m pytest benchmark/tests/test_broken_path_baseline_c4_queue.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

CELL = "baseline_c4_queue_5k.rollout30_profile"
NODES = 40
SEED = "2147483777"
WARMUP = {"cycles": 4, "clean_cycles": 2, "max_cycles": 12}
BROKEN_ENV = "BENCH_TEST_SPREAD_FOLDS_BY_DOMAIN"

# what the server child imports first where BROKEN_ENV is set: the
# plugin's Filter with ONE line changed (the values folded are every keyed
# node's, not the counted nodes')
SITECUSTOMIZE = '''
import os, sys
if (os.environ.get(%(env)r)
        and any(a.endswith("cmd.simulator") for a in sys.orig_argv)):
    sys.path.insert(0, %(repo)r)
    import jax.numpy as jnp
    from kube_scheduler_simulator_tpu.plugins import topologyspread as t

    def filter_kernel(static, pod, counts):
        code = jnp.zeros(static.dom_idx.shape[1], dtype=jnp.int32)
        keyed = t._kind_keys(static, pod, counts, pod.is_filter)
        for m in range(t.MAX_CONSTRAINTS):
            active, k, dom, has_key, per_node = t._slot(static, pod, counts, m)
            counted = static.elig_rows[pod.elig_idx[m]] & keyed & has_key
            cnt = t._fold(static, k, dom, jnp.where(has_key, per_node, 0))
            low = jnp.min(jnp.where(counted, cnt.astype(jnp.int64), t._BIG))
            check = active & pod.is_filter[m]
            own = pod.pm[jnp.maximum(pod.c_id[m], 0)].astype(jnp.int64)
            viol = jnp.where(has_key, jnp.where(
                cnt + own - low > pod.max_skew[m], 2 + 2 * m, 0), 1 + 2 * m)
            viol = jnp.where(check, viol, 0).astype(jnp.int32)
            code = jnp.where((code == 0) & (viol > 0), viol, code)
        return code

    t.filter_kernel = filter_kernel
    print("the server folds PodTopologySpread's counts by domain",
          file=sys.stderr, flush=True)
'''


def _override() -> dict:
    params = json.loads((BENCH / "configs" / "baseline_c4_queue_5k.json")
                        .read_text())["parameters"]
    return {
        "nodes": NODES,
        "node_shape": dict(params["node_shape"], zones=4),
        "pod_shape": dict(params["pod_shape"], apps=2, spread_constraints=[
            dict(c, maxSkew=1)
            for c in params["pod_shape"]["spread_constraints"]]),
    }


def _child() -> int:
    import run

    return run.main(["--workload", CELL, "--seed", SEED,
                     "--seconds", "6", "--trace", "0"],
                    platform_required="cpu", override=_override(),
                    warmup_override=WARMUP)


def _run(broken: bool) -> tuple[dict, list[str], list[str]]:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    if broken:
        site = Path(tempfile.mkdtemp(prefix="kss_broken_spread_"))
        (site / "sitecustomize.py").write_text(SITECUSTOMIZE % {
            "env": BROKEN_ENV, "repo": str(BENCH.parent)})
        env[BROKEN_ENV] = "1"
        env["PYTHONPATH"] = os.pathsep.join(
            [str(site)] + [p for p in (env.get("PYTHONPATH"),) if p])
    p = subprocess.run([sys.executable, __file__, "--child"],
                       cwd=str(BENCH.parent), stdout=subprocess.PIPE, env=env)
    lines = p.stdout.decode().splitlines()
    assert p.returncode == 0, "\n".join(lines[-20:])
    checks = [ln for ln in lines if ln.startswith("check ")]
    return json.loads(lines[-1]), checks, lines


def _not_ok(checks: list[str]) -> set[str]:
    return {c.split(":")[0] for c in checks if "NOT OK" in c}


def test_run_on_a_program_that_folds_by_domain():
    sound, checks, lines = _run(False)
    assert sound["correct"] is True, checks
    assert any("reference spread_affinity_taints" in ln for ln in lines), \
        "the cell was not checked by its own reference"
    assert any(ln.startswith("profile posted and read back") and
               "PodTopologySpread" in ln for ln in lines)
    shapes = [ln for ln in lines if ln.startswith("warm-up cycle ")]
    assert [int(ln.split(": ")[1].split(" pods")[0]) for ln in shapes[:31]] \
        == list(range(1, 30)) + [30, 30], shapes[:31]
    assert sound["attempted"] % 30 == 0 and sound["attempted"] >= 30

    broken, checks, lines = _run(True)
    log = (BENCH.parent / ".bench_work" / CELL / "server.log").read_text()
    assert "the server folds PodTopologySpread's counts by domain" in log
    assert broken["correct"] is False, checks
    failed = _not_ok(checks)
    assert failed and failed <= {
        "check annotation_and_nodeName_values_differing",
        "check replayed_pods_placed_elsewhere"}, checks


if __name__ == "__main__":
    if len(sys.argv) == 2 and sys.argv[1] == "--child":
        sys.exit(_child())
    test_run_on_a_program_that_folds_by_domain()
    print("ok")
