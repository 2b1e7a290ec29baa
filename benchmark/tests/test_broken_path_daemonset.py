"""`correct` comes out false when the served path evaluates every node for
a pod that names one: the broken path for reference/daemonset.py, as
test_broken_path_antiaffinity.py is for reference/antiaffinity.py (the
verdict tests of test_broken_path.py hold for every cell and are not
repeated here).

test_run_that_evaluates_every_node (slow: two server runs on the CPU
backend, ~1 min): skips the harness's look for a chip (platform "cpu") and
drives `daemonset_15k.interactive` twice at 40 node-default nodes + the
named one: once as it is (`correct` true), once with what a program
WITHOUT PreFilterResult answers put in the server's place: a
prefilter-result of `{}` and a filter-result with an entry for every other
node too, each ending at NodeAffinity's refusal (`correct` false, for that
reason alone: the pod is bound to the same node either way).

    python3 -m pytest benchmark/tests/test_broken_path_daemonset.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

CELL = "daemonset_15k.interactive"
NODES = 40
PREFIX = "kube-scheduler-simulator.sigs.k8s.io/"
REFUSED = {"NodeUnschedulable": "passed", "NodeName": "passed",
           "TaintToleration": "passed",
           "NodeAffinity": "node(s) didn't match Pod's node affinity/selector"}


def _evaluate_every_node(raw: bytes) -> bytes:
    """The pod as the parent of PR 38 serves it: no PreFilterResult on
    record, every node asked, all but the named one refused."""
    pod = json.loads(raw)
    anns = pod["metadata"]["annotations"]
    assert json.loads(anns[PREFIX + "prefilter-result"]) == {
        "NodeAffinity": ["scheduler-perf-node"]}, anns[PREFIX + "prefilter-result"]
    anns[PREFIX + "prefilter-result"] = "{}"
    entries = json.loads(anns[PREFIX + "filter-result"])
    assert list(entries) == ["scheduler-perf-node"], sorted(entries)
    for i in range(NODES):
        entries[f"scheduler-perf-other{i:02d}"] = REFUSED
    anns[PREFIX + "filter-result"] = json.dumps(
        entries, sort_keys=True, separators=(",", ":"))
    return json.dumps(pod).encode()


def _child(tampered: str) -> int:
    import run

    return run.main(["--workload", CELL, "--seed", "2147483777",
                     "--seconds", "8", "--trace", "0"],
                    platform_required="cpu", override={"nodes": NODES},
                    warmup_override={"cycles": 4, "clean_cycles": 2, "max_cycles": 12},
                    tamper=_evaluate_every_node if tampered == "1" else None)


def _run(tampered: bool) -> tuple[dict, list[str]]:
    p = subprocess.run([sys.executable, __file__, "--child", str(int(tampered))],
                       cwd=str(BENCH.parent), stdout=subprocess.PIPE,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = p.stdout.decode().splitlines()
    assert p.returncode == 0, "\n".join(lines[-20:])
    checks = [ln for ln in lines if ln.startswith("check ")]
    assert any("reference daemonset" in ln for ln in lines), \
        "the cell was not checked by its own reference"
    return json.loads(lines[-1]), checks


def test_run_that_evaluates_every_node():
    sound, checks = _run(False)
    assert sound["correct"] is True, checks
    broken, checks = _run(True)
    assert broken["correct"] is False, checks
    # and for the one reason that was planted: differing values
    assert [c for c in checks if "NOT OK" in c] == [
        c for c in checks if c.startswith("check annotation_and_nodeName")], checks


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--child":
        sys.exit(_child(sys.argv[2]))
    test_run_that_evaluates_every_node()
    print("ok")
