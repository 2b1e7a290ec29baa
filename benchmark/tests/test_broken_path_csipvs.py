"""`correct` comes out false when a NodeVolumeLimits refusal is altered on
its way out of the server: the broken path for reference/csi_volumes.py,
as test_broken_path_antiaffinity.py is for reference/antiaffinity.py (the
verdict tests of test_broken_path.py hold for every cell and are not
repeated here).

test_run_with_an_altered_refusal (slow: two server runs on the CPU
backend, ~2 min): skips the harness's look for a chip (platform "cpu")
and drives `csipvs_5k.interactive_volumes` twice at 600 nodes / 120
initial pods WITH THE CSINODE COUNT SET TO 1, so that every node that
holds a pod refuses the next one (at the source's 39 no node ever
refuses) and an 8 s window does not use up the free nodes: once as it is
(`correct` true, no malformed read: every node's entry carries the
family), once with ONE byte of one refusal's message in filter-result
altered (`correct` false, for that reason alone).

    python3 -m pytest benchmark/tests/test_broken_path_csipvs.py
"""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

CELL = "csipvs_5k.interactive_volumes"
NODES, INITIAL = 600, 120


def _alter_one_byte(raw: bytes) -> bytes:
    """The last letter of the first refusal's message in filter-result."""
    i = raw.index(b"/filter-result")
    msg = b"node(s) exceed max volume count"
    j = raw.index(msg, i) + len(msg) - 1
    assert raw[j:j + 1] == b"t", raw[j - 20:j + 5]
    return raw[:j] + b"z" + raw[j + 1:]


def _child(tampered: str) -> int:
    import run

    params = json.loads(
        (BENCH / "configs/sched_perf_csipvs_5k.json").read_text())["parameters"]
    volumes = copy.deepcopy(params["volumes"])
    volumes["csinode"]["count"] = 1
    override = {"nodes": NODES, "volumes": volumes,
                "initial_pods": dict(params["initial_pods"], count=INITIAL)}
    return run.main(["--workload", CELL, "--seed", "2147483777",
                     "--seconds", "8", "--trace", "0"],
                    platform_required="cpu", override=override,
                    warmup_override={"cycles": 4, "clean_cycles": 2, "max_cycles": 12},
                    tamper=_alter_one_byte if tampered == "1" else None)


def _run(tampered: bool) -> tuple[dict, list[str]]:
    p = subprocess.run([sys.executable, __file__, "--child", str(int(tampered))],
                       cwd=str(BENCH.parent), stdout=subprocess.PIPE,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = p.stdout.decode().splitlines()
    assert p.returncode == 0, "\n".join(lines[-20:])
    checks = [ln for ln in lines if ln.startswith("check ")]
    assert any("reference csi_volumes" in ln for ln in lines), \
        "the cell was not checked by its own reference"
    return json.loads(lines[-1]), checks


def test_run_with_an_altered_refusal():
    sound, checks = _run(False)
    assert sound["correct"] is True, checks
    broken, checks = _run(True)
    assert broken["correct"] is False, checks
    # and for the one reason that was planted: a differing value
    assert [c for c in checks if "NOT OK" in c] == [
        c for c in checks if c.startswith("check annotation_and_nodeName")], checks


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--child":
        sys.exit(_child(sys.argv[2]))
    test_run_with_an_altered_refusal()
    print("ok")
