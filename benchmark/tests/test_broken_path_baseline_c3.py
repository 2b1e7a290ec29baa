"""`correct` comes out false when one of the cell's refusals or scores is
altered on its way out of the server: the broken path for
reference/affinity_taints.py, as test_broken_path_nodeinclusion.py is for
reference/node_inclusion.py (the verdict tests of test_broken_path.py hold
for every cell and are not repeated here).

test_run_with_an_altered_value (slow: four server runs on the CPU backend,
~1.5 min): skips the harness's look for a chip (platform "cpu") and drives
`baseline_c3_1k.interactive_profile` at 200 nodes and 300 initial pods
under the posted four-plugin profile: once as it is (`correct` true), once
with ONE byte of one node's untolerated-taint message altered, once with
one byte of one node's affinity/selector message, once with one digit of a
NodeAffinity final score (`correct` false each time, for that reason
alone).

    python3 -m pytest benchmark/tests/test_broken_path_baseline_c3.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

CELL = "baseline_c3_1k.interactive_profile"
NODES, INITIAL = 200, 300
# (annotation, what to find in it, the byte of it that changes)
TAMPERS = {
    "taint": (b"/filter-result", b"had untolerated taint {dedicated: batch}",
              b"}", b")"),
    "affinity": (b"/filter-result", b"didn't match Pod's node affinity/selector",
                 b"r", b"x"),
    "score": (b"/finalscore-result", b'\\"TaintToleration\\":\\"300', b"0", b"1"),
}


def _alter_one_byte(kind: str):
    """The last byte of the first such value in the annotation, in any
    checked pod that has it."""
    ann, msg, old, new = TAMPERS[kind]

    def tamper(raw: bytes) -> bytes:
        i = raw.index(ann)
        j = raw.find(msg, i)
        if j < 0:  # this pod has no such value (no ssd term, ...)
            return raw
        j += len(msg) - 1
        assert raw[j:j + 1] == old, raw[j - 20:j + 5]
        return raw[:j] + new + raw[j + 1:]

    return tamper


def _child(tampered: str) -> int:
    import run

    params = json.loads((BENCH / "configs" / "baseline_c3_1k.json")
                        .read_text())["parameters"]
    return run.main(["--workload", CELL, "--seed", "2147483777",
                     "--seconds", "8", "--trace", "0"],
                    platform_required="cpu",
                    override={"nodes": NODES, "initial_pods": dict(
                        params["initial_pods"], count=INITIAL)},
                    warmup_override={"cycles": 4, "clean_cycles": 2, "max_cycles": 12},
                    tamper=_alter_one_byte(tampered) if tampered in TAMPERS else None)


def _run(tampered: str) -> tuple[dict, list[str]]:
    p = subprocess.run([sys.executable, __file__, "--child", tampered],
                       cwd=str(BENCH.parent), stdout=subprocess.PIPE,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = p.stdout.decode().splitlines()
    assert p.returncode == 0, "\n".join(lines[-20:])
    checks = [ln for ln in lines if ln.startswith("check ")]
    assert any("reference affinity_taints" in ln for ln in lines), \
        "the cell was not checked by its own reference"
    assert any(ln.startswith("profile posted and read back") for ln in lines)
    return json.loads(lines[-1]), checks


def test_run_with_an_altered_value():
    sound, checks = _run("none")
    assert sound["correct"] is True, checks
    for kind in TAMPERS:
        broken, checks = _run(kind)
        assert broken["correct"] is False, (kind, checks)
        # and for the one reason that was planted: a differing value
        assert [c for c in checks if "NOT OK" in c] == [
            c for c in checks if c.startswith("check annotation_and_nodeName")], checks


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--child":
        sys.exit(_child(sys.argv[2]))
    test_run_with_an_altered_value()
    print("ok")
