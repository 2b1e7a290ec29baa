"""`correct` comes out false when the churn node's refusal is altered on
its way out of the server: the broken path for reference/mixed_churn.py,
as test_broken_path_antiaffinity.py is for reference/antiaffinity.py
(the verdict tests of test_broken_path.py hold for every cell and are not
repeated here).

test_run_with_an_altered_refusal (slow: two server runs on the CPU
backend, ~2 min): skips the harness's look for a chip (platform "cpu")
and drives `mixed_churn_5k.interactive_churn` twice at 60 nodes: once as
it is (`correct` true, no malformed churn-pod read), once with ONE byte of
the churn node's NodeResourcesFit message in a measured pod's
filter-result altered (`correct` false, for that reason alone).

    python3 -m pytest benchmark/tests/test_broken_path_mixed_churn.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

CELL = "mixed_churn_5k.interactive_churn"
NODES = 60


def _alter_one_byte(raw: bytes) -> bytes:
    """The last letter of the churn node's refusal in filter-result."""
    i = raw.index(b"/filter-result")
    msg = b"Too many pods, Insufficient cpu, Insufficient memory"
    j = raw.index(msg, i) + len(msg) - 1
    assert raw[j:j + 1] == b"y", raw[j - 20:j + 5]
    return raw[:j] + b"z" + raw[j + 1:]


def _child(tampered: str) -> int:
    import run

    return run.main(["--workload", CELL, "--seed", "2147483777",
                     "--seconds", "8", "--trace", "0"],
                    platform_required="cpu", override={"nodes": NODES},
                    warmup_override={"cycles": 4, "clean_cycles": 2, "max_cycles": 12},
                    tamper=_alter_one_byte if tampered == "1" else None)


def _run(tampered: bool) -> tuple[dict, list[str]]:
    p = subprocess.run([sys.executable, __file__, "--child", str(int(tampered))],
                       cwd=str(BENCH.parent), stdout=subprocess.PIPE,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = p.stdout.decode().splitlines()
    assert p.returncode == 0, "\n".join(lines[-20:])
    checks = [ln for ln in lines if ln.startswith("check ")]
    assert any("reference mixed_churn" in ln for ln in lines), \
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
