"""`correct` comes out false when one of the cell's two refusals is
altered on its way out of the server: the broken path for
reference/node_inclusion.py, as test_broken_path_antiaffinity.py is for
reference/antiaffinity.py (the verdict tests of test_broken_path.py hold
for every cell and are not repeated here).

test_run_with_an_altered_refusal (slow: three server runs on the CPU
backend, ~1.5 min): skips the harness's look for a chip (platform "cpu")
and drives `nodeinclusion_5k.interactive` three times at 600 nodes (480
plain, 120 tainted: the generator keeps the source's 4 : 1), a size at
which an 8 s window does not use up the 480 eligible hostnames
(rehearse.py's 50 would: 40 eligible, then the second round, which is
still `correct: true` but another thing): once as it is (`correct` true),
once with ONE byte of one tainted node's message in filter-result
altered, once with one byte of one taken hostname's skew message altered
(`correct` false both times, for that reason alone).

    python3 -m pytest benchmark/tests/test_broken_path_nodeinclusion.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

CELL = "nodeinclusion_5k.interactive"
NODES = 600
TAMPERS = {
    "taint": (b"had untolerated taint {foo: }", b"}", b")"),
    "spread": (b"didn't match pod topology spread constraints", b"s", b"z"),
}


def _alter_one_byte(kind: str):
    """The last byte of the first such refusal's message in filter-result."""
    msg, old, new = TAMPERS[kind]

    def tamper(raw: bytes) -> bytes:
        i = raw.index(b"/filter-result")
        j = raw.index(msg, i) + len(msg) - 1
        assert raw[j:j + 1] == old, raw[j - 20:j + 5]
        return raw[:j] + new + raw[j + 1:]

    return tamper


def _child(tampered: str) -> int:
    import run

    return run.main(["--workload", CELL, "--seed", "2147483777",
                     "--seconds", "8", "--trace", "0"],
                    platform_required="cpu", override={"nodes": NODES},
                    warmup_override={"cycles": 4, "clean_cycles": 2, "max_cycles": 12},
                    tamper=_alter_one_byte(tampered) if tampered in TAMPERS else None)


def _run(tampered: str) -> tuple[dict, list[str]]:
    p = subprocess.run([sys.executable, __file__, "--child", tampered],
                       cwd=str(BENCH.parent), stdout=subprocess.PIPE,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = p.stdout.decode().splitlines()
    assert p.returncode == 0, "\n".join(lines[-20:])
    checks = [ln for ln in lines if ln.startswith("check ")]
    assert any("reference node_inclusion" in ln for ln in lines), \
        "the cell was not checked by its own reference"
    return json.loads(lines[-1]), checks


def test_run_with_an_altered_refusal():
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
    test_run_with_an_altered_refusal()
    print("ok")
