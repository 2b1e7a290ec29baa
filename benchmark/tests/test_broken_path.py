"""`correct` comes out false when the path under the harness is broken.

test_verdict_*: the arithmetic of the verdict, on made-up counts: one
differing byte, one pod placed elsewhere, one acknowledged pod left
undecided, one malformed read or one hidden rung each make it false.

test_run_with_an_altered_answer (slow: two server runs on the CPU
backend, ~1 min): skips the harness's look for a chip (platform "cpu",
50 nodes) and drives the rest of a run twice: once as it is (`correct`
true), once with ONE byte of one result annotation altered on its way
out of the server (`correct` false).

    python3 -m pytest benchmark/tests/test_broken_path.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

SOUND = {"annotation_mismatches": [], "placement_mismatches": []}


def _verdict(**kw):
    import run

    args = dict(cmp_res=SOUND, n_checked=16, check_pods=16, undecided=0,
                malformed=0, rung_problems=0)
    args.update(kw)
    return run.verdict(**args)[0]


def test_verdict_sound_is_correct():
    assert _verdict() is True


def test_verdict_each_violation_is_not_correct():
    assert not _verdict(cmp_res=dict(SOUND, annotation_mismatches=[("p", "k")]))
    assert not _verdict(cmp_res=dict(SOUND, placement_mismatches=["p"]))
    assert not _verdict(undecided=1)
    assert not _verdict(malformed=1)
    assert not _verdict(rung_problems=1)
    assert not _verdict(n_checked=0)


def _alter_one_byte(raw: bytes) -> bytes:
    """One digit of one score inside the score-result annotation."""
    i = raw.index(b"/score-result")
    j = raw.index(b"NodeResourcesFit", i)
    k = j + len(b'NodeResourcesFit\\":\\"')
    assert raw[k:k + 1].isdigit(), raw[j:j + 40]
    return raw[:k] + (b"1" if raw[k:k + 1] != b"1" else b"2") + raw[k + 1:]


def _child(tampered: str) -> int:
    import run

    params = json.loads((BENCH / "configs/sched_perf_basic_5k.json").read_text())["parameters"]
    override = {"nodes": 50, "initial_pods": dict(params["initial_pods"], count=20)}
    return run.main(["--workload", "basic_5k.interactive", "--seed", "2147483777",
                     "--seconds", "8", "--trace", "0"],
                    platform_required="cpu", override=override,
                    warmup_override={"cycles": 4, "clean_cycles": 2, "max_cycles": 12},
                    tamper=_alter_one_byte if tampered == "1" else None)


def _run(tampered: bool) -> dict:
    p = subprocess.run([sys.executable, __file__, "--child", str(int(tampered))],
                       cwd=str(BENCH.parent), stdout=subprocess.PIPE,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = p.stdout.decode().splitlines()
    assert p.returncode == 0, "\n".join(lines[-20:])
    checks = [ln for ln in lines if ln.startswith("check ")]
    return json.loads(lines[-1]), checks


def test_run_with_an_altered_answer():
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
    test_verdict_sound_is_correct()
    test_verdict_each_violation_is_not_correct()
    test_run_with_an_altered_answer()
    print("ok")
