"""`correct` comes out false when one value of one pod of a BURST is
altered on its way out of the server, or one pod of a burst is bound
elsewhere than the reference binds it: the broken path for the cell
`baseline_c3_queue_1k.rollout30_profile` (reference/affinity_taints.py,
driver drivers/closed_loop_profile_burst.py), as
test_broken_path_baseline_c3.py is for the one-pod cell of the same
deployment.  Beside it, a rehearsal of `basic_5k.rollout10` (the mix
traffic/rollout10.json over drivers/closed_loop.py with a burst of 10),
which PR 50 measured once and could not bound, so it runs from a
temporary BENCHMARK.json that lists it.

test_run_with_an_altered_burst (slow: three server runs on the CPU
backend, ~2.5 min): skips the harness's look for a chip (platform "cpu")
and drives the cell at 40 nodes under the posted four-plugin profile, the
29 smaller bursts first: once as it is (`correct` true; the passes of the
run hold every count from 1 to 30, the warm-up's own and whatever the
loop's 50 ms cap splits off), once with ONE byte of one annotation of ONE
checked pod of the window's first burst altered (`correct` false by the
annotation limit alone), once with one pod in the middle of that burst
reported bound to another node (`correct` false by the placement limit
alone).

    python3 -m pytest benchmark/tests/test_broken_path_baseline_c3_queue.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

CELL = "baseline_c3_queue_1k.rollout30_profile"
ROLLOUT10 = "basic_5k.rollout10"
NODES = 40
SEED = "2147483777"
MESSAGE = b"had untolerated taint {dedicated: batch}"
WARMUP = {"cycles": 4, "clean_cycles": 2, "max_cycles": 12}


def _alter_one_byte_of_one_pod():
    """The last byte of the first untolerated-taint message in the
    filter-result of the FIRST checked pod that has one; every other pod
    is handed on as the server sent it."""
    altered = []

    def tamper(raw: bytes) -> bytes:
        j = raw.find(MESSAGE, raw.index(b"/filter-result"))
        if altered or j < 0:
            return raw
        j += len(MESSAGE) - 1
        assert raw[j:j + 1] == b"}", raw[j - 20:j + 5]
        altered.append(j)
        return raw[:j] + b")" + raw[j + 1:]

    return tamper


def _bind_one_pod_elsewhere():
    """The watch stream's word on ONE pod, the middle one of the last
    burst the reference replays, turned to a node the cluster does not
    have: what a pod bound elsewhere than the reference binds it looks
    like to the harness."""
    from lib import client as cl

    order = cl.WatchStream.queue_order

    def queue_order(self, names):
        out = order(self, names)
        victim = out[-15]
        print(f"tampered: {victim} reported on 'node-elsewhere', was on "
              f"{self.decided[victim]!r}", flush=True)
        self.decided[victim] = "node-elsewhere"
        return out

    cl.WatchStream.queue_order = queue_order


def _bench_file_with_rollout10() -> Path:
    """BENCHMARK.json as it is plus the cell `basic_5k.rollout10`, in a
    temporary directory whose `benchmark` is this one: the cell was
    measured once on the chip and not bounded (PERF.md section 7, row 1),
    so the accepted benchmark does not list it; its mix and its driver
    are rehearsed here all the same."""
    import tempfile

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    if all(w["name"] != ROLLOUT10 for w in bench["workloads"]):
        bench["workloads"].append({
            "name": ROLLOUT10, "config": "sched_perf_basic_5k",
            "traffic": "rollout10", "chips": 1, "why": "rehearsal"})
        for m in bench["end_to_end"]:
            if m["name"] == "result_latency_p50_s":
                m["workloads"].append(ROLLOUT10)
    root = Path(tempfile.mkdtemp(prefix="kss_bench_"))
    (root / "benchmark").symlink_to(BENCH)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root / "BENCHMARK.json"


def _child(cell: str, tampered: str) -> int:
    import run

    if cell == ROLLOUT10:
        return run.main(["--workload", cell, "--seed", SEED,
                         "--seconds", "6", "--trace", "0"],
                        platform_required="cpu", override={"nodes": NODES},
                        warmup_override=WARMUP,
                        bench_file=_bench_file_with_rollout10())
    tamper = None
    if tampered == "annotation":
        tamper = _alter_one_byte_of_one_pod()
    elif tampered == "placement":
        _bind_one_pod_elsewhere()
    return run.main(["--workload", cell, "--seed", SEED,
                     "--seconds", "6", "--trace", "0"],
                    platform_required="cpu", override={"nodes": NODES},
                    warmup_override=WARMUP, tamper=tamper)


def _run(cell: str, tampered: str) -> tuple[dict, list[str], list[str]]:
    p = subprocess.run([sys.executable, __file__, "--child", cell, tampered],
                       cwd=str(BENCH.parent), stdout=subprocess.PIPE,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = p.stdout.decode().splitlines()
    assert p.returncode == 0, "\n".join(lines[-20:])
    checks = [ln for ln in lines if ln.startswith("check ")]
    return json.loads(lines[-1]), checks, lines


def _not_ok(checks: list[str]) -> list[str]:
    return [c.split(":")[0] for c in checks if "NOT OK" in c]


def test_run_with_an_altered_burst():
    sound, checks, lines = _run(CELL, "none")
    assert sound["correct"] is True, checks
    assert any("reference affinity_taints" in ln for ln in lines), \
        "the cell was not checked by its own reference"
    assert any(ln.startswith("profile posted and read back") for ln in lines)
    # the 29 smaller bursts came first, smallest first, then the cell's own
    shapes = [ln for ln in lines if ln.startswith("warm-up cycle ")]
    assert [int(ln.split(": ")[1].split(" pods")[0]) for ln in shapes[:31]] \
        == list(range(1, 30)) + [30, 30], shapes[:31]
    assert sound["attempted"] % 30 == 0 and sound["attempted"] >= 30
    broken, checks, _ = _run(CELL, "annotation")
    assert broken["correct"] is False, checks
    assert _not_ok(checks) == ["check annotation_and_nodeName_values_differing"], checks
    assert any(c.startswith("check annotation_and_nodeName_values_differing: 1 ")
               for c in checks), checks
    broken, checks, lines = _run(CELL, "placement")
    assert broken["correct"] is False, checks
    assert any(ln.startswith("tampered: ") for ln in lines)
    assert _not_ok(checks) == ["check replayed_pods_placed_elsewhere"], checks
    assert any(c.startswith("check replayed_pods_placed_elsewhere: 1 ")
               for c in checks), checks


def test_rollout10_rehearsal():
    """`basic_5k.rollout10` at 40 nodes: bursts of 1..9 first, then 10 an
    import under the default profile, checked by the default profile's
    reference in the queue's order."""
    sound, checks, lines = _run(ROLLOUT10, "none")
    assert sound["correct"] is True, checks
    assert any("reference default_profile" in ln for ln in lines)
    shapes = [ln for ln in lines if ln.startswith("warm-up cycle ")]
    assert [int(ln.split(": ")[1].split(" pods")[0]) for ln in shapes[:11]] \
        == list(range(1, 10)) + [10, 10], shapes[:11]
    assert sound["attempted"] % 10 == 0 and sound["attempted"] >= 10


if __name__ == "__main__":
    if len(sys.argv) == 4 and sys.argv[1] == "--child":
        sys.exit(_child(sys.argv[2], sys.argv[3]))
    test_run_with_an_altered_burst()
    test_rollout10_rehearsal()
    print("ok")
