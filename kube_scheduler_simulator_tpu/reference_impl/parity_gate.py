"""The streamed parity gate: device replay vs the sequential CPU oracle.

`stream_oracle_parity` replays one of `models/workloads.py`'s
BASELINE_CONFIGS on whatever backend JAX has, runs
`reference_impl/sequential.py` over the same workload in a CPU-forced
child process, and compares every annotation of every pod as the lines
arrive (PARITY.md, "The parity protocol").  `chip_smoke.py`'s gate phase
and `tools/parity_fullscale.py` drive it; `run_parity_gate` is the
pass/fail wrapper that tells a dead oracle child from a mismatch.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

# the checkout's root: the oracle child puts it on its sys.path
_REPO = Path(__file__).resolve().parents[2]


def log(*a):
    print(*a, file=sys.stderr, flush=True)


_ORACLE_CHILD = """\
import json, resource, sys
# self-imposed address-space cap: a runaway oracle gets a MemoryError in
# its own process instead of inviting the kernel OOM killer to take the
# whole gate with it.  Set here post-exec rather than via
# preexec_fn: running Python in a child forked from the
# JAX-multithreaded parent can deadlock before exec.  The parent starts
# this child with JAX_PLATFORMS=cpu: the oracle's plugin-helper imports
# pull jax in, and the chip belongs to the parent.
resource.setrlimit(resource.RLIMIT_AS, (4 << 30, 4 << 30))
sys.path.insert(0, {repo!r})
from kube_scheduler_simulator_tpu.models.workloads import baseline_config
from kube_scheduler_simulator_tpu.reference_impl.sequential import (
    SequentialScheduler)
nodes, pods, cfg = baseline_config({idx}, scale={scale}, seed={seed})
s = SequentialScheduler(nodes, pods, cfg)
w = sys.stdout
for pod in s.pods:
    anns, _ = s.schedule_one(pod)
    w.write(json.dumps(anns) + chr(10))
w.write("DONE " + str(len(s.pods)) + chr(10))
"""


def stream_oracle_parity(idx: int, scale: float, seed: int, chunk: int = 64,
                         want_digest: bool = False, heartbeat=None) -> dict:
    """Bit-parity check: device replay vs the sequential CPU oracle,
    both sides streamed so neither ever materializes the full annotation
    product (~13 GB at 10k x 5k).

    The oracle runs in ONE separate CPU-forced subprocess (address space
    self-capped via RLIMIT_AS) and streams one pod's annotations per
    line; this process decodes the same pod from the device replay and
    compares as lines arrive, holding one pod at a time.  An in-process
    oracle once had the kernel OOM-kill the whole run on a memory-starved
    host (exit 137): the parity machinery must never be able to take the
    process that owns the device down with it.
    The sequential oracle is the ground truth (reference semantics:
    simulator/scheduler/plugin/wrappedplugin.go recording shim,
    resultstore/store.go score math).

    Returns {ok, pods, compared, keys_checked, mismatches,
    first_mismatch, sha256 (of every compared value, when want_digest),
    oracle_rc, oracle_err, oracle_seconds, replay_seconds}."""
    import hashlib
    import os as _os
    import subprocess as _sp
    import tempfile

    from kube_scheduler_simulator_tpu.framework.replay import replay
    from kube_scheduler_simulator_tpu.models.workloads import baseline_config
    from kube_scheduler_simulator_tpu.state.compile import compile_workload
    from kube_scheduler_simulator_tpu.store.decode import decode_pod_result

    nodes, pods, cfg = baseline_config(idx, scale=scale, seed=seed)
    t0 = time.time()
    rr = replay(compile_workload(nodes, pods, cfg), chunk=chunk)
    replay_s = time.time() - t0
    h = hashlib.sha256() if want_digest else None
    out = {"ok": False, "pods": len(pods), "compared": 0, "keys_checked": 0,
           "mismatches": 0, "first_mismatch": None, "sha256": None,
           "oracle_rc": None, "oracle_err": "",
           "replay_seconds": round(replay_s, 1)}
    t0 = time.time()
    # child stderr goes to a temp file, not a pipe: this loop only drains
    # stdout, and a filled stderr pipe would deadlock the child mid-run
    with tempfile.TemporaryFile(mode="w+") as errf:
        child = _sp.Popen(
            [sys.executable, "-c",
             _ORACLE_CHILD.format(repo=str(_REPO), idx=idx,
                                  scale=scale, seed=seed)],
            stdout=_sp.PIPE, stderr=errf, text=True,
            env={**_os.environ, "JAX_PLATFORMS": "cpu"},
        )
        i = 0
        done = False
        try:
            for line in child.stdout:
                if heartbeat is not None:
                    heartbeat(i)
                if line.startswith("DONE "):
                    done = int(line[5:]) == len(pods) == i
                    break
                sa = json.loads(line)
                da = decode_pod_result(rr, i)
                for k, v in sa.items():
                    out["keys_checked"] += 1
                    if h is not None:
                        h.update(v.encode())
                    # .get: a device-side MISSING key is a mismatch to
                    # record, not a KeyError that kills the whole check
                    if da.get(k, "\0missing") != v:
                        out["mismatches"] += 1
                        if out["first_mismatch"] is None:
                            out["first_mismatch"] = {
                                "pod": i, "key": k,
                                "dev": da.get(k, "<missing>")[:200],
                                "oracle": v[:200]}
                i += 1
                out["compared"] = i
        finally:
            # clean DONE: give the child a moment to exit on its own so
            # the artifact records its true rc (not a kill's -9)
            try:
                child.wait(timeout=10 if done else 0.1)
            except _sp.TimeoutExpired:
                child.kill()
                child.wait()
            errf.seek(0)
            out["oracle_err"] = errf.read().strip()[-300:]
    out["oracle_rc"] = child.returncode
    out["oracle_seconds"] = round(time.time() - t0, 1)
    out["ok"] = done and out["mismatches"] == 0
    if h is not None:
        out["sha256"] = h.hexdigest()
    if not done and out["mismatches"] == 0:
        out["oracle_died"] = True  # environment failure, not a parity one
    return out


def run_parity_gate(idx: int, scale: float, seed: int,
                    _retry: bool = True) -> bool:
    r = stream_oracle_parity(idx, scale, seed)
    if r["ok"]:
        return True
    if r["first_mismatch"]:
        m = r["first_mismatch"]
        log(f"PARITY MISMATCH config {idx} pod {m['pod']} key {m['key']}\n"
            f"  dev={m['dev']}\n  seq={m['oracle']}")
        return False
    # the oracle child died (rlimit MemoryError, OOM kill, crash) — that
    # is an environment failure, not a parity failure; shed load and
    # retry once at a smaller gate shape rather than reporting value 0
    log(f"parity-gate oracle child died at pod {r['compared']}/{r['pods']} "
        f"(rc={r['oracle_rc']}): {r['oracle_err']}")
    if _retry and scale > 0.011:
        log(f"  retrying gate config {idx} at scale {scale / 4}")
        return run_parity_gate(idx, scale / 4, seed, _retry=False)
    return False
