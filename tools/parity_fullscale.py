"""Full-scale parity artifact: byte-identical annotations at 10k x 5k.

The parity gates elsewhere run at reduced scale; this script executes
configs 4 and 5 of models/workloads.py BASELINE_CONFIGS at their FULL
shape (10,000 pods x 5,000 nodes) against the sequential CPU oracle and
writes the verdict under chiprun_out/ (git-ignored; the chip tool brings
it back from a device run).

Every one of the 13 per-pod result annotations (filter-result,
score-result, finalscore-result, selected-node, ...) must match the
oracle byte-for-byte for every pod.  Both sides stream
(reference_impl/parity_gate.py stream_oracle_parity): the oracle runs in
a separate CPU-forced RLIMIT-capped subprocess emitting one pod per
line, and the comparison holds one pod at a time — the full ~13 GB
annotation product is never resident.

By default forces the CPU XLA backend; with --device it uses whatever
backend jax initializes (the TPU where there is one) so the artifact
proves DEVICE-layout parity at full scale.  Wall times are recorded but
are NOT benchmark figures (the run may share the host with other work).

Usage: python tools/parity_fullscale.py [outfile] [--device]
       [--configs 4,5] [--scale 1.0]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, ".")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("outfile", nargs="?",
                    default="chiprun_out/parity_fullscale.json")
    ap.add_argument("--device", action="store_true",
                    help="use the default jax backend (TPU where present) "
                         "instead of forcing CPU")
    ap.add_argument("--configs", default="4,5")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not args.device:
        from kube_scheduler_simulator_tpu.utils.platform import force_cpu

        force_cpu()
    import jax

    from kube_scheduler_simulator_tpu.reference_impl.parity_gate import (
        stream_oracle_parity)

    backend = jax.devices()[0].platform
    print(f"backend: {backend} ({jax.devices()})", flush=True)

    results = []
    for idx in [int(x) for x in args.configs.split(",") if x]:
        t0 = time.time()
        last = {"n": 0}

        def hb(i, _last=last):
            if i - _last["n"] >= 2000:
                _last["n"] = i
                print(f"  compared {i} pods", flush=True)

        r = stream_oracle_parity(idx, args.scale, args.seed, chunk=512,
                                 want_digest=True, heartbeat=hb)
        ok = r["ok"]
        print(f"config {idx}: {'BYTE-PARITY OK' if ok else 'FAILED'} "
              f"({r['keys_checked']} annotation values, "
              f"{time.time() - t0:.0f}s)", flush=True)
        results.append({
            "config": idx, "pods": r["pods"],
            "mismatches": r["mismatches"],
            "keys_compared": r["keys_checked"],
            "first_mismatch": r["first_mismatch"],
            "oracle_completed": r["compared"] == r["pods"],
            "oracle_rc": r["oracle_rc"],
            "oracle_annotations_sha256": r["sha256"],
            "wall_seconds": {"oracle_stream_and_compare": r["oracle_seconds"],
                             "replay_and_transfer": r["replay_seconds"]},
        })

    import subprocess

    rev = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                         capture_output=True, text=True).stdout.strip()
    artifact = {"rev": rev, "backend": backend,
                "protocol": "PARITY.md parity protocol, full scale",
                "scale": args.scale,
                "results": results,
                "all_parity_ok": all(
                    r["mismatches"] == 0 and r["oracle_completed"]
                    for r in results)}
    Path(args.outfile).parent.mkdir(parents=True, exist_ok=True)
    with open(args.outfile, "w") as f:
        json.dump(artifact, f, indent=2)
    print(f"wrote {args.outfile}; all_parity_ok={artifact['all_parity_ok']}",
          flush=True)


if __name__ == "__main__":
    main()
