"""The oracle child: the plain reference beside the window, on the CPU.

Started before the window opens; it builds the deployment from the same
seed as the parent (same generator, same draws) and waits.  One JSON line
on stdin names the measured pods to replay, in the server's queue order,
and which of them to render in full.  It writes its answer to a file and
prints one short line.  The reference is the file in reference/ that the
configuration's file names under `reference` (its interface is at the
head of reference/default_profile.py), so a configuration that works a
part of the scheduler no reference here covers brings its own.  Imports
nothing of the program, and no JAX.
"""

from __future__ import annotations

import importlib
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))


def main() -> None:
    spec = json.loads(sys.stdin.readline())
    cfg = json.loads(Path(spec["config_file"]).read_text())
    reference = importlib.import_module(f"reference.{cfg['reference']}")
    gen = importlib.import_module(f"generators.{cfg['generator']}")
    dep = gen.generate(dict(cfg["parameters"], **spec.get("override") or {}),
                       spec["seed"])
    arith = reference.ARITHMETICS[spec.get("arith", "exact")]
    ref = reference.ReferenceScheduler(dep.nodes, dep.initial_pods, arith)
    print(json.dumps({"ready": True}), flush=True)

    job = json.loads(sys.stdin.readline())
    order, full = job["order"], set(job["check"])
    t0 = time.time()
    by_name = {}
    for _ in order:  # the parent drew exactly these, in generation order
        pod = dep.measured_pod()
        by_name[pod["metadata"]["name"]] = pod
    placements, annotations = {}, {}
    for name in order:
        anns, node = ref.schedule_one(by_name[name], annotate=name in full)
        placements[name] = node
        if anns is not None:
            annotations[name] = anns
    Path(job["out"]).write_text(json.dumps(
        {"placements": placements, "annotations": annotations}))
    print(json.dumps({"done": True, "pods": len(order), "full": len(full),
                      "reference": cfg["reference"], "arith": arith.name,
                      "seconds": round(time.time() - t0, 3)}), flush=True)


if __name__ == "__main__":
    main()
