#!/usr/bin/env python3
"""Run a list of benchmark runs one after another and keep everything
they printed: how this benchmark's spreads and bounds were measured
(PERF.md section 2), and how a later PR can measure them again.

    python3 benchmark/study.py --tag t1 --out chiprun_out \\
        basic_5k.interactive:101:40:0 basic_5k.interactive:102:40:1

Each run is `cell:seed:seconds:trace`, a process of its own
(one process per chip, one after another).  Writes
<out>/study_<tag>.jsonl (one object per run: the result line, the exit
code, the wall seconds and every line the run printed) and prints, per
cell, each end-to-end metric's values and their spread
(interquartile range over the median, statistics.quantiles).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
from lib.stats import spread  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tag", required=True)
    ap.add_argument("--out", default="chiprun_out")
    ap.add_argument("--keep-trace", action="store_true",
                    help="after a --trace 1 run, write the trace as a "
                         "compact fixture (lib/xplane.py --fixture) to <out>")
    ap.add_argument("runs", nargs="+")
    args = ap.parse_args()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    path = out / f"study_{args.tag}.jsonl"
    groups: dict[tuple, list[dict]] = {}
    rc_all = 0
    with open(path, "a") as f:
        for spec in args.runs:
            cell, seed, seconds, trace = spec.split(":")
            cmd = [sys.executable, str(BENCH / "run.py"), "--workload", cell,
                   "--seed", seed, "--seconds", seconds, "--trace", trace]
            t0 = time.time()
            p = subprocess.run(cmd, cwd=str(BENCH.parent),
                               stdout=subprocess.PIPE, stderr=subprocess.PIPE)
            lines = p.stdout.decode().splitlines()
            result = None
            if p.returncode == 0 and lines:
                result = json.loads(lines[-1])
            rec = {"spec": spec, "rc": p.returncode,
                   "wall_s": round(time.time() - t0, 1), "result": result,
                   "lines": lines[:-1] if result else lines,
                   "stderr": p.stderr.decode()[-2000:]}
            f.write(json.dumps(rec) + "\n")
            f.flush()
            rc_all |= p.returncode
            if args.keep_trace and trace == "1" and p.returncode == 0:
                prof = BENCH.parent / ".bench_work" / cell / "profile"
                fx = out / f"trace_{args.tag}_{cell}_{seed}.json.gz"
                q = subprocess.run(
                    [sys.executable, str(BENCH / "lib" / "xplane.py"),
                     str(prof), "--fixture", str(fx)], cwd=str(BENCH.parent),
                    env={**os.environ, "JAX_PLATFORMS": "cpu"},
                    stdout=subprocess.PIPE, stderr=subprocess.PIPE)
                print(f"    fixture {fx.name}: rc {q.returncode} "
                      f"{q.stdout.decode()[-600:]}", flush=True)
            brief = {k: round(v["value"], 5) for k, v in
                     (result or {}).get("metrics", {}).items()}
            print(f"{spec} rc={p.returncode} wall={rec['wall_s']}s "
                  f"correct={(result or {}).get('correct')} {brief}", flush=True)
            for ln in lines[:-1]:
                if ln.startswith(("warm-up", "samples", "set-up", "client",
                                  "drift", "trace", "window", "check ann",
                                  "check rep", "  ")):
                    print("    " + ln, flush=True)
            if p.returncode:
                print("    stderr: " + p.stderr.decode()[-600:], flush=True)
            if result and trace == "0":
                groups.setdefault((cell, seconds), []).append(result)
    for (cell, seconds), results in groups.items():
        print(f"== {cell} {seconds}s: {len(results)} runs")
        for name in results[0]["metrics"]:
            vals = [r["metrics"][name]["value"] for r in results]
            sp = f"{100 * spread(vals):.2f}%" if len(vals) >= 2 else "n/a"
            print(f"   {name}: spread {sp}  values "
                  + " ".join(f"{v:.5g}" for v in vals))
    return rc_all


if __name__ == "__main__":
    sys.exit(main())
