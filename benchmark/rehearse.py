#!/usr/bin/env python3
"""benchmark/rehearse.py — the CPU rehearsal.  platform: cpu.  NEVER a result.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [--quick]

What it checks, at tiny sizes (50 nodes, 20 initial pods, a 6 s window):
  1. every cell of BENCHMARK.json runs end to end through benchmark/run.py
     against a server on the CPU backend, --trace 0 and --trace 1, and the
     result line has the contract's keys, the cell's own metrics, and
     `correct: true` (--quick: --trace 1 for the first cell only);
  2. the harness is driven by data: in a temporary copy, a new
     configuration with a plain reference of its own, a new traffic mix, a
     new cell and a new span-based per-layer metric are added AS FILES plus
     one entry each in BENCHMARK.json, nothing that was there is edited,
     and the new cell runs, is checked by the new reference and reports
     the new metric;
  3. the trace reduction (lib/xplane.py) reproduces the numbers recorded
     beside the small recorded trace in benchmark/testdata/.
Every number it prints comes from XLA's CPU backend and means nothing
about the chip.  benchmark/run.py itself refuses to run without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
TINY_NODES, TINY_INITIAL = 50, 20
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def one(bench_file: Path, cell: str, trace: int, seconds: float) -> int:
    """In a process of its own (benchmark/run.py times set-up from its own
    start): one run with the chip check turned to `cpu` and tiny sizes."""
    sys.path.insert(0, str(bench_file.parent / "benchmark"))
    import run

    bench = json.loads(bench_file.read_text())
    w = next(x for x in bench["workloads"] if x["name"] == cell)
    cfg_file = next(c["file"] for c in bench["configs"] if c["name"] == w["config"])
    params = json.loads((bench_file.parent / cfg_file).read_text())["parameters"]
    override = {"nodes": TINY_NODES,
                "initial_pods": dict(params["initial_pods"], count=TINY_INITIAL)}
    return run.main(["--workload", cell, "--seed", "2147483659",
                     "--seconds", str(seconds), "--trace", str(trace)],
                    platform_required="cpu", override=override,
                    warmup_override={"cycles": 4, "clean_cycles": 2, "max_cycles": 12},
                    bench_file=bench_file)


def rehearse_cell(bench_file: Path, cell: str, trace: int, seconds: float,
                  want_metrics: list[str]) -> dict:
    p = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), "--one", cell,
         "--trace", str(trace), "--seconds", str(seconds),
         "--bench-file", str(bench_file)],
        cwd=str(bench_file.parent), stdout=subprocess.PIPE,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = p.stdout.decode().splitlines()
    if p.returncode != 0 or not lines:
        print("\n".join(lines[-15:]))
        raise SystemExit(f"rehearsal of {cell} --trace {trace} exited "
                         f"{p.returncode}")
    res = json.loads(lines[-1])
    missing = KEYS - set(res)
    assert not missing, f"{cell}: result line lacks {missing}"
    assert res["device"]["platform"] == "cpu", res["device"]
    assert res["correct"] is True, f"{cell}: correct is {res['correct']}"
    lacking = [m for m in want_metrics if m not in res["metrics"]]
    assert not lacking, f"{cell} --trace {trace}: no {lacking}"
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"]), res["device"]
    print(f"platform: cpu  {cell} --trace {trace}: ok, "
          f"{len(res['metrics'])} metrics, correct {res['correct']}")
    res["lines"] = lines[:-1]
    return res


def reported(bench_file: Path, family: str, cell: str) -> list[str]:
    """The metric names run.py owes for this cell, less those that only a
    device trace can give (nothing runs on a device here)."""
    sys.path.insert(0, str(BENCH))
    import run

    bench = json.loads(bench_file.read_text())
    return [m["name"] for m in run.metrics_of(bench, family, cell)
            if m["source"] != "device_trace" or m["name"].startswith("xla_compile")]


def data_driven(seconds: float) -> None:
    """A configuration + its reference + a traffic mix + a cell + a span
    metric, as new files only."""
    tmp = Path(tempfile.mkdtemp(prefix="bench_rehearse_"))
    try:
        shutil.copytree(BENCH, tmp / "benchmark",
                        ignore=shutil.ignore_patterns("__pycache__"))
        os.symlink(REPO / "kube_scheduler_simulator_tpu",
                   tmp / "kube_scheduler_simulator_tpu")
        before = {p: p.read_bytes() for p in (tmp / "benchmark").rglob("*")
                  if p.is_file()}
        bench = json.loads((REPO / "BENCHMARK.json").read_text())
        # the configuration: a file of its own that names ITS reference
        # (here a stand-in that re-exports the default profile's; a real one
        # implements the plugins its deployment adds)
        cfg = json.loads((BENCH / "configs/sched_perf_basic_5k.json").read_text())
        cfg.update(name="rehearsal_cfg", reference="rehearsal_reference")
        (tmp / "benchmark/configs/rehearsal_cfg.json").write_text(json.dumps(cfg))
        (tmp / "benchmark/reference/rehearsal_reference.py").write_text(
            '"""Rehearsal only: the reference a new configuration brings."""\n'
            "from reference.default_profile import (  # noqa: F401\n"
            "    ARITHMETICS, KEYS, ReferenceScheduler)\n")
        bench["configs"].append({
            "name": "rehearsal_cfg", "source": cfg["source"],
            "file": "benchmark/configs/rehearsal_cfg.json",
            "reduced": cfg["reduced"], "why": "rehearsal only"})
        (tmp / "benchmark/traffic/rollout7.json").write_text(json.dumps({
            "name": "rollout7", "loop": "closed", "clients": 1,
            "driver": "closed_loop",
            "parameters": {"burst": 7, "submit": "import", "read": "seeded",
                           "think_s": 0.05},
            "warmup": {"ascending_shapes": True, "cycles": 4,
                       "clean_cycles": 2, "max_cycles": 12,
                       "retry_cap_s": 0.5},
            "provision_cycles_per_s": 4, "check_pods": 5}))
        (tmp / "benchmark/metrics/decode_chunk_share_lat.json").write_text(
            json.dumps({"name": "decode_chunk_share_lat",
                        "layer": "read", "unit": "%",
                        "moves": "result_latency_p50_s", "reader": "span_share",
                        "parameters": {"spans": ["decode_chunk"]}}))
        cell = "rehearsal_cfg.rollout7"
        bench["workloads"].append({
            "name": cell, "config": "rehearsal_cfg",
            "traffic": "rollout7", "chips": 1, "why": "rehearsal only"})
        for m in bench["end_to_end"]:
            if m["name"] == "result_latency_p50_s":
                m["workloads"].append(cell)
        bench["per_layer"].append({
            "name": "decode_chunk_share_lat", "unit": "%", "better": "lower",
            "source": "program_span", "layer": "read",
            "moves": "result_latency_p50_s", "workloads": [cell]})
        (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
        res = rehearse_cell(tmp / "BENCHMARK.json", cell, 1, seconds,
                            ["decode_chunk_share_lat"])
        assert any("reference rehearsal_reference" in ln for ln in res["lines"]), \
            "the new configuration was not checked by its own reference"
        for p, data in before.items():
            assert p.read_bytes() == data, f"{p} was edited"
        print("platform: cpu  data-driven: a configuration with its reference, "
              "a traffic mix, a cell and a span metric added as files only: ok")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def recorded_trace() -> None:
    sys.path.insert(0, str(BENCH))
    from tests.test_xplane import test_recorded_trace
    test_recorded_trace()
    print("platform: cpu  trace reduction against the recorded trace: ok")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--one")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--bench-file", default=str(REPO / "BENCHMARK.json"))
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    if args.one:
        return one(Path(args.bench_file), args.one, args.trace, args.seconds)
    print("platform: cpu — a rehearsal, never a result")
    recorded_trace()
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for i, w in enumerate(bench["workloads"]):
        rehearse_cell(REPO / "BENCHMARK.json", w["name"], 0, args.seconds,
                      reported(REPO / "BENCHMARK.json", "end_to_end", w["name"]))
        if i == 0 or not args.quick:
            rehearse_cell(REPO / "BENCHMARK.json", w["name"], 1, args.seconds,
                          reported(REPO / "BENCHMARK.json", "per_layer", w["name"]))
    data_driven(args.seconds)
    print("platform: cpu  rehearsal passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
