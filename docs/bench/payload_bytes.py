"""Measure the compact replay's D2H payload per pod, per config.

Every byte per (pod, node) crosses the device->host link once per
replay.  This script builds each BASELINE config at a reduced queue (payload per pod is
queue-length independent: [N]-shaped rows) and sums the actual transferred
chunk bytes, splitting out rows that stayed host-resident
("host" score group, framework/replay.py) as the saving.

Usage: python docs/bench/payload_bytes.py  (hermetically CPU-backed)
Writes docs/bench/r04-payload-bytes.json.
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from kube_scheduler_simulator_tpu.utils.platform import force_cpu  # noqa: E402

force_cpu()  # byte counts only: valid from the CPU backend

from kube_scheduler_simulator_tpu.framework.replay import replay  # noqa: E402
from kube_scheduler_simulator_tpu.models.workloads import baseline_config  # noqa: E402
from kube_scheduler_simulator_tpu.state.compile import compile_workload  # noqa: E402


def measure(idx: int, scale: float = 0.02) -> dict:
    nodes, pods, cfg = baseline_config(idx, scale=scale, seed=0, node_scale=1.0)
    cw = compile_workload(nodes, pods, cfg)
    rr = replay(cw, chunk=64)
    cc = rr._compact
    p = len(pods)
    n = len(nodes)
    # per-POD bytes = per-row bytes: the last chunk is padded to the full
    # chunk size, so divide by the padded row count, not by p
    total_rows = sum(a.shape[0] for a in cc.packed)
    transferred = round(sum(
        a.nbytes for group in (cc.packed, cc.raw8, cc.raw16, cc.raw32)
        for a in group) * p / max(total_rows, 1))
    host_rows = [name for g, name in cc.score_cols if g == "host"]
    # bytes those rows would have cost at their narrowest transfer dtype
    # (the pre-change behavior: bound-derived i8/i16/i32/i64)
    saved = 0
    for name in host_rows:
        src = cw.host["static_score_rows"][name]
        bound = max(int(src.max(initial=0)), -int(src.min(initial=0)))
        width = 1 if bound <= 0x7F else 2 if bound <= 0x7FFF else 4 if bound <= 0x7FFFFFFF else 8
        saved += p * n * width
    return {
        "pods": p, "nodes": n, "plugins": cfg.enabled,
        "transferred_bytes_per_pod": round(transferred / p),
        "host_resident_rows": host_rows,
        "saved_bytes_per_pod": round(saved / p),
        "saving_fraction": round(saved / (saved + transferred), 3),
        "full_scale_transfer_gb": round(
            transferred / p * {1: 100, 2: 1000, 3: 5000, 4: 10000, 5: 10000}[idx]
            / 1e9, 2),
    }


def main():
    out = {f"config{i}": measure(i) for i in (1, 2, 3, 4, 5)}
    path = Path(__file__).parent / "r04-payload-bytes.json"
    path.write_text(json.dumps(out, indent=1))
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
