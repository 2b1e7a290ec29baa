"""Profile the annotation decode path at the config-4 node shape.

Usage: python docs/bench/profile_decode.py [n_pods] [config_idx]
Runs on the CPU XLA backend (force_cpu): it profiles host decode only.
"""
import sys
import time

sys.path.insert(0, ".")
from kube_scheduler_simulator_tpu.utils.platform import force_cpu

force_cpu()

import numpy as np

from kube_scheduler_simulator_tpu.framework.replay import replay
from kube_scheduler_simulator_tpu.models.workloads import baseline_config
from kube_scheduler_simulator_tpu.state.compile import compile_workload
from kube_scheduler_simulator_tpu.store import decode

n_pods = int(sys.argv[1]) if len(sys.argv) > 1 else 256
idx = int(sys.argv[2]) if len(sys.argv) > 2 else 4

nodes, pods, cfg = baseline_config(idx, scale=n_pods / 10000, node_scale=1.0)
print(f"{len(pods)} pods x {len(nodes)} nodes, plugins={cfg.enabled}")
cw = compile_workload(nodes, pods, cfg)
rr = replay(cw, chunk=256)
print("replay done")

# warm (native ctx build, first chunk recon)
decode.decode_pod_result(rr, 0)

t0 = time.time()
anns = decode.decode_all_parallel(rr, n_pods)
dt = time.time() - t0
total_bytes = sum(len(v) for a in anns for v in a.values())
print(f"decode_all_parallel: {dt:.2f}s -> {n_pods/dt:.1f} pods/s, "
      f"{total_bytes/n_pods/1024:.0f} KiB/pod, {total_bytes/dt/1e6:.0f} MB/s")

# cProfile on the serial path
import cProfile
import pstats

pr = cProfile.Profile()
pr.enable()
for i in range(min(64, n_pods)):
    decode.decode_pod_result(rr, i)
pr.disable()
st = pstats.Stats(pr)
st.sort_stats("cumulative").print_stats(25)
