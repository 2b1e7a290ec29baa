"""kube_scheduler_simulator_tpu — a TPU-native kube-scheduler simulator.

A from-scratch re-design of the capabilities of
sigs.k8s.io/kube-scheduler-simulator (reference mounted at /root/reference)
for TPU hardware via JAX/XLA:

* The reference runs the real Go kube-scheduler one pod at a time, fanning
  Filter/Score across nodes with 16 goroutines (reference:
  simulator/docs/how-it-works.md:1-33, upstream Parallelizer).  Here the
  per-pod x per-node x per-plugin Filter/Score evaluation is a dense tensor
  program: a single jitted `lax.scan` over the pod queue whose carry is the
  mutable cluster state (resource accumulators, topology-domain counts) and
  whose per-step outputs are the full filter/score/finalscore tensors.

* Everything *static* during a replay — node labels, taints, affinity
  expressions, label selectors — is precompiled host-side into dense match
  arrays (`state/compile.py`); only resource counters and domain counts
  evolve on device.

* The behavioral contract of the reference is preserved: the 13+4 result
  annotation keys and their exact JSON encodings
  (reference: simulator/scheduler/plugin/annotation/annotation.go:3-30),
  scheduling-framework extension-point semantics
  (reference: simulator/scheduler/plugin/wrappedplugin.go), the HTTP API
  surface (reference: simulator/server/server.go:42-54), and the
  snapshot/reset/record/replay/import/sync services.
"""

import jax as _jax

# Bit-exact parity with the reference requires int64 score math
# (resultstore applies int64 weights, reference:
# simulator/scheduler/plugin/resultstore/store.go:504-507) and float64 for
# the few upstream float paths (balanced allocation, topology-spread
# normalizing weights).  x64 therefore is a hard requirement, enabled at
# import; XLA:TPU lowers i64/f64 (emulated) — the arrays on these paths are
# small relative to the [pods, nodes] tensors, which stay i32/bool.
_jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: the scan program compiles in tens of
# seconds on TPU, and every process of a run (server, standalone
# scheduler, parity gate) should pay that once.  The directory is part of
# the cache key, so it is a FIXED path: JAX_COMPILATION_CACHE_DIR when the
# environment sets it (jax reads that variable itself — nothing to
# configure here), else <checkout>/.jax_cache next to the package.  An
# installed copy (site-/dist-packages) gets neither: the package manager
# owns that tree, set the variable there.  A directory that cannot be
# created is an error at import: a silently cold cache looks like a slow
# chip.
import os as _os
from pathlib import Path as _Path

_parent = _Path(__file__).resolve().parent.parent
if not (_os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or {"site-packages", "dist-packages"} & set(_parent.parts)):
    _cache = _parent / ".jax_cache"
    _cache.mkdir(exist_ok=True)
    _jax.config.update("jax_compilation_cache_dir", str(_cache))
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 2)

__version__ = "0.1.0"

ANNOTATION_PREFIX = "kube-scheduler-simulator.sigs.k8s.io/"
