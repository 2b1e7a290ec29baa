"""Black-box smoke gate: `make blackbox-smoke` / `python -m tools.blackbox_smoke`.

Arms a ONE-RULE fault plan through the real KSS_TPU_FAULT_PLAN env
surface, runs an engine wave with the retry budget pinned to 0 (so the
transient fault aborts the wave instead of healing), and asserts that a
well-formed post-mortem dump landed in KSS_TPU_BLACKBOX_DIR — schema-
checked by utils.blackbox.validate_dump, which requires:

  * the fault trip on the record (seam + error + classification) and a
    classified cause;
  * the protocol's action (wave.abort here);
  * non-empty counter deltas for the failing wave;
  * a device fingerprint with an explicit hbm_available flag.

This is the cheapest end-to-end proof that a crashed wave ships its own
evidence (docs/fault-injection.md) — `make test` runs it before the
tier-1 suite.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import tempfile


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    dump_dir = tempfile.mkdtemp(prefix="kss-blackbox-smoke-")
    plan = {"seed": 7, "rules": [
        {"seam": "replay.decision_fetch", "nth": 2, "error": "runtime"},
    ]}
    plan_path = os.path.join(dump_dir, "plan.json")
    with open(plan_path, "w", encoding="utf-8") as fh:
        json.dump(plan, fh)
    # env BEFORE the simulator imports: faults arms KSS_TPU_FAULT_PLAN
    # at module load, and the dump dir must be in force at abort time
    os.environ["KSS_TPU_FAULT_PLAN"] = "@" + plan_path
    os.environ["KSS_TPU_BLACKBOX_DIR"] = dump_dir
    os.environ["KSS_TPU_WAVE_MAX_RETRIES"] = "0"
    # pin the toggle the assertions depend on: an inherited
    # KSS_TPU_BLACKBOX=0 must not fail `make test` spuriously — the
    # smoke asserts the default-configuration behavior
    os.environ["KSS_TPU_BLACKBOX"] = "1"

    from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
    from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
    from kube_scheduler_simulator_tpu.models.workloads import (
        make_nodes, make_pods)
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
    from kube_scheduler_simulator_tpu.utils.blackbox import validate_dump
    from kube_scheduler_simulator_tpu.utils.faults import InjectedFault

    store = ObjectStore()
    for n in make_nodes(6, seed=1):
        store.create("nodes", n)
    for p in make_pods(24, seed=2):
        store.create("pods", p)
    engine = SchedulerEngine(
        store, plugin_config=PluginSetConfig(enabled=["NodeResourcesFit"]),
        chunk=8)
    surfaced = None
    try:
        engine.schedule_pending()
    except InjectedFault as e:
        surfaced = e
    finally:
        engine.close()
    if surfaced is None:
        print("blackbox-smoke: FAIL — the armed fault never surfaced "
              "(retry budget 0 should abort the wave)", file=sys.stderr)
        return 1

    files = sorted(glob.glob(os.path.join(dump_dir, "blackbox-*.json")))
    if not files:
        print(f"blackbox-smoke: FAIL — no dump landed in {dump_dir}",
              file=sys.stderr)
        return 1
    with open(files[-1], encoding="utf-8") as fh:
        doc = json.load(fh)
    try:
        res = validate_dump(doc, require_fault=True)
    except ValueError as e:
        print(f"blackbox-smoke: FAIL — malformed dump {files[-1]}: {e}",
              file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "dump": files[-1],
        "reason": doc["reason"],
        "cause": doc["cause"],
        "event_kinds": res["kinds"],
        "deltas": len(doc["counter_deltas"]),
        "hbm_available": doc["device"]["hbm_available"],
    }))
    print(f"blackbox-smoke: ok — {doc['reason']} dump at {files[-1]} "
          f"({sum(res['kinds'].values())} events, "
          f"{len(doc['counter_deltas'])} counter deltas)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
