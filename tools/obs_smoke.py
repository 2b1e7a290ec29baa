"""Observability smoke gate: `make obs-smoke` / `python -m tools.obs_smoke`.

The causal-telemetry proof in one process: arms a ONE-RULE fault plan,
runs a single engine wave UNDER AN EXPLICIT TRACE ID (the same
`trace_scope` the HTTP server enters for a stamped request), lets the
retry budget of 0 abort the wave, and asserts the one trace id threads
every observability surface:

  * tracer spans — the wave's spans carry the id as an attr;
  * the black-box post-mortem dump — its events carry the id, and its
    embedded telemetry-history window passes validate_dump's schema
    check (columns rectangular, timestamps aligned);
  * the Perfetto export — filtering by the id returns the wave's spans
    plus the black-box instants.

This is the cheapest end-to-end proof of causal correlation
(docs/metrics.md "History & correlation") — `make test` runs it before
the tier-1 suite.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import tempfile

TRACE_ID = "obs-smoke-trace"


def _fail(msg: str) -> int:
    print(f"obs-smoke: FAIL — {msg}", file=sys.stderr)
    return 1


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    dump_dir = tempfile.mkdtemp(prefix="kss-obs-smoke-")
    plan = {"seed": 7, "rules": [
        {"seam": "replay.decision_fetch", "nth": 2, "error": "runtime"},
    ]}
    plan_path = os.path.join(dump_dir, "plan.json")
    with open(plan_path, "w", encoding="utf-8") as fh:
        json.dump(plan, fh)
    # env BEFORE the simulator imports: faults arms KSS_TPU_FAULT_PLAN
    # at module load, and the toggles the assertions depend on must not
    # be overridden by an inherited KSS_TPU_HISTORY=0 / _BLACKBOX=0
    os.environ["KSS_TPU_FAULT_PLAN"] = "@" + plan_path
    os.environ["KSS_TPU_BLACKBOX_DIR"] = dump_dir
    os.environ["KSS_TPU_WAVE_MAX_RETRIES"] = "0"
    os.environ["KSS_TPU_BLACKBOX"] = "1"
    os.environ["KSS_TPU_HISTORY"] = "1"

    from kube_scheduler_simulator_tpu.cluster.store import ObjectStore
    from kube_scheduler_simulator_tpu.framework.engine import SchedulerEngine
    from kube_scheduler_simulator_tpu.models.workloads import (
        make_nodes, make_pods)
    from kube_scheduler_simulator_tpu.plugins.registry import PluginSetConfig
    from kube_scheduler_simulator_tpu.utils.blackbox import (
        FEEDER, validate_dump)
    from kube_scheduler_simulator_tpu.utils.faults import InjectedFault
    from kube_scheduler_simulator_tpu.utils.tracing import TRACER

    store = ObjectStore()
    for n in make_nodes(6, seed=1):
        store.create("nodes", n)
    for p in make_pods(24, seed=2):
        store.create("pods", p)
    engine = SchedulerEngine(
        store, plugin_config=PluginSetConfig(enabled=["NodeResourcesFit"]),
        chunk=8)
    FEEDER.sample()  # pre-wave ring row: the dump's window has a baseline
    surfaced = None
    try:
        with TRACER.trace_scope(TRACE_ID):
            engine.schedule_pending()
    except InjectedFault as e:
        surfaced = e
    finally:
        engine.close()
    if surfaced is None:
        return _fail("the armed fault never surfaced "
                     "(retry budget 0 should abort the wave)")

    # 1. spans: the wave's span tree carries the trace id as an attr
    traced_spans = [ev for ev in TRACER.events(limit=500)
                    if ev.get("trace_id") == TRACE_ID]
    if not traced_spans:
        return _fail("no tracer span carries the trace id "
                     f"{TRACE_ID!r} — trace_scope is not folding into "
                     "span attrs")

    # 2. the post-mortem dump: events stamped with the id + an embedded
    #    history window that validates (shape-checked by validate_dump)
    files = sorted(glob.glob(os.path.join(dump_dir, "blackbox-*.json")))
    if not files:
        return _fail(f"no dump landed in {dump_dir}")
    with open(files[-1], encoding="utf-8") as fh:
        doc = json.load(fh)
    try:
        res = validate_dump(doc, require_fault=True)
    except ValueError as e:
        return _fail(f"malformed dump {files[-1]}: {e}")
    traced_events = [ev for ev in doc["events"]
                     if ev.get("trace_id") == TRACE_ID]
    if not traced_events:
        return _fail("no black-box event in the dump carries the trace "
                     f"id {TRACE_ID!r}")
    hist = doc.get("history")
    if not isinstance(hist, dict) or not hist.get("index"):
        return _fail("the dump's embedded history window is missing or "
                     "empty — the feeder never populated the ring")

    # 3. Perfetto: filtering the export by the id returns the wave
    pf = TRACER.perfetto(trace_id=TRACE_ID)
    tevs = pf.get("traceEvents") or []
    pf_spans = [ev for ev in tevs if ev.get("ph") == "X"]
    pf_instants = [ev for ev in tevs if ev.get("ph") == "i"]
    if not pf_spans:
        return _fail("perfetto(trace_id=...) returned no spans for "
                     f"{TRACE_ID!r}")
    if not pf_instants:
        return _fail("perfetto(trace_id=...) returned no black-box "
                     f"instant events for {TRACE_ID!r}")

    print(json.dumps({
        "ok": True,
        "trace_id": TRACE_ID,
        "dump": files[-1],
        "reason": doc["reason"],
        "traced_spans": len(traced_spans),
        "traced_dump_events": len(traced_events),
        "history_rows": len(hist["index"]),
        "history_series": len(hist.get("series") or {}),
        "perfetto_spans": len(pf_spans),
        "perfetto_instants": len(pf_instants),
        "event_kinds": res["kinds"],
    }))
    print(f"obs-smoke: ok — trace {TRACE_ID!r} threads "
          f"{len(traced_spans)} spans, {len(traced_events)} dump events, "
          f"{len(pf_spans)}+{len(pf_instants)} perfetto events; history "
          f"window {len(hist['index'])} rows x "
          f"{len(hist.get('series') or {})} series")
    return 0


if __name__ == "__main__":
    sys.exit(main())
