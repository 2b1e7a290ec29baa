"""Numbers from the program's own spans as they appear IN the profiler's
trace (kss:<name> TraceMe events on the host plane of the same
.xplane.pb as the device ops), reduced by lib/xplane_spans.py in a child
(the benchmark's parent never imports JAX).
parameters: {"field": "idle_in_spans_share"}
Returns nothing without a trace (a --trace 0 run) and where the trace
holds no kss: event (a program from before the spans were annotated).
Two files are left in the run's work directory for PERF.md: the child's
kss_idle_by_span.json (the device-idle seconds by deepest span), and
window_counters.json: every counter and span delta of the UN-profiled
window the metrics were computed from (which functions compiled, each
child of compile_workload), which the result line has no room for."""

import json

from pathlib import Path

from lib import xplane_spans


def read(ctx: dict, params: dict):
    t = ctx.get("trace")
    if not t or not t.get("file"):
        return None
    work = xplane_spans.work_dir_of(Path(t["file"]))
    (work / "window_counters.json").write_text(json.dumps(
        {"window_s": ctx["counter_window_s"], "cycles": len(ctx["cycles"]),
         "pods": sum(r["pods"] for r in ctx["cycles"]),
         "counters": dict(sorted(ctx["counters"].items()))}, indent=1))
    red = xplane_spans.reduce_in_child(Path(t["file"]))
    if not red or not red.get("kss_events"):
        return None
    if params["field"] == "idle_in_spans_share":
        return (100.0 * red["idle_in_spans_s"] / red["idle_s"]
                if red["idle_s"] else None)
    raise ValueError(f"unknown field {params['field']!r}")
