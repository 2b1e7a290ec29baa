"""Numbers from the profiler's trace of the server process (the only
source of device time), reduced by lib/xplane.py.
parameters: {"field": "idle_share" | "busy_us_per_pod" |
                      "xla_compile_share" | "xla_compiles_per_cycle"}
Returns nothing where there is no trace to read (a --trace 0 run).  The
compile metrics count the host's `backend_compile_and_load` events: real
XLA compiles, which the program makes in every pass today (PERF.md)."""


def read(ctx: dict, params: dict):
    t = ctx.get("trace")
    if not t or not t.get("window_s"):
        return None
    field = params["field"]
    if field == "idle_share":
        return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
    if field == "busy_us_per_pod":
        return 1e6 * t["busy_s"] / t["pods"] if t.get("pods") else None
    if field == "xla_compile_share":  # no compile event in the trace: 0 %
        return 100.0 * t["xla_compile_s"] / t["window_s"]
    if field == "xla_compiles_per_cycle":
        return t["xla_compile_events"] / t["cycles"] if t.get("cycles") else None
    raise ValueError(f"unknown field {field!r}")
