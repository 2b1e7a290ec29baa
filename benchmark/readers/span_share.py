"""Seconds inside one or more of the program's tracer spans, as a share
(%) of the window.  parameters: {"spans": ["compile_workload", ...]}.
The spans come from GET /api/v1/metrics as deltas over the window.  A
span the program never opened in the window reads 0; a span name the
program does not know at all returns nothing."""


def read(ctx: dict, params: dict):
    c = ctx["counters"]
    keys = [f"span:{name}" for name in params["spans"]]
    if not any(k in c for k in keys):
        return None
    return 100.0 * sum(c.get(k, 0.0) for k in keys) / ctx["counter_window_s"]
