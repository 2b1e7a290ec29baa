"""Seconds inside some of the program's tracer spans as a share (%) of
the seconds inside others: how far a span's children cover it.
parameters: {"num": ["wave_setup", "compile_workload", ...],
             "den": ["wave"]}
Deltas over the window, from GET /api/v1/metrics.  Returns nothing where
the program does not know the denominator's spans, or none ran."""


def read(ctx: dict, params: dict):
    c = ctx["counters"]
    den = sum(c.get(f"span:{name}", 0.0) for name in params["den"])
    if not den:
        return None
    return 100.0 * sum(c.get(f"span:{name}", 0.0)
                       for name in params["num"]) / den
