"""The growth of a program counter over the window, per unit of work.
parameters: {"counter": "wave_d2h_bytes_total",
             "per": "pod" | "cycle" | "window"}
Counters come from GET /api/v1/metrics (labeled ones as
name{label=value}).  A counter the program never touched reads 0 where
`"absent_is_zero": true` says that is what absence means."""


def read(ctx: dict, params: dict):
    c = ctx["counters"]
    name = params["counter"]
    if name not in c and not params.get("absent_is_zero"):
        return None
    den = {"pod": sum(r["pods"] for r in ctx["cycles"]),
           "cycle": len(ctx["cycles"]), "window": 1}[params["per"]]
    if not den:
        return None
    return c.get(name, 0) / den
