"""A percentile of something the load generator timed on its own clock,
over the cycles completed in the window.
parameters: {"series": "read_s" | "result_latency_s" | "decided_s", "q": 50}"""

from lib.stats import percentile


def read(ctx: dict, params: dict):
    rows = ctx["cycles"]
    if not rows:
        return None
    series = {"read_s": [r["read_s"] for r in rows],
              "result_latency_s": [r["t1"] - r["t0"] for r in rows],
              "decided_s": [r["t_decided"] - r["t0"] for r in rows]}
    return percentile(series[params["series"]], params["q"])
