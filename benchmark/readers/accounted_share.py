"""How much of the window's cycles the program's own clocks account
for: seconds inside the named spans plus the named counters of seconds,
as a share (%) of the cycles' summed length on the client's clock.
parameters: {"spans": ["http_pod_create", "wave", "http_pod_read"],
             "counters": ["queue_wait_oldest_seconds_total"]}
The parts are chosen to follow one another in a cycle (submit answered,
the pod queued, the pass that took it, the read), so the residue is what
no span or counter sees: the network, the client, the watch delivery.  A
program that has none of the spans returns nothing."""


def read(ctx: dict, params: dict):
    c = ctx["counters"]
    span_keys = [f"span:{name}" for name in params["spans"]]
    if not any(k in c for k in span_keys):
        return None
    cycles_s = sum(r["t1"] - r["t0"] for r in ctx["cycles"])
    if not cycles_s:
        return None
    inside = sum(c.get(k, 0.0) for k in span_keys) \
        + sum(c.get(name, 0.0) for name in params.get("counters", ()))
    return 100.0 * inside / cycles_s
