"""A ratio (%) of two of the load generator's own counts over the window.
parameters: {"num": "shed_429s", "den": "requests_sent"}"""


def read(ctx: dict, params: dict):
    den = ctx["client"].get(params["den"])
    if not den:
        return None
    return 100.0 * ctx["client"][params["num"]] / den
