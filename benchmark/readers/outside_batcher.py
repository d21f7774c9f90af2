"""Reader ``outside_batcher``: the time a request spends outside the
micro-batcher (HTTP edge, REST parse, planner, fetch and the response
body): the median client latency of the window less the per-request mean
of the batcher's four stages over the same window."""

import statistics


def read(ctx: dict, params: dict):
    d = ctx["counters"]
    lat = ctx["latencies_ms"]
    if not lat or d["queries"] <= 0:
        return None
    inside = sum(d[f"{s}_ms"] for s in ("queue", "prep", "dispatch",
                                        "fetch")) / d["queries"]
    return statistics.median(lat) - inside
