"""Reader ``latency_percentile``: one percentile (``params``: ``q``, 0..1)
of the window's client-side latencies, send to last byte, in ms. Of the
traced window, as every per-layer metric."""

from harness import loadgen


def read(ctx: dict, params: dict):
    lat = ctx["latencies_ms"]          # ascending
    return loadgen.percentile(lat, float(params["q"])) if lat else None
