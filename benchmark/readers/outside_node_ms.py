"""Reader ``outside_node_ms``: the traced window's mean client latency
(send to last byte, the client's clock) less the mean of
``http[in]``.start to ``http[out]``.end over the requests the trace
holds (``harness/xplane_spans.py``): what a request spends outside the
node's spans, in the kernel's sockets, the listen queue, the event loop
before it reads the request and the load generator's own threads. A
difference of two means over the same window, not a per-request
difference: the two clocks are not joined request by request."""

import statistics

from harness import xplane_spans


def read(ctx: dict, params: dict):
    summary = xplane_spans.load(ctx)
    if summary is None or not ctx["latencies_ms"]:
        return None
    server, _n = xplane_spans.edge_ms(
        summary, {"span": "http[in]", "edge": "start"},
        {"span": "http[out]", "edge": "end"})
    if server is None:
        return None
    return statistics.fmean(ctx["latencies_ms"]) - server
