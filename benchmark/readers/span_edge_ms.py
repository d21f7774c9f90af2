"""Reader ``span_edge_ms``: the mean, over the traced window's requests,
of the time from one edge of one of the program's spans to another
(``params``: ``from`` and ``to``, each ``{"span": name, "edge":
"start"|"end"}``, with ``"of": "dispatch"`` to take the span from the
dispatch that carried the request, joined by its ``seq``). The spans are
the node's ``jax.profiler.TraceAnnotation``s in the trace's host plane,
joined by trace id across threads (``harness/xplane_spans.py``). A request
that lacks either edge is left out."""

from harness import xplane_spans


def read(ctx: dict, params: dict):
    summary = xplane_spans.load(ctx)
    if summary is None:
        return None
    ms, _n = xplane_spans.edge_ms(summary, params["from"], params["to"])
    return ms
