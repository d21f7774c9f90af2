"""Reader ``host_span_value``: one number of the traced window's host
summary (``harness/host_spans.py``; ``params``: ``key``, one of the
summary's ``[mean, count]`` pairs: ``post_batcher_cpu_ms``,
``request_cpu_ms``, ``execute_cpu_ms``, ``loop_lag_ms``; or
``python_cpu_pct``, the node's Python threads' CPU over the wall of the
event loop's ticks). A program whose twins carry no CPU clock and that
leaves no ``host[...]`` twin reads nothing."""

from harness import host_spans


def read(ctx: dict, params: dict):
    summary = host_spans.load(ctx)
    if summary is None:
        return None
    key = params["key"]
    if key == "python_cpu_pct":
        return summary["python_cpu"]["pct"] if summary["python_cpu"] \
            else None
    return summary[key][0]
