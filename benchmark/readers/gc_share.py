"""Reader ``gc_share``: per cent of the traced window in which a pass of
the node's cyclic collector ran, every Python thread stopped: the union
of the ``host[gc]`` twins (``harness/host_spans.py``) over the window the
harness traced (trace start to stop, its own clock). A program that
leaves no ``host[...]`` twin reads nothing."""

from harness import host_spans


def read(ctx: dict, params: dict):
    summary = host_spans.load(ctx)
    window_s = ctx["device"].get("window_s")
    if summary is None or summary["gc"] is None or not window_s:
        return None
    return 100.0 * summary["gc"]["union_s"] / window_s
