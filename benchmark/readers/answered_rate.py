"""Reader ``answered_rate``: the window's answered requests over the time
from its first send to its last answer, in requests a second."""


def read(ctx: dict, params: dict):
    return len(ctx["requests"]) / ctx["span_s"] if ctx["span_s"] > 0 else None
