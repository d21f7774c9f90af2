"""Reader ``counter_ratio``: one counter's rise over the window divided by
another's (``params``: ``numerator``, ``denominator``, names of
``harness/counters.snapshot`` keys). Nothing to read when the denominator
did not rise."""


def read(ctx: dict, params: dict):
    d = ctx["counters"]
    den = d[params["denominator"]]
    return d[params["numerator"]] / den if den > 0 else None
