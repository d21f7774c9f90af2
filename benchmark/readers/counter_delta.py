"""Reader ``counter_delta``: one counter's rise over the window
(``params``: ``counter``, a ``harness/counters.snapshot`` key)."""


def read(ctx: dict, params: dict):
    return float(ctx["counters"][params["counter"]])
