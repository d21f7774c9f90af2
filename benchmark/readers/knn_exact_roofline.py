"""Reader ``knn_exact_roofline``: the least time a chip needs for the exact
scan's work over the device time of the step program, per dispatch. The
work is ``harness/work.knn_exact`` at the corpus's own row count and stored
width and each traced dispatch's request count."""

from harness import work
from harness.xplane import step_ms


def read(ctx: dict, params: dict):
    ms = step_ms(ctx.get("trace"))
    groups = ctx["dispatch_groups"]
    if ms is None or not groups:
        return None
    peaks = work.peaks_for(ctx["device"]["kind"])
    d = ctx["config"]["data"]["params"]
    works = [work.knn_exact(int(d["docs"]), int(d["dims"]),
                            int(params["stored_bytes"]), len(g),
                            int(params["k"])) for g in groups]
    return work.roofline_share(works, ms, peaks, ctx["say"],
                               "knn_exact_roofline")
