"""Reader ``idle_share``: 1 - the union of the device's busy intervals over
the traced window, from the profiler trace, averaged over the chips."""


def read(ctx: dict, params: dict):
    dev = ctx["device"]
    if not dev.get("busy_s") or not dev.get("window_s"):
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
