"""Reader ``hbm_peak_gb``: the peak device memory in use on the fullest
chip (``_nodes/stats/device``, JAX's ``memory_stats()``) after the
window."""


def read(ctx: dict, params: dict):
    peak = ctx["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
