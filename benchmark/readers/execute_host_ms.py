"""Reader ``execute_host_ms``: per dispatch, the dispatcher's
``batch[execute]`` span less the device time of the step executions inside
it (``jit_<kernel>`` modules on the device plane of the same trace):
launch, transfers, result decode, and the step's wait for the device."""

from harness import xplane_spans


def read(ctx: dict, params: dict):
    summary = xplane_spans.load(ctx)
    if summary is None:
        return None
    ms, _n = xplane_spans.execute_host_ms(summary)
    return ms
