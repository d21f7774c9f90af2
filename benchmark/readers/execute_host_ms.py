"""Reader ``execute_host_ms``: per dispatch, the dispatcher's
``batch[execute]`` span less the time inside it in which any ``jit_*``
program ran on the device (the device plane of the same trace), the
dispatch's own step or the other in-flight dispatch's: launch, transfers
and result decode that no step hides, and the waits with the device
idle."""

from harness import xplane_spans


def read(ctx: dict, params: dict):
    summary = xplane_spans.load(ctx)
    if summary is None:
        return None
    ms, _n = xplane_spans.execute_host_ms(summary)
    return ms
