"""Reader ``step_device_ms``: device time of the cell's step program per
execution, from the profiler trace's XLA-module events. The program has no
``named_scope`` yet, so the unit is the jitted program: the module that
took most device time in the traced window is the step, and the harness
names it on an earlier output line."""

from harness.xplane import step_ms


def read(ctx: dict, params: dict):
    return step_ms(ctx.get("trace"))
