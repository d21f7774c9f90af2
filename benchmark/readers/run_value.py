"""Reader ``run_value``: a number the harness took itself and put into the
readers' context under ``params``' ``key`` (``setup_s``: process start to
the end of the warm-up)."""


def read(ctx: dict, params: dict):
    return ctx.get(params["key"])
