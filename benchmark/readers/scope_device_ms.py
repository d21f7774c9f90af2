"""Reader ``scope_device_ms``: device own time of the ops under a list of
``jax.named_scope`` paths, per execution of one jitted module
(``params``: ``module``, ``scopes``; a scope that ends in ``$`` names the
ops whose path ends there, ``xplane_spans.scope_of``). The path is the HLO
``op_name`` that the device's op events carry as a stat; a trace whose op
events carry none reads nothing."""

from harness import xplane_spans


def read(ctx: dict, params: dict):
    summary = xplane_spans.load(ctx)
    if summary is None:
        return None
    return xplane_spans.scope_ms(summary, params["module"],
                                 list(params["scopes"]))
