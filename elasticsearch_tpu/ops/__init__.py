"""Device kernels. Every public one opens a ``jax.named_scope`` of its own
name, so the ops it stages carry that name in their HLO metadata
(``op_name``) and a profiler trace can be reduced by kernel, whatever the
compiler calls the fused ops this week."""

from __future__ import annotations

import functools

import jax


def in_named_scope(name: str):
    """Decorator: trace the function's body under ``jax.named_scope(name)``.
    (``jax.named_scope`` itself also decorates, but one scope object is then
    shared by every thread that traces the function, and two dispatchers
    do compile at once.)"""
    def deco(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return deco
