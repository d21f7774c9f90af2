"""Process start-up for the launchers (``cli/node.py``, ``bench.py``,
``chip_smoke.py``): where compiled programs are cached, and the rule that
nothing serves from the CPU by accident.

One process holds a chip, so these run in the process that will serve:
call them before the node stack touches JAX. Nothing else in the tree
sets a compilation cache directory.
"""

from __future__ import annotations

import os

#: the checkout that holds this package (``<checkout>/elasticsearch_tpu/
#: common/runtime.py``): the cache path is part of a cached program's
#: key, so it derives from a fixed location, never a temporary name
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives for this process:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    this sets nothing."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def cpu_requested() -> bool:
    """True when the environment names the CPU platform outright
    (``JAX_PLATFORMS=cpu``): the only way a launcher serves from it."""
    return "cpu" in os.environ.get("JAX_PLATFORMS", "").lower().split(",")


def require_accelerator(allow_cpu: bool = False):
    """``jax.devices()``, or :class:`SystemExit` when the backend that
    came up is the CPU and nobody asked for it — a node that lost its
    chip must fail at start-up, not serve from the host."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu" and not (allow_cpu or cpu_requested()):
        raise SystemExit(
            "no accelerator: jax came up on the cpu platform and "
            "JAX_PLATFORMS=cpu was not given")
    return devices
