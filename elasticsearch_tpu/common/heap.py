"""The resident heap and the cyclic collector: the one module that speaks
to ``gc``.

A node builds most of its heap once and serves from it for as long as it
lives: the recovered segments and their version map, the packed serving
planes, the loaded programs. None of it dies, but CPython's
generation-2 collection walks all of it every time it runs, with every
thread stopped: at GloVe's scale 2.47 M tracked objects (two a document,
the engine's version map), 0.62-0.72 s a pass, four passes in a 51 s
window under 64 clients (PERF.md, PR 28 and PR 33).
:func:`settle` moves that heap out of the collector's generations at the
moment it is installed, so that a pass in a serving window walks what
requests allocated.

**Who arms it.** ``IndicesService`` and ``ServingPlaneCache`` also run
inside other people's processes (a test suite, an embedding program), and
a library must not freeze its host's heap or run a full pass of it per
index: :func:`settle` does nothing until the process's owner calls
:func:`arm`, and the only owner that does is ``cli.node.main``. That this
process is a node is a fact about the process, stated once at its start;
it is not a setting.

**Why it is exact.** ``gc.freeze()`` alone would leak: a frozen object in
a dead cycle is never freed. So every :func:`settle` first brings the
frozen heap back into reach (``gc.unfreeze()``), runs the full pass that
frees whatever an install retired (the generation a repack replaced),
and only then freezes what is left. No collection is skipped that could
free anything; no threshold is touched. The full pass is paid once per
install on the installing thread (for a repack, the background repack
thread); it stops the world like any other and is counted like any other.

**The counter.** One ``gc.callbacks`` hook, installed with the arming,
adds up passes and pause time by generation: integer adds under the
interpreter lock, no lock of its own, nothing that lets the interpreter
lock go (PR 25). ``GET /_nodes/stats`` reads it as ``jvm.gc.collectors``
(``young``: generations 0 and 1, ``old``: generation 2),
``/_prometheus/metrics`` as the four ``es_gc_*`` families. After a
freeze, generation-2 passes come *more* often (the collector's
long-lived total is small again) while each costs tens of milliseconds:
the time is the figure, not the count.

**The twin.** While a ``jax.profiler`` session is active the same hook
opens a ``host[gc]`` annotation at a pass's start (stat ``generation``)
and closes it at its stop (stat ``collected``), so each pass lies in the
host plane of the trace beside the device's ops and the request spans
(``common/tracing.py``): a device idle gap or a request span that
overlaps one was stopped by the collector. Without a session the hook
adds one ``is_enabled()`` check a pass.
"""

from __future__ import annotations

import gc
import time
from typing import Dict

from . import telemetry
from .tracing import TraceAnnotation

_armed = False
# by generation; written only by _on_collection, which the collector never
# runs twice at once (one collection at a time, the process over)
_passes = [0, 0, 0]
_pause_ns = [0, 0, 0]
_started_ns = 0
_twin = None                        # the open pass's host[gc] annotation
_settles = telemetry.Counter()      # settle() runs on any installing thread


def _on_collection(phase: str, info: dict) -> None:
    global _started_ns, _twin
    if phase == "start":
        if TraceAnnotation.is_enabled():
            _twin = TraceAnnotation("host[gc]",
                                    generation=info["generation"])
            _twin.__enter__()
        _started_ns = time.perf_counter_ns()
    else:
        g = info["generation"]
        _passes[g] += 1
        _pause_ns[g] += time.perf_counter_ns() - _started_ns
        if _twin is not None:
            _twin.set_metadata(collected=info["collected"])
            _twin.__exit__(None, None, None)
            _twin = None


def arm() -> None:
    """This process is a node: from now on :func:`settle` acts, and the
    collector's passes are counted. Idempotent."""
    global _armed
    if _armed:
        return
    _armed = True
    gc.callbacks.append(_on_collection)
    telemetry.DEFAULT.register_collector("gc", _metrics_doc)


def disarm() -> None:
    """Undo :func:`arm` (tests): the frozen heap comes back into reach,
    the hook and the ``es_gc_*`` families go; the counts stay."""
    global _armed
    if not _armed:
        return
    _armed = False
    gc.callbacks.remove(_on_collection)
    gc.unfreeze()
    telemetry.DEFAULT.unregister_collector("gc")


def settle() -> None:
    """Long-lived state has just been installed (a shard recovered, a
    serving generation swapped in, a warm-up's programs loaded): take
    everything that is alive now out of the collector's reach, after one
    full pass over all of it, the earlier frozen heap included, has freed
    what the install retired. Call it where such state is installed and
    nowhere else: never per request, never on a timer. Inert unless
    :func:`arm` was called."""
    if not _armed:
        return
    gc.unfreeze()
    gc.collect()
    gc.freeze()
    # a pass over the generations the freeze just emptied costs nothing and
    # sets the collector's long-lived total to what is in its reach: none.
    # Left at the frozen heap's size, the first generation-2 pass after an
    # install waits for a quarter of that many promotions and then walks
    # all the garbage requests made meanwhile at once (335 ms, 300 k
    # objects: PERF.md, PR 33)
    gc.collect()
    _settles.inc()


def collectors_doc() -> Dict[str, dict]:
    """``jvm.gc.collectors`` of ``GET /_nodes/stats``, as Elasticsearch
    shapes it; empty where the process is not a node (no hook counts)."""
    if not _armed:
        return {}

    def one(gens) -> dict:
        return {"collection_count": sum(_passes[g] for g in gens),
                "collection_time_in_millis":
                    sum(_pause_ns[g] for g in gens) // 1_000_000}
    return {"young": one((0, 1)), "old": one((2,))}


def _metrics_doc() -> dict:
    gens = [({"generation": str(g)}, g) for g in (0, 1, 2)]
    return {
        "es_gc_collections_total": {
            "type": "counter",
            "help": "cyclic collector passes by generation",
            "samples": [(lbl, _passes[g]) for lbl, g in gens]},
        "es_gc_pause_millis_total": {
            "type": "counter",
            "help": "time every thread was stopped by a collector pass, "
                    "by generation",
            "samples": [(lbl, _pause_ns[g] / 1e6) for lbl, g in gens]},
        "es_gc_settles_total": {
            "type": "counter",
            "help": "installs of long-lived state that re-froze the "
                    "resident heap (one full pass each)",
            "samples": [({}, int(_settles.value))]},
        "es_gc_frozen_objects": {
            "type": "gauge",
            "help": "objects out of the collector's reach "
                    "(gc.get_freeze_count)",
            "samples": [({}, gc.get_freeze_count())]},
    }
