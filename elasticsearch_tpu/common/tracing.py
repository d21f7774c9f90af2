"""End-to-end query tracing: trace/span propagation + a bounded store.

Reference: ES 8's APM tracing (``tracing.apm`` — every REST request gets
a ``trace.id`` that follows the task through the transport) and the
``X-Opaque-Id`` request header that is echoed back and stamped into slow
logs and task descriptions. Here:

- A ``trace.id``/``span.id`` pair is minted at the REST edge
  (``rest/api.py``) — or adopted from an incoming ``traceparent`` /
  ``x-trace-id`` header — and carried in a ``contextvars`` context so
  every layer on the request's call path (coordinator fan-out, shard
  search, slow log) sees it without plumbing arguments.
- Cross-node hops serialize the context into transport request payload
  headers (:func:`wire_headers`) and the receiving handler re-binds it
  (``span(..., headers=...)``) — coordinator → shard fan-out keeps one
  trace id cluster-wide.
- Completed spans land in a bounded in-memory :class:`TraceStore`
  (``GET /_trace/{trace_id}`` renders the span tree). The store is
  PROCESS-scoped like ``breakers.DEFAULT``: in-process multi-node test
  clusters share it, and each span records the ``node`` that emitted it,
  so propagation is still proven by the trace id crossing the wire (a
  data-node span only joins the trace if the RPC payload carried the
  context).

Every span has a twin on the profiler's clock: while a ``jax.profiler``
session is active (an operator's capture, the benchmark's ``--trace 1``)
a span also opens a ``jax.profiler.TraceAnnotation`` of the same name,
so it lands in the host plane of the same ``.xplane.pb`` as the device's
events, on one clock. A traced span's twin carries ``trace_id`` /
``span_id`` / ``parent`` (its attributes are in the store under that span
id; :data:`_LINK_KEYS` names the exception). Threads that serve no single
request (the micro-batcher's dispatchers) get the twin only, with their
scalar attributes: those spans carry the dispatch ``seq`` that the
requests' ``plane_dispatch`` spans point at. Every dispatcher twin, and
every twin of one request in :data:`CPU_SAMPLE` (chosen by its trace id,
so a request's twins carry it all or none), also carries its thread's CPU
clock (``time.thread_time_ns``, the clock of the task ledger's
checkpoints) as it opens, ``cpu0_us``, and as it closes, ``cpu1_us``:
absolute microseconds of that thread, so the CPU between any two edges of
one thread is a subtraction, and the rest of their wall time is waiting.
With no session no clock is read, nothing is formatted and nothing is
written. (That twin is why this module,
alone under ``common/``, imports ``jax``: the profiler's front end only,
no backend is initialised by it.)

Cost per span, with and without a session: TELEMETRY.md "Overhead
budget".
"""

from __future__ import annotations

import contextvars
import random
import threading
import time
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

__all__ = ["TraceStore", "DEFAULT_STORE", "span", "open_span", "handoff",
           "Phases", "current_trace_id", "current_span_id",
           "wire_headers", "new_trace_id", "set_opaque_id",
           "current_opaque_id"]

#: the innermost open traced span (a :class:`SpanHandle`) on this
#: context, or None
_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "es_trace_ctx", default=None)
#: the request's X-Opaque-Id (slow-log / task stamping), or None
_OPAQUE: contextvars.ContextVar = contextvars.ContextVar(
    "es_opaque_id", default=None)


#: ids come from a generator seeded from the OS once, not from
#: ``os.urandom``: that is a system call made with the interpreter lock
#: released, and on a node whose request threads queue for that lock a
#: span that gives it up pays a full wait for it (PERF.md §6 "PR 25")
_IDS = random.Random()


def new_trace_id() -> str:
    return "%032x" % _IDS.getrandbits(128)


def _new_span_id() -> str:
    return "%016x" % _IDS.getrandbits(64)


def current() -> Optional[Tuple[str, str]]:
    ctx = _CTX.get()
    return (ctx.trace_id, ctx.span_id) if ctx else None


def current_trace_id() -> Optional[str]:
    ctx = _CTX.get()
    return ctx.trace_id if ctx else None


def current_span_id() -> Optional[str]:
    ctx = _CTX.get()
    return ctx.span_id if ctx else None


def set_opaque_id(opaque: Optional[str]):
    return _OPAQUE.set(opaque)


def current_opaque_id() -> Optional[str]:
    return _OPAQUE.get()


def wire_headers() -> Optional[Dict[str, str]]:
    """The active context as transport request headers, or None when no
    trace is active (internal maintenance RPCs stay untraced)."""
    ctx = _CTX.get()
    if ctx is None:
        return None
    out = {"trace.id": ctx.trace_id, "parent.span.id": ctx.span_id}
    opaque = _OPAQUE.get()
    if opaque:
        out["x-opaque-id"] = opaque
    return out


def parse_incoming(headers: Optional[dict]) \
        -> Tuple[Optional[str], Optional[str]]:
    """(trace_id, parent_span_id) from HTTP/transport headers: our own
    wire form first, then W3C ``traceparent``
    (``00-<trace32>-<span16>-<flags>``), then a bare ``x-trace-id``."""
    if not headers:
        return None, None
    hmap = {str(k).lower(): v for k, v in headers.items()}
    tid = hmap.get("trace.id")
    if tid:
        return str(tid), hmap.get("parent.span.id")
    tp = hmap.get("traceparent")
    if tp:
        parts = str(tp).split("-")
        if len(parts) >= 3 and len(parts[1]) == 32:
            return parts[1], parts[2] if len(parts[2]) == 16 else None
    tid = hmap.get("x-trace-id")
    if tid:
        return str(tid), None
    return None, None


class TraceStore:
    """Bounded in-memory span store: trace_id → span list, FIFO-evicted
    past MAX_TRACES; spans past MAX_SPANS_PER_TRACE are counted, not
    kept (a scroll hammering one trace id must not grow memory)."""

    MAX_TRACES = 512
    MAX_SPANS_PER_TRACE = 512

    def __init__(self):
        self._lock = threading.Lock()
        from collections import OrderedDict
        self._traces: "OrderedDict[str, dict]" = OrderedDict()

    def record(self, span_doc: dict) -> None:
        tid = span_doc.get("trace_id")
        if not tid:
            return
        with self._lock:
            ent = self._traces.get(tid)
            if ent is None:
                ent = self._traces[tid] = {"spans": [], "dropped": 0}
                while len(self._traces) > self.MAX_TRACES:
                    self._traces.popitem(last=False)
            if len(ent["spans"]) >= self.MAX_SPANS_PER_TRACE:
                ent["dropped"] += 1
                return
            ent["spans"].append(span_doc)

    def get(self, trace_id: str) -> Optional[dict]:
        """{"trace_id", "spans" (flat, start-ordered), "tree" (nested by
        parent span id — orphans surface at the root)} or None."""
        with self._lock:
            ent = self._traces.get(trace_id)
            if ent is None:
                return None
            spans = [dict(s) for s in ent["spans"]]
            dropped = ent["dropped"]
        spans.sort(key=lambda s: s.get("start_ms", 0))
        # the tree gets its OWN node copies: attaching children to the
        # flat list's dicts would nest every subtree into its ancestors
        # there too (O(n²) serialization, double-counted children)
        nodes = {s["span_id"]: dict(s) for s in spans}
        roots: List[dict] = []
        for s in spans:
            n = nodes[s["span_id"]]
            parent = nodes.get(s.get("parent_span_id"))
            if parent is not None and parent is not n:
                parent.setdefault("children", []).append(n)
            else:
                roots.append(n)
        doc = {"trace_id": trace_id, "span_count": len(spans),
               "spans": spans, "tree": roots}
        if dropped:
            doc["dropped_spans"] = dropped
        return doc

    def recent(self, n: int = 50, min_ms: Optional[float] = None,
               tenant: Optional[str] = None) -> List[dict]:
        """The newest-first trace index: one row per retained trace with
        its request's action, start and duration (``GET /_trace`` — the
        listing that makes an evicted id's 404 explainable and lets
        ``trace_dump.py --last`` stop guessing). A request served over
        HTTP roots at ``http[in]``, which ends at the hand-off to the
        handler's thread: the row is the request, not that edge —
        ``root`` and ``tenant`` are those of the first span under the
        root that names an action (``rest[<action>]``; the root itself
        where none does), ``took_ms`` runs from the root's start to the
        last end under it. ``min_ms`` keeps only traces that took at
        least that long; ``tenant`` keeps only traces of that
        X-Opaque-Id — both filter BEFORE the ``n`` cap, so "the slowest
        tenant's last 50" works on a busy store."""
        n = int(n)
        if n <= 0:
            return []
        with self._lock:
            items = [(tid, list(ent["spans"]))
                     for tid, ent in self._traces.items()]
        out: List[dict] = []
        for tid, spans in reversed(items):
            row = {"trace_id": tid, "span_count": len(spans)}
            if spans:
                def start(s):
                    return s.get("start_ms", 0)
                kids: Dict[Optional[str], List[dict]] = {}
                for s in spans:
                    kids.setdefault(s.get("parent_span_id"), []).append(s)
                ids = {s.get("span_id") for s in spans}
                roots = [s for s in spans
                         if s.get("parent_span_id") not in ids]
                root = min(roots or spans, key=start)
                # the root's subtree (a scroll's later requests under
                # the same trace id are other roots)
                under, todo, seen = [], [root], set()
                while todo:
                    s = todo.pop()
                    if s.get("span_id") not in seen:
                        seen.add(s.get("span_id"))
                        under.append(s)
                        todo.extend(kids.get(s.get("span_id"), ()))
                end_ms = max(start(s) + s.get("took_ms", 0)
                             for s in under)
                request = min((s for s in under
                               if (s.get("attrs") or {}).get("action")),
                              key=start, default=root)
                start_ms = start(root)
                row.update(root=request.get("name"), start_ms=start_ms,
                           took_ms=round(end_ms - start_ms, 3))
                node = request.get("node") or root.get("node")
                if node:
                    row["node"] = node
                row_tenant = (request.get("attrs") or {}).get("tenant")
                if row_tenant:
                    row["tenant"] = row_tenant
            if min_ms is not None and \
                    float(row.get("took_ms") or 0.0) < float(min_ms):
                continue
            if tenant is not None and row.get("tenant") != tenant:
                continue
            out.append(row)
            if len(out) >= n:
                break
        return out

    def stats_doc(self) -> dict:
        with self._lock:
            return {"traces": len(self._traces),
                    "spans": sum(len(e["spans"])
                                 for e in self._traces.values())}


#: PROCESS-scoped store (documented singleton, like breakers.DEFAULT);
#: spans carry their emitting node's id
DEFAULT_STORE = TraceStore()


_SCALARS = (str, int, float, bool)
#: one request in CPU_SAMPLE has twins that carry the thread's CPU clock.
#: A read is a system call made with the interpreter lock held: 5.9 µs on
#: the benchmark's TPU host, where a clock on every twin of every request
#: (~28 reads a request) cut a traced window's throughput by a fifth
#: (TELEMETRY.md "Overhead budget")
CPU_SAMPLE = 8
#: the attributes a traced span's twin carries besides its ids and, where
#: sampled, its thread's CPU clock (``cpu0_us`` / ``cpu1_us``): what
#: links it to a span of another thread (a request's ``plane_dispatch``
#: to the dispatcher's ``batch[...]`` spans of that ``seq``)
_LINK_KEYS = ("dispatch_seq",)
#: the profiler encodes an annotation as ``name#k=v,k=v#``
_STAT_UNSAFE = str.maketrans("#,", "__")


def _stats(attrs: dict) -> dict:
    """The scalar attributes, as annotation stats."""
    return {k: v.translate(_STAT_UNSAFE) if isinstance(v, str) else v
            for k, v in attrs.items() if isinstance(v, _SCALARS)}


class SpanHandle:
    """One open span: yielded by :func:`span` / returned by
    :func:`open_span` so the body can attach attributes and read the
    ids. ``trace_id`` is None for a span outside any trace, which has
    only its profiler twin. A handle is its span's own state: the thread
    that runs the span opens and closes it."""

    __slots__ = ("name", "trace_id", "span_id", "parent_span_id", "attrs",
                 "node", "store", "manual", "_token", "_t0", "_start_ms",
                 "_ann", "_ann_keys", "_clocked")

    def __init__(self, name: str, trace_id: Optional[str],
                 parent_span_id: Optional[str], attrs: Optional[dict],
                 node: Optional[str], store: Optional[TraceStore],
                 manual: bool, profiling: bool):
        self.name = name
        self.trace_id = trace_id
        self.span_id = _new_span_id()
        self.parent_span_id = parent_span_id
        self.attrs = dict(attrs) if attrs else {}
        self.node = node
        self.store = store
        self.manual = manual
        self._ann = self._ann_keys = None
        if profiling:
            self._clocked = trace_id is None or \
                hash(trace_id) % CPU_SAMPLE == 0
            cpu = {"cpu0_us": time.thread_time_ns() // 1000} \
                if self._clocked else {}
            if trace_id is None:
                self._ann_keys = tuple(self.attrs)
                self._ann = TraceAnnotation(name, **cpu,
                                            **_stats(self.attrs))
            elif parent_span_id:
                # ids get a letter in front: a stat that looks like a
                # number is read back as one, and one hex id in a few
                # thousand does
                self._ann = TraceAnnotation(
                    name, trace_id="t" + trace_id,
                    span_id="s" + self.span_id,
                    parent="s" + str(parent_span_id), **cpu)
            else:
                self._ann = TraceAnnotation(
                    name, trace_id="t" + trace_id,
                    span_id="s" + self.span_id, **cpu)
            self._ann.__enter__()
        self._token = _CTX.set(self) if trace_id is not None else None
        self._start_ms = time.time() * 1e3
        self._t0 = time.perf_counter()

    def close(self) -> None:
        """End the span: idempotent (an edge span ends at the hand-off
        or when its opener is done, whichever comes first)."""
        if self._t0 is None:
            return
        took_ms = (time.perf_counter() - self._t0) * 1e3
        self._t0 = None
        ann = self._ann
        if ann is not None:
            self._ann = None
            if self.trace_id is None:
                late = {k: v for k, v in self.attrs.items()
                        if k not in self._ann_keys}
            else:
                late = {k: self.attrs[k] for k in _LINK_KEYS
                        if k in self.attrs}
            if self._clocked:
                late["cpu1_us"] = time.thread_time_ns() // 1000
            if late:
                ann.set_metadata(**_stats(late))
            ann.__exit__(None, None, None)
        if self.trace_id is None:
            return
        _CTX.reset(self._token)
        doc = {"trace_id": self.trace_id, "span_id": self.span_id,
               "parent_span_id": self.parent_span_id, "name": self.name,
               "start_ms": round(self._start_ms, 3),
               "took_ms": round(took_ms, 3)}
        if self.node:
            doc["node"] = self.node
        if self.attrs:
            doc["attrs"] = self.attrs
        (self.store or DEFAULT_STORE).record(doc)


def open_span(name: str, *, node: Optional[str] = None,
              attrs: Optional[dict] = None,
              headers: Optional[dict] = None,
              trace_id: Optional[str] = None,
              parent_span_id: Optional[str] = None,
              root: bool = False,
              store: Optional[TraceStore] = None,
              manual: bool = True) -> Optional[SpanHandle]:
    """Open a span and return its handle; the caller ends it with
    :meth:`SpanHandle.close` (:func:`span` is the ``with`` form).

    Parent resolution order: explicit ``trace_id`` (+ ``parent_span_id``),
    wire ``headers`` (cross-node hop; the ambient span stays the parent
    when it is already part of that trace), then the ambient context.
    ``root=True`` mints a fresh trace when none of those yield one (the
    HTTP/REST edge). Without a trace the span has only its profiler
    twin, and with no profiler session either, None is returned: a body
    running outside any trace records nothing (maintenance paths stay
    free)."""
    parent_span = parent_span_id
    tid = trace_id
    ctx = _CTX.get()
    if tid is None and headers is not None:
        tid, parent_span = parse_incoming(headers)
        if tid is not None and ctx is not None and ctx.trace_id == tid:
            parent_span = ctx.span_id
    if tid is None:
        if ctx is not None:
            tid, parent_span = ctx.trace_id, ctx.span_id
        elif root:
            tid = new_trace_id()
    profiling = TraceAnnotation.is_enabled()
    if tid is None and not profiling:
        return None
    return SpanHandle(name, tid, parent_span, attrs, node, store, manual,
                      profiling)


class span:
    """One traced span around the body: ``with span(name, ...) as sp``
    (:func:`open_span`'s arguments). ``sp`` is the :class:`SpanHandle`,
    or None where nothing is recorded."""

    __slots__ = ("_name", "_kw", "_handle")

    def __init__(self, name: str, **kw):
        self._name = name
        self._kw = kw

    def __enter__(self) -> Optional[SpanHandle]:
        self._handle = open_span(self._name, manual=False, **self._kw)
        return self._handle

    def __exit__(self, *exc) -> None:
        if self._handle is not None:
            self._handle.close()


def handoff() -> None:
    """The request leaves this thread (event loop -> handler pool): an
    ambient span its opener left open for this (:func:`open_span`) ends
    here. Spans made under a copy of the context taken before the call
    keep it as their parent."""
    h = _CTX.get()
    if h is not None and h.manual:
        h.close()


class Phases:
    """Consecutive sibling spans under the ambient span, for a pipeline
    written as one long function: entering a phase ends the one before,
    leaving the ``with`` block (:meth:`close`) ends the last, also where
    the pipeline raises: an open phase left behind would stay the
    ambient span, and every later annotation of the thread would nest
    under its twin."""

    __slots__ = ("_open",)

    def __init__(self):
        self._open: Optional[SpanHandle] = None

    def __enter__(self) -> "Phases":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def enter(self, name: str, **attrs) -> Optional[SpanHandle]:
        self.close()
        self._open = open_span(name, attrs=attrs, manual=False)
        return self._open

    def close(self) -> None:
        if self._open is not None:
            self._open.close()
            self._open = None
