"""The host's CPU and its waiting, read from the profiler trace.

While a ``jax.profiler`` session is active, the node's span twins carry
their thread's CPU clock as they open and as they close, ``cpu0_us`` and
``cpu1_us``: absolute microseconds of that thread (``common/tracing.py``:
every dispatcher twin, and every twin of a sample of the requests, all of
a request's or none, so a mean over the requests that carry them is a
mean over the window's).
The CPU a thread spent between two edges is a subtraction when both lie on
that thread's line, and the rest of the wall between them is waiting: for
the interpreter lock, for the event loop, for another thread. Two threads'
clocks never subtract: :func:`cpu_between` refuses such a pair. Beside the
spans the node leaves two twins of its own, named ``host[...]`` so that
``xplane_spans.SPAN_NAME`` matches neither:

- ``host[gc]`` around each pass of the cyclic collector, stats
  ``generation`` and ``collected`` (``common/heap.py``); every Python
  thread is stopped while one is open;
- ``host[loop]``, the event loop's tick every 10 ms, stat ``lag_us`` (how
  late the loop woke it); every tenth also the CPU clocks of the node's
  Python threads summed by role, ``cpu_loop_us``, ``cpu_pool_us``,
  ``cpu_dispatch_us``, ``cpu_other_us``, and ``threads``
  (``rest/http_server.py``).

:func:`load` is what the readers call: one helper process a run
(``python benchmark/harness/host_spans.py <trace.xplane.pb> <out.json>``
with ``JAX_PLATFORMS=cpu``, once the node is gone) reads the host plane
with :func:`read_planes`, which keeps what ``xplane_spans.read_planes``
filters out; :func:`reduce` is plain Python, checked on events written by
hand. A program whose twins carry no clocks and that leaves no ``host[...]``
twin gives nothing to read, and the readers then report nothing.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import time

ROLES = ("cpu_loop_us", "cpu_pool_us", "cpu_dispatch_us", "cpu_other_us")


def read_planes(path: str) -> dict:
    """{"host": [line...]}: each host line as [[name, start_ns, dur_ns,
    stats]] of its ``host[...]`` twins and of the spans whose twins carry
    a CPU clock."""
    from jax.profiler import ProfileData
    from xplane_spans import SPAN_NAME   # beside this file as a script
    host = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = []
            for ev in line.events:
                mine = ev.name.startswith("host[")
                if mine or SPAN_NAME.match(ev.name):
                    st = dict(ev.stats)
                    if mine or "cpu0_us" in st:
                        evs.append([ev.name, float(ev.start_ns),
                                    float(ev.duration_ns), st])
            if evs:
                host.append(evs)
    return {"host": host}


# ---------------------------------------------------------------------------
# plain Python from here on
# ---------------------------------------------------------------------------


def cpu_between(frm, to):
    """CPU microseconds of one thread from edge ``frm`` to edge ``to``, each
    ``(line, clock µs)``; None where either is missing or the two lie on two
    threads' lines."""
    if frm is None or to is None or frm[0] != to[0]:
        return None
    return to[1] - frm[1]


def _edge(span, edge: str):
    """``(line, clock µs)`` of one edge of a span record, or None."""
    if span is None:
        return None
    return (span["line"], span["cpu0"] if edge == "start" else span["cpu1"])


def _mean(vals: list):
    return [statistics.fmean(vals), len(vals)] if vals else [None, 0]


def reduce(raw: dict, search_spans, busy: list | None = None) -> dict:
    """The summary the readers use (times in ms unless named otherwise).
    A request is a trace that holds one of ``search_spans``
    (``xplane_spans.SEARCH_SPANS``: the harness's own polls of the node
    are traced too).

    - ``post_batcher_cpu_ms``: [mean, requests]: a request's CPU after its
      dispatch, ``plane_dispatch``.cpu1 to ``rest[render]``.cpu1 on the
      handler's thread plus ``http[out]``'s own;
    - ``request_cpu_ms``: [mean, requests]: ``http[in]``'s own CPU +
      ``rest[parse]``.cpu0 to ``rest[render]``.cpu1 on the handler's
      thread + ``http[out]``'s own;
    - ``parts_ms``: the means of those parts, for the report;
    - ``execute_cpu_ms`` / ``execute_wall_ms``: [mean, dispatches] of
      ``batch[execute]``'s own CPU and wall;
    - ``phases``: {``plane[...]``: [dispatches, wall ms, CPU ms]}, each
      phase's time summed a dispatch (inside its ``batch[execute]``, on
      its line) and averaged over the dispatches that ran it;
    - ``loop_lag_ms``: [mean, ticks] of ``host[loop]``'s ``lag_us``, and
      ``loop_lag_p95_ms``, ``loop_lag_max_ms``;
    - ``python_cpu``: the summed role clocks of the first and the last
      tick that carries them, over the wall between those ticks: ``pct``
      (per cent of one core), ``roles`` ({role: per cent}), ``seconds``,
      ``threads`` ([least, most]); None with fewer than two such ticks.
      A thread born between the two counts whole (its clock starts at
      0), one that ended between them is missing from the last sum: an
      undercount;
    - ``gc``: ``union_s`` of the ``host[gc]`` twins, and ``by_gen``
      {generation: [passes, seconds, device idle seconds inside them or
      None, objects collected]}, the idle measured against ``busy`` (the device's busy
      intervals on the trace's clock, where known); None where the trace
      holds neither a tick nor a collector twin (a program without them).
    """
    from harness.xplane_spans import _covered, _union
    requests: dict = {}
    executes, planes, ticks, clocks, gcs = [], [], [], [], []
    for li, events in enumerate(raw["host"]):
        for name, s, d, st in events:
            rec = {"line": li, "start": s, "end": s + d,
                   "cpu0": st.get("cpu0_us"), "cpu1": st.get("cpu1_us")}
            if name == "host[loop]":
                ticks.append([s, st["lag_us"]])
                if "threads" in st:
                    clocks.append([s, {r: st[r] for r in ROLES},
                                   st["threads"]])
            elif name == "host[gc]":
                gcs.append([s, s + d, int(st["generation"]),
                            int(st.get("collected", 0))])
            elif rec["cpu0"] is None or rec["cpu1"] is None:
                continue
            elif "trace_id" in st:
                req = requests.setdefault(str(st["trace_id"]), {})
                req.setdefault(name, rec)
            elif name == "batch[execute]":
                executes.append(rec)
            elif name.startswith("plane["):
                planes.append((name, rec))

    # -- requests: what each costs its threads
    post, whole = [], []
    parts: dict = {"http[in]": [], "rest[parse]..rest[render]": [],
                   "plane_dispatch.end..rest[render].end": [],
                   "http[out]": []}

    def own(sp):
        return cpu_between(_edge(sp, "start"), _edge(sp, "end"))

    for req in requests.values():
        if not search_spans & set(req):
            continue
        out = own(req.get("http[out]"))
        after = cpu_between(_edge(req.get("plane_dispatch"), "end"),
                            _edge(req.get("rest[render]"), "end"))
        inn = own(req.get("http[in]"))
        handler = cpu_between(_edge(req.get("rest[parse]"), "start"),
                              _edge(req.get("rest[render]"), "end"))
        for k, v in zip(parts, (inn, handler, after, out)):
            if v is not None:
                parts[k].append(v / 1e3)
        if after is not None and out is not None:
            post.append((after + out) / 1e3)
        if None not in (inn, handler, out):
            whole.append((inn + handler + out) / 1e3)

    # -- dispatches: batch[execute] and the plane's phases inside it
    per_phase: dict = {}
    for ex in executes:
        inside: dict = {}
        for name, p in planes:
            if p["line"] == ex["line"] and p["start"] >= ex["start"] \
                    and p["end"] <= ex["end"]:
                w_c = inside.setdefault(name, [0.0, 0.0])
                w_c[0] += (p["end"] - p["start"]) / 1e6
                w_c[1] += (p["cpu1"] - p["cpu0"]) / 1e3
        for name, (w, c) in inside.items():
            per_phase.setdefault(name, []).append((w, c))
    phases = {name: [len(v), statistics.fmean(w for w, _ in v),
                     statistics.fmean(c for _, c in v)]
              for name, v in per_phase.items()}

    # -- the loop's ticks and the threads' clocks
    lags = sorted(lag / 1e3 for _t, lag in ticks)
    python_cpu = None
    clocks.sort(key=lambda c: c[0])
    if len(clocks) >= 2 and clocks[-1][0] > clocks[0][0]:
        (t0, c0, _n0), (t1, c1, _n1) = clocks[0], clocks[-1]
        wall_us = (t1 - t0) / 1e3
        roles = {r: 100.0 * (c1[r] - c0[r]) / wall_us for r in ROLES}
        python_cpu = {"pct": sum(roles.values()), "roles": roles,
                      "seconds": wall_us / 1e6,
                      "threads": [min(c[2] for c in clocks),
                                  max(c[2] for c in clocks)]}

    # -- the collector's passes
    gc = None
    if ticks or gcs:
        by_gen: dict = {}
        first, last = (busy[0][0], busy[-1][1]) if busy else (None, None)
        for s, e, g, freed in gcs:
            gen = by_gen.setdefault(str(g), [0, 0.0, None, 0])
            gen[0] += 1
            gen[1] += (e - s) / 1e9
            gen[3] += freed
            if busy:
                a, b = max(s, first), min(e, last)
                if b > a:
                    gen[2] = (gen[2] or 0.0) + \
                        ((b - a) - _covered(a, b, busy)) / 1e9
                elif gen[2] is None:
                    gen[2] = 0.0
        gc = {"union_s": sum(b - a for a, b in
                             _union([[s, e] for s, e, _g, _f in gcs]))
              / 1e9,
              "by_gen": by_gen}

    return {
        "post_batcher_cpu_ms": _mean(post),
        "request_cpu_ms": _mean(whole),
        "parts_ms": {k: _mean(v) for k, v in parts.items()},
        "execute_cpu_ms": _mean([(e["cpu1"] - e["cpu0"]) / 1e3
                                 for e in executes]),
        "execute_wall_ms": _mean([(e["end"] - e["start"]) / 1e6
                                  for e in executes]),
        "phases": phases,
        "loop_lag_ms": _mean(lags),
        "loop_lag_p95_ms": lags[min(len(lags) - 1, int(0.95 * len(lags)))]
        if lags else None,
        "loop_lag_max_ms": lags[-1] if lags else None,
        "python_cpu": python_cpu,
        "gc": gc}


def _fmt(v, nd: int = 3) -> str:
    return "n/a" if v is None else f"{v:.{nd}f}"


def report(summary: dict, say) -> None:
    """The earlier lines for people."""
    p = summary["parts_ms"]
    say(f"host spans: a request's CPU, mean ms over "
        f"{summary['request_cpu_ms'][1]} requests: "
        + ", ".join(f"{k} {_fmt(v[0])}" for k, v in p.items())
        + f"; whole request {_fmt(summary['request_cpu_ms'][0])}, after "
          f"its dispatch {_fmt(summary['post_batcher_cpu_ms'][0])}")
    say(f"host spans: batch[execute] a dispatch, over "
        f"{summary['execute_wall_ms'][1]}: wall "
        f"{_fmt(summary['execute_wall_ms'][0])} ms, CPU "
        f"{_fmt(summary['execute_cpu_ms'][0])} ms; plane phases a dispatch "
        f"(dispatches, wall ms, CPU ms): " + (", ".join(
            f"{k} {n} {w:.3f} {c:.3f}" for k, (n, w, c) in sorted(
                summary["phases"].items(), key=lambda kv: -kv[1][1]))
            or "none"))
    say(f"host spans: event loop lag over {summary['loop_lag_ms'][1]} "
        f"ticks: mean {_fmt(summary['loop_lag_ms'][0])} ms, p95 "
        f"{_fmt(summary['loop_lag_p95_ms'])}, max "
        f"{_fmt(summary['loop_lag_max_ms'])}")
    pc = summary["python_cpu"]
    if pc is not None:
        say(f"host spans: the node's Python threads' CPU over "
            f"{pc['seconds']:.3f} s of ticks: {pc['pct']:.1f} % of one "
            f"core (" + ", ".join(f"{r[4:-3]} {v:.1f} %" for r, v in
                                  pc["roles"].items())
            + f"); {pc['threads'][0]}-{pc['threads'][1]} threads")
    gc = summary["gc"]
    if gc is not None:
        say(f"host spans: collector passes, union {gc['union_s']:.4f} s; by "
            f"generation (passes, seconds, device idle seconds inside "
            f"them, objects collected): " + (", ".join(
                f"{g}: {n} {s:.4f} {_fmt(i, 4)} {c}"
                for g, (n, s, i, c) in sorted(gc["by_gen"].items()))
                or "none"))


def _nothing(summary: dict) -> bool:
    """A trace with no clock and no ``host[...]`` twin: a program that
    lacks them."""
    return (summary["gc"] is None and not summary["request_cpu_ms"][1]
            and not summary["execute_cpu_ms"][1]
            and not summary["post_batcher_cpu_ms"][1])


def load(ctx: dict):
    """The traced window's host summary, or None. Reads the trace once a
    run: the summary is kept beside it."""
    from harness import xplane, xplane_spans
    tdir = xplane_spans.trace_dir(ctx)
    out = os.path.join(tdir, "host_spans.json")
    if not os.path.isfile(out):
        pb = xplane.find_xplane(tdir)
        if pb is None:
            return None
        t0 = time.perf_counter()
        raw_out = os.path.join(tdir, "host_spans.raw.json")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), pb, raw_out],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=600)
        summary = None
        if proc.returncode != 0:
            ctx["say"](f"host spans: reading {pb} failed: "
                       f"{proc.stderr[-800:]}")
        else:
            with open(raw_out) as f:
                raw = json.load(f)
            spans = xplane_spans.load(ctx)
            summary = reduce(raw, xplane_spans.SEARCH_SPANS,
                             spans["step_intervals"] if spans else None)
            if _nothing(summary):
                ctx["say"]("host spans: no span twin with a CPU clock and "
                           "no host[...] twin in the trace: nothing to "
                           "read")
                summary = None
            else:
                ctx["say"](f"host spans: read in "
                           f"{time.perf_counter() - t0:.1f} s")
                report(summary, ctx["say"])
        with open(out, "w") as f:
            json.dump(summary, f)
    with open(out) as f:
        return json.load(f)


def main(argv) -> int:
    src, dst = argv
    with open(dst, "w") as f:
        json.dump(read_planes(src), f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
