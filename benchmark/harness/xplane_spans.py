"""The program's own spans and scopes, read from the profiler trace.

The node records every span of its request path twice: in its trace store,
and, while a ``jax.profiler`` session is active, as a
``jax.profiler.TraceAnnotation`` in the host plane of the same
``.xplane.pb`` that holds the device's events (``common/tracing.py``): one
file, one clock. Request spans carry ``trace_id`` / ``span_id`` /
``parent`` stats, the dispatcher's ``batch[...]`` spans the dispatch's
``seq``, a request's ``plane_dispatch`` the ``dispatch_seq`` of the
dispatch that carried it. The jitted steps are named for their kernel
family (``jit_knn_exact``) and their ops carry ``jax.named_scope`` paths
in the HLO ``op_name``.

Two steps, as in ``xplane.py``:

1. :func:`read_planes` (the helper process: ``python
   benchmark/harness/xplane_spans.py <trace.xplane.pb> <out.json>`` with
   ``JAX_PLATFORMS=cpu``, once the node is gone) reads the events and
   writes :func:`reduce`'s summary.
2. :func:`reduce` and the functions below it are plain Python over those
   events, checked on a recorded fixture
   (``tests/data/span_trace_planes.json``).

:func:`load` is what the readers call: it runs the helper once a run and
prints the report lines. A parent or a trace without spans gives None, and
the readers then report nothing.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CACHE_DIR = os.path.join(os.path.dirname(HERE), ".cache")

SPAN_NAME = re.compile(
    r"^(?:(?:http|rest|coordinator|shards|shard|batch|plane)\[.*\]"
    r"|plane_dispatch|fused_dispatch)$")
LAUNCH_NAME = re.compile(r"^PjitFunction\((.*)\)$")
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
#: the stat that carries an op's HLO ``op_name`` (the ``jax.named_scope``
#: path). On a TPU it sits on the op's *metadata* (``XEventMetadata.stats``),
#: which ``ProfileData`` does not hand out: :func:`_metadata_op_names` reads
#: it from the file's protobuf wire format
OP_NAME_STAT = "tf_op"
GAP_MIN_NS = 1e6
#: a trace that holds one of these is a ``_search`` request
SEARCH_SPANS = {"coordinator[search]", "shard[plan]", "plane_dispatch",
                "fused_dispatch"}


def _varint(buf, i: int):
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of one protobuf message: varints as ints,
    length-delimited fields as ``memoryview``s, fixed ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wire in (1, 5):
            val, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, val


def _metadata_op_names(path: str) -> dict:
    """{device plane: {event name: HLO op_name}} from the ``tf_op`` stat of
    each event's metadata. ``XSpace.planes = 1``; ``XPlane``: ``name = 2``,
    ``event_metadata = 4``, ``stat_metadata = 5`` (maps: key 1, value 2);
    ``XEventMetadata``: ``name = 2``, ``stats = 5``; ``XStatMetadata``:
    ``name = 2``; ``XStat``: ``metadata_id = 1``, ``str_value = 5``,
    ``ref_value = 7`` (a stat metadata whose name is the value). The
    planes' lines are skipped unread."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for fno, plane in _fields(space):
        if fno != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pf, val in _fields(plane):
            if pf == 2:
                name = bytes(val).decode()
            elif pf == 4:
                metas.append(val)
            elif pf == 5:
                ent = dict(_fields(val))
                stat_names[ent.get(1)] = bytes(
                    dict(_fields(ent[2])).get(2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        names = out.setdefault(name, {})
        for ent in metas:
            ev_name, op_name = "", ""
            for mf, val in _fields(dict(_fields(ent))[2]):
                if mf == 2:
                    ev_name = bytes(val).decode()
                elif mf == 5:
                    st = dict(_fields(val))
                    if stat_names.get(st.get(1)) == OP_NAME_STAT:
                        op_name = bytes(st[5]).decode() if 5 in st \
                            else stat_names.get(st.get(7), "")
            if op_name:
                names[ev_name] = op_name.rstrip(":")
    return out


def read_planes(path: str) -> dict:
    """{"host": [line...], "devices": [{"name", "modules", "ops"}]}: host
    lines as {"spans": [[name, start_ns, dur_ns, stats]], "launches":
    [[module, start_ns, dur_ns]]}; device modules as [name, start, dur],
    ops as [event name, start, dur, op_name]."""
    from jax.profiler import ProfileData
    host, devices = [], []
    op_names = _metadata_op_names(path)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans, launches = [], []
                for ev in line.events:
                    if SPAN_NAME.match(ev.name):
                        spans.append([ev.name, float(ev.start_ns),
                                      float(ev.duration_ns),
                                      dict(ev.stats)])
                    else:
                        m = LAUNCH_NAME.match(ev.name)
                        if m:
                            launches.append([m.group(1),
                                             float(ev.start_ns),
                                             float(ev.duration_ns)])
                if spans or launches:
                    host.append({"spans": spans, "launches": launches})
        elif plane.name.startswith("/device:"):
            mods, ops = [], []
            for line in plane.lines:
                if line.name in MODULE_LINES:
                    mods += [[ev.name, float(ev.start_ns),
                              float(ev.duration_ns)] for ev in line.events]
                elif line.name in OP_LINES:
                    names = op_names.get(plane.name, {})
                    ops += [[ev.name[:60], float(ev.start_ns),
                             float(ev.duration_ns), names.get(ev.name, "")]
                            for ev in line.events]
            if ops and not any(op[3] for op in ops):
                # a layout this decoder does not know: better no span
                # metric at all than every op counted as unscoped
                raise ValueError(
                    f"{plane.name}: none of {len(ops)} op events has a "
                    f"{OP_NAME_STAT!r} stat on its metadata (the XPlane "
                    f"field numbers of _metadata_op_names no longer hold?)")
            if mods or ops:
                devices.append({"name": plane.name, "modules": mods,
                                "ops": ops})
    return {"host": host, "devices": devices}


# ---------------------------------------------------------------------------
# plain Python from here on
# ---------------------------------------------------------------------------


def _module_name(name: str) -> str:
    """``jit_knn_exact(123)`` -> ``jit_knn_exact``."""
    return re.sub(r"\(\d+\)$", "", name)


def _span_key(name: str) -> str:
    """The name a span is reported under: ``rest[<action>]`` and
    ``shards[<index>]`` as ``rest[...]`` / ``shards[...]``, whatever the
    action or the index."""
    return re.sub(r"^(rest|shards)\[(?!parse|render).*\]$", r"\1[...]", name)


def _strip_id(v) -> str:
    """``trace_id`` / ``span_id`` / ``parent`` stats carry a letter in
    front (a stat that looks like a number is read back as one)."""
    return str(v)[1:]


def scope_path(op_name: str) -> list:
    """The components of an HLO ``op_name``, transform wrappers taken off
    (``vmap(scores)`` is ``scores``, ``vmap()`` is nothing)."""
    parts = []
    for p in op_name.split("/"):
        m = re.match(r"^(?:vmap|pmap|jvp|transpose|remat|checkpoint)"
                     r"\((.*)\)$", p)
        p = m.group(1) if m else p
        if p:
            parts.append(p)
    return parts


def scope_of(op_name: str, scopes: list):
    """The first of ``scopes`` (``knn_exact/scores``) whose components
    appear in order among the op's. A scope that ends in ``$``
    (``knn_exact/while$``) names the ops whose path ends there: the
    loop's own ops, not what runs inside it (XLA gives a fusion it hoists
    to the loop's level the loop's name: a scan's block reads)."""
    parts = scope_path(op_name)
    for scope in scopes:
        want = scope.rstrip("$").split("/")
        if scope.endswith("$"):
            if parts[-len(want):] == want:
                return scope
            continue
        it = iter(parts)
        if all(c in it for c in want):
            return scope
    return None


def _self_ns(events: list) -> list:
    """Own nanoseconds of each of ``events`` ([start, dur, ...]) that nest
    on one line: duration less what the events inside it cover."""
    own = [0.0] * len(events)
    stack: list = []          # [end, index, child_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, i, child = stack.pop()
            own[i] = max(events[i][1] - child, 0.0)
            if stack:
                stack[-1][2] += events[i][1]

    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    for i in order:
        close(events[i][0])
        stack.append([events[i][0] + events[i][1], i, 0.0])
    close(float("inf"))
    return own


def _union(intervals: list) -> list:
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _covered(a: float, b: float, intervals: list) -> float:
    """Nanoseconds of [a, b] that the union of ``intervals`` covers."""
    return sum(min(e, b) - max(s, a)
               for s, e in _union([[max(s, a), min(e, b)]
                                   for s, e in intervals
                                   if e > a and s < b]))


def _innermost(span: list):
    """Sort key: of spans open at one time, the one that started last,
    and of two that started together the shorter."""
    return span[1], -span[2]


def reduce(raw: dict) -> dict:
    """The summary the readers use; every time in nanoseconds on the
    trace's clock.

    - ``requests``: {trace id: {"spans": {name: [start, end]},
      "dispatch_seq": "n"}} for every trace with at least one span (a
      name seen twice in a trace keeps its first span);
    - ``dispatches``: {seq: {"spans": {name: [start, end]}, "line": i,
      "kernel", "requests", "b_pad", "steps": [[start, end]...]}}: the
      dispatcher's ``batch[...]`` spans, the ``plane[...]`` spans inside
      ``batch[execute]``, and the executions of ``jit_<kernel>`` on the
      device that lie inside ``batch[execute]``, each counted once;
    - ``span_stats``: {name: {"count", "mean_ms", "p95_ms",
      "self_mean_ms"}};
    - ``launches``: {span name: programs launched on request threads
      while that span was the innermost open one}, with ``n_requests``;
    - ``modules``: {module: {"count", "seconds"}}; ``op_self_s``:
      {module: {op_name: own seconds}}; ``step_intervals``: the union of
      the first device's ``jit_*`` executions, [[start, end]];
    - ``idle_gaps``: [[what the dispatchers were doing, seconds]], gaps
      over 1 ms only, and ``idle_s`` / ``window_s`` of the first device.
    """
    traced, untraced = [], []       # [name, start, end, line, stats]
    launches = []
    for li, line in enumerate(raw["host"]):
        for name, s, d, stats in line["spans"]:
            (traced if "trace_id" in stats else untraced).append(
                [name, s, s + d, li, stats])
        # one call can leave two nested events of one name (the call and
        # its cache miss): the outermost is the launch
        end = {}
        for m, s, d in sorted(line["launches"], key=lambda e: e[1]):
            if s >= end.get(m, 0.0):
                launches.append([m, s, li])
                end[m] = s + d

    # -- requests, by trace id
    requests: dict = {}
    by_id, kids = {}, {}
    for sp in traced:
        name, s, e, _li, st = sp
        tid = _strip_id(st["trace_id"])
        req = requests.setdefault(tid, {"spans": {}, "dispatch_seq": None})
        req["spans"].setdefault(name, [s, e])
        if name == "plane_dispatch" and "dispatch_seq" in st \
                and req["dispatch_seq"] is None:
            req["dispatch_seq"] = str(int(st["dispatch_seq"]))
        by_id[_strip_id(st["span_id"])] = sp
        if "parent" in st:
            kids.setdefault(_strip_id(st["parent"]), []).append([s, e])

    # the harness's own polls of the node's counters are traced too: a
    # request of the window is a trace that reached the search path
    requests = {tid: r for tid, r in requests.items()
                if SEARCH_SPANS & set(r["spans"])}

    # -- span statistics; self time by parent link for request spans, by
    # nesting on the thread's line for the dispatchers'
    durs, selfs = {}, {}
    for sid, (name, s, e, _li, _st) in by_id.items():
        key = _span_key(name)
        durs.setdefault(key, []).append(e - s)
        selfs.setdefault(key, []).append(
            (e - s) - _covered(s, e, kids.get(sid, [])))
    lines = {}
    for sp in untraced:
        lines.setdefault(sp[3], []).append(sp)
    for li, sps in lines.items():
        own = _self_ns([[s, e - s] for _n, s, e, _l, _st in sps])
        for (name, s, e, _l, _st), o in zip(sps, own):
            durs.setdefault(name, []).append(e - s)
            selfs.setdefault(name, []).append(o)
    span_stats = {}
    for name, vals in durs.items():
        vals = sorted(vals)
        span_stats[name] = {
            "count": len(vals),
            "mean_ms": statistics.fmean(vals) / 1e6,
            "p95_ms": vals[min(len(vals) - 1,
                               int(0.95 * len(vals)))] / 1e6,
            "self_mean_ms": statistics.fmean(selfs[name]) / 1e6}

    # -- device: modules, op self time by op_name, busy intervals
    modules, op_self, step_execs = {}, {}, {}
    busy, first, last = [], None, None
    for di, dev in enumerate(raw["devices"]):
        mods = sorted(([_module_name(n), s, s + d]
                       for n, s, d in dev["modules"]), key=lambda m: m[1])
        for n, s, e in mods:
            m = modules.setdefault(n, {"count": 0, "seconds": 0.0})
            m["count"] += 1
            m["seconds"] += (e - s) / 1e9
            if di == 0:
                step_execs.setdefault(n, []).append([s, e])
        own = _self_ns([[s, d] for _n, s, d, _o in dev["ops"]])
        starts = [m[1] for m in mods]
        for (ev_name, s, d, op_name), o in zip(dev["ops"], own):
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][0] if i >= 0 and s < mods[i][2] else "(no module)"
            per = op_self.setdefault(mod, {})
            key = op_name or ev_name
            per[key] = per.get(key, 0.0) + o / 1e9
        if di == 0:
            src = dev["ops"] or dev["modules"]
            busy = _union([[e[1], e[1] + e[2]] for e in src])
            if busy:
                first, last = busy[0][0], busy[-1][1]

    # -- dispatches, by seq
    dispatches: dict = {}
    for name, s, e, li, st in untraced:
        if name.startswith("batch[") and "seq" in st:
            d = dispatches.setdefault(str(int(st["seq"])), {
                "spans": {}, "line": li, "kernel": None, "requests": None,
                "b_pad": None, "steps": []})
            d["spans"][name] = [s, e]
            for k in ("kernel", "requests", "b_pad"):
                if k in st:
                    d[k] = st[k]
    for d in dispatches.values():
        ex = d["spans"].get("batch[execute]")
        if ex is None:
            continue
        for name, s, e, li, _st in untraced:
            if li == d["line"] and name.startswith("plane[") \
                    and s >= ex[0] and e <= ex[1]:
                d["spans"].setdefault(name, [s, e])
    taken = set()
    for seq in sorted(dispatches, key=lambda q: dispatches[q]["spans"].get(
            "plane[launch]", dispatches[q]["spans"].get(
                "batch[execute]", [0]))[0]):
        d = dispatches[seq]
        ex = d["spans"].get("batch[execute]")
        if ex is None or not d["kernel"]:
            continue
        for i, (s, e) in enumerate(step_execs.get(f"jit_{d['kernel']}",
                                                  [])):
            if (i not in taken) and s >= ex[0] and e <= ex[1]:
                taken.add(i)
                d["steps"].append([s, e])

    # -- programs launched on request threads, by the innermost request
    # span open on that thread at that time
    per_line = {}
    for sp in traced:
        per_line.setdefault(sp[3], []).append(sp)
    launched: dict = {}
    for mod, t, li in launches:
        open_ = [sp for sp in per_line.get(li, []) if sp[1] <= t < sp[2]]
        if not open_:
            continue
        inner = max(open_, key=_innermost)
        key = _span_key(inner[0])
        per = launched.setdefault(key, {})
        per[mod] = per.get(mod, 0) + 1

    # -- idle gaps over 1 ms, by what the dispatchers were doing
    idle: dict = {}
    idle_s = 0.0
    if busy:
        disp = sorted(untraced, key=lambda sp: sp[1])
        cur = first
        gaps = []
        for a, b in busy:
            if a > cur:
                gaps.append([cur, a])
            cur = max(cur, b)
        idle_s = sum(b - a for a, b in gaps) / 1e9
        for a, b in gaps:
            if b - a < GAP_MIN_NS:
                continue
            over = [sp for sp in disp if sp[2] > a and sp[1] < b]
            cuts = sorted({a, b} | {t for sp in over for t in sp[1:3]
                                    if a < t < b})
            for x, y in zip(cuts, cuts[1:]):
                mid = (x + y) / 2
                open_ = [sp for sp in over if sp[1] <= mid < sp[2]]
                what = max(open_, key=_innermost)[0] if open_ \
                    else "no dispatcher busy"
                idle[what] = idle.get(what, 0.0) + (y - x) / 1e9
    return {
        "requests": requests, "dispatches": dispatches,
        "span_stats": span_stats,
        "launches": {"by_span": launched, "n_requests": sum(
            1 for r in requests.values() if "http[in]" in r["spans"])},
        "modules": modules, "op_self_s": op_self,
        "step_intervals": _union([iv for n, ivs in step_execs.items()
                                  if n.startswith("jit_") for iv in ivs]),
        "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1]),
        "idle_s": idle_s,
        "window_s": (last - first) / 1e9 if busy else None}


# ---------------------------------------------------------------------------
# what the readers compute
# ---------------------------------------------------------------------------


def _edge_ns(summary: dict, req: dict, spec: dict):
    """One end of one span of a request; ``"of": "dispatch"`` takes the
    span from the dispatch that carried the request."""
    spans = req["spans"]
    if spec.get("of") == "dispatch":
        d = summary["dispatches"].get(req["dispatch_seq"])
        if d is None:
            return None
        spans = d["spans"]
    iv = spans.get(spec["span"])
    if iv is None:
        return None
    return iv[0] if spec["edge"] == "start" else iv[1]


def edge_ms(summary: dict, frm: dict, to: dict):
    """(mean ms, requests counted) of ``to`` - ``frm`` over the requests
    that have both edges; a request that lacks one (its dispatch fell
    outside the traced window) is dropped, not guessed."""
    vals = []
    for req in summary["requests"].values():
        a, b = _edge_ns(summary, req, frm), _edge_ns(summary, req, to)
        if a is not None and b is not None:
            vals.append((b - a) / 1e6)
    return (statistics.fmean(vals), len(vals)) if vals else (None, 0)


def execute_host_ms(summary: dict):
    """(mean ms, dispatches counted) of ``batch[execute]`` less the time
    inside it in which a jitted program ran on the device: the dispatch's
    own step, and the other in-flight dispatch's, which it waits out in its
    ``plane[sync]`` (two overlap since PR 26). What is left is the host's
    time that no step hides."""
    vals = []
    for d in summary["dispatches"].values():
        ex = d["spans"].get("batch[execute]")
        if ex is None or not d["steps"]:
            continue
        vals.append(((ex[1] - ex[0]) - _covered(
            ex[0], ex[1], summary["step_intervals"])) / 1e6)
    return (statistics.fmean(vals), len(vals)) if vals else (None, 0)


def scope_ms(summary: dict, module: str, scopes: list):
    """Device own milliseconds under ``scopes`` per execution of
    ``module``, or None where the module did not run or its ops carry no
    scope at all."""
    mod = summary["modules"].get(module)
    ops = summary["op_self_s"].get(module)
    if not mod or not ops or not mod["count"]:
        return None
    if not any("/" in name for name in ops):
        return None
    total = sum(s for name, s in ops.items()
                if scope_of(name, scopes) is not None)
    return total / mod["count"] * 1e3


def report(summary: dict, say, answered: int, client_ms=None) -> None:
    """The earlier lines for people. ``client_ms``: the window's latencies
    on the client's clock, for the line that sets the server's time
    beside them."""
    reqs = summary["requests"]
    joined = sum(1 for r in reqs.values()
                 if "http[in]" in r["spans"] and "http[out]" in r["spans"])
    say(f"spans: {len(reqs)} traces in the profiler's host plane, "
        f"{joined} joined from http[in] to http[out] by trace id, of "
        f"{answered} answered requests of the window "
        f"({100.0 * joined / max(answered, 1):.1f} %); "
        f"{len(summary['dispatches'])} dispatches by seq")
    server, n = edge_ms(summary, {"span": "http[in]", "edge": "start"},
                        {"span": "http[out]", "edge": "end"})
    if server is not None and client_ms:
        say(f"spans: http[in].start to http[out].end mean {server:.3f} ms "
            f"over {n} requests; the client's clock, send to last byte: "
            f"mean {statistics.fmean(client_ms):.3f} ms, median "
            f"{statistics.median(client_ms):.3f} ms")
    say("spans: name count mean_ms p95_ms self_ms")
    for name, st in sorted(summary["span_stats"].items(),
                           key=lambda kv: -kv[1]["mean_ms"]):
        say(f"spans:   {name} {st['count']} {st['mean_ms']:.3f} "
            f"{st['p95_ms']:.3f} {st['self_mean_ms']:.3f}")
    # the waterfall: where each span of a request starts and ends, as
    # mean offsets from its http[in].start; what lies between two spans
    # is time with no span open (a thread waiting for the interpreter)
    offs: dict = {}
    for r in reqs.values():
        sp = r["spans"]
        if "http[in]" not in sp or "http[out]" not in sp:
            continue
        for name, (a, b) in sp.items():
            key = _span_key(name)
            o = offs.setdefault(key, [[], []])
            o[0].append(a - sp["http[in]"][0])
            o[1].append(b - sp["http[in]"][0])
    say("spans: waterfall, mean ms from http[in].start: "
        + ", ".join(f"{k} {statistics.fmean(v[0]) / 1e6:.3f}.."
                    f"{statistics.fmean(v[1]) / 1e6:.3f}"
                    for k, v in sorted(offs.items(), key=lambda kv:
                                       statistics.fmean(kv[1][0]))))
    n = max(summary["launches"]["n_requests"], 1)
    for span, mods in sorted(summary["launches"]["by_span"].items()):
        per = ", ".join(f"{m} {c / n:.2f}" for m, c in
                        sorted(mods.items(), key=lambda kv: -kv[1]))
        say(f"spans: device programs launched per request inside {span}: "
            f"{sum(mods.values()) / n:.2f} ({per})")
    for mod, ops in sorted(summary["op_self_s"].items(), key=lambda kv:
                           -sum(kv[1].values()))[:6]:
        by_scope: dict = {}
        for name, s in ops.items():
            parts = scope_path(name)
            # the scope is what follows the jit(...)/shard_map frames,
            # down to the last named component before the primitive
            named = [p for p in parts[:-1]
                     if not re.match(r"^(jit|pjit)\(|^shard_map$|^while$|"
                                     r"^body$|^cond$|^closed_call$", p)]
            key = "/".join(named[:2]) if named else "(unscoped)"
            by_scope[key] = by_scope.get(key, 0.0) + s
        tot = sum(by_scope.values())
        cnt = summary["modules"].get(mod, {}).get("count", 0)
        say(f"spans: device time of {mod} ({cnt} executions, {tot:.4f} s "
            f"own time of its ops): " + ", ".join(
                f"{k} {v:.4f}" for k, v in sorted(
                    by_scope.items(), key=lambda kv: -kv[1])[:8]))
    say(f"spans: device idle {summary['idle_s']:.4f} s of "
        f"{summary['window_s'] or 0:.4f} s between its first and last op; "
        f"gaps over 1 ms by the innermost dispatcher span open: "
        + (", ".join(f"{k} {v:.4f}" for k, v in summary["idle_gaps"][:10])
           or "none"))


def trace_dir(ctx: dict) -> str:
    """Where ``run.py`` keeps the run's trace (benchmark/README.md)."""
    return os.path.join(CACHE_DIR, "run", ctx["cell"].name, "trace")


def load(ctx: dict):
    """The run's span summary, or None where the trace has none (no trace,
    or a program without the spans). Reads the trace once a run: the
    summary is kept beside it."""
    from harness import xplane
    tdir = trace_dir(ctx)
    out = os.path.join(tdir, "spans.json")
    if not os.path.isfile(out):
        pb = xplane.find_xplane(tdir)
        if pb is None:
            return None
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), pb, out],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=600)
        if proc.returncode != 0:
            ctx["say"](f"spans: reading {pb} failed: {proc.stderr[-800:]}")
            with open(out, "w") as f:
                json.dump(None, f)
            return None
        with open(out) as f:
            summary = json.load(f)
        if summary and summary["requests"]:
            ctx["say"](f"spans: read in {time.perf_counter() - t0:.1f} s")
            report(summary, ctx["say"], len(ctx["requests"]),
                   ctx.get("latencies_ms"))
        else:
            ctx["say"]("spans: the trace's host plane holds no request "
                       "span (a program without them): nothing to read")
    with open(out) as f:
        summary = json.load(f)
    return summary if summary and summary["requests"] else None


def main(argv) -> int:
    src, dst = argv
    doc = reduce(read_planes(src))
    with open(dst, "w") as f:
        json.dump(doc, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
