#!/usr/bin/env python
"""Pretty-print a span tree from a running node's trace store.

Usage:
    python scripts/trace_dump.py TRACE_ID [--host http://127.0.0.1:9200]
    python scripts/trace_dump.py --last [--host ...]   # newest trace
    python scripts/trace_dump.py --list [--min-ms 100] [--tenant T]
    python scripts/trace_dump.py TRACE_ID --events     # + journal events

``--last`` reads the node's ``GET /_trace`` listing (newest-first trace
index with root action + duration) and dumps the newest trace — no more
probe-request guessing; if the store is empty it issues one probe
request to mint a trace. ``--list`` prints the listing itself;
``--min-ms`` and ``--tenant`` pass through to the server-side
``GET /_trace?min_ms=&tenant=`` filters (applied BEFORE the listing
cap, so they surface the newest matching traces).

``--events`` additionally fetches the flight-recorder journal
(``GET /_flight_recorder?trace_id=...``) and interleaves each event into
the span tree at the deepest span whose window contains the event's
timestamp — a failover wave or breaker trip renders INSIDE the request
that felt it.

``--chrome PATH`` writes the span tree (plus the ``--events`` journal
interleave when requested) as Chrome trace-event JSON — the SAME format
``GET /_profiler/timeline`` serves for dispatch timelines — so a
request's span tree and the dispatch timeline that served it load
side-by-side in perfetto/chrome://tracing: spans render as complete
``X`` events (one process per node, nested by time containment),
journal events as instant ``i`` marks.

Output, one line per span, indented by tree depth:

    http[in]                                     0.09ms  bytes_in=57
      rest[parse]                                0.12ms
      rest[indices:data/read/search]            12.41ms  node=n0
        coordinator[search]                     11.80ms  indices=logs
          shards[logs]                          11.02ms
            shard[plan]                          0.21ms  route=plane
            plane_dispatch                       9.13ms  compile_cache=hit  dispatch_seq=88
            * failover_wave                      @+3.20ms  failed=n2
            shard[rank]                          0.02ms
            shard[fetch]                         1.40ms  hits=10
      rest[render]                               0.30ms
      http[out]                                  0.05ms  status=200  bytes_out=4211

(``http[in]`` ends where the request leaves the event loop for a handler
thread; its children start after it has ended.)
"""
from __future__ import annotations

import argparse
import json
import sys
import urllib.parse
import urllib.request
import zlib


def _get(host: str, path: str, headers=None):
    req = urllib.request.Request(host.rstrip("/") + path,
                                 headers=headers or {})
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.status, dict(r.headers), r.read()


def _fmt_attrs(span: dict) -> str:
    parts = []
    if span.get("node"):
        parts.append(f"node={span['node']}")
    for k, v in (span.get("attrs") or {}).items():
        if isinstance(v, float):
            v = round(v, 2)
        parts.append(f"{k}={v}")
    return "  ".join(parts)


def attach_events(tree: list, events: list) -> list:
    """Hang each journal event off the DEEPEST span whose
    [start, start+took] window contains the event's wall timestamp;
    events outside every span surface at the root. Returns the events
    that attached nowhere."""
    def best_span(spans, ts):
        for span in spans:
            s0 = span.get("start_ms")
            if s0 is None:
                continue
            if s0 <= ts <= s0 + max(span.get("took_ms", 0), 0):
                deeper = best_span(span.get("children") or [], ts)
                return deeper if deeper is not None else span
        return None

    orphans = []
    for ev in events:
        host = best_span(tree, ev.get("ts_ms", 0))
        if host is None:
            orphans.append(ev)
        else:
            host.setdefault("_events", []).append(ev)
    return orphans


def _print_event(ev: dict, depth: int, base_ms=None) -> None:
    name = "  " * depth + "* " + ev.get("type", "?")
    when = f"@{ev.get('ts_ms', 0):.0f}" if base_ms is None else \
        f"@+{ev.get('ts_ms', 0) - base_ms:.2f}ms"
    parts = [when]
    if ev.get("node"):
        parts.append(f"node={ev['node']}")
    for k, v in (ev.get("attrs") or {}).items():
        if isinstance(v, float):
            v = round(v, 2)
        parts.append(f"{k}={v}")
    print(f"{name:<48}{'':>9}  {'  '.join(parts)}".rstrip())


def print_tree(spans: list, depth: int = 0) -> None:
    for span in spans:
        name = "  " * depth + span.get("name", "?")
        took = f"{span.get('took_ms', 0):9.2f}ms"
        print(f"{name:<48}{took}  {_fmt_attrs(span)}".rstrip())
        base = span.get("start_ms")
        # interleave children spans + attached events by start time
        kids = [("span", c) for c in span.get("children") or []]
        kids += [("event", e) for e in span.get("_events") or []]
        kids.sort(key=lambda kv: kv[1].get("start_ms", kv[1].get(
            "ts_ms", 0)))
        for kind, item in kids:
            if kind == "span":
                print_tree([item], depth + 1)
            else:
                _print_event(item, depth + 1, base_ms=base)


def chrome_from_spans(doc: dict, events=None) -> dict:
    """Span tree + journal events -> Chrome trace-event JSON.

    One *process* per emitting node (pid derived from the node name the
    same way ``search/dispatch_profile.chrome_trace`` derives batcher
    pids, so a merged load never conflates nodes); spans become
    complete ``X`` events that nest by time containment on one track,
    journal events become instant ``i`` marks at their wall
    timestamp."""
    out = []
    named = set()

    def pid_of(node: str) -> int:
        pid = (zlib.crc32(f"trace\x00{node}".encode()) & 0x3FFFFFFF) | 1
        if pid not in named:
            named.add(pid)
            out.append({"ph": "M", "name": "process_name", "pid": pid,
                        "ts": 0, "args": {"name": f"{node} trace"}})
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": 1, "ts": 0, "args": {"name": "spans"}})
        return pid

    def walk(spans):
        for span in spans:
            node = str(span.get("node") or "local")
            args = {k: v for k, v in (span.get("attrs") or {}).items()}
            if span.get("span_id"):
                args["span_id"] = span["span_id"]
            out.append({
                "ph": "X", "name": str(span.get("name", "?")),
                "cat": "span", "pid": pid_of(node), "tid": 1,
                "ts": round(float(span.get("start_ms", 0)) * 1e3, 1),
                "dur": round(max(float(span.get("took_ms", 0)), 0.0)
                             * 1e3, 1),
                "args": args})
            walk(span.get("children") or [])

    walk(doc.get("tree") or [])
    for ev in events or []:
        node = str(ev.get("node") or "local")
        args = dict(ev.get("attrs") or {})
        if ev.get("trace_id"):
            args["trace_id"] = ev["trace_id"]
        out.append({
            "ph": "i", "name": str(ev.get("type", "?")), "cat": "journal",
            "pid": pid_of(node), "tid": 1, "s": "p",
            "ts": round(float(ev.get("ts_ms", 0)) * 1e3, 1),
            "args": args})
    return {"traceEvents": out, "displayTimeUnit": "ms",
            "otherData": {"trace_id": doc.get("trace_id")}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_id", nargs="?", help="trace id to dump")
    ap.add_argument("--host", default="http://127.0.0.1:9200")
    ap.add_argument("--last", action="store_true",
                    help="dump the newest trace from the GET /_trace "
                         "listing")
    ap.add_argument("--list", action="store_true", dest="list_traces",
                    help="print the recent-trace listing and exit")
    ap.add_argument("--min-ms", type=float, default=None,
                    help="with --list/--last: keep only traces at least "
                         "this slow (server-side GET /_trace?min_ms=)")
    ap.add_argument("--tenant", default=None,
                    help="with --list/--last: keep only one tenant's "
                         "traces (server-side GET /_trace?tenant=, the "
                         "X-Opaque-Id stamped on the rest[...] span)")
    ap.add_argument("--json", action="store_true",
                    help="raw JSON instead of the tree rendering")
    ap.add_argument("--events", action="store_true",
                    help="interleave flight-recorder journal events "
                         "(GET /_flight_recorder?trace_id=...) into the "
                         "span tree")
    ap.add_argument("--chrome", metavar="PATH", default=None,
                    help="write the span tree (and --events journal) as "
                         "Chrome trace-event JSON loadable in perfetto "
                         "next to GET /_profiler/timeline output")
    args = ap.parse_args()
    tid = args.trace_id

    def _listing():
        qs = []
        if args.min_ms is not None:
            qs.append(f"min_ms={args.min_ms:g}")
        if args.tenant:
            qs.append("tenant=" + urllib.parse.quote(args.tenant))
        path = "/_trace" + ("?" + "&".join(qs) if qs else "")
        status, _h, body = _get(args.host, path)
        if status != 200:
            print(f"GET {path} -> {status}: {body[:300]!r}",
                  file=sys.stderr)
            return None
        return json.loads(body).get("traces") or []

    if args.list_traces:
        rows = _listing()
        if rows is None:
            return 1
        for row in rows:
            line = (f"{row['trace_id']}  "
                    f"{row.get('took_ms', 0):9.2f}ms  "
                    f"{row.get('root', '?')}  "
                    f"spans={row.get('span_count', 0)}")
            if row.get("tenant"):
                line += f"  tenant={row['tenant']}"
            print(line)
        return 0
    if args.last:
        rows = _listing()
        if rows is None:
            return 1
        if not rows:
            # empty store: one probe request mints a trace
            _get(args.host, "/")
            rows = _listing() or []
        if not rows:
            print("trace store is empty", file=sys.stderr)
            return 2
        tid = rows[0]["trace_id"]
    if not tid:
        ap.error("pass TRACE_ID, --last or --list")
    status, _headers, body = _get(args.host, f"/_trace/{tid}")
    if status != 200:
        print(f"GET /_trace/{tid} -> {status}: {body[:300]!r}",
              file=sys.stderr)
        return 1
    doc = json.loads(body)
    events = []
    if args.events:
        status, _h, ebody = _get(
            args.host, f"/_flight_recorder?trace_id={tid}&limit=512")
        if status == 200:
            events = json.loads(ebody).get("events") or []
        else:
            print(f"GET /_flight_recorder -> {status} (events omitted)",
                  file=sys.stderr)
    if args.chrome:
        with open(args.chrome, "w") as f:
            json.dump(chrome_from_spans(doc, events), f)
        print(f"wrote {args.chrome} (load in ui.perfetto.dev or "
              f"chrome://tracing)")
        return 0
    if args.json:
        if events:
            doc["events"] = events
        json.dump(doc, sys.stdout, indent=2)
        print()
        return 0
    print(f"trace {doc['trace_id']} — {doc['span_count']} span(s)"
          + (f", {doc['dropped_spans']} dropped"
             if doc.get("dropped_spans") else "")
          + (f", {len(events)} journal event(s)" if events else ""))
    orphans = attach_events(doc["tree"], events) if events else []
    print_tree(doc["tree"])
    for ev in orphans:
        _print_event(ev, 0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
