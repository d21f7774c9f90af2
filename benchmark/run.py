#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: set-up (seeded data written as a store, the node child
started on it, the index created so that the engine recovers the segment,
the serving plane packed by the first ``_search``, every shape of the
cell's traffic warmed), a measured window of real HTTP ``_search`` traffic
on loopback, the comparison with the configuration's plain reference, one
last JSON line. This process never imports JAX: the node child
(``harness/node_main.py``) is the only one that touches the chip. A run
that finds no TPU fails; it never falls back.

``--control 1`` (not used by the driver) also puts the reference's
lower-precision control in the engine's place on the same sampled requests
and prints its numbers beside the limits (PERF.md says how the limits were
set from the two).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from harness import (counters, loadgen, store, xplane,  # noqa: E402
                     xplane_spans)
from harness.manifest import (CHECKOUT, Cell, ManifestError,  # noqa: E402
                              load_manifest, load_module)
from harness.node import Node, NodeError               # noqa: E402

CACHE_DIR = os.path.join(HERE, ".cache")
WAVE_TRIES = 4
# a wave's timing: (seconds between its blockers, seconds from the last one
# to its group). A generator's blocker holds its dispatcher while a program
# loads (0.5 s or more): two of them, well apart. A plain request of the mix
# holds one for a lone step (11 ms on the kNN cell). A group of up to TRAIN
# requests arrives within one such step: it is sent behind a train of TRAIN
# plain requests, so close together that the device has a backlog and both
# dispatchers are held while it arrives. A larger group takes longer to
# arrive than any plain request lasts: behind two of them it splits into
# one, two and the rest, which is enough to meet its padded batch (PERF.md
# section 6, PR 28, has the counts)
SLOW_WAVE = (0.01, 0.02)
PLAIN_WAVE = (0.004, 0.004)
TRAIN_WAVE = (0.004, 0.0)
TRAIN = 4


def say(msg: str) -> None:
    print(msg, flush=True)


class RunFailure(Exception):
    """The run cannot produce a result (no chip, node down, ...)."""


class _WarmQueries:
    """The warm-up's stream of a query generator, as a loop's source."""

    def __init__(self, queries):
        self.queries = queries

    def more(self, client: int) -> list:
        return self.queries.warmup(32)


def _answer(raw: bytes):
    """What a ``_search`` response held, or None when it is not a whole
    answer (shard failures and time-outs count as failed requests)."""
    try:
        doc = json.loads(raw)
        if doc["timed_out"] or doc["_shards"]["failed"]:
            return None
        hits = doc["hits"]["hits"]
        return {"ids": [int(h["_id"]) for h in hits],
                "scores": [float(h["_score"]) for h in hits],
                "total": doc["hits"]["total"]}
    except (ValueError, KeyError, TypeError):
        return None


def _wave(node: Node, path: str, blockers: list, group: list,
          timing: tuple = (0.0, 0.0)) -> None:
    """The blockers as single requests ``timing[0]`` seconds apart (each
    taken by a dispatcher before the next arrives), then after
    ``timing[1]`` more ``group`` at once while those execute: the group
    queues up and leaves as one batch, where the blockers hold both
    dispatchers for longer than the group takes to arrive."""
    gap, lead = timing
    threads = []
    for r in blockers:
        t = threading.Thread(target=loadgen.send_group,
                             args=(node.port, path, [r]), daemon=True)
        t.start()
        threads.append(t)
        time.sleep(gap)
    time.sleep(lead)
    out = loadgen.send_group(node.port, path, group)
    for t in threads:
        t.join(900)
    bad = [r for r in out if r.error is not None or r.status != 200]
    if bad:
        r = bad[0]
        raise RunFailure(f"warm-up request failed: status {r.status} "
                         f"{r.error!r} {r.raw[:400]!r}")


def warm_up(node: Node, cell: Cell, path: str, queries, clients: int):
    """Meet every shape the cell's traffic can ask for: for each padded
    batch a closed loop of ``clients`` can produce, largest first, the
    groups its query generator names, each sent as one batch behind
    blockers and sent again while the node's timeline shows no dispatch
    at that size since the warm-up began: two of the generator's blockers
    (slow ones, for the groups that take longest to arrive) while it has
    any, then plain requests of the mix; then the cell's own traffic until
    no new program compiles and every one of those padded batches has
    been dispatched (PR 28 met a padded batch 4 for the first time inside
    a window)."""
    w = cell.traffic.get("warmup", {})
    t_warm_ms = time.time() * 1e3
    buckets, b = [], 1
    while b < clients:
        buckets.append(b)
        b *= 2
    buckets.append(b)
    for b in reversed(buckets):
        met, sent = 0, 0
        for _try in range(WAVE_TRIES):
            for group in queries.warmup_groups(min(b, clients)):
                blockers, timing = queries.blockers(2), SLOW_WAVE
                if not blockers and len(group) <= TRAIN:
                    blockers, timing = queries.warmup(TRAIN), TRAIN_WAVE
                elif not blockers:
                    blockers, timing = queries.warmup(2), PLAIN_WAVE
                _wave(node, path, blockers, group, timing)
                sent += 1
            met = [d["b_pad"] for d in counters.dispatches_since(
                node, t_warm_ms)].count(b)
            if met:
                break
        say(f"warm-up: padded batch {b}: {sent} group(s) sent, "
            f"{met} dispatch(es) at that size")
    chunk = float(w.get("chunk_seconds", 3))
    quiet_needed = int(w.get("quiet_chunks", 1))
    quiet = 0
    for i in range(int(w.get("max_chunks", 10))):
        before = counters.snapshot(node)
        loop = loadgen.Loop(node.port, path, _WarmQueries(queries),
                            clients, prefill=1)
        res = loop.run(chunk)
        d = counters.delta(before, counters.snapshot(node))
        say(f"warm-up: closed loop #{i}: {len(res['requests'])} requests "
            f"in {chunk:.0f} s + drain, {int(d['compiles'])} compile(s), "
            f"{d['compile_ms'] / 1e3:.1f} s compiling")
        quiet = quiet + 1 if d["compiles"] == 0 else 0
        unmet = sorted(set(buckets) - {
            x["b_pad"] for x in counters.dispatches_since(node, t_warm_ms)})
        if unmet:
            say(f"warm-up: no dispatch yet at padded batch {unmet}")
        elif quiet >= quiet_needed:
            break


def _dispatch_groups(requests: list, dispatches: list) -> list:
    """The window's answered requests grouped by the dispatch that carried
    them: the requests of one dispatch are answered together, so the
    requests in order of arrival of their answers are cut by the
    dispatches' request counts in order of their end."""
    reqs = sorted(requests, key=lambda r: r.received)
    order = sorted(dispatches,
                   key=lambda d: d["stages"].get("execute", (0, 0))[1])
    if sum(d["requests"] for d in order) != len(reqs):
        # boundary effects (a dispatch straddling the window's edge):
        # fall back to equal cuts at the mean batch
        n = max(len(order), 1)
        size = max(1, round(len(reqs) / n))
        return [reqs[i: i + size] for i in range(0, len(reqs), size)]
    groups, i = [], 0
    for d in order:
        groups.append(reqs[i: i + d["requests"]])
        i += d["requests"]
    return [g for g in groups if g]


def _read_trace(ctx: dict, trace_dir: str, marks: dict):
    """(trace summary, device busy/window seconds, breakdown) from the
    profiler's files, read by helper processes that hold no chip."""
    pb = xplane.find_xplane(trace_dir)
    if pb is None:
        say("trace: the profiler left no xplane file")
        return None, {}, None
    out = os.path.join(trace_dir, "summary.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "harness", "xplane.py"), pb,
         out], env=env, capture_output=True, text=True, timeout=300)
    if proc.returncode != 0:
        say(f"trace: reading {pb} failed: {proc.stderr[-800:]}")
        return None, {}, None
    with open(out) as f:
        trace = json.load(f)
    say(f"trace: {os.path.getsize(pb)} bytes of xplane read in "
        f"{time.perf_counter() - t0:.1f} s; device planes "
        f"{[(d['plane'], d['lines']) for d in trace['devices']]}")
    window_s = marks["stop_wall_s"] - marks["ready_wall_s"]
    devs = trace["devices"]
    if not devs:
        say("trace: no device plane with events (not a chip run?)")
        return trace, {"window_s": window_s}, None
    busy_s = sum(d["busy_s"] for d in devs) / len(devs)
    top = xplane.top_module(trace)
    if top:
        say(f"trace: kernel metrics matched XLA module {top[0]!r}: "
            f"{top[1]:.0f} executions, {top[2]:.4f} s on the device")
    # idle gaps by the dispatcher span open in them: the program's spans
    # are in the same file, on the device's clock
    d0 = devs[0]
    spans = xplane_spans.load(ctx)
    if spans is not None:
        idle = spans["idle_gaps"][:10]
    else:
        idle = [["unattributed",
                 (d0["last_ns"] - d0["first_ns"]) / 1e9 - d0["busy_s"]]]
    breakdown = {"device_ops": [[n, s] for n, s in d0["ops"]],
                 "idle_gaps": idle}
    return trace, {"busy_s": busy_s, "window_s": window_s}, breakdown


def run(args, *, rehearsal: bool = False, node_launcher: str | None = None,
        manifest: dict | None = None) -> dict:
    """One run; returns the result line's object. ``rehearsal`` (tests
    only) lets the node come up on the CPU platform."""
    t_start = time.perf_counter()
    if not os.path.isdir(os.path.join(CHECKOUT, "elasticsearch_tpu")):
        raise RunFailure(f"no elasticsearch_tpu package under {CHECKOUT}: "
                         f"the benchmark measures the node, it has none")
    cell = Cell(manifest or load_manifest(), args.workload)
    cfg, traffic = cell.config, cell.traffic
    index = cfg["index"]["name"]
    path = f"/{index}/_search"
    clients = int(traffic["clients"])
    if traffic["loop"] != "closed":
        raise RunFailure(f"loop kind {traffic['loop']!r} is not built")
    say(f"cell {cell.name}: configuration {cell.config_name}, traffic "
        f"{cell.traffic_name} ({clients} clients, {traffic['loop']} loop), seed "
        f"{args.seed}, window {args.seconds} s, trace {args.trace}")
    env = {k: v["value"] for k, v in cfg["assumed"]["env"].items()}
    for k, v in cfg["assumed"]["env"].items():
        say(f"node setting {k}={v['value']}: {v['why']}")
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    work_dir = os.path.join(CACHE_DIR, "run", cell.name)
    shutil.rmtree(work_dir, ignore_errors=True)
    data_dir = os.path.join(work_dir, "data")
    trace_dir = os.path.join(work_dir, "trace")
    os.makedirs(data_dir)
    split = {}
    node = Node(data_dir, os.path.join(work_dir, "node.log"), env,
                launcher=node_launcher)
    try:
        # -- set-up: data (while the node child imports and finds its chip)
        t0 = time.perf_counter()
        gen = load_module("generators", cfg["data"]["generator"])
        data = gen.make(cfg["data"]["params"], args.seed)
        t1 = time.perf_counter()
        written = store.write_index_store(
            data_dir, index, cfg["index"]["mappings"], data["n_docs"],
            data["text_fields"], data["vector_fields"])
        qgen = load_module("generators", traffic["queries"]["generator"])
        queries = qgen.Queries(traffic["queries"]["params"], data,
                               args.seed, clients)
        loop = loadgen.Loop(node.port, path, queries, clients,
                            int(traffic.get("prefill_per_client", 1)))
        split["data_s"] = time.perf_counter() - t0
        say(f"set-up: data made in {t1 - t0:.1f} s, store of {written} "
            f"bytes written and requests prepared in "
            f"{time.perf_counter() - t1:.1f} s")
        # -- node start + recovery
        t0 = time.perf_counter()
        node.wait_up()
        device = node.device()
        cache_line = next((ln.strip() for ln in
                           node.log_tail(100000).splitlines()
                           if "compile cache" in ln), "")
        say(f"node: platform {device['platform']}, device_kind "
            f"{device['kind']!r}, {device['count']} device(s); {cache_line}")
        if device["platform"] != "tpu" and not rehearsal:
            raise RunFailure(f"no TPU: the node's JAX reports platform "
                             f"{device['platform']!r}")
        if device["count"] < cell.chips:
            raise RunFailure(f"the cell asks for {cell.chips} chip(s), the "
                             f"node sees {device['count']}")
        settings = cfg["assumed"].get("cluster_settings", {})
        if settings:
            for k, v in settings.items():
                say(f"cluster setting {k}={v['value']}: {v['why']}")
            node.http.ok("PUT", "/_cluster/settings", {"persistent": {
                k: v["value"] for k, v in settings.items()}})
        node.http.ok("PUT", f"/{index}",
                     {"settings": cfg["index"]["settings"],
                      "mappings": cfg["index"]["mappings"]})
        count = node.http.ok("GET", f"/{index}/_count")["count"]
        if count != data["n_docs"]:
            raise RunFailure(f"the engine recovered {count} documents, "
                             f"the store holds {data['n_docs']}")
        split["node_start_recovery_s"] = time.perf_counter() - t0
        # -- plane pack + first compile: the first _search, alone (64
        # first requests at once each pack a plane of their own: the node
        # ran out of the host's 40 GiB, PERF.md)
        t0 = time.perf_counter()
        c0 = counters.snapshot(node)
        _wave(node, path, [], queries.warmup(1))
        c1 = counters.snapshot(node)
        first_s = time.perf_counter() - t0
        first_compile_s = (c1["compile_ms"] - c0["compile_ms"]) / 1e3
        split["plane_pack_s"] = max(first_s - first_compile_s, 0.0)
        # -- warm-up
        t0 = time.perf_counter()
        warm_up(node, cell, path, queries, clients)
        c2 = counters.snapshot(node)
        split["compile_or_cache_load_s"] = \
            (c2["compile_ms"] - c0["compile_ms"]) / 1e3
        split["warmup_s"] = max(time.perf_counter() - t0
                                - (c2["compile_ms"] - c1["compile_ms"])
                                / 1e3, 0.0)
        setup_s = time.perf_counter() - t_start
        say("set-up: " + ", ".join(f"{k} {v:.1f}" for k, v in split.items())
            + f"; {int(c2['compiles'])} programs compiled or loaded; "
              f"setup_s {setup_s:.1f} (data overlaps the node's start)")
        # -- the window
        window_s = float(args.seconds)
        if args.trace:
            window_s = min(window_s, float(traffic.get("trace_seconds",
                                                       window_s)))
            marks = node.control(f"/trace/start?dir={trace_dir}")
        before = counters.snapshot(node)
        res = loop.run(window_s)
        if args.trace:
            marks.update(node.control("/trace/stop"))
        after = counters.snapshot(node)
        dispatches = counters.dispatches_since(node, res["wall0"] * 1e3)
        device = node.device()
    except NodeError as e:
        raise RunFailure(f"{e}\nnode log tail:\n{node.log_tail()}") from e
    finally:
        node.stop()
        # the store is hundreds of MB: gone whatever the outcome (a failed
        # run keeps its node log)
        shutil.rmtree(data_dir, ignore_errors=True)
    # -- the node is gone and the chip is free: reduce, then compare
    delta = counters.delta(before, after)
    reqs = res["requests"]
    ok = [r for r in reqs if r.error is None and r.status == 200]
    answers = {id(r): _answer(r.raw) for r in ok}
    ok = [r for r in ok if answers[id(r)] is not None]
    failed = len(reqs) - len(ok) + res["hung_clients"]
    if not ok:
        raise RunFailure(f"no request of the window was answered "
                         f"({len(reqs)} sent); first: "
                         f"{reqs[0].status if reqs else None} "
                         f"{reqs[0].raw[:300] if reqs else b''!r}")
    # what every metric's reader sees: the answered requests over the time
    # from the first send to the last answer, their latencies ascending
    lat = sorted((r.received - r.sent) * 1e3 for r in ok)
    span = max(r.received for r in ok) - min(r.sent for r in reqs)
    ctx = {"cell": cell, "config": cfg, "traffic": traffic,
           "counters": delta, "latencies_ms": lat, "requests": ok,
           "span_s": span, "setup_s": setup_s, "dispatches": dispatches,
           "device": device, "data": data, "say": say}
    gaps = res["gaps"]
    say(f"window: {len(reqs)} requests sent, {len(ok)} answered, "
        f"{failed} failed, over {span:.3f} s; generator overhead (reply "
        f"to next send) mean {statistics.fmean(gaps) * 1e6 if gaps else 0:.0f}"
        f" us, max {max(gaps) * 1e3 if gaps else 0:.2f} ms")
    p50 = loadgen.percentile(lat, 0.5)
    say("window: latency ms " + ", ".join(
        f"p{round(q * 100)} {loadgen.percentile(lat, q):.1f}"
        for q in (0.5, 0.9, 0.95, 0.99)) + f", max {lat[-1]:.1f}; "
        f"{sum(v > 2 * p50 for v in lat)} of {len(lat)} over twice the "
        f"median")
    routes: dict = {}
    for d in dispatches:
        key = f"{d['kernel']}/B{d['b_pad']}/{d['compile_cache']}"
        routes[key] = routes.get(key, 0) + 1
    say(f"window: {len(dispatches)} dispatches by route (kernel/padded "
        f"batch/compile cache): {json.dumps(routes, sort_keys=True)}")
    host = sum(1 for d in dispatches
               if d["compile_cache"] not in ("hit", "miss"))
    say(f"window: counters {json.dumps(delta, sort_keys=True)}")

    breakdown = None
    if args.trace:
        ctx["dispatch_groups"] = _dispatch_groups(ok, dispatches)
        ctx["trace"], dev_times, breakdown = _read_trace(ctx, trace_dir,
                                                         marks)
        device.update(dev_times)
    # -- correctness: a seeded sample of the window's answers, the slowest
    # request among them, against the plain reference
    ref_mod = load_module("references", cfg["reference"]["name"])
    ref = ref_mod.Reference(cfg, data)
    rng = np.random.default_rng([int(args.seed), 9])
    n_sample = min(int(cfg["reference"]["sample"]), len(ok))
    picked = set(rng.choice(len(ok), n_sample, replace=False).tolist())
    picked.add(max(range(len(ok)),
                   key=lambda i: ok[i].received - ok[i].sent))
    sample = [ok[i] for i in sorted(picked)]
    t0 = time.perf_counter()
    got = ref.compare([r.qrec for r in sample],
                      [answers[id(r)] for r in sample])
    ref_s = time.perf_counter() - t0
    limits = cfg["reference"]["limits"]
    compared = {k: {"value": v, "limit": limits[k]} for k, v in got.items()}
    compared["host_dispatches"] = {"value": host, "limit": 0}
    compared["deduped_queries"] = {"value": delta["deduped_queries"],
                                   "limit": 0}
    compared["cache_served"] = {"value": delta["cache_hits"], "limit": 0}
    compared["unanswered"] = {"value": res["hung_clients"], "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    say(f"reference: {len(sample)} answers compared in {ref_s:.1f} s"
        + (f"; parts {json.dumps(ref.parts)}" if hasattr(ref, "parts")
           else ""))
    control = None
    if args.control:
        t0 = time.perf_counter()
        qrecs = [r.qrec for r in sample]
        cgot = ref.compare(qrecs, ref.control(qrecs))
        if hasattr(ref, "parts"):
            say(f"control: parts {json.dumps(ref.parts)}")
        control = {k: {"value": v, "limit": limits[k],
                       "fails": v > limits[k]} for k, v in cgot.items()}
        say(f"control ({time.perf_counter() - t0:.1f} s): "
            f"{json.dumps(control)}")

    # every metric is read by the reader its descriptor names: the
    # untraced line carries the end-to-end ones, the traced line the layers'
    metrics = {}
    for m in cell.per_layer() if args.trace else cell.end_to_end():
        v = load_module("readers", m["reader"]).read(ctx, m["params"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": len(reqs),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control is not None:
        result["control"] = control
    result["compared"] = compared
    shutil.rmtree(work_dir, ignore_errors=True)
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, **kw) -> int:
    args = parse_args(argv)
    try:
        result = run(args, **kw)
    except (RunFailure, ManifestError) as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr, flush=True)
        return 3
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})"
              f"{'' if c['value'] <= c['limit'] else '  <-- over'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
