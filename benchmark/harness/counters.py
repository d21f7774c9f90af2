"""The node's counters, read from outside: one snapshot before the window
and one after, and their difference. Sources: ``GET /_nodes/stats``
(``indices.plane_serving``), ``GET /_prometheus/metrics``
(``es_xla_compiles_total``, ``es_xla_compile_millis_total``) and
``GET /_profiler/timeline`` (one record per dispatch)."""

from __future__ import annotations

import time

from .node import Node, prom_values

STAGES = ("queue", "prep", "dispatch", "fetch")


def snapshot(node: Node) -> dict:
    stats = next(iter(node.http.ok("GET", "/_nodes/stats")
                      ["nodes"].values()))
    ps = stats["indices"]["plane_serving"]
    prom = node.http.ok("GET", "/_prometheus/metrics")
    snap = {"wall_ms": time.time() * 1e3,
            "dispatches": ps["dispatches"], "queries": ps["queries"],
            "deduped_queries": ps["deduped_queries"],
            "cache_hits": ps["cache_hit_count"],
            "rebuilds": ps["rebuilds_sync"] + ps["rebuilds_background"],
            "compiles": sum(prom_values(
                prom, "es_xla_compiles_total").values()),
            "compile_ms": sum(prom_values(
                prom, "es_xla_compile_millis_total").values())}
    for s in STAGES:
        snap[f"{s}_ms"] = ps[f"{s}_time_in_millis"]
    return snap


def delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def dispatches_since(node: Node, wall_ms: float) -> list:
    """One dict per dispatch recorded since ``wall_ms``: kernel family,
    compile-cache verdict, requests, padded batch, and the host-clock
    stage intervals (epoch ms)."""
    doc = node.http.ok(
        "GET", f"/_profiler/timeline?since={wall_ms:.3f}&limit=1000000")
    recs: dict = {}
    for ev in doc.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        a = ev["args"]
        r = recs.setdefault(a["rec"], {
            "kernel": a.get("kernel"),
            "compile_cache": a.get("compile_cache"),
            "requests": a["batch"]["requests"],
            "b_pad": a["batch"]["b_pad"], "stages": {}})
        r["stages"][ev["name"]] = (ev["ts"] / 1e3,
                                   (ev["ts"] + ev["dur"]) / 1e3)
    return [recs[k] for k in sorted(recs)]
