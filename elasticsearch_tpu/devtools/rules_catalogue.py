"""Rule family 3 — telemetry-catalogue discipline (ESTP-C*).

Generalizes the old ``scripts/telemetry_lint.py`` (which survives as a
thin shim): registry families, TELEMETRY.md rows, and health-indicator
diagnoses must stay THREE-way consistent, so an operator paging through
a diagnosis ("watch ``es_plane_rebuild_total{mode="sync"}``") always
lands on a documented, actually-registered family.

- **ESTP-C01 undocumented-runtime-family** — a family the live engine
  registers (driven by the miniature real-stack workload below) has no
  TELEMETRY.md row.
- **ESTP-C02 stale-documented-family** — a documented family that the
  workload cannot produce and the CONDITIONAL allowlist cannot explain.
- **ESTP-C03 unknown-family-in-diagnosis** — an ``es_*`` token in
  ``common/health.py`` (indicator details, impacts, diagnosis prose)
  that TELEMETRY.md does not document: the health report would point
  operators at a metric that does not exist.

C01/C02 need a live registry (the workload imports jax and serves real
dispatches) — they run when ``runtime=True`` (the CLI default and the
tier-1 gate) and are skipped in pure-AST scans. C03 is static and
always runs.
"""

from __future__ import annotations

import json
import os
import re
import sys
import tempfile
from typing import List, Optional, Set

from .analyzer import Finding, Project

RULE_C01 = "ESTP-C01"
RULE_C02 = "ESTP-C02"
RULE_C03 = "ESTP-C03"

#: documented families the lint workload cannot produce, with the reason
#: they are still correct documentation
CONDITIONAL = {
    # registered only on cluster fronts (ARS EWMAs need peers)
    "es_adaptive_selection_response_seconds":
        "cluster fronts only (adaptive replica selection)",
    # cluster failover/recovery families: written by the multi-node
    # search fan-out, the master's failover update, and the
    # recovery:plane_* warm-handoff transfer — none of which exist in
    # the single-process lint workload (tests/test_chaos_failover.py
    # and tests/test_plane_handoff.py exercise them on real clusters)
    "es_search_retries_total":
        "cluster coordinators only (search copy failover)",
    "es_shard_failovers_total":
        "cluster masters only (dead-node primary promotion)",
    "es_recovery_bytes_total":
        "cluster recovery only (plane handoff / translog replay)",
    "es_plane_handoff_ms":
        "cluster recovery only (warm plane handoff import)",
    # the collector's hook is installed by the process's owner
    # (common/heap.arm, called by cli/node.main alone): the lint workload
    # is a library user, whose heap is left alone
    # (tests/test_heap_settle.py drives them, in-process and on a node)
    "es_gc_collections_total": "node processes only (heap.arm)",
    "es_gc_pause_millis_total": "node processes only (heap.arm)",
    "es_gc_settles_total": "node processes only (heap.arm)",
    "es_gc_frozen_objects": "node processes only (heap.arm)",
}

_DOC_NAME_RE = re.compile(r"`(es_[a-z0-9_]+)`")
_REF_NAME_RE = re.compile(r"\bes_[a-z0-9_]+")

HEALTH_MODULE = re.compile(r"(^|\.)common\.health$")


def documented_families(path: str) -> Set[str]:
    """Every backticked ``es_*`` family name in TELEMETRY.md."""
    if not os.path.exists(path):
        return set()
    with open(path) as f:
        return set(_DOC_NAME_RE.findall(f.read()))


def runtime_families() -> Set[str]:
    """Register every producible family by exercising the real stack:
    REST + index + text/kNN plane dispatch + delta tier + sync repack +
    forced jitted dispatch + IVF tier + block-max tier + a lockdep
    witness pair (so the ``es_lockdep_*`` families land in the registry
    the same deterministic way)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from elasticsearch_tpu.common import lockdep, telemetry
    from elasticsearch_tpu.node.indices_service import IndicesService
    from elasticsearch_tpu.rest.api import RestAPI

    with tempfile.TemporaryDirectory() as d:
        api = RestAPI(IndicesService(d))
        api.handle("PUT", "/lint", "", json.dumps(
            {"mappings": {"properties": {
                "body": {"type": "text"},
                "tag": {"type": "keyword"},
                "price": {"type": "double"},
                "vec": {"type": "dense_vector", "dims": 4}}}}).encode())
        api.handle("PUT", "/lint/_doc/1", "refresh=true", json.dumps(
            {"body": "quick brown fox", "tag": "a", "price": 3.0,
             "vec": [1, 0, 0, 0]}).encode())
        # text plane dispatch (+ latency family with exemplar); the
        # X-Opaque-Id header registers the per-tenant es_tenant_*
        # attribution rollup the same deterministic way
        api.handle("POST", "/lint/_search", "", json.dumps(
            {"query": {"match": {"body": "quick"}}}).encode(),
            headers={"X-Opaque-Id": "lint-tenant"})
        # plane-path request cache hit/miss counters
        api.handle("POST", "/lint/_search", "", json.dumps(
            {"query": {"match": {"body": "quick"}}}).encode())
        # kNN plane dispatch
        api.handle("POST", "/lint/_search", "", json.dumps(
            {"knn": {"field": "vec", "query_vector": [1, 0, 0, 0],
                     "k": 1, "num_candidates": 5}}).encode())
        # fused one-dispatch planner: a lowerable hybrid RRF body runs
        # lexical + knn + fusion as ONE dispatch and registers the
        # es_planner_* families (lowered counter + stage histogram)
        api.handle("POST", "/lint/_search", "", json.dumps(
            {"query": {"match": {"body": "quick"}},
             "knn": {"field": "vec", "query_vector": [1, 0, 0, 0],
                     "k": 1, "num_candidates": 5},
             "rank": {"rrf": {"rank_window_size": 5}}}).encode())
        # fused AGG stages: an agg-carrying lowerable body rides the
        # same planner dispatch and registers the es_agg_* families
        # (stage histogram + sketch-merge kinds); DEVICE_MIN_PAIRS is
        # shrunk for the call so the device kernel call sites register
        # es_agg_device_pairs_total on this one-doc corpus too
        from elasticsearch_tpu.ops import aggs as _ops_aggs
        _mp = _ops_aggs.DEVICE_MIN_PAIRS
        _ops_aggs.DEVICE_MIN_PAIRS = 1
        try:
            api.handle("POST", "/lint/_search", "request_cache=false",
                       json.dumps(
                           {"query": {"match": {"body": "quick"}},
                            "size": 0, "aggs": {
                                "tags": {"terms": {"field": "tag"}},
                                "n": {"cardinality": {
                                    "field": "price"}}}}).encode())
        finally:
            _ops_aggs.DEVICE_MIN_PAIRS = _mp
        # delta tier + sync repack path (delta-serve + rebuild families)
        svc = api.indices.get("lint")
        svc.plane_cache.repack_mode = "sync"
        # force the block-max tier onto the repacked generation so the
        # es_lex_* families register: a pruned dispatch (track_total_hits
        # bounded → prune defaults on) and an explicit prune=off (the
        # drift counter the plane_serving health indicator reads)
        svc.plane_cache.lex_prune_min_docs = 1
        api.handle("PUT", "/lint/_doc/2", "refresh=true", json.dumps(
            {"body": "quick red fox"}).encode())
        api.handle("POST", "/lint/_search", "", json.dumps(
            {"query": {"match": {"body": "quick"}}}).encode())
        # second delta doc pushes past REPACK_DELTA_FRACTION: the sync
        # repack folds the delta into a fresh base that now carries the
        # block-max tier (lex_prune_min_docs=1 above)
        api.handle("PUT", "/lint/_doc/3", "refresh=true", json.dumps(
            {"body": "quick blue fox"}).encode())
        api.handle("POST", "/lint/_search", "request_cache=false",
                   json.dumps({"query": {"match": {"body": "quick"}},
                               "track_total_hits": 10}).encode())
        api.handle("POST", "/lint/_search", "request_cache=false",
                   json.dumps({"query": {"match": {"body": "quick"}},
                               "prune": False}).encode())
        # storage-tier cycle: demote the live text generation to warm
        # and promote it straight back — one round trip registers the
        # es_plane_tier_{promotions,demotions}_total counters (full
        # label space is pre-created on first transition) while the
        # es_plane_tier_bytes gauge rides the tier manager's object
        # collector
        _tgen = svc.plane_cache.generations()[0]
        svc.plane_cache.tiers.demote_to_warm(_tgen, reason="lint")
        svc.plane_cache.tiers._promote(_tgen)
        # forced jitted dispatch so the XLA compile/transfer families
        # register even on the CPU test backend (host-eager otherwise)
        import numpy as np
        from elasticsearch_tpu.parallel import (DistributedSearchPlane,
                                                make_search_mesh)
        from elasticsearch_tpu.utils.synth import synthetic_csr_corpus_fast
        import jax
        rng = np.random.RandomState(7)
        corpus = synthetic_csr_corpus_fast(rng, 128, 64, 8, zipf_s=1.2)
        corpus["term_ids"] = {f"t{t}": t for t in range(64)}
        mesh = make_search_mesh(n_shards=1, n_replicas=1,
                                devices=jax.devices()[:1])
        # register the serving-owner gauge family for the catalogue
        # cross-check (make_search_mesh itself deliberately doesn't
        # write it — only serving-mesh owners do)
        from elasticsearch_tpu.parallel.mesh import record_mesh_devices
        record_mesh_devices(1, 0)
        plane = DistributedSearchPlane(mesh, [corpus], field="body")
        plane._host_csr = None
        plane.serve([["t1"]], k=4, with_totals=True)
        # warm-tier streamed dispatch: demote the jitted plane's corpus
        # to host and re-serve — the per-dispatch device_put stream
        # registers es_plane_tier_stream_bytes_total and the *_streamed
        # roofline kernel family
        plane.demote_to_warm()
        plane.serve([["t1"]], k=4, with_totals=True)
        # IVF (cluster-pruned ANN) dispatch: registers the es_ann_*
        # families (clusters probed / candidates re-ranked / bytes per
        # tier), plus the nprobe-below-default drift counter the
        # plane_serving health indicator reads
        from elasticsearch_tpu.parallel.dist_search import \
            DistributedKnnPlane
        kvecs = rng.randn(256, 8).astype(np.float32)
        kplane = DistributedKnnPlane(
            mesh, [dict(vectors=kvecs)], similarity="cosine",
            ivf=dict(nlist=8, seed=0))
        kplane.serve(np.zeros((2, 8), np.float32), k=3)
        kplane.serve(np.zeros((1, 8), np.float32), k=3, nprobe=1)
        # lockdep witness: a nested acquisition through two witnessed
        # locks registers the es_lockdep_* families (depth, hold time,
        # inversions) without needing ES_TPU_LOCKDEP in the environment
        outer = lockdep.witness_lock("lint-outer")
        inner = lockdep.witness_lock("lint-inner")
        with outer:
            with inner:
                pass
        # racedep witness: register the es_racedep_* evidence families
        # the same deterministic way — collector + one tracked access
        # pair (single-threaded: records evidence, never a candidate)
        from elasticsearch_tpu.common import racedep
        racedep.ensure_collector()
        racedep.WITNESS.access(("lint-race-key", 0), write=True)
        racedep.WITNESS.access(("lint-race-key", 0), write=False)
        # flight recorder + SLO watchdog: the searches above already
        # journaled events (plane rebuilds); a thread-less watchdog
        # instance ticks once (burn gauges + capture counter label
        # space) and seeds one manual capture so es_flightrec_* /
        # es_watchdog_* / es_slo_burn_rate register deterministically
        from elasticsearch_tpu.common import flightrec
        flightrec.record("lint_probe", source="telemetry-lint")
        wd = flightrec.Watchdog()
        wd.tick()
        wd.capture("manual")
        wd.close()
        # continuous-profiler round: a thread-less sampler drives one
        # sampled window synchronously (es_contprof_* families register
        # deterministically — no cadence race) and the endpoint read
        # exercises the REST surface the same way as insights below
        from elasticsearch_tpu.common import contprof
        prof = contprof.ContinuousProfiler(interval_ms_=1.0)
        prof.sample_once()
        prof.sample_once()
        prof.top_doc(window="both")
        api.handle("GET", "/_profiler/flamegraph",
                   "window=both&limit=8", None)
        # query-insights round: the searches above already folded into
        # the heavy-hitter store (es_insight_* families); read both new
        # observability endpoints so the whole insight surface — store,
        # history ring (fed by the watchdog tick above:
        # es_history_samples_total / es_history_series), REST layer —
        # runs under the lint the same deterministic way
        api.handle("GET", "/_insights/top_queries",
                   "metric=device_ms", None)
        api.handle("GET", "/_telemetry/history",
                   "family=es_query_latency_ms&window=raw&rate=true",
                   None)
        # multi-tenant QoS round: the searches above were all ADMITTED
        # (es_qos_admitted_total / es_qos_tokens); drive both rejection
        # paths too — charge the lint tenant into token debt so its
        # next request throttles 429, then trip the shed state machine
        # so an analytics-class request sheds 429 — and reset the
        # process controller so the synthetic debt/engagement cannot
        # leak into other suites sharing this process
        from elasticsearch_tpu.common import qos as _qos
        ctl = _qos.controller()
        ctl.charge("lint-tenant", cpu_ms=0.0, device_ms=1e9, bytes_=0)
        api.handle("POST", "/lint/_search", "", json.dumps(
            {"query": {"match": {"body": "quick"}}}).encode(),
            headers={"X-Opaque-Id": "lint-tenant"})
        ctl.note_signals(queue_depth=10 ** 6, burn_status="red",
                         breaker_fraction=1.0)
        api.handle("POST", "/lint/_search", "", json.dumps(
            {"query": {"match": {"body": "quick"}},
             "size": 0}).encode(),
            headers={"X-Opaque-Id": "lint-shed-tenant"})
        _qos.reset_controller()

        snap = telemetry.DEFAULT.stats_doc()
        return {name for name in snap if name.startswith("es_")}


def referenced_families(project: Project):
    """(family, file, line) for every ``es_*`` token in a string literal
    of ``common/health.py`` — indicator details and diagnosis prose."""
    import ast
    out = []
    for mod in project.modules.values():
        if not HEALTH_MODULE.search(mod.dotted):
            continue
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str):
                for name in _REF_NAME_RE.findall(node.value):
                    out.append((name, mod.relpath, node.lineno))
    return out


def catalogue_drift(documented: Set[str], runtime_set: Set[str]):
    """The three-way comparison both the estpulint gate and the
    telemetry_lint shim render: (undocumented, stale, phantom) — one
    copy of the semantics so the two entry points can never diverge."""
    undocumented = sorted(runtime_set - documented)
    stale = sorted(documented - runtime_set - set(CONDITIONAL))
    phantom = sorted(set(CONDITIONAL) & runtime_set)
    return undocumented, stale, phantom


def check(project: Project, runtime: bool = True,
          telemetry_md: Optional[str] = None) -> List[Finding]:
    md_path = telemetry_md or os.path.join(project.root, "TELEMETRY.md")
    documented = documented_families(md_path)
    findings: List[Finding] = []
    md_rel = os.path.relpath(md_path, project.root)
    if runtime:
        undocumented, stale, _phantom = catalogue_drift(
            documented, runtime_families())
        for name in undocumented:
            findings.append(Finding(
                RULE_C01, md_rel, 0, "catalogue", f"undocumented {name}",
                f"runtime-registered family [{name}] has no TELEMETRY.md "
                f"row — add one (name, type, labels, meaning)"))
        for name in stale:
            findings.append(Finding(
                RULE_C02, md_rel, 0, "catalogue", f"stale {name}",
                f"documented family [{name}] is never registered by the "
                f"lint workload — remove the row or add a CONDITIONAL "
                f"entry with a reason"))
    seen: Set[str] = set()
    for name, relpath, line in referenced_families(project):
        if name in documented or name in seen:
            continue
        seen.add(name)
        findings.append(Finding(
            RULE_C03, relpath, line, "health-indicators",
            f"unknown family {name}",
            f"health-indicator text references [{name}], which "
            f"TELEMETRY.md does not document — operators would be "
            f"pointed at a metric that does not exist"))
    return findings


def main(repo_root: Optional[str] = None) -> int:
    """The ``scripts/telemetry_lint.py`` entry: same output contract as
    the original standalone lint (UNDOCUMENTED / STALE / note lines,
    rc 1 on drift)."""
    # .../repo/elasticsearch_tpu/devtools/rules_catalogue.py -> repo
    root = repo_root or os.path.abspath(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", ".."))
    documented = documented_families(os.path.join(root, "TELEMETRY.md"))
    runtime = runtime_families()
    undocumented, stale, phantom = catalogue_drift(documented, runtime)
    rc = 0
    if undocumented:
        rc = 1
        print("UNDOCUMENTED runtime families (add TELEMETRY.md rows):",
              file=sys.stderr)
        for n in undocumented:
            print(f"  {n}", file=sys.stderr)
    if stale:
        rc = 1
        print("STALE documented families (never registered by the lint "
              "workload; remove the row or add a CONDITIONAL entry with "
              "a reason):", file=sys.stderr)
        for n in stale:
            print(f"  {n}", file=sys.stderr)
    if phantom:
        # informational only: the process-scoped registry may carry
        # families from OTHER stacks in this process (a cluster test
        # that ran earlier in the same pytest session) — documented +
        # registered is never drift
        print("note: CONDITIONAL families present in this process: "
              + ", ".join(phantom))
    if rc == 0:
        print(f"telemetry lint OK: {len(runtime)} runtime families "
              f"match TELEMETRY.md ({len(CONDITIONAL)} conditional)")
    return rc
