"""Cluster health report: the 8.x indicator API over the telemetry layer.

Reference: ``GET /_health_report`` (``health/HealthService.java`` +
one ``HealthIndicatorService`` per concern) — each indicator evaluates
live node state into ``green``/``yellow``/``red`` with a human
``symptom``, machine ``details``, and, when degraded, reference-shaped
``impacts`` (what stops working) and ``diagnosis`` (cause → action).
The top-level ``status`` is the worst indicator.

The TPU-native indicators are registry-driven — they read the SAME
counters ``/_prometheus/metrics`` exposes, so an alert and the health
report can never disagree:

- ``shards_availability`` — unassigned/active shard counts (the cluster
  front recomputes this from the published routing table, where ``red``
  is reachable; the single-node view caps at ``yellow``).
- ``plane_serving`` — synchronous request-thread plane rebuilds beyond
  the cold builds. Per TELEMETRY.md, ``es_plane_rebuild_total{mode=
  "sync"}`` rising past the cold count is the rebuild-storm signature
  (every refresh repacking the serving plane on request threads).
- ``compile_churn`` — steady-state XLA compiles: compiles recorded past
  what the warmup lattice pre-compiled mean first-hit compiles are
  landing mid-traffic (the multi-second p99 signature). Windowed per
  evaluator since the previous health evaluation (the compile counter
  is process-cumulative while warmed credits die with retired
  batchers; judging all of process history against live batchers only
  would accumulate phantom excess).
- ``breakers`` — circuit-breaker trips (parent trip → red).
- ``indexing_pressure`` — 429 rejections + current bytes vs the budget.
- ``task_backlog`` — live registered tasks and the oldest task's age.

Evaluation is snapshot-time only (never on a request path) and each
indicator is fail-safe: an indicator that throws reports itself
``unknown`` instead of failing the endpoint.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

GREEN, YELLOW, RED, UNKNOWN = "green", "yellow", "red", "unknown"

#: guards the ANN-drift watermark's read-modify-write (concurrent
#: health pollers must not double-count or swallow a drift window)
_ANN_DRIFT_LOCK = threading.Lock()

_RANK = {GREEN: 0, UNKNOWN: 1, YELLOW: 2, RED: 3}


def worst_status(statuses) -> str:
    return max(statuses, key=lambda s: _RANK.get(s, 1), default=GREEN)


def _impact(id_: str, severity: int, description: str,
            areas: List[str]) -> dict:
    return {"id": id_, "severity": severity, "description": description,
            "impact_areas": areas}


def _diagnosis(id_: str, cause: str, action: str,
               affected: Optional[dict] = None) -> dict:
    return {"id": id_, "cause": cause, "action": action,
            "help_url": "TELEMETRY.md",
            "affected_resources": affected or {}}


class HealthService:
    """Evaluates every indicator against one node's live surfaces.

    ``api`` is the node's ``RestAPI`` (indices, task manager, plane
    caches); the process telemetry registry and breaker/pressure
    singletons are read directly."""

    INDICATORS = ("shards_availability", "plane_serving", "plane_tiers",
                  "compile_churn", "breakers", "indexing_pressure",
                  "task_backlog", "slo_burn", "dispatch_efficiency",
                  "query_insights", "qos")

    #: sync non-cold rebuilds: first one turns yellow, a storm turns red
    SYNC_REBUILD_YELLOW = 1
    SYNC_REBUILD_RED = 8
    #: steady-state compiles past the warmed lattice before degrading
    COMPILE_SLACK = 4
    COMPILE_RED = 64
    #: live-task backlog thresholds
    BACKLOG_YELLOW = 64
    BACKLOG_RED = 512
    OLDEST_TASK_YELLOW_S = 60.0
    OLDEST_TASK_RED_S = 300.0
    #: indexing-pressure utilization fraction that reads as saturation
    PRESSURE_YELLOW_FRACTION = 0.8

    def __init__(self, api):
        self.api = api

    # -- entry ---------------------------------------------------------------

    def report(self, indicator: Optional[str] = None,
               verbose: bool = True) -> dict:
        from .errors import ResourceNotFoundError
        names = self.INDICATORS
        if indicator is not None:
            if indicator not in self.INDICATORS:
                raise ResourceNotFoundError(
                    f"health indicator [{indicator}] does not exist; "
                    f"known indicators are {sorted(self.INDICATORS)}")
            names = (indicator,)
        indicators: Dict[str, dict] = {}
        for name in names:
            try:
                doc = getattr(self, f"_ind_{name}")()
            except Exception as e:   # noqa: BLE001 — one broken indicator
                doc = {"status": UNKNOWN,          # must not fail the API
                       "symptom": f"indicator evaluation failed: {e}"}
            if not verbose:
                doc = {k: v for k, v in doc.items()
                       if k in ("status", "symptom")}
            indicators[name] = doc
        return {
            "status": worst_status(d["status"]
                                   for d in indicators.values()),
            "cluster_name": self.api.cluster_name,
            "indicators": indicators,
        }

    # -- indicators ----------------------------------------------------------

    def _ind_shards_availability(self) -> dict:
        h = self.api._health()
        unassigned = int(h.get("unassigned_shards", 0))
        active = int(h.get("active_shards", 0))
        status = {"green": GREEN, "yellow": YELLOW,
                  "red": RED}.get(h.get("status"), UNKNOWN)
        doc = {
            "status": status,
            "symptom": ("This cluster has all shards available."
                        if status == GREEN else
                        f"This cluster has {unassigned} unassigned "
                        f"shard{'s' if unassigned != 1 else ''}."),
            "details": {"active_shards": active,
                        "unassigned_shards": unassigned,
                        "active_primary_shards":
                            int(h.get("active_primary_shards", 0))},
        }
        if status != GREEN:
            doc["impacts"] = [_impact(
                "shards_availability:degraded", 2,
                "Searches may return partial results and writes may be "
                "rejected for unassigned shards.", ["search", "ingest"])]
            doc["diagnosis"] = [_diagnosis(
                "shards_availability:unassigned",
                f"{unassigned} shard copies are not assigned to any "
                f"live node (replica count exceeds allocatable nodes, "
                f"or owning nodes left the cluster).",
                "Add data nodes, lower index.number_of_replicas, or "
                "POST /_cluster/reroute?retry_failed=true.")]
        return doc

    def _ind_plane_serving(self) -> dict:
        sync = cold = background = 0
        delta_serves = 0
        per_index: Dict[str, int] = {}
        for name, svc in list(self.api.indices.indices.items()):
            try:
                rb = svc.plane_cache.rebuild_stats()
            except Exception:   # noqa: BLE001 — no plane cache: skip
                continue
            sync += rb.get("sync", 0)
            cold += rb.get("cold", 0)
            background += rb.get("background", 0)
            delta_serves += rb.get("delta_serves", 0)
            storm_i = rb.get("sync", 0) - rb.get("cold", 0)
            if storm_i > 0:
                per_index[name] = storm_i
        # every cold build is mode="sync"; a sync count past the cold
        # count means NON-cold repacks ran on request threads — the
        # rebuild-storm signature (TELEMETRY.md es_plane_rebuild_total)
        storm = max(sync - cold, 0)
        # ANN recall-config drift: dispatches served with nprobe BELOW
        # the benched default (TELEMETRY.md
        # es_ann_nprobe_below_default_total) — the knn_ivf_recall bench
        # certifies recall@k at the default; lowering nprobe trades
        # recall silently, which is a health concern, not an error.
        # Windowed against the previous health evaluation (watermark on
        # the api object): the counter is cumulative and would latch
        # yellow forever, making its own remediation ("drop the
        # override") unverifiable — yellow means drift SINCE last check.
        # The evaluation CONSUMES the window (first poller wins);
        # rate()-style monitors should read the cumulative
        # ann_below_default_total in details instead.
        from . import telemetry as _tm
        with _ANN_DRIFT_LOCK:
            ann_total = _tm.ann_drift_count()
            seen = getattr(self.api, "_ann_drift_seen", 0)
            ann_drift = max(ann_total - seen, 0)
            self.api._ann_drift_seen = ann_total
            # lexical pruning drift, windowed the same way: requests
            # explicitly forcing prune=off on a block-max plane fall
            # off the benched WAND-as-a-scan serving path (TELEMETRY.md
            # es_lex_prune_off_total) — a latency concern, not an error
            lex_total = _tm.lex_prune_off_count()
            lseen = getattr(self.api, "_lex_drift_seen", 0)
            lex_drift = max(lex_total - lseen, 0)
            self.api._lex_drift_seen = lex_total
        # mesh under-utilization: the serving mesh left devices out of
        # the slice (TELEMETRY.md es_mesh_devices{state="idle"}) — paid
        # chips stream zero corpus bytes. A gauge, not a window: idle
        # devices stay idle until the mesh knobs change.
        idle_devices = _tm.mesh_idle_devices()
        if storm >= self.SYNC_REBUILD_RED:
            status = RED
        elif storm >= self.SYNC_REBUILD_YELLOW or ann_drift > 0 \
                or lex_drift > 0 or idle_devices > 0:
            status = YELLOW
        else:
            status = GREEN
        if storm > 0:
            symptom = (f"{storm} synchronous serving-plane rebuilds ran "
                       f"on request threads (rebuild storm).")
        elif ann_drift > 0:
            symptom = (f"{ann_drift} ANN dispatches served below the "
                       f"benched nprobe (recall-config drift).")
        elif lex_drift > 0:
            symptom = (f"{lex_drift} lexical dispatches forced prune=off "
                       f"on a block-max plane (pruning drift).")
        elif idle_devices > 0:
            symptom = (f"{idle_devices} device(s) stranded idle outside "
                       f"the serving mesh (under-utilization).")
        else:
            symptom = "Serving planes are maintained off the request path."
        doc = {
            "status": status,
            "symptom": symptom,
            "details": {"sync_rebuilds": sync, "cold_builds": cold,
                        "background_repacks": background,
                        "sync_noncold_rebuilds": storm,
                        "delta_served_queries": delta_serves,
                        "ann_below_default_dispatches": ann_drift,
                        "ann_below_default_total": ann_total,
                        "lex_prune_off_dispatches": lex_drift,
                        "lex_prune_off_total": lex_total,
                        "idle_mesh_devices": idle_devices,
                        "storming_indices": per_index},
        }
        if status != GREEN:
            doc["impacts"] = []
            doc["diagnosis"] = []
            if storm > 0:
                doc["impacts"].append(_impact(
                    "plane_serving:rebuild_storm", 1,
                    "Search requests stall behind full plane repacks "
                    "(O(postings) pack + device upload per refresh); p99 "
                    "collapses under live indexing.", ["search"]))
                doc["diagnosis"].append(_diagnosis(
                    "plane_serving:sync_rebuilds",
                    "Refreshes are invalidating serving planes faster "
                    "than the background repack absorbs them (merges "
                    "and deletes restructure the base; appends past "
                    "1/8 of it trigger a repack).",
                    "Lower the refresh rate or batch the writes; watch "
                    "es_plane_rebuild_total{mode=\"sync\"}.",
                    {"indices": sorted(per_index)}))
            if ann_drift > 0:
                doc["impacts"].append(_impact(
                    "plane_serving:ann_recall_drift", 3,
                    "kNN results may fall below the benched recall@k: "
                    "queries are probing fewer IVF clusters than the "
                    "knn_ivf_recall bench certified.", ["search"]))
                doc["diagnosis"].append(_diagnosis(
                    "plane_serving:ann_nprobe_below_default",
                    "Requests set [knn.nprobe] below the serving "
                    "default the recall bench measured.",
                    "Drop the explicit nprobe override (or re-bench "
                    "knn_ivf_recall at the lower nprobe and accept its "
                    "recall@k); watch "
                    "es_ann_nprobe_below_default_total."))
            if lex_drift > 0:
                doc["impacts"].append(_impact(
                    "plane_serving:lex_prune_drift", 3,
                    "Lexical queries are eager-scoring every posting of "
                    "a corpus the lexical_10m_prune bench serves "
                    "block-max pruned — latency runs over the benched "
                    "profile at large corpora.", ["search"]))
                doc["diagnosis"].append(_diagnosis(
                    "plane_serving:lex_prune_off",
                    "Requests set [prune]=false on an index whose "
                    "serving plane carries a block-max tier (results "
                    "are identical either way — pruning is rank-safe).",
                    "Drop the explicit prune override, or accept the "
                    "eager latency profile; watch "
                    "es_lex_blocks_skipped_total and "
                    "es_lex_prune_off_total."))
            if idle_devices > 0:
                doc["impacts"].append(_impact(
                    "plane_serving:mesh_underutilization", 3,
                    "Devices outside the serving mesh hold no corpus "
                    "partition and serve no queries — per-chip corpus "
                    "bytes and throughput are worse than the slice "
                    "could deliver.", ["search"]))
                doc["diagnosis"].append(_diagnosis(
                    "plane_serving:idle_mesh_devices",
                    "ES_TPU_MESH_SHARDS x ES_TPU_MESH_REPLICAS covers "
                    "fewer devices than the slice provides.",
                    "Raise ES_TPU_MESH_SHARDS (corpus capacity) or "
                    "ES_TPU_MESH_REPLICAS (query throughput) to cover "
                    "the slice; watch es_mesh_devices{state=\"idle\"}."))
        return doc

    #: tier transitions per health window that read as promotion churn
    #: (planes ping-ponging between HBM and host — the working set does
    #: not fit the configured budget)
    TIER_CHURN_YELLOW = 8
    TIER_CHURN_RED = 64

    def _ind_plane_tiers(self) -> dict:
        """Storage-tier pressure: per-tier resident bytes plus WINDOWED
        promote/demote churn (the ann-drift watermark pattern — the
        counters are cumulative, and latched yellow would make 'raise
        the budget' unverifiable). Steady demotion under a budget is by
        design; sustained promotion churn means the Zipf hot set is
        larger than the HBM budget and every probe is paying a
        host→device re-upload."""
        promotions = demotions = 0
        hot_b = warm_b = cold_b = 0
        warm_planes = cold_planes = 0
        budgeted = False
        for _name, svc in list(self.api.indices.indices.items()):
            try:
                tiers = svc.plane_cache.tiers
                st = tiers.stats()
            except Exception:   # noqa: BLE001 — no plane cache: skip
                continue
            budgeted = budgeted or tiers.enabled()
            promotions += st["promotions"]
            demotions += st["demotions"]
            hot_b += st["hot_bytes"]
            warm_b += st["warm_bytes"]
            cold_b += st["cold_bytes"]
            warm_planes += st["warm_planes"]
            cold_planes += st["cold_planes"]
        with _ANN_DRIFT_LOCK:
            seen = getattr(self.api, "_tier_churn_seen", None)
            total = promotions + demotions
            self.api._tier_churn_seen = total
            churn = 0 if seen is None else max(total - seen, 0)
        if churn >= self.TIER_CHURN_RED:
            status = RED
        elif churn >= self.TIER_CHURN_YELLOW:
            status = YELLOW
        else:
            status = GREEN
        doc = {
            "status": status,
            "symptom": (f"{churn} plane tier transitions since the last "
                        f"evaluation (promotion churn)."
                        if status != GREEN else
                        ("Plane storage tiers are stable under the "
                         "configured budgets." if budgeted else
                         "Plane tiering is not budget-constrained "
                         "(every plane device-resident).")),
            "details": {"tier_transitions_window": churn,
                        "promotions_total": promotions,
                        "demotions_total": demotions,
                        "hot_bytes": hot_b, "warm_bytes": warm_b,
                        "cold_bytes": cold_b,
                        "warm_planes": warm_planes,
                        "cold_planes": cold_planes,
                        "budgeted": budgeted},
        }
        if status != GREEN:
            doc["impacts"] = [_impact(
                "plane_tiers:promotion_churn", 2,
                "Serving planes ping-pong between HBM and host tiers: "
                "promoted planes are evicted before their next access, "
                "so dispatches repeatedly pay host→device streaming and "
                "re-upload instead of HBM-resident scans.", ["search"])]
            doc["diagnosis"] = [_diagnosis(
                "plane_tiers:working_set_over_budget",
                "The query mix's hot set is larger than "
                "ES_TPU_PLANE_HBM_BUDGET_BYTES: LRU demotion and demand "
                "promotion are fighting over the same planes.",
                "Raise ES_TPU_PLANE_HBM_BUDGET_BYTES (or add shard "
                "devices to shrink per-device plane bytes); watch "
                "es_plane_tier_promotions_total vs "
                "es_plane_tier_bytes{tier=\"hot\"}.")]
        return doc

    def _ind_compile_churn(self) -> dict:
        from . import telemetry as _tm
        compiles = _tm.compile_count()
        live_warmed = 0
        doc_reg = _tm.DEFAULT.stats_doc().get(
            "es_plane_serving_warmed_shapes_total")
        if doc_reg:
            live_warmed = int(sum(s["value"]
                                  for s in doc_reg["series"]))
        # warmed credit comes from the PROCESS-CUMULATIVE counter
        # (telemetry.record_warmed_shapes), not the live batchers'
        # rollup: per-batcher credits die with their weakref'd
        # collectors when a generation retires, so a repack inside one
        # window would otherwise cancel its replacement's warmup credit
        # and read as phantom churn.
        warmed = max(_tm.warmed_shapes_count(), live_warmed)
        # windowed against the previous health evaluation (watermark on
        # the api object, the ann-drift pattern above): both counters
        # are monotone, so churn is judged on compiles SINCE the last
        # evaluation vs warmed since the last evaluation; the first
        # evaluation baselines the watermark (process history has no
        # matching warmed history).
        if self.api is not None:
            with _ANN_DRIFT_LOCK:
                seen_c = getattr(self.api, "_compile_seen", None)
                seen_w = getattr(self.api, "_warmed_seen", 0)
                self.api._compile_seen = compiles
                self.api._warmed_seen = warmed
            if seen_c is None:
                excess = 0
            else:
                excess = max((compiles - seen_c)
                             - max(warmed - seen_w, 0), 0)
        else:
            excess = max(compiles - warmed, 0)
        if excess > self.COMPILE_RED:
            status = RED
        elif excess > self.COMPILE_SLACK:
            status = YELLOW
        else:
            status = GREEN
        doc = {
            "status": status,
            "symptom": ("XLA compiles are covered by the warmup "
                        "lattice." if status == GREEN else
                        f"{excess} XLA compiles landed outside the "
                        f"warmup lattice (steady-state compile churn)."),
            "details": {"compiles_total": compiles,
                        "warmed_shapes_total": warmed,
                        "excess_compiles": excess},
        }
        if status != GREEN:
            doc["impacts"] = [_impact(
                "compile_churn:first_hit_compiles", 2,
                "First requests of an uncompiled shape pay multi-second "
                "XLA compiles mid-traffic (serving p99 spikes).",
                ["search"])]
            doc["diagnosis"] = [_diagnosis(
                "compile_churn:unwarmed_shapes",
                "Serving dispatches hit input shapes the warmup lattice "
                "never pre-compiled (new k buckets, ragged batch sizes, "
                "or ES_TPU_SERVING_WARMUP=0).",
                "Check es_xla_compiles_by_shape_total for the offending "
                "shapes and widen the warmup ks / batch lattice.")]
        return doc

    def _ind_breakers(self) -> dict:
        from .breakers import DEFAULT as svc
        tripped = {}
        details = {}
        for name, st in svc.stats().items():
            details[name] = {
                "estimated_bytes": st["estimated_size_in_bytes"],
                "limit_bytes": st["limit_size_in_bytes"],
                "tripped": st["tripped"]}
            if st["tripped"]:
                tripped[name] = st["tripped"]
        if tripped.get("parent"):
            status = RED
        elif tripped:
            status = YELLOW
        else:
            status = GREEN
        doc = {
            "status": status,
            "symptom": ("No circuit breakers have tripped."
                        if status == GREEN else
                        f"Circuit breakers tripped: "
                        f"{', '.join(sorted(tripped))}."),
            "details": details,
        }
        if status != GREEN:
            doc["impacts"] = [_impact(
                "breakers:rejections", 1 if status == RED else 2,
                "Requests over the tripped budget are rejected with "
                "429 circuit_breaking_exception.", ["search", "ingest"])]
            doc["diagnosis"] = [_diagnosis(
                "breakers:memory_pressure",
                f"Memory budgets exhausted on "
                f"{', '.join(sorted(tripped))}.",
                "Reduce concurrent request size/fan-out, shrink "
                "fielddata usage, or raise the breaker limits.")]
        return doc

    def _ind_indexing_pressure(self) -> dict:
        from .indexing_pressure import DEFAULT as ip
        frac = (ip.current_bytes / ip.limit_bytes) if ip.limit_bytes else 0
        if ip.rejections and frac >= self.PRESSURE_YELLOW_FRACTION:
            status = RED
        elif ip.rejections or frac >= self.PRESSURE_YELLOW_FRACTION:
            status = YELLOW
        else:
            status = GREEN
        doc = {
            "status": status,
            "symptom": ("Indexing pressure is within budget."
                        if status == GREEN else
                        f"Indexing pressure degraded: {ip.rejections} "
                        f"rejections, {int(frac * 100)}% of the byte "
                        f"budget in flight."),
            "details": {"current_bytes": ip.current_bytes,
                        "limit_bytes": ip.limit_bytes,
                        "total_bytes": ip.total_bytes,
                        "rejections": ip.rejections},
        }
        if status != GREEN:
            doc["impacts"] = [_impact(
                "indexing_pressure:rejections", 2,
                "Bulk/index requests beyond the byte budget are "
                "rejected with 429.", ["ingest"])]
            doc["diagnosis"] = [_diagnosis(
                "indexing_pressure:saturation",
                "Concurrent indexing payload bytes exceed the node's "
                "indexing-pressure budget.",
                "Reduce bulk concurrency/size or add indexing "
                "capacity.")]
        return doc

    def _ind_slo_burn(self) -> dict:
        """SLO burn-rate watchdog (``common/flightrec.py``): multi-window
        burn over ``es_slo_burn_rate{window}`` — red means BOTH the fast
        and slow windows burned past the threshold and an automatic
        post-mortem capture fired (``GET /_flight_recorder/captures``);
        yellow means one window is burning (onset, or the slow window
        still draining through recovery)."""
        from . import flightrec
        wd = flightrec.get_watchdog()
        if wd is None:
            return {"status": GREEN,
                    "symptom": "The SLO watchdog is disabled "
                               "(ES_TPU_WATCHDOG=0).",
                    "details": {"watchdog": "disabled"}}
        st = wd.status_doc()
        status = {flightrec.GREEN: GREEN, flightrec.YELLOW: YELLOW,
                  flightrec.RED: RED}.get(st.get("status"), UNKNOWN)
        rates = st.get("burn_rates") or {}
        fast = (rates.get("fast") or {}).get("burn", 0.0)
        slow = (rates.get("slow") or {}).get("burn", 0.0)
        doc = {
            "status": status,
            "symptom": ("Error-budget burn is within the SLO."
                        if status == GREEN else
                        f"SLO burn rate fast={fast} slow={slow} "
                        f"(red threshold {wd.engine.burn_red}); "
                        f"{st.get('captures', 0)} post-mortem capture(s) "
                        f"retained."),
            "details": {"burn_rates": rates,
                        "burn_red_threshold": wd.engine.burn_red,
                        "latency_threshold_ms":
                            wd.engine.latency_threshold_ms,
                        "windows_s": {"fast": wd.engine.fast_s,
                                      "slow": wd.engine.slow_s},
                        "captures": st.get("captures", 0),
                        "watchdog_running": st.get("running", False)},
        }
        if status not in (GREEN, UNKNOWN):
            doc["impacts"] = [_impact(
                "slo_burn:error_budget", 1 if status == RED else 2,
                "Queries are breaching the latency/failure SLO fast "
                "enough to exhaust the error budget; users are seeing "
                "slow or failed searches now.", ["search"])]
            doc["diagnosis"] = [_diagnosis(
                "slo_burn:degradation",
                "Sustained latency over the SLO threshold or elevated "
                "search failover/retry rates across both burn windows.",
                "Read the automatic capture (GET /_flight_recorder/"
                "captures — hot threads, journal slice, batcher queue "
                "depths taken AT the red transition) and watch "
                "es_slo_burn_rate{window} + es_watchdog_captures_total.")]
        return doc

    def _ind_query_insights(self) -> dict:
        """Query-shape dominance (``search/query_insight.py``): yellow
        when one query shape OR one tenant accounts for more than the
        configured fraction (``insights.dominance_fraction`` /
        ``ES_TPU_INSIGHTS_DOMINANCE``, default 0.5) of the windowed
        device-ms on this node — the "one tenant's 10M-doc agg starves
        point queries" signal, with the shape id and its retained
        sample body in the diagnosis so the offending request is
        reproducible without log archaeology. Windows below the
        observation volume floor carry no signal (the SLO engine's
        min_window_queries shape)."""
        from ..search import query_insight as _qi
        if not _qi.insights_enabled():
            return {"status": GREEN,
                    "symptom": "Query insights are disabled "
                               "(ES_TPU_INSIGHTS=0).",
                    "details": {"insights": "disabled"}}
        store = _qi.store_for(getattr(self.api, "node_id", None))
        dom = store.dominance()
        frac_limit = _qi.dominance_fraction()
        min_obs = _qi.min_window_observations()
        obs = int(dom.get("observations", 0))
        details = {"dominance": dom,
                   "dominance_fraction_threshold": frac_limit,
                   "min_window_observations": min_obs}
        if obs < min_obs:
            return {"status": GREEN,
                    "symptom": f"Below the insight volume floor "
                               f"({obs}/{min_obs} windowed "
                               f"observations): no dominance signal.",
                    "details": details}
        offenders = []
        for dim in ("shape", "tenant"):
            ent = dom.get(dim)
            if ent and float(ent.get("fraction", 0.0)) > frac_limit:
                offenders.append((dim, ent))
        if not offenders:
            return {"status": GREEN,
                    "symptom": "No query shape or tenant dominates the "
                               "windowed device time.",
                    "details": details}
        dim, ent = offenders[0]
        key = ent.get("key")
        frac_pct = round(float(ent.get("fraction", 0.0)) * 100, 1)
        doc = {
            "status": YELLOW,
            "symptom": (f"One {dim} [{key}] accounts for {frac_pct}% "
                        f"of windowed device time (threshold "
                        f"{round(frac_limit * 100, 1)}%)."),
            "details": details,
            "impacts": [_impact(
                "query_insights:dominance", 2,
                "A single query shape or tenant is consuming most of "
                "the device budget; other tenants' queries queue "
                "behind its dispatches.", ["search"])],
        }
        affected = {dim: [key] if key else []}
        sample = ent.get("sample")
        if sample is not None:
            affected["sample_body"] = sample
        doc["diagnosis"] = [_diagnosis(
            "query_insights:dominance",
            f"The {dim} [{key}] burned "
            f"{ent.get('device_ms', 0)} device-ms of the recent "
            f"insight windows — {frac_pct}% of the node total.",
            "Inspect GET /_insights/top_queries (the shape's exemplar "
            "trace id links to GET /_trace/{id}); throttle or rewrite "
            "the offending request, or isolate the tenant.",
            affected)]
        return doc

    def _ind_qos(self) -> dict:
        """Multi-tenant QoS (``common/qos.py``): green while the edge
        admits everything, yellow while load shedding is engaged (the
        cluster is deliberately bouncing non-interactive traffic with
        429s), red when shedding has stayed engaged past
        ``qos.shed.sustained_seconds`` — sustained shedding means the
        overload is not draining and interactive traffic is next. The
        diagnosis names the dominant shed tenant so the abusive
        workload is actionable, and the trigger evidence (queue depth,
        breaker fraction, SLO burn) rides in the details — the same
        evidence each ``qos_shed`` flight-recorder event carries."""
        from . import qos as _qos
        doc = _qos.controller().status_doc()
        details = {"qos": doc}
        if not doc.get("enabled", True):
            return {"status": GREEN,
                    "symptom": "QoS admission control is disabled "
                               "(ES_TPU_QOS=0).",
                    "details": details}
        if not doc.get("engaged"):
            return {"status": GREEN,
                    "symptom": "No load shedding: all tenants within "
                               "their token budgets.",
                    "details": details}
        sheds = doc.get("sheds_by_tenant") or {}
        top_tenant = max(sheds, key=lambda t: sheds[t]) if sheds else None
        sustained = bool(doc.get("sustained"))
        engaged_for = doc.get("engaged_for_s", 0.0)
        status = RED if sustained else YELLOW
        severity = 1 if sustained else 2
        out = {
            "status": status,
            "symptom": (f"Load shedding has been engaged for "
                        f"{engaged_for}s"
                        + (" (sustained past the "
                           "qos.shed.sustained_seconds bound)."
                           if sustained else ".")),
            "details": details,
            "impacts": [_impact(
                "qos:shedding", severity,
                "The REST edge is rejecting bulk/analytics traffic "
                "with 429s to protect interactive latency"
                + ("; sustained shedding means the overload is not "
                   "draining and interactive requests shed next."
                   if sustained else "."),
                ["search", "ingest"])],
        }
        affected = {"tenants": [top_tenant] if top_tenant else []}
        cause = (f"Overload signals tripped the shed state machine: "
                 f"{doc.get('signals')}.")
        if top_tenant is not None:
            cause += (f" Tenant [{top_tenant}] absorbed the most sheds "
                      f"({sheds[top_tenant]}).")
        out["diagnosis"] = [_diagnosis(
            "qos:shedding", cause,
            "Inspect GET /_flight_recorder?type=qos_shed for the "
            "engage evidence and GET /_insights/top_queries for the "
            "shed-heavy shapes; throttle the dominant tenant "
            "(qos.tenant.refill_per_s) or raise capacity.",
            affected)]
        return out

    def _ind_dispatch_efficiency(self) -> dict:
        """Continuous roofline audit (``common/roofline.py``): every
        serving dispatch's achieved bandwidth is compared against the
        ROOFLINE.md bytes model; this indicator judges the windowed
        mean efficiency per kernel family SINCE the last evaluation
        (the compile_churn windowed-watermark pattern — the underlying
        accumulators are process-cumulative). Yellow means a kernel's
        window drifted below the floor: an explicit
        ``dispatch_efficiency.floor_pct`` / ``ES_TPU_DISPATCH_EFF_
        FLOOR_PCT`` when set, else ``drift_fraction`` of the session's
        best windowed mean for that kernel (auto mode — absolute
        efficiency differs per backend, drift does not). Windows below
        the ``min_dispatches`` volume floor carry no signal and are NOT
        consumed, so trickle traffic accumulates until judgeable (the
        SLO engine's min_window_queries shape). Status transitions are
        journaled to the flight recorder."""
        from . import flightrec as _fr
        from . import roofline as _rl
        totals = _rl.audit_totals()
        floor = _rl.efficiency_floor_pct()
        drift_frac = _rl.efficiency_drift_fraction()
        min_d = _rl.efficiency_min_dispatches()
        drifting: Dict[str, dict] = {}
        kernels: Dict[str, dict] = {}
        with _ANN_DRIFT_LOCK:
            seen = dict(getattr(self.api, "_eff_seen", {}))
            baselines = dict(getattr(self.api, "_eff_baseline", {}))
            for kern, (n, s) in sorted(totals.items()):
                n0, s0 = seen.get(kern, (0, 0.0))
                wn, ws = n - n0, s - s0
                if wn < min_d:
                    # below the volume floor: no signal, window NOT
                    # consumed (one slow dispatch on an idle node is a
                    # blip, not drift)
                    kernels[kern] = {"window_dispatches": wn,
                                     "pending": True}
                    continue
                mean = ws / wn
                seen[kern] = (n, s)
                base = baselines.get(kern)
                thr = floor if floor > 0 else (
                    base * drift_frac if base is not None else None)
                # watermark: the best windowed mean seen this session
                # (a drifting window sits below it and never lowers it)
                baselines[kern] = mean if base is None \
                    else max(base, mean)
                kernels[kern] = {
                    "window_dispatches": wn,
                    "window_mean_pct": round(mean, 3),
                    "baseline_pct": round(baselines[kern], 3),
                    "threshold_pct": round(thr, 3)
                    if thr is not None else None}
                if thr is not None and mean < thr:
                    drifting[kern] = kernels[kern]
            self.api._eff_seen = seen
            self.api._eff_baseline = baselines
            prev = getattr(self.api, "_eff_status", GREEN)
            status = YELLOW if drifting else GREEN
            self.api._eff_status = status
        if status != prev:
            _fr.record("dispatch_efficiency",
                       transition=f"{prev}->{status}",
                       kernels=sorted(drifting))
        doc = {
            "status": status,
            "symptom": ("Dispatch bandwidth tracks the roofline model."
                        if status == GREEN else
                        f"Kernel(s) {', '.join(sorted(drifting))} ran "
                        f"below the roofline efficiency floor over the "
                        f"last window."),
            "details": {"kernels": kernels,
                        "floor_pct": floor,
                        "drift_fraction": drift_frac,
                        "min_window_dispatches": min_d,
                        "peak_bandwidth_gbps":
                            _rl.peak_bandwidth_gbps()},
        }
        if status != GREEN:
            doc["impacts"] = [_impact(
                "dispatch_efficiency:bandwidth_drift", 3,
                "Dispatches are moving their modeled bytes slower than "
                "this machine has demonstrated it can — latency and "
                "throughput are degraded relative to the same "
                "hardware's own recent baseline.", ["search"])]
            doc["diagnosis"] = [_diagnosis(
                "dispatch_efficiency:below_floor",
                "Sustained per-dispatch bandwidth below the configured "
                "floor (or the session's watermark): device/host "
                "contention, a throttled container, or a kernel "
                "regression.",
                "Read GET /_profiler/timeline for the dispatch "
                "timeline (queue/prep/execute/fetch overlap per "
                "dispatcher thread) and watch "
                "es_dispatch_efficiency_pct{kernel} / "
                "es_dispatch_bandwidth_gbps{kernel}.",
                {"kernels": sorted(drifting)})]
        return doc

    def _ind_task_backlog(self) -> dict:
        tm = self.api.task_manager
        with tm.lock:
            live = list(tm.tasks.values())
        now = time.time()
        # monitor-lane tasks (including the health-report request
        # itself) are not backlog
        others = [t for t in live if ":monitor/" not in t.action]
        count = len(others)
        oldest_s = max((now - t.start_time for t in others), default=0.0)
        if count > self.BACKLOG_RED or oldest_s > self.OLDEST_TASK_RED_S:
            status = RED
        elif count > self.BACKLOG_YELLOW or \
                oldest_s > self.OLDEST_TASK_YELLOW_S:
            status = YELLOW
        else:
            status = GREEN
        doc = {
            "status": status,
            "symptom": ("The task backlog is nominal."
                        if status == GREEN else
                        f"{count} live tasks; oldest has run "
                        f"{oldest_s:.0f}s."),
            "details": {"running_tasks": len(live),
                        "running_non_monitor_tasks": count,
                        "oldest_task_age_seconds": round(oldest_s, 1)},
        }
        if status != GREEN:
            doc["impacts"] = [_impact(
                "task_backlog:queueing", 3,
                "Requests queue behind a deep task backlog; latency "
                "grows.", ["search", "ingest"])]
            doc["diagnosis"] = [_diagnosis(
                "task_backlog:long_running",
                "Long-running or piling-up tasks (check "
                "GET /_tasks?detailed for their resource_stats).",
                "Cancel runaway tasks via POST /_tasks/{id}/_cancel or "
                "add capacity.")]
        return doc


def merge_reports(local: dict, remote_docs: Dict[str, dict]) -> dict:
    """Cluster fan-in: fold per-node reports into one (the reference
    computes indicators on the coordinating node from cluster state;
    here each node evaluates its registry-local view and the front takes
    the worst per indicator, keeping a per-node status map in details).
    ``remote_docs``: node_id -> that node's local report."""
    merged = {"cluster_name": local.get("cluster_name"),
              "indicators": {}}
    all_docs = dict(remote_docs)
    names = set(local.get("indicators", ()))
    for doc in all_docs.values():
        names.update(doc.get("indicators", ()))
    for name in sorted(names):
        per_node = {}
        worst_doc = None
        worst = GREEN
        for node_id, rep in all_docs.items():
            ind = (rep.get("indicators") or {}).get(name)
            if not ind:
                continue
            per_node[node_id] = ind.get("status", UNKNOWN)
            if worst_doc is None or \
                    _RANK.get(ind.get("status"), 1) > _RANK.get(worst, 1):
                worst_doc = ind
                worst = ind.get("status", UNKNOWN)
        out = dict(worst_doc or {"status": UNKNOWN,
                                 "symptom": "no node reported"})
        details = dict(out.get("details") or {})
        details["nodes"] = per_node
        out["details"] = details
        merged["indicators"][name] = out
    merged["status"] = worst_status(
        d["status"] for d in merged["indicators"].values())
    return merged
