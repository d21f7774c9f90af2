"""REST API: routes + handlers with ES-shaped JSON in and out.

Re-design of the reference's REST layer: ``rest/RestController.java:196``
(dispatch), handlers under ``rest/action/`` (119 classes), response wire
shapes per ``rest-api-spec`` (144 JSON specs). One class holds the route
table; handlers are sync functions (the engine is single-writer per shard)
invoked from the asyncio HTTP server.
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
import uuid
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np
from urllib.parse import parse_qs, unquote

from ..common import heap as _heap
from ..common.errors import (ActionRequestValidationError,
                             DocumentMissingError, ElasticsearchError,
                             ResourceNotFoundError,
                             IllegalArgumentError, IndexClosedError,
                             IndexNotFoundError,
                             ParsingError, ResourceAlreadyExistsError,
                             VersionConflictError)
from ..index.mapping import MapperService
from ..ingest import IngestService
from ..node.indices_service import IndexService, IndicesService
from ..snapshots import SnapshotsService
from ..search.shard_search import ShardHit, ShardSearcher

JSON_CT = "application/json"


def _json_body(body) -> dict:
    if not body:
        return {}
    if isinstance(body, dict):      # already parsed upstream
        return body
    try:
        return json.loads(body)
    except json.JSONDecodeError as e:
        raise ParsingError(f"request body is not valid JSON: {e}")


def _script_service():
    """The process-wide ScriptService (live stats for nodes stats)."""
    from ..script.service import DEFAULT
    return DEFAULT


def _indexing_pressure():
    """The process-wide IndexingPressure (live stats + bulk gate)."""
    from ..common.indexing_pressure import DEFAULT
    return DEFAULT


def _device_stats() -> dict:
    """The nodes-stats ``device`` section (common/telemetry.py)."""
    from ..common.telemetry import device_stats_doc
    return device_stats_doc()


def _node_telemetry_families(api) -> dict:
    """This node's contribution to the process telemetry registry —
    plane-serving counters, running tasks, adaptive selection — as
    Prometheus-shaped families (registered weakly in RestAPI.__init__,
    rendered by /_prometheus/metrics and /_nodes/telemetry)."""
    lbl = {"node": api.node_name}
    ps = api._plane_serving_rollup()
    fams = {
        "es_plane_serving_dispatches_total": {
            "type": "counter", "help": "micro-batch device dispatches",
            "samples": [(lbl, ps["dispatches"])]},
        "es_plane_serving_queries_total": {
            "type": "counter", "samples": [(lbl, ps["queries"])]},
        "es_plane_serving_deduped_queries_total": {
            "type": "counter", "samples": [(lbl, ps["deduped_queries"])]},
        "es_plane_serving_delta_queries_total": {
            "type": "counter",
            "help": "queries whose dispatch merged a live delta tier",
            "samples": [(lbl, ps["delta_queries"])]},
        "es_plane_serving_max_batch": {
            "type": "gauge", "samples": [(lbl, ps["max_batch"])]},
        "es_plane_serving_cache_hits_total": {
            "type": "counter", "samples": [(lbl, ps["cache_hit_count"])]},
        "es_plane_serving_cache_misses_total": {
            "type": "counter",
            "samples": [(lbl, ps["cache_miss_count"])]},
        "es_plane_serving_warmed_shapes_total": {
            "type": "counter", "samples": [(lbl, ps["warmed_shapes"])]},
        "es_plane_serving_stage_millis_total": {
            "type": "counter",
            "help": "per-stage serving-pipeline milliseconds",
            "samples": [
                (dict(lbl, stage=s), ps[f"{s}_time_in_millis"])
                for s in ("queue", "prep", "dispatch", "fetch")]},
        "es_tasks_running": {
            "type": "gauge", "help": "registered live tasks",
            "samples": [(lbl, len(api.task_manager.tasks))]},
    }
    if api.adaptive_selection_provider:
        try:
            ars = api.adaptive_selection_provider()
        except Exception:   # noqa: BLE001 — cluster seam gone: skip
            ars = {}
        if ars:
            fams["es_adaptive_selection_response_seconds"] = {
                "type": "gauge",
                "samples": [(dict(lbl, target=n),
                             rec["avg_response_time_ns"] / 1e9)
                            for n, rec in ars.items()]}
    return fams


def _os_stats() -> dict:
    """Real host memory/load figures (reference: ``monitor/os/OsProbe``;
    /proc is authoritative on this platform — no psutil dependency)."""
    total = free = avail = 0
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                k, _, rest = line.partition(":")
                kb = int(rest.strip().split()[0])
                if k == "MemTotal":
                    total = kb * 1024
                elif k == "MemFree":
                    free = kb * 1024
                elif k == "MemAvailable":
                    avail = kb * 1024
    except OSError:
        pass
    used = max(total - (avail or free), 0)
    pct = int(round(used * 100 / total)) if total else 0
    try:
        load1, load5, load15 = os.getloadavg()
    except OSError:
        load1 = load5 = load15 = 0.0
    return {"timestamp": int(time.time() * 1000),
            "cpu": {"percent": min(99, int(load1 * 100 /
                                           (os.cpu_count() or 1))),
                    "load_average": {"1m": round(load1, 2),
                                     "5m": round(load5, 2),
                                     "15m": round(load15, 2)}},
            "mem": {"total_in_bytes": total,
                    "free_in_bytes": avail or free,
                    "used_in_bytes": used,
                    "free_percent": 100 - pct, "used_percent": pct}}


def _os_mem_stats() -> dict:
    """Memory slice of the shared /proc/meminfo probe — cluster-stats
    and node-stats must report from identical parsing."""
    return {"mem": _os_stats()["mem"]}


def _fs_stats(path: str) -> dict:
    """Real filesystem figures for the data path
    (``monitor/fs/FsProbe.java``)."""
    try:
        import shutil as _sh
        du = _sh.disk_usage(path)
        return {"total_in_bytes": du.total, "free_in_bytes": du.free,
                "available_in_bytes": du.free}
    except OSError:
        return {"total_in_bytes": 0, "free_in_bytes": 0,
                "available_in_bytes": 0}


def _process_stats() -> dict:
    """Real process figures (reference: ``monitor/process/ProcessProbe``)."""
    import resource
    ru = resource.getrusage(resource.RUSAGE_SELF)
    cpu_ms = int((ru.ru_utime + ru.ru_stime) * 1000)
    try:
        n_fds = len(os.listdir("/proc/self/fd"))
    except OSError:
        n_fds = 0
    try:
        max_fds = resource.getrlimit(resource.RLIMIT_NOFILE)[0]
    except (ValueError, OSError):
        max_fds = 0
    vsize = 0
    try:
        with open("/proc/self/statm") as f:
            vsize = int(f.read().split()[0]) * (os.sysconf("SC_PAGE_SIZE")
                                                if hasattr(os, "sysconf")
                                                else 4096)
    except (OSError, ValueError):
        pass
    return {"timestamp": int(time.time() * 1000),
            "open_file_descriptors": n_fds,
            "max_file_descriptors": max_fds,
            "cpu": {"percent": 0, "total_in_millis": cpu_ms},
            "mem": {"total_virtual_in_bytes": vsize}}


def _error_payload(e: Exception) -> Tuple[int, dict]:
    if isinstance(e, ElasticsearchError):
        status = getattr(e, "status", 500)
        etype = getattr(e, "error_type", type(e).__name__)
        reason = str(e)
    else:
        status, etype, reason = 500, "exception", str(e)
    rc = {"type": etype, "reason": reason}
    idx = getattr(e, "index", None)
    if idx is not None:
        rc["index"] = idx
        rc["resource.type"] = "index_or_alias"
        rc["resource.id"] = idx
    err = {"root_cause": [rc], "type": etype, "reason": reason}
    if idx is not None:
        err["index"] = idx
    caused_by = getattr(e, "caused_by", None)
    if caused_by:
        err["caused_by"] = caused_by
    extra_header = (e.to_dict().get("error", {}).get("header")
                    if isinstance(e, ElasticsearchError) else None)
    if extra_header:
        err["header"] = extra_header      # 401 WWW-Authenticate etc.
    return status, {"error": err, "status": status}




class _RequireAliasError(ElasticsearchError):
    status = 404
    error_type = "index_not_found_exception"


def _require_alias_error(index: str) -> "_RequireAliasError":
    return _RequireAliasError(
        f"no such index [{index}] and [require_alias] request flag is "
        f"[true] and [{index}] is not an alias")


#: (suffix → transport action name) for per-request task registration —
#: the names conformance filters match on (``actions: "cluster:monitor/
#: tasks/lists"`` etc.); everything else registers under a generic name
_ACTION_SUFFIXES = [
    ("/_tasks", "cluster:monitor/tasks/lists"),
    ("/_search", "indices:data/read/search"),
    ("/_msearch", "indices:data/read/msearch"),
    ("/_count", "indices:data/read/search"),
    ("/_reindex", "indices:data/write/reindex"),
    ("/_update_by_query", "indices:data/write/update/byquery"),
    ("/_delete_by_query", "indices:data/write/delete/byquery"),
    ("/_bulk", "indices:data/write/bulk"),
    ("/_forcemerge", "indices:admin/forcemerge"),
    ("/_snapshot", "cluster:admin/snapshot"),
]


def _action_name(method: str, path: str) -> str:
    p = path.rstrip("/")
    for suffix, action in _ACTION_SUFFIXES:
        if p.endswith(suffix) or (suffix + "/") in p:
            return action
    if p.startswith("/_cluster") or p.startswith("/_nodes"):
        return "cluster:monitor/state"
    if method == "GET":
        return "indices:monitor/rest"
    return "indices:admin/rest"


def _render_filter(spec):
    """Alias filters render back in Lucene-normalized form (boost made
    explicit, term values wrapped) — ``AbstractQueryBuilder.toXContent``
    shapes, as ``_search_shards`` and explain APIs return them."""
    if not isinstance(spec, dict) or len(spec) != 1:
        return spec
    (kind, inner), = spec.items()
    if kind == "term" and isinstance(inner, dict):
        out = {}
        for field, v in inner.items():
            if isinstance(v, dict):
                out[field] = {"boost": 1.0, **v}
            else:
                out[field] = {"value": v, "boost": 1.0}
        return {"term": out}
    if kind == "bool" and isinstance(inner, dict):
        rendered = {}
        for sec in ("must", "should", "filter", "must_not"):
            clauses = inner.get(sec)
            if clauses is None:
                continue
            if isinstance(clauses, dict):
                clauses = [clauses]
            rendered[sec] = [_render_filter(c) for c in clauses]
        rendered["adjust_pure_negative"] = inner.get(
            "adjust_pure_negative", True)
        rendered["boost"] = inner.get("boost", 1.0)
        return {"bool": rendered}
    return spec


def _flag(params: dict, name: str, default: bool = False) -> bool:
    v = params.get(name)
    if v is None:
        return default
    return str(v).lower() not in ("false", "0", "no")


_RECOVERY_NODE = {"id": "node_0", "host": "127.0.0.1",
                  "transport_address": "127.0.0.1:9300",
                  "ip": "127.0.0.1", "name": "node_0"}


class RestAPI:
    """Route table + handlers over one node's IndicesService."""

    def __init__(self, indices: IndicesService, cluster_name: str = "es-tpu",
                 node_name: str = "node-0"):
        self.indices = indices
        self.cluster_name = cluster_name
        self.node_name = node_name
        self.node_id = uuid.uuid4().hex[:20]
        # security (x-pack analog): off by default — conformance runs
        # unauthenticated; the node binary enables it via settings
        from ..lifecycle import DataStreamService, IlmService
        from ..security import SecurityService
        from ..transport.remote import RemoteClusterRegistry
        self.remotes = RemoteClusterRegistry(
            lambda: self.cluster_settings)
        self.datastreams = DataStreamService(self)
        self.ilm = IlmService(self)
        self._async_searches: Dict[str, Any] = {}
        self.indices.data_streams_provider = \
            self.datastreams.backing_indices
        #: internal re-entrant dispatches (async search task threads)
        #: ride on the SUBMITTING request's authentication
        self._internal_tls = threading.local()
        #: cluster seam: () -> adaptive_selection stats (ARS EWMAs live
        #: on the ClusterNode; single-node has no peers to rank)
        self.adaptive_selection_provider = None
        self.security = SecurityService(enabled=False)
        self.enforce_security = True
        # per-REQUEST principal: requests run on a worker pool, so the
        # authenticated identity must be thread-local
        self._principal_tls = threading.local()
        self.start_time = time.time()
        #: the HTTP server stamps its real bind address here on start
        #: (client sniffing reads nodes.*.http.publish_address)
        self.http_publish_address = "127.0.0.1:9200"
        self.voting_exclusions: List[dict] = []
        self.component_templates: Dict[str, dict] = {}
        #: x-pack logstash plugin pipeline configs (h_logstash_*)
        self._logstash_pipelines: Dict[str, dict] = {}
        self.cluster_settings: Dict[str, dict] = {"persistent": {},
                                                  "transient": {}}
        self.templates: Dict[str, dict] = {}
        self.scrolls: Dict[str, dict] = {}
        self.pits: Dict[str, dict] = {}
        from ..node.task_manager import TaskManager
        self.task_manager = TaskManager(self.node_id, self.node_name)
        self._req_task = threading.local()
        #: (trace_id, x_opaque_id) of the last request on this thread —
        #: handle() echoes them as response headers (reference:
        #: X-Opaque-Id echo + APM trace.id)
        self._trace_tls = threading.local()
        #: extra response headers an error on this thread wants promoted
        #: to the wire (QoS 429 Retry-After, security WWW-Authenticate)
        #: — handle() merges them into resp_headers after dispatch
        self._extra_hdr_tls = threading.local()
        # node-scoped telemetry producers register against the process
        # registry via weakref (pruned when this API is collected):
        # plane serving rollup, running tasks, adaptive selection
        from ..common import telemetry as _telemetry
        _telemetry.DEFAULT.register_object_collector(
            f"node:{self.node_id}", self, _node_telemetry_families)
        # flight recorder: this node's serving surfaces are capture-able
        # (weakref — a retired test node never pins itself) and the
        # process SLO watchdog runs whenever any node does
        from ..common import flightrec as _flightrec
        _flightrec.register_node(self)
        _flightrec.ensure_watchdog()
        # continuous profiler: the always-on flamegraph sampler runs
        # whenever any node does, like the watchdog (ES_TPU_CONTPROF=0
        # gates it off)
        from ..common import contprof as _contprof
        _contprof.ensure_profiler()
        self.stored_scripts: Dict[str, dict] = {}
        self.ingest = IngestService()
        self.snapshots = SnapshotsService(indices)
        self._routes: List[Tuple[str, re.Pattern, List[str], Callable]] = []
        self._build_routes()

    # ------------------------------------------------------------------
    # routing
    # ------------------------------------------------------------------

    def _add(self, methods: str, pattern: str, fn: Callable) -> None:
        names = re.findall(r"\{(\w+)\}", pattern)
        body = re.sub(r"\{\w+\}", r"([^/]+)", pattern)
        if pattern.startswith("/{"):
            # a leading {index} placeholder must not swallow unknown _api
            # paths (ES: "no handler found", 400 — RestController.java:196);
            # _-prefixed names are reserved — except the _all expression
            body = body.replace("([^/]+)", "((?:_all|(?!_)[^/]+))", 1)
        rx = re.compile("^" + body + "$")
        for m in methods.split(","):
            self._routes.append((m, rx, names, fn))

    def _build_routes(self) -> None:
        add = self._add
        add("GET,HEAD", "/", self.h_root)
        # cluster
        add("GET", "/_cluster/health", self.h_cluster_health)
        add("GET", "/_cluster/health/{index}", self.h_cluster_health)
        add("GET", "/_cluster/stats", self.h_cluster_stats)
        add("GET", "/_cluster/state", self.h_cluster_state)
        add("GET", "/_cluster/state/{metric}", self.h_cluster_state)
        add("GET", "/_cluster/state/{metric}/{index}",
            self.h_cluster_state)
        add("GET", "/_cluster/pending_tasks", self.h_pending_tasks)
        add("POST", "/_cluster/reroute", self.h_cluster_reroute)
        add("GET,POST", "/_cluster/allocation/explain",
            self.h_allocation_explain)
        add("GET", "/_cluster/settings", self.h_cluster_get_settings)
        add("PUT", "/_cluster/settings", self.h_cluster_put_settings)
        add("GET", "/_nodes", self.h_nodes)
        add("GET", "/_remote/info", self.h_remote_info)
        add("POST", "/{index}/_async_search", self.h_submit_async_search)
        add("GET", "/_async_search/{id}", self.h_get_async_search)
        add("DELETE", "/_async_search/{id}", self.h_delete_async_search)
        add("PUT", "/_data_stream/{name}", self.h_create_data_stream)
        add("GET", "/_data_stream", self.h_get_data_streams)
        add("GET", "/_data_stream/{name}", self.h_get_data_streams)
        add("DELETE", "/_data_stream/{name}", self.h_delete_data_stream)
        add("PUT", "/_ilm/policy/{name}", self.h_put_ilm_policy)
        add("GET", "/_ilm/policy", self.h_get_ilm_policy)
        add("GET", "/_ilm/policy/{name}", self.h_get_ilm_policy)
        add("DELETE", "/_ilm/policy/{name}", self.h_delete_ilm_policy)
        add("GET", "/{index}/_ilm/explain", self.h_ilm_explain)
        add("POST", "/_ilm/_tick", self.h_ilm_tick)
        add("GET,POST", "/{index}/_eql/search", self.h_eql_search)
        add("GET,POST", "/{index}/_graph/explore", self.h_graph_explore)
        # transform (x-pack/plugin/transform)
        add("PUT", "/_transform/{id}", self.h_put_transform)
        add("GET", "/_transform", self.h_get_transform)
        add("GET", "/_transform/_stats", self.h_transform_stats)
        add("GET", "/_transform/{id}", self.h_get_transform)
        add("GET", "/_transform/{id}/_stats", self.h_transform_stats)
        add("POST", "/_transform/_preview", self.h_preview_transform)
        add("POST", "/_transform/{id}/_start", self.h_start_transform)
        add("POST", "/_transform/{id}/_stop", self.h_stop_transform)
        add("DELETE", "/_transform/{id}", self.h_delete_transform)
        # rollup (x-pack/plugin/rollup)
        add("PUT", "/_rollup/job/{id}", self.h_put_rollup_job)
        add("GET", "/_rollup/job", self.h_get_rollup_jobs)
        add("GET", "/_rollup/job/{id}", self.h_get_rollup_jobs)
        add("DELETE", "/_rollup/job/{id}", self.h_delete_rollup_job)
        add("POST", "/_rollup/job/{id}/_start", self.h_start_rollup_job)
        add("POST", "/_rollup/job/{id}/_stop", self.h_stop_rollup_job)
        add("GET", "/_rollup/data/{pattern}", self.h_rollup_caps)
        add("GET,POST", "/{index}/_rollup_search", self.h_rollup_search)
        # watcher (x-pack/plugin/watcher)
        add("PUT,POST", "/_watcher/watch/{id}", self.h_put_watch)
        add("GET", "/_watcher/watch/{id}", self.h_get_watch)
        add("DELETE", "/_watcher/watch/{id}", self.h_delete_watch)
        add("PUT,POST", "/_watcher/watch/{id}/_execute",
            self.h_execute_watch)
        add("PUT,POST", "/_watcher/watch/{id}/_activate",
            self.h_activate_watch)
        add("PUT,POST", "/_watcher/watch/{id}/_deactivate",
            self.h_deactivate_watch)
        add("GET", "/_watcher/stats", self.h_watcher_stats)
        add("POST", "/_watcher/_tick", self.h_watcher_tick)
        # ccr (x-pack/plugin/ccr)
        add("GET", "/{index}/_ccr/shard_changes", self.h_ccr_changes)
        add("PUT,POST", "/{index}/_ccr/follow", self.h_ccr_follow)
        add("POST", "/{index}/_ccr/pause_follow", self.h_ccr_pause)
        add("POST", "/{index}/_ccr/resume_follow", self.h_ccr_resume)
        add("POST", "/{index}/_ccr/unfollow", self.h_ccr_unfollow)
        add("GET", "/_ccr/stats", self.h_ccr_stats)
        add("POST", "/_ccr/_tick", self.h_ccr_tick)
        add("PUT", "/_ccr/auto_follow/{name}", self.h_ccr_put_auto)
        add("GET", "/_ccr/auto_follow", self.h_ccr_get_auto)
        add("GET", "/_ccr/auto_follow/{name}", self.h_ccr_get_auto)
        add("DELETE", "/_ccr/auto_follow/{name}", self.h_ccr_del_auto)
        # ml (x-pack/plugin/ml)
        add("PUT", "/_ml/anomaly_detectors/{job_id}", self.h_ml_put_job)
        add("GET", "/_ml/anomaly_detectors", self.h_ml_get_jobs)
        add("GET", "/_ml/anomaly_detectors/_stats", self.h_ml_job_stats)
        add("GET", "/_ml/anomaly_detectors/{job_id}", self.h_ml_get_jobs)
        add("GET", "/_ml/anomaly_detectors/{job_id}/_stats",
            self.h_ml_job_stats)
        add("DELETE", "/_ml/anomaly_detectors/{job_id}",
            self.h_ml_delete_job)
        add("POST", "/_ml/anomaly_detectors/{job_id}/_open",
            self.h_ml_open_job)
        add("POST", "/_ml/anomaly_detectors/{job_id}/_close",
            self.h_ml_close_job)
        add("POST", "/_ml/anomaly_detectors/{job_id}/_data",
            self.h_ml_post_data)
        add("POST", "/_ml/anomaly_detectors/{job_id}/_flush",
            self.h_ml_flush_job)
        add("GET,POST", "/_ml/anomaly_detectors/{job_id}/results/buckets",
            self.h_ml_get_buckets)
        add("GET,POST", "/_ml/anomaly_detectors/{job_id}/results/records",
            self.h_ml_get_records)
        add("GET,POST",
            "/_ml/anomaly_detectors/{job_id}/results/overall_buckets",
            self.h_ml_overall_buckets)
        add("GET", "/_ml/anomaly_detectors/{job_id}/model_snapshots",
            self.h_ml_get_snapshots)
        add("POST", "/_ml/anomaly_detectors/{job_id}/model_snapshots"
            "/{snapshot_id}/_revert", self.h_ml_revert_snapshot)
        add("PUT", "/_ml/datafeeds/{feed_id}", self.h_ml_put_datafeed)
        add("GET", "/_ml/datafeeds", self.h_ml_get_datafeeds)
        add("GET", "/_ml/datafeeds/_stats", self.h_ml_datafeed_stats)
        add("GET", "/_ml/datafeeds/{feed_id}", self.h_ml_get_datafeeds)
        add("GET", "/_ml/datafeeds/{feed_id}/_stats",
            self.h_ml_datafeed_stats)
        add("DELETE", "/_ml/datafeeds/{feed_id}", self.h_ml_del_datafeed)
        add("POST", "/_ml/datafeeds/{feed_id}/_start",
            self.h_ml_start_datafeed)
        add("POST", "/_ml/datafeeds/{feed_id}/_stop",
            self.h_ml_stop_datafeed)
        add("GET,POST", "/_ml/datafeeds/{feed_id}/_preview",
            self.h_ml_preview_datafeed)
        add("PUT", "/_ml/trained_models/{model_id}", self.h_ml_put_model)
        add("GET", "/_ml/trained_models", self.h_ml_get_models)
        add("GET", "/_ml/trained_models/_stats", self.h_ml_model_stats)
        add("GET", "/_ml/trained_models/{model_id}", self.h_ml_get_models)
        add("GET", "/_ml/trained_models/{model_id}/_stats",
            self.h_ml_model_stats)
        add("DELETE", "/_ml/trained_models/{model_id}",
            self.h_ml_del_model)
        add("POST", "/_ml/trained_models/{model_id}/_infer",
            self.h_ml_infer)
        add("POST", "/_ml/trained_models/{model_id}/deployment/_infer",
            self.h_ml_infer)
        add("GET,POST", "/_ml/data_frame/analytics/_explain",
            self.h_ml_explain_analytics)
        add("PUT", "/_ml/data_frame/analytics/{id}",
            self.h_ml_put_analytics)
        add("GET", "/_ml/data_frame/analytics", self.h_ml_get_analytics)
        add("GET", "/_ml/data_frame/analytics/_stats",
            self.h_ml_analytics_stats)
        add("GET", "/_ml/data_frame/analytics/{id}",
            self.h_ml_get_analytics)
        add("GET", "/_ml/data_frame/analytics/{id}/_stats",
            self.h_ml_analytics_stats)
        add("DELETE", "/_ml/data_frame/analytics/{id}",
            self.h_ml_del_analytics)
        add("POST", "/_ml/data_frame/analytics/{id}/_start",
            self.h_ml_start_analytics)
        add("POST", "/_ml/data_frame/analytics/{id}/_stop",
            self.h_ml_stop_analytics)
        add("PUT", "/_ml/calendars/{calendar_id}", self.h_ml_put_calendar)
        add("GET", "/_ml/calendars", self.h_ml_get_calendars)
        add("GET", "/_ml/calendars/{calendar_id}", self.h_ml_get_calendars)
        add("DELETE", "/_ml/calendars/{calendar_id}",
            self.h_ml_del_calendar)
        add("POST", "/_ml/calendars/{calendar_id}/events",
            self.h_ml_post_cal_events)
        add("GET", "/_ml/calendars/{calendar_id}/events",
            self.h_ml_get_cal_events)
        add("PUT", "/_ml/filters/{filter_id}", self.h_ml_put_filter)
        add("GET", "/_ml/filters", self.h_ml_get_filters)
        add("GET", "/_ml/filters/{filter_id}", self.h_ml_get_filters)
        add("DELETE", "/_ml/filters/{filter_id}", self.h_ml_del_filter)
        add("GET", "/_ml/info", self.h_ml_info)
        add("POST", "/_ml/set_upgrade_mode", self.h_ml_upgrade_mode)
        # enrich (x-pack/plugin/enrich)
        add("PUT", "/_enrich/policy/{name}", self.h_put_enrich_policy)
        add("GET", "/_enrich/policy", self.h_get_enrich_policy)
        add("GET", "/_enrich/policy/{name}", self.h_get_enrich_policy)
        add("DELETE", "/_enrich/policy/{name}",
            self.h_delete_enrich_policy)
        add("PUT,POST", "/_enrich/policy/{name}/_execute",
            self.h_execute_enrich_policy)
        # logstash config management (x-pack logstash plugin)
        add("PUT", "/_logstash/pipeline/{id}", self.h_logstash_put)
        add("GET", "/_logstash/pipeline", self.h_logstash_get)
        add("GET", "/_logstash/pipeline/{id}", self.h_logstash_get)
        add("DELETE", "/_logstash/pipeline/{id}", self.h_logstash_delete)
        # repositories metering (x-pack repositories-metering-api)
        add("GET", "/_nodes/{node_id}/_repositories_metering",
            self.h_repositories_metering)
        # searchable snapshots + frozen indices + autoscaling (x-pack)
        add("POST", "/_snapshot/{repo}/{snap}/_mount",
            self.h_mount_snapshot)
        add("GET", "/_searchable_snapshots/stats",
            self.h_searchable_snapshot_stats)
        add("GET", "/{index}/_searchable_snapshots/stats",
            self.h_searchable_snapshot_stats)
        add("POST", "/_searchable_snapshots/cache/clear",
            self.h_searchable_snapshot_clear_cache)
        add("POST", "/{index}/_searchable_snapshots/cache/clear",
            self.h_searchable_snapshot_clear_cache)
        add("POST", "/{index}/_freeze", self.h_freeze_index)
        add("POST", "/{index}/_unfreeze", self.h_unfreeze_index)
        add("PUT", "/_autoscaling/policy/{name}",
            self.h_autoscaling_put_policy)
        add("GET", "/_autoscaling/policy/{name}",
            self.h_autoscaling_get_policy)
        add("DELETE", "/_autoscaling/policy/{name}",
            self.h_autoscaling_del_policy)
        add("GET", "/_autoscaling/capacity", self.h_autoscaling_capacity)
        # slm (x-pack snapshot lifecycle management)
        add("GET", "/_slm/policy", self.h_slm_get_policy)
        add("GET", "/_slm/stats", self.h_slm_stats)
        add("GET", "/_slm/status", self.h_slm_status)
        add("POST", "/_slm/start", self.h_slm_start)
        add("POST", "/_slm/stop", self.h_slm_stop)
        add("POST", "/_slm/_execute_retention", self.h_slm_retention)
        add("POST", "/_slm/_tick", self.h_slm_tick)
        add("PUT", "/_slm/policy/{policy_id}", self.h_slm_put_policy)
        add("GET", "/_slm/policy/{policy_id}", self.h_slm_get_policy)
        add("DELETE", "/_slm/policy/{policy_id}", self.h_slm_del_policy)
        add("PUT,POST", "/_slm/policy/{policy_id}/_execute",
            self.h_slm_execute)
        # license + /_xpack (x-pack/plugin/core license/)
        add("GET", "/_license", self.h_get_license)
        add("PUT,POST", "/_license", self.h_put_license)
        add("DELETE", "/_license", self.h_delete_license)
        add("POST", "/_license/start_trial", self.h_start_trial)
        add("POST", "/_license/start_basic", self.h_start_basic)
        add("GET", "/_license/trial_status", self.h_trial_status)
        add("GET", "/_license/basic_status", self.h_basic_status)
        add("GET", "/_xpack", self.h_xpack_info)
        add("GET", "/_xpack/usage", self.h_xpack_usage)
        # deprecation checkup (x-pack/plugin/deprecation)
        add("GET", "/_migration/deprecations", self.h_deprecations)
        add("GET", "/{index}/_migration/deprecations",
            self.h_deprecations)
        # monitoring (x-pack/plugin/monitoring)
        add("POST,PUT", "/_monitoring/bulk", self.h_monitoring_bulk)
        add("POST", "/_monitoring/_collect", self.h_monitoring_collect)
        add("POST", "/_monitoring/_tick", self.h_monitoring_tick)
        add("GET,POST", "/_sql", self.h_sql)
        add("POST", "/_sql/translate", self.h_sql_translate)
        add("POST", "/_sql/close", self.h_sql_close)
        add("PUT,POST", "/_security/api_key", self.h_create_api_key)
        add("DELETE", "/_security/api_key", self.h_invalidate_api_key)
        add("GET", "/_security/api_key", self.h_get_api_keys)
        add("GET", "/_security/_authenticate", self.h_authenticate)
        # native users + roles (x-pack security RBAC — security/rbac.py)
        add("GET,POST", "/_security/user/_has_privileges",
            self.h_has_privileges)
        add("PUT,POST", "/_security/user/{username}", self.h_put_user)
        add("GET", "/_security/user", self.h_get_users)
        add("GET", "/_security/user/{username}", self.h_get_users)
        add("DELETE", "/_security/user/{username}", self.h_delete_user)
        add("PUT,POST", "/_security/user/{username}/_password",
            self.h_change_password)
        add("PUT,POST", "/_security/user/{username}/_enable",
            self.h_enable_user)
        add("PUT,POST", "/_security/user/{username}/_disable",
            self.h_disable_user)
        add("PUT,POST", "/_security/role/{name}", self.h_put_role)
        add("GET", "/_security/role", self.h_get_roles)
        add("GET", "/_security/role/{name}", self.h_get_roles)
        add("DELETE", "/_security/role/{name}", self.h_delete_role)
        add("GET", "/_nodes/hot_threads", self.h_hot_threads)
        add("GET", "/_nodes/{node_id}/hot_threads", self.h_hot_threads)
        add("POST", "/_nodes/reload_secure_settings",
            self.h_reload_secure_settings)
        add("POST", "/_nodes/{node_id}/reload_secure_settings",
            self.h_reload_secure_settings)
        add("PUT", "/{index}/_block/{block}", self.h_add_block)
        add("GET", "/_nodes/telemetry", self.h_nodes_telemetry)
        add("GET", "/_prometheus/metrics", self.h_prometheus)
        add("GET", "/_trace", self.h_trace_list)
        add("GET", "/_trace/{trace_id}", self.h_trace_get)
        add("GET", "/_insights/top_queries",
            self.h_insights_top_queries)
        add("GET", "/_telemetry/history", self.h_telemetry_history)
        add("GET", "/_profiler/timeline", self.h_profiler_timeline)
        add("GET", "/_profiler/flamegraph", self.h_profiler_flamegraph)
        add("GET", "/_flight_recorder", self.h_flight_recorder)
        add("GET", "/_flight_recorder/captures", self.h_flight_captures)
        add("GET", "/_flight_recorder/captures/{capture_id}",
            self.h_flight_capture_get)
        add("GET", "/_health_report", self.h_health_report)
        add("GET", "/_health_report/{indicator}", self.h_health_report)
        add("GET", "/_nodes/stats", self.h_nodes_stats)
        add("GET", "/_nodes/stats/{metric}", self.h_nodes_stats)
        add("GET", "/_nodes/stats/{metric}/{index_metric}",
            self.h_nodes_stats)
        add("GET", "/_nodes/{node_id}/stats", self.h_nodes_stats)
        add("GET", "/_nodes/{node_id}/stats/{metric}",
            self.h_nodes_stats)
        add("GET", "/_nodes/{node_id}", self.h_nodes)
        add("GET", "/_nodes/{node_id}/{metric}", self.h_nodes)
        # cat
        add("GET,POST", "/_msearch", self.h_msearch)
        add("GET,POST", "/{index}/_msearch", self.h_msearch)
        add("GET", "/_cat/shards/{index}", self.h_cat_shards)
        add("GET", "/_cat/indices", self.h_cat_indices)
        add("GET", "/_cat/indices/{index}", self.h_cat_indices)
        add("GET", "/_cat/health", self.h_cat_health)
        add("GET", "/_cat/count", self.h_cat_count)
        add("GET", "/_cat/count/{index}", self.h_cat_count)
        add("GET", "/_cat/shards", self.h_cat_shards)
        add("GET", "/_cat/nodes", self.h_cat_nodes)
        add("GET", "/_cat/aliases", self.h_cat_aliases)
        add("GET", "/_cat/templates", self.h_cat_templates)
        add("GET", "/_cat/templates/{name}", self.h_cat_templates)
        add("GET", "/_resolve/index/{name}", self.h_resolve_index)
        add("GET", "/_segments", self.h_segments)
        add("GET", "/{index}/_segments", self.h_segments)
        add("GET", "/_shard_stores", self.h_shard_stores)
        add("GET", "/{index}/_shard_stores", self.h_shard_stores)
        add("POST", "/_cache/clear", self.h_clear_cache)
        add("POST", "/{index}/_cache/clear", self.h_clear_cache)
        add("GET,POST", "/{index}/_termvectors", self.h_termvectors)
        add("GET,POST", "/_mtermvectors", self.h_mtermvectors)
        add("GET,POST", "/{index}/_mtermvectors", self.h_mtermvectors)
        add("GET", "/_recovery", self.h_recovery)
        add("GET", "/{index}/_recovery", self.h_recovery)
        add("GET", "/_cat/allocation", self.h_cat_allocation)
        add("GET", "/_cat/allocation/{node_id}", self.h_cat_allocation)
        add("POST", "/_cluster/voting_config_exclusions",
            self.h_post_voting_exclusions)
        add("DELETE", "/_cluster/voting_config_exclusions",
            self.h_delete_voting_exclusions)
        add("PUT,POST", "/_component_template/{name}",
            self.h_put_component_template)
        add("GET", "/_component_template/{name}",
            self.h_get_component_template)
        add("GET", "/_component_template", self.h_get_component_template)
        add("DELETE", "/_component_template/{name}",
            self.h_delete_component_template)
        add("GET", "/_cat/aliases/{name}", self.h_cat_aliases)
        add("GET", "/_cat/fielddata", self.h_cat_fielddata)
        add("GET", "/_cat/fielddata/{fields}", self.h_cat_fielddata)
        add("GET", "/_cat/nodeattrs", self.h_cat_nodeattrs)
        add("GET", "/_cat/plugins", self.h_cat_plugins)
        add("GET", "/_cat/recovery", self.h_cat_recovery)
        add("GET", "/_cat/recovery/{index}", self.h_cat_recovery)
        add("GET", "/_cat/repositories", self.h_cat_repositories)
        add("GET", "/_cat/segments", self.h_cat_segments)
        add("GET", "/_cat/segments/{index}", self.h_cat_segments)
        add("GET", "/_cat/snapshots", self.h_cat_snapshots)
        add("GET", "/_cat/snapshots/{repository}", self.h_cat_snapshots)
        add("GET", "/_cat/tasks", self.h_cat_tasks)
        add("GET", "/_cat/thread_pool", self.h_cat_thread_pool)
        add("GET", "/_cat/thread_pool/{pools}", self.h_cat_thread_pool)
        # search / count / mget / analyze / field caps
        add("GET,POST", "/_search", self.h_search)
        add("GET,POST", "/{index}/_search", self.h_search)
        add("GET,POST", "/_search/scroll", self.h_scroll)
        add("GET,POST", "/_search/scroll/{scroll_id}", self.h_scroll)
        add("DELETE", "/_search/scroll", self.h_clear_scroll)
        add("DELETE", "/_search/scroll/{scroll_id}", self.h_clear_scroll)
        add("GET,POST", "/{index}/_validate/query", self.h_validate_query)
        add("GET,POST", "/_validate/query", self.h_validate_query)
        add("GET,POST", "/_count", self.h_count)
        add("GET,POST", "/{index}/_count", self.h_count)
        add("GET,POST", "/_mget", self.h_mget)
        add("GET,POST", "/{index}/_mget", self.h_mget)
        add("GET,POST", "/_analyze", self.h_analyze)
        add("GET,POST", "/{index}/_analyze", self.h_analyze)
        add("GET,POST", "/_field_caps", self.h_field_caps)
        add("GET,POST", "/{index}/_field_caps", self.h_field_caps)
        add("POST", "/{index}/_pit", self.h_open_pit)
        add("DELETE", "/_pit", self.h_close_pit)
        # snapshots / repositories
        add("PUT,POST", "/_snapshot/{repo}", self.h_put_repo)
        add("GET", "/_snapshot", self.h_get_repo)
        add("GET", "/_snapshot/{repo}", self.h_get_repo)
        add("DELETE", "/_snapshot/{repo}", self.h_delete_repo)
        add("POST", "/_snapshot/{repo}/_verify", self.h_verify_repo)
        add("POST", "/_snapshot/{repo}/_cleanup", self.h_cleanup_repo)
        add("PUT,POST", "/_snapshot/{repo}/{snap}", self.h_create_snapshot)
        add("GET", "/_snapshot/{repo}/{snap}", self.h_get_snapshot)
        add("GET", "/_snapshot/{repo}/{snap}/_status",
            self.h_snapshot_status)
        add("DELETE", "/_snapshot/{repo}/{snap}", self.h_delete_snapshot)
        add("PUT,POST", "/_snapshot/{repo}/{snap}/_clone/{target}",
            self.h_clone_snapshot)
        add("POST", "/_snapshot/{repo}/{snap}/_restore",
            self.h_restore_snapshot)
        # ingest pipelines (_simulate before {id}: routes match in
        # registration order and {id} would swallow the literal _simulate)
        add("POST,GET", "/_ingest/pipeline/_simulate",
            self.h_simulate_pipeline)
        add("POST,GET", "/_ingest/pipeline/{id}/_simulate",
            self.h_simulate_pipeline)
        add("PUT", "/_ingest/pipeline/{id}", self.h_put_pipeline)
        add("GET", "/_ingest/pipeline/{id}", self.h_get_pipeline)
        add("GET", "/_ingest/pipeline", self.h_get_pipeline)
        add("DELETE", "/_ingest/pipeline/{id}", self.h_delete_pipeline)
        # bulk + by-query
        add("POST,PUT", "/_bulk", self.h_bulk)
        add("POST,PUT", "/{index}/_bulk", self.h_bulk)
        add("POST", "/{index}/_delete_by_query", self.h_delete_by_query)
        add("POST", "/{index}/_update_by_query", self.h_update_by_query)
        add("POST", "/_reindex", self.h_reindex)
        add("GET,POST", "/{index}/_explain/{id}", self.h_explain)
        add("GET,POST", "/{index}/_termvectors/{id}", self.h_termvectors)
        add("GET", "/_tasks", self.h_tasks)
        add("GET", "/_tasks/{task_id}", self.h_task_get)
        add("POST", "/_tasks/_cancel", self.h_tasks_cancel)
        add("POST", "/_tasks/{task_id}/_cancel", self.h_tasks_cancel)
        # search templates (modules/lang-mustache:
        # RestSearchTemplateAction / RestRenderSearchTemplateAction /
        # RestMultiSearchTemplateAction)
        add("GET,POST", "/_search/template", self.h_search_template)
        add("GET,POST", "/{index}/_search/template",
            self.h_search_template)
        add("GET,POST", "/_render/template", self.h_render_template)
        add("GET,POST", "/_render/template/{id}",
            self.h_render_template)
        add("GET,POST", "/_msearch/template",
            self.h_msearch_template)
        add("GET,POST", "/{index}/_msearch/template",
            self.h_msearch_template)
        # stored scripts + script metadata
        add("PUT,POST", "/_scripts/{id}", self.h_put_script)
        add("GET", "/_scripts/{id}", self.h_get_script)
        add("DELETE", "/_scripts/{id}", self.h_delete_script)
        add("GET", "/_script_context", self.h_script_context)
        add("GET", "/_script_language", self.h_script_language)
        add("GET,POST", "/{index}/_search_shards", self.h_search_shards)
        add("GET,POST", "/_search_shards", self.h_search_shards)
        add("GET,POST", "/_rank_eval", self.h_rank_eval)
        add("GET,POST", "/{index}/_rank_eval", self.h_rank_eval)
        # templates
        add("POST", "/_index_template/_simulate_index/{name}",
            self.h_simulate_index_template)
        add("POST", "/_index_template/_simulate/{name}",
            self.h_simulate_template)
        add("POST", "/_index_template/_simulate",
            self.h_simulate_template)
        add("PUT,POST", "/_index_template/{name}", self.h_put_template)
        add("GET", "/_index_template/{name}", self.h_get_template)
        add("GET", "/_index_template", self.h_get_template)
        add("DELETE", "/_index_template/{name}", self.h_delete_template)
        add("PUT,POST", "/_template/{name}", self.h_put_template_legacy)
        add("GET", "/_template/{name}", self.h_get_template_legacy)
        add("GET", "/_template", self.h_get_template_legacy)
        add("DELETE", "/_template/{name}", self.h_delete_template)
        # aliases
        add("POST", "/_aliases", self.h_update_aliases)
        add("GET", "/_alias", self.h_get_alias)
        add("GET", "/_alias/{name}", self.h_get_alias)
        add("GET", "/{index}/_alias", self.h_get_alias)
        add("GET", "/{index}/_alias/{name}", self.h_get_alias)
        add("PUT,POST", "/{index}/_alias/{name}", self.h_put_alias)
        add("PUT,POST", "/{index}/_aliases/{name}", self.h_put_alias)
        add("DELETE", "/{index}/_alias/{name}", self.h_delete_alias)
        # index admin
        add("GET", "/_stats", self.h_stats)
        add("GET", "/_stats/{metric}", self.h_stats)
        add("GET", "/{index}/_stats", self.h_stats)
        add("GET", "/{index}/_stats/{metric}", self.h_stats)
        add("POST", "/{index}/_rollover", self.h_rollover)
        add("POST", "/{index}/_rollover/{new_index}", self.h_rollover)
        add("PUT,POST", "/{index}/_shrink/{target}", self.h_shrink)
        add("PUT,POST", "/{index}/_split/{target}", self.h_split)
        add("PUT,POST", "/{index}/_clone/{target}", self.h_clone)
        add("POST", "/{index}/_close", self.h_close_index)
        add("POST", "/{index}/_open", self.h_open_index)
        add("GET,PUT,POST", "/{index}/_mapping", self.h_mapping)
        add("GET", "/_mapping", self.h_mapping)
        add("GET", "/{index}/_mapping/field/{fields}",
            self.h_field_mapping)
        add("GET", "/_mapping/field/{fields}", self.h_field_mapping)
        add("GET,PUT", "/{index}/_settings", self.h_settings)
        add("GET,PUT", "/_settings", self.h_settings)
        add("GET", "/{index}/_settings/{name}", self.h_settings)
        add("GET", "/_settings/{name}", self.h_settings)
        add("POST", "/{index}/_refresh", self.h_refresh)
        add("POST", "/_refresh", self.h_refresh)
        add("POST", "/{index}/_flush", self.h_flush)
        add("POST", "/_flush", self.h_flush)
        add("POST", "/{index}/_forcemerge", self.h_forcemerge)
        # documents
        add("PUT,POST", "/{index}/_doc/{id}", self.h_index_doc)
        add("POST", "/{index}/_doc", self.h_index_doc_auto)
        add("GET,HEAD", "/{index}/_doc/{id}", self.h_get_doc)
        add("DELETE", "/{index}/_doc/{id}", self.h_delete_doc)
        add("PUT,POST", "/{index}/_create/{id}", self.h_create_doc)
        add("GET,HEAD", "/{index}/_source/{id}", self.h_get_source)
        add("POST", "/{index}/_update/{id}", self.h_update_doc)
        # index CRUD last ({index} captures anything)
        add("PUT", "/{index}", self.h_create_index)
        add("DELETE", "/{index}", self.h_delete_index)
        add("GET,HEAD", "/{index}", self.h_get_index)

    def handle(self, method: str, path: str, query: str,
               body: bytes,
               headers: Optional[dict] = None,
               resp_headers: Optional[dict] = None) \
            -> Tuple[int, str, bytes]:
        """Entry: x-content negotiation around the JSON-native core
        (reference: ``RestController.dispatchRequest`` resolving
        ``XContentType`` from Content-Type/Accept — libs/x-content).

        ``resp_headers``: optional out-param dict — receives the echoed
        ``X-Opaque-Id`` and the request's ``Trace-Id`` (reference: the
        opaque id is echoed on every response; the trace id is the
        ``GET /_trace/{id}`` handle)."""
        self._trace_tls.value = None
        self._extra_hdr_tls.value = None
        accept = None
        if headers:
            hmap = {k.lower(): v for k, v in headers.items()}
            ct = hmap.get("content-type")
            accept = hmap.get("accept")
            if body and ct:
                from ..common.xcontent import (UnsupportedContentType,
                                               decode_request)
                try:
                    body = decode_request(body, ct)
                except UnsupportedContentType as e:
                    payload = {"error": {"type": e.error_type,
                                         "reason": str(e)},
                               "status": e.status}
                    self._stamp_trace_echo(resp_headers, headers)
                    return (e.status, JSON_CT,
                            json.dumps(payload).encode())
        status, out_ct, payload = self._handle_json(
            method, path, query, body, headers)
        self._stamp_trace_echo(resp_headers, headers)
        # error-declared response headers (QoS Retry-After, security
        # WWW-Authenticate) reach the wire, not just the error body
        extra = getattr(self._extra_hdr_tls, "value", None)
        if resp_headers is not None and extra:
            for k, v in extra.items():
                resp_headers.setdefault(k, v)
        if accept and payload:
            from ..common.xcontent import (UnsupportedContentType,
                                           encode_response)
            try:
                payload, out_ct = encode_response(payload, out_ct,
                                                  accept)
            except UnsupportedContentType as e:
                err = {"error": {"type": e.error_type,
                                 "reason": str(e)}, "status": e.status}
                return e.status, JSON_CT, json.dumps(err).encode()
        return status, out_ct, payload

    def _stamp_trace_echo(self, resp_headers: Optional[dict],
                          headers: Optional[dict]) -> None:
        """Echo ``Trace-Id``/``X-Opaque-Id`` into the response out-param.
        Error paths that never entered a traced span (unknown-route
        400/405, security 401/403, content-type 415) still echo: the
        incoming trace id is adopted — or a fresh one minted — so EVERY
        response, success or failure, is correlatable (the 4xx/5xx
        regression the flight-recorder PR closed)."""
        if resp_headers is None:
            return
        info = getattr(self._trace_tls, "value", None)
        if not info or not info[0]:
            from ..common import tracing as _tracing
            # the HTTP edge's trace (http[in]) when there is one
            tid = _tracing.current_trace_id() or \
                _tracing.parse_incoming(headers)[0]
            hmap = {str(k).lower(): v for k, v in (headers or {}).items()}
            info = (tid or _tracing.new_trace_id(),
                    (info[1] if info else None) or hmap.get("x-opaque-id"))
            self._trace_tls.value = info
        tid, opaque = info
        if tid:
            resp_headers["Trace-Id"] = tid
        if opaque:
            resp_headers["X-Opaque-Id"] = opaque

    def _error_response(self, e: Exception) -> Tuple[int, str, bytes]:
        """ES-shaped error body; ``header`` metadata on the error
        (Retry-After, WWW-Authenticate) is additionally stashed for
        promotion to REAL response headers by :meth:`handle`."""
        status, payload = _error_payload(e)
        hdr = payload.get("error", {}).get("header")
        if hdr:
            stash = getattr(self._extra_hdr_tls, "value", None) or {}
            for k, v in hdr.items():
                stash[str(k)] = v[0] if isinstance(v, (list, tuple)) \
                    and v else v
            self._extra_hdr_tls.value = stash
        return status, JSON_CT, json.dumps(payload).encode()

    @staticmethod
    def _qos_body(body) -> Optional[dict]:
        """Best-effort parse of the request body for QoS priority
        classification (aggs / size:0 → analytics). NDJSON (bulk) and
        junk parse to None — those classify from the action alone."""
        if not body or not isinstance(body, (bytes, bytearray, str)):
            return None
        try:
            doc = json.loads(body)
            return doc if isinstance(doc, dict) else None
        except Exception:   # noqa: BLE001 — classification is advisory
            return None

    def _note_shed(self, body: Optional[dict], tenant, trace_id) -> None:
        """Fold one rejected (429) request into the query-insight
        sketches so a throttled tenant's rows distinguish served from
        shed traffic."""
        try:
            from ..search import query_insight as _qi
            if not _qi.insights_enabled():
                return
            _qi.store_for(self.node_id).observe(
                _qi.shape_of(body), tenant, shed=1.0,
                trace_id=trace_id, sample_body=body)
        except Exception:   # noqa: BLE001 — insight must not fail
            pass            # the rejection path either

    def _handle_json(self, method: str, path: str, query: str,
                     body: bytes,
                     headers: Optional[dict] = None) \
            -> Tuple[int, str, bytes]:
        from ..common import tracing as _tracing
        # rest[parse]: what a request pays before its action's handler
        # (security, query string, the scan of the route table)
        with _tracing.span("rest[parse]"):
            if self.security.enabled and self.enforce_security and \
                    not getattr(self._internal_tls, "active", False):
                # every route requires credentials when security is on
                # (reference: SecurityRestFilter wraps the whole dispatcher);
                # the cluster front enforces at ITS door and disables this
                # inner check for trusted internal dispatches
                try:
                    self._principal_tls.value = \
                        self.security.authenticate(headers)
                    # role-based authorization on every route except the
                    # self-service endpoints any authenticated user may
                    # call (AuthorizationService.authorize +
                    # RestAuthenticateAction / HasPrivileges)
                    if path.rstrip("/") not in (
                            "/_security/_authenticate",
                            "/_security/user/_has_privileges"):
                        self.security.rbac.authorize(
                            self._principal_tls.value, method, path)
                except Exception as e:   # noqa: BLE001 — 401/403 ES body
                    return self._error_response(e)
            if not getattr(self._internal_tls, "active", False):
                # fresh warning scope per EXTERNAL request only — internal
                # re-dispatches (SQL/transform/ML seams) keep accumulating
                # into the outer request's scope
                from ..xpack.deprecation import begin_request
                begin_request()
            params = {k: v[-1] for k, v in
                      parse_qs(query, keep_blank_values=True).items()}
            if query:
                # bare flags like ?v
                for part in query.split("&"):
                    if part and "=" not in part:
                        params[part] = "true"
            # match routes on the ENCODED path, decode per captured segment
            # (RestUtils.decodeComponent: %2F inside one segment — date-math
            # index names, slashed ids — must not split routing)
            path = path.rstrip("/") or "/"
            while "//" in path:
                # an empty path segment (index: [] in specs) collapses away
                path = path.replace("//", "/")
            fn = kwargs = None
            matched_path = False
            for m, rx, names, route_fn in self._routes:
                match = rx.match(path)
                if match is None:
                    continue
                matched_path = True
                if m != method and not (method == "HEAD" and m == "GET"):
                    continue
                fn = route_fn
                kwargs = {k: (unquote(v) if v is not None else v)
                          for k, v in zip(names, match.groups())}
                break
        if fn is None:
            if matched_path:
                status, payload = 405, {
                    "error": f"Incorrect HTTP method for uri [{path}] and "
                             f"method [{method}]", "status": 405}
            else:
                status, payload = 400, {
                    "error": f"no handler found for uri [{path}] and method "
                             f"[{method}]", "status": 400}
            return status, JSON_CT, json.dumps(payload).encode()
        # every request runs as a registered task for its lifetime
        # (reference: TaskManager.java:76 registers every action) and
        # inside a traced root span: the trace id is minted here — or
        # adopted from an incoming traceparent/trace.id header — and
        # follows the request through coordinator → shard fan-out →
        # microbatch dispatch (common/tracing.py)
        hmap2 = {str(k).lower(): v for k, v in (headers or {}).items()}
        opaque = params.get("__x_opaque_id") or \
            hmap2.get("x-opaque-id")
        action = _action_name(method, path)
        desc = f"{method} {path}"
        if opaque:
            desc += f" [x-opaque-id={opaque}]"
        _op_token = _tracing.set_opaque_id(opaque)
        # the root span carries the tenant (X-Opaque-Id) so the
        # GET /_trace listing's ?tenant= filter works off the store
        root_attrs = {"action": action}
        if opaque:
            root_attrs["tenant"] = opaque
        try:
            with _tracing.span(f"rest[{action}]", node=self.node_id,
                               headers=headers, root=True,
                               attrs=root_attrs) as sp:
                task_headers = {"trace.id": sp.trace_id}
                if opaque:
                    task_headers["X-Opaque-Id"] = opaque
                self._trace_tls.value = (sp.trace_id, opaque)
                # QoS edge: classify + admission-check data-path
                # actions INSIDE the span (the 429 carries the
                # trace id; the journal event inherits the ambient
                # trace) but BEFORE task registration — a shed
                # request must cost O(1)
                _pri_token = None
                if action.startswith("indices:data/"):
                    from ..common import qos as _qos
                    if _qos.qos_enabled():
                        override = hmap2.get("x-es-priority")
                        qbody = None
                        if not override and \
                                action.startswith("indices:data/read"):
                            qbody = self._qos_body(body)
                        pri = _qos.classify(action=action,
                                            body=qbody,
                                            override=override)
                        decision = _qos.controller().admit(
                            tenant=opaque, priority=pri,
                            action=action)
                        if not decision.allowed:
                            sp.attrs["error"] = "QosRejectedError"
                            self._note_shed(qbody, opaque,
                                            sp.trace_id)
                            what = ("request throttled: tenant "
                                    "token bucket in debt"
                                    if decision.kind == "throttle"
                                    else "request shed: cluster "
                                    "overloaded")
                            return self._error_response(
                                _qos.QosRejectedError(
                                    what, decision, tenant=opaque))
                        _pri_token = _qos.bind_priority(pri)
                task = self.task_manager.register(
                    action,
                    description=desc + f" [trace.id={sp.trace_id}]",
                    headers=task_headers)
                self._req_task.task = task
                # resource attribution: the task's ledger rides the
                # request context (shard search / plane dispatch
                # charge it at stage boundaries), and the request
                # thread's CPU window opens here
                from ..node.task_manager import (bind_resources,
                                                 unbind_resources)
                _res_token = bind_resources(task.resources)
                # flight-recorder ambient context: journal events on
                # this request's path stamp node + task id
                from ..common import flightrec as _flightrec
                _fr_token = _flightrec.bind_ambient(
                    node=self.node_id, task=f"{task.node}:{task.id}")
                # continuous-profiler attribution: this thread
                # samples into the "rest" pool under this tenant
                # for the request's lifetime (the shape holder is
                # published by flightrec.bind_shape on the search
                # path) — nest-safe for internal re-dispatches
                from ..common import contprof as _contprof
                _cp_token = _contprof.bind_request_thread(opaque)
                task.resources.cpu_mark()
                try:
                    result = fn(params, body, **kwargs)
                except Exception as e:  # noqa: BLE001 — ES-shaped
                    sp.attrs["error"] = type(e).__name__
                    return self._error_response(e)
                finally:
                    if _pri_token is not None:
                        from ..common import qos as _qos
                        _qos.unbind_priority(_pri_token)
                    task.resources.cpu_release()
                    _contprof.unbind_request_thread(_cp_token)
                    _flightrec.reset_ambient(_fr_token)
                    unbind_resources(_res_token)
                    self._req_task.task = None
                    if task.running and \
                            not getattr(task, "async_detached", False):
                        self.task_manager.unregister(task)
                    # internal re-dispatches (monitoring fetch, SQL
                    # seams) overwrite the echo stash — the OUTER
                    # request's pair must win
                    self._trace_tls.value = (sp.trace_id, opaque)
        finally:
            _tracing._OPAQUE.reset(_op_token)
        # rest[render]: the handler's result to the bytes of the body
        with _tracing.span("rest[render]"):
            if isinstance(result, tuple) and len(result) == 3:
                # (status, content_type, str|bytes) — non-JSON bodies
                # (SQL txt/csv/tsv, hot_threads text) pick their own type
                st3, ct3, body3 = result
                if isinstance(body3, str):
                    body3 = body3.encode()
                return st3, ct3, body3
            if isinstance(result, tuple):
                status, payload = result
            else:
                status, payload = 200, result
            if isinstance(payload, (dict, list)):
                fp = params.get("filter_path")
                if fp and isinstance(payload, dict):
                    payload = _apply_filter_path(payload, fp)
                if params.get("format") == "yaml":
                    import yaml as _yaml
                    return (status, "application/yaml",
                            _yaml.safe_dump(payload).encode())
                return status, JSON_CT, json.dumps(payload).encode()
            if isinstance(payload, str):
                return status, "text/plain; charset=UTF-8", payload.encode()
            if payload is None:
                return status, JSON_CT, b"null"
            return status, JSON_CT, payload

    # ------------------------------------------------------------------
    # root / cluster
    # ------------------------------------------------------------------

    def h_root(self, params, body):
        return {
            "name": self.node_name,
            "cluster_name": self.cluster_name,
            "cluster_uuid": self.node_id,
            "version": {"number": "8.0.0-tpu",
                        "build_flavor": "tpu-native",
                        "lucene_version": "n/a"},
            "tagline": "You Know, for Search",
        }

    #: replica-allocation capacity emulated for health (the reference CI
    #: runs 2 data nodes: one replica per shard allocates, more stay
    #: unassigned → yellow)
    _HEALTH_REPLICA_CAP = 1

    def _health(self, index: Optional[str] = None,
                params: Optional[dict] = None) -> dict:
        params = params or {}
        try:
            names = self.indices.resolve(index)
        except IndexNotFoundError:
            if params.get("ignore_unavailable") in ("true", ""):
                names = []
            else:
                raise
        ew = (params.get("expand_wildcards") or "all").split(",")
        if index and (any(c in index for c in "*?")
                      or index == "_all") and "all" not in ew:
            names = [n for n in names
                     if ("open" in ew
                         and not self.indices.indices[n].closed)
                     or ("closed" in ew
                         and self.indices.indices[n].closed)]
        per_index = {}
        for n in names:
            svc = self.indices.indices[n]
            repl = svc.num_replicas
            active_repl = min(repl, self._HEALTH_REPLICA_CAP)
            active = svc.num_shards * (1 + active_repl)
            unassigned = svc.num_shards * (repl - active_repl)
            per_index[n] = {
                "status": "yellow" if unassigned else "green",
                "number_of_shards": svc.num_shards,
                "number_of_replicas": repl,
                "active_primary_shards": svc.num_shards,
                "active_shards": active,
                "relocating_shards": 0,
                "initializing_shards": 0,
                "unassigned_shards": unassigned,
            }
        status = "yellow" if any(v["status"] == "yellow"
                                 for v in per_index.values()) else "green"
        total_active = sum(v["active_shards"] for v in per_index.values())
        out = {
            "cluster_name": self.cluster_name,
            "status": status,
            "timed_out": False,
            "number_of_nodes": 1,
            "number_of_data_nodes": 1,
            "active_primary_shards": sum(
                v["active_primary_shards"] for v in per_index.values()),
            "active_shards": total_active,
            "relocating_shards": 0,
            "initializing_shards": 0,
            "unassigned_shards": sum(
                v["unassigned_shards"] for v in per_index.values()),
            "delayed_unassigned_shards": 0,
            "number_of_pending_tasks": 0,
            "number_of_in_flight_fetch": 0,
            "task_max_waiting_in_queue_millis": 0,
            "active_shards_percent_as_number": 100.0,
        }
        level = params.get("level")
        if level in ("indices", "shards"):
            for n, v in per_index.items():
                if level == "shards":
                    svc = self.indices.indices[n]
                    v = dict(v, shards={
                        str(i): {"status": v["status"],
                                 "primary_active": True,
                                 "active_shards": v["active_shards"]
                                 // max(svc.num_shards, 1),
                                 "relocating_shards": 0,
                                 "initializing_shards": 0,
                                 "unassigned_shards":
                                     v["unassigned_shards"]
                                     // max(svc.num_shards, 1)}
                        for i in range(svc.num_shards)})
                    per_index[n] = v
            out["indices"] = per_index
        return out

    _STATUS_RANK = {"green": 0, "yellow": 1, "red": 2}

    def h_cluster_health(self, params, body, index=None):
        out = self._health(index, params)
        timed_out = False
        wn = params.get("wait_for_nodes")
        if wn is not None:
            try:
                if int(str(wn).lstrip(">=<")) > 1:
                    timed_out = True
            except ValueError:
                pass
        was = params.get("wait_for_active_shards")
        if was not in (None, "", "all") and \
                int(was) > out["active_shards"]:
            timed_out = True
        ws = params.get("wait_for_status")
        if ws in self._STATUS_RANK and \
                self._STATUS_RANK[out["status"]] > self._STATUS_RANK[ws]:
            timed_out = True
        if timed_out:
            out["timed_out"] = True
            return 408, out
        return out

    #: cluster-state response sections selectable by the metric path
    CLUSTER_STATE_METRICS = ("version", "master_node", "nodes",
                             "routing_table", "routing_nodes", "metadata",
                             "blocks", "customs")

    def _index_blocks(self) -> Dict[str, dict]:
        """Per-index block entries: an index may carry several blocks
        (closed AND read-only) at once."""
        out: Dict[str, dict] = {}
        for n, sv in self.indices.indices.items():
            entry = {}
            if sv.closed:
                entry["4"] = {"description": "index closed",
                              "retryable": False,
                              "levels": ["read", "write"]}
            if str(sv.settings.get("index.blocks.read_only",
                                   "")).lower() == "true":
                entry["5"] = {"description": "index read-only (api)",
                              "retryable": False,
                              "levels": ["write", "metadata_write"]}
            if entry:
                out[n] = entry
        return out

    def h_cluster_state(self, params, body, metric=None, index=None):
        """Cluster state (reference: ``RestClusterStateAction``): the
        single-node composition of the same sections the coordinator
        publishes in the multi-node tier; the metric path filters the
        emitted sections."""
        if index is not None and params.get(
                "ignore_unavailable") in ("true", ""):
            names = []
            for part in index.split(","):
                try:
                    names.extend(self.indices.resolve(part))
                except IndexNotFoundError:
                    pass
        else:
            names = self.indices.resolve(index)
        if not names and index and \
                params.get("allow_no_indices") == "false":
            raise IndexNotFoundError(index)
        ew = params.get("expand_wildcards", "open")
        if index and any(c in index for c in "*,") or index == "_all":
            if "closed" not in ew and "all" not in ew:
                names = [n for n in names
                         if not self.indices.indices[n].closed]
            elif ew == "closed":
                names = [n for n in names
                         if self.indices.indices[n].closed]
        meta_indices = {}
        routing_table = {}
        for n in names:
            svc = self.indices.indices[n]
            meta_indices[n] = {
                "state": "close" if getattr(svc, "closed", False)
                else "open",
                "settings": {"index": dict(svc.settings)},
                "mappings": svc.mapper.mapping_dict(),
                "aliases": sorted(svc.aliases),
            }
            routing_table[n] = {"shards": {
                str(s): [{"state": "STARTED", "primary": True,
                          "node": self.node_id, "shard": s, "index": n}]
                for s in range(svc.num_shards)}}
        sections = {
            "version": 1,
            "master_node": self.node_id,
            "blocks": {"indices": self._index_blocks()},
            "nodes": {self.node_id: {"name": self.node_name,
                                     "transport_address": "127.0.0.1:9300",
                                     "attributes": {}}},
            "routing_nodes": {"unassigned": [],
                              "nodes": {self.node_id: []}},
            "metadata": {"cluster_uuid": self.node_id,
                         "templates": self.templates,
                         "cluster_coordination": {
                             "voting_config_exclusions":
                                 list(self.voting_exclusions)},
                         "indices": meta_indices},
            "routing_table": {"indices": routing_table},
        }
        out = {"cluster_name": self.cluster_name,
               "cluster_uuid": self.node_id}
        wanted = set(self.CLUSTER_STATE_METRICS)
        if metric and metric != "_all":
            wanted = {m.strip() for m in metric.split(",")}
            bad = wanted - set(self.CLUSTER_STATE_METRICS)
            if bad:
                raise IllegalArgumentError(
                    f"request [/_cluster/state/{metric}] contains "
                    f"unrecognized metric: [{sorted(bad)[0]}]")
        out["state_uuid"] = self.node_id
        for k in self.CLUSTER_STATE_METRICS:
            if k in wanted and k in sections:
                v = sections[k]
                if k == "blocks" and not v.get("indices"):
                    v = {}
                out[k] = v
        return out

    def h_pending_tasks(self, params, body):
        return {"tasks": []}

    _ROLLOVER_RE = re.compile(r"^(.*?)-(\d+)$")

    def h_rollover(self, params, body, index, new_index=None):
        if index in self.datastreams.streams:
            payload = _json_body(body) if body else {}
            conds = payload.get("conditions") or {}
            if conds:
                # condition-gated stream rollover: reuse the ILM checks
                svc = self.indices.get(
                    self.datastreams.write_index(index))
                import time as _t
                age_ms = int(_t.time() * 1000) - svc.creation_date
                from ..lifecycle.ilm import IlmService as _Ilm
                if not _Ilm._rollover_due(svc, conds, age_ms):
                    return {"acknowledged": False, "rolled_over": False,
                            "dry_run": False, "conditions": {
                                c: False for c in conds}}
            return self.datastreams.rollover(index)
        return self._rollover_impl(params, body, index, new_index)

    def _rollover_impl(self, params, body, index, new_index=None):
        """Rollover (reference: ``MetadataRolloverService`` /
        ``TransportRolloverAction``): the alias moves to a freshly created
        index when any condition matches (or unconditionally)."""
        alias = index
        targets = [n for n, svc in self.indices.indices.items()
                   if alias in svc.aliases]
        if len(targets) != 1:
            raise IllegalArgumentError(
                f"rollover target [{alias}] must point to exactly one "
                f"index, found {len(targets)}")
        old = targets[0]
        svc = self.indices.get(old)
        payload = _json_body(body) if body else {}
        conditions = payload.get("conditions") or {}
        st = svc.stats(with_field_bytes=False)
        doc_count = st["docs"]["count"]
        if svc.cluster_hooks is not None and "max_docs" in conditions:
            # routed index: the doc condition needs the CLUSTER count
            # (front engines hold only locally-primaried shards)
            try:
                doc_count = int(svc.count({"query": {"match_all": {}}}))
            except Exception:   # noqa: BLE001 — fall back to local
                pass
        age_s = max(0.0, time.time() - svc.creation_date / 1000.0)
        results = {}
        for cond, want in conditions.items():
            if cond == "max_docs":
                results[cond] = doc_count >= int(want)
            elif cond == "max_age":
                from ..common.settings import parse_time_millis
                results[cond] = age_s * 1000 >= parse_time_millis(want)
            elif cond in ("max_size", "max_primary_shard_size"):
                from ..common.settings import parse_bytes
                # a doc-less index counts as size 0: its on-disk commit
                # scaffolding isn't doc data (the reference reads docs
                # store stats, 0 before anything is indexed)
                size = st["store"]["size_in_bytes"] \
                    if st["docs"]["count"] else 0
                results[cond] = size >= parse_bytes(want)
            else:
                raise IllegalArgumentError(
                    f"unknown rollover condition [{cond}]")
        do_roll = (not conditions) or any(results.values())
        if new_index is None:
            m = self._ROLLOVER_RE.match(old)
            if m is None:
                raise IllegalArgumentError(
                    f"index name [{old}] does not match pattern '^.*-\\d+$'"
                )
            new_index = f"{m.group(1)}-{int(m.group(2)) + 1:06d}"
        from ..node.indices_service import validate_index_name
        validate_index_name(new_index)
        dry = _flag(params, "dry_run")
        if new_index in self.indices.indices:
            # the rollover target must be free — validated up front,
            # even for a dry run or unmatched conditions
            raise ResourceAlreadyExistsError(
                f"index [{new_index}] already exists")
        if do_roll and not dry:
            self.indices.create_index(
                new_index, payload.get("settings"),
                payload.get("mappings") or
                svc.mapper.mapping_dict())
            self.indices.indices[new_index].aliases[alias] =                 dict(svc.aliases.get(alias) or {})
            del svc.aliases[alias]
        return {"acknowledged": do_roll and not dry,
                "shards_acknowledged": do_roll and not dry,
                "old_index": old, "new_index": new_index,
                "rolled_over": do_roll and not dry, "dry_run": dry,
                "conditions": {f"[{k}: {conditions[k]}]": v
                               for k, v in results.items()}}

    @staticmethod
    def _default_routing_shards(num_shards: int) -> int:
        """Default routing-shard count for indices created without
        ``index.number_of_routing_shards`` — largest power-of-two multiple
        of ``num_shards`` within 1024, so any power-of-two split works
        (reference: ``MetadataCreateIndexService.calculateNumRoutingShards``).
        """
        log2_num = max(0, (num_shards - 1).bit_length())
        return num_shards << max(1, 10 - log2_num)

    def _resize(self, index, target, num_shards, body, kind):
        from ..common.errors import IllegalStateError
        from ..node.indices_service import _flatten_settings
        svc = self.indices.get(index)
        payload = _json_body(body) if body else {}
        flat_requested = _flatten_settings(payload.get("settings") or {})

        def req(key, default=None):
            return flat_requested.get(
                f"index.{key}", flat_requested.get(key, default))

        # validation order mirrors the reference: shard-count factor checks
        # first (TransportResizeAction.java:134-155 via selectShrink/Split/
        # CloneShard), then the routing-shards-on-resize rejection
        # (TransportResizeAction.java:160-166, legal only when splitting
        # from one shard), then the source read-only requirement
        # (MetadataCreateIndexService.java:1068).
        n = int(req("number_of_shards", num_shards))
        if kind == "shrink" and svc.num_shards % n:
            raise IllegalArgumentError(
                f"the number of source shards [{svc.num_shards}] must be "
                f"a multiple of [{n}]")
        if kind == "split" and (n % svc.num_shards or n <= svc.num_shards):
            raise IllegalArgumentError(
                f"the number of target shards [{n}] must be a larger "
                f"multiple of the source shards [{svc.num_shards}]")
        if kind == "split":
            # from one shard any split is legal (unless the request pins
            # routing shards explicitly); otherwise the target count must
            # divide the source's routing-shard count
            # (IndexMetadata.java:1648-1652)
            requested_rn = req("number_of_routing_shards")
            explicit = svc.settings.get("index.number_of_routing_shards")
            if svc.num_shards == 1:
                rn = int(requested_rn) if requested_rn is not None else n
            elif explicit:
                rn = int(explicit)
            else:
                rn = self._default_routing_shards(svc.num_shards)
            if rn % n:
                raise IllegalStateError(
                    f"the number of routing shards [{rn}] must be a "
                    f"multiple of the target shards [{n}]")
        if kind == "clone" and n != svc.num_shards:
            raise IllegalArgumentError(
                f"cannot clone to a different shard count [{n}] than the "
                f"source [{svc.num_shards}]")
        if req("number_of_routing_shards") is not None and not (
                kind == "split" and svc.num_shards == 1):
            raise IllegalArgumentError(
                "cannot provide index.number_of_routing_shards on resize")
        if str(svc.settings.get("index.blocks.write", "")).lower() != "true":
            raise IllegalStateError(
                f"index {index} must be read-only to resize index. "
                f'use "index.blocks.write=true"')
        # target settings: the source's (minus shard count — analysis etc.
        # must survive or copied mappings dangle), overlaid with requested
        base = {k: v for k, v in svc.settings.items()
                if k not in ("index.number_of_shards", "number_of_shards")}
        base.update({f"index.{k}" if not k.startswith("index.") else k: v
                     for k, v in flat_requested.items()})
        base["index.number_of_shards"] = n
        dst = self.indices.create_index(target, base,
                                        svc.mapper.mapping_dict())
        for alias, spec in (payload.get("aliases") or {}).items():
            dst.aliases[alias] = self._alias_spec(spec or {})
        # the reference hard-links segment files and rewrites routing;
        # shard counts change here so documents re-route through the data
        # path (same semantics, different mechanics)
        svc.refresh()
        total = svc.count({"query": {"match_all": {}}})
        if total > self.SCROLL_MAX_DOCS:
            self.indices.delete_index(target)
            raise IllegalArgumentError(
                f"[{kind}] source has {total} docs, beyond the "
                f"{self.SCROLL_MAX_DOCS}-doc single-pass copy limit")
        res = svc.search({"query": {"match_all": {}},
                          "size": self.SCROLL_MAX_DOCS})
        # the internal copy bypasses application-level write blocks: the
        # target inherits index.blocks.write from the source, but the
        # reference copies segments below the write API
        # (TransportResizeAction.java — Lucene-level recovery), so the
        # block must not stop the resize itself (thread-local scope:
        # concurrent client writes still hit the block)
        from ..node.indices_service import internal_copy_writes
        with internal_copy_writes():
            for h in res.hits:
                dst.index_doc(h.doc_id, h.source)
            dst.refresh()
        return {"acknowledged": True, "shards_acknowledged": True,
                "index": target}

    def h_shrink(self, params, body, index, target):
        return self._resize(index, target, 1, body, "shrink")

    def h_split(self, params, body, index, target):
        svc = self.indices.get(index)
        return self._resize(index, target, svc.num_shards * 2, body,
                            "split")

    def h_clone(self, params, body, index, target):
        svc = self.indices.get(index)
        return self._resize(index, target, svc.num_shards, body, "clone")

    def h_close_index(self, params, body, index):
        names = self.indices.resolve(index)
        for n in names:
            self.indices.indices[n].closed = True
        return {"acknowledged": True, "shards_acknowledged": True,
                "indices": {n: {"closed": True} for n in names}}

    def h_open_index(self, params, body, index):
        names = self.indices.resolve(index)
        for n in names:
            svc = self.indices.indices[n]
            svc.closed = False
            svc._reopened = True         # recovery reports EXISTING_STORE
        return {"acknowledged": True, "shards_acknowledged": True}

    def h_field_mapping(self, params, body, fields, index=None):
        """GET field mappings (reference: ``RestGetFieldMappingAction``)."""
        if "local" in params:
            raise IllegalArgumentError(
                "Unsupported parameter [local]")
        names = self.indices.resolve(index)
        want = fields.split(",")
        out = {}
        for n in names:
            svc = self.indices.indices[n]
            fmap = {}
            for f in want:
                import fnmatch
                for fname, ft in svc.mapper._fields.items():
                    if not fnmatch.fnmatchcase(fname, f):
                        continue
                    leaf = fname.split(".")[-1]
                    m = ft.to_mapping()
                    if _flag(params, "include_defaults") and \
                            m.get("type") == "text" and \
                            "analyzer" not in m:
                        m["analyzer"] = "default"
                    fmap[fname] = {"full_name": fname,
                                   "mapping": {leaf: m}}
            out[n] = {"mappings": fmap}
        return out

    def h_cluster_stats(self, params, body):
        docs = sum(sum(s.doc_count for s in svc.shards)
                   for svc in self.indices.indices.values())
        zero = {"memory_size_in_bytes": 0, "evictions": 0}
        return {
            "cluster_name": self.cluster_name,
            "cluster_uuid": self.node_id,
            "timestamp": int(time.time() * 1000),
            "status": "green",
            "indices": {
                "count": len(self.indices.indices),
                "docs": {"count": docs, "deleted": 0},
                "store": {"size_in_bytes": 0,
                          "total_data_set_size_in_bytes": 0,
                          "reserved_in_bytes": 0},
                "fielddata": dict(zero),
                "query_cache": dict(zero, total_count=0, hit_count=0,
                                    miss_count=0, cache_size=0,
                                    cache_count=0),
                "completion": {"size_in_bytes": 0},
                "segments": {"count": 0, "memory_in_bytes": 0},
                "shards": {"total": sum(
                    svc.num_shards
                    for svc in self.indices.indices.values())}},
            "nodes": {
                "count": {"total": 1, "data": 1, "master": 1,
                          "ingest": 1, "coordinating_only": 0,
                          "remote_cluster_client": 1, "ml": 0,
                          "voting_only": 0},
                "versions": ["8.0.0"],
                "os": dict(_os_mem_stats(),
                           available_processors=os.cpu_count() or 1,
                           allocated_processors=os.cpu_count() or 1,
                           names=[{"name": "Linux", "count": 1}],
                           pretty_names=[{"pretty_name": "Linux",
                                          "count": 1}],
                           architectures=[{"arch": "x86_64",
                                           "count": 1}]),
                "process": (lambda p: {
                    "cpu": p["cpu"],
                    "open_file_descriptors": {
                        "min": p["open_file_descriptors"],
                        "max": p["open_file_descriptors"],
                        "avg": p["open_file_descriptors"]}})(
                    _process_stats()),
                "jvm": {"max_uptime_in_millis": 0, "versions": [],
                        "mem": {"heap_used_in_bytes": 0,
                                "heap_max_in_bytes": 0},
                        "threads": 1},
                "fs": _fs_stats(self.indices.data_path),
                "plugins": [{"name": "tpu-engine"}],
                "network_types": {"transport_types": {"netty4": 1},
                                  "http_types": {"netty4": 1}},
                "discovery_types": {"single-node": 1},
                "packaging_types": [{"flavor": "default", "type": "tar",
                                     "count": 1}],
            },
        }

    _REROUTE_COMMANDS = {"move", "cancel", "allocate_replica",
                         "allocate_stale_primary",
                         "allocate_empty_primary"}

    def h_cluster_reroute(self, params, body):
        """Reroute (reference: ``RestClusterRerouteAction``). Single-node:
        commands can't actually move shards, so explain-mode reports the
        allocation deciders' verdicts and the state echo mirrors
        cluster-state metric filtering."""
        payload = _json_body(body) if body else {}
        explanations = []
        for cmd in payload.get("commands") or []:
            if not isinstance(cmd, dict) or len(cmd) != 1:
                raise ParsingError(f"malformed reroute command {cmd}")
            (kind, args), = cmd.items()
            if kind not in self._REROUTE_COMMANDS:
                raise IllegalArgumentError(
                    f"unknown reroute command [{kind}]")
            args = args or {}
            idx = args.get("index")
            shard = args.get("shard")
            node = args.get("node")
            svc = self.indices.indices.get(idx)
            valid = (svc is not None and isinstance(shard, int)
                     and 0 <= shard < svc.num_shards
                     and node in (self.node_id, self.node_name, "node_0"))
            parameters = {"index": idx, "shard": shard, "node": node}
            if kind == "cancel":
                parameters["allow_primary"] = bool(
                    args.get("allow_primary", False))
            explanations.append({
                "command": kind,
                "parameters": parameters,
                "decisions": [{
                    "decider": f"{kind}_allocation_command",
                    "decision": "YES" if valid else "NO",
                    "explanation":
                        f"{kind} command for shard [{shard}] of "
                        f"[{idx}] on node [{node}]" +
                        ("" if valid else ": shard or node not found")}],
            })
        metric = params.get("metric", "")
        state: dict = {"cluster_uuid": self.node_id}
        metrics = metric.split(",") if metric else []
        if "metadata" in metrics or "_all" in metrics:
            state["metadata"] = {"cluster_uuid": self.node_id,
                                 "indices": {
                                     n: {"state": "close" if sv.closed
                                         else "open"}
                                     for n, sv in
                                     self.indices.indices.items()}}
        if not metrics or "nodes" in metrics or "_all" in metrics:
            state["nodes"] = {self.node_id: {"name": self.node_name}}
        out = {"acknowledged": True, "state": state}
        if params.get("explain") in ("true", ""):
            out["explanations"] = explanations
        return out

    def h_allocation_explain(self, params, body):
        """Allocation explain (reference:
        ``RestClusterAllocationExplainAction``)."""
        import datetime as _dtm
        payload = _json_body(body) if body else {}
        node = {"id": self.node_id, "name": self.node_name,
                "transport_address": "127.0.0.1:9300"}
        if payload.get("index") is not None:
            svc = self.indices.get(payload["index"])
            shard = int(payload.get("shard", 0))
            if not 0 <= shard < svc.num_shards:
                raise IllegalArgumentError(
                    f"No shard was specified in the explain request "
                    f"which means the response should explain a "
                    f"randomly-chosen unassigned shard")
            return {
                "index": payload["index"], "shard": shard,
                "primary": bool(payload.get("primary", False)),
                "current_state": "started",
                "current_node": node,
                "can_remain_on_current_node": "yes",
                "can_rebalance_cluster": "yes",
                "can_rebalance_to_other_node": "no",
                "rebalance_explanation":
                    "cannot rebalance as no target node exists that can "
                    "both allocate this shard and improve the cluster "
                    "balance",
            }
        # empty request: explain the first UNASSIGNED shard (a replica
        # beyond this node's allocation capacity)
        for n, svc in sorted(self.indices.indices.items()):
            if svc.num_replicas > self._HEALTH_REPLICA_CAP:
                out = {
                    "index": n, "shard": 0, "primary": False,
                    "current_state": "unassigned",
                    "unassigned_info": {
                        "reason": "INDEX_CREATED",
                        "at": _dtm.datetime.fromtimestamp(
                            svc.creation_date / 1000.0,
                            tz=_dtm.timezone.utc).strftime(
                            "%Y-%m-%dT%H:%M:%S.%fZ"),
                        "last_allocation_status": "no_attempt"},
                    "can_allocate": "no",
                    "allocate_explanation":
                        "cannot allocate because allocation is not "
                        "permitted to any of the nodes",
                }
                if params.get("include_disk_info") in ("true", ""):
                    out["cluster_info"] = {
                        "nodes": {self.node_id: {
                            "node_name": self.node_name,
                            "least_available": {
                                "total_bytes": 1 << 33,
                                "free_bytes": 1 << 32}}}}
                return out
        raise IllegalArgumentError(
            "unable to find any unassigned shards to explain [explain "
            "the first unassigned shard by sending an empty body]")

    def _breaker_stats(self) -> dict:
        """Live breaker hierarchy stats. The breaker service is
        process-scoped (nodes in one process share real host memory);
        the fielddata estimate for THIS node's surface is computed from
        its own loaded column footprints at render time — never written
        back into the shared service, so one node's stats cannot clobber
        another's."""
        from ..common.breakers import DEFAULT as _breakers
        fd_total = 0
        for svc in self.indices.indices.values():
            try:
                fd, _comp = svc.field_bytes()
                fd_total += sum(fd.values())
            except Exception:   # noqa: BLE001 — closed index edge
                pass
        out = _breakers.stats()
        out["fielddata"] = dict(out["fielddata"],
                                estimated_size_in_bytes=fd_total)
        return out

    def h_cluster_get_settings(self, params, body):
        defaults: Dict[str, Any] = {}
        if _flag(params, "include_defaults"):
            # the reference test cluster launches nodes with
            # node.attr.testattr=test (gradle testclusters config);
            # defaults echo the node's effective configuration
            defaults = {
                "node": {"attr": {"testattr": "test"},
                         "name": self.node_name},
                "cluster": {"name": self.cluster_name},
                "search": {"max_buckets": "65536"},
            }
        return dict(self.cluster_settings, defaults=defaults)

    def h_cluster_put_settings(self, params, body):
        from ..search import aggregations as _aggs_mod
        b0 = _json_body(body)
        for scope in ("persistent", "transient"):
            sc = b0.get(scope) or {}
            mb = sc.get("search.max_buckets",
                        (sc.get("search") or {}).get("max_buckets", ...))
            if mb is not ...:
                _aggs_mod.MAX_BUCKETS[0] = (65536 if mb is None
                                            else int(mb))
        from ..common.breakers import DEFAULT as _breakers
        for scope in ("persistent", "transient"):
            for k, v in (b0.get(scope) or {}).items():
                if k.startswith("indices.breaker."):
                    _breakers.apply_setting(k, v)
                if k == "stack.templates.enabled" and \
                        str(v).lower() == "true":
                    self.register_stack_templates()
                if v is None:
                    # null resets a setting to its default
                    self.cluster_settings[scope].pop(k, None)
                else:
                    self.cluster_settings[scope][k] = v
        if any(k.startswith(("slo.", "flightrec."))
               for scope in ("persistent", "transient")
               for k in (b0.get(scope) or {})):
            # dynamic SLO-watchdog / flight-recorder knobs: re-resolve
            # the live engine from the effective overlay (transient
            # wins over persistent, env overrides win over both)
            from ..common import flightrec as _flightrec
            _flightrec.apply_cluster_settings({
                **self.cluster_settings["persistent"],
                **self.cluster_settings["transient"]})
        if any(k.startswith("qos.")
               for scope in ("persistent", "transient")
               for k in (b0.get(scope) or {})):
            # dynamic QoS knobs (tenant refill/burst, shed thresholds)
            # re-resolve live, same overlay precedence as slo.*
            from ..common import qos as _qos
            _qos.apply_cluster_settings({
                **self.cluster_settings["persistent"],
                **self.cluster_settings["transient"]})
        return {"acknowledged": True,
                "persistent": self.cluster_settings["persistent"],
                "transient": self.cluster_settings["transient"]}

    #: nodes.info sections selectable via the metric path
    NODES_INFO_METRICS = ("settings", "os", "process", "jvm",
                          "thread_pool", "transport", "http", "plugins",
                          "modules", "ingest", "aggregations", "indices")

    def h_nodes(self, params, body, node_id=None, metric=None):
        if metric is None and node_id is not None and all(
                m.strip() in self.NODES_INFO_METRICS
                for m in node_id.split(",")):
            # GET /_nodes/{metric}: a metric list in the node_id slot
            node_id, metric = None, node_id
        info = {
            "name": self.node_name,
            "transport_address": "127.0.0.1:9300",
            "host": "127.0.0.1", "ip": "127.0.0.1",
            "version": "8.0.0-tpu",
            "build_flavor": "tpu-native", "build_type": "source",
            "build_hash": "unknown",
            "roles": ["data", "ingest", "master",
                      "remote_cluster_client"],    # sorted (7.8+)
            "attributes": {},
            "settings": {"client": {"type": "node"},
                         "cluster": {"name": self.cluster_name},
                         "node": {"name": self.node_name}},
            "os": {"refresh_interval_in_millis": 1000},
            "process": {"id": os.getpid(), "mlockall": False},
            "jvm": {"pid": os.getpid(), "version": "n/a",
                    "using_compressed_ordinary_object_pointers": "true"},
            "thread_pool": {"search": {"type": "fixed"},
                            "write": {"type": "fixed"}},
            "transport": {"bound_address": ["127.0.0.1:9300"],
                          "publish_address": "127.0.0.1:9300",
                          "profiles": {}},
            "http": {"bound_address": [self.http_publish_address],
                     "publish_address": self.http_publish_address,
                     "max_content_length_in_bytes": 104857600},
            "plugins": [], "modules": [],
            "ingest": {"processors": [
                {"type": t} for t in sorted(
                    __import__("elasticsearch_tpu.ingest.pipeline",
                               fromlist=["_PROCESSOR_TYPES"]
                               )._PROCESSOR_TYPES)]},
            "aggregations": {
                kind: {"types": ["other"]}
                for kind in sorted(__import__(
                    "elasticsearch_tpu.search.aggregations",
                    fromlist=["_AGG_PARSERS"])._AGG_PARSERS)},
        }
        if params.get("flat_settings") in ("true", ""):
            from ..node.indices_service import _flatten_settings
            info["settings"] = {k: str(v) for k, v in
                                _flatten_settings(
                                    info["settings"]).items()}
        if metric:
            wanted = {m.strip() for m in metric.split(",")}
            keep = {"name", "transport_address", "host", "ip", "version",
                    "build_flavor", "build_type", "build_hash", "roles",
                    "attributes"}
            info = {k: v for k, v in info.items()
                    if k in keep or k in wanted}
        return {"_nodes": {"total": 1, "successful": 1, "failed": 0},
                "cluster_name": self.cluster_name,
                "nodes": {self.node_id: info}}

    #: nodes.stats sections (reference: NodesStatsRequest.Metric; "device"
    #: is the TPU-native extension — XLA compiles, transfer bytes,
    #: device-memory watermarks)
    NODES_STATS_METRICS = ("indices", "os", "process", "jvm", "thread_pool",
                           "fs", "transport", "http", "breaker", "script",
                           "discovery", "ingest", "adaptive_selection",
                           "script_cache", "indexing_pressure", "device")

    def h_nodes_stats(self, params, body, metric=None,
                      index_metric=None, node_id=None):
        uri = "/_nodes/stats" + (f"/{metric}" if metric else "")
        self._check_params(params, {"level", "types", "fields", "groups",
                                    "completion_fields", "fielddata_fields",
                                    "include_segment_file_sizes",
                                    "include_unloaded_segments"}, uri)
        wanted = set(self.NODES_STATS_METRICS)
        if metric and metric != "_all":
            wanted = self._check_metrics(metric, wanted, uri)
        from ..node.indices_service import empty_index_stats
        indices_stats: Dict[str, Any] = empty_index_stats()
        per_index: Dict[str, Any] = {}
        for n, svc in self.indices.indices.items():
            st = svc.stats()
            _merge_numeric_tree(indices_stats, st)
            per_index[n] = st
        if index_metric and index_metric != "_all":
            im = self._check_metrics(
                index_metric, set(self.STATS_METRICS),
                f"{uri}/{index_metric}")
            keep = {self._METRIC_SECTION.get(m, m) for m in im}
            indices_stats = {k: v for k, v in indices_stats.items()
                             if k in keep}
        if params.get("include_segment_file_sizes") in ("true", "") and \
                "segments" in indices_stats:
            indices_stats["segments"]["file_sizes"] = _segment_file_sizes(
                [sh for svc in self.indices.indices.values()
                 for sh in svc.shards])
        if params.get("level") == "indices":
            indices_stats["indices"] = per_index
        sections = {
            "indices": indices_stats,
            "os": _os_stats(),
            "process": _process_stats(),
            "jvm": {"timestamp": int(time.time() * 1000),
                    "uptime_in_millis": int(
                        (time.time() - self.start_time) * 1000),
                    "mem": {"heap_used_in_bytes": 0, "heap_used_percent": 0,
                            "heap_committed_in_bytes": 0,
                            "heap_max_in_bytes": 0,
                            "non_heap_used_in_bytes": 0,
                            "non_heap_committed_in_bytes": 0,
                            "pools": {}},
                    "threads": {"count": 1, "peak_count": 1},
                    "gc": {"collectors": _heap.collectors_doc()},
                    "buffer_pools": {
                        "direct": {"count": 0, "used_in_bytes": 0,
                                   "total_capacity_in_bytes": 0},
                        "mapped": {"count": 0, "used_in_bytes": 0,
                                   "total_capacity_in_bytes": 0}},
                    "classes": {"current_loaded_count": 0,
                                "total_loaded_count": 0,
                                "total_unloaded_count": 0}},
            "thread_pool": {"search": {"threads": 1, "queue": 0,
                                       "active": 0, "rejected": 0,
                                       "largest": 1, "completed": 0},
                            "write": {"threads": 1, "queue": 0,
                                      "active": 0, "rejected": 0,
                                      "largest": 1, "completed": 0}},
            "fs": (lambda t: {
                "timestamp": int(time.time() * 1000),
                "total": t,
                "data": [dict(t, path=self.indices.data_path,
                              mount="/", type="fs")]})(
                _fs_stats(self.indices.data_path)),
            "transport": {"server_open": 0,
                          "total_outbound_connections": 0,
                          "rx_count": 0, "rx_size_in_bytes": 0,
                          "tx_count": 0, "tx_size_in_bytes": 0},
            "http": {"current_open": 0, "total_opened": 0,
                     "clients": []},
            "breaker": self._breaker_stats(),
            "script": _script_service().stats_doc(),
            "discovery": {
                "cluster_state_queue": {"total": 0, "pending": 0,
                                        "committed": 0},
                "published_cluster_states": {"full_states": 0,
                                             "incompatible_diffs": 0,
                                             "compatible_diffs": 0},
                "cluster_state_update": {"unchanged": {"count": 0}},
                "serialized_cluster_states": {
                    "full_states": {"count": 0},
                    "diffs": {"count": 0}}},
            "ingest": {"total": {"count": 0, "time_in_millis": 0,
                                 "current": 0, "failed": 0},
                       "pipelines": {}},
            "adaptive_selection": (self.adaptive_selection_provider()
                                   if self.adaptive_selection_provider
                                   else {}),
            "script_cache": {"sum": {"compilations": 0,
                                     "cache_evictions": 0,
                                     "compilation_limit_triggered": 0}},
            "indexing_pressure": _indexing_pressure().stats_doc(),
            "device": _device_stats(),
        }
        node = {"timestamp": int(time.time() * 1000),
                "name": self.node_name,
                "transport_address": "127.0.0.1:9300",
                "host": "127.0.0.1", "ip": "127.0.0.1:9300",
                "roles": ["master", "data", "ingest"],
                "attributes": {}}
        for k in self.NODES_STATS_METRICS:
            if k in wanted and k in sections:
                # the "breaker" metric serializes under "breakers"
                node["breakers" if k == "breaker" else k] = sections[k]
        return {"_nodes": {"total": 1, "successful": 1, "failed": 0},
                "cluster_name": self.cluster_name,
                "nodes": {self.node_id: node}}

    # ------------------------------------------------------------------
    # telemetry + tracing (common/telemetry.py, common/tracing.py)
    # ------------------------------------------------------------------

    def _plane_serving_rollup(self) -> dict:
        """Node-level plane_serving rollup (cheap: batcher counters only,
        no store walk)."""
        from ..search.microbatch import empty_serving_stats
        out = dict(empty_serving_stats(), cache_hit_count=0,
                   cache_miss_count=0)
        for svc in list(self.indices.indices.values()):
            doc = svc.plane_serving_stats()
            for k, v in doc.items():
                out[k] = max(out.get(k, 0), v) if k == "max_batch" \
                    else out.get(k, 0) + v
        return out

    def h_nodes_telemetry(self, params, body):
        """GET /_nodes/telemetry: the full registry snapshot (counters /
        gauges / histograms + collector families) plus node sections —
        device/XLA instrumentation, plane serving, tasks, trace store."""
        from ..common import telemetry, tracing
        node = {
            "name": self.node_name,
            "timestamp": int(time.time() * 1000),
            "registry": telemetry.DEFAULT.stats_doc(),
            "device": telemetry.device_stats_doc(),
            "plane_serving": self._plane_serving_rollup(),
            "tasks": {"running": len(self.task_manager.tasks)},
            "trace_store": tracing.DEFAULT_STORE.stats_doc(),
        }
        if self.adaptive_selection_provider:
            node["adaptive_selection"] = self.adaptive_selection_provider()
        return {"_nodes": {"total": 1, "successful": 1, "failed": 0},
                "cluster_name": self.cluster_name,
                "nodes": {self.node_id: node}}

    def h_prometheus(self, params, body):
        """GET /_prometheus/metrics: text exposition format 0.0.4 over
        the same registry (node families contribute via collectors).
        ``?exemplars=true`` adds OpenMetrics trace-id exemplars to p99
        quantile lines (opt-in: strict 0.0.4 parsers reject them)."""
        from ..common import telemetry
        exemplars = _flag(params, "exemplars")
        ct = ("application/openmetrics-text; version=1.0.0; charset=utf-8"
              if exemplars else "text/plain; version=0.0.4; charset=utf-8")
        return (200, ct,
                telemetry.DEFAULT.prometheus_text(exemplars=exemplars))

    def h_trace_list(self, params, body):
        """GET /_trace: newest-first index of retained trace ids with
        each root span's action + duration — the listing that explains
        an evicted id's 404 and feeds ``trace_dump.py --last``.
        ``?min_ms=`` keeps only traces at least that slow; ``?tenant=``
        keeps only one X-Opaque-Id's traces (both filter before the
        ``size`` cap)."""
        from ..common.tracing import DEFAULT_STORE
        try:
            n = int(params.get("size", 50))
        except ValueError:
            raise IllegalArgumentError(
                f"[size] must be an integer, got [{params.get('size')}]")
        min_ms = None
        raw = params.get("min_ms")
        if raw not in (None, ""):
            try:
                min_ms = float(raw)
            except ValueError:
                raise IllegalArgumentError(
                    f"[min_ms] must be a number, got [{raw}]")
        tenant = params.get("tenant") or None
        return {"traces": DEFAULT_STORE.recent(n, min_ms=min_ms,
                                               tenant=tenant),
                "store": DEFAULT_STORE.stats_doc()}

    def h_insights_top_queries(self, params, body):
        """GET /_insights/top_queries: this node's heavy-hitter query
        shapes and tenants by count/latency/cpu/device-ms/bytes
        (``search/query_insight.py``), ranked by ``?metric=`` (default
        ``count``), capped at ``?limit=``; ``?window=current|previous|
        both`` picks the rotation window. Each shape row carries one
        exemplar trace id and one verbatim sample body. The cluster
        front fans this out per node and MERGES sketches
        (``node/cluster_rest``)."""
        from ..search import query_insight as _qi
        try:
            limit = int(params.get("limit", _qi.topn()))
        except ValueError:
            raise IllegalArgumentError(
                f"[limit] must be an integer, got [{params.get('limit')}]")
        metric = params.get("metric", "count")
        if metric not in _qi.METRICS:
            raise IllegalArgumentError(
                f"[metric] must be one of {list(_qi.METRICS)}, got "
                f"[{metric}]")
        window = params.get("window", "current")
        if window not in ("current", "previous", "both"):
            raise IllegalArgumentError(
                f"[window] must be current, previous or both, got "
                f"[{window}]")
        return _qi.store_for(self.node_id).top_doc(
            limit=limit, metric=metric, window=window)

    def h_profiler_flamegraph(self, params, body):
        """GET /_profiler/flamegraph: this node's continuous-profiler
        windows (``common/contprof.py``) as attributed flamegraph rows
        + a d3-flamegraph tree. ``?window=current|previous|both`` picks
        the rotation window, ``?pool=``/``?tenant=`` filter the
        attribution subtree, ``?limit=`` caps the row count and
        ``?format=collapsed`` renders Brendan-Gregg collapsed-stack
        text instead of JSON. The cluster front fans this out per node
        and MERGES rows (``node/cluster_rest``)."""
        from ..common import contprof as _contprof
        try:
            limit = int(params.get("limit", _contprof.DEFAULT_LIMIT))
        except ValueError:
            raise IllegalArgumentError(
                f"[limit] must be an integer, got [{params.get('limit')}]")
        window = params.get("window", "current")
        if window not in ("current", "previous", "both"):
            raise IllegalArgumentError(
                f"[window] must be current, previous or both, got "
                f"[{window}]")
        fmt = params.get("format", "json")
        if fmt not in ("json", "collapsed"):
            raise IllegalArgumentError(
                f"[format] must be json or collapsed, got [{fmt}]")
        doc = _contprof.profile_doc(
            window=window, pool=params.get("pool"),
            tenant=params.get("tenant"), limit=limit)
        doc["node"] = self.node_id
        if fmt == "collapsed":
            return (200, "text/plain; charset=UTF-8",
                    _contprof.collapsed_text(doc["rows"]))
        return doc

    def h_telemetry_history(self, params, body):
        """GET /_telemetry/history?family=&window=: the bounded
        downsampling ring over selected ``es_*`` families
        (``common/metrics_history.py``). ``window`` picks the tier
        (``raw``/``10s``/``1m``), ``since`` is an epoch-seconds floor,
        ``rate=true`` returns per-second derivatives instead of raw
        points. Without ``family`` the response is the store's stats
        doc (recorded families, tiers, series counts)."""
        from ..common import metrics_history as _mh
        family = params.get("family")
        if not family:
            return _mh.DEFAULT.stats_doc()
        window = params.get("window", "raw")
        if window not in {t[0] for t in _mh.TIERS}:
            raise IllegalArgumentError(
                f"[window] must be one of "
                f"{[t[0] for t in _mh.TIERS]}, got [{window}]")
        since = None
        raw = params.get("since")
        if raw not in (None, ""):
            try:
                since = float(raw)
            except ValueError:
                raise IllegalArgumentError(
                    f"[since] must be epoch seconds, got [{raw}]")
        return _mh.DEFAULT.doc(family, window=window, since=since,
                               rate=_flag(params, "rate"))

    def h_trace_get(self, params, body, trace_id):
        """GET /_trace/{trace_id}: the recorded span tree for one
        request (REST edge → coordinator → shard fan-out → plane
        dispatch)."""
        from ..common.tracing import DEFAULT_STORE
        doc = DEFAULT_STORE.get(trace_id)
        if doc is None:
            raise ResourceNotFoundError(
                f"trace [{trace_id}] is not in the trace store (bounded "
                f"ring of {DEFAULT_STORE.MAX_TRACES} traces; GET /_trace "
                f"lists the ids still retained)")
        return doc

    def h_profiler_timeline(self, params, body):
        """GET /_profiler/timeline: the per-dispatch timeline ring
        (``search/dispatch_profile.py``) rendered as Chrome trace-event
        JSON (perfetto-loadable — one process per batcher, one track
        per dispatcher thread plus a ``queue`` track). ``since`` is an
        epoch-ms floor (or a relative value like ``30s``), ``limit``
        caps the record count. The cluster front fans this out per node
        and merges with per-node dedup (``node/cluster_rest``)."""
        from ..search import dispatch_profile as _dp
        since_ms = None
        raw = params.get("since")
        if raw:
            try:
                since_ms = float(raw)
            except ValueError:
                from ..common.settings import parse_time_millis
                since_ms = time.time() * 1e3 - parse_time_millis(raw)
        try:
            limit = int(params.get("limit", 256))
        except ValueError:
            raise IllegalArgumentError(
                f"[limit] must be an integer, got [{params.get('limit')}]")
        # records carry the node bound at slot enqueue; the renderer
        # deliberately does NOT substitute this node's id for node-less
        # records — in-process cluster nodes share the ring, and the
        # fan-in's dedup needs every node to render a shared record
        # IDENTICALLY
        recs = _dp.RING.records(since_ms=since_ms, limit=limit)
        doc = _dp.chrome_trace(recs)
        doc["ring"] = _dp.RING.stats_doc()
        return doc

    def h_flight_recorder(self, params, body):
        """GET /_flight_recorder: the node's bounded event journal
        (``common/flightrec.py``) with ``type`` (comma list), ``since``
        (epoch ms, or a relative time value like ``30s`` meaning "the
        last 30s"), ``trace_id`` and ``limit`` filters. The cluster
        front fans this out per node and merges (``node/cluster_rest``)."""
        from ..common import flightrec
        since_ms = None
        raw = params.get("since")
        if raw:
            try:
                since_ms = float(raw)
            except ValueError:
                from ..common.settings import parse_time_millis
                since_ms = time.time() * 1e3 - parse_time_millis(raw)
        try:
            limit = int(params.get("limit", 256))
        except ValueError:
            raise IllegalArgumentError(
                f"[limit] must be an integer, got [{params.get('limit')}]")
        doc = {"events": flightrec.DEFAULT.events(
                   type_=params.get("type"), since_ms=since_ms,
                   trace_id=params.get("trace_id"), limit=limit),
               "journal": flightrec.DEFAULT.stats_doc()}
        wd = flightrec.get_watchdog()
        if wd is not None:
            doc["watchdog"] = wd.status_doc()
        return doc

    def h_flight_captures(self, params, body):
        """GET /_flight_recorder/captures: the watchdog's bounded
        post-mortem capture store (summaries; fetch one by id for the
        full hot-threads/telemetry/journal payload)."""
        from ..common import flightrec
        wd = flightrec.get_watchdog()
        doc = {"captures": wd.captures() if wd is not None else []}
        if wd is not None:
            doc["watchdog"] = wd.status_doc()
        return doc

    def h_flight_capture_get(self, params, body, capture_id):
        from ..common import flightrec
        wd = flightrec.get_watchdog()
        cap = wd.get_capture(capture_id) if wd is not None else None
        if cap is None:
            raise ResourceNotFoundError(
                f"capture [{capture_id}] is not in the bounded capture "
                f"store; GET /_flight_recorder/captures lists the ids "
                f"still retained")
        return cap

    def h_health_report(self, params, body, indicator=None):
        """GET /_health_report[/{indicator}] (reference: the 8.x health
        indicator API — ``RestGetHealthAction``): every indicator
        evaluated against this node's live registry/serving state."""
        from ..common.health import HealthService
        svc = getattr(self, "_health_svc", None)
        if svc is None:
            svc = self._health_svc = HealthService(self)
        return svc.report(indicator=indicator,
                          verbose=_flag(params, "verbose", True))

    # ------------------------------------------------------------------
    # cat
    # ------------------------------------------------------------------

    @staticmethod
    def _cat_cell(c) -> str:
        if isinstance(c, bool):
            return "true" if c else "false"
        return str(c)

    @staticmethod
    def _cat_sort_key(cell):
        """Numeric-aware sort key: numbers order numerically, before
        strings (mirrors the reference cat table comparator)."""
        try:
            return (0, float(cell), "")
        except (TypeError, ValueError):
            return (1, 0.0, str(cell))

    def _cat_table(self, rows: List[List[str]], headers: List[str],
                   verbose: bool, params: Optional[dict] = None,
                   default_columns: Optional[List[str]] = None,
                   aliases: Optional[Dict[str, str]] = None):
        params = params or {}
        aliases = aliases or {}
        if _flag(params, "help"):
            w = max((len(h) for h in headers), default=0)
            return "".join(f"{h.ljust(w)} | {h} | {h}\n" for h in headers)
        col_of = {h: i for i, h in enumerate(headers)}
        if params.get("s"):
            # stable multi-key sort with per-key :asc/:desc suffixes:
            # apply keys right-to-left
            specs = []
            for k in str(params["s"]).split(","):
                k = k.strip()
                name, _, order = k.partition(":")
                name = aliases.get(name, name)
                if name in col_of:
                    specs.append((name, order == "desc"))
            for name, desc in reversed(specs):
                c = col_of[name]
                # empty cells order as the SMALLEST value (first asc,
                # last desc — the reference comparator's null handling)
                rows = sorted(rows, key=lambda r: (
                    (self._cat_cell(r[c]) != "",) +
                    self._cat_sort_key(r[c])), reverse=desc)
        if params.get("h"):
            sel = []                    # (display, canonical)
            import fnmatch as _fn
            for tok in str(params["h"]).split(","):
                tok = tok.strip()
                canon = aliases.get(tok, tok)
                if canon in col_of:
                    sel.append((tok if tok in aliases else canon, canon))
                elif "*" in tok:
                    sel.extend((h2, h2) for h2 in headers
                               if _fn.fnmatchcase(h2, tok))
            rows = [[r[col_of[c]] for _, c in sel] for r in rows]
            headers = [d for d, _ in sel]
            col_of = {h2: i for i, h2 in enumerate(headers)}
        elif default_columns:
            sel = [c for c in default_columns if c in col_of]
            rows = [[r[col_of[c]] for c in sel] for r in rows]
            headers = sel
        if params.get("format") in ("json", "yaml"):
            return [dict(zip(headers, (self._cat_cell(c) for c in r)))
                    for r in rows]
        if not rows and not verbose:
            return ""
        # without the header row, column widths come from the data alone
        widths = [len(h) if verbose else 0 for h in headers]
        for r in rows:
            for i, c in enumerate(r):
                widths[i] = max(widths[i], len(self._cat_cell(c)))
        # numeric and byte-valued columns right-align, headers included
        # (the reference's Table renderer)
        _bytes_re = re.compile(r"\d+(\.\d+)?[kmgtp]?b")
        def _is_num(c):
            if isinstance(c, (int, float)) and not isinstance(c, bool):
                return True
            return isinstance(c, str) and bool(_bytes_re.fullmatch(c))
        numeric_col = [bool(rows) and all(_is_num(r[i]) or r[i] in ("",)
                                          for r in rows)
                       for i in range(len(headers))]
        lines = []
        if verbose:
            lines.append(" ".join(h.ljust(widths[i])
                                  for i, h in enumerate(headers)).rstrip())
        for r in rows:
            cells = []
            for i, c in enumerate(r):
                txt = self._cat_cell(c)
                cells.append(txt.rjust(widths[i]) if numeric_col[i]
                             else txt.ljust(widths[i]))
            line = " ".join(cells)
            # trailing pads stay only when the LAST cell is an empty
            # placeholder (the reference width-pads empty cells)
            if r and self._cat_cell(r[-1]) != "":
                line = line.rstrip()
            lines.append(line)
        return "\n".join(lines) + "\n"

    #: cat indices column aliases (Table cell aliases in the reference)
    _CAT_IDX_ALIASES = {"i": "index", "idx": "index", "h": "health",
                        "s": "status", "dc": "docs.count",
                        "docsCount": "docs.count", "dd": "docs.deleted",
                        "cd": "creation.date",
                        "cds": "creation.date.string",
                        "ss": "store.size", "p": "pri", "r": "rep",
                        "id": "uuid"}

    def h_cat_indices(self, params, body, index=None):
        health_filter = params.get("health")
        if health_filter is not None and health_filter not in (
                "green", "yellow", "red"):
            raise IllegalArgumentError(
                f"unknown health value [{health_filter}]")
        rows = []
        ew = params.get("expand_wildcards", "open,closed")
        wildcarded = index is None or any(c in index for c in "*")
        for name in self.indices.resolve(index):
            svc = self.indices.indices[name]
            hidden = str(svc.settings.get("index.hidden",
                                          "")).lower() == "true" or                 name.startswith(".")
            if hidden and wildcarded and "all" not in ew and                     "hidden" not in ew and                     not (index or "").startswith("."):
                continue
            closed = svc.closed
            st = svc.stats(with_field_bytes=False)
            size = _human_bytes(st["store"]["size_in_bytes"])
            health = "green" if svc.num_replicas == 0 or closed \
                else "yellow"       # unassigned replicas on one node
            rows.append([health, "close" if closed else "open",
                         name, svc.uuid,
                         svc.num_shards, svc.num_replicas,
                         "" if closed else st["docs"]["count"],
                         "" if closed else st["docs"]["deleted"],
                         "" if closed else size,
                         "" if closed else size,
                         str(svc.creation_date),
                         format_date_millis_cat(svc.creation_date)])
        if health_filter is not None:
            rows = [r for r in rows if r[0] == health_filter]
        return self._cat_table(rows, ["health", "status", "index", "uuid",
                                      "pri", "rep", "docs.count",
                                      "docs.deleted", "store.size",
                                      "pri.store.size", "creation.date",
                                      "creation.date.string"],
                               _flag(params, "v"), params,
                               aliases=self._CAT_IDX_ALIASES,
                               default_columns=["health", "status",
                                                "index", "uuid", "pri",
                                                "rep", "docs.count",
                                                "docs.deleted",
                                                "store.size",
                                                "pri.store.size"])

    def h_cat_health(self, params, body):
        h = self._health()
        rows = [[int(time.time()), time.strftime("%H:%M:%S"),
                 h["cluster_name"], h["status"], 1, 1,
                 h["active_shards"], h["active_primary_shards"], 0, 0,
                 h["unassigned_shards"], 0, "-", "100.0%"]]
        headers = ["epoch", "timestamp", "cluster", "status", "node.total",
                   "node.data", "shards", "pri", "relo", "init",
                   "unassign", "pending_tasks", "max_task_wait_time",
                   "active_shards_percent"]
        if params.get("ts") == "false":
            rows = [r[2:] for r in rows]
            headers = headers[2:]
        return self._cat_table(rows, headers, _flag(params, "v"), params)

    def h_cat_count(self, params, body, index=None):
        total = 0
        for name in self.indices.resolve(index):
            svc = self.indices.indices[name]
            if svc.cluster_hooks is not None:
                # routed index: count cluster-wide (front engines hold
                # only locally-primaried shards)
                c = svc.count({"query": {"match_all": {}}})
                total += int(c)
                continue
            total += sum(s.doc_count for s in svc.shards)
        return self._cat_table(
            [[int(time.time()), time.strftime("%H:%M:%S"), total]],
            ["epoch", "timestamp", "count"], _flag(params, "v"), params)

    #: full cat.shards column catalog (RestShardsAction.getTableWithHeader
    #: — the long stats tail renders zeros on this engine)
    _CAT_SHARDS_EXTRA = [
        "sync_id", "unassigned.reason", "unassigned.at",
        "unassigned.for", "unassigned.details", "recoverysource.type",
        "completion.size", "fielddata.memory_size", "fielddata.evictions",
        "query_cache.memory_size", "query_cache.evictions", "flush.total",
        "flush.total_time", "get.current", "get.time", "get.total",
        "get.exists_time", "get.exists_total", "get.missing_time",
        "get.missing_total", "indexing.delete_current",
        "indexing.delete_time", "indexing.delete_total",
        "indexing.index_current", "indexing.index_time",
        "indexing.index_total", "indexing.index_failed",
        "merges.current", "merges.current_docs", "merges.current_size",
        "merges.total", "merges.total_docs", "merges.total_size",
        "merges.total_time", "refresh.total", "refresh.time",
        "refresh.external_total", "refresh.external_time",
        "refresh.listeners", "search.fetch_current", "search.fetch_time",
        "search.fetch_total", "search.open_contexts",
        "search.query_current", "search.query_time",
        "search.query_total", "search.scroll_current",
        "search.scroll_time", "search.scroll_total", "segments.count",
        "segments.memory", "segments.index_writer_memory",
        "segments.version_map_memory", "segments.fixed_bitset_memory",
        "seq_no.max", "seq_no.local_checkpoint",
        "seq_no.global_checkpoint", "warmer.current", "warmer.total",
        "warmer.total_time", "path.data", "path.state",
        "bulk.total_operations", "bulk.total_time",
        "bulk.total_size_in_bytes", "bulk.avg_time",
        "bulk.avg_size_in_bytes"]

    def h_cat_shards(self, params, body, index=None):
        rows = []
        extra = ["" for _ in self._CAT_SHARDS_EXTRA]
        for name in sorted(self.indices.resolve(index)):
            svc = self.indices.indices[name]
            for i, shard in enumerate(svc.shards):
                rows.append([name, i, "p", "STARTED", shard.doc_count,
                             "0b", "127.0.0.1", self.node_id,
                             self.node_name] + list(extra))
                for _r in range(svc.num_replicas):
                    # single node: replica copies have nowhere to go
                    rows.append([name, i, "r", "UNASSIGNED", "", "", "",
                                 "", ""] + list(extra))
        return self._cat_table(
            rows,
            ["index", "shard", "prirep", "state", "docs", "store", "ip",
             "id", "node"] + self._CAT_SHARDS_EXTRA,
            _flag(params, "v"), params,
            default_columns=["index", "shard", "prirep", "state", "docs",
                             "store", "ip", "id", "node"],
            aliases={"i": "index", "s": "shard", "p": "prirep",
                     "st": "state", "d": "docs", "sto": "store",
                     "n": "node"})

    def h_cat_nodes(self, params, body):
        import shutil as _sh
        du = _sh.disk_usage(self.indices.data_path)
        full_id = _flag(params, "full_id")
        # the short id is ALWAYS 4 chars (cat/RestNodesAction renders
        # the uuid prefix) — cluster node names like "n2" are shorter,
        # so derive a stable 4-char form from a hash
        # reference ids are 20+ char uuids: short form is its 4-char
        # prefix, full form the whole id — cluster node names like "n2"
        # get a stable derived suffix to keep both shapes
        if len(self.node_id) >= 5:
            short_id, long_id = self.node_id[:4], self.node_id
        else:
            import hashlib as _hl
            digest = _hl.sha1(self.node_id.encode()).hexdigest()
            short_id = self.node_id[:4] if len(self.node_id) >= 4 \
                else digest[:4]
            long_id = f"{self.node_id}-{digest[:8]}"
        rows = [["127.0.0.1", long_id if full_id
                 else short_id, "42mb", 42, "100mb", 42, 1,
                 1, 1, 1024, "127.0.0.1:9200", "0.00", "0.00", "0.00",
                 "dim", "*", self.node_name,
                 _human_bytes(du.free), _human_bytes(du.total),
                 _human_bytes(du.used),
                 f"{du.used / du.total * 100:.2f}"
                 if du.total else "0.00", 1]]
        return self._cat_table(
            rows,
            ["ip", "id", "heap.current", "heap.percent", "heap.max",
             "ram.percent", "cpu", "file_desc.current",
             "file_desc.percent", "file_desc.max", "http", "load_1m",
             "load_5m", "load_15m", "node.role", "master", "name",
             "diskAvail", "diskTotal", "diskUsed", "diskUsedPercent",
             "pid"],
            _flag(params, "v"), params,
            default_columns=["ip", "heap.percent", "ram.percent", "cpu",
                             "load_1m", "load_5m", "load_15m",
                             "node.role", "master", "name"],
            aliases={"disk": "diskAvail", "dt": "diskTotal",
                     "du": "diskUsed", "dup": "diskUsedPercent"})

    def h_cat_templates(self, params, body, name=None):
        import fnmatch
        rows = []
        pats = [p.strip() for p in name.split(",")] if name else None
        for tname, t in sorted(self.templates.items()):
            if pats and not any(fnmatch.fnmatchcase(tname, p)
                                for p in pats):
                continue
            rows.append([tname,
                         "[" + ", ".join(t.get("index_patterns", []))
                         + "]",
                         t.get("order", t.get("priority", "")),
                         t.get("version", ""),
                         ("[" + ", ".join(t["composed_of"]) + "]")
                         if "composed_of" in t else ""])
        out = self._cat_table(rows, ["name", "index_patterns", "order",
                                     "version", "composed_of"],
                              _flag(params, "v"), params,
                              aliases={"n": "name",
                                       "t": "index_patterns",
                                       "o": "order", "p": "order",
                                       "v": "version",
                                       "c": "composed_of"})
        if isinstance(out, str) and rows and not _flag(params, "help"):
            # the 7.8+ table renders one blank line after every template
            # row (composable-template section separator)
            lines = [x for x in out.split("\n") if x != ""]
            head = ""
            if _flag(params, "v") and lines:
                head, lines = lines[0] + "\n", lines[1:]
            out = head + "".join(d + "\n\n" for d in lines)
        return out

    def h_cat_allocation(self, params, body, node_id=None):
        import shutil as _sh
        if node_id is not None and node_id not in (
                "_master", "_local", "*", "_all", self.node_id,
                self.node_name):
            rows = []
        else:
            du = _sh.disk_usage(self.indices.data_path)
            shards = sum(svc.num_shards
                         for svc in self.indices.indices.values())
            used = sum(svc.stats(with_field_bytes=False)
                       ["store"]["size_in_bytes"]
                       for svc in self.indices.indices.values())
            pct = round(du.used / du.total * 100) if du.total else 0
            unit = params.get("bytes")
            if unit:
                div = {"b": 1, "kb": 1 << 10, "mb": 1 << 20,
                       "gb": 1 << 30, "tb": 1 << 40}.get(unit, 1)
                fmt = lambda v: int(v // div)     # noqa: E731
            else:
                fmt = _human_bytes
            rows = [[shards, fmt(used), fmt(du.used), fmt(du.free),
                     fmt(du.total), pct, "127.0.0.1",
                     "127.0.0.1", self.node_name]]
        return self._cat_table(rows, ["shards", "disk.indices",
                                      "disk.used", "disk.avail",
                                      "disk.total", "disk.percent",
                                      "host", "ip", "node"],
                               _flag(params, "v"), params)

    def h_post_voting_exclusions(self, params, body):
        names = params.get("node_names")
        ids = params.get("node_ids")
        if (names is None) == (ids is None):
            raise IllegalArgumentError(
                "You must set [node_names] or [node_ids] but not both")
        for w in (names or ids).split(","):
            if ids is not None:
                entry = {"node_id": w,
                         "node_name": (self.node_name
                                       if w == self.node_id
                                       else "_absent_")}
            else:
                entry = {"node_id": (self.node_id
                                     if w == self.node_name
                                     else "_absent_"),
                         "node_name": w}
            self.voting_exclusions.append(entry)
        return 200, {}

    def h_delete_voting_exclusions(self, params, body):
        self.voting_exclusions = []
        return 200, {}

    def h_put_component_template(self, params, body, name):
        self.component_templates[name] = _json_body(body)
        return {"acknowledged": True}

    @staticmethod
    def _template_settings_json(t: dict) -> dict:
        """Render a stored template with its settings in the reference's
        normalized form: index-scoped keys grouped under "index", values
        as strings (``Settings.toXContent``)."""
        tpl = (t or {}).get("template")
        if not isinstance(tpl, dict) or not isinstance(
                tpl.get("settings"), dict):
            return t
        flat: Dict[str, str] = {}
        def walk(prefix, obj):
            for k, v in obj.items():
                key = f"{prefix}.{k}" if prefix else k
                if isinstance(v, dict):
                    walk(key, v)
                else:
                    flat[key] = str(v).lower() \
                        if isinstance(v, bool) else str(v)
        walk("", tpl["settings"])
        nested: Dict[str, Any] = {}
        for k, v in flat.items():
            if not k.startswith("index."):
                k = f"index.{k}"
            cur = nested
            parts = k.split(".")
            for p in parts[:-1]:
                cur = cur.setdefault(p, {})
            cur[parts[-1]] = v
        out = dict(t)
        out["template"] = dict(tpl, settings=nested)
        return out

    def h_get_component_template(self, params, body, name=None):
        items = [{"name": n,
                  "component_template": self._template_settings_json(t)}
                 for n, t in self.component_templates.items()
                 if name is None or n == name]
        if name is not None and not items:
            raise ResourceNotFoundError(
                f"component template matching [{name}] not found")
        return {"component_templates": items}

    def h_delete_component_template(self, params, body, name):
        if self.component_templates.pop(name, None) is None:
            raise ResourceNotFoundError(
                f"component template [{name}] missing")
        return {"acknowledged": True}


    def h_cat_fielddata(self, params, body, fields=None):
        want = set(fields.split(",")) if fields else None
        rows = []
        for n in sorted(self.indices.indices):
            svc = self.indices.indices[n]
            loaded = sorted(getattr(svc.mapper, "fielddata_loaded", ()))
            if not loaded:
                continue
            fd, _comp = svc.field_bytes()
            for f in loaded:
                if want is not None and f not in want:
                    continue
                rows.append([self.node_id[:4], "127.0.0.1", "127.0.0.1",
                             self.node_name, f,
                             _human_bytes(int(fd.get(f, 0)))])
        return self._cat_table(rows, ["id", "host", "ip", "node",
                                      "field", "size"],
                               _flag(params, "v"), params)

    def h_cat_nodeattrs(self, params, body):
        rows = [[self.node_name, self.node_id[:4], os.getpid(),
                 "127.0.0.1", "127.0.0.1", 9300, "testattr", "test"]]
        return self._cat_table(
            rows, ["node", "id", "pid", "host", "ip", "port", "attr",
                   "value"],
            _flag(params, "v"), params,
            default_columns=["node", "host", "ip", "attr", "value"])

    def h_cat_plugins(self, params, body):
        rows = [[self.node_id[:4], self.node_name, "tpu-engine",
                 "8.0.0", "TPU-native execution engine"]]
        return self._cat_table(rows, ["id", "name", "component",
                                      "version", "description"],
                               _flag(params, "v"), params,
                               default_columns=["name", "component",
                                                "version",
                                                "description"])

    def h_cat_recovery(self, params, body, index=None):
        names = sorted(self.indices.resolve(index)) if index else \
            sorted(self.indices.indices)
        rows = []
        for n in names:
            svc = self.indices.indices[n]
            rinfo = getattr(svc, "recovery_info", None) or {}
            rtype = (rinfo.get("type") or (
                "EXISTING_STORE" if getattr(svc, "_reopened", False)
                or svc.closed else "EMPTY_STORE")).lower()
            files = int(rinfo.get("files", 0))
            size = int(rinfo.get("bytes", 0))
            fp = "100.0%" if files else "0.0%"
            for sid in range(svc.num_shards):
                rows.append([
                    n, sid, "0s", rtype, "done", "127.0.0.1",
                    self.node_name, "127.0.0.1", self.node_name,
                    "n/a", "n/a", files, files, fp, files,
                    _human_bytes(size), _human_bytes(size),
                    "100.0%" if size else "0.0%", _human_bytes(size),
                    0, 0, "100.0%"])
        return self._cat_table(
            rows,
            ["index", "shard", "time", "type", "stage", "source_host",
             "source_node", "target_host", "target_node", "repository",
             "snapshot", "files", "files_recovered", "files_percent",
             "files_total", "bytes", "bytes_recovered", "bytes_percent",
             "bytes_total", "translog_ops", "translog_ops_recovered",
             "translog_ops_percent"],
            _flag(params, "v"), params,
            aliases={"i": "index", "s": "shard", "t": "time",
                     "ty": "type", "st": "stage", "shost": "source_host",
                     "thost": "target_host", "rep": "repository",
                     "snap": "snapshot", "f": "files",
                     "fr": "files_recovered", "fp": "files_percent",
                     "tf": "files_total", "b": "bytes",
                     "br": "bytes_recovered", "bp": "bytes_percent",
                     "tb": "bytes_total", "to": "translog_ops",
                     "tor": "translog_ops_recovered",
                     "top": "translog_ops_percent"})

    def h_cat_repositories(self, params, body):
        rows = [[name, "fs"]
                for name in sorted(self.snapshots.repositories)]
        return self._cat_table(rows, ["id", "type"],
                               _flag(params, "v"), params)

    @staticmethod
    def cat_segment_row(index: str, sid: int, owner_short: str,
                        seg_id: str, generation: int, live: int,
                        deleted: int) -> list:
        """One cat-segments row (shared by the single-node handler and
        the cluster front's owner-gathered rendering)."""
        return [index, sid, "p", "127.0.0.1", owner_short, seg_id,
                generation, live, deleted,
                "1kb", 0, "true", "true", "9.0.0", "false"]

    def cat_segments_table(self, rows, params):
        """Render cat-segments rows with the canonical column spec."""
        return self._cat_table(
            rows,
            ["index", "shard", "prirep", "ip", "id", "segment",
             "generation", "docs.count", "docs.deleted", "size",
             "size.memory", "committed", "searchable", "version",
             "compound"],
            _flag(params, "v"), params,
            default_columns=["index", "shard", "prirep", "ip", "segment",
                             "generation", "docs.count", "docs.deleted",
                             "size", "size.memory", "committed",
                             "searchable", "version", "compound"],
            aliases={"i": "index", "s": "shard", "seg": "segment"})

    def h_cat_segments(self, params, body, index=None):
        names = sorted(self.indices.resolve(index)) if index else \
            sorted(self.indices.indices)
        rows = []
        for n in names:
            svc = self.indices.indices[n]
            if svc.closed:
                from ..common.errors import IndexClosedError
                raise IndexClosedError(f"closed index [{n}]")
            for sid, engine in enumerate(svc.shards):
                for gi, seg in enumerate(engine.searchable_segments()):
                    rows.append(self.cat_segment_row(
                        n, sid, self.node_id[:4], seg.seg_id, gi,
                        int(seg.live.sum()), int((~seg.live).sum())))
        return self.cat_segments_table(rows, params)

    def h_cat_snapshots(self, params, body, repository=None):
        rows = []
        repos = [repository] if repository else \
            sorted(self.snapshots.repositories)
        for rname in repos:
            repo = self.snapshots.get_repository(rname)
            for entry in repo.read_index()["snapshots"]:
                meta = repo.read_snapshot(entry["snapshot"])
                start = meta.get("start_time_in_millis", 0) // 1000
                end = meta.get("end_time_in_millis", 0) // 1000
                sh = meta.get("shards") or {}
                rows.append([
                    meta["snapshot"], rname,
                    meta.get("state", "SUCCESS"), start,
                    time.strftime("%H:%M:%S", time.gmtime(start)),
                    end, time.strftime("%H:%M:%S", time.gmtime(end)),
                    f"{max(0, end - start)}s",
                    len(meta.get("indices") or {}),
                    sh.get("successful", 0), sh.get("failed", 0),
                    sh.get("total", 0), ""])
        return self._cat_table(
            rows,
            ["id", "repository", "status", "start_epoch", "start_time",
             "end_epoch", "end_time", "duration", "indices",
             "successful_shards", "failed_shards", "total_shards",
             "reason"],
            _flag(params, "v"), params,
            default_columns=["id", "repository", "status", "start_epoch",
                            "start_time", "end_epoch", "end_time",
                            "duration", "indices", "successful_shards",
                            "failed_shards", "total_shards"])

    _THREAD_POOLS = ("analyze", "fetch_shard_started",
                     "fetch_shard_store", "flush", "force_merge",
                     "generic", "get", "listener", "management",
                     "refresh", "search", "search_throttled", "snapshot",
                     "warmer", "write")

    def h_cat_thread_pool(self, params, body, pools=None):
        import fnmatch
        pats = pools or params.get("thread_pool_patterns")
        sel = pats.split(",") if pats else None
        rows = []
        for pname in self._THREAD_POOLS:
            if sel and not any(fnmatch.fnmatchcase(pname, p)
                               for p in sel):
                continue
            fixed = pname in ("get", "search", "write",
                              "search_throttled")
            rows.append([self.node_name, self.node_id[:4], "127.0.0.1",
                         "127.0.0.1", os.getpid(), 9300, pname,
                         "fixed" if fixed else "scaling", 0, 0, 0,
                         1, 1, -1, 0, 0, "" if fixed else 1,
                         "" if fixed else "5m", ""])
        return self._cat_table(
            rows,
            ["node_name", "id", "ip", "host", "pid", "port", "name",
             "type", "active", "queue", "rejected", "size", "pool_size",
             "queue_size", "largest", "completed", "core", "keep_alive",
             "max"],
            _flag(params, "v"), params,
            default_columns=["node_name", "name", "active", "queue",
                             "rejected"],
            aliases={"h": "host", "i": "ip", "po": "port",
                     "nn": "node_name", "n": "name", "t": "type",
                     "a": "active", "q": "queue", "r": "rejected",
                     "l": "largest", "c": "completed", "cr": "core",
                     "ka": "keep_alive", "sz": "size",
                     "psz": "pool_size", "qs": "queue_size"})

    def h_cat_tasks(self, params, body):
        now_ms = int(time.time() * 1000)
        rows = [["cluster:monitor/tasks/lists", f"{self.node_id}:1",
                 "-", "transport", now_ms,
                 time.strftime("%H:%M:%S"), "1ms", "127.0.0.1",
                 self.node_name, "requests[1]",
                 params.get("__x_opaque_id", "-")]]
        headers = ["action", "task_id", "parent_task_id", "type",
                   "start_time", "timestamp", "running_time", "ip",
                   "node", "description", "x_opaque_id"]
        default = headers[:-2]
        if params.get("detailed") in ("true", ""):
            default = headers[:-1]
        return self._cat_table(rows, headers, _flag(params, "v"),
                               params, default_columns=default)

    def h_cat_aliases(self, params, body, name=None):
        import fnmatch
        rows = []
        pats = [p.strip() for p in name.split(",")] if name else None
        ew = (params.get("expand_wildcards") or "all").split(",")
        for alias, names in sorted(self.indices.all_aliases().items()):
            if pats and not any(fnmatch.fnmatchcase(alias, p)
                                for p in pats):
                continue
            for n in names:
                spec = self.indices.indices[n].aliases.get(alias, {})
                hidden_idx = str(self.indices.indices[n].settings.get(
                    "index.hidden", "")).lower() == "true"
                if hidden_idx and params.get("expand_wildcards") and \
                        "hidden" not in ew and "all" not in ew:
                    continue    # explicit expand excludes hidden indices
                rows.append([
                    alias, n,
                    "*" if spec.get("filter") else "-",
                    spec.get("index_routing") or "-",
                    spec.get("search_routing") or "-",
                    spec.get("is_write_index", "-")])
        return self._cat_table(rows, ["alias", "index", "filter",
                                      "routing.index", "routing.search",
                                      "is_write_index"],
                               _flag(params, "v"), params,
                               aliases={"a": "alias", "i": "index",
                                        "idx": "index"})

    # ------------------------------------------------------------------
    # index CRUD / admin
    # ------------------------------------------------------------------

    def _apply_templates(self, name: str, settings: dict,
                         mappings: dict) -> Tuple[dict, dict, dict]:
        import fnmatch
        matching = []
        for tname, t in self.templates.items():
            for pat in t.get("index_patterns", []):
                if fnmatch.fnmatchcase(name, pat):
                    matching.append((t.get("priority", 0), tname, t))
                    break
        merged_settings: dict = {}
        merged_mappings: dict = {}

        def _deep_props(dst: dict, src: dict) -> None:
            for k, v in (src or {}).items():
                if isinstance(v, dict) and isinstance(dst.get(k), dict):
                    _deep_props(dst[k], v)
                else:
                    dst[k] = v

        merged_aliases: dict = {}
        for _, _, t in sorted(matching, key=lambda x: x[0]):
            layers = []
            for comp in t.get("composed_of", []):
                ct = (self.component_templates.get(comp) or {})
                layers.append(ct.get("template") or {})
            layers.append(t.get("template", t))
            for tpl in layers:
                merged_settings.update(tpl.get("settings") or {})
                props = (tpl.get("mappings") or {}).get("properties") or {}
                _deep_props(merged_mappings.setdefault("properties", {}),
                            props)
                merged_aliases.update(tpl.get("aliases") or {})
        merged_settings.update(settings or {})
        if mappings:
            merged_mappings.setdefault("properties", {}).update(
                mappings.get("properties") or {})
            for k, v in mappings.items():
                if k != "properties":
                    merged_mappings[k] = v
        return merged_settings, merged_mappings, merged_aliases

    def h_create_index(self, params, body, index):
        b = _json_body(body)
        settings, mappings, aliases = self._apply_templates(
            index, b.get("settings") or {}, b.get("mappings") or {})
        flat_settings = {k: v for grp in (settings.get("index", {})
                                          if isinstance(settings.get(
                                              "index"), dict) else {},
                                          settings)
                         for k, v in (grp or {}).items()}
        sd_vals = [flat_settings.get("soft_deletes.enabled"),
                   flat_settings.get("index.soft_deletes.enabled")]
        for container in (flat_settings.get("soft_deletes"),
                          (flat_settings.get("index") or {})
                          if isinstance(flat_settings.get("index"), dict)
                          else {}):
            if isinstance(container, dict):
                sd_vals.append(container.get("enabled"))
                inner = container.get("soft_deletes")
                if isinstance(inner, dict):
                    sd_vals.append(inner.get("enabled"))
        if any(str(v).lower() == "false" for v in sd_vals
               if v is not None):
            raise IllegalArgumentError(
                "Creating indices with soft-deletes disabled is no "
                "longer supported")

        def _check_empty_names(props):
            for fname, spec in (props or {}).items():
                if fname == "":
                    raise IllegalArgumentError(
                        "field name cannot be an empty string")
                if isinstance(spec, dict):
                    _check_empty_names(spec.get("properties"))
        _check_empty_names((mappings or {}).get("properties"))
        aliases = dict(aliases)
        aliases.update(b.get("aliases") or {})
        aliases = {a: self._alias_spec(sp or {})
                   for a, sp in aliases.items()}
        self.indices.create_index(index, settings, mappings,
                                  aliases or None)
        return {"acknowledged": True, "shards_acknowledged": True,
                "index": index}

    def h_delete_index(self, params, body, index):
        """DELETE index. Aliases are NOT deletable and wildcards match
        concrete index names only (``TransportDeleteIndexAction`` +
        DestructiveOperations semantics)."""
        import fnmatch
        ignore = params.get("ignore_unavailable") in ("true", "")
        allow_no = params.get("allow_no_indices") != "false"
        names: List[str] = []
        for part in (index or "").split(","):
            if part in ("_all", "*") or any(c in part for c in "*?"):
                got = sorted(self.indices.indices) \
                    if part in ("_all", "*") else \
                    [n for n in self.indices.indices
                     if fnmatch.fnmatchcase(n, part)]
                if not got and not allow_no:
                    raise IndexNotFoundError(part)
                names.extend(got)
            elif part in self.indices.indices:
                names.append(part)
            else:
                if ignore:
                    continue
                if any(part in svc.aliases
                       for svc in self.indices.indices.values()):
                    raise IllegalArgumentError(
                        f"The provided expression [{part}] matches an "
                        f"alias, specify the corresponding concrete "
                        f"indices instead.")
                raise IndexNotFoundError(part)
        for n in dict.fromkeys(names):
            self.indices.delete_index(n)
        return {"acknowledged": True}

    def h_get_index(self, params, body, index):
        ew = (params.get("expand_wildcards") or "open").split(",")
        ignore = params.get("ignore_unavailable") in ("true", "")
        allow_no = params.get("allow_no_indices") != "false"
        human = params.get("human") in ("true", "")
        names: List[str] = []
        for part in (index or "_all").split(","):
            is_pat = any(c in part for c in "*?") or \
                part in ("_all", "")
            try:
                got = self.indices.resolve(part)
            except IndexNotFoundError:
                if ignore:
                    continue
                raise
            if is_pat and "all" not in ew:
                got = [n for n in got
                       if ("open" in ew
                           and not self.indices.indices[n].closed)
                       or ("closed" in ew
                           and self.indices.indices[n].closed)]
            names.extend(n for n in got if n not in names)
        if not names:
            if index and not allow_no:
                raise IndexNotFoundError(index)
            return {}
        out = {}
        for name in names:
            svc = self.indices.indices[name]
            # full settings render (custom keys like index.priority
            # included), same source as GET /{index}/_settings
            idx_settings = self._nest_flat(
                self._index_flat_settings(name)).get("index", {})
            if human:
                import datetime as _dtm
                idx_settings["creation_date_string"] = \
                    _dtm.datetime.fromtimestamp(
                        svc.creation_date / 1000.0,
                        tz=_dtm.timezone.utc).strftime(
                        "%Y-%m-%dT%H:%M:%S.%fZ")
                idx_settings["version"]["created_string"] = "8.0.0"
            out[name] = {
                "aliases": svc.aliases,
                "mappings": svc.mapper.mapping_dict(),
                "settings": {"index": idx_settings},
            }
        return out

    def h_mapping(self, params, body, index=None):
        ew = params.get("expand_wildcards")
        if index is not None and \
                params.get("ignore_unavailable") in ("true", ""):
            names = []
            for part in index.split(","):
                try:
                    names.extend(self.indices.resolve(part))
                except IndexNotFoundError:
                    pass
        else:
            names = self.indices.resolve(index)
        if ew == "none" and index and any(c in index for c in "*"):
            names = []
        if not names and index and \
                params.get("allow_no_indices") == "false":
            raise IndexNotFoundError(index)
        if params.get("__method") == "PUT" or body:
            b = _json_body(body)
            for n in names:
                self.indices.indices[n].put_mapping(b)
            return {"acknowledged": True}
        return {n: {"mappings": self.indices.indices[n].mapper.mapping_dict()}
                for n in names}

    #: defaults surfaced by include_defaults=true (scoped subset of
    #: IndexSettings' registered defaults)
    SETTINGS_DEFAULTS = {
        "index.refresh_interval": "1s",
        "index.max_result_window": "10000",
        "index.max_inner_result_window": "100",
        "index.max_rescore_window": "10000",
        "index.max_ngram_diff": "1",
        "index.max_shingle_diff": "3",
        "index.blocks.read_only": "false",
        "index.gc_deletes": "60s",
        "index.flush_after_merge": "512mb",
        "index.translog.durability": "REQUEST",
        "index.translog.flush_threshold_size": "512mb",
        "index.soft_deletes.enabled": "true",
    }

    def _index_flat_settings(self, n: str) -> Dict[str, str]:
        svc = self.indices.indices[n]

        def s(v):
            if isinstance(v, bool):
                return "true" if v else "false"
            return str(v)
        flat = {}
        for k, v in svc.settings.items():
            k2 = k if k.startswith("index.") else f"index.{k}"
            flat[k2] = s(v)
        flat["index.number_of_shards"] = str(svc.num_shards)
        flat["index.number_of_replicas"] = str(svc.num_replicas)
        flat["index.uuid"] = svc.uuid
        flat["index.creation_date"] = str(svc.creation_date)
        flat["index.version.created"] = "8000099"
        flat["index.provided_name"] = n
        return flat

    @staticmethod
    def _nest_flat(flat: Dict[str, str]) -> dict:
        out: dict = {}
        for k, v in flat.items():
            cur = out
            parts = k.split(".")
            ok = True
            for p in parts[:-1]:
                nxt = cur.setdefault(p, {})
                if not isinstance(nxt, dict):
                    ok = False
                    break
                cur = nxt
            if ok:
                cur[parts[-1]] = v
        return out

    def h_settings(self, params, body, index=None, name=None):
        if body:
            b = _json_body(body)
            if params.get("ignore_unavailable") in ("true", "") and index:
                names = []
                for part in index.split(","):
                    try:
                        names.extend(self.indices.resolve(part))
                    except IndexNotFoundError:
                        pass
            else:
                names = self.indices.resolve(index)
            preserve = params.get("preserve_existing") in ("true", "")
            for n in names:
                svc = self.indices.indices[n]
                spec = b.get("settings", b)
                if preserve:
                    from ..node.indices_service import _flatten_settings
                    flat = _flatten_settings(dict(spec))
                    spec = {k: v for k, v in flat.items()
                            if (k if k.startswith("index.")
                                else f"index.{k}") not in svc.settings
                            and k.split(".")[-1] not in
                            ("number_of_replicas", "number_of_shards")}
                svc.update_settings(spec)
            return {"acknowledged": True}
        names = self.indices.resolve(index)
        if index is not None and not names and \
                not any(c in index for c in "*,"):
            raise IndexNotFoundError(index)
        import fnmatch
        pats = None
        if name is not None and name not in ("_all", "*"):
            pats = [p.strip() for p in name.split(",") if p.strip()]
        flat_form = params.get("flat_settings") in ("true", "")
        out = {}
        for n in names:
            flat = self._index_flat_settings(n)
            if pats is not None:
                flat = {k: v for k, v in flat.items()
                        if any(fnmatch.fnmatchcase(k, p) for p in pats)}
            entry: dict = {
                "settings": (flat if flat_form else self._nest_flat(flat))}
            if params.get("include_defaults") in ("true", ""):
                d = {k: v for k, v in self.SETTINGS_DEFAULTS.items()
                     if k not in self._index_flat_settings(n)}
                if pats is not None:
                    d = {k: v for k, v in d.items()
                         if any(fnmatch.fnmatchcase(k, p) for p in pats)}
                entry["defaults"] = d if flat_form else self._nest_flat(d)
            out[n] = entry
        return out

    def h_refresh(self, params, body, index=None):
        names = self.indices.resolve(index)
        shards = 0
        for n in names:
            svc = self.indices.indices[n]
            svc.refresh()
            shards += svc.num_shards
        return {"_shards": {"total": shards, "successful": shards,
                            "failed": 0}}

    # -- security (x-pack ApiKeyService analog) -------------------------

    def h_create_api_key(self, params, body):
        b = _json_body(body)
        name = b.get("name")
        if not name:
            raise IllegalArgumentError("api key name is required")
        exp = b.get("expiration")
        exp_ms = None
        if exp:
            from ..common.settings import parse_time_millis
            exp_ms = int(parse_time_millis(exp))
        out = self.security.create_key(
            name, expiration_ms=exp_ms,
            role_descriptors=b.get("role_descriptors"))
        return {"id": out["id"], "name": out["name"],
                "api_key": out["api_key"], "encoded": out["encoded"]}

    def h_invalidate_api_key(self, params, body):
        b = _json_body(body)
        ids = b.get("ids") or ([b["id"]] if b.get("id") else None)
        name = b.get("name")
        if not ids and not name:
            raise IllegalArgumentError(
                "One of [ids, name] must be specified")
        return self.security.invalidate(ids=ids, name=name)

    def h_get_api_keys(self, params, body):
        return self.security.list_keys()

    def h_authenticate(self, params, body):
        if not self.security.enabled:
            return {"username": "_anonymous", "roles": ["superuser"],
                    "authentication_type": "anonymous"}
        p = getattr(self._principal_tls, "value", None) or {}
        # API keys report no role names (their effective privileges are
        # the key's role_descriptors); realm users report their roles
        return {"username": p.get("username"),
                "roles": p.get("roles", []),
                "authentication_type": p.get("authentication_type"),
                "api_key": p.get("api_key")}

    def _principal(self) -> dict:
        return getattr(self._principal_tls, "value", None) or \
            {"username": "_anonymous", "roles": ["superuser"]}

    def h_put_user(self, params, body, username):
        return self.security.rbac.put_user(username, _json_body(body))

    def h_get_users(self, params, body, username=None):
        return self.security.rbac.get_users(username)

    def h_delete_user(self, params, body, username):
        out = self.security.rbac.delete_user(username)
        return (200 if out["found"] else 404), out

    def h_change_password(self, params, body, username):
        return self.security.rbac.change_password(username,
                                                  _json_body(body))

    def h_enable_user(self, params, body, username):
        return self.security.rbac.set_enabled(username, True)

    def h_disable_user(self, params, body, username):
        return self.security.rbac.set_enabled(username, False)

    def h_put_role(self, params, body, name):
        return self.security.rbac.put_role(name, _json_body(body))

    def h_get_roles(self, params, body, name=None):
        return self.security.rbac.get_roles(name)

    def h_delete_role(self, params, body, name):
        out = self.security.rbac.delete_role(name)
        return (200 if out["found"] else 404), out

    def h_has_privileges(self, params, body):
        return self.security.rbac.has_privileges(self._principal(),
                                                 _json_body(body))

    # -- async search (x-pack async-search analog:
    # TransportSubmitAsyncSearchAction.java:48) ------------------------

    def h_submit_async_search(self, params, body, index):
        """Submit: run the search on a detached task; block up to
        ``wait_for_completion_timeout`` (default 1s) and return inline
        when it finishes in time, else the async envelope with the id."""
        import uuid as _uuid
        from ..common.settings import parse_time_millis
        wait_ms = parse_time_millis(
            params.get("wait_for_completion_timeout", "1s"))
        body_bytes = body
        q = "&".join(f"{k}={v}" for k, v in params.items()
                     if k not in ("wait_for_completion_timeout",
                                  "keep_on_completion", "keep_alive"))
        task = self.task_manager.register(
            "indices:data/read/async_search",
            description=f"async_search [{index}]")
        sid = _uuid.uuid4().hex
        self._async_searches[sid] = task

        def run():
            # the submitter already authenticated: this internal hop
            # must not re-challenge (it runs with no client headers)
            self._internal_tls.active = True
            try:
                st, _ct, out = self.handle("POST", f"/{index}/_search",
                                           q, body_bytes)
            finally:
                self._internal_tls.active = False
            doc = json.loads(out)
            if st >= 400:
                raise ElasticsearchError(
                    (doc.get("error") or {}).get("reason", "failed"))
            return doc

        self.task_manager.run_async(task, run)
        deadline = time.time() + wait_ms / 1e3
        while task.running and time.time() < deadline:
            time.sleep(0.005)
        return self._async_envelope(sid, task)

    def _async_envelope(self, sid: str, task) -> dict:
        out = {"id": sid, "is_partial": bool(task.running),
               "is_running": bool(task.running),
               "start_time_in_millis": int(task.start_time * 1000),
               "expiration_time_in_millis":
                   int(task.start_time * 1000) + 432_000_000}
        if not task.running:
            if getattr(task, "error", None):
                return (400, {"error": task.error,
                              "id": sid, "is_running": False,
                              "is_partial": True})
            out["response"] = task.result
        return out

    def h_get_async_search(self, params, body, id):
        task = self._async_searches.get(id)
        if task is None:
            raise ResourceNotFoundError(id)
        return self._async_envelope(id, task)

    def h_delete_async_search(self, params, body, id):
        task = self._async_searches.pop(id, None)
        if task is None:
            raise ResourceNotFoundError(id)
        if task.running:
            self.task_manager.cancel(task, "deleted")
        return {"acknowledged": True}

    # ------------------------------------------------------------------
    # internal re-dispatch seam (SQL/EQL/graph/transform ride the full
    # cluster-aware search path by calling back through handle())
    # ------------------------------------------------------------------

    def internal_search(self, index: str, body: dict,
                        params: str = "") -> dict:
        """Run a search as an already-authenticated internal dispatch and
        return the parsed response; ES-shaped errors re-raise."""
        prev = getattr(self._internal_tls, "active", False)
        self._internal_tls.active = True
        try:
            st, _ct, out = self.handle(
                "POST", f"/{index}/_search", params,
                json.dumps(body).encode())
        finally:
            self._internal_tls.active = prev
        doc = json.loads(out)
        if st >= 400:
            err = (doc.get("error") or {})
            if isinstance(err, str):
                err = {"reason": err}
            e = ElasticsearchError(err.get("reason", "search failed"))
            e.error_type = err.get("type", "exception")
            e.status = st
            raise e
        return doc

    def internal_bulk(self, index: str, lines: List[dict],
                      refresh: bool = False) -> dict:
        """Internal bulk write (transform/rollup/watcher destinations)."""
        prev = getattr(self._internal_tls, "active", False)
        self._internal_tls.active = True
        try:
            payload = "".join(json.dumps(ln) + "\n" for ln in lines)
            st, _ct, out = self.handle(
                "POST", f"/{index}/_bulk",
                "refresh=true" if refresh else "",
                payload.encode())
        finally:
            self._internal_tls.active = prev
        doc = json.loads(out)
        if st >= 400:
            raise ElasticsearchError(str(doc.get("error")))
        return doc

    # ------------------------------------------------------------------
    # SQL (x-pack/plugin/sql analog — xpack/sql.py)
    # ------------------------------------------------------------------

    @property
    def sql(self):
        if getattr(self, "_sql_svc", None) is None:
            from ..xpack.sql import SqlService

            def mapper_of(table):
                names = self.indices.resolve(table)
                return self.indices.indices[names[0]].mapper \
                    if names else None
            self._sql_svc = SqlService(
                lambda index, b: self.internal_search(index, b),
                mapper_of)
        return self._sql_svc

    def h_sql(self, params, body):
        payload = _json_body(body)
        fmt = params.get("format", "json")
        out = self.sql.execute(payload, fmt)
        if isinstance(out, str):
            ct = {"csv": "text/csv; charset=UTF-8",
                  "tsv": "text/tab-separated-values; charset=UTF-8",
                  "txt": "text/plain; charset=UTF-8"}.get(
                      fmt, "text/plain; charset=UTF-8")
            return 200, ct, out
        return out

    # ------------------------------------------------------------------
    # EQL (x-pack/plugin/eql analog — xpack/eql.py)
    # ------------------------------------------------------------------

    @property
    def eql(self):
        if getattr(self, "_eql_svc", None) is None:
            from ..xpack.eql import EqlService

            def mapper_of(table):
                names = self.indices.resolve(table)
                return self.indices.indices[names[0]].mapper \
                    if names else None
            self._eql_svc = EqlService(
                lambda index, b: self.internal_search(index, b),
                mapper_of)
        return self._eql_svc

    def h_eql_search(self, params, body, index):
        self._deny_if_restricted(index)
        self.indices.resolve(index)      # 404 before parsing, like ES
        return self.eql.search(index, _json_body(body))

    def h_graph_explore(self, params, body, index):
        self._deny_if_restricted(index)
        """POST /{index}/_graph/explore (x-pack graph analog)."""
        self.indices.resolve(index)
        from ..xpack.graph import GraphService
        if getattr(self, "_graph_svc", None) is None:
            self._graph_svc = GraphService(
                lambda i, b: self.internal_search(i, b))
        return self._graph_svc.explore(index, _json_body(body))

    # ------------------------------------------------------------------
    # transform / rollup / watcher / enrich (x-pack analogs)
    # ------------------------------------------------------------------

    @property
    def transform(self):
        if getattr(self, "_transform_svc", None) is None:
            from ..xpack.transform import TransformService
            self._transform_svc = TransformService(
                lambda i, b: self.internal_search(i, b),
                lambda i, lines: self.internal_bulk(i, lines,
                                                    refresh=True))
        return self._transform_svc

    def h_put_transform(self, params, body, id):
        return self.transform.put(id, _json_body(body))

    def h_get_transform(self, params, body, id=None):
        return self.transform.get(id)

    def h_transform_stats(self, params, body, id=None):
        return self.transform.stats(id)

    def h_preview_transform(self, params, body):
        return self.transform.preview(_json_body(body))

    def h_start_transform(self, params, body, id):
        return self.transform.start(id)

    def h_stop_transform(self, params, body, id):
        return self.transform.stop(id)

    def h_delete_transform(self, params, body, id):
        return self.transform.delete(id,
                                     force=params.get("force") == "true")

    @property
    def rollup(self):
        if getattr(self, "_rollup_svc", None) is None:
            from ..xpack.rollup import RollupService
            def create_index(i, mappings):
                prev = getattr(self._internal_tls, "active", False)
                self._internal_tls.active = True
                try:
                    self.handle("PUT", f"/{i}", "", json.dumps(
                        {"mappings": mappings}).encode())
                finally:
                    self._internal_tls.active = prev
            self._rollup_svc = RollupService(
                lambda i, b: self.internal_search(i, b),
                lambda i, lines: self.internal_bulk(i, lines,
                                                    refresh=True),
                create_index)
        return self._rollup_svc

    def h_put_rollup_job(self, params, body, id):
        return self.rollup.put_job(id, _json_body(body))

    def h_get_rollup_jobs(self, params, body, id=None):
        return self.rollup.get_jobs(id)

    def h_delete_rollup_job(self, params, body, id):
        return self.rollup.delete_job(id)

    def h_start_rollup_job(self, params, body, id):
        return self.rollup.start_job(id)

    def h_stop_rollup_job(self, params, body, id):
        return self.rollup.stop_job(id)

    def h_rollup_caps(self, params, body, pattern=None):
        return self.rollup.caps(pattern)

    def h_rollup_search(self, params, body, index):
        self.indices.resolve(index)
        return self.rollup.rollup_search(index, _json_body(body))

    @property
    def watcher(self):
        if getattr(self, "_watcher_svc", None) is None:
            from ..xpack.watcher import WatcherService
            self._watcher_svc = WatcherService(
                lambda i, b: self.internal_search(i, b),
                lambda i, lines: self.internal_bulk(i, lines,
                                                    refresh=True))
        return self._watcher_svc

    def h_put_watch(self, params, body, id):
        return self.watcher.put(id, _json_body(body),
                                active=params.get("active", "true")
                                != "false")

    def h_get_watch(self, params, body, id):
        return self.watcher.get(id)

    def h_delete_watch(self, params, body, id):
        return self.watcher.delete(id)

    def h_execute_watch(self, params, body, id):
        return self.watcher.execute(id, _json_body(body))

    def h_activate_watch(self, params, body, id):
        return self.watcher.activate(id, True)

    def h_deactivate_watch(self, params, body, id):
        return self.watcher.activate(id, False)

    def h_watcher_stats(self, params, body):
        return self.watcher.stats()

    def h_watcher_tick(self, params, body):
        now = params.get("now_ms")
        return self.watcher.tick(int(now) if now else None)

    @property
    def ccr(self):
        if getattr(self, "_ccr_svc", None) is None:
            from ..xpack.ccr import CcrService
            self._ccr_svc = CcrService(self)
        return self._ccr_svc

    def h_ccr_changes(self, params, body, index):
        return self.ccr.shard_changes(
            index, int(params.get("shard", 0)),
            int(params.get("from_seq_no", 0)),
            int(params.get("max_ops", 5120)))

    def h_ccr_follow(self, params, body, index):
        return self.ccr.follow(index, _json_body(body))

    def h_ccr_pause(self, params, body, index):
        return self.ccr.pause(index)

    def h_ccr_resume(self, params, body, index):
        return self.ccr.resume(index)

    def h_ccr_unfollow(self, params, body, index):
        return self.ccr.unfollow(index)

    def h_ccr_stats(self, params, body):
        return self.ccr.stats()

    def h_ccr_tick(self, params, body):
        return self.ccr.tick()

    def h_ccr_put_auto(self, params, body, name):
        return self.ccr.put_auto_follow(name, _json_body(body))

    def h_ccr_get_auto(self, params, body, name=None):
        return self.ccr.get_auto_follow(name)

    def h_ccr_del_auto(self, params, body, name):
        return self.ccr.delete_auto_follow(name)

    @property
    def ml(self):
        if getattr(self, "_ml_svc", None) is None:
            from ..xpack.ml import MlService, registry_bind
            self._ml_svc = MlService(
                lambda i, b: self.internal_search(i, b),
                lambda i, lines: self.internal_bulk(i, lines,
                                                    refresh=True))
            registry_bind(self._ml_svc)
        return self._ml_svc

    def h_ml_put_job(self, params, body, job_id):
        return self.ml.put_job(job_id, _json_body(body))

    def h_ml_get_jobs(self, params, body, job_id=None):
        return self.ml.get_jobs(job_id)

    def h_ml_job_stats(self, params, body, job_id=None):
        return self.ml.job_stats(job_id)

    def h_ml_delete_job(self, params, body, job_id):
        return self.ml.delete_job(job_id,
                                  force=params.get("force") == "true")

    def h_ml_open_job(self, params, body, job_id):
        return self.ml.open_job(job_id)

    def h_ml_close_job(self, params, body, job_id):
        return self.ml.close_job(job_id,
                                 force=params.get("force") == "true")

    def h_ml_post_data(self, params, body, job_id):
        return self.ml.post_data(job_id, body)

    def h_ml_flush_job(self, params, body, job_id):
        return self.ml.flush_job(job_id)

    def h_ml_get_buckets(self, params, body, job_id):
        return self.ml.get_buckets(job_id, _json_body(body), params)

    def h_ml_get_records(self, params, body, job_id):
        return self.ml.get_records(job_id, _json_body(body), params)

    def h_ml_overall_buckets(self, params, body, job_id):
        return self.ml.get_overall_buckets(job_id, _json_body(body))

    def h_ml_get_snapshots(self, params, body, job_id):
        return self.ml.get_model_snapshots(job_id)

    def h_ml_revert_snapshot(self, params, body, job_id, snapshot_id):
        return self.ml.revert_model_snapshot(job_id, snapshot_id)

    def h_ml_put_datafeed(self, params, body, feed_id):
        return self.ml.put_datafeed(feed_id, _json_body(body))

    def h_ml_get_datafeeds(self, params, body, feed_id=None):
        return self.ml.get_datafeeds(feed_id)

    def h_ml_datafeed_stats(self, params, body, feed_id=None):
        return self.ml.datafeed_stats(feed_id)

    def h_ml_del_datafeed(self, params, body, feed_id):
        return self.ml.delete_datafeed(feed_id)

    def h_ml_start_datafeed(self, params, body, feed_id):
        payload = _json_body(body)
        return self.ml.start_datafeed(
            feed_id, payload.get("start") or params.get("start"),
            payload.get("end") or params.get("end"))

    def h_ml_stop_datafeed(self, params, body, feed_id):
        return self.ml.stop_datafeed(feed_id)

    def h_ml_preview_datafeed(self, params, body, feed_id):
        return self.ml.preview_datafeed(feed_id)

    def h_ml_put_model(self, params, body, model_id):
        return self.ml.put_trained_model(model_id, _json_body(body))

    def h_ml_get_models(self, params, body, model_id=None):
        return self.ml.get_trained_models(model_id)

    def h_ml_model_stats(self, params, body, model_id=None):
        return self.ml.trained_model_stats(model_id)

    def h_ml_del_model(self, params, body, model_id):
        return self.ml.delete_trained_model(model_id)

    def h_ml_infer(self, params, body, model_id):
        return self.ml.infer(model_id, _json_body(body))

    def h_ml_put_analytics(self, params, body, id):
        return self.ml.put_analytics(id, _json_body(body))

    def h_ml_get_analytics(self, params, body, id=None):
        return self.ml.get_analytics(id)

    def h_ml_analytics_stats(self, params, body, id=None):
        return self.ml.analytics_stats(id)

    def h_ml_del_analytics(self, params, body, id):
        return self.ml.delete_analytics(id)

    def h_ml_start_analytics(self, params, body, id):
        return self.ml.start_analytics(id)

    def h_ml_stop_analytics(self, params, body, id):
        return self.ml.stop_analytics(id)

    def h_ml_explain_analytics(self, params, body):
        return self.ml.explain_analytics(_json_body(body))

    def h_ml_put_calendar(self, params, body, calendar_id):
        return self.ml.put_calendar(calendar_id, _json_body(body))

    def h_ml_get_calendars(self, params, body, calendar_id=None):
        return self.ml.get_calendars(calendar_id)

    def h_ml_del_calendar(self, params, body, calendar_id):
        return self.ml.delete_calendar(calendar_id)

    def h_ml_post_cal_events(self, params, body, calendar_id):
        return self.ml.post_calendar_events(calendar_id, _json_body(body))

    def h_ml_get_cal_events(self, params, body, calendar_id):
        return self.ml.get_calendar_events(calendar_id)

    def h_ml_put_filter(self, params, body, filter_id):
        return self.ml.put_filter(filter_id, _json_body(body))

    def h_ml_get_filters(self, params, body, filter_id=None):
        return self.ml.get_filters(filter_id)

    def h_ml_del_filter(self, params, body, filter_id):
        return self.ml.delete_filter(filter_id)

    def h_ml_info(self, params, body):
        return self.ml.info()

    def h_ml_upgrade_mode(self, params, body):
        return self.ml.set_upgrade_mode(
            params.get("enabled", "false") == "true")

    # ------------------------------------------------------------------
    # logstash config management + repositories metering (x-pack)
    # ------------------------------------------------------------------

    def register_stack_templates(self) -> int:
        """Built-in logs/metrics/synthetics data-stream templates
        (x-pack ``stack`` plugin — ``StackTemplateRegistry.java``).
        Off by default so conformance suites see a clean template
        registry; flipped on via the ``stack.templates.enabled``
        cluster setting or an explicit call."""
        components = {
            "data-streams-mappings": {"template": {"mappings": {
                "properties": {
                    "@timestamp": {"type": "date"},
                    "data_stream": {"properties": {
                        "dataset": {"type": "constant_keyword"},
                        "namespace": {"type": "constant_keyword"},
                        "type": {"type": "constant_keyword"}}}}}}},
            "logs-mappings": {"template": {"mappings": {"properties": {
                "message": {"type": "text"},
                "log": {"properties": {
                    "level": {"type": "keyword"}}}}}}},
            "logs-settings": {"template": {"settings": {
                "index": {"number_of_replicas": 1}}}},
            "metrics-mappings": {"template": {"mappings": {
                "properties": {"host": {"properties": {
                    "name": {"type": "keyword"}}}}}}},
            "metrics-settings": {"template": {"settings": {
                "index": {"number_of_replicas": 1}}}},
            "synthetics-mappings": {"template": {"mappings": {
                "properties": {"monitor": {"properties": {
                    "id": {"type": "keyword"}}}}}}},
            "synthetics-settings": {"template": {"settings": {
                "index": {"number_of_replicas": 1}}}},
        }
        n = 0
        for name, body in components.items():
            if name not in self.component_templates:
                self.component_templates[name] = dict(
                    body, _meta={"managed": True})
                n += 1
        for name, pattern, comps in (
                ("logs", "logs-*-*",
                 ["data-streams-mappings", "logs-mappings",
                  "logs-settings"]),
                ("metrics", "metrics-*-*",
                 ["data-streams-mappings", "metrics-mappings",
                  "metrics-settings"]),
                ("synthetics", "synthetics-*-*",
                 ["data-streams-mappings", "synthetics-mappings",
                  "synthetics-settings"])):
            if name not in self.templates:
                self.templates[name] = {
                    "index_patterns": [pattern],
                    "composed_of": comps,
                    "data_stream": {},
                    "priority": 100,
                    "_meta": {"managed": True,
                              "description": f"default {name} template "
                              f"installed by x-pack"},
                    "version": 1}
                n += 1
        return n

    def h_logstash_put(self, params, body, id):
        """Centralized logstash pipeline configs (x-pack ``logstash``
        plugin — CRUD over the ``.logstash`` system index; an in-memory
        registry carries the same surface)."""
        doc = _json_body(body)
        if not doc.get("pipeline"):
            raise IllegalArgumentError("[pipeline] is required")
        created = id not in self._logstash_pipelines
        self._logstash_pipelines[id] = dict(doc, pipeline_id=id)
        return (201 if created else 200), {}

    def h_logstash_get(self, params, body, id=None):
        store = self._logstash_pipelines
        if id is None:
            return {k: v for k, v in sorted(store.items())}
        if id not in store:
            raise ResourceNotFoundError(
                f"logstash pipeline [{id}] not found")
        return {id: store[id]}

    def h_logstash_delete(self, params, body, id):
        store = self._logstash_pipelines
        if id not in store:
            raise ResourceNotFoundError(
                f"logstash pipeline [{id}] not found")
        del store[id]
        return {}

    def h_repositories_metering(self, params, body, node_id):
        """Per-repository blob operation counters
        (``RepositoriesMeteringAction``)."""
        repos = []
        for name, repo in sorted(self.snapshots.repositories.items()):
            m = getattr(repo, "metering", {})
            repos.append({
                "repository_name": name,
                "repository_type": "fs",
                "repository_location": {"location": repo.location},
                "request_counts": {
                    "PutObject": m.get("PutObject", 0),
                    "GetObject": m.get("GetObject", 0)}})
        return {"_nodes": {"total": 1, "successful": 1, "failed": 0},
                "cluster_name": self.cluster_name,
                "nodes": {self.node_id: repos}}

    # ------------------------------------------------------------------
    # searchable snapshots + frozen + autoscaling
    # (xpack/{searchable_snapshots,autoscaling}.py)
    # ------------------------------------------------------------------

    def h_mount_snapshot(self, params, body, repo, snap):
        from ..xpack import searchable_snapshots as ss
        return ss.mount(self.snapshots, repo, snap, _json_body(body),
                        storage=params.get("storage", "full_copy"))

    def h_searchable_snapshot_stats(self, params, body, index=None):
        from ..xpack import searchable_snapshots as ss
        return ss.stats(self.indices, index)

    def h_searchable_snapshot_clear_cache(self, params, body,
                                          index=None):
        from ..xpack import searchable_snapshots as ss
        return ss.clear_cache(self.indices, index)

    def h_freeze_index(self, params, body, index):
        """Freeze: memory-minimal read-only index searched through the
        throttled path (``FrozenIndices.java:40`` — engine swapped for
        one that loads per search; here the plane/request caches drop,
        which is where this build's per-index memory lives)."""
        for n in self.indices.resolve(index):
            svc = self.indices.get(n)
            svc.settings["index.frozen"] = "true"
            # remember whether a write block pre-existed (mounted
            # snapshot / user block) so unfreeze can restore it
            svc._pre_freeze_write_block = \
                str(svc.settings.get("index.blocks.write")) == "true"
            svc.settings["index.blocks.write"] = "true"
            from ..search.plane_route import ServingPlaneCache
            try:
                svc.plane_cache.release()
            except Exception:   # noqa: BLE001 — freeze must not throw
                pass
            svc.plane_cache = ServingPlaneCache()
            svc.request_cache.clear()
        return {"acknowledged": True, "shards_acknowledged": True}

    def h_unfreeze_index(self, params, body, index):
        for n in self.indices.resolve(index):
            svc = self.indices.get(n)
            svc.settings.pop("index.frozen", None)
            if not getattr(svc, "_pre_freeze_write_block", False):
                svc.settings.pop("index.blocks.write", None)
        return {"acknowledged": True, "shards_acknowledged": True}

    @property
    def autoscaling(self):
        if getattr(self, "_autoscaling_svc", None) is None:
            from ..xpack.autoscaling import AutoscalingService

            def store_bytes():
                total = 0
                for n in list(self.indices.indices):
                    try:
                        st = self.indices.get(n).stats(
                            with_field_bytes=False)
                        total += int(st["store"]["size_in_bytes"])
                    except Exception:   # noqa: BLE001 — index vanished
                        continue
                return total

            self._autoscaling_svc = AutoscalingService(store_bytes)
        return self._autoscaling_svc

    def h_autoscaling_put_policy(self, params, body, name):
        return self.autoscaling.put_policy(name, _json_body(body))

    def h_autoscaling_get_policy(self, params, body, name):
        return self.autoscaling.get_policy(name)

    def h_autoscaling_del_policy(self, params, body, name):
        return self.autoscaling.delete_policy(name)

    def h_autoscaling_capacity(self, params, body):
        return self.autoscaling.capacity()

    # ------------------------------------------------------------------
    # SLM (x-pack snapshot lifecycle — xpack/slm.py)
    # ------------------------------------------------------------------

    @property
    def slm(self):
        if getattr(self, "_slm_svc", None) is None:
            from ..xpack.slm import SlmService

            def create(repo, name, config):
                return self._create_snapshot_from_config(
                    repo, name, config)

            def list_snaps(repo):
                return [self._snapshot_info(m, repository=repo)
                        for m in self.snapshots.get(repo, "_all")]

            self._slm_svc = SlmService(
                create,
                lambda repo, name: self.snapshots.delete(repo, name),
                list_snaps)
        return self._slm_svc

    def h_slm_put_policy(self, params, body, policy_id):
        return self.slm.put_policy(policy_id, _json_body(body))

    def h_slm_get_policy(self, params, body, policy_id=None):
        return self.slm.get_policies(policy_id)

    def h_slm_del_policy(self, params, body, policy_id):
        return self.slm.delete_policy(policy_id)

    def h_slm_execute(self, params, body, policy_id):
        return self.slm.execute_policy(policy_id)

    def h_slm_retention(self, params, body):
        self.slm.execute_retention()
        return {"acknowledged": True}

    def h_slm_tick(self, params, body):
        """Injectable-clock scheduler seam, like ``/_ilm/_tick`` and
        ``/_watcher/_tick`` — the cluster tier (or an operator cron)
        drives scheduled policies through here."""
        now = int(params["now"]) if params.get("now") else None
        return {"executed": self.slm.tick(now)}

    def h_slm_stats(self, params, body):
        return self.slm.get_stats()

    def h_slm_status(self, params, body):
        return self.slm.status()

    def h_slm_start(self, params, body):
        return self.slm.start()

    def h_slm_stop(self, params, body):
        return self.slm.stop()

    # ------------------------------------------------------------------
    # license + /_xpack (xpack/license.py)
    # ------------------------------------------------------------------

    @property
    def license(self):
        if getattr(self, "_license_svc", None) is None:
            from ..xpack.license import LicenseService
            self._license_svc = LicenseService(self.node_id)
        return self._license_svc

    def h_get_license(self, params, body):
        return self.license.get_license()

    def h_put_license(self, params, body):
        return self.license.put_license(
            _json_body(body), params.get("acknowledge") == "true")

    def h_delete_license(self, params, body):
        return self.license.delete_license()

    def h_start_trial(self, params, body):
        return self.license.start_trial(
            params.get("acknowledge") == "true")

    def h_start_basic(self, params, body):
        return self.license.start_basic(
            params.get("acknowledge") == "true")

    def h_trial_status(self, params, body):
        return self.license.trial_status()

    def h_basic_status(self, params, body):
        return self.license.basic_status()

    def h_xpack_info(self, params, body):
        return self.license.xpack_info()

    def h_xpack_usage(self, params, body):
        """Per-feature usage counts (``XPackUsageAction``) — live
        numbers from each lazily-built service (zeroes before use)."""
        ml = getattr(self, "_ml_svc", None)
        transform = getattr(self, "_transform_svc", None)
        watcher = getattr(self, "_watcher_svc", None)
        slm = getattr(self, "_slm_svc", None)
        return {
            "security": {"available": True,
                         "enabled": self.security.enabled},
            "ml": {"available": True, "enabled": True,
                   "jobs": {"_all": {"count":
                            len(ml.jobs) if ml else 0}},
                   "data_frame_analytics_jobs": {
                       "_all": {"count":
                                len(ml.analytics) if ml else 0}},
                   "inference": {"trained_models": {
                       "_all": {"count": len(ml.models) if ml else 0}}}},
            "transform": {"available": True, "enabled": True},
            "watcher": {"available": True, "enabled": True,
                        "count": {"total":
                                  len(watcher.watches)
                                  if watcher else 0}},
            "slm": {"available": True, "enabled": True,
                    "policy_count": len(slm.policies) if slm else 0},
            "ilm": {"policy_count": len(self.ilm.policies)},
            "sql": {"available": True, "enabled": True},
            "eql": {"available": True, "enabled": True},
            "rollup": {"available": True, "enabled": True},
            "ccr": {"available": True, "enabled": True},
            "graph": {"available": True, "enabled": True},
            "enrich": {"available": True, "enabled": True},
            "monitoring": {"available": True, "enabled": True},
            "data_streams": {"available": True, "enabled": True},
            "voting_only": {"available": True, "enabled": True},
        }

    # ------------------------------------------------------------------
    # deprecation + monitoring (xpack/{deprecation,monitoring}.py)
    # ------------------------------------------------------------------

    def h_deprecations(self, params, body, index=None):
        from ..node.indices_service import _flatten_settings
        from ..xpack.deprecation import deprecation_info

        def indices_settings():
            names = self.indices.resolve(index or "_all")
            out = {}
            for n in names:
                try:
                    out[n] = _flatten_settings(
                        dict(self.indices.get(n).settings or {}))
                except Exception:   # noqa: BLE001 — index vanished
                    continue
            return out

        return deprecation_info(
            indices_settings,
            lambda: {},
            lambda: sorted(getattr(self, "_legacy_template_names",
                                   set())))

    @property
    def monitoring(self):
        if getattr(self, "_monitoring_svc", None) is None:
            from ..xpack.monitoring import MonitoringService

            def fetch(method, path):
                prev = getattr(self._internal_tls, "active", False)
                self._internal_tls.active = True
                try:
                    st, _ct, out = self.handle(method, path, "", b"")
                finally:
                    self._internal_tls.active = prev
                return json.loads(out)

            self._monitoring_svc = MonitoringService(
                fetch,
                lambda i, lines: self.internal_bulk(i, lines,
                                                    refresh=True),
                cluster_uuid=self.node_id)
        return self._monitoring_svc

    def h_monitoring_bulk(self, params, body):
        return self.monitoring.bulk(
            params.get("system_id", ""),
            params.get("interval", ""), body)

    def h_monitoring_collect(self, params, body):
        n = self.monitoring.collect()
        return {"collected": n}

    def h_monitoring_tick(self, params, body):
        now = int(params["now"]) if params.get("now") else None
        return {"collected": bool(self.monitoring.tick(now))}

    @property
    def enrich(self):
        if getattr(self, "_enrich_svc", None) is None:
            from ..xpack.enrich import EnrichService
            self._enrich_svc = EnrichService(
                lambda i, b: self.internal_search(i, b))
        return self._enrich_svc

    def h_put_enrich_policy(self, params, body, name):
        return self.enrich.put_policy(name, _json_body(body))

    def h_get_enrich_policy(self, params, body, name=None):
        return self.enrich.get_policy(name)

    def h_delete_enrich_policy(self, params, body, name):
        return self.enrich.delete_policy(name)

    def h_execute_enrich_policy(self, params, body, name):
        return self.enrich.execute_policy(name)

    def h_sql_translate(self, params, body):
        return self.sql.translate(_json_body(body))

    def h_sql_close(self, params, body):
        payload = _json_body(body)
        found = self.sql.close_cursor(payload.get("cursor", ""))
        return {"succeeded": found}

    def h_create_data_stream(self, params, body, name):
        return self.datastreams.create(name)

    def h_get_data_streams(self, params, body, name=None):
        return self.datastreams.get(name)

    def h_delete_data_stream(self, params, body, name):
        return self.datastreams.delete(name)

    def h_put_ilm_policy(self, params, body, name):
        return self.ilm.put_policy(name, _json_body(body))

    def h_get_ilm_policy(self, params, body, name=None):
        return self.ilm.get_policy(name)

    def h_delete_ilm_policy(self, params, body, name):
        return self.ilm.delete_policy(name)

    def h_ilm_explain(self, params, body, index):
        return {"indices": {index: self.ilm.explain(index)}}

    def h_ilm_tick(self, params, body):
        """Test/ops hook: one ILM evaluation round, optionally at a
        caller-provided clock (?now_ms=) — the reference schedules the
        same evaluation off indices.lifecycle.poll_interval."""
        now = params.get("now_ms")
        return self.ilm.tick(int(now) if now else None)

    def close(self) -> None:
        """Release external resources (remote-cluster connections)."""
        self.remotes.close()

    def h_remote_info(self, params, body):
        """GET /_remote/info — configured remote-cluster connections
        (``RestRemoteClusterInfoAction``; connections dial lazily, so
        ``connected`` reflects configuration here)."""
        return {alias: {
            "connected": True, "mode": "proxy",
            "proxy_address": f"{host}:{port}",
            "seeds": [f"{host}:{port}"],
            "num_proxy_sockets_connected": 1,
            "max_proxy_socket_connections": 1,
            "initial_connect_timeout": "30s",
            "skip_unavailable": False,
        } for alias, (host, port) in sorted(
            self.remotes.aliases().items())}

    def _ccs_search(self, params, body, local_parts, remote_parts):
        """Cross-cluster search (``TransportSearchAction`` +
        ``SearchResponseMerger``): each remote executes the FULL
        sub-search on its own cluster over ``rest:exec``; hits merge by
        score/sort here. Aggregations, scroll and PIT require
        single-cluster scope (documented divergence: the reference
        merges final agg trees; this engine's exact reduce runs on
        partials that don't cross the REST boundary)."""
        search_body = _json_body(body)
        if search_body.get("aggs") or search_body.get("aggregations") \
                or params.get("scroll") or search_body.get("pit"):
            raise IllegalArgumentError(
                "aggregations/scroll/pit are not supported on "
                "cross-cluster expressions by this engine")
        # URL size/from would re-page each sub-search (h_search applies
        # them over the body): page ONCE at this coordinator
        size = int(params.get("size", search_body.get("size", 10)))
        from_ = int(params.get("from", search_body.get("from", 0)))
        sub_params = {k: v for k, v in params.items()
                      if k not in ("size", "from")}
        sub_body = dict(search_body, size=size + from_)
        sub_body["from"] = 0
        raw = json.dumps(sub_body).encode()
        from urllib.parse import urlencode
        q = urlencode(sub_params)      # re-encode: values were decoded
        results: Dict[object, dict] = {}

        def run_local():
            out = self.h_search(dict(sub_params), raw,
                                ",".join(local_parts))
            if isinstance(out, tuple):
                out = out[1]
            results[None] = out if isinstance(out, dict) \
                else json.loads(out)

        def run_remote(alias, patterns):
            st, _ct, payload = self.remotes.client(alias).exec(
                "POST", f"/{','.join(patterns)}/_search", q, raw)
            doc = json.loads(payload)
            if st >= 400:
                raise ElasticsearchError(
                    f"remote cluster [{alias}] search failed: "
                    f"{(doc.get('error') or {}).get('reason')}")
            results[alias] = doc

        # the reference fans out per cluster concurrently — a slow remote
        # must cost max(latency), not sum
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(max_workers=1 + len(remote_parts),
                                thread_name_prefix="es-rest-remote"
                                ) as ex:
            futs = []
            if local_parts:
                futs.append(ex.submit(run_local))
            for alias, patterns in sorted(remote_parts.items()):
                futs.append(ex.submit(run_remote, alias, patterns))
            for f in futs:
                f.result()
        responses = [(a, results[a]) for a in
                     ([None] if local_parts else []) +
                     sorted(remote_parts)]
        merged_hits = []
        total = 0
        relation = "eq"
        max_score = None
        shards = {"total": 0, "successful": 0, "skipped": 0, "failed": 0}
        took = 0
        for ci, (alias, doc) in enumerate(responses):
            h = doc.get("hits") or {}
            t = h.get("total") or {}
            total += int(t.get("value", 0))
            if t.get("relation") == "gte":
                relation = "gte"
            ms = h.get("max_score")
            if ms is not None:
                max_score = ms if max_score is None else max(max_score,
                                                             ms)
            sh = doc.get("_shards") or {}
            for k in shards:
                shards[k] += int(sh.get(k, 0))
            took = max(took, int(doc.get("took", 0)))
            for hit in h.get("hits", []):
                if alias is not None:
                    hit = dict(hit, _index=f"{alias}:{hit['_index']}")
                merged_hits.append((ci, hit))

        clauses = None
        if search_body.get("sort"):
            from ..search.shard_search import normalize_sort
            clauses = normalize_sort(search_body["sort"])

        def sort_key(entry):
            ci, hit = entry
            sv = hit.get("sort")
            if clauses and sv:
                # the same direction-aware comparator every merge tier
                # uses (dist_query.merge_sort_key)
                from ..search.dist_query import merge_sort_key
                return (0, merge_sort_key(clauses, sv), ci)
            sc = hit.get("_score")
            return (1, -(sc if sc is not None else float("-inf")), ci)

        try:
            merged_hits = sorted(merged_hits, key=sort_key)
        except TypeError:
            pass    # cross-cluster sort-type mismatch: keep the per-
            #         cluster order intact (sorted() left it untouched)
        page = [h for _ci, h in merged_hits[from_: from_ + size]]
        return {
            "took": took, "timed_out": False, "num_reduce_phases": 1,
            "_shards": shards,
            "_clusters": {"total": len(responses),
                          "successful": len(responses), "skipped": 0},
            "hits": {"total": {"value": total, "relation": relation},
                     "max_score": max_score, "hits": page},
        }

    def _node_id_matches(self, node_id: Optional[str]) -> bool:
        """Does a ``/_nodes/{node_id}/...`` filter select THIS node?
        Comma lists, ``_all``/``_local`` and id/name wildcards, per the
        reference's node-id resolution."""
        if node_id is None:
            return True
        import fnmatch
        for part in str(node_id).split(","):
            part = part.strip()
            if part in ("", "_all", "_local") or \
                    fnmatch.fnmatchcase(self.node_id, part) or \
                    fnmatch.fnmatchcase(self.node_name, part):
                return True
        return False

    def h_hot_threads(self, params, body, node_id=None):
        """GET /_nodes/hot_threads (monitor/jvm/HotThreads.java:41) —
        thread stack sampling, text response. A ``{node_id}`` filter
        that does not select this node samples nothing (the cluster
        front fans the sampler out per selected node)."""
        from ..utils.hot_threads import hot_threads
        from ..common.settings import parse_time_millis
        if not self._node_id_matches(node_id):
            return 200, "text/plain; charset=UTF-8", ""
        text = hot_threads(
            threads=int(params.get("threads", 3)),
            interval_ms=parse_time_millis(
                params.get("interval", "500ms")),
            snapshots=int(params.get("snapshots", 10)),
            ignore_idle=params.get("ignore_idle_threads", "true")
            != "false",
            node_name=self.node_name, node_id=self.node_id)
        return 200, "text/plain; charset=UTF-8", text

    @property
    def keystore_path(self) -> str:
        from ..common.keystore import Keystore
        return os.path.join(self.indices.data_path, Keystore.FILENAME)

    def h_reload_secure_settings(self, params, body, node_id=None):
        """POST /_nodes/reload_secure_settings (reference:
        ``NodesReloadSecureSettingsAction`` re-reading the keystore with
        the client-supplied password — KeyStoreWrapper.java:83)."""
        from ..common.keystore import Keystore, KeystoreError
        b = _json_body(body) if body else {}
        entry: Dict[str, Any] = {"name": self.node_name}
        pw = b.get("secure_settings_password") or ""
        if not os.path.exists(self.keystore_path):
            # nodes auto-create an empty-password keystore (the 7.x
            # default) — a non-empty supplied password then mismatches
            Keystore(self.keystore_path, "").save()
        try:
            ks = Keystore.load(self.keystore_path, pw)
            #: secure settings live beside (not inside) normal settings;
            #: consumers read them via this map (repo credentials,
            #: remote-cluster secrets)
            self.secure_settings = dict(ks.entries)
        except KeystoreError as e:
            entry["reload_exception"] = {
                "type": "security_exception", "reason": str(e)}
        return {"cluster_name": self.cluster_name,
                "_nodes": {"total": 1, "successful": 1, "failed": 0},
                "nodes": {self.node_id: entry}}

    #: blocks settable through the add-block API (IndexMetadata.APIBlock)
    _API_BLOCKS = {"metadata": "index.blocks.metadata",
                   "read": "index.blocks.read",
                   "read_only": "index.blocks.read_only",
                   "write": "index.blocks.write"}

    def h_add_block(self, params, body, index, block):
        setting = self._API_BLOCKS.get(block)
        if setting is None:
            raise IllegalArgumentError(f"unknown block type [{block}]")
        names = self.indices.resolve(index, allow_aliases=False)
        for n in names:
            self.indices.indices[n].settings[setting] = "true"
        return {"acknowledged": True, "shards_acknowledged": True,
                "indices": [{"name": n, "blocked": True} for n in names]}

    def h_flush(self, params, body, index=None):
        if params.get("force") in ("true", "") and \
                params.get("wait_if_ongoing") == "false":
            raise ActionRequestValidationError(
                "Validation Failed: 1: wait_if_ongoing must be true for "
                "a force flush;")
        names = self.indices.resolve(index)
        for n in names:
            self.indices.indices[n].flush()
        return {"_shards": {"total": len(names), "successful": len(names),
                            "failed": 0}}

    def h_forcemerge(self, params, body, index):
        if params.get("only_expunge_deletes") in ("true", "") and \
                params.get("max_num_segments") is not None:
            raise ActionRequestValidationError(
                "Validation Failed: 1: cannot set only_expunge_deletes "
                "and max_num_segments at the same time, those two "
                "parameters are mutually exclusive;")
        for n in self.indices.resolve(index):
            self.indices.indices[n].force_merge()
        return {"_shards": {"total": 1, "successful": 1, "failed": 0}}

    #: valid stats metric names (reference: CommonStatsFlags.Flag); note
    #: the API metric "merge" serializes as section "merges"
    STATS_METRICS = ("docs", "store", "indexing", "get", "search", "merge",
                     "refresh", "flush", "warmer", "query_cache",
                     "fielddata", "completion", "segments", "translog",
                     "suggest", "request_cache", "recovery", "bulk",
                     "plane_serving")
    _METRIC_SECTION = {"merge": "merges", "suggest": "search"}
    STATS_PARAMS = {"level", "types", "completion_fields",
                    "fielddata_fields", "fields", "groups",
                    "include_segment_file_sizes",
                    "include_unloaded_segments", "expand_wildcards",
                    "forbid_closed_indices", "ignore_unavailable",
                    "allow_no_indices"}

    @staticmethod
    def _check_params(params: dict, allowed: set, uri: str) -> None:
        common = {"pretty", "human", "error_trace", "filter_path", "format",
                  "master_timeout", "timeout", "rest_total_hits_as_int"}
        for p in params:
            if p not in allowed and p not in common:
                raise IllegalArgumentError(
                    f"request [{uri}] contains unrecognized parameter: "
                    f"[{p}]")

    @staticmethod
    def _check_metrics(metric: str, valid, uri: str) -> set:
        import difflib
        wanted = set()
        for m in metric.split(","):
            m = m.strip()
            if m in ("_all", ""):
                return set(valid)
            if m not in valid:
                hint = difflib.get_close_matches(m, list(valid), n=3)
                suffix = f" -> did you mean [{hint[0]}]?" if len(hint) == 1 \
                    else (f" -> did you mean any of {sorted(hint)}?"
                          if hint else "")
                raise IllegalArgumentError(
                    f"request [{uri}] contains unrecognized metric: "
                    f"[{m}]{suffix}")
            wanted.add(m)
        return wanted

    @staticmethod
    def _match_fields(patterns: str, candidates) -> List[str]:
        import fnmatch
        pats = [p.strip() for p in str(patterns).split(",") if p.strip()]
        out = []
        for c in candidates:
            if any(fnmatch.fnmatchcase(c, p) for p in pats):
                out.append(c)
        return out

    def h_stats(self, params, body, index=None, metric=None):
        self._check_params(params, self.STATS_PARAMS,
                           "/_stats" if index is None else f"/{index}/_stats")
        names = self.indices.resolve(index)
        metrics = None
        if metric and metric != "_all":
            metrics = self._check_metrics(
                metric, set(self.STATS_METRICS) | {"_all"},
                f"/_stats/{metric}")

        fields = params.get("fields")
        fd_fields = params.get("fielddata_fields") or fields
        comp_fields = params.get("completion_fields") or fields
        groups = params.get("groups")

        def decorate(svc, st: dict) -> dict:
            st = {k: (dict(v) if isinstance(v, dict) else v)
                  for k, v in st.items()}
            if svc.closed:
                # a closed index has no open engine: translog is drained
                # and segments are unloaded unless explicitly included
                st["translog"] = {k: 0 for k in st["translog"]}
                if params.get("include_unloaded_segments") not in \
                        ("true", ""):
                    st["segments"] = dict(st["segments"], count=0,
                                          memory_in_bytes=0)
            if params.get("include_segment_file_sizes") in ("true", ""):
                st["segments"] = dict(
                    st["segments"],
                    file_sizes=_segment_file_sizes(svc.shards))
            if fd_fields or comp_fields:
                fd, comp = svc.field_bytes()
                if fd_fields:
                    matched = self._match_fields(fd_fields, sorted(fd))
                    st["fielddata"]["fields"] = {
                        f: {"memory_size_in_bytes": fd[f]} for f in matched}
                if comp_fields:
                    matched = self._match_fields(comp_fields, sorted(comp))
                    st["completion"]["fields"] = {
                        f: {"size_in_bytes": comp[f]} for f in matched}
            if groups:
                gstats = svc.search_stats.get("groups", {})
                matched = self._match_fields(groups, sorted(gstats))
                st["search"] = dict(st["search"])
                st["search"]["groups"] = {
                    g: dict(gstats[g], query_time_in_millis=0,
                            query_current=0, fetch_time_in_millis=0,
                            fetch_current=0)
                    for g in matched}
            return st

        def trim(st: dict) -> dict:
            if metrics is None:
                return st
            keep = {self._METRIC_SECTION.get(m, m) for m in metrics}
            return {k: v for k, v in st.items() if k in keep}

        stats_of = {}
        for n in names:
            svc = self.indices.indices[n]
            stats_of[n] = trim(decorate(svc, svc.stats()))
        level = params.get("level", "indices")
        per_index = {}
        for n in names:
            entry = {"uuid": self.indices.indices[n].uuid,
                     "primaries": stats_of[n], "total": stats_of[n]}
            if level == "shards":
                entry["shards"] = self.indices.indices[n].shard_stats(
                    self.node_id)
            per_index[n] = entry
        agg: Dict[str, Any] = {}
        for n in names:
            _merge_numeric_tree(agg, stats_of[n])
        out = {"_shards": {"total": sum(
            self.indices.indices[n].num_shards *
            (1 + self.indices.indices[n].num_replicas) for n in names),
            "successful": sum(self.indices.indices[n].num_shards
                              for n in names), "failed": 0},
            "_all": {"primaries": agg, "total": agg}}
        if level != "cluster":
            out["indices"] = per_index
        return out

    # ------------------------------------------------------------------
    # aliases / templates
    # ------------------------------------------------------------------

    @staticmethod
    def _alias_spec(spec: dict) -> dict:
        """Normalize an alias definition: plain ``routing`` expands to
        index_routing + search_routing (AliasAction semantics)."""
        out = {}
        if "filter" in spec:
            out["filter"] = spec["filter"]
        routing = spec.get("routing")
        if routing is not None:
            out["index_routing"] = str(routing)
            out["search_routing"] = str(routing)
        if spec.get("index_routing") is not None:
            out["index_routing"] = str(spec["index_routing"])
        if spec.get("search_routing") is not None:
            out["search_routing"] = str(spec["search_routing"])
        if "is_write_index" in spec:
            out["is_write_index"] = bool(spec["is_write_index"])
        if "is_hidden" in spec:
            out["is_hidden"] = bool(spec["is_hidden"])
        return out

    def h_update_aliases(self, params, body):
        b = _json_body(body)
        for action in b.get("actions", []):
            (verb, spec), = action.items()
            if verb != "remove" and "must_exist" in spec:
                raise IllegalArgumentError(
                    "[must_exist] is unsupported for "
                    f"[{verb.upper().replace('_', ' ')}]")
            if verb == "remove_index":
                target = spec.get("index") or ",".join(
                    spec.get("indices", []))
                if not target:
                    raise IllegalArgumentError(
                        "[remove_index] requires an index")
                self.indices.delete_index(target)
                continue
            idx_names = self.indices.resolve(
                spec.get("index") or ",".join(spec.get("indices", [])),
                allow_aliases=False)
            aliases = spec.get("aliases") or [spec.get("alias")]
            if isinstance(aliases, str):
                aliases = [aliases]
            for n in idx_names:
                svc = self.indices.indices[n]
                for a in aliases:
                    if verb == "add":
                        svc.aliases[a] = self._alias_spec(spec)
                    elif verb == "remove":
                        pass             # applied after validation below
                    else:
                        raise IllegalArgumentError(
                            f"unknown alias action [{verb}]")
            if verb == "remove":
                # must_exist validates across ALL targets BEFORE mutating
                # (atomic; the reference rejects when the alias exists on
                # none of the indices)
                if spec.get("must_exist", False) and not any(
                        a in self.indices.indices[n].aliases
                        for n in idx_names for a in aliases):
                    raise ResourceNotFoundError(
                        f"aliases [{','.join(aliases)}] missing")
                for n in idx_names:
                    for a in aliases:
                        self.indices.indices[n].aliases.pop(a, None)
        return {"acknowledged": True}

    def h_get_alias(self, params, body, index=None, name=None):
        """Alias name expressions support comma lists, wildcards and
        ``-`` exclusions; only CONCRETE names that match nothing 404
        (reference: ``TransportGetAliasesAction.java`` postProcess)."""
        import fnmatch
        all_alias_names = set(self.indices.all_aliases())
        concrete_missing: List[str] = []
        if name is None or name in ("_all", "*"):
            selected = set(all_alias_names)
        else:
            parts = [p.strip() for p in name.split(",") if p.strip()]
            selected = set()
            # a dash expression is an EXCLUSION only once a wildcard
            # expression has been seen; before that it is a literal
            # (missing) alias name — RestGetAliasesAction semantics
            seen_wildcard = False
            for p in parts:
                is_pat = "*" in p or "?" in p
                if p.startswith("-") and (seen_wildcard or is_pat):
                    pat = p[1:]
                    selected -= {a for a in selected
                                 if fnmatch.fnmatchcase(a, pat)}
                    seen_wildcard = seen_wildcard or is_pat
                elif p in ("_all", "*"):
                    selected |= all_alias_names
                    seen_wildcard = True
                elif is_pat:
                    selected |= {a for a in all_alias_names
                                 if fnmatch.fnmatchcase(a, p)}
                    seen_wildcard = True
                elif p in all_alias_names:
                    selected.add(p)
                else:
                    concrete_missing.append(p)
        ew = params.get("expand_wildcards", "all")
        out: Dict[str, dict] = {}
        for n in self.indices.resolve(index):
            svc = self.indices.indices[n]
            if svc.closed and "closed" not in ew and "all" not in ew:
                continue
            aliases = {a: s for a, s in svc.aliases.items()
                       if a in selected}
            if aliases or name is None:
                out[n] = {"aliases": aliases}
        if concrete_missing:
            noun = "aliases" if len(concrete_missing) > 1 else "alias"
            payload = {"error": f"{noun} "
                       f"[{','.join(sorted(concrete_missing))}] missing",
                       "status": 404}
            payload.update(out)
            return 404, payload
        return out

    def h_put_alias(self, params, body, index, name):
        from ..common.errors import InvalidAliasNameError
        from ..node.indices_service import validate_index_name
        try:
            validate_index_name(name)
        except ElasticsearchError as e:
            raise InvalidAliasNameError(
                f"Invalid alias name [{name}]: {e}")
        if name in self.indices.indices:
            raise InvalidAliasNameError(
                f"Invalid alias name [{name}]: an index or data stream "
                f"exists with the same name as the alias")
        spec = self._alias_spec(_json_body(body)) if body else {}
        for n in self.indices.resolve(index, allow_aliases=False):
            self.indices.indices[n].aliases[name] = spec
        return {"acknowledged": True}

    def h_delete_alias(self, params, body, index, name):
        """DELETE /{index}/_alias/{name}: name may be a CSV of alias
        names/wildcards (* and _all remove every alias); 404 when
        nothing matched (``TransportIndicesAliasesAction``)."""
        import fnmatch
        names = self.indices.resolve(index, allow_aliases=False)
        removed_any = False
        for n in names:
            svc = self.indices.indices[n]
            for pat in name.split(","):
                if pat in ("_all", "*"):
                    removed_any = removed_any or bool(svc.aliases)
                    svc.aliases.clear()
                elif any(c in pat for c in "*?"):
                    hit = [a for a in svc.aliases
                           if fnmatch.fnmatchcase(a, pat)]
                    for a in hit:
                        del svc.aliases[a]
                    removed_any = removed_any or bool(hit)
                elif pat in svc.aliases:
                    del svc.aliases[pat]
                    removed_any = True
        if not removed_any:
            e = ElasticsearchError(f"aliases [{name}] missing")
            e.status = 404
            e.error_type = "aliases_not_found_exception"
            raise e
        return {"acknowledged": True}

    def h_put_template_legacy(self, params, body, name):
        b = _json_body(body)
        if "index_patterns" not in b:
            raise IllegalArgumentError("index patterns are missing")
        if params.get("create") in ("true", "") and name in self.templates:
            raise IllegalArgumentError(
                f"index_template [{name}] already exists")
        from ..xpack.deprecation import warn
        warn("legacy_template",
             "Legacy index templates are deprecated in favor of "
             "composable templates.")
        result = self.h_put_template(params, body, name)
        if not hasattr(self, "_legacy_template_names"):
            self._legacy_template_names = set()
        self._legacy_template_names.add(name)
        return result

    def h_get_template_legacy(self, params, body, name=None):
        import fnmatch
        flat = params.get("flat_settings") in ("true", "")
        if name is None:
            return {n: self._legacy_template_view(t, flat)
                    for n, t in self.templates.items()}
        pats = [p_.strip() for p_ in name.split(",") if p_.strip()]
        matched = {n: self._legacy_template_view(t, flat)
                   for n, t in self.templates.items()
                   if any(fnmatch.fnmatchcase(n, p_) or n == p_
                          for p_ in pats)}
        if not matched and not any(c in name for c in "*,"):
            return 404, {"error": f"index template matching [{name}] not "
                                  f"found", "status": 404}
        return matched

    def _legacy_template_view(self, t: dict, flat_form: bool = False
                              ) -> dict:
        from ..node.indices_service import _flatten_settings
        raw = _flatten_settings(dict(t.get("settings") or {}))
        flat = {(k if k.startswith("index.") else f"index.{k}"): str(v)
                for k, v in raw.items()}
        out = {"order": t.get("order", 0),
               "index_patterns": t.get("index_patterns", []),
               "settings": flat if flat_form else self._nest_flat(flat),
               "mappings": t.get("mappings", {}),
               "aliases": {a: self._alias_spec(spec or {})
                           for a, spec in (t.get("aliases") or {}).items()}}
        if "version" in t:
            out["version"] = t["version"]
        return out

    @staticmethod
    def _patterns_of(tpl) -> List[str]:
        pats = tpl.get("index_patterns") or []
        return [pats] if isinstance(pats, str) else list(pats)

    def _compose_template_view(self, tpl: dict) -> dict:
        """Composable template (+ composed_of component layers) →
        resolved {settings, mappings, aliases} view (reference:
        ``TransportSimulateIndexTemplateAction.resolveTemplate``)."""
        def _deep_props(dst, src):
            for k, v in (src or {}).items():
                if isinstance(v, dict) and isinstance(dst.get(k), dict):
                    _deep_props(dst[k], v)
                else:
                    dst[k] = v

        settings: dict = {}
        mappings: dict = {}
        aliases: dict = {}
        layers = [(self.component_templates.get(c) or {}).get(
            "template") or {} for c in tpl.get("composed_of", [])]
        layers.append(tpl.get("template") or {})
        for layer in layers:
            raw = layer.get("settings") or {}
            flat = dict(raw.get("index", raw)) \
                if "index" in raw and isinstance(
                    raw.get("index"), dict) else dict(raw)
            for k, v in flat.items():
                k = k[6:] if k.startswith("index.") else k
                sval = ("true" if v is True else
                        "false" if v is False else str(v))
                # dotted keys nest (the response renders the settings
                # tree, not flat keys)
                node = settings
                parts = k.split(".")
                for part in parts[:-1]:
                    node = node.setdefault(part, {})
                node[parts[-1]] = sval
            props = (layer.get("mappings") or {}).get("properties") or {}
            if props:
                _deep_props(mappings.setdefault("properties", {}), props)
            for k, v in (layer.get("mappings") or {}).items():
                if k != "properties":
                    mappings[k] = v
            aliases.update(layer.get("aliases") or {})
        return {"settings": {"index": settings},
                "mappings": mappings, "aliases": aliases}

    @staticmethod
    def _is_composable(tpl: dict) -> bool:
        return any(k in tpl for k in ("template", "composed_of",
                                      "priority"))

    def h_simulate_index_template(self, params, body, name):
        """POST /_index_template/_simulate_index/{index}: resolve the
        template that WOULD apply to a new index of that name."""
        import fnmatch
        body_tpl = _json_body(body) if body else None
        candidates = []                # (priority, tname, tpl)
        for tname, t in self.templates.items():
            if self._is_composable(t) and any(
                    fnmatch.fnmatchcase(name, p)
                    for p in self._patterns_of(t)):
                candidates.append((int(t.get("priority", 0)), tname, t))
        if body_tpl:
            candidates.append((int(body_tpl.get("priority", 0)),
                               None, body_tpl))
        if not candidates:
            return None                # serialized as a JSON null body
        _, win_name, winner = max(candidates, key=lambda c: c[0])
        overlapping = sorted(
            ({"name": tname, "index_patterns": self._patterns_of(t)}
             for tname, t in self.templates.items()
             if tname != win_name and any(
                 fnmatch.fnmatchcase(name, p)
                 for p in self._patterns_of(t))),
            key=lambda e: e["name"])
        return {"template": self._compose_template_view(winner),
                "overlapping": overlapping}

    def h_simulate_template(self, params, body, name=None):
        """POST /_index_template/_simulate[/{name}]: resolve a stored or
        request-provided template and report pattern overlaps."""
        import fnmatch
        tpl = _json_body(body) if body else None
        if tpl is None:
            if name is None or name not in self.templates:
                raise IllegalArgumentError(
                    f"unable to simulate template [{name}] that does "
                    f"not exist")
            tpl = self.templates[name]
        pats = self._patterns_of(tpl)

        def _overlaps(other) -> bool:
            return any(fnmatch.fnmatchcase(p2, p1)
                       or fnmatch.fnmatchcase(p1, p2)
                       for p1 in pats for p2 in self._patterns_of(other))

        overlapping = sorted(
            ({"name": tname, "index_patterns": self._patterns_of(t)}
             for tname, t in self.templates.items()
             if tname != name and _overlaps(t)),
            key=lambda e: e["name"])
        return {"template": self._compose_template_view(tpl),
                "overlapping": overlapping}

    def h_put_template(self, params, body, name):
        b = _json_body(body)
        if params.get("create") in ("true", "") and name in self.templates:
            raise IllegalArgumentError(
                f"index template [{name}] already exists")
        if "index_patterns" not in b:
            raise IllegalArgumentError(
                "index template requires [index_patterns]")
        if isinstance(b["index_patterns"], str):
            b["index_patterns"] = [b["index_patterns"]]
        self.templates[name] = b
        return {"acknowledged": True}

    def _composable_template_view(self, t: dict) -> dict:
        out = dict(t)
        tpl = t.get("template")
        if isinstance(tpl, dict):
            new_tpl = dict(tpl)
            if tpl.get("settings"):
                from ..node.indices_service import _flatten_settings
                flat = {(k if k.startswith("index.")
                         else f"index.{k}"): str(v)
                        for k, v in _flatten_settings(
                            dict(tpl["settings"])).items()}
                new_tpl["settings"] = self._nest_flat(flat)
            if tpl.get("aliases"):
                new_tpl["aliases"] = {
                    a: self._alias_spec(spec or {})
                    for a, spec in tpl["aliases"].items()}
            out = dict(t, template=new_tpl)
        return out

    def h_get_template(self, params, body, name=None):
        if name is None:
            return {"index_templates": [
                {"name": n,
                 "index_template": self._composable_template_view(t)}
                for n, t in self.templates.items()]}
        import fnmatch
        matched = {n: t for n, t in self.templates.items()
                   if fnmatch.fnmatchcase(n, name)}
        if not matched:
            return 404, {"error": f"index template matching [{name}] not "
                                  f"found", "status": 404}
        return {"index_templates": [
            {"name": n, "index_template": self._composable_template_view(t)}
            for n, t in matched.items()]}

    def h_delete_template(self, params, body, name):
        if name not in self.templates:
            return 404, {"error": f"index template [{name}] missing",
                         "status": 404}
        del self.templates[name]
        getattr(self, "_legacy_template_names", set()).discard(name)
        return {"acknowledged": True}

    # ------------------------------------------------------------------
    # documents
    # ------------------------------------------------------------------

    def _doc_response(self, index: str, result, op: str) -> dict:
        return {"_index": index, "_id": result.doc_id,
                "_version": result.version,
                "result": op,
                "_shards": {"total": 1, "successful": 1, "failed": 0},
                "_seq_no": result.seq_no, "_primary_term": 1}

    def h_index_doc(self, params, body, index, id):
        if id == "":
            raise IllegalArgumentError("if _id is specified it must not "
                                       "be empty")
        if len(str(id).encode()) > 512:
            raise IllegalArgumentError(
                f"id [{id}] is too long, must be no longer than 512 bytes "
                f"but was: {len(str(id).encode())}")
        if params.get("require_alias") in ("true", "") and \
                index not in self.indices.all_aliases():
            raise _require_alias_error(index)
        svc = self._get_or_autocreate(index)
        index = svc.name        # data stream/alias writes report the
        op_type = params.get("op_type", "index")    # concrete index
        ext_version = None
        if params.get("version_type") in ("external", "external_gte"):
            ext_version = int(params.get("version", 0))
            if op_type == "create":
                from ..common.errors import ActionRequestValidationError
                raise ActionRequestValidationError(
                    "Validation Failed: 1: create operations only "
                    "support internal versioning. use index instead;")
        ingested = self._run_ingest(svc, index, id, _json_body(body),
                                    params.get("routing"),
                                    params.get("pipeline"))
        if ingested is None:                 # dropped by a drop processor
            return {"_index": index, "_id": id, "_version": -3,
                    "result": "noop", "_shards": {"total": 0,
                                                  "successful": 0,
                                                  "failed": 0}}
        source, new_index, new_id, routing = ingested
        if new_index != index:               # pipeline rerouted the doc
            svc = self._get_or_autocreate(new_index)
            index = new_index
        id = new_id or id
        if ext_version is not None:
            # external versioning: validate BEFORE applying the write
            gte = params.get("version_type") == "external_gte"
            shard = svc.shard_for_doc(id, routing)
            if not hasattr(shard, "external_versions"):
                shard.external_versions = {}
            cur = shard.external_versions.get(id)
            if cur is not None and (
                    ext_version < cur or
                    (not gte and ext_version == cur)):
                raise VersionConflictError(
                    f"[{id}]: version conflict, current version [{cur}] "
                    f"is higher or equal to the one provided "
                    f"[{ext_version}]")
        r = svc.index_doc(id, source,
                          routing=routing, op_type=op_type,
                          if_seq_no=_int_or_none(params.get("if_seq_no")),
                          if_primary_term=_int_or_none(
                              params.get("if_primary_term")))
        if ext_version is not None:
            shard.external_versions[id] = ext_version
            r = type(r)(**{**r.__dict__, "version": ext_version}) \
                if hasattr(r, "__dict__") else r
        if params.get("refresh") in ("true", "wait_for", ""):
            svc.refresh_shard(id, routing)
            resp = self._doc_response(index, r,
                                      "created" if r.created else "updated")
            # wait_for waits for a scheduled refresh rather than forcing
            # one (synchronous here, but the reported flag keeps the
            # reference's contract)
            resp["forced_refresh"] = params["refresh"] != "wait_for"
            return (201 if r.created else 200), resp
        return (201 if r.created else 200), self._doc_response(
            index, r, "created" if r.created else "updated")

    def h_index_doc_auto(self, params, body, index):
        return self.h_index_doc(params, body, index, uuid.uuid4().hex[:20])

    def h_create_doc(self, params, body, index, id):
        params = dict(params, op_type="create")
        return self.h_index_doc(params, body, index, id)

    def _get_source_spec(self, params):
        spec = params.get("_source")
        if spec in ("true", "false", ""):
            spec = spec != "false"
        elif spec is not None:
            spec = spec.split(",")
        if "_source_includes" in params or "_source_excludes" in params:
            spec = {k: params[p].split(",")
                    for k, p in (("includes", "_source_includes"),
                                 ("excludes", "_source_excludes"))
                    if p in params}
        return spec

    def _doc_visible(self, svc, doc_id, realtime: bool,
                     routing=None) -> bool:
        if realtime:
            return True
        if svc.cluster_hooks is not None:
            vis = svc.cluster_hooks.doc_visible(
                svc.name, svc.shard_id_for(doc_id, routing), doc_id)
            if vis is not None:
                return vis
        return any(seg.find_doc(doc_id) is not None
                   for sh in svc.shards
                   for seg in sh.searchable_segments())

    def h_get_doc(self, params, body, index, id):
        svc = self.indices.get(index)
        index = svc.name            # alias → concrete name in responses
        if params.get("refresh") in ("true", ""):
            svc.refresh()
        r = svc.get_doc(id, routing=params.get("routing"))
        realtime = params.get("realtime") not in ("false",)
        visible, fls = self._doc_read_guard(index, id)
        if not r.found or not visible or not self._doc_visible(
                svc, id, realtime, params.get("routing")):
            return 404, {"_index": index, "_id": id, "found": False}
        if params.get("version"):
            want = int(params["version"])
            if want != r.version:
                raise VersionConflictError(
                    f"[{id}]: version conflict, current version "
                    f"[{r.version}] is different than the one provided "
                    f"[{want}]")
        out = {"_index": index, "_id": id, "_version": r.version,
               "_seq_no": r.seq_no, "_primary_term": 1, "found": True}
        src_spec = self._get_source_spec(params)
        stored = params.get("stored_fields")
        if stored:
            from ..search.fetch import fetch_fields
            names = [f for f in stored.split(",") if f != "_source"]
            flds = fetch_fields(svc.mapper, r.source, names)
            if flds:
                out["fields"] = flds
            if src_spec is None:
                src_spec = "_source" in stored.split(",")
        if src_spec is not False:
            from ..search.fetch import filter_source
            out["_source"] = filter_source(
                r.source, True if src_spec is None else src_spec)
        if getattr(r, "routing", None) is not None:
            out["_routing"] = r.routing
        return self._fls_trim_doc(out, fls)

    def h_get_source(self, params, body, index, id):
        svc = self.indices.get(index)
        if not svc.mapper.source_enabled:
            return 404, {"error": f"document [{id}] missing: _source is "
                                  f"disabled", "status": 404}
        if params.get("refresh") in ("true", ""):
            svc.refresh()
        r = svc.get_doc(id, routing=params.get("routing"))
        realtime = params.get("realtime") not in ("false",)
        visible, fls = self._doc_read_guard(index, id)
        if not r.found or not visible or not self._doc_visible(
                svc, id, realtime, params.get("routing")):
            return 404, {"error": f"document [{id}] missing", "status": 404}
        src_spec = self._get_source_spec(params)
        from ..search.fetch import filter_source
        out_src = filter_source(r.source,
                                True if src_spec is None else src_spec)
        if fls is not None and isinstance(out_src, dict):
            import fnmatch
            out_src = {k: v for k, v in out_src.items()
                       if any(fnmatch.fnmatchcase(k, g) for g in fls)}
        return out_src

    def h_delete_doc(self, params, body, index, id):
        svc = self.indices.get(index)
        if params.get("version_type") in ("external", "external_gte"):
            want = int(params.get("version", 0))
            gte = params.get("version_type") == "external_gte"
            shard = svc.shard_for_doc(id, params.get("routing"))
            cur = getattr(shard, "external_versions", {}).get(id)
            if cur is not None and (want < cur or
                                    (not gte and want == cur)):
                raise VersionConflictError(
                    f"[{id}]: version conflict, current version [{cur}] "
                    f"is higher or equal to the one provided [{want}]")
            if not hasattr(shard, "external_versions"):
                shard.external_versions = {}
            shard.external_versions[id] = want
            r = svc.delete_doc(id, routing=params.get("routing"))
            if params.get("refresh") in ("true", "wait_for", ""):
                svc.refresh_shard(id, params.get("routing"))
            resp = self._doc_response(index, r,
                                      "deleted" if r.found
                                      else "not_found")
            resp["_version"] = want
            if not r.found:
                return 404, resp
            return resp
        r = svc.delete_doc(id, routing=params.get("routing"),
                           if_seq_no=_int_or_none(params.get("if_seq_no")),
                           if_primary_term=_int_or_none(
                               params.get("if_primary_term")))
        if params.get("refresh") in ("true", "wait_for", ""):
            svc.refresh_shard(id, params.get("routing"))
        if not r.found:
            return 404, self._doc_response(index, r, "not_found")
        return self._doc_response(index, r, "deleted")

    #: UpdateRequest body fields (unknown keys get did-you-mean 400s)
    UPDATE_BODY_KEYS = {"doc", "script", "upsert", "doc_as_upsert",
                        "detect_noop", "scripted_upsert", "_source",
                        "if_seq_no", "if_primary_term"}

    def h_update_doc(self, params, body, index, id):
        import difflib
        b = _json_body(body)
        for k in b:
            if k not in self.UPDATE_BODY_KEYS:
                hint = difflib.get_close_matches(
                    k, sorted(self.UPDATE_BODY_KEYS), n=1)
                suffix = f" did you mean [{hint[0]}]?" if hint else ""
                raise IllegalArgumentError(
                    f"[UpdateRequest] unknown field [{k}]{suffix}")
        if params.get("require_alias") in ("true", "") and \
                index not in self.indices.all_aliases():
            raise _require_alias_error(index)
        svc = self._get_or_autocreate(index)
        if_seq_no = _int_or_none(params.get("if_seq_no",
                                            b.get("if_seq_no")))
        if_primary_term = _int_or_none(params.get("if_primary_term",
                                                  b.get("if_primary_term")))
        refresh = params.get("refresh") in ("true", "wait_for", "")

        def finish(status, resp, src_after=None):
            if refresh:
                svc.refresh()
                resp["forced_refresh"] = \
                    params.get("refresh") != "wait_for"
            src_spec = params.get("_source", b.get("_source"))
            if "_source_includes" in params or \
                    "_source_excludes" in params:
                src_spec = {k: params[p].split(",")
                            for k, p in (("includes", "_source_includes"),
                                         ("excludes", "_source_excludes"))
                            if p in params}
            if src_spec is not None and src_spec not in ("false", False):
                from ..search.fetch import filter_source
                if isinstance(src_spec, str) and src_spec not in (
                        "true", ""):
                    src_spec = src_spec.split(",")
                elif src_spec in ("true", "", True):
                    src_spec = True
                resp["get"] = {"found": True,
                               "_source": filter_source(src_after or {},
                                                        src_spec)}
            return (status, resp) if status != 200 else resp

        existing = svc.get_doc(id, routing=params.get("routing"))
        if not existing.found:
            # a CAS update on a missing doc is DocumentMissing (404), not
            # a version conflict — UpdateHelper checks existence first
            if "upsert" in b:
                src = b["upsert"]
                if b.get("scripted_upsert") and "script" in b:
                    script = b["script"]
                    source = script.get("source") if isinstance(
                        script, dict) else script
                    src = _apply_update_script(
                        dict(src), source,
                        script.get("params", {}) if isinstance(
                            script, dict) else {})
                r = svc.index_doc(id, src, routing=params.get("routing"))
                return finish(201, self._doc_response(index, r, "created"),
                              src)
            if b.get("doc_as_upsert") and "doc" in b:
                r = svc.index_doc(id, b["doc"],
                                  routing=params.get("routing"))
                return finish(201, self._doc_response(index, r, "created"),
                              b["doc"])
            raise DocumentMissingError(f"[{id}]: document missing")
        if if_seq_no is not None and existing.seq_no != if_seq_no:
            raise VersionConflictError(
                f"[{id}]: version conflict, required seqNo [{if_seq_no}], "
                f"current [{existing.seq_no}]")
        if if_primary_term is not None and if_primary_term != 1:
            raise VersionConflictError(
                f"[{id}]: version conflict, required primary term "
                f"[{if_primary_term}]")
        if "doc" in b:
            merged = _deep_merge(dict(existing.source or {}), b["doc"])
            if b.get("detect_noop", True) and merged == existing.source:
                resp = {"_index": index, "_id": id,
                        "_version": existing.version, "result": "noop",
                        "_seq_no": existing.seq_no, "_primary_term": 1,
                        "_shards": {"total": 0, "successful": 0,
                                    "failed": 0}}
                return finish(200, resp, existing.source)
            r = svc.index_doc(id, merged, routing=params.get("routing"))
            return finish(200, self._doc_response(index, r, "updated"),
                          merged)
        if "script" in b:
            src = dict(existing.source or {})
            script = b["script"]
            if isinstance(script, dict):
                source = self._resolve_script_source(script)
                ctx_params = script.get("params", {})
            else:
                source, ctx_params = script, {}
            ctx_extra = {"op": "index", "_id": id, "_index": index}
            new_src = _apply_update_script(src, source, ctx_params,
                                           ctx_extra=ctx_extra)
            if ctx_extra.get("op") == "none":
                noop = {"_index": index, "_id": id,
                        "_version": existing.version, "result": "noop",
                        "_shards": {"total": 0, "successful": 0,
                                    "failed": 0},
                        "_seq_no": existing.seq_no, "_primary_term": 1}
                return finish(200, noop, src)
            if ctx_extra.get("op") == "delete":
                r = svc.delete_doc(id, routing=params.get("routing"))
                return finish(200,
                              self._doc_response(index, r, "deleted"),
                              None)
            r = svc.index_doc(id, new_src, routing=params.get("routing"))
            return finish(200, self._doc_response(index, r, "updated"),
                          new_src)
        raise IllegalArgumentError(
            "update requires [doc], [script], or [upsert]")

    def h_mget(self, params, body, index=None):
        b = _json_body(body)
        if "docs" in b:
            entries = b["docs"]
        elif "ids" in b:
            entries = [{"_id": i} for i in b.get("ids", [])]
        else:
            entries = None
        errors = []
        if not entries:
            errors.append("no documents to get")
        for i, e in enumerate(entries or []):
            if not isinstance(e, dict) or "_id" not in e:
                errors.append(f"id is missing for doc {i}")
            else:
                bad = [k for k in ("_type", "_routing", "_version",
                                   "_version_type", "_parent")
                       if k in e]
                if bad:
                    errors.append(
                        f"Action/metadata line [{i}] contains an unknown "
                        f"parameter [{bad[0]}]")
                if e.get("_index", index) is None:
                    errors.append(f"index is missing for doc {i}")
        if errors:
            from ..common.errors import ActionRequestValidationError
            raise ActionRequestValidationError(
                "Validation Failed: " + "; ".join(
                    f"{i + 1}: {m}" for i, m in enumerate(errors)) + ";")
        out = []
        from ..search.fetch import fetch_fields, filter_source
        req_src = self._get_source_spec(params)
        realtime = params.get("realtime") not in ("false",)
        if params.get("refresh") in ("true", ""):
            seen_idx = {e.get("_index", index) for e in entries
                        if isinstance(e, dict)}
            for ix in seen_idx:
                try:
                    self.indices.get(ix).refresh()
                except Exception:   # noqa: BLE001 — missing index
                    pass
        for e in entries:
            idx = e.get("_index", index)
            if idx is None:
                raise IllegalArgumentError("mget requires an index per doc")
            doc_id = str(e["_id"])
            routing = e.get("routing")
            routing = str(routing) if routing is not None else None
            try:
                resolved = self.indices.resolve(idx)
                if len(resolved) > 1:
                    out.append({"_index": idx, "_id": doc_id, "error": {
                        "root_cause": [{
                            "type": "illegal_argument_exception",
                            "reason": f"alias [{idx}] has more than one "
                                      f"index associated with it "
                                      f"[{', '.join(sorted(resolved))}], "
                                      f"can't execute a single index "
                                      f"op"}],
                        "type": "illegal_argument_exception",
                        "reason": f"alias [{idx}] has more than one index "
                                  f"associated with it "
                                  f"[{', '.join(sorted(resolved))}], "
                                  f"can't execute a single index op"}})
                    continue
                svc = self.indices.get(idx)
                r = svc.get_doc(doc_id, routing=routing)
            except IndexNotFoundError:
                out.append({"_index": idx, "_id": doc_id, "found": False})
                continue
            if r.found and not self._doc_visible(svc, doc_id, realtime,
                                                 routing):
                out.append({"_index": idx, "_id": doc_id, "found": False})
                continue
            if r.found:
                src_spec = e.get("_source", req_src)
                entry = {"_index": idx, "_id": doc_id,
                         "_version": r.version, "found": True}
                if routing is not None:
                    entry["_routing"] = routing
                stored = e.get("stored_fields",
                               params.get("stored_fields"))
                if stored:
                    if isinstance(stored, str):
                        stored = stored.split(",")
                    flds = fetch_fields(svc.mapper, r.source,
                                        [f for f in stored
                                         if f != "_source"])
                    if flds:
                        entry["fields"] = flds
                    if src_spec is None:
                        src_spec = "_source" in stored
                if src_spec is None:
                    src_spec = True
                filtered = filter_source(r.source, src_spec)
                if src_spec is not False:
                    entry["_source"] = filtered
                out.append(entry)
            else:
                out.append({"_index": idx, "_id": doc_id, "found": False})
        if self.security.enabled and self.enforce_security and \
                not getattr(self._internal_tls, "active", False):
            # per-doc DLS visibility + FLS trim, like the single get
            for d in out:
                if not d.get("found"):
                    continue
                visible, fls = self._doc_read_guard(d["_index"],
                                                    d["_id"])
                if not visible:
                    idx_, id_ = d["_index"], d["_id"]
                    d.clear()
                    d.update({"_index": idx_, "_id": id_,
                              "found": False})
                else:
                    self._fls_trim_doc(d, fls)
        return {"docs": out}

    def _get_or_autocreate(self, index: str) -> IndexService:
        wi = self.datastreams.write_index(index)
        if wi is not None:
            return self.indices.get(wi)
        try:
            return self.indices.get(index)
        except IndexNotFoundError:
            # a matching data-stream template auto-creates the STREAM
            # (reference: auto-create routes through the data-stream
            # metadata service when the template carries data_stream)
            wi = self.datastreams.auto_create(index)
            if wi is not None:
                return self.indices.get(wi)
            settings, mappings, aliases = self._apply_templates(
                index, {}, {})
            return self.indices.create_index(index, settings, mappings,
                                             aliases or None)

    # ------------------------------------------------------------------
    # bulk
    # ------------------------------------------------------------------

    # ------------------------------------------------------------------
    # snapshots (reference: snapshots/SnapshotsService.java,
    # repositories/blobstore/BlobStoreRepository.java)
    # ------------------------------------------------------------------

    def _stores_index_selection(self, params, index):
        """Shared indices-options resolution for segments/shard_stores:
        closed indices 400 unless ignore_unavailable, missing wildcard
        matches honor allow_no_indices."""
        ignore = params.get("ignore_unavailable") in ("true", "")
        allow_no = params.get("allow_no_indices") != "false"
        try:
            names = self.indices.resolve(index)
        except IndexNotFoundError:
            if ignore:
                names = []
            else:
                raise
        kept = []
        for n in names:
            svc = self.indices.indices[n]
            if svc.closed:
                if ignore:
                    continue
                from ..common.errors import IndexClosedError
                raise IndexClosedError(f"closed index [{n}]")
            kept.append(n)
        if not kept and not allow_no:
            raise IndexNotFoundError(index or "_all")
        return kept

    def h_resolve_index(self, params, body, name):
        """GET /_resolve/index/{expr} (reference:
        ``ResolveIndexAction``): concrete indices, aliases and data
        streams matching the expression."""
        import fnmatch
        ew = (params.get("expand_wildcards") or "open").split(",")
        out_idx = []
        out_alias = {}
        for part in name.split(","):
            for n in sorted(self.indices.indices):
                svc = self.indices.indices[n]
                hidden = str(svc.settings.get(
                    "index.hidden", "")).lower() == "true"
                is_pat = any(c in part for c in "*?")
                if not (fnmatch.fnmatchcase(n, part) or n == part):
                    continue
                if is_pat and hidden and "hidden" not in ew and \
                        "all" not in ew:
                    continue
                if is_pat and "all" not in ew:
                    if svc.closed and "closed" not in ew:
                        continue
                    if not svc.closed and "open" not in ew:
                        continue
                attrs = ["open"] if not svc.closed else ["closed"]
                if hidden:
                    attrs.append("hidden")
                entry = {"name": n, "attributes": sorted(attrs)}
                aliases = sorted(svc.aliases)
                if aliases:
                    entry["aliases"] = aliases
                if not any(e["name"] == n for e in out_idx):
                    out_idx.append(entry)
            for alias, idxs in self.indices.all_aliases().items():
                if fnmatch.fnmatchcase(alias, part) or alias == part:
                    out_alias.setdefault(alias, set()).update(idxs)
        return {"indices": sorted(out_idx, key=lambda e: e["name"]),
                "aliases": [{"name": a, "indices": sorted(v)}
                            for a, v in sorted(out_alias.items())],
                "data_streams": [
                    {"name": n,
                     "backing_indices": list(st["indices"]),
                     "timestamp_field": "@timestamp"}
                    for n, st in sorted(self.datastreams.streams.items())
                    if any(fnmatch.fnmatchcase(n, p) or n == p
                           for p in name.split(","))]}

    def h_segments(self, params, body, index=None):
        """GET /_segments (reference: ``RestIndicesSegmentsAction``)."""
        names = self._stores_index_selection(params, index)
        indices_out = {}
        shards_total = 0
        for n in names:
            svc = self.indices.indices[n]
            shards_out = {}
            for sid, engine in enumerate(svc.shards):
                shards_total += 1
                segs = {}
                for gi, seg in enumerate(engine.searchable_segments()):
                    segs[seg.seg_id] = {
                        "generation": gi,
                        "num_docs": int(seg.live.sum()),
                        "deleted_docs": int((~seg.live).sum()),
                        "size_in_bytes": 0,
                        "memory_in_bytes": 0,
                        "committed": True, "search": True,
                        "version": "9.0.0",
                        "compound": False}
                shards_out[str(sid)] = [{
                    "routing": {"state": "STARTED", "primary": True,
                                "node": self.node_id},
                    "num_committed_segments": len(segs),
                    "num_search_segments": len(segs),
                    "segments": segs}]
            indices_out[n] = {"shards": shards_out}
        return {"_shards": {"total": shards_total,
                            "successful": shards_total, "failed": 0},
                "indices": indices_out}

    def h_shard_stores(self, params, body, index=None):
        """GET /_shard_stores (reference: ``RestIndicesShardStoresAction``)
        — single node: every primary store lives here."""
        names = self._stores_index_selection(params, index)
        indices_out = {}
        for n in names:
            svc = self.indices.indices[n]
            shards_out = {}
            for sid in range(svc.num_shards):
                shards_out[str(sid)] = {"stores": [{
                    self.node_id: {
                        "name": self.node_name,
                        "transport_address": "127.0.0.1:9300"},
                    "allocation_id": uuid.uuid4().hex[:20],
                    "allocation": "primary"}]}
            indices_out[n] = {"shards": shards_out}
        return {"indices": indices_out}

    def h_clear_cache(self, params, body, index=None):
        """POST /_cache/clear (reference: ``RestClearIndicesCacheAction``)
        — caches are per-request here, so clearing is a counted no-op."""
        names = self._stores_index_selection(params, index)
        shards = sum(self.indices.indices[n].num_shards for n in names)
        return {"_shards": {"total": shards, "successful": shards,
                            "failed": 0}}

    def h_recovery(self, params, body, index=None):
        """Per-shard recovery report (reference:
        ``RestRecoveryAction`` / ``RecoveryState``): single-node, every
        shard recovered at index open, stage DONE."""
        if index is None or index in ("_all", "*"):
            names = sorted(self.indices.indices)
        else:
            names = self.indices.resolve(index)
        out = {}
        for n in names:
            svc = self.indices.indices[n]
            rinfo = getattr(svc, "recovery_info", None) or {}
            rtype = rinfo.get("type") or (
                "EXISTING_STORE" if getattr(svc, "_reopened", False)
                or svc.closed else "EMPTY_STORE")
            files = int(rinfo.get("files", 0))
            size = int(rinfo.get("bytes", 0))
            import datetime as _dtm
            start_ms = svc.creation_date
            start_iso = _dtm.datetime.fromtimestamp(
                start_ms / 1000.0, tz=_dtm.timezone.utc).strftime(
                "%Y-%m-%dT%H:%M:%S.%fZ")
            shards = []
            for sid in range(svc.num_shards):
                shards.append({
                    "id": sid, "type": rtype, "stage": "DONE",
                    "primary": True,
                    "start_time": start_iso,
                    "start_time_in_millis": start_ms,
                    "stop_time": start_iso,
                    "stop_time_in_millis": start_ms,
                    "total_time": "0s", "total_time_in_millis": 0,
                    "source": dict(_RECOVERY_NODE) if rtype !=
                    "EMPTY_STORE" else {},
                    "target": dict(_RECOVERY_NODE),
                    "index": {
                        "files": {"total": files, "reused": 0,
                                  "recovered": files,
                                  "percent": "100.0%",
                                  **({"details": []} if params.get(
                                      "detailed") in ("true", "")
                                      else {})},
                        "size": {"total_in_bytes": size,
                                 "reused_in_bytes": 0,
                                 "recovered_in_bytes": size,
                                 "percent": "100.0%"},
                        "source_throttle_time_in_millis": 0,
                        "target_throttle_time_in_millis": 0},
                    "translog": {"recovered": 0, "total": 0,
                                 "total_on_start": 0,
                                 "total_time": "0s",
                                 "total_time_in_millis": 0,
                                 "percent": "100.0%"},
                    "verify_index": {"check_index_time": "0s",
                                     "check_index_time_in_millis": 0,
                                     "total_time": "0s",
                                     "total_time_in_millis": 0}})
            out[n] = {"shards": shards}
        return out

    def h_put_repo(self, params, body, repo):
        self.snapshots.put_repository(repo, _json_body(body))
        return {"acknowledged": True}

    def h_get_repo(self, params, body, repo=None):
        repos = self.snapshots.repositories
        if repo is None or repo in ("_all", "*"):
            names = sorted(repos)
        else:
            names = [r for r in repo.split(",") if r in repos]
            if not names:
                self.snapshots.get_repository(repo)   # raises 404
        return {n: {"type": "fs",
                    "settings": {"location": repos[n].location}}
                for n in names}

    def h_delete_repo(self, params, body, repo):
        self.snapshots.delete_repository(repo)
        return {"acknowledged": True}

    @staticmethod
    def _snapshot_info(meta: dict, verbose: bool = True,
                       repository: Optional[str] = None) -> dict:
        """Stored snapshot meta → the API's SnapshotInfo view (indices
        dict → name list; verbose=false keeps only the summary keys)."""
        info = {"snapshot": meta["snapshot"], "uuid": meta["uuid"],
                "repository": repository or meta.get("repository"),
                "indices": sorted(meta.get("indices") or {}),
                "state": meta.get("state", "SUCCESS")}
        if not verbose:
            return info
        info.update({
            "include_global_state": meta.get("include_global_state", True),
            "start_time_in_millis": meta.get("start_time_in_millis", 0),
            "end_time_in_millis": meta.get("end_time_in_millis", 0),
            "duration_in_millis": max(
                0, meta.get("end_time_in_millis", 0)
                - meta.get("start_time_in_millis", 0)),
            "version": meta.get("version", "8.0.0"),
            "version_id": 8000099,
            "shards": meta.get("shards") or
            {"total": 0, "failed": 0, "successful": 0},
            "failures": meta.get("failures") or [],
        })
        if meta.get("metadata") is not None:
            info["metadata"] = meta["metadata"]
        return info

    def _create_snapshot_from_config(self, repo: str, snap: str,
                                     config: dict) -> dict:
        """Single marshalling point for snapshot-create config (used by
        the REST handler AND the SLM executor, so they can't diverge)."""
        return self.snapshots.create(
            repo, snap, config.get("indices"),
            include_global_state=config.get("include_global_state", True),
            ignore_unavailable=bool(config.get("ignore_unavailable")),
            metadata=config.get("metadata"))

    def h_create_snapshot(self, params, body, repo, snap):
        payload = _json_body(body) if body else {}
        meta = self._create_snapshot_from_config(repo, snap, payload)
        if params.get("wait_for_completion") in ("true", ""):
            return {"snapshot": self._snapshot_info(meta,
                                                    repository=repo)}
        return {"accepted": True}

    def h_get_snapshot(self, params, body, repo, snap):
        """8.0 response format: one entry per repository with its
        snapshots (or error), like ``RestGetSnapshotsAction``."""
        from ..common.errors import SnapshotMissingError
        verbose = params.get("verbose") not in ("false", "0")
        ignore = params.get("ignore_unavailable") in ("true", "")
        try:
            snaps = self.snapshots.get(repo, snap)
            infos = [self._snapshot_info(m, verbose=verbose,
                                         repository=repo)
                     for m in snaps]
            entry = {"repository": repo, "snapshots": infos}
        except SnapshotMissingError as e:
            if ignore:
                entry = {"repository": repo, "snapshots": []}
            else:
                entry = {"repository": repo,
                         "error": {"type": e.error_type,
                                   "reason": str(e)}}
        return {"responses": [entry]}

    def h_clone_snapshot(self, params, body, repo, snap, target):
        payload = _json_body(body) if body else {}
        self.snapshots.clone(repo, snap, target, payload.get("indices"))
        return {"acknowledged": True}

    def h_verify_repo(self, params, body, repo):
        self.snapshots.get_repository(repo)      # 404 when missing
        return {"nodes": {"node_0": {"name": "node_0"}}}

    def h_cleanup_repo(self, params, body, repo):
        r = self.snapshots.get_repository(repo)
        removed = r.gc_blobs()
        return {"results": {"deleted_bytes": 0,
                            "deleted_blobs": int(removed or 0)}}

    def h_snapshot_status(self, params, body, repo, snap):
        from ..common.errors import SnapshotMissingError
        try:
            return self.snapshots.status(repo, snap)
        except SnapshotMissingError:
            if params.get("ignore_unavailable") in ("true", ""):
                return {"snapshots": []}
            raise

    def h_delete_snapshot(self, params, body, repo, snap):
        self.snapshots.delete(repo, snap)
        return {"acknowledged": True}

    def h_restore_snapshot(self, params, body, repo, snap):
        payload = _json_body(body) if body else {}
        return self.snapshots.restore(
            repo, snap, payload.get("indices"),
            rename_pattern=payload.get("rename_pattern"),
            rename_replacement=payload.get("rename_replacement"))

    # ------------------------------------------------------------------
    # ingest pipelines (reference: ingest/IngestService.java:437,
    # RestPutPipelineAction / RestSimulatePipelineAction)
    # ------------------------------------------------------------------

    def h_put_pipeline(self, params, body, id):
        self.ingest.put_pipeline(id, _json_body(body))
        return {"acknowledged": True}

    def h_get_pipeline(self, params, body, id=None):
        if id is None:
            return {pid: p.config for pid, p in
                    self.ingest.pipelines.items()}
        import fnmatch
        out = {}
        for pid in id.split(","):
            if "*" in pid:
                for k, p in self.ingest.pipelines.items():
                    if fnmatch.fnmatchcase(k, pid):
                        out[k] = p.config
            elif pid in self.ingest.pipelines:
                out[pid] = self.ingest.pipelines[pid].config
        if not out and "*" not in (id or ""):
            return 404, {}
        return out

    def h_delete_pipeline(self, params, body, id):
        self.ingest.delete_pipeline(id)
        return {"acknowledged": True}

    def h_simulate_pipeline(self, params, body, id=None):
        from ..ingest.pipeline import Pipeline
        payload = _json_body(body)
        if id is not None:
            pipeline = self.ingest.get_pipeline(id)
        else:
            if "pipeline" not in payload:
                raise ParsingError("required property is missing: "
                                   "[pipeline]")
            pipeline = Pipeline("_simulate_pipeline", payload["pipeline"])
            self.ingest._inject(pipeline)
        docs = payload.get("docs")
        if not isinstance(docs, list) or not docs:
            raise ParsingError("must specify at least one document in "
                               "[docs]")
        verbose = params.get("verbose") in ("true", "")
        return self.ingest.simulate(pipeline, docs, verbose=verbose)

    def _run_ingest(self, svc: IndexService, index: str,
                    doc_id: Optional[str], source: dict,
                    routing: Optional[str],
                    pipeline_param: Optional[str]):
        """Apply request/default pipeline then final_pipeline. Returns
        (source, index, doc_id, routing) honoring pipeline mutations of
        ``_index``/``_id``/``_routing`` (the reference's reroute-on-ingest
        in ``TransportBulkAction``), or None when the doc was dropped."""
        pid = pipeline_param or svc.settings.get("index.default_pipeline")
        if pid and pid != "_none":
            doc = self.ingest.run(pid, index, doc_id, source, routing)
            if doc is None:
                return None
            source = doc.source
            new_index = doc.meta.get("_index") or index
            if new_index != index:
                # the TARGET index's final_pipeline applies after a
                # reroute (TransportBulkAction re-resolves the pipeline)
                index = new_index
                svc = self._get_or_autocreate(index)
            doc_id = doc.meta.get("_id") or doc_id
            routing = doc.meta.get("_routing")
        final = svc.settings.get("index.final_pipeline")
        if final and final != "_none":
            doc = self.ingest.run(final, index, doc_id, source, routing)
            if doc is None:
                return None
            source = doc.source
            index = doc.meta.get("_index") or index
            doc_id = doc.meta.get("_id") or doc_id
            routing = doc.meta.get("_routing")
        return source, index, doc_id, routing

    def h_bulk(self, params, body, index=None):
        from ..common.indexing_pressure import DEFAULT as _pressure
        with _pressure.coordinating(len(body), "bulk request"):
            return self._bulk_inner(params, body, index)

    def _bulk_inner(self, params, body, index=None):
        t0 = time.time()
        lines = body.split(b"\n")
        items = []
        errors = False
        i = 0
        touched: set = set()
        while i < len(lines):
            line = lines[i].strip()
            i += 1
            if not line:
                continue
            try:
                action = json.loads(line)
            except json.JSONDecodeError as e:
                raise ParsingError(f"Malformed action/metadata line: {e}")
            if not action:
                raise IllegalArgumentError(
                    f"Malformed action/metadata line [{i}], expected "
                    f"FIELD_NAME but found [END_OBJECT]")
            (verb, meta), = action.items()
            if verb == "index" and meta.get("op_type") == "create":
                verb = "create"
            if verb not in ("index", "create", "delete", "update"):
                raise IllegalArgumentError(
                    f"Malformed action/metadata line, expected one of "
                    f"[create, delete, index, update] but found [{verb}]")
            if "_type" in meta:
                raise IllegalArgumentError(
                    f"Action/metadata line [{i}] contains an unknown "
                    f"parameter [_type]")
            idx = meta.get("_index", index)
            if idx is None:
                raise IllegalArgumentError("bulk item requires _index")
            doc_id = meta.get("_id")
            has_explicit_id = doc_id is not None
            doc_id = str(doc_id) if doc_id is not None \
                else uuid.uuid4().hex[:20]
            source = None
            if verb != "delete":
                if i >= len(lines):
                    raise ParsingError("bulk body truncated")
                source = json.loads(lines[i])
                i += 1
            try:
                if has_explicit_id and doc_id == "":
                    if verb == "create":
                        doc_id = uuid.uuid4().hex[:20]
                    else:
                        raise IllegalArgumentError(
                            "if _id is specified it must not be empty")
                require_alias = meta.get(
                    "require_alias",
                    params.get("require_alias") in ("true", ""))
                if require_alias and idx not in self.indices.all_aliases():
                    raise _require_alias_error(idx)
                resolved = self.indices.resolve(idx) \
                    if idx in self.indices.all_aliases() else [idx]
                if len(resolved) > 1:
                    writers = [n for n in resolved
                               if self.indices.indices[n].aliases.get(
                                   idx, {}).get("is_write_index")]
                    if len(writers) == 1:
                        resolved = writers
                        idx = writers[0]
                    else:
                        raise IllegalArgumentError(
                            f"no write index is defined for alias "
                            f"[{idx}]. The write index may be explicitly "
                            f"disabled using is_write_index=false or the "
                            f"alias points to multiple indices without "
                            f"one being designated as a write index")
                svc = self._get_or_autocreate(idx)
                touched.add(idx)
                if verb == "delete":
                    r = svc.delete_doc(doc_id, routing=meta.get("routing"))
                    items.append({"delete": dict(
                        self._doc_response(idx, r, "deleted" if r.found
                                           else "not_found"),
                        status=200 if r.found else 404)})
                elif verb == "update":
                    up_params = {}
                    if meta.get("routing"):
                        up_params["routing"] = meta["routing"]
                    for cas in ("if_seq_no", "if_primary_term"):
                        if meta.get(cas) is not None:
                            up_params[cas] = meta[cas]
                    msrc = meta.get("_source", params.get("_source"))
                    if msrc is not None:
                        up_params["_source"] = msrc if isinstance(
                            msrc, (str, dict)) \
                            else ("true" if msrc else "false")
                    for p_ in ("_source_includes", "_source_excludes"):
                        if params.get(p_) is not None:
                            up_params[p_] = params[p_]
                    r = self.h_update_doc(up_params,
                                          json.dumps(source).encode(),
                                          idx, doc_id)
                    status, resp = r if isinstance(r, tuple) else (200, r)
                    items.append({"update": dict(resp or {}, status=status)})
                else:
                    ingested = self._run_ingest(
                        svc, idx, doc_id, source, meta.get("routing"),
                        meta.get("pipeline") or params.get("pipeline"))
                    if ingested is None:     # dropped by a drop processor
                        items.append({verb: {
                            "_index": idx, "_id": doc_id, "_version": -3,
                            "result": "noop", "status": 200}})
                        continue
                    source, idx2, doc_id2, routing = ingested
                    if idx2 != idx:          # pipeline rerouted the doc
                        svc = self._get_or_autocreate(idx2)
                        idx = idx2
                        touched.add(idx)
                    doc_id = doc_id2 or doc_id
                    r = svc.index_doc(doc_id, source,
                                      routing=routing,
                                      op_type=("create" if verb == "create"
                                               else "index"),
                                      if_seq_no=_int_or_none(
                                          meta.get("if_seq_no")),
                                      if_primary_term=_int_or_none(
                                          meta.get("if_primary_term")))
                    items.append({verb: dict(
                        self._doc_response(idx, r, "created" if r.created
                                           else "updated"),
                        status=201 if r.created else 200)})
            except ElasticsearchError as e:
                errors = True
                status, payload = _error_payload(e)
                items.append({verb: {"_index": idx, "_id": doc_id,
                                     "status": status,
                                     "error": payload["error"]}})
        if params.get("refresh") in ("true", "wait_for", ""):
            for idx in touched:
                self.indices.get(idx).refresh()
            forced = params["refresh"] != "wait_for"
            for item in items:
                for verb_resp in item.values():
                    if "error" not in verb_resp:
                        verb_resp["forced_refresh"] = forced
        return {"took": int((time.time() - t0) * 1000), "errors": errors,
                "items": items}

    # ------------------------------------------------------------------
    # search
    # ------------------------------------------------------------------

    #: inner_hits options forwarded verbatim into the per-group sub-search
    _INNER_HIT_KEYS = ("sort", "_source", "fields", "docvalue_fields",
                      "stored_fields", "version", "seq_no_primary_term",
                      "highlight", "collapse", "explain")

    def _collapse_inner_hits(self, names, search_body, collapse_field,
                             specs, page, hits_out) -> None:
        """Per collapsed group, one sub-search per inner_hits spec: the
        original query AND the group value (reference:
        ``ExpandSearchPhase.java`` — sends multi-search group requests).
        """
        orig_q = search_body.get("query")
        for (n, h), hit_out in zip(page, hits_out):
            gv = (h.fields or {}).get(collapse_field, [None])[0]
            if gv is None:
                group_q = {"bool": {"must_not": [
                    {"exists": {"field": collapse_field}}]}}
            else:
                group_q = {"term": {collapse_field: gv}}
            ih_out = {}
            for sp in specs:
                sp = sp or {}
                name = sp.get("name", collapse_field)
                sub = {"query": {"bool": {
                    "must": [orig_q] if orig_q else [],
                    "filter": [group_q]}},
                    "size": int(sp.get("size", 3)),
                    "from": int(sp.get("from", 0))}
                for k in self._INNER_HIT_KEYS:
                    if k in sp:
                        sub[k] = sp[k]
                r = self._search_indices(names, sub, record_stats=False)
                ih_out[name] = {"hits": r["hits"]}
            hit_out["inner_hits"] = ih_out

    def _script_fields_for(self, sf: dict, h: ShardHit) -> dict:
        """script_fields through the Painless-lite engine: per hit, each
        script sees ``doc`` (source-backed doc values), ``params``, and
        ``_source`` (reference: ``fetch/subphase/ScriptFieldsPhase``)."""
        from ..script.painless_lite import DocAccessor
        from ..script.service import DEFAULT as _scripts
        source = h.source or {}

        def lookup(field):
            node: Any = source
            for part in field.split("."):
                node = node.get(part) if isinstance(node, dict) else None
                if node is None:
                    break
            return node if isinstance(node, list) else (
                [] if node is None else [node])
        out = {}
        for name, spec in sf.items():
            script = (spec or {}).get("script") or {}
            if isinstance(script, str):
                script = {"source": script}
            src_code = self._resolve_script_source(script)
            env = {"doc": DocAccessor(lookup),
                   "params": dict(script.get("params") or {},
                                  _source=source),
                   "_source": source}
            v = _scripts.run(src_code, env)
            out[name] = v if isinstance(v, list) else [v]
        return out

    def _resolve_script_source(self, script: dict) -> str:
        """Inline ``source`` or stored-script ``id`` lookup (reference:
        ``script/StoredScriptSource``)."""
        if script.get("id"):
            stored = self.stored_scripts.get(script["id"])
            if stored is None:
                raise ResourceNotFoundError(
                    f"unable to find script [{script['id']}]")
            return stored.get("source", "")
        return script.get("source", "")

    def _hit_json(self, index_name: str, h: ShardHit,
                  flags: Optional[dict] = None,
                  n_sort: Optional[int] = None) -> dict:
        """``n_sort``: how many leading sort values are user-visible
        (the internal shard-doc tiebreak is NOT serialized — the
        reference only emits it under a PIT's implicit _shard_doc);
        None = legacy passthrough, -1 = suppress the sort array."""
        out = {"_index": index_name, "_id": h.doc_id, "_score": h.score}
        if h.source is not None:
            out["_source"] = h.source
        flags = flags or {}
        stored = flags.get("stored_fields")
        if stored == "_none_" or stored == ["_none_"]:
            out.pop("_id", None)
        if flags.get("seq_no_primary_term") and h.seq_no is not None:
            out["_seq_no"] = h.seq_no
            out["_primary_term"] = 1
        if flags.get("version"):
            try:
                svc = self.indices.get(index_name)
                sid = svc.shard_id_for(h.doc_id)
                ext = getattr(svc.shards[sid], "external_versions",
                              {}).get(h.doc_id)
                if ext is not None:
                    out["_version"] = ext
                else:
                    g = svc.get_doc(h.doc_id)
                    out["_version"] = g.version if g.found else None
            except Exception:   # noqa: BLE001 — alias/closed edge cases
                out["_version"] = None
        if flags.get("explain") and h.score is not None:
            # flat explanation tree: value parity is what clients (and
            # the conformance corpus) assert; full per-clause breakdown
            # comes from the explain API (h_explain)
            out["_explanation"] = {"value": h.score,
                                   "description": "sum of:",
                                   "details": []}
        if h.ignored:
            out["_ignored"] = sorted(set(h.ignored))
        if h.sort_values is not None and n_sort != -1:
            out["sort"] = (h.sort_values if n_sort is None
                           else h.sort_values[:n_sort])
        if h.fields:
            out["fields"] = h.fields
        sf = flags.get("script_fields")
        if isinstance(sf, dict) and sf:
            out.setdefault("fields", {})
            out["fields"].update(self._script_fields_for(sf, h))
        if h.highlight:
            out["highlight"] = h.highlight
        if h.inner_hits:
            rendered = {}
            for nm, grp in h.inner_hits.items():
                g2 = {k: v for k, v in grp.items()
                      if k != "_want_version"}
                if grp.get("_want_version"):
                    root_v = out.get("_version")
                    if root_v is None:
                        try:
                            svc = self.indices.get(index_name)
                            g = svc.get_doc(h.doc_id)
                            root_v = g.version if g.found else None
                        except Exception:   # noqa: BLE001
                            root_v = None
                    for ihh in g2.get("hits", {}).get("hits", []):
                        ihh["_version"] = root_v
                rendered[nm] = g2
            out["inner_hits"] = rendered
        return out

    # search_after tiebreak cursors fold the index ordinal into the high
    # bits of the shard-doc component (ES: PIT's implicit _shard_doc is
    # likewise a global composite). 64 clears the DistributedSearcher's
    # shard<<48 | seg<<32 | doc encoding for any shard count.
    _GSD_ORD_SHIFT = 64

    def _index_local_cursor(self, sa, idx_ord: int, score_sorted: bool,
                            n_user: int):
        """Translate a cross-index search_after cursor into one index's
        local cursor: the cursor index gets the local composite, earlier
        indices exclude equal-tiebreak rows, later ones include them.
        Returns None to drop the cursor for this index."""
        shift = self._GSD_ORD_SHIFT
        if score_sorted:
            if len(sa) < 2:
                return list(sa)
            gsd = int(sa[1])
            a_ord = gsd >> shift
            local = gsd & ((1 << shift) - 1)
            if a_ord == idx_ord:
                return [sa[0], local]
            if a_ord < idx_ord:
                return [sa[0], -1]           # include all ties
            return [sa[0]]                   # exclude all ties
        if len(sa) != n_user + 1:
            return list(sa)                  # legacy strict tuple cursor
        try:
            gsd = int(sa[-1])
        except (OverflowError, ValueError):  # e.g. inf sentinel
            return list(sa)
        if gsd < 0:
            return list(sa)
        a_ord = gsd >> shift
        local = gsd & ((1 << shift) - 1)
        prefix = list(sa[:-1])
        if a_ord == idx_ord:
            return prefix + [local]
        if a_ord < idx_ord:
            return prefix + [-1.0]           # equal-prefix rows all pass
        return prefix + [float("inf")]       # equal-prefix rows excluded

    def _search_indices(self, names: List[str], search_body: dict,
                        record_stats: bool = True) -> dict:
        """Coordinator phase: fans the windowed body out per index and
        merges — one traced span covering fan-out + reduce (the
        coordinator tier of the ``GET /_trace/{id}`` span tree)."""
        from ..common import tracing as _tracing
        with _tracing.span("coordinator[search]", node=self.node_id,
                           attrs={"indices": ",".join(names)}):
            return self._search_indices_traced(names, search_body,
                                               record_stats)

    def _search_indices_traced(self, names: List[str], search_body: dict,
                               record_stats: bool = True) -> dict:
        from ..search.dist_query import merge_sort_key
        from ..search.shard_search import normalize_sort
        t0 = time.time()
        # ?request_cache= rides in on a private body key (params don't
        # reach this layer), same pattern as _pre_filter_shard_size
        request_cache_flag = search_body.pop("_request_cache", None)
        groups = search_body.get("stats")
        if record_stats:
            for _n in names:
                svc = self.indices.indices.get(_n)
                if svc is not None:
                    svc.record_search(groups)
        pfss = search_body.get("_pre_filter_shard_size")
        if pfss is not None:
            search_body = {k: v for k, v in search_body.items()
                           if k != "_pre_filter_shard_size"}
        skipped_shards = 0

        def _aggs_need_all_shards(spec) -> bool:
            # global aggs and min_doc_count:0 terms report buckets even
            # for shards with zero matches — those shards can't skip
            if not isinstance(spec, dict):
                return False
            for body_a in spec.values():
                if not isinstance(body_a, dict):
                    continue
                if "global" in body_a:
                    return True
                for kind, ab in body_a.items():
                    if kind in ("aggs", "aggregations"):
                        if _aggs_need_all_shards(ab):
                            return True
                    elif isinstance(ab, dict) and \
                            ab.get("min_doc_count") == 0:
                        return True
            return False

        if pfss is not None and search_body.get("query") and not \
                _aggs_need_all_shards(search_body.get("aggs")
                                      or search_body.get("aggregations")):
            total_shards_pre = sum(self.indices.indices[n].num_shards
                                   for n in names)
            if int(pfss) <= total_shards_pre:
                from ..search.dist_query import (_required_ranges,
                                                 _shard_can_match)
                bounds = _required_ranges(search_body["query"])
                if bounds:
                    nonmatch = []
                    for n in names:
                        svc = self.indices.indices[n]
                        verdict = None
                        if svc.cluster_hooks is not None:
                            # remote-owned shards: each owner evaluates
                            # over its own segments
                            verdict = svc.cluster_hooks.can_match(
                                n, [list(b) for b in bounds])
                        if verdict is None:
                            verdict = _shard_can_match(svc.searcher(),
                                                       bounds)
                        if not verdict:
                            nonmatch.append(n)
                    if len(nonmatch) == len(names):
                        nonmatch = nonmatch[1:]   # one shard must report
                    skipped_shards = sum(
                        self.indices.indices[n].num_shards
                        for n in nonmatch)
        size = int(search_body.get("size", 10))
        from_ = int(search_body.get("from", 0))
        results = []
        # explicit trailing _shard_doc (the reference's PIT tiebreak):
        # strip it before the shards (they always compute the composite)
        # and serialize the tiebreak component in hit.sort
        raw_sort = search_body.get("sort")
        include_tiebreak = False
        if isinstance(raw_sort, list) and raw_sort and (
                raw_sort[-1] == "_shard_doc" or
                (isinstance(raw_sort[-1], dict)
                 and "_shard_doc" in raw_sort[-1])):
            include_tiebreak = True
            search_body = dict(search_body)
            if len(raw_sort) > 1:
                search_body["sort"] = raw_sort[:-1]
            else:
                search_body.pop("sort", None)
        window_body = dict(search_body)
        window_body["size"] = size + from_
        window_body["from"] = 0
        sort_spec = search_body.get("sort")
        score_sorted = not (sort_spec and not _sort_is_score(sort_spec))
        user_clauses = normalize_sort(sort_spec) if sort_spec and \
            not score_sorted else []
        n_user = len(user_clauses)
        sa = search_body.get("search_after")
        if sa and user_clauses and names:
            # cursor values arrive in field format space (e.g. formatted
            # dates) — coerce through the field type like SortField.parse
            from ..index.mapping import DateFieldType
            mapper = self.indices.indices[names[0]].mapper
            sa = list(sa)
            for i, cl in enumerate(user_clauses[: len(sa)]):
                ft = mapper.field_type(cl["field"])
                if isinstance(ft, DateFieldType):
                    if ft.nanos:
                        # exact-ns sort domain: numeric cursors are
                        # ALREADY epoch nanos; strings parse exactly
                        from ..index.mapping import parse_date_nanos
                        if isinstance(sa[i], str):
                            try:
                                sa[i] = parse_date_nanos(
                                    sa[i], ft.format, ft.locale)
                            except Exception:  # noqa: BLE001 — keep raw
                                pass
                        elif isinstance(sa[i], (int, float)) and \
                                not isinstance(sa[i], bool):
                            sa[i] = int(sa[i])
                    elif isinstance(sa[i], str):
                        try:
                            sa[i] = ft.parse_value(sa[i])
                        except Exception:  # noqa: BLE001 — keep raw cursor
                            pass
        ord_of = {n: i for i, n in enumerate(names)}
        shift = self._GSD_ORD_SHIFT
        local_mask = (1 << shift) - 1
        for n in names:
            body_n = window_body
            if sa is not None and len(names) > 1:
                body_n = dict(window_body)
                cursor = self._index_local_cursor(
                    sa, ord_of[n], score_sorted, n_user)
                if cursor is not None:
                    body_n["search_after"] = cursor
            elif sa is not None:
                body_n = dict(window_body, search_after=sa)
            svc = self.indices.indices[n]
            try:
                r = svc.search(body_n,
                               request_cache=request_cache_flag)
            except ElasticsearchError as e:
                # one index's EVERY shard copy failed inside a
                # multi-index fan-out (a dead owner with no replicas):
                # degrade that index to ES-shaped per-shard failures —
                # the other indices' hits/aggs still answer. Request-
                # level errors (4xx parse/validation) still raise.
                if len(names) == 1 or \
                        int(getattr(e, "status", 500)) < 500 or \
                        getattr(e, "request_level", False):
                    raise
                from ..search.shard_search import ShardSearchResult
                r = ShardSearchResult(
                    total=0, total_relation="eq", hits=[],
                    max_score=None,
                    shard_failures=[{
                        "shard": sid, "node": None,
                        "reason": {"type": e.error_type,
                                   "reason": str(e)},
                        "status": int(getattr(e, "status", 500))}
                        for sid in range(svc.num_shards)])
            results.append((n, r))
        total = sum(r.total for _, r in results)
        relation = "eq"
        if any(r.total_relation == "gte" for _, r in results):
            relation = "gte"
        tth = search_body.get("track_total_hits")
        if isinstance(tth, int) and not isinstance(tth, bool) \
                and tth != -1 and total > tth:
            # -1 means fully-accurate tracking, not a cap
            total, relation = tth, "gte"
        max_scores = [r.max_score for _, r in results
                      if r.max_score is not None]
        all_hits = [(n, h) for n, r in results for h in r.hits]
        ib = search_body.get("indices_boost")
        if ib:
            import fnmatch
            entries = list(ib.items()) if isinstance(ib, dict) else \
                [e for d in ib for e in d.items()]
            boost_of: Dict[str, float] = {}
            for pat, b in entries:
                resolved = [n for n in names
                            if fnmatch.fnmatchcase(n, pat)
                            or pat in self.indices.indices[n].aliases]
                if not resolved and not search_body.get(
                        "_lenient_indices_boost"):
                    raise IndexNotFoundError(pat)
                for n in resolved:         # first matching entry wins
                    boost_of.setdefault(n, float(b))
            for n, h in all_hits:
                if h.score is not None:
                    h.score *= boost_of.get(n, 1.0)
            max_scores = [h.score for _, h in all_hits
                          if h.score is not None]
        if not score_sorted:
            # clause-aware merge (direction + missing placement), then the
            # global (index ordinal, shard-doc) tiebreak — matching the
            # cursor translation order
            def _fkey(nh):
                n, h = nh
                vals = h.sort_values or []
                sd = vals[n_user] if len(vals) > n_user else 0
                return (merge_sort_key(user_clauses, vals[:n_user]),
                        ord_of[n], sd)
            all_hits.sort(key=_fkey)
            for n, h in all_hits:
                if h.sort_values is not None and \
                        len(h.sort_values) == n_user + 1:
                    h.sort_values = h.sort_values[:n_user] + [
                        (ord_of[n] << shift) | int(h.sort_values[n_user])]
        else:
            # tie order MUST match the shards' (score desc, shard_doc asc)
            # cursor order or pagination duplicates/skips tied docs
            def _skey(nh):
                n, h = nh
                sd = (h.sort_values[1]
                      if h.sort_values and len(h.sort_values) > 1 else 0)
                return (-(h.score if h.score is not None else float("-inf")),
                        ord_of[n], sd)
            all_hits.sort(key=_skey)
            for n, h in all_hits:
                if h.sort_values is not None and len(h.sort_values) > 1:
                    h.sort_values = [
                        h.sort_values[0],
                        (ord_of[n] << shift) | int(h.sort_values[1])]
        collapse_field = (search_body.get("collapse") or {}).get("field")
        if collapse_field:
            from ..search.dist_query import collapse_first_by_key
            all_hits = collapse_first_by_key(
                all_hits, lambda nh: (nh[1].fields or {}).get(
                    collapse_field, [None])[0])
        page = all_hits[from_: from_ + size]
        aggregations = None
        agg_failures: List[dict] = []
        if len(names) == 1:
            aggregations = results[0][1].aggregations
        elif any(r.aggregations for _, r in results):
            # cross-index agg reduce: re-run with partial collection;
            # per-owner shard failures (a dead node's copies all down)
            # surface under _shards.failures instead of 500ing
            aggregations = self._reduce_cross_index_aggs(
                names, search_body, failures_out=agg_failures)
        shards_total = sum(self.indices.indices[n].num_shards for n in names)
        failures = list(agg_failures)
        for n, r in results:
            for f in (r.shard_failures or []):
                failures.append(dict(f, index=n))
        # the hits phase and the agg-partials fan-out may both report
        # the same dead shard — one failure entry per (index, shard)
        seen_f: set = set()
        deduped: List[dict] = []
        for f in failures:
            fk = (f.get("index"), f.get("shard"))
            if fk in seen_f:
                continue
            seen_f.add(fk)
            deduped.append(f)
        failures = deduped
        shards_out = {"total": shards_total,
                      "successful": shards_total - len(failures),
                      "skipped": skipped_shards,
                      "failed": len(failures)}
        if failures:
            shards_out["failures"] = failures
        out = {
            "took": int((time.time() - t0) * 1000),
            "timed_out": False,
            "_shards": shards_out,
            "hits": {
                "total": {"value": total, "relation": relation},
                "max_score": max(max_scores) if max_scores else None,
                "hits": [self._hit_json(
                    n, h, search_body,
                    n_sort=(None if include_tiebreak
                            else -1 if sort_spec is None
                            else (n_user if not score_sorted else 1)))
                    for n, h in page],
            },
        }
        if search_body.get("track_total_hits") is False:
            out["hits"].pop("total", None)
        inner_specs = (search_body.get("collapse") or {}).get("inner_hits")
        if collapse_field and inner_specs:
            self._collapse_inner_hits(
                names, search_body, collapse_field,
                inner_specs if isinstance(inner_specs, list)
                else [inner_specs],
                page, out["hits"]["hits"])
        if aggregations is not None:
            out["aggregations"] = aggregations
        # cross-index suggest: merge options per (suggester, token entry) —
        # dedupe by text keeping the best score, re-rank score-descending
        suggests = []
        for n, r in results:
            if not r.suggest:
                continue
            for entries in r.suggest.values():
                for entry in entries:
                    for opt in entry.get("options", []):
                        opt.setdefault("_index", n)
            suggests.append(r.suggest)
        if suggests:
            out["suggest"] = _merge_suggest(suggests)
        profiles = [r.profile for _, r in results if r.profile]
        if profiles:
            out["profile"] = {"shards": [sh for p in profiles
                                         for sh in p["shards"]]}
        return out

    def _reduce_cross_index_aggs(self, names: List[str],
                                 search_body: dict,
                                 failures_out: Optional[List[dict]]
                                 = None) -> dict:
        from ..search.aggregations import (AggregationContext, parse_aggs,
                                           run_aggregations_multi)
        from ..search.query_dsl import MatchAllQuery, parse_query
        import numpy as np
        spec = search_body.get("aggs") or search_body.get("aggregations")
        aggs = parse_aggs(spec)
        ctx_seg_masks = []
        extra_partials: dict = {}
        for n in names:
            svc = self.indices.indices[n]
            if svc.cluster_hooks is not None:
                # cluster-routed index: the owning nodes collect partials
                # and ship them into this one shared reduce; per-shard
                # failures come back ES-shaped with the index stamped
                per_index: List[dict] = []
                remote = svc.cluster_hooks.agg_partials(
                    n, search_body, failures_out=per_index)
                if failures_out is not None:
                    failures_out.extend(
                        dict(f, index=n) for f in per_index)
                if remote is not None:
                    for name_, parts in remote.items():
                        extra_partials.setdefault(name_, []).extend(parts)
                    # reduce-side rendering (key_as_string...) reads the
                    # mapper captured at collect time; remote partials
                    # never collected here, so prime from the replicated
                    # local mapping
                    _prime_agg_mappers(aggs, svc.mapper)
                    continue
            searcher = svc.searcher()
            # per-index context: sub-queries and field-type decisions must
            # see THIS index's mapping and term statistics
            ctx = AggregationContext(svc.mapper, shard_ctx=searcher.ctx)
            q = (parse_query(search_body["query"])
                 if search_body.get("query") else MatchAllQuery())
            for seg in searcher.segments:
                _, mask = q.execute(searcher.ctx, seg)
                mask = mask & seg.live_dev
                ctx_seg_masks.append((ctx, seg, np.asarray(mask)))
        return run_aggregations_multi(aggs, ctx_seg_masks,
                                      extra_partials=extra_partials)

    def _rewrite_terms_lookup(self, node):
        """Coordinator-side rewrite of terms-lookup clauses
        ({"terms": {f: {"index","id","path"}}}) into literal value lists —
        the reference resolves these with an async GET during query rewrite
        (``TermsQueryBuilder.doRewrite``)."""
        if isinstance(node, list):
            for item in node:
                self._rewrite_terms_lookup(item)
            return
        if not isinstance(node, dict):
            return
        t = node.get("terms")
        if isinstance(t, dict):
            for field, spec in list(t.items()):
                if isinstance(spec, dict) and "index" in spec \
                        and "id" in spec:
                    # a missing lookup INDEX is an error (the reference's
                    # coordinator rewrite GET fails the request); a
                    # missing DOC resolves to no terms
                    svc = self.indices.get(spec["index"])
                    try:
                        r = svc.get_doc(str(spec["id"]),
                                        routing=spec.get("routing"))
                        src = r.source if r.found else {}
                    except Exception:   # noqa: BLE001 — doc-level miss
                        src = {}
                    vals = [src]
                    for part in str(spec.get("path", "")).split("."):
                        nxt = []
                        for v in vals:
                            if isinstance(v, dict) and part in v:
                                hit = v[part]
                                nxt.extend(hit if isinstance(hit, list)
                                           else [hit])
                        vals = nxt
                    t[field] = [v for v in vals
                                if not isinstance(v, (dict, list))]
        p = node.get("percolate")
        if isinstance(p, dict) and "document" not in p and \
                "documents" not in p and "index" in p and "id" in p:
            # fetch-form percolate: resolve the candidate doc here (the
            # reference's coordinator GET during query rewrite)
            svc = self.indices.get(p["index"])
            r = svc.get_doc(str(p["id"]), routing=p.get("routing"))
            if not r.found:
                raise ResourceNotFoundError(
                    f"indexed document [{p['index']}/{p['id']}] couldn't "
                    f"be found")
            p["document"] = r.source or {}
        for v in node.values():
            self._rewrite_terms_lookup(v)

    #: accepted top-level search body keys (SearchSourceBuilder fields)
    SEARCH_BODY_KEYS = {
        "query", "from", "size", "sort", "_source", "fields",
        "docvalue_fields", "stored_fields", "script_fields", "aggs",
        "aggregations", "highlight", "suggest", "search_after", "collapse",
        "rescore", "explain", "version", "seq_no_primary_term",
        "track_total_hits", "track_scores", "min_score", "post_filter",
        "knn", "pit", "profile", "indices_boost", "stats", "timeout",
        "terminate_after", "runtime_mappings", "slice", "rank", "ext",
        "indices_options", "prune"}

    def _validate_search(self, search_body: dict, params: dict,
                         names: List[str], scroll: bool = False) -> None:
        """Request validations the reference performs up front
        (SearchSourceBuilder parse + SearchService.validate)."""
        for key in search_body:
            if key not in self.SEARCH_BODY_KEYS:
                raise ParsingError(f"unknown key [{key}] in the search "
                                   f"request")
        tth = search_body.get("track_total_hits")
        if isinstance(tth, int) and not isinstance(tth, bool) and \
                tth < 0 and tth != -1:
            raise IllegalArgumentError(
                f"[track_total_hits] parameter must be positive or equals "
                f"to -1, got {tth}")
        frm = search_body.get("from", params.get("from"))
        if frm is not None and int(frm) < 0:
            raise IllegalArgumentError(
                f"[from] parameter cannot be negative but was [{frm}]")
        size = search_body.get("size", params.get("size"))
        if size is not None and int(size) < 0:
            raise IllegalArgumentError(
                f"[size] parameter cannot be negative, found [{size}]")
        max_window = 10000
        for n in names:
            try:
                max_window = int(self.indices.indices[n].settings.get(
                    "index.max_result_window", max_window))
            except (KeyError, ValueError):
                pass
        f, s = int(frm or 0), int(size if size is not None else 10)
        if scroll:
            if s > max_window:
                raise IllegalArgumentError(
                    f"Batch size is too large, size must be less than or "
                    f"equal to: [{max_window}] but was [{s}]. Scroll batch "
                    f"sizes cost as much memory as result windows so they "
                    f"are controlled by the [index.max_result_window] "
                    f"index level setting.")
        elif f + s > max_window:
            raise IllegalArgumentError(
                f"Result window is too large, from + size must be less "
                f"than or equal to: [{max_window}] but was [{f + s}]. See "
                f"the scroll api for a more efficient way to request "
                f"large data sets. This limit can be set by changing the "
                f"[index.max_result_window] index level setting.")
        # lexical block-max pruning knob (see shard_search.search):
        # reject malformed values at the edge, like from/size above
        pr = search_body.get("prune")
        if pr is not None and not isinstance(pr, bool):
            raise IllegalArgumentError(
                f"[prune] must be a boolean, got [{pr}]")
        for kspec in _as_list(search_body.get("knn")):
            if not isinstance(kspec, dict):
                continue
            # ANN accuracy knobs (see shard_search._knn_candidates):
            # reject malformed values at the edge, like from/size above
            np_ = kspec.get("nprobe")
            if np_ is not None and (isinstance(np_, bool)
                                    or not isinstance(np_, int)
                                    or np_ < 0):
                raise IllegalArgumentError(
                    f"[knn] [nprobe] must be a non-negative integer, "
                    f"got [{np_}]")
            rr = kspec.get("rerank")
            if rr is not None and (isinstance(rr, bool)
                                   or not isinstance(rr, int) or rr < 1):
                raise IllegalArgumentError(
                    f"[knn] [rerank] must be a positive integer, "
                    f"got [{rr}]")
        rank = search_body.get("rank")
        if rank is not None:
            # rank method validation (RankBuilder parse): one method,
            # rrf only, positive integer knobs — the fused planner and
            # the pooled RRF path both rely on these invariants
            if not isinstance(rank, dict) or len(rank) != 1:
                raise IllegalArgumentError(
                    "[rank] must specify exactly one rank method")
            (method, rbody), = rank.items()
            if method != "rrf":
                raise IllegalArgumentError(
                    f"unknown rank method [{method}]")
            rbody = rbody or {}
            if not isinstance(rbody, dict) or \
                    set(rbody) - {"rank_constant", "rank_window_size"}:
                raise IllegalArgumentError(
                    "[rrf] supports [rank_constant] and "
                    "[rank_window_size]")
            rc = rbody.get("rank_constant", 60)
            if isinstance(rc, bool) or not isinstance(rc, int) or rc < 1:
                raise IllegalArgumentError(
                    f"[rank_constant] must be greater or equal to [1] "
                    f"for [rrf], got [{rc}]")
            rws = rbody.get("rank_window_size", 10)
            if isinstance(rws, bool) or not isinstance(rws, int) \
                    or rws < 1:
                raise IllegalArgumentError(
                    f"[rank_window_size] must be greater or equal to "
                    f"[1] for [rrf], got [{rws}]")
            if search_body.get("sort") or search_body.get("collapse"):
                raise IllegalArgumentError(
                    "[rank] cannot be used with [sort] or [collapse]")
        for resc in _as_list(search_body.get("rescore")):
            w = int((resc or {}).get("window_size", 10))
            if w > 10000:
                raise IllegalArgumentError(
                    f"Rescore window [{w}] is too large. It must be less "
                    f"than [10000]. This prevents allocating massive "
                    f"heaps for storing the results to be rescored. This "
                    f"limit can be set by changing the "
                    f"[index.max_rescore_window] index level setting.")
        def idx_setting(key: str, default: int) -> int:
            v = default
            for n in names:
                raw = self.indices.indices[n].settings.get(key)
                if raw is not None:
                    try:
                        v = int(raw)
                    except (TypeError, ValueError):
                        pass
            return v

        dvf = search_body.get("docvalue_fields")
        max_dvf = idx_setting("index.max_docvalue_fields_search", 100)
        if isinstance(dvf, list) and len(dvf) > max_dvf:
            raise IllegalArgumentError(
                f"Trying to retrieve too many docvalue_fields. Must be "
                f"less than or equal to: [{max_dvf}] but was [{len(dvf)}]. "
                f"This limit can be set by changing the "
                f"[index.max_docvalue_fields_search] index level setting.")
        sf = search_body.get("script_fields")
        max_sf = idx_setting("index.max_script_fields", 32)
        if isinstance(sf, dict) and len(sf) > max_sf:
            raise IllegalArgumentError(
                f"Trying to retrieve too many script_fields. Must be less "
                f"than or equal to: [{max_sf}] but was [{len(sf)}]. This "
                f"limit can be set by changing the [index.max_script_fields]"
                f" index level setting.")
        max_regex = idx_setting("index.max_regex_length", 1000)
        max_terms = idx_setting("index.max_terms_count", 65536)
        allow_expensive = str(
            (self.cluster_settings.get("transient") or {}).get(
                "search.allow_expensive_queries",
                (self.cluster_settings.get("persistent") or {}).get(
                    "search.allow_expensive_queries",
                    "true"))).lower() != "false"
        expensive_kinds = {"prefix", "wildcard", "regexp", "fuzzy",
                           "intervals", "script_score", "percolate",
                           "distance_feature", "nested", "has_child",
                           "has_parent", "parent_id"}
        expensive_label = {"nested": "joining", "has_child": "joining",
                           "has_parent": "joining",
                           "parent_id": "joining"}

        #: clause kind → positions holding SUB-CLAUSES (clause-position
        #: recursion only; field names never read as clause kinds)
        _SUBCLAUSE_POS = {
            "bool": ("must", "should", "must_not", "filter"),
            "dis_max": ("queries",),
            "constant_score": ("filter", "query"),
            "nested": ("query",),
            "boosting": ("positive", "negative"),
            "function_score": ("query",),
            "has_child": ("query",), "has_parent": ("query",),
            "span_multi": (), "script_score": ("query",),
        }

        def walk_clause(q):
            if isinstance(q, list):
                for item in q:
                    walk_clause(item)
                return
            if not isinstance(q, dict):
                return
            for k, v in q.items():
                if not allow_expensive and k == "range" and \
                        isinstance(v, dict) and names:
                    from ..index.mapping import (KeywordFieldType,
                                                 TextFieldType)
                    mp = self.indices.indices[names[0]].mapper
                    for fld in v:
                        if isinstance(mp.field_type(fld),
                                      (TextFieldType, KeywordFieldType)):
                            raise IllegalArgumentError(
                                f"[range] queries on [text] or [keyword] "
                                f"fields cannot be executed when "
                                f"'search.allow_expensive_queries' is "
                                f"set to false.")
                if not allow_expensive and k in expensive_kinds:
                    extra = (" For optimised prefix queries on text "
                             "fields please enable [index_prefixes]."
                             if k == "prefix" else "")
                    label = expensive_label.get(k, k)
                    raise IllegalArgumentError(
                        f"[{label}] queries cannot be executed when "
                        f"'search.allow_expensive_queries' is set to "
                        f"false.{extra}")
                for pos in _SUBCLAUSE_POS.get(k, ()):
                    if isinstance(v, dict) and pos in v:
                        walk_clause(v[pos])

        def walk_limits(q):
            # regex/terms size limits recurse EVERYWHERE (field names
            # can't collide with these checks — they inspect values)
            if isinstance(q, list):
                for item in q:
                    walk_limits(item)
                return
            if not isinstance(q, dict):
                return
            for k, v in q.items():
                if k == "regexp" and isinstance(v, dict):
                    for spec in v.values():
                        val = spec.get("value") if isinstance(spec, dict) \
                            else spec
                        if val is not None and len(str(val)) > max_regex:
                            raise IllegalArgumentError(
                                f"The length of regex [{len(str(val))}] "
                                f"used in the Regexp Query request has "
                                f"exceeded the allowed maximum of "
                                f"[{max_regex}]. This maximum can be set "
                                f"by changing the [index.max_regex_length]"
                                f" index level setting.")
                if k == "terms" and isinstance(v, dict):
                    for vals in v.values():
                        if isinstance(vals, list) and \
                                len(vals) > max_terms:
                            raise IllegalArgumentError(
                                f"The number of terms [{len(vals)}] used "
                                f"in the Terms Query request has exceeded "
                                f"the allowed maximum of [{max_terms}]. "
                                f"This maximum can be set by changing the "
                                f"[index.max_terms_count] index level "
                                f"setting.")
                walk_limits(v)

        walk_clause(search_body.get("query"))
        walk_limits(search_body.get("query"))
        if scroll and size is not None and int(size) == 0:
            raise IllegalArgumentError(
                "[size] cannot be [0] in a scroll context")
        if scroll and params.get("request_cache") is not None:
            raise IllegalArgumentError(
                "[request_cache] cannot be used in a scroll context")
        if scroll and search_body.get("track_total_hits") is False:
            raise IllegalArgumentError(
                "disabling [track_total_hits] is not allowed in a "
                "scroll context")
        collapse = search_body.get("collapse")
        if collapse:
            if scroll:
                raise IllegalArgumentError(
                    "cannot use `collapse` in a scroll context")
            if search_body.get("search_after") is not None:
                raise IllegalArgumentError(
                    "cannot use `collapse` in conjunction with "
                    "`search_after`")
            if search_body.get("rescore"):
                raise IllegalArgumentError(
                    "cannot use `collapse` in conjunction with `rescore`")
            ih = collapse.get("inner_hits")
            for sp in (ih if isinstance(ih, list) else [ih] if ih else []):
                icol = (sp or {}).get("collapse")
                if isinstance(icol, dict) and (
                        "inner_hits" in icol or "collapse" in icol):
                    from ..common.errors import ElasticsearchParseError
                    raise ElasticsearchParseError(
                        "[collapse] inner collapse does not support "
                        "inner hits or nested collapse")
        st = params.get("search_type")
        if st and st not in ("query_then_fetch", "dfs_query_then_fetch"):
            raise IllegalArgumentError(
                f"No search type for [{st}]")
        brs = params.get("batched_reduce_size")
        if brs is not None and int(brs) < 2:
            raise IllegalArgumentError("batchedReduceSize must be >= 2")
        pfss = params.get("pre_filter_shard_size")
        if pfss is not None and int(pfss) < 1:
            raise IllegalArgumentError("preFilterShardSize must be >= 1")

    @staticmethod
    def _resolve_date_math(expr: Optional[str]) -> Optional[str]:
        """``<logstash-{now/d}>`` style date-math index names
        (IndexNameExpressionResolver.DateMathExpressionResolver)."""
        if not expr or "<" not in expr:
            return expr
        import datetime

        def one(name: str) -> str:
            if not (name.startswith("<") and name.endswith(">")):
                return name
            inner = name[1:-1]
            m = re.match(r"^(.*)\{now(?:/([dMyHhms]))?"
                         r"(?:\{([^}|]+)(?:\|[^}]*)?\})?\}$", inner)
            if not m:
                return name
            static, unit, fmt = m.group(1), m.group(2), m.group(3)
            now = datetime.datetime.now(datetime.timezone.utc)
            if unit in ("d",):
                now = now.replace(hour=0, minute=0, second=0, microsecond=0)
            elif unit == "M":
                now = now.replace(day=1, hour=0, minute=0, second=0,
                                  microsecond=0)
            elif unit == "y":
                now = now.replace(month=1, day=1, hour=0, minute=0,
                                  second=0, microsecond=0)
            pattern = fmt or "yyyy.MM.dd"
            out = pattern
            for java, strf in (("yyyy", "%Y"), ("MM", "%m"), ("dd", "%d"),
                               ("HH", "%H"), ("mm", "%M"), ("ss", "%S")):
                out = out.replace(java, now.strftime(strf))
            return static + out
        return ",".join(one(p) for p in expr.split(","))

    def _resolve_search_indices(self, index: Optional[str],
                                params: dict) -> List[str]:
        """Index resolution with indices-options semantics."""
        index = self._resolve_date_math(index)
        ignore_unavail = params.get("ignore_unavailable") in ("true", "")
        if ignore_unavail and index:
            names = []
            for part in index.split(","):
                try:
                    names.extend(self.indices.resolve(part))
                except IndexNotFoundError:
                    pass
            names = [n for n in names
                     if not self.indices.indices[n].closed]
        else:
            names = self.indices.resolve(index)
            ew = params.get("expand_wildcards", "open")
            for n in names:
                if self.indices.indices[n].closed and index and (
                        (not any(c in index for c in "*,")
                         and index != "_all")
                        or "closed" in ew or ew == "all"):
                    raise IndexClosedError(f"closed index [{n}]")
            names = [n for n in names
                     if not self.indices.indices[n].closed]
        # frozen (throttled) indices are skipped unless the caller opts
        # in with ignore_throttled=false (FrozenIndices: the search
        # request's default indices options carry ignoreThrottled=true)
        if params.get("ignore_throttled") != "false":
            kept = []
            for n in names:
                svc = self.indices.indices[n]
                if str(svc.settings.get("index.frozen")) == "true":
                    continue
                kept.append(n)
            names = kept
        else:
            for n in names:
                svc = self.indices.indices[n]
                if str(svc.settings.get("index.frozen")) == "true":
                    svc.search_stats["throttled_total"] = \
                        svc.search_stats.get("throttled_total", 0) + 1
        if not names and index and \
                params.get("allow_no_indices") == "false":
            raise IndexNotFoundError(index)
        return names

    def _typed_prefix(self, kind: str, body: dict, mapper) -> str:
        """typed_keys prefixes (InternalAggregation type names)."""
        from ..index.mapping import (BooleanFieldType, DateFieldType,
                                     KeywordFieldType, NumberFieldType)
        if kind in ("terms", "significant_terms"):
            sig = "sig" if kind == "significant_terms" else ""
            ft = mapper.field_type(body.get("field", "")) if mapper else None
            tn = getattr(ft, "type_name", "")
            if isinstance(ft, NumberFieldType):
                return f"{sig}dterms" if tn in ("double", "float",
                                                "half_float") \
                    else f"{sig}lterms"
            if isinstance(ft, (BooleanFieldType, DateFieldType)):
                return f"{sig}lterms"
            return f"{sig}sterms"
        if kind == "percentiles":
            return "hdr_percentiles" if "hdr" in body \
                else "tdigest_percentiles"
        if kind == "percentile_ranks":
            return "hdr_percentile_ranks" if "hdr" in body \
                else "tdigest_percentile_ranks"
        if kind == "rare_terms":
            return "srareterms"
        if kind in ("max_bucket", "min_bucket", "avg_bucket", "sum_bucket"):
            return "bucket_metric_value"
        if kind in ("cumulative_sum", "bucket_script", "moving_fn",
                    "serial_diff"):
            return "simple_value"
        return kind

    def _apply_typed_keys(self, spec: dict, node: dict, mapper) -> None:
        if not isinstance(spec, dict) or not isinstance(node, dict):
            return
        for name, body in spec.items():
            if not isinstance(body, dict) or name not in node:
                continue
            kinds = [k for k in body
                     if k not in ("aggs", "aggregations", "meta")]
            if len(kinds) != 1:
                continue
            kind = kinds[0]
            sub_spec = body.get("aggs") or body.get("aggregations")
            val = node.pop(name)
            if sub_spec and isinstance(val, dict):
                buckets = val.get("buckets")
                if isinstance(buckets, list):
                    for b in buckets:
                        self._apply_typed_keys(sub_spec, b, mapper)
                elif isinstance(buckets, dict):
                    for b in buckets.values():
                        self._apply_typed_keys(sub_spec, b, mapper)
                else:
                    self._apply_typed_keys(sub_spec, val, mapper)
            node[f"{self._typed_prefix(kind, body[kind], mapper)}#{name}"] \
                = val

    def h_msearch(self, params, body, index=None):
        """Multi-search (reference: ``TransportMultiSearchAction``):
        NDJSON header/body pairs, each executed like an independent
        search; failures surface per-response with their status."""
        lines = [ln for ln in body.split(b"\n")]
        responses = []
        i = 0
        t0 = time.time()
        while i < len(lines):
            raw = lines[i].strip()
            i += 1
            if not raw:
                continue
            try:
                header = json.loads(raw)
            except json.JSONDecodeError as e:
                raise ParsingError(
                    f"Malformed msearch header line: {e}")
            if i >= len(lines):
                raise IllegalArgumentError("msearch body truncated")
            search_body_raw = lines[i]
            i += 1
            idx = header.get("index", index)
            if isinstance(idx, list):
                idx = ",".join(idx)
            sub_params = dict(params)
            for hk in ("preference", "routing", "search_type",
                       "ignore_unavailable", "expand_wildcards",
                       "allow_no_indices"):
                if hk in header:
                    v = header[hk]
                    sub_params[hk] = (str(v).lower()
                                      if isinstance(v, bool) else str(v))
            try:
                r = self.h_search(sub_params, search_body_raw, idx)
                status, payload = r if isinstance(r, tuple) else (200, r)
                payload = dict(payload, status=status)
            except Exception as e:   # noqa: BLE001 — per-item failure
                if getattr(e, "request_level", False):
                    raise            # request-level validation, not item
                status, err = _error_payload(e)
                payload = dict(err, status=status)
            responses.append(payload)
        return {"took": int((time.time() - t0) * 1000),
                "responses": responses}

    def h_search(self, params, body, index=None):
        """Shape attribution opens at the REST boundary: the structural
        fingerprint binds as soon as the body parses, so validation,
        security filtering and response serialization all profile (and
        slow-log) under the query's shape — the shard layer upgrades
        the bound holder to the plan-based id in place."""
        from ..common import flightrec as _fr
        from ..search import query_insight as _qi
        body = _json_body(body)
        tok = _fr.bind_shape(_qi.shape_of(body)) \
            if _qi.insights_enabled() else None
        try:
            return self._h_search_parsed(params, body, index=index)
        finally:
            if tok is not None:
                _fr.reset_shape(tok)

    def _h_search_parsed(self, params, body, index=None):
        brs_p = params.get("batched_reduce_size")
        if brs_p is not None and int(brs_p) < 2:
            raise IllegalArgumentError("batchedReduceSize must be >= 2")
        pfss_p = params.get("pre_filter_shard_size")
        if pfss_p is not None and int(pfss_p) < 1:
            raise IllegalArgumentError("preFilterShardSize must be >= 1")
        local_parts, remote_parts = self.remotes.split_expression(index)
        if remote_parts:
            return self._ccs_search(params, body, local_parts,
                                    remote_parts)
        names = self._resolve_search_indices(index, params)
        search_body = _json_body(body)
        fls_grant = None
        if self.security.enabled and self.enforce_security and \
                not getattr(self._internal_tls, "active", False):
            search_body, fls_grant = self._apply_dls_fls(
                names, search_body)
        # URL-param forms of fetch options (they OVERRIDE body _source
        # filtering, RestSearchAction.parseSearchSource)
        if "_source_includes" in params or "_source_excludes" in params:
            search_body["_source"] = {
                k: params[p].split(",")
                for k, p in (("includes", "_source_includes"),
                             ("excludes", "_source_excludes")) if p in params}
        elif "_source" in params:
            v = params["_source"]
            search_body["_source"] = (v.lower() == "true") \
                if v.lower() in ("true", "false") else v.split(",")
        if "docvalue_fields" in params:
            search_body["docvalue_fields"] = \
                params["docvalue_fields"].split(",")
        if "stored_fields" in params:
            search_body["stored_fields"] = params["stored_fields"].split(",")
        if "track_total_hits" in params:
            v = params["track_total_hits"].lower()
            search_body["track_total_hits"] = (
                v == "true" if v in ("true", "false") else int(v))
        for bflag in ("seq_no_primary_term", "version", "explain"):
            if bflag in params:
                search_body[bflag] = _flag(params, bflag)
        if search_body.get("fields"):
            for n in names:
                if not self.indices.indices[n].mapper.source_enabled:
                    raise IllegalArgumentError(
                        f"Unable to retrieve the requested [fields] since "
                        f"_source is disabled in the mappings for index "
                        f"[{n}]")
        self._rewrite_terms_lookup(search_body)
        self._validate_search(search_body, params, names,
                              scroll=bool(params.get("scroll")))
        if params.get("request_cache") is not None:
            search_body["_request_cache"] = \
                params["request_cache"] in ("true", "")
        if params.get("rest_total_hits_as_int") in ("true", "") and \
                isinstance(search_body.get("track_total_hits"), int) and \
                not isinstance(search_body.get("track_total_hits"), bool) \
                and search_body.get("track_total_hits") != -1:
            e = IllegalArgumentError(
                "[rest_total_hits_as_int] cannot be used if the tracking "
                "of total hits is not accurate, got "
                f"{search_body['track_total_hits']}")
            e.request_level = True      # msearch fails the whole request
            raise e
        if params.get("ignore_unavailable") in ("true", "") and \
                search_body.get("indices_boost"):
            search_body = dict(search_body, _lenient_indices_boost=True)
        if "q" in params:
            search_body["query"] = {"query_string": {
                "query": params["q"],
                **({"default_field": params["df"]} if "df" in params
                   else {}),
                **({"default_operator": params["default_operator"]}
                   if "default_operator" in params else {}),
                **({"analyzer": params["analyzer"]}
                   if "analyzer" in params else {}),
                **({"lenient": params["lenient"] == "true"}
                   if "lenient" in params else {}),
            }}
        for p in ("size", "from"):
            if p in params:
                search_body[p] = int(params[p])
        if not names:
            # the reference still PARSES the request against zero indices —
            # malformed aggs/queries must error, not silently return empty
            from ..search.aggregations import parse_aggs
            from ..search.query_dsl import parse_query
            if search_body.get("aggs") or search_body.get("aggregations"):
                parse_aggs(search_body.get("aggs")
                           or search_body.get("aggregations"))
            if search_body.get("query") is not None:
                parse_query(search_body["query"])
            empty = {"took": 0, "timed_out": False,
                     "_shards": {"total": 0, "successful": 0, "skipped": 0,
                                 "failed": 0},
                     "hits": {"total": {"value": 0, "relation": "eq"},
                              "max_score": None, "hits": []}}
            if params.get("rest_total_hits_as_int") in ("true", ""):
                empty["hits"]["total"] = 0
            return empty
        scroll = params.get("scroll")
        if scroll:
            if int(search_body.get("size", 10)) == 0:
                raise IllegalArgumentError(
                    "[size] cannot be [0] in a scroll context")
            out = self._start_scroll(names, search_body, scroll)
        else:
            body_x = search_body
            if pfss_p is not None:
                body_x = dict(search_body,
                              _pre_filter_shard_size=int(pfss_p))
            out = self._search_indices(names, body_x)
            shards_n = out.get("_shards", {}).get("total", 0)
            brs = int(brs_p) if brs_p is not None else 512
            if shards_n > brs:
                # one partial reduce per buffered batch past the window
                out["num_reduce_phases"] = shards_n - brs + 1
        if _flag(params, "typed_keys") and out.get("aggregations") \
                and names:
            self._apply_typed_keys(
                search_body.get("aggs") or search_body.get("aggregations")
                or {}, out["aggregations"],
                self.indices.indices[names[0]].mapper)
        if _flag(params, "typed_keys") and out.get("suggest"):
            sspec = search_body.get("suggest") or {}
            renamed = {}
            for sname, entries in out["suggest"].items():
                body_s = sspec.get(sname) or {}
                kind = next((k for k in ("term", "phrase", "completion")
                             if k in body_s), None)
                renamed[f"{kind}#{sname}" if kind else sname] = entries
            out["suggest"] = renamed
        if params.get("rest_total_hits_as_int") in ("true", ""):
            total = out.get("hits", {}).get("total")
            if isinstance(total, dict):
                out["hits"]["total"] = total["value"]
            elif total is None and "hits" in out:
                out["hits"]["total"] = -1    # track_total_hits=false
            for hit in out.get("hits", {}).get("hits", []):
                for ih in (hit.get("inner_hits") or {}).values():
                    t = ih.get("hits", {}).get("total")
                    if isinstance(t, dict):
                        ih["hits"]["total"] = t["value"]
        if fls_grant is not None:
            self._apply_fls(out, fls_grant)
        return out

    def _restrictions_for(self, names):
        """(dls_queries, fls_grant) for a set of target indices, or
        (None, None) when the principal is unrestricted.  Mixed
        restrictions across indices in ONE request are rejected rather
        than risk cross-index leakage through a shared filter."""
        principal = self._principal()
        if "superuser" in (principal.get("roles") or []):
            return None, None
        per_index = [self.security.rbac.dls_fls(principal, n)
                     for n in names]
        if not per_index:
            return None, None
        first = per_index[0]
        if any(p != first for p in per_index[1:]):
            from ..security.rbac import AuthorizationError
            raise AuthorizationError(
                "searching across indices with differing document- or "
                "field-level security is not supported in one request")
        queries, fls = first
        return (queries or None), fls

    #: body sections whose field references would leak restricted
    #: values past an _source-level trim
    _FLS_SENSITIVE = ("aggs", "aggregations", "sort", "docvalue_fields",
                      "script_fields", "highlight", "suggest",
                      "collapse", "runtime_mappings")

    def _apply_dls_fls(self, names, search_body):
        """Document- and field-level security for one search request
        (``authz/accesscontrol/SecurityIndexSearcherWrapper`` analog:
        DLS role queries filter the query; FLS grants trim _source)."""
        queries, fls = self._restrictions_for(names)
        if queries:
            dls = {"bool": {"should": queries,
                            "minimum_should_match": 1}} \
                if len(queries) > 1 else queries[0]
            orig = search_body.get("query") or {"match_all": {}}
            search_body = dict(search_body,
                               query={"bool": {"must": [orig],
                                               "filter": [dls]}})
        if fls is not None:
            # sections that surface raw field VALUES outside _source
            # (agg buckets, sort keys, highlights …) cannot be trimmed
            # after the fact — reject unless every referenced field is
            # granted
            import fnmatch

            def granted(f):
                return any(fnmatch.fnmatchcase(str(f), g) for g in fls)

            def scan(node):
                if isinstance(node, dict):
                    for k, v in node.items():
                        if k == "field" and isinstance(v, str) and \
                                not granted(v):
                            return v
                        if k == "fields" and isinstance(v, list):
                            for f in v:
                                fv = f.get("field") if \
                                    isinstance(f, dict) else f
                                if isinstance(fv, str) and \
                                        not granted(fv):
                                    return fv
                        bad = scan(v)
                        if bad:
                            return bad
                elif isinstance(node, list):
                    for v in node:
                        bad = scan(v)
                        if bad:
                            return bad
                return None

            for section in self._FLS_SENSITIVE:
                spec = search_body.get(section)
                if spec is None:
                    continue
                if section == "sort":
                    items = spec if isinstance(spec, list) else [spec]
                    for s in items:
                        fields = [s] if isinstance(s, str) else \
                            list(s) if isinstance(s, dict) else []
                        for f in fields:
                            if f not in ("_score", "_doc",
                                         "_shard_doc") and \
                                    not granted(f):
                                self._fls_reject(f)
                    continue
                bad = scan(spec)
                if bad:
                    self._fls_reject(bad)
        return search_body, fls

    @staticmethod
    def _fls_reject(field):
        from ..security.rbac import AuthorizationError
        raise AuthorizationError(
            f"field [{field}] is not granted by this role's field "
            f"level security")

    def _doc_read_guard(self, index: str, doc_id: str):
        """DLS/FLS for single-document reads.  Returns the FLS grant
        (or None); raises not-visible as a KeyError-style miss by
        returning False when the DLS query excludes the doc.  The DLS
        check runs as an internal ids+filter search — the reference
        likewise rewrites realtime gets to a filtered search when DLS
        applies (``SecuritySearchOperationListener``)."""
        if not (self.security.enabled and self.enforce_security) or \
                getattr(self._internal_tls, "active", False):
            return True, None
        queries, fls = self._restrictions_for([index])
        if queries:
            dls = {"bool": {"should": queries,
                            "minimum_should_match": 1}} \
                if len(queries) > 1 else queries[0]
            resp = self.internal_search(index, {
                "size": 0, "track_total_hits": True,
                "query": {"bool": {
                    "filter": [{"ids": {"values": [doc_id]}}, dls]}}})
            if resp["hits"]["total"]["value"] == 0:
                return False, fls
        return True, fls

    def _fls_trim_doc(self, out: dict, fls) -> dict:
        if fls is None:
            return out
        import fnmatch

        def allowed(f):
            return any(fnmatch.fnmatchcase(f, g) for g in fls)

        if isinstance(out.get("_source"), dict):
            out["_source"] = {k: v for k, v in out["_source"].items()
                              if allowed(k)}
        if isinstance(out.get("fields"), dict):
            out["fields"] = {k: v for k, v in out["fields"].items()
                             if allowed(k)}
        return out

    def _deny_if_restricted(self, index_expr):
        """Endpoints whose responses can't be post-filtered (explain,
        termvectors, EQL, graph) refuse under DLS/FLS rather than
        leak."""
        if not (self.security.enabled and self.enforce_security) or \
                getattr(self._internal_tls, "active", False):
            return
        try:
            names = self.indices.resolve(index_expr)
        except Exception:   # noqa: BLE001 — missing index: 404 later
            return
        queries, fls = self._restrictions_for(names)
        if queries or fls is not None:
            from ..security.rbac import AuthorizationError
            raise AuthorizationError(
                "this endpoint is not available for roles with "
                "document- or field-level security")

    @staticmethod
    def _apply_fls(out, grant):
        """Trim every hit's _source to the granted field patterns."""
        import fnmatch

        def allowed(field):
            return any(fnmatch.fnmatchcase(field, g) for g in grant)

        for hit in out.get("hits", {}).get("hits", []):
            src = hit.get("_source")
            if isinstance(src, dict):
                hit["_source"] = {k: v for k, v in src.items()
                                  if allowed(k)}
            flds = hit.get("fields")
            if isinstance(flds, dict):
                hit["fields"] = {k: v for k, v in flds.items()
                                 if allowed(k)}

    def h_validate_query(self, params, body, index=None):
        """Query validation (reference: ``RestValidateQueryAction``):
        parse the query; explain=true adds the parsed description and
        the rewritten Lucene form."""
        from ..search.query_dsl import parse_query
        payload = _json_body(body) if body else {}
        valid = True
        error = None
        bad_top = [k for k in payload if k != "query"]
        spec = payload.get("query")
        if bad_top:
            valid = False
            error = (f"org.elasticsearch.common.ParsingException: "
                     f"request does not support [{bad_top[0]}]")
        elif spec is None and params.get("q"):
            spec = {"query_string": {"query": params["q"], **(
                {"default_field": params["df"]} if "df" in params
                else {})}}
        if valid and spec is not None:
            try:
                parse_query(spec)
            except Exception as e:      # noqa: BLE001 — any parse failure
                valid = False
                error = (f"{type(e).__name__}: {e} "
                         f"(while parsing [query])")
        explain = params.get("explain") in ("true", "")
        out = {"valid": valid,
               "_shards": {"total": 1, "successful": 1, "failed": 0}}
        if explain and error:
            out["error"] = error
        if explain or (error and not bad_top):
            resolved = None
            if index:
                try:
                    resolved = (self.indices.resolve(index)
                                or [index])[0]
                except IndexNotFoundError:
                    resolved = index
            elif self.indices.indices:
                # no index in the request: one explanation per index
                # (first suffices for this single-node tier)
                resolved = sorted(self.indices.indices)[0]
            expl = {"index": resolved or "_all", "valid": valid}
            if error:
                expl["error"] = error
            elif spec is None or "match_all" in spec:
                expl["explanation"] = "*:*"
            else:
                expl["explanation"] = json.dumps(spec)
            out["explanations"] = [expl]
        return out

    def h_count(self, params, body, index=None):
        names = self.indices.resolve(index)
        b = _json_body(body)
        bad = [k for k in b if k != "query"]
        if bad:
            raise ActionRequestValidationError(
                f"request does not support [{bad[0]}]")
        if "q" in params:
            qs = {"query": params["q"]}
            if "df" in params:
                qs["default_field"] = params["df"]
            if "default_operator" in params:
                qs["default_operator"] = params["default_operator"]
            if params.get("lenient") in ("true", ""):
                qs["lenient"] = True
            if "analyzer" in params:
                qs["analyzer"] = params["analyzer"]
            b = {"query": {"query_string": qs}}
        self._rewrite_terms_lookup(b)
        if self.security.enabled and self.enforce_security and \
                not getattr(self._internal_tls, "active", False):
            queries, _fls = self._restrictions_for(names)
            if queries:
                dls = {"bool": {"should": queries,
                                "minimum_should_match": 1}} \
                    if len(queries) > 1 else queries[0]
                orig = b.get("query") or {"match_all": {}}
                b = dict(b, query={"bool": {"must": [orig],
                                            "filter": [dls]}})
        total = 0
        for n in names:
            total += self.indices.indices[n].count(b)
        return {"count": total,
                "_shards": {"total": len(names), "successful": len(names),
                            "skipped": 0, "failed": 0}}

    # -- scroll ---------------------------------------------------------

    SCROLL_MAX_DOCS = 500_000


    def _max_keep_alive_ms(self) -> float:
        from ..common.settings import parse_time_millis
        raw = (self.cluster_settings.get("transient") or {}).get(
            "search.max_keep_alive")
        if raw is None:
            raw = (self.cluster_settings.get("persistent") or {}).get(
                "search.max_keep_alive")
        if raw is None:
            raw = "24h"
        return parse_time_millis(raw)

    def _check_keep_alive(self, keep_alive) -> None:
        if not keep_alive or keep_alive == "_none":
            return
        from ..common.settings import parse_time_millis
        max_ka = self._max_keep_alive_ms()
        if parse_time_millis(keep_alive) > max_ka:
            raise IllegalArgumentError(
                f"Keep alive for request ({keep_alive}) is too large. It "
                f"must be less than ({int(max_ka // 60000)}m). This limit "
                f"can be set by changing the [search.max_keep_alive] "
                f"cluster level setting.")

    def _start_scroll(self, names, search_body, keep_alive) -> dict:
        self._check_keep_alive(keep_alive)
        size = int(search_body.get("size", 10))
        big = dict(search_body)
        big["size"] = self.SCROLL_MAX_DOCS
        big["from"] = 0
        all_hits = []
        for n in names:
            r = self.indices.indices[n].search(big)
            all_hits.extend((n, h) for h in r.hits)
        if search_body.get("sort") and not _sort_is_score(
                search_body.get("sort")):
            all_hits.sort(key=lambda nh: _sort_key_tuple(nh[1]))
        else:
            all_hits.sort(key=lambda nh: (
                -(nh[1].score if nh[1].score is not None else float("-inf")),
                nh[0], nh[1].doc_id))
        slc = search_body.get("slice")
        if slc:
            sid_, smax = int(slc.get("id", 0)), int(slc.get("max", 1))
            if smax <= 1:
                raise IllegalArgumentError(
                    f"max must be greater than 1, got [{smax}]")
            if not (0 <= sid_ < smax):
                raise IllegalArgumentError(
                    f"id must be less than max, got id [{sid_}] and "
                    f"max [{smax}]")
            explicit = []
            for n in names:
                raw = self.indices.indices[n].settings.get(
                    "index.max_slices_per_scroll")
                if raw is not None:
                    try:
                        explicit.append(int(raw))
                    except (TypeError, ValueError):
                        pass
            max_slices = min(explicit) if explicit else 1024
            if smax > max_slices:
                raise IllegalArgumentError(
                    f"The number of slices [{smax}] is too large. It must "
                    f"be less than [{max_slices}]. This limit can be set "
                    f"by changing the [index.max_slices_per_scroll] index "
                    f"level setting.")
            from ..utils.murmur3 import murmur3_32, shard_for
            def _slice_of(n, h):
                shards = self.indices.indices[n].num_shards
                if smax <= shards:
                    # slice by shard id (SliceBuilder shard partitioning)
                    return shard_for(h.doc_id, shards) % smax
                return murmur3_32(h.doc_id.encode()) % smax
            all_hits = [nh for nh in all_hits
                        if _slice_of(*nh) == sid_]
        sid = uuid.uuid4().hex
        hit_flags = {k: search_body[k] for k in ("script_fields",)
                     if k in search_body}
        self.scrolls[sid] = {"hits": all_hits, "pos": size, "size": size,
                             "total": len(all_hits),
                             "flags": hit_flags,
                             "expiry": time.time() + 300}
        page = all_hits[:size]
        return {
            "_scroll_id": sid, "took": 0, "timed_out": False,
            "_shards": {"total": len(names), "successful": len(names),
                        "skipped": 0, "failed": 0},
            "hits": {"total": {"value": len(all_hits), "relation": "eq"},
                     "max_score": None,
                     "hits": [self._hit_json(n, h, hit_flags)
                              for n, h in page]}}

    def h_scroll(self, params, body, scroll_id=None):
        b = _json_body(body) if body else {}
        # body params OVERRIDE query-string/path ones (RestSearchScroll)
        sid = b.get("scroll_id") or scroll_id or params.get("scroll_id")
        ka = b.get("scroll") or params.get("scroll")
        self._check_keep_alive(ka)
        ctx = self.scrolls.get(sid)
        if ctx is None:
            return 404, {"error": {"type": "search_context_missing_exception",
                                   "reason": f"No search context found for "
                                             f"id [{sid}]"}, "status": 404}
        size = ctx.get("size", 10)
        page = ctx["hits"][ctx["pos"]: ctx["pos"] + size]
        ctx["pos"] += size
        out = {
            "_scroll_id": sid, "took": 0, "timed_out": False,
            "_shards": {"total": 1, "successful": 1, "skipped": 0,
                        "failed": 0},
            "hits": {"total": {"value": ctx["total"], "relation": "eq"},
                     "max_score": None,
                     "hits": [self._hit_json(n, h, ctx.get("flags"))
                              for n, h in page]}}
        if params.get("rest_total_hits_as_int") in ("true", ""):
            out["hits"]["total"] = ctx["total"]
        return out

    def h_clear_scroll(self, params, body, scroll_id=None):
        b = _json_body(body) if body else {}
        ids = b.get("scroll_id", [])
        if isinstance(ids, str):
            ids = [ids]
        if scroll_id:
            ids = list(ids) + (["_all"] if scroll_id == "_all"
                               else scroll_id.split(","))
        if "_all" in ids:
            n = len(self.scrolls)
            self.scrolls.clear()
            return {"succeeded": True, "num_freed": n}
        n = 0
        for sid in ids:
            if self.scrolls.pop(sid, None) is not None:
                n += 1
        if n == 0:
            return 404, {"succeeded": True, "num_freed": 0}
        return {"succeeded": True, "num_freed": n}

    def h_open_pit(self, params, body, index):
        names = self.indices.resolve(index)
        pid = uuid.uuid4().hex
        self.pits[pid] = {"indices": names,
                          "expiry": time.time() + 300}
        return {"id": pid}

    def h_close_pit(self, params, body):
        b = _json_body(body)
        ok = self.pits.pop(b.get("id"), None) is not None
        return {"succeeded": ok, "num_freed": 1 if ok else 0}

    # -- by query --------------------------------------------------------

    def _matched_ids(self, svc: IndexService, query: dict) -> List[str]:
        searcher = svc.searcher()
        r = searcher.search({"query": query, "size": self.SCROLL_MAX_DOCS,
                             "_source": False})
        return [h.doc_id for h in r.hits]

    def h_delete_by_query(self, params, body, index):
        b = _json_body(body)
        self._rewrite_terms_lookup(b)
        query = b.get("query") or {"match_all": {}}
        names = self.indices.resolve(index)
        task = self.current_task()
        task.cancellable = True
        task.description = f"delete-by-query [{index}]"

        def run():
            t0 = time.time()
            deleted = 0
            for n in names:
                svc = self.indices.indices[n]
                for i, doc_id in enumerate(self._matched_ids(svc, query)):
                    if i % 100 == 0:
                        task.check_cancelled()
                    r = svc.delete_doc(doc_id)
                    if r.found:
                        deleted += 1
                        task.status.update(total=deleted, deleted=deleted)
                svc.refresh()
            return {"took": int((time.time() - t0) * 1000),
                    "timed_out": False, "deleted": deleted,
                    "total": deleted, "failures": [], "batches": 1,
                    "version_conflicts": 0, "noops": 0,
                    "retries": {"bulk": 0, "search": 0}}

        if params.get("wait_for_completion") == "false":
            self.task_manager.run_async(task, run)
            return {"task": task.tid}
        return run()

    def h_explain(self, params, body, index, id):
        self._deny_if_restricted(index)
        """Score explanation for one document (reference:
        ``RestExplainAction`` → ``TransportExplainAction``): the query
        executes against the owning segment and the per-top-level-clause
        contributions are reported (the dense execution model scores whole
        segments; the per-doc breakdown gathers each clause's score at the
        doc)."""
        from ..search.query_dsl import parse_query
        svc = self.indices.get(index)
        index = svc.name             # alias → concrete in responses
        payload = _json_body(body)
        self._rewrite_terms_lookup(payload)
        if payload and "query" not in payload:
            raise ParsingError(
                "Expected [query] element, but found none")
        query_spec = payload.get("query")
        if "q" in params:
            qs = {"query": params["q"]}
            if "df" in params:
                qs["default_field"] = params["df"]
            if "default_operator" in params:
                qs["default_operator"] = params["default_operator"]
            if params.get("lenient") in ("true", ""):
                qs["lenient"] = True
            query_spec = {"query_string": qs}
        query_spec = query_spec or {"match_all": {}}
        searcher = svc.searcher()
        target = None
        for seg_idx, seg in enumerate(searcher.segments):
            d = seg.find_doc(id)
            if d is not None:
                target = (seg_idx, seg, d)
                break
        if target is None:
            return 404, {"_index": index, "_id": id, "matched": False,
                         "error": f"document [{id}] does not exist"}
        seg_idx, seg, d = target
        query = parse_query(query_spec)
        scores, mask = query.execute(searcher.ctx, seg)
        matched = bool(np.asarray(mask)[d]) and bool(seg.live[d])
        value = float(np.asarray(scores)[d]) if matched else 0.0
        details = []
        if isinstance(query_spec, dict) and "bool" in query_spec:
            for section in ("must", "should", "filter"):
                clauses = query_spec["bool"].get(section) or []
                if isinstance(clauses, dict):
                    clauses = [clauses]
                for c in clauses:
                    cs, cm = parse_query(c).execute(searcher.ctx, seg)
                    if bool(np.asarray(cm)[d]):
                        details.append({
                            "value": float(np.asarray(cs)[d]),
                            "description": f"{section} clause: "
                                           f"{json.dumps(c)}",
                            "details": []})
        out = {"_index": index, "_id": id, "matched": matched,
               "explanation": {
                   "value": value,
                   "description": ("sum of:" if details else
                                   f"query: {json.dumps(query_spec)}"),
                   "details": details}}
        src_spec = self._get_source_spec(params)
        if src_spec is not None and src_spec is not False:
            from ..search.fetch import filter_source
            out["get"] = {"found": True,
                          "_source": filter_source(seg.sources[d],
                                                   src_spec)}
        return out

    def _termvectors_one(self, params, body_spec, index, id):
        """Term vectors for ONE doc. Multi-index aliases reject like the
        reference's single-shard routing check."""
        names = self.indices.resolve(index)
        if len(names) > 1:
            listed = "[" + ", ".join(sorted(names)) + "]"
            raise IllegalArgumentError(
                f"Alias [{index}] has more than one index associated "
                f"with it [{listed}], can't execute a single index op")
        concrete = names[0]
        svc = self.indices.indices[concrete]
        if params.get("realtime") != "false":
            # realtime reads see the doc even before an explicit refresh
            svc.refresh()
        want_stats = params.get("term_statistics") in ("true", "") or \
            (body_spec or {}).get("term_statistics") is True
        fields_filter = params.get("fields") or \
            (body_spec or {}).get("fields")
        if isinstance(fields_filter, str):
            fields_filter = fields_filter.split(",")
        wanted = set(fields_filter) if fields_filter else None
        searcher = svc.searcher()
        for seg in searcher.segments:
            d = seg.find_doc(id)
            if d is None or not seg.live[d]:
                continue
            src = seg.sources[d] or {}
            tv = {}
            for fname, f in seg.text_fields.items():
                if wanted is not None and fname not in wanted:
                    continue
                ft = svc.mapper.field_type(fname)
                analyzer = getattr(ft, "analyzer", None)
                value = src
                for part in fname.split("."):
                    value = value.get(part) if isinstance(value, dict) \
                        else None
                    if value is None:
                        break
                # offsets come from re-analysis of the stored source
                # (positions ride the postings CSR, offsets don't)
                tok_of: Dict[str, list] = {}
                if analyzer is not None and value is not None:
                    vals = value if isinstance(value, list) else [value]
                    base_pos = 0
                    base_off = 0
                    for v in vals:
                        text = str(v)
                        last = -1
                        for tok in analyzer.analyze(text):
                            last = max(last, tok.position)
                            tok_of.setdefault(tok.term, []).append(
                                {"position": base_pos + tok.position,
                                 "start_offset":
                                     base_off + tok.start_offset,
                                 "end_offset":
                                     base_off + tok.end_offset})
                        # multi-valued gap matches index-time postings
                        # (position_increment_gap 100 + 1, offsets run
                        # on as if values were space-joined)
                        base_pos += last + 101
                        base_off += len(text) + 1
                terms_out = {}
                for term, tid in f.term_ids.items():
                    st, ln, df = f.term_run(term)
                    run = f.docs_host[st: st + ln]
                    i = int(np.searchsorted(run, d))
                    if i >= ln or run[i] != d:
                        continue
                    p = st + i
                    toks = tok_of.get(term)
                    if not toks:
                        toks = [{"position": int(pos)} for pos in
                                f.pos_flat[f.pos_offsets[p]:
                                           f.pos_offsets[p + 1]]]
                    entry = {"term_freq": int(f.tf_host[p]),
                             "tokens": toks}
                    if want_stats:
                        entry["doc_freq"] = int(df)
                        entry["ttf"] = int(f.total_term_freq[tid])
                    terms_out[term] = entry
                if terms_out:
                    tv[fname] = {
                        "field_statistics": {
                            "sum_doc_freq": int(f.df.sum()),
                            "doc_count": f.field_doc_count,
                            "sum_ttf": int(f.total_term_freq.sum())},
                        "terms": terms_out}
            return {"_index": concrete, "_id": id, "_version": 1,
                    "found": True, "took": 0, "term_vectors": tv}
        return {"_index": concrete, "_id": id, "found": False}

    def h_termvectors(self, params, body, index, id=None):
        self._deny_if_restricted(index)
        """Term vectors of one doc's text fields (reference:
        ``RestTermVectorsAction``): term freq, positions + re-analyzed
        offsets, and (with ``term_statistics=true``) df/ttf."""
        spec = _json_body(body) if body else {}
        if id is None:
            id = spec.get("_id") or spec.get("id")
        return self._termvectors_one(params, spec, index, id)

    def h_mtermvectors(self, params, body, index=None):
        """Multi term-vectors (reference: ``RestMultiTermVectorsAction``):
        per-item payloads with per-item error entries."""
        spec = _json_body(body) if body else {}
        items = spec.get("docs")
        if items is None and spec.get("ids"):
            items = [{"_id": i} for i in spec["ids"]]
        if items is None and params.get("ids"):
            items = [{"_id": i} for i in params["ids"].split(",")]
        if not items:
            from ..common.errors import ActionRequestValidationError
            raise ActionRequestValidationError(
                "multi term vectors: no documents requested")
        out = []
        for item in items or []:
            bad = [k for k in item
                   if k not in ("_index", "_id", "id", "_routing",
                                "routing", "fields", "term_statistics",
                                "field_statistics", "offsets",
                                "positions", "payloads", "doc",
                                "version", "version_type", "filter")]
            if bad:
                raise ParsingError(
                    f"unknown parameter [{bad[0]}] in request body")
            idx = item.get("_index") or index
            did = item.get("_id") or item.get("id")
            try:
                if idx is None:
                    from ..common.errors import \
                        ActionRequestValidationError
                    raise ActionRequestValidationError(
                        "index is missing")
                r = self._termvectors_one(params, item, idx, did)
                out.append(r)
            except ElasticsearchError as e:
                status, payload = _error_payload(e)
                out.append({"_index": idx, "_id": did,
                            "error": payload["error"]})
        return {"docs": out}

    def h_reindex(self, params, body):
        """Copy documents between indices (reference: ``modules/reindex``
        ``TransportReindexAction`` — scroll source + bulk dest; here a
        snapshot scan + batched writes, cancellable between batches, and
        async under ``wait_for_completion=false`` like the reference's
        task-running reindexer)."""
        payload = _json_body(body)
        src_spec = payload.get("source") or {}
        dst_spec = payload.get("dest") or {}
        if not src_spec.get("index"):
            raise IllegalArgumentError("[source.index] is required")
        dst_name = dst_spec.get("index")
        if not dst_name:
            raise IllegalArgumentError("[dest.index] is required")
        src_names = self.indices.resolve(src_spec.get("index"))
        query = src_spec.get("query")
        refresh = params.get("refresh") in ("true", "")
        dst = self._get_or_autocreate(dst_name)
        task = self.current_task()
        task.cancellable = True
        task.description = (f"reindex from [{src_spec.get('index')}] to "
                            f"[{dst_name}]")

        def run():
            t0 = time.time()
            created = updated = total = 0
            for sname in src_names:
                svc = self.indices.get(sname)
                svc.refresh()
                searcher = svc.searcher()
                res = searcher.search({
                    "query": query or {"match_all": {}},
                    "size": self.SCROLL_MAX_DOCS})
                for i, h in enumerate(res.hits):
                    if i % 100 == 0:
                        task.check_cancelled()
                    total += 1
                    r = dst.index_doc(h.doc_id, h.source)
                    if r.created:
                        created += 1
                    else:
                        updated += 1
                    task.status.update(total=total, created=created,
                                       updated=updated)
            if refresh:
                dst.refresh()
            return {"took": int((time.time() - t0) * 1000),
                    "timed_out": False, "total": total, "created": created,
                    "updated": updated, "deleted": 0, "batches": 1,
                    "noops": 0, "version_conflicts": 0, "failures": []}

        if params.get("wait_for_completion") == "false":
            self.task_manager.run_async(task, run)
            return {"task": task.tid}
        return run()

    # ------------------------------------------------------------------
    # task management (reference: tasks/TaskManager.java:76,
    # TaskCancellationService.java:47, RestListTasksAction)
    # ------------------------------------------------------------------

    def current_task(self):
        return getattr(self._req_task, "task", None)

    def _node_task_entry(self, tasks: Dict[str, dict]) -> dict:
        return {"name": self.node_name,
                "transport_address": "127.0.0.1:9300",
                "host": "127.0.0.1", "ip": "127.0.0.1:9300",
                "roles": ["data", "ingest", "master"],
                "tasks": tasks}

    # ------------------------------------------------------------------
    # stored scripts (reference: ``script/ScriptService.java`` cluster-
    # state stored scripts + RestPutStoredScriptAction)
    # ------------------------------------------------------------------

    #: script languages this engine compiles (expression arithmetic +
    #: mustache templates; "painless" sources are accepted for storage —
    #: execution supports the expression-compatible subset)
    SCRIPT_LANGS = ("painless", "expression", "mustache")

    def _render_search_template(self, spec: dict) -> dict:
        """Mustache template + params → a concrete search body
        (``MustacheScriptEngine`` — utils/mustache.py is the engine)."""
        from ..utils.mustache import render_mustache
        source = spec.get("source")
        if source is None and spec.get("id"):
            stored = self.stored_scripts.get(spec["id"])
            if stored is None:
                raise ResourceNotFoundError(
                    f"unable to find script [{spec['id']}]")
            if stored.get("lang") not in (None, "mustache"):
                raise IllegalArgumentError(
                    f"search template expects lang [mustache], but "
                    f"stored script [{spec['id']}] is "
                    f"[{stored.get('lang')}]")
            source = stored["source"]
        if source is None:
            raise IllegalArgumentError(
                "template is missing: specify [source] or [id]")
        if isinstance(source, dict):
            # object-form templates render through their JSON text
            source = json.dumps(source)
        rendered = render_mustache(str(source), spec.get("params") or {})
        try:
            return json.loads(rendered)
        except ValueError as e:
            raise IllegalArgumentError(
                f"Failed to parse rendered search template: {e}")

    def h_search_template(self, params, body, index=None):
        spec = _json_body(body)
        search_body = self._render_search_template(spec)
        if params.get("explain") in ("true", ""):
            search_body["explain"] = True
        if params.get("profile") in ("true", ""):
            search_body["profile"] = True
        return self.h_search(params, json.dumps(search_body).encode(),
                             index)

    def h_render_template(self, params, body, id=None):
        spec = _json_body(body)
        if id is not None and not spec.get("id"):
            spec = dict(spec, id=id)
        return {"template_output": self._render_search_template(spec)}

    def h_msearch_template(self, params, body, index=None):
        """NDJSON header/template pairs: render each template line to a
        concrete search body, then delegate the whole batch to
        h_msearch so header-param forwarding, request-level error
        semantics, and per-item failure shaping stay in ONE place
        (``RestMultiSearchTemplateAction`` likewise converts to a
        multi-search request)."""
        lines = [ln for ln in (body or b"").split(b"\n") if ln.strip()]
        if len(lines) % 2:
            raise IllegalArgumentError(
                "msearch template must have an even number of lines")
        out_lines: List[bytes] = []
        render_errors: Dict[int, dict] = {}
        n_items = 0
        for i in range(0, len(lines), 2):
            slot = n_items
            n_items += 1
            try:
                spec = json.loads(lines[i + 1])
                rendered = self._render_search_template(spec)
            except Exception as e:   # noqa: BLE001 — render fails the
                status, payload = _error_payload(e)   # ITEM, not request
                render_errors[slot] = dict(payload, status=status)
                continue
            out_lines.append(lines[i])
            out_lines.append(json.dumps(rendered).encode())
        if out_lines:
            result = self.h_msearch(params,
                                    b"\n".join(out_lines) + b"\n", index)
        else:
            result = {"took": 0, "responses": []}
        # splice render failures back into their original positions
        if render_errors:
            merged: List[dict] = []
            executed = iter(result["responses"])
            for slot in range(n_items):
                merged.append(render_errors.get(slot)
                              or next(executed))
            result = dict(result, responses=merged)
        return result

    def h_put_script(self, params, body, id):
        spec = _json_body(body)
        script = spec.get("script")
        if not isinstance(script, dict) or "source" not in script:
            raise IllegalArgumentError("must specify [script] with [source]")
        lang = script.get("lang", "painless")
        if lang not in self.SCRIPT_LANGS:
            raise IllegalArgumentError(
                f"unable to put stored script with unsupported lang "
                f"[{lang}]")
        self.stored_scripts[id] = {
            "lang": lang, "source": script["source"],
            "options": script.get("options", {})}
        return {"acknowledged": True}

    def h_get_script(self, params, body, id):
        s = self.stored_scripts.get(id)
        if s is None:
            return 404, {"_id": id, "found": False}
        return {"_id": id, "found": True, "script": s}

    def h_delete_script(self, params, body, id):
        if id not in self.stored_scripts:
            raise ResourceNotFoundError(f"stored script [{id}] not found")
        del self.stored_scripts[id]
        return {"acknowledged": True}

    def h_script_context(self, params, body):
        """GET /_script_context — the ~40 ScriptContexts of
        ``script/ScriptService.java:289``, reduced to the contexts this
        engine actually compiles for."""
        contexts = []
        for name, ret in [("score", "double"), ("filter", "boolean"),
                          ("aggs", "Object"), ("field", "Object"),
                          ("ingest", "void"), ("update", "void"),
                          ("template", "String"),
                          ("runtime_fields", "void"),
                          ("number_sort", "double"),
                          ("string_sort", "String"),
                          ("similarity", "double"),
                          ("aggregation_selector", "boolean")]:
            contexts.append({"name": name, "methods": [
                {"name": "execute", "return_type": ret, "params": []},
                {"name": "getParams", "return_type":
                    "java.util.Map", "params": []}]})
        return {"contexts": contexts}

    def h_script_language(self, params, body):
        return {
            "types_allowed": ["inline", "stored"],
            "language_contexts": [
                {"language": lang,
                 "contexts": sorted(c["name"] for c in
                                    self.h_script_context({}, b"")
                                    ["contexts"])}
                for lang in self.SCRIPT_LANGS],
        }

    def resolve_script(self, script):
        """Inline-or-stored script spec → dict with a ``source`` (the
        reference resolves ``{"id": ...}`` against cluster-state stored
        scripts at compile time)."""
        if isinstance(script, dict) and "id" in script and \
                "source" not in script:
            stored = self.stored_scripts.get(script["id"])
            if stored is None:
                raise ResourceNotFoundError(
                    f"unable to find script [{script['id']}]")
            out = dict(stored)
            if "params" in script:
                out["params"] = script["params"]
            return out
        return script

    # ------------------------------------------------------------------
    # search_shards (reference: RestClusterSearchShardsAction)
    # ------------------------------------------------------------------

    def h_search_shards(self, params, body, index=None):
        expression = index or params.get("index") or "_all"
        names = self.indices.resolve(expression)
        requested = [p for p in str(expression).split(",") if p]
        indices_doc: Dict[str, dict] = {}
        shards = []
        import fnmatch
        for n in sorted(names):
            svc = self.indices.indices[n]
            # aliases referenced by THIS request (by name or wildcard)
            # that point at the index
            alias_hits = set()
            for part in requested:
                if part in svc.aliases:
                    alias_hits.add(part)
                elif "*" in part or "?" in part:
                    alias_hits.update(
                        a for a in svc.aliases
                        if fnmatch.fnmatchcase(a, part))
            entry: Dict[str, Any] = {}
            if alias_hits:
                entry["aliases"] = sorted(alias_hits)
                specs = [(svc.aliases[a] or {}) for a in
                         sorted(alias_hits)]
                filters = [s.get("filter") for s in specs]
                # an unfiltered alias grants unfiltered access: any alias
                # without a filter drops filtering entirely
                if all(filters):
                    if len(filters) == 1:
                        entry["filter"] = _render_filter(filters[0])
                    else:
                        entry["filter"] = {"bool": {
                            "should": [_render_filter(f)
                                       for f in filters],
                            "adjust_pure_negative": True, "boost": 1.0}}
            indices_doc[n] = entry
            for sid in range(svc.num_shards):
                shards.append([{
                    "index": n, "shard": sid, "primary": True,
                    "state": "STARTED", "node": self.node_id,
                    "relocating_node": None,
                    "allocation_id": {"id": f"{n}-{sid}"}}])
        return {"nodes": {self.node_id: {
                    "name": self.node_name,
                    "transport_address": "127.0.0.1:9300"}},
                "indices": indices_doc,
                "shards": shards}

    # ------------------------------------------------------------------
    # rank evaluation (reference: ``modules/rank-eval/RankEvalSpec.java``)
    # ------------------------------------------------------------------

    def h_rank_eval(self, params, body, index=None):
        import math
        spec = _json_body(body)
        expression = index or params.get("index")
        templates = {t["id"]: (t.get("template") or {}).get("source")
                     for t in spec.get("templates") or []}
        (metric_name, metric_opts), = (spec.get("metric")
                                       or {"precision": {}}).items()
        t0 = time.time()
        details: Dict[str, dict] = {}
        failures: Dict[str, dict] = {}
        scores: List[float] = []
        for req_spec in spec.get("requests") or []:
            qid = req_spec.get("id")
            try:
                request = req_spec.get("request")
                if request is None and req_spec.get("template_id"):
                    from ..utils.mustache import render_mustache
                    tpl = templates.get(req_spec["template_id"])
                    if isinstance(tpl, dict):
                        tpl = json.dumps(tpl)
                    request = json.loads(render_mustache(
                        tpl or "{}", req_spec.get("params") or {}))
                request = dict(request or {})
                if "aggs" in request or "aggregations" in request:
                    raise IllegalArgumentError(
                        "Query in rated requests should not contain "
                        "aggregations.")
                if "suggest" in request:
                    raise IllegalArgumentError(
                        "Query in rated requests should not contain a "
                        "suggest section.")
                if "highlight" in request:
                    raise IllegalArgumentError(
                        "Query in rated requests should not contain a "
                        "highlighter section.")
                if "explain" in request:
                    raise IllegalArgumentError(
                        "Query in rated requests should not use "
                        "explain.")
                if "profile" in request:
                    raise IllegalArgumentError(
                        "Query in rated requests should not use "
                        "profile.")
                k = int(metric_opts.get("k", 10))
                request.setdefault("size", k)
                out = self._search_indices(
                    self.indices.resolve(expression), request,
                    record_stats=False)
                hits = out["hits"]["hits"]
                ratings = {(r["_index"], str(r["_id"])): int(r["rating"])
                           for r in req_spec.get("ratings") or []}
                rated_hits = []
                unrated = []
                ranks: List[Optional[int]] = []
                for h in hits:
                    key = (h["_index"], str(h["_id"]))
                    entry = {"hit": {"_index": h["_index"],
                                     "_id": h["_id"],
                                     "_score": h.get("_score")}}
                    if key in ratings:
                        entry["rating"] = ratings[key]
                        ranks.append(ratings[key])
                    else:
                        unrated.append({"_index": h["_index"],
                                        "_id": h["_id"]})
                        ranks.append(None)
                    rated_hits.append(entry)
                score, mdetails = _rank_metric(
                    metric_name, metric_opts, ranks, ratings)
                scores.append(score)
                details[qid] = {
                    "metric_score": score,
                    "unrated_docs": unrated,
                    "hits": rated_hits,
                    "metric_details": {metric_name: mdetails},
                }
            except IllegalArgumentError:
                raise
            except Exception as e:   # noqa: BLE001 — per-request failure
                _status, payload = _error_payload(e)
                failures[qid] = payload.get("error", {
                    "type": "exception", "reason": str(e)})
        doc = {
            "took": int((time.time() - t0) * 1000),
            "metric_score": (sum(scores) / len(scores)) if scores else 0.0,
            "details": details,
            "failures": failures,
        }
        return doc

    def h_tasks(self, params, body):
        group_by = params.get("group_by", "nodes")
        actions = params.get("actions")
        actions = actions.split(",") if actions else None
        # ?detailed adds the per-task resource ledger (resource_stats:
        # cpu/device ms, transfer bytes, docs scanned — the reference's
        # task resource tracking surface)
        detailed = _flag(params, "detailed")
        tasks = self.task_manager.list(actions=actions)
        docs = {t.tid: t.to_dict(detailed=detailed) for t in tasks}
        if group_by == "none":
            return {"tasks": list(docs.values())}
        if group_by == "parents":
            top: Dict[str, dict] = {}
            for tid, d in docs.items():
                if d.get("parent_task_id") in docs:
                    parent = top.setdefault(
                        d["parent_task_id"], docs[d["parent_task_id"]])
                    parent.setdefault("children", []).append(d)
                else:
                    top.setdefault(tid, d)
            return {"tasks": top}
        return {"nodes": {self.node_id: self._node_task_entry(docs)}}

    def h_task_get(self, params, body, task_id):
        node, _, raw = task_id.partition(":")
        if not raw or node != self.node_id:
            raise ResourceNotFoundError(
                f"task [{task_id}] belongs to the node [{node}] which "
                f"isn't part of the cluster and there is no record of "
                f"the task")
        try:
            tid = int(raw)
        except ValueError:
            raise IllegalArgumentError(f"malformed task id {task_id}")
        t = self.task_manager.get(tid)
        if t is None:
            raise ResourceNotFoundError(
                f"task [{task_id}] isn't running and hasn't stored its "
                f"results")
        if _flag(params, "wait_for_completion"):
            from ..common.settings import parse_time_millis
            t.completed.wait(
                parse_time_millis(params.get("timeout", "30s")) / 1e3)
        doc = {"completed": not t.running, "task": t.to_dict(detailed=True)}
        if t.result is not None:
            doc["response"] = t.result
        if t.error is not None:
            doc["error"] = t.error
        return doc

    def h_tasks_cancel(self, params, body, task_id=None):
        reason = "by user request"
        if task_id is not None:
            node, _, raw = task_id.partition(":")
            if node != self.node_id:
                raise ResourceNotFoundError(
                    f"task [{task_id}] isn't running and hasn't stored "
                    f"its results")
            try:
                tid_num = int(raw)
            except ValueError:
                raise IllegalArgumentError(
                    f"malformed task id {task_id}")
            t = self.task_manager.get(tid_num)
            if t is None:
                raise ResourceNotFoundError(
                    f"task [{task_id}] isn't running and hasn't stored "
                    f"its results")
            self.task_manager.cancel(t, reason)
            hit = [t] if t.cancellable else []
        else:
            actions = params.get("actions")
            hit = self.task_manager.cancel_matching(
                actions=actions.split(",") if actions else None,
                reason=reason)
        nodes = {}
        if hit:
            nodes[self.node_id] = self._node_task_entry(
                {t.tid: t.to_dict() for t in hit})
        return {"nodes": nodes, "node_failures": []} if not hit else \
            {"nodes": nodes}

    def h_update_by_query(self, params, body, index):
        b = _json_body(body)
        self._rewrite_terms_lookup(b)
        query = b.get("query") or {"match_all": {}}
        script = b.get("script")
        names = self.indices.resolve(index)
        task = self.current_task()
        task.cancellable = True
        task.description = f"update-by-query [{index}]"

        def run():
            t0 = time.time()
            updated = 0
            for n in names:
                svc = self.indices.indices[n]
                for i, doc_id in enumerate(self._matched_ids(svc, query)):
                    if i % 100 == 0:
                        task.check_cancelled()
                    g = svc.get_doc(doc_id)
                    if not g.found:
                        continue
                    src = dict(g.source or {})
                    if script:
                        source = (script.get("source")
                                  if isinstance(script, dict) else script)
                        src = _apply_update_script(
                            src, source, script.get("params", {})
                            if isinstance(script, dict) else {})
                    svc.index_doc(doc_id, src)
                    updated += 1
                    task.status.update(total=updated, updated=updated)
                svc.refresh()
            return {"took": int((time.time() - t0) * 1000),
                    "timed_out": False, "updated": updated,
                    "total": updated, "failures": [], "batches": 1,
                    "version_conflicts": 0, "noops": 0,
                    "retries": {"bulk": 0, "search": 0}}

        if params.get("wait_for_completion") == "false":
            self.task_manager.run_async(task, run)
            return {"task": task.tid}
        return run()

    # ------------------------------------------------------------------
    # analyze / field caps
    # ------------------------------------------------------------------

    @staticmethod
    def _analyze_token_dicts(tokens):
        return [{"token": tok.term, "start_offset": tok.start_offset,
                 "end_offset": tok.end_offset, "type": "<ALPHANUM>",
                 "position": tok.position} for tok in tokens]

    def h_analyze(self, params, body, index=None):
        from ..index.analysis import (AnalysisRegistry, BUILTIN_ANALYZERS,
                                      TOKENIZERS)
        b = _json_body(body)
        text = b.get("text")
        if text is None:
            raise IllegalArgumentError("[_analyze] requires [text]")
        texts = text if isinstance(text, list) else [text]
        explain = b.get("explain") in (True, "true")
        tokenizer_spec = b.get("tokenizer")
        filter_specs = b.get("filter") or b.get("token_filters") or []

        analyzer = None
        analyzer_name = None
        tokenizer_fn = None
        tokenizer_name = None
        filters = []
        if tokenizer_spec is not None and "analyzer" not in b:
            # bare tokenizer (+ optional inline/named filters): the
            # custom-at-request-time form of _analyze
            if isinstance(tokenizer_spec, str):
                tokenizer_name = tokenizer_spec
                tokenizer_fn = TOKENIZERS.get(tokenizer_spec)
                if tokenizer_fn is None:
                    raise IllegalArgumentError(
                        f"failed to find global tokenizer under "
                        f"[{tokenizer_spec}]")
            else:
                tokenizer_name = tokenizer_spec.get(
                    "type", "_anonymous_tokenizer")
                tokenizer_fn = AnalysisRegistry._build_tokenizer(
                    tokenizer_name, tokenizer_spec)
            for i, fs in enumerate(filter_specs):
                if isinstance(fs, str):
                    fname = fs
                    fspec = {"type": fs}
                else:
                    fname = fs.get("type", f"_anonymous_tokenfilter_{i}")
                    fspec = fs
                filters.append((fname,
                                AnalysisRegistry._build_token_filter(
                                    fname, fspec)))
        elif index is not None and b.get("field"):
            svc = self.indices.get(index)
            ft = svc.mapper.field_type(b["field"])
            analyzer = getattr(ft, "analyzer", None)
            if analyzer is None:
                analyzer = BUILTIN_ANALYZERS["standard"]
            analyzer_name = analyzer.name
        else:
            analyzer_name = b.get("analyzer", "standard")
            analyzer = BUILTIN_ANALYZERS.get(analyzer_name)
            if analyzer is None and index is not None:
                svc = self.indices.get(index)
                analyzer = svc.mapper.analysis.get(analyzer_name)
            if analyzer is None:
                raise IllegalArgumentError(
                    f"failed to find global analyzer [{analyzer_name}]")

        max_tokens = None
        if index is not None:
            svc = self.indices.indices.get(index)
            if svc is not None:
                try:
                    max_tokens = int(svc.settings.get(
                        "index.analyze.max_token_count", 10000))
                except (TypeError, ValueError):
                    max_tokens = 10000

        def _check_limit(n):
            if max_tokens is not None and n > max_tokens:
                raise IllegalArgumentError(
                    f"The number of tokens produced by calling _analyze "
                    f"has exceeded the allowed maximum of [{max_tokens}]."
                    f" This limit can be set by changing the "
                    f"[index.analyze.max_token_count] index level "
                    f"setting.")

        if tokenizer_fn is not None:
            tokenized = []
            for t in texts:
                tokenized.extend(tokenizer_fn(str(t)))
            _check_limit(len(tokenized))
            stages = []             # (filter name, tokens after it)
            cur = tokenized
            for fname, fn in filters:
                cur = fn(cur)
                _check_limit(len(cur))
                stages.append((fname, list(cur)))
            if explain:
                detail = {"custom_analyzer": True,
                          "tokenizer": {
                              "name": tokenizer_name,
                              "tokens": self._analyze_token_dicts(
                                  tokenized)}}
                if stages:
                    detail["tokenfilters"] = [
                        {"name": fname,
                         "tokens": self._analyze_token_dicts(toks)}
                        for fname, toks in stages]
                return {"detail": detail}
            return {"tokens": self._analyze_token_dicts(cur)}

        tokens = []
        for t in texts:
            tokens.extend(analyzer.analyze(str(t)))
        _check_limit(len(tokens))
        if explain:
            return {"detail": {
                "custom_analyzer": False,
                "analyzer": {"name": analyzer_name,
                             "tokens": self._analyze_token_dicts(
                                 tokens)}}}
        return {"tokens": self._analyze_token_dicts(tokens)}

    def h_field_caps(self, params, body, index=None):
        names = self.indices.resolve(index)
        b = _json_body(body)
        patterns = (params.get("fields") or b.get("fields") or "*")
        if isinstance(patterns, str):
            patterns = patterns.split(",")
        index_filter = b.get("index_filter")
        if index_filter is not None:
            from ..search.query_dsl import parse_query
            # an unparseable filter fails the whole REQUEST (400), like
            # the reference — only per-index evaluation verdicts drop
            # individual indices below
            parse_query(index_filter)

            from ..common.errors import remote_status as _err_status

            kept = []
            for n in names:
                svc = self.indices.indices[n]
                try:
                    svc.refresh()        # filter evaluates live contents
                    if svc.cluster_hooks is not None:
                        # routed: count cluster-wide (front engines hold
                        # only locally-primaried shards)
                        docs = int(svc.count(
                            {"query": {"match_all": {}}}))
                    else:
                        docs = sum(sh.doc_count for sh in svc.shards)
                    if docs == 0 or svc.count(
                            {"query": index_filter}) > 0:
                        kept.append(n)   # empty shard → can_match true
                except Exception as e:   # noqa: BLE001
                    # a 4xx (unmapped field) is a real no-match verdict;
                    # anything else (transient RPC under cluster load)
                    # must KEEP the index — silently dropping caps is
                    # worse than an extra entry
                    if not (400 <= _err_status(e) < 500):
                        kept.append(n)
            names = kept
        import fnmatch
        from ..index.mapping import (DateFieldType, NestedFieldType,
                                     ObjectFieldType)
        # (field, type) → caps + the indices carrying that type
        per_type_idx: Dict[str, Dict[str, list]] = {}
        fields: Dict[str, Dict[str, dict]] = {}
        mapped_in: Dict[str, set] = {}
        for n in names:
            svc = self.indices.indices[n]
            for fname in svc.mapper.field_names():
                if not any(fnmatch.fnmatchcase(fname, p)
                           for p in patterns):
                    continue
                mapped_in.setdefault(fname, set()).add(n)
                ft = svc.mapper.field_type(fname)
                tname = getattr(ft, "type_name", "object")
                if isinstance(ft, DateFieldType) and ft.nanos:
                    tname = "date_nanos"
                is_obj = isinstance(ft, (ObjectFieldType, NestedFieldType))
                unsearchable = is_obj or (
                    (getattr(ft, "params", None) or {}).get("index")
                    is False)
                no_dv = is_obj or (
                    (getattr(ft, "params", None) or {}).get("doc_values")
                    is False) or not getattr(ft, "has_doc_values", False)
                caps = fields.setdefault(fname, {}).setdefault(tname, {
                    "type": tname, "metadata_field": False,
                    "searchable": True, "aggregatable": True,
                    "_search_in": [], "_nosearch_in": [],
                    "_agg_in": [], "_noagg_in": []})
                (caps["_nosearch_in"] if unsearchable
                 else caps["_search_in"]).append(n)
                (caps["_noagg_in"] if no_dv
                 else caps["_agg_in"]).append(n)
                meta = (ft.params or {}).get("meta") \
                    if hasattr(ft, "params") else None
                if meta:
                    m = caps.setdefault("meta", {})
                    for mk, mv in meta.items():
                        m.setdefault(mk, set()).add(str(mv))
                per_type_idx.setdefault(fname, {}).setdefault(
                    tname, []).append(n)

        # finalize searchability: true iff searchable in EVERY index
        # carrying the type; mixed → non_searchable_indices
        for fname, types in fields.items():
            for tname, caps in types.items():
                nosearch = caps.pop("_nosearch_in", [])
                search = caps.pop("_search_in", [])
                caps["searchable"] = not nosearch
                if nosearch and search:
                    caps["non_searchable_indices"] = sorted(nosearch)
                noagg = caps.pop("_noagg_in", [])
                agg = caps.pop("_agg_in", [])
                caps["aggregatable"] = not noagg
                if noagg and agg:
                    caps["non_aggregatable_indices"] = sorted(noagg)
                if "meta" in caps:
                    caps["meta"] = {k: sorted(v)
                                    for k, v in caps["meta"].items()}
        # a type entry lists its indices when the field maps to MULTIPLE
        # types across the queried indices (FieldCapabilities.indices)
        for fname, types in fields.items():
            for tname, caps in types.items():
                idxs = per_type_idx.get(fname, {}).get(tname, [])
                if len(types) > 1:
                    caps["indices"] = sorted(idxs)
            unmapped = [n for n in names
                        if n not in mapped_in.get(fname, set())]
            if _flag(params, "include_unmapped") and unmapped and types:
                missing = sorted(unmapped)
                if missing:
                    types["unmapped"] = {
                        "type": "unmapped", "metadata_field": False,
                        "searchable": False, "aggregatable": False,
                        "indices": missing}
                    for tname2, caps2 in list(types.items()):
                        if tname2 != "unmapped":
                            caps2.setdefault(
                                "indices",
                                sorted(per_type_idx.get(fname, {}).get(
                                    tname2, [])))
        return {"indices": sorted(names), "fields": fields}


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _rank_metric(name: str, opts: dict, ranks, ratings) -> Tuple[float,
                                                                 dict]:
    """IR metric over one ranked result list (reference:
    ``modules/rank-eval``: PrecisionAtK, RecallAtK, MeanReciprocalRank,
    DiscountedCumulativeGain, ExpectedReciprocalRank). ``ranks`` is the
    per-position rating (None = unlabeled); ``ratings`` the full rated
    set for recall denominators."""
    import math
    threshold = int(opts.get("relevant_rating_threshold", 1))
    if name == "precision":
        ignore_unlabeled = bool(opts.get("ignore_unlabeled"))
        retrieved = relevant = 0
        for r in ranks:
            if r is None and ignore_unlabeled:
                continue
            retrieved += 1
            if r is not None and r >= threshold:
                relevant += 1
        score = relevant / retrieved if retrieved else 0.0
        return score, {"relevant_docs_retrieved": relevant,
                       "docs_retrieved": retrieved}
    if name == "recall":
        relevant_retrieved = sum(1 for r in ranks
                                 if r is not None and r >= threshold)
        total_relevant = sum(1 for r in ratings.values()
                             if r >= threshold)
        score = relevant_retrieved / total_relevant \
            if total_relevant else 0.0
        return score, {"relevant_docs_retrieved": relevant_retrieved,
                       "relevant_docs": total_relevant}
    if name == "mean_reciprocal_rank":
        first = -1
        for i, r in enumerate(ranks):
            if r is not None and r >= threshold:
                first = i + 1
                break
        score = 1.0 / first if first > 0 else 0.0
        return score, {"first_relevant": first}
    if name == "dcg":
        def dcg_of(gains):
            return sum((2 ** g - 1) / math.log2(i + 2)
                       for i, g in enumerate(gains))
        gains = [r or 0 for r in ranks]
        score = dcg_of(gains)
        details = {"dcg": score}
        if opts.get("normalize"):
            ideal = dcg_of(sorted((r for r in ratings.values()),
                                  reverse=True)[: len(ranks)])
            details["ideal_dcg"] = ideal
            score = score / ideal if ideal else 0.0
            details["normalized_dcg"] = score
        return score, details
    if name == "expected_reciprocal_rank":
        max_rel = int(opts.get("maximum_relevance", 4))
        denom = 2 ** max_rel
        p_look = 1.0
        err = 0.0
        for i, r in enumerate(ranks):
            rel = (2 ** (r or 0) - 1) / denom
            err += p_look * rel / (i + 1)
            p_look *= (1 - rel)
        return err, {"unrated_docs": sum(1 for r in ranks if r is None)}
    raise IllegalArgumentError(f"unknown rank-eval metric [{name}]")


def _int_or_none(v):
    if v == "":
        return None
    return int(v) if v is not None else None


def _deep_merge(base: dict, patch: dict) -> dict:
    for k, v in patch.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            base[k] = _deep_merge(dict(base[k]), v)
        else:
            base[k] = v
    return base


def _apply_update_script(src: dict, source: str, params: dict,
                         ctx_extra: Optional[dict] = None) -> dict:
    """Update-context scripts through the sandboxed Painless-lite engine
    (``script/painless_lite.py`` — statements, loops, method calls on
    ``ctx._source`` values; the reference's ``modules/lang-painless``).
    ``ctx_extra`` carries extra ctx fields (e.g. ``op``) whose mutations
    the caller reads back."""
    from ..script.service import DEFAULT as _scripts
    ctx = {"_source": src}
    if ctx_extra is not None:
        ctx.update(ctx_extra)
    _scripts.run_update(source, ctx, params)
    if ctx_extra is not None:
        for k in list(ctx_extra):
            ctx_extra[k] = ctx.get(k)
    return src


def _sort_is_score(sort_spec) -> bool:
    if isinstance(sort_spec, (str, dict)):
        sort_spec = [sort_spec]
    first = sort_spec[0] if sort_spec else "_score"
    return first == "_score" or (isinstance(first, dict) and
                                 "_score" in first)


def _merge_suggest(suggests: List[Dict[str, list]]) -> Dict[str, list]:
    """Merge suggest sections from several shards/indices/nodes: per
    suggester, per token entry (matched by offset), options dedupe by text
    keeping the best score and re-rank (score desc, freq desc)."""
    merged: Dict[str, list] = {}
    for s in suggests:
        for sname, entries in s.items():
            if sname not in merged:
                merged[sname] = [dict(e, options=list(e["options"]))
                                 for e in entries]
                continue
            by_offset = {e["offset"]: e for e in merged[sname]}
            for e in entries:
                tgt = by_offset.get(e["offset"])
                if tgt is None:
                    merged[sname].append(dict(e,
                                              options=list(e["options"])))
                else:
                    tgt["options"] = tgt["options"] + list(e["options"])
    for entries in merged.values():
        for e in entries:
            best: Dict[str, dict] = {}
            for o in e["options"]:
                cur = best.get(o["text"])
                score = o.get("score", o.get("_score", 0.0))
                if cur is None or score > cur.get("score",
                                                  cur.get("_score", 0.0)):
                    best[o["text"]] = o
            e["options"] = sorted(
                best.values(),
                key=lambda o: (-o.get("score", o.get("_score", 0.0)),
                               -o.get("freq", 0), o["text"]))
    return merged


def _sort_key_tuple(h: ShardHit):
    out = []
    for v in h.sort_values or []:
        if v is None:
            out.append((1, 0))
        elif isinstance(v, str):
            out.append((0, v))
        else:
            out.append((0, v))
    return tuple(out)


#: stats leaves that combine by MAX, not sum (sentinel/high-watermark)
_MERGE_MAX_KEYS = {"max_unsafe_auto_id_timestamp", "max_seq_no",
                   "max_batch"}


def _merge_numeric_tree(dst: dict, src: dict) -> None:
    """Recursively sum numeric leaves of ``src`` into ``dst`` (stats
    aggregation across indices/shards); non-numeric leaves copy through."""
    for k, v in src.items():
        if isinstance(v, dict):
            _merge_numeric_tree(dst.setdefault(k, {}), v)
        elif isinstance(v, bool):
            dst[k] = dst.get(k, False) or v
        elif isinstance(v, (int, float)):
            if k in _MERGE_MAX_KEYS:
                dst[k] = max(dst.get(k, v), v)
            else:
                dst[k] = dst.get(k, 0) + v
        else:
            dst.setdefault(k, v)


# ---------------------------------------------------------------------------
# filter_path response filtering (reference: XContentMapValues.filter /
# rest FilterPath) — dot paths with * and ** wildcards, "-" for excludes
# ---------------------------------------------------------------------------

def _fp_match(key: str, pat: str) -> bool:
    import fnmatch
    return fnmatch.fnmatchcase(str(key), pat)


def _fp_include(obj, patterns):
    if not isinstance(obj, dict):
        return obj
    out = {}
    for k, v in obj.items():
        keep_all = False
        sub = []
        for p in patterns:
            if not p:
                continue
            seg = p[0]
            if seg == "**":
                if len(p) == 1:             # trailing ** keeps the subtree
                    keep_all = True
                    continue
                sub.append(p)               # ** can keep matching deeper
                rest = p[1:]
                if rest and _fp_match(k, rest[0]):
                    if len(rest) == 1:
                        keep_all = True
                    else:
                        sub.append(rest[1:])
            elif _fp_match(k, seg):
                if len(p) == 1:
                    keep_all = True
                else:
                    sub.append(p[1:])
        if keep_all:
            out[k] = v
        elif sub:
            if isinstance(v, dict):
                f = _fp_include(v, sub)
                if f:
                    out[k] = f
            elif isinstance(v, list):
                fl = []
                for item in v:
                    if isinstance(item, dict):
                        fi = _fp_include(item, sub)
                        if fi:
                            fl.append(fi)
                if fl:
                    out[k] = fl
    return out


def _fp_exclude(obj, patterns):
    if not isinstance(obj, dict):
        return obj
    out = {}
    for k, v in obj.items():
        drop = False
        sub = []
        for p in patterns:
            if not p:
                continue
            seg = p[0]
            if seg == "**":
                if len(p) == 1:             # trailing ** drops the subtree
                    drop = True
                    continue
                sub.append(p)
                rest = p[1:]
                if rest and _fp_match(k, rest[0]):
                    if len(rest) == 1:
                        drop = True
                    else:
                        sub.append(rest[1:])
            elif _fp_match(k, seg):
                if len(p) == 1:
                    drop = True
                else:
                    sub.append(p[1:])
        if drop:
            continue
        if sub and isinstance(v, dict):
            out[k] = _fp_exclude(v, sub)
        elif sub and isinstance(v, list):
            out[k] = [_fp_exclude(i, sub) if isinstance(i, dict) else i
                      for i in v]
        else:
            out[k] = v
    return out


def _apply_filter_path(payload: dict, filter_path: str) -> dict:
    includes, excludes = [], []
    for raw in str(filter_path).split(","):
        raw = raw.strip()
        if not raw:
            continue
        if raw.startswith("-"):
            excludes.append(raw[1:].split("."))
        else:
            includes.append(raw.split("."))
    out = payload
    if includes:
        out = _fp_include(out, includes)
    if excludes:
        out = _fp_exclude(out, excludes)
    return out


from ..search.shard_search import _as_list_ as _as_list  # noqa: E402


def _human_bytes(n) -> str:
    """cat-style byte sizes (ByteSizeValue): 88 → '88b', 4608 → '4.5kb'."""
    n = float(n)
    for unit, div in (("tb", 1 << 40), ("gb", 1 << 30), ("mb", 1 << 20),
                      ("kb", 1 << 10)):
        if n >= div:
            v = n / div
            return f"{v:.1f}{unit}".replace(".0" + unit, unit)
    return f"{int(n)}b"


def format_date_millis_cat(ms) -> str:
    from ..index.mapping import format_date_millis
    return format_date_millis(float(ms))


def _segment_file_sizes(shards) -> Dict[str, dict]:
    """Per-extension on-disk footprint across shard directories
    (include_segment_file_sizes=true serialization)."""
    sizes: Dict[str, dict] = {}
    for sh in shards:
        for root, _, files in os.walk(sh.path):
            for fname in files:
                ext = fname.rsplit(".", 1)[-1]
                try:
                    sz = os.path.getsize(os.path.join(root, fname))
                except OSError:
                    continue
                e = sizes.setdefault(ext, {"size_in_bytes": 0, "count": 0,
                                           "description": ext})
                e["size_in_bytes"] += sz
                e["count"] += 1
    return sizes


def _prime_agg_mappers(aggs: dict, mapper) -> None:
    """Recursively hand agg instances a mapper for reduce-side rendering
    when their collect phase ran on a REMOTE node (cluster agg partials)."""
    for a in aggs.values():
        if getattr(a, "_mapper", None) is None:
            a._mapper = mapper
        subs = getattr(a, "subs", None)
        if subs:
            _prime_agg_mappers(subs, mapper)
