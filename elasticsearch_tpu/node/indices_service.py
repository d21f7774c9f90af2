"""Index lifecycle + per-index shard management on one node.

Reference parity targets: ``indices/IndicesService.java:176`` (create/
remove index services), ``index/IndexService.java`` (shard ownership),
``cluster/metadata/MetadataCreateIndexService.java`` (validation,
settings), ``action/bulk/TransportBulkAction.java:99`` (routing + per-shard
grouping). Single-node scope here; the distributed data plane in
``parallel/`` takes over shard placement across a device mesh.
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..common import heap as _heap
from ..common.errors import (ElasticsearchError, IllegalArgumentError,
                             IndexClosedError, IndexNotFoundError,
                             ResourceAlreadyExistsError)
from ..index.engine import Engine
from ..index.mapping import MapperService
from ..search.shard_search import ShardSearcher, ShardSearchResult
from ..utils.murmur3 import shard_for

_VALID_INDEX_RE = re.compile(r"^[^A-Z _\-+][^A-Z\\/*?\"<>| ,#]*$")

#: thread-local marker: the current thread is performing an internal
#: resize/recovery copy and may write through application write blocks
_INTERNAL_COPY = threading.local()


@contextlib.contextmanager
def internal_copy_writes():
    """Scope an internal (resize/recovery) copy on the current thread so
    ``IndexService._check_write_block`` lets its writes through."""
    prev = getattr(_INTERNAL_COPY, "active", False)
    _INTERNAL_COPY.active = True
    try:
        yield
    finally:
        _INTERNAL_COPY.active = prev


def validate_index_name(name: str) -> None:
    from ..common.errors import InvalidIndexNameError
    if not name or name in (".", ".."):
        raise InvalidIndexNameError(f"Invalid index name [{name}]")
    if name.startswith(("-", "_", "+")) or name != name.lower() or \
            any(c in name for c in '\\/*?"<>| ,#'):
        raise InvalidIndexNameError(
            f"Invalid index name [{name}], must be lowercase and may not "
            f"contain spaces or the characters \\/*?\"<>|,#")


class IndexService:
    """One index: settings, mapper, and its primary shards."""

    def __init__(self, name: str, path: str, settings: Optional[dict] = None,
                 mappings: Optional[dict] = None):
        self.name = name
        self.path = path
        settings = dict(settings or {})
        flat = _flatten_settings(settings)
        self.num_shards = int(flat.get("index.number_of_shards",
                                       flat.get("number_of_shards", 1)))
        self.num_replicas = int(flat.get("index.number_of_replicas",
                                         flat.get("number_of_replicas", 1)))
        if self.num_shards < 1 or self.num_shards > 1024:
            raise IllegalArgumentError(
                f"invalid number_of_shards [{self.num_shards}]")
        _reject_retired_settings(flat)
        # settings store under their canonical "index."-prefixed keys so
        # later lookups (preserve_existing, GET _settings) are uniform
        self.settings = {
            (k if k.startswith("index.") else f"index.{k}"): v
            for k, v in flat.items()}
        self.creation_date = int(time.time() * 1000)
        self.uuid = f"{abs(hash((name, self.creation_date))):022x}"[:22]
        self.mapper = MapperService(mappings or {})
        self.mapper.index_name = name       # hit rendering (_index)
        try:
            self.mapper.nested_limit = int(self.settings.get(
                "index.mapping.nested_objects.limit", 10000))
        except (TypeError, ValueError):
            pass
        # index sorting (reference: IndexSortConfig — segments hold docs
        # ordered by these fields; forbidden with nested docs)
        sort_fields = flat.get("index.sort.field")
        index_sort = None
        if sort_fields:
            if not isinstance(sort_fields, list):
                sort_fields = [sort_fields]
            sort_orders = flat.get("index.sort.order") or []
            if not isinstance(sort_orders, list):
                sort_orders = [sort_orders]
            index_sort = [
                (f, (sort_orders[i] if i < len(sort_orders) else "asc"))
                for i, f in enumerate(sort_fields)]
        self.shards: List[Engine] = []
        for i in range(self.num_shards):
            shard_path = os.path.join(path, str(i))
            os.makedirs(shard_path, exist_ok=True)
            self.shards.append(Engine(
                shard_path, self.mapper,
                translog_durability=flat.get("index.translog.durability",
                                             "request"),
                gc_deletes_seconds=_parse_time_seconds(
                    flat.get("index.gc_deletes", "60s")),
                index_sort=index_sort))
        self.aliases: Dict[str, dict] = {}
        self.closed = False
        # search-phase counters (+ per-group when a search carries a
        # ``stats`` group list; reference: SearchStats.groupStats)
        self.search_stats: Dict[str, object] = {
            "query_total": 0, "fetch_total": 0, "scroll_total": 0,
            "suggest_total": 0, "groups": {}}
        # shard request cache (reference: IndicesRequestCache.java):
        # size==0 results keyed on (segment signature, body); the
        # signature bakes in liveness so refresh/merge/delete invalidate
        from collections import OrderedDict
        self.request_cache: "OrderedDict" = OrderedDict()
        self.request_cache_stats = {"hit_count": 0, "miss_count": 0}
        # plane-served slice of the request cache (identical plane-eligible
        # bodies served before the micro-batcher) — counted separately so
        # the serving bench can attribute hits to this path. The counters
        # are telemetry-registry citizens: instance-owned Counter objects
        # (fresh per index — exact per-index counts) exposed through the
        # process registry via a weakref collector, like every other
        # node-scoped producer; :attr:`plane_cache_stats` is the
        # dict-shaped read view the stats/bench surfaces keep using.
        from ..common import telemetry as _tm
        self._plane_cache_counters = {"hit": _tm.Counter(),
                                      "miss": _tm.Counter()}
        _tm.DEFAULT.register_object_collector(
            f"plane_cache_requests_{self.uuid}", self,
            IndexService._plane_cache_requests_doc)
        # the plane path puts the concurrent serving hot path through this
        # cache: get's move_to_end racing put's eviction would KeyError
        self._cache_lock = threading.Lock()
        #: search/indexing slow-log ring (reference: SearchSlowLog.java /
        #: IndexingSlowLog.java write per-index log files; entries also
        #: persist to <index>/_index_*_slowlog.log)
        self.slow_log: List[dict] = []
        # serving planes for the tiered TPU kernel (search/plane_route.py);
        # lazily built per text field, invalidated by segment-list changes
        from ..search.plane_route import ServingPlaneCache
        self.plane_cache = ServingPlaneCache()
        # serving-plane refresh hook: every engine refresh/merge that
        # changed the searchable segment list reconciles the plane
        # generations immediately (delta pack / background repack start
        # on the indexing thread), instead of the first search paying a
        # signature miss
        for sh in self.shards:
            sh.refresh_listeners.append(self._on_shard_refresh)
        # cluster seam (node/cluster_rest.py): when set, per-shard doc ops
        # and whole-index search route through the cluster instead of the
        # local engines (which hold data only for locally-assigned shards).
        # None on the single-node path — zero behavior change.
        self.cluster_hooks = None
        # what recovery brought back (segments, the version map) lives as
        # long as the index: in a node's process it leaves the cyclic
        # collector's reach here (a new, empty index installs nothing)
        if any(sh.segments for sh in self.shards):
            _heap.settle()

    def _on_shard_refresh(self) -> None:
        """Engine refresh listener → plane-generation reconcile. Text
        generations serve the POOLED list; kNN generations may be keyed
        per index shard (the distributed searcher probes one per shard),
        so every candidate view is offered and each generation
        reconciles against its best match."""
        try:
            shard_lists = [sh.searchable_segments() for sh in self.shards]
            segments = [seg for lst in shard_lists for seg in lst]
            knn_lists = list(shard_lists)
            if len(shard_lists) > 1:
                knn_lists.append(segments)     # pooled RRF probes
            self.plane_cache.notify_refresh(segments, self.mapper,
                                            knn_lists=knn_lists)
        except Exception:   # noqa: BLE001 — reconcile is best-effort;
            pass            # the query path re-reconciles on its own

    def _plane_cache_requests_doc(self) -> dict:
        return {"es_plane_cache_requests_total": {
            "type": "counter",
            "help": "plane-path request cache lookups by result",
            "samples": [({"index": self.name, "result": r}, c.value)
                        for r, c in self._plane_cache_counters.items()]}}

    @property
    def plane_cache_stats(self) -> Dict[str, int]:
        """Dict view over the plane-path cache counters (kept for the
        stats document / bench surfaces that predate the registry)."""
        return {"hit_count": int(self._plane_cache_counters["hit"].value),
                "miss_count": int(self._plane_cache_counters["miss"].value)}

    def record_search(self, groups: Optional[List[str]] = None) -> None:
        self.search_stats["query_total"] += 1
        self.search_stats["fetch_total"] += 1
        for g in groups or []:
            gs = self.search_stats["groups"].setdefault(
                str(g), {"query_total": 0, "fetch_total": 0})
            gs["query_total"] += 1
            gs["fetch_total"] += 1

    def _check_open(self) -> None:
        if self.closed:
            raise IndexClosedError(f"closed index [{self.name}]")

    def _check_write_block(self) -> None:
        """Write-level index blocks (reference: ``IndexMetadata``
        INDEX_WRITE_BLOCK / INDEX_READ_ONLY_BLOCK; set via the add-block
        API or ``index.blocks.*`` settings)."""
        from ..common.errors import ClusterBlockError
        if getattr(_INTERNAL_COPY, "active", False):
            # internal resize/recovery copy on THIS thread — the reference
            # moves segment files below the write API
            # (TransportResizeAction.java), so application write blocks
            # must not stop it; concurrent client writes on other threads
            # still hit the block
            return
        s = self.settings
        for key, desc in (("index.blocks.write", "index write (api)"),
                          ("index.blocks.read_only", "index read-only"),
                          ("index.blocks.read_only_allow_delete",
                           "index read-only / allow delete (api)")):
            if str(s.get(key, "")).lower() == "true":
                raise ClusterBlockError(
                    f"index [{self.name}] blocked by: [FORBIDDEN/8/"
                    f"{desc}];")

    # -- routing ------------------------------------------------------------

    def shard_id_for(self, doc_id: str, routing: Optional[str] = None) -> int:
        return shard_for(routing if routing is not None else doc_id,
                         self.num_shards)

    def shard_for_doc(self, doc_id: str, routing: Optional[str] = None) -> Engine:
        return self.shards[self.shard_id_for(doc_id, routing)]

    # -- document ops -------------------------------------------------------

    def index_doc(self, doc_id: str, source: dict, *,
                  routing: Optional[str] = None, op_type: str = "index",
                  if_seq_no=None, if_primary_term=None):
        self._check_open()
        self._check_write_block()
        t0 = time.perf_counter()
        try:
            return self._index_doc_inner(
                doc_id, source, routing=routing, op_type=op_type,
                if_seq_no=if_seq_no, if_primary_term=if_primary_term)
        finally:
            self._slowlog_record("index", time.perf_counter() - t0,
                                 f"[{doc_id}] " + str(source)[:500])

    def _index_doc_inner(self, doc_id, source, *, routing=None,
                         op_type="index", if_seq_no=None,
                         if_primary_term=None):
        if self.cluster_hooks is not None:
            w = self.cluster_hooks.writer(self.name, self.shard_id_for(
                doc_id, routing))
            if w is not None:
                return w.index(doc_id, source, routing=routing,
                               op_type=op_type, if_seq_no=if_seq_no,
                               if_primary_term=if_primary_term)
        return self.shard_for_doc(doc_id, routing).index(
            doc_id, source, routing=routing, op_type=op_type,
            if_seq_no=if_seq_no, if_primary_term=if_primary_term)

    def get_doc(self, doc_id: str, routing: Optional[str] = None):
        self._check_open()
        if self.cluster_hooks is not None:
            w = self.cluster_hooks.writer(self.name, self.shard_id_for(
                doc_id, routing), for_read=True)
            if w is not None:
                return w.get(doc_id)
        return self.shard_for_doc(doc_id, routing).get(doc_id)

    def delete_doc(self, doc_id: str, *, routing: Optional[str] = None,
                   if_seq_no=None, if_primary_term=None):
        self._check_open()
        self._check_write_block()
        if self.cluster_hooks is not None:
            w = self.cluster_hooks.writer(self.name, self.shard_id_for(
                doc_id, routing))
            if w is not None:
                return w.delete(doc_id, if_seq_no=if_seq_no,
                                if_primary_term=if_primary_term)
        return self.shard_for_doc(doc_id, routing).delete(
            doc_id, if_seq_no=if_seq_no, if_primary_term=if_primary_term)

    # -- search -------------------------------------------------------------

    def searcher(self) -> ShardSearcher:
        """Pooled searcher over every shard's searchable segments (used by
        single-shard paths and features that need one flat segment list,
        e.g. scroll snapshots). Term statistics are computed over the
        union — equivalent to the reference's DFS phase being always-on
        (``search/dfs/DfsPhase.java``)."""
        segments = []
        for shard in self.shards:
            segments.extend(shard.searchable_segments())
        sr = ShardSearcher(
            segments, self.mapper,
            plane_provider=lambda segs, field:
                self.plane_cache.plane_for(segs, self.mapper, field),
            knn_plane_provider=lambda segs, field:
                self.plane_cache.knn_plane_for(segs, self.mapper, field),
            fused_provider=lambda segs, tf, kf:
                self.plane_cache.fused_runner_for(segs, self.mapper,
                                                  tf, kf))
        mao = self.settings.get("index.highlight.max_analyzed_offset")
        if mao is not None:
            sr.max_analyzed_offset = int(mao)
        return sr

    def dist_searcher(self) -> "DistributedSearcher":
        """Scatter-gather searcher: one query phase per shard, one global
        reduce (``search/dist_query.py`` — the coordinating-node role)."""
        from ..search.dist_query import DistributedSearcher
        return DistributedSearcher(
            [shard.searchable_segments() for shard in self.shards],
            self.mapper,
            plane_provider=lambda segs, field:
                self.plane_cache.plane_for(segs, self.mapper, field),
            knn_plane_provider=lambda segs, field:
                self.plane_cache.knn_plane_for(segs, self.mapper, field),
            fused_provider=lambda segs, tf, kf:
                self.plane_cache.fused_runner_for(segs, self.mapper,
                                                  tf, kf))

    #: request-cache entry cap per index (reference sizes by bytes —
    #: indices.requests.cache.size 1%; entries are simpler and safe here)
    REQUEST_CACHE_MAX = 256

    def _request_cache_blob(self, body: dict,
                            explicit: Optional[bool]) -> Optional[str]:
        """The canonical body blob when this request is cacheable, else
        None (reference: ``IndicesRequestCache.java`` — size==0 requests
        by default, opt-in/out via ?request_cache, never
        non-deterministic bodies). No invalidation component here —
        callers add their own (segment signature locally, write
        generation on the cluster front)."""
        if explicit is False:
            return None
        if str(self.settings.get("index.requests.cache.enable", "true")
               ).lower() == "false":
            return None
        if int(body.get("size", 10)) != 0:
            # only size==0 shapes are safe to cache: the coordinator
            # mutates hit objects in place (sort-cursor lifting, boosts),
            # so a cached hit would be re-mutated on every cache hit —
            # the reference likewise only caches size==0 even under
            # ?request_cache=true
            return None
        try:
            blob = json.dumps(body, sort_keys=True)
        except (TypeError, ValueError):
            return None
        if "now" in blob or "random_score" in blob or \
                body.get("profile"):
            return None
        return blob

    def _request_cache_key(self, body: dict,
                           explicit: Optional[bool]) -> Optional[tuple]:
        """Local cache key: the segment-list+liveness signature IS the
        invalidation, like the reference cache's reader-key."""
        blob = self._request_cache_blob(body, explicit)
        if blob is None:
            return None
        sig = tuple((seg.seg_id, seg.n_docs, int(seg.live.sum()))
                    for sh in self.shards
                    for seg in sh.searchable_segments())
        return (sig, blob)

    def _plane_cache_key(self, body: dict,
                         explicit: Optional[bool]) -> Optional[tuple]:
        """Request-cache key for PLANE-ELIGIBLE bodies (size>0): a pure
        bag-of-terms query with no feature sections is a deterministic
        read of the segment state, so identical bodies can be served from
        the cache before they ever reach the micro-batcher. The usual
        size==0-only rule exists because the coordinator mutates hit
        objects in place (sort-cursor lifting, boosts) — the plane path
        instead caches a pristine copy and hands out per-hit copies
        (:func:`_copy_shard_result`), keeping cached hits immutable."""
        if explicit is False:
            return None
        if str(self.settings.get("index.requests.cache.enable", "true")
               ).lower() == "false":
            return None
        if not isinstance(body, dict) or not body.get("query"):
            return None
        # cursor/threshold kwargs keep per-request semantics out of the
        # cache (mirrors the plane route's own kwargs checks); scripted
        # fetch sections may be nondeterministic. No "now"-substring
        # guard like the size==0 cache: bag-of-terms queries cannot carry
        # date math, and a substring check would silently disable caching
        # for any body containing those letters ("snow", "know", ...).
        if body.get("search_after") is not None or \
                body.get("min_score") is not None or \
                body.get("script_fields") or body.get("runtime_mappings") \
                or body.get("profile"):
            # profiled bodies ride the plane but are never cached: a
            # cached profile would replay stale stage timings
            return None
        from ..search.plane_route import body_eligible, extract_bag_of_terms
        if not body_eligible(body):
            return None
        if extract_bag_of_terms(body["query"], self.mapper) is None:
            return None
        try:
            blob = json.dumps(body, sort_keys=True)
        except (TypeError, ValueError):
            return None
        sig = tuple((seg.seg_id, seg.n_docs, int(seg.live.sum()))
                    for sh in self.shards
                    for seg in sh.searchable_segments())
        return (sig, "plane", blob)

    def cache_get(self, key):
        with self._cache_lock:
            hit = self.request_cache.get(key)
            if hit is not None:
                self.request_cache.move_to_end(key)
                self.request_cache_stats["hit_count"] += 1
            return hit

    def cache_put(self, key, result) -> None:
        with self._cache_lock:
            self.request_cache_stats["miss_count"] += 1
            self.request_cache[key] = result
            while len(self.request_cache) > self.REQUEST_CACHE_MAX:
                self.request_cache.popitem(last=False)

    #: slow-log ring size per index (entries also append to the on-disk
    #: log file, the reference's actual surface)
    SLOWLOG_MAX = 512

    def _slowlog_threshold(self, kind: str, level: str) -> Optional[float]:
        """Threshold seconds for ``index.(search|indexing).slowlog.
        threshold...`` settings, None = disabled (reference:
        ``index/SearchSlowLog.java:43`` / ``IndexingSlowLog.java:46``)."""
        key = (f"index.search.slowlog.threshold.query.{level}"
               if kind == "query" else
               f"index.indexing.slowlog.threshold.index.{level}")
        raw = self.settings.get(key)
        if raw in (None, "", "-1", -1):
            return None
        try:
            return _parse_time_seconds(raw)
        except Exception:   # noqa: BLE001 — malformed threshold: off
            return None

    def _slowlog_record(self, kind: str, took_s: float,
                        detail: str, stages: Optional[dict] = None,
                        planner: Optional[dict] = None) -> None:
        worst = None
        for level in ("warn", "info", "debug", "trace"):
            thr = self._slowlog_threshold(kind, level)
            if thr is not None and took_s >= thr:
                worst = level
                break
        if worst is None:
            return
        entry = {"level": worst, "took_ms": round(took_s * 1e3, 3),
                 "index": self.name, "kind": kind, "source": detail,
                 "timestamp": time.time()}
        # request correlation (reference: SearchSlowLog stamps
        # X-Opaque-Id and the APM trace.id into every slow-log line)
        from ..common import tracing as _tracing
        tid = _tracing.current_trace_id()
        if tid:
            entry["trace.id"] = tid
        opaque = _tracing.current_opaque_id()
        if opaque:
            entry["x_opaque_id"] = opaque
        # the query shape id joins this line to /_insights/top_queries
        # and flight-recorder events without replaying the source
        from ..common import flightrec as _fr
        shape = _fr.current_shape()
        if shape:
            entry["shape"] = shape
        if stages:
            # plane-served queries: which pipeline stage ate the time
            # (queue wait / host prep / device dispatch / fetch)
            entry["serving_stages"] = {
                s: (round(ms, 3) if isinstance(ms, (int, float)) else ms)
                for s, ms in stages.items()}
        if planner:
            # one-dispatch planner context (PR 11's fused route): which
            # route served (fused vs fallback), the host-side lowering
            # cost, and the stages folded into the dispatch — a slow
            # fused query is bisectable from its slow-log line alone
            entry["planner"] = planner
        from .task_manager import current_resources
        res = current_resources()
        if res is not None:
            # the owning task's resource ledger AT THIS POINT: a slow
            # entry names what the request had already burned (CPU,
            # device-ms, docs scanned) when it crossed the threshold
            entry["task_resources"] = res.to_dict()
        self.slow_log.append(entry)
        del self.slow_log[: -self.SLOWLOG_MAX]
        try:
            import json as _json
            fname = ("_index_search_slowlog.log" if kind == "query"
                     else "_index_indexing_slowlog.log")
            with open(os.path.join(self.path, fname), "a") as f:
                f.write(_json.dumps(entry) + "\n")
        except OSError:
            pass

    def search(self, body: Optional[dict] = None,
               request_cache: Optional[bool] = None) -> ShardSearchResult:
        """One index's query execution. When a trace is active (REST
        requests), the whole shard-level phase records as a span under
        the coordinator's — the ``GET /_trace/{id}`` tree's shard tier."""
        from ..common import telemetry as _tm
        from ..common import tracing as _tracing
        from ..common import flightrec as _fr
        from ..search import query_insight as _qi
        from .task_manager import current_resources
        t0 = time.perf_counter()
        insights = _qi.insights_enabled()
        shape_token = None
        res = cpu0 = dev0 = bytes0 = None
        if insights:
            # bind the structural fingerprint up front; the shard layer
            # upgrades it in place to the plan-based id once the
            # planner lowers the body (flightrec.set_shape), so slow
            # log, ledger, dispatch records and this observation all
            # end on the same id
            if _fr.has_shape_holder():
                # the REST edge already bound a holder — upgrade it in
                # place so the whole request converges on one id
                _fr.set_shape(_qi.shape_of(body))
            else:
                shape_token = _fr.bind_shape(_qi.shape_of(body))
            cpu0 = time.thread_time()
            res = current_resources()
            if res is not None:
                dev0 = res.device_ms
                bytes0 = res.h2d_bytes + res.d2h_bytes
                # stamp the ledger NOW so a live _tasks?detailed poll
                # sees the shape while the task runs; the post-search
                # stamp below appends the plan-upgraded id if the
                # planner changed it mid-flight
                res.note_shape(_fr.current_shape())
        try:
            with _tracing.span(f"shards[{self.name}]",
                               attrs={"index": self.name,
                                      "shards": self.num_shards}):
                r = self._search_traced(body, request_cache)
                # SLO latency family: each sample may carry its trace
                # id as an OpenMetrics exemplar, so a p99 breach on the
                # scrape links straight to GET /_trace/{id} (O(1) on
                # this path)
                took_ms = (time.perf_counter() - t0) * 1e3
                _tm.DEFAULT.histogram(
                    "es_query_latency_ms", {"index": self.name},
                    help="per-index shard-phase query latency ms "
                         "(exemplars carry trace ids)").observe(
                    took_ms, exemplar=_tracing.current_trace_id())
                # the same sample feeds the SLO burn-rate engine (one
                # locked per-second bucket update — the watchdog
                # evaluates windows off this path)
                _fr.observe_query_latency(took_ms)
                if insights:
                    dev_ms = (res.device_ms - dev0) \
                        if res is not None else 0.0
                    xfer = (res.h2d_bytes + res.d2h_bytes - bytes0) \
                        if res is not None else 0.0
                    shape = _fr.current_shape()
                    if res is not None and shape:
                        res.note_shape(shape)
                    _qi.store_for(_fr.ambient_node()).observe(
                        shape, _tracing.current_opaque_id(),
                        latency_ms=took_ms,
                        cpu_ms=(time.thread_time() - cpu0) * 1e3,
                        device_ms=dev_ms, bytes_=xfer,
                        trace_id=_tracing.current_trace_id(),
                        sample_body=body)
                return r
        finally:
            if shape_token is not None:
                _fr.reset_shape(shape_token)

    def _search_traced(self, body: Optional[dict],
                       request_cache: Optional[bool]) -> ShardSearchResult:
        self._check_open()
        t0 = time.perf_counter()
        if self.cluster_hooks is not None:
            r = self.cluster_hooks.search(self.name, body or {},
                                          request_cache=request_cache)
            if r is not None:
                self._slowlog_record("query", time.perf_counter() - t0,
                                     str(body or {})[:1000],
                                     stages=getattr(r, "serving_stages",
                                                    None),
                                     planner=getattr(r, "planner", None))
                return r
        key = self._request_cache_key(body or {}, request_cache)
        plane_key = None
        if key is not None:
            hit = self.cache_get(key)
            if hit is not None:
                return hit
        else:
            # plane-served path: identical plane-eligible bodies hit the
            # shard request cache BEFORE the micro-batcher (cached hits
            # stay pristine — copies in, copies out)
            plane_key = self._plane_cache_key(body or {}, request_cache)
            if plane_key is not None:
                hit = self.cache_get(plane_key)
                if hit is not None:
                    self._plane_cache_counters["hit"].inc()
                    return _copy_shard_result(hit)
        if self.num_shards > 1:
            r = self.dist_searcher().search(body or {})
        else:
            r = self.searcher().search(body or {})
        if key is not None:
            self.cache_put(key, r)
        elif plane_key is not None:
            self._plane_cache_counters["miss"].inc()
            self.cache_put(plane_key, _copy_shard_result(r))
        self._slowlog_record("query", time.perf_counter() - t0,
                             str(body or {})[:1000],
                             stages=getattr(r, "serving_stages", None),
                             planner=getattr(r, "planner", None))
        return r

    def count(self, body: Optional[dict] = None) -> int:
        self._check_open()
        if self.cluster_hooks is not None:
            c = self.cluster_hooks.count(self.name, body or {})
            if c is not None:
                return c
        if self.num_shards > 1:
            return self.dist_searcher().count(body or {})
        return self.searcher().count(body or {})

    # -- admin --------------------------------------------------------------

    def refresh(self) -> None:
        if self.cluster_hooks is not None and \
                self.cluster_hooks.refresh(self.name):
            return
        for s in self.shards:
            s.refresh()

    def refresh_shard(self, doc_id: str,
                      routing: Optional[str] = None) -> None:
        """Refresh only the shard owning ``doc_id`` — the scope of a doc
        op's ``?refresh=true`` (reference: ``TransportShardBulkAction``
        refreshes the affected shard, never the whole index; other
        shards' pending NRT deletes must stay invisible)."""
        sid = self.shard_id_for(doc_id, routing)
        if self.cluster_hooks is not None and \
                self.cluster_hooks.refresh(self.name, shard=sid):
            return
        self.shards[sid].refresh()

    def flush(self) -> None:
        for s in self.shards:
            s.flush()

    def force_merge(self) -> None:
        for s in self.shards:
            s.force_merge()

    def put_mapping(self, mappings: dict) -> None:
        self.mapper.merge(mappings)

    def update_settings(self, settings: dict) -> None:
        flat = {(k if k.startswith("index.") else f"index.{k}"): v
                for k, v in _flatten_settings(settings).items()}
        _reject_retired_settings(flat)
        for k in flat:
            if k == "index.number_of_shards":
                raise IllegalArgumentError(
                    f"final {self.name} setting [{k}], not updateable")
        self.settings.update(flat)
        if "index.number_of_replicas" in flat:
            self.num_replicas = int(flat["index.number_of_replicas"])
        if "index.mapping.nested_objects.limit" in flat:
            try:
                self.mapper.nested_limit = int(
                    flat["index.mapping.nested_objects.limit"])
            except (TypeError, ValueError):
                pass

    def field_bytes(self):
        """(fielddata_bytes_by_field, completion_bytes_by_field) — host
        array footprints of each field's loaded columns, the analog of
        Lucene fielddata / completion FST memory accounting."""
        from ..index.mapping import CompletionFieldType
        completion_fields = {n for n, ft in self.mapper._fields.items()
                             if isinstance(ft, CompletionFieldType)}
        loaded = self.mapper.fielddata_loaded
        fd: Dict[str, int] = {}
        comp: Dict[str, int] = {}
        for s in self.shards:
            for seg in s.searchable_segments():
                for fname, f in seg.text_fields.items():
                    if fname not in loaded:
                        continue          # fielddata loads lazily
                    fd[fname] = fd.get(fname, 0) + int(
                        f.docs_host.nbytes + f.tf_host.nbytes +
                        f.pos_flat.nbytes + f.doc_len_host.nbytes)
                for fname, f in seg.keyword_fields.items():
                    n = int(f.docs_host.nbytes + f.dv_ords_host.nbytes +
                            f.dv_docs_host.nbytes +
                            sum(len(t) for t in f.ord_terms))
                    if fname in completion_fields:
                        comp[fname] = comp.get(fname, 0) + n
                    elif fname in loaded:
                        fd[fname] = fd.get(fname, 0) + n
                for fname, f in seg.numeric_fields.items():
                    if fname not in loaded:
                        continue
                    fd[fname] = fd.get(fname, 0) + int(
                        f.vals_host.nbytes + f.docs_host.nbytes)
        return fd, comp

    def plane_serving_stats(self) -> dict:
        """Micro-batcher serving stats aggregated over this index's
        serving generations (lexical + kNN), plus the plane-path cache
        counters and the generation-maintenance rollup (rebuilds by mode,
        delta-served queries) — the ``plane_serving`` nodes-stats
        section."""
        from ..search.microbatch import empty_serving_stats
        out = empty_serving_stats()
        # locked generation snapshot: iterating the registry dicts raw
        # races the background repack thread's atomic swap — a scrape
        # mid-swap would die with "dictionary changed size during
        # iteration" (ESTP-R01, found by the first full race scan)
        # topology keys describe the shared serving mesh, not per-batcher
        # work — max-merge them; everything else is additive
        _topo = ("max_batch", "mesh_shard_devices", "mesh_replica_devices")
        for b in self.plane_cache.serving_batchers():
            doc = b.stats_doc()
            for k, v in doc.items():
                out[k] = max(out[k], v) if k in _topo else out[k] + v
        out["cache_hit_count"] = self.plane_cache_stats["hit_count"]
        out["cache_miss_count"] = self.plane_cache_stats["miss_count"]
        try:
            rb = self.plane_cache.rebuild_stats()
        except Exception:   # noqa: BLE001 — stats must never fail a node
            rb = {}
        out["rebuilds_sync"] = rb.get("sync", 0)
        out["rebuilds_background"] = rb.get("background", 0)
        out["delta_served_queries"] = rb.get("delta_serves", 0)
        return out

    def stats(self, with_field_bytes: bool = True) -> dict:
        """``with_field_bytes=False`` skips the per-field column-footprint
        walk (O(vocabulary)) for callers that only need counts (cat,
        rollover conditions)."""
        docs = sum(s.doc_count for s in self.shards)
        deleted = sum(s.deleted_count for s in self.shards)
        seg_count = sum(len(s.searchable_segments()) for s in self.shards)
        store = 0
        for s in self.shards:
            for root, _, files in os.walk(s.path):
                for f in files:
                    try:
                        store += os.path.getsize(os.path.join(root, f))
                    except OSError:
                        pass
        ops = {}
        for key in ("index_total", "delete_total", "refresh_total",
                    "flush_total", "merge_total", "get_total"):
            ops[key] = sum(s.stats.get(key, 0) for s in self.shards)
        tl_ops = sum(s.translog.total_operations() for s in self.shards)
        tl_size = sum(s.translog.size_in_bytes() for s in self.shards)
        fd, comp = self.field_bytes() if with_field_bytes else ({}, {})
        ss = self.search_stats
        out = empty_index_stats()
        # request_cache_stats already count the plane-path entries (they
        # share cache_get/cache_put); plane_serving breaks them out
        out["request_cache"].update(self.request_cache_stats)
        out["plane_serving"].update(self.plane_serving_stats())
        out["docs"].update(count=docs, deleted=deleted)
        out["store"].update(size_in_bytes=store,
                            total_data_set_size_in_bytes=store)
        out["translog"].update(operations=tl_ops, size_in_bytes=tl_size,
                               uncommitted_operations=tl_ops,
                               uncommitted_size_in_bytes=tl_size)
        out["segments"].update(count=seg_count,
                               memory_in_bytes=sum(fd.values()))
        out["indexing"].update(index_total=ops["index_total"],
                               delete_total=ops["delete_total"])
        out["get"].update(total=ops["get_total"])
        out["search"].update(query_total=ss["query_total"],
                             fetch_total=ss["fetch_total"],
                             scroll_total=ss["scroll_total"],
                             suggest_total=ss["suggest_total"])
        out["refresh"].update(total=ops["refresh_total"],
                              external_total=ops["refresh_total"])
        out["flush"].update(total=ops["flush_total"])
        out["merges"].update(total=ops["merge_total"])
        out["fielddata"].update(memory_size_in_bytes=sum(fd.values()))
        out["completion"].update(size_in_bytes=sum(comp.values()))
        return out

    def shard_stats(self, node_id: str = "node") -> Dict[str, list]:
        """level=shards payload: shard number → list of copies."""
        out: Dict[str, list] = {}
        for i, s in enumerate(self.shards):
            segs = s.searchable_segments()
            commit_id = f"{abs(hash(tuple(sorted(g.seg_id for g in segs)))):016x}"
            out[str(i)] = [{
                "routing": {"state": "STARTED", "primary": True,
                            "node": node_id, "relocating_node": None},
                "docs": {"count": s.doc_count, "deleted": s.deleted_count},
                "store": {"size_in_bytes": 0},
                "commit": {"id": commit_id,
                           "generation": s.stats.get("flush_total", 0) + 1,
                           "user_data": {}, "num_docs": s.doc_count},
                "seq_no": {"max_seq_no": s.tracker.max_seq_no,
                           "local_checkpoint": s.tracker.checkpoint,
                           "global_checkpoint": s.tracker.checkpoint},
                "shard_path": {"data_path": s.path,
                               "is_custom_data_path": False},
            }]
        return out

    def close(self) -> None:
        for s in self.shards:
            s.close()
        # release the serving planes' breaker reservations (their dense
        # tiers die with the index)
        try:
            self.plane_cache.release()
        except Exception:   # noqa: BLE001 — close must not throw
            pass


class IndicesService:
    """All indices on this node (reference: ``IndicesService.java:176``).
    Resolves index expressions (names, aliases, wildcards, _all)."""

    def __init__(self, data_path: str):
        self.data_path = data_path
        os.makedirs(data_path, exist_ok=True)
        self.indices: Dict[str, IndexService] = {}
        #: data-stream seam: name -> backing index list (or None) —
        #: set by the owning RestAPI's DataStreamService so stream names
        #: resolve like aliases over their generations
        self.data_streams_provider = None

    # -- lifecycle ----------------------------------------------------------

    def create_index(self, name: str, settings: Optional[dict] = None,
                     mappings: Optional[dict] = None,
                     aliases: Optional[dict] = None) -> IndexService:
        validate_index_name(name)
        if name in self.indices or name in self.all_aliases():
            raise ResourceAlreadyExistsError(f"index [{name}] already exists")
        svc = IndexService(name, os.path.join(self.data_path, name),
                           settings, mappings)
        for alias, spec in (aliases or {}).items():
            svc.aliases[alias] = spec or {}
        self.indices[name] = svc
        return svc

    def delete_index(self, expression: str) -> List[str]:
        names = self.resolve(expression, allow_aliases=False)
        mounted = getattr(self, "_mounted_snapshots", None)
        for n in names:
            svc = self.indices.pop(n)
            svc.close()
            shutil.rmtree(svc.path, ignore_errors=True)
            if mounted is not None:
                # searchable-snapshot bookkeeping follows the index out
                # on EVERY deletion path (REST, ILM, resize cleanup)
                mounted.pop(n, None)
        return names

    def get(self, name: str) -> IndexService:
        svc = self.indices.get(name)
        if svc is None:
            resolved = self.resolve(name)
            if len(resolved) != 1:
                raise IllegalArgumentError(
                    f"alias [{name}] has more than one index associated")
            return self.indices[resolved[0]]
        return svc

    def exists(self, expression: str) -> bool:
        try:
            return bool(self.resolve(expression))
        except IndexNotFoundError:
            return False

    def all_aliases(self) -> Dict[str, List[str]]:
        out: Dict[str, List[str]] = {}
        for name, svc in self.indices.items():
            for a in svc.aliases:
                out.setdefault(a, []).append(name)
        return out

    def resolve(self, expression: Optional[str],
                allow_aliases: bool = True) -> List[str]:
        """Index expression → concrete index names (reference:
        ``IndexNameExpressionResolver``): comma lists, wildcards, _all,
        aliases."""
        if expression in (None, "", "_all", "*"):
            return sorted(self.indices)
        aliases = self.all_aliases() if allow_aliases else {}
        out: List[str] = []
        for part in str(expression).split(","):
            part = part.strip()
            if not part:
                continue
            if part in self.indices:
                out.append(part)
            elif part in aliases:
                out.extend(aliases[part])
            elif self.data_streams_provider is not None and \
                    self.data_streams_provider(part) is not None:
                out.extend(self.data_streams_provider(part))
            elif "*" in part or "?" in part:
                import fnmatch
                matched = [n for n in self.indices
                           if fnmatch.fnmatchcase(n, part)]
                if allow_aliases:
                    for a, names in aliases.items():
                        if fnmatch.fnmatchcase(a, part):
                            matched.extend(names)
                out.extend(sorted(set(matched)))
            else:
                raise IndexNotFoundError(part)
        seen = set()
        uniq = []
        for n in out:
            if n not in seen:
                seen.add(n)
                uniq.append(n)
        return uniq

    def close(self) -> None:
        for svc in self.indices.values():
            svc.close()


def _copy_shard_result(r: ShardSearchResult) -> ShardSearchResult:
    """Defensive copy for plane-path cache entries: the coordinator
    mutates hit objects in place (score boosts, sort-cursor lifting), so
    both the stored entry and every served hit get fresh ShardHit shells
    (sources/highlights are shared read-only payloads)."""
    import copy
    hits = []
    for h in r.hits:
        h2 = copy.copy(h)
        if h2.sort_values is not None:
            h2.sort_values = list(h2.sort_values)
        if h2.fields is not None:
            h2.fields = dict(h2.fields)
        hits.append(h2)
    r2 = copy.copy(r)
    r2.hits = hits
    return r2


def _flatten_settings(settings: dict, prefix: str = "") -> Dict[str, Any]:
    """{"index": {"number_of_shards": 2}} → {"index.number_of_shards": 2}."""
    out: Dict[str, Any] = {}
    for k, v in settings.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten_settings(v, key + "."))
        else:
            out[key] = v
    return out


def _parse_time_seconds(v) -> float:
    if isinstance(v, (int, float)):
        return float(v)
    s = str(v)
    m = re.fullmatch(r"(\d+(?:\.\d+)?)(ms|s|m|h|d)?", s)
    if not m:
        raise IllegalArgumentError(f"failed to parse time value [{v}]")
    mult = {"ms": 0.001, "s": 1.0, "m": 60.0, "h": 3600.0,
            "d": 86400.0}.get(m.group(2) or "s", 1.0)
    return float(m.group(1)) * mult


#: settings removed in 8.0 — using them is an error, not a no-op
#: (reference: IndexSettings deprecation/removal of translog retention)
_RETIRED_SETTING_PREFIXES = ("index.translog.retention.",
                             "translog.retention.")


def _reject_retired_settings(flat: Dict[str, Any]) -> None:
    for k in flat:
        if any(k.startswith(p) for p in _RETIRED_SETTING_PREFIXES):
            raise IllegalArgumentError(
                f"unknown setting [{k}] please check that any required "
                f"plugins are installed, or check the breaking changes "
                f"documentation for removed settings")


def empty_index_stats() -> Dict[str, Any]:
    """Zero-valued index stats tree — the full section/field shape of the
    reference's CommonStats serialization; IndexService.stats() fills in
    the live numbers and nodes-level rollups start from this so every
    section exists even with zero indices."""
    from ..search.microbatch import \
        empty_serving_stats as _empty_serving_stats
    zero_cache = {"memory_size_in_bytes": 0, "evictions": 0,
                  "hit_count": 0, "miss_count": 0}
    return {
        "docs": {"count": 0, "deleted": 0},
        "store": {"size_in_bytes": 0, "total_data_set_size_in_bytes": 0,
                  "reserved_in_bytes": 0},
        "indexing": {"index_total": 0, "index_time_in_millis": 0,
                     "index_current": 0, "index_failed": 0,
                     "delete_total": 0, "delete_time_in_millis": 0,
                     "delete_current": 0, "noop_update_total": 0,
                     "is_throttled": False, "throttle_time_in_millis": 0},
        "get": {"total": 0, "time_in_millis": 0, "exists_total": 0,
                "exists_time_in_millis": 0, "missing_total": 0,
                "missing_time_in_millis": 0, "current": 0},
        "search": {"open_contexts": 0, "query_total": 0,
                   "query_time_in_millis": 0, "query_current": 0,
                   "fetch_total": 0, "fetch_time_in_millis": 0,
                   "fetch_current": 0, "scroll_total": 0,
                   "scroll_time_in_millis": 0, "scroll_current": 0,
                   "suggest_total": 0, "suggest_time_in_millis": 0,
                   "suggest_current": 0},
        "merges": {"current": 0, "current_docs": 0,
                   "current_size_in_bytes": 0, "total": 0,
                   "total_time_in_millis": 0, "total_docs": 0,
                   "total_size_in_bytes": 0},
        "refresh": {"total": 0, "total_time_in_millis": 0,
                    "external_total": 0,
                    "external_total_time_in_millis": 0, "listeners": 0},
        "flush": {"total": 0, "periodic": 0, "total_time_in_millis": 0},
        "warmer": {"current": 0, "total": 0, "total_time_in_millis": 0},
        "query_cache": dict(zero_cache, total_count=0, cache_size=0,
                            cache_count=0),
        "fielddata": {"memory_size_in_bytes": 0, "evictions": 0},
        "completion": {"size_in_bytes": 0},
        "segments": {"count": 0, "memory_in_bytes": 0,
                     "terms_memory_in_bytes": 0,
                     "stored_fields_memory_in_bytes": 0,
                     "doc_values_memory_in_bytes": 0,
                     "index_writer_memory_in_bytes": 0,
                     "version_map_memory_in_bytes": 0,
                     "fixed_bit_set_memory_in_bytes": 0,
                     "max_unsafe_auto_id_timestamp": -1, "file_sizes": {}},
        "translog": {"operations": 0, "size_in_bytes": 0,
                     "uncommitted_operations": 0,
                     "uncommitted_size_in_bytes": 0,
                     "earliest_last_modified_age": 0},
        "request_cache": dict(zero_cache),
        # serving-pipeline observability (search/microbatch.py): per-stage
        # time totals + dispatch/coalescing counters + plane-path cache +
        # generation maintenance (rebuild storms must be visible)
        "plane_serving": dict(_empty_serving_stats(),
                              cache_hit_count=0, cache_miss_count=0,
                              rebuilds_sync=0, rebuilds_background=0,
                              delta_served_queries=0),
        "recovery": {"current_as_source": 0, "current_as_target": 0,
                     "throttle_time_in_millis": 0},
        "bulk": {"total_operations": 0, "total_time_in_millis": 0,
                 "total_size_in_bytes": 0, "avg_time_in_millis": 0,
                 "avg_size_in_bytes": 0},
    }
