"""Shard-level search execution: query phase + knn + sort + fetch.

Re-design of the reference's shard search entry
(``search/SearchService.java:378 executeQueryPhase`` →
``search/query/QueryPhase.java:132`` → per-segment collectors). Here the
"collector" is data-parallel: every segment is scored eagerly to dense
(scores, mask) arrays by the query tree (``query_dsl.py``), top-k hits are
selected on device per segment (``ops/topk.py``), and the tiny per-segment
candidate lists are merged on the host (score desc, then segment/doc id asc —
Lucene's tie-break order). Field sorting builds normalized sort-key columns
and lexsorts matched docs; ``knn`` runs the brute-force einsum per segment
and merges with the query's candidates (hybrid score sum, or reciprocal
rank fusion under ``rank.rrf``). A body with ``knn`` and no ``query`` runs
no query phase, as in the reference: its hits and ``hits.total`` are the
kNN rankings'; the phase still runs where ``aggs`` or a field sort read
its masks."""

from __future__ import annotations

import json
import time as _time
from dataclasses import dataclass, field as dc_field
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..common import tracing as _tracing
from ..common.errors import IllegalArgumentError, ParsingError
from ..index.mapping import (DateFieldType, DenseVectorFieldType,
                             KeywordFieldType, MapperService, NumberFieldType,
                             RuntimeFieldType)
from ..index.segment import Segment
from ..ops.topk import get_topk_kernel
from ..utils.shapes import round_up_pow2
from .aggregations import (AggregationContext, BucketAggregator, TopHitsAgg,
                           parse_aggs, run_aggregations)
from .fetch import docvalue_fields, filter_source, highlight
from .query_dsl import (MatchAllQuery, ShardContext, _vector_similarity,
                        parse_query)

_MISSING_LAST = float("inf")


def _attribute_dispatch(stages: Optional[dict],
                        info: Optional[dict]) -> None:
    """Charge one micro-batch dispatch to the request's task ledger
    (``node/task_manager.TaskResources``, contextvars-bound at the REST
    edge): host CPU since the last boundary, the dispatch's device
    wall-ms, its transfer-byte share and the docs it scanned (base
    corpus + delta tier). O(1) per dispatch; no-op outside any task."""
    from ..node.task_manager import current_resources
    res = current_resources()
    if res is None:
        return
    res.cpu_checkpoint()
    stages = stages or {}
    info = info or {}
    res.add(device_ms=float(stages.get("dispatch", 0.0)),
            h2d_bytes=int(info.get("h2d_bytes", 0)),
            d2h_bytes=int(info.get("d2h_bytes", 0)),
            docs_scanned=int(info.get("docs_scanned", 0)),
            delta_docs_scanned=int(info.get("delta_docs", 0)),
            dispatches=1)


def _dispatch_attrs(span, stages: dict, info: dict) -> None:
    """A dispatch span's attributes once the dispatch has come back: the
    request's stage timings and the batcher's stamp (batch size,
    ``compile_cache``, ``dispatch_seq``: the number of the dispatch that
    carried it, as its timeline record and ``batch[...]`` spans bear
    it)."""
    if span is not None:
        span.attrs.update({s: round(ms, 3) for s, ms in stages.items()
                           if isinstance(ms, (int, float))})
        span.attrs.update(info)


def _attribute_segment_scan(segments) -> None:
    """Per-segment (non-plane) query phase: the docs the eager scorers
    covered, plus a CPU boundary checkpoint."""
    from ..node.task_manager import current_resources
    res = current_resources()
    if res is None:
        return
    res.cpu_checkpoint()
    res.add(docs_scanned=sum(s.n_docs for s in segments))


def _collect_nested_inner_specs(spec, out: list,
                                join_out: Optional[list] = None) -> None:
    """Walk a raw query spec for nested / has_child / has_parent clauses
    carrying ``inner_hits`` (reference:
    ``InnerHitContextBuilder.extractInnerHits``)."""
    if isinstance(spec, dict):
        n = spec.get("nested")
        if isinstance(n, dict) and "inner_hits" in n:
            out.append(n)
        if join_out is not None:
            for kind in ("has_child", "has_parent"):
                j = spec.get(kind)
                if isinstance(j, dict) and "inner_hits" in j:
                    join_out.append((kind, j))
        for v in spec.values():
            _collect_nested_inner_specs(v, out, join_out)
    elif isinstance(spec, list):
        for v in spec:
            _collect_nested_inner_specs(v, out, join_out)


def _tree_needs_scores(aggs: dict) -> bool:
    for a in aggs.values():
        if isinstance(a, TopHitsAgg):
            return True
        if isinstance(a, BucketAggregator) and _tree_needs_scores(a.subs):
            return True
    return False


@dataclass
class ShardHit:
    doc_id: str
    score: Optional[float]
    seg_idx: int
    local_doc: int
    source: Optional[dict]
    sort_values: Optional[List[Any]] = None
    seq_no: Optional[int] = None
    fields: Optional[Dict[str, List[Any]]] = None
    highlight: Optional[Dict[str, List[str]]] = None
    ignored: Optional[List[str]] = None
    inner_hits: Optional[Dict[str, dict]] = None


@dataclass
class ShardSearchResult:
    total: int
    total_relation: str
    hits: List[ShardHit]
    max_score: Optional[float]
    aggregations: Optional[Dict[str, Any]] = None
    profile: Optional[dict] = None
    suggest: Optional[Dict[str, list]] = None
    #: (segment, host mask, host scores | None) per segment — returned
    #: instead of reduced aggregations when the caller (the distributed
    #: coordinator) wants ONE global reduce across shards
    agg_inputs: Optional[List[Tuple[Segment, np.ndarray,
                                    Optional[np.ndarray]]]] = None
    #: per-shard partial failures (aggs that errored on one shard — the
    #: reference's ShardSearchFailure list; hits of failed shards are
    #: excluded, the rest of the response stands)
    shard_failures: Optional[List[dict]] = None
    #: per-stage serving-pipeline ms for plane-served queries (queue wait /
    #: host prep / device dispatch / fetch — microbatch.STAGES); None when
    #: the per-segment path served. Slow-log entries carry this so a slow
    #: query is attributable to a stage.
    serving_stages: Optional[Dict[str, float]] = None
    #: one-dispatch planner verdict for this request ({outcome,
    #: lower_ms, stages_per_dispatch}); None when the planner was never
    #: consulted. Slow-log entries carry this so a slow fused query
    #: names its route without re-running with profile:true.
    planner: Optional[dict] = None


def _knn_score_transform(similarity: str, sim):
    """Raw similarity → ES _score (reference: DenseVectorFieldMapper docs /
    KnnVectorQuery score translation)."""
    if similarity in ("cosine", "cos"):
        return (1.0 + sim) / 2.0
    if similarity == "dot_product":
        return (1.0 + sim) / 2.0
    if similarity == "max_inner_product":
        return jnp.where(sim < 0, 1.0 / (1.0 - sim), sim + 1.0)
    # l2_norm: sim here is the distance
    return 1.0 / (1.0 + sim * sim)


class ShardSearcher:
    """Executes one search request against one shard's segment list.

    ``plane_provider``: optional ``field -> DistributedSearchPlane | None``
    hook (``plane_route.ServingPlaneCache``). When set, eligible bag-of-
    terms queries execute through the tiered TPU plane — the production
    scorer — instead of the per-segment eager path; everything else
    (fetch, error shapes, pagination) is shared."""

    def __init__(self, segments: List[Segment], mapper: MapperService,
                 plane_provider=None, knn_plane_provider=None,
                 fused_provider=None):
        self.segments = [s for s in segments if s.n_docs > 0]
        self.mapper = mapper
        self.ctx = ShardContext(self.segments, mapper)
        self.plane_provider = plane_provider
        #: optional ``(segments, field) -> DistributedKnnPlane | None``
        #: hook: eligible knn clauses run through the blocked device plane
        #: (pack-time invariants + streaming top-k) with query_vector
        #: micro-batching across concurrent requests
        self.knn_plane_provider = knn_plane_provider
        #: optional ``(segments, text_field, knn_field|None) ->
        #: FusedPlanRunner | None`` hook
        #: (``plane_route.ServingPlaneCache.fused_runner_for``): bodies
        #: the query planner can lower (bool tree + knn + rescore) run
        #: as ONE fused dispatch over both serving generations instead
        #: of two dispatches + host fusion
        self.fused_provider = fused_provider

    # ------------------------------------------------------------------
    # knn
    # ------------------------------------------------------------------

    @staticmethod
    def _knn_score_from_raw(similarity: str, raw: float) -> float:
        """Plane raw similarity → ES _score (host-side scalar form of
        :func:`_knn_score_transform`; the plane's l2 raw is ``-‖q-v‖²``,
        clamped at 0 for float cancellation)."""
        if similarity in ("cosine", "cos", "dot_product"):
            return (1.0 + raw) / 2.0
        if similarity == "max_inner_product":
            return 1.0 / (1.0 - raw) if raw < 0 else raw + 1.0
        return 1.0 / (1.0 + max(0.0, -raw))        # l2_norm

    def _knn_candidates(self, spec: dict,
                        serving_out: Optional[list] = None
                        ) -> List[Tuple[float, int, int]]:
        """Brute-force kNN for one knn clause: einsum per segment + top-k
        (reference: the 8.x ``_knn_search``/``knn`` section; scoring per
        ``x-pack/plugin/vectors`` brute force, but one matmul per segment
        instead of a per-doc script loop). ``serving_out`` receives the
        kNN plane dispatch's stage timings + metadata when that route
        served (the Profile API's ``serving_knn`` section)."""
        field = spec.get("field")
        qv = spec.get("query_vector")
        if field is None or qv is None:
            raise ParsingError("knn requires [field] and [query_vector]")
        k = int(spec.get("k", 10))
        num_candidates = int(spec.get("num_candidates", max(k, 10)))
        boost = float(spec.get("boost", 1.0))
        # ANN accuracy knobs (num_candidates-style): nprobe = IVF
        # clusters visited per query (0 forces the exact scan), rerank =
        # exact-re-scoring window factor. Inert on the per-segment path
        # and on planes below the IVF corpus threshold (brute force).
        nprobe = spec.get("nprobe")
        if nprobe is not None:
            nprobe = int(nprobe)
            if nprobe < 0:
                raise IllegalArgumentError(
                    f"[knn] [nprobe] must be non-negative, got [{nprobe}]")
        rerank = spec.get("rerank")
        if rerank is not None:
            rerank = int(rerank)
            if rerank < 1:
                raise IllegalArgumentError(
                    f"[knn] [rerank] must be positive, got [{rerank}]")
        ft = self.mapper.field_type(field)
        if not isinstance(ft, DenseVectorFieldType):
            raise IllegalArgumentError(
                f"[knn] field [{field}] is not a dense_vector field")
        sim_kind = {"cosine": "cosineSimilarity", "dot_product": "dotProduct",
                    "l2_norm": "l2norm",
                    "max_inner_product": "dotProduct"}[ft.similarity] \
            if ft.similarity in ("cosine", "dot_product", "l2_norm",
                                 "max_inner_product") else "cosineSimilarity"
        filt = spec.get("filter")
        filter_q = parse_query(filt) if filt else None
        qv = np.asarray(qv, np.float32)

        # --- knn plane route (the production vector kernel) ---------------
        # Filter-free clauses over clean segments (no deletes / nested)
        # run through the DistributedKnnPlane: corpus invariants packed
        # once, blocked streaming top-k, and concurrent requests coalesce
        # their query_vector batches into one dispatch (microbatch.py).
        if (self.knn_plane_provider is not None and filter_q is None
                and num_candidates >= k):
            plane = self.knn_plane_provider(self.segments, field)
            if plane is not None:
                from .microbatch import batched_knn_search
                knn_stages: Dict[str, float] = {}
                knn_info: Dict[str, object] = {}
                with _tracing.span("plane_dispatch") as dsp:
                    raw, phits = batched_knn_search(plane, qv,
                                                    k=num_candidates,
                                                    view=self.segments,
                                                    stages=knn_stages,
                                                    info=knn_info,
                                                    nprobe=nprobe,
                                                    rerank=rerank)
                    _dispatch_attrs(dsp, knn_stages, knn_info)
                _attribute_dispatch(knn_stages, knn_info)
                if serving_out is not None:
                    serving_out.append({
                        "stages_ms": {s: round(ms, 3)
                                      for s, ms in knn_stages.items()},
                        **knn_info})
                cands = [
                    (self._knn_score_from_raw(ft.similarity, float(v))
                     * boost, si, d)
                    for v, (si, d) in zip(raw, phits)]
                # monotone transforms preserve the plane's (score desc,
                # shard asc, doc asc) order; re-sort for boost safety
                cands.sort(key=lambda c: (-c[0], c[1], c[2]))
                return cands[:k]

        pending = []
        for seg_idx, seg in enumerate(self.segments):
            sim, exists = _vector_similarity(sim_kind, qv, seg, field)
            scores = _knn_score_transform(ft.similarity, sim)
            mask = exists & seg.live_dev
            if seg.has_nested:
                mask = mask & seg.parent_mask_dev
            if filter_q is not None:
                _, fm = filter_q.execute(self.ctx, seg)
                mask = mask & fm
            kk = min(num_candidates, seg.n_pad)
            topk = get_topk_kernel(seg.n_pad, kk)
            vals_dev, idx_dev = topk(jnp.asarray(scores, jnp.float32), mask)
            pending.append((seg_idx, vals_dev, idx_dev))
        cands: List[Tuple[float, int, int]] = []
        for seg_idx, vals_dev, idx_dev in pending:
            vals = np.asarray(vals_dev)
            idx = np.asarray(idx_dev)
            ok = vals > float("-inf")
            for v, d in zip(vals[ok], idx[ok]):
                cands.append((float(v) * boost, seg_idx, int(d)))
        cands.sort(key=lambda c: (-c[0], c[1], c[2]))
        return cands[:k]

    # ------------------------------------------------------------------
    # sort keys
    # ------------------------------------------------------------------

    def _normalize_sort(self, sort_spec) -> List[dict]:
        return normalize_sort(sort_spec)

    def _sort_raw_for(self, clause: dict, seg_idx: int, seg: Segment,
                      docs: np.ndarray, scores: Optional[np.ndarray]):
        """Raw (un-normalized) sort values for matched docs of one segment:
        float64 array for numeric/_score/_doc, object array (str | None)
        for keyword fields."""
        field = clause["field"]
        if field not in ("_score", "_doc", "_shard_doc"):
            self.mapper.fielddata_loaded.add(field)
        if field == "_score":
            sc = scores[docs] if scores is not None else np.zeros(len(docs))
            return sc.astype(np.float64)
        if field == "_doc":
            return ((np.int64(seg_idx) << 32) +
                    docs.astype(np.int64)).astype(np.float64)
        ft = self.mapper.field_type(field)
        if isinstance(ft, RuntimeFieldType):
            return ft.column(seg)[docs]
        if isinstance(ft, DateFieldType) and ft.nanos:
            if clause.get("numeric_type") == "date":
                # unified ms domain requested: the float column suffices
                return seg.numeric_first_value_column(field)[docs]
            i64 = getattr(seg, "int64_fields", {}).get(
                ft.name if ft.name else field)
            vals = np.full(len(docs), None, dtype=object)
            if i64 is not None:
                idocs, ivals = i64
                first: Dict[int, int] = {}
                for d_, v_ in zip(idocs.tolist()[::-1],
                                  ivals.tolist()[::-1]):
                    first[d_] = v_
                for i, d_ in enumerate(docs):
                    vals[i] = first.get(int(d_))
            # exact ns longs as an object column: float64 loses the
            # bottom bits of ns-resolution epochs
            return vals
        nf = seg.numeric_fields.get(field)
        if nf is not None or isinstance(ft, (NumberFieldType, DateFieldType)):
            return seg.numeric_first_value_column(field)[docs]
        kf = seg.keyword_fields.get(field)
        vals = np.full(len(docs), None, dtype=object)
        if kf is not None:
            first_term: Dict[int, str] = {}
            for d, o in zip(kf.dv_docs_host[::-1], kf.dv_ords_host[::-1]):
                first_term[int(d)] = kf.ord_terms[int(o)]
            for i, d in enumerate(docs):
                vals[i] = first_term.get(int(d))
        return vals

    @staticmethod
    def _normalize_keys(clause: dict, raw: np.ndarray) -> np.ndarray:
        """Global ascending-normalized float64 key column. String values
        factorize over the *whole* candidate set (even codes, so a
        search_after cursor of an absent string can land between codes)."""
        desc = clause["order"] == "desc"
        missing_last = clause["missing"] != "_first"
        fill = _MISSING_LAST if (missing_last != desc) else -_MISSING_LAST
        if raw.dtype == object:
            uniq = sorted({v for v in raw if v is not None})
            code_of = {v: i * 2 for i, v in enumerate(uniq)}
            keys = np.asarray([code_of[v] if v is not None else fill
                               for v in raw], np.float64)
        else:
            keys = np.where(np.isnan(raw), fill, raw)
        return -keys if desc else keys

    # ------------------------------------------------------------------
    # main entry
    # ------------------------------------------------------------------

    def search(self, body: Optional[dict] = None, **kw) -> ShardSearchResult:
        """One shard-level search (:meth:`_search` has the arguments), as
        consecutive spans under the ambient one: ``shard[plan]``, then
        the dispatch span of the route taken (``plane_dispatch`` /
        ``fused_dispatch``) or ``shard[query_phase]`` (not for a knn-only
        body: ``shard[plan]``'s ``route`` is ``knn``), ``shard[knn]``,
        ``shard[rank]``, ``shard[fetch]``."""
        with _tracing.Phases() as phases:
            return self._search(phases, body, **kw)

    def _search(self, phases, body: Optional[dict] = None, *,
                size: int = 10,
                from_: int = 0, min_score: Optional[float] = None,
                track_total_hits=True,
                collect_agg_inputs: bool = False,
                knn_override: Optional[List[List[Tuple[float, int, int]]]]
                = None) -> ShardSearchResult:
        plan_span = phases.enter("shard[plan]")
        body = body or {}
        size = int(body.get("size", size))
        from_ = int(body.get("from", from_))
        min_score = body.get("min_score", min_score)
        track_total_hits = body.get("track_total_hits", track_total_hits)
        query_spec = body.get("query")
        knn_spec = body.get("knn")
        # block-max pruning knob (rank-safe WAND-as-a-scan on the plane
        # route): absent → pruned only when totals are already
        # approximate (Lucene disables WAND under exact total tracking);
        # true → force pruned (totals become "gte" lower bounds under an
        # early exit); false → force the eager scan
        prune_opt = body.get("prune")
        if prune_opt is not None and not isinstance(prune_opt, bool):
            raise IllegalArgumentError(
                f"[prune] must be a boolean, got [{prune_opt}]")
        query = parse_query(query_spec) if query_spec else MatchAllQuery()
        aggs_spec = body.get("aggs") or body.get("aggregations")
        aggs = parse_aggs(aggs_spec) if aggs_spec else None
        sort_spec = body.get("sort")
        search_after = body.get("search_after")
        rank_spec = body.get("rank")
        rescore_spec = body.get("rescore")
        collapse_spec = body.get("collapse")
        profile_on = bool(body.get("profile"))
        suggest_spec = body.get("suggest")
        t_query0 = _time.perf_counter() if profile_on else 0.0

        use_field_sort = bool(sort_spec) and self._normalize_sort(
            sort_spec)[0]["field"] != "_score"

        k = size + from_
        # window widened for search_after-less deep pagination handled by
        # caller; knn/rrf need their own candidate windows
        window = k
        if rank_spec and "rrf" in rank_spec:
            window = max(window, int(rank_spec["rrf"].get(
                "rank_window_size", max(k, 10))))
        if rescore_spec:
            if use_field_sort:
                raise IllegalArgumentError(
                    "Cannot use [sort] option in conjunction with "
                    "[rescore].")
            for rs in (rescore_spec if isinstance(rescore_spec, list)
                       else [rescore_spec]):
                mode = (rs.get("query") or {}).get("score_mode", "total")
                if mode not in ("total", "multiply", "avg", "max", "min"):
                    # parse-time validation, not data-dependent
                    raise IllegalArgumentError(
                        f"[rescore] illegal score_mode [{mode}]")
                window = max(window, int(rs.get("window_size", 10)))
        if collapse_spec:
            # exact collapse needs the full ranking: every group's best hit
            # must be visible (the reference's grouping collector sees all
            # matches; here the per-segment top-k window opens fully)
            window = 1 << 30

        # --- plane route (the production TPU kernel) ----------------------
        # Eligible bag-of-terms queries run through the tiered distributed
        # plane: one dispatch returns top-k AND exact totals. The provider
        # hands back a serving GENERATION (packed base + append-only delta
        # tier merged per dispatch — plane_route.py), or None both when
        # the route is ineligible and while a structural change (merge/
        # delete) has the base mid-repack on the background thread — the
        # per-segment path below serves the gap. Features that need per-doc
        # masks (aggs, field sort) or reordering (rescore, collapse,
        # search_after cursors) stay on the per-segment path.
        plane_route = None
        if (self.plane_provider is not None and query_spec
                and knn_override is None and window > 0
                and min_score is None and search_after is None):
            from .plane_route import body_eligible, extract_bag_of_terms
            # body_eligible re-checks body-carried features; the kwargs
            # variants (min_score/search_after above) are checked directly
            if body_eligible(body):
                ext = extract_bag_of_terms(query_spec, self.mapper)
                if ext is not None:
                    plane = self.plane_provider(self.segments, ext[0])
                    if plane is not None:
                        plane_route = (plane, ext[1])

        # --- fused one-dispatch route (the query planner) -----------------
        # A lowerable bool tree / hybrid knn / rescore pipeline executes
        # as ONE fused dispatch over the serving generations
        # (search/query_planner.py) instead of two dispatches + host
        # fusion; anything the planner or its runner cannot serve falls
        # through to the existing paths below unchanged.
        fused_result = None
        fused_plan = None
        fused_aggs = None
        planner_consulted = False
        shape_id = None
        if (self.fused_provider is not None and query_spec
                and knn_override is None
                and (window > 0 or aggs is not None)
                and min_score is None and search_after is None
                and not use_field_sort and not collect_agg_inputs):
            from . import query_planner as qp
            if qp.planner_enabled():
                planner_consulted = True
                fused_plan = qp.lower_body(body, self.mapper)
                if fused_plan is not None:
                    # upgrade the request's ambient shape id from the
                    # structural fingerprint (bound at the index-service
                    # edge) to the plan-based one BEFORE any dispatch
                    # enqueues, so micro-batch slots and journal events
                    # carry the same id the slow log will
                    from . import query_insight as _qi
                    from ..common import flightrec as _fr
                    shape_id = _qi.shape_of(body, plan=fused_plan)
                    _fr.set_shape(shape_id)
                runner = None
                if fused_plan is not None:
                    runner = self.fused_provider(
                        self.segments, fused_plan.field,
                        fused_plan.knn.field
                        if fused_plan.knn is not None else None)
                if fused_plan is not None and runner is not None and \
                        runner.can_serve(fused_plan):
                    if prune_opt is None:
                        fprune = False if track_total_hits is True \
                            else None
                    else:
                        fprune = prune_opt
                    from .microbatch import batched_fused_search
                    fstages: Dict[str, float] = {}
                    finfo: Dict[str, object] = {}
                    if plan_span is not None:
                        plan_span.attrs["route"] = "fused"
                    phases.close()
                    with _tracing.span("fused_dispatch") as dsp:
                        try:
                            fused_result = batched_fused_search(
                                runner, qp.make_item(fused_plan),
                                view=self.segments, stages=fstages,
                                info=finfo, prune=fprune)
                        except qp.FusedFallback:
                            fused_result = None
                        _dispatch_attrs(dsp, fstages, finfo)
                from ..common import telemetry as _tm
                _tm.record_planner(
                    "fused" if fused_result is not None
                    else "fallback")
        # the planner's verdict + lowering cost, shared by the Profile
        # API section below and the slow-log entry (ShardSearchResult.
        # planner): a slow fused dispatch is bisectable from its
        # slow-log line alone
        planner_doc = None
        if planner_consulted:
            planner_doc = {
                "outcome": ("fused" if fused_result is not None
                            else "fallback"),
                "lower_ms": round(fused_plan.lower_ms, 3)
                if fused_plan is not None else None,
                "stages_per_dispatch": fused_plan.n_stages()
                if fused_plan is not None else None,
                "shape": shape_id,
            }

        # --- knn route: no query phase -----------------------------------
        # With no `query`, and kNN rankings to come (the body's `knn`, or
        # the coordinator's knn_override), the rank section below takes
        # its candidates and its total from those rankings alone and
        # drops the query phase's. What else reads the phase: aggs
        # (agg_pending: masks and scores, for the reduce here or as the
        # collect_agg_inputs payload) and a field sort (host_masks /
        # host_scores). With neither, nothing reads it and it does not
        # run (the reference runs no match_all for such a body either).
        knn_only = (not query_spec
                    and bool(knn_spec if knn_override is None
                             else knn_override)
                    and aggs is None and not use_field_sort)

        # --- query phase (device) -----------------------------------------
        pending = []
        agg_pending = []
        host_masks: Dict[int, np.ndarray] = {}
        host_scores: Dict[int, np.ndarray] = {}
        need_host_mask = use_field_sort
        serving_stages: Optional[Dict[str, float]] = None
        serving_info: Optional[Dict[str, object]] = None
        plane_total_gte = False
        if plan_span is not None:
            plan_span.attrs["route"] = (
                "fused" if fused_result is not None
                else "plane" if plane_route is not None
                else "knn" if knn_only else "segments")
        if fused_result is not None:
            # the fused dispatch already ran the whole retrieval
            # pipeline (bool scoring, knn, fusion, rescore): its rows
            # ARE the candidates, its lexical count the total, and the
            # knn/rescore sections below must not run again
            fvals, fhits, ftotal = fused_result[:3]
            # an agg-carrying fused dispatch returns its analytics
            # stages' result as a 4th element (agg_planner.py)
            if len(fused_result) > 3:
                fused_aggs = fused_result[3]
            serving_stages = fstages
            serving_info = finfo
            from ..parallel.dist_search import (total_is_lower_bound,
                                                total_value)
            plane_total_gte = total_is_lower_bound(ftotal)
            total = total_value(ftotal)
            candidates = [(float(v), si, d)
                          for v, (si, d) in zip(fvals, fhits)]
            knn_spec = None
            rescore_spec = None
            rank_spec = None
            _attribute_dispatch(serving_stages, serving_info)
        elif plane_route is not None:
            plane, bag_terms = plane_route
            # concurrent eligible queries coalesce into one device dispatch
            # (search/microbatch.py — the search-thread-pool analog); the
            # batcher stamps this request's per-stage pipeline timings and
            # dispatch metadata (compile-cache hit/miss, batch size)
            from .microbatch import batched_search
            serving_stages = {}
            serving_info = {}
            # prune resolution: an explicit body knob wins; the default
            # prunes only when the request does not demand exact totals
            # (track_total_hits true = Lucene's complete-collection
            # mode, which disables WAND there too). An explicit
            # prune=false on a tier-bearing plane is benched-default
            # drift — counted for the plane_serving health indicator.
            if prune_opt is None:
                prune_eff = False if track_total_hits is True else None
            else:
                prune_eff = prune_opt
            if prune_opt is False and \
                    getattr(plane, "blockmax", None) is not None:
                from ..common.telemetry import record_lex
                record_lex(prune_off=True)
            # view=self.segments: hit coordinates must decode against
            # THIS searcher's snapshot even if a refresh mutates the
            # generation's delta while the request sits in the queue
            phases.close()
            with _tracing.span("plane_dispatch") as dsp:
                pvals0, phits0, ptotal0 = batched_search(
                    plane, bag_terms, k=max(window, 1),
                    stages=serving_stages, info=serving_info,
                    view=self.segments, prune=prune_eff)
                _dispatch_attrs(dsp, serving_stages, serving_info)
            from ..parallel.dist_search import (total_is_lower_bound,
                                                total_value)
            plane_total_gte = total_is_lower_bound(ptotal0)
            total = total_value(ptotal0)
            candidates = [(float(v), si, d)
                          for v, (si, d) in zip(pvals0, phits0)]
            _attribute_dispatch(serving_stages, serving_info)
        elif knn_only:
            total = 0
            candidates: List[Tuple[float, int, int]] = []
        else:
            # the per-segment eager scorers; a body with no query scores
            # match_all here (knn + aggs, knn + field sort, empty body)
            phases.enter("shard[query_phase]", segments=len(self.segments),
                         has_query=bool(query_spec))
            for seg_idx, seg in enumerate(self.segments):
                scores, mask = query.execute(self.ctx, seg)
                mask = mask & seg.live_dev
                if seg.has_nested:
                    # hidden block-join children never surface at top level
                    mask = mask & seg.parent_mask_dev
                if min_score is not None:
                    mask = mask & (scores >= np.float32(min_score))
                count_dev = jnp.sum(mask) if track_total_hits is not False else None
                vals_dev = idx_dev = None
                # the sort path needs the query top-k only to combine with knn
                if window > 0 and (not use_field_sort or knn_spec):
                    # push the search_after cursor into the selection mask so
                    # the per-segment top-k window starts AFTER the cursor —
                    # otherwise docs tied on score beyond the global top-k are
                    # unreachable on later pages (totals/aggs keep the full mask)
                    sel_mask = mask
                    if search_after is not None and not use_field_sort \
                            and not knn_spec:
                        a_sc = jnp.float32(float(search_after[0]))
                        if len(search_after) > 1:
                            asd = int(search_after[1])
                            a_si, a_d = asd >> 32, asd & 0xFFFFFFFF
                            if seg_idx < a_si:
                                cond = scores < a_sc
                            elif seg_idx == a_si:
                                cond = (scores < a_sc) | (
                                    (scores == a_sc) &
                                    (jnp.arange(seg.n_pad) > a_d))
                            else:
                                cond = scores <= a_sc
                        else:
                            cond = scores < a_sc
                        sel_mask = mask & cond
                    kk = min(max(window, 1), seg.n_pad)
                    topk = get_topk_kernel(seg.n_pad, kk)
                    vals_dev, idx_dev = topk(scores, sel_mask)
                pending.append((seg_idx, count_dev, vals_dev, idx_dev))
                if aggs is not None:
                    agg_pending.append((seg, mask, scores))
                if need_host_mask:
                    host_masks[seg_idx] = np.asarray(mask)
                    if not use_field_sort or _sort_includes_score(sort_spec):
                        host_scores[seg_idx] = np.asarray(scores)

            total = 0
            candidates = []
            for seg_idx, count_dev, vals_dev, idx_dev in pending:
                if count_dev is not None:
                    total += int(count_dev)
                if vals_dev is not None:
                    vals = np.asarray(vals_dev)
                    idx = np.asarray(idx_dev)
                    ok = vals > float("-inf")
                    for v, d in zip(vals[ok], idx[ok]):
                        candidates.append((float(v), seg_idx, int(d)))
            candidates.sort(key=lambda c: (-c[0], c[1], c[2]))
            _attribute_segment_scan(self.segments)

        # --- knn section ---------------------------------------------------
        knn_rankings: List[List[Tuple[float, int, int]]] = []
        knn_serving: List[dict] = []
        if knn_override is not None:
            # the coordinator already reduced per-shard knn candidates to
            # the GLOBAL top-k and handed us this shard's slice
            knn_rankings = knn_override
        elif knn_spec:
            specs = knn_spec if isinstance(knn_spec, list) else [knn_spec]
            phases.enter("shard[knn]", clauses=len(specs))
            for spec in specs:
                knn_rankings.append(self._knn_candidates(
                    spec, serving_out=knn_serving if profile_on else None))

        phases.enter("shard[rank]")
        max_score: Optional[float] = None
        if knn_rankings:
            # ONE copy of the fusion arithmetic, shared with the fused
            # planner's host runner (query_planner) — the fused path's
            # bit-parity with this section holds by shared code
            from .query_planner import rrf_fuse_rows, sum_fuse_rows
            if rank_spec and "rrf" in rank_spec:
                rc = int(rank_spec["rrf"].get("rank_constant", 60))
                rankings = ([candidates[:window]] if query_spec else []) \
                    + knn_rankings
                candidates = rrf_fuse_rows(rankings, rc)
            else:
                # hybrid: sum scores for docs in both result sets
                rankings = ([candidates] if query_spec else []) \
                    + knn_rankings
                candidates = sum_fuse_rows(rankings)
            if not query_spec:
                total = len(candidates)
            if use_field_sort:
                # knn + sort: the knn/hybrid result set IS the doc set; the
                # sort only orders it (reference: knn section + sort)
                restricted: Dict[int, np.ndarray] = {}
                for _, si, d in candidates:
                    m = restricted.get(si)
                    if m is None:
                        m = restricted[si] = np.zeros(
                            self.segments[si].n_pad, bool)
                    m[d] = True
                host_masks = {si: host_masks[si] & m if si in host_masks
                              else m for si, m in restricted.items()}
                total = len(candidates)

        # --- rescore (QueryRescorer.java: reorder the top window only) -----
        if rescore_spec and candidates:
            candidates = self._apply_rescore(rescore_spec, candidates)

        # --- ranking → page ------------------------------------------------
        if use_field_sort:
            page, sort_clauses = self._field_sorted_page(
                sort_spec, search_after, host_masks, host_scores, k,
                collapse_field=(collapse_spec or {}).get("field"))
            page = page[from_:]
            if track_total_hits is not False and not knn_rankings:
                total = sum(int(m[: self.segments[si].n_docs].sum())
                            for si, m in host_masks.items())
        else:
            sort_clauses = None
            if candidates:
                max_score = candidates[0][0]
            if search_after is not None:
                # search_after on _score desc. Hits carry a [score,
                # shard_doc] composite cursor (mirroring ES's implicit
                # _shard_doc tiebreak under PIT); when the client passes it
                # back, docs tied on score paginate correctly instead of
                # being skipped by a bare strict-< filter.
                after = float(search_after[0])
                if len(search_after) > 1:
                    after_sd = int(search_after[1])
                    candidates = [
                        c for c in candidates
                        if c[0] < after or
                        (c[0] == after and self._shard_doc(c[1], c[2])
                         > after_sd)]
                else:
                    candidates = [c for c in candidates if c[0] < after]
            if collapse_spec:
                candidates = self._collapse_candidates(
                    collapse_spec["field"], candidates)
            page = [(float(sc), si, d,
                     [float(sc), self._shard_doc(si, d)])
                    for sc, si, d in candidates[from_: from_ + size]]
        total_relation = "eq"
        if track_total_hits is False:
            total = len(page) if use_field_sort else len(candidates)
            total_relation = "gte" if total >= k else "eq"
        elif isinstance(track_total_hits, int) and not isinstance(
                track_total_hits, bool) and total > track_total_hits:
            total = track_total_hits
            total_relation = "gte"
        elif plane_total_gte:
            # block-max pruned dispatch early-exited: the skipped
            # blocks' docs were never counted — the total is an honest
            # lower bound (Lucene's WAND total semantics)
            total_relation = "gte"

        # --- fetch phase ---------------------------------------------------
        phases.enter("shard[fetch]", hits=len(page))
        source_spec = body.get("_source", True)
        stored = body.get("stored_fields")
        if stored is not None and "_source" not in body and \
                "_source" not in _as_list_(stored):
            # stored_fields [] / "_none_" / list without _source → no source
            source_spec = False
        if not self.mapper.source_enabled:
            source_spec = False
        dv_specs = body.get("docvalue_fields") or []
        field_specs = body.get("fields") or []
        hl_spec = body.get("highlight")
        hl_terms: Dict[str, set] = {}
        hl_field_terms: Dict[str, set] = {}
        if hl_spec:
            query.collect_highlight_terms(self.ctx, hl_terms)
            fs = hl_spec.get("fields", {})
            if isinstance(fs, list):
                merged_fs = {}
                for f_ in fs:
                    merged_fs.update(f_)
                fs = merged_fs
            for hf, hf_spec in fs.items():
                hq = (hf_spec or {}).get("highlight_query")
                if hq:
                    # per-field override query supplies THE terms
                    # (HighlightBuilder#highlightQuery)
                    ov: Dict[str, set] = {}
                    parse_query(hq).collect_highlight_terms(self.ctx, ov)
                    hl_field_terms[hf] = set().union(*ov.values()) \
                        if ov else set()
            hl_spec = dict(hl_spec, _field_terms=hl_field_terms,
                           _max_analyzed_offset=getattr(
                               self, "max_analyzed_offset", None))

        collapse_keyf = (self._collapse_key_fn(collapse_spec["field"])
                         if collapse_spec else None)
        hits = []
        for score, seg_idx, d, sort_values in page:
            seg = self.segments[seg_idx]
            src = seg.sources[d]
            hit = ShardHit(
                doc_id=seg.doc_uids[d], score=score, seg_idx=seg_idx,
                local_doc=d, source=filter_source(src, source_spec),
                sort_values=sort_values, seq_no=int(seg.seq_nos[d]))
            ign = seg.keyword_fields.get("_ignored")
            if ign is not None and ign.dv_docs_host.size:
                # dv pairs are doc-sorted: O(log M) slice per hit
                lo_i = int(np.searchsorted(ign.dv_docs_host, d, "left"))
                hi_i = int(np.searchsorted(ign.dv_docs_host, d, "right"))
                if hi_i > lo_i:
                    hit.ignored = [ign.ord_terms[o] for o in
                                   ign.dv_ords_host[lo_i:hi_i]]
            if dv_specs:
                hit.fields = docvalue_fields(seg, self.mapper, d, dv_specs)
            if field_specs:
                from .fetch import fetch_fields
                hit.fields = dict(fetch_fields(self.mapper, src,
                                               field_specs),
                                  **(hit.fields or {}))
            stored_list = [f for f in _as_list_(stored or [])
                           if f not in ("_none_", "_source")]
            if stored_list:
                from .fetch import fetch_fields
                hit.fields = dict(fetch_fields(self.mapper, src,
                                               stored_list),
                                  **(hit.fields or {}))
            if collapse_keyf is not None:
                kv = collapse_keyf(seg_idx, d)
                hit.fields = dict(hit.fields or {},
                                  **{collapse_spec["field"]: [kv]})
            if hl_spec:
                hit.highlight = highlight(self.mapper, src, hl_spec, hl_terms)
            hits.append(hit)

        ih_specs: List[dict] = []
        join_specs: List[tuple] = []
        _collect_nested_inner_specs(query_spec, ih_specs, join_specs)
        if ih_specs and hits:
            self._attach_nested_inner_hits(hits, ih_specs)
        if join_specs and hits:
            self._attach_join_inner_hits(hits, join_specs)

        agg_results = None
        agg_inputs = None
        if aggs is not None and collect_agg_inputs:
            need_scores = _tree_needs_scores(aggs)
            agg_inputs = [(seg, np.asarray(m),
                           np.asarray(sc) if need_scores else None)
                          for seg, m, sc in agg_pending]
        elif fused_aggs is not None:
            # the fused dispatch's agg stages already reduced this
            # shard's tree (same collect/reduce code — agg_planner.py):
            # the legacy second pass below must not run again
            agg_results = fused_aggs
        elif aggs is not None:
            seg_scores = ({seg.seg_id: np.asarray(sc)
                           for seg, _, sc in agg_pending}
                          if _tree_needs_scores(aggs) else {})
            agg_ctx = AggregationContext(self.mapper, shard_ctx=self.ctx,
                                         seg_scores=seg_scores)
            seg_masks = [(seg, np.asarray(m)) for seg, m, _ in agg_pending]
            agg_results = run_aggregations(aggs, agg_ctx, seg_masks)

        suggest_out = None
        if suggest_spec:
            from .suggest import run_suggest
            suggest_out = run_suggest(self.ctx, suggest_spec)

        profile_out = None
        if profile_on:
            # per-request query-phase timing (search/profile/Profilers.java
            # — segment-level collectors folded into one query node)
            total_nanos = int((_time.perf_counter() - t_query0) * 1e9)
            shard_prof = {
                "id": "[tpu][0]",
                "searches": [{
                    "query": [{
                        "type": type(query).__name__,
                        "description": json.dumps(query_spec or
                                                  {"match_all": {}}),
                        "time_in_nanos": total_nanos,
                        "breakdown": {
                            "segments": len(self.segments),
                            "score_mode": ("field_sort" if use_field_sort
                                           else "score"),
                        },
                    }],
                    "rewrite_time": 0,
                    "collector": [{
                        "name": ("PlaneMicroBatchCollector"
                                 if serving_stages is not None
                                 else "EagerDenseCollector"),
                        "reason": "search_top_hits",
                        "time_in_nanos": total_nanos,
                    }],
                }],
                "aggregations": build_agg_profile(
                    aggs or {}, agg_results, self.mapper, self.segments,
                    sum(int(np.asarray(m)[: seg.n_docs].sum())
                        for seg, m, _ in agg_pending)) if aggs else [],
            }
            if serving_stages is not None:
                # the real plane path: per-stage pipeline timings + this
                # dispatch's compile-cache verdict — the Profile API now
                # reflects serving, not just host-side query rewriting
                shard_prof["serving"] = {
                    "stages_ms": {s: round(ms, 3)
                                  for s, ms in serving_stages.items()},
                    **(serving_info or {})}
                # the query shape id joins this profile to its
                # /_insights/top_queries row and flight-recorder events
                from ..common import flightrec as _fr
                prof_shape = shape_id or _fr.current_shape()
                if prof_shape:
                    shard_prof["serving"]["shape"] = prof_shape
            if knn_serving:
                # the knn plane's own dispatches (one per knn clause): a
                # knn-only or two-dispatch hybrid request has no lexical
                # plane dispatch to report under "serving"
                shard_prof["serving_knn"] = knn_serving
            if planner_doc is not None:
                # the one-dispatch planner's verdict + lowering cost:
                # operators bisecting a fused-path regression see which
                # route served and what the compile step of the request
                # (host-side lowering) cost
                shard_prof["planner"] = planner_doc
                if serving_stages is not None and \
                        fused_result is not None:
                    shard_prof["serving"]["planner"] = planner_doc
            profile_out = {"shards": [shard_prof]}

        return ShardSearchResult(total=total, total_relation=total_relation,
                                 hits=hits, max_score=max_score,
                                 aggregations=agg_results,
                                 agg_inputs=agg_inputs,
                                 profile=profile_out, suggest=suggest_out,
                                 serving_stages=serving_stages or None,
                                 planner=planner_doc)

    def _attach_nested_inner_hits(self, hits: List[ShardHit],
                                  ih_specs: List[dict]) -> None:
        """Per root hit, the matching CHILD rows of each nested clause
        that asked for inner_hits (reference:
        ``search/fetch/subphase/InnerHitsPhase.java`` re-running the
        child query per fetched root). The child query executes once per
        segment; per-hit work is a parent-id filter over its matches."""
        from .fetch import docvalue_fields as _dvf
        from .query_dsl import parse_query as _pq
        index_name = getattr(self.mapper, "index_name", None)
        for spec in ih_specs:
            path = spec.get("path")
            ih = spec.get("inner_hits") or {}
            name = ih.get("name") or path
            size = int(ih.get("size", 3))
            from_ = int(ih.get("from", 0))
            inner_q = _pq(spec.get("query") or {"match_all": {}})
            per_seg: Dict[int, tuple] = {}
            for hit in hits:
                si = hit.seg_idx
                seg = self.segments[si]
                if si not in per_seg:
                    pm = seg.nested_paths.get(path)
                    if pm is None:
                        per_seg[si] = None
                    else:
                        s2, m2 = inner_q.execute(self.ctx, seg)
                        cm = np.zeros(seg.n_pad, bool)
                        cm[: seg.n_docs] = pm & seg.live[: seg.n_docs]
                        cm &= np.asarray(m2)
                        per_seg[si] = (np.asarray(s2), cm, pm)
                entry = per_seg[si]
                root = hit.local_doc
                if entry is None:
                    group = {"hits": {"total": {"value": 0,
                                                "relation": "eq"},
                                      "max_score": None, "hits": []}}
                else:
                    s2, cm, pm = entry
                    par = seg.parent_of[: seg.n_docs]
                    kids = np.flatnonzero(cm[: seg.n_docs] & (par == root))
                    siblings = np.flatnonzero(pm & (par == root))
                    order = np.lexsort((kids, -s2[kids])) \
                        if kids.size else np.empty(0, np.int64)
                    sel = kids[order][from_: from_ + size]
                    ihits = []
                    for c in sel:
                        off = int(np.searchsorted(siblings, c))
                        obj = seg.sources[root]
                        try:
                            for part in path.split("."):
                                obj = obj[part]
                            child_src = obj[off] \
                                if isinstance(obj, list) else obj
                        except (KeyError, IndexError, TypeError):
                            child_src = None
                        d = {"_index": index_name,
                             "_id": seg.doc_uids[root],
                             "_nested": {"field": path, "offset": off},
                             "_score": float(s2[c])}
                        if ih.get("_source") is not False:
                            d["_source"] = child_src
                        dvf = ih.get("docvalue_fields")
                        if dvf:
                            d["fields"] = _dvf(seg, self.mapper, int(c),
                                               dvf)
                        ihits.append(d)
                    mx = float(s2[sel].max()) if sel.size else None
                    group = {"hits": {
                        "total": {"value": int(kids.size),
                                  "relation": "eq"},
                        "max_score": mx, "hits": ihits}}
                if ih.get("version"):
                    group["_want_version"] = True
                hit.inner_hits = dict(hit.inner_hits or {},
                                      **{name: group})

    def _attach_join_inner_hits(self, hits: List[ShardHit],
                                join_specs: List[tuple]) -> None:
        """Per root hit, the matching related REAL docs of each
        has_child / has_parent clause that asked for inner_hits
        (reference: parent-join's ``ParentChildInnerHitContextBuilder``).
        Related docs share the root's shard (routing contract)."""
        from .query_dsl import (_join_field, _kw_values_by_doc,
                                parse_query)
        index_name = getattr(self.mapper, "index_name", None)
        jf = _join_field(self.ctx)
        if jf is None:
            return
        for kind, spec in join_specs:
            ih = spec.get("inner_hits") or {}
            rel = spec.get("type") if kind == "has_child" \
                else spec.get("parent_type")
            name = ih.get("name") or rel
            size = int(ih.get("size", 3))
            from_ = int(ih.get("from", 0))
            inner_q = parse_query(spec.get("query") or {"match_all": {}})
            per_seg: Dict[int, tuple] = {}
            for hit in hits:
                si = hit.seg_idx
                seg = self.segments[si]
                if si not in per_seg:
                    s2, m2 = inner_q.execute(self.ctx, seg)
                    rels = _kw_values_by_doc(seg, jf.name)
                    if kind == "has_child":
                        fam = _kw_values_by_doc(
                            seg, jf.id_field_for(rel))
                    else:
                        fam = _kw_values_by_doc(seg, f"{jf.name}#{rel}")
                    per_seg[si] = (np.asarray(s2), np.asarray(m2),
                                   rels, fam)
                s2, m2, rels, fam = per_seg[si]
                seg = self.segments[hit.seg_idx]
                sel: List[int] = []
                if kind == "has_child":
                    # inner hits = matching CHILD docs of this parent
                    for d, pid in fam.items():
                        if pid == hit.doc_id and rels.get(d) == rel \
                                and m2[d] and seg.live[d]:
                            sel.append(d)
                else:
                    # inner hits = this child's matching PARENT doc
                    my_pid = _kw_values_by_doc(
                        seg, f"{jf.name}#{rel}").get(hit.local_doc)
                    pd = seg.find_doc(my_pid) if my_pid else None
                    if pd is not None and rels.get(pd) == rel and \
                            m2[pd] and seg.live[pd]:
                        sel.append(pd)
                sel.sort(key=lambda d: (-float(s2[d]), d))
                window = sel[from_: from_ + size]
                ihits = []
                for d in window:
                    doc_out = {"_index": index_name,
                               "_id": seg.doc_uids[d],
                               "_score": float(s2[d])}
                    if ih.get("_source") is not False:
                        doc_out["_source"] = seg.sources[d]
                    if ih.get("seq_no_primary_term"):
                        doc_out["_seq_no"] = int(seg.seq_nos[d])
                        doc_out["_primary_term"] = 1
                    ihits.append(doc_out)
                group = {"hits": {
                    "total": {"value": len(sel), "relation": "eq"},
                    "max_score": (float(s2[window[0]]) if window
                                  else None),
                    "hits": ihits}}
                hit.inner_hits = dict(hit.inner_hits or {},
                                      **{name: group})

    @staticmethod
    def _shard_doc(seg_idx: int, doc: int) -> int:
        """Stable tiebreak key over (segment, doc) — ES's ``_shard_doc``."""
        return (seg_idx << 32) | doc

    # ------------------------------------------------------------------
    # rescore + collapse
    # ------------------------------------------------------------------

    def _apply_rescore(self, rescore_spec, candidates):
        """Second-pass scoring of the top window
        (``search/rescore/QueryRescorer.java``): the window reorders by
        ``query_weight·orig + rescore_query_weight·secondary``; ranks
        below the window keep their original order."""
        specs = rescore_spec if isinstance(rescore_spec, list) \
            else [rescore_spec]
        for spec in specs:
            body = spec.get("query") or {}
            rq_spec = body.get("rescore_query")
            if rq_spec is None:
                raise ParsingError("rescore requires [query.rescore_query]")
            qw = float(body.get("query_weight", 1.0))
            rw = float(body.get("rescore_query_weight", 1.0))
            mode = body.get("score_mode", "total")
            window = min(int(spec.get("window_size", 10)), len(candidates))
            rq = parse_query(rq_spec)
            seg_scores: Dict[int, np.ndarray] = {}
            seg_masks: Dict[int, np.ndarray] = {}
            needed = {si for _, si, _ in candidates[:window]}
            for si in needed:
                sc, m = rq.execute(self.ctx, self.segments[si])
                seg_scores[si] = np.asarray(sc)
                seg_masks[si] = np.asarray(m)
            rescored = []
            for sc, si, d in candidates[:window]:
                if seg_masks[si][d]:
                    rs = float(seg_scores[si][d])
                    if mode == "total":
                        ns = qw * sc + rw * rs
                    elif mode == "multiply":
                        ns = (qw * sc) * (rw * rs)
                    elif mode == "avg":
                        ns = (qw * sc + rw * rs) / 2.0
                    elif mode == "max":
                        ns = max(qw * sc, rw * rs)
                    else:                    # "min" (validated at parse)
                        ns = min(qw * sc, rw * rs)
                else:
                    ns = qw * sc
                rescored.append((ns, si, d))
            rescored.sort(key=lambda c: (-c[0], c[1], c[2]))
            # below the window, ranks hold but the primary weight still
            # applies (QueryRescorer keeps score*queryWeight there)
            tail = [(qw * sc, si, d) for sc, si, d in candidates[window:]]
            candidates = rescored + tail
        return candidates

    def _collapse_key_fn(self, field: str):
        """(seg_idx, doc) → group key for the collapse field (first value;
        None groups together, like the reference's null group)."""
        ft = self.mapper.field_type(field)
        if ft is not None and ft.name != field:
            field = ft.name             # alias → concrete column

        if isinstance(ft, KeywordFieldType):
            tables: Dict[int, Dict[int, str]] = {}

            def key(si, d):
                t = tables.get(si)
                if t is None:
                    t = tables[si] = {}
                    kf = self.segments[si].keyword_fields.get(field)
                    if kf is not None:
                        for doc, o in zip(kf.dv_docs_host[::-1],
                                          kf.dv_ords_host[::-1]):
                            t[int(doc)] = kf.ord_terms[int(o)]
                return t.get(d)
            return key

        def nkey(si, d):
            v = self.segments[si].numeric_first_value_column(field)[d]
            return None if np.isnan(v) else float(v)
        return nkey

    def _collapse_candidates(self, field: str, candidates):
        keyf = self._collapse_key_fn(field)
        return collapse_first_by_key(candidates,
                                     lambda c: keyf(c[1], c[2]))

    def _field_sorted_page(self, sort_spec, search_after, host_masks,
                           host_scores, k, collapse_field=None):
        """Sorted query path: lexsort matched docs on normalized keys
        (reference: ``search/sort/SortBuilder`` → Lucene ``SortField``).

        An implicit trailing ``_doc`` tiebreak is always appended (the
        reference's PIT ``_shard_doc``): without it, docs exactly tied on
        every user sort key at a page boundary are skipped by the strict
        search_after tuple filter. Cursors may carry the tiebreak value or
        omit it (legacy strict-tuple semantics)."""
        clauses = self._normalize_sort(sort_spec)
        n_user = len(clauses)
        if clauses[-1]["field"] != "_doc":
            clauses.append({"field": "_doc", "order": "asc",
                            "missing": "_last"})
        if search_after is not None and len(search_after) == n_user \
                and len(clauses) == n_user + 1:
            # no tiebreak in the cursor: exclude all equal-prefix rows
            search_after = list(search_after) + [float("inf")]
        all_rows = []       # (seg_idx, doc)
        raw_cols = [[] for _ in clauses]
        for seg_idx, seg in enumerate(self.segments):
            m = host_masks.get(seg_idx)
            if m is None:
                continue
            docs = np.flatnonzero(m[: seg.n_docs])
            if docs.size == 0:
                continue
            scores = host_scores.get(seg_idx)
            for ci, clause in enumerate(clauses):
                raw_cols[ci].append(self._sort_raw_for(
                    clause, seg_idx, seg, docs, scores))
            all_rows.extend((seg_idx, int(d)) for d in docs)
        if not all_rows:
            return [], clauses
        raws = [np.concatenate(c) for c in raw_cols]
        keys = [self._normalize_keys(clause, raw)
                for clause, raw in zip(clauses, raws)]
        n = len(all_rows)
        keep = np.ones(n, bool)
        if search_after is not None:
            if len(search_after) != len(clauses):
                raise IllegalArgumentError(
                    f"search_after must have {len(clauses)} values")
            eq_prefix = np.ones(n, bool)
            gt_any = np.zeros(n, bool)
            for ci, clause in enumerate(clauses):
                after_key = self._after_key(clause, search_after[ci],
                                            raws[ci], keys[ci])
                gt_any |= eq_prefix & (keys[ci] > after_key)
                eq_prefix &= keys[ci] == after_key
            keep = gt_any
        idx = np.flatnonzero(keep)
        order = np.lexsort(tuple(keys[ci][idx] for ci in
                                 range(len(clauses) - 1, -1, -1)))
        if collapse_field is not None:
            keyf = self._collapse_key_fn(collapse_field)
            seen = set()
            kept = []
            for i in idx[order]:
                si, d = all_rows[i]
                kv = keyf(si, d)
                if kv in seen:
                    continue
                seen.add(kv)
                kept.append(i)
                if len(kept) >= k:
                    break
            top = np.asarray(kept, dtype=np.int64)
        else:
            top = idx[order[:k]]
        page = []
        for i in top:
            seg_idx, d = all_rows[i]
            sort_values = []
            for ci, clause in enumerate(clauses):
                v = raws[ci][i]
                if isinstance(v, float) and np.isnan(v):
                    sort_values.append(None)
                elif isinstance(v, (np.floating, np.integer)):
                    fv = float(v)
                    sort_values.append(int(fv) if fv.is_integer() else fv)
                else:
                    sort_values.append(v)
            score = None
            for ci, clause in enumerate(clauses):
                if clause["field"] == "_score":
                    score = float(raws[ci][i])
            page.append((score, seg_idx, d, sort_values))
        return page, clauses

    def _after_key(self, clause, after_value, raw_col, key_col):
        """Normalize a search_after cursor value into key space."""
        field = clause["field"]
        desc = clause["order"] == "desc"
        if after_value is None:
            # same fill + desc negation as _normalize_keys, so a null cursor
            # lands exactly on the missing block's key
            missing_last = clause["missing"] != "_first"
            fill = _MISSING_LAST if (missing_last != desc) else -_MISSING_LAST
            return -fill if desc else fill
        if raw_col.dtype != object and (
                field == "_score" or field == "_doc" or isinstance(
                    after_value, (int, float))):
            v = float(after_value)
            return -v if desc else v
        # object-column cursor (strings, exact ns longs): odd/even code
        # trick — present values have even codes; an absent cursor value
        # lands between codes
        uniq = sorted({v for v in raw_col if v is not None})
        import bisect
        i = bisect.bisect_left(uniq, after_value)
        if i < len(uniq) and uniq[i] == after_value:
            code = i * 2
        else:
            code = i * 2 - 1
        return -code if desc else code

    def count(self, body: Optional[dict] = None) -> int:
        body = body or {}
        query = (parse_query(body["query"]) if body.get("query")
                 else MatchAllQuery())
        total = 0
        for seg in self.segments:
            _, mask = query.execute(self.ctx, seg)
            mask = mask & seg.live_dev
            if seg.has_nested:
                mask = mask & seg.parent_mask_dev
            total += int(jnp.sum(mask))
        return total


def collapse_first_by_key(items, key_fn):
    """First-wins group dedupe over an already-ranked list — THE collapse
    semantics, shared by every merge tier (shard, index, cluster, REST)."""
    seen = set()
    out = []
    for it in items:
        kv = key_fn(it)
        if kv in seen:
            continue
        seen.add(kv)
        out.append(it)
    return out


def normalize_sort(sort_spec) -> List[dict]:
    """Sort spec → [{field, order, missing}] (shared by the shard searcher
    and the coordinating merges in ``dist_query.py`` / the REST layer)."""
    if isinstance(sort_spec, (str, dict)):
        sort_spec = [sort_spec]
    out = []
    for clause in sort_spec:
        if isinstance(clause, str):
            field, opts = clause, {}
        elif isinstance(clause, dict) and len(clause) == 1:
            (field, opts), = clause.items()
            if isinstance(opts, str):
                opts = {"order": opts}
        else:
            raise ParsingError(f"invalid sort clause [{clause}]")
        order = opts.get("order", "desc" if field == "_score" else "asc")
        out.append({"field": field, "order": order,
                    "missing": opts.get("missing", "_last"),
                    "numeric_type": opts.get("numeric_type")})
    return out


def _sort_includes_score(sort_spec) -> bool:
    if isinstance(sort_spec, (str, dict)):
        sort_spec = [sort_spec]
    for c in sort_spec or []:
        if c == "_score" or (isinstance(c, dict) and "_score" in c):
            return True
    return False


def _as_list_(v) -> list:
    """Shared list coercion (REST layer imports this as _as_list)."""
    if v is None:
        return []
    return v if isinstance(v, list) else [v]


def build_agg_profile(aggs: dict, results: Optional[dict], mapper,
                      segments, collect_count: int) -> List[dict]:
    """Aggregation profile entries (search/profile/aggregation/
    AggregationProfiler): ES aggregator class names + debug payloads
    mapped from this engine's aggregator classes."""
    from ..index.mapping import KeywordFieldType, NumberFieldType
    from .aggregations import (DateHistogramAgg, HistogramAgg,
                               PipelineAggregator, TermsAgg)
    out: List[dict] = []
    for name, agg in (aggs or {}).items():
        if isinstance(agg, PipelineAggregator):
            continue
        res = (results or {}).get(name, {}) or {}
        raw = getattr(agg, "_raw", {}) or {}
        entry = {"type": type(agg).__name__, "description": name,
                 "time_in_nanos": 1000,
                 "breakdown": {"initialize": 1, "initialize_count": 1,
                               "collect": 1, "collect_count": collect_count,
                               "build_aggregation": 1,
                               "build_aggregation_count": 1,
                               "build_leaf_collector": 1,
                               "build_leaf_collector_count":
                                   max(len(segments), 1),
                               "reduce": 0, "reduce_count": 0,
                               "post_collection": 1,
                               "post_collection_count": 1},
                 "debug": dict(getattr(agg, "_debug", {}) or {})}
        buckets = res.get("buckets")
        blist = list(buckets.values()) if isinstance(buckets, dict) \
            else (buckets or [])
        nonempty = sum(1 for b in blist
                       if isinstance(b, dict) and b.get("doc_count", 0) > 0)
        if isinstance(agg, TermsAgg):
            field = getattr(agg, "field", "")
            ft = mapper.field_type(field) if mapper else None
            if isinstance(ft, NumberFieldType) or (
                    ft is not None and not isinstance(ft, KeywordFieldType)):
                entry["type"] = "NumericTermsAggregator"
                tn = getattr(ft, "type_name", "long")
                entry["debug"].setdefault(
                    "result_strategy",
                    "double_terms" if tn in ("double", "float", "half_float")
                    else "long_terms")
                entry["debug"].setdefault("total_buckets", len(blist))
            else:
                hint = raw.get("execution_hint", "global_ordinals")
                entry["type"] = ("MapStringTermsAggregator"
                                 if hint == "map"
                                 else "GlobalOrdinalsStringTermsAggregator")
                entry["debug"].setdefault("result_strategy", "terms")
                entry["debug"].setdefault("collection_strategy",
                                          "from string terms"
                                          if hint == "map" else "dense")
                entry["debug"].setdefault("has_filter", False)
                single = multi = 0
                for seg in segments:
                    kf = seg.keyword_fields.get(field)
                    if kf is None or kf.dv_docs_host.shape[0] == 0:
                        continue
                    if np.unique(kf.dv_docs_host).size == \
                            kf.dv_docs_host.shape[0]:
                        single += 1
                    else:
                        multi += 1
                entry["debug"].setdefault(
                    "segments_with_single_valued_ords", single)
                entry["debug"].setdefault(
                    "segments_with_multi_valued_ords", multi)
                if raw.get("collect_mode") == "breadth_first" and agg.subs:
                    entry["debug"].setdefault("deferred_aggregators",
                                              sorted(agg.subs))
        elif isinstance(agg, DateHistogramAgg):
            ft = mapper.field_type(getattr(agg, "field", "")) \
                if mapper else None
            entry["type"] = "DateHistogramAggregator"
            entry["debug"].setdefault("total_buckets", nonempty)
        elif isinstance(agg, HistogramAgg):
            entry["type"] = "NumericHistogramAggregator"
            entry["debug"].setdefault("total_buckets", nonempty)
        elif type(agg).__name__ == "AutoDateHistogramAgg":
            entry["type"] = "AutoDateHistogramAggregator.FromSingle"
        elif type(agg).__name__ == "CardinalityAgg":
            field = getattr(agg, "field", "")
            ft = mapper.field_type(field) if mapper else None
            is_kw = isinstance(ft, KeywordFieldType) or (
                ft is None and any(field in seg.keyword_fields
                                   for seg in segments))
            entry["type"] = ("GlobalOrdCardinalityAggregator" if is_kw
                             else "CardinalityAggregator")
            entry["debug"].update({
                "empty_collectors_used": 0,
                "numeric_collectors_used": 0 if is_kw else 1,
                "ordinals_collectors_used": 1 if is_kw else 0,
                "ordinals_collectors_overhead_too_high": 0,
                "string_hashing_collectors_used": 0})
        if getattr(agg, "subs", None):
            children = build_agg_profile(
                agg.subs,
                blist[0] if blist and isinstance(blist[0], dict) else res,
                mapper, segments, collect_count)
            # metric children get their ES metric class names
            for c in children:
                c["type"] = {
                    "MaxAgg": "MaxAggregator", "MinAgg": "MinAggregator",
                    "SumAgg": "SumAggregator", "AvgAgg": "AvgAggregator",
                    "ValueCountAgg": "ValueCountAggregator",
                    "CardinalityAgg": "CardinalityAggregator",
                }.get(c["type"], c["type"])
            if children:
                entry["children"] = children
        out.append(entry)
        # ES metric class names at the top level too
        entry["type"] = {
            "MaxAgg": "MaxAggregator", "MinAgg": "MinAggregator",
            "SumAgg": "SumAggregator", "AvgAgg": "AvgAggregator",
            "ValueCountAgg": "ValueCountAggregator",
            "CardinalityAgg": "CardinalityAggregator",
            "GlobalAgg": "GlobalAggregator",
        }.get(entry["type"], entry["type"])
    return out
