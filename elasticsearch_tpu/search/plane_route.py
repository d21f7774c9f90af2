"""Serving route onto the tiered TPU search plane.

The flagship distributed kernel (``parallel/dist_search.py``: tiered BM25 —
dense Zipf-head streaming matmuls + sparse sorted-merge — with the ICI
all_gather/top_k reduce) must serve PRODUCT traffic, not just the bench:
the reference executes every eligible query through its one production
scorer (``action/search/AbstractSearchAsyncAction.java:70`` →
``search/internal/ContextIndexSearcher.java:210-224``). This module is the
bridge from the REST/cluster search path into the plane:

- :func:`extract_bag_of_terms` recognizes request bodies whose query
  reduces to a weighted bag of terms over ONE text field — ``match``
  (OR operator), ``term`` on a text field, and ``bool``/``dis_max``-free
  pure-``should`` disjunctions of those — exactly the shapes whose scoring
  model (sum of per-term BM25 over shard-level stats) the plane computes.
- :class:`ServingPlaneCache` owns one serving GENERATION per (shard,
  field): a packed base plane (:class:`DistributedSearchPlane` /
  :class:`DistributedKnnPlane` over the segment list as of the last
  repack) plus an append-only DELTA tier (segments created since),
  scored eagerly per query and merged into the base dispatch's top-k.
  Segments with deletes or nested docs disable the route (plane postings
  would score hidden/dead docs).

Incremental maintenance (the NRT-refresh problem): under live indexing a
refresh appends a segment every second while a full plane repack — CSR
pack, dense tier, device upload, warmup lattice — costs far more. The old
design repacked EVERY segment synchronously on the first request to
notice the signature change, collapsing search throughput into rebuild
storms. Generations fix this the way Lucene-tier systems do (segment
-tiered serving + background merges — the Anserini/HNSW line):

- an append-only refresh never invalidates the base: the new segments
  form the delta tier (``parallel/dist_search.EagerDeltaScorer`` /
  ``KnnDeltaScorer``), and the request thread at most packs the delta's
  CSR — O(delta), no device work;
- a background repack thread folds the delta into a new base generation
  once the delta exceeds :attr:`ServingPlaneCache.REPACK_DELTA_FRACTION`
  of the base doc count, builds and warms the new plane OFF the request
  thread, then atomically swaps generations (double-buffering: the old
  generation serves until the new one is ready; its warmup is retired as
  before);
- a merge/delete restructures the base segment list, which the old base
  cannot serve (its hit coordinates decode against segments that no
  longer exist): the repack still happens in the background while the
  per-segment path serves the gap.

Score parity with ``query_dsl._score_text_terms``: idf uses the identical
``idf_weight`` over summed dfs and total docs — the delta tier's df/doc
mass is folded into every base dispatch (``extra_df``/``extra_docs``), so
base and delta docs score under ONE stat set. The generation's length
norm (avgdl) is FROZEN at base-pack time (base impacts bake it); the
delta scores under the same frozen value, so base+delta serving is
bit-equal to a full repack pinned to that avgdl, and drifts from the
live per-segment path only by the delta window's avgdl movement — the
repack threshold bounds the window, and the swap restores exactness.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..common import heap as _heap
from ..common import racedep
from ..index.mapping import MapperService, TextFieldType
from ..index.segment import Segment

#: plane construction is O(postings); don't bother below this many docs
#: unless a test forces it (ENV knob in ServingPlaneCache)
_MIN_DOCS_DEFAULT = 0


def _match_terms(field: str, spec, mapper: MapperService) \
        -> Optional[Tuple[str, List[str]]]:
    """One match clause → (concrete text field, analyzed terms)."""
    if isinstance(spec, dict):
        if set(spec) - {"query", "operator", "boost",
                        "minimum_should_match"}:
            return None
        if str(spec.get("operator", "or")).lower() != "or":
            return None
        if spec.get("boost", 1.0) != 1.0:
            return None
        msm = spec.get("minimum_should_match")
        if msm is not None and msm != 1:
            return None
        text = spec.get("query")
    else:
        text = spec
    if text is None or isinstance(text, (dict, list)):
        return None
    ft = mapper.field_type(field)
    if not isinstance(ft, TextFieldType):
        return None
    terms = ft.search_analyzer.terms(str(text))
    return (ft.name, terms) if terms else None


def _term_terms(field: str, spec, mapper: MapperService) \
        -> Optional[Tuple[str, List[str]]]:
    """One term clause on a TEXT field → single unanalyzed term."""
    if isinstance(spec, dict):
        if set(spec) - {"value", "boost"}:
            return None
        if spec.get("boost", 1.0) != 1.0:
            return None
        value = spec.get("value")
    else:
        value = spec
    if value is None or isinstance(value, (dict, list)):
        return None
    ft = mapper.field_type(field)
    if not isinstance(ft, TextFieldType):
        return None
    return ft.name, [str(value)]


def extract_bag_of_terms(query_spec, mapper: MapperService) \
        -> Optional[Tuple[str, List[str]]]:
    """Request query → (field, bag of terms with duplicates) when the query
    is plane-eligible, else None. Duplicate terms encode weight (the plane
    counts repeats into idfw, matching the per-segment path's weights)."""
    if not isinstance(query_spec, dict) or len(query_spec) != 1:
        return None
    (kind, body), = query_spec.items()
    if kind == "match":
        if not isinstance(body, dict) or len(body) != 1:
            return None
        (field, spec), = body.items()
        return _match_terms(field, spec, mapper)
    if kind == "term":
        if not isinstance(body, dict) or len(body) != 1:
            return None
        (field, spec), = body.items()
        return _term_terms(field, spec, mapper)
    if kind == "bool":
        if not isinstance(body, dict):
            return None
        if set(body) - {"should", "minimum_should_match", "boost"}:
            return None           # must/filter/must_not change semantics
        if body.get("boost", 1.0) != 1.0:
            return None
        msm = body.get("minimum_should_match")
        if msm is not None and msm != 1:
            return None
        should = body.get("should")
        if isinstance(should, dict):
            should = [should]
        if not should:
            return None
        field = None
        terms: List[str] = []
        for clause in should:
            sub = extract_bag_of_terms(clause, mapper)
            if sub is None:
                return None
            f, ts = sub
            if field is None:
                field = f
            elif field != f:
                return None       # cross-field disjunction: scores differ
            terms.extend(ts)
        return (field, terms) if field is not None and terms else None
    return None


#: request-body features the plane cannot serve (need per-doc masks or
#: post-hoc reordering); shared by the single-shard and pooled dist
#: routes. ``profile`` is NOT here: profiled plane queries ride the real
#: serving path and report a ``serving`` profile section (stage timings,
#: compile-cache) — the Profile API must reflect production execution.
#: (Profiled bodies still never enter the request cache:
#: ``IndexService._plane_cache_key`` checks ``profile`` separately.)
_PLANE_INCOMPATIBLE = ("aggs", "aggregations", "sort", "knn", "rescore",
                       "collapse", "suggest", "search_after", "min_score",
                       "rank")


def body_eligible(body: dict) -> bool:
    """True when the request body's FEATURE set allows the plane route
    (the query shape itself is judged by :func:`extract_bag_of_terms`)."""
    if any(body.get(k) for k in _PLANE_INCOMPATIBLE):
        return False
    return int(body.get("size", 10)) + int(body.get("from", 0)) > 0


# ---------------------------------------------------------------------------
# Serving generations: packed base plane + append-only delta tier
# ---------------------------------------------------------------------------


class _ServingGeneration:
    """One serving generation: a packed base plane over an immutable
    snapshot of the segment list, plus a swappable delta tier covering
    segments appended since. Unknown attributes delegate to the base
    plane (``n_dispatches``, ``_host_csr``/``_host_pack``, ladder/warmup
    surface), so the micro-batcher and the stats layer treat a
    generation exactly like a bare plane."""

    kind = "plane"

    #: per-view delta-scorer memo entries kept besides the live one
    VIEW_MEMO_MAX = 4

    def __init__(self, base, base_segments: Sequence[Segment], cache):
        self.base = base
        #: strong refs — identity (``is``) anchors for delta matching;
        #: kept alive until the generation is released
        self.base_segments = list(base_segments)
        self.base_docs = sum(s.n_docs for s in base_segments)
        self._cache = cache
        self.delta = None
        self._base_positions: List[int] = list(range(len(base_segments)))
        self._delta_key: Optional[tuple] = None
        self._delta_ver = -1
        self._delta_lock = threading.Lock()
        #: view key → (scorer, base_positions) for views that are not
        #: the live delta (a dispatch racing a refresh serves its own
        #: older view; see :meth:`_delta_for_view`)
        self._view_memo: "OrderedDict[tuple, tuple]" = OrderedDict()

    def __getattr__(self, name):
        base = self.__dict__.get("base")
        if base is None:
            raise AttributeError(name)
        return getattr(base, name)

    # -- delta bookkeeping ---------------------------------------------------

    def match(self, segments: Sequence[Segment]):
        """Identity-subsequence match of this generation's base against
        the CURRENT segment list. Returns (delta_segments,
        delta_positions, base_positions) when every base segment appears
        unchanged and in order (append-only refreshes, including
        interleaved appends from other index shards), else None (a
        merge/delete restructured the base — repack required)."""
        base = self.base_segments
        bi = 0
        delta: List[Segment] = []
        dpos: List[int] = []
        bpos: List[int] = []
        for pos, seg in enumerate(segments):
            if bi < len(base) and seg is base[bi]:
                bpos.append(pos)
                bi += 1
            else:
                delta.append(seg)
                dpos.append(pos)
        if bi != len(base):
            return None
        return delta, dpos, bpos

    def clear_delta(self, base_positions: Optional[List[int]] = None,
                    ver: int = -1) -> None:
        with self._delta_lock:
            if ver >= 0 and ver < self._delta_ver:
                return
            self.delta = None
            self._delta_key = None
            self._delta_ver = max(self._delta_ver, ver)
            if base_positions is not None:
                self._base_positions = base_positions

    def _swap_delta(self, scorer, key: tuple, base_positions: List[int],
                    ver: int) -> None:
        with self._delta_lock:
            racedep.note_write("generation.delta", self)
            if ver < self._delta_ver:
                return          # a newer segment list already swapped in
            self.delta = scorer
            self._delta_key = key
            self._delta_ver = ver
            self._base_positions = base_positions

    def delta_docs(self) -> int:
        # under _delta_lock: the repack thread swaps (delta, positions)
        # as a pair, and a torn read here would size the repack
        # threshold off a half-swapped generation (ESTP-R01)
        with self._delta_lock:
            d = self.delta
        return d.n_docs if d is not None else 0

    def _snapshot(self):
        with self._delta_lock:
            racedep.note_read("generation.delta", self)
            return self.delta, self._base_positions

    def _build_delta(self, delta_segs: Sequence[Segment],
                     delta_pos: List[int]):
        raise NotImplementedError

    def _delta_for_view(self, view: Sequence[Segment]):
        """(delta scorer | None, base positions) for EXACTLY the given
        segment list — the dispatch-time resolution that keeps hit
        coordinates in the caller's NRT snapshot space. A refresh landing
        between the caller's ``plane_for`` and the micro-batch dispatch
        mutates the generation's live delta, so serving that newer delta
        would emit coordinates past (or shifted within) the caller's
        list; resolving per view instead makes the race harmless. The
        live delta is the common-case hit; other views pay one O(delta)
        pack memoized per view key."""
        key = tuple(id(s) for s in view)
        with self._delta_lock:
            if self._delta_key == key:
                return self.delta, self._base_positions
            memo = self._view_memo.get(key)
            if memo is not None:
                self._view_memo.move_to_end(key)
                return memo
        m = self.match(view)
        if m is None:
            # unreachable for views that obtained this generation from
            # plane_for (the base is immutable), but a stale caller must
            # fail loudly rather than decode foreign coordinates
            raise RuntimeError(
                "serving view no longer contains this generation's base")
        delta_segs, delta_pos, base_pos = m
        scorer = self._build_delta(delta_segs, delta_pos) \
            if delta_segs else None
        with self._delta_lock:
            self._view_memo[key] = (scorer, base_pos)
            while len(self._view_memo) > self.VIEW_MEMO_MAX:
                self._view_memo.popitem(last=False)
        return scorer, base_pos


class TextServingGeneration(_ServingGeneration):
    """Lexical generation: ``DistributedSearchPlane`` base + eager CSR
    delta (``parallel/dist_search.EagerDeltaScorer``)."""

    kind = "text"

    def __init__(self, base, base_segments, field: str, avgdl: float,
                 cache):
        super().__init__(base, base_segments, cache)
        self.field = field
        #: the generation's frozen length norm (baked into base impacts)
        self.avgdl = avgdl

    def _build_delta(self, delta_segs: Sequence[Segment],
                     delta_pos: List[int]):
        """Pack a delta scorer — O(delta postings), the only
        serving-path cost a refresh adds."""
        from ..parallel.dist_search import EagerDeltaScorer
        shards = []
        for seg in delta_segs:
            f = seg.text_fields.get(self.field)
            if f is None:
                shards.append(dict(
                    term_ids={}, df=np.zeros(0, np.int32),
                    offsets=np.zeros(1, np.int64),
                    docs=np.zeros(0, np.int32),
                    tf=np.zeros(0, np.float32),
                    doc_len=np.zeros(seg.n_docs, np.float32)))
            else:
                shards.append(dict(
                    term_ids=f.term_ids, df=f.df, offsets=f.offsets,
                    docs=f.docs_host, tf=f.tf_host,
                    doc_len=f.doc_len_host))
        return EagerDeltaScorer(shards, delta_pos, avgdl=self.avgdl)

    def update_delta(self, segments: Sequence[Segment],
                     delta_segs: Sequence[Segment], delta_pos: List[int],
                     base_pos: List[int], ver: int) -> None:
        """Pack (or reuse) the LIVE delta scorer for the current segment
        list (the common serving view; dispatches for other views resolve
        through :meth:`_delta_for_view`)."""
        key = tuple(id(s) for s in segments)
        with self._delta_lock:
            if self._delta_key == key:
                self._base_positions = base_pos
                return
        scorer = self._build_delta(delta_segs, delta_pos)
        self._swap_delta(scorer, key, base_pos, ver)

    def serve_view(self, queries, k: int = 10, *, view,
                   with_totals: bool = False,
                   stages: Optional[dict] = None,
                   prune: Optional[bool] = None):
        """Micro-batcher dispatch hook: base dispatch (idf widened by the
        delta's df/doc mass) + eager delta scan + host top-k merge, with
        the delta resolved for the batch's exact segment view. The BASE
        dispatch may be block-max pruned (``prune``); the delta tier
        always scores eagerly — appended segments are small and
        exactness there keeps the merge honest for fresh docs."""
        delta, base_pos = self._delta_for_view(view)
        return self._serve_merged(queries, k, delta, base_pos,
                                  with_totals=with_totals, stages=stages,
                                  prune=prune)

    def serve(self, queries, k: int = 10, *, with_totals: bool = False,
              stages: Optional[dict] = None,
              prune: Optional[bool] = None):
        """Viewless entry (tests / direct callers): serve against the
        generation's CURRENT delta snapshot."""
        delta, base_pos = self._snapshot()
        return self._serve_merged(queries, k, delta, base_pos,
                                  with_totals=with_totals, stages=stages,
                                  prune=prune)

    def _serve_merged(self, queries, k, delta, base_pos, *,
                      with_totals: bool = False,
                      stages: Optional[dict] = None,
                      prune: Optional[bool] = None):
        # tier bookkeeping BEFORE the dispatch (outside every lock):
        # recency for the budget sweep, warm-hit hysteresis → promotion
        self._cache.tiers.note_dispatch(self)
        if delta is None:
            return self.base.serve(queries, k=k, with_totals=with_totals,
                                   stages=stages, prune=prune)
        # one shared stat set: the delta's term dfs fold into the base
        # dispatch's idf weights, and the delta scores under the same
        # combined idf — parity with a full repack at the frozen avgdl
        extra_df: Dict[str, int] = {}
        for q in queries:
            for t in set(q):
                if t not in extra_df:
                    extra_df[t] = delta.df(t)
        vals, hits, totals = self.base.serve(
            queries, k=k, with_totals=True, stages=stages,
            extra_docs=delta.n_docs, extra_df=extra_df, prune=prune)
        t1 = time.perf_counter()
        from ..ops.bm25 import idf_weight
        n_total = self.base.n_docs_total + delta.n_docs
        idf_cache: Dict[str, float] = {}

        def idf_of(t: str) -> float:
            v = idf_cache.get(t)
            if v is None:
                gdf = self.base.global_df(t) + extra_df.get(t, 0)
                v = float(idf_weight(n_total, np.int64(gdf))) if gdf \
                    else 0.0
                idf_cache[t] = v
            return v

        from ..parallel.dist_search import (merge_topk_rows,
                                            total_is_lower_bound,
                                            total_value)
        drows, dtotals = delta.score(queries, k, idf_of, with_totals=True)
        vals_out, hits_out, totals_out = [], [], []
        for bi in range(len(queries)):
            base_rows = [(float(v), base_pos[si], int(d))
                         for v, (si, d) in zip(vals[bi], hits[bi])]
            merged = merge_topk_rows(base_rows, drows[bi], k)
            vals_out.append(np.asarray([r[0] for r in merged], np.float32))
            hits_out.append([(r[1], r[2]) for r in merged])
            # a pruned base dispatch reports (value, "gte") lower-bound
            # totals — the delta's exact count adds on, relation sticks
            tv = total_value(totals[bi]) + int(dtotals[bi])
            totals_out.append((tv, "gte")
                              if total_is_lower_bound(totals[bi]) else tv)
        delta_ms = (time.perf_counter() - t1) * 1e3
        if stages is not None:
            stages["dispatch_ms"] = stages.get("dispatch_ms", 0.0) \
                + delta_ms
            stages["delta_ms"] = delta_ms
            stages["delta_docs"] = delta.n_docs
        self._cache._record_delta_serve("text", len(queries))
        if with_totals:
            return vals_out, hits_out, totals_out
        return vals_out, hits_out


class KnnServingGeneration(_ServingGeneration):
    """Vector generation: ``DistributedKnnPlane`` base + BLAS delta
    (``parallel/dist_search.KnnDeltaScorer``). No corpus-wide stats, so
    delta serving is exactly exact."""

    kind = "knn"

    def __init__(self, base, base_segments, field: str, cache):
        super().__init__(base, base_segments, cache)
        self.field = field

    def _build_delta(self, delta_segs: Sequence[Segment],
                     delta_pos: List[int]):
        from ..parallel.dist_search import KnnDeltaScorer
        shards = []
        for seg in delta_segs:
            f = seg.vector_fields.get(self.field)
            if f is None:
                shards.append(dict(
                    vectors=np.zeros((seg.n_docs, max(self.base.dim, 1)),
                                     np.float32),
                    exists=np.zeros(seg.n_docs, bool)))
            else:
                ex = np.zeros(seg.n_docs, bool)
                ex[: f.exists.shape[0]] = f.exists
                shards.append(dict(vectors=f.matrix_host, exists=ex))
        return KnnDeltaScorer(shards, delta_pos,
                              similarity=self.base.similarity)

    def update_delta(self, segments: Sequence[Segment],
                     delta_segs: Sequence[Segment], delta_pos: List[int],
                     base_pos: List[int], ver: int) -> None:
        key = tuple(id(s) for s in segments)
        with self._delta_lock:
            if self._delta_key == key:
                self._base_positions = base_pos
                return
        scorer = self._build_delta(delta_segs, delta_pos)
        self._swap_delta(scorer, key, base_pos, ver)

    def serve_view(self, query_vectors, k: int = 10, *, view,
                   stages: Optional[dict] = None,
                   nprobe: Optional[int] = None,
                   rerank: Optional[int] = None):
        delta, base_pos = self._delta_for_view(view)
        return self._serve_merged(query_vectors, k, delta, base_pos,
                                  stages=stages, nprobe=nprobe,
                                  rerank=rerank)

    def serve(self, query_vectors, k: int = 10,
              stages: Optional[dict] = None,
              nprobe: Optional[int] = None,
              rerank: Optional[int] = None):
        delta, base_pos = self._snapshot()
        return self._serve_merged(query_vectors, k, delta, base_pos,
                                  stages=stages, nprobe=nprobe,
                                  rerank=rerank)

    def _serve_merged(self, query_vectors, k, delta, base_pos, *,
                      stages: Optional[dict] = None,
                      nprobe: Optional[int] = None,
                      rerank: Optional[int] = None):
        self._cache.tiers.note_dispatch(self)
        # the base dispatch may be cluster-pruned (IVF tier at the
        # resolved nprobe/rerank); the DELTA tier always scores exact
        # brute-force — appended segments are small, and exactness there
        # keeps the merge's top-k honest for fresh docs
        vals, hits = self.base.serve(query_vectors, k=k, stages=stages,
                                     nprobe=nprobe, rerank=rerank)
        if delta is None:
            return vals, hits
        t1 = time.perf_counter()
        from ..parallel.dist_search import NEG_INF, merge_topk_rows
        drows = delta.score(query_vectors, k)
        B = len(hits)
        vals_out = np.full((B, k), NEG_INF, np.float32)
        hits_out = []
        for bi in range(B):
            base_rows = [(float(v), base_pos[si], int(d))
                         for v, (si, d) in zip(vals[bi], hits[bi])]
            merged = merge_topk_rows(base_rows, drows[bi], k)
            for j, r in enumerate(merged):
                vals_out[bi, j] = r[0]
            hits_out.append([(r[1], r[2]) for r in merged])
        delta_ms = (time.perf_counter() - t1) * 1e3
        if stages is not None:
            stages["dispatch_ms"] = stages.get("dispatch_ms", 0.0) \
                + delta_ms
            stages["delta_ms"] = delta_ms
            stages["delta_docs"] = delta.n_docs
        self._cache._record_delta_serve("knn", B)
        return vals_out, hits_out


# ---------------------------------------------------------------------------
# ServingPlaneCache: generation registry + background repack
# ---------------------------------------------------------------------------


class ServingPlaneCache:
    """Per-(shard, field) serving-generation registry for the product
    search path. Request threads only ever (a) hit a generation, (b)
    pack an O(delta) delta scorer, or (c) pay the one cold build per
    field; full repacks run on a background thread and swap atomically
    (see the module docstring)."""

    #: max cached kNN generations (each base is one packed f32 corpus)
    KNN_PLANE_CACHE_MAX = 32

    #: delta-tier doc fraction (of the base generation's docs) above
    #: which a background repack folds the delta into a new base
    REPACK_DELTA_FRACTION = 0.125

    #: corpus size above which a kNN base pack also builds the IVF tier
    #: (k-means + cluster-contiguous int8 quantized rows — cluster-pruned
    #: approximate serving with exact re-rank). Below it the plane stays
    #: exact brute force: the pruned scan only wins once the corpus
    #: outgrows what one blocked f32 scan streams comfortably.
    KNN_IVF_MIN_DOCS = int(os.environ.get(
        "ES_TPU_KNN_IVF_MIN_DOCS", str(1 << 16)))

    #: corpus size above which a text base pack also builds the
    #: block-max pruning tier (impact-ordered int8 blocks + bound
    #: table — rank-safe WAND-as-a-scan serving via the ``prune``
    #: knob). Below it eager scoring wins outright (the BM25S bet) and
    #: the tier would only cost pack time and bytes.
    LEX_PRUNE_MIN_DOCS = int(os.environ.get(
        "ES_TPU_LEX_PRUNE_MIN_DOCS", str(1 << 17)))

    #: max cached fused-plan runners (generation pairs; runners hold no
    #: corpus bytes of their own — only batcher state)
    FUSED_RUNNER_CACHE_MAX = 8

    def __init__(self, mesh_factory=None, min_docs: int = _MIN_DOCS_DEFAULT):
        self._mesh_factory = mesh_factory
        self._mesh = None
        self._planes: Dict[str, TextServingGeneration] = {}
        #: (text gen id, knn gen id) → query_planner.FusedPlanRunner —
        #: the one-dispatch planner's executor per generation pair;
        #: entries die with either generation (see _release_gen)
        self._fused_runners: "OrderedDict[tuple, object]" = OrderedDict()
        # kNN generations key on (field, base segment identity): the
        # distributed searcher probes one plane per index shard (distinct
        # segment lists), and field-only keying would rebuild on every
        # alternating probe. LRU-capped; evicted generations release
        # their breaker bytes.
        self._knn_planes: "OrderedDict[tuple, KnnServingGeneration]" = \
            OrderedDict()
        #: consecutive plane builds without a cache hit — when more
        #: distinct (field, segment-list) combinations are in flight than
        #: the cache holds, packing a corpus per probe would thrash; the
        #: route bows out to the per-segment path instead
        self._knn_build_streak = 0
        self.min_docs = min_docs
        #: instance override of :attr:`KNN_IVF_MIN_DOCS` (tests force
        #: IVF on tiny corpora by lowering it)
        self.knn_ivf_min_docs = self.KNN_IVF_MIN_DOCS
        #: instance override of :attr:`LEX_PRUNE_MIN_DOCS` (tests force
        #: the block-max tier on tiny corpora by lowering it)
        self.lex_prune_min_docs = self.LEX_PRUNE_MIN_DOCS
        #: delta-tier serving on/off (off = the old rebuild-every-refresh
        #: behavior; the live-indexing bench sets it as its baseline)
        self.delta_enabled = True
        #: "background" (production) or "sync" (deterministic tests /
        #: callers that need the swap visible before the call returns
        #: set it on the instance)
        self.repack_mode = "background"
        self._gen_lock = threading.RLock()
        #: guards the lazy mesh singleton — its OWN leaf lock, not
        #: _gen_lock: the cold build (jax import + device enumeration,
        #: or an arbitrary user factory) can take seconds and must not
        #: stall stats scrapes / refresh reconciles on the registry lock
        self._mesh_lock = threading.Lock()
        self._gen_ver = 0
        self._repacking: set = set()
        self._repack_threads: List[threading.Thread] = []
        self._closed = False
        # plane.rebuild / plane.delta_serve / plane.swap_ms metrics:
        # instance-owned (fresh per cache — exact per-index counts) and
        # exposed through the process telemetry registry via a weakref
        # collector, like every other node-scoped producer
        from ..common import telemetry as _tm
        self._metric_lock = threading.Lock()
        self._rebuild_counts: Dict[Tuple[str, str, str], _tm.Counter] = {}
        self._delta_serve_counts: Dict[str, _tm.Counter] = {}
        # per-kind swap histograms (pre-created so the family's label
        # space is stable for the telemetry lint): a kNN repack packs a
        # full f32 corpus while a text repack packs CSR+dense tiers —
        # their swap costs must be distinguishable
        self._swap_ms: Dict[str, _tm.Histogram] = {
            "text": _tm.Histogram(), "knn": _tm.Histogram()}
        #: device ids that ever reported plane bytes — the gauge emits
        #: explicit 0 samples for them once their planes demote/release
        #: (a vanished sample reads as "last value" to most scrapers:
        #: the PR 15 es_batcher_queue_depth stale-gauge class)
        self._hbm_devices: set = set()
        _tm.DEFAULT.register_object_collector(
            f"plane_cache_{id(self):x}", self,
            ServingPlaneCache._metrics_doc)
        #: storage-tier policy (hot/warm/cold budgets + demand
        #: promotion); budgets default to 0 = unlimited, every plane hot
        from .plane_tiers import PlaneTierManager
        self.tiers = PlaneTierManager(self)

    # -- telemetry -----------------------------------------------------------

    def _metrics_doc(self):
        with self._metric_lock:
            rb = [({"kind": k, "trigger": t, "mode": m}, c.value)
                  for (k, t, m), c in self._rebuild_counts.items()]
            ds = [({"kind": k}, c.value)
                  for k, c in self._delta_serve_counts.items()]
        # per-device resident plane bytes: every generation's base plane
        # reports its per-chip share (shard-axis sharding divides the
        # corpus; replica rows hold full copies), summed per device id —
        # the HBM-budget view of multichip serving. Outside _metric_lock
        # (generations() takes _gen_lock; keep the two independent).
        per_dev: Dict[int, int] = {}
        for gen in self.generations():
            base = gen.__dict__.get("base", gen)
            try:
                # warm/cold planes hold no HBM: device_corpus_bytes()
                # reports 0 once demoted, so the gauge decrements on
                # every demotion without tier-specific cases here
                share = int(base.device_corpus_bytes())
                devices = list(base.mesh.devices.flat)
            except Exception:   # noqa: BLE001 — foreign/legacy planes
                continue
            for d in devices:
                did = int(getattr(d, "id", 0))
                per_dev[did] = per_dev.get(did, 0) + share
        # devices whose planes all demoted/released still emit explicit
        # 0 samples (under _metric_lock: scrapes race each other)
        with self._metric_lock:
            self._hbm_devices |= set(per_dev)
            hbm_devices = sorted(self._hbm_devices)
        return {
            "es_plane_rebuild_total": {
                "type": "counter",
                "help": "serving plane (re)builds by kind/trigger/mode",
                "samples": rb},
            "es_plane_delta_serve_total": {
                "type": "counter",
                "help": "queries served through base+delta merge",
                "samples": ds},
            "es_plane_swap_ms": {
                "type": "histogram",
                "help": "background repack build+swap wall ms by kind",
                "samples": [({"kind": k}, h.snapshot())
                            for k, h in self._swap_ms.items()]},
            "es_plane_hbm_bytes": {
                "type": "gauge",
                "help": "packed serving-plane bytes resident per device "
                        "(estimate; shard-sharded corpus / replica "
                        "copies)",
                "samples": [({"device": str(did)}, per_dev.get(did, 0))
                            for did in hbm_devices]},
        }

    def _record_rebuild(self, kind: str, trigger: str, mode: str) -> None:
        from ..common import telemetry as _tm
        with self._metric_lock:
            c = self._rebuild_counts.get((kind, trigger, mode))
            if c is None:
                c = self._rebuild_counts[(kind, trigger, mode)] = \
                    _tm.Counter()
        c.inc()
        # flight-recorder journal: every generation install (cold pack,
        # threshold/structural repack, warm-handoff import) is a durable
        # event — emitted outside every cache lock (ESTP-L02)
        from ..common import flightrec as _fr
        _fr.record("plane_rebuild", kind=kind, trigger=trigger, mode=mode)
        # the generation just installed serves until a repack retires it,
        # and the one it replaced is garbage now: in a node's process one
        # full pass frees the old and takes the new out of the cyclic
        # collector's reach, on the installing thread (the repack thread
        # for a background repack)
        _heap.settle()

    def _record_delta_serve(self, kind: str, n: int) -> None:
        from ..common import telemetry as _tm
        with self._metric_lock:
            c = self._delta_serve_counts.get(kind)
            if c is None:
                c = self._delta_serve_counts[kind] = _tm.Counter()
        c.inc(n)

    def rebuild_stats(self) -> Dict[str, int]:
        """Rollup for benches/tests: rebuild counts by mode and trigger,
        plus delta-served query count."""
        with self._metric_lock:
            out: Dict[str, int] = {"sync": 0, "background": 0,
                                   "cold": 0, "threshold": 0,
                                   "structure": 0, "delta_serves": 0}
            for (kind, trigger, mode), c in self._rebuild_counts.items():
                out[mode] = out.get(mode, 0) + int(c.value)
                out[trigger] = out.get(trigger, 0) + int(c.value)
            for c in self._delta_serve_counts.values():
                out["delta_serves"] += int(c.value)
        return out

    # -- shared plumbing -----------------------------------------------------

    def generations(self) -> list:
        """Locked snapshot of every live serving generation (lexical +
        kNN). Stats/health surfaces iterate THIS, never the raw dicts —
        a nodes-stats scrape racing the repack thread's swap would
        otherwise walk a dict mid-mutation (ESTP-R01, found by the
        first full scan)."""
        with self._gen_lock:
            racedep.note_read("plane_cache.generations", self)
            return list(self._planes.values()) + \
                list(self._knn_planes.values())

    def serving_batchers(self) -> list:
        """The micro-batchers of every live generation AND fused-plan
        runner (stats rollup)."""
        with self._gen_lock:
            runners = list(self._fused_runners.values())
        out = []
        for gen in self.generations() + runners:
            b = getattr(gen, "_microbatcher", None)
            if b is not None:
                out.append(b)
        return out

    def fused_runner_for(self, segments: Sequence[Segment],
                         mapper: MapperService, text_field: str,
                         knn_field: Optional[str] = None):
        """The one-dispatch planner's executor for this segment list —
        a ``query_planner.FusedPlanRunner`` over the (text, knn)
        serving-generation pair — or None when either generation is
        unavailable (route ineligible / mid-repack): the caller falls
        back to the legacy two-dispatch path."""
        segments = [s for s in segments if s.n_docs > 0]
        if not segments:
            return None
        tgen = self.plane_for(segments, mapper, text_field)
        if tgen is None:
            return None
        kgen = None
        if knn_field is not None:
            kgen = self.knn_plane_for(segments, mapper, knn_field)
            if kgen is None:
                return None
        key = (id(tgen), id(kgen) if kgen is not None else None)
        with self._gen_lock:
            r = self._fused_runners.get(key)
            if r is not None:
                self._fused_runners.move_to_end(key)
                return r
        from .query_planner import FusedPlanRunner
        r = FusedPlanRunner(tgen, kgen, cache=self)
        doomed = []
        with self._gen_lock:
            raced = self._fused_runners.get(key)
            if raced is not None:
                return raced
            if self._closed:
                return None
            self._fused_runners[key] = r
            while len(self._fused_runners) > self.FUSED_RUNNER_CACHE_MAX:
                _k, old = self._fused_runners.popitem(last=False)
                doomed.append(old)
        for old in doomed:
            self._retire(old)
        return r

    @staticmethod
    def _attach_batcher(plane, knn: bool = False):
        """Pre-create the plane's micro-batcher at plane-build time and
        kick off its serving-shape-lattice warmup (background thread; see
        ``microbatch.PlaneMicroBatcher.warmup``) — a first-hit XLA
        compile landing mid-traffic is the multi-second serving-p99
        signature. Host-serving (CPU) planes compile nothing so warmup
        returns immediately. ``ES_TPU_SERVING_WARMUP=0`` disables."""
        import os
        from .microbatch import KnnPlaneMicroBatcher, PlaneMicroBatcher
        cls = KnnPlaneMicroBatcher if knn else PlaneMicroBatcher
        batcher = cls(plane)
        plane._microbatcher = batcher
        if os.environ.get("ES_TPU_SERVING_WARMUP", "1").lower() \
                not in ("0", "false"):
            batcher.warmup()
        return batcher

    @staticmethod
    def _retire(plane) -> None:
        """Stop a superseded/evicted plane's in-flight warmup so rebuild
        storms (refresh-heavy indices) don't stack background compile
        threads each pinning an orphaned corpus copy."""
        b = plane.__dict__.get("_microbatcher") \
            if isinstance(plane, _ServingGeneration) \
            else getattr(plane, "_microbatcher", None)
        if b is not None:
            b.retire()

    def _release_gen(self, gen) -> None:
        """Release a generation's (or bare plane's) breaker reservation
        and retire its batcher — plus any fused-plan runner built over
        it (a stale runner would pin the superseded corpus). Both tier
        ledgers drain: a hot generation holds ``accounting`` (device)
        bytes, a warm one ``host_tier`` bytes."""
        from ..common.breakers import DEFAULT as _breakers
        acct = _breakers.breaker("accounting")
        acct.release(getattr(gen, "_acct_bytes", 0))
        _breakers.breaker("host_tier").release(
            getattr(gen, "_host_acct_bytes", 0))
        self._retire(gen)
        with self._gen_lock:
            doomed = [k for k, r in self._fused_runners.items()
                      if r.text_gen is gen or r.knn_gen is gen]
            runners = [self._fused_runners.pop(k) for k in doomed]
        for r in runners:
            self._retire(r)

    def _get_mesh(self):
        # every read goes through _mesh_lock — a lock-free fast path
        # would empty the static lockset intersection (ESTP-R01), and
        # one uncontended acquire is noise next to a plane build. Leaf
        # lock: nothing inside takes _gen_lock, so build paths holding
        # _gen_lock nest safely (gen -> mesh only).
        with self._mesh_lock:
            mesh = self._mesh
        if mesh is not None:
            return mesh
        # build OUTSIDE the lock: the cold build (jax import + device
        # enumeration + the es_mesh_devices gauge registration, or an
        # arbitrary user factory) can take seconds and must not stall
        # stats scrapes on the lock — and telemetry must never run
        # under a serving lock (ESTP-L02). Concurrent cold builders
        # race benignly: the first swap wins, the loser's mesh is
        # dropped (meshes hold no device memory).
        if self._mesh_factory is not None:
            mesh = self._mesh_factory()
            # the factory mesh IS the serving mesh: own the idle-device
            # health gauge the same way mesh_from_env does for the
            # default path (auxiliary make_search_mesh builds don't)
            import jax
            from ..parallel.mesh import record_mesh_devices
            used = int(mesh.devices.size)
            record_mesh_devices(used,
                                max(len(jax.devices()) - used, 0))
        else:
            # serving default: the (replica, shard) mesh over EVERY
            # available device — all devices on the shard axis unless
            # ES_TPU_MESH_SHARDS / ES_TPU_MESH_REPLICAS say otherwise
            # (parallel/mesh.mesh_from_env) — so per-device corpus
            # bytes scale ~1/n_shards out of the box.
            from .. import parallel as par
            mesh = par.mesh_from_env()
        with self._mesh_lock:
            if self._mesh is None:
                self._mesh = mesh
            return self._mesh

    def _mesh_fanout(self):
        """(shard-axis devices, replica-axis devices) of the serving
        mesh — pack paths pad shard lists to a shard-axis multiple and
        scale breaker estimates by the replica fan-out."""
        from ..parallel.mesh import AXIS_REPLICA, AXIS_SHARD
        mesh = self._get_mesh()
        return mesh.shape[AXIS_SHARD], mesh.shape[AXIS_REPLICA]

    def _next_ver(self) -> int:
        with self._gen_lock:
            self._gen_ver += 1
            return self._gen_ver

    # -- repack scheduling ---------------------------------------------------

    def _delta_over_threshold(self, gen) -> bool:
        d = gen.delta_docs()
        return d > max(1, int(gen.base_docs * self.REPACK_DELTA_FRACTION))

    def _schedule_repack(self, kind: str, field: str,
                         segments: Sequence[Segment],
                         mapper: MapperService, trigger: str) -> None:
        """Fold the current segment list into a new base generation off
        the request thread, then swap. One in-flight repack per (kind,
        field); ``repack_mode == "sync"`` runs inline (tests)."""
        with self._gen_lock:
            if self._closed or (kind, field) in self._repacking:
                return
            self._repacking.add((kind, field))
            self._repack_threads = [t for t in self._repack_threads
                                    if t.is_alive()]
        segments = list(segments)

        def _run():
            t0 = time.perf_counter()
            try:
                if kind == "text":
                    self._build_text_generation(segments, mapper, field,
                                                trigger=trigger,
                                                mode="background")
                else:
                    self._build_knn_generation(segments, mapper, field,
                                               trigger=trigger,
                                               mode="background")
                swap_ms = (time.perf_counter() - t0) * 1e3
                self._swap_ms[kind].observe(swap_ms)
                from ..common import flightrec as _fr
                _fr.record("plane_swap", kind=kind, field=field,
                           trigger=trigger, ms=round(swap_ms, 3))
            except Exception as e:   # noqa: BLE001 — a failed repack
                # must never take down serving (the old generation keeps
                # serving and the next refresh retries) but it is
                # journaled and counted by event type, not passed over
                from ..common import flightrec as _fr
                _fr.record("plane_repack_failed", kind=kind, field=field,
                           trigger=trigger, error=repr(e)[:500])
            finally:
                with self._gen_lock:
                    self._repacking.discard((kind, field))

        if self.repack_mode == "sync":
            _run()
            return
        t = threading.Thread(target=_run, daemon=True,
                             name=f"es-repack-{kind}-{field}")
        with self._gen_lock:
            self._repack_threads.append(t)
        t.start()

    def drain_repacks(self, timeout: float = 30.0) -> None:
        """Join in-flight background repacks (tests / orderly shutdown)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._gen_lock:
                threads = [t for t in self._repack_threads if t.is_alive()]
                busy = bool(self._repacking)
            if not threads and not busy:
                return
            for t in threads:
                t.join(max(0.01, deadline - time.monotonic()))

    def notify_refresh(self, segments: Sequence[Segment],
                       mapper: MapperService,
                       knn_lists: Optional[Sequence[Sequence[Segment]]]
                       = None) -> None:
        """Engine refresh/merge hook (``index/engine.py`` →
        ``IndexService``): reconcile every cached generation against the
        new segment list NOW — delta packs and repack scheduling happen
        at refresh time on the indexing thread, not on the first search
        to notice the signature change. Never builds cold planes.

        ``segments`` is the POOLED (cross-shard) list — the space text
        generations serve in. ``knn_lists`` are the candidate views kNN
        generations may be keyed by (per-index-shard lists from the
        distributed searcher, plus the pooled list): each kNN generation
        reconciles against the candidate matching it with the SMALLEST
        delta, so another shard's corpus is never mistaken for this
        generation's delta tier (which would schedule repacks onto a
        pooled list no per-shard probe can ever match)."""
        segments = [s for s in segments if s.n_docs > 0]
        if not segments:
            return
        with self._gen_lock:
            # _closed is read under the lock it is written under —
            # release() racing a refresh listener must not see a torn
            # view of (closed, registry) (ESTP-R01)
            if self._closed:
                return
            text_fields = list(self._planes)
        for field in text_fields:
            sig = self._signature(segments, field)
            if sig is None:
                continue
            self._text_generation(segments, mapper, field,
                                  allow_sync_build=False)
        self._knn_reconcile(knn_lists or [segments], mapper)

    def _knn_reconcile(self, lists: Sequence[Sequence[Segment]],
                       mapper: MapperService) -> None:
        with self._gen_lock:
            items = list(self._knn_planes.items())
        for key, gen in items:
            field = key[0]
            best = None           # (delta_count, filtered_list, match)
            for lst in lists:
                lstf = [s for s in lst if s.n_docs > 0]
                if not lstf or \
                        self._knn_signature(lstf, field) is None:
                    continue
                m = gen.match(lstf)
                if m is None:
                    continue
                if best is None or len(m[0]) < best[0]:
                    best = (len(m[0]), lstf, m)
            if best is None:
                continue
            _, lstf, (delta_segs, delta_pos, base_pos) = best
            ver = self._next_ver()
            if not delta_segs:
                gen.clear_delta(base_pos, ver)
                continue
            if not self.delta_enabled:
                continue
            gen.update_delta(lstf, delta_segs, delta_pos, base_pos, ver)
            if self._delta_over_threshold(gen):
                self._schedule_repack("knn", field, lstf, mapper,
                                      "threshold")

    # -- lexical plane -------------------------------------------------------

    @staticmethod
    def _signature(segments: Sequence[Segment], field: str) -> Optional[tuple]:
        """Route-eligibility key over the segment list; None → route
        ineligible (deletes, nested docs, absent field)."""
        sig = []
        any_field = False
        for s in segments:
            if s.has_nested or not bool(s.live.all()):
                return None
            if field in s.text_fields:
                any_field = True
            sig.append((s.seg_id, s.n_docs))
        return tuple(sig) if any_field else None

    def _pack_text_shards(self, segments: Sequence[Segment], field: str):
        """(plane shard dicts, cross-segment avgdl) for a base pack."""
        sum_dl = 0.0
        doc_count = 0
        for s in segments:
            sdl, dc = s.field_stats(field)
            sum_dl += sdl
            doc_count += dc
        avgdl = sum_dl / doc_count if doc_count else 1.0
        shards = []
        for seg in segments:
            f = seg.text_fields.get(field)
            if f is None:
                n = seg.n_docs
                shards.append(dict(
                    term_ids={}, df=np.zeros(0, np.int32),
                    offsets=np.zeros(1, np.int64),
                    docs=np.zeros(0, np.int32), tf=np.zeros(0, np.float32),
                    doc_len=np.zeros(n, np.float32), avgdl=avgdl))
            else:
                shards.append(dict(
                    term_ids=f.term_ids, df=f.df, offsets=f.offsets,
                    docs=f.docs_host, tf=f.tf_host,
                    doc_len=f.doc_len_host, avgdl=avgdl))
        return shards, avgdl

    def _build_text_generation(self, segments: Sequence[Segment],
                               mapper: MapperService, field: str, *,
                               trigger: str, mode: str
                               ) -> TextServingGeneration:
        """Full base pack: breaker reservation, plane construction,
        batcher + warmup, atomic swap (releasing the old generation)."""
        from ..parallel.dist_search import DistributedSearchPlane as _P
        shards, avgdl = self._pack_text_shards(segments, field)
        # pad the shard list to a shard-axis multiple with empty shards
        # (no postings, no docs): the mesh partitions the leading corpus
        # dim over the shard axis, and a segment count that doesn't
        # divide it must not bounce the route back to the per-segment
        # path. Padding shards score nothing (no postings) and never
        # emit hits, so base_pos decoding only ever sees real shards.
        s_dev, n_repl = self._mesh_fanout()
        for _ in range((-len(shards)) % s_dev):
            shards.append(_P.empty_pad_shard(avgdl))
        # the dense tier is the big persistent allocation (T_pad × n_pad
        # bf16 per shard): reserve its estimate against the accounting
        # breaker BEFORE building, so an overfull node 429s instead of
        # OOMing inside the constructor
        from ..common.breakers import DEFAULT as _breakers
        from ..utils.shapes import round_up_multiple, round_up_pow2
        acct = _breakers.breaker("accounting")
        n_pad = round_up_pow2(max(
            max(s["doc_len"].shape[0] for s in shards), 1))
        threshold = max(n_pad // 256, 4096)
        t_est = max((min(int((np.asarray(s["df"]) > threshold).sum()),
                         _P.MAX_DENSE_TERMS) for s in shards),
                    default=0)
        nbytes = round_up_multiple(max(t_est, 1), 16) * n_pad * 2 * \
            len(shards) if t_est else 0
        # past the prune threshold the pack also builds the block-max
        # tier (impact-ordered int8 blocks ≈ docs i32 + codes i8 +
        # 12 B/block of bound metadata) and serves the rank-safe pruned
        # scan by default; the delta tier stays eager
        total_docs = sum(int(s["doc_len"].shape[0]) for s in shards)
        n_postings = sum(int(np.asarray(s["docs"]).shape[0])
                         for s in shards)
        bmx_kw = None
        if total_docs >= max(self.lex_prune_min_docs, 1):
            bmx_kw = {}
            nbytes += int(n_postings * 5.2) + 4096
        # device arrays replicate across the replica axis (each replica
        # group holds a full corpus copy), so the reservation scales by
        # the replica fan-out; the label records the per-DEVICE share
        # (shard-axis partitioning divides the bytes each chip holds)
        nbytes *= max(n_repl, 1)
        acct.add_estimate(
            nbytes, f"<serving plane [{field}] mesh {n_repl}x{s_dev}, "
                    f"~{nbytes // max(s_dev * n_repl, 1)} B/device>")
        try:
            plane = _P(self._get_mesh(), shards, field, blockmax=bmx_kw)
        except Exception:
            acct.release(nbytes)
            raise
        plane._acct_bytes = nbytes
        gen = TextServingGeneration(plane, segments, field, avgdl, self)
        return self._install_text_generation(gen, field, trigger, mode)

    def _install_text_generation(self, gen: TextServingGeneration,
                                 field: str, trigger: str,
                                 mode: str) -> TextServingGeneration:
        """Batcher + atomic swap, shared by the pack path and the
        warm-handoff import."""
        self._attach_batcher(gen)
        with self._gen_lock:
            racedep.note_write("plane_cache.generations", self)
            if self._closed:
                self._release_gen(gen)
                return gen
            old = self._planes.get(field)
            self._planes[field] = gen
        if old is not None:
            # double-buffering: the old generation served until this
            # swap; drop its reservation and stop its warmup now
            self._release_gen(old)
        self._record_rebuild("text", trigger, mode)
        # tier sweep OUTSIDE _gen_lock: the new resident plane may push
        # the node past its HBM budget — spill the LRU ones
        self.tiers.touch(gen)
        self.tiers.enforce_budget()
        return gen

    def plane_for(self, segments: Sequence[Segment], mapper: MapperService,
                  field: str):
        """The serving generation for this segment list, or None when the
        route is ineligible (deletes, nested docs, absent field) or the
        base is mid-repack after a structural change (the per-segment
        path serves the gap)."""
        segments = [s for s in segments if s.n_docs > 0]
        if not segments:
            return None
        if sum(s.n_docs for s in segments) < self.min_docs:
            return None
        if self._signature(segments, field) is None:
            return None
        return self._text_generation(segments, mapper, field,
                                     allow_sync_build=True)

    def _text_generation(self, segments, mapper, field: str,
                         allow_sync_build: bool):
        with self._gen_lock:
            gen = self._planes.get(field)
        if gen is not None:
            m = gen.match(segments)
            if m is not None:
                delta_segs, delta_pos, base_pos = m
                ver = self._next_ver()
                if not delta_segs:
                    gen.clear_delta(base_pos, ver)
                    return gen
                if self.delta_enabled:
                    gen.update_delta(segments, delta_segs, delta_pos,
                                     base_pos, ver)
                    if self._delta_over_threshold(gen):
                        self._schedule_repack("text", field, segments,
                                              mapper, "threshold")
                        if self.repack_mode == "sync":
                            with self._gen_lock:
                                return self._planes.get(field)
                    return gen
            elif self.delta_enabled:
                # merge/delete restructured the base: the old plane's hit
                # coordinates no longer decode against this list — repack
                # in the background, per-segment path serves meanwhile
                self._schedule_repack("text", field, segments, mapper,
                                      "structure")
                if self.repack_mode == "sync":
                    with self._gen_lock:
                        return self._planes.get(field)
                return None
        if not allow_sync_build:
            return None
        # the cold TIER beats a cold PACK: a demoted pack file matching
        # this list promotes through the handoff import (chunked local
        # read + device upload — no O(postings) re-pack)
        promoted = self._promote_from_cold("text", field, segments,
                                           mapper)
        if promoted is not None:
            return promoted
        # cold start (first build for this field) or legacy mode
        # (delta_enabled=False: rebuild-every-refresh, the pre-generation
        # behavior the live-indexing bench measures as its baseline)
        return self._build_text_generation(
            segments, mapper, field,
            trigger="cold" if gen is None else "structure", mode="sync")

    # -- kNN plane -----------------------------------------------------------

    @staticmethod
    def _knn_signature(segments: Sequence[Segment],
                       field: str) -> Optional[tuple]:
        """Route-eligibility key for the kNN plane; None → ineligible
        (deletes, nested docs, or the field has no vectors anywhere — the
        plane packs exists-masked rows but per-doc liveness/parent masks
        stay on the per-segment path)."""
        sig = []
        any_field = False
        for s in segments:
            if s.has_nested or not bool(s.live.all()):
                return None
            if field in s.vector_fields:
                any_field = True
            sig.append((s.seg_id, s.n_docs))
        return tuple(sig) if any_field else None

    def knn_plane_for(self, segments: Sequence[Segment],
                      mapper: MapperService, field: str):
        """The kNN serving generation (``DistributedKnnPlane`` base —
        pack-time corpus invariants + blocked running-top-k — plus a BLAS
        delta tier) for this segment list, or None when the route is
        ineligible. One SEGMENT per plane shard, same as the lexical
        plane, so tie order matches the per-segment path."""
        from ..index.mapping import DenseVectorFieldType
        segments = [s for s in segments if s.n_docs > 0]
        if not segments:
            return None
        ft = mapper.field_type(field)
        if not isinstance(ft, DenseVectorFieldType):
            return None
        if self._knn_signature(segments, field) is None:
            return None
        return self._knn_generation(segments, mapper, field,
                                    allow_build=True)

    def _knn_generation(self, segments, mapper, field: str,
                        allow_build: bool):
        with self._gen_lock:
            items = list(self._knn_planes.items())
        # pick the generation whose base covers this list with the
        # SMALLEST delta (a pooled probe must prefer a pooled base over
        # eagerly scanning every other shard's corpus as "delta")
        best = None                   # (delta_count, key, gen, match)
        for key, gen in items:
            if key[0] != field:
                continue
            m = gen.match(segments)
            if m is None:
                continue
            if best is None or len(m[0]) < best[0]:
                best = (len(m[0]), key, gen, m)
        if best is not None:
            _, key, gen, (delta_segs, delta_pos, base_pos) = best
            with self._gen_lock:
                if key in self._knn_planes:
                    self._knn_planes.move_to_end(key)
                self._knn_build_streak = 0
            ver = self._next_ver()
            if not delta_segs:
                gen.clear_delta(base_pos, ver)
                return gen
            if self.delta_enabled:
                gen.update_delta(segments, delta_segs, delta_pos,
                                 base_pos, ver)
                if self._delta_over_threshold(gen):
                    self._schedule_repack("knn", field, segments, mapper,
                                          "threshold")
                return gen
            # legacy mode: fall through to a full rebuild
        if not allow_build:
            return None
        # cold-tier probe before any build-vs-thrash reasoning: a
        # spilled plane of this exact base is this probe's own data
        promoted = self._promote_from_cold("knn", field, segments,
                                           mapper)
        if promoted is not None:
            return promoted
        with self._gen_lock:
            # read under the lock: the streak is reset/bumped under it,
            # and an off-lock read races the repack thread (ESTP-R01)
            build_streak = self._knn_build_streak
        if build_streak >= self.KNN_PLANE_CACHE_MAX:
            # every recent probe missed: building would evict entries the
            # same request needs again (O(corpus) repack per query) — the
            # per-segment fallback is the cheaper correct path
            return None
        gen = self._build_knn_generation(segments, mapper, field,
                                         trigger="cold", mode="sync")
        if gen is not None:
            with self._gen_lock:
                self._knn_build_streak += 1
        return gen

    @staticmethod
    def _pack_knn_shards(segments: Sequence[Segment], field: str):
        """(plane shard dicts, dim) for a kNN base pack, or None when the
        field's dims disagree across segments — shared by the build path
        and the warm-handoff bundle export."""
        shards = []
        for seg in segments:
            f = seg.vector_fields.get(field)
            if f is None:
                shards.append(dict(
                    vectors=np.zeros((seg.n_docs, 1), np.float32),
                    exists=np.zeros(seg.n_docs, bool)))
            else:
                ex = np.zeros(seg.n_docs, bool)
                ex[: f.exists.shape[0]] = f.exists
                shards.append(dict(vectors=f.matrix_host, exists=ex))
        dims = {s["vectors"].shape[1] for s in shards if s["exists"].any()}
        if len(dims) > 1:
            return None
        dim = dims.pop() if dims else 1
        for s in shards:
            if not s["exists"].any():
                s["vectors"] = np.zeros((s["exists"].shape[0], dim),
                                        np.float32)
        return shards, dim

    def _build_knn_generation(self, segments, mapper, field: str, *,
                              trigger: str, mode: str):
        """Full kNN base pack + atomic swap into the LRU (superseded
        generations of the same field sharing base segments are
        released first — a repack kept part of the list, so identity
        overlap marks the predecessors; generations for OTHER index
        shards of the same field are disjoint and survive)."""
        from ..index.mapping import DenseVectorFieldType
        ft = mapper.field_type(field)
        if not isinstance(ft, DenseVectorFieldType):
            return None
        from ..parallel.dist_search import DistributedKnnPlane
        # step similarity: ranking by raw dot is order-equivalent for
        # max_inner_product (its _score transform is monotone); unknown
        # similarity strings keep the per-segment path's quirks
        similarity = {"cosine": "cosine", "dot_product": "dot_product",
                      "l2_norm": "l2_norm",
                      "max_inner_product": "dot_product"}.get(
                          getattr(ft, "similarity", "cosine"))
        if similarity is None:
            return None
        got = self._pack_knn_shards(segments, field)
        if got is None:
            return None
        shards, dim = got
        # pad the shard list to a shard-axis multiple with empty shards
        # (exists all-False — they score NEG_INF and never emit hits),
        # same as the lexical pack: the corpus dim must divide the mesh
        s_dev, n_repl = self._mesh_fanout()
        for _ in range((-len(shards)) % s_dev):
            shards.append(DistributedKnnPlane.empty_pad_shard(dim))
        # the packed corpus (f32[S, n_pad, dim] + invariants) is the big
        # persistent allocation: reserve it against the accounting breaker
        # before building, like the lexical plane's dense tier
        from ..common.breakers import DEFAULT as _breakers
        from ..utils.shapes import round_up_pow2
        acct = _breakers.breaker("accounting")
        n_pad = round_up_pow2(max(max(s["exists"].shape[0]
                                      for s in shards), 1))
        nbytes = len(shards) * n_pad * (dim * 4 + 5)
        # past the IVF threshold the pack also builds the quantized tier
        # (int8 codes + scale/off/row maps ≈ dim+12 B/row) and serves
        # cluster-pruned by default; the delta tier stays exact
        total_docs = sum(int(s["exists"].shape[0]) for s in shards)
        ivf_kw = None
        if total_docs >= max(self.knn_ivf_min_docs, 1):
            ivf_kw = {}
            nbytes += len(shards) * n_pad * (dim + 12)
        key = (field, tuple(id(s) for s in segments))
        # replica groups hold full corpus copies (see the lexical pack)
        nbytes *= max(n_repl, 1)
        acct.add_estimate(
            nbytes, f"<knn serving plane [{field}] mesh {n_repl}x{s_dev},"
                    f" ~{nbytes // max(s_dev * n_repl, 1)} B/device>")
        try:
            plane = DistributedKnnPlane(self._get_mesh(), shards,
                                        similarity=similarity,
                                        ivf=ivf_kw)
        except Exception:
            acct.release(nbytes)
            raise
        plane._acct_bytes = nbytes
        gen = KnnServingGeneration(plane, segments, field, self)
        return self._install_knn_generation(gen, key, nbytes, trigger,
                                            mode)

    def _install_knn_generation(self, gen: KnnServingGeneration,
                                key: tuple, nbytes: int, trigger: str,
                                mode: str):
        """Atomic swap into the kNN LRU + batcher, shared by the pack
        path and the warm-handoff import. Evicts ONLY at swap time,
        never before the build: the predecessor generations keep
        serving for the whole pack window (double-buffering — a
        pre-build eviction would leave a gap that concurrent probes
        fill with synchronous request-thread cold builds, the exact
        storm this module eliminates). The breaker transiently holds
        old+new, same as the lexical path."""
        from ..common.breakers import DEFAULT as _breakers
        acct = _breakers.breaker("accounting")
        field = key[0]
        new_ids = set(key[1])
        with self._gen_lock:
            racedep.note_write("plane_cache.generations", self)
            raced = self._knn_planes.get(key)
            if raced is not None:
                # another thread built the same base meanwhile: keep the
                # winner, release this copy's reservation
                acct.release(nbytes)
                self._knn_planes.move_to_end(key)
                return raced
            if self._closed:
                acct.release(nbytes)
                return None
            # superseded generations of this field (identity overlap
            # with the new base — a repack kept part of their list) +
            # any LRU overflow go out as the new generation goes in
            doomed = [ok for ok in self._knn_planes
                      if ok[0] == field and ok != key
                      and any(sid in new_ids for sid in ok[1])]
            old_gens = [self._knn_planes.pop(ok) for ok in doomed]
            while len(self._knn_planes) >= self.KNN_PLANE_CACHE_MAX:
                _, g = self._knn_planes.popitem(last=False)
                old_gens.append(g)
            self._knn_planes[key] = gen
        for g in old_gens:
            self._release_gen(g)
        self._attach_batcher(gen, knn=True)
        self._record_rebuild("knn", trigger, mode)
        self.tiers.touch(gen)
        self.tiers.enforce_budget()
        return gen

    # -- warm handoff: plane-bundle export / import --------------------------
    #
    # The packed base plane is a self-contained tensor bundle (CSR
    # postings + frozen avgdl for text, vector matrices + similarity for
    # kNN) keyed by the (seg_id, n_docs) signature of its base segment
    # list. A recovering/rejoining node whose copies carry the same
    # signature (file-based recovery ships the store wholesale;
    # kill-and-rejoin reloads it) can install the donor's bundle as a
    # live serving generation and serve warm immediately — no segment
    # re-extraction, no request-thread cold pack (the rebuild-storm
    # signature). Serialization is the data-only wire codec
    # (common/datacodec): tensors in, tensors out, nothing executable.

    def _bundle_for(self, gen) -> Optional[dict]:
        """One generation → its self-contained handoff bundle (also the
        cold-tier pack-file payload), or None for a foreign/legacy plane
        that cannot export. ``export_packed`` is warm-safe: a demoted
        plane serializes from its host copies without re-upload."""
        try:
            packed = gen.base.export_packed()
        except Exception:   # noqa: BLE001 — foreign/legacy plane
            return None
        doc = {"kind": gen.kind, "field": gen.field,
               "signature": [(s.seg_id, int(s.n_docs))
                             for s in gen.base_segments],
               "packed": packed}
        if gen.kind == "text":
            doc["avgdl"] = float(gen.avgdl)
        return doc

    def export_bundles(self) -> List[dict]:
        """One handoff bundle per live serving generation, carrying the
        plane's POST-pack tensors (``export_packed``: sorted-merge
        tables, dense tier, block-max/IVF tiers, host-CSR) plus the
        frozen invariants (avgdl) and the base segment signature — the
        importer reconstructs bit-identical serving with zero pack
        work."""
        out: List[dict] = []
        for gen in self.generations():
            bundle = self._bundle_for(gen)
            if bundle is not None:
                out.append(bundle)
        return out

    def export_bundle_blobs(self) -> List[dict]:
        """Pre-serialized handoff payloads (``{kind, field, blob}``):
        live generations serialize now; COLD-tier planes ship their
        pack file's text as-is — a spilled plane is its own handoff
        artifact, no re-serialization on the donor offer."""
        from ..common.datacodec import dumps_b64
        out: List[dict] = []
        for bundle in self.export_bundles():
            out.append({"kind": bundle["kind"], "field": bundle["field"],
                        "blob": dumps_b64(bundle)})
        for rec in self.tiers.cold_records():
            try:
                out.append({"kind": rec.kind, "field": rec.field,
                            "blob": self.tiers.cold_blob(rec)})
            except Exception:   # noqa: BLE001 — spill file vanished
                continue
        return out

    def _evict_generation(self, gen) -> bool:
        """Remove ONE generation from the serving registry (cold
        demotion): registry pop under ``_gen_lock``, breaker release +
        batcher retire outside it. False → the generation was no longer
        registered (a racing swap/release already owns its teardown)."""
        found = False
        with self._gen_lock:
            racedep.note_write("plane_cache.generations", self)
            field = getattr(gen, "field", None)
            if self._planes.get(field) is gen:
                self._planes.pop(field)
                found = True
            else:
                for k, g in list(self._knn_planes.items()):
                    if g is gen:
                        self._knn_planes.pop(k)
                        found = True
                        break
        if not found:
            return False
        self._release_gen(gen)
        return True

    def _promote_from_cold(self, kind: str, field: str,
                           segments: Sequence[Segment],
                           mapper: MapperService):
        """Probe the cold tier before a cold pack: a spilled plane whose
        base signature still matches the local segment list promotes
        through the SAME import path warm handoff uses (chunked mmap
        read of the pack file → ``import_bundle``) — device upload only,
        no re-pack. Returns the installed generation or None."""
        for rec in self.tiers.cold_records(kind, field):
            if self._match_signature(segments, rec.signature) is None:
                continue
            try:
                bundle = self.tiers.cold_bundle(rec)
            except Exception:   # noqa: BLE001 — unreadable pack file:
                continue        # fall back to the ordinary cold build
            if not self.import_bundle(bundle, segments, mapper):
                continue
            sig = [(str(a), int(b)) for a, b in rec.signature]
            with self._gen_lock:
                if kind == "text":
                    gen = self._planes.get(field)
                else:
                    gen = next(
                        (g for (f, _k), g in self._knn_planes.items()
                         if f == field and [(s.seg_id, int(s.n_docs))
                                            for s in g.base_segments]
                         == sig), None)
            self.tiers.on_cold_promoted(rec, gen)
            return gen
        return None

    def _match_signature(self, segments: Sequence[Segment],
                         signature) -> Optional[List[Segment]]:
        """Ordered-subsequence match of a bundle's base signature
        against LOCAL segments by (seg_id, n_docs) — identity across
        processes. None → the local copies diverged (ops-based recovery
        re-segmented differently); the caller falls back to a repack."""
        matched: List[Segment] = []
        pos = 0
        for want in signature or ():
            wid, wnd = str(want[0]), int(want[1])
            nxt = next((i for i in range(pos, len(segments))
                        if segments[i].seg_id == wid
                        and int(segments[i].n_docs) == wnd), None)
            if nxt is None:
                return None
            matched.append(segments[nxt])
            pos = nxt + 1
        return matched if matched else None

    def import_bundle(self, bundle: dict, segments: Sequence[Segment],
                      mapper: MapperService) -> bool:
        """Install one handoff bundle as a live serving generation over
        the LOCAL segments matching its base signature. Returns False
        (never raises) when the bundle cannot be adopted — signature
        mismatch, route-ineligible local copies (deletes/nested), or a
        failed build — so recovery degrades to the ordinary cold pack
        instead of failing."""
        try:
            segments = [s for s in segments if s.n_docs > 0]
            matched = self._match_signature(segments,
                                            bundle.get("signature"))
            if matched is None:
                return False
            field = str(bundle["field"])
            if self._have_same_base(bundle.get("kind"), field,
                                    bundle.get("signature")):
                # idempotent: per-shard recovery offers and the
                # replica-wiring trigger race duplicate pulls of the
                # same bundles — a second import of an identical base
                # would only churn generations (and retire the batcher
                # a concurrent probe is using)
                return True
            if bundle.get("kind") == "text":
                if self._signature(matched, field) is None:
                    return False
                return self._import_text_generation(
                    matched, field, float(bundle["avgdl"]),
                    bundle["packed"]) is not None
            if bundle.get("kind") == "knn":
                if self._knn_signature(matched, field) is None:
                    return False
                return self._import_knn_generation(
                    matched, field, bundle["packed"]) is not None
            return False
        except Exception:   # noqa: BLE001 — a bad bundle must degrade
            return False    # to the repack path, never break recovery

    def _have_same_base(self, kind, field: str, signature) -> bool:
        """True when a live generation of (kind, field) already covers
        exactly this base signature."""
        want = [(str(a), int(b)) for a, b in (signature or ())]
        if kind == "text":
            with self._gen_lock:
                gen = self._planes.get(field)
            gens = [gen] if gen is not None else []
        else:
            with self._gen_lock:
                gens = [g for (f, _k), g in self._knn_planes.items()
                        if f == field]
        return any(
            [(s.seg_id, int(s.n_docs)) for s in g.base_segments] == want
            for g in gens)

    def _import_text_generation(self, segments: Sequence[Segment],
                                field: str, avgdl: float, packed: dict):
        """Install a shipped text plane: breaker reservation from the
        bundle's real tensor sizes, ``from_packed`` reconstruction
        (device upload only — no pack), then the shared swap."""
        from ..common.breakers import DEFAULT as _breakers
        from ..parallel.dist_search import DistributedSearchPlane as _P
        acct = _breakers.breaker("accounting")
        nbytes = int(np.asarray(packed["docs"]).nbytes
                     + np.asarray(packed["impacts"]).nbytes)
        if packed.get("dense") is not None:
            # shipped as exact f32; resident as bf16 (half)
            nbytes += int(np.asarray(packed["dense"]).nbytes) // 2
        acct.add_estimate(
            nbytes, f"<serving plane [{field}] warm-handoff import, "
                    f"{nbytes} B>")
        try:
            plane = _P.from_packed(self._get_mesh(), packed)
        except Exception:
            acct.release(nbytes)
            raise
        plane._acct_bytes = nbytes
        gen = TextServingGeneration(plane, segments, field, avgdl, self)
        return self._install_text_generation(gen, field, "handoff",
                                             "import")

    def _import_knn_generation(self, segments: Sequence[Segment],
                               field: str, packed: dict):
        from ..common.breakers import DEFAULT as _breakers
        from ..parallel.dist_search import DistributedKnnPlane
        acct = _breakers.breaker("accounting")
        nbytes = int(packed.get("nbytes") or 0) or (
            int(np.asarray(packed["vecs"]).nbytes)
            + int(np.asarray(packed["vnorm2"]).nbytes)
            + int(np.asarray(packed["exists"]).nbytes))
        acct.add_estimate(
            nbytes, f"<knn serving plane [{field}] warm-handoff "
                    f"import, {nbytes} B>")
        try:
            plane = DistributedKnnPlane.from_packed(self._get_mesh(),
                                                    packed)
        except Exception:
            acct.release(nbytes)
            raise
        plane._acct_bytes = nbytes
        gen = KnnServingGeneration(plane, segments, field, self)
        key = (field, tuple(id(s) for s in segments))
        return self._install_knn_generation(gen, key, nbytes, "handoff",
                                            "import")

    # -- lifecycle -----------------------------------------------------------

    def release(self) -> None:
        """Release every generation's breaker reservation (the owning
        index is closing or being deleted); in-flight repacks see
        ``_closed`` and drop their build instead of swapping it in,
        and are then JOINED so no repack thread outlives its cache
        (ESTP-T01 lifecycle discipline: a late swap into a released
        registry would leak the new plane's breaker bytes)."""
        with self._gen_lock:
            self._closed = True
            racedep.note_write("plane_cache.generations", self)
            gens = list(self._planes.values()) + \
                list(self._knn_planes.values())
            self._planes.clear()
            self._knn_planes.clear()
            runners = list(self._fused_runners.values())
            self._fused_runners.clear()
        for r in runners:
            self._retire(r)
        for gen in gens:
            self._release_gen(gen)
        self.drain_repacks(timeout=5.0)
        # drop the cold tier's pack files; the next _metrics_doc scrape
        # reports explicit per-device zeros (every generation is gone)
        self.tiers.release()
