"""One-dispatch query planner: lower a request's bool tree + knn clause
+ rescore window into ONE serving dispatch over both planes.

A hybrid RRF request historically cost two serving dispatches (text
plane, knn plane) plus host-side Python fusion, and bool trees were
scored clause-by-clause on the per-segment path — the opposite of the
"Lucene is all you need" single-engine retrieval story (arxiv
2308.14963; Anserini's dense+sparse integration, arxiv 2304.12139).
This module is the small query compiler that closes that gap:

- :func:`lower_body` recognizes request bodies whose retrieval pipeline
  the planes can run END TO END — a bool tree of bag-of-terms clauses
  over one text field (must/should/filter/must_not + resolved
  minimum_should_match), at most one filter-free knn clause, RRF or
  linear rank fusion, and a rescore window whose rescore_query is a bag
  over the same field — and compiles it into a :class:`FusedPlan`.
- :class:`FusedPlanRunner` executes a plan batch through the serving
  GENERATIONS (``plane_route.py``) recast as providers of scoring
  *stages*: the lexical bool scan, the kNN blocked scan, rank fusion
  and the rescore-window reorder. On an accelerator backend the whole
  pipeline is one jitted program over both planes' tensors
  (``parallel/dist_search.build_fused_hybrid_step``), bucketed into the
  same (B, k, L, params) shape lattice as every other serving step —
  it compiles per request SHAPE, never per query. On the CPU backend
  the stages are the planes' host-native scorers, with the lexical and
  kNN stages running concurrently inside the one dispatch (the BLAS
  kNN scan releases the GIL under the lexical scatter-adds).

Non-lowerable bodies — and lowerable ones whose runner cannot serve
them (dense-tier terms on a jitted bool slice, mis-aligned base
generations) — fall back to the existing two-dispatch + host-fusion
path unchanged; ``es_planner_lowered_total{outcome}`` counts both
verdicts. ``ES_TPU_FUSED_PLANNER=0`` disables the planner outright
(the bisection knob)."""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..index.mapping import DenseVectorFieldType, MapperService
from ..ops.fused_query import MAX_BOOL_CLAUSES
from .plane_route import extract_bag_of_terms

#: body features the fused path cannot serve (same set the plane route
#: excludes, minus the three the planner exists to fuse; ``aggs`` left
#: this list in PR 16 — agg trees lower via ``agg_planner.lower_aggs``)
_FUSED_INCOMPATIBLE = ("sort", "collapse", "suggest", "search_after",
                       "min_score")

_RESCORE_MODES = ("total", "multiply", "avg", "max", "min")


def planner_enabled() -> bool:
    """The fused on/off env gate (bisection knob): default on."""
    return os.environ.get("ES_TPU_FUSED_PLANNER", "1").lower() \
        not in ("0", "false")


@dataclass
class KnnPlan:
    field: str
    query_vector: np.ndarray
    k: int
    num_candidates: int
    boost: float = 1.0
    nprobe: Optional[int] = None
    rerank: Optional[int] = None


@dataclass
class RescorePlan:
    terms: List[str]
    qw: float = 1.0
    rw: float = 1.0
    mode: str = "total"
    window: int = 10


@dataclass
class FusedPlan:
    """A lowered request: the planner's IR. ``bag`` is set (and
    ``clauses`` holds the single should clause) when the query is a
    plain bag of terms — the lexical stage then rides the existing
    ``serve`` path with its pruning tier; real bool trees use the
    clause-bit bool stage."""
    field: str
    clauses: List[Tuple[str, List[str]]]
    msm: int
    bag: Optional[List[str]] = None
    knn: Optional[KnnPlan] = None
    fusion: Optional[str] = None          # "rrf" | "sum" | None
    rank_constant: int = 60
    rank_window: int = 10
    rescore: Optional[RescorePlan] = None
    k: int = 10                           # size + from
    window_text: int = 10                 # lexical stage dispatch width
    aggs: Optional[object] = None         # agg_planner.AggPlan
    lower_ms: float = 0.0

    def n_stages(self) -> int:
        """Stages this plan fuses into one dispatch (the
        ``es_planner_stages_per_dispatch`` observation)."""
        n = 1                              # lexical scan
        if self.knn is not None:
            n += 2                         # knn scan + rank fusion
        if self.rescore is not None:
            n += 1
        if self.aggs is not None:
            n += self.aggs.n_stages        # one stage per tree node
        return n


def _lower_bool_tree(query_spec, mapper: MapperService):
    """Query spec → (field, clauses, msm, bag|None) when every clause is
    a bag of terms over ONE text field, else None. ``bag`` is the merged
    single-clause form when :func:`extract_bag_of_terms` recognizes the
    whole query (pure-should shapes)."""
    ext = extract_bag_of_terms(query_spec, mapper)
    if ext is not None:
        field, terms = ext
        return field, [("should", list(terms))], 1, list(terms)
    if not isinstance(query_spec, dict) or len(query_spec) != 1:
        return None
    (kind, body), = query_spec.items()
    if kind != "bool" or not isinstance(body, dict):
        return None
    if set(body) - {"must", "should", "filter", "must_not",
                    "minimum_should_match", "boost"}:
        return None
    if body.get("boost", 1.0) != 1.0:
        return None
    field = None
    clauses: List[Tuple[str, List[str]]] = []
    n_should = n_positive = 0
    for role in ("must", "should", "filter", "must_not"):
        members = body.get(role)
        if members is None:
            continue
        if isinstance(members, dict):
            members = [members]
        if not isinstance(members, list):
            return None
        for member in members:
            sub = extract_bag_of_terms(member, mapper)
            if sub is None:
                return None
            f, terms = sub
            if field is None:
                field = f
            elif field != f:
                return None       # cross-field: scores/stats differ
            clauses.append((role, list(terms)))
            if role == "should":
                n_should += 1
            if role in ("must", "should", "filter"):
                n_positive += 1
    if field is None or not clauses or n_positive == 0:
        # a pure must_not tree matches "everything else" — the plane
        # only sees docs its candidate runs touch, so it cannot serve it
        return None
    if len(clauses) > MAX_BOOL_CLAUSES:
        return None
    msm = body.get("minimum_should_match")
    if msm is None:
        msm_eff = 0 if any(r in ("must", "filter")
                           for r, _ in clauses) else (1 if n_should
                                                     else 0)
    else:
        if not isinstance(msm, int) or isinstance(msm, bool) or msm < 0:
            return None           # percent / negative forms: fall back
        msm_eff = msm
    return field, clauses, msm_eff, None


def _lower_knn(knn_spec, mapper: MapperService) -> Optional[KnnPlan]:
    if isinstance(knn_spec, list):
        if len(knn_spec) != 1:
            return None
        knn_spec = knn_spec[0]
    if not isinstance(knn_spec, dict):
        return None
    if set(knn_spec) - {"field", "query_vector", "k", "num_candidates",
                        "boost", "nprobe", "rerank"}:
        return None               # filter / similarity override etc.
    field = knn_spec.get("field")
    qv = knn_spec.get("query_vector")
    if field is None or qv is None:
        return None
    if not isinstance(mapper.field_type(field), DenseVectorFieldType):
        return None
    try:
        k = int(knn_spec.get("k", 10))
        num_candidates = int(knn_spec.get("num_candidates", max(k, 10)))
        boost = float(knn_spec.get("boost", 1.0))
    except (TypeError, ValueError):
        return None
    if k < 1 or num_candidates < k:
        return None
    nprobe = knn_spec.get("nprobe")
    rerank = knn_spec.get("rerank")
    if nprobe is not None:
        nprobe = int(nprobe)
        if nprobe < 0:
            return None
    if rerank is not None:
        rerank = int(rerank)
        if rerank < 1:
            return None
    return KnnPlan(field=field,
                   query_vector=np.asarray(qv, np.float32), k=k,
                   num_candidates=num_candidates, boost=boost,
                   nprobe=nprobe, rerank=rerank)


def _lower_rescore(rescore_spec, field: str,
                   mapper: MapperService) -> Optional[RescorePlan]:
    if isinstance(rescore_spec, list):
        if len(rescore_spec) != 1:
            return None
        rescore_spec = rescore_spec[0]
    if not isinstance(rescore_spec, dict) or \
            set(rescore_spec) - {"window_size", "query"}:
        return None
    q = rescore_spec.get("query") or {}
    if set(q) - {"rescore_query", "query_weight",
                 "rescore_query_weight", "score_mode"}:
        return None
    rq = q.get("rescore_query")
    if rq is None:
        return None
    sub = extract_bag_of_terms(rq, mapper)
    if sub is None or sub[0] != field:
        return None
    mode = q.get("score_mode", "total")
    if mode not in _RESCORE_MODES:
        return None
    try:
        return RescorePlan(terms=list(sub[1]),
                           qw=float(q.get("query_weight", 1.0)),
                           rw=float(q.get("rescore_query_weight", 1.0)),
                           mode=mode,
                           window=int(rescore_spec.get("window_size",
                                                       10)))
    except (TypeError, ValueError):
        return None


def lower_body(body: dict, mapper: MapperService) -> Optional[FusedPlan]:
    """Request body → :class:`FusedPlan`, or None when any part of the
    pipeline is outside the planner's fragment (the caller then takes
    the existing path unchanged). Plain bag queries WITHOUT knn or
    rescore are deliberately not lowered — the existing plane route
    already serves them (request cache, pruning tier and all)."""
    t0 = time.perf_counter()
    if any(body.get(k) for k in _FUSED_INCOMPATIBLE):
        return None
    agg_plan = None
    agg_spec = body.get("aggs") or body.get("aggregations")
    if agg_spec is not None:
        from .agg_planner import fused_aggs_enabled, lower_aggs
        if not fused_aggs_enabled():
            return None
        agg_plan = lower_aggs(agg_spec, mapper)
        if agg_plan is None:
            return None           # tree outside the fused fragment
    k = int(body.get("size", 10)) + int(body.get("from", 0))
    if k <= 0:
        if agg_plan is None:
            return None
        k = 0                     # size:0 analytics — agg stages only
    query_spec = body.get("query")
    knn_spec = body.get("knn")
    rank_spec = body.get("rank")
    rescore_spec = body.get("rescore")
    if query_spec is None:
        # knn-only: nothing to lower. ShardSearcher serves it from the
        # kNN rankings alone (shard[plan] route=knn, no query phase)
        return None
    if agg_plan is not None and knn_spec is not None:
        # top-level knn widens the match set the aggs run over
        # (hybrid hits participate in aggregations) — the agg stages
        # pool text masks only, so hybrid analytics keeps the legacy
        # path
        return None
    lowered = _lower_bool_tree(query_spec, mapper)
    if lowered is None:
        return None
    field, clauses, msm, bag = lowered
    knn = None
    fusion = None
    rank_constant, rank_window = 60, max(k, 10)
    if knn_spec is not None:
        knn = _lower_knn(knn_spec, mapper)
        if knn is None:
            return None
        if rank_spec is not None:
            if not isinstance(rank_spec, dict) or \
                    set(rank_spec) != {"rrf"}:
                return None
            rrf = rank_spec.get("rrf") or {}
            if not isinstance(rrf, dict) or \
                    set(rrf) - {"rank_constant", "rank_window_size"}:
                return None
            try:
                rank_constant = int(rrf.get("rank_constant", 60))
                rank_window = int(rrf.get("rank_window_size",
                                          max(k, 10)))
            except (TypeError, ValueError):
                return None
            if rank_constant < 1 or rank_window < 1:
                return None
            fusion = "rrf"
        else:
            fusion = "sum"
    elif rank_spec is not None:
        return None               # rank without knn: nothing to fuse
    rescore = None
    if rescore_spec is not None:
        rescore = _lower_rescore(rescore_spec, field, mapper)
        if rescore is None:
            return None
    if knn is None and rescore is None and bag is not None and \
            agg_plan is None:
        return None               # plain bag: existing plane route
    window_text = max(k, rank_window) if fusion == "rrf" else k
    if rescore is not None:
        window_text = max(window_text, rescore.window)
    plan = FusedPlan(field=field, clauses=clauses, msm=msm, bag=bag,
                     knn=knn, fusion=fusion,
                     rank_constant=rank_constant,
                     rank_window=rank_window, rescore=rescore, k=k,
                     window_text=window_text, aggs=agg_plan)
    plan.lower_ms = (time.perf_counter() - t0) * 1e3
    return plan


# ---------------------------------------------------------------------------
# Plan execution: the serving generations as stage providers
# ---------------------------------------------------------------------------


class FusedFallback(Exception):
    """The runner cannot serve this dispatch after all (dense-tier
    terms on a jitted slice, delta+rescore on a device backend, …):
    the caller re-serves through the legacy two-dispatch path."""


def knn_raw_to_score_host(similarity: str, raw: float) -> float:
    """Host scalar twin of ``ops/fused_query.knn_raw_to_score`` —
    identical formulas to ``ShardSearcher._knn_score_from_raw`` so the
    fused path's knn _scores match the legacy knn section bit-for-bit."""
    if similarity in ("cosine", "cos", "dot_product"):
        return (1.0 + raw) / 2.0
    if similarity == "max_inner_product":
        return 1.0 / (1.0 - raw) if raw < 0 else raw + 1.0
    return 1.0 / (1.0 + max(0.0, -raw))


def rrf_fuse_rows(rankings, rc: int):
    """THE host RRF fusion (float64 dict, rankings in list order,
    (score desc, shard asc, doc asc) sort) — one copy shared by the
    legacy knn section (``shard_search.py``) and the fused runner, so
    fused-vs-two-dispatch parity is bitwise BY SHARED CODE, not by
    keeping two loops in sync. ``rankings``: ranked
    ``[(score, shard, doc), ...]`` lists."""
    rrf: Dict[Tuple[int, int], float] = {}
    for ranking in rankings:
        for rank_i, row in enumerate(ranking):
            si, d = row[1], row[2]
            rrf[(si, d)] = rrf.get((si, d), 0.0) + 1.0 / (rc + rank_i
                                                          + 1)
    return sorted(((sc, si, d) for (si, d), sc in rrf.items()),
                  key=lambda c: (-c[0], c[1], c[2]))


def sum_fuse_rows(rankings):
    """THE host linear (hybrid-sum) fusion — docs in several rankings
    sum their scores in list order; shared by the legacy knn section
    and the fused runner (see :func:`rrf_fuse_rows`)."""
    combined: Dict[Tuple[int, int], float] = {}
    for ranking in rankings:
        for sc, si, d in ranking:
            combined[(si, d)] = combined.get((si, d), 0.0) + sc
    return sorted(((sc, si, d) for (si, d), sc in combined.items()),
                  key=lambda c: (-c[0], c[1], c[2]))


class FusedPlanRunner:
    """Executes plan batches over a (text generation, knn generation)
    pair — the two planes recast as stage providers the planner
    composes. One runner per generation pair, owned by
    ``plane_route.ServingPlaneCache``; its micro-batcher co-batches
    concurrent fused requests exactly like the per-plane batchers."""

    kind = "fused"

    def __init__(self, text_gen, knn_gen=None, cache=None):
        self.text_gen = text_gen
        self.knn_gen = knn_gen
        self._cache = cache
        # the micro-batcher hangs off the runner like off a plane
        self._microbatcher = None

    # -- capability probes ---------------------------------------------------

    def _text_base(self):
        return self.text_gen.__dict__.get("base", self.text_gen)

    def _knn_base(self):
        return self.knn_gen.__dict__.get("base", self.knn_gen) \
            if self.knn_gen is not None else None

    def serves_host(self) -> bool:
        return self._text_base()._host_csr is not None

    def serving_shapes(self, k_buckets, max_b) -> list:
        """Nothing to warm ahead of traffic: a fused program's shape
        follows the plan (windows, clause slots, rescore), so each
        compiles on its first dispatch."""
        return []

    def _bases_aligned(self) -> bool:
        """Device fused step unifies candidates by SHARD INDEX — valid
        only when both generations packed the same base segment list."""
        if self.knn_gen is None:
            return True
        tb = getattr(self.text_gen, "base_segments", None)
        kb = getattr(self.knn_gen, "base_segments", None)
        if tb is None or kb is None:
            return True           # bare planes (tests) — caller aligned
        return len(tb) == len(kb) and \
            all(a is b for a, b in zip(tb, kb))

    def can_serve(self, plan: FusedPlan) -> bool:
        if plan.knn is not None and self.knn_gen is None:
            return False
        if plan.aggs is not None and not self.serves_host():
            # agg stages pool masks from the host CSR tier; a jitted-
            # only plane keeps the legacy two-pass analytics path
            return False
        if self.serves_host():
            return True
        # jitted path: the bool/fused steps slice only the sparse tier
        base = self._text_base()
        terms = [t for _r, ts in plan.clauses for t in ts]
        if plan.rescore is not None:
            terms += list(plan.rescore.terms)
        if base.has_dense_terms(terms):
            return False
        kb = self._knn_base()
        if kb is not None:
            if base.mesh is not kb.mesh or \
                    base.n_shards != kb.n_shards:
                return False
            if not self._bases_aligned():
                return False
            # the fused scan is the exact brute-force stage; a plane
            # whose IVF tier would prune changes results vs two-dispatch
            if kb.resolve_ann(plan.knn.nprobe, plan.knn.rerank) \
                    is not None:
                return False
        return True

    # -- dispatch ------------------------------------------------------------

    def serve_view(self, items: Sequence[dict], *, view,
                   stages: Optional[dict] = None,
                   prune: Optional[bool] = None):
        """One fused dispatch over a co-batched item list (see
        ``microbatch.FusedPlaneMicroBatcher``). Each item carries the
        plan-derived per-request data (``make_item``). Returns
        (vals, hits, totals) aligned with ``items``: ``vals[i]`` the
        fused scores np.f32[k_i], ``hits[i]`` the [(shard, doc)] rows
        in VIEW space, ``totals[i]`` the lexical total (possibly
        ``(value, "gte")``). When any item carries agg stages the
        return grows a fourth element: per-item aggregations dicts
        (None on agg-free slots)."""
        t0 = time.perf_counter()
        has_aggs = any(it.get("aggs") is not None for it in items)
        if has_aggs and (view is None or not self.serves_host()):
            raise FusedFallback("agg stages need a host CSR view")
        if self.serves_host():
            out = self._serve_host(items, view=view, stages=stages,
                                   prune=prune)
        else:
            out = self._serve_device(items, view=view, stages=stages)
        if has_aggs:
            from .agg_planner import serve_agg_stages
            out = out + (serve_agg_stages(self, items, view=view,
                                          stages=stages),)
        if stages is not None:
            stages.setdefault("dispatch_ms",
                              (time.perf_counter() - t0) * 1e3)
        from ..common import telemetry as _tm
        _tm.record_planner_dispatch(max(
            (it.get("n_stages", 1) for it in items), default=1))
        return out

    # -- host path: generation stages + legacy-arithmetic fusion -------------

    def _serve_host(self, items, *, view, stages, prune):
        gen = self.text_gen
        all_bags = all(it.get("bag") is not None for it in items)
        wt = max(max((it["wt"] for it in items), default=1), 1)
        text_res: dict = {}
        knn_res: dict = {}
        t_stages: dict = {}
        k_stages: dict = {}

        def run_text():
            if all_bags:
                bags = [it["bag"] for it in items]
                text_res["out"] = gen.serve_view(
                    bags, k=wt, view=view, with_totals=True,
                    stages=t_stages, prune=prune) \
                    if hasattr(gen, "serve_view") else gen.serve(
                        bags, k=wt, with_totals=True, stages=t_stages,
                        prune=prune)
            else:
                bqs = [{"clauses": it["clauses"], "msm": it["msm"]}
                       for it in items]
                text_res["out"] = self._text_bool_view(
                    bqs, k=wt, view=view, stages=t_stages)

        def run_knn():
            if self.knn_gen is None or not any(
                    it.get("qv") is not None for it in items):
                return
            kbase = self._knn_base()
            dim = max(kbase.dim, 1)
            qvs = np.stack([
                np.asarray(it["qv"], np.float32)
                if it.get("qv") is not None
                else np.zeros(dim, np.float32) for it in items])
            wk = max(max((it["knn_nc"] for it in items), default=1), 1)
            kg = self.knn_gen
            # the SAME pow2-bucketed IVF knobs the legacy dispatch path
            # resolves (microbatch.knn_dispatch_params): co-batched
            # items share one bucket by construction, and raw values
            # here would probe fewer clusters than planner-off serving
            from .microbatch import knn_dispatch_params
            kp = knn_dispatch_params(kbase, items[0].get("nprobe"),
                                     items[0].get("rerank"))
            nprobe, rerank = kp if kp is not None else (None, None)
            if hasattr(kg, "serve_view"):
                knn_res["out"] = kg.serve_view(
                    qvs, k=wk, view=view, stages=k_stages,
                    nprobe=nprobe, rerank=rerank)
            else:
                knn_res["out"] = kg.serve(qvs, k=wk, stages=k_stages,
                                          nprobe=nprobe, rerank=rerank)

        def run_knn_guarded():
            try:
                run_knn()
            except BaseException as e:   # noqa: BLE001 — re-raised on
                knn_res["error"] = e     # the dispatcher thread below

        # the two retrieval stages run concurrently inside the ONE
        # dispatch: the kNN stage is BLAS-bound (releases the GIL), so
        # it overlaps the lexical scatter-adds — the fused path's
        # latency win on the host backend, in place of XLA overlapping
        # the two pipelines on device
        if self.knn_gen is not None and len(items) > 0 and any(
                it.get("qv") is not None for it in items):
            kt = threading.Thread(target=run_knn_guarded,
                                  name="es-dispatcher-knn-stage")
            kt.start()
            run_text()
            kt.join()
            if "error" in knn_res:
                # a failed kNN stage must fail the request like the
                # legacy knn section would — never silently degrade a
                # hybrid request to text-only results
                raise knn_res["error"]
        else:
            run_text()
        tvals, thits, ttotals = text_res["out"]
        vals_out, hits_out, totals_out = [], [], []
        for bi, it in enumerate(items):
            text_rows = [(float(v), si, d)
                         for v, (si, d) in zip(tvals[bi], thits[bi])
                         ][: it["wt"]]
            rows = text_rows
            if knn_res.get("out") is not None and \
                    it.get("qv") is not None:
                kvals, khits = knn_res["out"]
                sim = self._knn_base().similarity
                kr = [(knn_raw_to_score_host(sim, float(v))
                       * it["kboost"], si, d)
                      for v, (si, d) in zip(kvals[bi], khits[bi])]
                # monotone transform preserves plane order; re-sort for
                # boost safety (the legacy knn section's exact step)
                kr.sort(key=lambda c: (-c[0], c[1], c[2]))
                knn_rows = kr[: it["knn_k"]]
                if it["fusion"] == "rrf":
                    rows = rrf_fuse_rows([text_rows, knn_rows],
                                         it["rc"])
                else:
                    rows = sum_fuse_rows([text_rows, knn_rows])
            if it.get("rescore") is not None:
                rows = self._rescore_rows_host(it["rescore"], rows,
                                               view)
            rows = rows[: it["k"]]
            # float64 on purpose: the legacy host fusion/rescore work in
            # python floats, and fused-vs-two-dispatch parity is BITWISE
            vals_out.append(np.asarray([r[0] for r in rows]))
            hits_out.append([(r[1], r[2]) for r in rows])
            totals_out.append(ttotals[bi])
        if stages is not None:
            for src in (t_stages, k_stages):
                for key, ms in src.items():
                    if key.endswith("_ms"):
                        stages[key] = stages.get(key, 0.0) + ms
            stages["compile_cache"] = "host"
            if "docs_scanned" in t_stages:
                stages["docs_scanned"] = t_stages["docs_scanned"]
            # roofline audit: the fused dispatch's model bytes are the
            # sum of its component stages' stamped models (the text
            # side may be pruned — the coarse fused fallback would
            # overcharge it a full eager scan)
            mb = int(t_stages.get("model_bytes") or 0) + \
                int(k_stages.get("model_bytes") or 0)
            if mb:
                stages["model_bytes"] = mb
        return vals_out, hits_out, totals_out

    def _text_bool_view(self, bqs, *, k, view, stages):
        """Bool-tree lexical stage through the text generation: base
        bool dispatch with the delta's df/doc mass folded into idf +
        delta bool scan + host top-k merge (the bool twin of
        ``TextServingGeneration._serve_merged``)."""
        gen = self.text_gen
        base = self._text_base()
        if not hasattr(gen, "_delta_for_view"):
            vals, hits, totals = base.serve_bool(
                bqs, k=k, with_totals=True, stages=stages)
            return vals, hits, totals
        delta, base_pos = gen._delta_for_view(view)
        if delta is None:
            vals, hits, totals = base.serve_bool(
                bqs, k=k, with_totals=True, stages=stages)
            rows = [[(base_pos[si], d) for (si, d) in h] for h in hits]
            return vals, rows, totals
        extra_df: Dict[str, int] = {}
        for bq in bqs:
            for _role, terms in bq["clauses"]:
                for t in set(terms):
                    if t not in extra_df:
                        extra_df[t] = delta.df(t)
        vals, hits, totals = base.serve_bool(
            bqs, k=k, with_totals=True, stages=stages,
            extra_docs=delta.n_docs, extra_df=extra_df)
        from ..ops.bm25 import idf_weight
        from ..parallel.dist_search import (merge_topk_rows,
                                            total_is_lower_bound,
                                            total_value)
        n_total = base.n_docs_total + delta.n_docs
        idf_cache: Dict[str, float] = {}

        def idf_of(t: str) -> float:
            v = idf_cache.get(t)
            if v is None:
                gdf = base.global_df(t) + extra_df.get(t, 0)
                v = float(idf_weight(n_total, np.int64(gdf))) if gdf \
                    else 0.0
                idf_cache[t] = v
            return v

        drows, dtotals = delta.score_bool(bqs, k, idf_of,
                                          with_totals=True)
        vals_out, hits_out, totals_out = [], [], []
        for bi in range(len(bqs)):
            base_rows = [(float(v), base_pos[si], int(d))
                         for v, (si, d) in zip(vals[bi], hits[bi])]
            merged = merge_topk_rows(base_rows, drows[bi], k)
            vals_out.append(np.asarray([r[0] for r in merged],
                                       np.float32))
            hits_out.append([(r[1], r[2]) for r in merged])
            tv = total_value(totals[bi]) + int(dtotals[bi])
            totals_out.append((tv, "gte")
                              if total_is_lower_bound(totals[bi])
                              else tv)
        if self._cache is not None:
            self._cache._record_delta_serve("text", len(bqs))
        return vals_out, hits_out, totals_out

    def _rescore_rows_host(self, rs: dict, rows, view):
        """Fused rescore stage (host): exact secondary scores from the
        base plane's CSR (and the delta segments' CSR for delta docs)
        under the combined base+delta stats, then the QueryRescorer
        window combine/reorder."""
        base = self._text_base()
        gen = self.text_gen
        delta, base_pos = gen._delta_for_view(view) \
            if hasattr(gen, "_delta_for_view") \
            else (None, list(range(base.n_shards)))
        pos2base = {vp: bi for bi, vp in enumerate(base_pos)}
        pos2delta = {}
        if delta is not None:
            for di, vp in enumerate(delta.seg_positions):
                pos2delta[vp] = di
        terms = rs["terms"]
        weights: Dict[str, float] = {}
        for t in terms:
            weights[t] = weights.get(t, 0.0) + 1.0
        from ..ops.bm25 import idf_weight
        extra_docs = delta.n_docs if delta is not None else 0
        idfw_of: Dict[str, float] = {}
        for t, w in weights.items():
            gdf = base.global_df(t) + (delta.df(t) if delta is not None
                                       else 0)
            if gdf:
                idfw_of[t] = float(idf_weight(
                    base.n_docs_total + extra_docs, np.int64(gdf))) * w
        slot_terms = list(idfw_of)

        def secondary(si: int, d: int):
            # accumulate in REVERSED slot order — the device kernel's
            # highest-slot-first f32 summation (bisect_exact_scores)
            if si in pos2base:
                csr = base._host_csr[pos2base[si]]
                sh = base.shards[pos2base[si]]
                tids = sh["term_ids"]
            else:
                csr = delta._csr[pos2delta[si]]
                tids = csr["term_ids"]
            s = np.float32(0.0)
            fnd = False
            for t in reversed(slot_terms):
                tid = tids.get(t)
                if tid is None:
                    continue
                st = int(csr["offsets"][tid])
                en = int(csr["offsets"][tid + 1])
                if en <= st:
                    continue
                run = csr["docs"][st:en]
                p = int(np.searchsorted(run, d))
                if p < en - st and run[p] == d:
                    s = np.float32(s + np.float32(
                        idfw_of[t] * csr["impacts"][st + p]))
                    fnd = True
            return float(s), fnd

        qw, rw, mode = rs["qw"], rs["rw"], rs["mode"]
        window = min(rs["window"], len(rows))
        rescored = []
        for sc, si, d in rows[:window]:
            rsec, fnd = secondary(si, d)
            if fnd:
                if mode == "total":
                    ns = qw * sc + rw * rsec
                elif mode == "multiply":
                    ns = (qw * sc) * (rw * rsec)
                elif mode == "avg":
                    ns = (qw * sc + rw * rsec) / 2.0
                elif mode == "max":
                    ns = max(qw * sc, rw * rsec)
                else:                          # "min"
                    ns = min(qw * sc, rw * rsec)
            else:
                ns = qw * sc
            rescored.append((ns, si, d))
        rescored.sort(key=lambda c: (-c[0], c[1], c[2]))
        tail = [(qw * sc, si, d) for sc, si, d in rows[window:]]
        return rescored + tail

    # -- device path: ONE jitted program over both planes --------------------

    def _serve_device(self, items, *, view, stages):
        from ..parallel.dist_search import fused_search_device
        gen = self.text_gen
        base = self._text_base()
        kbase = self._knn_base()
        tdelta, tbase_pos = gen._delta_for_view(view) \
            if hasattr(gen, "_delta_for_view") \
            else (None, list(range(base.n_shards)))
        if self.knn_gen is not None and \
                hasattr(self.knn_gen, "_delta_for_view"):
            kdelta, _kpos = self.knn_gen._delta_for_view(view)
        else:
            kdelta = None
        has_delta = (tdelta is not None) or (kdelta is not None)
        if has_delta and any(it.get("rescore") is not None
                             for it in items):
            # base-doc secondaries live in-kernel but delta docs would
            # need a host CSR the device backend does not retain
            raise FusedFallback("delta tier + rescore on device")
        if kbase is None:
            return self._serve_device_lexical(items, base, tdelta,
                                              tbase_pos, stages)
        extra_df: Dict[str, int] = {}
        if tdelta is not None:
            for it in items:
                for _role, terms in it["clauses"]:
                    for t in set(terms):
                        if t not in extra_df:
                            extra_df[t] = tdelta.df(t)
        fusion = next(it["fusion"] for it in items
                      if it["fusion"] is not None)
        rescore_mode = next(
            (it["rescore"]["mode"] for it in items
             if it.get("rescore") is not None), None)
        pad_rs = {"terms": [], "qw": 1.0, "rw": 1.0, "window": 0}
        dim = max(kbase.dim, 1)
        fqs = []
        for it in items:
            fqs.append({
                "clauses": it["clauses"], "msm": it["msm"],
                "qv": (it["qv"] if it.get("qv") is not None
                       else np.zeros(dim, np.float32)),
                "kboost": it["kboost"],
                "rc": float(it["rc"]), "wt": it["wt"],
                "wk": it["knn_k"], "k": it["k"],
                "rescore": (it.get("rescore") or pad_rs)
                if rescore_mode is not None else None})
        try:
            rows, totals, text_rows, knn_rows = fused_search_device(
                base, kbase, fqs, fusion=fusion,
                rescore_mode=rescore_mode, stages=stages,
                extra_docs=tdelta.n_docs if tdelta is not None else 0,
                extra_df=extra_df or None)
        except ValueError as e:
            raise FusedFallback(str(e))
        if not has_delta:
            vals_out = [np.asarray([r[0] for r in rows[bi]], np.float32)
                        for bi in range(len(items))]
            hits_out = [[(tbase_pos[r[1]], r[2]) for r in rows[bi]]
                        for bi in range(len(items))]
            return vals_out, hits_out, totals
        # a live delta tier: the one dispatch still produced both raw
        # rankings — merge the delta scans on the host and re-run the
        # (tiny) fusion over the merged lists
        return self._merge_delta_and_fuse(items, text_rows, knn_rows,
                                          totals, tdelta, kdelta,
                                          tbase_pos, extra_df)

    def _serve_device_lexical(self, items, base, tdelta, tbase_pos,
                              stages):
        bqs = [{"clauses": it["clauses"], "msm": it["msm"]}
               for it in items]
        wt = max(max((it["wt"] for it in items), default=1), 1)
        if any(it.get("rescore") is not None for it in items):
            # lexical + rescore fused program (bool step's Q2 stage)
            rs0 = items[0]["rescore"]
            try:
                vals, hits, totals = self._bool_rescore_device(
                    base, bqs, items, wt, rs0["mode"], stages)
            except ValueError as e:
                raise FusedFallback(str(e))
        else:
            try:
                vals, hits, totals = base.serve_bool(
                    bqs, k=wt, with_totals=True, stages=stages)
            except ValueError as e:
                raise FusedFallback(str(e))
        if tdelta is None:
            out_v, out_h, out_t = [], [], []
            for bi, it in enumerate(items):
                out_v.append(np.asarray(vals[bi][: it["k"]],
                                        np.float32))
                out_h.append([(tbase_pos[si], d)
                              for (si, d) in hits[bi][: it["k"]]])
                out_t.append(totals[bi])
            return out_v, out_h, out_t
        raise FusedFallback("delta tier on the device lexical path")

    def _bool_rescore_device(self, base, bqs, items, wt, mode, stages):
        from ..parallel.dist_search import (NEG_INF, _run_step,
                                            build_bool_bm25_step)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.mesh import AXIS_REPLICA, AXIS_SHARD
        from ..utils.shapes import round_up_pow2
        mesh = base.mesh
        B = len(bqs)
        n_repl = mesh.shape[AXIS_REPLICA]
        B_pad = -(-B // n_repl) * n_repl
        bqs = list(bqs) + [{"clauses": [], "msm": 0}] * (B_pad - B)
        pad_rs = {"terms": [], "qw": 1.0, "rw": 1.0, "window": 0}
        rss = [it.get("rescore") or pad_rs for it in items] \
            + [pad_rs] * (B_pad - B)
        Q = max(base.SERVING_Q_MIN,
                round_up_pow2(base.bool_slot_count(bqs)))
        (starts, lengths, idfw, cbits, req, neg, shd, msm, max_len,
         any_dense) = base.bool_inputs(bqs, Q)
        if any_dense:
            raise ValueError("bool batch touches dense-tier terms")
        L = min(base.ladder_L(max_len), base.L_cap)
        np.minimum(lengths, L, out=lengths)
        bags2 = [list(rs["terms"]) for rs in rss]
        Q2 = max(8, round_up_pow2(max(
            max((len(set(b)) for b in bags2), default=1), 1)))
        (st2, ln2, iw2, _dr, _dh, _ml2, dense2) = base._lookup(bags2, Q2)
        if dense2:
            raise ValueError("rescore touches dense-tier terms")
        qw = np.asarray([rs["qw"] for rs in rss], np.float32)
        rw = np.asarray([rs["rw"] for rs in rss], np.float32)
        rwin = np.asarray([rs["window"] for rs in rss], np.int32)
        from ..ops.fused_query import MAX_BOOL_CLAUSES as NC
        step = base.cached_step(
            ("bool", Q, L, wt, True, NC, Q2, mode),
            lambda: build_bool_bm25_step(
                mesh, n_pad=base.n_pad, Q=Q, L=L, k=wt, nc=NC,
                n_shards=base.n_shards, with_count=True, Q2=Q2,
                rescore_mode=mode),
            "text_plane_bool")
        repl = NamedSharding(mesh, P(AXIS_REPLICA, None))
        repl1 = NamedSharding(mesh, P(AXIS_REPLICA))
        repl3 = NamedSharding(mesh, P(AXIS_REPLICA, AXIS_SHARD, None))
        out = _run_step(
            base._serial_dispatch, step, base.docs_dev,
            base.impacts_dev,
            jax.device_put(starts, repl3), jax.device_put(lengths, repl3),
            jax.device_put(idfw, repl), jax.device_put(cbits, repl),
            jax.device_put(req, repl1), jax.device_put(neg, repl1),
            jax.device_put(shd, repl1), jax.device_put(msm, repl1),
            jax.device_put(st2, repl3), jax.device_put(ln2, repl3),
            jax.device_put(iw2, repl), jax.device_put(qw, repl1),
            jax.device_put(rw, repl1), jax.device_put(rwin, repl1))
        if stages is not None:
            jax.block_until_ready(out)
        base.n_dispatches += 1
        from ..common import telemetry as _tm
        _tm.record_mesh_dispatch(mesh.shape[AXIS_SHARD],
                                 mesh.shape[AXIS_REPLICA])
        if stages is not None:
            stages["compile_cache"] = \
                "miss" if _tm.last_call_compiled() else "hit"
        vals = np.asarray(out[0])[:B]
        gdocs = np.asarray(out[1])[:B]
        counts = np.asarray(out[2])[:B]
        pad_id = base.n_shards * base.n_pad
        hits = []
        for bi in range(B):
            row = []
            for v, g in zip(vals[bi], gdocs[bi]):
                if v == NEG_INF or g >= pad_id:
                    break
                row.append((int(g) // base.n_pad,
                            int(g) % base.n_pad))
            hits.append(row)
        return vals, hits, [int(c) for c in counts]

    def _merge_delta_and_fuse(self, items, text_rows, knn_rows, totals,
                              tdelta, kdelta, base_pos, extra_df):
        from ..ops.bm25 import idf_weight
        from ..parallel.dist_search import merge_topk_rows
        base = self._text_base()
        kbase = self._knn_base()
        vals_out, hits_out, totals_out = [], [], []
        idf_cache: Dict[str, float] = {}
        n_total = base.n_docs_total + (tdelta.n_docs
                                       if tdelta is not None else 0)

        def idf_of(t: str) -> float:
            v = idf_cache.get(t)
            if v is None:
                gdf = base.global_df(t) + extra_df.get(t, 0)
                v = float(idf_weight(n_total, np.int64(gdf))) if gdf \
                    else 0.0
                idf_cache[t] = v
            return v

        bqs = [{"clauses": it["clauses"], "msm": it["msm"]}
               for it in items]
        drows, dtotals = tdelta.score_bool(
            bqs, max(it["wt"] for it in items), idf_of,
            with_totals=True) if tdelta is not None \
            else ([[] for _ in items], [0] * len(items))
        if kdelta is not None:
            dim = max(kbase.dim, 1)
            qvs = np.stack([np.asarray(it["qv"], np.float32)
                            if it.get("qv") is not None
                            else np.zeros(dim, np.float32)
                            for it in items])
            kd_rows = kdelta.score(qvs, max(it["knn_nc"]
                                            for it in items))
        else:
            kd_rows = [[] for _ in items]
        sim = kbase.similarity
        for bi, it in enumerate(items):
            t_base = [(v, base_pos[si], d)
                      for (v, si, d) in text_rows[bi]]
            t_merged = merge_topk_rows(t_base, drows[bi],
                                       it["wt"])
            k_base = [(v, base_pos[si], d)
                      for (v, si, d) in knn_rows[bi]]
            k_merged = merge_topk_rows(k_base, kd_rows[bi],
                                       it["knn_nc"])
            kr = [(knn_raw_to_score_host(sim, float(v))
                   * it["kboost"], si, d) for v, si, d in k_merged]
            kr.sort(key=lambda c: (-c[0], c[1], c[2]))
            knn_ranked = kr[: it["knn_k"]]
            if it["fusion"] == "rrf":
                rows = rrf_fuse_rows([t_merged, knn_ranked], it["rc"])
            else:
                rows = sum_fuse_rows([t_merged, knn_ranked])
            rows = rows[: it["k"]]
            vals_out.append(np.asarray([r[0] for r in rows]))
            hits_out.append([(r[1], r[2]) for r in rows])
            tv = totals[bi] + int(dtotals[bi])
            totals_out.append(tv)
        if self._cache is not None:
            self._cache._record_delta_serve("text", len(items))
        return vals_out, hits_out, totals_out


def make_item(plan: FusedPlan, *, prune_param=None) -> dict:
    """Plan → the per-request dispatch item the runner consumes (plain
    data, hashable key for in-flight dedup)."""
    rescore = None
    if plan.rescore is not None:
        rescore = {"terms": list(plan.rescore.terms),
                   "qw": plan.rescore.qw, "rw": plan.rescore.rw,
                   "mode": plan.rescore.mode,
                   "window": plan.rescore.window}
    item = {
        "bag": list(plan.bag) if plan.bag is not None else None,
        "clauses": [(r, list(ts)) for r, ts in plan.clauses],
        "msm": plan.msm,
        "qv": plan.knn.query_vector if plan.knn is not None else None,
        "kboost": plan.knn.boost if plan.knn is not None else 1.0,
        "knn_k": plan.knn.k if plan.knn is not None else 0,
        "knn_nc": plan.knn.num_candidates if plan.knn is not None
        else 0,
        "nprobe": plan.knn.nprobe if plan.knn is not None else None,
        "rerank": plan.knn.rerank if plan.knn is not None else None,
        "fusion": plan.fusion,
        "rc": plan.rank_constant,
        "wt": plan.window_text,
        "k": plan.k,
        "rescore": rescore,
        "aggs": plan.aggs,
        "n_stages": plan.n_stages(),
    }
    item["key"] = (
        tuple((r, tuple(ts)) for r, ts in plan.clauses), plan.msm,
        plan.knn.query_vector.tobytes() if plan.knn is not None
        else None,
        item["knn_k"], item["knn_nc"], item["kboost"], item["nprobe"],
        item["rerank"], plan.fusion, plan.rank_constant,
        plan.window_text, plan.k,
        (tuple(rescore["terms"]), rescore["qw"], rescore["rw"],
         rescore["mode"], rescore["window"]) if rescore else None,
        plan.aggs.spec_key if plan.aggs is not None else None,
        prune_param)
    return item
