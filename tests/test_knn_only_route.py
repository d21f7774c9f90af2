"""The per-segment query phase runs only when something reads it.

A body with no ``query`` whose hits come from kNN rankings (its own ``knn``
or the coordinator's ``knn_override``), with no ``aggs`` and no field sort,
takes ``shard[plan]``'s route ``knn``: no ``match_all`` scored over the
corpus, no top-k kernel, no ``shard[query_phase]`` span, no per-segment
scan charged to the task. Every other body takes the path it took before.
Each case is held to a plain float64 numpy reference of the same
semantics, on the per-segment kNN path and on the kNN plane route.
"""
from __future__ import annotations

import numpy as np
import pytest

from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.index.mapping import MapperService
from elasticsearch_tpu.index.segment import SegmentBuilder
from elasticsearch_tpu.node.task_manager import (TaskResources,
                                                 bind_resources,
                                                 unbind_resources)
from elasticsearch_tpu.search import query_dsl
from elasticsearch_tpu.search import shard_search as ss
from elasticsearch_tpu.search.dist_query import DistributedSearcher
from elasticsearch_tpu.search.plane_route import ServingPlaneCache

DIM = 8
SEG_SIZES = (7, 9, 11)
N_DOCS = sum(SEG_SIZES)
RANGE = {"range": {"n": {"gte": 10}}}


class Corpus:
    """Three segments of small docs (a few without a vector) and the
    numpy reference over the same rows: every ranking is a list of
    ``(score, segment, doc)`` in (score desc, segment asc, doc asc)."""

    def __init__(self):
        rng = np.random.RandomState(7)
        self.mapper = MapperService({"properties": {
            "body": {"type": "text"}, "tag": {"type": "keyword"},
            "n": {"type": "integer"},
            "v": {"type": "dense_vector", "dims": DIM,
                  "similarity": "cosine"}}})
        self.segments, self.rows = [], []
        uid = 0
        for si, n in enumerate(SEG_SIZES):
            b = SegmentBuilder(f"s{si}")
            for d in range(n):
                doc = {"body": "quick fox" if uid % 2 else "lazy dog",
                       "tag": f"t{uid % 4}", "n": (uid * 7) % 29}
                if uid % 9 != 4:
                    doc["v"] = [float(x) for x in rng.randn(DIM)]
                b.add(self.mapper.parse_document(str(uid), doc),
                      seq_no=uid)
                self.rows.append((si, d, str(uid), doc))
                uid += 1
            self.segments.append(b.build())
        self.q1 = [float(x) for x in rng.randn(DIM)]
        self.q2 = [float(x) for x in rng.randn(DIM)]
        self.by_pos = {(si, d): (uid_, doc)
                       for si, d, uid_, doc in self.rows}

    @staticmethod
    def ranked(rows):
        return sorted(rows, key=lambda r: (-r[0], r[1], r[2]))

    def knn(self, qv, k):
        q = np.asarray(qv, np.float64)
        out = []
        for si, d, _, doc in self.rows:
            if "v" in doc:
                v = np.asarray(doc["v"], np.float64)
                cos = q @ v / (np.linalg.norm(q) * np.linalg.norm(v))
                out.append(((1.0 + cos) / 2.0, si, d))
        return self.ranked(out)[:k]

    def matches(self):
        """The RANGE query's rows: a constant score of 1."""
        return [(1.0, si, d) for si, d, _, doc in self.rows
                if doc["n"] >= 10]

    def sum_fuse(self, rankings):
        acc = {}
        for ranking in rankings:
            for sc, si, d in ranking:
                acc[(si, d)] = acc.get((si, d), 0.0) + sc
        return self.ranked([(sc, si, d) for (si, d), sc in acc.items()])

    def rrf_fuse(self, rankings, rc=60):
        acc = {}
        for ranking in rankings:
            for rank, (_, si, d) in enumerate(ranking, 1):
                acc[(si, d)] = acc.get((si, d), 0.0) + 1.0 / (rc + rank)
        return self.ranked([(sc, si, d) for (si, d), sc in acc.items()])

    def uid(self, row):
        return self.by_pos[(row[1], row[2])][0]

    def field(self, row, name):
        return self.by_pos[(row[1], row[2])][1][name]


@pytest.fixture(scope="module")
def corpus():
    return Corpus()


@pytest.fixture(scope="module")
def plane_cache():
    return ServingPlaneCache()


def _knn(qv, k):
    return {"field": "v", "query_vector": qv, "k": k, "num_candidates": k}


def _expect(rows, page, total=None, relation="eq", scored=True):
    return {"page": page, "total": len(rows) if total is None else total,
            "relation": relation,
            "max_score": rows[0][0] if rows and scored else None}


#: name -> (skips the phase, goes through DistributedSearcher,
#: corpus -> (body, search kwargs, expected))
CASES = {}


def case(skips, dist=False):
    def register(build):
        CASES[build.__name__] = (skips, dist, build)
        return build
    return register


@case(skips=True)
def knn_only(c):
    rows = c.knn(c.q1, 5)
    return {"knn": _knn(c.q1, 5), "size": 5}, {}, _expect(rows, rows)


@case(skips=True)
def size_0(c):
    rows = c.knn(c.q1, 5)
    return {"knn": _knn(c.q1, 5), "size": 0}, {}, _expect(rows, [])


@case(skips=True)
def from_2(c):
    rows = c.knn(c.q1, 6)
    return ({"knn": _knn(c.q1, 6), "from": 2, "size": 3}, {},
            _expect(rows, rows[2:5]))


@case(skips=True)
def no_track_total(c):
    rows = c.knn(c.q1, 6)
    return ({"knn": _knn(c.q1, 6), "size": 4, "track_total_hits": False},
            {}, _expect(rows, rows[:4], relation="gte"))


@case(skips=True)
def two_clauses(c):
    rows = c.sum_fuse([c.knn(c.q1, 5), c.knn(c.q2, 5)])
    return ({"knn": [_knn(c.q1, 5), _knn(c.q2, 5)], "size": 8}, {},
            _expect(rows, rows[:8]))


@case(skips=True)
def rrf(c):
    rows = c.rrf_fuse([c.knn(c.q1, 5), c.knn(c.q2, 5)])
    return ({"knn": [_knn(c.q1, 5), _knn(c.q2, 5)],
             "rank": {"rrf": {}}, "size": 6}, {}, _expect(rows, rows[:6]))


@case(skips=True)
def collapse(c):
    rows = c.knn(c.q1, 8)
    seen, page = set(), []
    for r in rows:
        if c.field(r, "tag") not in seen:
            seen.add(c.field(r, "tag"))
            page.append(r)
    return ({"knn": _knn(c.q1, 8), "collapse": {"field": "tag"},
             "size": 8}, {}, _expect(rows, page))


@case(skips=True)
def profile(c):
    rows = c.knn(c.q1, 5)
    return ({"knn": _knn(c.q1, 5), "size": 5, "profile": True}, {},
            _expect(rows, rows))


@case(skips=True)
def override(c):
    # as DistributedSearcher hands a shard its slice of the global top-k
    rows = c.knn(c.q1, 5)
    return ({"size": 5}, {"knn_override": [rows],
                          "collect_agg_inputs": True}, _expect(rows, rows))


@case(skips=True, dist=True)
def dist_knn_only(c):
    rows = c.knn(c.q1, 5)
    return {"knn": _knn(c.q1, 5), "size": 5}, {}, _expect(rows, rows)


@case(skips=False)
def hybrid_sum(c):
    rows = c.sum_fuse([c.matches(), c.knn(c.q1, 5)])
    return ({"knn": _knn(c.q1, 5), "query": RANGE, "size": 30}, {},
            _expect(rows, rows, total=len(c.matches())))


@case(skips=False)
def hybrid_rrf(c):
    rows = c.rrf_fuse([c.matches(), c.knn(c.q1, 5)])
    return ({"knn": _knn(c.q1, 5), "query": RANGE, "rank": {"rrf": {}},
             "size": 30}, {}, _expect(rows, rows, total=len(c.matches())))


@case(skips=False)
def knn_aggs(c):
    rows = c.knn(c.q1, 5)
    counts = {}
    for _, _, _, doc in c.rows:         # over every live doc, as today
        counts[doc["tag"]] = counts.get(doc["tag"], 0) + 1
    buckets = [{"key": t, "doc_count": n} for t, n in
               sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))]
    return ({"knn": _knn(c.q1, 5), "size": 5,
             "aggs": {"t": {"terms": {"field": "tag"}}}}, {},
            dict(_expect(rows, rows), buckets=buckets))


@case(skips=False)
def knn_sort(c):
    rows = c.knn(c.q1, 5)
    page = sorted(rows, key=lambda r: (c.field(r, "n"), r[1], r[2]))
    return ({"knn": _knn(c.q1, 5), "sort": [{"n": "asc"}], "size": 5}, {},
            dict(_expect(rows, page, scored=False),
                 sort_keys=[c.field(r, "n") for r in page]))


@case(skips=False)
def query_only(c):
    rows = c.matches()
    return {"query": RANGE, "size": 30}, {}, _expect(rows, rows)


@case(skips=False)
def empty_body(c):
    rows = [(1.0, si, d) for si, d, _, _ in c.rows]
    return {}, {}, _expect(rows, rows[:10])




def _subclasses(cls):
    for sub in cls.__subclasses__():
        yield sub
        yield from _subclasses(sub)


@pytest.fixture()
def outside_knn(monkeypatch):
    """Counts of ``get_topk_kernel`` and ``Query.execute`` calls made
    outside ``_knn_candidates``, and the ``docs_scanned`` of each
    dispatch charged to the task."""
    seen = {"topk": 0, "execute": 0, "dispatch_docs": []}
    inside = []
    real_knn = ss.ShardSearcher._knn_candidates

    def knn(self, *a, **kw):
        inside.append(1)
        try:
            return real_knn(self, *a, **kw)
        finally:
            inside.pop()

    real_topk = ss.get_topk_kernel

    def topk(*a, **kw):
        seen["topk"] += not inside
        return real_topk(*a, **kw)

    real_attr = ss._attribute_dispatch

    def attribute(stages, info):
        seen["dispatch_docs"].append(int((info or {}).get("docs_scanned",
                                                          0)))
        return real_attr(stages, info)

    monkeypatch.setattr(ss.ShardSearcher, "_knn_candidates", knn)
    monkeypatch.setattr(ss, "get_topk_kernel", topk)
    monkeypatch.setattr(ss, "_attribute_dispatch", attribute)
    for cls in _subclasses(query_dsl.Query):
        real = cls.__dict__.get("execute")
        if real is None:
            continue

        def execute(self, ctx, seg, _real=real):
            seen["execute"] += not inside
            return _real(self, ctx, seg)

        monkeypatch.setattr(cls, "execute", execute)
    return seen


@pytest.mark.parametrize("knn_path", ("segments", "plane"))
@pytest.mark.parametrize("case", sorted(CASES))
def test_query_phase_runs_only_when_read(case, knn_path, corpus,
                                         plane_cache, outside_knn):
    skips, dist, build = CASES[case]
    body, kwargs, want = build(corpus)
    provider = None
    if knn_path == "plane":
        def provider(segs, field):
            return plane_cache.knn_plane_for(segs, corpus.mapper, field)
    if dist:
        searcher = DistributedSearcher(
            [corpus.segments[:1], corpus.segments[1:]], corpus.mapper,
            knn_plane_provider=provider)
    else:
        searcher = ss.ShardSearcher(corpus.segments, corpus.mapper,
                                    knn_plane_provider=provider)
    n_shards = len(getattr(searcher, "shards", [searcher]))

    res = TaskResources()
    token = bind_resources(res)
    try:
        with tracing.span("test[search]", root=True) as root:
            r = searcher.search(dict(body), **kwargs)
    finally:
        unbind_resources(token)

    # the answer, against numpy
    page = want["page"]
    assert [h.doc_id for h in r.hits] == [corpus.uid(p) for p in page]
    if want["max_score"] is None:
        assert r.max_score is None
        assert [h.score for h in r.hits] == [None] * len(page)
        assert [h.sort_values[0] for h in r.hits] == want["sort_keys"]
    else:
        assert r.max_score == pytest.approx(want["max_score"], abs=2e-6)
        assert [h.score for h in r.hits] == pytest.approx(
            [p[0] for p in page], abs=2e-6)
    assert (r.total, r.total_relation) == (want["total"], want["relation"])
    if "buckets" in want:
        assert r.aggregations["t"]["buckets"] == want["buckets"]
    else:
        assert r.aggregations is None and r.agg_inputs is None
    if body.get("profile"):
        prof = r.profile["shards"][0]
        assert ("serving_knn" in prof) == (knn_path == "plane")

    # the path, by the span tree
    spans = tracing.DEFAULT_STORE.get(root.trace_id)["spans"]
    names = [s["name"] for s in spans]
    routes = [s["attrs"]["route"] for s in spans
              if s["name"] == "shard[plan]"]
    phase = [s["attrs"] for s in spans if s["name"] == "shard[query_phase]"]
    n_clauses = len(body["knn"]) if isinstance(body.get("knn"), list) \
        else int("knn" in body)
    assert names.count("plane_dispatch") == \
        (n_clauses * n_shards if knn_path == "plane" else 0)
    if skips:
        assert routes == ["knn"] * n_shards
        assert phase == []
        assert (outside_knn["topk"], outside_knn["execute"]) == (0, 0)
    else:
        assert routes == ["segments"]
        assert phase == [{"segments": len(SEG_SIZES),
                          "has_query": "query" in body}]
        assert outside_knn["execute"] >= len(SEG_SIZES)
        assert outside_knn["topk"] >= len(SEG_SIZES)

    # the task's ledger: the kNN dispatches' own attribution, and the
    # per-segment scan only where the phase ran
    assert len(outside_knn["dispatch_docs"]) == \
        names.count("plane_dispatch") == res.dispatches
    if knn_path == "plane":
        assert all(n > 0 for n in outside_knn["dispatch_docs"])
    assert res.docs_scanned == sum(outside_knn["dispatch_docs"]) + \
        (0 if skips else N_DOCS)
