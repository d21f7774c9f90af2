"""Blocked kNN scoring: cached corpus invariants + streaming running top-k.

- Parity: the blocked step (``lax.scan`` over corpus blocks with a carried
  top-k) must return IDENTICAL (value, index) results to the one-shot
  full-matrix reference (``block=None``) for all three similarities,
  including exists-masked padding rows and k > live-doc-count.
- Shard invariance: the global ICI top-k reduce is unaffected by the
  per-shard blocking — 1/2/4-shard partitions of one corpus agree.
- Ratchet: the step's jaxpr contains no corpus-side div/rsqrt/sqrt
  (normalization is a pack-time invariant, never in the per-query trace).
- Serving: the ``DistributedKnnPlane`` route through ``ShardSearcher``
  matches the per-segment path, and concurrent requests coalesce through
  the query_vector micro-batcher.
"""

import threading

import numpy as np
import pytest
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from elasticsearch_tpu.parallel import (DistributedKnnPlane, build_knn_step,
                                        make_search_mesh, prepare_knn_corpus)
from elasticsearch_tpu.parallel.dist_search import KNN_BLOCK
from elasticsearch_tpu.parallel.mesh import AXIS_REPLICA, AXIS_SHARD

SIMS = ("dot_product", "cosine", "l2_norm")


def _run_step(mesh, vecs, vnorm2, exists, qs, *, k, n_shards, similarity,
              block):
    step = build_knn_step(mesh, n_pad=vecs.shape[1], dim=vecs.shape[2], k=k,
                          n_shards=n_shards, similarity=similarity,
                          block=block)
    vals, gdocs = step(
        jax.device_put(vecs, NamedSharding(mesh, P(AXIS_SHARD, None, None))),
        jax.device_put(vnorm2, NamedSharding(mesh, P(AXIS_SHARD, None))),
        jax.device_put(exists, NamedSharding(mesh, P(AXIS_SHARD, None))),
        jax.device_put(qs, NamedSharding(mesh, P(AXIS_REPLICA, None))))
    return np.asarray(vals), np.asarray(gdocs)


def _packed_corpus(rng, n_shards, n_pad, dim, similarity):
    vecs = rng.randn(n_shards, n_pad, dim).astype(np.float32)
    # exact ties across blocks and across shards: duplicated rows must
    # resolve by ascending global index in BOTH paths
    vecs[0, 90] = vecs[0, 5]
    vecs[1 % n_shards, 40] = vecs[0, 3]
    exists = np.ones((n_shards, n_pad), bool)
    exists[0, 100:] = False          # masked padding tail
    exists[1 % n_shards, ::7] = False  # scattered holes
    pv, vn = prepare_knn_corpus(vecs, similarity)
    pv = pv.copy()
    pv[~exists] = 0.0
    vn = vn.copy()
    vn[~exists] = 0.0
    return pv, vn, exists


@pytest.mark.parametrize("similarity", SIMS)
def test_blocked_matches_oneshot(similarity):
    rng = np.random.RandomState(11)
    n_shards, n_pad, dim, k = 2, 128, 16, 8
    pv, vn, exists = _packed_corpus(rng, n_shards, n_pad, dim, similarity)
    qs = rng.randn(4, dim).astype(np.float32)
    # one query exactly equal to a duplicated corpus row: guaranteed tie
    qs[0] = pv[0, 5] if similarity != "cosine" else pv[0, 5]
    mesh = make_search_mesh(n_shards=n_shards, n_replicas=1)
    bv, bd = _run_step(mesh, pv, vn, exists, qs, k=k, n_shards=n_shards,
                       similarity=similarity, block=32)
    ov, od = _run_step(mesh, pv, vn, exists, qs, k=k, n_shards=n_shards,
                       similarity=similarity, block=None)
    np.testing.assert_array_equal(bv, ov)
    np.testing.assert_array_equal(bd, od)
    # and both agree with a plain numpy oracle on values
    flat = pv.reshape(-1, dim)
    if similarity == "l2_norm":
        ref = 2.0 * (qs @ flat.T) - np.sum(flat * flat, 1)[None, :] \
            - np.sum(qs * qs, 1)[:, None]
    elif similarity == "cosine":
        qn = qs / np.maximum(np.linalg.norm(qs, axis=1, keepdims=True),
                             1e-12)
        ref = qn @ flat.T
    else:
        ref = qs @ flat.T
    ref[:, ~exists.reshape(-1)] = -np.inf
    for bi in range(qs.shape[0]):
        order = np.argsort(-ref[bi], kind="stable")[:k]
        np.testing.assert_allclose(bv[bi], ref[bi][order],
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("similarity", SIMS)
def test_blocked_k_exceeds_live_docs(similarity):
    """k larger than the live doc count: -inf padding entries must carry
    the same indices in the blocked and one-shot paths."""
    rng = np.random.RandomState(5)
    n_shards, n_pad, dim, k = 2, 128, 8, 8
    vecs = rng.randn(n_shards, n_pad, dim).astype(np.float32)
    exists = np.zeros((n_shards, n_pad), bool)
    exists[0, [2, 50, 97]] = True      # 3 live docs in shard 0
    exists[1, 10] = True               # 1 live doc in shard 1
    pv, vn = prepare_knn_corpus(vecs, similarity)
    qs = rng.randn(2, dim).astype(np.float32)
    mesh = make_search_mesh(n_shards=n_shards, n_replicas=1)
    bv, bd = _run_step(mesh, pv, vn, exists, qs, k=k, n_shards=n_shards,
                       similarity=similarity, block=32)
    ov, od = _run_step(mesh, pv, vn, exists, qs, k=k, n_shards=n_shards,
                       similarity=similarity, block=None)
    np.testing.assert_array_equal(bv, ov)
    np.testing.assert_array_equal(bd, od)
    assert (bv[:, :4] > -np.inf).all() and (bv[:, 4:] == -np.inf).all()


@pytest.mark.parametrize("similarity", ("dot_product", "cosine"))
def test_multi_shard_reduce_invariant(similarity):
    """The same corpus partitioned over 1, 2, and 4 shards must produce
    the same global (doc, value) top-k — the ICI reduce is independent of
    the per-shard blocking."""
    rng = np.random.RandomState(23)
    n, dim, k = 256, 8, 10
    flat = rng.randn(n, dim).astype(np.float32)
    flat[77] = flat[12]                       # cross-partition tie
    qs = rng.randn(3, dim).astype(np.float32)
    results = {}
    for s in (1, 2, 4):
        per = n // s
        vecs = flat.reshape(s, per, dim)
        exists = np.ones((s, per), bool)
        pv, vn = prepare_knn_corpus(vecs, similarity)
        mesh = make_search_mesh(n_shards=s, n_replicas=1)
        vals, gdocs = _run_step(mesh, pv, vn, exists, qs, k=k, n_shards=s,
                                similarity=similarity, block=64)
        # globalize: plane doc id = shard * per + local = flat row id
        results[s] = (vals, gdocs)
    v1, d1 = results[1]
    for s in (2, 4):
        vs, ds = results[s]
        np.testing.assert_allclose(vs, v1, rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(ds, d1)


def _collect_eqns(obj, out):
    """Recursively collect every eqn in a (Closed)Jaxpr, including the
    bodies of pjit / scan / shard_map / cond sub-jaxprs."""
    jaxpr = getattr(obj, "jaxpr", obj)
    for eqn in getattr(jaxpr, "eqns", ()):
        out.append(eqn)
        for p in eqn.params.values():
            _collect_param(p, out)


def _collect_param(p, out):
    if isinstance(p, (list, tuple)):
        for x in p:
            _collect_param(x, out)
    elif hasattr(p, "eqns") or hasattr(p, "jaxpr"):
        _collect_eqns(p, out)


@pytest.mark.parametrize("similarity", SIMS)
def test_knn_step_trace_has_no_corpus_normalization(similarity):
    """Ratchet for the invariant-caching fix: the per-query trace must
    contain NO div/rsqrt/sqrt over corpus-sized operands (cosine rows are
    unit-normalized and ‖v‖² rows cached at pack time; only the [B, dim]
    query side may normalize in-trace)."""
    n_shards, n_pad, dim, k, B = 1, 128, 16, 8, 4
    mesh = make_search_mesh(n_shards=1, n_replicas=1)
    step = build_knn_step(mesh, n_pad=n_pad, dim=dim, k=k,
                          n_shards=n_shards, similarity=similarity,
                          block=32)
    vecs = np.zeros((n_shards, n_pad, dim), np.float32)
    vn = np.zeros((n_shards, n_pad), np.float32)
    exists = np.ones((n_shards, n_pad), bool)
    qs = np.zeros((B, dim), np.float32)
    closed = jax.make_jaxpr(step)(vecs, vn, exists, qs)
    eqns = []
    _collect_eqns(closed, eqns)
    assert eqns, "jaxpr walker found no equations"
    offenders = []
    for eqn in eqns:
        if eqn.primitive.name not in ("div", "rsqrt", "sqrt"):
            continue
        for var in eqn.invars:
            aval = getattr(var, "aval", None)
            size = int(np.prod(getattr(aval, "shape", ()) or (1,)))
            if size >= n_pad:
                offenders.append((eqn.primitive.name, aval.shape))
    assert not offenders, (
        f"corpus-side normalization leaked into the knn trace: {offenders}")


def test_knn_step_scan_sorts_at_most_a_quarter_block_per_query():
    """Ratchet for the top-k selection (``ops/topk.py``): at the served
    shape (GloVe: n_pad 2^21, dim 100, k 100, B 32; shapes only, nothing
    is allocated) the ``top_k`` equations inside the scan body together
    take at most block / 4 elements per query. ``top_k`` is a full sort
    of its operand on the TPU; a step that sorts every score of a block
    (4 x 16,384 + 400 + 200 per query before the selection) fails."""
    n_pad, dim, k, B = 1 << 21, 100, 100, 32
    mesh = make_search_mesh(n_shards=1, n_replicas=1)
    step = build_knn_step(mesh, n_pad=n_pad, dim=dim, k=k, n_shards=1,
                          similarity="cosine")
    sds = jax.ShapeDtypeStruct
    closed = jax.make_jaxpr(step)(
        sds((1, n_pad, dim), np.float32), sds((1, n_pad), np.float32),
        sds((1, n_pad), bool), sds((B, dim), np.float32))
    eqns = []
    _collect_eqns(closed, eqns)
    scans = [e for e in eqns if e.primitive.name == "scan"]
    assert len(scans) == 1, "the step is one scan over the corpus blocks"
    body = []
    _collect_eqns(scans[0].params["jaxpr"], body)
    sorted_per_query = [int(np.prod(e.invars[0].aval.shape)) // B
                        for e in body if e.primitive.name == "top_k"]
    assert sorted_per_query, "no top_k found in the scan body"
    assert sum(sorted_per_query) <= KNN_BLOCK // 4, sorted_per_query


# ---------------------------------------------------------------------------
# serving plane + micro-batching
# ---------------------------------------------------------------------------


def _build_vector_segments(rng, similarity, n_segs=3, dim=8):
    from elasticsearch_tpu.index.mapping import MapperService
    from elasticsearch_tpu.index.segment import SegmentBuilder
    mapper = MapperService({"properties": {
        "body": {"type": "text"},
        "vec": {"type": "dense_vector", "dims": dim,
                "similarity": similarity}}})
    segs = []
    uid = 0
    for si in range(n_segs):
        b = SegmentBuilder(f"ks{si}")
        for _ in range(5 + 3 * si):
            doc = {"body": f"doc {uid}"}
            if uid % 7 != 3:            # some docs lack the vector
                doc["vec"] = [float(x) for x in rng.randn(dim)]
            b.add(mapper.parse_document(str(uid), doc), seq_no=uid)
            uid += 1
        segs.append(b.build())
    return mapper, segs


@pytest.mark.parametrize("similarity", ("cosine", "l2_norm", "dot_product"))
def test_knn_plane_route_matches_per_segment(similarity):
    """ShardSearcher with a knn_plane_provider must return the same hits
    (ids, order, scores) as the per-segment einsum path."""
    from elasticsearch_tpu.search.plane_route import ServingPlaneCache
    from elasticsearch_tpu.search.shard_search import ShardSearcher
    rng = np.random.RandomState(31)
    mapper, segs = _build_vector_segments(rng, similarity)
    cache = ServingPlaneCache()
    routed = ShardSearcher(
        segs, mapper,
        knn_plane_provider=lambda s, f: cache.knn_plane_for(s, mapper, f))
    plain = ShardSearcher(segs, mapper)
    body = {"knn": {"field": "vec", "query_vector":
                    [float(x) for x in rng.randn(8)],
                    "k": 6, "num_candidates": 10}, "size": 6}
    r1 = routed.search(dict(body))
    r2 = plain.search(dict(body))
    assert cache._knn_planes, "plane route did not engage"
    plane = next(iter(cache._knn_planes.values()))
    assert plane.n_dispatches >= 1
    assert [h.doc_id for h in r1.hits] == [h.doc_id for h in r2.hits]
    for h1, h2 in zip(r1.hits, r2.hits):
        assert h1.score == pytest.approx(h2.score, rel=1e-5, abs=1e-5)
    # a filtered clause must fall back to the per-segment path (and agree)
    fbody = {"knn": {"field": "vec", "query_vector":
                     [float(x) for x in rng.randn(8)],
                     "k": 3, "num_candidates": 5,
                     "filter": {"match": {"body": "doc"}}}, "size": 3}
    f1 = routed.search(dict(fbody))
    f2 = plain.search(dict(fbody))
    assert [h.doc_id for h in f1.hits] == [h.doc_id for h in f2.hits]


def test_knn_plane_route_ineligible_on_deletes():
    """Segments with deletes keep the per-doc liveness mask — the plane
    route must bow out and results must still exclude the deleted doc."""
    from elasticsearch_tpu.search.plane_route import ServingPlaneCache
    from elasticsearch_tpu.search.shard_search import ShardSearcher
    rng = np.random.RandomState(13)
    mapper, segs = _build_vector_segments(rng, "cosine")
    deleted_uid = segs[0].doc_uids[0]
    segs[0].delete_doc(0)
    cache = ServingPlaneCache()
    routed = ShardSearcher(
        segs, mapper,
        knn_plane_provider=lambda s, f: cache.knn_plane_for(s, mapper, f))
    r = routed.search({"knn": {"field": "vec",
                               "query_vector": [1.0] + [0.0] * 7,
                               "k": 20, "num_candidates": 30}, "size": 20})
    assert not cache._knn_planes
    assert deleted_uid not in [h.doc_id for h in r.hits]


def test_knn_microbatch_coalesces_concurrent_queries():
    """Concurrent kNN requests share dispatches through the query_vector
    micro-batcher, with per-query results intact."""
    from elasticsearch_tpu.search.microbatch import batched_knn_search
    rng = np.random.RandomState(3)
    n, dim = 64, 8
    flat = rng.randn(n, dim).astype(np.float32)
    mesh = make_search_mesh(n_shards=1, n_replicas=1)
    plane = DistributedKnnPlane(mesh, [dict(vectors=flat)],
                                similarity="dot_product")
    # warm the (B, k) compile shapes so the timed window coalesces
    batched_knn_search(plane, flat[0], k=4)
    expect = {}
    for i in range(12):
        sc = flat[i] @ flat.T
        expect[i] = int(np.argmax(sc))
    results = {}
    errs = []

    def go(i):
        try:
            vals, hits = batched_knn_search(plane, flat[i], k=4)
            results[i] = hits[0]
        except Exception as e:          # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=go, args=(i,)) for i in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    for i in range(12):
        assert results[i] == (0, expect[i]), (i, results[i])
    b = plane._microbatcher
    assert b.n_queries == 13
    assert b.n_dispatches <= 13


@pytest.mark.parametrize("similarity", SIMS)
def test_search_host_matches_device_step(similarity):
    """The CPU-native blocked scorer (search_host: BLAS + threshold-pruned
    running top-k) must agree with the jitted device step — same hits,
    same tie order, scores within matmul ulp — including masked rows and
    k > live-doc-count."""
    rng = np.random.RandomState(17)
    v0 = rng.randn(40, 8).astype(np.float32)
    v1 = rng.randn(70, 8).astype(np.float32)
    v1[12] = v0[7]                      # cross-shard exact tie
    e0 = np.ones(40, bool)
    e0[5:9] = False
    e1 = np.ones(70, bool)
    e1[::11] = False
    mesh = make_search_mesh(n_shards=2, n_replicas=1)
    plane = DistributedKnnPlane(
        mesh, [dict(vectors=v0, exists=e0), dict(vectors=v1, exists=e1)],
        similarity=similarity, block=32)
    assert plane._host_pack is not None
    qs = rng.randn(5, 8).astype(np.float32)
    qs[1] = v0[7]                       # lands exactly on the tie pair
    for k in (4, 200):                  # 200 > live count: -inf padding
        dv, dh = plane.search(qs, k=k)
        hv, hh = plane.search_host(qs, k=k)
        assert dh == hh
        np.testing.assert_allclose(hv, dv, rtol=1e-5, atol=1e-5)


def test_knn_plane_search_shapes_and_tie_order():
    """Plane-level API: raw scores descend, ties resolve (shard, doc)
    ascending, absent rows never surface."""
    rng = np.random.RandomState(9)
    v0 = rng.randn(6, 4).astype(np.float32)
    v1 = rng.randn(10, 4).astype(np.float32)
    v1[4] = v0[2]                        # cross-shard duplicate
    exists1 = np.ones(10, bool)
    exists1[7] = False
    mesh = make_search_mesh(n_shards=2, n_replicas=1)
    plane = DistributedKnnPlane(
        mesh, [dict(vectors=v0), dict(vectors=v1, exists=exists1)],
        similarity="dot_product")
    q = v0[2]
    vals, hits = plane.search(q[None, :], k=5)
    # numpy oracle with the plane's (score desc, shard asc, doc asc) order
    rows = [(float(v0[d] @ q), 0, d) for d in range(6)] + \
        [(float(v1[d] @ q), 1, d) for d in range(10) if exists1[d]]
    rows.sort(key=lambda r: (-r[0], r[1], r[2]))
    assert hits[0] == [(s, d) for _, s, d in rows[:5]]
    # the duplicated vector ties exactly: lower (shard, doc) address first
    dup_rank = [i for i, (_, s, d) in enumerate(rows)
                if (s, d) in ((0, 2), (1, 4))]
    assert dup_rank == [dup_rank[0], dup_rank[0] + 1]
    assert rows[dup_rank[0]][1:] == (0, 2)
    assert (1, 7) not in hits[0]
    assert all(vals[0][i] >= vals[0][i + 1]
               for i in range(len(hits[0]) - 1))
