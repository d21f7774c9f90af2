"""IVF cluster-pruned ANN: k-means coarse quantizer + int8 tier + exact
re-rank (``parallel/dist_search.py`` IvfKnnTier / build_ivf_knn_step /
DistributedKnnPlane.search_ivf*).

Invariants under test:
- PROPERTY: with pruning disabled (``nprobe == nlist``) and a rerank
  window covering the corpus, the int8-scan + exact-re-rank pipeline
  returns IDENTICAL (value, hit, tie-order) results to the exact f32
  scan — including adversarial near-tie vectors whose int8 codes
  collapse (the exact re-rank must restore f32 order);
- the jitted device step and the CPU host path agree exactly;
- per-row int8 quantization reconstruction error is bounded by scale/2;
- recall@10 at the serving defaults is high on clustered corpora (the
  shape real embedding corpora have);
- the serving route (ServingPlaneCache past the IVF corpus threshold)
  honors the ``nprobe``/``rerank`` knobs, falls back to exact brute
  force below the threshold, and records the es_ann_* telemetry
  incl. the nprobe-below-default drift counter.
"""

import numpy as np
import pytest
import jax

from elasticsearch_tpu.parallel import make_search_mesh
from elasticsearch_tpu.parallel.dist_search import (
    DistributedKnnPlane, IvfKnnTier, kmeans_fit, quantize_int8_rows)

SIMS = ("dot_product", "cosine", "l2_norm")


def _mesh():
    return make_search_mesh(n_shards=1, n_replicas=1,
                            devices=jax.devices()[:1])


def _near_tie_corpus(rng, n, dim, delta):
    """Random rows plus adversarial blocks: exact duplicates (pure tie —
    must resolve by ascending doc id) and delta-separated near-ties
    whose separations drown in int8 quantization error (the quantized
    scan cannot order them; only the exact re-rank can). ``delta`` is
    picked per similarity: far below one int8 step, but above the f32
    noise floor of that similarity's score expansion (l2's
    ``2q·v - ‖v‖² - ‖q‖²`` cancels catastrophically near zero
    distance, so its resolvable gap is coarser)."""
    vecs = rng.randn(n, dim).astype(np.float32)
    t = rng.randn(dim).astype(np.float32)
    t /= np.linalg.norm(t)
    for i in range(20):
        vecs[50 + i] = t * (2.0 + delta * i)
    # exact duplicates scattered across the corpus
    for i in range(10):
        vecs[200 + i] = vecs[10 + i]
    return vecs, t


@pytest.mark.parametrize("similarity", SIMS)
@pytest.mark.parametrize("seed", (0, 7))
def test_int8_rerank_equals_exact_when_prune_disabled(similarity, seed):
    rng = np.random.RandomState(seed)
    delta = 1e-2 if similarity == "l2_norm" else 1e-4
    vecs, t = _near_tie_corpus(rng, 400, 12, delta)
    plane = DistributedKnnPlane(_mesh(), [dict(vectors=vecs)],
                                similarity=similarity,
                                ivf=dict(nlist=8, seed=seed))
    # query 2 sits OFF-center in the near-tie lattice: a query exactly
    # on a lattice point makes symmetric neighbor pairs exact ties in
    # ℝ under l2, which f32 rounds differently per evaluation order —
    # not a property any implementation can promise
    qs = np.stack([t, rng.randn(12).astype(np.float32),
                   t * np.float32(2.0 + delta * 5.3), vecs[203]])
    ev, eh = plane.search_host(qs, k=25)
    # nprobe == nlist (no pruning), rerank window covers the corpus
    av, ah = plane.search_ivf_host(qs, k=25, nprobe=8, rerank=64)
    assert np.allclose(ev, av, atol=1e-5), (ev[0][:6], av[0][:6])
    assert eh == ah


@pytest.mark.parametrize("similarity", SIMS)
def test_device_step_matches_host_path(similarity):
    rng = np.random.RandomState(5)
    shards = [dict(vectors=rng.randn(n, 12).astype(np.float32))
              for n in (300, 150, 220)]
    shards[1]["vectors"][:30] = shards[0]["vectors"][:30]  # cross ties
    plane = DistributedKnnPlane(_mesh(), shards, similarity=similarity,
                                ivf=dict(nlist=6, seed=3))
    qs = np.concatenate([rng.randn(3, 12).astype(np.float32),
                         shards[0]["vectors"][:2]])
    hv, hh = plane.search_ivf_host(qs, k=12, nprobe=3, rerank=4)
    plane._host_pack = None                   # force the jitted path
    dv, dh = plane.serve(qs, k=12, nprobe=3, rerank=4)
    assert np.allclose(hv, dv, atol=1e-4)
    assert hh == dh


def test_quantization_roundtrip_error_bound():
    rng = np.random.RandomState(1)
    vecs = np.concatenate([
        rng.randn(64, 16).astype(np.float32) * 3.0,
        np.zeros((2, 16), np.float32),          # degenerate constant rows
        np.full((2, 16), 2.5, np.float32)])
    codes, scale, off = quantize_int8_rows(vecs)
    assert codes.dtype == np.int8
    recon = scale[:, None] * codes.astype(np.float32) + off[:, None]
    # per-row error ≤ half a quantization step
    err = np.abs(recon - vecs).max(axis=1)
    assert np.all(err <= scale * 0.5 + 1e-6)


def test_kmeans_fit_uses_every_centroid():
    rng = np.random.RandomState(2)
    centers = rng.randn(16, 8).astype(np.float32) * 4
    x = (centers[rng.randint(0, 16, 2000)]
         + 0.2 * rng.randn(2000, 8)).astype(np.float32)
    cent = kmeans_fit(x, 16, iters=8, seed=0)
    assert cent.shape == (16, 8) and np.isfinite(cent).all()
    from elasticsearch_tpu.parallel.dist_search import _assign_clusters
    assign = _assign_clusters(x, cent, l2=False)
    # every centroid owns rows (empty clusters were re-seeded)
    assert len(np.unique(assign)) >= 14


def test_cluster_contiguous_reorder_and_offsets():
    rng = np.random.RandomState(4)
    vecs = rng.randn(1, 500, 8).astype(np.float32)
    exists = np.ones((1, 500), bool)
    exists[0, 490:] = False
    tier = IvfKnnTier.build(vecs, exists, "dot_product", nlist=8, seed=0)
    sh = tier.shards[0]
    assert int(sh["offsets"][-1]) == 490          # only existing rows
    assert sorted(sh["rows"].tolist()) == list(range(490))
    # within a cluster rows stay doc-ascending (stable reorder = exact
    # tie order after re-rank)
    for c in range(tier.nlist):
        lo, hi = int(sh["offsets"][c]), int(sh["offsets"][c + 1])
        run = sh["rows"][lo:hi]
        assert np.all(np.diff(run) > 0)


def test_ivf_recall_on_clustered_corpus():
    rng = np.random.RandomState(9)
    centers = rng.randn(128, 16).astype(np.float32)
    idx = rng.randint(0, 128, 20000)
    corpus = (centers[idx] + 0.3 * rng.randn(20000, 16)).astype(np.float32)
    plane = DistributedKnnPlane(_mesh(), [dict(vectors=corpus)],
                                similarity="cosine",
                                ivf=dict(nlist=64, seed=0))
    q = corpus[rng.randint(0, 20000, 16)] \
        + 0.1 * rng.randn(16, 16).astype(np.float32)
    ev, eh = plane.serve(q, k=10, nprobe=0)
    av, ah = plane.serve(q, k=10)              # serving defaults
    rec = np.mean([len(set(a) & set(e)) / 10 for a, e in zip(ah, eh)])
    assert rec >= 0.95, rec


def test_bf16_tier_parity_when_prune_disabled():
    rng = np.random.RandomState(6)
    vecs = rng.randn(300, 8).astype(np.float32)
    plane = DistributedKnnPlane(_mesh(), [dict(vectors=vecs)],
                                similarity="cosine",
                                ivf=dict(nlist=4, seed=0, quant="bf16"))
    assert plane.ivf.quant_bytes_per_dim() == 2
    q = rng.randn(3, 8).astype(np.float32)
    ev, eh = plane.search_host(q, k=10)
    av, ah = plane.search_ivf_host(q, k=10, nprobe=4, rerank=32)
    assert np.allclose(ev, av, atol=1e-5) and eh == ah


def test_exists_masked_rows_never_surface():
    rng = np.random.RandomState(8)
    vecs = rng.randn(200, 8).astype(np.float32)
    exists = np.ones(200, bool)
    exists[::3] = False
    plane = DistributedKnnPlane(_mesh(),
                                [dict(vectors=vecs, exists=exists)],
                                similarity="dot_product",
                                ivf=dict(nlist=4, seed=0))
    q = rng.randn(4, 8).astype(np.float32)
    for nprobe in (1, 4):
        _v, hits = plane.search_ivf_host(q, k=20, nprobe=nprobe, rerank=8)
        for row in hits:
            assert all(exists[d] for (_si, d) in row)
    plane._host_pack = None
    _v, hits = plane.serve(q, k=20, nprobe=4, rerank=8)
    for row in hits:
        assert all(exists[d] for (_si, d) in row)


def test_serving_route_knobs_threshold_and_drift(tmp_path):
    import json
    from elasticsearch_tpu.common import telemetry as tm
    from elasticsearch_tpu.node.indices_service import IndicesService
    from elasticsearch_tpu.rest.api import RestAPI

    api = RestAPI(IndicesService(str(tmp_path)))
    api.handle("PUT", "/iv", "", json.dumps({"mappings": {"properties": {
        "vec": {"type": "dense_vector", "dims": 8,
                "similarity": "cosine"}}}}).encode())
    rng = np.random.RandomState(0)
    lines = []
    for i in range(400):
        lines.append(json.dumps({"index": {"_id": str(i)}}))
        lines.append(json.dumps(
            {"vec": [round(float(x), 4) for x in rng.randn(8)]}))
    api.handle("POST", "/iv/_bulk", "refresh=true",
               ("\n".join(lines) + "\n").encode())
    svc = api.indices.get("iv")
    q = [round(float(x), 4) for x in rng.randn(8)]

    def hits(extra):
        body = {"knn": {"field": "vec", "query_vector": q, "k": 10,
                        "num_candidates": 40, **extra}, "size": 10}
        st, _, payload = api.handle("POST", "/iv/_search",
                                    "request_cache=false",
                                    json.dumps(body).encode())
        doc = json.loads(payload)
        assert st == 200, doc
        return [h["_id"] for h in doc["hits"]["hits"]]

    # below the corpus threshold: brute-force fallback, knobs inert,
    # no IVF tier built
    exact = hits({})
    gen = next(iter(svc.plane_cache._knn_planes.values()))
    assert gen.base.ivf is None
    assert hits({"nprobe": 1}) == exact

    # force the threshold down and rebuild: the tier engages
    svc.plane_cache.knn_ivf_min_docs = 1
    svc.plane_cache._knn_planes.clear()
    full = hits({"nprobe": 10 ** 6, "rerank": 64})
    assert full == exact                       # prune disabled == exact
    gen = next(iter(svc.plane_cache._knn_planes.values()))
    assert gen.base.ivf is not None
    assert hits({"nprobe": 0}) == exact        # nprobe=0 forces exact

    # a below-default nprobe dispatch records recall-config drift and
    # turns the plane_serving indicator yellow
    drift0 = tm.ann_drift_count()
    hits({"nprobe": 1})
    assert tm.ann_drift_count() > drift0
    st, _, payload = api.handle("GET", "/_health_report/plane_serving",
                                "", b"")
    ind = json.loads(payload)["indicators"]["plane_serving"]
    assert ind["status"] in ("yellow", "red")
    assert any(d["id"] == "plane_serving:ann_nprobe_below_default"
               for d in ind.get("diagnosis", []))

    # validation at the REST edge
    st, _, _ = api.handle("POST", "/iv/_search", "", json.dumps(
        {"knn": {"field": "vec", "query_vector": q, "k": 5,
                 "nprobe": -1}}).encode())
    assert st == 400
    st, _, _ = api.handle("POST", "/iv/_search", "", json.dumps(
        {"knn": {"field": "vec", "query_vector": q, "k": 5,
                 "rerank": 0}}).encode())
    assert st == 400


def test_ann_telemetry_families_register():
    from elasticsearch_tpu.common import telemetry as tm
    rng = np.random.RandomState(11)
    vecs = rng.randn(300, 8).astype(np.float32)
    plane = DistributedKnnPlane(_mesh(), [dict(vectors=vecs)],
                                similarity="cosine",
                                ivf=dict(nlist=4, seed=0))
    snap0 = tm.DEFAULT.stats_doc()

    def total(name):
        fam = tm.DEFAULT.stats_doc().get(name)
        return sum(s["value"] for s in fam["series"]) if fam else 0.0

    before = {n: total(n) for n in ("es_ann_clusters_probed_total",
                                    "es_ann_candidates_reranked_total")}
    stages = {}
    plane.search_ivf_host(rng.randn(2, 8).astype(np.float32), k=5,
                          nprobe=2, rerank=4, stages=stages)
    assert total("es_ann_clusters_probed_total") == \
        before["es_ann_clusters_probed_total"] + 2 * 2
    assert total("es_ann_candidates_reranked_total") > \
        before["es_ann_candidates_reranked_total"]
    assert stages["ann_quantized_bytes"] > 0
    assert stages["ann_exact_bytes"] > 0
    assert stages["docs_scanned"] > 0
    del snap0


def _per_block_merge(blk):
    """The selection of PR 35's IVF step, kept as the reference: a
    ``lax.scan`` over the gathered blocks of the flat scores, each
    block's top-``min(r_cand, blk)`` merged into a running window of
    ``r_cand`` (the carry first, so ``top_k`` keeps the earlier
    position). Same signature as ``batched_blockwise_topk``."""
    from jax import lax
    import jax.numpy as jnp
    from elasticsearch_tpu.ops.topk import batched_blockwise_topk

    def select(scores, r_cand):
        B = scores.shape[0]
        blocks = scores.reshape(B, -1, blk).transpose(1, 0, 2)
        rr = min(r_cand, blk)

        def step_blk(carry, xs):
            p_idx, s_b = xs
            bv, bi = batched_blockwise_topk(s_b, rr)
            cat_v = jnp.concatenate([carry[0], bv], axis=1)
            cat_i = jnp.concatenate([carry[1], bi + p_idx * blk], axis=1)
            nv, sel = lax.top_k(cat_v, min(r_cand, cat_v.shape[1]))
            return (nv, jnp.take_along_axis(cat_i, sel, axis=1)), None

        v0, i0 = batched_blockwise_topk(blocks[0], rr)
        if rr < r_cand:
            v0 = jnp.pad(v0, ((0, 0), (0, r_cand - rr)),
                         constant_values=float("-inf"))
            i0 = jnp.pad(i0, ((0, 0), (0, r_cand - rr)))
        (vals, pos), _ = lax.scan(
            step_blk, (v0, i0),
            (jnp.arange(1, blocks.shape[0], dtype=jnp.int32), blocks[1:]))
        return vals, pos

    return select

@pytest.fixture(scope="module")
def dup_plane():
    """20,480 clustered rows of which 2,560 are 64 copies each of 40
    rows, scattered: their int8 codes are equal, so quantized scores tie
    across blocks at the edge of the re-rank window."""
    rng = np.random.RandomState(36)
    centers = rng.randn(48, 8).astype(np.float32)
    vecs = (centers[rng.randint(0, 48, 20480)]
            + 0.3 * rng.randn(20480, 8)).astype(np.float32)
    vecs[rng.permutation(20480)[:2560]] = np.repeat(vecs[:40], 64, axis=0)
    planes = {sim: DistributedKnnPlane(_mesh(), [dict(vectors=vecs)],
                                       similarity=sim,
                                       ivf=dict(nlist=64, seed=0))
              for sim in ("cosine", "l2_norm")}
    return planes, vecs


@pytest.mark.parametrize("similarity", ("cosine", "l2_norm"))
@pytest.mark.parametrize("b,width,k,nprobe", [
    (1, 16, 16, 8), (1, 64, 16, 8), (2, 16, 16, 8), (2, 64, 16, 8),
    (8, 16, 16, 8), (8, 64, 16, 8), (32, 16, 16, 8), (32, 64, 16, 8),
    (8, 64, 128, 1)])
def test_one_pass_selection_equals_the_per_block_merge(
        dup_plane, similarity, b, width, k, nprobe):
    """PR 36: one exact selection over the flat probed union returns what
    the running per-block merge returned, bitwise, on both of its paths
    (a width of 16 blocks sorts its 4,096 columns whole, 64 blocks take
    the group path), and where fewer than ``r_cand`` rows are live
    (``nprobe`` 1, ``k`` 128) in its finite entries."""
    import jax.numpy as jnp
    from elasticsearch_tpu.ops.topk import _group_width
    from elasticsearch_tpu.parallel import dist_search as ds
    planes, vecs = dup_plane
    plane = planes[similarity]
    tier, r_cand = plane.ivf, 4 * k
    blk = tier.block
    assert bool(_group_width(width * blk, r_cand)) == (width == 64)
    rng = np.random.RandomState(b * width + k)
    q = (vecs[rng.randint(0, 40, b)]
         + 0.05 * rng.randn(b, 8)).astype(np.float32)
    probed = tier.probe(plane._probe_queries(q)[0], nprobe)
    # the union's blocks first, in a seeded order, then the rest of the
    # tier and the sentinel: ties between blocks fall both ways
    union = np.unique(tier.union_blocks(probed, 1)[0])
    rest = np.setdiff1d(np.arange(tier.n_blocks + 1), union)
    u = np.concatenate([rng.permutation(union), rng.permutation(rest)])
    u = u[:width].astype(np.int32)[None]
    dev = tier.device_arrays(plane.mesh, plane.n_pad)
    vecs_dev, vn_dev, _ex = plane._device_arrays()
    args = (dev["codes"], dev["scale"], dev["off"], dev["rowid"],
            dev["rcl"], vecs_dev, vn_dev, dev["centroids"], dev["cnorm2"],
            dev["c_first"], dev["c_end"], dev["c_rows"], q)
    kw = dict(n_pad=plane.n_pad, dim=8, k=k, n_shards=1,
              similarity=similarity, nprobe=nprobe, r_cand=r_cand, blk=blk,
              width=width)

    def seeded_union(*_a, **_kw):
        # the step's own probe still masks each query's clusters; only
        # the gathered blocks and their order are the test's
        return jnp.asarray(u), jnp.zeros(4, jnp.int32)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ds, "_ivf_union", seeded_union)
        got_v, got_i, _ = (np.asarray(a) for a in
                           ds.build_ivf_knn_step(plane.mesh, **kw)(*args))
        mp.setattr(ds, "batched_blockwise_topk", _per_block_merge(blk))
        want_v, want_i, _ = (np.asarray(a) for a in
                             ds.build_ivf_knn_step(plane.mesh, **kw)(*args))
    fin = np.isfinite(want_v)
    assert (np.isfinite(got_v) == fin).all()
    assert got_v[fin].tobytes() == want_v[fin].tobytes()
    assert (got_i[fin] == want_i[fin]).all()
    live = int(fin.sum(axis=1).min())
    assert live == k if nprobe > 1 else 0 < live < k
