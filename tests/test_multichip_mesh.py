"""Multichip serving: mesh-shape invariance + mesh telemetry.

The tentpole contract under test: the serving planes are MESH-SHAPE
TRANSPARENT — any (replica, shard) mesh over the conftest's 8 virtual
CPU devices produces bit-identical hits/values/tie-order to the 1x1
mesh for every serving path (eager BM25, block-max pruned, exact and
IVF kNN, base+delta merged serving), because the shard axis only
partitions per-shard work that was already independent and the replica
axis only partitions the batch. Plus the supporting machinery: env-knob
mesh selection (``mesh_from_env``), idle-device warning + gauge,
replica-aware micro-batcher stats/attribution, per-device HBM gauge,
the compile-churn ratchet on a 2-D mesh, and ``bench_diff``'s
MULTICHIP sweep gates.
"""

import json
import os

import numpy as np
import pytest

import jax

import elasticsearch_tpu.parallel.dist_search as ds
from elasticsearch_tpu.common import telemetry as tm
from elasticsearch_tpu.parallel.mesh import (AXIS_REPLICA, AXIS_SHARD,
                                             make_search_mesh,
                                             mesh_from_env)
from elasticsearch_tpu.search.microbatch import PlaneMicroBatcher
from elasticsearch_tpu.utils.synth import synthetic_csr_corpus

#: the parity matrix: (n_replicas, n_shards) over the 8 virtual devices
MESHES = [(1, 1), (1, 4), (2, 4), (8, 1)]


def _mesh(r, s):
    return make_search_mesh(n_shards=s, n_replicas=r)


@pytest.fixture(scope="module")
def text_shards():
    """3 shards — deliberately NOT dividing any multi-device shard axis,
    so every mesh exercises the constructors' empty-shard padding."""
    rng = np.random.RandomState(5)
    shards = []
    for _ in range(3):
        sh = synthetic_csr_corpus(rng, 192, 96, 7, zipf_s=1.25)
        sh["term_ids"] = {f"t{t}": t for t in range(96)}
        shards.append(sh)
    return shards


TEXT_QUERIES = [["t3", "t11"], ["t2"], ["t5", "t9", "t20"],
                ["t40", "t3"], ["t0", "t0", "t7"]]


def _text_result(plane, queries, k=10, pruned=False):
    if pruned:
        vals, hits, totals = plane.search_pruned(queries, k=k,
                                                 with_totals=True)
    else:
        vals, hits, totals = plane.search(queries, k=k, with_totals=True)
    return (np.asarray(vals).tobytes(), [list(h) for h in hits],
            list(totals))


# ---------------------------------------------------------------------------
# mesh-shape parity matrix
# ---------------------------------------------------------------------------


def test_bm25_parity_across_meshes(text_shards):
    ref = None
    for r, s in MESHES:
        plane = ds.DistributedSearchPlane(_mesh(r, s), text_shards,
                                          "body")
        cur = _text_result(plane, TEXT_QUERIES)
        if ref is None:
            ref = cur
        else:
            assert cur[0] == ref[0], f"values differ on mesh {r}x{s}"
            assert cur[1] == ref[1], f"hits/tie-order differ on {r}x{s}"
            assert cur[2] == ref[2], f"totals differ on mesh {r}x{s}"


def test_blockmax_pruned_parity_across_meshes(text_shards):
    """The rank-safe pruned scan is exact AND mesh-shape-invariant."""
    ref = eager = None
    for r, s in MESHES:
        plane = ds.DistributedSearchPlane(_mesh(r, s), text_shards,
                                          "body", blockmax={})
        cur = _text_result(plane, TEXT_QUERIES, pruned=True)
        if ref is None:
            ref = cur
            eager = _text_result(plane, TEXT_QUERIES)
            assert cur[0] == eager[0] and cur[1] == eager[1]
        else:
            assert cur == ref, f"pruned results differ on mesh {r}x{s}"


def test_knn_exact_and_ivf_parity_across_meshes():
    rng = np.random.RandomState(17)
    shards = [dict(vectors=rng.randn(200, 16).astype(np.float32))
              for _ in range(3)]
    qv = rng.randn(6, 16).astype(np.float32)
    ref_exact = ref_ivf = None
    for r, s in MESHES:
        knn = ds.DistributedKnnPlane(_mesh(r, s), shards,
                                     similarity="dot_product",
                                     ivf=dict(nlist=8, seed=0))
        vals, hits = knn.search(qv, k=5)
        exact = (np.asarray(vals).tobytes(), [list(h) for h in hits])
        ivals, ihits = knn.search_ivf(qv, k=5, nprobe=4, rerank=8)
        ivf = (np.asarray(ivals).tobytes(), [list(h) for h in ihits])
        if ref_exact is None:
            ref_exact, ref_ivf = exact, ivf
        else:
            assert exact == ref_exact, f"exact kNN differs on {r}x{s}"
            assert ivf == ref_ivf, f"IVF kNN differs on mesh {r}x{s}"


def test_base_delta_merged_parity_across_meshes(monkeypatch):
    """The full serving stack (ServingPlaneCache generations, base
    dispatch + delta merge through ShardSearcher) on the DEVICE path:
    every mesh shape returns identical ids/scores/totals."""
    from elasticsearch_tpu.index.mapping import MapperService
    from elasticsearch_tpu.index.segment import SegmentBuilder
    from elasticsearch_tpu.search.plane_route import ServingPlaneCache
    from elasticsearch_tpu.search.shard_search import ShardSearcher
    monkeypatch.setenv("ES_TPU_PLANE_HOST_SERVE", "0")
    monkeypatch.setenv("ES_TPU_SERVING_WARMUP", "0")
    svc = MapperService({"properties": {"body": {"type": "text"}}})
    words = ["quick", "brown", "fox", "dog", "lazy", "jump", "rank"]
    rng = np.random.RandomState(11)

    def mk(n_segs, per, start, prefix):
        segs, doc = [], start
        for si in range(n_segs):
            b = SegmentBuilder(f"{prefix}{si}")
            for _ in range(per):
                toks = [words[int(rng.randint(0, len(words)))]
                        for _ in range(5)]
                b.add(svc.parse_document(str(doc),
                                         {"body": " ".join(toks)}),
                      seq_no=doc)
                doc += 1
            segs.append(b.build())
        return segs

    base = mk(2, 20, 0, "s")
    delta = mk(1, 4, 500, "d")
    queries = [{"match": {"body": "quick dog"}},
               {"term": {"body": "fox"}},
               {"match": {"body": "lazy lazy rank"}}]
    results = {}
    for r, s in MESHES:
        cache = ServingPlaneCache(
            mesh_factory=lambda r=r, s=s: _mesh(r, s))
        cache.REPACK_DELTA_FRACTION = 10.0
        cache.plane_for(base, svc, "body")
        segs = base + delta
        searcher = ShardSearcher(
            segs, svc,
            plane_provider=lambda sl, f: cache.plane_for(sl, svc, f))
        out = []
        for q in queries:
            res = searcher.search({"query": q, "size": 10})
            out.append(([h.doc_id for h in res.hits],
                        [float(h.score) for h in res.hits], res.total))
        gen = cache.plane_for(segs, svc, "body")
        assert gen.delta is not None, "results must ride base+delta"
        assert gen.base._host_csr is None, "device path required"
        cache.release()
        results[(r, s)] = out
    ref = results[(1, 1)]
    for shape, out in results.items():
        assert out == ref, f"merged serving differs on mesh {shape}"


def test_empty_pad_shards_never_emit_hits(text_shards):
    """k deeper than the real corpus on a padded mesh: hit shard ids
    stay within the real shard range (pad shards are inert)."""
    plane = ds.DistributedSearchPlane(_mesh(1, 8), text_shards, "body")
    assert plane.n_shards == 8                # 3 real + 5 pad
    vals, hits, totals = plane.search([["t2", "t3"]], k=10,
                                      with_totals=True)
    assert totals[0] > 0
    for (si, _doc) in hits[0]:
        assert si < 3, "a pad shard emitted a hit"


# ---------------------------------------------------------------------------
# mesh selection knobs + idle-device surfacing
# ---------------------------------------------------------------------------


def test_mesh_from_env_default_all_shard(monkeypatch):
    monkeypatch.delenv("ES_TPU_MESH_SHARDS", raising=False)
    monkeypatch.delenv("ES_TPU_MESH_REPLICAS", raising=False)
    mesh = mesh_from_env()
    assert mesh.shape[AXIS_SHARD] == len(jax.devices())
    assert mesh.shape[AXIS_REPLICA] == 1
    assert tm.mesh_idle_devices() == 0


def test_mesh_from_env_knobs(monkeypatch):
    monkeypatch.setenv("ES_TPU_MESH_REPLICAS", "2")
    monkeypatch.delenv("ES_TPU_MESH_SHARDS", raising=False)
    mesh = mesh_from_env()
    assert (mesh.shape[AXIS_REPLICA], mesh.shape[AXIS_SHARD]) == (2, 4)
    monkeypatch.setenv("ES_TPU_MESH_SHARDS", "2")
    mesh = mesh_from_env()
    assert (mesh.shape[AXIS_REPLICA], mesh.shape[AXIS_SHARD]) == (2, 2)
    assert tm.mesh_idle_devices() == 4


def test_idle_devices_warned_and_gauged(caplog, monkeypatch):
    import logging
    with caplog.at_level(logging.WARNING, "elasticsearch_tpu.mesh"):
        make_search_mesh(n_shards=3, n_replicas=2)
    assert any("stranded idle" in r.message for r in caplog.records)
    # the gauge belongs to the SERVING-mesh owners (mesh_from_env, the
    # cache's factory path): a 3x2 serving mesh strands 2 devices...
    monkeypatch.setenv("ES_TPU_MESH_SHARDS", "3")
    monkeypatch.setenv("ES_TPU_MESH_REPLICAS", "2")
    mesh_from_env()
    assert tm.mesh_idle_devices() == 2
    # ...and an AUXILIARY build (bench reference plane, lint workload)
    # must not clobber the serving signal back to healthy
    make_search_mesh(n_shards=1, n_replicas=1)
    assert tm.mesh_idle_devices() == 2
    monkeypatch.delenv("ES_TPU_MESH_SHARDS")
    monkeypatch.delenv("ES_TPU_MESH_REPLICAS")
    mesh_from_env()                    # full slice: gauge resets
    assert tm.mesh_idle_devices() == 0


# ---------------------------------------------------------------------------
# replica-aware micro-batcher: topology stats + per-device attribution
# ---------------------------------------------------------------------------


def test_batcher_mesh_topology_and_per_device_attribution(text_shards):
    plane = ds.DistributedSearchPlane(_mesh(2, 4), text_shards, "body")
    b = PlaneMicroBatcher(plane)
    doc = b.stats_doc()
    assert doc["mesh_shard_devices"] == 4
    assert doc["mesh_replica_devices"] == 2
    info = {}
    b.search(["t3", "t5"], 10, info=info)
    assert info["docs_scanned_per_device"] == \
        -(-info["docs_scanned"] // 4)


def test_mesh_dispatch_counters_advance_by_axis_extent(text_shards):
    def _axis_counts():
        doc = tm.DEFAULT.metrics_doc().get("es_mesh_dispatch_total")
        out = {"shard": 0, "replica": 0}
        for srs in (doc or {}).get("series", []):
            out[srs["labels"]["axis"]] = int(srs["value"])
        return out
    plane = ds.DistributedSearchPlane(_mesh(2, 4), text_shards, "body")
    before = _axis_counts()
    plane.search([["t3"]], k=5)
    after = _axis_counts()
    assert after["shard"] - before["shard"] == 4
    assert after["replica"] - before["replica"] == 2


def test_plane_serving_stats_merge_topology_not_summed():
    """nodes-stats plane_serving: mesh topology keys are max-merged
    across batchers (text + kNN share one cache mesh), never summed."""
    import tempfile
    from elasticsearch_tpu.node.indices_service import IndicesService
    from elasticsearch_tpu.rest.api import RestAPI
    api = RestAPI(IndicesService(tempfile.mkdtemp(prefix="mesh_stats_")))
    lines = []
    for i in range(64):
        lines.append(json.dumps({"index": {"_id": str(i)}}))
        lines.append(json.dumps({"body": f"w{i % 7} w{(i + 1) % 7}"}))
    api.handle("POST", "/ms/_bulk", "refresh=true",
               ("\n".join(lines) + "\n").encode())
    st, _, _ = api.handle(
        "POST", "/ms/_search", "",
        json.dumps({"query": {"match": {"body": "w3"}}}).encode())
    assert st == 200
    svc = api.indices.get("ms")
    doc = svc.plane_serving_stats()
    n_dev = len(jax.devices())
    assert doc["mesh_shard_devices"] * doc["mesh_replica_devices"] \
        <= n_dev, "topology keys were summed across batchers"
    assert doc["mesh_shard_devices"] >= 1


# ---------------------------------------------------------------------------
# per-device HBM gauge + bytes accessor vs live buffers
# ---------------------------------------------------------------------------


def test_device_corpus_bytes_matches_live_buffers(text_shards):
    for r, s in [(1, 1), (1, 4), (2, 4)]:
        plane = ds.DistributedSearchPlane(_mesh(r, s), text_shards,
                                          "body")
        per_dev = {}
        for arr in (plane.docs_dev, plane.impacts_dev, plane.dense_dev):
            if arr is None:
                continue
            for sh in arr.addressable_shards:
                did = int(sh.device.id)
                per_dev[did] = per_dev.get(did, 0) + int(sh.data.nbytes)
        measured = max(per_dev.values())
        assert plane.device_corpus_bytes() == measured, (r, s)
        # the shard axis genuinely divides the resident bytes: each
        # device holds n_shards/s shard rows' worth (3 real shards pad
        # to 4 on the 4-wide axis, so compare per-SHARD-row bytes
        # against the unpadded 1x1 plane, not raw totals)
        if s > 1:
            one = ds.DistributedSearchPlane(_mesh(1, 1), text_shards,
                                            "body")
            per_shard_row = one.device_corpus_bytes() // one.n_shards
            assert measured * s == per_shard_row * plane.n_shards, (r, s)


def test_knn_device_corpus_bytes_scale_with_shards():
    rng = np.random.RandomState(3)
    shards = [dict(vectors=rng.randn(64, 8).astype(np.float32))
              for _ in range(4)]
    b1 = ds.DistributedKnnPlane(_mesh(1, 1), shards,
                                similarity="dot_product")
    b4 = ds.DistributedKnnPlane(_mesh(1, 4), shards,
                                similarity="dot_product")
    assert b4.device_corpus_bytes() * 4 == b1.device_corpus_bytes()


def test_cache_exports_per_device_hbm_gauge(monkeypatch):
    from elasticsearch_tpu.index.mapping import MapperService
    from elasticsearch_tpu.index.segment import SegmentBuilder
    from elasticsearch_tpu.search.plane_route import ServingPlaneCache
    monkeypatch.setenv("ES_TPU_SERVING_WARMUP", "0")
    svc = MapperService({"properties": {"body": {"type": "text"}}})
    b = SegmentBuilder("s0")
    for i in range(32):
        b.add(svc.parse_document(str(i), {"body": f"w{i % 5} w0"}),
              seq_no=i)
    cache = ServingPlaneCache(mesh_factory=lambda: _mesh(1, 4))
    gen = cache.plane_for([b.build()], svc, "body")
    assert gen is not None
    fam = cache._metrics_doc()["es_plane_hbm_bytes"]
    assert fam["type"] == "gauge"
    per_dev = {lbl["device"]: v for lbl, v in fam["samples"]}
    assert len(per_dev) == 4
    assert set(per_dev.values()) == {gen.base.device_corpus_bytes()}
    # the factory mesh is a serving mesh: the cache owns the gauge
    assert tm.mesh_idle_devices() == 4
    cache.release()
    # restore the full-slice signal so later health assertions in the
    # suite don't inherit this test's deliberately-small serving mesh
    from elasticsearch_tpu.parallel.mesh import record_mesh_devices
    record_mesh_devices(len(jax.devices()), 0)


# ---------------------------------------------------------------------------
# compile-churn ratchet on a 2-D mesh
# ---------------------------------------------------------------------------


def test_zero_steady_state_compiles_on_2d_mesh(monkeypatch, text_shards):
    """The plane's serving list covers what it serves at a 2x4 mesh too
    (padded batches are multiples of the replica axis): a post-warmup
    burst across batch sizes compiles nothing."""
    monkeypatch.setenv("ES_TPU_PLANE_HOST_SERVE", "0")
    plane = ds.DistributedSearchPlane(_mesh(2, 4), text_shards, "body")
    assert plane._host_csr is None
    b = PlaneMicroBatcher(plane)
    b.warmup(ks=(10,), max_b=4, sync=True)
    listed = plane.serving_shapes([16], 4)
    assert [s[0] for s in listed] == [2, 4]      # B 1 pads to 2 replicas
    assert b.warmed_shapes == len(listed)
    def _compiles():
        doc = tm.DEFAULT.metrics_doc().get("es_xla_compiles_total")
        return sum(int(s["value"]) for s in (doc or {}).get("series", []))
    before = _compiles()
    for q in TEXT_QUERIES * 2:
        b.search(q, 10)
    assert _compiles() == before, \
        "steady-state serving compiled new shapes on the 2-D mesh"


# ---------------------------------------------------------------------------
# bench_diff: MULTICHIP sweep gates
# ---------------------------------------------------------------------------


def _load_bench_diff():
    # the same loader the driver's sweep uses — one resolution path
    import __graft_entry__ as graft
    return graft._load_bench_diff(
        os.path.join(os.path.dirname(__file__), ".."))


def _mc_record(points):
    tail = json.dumps({"sweep": points, "parity": "exact", "ok": True,
                       "failures": []})
    return {"n_devices": 8, "rc": 0, "ok": True, "skipped": False,
            "tail": tail}


def _pt(devices, qps, text_b, knn_b):
    return {"devices": devices, "mesh": f"1x{devices}", "qps": qps,
            "p50_ms": 10.0, "p99_ms": 20.0, "steady_compiles": 0,
            "text_device_bytes": text_b, "knn_device_bytes": knn_b}


def test_bench_diff_multichip_gates():
    bd = _load_bench_diff()
    old = bd._unwrap(_mc_record([_pt(1, 100.0, 8000, 4000),
                                 _pt(4, 110.0, 2000, 1000)]))
    assert set(old["configs"]) == {"multichip_1dev", "multichip_4dev"}
    # clean: same sweep diffs green, scaling holds
    _, regs = bd.diff(old, old, 0.10)
    assert not regs and not bd._multichip_scaling_check(old)
    # throughput regression at one device count gates
    new = bd._unwrap(_mc_record([_pt(1, 100.0, 8000, 4000),
                                 _pt(4, 80.0, 2000, 1000)]))
    _, regs = bd.diff(old, new, 0.10)
    assert any("multichip_4dev" in r for r in regs)
    # per-device bytes growth gates even at flat qps
    new = bd._unwrap(_mc_record([_pt(1, 100.0, 8000, 4000),
                                 _pt(4, 110.0, 3000, 1000)]))
    _, regs = bd.diff(old, new, 0.10)
    assert any("text_device_bytes" in r for r in regs)
    # broken 1/n_shards scaling fails the intra-file check
    broken = bd._unwrap(_mc_record([_pt(1, 100.0, 8000, 4000),
                                    _pt(4, 110.0, 7900, 3900)]))
    assert bd._multichip_scaling_check(broken)
    # one-sided device counts skip with a note, never gate
    half = bd._unwrap(_mc_record([_pt(1, 100.0, 8000, 4000)]))
    lines, regs = bd.diff(old, half, 0.10)
    assert not regs
    assert any("SKIPPED" in ln for ln in lines)
    # legacy empty shell on BOTH sides diffs green
    shell = bd._unwrap({"n_devices": 8, "rc": 0, "ok": True,
                        "skipped": False, "tail": ""})
    _, regs = bd.diff(shell, shell, 0.10)
    assert not regs and bd._multichip_scaling_check(shell) == []


def test_bench_wrapper_not_misread_as_multichip():
    """The driver's BENCH_r*.json wrapper carries rc/tail TOO (nesting
    the bench doc under ``parsed``): it must unwrap to the bench doc,
    never to an empty multichip record — that would silently disable
    the whole bench regression gate."""
    bd = _load_bench_diff()
    wrapper = {"n": 5, "cmd": "python bench.py", "rc": 0, "tail": "...",
               "parsed": {"value": 123.0, "unit": "docs/s",
                          "configs": {"c1": {"value": 9.0,
                                             "unit": "q/s"}}}}
    out = bd._unwrap(wrapper)
    assert out == wrapper["parsed"]
    assert not out.get("multichip")
    # a >10% drop through the wrapper still gates
    worse = {**wrapper, "parsed": {**wrapper["parsed"],
                                   "configs": {"c1": {"value": 5.0,
                                                      "unit": "q/s"}}}}
    _, regs = bd.diff(bd._unwrap(wrapper), bd._unwrap(worse), 0.10)
    assert any("c1" in r for r in regs)
