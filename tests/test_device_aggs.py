"""Device aggregation kernels (ops/aggs.py): parity with the host numpy
path, forced on by shrinking DEVICE_MIN_PAIRS so the small fixtures take
the device route."""

import numpy as np
import pytest

from elasticsearch_tpu.index.mapping import MapperService
from elasticsearch_tpu.index.segment import SegmentBuilder
from elasticsearch_tpu.ops import aggs as ops_aggs
from elasticsearch_tpu.search.shard_search import ShardSearcher

MAPPING = {"properties": {
    "tag": {"type": "keyword"},
    "price": {"type": "double"},
    "ts": {"type": "date"},
    "body": {"type": "text"},
}}


@pytest.fixture(scope="module")
def searcher():
    rng = np.random.RandomState(3)
    mapper = MapperService(MAPPING)
    segs = []
    for si in range(2):
        b = SegmentBuilder(f"_d{si}")
        for i in range(150):
            did = si * 1000 + i
            b.add(mapper.parse_document(str(did), {
                "tag": f"k{rng.randint(12)}",
                "price": float(rng.randint(100)),
                "ts": 1_700_000_000_000 + did * 600_000,
                "body": "common" if i % 3 else "rare",
            }), seq_no=did)
        segs.append(b.build())
    return ShardSearcher(segs, mapper)


def _run(searcher, aggs, query=None):
    body = {"aggs": aggs, "size": 0}
    if query:
        body["query"] = query
    return searcher.search(body).aggregations


@pytest.mark.parametrize("query", [
    None, {"match": {"body": "common"}}, {"match": {"body": "rare"}}])
def test_terms_device_matches_host(searcher, query, monkeypatch):
    host = _run(searcher, {"t": {"terms": {"field": "tag", "size": 20}}},
                query)
    monkeypatch.setattr(ops_aggs, "DEVICE_MIN_PAIRS", 1)
    dev = _run(searcher, {"t": {"terms": {"field": "tag", "size": 20}}},
               query)
    assert dev == host   # int32-exact kernel: bitwise-identical buckets


@pytest.mark.parametrize("query", [None, {"match": {"body": "common"}}])
def test_histogram_device_matches_host(searcher, query, monkeypatch):
    spec = {"h": {"histogram": {"field": "price", "interval": 10}}}
    host = _run(searcher, spec, query)
    monkeypatch.setattr(ops_aggs, "DEVICE_MIN_PAIRS", 1)
    dev = _run(searcher, spec, query)
    assert dev == host


def test_terms_device_with_subagg_matches_host(searcher, monkeypatch):
    spec = {"t": {"terms": {"field": "tag", "size": 5},
                  "aggs": {"p": {"avg": {"field": "price"}}}}}
    host = _run(searcher, spec)
    monkeypatch.setattr(ops_aggs, "DEVICE_MIN_PAIRS", 1)
    dev = _run(searcher, spec)
    assert dev == host


def test_ordinal_kernel_against_numpy():
    rng = np.random.RandomState(0)
    import jax.numpy as jnp
    n_pad, V, M = 1 << 10, 37, 5000
    docs = rng.randint(0, 700, M).astype(np.int32)
    ords = rng.randint(0, V, M).astype(np.int32)
    order = np.lexsort((docs, ords))
    docs, ords = docs[order], ords[order]
    offsets = np.zeros(V + 1, np.int32)
    np.cumsum(np.bincount(ords, minlength=V).astype(np.int32),
              out=offsets[1:])
    mask = rng.rand(n_pad) < 0.4
    got = np.asarray(ops_aggs.masked_ordinal_counts(
        jnp.asarray(offsets), jnp.asarray(docs), jnp.asarray(mask)))
    want = np.bincount(ords[mask[docs]], minlength=V)
    np.testing.assert_array_equal(got, want)
    vals = rng.rand(M).astype(np.float32)
    got_s = np.asarray(ops_aggs.masked_ordinal_sums(
        jnp.asarray(offsets), jnp.asarray(docs), jnp.asarray(vals),
        jnp.asarray(mask)))
    want_s = np.zeros(V, np.float64)
    np.add.at(want_s, ords[mask[docs]], vals[mask[docs]])
    np.testing.assert_allclose(got_s, want_s, rtol=1e-4)


def test_masked_metrics_kernel():
    rng = np.random.RandomState(1)
    import jax.numpy as jnp
    n_pad, M = 256, 1000
    docs = rng.randint(0, 200, M).astype(np.int32)
    vals = rng.randn(M).astype(np.float32)
    mask = rng.rand(n_pad) < 0.5
    cnt, s, mn, mx = [np.asarray(x) for x in ops_aggs.masked_metrics(
        jnp.asarray(docs), jnp.asarray(vals), jnp.asarray(mask))]
    pm = mask[docs]
    assert cnt == pm.sum()
    np.testing.assert_allclose(s, vals[pm].sum(), rtol=1e-5)
    assert mn == vals[pm].min() and mx == vals[pm].max()


def test_masked_ordinal_percentiles_exact_vs_numpy():
    """The cumsum+searchsorted percentile kernel is EXACT (Hazen), unlike
    the reference's TDigest (metrics/TDigestState.java)."""
    import jax.numpy as jnp
    rng = np.random.RandomState(7)
    N, V, M = 3000, 12, 15000
    ords = rng.randint(0, V, M).astype(np.int32)
    docs = rng.randint(0, N, M).astype(np.int32)
    vals = (rng.randn(M) * 50).astype(np.float32)
    order = np.lexsort((vals, ords))
    ords_s, docs_s, vals_s = ords[order], docs[order], vals[order]
    offsets = np.cumsum(
        np.concatenate([[0], np.bincount(ords_s, minlength=V)])
    ).astype(np.int32)
    mask = rng.rand(N) < 0.3
    qs = [5.0, 50.0, 95.0]
    out = ops_aggs.masked_ordinal_percentiles(
        jnp.asarray(offsets), jnp.asarray(docs_s), jnp.asarray(vals_s),
        jnp.asarray(mask), np.arange(V, dtype=np.int32), qs)
    for o in range(V):
        mv = np.sort(vals[(ords == o) & mask[docs]])
        n = len(mv)
        for qi, q in enumerate(qs):
            if n == 0:
                assert np.isnan(out[o, qi])
                continue
            pos = min(max(q / 100 * n - 0.5, 0.0), n - 1.0)
            lo = int(np.floor(pos))
            hi = min(lo + 1, n - 1)
            frac = pos - lo
            ref = (1 - frac) * mv[lo] + frac * mv[hi]
            assert abs(out[o, qi] - ref) < 1e-3


@pytest.mark.parametrize("query", [None, {"match": {"body": "common"}}])
def test_date_histogram_device_matches_host(searcher, query, monkeypatch):
    """Fixed-interval no-tz date_histogram reuses the histogram bucket-id
    plane: device counts AND reconstructed epoch-millis keys are
    bitwise-identical to the host floor/multiply path."""
    spec = {"d": {"date_histogram": {"field": "ts",
                                     "fixed_interval": "1h"}}}
    host = _run(searcher, spec, query)
    assert sum(b["doc_count"]
               for b in host["d"]["buckets"]) > 0
    monkeypatch.setattr(ops_aggs, "DEVICE_MIN_PAIRS", 1)
    dev = _run(searcher, spec, query)
    assert dev == host


def test_hll_register_kernel_matches_host_twin(searcher):
    """masked_register_max vs the numpy maximum.at twin over the same
    cached (register, rho)-sorted pairs: integer max is
    order-independent, so the register arrays are bitwise-equal."""
    import jax.numpy as jnp
    seg = searcher.segments[0]
    rng = np.random.RandomState(11)
    for field in ("price", "tag"):
        pairs = ops_aggs.hll_sketch_pairs(seg, field)
        assert pairs["n_pairs"] == seg.n_docs
        for density in (0.0, 0.3, 1.0):
            mask = np.zeros(seg.n_pad, bool)
            mask[: seg.n_docs] = rng.rand(seg.n_docs) < density \
                if density < 1.0 else True
            dev = np.asarray(ops_aggs.masked_register_max(
                pairs["off_dev"], pairs["docs_dev"], pairs["rhos_dev"],
                jnp.asarray(mask)))[: pairs["m"]]
            np.testing.assert_array_equal(
                dev, ops_aggs.host_register_max(pairs, mask))


def test_hll_merge_add_estimate():
    """Register merge is max-commutative; folding raw values through the
    scalar hash equals sketching them in one pass; the estimate tracks
    the true distinct count in the linear-counting regime."""
    m = 1 << ops_aggs.HLL_P
    vals_a = [f"v{i}" for i in range(800)]
    vals_b = [f"v{i}" for i in range(400, 1200)]
    ra = ops_aggs.hll_add_values(np.zeros(m, np.int32), vals_a,
                                 ops_aggs.HLL_P)
    rb = ops_aggs.hll_add_values(np.zeros(m, np.int32), vals_b,
                                 ops_aggs.HLL_P)
    merged = ops_aggs.hll_merge(ra, rb)
    np.testing.assert_array_equal(merged, ops_aggs.hll_merge(rb, ra))
    one_pass = ops_aggs.hll_add_values(
        np.zeros(m, np.int32), vals_a + vals_b, ops_aggs.HLL_P)
    np.testing.assert_array_equal(merged, one_pass)
    est = ops_aggs.hll_estimate(merged)
    assert abs(est - 1200) <= 0.02 * 1200


def test_cardinality_exact_and_hll_regimes(searcher, monkeypatch):
    """Below precision_threshold cardinality stays an exact set union;
    above it both segments collect HLL sketches (the regime keys off the
    cached per-segment distinct count, so every route picks the same
    representation) and the device register kernel changes nothing."""
    exact = _run(searcher, {"c": {"cardinality": {"field": "tag"}}})
    assert exact == {"c": {"value": 12}}
    true_prices = _run(searcher, {"c": {"cardinality": {
        "field": "price"}}})["c"]["value"]
    spec = {"c": {"cardinality": {"field": "price",
                                  "precision_threshold": 10}}}
    host = _run(searcher, spec)
    monkeypatch.setattr(ops_aggs, "DEVICE_MIN_PAIRS", 1)
    dev = _run(searcher, spec)
    assert dev == host
    # ~100 distincts at m=2^14 sits in linear counting: near-exact
    assert abs(host["c"]["value"] - true_prices) <= 3


def test_batched_blockwise_topk_exact():
    """the group-maxima selection is bit-identical to plain lax.top_k,
    including boundary shapes and ascending-index tie-break
    (tests/test_topk_selection.py holds the property test)."""
    import jax.numpy as jnp
    from jax import lax
    from elasticsearch_tpu.ops.topk import batched_blockwise_topk

    rng = np.random.RandomState(3)
    for B, n, k in ((2, 65536, 100), (1, 16384, 10),
                    (2, 4096, 100),    # k groups pass a quarter: sorted whole
                    (3, 512, 600),     # k > n
                    (2, 1000, 5),      # no group divides n
                    (1, 512, 5)):
        scores = jnp.asarray(
            rng.randint(0, 50, (B, n)).astype(np.float32))
        want_v, want_i = lax.top_k(scores, min(k, n))
        got_v, got_i = batched_blockwise_topk(scores, k)
        np.testing.assert_array_equal(np.asarray(want_v),
                                      np.asarray(got_v))
        # heavy ties (values 0..49 over 4096 slots): index agreement
        # proves the block-major tie-break equals top_k's global
        # lowest-index preference
        np.testing.assert_array_equal(np.asarray(want_i),
                                      np.asarray(got_i))
