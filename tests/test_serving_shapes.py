"""The text plane states its programs: a served bag batch runs at a shape
that is a function of its padded batch and the packed plane, never of
its bags. One list (``DistributedSearchPlane.serving_shapes``), read by
``serve()`` and by the micro-batcher's warm-up.

Everything here runs the jitted path on XLA:CPU (``_host_csr = None``),
the code a chip node runs, on one tiny plane with a dense tier so that
the list is a handful of compiles."""

import jax
import numpy as np
import pytest

from elasticsearch_tpu.common import telemetry
from elasticsearch_tpu.parallel import (DistributedKnnPlane,
                                        DistributedSearchPlane,
                                        make_search_mesh)
from elasticsearch_tpu.search.microbatch import (KnnPlaneMicroBatcher,
                                                 PlaneMicroBatcher)
from elasticsearch_tpu.utils.synth import synthetic_csr_corpus_fast

MAX_BATCH = 4
K = 10
VOCAB = 384


def _mesh():
    return make_search_mesh(n_shards=1, n_replicas=1,
                            devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def served():
    """(plane, batcher, batches): a 256-doc Zipf plane whose every term
    of df > 1 is dense, warmed through the batcher's own warm-up, and 240
    seeded batches of bags (1-8 distinct terms, heads and tails, 1-4
    requests)."""
    rng = np.random.RandomState(7)
    corpus = synthetic_csr_corpus_fast(rng, 256, VOCAB, 8, zipf_s=1.2)
    corpus["term_ids"] = {f"t{t}": t for t in range(VOCAB)}
    plane = DistributedSearchPlane(_mesh(), [corpus], field="body",
                                   dense_threshold=1)
    plane._host_csr = None          # the jitted path, as on a chip
    assert plane.T_pad > 0 and plane.n_dense < VOCAB    # both tiers live
    batcher = PlaneMicroBatcher(plane, max_batch=MAX_BATCH)
    batcher.warmup(ks=(K,), sync=True)
    draw = np.random.default_rng(32)
    batches = []
    for i in range(240):
        batch = []
        for _ in range(1 + i % MAX_BATCH):
            n = int(draw.integers(1, 9))
            # heads (dense rows), tails (sparse runs), and terms the
            # corpus never saw
            pool = (0, 12) if draw.random() < 0.4 else (0, VOCAB + 4)
            ids = draw.choice(np.arange(*pool), size=min(n, pool[1]),
                              replace=False)
            batch.append([f"t{t}" for t in ids])
        batches.append(batch)
    return plane, batcher, batches


def _pad(batch):
    b_pad = 1 << max(0, (len(batch) - 1).bit_length())
    return batch + [[]] * (b_pad - len(batch))


def test_every_served_batch_runs_a_listed_program(served, monkeypatch):
    plane, batcher, batches = served
    listed = plane.serving_shapes([batcher._k_bucket(K)], MAX_BATCH)
    asked = []
    real = plane._get_step

    def spy(Q, L, k, *, tiered=False, with_count=False, U=None):
        asked.append((Q, L, k, tiered, with_count, U))
        return real(Q, L, k, tiered=tiered, with_count=with_count, U=U)

    monkeypatch.setattr(plane, "_get_step", spy)
    seen = set()
    for batch in batches:
        padded = _pad(batch)
        plane.serve(padded, k=batcher._k_bucket(K), with_totals=True)
        shape = (len(padded), asked[-1])
        assert shape in listed, f"{shape} is not in {listed}"
        seen.add(shape)
    assert len(asked) == len(batches)
    # the list is closed AND tight: no member is stated that no batch ran
    assert seen == set(listed)


def test_no_compile_after_the_batchers_warmup(served):
    plane, batcher, batches = served
    listed = plane.serving_shapes([batcher._k_bucket(K)], MAX_BATCH)
    assert batcher.warmed_shapes == len(listed)
    before = telemetry.compile_count()
    for batch in batches:
        plane.serve(_pad(batch), k=batcher._k_bucket(K), with_totals=True)
    for batch in batches[:8]:
        for bag in batch:
            batcher.search(bag, K)
    assert telemetry.compile_count() == before, \
        "a served bag batch compiled a program the warm-up did not"


def test_stated_shapes_answer_like_the_free_sizing(served):
    plane, batcher, batches = served
    for batch in batches[::6]:
        vals, hits, totals = plane.serve(batch, k=K, with_totals=True)
        rvals, rhits, rtotals = plane.search(batch, k=K, Q=None, L=None,
                                             with_totals=True)
        assert totals == rtotals
        np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals),
                                   rtol=1e-5, atol=1e-6)
        for row, rrow, v in zip(hits, rhits, np.asarray(rvals)):
            assert len(row) == len(rrow)
            # hits agree wherever the scores do not tie
            for j, (h, rh) in enumerate(zip(row, rrow)):
                tied = (j > 0 and np.isclose(v[j], v[j - 1], rtol=1e-5)) \
                    or (j + 1 < len(row)
                        and np.isclose(v[j], v[j + 1], rtol=1e-5))
                assert h == rh or tied


def test_a_repacked_plane_states_the_same_list(served):
    plane, _batcher, _batches = served
    again = DistributedSearchPlane.from_packed(_mesh(),
                                               plane.export_packed())
    for kbs, max_b in (([16], MAX_BATCH), ([16, 128], 64)):
        assert again.serving_shapes(kbs, max_b) == \
            plane.serving_shapes(kbs, max_b)
    # a function of its arguments and of pack-time constants alone
    Q, L, k, tiered, with_count, U = plane.serving_shape(4, 16)
    assert (Q, k, tiered, with_count) == \
        (plane.SERVING_Q_MIN, 16, True, True)
    assert L == plane.L_cap
    assert U == plane.T_pad         # the whole dense tier streams


def test_a_wide_query_opens_q_and_nothing_else(served):
    plane, _batcher, _batches = served
    wide = [[f"t{t}" for t in range(20, 31)]]          # 11 distinct terms
    assert plane.serving_q(wide) == 16
    assert plane.serving_q([["t1"] * 9]) == plane.SERVING_Q_MIN
    key = plane.serving_shape(4, 16, Q=16)
    base = plane.serving_shape(4, 16)
    assert key[0] == 16 and key[1:] == base[1:]


def test_knn_plane_lists_its_exact_scans():
    vecs = np.random.default_rng(3).standard_normal((64, 8)) \
        .astype(np.float32)
    plane = DistributedKnnPlane(_mesh(), [dict(vectors=vecs)],
                                similarity="cosine")
    batcher = KnnPlaneMicroBatcher(plane)
    listed = plane.serving_shapes([batcher._k_bucket(100)],
                                  batcher.max_batch)
    assert listed == [(b, (128,)) for b in (1, 2, 4, 8, 16, 32, 64)]
    assert len(list(batcher._warm_lattice((100,), batcher.max_batch))) == 7


def test_short_rungs_are_for_the_smallest_batches_only():
    """A plane whose L ladder has two rungs (L_cap 2048): a padded batch
    of at most SHORT_RUNG_MAX_B picks the rung its longest sparse run
    needs, every larger batch runs at L_cap whatever its bags hold, and
    the list states the short rungs for those smallest batches alone."""
    rng = np.random.RandomState(11)
    corpus = synthetic_csr_corpus_fast(rng, 8192, 2048, 8, zipf_s=1.2)
    corpus["term_ids"] = {f"t{t}": t for t in range(2048)}
    plane = DistributedSearchPlane(_mesh(), [corpus], field="body",
                                   dense_threshold=2048)
    plane._host_csr = None
    assert plane.ladder_rungs() == [1024, 2048] == [1024, plane.L_cap]
    assert plane.SHORT_RUNG_MAX_B == 2
    listed = plane.serving_shapes([16], 4)
    assert [(b, key[1]) for b, key in listed] == [
        (1, 1024), (1, 2048), (2, 1024), (2, 2048), (4, 2048)]
    sparse_df = plane.shards[0]["sparse_df"]
    long_t = f"t{int(np.argmax(sparse_df))}"
    short_t = f"t{int(np.flatnonzero((sparse_df > 0) & (sparse_df < 64))[0])}"
    assert plane.max_run_len([[long_t]]) > 1024 >= \
        plane.max_run_len([[short_t]])
    asked = []
    real = plane._get_step

    def spy(Q, L, k, **kw):
        asked.append(L)
        return real(Q, L, k, **kw)

    plane._get_step = spy
    for batch, want in (([[short_t]], 1024), ([[long_t]], 2048),
                        ([[short_t], [short_t, "t1"]], 1024),
                        ([[short_t]] * 3 + [[]], 2048)):
        plane.serve(batch, k=16, with_totals=True)
        assert asked[-1] == want, (batch, asked[-1])
