"""Serving-pipeline tests (search/microbatch.py rebuild): dispatcher-thread
micro-batching — bucket selection, the k-bucket starvation bound, ≥32-thread
mixed-shape stress, error fan-out scoped to exactly the failed batch —
plus the plane-path request cache and per-stage serving observability."""

import json
import tempfile
import threading
import time

import numpy as np
import pytest

from elasticsearch_tpu.search.microbatch import (PlaneMicroBatcher, _Slot,
                                                 batched_search)


class FakePlane:
    """Deterministic plane: query [i, ...] scores i - 0.01*j at rank j,
    hit (0, i + j); total is i + 1000. Records each dispatch's query ids."""

    def __init__(self, dispatch_s=0.0):
        self.batches = []
        self.dispatch_s = dispatch_s
        self.lock = threading.Lock()

    def serving_shapes(self, k_buckets, max_b):
        """The programs this plane states it serves: one a (padded
        batch, k-bucket)."""
        return [(1 << i, (kb,)) for i in range(max_b.bit_length())
                for kb in k_buckets]

    def warm_shape(self, shape):
        b_pad, (kb,) = shape
        self.search([[]] * b_pad, k=kb, with_totals=True)

    def search(self, queries, k=10, L=None, tiered=None, with_totals=False):
        real = [q for q in queries if len(q)]     # drop pow2 padding slots
        with self.lock:
            self.batches.append([int(q[0]) for q in real])
        if self.dispatch_s:
            time.sleep(self.dispatch_s)
        vals = [[float(q[0]) - 0.01 * j for j in range(k)]
                if len(q) else [] for q in queries]
        hits = [[(0, int(q[0]) + j) for j in range(k)]
                if len(q) else [] for q in queries]
        totals = [int(q[0]) + 1000 if len(q) else 0 for q in queries]
        return vals, hits, totals


# ---------------------------------------------------------------------------
# bucket selection + starvation bound
# ---------------------------------------------------------------------------


def test_minority_bucket_dispatches_within_bounded_rounds():
    """Regression (k-bucket starvation): a queued slot whose bucket never
    matches the popular bucket must still be dispatched within
    STARVATION_ROUNDS + 1 rounds, even when the popular bucket refills
    every round."""
    b = PlaneMicroBatcher(FakePlane())
    minority = _Slot([99], k=4)                 # bucket 4
    rounds = 0
    with b._cond:
        b._queue.append(minority)
        while True:
            # the popular bucket (k=10 → 16) never drains
            b._queue.extend(_Slot([i], k=10) for i in range(3))
            batch = b._take_batch_locked()
            rounds += 1
            if minority in batch:
                break
            assert rounds <= PlaneMicroBatcher.STARVATION_ROUNDS + 1, \
                "minority-bucket slot starved past the bound"
    assert b.n_starved_dispatches >= 1


def test_starved_bucket_served_under_live_flood():
    """End-to-end: one lone k=100 request completes while six threads
    flood the k=10 bucket continuously."""
    plane = FakePlane(dispatch_s=0.005)
    b = PlaneMicroBatcher(plane)
    stop = threading.Event()

    def flood(tid):
        while not stop.is_set():
            b.search([tid], k=10)

    floods = [threading.Thread(target=flood, args=(i,)) for i in range(6)]
    for t in floods:
        t.start()
    try:
        t0 = time.perf_counter()
        vals, hits, total = b.search([77], k=100)
        dt = time.perf_counter() - t0
    finally:
        stop.set()
        for t in floods:
            t.join()
    assert vals[0] == 77.0 and total == 1077
    assert dt < 5.0


def test_deep_queue_coalesces_across_buckets():
    """A queue deeper than one full batch dispatches across k-buckets at
    the max-k shape instead of leaving small buckets behind."""
    b = PlaneMicroBatcher(FakePlane(), max_batch=4)
    with b._cond:
        for i in range(6):
            b._queue.append(_Slot([i], k=2 if i % 2 else 10))
        batch = b._take_batch_locked()
    assert len(batch) == 4
    assert len({b._k_bucket(s.k) for s in batch}) > 1
    assert b.n_coalesced_dispatches == 1


# ---------------------------------------------------------------------------
# concurrency stress
# ---------------------------------------------------------------------------


def test_stress_32_threads_mixed_shapes_every_result_correct():
    """≥32 concurrent clients with mixed k and term counts: every request
    gets its OWN correct top-k (length, scores, hits, total), and the
    batcher's locked counters stay exact."""
    plane = FakePlane(dispatch_s=0.002)
    b = PlaneMicroBatcher(plane)
    out, errs = {}, []
    lock = threading.Lock()

    def go(i):
        k = 1 + (i % 7)
        terms = [i] * (1 + i % 3)          # mixed term counts
        try:
            vals, hits, total = b.search(terms, k=k)
            with lock:
                out[i] = (k, vals, hits, total)
        except Exception as e:              # noqa: BLE001
            with lock:
                errs.append(e)

    threads = [threading.Thread(target=go, args=(i,)) for i in range(48)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert len(out) == 48
    for i in range(48):
        k, vals, hits, total = out[i]
        assert len(vals) == k and len(hits) == k
        assert list(vals) == [float(i) - 0.01 * j for j in range(k)]
        assert list(hits) == [(0, i + j) for j in range(k)]
        assert total == i + 1000
    assert b.n_queries == 48
    assert b.n_dispatches == len(plane.batches)
    assert sum(len(bt) for bt in plane.batches) == 48


def test_dispatch_error_fans_out_to_exactly_the_failed_batch():
    """A dispatch error reaches every query of the FAILED batch and no
    other — queued survivors dispatch normally afterwards."""

    class Boom(FakePlane):
        def __init__(self):
            super().__init__(dispatch_s=0.01)
            self.failed_ids = None

        def search(self, queries, k=10, L=None, tiered=None,
                   with_totals=False):
            with self.lock:
                first = self.failed_ids is None
                if first:
                    self.failed_ids = [int(q[0]) for q in queries
                                       if len(q)]
            if first:
                time.sleep(0.01)
                raise RuntimeError("kernel exploded")
            return super().search(queries, k, L, tiered, with_totals)

    plane = Boom()
    b = PlaneMicroBatcher(plane)
    errs, oks = [], []
    lock = threading.Lock()

    def go(i):
        try:
            vals, _hits, _total = b.search([i], k=1)
            with lock:
                oks.append(int(vals[0]))
        except RuntimeError:
            with lock:
                errs.append(i)

    threads = [threading.Thread(target=go, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert plane.failed_ids, "a dispatch should have failed"
    assert sorted(errs) == sorted(plane.failed_ids)
    assert sorted(oks) == sorted(set(range(16)) - set(plane.failed_ids))
    # batcher still serves after the failure
    vals, _h, _t = b.search([3], k=1)
    assert vals[0] == 3.0


# ---------------------------------------------------------------------------
# per-stage observability + warmup
# ---------------------------------------------------------------------------


def test_per_request_stage_timings_and_stats_doc():
    plane = FakePlane(dispatch_s=0.01)
    b = PlaneMicroBatcher(plane)
    stages = {}
    b.search([5], k=2, stages=stages)
    assert set(stages) == {"queue", "prep", "dispatch", "fetch"}
    assert all(v >= 0.0 for v in stages.values())
    assert stages["dispatch"] >= 5.0        # the 10 ms sleep is dispatch
    pct = b.stage_percentiles()
    assert pct["dispatch"]["p99_ms"] >= 5.0 and pct["queue"]["n"] == 1
    doc = b.stats_doc()
    assert doc["dispatches"] == 1 and doc["queries"] == 1
    assert doc["dispatch_time_in_millis"] >= 5


def test_warmup_compiles_the_lattice_off_the_serving_path():
    plane = FakePlane()
    b = PlaneMicroBatcher(plane, max_batch=8)
    b.warmup(ks=(10,), sync=True)
    # the plane's own list: B ∈ {1,2,4,8} × one k bucket
    assert b.warmed_shapes == 4 == len(plane.serving_shapes([16], 8))
    assert all(bt == [] for bt in plane.batches)    # pad-only dispatches
    assert b.n_dispatches == 0                      # not serving traffic
    # a host-serving plane (CPU backend) has nothing to pre-compile
    plane._host_csr = [object()]
    b2 = PlaneMicroBatcher(plane)
    assert b2.warmup(sync=True) is None and b2.warmed_shapes == 0


def test_retired_batcher_stops_warmup_but_still_serves():
    plane = FakePlane()
    b = PlaneMicroBatcher(plane, max_batch=8)
    b.retire()                     # plane superseded before warmup ran
    b.warmup(ks=(10,), sync=True)
    assert b.warmed_shapes == 0    # no compiles for an orphaned plane
    # a late request through a stale reference still serves
    vals, _h, _t = b.search([4], k=1)
    assert vals[0] == 4.0


def test_plane_rebuild_retires_old_batcher():
    from elasticsearch_tpu.search.plane_route import ServingPlaneCache
    old = FakePlane()
    ServingPlaneCache._attach_batcher(old)
    assert old._microbatcher._retired is False
    ServingPlaneCache._retire(old)
    assert old._microbatcher._retired is True


# ---------------------------------------------------------------------------
# plane-path request cache + nodes-stats wiring
# ---------------------------------------------------------------------------


@pytest.fixture()
def text_index():
    from elasticsearch_tpu.node.indices_service import IndicesService
    with tempfile.TemporaryDirectory() as d:
        inds = IndicesService(d)
        svc = inds.create_index("pc", mappings={
            "properties": {"body": {"type": "text"}}})
        for i in range(8):
            svc.index_doc(str(i), {"body": f"quick fox doc{i}"})
        svc.refresh()
        yield svc


def test_plane_request_cache_identical_bodies(text_index):
    svc = text_index
    body = {"query": {"match": {"body": "quick"}}}
    r1 = svc.search(body)
    assert svc.plane_cache_stats == {"hit_count": 0, "miss_count": 1}
    r2 = svc.search(body)
    assert svc.plane_cache_stats["hit_count"] == 1
    assert [h.doc_id for h in r2.hits] == [h.doc_id for h in r1.hits]
    assert [h.score for h in r2.hits] == [h.score for h in r1.hits]
    assert r2.total == r1.total
    # served hits are fresh shells: coordinator-style in-place mutation
    # must not corrupt the cached entry
    assert r2.hits[0] is not r1.hits[0]
    r2.hits[0].score = -1.0
    r2.hits[0].sort_values = ["mutated"]
    r3 = svc.search(body)
    assert r3.hits[0].score == r1.hits[0].score
    assert r3.hits[0].sort_values == r1.hits[0].sort_values


def test_plane_request_cache_invalidates_on_new_segment(text_index):
    svc = text_index
    body = {"query": {"match": {"body": "quick"}}}
    r1 = svc.search(body)
    svc.index_doc("new", {"body": "quick fresh"})
    svc.refresh()
    r2 = svc.search(body)
    assert svc.plane_cache_stats["miss_count"] == 2
    assert r2.total == r1.total + 1


def test_plane_request_cache_skips_ineligible_and_opted_out(text_index):
    svc = text_index
    # explicit opt-out dispatches every time
    body = {"query": {"match": {"body": "quick"}}}
    svc.search(body, request_cache=False)
    svc.search(body, request_cache=False)
    assert svc.plane_cache_stats == {"hit_count": 0, "miss_count": 0}
    # non-plane shapes (match_all, sort) never enter the plane cache
    svc.search({"query": {"match_all": {}}})
    svc.search({"query": {"match": {"body": "quick"}},
                "sort": [{"_doc": "asc"}]})
    assert svc.plane_cache_stats == {"hit_count": 0, "miss_count": 0}


def test_plane_serving_stats_surface(text_index):
    svc = text_index
    body = {"query": {"match": {"body": "quick fox"}}}
    svc.search(body)
    svc.search(body)
    st = svc.stats()
    ps = st["plane_serving"]
    assert ps["dispatches"] >= 1 and ps["queries"] >= 1
    assert ps["cache_hit_count"] == 1 and ps["cache_miss_count"] == 1
    assert ps["dispatch_time_in_millis"] >= 0
    assert ps["max_batch"] >= 1


def test_nodes_stats_exposes_plane_serving():
    from elasticsearch_tpu.node.indices_service import IndicesService
    from elasticsearch_tpu.rest.api import RestAPI
    with tempfile.TemporaryDirectory() as d:
        api = RestAPI(IndicesService(d))
        api.handle("PUT", "/ns", "", json.dumps(
            {"mappings": {"properties": {"body": {"type": "text"}}}}
        ).encode())
        api.handle("PUT", "/ns/_doc/1", "refresh=true",
                   json.dumps({"body": "quick brown fox"}).encode())
        api.handle("POST", "/ns/_search", "", json.dumps(
            {"query": {"match": {"body": "quick"}}}).encode())
        st, _ct, payload = api.handle("GET", "/_nodes/stats", "", b"")
        assert st == 200
        node = next(iter(json.loads(payload)["nodes"].values()))
        ps = node["indices"]["plane_serving"]
        assert ps["dispatches"] >= 1 and ps["queries"] >= 1
        # the per-stage totals are present (attributable regressions)
        for k in ("queue_time_in_millis", "prep_time_in_millis",
                  "dispatch_time_in_millis", "fetch_time_in_millis"):
            assert k in ps


def test_serving_stages_stamped_on_plane_served_results(text_index):
    svc = text_index
    r = svc.search({"query": {"match": {"body": "quick"}}},
                   request_cache=False)
    assert r.serving_stages is not None
    assert set(r.serving_stages) == {"queue", "prep", "dispatch", "fetch"}
    # per-segment path results carry none
    r2 = svc.search({"query": {"match_all": {}}})
    assert r2.serving_stages is None
