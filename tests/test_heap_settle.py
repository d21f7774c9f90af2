"""``common/heap.py``: the node's resident heap leaves the cyclic collector's
reach when it is installed, only in a process its owner armed; the
collector's passes are counted and surface in ``GET /_nodes/stats`` and the
``es_gc_*`` families (PR 33)."""

import gc
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request
import weakref

import pytest

from elasticsearch_tpu.common import heap, telemetry
from elasticsearch_tpu.node.indices_service import IndicesService
from elasticsearch_tpu.rest.api import RestAPI

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILIES = ("es_gc_collections_total", "es_gc_pause_millis_total",
            "es_gc_settles_total", "es_gc_frozen_objects")


@pytest.fixture
def armed():
    """The process as ``cli.node.main`` leaves it, for one test."""
    heap.arm()
    try:
        yield heap
    finally:
        heap.disarm()


@pytest.fixture
def my_settles(armed, monkeypatch):
    """How often *this* thread has called ``settle()``: the arming is the
    process's, and a thread an earlier test of this worker left behind may
    install a plane of its own meanwhile."""
    callers = []
    settle = heap.settle

    def counted():
        callers.append(threading.get_ident())
        settle()
    monkeypatch.setattr(heap, "settle", counted)
    return lambda: callers.count(threading.get_ident())


class _Passes:
    """The collector's passes, seen by a hook of the test's own."""

    def __init__(self):
        self.generations = []

    def __call__(self, phase, info):
        if phase == "start":
            self.generations.append(info["generation"])

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def _settles() -> int:
    return int(sum(v for _, v in _family("es_gc_settles_total")))


def _family(name):
    fam = telemetry.DEFAULT.stats_doc().get(name)
    return [] if fam is None else [
        (s["labels"], s["value"]) for s in fam["series"]]


def _by_generation(name) -> dict:
    return {labels["generation"]: v for labels, v in _family(name)}


def _handle(api, method, path, body=None, query=""):
    status, _, out = api.handle(
        method, path, query,
        None if body is None else json.dumps(body).encode())
    assert status in (200, 201), out
    return json.loads(out)


def _nodes_stats(api) -> dict:
    return next(iter(_handle(api, "GET", "/_nodes/stats")["nodes"].values()))


MAPPINGS = {"properties": {"body": {"type": "text"},
                           "vec": {"type": "dense_vector", "dims": 4}}}


def _write_store(path) -> None:
    """An index of 40 documents, flushed and closed: what a restart finds."""
    api = RestAPI(IndicesService(path))
    _handle(api, "PUT", "/docs", {"mappings": MAPPINGS})
    for i in range(40):
        _handle(api, "PUT", f"/docs/_doc/{i}",
                {"body": f"quick brown fox {i}", "vec": [1, i, 0, 0]})
    _handle(api, "POST", "/docs/_flush")
    api.indices.close()


# -- the owner decides -------------------------------------------------------

def test_unarmed_settle_is_inert():
    frozen = gc.get_freeze_count()
    with _Passes() as seen:
        heap.settle()
    assert seen.generations == []           # no pass of any generation
    assert gc.get_freeze_count() == frozen
    assert gc.get_count()[0] > 0            # a full pass would have reset it
    assert heap.collectors_doc() == {}
    assert all(not _family(f) for f in FAMILIES)


def test_unarmed_library_use_never_settles(tmp_path):
    """A test suite or an embedding program builds indices and planes in a
    process that is not a node: its heap is left alone."""
    _write_store(str(tmp_path))
    frozen = gc.get_freeze_count()
    api = RestAPI(IndicesService(str(tmp_path)))
    _handle(api, "PUT", "/docs", {"mappings": MAPPINGS})        # recovers
    r = _handle(api, "POST", "/docs/_search",
                {"query": {"match": {"body": "quick"}}})        # packs
    assert r["hits"]["total"]["value"] == 40
    assert gc.get_freeze_count() == frozen
    assert _nodes_stats(api)["jvm"]["gc"]["collectors"] == {}
    api.indices.close()


def test_arm_is_idempotent_and_disarm_undoes_it():
    hooks = len(gc.callbacks)
    heap.arm()
    heap.arm()
    try:
        assert heap.collectors_doc() and len(gc.callbacks) == hooks + 1
        heap.settle()
        assert gc.get_freeze_count() > 1000
    finally:
        heap.disarm()
    heap.disarm()
    assert heap.collectors_doc() == {} and len(gc.callbacks) == hooks
    assert gc.get_freeze_count() == 0       # back in the collector's reach
    assert all(not _family(f) for f in FAMILIES)


# -- what settle() does ------------------------------------------------------

def test_settled_heap_is_out_of_a_later_pass(armed):
    resident = [{"n": i, "peer": [i]} for i in range(50_000)]
    heap.settle()
    frozen = gc.get_freeze_count()
    assert frozen >= 100_000                # a dict and a list an entry
    # what a later full pass walks: what was allocated since, not the heap
    assert len(gc.get_objects()) < frozen // 10
    t0 = time.perf_counter()
    gc.collect()
    after_s = time.perf_counter() - t0
    gc.unfreeze()
    t0 = time.perf_counter()
    gc.collect()
    whole_s = time.perf_counter() - t0
    assert after_s < whole_s
    assert len(resident) == 50_000


def test_cycle_retired_after_a_settle_is_freed_by_the_next(armed):
    """No leak across a repack: a frozen generation that dies comes back
    into reach before the next freeze."""
    class Generation:
        pass
    old = Generation()
    old.me = old                            # a cycle: only a pass frees it
    died = weakref.ref(old)
    heap.settle()                           # installed, and frozen
    del old                                 # a repack retires it
    gc.collect()
    assert died() is not None               # frozen: a plain pass misses it
    heap.settle()                           # the next install
    assert died() is None


@pytest.mark.parametrize("generation,name", [(0, "young"), (1, "young"),
                                             (2, "old")])
def test_passes_are_counted_by_generation(armed, tmp_path, generation, name):
    api = RestAPI(IndicesService(str(tmp_path)))
    before = _nodes_stats(api)["jvm"]["gc"]["collectors"]
    assert set(before) == {"young", "old"}
    g = str(generation)
    counts_before = _by_generation("es_gc_collections_total")
    pauses_before = _by_generation("es_gc_pause_millis_total")
    junk = [[i] for i in range(200_000)]    # a pass worth a millisecond
    for _ in range(3):
        gc.collect(generation)
    del junk
    after = _nodes_stats(api)["jvm"]["gc"]["collectors"]
    assert after[name]["collection_count"] \
        >= before[name]["collection_count"] + 3
    assert after[name]["collection_time_in_millis"] \
        >= before[name]["collection_time_in_millis"]
    counts = _by_generation("es_gc_collections_total")
    assert set(counts) == {"0", "1", "2"}
    assert counts[g] >= counts_before[g] + 3
    assert _by_generation("es_gc_pause_millis_total")[g] > pauses_before[g]


def test_families_in_the_text_exposition(armed, tmp_path):
    heap.settle()
    api = RestAPI(IndicesService(str(tmp_path)))
    status, _, text = api.handle("GET", "/_prometheus/metrics", "", None)
    assert status == 200
    text = text if isinstance(text, str) else text.decode()
    for fam in FAMILIES:
        assert re.search(rf"^# TYPE {fam} (counter|gauge)$", text, re.M), fam
    assert re.search(r'^es_gc_collections_total\{generation="2"\} [1-9]',
                     text, re.M)
    frozen = re.search(r"^es_gc_frozen_objects ([0-9.e+]+)$", text, re.M)
    assert float(frozen.group(1)) > 1000
    assert re.search(r"^es_gc_settles_total [1-9]", text, re.M)


# -- where it is called ------------------------------------------------------

def test_recovery_and_cold_pack_settle_once_each_a_search_never(
        my_settles, tmp_path):
    _write_store(str(tmp_path))
    api = RestAPI(IndicesService(str(tmp_path)))
    counted = _settles()
    _handle(api, "PUT", "/fresh", {"mappings": MAPPINGS})
    assert my_settles() == 0                # an empty index installs nothing
    _handle(api, "PUT", "/docs", {"mappings": MAPPINGS})
    assert my_settles() == 1                # the shard's recovery
    assert _handle(api, "GET", "/docs/_count")["count"] == 40
    match = {"query": {"match": {"body": "quick"}}}
    knn = {"knn": {"field": "vec", "query_vector": [1, 2, 0, 0], "k": 3,
                   "num_candidates": 10}}
    assert _handle(api, "POST", "/docs/_search",
                   match)["hits"]["total"]["value"] == 40
    assert my_settles() == 2                # the text plane's cold pack
    assert len(_handle(api, "POST", "/docs/_search",
                       knn)["hits"]["hits"]) == 3
    assert my_settles() == 3                # the kNN plane's cold pack
    rebuilds = api.indices.get("docs").plane_cache.rebuild_stats()
    assert rebuilds["cold"] == 2
    for _ in range(20):
        _handle(api, "POST", "/docs/_search", match,
                query="request_cache=false")
        _handle(api, "POST", "/docs/_search", knn)
    assert my_settles() == 3                # a _search never
    assert _settles() >= counted + 3        # and the family counts them
    api.indices.close()


def test_batcher_warmup_settles_after_its_programs_loaded(my_settles):
    from elasticsearch_tpu.search.microbatch import PlaneMicroBatcher

    class Plane:
        warmed = []

        def serving_shapes(self, k_buckets, max_b):
            return [("shape", b) for b in (1, 2)]

        def warm_shape(self, shape):
            self.warmed.append(shape)

    batcher = PlaneMicroBatcher(Plane())
    batcher.warmup(sync=True)
    assert Plane.warmed == [("shape", 1), ("shape", 2)]
    assert batcher.warmed_shapes == 2
    assert my_settles() == 1


# -- the owner that arms it --------------------------------------------------

def test_node_main_arms_the_heap(tmp_path):
    """``python -m elasticsearch_tpu.cli.node`` is a node: its heap is
    settled, ``_nodes/stats`` says how often it paused, SIGTERM ends it."""
    _write_store(str(tmp_path))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO_ROOT)
    proc = subprocess.Popen(
        [sys.executable, "-m", "elasticsearch_tpu.cli.node", "--port",
         str(port), "--data", str(tmp_path), "--jax-platform", "cpu"],
        cwd=REPO_ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT)

    def call(method, path, body=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", method=method,
            data=None if body is None else json.dumps(body).encode(),
            headers={"content-type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.read()

    try:
        deadline = time.monotonic() + 120
        while True:
            assert proc.poll() is None, proc.stdout.read().decode()[-2000:]
            try:
                call("GET", "/")
                break
            except OSError:
                assert time.monotonic() < deadline, "node did not serve"
                time.sleep(0.25)
        call("PUT", "/docs", {"mappings": MAPPINGS})
        stats = next(iter(json.loads(
            call("GET", "/_nodes/stats"))["nodes"].values()))
        col = stats["jvm"]["gc"]["collectors"]
        assert col["old"]["collection_count"] >= 1      # recovery's settle
        assert col["young"]["collection_count"] >= 1
        prom = call("GET", "/_prometheus/metrics").decode()
        assert re.search(r"^es_gc_settles_total 1$", prom, re.M), prom[-800:]
        frozen = re.search(r"^es_gc_frozen_objects ([0-9.e+]+)$", prom, re.M)
        assert float(frozen.group(1)) > 10_000
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(10)
            pytest.fail("the node did not exit on SIGTERM within 30 s")
    assert proc.returncode == 0
