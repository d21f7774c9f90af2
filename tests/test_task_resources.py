"""Per-task resource attribution, health report, SLO exemplars
(node/task_manager.TaskResources, common/health.py, telemetry
exemplars): attribution sums reconcile with the micro-batcher's
dispatch totals, an in-flight plane search already shows non-zero
cpu/device in ``_tasks?detailed``, the coordinator rolls data-node
ledgers up across a 3-node fan-out, a forced sync-rebuild storm turns
``plane_serving`` red with a diagnosis, OpenMetrics exemplar escaping
conformance, the ``es_plane_swap_ms`` kind label, the ``GET /_trace``
listing, cluster hot-threads fan-out, and the TELEMETRY.md lint."""

import importlib.util
import json
import os
import re
import tempfile
import threading
import time

import pytest

from elasticsearch_tpu.common import telemetry
from elasticsearch_tpu.node.task_manager import (TaskResources,
                                                 bind_resources,
                                                 current_resources,
                                                 unbind_resources)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# TaskResources unit behavior
# ---------------------------------------------------------------------------


def test_task_resources_cpu_boundaries_and_merge():
    res = TaskResources()
    res.cpu_mark()
    # burn a little CPU so the checkpoint has something to fold
    x = 0
    for i in range(200_000):
        x += i * i
    res.cpu_checkpoint()
    first = res.cpu_ms
    assert first > 0
    res.cpu_release()
    # release folds the tail once and drops the mark: a further
    # checkpoint starts a fresh window instead of double counting
    res.cpu_checkpoint()
    assert res.cpu_ms == pytest.approx(res.cpu_ms)
    res.add(device_ms=2.5, h2d_bytes=100, d2h_bytes=50,
            docs_scanned=10, delta_docs_scanned=3, dispatches=1)
    other = TaskResources()
    other.merge_doc(res.to_dict())
    d = other.to_dict()
    assert d["device_time_ms"] == pytest.approx(2.5)
    assert d["transfer_bytes"] == {"h2d": 100, "d2h": 50}
    assert d["docs_scanned"] == 10 and d["delta_docs_scanned"] == 3
    assert d["cpu_time_ms"] == pytest.approx(res.to_dict()["cpu_time_ms"])


def test_resources_contextvar_bind_unbind():
    assert current_resources() is None
    res = TaskResources()
    tok = bind_resources(res)
    try:
        assert current_resources() is res
    finally:
        unbind_resources(tok)
    assert current_resources() is None


# ---------------------------------------------------------------------------
# single-node attribution through the REST stack
# ---------------------------------------------------------------------------


@pytest.fixture()
def api_with_index():
    from elasticsearch_tpu.node.indices_service import IndicesService
    from elasticsearch_tpu.rest.api import RestAPI
    with tempfile.TemporaryDirectory() as d:
        api = RestAPI(IndicesService(d))
        api.handle("PUT", "/attr", "", json.dumps(
            {"mappings": {"properties": {"body": {"type": "text"}}}}
        ).encode())
        for i, words in enumerate(("quick brown fox", "lazy dog",
                                   "quick red panda")):
            api.handle("PUT", f"/attr/_doc/{i}", "",
                       json.dumps({"body": words}).encode())
        api.handle("POST", "/attr/_refresh", "", b"")
        yield api


def test_attribution_sums_to_dispatch_totals(api_with_index):
    """Acceptance: per-task device attribution reconciles with the
    micro-batcher's own dispatch-stage totals, and docs scanned covers
    the corpus once per query."""
    api = api_with_index
    terms = ["quick", "brown", "fox", "lazy", "dog", "red", "panda"]
    n = len(terms)
    for t in terms:           # distinct bodies: no request-cache hits
        st, _ct, p = api.handle(
            "POST", "/attr/_search", "",
            json.dumps({"query": {"match": {"body": t}}}).encode())
        assert st == 200, p
    svc = api.indices.get("attr")
    gen = svc.plane_cache._planes["body"]
    batcher = gen._microbatcher
    totals = api.task_manager.action_totals()["indices:data/read/search"]
    # device_ms per task is its dispatch's wall time — identical to the
    # per-slot stage totals the batcher keeps, so the sums reconcile
    assert totals["device_ms"] == pytest.approx(
        batcher.stage_totals_ms["dispatch"], rel=0.05, abs=0.5)
    assert totals["dispatches"] == n
    assert totals["docs_scanned"] == n * 3     # full corpus per query
    # cpu_ms is >= 0 only: this kernel's thread_time ticks at 10ms, so
    # fast requests legitimately attribute 0 CPU (the in-flight test
    # covers non-zero CPU deterministically by burning a tick)
    assert totals["cpu_ms"] >= 0
    assert totals["count"] == n
    # the same numbers reach the registry's es_task_* families (other
    # tests' stacks may contribute same-labeled series to the process
    # registry — ours must be among them)
    snap = telemetry.DEFAULT.stats_doc()
    fam = snap["es_task_device_millis_total"]["series"]
    mine = [s for s in fam
            if s["labels"].get("action") == "indices:data/read/search"
            and s["labels"].get("node") == api.node_name]
    assert any(s["value"] == pytest.approx(totals["device_ms"],
                                           rel=0.05, abs=0.5)
               for s in mine), mine


def test_attribution_transfer_bytes_on_jitted_path(api_with_index):
    """Forcing the jitted dispatch (the TPU-shaped path) attributes
    per-dispatch h2d/d2h byte shares to the owning tasks."""
    api = api_with_index
    api.handle("POST", "/attr/_search", "", json.dumps(
        {"query": {"match": {"body": "quick"}}}).encode())
    svc = api.indices.get("attr")
    gen = svc.plane_cache._planes["body"]
    gen.base._host_csr = None          # CPU backend would serve host-eager
    before = api.task_manager.action_totals()[
        "indices:data/read/search"].get("h2d_bytes", 0)
    st, _ct, p = api.handle("POST", "/attr/_search", "", json.dumps(
        {"query": {"match": {"body": "panda"}}}).encode())
    assert st == 200, p
    totals = api.task_manager.action_totals()["indices:data/read/search"]
    assert totals["h2d_bytes"] > before
    assert totals["d2h_bytes"] > 0


def test_in_flight_task_shows_resources(monkeypatch):
    """Acceptance: ``_tasks?detailed`` reports non-zero cpu/device for a
    plane search that is STILL RUNNING (attribution lands at stage
    boundaries, not at request teardown)."""
    from elasticsearch_tpu.node.indices_service import IndicesService
    from elasticsearch_tpu.rest.api import RestAPI
    orig = RestAPI.h_search
    entered = threading.Event()
    release = threading.Event()

    def slow_h_search(self, params, body, index=None):
        # burn past this kernel's thread_time granularity (10ms ticks)
        # INSIDE the request, before the dispatch boundary, so the
        # boundary checkpoint deterministically folds non-zero CPU
        t0 = time.thread_time()
        x = 0
        while time.thread_time() - t0 < 0.025:
            x += 1
        out = orig(self, params, body, index=index)
        entered.set()
        release.wait(10)               # hold the task open, post-dispatch
        return out

    monkeypatch.setattr(RestAPI, "h_search", slow_h_search)
    with tempfile.TemporaryDirectory() as d:
        api = RestAPI(IndicesService(d))   # routes bind the patched handler
        api.handle("PUT", "/live", "", json.dumps(
            {"mappings": {"properties": {"body": {"type": "text"}}}}
        ).encode())
        api.handle("PUT", "/live/_doc/1", "refresh=true",
                   json.dumps({"body": "quick brown fox"}).encode())
        box = {}

        def client():
            box["resp"] = api.handle(
                "POST", "/live/_search", "",
                json.dumps({"query": {"match": {"body": "quick"}}}
                           ).encode())

        t = threading.Thread(target=client)
        t.start()
        try:
            assert entered.wait(10), "search never reached the handler"
            st, _ct, p = api.handle(
                "GET", "/_tasks",
                "detailed=true&actions=indices:data/read/search", b"")
            assert st == 200
            tasks = next(iter(json.loads(p)["nodes"].values()))["tasks"]
            in_flight = [tk for tk in tasks.values()
                         if tk["action"] == "indices:data/read/search"]
            assert in_flight, "the running search task is not listed"
            rs = in_flight[0]["resource_stats"]
            assert rs["cpu_time_ms"] > 0
            assert rs["device_time_ms"] > 0
            assert rs["docs_scanned"] >= 1
            assert rs["dispatches"] >= 1
        finally:
            release.set()
            t.join(10)
        assert box["resp"][0] == 200
        # without ?detailed the listing stays reference-lean
        st2, _c2, p2 = api.handle("GET", "/_tasks", "", b"")
        tasks2 = next(iter(json.loads(p2)["nodes"].values()))["tasks"]
        assert all("resource_stats" not in tk for tk in tasks2.values())


# ---------------------------------------------------------------------------
# health indicators
# ---------------------------------------------------------------------------


def test_health_report_green_shape(api_with_index):
    api = api_with_index
    st, _ct, p = api.handle("GET", "/_health_report", "", b"")
    assert st == 200
    doc = json.loads(p)
    assert doc["status"] in ("green", "yellow")
    assert set(doc["indicators"]) == {
        "shards_availability", "plane_serving", "plane_tiers",
        "compile_churn", "breakers", "indexing_pressure",
        "task_backlog", "slo_burn", "query_insights",
        "dispatch_efficiency", "qos"}
    for ind in doc["indicators"].values():
        assert ind["status"] in ("green", "yellow", "red", "unknown")
        assert ind["symptom"]
    # single-indicator route
    st2, _c2, p2 = api.handle(
        "GET", "/_health_report/plane_serving", "", b"")
    assert st2 == 200
    assert list(json.loads(p2)["indicators"]) == ["plane_serving"]
    # unknown indicator 404s
    st3, _c3, _p3 = api.handle("GET", "/_health_report/nope", "", b"")
    assert st3 == 404


def test_sync_rebuild_storm_turns_plane_serving_red(api_with_index):
    """Acceptance: disable delta-tier serving (the legacy rebuild-every-
    refresh behavior) and hammer index+refresh+search — the sync rebuild
    count rises past the cold builds and ``plane_serving`` goes red with
    a diagnosis naming the storming index."""
    from elasticsearch_tpu.common.health import HealthService
    api = api_with_index
    svc = api.indices.get("attr")
    svc.plane_cache.delta_enabled = False
    for i in range(HealthService.SYNC_REBUILD_RED + 2):
        api.handle("PUT", f"/attr/_doc/s{i}", "refresh=true",
                   json.dumps({"body": f"quick event {i}"}).encode())
        st, _ct, p = api.handle(
            "POST", "/attr/_search", "",
            json.dumps({"query": {"match": {"body": "quick"}}}).encode())
        assert st == 200, p
    st, _ct, p = api.handle("GET", "/_health_report", "", b"")
    doc = json.loads(p)
    ind = doc["indicators"]["plane_serving"]
    assert ind["status"] == "red"
    assert doc["status"] == "red"
    assert ind["details"]["sync_noncold_rebuilds"] >= \
        HealthService.SYNC_REBUILD_RED
    assert "attr" in ind["details"]["storming_indices"]
    assert ind["diagnosis"] and ind["diagnosis"][0]["action"]
    assert "attr" in ind["diagnosis"][0]["affected_resources"]["indices"]
    assert ind["impacts"] and ind["impacts"][0]["impact_areas"]


def test_monitoring_collects_health_doc(api_with_index):
    api = api_with_index
    api.monitoring.collect()
    api.handle("POST", "/.monitoring-es-8-*/_refresh", "", b"")
    st, _ct, p = api.handle(
        "POST", "/.monitoring-es-8-*/_search", "",
        json.dumps({"size": 50}).encode())
    assert st == 200
    hits = json.loads(p)["hits"]["hits"]
    hdoc = next(h["_source"] for h in hits
                if h["_source"]["type"] == "health_report")
    assert hdoc["health_report"]["status"] in ("green", "yellow", "red")
    assert "plane_serving" in hdoc["health_report"]["indicators"]


# ---------------------------------------------------------------------------
# SLO exemplars: OpenMetrics conformance
# ---------------------------------------------------------------------------

_EXEMPLAR_LINE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*\{[^}]*quantile=\"0\.99\"[^}]*\}) "
    r"(-?[0-9.eE+]+)"
    r" # \{trace_id=\"((?:[^\"\\\n]|\\\\|\\\"|\\n)*)\"\} "
    r"(-?[0-9.eE+]+)$")


def test_exemplar_openmetrics_escaping_conformance():
    reg = telemetry.TelemetryRegistry()
    h = reg.histogram("lat_ms", {"stage": "dispatch"})
    for v in (1.0, 2.0, 3.0):
        h.observe(v, exemplar=f"trace{v}")
    # hostile exemplar value: escaping must keep the line parseable
    h.observe(99.0, exemplar='say "hi"\\x\nline2')
    text = reg.prometheus_text(exemplars=True)
    ex_lines = [ln for ln in text.splitlines() if " # {" in ln]
    assert len(ex_lines) == 1, text       # only the p99 line carries one
    m = _EXEMPLAR_LINE.match(ex_lines[0])
    assert m, f"malformed exemplar line: {ex_lines[0]!r}"
    assert '\\"hi\\"' in m.group(3) and "\\n" in m.group(3)
    assert float(m.group(4)) == pytest.approx(99.0)
    # the DEFAULT rendering stays strict 0.0.4: no suffixes anywhere (a
    # scrape that errors drops every metric, so exemplars are opt-in)
    assert " # {" not in reg.prometheus_text()
    # non-exemplar histograms render without any suffix either way
    reg2 = telemetry.TelemetryRegistry()
    reg2.histogram("plain_ms").observe(1.0)
    assert " # {" not in reg2.prometheus_text(exemplars=True)


def test_prometheus_endpoint_exemplar_opt_in(api_with_index):
    api = api_with_index
    api.handle("POST", "/attr/_search", "",
               json.dumps({"query": {"match": {"body": "quick"}}}
                          ).encode())
    st, ct, p = api.handle("GET", "/_prometheus/metrics", "", b"")
    assert st == 200 and "0.0.4" in ct
    assert " # {" not in p.decode()        # default scrape stays strict
    st2, ct2, p2 = api.handle("GET", "/_prometheus/metrics",
                              "exemplars=true", b"")
    assert st2 == 200 and ct2.startswith("application/openmetrics-text")
    # this index's line: the registry is the process's, and indices of
    # earlier tests in the same process may sort before it
    lat = [ln for ln in p2.decode().splitlines()
           if ln.startswith('es_query_latency_ms{index="attr",')
           and 'quantile="0.99"' in ln]
    assert lat and " # {trace_id=" in lat[0]


def test_exemplar_selection_tracks_p99():
    h = telemetry.Histogram()
    for i in range(100):
        h.observe(float(i), exemplar=f"t{i}")
    snap = h.snapshot()
    ex = snap["exemplar"]
    # the exemplar illustrates the p99, not a random sample
    assert ex["value"] >= snap["p99"]
    assert ex["trace_id"] == f"t{int(ex['value'])}"


def test_query_latency_family_carries_trace_exemplar(api_with_index):
    api = api_with_index
    rh = {}
    api.handle("POST", "/attr/_search", "",
               json.dumps({"query": {"match": {"body": "quick"}}}
                          ).encode(), resp_headers=rh)
    fam = telemetry.DEFAULT.metrics_doc()["es_query_latency_ms"]
    series = [s for s in fam["series"]
              if s["labels"].get("index") == "attr"]
    assert series
    assert series[0]["value"]["exemplar"]["trace_id"]


# ---------------------------------------------------------------------------
# es_plane_swap_ms kind label (satellite label fix)
# ---------------------------------------------------------------------------


def test_plane_swap_histogram_has_kind_label():
    from elasticsearch_tpu.search.plane_route import ServingPlaneCache
    cache = ServingPlaneCache()
    cache._swap_ms["text"].observe(5.0)
    doc = cache._metrics_doc()
    samples = doc["es_plane_swap_ms"]["samples"]
    kinds = {labels["kind"] for labels, _snap in samples}
    assert kinds == {"text", "knn"}     # label space stable for the lint
    text_snap = next(s for labels, s in samples
                     if labels["kind"] == "text")
    assert text_snap["count"] == 1


# ---------------------------------------------------------------------------
# GET /_trace listing (satellite)
# ---------------------------------------------------------------------------


def test_trace_listing_newest_first(api_with_index):
    api = api_with_index
    rh = {}
    st, _ct, _p = api.handle(
        "POST", "/attr/_search", "",
        json.dumps({"query": {"match": {"body": "quick"}}}).encode(),
        resp_headers=rh)
    assert st == 200
    tid = rh["Trace-Id"]
    st2, _c2, p2 = api.handle("GET", "/_trace", "", b"")
    assert st2 == 200
    doc = json.loads(p2)
    rows = doc["traces"]
    assert rows and rows[0]["trace_id"] == tid
    assert rows[0]["root"].startswith("rest[")
    assert rows[0]["took_ms"] >= 0
    assert doc["store"]["traces"] >= 1
    # size param caps the listing
    st3, _c3, p3 = api.handle("GET", "/_trace", "size=1", b"")
    assert len(json.loads(p3)["traces"]) == 1


# ---------------------------------------------------------------------------
# single-node hot_threads node filter (satellite)
# ---------------------------------------------------------------------------

_HT_Q = "interval=40ms&snapshots=2&threads=2"


def test_hot_threads_node_filter_single_node(api_with_index):
    api = api_with_index
    st, ct, p = api.handle("GET", "/_nodes/_local/hot_threads",
                           _HT_Q, b"")
    assert st == 200 and ct.startswith("text/plain")
    assert f"::: {{{api.node_name}}}" in p.decode()
    # a filter selecting no node samples nothing
    st2, _c2, p2 = api.handle("GET", "/_nodes/no-such-node/hot_threads",
                              _HT_Q, b"")
    assert st2 == 200 and p2 == b""


# ---------------------------------------------------------------------------
# 3-node cluster: coordinator roll-up, health fan-in, hot-threads fan-out
# ---------------------------------------------------------------------------

BASE_PORT = 29520


@pytest.fixture()
def cluster(tmp_path):
    from elasticsearch_tpu.node.cluster_node import ClusterNode
    peers = {f"n{i}": ("127.0.0.1", BASE_PORT + i) for i in range(3)}
    nodes = [ClusterNode(f"n{i}", "127.0.0.1", BASE_PORT + i, peers,
                         str(tmp_path / f"n{i}"), seed=i)
             for i in range(3)]
    try:
        yield nodes
    finally:
        for n in nodes:
            try:
                n.stop()
            except Exception:               # noqa: BLE001
                pass


def _wait_leader(nodes, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        leaders = [n for n in nodes
                   if not n.stopped and n.coordinator.mode == "LEADER"]
        if len(leaders) == 1:
            followers = [n for n in nodes if not n.stopped and
                         n.coordinator.known_leader == leaders[0].node_id]
            if len(followers) * 2 > len(nodes):
                return leaders[0]
        time.sleep(0.05)
    raise AssertionError("no stable leader over TCP")


def test_cluster_rollup_health_and_hot_threads(cluster):
    nodes = cluster
    leader = _wait_leader(nodes)
    front = nodes[(nodes.index(leader) + 1) % 3]      # non-master front
    st, _ct, out = front.rest.handle("PUT", "/rlogs", "", json.dumps(
        {"settings": {"number_of_shards": 3},
         "mappings": {"properties": {"body": {"type": "text"}}}}
    ).encode())
    assert st == 200, out
    lines = []
    n_docs = 12
    for i in range(n_docs):
        lines.append(json.dumps({"index": {"_index": "rlogs",
                                           "_id": str(i)}}))
        lines.append(json.dumps({"body": f"quick fox event {i}"}))
    st, _ct, out = front.rest.handle(
        "POST", "/_bulk", "refresh=true",
        ("\n".join(lines) + "\n").encode())
    assert st == 200, out

    # ---- coordinator-side resource roll-up across the shard fan-out
    deadline = time.monotonic() + 10.0
    rolled = None
    while time.monotonic() < deadline:
        st, _ct, out = front.rest.handle(
            "POST", "/rlogs/_search", "",
            json.dumps({"query": {"match": {"body": "quick"}}}).encode())
        doc = json.loads(out)
        totals = front.rest.api.task_manager.action_totals().get(
            "indices:data/read/search")
        if st == 200 and doc["hits"]["total"]["value"] == n_docs and \
                totals and totals["docs_scanned"] >= n_docs:
            rolled = totals
            break
        time.sleep(0.2)
    assert rolled, "coordinator never rolled up a full-corpus scan " \
        "(data-node ledgers missing from the fan-out)"
    assert rolled["cpu_ms"] >= 0      # 10ms thread_time tick: may be 0

    # ---- GET /_health_report via the non-master front
    st, _ct, out = front.rest.handle("GET", "/_health_report", "", b"")
    assert st == 200, out
    doc = json.loads(out)
    assert doc["status"] in ("green", "yellow", "red")
    ind = doc["indicators"]["shards_availability"]
    per_node = ind["details"]["nodes"]
    assert len(per_node) == 3, per_node    # every node's report fanned in
    assert ind["details"]["number_of_nodes"] == 3
    assert set(doc["indicators"]) >= {"plane_serving", "breakers",
                                      "task_backlog"}

    # ---- cluster hot_threads: one block per node, filter honored
    st, ct, out = front.rest.handle("GET", "/_nodes/hot_threads",
                                    _HT_Q, b"")
    assert st == 200 and ct.startswith("text/plain")
    text = out.decode()
    for n in nodes:
        assert f"::: {{{n.node_id}}}" in text, \
            f"{n.node_id} missing from cluster hot_threads:\n{text[:400]}"
    other = nodes[(nodes.index(leader) + 2) % 3]
    st, _ct, out = front.rest.handle(
        "GET", f"/_nodes/{other.node_id}/hot_threads", _HT_Q, b"")
    text = out.decode()
    assert f"::: {{{other.node_id}}}" in text
    assert f"::: {{{front.node_id}}}" not in text


# ---------------------------------------------------------------------------
# TELEMETRY.md lint (satellite: metric docs can't drift again)
# ---------------------------------------------------------------------------


def test_telemetry_lint():
    spec = importlib.util.spec_from_file_location(
        "telemetry_lint",
        os.path.join(REPO_ROOT, "scripts", "telemetry_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main() == 0, "telemetry families drifted from TELEMETRY.md"
