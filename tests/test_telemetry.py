"""Telemetry + tracing tests (common/telemetry.py, common/tracing.py):
registry thread-safety, Prometheus exposition conformance, the
compile-churn ratchet (zero steady-state compiles after warmup — the
PR-2 regression guard), end-to-end trace spans through the single-node
REST stack, 3-node trace propagation through a non-master front, the
X-Opaque-Id / Trace-Id echo, slow-log stamping, the profile ``serving``
section, and the monitoring collector's telemetry doc."""

import json
import re
import tempfile
import threading
import time

import numpy as np
import pytest

from elasticsearch_tpu.common import telemetry, tracing
from elasticsearch_tpu.common.telemetry import TelemetryRegistry


# ---------------------------------------------------------------------------
# registry basics + thread safety
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram_basics():
    reg = TelemetryRegistry()
    c = reg.counter("reqs_total", {"route": "a"})
    c.inc()
    c.inc(2.5)
    assert reg.counter("reqs_total", {"route": "a"}) is c     # get-or-create
    g = reg.gauge("queue_depth")
    g.set(7)
    g.set_max(3)                       # watermark never regresses
    assert g.value == 7
    h = reg.histogram("lat_ms")
    for v in range(100):
        h.observe(float(v))
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["sum"] == pytest.approx(4950.0)
    assert snap["p50"] == pytest.approx(50.0, abs=2)
    assert snap["p99"] == pytest.approx(99.0, abs=2)
    doc = reg.stats_doc()
    assert doc["reqs_total"]["type"] == "counter"
    series = doc["reqs_total"]["series"]
    assert series[0]["labels"] == {"route": "a"}
    assert series[0]["value"] == pytest.approx(3.5)
    # kind conflicts are an error, not silent corruption
    with pytest.raises(ValueError):
        reg.gauge("reqs_total")


def test_registry_series_cardinality_is_bounded():
    reg = TelemetryRegistry()
    for i in range(reg.MAX_SERIES * 2):
        reg.counter("shapes_total", {"shape": f"s{i}"}).inc()
    fam = reg.stats_doc()["shapes_total"]["series"]
    assert len(fam) <= reg.MAX_SERIES + 1
    overflow = [s for s in fam if s["labels"].get("overflow") == "true"]
    assert overflow and overflow[0]["value"] >= reg.MAX_SERIES


def test_registry_thread_safety_16_writers_vs_snapshots():
    """16 threads hammer counters/histograms while a reader snapshots
    stats_doc() and prometheus_text() concurrently; final counts are
    exact and no snapshot throws."""
    reg = TelemetryRegistry()
    N, THREADS = 500, 16
    errs = []
    stop = threading.Event()

    def writer(tid):
        try:
            for i in range(N):
                reg.counter("w_total", {"t": str(tid % 4)}).inc()
                reg.histogram("w_ms").observe(float(i))
                reg.gauge("w_depth").set(i)
        except Exception as e:              # noqa: BLE001
            errs.append(e)

    def reader():
        try:
            while not stop.is_set():
                reg.stats_doc()
                reg.prometheus_text()
        except Exception as e:              # noqa: BLE001
            errs.append(e)

    r = threading.Thread(target=reader)
    r.start()
    ws = [threading.Thread(target=writer, args=(t,))
          for t in range(THREADS)]
    for t in ws:
        t.start()
    for t in ws:
        t.join()
    stop.set()
    r.join()
    assert not errs
    total = sum(s["value"]
                for s in reg.stats_doc()["w_total"]["series"])
    assert total == THREADS * N
    assert reg.histogram("w_ms").snapshot()["count"] == THREADS * N


# ---------------------------------------------------------------------------
# Prometheus exposition conformance
# ---------------------------------------------------------------------------

_METRIC_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"                 # name
    r"(\{[a-zA-Z0-9_]+=\"(?:[^\"\\\n]|\\\\|\\\"|\\n)*\""   # first label
    r"(,[a-zA-Z0-9_]+=\"(?:[^\"\\\n]|\\\\|\\\"|\\n)*\")*\})?"
    r" (-?[0-9.eE+]+|NaN|[+-]Inf)$")
_TYPE_RE = re.compile(
    r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) "
    r"(counter|gauge|summary|histogram|untyped)$")


def test_prometheus_exposition_parses_cleanly():
    reg = TelemetryRegistry()
    # hostile label values: escaping must keep the line parseable
    reg.counter("esc_total", {"q": 'say "hi"\\path\nline2'},
                help="escaping probe").inc()
    reg.gauge("plain")
    reg.gauge("labeled", {"a": "1", "b": "x y"}).set(2.5)
    h = reg.histogram("lat_ms", {"stage": "queue"})
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    text = reg.prometheus_text()
    typed = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            m = _TYPE_RE.match(line)
            assert m, f"malformed TYPE line: {line!r}"
            typed[m.group(1)] = m.group(2)
            continue
        m = _METRIC_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        base = m.group(1)
        for suffix in ("_count", "_sum", "_bucket"):
            if base.endswith(suffix) and base[: -len(suffix)] in typed:
                base = base[: -len(suffix)]
                break
        assert base in typed, f"sample {base} has no TYPE declaration"
    # histograms render as summaries with quantile + count/sum series
    assert typed["lat_ms"] == "summary"
    assert 'lat_ms{quantile="0.5",stage="queue"}' in text
    assert 'lat_ms_count{stage="queue"} 3' in text
    # the escaped label round-trips its specials
    assert '\\"hi\\"' in text and "\\n" in text and "\\\\" in text


def test_prometheus_endpoint_over_rest():
    from elasticsearch_tpu.node.indices_service import IndicesService
    from elasticsearch_tpu.rest.api import RestAPI
    with tempfile.TemporaryDirectory() as d:
        api = RestAPI(IndicesService(d))
        st, ct, payload = api.handle("GET", "/_prometheus/metrics", "",
                                     b"")
        assert st == 200 and ct.startswith("text/plain")
        text = payload.decode()
        # node families + process collectors are both present
        assert "es_plane_serving_dispatches_total" in text
        assert "es_breaker_estimated_bytes" in text
        assert "es_tasks_running" in text


# ---------------------------------------------------------------------------
# XLA instrumentation: compile counting + the compile-churn ratchet
# ---------------------------------------------------------------------------


def _tiny_plane():
    import jax
    from elasticsearch_tpu.parallel import (DistributedSearchPlane,
                                            make_search_mesh)
    from elasticsearch_tpu.utils.synth import synthetic_csr_corpus_fast
    rng = np.random.RandomState(7)
    corpus = synthetic_csr_corpus_fast(rng, 256, 128, 8, zipf_s=1.2)
    corpus["term_ids"] = {f"t{t}": t for t in range(128)}
    mesh = make_search_mesh(n_shards=1, n_replicas=1,
                            devices=jax.devices()[:1])
    return DistributedSearchPlane(mesh, [corpus], field="body")


def test_compile_churn_ratchet_zero_compiles_after_warmup():
    """Regression guard for the PR-2 fix: after ``warmup(sync=True)``
    pre-compiles the plane's serving list (``plane.serving_shapes``), a
    steady-state burst (mixed B arrival patterns, mixed term counts,
    k inside the warmed bucket) must register ZERO new compiles."""
    from elasticsearch_tpu.search.microbatch import PlaneMicroBatcher
    plane = _tiny_plane()
    # force the jitted serving path (on the CPU test backend the plane
    # would otherwise serve host-eager and compile nothing)
    plane._host_csr = None
    b = PlaneMicroBatcher(plane, max_batch=4)
    before_warm = telemetry.compile_count()
    b.warmup(ks=(10,), sync=True)
    after_warm = telemetry.compile_count()
    # the plane's own list, one program a padded batch: B ∈ {1,2,4}
    assert b.warmed_shapes == len(plane.serving_shapes([16], 4)) == 3
    assert after_warm - before_warm == 3, \
        "warmup should compile the list, one program a member"

    errs = []

    def client(tid):
        try:
            for j in range(6):
                terms = [f"t{(tid * 5 + j) % 64}"] * (1 + j % 2) + \
                    [f"t{(tid + j) % 64}"]
                vals, hits, total = b.search(terms, k=10)
                assert total is not None
        except Exception as e:                   # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert b.n_dispatches > 0
    assert telemetry.compile_count() == after_warm, \
        "steady-state serving burst must not compile new shapes"


def test_compile_registry_counts_per_site_and_shape():
    plane = _tiny_plane()
    plane._host_csr = None
    before = telemetry.compile_count()
    plane.serve([["t1", "t2"]], k=4, with_totals=True)
    assert telemetry.compile_count() == before + 1
    # second dispatch at the same shape: cache hit, no new compile
    stages = {}
    plane.serve([["t3"]], k=4, with_totals=True, stages=stages)
    assert telemetry.compile_count() == before + 1
    assert stages["compile_cache"] == "hit"
    doc = telemetry.DEFAULT.stats_doc()
    sites = {s["labels"]["site"]
             for s in doc["es_xla_compiles_total"]["series"]}
    assert "text_plane" in sites
    # per-shape attribution + compile milliseconds exist
    assert any(s["labels"].get("site") == "text_plane"
               for s in doc["es_xla_compiles_by_shape_total"]["series"])
    ms = sum(s["value"]
             for s in doc["es_xla_compile_millis_total"]["series"])
    assert ms > 0


def test_device_transfer_bytes_counted():
    plane = _tiny_plane()
    plane._host_csr = None
    snap0 = telemetry.device_stats_doc().get("transfer", {})
    plane.serve([["t1"]], k=4, with_totals=True)
    snap1 = telemetry.device_stats_doc()["transfer"]
    assert snap1.get("h2d", 0) > snap0.get("h2d", 0)
    assert snap1.get("d2h", 0) > snap0.get("d2h", 0)


# ---------------------------------------------------------------------------
# tracing: spans, store bounds, single-node end-to-end
# ---------------------------------------------------------------------------


def test_trace_store_bounded_and_tree_shape():
    store = tracing.TraceStore()
    with tracing.span("root", root=True, store=store, node="n0") as sp:
        tid = sp.trace_id
        with tracing.span("child", store=store, attrs={"x": 1}):
            pass
    doc = store.get(tid)
    assert doc["span_count"] == 2
    assert doc["tree"][0]["name"] == "root"
    assert doc["tree"][0]["children"][0]["name"] == "child"
    assert doc["tree"][0]["children"][0]["attrs"] == {"x": 1}
    # the flat list stays flat: tree nodes are separate copies, so a
    # deep chain can't nest every subtree into its ancestors here too
    assert all("children" not in s for s in doc["spans"])
    # bounded: at most MAX_TRACES retained, FIFO evicted
    for i in range(store.MAX_TRACES + 10):
        store.record({"trace_id": f"t{i}", "span_id": "s", "name": "x"})
    assert store.stats_doc()["traces"] <= store.MAX_TRACES
    assert store.get(tid) is None            # evicted


def test_span_without_context_records_nothing():
    store = tracing.TraceStore()
    with tracing.span("maintenance", store=store) as sp:
        assert sp is None                    # untraced paths stay free
    assert store.stats_doc()["traces"] == 0


@pytest.fixture()
def api_with_index():
    from elasticsearch_tpu.node.indices_service import IndicesService
    from elasticsearch_tpu.rest.api import RestAPI
    with tempfile.TemporaryDirectory() as d:
        api = RestAPI(IndicesService(d))
        api.handle("PUT", "/tr", "", json.dumps(
            {"mappings": {"properties": {"body": {"type": "text"}}}}
        ).encode())
        api.handle("PUT", "/tr/_doc/1", "refresh=true",
                   json.dumps({"body": "quick brown fox"}).encode())
        yield api


def test_single_node_trace_spans_rest_to_shard(api_with_index):
    api = api_with_index
    rh = {}
    st, _ct, _p = api.handle(
        "POST", "/tr/_search", "",
        json.dumps({"query": {"match": {"body": "quick"}}}).encode(),
        resp_headers=rh)
    assert st == 200
    tid = rh["Trace-Id"]
    st2, _ct2, p2 = api.handle("GET", f"/_trace/{tid}", "", b"")
    assert st2 == 200
    doc = json.loads(p2)
    names = [s["name"] for s in doc["spans"]]
    assert any(n.startswith("rest[") for n in names)
    assert "coordinator[search]" in names
    assert "shards[tr]" in names
    assert "plane_dispatch" in names
    # the tree nests rest → coordinator → shards
    root = doc["tree"][0]
    assert root["name"].startswith("rest[")
    coord = root["children"][0]
    assert coord["name"] == "coordinator[search]"
    assert coord["children"][0]["name"] == "shards[tr]"
    # plane dispatch carries stage + compile-cache attribution
    pd = next(c for c in coord["children"][0]["children"]
              if c["name"] == "plane_dispatch")
    assert "compile_cache" in pd["attrs"]
    # unknown traces 404
    st3, _c, _p3 = api.handle("GET", "/_trace/deadbeef", "", b"")
    assert st3 == 404


def test_incoming_traceparent_is_adopted(api_with_index):
    api = api_with_index
    rh = {}
    tid = "a" * 32
    api.handle("POST", "/tr/_search", "",
               json.dumps({"query": {"match_all": {}}}).encode(),
               headers={"traceparent": f"00-{tid}-{'b' * 16}-01"},
               resp_headers=rh)
    assert rh["Trace-Id"] == tid
    st, _ct, p = api.handle("GET", f"/_trace/{tid}", "", b"")
    assert st == 200
    root = json.loads(p)["tree"][0]
    assert root["parent_span_id"] == "b" * 16


def test_opaque_id_echo_task_headers_and_slow_log(api_with_index):
    api = api_with_index
    svc = api.indices.get("tr")
    svc.settings["index.search.slowlog.threshold.query.trace"] = "0ms"
    rh = {}
    st, _ct, _p = api.handle(
        "POST", "/tr/_search", "",
        json.dumps({"query": {"match_all": {}}}).encode(),
        headers={"X-Opaque-Id": "my-req-42"}, resp_headers=rh)
    assert st == 200
    assert rh["X-Opaque-Id"] == "my-req-42"
    assert rh["Trace-Id"]
    entry = svc.slow_log[-1]
    assert entry["x_opaque_id"] == "my-req-42"
    assert entry["trace.id"] == rh["Trace-Id"]
    # every request's task carries both in headers + description
    st2, _c2, p2 = api.handle("GET", "/_tasks", "__x_opaque_id=cat-7",
                              b"")
    tasks = next(iter(json.loads(p2)["nodes"].values()))["tasks"]
    own = [t for t in tasks.values()
           if t["headers"].get("X-Opaque-Id") == "cat-7"]
    assert own
    assert own[0]["headers"]["trace.id"]
    assert "x-opaque-id=cat-7" in own[0]["description"]
    assert "trace.id=" in own[0]["description"]


def test_http_layer_sanitizes_echoed_header_values():
    """The X-Opaque-Id echo is client-controlled (and percent-decoded
    via __x_opaque_id) — the HTTP layer must strip CR/LF before
    reflection or a crafted id injects response headers."""
    import asyncio
    import urllib.request
    from elasticsearch_tpu.node.indices_service import IndicesService
    from elasticsearch_tpu.rest.api import RestAPI
    from elasticsearch_tpu.rest.http_server import HttpServer

    with tempfile.TemporaryDirectory() as d:
        api = RestAPI(IndicesService(d))

        def handler(method, path, query, body, headers=None):
            rh = {}
            status, ct, out = api.handle(method, path, query, body,
                                         headers=headers,
                                         resp_headers=rh)
            return status, ct, out, rh

        box = {}

        async def run():
            srv = HttpServer(handler, host="127.0.0.1", port=0)
            await srv.start()
            port = srv._server.sockets[0].getsockname()[1]

            def fetch():
                # percent-encoded CRLF in the opaque-id param
                url = (f"http://127.0.0.1:{port}/?__x_opaque_id="
                       "a%0d%0aSet-Cookie:%20sid=evil")
                with urllib.request.urlopen(url, timeout=5) as r:
                    return dict(r.headers)

            box["headers"] = await asyncio.get_running_loop() \
                .run_in_executor(None, fetch)
            await srv.stop()

        asyncio.run(run())
        hdrs = box["headers"]
        assert "Set-Cookie" not in hdrs
        assert "Set-Cookie" in hdrs.get("X-Opaque-Id", ""), \
            "sanitized value should survive on one line"
        assert hdrs.get("Trace-Id")


def test_profile_serving_section_on_plane_path(api_with_index):
    """Acceptance: profile:true over the plane path returns a ``serving``
    section with per-stage timings and the compile-cache verdict."""
    api = api_with_index
    st, _ct, p = api.handle(
        "POST", "/tr/_search", "",
        json.dumps({"query": {"match": {"body": "quick"}},
                    "profile": True}).encode())
    assert st == 200
    doc = json.loads(p)
    assert doc["hits"]["total"]["value"] == 1
    shard = doc["profile"]["shards"][0]
    serving = shard["serving"]
    assert set(serving["stages_ms"]) == {"queue", "prep", "dispatch",
                                         "fetch"}
    assert serving["compile_cache"] in ("hit", "miss", "host")
    assert serving["batch_size"] >= 1
    assert shard["searches"][0]["collector"][0]["name"] == \
        "PlaneMicroBatchCollector"
    # non-plane shapes keep the classic profile (no serving section)
    st2, _c, p2 = api.handle(
        "POST", "/tr/_search", "",
        json.dumps({"query": {"match_all": {}},
                    "profile": True}).encode())
    assert "serving" not in json.loads(p2)["profile"]["shards"][0]


# ---------------------------------------------------------------------------
# nodes telemetry endpoint + device section + monitoring collector
# ---------------------------------------------------------------------------


def test_nodes_telemetry_endpoint_and_device_section(api_with_index):
    api = api_with_index
    api.handle("POST", "/tr/_search", "", json.dumps(
        {"query": {"match": {"body": "quick"}}}).encode())
    st, _ct, p = api.handle("GET", "/_nodes/telemetry", "", b"")
    assert st == 200
    node = next(iter(json.loads(p)["nodes"].values()))
    assert node["plane_serving"]["dispatches"] >= 1
    assert "registry" in node and "device" in node
    assert "trace_store" in node and node["trace_store"]["traces"] >= 1
    # nodes stats gained the device section (and the metric filter
    # accepts it)
    st2, _c, p2 = api.handle("GET", "/_nodes/stats/device", "", b"")
    assert st2 == 200
    node2 = next(iter(json.loads(p2)["nodes"].values()))
    assert "devices" in node2["device"]
    assert node2["device"]["live_array_bytes_watermark"] >= 0


def test_monitoring_collects_telemetry_doc(api_with_index):
    api = api_with_index
    api.monitoring.collect()
    api.handle("POST", "/.monitoring-es-8-*/_refresh", "", b"")
    st, _ct, p = api.handle(
        "POST", "/.monitoring-es-8-*/_search", "",
        json.dumps({"size": 50}).encode())
    assert st == 200
    hits = json.loads(p)["hits"]["hits"]
    types = {h["_source"]["type"] for h in hits}
    assert "node_telemetry" in types
    tdoc = next(h["_source"] for h in hits
                if h["_source"]["type"] == "node_telemetry")
    assert "device" in tdoc["node_telemetry"]
    assert "plane_serving" in tdoc["node_telemetry"]
    ndoc = next(h["_source"] for h in hits
                if h["_source"]["type"] == "node_stats")
    assert "plane_serving" in ndoc["node_stats"]


# ---------------------------------------------------------------------------
# 3-node cluster: trace propagation through a non-master front
# ---------------------------------------------------------------------------

BASE_PORT = 29470


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


def test_trace_propagates_through_non_master_front(cluster):
    nodes = cluster
    leader = _wait_leader(nodes)
    front = nodes[(nodes.index(leader) + 1) % 3]      # non-master front
    st, _ct, out = front.rest.handle("PUT", "/tlogs", "", json.dumps(
        {"settings": {"number_of_shards": 3},
         "mappings": {"properties": {"body": {"type": "text"}}}}
    ).encode())
    assert st == 200, out
    lines = []
    for i in range(12):
        lines.append(json.dumps({"index": {"_index": "tlogs",
                                           "_id": str(i)}}))
        lines.append(json.dumps({"body": f"quick fox event {i}"}))
    st, _ct, out = front.rest.handle(
        "POST", "/_bulk", "refresh=true",
        ("\n".join(lines) + "\n").encode())
    assert st == 200, out

    # shards spread across nodes: retry until the search fans out and
    # every doc is visible
    tid = None
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        rh = {}
        st, _ct, out = front.rest.handle(
            "POST", "/tlogs/_search", "",
            json.dumps({"query": {"match": {"body": "quick"}}}).encode(),
            resp_headers=rh)
        doc = json.loads(out)
        if st == 200 and doc["hits"]["total"]["value"] == 12 \
                and rh.get("Trace-Id"):
            tid = rh["Trace-Id"]
            break
        time.sleep(0.2)
    assert tid, "search never completed with a trace id"

    st, _ct, out = front.rest.handle("GET", f"/_trace/{tid}", "", b"")
    assert st == 200
    doc = json.loads(out)
    spans = doc["spans"]
    assert all(s["trace_id"] == tid for s in spans)
    names = [s["name"] for s in spans]
    assert any(n.startswith("rest[") for n in names)
    # ≥1 data-node shard span recorded by a node OTHER than the front:
    # the trace context crossed the transport in request headers
    remote_shard_spans = [
        s for s in spans
        if s["name"].startswith(("shard_search[", "shard_stats["))
        and s.get("node") not in (None, front.node_id)]
    assert remote_shard_spans, (
        f"no remote shard spans joined the trace: {names}")
    front_shard_spans = [s for s in spans
                         if s["name"].startswith("shard_search[")]
    assert front_shard_spans
