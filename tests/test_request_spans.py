"""One request, one span tree (common/tracing.py): a ``_search`` through
``HttpServer`` + ``cli.node._wrap_handler`` leaves one trace from
``http[in]`` to ``http[out]``; the dispatcher's ``batch[...]`` spans and
the request's ``plane_dispatch`` bear the dispatch-profile ``seq``; under
a ``jax.profiler`` session every span has a twin in the ``.xplane.pb``
host plane; the jitted steps carry stable module and scope names.
"""
from __future__ import annotations

import asyncio
import contextlib
import glob
import json
import os
import re
import tempfile
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
import pytest

from elasticsearch_tpu.cli.node import _wrap_handler
from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.node.indices_service import IndicesService
from elasticsearch_tpu.rest.api import RestAPI
from elasticsearch_tpu.rest.http_server import HttpServer
from elasticsearch_tpu.search import dispatch_profile

DIM = 8
KNN_BODY = {"knn": {"field": "v", "query_vector": [0.5] * DIM, "k": 3,
                    "num_candidates": 3, "nprobe": 0}, "size": 3}
TEXT_BODY = {"query": {"match": {"body": "quick"}}}
#: knn beside a query the planner does not lower: the per-segment query
#: phase scores the phrase, the kNN plane serves the clause
HYBRID_BODY = dict(KNN_BODY,
                   query={"match_phrase": {"body": "quick brown"}})


@contextlib.contextmanager
def served_node():
    """A node as ``cli.node.main`` serves it (REST handlers on a pool
    behind the asyncio HTTP server), with one index holding text and
    vectors. Yields ``post(path, body=None, headers=None) -> (headers,
    doc)``: a GET where there is no body."""
    with tempfile.TemporaryDirectory() as d:
        api = RestAPI(IndicesService(d))
        api.handle("PUT", "/sp", "", json.dumps({"mappings": {"properties": {
            "body": {"type": "text"},
            "v": {"type": "dense_vector", "dims": DIM,
                  "similarity": "cosine"}}}}).encode())
        rng = np.random.default_rng(5)
        for i in range(12):
            api.handle("PUT", f"/sp/_doc/{i}", "", json.dumps({
                "body": "quick brown fox" if i % 2 else "lazy dog",
                "v": rng.standard_normal(DIM).round(3).tolist()}).encode())
        api.handle("POST", "/sp/_refresh", "", b"")
        pool = ThreadPoolExecutor(max_workers=4,
                                  thread_name_prefix="es-rest-http")
        loop = asyncio.new_event_loop()
        srv = HttpServer(_wrap_handler(api.handle, pool, owner=api),
                         host="127.0.0.1", port=0, pass_headers=True)
        thread = threading.Thread(target=loop.run_forever, daemon=True)
        thread.start()
        asyncio.run_coroutine_threadsafe(srv.start(), loop).result(10)
        port = srv._server.sockets[0].getsockname()[1]

        def post(path, body=None, headers=None):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}",
                data=None if body is None else json.dumps(body).encode(),
                headers={"content-type": "application/json",
                         **(headers or {})})
            with urllib.request.urlopen(req, timeout=30) as r:
                return dict(r.headers), json.loads(r.read())

        try:
            yield post
        finally:
            asyncio.run_coroutine_threadsafe(srv.stop(), loop).result(10)
            loop.call_soon_threadsafe(loop.stop)
            thread.join(10)
            pool.shutdown(wait=False)


def _trace_of(headers):
    """The stored trace of a response, once ``http[out]`` has landed (it
    is recorded just after the last byte is written)."""
    deadline = time.time() + 5
    while True:
        doc = tracing.DEFAULT_STORE.get(headers["Trace-Id"])
        if doc and any(s["name"] == "http[out]" for s in doc["spans"]):
            return doc
        assert time.time() < deadline, doc
        time.sleep(0.01)


def _by_name(doc):
    out = {}
    for s in doc["spans"]:
        out.setdefault(s["name"], []).append(s)
    return out


def _end(span):
    return span["start_ms"] + span["took_ms"]


#: another body of the same route (an equal one is answered by the shard
#: request cache, and never reaches the shard)
WARM = {"knn": KNN_BODY, "text": {"query": {"match": {"body": "lazy"}}},
        "hybrid": dict(KNN_BODY,
                       query={"match_phrase": {"body": "lazy dog"}})}
#: route -> (body, the shard's phases, plane_dispatch's parent,
#: shard[plan]'s route attribute)
ROUTES = {
    "knn": (KNN_BODY, ["shard[plan]", "shard[knn]", "shard[rank]",
                       "shard[fetch]"], "shard[knn]", "knn"),
    "hybrid": (HYBRID_BODY, ["shard[plan]", "shard[query_phase]",
                             "shard[knn]", "shard[rank]", "shard[fetch]"],
               "shard[knn]", "segments"),
    "text": (TEXT_BODY, ["shard[plan]", "plane_dispatch", "shard[rank]",
                         "shard[fetch]"], "shards[sp]", "plane"),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_search_leaves_one_span_tree(route):
    body, shard_children, dispatch_parent, plan_route = ROUTES[route]
    with served_node() as post:
        post("/sp/_search", WARM[route])       # pack the plane
        headers, resp = post("/sp/_search", body)
    assert resp["hits"]["hits"]
    doc = _trace_of(headers)
    spans = _by_name(doc)
    assert {s["trace_id"] for s in doc["spans"]} == {headers["Trace-Id"]}
    rest_name = next(n for n in spans if n.startswith("rest[indices:"))
    one = {n: spans[n][0] for n in spans}
    assert all(len(v) == 1 for v in spans.values()), \
        {n: len(v) for n, v in spans.items()}
    ids = {s["span_id"]: s["name"] for s in doc["spans"]}

    def parent(name):
        return ids.get(one[name]["parent_span_id"])

    # the edge: http[in] is the root, everything else hangs off it
    assert [r["name"] for r in doc["tree"]] == ["http[in]"]
    for name in ("rest[parse]", rest_name, "rest[render]", "http[out]"):
        assert parent(name) == "http[in]", (name, parent(name))
    assert parent("coordinator[search]") == rest_name
    assert parent("shards[sp]") == "coordinator[search]"
    for name in shard_children:
        assert parent(name) == "shards[sp]", (name, parent(name))
    assert parent("plane_dispatch") == dispatch_parent
    # the order a request passes through them
    order = ["http[in]", "rest[parse]", rest_name, "coordinator[search]",
             "shards[sp]"] + shard_children + ["rest[render]", "http[out]"]
    starts = [one[n]["start_ms"] for n in order]
    assert starts == sorted(starts), list(zip(order, starts))
    assert one["http[in]"]["attrs"]["bytes_in"] == \
        len(json.dumps(body).encode())
    assert one["http[out]"]["attrs"]["status"] == 200
    assert one["shard[plan]"]["attrs"]["route"] == plan_route
    # a knn-only body runs no query phase; beside a query it does
    assert ("shard[query_phase]" in one) == (route == "hybrid")
    if route == "hybrid":
        assert one["shard[query_phase]"]["attrs"] == \
            {"segments": 1, "has_query": True}
    # the dispatch span bears the dispatch's number, and the timeline
    # has that record
    pd = one["plane_dispatch"]["attrs"]
    assert {"compile_cache", "queue", "prep", "dispatch", "fetch"} <= set(pd)
    rec = [r for r in dispatch_profile.RING.records(limit=0)
           if r["seq"] == pd["dispatch_seq"]]
    assert len(rec) == 1 and rec[0]["batch"]["requests"] == pd["batch_size"]


def test_children_cover_the_rest_span(monkeypatch):
    """With the device work stubbed to a sleep, the children of
    ``rest[...]`` account for at least 90 % of it: no layer boundary of
    the served path is left without a span."""
    from elasticsearch_tpu.search import microbatch

    real = microbatch.KnnPlaneMicroBatcher._dispatch

    def slow(self, *a, **kw):
        time.sleep(0.05)
        return real(self, *a, **kw)

    monkeypatch.setattr(microbatch.KnnPlaneMicroBatcher, "_dispatch", slow)
    with served_node() as post:
        post("/sp/_search", KNN_BODY)
        headers, _ = post("/sp/_search", KNN_BODY)
    doc = _trace_of(headers)

    def covered(node):
        kids = node.get("children", [])
        return sum(min(_end(k), _end(node)) - max(k["start_ms"],
                                                  node["start_ms"])
                   for k in kids)

    def find(nodes, pred):
        for n in nodes:
            if pred(n):
                return n
            hit = find(n.get("children", []), pred)
            if hit:
                return hit

    node = find(doc["tree"], lambda n: n["name"].startswith("rest[ind"))
    while node["name"] != "plane_dispatch":
        assert covered(node) >= 0.9 * node["took_ms"], \
            (node["name"], node["took_ms"], covered(node))
        node = max(node["children"], key=lambda n: n["took_ms"])
    assert node["took_ms"] >= 50


def test_trace_listing_describes_the_request_not_the_edge(monkeypatch):
    """``GET /_trace`` on a served node: the row of a request that came
    over HTTP is the request (its action, its tenant, socket to socket),
    not ``http[in]``, which ends at the hand-off."""
    from elasticsearch_tpu.search import microbatch

    real = microbatch.KnnPlaneMicroBatcher._dispatch

    def slow(self, *a, **kw):
        time.sleep(0.05)
        return real(self, *a, **kw)

    monkeypatch.setattr(microbatch.KnnPlaneMicroBatcher, "_dispatch", slow)
    with served_node() as post:
        headers, _ = post("/sp/_search", KNN_BODY,
                          {"X-Opaque-Id": "tenant-z"})
        doc = _trace_of(headers)
        tid = headers["Trace-Id"]

        def listed(query):
            return {r["trace_id"]: r
                    for r in post("/_trace?" + query)[1]["traces"]}

        row = listed("min_ms=50&tenant=tenant-z")[tid]
        assert tid not in listed("tenant=tenant-y")
        assert tid not in listed("min_ms=1e9")
    spans = _by_name(doc)
    edge, out = spans["http[in]"][0], spans["http[out]"][0]
    assert edge["took_ms"] < 50 <= row["took_ms"]
    assert row["root"].startswith("rest[indices:") \
        and row["tenant"] == "tenant-z"
    assert row["start_ms"] == edge["start_ms"]
    assert row["took_ms"] == pytest.approx(_end(out) - edge["start_ms"],
                                           abs=0.01)
    assert row["span_count"] == len(doc["spans"])


def _host_events(trace_dir):
    """{name: [stats dict]} of the annotated events in the trace's host
    planes."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = {}
    for pb in files:
        for plane in ProfileData.from_file(pb).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if "[" in ev.name or ev.name.endswith("_dispatch"):
                        out.setdefault(ev.name, []).append(
                            dict(ev.stats, _start=ev.start_ns,
                                 _dur=ev.duration_ns))
    return out


def test_spans_have_twins_on_the_profilers_clock(monkeypatch):
    # the jitted step, as on a chip (the CPU backend's host twin has no
    # transfers to span)
    monkeypatch.setenv("ES_TPU_PLANE_HOST_SERVE", "0")
    # no dispatcher left inside XLA when the interpreter exits
    from elasticsearch_tpu.search.microbatch import PlaneMicroBatcher
    monkeypatch.setattr(PlaneMicroBatcher, "IDLE_EXIT_S", 0.2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with served_node() as post, tempfile.TemporaryDirectory() as tdir:
        post("/sp/_search", KNN_BODY)
        # no session: nothing is written anywhere
        assert not os.listdir(tdir)
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            headers, _ = post("/sp/_search", KNN_BODY)
            doc = _trace_of(headers)
        finally:
            jax.profiler.stop_trace()
        events = _host_events(tdir)
    for t in threading.enumerate():
        if t.name.startswith("es-dispatcher"):
            t.join(10)
            assert not t.is_alive()
    tid = "t" + headers["Trace-Id"]
    stored = {s["name"]: s for s in doc["spans"]}
    for name, s in stored.items():
        twins = [e for e in events.get(name, []) if e.get("trace_id") == tid]
        assert len(twins) == 1, (name, events.get(name))
        assert twins[0]["span_id"] == "s" + s["span_id"]
        if s["parent_span_id"]:
            assert twins[0]["parent"] == "s" + s["parent_span_id"]
    # a traced span's twin bears its ids and what links it to another
    # thread's spans, known only when it ends; the rest is in the store
    seq = stored["plane_dispatch"]["attrs"]["dispatch_seq"]
    twin = next(e for e in events["plane_dispatch"]
                if e.get("trace_id") == tid)
    assert twin["dispatch_seq"] == seq and "queue" not in twin
    assert "queue" in stored["plane_dispatch"]["attrs"]
    # the dispatcher's spans have no trace: they bear the dispatch's seq,
    # and the plane's own steps nest inside batch[execute]
    for name in ("batch[prep]", "batch[execute]", "batch[fetch]"):
        assert [e for e in events[name] if e["seq"] == seq], name
    execute = next(e for e in events["batch[execute]"] if e["seq"] == seq)
    assert execute["requests"] == 1 and execute["kernel"] == "knn_exact"
    inside = [n for n in ("plane[h2d]", "plane[launch]", "plane[sync]",
                          "plane[d2h]", "plane[decode]")
              if any(execute["_start"] <= e["_start"] and
                     e["_start"] + e["_dur"] <= execute["_start"]
                     + execute["_dur"] for e in events.get(n, []))]
    assert len(inside) == 5, (inside, sorted(events))


def test_a_raising_dispatch_leaves_no_span_open(monkeypatch):
    """A step that raises inside ``plane[h2d]`` ends the plane's and the
    batcher's phases on its way out: their twins are written (one left
    open never is), nothing later nests under them, and the next request
    has its whole tree."""
    monkeypatch.setenv("ES_TPU_PLANE_HOST_SERVE", "0")
    from elasticsearch_tpu.parallel.dist_search import DistributedKnnPlane
    from elasticsearch_tpu.search.microbatch import PlaneMicroBatcher
    monkeypatch.setattr(PlaneMicroBatcher, "IDLE_EXIT_S", 0.2)
    real = DistributedKnnPlane._get_step
    failures = [RuntimeError("no step today")]

    def flaky(self, k):
        if failures:
            raise failures.pop()
        return real(self, k)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with served_node() as post, tempfile.TemporaryDirectory() as tdir:
        post("/sp/_search", KNN_BODY)
        monkeypatch.setattr(DistributedKnnPlane, "_get_step", flaky)
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            try:
                _, failed = post("/sp/_search", KNN_BODY)
                assert failed["_shards"]["failed"] == 1, failed
            except urllib.error.HTTPError as e:
                assert e.code >= 500
            assert not failures
            headers, resp = post("/sp/_search", KNN_BODY)
            doc = _trace_of(headers)
        finally:
            jax.profiler.stop_trace()
        events = _host_events(tdir)
    for t in threading.enumerate():
        if t.name.startswith("es-dispatcher"):
            t.join(10)
    assert resp["hits"]["hits"]

    def within(inner, outers):
        return any(o["_start"] <= inner["_start"] and
                   inner["_start"] + inner["_dur"] <= o["_start"] + o["_dur"]
                   for o in outers)

    # both dispatches closed every phase they opened: the failed call
    # got as far as plane[h2d], inside its batch[execute]
    for name in ("batch[prep]", "batch[execute]", "batch[fetch]"):
        assert len(events[name]) == 2, (name, events.get(name))
    failed_execute = min(events["batch[execute]"],
                         key=lambda e: e["_start"])
    assert any(within(e, [failed_execute]) for e in events["plane[h2d]"])
    assert not any(within(e, [failed_execute])
                   for e in events["plane[launch]"])
    for name in ("batch[prep]", "batch[execute]", "batch[fetch]"):
        assert not any(within(e, events["plane[h2d]"])
                       for e in events[name]), name
    names = {s["name"] for s in doc["spans"]}
    assert {"http[in]", "shard[knn]", "plane_dispatch", "shard[fetch]",
            "http[out]"} <= names


def test_phases_end_where_the_pipeline_raises():
    with tracing.span("rest[x]", root=True) as sp:
        with pytest.raises(ValueError):
            with tracing.Phases() as ph:
                ph.enter("plane[h2d]")
                assert tracing.current_span_id() != sp.span_id
                raise ValueError("bad shape")
        assert tracing.current_span_id() == sp.span_id
    assert tracing.current_span_id() is None
    assert [s["name"] for s in
            tracing.DEFAULT_STORE.get(sp.trace_id)["spans"]] == \
        ["rest[x]", "plane[h2d]"]


def test_span_without_trace_or_session_is_none():
    assert not tracing.TraceAnnotation.is_enabled()
    with tracing.span("batch[prep]", attrs={"seq": 1}) as sp:
        assert sp is None
    ph = tracing.Phases()
    assert ph.enter("plane[h2d]") is None
    ph.close()


def test_handoff_ends_the_edge_span_and_children_keep_it_as_parent():
    store = tracing.TraceStore()
    edge = tracing.open_span("http[in]", root=True, store=store)
    import contextvars
    ctx = contextvars.copy_context()
    tracing.handoff()
    assert tracing.current_trace_id() is None
    edge.close()                                  # idempotent

    def on_pool():
        with tracing.span("rest[x]", store=store):
            pass

    t = threading.Thread(target=ctx.run, args=(on_pool,))
    t.start()
    t.join(10)
    with tracing.span("http[out]", trace_id=edge.trace_id,
                      parent_span_id=edge.span_id, store=store):
        pass
    doc = store.get(edge.trace_id)
    assert [s["name"] for s in doc["spans"]] == \
        ["http[in]", "rest[x]", "http[out]"]
    assert [c["name"] for c in doc["tree"][0]["children"]] == \
        ["rest[x]", "http[out]"]
    # a with-span is not ended by a hand-off
    with tracing.span("root", root=True, store=store) as sp:
        tracing.handoff()
        assert tracing.current_span_id() == sp.span_id


def _scope_of(op_name, scopes):
    """The first of ``scopes`` whose components appear, in order, among
    the '/'-separated components of an HLO ``op_name`` (``vmap(x)``
    counts as ``x``)."""
    parts = [re.sub(r"^\w+\((.*)\)$", r"\1", p)
             for p in op_name.split("/")]
    for scope in scopes:
        it = iter(parts)
        if all(c in it for c in scope.split("/")):
            return scope
    return None


def test_knn_step_is_named_and_scoped_and_unchanged():
    from elasticsearch_tpu.parallel import dist_search as ds
    from elasticsearch_tpu.parallel.mesh import make_search_mesh
    mesh = make_search_mesh(n_shards=2, n_replicas=1)
    n_pad, dim, k = 1 << 10, 16, 8
    rng = np.random.default_rng(3)
    vecs, vn = ds.prepare_knn_corpus(
        rng.standard_normal((2, n_pad, dim)).astype(np.float32), "cosine")
    exists = rng.random((2, n_pad)) > 0.05
    q = rng.standard_normal((4, dim)).astype(np.float32)
    kw = dict(n_pad=n_pad, dim=dim, k=k, n_shards=2, similarity="cosine",
              block=256)
    step = ds.build_knn_step(mesh, **kw)
    hlo = step.lower(vecs, vn, exists, q).compile().as_text()
    assert "HloModule jit_knn_exact" in hlo
    scopes = ["knn_exact/scores", "knn_exact/block_topk",
              "knn_exact/merge"]
    found = {_scope_of(n, scopes)
             for n in re.findall(r'op_name="([^"]*)"', hlo)}
    assert set(scopes) <= found, found
    # the dot and the top-k sit where the names say
    assert any(_scope_of(n, scopes) == "knn_exact/scores"
               for n in re.findall(r'op_name="([^"]*dot_general)"', hlo))
    assert not any(_scope_of(n, scopes) == "knn_exact/scores"
                   for n in re.findall(r'op_name="([^"]*top_k)"', hlo))
    # scopes are metadata: the same step built with them switched off
    # answers bit for bit the same
    got = [np.asarray(a) for a in step(vecs, vn, exists, q)]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope",
                   lambda name: contextlib.nullcontext())
        plain = ds.build_knn_step(mesh, **kw)
        plain_hlo = plain.lower(vecs, vn, exists, q).compile().as_text()
        want = [np.asarray(a) for a in plain(vecs, vn, exists, q)]
    assert "knn_exact/merge" not in plain_hlo
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


@pytest.mark.parametrize("family,builder", [
    ("bm25_topk", "build_bm25_topk_step"),
    ("bm25_tiered", "build_tiered_bm25_step"),
    ("knn_exact", "build_knn_step"), ("knn_ivf", "build_ivf_knn_step"),
    ("bm25_pruned", "build_pruned_bm25_step"),
    ("bm25_bool", "build_bool_bm25_step"),
    ("fused_hybrid", "build_fused_hybrid_step")])
def test_every_step_builder_names_its_module_and_scope(family, builder):
    import inspect
    from elasticsearch_tpu.parallel import dist_search as ds
    src = inspect.getsource(getattr(ds, builder))
    assert f'_jit_step(step, "{family}")' in src
    assert f'@in_named_scope("{family}")' in src
