"""Continuous profiler: sampling, attribution, rendering, fan-in.

Covers the always-on flamegraph sampler (``common/contprof.py``), the
shared idle classifier it lends to hot-threads, the ``es-`` thread
naming sweep, the ``/_profiler/flamegraph`` endpoint (params, filters,
formats, cluster merge), the ``flame_dump`` renderer, and the
``bench_diff`` overhead gate.
"""

import ast
import functools
import json
import os
import sys
import tempfile
import threading
import time
import traceback

import pytest

from elasticsearch_tpu.common import contprof
from elasticsearch_tpu.common.contprof import (
    ContinuousProfiler,
    _Window,
    classify_idle,
    collapsed_text,
    flame_json,
    merge_docs,
    sample_stacks,
)

FS = traceback.FrameSummary


def _spin_until(flag):
    while flag["on"]:
        sum(i * i for i in range(2000))


# ---------------------------------------------------------------------------
# idle classifier (shared with hot_threads) — satellite #1
# ---------------------------------------------------------------------------


def test_classify_idle_parked_thread():
    # normal parked thread: waiter is the INNERMOST frame
    parked = [FS("/x/app.py", 10, "serve"),
              FS("/usr/lib/python3.11/threading.py", 320, "wait")]
    assert classify_idle(parked)


def test_classify_idle_busy_under_thread_run_is_busy():
    """Regression for the old top-frame-only bug's inverse: app code
    running UNDER ``Thread.run`` must stay busy — ``run``/``_bootstrap``
    are not waiter frames."""
    busy = [FS("/usr/lib/python3.11/threading.py", 975, "_bootstrap"),
            FS("/usr/lib/python3.11/threading.py", 1012, "run"),
            FS("/x/app.py", 44, "score_block")]
    assert not classify_idle(busy)


def test_classify_idle_waiter_one_frame_out():
    """Regression: a runtime waiter at stack[-2] with an app frame
    innermost (e.g. a callback evaluated inside ``Condition.wait``'s
    bookkeeping) is parked, not hot.  The old hot-threads classifier
    looked only at the innermost frame and called this busy."""
    inverted = [FS("/x/app.py", 10, "loop"),
                FS("/usr/lib/python3.11/threading.py", 320, "wait"),
                FS("/x/app.py", 12, "predicate")]
    assert classify_idle(inverted)
    # and the empty stack degenerates to idle
    assert classify_idle([])


def test_classify_idle_live_parked_vs_busy_pair():
    """Seeded pair: an Event-parked thread classifies idle while a
    spinning sibling classifies busy, from real sampled stacks."""
    ev = threading.Event()
    flag = {"on": True}
    parked = threading.Thread(target=ev.wait, name="es-warmup-parked",
                              daemon=True)
    busy = threading.Thread(target=_spin_until, args=(flag,),
                            name="es-repack-busy", daemon=True)
    parked.start()
    busy.start()
    time.sleep(0.05)
    try:
        stacks = sample_stacks()
        assert classify_idle(stacks[parked.ident])
        assert not classify_idle(stacks[busy.ident])
    finally:
        flag["on"] = False
        ev.set()
        parked.join(timeout=2)
        busy.join(timeout=2)


def test_hot_threads_uses_shared_classifier_and_keeps_format():
    """hot_threads output stays byte-parse-compatible and, with the
    shared classifier, surfaces the busy thread while hiding the
    parked one."""
    from elasticsearch_tpu.utils import hot_threads as ht

    assert ht._IDLE_HINTS is contprof.IDLE_HINTS
    ev = threading.Event()
    flag = {"on": True}
    parked = threading.Thread(target=ev.wait, name="es-warmup-ht-parked",
                              daemon=True)
    busy = threading.Thread(target=_spin_until, args=(flag,),
                            name="es-repack-ht-busy", daemon=True)
    parked.start()
    busy.start()
    try:
        out = ht.hot_threads(threads=4, interval_ms=80, snapshots=3,
                             ignore_idle=True)
    finally:
        flag["on"] = False
        ev.set()
        parked.join(timeout=2)
        busy.join(timeout=2)
    assert "Hot threads at" in out and "cpu usage by thread" in out
    assert "es-repack-ht-busy" in out
    assert "es-warmup-ht-parked" not in out


# ---------------------------------------------------------------------------
# the sampler's frame walk: code objects, no source lines
# ---------------------------------------------------------------------------


def _nest(depth, fn, *args):
    """Call ``fn`` ``depth`` frames below here."""
    if depth:
        return _nest(depth - 1, fn, *args)
    return fn(*args)


def _hold_busy(gate):
    """Blocked inside a C call with an application frame innermost: the
    sampler sees a busy thread whose stack stands still."""
    gate.acquire()
    gate.release()
    return True


def _inverted_park(cond, gate):
    # Condition.wait_for runs the predicate from threading.py, so the
    # application frame is innermost and the runtime waiter one outward
    with cond:
        cond.wait_for(functools.partial(_hold_busy, gate))


def _start_named(name, target, *args):
    t = threading.Thread(target=target, args=args, name=name, daemon=True)
    t.start()
    return t


def _reference_pass(frames, win):
    """The sampler's fold as it was built on ``traceback.extract_stack``
    (source lines and all), for the equivalence check."""
    names = {t.ident: t.name for t in threading.enumerate()}
    for tid, frame in frames.items():
        stack = traceback.extract_stack(frame, limit=contprof.STACK_DEPTH)
        if classify_idle(stack):
            win.idle += 1
            continue
        path = (contprof.thread_role(tid, names.get(tid, "")), "-", "-") \
            + tuple(f"{fs.filename.rsplit('/', 1)[-1]}:{fs.name}"
                    for fs in stack)
        win.fold(path, cap=1 << 16)


def test_frame_walk_folds_what_extract_stack_folds(monkeypatch):
    """Threads parked below and above STACK_DEPTH, busy below and above
    it, and one parked under the inverted listing: one pass of the
    code-object walk folds the same rows and counts the same idle
    threads as a pass built on ``traceback.extract_stack``."""
    depth = contprof.STACK_DEPTH
    ev = threading.Event()
    gate = threading.Lock()
    gate.acquire()
    cond = threading.Condition()
    threads = [
        _start_named("es-warmup-shallow", _nest, 3, ev.wait),
        _start_named("es-warmup-deep", _nest, depth + 10, ev.wait),
        _start_named("es-repack-shallow", _nest, 2, _hold_busy, gate),
        _start_named("es-repack-deep", _nest, depth + 7, _hold_busy, gate),
        _start_named("es-dispatcher-inv", _nest, 4, _inverted_park, cond,
                     gate),
    ]
    try:
        time.sleep(0.1)
        frames = sys._current_frames()
        live = {t.ident: frames[t.ident] for t in threads}
        ref = _Window(started=0.0)
        _reference_pass(live, ref)
        monkeypatch.setattr(contprof.sys, "_current_frames", lambda: live)
        prof = ContinuousProfiler(interval_ms_=50.0, cap=1 << 16)
        assert prof.sample_once() == 2
        win = prof._current
        assert ref.idle == win.idle == 3
        assert ref.busy == win.busy == 2
        assert sorted(win.rows()) == sorted(ref.rows())
        deep = [p for p, _n in win.rows() if p[0] == "repack"
                and len(p) == 3 + depth]
        assert deep and deep[0][-1] == "test_contprof.py:_hold_busy"
        idle, labels, read = contprof._walk_stack(
            live[threads[4].ident], {})
        assert idle and labels is None and read == 2
    finally:
        ev.set()
        gate.release()
        for t in threads:
            t.join(timeout=2)


def test_parked_pass_reads_no_source_and_two_frames(monkeypatch):
    """Cost by calls, not time: a pass over 64 parked threads makes no
    ``linecache`` line read or cache check and no ``os.stat``, and each
    parked thread's walk reads at most its innermost two frames."""
    import linecache

    ev = threading.Event()
    threads = [_start_named(f"es-rest-http-park{i}", _nest, 30, ev.wait)
               for i in range(64)]
    me = threading.get_ident()
    calls = []

    def recorder(name, real):
        def wrapped(*a, **kw):
            if threading.get_ident() == me:
                calls.append(name)
            return real(*a, **kw)
        return wrapped

    try:
        time.sleep(0.1)
        for name, mod in (("getline", linecache), ("checkcache", linecache),
                          ("lazycache", linecache), ("stat", os)):
            monkeypatch.setattr(mod, name, recorder(name, getattr(mod,
                                                                  name)))
        prof = ContinuousProfiler(interval_ms_=50.0)
        prof.sample_once()
        monkeypatch.undo()
        assert calls == []
        assert prof._current.idle >= 64
        frames = sys._current_frames()
        traits: dict = {}
        for t in threads:
            idle, labels, read = contprof._walk_stack(frames[t.ident],
                                                      traits)
            assert idle and labels is None and read <= 2
    finally:
        ev.set()
        for t in threads:
            t.join(timeout=2)


# ---------------------------------------------------------------------------
# thread naming sweep — satellite #2
# ---------------------------------------------------------------------------


def _first_literal(node):
    """The leading string literal of a name= value: Constant, or the
    first piece of an f-string."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr) and node.values:
        head = node.values[0]
        if isinstance(head, ast.Constant) and isinstance(head.value, str):
            return head.value
    return None


def test_every_package_thread_is_named_with_es_prefix():
    """Every ``threading.Thread(...)`` in the package passes an ``es-``
    name and every ``ThreadPoolExecutor(...)`` an ``es-`` prefix, so
    profiler pool attribution never lands in 'other'."""
    pkg = os.path.join(os.path.dirname(__file__), "..", "elasticsearch_tpu")
    offenders = []
    for root, _dirs, files in os.walk(pkg):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            with open(path, "r", encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), filename=path)
            rel = os.path.relpath(path, pkg)
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                name = None
                if isinstance(node.func, ast.Attribute):
                    name = node.func.attr
                elif isinstance(node.func, ast.Name):
                    name = node.func.id
                if name == "Thread":
                    kw = {k.arg: k.value for k in node.keywords}
                    lit = _first_literal(kw.get("name"))
                    if lit is None or not lit.startswith("es-"):
                        offenders.append(f"{rel}:{node.lineno} Thread")
                elif name == "ThreadPoolExecutor":
                    kw = {k.arg: k.value for k in node.keywords}
                    lit = _first_literal(kw.get("thread_name_prefix"))
                    if lit is None or not lit.startswith("es-"):
                        offenders.append(f"{rel}:{node.lineno} Executor")
    assert not offenders, "anonymous/unprefixed threads: " + ", ".join(
        offenders)


def test_thread_role_resolution():
    assert contprof.thread_role(-1, "es-dispatcher-abc") == "dispatcher"
    assert contprof.thread_role(-1, "es-rest-http-n1_0") == "rest"
    assert contprof.thread_role(-1, "MainThread") == "main"
    assert contprof.thread_role(-1, "weird") == "other"
    tok_ident = threading.get_ident()
    contprof.register_thread("sampler")
    try:
        assert contprof.thread_role(tok_ident, "whatever") == "sampler"
    finally:
        with contprof._ATTR_LOCK:
            contprof._ROLES.pop(tok_ident, None)


# ---------------------------------------------------------------------------
# windows, trie cap, rotation
# ---------------------------------------------------------------------------


def test_window_fold_rows_and_node_cap():
    w = _Window(started=0.0)
    p1 = ("dispatcher", "a", "s1", "m.py:f", "m.py:g")
    p2 = ("dispatcher", "a", "s1", "m.py:f")
    for _ in range(3):
        assert w.fold(p1, cap=16)
    assert w.fold(p2, cap=16)
    rows = dict(w.rows())
    # p1 is a leaf with 3 self samples; p2's count includes the deeper
    # passes so its SELF count is 1
    assert rows[p1] == 3
    assert rows[p2] == 1
    # cap: a fresh window with a tiny cap truncates new branches
    w2 = _Window(started=0.0)
    assert w2.fold(("rest", "-", "-", "a.py:x"), cap=4)
    assert not w2.fold(("rest", "-", "-", "b.py:y", "c.py:z"), cap=4)
    assert w2.truncated >= 1


def test_window_rotation_with_fake_clock():
    ev = threading.Event()
    helper = threading.Thread(target=ev.wait, name="es-warmup-rotate",
                              daemon=True)
    helper.start()                      # ensures >=1 sampled thread
    try:
        now = [100.0]
        prof = ContinuousProfiler(clock=lambda: now[0],
                                  interval_ms_=10.0, window_s=5.0)
        prof.sample_once(now=now[0])
        first = prof.top_doc(window="current")["samples"]
        assert first >= 1
        now[0] += 6.0                   # past the window boundary
        prof.sample_once(now=now[0])
        prev = prof.top_doc(window="previous")
        cur = prof.top_doc(window="current")
        both = prof.top_doc(window="both")
        assert prev["samples"] == first
        assert cur["samples"] >= 1
        assert both["samples"] == prev["samples"] + cur["samples"]
    finally:
        ev.set()
        helper.join(timeout=2)


# ---------------------------------------------------------------------------
# attribution: request threads and dispatcher stamping
# ---------------------------------------------------------------------------


def test_request_thread_attribution_with_live_shape_upgrade():
    """A request-bound thread is attributed (pool=rest, tenant, shape)
    and a mid-request ``set_shape`` upgrade is visible to the sampler
    through the shared holder."""
    from elasticsearch_tpu.common import flightrec

    ready = threading.Event()
    flag = {"on": True}

    def worker():
        tok = contprof.bind_request_thread("ten-x")
        st = flightrec.bind_shape("shape-early")
        flightrec.set_shape("shape-final")
        ready.set()
        try:
            _spin_until(flag)
        finally:
            flightrec.reset_shape(st)
            contprof.unbind_request_thread(tok)

    t = threading.Thread(target=worker, name="es-rest-attr-worker",
                         daemon=True)
    t.start()
    assert ready.wait(2)
    prof = ContinuousProfiler(interval_ms_=2.0)
    try:
        for _ in range(10):
            prof.sample_once()
            time.sleep(0.002)
    finally:
        flag["on"] = False
        t.join(timeout=2)
    doc = prof.top_doc(window="both")
    rows = [r for r in doc["rows"] if r["tenant"] == "ten-x"]
    assert rows, doc["rows"]
    assert all(r["pool"] == "rest" for r in rows)
    assert all(r["shape"] == "shape-final" for r in rows)


def test_shape_alias_converges_upgraded_ids():
    """A mid-request set_shape upgrade (structural fingerprint -> plan
    id) aliases the early id onto the final one; render-time resolution
    merges both sides of the upgrade into ONE row, chains included."""
    contprof.note_shape_alias("qs-unit-a", "qs-unit-b")
    contprof.note_shape_alias("qs-unit-b", "qs-unit-c")
    assert contprof.resolve_shape("qs-unit-a") == "qs-unit-c"
    assert contprof.resolve_shape("qs-unit-zzz") == "qs-unit-zzz"
    prof = ContinuousProfiler(interval_ms_=5.0)
    with prof._lock:
        prof._current.fold(("rest", "t", "qs-unit-a", "m.py:f"), cap=64)
        prof._current.fold(("rest", "t", "qs-unit-c", "m.py:f"), cap=64)
    doc = prof.top_doc(window="current")
    rows = [r for r in doc["rows"] if r["tenant"] == "t"]
    assert len(rows) == 1
    assert rows[0]["shape"] == "qs-unit-c" and rows[0]["samples"] == 2


def test_dispatch_binding_stamps_and_restores():
    tok = contprof.bind_dispatch("ten-d", "shape-d")
    ident = threading.get_ident()
    with contprof._ATTR_LOCK:
        assert contprof._DISPATCH[ident] == ("ten-d", "shape-d")
    contprof.unbind_dispatch(tok)
    with contprof._ATTR_LOCK:
        assert ident not in contprof._DISPATCH


# ---------------------------------------------------------------------------
# renderers + cluster merge
# ---------------------------------------------------------------------------


def _doc_with(rows):
    return {"rows": [dict(r) for r in rows],
            "samples": sum(r["samples"] for r in rows),
            "idle_samples": 0, "truncated": 0, "trie_nodes": len(rows)}


def test_collapsed_and_flame_json_rendering():
    rows = [{"pool": "dispatcher", "tenant": "a", "shape": "s1",
             "stack": ["m.py:f", "m.py:g"], "samples": 3},
            {"pool": "rest", "tenant": "b", "shape": "-",
             "stack": ["r.py:h"], "samples": 1}]
    text = collapsed_text(rows)
    lines = text.splitlines()
    assert lines[0] == "dispatcher;a;s1;m.py:f;m.py:g 3"
    assert lines[1] == "rest;b;-;r.py:h 1"
    tree = flame_json(rows)
    assert tree["name"] == "all" and tree["value"] == 4
    pools = {c["name"] for c in tree["children"]}
    assert pools == {"dispatcher", "rest"}


def test_merge_docs_sums_paths_and_truncates_after_merge():
    a = _doc_with([
        {"pool": "dispatcher", "tenant": "a", "shape": "s1",
         "stack": ["m.py:f"], "samples": 10},
        {"pool": "rest", "tenant": "a", "shape": "-",
         "stack": ["r.py:h"], "samples": 1}])
    b = _doc_with([
        {"pool": "rest", "tenant": "a", "shape": "-",
         "stack": ["r.py:h"], "samples": 10},
        {"pool": "main", "tenant": "-", "shape": "-",
         "stack": ["x.py:y"], "samples": 2}])
    merged = merge_docs([a, b], limit=2)
    rows = merged["rows"]
    assert len(rows) == 2
    # identical paths summed ACROSS nodes before the limit applies:
    # rest row totals 11 and survives, the per-node-top dispatcher row
    # (10) survives, the main row (2) is truncated after the merge
    assert rows[0]["samples"] == 11 and rows[0]["pool"] == "rest"
    assert rows[1]["samples"] == 10 and rows[1]["pool"] == "dispatcher"
    assert merged["rows_dropped"] == 1
    assert merged["samples"] == a["samples"] + b["samples"]


# ---------------------------------------------------------------------------
# REST endpoint + acceptance workload
# ---------------------------------------------------------------------------


@pytest.fixture()
def api_with_corpus():
    from elasticsearch_tpu.node.indices_service import IndicesService
    from elasticsearch_tpu.rest.api import RestAPI
    with tempfile.TemporaryDirectory() as d:
        api = RestAPI(IndicesService(d))
        api.handle("PUT", "/prof", "", json.dumps(
            {"mappings": {"properties": {"body": {"type": "text"}}}}
        ).encode())
        vocab = ("quick brown fox jumps over the lazy dog near the "
                 "riverbank while a red panda naps").split()
        lines = []
        for i in range(600):
            words = " ".join(vocab[(i + j) % len(vocab)] for j in range(16))
            lines.append(json.dumps({"index": {"_index": "prof",
                                               "_id": str(i)}}))
            lines.append(json.dumps({"body": words}))
        api.handle("POST", "/_bulk", "", ("\n".join(lines) + "\n").encode())
        api.handle("POST", "/prof/_refresh", "", b"")
        yield api
        api.close()


def test_flamegraph_endpoint_param_validation(api_with_corpus):
    api = api_with_corpus
    st, _ct, p = api.handle("GET", "/_profiler/flamegraph", "limit=x", b"")
    assert st == 400, p
    st, _ct, p = api.handle("GET", "/_profiler/flamegraph", "window=zzz", b"")
    assert st == 400, p
    st, _ct, p = api.handle("GET", "/_profiler/flamegraph", "format=xml", b"")
    assert st == 400, p


def test_flamegraph_endpoint_disabled_reports_enabled_false(
        api_with_corpus, monkeypatch):
    monkeypatch.setenv("ES_TPU_CONTPROF", "0")
    contprof.close_profiler()
    st, ct, p = api_with_corpus.handle(
        "GET", "/_profiler/flamegraph", "", b"")
    assert st == 200
    doc = json.loads(p)
    assert doc["enabled"] is False
    assert doc["rows"] == []
    assert doc["node"]


def test_flamegraph_workload_attributes_heavy_tenant(
        api_with_corpus, monkeypatch):
    """Acceptance: a CPU-heavy tenant A at one fixed query shape versus
    a near-idle tenant B yields a flamegraph whose dominant
    (pool, tenant, shape) names tenant A's shape — cross-checked
    against the query-insights top shape — in the dispatcher or rest
    pool."""
    api = api_with_corpus
    # tenant B's match_all compiles its program on first use (~0.6 s of
    # rest samples on a CPU backend); warm it before the sampler starts
    # so the window holds what each tenant's traffic costs, not a compile
    st, _ct, p = api.handle("POST", "/prof/_search", "",
                            json.dumps({"query": {"match_all": {}}}).encode())
    assert st == 200, p
    monkeypatch.setenv("ES_TPU_CONTPROF", "1")
    monkeypatch.setenv("ES_TPU_CONTPROF_INTERVAL_MS", "2")
    contprof.close_profiler()
    prof = contprof.ensure_profiler()
    assert prof is not None and prof.running
    try:
        # tenant B: two light, differently-shaped requests
        api.handle("GET", "/_cluster/health", "__x_opaque_id=tenant-b", b"")
        api.handle("POST", "/prof/_search", "__x_opaque_id=tenant-b",
                   json.dumps({"query": {"match_all": {}}}).encode())
        # tenant A: a sustained burn at ONE shape (the cache-busting
        # _i param keeps the request cache out of the way; the body is
        # precomputed so driver overhead stays off the profile)
        qbody = json.dumps({"query": {"match": {
            "body": "quick brown fox lazy dog"}}, "size": 20}).encode()
        deadline = time.time() + 2.5
        i = 0
        while time.time() < deadline:
            st, _ct, p = api.handle(
                "POST", "/prof/_search",
                f"request_cache=false&__x_opaque_id=tenant-a&_i={i}",
                qbody)
            assert st == 200, p
            i += 1
        st, _ct, payload = api.handle(
            "GET", "/_profiler/flamegraph", "window=both&limit=512", b"")
        assert st == 200
        doc = json.loads(payload)
        assert doc["enabled"] is True
        assert doc["samples"] > 20, doc
        dom = doc["dominant"]
        assert dom["tenant"] == "tenant-a", doc["attribution"]
        assert dom["pool"] in ("dispatcher", "rest", "data")
        assert dom["shape"] not in ("", "-")
        # the dominant shape IS tenant A's search shape per insights
        # (the alias map converges the structural fingerprint onto the
        # plan id insights reports)
        st, _ct, ip = api.handle("GET", "/_insights/top_queries",
                                 "metric=count&limit=3", b"")
        shapes = [r["shape"] for r in json.loads(ip)["shapes"]]
        assert dom["shape"] in shapes
        # tenant filter narrows to tenant B's rows only
        st, _ct, fp = api.handle(
            "GET", "/_profiler/flamegraph",
            "window=both&tenant=tenant-a&limit=512", b"")
        fdoc = json.loads(fp)
        assert fdoc["rows"] and all(
            r["tenant"] == "tenant-a" for r in fdoc["rows"])
        # collapsed rendering
        st, ct, cp = api.handle(
            "GET", "/_profiler/flamegraph",
            "window=both&format=collapsed&limit=32", b"")
        assert st == 200 and ct.startswith("text/plain")
        line = cp.decode() if isinstance(cp, bytes) else cp
        assert line.splitlines()[0].rsplit(" ", 1)[1].isdigit()
    finally:
        contprof.close_profiler()


@pytest.mark.slow
def test_cluster_fanin_merges_nodes(tmp_path, monkeypatch):
    """The cluster REST layer fans /_profiler/flamegraph out to every
    node and merges per-path — nodes_reporting reflects the fleet."""
    from elasticsearch_tpu.node.cluster_node import ClusterNode

    monkeypatch.setenv("ES_TPU_CONTPROF", "1")
    monkeypatch.setenv("ES_TPU_CONTPROF_INTERVAL_MS", "5")
    contprof.close_profiler()
    base = 29790
    peers = {f"cp{i}": ("127.0.0.1", base + i) for i in range(2)}
    nodes = [ClusterNode(f"cp{i}", "127.0.0.1", base + i, peers,
                         str(tmp_path / f"cp{i}"), seed=i)
             for i in range(2)]
    try:
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            if any(n.coordinator.mode == "LEADER" for n in nodes):
                break
            time.sleep(0.05)
        contprof.ensure_profiler()
        time.sleep(0.1)
        st, _ct, payload = nodes[0].rest.handle(
            "GET", "/_profiler/flamegraph", "window=both&limit=64", b"")
        assert st == 200
        doc = json.loads(payload)
        assert doc.get("nodes_reporting") == 2
        assert "rows" in doc and "attribution" in doc
        st, ct, _text = nodes[0].rest.handle(
            "GET", "/_profiler/flamegraph",
            "window=both&format=collapsed&limit=8", b"")
        assert st == 200 and ct.startswith("text/plain")
    finally:
        contprof.close_profiler()
        for n in nodes:
            try:
                n.stop()
            except Exception:
                pass


# ---------------------------------------------------------------------------
# flame_dump CLI — satellite #3
# ---------------------------------------------------------------------------


def _burst_doc():
    contprof.close_profiler()       # force the deterministic burst path
    flag = {"on": True}
    t = threading.Thread(target=_spin_until, args=(flag,),
                         name="es-dispatcher-dumpburn", daemon=True)
    t.start()
    try:
        doc = contprof.capture_doc(limit=64)
    finally:
        flag["on"] = False
        t.join(timeout=2)
    assert doc["rows"]
    return doc


def test_flame_dump_collapsed_and_html(tmp_path, capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "flame_dump", os.path.join(os.path.dirname(__file__), "..",
                                   "scripts", "flame_dump.py"))
    fd = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fd)

    doc = _burst_doc()
    src = tmp_path / "prof.json"
    src.write_text(json.dumps(doc))
    assert fd.main([str(src)]) == 0
    out = capsys.readouterr().out
    assert out.strip() and out.splitlines()[0].rsplit(" ", 1)[1].isdigit()
    html = tmp_path / "prof.html"
    assert fd.main([str(src), "--html", str(html)]) == 0
    body = html.read_text()
    assert body.lstrip().startswith("<!DOCTYPE html") or "<html" in body
    assert "dispatcher" in body
    # capture-shaped input (a watchdog capture embedding the profile)
    wrapped = tmp_path / "cap.json"
    wrapped.write_text(json.dumps({"trigger": "slo_red", "profile": doc}))
    assert fd.main([str(wrapped)]) == 0
    assert capsys.readouterr().out.strip()


# ---------------------------------------------------------------------------
# bench_diff overhead gate — satellite #6
# ---------------------------------------------------------------------------


def _load_bench_diff():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_diff_cp", os.path.join(os.path.dirname(__file__), "..",
                                      "scripts", "bench_diff.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(contprof_block):
    cfg = {"value": 100.0}
    if contprof_block is not None:
        cfg["contprof"] = contprof_block
    return {"configs": {"rest_serving_32_clients": cfg}}


def test_bench_diff_contprof_skip_pass_fail():
    bd = _load_bench_diff()
    new_ok = _cfg({"on_qps": 100.0, "off_qps": 101.0,
                   "pct_off_vs_on": 1.0})
    new_bad = _cfg({"on_qps": 100.0, "off_qps": 106.0,
                    "pct_off_vs_on": 6.0})
    old_nopair = _cfg(None)
    old_pair = _cfg({"on_qps": 99.0, "off_qps": 100.0,
                     "pct_off_vs_on": 1.0})
    # first landing: old side has no contprof pair -> one-sided SKIP
    lines, fails = bd._contprof_check(old_nopair, new_ok)
    assert not fails
    assert any("SKIP" in ln for ln in lines)
    # within gate
    lines, fails = bd._contprof_check(old_pair, new_ok)
    assert not fails
    # over gate
    lines, fails = bd._contprof_check(old_pair, new_bad)
    assert fails
    assert any("CONTPROF-OVERHEAD" in ln for ln in lines)


# ---------------------------------------------------------------------------
# self-metering
# ---------------------------------------------------------------------------


def test_self_metrics_families_present_and_counting():
    from elasticsearch_tpu.common.telemetry import TelemetryRegistry

    reg = TelemetryRegistry()
    prof = ContinuousProfiler(registry=reg, interval_ms_=5.0)
    text = reg.prometheus_text()
    for fam in ("es_contprof_samples_total",
                "es_contprof_stacks_retained_total",
                "es_contprof_dropped_total",
                "es_contprof_duty_cycle"):
        assert fam in text, text
    ev = threading.Event()
    helper = threading.Thread(target=ev.wait, name="es-warmup-meter",
                              daemon=True)
    helper.start()                  # ensures >=1 sampled thread
    try:
        prof.sample_once()
        prof.sample_once()
    finally:
        ev.set()
        helper.join(timeout=2)
    text = reg.prometheus_text()
    line = [ln for ln in text.splitlines()
            if ln.startswith("es_contprof_samples_total")][0]
    assert float(line.rsplit(" ", 1)[1]) >= 2.0
