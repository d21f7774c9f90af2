"""The reduction from the program's spans and scopes in a profiler trace to
the eight per-layer numbers: each reader's arithmetic on a hand-made trace
(``data/span_trace_planes.json``; a unit there is 1e4 ns) and a live round
trip on the CPU: annotate, trace, read."""

import json
import os
import subprocess
import sys
import types

import pytest

from conftest import BENCH, CHECKOUT
from harness import xplane_spans as xs
from harness.manifest import load_manifest, load_module

U = 1e4 / 1e6            # one unit of the fixture, in ms


@pytest.fixture(scope="module")
def summary():
    with open(os.path.join(BENCH, "tests", "data",
                           "span_trace_planes.json")) as f:
        raw = json.load(f)
    # through JSON, as the helper process hands it to the readers
    return json.loads(json.dumps(xs.reduce(raw)))


def _metric(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


def _edge(summary, name):
    p = _metric(name)["params"]
    return xs.edge_ms(summary, p["from"], p["to"])


def test_edges_join_by_trace_id_across_threads(summary):
    # A: http[in] 0..100 on the loop, http[out] ..9100; B: 50.. ..9600; C
    # has no http[out] in the window and is left out
    assert _edge(summary, "rest.server_ms") == \
        (pytest.approx((9100 + 9550) / 2 * U), 2)
    # http[in].end -> rest[parse].start, on another thread: A 100->300,
    # B 150->500, C 8100->8200
    assert _edge(summary, "rest.pool_wait_ms") == \
        (pytest.approx((200 + 350 + 100) / 3 * U), 3)
    # rest[parse].start -> plane_dispatch.start: A 300->2800, B 500->3000,
    # C 8200->9000
    assert _edge(summary, "rest.pre_batcher_ms") == \
        (pytest.approx((2500 + 2500 + 800) / 3 * U), 3)
    # plane_dispatch.end -> http[out].end: A 7900->9100, B 8300->9600
    assert _edge(summary, "rest.post_batcher_ms") == \
        (pytest.approx((1200 + 1300) / 2 * U), 2)


def test_wake_joins_the_dispatch_by_seq_and_drops_what_it_cannot(summary):
    # batch[fetch] of dispatch 5 ends at 7500; A wakes at 7900, B at 8300.
    # C was carried by dispatch 9, which the trace does not hold: dropped,
    # not guessed
    assert summary["requests"]["cccc"]["dispatch_seq"] == "9"
    assert "9" not in summary["dispatches"]
    assert _edge(summary, "batcher.wake_ms") == \
        (pytest.approx((400 + 800) / 2 * U), 2)


def test_waterfall_identity_on_the_fixture(summary):
    # server = http[in] + pool wait + pre-batcher + plane_dispatch + post,
    # request by request (A: 100 + 200 + 2500 + 5100 + 1200 = 9100)
    a = summary["requests"]["aaaa"]["spans"]
    parts = [a["http[in]"][1] - a["http[in]"][0],
             a["rest[parse]"][0] - a["http[in]"][1],
             a["plane_dispatch"][0] - a["rest[parse]"][0],
             a["plane_dispatch"][1] - a["plane_dispatch"][0],
             a["http[out]"][1] - a["plane_dispatch"][1]]
    assert sum(parts) == a["http[out]"][1] - a["http[in]"][0] == 9100 * 1e4


def test_execute_host_takes_both_step_executions_of_a_dispatch(summary):
    d = summary["dispatches"]["5"]
    assert d["kernel"] == "knn_exact" and d["requests"] == 2
    # two executions of jit_knn_exact lie inside batch[execute]
    # (3300..7300): 3600..5000 and 5200..6800; the third (7600..) does not
    assert [[s / 1e4, e / 1e4] for s, e in d["steps"]] == \
        [[3600, 5000], [5200, 6800]]
    assert set(d["spans"]) == {
        "batch[prep]", "batch[execute]", "batch[fetch]", "plane[h2d]",
        "plane[launch]", "plane[sync]", "plane[d2h]", "plane[decode]"}
    assert xs.execute_host_ms(summary) == \
        (pytest.approx((4000 - 1400 - 1600) * U), 1)


def test_execute_host_leaves_out_the_other_dispatchs_step():
    """Two dispatches in flight (PR 26): A's ``batch[execute]`` 100..400
    holds its own step 120..300 and waits out 100 units of B's (300..500);
    B's 150..600 holds its own and, from 150 to 300, A's. Own steps alone
    would leave A 120 and B 250 units; no step at all runs in A's for 20
    (100..120) and in B's for 100 (500..600)."""
    def disp(seq, a, b):
        st = {"seq": seq, "kernel": "knn_exact", "requests": 8, "b_pad": 8}
        return {"spans": [["batch[execute]", a * 1e4, (b - a) * 1e4, st]],
                "launches": []}
    raw = {"host": [disp(1, 100, 400), disp(2, 150, 600)],
           "devices": [{"name": "/device:TPU:0", "modules": [
               ["jit_knn_exact(3)", 120 * 1e4, 180 * 1e4],
               ["jit_knn_exact(3)", 300 * 1e4, 200 * 1e4]], "ops": []}]}
    s = json.loads(json.dumps(xs.reduce(raw)))
    assert [[a / 1e4, b / 1e4] for a, b in s["step_intervals"]] == \
        [[120, 500]]
    assert [len(d["steps"]) for d in s["dispatches"].values()] == [1, 1]
    assert xs.execute_host_ms(s) == (pytest.approx((20 + 100) / 2 * U), 2)


def test_self_time_is_duration_less_what_children_cover(summary):
    st = summary["span_stats"]
    # request spans, by parent link: shard[knn] 5300 long; its child
    # plane_dispatch covers 5100 (A) and 5300 clipped to 5300 - 100 (B:
    # 3000..8300 inside 2900..8200 -> 5200)
    assert st["shard[knn]"]["count"] == 2
    assert st["shard[knn]"]["self_mean_ms"] == \
        pytest.approx((200 + 100) / 2 * U)
    # http[in]'s children run on other threads, after it has ended: they
    # cover nothing of it
    assert st["http[in]"]["self_mean_ms"] == pytest.approx(100 * U)
    # rest[indices:...] is reported under one name whatever the action
    assert st["rest[...]"]["mean_ms"] == pytest.approx(8500 * U)
    assert st["rest[...]"]["self_mean_ms"] == pytest.approx(200 * U)
    # dispatcher spans, by nesting on their thread: batch[execute] 4000
    # less its five plane[...] children (200+100+3300+100+300)
    assert st["batch[execute]"]["self_mean_ms"] == pytest.approx(0.0)
    assert st["plane[sync]"]["mean_ms"] == pytest.approx(3300 * U)


def test_launches_by_the_span_they_were_launched_in(summary):
    la = summary["launches"]
    assert la["n_requests"] == 3
    assert la["by_span"] == {
        "shard[query_phase]": {"topk_kernel": 2, "_reduce_sum": 2},
        "shard[fetch]": {"take": 2}}


def test_device_time_by_scope(summary):
    assert summary["modules"]["jit_knn_exact"] == \
        {"count": 3, "seconds": pytest.approx(3400 * 1e4 / 1e9)}
    p = _metric("kernels.knn_scores_ms")["params"]
    # scores: 600 + 800 + 400 own units over three executions, and the
    # while's own 200 (the block reads, which XLA names after the loop)
    assert xs.scope_ms(summary, p["module"], p["scopes"]) == \
        pytest.approx(2000 / 3 * U)
    assert xs.scope_ms(summary, p["module"], ["knn_exact/scores"]) == \
        pytest.approx(1800 / 3 * U)
    p = _metric("kernels.knn_topk_ms")["params"]
    # block_topk 500 + 700, merge 100 + 100
    assert xs.scope_ms(summary, p["module"], p["scopes"]) == \
        pytest.approx(1400 / 3 * U)
    # the while's own 200 units are under knn_exact but under no part:
    # only a scope that ends there names them, and nothing inside the loop
    ops = summary["op_self_s"]["jit_knn_exact"]
    unscoped = sum(s for n, s in ops.items() if xs.scope_of(n, [
        "knn_exact/scores", "knn_exact/block_topk",
        "knn_exact/merge"]) is None)
    assert unscoped == pytest.approx(200 * 1e4 / 1e9)
    assert [n for n in ops if xs.scope_of(n, ["knn_exact/while$"])] == \
        ["jit(knn_exact)/shard_map/knn_exact/vmap()/while"]
    assert xs.scope_ms(summary, "jit_body", p["scopes"]) is None
    assert xs.scope_of("jit(f)/knn_exact/vmap()/while/body/closed_call/"
                       "scores/dot_general", ["knn_exact/scores"])
    assert xs.scope_of("jit(f)/knn_exact/merge/top_k",
                       ["knn_exact/scores"]) is None


def test_idle_gaps_go_to_the_innermost_dispatcher_span(summary):
    # busy: 900..1000, 3600..5000, 5200..6800, 7600..8000
    assert summary["idle_s"] == pytest.approx(3600 * 1e4 / 1e9)
    got = {k: round(v / (1e4 / 1e9)) for k, v in summary["idle_gaps"]}
    assert got == {"no dispatcher busy": 2200, "plane[sync]": 300,
                   "plane[decode]": 300, "batch[prep]": 200,
                   "plane[h2d]": 200, "batch[fetch]": 200,
                   "plane[launch]": 100, "plane[d2h]": 100}


def test_recorded_dispatch_from_the_chip():
    """A slice of a real trace (``data/span_trace_recorded.json``: one TPU
    v5 lite, one dispatch of two requests, PR 25): the names, stats and
    ``op_name`` paths as the chip's profiler writes them."""
    with open(os.path.join(BENCH, "tests", "data",
                           "span_trace_recorded.json")) as f:
        s = json.loads(json.dumps(xs.reduce(json.load(f))))
    assert len(s["requests"]) == 2
    (seq, d), = s["dispatches"].items()
    assert {r["dispatch_seq"] for r in s["requests"].values()} == {seq}
    assert (d["kernel"], d["requests"], d["b_pad"]) == ("knn_exact", 2, 2)
    # one execution of the step inside batch[execute]: 36.98 of 65.26 ms
    assert len(d["steps"]) == 1
    # 28.278276 ms of it with the step not running, 17.858504 with no
    # program at all on the device (the request threads' eager programs
    # of that tree ran for the other 10.4)
    assert xs.execute_host_ms(s) == (pytest.approx(17.858504), 1)
    assert s["modules"]["jit_knn_exact"]["count"] == 1
    p = _metric("kernels.knn_scores_ms")["params"]
    # 13.513688 under knn_exact/scores, 8.691714 the loop's own block
    # reads
    assert xs.scope_ms(s, p["module"], p["scopes"]) == \
        pytest.approx(22.205402)
    p = _metric("kernels.knn_topk_ms")["params"]
    assert xs.scope_ms(s, p["module"], p["scopes"]) == \
        pytest.approx(10.622617)
    # what the two metrics leave: the relayout of the corpus argument,
    # which no scope can reach
    ops = s["op_self_s"]["jit_knn_exact"]
    left = {n for n, t in ops.items() if t > 1e-3 and xs.scope_of(
        n, ["knn_exact/scores", "knn_exact/while$",
            "knn_exact/block_topk", "knn_exact/merge"]) is None}
    assert left == {"vecs"}
    # each request launched seven programs of its own, all in the eager
    # query phase, before it could join the batch
    assert s["launches"]["by_span"] == {"shard[query_phase]": {
        "convert_element_type": 4, "broadcast_in_dim": 4, "bitwise_and": 2,
        "_reduce_sum": 2, "topk_kernel": 2}}
    assert _edge(s, "rest.server_ms") == (pytest.approx(741.430524), 2)
    assert _edge(s, "rest.pool_wait_ms") == (pytest.approx(0.660265), 2)
    assert _edge(s, "rest.pre_batcher_ms") == (pytest.approx(91.537365), 2)
    assert _edge(s, "rest.post_batcher_ms") == (pytest.approx(60.243374), 2)
    assert _edge(s, "batcher.wake_ms") == (pytest.approx(3.91195), 2)
    st = s["span_stats"]
    assert st["shard[query_phase]"]["mean_ms"] == pytest.approx(84.352676)
    assert st["plane_dispatch"]["mean_ms"] == pytest.approx(588.930935)


def _pb(fields):
    """A protobuf message from [(field, int | bytes | str)]."""
    out = b""

    def varint(n):
        b = b""
        while True:
            b += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return b

    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def test_op_names_are_read_from_the_events_metadata(tmp_path):
    """On a TPU the HLO ``op_name`` is the ``tf_op`` stat of an op's
    *metadata*; a string, or a reference to a stat metadata whose name is
    the value. Lines are skipped unread; host planes are left alone."""
    def stat_meta(i, name):
        return _pb([(1, i), (2, _pb([(1, i), (2, name)]))])

    def ev_meta(i, name, stats):
        return _pb([(1, i), (2, _pb([(1, i), (2, name)] + [
            (5, st) for st in stats]))])

    dev = _pb([
        (2, "/device:TPU:0"),
        (3, _pb([(2, "XLA Ops"), (4, _pb([(1, 1), (2, 5), (3, 7)]))])),
        (4, ev_meta(1, "%fusion.1 = f32[8] fusion(...)", [
            _pb([(1, 11), (3, 99)]),
            _pb([(1, 10), (5, "jit(knn_exact)/knn_exact/scores/dot:")])])),
        (4, ev_meta(2, "%sort.2 = f32[8] sort(...)",
                    [_pb([(1, 10), (7, 12)])])),
        (4, ev_meta(3, "%copy.3", [_pb([(1, 11), (3, 5)])])),
        (5, stat_meta(10, "tf_op")), (5, stat_meta(11, "flops")),
        (5, stat_meta(12, "jit(knn_exact)/knn_exact/merge/top_k:"))])
    host = _pb([(2, "/host:CPU"),
                (4, ev_meta(1, "x", [_pb([(1, 10), (5, "no")])])),
                (5, stat_meta(10, "tf_op"))])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb([(1, host), (1, dev)]))
    assert xs._metadata_op_names(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion(...)":
            "jit(knn_exact)/knn_exact/scores/dot",
        "%sort.2 = f32[8] sort(...)":
            "jit(knn_exact)/knn_exact/merge/top_k"}}


def test_device_plane_of_a_real_trace_file():
    """``data/v5e_device_slice.xplane.pb``: the device plane of a real
    trace file (one TPU v5 lite, PR 25), cut to one execution of
    ``jit_knn_exact`` at B=1 with its 1,005 op events and the metadata
    they use: what :func:`_metadata_op_names` decodes by field number and
    what ``ProfileData`` reads have to agree on the same bytes."""
    pb = os.path.join(BENCH, "tests", "data", "v5e_device_slice.xplane.pb")
    names = xs._metadata_op_names(pb)["/device:TPU:0"]
    dots = {n for n in names.values() if n.endswith("dot_general")}
    assert dots == {
        "jit(knn_exact)/knn_exact/vmap(scores)/bd,nd->bn/dot_general",
        "jit(knn_exact)/knn_exact/vmap()/while/body/closed_call/scores/"
        "scores/bd,nd->bn/dot_general"}
    raw = xs.read_planes(pb)
    assert raw["host"] == []
    (dev,) = raw["devices"]
    assert dev["name"] == "/device:TPU:0"
    assert [(xs._module_name(m[0]), m[2]) for m in dev["modules"]] == \
        [("jit_knn_exact", 8022335.0)]
    assert len(dev["ops"]) == 1005
    named = [op for op in dev["ops"] if op[3]]
    assert len(named) == 989
    by_event: dict = {}
    for ev, n in names.items():
        by_event.setdefault(ev[:60], set()).add(n)
    assert all(op[3] in by_event[op[0]] for op in named)
    s = xs.reduce(raw)
    assert s["modules"]["jit_knn_exact"]["count"] == 1
    # own time of the ops adds up to the module's (8.021217 of 8.022335)
    assert sum(s["op_self_s"]["jit_knn_exact"].values()) == \
        pytest.approx(0.008021217)
    p = _metric("kernels.knn_scores_ms")["params"]
    assert xs.scope_ms(s, p["module"], p["scopes"]) == \
        pytest.approx(1.564988)
    p = _metric("kernels.knn_topk_ms")["params"]
    assert xs.scope_ms(s, p["module"], p["scopes"]) == \
        pytest.approx(2.873302)


def test_a_device_plane_without_op_names_is_an_error(tmp_path):
    """Were the field numbers to move, every op would read as unscoped:
    the helper fails instead, and ``load`` says so."""
    pb = os.path.join(BENCH, "tests", "data", "v5e_device_slice.xplane.pb")
    moved = tmp_path / "moved.xplane.pb"
    with open(pb, "rb") as f:
        moved.write_bytes(f.read().replace(b"tf_op", b"tf_0p"))
    with pytest.raises(ValueError, match="none of 1005 op events"):
        xs.read_planes(str(moved))


def test_manifest_names_the_eight_and_their_files_exist():
    names = ["rest.server_ms", "rest.pool_wait_ms", "rest.pre_batcher_ms",
             "rest.post_batcher_ms", "batcher.wake_ms",
             "planes.execute_host_ms", "kernels.knn_scores_ms",
             "kernels.knn_topk_ms"]
    per_layer = {m["name"]: m for m in load_manifest()["per_layer"]}
    assert [m for m in per_layer][-8:] == names
    for n in names:
        assert per_layer[n]["moves"] == "search_qps"
        assert per_layer[n]["workloads"] == ["glove100_knn.exact_c64"]
        assert hasattr(load_module("readers", _metric(n)["reader"]), "read")


def test_readers_read_nothing_from_a_trace_without_spans(tmp_path,
                                                         monkeypatch):
    """A parent's trace (no span, ``jit_body``) and a run with no trace at
    all: every reader returns None and raises nothing."""
    monkeypatch.setattr(xs, "CACHE_DIR", str(tmp_path))
    said = []
    ctx = {"cell": types.SimpleNamespace(name="c"), "say": said.append,
           "requests": []}
    readers = {n: load_module("readers", n) for n in (
        "span_edge_ms", "execute_host_ms", "scope_device_ms")}
    for r in readers.values():
        r.xplane_spans = xs
    p = _metric("rest.server_ms")["params"]
    assert readers["span_edge_ms"].read(ctx, p) is None       # no trace
    tdir = tmp_path / "run" / "c" / "trace"
    tdir.mkdir(parents=True)
    (tdir / "spans.json").write_text(json.dumps(xs.reduce(
        {"host": [], "devices": [{"name": "/device:TPU:0", "modules": [
            ["jit_body(1)", 0, 10]], "ops": [["%a", 0, 10, ""]]}]})))
    assert readers["span_edge_ms"].read(ctx, p) is None
    assert readers["execute_host_ms"].read(ctx, {}) is None
    assert readers["scope_device_ms"].read(
        ctx, _metric("kernels.knn_scores_ms")["params"]) is None


LIVE = r"""
import sys, threading, time
sys.path.insert(0, sys.argv[2])
import jax, jax.numpy as jnp
from elasticsearch_tpu.common import tracing

@jax.jit
def prog(x):
    with jax.named_scope("fam/part"):
        return (x @ x.T).sum()

x = jnp.ones((64, 64))
prog(x).block_until_ready()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 1
jax.profiler.start_trace(sys.argv[1], profiler_options=opts)
edge = tracing.open_span("http[in]", root=True, attrs={"bytes_in": 7})
import contextvars
ctx = contextvars.copy_context()
tracing.handoff()

def request():
    with tracing.span("rest[parse]"):
        time.sleep(0.002)
    with tracing.span("rest[indices:data/read/search]"):
        with tracing.span("plane_dispatch") as sp:
            prog(x).block_until_ready()
            sp.attrs["dispatch_seq"] = 41

def dispatcher():
    ph = tracing.Phases()
    ph.enter("batch[prep]", seq=41, requests=1)
    ph.enter("batch[execute]", seq=41, requests=1, b_pad=1, kernel="fam")
    time.sleep(0.003)
    ph.enter("batch[fetch]", seq=41, requests=1)
    ph.close()

t = threading.Thread(target=ctx.run, args=(request,)); t.start(); t.join()
t = threading.Thread(target=dispatcher); t.start(); t.join()
with tracing.span("http[out]", trace_id=edge.trace_id,
                  parent_span_id=edge.span_id, attrs={"status": 200}):
    pass
jax.profiler.stop_trace()
print(edge.trace_id)
"""


def test_live_round_trip_annotate_trace_read(tmp_path):
    """The node's own tracing module under a profiler session on the CPU
    backend, read back by the helper process as a run reads it."""
    from harness import xplane
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", LIVE, str(tmp_path), CHECKOUT], env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    tid = proc.stdout.split()[-1]
    pb = xplane.find_xplane(str(tmp_path))
    out = tmp_path / "spans.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "harness", "xplane_spans.py"),
         pb, str(out)], env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    summary = json.loads(out.read_text())
    req = summary["requests"][tid]
    assert set(req["spans"]) == {
        "http[in]", "rest[parse]", "rest[indices:data/read/search]",
        "plane_dispatch", "http[out]"}
    assert req["dispatch_seq"] == "41"          # set when the span ended
    d = summary["dispatches"]["41"]
    assert d["kernel"] == "fam" and d["b_pad"] == 1
    ms, n = xs.edge_ms(summary, {"span": "http[in]", "edge": "start"},
                       {"span": "http[out]", "edge": "end"})
    assert n == 1 and ms > 5.0
    wake, n = xs.edge_ms(
        summary, {"span": "batch[fetch]", "edge": "end", "of": "dispatch"},
        {"span": "plane_dispatch", "edge": "end"})
    assert n == 1 and wake < 0                  # the threads ran in turn
    # the program was launched from inside plane_dispatch
    assert summary["launches"]["by_span"]["plane_dispatch"] == {"prog": 1}
    said = []
    xs.report(summary, said.append, 1)
    assert any("1 joined from http[in] to http[out]" in s for s in said)


def test_traced_rehearsal_reports_the_span_metrics(rehearsal_manifest):
    """``run.py --trace 1`` end to end on the CPU, with a manifest of this
    test's own: the rehearsal's, plus the eight metrics for its kNN cell.
    The five that read host spans alone have values and the waterfall
    closes; the three that need the device plane (step executions, scopes)
    find none on the CPU platform and are left out. No number here is a
    device number."""
    import argparse
    import run as bench_run
    mine = [dict(m, workloads=["tiny_knn.knn_c4"])
            for m in load_manifest()["per_layer"][-8:]]
    manifest = dict(rehearsal_manifest,
                    per_layer=rehearsal_manifest["per_layer"] + mine)
    said = []
    real_say = bench_run.say
    bench_run.say = lambda m: (said.append(m), real_say(m))
    try:
        r = bench_run.run(
            argparse.Namespace(workload="tiny_knn.knn_c4", seed=7,
                               seconds=2.0, trace=1, control=0),
            rehearsal=True, manifest=manifest)
    finally:
        bench_run.say = real_say
    assert r["correct"] is True
    got = {k: v["value"] for k, v in r["metrics"].items()}
    for name in ("rest.server_ms", "rest.pool_wait_ms",
                 "rest.pre_batcher_ms", "rest.post_batcher_ms"):
        assert got[name] > 0, (name, got)
    assert "batcher.wake_ms" in got
    for name in ("planes.execute_host_ms", "kernels.knn_scores_ms",
                 "kernels.knn_topk_ms"):
        assert name not in got
    assert "batcher.mean_batch" in got               # the old ones stay
    # at least 95 % of the answered requests joined edge to edge
    line = next(s for s in said if " joined from http[in]" in s)
    share = float(line.split("(")[1].split(" %")[0])
    assert 95.0 <= share <= 100.0, line
    # server = http[in] + pool wait + pre + plane_dispatch + post
    stats = {s.split()[1]: float(s.split()[3]) for s in said
             if s.startswith("spans:   ")}
    parts = (stats["http[in]"] + got["rest.pool_wait_ms"]
             + got["rest.pre_batcher_ms"] + stats["plane_dispatch"]
             + got["rest.post_batcher_ms"])
    assert parts == pytest.approx(got["rest.server_ms"], rel=0.05)
