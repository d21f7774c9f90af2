"""``harness/host_spans.py``: the host's CPU and waiting from the twins'
clocks, on host-plane events written by hand, and on a traced CPU
rehearsal of ``tiny_knn.knn_c4`` with the eight host metrics added to a
copy of the rehearsal manifest (they need no device plane). Nothing here
is a device number."""

import argparse
import json
import os

import pytest

from conftest import BENCH
import run as bench_run
from harness import host_spans, xplane_spans
from harness.manifest import load_module

SEARCH = xplane_spans.SEARCH_SPANS
MS = 1_000_000                          # ns


def _span(name, start_ms, dur_ms, cpu0_us, cpu1_us, **stats):
    return [name, start_ms * MS, dur_ms * MS,
            dict(stats, cpu0_us=cpu0_us, cpu1_us=cpu1_us)]


def _request(tid, loop_line, handler_line, render_line=None):
    """One request's spans as ``(line, event)``: ``http[in]`` and
    ``http[out]`` on the loop's line, the rest on the handler's (its
    ``rest[render]`` on ``render_line`` where given)."""
    t = {"trace_id": "t" + tid}
    return [
        (loop_line, _span("http[in]", 0, 1, 100, 300, **t)),
        (handler_line, _span("rest[parse]", 2, 1, 5_000, 5_400, **t)),
        (handler_line, _span("plane_dispatch", 4, 30, 5_600, 5_900, **t)),
        (render_line if render_line is not None else handler_line,
         _span("rest[render]", 40, 2, 6_500, 7_100, **t)),
        (loop_line, _span("http[out]", 60, 3, 900, 1_600, **t))]


def _raw(*groups, lines=3):
    host = [[] for _ in range(lines)]
    for group in groups:
        for li, ev in group:
            host[li].append(ev)
    return {"host": host}


def test_edge_cpu_is_a_subtraction_on_one_thread():
    assert host_spans.cpu_between((1, 5_900), (1, 7_100)) == 1_200
    # two threads' clocks do not subtract, and a missing edge gives none
    assert host_spans.cpu_between((1, 5_900), (2, 7_100)) is None
    assert host_spans.cpu_between(None, (1, 7_100)) is None
    s = host_spans.reduce(_raw(_request("a", 0, 1)), SEARCH)
    # after the dispatch: 7100 - 5900 on the handler, 1600 - 900 on the loop
    assert s["post_batcher_cpu_ms"] == [pytest.approx(1.9), 1]
    # http[in] 0.2 + parse.cpu0..render.cpu1 2.1 + http[out] 0.7
    assert s["request_cpu_ms"] == [pytest.approx(3.0), 1]
    assert s["parts_ms"]["http[in]"] == [pytest.approx(0.2), 1]


def test_a_pair_of_edges_on_two_threads_is_refused():
    # rest[render] on another thread than plane_dispatch and rest[parse]:
    # the request is left out, not guessed
    s = host_spans.reduce(_raw(_request("a", 0, 1, render_line=2),
                               _request("b", 0, 2)), SEARCH)
    assert s["post_batcher_cpu_ms"][1] == 1
    assert s["request_cpu_ms"][1] == 1
    assert s["parts_ms"]["http[in]"][1] == 2


def test_a_trace_without_a_search_span_is_not_a_request():
    poll = [(li, ev) for li, ev in _request("p", 0, 1)
            if ev[0] != "plane_dispatch"]
    s = host_spans.reduce(_raw(poll), SEARCH)
    assert s["request_cpu_ms"] == [None, 0]


def test_dispatch_cpu_and_its_phases():
    ex = [(1, _span("batch[execute]", 0, 10, 1_000, 4_000, seq=1)),
          (1, _span("plane[h2d]", 1, 3, 1_100, 1_600)),
          (1, _span("plane[d2h]", 6, 2, 2_000, 2_200)),
          (1, _span("plane[d2h]", 8, 1, 2_300, 2_400)),
          # another thread's phase inside the same wall is not this one's
          (2, _span("plane[h2d]", 2, 3, 1_000, 3_000)),
          (2, _span("batch[execute]", 20, 4, 9_000, 10_000, seq=2))]
    s = host_spans.reduce(_raw(ex), SEARCH)
    assert s["execute_cpu_ms"] == [pytest.approx(2.0), 2]
    assert s["execute_wall_ms"] == [pytest.approx(7.0), 2]
    assert s["phases"]["plane[h2d]"] == [1, pytest.approx(3.0),
                                         pytest.approx(0.5)]
    assert s["phases"]["plane[d2h]"] == [1, pytest.approx(3.0),
                                         pytest.approx(0.3)]


def _tick(t_ms, lag_us, **clocks):
    return ["host[loop]", t_ms * MS, 0.01 * MS, dict(lag_us=lag_us, **clocks)]


def test_role_split_and_loop_lag():
    roles = dict(cpu_loop_us=0, cpu_pool_us=0, cpu_dispatch_us=0,
                 cpu_other_us=0)
    ticks = [(0, _tick(0, 200, threads=70, **roles)),
             (0, _tick(10, 1_000)),
             (0, _tick(20, 4_800)),
             (0, _tick(1_000, 2_000, threads=72, cpu_loop_us=100_000,
                       cpu_pool_us=600_000, cpu_dispatch_us=200_000,
                       cpu_other_us=50_000))]
    s = host_spans.reduce(_raw(ticks), SEARCH)
    assert s["loop_lag_ms"] == [pytest.approx(2.0), 4]
    assert s["loop_lag_max_ms"] == pytest.approx(4.8)
    pc = s["python_cpu"]
    assert pc["pct"] == pytest.approx(95.0)         # of one core, over 1 s
    assert pc["roles"] == {"cpu_loop_us": pytest.approx(10.0),
                           "cpu_pool_us": pytest.approx(60.0),
                           "cpu_dispatch_us": pytest.approx(20.0),
                           "cpu_other_us": pytest.approx(5.0)}
    assert pc["threads"] == [70, 72] and pc["seconds"] == pytest.approx(1.0)
    # one tick with clocks is no interval
    s = host_spans.reduce(_raw(ticks[:3]), SEARCH)
    assert s["python_cpu"] is None


def test_gc_union_and_the_device_idle_inside_passes():
    gcs = [(0, ["host[gc]", 10 * MS, 5 * MS, {"generation": 0,
                                             "collected": 3}]),
           # a pass on another thread while the first runs: one stop
           (1, ["host[gc]", 12 * MS, 5 * MS, {"generation": 2,
                                             "collected": 9}]),
           (1, ["host[gc]", 40 * MS, 2 * MS, {"generation": 0,
                                             "collected": 0}])]
    busy = [[0, 11 * MS], [16 * MS, 100 * MS]]
    s = host_spans.reduce(_raw(gcs), SEARCH, busy)
    assert s["gc"]["union_s"] == pytest.approx(0.009)
    # generation 0: [10, 15] idle from 11 to 15, [40, 42] busy throughout
    # generation 2: [12, 17] idle from 12 to 16
    assert s["gc"]["by_gen"] == {
        "0": [2, pytest.approx(0.007), pytest.approx(0.004), 3],
        "2": [1, pytest.approx(0.005), pytest.approx(0.004), 9]}
    # without the device's intervals the idle is not known, not 0
    s = host_spans.reduce(_raw(gcs), SEARCH)
    assert s["gc"]["by_gen"]["2"][2] is None


def test_a_program_without_clocks_or_host_twins_reads_nothing(monkeypatch):
    s = host_spans.reduce({"host": []}, SEARCH)
    assert host_spans._nothing(s) and s["gc"] is None
    # a tick alone is the program: no collection in the window reads 0
    s = host_spans.reduce(_raw([(0, _tick(0, 100))]), SEARCH)
    assert not host_spans._nothing(s) and s["gc"]["union_s"] == 0.0
    monkeypatch.setattr(host_spans, "load", lambda ctx: None)
    ctx = {"device": {"window_s": 8.0}}
    for reader, params in (("host_span_value", {"key": "loop_lag_ms"}),
                           ("host_span_value", {"key": "python_cpu_pct"}),
                           ("gc_share", {})):
        assert load_module("readers", reader).read(ctx, params) is None


HOST_METRICS = ("rest.outside_node_ms", "rest.loop_lag_ms", "rest.resume_ms",
                "rest.post_batcher_cpu_ms", "host.request_cpu_ms",
                "planes.execute_cpu_ms", "host.python_cpu_pct",
                "process.gc_pct")


def test_traced_rehearsal_reports_the_host_metrics(rehearsal_manifest):
    """The rehearsal manifest with BENCHMARK.json's host entries (and
    ``rest.server_ms`` / ``rest.post_batcher_ms`` beside them) listed for
    ``tiny_knn.knn_c4``: the eight come out of a traced CPU run."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        real = json.load(f)
    manifest = json.loads(json.dumps(rehearsal_manifest))
    wanted = HOST_METRICS + ("rest.server_ms", "rest.post_batcher_ms")
    manifest["per_layer"] += [dict(m, workloads=["tiny_knn.knn_c4"])
                              for m in real["per_layer"]
                              if m["name"] in wanted]
    r = bench_run.run(argparse.Namespace(
        workload="tiny_knn.knn_c4", seed=2147483711, seconds=2.0, trace=1,
        control=0), rehearsal=True, manifest=manifest)
    assert r["correct"] is True, r["compared"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(HOST_METRICS) <= set(m), sorted(m)
    assert all(m[k] >= 0 for k in HOST_METRICS if k != "rest.outside_node_ms")
    # CPU never exceeds the wall it lies in
    assert m["rest.post_batcher_cpu_ms"] <= m["rest.post_batcher_ms"]
    assert m["host.request_cpu_ms"] <= m["rest.server_ms"]
    assert 0 < m["host.python_cpu_pct"] <= 100 * 200
    assert r["metrics"]["process.gc_pct"]["unit"] == "%"
