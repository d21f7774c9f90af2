"""The host's twins on the profiler's clock: every span twin carries its
thread's CPU clock (``common/tracing.py``), each pass of the cyclic
collector has a ``host[gc]`` twin (``common/heap.py``), and the event loop
leaves a ``host[loop]`` tick with its lag and the threads' CPU clocks by
role (``rest/http_server.py``); with no ``jax.profiler`` session none of
them reads a clock or makes an annotation.
"""
from __future__ import annotations

import asyncio
import contextlib
import gc
import glob
import os
import tempfile
import threading
import time

import jax
import pytest

from elasticsearch_tpu.common import heap, tracing
from elasticsearch_tpu.rest import http_server
from elasticsearch_tpu.rest.http_server import HttpServer


@contextlib.contextmanager
def profiled():
    """A host-only ``jax.profiler`` session; yields a list that holds, once
    the session has ended, each host line's events as ``(name, start_ns,
    dur_ns, stats)``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    lines: list = []
    with tempfile.TemporaryDirectory() as tdir:
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            yield lines
        finally:
            jax.profiler.stop_trace()
        from jax.profiler import ProfileData
        for pb in glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                         "*.xplane.pb")):
            for plane in ProfileData.from_file(pb).planes:
                if plane.name.startswith("/host:"):
                    lines += [[(ev.name, ev.start_ns, ev.duration_ns,
                                dict(ev.stats)) for ev in line.events]
                              for line in plane.lines]


def _named(lines, name):
    return [(li, ev) for li, evs in enumerate(lines) for ev in evs
            if ev[0] == name]


@contextlib.contextmanager
def http_on_a_loop():
    """An ``HttpServer`` on an event loop of its own thread; yields the
    server and the loop."""
    async def handler(method, path, query, body):
        return 200, "application/json", b"{}"

    loop = asyncio.new_event_loop()
    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()
    srv = HttpServer(handler, host="127.0.0.1", port=0)
    asyncio.run_coroutine_threadsafe(srv.start(), loop).result(10)
    try:
        yield srv, loop
    finally:
        asyncio.run_coroutine_threadsafe(srv.stop(), loop).result(10)
        loop.call_soon_threadsafe(loop.stop)
        thread.join(10)
        assert not thread.is_alive()
        loop.close()


def _trace_ids(clocked: bool, n: int = 1) -> list:
    """``n`` fresh trace ids whose twins do (or do not) carry the clock."""
    out = []
    while len(out) < n:
        tid = tracing.new_trace_id()
        if (hash(tid) % tracing.CPU_SAMPLE == 0) == clocked:
            out.append(tid)
    return out


def test_twins_carry_their_threads_cpu_clock():
    """``cpu0_us`` <= ``cpu1_us`` on a sampled request's twins and on the
    dispatchers'; a body that spins spends about its wall on the CPU, one
    that sleeps almost none of it."""
    budget_ns = 30_000_000
    tid, = _trace_ids(True)
    with profiled() as lines:
        with tracing.span("rest[spin]", trace_id=tid):
            t0 = time.thread_time_ns()
            while time.thread_time_ns() - t0 < budget_ns:
                pass
        with tracing.span("batch[execute]", attrs={"seq": 7}):
            time.sleep(0.05)
    (_, spin), = _named(lines, "rest[spin]")
    (_, nap), = _named(lines, "batch[execute]")
    assert spin[3]["trace_id"] == "t" + tid and nap[3]["seq"] == 7
    for _name, _s, dur_ns, st in (spin, nap):
        assert 0 <= st["cpu0_us"] <= st["cpu1_us"]
        assert (st["cpu1_us"] - st["cpu0_us"]) * 1e3 <= dur_ns * 1.01
    spin_us = spin[3]["cpu1_us"] - spin[3]["cpu0_us"]
    assert spin_us >= 0.95 * budget_ns / 1e3
    assert spin_us * 1e3 >= 0.25 * spin[2]
    assert (nap[3]["cpu1_us"] - nap[3]["cpu0_us"]) * 1e3 <= 0.05 * nap[2]


def test_one_request_in_cpu_sample_carries_the_clock():
    """A request's twins carry the clock all or none, by its trace id: the
    read is a system call made with the interpreter lock held."""
    ids = _trace_ids(True, 2) + _trace_ids(False, 6)
    with profiled() as lines:
        for tid in ids:
            with tracing.span("rest[x]", trace_id=tid):
                with tracing.Phases() as ph:
                    ph.enter("plane_dispatch")
    by_trace: dict = {}
    for name in ("rest[x]", "plane_dispatch"):
        for _li, ev in _named(lines, name):
            by_trace.setdefault(ev[3]["trace_id"][1:], []).append(
                "cpu0_us" in ev[3] and "cpu1_us" in ev[3])
    assert by_trace == {tid: [i < 2] * 2 for i, tid in enumerate(ids)}
    # over many ids, about one in CPU_SAMPLE
    n = 4000
    hit = sum(hash(tracing.new_trace_id()) % tracing.CPU_SAMPLE == 0
              for _ in range(n))
    assert abs(hit / n - 1 / tracing.CPU_SAMPLE) < 0.03


@pytest.fixture
def armed():
    heap.arm()
    try:
        yield
    finally:
        heap.disarm()


def test_collector_pass_has_a_twin_during_a_session(armed):
    with profiled() as lines:
        with jax.profiler.TraceAnnotation("test[collect]"):
            gc.collect()
    (li, outer), = _named(lines, "test[collect]")
    inside = [ev for lj, ev in _named(lines, "host[gc]") if lj == li
              and outer[1] <= ev[1] and ev[1] + ev[2] <= outer[1] + outer[2]]
    assert [ev[3]["generation"] for ev in inside] == [2]
    assert inside[0][3]["collected"] >= 0
    assert heap._twin is None


def test_without_a_session_no_clock_is_read_and_no_twin_made(
        monkeypatch, armed):
    """Spans, the collector's hook and the loop's tick read no thread
    clock and make no annotation while no session is active."""
    assert not tracing.TraceAnnotation.is_enabled()
    reads, made = [], []
    for fn in ("thread_time_ns", "clock_gettime_ns"):
        real = getattr(time, fn)
        monkeypatch.setattr(time, fn, lambda *a, _f=real, _n=fn: (
            reads.append(_n), _f(*a))[1])

    class Counted(tracing.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a[0])
            super().__init__(*a, **kw)

    monkeypatch.setattr(tracing, "TraceAnnotation", Counted)
    monkeypatch.setattr(heap, "TraceAnnotation", Counted)
    monkeypatch.setattr(http_server, "IDLE_TICK_S", 0.005)
    with http_on_a_loop():
        with tracing.span("rest[x]", root=True) as sp:
            with tracing.Phases() as ph:
                assert ph.enter("plane[h2d]") is not None
        with tracing.span("batch[execute]", attrs={"seq": 1}) as none:
            assert none is None
        gc.collect()
        time.sleep(0.1)                 # some twenty idle ticks
    assert reads == [] and made == []
    doc = tracing.DEFAULT_STORE.get(sp.trace_id)
    assert [s["name"] for s in doc["spans"]] == ["rest[x]", "plane[h2d]"]
    assert not any("cpu0_us" in (s.get("attrs") or {}) for s in doc["spans"])


def test_loop_ticks_carry_lag_and_role_clocks_until_stopped():
    with http_on_a_loop() as (srv, loop):
        tick = srv._tick_task
        time.sleep(0.15)                # the idle tick sees the session
        with profiled() as lines:
            time.sleep(0.4)
        assert not tick.done()
        asyncio.run_coroutine_threadsafe(srv.stop(), loop).result(10)
        assert tick.done() and srv._tick_task is None
    ticks = _named(lines, "host[loop]")
    assert len(ticks) >= 10
    assert len({li for li, _ev in ticks}) == 1      # the loop's thread
    assert all(ev[3]["lag_us"] >= 0 for _li, ev in ticks)
    swept = [ev[3] for _li, ev in ticks if "threads" in ev[3]]
    assert 1 <= len(swept) <= len(ticks) // http_server.ROLE_CLOCK_EVERY + 1
    roles = ("cpu_loop_us", "cpu_pool_us", "cpu_dispatch_us", "cpu_other_us")
    for st in swept:
        assert st["threads"] >= 2 and st["cpu_loop_us"] > 0
        assert st["cpu_other_us"] > 0 and all(st[r] >= 0 for r in roles)
    # the clocks only rise
    for a, b in zip(swept, swept[1:]):
        assert b["cpu_loop_us"] >= a["cpu_loop_us"]


def test_role_clocks_sum_the_threads_by_name():
    """A thread's clock counts under its name's role; one that has ended
    has no clock to read."""
    stop, spun = threading.Event(), threading.Event()

    def spin():
        t0 = time.thread_time_ns()
        while time.thread_time_ns() - t0 < 20_000_000:
            pass
        spun.set()
        stop.wait()

    threads = [threading.Thread(target=spin, name="es-rest-http_0"),
               threading.Thread(target=stop.wait, name="es-dispatcher-ab")]
    for t in threads:
        t.start()
    try:
        assert spun.wait(30)
        clocks = http_server._role_clocks(threading.get_ident())
    finally:
        stop.set()
        for t in threads:
            t.join(10)
            assert not t.is_alive()
    assert clocks["threads"] >= 3
    assert clocks["cpu_pool_us"] >= 20_000
    assert clocks["cpu_loop_us"] > 0        # this thread stands for the loop
    assert 0 <= clocks["cpu_dispatch_us"] < clocks["cpu_pool_us"]
    # the kernel refuses the clock of a thread that has ended (the OS
    # thread may outlive its join by a moment)
    clock = http_server._thread_clock_id(threads[0].native_id)
    deadline = time.monotonic() + 5
    while True:
        try:
            time.clock_gettime_ns(clock)
        except OSError:
            break
        assert time.monotonic() < deadline
        time.sleep(0.01)
