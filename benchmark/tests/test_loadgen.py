"""The load generator (``harness/loadgen.py``) against a stub HTTP server on
loopback, and the client-side readers (``answered_rate``,
``latency_percentile``) on request lists written by hand. No JAX, no node."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from harness import loadgen
from harness.manifest import load_module

ANSWER = json.dumps({"timed_out": False, "_shards": {"failed": 0},
                     "hits": {"total": 1, "hits": [
                         {"_id": "7", "_score": 1.0}]}}).encode()


# ------------------------------------------------------------------ readers


def test_percentile_interpolates_between_neighbours():
    assert loadgen.percentile([7.0], 0.99) == 7.0
    vals = [float(v) for v in range(10, 110, 10)]      # 10 .. 100
    assert loadgen.percentile(vals, 0.5) == pytest.approx(55.0)
    assert loadgen.percentile(vals, 0.95) == pytest.approx(95.5)
    assert loadgen.percentile(vals, 0.0) == 10.0
    assert loadgen.percentile(vals, 1.0) == 100.0


def test_client_side_readers_by_hand():
    # 200 answered requests, 13 ms apart, over a span of 3.0 .. 5.712 s
    lat = sorted(50.0 + 7.0 * (i % 11) for i in range(200))
    ctx = {"latencies_ms": lat, "requests": [object()] * 200,
           "span_s": 2.712, "setup_s": 41.5}
    rate = load_module("readers", "answered_rate").read(ctx, {})
    assert rate == pytest.approx(200 / 2.712)
    pct = load_module("readers", "latency_percentile").read
    assert pct(ctx, {"q": 0.5}) == loadgen.percentile(lat, 0.5)
    # the 99th percentile lies at position 197.01 of 0..199
    assert pct(ctx, {"q": 0.99}) == \
        pytest.approx(lat[197] + (lat[198] - lat[197]) * 0.01)
    assert load_module("readers", "run_value").read(
        ctx, {"key": "setup_s"}) == 41.5
    # nothing to read: nothing reported, never a 0
    empty = dict(ctx, latencies_ms=[], requests=[], span_s=0.0)
    assert pct(empty, {"q": 0.5}) is None
    assert load_module("readers", "answered_rate").read(empty, {}) is None


# ---------------------------------------------------------------- stub server


class _Stub:
    """Answers every POST after ``latency`` seconds; the ``fail``-th request
    it sees gets a 503, the ``hang``-th is held for 1.5 s."""

    def __init__(self, latency, fail=None, hang=None):
        self.lock = threading.Lock()
        self.served = 0
        stub = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *args):
                pass

            def do_POST(self):
                self.rfile.read(int(self.headers["content-length"]))
                with stub.lock:
                    nth = stub.served
                    stub.served += 1
                status, body = 200, ANSWER
                if nth == hang:
                    time.sleep(1.5)
                elif nth == fail:
                    status, body = 503, b'{"error": "scripted"}'
                else:
                    time.sleep(latency)
                # one write: headers and body in two would wait out the
                # peer's delayed acknowledgement (40 ms) on every answer
                self.wfile.write(b"HTTP/1.1 %d X\r\ncontent-length: %d"
                                 b"\r\n\r\n%s" % (status, len(body), body))

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.server.daemon_threads = True
        self.port = self.server.server_address[1]
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()

    def close(self):
        self.server.shutdown()
        self.server.server_close()


class _Queries:
    """Each client's stream counts up from 0."""

    def __init__(self):
        self.next = {}

    def more(self, client):
        i = self.next.get(client, 0)
        self.next[client] = i + 8
        return [(b'{"q": %d}' % j, {"client": client, "i": j})
                for j in range(i, i + 8)]


@pytest.fixture
def stub():
    made = []

    def make(*args, **kw):
        made.append(_Stub(*args, **kw))
        return made[-1]

    yield make
    for s in made:
        s.close()


def _loop(port, clients):
    return loadgen.Loop(port, "/idx/_search", _Queries(), clients, prefill=4)


def test_a_request_in_flight_at_the_windows_end_is_drained_and_counted(stub):
    server = stub(0.25)
    res = _loop(server.port, 2).run(0.4)
    # sends stop at 0.4 s; the second round, sent at ~0.25 s, is answered
    # at ~0.5 s and belongs to the window
    assert len(res["requests"]) == 4 and res["hung_clients"] == 0
    late = [r for r in res["requests"] if r.received > res["t0"] + 0.4]
    assert len(late) == 2 and all(r.status == 200 for r in late)
    assert all(r.sent < res["t0"] + 0.4 for r in res["requests"])
    assert all(r.raw == ANSWER and r.error is None for r in res["requests"])


def test_every_client_walks_its_own_stream_in_order(stub):
    server = stub(0.01)
    res = _loop(server.port, 4).run(0.5)
    per_client = {}
    for r in sorted(res["requests"], key=lambda r: r.sent):
        per_client.setdefault(r.qrec["client"], []).append(r.qrec["i"])
    assert len(per_client) == 4
    # past the prefill of 8 too: nothing sent twice, nothing skipped
    assert all(len(v) > 8 and v == list(range(len(v)))
               for v in per_client.values())
    # a closed loop: a client's next send follows its last answer
    for c in range(4):
        mine = sorted((r for r in res["requests"] if r.client == c),
                      key=lambda r: r.sent)
        assert all(a.received <= b.sent for a, b in zip(mine, mine[1:]))
    assert len(res["gaps"]) == len(res["requests"]) - 4


def test_a_failed_request_keeps_its_status_and_the_loop_goes_on(stub):
    server = stub(0.02, fail=3)
    res = _loop(server.port, 2).run(0.4)
    bad = [r for r in res["requests"] if r.status != 200]
    assert len(bad) == 1 and bad[0].status == 503 and bad[0].error is None
    assert len(res["requests"]) > 10 and res["hung_clients"] == 0


def test_a_hung_client_is_reported(stub):
    server = stub(0.02, hang=2)
    res = _loop(server.port, 2).run(0.3, drain_timeout=0.3)
    assert res["hung_clients"] == 1
