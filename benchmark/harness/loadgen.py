"""The load generator: C client threads on keep-alive connections, as a
closed loop: each client sends its next request when its last one answers
(Rally's load model).

It stops sending at the window's end, lets the requests in flight finish,
and keeps every request's times and its raw response; nothing is parsed
inside the window.

Requests are prepared before the window; a client that runs out asks its
query generator for more, outside the timed interval of any request (that
time shows up as the generator's own overhead).
"""

from __future__ import annotations

import threading
import time

from .node import Http


def percentile(sorted_vals: list, q: float) -> float:
    """Linear-interpolated percentile of an ascending list."""
    if len(sorted_vals) == 1:
        return sorted_vals[0]
    pos = q * (len(sorted_vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    return sorted_vals[lo] + (sorted_vals[hi] - sorted_vals[lo]) * (pos - lo)


class Request:
    __slots__ = ("client", "qrec", "sent", "received", "status", "raw",
                 "error")

    def __init__(self, client, qrec):
        self.client, self.qrec = client, qrec
        self.sent = self.received = 0.0
        self.status, self.raw, self.error = 0, b"", None


def send_group(port: int, path: str, reqs: list, timeout: float = 900):
    """Send ``reqs`` ((body, qrec) pairs) at once, one thread each; returns
    the finished :class:`Request` list. Used by the warm-up."""
    out = [Request(i, q) for i, (_b, q) in enumerate(reqs)]

    def one(i):
        r, http = out[i], Http(port, timeout)
        r.sent = time.perf_counter()
        try:
            r.status, r.raw = http.raw("POST", path, reqs[i][0])
        except Exception as e:   # noqa: BLE001 — reported by the caller
            r.error = e
        r.received = time.perf_counter()
        http.close()

    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    return out


class Loop:
    def __init__(self, port: int, path: str, queries, clients: int,
                 prefill: int):
        self.port, self.path = port, path
        self.queries, self.clients = queries, clients
        self.pending = [[] for _ in range(clients)]
        for c in range(clients):
            while len(self.pending[c]) < prefill:
                self.pending[c].extend(queries.more(c))

    def run(self, seconds: float, drain_timeout: float = 120.0) -> dict:
        """Drive the loop for ``seconds``; returns the requests and the
        window's clock readings."""
        done = [[] for _ in range(self.clients)]
        gaps = [[] for _ in range(self.clients)]
        start_gate = threading.Barrier(self.clients + 1)
        stop_at = [0.0]

        def client(c: int) -> None:
            http = Http(self.port)
            mine, pending = done[c], self.pending[c]
            pending.reverse()           # pop() from the front, cheaply
            start_gate.wait()
            last_recv = None
            while True:
                if not pending:
                    pending.extend(reversed(self.queries.more(c)))
                body, qrec = pending.pop()
                r = Request(c, qrec)
                now = time.perf_counter()
                if now >= stop_at[0]:
                    break
                if last_recv is not None:
                    gaps[c].append(now - last_recv)
                r.sent = now
                try:
                    r.status, r.raw = http.raw("POST", self.path, body)
                except Exception as e:   # noqa: BLE001 — counted failed
                    r.error = e
                r.received = last_recv = time.perf_counter()
                mine.append(r)
            http.close()

        threads = [threading.Thread(target=client, args=(c,), daemon=True,
                                    name=f"bench-client-{c}")
                   for c in range(self.clients)]
        for t in threads:
            t.start()
        t0 = time.perf_counter()
        stop_at[0] = t0 + seconds
        wall0 = time.time()
        start_gate.wait()
        deadline = stop_at[0] + drain_timeout
        for t in threads:
            t.join(max(0.0, deadline - time.perf_counter()))
        hung = sum(t.is_alive() for t in threads)
        reqs = [r for lst in done for r in lst]
        all_gaps = [g for lst in gaps for g in lst]
        return {"requests": reqs, "hung_clients": hung, "t0": t0,
                "wall0": wall0, "gaps": all_gaps}
