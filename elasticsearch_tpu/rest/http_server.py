"""Minimal asyncio HTTP/1.1 server for the REST layer.

The reference serves HTTP via Netty (``modules/transport-netty4/.../
Netty4HttpServerTransport.java``) with an in-repo pure-Java NIO alternative
(``libs/nio``). Here: asyncio streams — an event loop per process, no
threads in the request path, which matches the single-writer asyncio design
of the node. Supports keep-alive, Content-Length bodies, and chunked
transfer decoding (curl/clients use both).

The loop's tick: while the server runs, one coroutine on the loop checks
every 100 ms for a ``jax.profiler`` session. While one is active it
sleeps 10 ms at a time and measures how late it woke, which is what a
readable socket or a finished handler's future waits before the loop
runs it, and leaves a ``host[loop]`` annotation (``common/tracing.py``'s
twins) with ``lag_us``; every tenth also carries the CPU clocks of the
process's Python threads summed by role (:func:`_role_clocks`).
"""

from __future__ import annotations

import asyncio
import json
import threading
import time
from typing import Callable, Optional, Tuple

from ..common import tracing as _tracing

MAX_BODY = 100 * 1024 * 1024  # reference default http.max_content_length


class HttpError(Exception):
    def __init__(self, status: int, reason: str):
        self.status = status
        self.reason = reason


#: the loop's tick, seconds between wake-ups with a profiler session and
#: without; the role clocks ride every ROLE_CLOCK_EVERY-th traced tick
TICK_S = 0.01
IDLE_TICK_S = 0.1
ROLE_CLOCK_EVERY = 10
#: thread-name prefixes of the roles the tick sums CPU clocks by; any
#: other thread but the loop's counts as ``cpu_other_us``
_ROLES = (("es-rest-http", "cpu_pool_us"),
          ("es-dispatcher-", "cpu_dispatch_us"))


def _thread_clock_id(native_id: int) -> int:
    """The kernel's CPU clock of one thread of this process, by its thread
    id (Linux's ``MAKE_THREAD_CPUCLOCK(tid, CPUCLOCK_SCHED)``: the value
    ``time.pthread_getcpuclockid`` returns). By id and not by pthread
    handle: a foreign thread's ``_DummyThread`` stays listed after the
    thread has ended, and its handle then points at memory that may be
    gone, where the kernel refuses a dead id (``OSError``)."""
    return (~native_id << 3) | 6


def _role_clocks(loop_ident: int) -> dict:
    """Each live Python thread's CPU clock in microseconds, summed by role:
    the loop's own thread, the request pool, the micro-batcher's
    dispatchers and every other thread; and ``threads``, how many were
    read. A thread that has ended is left out."""
    out = {"cpu_loop_us": 0, "cpu_pool_us": 0, "cpu_dispatch_us": 0,
           "cpu_other_us": 0, "threads": 0}
    for t in threading.enumerate():
        if t.native_id is None:         # not started yet
            continue
        try:
            us = time.clock_gettime_ns(_thread_clock_id(t.native_id)) \
                // 1000
        except OSError:                 # ended
            continue
        if t.ident == loop_ident:
            role = "cpu_loop_us"
        else:
            role = next((r for p, r in _ROLES if t.name.startswith(p)),
                        "cpu_other_us")
        out[role] += us
        out["threads"] += 1
    return out


_STATUS_TEXT = {200: "OK", 201: "Created", 400: "Bad Request",
                404: "Not Found", 405: "Method Not Allowed",
                409: "Conflict", 413: "Payload Too Large",
                429: "Too Many Requests", 500: "Internal Server Error"}


class HttpServer:
    """handler(method, path, query_string, body_bytes) →
    (status, content_type, payload_bytes) — or a 4-tuple with a trailing
    extra-response-headers dict (X-Opaque-Id echo, Trace-Id)."""

    def __init__(self, handler: Callable, host: str = "127.0.0.1",
                 port: int = 9200, ssl_ctx=None,
                 pass_headers: bool = False):
        self.handler = handler
        self.host = host
        self.port = port
        self.ssl_ctx = ssl_ctx
        #: hand parsed request headers to the handler as a 5th argument
        #: (the security layer authenticates from Authorization)
        self.pass_headers = pass_headers
        self._server: Optional[asyncio.AbstractServer] = None
        self._tick_task: Optional[asyncio.Task] = None

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._serve_connection, self.host, self.port,
            ssl=self.ssl_ctx)
        self._tick_task = asyncio.get_running_loop().create_task(
            self._tick())
        owner = getattr(self.handler, "__self__", None)
        if owner is not None and hasattr(owner, "http_publish_address"):
            # advertise the REAL bound socket (host may be 0.0.0.0 and
            # port 0 means ephemeral) for client sniffing
            host, port = self._server.sockets[0].getsockname()[:2]
            if host in ("0.0.0.0", "::"):
                host = "127.0.0.1"
            owner.http_publish_address = f"{host}:{port}"

    async def stop(self) -> None:
        if self._tick_task is not None:
            self._tick_task.cancel()
            try:
                await self._tick_task
            except asyncio.CancelledError:
                pass
            self._tick_task = None
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()

    async def _tick(self) -> None:
        """The loop's tick (module docstring): runs until :meth:`stop`."""
        loop = asyncio.get_running_loop()
        me = threading.get_ident()
        n = 0
        while True:
            if not _tracing.TraceAnnotation.is_enabled():
                await asyncio.sleep(IDLE_TICK_S)
                continue
            due = loop.time() + TICK_S
            await asyncio.sleep(TICK_S)
            lag_us = int((loop.time() - due) * 1e6)
            with _tracing.TraceAnnotation("host[loop]",
                                          lag_us=lag_us) as ann:
                if n % ROLE_CLOCK_EVERY == 0:
                    ann.set_metadata(**_role_clocks(me))
            n += 1

    async def _serve_connection(self, reader: asyncio.StreamReader,
                                writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                try:
                    request = await self._read_request(reader)
                except (asyncio.IncompleteReadError, ConnectionError):
                    break
                if request is None:
                    break
                method, target, headers, body = request
                # the request's trace starts here: http[in] runs from
                # the last byte read to the hand-off to the handler's
                # thread (tracing.handoff in the handler), or to the
                # handler's return where it runs inline
                edge = _tracing.open_span(
                    "http[in]", headers=headers, root=True,
                    attrs={"bytes_in": len(body)})
                path, _, query = target.partition("?")
                # bind the deprecation-warning container in THIS task's
                # context before dispatch so a handler running on an
                # executor thread (cluster mode) shares it
                from ..xpack.deprecation import begin_request
                begin_request()
                extra_headers = {}
                try:
                    result = await self._dispatch(
                        method, path, query, body, headers)
                    if len(result) == 4:
                        status, ctype, payload, hx = result
                        extra_headers.update(hx or {})
                    else:
                        status, ctype, payload = result
                except HttpError as e:
                    status, ctype, payload = e.status, "application/json", \
                        json.dumps({"error": e.reason,
                                    "status": e.status}).encode()
                except Exception as e:  # handler bug → 500, keep serving
                    status, ctype, payload = 500, "application/json", \
                        json.dumps({"error": {
                            "type": "exception",
                            "reason": str(e)}, "status": 500}).encode()
                finally:
                    edge.close()
                out_span = _tracing.open_span(
                    "http[out]", trace_id=edge.trace_id,
                    parent_span_id=edge.span_id,
                    attrs={"status": status, "bytes_out": len(payload)})
                keep_alive = headers.get("connection", "").lower() != "close"
                # RFC-7234 299 deprecation warnings accumulated by the
                # handler (HeaderWarning analog — xpack/deprecation.py)
                from ..xpack.deprecation import drain_warnings
                warn_lines = "".join(f"Warning: {w}\r\n"
                                     for w in drain_warnings())
                # CR/LF-sanitize before emission: X-Opaque-Id is
                # client-controlled (and reaches here percent-decoded via
                # the __x_opaque_id param), so raw reflection would allow
                # response-header injection / response splitting
                def _hsafe(s):
                    return str(s).replace("\r", " ").replace("\n", " ")
                extra_lines = "".join(
                    f"{_hsafe(k)}: {_hsafe(v)}\r\n"
                    for k, v in extra_headers.items())
                head = (f"HTTP/1.1 {status} "
                        f"{_STATUS_TEXT.get(status, 'Unknown')}\r\n"
                        f"content-type: {ctype}\r\n"
                        f"content-length: {len(payload)}\r\n"
                        f"X-elastic-product: Elasticsearch\r\n"
                        + warn_lines + extra_lines +
                        f"connection: "
                        f"{'keep-alive' if keep_alive else 'close'}\r\n\r\n")
                writer.write(head.encode() + (b"" if method == "HEAD"
                                              else payload))
                out_span.close()
                await writer.drain()
                if not keep_alive:
                    break
        finally:
            writer.close()
            try:
                await writer.wait_closed()
            except Exception:
                pass

    async def _dispatch(self, method, path, query, body, headers=None):
        if self.pass_headers:
            result = self.handler(method, path, query, body, headers)
        else:
            result = self.handler(method, path, query, body)
        if asyncio.iscoroutine(result):
            result = await result
        return result

    async def _read_request(self, reader: asyncio.StreamReader):
        try:
            head = await reader.readuntil(b"\r\n\r\n")
        except asyncio.IncompleteReadError as e:
            if not e.partial:
                return None
            raise
        lines = head.decode("latin-1").split("\r\n")
        try:
            method, target, _version = lines[0].split(" ", 2)
        except ValueError:
            raise HttpError(400, "malformed request line")
        headers = {}
        for line in lines[1:]:
            if not line:
                continue
            k, _, v = line.partition(":")
            headers[k.strip().lower()] = v.strip()
        body = b""
        if headers.get("transfer-encoding", "").lower() == "chunked":
            chunks = []
            total = 0
            while True:
                size_line = await reader.readuntil(b"\r\n")
                size = int(size_line.strip() or b"0", 16)
                if size == 0:
                    await reader.readuntil(b"\r\n")
                    break
                total += size
                if total > MAX_BODY:
                    raise HttpError(413, "content length exceeded")
                chunks.append(await reader.readexactly(size))
                await reader.readexactly(2)
            body = b"".join(chunks)
        elif "content-length" in headers:
            n = int(headers["content-length"])
            if n > MAX_BODY:
                raise HttpError(413, "content length exceeded")
            body = await reader.readexactly(n)
        return method.upper(), target, headers, body
