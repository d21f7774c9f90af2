"""The node child: the only process of a run that touches the chip.

It starts the node exactly as ``python -m elasticsearch_tpu.cli.node`` does
(``cli.node.main`` is called unchanged: ``IndicesService(data)`` +
``RestAPI`` behind ``HttpServer``, handlers on the pool), and beside it a
control port on which the parent starts and stops ``jax.profiler`` around
the traced window: only the process that holds the chip can trace it, and
the program offers no such endpoint yet (PERF.md, Open questions).

    python benchmark/harness/node_main.py --control-port P -- <cli.node args>

Control requests (loopback, plain HTTP): ``POST /trace/start?dir=<path>``,
``POST /trace/stop``; each answers a JSON object with the host's wall clock
at the call, which the trace reduction uses to line the clocks up.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class _Control(BaseHTTPRequestHandler):
    def log_message(self, *args):       # quiet
        pass

    def do_POST(self):
        import jax
        url = urlparse(self.path)
        try:
            if url.path == "/trace/start":
                log_dir = parse_qs(url.query)["dir"][0]
                opts = jax.profiler.ProfileOptions()
                # the device's own events and the runtime's host events;
                # no Python tracer (it slows every request thread)
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                t = time.time()
                jax.profiler.start_trace(log_dir, profiler_options=opts)
                doc = {"started_wall_s": t, "ready_wall_s": time.time()}
            elif url.path == "/trace/stop":
                t = time.time()
                jax.profiler.stop_trace()
                doc = {"stop_wall_s": t, "written_wall_s": time.time()}
            else:
                self.send_error(404)
                return
            body = json.dumps(doc).encode()
            self.send_response(200)
        except Exception as e:   # noqa: BLE001 — reported to the parent
            body = json.dumps({"error": f"{type(e).__name__}: {e}"}).encode()
            self.send_response(500)
        self.send_header("content-type", "application/json")
        self.send_header("content-length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    port = int(argv[argv.index("--control-port") + 1])
    node_args = argv[argv.index("--") + 1:]
    sys.path.insert(0, CHECKOUT)
    control = ThreadingHTTPServer(("127.0.0.1", port), _Control)
    threading.Thread(target=control.serve_forever, name="bench-control",
                     daemon=True).start()
    from elasticsearch_tpu.cli import node
    try:
        return node.main(node_args)
    finally:
        control.shutdown()


if __name__ == "__main__":
    raise SystemExit(main())
