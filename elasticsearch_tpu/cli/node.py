"""estpu-node: launch a single node serving HTTP.

Reference: the ``elasticsearch`` launcher scripts
(``distribution/tools/launchers/``) + ``bootstrap/Elasticsearch.java:75``
reduced to the single-process case: build the node stack, bind the HTTP
port, serve until SIGINT. Cluster formation (multi-node) is configured
through ``--seed`` peers, in which case the full coordination stack runs.

    python -m elasticsearch_tpu.cli.node --port 9200 --data ./data
    python -m elasticsearch_tpu.cli.node --name n1 --transport-port 9300 \\
        --seed n1=127.0.0.1:9300 --seed n2=127.0.0.1:9301
"""
from __future__ import annotations

import argparse
import asyncio
import contextvars
import os
import signal
from concurrent.futures import ThreadPoolExecutor

# Opt-in runtime lockdep witness (ES_TPU_LOCKDEP=1): install BEFORE the
# node stack imports create their module/instance locks, so a live node
# serves with observed lock-order checking and exports the es_lockdep_*
# evidence families (see STATIC_ANALYSIS.md). Inert otherwise.
from ..common import lockdep as _lockdep

_lockdep.install()

from ..common import tracing as _tracing   # noqa: E402 — after lockdep


def _wrap_handler(handle, pool, owner=None):
    """Adapt a REST ``handle`` to the HttpServer's 4-tuple form: collect
    the echoed response headers (Trace-Id, X-Opaque-Id) per request.
    Requests execute on ``pool`` (as ``ClusterNode.start_http`` does):
    run inline on the event loop they would serialize, and the
    micro-batcher — whose batch is whatever requests are in flight
    together — could never see more than one. ``owner`` keeps the
    ``__self__`` link HttpServer.start uses to advertise the real bound
    address (http_publish_address)."""
    async def handler(method, path, query, body, headers=None):
        # copy_context: context-bound request state (deprecation
        # warnings, the trace context) follows the request to its thread
        ctx = contextvars.copy_context()
        rh = {}

        def run():
            status, ct, out = ctx.run(handle, method, path, query, body,
                                      headers=headers, resp_headers=rh)
            return status, ct, out, rh

        fut = asyncio.get_running_loop().run_in_executor(pool, run)
        # http[in] ends where the request leaves the event loop; what it
        # waits for a pool thread shows as the gap before rest[parse]
        _tracing.handoff()
        return await fut
    if owner is not None:
        handler.__self__ = owner
    return handler


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="estpu-node")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=9200)
    ap.add_argument("--data", default="./data")
    ap.add_argument("--name", default="estpu-node-0")
    ap.add_argument("--cluster-name", default="es-tpu")
    ap.add_argument("--transport-port", type=int, default=None,
                    help="enable the cluster transport on this port")
    ap.add_argument("--seed", action="append", default=[],
                    metavar="NAME=HOST:PORT",
                    help="cluster peer (repeatable; includes self)")
    ap.add_argument("--jax-platform", default=None,
                    help="force the jax backend (tpu/cpu); default: "
                         "ambient")
    args = ap.parse_args(argv)
    if args.jax_platform:
        import jax
        jax.config.update("jax_platforms", args.jax_platform)
    # this process is the one that holds the chip: compiled serving
    # steps persist across node starts, and a node whose backend came up
    # on the CPU unasked refuses to start instead of serving host-side
    from ..common import runtime
    cache_dir = runtime.enable_compile_cache()
    devices = runtime.require_accelerator(
        allow_cpu=args.jax_platform == "cpu")
    print(f"[{args.name}] jax {devices[0].platform} "
          f"[{devices[0].device_kind}] x{len(devices)}, compile cache "
          f"{cache_dir}", flush=True)
    os.makedirs(args.data, exist_ok=True)
    # this process is a node, whichever stack it builds below: what it
    # installs to serve from (recovered shards, packed planes, loaded
    # programs) leaves the cyclic collector's reach as it is installed,
    # and GET /_nodes/stats counts the passes (its jvm section, gc collectors)
    from ..common import heap
    heap.arm()
    # one request thread per slot of a full micro-batch
    from ..search.microbatch import MAX_BATCH
    pool = ThreadPoolExecutor(max_workers=MAX_BATCH,
                              thread_name_prefix="es-rest-http")

    if args.transport_port is not None and args.seed:
        peers = {}
        for s in args.seed:
            name, _, addr = s.partition("=")
            host, _, port = addr.partition(":")
            peers[name] = (host, int(port))
        from ..node.cluster_node import ClusterNode
        node = ClusterNode(args.name, args.host, args.transport_port,
                           peers, args.data)
        handler = _wrap_handler(node.rest.handle, pool)
        print(f"[{args.name}] cluster node up: transport "
              f"{args.host}:{args.transport_port}, peers "
              f"{sorted(peers)}")
    else:
        from ..node.indices_service import IndicesService
        from ..rest.api import RestAPI
        api = RestAPI(IndicesService(args.data),
                      cluster_name=args.cluster_name,
                      node_name=args.name)
        handler = _wrap_handler(api.handle, pool, owner=api)
        node = None

    from ..rest.http_server import HttpServer

    async def serve():
        srv = HttpServer(handler, host=args.host, port=args.port,
                         pass_headers=True)
        await srv.start()
        print(f"[{args.name}] HTTP listening on "
              f"http://{args.host}:{args.port}", flush=True)
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, stop.set)
            except NotImplementedError:   # pragma: no cover (windows)
                pass
        await stop.wait()
        await srv.stop()

    try:
        asyncio.run(serve())
    finally:
        pool.shutdown(wait=False, cancel_futures=True)
        if node is not None:
            node.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
