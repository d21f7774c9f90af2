"""The parent's side of the node child: start it on a data directory, talk
HTTP to it, stop it. Nothing here imports JAX. ``Http``, ``prom_values``
and ``free_port`` are ``chip_smoke.py``'s (PR 21)."""

from __future__ import annotations

import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time

from .manifest import BENCH_DIR, CHECKOUT


class NodeError(Exception):
    pass


class Http:
    """One keep-alive connection to the node (one per client thread)."""

    def __init__(self, port: int, timeout: float = 900):
        self.port = port
        self.timeout = timeout
        self.conn = None

    def raw(self, method: str, path: str, body: bytes | None = None):
        """(status, response bytes); reconnects once on a dropped
        keep-alive connection."""
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=self.timeout)
            try:
                self.conn.request(
                    method, path, body=body,
                    headers={"content-type": "application/json"})
                resp = self.conn.getresponse()
                return resp.status, resp.read()
            except (OSError, http.client.HTTPException) as e:
                self.close()
                if attempt or isinstance(e, TimeoutError):
                    raise

    def call(self, method: str, path: str, body=None):
        if isinstance(body, (dict, list)):
            body = json.dumps(body).encode()
        status, raw = self.raw(method, path, body)
        try:
            return status, json.loads(raw)
        except ValueError:
            return status, raw.decode(errors="replace")

    def ok(self, method: str, path: str, body=None):
        status, out = self.call(method, path, body)
        if status != 200:
            raise NodeError(f"{method} {path} -> {status}: {str(out)[:600]}")
        return out

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def prom_values(text: str, family: str) -> dict:
    """{label-string: value} of one family from the text exposition."""
    out = {}
    for m in re.finditer(
            rf"^{re.escape(family)}(\{{[^}}]*\}})?\s+([0-9.eE+-]+)$",
            text, re.M):
        out[m.group(1) or ""] = float(m.group(2))
    return out


class Node:
    """The node child on ``data_dir``, with the environment the
    configuration asks for."""

    def __init__(self, data_dir: str, log_path: str, env_extra: dict,
                 launcher: str | None = None):
        self.port = free_port()
        self.control_port = free_port()
        self.log_path = log_path
        env = dict(os.environ)
        env.update({k: str(v) for k, v in env_extra.items()})
        launcher = launcher or os.path.join(BENCH_DIR, "harness",
                                            "node_main.py")
        self.log = open(log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, launcher, "--control-port",
             str(self.control_port), "--", "--port", str(self.port),
             "--data", data_dir, "--name", "bench-node"],
            cwd=CHECKOUT, env=env, stdout=self.log,
            stderr=subprocess.STDOUT)
        self.http = Http(self.port)

    def wait_up(self, timeout: float = 300) -> None:
        deadline = time.monotonic() + timeout
        while True:
            if self.proc.poll() is not None:
                raise NodeError(f"node exited with code "
                                f"{self.proc.returncode} before serving:\n"
                                f"{self.log_tail()}")
            probe = Http(self.port, timeout=5)
            try:
                if probe.call("GET", "/")[0] == 200:
                    return
            except OSError:
                pass
            finally:
                probe.close()
            if time.monotonic() > deadline:
                raise NodeError(f"node did not serve in {timeout:.0f} s")
            time.sleep(0.25)

    def device(self) -> dict:
        """Platform, kind and count as the node's JAX reports them, and
        the peak device memory so far."""
        doc = self.http.ok("GET", "/_nodes/stats/device")
        dev = next(iter(doc["nodes"].values()))["device"]
        devs = dev["devices"]
        peak = max((d.get("memory", {}).get("peak_bytes_in_use") or 0)
                   for d in devs)
        return {"platform": devs[0]["platform"],
                "kind": devs[0]["device_kind"], "count": len(devs),
                "memory_peak_bytes": int(peak)}

    def control(self, path: str) -> dict:
        http = Http(self.control_port)
        try:
            status, out = http.call("POST", path)
        finally:
            http.close()
        if status != 200:
            raise NodeError(f"control {path} -> {status}: {out}")
        return out

    def log_tail(self, n: int = 3000) -> str:
        try:
            with open(self.log_path) as f:
                return f.read()[-n:]
        except OSError:
            return ""

    def stop(self) -> None:
        """SIGTERM, then SIGKILL: the node keeps nothing a run needs (its
        HTTP server waits for every keep-alive connection to close, so the
        parent's own are closed first)."""
        self.http.close()
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(5)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(20)
        self.log.close()
