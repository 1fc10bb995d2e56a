"""Broker HTTP API: the client edge.

Reference parity: pinot-broker api/resources/PinotClientRequest.java:100 —
POST /query/sql with JSON {"sql": "..."} returning the BrokerResponse
JSON. GET /health for liveness. Stdlib http.server on a daemon thread (no
web framework in the image; the broker edge is not the hot path).
"""
from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from pinot_tpu.broker.request_handler import BrokerRequestHandler


class BrokerHttpServer:
    def __init__(self, handler: BrokerRequestHandler, host: str = "127.0.0.1",
                 port: int = 0):
        self.handler = handler
        broker = self

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # quiet
                pass

            def do_GET(self):
                path = self.path.partition("?")[0].rstrip("/") or "/"
                if path == "/health":
                    body = b"OK"
                elif path == "/metrics":
                    from pinot_tpu.utils.metrics import get_registry
                    body = get_registry("broker").prometheus_text().encode() \
                        + get_registry("server").prometheus_text().encode()
                elif path.startswith("/debug/"):
                    # /debug/traces[/<id>] + /debug/queries: the broker's
                    # trace store + in-flight registry (trace_store.py)
                    from pinot_tpu.utils.trace_store import debug_payload
                    payload = debug_payload("broker", path)
                    if payload is None:
                        self.send_response(404)
                        self.end_headers()
                        return
                    body = json.dumps(payload, default=str).encode()
                else:
                    self.send_response(404)
                    self.end_headers()
                    return
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                if self.path not in ("/query/sql", "/query"):
                    self.send_response(404)
                    self.end_headers()
                    return
                n = int(self.headers.get("Content-Length", 0))
                try:
                    req = json.loads(self.rfile.read(n))
                    sql = req["sql"]
                    if not isinstance(sql, str):
                        raise TypeError("sql must be a string")
                except (json.JSONDecodeError, KeyError, TypeError):
                    self.send_response(400)
                    self.end_headers()
                    return
                resp, body = broker.handler.handle_encoded(sql)
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                # a large answer's rows take milliseconds to free: once
                # it is written, outside the client's wait
                del resp

        self._server = ThreadingHTTPServer((host, port), _Handler)
        self.port = self._server.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True,
            name=f"broker-http-{self.port}")
        self._thread.start()

    def stop(self) -> None:
        self._server.shutdown()
        if self._thread is not None:
            self._thread.join(timeout=5)
