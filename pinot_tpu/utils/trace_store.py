"""Per-role trace retention + in-flight query registry + /debug surfaces.

The observability backplane for the distributed tracing layer
(utils/tracing.py):

* ``TraceStore`` — a bounded in-memory ring of finished trace trees per
  role. ``trace=true`` traces and tail-captured slow queries land here;
  ``/debug/traces`` lists them, ``/debug/traces/<id>`` returns one.
* ``InflightRegistry`` — queries currently executing on this role, with
  elapsed time and the phase they're in (parse/route/scatter/gather/
  reduce broker-side; execute server-side). ``/debug/queries`` reads it:
  "what is the broker doing RIGHT NOW" without attaching a debugger.
* ``slow_query_log`` — one structured (JSON) log line per query over the
  slow threshold, trace id included, so production tails are grep-able
  after the fact even when the store has rolled over.
* ``DebugHttpServer`` — a tiny stdlib HTTP surface any role can mount
  (server, minion, cache server: roles with no existing HTTP edge)
  serving /health, /metrics (Prometheus exposition over the role's
  registries) and the /debug endpoints above. The broker and controller
  mount the same payloads into their existing HTTP APIs via
  ``debug_payload``.
"""
from __future__ import annotations

import json
import logging
import threading
import time
from collections import OrderedDict
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional, Sequence

slow_log = logging.getLogger("pinot_tpu.slowquery")

DEFAULT_CAPACITY = 256


class TraceStore:
    """Bounded FIFO of finished traces for one role (newest kept)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = max(1, int(capacity))
        self._traces: "OrderedDict[str, dict]" = OrderedDict()
        self._lock = threading.Lock()

    def record(self, trace_id: str, tree: dict, *, sql: str = "",
               duration_ms: float = 0.0, slow: bool = False,
               extra: Optional[dict] = None) -> None:
        entry = {"traceId": trace_id, "sql": sql,
                 "durationMs": round(float(duration_ms), 3),
                 "slow": bool(slow), "storedAt": time.time(),
                 "trace": tree}
        if extra:
            entry.update(extra)
        with self._lock:
            # re-recording (broker stores the sampled trace, then the
            # slow-capture pass fires too) replaces, never duplicates
            self._traces[trace_id] = entry
            self._traces.move_to_end(trace_id)
            while len(self._traces) > self.capacity:
                self._traces.popitem(last=False)

    def get(self, trace_id: str) -> Optional[dict]:
        with self._lock:
            hit = self._traces.get(trace_id)
            if hit is not None:
                return hit
            # instance-suffixed keys (several instances of one role in
            # a single process — the embedded-cluster topology — store
            # under "<traceId>@<instance>" so they don't overwrite each
            # other): fall back to a scan on the recorded traceId
            for e in reversed(self._traces.values()):
                if e.get("traceId") == trace_id:
                    return e
            return None

    def recent(self, limit: int = 50) -> List[dict]:
        """Newest first, trace trees elided (fetch one by id for the
        full tree) — the /debug/traces listing."""
        with self._lock:
            items = list(self._traces.values())[-max(1, int(limit)):]
        out = []
        for e in reversed(items):
            summary = {k: v for k, v in e.items() if k != "trace"}
            out.append(summary)
        return out

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._traces)


class InflightRegistry:
    """Queries currently executing on this role, with current phase."""

    def __init__(self):
        self._entries: Dict[str, dict] = {}
        self._lock = threading.Lock()

    def begin(self, key: str, *, sql: str = "", trace_id: str = "",
              detail: str = "", tenant: Optional[str] = None,
              deadline: Optional[float] = None) -> None:
        """tenant/deadline: attribution + the absolute wall-clock
        deadline (time.time() domain) — /debug/queries surfaces both so
        an incident responder sees WHOSE query is in flight and how much
        budget it has left, not just how long it has run."""
        with self._lock:
            self._entries[key] = {
                "queryId": key, "sql": sql, "traceId": trace_id,
                "startedAt": time.time(), "phase": "started",
                "detail": detail, "tenant": tenant, "deadline": deadline}

    def phase(self, key: str, phase: str, detail: str = "") -> None:
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                e["phase"] = phase
                if detail:
                    e["detail"] = detail

    def annotate(self, key: str, *, tenant: Optional[str] = None,
                 deadline: Optional[float] = None) -> None:
        """Late attribution: the broker learns tenant + deadline only
        after parse/route, well inside the entry's lifetime."""
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                if tenant is not None:
                    e["tenant"] = tenant
                if deadline is not None:
                    e["deadline"] = deadline

    def end(self, key: str) -> None:
        with self._lock:
            self._entries.pop(key, None)

    def snapshot(self) -> List[dict]:
        now = time.time()
        with self._lock:
            entries = [dict(e) for e in self._entries.values()]
        for e in entries:
            e["elapsedMs"] = round((now - e.pop("startedAt")) * 1000.0, 3)
            deadline = e.pop("deadline", None)
            e["remainingDeadlineMs"] = (
                round((deadline - now) * 1000.0, 3)
                if deadline is not None else None)
        entries.sort(key=lambda e: -e["elapsedMs"])
        return entries

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


# -- per-role singletons (the get_registry pattern) -------------------------
_stores: Dict[str, TraceStore] = {}
_inflight: Dict[str, InflightRegistry] = {}
_lock = threading.Lock()


def get_store(role: str = "server",
              capacity: Optional[int] = None) -> TraceStore:
    with _lock:
        s = _stores.get(role)
        if s is None:
            s = _stores[role] = TraceStore(capacity or DEFAULT_CAPACITY)
        elif capacity is not None:
            s.capacity = max(1, int(capacity))
        return s


def get_inflight(role: str = "server") -> InflightRegistry:
    with _lock:
        r = _inflight.get(role)
        if r is None:
            r = _inflight[role] = InflightRegistry()
        return r


def log_slow_query(role: str, trace_id: str, sql: str, duration_ms: float,
                   threshold_ms: float, **extra) -> None:
    """One structured line per slow query: grep-able JSON with the trace
    id linking to the stored tree (`/debug/traces/<id>`)."""
    payload = {"role": role, "traceId": trace_id, "sql": sql,
               "durationMs": round(float(duration_ms), 3),
               "thresholdMs": round(float(threshold_ms), 3), **extra}
    slow_log.warning("SLOW_QUERY %s", json.dumps(payload, default=str))


# -- shared HTTP payloads ----------------------------------------------------

def debug_payload(role: str, path: str) -> Optional[Any]:
    """The /debug router shared by every HTTP surface. Returns the JSON
    payload for the path, or None when the path isn't a debug route.
    Health-plane routes (PR 14) import lazily — the trace store must not
    drag the health package in at module import."""
    if path == "/debug/traces":
        return {"role": role, "traces": get_store(role).recent()}
    if path.startswith("/debug/traces/"):
        tid = path[len("/debug/traces/"):]
        entry = get_store(role).get(tid)
        return entry if entry is not None \
            else {"error": f"no trace {tid}", "role": role}
    if path == "/debug/queries":
        return {"role": role, "queries": get_inflight(role).snapshot()}
    if path == "/debug/metrics/sample":
        from pinot_tpu.utils.metrics import get_registry
        return get_registry(role).sample()
    if path == "/debug/metrics/history":
        from pinot_tpu.health.history import get_history
        return {"role": role, "samples": get_history(role).samples()}
    if path == "/debug/health":
        from pinot_tpu.health.rollup import role_health_summary
        return role_health_summary(role)
    if path == "/debug/workload":
        from pinot_tpu.health.workload import get_workload
        return get_workload(role).payload()
    return None


class DebugHttpServer:
    """Tiny ops surface for roles without an HTTP edge (server, minion,
    cache server): /health, /metrics (exposition over the role's
    registries), /debug/traces[/id], /debug/queries, plus the owner's
    own JSON `routes` (path -> zero-arg callable)."""

    def __init__(self, roles: Sequence[str], host: str = "127.0.0.1",
                 port: int = 0, routes=None):
        roles = list(roles)
        primary = roles[0] if roles else "server"
        routes = dict(routes or {})

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # quiet
                pass

            def do_GET(self):
                path = self.path.partition("?")[0].rstrip("/") or "/"
                if path == "/health":
                    body, ctype = b"OK", "text/plain"
                elif path == "/metrics":
                    from pinot_tpu.utils.metrics import get_registry
                    body = b"".join(
                        get_registry(r).prometheus_text().encode()
                        for r in roles)
                    ctype = "text/plain"
                else:
                    payload = routes[path]() if path in routes \
                        else debug_payload(primary, path)
                    if payload is None:
                        self.send_response(404)
                        self.end_headers()
                        return
                    body = json.dumps(payload, default=str).encode()
                    ctype = "application/json"
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self._server = ThreadingHTTPServer((host, port), _Handler)
        self.host, self.port = self._server.server_address[:2]
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True,
            name=f"debug-http-{self.port}")
        self._thread.start()

    def stop(self) -> None:
        if self._thread is not None:
            self._server.shutdown()
        self._server.server_close()
        self._thread = None
