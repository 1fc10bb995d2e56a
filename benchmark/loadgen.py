"""The load: a closed loop of clients, one thread each, against the
broker's POST /query/sql. One process, few threads; the query list is
made beforehand; the collector is frozen and off while a window runs.
The broker speaks HTTP/1.0, so every query opens its own connection, as
any client of it has to."""
from __future__ import annotations

import gc
import http.client
import json
import threading
import time

from judge import device_served, spans

#: a query that has not answered after this long is counted unanswered
QUERY_TIMEOUT_S = 120.0
#: how long past the close a window waits for what is still in flight
GRACE_S = 60.0


def post(host: str, port: int, sql: str, timeout: float = QUERY_TIMEOUT_S):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", "/query/sql", json.dumps({"sql": sql}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        if resp.status != 200:
            raise OSError(f"HTTP {resp.status}: {body[:200]!r}")
        return json.loads(body)
    finally:
        conn.close()


def one_query(host: str, port: int, query, traced: bool, t0: float) -> dict:
    """Send one query; the record of what came back. `failure` is set
    where the query counts as failed: an error, a partial or cached
    answer, a server missing, or (traced) a leg off the device."""
    template, literals, sql = query
    rec = {"template": template, "literals": literals, "traced": traced,
           "rows": None, "served": False, "failure": None,
           "sent_s": time.perf_counter() - t0}
    try:
        resp = post(host, port, sql)
    except (OSError, ValueError, http.client.HTTPException) as e:
        rec["failure"] = f"{type(e).__name__}: {e}"
        resp = None
    rec["done_s"] = time.perf_counter() - t0
    rec["done_wall"] = time.time()
    if resp is None:
        return rec
    if resp.get("exceptions"):
        rec["failure"] = f"exceptions: {resp['exceptions']}"[:300]
        return rec
    rec["rows"] = (resp.get("resultTable") or {}).get("rows") or []
    rec["time_used_ms"] = resp.get("timeUsedMs")
    if resp.get("partialResult") \
            or resp.get("numServersResponded") != resp.get("numServersQueried") \
            or not resp.get("numServersQueried"):
        rec["failure"] = (f"partial: {resp.get('numServersResponded')} of "
                          f"{resp.get('numServersQueried')} servers")
    if traced:
        info = resp.get("traceInfo")
        rec["served"] = device_served(info)
        if not rec["served"]:
            rec["failure"] = "not device-served: " + json.dumps(
                spans(info, "DeviceDispatch"))[:300]
        rec["trace"] = info
    return rec


def closed_loop(host: str, port: int, queries: list, clients: int,
                seconds: float, traced: bool, on_tick=None) -> list:
    """Each client sends its next query when the last has answered, and
    none after `seconds`. Returns the records. on_tick(elapsed) runs in
    this thread about every 50 ms."""
    records = [[] for _ in range(clients)]
    gc.collect()
    gc.freeze()
    gc.disable()
    t0 = time.perf_counter()

    def client(c: int) -> None:
        i = c
        while i < len(queries) and time.perf_counter() - t0 < seconds:
            records[c].append(one_query(host, port, queries[i], traced, t0))
            i += clients

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    try:
        for t in threads:
            t.start()
        deadline = t0 + seconds + GRACE_S + QUERY_TIMEOUT_S
        for t in threads:
            while t.is_alive() and time.perf_counter() < deadline:
                t.join(timeout=0.05)
                if on_tick is not None:
                    on_tick(time.perf_counter() - t0)
    finally:
        gc.enable()
        gc.unfreeze()
    return [r for per in records for r in per]
