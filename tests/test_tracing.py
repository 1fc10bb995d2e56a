"""Fleet-wide distributed tracing (ISSUE 12).

Covers: cross-process span propagation + stitching (broker -> servers ->
MSE stages), thread-safe capture-and-attach span handles through the
dispatch ring, tail-based slow-query capture with trace=false, the
/debug/traces + /debug/queries surfaces on every role, trace isolation
under the coalesced dispatch path, same-seed chaos structural identity,
the Timer thread-safety fix, exemplars, and the static exposition lint.
"""
import json
import os
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

from pinot_tpu.cluster.mini import MiniCluster
from pinot_tpu.utils import tracing, trace_store
from pinot_tpu.utils.config import PinotConfiguration
from pinot_tpu.utils.failpoints import failpoints
from pinot_tpu.utils.metrics import MetricsRegistry
from tests.queries.harness import (
    build_segments, synthetic_columns, synthetic_schema,
    synthetic_table_config)

NUM_DOCS = 400


def _spans(tree, name):
    """All spans named `name` anywhere in a trace tree dict."""
    out = []

    def walk(n):
        if n.get("operator") == name:
            out.append(n)
        for c in n.get("children", ()):
            walk(c)

    walk(tree)
    return out


def _shape(tree):
    """Structure-only view of a tree: operator names, child order-free —
    timings/ids/attrs stripped, so two same-seed chaos runs compare
    structurally."""
    return (tree.get("operator"),
            tuple(sorted(_shape(c) for c in tree.get("children", ()))))


# ---------------------------------------------------------------------------
# unit: span handles + trace contexts
# ---------------------------------------------------------------------------

class TestSpanHandles:
    def test_capture_and_attach_across_threads(self):
        rt = tracing.RequestTrace()
        with rt:
            h = tracing.capture()
        assert h is not None

        def worker(i):
            sp = h.child("Worker", idx=i)
            sp.end(done=True)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        d = rt.to_dict()
        assert len(_spans(d, "Worker")) == 16
        assert all(c["done"] for c in _spans(d, "Worker"))

    def test_concurrent_scope_hammer(self):
        """Scopes + handle children mutating one tree from many threads
        never corrupt it (the module tree lock)."""
        rt = tracing.RequestTrace()
        with rt:
            h = tracing.capture()
        errs = []

        def hammer():
            try:
                for i in range(200):
                    sp = h.child("S", i=i)
                    sp.set(j=i)
                    sp.end()
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=hammer) for _ in range(8)]
        for t in threads:
            t.start()
        readers_done = threading.Event()

        def reader():
            while not readers_done.is_set():
                rt.to_dict()

        r = threading.Thread(target=reader)
        r.start()
        for t in threads:
            t.join()
        readers_done.set()
        r.join()
        assert not errs
        assert len(_spans(rt.to_dict(), "S")) == 1600

    def test_graft_and_wire_context(self):
        rt = tracing.RequestTrace(sampled=True)
        wire = rt.wire_context()
        tc = tracing.TraceContext.from_wire(wire)
        assert tc.trace_id == rt.trace_id and tc.sampled
        remote = tracing.RequestTrace(operator="ServerRequest",
                                      trace_id=tc.trace_id)
        with remote:
            with tracing.Scope("Inner", x=1):
                pass
        rt.handle().graft(remote.to_dict())
        d = rt.to_dict()
        assert _spans(d, "ServerRequest")
        assert _spans(d, "Inner")[0]["x"] == 1
        # a torn tree must never fail the query path
        rt.handle().graft({"operator": object()})
        rt.handle().graft(None)

    def test_tracing_off_is_inert(self):
        assert tracing.capture() is None
        assert tracing.current_request() is None
        assert tracing.current_trace_id() is None
        tracing.annotate(x=1)  # no-op, no error
        with tracing.Scope("S") as sc:
            sc.set(y=2)  # inactive scope: no tree, no error
        # ... and no clock read: an inactive scope is never placed
        assert sc.node.start_ns == 0 and sc.node.start_ms == 0.0
        assert "startNs" not in sc.node.to_dict()


# ---------------------------------------------------------------------------
# satellite: Timer thread-safety + exemplars
# ---------------------------------------------------------------------------

class TestTimerThreadSafety:
    def test_concurrent_update_and_quantile(self):
        """quantile()/samples on a snapshot never race a concurrent
        update (pre-fix: timer() returned the LIVE Timer whose reservoir
        list update() mutates mid-iteration)."""
        reg = MetricsRegistry("t")
        stop = threading.Event()
        errs = []

        def writer():
            i = 0
            while not stop.is_set():
                reg.add_timing("lat", float(i % 100))
                i += 1

        def reader():
            try:
                while not stop.is_set():
                    t = reg.timer("lat")
                    t.quantile(0.95)
                    _ = t.samples
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=writer) for _ in range(4)] + \
                  [threading.Thread(target=reader) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.4)
        stop.set()
        for t in threads:
            t.join()
        assert not errs
        # consistent view: a snapshot's counters and reservoir agree
        snap = reg.timer("lat")
        assert snap.count >= len(snap.samples)

    def test_timer_miss_returns_empty_snapshot(self):
        reg = MetricsRegistry("t")
        t = reg.timer("never")
        assert t.count == 0 and t.quantile(0.5) == 0.0

    def test_exemplar_links_metrics_to_traces(self):
        reg = MetricsRegistry("broker")
        reg.add_timing("broker_query_ms", 12.5, exemplar="abc123")
        assert reg.exemplar("broker_query_ms") == "abc123"
        text = reg.prometheus_text()
        assert '# EXEMPLAR pinot_tpu_broker_broker_query_ms ' \
               'trace_id="abc123"' in text
        # exemplar lines are comments: every non-comment line still
        # parses as `name{labels} value`
        for line in text.splitlines():
            if line.startswith("#"):
                continue
            assert re.fullmatch(r'[a-zA-Z_:][\w:]*(\{.*\})? \S+', line), line


# ---------------------------------------------------------------------------
# acceptance: ONE stitched cross-process tree
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_cluster(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("traced")
    data = [synthetic_columns(NUM_DOCS, seed=11 + i) for i in range(4)]
    segs = build_segments(tmp, synthetic_schema(),
                          synthetic_table_config(), data)
    # a tiny dimension table for the MSE join leg
    from pinot_tpu.models.schema import Schema
    from pinot_tpu.models.table_config import TableConfig
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment
    dim_schema = Schema.from_dict({
        "schemaName": "dim",
        "dimensionFieldSpecs": [{"name": "g", "dataType": "STRING"},
                                {"name": "label", "dataType": "STRING"}]})
    creator = SegmentCreator(
        TableConfig.from_dict({"tableName": "dim",
                               "tableType": "OFFLINE"}), dim_schema)
    groups = sorted({str(g) for d in data for g in d["groupCol"]})
    ddir = str(tmp / "dim_0")
    creator.build({"g": np.array(groups),
                   "label": np.array([f"L{g}" for g in groups])},
                  ddir, "dim_0")
    dim_seg = load_segment(ddir)

    c = MiniCluster(num_servers=2, use_tpu=True)
    c.start(with_http=True)
    c.add_table("testTable")
    for i, seg in enumerate(segs):
        c.add_segment("testTable", seg, server_idx=i % 2)
    c.add_table("dim")
    c.add_segment("dim", dim_seg, server_idx=0)
    yield c, data
    c.stop()


class TestStitchedTrace:
    def test_scatter_trace_is_one_stitched_tree(self, traced_cluster):
        """Acceptance: trace=true over a >=2-server scatter returns ONE
        tree containing broker, per-server, and dispatch-phase spans
        with queue wait / batch size / kernel ms / fetch ms / transfer
        bytes attrs."""
        c, _ = traced_cluster
        resp = c.query("SET trace = true; SELECT SUM(intCol) "
                       "FROM testTable WHERE intCol >= 100")
        assert not resp.exceptions, resp.exceptions
        tree = resp.trace
        assert tree is not None and tree["operator"] == "BrokerRequest"
        scatters = _spans(tree, "ServerScatter")
        assert len(scatters) >= 2
        assert {s["server"] for s in scatters} == {"server_0", "server_1"}
        servers = _spans(tree, "ServerRequest")
        assert len(servers) >= 2, "server trees not stitched in"
        assert all("queueWaitMs" in s for s in servers)
        dispatches = _spans(tree, "DeviceDispatch")
        assert dispatches, "device dispatch phase missing"
        for d in dispatches:
            assert "kernelMs" in d and "fetchMs" in d
            assert "batchSize" in d and "queueWaitMs" in d
            assert "transferBytes" in d and "stagingMs" in d
        assert _spans(tree, "BrokerReduce")
        # the broker retains the sampled trace for /debug/traces
        stored = trace_store.get_store("broker").get(tree["traceId"])
        assert stored is not None and stored["trace"]["traceId"] == \
            tree["traceId"]

    def test_cache_tier_attr_lands_in_trace(self, traced_cluster):
        """The tier-2 segment cache annotates the server's span tree
        (cacheHit / SegmentResultCache scope)."""
        c, _ = traced_cluster
        sql = ("SET trace = true; SELECT MAX(intCol) FROM testTable "
               "WHERE intCol < 900")
        c.query(sql)
        resp = c.query(sql)  # second run: tier-2 hit server-side
        hits = _spans(resp.trace, "SegmentResultCache")
        assert hits and any(s.get("cacheHits", 0) > 0 for s in hits)

    def test_mse_join_trace_has_stage_spans(self, traced_cluster):
        """Acceptance: an MSE join returns the same stitched tree with
        per-stage spans (MseQuery -> MseStage trees shipped back over
        the control plane)."""
        c, _ = traced_cluster
        resp = c.query(
            "SET trace = true; "
            "SELECT d.label, COUNT(*) FROM testTable t "
            "JOIN dim d ON t.groupCol = d.g "
            "GROUP BY d.label ORDER BY d.label LIMIT 100")
        assert not resp.exceptions, resp.exceptions
        tree = resp.trace
        assert tree is not None
        mse = _spans(tree, "MseQuery")
        assert len(mse) == 1
        stages = _spans(tree, "MseStage")
        assert len(stages) >= 2, "per-stage worker trees missing"
        assert {s["instance"] for s in stages} >= {"server_0"}
        # op-level scopes inside the stage trees
        assert _spans(tree, "mse:leaf_agg") or _spans(tree, "mse:scan")
        assert _spans(tree, "mse:send")
        # stage ids distinguish the spans
        assert len({(s["stage"], s["instance"], s.get("workerIdx"))
                    for s in stages}) == len(stages)

    def test_trace_false_returns_no_trace(self, traced_cluster):
        c, _ = traced_cluster
        resp = c.query("SELECT COUNT(*) FROM testTable "
                       "OPTION(skipCache=true)")
        assert resp.trace is None


# ---------------------------------------------------------------------------
# tail-based slow-query capture + /debug surfaces
# ---------------------------------------------------------------------------

class TestSlowQueryCapture:
    @pytest.fixture()
    def slow_cluster(self, tmp_path):
        data = [synthetic_columns(NUM_DOCS, seed=3)]
        segs = build_segments(tmp_path, synthetic_schema(),
                              synthetic_table_config(), data)
        cfg = PinotConfiguration(overrides={
            "pinot.broker.slow.query.threshold.ms": 0.001})
        c = MiniCluster(num_servers=1, config=cfg)
        c.start(with_http=True)
        c.add_table("testTable")
        c.add_segment("testTable", segs[0], server_idx=0)
        yield c
        c.stop()

    def test_slow_query_retained_with_trace_false(self, slow_cluster,
                                                  caplog):
        """Acceptance: a query over the slow threshold is retrievable
        from /debug/traces — stitched server spans included — even with
        trace=false, plus a structured slow-query log line."""
        import logging
        trace_store.get_store("broker").clear()
        with caplog.at_level(logging.WARNING, logger="pinot_tpu.slowquery"):
            resp = slow_cluster.query(
                "SELECT SUM(intCol) FROM testTable "
                "OPTION(skipCache=true)")
        assert resp.trace is None  # client asked for nothing back
        recent = trace_store.get_store("broker").recent()
        assert recent and recent[0]["slow"] is True
        tid = recent[0]["traceId"]
        stored = trace_store.get_store("broker").get(tid)
        # the tail-captured tree is STITCHED: server spans are in it
        assert _spans(stored["trace"], "ServerRequest")
        # structured log line with the trace id
        lines = [r.message for r in caplog.records
                 if "SLOW_QUERY" in r.message]
        assert lines
        payload = json.loads(lines[-1].split("SLOW_QUERY ", 1)[1])
        assert payload["traceId"] == tid
        assert payload["durationMs"] >= 0.001
        # ... and over HTTP
        with urllib.request.urlopen(
                f"http://127.0.0.1:{slow_cluster.http.port}"
                f"/debug/traces/{tid}", timeout=10) as f:
            got = json.loads(f.read())
        assert got["traceId"] == tid and got["slow"] is True
        with urllib.request.urlopen(
                f"http://127.0.0.1:{slow_cluster.http.port}/debug/traces",
                timeout=10) as f:
            listing = json.loads(f.read())
        assert any(e["traceId"] == tid for e in listing["traces"])
        # the exemplar on the broker query timer names the latest trace
        from pinot_tpu.utils.metrics import get_registry
        assert get_registry("broker").exemplar("broker_query_ms")

    def test_debug_queries_shows_inflight_phase(self, slow_cluster):
        trace_store.get_inflight("broker")  # ensure registry exists
        with failpoints.armed("server.execute.before", delay=0.6):
            t = threading.Thread(
                target=slow_cluster.query,
                args=("SELECT COUNT(*) FROM testTable "
                      "OPTION(skipCache=true)",))
            t.start()
            deadline = time.time() + 5
            snap = []
            while time.time() < deadline:
                snap = trace_store.get_inflight("broker").snapshot()
                if snap:
                    break
                time.sleep(0.01)
            assert snap, "in-flight query not visible"
            assert snap[0]["phase"] in ("parse", "route", "scatter",
                                        "gather", "reduce")
            assert "COUNT(*)" in snap[0]["sql"]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{slow_cluster.http.port}"
                    "/debug/queries", timeout=10) as f:
                got = json.loads(f.read())
            assert got["queries"] and "elapsedMs" in got["queries"][0]
            t.join(timeout=10)
        assert trace_store.get_inflight("broker").snapshot() == []


# ---------------------------------------------------------------------------
# trace isolation through the coalesced dispatch path
# ---------------------------------------------------------------------------

class TestTraceIsolation:
    def test_concurrent_traces_never_cross(self, traced_cluster):
        """N concurrent trace=true queries whose launches may coalesce
        into shared batched kernels still produce N disjoint trees: each
        tree carries its own trace id, exactly its own scatter/dispatch
        spans, and the right rows for its own literal."""
        c, data = traced_cluster
        v = np.concatenate([np.asarray(d["intCol"]) for d in data])
        bounds = [100, 200, 300, 400, 500, 600, 700, 800]
        results = [None] * len(bounds)

        def run(i):
            resp = c.query(
                f"SET trace = true; SELECT SUM(intCol), COUNT(*) "
                f"FROM testTable WHERE intCol >= {bounds[i]}")
            results[i] = resp

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(bounds))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        trace_ids = set()
        for i, resp in enumerate(results):
            assert not resp.exceptions, resp.exceptions
            # correctness per literal: no cross-query result mixing
            want = float(v[v >= bounds[i]].sum())
            assert float(resp.rows[0][0]) == pytest.approx(want), i
            tree = resp.trace
            assert tree is not None, i
            trace_ids.add(tree["traceId"])
            # every span in MY tree belongs to MY trace: exactly one
            # scatter per server attempt-set, one grafted ServerRequest
            # per scatter, no duplicated/foreign subtrees
            scatters = _spans(tree, "ServerScatter")
            assert len(scatters) == 2, tree
            assert len(_spans(tree, "ServerRequest")) == 2
            for d_sp in _spans(tree, "DeviceDispatch"):
                # a shared batched launch reports into N distinct trees;
                # per-member attrs must be complete in each
                assert "kernelMs" in d_sp and "batchSize" in d_sp
        assert len(trace_ids) == len(bounds), "trace ids collided"


# ---------------------------------------------------------------------------
# same-seed chaos -> structurally identical trees
# ---------------------------------------------------------------------------

class TestChaosTraceIdentity:
    def _run_once(self, tmp_path, tag, chaos):
        data = [synthetic_columns(NUM_DOCS, seed=5)]
        segs = build_segments(tmp_path / tag, synthetic_schema(),
                              synthetic_table_config(), data)
        c = MiniCluster(num_servers=2, chaos=chaos)
        c.start()
        c.add_table("testTable")
        # same segment on BOTH servers: the retry has a surviving replica
        c.add_segment("testTable", segs[0], server_idx=0, replicas=[1])
        try:
            resp = c.query("SET trace = true; SELECT COUNT(*) "
                           "FROM testTable OPTION(skipCache=true)")
            assert resp.trace is not None
            return resp
        finally:
            c.stop()

    @pytest.mark.chaos
    def test_same_seed_retry_trees_identical(self, tmp_path):
        """A seeded one-shot scatter failure forces a retry; two fresh
        same-seed runs produce structurally identical trace trees
        (operator structure + outcome tags), so a chaos trace is a
        reproducible artifact, not a one-off."""
        def schedule():
            # broker.scatter.before raises on the fan-out thread, so the
            # failure takes the broker's retry path (connection.request
            # errors would be absorbed by the channel's own re-dial)
            return [("broker.scatter.before",
                     {"error": ConnectionError("chaos"), "times": 1,
                      "seed": 1234})]

        r1 = self._run_once(tmp_path, "a", schedule())
        r2 = self._run_once(tmp_path, "b", schedule())
        assert not r1.exceptions and not r2.exceptions
        assert _shape(r1.trace) == _shape(r2.trace)
        # the retry is visible: a failed attempt + a retry sibling
        outcomes1 = sorted(s.get("outcome", "") + (
            "retry" if s.get("retry") else "")
            for s in _spans(r1.trace, "ServerScatter"))
        outcomes2 = sorted(s.get("outcome", "") + (
            "retry" if s.get("retry") else "")
            for s in _spans(r2.trace, "ServerScatter"))
        assert outcomes1 == outcomes2
        assert any("failed" in o for o in outcomes1)
        assert any("retry" in o for o in outcomes1)


# ---------------------------------------------------------------------------
# /metrics on every role
# ---------------------------------------------------------------------------

class TestMetricsEveryRole:
    def test_controller_http_metrics_and_debug(self):
        from pinot_tpu.controller.cluster_state import ClusterState
        from pinot_tpu.controller.http_api import ControllerHttpServer
        from pinot_tpu.utils.metrics import get_registry
        get_registry("controller").add_meter("tables_added")
        srv = ControllerHttpServer(ClusterState())
        srv.start()
        try:
            with urllib.request.urlopen(
                    f"http://{srv.host}:{srv.port}/metrics",
                    timeout=10) as f:
                text = f.read().decode()
            assert "pinot_tpu_controller_tables_added" in text
            with urllib.request.urlopen(
                    f"http://{srv.host}:{srv.port}/debug/queries",
                    timeout=10) as f:
                got = json.loads(f.read())
            assert got["role"] == "controller"
        finally:
            srv.stop()

    def test_debug_http_server_for_server_and_minion_roles(self):
        """DebugHttpServer: the exposition surface server/minion/cache
        roles mount (ServerRole.start wires it via
        pinot.server.admin.port)."""
        from pinot_tpu.utils.metrics import get_registry
        from pinot_tpu.utils.trace_store import DebugHttpServer
        get_registry("minion").add_meter("minion_tasks_completed", 0)
        srv = DebugHttpServer(["minion"])
        srv.start()
        try:
            with urllib.request.urlopen(
                    f"http://{srv.host}:{srv.port}/metrics",
                    timeout=10) as f:
                text = f.read().decode()
            assert "pinot_tpu_minion_minion_tasks_completed" in text
            with urllib.request.urlopen(
                    f"http://{srv.host}:{srv.port}/health",
                    timeout=10) as f:
                assert f.read() == b"OK"
            trace_store.get_store("minion").record(
                "tid-1", {"operator": "MinionTask"}, sql="task:Purge")
            with urllib.request.urlopen(
                    f"http://{srv.host}:{srv.port}/debug/traces/tid-1",
                    timeout=10) as f:
                got = json.loads(f.read())
            assert got["trace"]["operator"] == "MinionTask"
        finally:
            srv.stop()

    def test_server_role_admin_knob_disabled(self):
        """pinot.server.admin.port < 0 disables the surface."""
        from pinot_tpu.cluster.roles import _start_admin
        cfg = PinotConfiguration(
            overrides={"pinot.server.admin.port": -1})
        assert _start_admin(cfg, "pinot.server.admin.port",
                            ["server"]) is None


# ---------------------------------------------------------------------------
# satellite: static exposition lint — MIGRATED into the analysis framework
# (pinot_tpu/analysis/checkers/exposition.py, gated by
# tests/test_static_analysis.py). Only the live-registry belt-and-braces
# check stays here.
# ---------------------------------------------------------------------------

class TestExpositionLive:
    def test_live_exposition_has_one_type_per_name(self):
        """Belt-and-braces on a real registry page (the static lint
        itself now lives in the analysis framework)."""
        reg = MetricsRegistry("lint")
        reg.add_meter("a", labels={"x": "1"})
        reg.add_meter("a", labels={"x": "2"})
        reg.set_gauge("b", 1.0)
        reg.add_timing("c", 5.0)
        text = reg.prometheus_text()
        names = [ln.split()[2] for ln in text.splitlines()
                 if ln.startswith("# TYPE")]
        assert len(names) == len(set(names))


# ---------------------------------------------------------------------------
# minion task traces
# ---------------------------------------------------------------------------

class TestMinionTaskTrace:
    def test_task_trace_rides_completion(self, tmp_path):
        """A purge task's span tree returns in the TaskEntry result
        (retrievable via /tasks/{id} semantics) with execute/upload/
        commit phases."""
        from tests.test_minion import _mini_cluster  # shared harness
        from pinot_tpu.controller.tasks import TaskConfig
        cluster, names = _mini_cluster(tmp_path, n_segments=1, minions=1,
                                       num_servers=1)
        try:
            entry = cluster.submit_task(TaskConfig(
                "PurgeTask", "ct_OFFLINE", names,
                {"purgePredicate": "ts < 30"}))
            done = cluster.wait_task(entry["task_id"], timeout_s=30)
            assert done["state"] == "COMPLETED", done
            result = done["result"]
            assert result.get("traceId")
            tree = result.get("trace")
            assert tree and tree["operator"] == "MinionTask"
            assert _spans(tree, "TaskExecute")
            assert _spans(tree, "TaskUpload")
        finally:
            cluster.stop()
