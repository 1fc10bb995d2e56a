"""Every wait on the served path has a name (ISSUE 27).

  * the DeviceDispatch span of every leg (agg, group-by, top-N, DISTINCT,
    star-tree, vector) carries lock wait, the three parts of staging,
    ring wait, launch / device wait / copy and the wall-clock stamps, on
    the inline path and on the ring path; a launch that traced a kernel
    says so (`retraceEvents`, `compileMs`)
  * a held `_engine_lock` shows as `lockWaitMs`, not as `stagingMs`
  * `startNs` survives the wire and places the server's tree inside the
    broker's `ServerScatter`; the named phases tile a `ServerRequest`
  * jitted kernels are named by kind + plan fingerprint and scoped
    inside; scopes change no bit of an answer
  * with no trace open nothing of this runs: no span, stamp, annotation
  * the plain leg's `scan_served` / `scan_fallback{reason=}` meters
  * a GROUP BY's span and the server's `/metrics` say which way its sums
    ran (`groupPath`, `group_path{path=}`: ISSUE 28); a scan says nothing
"""
import contextlib
import statistics
import threading
import time
import types

import numpy as np
import pytest

import jax

from pinot_tpu.cluster.mini import MiniCluster
from pinot_tpu.ops import dispatch as dispatch_mod
from pinot_tpu.ops import engine as engine_mod
from pinot_tpu.ops import kernels
from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.executor import QueryExecutor
from pinot_tpu.utils import tracing
from tests.queries.harness import (
    build_segments, synthetic_columns, synthetic_schema,
    synthetic_table_config)
from tests.test_startree_device import segs as _startree_segs  # noqa: F401
from tests.test_vector_device import _build_segs as _build_vector_segs
from tests.test_vector_device import _sql as _vector_sql

NUM_DOCS = 2000

#: what every DeviceDispatch that stayed on the device carries
DISPATCH_ATTRS = (
    "startNs", "lockWaitMs", "stagingMs", "planMs", "blocksMs", "paramsMs",
    "paramPuts", "transferBytes", "submitMs", "queueWaitMs", "batchSize",
    "variant", "launchNs", "launchMs", "readyNs", "deviceWaitMs", "d2hMs",
    "handoffMs", "kernelMs", "fetchMs")
#: the named phases of a server request, (span, attribute), in order
SERVER_PHASES = (
    ("ServerRequest", "parseMs"), ("DeviceDispatch", "lockWaitMs"),
    ("DeviceDispatch", "planMs"), ("DeviceDispatch", "blocksMs"),
    ("DeviceDispatch", "paramsMs"), ("DeviceDispatch", "submitMs"),
    ("DeviceDispatch", "queueWaitMs"), ("DeviceDispatch", "dispatchMs"),
    ("DeviceDispatch", "launchMs"), ("DeviceDispatch", "deviceWaitMs"),
    ("DeviceDispatch", "d2hMs"), ("DeviceDispatch", "handoffMs"),
    ("ServerRequest", "assembleMs"), ("ServerRequest", "serializeMs"))

SCAN_LEGS = {
    "agg": "SELECT SUM(intCol), COUNT(*) FROM testTable "
           "WHERE intCol >= {lit}",
    "groupby": "SELECT groupCol, SUM(intCol) FROM testTable "
               "WHERE intCol >= {lit} GROUP BY groupCol LIMIT 100",
    "topn": "SELECT intCol FROM testTable WHERE intCol >= {lit} "
            "ORDER BY intCol DESC LIMIT 5",
    "distinct": "SELECT DISTINCT groupCol FROM testTable "
                "WHERE intCol >= {lit} LIMIT 100",
}


def _spans(tree, name):
    out = [tree] if tree.get("operator") == name else []
    for c in tree.get("children", ()):
        out += _spans(c, name)
    return out


def _served(tree):
    return [d for d in _spans(tree, "DeviceDispatch")
            if "outcome" not in d]


@pytest.fixture(scope="module")
def scan_segs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("phases")
    return build_segments(
        tmp, synthetic_schema(), synthetic_table_config(),
        [synthetic_columns(NUM_DOCS, seed=31 + i) for i in range(4)])


@pytest.fixture(scope="module")
def vector_segs(tmp_path_factory):
    return _build_vector_segs(tmp_path_factory.mktemp("phases_vec"),
                              "emb", 200, 2)


@pytest.fixture(scope="module")
def legs(scan_segs, vector_segs, _startree_segs):  # noqa: F811
    """leg -> (segments, sql template with a {lit} literal)."""
    out = {leg: (scan_segs, sql) for leg, sql in SCAN_LEGS.items()}
    out["startree"] = (
        _startree_segs[1],
        "SELECT SUM(impressions) FROM st WHERE country = 'c{lit}'")
    rng = np.random.default_rng(5)
    out["vector"] = (vector_segs, None)
    out["vector_sql"] = lambda lit: _vector_sql(
        rng.normal(size=8).astype(np.float32) + lit)
    return out


def _sql(legs, leg, lit):
    segs, template = legs[leg]
    if leg == "vector":
        return segs, legs["vector_sql"](lit)
    if leg == "startree":
        lit = lit % 12
    return segs, template.format(lit=lit)


def _traced(segs, engine, sql):
    resp = QueryExecutor(segs, use_tpu=True, engine=engine).execute(
        "SET trace = true; " + sql)
    assert not resp.exceptions, resp.exceptions
    return resp.trace


def _check_dispatch(d, ring: bool):
    missing = [a for a in DISPATCH_ATTRS if a not in d]
    assert not missing, (missing, d)
    assert d["planMs"] + d["blocksMs"] + d["paramsMs"] == \
        pytest.approx(d["stagingMs"], abs=0.005)
    assert min(d[a] for a in DISPATCH_ATTRS
               if a.endswith("Ms")) >= 0.0, d
    assert d["startNs"] <= d["launchNs"] <= d["readyNs"]
    round_trip = d["launchMs"] + d["deviceWaitMs"] + d["d2hMs"]
    if ring:
        assert d["variant"] != "inline" and d["dispatchMs"] >= 0.0
        # ring: kernelMs is the launch call (+ the wait on the collective
        # path), fetchMs the rest; on the collective path the hand-off to
        # the fetch pool lies between them and is part of d2hMs
        assert d["kernelMs"] + d["fetchMs"] <= round_trip + 0.5
    else:
        assert d["variant"] == "inline" and d["fetchMs"] == 0.0
        assert "dispatchMs" not in d
        assert d["kernelMs"] == pytest.approx(round_trip, abs=0.5)


# -- (a) every attribute, every leg, both paths --------------------------------
@pytest.mark.parametrize(
    "leg", ["agg", "groupby", "topn", "distinct", "startree", "vector"])
def test_inline_dispatch_carries_every_wait(legs, leg):
    engine = TpuOperatorExecutor()
    for lit in (3, 4):
        segs, sql = _sql(legs, leg, lit)
        served = _served(_traced(segs, engine, sql))
        assert served, f"{leg} fell back"
        for d in served:
            _check_dispatch(d, ring=False)
            assert d["mode"] == {"groupby": "agg", "distinct": "agg"}.get(
                leg, leg)
            # 11 groups (DISTINCT groups too) over 2,048 docs: under the
            # one-hot's chunk
            assert d.get("groupPath") == (
                "scatter" if leg in ("groupby", "distinct") else None)


@pytest.mark.parametrize("leg", ["agg", "groupby", "topn", "startree"])
def test_ring_dispatch_carries_every_wait(legs, leg):
    """Four clients at once: launches ride the ring (single or
    coalesced), and each member's own span gets the batch's values."""
    ring = _ring_dispatches(legs, leg)
    assert all(d["batchSize"] >= 1 for d in ring)


def test_the_ring_s_bookkeeping_after_the_copy_is_no_fetch_time(
        legs, monkeypatch):
    """The fetch ends where the copy ends: the busy bookkeeping after it
    (the ring's lock, contended under load) is in neither `kernelMs` nor
    `fetchMs`, so their sum stays within launch + device wait + copy
    however long that lock takes."""
    busy_end = dispatch_mod.KernelDispatcher._busy_end

    def slow_busy_end(self):
        time.sleep(0.002)
        busy_end(self)
    monkeypatch.setattr(dispatch_mod.KernelDispatcher, "_busy_end",
                        slow_busy_end)
    for d in _ring_dispatches(legs, "topn"):
        assert d["kernelMs"] + d["fetchMs"] <= \
            d["launchMs"] + d["deviceWaitMs"] + d["d2hMs"] + 0.005, d


def _ring_dispatches(legs, leg) -> list:
    """The served spans of the first round of four concurrent clients
    that reached the ring, each checked by `_check_dispatch`."""
    engine = TpuOperatorExecutor()
    segs, sql = _sql(legs, leg, 1)
    _traced(segs, engine, sql)  # warm
    ring = []
    for attempt in range(8):
        trees, errors = [], []
        barrier = threading.Barrier(4)

        def client(c, attempt=attempt):
            try:
                barrier.wait(timeout=30)
                for i in range(4):
                    segs, sql = _sql(legs, leg, 10 * attempt + 4 * c + i)
                    trees.append(_traced(segs, engine, sql))
            except BaseException as e:  # noqa: BLE001
                errors.append(e)
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errors, errors
        for tree in trees:
            for d in _served(tree):
                ring_path = d["variant"] != "inline"
                _check_dispatch(d, ring=ring_path)
                ring += [d] if ring_path else []
        if ring:
            break
    assert ring, "four concurrent clients never reached the ring"
    return ring


def test_a_launch_that_traced_a_kernel_says_so(scan_segs):
    engine = TpuOperatorExecutor()
    sql = SCAN_LEGS["agg"].format(lit=7)
    kernels.compiled_kernel.cache_clear()  # a fresh jit: the next call traces
    cold, = _served(_traced(scan_segs, engine, sql))
    assert cold["retraceEvents"] >= 1
    assert cold["compileMs"] == cold["launchMs"]
    warm, = _served(_traced(scan_segs, engine, SCAN_LEGS["agg"].format(lit=8)))
    assert "retraceEvents" not in warm and "compileMs" not in warm


# -- (b) the wait for the engine lock -----------------------------------------
@pytest.mark.parametrize("leg", ["agg", "topn", "startree", "vector"])
def test_held_engine_lock_is_lock_wait_not_staging(legs, leg):
    engine = TpuOperatorExecutor()
    segs, sql = _sql(legs, leg, 2)
    _traced(segs, engine, sql)  # warm: blocks resident, kernel compiled
    held, release = threading.Event(), threading.Event()

    def holder():
        with engine._engine_lock:
            held.set()
            release.wait(10)
    t = threading.Thread(target=holder)
    t.start()
    assert held.wait(10)
    timer = threading.Timer(0.05, release.set)
    timer.start()
    try:
        segs, sql = _sql(legs, leg, 3)
        d = _served(_traced(segs, engine, sql))[0]
    finally:
        release.set()
        t.join(10)
    assert d["lockWaitMs"] >= 40.0, d
    assert d["stagingMs"] < 40.0, d
    assert d["durationMs"] >= d["lockWaitMs"]


# -- (c) spans on the wall clock ----------------------------------------------
def test_start_ns_survives_the_wire_and_the_graft():
    before = time.time_ns()
    with tracing.RequestTrace(operator="ServerRequest") as remote:
        with tracing.Scope("Inner"):
            h = tracing.capture()
            h.child("Leaf").end()
    shipped = remote.to_dict()
    assert before <= shipped["startNs"] <= time.time_ns()
    again = tracing.TraceNode.from_dict(shipped)
    assert again.start_ns == shipped["startNs"]
    assert "startNs" not in again.attrs
    assert again.to_dict() == shipped
    with tracing.RequestTrace() as local:
        sp = local.handle().child("ServerScatter")
        sp.graft(shipped)
        sp.end()
    grafted, = _spans(local.to_dict(), "ServerRequest")
    assert grafted == shipped
    assert tracing.TraceNode("never opened").to_dict().get("startNs") is None


def _no_child_before_its_parent(node):
    for c in node.get("children", ()):
        assert c["startNs"] >= node["startNs"], (node["operator"],
                                                 c["operator"])
        _no_child_before_its_parent(c)


@pytest.fixture(scope="module")
def cluster(scan_segs):
    c = MiniCluster(num_servers=1, use_tpu=True)
    c.start()
    c.add_table("testTable")
    for seg in scan_segs:
        c.add_segment("testTable", seg, server_idx=0)
    yield c
    c.stop()


def _cluster_trace(cluster, leg, lit):
    resp = cluster.query("SET trace = true; " + SCAN_LEGS[leg].format(lit=lit)
                         + " OPTION(skipCache=true)")
    assert not resp.exceptions, resp.exceptions
    return resp.trace


def test_server_tree_lies_inside_the_brokers_scatter(cluster):
    sent = time.time_ns()
    tree = _cluster_trace(cluster, "agg", 11)
    done = time.time_ns()
    assert tree["operator"] == "BrokerRequest"
    assert sent <= tree["startNs"] <= done
    _no_child_before_its_parent(tree)
    scatter, = _spans(tree, "ServerScatter")
    request, = _spans(scatter, "ServerRequest")
    dispatch, = _served(request)
    slack = 200_000  # two clocks a span: perf_counter times, time_ns places

    def end(span):
        return span["startNs"] + int(span["durationMs"] * 1e6)
    assert scatter["startNs"] <= request["startNs"]
    assert end(request) <= end(scatter) + slack
    assert request["startNs"] <= dispatch["startNs"] <= dispatch["launchNs"]
    assert dispatch["readyNs"] <= end(dispatch) + slack
    assert end(dispatch) <= end(request) + slack
    # the scheduler's wait lies before the span's opening, after the scatter's
    assert request["startNs"] - int(request["queueWaitMs"] * 1e6) \
        >= scatter["startNs"] - slack


# -- (d) the named phases tile a server request -------------------------------
@pytest.mark.parametrize("leg", ["agg", "groupby", "topn"])
def test_named_phases_tile_the_server_request(cluster, leg):
    _cluster_trace(cluster, leg, 20)  # warm
    holes, durations = [], []
    for lit in range(21, 36):
        request, = _spans(_cluster_trace(cluster, leg, lit), "ServerRequest")
        named = sum(s.get(attr, 0.0) for name, attr in SERVER_PHASES
                    for s in _spans(request, name))
        assert {"parseMs", "assembleMs", "serializeMs"} <= set(request)
        holes.append(request["durationMs"] - named)
        durations.append(request["durationMs"])
    assert min(holes) > -0.05, "phases overlap"
    assert statistics.median(holes) <= \
        0.10 * statistics.median(durations) + 0.5, (holes, durations)


# -- (e) kernels named in the device trace ------------------------------------
def _staged(engine, segs, sql):
    ctx = QueryContext.from_sql(sql)
    with engine._engine_lock:
        if ctx.aggregations:
            plan, _slots = engine._plan(segs, ctx)
        else:
            plan = engine._plan_topn(segs, ctx)
        cols, params, _S, _s, D, G = engine._stage(segs, ctx, plan)
    # num_docs rides the packed parameters: the kernels take None for it
    return plan, cols, params, None, D, G


def test_jitted_kernels_are_named_by_kind_and_fingerprint(scan_segs):
    engine = TpuOperatorExecutor()
    p1 = _staged(engine, scan_segs, SCAN_LEGS["agg"].format(lit=1))[0]
    p2 = _staged(engine, scan_segs, SCAN_LEGS["groupby"].format(lit=1))[0]
    p3 = _staged(engine, scan_segs, SCAN_LEGS["topn"].format(lit=1))[0]
    fp1, fp2, fp3 = (kernels.plan_fingerprint(p) for p in (p1, p2, p3))
    assert len({fp1, fp2}) == 2 and len(fp1) == 12
    assert kernels.compiled_kernel(p1).__name__ == f"agg_{fp1}"
    assert kernels.compiled_kernel(p2).__name__ == f"agg_{fp2}"
    assert kernels.compiled_topn_kernel(p3).__name__ == f"topn_{fp3}"
    assert kernels.compiled_batched_kernel(p1, 4).__name__ == \
        f"batched_b4_{fp1}"
    assert kernels.compiled_batched_kernel(p1, 2, True).__name__ == \
        f"batched_b2_stacked_{fp1}"
    assert kernels.compiled_batched_dedup_kernel(p1, 4, 2).__name__ == \
        f"batched_b4_dedup2_{fp1}"
    assert kernels.compiled_batched_topn_kernel(p3, 2).__name__ == \
        f"topn_batched_b2_{fp3}"
    assert kernels.compiled_row_assembler(
        4, 2048, (2048,) * 4, "<i4").__name__ == "assemble_s4"
    # the name the trace log and kernel_retrace_by_plan use is inside it
    kernels.compiled_kernel.cache_clear()
    plan, cols, params, num_docs, D, G = _staged(
        engine, scan_segs, SCAN_LEGS["agg"].format(lit=2))
    kernels.compiled_kernel(plan)(cols, params, num_docs, D=D, G=G)
    last = kernels.trace_log(1)[0]
    assert f"{last['kind']}_{last['plan']}" == f"agg_{fp1}"


def test_lowered_kernel_holds_the_scopes(scan_segs):
    engine = TpuOperatorExecutor()
    plan, cols, params, num_docs, D, G = _staged(
        engine, scan_segs, SCAN_LEGS["groupby"].format(lit=5))
    kernel = kernels.compiled_kernel(plan)
    lowered = kernel.lower(cols, params, num_docs, D=D, G=G)
    text = lowered.as_text(debug_info=True)
    for scope in ("filter", "group_keys", "reduce:", "pack"):
        assert scope in text, scope
    hlo = lowered.compile().as_text()
    assert hlo.startswith(f"HloModule jit_{kernel.__name__}")
    assert f"jit({kernel.__name__})/filter/" in hlo
    assert f"jit({kernel.__name__})/reduce:" in hlo


@pytest.mark.parametrize("leg", ["agg", "groupby", "topn"])
def test_scopes_change_no_bit_of_an_answer(scan_segs, leg, monkeypatch):
    engine = TpuOperatorExecutor()
    plan, cols, params, num_docs, D, G = _staged(
        engine, scan_segs, SCAN_LEGS[leg].format(lit=250))
    make = kernels.make_topn_kernel if leg == "topn" else kernels.make_kernel
    kw = {"D": D} if leg == "topn" else {"D": D, "G": G}
    scoped = np.asarray(jax.jit(make(plan), static_argnames=tuple(kw))(
        cols, params, num_docs, **kw))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = np.asarray(jax.jit(make(plan), static_argnames=tuple(kw))(
        cols, params, num_docs, **kw))
    assert scoped.dtype == plain.dtype
    assert scoped.tobytes() == plain.tobytes()


# -- (f) no trace open: nothing of this runs ----------------------------------
@pytest.mark.parametrize("leg,clients", [("agg", 1), ("topn", 1), ("agg", 4)])
def test_untraced_query_makes_no_span_stamp_or_annotation(
        legs, leg, clients, monkeypatch):
    """What a server with pinot.trace.enabled=false runs: no
    RequestTrace is open, so the engine and the ring find no span and
    must read no wall clock, build no node and annotate nothing."""
    calls = []

    def counted(label, fn):
        def wrapper(*a, **k):
            calls.append(label)
            return fn(*a, **k)
        return wrapper
    for mod in (dispatch_mod, engine_mod, tracing):
        clock = types.SimpleNamespace(**{
            n: getattr(time, n) for n in dir(time) if not n.startswith("_")})
        clock.time_ns = counted(f"{mod.__name__}.time_ns", time.time_ns)
        monkeypatch.setattr(mod, "time", clock)
    trace_annotation = jax.profiler.TraceAnnotation

    def annotation(name, **kw):
        if name != "pinot:gc":  # the collector's pauses, not the query's
            calls.append("TraceAnnotation")
        return trace_annotation(name, **kw)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    monkeypatch.setattr(tracing.TraceNode, "__init__", counted(
        "TraceNode", tracing.TraceNode.__init__))
    engine = TpuOperatorExecutor()
    errors = []

    def client(c):
        try:
            for i in range(3):
                segs, sql = _sql(legs, leg, 50 + 3 * c + i)
                resp = QueryExecutor(segs, use_tpu=True,
                                     engine=engine).execute(sql)
                assert not resp.exceptions and resp.trace is None
        except BaseException as e:  # noqa: BLE001
            errors.append(e)
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errors, errors
    assert calls == []
    # and the same query traced does all three
    segs, sql = _sql(legs, leg, 77)
    _traced(segs, engine, sql)
    assert {"TraceAnnotation", "TraceNode",
            "pinot_tpu.ops.dispatch.time_ns",
            "pinot_tpu.utils.tracing.time_ns"} <= set(calls)


def test_phase_annotations_are_tagged_with_the_trace_id(scan_segs,
                                                        monkeypatch):
    seen = []

    class Recorded(contextlib.nullcontext):
        def __init__(self, name, **kw):
            super().__init__()
            seen.append((name, kw))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorded)
    tree = _traced(scan_segs, TpuOperatorExecutor(),
                   SCAN_LEGS["agg"].format(lit=9))
    seen = [(name, kw) for name, kw in seen if name != "pinot:gc"]
    assert [name for name, _kw in seen] == [
        "pinot:lock_wait", "pinot:staging", "pinot:launch",
        "pinot:device_wait", "pinot:d2h"]
    assert {kw["trace_id"] for _name, kw in seen} == {tree["traceId"]}


# -- the plain leg's routing in /metrics (ROADMAP C9) -------------------------
def _meter(engine, name, reason=None):
    labels = dict(engine._labels)
    if reason is not None:
        labels["reason"] = reason
    return engine._metrics.meter(name, labels=labels)


@pytest.mark.parametrize("reason", ["served", "unsupported", "plan",
                                    "staging"])
def test_scan_leg_meters_served_and_fallback(scan_segs, reason,
                                             monkeypatch):
    engine = TpuOperatorExecutor(metrics_labels={"scan_test": reason})
    sql = SCAN_LEGS["agg"].format(lit=100)
    if reason == "unsupported":
        sql = "SELECT intCol FROM testTable ORDER BY intCol, longCol LIMIT 3"
    elif reason == "plan":
        monkeypatch.setattr(engine, "_plan", lambda segs, ctx: None)
    elif reason == "staging":
        def refuse(*a, **k):
            raise engine_mod._NotStageable()
        monkeypatch.setattr(engine, "_stage", refuse)
    want = QueryExecutor(scan_segs, use_tpu=False).execute(sql)
    got = QueryExecutor(scan_segs, use_tpu=True, engine=engine).execute(sql)
    assert not got.exceptions and got.result_table.rows == \
        want.result_table.rows
    served = _meter(engine, "scan_served")
    fallen = {r: _meter(engine, "scan_fallback", r)
              for r in ("unsupported", "plan", "staging")}
    if reason == "served":
        assert served == 1 and not any(fallen.values())
    else:
        assert served == 0
        assert fallen == {r: float(r == reason) for r in fallen}


# -- which way a GROUP BY's sums ran (ISSUE 28) --------------------------------
def test_served_group_by_names_its_path_and_a_scan_does_not(cluster):
    from pinot_tpu.utils.metrics import get_registry
    page = get_registry("server").prometheus_text

    def scatters():
        return sum(float(line.rsplit(" ", 1)[1])
                   for line in page().splitlines()
                   if line.startswith("pinot_tpu_server_group_path{")
                   and 'path="scatter"' in line)
    before = scatters()
    scan, = _served(_cluster_trace(cluster, "agg", 41))
    assert "groupPath" not in scan and scatters() == before
    grouped, = _served(_cluster_trace(cluster, "groupby", 41))
    dims = {k: grouped[k] for k in ("G", "D", "groupPath")}
    assert dims["groupPath"] == kernels.group_path(
        11, dims["D"], kernels._value_dtype(), finite=True) == "scatter"
    assert scatters() == before + 1
    assert "# HELP pinot_tpu_server_group_path " in page()
    # ISSUE 35: where the per-segment partials became one result, what was
    # fetched, and the decode: inside assembleMs, so the phases tile as ever
    assert grouped["groupFold"] == "device" and grouped["groupKeySpace"] == 11
    assert 0 < grouped["groupResultBytes"] <= 11 * 8 * 8
    assert 0 < grouped["groupsPresent"] <= 11
    request, = _spans(_cluster_trace(cluster, "groupby", 42), "ServerRequest")
    served, = _served(request)
    assert 0 <= served["groupDecodeMs"] <= request["assembleMs"]
    assert 'pinot_tpu_server_group_fold{' in page() \
        and "# HELP pinot_tpu_server_group_result_bytes " in page()


# -- the same phases on the profiler's clock ----------------------------------
def annotation_offsets_ns(segs, profile_dir, queries: int = 12) -> dict:
    """Run traced queries under a jax profile (host_tracer_level 1, as the
    benchmark's launcher sets it) and pair each `pinot:*` event of the
    xplane with its span's stamp by trace id: {phase: [stamp - event start]}.
    The xplane counts from the profile's start, so each difference is that
    start on the epoch clock, and their spread is how well the two clocks
    agree. Also run on the chip, once, for PERF.md (ISSUE 27)."""
    import glob

    from jax.profiler import ProfileData
    engine = TpuOperatorExecutor()
    sql = SCAN_LEGS["agg"]
    _traced(segs, engine, sql.format(lit=1))  # compile outside the profile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(profile_dir), profiler_options=opts)
    try:
        trees = [_traced(segs, engine, sql.format(lit=100 + i))
                 for i in range(queries)]
    finally:
        jax.profiler.stop_trace()
    stamps = {}
    for tree in trees:
        d, = _served(tree)
        stamps[tree["traceId"]] = {
            "pinot:lock_wait": d["startNs"], "pinot:launch": d["launchNs"],
            "pinot:staging": d["startNs"] + int(d["lockWaitMs"] * 1e6)}
    found, = glob.glob(str(profile_dir / "**" / "*.xplane.pb"),
                       recursive=True)
    offsets = {}
    for plane in ProfileData.from_file(found).planes:
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith("pinot:") or ev.name == "pinot:gc":
                    continue
                trace_id = {k: v for k, v in ev.stats}.get("trace_id")
                offsets.setdefault(ev.name, [])
                if ev.name in stamps.get(trace_id, ()):
                    offsets[ev.name].append(
                        stamps[trace_id][ev.name] - int(ev.start_ns))
    return offsets


def test_profile_holds_the_phases_on_the_spans_clock(scan_segs, tmp_path):
    offsets = annotation_offsets_ns(scan_segs, tmp_path)
    assert set(offsets) == {"pinot:lock_wait", "pinot:staging",
                            "pinot:launch", "pinot:device_wait", "pinot:d2h"}
    every = [o for phase in ("pinot:lock_wait", "pinot:staging",
                             "pinot:launch") for o in offsets[phase]]
    assert len(every) == 3 * 12
    # one profile start for all of them: the events and the spans' stamps
    # are the same clock (0.2 ms on the chip; a shared CPU box gets 2)
    assert max(every) - min(every) < 2_000_000, offsets
