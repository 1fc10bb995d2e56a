"""TSBS devops cpu-only, `double-groupby-1` (ISSUE 35), at a size a test
run can hold, in two layouts of the same 72 hours x 4000 hosts. `in time
order`: the configuration's OWN columns (benchmark/datagen, seeded), 16
segments of unequal docs, each the next 4.5 hours of the fleet's
readings, so a 12-hour window prunes all but 3 or 4 of them and an hour
that a segment boundary splits has its groups in two segments.
`shuffled`: every segment holds every hour (uniform draws), so nothing
is pruned and the key space is the whole 288,000, the XLA scatter.
Segments built with SegmentCreator, served by `ServerQueryExecutor` over
`_shared_engine()` holding one device, as the cell's server holds one
chip, in f32 as the chip runs it. The cell's one template at several
windows has to give the rows `benchmark/reference.py` gives, exactly
(keys, order, COUNT, SUM), through ONE GroupByResult a batch: the
per-segment partials are folded on the device over a global key space
(`kernels.fold_groups`), through a remap that one segment's shorter
dictionary exercises, and a count past 2^24 stays exact."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import datagen  # noqa: E402  (benchmark/)
import reference  # noqa: E402  (benchmark/)
import traffic  # noqa: E402  (benchmark/)

from pinot_tpu.ops import kernels  # noqa: E402
from pinot_tpu.ops.plan_ir import DevicePlan  # noqa: E402
from pinot_tpu.query.context import QueryContext  # noqa: E402
from pinot_tpu.query.reduce import reduce_results  # noqa: E402
from pinot_tpu.query.results import GroupByResult  # noqa: E402
from pinot_tpu.segment.creator import SegmentCreator  # noqa: E402
from pinot_tpu.segment.loader import load_segment  # noqa: E402
from pinot_tpu.utils.config import PinotConfiguration  # noqa: E402

SEED = 2_600_000_034  # past 2**31, as the driver's are
#: segments of unequal docs, none a power of two
DOCS = (60000, 17001, 25500, 9000)
#: hosts the second segment never saw: its dictionary is shorter
MISSING = {f"host_{i}" for i in range(0, 4000, 7)}
#: layout: (segments, what the spans have to read)
LAYOUTS = {
    # up to 5 hours a segment (fewer where a short segment's ticks are
    # far apart) x 4000 hosts; the fold's key space is the batch's union
    # in pow2 digits: up to 14 hours of 3 segments -> 16, 18-19 of 4 -> 32
    "in_time_order": (16, {"groupPath": "onehot2", "groupKeySpace": None,
                           "segments": (3, 4),
                           "folded": (16 * 4096, 32 * 4096)}),
    "shuffled": (4, {"groupPath": "scatter", "groupKeySpace": 72 * 4000,
                     "segments": (4,), "folded": (128 * 4096,)}),
}


def shuffled_columns(config, seed: int, segment: int, docs: int) -> dict:
    """The configuration's columns with host, hour and value drawn
    uniformly and independently: {column: (values, codes, domain)}."""
    rng = np.random.default_rng([seed, segment, 1])
    made = {}
    for name, domain in datagen.domains(config).items():
        domain = np.asarray(domain)
        codes = rng.integers(0, len(domain), docs, dtype=np.int32)
        made[name] = (domain[codes], codes, domain)
    return made


@pytest.fixture(scope="module")
def cell():
    _bench, cell, config, mix = traffic.load_cell(ROOT, "tsbs_dgb1_c1")
    assert cell["config"] == "tsbs_cpu_104m_1chip" and cell["chips"] == 1
    return config, mix


@pytest.fixture(scope="module", params=list(LAYOUTS))
def layout(request):
    return request.param


@pytest.fixture(scope="module")
def table(cell, layout, tmp_path_factory):
    """(loaded segments, the plain reference of the same rows)."""
    config, _mix = cell
    tmp = tmp_path_factory.mktemp("tsbs")
    tc, schema = datagen.table_and_schema(config)
    ref = reference.Reference(config, datagen.domains(config))
    segs = []
    make = datagen.make_columns if layout == "in_time_order" \
        else shuffled_columns
    for i in range(LAYOUTS[layout][0]):
        made = make(config, SEED, i, DOCS[i % len(DOCS)])
        if i == 1:
            keep = ~np.isin(made["hostname"][0], sorted(MISSING))
            made = {name: (values[keep], codes[keep], domain)
                    for name, (values, codes, domain) in made.items()}
        ref.add(reference.segment_share(config, made))
        name = f"{config['table']}_{i}"
        SegmentCreator(tc, schema).build(
            {k: v[0] for k, v in made.items()}, str(tmp / name), name)
        segs.append(load_segment(str(tmp / name)))
    assert segs[1].metadata.columns["hostname"].cardinality < 4000
    assert max(s.metadata.columns["hostname"].cardinality
               for s in segs) == 4000
    return segs, ref


@pytest.fixture(scope="module")
def served(cell, table):
    """The server's executor; `_shared_engine()` finds one device."""
    from pinot_tpu.server.data_manager import InstanceDataManager
    from pinot_tpu.server.query_server import ServerQueryExecutor
    config, _mix = cell
    dm = InstanceDataManager("server_0")
    ex = ServerQueryExecutor(dm, use_tpu=True, config=PinotConfiguration())
    one, everything = jax.devices()[:1], jax.devices
    jax.devices = lambda *a: one
    try:
        engine = ex._shared_engine()
    finally:
        jax.devices = everything
    for seg in table[0]:
        dm.table(config["table"] + "_OFFLINE").add_segment(seg)
    # the registry outlives the fixture: what the meters read before it
    engine.folds_before = {w: fold_meter(engine, w)
                           for w in ("device", "host")}
    yield ex, engine
    dm.shutdown()
    ex.segment_cache.close()
    ex.fingerprint_log.close()


def fold_meter(engine, where: str) -> float:
    return engine._metrics.meter(
        "group_fold", labels=dict(engine._labels or {}, where=where))


def spans(tree, name: str) -> list:
    out = [tree] if tree.get("operator") == name else []
    for c in tree.get("children", ()):
        out += spans(c, name)
    return out


def ask(ex, config, sql: str):
    """(broker rows, server results, DeviceDispatch spans) of one query
    through the server's executor and the broker's reduce, in f32."""
    from pinot_tpu.server.datatable import deserialize_results_ex
    with jax.enable_x64(False):
        payload = ex.execute(config["table"] + "_OFFLINE", sql, trace_ctx={
            "traceId": "tsbs", "spanId": "1", "sampled": True})
    results, exceptions, _stats, trace = deserialize_results_ex(payload)
    assert not exceptions
    resp = reduce_results(QueryContext.from_sql(sql), results)
    return resp, results, spans(trace, "DeviceDispatch")


def test_the_template_answers_as_the_reference_does(cell, layout, table,
                                                    served):
    config, mix = cell
    segs, ref = table
    ex, engine = served
    reads = LAYOUTS[layout][1]
    split = 0
    assert len(engine.devices) == 1 and engine._mesh is None
    assert [t["name"] for t in mix["templates"]] == ["dgb1_usage_user"]
    queries = traffic.make_queries(mix, config["table"], SEED, 1, 8, False)
    assert len({q[1]["w"][0] for q in queries}) > 5  # several windows
    for t, literals, sql in queries:
        template = mix["templates"][t]
        resp, results, dispatches = ask(ex, config, sql)
        want = ref.answer(template, literals)
        got = [list(r) for r in resp.result_table.rows]
        assert 6000 < len(want) <= 48000
        assert len(got) == len(want)
        for g, w in zip(got, want):
            # SUM, COUNT, ts_hour, hostname: exact, in the reference's order
            assert (float(g[0]), int(g[1]), int(g[2]), g[3]) \
                == (float(w[0]), w[1], w[2], w[3]), (template["name"], g, w)
        assert not resp.num_groups_limit_reached
        # one result for the batch, not one a segment
        assert len(results) == 1 and isinstance(results[0], GroupByResult)
        # the window's segments, the others pruned by ts_hour's min / max
        lo, hi = literals["w"]
        touched = [s for s in segs
                   if s.metadata.columns["ts_hour"].max_value >= lo
                   and s.metadata.columns["ts_hour"].min_value <= hi]
        assert len(touched) in reads["segments"]
        assert results[0].stats.num_segments_processed == len(touched)
        assert results[0].stats.num_docs_scanned \
            == sum(int(r[1]) for r in got)
        # an hour two of the window's segments share: its groups merge
        in_time = sorted(
            touched, key=lambda s: s.metadata.columns["ts_hour"].min_value)
        split += sum(
            1 for a, b in zip(in_time, in_time[1:])
            if a.metadata.columns["ts_hour"].max_value
            == b.metadata.columns["ts_hour"].min_value)
        span, = dispatches
        assert "outcome" not in span, "fell back to the host"
        assert span["groupPath"] == reads["groupPath"]
        assert span["groupKeySpace"] == (reads["groupKeySpace"] or 4000 * max(
            s.metadata.columns["ts_hour"].cardinality for s in touched))
        assert span["groupFold"] == "device"
        assert span["groupsPresent"] == len(want)
        n_slots = 2  # SUM, COUNT
        assert span["groupResultBytes"] in [
            g * n_slots * 4 for g in reads["folded"]]
        assert span["groupDecodeMs"] >= 0
    if layout == "in_time_order":
        assert split >= 2  # short segments stop short of their last hour


def block_meter(form: str) -> float:
    from pinot_tpu.utils.metrics import get_registry
    return get_registry("server").meter("group_block", labels={"form": form})


def test_the_answer_crosses_to_the_broker_as_columns(cell, layout, table,
                                                     served):
    """ISSUE 36: the template's answer through a broker and the framed
    TCP transport, over the server's warm engine: its payload writes
    arrays and dictionaries with ids and not one tagged-list column, the
    two ends of the wire are on the traced spans, and the rows are the
    reference's."""
    from pinot_tpu.cluster.mini import MiniCluster
    config, mix = cell
    segs, ref = table
    _ex, engine = served
    t, literals, sql = traffic.make_queries(
        mix, config["table"], SEED, 1, 2, False)[1]
    c = MiniCluster(num_servers=1, use_tpu=True)
    c.servers[0].executor._engine = engine  # one device, kernels compiled
    c.start()
    try:
        c.add_table(config["table"])
        for seg in segs:
            c.add_segment(config["table"], seg, server_idx=0)
        before = {f: block_meter(f) for f in ("array", "coded", "list")}
        resp = c.query("SET trace = true; " + sql)
    finally:
        c.servers[0].executor._engine = None  # the fixture's to close
        c.stop()
    assert not resp.exceptions, resp.exceptions
    want = ref.answer(mix["templates"][t], literals)
    assert [[float(r[0]), int(r[1]), int(r[2]), r[3]]
            for r in resp.result_table.rows] \
        == [[float(w[0]), w[1], w[2], w[3]] for w in want]
    # ts_hour + ids, hostname once + ids; SUM as f64, COUNT as i64
    assert block_meter("coded") - before["coded"] == 2
    assert block_meter("array") - before["array"] == 2
    assert block_meter("list") - before["list"] == 0
    scatter, = spans(resp.trace, "ServerScatter")
    request, = spans(scatter, "ServerRequest")
    span, = spans(request, "DeviceDispatch")
    assert span["groupFold"] == "device"
    # ISSUE 38: the broker finishes the one folded result as columns
    reduced, = spans(resp.trace, "BrokerReduce")
    assert (reduced["reducePath"], reduced["reduceRows"]) \
        == ("columns", len(want))
    assert span["groupsPresent"] == len(want)
    # 4 B an id and 8 B a value a column, a group; the host names once
    assert 24 * len(want) < request["serializeBytes"] \
        < 24 * len(want) + 80_000
    assert 0 <= request["serializeMs"] < request["durationMs"]
    assert 0 <= scatter["deserializeMs"] \
        <= scatter["durationMs"] - request["durationMs"]


def test_the_fold_is_metered(served):
    _ex, engine = served
    labels = dict(engine._labels or {})
    meter = engine._metrics.meter
    before = engine.folds_before
    assert fold_meter(engine, "device") - before["device"] >= 8
    assert fold_meter(engine, "host") - before["host"] == 0
    assert meter("group_result_bytes", labels=labels) >= 8 * 6000 * 8


def test_a_filter_on_a_missing_host_finds_the_other_segments(cell, table,
                                                             served):
    """host_0 is in every dictionary but the second segment's: the remap
    sends that segment's partials nowhere near it."""
    config, _mix = cell
    _segs, ref = table
    ex, _engine = served
    sql = ("SELECT SUM(usage_user), COUNT(*), ts_hour, hostname FROM "
           f"{config['table']} WHERE hostname = 'host_0' GROUP BY ts_hour, "
           "hostname ORDER BY ts_hour, hostname LIMIT 48000 "
           "OPTION(skipCache=true)")
    resp, results, _d = ask(ex, config, sql)
    k = ref.axes.index("hostname")
    host = int(np.flatnonzero(ref.domains[k] == "host_0")[0])
    want = [[int(ref.sums["usage_user"][h, host]), int(ref.count[h, host]),
             int(ref.domains[0][h]), "host_0"]
            for h in range(72) if ref.count[h, host]]
    got = [[int(r[0]), int(r[1]), int(r[2]), r[3]]
           for r in resp.result_table.rows]
    assert got == want and len(results) == 1


def test_counts_fold_as_integers_past_2_to_the_24():
    """Three segments each count 2^24 - 1 rows in one group: the f32 sum
    of the three is not representable, the fold's integer sum is."""
    plan = DevicePlan(
        filter_ir=None, leaves=(), value_irs=(("col", "m"),),
        agg_ops=(("sum", 0, None), ("count", None, None)),
        group_cols=("a", "b"), group_strides=(3, 1), num_groups=6,
        group_fold=(2, 3))
    big = (1 << 24) - 1
    with jax.enable_x64(False):
        counts = np.zeros((4, 6), np.float32)
        counts[:3, 4] = big
        counts[3, 1] = 5
        sums = counts * 2
        # every dictionary holds every value: the remap is the identity,
        # but for segment 2, which lacks column a's first value
        ginv0 = np.array([[0, 1]] * 4, np.int32)
        ginv1 = np.array([[0, 1, 2]] * 4, np.int32)
        ginv0[2] = [-1, 0]  # its id 0 is the union's second value
        counts[2] = 0
        counts[2, 1] = big   # local key (a id 0, b id 1) -> global 4
        sums[2] = counts[2] * 2
        row = np.asarray(kernels.fold_groups(
            plan, [("sum", jnp.asarray(sums)), ("count", jnp.asarray(counts))],
            {"ginv0": jnp.asarray(ginv0), "ginv1": jnp.asarray(ginv1)}))
    assert row.dtype == np.int32 and row.shape == (6 * 2 + 4,)
    table = row[:12].reshape(2, 6)  # a slot after the other
    assert table[1, 4] == 3 * big and float(np.float32(3 * big)) != 3 * big
    assert table[1, 1] == 5
    assert table[1].sum() == 3 * big + 5
    assert row[12:].tolist() == [big, big, big, 5]  # matched a segment
    sums_back = table[0].copy().view(np.float32)
    assert sums_back[1] == 10.0 and sums_back[0] == 0.0


def test_the_fold_s_caps_are_the_engine_s_own_and_hold_at_their_edge():
    from pinot_tpu.ops import engine as eng
    max_g = eng.MAX_DEVICE_GROUPS
    max_b = eng.TpuOperatorExecutor.GMAP_MAX_BYTES
    plan = DevicePlan(
        filter_ir=None, leaves=(), value_irs=(("col", "m"),),
        agg_ops=(("sum", 0, None), ("count", None, None)),
        group_cols=("a",), group_strides=(1,), num_groups=6)
    assert kernels.group_fold(plan, max_g, max_b, max_g, max_b) == "device"
    assert kernels.group_fold(plan, max_g + 1, 0, max_g, max_b) == "host"
    assert kernels.group_fold(plan, 0, max_b + 1, max_g, max_b) == "host"
    import dataclasses
    # a fused time bucket folds as any key does, under the same caps
    fused = dataclasses.replace(plan, tbucket=("ts", 8))
    assert kernels.group_fold(fused, max_g, max_b, max_g, max_b) == "device"
    assert kernels.group_fold(fused, max_g + 1, 0, max_g, max_b) == "host"


def test_a_remap_past_the_cap_keeps_the_per_segment_route(cell, table,
                                                          served, layout):
    """The same answers, a result a segment, the word on the span, and
    the remap that was turned down is not factorized again."""
    config, mix = cell
    _segs, ref = table
    ex, engine = served
    t, literals, sql = traffic.make_queries(
        mix, config["table"], SEED, 1, 3, False)[2]
    want = ref.answer(mix["templates"][t], literals)
    engine._gmap_cache.clear()
    engine.GMAP_MAX_BYTES = 1 << 10  # under any remap of 4000 hosts
    calls = []
    factorize = engine._factorize_groups
    engine._factorize_groups = lambda *a: calls.append(1) or factorize(*a)
    try:
        for _ in range(2):
            resp, results, (span,) = ask(ex, config, sql)
            assert span["groupFold"] == "host"
            assert len(results) == LAYOUTS[layout][1]["segments"][-1] \
                or len(results) in LAYOUTS[layout][1]["segments"]
            got = [[int(r[0]), int(r[1]), int(r[2]), r[3]]
                   for r in resp.result_table.rows]
            assert got == want
        assert len(calls) == 1
    finally:
        del engine.GMAP_MAX_BYTES, engine._factorize_groups
        engine._gmap_cache.clear()
    _resp, results, (span,) = ask(ex, config, sql)
    assert span["groupFold"] == "device" and len(results) == 1


@pytest.mark.parametrize("S", [1, 3, 13, 16, 64])
def test_sums_fold_with_their_roundings_carried(S):
    """f32 partials of a segment each: the fold's sum over the segment
    axis is the exact sum rounded once, as the host's f64 fold gave,
    where a plain f32 sum loses a rounding an addition."""
    rng = np.random.default_rng(S)
    x = (rng.integers(1, 1 << 30, (S, 4096)).astype(np.float64)
         * rng.choice([1.0, 1e-3, 1e3], (S, 1))).astype(np.float32)
    exact = x.astype(np.float64).sum(axis=0)
    with jax.enable_x64(False):
        got = np.asarray(jax.jit(kernels._sum_segments)(jnp.asarray(x)))
        plain = np.asarray(jnp.sum(jnp.asarray(x), axis=0))
    assert got.dtype == np.float32
    ulp = np.spacing(exact.astype(np.float32)).astype(np.float64)
    assert (np.abs(got - exact) <= 0.5 * ulp * (1 + 1e-6)).all()
    if S >= 13:
        assert (np.abs(plain - exact) > 0.5 * ulp).any()


def test_a_non_finite_partial_folds_as_the_plain_sum_does():
    x = np.ones((4, 3), np.float32)
    x[1, 0], x[2, 1], x[3, 1] = np.inf, np.inf, -np.inf
    with jax.enable_x64(False):
        got = np.asarray(kernels._sum_segments(jnp.asarray(x)))
    assert got[0] == np.inf and np.isnan(got[1]) and got[2] == 4.0


# -- the broker's reduce at 48,000 rows (ISSUE 35: repaired because a traced
# run named it as more than a fifth of the query) ---------------------------
REDUCE_SQLS = [
    # the cell's template: keys and aggregates picked by position
    "SELECT SUM(m), COUNT(*), a, b FROM t GROUP BY a, b ORDER BY a, b LIMIT 50",
    "SELECT a, b, SUM(m) AS s FROM t GROUP BY a, b ORDER BY s DESC, b, a DESC "
    "LIMIT 40",
    # an alias that shadows a group column, for ORDER BY only
    "SELECT COUNT(*) AS a, b FROM t GROUP BY a, b ORDER BY a DESC, b LIMIT 60",
    # computed: the bindings' path
    "SELECT a, SUM(m) / COUNT(*) FROM t GROUP BY a, b ORDER BY SUM(m) + 1 DESC "
    "LIMIT 30",
    "SELECT a, b, SUM(m) FROM t GROUP BY a, b HAVING SUM(m) > 40 "
    "ORDER BY SUM(m), a, b LIMIT 30",
]


@pytest.mark.parametrize("sql", REDUCE_SQLS)
def test_the_reduce_picks_by_position_what_the_bindings_would_give(sql):
    from pinot_tpu.query import reduce as reduce_mod
    from pinot_tpu.query.results import ExecutionStats
    ctx = QueryContext.from_sql(sql)
    rng = np.random.default_rng(7)
    results = []
    for _ in range(3):
        groups = {}
        for _g in range(120):
            key = (int(rng.integers(0, 9)), f"h{int(rng.integers(0, 12))}")
            groups[key] = [fn.from_device_slots(
                {op: float(rng.integers(1, 50)) for op in ("sum", "count")})
                for fn in ctx.agg_functions]
        results.append(GroupByResult(groups, ExecutionStats()))
    fast = reduce_results(ctx, results).result_table.rows
    direct, by_keys = reduce_mod._direct_columns, reduce_mod._sorted_by_keys
    reduce_mod._direct_columns = lambda ctx: None

    def comparator_only(rows, ascs):  # a None in every key: the fallback
        boxed = [((None,) + tuple(k), r) for k, r in rows]
        return [(k[1:], r) for k, r in by_keys(boxed, [True] + list(ascs))]
    reduce_mod._sorted_by_keys = comparator_only
    try:
        slow = reduce_results(ctx, results).result_table.rows
    finally:
        reduce_mod._direct_columns, reduce_mod._sorted_by_keys = direct, by_keys
    assert fast == slow and len(fast) > 20
    picked = direct(ctx)
    assert (picked is None) == ("HAVING" in sql or "/" in sql)


def test_keys_that_do_not_compare_take_the_comparator():
    from pinot_tpu.query.reduce import _sorted_by_keys
    rows = [((3, "x"), "r3x"), ((None, "a"), "rNa"), ((1, "z"), "r1z"),
            ((3, "a"), "r3a")]
    got = [r for _k, r in _sorted_by_keys(rows, [True, False])]
    # None orders by its string, after the digits
    assert got == ["r1z", "r3x", "r3a", "rNa"]
    plain = [((2, "b"), 0), ((1, "b"), 1), ((2, "a"), 2), ((1, "b"), 3)]
    assert [r for _k, r in _sorted_by_keys(plain, [False, True])] \
        == [2, 0, 1, 3]  # ties keep their order
