"""TSBS devops `double-groupby-1` on Pinot's epoch-millis time column
(the `tsbs_ts_dgb1_c1` cell) at a size a test run can hold: the
configuration's own columns (benchmark/datagen, seeded), 16 segments of
unequal docs in time order, `ts` a raw LONG of epoch milliseconds. The
cell's template groups by `DATETRUNC('HOUR', ts), hostname`: the device
computes the hour from ts's (hi, lo) planes inside the grouped kernel
(ops/timeseries_device.py) and folds the 3-4 surviving segments into one
`[G, slots]` table, the bucket digit carried unmapped. Each answer has to
be the host executor's and `benchmark/reference.py`'s, for windows that a
segment boundary splits, through the server's executor and through a
broker; the spans have to say so. Also: `fleet_ts` makes ts and the
reference's hour axis from one instant, and the time-series leaf's
`floor` bucket folds on the device too, with its bucket-index keys."""
import os
import sys

import numpy as np
import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import datagen  # noqa: E402  (benchmark/)
import reference  # noqa: E402  (benchmark/)
import traffic  # noqa: E402  (benchmark/)

from pinot_tpu.query.context import QueryContext  # noqa: E402
from pinot_tpu.query.executor import QueryExecutor  # noqa: E402
from pinot_tpu.query.reduce import reduce_results  # noqa: E402
from pinot_tpu.query.results import GroupByResult  # noqa: E402
from pinot_tpu.query.transform import datetrunc  # noqa: E402
from pinot_tpu.segment.creator import SegmentCreator  # noqa: E402
from pinot_tpu.segment.loader import load_segment  # noqa: E402
from pinot_tpu.utils.config import PinotConfiguration  # noqa: E402

SEED = 2_600_000_045  # past 2**31, as a benchmark run's seeds are
#: segments of unequal docs, none a power of two
DOCS = (60000, 17001, 25500, 9000)
HOUR = 3_600_000


@pytest.fixture(scope="module")
def cell():
    _bench, cell, config, mix = traffic.load_cell(ROOT, "tsbs_ts_dgb1_c1")
    assert cell["config"] == "tsbs_cpu_epochms_104m_1chip" \
        and cell["chips"] == 1
    return config, mix


@pytest.fixture(scope="module")
def table(cell, tmp_path_factory):
    """(loaded segments, the plain reference of the same rows)."""
    config, _mix = cell
    tmp = tmp_path_factory.mktemp("tsbs_ms")
    tc, schema = datagen.table_and_schema(config)
    assert tc.indexing.no_dictionary_columns == ["ts", "usage_user"]
    ref = reference.Reference(config, datagen.domains(config))
    segs = []
    for i in range(config["segments"]):
        made = datagen.make_columns(config, SEED, i, DOCS[i % len(DOCS)])
        ref.add(reference.segment_share(config, made))
        name = f"{config['table']}_{i}"
        SegmentCreator(tc, schema).build(
            {k: v[0] for k, v in made.items()}, str(tmp / name), name)
        segs.append(load_segment(str(tmp / name)))
    ts = segs[0].metadata.columns["ts"]
    assert not ts.has_dictionary and ts.data_type.name == "LONG"
    return segs, ref


@pytest.fixture(scope="module")
def served(cell, table):
    """The server's executor; `_shared_engine()` finds one device, as
    the cell's server holds one chip."""
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
    engine.buckets_before = bucket_meter(engine, "device")
    yield ex, engine
    dm.shutdown()
    ex.segment_cache.close()
    ex.fingerprint_log.close()


def bucket_meter(engine, fold: str) -> float:
    return engine._metrics.meter("time_bucket", labels=dict(
        engine._labels or {}, unit="HOUR", fold=fold))


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
            "traceId": "tsbs_ms", "spanId": "1", "sampled": True})
    results, exceptions, _stats, trace = deserialize_results_ex(payload)
    assert not exceptions
    resp = reduce_results(QueryContext.from_sql(sql), results)
    return resp, results, spans(trace, "DeviceDispatch")


def exact(rows) -> list:
    return [[int(r[0]), int(r[1]), int(r[2]), r[3]] for r in rows]


def test_the_hour_column_is_datetrunc_of_ts_row_by_row(cell):
    """The reference's axis is what the timed SQL's key gives, and a
    segment's ts lies inside its own 4.5 hours of the three days."""
    config, _mix = cell
    origin = config["columns"][0]["generator"]["origin_ms"]
    assert origin == 1_451_606_400_000  # 2016-01-01T00:00Z, TSBS's start
    stretch = [0, 8, 4, 12, 2, 10, 6, 14, 1, 9, 5, 13, 3, 11, 7, 15]
    for segment, docs in ((0, 6_480_000), (5, 9000), (15, 17001)):
        made = datagen.make_columns(config, SEED, segment, docs)
        ts, hour = made["ts"][0], made["ts_hour_ms"][0]
        assert ts.dtype == np.int64 and (np.diff(ts) >= 0).all()
        assert (datetrunc("HOUR", ts) == hour).all()
        first = origin + stretch[segment] * 16_200_000
        assert first <= ts.min() and ts.max() < first + 16_200_000
        assert ts.min() == first
        domain = made["ts_hour_ms"][2]
        assert len(domain) == 72 and (domain[made["ts_hour_ms"][1]]
                                      == hour).all()
    full = datagen.make_columns(config, SEED, 3, 6_480_000)
    # every host once a tick, at the tick's instant, 10 s apart
    ticks = full["ts"][0].reshape(-1, 4000)
    assert (ticks == ticks[:, :1]).all()
    assert (np.diff(ticks[:, 0]) == 10_000).all()


def test_the_template_is_served_on_the_device_as_host_and_reference_answer(
        cell, table, served):
    config, mix = cell
    segs, ref = table
    ex, engine = served
    host = QueryExecutor(segs, use_tpu=False)
    assert len(engine.devices) == 1 and engine._mesh is None
    assert [t["name"] for t in mix["templates"]] \
        == ["dgb1_epochms_usage_user"]
    queries = traffic.make_queries(mix, config["table"], SEED, 1, 6, False)
    assert len({q[1]["w"][0] for q in queries}) > 4  # several windows
    split = 0
    for t, literals, sql in queries:
        template = mix["templates"][t]
        resp, results, dispatches = ask(ex, config, sql)
        want = ref.answer(template, literals)
        assert 6000 < len(want) <= 48000
        got = exact(resp.result_table.rows)
        assert got == [[w[0], w[1], w[2], w[3]] for w in want]
        assert got == exact(host.execute(sql).rows)
        assert resp.result_table.column_types[2] == "LONG"
        assert all(type(r[2]) is int for r in resp.result_table.rows[:50])
        # one result for the batch, folded on the device
        assert len(results) == 1 and isinstance(results[0], GroupByResult)
        lo, _last, hi = literals["w"]
        touched = [s for s in segs if s.metadata.columns["ts"].max_value >= lo
                   and s.metadata.columns["ts"].min_value < hi]
        assert len(touched) in (3, 4)
        assert results[0].stats.num_segments_processed == len(touched)
        in_time = sorted(int(s.metadata.columns["ts"].min_value)
                         for s in touched)
        # a segment that starts inside an hour of the window splits it
        split += sum(1 for m in in_time[1:] if m % HOUR)
        span, = dispatches
        assert "outcome" not in span, "fell back to the host"
        assert (span["groupFold"], span["timeBucket"], span["bucketCount"],
                span["bucketPad"]) == ("device", "HOUR", 12, 16)
        assert span["groupPath"] == "onehot2"
        assert span["groupKeySpace"] == 16 * max(
            s.metadata.columns["hostname"].cardinality for s in touched)
        assert span["groupsPresent"] == len(want)
        # the fold's table: 4,096 hosts x 16 hours, SUM and COUNT, 4 B
        assert span["groupResultBytes"] == 4096 * 16 * 2 * 4
    assert split >= 2
    assert bucket_meter(engine, "device") - engine.buckets_before \
        == len(queries)
    assert bucket_meter(engine, "host") == 0


def test_the_answer_crosses_a_broker_as_columns(cell, table, served):
    """broker -> framed TCP -> server -> engine -> ring over the server's
    warm engine: the broker finishes the one folded result as columns and
    the DeviceDispatch span says the bucket ran fused and folded."""
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
        resp = c.query("SET trace = true; " + sql)
    finally:
        c.servers[0].executor._engine = None  # the fixture's to close
        c.stop()
    assert not resp.exceptions, resp.exceptions
    want = ref.answer(mix["templates"][t], literals)
    assert exact(resp.result_table.rows) == [list(w) for w in want]
    span, = spans(resp.trace, "DeviceDispatch")
    assert (span["groupFold"], span["timeBucket"]) == ("device", "HOUR")
    reduced, = spans(resp.trace, "BrokerReduce")
    assert (reduced["reducePath"], reduced["reduceRows"]) \
        == ("columns", len(want))
    # the HTTP edge would write its JSON a column at a time
    assert resp.encode_path == "columns"


@pytest.mark.parametrize("unit,hours,select_host", [
    ("MINUTE", 3, True), ("DAY", 60, False), ("WEEK", 3, True),
    ("SECOND", 2, False)])
def test_other_units_and_a_bucket_alone_fold_on_the_device(
        cell, table, served, unit, hours, select_host):
    """A unit of fixed width is the same fused bucket at another step;
    grouped by the bucket alone, the fold has no table to remap at all."""
    config, _mix = cell
    segs, _ref = table
    ex, _engine = served
    lo = 1_451_606_400_000 + 30 * HOUR + 1234
    hi = lo + hours * HOUR
    key = f"DATETRUNC('{unit}', ts)"
    by = f"{key}, hostname" if select_host else key
    sql = (f"SELECT {by}, COUNT(*), SUM(usage_user) FROM {config['table']} "
           f"WHERE ts >= {lo} AND ts < {hi} AND hostname <> 'host_7' "
           f"GROUP BY {by} ORDER BY {by} LIMIT 100000")
    resp, results, (span,) = ask(ex, config, sql)
    host = QueryExecutor(segs, use_tpu=False).execute(sql)
    assert len(results) == 1 and "outcome" not in span
    assert span["groupFold"] == "device" and span["timeBucket"] == unit
    got = [tuple(r[:-1]) + (float(r[-1]),) for r in resp.result_table.rows]
    assert got == [tuple(r[:-1]) + (float(r[-1]),) for r in host.rows]
    assert len(got) > 1


def test_months_and_a_missing_window_stay_host_expressions(cell, table,
                                                           served):
    config, _mix = cell
    segs, _ref = table
    ex, _engine = served
    lo = 1_451_606_400_000 + 30 * HOUR
    for key, where in (("DATETRUNC('MONTH', ts)", f"ts >= {lo}"),
                       ("DATETRUNC('HOUR', ts)", f"hostname = 'host_3'"),
                       ("DATETRUNC('HOUR', ts, 'MILLISECONDS')",
                        f"ts >= {lo} AND ts < {lo + HOUR}")):
        sql = (f"SELECT {key}, COUNT(*) FROM {config['table']} WHERE {where} "
               f"GROUP BY {key} ORDER BY {key} LIMIT 1000")
        resp, _results, dispatches = ask(ex, config, sql)
        assert all(d.get("outcome") or "timeBucket" not in d
                   for d in dispatches)
        assert resp.result_table.rows \
            == QueryExecutor(segs, use_tpu=False).execute(sql).rows


def test_past_the_remap_cap_the_host_folds_with_the_same_keys(cell, table,
                                                              served):
    config, mix = cell
    segs, ref = table
    ex, engine = served
    t, literals, sql = traffic.make_queries(
        mix, config["table"], SEED, 1, 3, False)[2]
    engine._gmap_cache.clear()
    engine.GMAP_MAX_BYTES = 1 << 10  # under any remap of 4000 hosts
    try:
        resp, results, (span,) = ask(ex, config, sql)
    finally:
        del engine.GMAP_MAX_BYTES
        engine._gmap_cache.clear()
    assert (span["groupFold"], span["timeBucket"]) == ("host", "HOUR")
    assert len(results) in (3, 4)
    assert exact(resp.result_table.rows) \
        == [list(w) for w in ref.answer(mix["templates"][t], literals)]
    assert bucket_meter(engine, "host") >= 1


def test_the_leaf_s_floor_bucket_folds_with_bucket_index_keys(cell, table,
                                                              served):
    """The time-series leaf's floor((t - start) / step) keeps its keys,
    bucket indices as the host's floor() over an f64 division gives them,
    whether the device or the host folds."""
    config, _mix = cell
    segs, _ref = table
    ex, engine = served
    start = 1_451_606_400_000 + 40 * HOUR
    key = f"floor((ts - {start}) / 600000)"
    sql = (f"SELECT {key}, hostname, SUM(usage_user) FROM {config['table']} "
           f"WHERE ts >= {start} AND ts < {start + 2 * HOUR} "
           f"GROUP BY {key}, hostname LIMIT 100000 OPTION(skipCache=true)")
    host = sorted(QueryExecutor(segs, use_tpu=False).execute(sql).rows)
    for cap in (None, 1 << 10):
        engine._gmap_cache.clear()
        if cap:
            engine.GMAP_MAX_BYTES = cap
        try:
            resp, _results, (span,) = ask(ex, config, sql)
        finally:
            if cap:
                del engine.GMAP_MAX_BYTES
            engine._gmap_cache.clear()
        assert span["timeBucket"] == "FLOOR" and span["bucketCount"] == 12
        assert span["groupFold"] == ("host" if cap else "device")
        got = sorted(resp.result_table.rows)
        assert [(r[0], r[1], float(r[2])) for r in got] \
            == [(r[0], r[1], float(r[2])) for r in host]
        assert all(type(r[0]) is float for r in got)
