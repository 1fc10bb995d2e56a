"""Device star-tree pre-aggregation (ISSUE 16).

The engine's `_prepare_startree` leg: host tree traversal + device
residual aggregation through the unified kernel factory. Covers

  * parity — device pre-agg vs host star-tree vs scan path, identical
    rows (1e-6 relative, the device-parity standard) on randomized data,
    flat and grouped, including AVG's (SUM, COUNT) decomposition
  * fit-check edges — FILTER aggs, OR filters, non-tree-dim predicates,
    `OPTION(useStarTree=false)`: each answers correctly via the scan
    path and meters its `startree_fallback{reason=}`; the
    `pinot.server.startree.enabled` knob disables the leg wholesale
  * coalescing — fingerprint-equal concurrent star-tree queries share
    batched launches (`dispatch_batch_size` > 1) with ZERO steady-state
    retraces once the shape buckets are warm
  * warmup — `SegmentWarmup` prestages the pre-agg pseudo-columns, so
    the first routed query ships zero column bytes
  * the `bench.py --startree` acceptance scenario at smoke scale
"""
import threading

import numpy as np
import pytest

from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              StarTreeIndexConfig, TableConfig, TableType)
from pinot_tpu.ops import kernels
from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.ops.plan_ir import batch_params
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.executor import QueryExecutor
from pinot_tpu.segment.creator import SegmentCreator
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.utils.config import PinotConfiguration

NUM_DOCS = 3_000   # per segment
NUM_SEGS = 2


@pytest.fixture(scope="module")
def segs(tmp_path_factory):
    """Identical data twice: plain segments and tree-carrying segments.
    `platform` stays OUT of the split order — the non-tree-dim
    fallback case."""
    tmp = tmp_path_factory.mktemp("startree_device")
    schema = Schema("st", [
        FieldSpec("country", DataType.STRING),
        FieldSpec("browser", DataType.STRING),
        FieldSpec("locale", DataType.STRING),
        FieldSpec("platform", DataType.STRING),
        FieldSpec("impressions", DataType.LONG, FieldType.METRIC),
        FieldSpec("cost", DataType.DOUBLE, FieldType.METRIC),
    ])
    tc_plain = TableConfig("st", TableType.OFFLINE)
    tc_tree = TableConfig("st", TableType.OFFLINE)
    tc_tree.indexing.star_tree_configs = [StarTreeIndexConfig(
        dimensions_split_order=["country", "browser", "locale"],
        function_column_pairs=["SUM__impressions", "MAX__cost",
                               "SUM__cost"],
        max_leaf_records=10)]
    plain, tree = [], []
    for i in range(NUM_SEGS):
        rng = np.random.default_rng(17 + i)
        cols = {
            "country": [f"c{v}" for v in rng.integers(0, 12, NUM_DOCS)],
            "browser": [f"b{v}" for v in rng.integers(0, 5, NUM_DOCS)],
            "locale": [f"l{v}" for v in rng.integers(0, 8, NUM_DOCS)],
            "platform": [f"p{v}" for v in rng.integers(0, 3, NUM_DOCS)],
            "impressions": rng.integers(0, 1000, NUM_DOCS).astype(np.int64),
            "cost": rng.random(NUM_DOCS) * 100,
        }
        SegmentCreator(tc_plain, schema).build(
            dict(cols), str(tmp / f"plain_{i}"), f"st_plain_{i}")
        SegmentCreator(tc_tree, schema).build(
            dict(cols), str(tmp / f"tree_{i}"), f"st_tree_{i}")
        plain.append(load_segment(str(tmp / f"plain_{i}")))
        tree.append(load_segment(str(tmp / f"tree_{i}")))
    return plain, tree


QUERIES = [
    "SELECT SUM(impressions) FROM st",
    "SELECT COUNT(*), SUM(impressions), MAX(cost) FROM st",
    "SELECT SUM(impressions) FROM st WHERE country = 'c3'",
    "SELECT SUM(impressions) FROM st "
    "WHERE country IN ('c1','c2','c3') AND browser = 'b2'",
    "SELECT SUM(impressions), AVG(cost) FROM st WHERE locale = 'l5'",
    "SELECT AVG(impressions), AVG(cost) FROM st WHERE browser = 'b1'",
    "SELECT country, SUM(impressions) FROM st "
    "GROUP BY country ORDER BY country LIMIT 100",
    "SELECT country, browser, COUNT(*), SUM(cost) FROM st "
    "WHERE locale = 'l1' GROUP BY country, browser "
    "ORDER BY country, browser LIMIT 200",
    "SELECT browser, MAX(cost) FROM st WHERE country BETWEEN 'c1' AND 'c4' "
    "GROUP BY browser ORDER BY browser LIMIT 100",
]


def _rows_close(a, b):
    if len(a) != len(b):
        return False
    for x, y in zip(a, b):
        if isinstance(x, float) or isinstance(y, float):
            if not (abs(float(x) - float(y))
                    <= 1e-6 * max(1.0, abs(float(x)))):
                return False
        elif x != y:
            return False
    return True


def _assert_same_rows(resp_a, resp_b, sql):
    assert not resp_a.exceptions and not resp_b.exceptions, sql
    ra = sorted(map(str, resp_a.result_table.rows))
    rb = sorted(map(str, resp_b.result_table.rows))
    assert len(ra) == len(rb), (sql, ra, rb)
    for a, b in zip(ra, rb):
        assert _rows_close(eval(a), eval(b)), (sql, a, b)


def _engine(name, **overrides):
    return TpuOperatorExecutor(
        config=PinotConfiguration(overrides=overrides),
        metrics_labels={"st_test": name})


def _meter(eng, name, reason=None):
    labels = {"st_test": eng._labels["st_test"]}
    if reason is not None:
        labels["reason"] = reason
    return eng._metrics.meter(name, labels=labels)


class TestDeviceParity:
    @pytest.mark.parametrize("sql", QUERIES)
    def test_device_tree_vs_host_tree_vs_scan(self, segs, sql):
        plain, tree = segs
        dev = QueryExecutor(tree, use_tpu=True).execute(sql)
        host = QueryExecutor(tree, use_tpu=False).execute(sql)
        scan = QueryExecutor(plain, use_tpu=False).execute(sql)
        _assert_same_rows(dev, host, sql)
        _assert_same_rows(dev, scan, sql)

    def test_served_meter_and_preagg_stats(self, segs):
        """The pre-agg leg actually serves (startree_served moves) and
        scans pre-agg records, not raw docs."""
        _, tree = segs
        eng = _engine("served")
        ex = QueryExecutor(tree, use_tpu=True, engine=eng)
        r = ex.execute("SELECT SUM(impressions) FROM st WHERE country = 'c3'")
        assert not r.exceptions
        assert _meter(eng, "startree_served") == 1
        assert 0 < r.stats.num_docs_scanned < NUM_SEGS * NUM_DOCS / 2

    def test_knob_disables_the_leg(self, segs):
        """pinot.server.startree.enabled=false: same rows via the scan
        path, nothing served from pre-agg."""
        plain, tree = segs
        eng = _engine("knob", **{"pinot.server.startree.enabled": False})
        ex = QueryExecutor(tree, use_tpu=True, engine=eng)
        sql = "SELECT SUM(impressions), COUNT(*) FROM st WHERE browser = 'b2'"
        _assert_same_rows(ex.execute(sql),
                          QueryExecutor(plain, use_tpu=False).execute(sql),
                          sql)
        assert _meter(eng, "startree_served") == 0


class TestFitFallback:
    """Queries a tree can't serve answer correctly via the scan path and
    meter their startree_fallback reason."""

    CASES = [
        ("SELECT SUM(impressions) FROM st OPTION(useStarTree=false)",
         "disabled"),
        ("SELECT SUM(impressions) FILTER (WHERE browser = 'b1'), COUNT(*) "
         "FROM st", "aggregation"),
        ("SELECT SUM(impressions) FROM st "
         "WHERE country = 'c1' OR browser = 'b1'", "filter"),
        ("SELECT SUM(impressions) FROM st WHERE platform = 'p1'", "filter"),
    ]

    @pytest.mark.parametrize("sql,reason", CASES)
    def test_fallback_reason_and_parity(self, segs, sql, reason):
        plain, tree = segs
        eng = _engine(f"fb_{reason}_{abs(hash(sql)) % 1000}")
        before = _meter(eng, "startree_fallback", reason=reason)
        dev = QueryExecutor(tree, use_tpu=True, engine=eng).execute(sql)
        scan = QueryExecutor(plain, use_tpu=False).execute(sql)
        _assert_same_rows(dev, scan, sql)
        assert _meter(eng, "startree_fallback", reason=reason) > before, sql
        assert _meter(eng, "startree_served") == 0


class TestCoalesce:
    def test_fingerprint_equal_queries_batch_with_zero_retraces(self, segs):
        """Concurrent star-tree queries that differ only in predicate
        constants share the (plan fingerprint, shape bucket) coalesce
        key: batched launches form, and once the pow2 batch buckets are
        traced, the measured window compiles NOTHING."""
        import contextlib

        import jax

        from pinot_tpu.ops import dispatch as dispatch_mod
        _, tree = segs
        clients = 6
        eng = _engine("coalesce")
        ex = QueryExecutor(tree, use_tpu=True, engine=eng)
        sqls = [f"SELECT SUM(impressions), COUNT(*) FROM st "
                f"WHERE country = 'c{i}'" for i in range(clients)]
        for sql in sqls:   # stage blocks + params, trace the single path
            assert not ex.execute(sql).exceptions
        launch = eng._prepare_startree(
            tree, QueryContext.from_sql(sqls[0]))[4]
        guard = dispatch_mod._CPU_COLLECTIVE_LOCK if launch.collective \
            else contextlib.nullcontext()
        b = 2
        while b <= dispatch_mod._pow2(clients):
            kern = launch.factory(b, False)
            with guard:
                jax.block_until_ready(kern(
                    launch.cols, batch_params([launch.params] * b), launch.num_docs,
                    D=launch.D, G=launch.G))
            b *= 2

        traces0 = kernels.trace_count()
        labels = {"st_test": "coalesce"}
        t0 = eng._metrics.timer("dispatch_batch_size", labels=labels)
        count0, max0 = t0.count, t0.max_ms
        rounds = 8

        def client(ci):
            for j in range(rounds):
                ex.execute(sqls[(ci + j) % clients])

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert kernels.trace_count() - traces0 == 0
        t1 = eng._metrics.timer("dispatch_batch_size", labels=labels)
        assert t1.count > count0
        assert max(t1.max_ms, max0) >= 2, \
            "fingerprint-equal star-tree queries never coalesced"


class TestWarmupPrestage:
    def test_warmup_prestages_preagg_columns(self, segs):
        """SegmentWarmup's replay prestages the star-tree pseudo-columns
        (engine.prestage takes the star-tree leg for fitted plans), so
        the first routed query ships zero column bytes."""
        from pinot_tpu.cache.segment_cache import SegmentResultCache
        from pinot_tpu.cache.warmup import FingerprintLog, SegmentWarmup
        from pinot_tpu.ops import residency
        _, tree = segs
        eng = _engine("warmup")
        log = FingerprintLog()
        sql = "SELECT SUM(impressions), COUNT(*) FROM st WHERE country = 'c2'"
        log.record("st", QueryContext.from_sql(sql).fingerprint(), sql)
        warm = SegmentWarmup(log, SegmentResultCache(), use_tpu=True,
                             engine_fn=lambda: eng)
        assert warm.warm("st", tree[0]) == 1
        # the seeded replay went through the pre-agg leg and admitted
        # the __startree__ pseudo-columns into residency
        assert _meter(eng, "startree_served") == 1
        assert eng.residency.resident_for(tree[0].name) > 0
        b0 = residency.column_transfer_bytes()
        r = QueryExecutor([tree[0]], use_tpu=True, engine=eng).execute(sql)
        assert not r.exceptions
        assert _meter(eng, "startree_served") == 2
        assert residency.column_transfer_bytes() - b0 == 0


class TestBenchSmoke:
    def test_startree_bench_smoke(self, tmp_path):
        """The --startree acceptance scenario at smoke scale: scaling
        A/B (pre-agg vs scan, parity inside), coalescing with zero
        steady-state retraces asserted inside."""
        import importlib
        import json
        import os
        import sys
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        bench = importlib.import_module("bench")
        out = str(tmp_path / "BENCH_startree_smoke.json")
        bench.startree_main(smoke=True, out_path=out)
        with open(out) as f:
            data = json.load(f)
        assert data["coalesce"]["retraces_steady"] == 0
        assert data["coalesce"]["batch_size_max"] >= 2
