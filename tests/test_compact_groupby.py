"""Compacted (sparse-key) device group-by.

Dense mixed-radix keys explode as the PRODUCT of cardinalities (three
1000-card dims = 1e9 keys); the compact path scatter-adds over per-segment
OBSERVED key codes instead. Ref: pinot-core
query/aggregation/groupby/DictionaryBasedGroupKeyGenerator.java map-based
modes — VERDICT r3 item 4.
"""
import numpy as np
import pytest

from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              TableConfig, TableType)
from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.executor import QueryExecutor
from pinot_tpu.segment.creator import SegmentCreator
from pinot_tpu.segment.loader import load_segment
from tests.queries.harness import assert_responses_equal


@pytest.fixture(scope="module")
def segs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("compactgb")
    schema = Schema("t", [
        FieldSpec("a", DataType.INT, FieldType.DIMENSION),
        FieldSpec("b", DataType.INT, FieldType.DIMENSION),
        FieldSpec("c", DataType.INT, FieldType.DIMENSION),
        FieldSpec("m", DataType.INT, FieldType.METRIC),
    ])
    tc = TableConfig("t", TableType.OFFLINE)
    tc.indexing.no_dictionary_columns = ["m"]
    creator = SegmentCreator(tc, schema)
    rng = np.random.default_rng(31)
    out = []
    for i in range(2):
        n = 20000
        cols = {
            # ~1000 distinct values per column: the dense key space is
            # ~1e9 >> MAX_DEVICE_GROUPS, but observed tuples <= n
            "a": rng.integers(0, 1000, n).astype(np.int32),
            "b": (rng.integers(0, 1000, n) * 7).astype(np.int32),
            "c": rng.integers(0, 900, n).astype(np.int32),
            "m": rng.integers(0, 1000, n).astype(np.int32),
        }
        d = str(tmp / f"seg_{i}")
        creator.build(cols, d, f"t_{i}")
        out.append(load_segment(d))
    return out


class TestCompactGroupBy:
    SQL = ("SELECT a, b, c, SUM(m), COUNT(*) FROM t "
           "GROUP BY a, b, c ORDER BY a, b, c LIMIT 100000")

    def test_plan_switches_to_compact(self, segs):
        eng = TpuOperatorExecutor()
        ctx = QueryContext.from_sql(self.SQL)
        plan, _ = eng._plan(segs, ctx)
        assert plan.group_compact
        assert plan.num_groups == 0
        # group-only columns drop their id planes (gkey replaces them)
        assert "a" not in plan.dict_cols

    def test_three_col_card1000_parity(self, segs):
        cpu = QueryExecutor(segs, use_tpu=False)
        tpu = QueryExecutor(segs, use_tpu=True,
                            engine=TpuOperatorExecutor())
        a = cpu.execute(self.SQL)
        b = tpu.execute(self.SQL)
        assert not a.exceptions and not b.exceptions
        assert_responses_equal(a, b, self.SQL)
        assert len(a.result_table.rows) > 10000  # genuinely sparse+wide
        assert any(k[1] == "gkey" for k in
                   tpu.tpu_engine.stager._block_cache), "compact path not used"

    def test_with_filter_and_min_max(self, segs):
        sql = ("SELECT a, b, c, MIN(m), MAX(m), AVG(m) FROM t "
               "WHERE c BETWEEN 100 AND 700 AND a < 900 "
               "GROUP BY a, b, c ORDER BY a, b, c LIMIT 100000")
        eng = TpuOperatorExecutor()
        ctx = QueryContext.from_sql(sql)
        plan, _ = eng._plan(segs, ctx)
        assert plan.group_compact
        # the filter still needs a/c id planes even in compact mode
        assert "a" in plan.dict_cols and "c" in plan.dict_cols
        cpu = QueryExecutor(segs, use_tpu=False)
        tpu = QueryExecutor(segs, use_tpu=True, engine=eng)
        assert_responses_equal(cpu.execute(sql), tpu.execute(sql), sql)

    def test_dense_path_still_used_when_small(self, segs):
        eng = TpuOperatorExecutor()
        ctx = QueryContext.from_sql(
            "SELECT c, COUNT(*) FROM t GROUP BY c LIMIT 1000")
        plan, _ = eng._plan(segs, ctx)
        assert not plan.group_compact and plan.num_groups > 0

    def test_repeat_query_hits_gkey_cache(self, segs):
        eng = TpuOperatorExecutor()
        tpu = QueryExecutor(segs, use_tpu=True, engine=eng)
        tpu.execute(self.SQL)
        hosts_before = len(eng.stager._host_rows)
        tpu.execute(self.SQL)
        assert len(eng.stager._host_rows) == hosts_before  # no re-factorize
