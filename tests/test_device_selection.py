"""Device offload for selection / order-by / DISTINCT.

Ref: operator/query/SelectionOrderByOperator.java +
MinMaxValueBasedSelectionOrderByCombineOperator (top-K with only winning
docs materialized) and DistinctOperator (dictionary-based distinct) —
VERDICT r3 item 3.
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
    tmp = tmp_path_factory.mktemp("devsel")
    schema = Schema("t", [
        FieldSpec("d", DataType.INT, FieldType.DIMENSION),
        FieldSpec("s", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("m", DataType.INT, FieldType.METRIC),
    ])
    tc = TableConfig("t", TableType.OFFLINE)
    tc.indexing.no_dictionary_columns = ["m"]
    creator = SegmentCreator(tc, schema)
    rng = np.random.default_rng(21)
    out = []
    for i in range(3):
        n = 5000
        cols = {
            "d": rng.integers(0, 20, n).astype(np.int32),
            "s": np.array([f"v{x}" for x in rng.integers(0, 6, n)], object),
            "m": rng.integers(0, 100000, n).astype(np.int32),
        }
        d = str(tmp / f"seg_{i}")
        creator.build(cols, d, f"t_{i}")
        out.append(load_segment(d))
    return out


def _fresh_pair(segs):
    return (QueryExecutor(segs, use_tpu=False),
            QueryExecutor(segs, use_tpu=True, engine=TpuOperatorExecutor()))


def _check(segs, sql, expect_device=True):
    cpu, tpu = _fresh_pair(segs)
    a = cpu.execute(sql)
    b = tpu.execute(sql)
    assert not a.exceptions and not b.exceptions, (a.exceptions, b.exceptions)
    assert_responses_equal(a, b, sql)
    if expect_device:
        assert len(tpu.tpu_engine.stager._block_cache) > 0, \
            f"device path never engaged for {sql!r}"
    return b


class TestSelectionOffload:
    def test_supports_shapes(self, segs):
        eng = TpuOperatorExecutor()
        yes = [
            "SELECT d, m FROM t WHERE d > 5 LIMIT 20",
            "SELECT d FROM t ORDER BY m LIMIT 10",
            "SELECT s, m FROM t WHERE d BETWEEN 2 AND 9 ORDER BY m DESC LIMIT 5",
            "SELECT DISTINCT d FROM t",
            "SELECT DISTINCT d, s FROM t WHERE d < 10",
        ]
        no = [
            "SELECT d FROM t LIMIT 5",                       # host early-exit
            "SELECT d FROM t ORDER BY m, d LIMIT 5",         # 2 sort keys
            "SELECT d FROM t ORDER BY m LIMIT 100000",       # K over cap
            "SELECT DISTINCT d + 1 FROM t",                  # expr distinct
        ]
        for sql in yes:
            assert eng.supports(QueryContext.from_sql(sql)), sql
        for sql in no:
            assert not eng.supports(QueryContext.from_sql(sql)), sql

    def test_order_by_raw_metric(self, segs):
        _check(segs, "SELECT d, m FROM t ORDER BY m DESC LIMIT 7")

    def test_order_by_asc_with_filter(self, segs):
        _check(segs, "SELECT d, m FROM t WHERE d IN (1, 3, 5) "
                     "ORDER BY m LIMIT 9")

    def test_order_by_dict_string_col(self, segs):
        """Sorted dictionary: ORDER BY a string dict column via dictIds."""
        _check(segs, "SELECT s, d FROM t WHERE m > 50000 "
                     "ORDER BY s LIMIT 11")

    def test_order_by_expression(self, segs):
        _check(segs, "SELECT d, m FROM t ORDER BY m * 2 DESC LIMIT 5")

    def test_selection_with_filter_no_order(self, segs):
        cpu, tpu = _fresh_pair(segs)
        sql = "SELECT d FROM t WHERE d = 7 LIMIT 2000"
        a, b = cpu.execute(sql), tpu.execute(sql)
        # unordered selection: compare as multisets
        assert sorted(a.result_table.rows) == sorted(b.result_table.rows)
        assert len(tpu.tpu_engine.stager._block_cache) > 0

    def test_offset(self, segs):
        _check(segs, "SELECT m FROM t ORDER BY m LIMIT 5 OFFSET 3")

    def test_select_star_order_by(self, segs):
        _check(segs, "SELECT * FROM t ORDER BY m DESC LIMIT 4")

    def test_limit_larger_than_matches(self, segs):
        _check(segs, "SELECT d, m FROM t WHERE d = 3 AND m < 2000 "
                     "ORDER BY m LIMIT 500")


class TestTopnSentinel:
    def test_matched_rows_never_lose_to_sentinel(self, tmp_path):
        """Matched docs whose score clamps to -inf territory (huge values
        under ASC negation) must still outrank unmatched docs."""
        schema = Schema("t", [
            FieldSpec("d", DataType.INT, FieldType.DIMENSION),
            FieldSpec("x", DataType.DOUBLE, FieldType.METRIC)])
        tc = TableConfig("t", TableType.OFFLINE)
        tc.indexing.no_dictionary_columns = ["x"]
        creator = SegmentCreator(tc, schema)
        x = np.full(1000, 1.0)
        dd = np.zeros(1000, np.int32)
        x[::100] = 1e300  # f32-staging overflows; ASC score becomes -inf
        dd[::100] = 1     # filter selects exactly the overflow rows
        cols = {"d": dd, "x": x}
        d = str(tmp_path / "seg")
        creator.build(cols, d, "t_0")
        seg = load_segment(d)
        cpu = QueryExecutor([seg], use_tpu=False)
        tpu = QueryExecutor([seg], use_tpu=True,
                            engine=TpuOperatorExecutor())
        sql = "SELECT d FROM t WHERE d = 1 ORDER BY x LIMIT 20"
        a, b = cpu.execute(sql), tpu.execute(sql)
        assert len(b.result_table.rows) == len(a.result_table.rows) == 10
        assert len(tpu.tpu_engine.stager._block_cache) > 0


class TestDistinctOffload:
    def test_distinct_single(self, segs):
        _check(segs, "SELECT DISTINCT d FROM t ORDER BY d LIMIT 100")

    def test_distinct_multi(self, segs):
        _check(segs, "SELECT DISTINCT d, s FROM t ORDER BY d, s LIMIT 500")

    def test_distinct_filtered(self, segs):
        _check(segs, "SELECT DISTINCT s FROM t WHERE d BETWEEN 5 AND 8 "
                     "ORDER BY s LIMIT 100")

    def test_distinct_empty(self, segs):
        # min/max pruning drops every segment before the engine sees them
        _check(segs, "SELECT DISTINCT d FROM t WHERE d > 1000",
               expect_device=False)

    def test_distinct_empty_match_on_device(self, segs):
        # unprunable empty result (IN set within min/max range)
        _check(segs, "SELECT DISTINCT s FROM t WHERE d IN (0, 19) "
                     "AND m < 0 ORDER BY s LIMIT 10", expect_device=False)
