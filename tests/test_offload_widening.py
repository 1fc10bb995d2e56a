"""Device-offload widening: big-int (epoch millis) filters via split
planes, FILTER-clause aggregations as per-slot masks, >65536-group
group-bys — all parity-checked against the host executor, with x64 OFF
(the production TPU default) where it matters.
"""
import numpy as np
import pytest

import jax

from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              TableConfig, TableType)
from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.query.executor import QueryExecutor
from tests.queries.harness import build_segments

N = 5000
MS0 = 1_690_000_000_000  # epoch millis base (~2^40.6)


@pytest.fixture(scope="module")
def time_segs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("toff")
    schema = Schema("testTable", [
        FieldSpec("tsMillis", DataType.LONG, FieldType.DIMENSION),
        FieldSpec("dim", DataType.INT, FieldType.DIMENSION),
        FieldSpec("dim2", DataType.INT, FieldType.DIMENSION),
        FieldSpec("val", DataType.INT, FieldType.METRIC),
    ])
    tc = TableConfig("testTable", TableType.OFFLINE)
    tc.indexing.no_dictionary_columns = ["tsMillis"]
    rng = np.random.default_rng(5)
    cols = []
    for i in range(2):
        ts = MS0 + rng.integers(0, 90 * 24 * 3600 * 1000, N)
        # plant exact boundary values so strict-vs-nonstrict differs
        ts[: N // 10] = MS0 + 1000
        cols.append({
            "tsMillis": ts.astype(np.int64),
            "dim": rng.integers(0, 300, N).astype(np.int32),
            "dim2": rng.integers(0, 300, N).astype(np.int32),
            "val": rng.integers(0, 1000, N).astype(np.int32),
        })
    return build_segments(tmp, schema, tc, cols)


def _parity(segs, sql, engine=None, expect_offload=True):
    cpu = QueryExecutor(segs, use_tpu=False)
    eng = engine if engine is not None else TpuOperatorExecutor()
    tpu = QueryExecutor(segs, use_tpu=True, engine=eng)
    a, b = cpu.execute(sql), tpu.execute(sql)
    assert not a.exceptions and not b.exceptions, (a.exceptions, b.exceptions)
    assert len(a.rows) == len(b.rows), (sql, a.rows, b.rows)
    for ra, rb in zip(a.rows, b.rows):
        for x, y in zip(ra, rb):
            if isinstance(x, float) or isinstance(y, float):
                assert abs(float(x) - float(y)) <= \
                    1e-4 * max(1.0, abs(float(y))), (sql, a.rows, b.rows)
            else:
                assert x == y, (sql, a.rows, b.rows)
    if expect_offload:
        assert eng.stager._block_cache, f"query fell back to host: {sql}"
    return b


class TestBigIntFilters:
    """Epoch-millis columns filter on device with x64 OFF (split planes)."""

    def test_between_exact_bounds(self, time_segs):
        with jax.enable_x64(False):
            lo, hi = MS0 + 1000, MS0 + 40 * 24 * 3600 * 1000
            r = _parity(time_segs,
                        f"SELECT COUNT(*), SUM(val) FROM testTable "
                        f"WHERE tsMillis BETWEEN {lo} AND {hi}")
            assert int(r.rows[0][0]) > 0

    def test_strict_gt_on_boundary(self, time_segs):
        with jax.enable_x64(False):
            b = MS0 + 1000  # planted boundary value
            gt = _parity(time_segs,
                         f"SELECT COUNT(*) FROM testTable WHERE tsMillis > {b}")
            ge = _parity(time_segs,
                         f"SELECT COUNT(*) FROM testTable WHERE tsMillis >= {b}")
            assert int(ge.rows[0][0]) - int(gt.rows[0][0]) >= N // 10

    def test_equals_and_combined(self, time_segs):
        with jax.enable_x64(False):
            b = MS0 + 1000
            _parity(time_segs,
                    f"SELECT COUNT(*), SUM(val) FROM testTable "
                    f"WHERE tsMillis = {b} AND dim < 150")

    def test_split_planes_staged(self, time_segs):
        with jax.enable_x64(False):
            eng = TpuOperatorExecutor()
            _parity(time_segs,
                    f"SELECT COUNT(*) FROM testTable WHERE tsMillis > {MS0}",
                    engine=eng)
            kinds = {k[1] for k in eng.stager._block_cache}
            assert "valhi" in kinds and "vallo" in kinds


class TestFilterAggs:
    """FILTER (WHERE ...) aggregations offload as per-slot masks."""

    def test_filtered_sum_count(self, time_segs):
        _parity(time_segs,
                "SELECT SUM(val) FILTER (WHERE dim < 100) AS a, "
                "COUNT(*) FILTER (WHERE dim >= 200) AS b, "
                "SUM(val) AS total FROM testTable")

    def test_filtered_with_main_filter(self, time_segs):
        _parity(time_segs,
                "SELECT COUNT(*) FILTER (WHERE dim2 < 50) AS c, COUNT(*) "
                "FROM testTable WHERE dim BETWEEN 10 AND 250")

    def test_filtered_group_by(self, time_segs):
        _parity(time_segs,
                "SELECT dim, SUM(val) FILTER (WHERE dim2 < 150), COUNT(*) "
                "FROM testTable GROUP BY dim ORDER BY dim LIMIT 500")

    def test_same_filter_deduped(self, time_segs):
        eng = TpuOperatorExecutor()
        _parity(time_segs,
                "SELECT SUM(val) FILTER (WHERE dim < 100), "
                "COUNT(*) FILTER (WHERE dim < 100) FROM testTable",
                engine=eng)


class TestBigIntReviewRegressions:
    def test_aggregate_over_split_plane_column_falls_back(self, time_segs):
        """MIN/MAX over a vrange64-filtered big-int column must fall back
        to the host (no 'val:' block exists), not crash."""
        with jax.enable_x64(False):
            b = MS0 + 1000
            _parity(time_segs,
                    f"SELECT MIN(tsMillis), MAX(tsMillis) FROM testTable "
                    f"WHERE tsMillis > {b}", expect_offload=False)

    def test_epoch_nanos_falls_back(self, tmp_path):
        """Values >= 2^55 would wrap the i32 hi plane: host fallback."""
        schema = Schema("t", [
            FieldSpec("tsNanos", DataType.LONG, FieldType.DIMENSION),
            FieldSpec("v", DataType.INT, FieldType.METRIC)])
        tc = TableConfig("t", TableType.OFFLINE)
        tc.indexing.no_dictionary_columns = ["tsNanos"]
        rng = np.random.default_rng(1)
        base = 1_690_000_000_000_000_000  # ~2^60.6
        cols = {"tsNanos": (base + rng.integers(0, 10**12, 500)
                            ).astype(np.int64),
                "v": rng.integers(0, 100, 500).astype(np.int32)}
        segs = build_segments(tmp_path, schema, tc, [cols])
        with jax.enable_x64(False):
            eng = TpuOperatorExecutor()
            _parity(segs,
                    f"SELECT COUNT(*), SUM(v) FROM t WHERE tsNanos > {base}",
                    engine=eng, expect_offload=False)
            kinds = {k[1] for k in eng.stager._block_cache}
            assert "valhi" not in kinds

    def test_infinite_literal_falls_back(self, time_segs):
        with jax.enable_x64(False):
            _parity(time_segs,
                    "SELECT COUNT(*) FROM testTable WHERE tsMillis < 1e400",
                    expect_offload=False)


class TestLargeGroupBy:
    def test_90k_groups(self, time_segs):
        """dim x dim2 = 300*300 = 90000 combined keys — above the old
        65536 device cap; parity incl. group values."""
        eng = TpuOperatorExecutor()
        r = _parity(time_segs,
                    "SELECT dim, dim2, COUNT(*), SUM(val) FROM testTable "
                    "GROUP BY dim, dim2 ORDER BY dim, dim2 LIMIT 200",
                    engine=eng)
        assert len(r.rows) == 200
