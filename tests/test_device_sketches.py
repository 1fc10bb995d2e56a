"""Device-path sketch aggregations: HLL registers + histogram TDigest.

Ref: pinot-core query/aggregation/function/DistinctCountHLLAggregationFunction,
PercentileTDigestAggregationFunction — VERDICT r4 item 1 (BASELINE config #4
ran host-side python at 55k rows/s). The device kernel hashes i32 split
planes into HLL register max-scatters and scatter-adds histogram partials;
HLL registers must be BIT-IDENTICAL to the host sketch so partials merge.
"""
import numpy as np
import pytest

from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              TableConfig)
from pinot_tpu.query.aggregation.sketches import HyperLogLog, TDigest
from pinot_tpu.query.executor import QueryExecutor
from pinot_tpu.segment.creator import SegmentCreator
from pinot_tpu.segment.loader import load_segment


@pytest.fixture(scope="module")
def segments(tmp_path_factory):
    rng = np.random.default_rng(7)
    tmp = tmp_path_factory.mktemp("sketch_segs")
    schema = Schema("taxi", [
        FieldSpec("trip_id", DataType.LONG, FieldType.DIMENSION),
        FieldSpec("vendor", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("fare", DataType.FLOAT, FieldType.METRIC),
    ])
    tc = TableConfig(name="taxi")
    segs = []
    for i in range(3):
        n = 50_000
        cols = {
            "trip_id": rng.integers(0, 1 << 40, size=n),
            "vendor": rng.choice(["a", "b", "c"], size=n),
            "fare": rng.gamma(2.0, 10.0, size=n).astype(np.float32),
        }
        out = str(tmp / f"s{i}")
        SegmentCreator(tc, schema).build(cols, out, f"s{i}")
        segs.append(load_segment(out))
    return segs


@pytest.fixture(scope="module")
def executors(segments):
    return (QueryExecutor(segments, use_tpu=False),
            QueryExecutor(segments, use_tpu=True))


class TestDeviceHll:
    def test_registers_bit_identical(self, executors):
        host, dev = executors
        sql = "SELECT DISTINCTCOUNTHLL(trip_id) FROM taxi"
        rh = host.execute(sql)
        rd = dev.execute(sql)
        assert rh.rows == rd.rows  # same registers -> same estimate
        assert len(dev._tpu_engine.stager._block_cache) > 0, "device not engaged"

    def test_estimate_accuracy(self, executors, segments):
        _host, dev = executors
        true = len(np.unique(np.concatenate(
            [s.data_source("trip_id").values() for s in segments])))
        est = dev.execute(
            "SELECT DISTINCTCOUNTHLL(trip_id) FROM taxi").rows[0][0]
        assert abs(est - true) / true < 0.05

    def test_with_filter(self, executors):
        host, dev = executors
        sql = "SELECT DISTINCTCOUNTHLL(trip_id) FROM taxi WHERE fare > 25"
        assert host.execute(sql).rows == dev.execute(sql).rows

    def test_grouped_hll_falls_back_to_host(self, executors, segments):
        host, dev = executors
        sql = ("SELECT vendor, DISTINCTCOUNTHLL(trip_id) FROM taxi "
               "GROUP BY vendor")
        assert not dev.tpu_engine.supports(_ctx(sql))
        # and the full path still answers correctly via host fallback
        assert sorted(host.execute(sql).rows) == sorted(dev.execute(sql).rows)

    def test_hll_plus_scalar_aggs_one_kernel(self, executors):
        host, dev = executors
        sql = ("SELECT DISTINCTCOUNTHLL(trip_id), COUNT(*), SUM(fare) "
               "FROM taxi")
        rh, rd = host.execute(sql), dev.execute(sql)
        assert rh.rows[0][0] == rd.rows[0][0]
        assert rh.rows[0][1] == rd.rows[0][1]
        assert rd.rows[0][2] == pytest.approx(rh.rows[0][2], rel=2e-3)


class TestDeviceTDigest:
    def test_close_to_exact(self, executors, segments):
        _host, dev = executors
        fares = np.concatenate(
            [s.data_source("fare").values() for s in segments])
        exact = np.quantile(fares, 0.95)
        est = dev.execute(
            "SELECT PERCENTILETDIGEST95(fare) FROM taxi").rows[0][0]
        # error bound: digest error + one histogram bucket width
        width = (fares.max() - fares.min()) / 8192
        assert abs(est - exact) < max(0.02 * exact, 5 * width)

    def test_with_filter(self, executors, segments):
        _host, dev = executors
        fares = np.concatenate(
            [s.data_source("fare").values() for s in segments])
        exact = np.quantile(fares[fares > 10], 0.5)
        est = dev.execute(
            "SELECT PERCENTILETDIGEST(fare, 50) FROM taxi "
            "WHERE fare > 10").rows[0][0]
        assert abs(est - exact) < max(0.03 * exact, 1.0)


class TestHashParity:
    def test_device_and_host_hash_agree(self):
        """The jnp uint32 hash must match sketches.hash32_pair exactly."""
        import jax.numpy as jnp
        from pinot_tpu.ops.kernels import _fmix32 as jfmix
        from pinot_tpu.query.aggregation.sketches import (_split_planes,
                                                          hash32_pair)
        rng = np.random.default_rng(3)
        vals = rng.integers(-(1 << 50), 1 << 50, size=10_000)
        hi, lo = _split_planes(vals)
        h1, h2 = hash32_pair(hi, lo)
        jhi = jnp.asarray(hi.astype(np.int32)).astype(jnp.uint32)
        jlo = jnp.asarray(lo.astype(np.int32)).astype(jnp.uint32)
        jh1 = jfmix(jfmix(jlo ^ jnp.uint32(0x9E3779B9)) ^ jhi)
        jh2 = jfmix(jfmix(jhi ^ jnp.uint32(0x85EBCA77)) ^ jlo)
        np.testing.assert_array_equal(np.asarray(jh1), h1)
        np.testing.assert_array_equal(np.asarray(jh2), h2)


def _ctx(sql: str):
    from pinot_tpu.query.context import QueryContext
    return QueryContext.from_sql(sql)


class TestExactIntSums:
    """Bit-exact device SUM for int columns (VERDICT r4 weak #2): the
    'isum' slot accumulates 6-bit planes in i32 (ops/kernels.py
    _isum_slot; ref SumAggregationFunction's exact doubles)."""

    @pytest.fixture(scope="class")
    def int_segments(self, tmp_path_factory):
        rng = np.random.default_rng(11)
        tmp = tmp_path_factory.mktemp("isum_segs")
        schema = Schema("it", [
            FieldSpec("v", DataType.INT, FieldType.METRIC),
            FieldSpec("neg", DataType.INT, FieldType.METRIC),
        ])
        tc = TableConfig(name="it")
        tc.indexing.no_dictionary_columns = ["v", "neg"]
        segs, arrays = [], []
        for i in range(2):
            n = 300_000
            cols = {
                "v": rng.integers(0, 1 << 24, size=n, dtype=np.int64),
                "neg": rng.integers(-(1 << 24), 1 << 24, size=n,
                                    dtype=np.int64),
            }
            out = str(tmp / f"s{i}")
            SegmentCreator(tc, schema).build(cols, out, f"s{i}")
            segs.append(load_segment(out))
            arrays.append(cols)
        return segs, arrays

    def test_sum_bit_exact(self, int_segments):
        segs, arrays = int_segments
        host = QueryExecutor(segs, use_tpu=False)
        dev = QueryExecutor(segs, use_tpu=True)
        exact = sum(int(a["v"].sum()) for a in arrays)
        rh = host.execute("SELECT SUM(v) FROM it").rows[0][0]
        rd = dev.execute("SELECT SUM(v) FROM it").rows[0][0]
        assert float(rd) == float(rh) == float(exact)
        assert len(dev.tpu_engine.stager._block_cache) > 0

    def test_negative_and_filtered(self, int_segments):
        segs, arrays = int_segments
        host = QueryExecutor(segs, use_tpu=False)
        dev = QueryExecutor(segs, use_tpu=True)
        sql = "SELECT SUM(neg), AVG(v) FROM it WHERE v > 1000"
        rh = host.execute(sql).rows[0]
        rd = dev.execute(sql).rows[0]
        assert float(rd[0]) == float(rh[0])
        assert float(rd[1]) == pytest.approx(float(rh[1]), rel=1e-12)


class TestHllFilterOnSameColumn:
    """Review finding: HLL forces its no-dict int column into split-plane
    staging, so a filter on the SAME column must use vrange64 (not the
    'val:' block that won't exist)."""

    def test_hll_with_filter_on_hll_column(self, tmp_path):
        rng = np.random.default_rng(13)
        schema = Schema("h", [
            FieldSpec("x", DataType.LONG, FieldType.DIMENSION),
        ])
        tc = TableConfig(name="h")
        tc.indexing.no_dictionary_columns = ["x"]
        n = 50_000
        xs = rng.integers(0, 1 << 20, size=n, dtype=np.int64)
        out = str(tmp_path / "s0")
        SegmentCreator(tc, schema).build({"x": xs}, out, "s0")
        seg = load_segment(out)
        host = QueryExecutor([seg], use_tpu=False)
        dev = QueryExecutor([seg], use_tpu=True)
        sql = "SELECT DISTINCTCOUNTHLL(x) FROM h WHERE x > 5000"
        assert host.execute(sql).rows == dev.execute(sql).rows
        assert len(dev.tpu_engine.stager._block_cache) > 0

    def test_huge_longs_fall_back_and_stay_distinct(self, tmp_path):
        # |v| >= 2^55: device path must decline, and the HOST fold must
        # keep values differing only in the top byte distinct
        schema = Schema("h2", [
            FieldSpec("x", DataType.LONG, FieldType.DIMENSION),
        ])
        tc = TableConfig(name="h2")
        tc.indexing.no_dictionary_columns = ["x"]
        xs = np.array([k << 55 for k in range(1, 100)], dtype=np.int64)
        out = str(tmp_path / "s0")
        SegmentCreator(tc, schema).build({"x": xs}, out, "s0")
        seg = load_segment(out)
        dev = QueryExecutor([seg], use_tpu=True)
        est = dev.execute("SELECT DISTINCTCOUNTHLL(x) FROM h2").rows[0][0]
        assert abs(est - 99) / 99 < 0.1
