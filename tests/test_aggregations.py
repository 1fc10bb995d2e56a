"""New aggregation-function coverage: moments, covariance, with-time,
histogram, bool folds, distinct folds, theta/KLL sketches, MV family —
each parity-checked host-vs-device (where a device spec exists) and
against numpy oracles; wire serde round-trips for the new sketch types.
"""
import numpy as np
import pytest

from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              TableConfig, TableType)
from pinot_tpu.query.executor import QueryExecutor
from pinot_tpu.query.aggregation.sketches import KLLSketch, ThetaSketch
from pinot_tpu.server import datatable
from pinot_tpu.query.results import AggregationResult, ExecutionStats
from tests.queries.harness import build_segments

N = 3000


@pytest.fixture(scope="module")
def segs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("aggseg")
    schema = Schema("testTable", [
        FieldSpec("x", DataType.DOUBLE, FieldType.METRIC),
        FieldSpec("y", DataType.DOUBLE, FieldType.METRIC),
        FieldSpec("ts", DataType.INT, FieldType.DIMENSION),
        FieldSpec("grp", DataType.INT, FieldType.DIMENSION),
        FieldSpec("flag", DataType.INT, FieldType.DIMENSION),
        FieldSpec("tags", DataType.INT, FieldType.DIMENSION,
                  single_value=False),
    ])
    tc = TableConfig("testTable", TableType.OFFLINE)
    rng0 = np.random.default_rng(100)
    cols = []
    for i in range(2):
        rng = np.random.default_rng(100 + i)
        cols.append({
            "x": rng.normal(50, 10, N),
            "y": rng.normal(5, 2, N),
            "ts": rng.permutation(N).astype(np.int32) + i * N,
            "grp": rng.integers(0, 7, N).astype(np.int32),
            "flag": rng.integers(0, 2, N).astype(np.int32),
            "tags": [rng.integers(0, 50, rng.integers(1, 5)).tolist()
                     for _ in range(N)],
        })
    segs = build_segments(tmp, schema, tc, cols)
    all_cols = {k: (np.concatenate([np.asarray(c[k]) for c in cols])
                    if k != "tags" else
                    [t for c in cols for t in c["tags"]])
                for k in cols[0]}
    return segs, all_cols


def _one_row(segs, sql):
    cpu = QueryExecutor(segs, use_tpu=False)
    tpu = QueryExecutor(segs, use_tpu=True)
    a, b = cpu.execute(sql), tpu.execute(sql)
    assert not a.exceptions and not b.exceptions, (a.exceptions, b.exceptions)
    for x, y in zip(a.rows[0], b.rows[0]):
        if isinstance(x, float) and isinstance(y, float):
            assert abs(x - y) <= 1e-4 * max(1.0, abs(x)), (sql, a.rows, b.rows)
        else:
            assert x == y, (sql, a.rows, b.rows)
    return a.rows[0]


class TestMoments:
    def test_variance_stddev(self, segs):
        segs, cols = segs
        r = _one_row(segs,
                     "SELECT VAR_POP(x), VAR_SAMP(x), STDDEV_POP(x), "
                     "STDDEV_SAMP(x) FROM testTable")
        x = cols["x"]
        assert abs(r[0] - np.var(x)) < 1e-6 * np.var(x)
        assert abs(r[1] - np.var(x, ddof=1)) < 1e-6 * np.var(x)
        assert abs(r[2] - np.std(x)) < 1e-6 * np.std(x)
        assert abs(r[3] - np.std(x, ddof=1)) < 1e-6 * np.std(x)

    def test_skew_kurtosis(self, segs):
        segs, cols = segs
        r = _one_row(segs, "SELECT SKEWNESS(x), KURTOSIS(x) FROM testTable")
        x = cols["x"]
        m = x.mean()
        m2 = ((x - m) ** 2).mean()
        skew = ((x - m) ** 3).mean() / m2 ** 1.5
        kurt = ((x - m) ** 4).mean() / m2 ** 2 - 3
        assert abs(r[0] - skew) < 1e-3
        assert abs(r[1] - kurt) < 1e-3

    def test_variance_group_by(self, segs):
        segs, cols = segs
        cpu = QueryExecutor(segs, use_tpu=False)
        tpu = QueryExecutor(segs, use_tpu=True)
        sql = ("SELECT grp, VAR_POP(x), STDDEV_SAMP(x) FROM testTable "
               "GROUP BY grp ORDER BY grp LIMIT 10")
        a, b = cpu.execute(sql), tpu.execute(sql)
        assert len(a.rows) == len(b.rows) == 7
        for ra, rb in zip(a.rows, b.rows):
            assert ra[0] == rb[0]
            assert abs(ra[1] - rb[1]) < 1e-4 * max(1.0, abs(ra[1]))
        x, g = cols["x"], cols["grp"]
        for row in a.rows:
            want = np.var(x[g == row[0]])
            assert abs(row[1] - want) < 1e-6 * max(1.0, want)

    def test_variance_filtered(self, segs):
        segs, cols = segs
        r = _one_row(segs, "SELECT VAR_POP(x) FILTER (WHERE flag = 1), "
                           "COUNT(*) FROM testTable")
        x, f = cols["x"], cols["flag"]
        want = np.var(x[f == 1])
        assert abs(r[0] - want) < 1e-6 * want


class TestCovariance:
    def test_covar(self, segs):
        segs, cols = segs
        r = _one_row(segs,
                     "SELECT COVAR_POP(x, y), COVAR_SAMP(x, y) FROM testTable")
        x, y = cols["x"], cols["y"]
        pop = np.cov(x, y, ddof=0)[0, 1]
        samp = np.cov(x, y, ddof=1)[0, 1]
        assert abs(r[0] - pop) < 1e-6 * max(1.0, abs(pop))
        assert abs(r[1] - samp) < 1e-6 * max(1.0, abs(samp))

    def test_covar_group_by(self, segs):
        segs, cols = segs
        cpu = QueryExecutor(segs, use_tpu=False)
        resp = cpu.execute("SELECT grp, COVAR_POP(x, y) FROM testTable "
                           "GROUP BY grp ORDER BY grp LIMIT 10")
        x, y, g = cols["x"], cols["y"], cols["grp"]
        for row in resp.rows:
            sel = g == row[0]
            want = np.cov(x[sel], y[sel], ddof=0)[0, 1]
            assert abs(row[1] - want) < 1e-6 * max(1.0, abs(want))


class TestWithTime:
    def test_first_last(self, segs):
        segs, cols = segs
        r = _one_row(segs, "SELECT FIRSTWITHTIME(x, ts, 'DOUBLE'), "
                           "LASTWITHTIME(x, ts, 'DOUBLE') FROM testTable")
        x, ts = cols["x"], cols["ts"]
        assert abs(r[0] - x[np.argmin(ts)]) < 1e-9
        assert abs(r[1] - x[np.argmax(ts)]) < 1e-9

    def test_last_group_by(self, segs):
        segs, cols = segs
        cpu = QueryExecutor(segs, use_tpu=False)
        resp = cpu.execute("SELECT grp, LASTWITHTIME(x, ts, 'DOUBLE') "
                           "FROM testTable GROUP BY grp ORDER BY grp LIMIT 10")
        x, ts, g = cols["x"], cols["ts"], cols["grp"]
        for row in resp.rows:
            sel = np.nonzero(g == row[0])[0]
            want = x[sel[np.argmax(ts[sel])]]
            assert abs(row[1] - want) < 1e-9


class TestHistogramBoolDistinct:
    def test_histogram(self, segs):
        segs, cols = segs
        r = _one_row(segs,
                     "SELECT HISTOGRAM(x, 0, 100, 10) FROM testTable")
        want, _ = np.histogram(cols["x"], bins=np.linspace(0, 100, 11))
        assert [int(v) for v in r[0]] == want.tolist()

    def test_bool_folds(self, segs):
        segs, cols = segs
        r = _one_row(segs,
                     "SELECT BOOL_AND(flag), BOOL_OR(flag) FROM testTable")
        assert r[0] == bool(np.all(cols["flag"])) \
            and r[1] == bool(np.any(cols["flag"]))
        r2 = _one_row(segs, "SELECT BOOL_AND(flag), BOOL_OR(flag) "
                            "FROM testTable WHERE flag = 1")
        assert r2[0] is True and r2[1] is True

    def test_distinct_folds(self, segs):
        segs, cols = segs
        r = _one_row(segs,
                     "SELECT DISTINCTSUM(grp), DISTINCTAVG(grp) FROM testTable")
        u = np.unique(cols["grp"])
        assert abs(r[0] - u.sum()) < 1e-9
        assert abs(r[1] - u.mean()) < 1e-9


class TestSketches:
    def test_theta(self, segs):
        segs, cols = segs
        r = _one_row(segs,
                     "SELECT DISTINCTCOUNTTHETASKETCH(ts) FROM testTable")
        true = len(np.unique(cols["ts"]))
        assert abs(r[0] - true) <= 0.05 * true

    def test_kll(self, segs):
        segs, cols = segs
        r = _one_row(segs, "SELECT PERCENTILEKLL(x, 90) FROM testTable")
        want = np.quantile(cols["x"], 0.9)
        assert abs(r[0] - want) < 0.05 * abs(want)
        r2 = _one_row(segs, "SELECT PERCENTILEKLL50(x) FROM testTable")
        assert abs(r2[0] - np.quantile(cols["x"], 0.5)) < 0.05 * 50

    def test_sketch_serde_roundtrip(self):
        rng = np.random.default_rng(0)
        t = ThetaSketch(1024)
        t.add_array(rng.integers(0, 10**6, 50000))
        k = KLLSketch(200)
        k.add_array(rng.random(50000))
        r = AggregationResult([t, k], ExecutionStats())
        buf = datatable.serialize_results([r])
        [out], exc, _ = datatable.deserialize_results(buf)
        assert not exc
        t2, k2 = out.intermediates
        assert t2.estimate() == t.estimate()
        assert abs(k2.quantile(0.5) - k.quantile(0.5)) < 1e-9
        # merged across the wire stays usable
        assert t2.merge(t).estimate() == t.estimate()


class TestMVFamily:
    def test_mv_aggs(self, segs):
        segs, cols = segs
        r = _one_row(segs,
                     "SELECT SUMMV(tags), MINMV(tags), MAXMV(tags), "
                     "AVGMV(tags), MINMAXRANGEMV(tags), "
                     "DISTINCTCOUNTMV(tags), COUNTMV(tags) FROM testTable")
        flat = np.concatenate([np.asarray(t) for t in cols["tags"]])
        assert abs(r[0] - flat.sum()) < 1e-6 * abs(flat.sum())
        assert r[1] == flat.min() and r[2] == flat.max()
        assert abs(r[3] - flat.mean()) < 1e-9
        assert r[4] == flat.max() - flat.min()
        assert r[5] == len(np.unique(flat))
        assert r[6] == len(flat)

    def test_mv_group_by(self, segs):
        segs, cols = segs
        cpu = QueryExecutor(segs, use_tpu=False)
        resp = cpu.execute("SELECT grp, SUMMV(tags) FROM testTable "
                           "GROUP BY grp ORDER BY grp LIMIT 10")
        g = np.asarray(cols["grp"])
        for row in resp.rows:
            want = sum(sum(t) for t, gi in zip(cols["tags"], g)
                       if gi == row[0])
            assert abs(row[1] - want) < 1e-6 * max(1.0, abs(want))

    def test_mv_with_filter(self, segs):
        segs, cols = segs
        r = _one_row(segs,
                     "SELECT SUMMV(tags) FROM testTable WHERE flag = 1")
        g = np.asarray(cols["flag"])
        want = sum(sum(t) for t, f in zip(cols["tags"], g) if f == 1)
        assert abs(r[0] - want) < 1e-6 * max(1.0, abs(want))


class TestDeviceSlotColumns:
    """`from_device_slot_columns` (a folded GROUP BY's whole result at
    once) against a loop of `from_device_slots`, element for element and
    type for type: counts past 2^24 from an integer word, sums / mins /
    maxes from the f32 or f64 word widened."""

    COUNTS = [0, 1, 360, (1 << 24) + 1, (1 << 31) - 1]
    VALUES = [0.0, -2.5, 0.1, 16777217.0, 3.0e38]

    @staticmethod
    def rows_of(cols):
        """What the result's `.groups` holds a row for this function."""
        from pinot_tpu.query.results import column_values
        if isinstance(cols, tuple):
            return list(zip(*map(column_values, cols)))
        return column_values(cols)

    @staticmethod
    def looped(fn, slots):
        ops = list(slots)
        return [fn.from_device_slots(dict(zip(ops, vals)))
                for vals in zip(*(slots[op].tolist() for op in ops))]

    @staticmethod
    def kinds(v):
        return tuple(map(type, v)) if isinstance(v, tuple) else type(v)

    @pytest.mark.parametrize("words", [np.float32, np.float64])
    @pytest.mark.parametrize("name,ops", [
        ("count", ("count",)), ("sum", ("sum",)), ("min", ("min",)),
        ("max", ("max",)), ("avg", ("sum", "count")),
        ("minmaxrange", ("min", "max"))])
    def test_vectorised_equals_the_loop(self, name, ops, words):
        from pinot_tpu.query.aggregation.base import get_aggregation
        from pinot_tpu.query.expressions import Identifier
        fn = get_aggregation(name, (Identifier("m"),))
        assert tuple(fn.device_spec.ops) == ops
        ints = np.int32 if words == np.float32 else np.int64
        values = np.array(self.VALUES, words)
        slots = {op: np.array(self.COUNTS, ints) if op == "count"
                 else values[::-1] if op == "max" else values for op in ops}
        cols = fn.from_device_slot_columns(slots)
        for col in cols if isinstance(cols, tuple) else (cols,):
            assert isinstance(col, np.ndarray) and col.dtype in (
                np.int64, np.float64)
        got, want = self.rows_of(cols), self.looped(fn, slots)
        assert got == want
        assert [self.kinds(v) for v in got] == [self.kinds(v) for v in want]
        if "count" in ops:
            flat = [v[-1] if isinstance(v, tuple) else v for v in got]
            assert flat[3] == (1 << 24) + 1 and type(flat[3]) is int

    def test_a_count_in_the_value_dtype_rounds_as_the_loop_does(self):
        from pinot_tpu.query.aggregation.base import get_aggregation
        fn = get_aggregation("count", ())
        words = np.array([0.0, 0.5, 1.5, 2.5, 359.9999, 16777216.0],
                         np.float32)
        got = fn.from_device_slot_columns({"count": words})
        assert got.dtype == np.int64
        assert got.tolist() == [fn.from_device_slots({"count": w})
                                for w in words] == [0, 0, 2, 2, 360, 1 << 24]

    def test_the_base_default_serves_a_sketch_and_a_tuple_function(self):
        from pinot_tpu.query.aggregation.base import get_aggregation
        from pinot_tpu.query.aggregation.sketches import HyperLogLog
        from pinot_tpu.query.expressions import Identifier
        hll = get_aggregation("distinctcounthll", (Identifier("m"),))
        op, = hll.device_spec.ops
        registers = np.zeros((3, 1 << hll._log2m()), np.uint8)
        registers[1, 5] = 3
        cols = hll.from_device_slot_columns({op: registers})
        assert isinstance(cols, list) and len(cols) == 3
        assert all(isinstance(h, HyperLogLog) for h in cols)
        assert cols[1].registers[5] == 3 and not cols[0].registers.any()
        var = get_aggregation("variance", (Identifier("m"),))
        slots = {o: np.array([2.0, 3.0], np.float32)
                 for o in var.device_spec.ops}
        cols = var.from_device_slot_columns(slots)
        assert cols == [var.from_device_slots(
            {o: np.float32(v) for o in slots}) for v in (2.0, 3.0)]
        assert isinstance(cols[0], tuple)

    @pytest.mark.parametrize("name,ops", [
        ("count", ("count",)), ("sum", ("sum",)), ("min", ("min",)),
        ("max", ("max",)), ("avg", ("sum", "count")),
        ("minmaxrange", ("min", "max"))])
    def test_final_column_equals_extract_final_a_row(self, name, ops):
        """ISSUE 38: the broker's finals a whole column at a time give,
        row for row, `extract_final`'s value and Python type: AVG's
        `-inf` where the count is 0, MINMAXRANGE's `nan` from inf - inf."""
        from pinot_tpu.query.aggregation.base import get_aggregation
        from pinot_tpu.query.expressions import Identifier
        from pinot_tpu.query.results import column_values
        fn = get_aggregation(name, (Identifier("m"),))
        values = np.array(self.VALUES + [np.inf, -np.inf], np.float64)
        slots = {op: np.array(self.COUNTS + [2, 0], np.int64) if op == "count"
                 else values[::-1] if op == "max" else values for op in ops}
        if name == "minmaxrange":
            slots["max"][-1] = np.inf  # [inf, inf]: inf - inf
        cols = fn.from_device_slot_columns(slots)
        final = fn.final_column(cols)
        assert isinstance(final, np.ndarray)
        got = column_values(final)
        want = [fn.extract_final(v) for v in self.rows_of(cols)]
        assert repr(got) == repr(want)
        assert list(map(type, got)) == list(map(type, want))
        if name == "avg":
            assert got[0] == got[-1] == -np.inf  # counts of 0
        # a column the wire brought as a list takes the base's loop
        listed = tuple(map(column_values, cols)) if isinstance(cols, tuple) \
            else column_values(cols)
        assert repr(column_values(fn.final_column(listed))) == repr(want)

    def test_final_column_loops_extract_final_for_a_sketch(self):
        from pinot_tpu.query.aggregation.base import get_aggregation
        from pinot_tpu.query.expressions import Identifier
        hll = get_aggregation("distinctcounthll", (Identifier("m"),))
        op, = hll.device_spec.ops
        registers = np.zeros((3, 1 << hll._log2m()), np.uint8)
        registers[1, 5] = 3
        registers[2, :7] = 1
        cols = hll.from_device_slot_columns({op: registers})
        got = hll.final_column(cols)
        assert isinstance(got, list)
        assert got == [hll.extract_final(h) for h in cols]
        assert list(map(type, got)) == [type(hll.extract_final(cols[0]))] * 3
        assert got[0] == 0 < got[1] < got[2]
