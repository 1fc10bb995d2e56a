"""DATETRUNC on the host (pinot-common DateTimeFunctions.dateTrunc):
epoch milliseconds in and out, UTC, every unit upstream names, at the
edges of each unit and before 1970, against a plain implementation
written here over Python's `datetime` a value at a time; the three-
argument form with the input's TimeUnit; and what it does not support
failing with a clear error, never a wrong answer. The broker's
post-aggregation table and the result type read it too."""
import datetime as dt

import numpy as np
import pytest

from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              TableConfig)
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.executor import QueryExecutor
from pinot_tpu.query.expressions import Function, Identifier, Literal
from pinot_tpu.query.reduce import _result_type, eval_scalar
from pinot_tpu.query.transform import datetrunc, evaluate
from pinot_tpu.segment.creator import SegmentCreator
from pinot_tpu.segment.loader import load_segment

EPOCH = dt.datetime(1970, 1, 1)
UNITS = ["MILLISECOND", "SECOND", "MINUTE", "HOUR", "DAY", "WEEK", "MONTH",
         "QUARTER", "YEAR"]


def plain(unit: str, ms: int) -> int:
    """The start of `ms`'s unit, one value, through `datetime`."""
    t = EPOCH + dt.timedelta(milliseconds=ms)
    if unit == "MILLISECOND":
        start = t
    elif unit == "SECOND":
        start = t.replace(microsecond=0)
    elif unit == "MINUTE":
        start = t.replace(second=0, microsecond=0)
    elif unit == "HOUR":
        start = t.replace(minute=0, second=0, microsecond=0)
    elif unit == "DAY":
        start = t.replace(hour=0, minute=0, second=0, microsecond=0)
    elif unit == "WEEK":  # ISO: the Monday on or before
        day = t.replace(hour=0, minute=0, second=0, microsecond=0)
        start = day - dt.timedelta(days=day.weekday())
    elif unit == "MONTH":
        start = dt.datetime(t.year, t.month, 1)
    elif unit == "QUARTER":
        start = dt.datetime(t.year, 3 * ((t.month - 1) // 3) + 1, 1)
    else:
        start = dt.datetime(t.year, 1, 1)
    return (start - EPOCH) // dt.timedelta(milliseconds=1)


def edges() -> np.ndarray:
    """Instants at and beside the boundaries of every unit, after 1970
    and before it, and random ones from 1900 to 2100."""
    marks = [dt.datetime(1970, 1, 1), dt.datetime(1969, 12, 29),
             dt.datetime(1969, 12, 31, 23, 59, 59), dt.datetime(1900, 3, 1),
             dt.datetime(1968, 2, 29), dt.datetime(2000, 2, 29),
             dt.datetime(2016, 1, 1), dt.datetime(2015, 12, 28),
             dt.datetime(2016, 1, 4), dt.datetime(2016, 4, 1),
             dt.datetime(2016, 7, 1, 13), dt.datetime(2024, 12, 31, 23)]
    base = [(m - EPOCH) // dt.timedelta(milliseconds=1) for m in marks]
    out = [b + d for b in base for d in (-1, 0, 1, 999, 1000, 59_999, 60_000,
                                         3_599_999, 86_399_999)]
    rng = np.random.default_rng(45)
    out += rng.integers(-2_208_988_800_000, 4_102_444_800_000, 400).tolist()
    return np.array(out, dtype=np.int64)


@pytest.mark.parametrize("unit", UNITS)
def test_every_unit_truncates_as_the_calendar_does(unit):
    t = edges()
    got = datetrunc(unit, t)
    assert got.dtype == np.int64
    assert got.tolist() == [plain(unit, int(v)) for v in t]
    # the unit's name in any case, and truncating twice changes nothing
    assert (datetrunc(unit.lower(), t) == got).all()
    assert (datetrunc(unit, got) == got).all()


def test_a_week_starts_on_monday_before_and_after_1970():
    monday_1969 = (dt.datetime(1969, 12, 29) - EPOCH) \
        // dt.timedelta(milliseconds=1)
    assert datetrunc("WEEK", np.array([0, -1, monday_1969 - 1])).tolist() \
        == [monday_1969, monday_1969, monday_1969 - 7 * 86_400_000]


@pytest.mark.parametrize("input_unit,per", [
    ("SECONDS", 1000), ("MINUTES", 60_000), ("HOURS", 3_600_000),
    ("DAYS", 86_400_000), ("MILLISECONDS", 1)])
@pytest.mark.parametrize("unit", ["SECOND", "HOUR", "DAY", "MONTH", "YEAR"])
def test_the_input_time_unit_is_read_and_given_back(unit, input_unit, per):
    v = edges() // per
    want = [plain(unit, int(x) * per) // per for x in v]
    assert datetrunc(unit, v, input_unit).tolist() == want
    assert datetrunc(unit, v, input_unit.lower()).tolist() == want


def test_finer_input_units_convert_as_java_s_timeunit_does():
    # toward zero into milliseconds, as TimeUnit.convert does
    v = np.array([-1, 1_999_999, -3_600_000_000_001], dtype=np.int64)
    assert datetrunc("SECOND", v, "MICROSECONDS").tolist() \
        == [0, 1_000_000, -3_600_000_000_000]
    assert datetrunc("HOUR", np.array([5_400_000_000_000]),
                     "NANOSECONDS").tolist() == [3_600_000_000_000]


def test_whole_floats_are_read_and_anything_else_is_refused():
    assert datetrunc("HOUR", np.array([7_200_001.0])).tolist() == [7_200_000]
    for bad in ([1.5], [np.nan], ["2016-01-01"], [True]):
        with pytest.raises(ValueError, match="DATETRUNC"):
            datetrunc("HOUR", np.array(bad))


@pytest.mark.parametrize("args,match", [
    (("FORTNIGHT", "ts"), "unsupported unit"),
    (("HOUR", "ts", "WEEKS"), "unsupported input time unit"),
    (("HOUR", "ts", "MILLISECONDS", "UTC"), "time zone"),
    (("HOUR",), "DATETRUNC takes"),
    ((Identifier("u"), "ts"), "literal units"),
])
def test_what_is_not_supported_fails_loudly(args, match):
    class Rows:
        num_docs = 2

        @staticmethod
        def column(name):
            return np.array([0, 1], dtype=np.int64)
    expr = Function("datetrunc", tuple(
        a if not isinstance(a, str) else
        Identifier(a) if a == "ts" else Literal(a) for a in args))
    with pytest.raises(ValueError, match=match):
        evaluate(expr, Rows())


@pytest.fixture(scope="module")
def segs(tmp_path_factory):
    """(two segments of a raw LONG epoch-ms column, across 1969 -> 1970
    and in 2016, and a host; every ts they hold)."""
    tmp = tmp_path_factory.mktemp("datetrunc")
    schema = Schema("t", [FieldSpec("ts", DataType.LONG, FieldType.DIMENSION),
                          FieldSpec("host", DataType.STRING,
                                    FieldType.DIMENSION),
                          FieldSpec("v", DataType.INT, FieldType.METRIC)])
    tc = TableConfig("t")
    tc.indexing.no_dictionary_columns = ["ts", "v"]
    out, stamps = [], []
    for i, t0 in enumerate((-5 * 86_400_000, 1_451_606_400_000)):
        rng = np.random.default_rng(i)
        n = 3000
        stamps.append(t0 + rng.integers(0, 40 * 86_400_000, n))
        SegmentCreator(tc, schema).build({
            "ts": stamps[-1],
            "host": np.array([f"h{x}" for x in rng.integers(0, 5, n)],
                             object),
            "v": rng.integers(0, 100, n)}, str(tmp / f"s{i}"), f"s{i}")
        out.append(load_segment(str(tmp / f"s{i}")))
    return out, np.concatenate(stamps)


@pytest.mark.parametrize("unit", ["HOUR", "WEEK", "MONTH", "QUARTER"])
def test_a_group_by_datetrunc_on_the_host(segs, unit):
    sql = (f"SELECT DATETRUNC('{unit}', ts), COUNT(*), SUM(v) FROM t "
           f"GROUP BY DATETRUNC('{unit}', ts) ORDER BY DATETRUNC('{unit}', ts) "
           "LIMIT 100000")
    segments, ts = segs
    resp = QueryExecutor(segments, use_tpu=False).execute(sql)
    assert not resp.exceptions
    assert resp.result_table.column_types[0] == "LONG"
    want = {}
    for x in ts.tolist():
        want[plain(unit, x)] = want.get(plain(unit, x), 0) + 1
    assert [(r[0], r[1]) for r in resp.rows] == sorted(want.items())
    assert all(isinstance(r[0], int) for r in resp.rows)


def test_the_broker_evaluates_it_over_an_aggregate():
    ctx = QueryContext.from_sql(
        "SELECT DATETRUNC('DAY', MAX(ts)) FROM t")
    expr = ctx.select[0]
    assert _result_type(expr, ctx) == "LONG"
    got = eval_scalar(expr, {expr.args[1]: 1_451_696_400_123})
    assert got == plain("DAY", 1_451_696_400_123) and isinstance(got, int)
    date_trunc = Function("date_trunc", (Literal("HOUR"), Literal(7_200_001),
                                         Literal("MILLISECONDS")))
    assert eval_scalar(date_trunc, {}) == 7_200_000
