"""ISSUE 38: the broker finishes ONE grouped result held as columns a whole
column at a time (`reduce._columnar_table`), and gives the rows the dict
path gives on the same content, value for value, Python type for type
and in the same order. Every case reduces the columnar result and a
`GroupByResult(groups)` with the same groups, compares their rows by
`repr` (which tells 1 from 1.0 and True, and reads a NaN), and reads the
path each took from the `BrokerReduce` span and `broker_reduce{path=}`."""
import numpy as np
import pytest

from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.reduce import reduce_results
from pinot_tpu.query.results import CodedColumn, ExecutionStats, GroupByResult
from pinot_tpu.utils import tracing
from pinot_tpu.utils.metrics import MetricsRegistry

SELECT = ("SELECT k0, k1, SUM(m), COUNT(*) AS cnt, AVG(m) AS a, "
          "MINMAXRANGE(m), DISTINCTCOUNT(m) AS dc FROM t GROUP BY k0, k1")
D0 = [3, -1, 7, 0, 12]
D1 = ["b", "a", "d", "c", "e", "aa", "B"]


def reduce(sql: str, results: list):
    """(response, reducePath, reduceRows) of one traced reduce, whose
    `broker_reduce{path=}` meter has moved by one."""
    registry = MetricsRegistry("broker")
    with tracing.RequestTrace() as trace:
        with tracing.Scope("BrokerReduce"):
            resp = reduce_results(QueryContext.from_sql(sql), results,
                                  registry)
    span, = trace.to_dict()["children"]
    path = span["reducePath"]
    assert registry.meter("broker_reduce", labels={"path": path}) == 1
    return resp, path, span["reduceRows"]


def as_dict(r: GroupByResult) -> GroupByResult:
    """The same content built as the dict path's input."""
    copy = GroupByResult(key_columns=r.key_columns,
                         value_columns=r.value_columns)
    return GroupByResult(dict(copy.groups), r.stats,
                         r.num_groups_limit_reached)


def key_column(kind: str, values: list, rng):
    """One key column of `kind` whose rows read `values`."""
    if kind == "ndarray":
        return np.array(values)
    if kind == "list":
        return list(values)
    distinct = sorted(set(values), key=repr)
    if kind == "coded_dup":  # a union that holds a value twice
        distinct = distinct + distinct[::2]
    if kind == "coded_pad":  # values no row holds: not ranked, not judged
        distinct = [None] + distinct + [float("nan"), "zz"]
    ids = np.array([rng.choice([i for i, d in enumerate(distinct)
                                if d == v]) for v in values], np.int32)
    if kind == "coded_num":
        distinct = np.array(distinct)
    return CodedColumn(distinct, ids)


def columnar(kinds, n: int, seed: int, **edit) -> GroupByResult:
    """n groups of unique (k0, k1) over D0 x D1, in a random order, with
    ties in every aggregate: SUM over {0.0, -0.0, 1.5, 2.5}, AVG counts of
    0 (its -inf), DISTINCTCOUNT's sets (a list column, the base loop)."""
    rng = np.random.default_rng(seed)
    pairs = [(a, b) for a in D0 for b in D1]
    keys = [pairs[i] for i in rng.permutation(len(pairs))[:n]]
    k0 = [a for a, _ in keys]
    k1 = [b for _, b in keys]
    if kinds[0] == "coded_num":
        k1 = [D1.index(b) for b in k1]  # numbers in both key columns
    sums = rng.choice([0.0, -0.0, 1.5, 2.5], n)
    counts = rng.integers(0, 3, n)
    value_columns = [
        sums, counts, (sums * 3, counts),
        (-sums, sums + rng.integers(0, 2, n)),
        [set(rng.integers(0, 4, int(c)).tolist()) for c in counts]]
    key_columns = [key_column(kinds[0], k0, rng),
                   key_column(kinds[1], k1, rng)]
    for i, col in edit.items():
        (key_columns if i.startswith("k") else value_columns)[
            int(i[1:])] = col
    return GroupByResult(key_columns=key_columns,
                         value_columns=value_columns,
                         stats=ExecutionStats(num_docs_scanned=n),
                         num_groups_limit_reached=bool(seed % 2))


def same_rows(got, want):
    assert repr(got.rows) == repr(want.rows)
    assert [tuple(map(type, r)) for r in got.rows] \
        == [tuple(map(type, r)) for r in want.rows]
    assert got.num_groups_limit_reached == want.num_groups_limit_reached
    assert got.result_table.columns == want.result_table.columns


KINDS = [("ndarray", "list"), ("list", "coded_dup"),
         ("coded_num", "coded_num"), ("coded_num", "coded_dup"),
         ("ndarray", "coded_str"), ("list", "list"),
         ("coded_pad", "coded_pad")]
ORDERS = ["k0, k1", "k1 DESC, k0", "SUM(m) DESC, k1, k0",
          "cnt, k1 DESC, k0 DESC", "a DESC", "MINMAXRANGE(m), dc DESC", "dc",
          ""]
PAGES = [(0, 1000), (3, 5), (30, 10), (40, 10), (0, 0)]


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("kinds", KINDS)
def test_one_columnar_result_gives_the_dict_path_s_rows(kinds, order):
    for seed, (offset, limit) in enumerate(PAGES):
        r = columnar(kinds, 33, seed)
        sql = SELECT + (f" ORDER BY {order}" if order else "") \
            + f" LIMIT {offset}, {limit}"
        got, path, n = reduce(sql, [r])
        want, want_path, _ = reduce(sql, [as_dict(r)])
        assert (path, want_path, n) == ("columns", "rows", 33)
        same_rows(got, want)
        assert len(got.rows) == max(0, min(limit, 33 - offset))


def test_empty_results_are_dropped_before_the_one_is_counted():
    r = columnar(KINDS[0], 20, 2)
    empty = columnar(KINDS[0], 0, 1)
    sql = SELECT + " ORDER BY SUM(m), k1 LIMIT 100"
    got, path, n = reduce(sql, [empty, r, GroupByResult({})])
    assert (path, n) == ("columns", 20)
    assert repr(got.rows) == repr(reduce(sql, [as_dict(r)])[0].rows)
    # the flag of a result that held no group is carried all the same
    assert got.num_groups_limit_reached and not r.num_groups_limit_reached
    got, path, n = reduce(sql, [empty])
    assert (path, n, got.rows) == ("rows", 0, [])


def two_results():
    r = columnar(KINDS[2], 30, 3)
    halves = [GroupByResult(key_columns=[CodedColumn(c.values, c.ids[s])
                                         for c in r.key_columns],
                            value_columns=[
                                tuple(p[s] for p in v) if isinstance(v, tuple)
                                else v[s] for v in r.value_columns])
              for s in (slice(0, 12), slice(12, None))]
    return halves, [as_dict(h) for h in halves]


def one(r):
    return [r], [as_dict(r)]


def query(order: str, computed: str = "SUM(m)", before: str = "",
          having: str = "") -> str:
    return before + SELECT.replace("SUM(m),", computed + ",") + having \
        + f" ORDER BY {order} LIMIT 100"


FALLBACKS = {
    "two results": (query("k0, k1"), two_results),
    "a dict-built result": (
        query("k1, k0"), lambda: ([as_dict(columnar(KINDS[0], 30, 4))],) * 2),
    "HAVING": (query("k0", having=" HAVING SUM(m) > 1"),
               lambda: one(columnar(KINDS[1], 30, 5))),
    "a computed select": (query("k0, k1", computed="SUM(m) * 2"),
                          lambda: one(columnar(KINDS[3], 30, 6))),
    "gapfill": (query("k0, k1", before="SET gapfillTimeCol = k0; "),
                lambda: one(columnar(KINDS[4], 30, 7))),
    "a NaN sort key": (query("SUM(m), k0, k1"), lambda: one(columnar(
        KINDS[0], 30, 8, v0=np.where(np.arange(30) % 7, 1.0, np.nan)))),
    "a None key": (query("k1, k0"), lambda: one(columnar(
        KINDS[0], 7, 9, k1=["x", None, "y", "z", "a", "b", "c"]))),
    "mixed-type keys": (query("k1 DESC, k0"), lambda: one(columnar(
        KINDS[0], 7, 10, k1=["x", 1, "y", 2.5, "a", 4, "c"]))),
}


@pytest.mark.parametrize("case", list(FALLBACKS))
def test_what_the_columns_cannot_finish_takes_the_dict_path(case):
    sql, make = FALLBACKS[case]
    results, dict_built = make()
    got, path, n = reduce(sql, results)
    want, want_path, _ = reduce(sql, dict_built)
    assert (path, want_path) == ("rows", "rows")
    assert n == sum(r.num_rows for r in results) > 0
    same_rows(got, want)


def test_the_reduce_runs_untraced_and_without_a_registry():
    r = columnar(KINDS[0], 10, 11)
    sql = SELECT + " ORDER BY k0, k1 LIMIT 100"
    got = reduce_results(QueryContext.from_sql(sql), [r])
    assert got.rows == reduce(sql, [as_dict(r)])[0].rows
