"""A GROUP BY answer the broker reduced as columns is held as columns
(`ResultTable.held`) and encoded a column at a time: every case reduces a
generated columnar result and a `GroupByResult(groups)` with the same
groups, and requires the held table's HTTP table body to be byte for byte
`json.dumps(to_dict(), default=str)` of the table the dict path built,
and its rows, made on first read, to be that table's rows value for
value and type for type. The guard below it shows that the encode makes
no container a row: no generation-0 collection runs inside it."""
import gc
import json
from decimal import Decimal

import numpy as np
import pytest

from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.reduce import BrokerResponse, ResultTable, reduce_results
from pinot_tpu.query.results import CodedColumn, GroupByResult

N = 40
SELECT = ("SELECT k0, k1, SUM(m), COUNT(*) AS cnt, AVG(m) AS a "
          "FROM t GROUP BY k0, k1 ORDER BY {order} LIMIT {offset}, {limit}")
TEXTS = ["a, b", 'q"uote', "back\\slash", "naïve", "日本語", "tab\tnl\n", "",
         "], [", " ", "plain"]
FLOATS = [float("nan"), float("inf"), -float("inf"), -0.0, 0.0, 1e16, 1e-7,
          0.1, 1.5e300, 5e-324, -2.5, 123456789.125]


def texts(rng, n):
    return [TEXTS[i % len(TEXTS)] + str(i) for i in rng.permutation(n)]


def coded(values, rng, n, pad):
    """A CodedColumn whose rows read `values` (all distinct), over a
    dictionary that also holds values no row uses (`pad`)."""
    table = list(pad) + list(values)
    order = rng.permutation(len(table))
    at = np.empty(len(table), np.int64)
    at[order] = np.arange(len(table))
    ids = at[len(pad) + np.arange(n)]
    return [table[i] for i in order], ids.astype(np.int32)


def coded_list(rng, n):
    values, ids = coded(texts(rng, n), rng, n, ["unused, x", "zz\\"])
    return CodedColumn(values, ids)


def coded_strings(rng, n):
    values, ids = coded(texts(rng, n), rng, n, ["never", "ø"])
    return CodedColumn(np.array(values, object), ids)


def coded_numbers(rng, n):
    values, ids = coded(rng.permutation(n) * 3 - 7, rng, n, [10**6, -10**6])
    return CodedColumn(np.array(values, np.int64), ids)


def float_sums(rng, n):
    return np.array([FLOATS[i % len(FLOATS)] for i in rng.permutation(n)])


#: name -> (the key column k0 a case holds, its SUM column); k1 is unique,
#: so every (k0, k1) is a group of its own
CASES = {
    "int64": (lambda rng, n: rng.integers(-2**62, 2**62, n),
              lambda rng, n: rng.integers(-10**12, 10**12, n)),
    "int8_keys": (lambda rng, n: rng.integers(-128, 127, n).astype(np.int8),
                  lambda rng, n: rng.integers(0, 2**15, n).astype(np.int16)),
    "int16_uint16": (lambda rng, n: rng.integers(-2**15, 2**15, n)
                     .astype(np.int16),
                     lambda rng, n: rng.integers(0, 2**16, n)
                     .astype(np.uint16)),
    "bools": (lambda rng, n: rng.integers(0, 2, n).astype(bool),
              lambda rng, n: rng.integers(0, 2, n).astype(bool)),
    "float_specials": (lambda rng, n: rng.integers(0, 5, n), float_sums),
    "float32": (lambda rng, n: (rng.random(n) * 100).astype(np.float32),
                lambda rng, n: np.float32(
                    [0.1, -0.0, float("nan"), float("inf"), 1e16, 1e-7, 3e38]
                )[rng.integers(0, 7, n)]),
    "string_list": (texts, lambda rng, n: rng.random(n) * 1e6),
    "coded_list": (coded_list, lambda rng, n: rng.integers(0, 36_000, n)
                   .astype(np.float64)),
    "coded_ndarray_strings": (coded_strings, float_sums),
    "coded_ndarray_numbers": (coded_numbers, lambda rng, n: rng.random(n)),
    "list_of_numbers": (lambda rng, n: [int(v) if v % 2 else v + 0.5
                                        for v in rng.permutation(n) * 2],
                        lambda rng, n: [FLOATS[i % len(FLOATS)]
                                        for i in range(n)]),
    "list_default_str": (lambda rng, n: [t.encode() for t in texts(rng, n)],
                         lambda rng, n: [Decimal(i) / 8 for i in range(n)]),
}
ORDERS = {"int64": "k0 DESC, k1", "bools": "k0, k1 DESC",
          "coded_list": "k0 DESC", "coded_ndarray_numbers": "k0",
          "list_of_numbers": "k0", "list_default_str": "k0 DESC"}
PAGES = {"all": (0, 1000), "offset_limit": (3, 5), "limit_0": (0, 0),
         "past_the_end": (N + 5, 10)}


def columnar(case: str, seed: int) -> GroupByResult:
    rng = np.random.default_rng(seed)
    key, sums = CASES[case]
    counts = rng.integers(0, 3, N)  # AVG's count of 0 reads -inf
    return GroupByResult(
        key_columns=[key(rng, N), rng.permutation(N) * 5],
        value_columns=[sums(rng, N), counts,
                       (rng.integers(0, 1000, N).astype(np.float64), counts)])


def as_dict(r: GroupByResult) -> GroupByResult:
    """The same groups built as the dict path's input."""
    return GroupByResult(dict(r.groups), r.stats)


@pytest.mark.parametrize("page", list(PAGES))
@pytest.mark.parametrize("case", list(CASES))
def test_a_held_table_encodes_the_bytes_its_rows_dump_to(case, page):
    offset, limit = PAGES[page]
    ctx = QueryContext.from_sql(SELECT.format(
        order=ORDERS.get(case, "k1"), offset=offset, limit=limit))
    r = columnar(case, seed=sorted(CASES).index(case))
    got = reduce_results(ctx, [r])
    want = reduce_results(ctx, [as_dict(r)])
    assert got.result_table.data is not None and got.encode_path == "columns"
    assert want.encode_path == "rows"
    body = got.encode_table()  # before anything reads its rows
    assert got.result_table._rows is None
    assert body == json.dumps(want.result_table.to_dict(),
                              default=str).encode() == want.encode_table()
    assert repr(got.rows) == repr(want.rows)
    assert [tuple(map(type, row)) for row in got.rows] \
        == [tuple(map(type, row)) for row in want.rows]
    assert len(got.rows) == max(0, min(limit, N - offset))
    # the rows are made once and kept; the encode still writes columns
    assert got.result_table.rows is got.result_table.rows
    assert got.encode_table() == body


def dgb1_answer(rows: bool) -> BrokerResponse:
    """`tsbs_dgb1_c1`'s answer: 12 hours x 4,000 hosts, one int, one coded
    string, one double and one long column, in a shuffled order; held as
    columns, or as the rows that form gives."""
    i = np.arange(48_000)
    table = ResultTable.held(
        ["ts_hour", "hostname", "sum(usage_user)", "count(*)"],
        ["LONG", "STRING", "DOUBLE", "LONG"],
        [458_000 + i // 4000,
         CodedColumn(np.array([f"host_{h}" for h in range(4000)], object),
                     (i % 4000).astype(np.int16)),
         np.random.default_rng(3).integers(0, 36_000, 48_000) * 1.0,
         np.full(48_000, 360)],
        np.random.default_rng(4).permutation(48_000))
    if rows:
        table = ResultTable(table.columns, table.column_types, table.rows)
    return BrokerResponse(result_table=table)


@pytest.mark.parametrize("rows", [False, True], ids=["columns", "rows"])
def test_the_columns_encode_runs_no_collection(rows):
    """No generation-0 collection inside the encode of the held table:
    it makes no container a row. The same table built from rows runs
    them (48,000 row lists), so the guard sees what it guards."""
    resp = dgb1_answer(rows)
    assert resp.encode_path == ("rows" if rows else "columns")
    collections = []

    def count(phase, info):
        if phase == "start":
            collections.append(info["generation"])
    assert gc.isenabled() and gc.get_threshold()[0] > 0
    gc.collect()
    gc.callbacks.append(count)
    try:
        body = resp.encode_table()
    finally:
        gc.callbacks.remove(count)
    assert len(body) > 1_700_000
    assert bool(collections) == rows
