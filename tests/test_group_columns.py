"""A grouped result as columns (ISSUE 36): `GroupByResult` holds key and
value columns, the DataTable's tag `2` carries them, and the round trip
is the identity on `.groups`: the same keys in the same order, the same
Python types, the same intermediates, whatever the producer (the folded
device table, a dict-building host path) and whatever the column holds
(numbers, strings, sketches, Decimal, None, mixed types). A payload that
is cut short or lies about a length raises; nothing reads past it."""
import pickle
from collections import Counter
from decimal import Decimal
from types import SimpleNamespace

import numpy as np
import pytest

from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.ops.plan_ir import DevicePlan
from pinot_tpu.query.aggregation.sketches import HyperLogLog, TDigest
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.reduce import reduce_results
from pinot_tpu.query.results import (
    CodedColumn, ExecutionStats, GroupByResult)
from pinot_tpu.server import datatable as dt

STATS = ExecutionStats(11, 22, 33, 4, 3, 66, 7)
HOSTS = np.array(["host_0", "host_1", "hôst_2", "host_3"], dtype=object)
HOURS = np.array([403224, 403225, 403226], dtype=np.int64)


def folded(sql: str, agg_ops, mappings, decode, slots: dict, words,
           compact: bool = False) -> GroupByResult:
    """`engine._assemble_folded` over a hand-made `[slots, G]` table:
    `slots` maps a plan slot's index to its G values, `words` is the
    fetched row's integer dtype (int32 carries f32 words, int64 f64)."""
    vdt = np.float32 if words == np.int32 else np.float64
    G = len(next(iter(slots.values())))
    table = np.zeros((len(agg_ops), G), words)
    for j, vals in slots.items():
        table[j] = np.asarray(vals, words) if agg_ops[j][0] == "count" \
            else np.asarray(vals, vdt).view(words)
    segments = [SimpleNamespace(num_docs=1000), SimpleNamespace(num_docs=500)]
    matched = np.array([7, 5], words)
    ctx = QueryContext.from_sql(sql)
    plan = DevicePlan(
        filter_ir=None, leaves=(), value_irs=(("col", "m"),),
        agg_ops=tuple(agg_ops), group_cols=tuple(
            str(e) for e in ctx.group_by),
        group_strides=(1,) * len(ctx.group_by), num_groups=G,
        group_compact=compact)
    engine = object.__new__(TpuOperatorExecutor)
    engine._metrics = None
    result, = engine._assemble_folded(
        segments, ctx, plan, np.concatenate([table.reshape(-1), matched]),
        2, mappings, {"G": G, "decode": decode})
    assert result.key_columns is not None and result._groups is None
    assert result.stats.num_docs_scanned == 12
    assert result.stats.total_docs == 1500
    return result


def folded_sum_count(words=np.int32) -> GroupByResult:
    # G = 4 hours (3 real, a padded digit) x 4 hosts; group 5 is absent
    counts = [360, 180, 0, 360, 1, 0, 360, (1 << 24) + 1,
              2, 3, 4, 5, 0, 0, 0, 0]
    sums = [c * 1.5 for c in counts]
    return folded(
        "SELECT SUM(m), COUNT(*) FROM t GROUP BY ts_hour, hostname",
        [("sum", 0, None), ("count", None, None)],
        [{"sum": 0}, {"count": 1}],
        ((4, 1), (4, 4), (HOURS, HOSTS)), {0: sums, 1: counts}, words)


def folded_avg_range() -> GroupByResult:
    counts = [2, 0, 3, 1]
    return folded(
        "SELECT AVG(m), MINMAXRANGE(m), MAX(m) FROM t GROUP BY hostname",
        [("sum", 0, None), ("count", None, None), ("min", 0, None),
         ("max", 0, None)],
        [{"sum": 0, "count": 1}, {"min": 2, "max": 3}, {"max": 3}],
        ((1,), (4,), (HOSTS,)),
        {0: [0.1, 0.0, 1e300, -2.5], 1: counts, 2: [-1.0, 0.0, 2.0, -2.5],
         3: [7.0, 0.0, 9.5, -2.5]}, np.int64)


def folded_compact() -> GroupByResult:
    # a compacted plan's decode: a column a group expression
    return folded(
        "SELECT COUNT(*) FROM t GROUP BY a, b",
        [("count", None, None)], [{"count": 0}],
        [["x", "x", "y"], [1, 2, 1]], {0: [4, 0, 9, 0]}, np.int32,
        compact=True)


def sketches() -> GroupByResult:
    hll = HyperLogLog(8)
    hll.add_array(np.arange(100))
    td = TDigest(100.0)
    td.add_array(np.linspace(0.0, 1.0, 50))
    return GroupByResult({("a",): [hll, td], ("b",): [HyperLogLog(8), td]},
                         STATS)


CASES = {
    "folded_f32_words": folded_sum_count,
    "folded_f64_words": lambda: folded_sum_count(np.int64),
    "folded_avg_and_minmaxrange": folded_avg_range,
    "folded_compact_plan": folded_compact,
    "host_dict": lambda: GroupByResult(
        {("b", 2): [3.0, 4], ("a", 1): [1.0, 2], ("a", 3): [0.5, 7]},
        STATS),
    "avg_and_minmaxrange_tuples": lambda: GroupByResult(
        {(1,): [(2.5, 2), (0.0, 9.0)], (2,): [(7.0, 1), (7.0, 7.0)]}, STATS),
    "grouped_hll_and_tdigest": sketches,
    "string_int_and_float_bucket_keys": lambda: GroupByResult(
        {(86400.0, "host_1", 7): [1], (0.0, "host_0", -7): [2],
         (86400.0, "hôst_2", 1 << 40): [3]}, STATS),
    "bool_key": lambda: GroupByResult(
        {(True, "a"): [1.0], (False, "a"): [2.0]}, STATS),
    "none_key": lambda: GroupByResult(
        {(None,): [1], ("x",): [2]}, STATS),
    "mixed_type_key_column": lambda: GroupByResult(
        {(1,): [1], ("1",): [2], (1.5,): [3], (True,): [4]}, STATS),
    "decimal_intermediate": lambda: GroupByResult(
        {("a",): [Decimal("12345678901234567890.123")],
         ("b",): [Decimal("-0.5")]}, STATS),
    "tuples_of_unequal_length_and_nested": lambda: GroupByResult(
        {(1,): [(1.0, 2), ((1, 2), 3.0)], (2,): [(1.0, 2, 3), ((4, 5), 6.0)]},
        STATS),
    "lists_stay_lists_and_empty_tuples": lambda: GroupByResult(
        {(1,): [[1, 2.0], ()], (2,): [[], ()]}, STATS),
    "int_past_63_bits_in_a_list_of_ints": lambda: GroupByResult(
        {(1,): [1.0], (1 << 62,): [2.0]}, STATS),
    "zero_groups_dict": lambda: GroupByResult({}, STATS),
    "zero_groups_folded": lambda: folded(
        "SELECT SUM(m), COUNT(*) FROM t GROUP BY hostname",
        [("sum", 0, None), ("count", None, None)],
        [{"sum": 0}, {"count": 1}], ((1,), (4,), (HOSTS,)),
        {0: [0.0] * 4, 1: [0] * 4}, np.int32),
    "one_group": lambda: GroupByResult({("only", 1): [2.0, 3]}, STATS),
    "no_aggregate": lambda: GroupByResult({("a",): [], ("b",): []}, STATS),
    "groups_limit_reached": lambda: GroupByResult(
        {("a",): [1]}, STATS, num_groups_limit_reached=True),
}


def shape(v):
    """The Python types of a value, through tuples and lists."""
    if isinstance(v, (tuple, list)):
        return (type(v).__name__,) + tuple(shape(x) for x in v)
    return type(v).__name__


def rows_of(result: GroupByResult) -> list:
    """A result's groups in order, every key and intermediate as typed
    bytes (a sketch has no `==`), beside its Python types."""
    return [(key, shape(key), shape(inters), dt.serialize_value(inters))
            for key, inters in result.groups.items()]


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_round_trip_is_the_identity_on_groups(case):
    result = CASES[case]()
    [back], exceptions, extra = dt.deserialize_results(
        dt.serialize_results([result]))
    assert not exceptions and extra is None
    assert back.key_columns is not None and back._groups is None
    assert rows_of(back) == rows_of(result)
    assert back.stats == result.stats
    assert back.num_groups_limit_reached == result.num_groups_limit_reached
    assert type(back.num_groups_limit_reached) is bool
    if "hll" not in case:
        assert back.groups == result.groups and back == result
    # what came off the wire goes back on it byte for byte (the tier-2
    # cache re-serializes what it was handed)
    again = dt.serialize_results([back])
    assert again == dt.serialize_results([result])
    # the in-process caches pickle a result, built dict or not
    assert rows_of(pickle.loads(pickle.dumps(back))) == rows_of(result)


def test_a_folded_result_gives_what_a_loop_of_from_device_slots_gave():
    groups = folded_sum_count().groups
    assert list(groups)[:4] == [(403224, "host_0"), (403224, "host_1"),
                                (403224, "host_3"), (403225, "host_0")]
    # the sum is the f32 word widened; the count an integer past 2^24
    assert groups[(403225, "host_3")] == [
        float(np.float32(((1 << 24) + 1) * 1.5)), (1 << 24) + 1]
    assert shape(next(iter(groups.items()))) == (
        "tuple", ("tuple", "int", "str"), ("list", "float", "int"))
    assert len(groups) == 10
    avg = folded_avg_range().groups
    assert avg == {("host_0",): [(0.1, 2), (-1.0, 7.0), 7.0],
                   ("hôst_2",): [(1e300, 3), (2.0, 9.5), 9.5],
                   ("host_3",): [(-2.5, 1), (-2.5, -2.5), -2.5]}
    assert folded_compact().groups == {("x", 1): [4], ("y", 1): [9]}


class Meters:
    def __init__(self):
        self.seen = {}

    def add_meter(self, name, value=1, labels=None):
        key = (name, labels["form"])
        self.seen[key] = self.seen.get(key, 0) + value


@pytest.mark.parametrize("case,forms", [
    # hours + ids and host names once + ids (a dictionary's own values
    # are not counted again), f64 sums, i64 counts
    ("folded_f32_words", {"coded": 2, "array": 2}),
    ("folded_avg_and_minmaxrange", {"coded": 1, "array": 5}),
    ("host_dict", {"coded": 1, "array": 3}),
    ("avg_and_minmaxrange_tuples", {"array": 5}),
    ("grouped_hll_and_tdigest", {"coded": 1, "list": 2}),
    ("string_int_and_float_bucket_keys", {"coded": 1, "array": 3}),
    ("bool_key", {"coded": 1, "list": 1, "array": 1}),
    ("none_key", {"list": 1, "array": 1}),
    ("mixed_type_key_column", {"list": 1, "array": 1}),
    ("decimal_intermediate", {"coded": 1, "list": 1}),
    ("int_past_63_bits_in_a_list_of_ints", {"array": 2}),
    ("zero_groups_dict", {}),
])
def test_a_column_s_form_follows_its_content_and_is_metered(case, forms):
    meters = Meters()
    dt.serialize_results([CASES[case]()], metrics=meters)
    assert meters.seen == {("group_block", f): n for f, n in forms.items()}


def test_an_int_past_64_bits_takes_the_tagged_list_and_fails_as_before():
    import struct
    with pytest.raises(struct.error):
        dt.serialize_results([GroupByResult({(1 << 70,): [1.0]}, STATS)])


def test_coded_strings_are_written_once():
    n = 3000
    ids = np.arange(n) % 4
    result = GroupByResult(stats=STATS, key_columns=[
        CodedColumn(HOSTS, ids)], value_columns=[np.arange(n)])
    payload = dt.serialize_results([result])
    assert payload.count(b"host_3") == 1
    assert len(payload) < n * (4 + 8) + 200  # int32 ids + int64 values
    [back], _e, _s = dt.deserialize_results(payload)
    assert list(back.groups.items())[-1] == (("host_3",), [n - 1])
    # a plain list of strings is coded on its way out, too
    rows = GroupByResult({(f"host_{i % 4}", i): [i] for i in range(n)}, STATS)
    assert dt.serialize_results([rows]).count(b"host_3") == 1


def test_the_reduce_reads_a_columnar_result_as_it_read_the_dict():
    sql = ("SELECT hostname, ts_hour, SUM(m), COUNT(*) FROM t GROUP BY "
           "ts_hour, hostname ORDER BY ts_hour, hostname LIMIT 100")
    result = folded_sum_count()
    [back], _e, _s = dt.deserialize_results(dt.serialize_results([result]))
    want = reduce_results(QueryContext.from_sql(sql), [
        GroupByResult(dict(result.groups), STATS)]).result_table.rows
    got = reduce_results(QueryContext.from_sql(sql), [back]).result_table.rows
    assert got == want and len(got) == 10
    assert [shape(r) for r in got] == [shape(r) for r in want]


def test_a_result_takes_groups_or_columns_not_both_or_neither():
    with pytest.raises(ValueError):
        GroupByResult()
    with pytest.raises(ValueError):
        GroupByResult({}, STATS, key_columns=[], value_columns=[])
    with pytest.raises(TypeError):  # rows of unequal shapes never transpose
        GroupByResult({(1,): [1], (1, 2): [2]}, STATS).columns()
    with pytest.raises(TypeError):
        GroupByResult({(1,): [1], (2,): [2, 3]}, STATS).columns()


# -- payloads that are cut short or lie ---------------------------------

def grouped_payload(body) -> bytes:
    """A one-result DataTable whose tag `2` body `body(writer)` writes."""
    w = dt._Writer()
    w.raw(dt.MAGIC)
    w.value([])
    w.value(None)
    w.u32(1)
    w.tag("2")
    w.value(dt._stats_tuple(STATS))
    w.value(False)
    body(w)
    return w.bytes()


def sound(w):
    w.u32(2)                                   # rows
    w.u32(1)                                   # key columns
    w.column(CodedColumn(["a", "b"], np.array([1, 0])), Counter())
    w.u32(1)                                   # functions
    w.u32(0)
    w.value(np.array([1.5, 2.5]))


def array_header(w, dtype: str, n: int):
    w.tag("A")
    w.u32(len(dtype))
    w.raw(dtype.encode())
    w.u32(1)
    w.u32(n)


def lying(what: str):
    def body(w):
        if what == "more_rows_than_the_columns_hold":
            sound(w)
            w.parts[w.parts.index(dt._U32.pack(2))] = dt._U32.pack(3)
            return
        w.u32(2)
        w.u32(1)
        if what == "an_array_longer_than_the_buffer":
            array_header(w, "<i8", 1 << 30)
            w.raw(b"\0" * 16)
        elif what == "an_object_dtype":
            array_header(w, "|O", 2)
            w.raw(b"\0" * 16)
        elif what == "ids_past_the_dictionary":
            w.column(CodedColumn(["a", "b"], np.array([0, 2])), Counter())
        elif what == "negative_ids":
            w.column(CodedColumn(["a", "b"], np.array([0, -1])), Counter())
        elif what == "ids_that_are_floats":
            w.tag("C")
            w.value(np.array([1, 2]))
            w.value(np.array([0.0, 1.0]))
        elif what == "more_strings_than_the_buffer":
            w.tag("C")
            w.tag("U")
            w.u32(1 << 31)
        elif what == "string_lengths_past_the_buffer":
            w.tag("C")
            w.tag("U")
            w.u32(2)
            w.raw(np.array([1, 1 << 30], "<u4").tobytes())
            w.raw(b"ab")
        elif what == "a_column_tag_nobody_writes":
            w.value("a string is no column")
        elif what == "a_two_dimensional_array":
            w.value(np.zeros((2, 1)))
        else:
            raise AssertionError(what)
        w.u32(0)
    return body


def test_the_sound_payload_reads():
    [back], _e, _s = dt.deserialize_results(grouped_payload(sound))
    assert back.groups == {("b",): [1.5], ("a",): [2.5]}


@pytest.mark.parametrize("what", [
    "more_rows_than_the_columns_hold", "an_array_longer_than_the_buffer",
    "an_object_dtype", "ids_past_the_dictionary", "negative_ids",
    "ids_that_are_floats", "more_strings_than_the_buffer",
    "string_lengths_past_the_buffer", "a_column_tag_nobody_writes",
    "a_two_dimensional_array"])
def test_a_length_lying_payload_raises(what):
    with pytest.raises((ValueError, TypeError)):
        dt.deserialize_results(grouped_payload(lying(what)))


@pytest.mark.parametrize("case", ["folded_f32_words", "host_dict",
                                  "grouped_hll_and_tdigest"])
def test_every_truncation_raises_instead_of_reading_past_the_buffer(case):
    import struct
    payload = dt.serialize_results([CASES[case]()])
    for cut in range(4, len(payload)):
        with pytest.raises((ValueError, IndexError, struct.error)):
            dt.deserialize_results(payload[:cut])
    # and a cache's wire loader turns any of it into a miss
    from pinot_tpu.cache.core import wire_dumps_results, wire_loads_results
    wired = wire_dumps_results([CASES[case]()])
    assert rows_of(wire_loads_results(wired)[0]) == rows_of(CASES[case]())
    assert wire_loads_results(wired[:len(wired) // 2]) is None
