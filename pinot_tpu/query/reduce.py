"""Broker-side reduce: merge per-segment/per-server results into the final
result table.

Reference parity: pinot-core query/reduce/BrokerReduceService.java:61 and
the per-shape reducers (AggregationDataTableReducer,
GroupByDataTableReducer with IndexedTable merge + HavingFilterHandler +
PostAggregationHandler, SelectionDataTableReducer, DistinctDataTableReducer).
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.expressions import (
    Expression, Function, Identifier, Literal, extract_aggregations)
from pinot_tpu.query.results import (
    AggregationResult, CodedColumn, DistinctResult, ExecutionStats,
    GroupByResult, SelectionResult)
from pinot_tpu.query.transform import DATETRUNC_NAMES, datetrunc
from pinot_tpu.utils import tracing


class ResultTable:
    """The answer's table, built from its rows or, by the GROUP BY
    reduce's columns path (`held`), from its output columns and `kept`,
    the indices of the rows kept in order. Such a table makes its
    rows, Python tuples, the first time `rows` is read, and keeps them;
    `to_json` writes its JSON from the columns."""

    def __init__(self, columns: List[str], column_types: List[str],
                 rows: Optional[List[Tuple]]):
        self.columns = columns
        self.column_types = column_types
        self._rows = rows
        self.data: Optional[List[Any]] = None  # columns where held
        self.kept: Optional[np.ndarray] = None

    @classmethod
    def held(cls, columns: List[str], column_types: List[str],
             data: List[Any], kept: np.ndarray) -> "ResultTable":
        table = cls(columns, column_types, None)
        table.data, table.kept = data, kept
        return table

    @property
    def rows(self) -> List[Tuple]:
        if self._rows is None:
            self._rows = list(zip(*(_take(c, self.kept) for c in self.data)))
        return self._rows

    def __repr__(self) -> str:
        return (f"ResultTable(columns={self.columns!r}, "
                f"column_types={self.column_types!r}, rows={self.rows!r})")

    def to_dict(self) -> dict:
        return {"dataSchema": {"columnNames": self.columns,
                               "columnDataTypes": self.column_types},
                "rows": [list(r) for r in self.rows]}

    def to_json(self) -> str:
        """`json.dumps(self.to_dict(), default=str)`, byte for byte; a
        table held as columns writes its rows without making them."""
        if self.data is None:
            return json.dumps(self.to_dict(), default=str)
        schema = {"columnNames": self.columns,
                  "columnDataTypes": self.column_types}
        head = _to_json({"dataSchema": schema, "rows": []})
        return head[:-len("[]}")] + self.rows_json() + "}"

    def rows_json(self) -> str:
        """The rows of a table held as columns as `to_json` writes them,
        made a column at a time: each column's kept rows become JSON
        texts (a numeric column in one `json.dumps`, a coded one a
        distinct value once), joined row by row as strings, so no
        container is made a row."""
        if not len(self.kept):
            return "[]"
        texts = [_json_texts(c, self.kept) for c in self.data]
        return "[[" + "], [".join(map(", ".join, zip(*texts))) + "]]"


#: `json.dumps(obj, default=str)` without making an encoder a call
_to_json = json.JSONEncoder(default=str).encode


def _json_texts(col, idx: np.ndarray) -> List[str]:
    """The JSON text of each of a column's rows at `idx`, as
    `json.dumps(..., default=str)` writes each value of `_take`."""
    if isinstance(col, CodedColumn):
        # only the values some kept row holds, each encoded once
        ids = col.ids[idx]
        counts = np.bincount(ids)
        used = np.flatnonzero(counts)
        table = np.empty(len(counts), object)
        table[used] = _json_texts(col.values, used)
        return table[ids].tolist()
    if isinstance(col, np.ndarray) and col.dtype.kind in "biuf":
        # no number, NaN, Infinity, true or false holds the separator
        return _to_json(col[idx].tolist())[1:-1].split(", ")
    return list(map(_to_json, _take(col, idx)))


@dataclass
class BrokerResponse:
    """Ref BrokerResponseNative (pinot-common response/broker/)."""
    result_table: Optional[ResultTable] = None
    exceptions: List[dict] = field(default_factory=list)
    stats: ExecutionStats = field(default_factory=ExecutionStats)
    time_used_ms: float = 0.0
    num_servers_queried: int = 0
    num_servers_responded: int = 0
    num_groups_limit_reached: bool = False
    trace: Optional[dict] = None  # operator trace tree when trace=true
    #: True when this response was served from the broker result cache
    #: (tier 1); never True on a freshly executed response
    cache_hit: bool = False
    #: True when the answer is known-incomplete (a server timed out, died
    #: mid-query, or segments had no surviving replica) — the exceptions
    #: list carries the why (ref BrokerResponseNative partialResult)
    partial_result: bool = False
    #: True when the answer was served from the result cache PAST its
    #: TTL under brownout (health/brownout.py rung 2): correct as of
    #: when it was cached, knowingly stale now — clients choose whether
    #: stale beats failed
    stale_result: bool = False

    def to_dict(self) -> dict:
        return {"resultTable": (self.result_table.to_dict()
                                if self.result_table else None),
                **self._envelope()}

    @property
    def encode_path(self) -> str:
        """`columns` where encode_table() writes a table held as
        columns, `rows` where it dumps to_dict()."""
        t = self.result_table
        return "rows" if t is None or t.data is None else "columns"

    def encode_table(self) -> bytes:
        """The result table as the HTTP body carries it (JSON; `null`
        where there is none): the broker's encode, timed on its own."""
        return (self.result_table.to_json() if self.result_table
                else "null").encode()

    def encode(self, table: bytes) -> bytes:
        """The HTTP body round a table from encode_table(): byte for byte
        `json.dumps(self.to_dict(), default=str)`, whose first key is
        resultTable and whose other keys are never empty."""
        rest = json.dumps(self._envelope(), default=str).encode()
        return b"".join((b'{"resultTable": ', table, b", ", rest[1:]))

    def _envelope(self) -> dict:
        """Every key of to_dict() but resultTable, in order."""
        d = {
            "exceptions": self.exceptions,
            "numServersQueried": self.num_servers_queried,
            "numServersResponded": self.num_servers_responded,
            "numDocsScanned": self.stats.num_docs_scanned,
            "numEntriesScannedInFilter": self.stats.num_entries_scanned_in_filter,
            "numEntriesScannedPostFilter": self.stats.num_entries_scanned_post_filter,
            "numSegmentsProcessed": self.stats.num_segments_processed,
            "numSegmentsMatched": self.stats.num_segments_matched,
            "numSegmentsPrunedByServer": self.stats.num_segments_pruned,
            "totalDocs": self.stats.total_docs,
            "numGroupsLimitReached": self.num_groups_limit_reached,
            "timeUsedMs": self.time_used_ms,
            "cacheHit": self.cache_hit,
            "partialResult": self.partial_result,
            "staleResult": self.stale_result,
        }
        if self.trace is not None:
            d["traceInfo"] = self.trace
        return d

    @property
    def rows(self) -> List[Tuple]:
        return self.result_table.rows if self.result_table else []


# ---------------------------------------------------------------------------
# Post-aggregation expression evaluation (scalar space)
# ---------------------------------------------------------------------------

_SCALAR_FUNCS = {
    "plus": lambda a, b: a + b,
    "minus": lambda a, b: a - b,
    "times": lambda a, b: a * b,
    "divide": lambda a, b: a / b if b else float("inf") if a > 0 else float("-inf") if a < 0 else float("nan"),
    "mod": lambda a, b: a % b,
    "abs": abs,
    "sqrt": math.sqrt,
    "ln": math.log, "log": math.log, "log10": math.log10, "log2": math.log2,
    "exp": math.exp,
    "ceil": math.ceil, "floor": math.floor,
    "equals": lambda a, b: a == b,
    "not_equals": lambda a, b: a != b,
    "greater_than": lambda a, b: a > b,
    "greater_than_or_equal": lambda a, b: a >= b,
    "less_than": lambda a, b: a < b,
    "less_than_or_equal": lambda a, b: a <= b,
    "and": lambda *xs: all(xs),
    "or": lambda *xs: any(xs),
    "not": lambda a: not a,
    **dict.fromkeys(DATETRUNC_NAMES, lambda unit, v, *input_unit: int(
        datetrunc(unit, v, *input_unit))),
}


def eval_scalar(expr: Expression, bindings: Dict[Expression, Any]) -> Any:
    """Evaluate an expression over scalar bindings (ref
    PostAggregationHandler / HavingFilterHandler)."""
    if expr in bindings:
        return bindings[expr]
    if isinstance(expr, Literal):
        return expr.value
    if isinstance(expr, Function):
        if expr.name == "between":
            v = eval_scalar(expr.args[0], bindings)
            return (eval_scalar(expr.args[1], bindings) <= v
                    <= eval_scalar(expr.args[2], bindings))
        if expr.name == "in":
            v = eval_scalar(expr.args[0], bindings)
            return any(v == eval_scalar(a, bindings) for a in expr.args[1:])
        fn = _SCALAR_FUNCS.get(expr.name)
        if fn is None:
            raise ValueError(f"unsupported post-aggregation function: {expr.name}")
        return fn(*(eval_scalar(a, bindings) for a in expr.args))
    raise ValueError(f"unbound expression in post-aggregation: {expr}")


# ---------------------------------------------------------------------------
# Reducers
# ---------------------------------------------------------------------------

def reduce_results(ctx: QueryContext, results: Sequence[Any],
                   metrics=None) -> BrokerResponse:
    """Merge SegmentResults (from any mix of servers/paths) into the final
    BrokerResponse (ref BrokerReduceService.reduceOnDataTable). `metrics`
    (the broker's MetricsRegistry) gets `broker_reduce{path=}` a GROUP
    BY."""
    resp = BrokerResponse()
    results = [r for r in results if r is not None]
    for r in results:
        resp.stats.merge(r.stats)
    if ctx.is_group_by_query:
        resp.result_table = _reduce_group_by(ctx, results, resp, metrics)
    elif ctx.is_aggregation_query:
        resp.result_table = _reduce_aggregation(ctx, results)
    elif ctx.is_distinct_query:
        resp.result_table = _reduce_distinct(ctx, results)
    else:
        resp.result_table = _reduce_selection(ctx, results)
    return resp


def _final_type(v: Any, declared: str) -> str:
    return declared


def _reduce_aggregation(ctx: QueryContext, results: List[AggregationResult]) -> ResultTable:
    merged = [fn.identity() for fn in ctx.agg_functions]
    for r in results:
        for i, fn in enumerate(ctx.agg_functions):
            merged[i] = fn.merge(merged[i], r.intermediates[i])
    finals = [fn.extract_final(m) for fn, m in zip(ctx.agg_functions, merged)]
    bindings: Dict[Expression, Any] = {
        node: v for node, v in zip(ctx.agg_keys, finals)}
    row = tuple(eval_scalar(e, bindings) for e in ctx.select)
    names = ctx.result_column_names()
    types = [_result_type(e, ctx) for e in ctx.select]
    return ResultTable(names, types, [row])


def _reduce_group_by(ctx: QueryContext, results: List[GroupByResult],
                     resp: BrokerResponse, metrics=None) -> ResultTable:
    for r in results:
        resp.num_groups_limit_reached |= r.num_groups_limit_reached
    names = ctx.result_column_names()
    types = [_result_type(e, ctx) for e in ctx.select]
    # where every output and sort expression IS a group key, an
    # aggregate or an alias of one (the plain report: 48,000 rows of it
    # cost the broker more in hashing expressions than the device took),
    # a row is picked by position; anything computed takes the bindings
    direct = _direct_columns(ctx)
    held = [r for r in results if r.num_rows]
    table = None
    if direct is not None and "gapfillTimeCol" not in ctx.options \
            and len(held) == 1 and held[0].key_columns is not None:
        table = _columnar_table(ctx, held[0], direct, names, types)
    path = "rows" if table is None else "columns"
    tracing.annotate(reducePath=path,
                     reduceRows=sum(r.num_rows for r in results))
    if metrics is not None:
        metrics.add_meter("broker_reduce", labels={"path": path})
    if table is None:
        table = ResultTable(names, types,
                            _merged_rows(ctx, results, direct, names, types))
    return table


def _columnar_table(ctx: QueryContext, r: GroupByResult, direct,
                    names: List[str], types: List[str]
                    ) -> Optional[ResultTable]:
    """The answer from ONE result held as columns, a whole column at a
    time: finals by `final_column`, ORDER BY as one stable lexsort over
    ranks, OFFSET/LIMIT sliced from the permutation; a table held as
    the output columns and the rows kept, whose rows are, value for
    value, type for type and in order, those `_merged_rows` gives. None
    where an ORDER BY column has no rank in Python's own order."""
    select, order = direct
    finals = [fn.final_column(col)
              for fn, col in zip(ctx.agg_functions, r.value_columns)]
    cols = (r.key_columns, finals)
    out = [cols[w][i] for w, i in select]
    keys = []
    for (w, i), (_, asc) in zip(order, ctx.order_by):
        rank = _rank(out[i] if w == 2 else cols[w][i])
        if rank is None:
            return None
        # negated for DESC: ties keep row order, as the stable reverse
        # sort of the rows path keeps them
        keys.append(rank if asc else -rank)
    perm = np.lexsort(keys[::-1]) if keys else np.arange(r.num_rows)
    return ResultTable.held(names, types, out,
                            perm[ctx.offset:ctx.offset + ctx.limit])


def _rank(col) -> Optional[np.ndarray]:
    """Each row's rank among the column's distinct values in Python's
    own order, equal values of equal rank; None where Python would not
    order the column that way (a NaN, a None, types that do not
    compare)."""
    if isinstance(col, CodedColumn):
        # only the values some row holds: a union of 1,000 names ranked
        # whole cost more than the seven rows an answer may have
        table = np.bincount(col.ids)
        used = np.flatnonzero(table)
        ranks = _rank(col.values[used] if isinstance(col.values, np.ndarray)
                      else [col.values[i] for i in used.tolist()])
        if ranks is None:
            return None
        table[used] = ranks
        return table[col.ids]
    if isinstance(col, np.ndarray):
        if col.dtype.kind not in "biuf" \
                or (col.dtype.kind == "f" and np.isnan(col).any()):
            return None
        return np.unique(col, return_inverse=True)[1]
    try:
        distinct = set(col)
        if None in distinct or any(v != v for v in distinct):
            return None
        rank = {v: i for i, v in enumerate(sorted(distinct))}
    except TypeError:
        return None
    return np.fromiter(map(rank.__getitem__, col), np.int64, len(col))


def _take(col, idx: np.ndarray) -> list:
    """A result column's rows at `idx` as Python values, as
    `column_values` gives them."""
    if isinstance(col, CodedColumn):
        return _take(col.values, col.ids[idx])
    if isinstance(col, np.ndarray):
        return col[idx].tolist()
    return [col[i] for i in idx.tolist()]


def _merged_rows(ctx: QueryContext, results: List[GroupByResult], direct,
                 names: List[str], types: List[str]) -> List[Tuple]:
    """The rows path: any number of results of any form, merged a group
    at a time into a dict (ref GroupByDataTableReducer's IndexedTable),
    then HAVING, post-aggregation, gapfill, sort and limit."""
    merged: Dict[Tuple, List[Any]] = {}
    for r in results:
        for key, inters in r.groups.items():
            cur = merged.get(key)
            if cur is None:
                merged[key] = list(inters)
            else:
                for i, fn in enumerate(ctx.agg_functions):
                    cur[i] = fn.merge(cur[i], inters[i])

    rows = []
    for key, inters in merged.items():
        finals = [fn.extract_final(m) for fn, m in zip(ctx.agg_functions, inters)]
        if direct is not None:
            cols = (key, finals)
            out_row = tuple(cols[w][i] for w, i in direct[0])
            cols = (key, finals, out_row)
            rows.append((tuple(cols[w][i] for w, i in direct[1]), out_row))
            continue
        bindings: Dict[Expression, Any] = dict(zip(ctx.group_by, key))
        bindings.update(zip(ctx.agg_keys, finals))
        if ctx.having is not None and not eval_scalar(ctx.having, bindings):
            continue
        # the output row evaluates against CLEAN bindings first (an alias
        # may shadow a column its own expression reads); aliases then bind
        # to the COMPUTED values for ORDER BY — after the HAVING gate, so
        # guarded expressions never evaluate for excluded groups
        out_row = tuple(eval_scalar(e, bindings) for e in ctx.select)
        for val, alias in zip(out_row, ctx.aliases):
            if alias is not None:
                bindings[Identifier(alias)] = val
        sort_key = tuple(eval_scalar(e, bindings) for e, _ in ctx.order_by)
        rows.append((sort_key, out_row))

    if "gapfillTimeCol" in ctx.options:
        # fill BEFORE sort/limit so ordering + limit apply to the filled
        # series (ref GapfillProcessor running inside the reducer)
        from pinot_tpu.query.gapfill import maybe_gapfill
        pre = ResultTable(names, types, [r for _, r in rows])
        filled = maybe_gapfill(ctx, pre)
        if filled is not pre:  # options were valid and fill applied
            try:
                return _sort_limit_filled(ctx, names, filled.rows)
            except (ValueError, KeyError):
                # ORDER BY references something not reconstructible from
                # the output row (e.g. an unselected column): fall back
                # to the unfilled path rather than failing the query
                pass
    if ctx.order_by:
        rows = _sorted_by_keys(rows, [asc for _, asc in ctx.order_by])
    return [r for _, r in rows][ctx.offset:ctx.offset + ctx.limit]


def _direct_columns(ctx: QueryContext):
    """([(where, index) a select expression], [the same an ORDER BY
    expression]) with where 0 = the group key, 1 = the aggregates'
    finals, 2 = the output row (an alias), or None where HAVING or any
    expression has to be evaluated."""
    if ctx.having is not None:
        return None
    group_by, agg_keys = list(ctx.group_by), list(ctx.agg_keys)

    def where(e):
        if e in group_by:
            return 0, group_by.index(e)
        if e in agg_keys:
            return 1, agg_keys.index(e)
        return None

    aliases = {Identifier(a): (2, i) for i, a in enumerate(ctx.aliases)
               if a is not None}
    select = [where(e) for e in ctx.select]
    # an alias shadows a column of its name for ORDER BY
    order = [aliases.get(e) or where(e) for e, _ in ctx.order_by]
    if any(c is None for c in select + order):
        return None
    return select, order


def _sort_limit_filled(ctx: QueryContext, names, filled_rows):
    """ORDER BY + OFFSET/LIMIT over gap-filled rows: sort keys re-derive
    from the output columns (select expressions + aliases)."""
    if not ctx.order_by:
        return list(filled_rows)[ctx.offset:ctx.offset + ctx.limit]
    keyed = []
    for row in filled_rows:
        bindings = {Identifier(n): v for n, v in zip(names, row)}
        for e, v in zip(ctx.select, row):
            bindings[e] = v
        keyed.append((tuple(eval_scalar(e, bindings)
                            for e, _ in ctx.order_by), row))
    keyed = _sorted_by_keys(keyed, [asc for _, asc in ctx.order_by])
    return [r for _, r in keyed][ctx.offset:ctx.offset + ctx.limit]


def _sorted_by_keys(rows, ascs: List[bool]):
    """Sort (sort_key, row) pairs honoring per-key direction."""
    import functools

    # keys of one type a position (the usual answer) sort natively, a
    # stable pass a key from the last to the first; a None or a mix of
    # types raises and takes the comparator below, which orders those
    # by their strings
    try:
        out = list(rows)
        for i in reversed(range(len(ascs))):
            out.sort(key=lambda r, _i=i: r[0][_i], reverse=not ascs[i])
        return out
    except TypeError:
        pass

    def cmp(a, b):
        for i, asc in enumerate(ascs):
            ka, kb = a[0][i], b[0][i]
            if ka == kb:
                continue
            lt = _lt(ka, kb)
            return (-1 if lt else 1) if asc else (1 if lt else -1)
        return 0

    return sorted(rows, key=functools.cmp_to_key(cmp))


def _lt(a, b) -> bool:
    try:
        return a < b
    except TypeError:
        return str(a) < str(b)


def _reduce_selection(ctx: QueryContext, results: List[SelectionResult]) -> ResultTable:
    names = list(ctx.result_column_names())
    for r in results:
        if getattr(r, "columns", None):
            names = list(r.columns)
            break
    if not ctx.order_by:
        rows: List[Tuple] = []
        for r in results:
            rows.extend(r.rows)
        rows = rows[ctx.offset:ctx.offset + ctx.limit]
        return ResultTable(names, ["UNKNOWN"] * len(names), rows)
    paired = []
    for r in results:
        ov = r.order_values if r.order_values is not None else r.rows
        paired.extend(zip(ov, r.rows))
    paired = _sorted_by_keys(paired, [asc for _, asc in ctx.order_by])
    rows = [row for _, row in paired][ctx.offset:ctx.offset + ctx.limit]
    return ResultTable(names, ["UNKNOWN"] * len(names), rows)


def _reduce_distinct(ctx: QueryContext, results: List[DistinctResult]) -> ResultTable:
    seen = set()
    for r in results:
        seen |= r.rows
    rows = list(seen)
    if ctx.order_by:
        # order-by exprs must be in the select list for distinct
        idx = {e: i for i, e in enumerate(ctx.select)}
        paired = [(tuple(row[idx[e]] for e, _ in ctx.order_by), row) for row in rows]
        paired = _sorted_by_keys(paired, [asc for _, asc in ctx.order_by])
        rows = [row for _, row in paired]
    rows = rows[ctx.offset:ctx.offset + ctx.limit]
    names = list(ctx.result_column_names())
    return ResultTable(names, ["UNKNOWN"] * len(names), rows)


def _result_type(e: Expression, ctx: QueryContext) -> str:
    from pinot_tpu.query.aggregation import get_aggregation, is_aggregation
    if isinstance(e, Function) and is_aggregation(e.name):
        return get_aggregation(e.name, e.args).final_dtype
    if isinstance(e, Function) and e.name in DATETRUNC_NAMES:
        return "LONG"
    if isinstance(e, Function):
        return "DOUBLE"
    return "UNKNOWN"
