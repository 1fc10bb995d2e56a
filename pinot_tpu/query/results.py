"""Per-segment / per-server intermediate result containers.

Reference parity: pinot-core operator result blocks
(AggregationResultsBlock, GroupByResultsBlock, SelectionResultsBlock,
DistinctResultsBlock) and the serialized DataTable (pinot-common
datatable/DataTableImplV4.java:82) they travel as. Here they are plain
Python containers (a grouped result holds columns, as the reference's
GroupByResultsBlock holds an IndexedTable); the wire serde lives in
server/datatable.py.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np


@dataclass
class ExecutionStats:
    """Ref core/operator/ExecutionStatistics.java + DataTable metadata."""
    num_docs_scanned: int = 0
    num_entries_scanned_in_filter: int = 0
    num_entries_scanned_post_filter: int = 0
    num_segments_processed: int = 0
    num_segments_matched: int = 0
    total_docs: int = 0
    num_segments_pruned: int = 0

    def merge(self, o: "ExecutionStats") -> None:
        self.num_docs_scanned += o.num_docs_scanned
        self.num_entries_scanned_in_filter += o.num_entries_scanned_in_filter
        self.num_entries_scanned_post_filter += o.num_entries_scanned_post_filter
        self.num_segments_processed += o.num_segments_processed
        self.num_segments_matched += o.num_segments_matched
        self.total_docs += o.total_docs
        self.num_segments_pruned += o.num_segments_pruned


@dataclass
class AggregationResult:
    """One intermediate per aggregation function."""
    intermediates: List[Any]
    stats: ExecutionStats = field(default_factory=ExecutionStats)


@dataclass(frozen=True)
class CodedColumn:
    """A group-key column as a dictionary with ids: row i's key is
    `values[ids[i]]` (4,000 host names once, not 48,000 times)."""
    values: Any      # the distinct values: ndarray or list
    ids: np.ndarray  # [rows] integer indices into values


def column_values(col: Any) -> list:
    """A result column's rows as Python values: `CodedColumn`, ndarray
    (`tolist`: int64 -> int, float64 -> float, bool_ -> bool) or a list
    already."""
    if isinstance(col, CodedColumn):
        if isinstance(col.values, np.ndarray):
            return col.values[col.ids].tolist()
        values = col.values
        return [values[i] for i in col.ids.tolist()]
    return col.tolist() if isinstance(col, np.ndarray) else col


class GroupByResult:
    """A grouped partial, columns first: `key_columns` holds one column
    a GROUP BY expression (ndarray, list or `CodedColumn`) and
    `value_columns` one entry an aggregation function: a column whose
    rows ARE the intermediates (SUM, COUNT, MIN, MAX; a sketch: a list
    of objects), or a tuple of columns, one a component, where the
    intermediate is a tuple (AVG `(sum, count)`, MINMAXRANGE
    `(min, max)`).

    `.groups`, `{key tuple: [intermediates]}` in row order, is built
    from the columns on first use and kept. A producer that has the dict
    passes it (`GroupByResult(groups, stats)`); `columns()` transposes it
    for the wire."""

    def __init__(self, groups: Optional[Dict[Tuple, List[Any]]] = None,
                 stats: Optional[ExecutionStats] = None,
                 num_groups_limit_reached: bool = False, *,
                 key_columns: Optional[List[Any]] = None,
                 value_columns: Optional[List[Any]] = None):
        if (groups is None) == (key_columns is None):
            raise ValueError("a GroupByResult takes groups or columns")
        self._groups = groups
        self.key_columns = key_columns
        self.value_columns = value_columns
        self.stats = stats if stats is not None else ExecutionStats()
        self.num_groups_limit_reached = num_groups_limit_reached

    @property
    def groups(self) -> Dict[Tuple, List[Any]]:
        if self._groups is None:
            keys = zip(*map(column_values, self.key_columns))
            per_fn = [list(zip(*map(column_values, col)))
                      if isinstance(col, tuple) else column_values(col)
                      for col in self.value_columns]
            self._groups = (
                dict(zip(keys, map(list, zip(*per_fn)))) if per_fn
                else {key: [] for key in keys})
        return self._groups

    @property
    def num_rows(self) -> int:
        """Groups held, counted without building or transposing."""
        if self._groups is not None:
            return len(self._groups)
        first = self.key_columns[0] if self.key_columns else ()
        return len(first.ids if isinstance(first, CodedColumn) else first)

    def columns(self) -> Tuple[int, List[Any], List[Any]]:
        """(rows, key columns, value columns). A dict-built result is
        transposed: plain lists, and a function whose intermediates are
        all tuples of one length >= 1 splits into its components."""
        if self.key_columns is not None:
            return self.num_rows, self.key_columns, self.value_columns
        groups = self._groups
        if not groups:
            return 0, [], []
        if len({len(k) for k in groups}) != 1 or not next(iter(groups)) \
                or len({len(v) for v in groups.values()}) != 1:
            raise TypeError("group rows of unequal or zero key length")
        value_columns: List[Any] = []
        for col in zip(*groups.values()):
            arity = {len(v) if type(v) is tuple else 0 for v in col}
            if len(arity) == 1 and min(arity) >= 1:
                value_columns.append(tuple(list(c) for c in zip(*col)))
            else:
                value_columns.append(list(col))
        return (len(groups), [list(c) for c in zip(*groups)],
                value_columns)

    def __eq__(self, other: Any) -> bool:
        return (isinstance(other, GroupByResult)
                and self.groups == other.groups
                and self.stats == other.stats
                and self.num_groups_limit_reached
                == other.num_groups_limit_reached)

    def __repr__(self) -> str:  # never builds the dict
        return (f"GroupByResult({self.num_rows} groups, "
                f"stats={self.stats!r}, "
                f"num_groups_limit_reached="
                f"{self.num_groups_limit_reached!r})")


@dataclass
class SelectionResult:
    """Projected rows; order_values present when pre-sorted server-side."""
    rows: List[Tuple]
    order_values: Optional[List[Tuple]] = None
    columns: Optional[List[str]] = None  # star-expanded column names
    stats: ExecutionStats = field(default_factory=ExecutionStats)


@dataclass
class DistinctResult:
    rows: set
    stats: ExecutionStats = field(default_factory=ExecutionStats)


SegmentResult = Any  # union of the above
