"""Aggregation function base contract + registry."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Type

import numpy as np

from pinot_tpu.query.results import column_values


@dataclass(frozen=True)
class DeviceAggSpec:
    """How the TPU kernel computes this aggregation's intermediate.

    op: one of 'sum' | 'min' | 'max' | 'count' | 'sumsq' | 'sum3' | 'sum4'
    — the masked reduction the fused device kernel emits. Functions whose
    intermediate is a tuple of these (AVG = sum+count; moments =
    sum+sumsq[+sum3+sum4]+count) list several slots. Functions with no
    spec run host-side.
    """
    ops: tuple  # e.g. ('sum',), ('sum', 'count')


class AggregationFunction:
    """One aggregation instance bound to its argument expressions."""

    #: canonical lower-case name(s) to register under
    names: Sequence[str] = ()
    #: device kernel composition, or None for host-only
    device_spec: Optional[DeviceAggSpec] = None
    #: True: `values` arrives stacked [n_args, n] (covariance, with-time)
    multi_arg: bool = False
    #: True: `values` arrives FLAT (all MV entries) with the mask/keys
    #: pre-expanded per entry by the executor (the *MV family)
    mv_input: bool = False

    def __init__(self, args: tuple):
        self.args = args  # tuple[Expression]

    # -- host (numpy) path --------------------------------------------------
    def aggregate(self, values: Optional[np.ndarray], mask: np.ndarray) -> Any:
        """Whole-block aggregate -> intermediate result.

        values: materialized argument column (None for COUNT(*));
        mask: boolean filter mask over docs.
        """
        raise NotImplementedError

    def aggregate_grouped(self, values: Optional[np.ndarray],
                          keys: np.ndarray, num_groups: int,
                          mask: np.ndarray) -> list:
        """Group-by aggregate: returns list of intermediates per group key.

        keys: int group-key per doc (only where mask); num_groups: key space.
        Default implementation loops groups via sorting; subclasses override
        with vectorized bincount-style paths.
        """
        out = []
        for g in range(num_groups):
            gmask = mask & (keys == g)
            out.append(self.aggregate(values, gmask))
        return out

    # -- merge/extract (ref merge / extractFinalResult) ---------------------
    def merge(self, a: Any, b: Any) -> Any:
        raise NotImplementedError

    def identity(self) -> Any:
        """Intermediate for an empty input (merge identity)."""
        raise NotImplementedError

    def extract_final(self, intermediate: Any) -> Any:
        return intermediate

    def final_column(self, value_column: Any) -> Any:
        """`extract_final` for a whole `GroupByResult.value_columns`
        entry at once: a column (ndarray or list) whose rows, as
        `column_values` gives them, are the finals. Where extract_final
        is the identity (COUNT, SUM, MIN, MAX, ...) that is the column
        itself; otherwise this default loops (a list: any function keeps
        working), and AVG / MINMAXRANGE override it with array
        arithmetic that gives, row for row, extract_final's value and
        Python type."""
        if type(self).extract_final is AggregationFunction.extract_final:
            return value_column
        inters = zip(*map(column_values, value_column)) \
            if isinstance(value_column, tuple) \
            else column_values(value_column)
        return [self.extract_final(v) for v in inters]

    # -- device path --------------------------------------------------------
    def from_device_slots(self, slots: Dict[str, Any]) -> Any:
        """Build the intermediate from this function's device reduction
        outputs; slots maps op-name -> scalar/array for this function's
        DeviceAggSpec.ops."""
        raise NotImplementedError

    def from_device_slot_columns(self, slots: Dict[str, np.ndarray]) -> Any:
        """`from_device_slots` for a whole result at once: slots maps
        op-name -> [rows] array (a row a group). Returns the function's
        value columns as `GroupByResult.value_columns` holds them: one
        column whose rows are the intermediates, or a tuple of columns,
        one a component of a tuple intermediate. This default loops
        (an object column: any function keeps working); the plain
        reductions override it with array arithmetic that gives, row for
        row, the value and Python type `from_device_slots` gives."""
        ops = list(slots)
        return [self.from_device_slots(dict(zip(ops, vals)))
                for vals in zip(*(slots[op] for op in ops))]

    # -- metadata -----------------------------------------------------------
    @property
    def result_name(self) -> str:
        a = ",".join(str(x) for x in self.args)
        return f"{self.names[0]}({a})"

    @property
    def final_dtype(self) -> str:
        return "DOUBLE"


def count_column(words: np.ndarray) -> np.ndarray:
    """A device count slot's column as int64: `int(round(float(x)))` a
    row (counts arrive as integer words from the fold, in the value
    dtype elsewhere; both round half to even)."""
    words = np.asarray(words)
    if words.dtype.kind in "iu":
        return words.astype(np.int64)
    return np.rint(words).astype(np.int64)


def float_column(words: np.ndarray) -> np.ndarray:
    """A device sum / min / max slot's column as float64: `float(x)` a
    row, the f32 or f64 word widened, never narrowed."""
    return np.asarray(words).astype(np.float64)


def scalar(v):
    """Unwrap a numpy scalar to its Python value."""
    return v.item() if isinstance(v, np.generic) else v


REGISTRY: Dict[str, Type[AggregationFunction]] = {}


def register(cls: Type[AggregationFunction]) -> Type[AggregationFunction]:
    for name in cls.names:
        REGISTRY[name.lower()] = cls
    return cls


def is_aggregation(name: str) -> bool:
    if name.lower() in REGISTRY:
        return True
    from pinot_tpu.query.aggregation.functions import resolve_percentile_suffix
    return resolve_percentile_suffix(name, ()) is not None


def get_aggregation(name: str, args: tuple) -> AggregationFunction:
    cls = REGISTRY.get(name.lower())
    if cls is not None:
        return cls(args)
    from pinot_tpu.query.aggregation.functions import resolve_percentile_suffix
    inst = resolve_percentile_suffix(name, args)
    if inst is None:
        raise ValueError(f"unknown aggregation function: {name}")
    return inst
