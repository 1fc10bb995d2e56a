"""Concrete aggregation functions.

Reference parity: pinot-core query/aggregation/function/ — the families
implemented so far (SUM/MIN/MAX/COUNT/AVG/MINMAXRANGE, DISTINCTCOUNT exact
+ HLL, PERCENTILE exact/est/TDigest, MODE, SUMPRECISION, and the
value-array helpers). Sketches live in sketches.py.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from pinot_tpu.query.aggregation.base import (
    AggregationFunction, DeviceAggSpec, count_column, float_column,
    register)
from pinot_tpu.query.aggregation.sketches import HyperLogLog, TDigest


def _masked(values: np.ndarray, mask: np.ndarray) -> np.ndarray:
    if mask is None:
        return values
    return values[mask]


def _f64(col) -> bool:
    """col is a float64 ndarray: array arithmetic on it gives, row for
    row, what Python floats give."""
    return isinstance(col, np.ndarray) and col.dtype == np.float64


def _grouped_bincount(keys, num_groups, mask, weights=None):
    k = keys[mask]
    w = None if weights is None else weights[mask]
    return np.bincount(k, weights=w, minlength=num_groups)


@register
class CountAggregation(AggregationFunction):
    names = ("count",)
    device_spec = DeviceAggSpec(("count",))

    def aggregate(self, values, mask):
        return int(np.count_nonzero(mask))

    def aggregate_grouped(self, values, keys, num_groups, mask):
        return _grouped_bincount(keys, num_groups, mask).astype(np.int64).tolist()

    def merge(self, a, b):
        return a + b

    def identity(self):
        return 0

    def from_device_slots(self, slots):
        # device counts arrive in the value dtype (single packed output)
        return int(round(float(slots["count"])))

    def from_device_slot_columns(self, slots):
        return count_column(slots["count"])

    @property
    def result_name(self):
        return "count(*)" if not self.args or str(self.args[0]) == "*" \
            else super().result_name

    @property
    def final_dtype(self):
        return "LONG"


@register
class SumAggregation(AggregationFunction):
    names = ("sum",)
    device_spec = DeviceAggSpec(("sum",))

    def aggregate(self, values, mask):
        return float(np.sum(_masked(values, mask), dtype=np.float64))

    def aggregate_grouped(self, values, keys, num_groups, mask):
        return _grouped_bincount(keys, num_groups, mask,
                                 values.astype(np.float64)).tolist()

    def merge(self, a, b):
        return a + b

    def identity(self):
        return 0.0

    def from_device_slots(self, slots):
        return float(slots["sum"])

    def from_device_slot_columns(self, slots):
        return float_column(slots["sum"])


@register
class MinAggregation(AggregationFunction):
    names = ("min",)
    device_spec = DeviceAggSpec(("min",))

    def aggregate(self, values, mask):
        v = _masked(values, mask)
        return float(np.min(v)) if len(v) else float("inf")

    def aggregate_grouped(self, values, keys, num_groups, mask):
        out = np.full(num_groups, np.inf)
        k, v = keys[mask], values[mask].astype(np.float64)
        np.minimum.at(out, k, v)
        return out.tolist()

    def merge(self, a, b):
        return min(a, b)

    def identity(self):
        return float("inf")

    def from_device_slots(self, slots):
        return float(slots["min"])

    def from_device_slot_columns(self, slots):
        return float_column(slots["min"])


@register
class MaxAggregation(AggregationFunction):
    names = ("max",)
    device_spec = DeviceAggSpec(("max",))

    def aggregate(self, values, mask):
        v = _masked(values, mask)
        return float(np.max(v)) if len(v) else float("-inf")

    def aggregate_grouped(self, values, keys, num_groups, mask):
        out = np.full(num_groups, -np.inf)
        k, v = keys[mask], values[mask].astype(np.float64)
        np.maximum.at(out, k, v)
        return out.tolist()

    def merge(self, a, b):
        return max(a, b)

    def identity(self):
        return float("-inf")

    def from_device_slots(self, slots):
        return float(slots["max"])

    def from_device_slot_columns(self, slots):
        return float_column(slots["max"])


@register
class AvgAggregation(AggregationFunction):
    """Intermediate is (sum, count) (ref AvgAggregationFunction AvgPair)."""
    names = ("avg",)
    device_spec = DeviceAggSpec(("sum", "count"))

    def aggregate(self, values, mask):
        v = _masked(values, mask)
        return (float(np.sum(v, dtype=np.float64)), len(v))

    def aggregate_grouped(self, values, keys, num_groups, mask):
        s = _grouped_bincount(keys, num_groups, mask, values.astype(np.float64))
        c = _grouped_bincount(keys, num_groups, mask)
        return list(zip(s.tolist(), c.astype(np.int64).tolist()))

    def merge(self, a, b):
        return (a[0] + b[0], a[1] + b[1])

    def identity(self):
        return (0.0, 0)

    def extract_final(self, intermediate):
        s, c = intermediate
        return s / c if c else float("-inf")  # ref returns NEGATIVE_INFINITY

    def from_device_slots(self, slots):
        return (float(slots["sum"]), int(round(float(slots["count"]))))

    def from_device_slot_columns(self, slots):
        return (float_column(slots["sum"]), count_column(slots["count"]))

    def final_column(self, value_column):
        s, c = value_column if isinstance(value_column, tuple) else (0, 0)
        if not (_f64(s) and isinstance(c, np.ndarray)
                and c.dtype.kind in "iu"):
            return super().final_column(value_column)
        out = np.full(len(s), -np.inf)
        return np.divide(s, c, out=out, where=c != 0)


@register
class MinMaxRangeAggregation(AggregationFunction):
    """Intermediate is (min, max) (ref MinMaxRangeAggregationFunction)."""
    names = ("minmaxrange",)
    device_spec = DeviceAggSpec(("min", "max"))

    def aggregate(self, values, mask):
        v = _masked(values, mask)
        if not len(v):
            return (float("inf"), float("-inf"))
        return (float(np.min(v)), float(np.max(v)))

    def merge(self, a, b):
        return (min(a[0], b[0]), max(a[1], b[1]))

    def identity(self):
        return (float("inf"), float("-inf"))

    def extract_final(self, intermediate):
        return intermediate[1] - intermediate[0]

    def from_device_slots(self, slots):
        return (float(slots["min"]), float(slots["max"]))

    def from_device_slot_columns(self, slots):
        return (float_column(slots["min"]), float_column(slots["max"]))

    def final_column(self, value_column):
        lo, hi = value_column if isinstance(value_column, tuple) else (0, 0)
        if not (_f64(lo) and _f64(hi)):
            return super().final_column(value_column)
        with np.errstate(invalid="ignore"):  # inf - inf: nan, as floats do
            return hi - lo


@register
class SumPrecisionAggregation(AggregationFunction):
    """Exact big-decimal sum (ref SumPrecisionAggregationFunction)."""
    names = ("sumprecision",)

    def aggregate(self, values, mask):
        from decimal import Decimal
        v = _masked(values, mask)
        total = Decimal(0)
        for x in v.tolist():
            total += Decimal(str(x))
        return total

    def merge(self, a, b):
        return a + b

    def identity(self):
        from decimal import Decimal
        return Decimal(0)

    def extract_final(self, intermediate):
        return str(intermediate)

    @property
    def final_dtype(self):
        return "BIG_DECIMAL"


@register
class DistinctCountAggregation(AggregationFunction):
    """Exact distinct count; intermediate is a value set
    (ref DistinctCountAggregationFunction)."""
    names = ("distinctcount", "distinctcountbitmap", "segmentpartitioneddistinctcount")

    def aggregate(self, values, mask):
        return set(np.unique(_masked(values, mask)).tolist())

    def aggregate_grouped(self, values, keys, num_groups, mask):
        out = [set() for _ in range(num_groups)]
        k, v = keys[mask], values[mask]
        order = np.argsort(k, kind="stable")
        k, v = k[order], v[order]
        bounds = np.searchsorted(k, np.arange(num_groups + 1))
        for g in range(num_groups):
            seg = v[bounds[g]:bounds[g + 1]]
            if len(seg):
                out[g] = set(np.unique(seg).tolist())
        return out

    def merge(self, a, b):
        return a | b

    def identity(self):
        return set()

    def extract_final(self, intermediate):
        return len(intermediate)

    @property
    def final_dtype(self):
        return "INT"


@register
class DistinctCountHLLAggregation(AggregationFunction):
    """Approximate distinct count via HyperLogLog
    (ref DistinctCountHLLAggregationFunction, log2m default 12)."""
    names = ("distinctcounthll", "distinctcounthllplus", "distinctcountull",
             "distinctcountcpcsketch")

    def _log2m(self) -> int:
        from pinot_tpu.query.expressions import Literal
        if len(self.args) > 1 and isinstance(self.args[1], Literal):
            return int(self.args[1].value)
        return 12

    def aggregate(self, values, mask):
        hll = HyperLogLog(self._log2m())
        hll.add_array(_masked(values, mask))
        return hll

    def merge(self, a, b):
        return a.merge(b)

    def identity(self):
        return HyperLogLog(self._log2m())

    def extract_final(self, intermediate):
        return intermediate.cardinality()

    @property
    def device_spec(self):
        """Device path: registers computed by a hash->bucket->max-scatter
        kernel over the column's i32 split planes (ops/kernels.py 'hll'
        op); bit-identical to the host sketch, so partials merge exactly.
        Plain-column args only (the kernel hashes staged planes)."""
        from pinot_tpu.query.expressions import Identifier
        if self.args and isinstance(self.args[0], Identifier) \
                and self.args[0].name != "*":
            return DeviceAggSpec(ops=(f"hll:{self._log2m()}",))
        return None

    def from_device_slots(self, slots):
        return HyperLogLog.from_registers(
            slots[f"hll:{self._log2m()}"], self._log2m())

    @property
    def final_dtype(self):
        return "LONG"


class _ValueCollectingAggregation(AggregationFunction):
    """Base for functions whose intermediate is the collected value array."""

    def aggregate(self, values, mask):
        return _masked(values, mask).astype(np.float64)

    def aggregate_grouped(self, values, keys, num_groups, mask):
        k, v = keys[mask], values[mask].astype(np.float64)
        order = np.argsort(k, kind="stable")
        k, v = k[order], v[order]
        bounds = np.searchsorted(k, np.arange(num_groups + 1))
        return [v[bounds[g]:bounds[g + 1]] for g in range(num_groups)]

    def merge(self, a, b):
        return np.concatenate([a, b])

    def identity(self):
        return np.empty(0, dtype=np.float64)


@register
class PercentileAggregation(_ValueCollectingAggregation):
    """Exact percentile (ref PercentileAggregationFunction).

    percentile(col, p) or legacy percentileNN(col) via name suffix.
    """
    names = ("percentile", "percentileest", "percentilerawest")

    def __init__(self, args, percent: Optional[float] = None):
        super().__init__(args)
        from pinot_tpu.query.expressions import Literal
        if percent is not None:
            self._pct = percent
        elif len(args) > 1 and isinstance(args[1], Literal):
            self._pct = float(args[1].value)
        else:
            self._pct = 50.0

    def extract_final(self, intermediate):
        if not len(intermediate):
            return float("-inf")
        # ref PercentileAggregationFunction: index = floor(len * p / 100) on
        # the sorted array, clamped to the last element
        v = np.sort(intermediate)
        idx = min(int(len(v) * self._pct / 100.0), len(v) - 1)
        return float(v[idx])


@register
class PercentileTDigestAggregation(AggregationFunction):
    """Approximate percentile via t-digest
    (ref PercentileTDigestAggregationFunction, compression 100)."""
    names = ("percentiletdigest", "percentilerawtdigest")

    def __init__(self, args, percent: Optional[float] = None):
        super().__init__(args)
        from pinot_tpu.query.expressions import Literal
        self._pct = percent if percent is not None else (
            float(args[1].value) if len(args) > 1 and isinstance(args[1], Literal)
            else 50.0)
        self._compression = (
            float(args[2].value) if len(args) > 2 and isinstance(args[2], Literal)
            else 100.0)

    #: device histogram resolution (quantile error <= one bucket width of
    #: the column's [min, max] range on top of the digest's own error)
    DEVICE_BUCKETS = 8192

    def aggregate(self, values, mask):
        td = TDigest(self._compression)
        td.add_array(_masked(values, mask))
        return td

    def merge(self, a, b):
        return a.merge(b)

    def identity(self):
        return TDigest(self._compression)

    def extract_final(self, intermediate):
        return intermediate.quantile(self._pct / 100.0)

    @property
    def device_spec(self):
        """Device path: fixed-bucket histogram partials (scatter-add over
        value buckets, bounds from segment metadata min/max) converted to
        centroid weights host-side. Plain-column args only (bucket bounds
        come from that column's metadata)."""
        from pinot_tpu.query.expressions import Identifier
        if self.args and isinstance(self.args[0], Identifier) \
                and self.args[0].name != "*":
            return DeviceAggSpec(ops=(f"hist:{self.DEVICE_BUCKETS}",))
        return None

    def from_device_slots(self, slots):
        return TDigest.from_histogram(
            slots["hist_lo"], slots["hist_width"],
            slots[f"hist:{self.DEVICE_BUCKETS}"], self._compression)


@register
class ModeAggregation(AggregationFunction):
    """Most frequent value; intermediate is value->count dict
    (ref ModeAggregationFunction, default MIN tie-break)."""
    names = ("mode",)

    def aggregate(self, values, mask):
        v, c = np.unique(_masked(values, mask), return_counts=True)
        return dict(zip(v.tolist(), c.tolist()))

    def merge(self, a, b):
        for k, v in b.items():
            a[k] = a.get(k, 0) + v
        return a

    def identity(self):
        return {}

    def extract_final(self, intermediate):
        if not intermediate:
            return float("-inf")
        best = max(intermediate.items(), key=lambda kv: (kv[1], -_as_float(kv[0])))
        return float(best[0])


def _as_float(x) -> float:
    try:
        return float(x)
    except (TypeError, ValueError):
        return 0.0


@register
class CountMVAggregation(AggregationFunction):
    """COUNT over multi-value column entries (ref CountMVAggregationFunction);
    values here is the per-doc entry-count array."""
    names = ("countmv",)

    def aggregate(self, values, mask):
        return int(np.sum(_masked(values, mask)))

    def merge(self, a, b):
        return a + b

    def identity(self):
        return 0

    @property
    def final_dtype(self):
        return "LONG"


# Legacy percentileNN / percentileTDigestNN names (ref
# AggregationFunctionFactory parses the numeric suffix).
def resolve_percentile_suffix(name: str, args: tuple):
    """percentile95(col) style names -> configured instance, or None."""
    import re
    m = re.fullmatch(r"(percentile(?:est|kll|tdigest|rawest|rawtdigest)?)(\d{1,3})",
                     name.lower())
    if m is None:
        return None
    base, pct = m.group(1), float(m.group(2))
    if "tdigest" in base:
        return PercentileTDigestAggregation(args, percent=pct)
    if "kll" in base:
        from pinot_tpu.query.aggregation.functions_stats import (
            PercentileKLLAggregation)
        return PercentileKLLAggregation(args, percent=pct)
    return PercentileAggregation(args, percent=pct)
