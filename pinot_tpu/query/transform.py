"""Transform-function evaluation over column blocks.

Reference parity: pinot-core
operator/transform/function/TransformFunction.java:35 (block-at-a-time
evaluation; 72 function classes) + the scalar function registry in
pinot-common function/. Here an expression tree evaluates directly over
whole-column numpy arrays supplied by a ColumnProvider; the device engine
mirrors the same arithmetic in jnp for the shapes it offloads.
"""
from __future__ import annotations

import json
from typing import Any, Callable, Dict, Protocol

import numpy as np

from pinot_tpu.query.expressions import Expression, Function, Identifier, Literal


class ColumnProvider(Protocol):
    def column(self, name: str) -> np.ndarray: ...
    @property
    def num_docs(self) -> int: ...


_BINARY_NUMERIC: Dict[str, Callable] = {
    "plus": np.add,
    "minus": np.subtract,
    "times": np.multiply,
    "divide": lambda a, b: np.divide(np.asarray(a, dtype=np.float64), b),
    "mod": np.mod,
    "pow": np.power,
    "power": np.power,
}

_UNARY_NUMERIC: Dict[str, Callable] = {
    "abs": np.abs,
    "ceil": np.ceil,
    "floor": np.floor,
    "exp": np.exp,
    "ln": np.log,
    "log": np.log,
    "log2": np.log2,
    "log10": np.log10,
    "sqrt": np.sqrt,
    "sign": np.sign,
    "negate": np.negative,
    "sin": np.sin, "cos": np.cos, "tan": np.tan,
    "asin": np.arcsin, "acos": np.arccos, "atan": np.arctan,
    "sinh": np.sinh, "cosh": np.cosh, "tanh": np.tanh,
    "degrees": np.degrees, "radians": np.radians,
}

_COMPARISONS: Dict[str, Callable] = {
    "equals": lambda a, b: _eq(a, b),
    "not_equals": lambda a, b: ~_eq(a, b),
    "greater_than": lambda a, b: np.greater(a, b),
    "greater_than_or_equal": lambda a, b: np.greater_equal(a, b),
    "less_than": lambda a, b: np.less(a, b),
    "less_than_or_equal": lambda a, b: np.less_equal(a, b),
}


def _eq(a, b):
    a_s = np.asarray(a).dtype.kind in "UOS"
    b_s = np.asarray(b).dtype.kind in "UOS"
    if a_s != b_s:  # numeric vs string comparison via string form
        a = np.asarray(a).astype(str) if not a_s else a
        b = np.asarray(b).astype(str) if not b_s else b
    return np.equal(a, b)


def evaluate(expr: Expression, provider: ColumnProvider) -> Any:
    """Evaluate expr to a numpy array (or scalar for literal-only trees)."""
    if isinstance(expr, Literal):
        return expr.value
    if isinstance(expr, Identifier):
        return provider.column(expr.name)
    assert isinstance(expr, Function)
    name = expr.name
    if name in _BINARY_NUMERIC:
        a = evaluate(expr.args[0], provider)
        b = evaluate(expr.args[1], provider)
        return _BINARY_NUMERIC[name](a, b)
    if name in _UNARY_NUMERIC:
        return _UNARY_NUMERIC[name](_as_numeric(evaluate(expr.args[0], provider)))
    if name in _COMPARISONS:
        return _COMPARISONS[name](evaluate(expr.args[0], provider),
                                  evaluate(expr.args[1], provider))
    if name in _PREDICATES:
        return _PREDICATES[name](expr, provider)
    handler = _SPECIAL.get(name)
    if handler is not None:
        return handler(expr, provider)
    raise ValueError(f"unsupported transform function: {name}")


# -- predicate evaluation over plain providers (MSE intermediate blocks;
#    segment scans use the index-aware path in query/filter.py instead) ----

def _bool(x, p: ColumnProvider) -> np.ndarray:
    arr = np.asarray(x)
    if arr.ndim == 0:
        arr = np.full(p.num_docs, bool(arr))
    return arr.astype(bool, copy=False)


def _pred_and(e: Function, p: ColumnProvider):
    out = _bool(evaluate(e.args[0], p), p)
    for a in e.args[1:]:
        out = out & _bool(evaluate(a, p), p)
    return out


def _pred_or(e: Function, p: ColumnProvider):
    out = _bool(evaluate(e.args[0], p), p)
    for a in e.args[1:]:
        out = out | _bool(evaluate(a, p), p)
    return out


def _pred_between(e: Function, p: ColumnProvider):
    v = evaluate(e.args[0], p)
    lo = evaluate(e.args[1], p)
    hi = evaluate(e.args[2], p)
    return np.greater_equal(v, lo) & np.less_equal(v, hi)


def _pred_in(e: Function, p: ColumnProvider):
    v = np.asarray(evaluate(e.args[0], p))
    vals = [a.value for a in e.args[1:]]  # type: ignore[union-attr]
    if v.dtype.kind in "UOS":
        vals = [str(x) for x in vals]
        v = v.astype(str)
    else:
        # numeric column: coerce string literals into the value domain
        # (parity with the leaf path, query/filter.py _value_space_mask)
        vals = [float(x) if isinstance(x, str) else x for x in vals]
    return np.isin(v, np.asarray(vals))


def _pred_like(e: Function, p: ColumnProvider):
    import re as _re
    from pinot_tpu.query.filter import like_to_regex
    v = np.asarray(evaluate(e.args[0], p))
    pattern = e.args[1].value  # type: ignore[union-attr]
    rx = _re.compile(like_to_regex(pattern) if e.name == "like" else pattern)
    return np.array([rx.search(str(x)) is not None for x in v], bool)


def _pred_is_null(e: Function, p: ColumnProvider):
    v = np.asarray(evaluate(e.args[0], p))
    if v.dtype.kind == "f":
        return np.isnan(v)
    if v.dtype.kind == "O":
        return np.array([x is None for x in v], bool)
    return np.zeros(len(v), bool)


_PREDICATES: Dict[str, Callable] = {
    "and": _pred_and,
    "or": _pred_or,
    "not": lambda e, p: ~_bool(evaluate(e.args[0], p), p),
    "between": _pred_between,
    "in": _pred_in,
    "not_in": lambda e, p: ~_pred_in(e, p),
    "like": _pred_like,
    "regexp_like": _pred_like,
    "is_null": _pred_is_null,
    "is_not_null": lambda e, p: ~_pred_is_null(e, p),
}


def _as_numeric(x):
    arr = np.asarray(x)
    if arr.dtype.kind in "UOS":
        return arr.astype(np.float64)
    return x


def _broadcast(x, n: int) -> np.ndarray:
    arr = np.asarray(x)
    if arr.ndim == 0:
        return np.broadcast_to(arr, (n,))
    return arr


# -- special forms ----------------------------------------------------------

def _case(expr: Function, p: ColumnProvider):
    n = p.num_docs
    *pairs, default = expr.args
    result = _broadcast(evaluate(default, p), n).copy() \
        if default is not None else np.full(n, np.nan)
    assigned = np.zeros(n, dtype=bool)
    for i in range(0, len(pairs), 2):
        cond = _broadcast(evaluate(pairs[i], p), n).astype(bool)
        val = _broadcast(evaluate(pairs[i + 1], p), n)
        take = cond & ~assigned
        if result.dtype != val.dtype and (result.dtype.kind in "UOS"
                                          or val.dtype.kind in "UOS"):
            result = result.astype(object)
            val = val.astype(object)
        result = np.where(take, val, result)
        assigned |= cond
    return result


def _concat(expr: Function, p: ColumnProvider):
    parts = [np.asarray(evaluate(a, p)).astype(str) for a in expr.args]
    n = max((len(x) for x in parts if x.ndim), default=1)
    parts = [_broadcast(x, n) for x in parts]
    out = parts[0]
    for part in parts[1:]:
        out = np.char.add(out, part)
    return out


def _substr(expr: Function, p: ColumnProvider):
    s = np.asarray(evaluate(expr.args[0], p)).astype(str)
    start = int(evaluate(expr.args[1], p))
    if len(expr.args) > 2:
        end = int(evaluate(expr.args[2], p))
        return np.array([x[start:end] for x in s])
    return np.array([x[start:] for x in s])


def _cast(expr: Function, p: ColumnProvider):
    v = evaluate(expr.args[0], p)
    target = expr.args[1]
    tname = (target.value if isinstance(target, Literal) else target.name).upper()
    arr = np.asarray(v)
    if tname in ("INT", "INTEGER"):
        return arr.astype(np.float64).astype(np.int32) if arr.dtype.kind in "UOS" \
            else arr.astype(np.int32)
    if tname == "LONG":
        return arr.astype(np.float64).astype(np.int64) if arr.dtype.kind in "UOS" \
            else arr.astype(np.int64)
    if tname == "FLOAT":
        return arr.astype(np.float32)
    if tname == "DOUBLE":
        return arr.astype(np.float64)
    if tname in ("STRING", "VARCHAR"):
        return arr.astype(str)
    if tname == "BOOLEAN":
        return arr.astype(bool)
    raise ValueError(f"unsupported cast target {tname}")


_DAY_MS = 86_400_000

#: the function's names: upstream's registry drops the underscore
DATETRUNC_NAMES = ("datetrunc", "date_trunc")

#: DATETRUNC's units (pinot-common DateTimeUtils.getTimestampField), each
#: with its width in milliseconds where that is fixed in UTC; MONTH,
#: QUARTER and YEAR vary in length (None)
DATETRUNC_UNITS: Dict[str, Any] = {
    "MILLISECOND": 1, "SECOND": 1000, "MINUTE": 60_000,
    "HOUR": 3_600_000, "DAY": _DAY_MS, "WEEK": 7 * _DAY_MS,
    "MONTH": None, "QUARTER": None, "YEAR": None,
}

#: ISO weeks start on Monday; 1970-01-01 was a Thursday, so the week
#: grid sits 3 days before the epoch
_WEEK_ORIGIN_MS = -3 * _DAY_MS

#: java.util.concurrent.TimeUnit, in milliseconds: a value in one of
#: these is what DATETRUNC's third argument names
_INPUT_UNITS_MS = {
    "MILLISECONDS": 1, "SECONDS": 1000, "MINUTES": 60_000,
    "HOURS": 3_600_000, "DAYS": _DAY_MS,
}
_INPUT_UNITS_PER_MS = {"MICROSECONDS": 1000, "NANOSECONDS": 1_000_000}


def datetrunc_unit(unit: Any) -> str:
    """The DATETRUNC unit a literal names, upper case; ValueError for any
    other."""
    name = str(unit).upper()
    if name not in DATETRUNC_UNITS:
        raise ValueError(f"DATETRUNC: unsupported unit {unit!r}; one of "
                         f"{', '.join(DATETRUNC_UNITS)}")
    return name


def datetrunc_ms(unit: str, millis) -> np.ndarray:
    """Epoch milliseconds (UTC) truncated to the start of their `unit`,
    as int64: floor for a unit of fixed width (before 1970 too), the
    calendar's month, quarter or year otherwise."""
    t = np.asarray(millis, dtype=np.int64)
    unit = datetrunc_unit(unit)
    width = DATETRUNC_UNITS[unit]
    if width is not None:
        origin = _WEEK_ORIGIN_MS if unit == "WEEK" else 0
        return (t - origin) // width * width + origin
    months = t.astype("datetime64[ms]").astype("datetime64[M]")
    if unit == "YEAR":
        months = months.astype("datetime64[Y]").astype("datetime64[M]")
    elif unit == "QUARTER":
        m = months.astype(np.int64)
        months = (m // 3 * 3).astype("datetime64[M]")
    return months.astype("datetime64[ms]").astype(np.int64)


def _datetrunc_values(values) -> np.ndarray:
    """DATETRUNC's time argument as int64: a value of a non-integer
    dtype has to hold a whole number (no NaN, no fraction)."""
    arr = np.asarray(values)
    if arr.dtype.kind in "iu":
        return arr.astype(np.int64)
    if arr.dtype.kind == "b" or arr.dtype.kind not in "fO":
        raise ValueError(f"DATETRUNC: time value of type {arr.dtype} is "
                         "not a number of the input time unit")
    as_float = arr.astype(np.float64)
    if not np.isfinite(as_float).all() \
            or (as_float != np.floor(as_float)).any():
        raise ValueError("DATETRUNC: time value is not a whole number of "
                         "the input time unit")
    return as_float.astype(np.int64)


def _to_java_div(v: np.ndarray, per: int) -> np.ndarray:
    """v / per toward zero, as java's TimeUnit.convert to a coarser
    unit."""
    return np.where(v < 0, -((-v) // per), v // per)


def datetrunc(unit: Any, values, input_unit: Any = "MILLISECONDS"
              ) -> np.ndarray:
    """DATETRUNC(unit, value[, inputTimeUnit]) (pinot-common
    DateTimeFunctions.dateTrunc): the value in `input_unit` since the
    epoch, truncated in UTC to the start of its `unit`, in the input
    unit again, as int64. From a unit no finer than milliseconds the
    way back is exact: a truncation to a unit at least as wide as the
    input's is a whole number of input units, and one to a finer unit
    leaves the value as it was."""
    unit = datetrunc_unit(unit)
    v = _datetrunc_values(values)
    name = str(input_unit).upper()
    if name in _INPUT_UNITS_MS:
        per = _INPUT_UNITS_MS[name]
        return datetrunc_ms(unit, v * per) // per
    if name in _INPUT_UNITS_PER_MS:
        per = _INPUT_UNITS_PER_MS[name]
        return datetrunc_ms(unit, _to_java_div(v, per)) * per
    raise ValueError(f"DATETRUNC: unsupported input time unit "
                     f"{input_unit!r}; one of "
                     f"{', '.join([*_INPUT_UNITS_MS, *_INPUT_UNITS_PER_MS])}")


def _datetrunc(expr: Function, p: ColumnProvider):
    if len(expr.args) not in (2, 3) or not all(
            isinstance(a, Literal) for a in expr.args[:1] + expr.args[2:]):
        raise ValueError(
            "DATETRUNC takes ('<unit>', <time value>[, '<input time unit>'])"
            " with literal units; the time zone and output unit forms are "
            "not supported")
    out = datetrunc(expr.args[0].value, evaluate(expr.args[1], p),
                    *(a.value for a in expr.args[2:]))
    return out if out.ndim else int(out)


def _clpdecode(expr: Function, p: ColumnProvider):
    """clpDecode(logtypeCol, dictVarsCol, encodedVarsCol) -> message strings
    (ref CLPDecodeTransformFunction, used with clp-log ingestion where the
    enricher split a field into three columns)."""
    from pinot_tpu.segment.clp import decode_message
    lt = np.asarray(evaluate(expr.args[0], p)).astype(str)
    dv = p.mv_lists(expr.args[1].name)  # type: ignore[union-attr]
    ev = p.mv_lists(expr.args[2].name)  # type: ignore[union-attr]
    return np.array([decode_message(lt[i], dv[i], [int(x) for x in ev[i]])
                     for i in range(len(lt))], dtype=object)


_SPECIAL: Dict[str, Callable] = {
    "clpdecode": _clpdecode,
    **dict.fromkeys(DATETRUNC_NAMES, _datetrunc),
    "case": _case,
    "concat": _concat,
    "substr": _substr,
    "substring": _substr,
    "cast": _cast,
    "lower": lambda e, p: np.char.lower(np.asarray(evaluate(e.args[0], p)).astype(str)),
    "upper": lambda e, p: np.char.upper(np.asarray(evaluate(e.args[0], p)).astype(str)),
    "trim": lambda e, p: np.char.strip(np.asarray(evaluate(e.args[0], p)).astype(str)),
    "length": lambda e, p: np.char.str_len(np.asarray(evaluate(e.args[0], p)).astype(str)),
    "strlen": lambda e, p: np.char.str_len(np.asarray(evaluate(e.args[0], p)).astype(str)),
    "reverse": lambda e, p: np.array(
        [x[::-1] for x in np.asarray(evaluate(e.args[0], p)).astype(str)]),
    "coalesce": lambda e, p: _coalesce(e, p),
    "json_extract_scalar": lambda e, p: _json_extract_scalar(e, p),
    "map_value": lambda e, p: _map_value(e, p),
    "st_distance": lambda e, p: _st_distance(e, p),
    "json_extract_key": lambda e, p: _json_extract_key(e, p),
    "json_format": lambda e, p: np.array(
        [_json_format_one(v) for v in np.asarray(evaluate(e.args[0], p))],
        dtype=object),
}


def _map_value(expr: Function, p: ColumnProvider):
    """map_value(col, 'key'[, default]) — index-backed dense sub-column
    when the segment carries a map index (ref segment/index/map/ dense
    keys), JSON parse per row otherwise."""
    col = expr.args[0]
    key = str(expr.args[1].value)  # type: ignore[union-attr]
    default = expr.args[2].value if len(expr.args) > 2 else None  # type: ignore[union-attr]
    index = None
    ds_getter = getattr(p, "data_source", None)
    if isinstance(col, Identifier) and ds_getter is not None:
        ds = ds_getter(col.name)
        index = getattr(ds, "map_index", None) if ds is not None else None
    if index is not None:
        sub = index.value_column(key)
        if sub is None:
            return np.full(index.num_docs, default, object)
        out = sub.copy()
        if default is not None:
            out[out == None] = default  # noqa: E711
        return out
    vals = np.asarray(evaluate(col, p))
    out = np.full(len(vals), default, object)
    for i, v in enumerate(vals):
        try:
            m = json.loads(str(v))
            if isinstance(m, dict) and key in m:
                out[i] = m[key]
        except ValueError:
            pass
    return out


def _st_distance(expr: Function, p: ColumnProvider):
    """st_distance(col, 'lat,lng') — haversine meters to a fixed point
    (ref StDistanceFunction; points are 'lat,lng' strings here).
    Malformed/null points yield NaN (same contract as the geo index)."""
    from pinot_tpu.segment.geo_index import haversine_m, parse_point
    vals = np.asarray(evaluate(expr.args[0], p))
    rlat, rlng = parse_point(expr.args[1].value)  # type: ignore[union-attr]
    pts = [parse_point(v) for v in vals]
    return haversine_m(np.array([a for a, _ in pts]),
                       np.array([b for _, b in pts]), rlat, rlng)


def _json_format_one(v) -> str:
    if v is None:
        return ""
    try:
        return json.dumps(json.loads(str(v)))
    except ValueError:
        return str(v)


def _json_extract_scalar(expr: Function, p: ColumnProvider):
    """json_extract_scalar(col, '$.path', resultType[, default]) — ref
    pinot-common function/scalar JsonFunctions + the
    JsonExtractScalarTransformFunction block evaluator."""
    from pinot_tpu.segment.json_index import extract_path
    col = np.asarray(evaluate(expr.args[0], p))
    path = str(expr.args[1].value)  # type: ignore[union-attr]
    rtype = str(expr.args[2].value).upper() if len(expr.args) > 2 else "STRING"
    default = expr.args[3].value if len(expr.args) > 3 else None  # type: ignore

    def conv(v):
        if v is None:
            return default
        if rtype in ("INT", "LONG"):
            try:
                return int(v)
            except (TypeError, ValueError):
                return default
        if rtype in ("FLOAT", "DOUBLE"):
            try:
                return float(v)
            except (TypeError, ValueError):
                return default
        if isinstance(v, (dict, list)):
            return json.dumps(v)
        return str(v)

    out = np.empty(len(col), dtype=object)
    for i, raw in enumerate(col):
        try:
            doc = json.loads(raw) if isinstance(raw, (str, bytes)) else raw
        except (ValueError, TypeError):
            doc = None
        out[i] = conv(extract_path(doc, path))
    if rtype in ("INT", "LONG"):
        if all(v is not None for v in out):
            return out.astype(np.int64)
        # missing paths with no default fall back to NaN floats (like the
        # DOUBLE branch) — but only while every present value survives the
        # f64 round trip; big int64s (snowflake ids) would silently alias
        if any(v is not None and abs(int(v)) > (1 << 53) for v in out):
            raise ValueError(
                f"json_extract_scalar {rtype} over {path!r}: some documents "
                "lack the path and values exceed float precision — pass an "
                "explicit default argument")
        return np.array([np.nan if v is None else float(v) for v in out],
                        dtype=np.float64)
    if rtype in ("FLOAT", "DOUBLE"):
        return np.array([np.nan if v is None else v for v in out],
                        dtype=np.float64)
    return out


def _json_extract_key(expr: Function, p: ColumnProvider):
    """json_extract_key(col, '$.path') -> sorted keys of the object."""
    from pinot_tpu.segment.json_index import extract_path
    col = np.asarray(evaluate(expr.args[0], p))
    path = str(expr.args[1].value)  # type: ignore[union-attr]
    out = np.empty(len(col), dtype=object)
    for i, raw in enumerate(col):
        try:
            doc = json.loads(raw) if isinstance(raw, (str, bytes)) else raw
        except (ValueError, TypeError):
            doc = None
        v = extract_path(doc, path)
        out[i] = sorted(v.keys()) if isinstance(v, dict) else []
    return out


def _missing_mask(arr: np.ndarray) -> np.ndarray:
    """Per-element missing test: NaN for float arrays, None/NaN elements
    for object arrays (ingestion records carry None, not NaN)."""
    if arr.dtype.kind == "f":
        return np.isnan(arr)
    if arr.dtype.kind == "O":
        return np.fromiter(
            (x is None or (isinstance(x, float) and np.isnan(x))
             for x in arr), dtype=bool, count=len(arr))
    return np.zeros(len(arr), dtype=bool)


def _coalesce(expr: Function, p: ColumnProvider):
    n = p.num_docs
    result = None
    for a in expr.args:
        try:
            v = _broadcast(evaluate(a, p), n)
        except TypeError:
            continue  # null-propagating sub-expression: the arg is NULL
        if result is None:
            result = v.copy()
        else:
            result = np.where(missing, v, result)
        missing = _missing_mask(result)
        if not missing.any():
            break
    if result is None:  # every argument was NULL
        result = np.full(n, None, dtype=object)
    return result
