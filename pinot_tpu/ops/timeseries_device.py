"""Device-side time-bucket group-by (ref the `pinot-timeseries` SPI's
TimeBuckets leaf push-down; here the bucketing runs INSIDE the group-by
kernel instead of as a host expression column).

The time-series leaf SQL groups by `floor((t - start) / step)` — an
expression group-by the device scan leg can't admit (group keys must be
dictionary ids), so every dashboard panel used to fall back to the host
executor. This module recognizes that exact shape host-side and fuses
the bucket id into the scatter key: the timestamp stages through the
existing (hi, lo) i32 raw64 planes (exact below 2^55), the kernel
computes `b = (t - start) // step` in i32 from those planes, and `b`
becomes the LOWEST digit of the composite group key — the engine's
successive-division strides then decode it for free.

`DATETRUNC('<unit>', t)` over epoch milliseconds is the same bucket
where the unit has a fixed width in UTC (MILLISECOND .. WEEK): start is the
window's lower bound truncated to the unit, step the unit's width, and
the key decodes to the absolute `start + b * step`. MONTH, QUARTER and
YEAR vary in length and stay host expressions.

start / step / count are PARAMS (per-segment i32 cells), not plan
fields: a dashboard's sliding window changes `start` every refresh, and
only `count_pad` — the pow2 bucket of the window's bucket count — is
baked into the plan, so steady-state refreshes re-stage four scalar
param rows and never retrace.

No `kernels` import here: kernels.py imports this module (one-way, the
same direction as its clp_device import).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pinot_tpu.query import transform
from pinot_tpu.query.expressions import Function, Identifier, Literal

#: timestamps stage as (v >> 24, v & 0xFFFFFF) i32 planes — exact while
#: the hi plane fits i32
MAX_TS = 1 << 55

#: widest admissible window in timestamp units: delta must fit i32 with
#: a 2^24 margin (the hi-plane partial product can overshoot the true
#: delta by up to one lo-plane carry before the correction lands)
MAX_WINDOW = (1 << 31) - (1 << 24)

_SHIFT = 1 << 24


class BucketSpec(NamedTuple):
    """Host-side admission result for one leaf query's bucket leg."""
    col: str
    start: int
    step: int
    count: int      # buckets actually addressed by the window
    count_pad: int  # pow2 bucket -> the plan's static group width
    #: DATETRUNC's unit (its keys are `start + b * step`, LONG), or None
    #: for the leaf's floor((t - start) / step) (its keys are b)
    unit: Optional[str] = None


def _pow2(n: int, floor: int = 8) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


def _int_lit(e) -> Optional[int]:
    if not isinstance(e, Literal):
        return None
    try:
        v = float(e.value)
    except (TypeError, ValueError):
        return None
    return int(v) if v.is_integer() else None


def extract_bucket(e) -> Optional[Tuple[str, Optional[int], int,
                                        Optional[str]]]:
    """(col, start, step, unit) of a group key the fused bucket serves,
    or None:
      * floor((Identifier - int) / int) with a positive step, the shape
        the time-series leaf SQL emits (unit None);
      * DATETRUNC('<unit>', Identifier) over epoch milliseconds with a
        unit of fixed width in UTC (`transform.DATETRUNC_UNITS`: MONTH
        and longer stay on the host). Its start is not in the key: it is
        the filter window's lower bound truncated to the unit
        (`plan_bucket`), so start is None here."""
    if isinstance(e, Function) and e.name in transform.DATETRUNC_NAMES \
            and len(e.args) == 2 and isinstance(e.args[0], Literal) \
            and isinstance(e.args[1], Identifier):
        unit = str(e.args[0].value).upper()
        step = transform.DATETRUNC_UNITS.get(unit)
        if step is None:
            return None
        return e.args[1].name, None, step, unit
    if not (isinstance(e, Function) and e.name == "floor"
            and len(e.args) == 1):
        return None
    div = e.args[0]
    if not (isinstance(div, Function) and div.name == "divide"
            and len(div.args) == 2):
        return None
    sub, step_e = div.args
    if not (isinstance(sub, Function) and sub.name == "minus"
            and len(sub.args) == 2 and isinstance(sub.args[0], Identifier)):
        return None
    start = _int_lit(sub.args[1])
    step = _int_lit(step_e)
    if start is None or step is None or step <= 0:
        return None
    return sub.args[0].name, start, step, None


def extract_window(flt, col: str) -> Optional[Tuple[int, int]]:
    """(lo, hi_excl) from top-level `col >= lo AND col < hi` conjuncts
    — the window the leaf SQL always carries; None when either bound is
    missing (an unbounded scan can't size the bucket grid)."""
    conjuncts = list(flt.args) if isinstance(flt, Function) \
        and flt.name == "and" else [flt] if flt is not None else []
    lo = hi = None
    for c in conjuncts:
        if not (isinstance(c, Function) and len(c.args) == 2
                and isinstance(c.args[0], Identifier)
                and c.args[0].name == col):
            continue
        v = _int_lit(c.args[1])
        if v is None:
            continue
        if c.name == "greater_than_or_equal":
            lo = v if lo is None else max(lo, v)
        elif c.name == "greater_than":
            lo = v + 1 if lo is None else max(lo, v + 1)
        elif c.name == "less_than":
            hi = v if hi is None else min(hi, v)
        elif c.name == "less_than_or_equal":
            hi = v + 1 if hi is None else min(hi, v + 1)
    if lo is None or hi is None or hi <= lo:
        return None
    return lo, hi


def plan_bucket(group_expr, flt, segments) -> Optional[BucketSpec]:
    """Admit the first group-by expression as a fused device time
    bucket, or None (the query stays on whatever path it had). Checks:
    a shape `extract_bucket` admits, an int timestamp column bounded in
    [0, 2^55) on every segment, a filter window starting at/after
    `start` (so the kernel's delta is never negative for surviving rows;
    DATETRUNC's start is that lower bound truncated to its unit), and
    the window fitting the exact-i32 envelope."""
    shape = extract_bucket(group_expr)
    if shape is None:
        return None
    col, start, step, unit = shape
    win = extract_window(flt, col)
    if win is None:
        return None
    if unit is not None:
        start = int(transform.datetrunc_ms(unit, win[0]))
    if win[0] < start:
        return None
    for seg in segments:
        m = seg.metadata.columns.get(col)
        if m is None or m.data_type.np_dtype.kind not in "iu" \
                or m.min_value is None or m.max_value is None:
            return None
        if int(m.min_value) < 0 or int(m.max_value) >= MAX_TS:
            return None
    window = win[1] - 1 - start
    if window >= MAX_WINDOW:
        return None
    count = window // step + 1
    return BucketSpec(col, start, step, count, _pow2(count), unit)


def bucket_keys(spec: BucketSpec, b) -> np.ndarray:
    """The group keys of bucket ids `b`: DATETRUNC's absolute
    `start + b * step` (int64 ms), or the leaf's bucket id itself as the
    host's floor() over an f64 division gives it (float64)."""
    b = np.asarray(b, dtype=np.int64)
    if spec.unit is None:
        return b.astype(np.float64)
    return spec.start + b * spec.step


def bucket_label(spec: BucketSpec) -> str:
    """What `timeBucket` and the `time_bucket{unit=}` meter say: the
    DATETRUNC unit, or FLOOR for the leaf's floor((t - start) / step)
    (its step is a literal of any size: no label)."""
    return spec.unit or "FLOOR"


def leaf_params(spec: BucketSpec, S: int):
    """The four per-segment i32 param cells the kernel reads: start's
    (hi, lo) planes, step, and the live bucket count. Imported lazily by
    the engine's _stage; numpy-side only."""
    import numpy as np
    return {
        "tb:shi": np.full(S, spec.start >> 24, np.int32),
        "tb:slo": np.full(S, spec.start & 0xFFFFFF, np.int32),
        "tb:step": np.full(S, spec.step, np.int32),
        "tb:count": np.full(S, spec.count, np.int32),
    }


# ---------------------------------------------------------------------------
# Traced bucket math (called from kernels._compute_slots)
# ---------------------------------------------------------------------------

def bucket_ids(vhi, vlo, shi, slo, step, count, count_pad: int):
    """(bucket ids clipped to [0, count_pad), in-window gate) from the
    staged (hi, lo) timestamp planes. delta reconstructs exactly in i32
    for every row the window filter keeps; out-of-window rows may wrap,
    but the gate (and the query's own t-range conjuncts) zero their
    contribution before the scatter."""
    delta = (vhi - shi[:, None]) * jnp.int32(_SHIFT) + (vlo - slo[:, None])
    b = jnp.floor_divide(delta, step[:, None])
    gate = (delta >= 0) & (b < count[:, None])
    return jnp.clip(b, 0, count_pad - 1).astype(jnp.int32), gate


#: standalone jit entry so tests (and the purity checker's traced-fn
#: sweep) exercise the bucket math without a full kernel launch
compiled_bucket_ids = jax.jit(bucket_ids, static_argnames=("count_pad",))
