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

The kernel's bucket digit is as wide as the most buckets one segment of
the batch spans (`BucketSpec.width`, from each segment's own time range
in its metadata), not as the window: each segment keys its rows from
its own first bucket of the window, `off` buckets in, and the fold (or
the host, past the remap cap) shifts the digit back onto the window's
axis of `count_pad` buckets, the pow2 bucket of the window's count. A
batch with a segment that spans `count_pad` buckets or more gets
width == count_pad, the digit the window alone would give. Both widths
are plan fields; start / step / count / origin are PARAMS (per-segment
i32 cells): a dashboard's sliding window changes `start`
every refresh, and the width, fixed by the segments' ranges and by where
start lies within a step (DATETRUNC's start is always on the unit; a
leaf's moves by whole steps), does not, so steady-state refreshes
re-stage five scalar param rows and never retrace.

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

#: bound on bucket_ids' f32 quotient estimate before its i32 conversion
#: (far past any width, far inside i32)
_EST_MAX = float(1 << 30)


class BucketSpec(NamedTuple):
    """Host-side admission result for one leaf query's bucket leg."""
    col: str
    start: int
    step: int
    count: int      # buckets actually addressed by the window
    #: pow2 bucket of `count`: the window's axis, the fold's bucket digit
    count_pad: int
    #: the kernel's bucket digit: the most buckets of the window's grid
    #: one segment of the batch spans, at most count_pad
    width: int
    lo: int         # the window's lower bound (inclusive)
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
    the window fitting the exact-i32 envelope.

    The width is the most buckets of the window's grid one segment's
    whole [min, max] spans, not that range clipped to the window: a
    batch keeps one width, and one compiled kernel, while the window
    slides over it."""
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
    span = 1
    for seg in segments:
        m = seg.metadata.columns.get(col)
        if m is None or m.data_type.np_dtype.kind not in "iu" \
                or m.min_value is None or m.max_value is None:
            return None
        lo, hi = int(m.min_value), int(m.max_value)
        if lo < 0 or hi >= MAX_TS:
            return None
        span = max(span, (hi - start) // step - (lo - start) // step + 1)
    window = win[1] - 1 - start
    if window >= MAX_WINDOW:
        return None
    count = window // step + 1
    count_pad = _pow2(count)
    return BucketSpec(col, start, step, count, count_pad,
                      min(span, count_pad), win[0], unit)


def origin(spec: BucketSpec, seg) -> int:
    """The bucket of the window's grid that segment `seg` keys its rows
    from: the one its first instant inside the window falls in (at most
    `count`, where the segment lies past the window and keys nothing).
    Its rows' bucket digit counts from there."""
    first = max(int(seg.metadata.columns[spec.col].min_value), spec.lo)
    return min((first - spec.start) // spec.step, spec.count)


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


def leaf_params(spec: BucketSpec, segments, S: int):
    """The five per-segment i32 param cells the kernel reads, a segment
    from its own origin bucket (`origin`; 0 on padded slots): the
    origin instant's (hi, lo) planes, step, the buckets left to it in
    the window (at most the width), and the origin's offset into the
    window's grid, which the fold shifts the bucket digit by. Numpy
    only: the engine packs them (`_resolve_params`)."""
    off = np.zeros(S, np.int64)
    off[:len(segments)] = [origin(spec, seg) for seg in segments]
    first = spec.start + off * spec.step
    return {
        "tb:shi": (first >> 24).astype(np.int32),
        "tb:slo": (first & 0xFFFFFF).astype(np.int32),
        "tb:step": np.full(S, spec.step, np.int32),
        "tb:count": np.minimum(spec.count - off, spec.width).astype(np.int32),
        "tb:off": off.astype(np.int32),
    }


# ---------------------------------------------------------------------------
# Traced bucket math (called from kernels._compute_slots)
# ---------------------------------------------------------------------------

def bucket_ids(vhi, vlo, shi, slo, step, count, width: int):
    """(bucket ids clipped to [0, width), in-window gate) from the
    staged (hi, lo) timestamp planes. delta reconstructs exactly in i32
    for every row the window filter keeps; out-of-window rows may wrap,
    but the gate (and the query's own t-range conjuncts) zero their
    contribution before the scatter.

    floor(delta / step) is exact without an integer divide, which the
    TPU has no unit for (XLA's expansion of an i32 divide by a runtime
    divisor took ~35 s to compile a v5e kernel over 2^23 docs, where
    the rest of it takes ~1 s): an f32 estimate, three roundings of at
    most 2^-24 each, is within one of the quotient below 2^21, and one
    exact i32 correction each way makes it the quotient. A width is at
    most MAX_DEVICE_GROUPS = 2^20 buckets, so every row the gate passes
    gets its exact bucket, and a row past 2^21 buckets stays past the
    width and the gate."""
    delta = (vhi - shi[:, None]) * jnp.int32(_SHIFT) + (vlo - slo[:, None])
    step = step[:, None]
    est = delta.astype(jnp.float32) * (1 / step.astype(jnp.float32))
    b = jnp.floor(jnp.clip(est, -_EST_MAX, _EST_MAX)).astype(jnp.int32)
    r = delta - b * step
    b = b - (r < 0) + (r >= step)
    gate = (delta >= 0) & (b < count[:, None])
    return jnp.clip(b, 0, width - 1).astype(jnp.int32), gate


#: standalone jit entry so tests (and the purity checker's traced-fn
#: sweep) exercise the bucket math without a full kernel launch
compiled_bucket_ids = jax.jit(bucket_ids, static_argnames=("width",))
