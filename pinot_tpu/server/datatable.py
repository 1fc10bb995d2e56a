"""DataTable: the server->broker wire format.

Reference parity: pinot-common datatable/DataTableImplV4.java:82 — the
binary container a server returns per query: result payload + metadata
(stats) + exceptions. The reference serializes aggregation intermediates
with a typed ObjectSerDe registry; same approach here (tag byte + typed
payload, numpy-backed), deliberately NOT pickle: the broker must never
execute payload-controlled code.

Layout: 4-byte magic 'PDT1', then a tagged value tree:
  N null | i int64 | f float64 | s utf-8 str | b bytes | T/F bool
  D Decimal(str)  | t tuple | l list | S set | M dict
  A numpy array (dtype str, ndim, shape, raw bytes)
  H HyperLogLog (log2m + registers) | G TDigest (compression, means, weights)
  E ThetaSketch | K KLLSketch
then per result a shape tag and its fields: 1 aggregation | 3 selection |
4 distinct as tagged values, and

  2 group-by, as COLUMNS (one wire form; `GroupByResult.columns()`
    transposes a dict-built result when it is written):
      stats (tagged tuple) | limit flag (T/F) | u32 rows
      u32 key columns, then a column each
      u32 functions, then for each: u32 arity, max(arity, 1) columns
        (arity 0: the column's rows ARE the intermediates; k >= 1: the
        intermediate is the tuple of the k columns' rows: AVG's
        (sum, count), MINMAXRANGE's (min, max))
    a column is written by what it holds, never by a knob or a name:
      A  a numeric array (kind b/i/u/f), as the value tag above: raw bytes
      C  a dictionary with ids: a values column (A, U or l) and an A of
         int32 ids (a key column's distinct values once: 4,000 host
         names, not 48,000)
      U  strings: u32 count, count u32 byte lengths, then their utf-8
         bytes end to end (only inside C; a plain string column is
         written as C over its distinct strings)
      l  anything else (sketches, Decimal, None, a list of Python
         bools, mixed types, nested tuples): the tagged list above
    A list of exactly-`int` rows travels as an int64 A, of exactly-
    `float` rows as a float64 A: no value is narrowed, and the reader's
    `tolist` gives the same Python types back. Row order is dict order.

Nothing persists DataTable bytes across versions of the program (server
and broker of one deployment speak it, and both result caches live in
their process), so tag 2's layout changed in place when it became
columnar; there is no row form left to fall back to.

The reader checks every length against the buffer before it slices and
builds arrays with `np.frombuffer` over a whitelisted dtype kind: a
truncated or length-lying payload raises, nothing is ever unpickled.
"""
from __future__ import annotations

import math
import struct
from collections import Counter
from decimal import Decimal
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pinot_tpu.utils import errorcodes
from pinot_tpu.query.aggregation.sketches import (
    HyperLogLog, KLLSketch, TDigest, ThetaSketch)
from pinot_tpu.query.results import (
    AggregationResult, CodedColumn, DistinctResult, ExecutionStats,
    GroupByResult, SelectionResult)

MAGIC = b"PDT1"


def _listed(col: Any) -> Any:
    """A string or object array as the list of its values."""
    if isinstance(col, np.ndarray) and col.dtype.kind in "UO":
        return col.tolist()
    return col


def _all_of(col: Any, kind: type) -> bool:
    """A non-empty list whose values are all exactly `kind` (a bool is
    no int, a numpy scalar no float)."""
    return isinstance(col, list) and bool(col) \
        and set(map(type, col)) == {kind}

_I64 = struct.Struct("<q")
_F64 = struct.Struct("<d")
_U32 = struct.Struct("<I")


class _Writer:
    def __init__(self):
        self.parts: List[bytes] = []

    def u32(self, v: int):
        self.parts.append(_U32.pack(v))

    def raw(self, b: bytes):
        self.parts.append(b)

    def tag(self, t: str):
        self.parts.append(t.encode())

    def value(self, v: Any):
        if v is None:
            self.tag("N")
        elif isinstance(v, bool):
            self.tag("T" if v else "F")
        elif isinstance(v, (int, np.integer)):
            self.tag("i")
            self.raw(_I64.pack(int(v)))
        elif isinstance(v, (float, np.floating)):
            self.tag("f")
            self.raw(_F64.pack(float(v)))
        elif isinstance(v, str):
            b = v.encode()
            self.tag("s")
            self.u32(len(b))
            self.raw(b)
        elif isinstance(v, bytes):
            self.tag("b")
            self.u32(len(v))
            self.raw(v)
        elif isinstance(v, Decimal):
            b = str(v).encode()
            self.tag("D")
            self.u32(len(b))
            self.raw(b)
        elif isinstance(v, tuple):
            self.tag("t")
            self.u32(len(v))
            for x in v:
                self.value(x)
        elif isinstance(v, list):
            self.tag("l")
            self.u32(len(v))
            for x in v:
                self.value(x)
        elif isinstance(v, (set, frozenset)):
            self.tag("S")
            self.u32(len(v))
            for x in v:
                self.value(x)
        elif isinstance(v, dict):
            self.tag("M")
            self.u32(len(v))
            for k, x in v.items():
                self.value(k)
                self.value(x)
        elif isinstance(v, np.ndarray):
            self.tag("A")
            if v.dtype.kind in "UO":  # store as list of strings
                self.value([str(x) for x in v.tolist()])
            else:
                dt = v.dtype.str.encode()
                self.u32(len(dt))
                self.raw(dt)
                self.u32(v.ndim)
                for d in v.shape:
                    self.u32(d)
                self.raw(np.ascontiguousarray(v).tobytes())
        elif isinstance(v, HyperLogLog):
            self.tag("H")
            self.u32(v.log2m)
            self.raw(v.registers.tobytes())
        elif isinstance(v, TDigest):
            v._compress(force=True)
            self.tag("G")
            self.raw(_F64.pack(v.compression))
            self.raw(_F64.pack(v.total))
            self.value(v.means)
            self.value(v.weights)
        elif isinstance(v, ThetaSketch):
            self.tag("E")
            self.u32(v.k)
            self.raw(struct.pack("<Q", int(v.theta)))
            self.value(v.hashes)
        elif isinstance(v, KLLSketch):
            self.tag("K")
            self.u32(v.k)
            self.raw(_I64.pack(v.n))
            self.value([lvl for lvl in v.levels])
        else:
            raise TypeError(f"unserializable value type {type(v)}")

    def column(self, col: Any, forms: Counter):
        """One column of a grouped result, in the form its content
        allows (module docstring); `forms` counts the forms written."""
        col = _listed(col)
        if _all_of(col, str):
            index: Dict[str, int] = {}
            ids = np.fromiter(
                (index.setdefault(x, len(index)) for x in col),
                np.int32, len(col))
            col = CodedColumn(list(index), ids)
        if not isinstance(col, CodedColumn):
            self._plain_column(col, forms)
            return
        forms["coded"] += 1
        self.tag("C")
        values = _listed(col.values)
        if _all_of(values, str):
            enc = [x.encode() for x in values]
            self.tag("U")
            self.u32(len(enc))
            self.raw(np.fromiter(map(len, enc), "<u4", len(enc)).tobytes())
            self.raw(b"".join(enc))
        else:
            self._plain_column(values, Counter())
        self.value(np.asarray(col.ids).astype(np.int32, copy=False))

    def _plain_column(self, col: Any, forms: Counter):
        try:
            if _all_of(col, int):
                col = np.array(col, np.int64)
            elif _all_of(col, float):
                col = np.array(col, np.float64)
        except OverflowError:  # an int past 64 bits: the tagged list
            pass
        if isinstance(col, np.ndarray) and col.ndim == 1 \
                and col.dtype.kind in "biuf":
            forms["array"] += 1
            self.value(col)
        else:
            forms["list"] += 1
            self.value(list(col))

    def bytes(self) -> bytes:
        return b"".join(self.parts)


class _Reader:
    def __init__(self, buf: bytes, pos: int = 0):
        self.buf = buf
        self.pos = pos

    def u32(self) -> int:
        v = _U32.unpack_from(self.buf, self.pos)[0]
        self.pos += 4
        return v

    def take(self, n: int) -> bytes:
        if self.pos + n > len(self.buf):
            raise ValueError(f"truncated DataTable: {n} bytes wanted at "
                             f"{self.pos} of {len(self.buf)}")
        b = self.buf[self.pos:self.pos + n]
        self.pos += n
        return b

    def column(self, rows: int) -> Any:
        """One column of a grouped result (`_Writer.column`): an array,
        a list or a CodedColumn of `rows` rows, or ValueError."""
        if chr(self.buf[self.pos]) != "C":
            return self._plain_column(rows)
        self.pos += 1
        values = self._strings() if chr(self.buf[self.pos]) == "U" \
            else self._plain_column(None)
        ids = self._plain_column(rows)
        if not isinstance(ids, np.ndarray) or ids.dtype.kind != "i" or (
                rows and not
                0 <= int(ids.min()) <= int(ids.max()) < len(values)):
            raise ValueError("bad dictionary ids in a group column")
        return CodedColumn(values, ids)

    def _strings(self) -> List[str]:
        self.pos += 1
        n = self.u32()
        ends = np.cumsum(np.frombuffer(self.take(4 * n), "<u4"),
                         dtype=np.int64).tolist()
        blob = self.take(ends[-1] if ends else 0)
        text = blob.decode()
        if len(text) == len(blob):  # ASCII: a byte a character
            return [text[a:b] for a, b in zip([0] + ends, ends)]
        return [blob[a:b].decode() for a, b in zip([0] + ends, ends)]

    def _plain_column(self, rows: Optional[int]) -> Any:
        t = chr(self.buf[self.pos])
        if t not in "Al":
            raise ValueError(f"bad column tag {t!r} at {self.pos}")
        col = self.value()
        if t == "A" and (col.ndim != 1 or col.dtype.kind not in "biuf"):
            raise ValueError("bad array in a group column")
        if rows is not None and len(col) != rows:
            raise ValueError(f"group column of {len(col)} rows, "
                             f"{rows} stated")
        return col

    def value(self) -> Any:
        t = chr(self.buf[self.pos])
        self.pos += 1
        if t == "N":
            return None
        if t == "T":
            return True
        if t == "F":
            return False
        if t == "i":
            v = _I64.unpack_from(self.buf, self.pos)[0]
            self.pos += 8
            return v
        if t == "f":
            v = _F64.unpack_from(self.buf, self.pos)[0]
            self.pos += 8
            return v
        if t == "s":
            return self.take(self.u32()).decode()
        if t == "b":
            return self.take(self.u32())
        if t == "D":
            return Decimal(self.take(self.u32()).decode())
        if t == "t":
            return tuple(self.value() for _ in range(self.u32()))
        if t == "l":
            return [self.value() for _ in range(self.u32())]
        if t == "S":
            return {self.value() for _ in range(self.u32())}
        if t == "M":
            return {self.value(): self.value() for _ in range(self.u32())}
        if t == "A":
            if chr(self.buf[self.pos]) == "l":  # string array stored as list
                return np.array(self.value(), dtype=object)
            dt = np.dtype(self.take(self.u32()).decode())
            ndim = self.u32()
            shape = tuple(self.u32() for _ in range(ndim))
            if dt.kind not in "biufcmMSV" or dt.hasobject:
                raise ValueError(f"bad array dtype {dt!r}")
            n = math.prod(shape)
            arr = np.frombuffer(self.take(n * dt.itemsize), dtype=dt)
            return arr.reshape(shape).copy()
        if t == "H":
            h = HyperLogLog(self.u32())
            h.registers = np.frombuffer(self.take(h.m), dtype=np.uint8).copy()
            return h
        if t == "G":
            comp = _F64.unpack_from(self.buf, self.pos)[0]
            self.pos += 8
            total = _F64.unpack_from(self.buf, self.pos)[0]
            self.pos += 8
            td = TDigest(comp)
            td.total = total
            td.means = self.value()
            td.weights = self.value()
            return td
        if t == "E":
            sk = ThetaSketch(self.u32())
            sk.theta = np.uint64(
                struct.unpack_from("<Q", self.buf, self.pos)[0])
            self.pos += 8
            sk.hashes = self.value().astype(np.uint64)
            return sk
        if t == "K":
            k = self.u32()
            sk = KLLSketch(k)
            sk.n = _I64.unpack_from(self.buf, self.pos)[0]
            self.pos += 8
            sk.levels = [np.asarray(lvl, dtype=np.float64)
                         for lvl in self.value()]
            return sk
        raise ValueError(f"bad tag {t!r} at {self.pos - 1}")


def _stats_tuple(s: ExecutionStats) -> tuple:
    return (s.num_docs_scanned, s.num_entries_scanned_in_filter,
            s.num_entries_scanned_post_filter, s.num_segments_processed,
            s.num_segments_matched, s.total_docs, s.num_segments_pruned)


def _stats_from(t: tuple) -> ExecutionStats:
    return ExecutionStats(*t)


def serialize_value(v: Any) -> bytes:
    """One typed value (incl. sketches) -> bytes. Used for aggregation
    intermediates crossing the MSE mailbox plane as opaque block cells
    (ref DataBlock variable-size payloads)."""
    w = _Writer()
    w.value(v)
    return w.bytes()


def deserialize_value(buf: bytes) -> Any:
    return _Reader(buf).value()


def serialize_results(results: List[Any], exceptions: List[dict] = (),
                      extra_stats: Optional[ExecutionStats] = None,
                      metrics=None) -> bytes:
    """Server response: list of shape-tagged SegmentResults + exceptions +
    server-level stats (pruning counts survive even with zero results —
    the reference carries these in DataTable metadata). `metrics` (a
    MetricsRegistry) gets `group_block{form=array|coded|list}`, the
    columns of grouped results written, by form.

    Layout note: a server-side span tree may be APPENDED to the returned
    bytes as one extra tagged value (ServerQueryExecutor.execute does
    `payload + serialize_value(tree)`); readers that stop at the result
    count skip it, `deserialize_results_ex` picks it up."""
    w = _Writer()
    w.raw(MAGIC)
    w.value([_exc_tuple(e) for e in exceptions])
    w.value(_stats_tuple(extra_stats) if extra_stats is not None else None)
    w.u32(len(results))
    forms: Counter = Counter()
    for r in results:
        if isinstance(r, AggregationResult):
            w.tag("1")
            w.value(r.intermediates)
            w.value(_stats_tuple(r.stats))
        elif isinstance(r, GroupByResult):
            rows, key_columns, value_columns = r.columns()
            w.tag("2")
            w.value(_stats_tuple(r.stats))
            w.value(bool(r.num_groups_limit_reached))
            w.u32(rows)
            w.u32(len(key_columns))
            for col in key_columns:
                w.column(col, forms)
            w.u32(len(value_columns))
            for col in value_columns:
                parts = col if isinstance(col, tuple) else (col,)
                w.u32(len(col) if isinstance(col, tuple) else 0)
                for part in parts:
                    w.column(part, forms)
        elif isinstance(r, SelectionResult):
            w.tag("3")
            w.value(r.rows)
            w.value(r.order_values)
            w.value(r.columns)
            w.value(_stats_tuple(r.stats))
        elif isinstance(r, DistinctResult):
            w.tag("4")
            w.value(r.rows)
            w.value(_stats_tuple(r.stats))
        else:
            raise TypeError(f"unserializable result {type(r)}")
    if metrics is not None:
        for form, n in forms.items():
            metrics.add_meter("group_block", n, labels={"form": form})
    return w.bytes()


def deserialize_results(buf: bytes
                        ) -> Tuple[List[Any], List[dict], Optional[ExecutionStats]]:
    results, exceptions, extra_stats, _trace = deserialize_results_ex(buf)
    return results, exceptions, extra_stats


def deserialize_results_ex(buf: bytes) -> Tuple[
        List[Any], List[dict], Optional[ExecutionStats], Optional[dict]]:
    """deserialize_results + the optional trailing trace tree (None when
    the payload carries none — e.g. tracing disabled on the server)."""
    if buf[:4] != MAGIC:
        raise ValueError("bad DataTable magic")
    r = _Reader(buf, 4)
    exceptions = [_exc_from(t) for t in r.value()]
    st = r.value()
    extra_stats = _stats_from(st) if st is not None else None
    n = r.u32()
    out: List[Any] = []
    for _ in range(n):
        tag = chr(r.buf[r.pos])
        r.pos += 1
        if tag == "1":
            inters = r.value()
            out.append(AggregationResult(inters, _stats_from(r.value())))
        elif tag == "2":
            stats = _stats_from(r.value())
            limit_reached = r.value()
            rows = r.u32()
            key_columns = [r.column(rows) for _ in range(r.u32())]
            value_columns: List[Any] = []
            for _ in range(r.u32()):
                arity = r.u32()
                value_columns.append(
                    tuple(r.column(rows) for _ in range(arity))
                    if arity else r.column(rows))
            if rows and not key_columns:
                raise ValueError("group rows without a key column")
            out.append(GroupByResult(
                stats=stats, num_groups_limit_reached=limit_reached,
                key_columns=key_columns, value_columns=value_columns))
        elif tag == "3":
            rows = r.value()
            order_values = r.value()
            columns = r.value()
            out.append(SelectionResult(rows, order_values=order_values,
                                       columns=columns,
                                       stats=_stats_from(r.value())))
        elif tag == "4":
            rows = r.value()
            out.append(DistinctResult(rows, _stats_from(r.value())))
        else:
            raise ValueError(f"bad result tag {tag!r}")
    trace = None
    if r.pos < len(r.buf):
        t = r.value()
        if isinstance(t, dict):
            trace = t
    return out, exceptions, extra_stats, trace


def _exc_tuple(e: dict) -> tuple:
    return (int(e.get("errorCode", errorcodes.QUERY_EXECUTION)),
            str(e.get("message", "")))


def _exc_from(t: tuple) -> dict:
    return {"errorCode": t[0], "message": t[1]}
