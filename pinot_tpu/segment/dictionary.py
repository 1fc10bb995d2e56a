"""Sorted per-column dictionaries: value <-> dictId.

Reference parity: pinot-segment-spi index/reader/Dictionary.java:37 and
pinot-segment-local readers ({Int,Long,Float,Double,String,Bytes}Dictionary,
creator SegmentDictionaryCreator). As in the reference, dictionaries are
value-sorted, so range predicates resolve to contiguous dictId ranges
(searchsorted) and min/max are dictIds 0 and N-1 — which is what lets device
filter kernels compare int32 dictIds instead of values.

Serialized form:
  numeric: the sorted value array, raw little-endian.
  string/bytes: int32 offsets array (n+1 entries) followed by the UTF-8 blob.
"""
from __future__ import annotations

import hashlib
from typing import Any, Optional, Tuple

import numpy as np

from pinot_tpu.models.field_spec import DataType


class Dictionary:
    """Immutable sorted dictionary over a column's distinct values."""

    def __init__(self, data_type: DataType, values: np.ndarray):
        self.data_type = data_type
        self._values = values  # sorted; numeric ndarray or object ndarray

    # -- factory ------------------------------------------------------------
    @classmethod
    def build(cls, data_type: DataType, column: np.ndarray) -> Tuple["Dictionary", np.ndarray]:
        """Build from raw column values; returns (dictionary, dictIds)."""
        uniques, inverse = np.unique(column, return_inverse=True)
        return cls(data_type, uniques), inverse.astype(np.int32)

    # -- Dictionary contract (ref Dictionary.java:37) -----------------------
    def __len__(self) -> int:
        return len(self._values)

    @property
    def cardinality(self) -> int:
        return len(self._values)

    def get_value(self, dict_id: int) -> Any:
        v = self._values[dict_id]
        if isinstance(v, np.generic):
            return v.item()
        return v

    def get_values(self, dict_ids: np.ndarray) -> np.ndarray:
        return self._values[dict_ids]

    @property
    def values(self) -> np.ndarray:
        return self._values

    @property
    def content_key(self) -> bytes:
        """Digest of (dtype, values), computed once: equal for exactly the
        dictionaries that resolve every literal to the same dictIds, so a
        predicate resolved against one stands for all of them (the
        engine stages a leaf once a DISTINCT dictionary, not once a
        segment: segments of one table mostly share their low-cardinality
        dictionaries)."""
        key = getattr(self, "_content_key", None)
        if key is None:
            vals = self._values
            h = hashlib.sha1(vals.dtype.str.encode())
            if vals.dtype == np.dtype(object):
                for v in vals:
                    b = v.encode("utf-8") if isinstance(v, str) else bytes(v)
                    h.update(len(b).to_bytes(4, "little") + b)
            else:
                h.update(np.ascontiguousarray(vals).tobytes())
            key = self._content_key = h.digest()
        return key

    @property
    def fst_index(self):
        """Lazy FST-style regex/prefix index over the sorted terms (ref
        LuceneFSTIndexReader; see segment/fst_index.py)."""
        fst = getattr(self, "_fst", None)
        if fst is None:
            from pinot_tpu.segment.fst_index import FstIndex
            fst = self._fst = FstIndex(self._values)
        return fst

    def index_of(self, value: Any) -> int:
        """DictId of value, or -1 (ref Dictionary.indexOf null handling).

        Exact-match semantics: a non-integral float never matches an int
        dictionary, out-of-dtype-range values never match.
        """
        i = self.insertion_index(value, side="left")
        if i < len(self._values) and self._values[i] == value:
            return i
        return -1

    def insertion_index(self, value: Any, side: str = "left") -> int:
        """searchsorted position — used to resolve range predicates.

        The value is NOT coerced to the dictionary dtype: numpy's comparison
        promotion handles mixed int/float and out-of-range bounds correctly
        (e.g. `x > 3.5` on an int column resolves at position of 4).
        """
        return int(np.searchsorted(self._values, value, side=side))

    @property
    def min_value(self) -> Any:
        return self.get_value(0)

    @property
    def max_value(self) -> Any:
        return self.get_value(len(self._values) - 1)

    # -- numeric view for device upload -------------------------------------
    def values_as_f64(self) -> Optional[np.ndarray]:
        """Dictionary values as float64 (None for non-numeric) — used to map
        dictId aggregation results back to value space on device."""
        if self._values.dtype == np.dtype(object):
            return None
        return self._values.astype(np.float64)

    # -- serde --------------------------------------------------------------
    def to_bytes(self) -> bytes:
        if self._values.dtype == np.dtype(object):
            encoded = [v.encode("utf-8") if isinstance(v, str) else bytes(v)
                       for v in self._values]
            offsets = np.zeros(len(encoded) + 1, dtype=np.int32)
            np.cumsum([len(b) for b in encoded], out=offsets[1:])
            return offsets.tobytes() + b"".join(encoded)
        return np.ascontiguousarray(self._values).tobytes()

    @classmethod
    def from_bytes(cls, data_type: DataType, data: np.ndarray, cardinality: int) -> "Dictionary":
        npdt = data_type.np_dtype
        if npdt == np.dtype(object):
            raw = np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, memoryview)) \
                else np.asarray(data, dtype=np.uint8)
            offsets = raw[: (cardinality + 1) * 4].view(np.int32)
            blob = raw[(cardinality + 1) * 4:].tobytes()
            is_bytes = data_type.stored_type is DataType.BYTES
            vals = np.empty(cardinality, dtype=object)
            for i in range(cardinality):
                chunk = blob[offsets[i]:offsets[i + 1]]
                vals[i] = chunk if is_bytes else chunk.decode("utf-8")
            return cls(data_type, vals)
        raw = np.frombuffer(data, dtype=npdt, count=cardinality) \
            if isinstance(data, (bytes, memoryview)) else np.asarray(data).view(npdt)[:cardinality]
        return cls(data_type, raw)
