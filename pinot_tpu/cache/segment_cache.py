"""Tier 2: server-side per-segment partial-result cache.

Reference parity: Druid's historical segment cache (`useCache` /
`populateCache`, immutable segments only) mapped onto this repo's
ImmutableSegment / consuming-segment split. Cached unit: ONE segment's
aggregation / group-by / distinct partial for ONE plan fingerprint.
A device GROUP BY folds its segments' partials before they leave the
device (ops/engine.py `_group_fold`), so there the cached unit is the ONE
partial of the whole segment batch, keyed by every member's name and
version (`get_batch` / `put_batch`): the same segments asked the same
plan again are served without the device. Consuming (mutable) segments
and upsert segments (live `valid_doc_ids`)
are never cached — the mutable tail always re-executes, which is exactly
what keeps hybrid tables fresh while the immutable bulk is served from
cache.

Invalidation is version-based: the key carries `segment_version()` —
content CRC when the segment has one, else a per-process generation
stamp — so a replace-by-name simply addresses a different key and the
old entry ages out. `TableDataManager` additionally calls
`invalidate_segment` on replace/remove for prompt byte reclamation.
"""
from __future__ import annotations

import itertools
import threading
from typing import Any, Optional

from pinot_tpu.cache.core import (LruTtlCache, dumps, loads,
                                  wire_dumps_results, wire_loads_results)
from pinot_tpu.query.context import QueryContext
from pinot_tpu.segment.loader import ImmutableSegment

#: per-process generation stamps for segments without a content CRC —
#: monotonically increasing, never reused, so two same-named segment
#: objects (a replace) can never collide on a key
_gen_counter = itertools.count(1)
_gen_lock = threading.Lock()


def segment_version(segment: Any):
    """Stable version token for a loaded segment: the content CRC when
    present (survives reload of the same directory), else a per-object
    generation stamp (unique per process)."""
    crc = getattr(getattr(segment, "metadata", None), "crc", 0)
    if crc:
        return ("crc", crc)
    gen = getattr(segment, "_ptpu_cache_gen", None)
    if gen is None:
        with _gen_lock:
            gen = getattr(segment, "_ptpu_cache_gen", None)
            if gen is None:
                gen = next(_gen_counter)
                try:
                    segment._ptpu_cache_gen = gen
                except AttributeError:
                    return ("id", id(segment))  # slotted object: best effort
    return ("gen", gen)


def is_cacheable_segment(segment: Any) -> bool:
    """Immutable AND no live validity bitmap (upsert mutates
    `valid_doc_ids` in place without a version change)."""
    return (isinstance(segment, ImmutableSegment)
            and getattr(segment, "valid_doc_ids", None) is None)


def is_cacheable_shape(ctx: QueryContext) -> bool:
    """Aggregation / group-by / distinct partials only: selection results
    are large, cheap to recompute, and LIMIT-dependent per segment."""
    return bool(ctx.aggregations) or ctx.distinct


def segment_remote_key(key) -> Optional[str]:
    """Tuple key -> wire key string for the shared remote tier, or None
    when the entry must stay process-local: 'gen'/'id' version stamps are
    per-process counters — identical stamps on two instances would alias
    DIFFERENT segment contents, so only content-CRC versions are shared."""
    name, version, plan_fp = key
    if isinstance(name, tuple):
        return None  # a batch's folded partial stays process-local
    if not (isinstance(version, tuple) and version[0] == "crc"):
        return None
    return f"seg|{name}|crc:{version[1]}|{plan_fp}"


class SegmentResultCache:
    """Per-segment partial results keyed by
    (segment name, segment version, plan fingerprint)."""

    def __init__(self, max_bytes: int = 256 << 20,
                 ttl_seconds: float = 300.0, enabled: bool = True,
                 metrics=None, labels: Optional[dict] = None,
                 backend=None):
        """labels: metric labels (e.g. {'instance': id}) — several server
        instances in one process share the 'server' registry, so unlabeled
        gauges would clobber each other.
        backend: a prebuilt cache (e.g. cache/tiered.py TieredCache) to
        use instead of the default local LruTtlCache. Remote-capable
        backends switch the payload codec from pickle to the typed wire
        encoding (cache/core.py wire_*): a shared store must never feed
        pickle.loads, and an undecodable entry degrades to a miss."""
        self.enabled = enabled
        if backend is not None:
            self._cache = backend
            self._wire = getattr(backend, "wire_codec", False)
        else:
            self._cache = LruTtlCache(max_bytes, ttl_seconds,
                                      metrics=metrics,
                                      metric_prefix="segment_result_cache",
                                      labels=labels)
            self._wire = False

    @classmethod
    def from_config(cls, config, metrics=None,
                    labels: Optional[dict] = None) -> "SegmentResultCache":
        backend = None
        if config.get_str("pinot.server.segment.cache.backend") == "tiered":
            from pinot_tpu.cache.tiered import tiered_backend_from_config
            backend = tiered_backend_from_config(
                config, "pinot.server.segment.cache",
                "segment_result_cache", segment_remote_key,
                metrics=metrics, labels=labels)
        return cls(
            max_bytes=config.get_int("pinot.server.segment.cache.bytes"),
            ttl_seconds=config.get_float(
                "pinot.server.segment.cache.ttl.seconds"),
            enabled=config.get_bool("pinot.server.segment.cache.enabled"),
            metrics=metrics, labels=labels, backend=backend)

    # ------------------------------------------------------------------
    def _decode(self, payload: bytes) -> Optional[Any]:
        if self._wire:
            results = wire_loads_results(payload)
            return results[0] if results else None
        return loads(payload)

    def _encode(self, result: Any) -> Optional[bytes]:
        return wire_dumps_results([result]) if self._wire else dumps(result)

    def get(self, segment: Any, plan_fp: str) -> Optional[Any]:
        if not self.enabled or not is_cacheable_segment(segment):
            return None
        return self._get((segment.name, segment_version(segment), plan_fp))

    @staticmethod
    def _batch_key(segments, plan_fp: str):
        """(names, versions, plan): a replaced member addresses another
        key, as a replaced segment does."""
        return (tuple(s.name for s in segments),
                tuple(segment_version(s) for s in segments), plan_fp)

    def get_batch(self, segments, plan_fp: str) -> Optional[Any]:
        """The folded partial of exactly these segments, in this order,
        or None."""
        if not self.enabled or len(segments) < 2 \
                or not all(is_cacheable_segment(s) for s in segments):
            return None
        return self._get(self._batch_key(segments, plan_fp))

    def put_batch(self, segments, plan_fp: str, result: Any) -> bool:
        if not self.enabled or len(segments) < 2 \
                or not all(is_cacheable_segment(s) for s in segments):
            return False
        return self._put(self._batch_key(segments, plan_fp), result)

    def _get(self, key) -> Optional[Any]:
        payload = self._cache.get(key)
        if payload is None:
            return None
        # workload accounting: serving this partial cost the cache tier
        # these bytes instead of a re-execution (per-query attribution)
        from pinot_tpu.utils.accounting import current_slip
        slip = current_slip()
        if slip is not None:
            slip.add(cache_hit_bytes=len(payload))
        return self._decode(payload)

    def put(self, segment: Any, plan_fp: str, result: Any) -> bool:
        if not self.enabled or not is_cacheable_segment(segment):
            return False
        return self._put(
            (segment.name, segment_version(segment), plan_fp), result)

    def _put(self, key, result: Any) -> bool:
        payload = self._encode(result)
        if payload is None:
            return False
        # a put is the byte-priced face of a MISS: these bytes had to be
        # computed (and written) because no tier held them
        from pinot_tpu.utils.accounting import current_slip
        slip = current_slip()
        if slip is not None:
            slip.add(cache_miss_bytes=len(payload))
        return self._cache.put(key, payload)

    def invalidate_segment(self, name: str, except_version=None) -> int:
        """Drop cached partials for the named segment. except_version
        spares entries of ONE version — a refresh-push replaces the
        segment right after warmup populated the NEW version's entries,
        and a name-only purge would wipe that warmup work along with the
        stale version."""
        def stale(k) -> bool:
            if isinstance(k[0], tuple):  # a batch with this member
                return any(n == name and (except_version is None
                                          or v != except_version)
                           for n, v in zip(k[0], k[1]))
            return k[0] == name and (except_version is None
                                     or k[1] != except_version)
        return self._cache.invalidate(stale)

    def clear(self) -> None:
        self._cache.clear()

    def close(self) -> None:
        """Release a tiered backend's remote connection pool (no-op for
        the local backend)."""
        close = getattr(self._cache, "close", None)
        if close is not None:
            close()

    @property
    def stats(self):
        return self._cache.stats

    @property
    def size_bytes(self) -> int:
        return self._cache.size_bytes

    def __len__(self) -> int:
        return len(self._cache)
