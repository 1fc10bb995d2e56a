"""HBM segment residency: a per-(segment, column) device-resident tier.

The engine's device tier used to cache only whole stacked blocks keyed by
the exact segment-batch tuple — a different pruned subset, or one newly
sealed segment joining the batch, missed the device tier entirely and
re-shipped EVERY column from the host to the device. This module
holds the unit that actually survives batch recomposition: one padded
device row per (segment object, column kind), assembled into kernel-ready
[S, D] blocks ON DEVICE (ops/kernels.compiled_row_assembler), so a new
batch composition uploads only the rows it has never seen.

Policy (the tier is HBM — it must never grow past its budget, and one
cold table scan must not flush the hot working set):

  * recency — entries are LRU-ordered; hits refresh.
  * frequency-based admission (TinyLFU-style) — every access, hit or
    miss, bumps a per-(segment name, kind, column) counter in a bounded
    sample window (counters halve when the window fills, so stale
    popularity decays). When the tier is full, a candidate is admitted
    only if its frequency exceeds the LRU victim's — a cold scan's
    once-touched rows lose to the dashboard working set and are simply
    not retained (the query still ran; retention is what's refused).
  * warmup seeding — `seeding()` marks accesses made by the segment
    warmup replay (cache/warmup.py): seeded admissions bypass the
    frequency duel and carry a seed boost, because the FingerprintLog
    replaying them IS the evidence of plan traffic.
  * eviction drops the reference only — in-flight kernels hold evicted
    rows as inputs and JAX refcounting frees the HBM when the last
    consumer finishes (same discipline as the block cache).

The module also owns the host->device **transfer odometer**: every byte
the engine ships through `_put`/row uploads is counted process-wide,
exposed like `kernels.trace_count()` so tests and the bench can assert a
repeated-query steady state uploads NOTHING.
"""
from __future__ import annotations

import contextlib
import threading
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# transfer odometer (process-wide, like the kernels.py compile odometer):
# counts bytes shipped host->device through the engine's upload paths.
# Steady-state traffic over resident columns must keep this flat — a
# growing count means the hot path is paying the link again.
# ---------------------------------------------------------------------------
_transfer_lock = threading.Lock()
_transfer_bytes = 0
_transfer_count = 0
_column_bytes = 0


def note_transfer(nbytes: int, column: bool = False) -> None:
    """column=True marks COLUMN payloads (resident rows / stacked
    blocks) as opposed to per-query predicate params — the steady-state
    guard asserts column bytes specifically, because params are tiny and
    plan-keyed while columns are the link-saturating payload."""
    global _transfer_bytes, _transfer_count, _column_bytes
    with _transfer_lock:
        _transfer_bytes += int(nbytes)
        _transfer_count += 1
        if column:
            _column_bytes += int(nbytes)


def transfer_bytes() -> int:
    with _transfer_lock:
        return _transfer_bytes


def transfer_count() -> int:
    with _transfer_lock:
        return _transfer_count


def column_transfer_bytes() -> int:
    with _transfer_lock:
        return _column_bytes


class ResidencyManager:
    """Budgeted per-(segment, column) device-row tier with frequency-based
    admission on top of recency LRU.

    Keys carry (id(segment), segment name) and entries pin the segment
    object, verified by identity on every hit — a same-name/new-object
    segment (the PR-5 replace swap, an ingest re-add) can never serve a
    stale row: id() is not recycled while the entry pins the old object,
    and the new object misses. Frequency counters key on the NAME (they
    survive a version swap: the replacement inherits its plan traffic).
    """

    #: admission credit granted to warmup-seeded rows on top of the
    #: per-access bump — one replayed plan outweighs a few cold touches
    SEED_BOOST = 3

    def __init__(self, budget_bytes: int, admission: bool = True,
                 sample_window: int = 4096, metrics=None,
                 labels: Optional[Dict[str, str]] = None,
                 devices=None):
        self.budget_bytes = max(0, int(budget_bytes))
        self.enabled = self.budget_bytes > 0
        self.admission = bool(admission)
        self.sample_window = max(64, int(sample_window))
        #: mesh devices (multi-chip engines): a resident row commits
        #: whole to ONE chip, so the pool (the engine passes the
        #: per-chip knob times its chips) splits evenly into per-chip
        #: shares and eviction/pressure watch the most-loaded chip —
        #: one hot chip OOMs alone long before the pool looks full
        self.devices = list(devices) if devices else []
        n = len(self.devices)
        self.device_budget_bytes = \
            self.budget_bytes // n if n > 1 else self.budget_bytes
        self._metrics = metrics
        self._labels = labels
        self._lock = threading.RLock()
        #: key -> (segment, device row, nbytes, device label); LRU order
        self._entries: "OrderedDict[tuple, Tuple[Any, Any, int, str]]" = \
            OrderedDict()
        self._bytes = 0
        #: device label -> resident bytes (labeled admissions only)
        self._dev_bytes: Dict[str, int] = {}
        #: (segment name, kind, col) -> access count (TinyLFU sketch —
        #: a plain dict is exact and bounded by the halving pass)
        self._freq: Dict[tuple, int] = {}
        self._obs = 0
        self._seeding = threading.local()
        # plain tallies (cheap asserts in tests; the metrics registry
        # carries the same numbers for ops)
        self.hits = 0
        self.misses = 0
        self.admitted = 0
        self.rejected = 0
        self.evicted = 0

    # -- keys -----------------------------------------------------------
    @staticmethod
    def _key(seg, kind: str, col: str, dtype_str: str) -> tuple:
        return (id(seg), seg.name, kind, col, dtype_str)

    @staticmethod
    def _fkey(seg, kind: str, col: str) -> tuple:
        return (seg.name, kind, col)

    # -- seeding (warmup replay) ---------------------------------------
    @contextlib.contextmanager
    def seeding(self):
        """Accesses inside this context are warmup-seeded: admissions
        bypass the frequency duel and carry SEED_BOOST extra credit."""
        depth = getattr(self._seeding, "depth", 0)
        self._seeding.depth = depth + 1
        try:
            yield
        finally:
            self._seeding.depth = depth

    @property
    def seeding_active(self) -> bool:
        return getattr(self._seeding, "depth", 0) > 0

    # -- metering -------------------------------------------------------
    def _meter(self, name: str, value: float = 1) -> None:
        if self._metrics is not None:
            self._metrics.add_meter(name, value, labels=self._labels)

    def _touch(self, fkey: tuple, n: int = 1) -> None:
        self._freq[fkey] = self._freq.get(fkey, 0) + n
        self._obs += n
        if self._obs >= self.sample_window:
            # aging: halve everything so popularity is RECENT popularity
            # (and the dict stays bounded — zeroed keys drop out)
            self._freq = {k: v // 2 for k, v in self._freq.items()
                          if v // 2 > 0}
            self._obs //= 2

    # -- access ---------------------------------------------------------
    def get(self, seg, kind: str, col: str, dtype_str: str):
        """The resident device row for (seg, kind, col), or None on miss.
        Every call counts toward the column's admission frequency."""
        if not self.enabled:
            return None
        key = self._key(seg, kind, col, dtype_str)
        with self._lock:
            boost = self.SEED_BOOST if self.seeding_active else 0
            self._touch(self._fkey(seg, kind, col), 1 + boost)
            entry = self._entries.get(key)
            if entry is not None and entry[0] is seg:
                self._entries.move_to_end(key)
                self.hits += 1
                self._meter("hbm_resident_hit")
                return entry[1]
            self.misses += 1
            self._meter("hbm_resident_miss")
            return None

    def admit(self, seg, kind: str, col: str, dtype_str: str, dev_row,
              nbytes: int, device: Optional[str] = None) -> bool:
        """Offer an uploaded row for retention. Returns True if resident.
        Rejection never fails the query — the caller keeps its transient
        reference; the tier just declines to retain the bytes. `device`
        names the chip holding the row (multi-chip meshes): the row then
        charges THAT chip's share of the budget, so a skewed chip evicts
        (or declines) on its own while the others stay warm."""
        if not self.enabled or nbytes > self.budget_bytes:
            return False
        dlabel = device or ""
        if dlabel and nbytes > self.device_budget_bytes:
            return False
        key = self._key(seg, kind, col, dtype_str)
        fkey = self._fkey(seg, kind, col)
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[2]
                if old[3]:
                    self._dev_bytes[old[3]] -= old[2]
            seeded = self.seeding_active
            cand = self._freq.get(fkey, 0)

            # the candidate's own chip first: on a mesh the per-chip
            # share is the binding constraint (a row never spans chips)
            while dlabel and self._dev_bytes.get(dlabel, 0) + nbytes \
                    > self.device_budget_bytes:
                vkey = next((k for k, e in self._entries.items()
                             if e[3] == dlabel), None)
                if vkey is None:
                    break
                if not self._evict_one_locked(vkey, cand, seeded):
                    return False
            while self._bytes + nbytes > self.budget_bytes and self._entries:
                if not self._evict_one_locked(next(iter(self._entries)),
                                              cand, seeded):
                    return False
            self._entries[key] = (seg, dev_row, int(nbytes), dlabel)
            self._bytes += int(nbytes)
            if dlabel:
                self._dev_bytes[dlabel] = \
                    self._dev_bytes.get(dlabel, 0) + int(nbytes)
            self.admitted += 1
            return True

    def _evict_one_locked(self, vkey, cand: int, seeded: bool) -> bool:
        """TinyLFU duel for one eviction victim (caller holds the lock).
        Returns False when the victim is at least as hot as the admission
        candidate — decline retention; this is what stops a cold scan
        flushing the working set."""
        vfreq = self._freq.get((vkey[1], vkey[2], vkey[3]), 0)
        if self.admission and not seeded and cand <= vfreq:
            self.rejected += 1
            self._meter("hbm_admission_rejected")
            return False
        _vseg, _vdev, vnb, vlab = self._entries.pop(vkey)
        self._bytes -= vnb
        if vlab:
            self._dev_bytes[vlab] -= vnb
        self.evicted += 1
        self._meter("hbm_evicted")
        return True

    # -- invalidation ---------------------------------------------------
    def invalidate_segment(self, name: str, keep=None) -> int:
        """Drop resident rows for a replaced/removed segment NAME,
        sparing entries pinned to `keep` (the just-warmed live object).
        Identity keying already guarantees a new object misses; this
        reclaims the old version's HBM promptly. Frequency counters are
        kept — the replacement inherits its column traffic."""
        with self._lock:
            stale = [k for k, e in self._entries.items()
                     if k[1] == name and (keep is None or e[0] is not keep)]
            for k in stale:
                _seg, _dev, nb, lab = self._entries.pop(k)
                self._bytes -= nb
                if lab:
                    self._dev_bytes[lab] -= nb
                self.evicted += 1
                self._meter("hbm_evicted")
            return len(stale)

    def invalidate_superseded_kind(self, seg, kind_prefix: str,
                                   keep_kind: str, col: str) -> int:
        """Drop this segment's resident rows whose kind starts with
        `kind_prefix` but is not `keep_kind` — the version-stamped vmask
        rows: every bitmap mutation admits a fresh 'vmask:<stamp>' row,
        and without this purge the unreachable old-stamp rows would
        squat in the HBM budget until LRU pressure evicts live columns
        (the assembled-block cache gets the same purge engine-side)."""
        with self._lock:
            stale = [k for k, e in self._entries.items()
                     if e[0] is seg and k[3] == col
                     and k[2].startswith(kind_prefix) and k[2] != keep_kind]
            for k in stale:
                _seg, _dev, nb, lab = self._entries.pop(k)
                self._bytes -= nb
                if lab:
                    self._dev_bytes[lab] -= nb
                self.evicted += 1
                self._meter("hbm_evicted")
            return len(stale)

    def drop_all(self) -> None:
        """Bench/test hook: release every resident row (references only —
        in-flight kernels still hold theirs)."""
        with self._lock:
            self._entries.clear()
            self._bytes = 0
            self._dev_bytes.clear()

    # -- introspection --------------------------------------------------
    @property
    def bytes(self) -> int:
        with self._lock:
            return self._bytes

    def bytes_by_device(self) -> Dict[str, int]:
        """Resident bytes per chip label. Only labeled admissions count —
        single-device engines never label, so this is empty there."""
        with self._lock:
            return dict(self._dev_bytes)

    def max_device_bytes(self) -> int:
        """The most-loaded chip's resident bytes (pooled bytes when no
        admission was ever labeled — one device IS the max chip)."""
        with self._lock:
            if self._dev_bytes:
                return max(self._dev_bytes.values())
            return self._bytes

    def pressure(self) -> float:
        """Budget fraction the admission plane gates on: the most-loaded
        chip's fill of its per-chip share on a mesh (one hot chip OOMs
        alone — the pooled number hides that), the pooled fill
        otherwise. 0.0 when unbudgeted."""
        with self._lock:
            if not self.enabled:
                return 0.0
            if self._dev_bytes and self.device_budget_bytes:
                return max(self._dev_bytes.values()) \
                    / self.device_budget_bytes
            return self._bytes / self.budget_bytes

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def resident_for(self, name: str) -> int:
        """Resident row count for a segment name (tests)."""
        with self._lock:
            return sum(1 for k in self._entries if k[1] == name)

    def resident_bytes_by_segment(self) -> Dict[str, int]:
        """Resident bytes keyed by segment NAME — the instance-sweep
        residency payload's raw material (the server maps names to
        tables; brokers then prefer replicas already holding a table's
        columns in HBM)."""
        with self._lock:
            out: Dict[str, int] = {}
            for k, e in self._entries.items():
                out[k[1]] = out.get(k[1], 0) + e[2]
            return out

    def frequency(self, name: str, kind: str, col: str) -> int:
        with self._lock:
            return self._freq.get((name, kind, col), 0)
