"""Segment warmup: replay recently cached query plans on segment load.

ROADMAP item delivered: a rollout of a fresh immutable segment should not
start cold. Every time the server caches a tier-2 partial it also logs
(table, plan fingerprint, canonical SQL) into a per-table recency log;
when a new immutable segment arrives, the warmup pass replays the logged
plans against JUST that segment — populating the segment cache (and,
through a tiered backend, the shared remote tier) AND proactively staging
the plans' columns into device HBM residency (ops/residency.py, under the
seeding context so admission favors them) — BEFORE the segment is
published for queries. The first routed query then hits tier 2 instead of
scanning, and even a cache-missing literal variant runs device-resident.

The log stores the SQL, not a parsed context: QueryContext is cheap to
rebuild, and SQL is the only representation that round-trips the plan
fingerprint exactly (fingerprint() is derived from the parsed tree).

Failure semantics: warmup is strictly best-effort — any per-plan error is
swallowed (the segment still loads, it just starts cold for that plan),
and the pass is bounded by `max_plans` so a hot table's log can't stall
segment rollout.
"""
from __future__ import annotations

import json
import logging
import os
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

log = logging.getLogger(__name__)


class FingerprintLog:
    """Per-table bounded recency log: plan fingerprint -> canonical SQL.

    Re-recording an already-logged fingerprint refreshes its recency (an
    OrderedDict move-to-end), so the replay set tracks the CURRENT
    dashboard mix, not the first N plans ever seen.

    journal_path (ROADMAP item): an append-only JSON-lines journal of
    every record(), reloaded at construction — a RESTARTED server warms
    fresh segments from its pre-restart traffic instead of an empty log.
    The journal compacts to a snapshot of the live (bounded) plan set
    whenever it grows past journal_max_bytes, via atomic tmp+rename.
    Torn/corrupt journals degrade line-by-line to whatever parses (a
    half-written tail costs one plan, never the log); an unreadable file
    degrades to empty. Journal I/O failures are swallowed — persistence
    is an optimization, the in-memory log is the source of truth."""

    def __init__(self, max_plans_per_table: int = 64,
                 journal_path: Optional[str] = None,
                 journal_max_bytes: int = 1 << 20):
        self.max_plans_per_table = max(1, int(max_plans_per_table))
        self._tables: Dict[str, "OrderedDict[str, tuple]"] = {}
        self._lock = threading.Lock()
        self.journal_path = journal_path
        self.journal_max_bytes = max(4096, int(journal_max_bytes))
        #: kept-open append handle + in-memory size mirror: record() is
        #: on the query path, so it pays one buffered write + flush, not
        #: an open/close + getsize syscall pair per plan
        self._journal_file = None
        self._journal_bytes = 0
        if journal_path:
            self._replay_journal()

    # -- journal -------------------------------------------------------
    def _replay_journal(self) -> None:
        try:
            # errors="replace": a binary-garbage journal must degrade to
            # per-line JSON failures (skipped below), not a decode crash
            with open(self.journal_path, encoding="utf-8",
                      errors="replace") as f:
                lines = f.readlines()
        except OSError:
            return  # no journal yet (first boot) or unreadable: start cold
        for raw in lines:
            try:
                e = json.loads(raw)
                table, fp, sql = e["t"], e["f"], e["s"]
            except (ValueError, TypeError, KeyError):
                continue  # torn/corrupt line: skip it, keep the rest
            plans = self._tables.setdefault(table, OrderedDict())
            if fp in plans:
                plans.move_to_end(fp)
            plans[fp] = (sql, e.get("x"))
            while len(plans) > self.max_plans_per_table:
                plans.popitem(last=False)

    def _append_journal_locked(self, table: str, fingerprint: str,
                               sql: str, extra_filter) -> None:
        line = json.dumps({"t": table, "f": fingerprint, "s": sql,
                           "x": extra_filter}) + "\n"
        try:
            if self._journal_file is None:
                self._journal_file = open(self.journal_path, "a",
                                          encoding="utf-8")
                self._journal_bytes = os.path.getsize(self.journal_path)
            self._journal_file.write(line)
            self._journal_file.flush()  # torn tail = at most one line
            self._journal_bytes += len(line.encode("utf-8"))
            if self._journal_bytes > self.journal_max_bytes:
                self._compact_locked()
        except OSError:
            log.debug("fingerprint journal write failed", exc_info=True)

    def _compact_locked(self) -> None:
        """Rewrite the journal as a snapshot of the LIVE plan set (the
        bound already dropped everything else), atomically: a crash
        mid-compaction leaves either the old or the new file, never a
        mix."""
        if self._journal_file is not None:
            self._journal_file.close()
            self._journal_file = None
        tmp = self.journal_path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            for table, plans in self._tables.items():
                for fp, (sql, extra) in plans.items():
                    f.write(json.dumps({"t": table, "f": fp, "s": sql,
                                        "x": extra}) + "\n")
        os.replace(tmp, self.journal_path)
        self._journal_bytes = os.path.getsize(self.journal_path)

    def close(self) -> None:
        """Release the journal handle (in-memory state stays usable)."""
        with self._lock:
            if self._journal_file is not None:
                try:
                    self._journal_file.close()
                except OSError:
                    pass
                self._journal_file = None

    def record(self, table: str, fingerprint: str, sql: str,
               extra_filter: Optional[str] = None) -> None:
        """extra_filter: the hybrid time-boundary predicate that was
        ANDed into the plan server-side — the fingerprint covers the
        merged tree, so replay needs it to reproduce the same key."""
        with self._lock:
            plans = self._tables.setdefault(table, OrderedDict())
            if fingerprint in plans:
                plans.move_to_end(fingerprint)
            plans[fingerprint] = (sql, extra_filter)
            while len(plans) > self.max_plans_per_table:
                plans.popitem(last=False)
            if self.journal_path:
                self._append_journal_locked(table, fingerprint, sql,
                                            extra_filter)

    def plans(self, table: str) -> List[Tuple[str, str, Optional[str]]]:
        """[(fingerprint, sql, extra_filter)] most-recent-last."""
        with self._lock:
            return [(fp, sql, extra)
                    for fp, (sql, extra)
                    in self._tables.get(table, OrderedDict()).items()]

    def __len__(self) -> int:
        with self._lock:
            return sum(len(p) for p in self._tables.values())


class SegmentWarmup:
    """The warmup pass: replay a table's logged plans on one segment."""

    def __init__(self, fingerprint_log: FingerprintLog, segment_cache,
                 max_plans: int = 32, use_tpu: bool = False,
                 engine_fn=None, metrics=None,
                 labels: Optional[dict] = None):
        """engine_fn: zero-arg callable returning the server's shared
        device engine (or None) — resolved lazily per warmup so the
        engine exists by the time segments start arriving."""
        self.log = fingerprint_log
        self.segment_cache = segment_cache
        self.max_plans = max(1, int(max_plans))
        self.use_tpu = use_tpu
        self._engine_fn = engine_fn
        self._metrics = metrics
        self._labels = labels
        #: local tallies (cheap asserts in tests)
        self.segments_warmed = 0
        self.entries_warmed = 0
        #: plans whose columns were prestaged into HBM residency for a
        #: NON-cacheable (upsert) segment — the seal pipeline's
        #: warm-before-swap evidence for tables the result cache skips
        self.segments_prestaged = 0

    def warm(self, table: str, segment: Any) -> int:
        """Replay logged plans against `segment`; returns entries warmed.
        Never raises — a failed warmup only costs cold-start."""
        from pinot_tpu.cache.segment_cache import (is_cacheable_segment,
                                                   is_cacheable_shape)
        from pinot_tpu.query.context import QueryContext
        from pinot_tpu.query.executor import QueryExecutor

        plans = self.log.plans(table)
        if not plans:
            return 0
        # result-cache warmup needs the cache; residency PRESTAGING does
        # not — a cache-disabled deployment still wants sealed segments'
        # columns in HBM before they publish (the zero-gap pipeline)
        cache_on = (self.segment_cache is not None
                    and self.segment_cache.enabled)
        cacheable = cache_on and is_cacheable_segment(segment)
        if not cache_on and self._engine_fn is None:
            return 0  # nothing to warm with
        warmed = 0
        # most recent plans first — when the budget cuts, keep the mix
        # dashboards are refreshing NOW
        for fingerprint, sql, extra_filter in reversed(
                plans[-self.max_plans:]):
            try:
                ctx = QueryContext.from_sql(sql)
                # the SAME merge the server execute path applies — the
                # fingerprint hashes the merged tree, so any divergence
                # would warm keys no routed query ever looks up
                from pinot_tpu.query.context import merge_extra_filter
                merge_extra_filter(ctx, extra_filter)
                if not is_cacheable_shape(ctx):
                    continue
                engine = self._engine_fn() if self._engine_fn else None
                if not cacheable:
                    # upsert segments never enter the result cache (their
                    # validity bitmap mutates in place), but their column
                    # + mask blocks still belong in HBM before the seal
                    # swap publishes them — the zero-gap pipeline's
                    # residency half applies regardless of cacheability
                    if engine is not None:
                        with engine.residency.seeding():
                            if engine.prestage([segment], ctx):
                                self.segments_prestaged += 1
                    continue
                if self.segment_cache.get(segment, fingerprint) is not None:
                    # already warm — an L2 hit here ALSO back-filled L1,
                    # which is exactly the rollout warmup we want. The
                    # DEVICE tier still starts cold on a result-cache
                    # hit, so stage the plan's columns into HBM anyway:
                    # literals drift, caches expire, and the resident
                    # columns are what survive both
                    warmed += 1
                    if engine is not None:
                        with engine.residency.seeding():
                            engine.prestage([segment], ctx)
                    continue
                ex = QueryExecutor([segment], use_tpu=self.use_tpu,
                                   engine=engine,
                                   segment_cache=self.segment_cache)
                if engine is not None:
                    # replayed plans ARE the FingerprintLog's evidence of
                    # per-segment plan traffic: staging done under the
                    # seeding context admits the columns into HBM
                    # residency with the frequency seed, so the fresh
                    # segment's first routed queries run device-resident
                    with engine.residency.seeding():
                        ex.execute_context(ctx)
                else:
                    ex.execute_context(ctx)
                if self.segment_cache.get(segment, fingerprint) is not None:
                    warmed += 1
            except Exception:  # noqa: BLE001 — warmup must never block load
                log.debug("warmup plan failed for %s on %s",
                          fingerprint, getattr(segment, "name", "?"),
                          exc_info=True)
        if warmed:
            self.segments_warmed += 1
            self.entries_warmed += warmed
            if self._metrics is not None:
                self._metrics.add_meter("segment_warmup_segments",
                                        labels=self._labels)
                self._metrics.add_meter("segment_warmup_entries", warmed,
                                        labels=self._labels)
        return warmed
