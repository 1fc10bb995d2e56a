"""Server-side query executor: prune, fan out over segments, combine.

Reference parity: pinot-core
query/executor/ServerQueryExecutorV1Impl.java:94,159 (segment acquisition +
pruning + plan + execute) and operator/combine/BaseCombineOperator.java:54
(fan N segment plans over worker threads, merge results). The TPU twist:
instead of one thread per segment, dict-encoded scan shapes are STACKED
into [num_segments, padded_docs] device blocks and executed as ONE jit'd
kernel over the mesh's `segments` axis (ops/engine.py); shapes the device
engine doesn't cover fall back per-segment to the numpy reference path.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, List, Optional, Sequence

from pinot_tpu.query import executor_cpu
from pinot_tpu.ops import dispatch as dispatch_mod
from pinot_tpu.cache.core import cache_bypassed
from pinot_tpu.cache.segment_cache import is_cacheable_shape
from pinot_tpu.utils import tracing
from pinot_tpu.utils.failpoints import fire
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.pruner import prune_segments
from pinot_tpu.query.reduce import BrokerResponse, reduce_results
from pinot_tpu.query.results import ExecutionStats
from pinot_tpu.segment.loader import ImmutableSegment


class QueryExecutor:
    """Executes queries over a set of loaded segments (one 'server')."""

    def __init__(self, segments: Sequence[ImmutableSegment],
                 use_tpu: bool = True, max_threads: int = 8, engine=None,
                 segment_cache=None, cancel_check=None):
        """engine: a shared TpuOperatorExecutor. Long-lived callers (the
        server) MUST pass one — the engine owns the HBM block cache, and a
        per-request engine would re-upload every column on every query.
        segment_cache: a shared SegmentResultCache (cache/segment_cache.py)
        — same lifetime rule as the engine; None disables tier-2 caching.
        cancel_check: zero-arg callable polled between segments (the
        ResourceAccountant.check_cancelled discipline, ref
        Tracing.ThreadAccountantOps.sample in DocIdSetOperator:70) —
        raises to stop the loop when the query is cancelled or past its
        deadline. Segment granularity is the unit of work here; finer
        checks would sit inside jit'd kernels where Python can't poll."""
        self.segments = list(segments)
        self.max_threads = max_threads
        self._tpu_engine = engine
        self._use_tpu = use_tpu
        self._segment_cache = segment_cache
        self._cancel_check = cancel_check

    @property
    def tpu_engine(self):
        if self._tpu_engine is None and self._use_tpu:
            from pinot_tpu.ops.engine import TpuOperatorExecutor
            self._tpu_engine = TpuOperatorExecutor()
        return self._tpu_engine

    # ------------------------------------------------------------------
    def execute_context(self, ctx: QueryContext):
        """Per-segment results for a query context (server-side half).
        Returns (results, prune_stats)."""
        selected = prune_segments(self.segments, ctx)
        selected_set = set(id(s) for s in selected)
        prune_stats = ExecutionStats()
        for seg in self.segments:
            if id(seg) not in selected_set:
                prune_stats.num_segments_pruned += 1
                prune_stats.total_docs += seg.num_docs
        results: List[Any] = []

        # tier-2 segment result cache: immutable segments with a cached
        # partial for this plan fingerprint skip execution entirely;
        # consuming/upsert segments never hit (is_cacheable_segment), so
        # the mutable tail of a hybrid table always re-executes
        cache = self._segment_cache
        plan_fp: Optional[str] = None
        to_run = selected
        cache_hits = 0
        if cache is not None and cache.enabled and is_cacheable_shape(ctx) \
                and not cache_bypassed(ctx.options):
            plan_fp = ctx.fingerprint()
            with tracing.Scope("SegmentResultCache") as sc:
                to_run = []
                # a device GROUP BY is cached as the ONE folded partial
                # of its whole batch, every other shape a segment
                folded = cache.get_batch(selected, plan_fp) \
                    if self._use_tpu and ctx.group_by else None
                if folded is not None:
                    results.append(folded)
                    cache_hits = len(selected)
                for s in () if folded is not None else selected:
                    hit = cache.get(s, plan_fp)
                    if hit is not None:
                        results.append(hit)
                        cache_hits += 1
                    else:
                        to_run.append(s)
                sc.set(cacheHit=cache_hits > 0, cacheHits=cache_hits,
                       cacheMisses=len(to_run))
            # mirror on the enclosing request node so trace consumers see
            # cacheHit without walking children
            tracing.annotate(cacheHit=cache_hits > 0)

        # consuming (mutable) segments always run host-side: their columns
        # are unsorted-dict/append buffers, not stageable blocks. Upsert
        # segments with live validDocIds DO ride the device path: the
        # engine stages the bitmap as a version-stamped mask block and
        # ANDs it in-kernel (plan.valid_mask), so upsert/dedup tables
        # share the same jit(vmap) coalesced launches as append-only ones
        device_candidates = [
            s for s in to_run if isinstance(s, ImmutableSegment)]
        dc = set(id(s) for s in device_candidates)
        host_only = [s for s in to_run if id(s) not in dc]
        remaining = device_candidates
        device_fut = None
        device_results_now = None
        if self._use_tpu and device_candidates:
            if self._cancel_check is not None:
                self._cancel_check()
            engine = self.tpu_engine
            if engine is not None and not engine.supports(ctx):
                engine.scan_fallback("unsupported")
            elif engine is not None:
                if host_only:
                    # staging + launch ride the engine's dispatch
                    # pipeline; the future resolves off-thread, so this
                    # server thread executes its host-path segments IN
                    # PARALLEL with the device round trip instead of
                    # after it
                    device_fut = engine.execute_async(
                        device_candidates, ctx,
                        cancel_check=self._cancel_check)
                else:
                    # nothing to overlap within this query: skip the
                    # async hop (lone-query p50 stays at the floor);
                    # cross-query overlap still happens in the ring
                    device_results_now, remaining = engine.execute(
                        device_candidates, ctx,
                        cancel_check=self._cancel_check)
                if device_fut is not None:
                    remaining = []

        # captured on the REQUEST thread: run_one executes on pool
        # workers where the accounting thread-local doesn't flow (the
        # span-handle discipline) — cache puts there still charge the
        # query's miss bytes
        from pinot_tpu.utils import accounting
        slip = accounting.current_slip()

        def run_one(s):
            # cooperative cancel poll per segment: a deadline-expired
            # or broker-cancelled query stops HERE instead of
            # finishing work nobody will read (the failpoint site
            # lets chaos tests make each segment arbitrarily slow)
            if self._cancel_check is not None:
                self._cancel_check()
            fire("server.execute.segment",
                 segment=getattr(s, "name", None))
            with accounting.charging(slip):
                r = executor_cpu.execute_segment(s, ctx)
                if plan_fp is not None:
                    cache.put(s, plan_fp, r)  # no-op for mutable segments
            return r

        def run_host(seg_list):
            if not seg_list:
                return []
            if len(seg_list) == 1:
                return [run_one(seg_list[0])]
            with ThreadPoolExecutor(
                    max_workers=min(len(seg_list), self.max_threads)) as pool:
                return list(pool.map(run_one, seg_list))

        # host-only segments overlap the in-flight device future
        host_results = run_host(host_only)
        if device_fut is not None:
            # bounded by the query's deadline/cancel checker when one is
            # attached; callers without one (no query id, MSE leaf path,
            # warmup replay) fall back to wait_result's default hard cap
            # so a stranded engine future can never park this thread
            device_results_now, remaining = dispatch_mod.wait_result(
                device_fut, self._cancel_check)
        if device_results_now is not None:
            results.extend(device_results_now)
            # engine results are positional per candidate when nothing
            # fell back; only then is the segment<->result mapping
            # known for cache population
            if plan_fp is not None and not remaining \
                    and len(device_results_now) == len(device_candidates):
                for s, r in zip(device_candidates, device_results_now):
                    cache.put(s, plan_fp, r)
            elif plan_fp is not None and not remaining \
                    and len(device_results_now) == 1 \
                    and len(device_candidates) == len(selected):
                # one result for the batch (the engine folded a GROUP
                # BY's partials on the device), and the batch is all the
                # query selected: the key `get_batch` will look under
                cache.put_batch(selected, plan_fp, device_results_now[0])
        results.extend(host_results)
        # device fallbacks (shapes/columns the engine rejected) run last
        results.extend(run_host(list(remaining)))
        return results, prune_stats

    def execute(self, sql: str) -> BrokerResponse:
        """Full single-process path: parse -> execute -> reduce
        (the BaseQueriesTest.getBrokerResponse analog)."""
        start = time.time()
        ctx = QueryContext.from_sql(sql)
        trace_on = ctx.options.get("trace", "false").lower() == "true"
        req_trace = tracing.RequestTrace() if trace_on else None
        if req_trace is not None:
            with req_trace:
                results, prune_stats = self.execute_context(ctx)
                with tracing.Scope("BrokerReduce"):
                    resp = reduce_results(ctx, results)
        else:
            results, prune_stats = self.execute_context(ctx)
            resp = reduce_results(ctx, results)
        resp.stats.merge(prune_stats)
        if req_trace is not None:
            resp.trace = req_trace.to_dict()
        resp.num_servers_queried = resp.num_servers_responded = 1
        resp.time_used_ms = (time.time() - start) * 1000.0
        return resp
