"""Server transport + per-server query execution.

Reference parity: pinot-core transport — QueryServer (Netty) +
InstanceRequestHandler.channelRead0 (transport/InstanceRequestHandler.java:122)
+ QueryScheduler.submit (query/scheduler/QueryScheduler.java:93). Here:
an asyncio TCP server speaking length-prefixed frames:

  request : u32 len | JSON {requestId, tableName, sql, segments?: [...]}
  response: u32 len | DataTable bytes (server/datatable.py)

Execution itself reuses QueryExecutor (pruning + device engine + host
fallback) over the acquired segments; a thread pool keeps the event loop
free (FCFS scheduling, the QuerySchedulerFactory default).
"""
from __future__ import annotations

import asyncio
import json
import socket
import struct
import threading
import time
from typing import List, Optional

from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.executor import QueryExecutor
from pinot_tpu.server import datatable
from pinot_tpu.server.data_manager import InstanceDataManager, TableDataManager
from pinot_tpu.utils import errorcodes, tracing
from pinot_tpu.utils.accounting import (BrokerTimeoutError,
                                        QueryCancelledError,
                                        ResourceAccountant,
                                        ServerOverloadedError)
from pinot_tpu.utils.failpoints import fire

_LEN = struct.Struct("<I")

#: extra seconds a broker-side socket read waits past the shipped budget —
#: covers the server's own deadline grace + scheduling jitter, so the
#: server's typed 250 response (not a raw socket timeout) is the normal
#: way a deadline surfaces
_SOCKET_GRACE_S = 2.0


def _timeout_response(e: BaseException) -> bytes:
    """The typed deadline-miss payload (ref QueryException
    EXECUTION_TIMEOUT_ERROR_CODE): empty results + an errorCode-250
    entry; the broker merges it as a partial, never a hang."""
    return datatable.serialize_results(
        [], [{"errorCode": BrokerTimeoutError.ERROR_CODE,
              "message": f"BrokerTimeoutError: {e}"}])


def _overload_response(e: ServerOverloadedError) -> bytes:
    """The typed admission-rejection payload: errorCode-211, no
    results, the drain hint embedded in the message (the exception wire
    format is (code, message) tuples — see datatable._exc_tuple — so
    the hint travels in-band, formatted/parsed through the shared
    errorcodes helpers). The hint is floored even for scheduler-
    backstop rejections that carry retry_after_ms=0 — a client told
    "retry now" would tight-loop against the saturated server."""
    hint = errorcodes.format_retry_after(max(10.0, e.retry_after_ms))
    return datatable.serialize_results(
        [], [{"errorCode": ServerOverloadedError.ERROR_CODE,
              "message": f"ServerOverloadedError: {e.reason or e} "
                         f"{hint}"}])


class ServerQueryExecutor:
    """Ref ServerQueryExecutorV1Impl: executes one query over this server's
    segments for a table."""

    def __init__(self, data_manager: InstanceDataManager, use_tpu: bool = True,
                 config=None):
        self.data_manager = data_manager
        self.use_tpu = use_tpu
        #: instance config (PinotConfiguration); threads through to the
        #: device engine's cache budgets and the streaming chunk size
        self.config = config
        #: per-query deadline/cancel registry: the broker ships the
        #: REMAINING budget with each request, and a broker-side expiry
        #: sends an explicit cancel keyed by the query id — either way
        #: the segment loop's cooperative checks stop abandoned work
        self.accountant = ResourceAccountant()
        self.deadline_grace_s = (
            config.get_int("pinot.server.query.deadline.grace.ms") / 1000.0
            if config is not None else 0.05)
        #: distributed tracing: open a server-side span tree per traced
        #: request and ship it back in the response (utils/tracing.py)
        if config is not None:
            self._trace_enabled = config.get_bool(
                "pinot.trace.enabled", True)
            self._slow_threshold_ms = config.get_float(
                "pinot.server.slow.query.threshold.ms")
            self._trace_capacity = config.get_int(
                "pinot.trace.store.capacity")
        else:
            self._trace_enabled = True
            self._slow_threshold_ms = 0.0
            self._trace_capacity = None
        tracing.install_gc_probe("server")
        #: latency-SLO target — queries over it bump the slo_latency_bad
        #: counter the burn-rate watchdog reads as windowed deltas
        self._slo_p99_ms = (config.get_float("pinot.slo.query.p99.ms")
                            if config is not None else 0.0)
        if config is not None:
            # the catalog default applies whenever a config is present
            # (the class attribute only backs config-less construction)
            self.STREAM_CHUNK_SEGMENTS = config.get_int(
                "pinot.server.stream.chunk.segments")
        #: per-query workload accounting (ChargeSlip + WorkloadStats
        #: rollup); off = the bench --health A-side
        self._accounting_enabled = (
            config is None or config.get_bool(
                "pinot.workload.accounting.enabled", True))
        #: ONE engine for the server's lifetime — it owns the HBM block
        #: cache, which must survive across requests
        self._engine = None
        self._engine_lock = threading.Lock()
        #: extra memory-pressure inputs for admission (0..1 fractions):
        #: ServerRole registers realtime-ingest bytes vs budget here;
        #: the residency tier is consulted built-in (memory_pressure)
        self._pressure_sources = []
        #: tier-2 per-segment partial-result cache — shared across requests
        #: for the same reason as the engine. Version-keyed entries go
        #: stale-unaddressable on replace; the data-manager hook below
        #: additionally reclaims their bytes promptly.
        from pinot_tpu.cache.segment_cache import SegmentResultCache
        from pinot_tpu.cache.warmup import FingerprintLog, SegmentWarmup
        from pinot_tpu.utils.metrics import get_registry
        labels = {"instance": data_manager.instance_id}
        if config is not None:
            self.segment_cache = SegmentResultCache.from_config(
                config, metrics=get_registry("server"), labels=labels)
        else:
            self.segment_cache = SegmentResultCache(
                metrics=get_registry("server"), labels=labels)
        data_manager.add_segment_listener(self._on_segment_event)
        # warmup fabric: log cacheable plans per table; replay them on
        # every fresh immutable segment BEFORE it serves queries, so a
        # rollout's first routed query hits tier 2 (cache/warmup.py)
        warm_on = (config is None or config.get_bool(
            "pinot.server.segment.warmup.enabled", True))
        log_size = (config.get_int(
            "pinot.server.segment.warmup.log.plans.per.table")
            if config is not None else 64)
        max_plans = (config.get_int("pinot.server.segment.warmup.max.plans")
                     if config is not None else 32)
        # a knob explicitly set to 0 means OFF (the classes themselves
        # clamp to >=1, so 0 must be honored here, not passed through)
        warm_on = warm_on and log_size > 0 and max_plans > 0
        self._plan_log_enabled = warm_on
        # journal (ROADMAP): persist the plan log so a restart warms from
        # pre-restart traffic; one file per instance, off when dir unset
        journal_path = None
        journal_max = 1 << 20
        if config is not None:
            journal_dir = config.get_str(
                "pinot.server.segment.warmup.journal.dir")
            if journal_dir:
                import os
                os.makedirs(journal_dir, exist_ok=True)
                journal_path = os.path.join(
                    journal_dir, f"{data_manager.instance_id}.fplog.jsonl")
                journal_max = config.get_int(
                    "pinot.server.segment.warmup.journal.max.bytes")
        self.fingerprint_log = FingerprintLog(max(1, log_size),
                                              journal_path=journal_path,
                                              journal_max_bytes=journal_max)
        self.warmup = SegmentWarmup(
            self.fingerprint_log, self.segment_cache,
            max_plans=max(1, max_plans), use_tpu=use_tpu,
            engine_fn=self._shared_engine,
            metrics=get_registry("server"), labels=labels)
        if warm_on:
            data_manager.set_warmup_hook(self.warmup.warm)

    def _on_segment_event(self, event: str, table_name: str,
                          segment_name: str) -> None:
        """TableDataManager version-bump hook: drop cached partials for a
        replaced/removed segment immediately (version keying already makes
        them unreachable; this reclaims the bytes). On replace, entries
        for the LIVE version are spared — warmup just populated them
        (add_segment warms before the swap commits), and wiping them
        would re-introduce the rollout cold start warmup exists to
        remove."""
        if event not in ("replace", "remove"):
            return
        keep = keep_obj = None
        if event == "replace":
            from pinot_tpu.cache.segment_cache import segment_version
            tdm = self.data_manager.table(table_name, create=False)
            if tdm is not None:
                keep_obj = tdm.current_segment(segment_name)
                if keep_obj is not None:
                    keep = segment_version(keep_obj)
        self.segment_cache.invalidate_segment(segment_name,
                                              except_version=keep)
        # device tier rides the same epoch-moving event: drop the old
        # version's resident rows / assembled blocks / params promptly
        # (identity keys already make them unreachable), sparing the
        # just-warmed live object's entries
        engine = self._engine
        if engine is not None:
            engine.invalidate_segment(segment_name, keep=keep_obj)

    def _record_plan(self, table_name: str, ctx, sql_or_ctx,
                     extra_filter) -> None:
        """Feed the warmup fingerprint log: cacheable-shape queries only
        (the replay would be a no-op otherwise), and only when the raw
        SQL is available to replay. extra_filter (the hybrid
        time-boundary predicate) is logged alongside — the fingerprint
        covers the MERGED filter tree, so replay must merge it too."""
        if not self._plan_log_enabled or not isinstance(sql_or_ctx, str):
            return
        from pinot_tpu.cache.core import cache_bypassed
        from pinot_tpu.cache.segment_cache import is_cacheable_shape
        if is_cacheable_shape(ctx) and not cache_bypassed(ctx.options):
            self.fingerprint_log.record(table_name, ctx.fingerprint(),
                                        sql_or_ctx,
                                        extra_filter=extra_filter)

    def _shared_engine(self):
        if not self.use_tpu:
            return None
        with self._engine_lock:
            if self._engine is None:
                from pinot_tpu.ops.engine import TpuOperatorExecutor
                # instance labels thread through to the dispatch-ring
                # metrics (dispatch_queue_depth / dispatch_batch_size /
                # kernel_retrace / staging_overlap_ms)
                self._engine = TpuOperatorExecutor(
                    config=self.config,
                    metrics_labels={
                        "instance": self.data_manager.instance_id})
            return self._engine

    def device_report(self) -> dict:
        """The device the engine runs on, as JAX reports it (the server's
        start-up line and its /debug/device route); builds the engine —
        and with it the JAX backend — on first call. Empty when the
        device path is off."""
        engine = self._shared_engine()
        if engine is None:
            return {}
        from pinot_tpu.ops.device import device_report
        return device_report(engine.devices)

    def residency_report(self) -> dict:
        """Per-physical-table HBM-resident bytes this server can
        advertise in its heartbeat (the instance-sweep residency
        payload): brokers break replica-choice ties toward servers whose
        device memory already holds the table's columns. Empty when no
        device engine/resident tier exists — the hint is best-effort."""
        engine = self._engine
        if engine is None or not engine.residency.enabled:
            return {}
        by_seg = engine.residency.resident_bytes_by_segment()
        if not by_seg:
            return {}
        out: dict = {}
        for table in self.data_manager.table_names:
            tdm = self.data_manager.table(table, create=False)
            if tdm is None:
                continue
            total = sum(by_seg.get(n, 0) for n in tdm.segment_names)
            if total:
                out[table] = total
        return out

    def add_memory_pressure_source(self, fn) -> None:
        """Register a () -> 0..1 fraction the admission controller folds
        into its memory-pressure decision (worst-of across sources)."""
        self._pressure_sources.append(fn)

    def memory_pressure(self) -> float:
        """Worst-of memory-pressure fraction across this server's
        accountings: the HBM residency tier's fill — on a multi-chip
        mesh the MOST-LOADED chip against its per-chip share, not the
        pooled total (ResidencyManager.pressure) — plus every registered
        source (realtime-ingest bytes against the ingest memory budget,
        wired by ServerRole). 0.0 when nothing is budgeted — an
        unbudgeted server never sheds on memory."""
        worst = 0.0
        # lint: unlocked(reference snapshot; _shared_engine publishes the engine once under its lock and never unsets it)
        engine = self._engine
        if engine is not None and engine.residency.enabled:
            worst = max(worst, engine.residency.pressure())
        for fn in list(self._pressure_sources):
            try:
                worst = max(worst, float(fn()))
            except Exception:  # noqa: BLE001 — a broken source must not
                pass           # take admission down with it
        return worst

    def cancel(self, query_id) -> bool:
        """Broker-initiated cancel (rides ResourceAccountant.cancel): the
        next cooperative check in the query's segment loop raises and the
        worker thread frees. A cancel for a query still sitting in the
        scheduler queue is a no-op here — the shipped deadline kills it
        at pick-up instead."""
        return self.accountant.cancel(str(query_id))

    def execute(self, table_name: str, sql_or_ctx,
                segments: Optional[List[str]] = None,
                extra_filter: Optional[str] = None,
                query_id=None, timeout_ms: Optional[float] = None,
                deadline: Optional[float] = None,
                trace_ctx: Optional[dict] = None,
                arrival_s: Optional[float] = None,
                tenant: Optional[str] = None):
        """Returns serialized DataTable bytes (see _execute_inner for the
        execution semantics). trace_ctx: the broker-shipped TraceContext
        wire dict — when present (and tracing is enabled) this server
        opens its OWN span tree rooted at ServerRequest, records
        scheduler queue wait (arrival_s = transport read time), runs the
        query under it so engine/cache instrumentation lands in it, and
        appends the tree to the response bytes so the broker stitches
        one cross-process trace. Slow requests (and sampled ones) are
        retained in the server's trace store."""
        from pinot_tpu.utils import trace_store
        tc = tracing.TraceContext.from_wire(trace_ctx)
        if tc is None or not self._trace_enabled:
            return self._execute_inner(table_name, sql_or_ctx, segments,
                                       extra_filter, query_id, timeout_ms,
                                       deadline, tenant=tenant)
        rt = tracing.RequestTrace(
            request_id=str(query_id or ""), operator="ServerRequest",
            trace_id=tc.trace_id, sampled=tc.sampled,
            instance=self.data_manager.instance_id, table=table_name)
        if arrival_s is not None:
            rt.handle().set(queueWaitMs=round(
                max(0.0, time.time() - arrival_s) * 1000.0, 3))
        inflight = trace_store.get_inflight("server")
        key = f"{tc.trace_id}:{query_id}"
        sql_text = sql_or_ctx if isinstance(sql_or_ctx, str) else ""
        inflight.begin(key, sql=sql_text, trace_id=tc.trace_id,
                       detail=table_name, tenant=tenant, deadline=deadline)
        inflight.phase(key, "execute", table_name)
        try:
            with rt:
                payload = self._execute_inner(
                    table_name, sql_or_ctx, segments, extra_filter,
                    query_id, timeout_ms, deadline, tenant=tenant)
        finally:
            inflight.end(key)
        dur = rt.root.duration_ms
        tree = rt.to_dict()
        slow = (self._slow_threshold_ms > 0
                and dur >= self._slow_threshold_ms)
        if tc.sampled or slow:
            # key carries the instance: two embedded servers sharing a
            # process (and therefore the role store) both record the
            # same trace id for one scattered query — they must not
            # overwrite each other (TraceStore.get scans by traceId)
            trace_store.get_store(
                "server", self._trace_capacity).record(
                f"{tc.trace_id}@{self.data_manager.instance_id}",
                tree, sql=sql_text, duration_ms=dur, slow=slow,
                extra={"traceId": tc.trace_id,
                       "instance": self.data_manager.instance_id})
            if slow:
                trace_store.log_slow_query(
                    "server", tc.trace_id, sql_text, dur,
                    self._slow_threshold_ms, table=table_name,
                    instance=self.data_manager.instance_id)
        from pinot_tpu.utils.metrics import get_registry
        get_registry("server").set_exemplar(
            "query_execution", {"table": table_name}, tc.trace_id)
        # the tree rides AFTER the result payload — append-compatible
        # with every reader (deserialize_results_ex picks it up)
        return payload + datatable.serialize_value(tree)

    def _execute_inner(self, table_name: str, sql_or_ctx,
                       segments: Optional[List[str]] = None,
                       extra_filter: Optional[str] = None,
                       query_id=None, timeout_ms: Optional[float] = None,
                       deadline: Optional[float] = None,
                       tenant: Optional[str] = None):
        """Returns serialized DataTable bytes. extra_filter (an expression
        string, e.g. the hybrid time-boundary predicate) is ANDed into the
        filter tree — the reference rewrites the BrokerRequest the same way.
        timeout_ms: REMAINING broker budget; the local deadline (plus a
        small grace for clock skew) cancels the segment loop
        cooperatively and answers with an errorCode-250 partial.
        deadline: ARRIVAL-anchored absolute deadline (the transport
        handler computes it when the request is read) — it wins over
        timeout_ms, which anchored here would silently extend the budget
        by however long the request waited in the scheduler queue."""
        from pinot_tpu.utils.metrics import get_registry
        metrics = get_registry("server")
        metrics.add_meter("queries", labels={"table": table_name})
        timer = metrics.time("query_execution", labels={"table": table_name})
        timer.__enter__()
        slo_t0 = time.perf_counter()
        from pinot_tpu.utils.accounting import charging
        qid = None if query_id is None else str(query_id)
        cancel_check = None
        slip = None
        if qid is not None:
            if deadline is not None:
                timeout_s = deadline - time.time()
            else:
                timeout_s = (float(timeout_ms) / 1000.0
                             + self.deadline_grace_s if timeout_ms else None)
            self.accountant.begin_query(qid, timeout_s)
            cancel_check = self.accountant.checker(qid)
            if self._accounting_enabled:
                slip = self.accountant.slip(qid)
        error = False
        try:
            fire("server.execute.before",
                 instance=self.data_manager.instance_id, table=table_name)
            ctx = (sql_or_ctx if isinstance(sql_or_ctx, QueryContext)
                   else QueryContext.from_sql(sql_or_ctx))
            from pinot_tpu.query.context import merge_extra_filter
            merge_extra_filter(ctx, extra_filter)
            if slip is not None:
                # attribution dimensions the per-(tenant, table, plan)
                # workload rollup keys on
                self.accountant.annotate(
                    qid, tenant=tenant or "", table=table_name,
                    plan_fingerprint=ctx.fingerprint())
            self._record_plan(table_name, ctx, sql_or_ctx, extra_filter)
            tdm = self.data_manager.table(table_name, create=False)
            if tdm is None:
                return datatable.serialize_results(
                    [], [{"errorCode": errorcodes.TABLE_DOES_NOT_EXIST,
                          "message": f"table {table_name} not found"}])
            sdms = tdm.acquire_segments(segments)
            try:
                ex = QueryExecutor([s.segment for s in sdms],
                                   use_tpu=self.use_tpu,
                                   engine=self._shared_engine(),
                                   segment_cache=self.segment_cache,
                                   cancel_check=cancel_check)
                # the slip rides the thread-local for the execution scope:
                # engine staging (transfer bytes), the dispatch ring
                # (kernel ms, batch-split), and the tier-2 cache
                # (hit/miss bytes) all charge this query through it
                t_exec = time.perf_counter()
                with charging(slip):
                    results, prune_stats = ex.execute_context(ctx)
                t_done = time.perf_counter()
                if slip is not None:
                    rows = sum(r.stats.num_docs_scanned for r in results)
                    entries = sum(r.stats.num_entries_scanned_in_filter
                                  + r.stats.num_entries_scanned_post_filter
                                  for r in results)
                    # bytes: dict-encoded scan entries are int32 ids —
                    # 4 bytes per entry is the storage-traffic cost
                    slip.add(rows_scanned=rows, bytes_scanned=4 * entries)
                payload = datatable.serialize_results(
                    results, extra_stats=prune_stats, metrics=metrics)
                # the request's two ends on its ServerRequest span (no-op
                # untraced): parse + segment acquire before the executor
                # runs, DataTable bytes after it; the engine's phases on
                # DeviceDispatch and assembleMs lie between them
                tracing.annotate(
                    parseMs=round((t_exec - slo_t0) * 1e3, 3),
                    serializeMs=round(
                        (time.perf_counter() - t_done) * 1e3, 3),
                    serializeBytes=len(payload))
                return payload
            finally:
                TableDataManager.release_all(sdms)
        except (QueryCancelledError, BrokerTimeoutError) as e:
            # late work is CANCELLED, not silently finished: drop any
            # half-built partials (merging them would risk double counts
            # against a hedged replica) and answer with the typed 250
            error = True
            metrics.add_meter("queries_killed", labels={"table": table_name})
            return _timeout_response(e)
        except Exception as e:  # noqa: BLE001 — server must answer, not die
            error = True
            metrics.add_meter("query_exceptions", labels={"table": table_name})
            return datatable.serialize_results(
                [], [{"errorCode": errorcodes.QUERY_EXECUTION,
                      "message": f"{type(e).__name__}: {e}"}])
        finally:
            if qid is not None:
                usage = self.accountant.finish_query(qid)
                if usage is not None and slip is not None:
                    # fold the finished query's bill into the
                    # per-(tenant, table, plan) workload rollup
                    from pinot_tpu.health.workload import get_workload
                    get_workload("server").record_usage(usage, error=error)
            timer.__exit__(None, None, None)
            if self._slo_p99_ms and (time.perf_counter() - slo_t0) \
                    * 1000.0 > self._slo_p99_ms:
                metrics.add_meter("slo_latency_bad",
                                  labels={"table": table_name})

    #: segments per streamed response frame
    STREAM_CHUNK_SEGMENTS = 4

    def execute_streaming(self, table_name: str, sql_or_ctx,
                          segments: Optional[List[str]] = None,
                          extra_filter: Optional[str] = None):
        """Per-block response frames for large results (ref
        GrpcQueryServer's streaming Submit + StreamingInstanceResponse
        PlanNode): a GENERATOR — each segment chunk executes and
        serializes lazily as the transport consumes it, so the server
        never materializes the full result and the first frame ships
        while later chunks still compute."""
        try:
            ctx = (sql_or_ctx if isinstance(sql_or_ctx, QueryContext)
                   else QueryContext.from_sql(sql_or_ctx))
            from pinot_tpu.query.context import merge_extra_filter
            merge_extra_filter(ctx, extra_filter)
            tdm = self.data_manager.table(table_name, create=False)
            if tdm is None:
                yield datatable.serialize_results(
                    [], [{"errorCode": errorcodes.TABLE_DOES_NOT_EXIST,
                          "message": f"table {table_name} not found"}])
                return
            sdms = tdm.acquire_segments(segments)
            try:
                chunk = self.STREAM_CHUNK_SEGMENTS
                segs = [s.segment for s in sdms]
                for i in range(0, max(len(segs), 1), chunk):
                    ex = QueryExecutor(segs[i:i + chunk],
                                       use_tpu=self.use_tpu,
                                       engine=self._shared_engine(),
                                       segment_cache=self.segment_cache)
                    results, prune_stats = ex.execute_context(ctx)
                    yield datatable.serialize_results(
                        results, extra_stats=prune_stats)
            finally:
                TableDataManager.release_all(sdms)
        except Exception as e:  # noqa: BLE001
            yield datatable.serialize_results(
                [], [{"errorCode": errorcodes.QUERY_EXECUTION,
                      "message": f"{type(e).__name__}: {e}"}])


class QueryServer:
    """Asyncio TCP server (the Netty QueryServer analog)."""

    def __init__(self, executor: ServerQueryExecutor, host: str = "127.0.0.1",
                 port: int = 0, num_threads: int = 8,
                 scheduler: str = "fcfs"):
        from pinot_tpu.server.admission import AdmissionController
        from pinot_tpu.server.scheduler import make_scheduler
        from pinot_tpu.utils.metrics import get_registry
        self.executor = executor
        self.host = host
        self.port = port
        #: pluggable query scheduler (ref QuerySchedulerFactory.java:45 —
        #: fcfs | priority | binary); owns the query worker threads
        self.scheduler = make_scheduler(
            scheduler, num_threads, metrics=get_registry("server"),
            labels={"instance": executor.data_manager.instance_id})
        self.scheduler.start()
        #: overload protection at the transport edge (server/admission.py):
        #: deadline-aware, memory-aware, tenant-weighted rejection BEFORE
        #: the scheduler queue; the scheduler's own bounded queue is the
        #: backstop for submissions racing the controller's estimate
        self.admission = AdmissionController.from_config(
            executor.config, num_threads=num_threads,
            tenant_weights_fn=self.scheduler.tenant_weights,
            memory_pressure_fn=executor.memory_pressure,
            metrics=get_registry("server"),
            labels={"instance": executor.data_manager.instance_id})
        self.scheduler.set_queue_limit(
            self.admission.queue_limit if self.admission.enabled else 0)
        self._server: Optional[asyncio.AbstractServer] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._started = threading.Event()

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                hdr = await reader.readexactly(4)
                n = _LEN.unpack(hdr)[0]
                payload = await reader.readexactly(n)
                req = json.loads(payload)
                if "cancel" in req:
                    # out-of-band cancel (ref InstanceRequestHandler's
                    # CANCEL_QUERY): arrives on its OWN short-lived
                    # connection because the originating channel is
                    # blocked waiting for the very response being
                    # cancelled
                    ok = self.executor.cancel(req["cancel"])
                    ack = json.dumps({"cancelled": bool(ok)}).encode()
                    writer.write(_LEN.pack(len(ack)) + ack)
                    await writer.drain()
                    continue
                # REMAINING broker budget -> local absolute deadline; the
                # scheduler refuses to start work whose whole budget was
                # spent in its queue, the executor's cooperative checks
                # stop work that expires mid-scan
                timeout_ms = req.get("timeoutMs")
                deadline = (time.time() + float(timeout_ms) / 1000.0
                            + self.executor.deadline_grace_s
                            if timeout_ms else None)
                # -- admission: reject in O(1) BEFORE the scheduler when
                # the query cannot plausibly answer inside its budget
                # (queue full / deadline unservable / memory pressure /
                # shed priority class) — a typed 211 with a retry-after
                # hint, having consumed no worker thread
                rejection = self.admission.admit(
                    table=req.get("tableName", ""),
                    tenant=req.get("tenant"),
                    workload=req.get("workload", "primary"),
                    deadline=deadline)
                if rejection is not None:
                    resp = _overload_response(rejection)
                    writer.write(_LEN.pack(len(resp)) + resp)
                    if req.get("streaming"):
                        writer.write(_LEN.pack(0))  # EOS
                    await writer.drain()
                    continue
                if req.get("streaming"):
                    # per-block response stream (ref GrpcQueryServer.Submit
                    # server-stream): generator creation is cheap; EACH
                    # frame's execution is its own scheduler submission so
                    # priority/binary-workload accounting still throttles
                    # streaming work, and frames ship as they compute
                    gen = self.executor.execute_streaming(
                        req["tableName"], req["sql"], req.get("segments"),
                        req.get("extraFilter"))
                    while True:
                        ticket = self.admission.register()
                        try:
                            fut = self.scheduler.submit(
                                lambda g=gen, t=ticket:
                                t.run(lambda: next(g, None)),
                                table=req.get("tableName", ""),
                                workload=req.get("workload", "primary"),
                                deadline=deadline,
                                tenant=req.get("tenant"))
                        except ServerOverloadedError as e:
                            # the scheduler's bounded-queue backstop won
                            # the race against the admission estimate
                            ticket.release()
                            frame = _overload_response(e)
                            writer.write(_LEN.pack(len(frame)) + frame)
                            break
                        fut.add_done_callback(
                            lambda _f, t=ticket: t.release())
                        try:
                            frame = await asyncio.wrap_future(fut)
                        except (QueryCancelledError, BrokerTimeoutError) as e:
                            frame = _timeout_response(e)
                            writer.write(_LEN.pack(len(frame)) + frame)
                            frame = None
                        if frame is None:
                            break
                        writer.write(_LEN.pack(len(frame)) + frame)
                        await writer.drain()
                    writer.write(_LEN.pack(0))  # EOS
                    await writer.drain()
                    continue
                arrival = time.time()
                ticket = self.admission.register()
                try:
                    fut = self.scheduler.submit(
                        lambda r=req, d=deadline, a=arrival, t=ticket:
                        t.run(lambda: self.executor.execute(
                            r["tableName"], r["sql"], r.get("segments"),
                            r.get("extraFilter"),
                            query_id=r.get("queryId") or r.get("requestId"),
                            timeout_ms=r.get("timeoutMs"), deadline=d,
                            trace_ctx=r.get("traceContext"), arrival_s=a,
                            tenant=r.get("tenant"))),
                        table=req.get("tableName", ""),
                        workload=req.get("workload", "primary"),
                        deadline=deadline,
                        tenant=req.get("tenant"))
                except ServerOverloadedError as e:
                    ticket.release()
                    resp = _overload_response(e)
                    writer.write(_LEN.pack(len(resp)) + resp)
                    await writer.drain()
                    continue
                fut.add_done_callback(lambda _f, t=ticket: t.release())
                try:
                    resp = await asyncio.wrap_future(fut)
                except (QueryCancelledError, BrokerTimeoutError) as e:
                    # reap any cancel tombstone for this id NOW — the
                    # guard killed the query before execute()'s own
                    # begin/finish pair could run, so nothing else will
                    qid = req.get("queryId") or req.get("requestId")
                    if qid is not None:
                        self.executor.accountant.finish_query(str(qid))
                    resp = _timeout_response(e)
                writer.write(_LEN.pack(len(resp)) + resp)
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionResetError):
            pass
        finally:
            writer.close()

    def start(self) -> None:
        """Start serving on a background thread; sets self.port."""
        def run():
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            self._loop = loop

            async def main():
                self._server = await asyncio.start_server(
                    self._handle, self.host, self.port)
                self.port = self._server.sockets[0].getsockname()[1]
                self._started.set()
                async with self._server:
                    await self._server.serve_forever()

            try:
                loop.run_until_complete(main())
            except asyncio.CancelledError:
                pass
            finally:
                loop.close()

        self._thread = threading.Thread(target=run, daemon=True,
                                        name=f"query-server-{self.port}")
        self._thread.start()
        if not self._started.wait(10):
            raise RuntimeError("query server failed to start")

    def stop(self) -> None:
        """Idempotent: a failover test (or ops) may stop a server that was
        already killed."""
        if self._loop is not None and not self._loop.is_closed():
            def shutdown():
                for task in asyncio.all_tasks(self._loop):
                    task.cancel()
            try:
                self._loop.call_soon_threadsafe(shutdown)
            except RuntimeError:
                pass  # loop closed between the check and the call
        if self._thread is not None:
            self._thread.join(timeout=5)
        self.scheduler.stop()


class ServerConnection:
    """Broker-side channel POOL to one server (ref ServerChannels:65).

    The original single-socket channel held its lock for the whole
    request round trip, which silently serialized scatter concurrency
    to ONE in-flight request per (broker, server) pair — the server's
    scheduler queue (where admission control watches) could never form,
    and the real overload queue hid inside a broker-side lock nobody
    measures. Now each request takes its own socket: up to
    ``pool_size`` idle sockets are retained for reuse, an empty pool
    dials fresh, so per-server concurrency is bounded by the fan-out
    pool (the intended bound), not by channel serialization."""

    #: idle sockets retained per server (concurrency itself is bounded
    #: by the broker's fan-out pool, not by this)
    POOL_SIZE = 4

    def __init__(self, host: str, port: int,
                 pool_size: Optional[int] = None):
        self.host, self.port = host, port
        self._idle: List[socket.socket] = []
        self._lock = threading.Lock()
        self.pool_size = pool_size if pool_size is not None \
            else self.POOL_SIZE

    def _take(self) -> tuple:
        """(socket, was_pooled). A pooled socket may be stale (server
        restarted since); callers retry once on a fresh dial."""
        with self._lock:
            if self._idle:
                return self._idle.pop(), True
        return socket.create_connection((self.host, self.port),
                                        timeout=30), False

    def _give(self, sock: socket.socket) -> None:
        with self._lock:
            if len(self._idle) < self.pool_size:
                self._idle.append(sock)
                return
        _close_quietly(sock)

    def request(self, table_name: str, sql: str,
                segments: Optional[List[str]] = None,
                request_id: int = 0,
                extra_filter: Optional[str] = None,
                timeout_ms: Optional[float] = None,
                query_id=None, tenant: Optional[str] = None,
                trace_ctx: Optional[dict] = None) -> bytes:
        """timeout_ms: remaining query budget, shipped to the server AND
        used as this socket's read timeout (+grace) so a dead server
        can't pin a broker fan-out thread past the deadline. tenant:
        the weighted-fair scheduling group the server charges this
        query's wall time to (from TableConfig tenant tags). trace_ctx:
        the TraceContext wire dict — the server joins the trace and
        ships its span tree back in the response metadata."""
        payload = json.dumps({
            "requestId": request_id, "tableName": table_name, "sql": sql,
            "segments": segments, "extraFilter": extra_filter,
            "timeoutMs": timeout_ms, "tenant": tenant,
            "queryId": query_id, "traceContext": trace_ctx}).encode()
        sock, pooled = self._take()
        try:
            self._set_timeout(sock, timeout_ms)
            sock.sendall(_LEN.pack(len(payload)) + payload)
            resp = self._read_frame(sock)
        except socket.timeout:
            # a slow query, NOT a dead channel: retransmitting would run
            # it twice server-side; drop the socket and surface the
            # timeout (ref: the reference fails the query, the failure
            # detector handles the server)
            _close_quietly(sock)
            raise
        except ConnectionError:
            # one retry on a FRESH dial (ref channel re-establish) —
            # pooled sockets go stale across server restarts, and even
            # a fresh socket gets the one reconnect the old channel had
            _close_quietly(sock)
            sock = socket.create_connection((self.host, self.port),
                                            timeout=30)
            try:
                self._set_timeout(sock, timeout_ms)
                sock.sendall(_LEN.pack(len(payload)) + payload)
                resp = self._read_frame(sock)
            except (socket.timeout, ConnectionError):
                _close_quietly(sock)
                raise
        # return the (clean — full frame read) socket BEFORE the chaos
        # hook: an armed torn/error policy must not leak the socket
        self._give(sock)
        return self._fire_response(resp)

    def _fire_response(self, payload: bytes) -> bytes:
        """Chaos site on the response payload: torn bytes here exercise
        the broker's deserialize-failure -> retry path."""
        return fire("connection.request", payload=payload,
                    server=f"{self.host}:{self.port}")

    @staticmethod
    def _set_timeout(sock: socket.socket,
                     timeout_ms: Optional[float]) -> None:
        sock.settimeout(float(timeout_ms) / 1000.0 + _SOCKET_GRACE_S
                        if timeout_ms else 30.0)

    def cancel(self, query_id) -> bool:
        """Best-effort out-of-band cancel on a FRESH socket — the pooled
        channel is blocked waiting for the response being cancelled.
        Never raises: cancellation is advisory; the server's own deadline
        is the backstop."""
        try:
            with socket.create_connection((self.host, self.port),
                                          timeout=2.0) as sock:
                msg = json.dumps({"cancel": str(query_id)}).encode()
                sock.sendall(_LEN.pack(len(msg)) + msg)
                ack = json.loads(self._read_frame(sock))
                return bool(ack.get("cancelled"))
        except (OSError, ValueError):
            return False

    def request_streaming(self, table_name: str, sql: str,
                          segments: Optional[List[str]] = None,
                          request_id: int = 0,
                          extra_filter: Optional[str] = None):
        """Generator of per-block DataTable payloads until the server's
        zero-length EOS frame (ref GrpcQueryServer server-stream). The
        stream owns its socket exclusively — frames of one query cannot
        interleave with another request's."""
        payload = json.dumps({
            "requestId": request_id, "tableName": table_name, "sql": sql,
            "segments": segments, "extraFilter": extra_filter,
            "streaming": True}).encode()
        sock, _pooled = self._take()
        completed = False
        try:
            sock.sendall(_LEN.pack(len(payload)) + payload)
            while True:
                frame = self._read_frame(sock, allow_empty=True)
                if not frame:
                    completed = True
                    return  # EOS
                yield frame
        finally:
            if completed:
                self._give(sock)
            else:
                # consumer aborted (or the read failed) mid-stream:
                # unread frames would poison the next request on this
                # socket — drop it, the pool dials fresh
                _close_quietly(sock)

    @staticmethod
    def _read_frame(sock: socket.socket, allow_empty: bool = False) -> bytes:
        hdr = b""
        while len(hdr) < 4:
            chunk = sock.recv(4 - len(hdr))
            if not chunk:
                raise ConnectionError("server closed connection")
            hdr += chunk
        n = _LEN.unpack(hdr)[0]
        buf = bytearray()
        while len(buf) < n:
            chunk = sock.recv(min(1 << 20, n - len(buf)))
            if not chunk:
                raise ConnectionError("server closed connection mid-frame")
            buf += chunk
        return bytes(buf)

    def close(self) -> None:
        with self._lock:
            idle, self._idle = self._idle, []
        for sock in idle:
            _close_quietly(sock)


def _close_quietly(sock: socket.socket) -> None:
    try:
        sock.close()
    except OSError:
        pass
