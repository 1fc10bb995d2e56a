"""Metric-name catalog: one description per metric family.

The exposition analog of the ``KEYS`` knob catalog in utils/config.py:
every literal metric name emitted through a registry
(``add_meter``/``set_gauge``/``add_timing``/``time``/``observe``) has an
entry here, ``MetricsRegistry.prometheus_text`` emits the description as
the family's ``# HELP`` line, and the README "Metrics reference"
appendix is generated from the same text — so /metrics, the docs, and
the code can't drift apart. The ``metrics_docs`` static-analysis checker
(analysis/checkers/metrics_docs.py) enforces all three legs in tier-1.

Prefix-composed families (cache/core.py's ``<prefix>_hits/misses/...``,
cache/remote.py's ``remote_cache_<name>``) are namespaced by
construction and documented as families in the README prose; their
short suffixes are not catalog entries.
"""
from __future__ import annotations

from typing import Dict

#: metric name -> one-line HELP description (kind lives at the emission
#: site; the exposition checker keeps each name single-kind)
METRICS: Dict[str, str] = {
    # -- broker query path ------------------------------------------------
    "broker_query_ms": "end-to-end broker latency per query (ms)",
    "broker_queries": "queries handled by this broker",
    "broker_query_errors": "broker responses carrying any exception",
    "broker_error_code_250":
        "broker responses carrying an errorCode-250 (deadline) entry",
    "deadline_expired":
        "queries whose gather abandoned servers at the deadline",
    "hedge_issued": "hedged scatter attempts issued",
    "hedge_won": "hedge attempts that beat the primary",
    "hedge_wasted": "hedge attempts the primary beat",
    "hedge_split": "hedges split across replicas (partial layouts)",
    "slow_queries": "queries at/over the slow-query threshold",
    "broker_reduce":
        "GROUP BY answers reduced, by path (label path=columns|rows: one "
        "result held as columns, sorted and sliced a whole column at a "
        "time, or the dict merge a group at a time)",
    "broker_encode":
        "result tables encoded for the HTTP body, by path (label "
        "path=columns|rows: a table held as columns written a column at a "
        "time, or its rows dumped)",
    # -- server query path ------------------------------------------------
    "queries": "queries executed by this server",
    "queries_killed": "queries stopped by deadline/cancel",
    # -- overload protection (PR 15) --------------------------------------
    "server_admission_rejected":
        "queries rejected at server admission (label reason=queue|"
        "deadline|memory|tenant|workload|chaos)",
    "scheduler_queue_rejected":
        "submissions refused by a scheduler's bounded queue backstop",
    "broker_overload_rejections":
        "server overload (211) rejections received by this broker",
    "broker_overload_partials":
        "responses where an overload rejection surfaced as a typed "
        "partial (no replica absorbed the retry)",
    "broker_retries_issued": "scatter retry units launched after failures",
    "broker_retry_budget_exhausted":
        "retries/hedges suppressed by an exhausted per-table budget",
    "broker_retry_budget_tokens":
        "per-table retry-budget tokens remaining (label table=)",
    "brownout_level":
        "current brownout ladder level (0 = healthy, 4 = full brownout)",
    "brownout_transitions":
        "brownout ladder moves (label direction=up|down)",
    "stale_results_served":
        "result-cache entries served past TTL under brownout "
        "(staleResult=true)",
    "query_exceptions": "queries that raised server-side",
    "query_execution": "server-side execution latency per query (ms)",
    "scheduler_inflight": "queries currently inside the scheduler",
    # -- dispatch ring / kernel factory ----------------------------------
    "dispatch_queue_depth": "launches waiting in the dispatch ring",
    "dispatch_batch_size": "coalesced members per launch",
    "dispatch_held":
        "launches the ring held for an in-flight slot (the batch grows "
        "meanwhile)",
    "dispatch_batch_cross_table":
        "batch members coalesced across tables (stacked/dedup variants)",
    "dispatch_batch_dedup":
        "batch members sharing a stack entry via same-cols grouping",
    "kernel_retrace": "kernel retraces (steady-state retraces are bugs)",
    "kernel_retrace_by_plan":
        "kernel retraces attributed per plan fingerprint",
    "group_path":
        "device GROUP BYs by the way their additive slots run (label "
        "path=onehot|onehot2|scatter: kernels.group_path)",
    "group_fold":
        "device GROUP BYs by where their per-segment partials became one "
        "result (label where=device|host: kernels.group_fold)",
    "group_result_bytes":
        "bytes of group table fetched from the device ([G, slots] a "
        "folded query, [S, G, slots] where the host folds)",
    "scatter_rows":
        "rows x additive slots device GROUP BYs handed to XLA's scatter-add "
        "(padding included; on the scatter path each segment's compacted "
        "rung of kept rows, or its whole docs past the top rung; 0 a launch "
        "whose slots all run one-hot: kernels.scatter_rows)",
    "scatter_compact":
        "device GROUP BYs on the scatter path by the rows a segment their "
        "kept rows were compacted to before the scatter (label cap=, 0 the "
        "full scatter: kernels.compact_cap)",
    "mesh_exchange_bytes":
        "bytes a chip handed to the collectives of grouped programs on a "
        "server of several chips (the fold's all-reduces; read once a "
        "compiled program, added a launch)",
    "group_block":
        "columns of grouped results written to the DataTable, by the form "
        "their content allowed (label form=array|coded|list: raw numeric "
        "bytes, a dictionary with ids, the tagged list a value at a time)",
    "scan_served":
        "queries staged for the device scan leg (agg, group-by, top-N, "
        "DISTINCT)",
    "scan_fallback":
        "queries the scan leg handed to the host path (label reason="
        "unsupported|plan|staging)",
    "startree_served":
        "queries answered by the device star-tree pre-agg leg",
    "startree_fallback":
        "tree-carrying batches routed to the scan path (label reason="
        "disabled|aggregation|groupBy|noTree|fit|filter|precision|"
        "groups|staging)",
    "clp_served":
        "queries whose CLP-column LIKE/regex filter served device-side",
    "clp_fallback":
        "CLP-column LIKE/regex filters routed to the host decode path "
        "(label reason=disabled|predicate|charWildcard|regex|wildcard|"
        "partial|slots|alignments|staging)",
    "vector_served":
        "vector_similarity top-K queries answered by the device "
        "batched-matmul leg",
    "vector_fallback":
        "vector_similarity queries routed to the host index scan "
        "(label reason=disabled|noIndex|metric|hybrid|staging|"
        "precision)",
    "timeseries_leaf_device":
        "leaf group-bys whose time bucket fused into the device "
        "group-by kernel (ops/timeseries_device.py) instead of the "
        "host expression path",
    "time_bucket":
        "device GROUP BYs whose leading key is the fused time bucket, by "
        "unit, fold and kernel width (labels unit=SECOND..WEEK for "
        "DATETRUNC, FLOOR for the leaf's floor((t - start) / step); "
        "fold=device|host; width= the bucket digit the kernel ran, the "
        "most buckets one segment of the batch spans)",
    "mesh_merge_served":
        "mesh queries whose cross-segment partial merge ran as ONE "
        "on-device collective (no host IndexedTable fold)",
    "mesh_merge_fallback":
        "mesh queries routed to the host partial fold (label reason="
        "disabled|chaos|precision|groups|staging)",
    # -- memory tiers (HBM residency) ------------------------------------
    "hbm_cache_bytes":
        "assembled [S, D] block-cache bytes on device (multi-chip "
        "engines also emit a per-chip split under a device= label)",
    "hbm_resident_bytes":
        "resident-row tier bytes per chip (label device=platform:id — "
        "the skew the per-chip admission pressure gates on)",
    "hbm_block_hit": "assembled-block cache hits",
    "hbm_block_miss": "assembled-block cache misses",
    "hbm_resident_hit": "resident-row tier hits",
    "hbm_resident_miss": "resident-row tier misses",
    "hbm_admission_rejected": "rows the TinyLFU admission duel rejected",
    "hbm_evicted": "rows evicted from the resident tier",
    "hbm_transfer_bytes": "host->device bytes shipped by residency and the "
                          "engine's puts, plus the packed-parameter argument "
                          "of every launch",
    "hbm_cross_chip_bytes":
        "bytes of resident rows copied chip to chip at block assembly (a "
        "row found on another chip than its slab's: a batch recomposed "
        "after pruning); 0 while every batch is the one its rows were "
        "uploaded for",
    "host_row_cache_bytes": "host padded-row cache bytes",
    "host_row_hit": "host row-cache hits",
    "host_row_miss": "host row-cache misses",
    "host_row_evicted": "host row-cache evictions",
    # -- ingestion --------------------------------------------------------
    "ingest_rows_indexed": "rows indexed into mutable segments",
    "ingest_rows_skipped": "rows dropped by transforms/poison guards",
    "ingest_segments_sealed": "mutable segments sealed",
    "ingest_seal_build_failures": "immutable builds that failed (retried)",
    "ingest_checkpoint_torn": "torn checkpoint writes detected",
    "ingest_backpressure_pauses": "consumer pauses at the memory budget",
    "ingest_lag_shed_seals": "force-seals shed by the lag ceiling",
    "ingestion_delay_ms": "per-partition end-to-end ingestion lag (ms)",
    # -- caches / remote fabric ------------------------------------------
    "remote_cache_request": "remote cache-tier round-trip latency (ms)",
    "remote_cache_errors": "remote cache-tier request failures",
    "remote_cache_breaker_state":
        "remote-tier circuit breaker (0 closed, 1 open, 2 half-open)",
    "remote_cache_compressed_bytes":
        "bytes saved by remote-tier payload compression",
    "segment_warmup_segments": "segments warmed before first serve",
    "segment_warmup_entries": "cache entries populated by warmup",
    # -- multi-stage engine ----------------------------------------------
    "mse_queries": "multi-stage queries dispatched",
    "mse_cancelled": "multi-stage queries cancelled",
    "mse_deadline_expired": "multi-stage queries past their budget",
    "mse_mailbox_sent_frames": "mailbox frames sent",
    "mse_mailbox_sent_bytes": "mailbox bytes sent",
    "mse_mailbox_recv_frames": "mailbox frames received",
    "mse_mailbox_recv_bytes": "mailbox bytes received",
    "mse_mailbox_retries": "mailbox sends retried on a fresh socket",
    "mse_mailbox_poisoned": "mailbox queues poisoned by abort",
    "mse_stage_hedge_issued": "MSE stage hedges issued",
    "mse_stage_hedge_won": "MSE stage hedges that won",
    "mse_stage_hedge_wasted": "MSE stage hedges the primary beat",
    "mse_stage_cache_remote_hits":
        "leaf-stage cache hits served from the shared remote tier",
    # -- minion task fabric ----------------------------------------------
    "task_queue_depth": "active (non-terminal) tasks in the queue",
    "minion_running_tasks": "tasks currently executing on this worker",
    "minion_tasks_completed": "tasks completed by this worker",
    "minion_tasks_failed": "tasks failed by this worker",
    "minion_tasks_retried": "expired leases requeued for retry",
    "minion_task_duration_ms": "per-type task execution latency (ms)",
    "minion_manifest_resumes": "crash-mid-commit manifest resumes",
    # -- fleet health plane (PR 14) --------------------------------------
    "metrics_history_samples": "registry samples appended to the history",
    "slo_burn_rate":
        "short-window SLO error-budget burn rate (label slo=<target>)",
    "slo_latency_bad":
        "queries over the pinot.slo.query.p99.ms target "
        "(the latency-burn numerator)",
    "slo_breaches": "SLO breach onsets (multi-window burn over threshold)",
    "workload_tenant_cost_ms":
        "accumulated per-tenant cost (device kernel ms + cpu ms)",
    "cluster_scrape_failures": "instance scrapes that failed",
    "cluster_instances_live": "instances the last sweep verdicted live",
    "cluster_instances_degraded":
        "instances the last sweep verdicted degraded",
    "gc_pause_ms":
        "pauses of the Python collector in this process (ms), by "
        "generation (label generation=0|1|2); the same pauses are "
        "charged to the spans they stop (gcPauseMs)",
    # -- self-healing maintenance (PR 18) ---------------------------------
    "segments_missing_replicas":
        "segments below their configured replication (label table=; "
        "repair draining this to zero is the convergence signal)",
    "segments_offline": "segments in OFFLINE status (label table=)",
    "rebalance_moves_completed":
        "segment moves the rebalance engine completed (DONE)",
    "repair_replications":
        "segments re-replicated by the automatic failure repair loop",
}
