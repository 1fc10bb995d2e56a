"""Instance configuration: layered properties + env overrides.

Reference parity: pinot-spi env/PinotConfiguration.java (commons-config
over properties files with relaxed env-var overrides) + the
CommonConstants key catalog (utils/CommonConstants.java — all config
keys in one place). Precedence, highest first:

  1. explicit overrides passed to the constructor
  2. environment variables: `pinot.server.query.port` reads
     `PINOT_TPU_SERVER_QUERY_PORT` (relaxed upper-snake mapping)
  3. a java-style .properties file (key=value, '#' comments)
  4. catalog defaults (KEYS below)
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional

ENV_PREFIX = "PINOT_TPU_"

#: the CommonConstants analog — every tunable in one catalog with its
#: default (subsystems read through a PinotConfiguration, not os.environ)
KEYS: Dict[str, Any] = {
    "pinot.server.query.port": 0,
    "pinot.server.query.num.threads": 8,
    "pinot.server.query.scheduler": "fcfs",     # fcfs | priority | binary
    "pinot.server.stream.chunk.segments": 4,
    # concurrent-query dispatch pipeline (ops/dispatch.py):
    # mode 'pipelined' = dispatch ring + shared-plan micro-batching +
    # staging/compute overlap; 'serialized' reproduces the pre-ring
    # inline dispatch (A/B baseline + escape hatch)
    "pinot.server.dispatch.mode": "pipelined",
    "pinot.server.dispatch.ring.size": 64,      # bounded launch queue
    # micro-batch coalescing: fingerprint-equal concurrent queries merge
    # into one launch within this window (only waited when >1 caller is
    # active), capped at batch.max per launch. 'auto' sizes the window
    # from an EWMA of observed caller inter-arrival times, clamped to
    # [0.5x, 4x] of the static default below — bursty fleets wait just
    # long enough for their peers, lone callers converge to the floor
    "pinot.server.dispatch.batch.window.ms": 2.0,
    "pinot.server.dispatch.batch.max": 16,
    # cross-table shape-bucketed batching (the unified kernel factory,
    # ops/kernels.py): queries coalesce on (plan fingerprint, shape
    # bucket) — padded S/D pow2 buckets + staged-array shape signature —
    # so same-plan queries over DIFFERENT tables/partitions share one
    # launch (column blocks stack along a leading batch axis). Off =
    # PR-4 behavior (identical segment batch only). doc.bucket.max caps
    # the doc bucket eligible for cross-table stacking: above it, a
    # stacked [B, S, D] copy would dominate HBM, so such launches keep
    # the same-batch (broadcast-only) key.
    "pinot.server.dispatch.batch.cross.table": True,
    "pinot.server.dispatch.doc.bucket.max": 1 << 20,
    # HBM memory tiers (ops/staging.py + ops/residency.py):
    # .hbm.cache.bytes bounds the ASSEMBLED [S, D] block cache;
    # .hbm.resident.bytes bounds the per-(segment, column) resident-row
    # tier that survives batch recomposition (misses assemble on-device;
    # 0 retains nothing, and every miss uploads its rows one by one).
    # Admission is TinyLFU-style: when full, a candidate row must be
    # more frequent than the LRU victim to be retained (warmup-seeded
    # rows bypass the duel); .admission.sample is the frequency aging
    # window (counters halve when it fills).
    "pinot.server.hbm.cache.bytes": 8 << 30,
    "pinot.server.hbm.resident.bytes": 6 << 30,
    "pinot.server.hbm.admission.enabled": True,
    "pinot.server.hbm.admission.sample": 4096,
    "pinot.server.host.row.cache.bytes": 16 << 30,
    # collective broker merge (ops/collective.py): on a multi-chip mesh
    # the cross-segment partial fold runs as ONE on-device collective;
    # False is the escape hatch back to the host IndexedTable fold
    "pinot.server.mesh.collective.merge": True,
    # star-tree device leg (ops/startree_device.py): fitted aggregations
    # answer from pre-agg records through the kernel factory
    "pinot.server.startree.enabled": True,
    # CLP log-column LIKE/regex pushdown (ops/clp_device.py): patterns
    # compile to logtype LUTs + variable-slot conditions evaluated as
    # device filter leaves
    "pinot.server.clp.enabled": True,
    # vector-similarity device leg (ops/vector_device.py): ANN top-K as
    # a batched matmul over staged vector blocks
    "pinot.server.vector.enabled": True,
    # time-series device leg (ops/timeseries_device.py): fuse
    # floor((t-start)/step) into the group-by kernel's key instead of
    # falling back to the host expression path
    "pinot.server.timeseries.bucket.enabled": True,
    # time-series leaf fetch cap: a leaf SQL may return at most
    # count * this many group rows before the engine fails loud
    # (silent truncation would corrupt downstream sums)
    "pinot.timeseries.leaf.max.groups": 10_000,
    "pinot.server.segment.cache.enabled": True,   # tier-2 partial cache
    "pinot.server.segment.cache.bytes": 256 << 20,
    "pinot.server.segment.cache.ttl.seconds": 300.0,
    # tier-2 backend: local (process-private L1) | tiered (L1 + shared
    # remote L2 at .remote.address — a cache-server role instance)
    "pinot.server.segment.cache.backend": "local",
    "pinot.server.segment.cache.remote.address": "127.0.0.1:9600",
    # warmup: replay the recent-plan fingerprint log against freshly
    # loaded immutable segments BEFORE they serve queries
    "pinot.server.segment.warmup.enabled": True,
    "pinot.server.segment.warmup.max.plans": 32,
    "pinot.server.segment.warmup.log.plans.per.table": 64,
    # fingerprint-log journal: persist the warmup plan log so a restarted
    # server warms from history, not an empty log ("" = in-memory only)
    "pinot.server.segment.warmup.journal.dir": "",
    "pinot.server.segment.warmup.journal.max.bytes": 1 << 20,
    # server-side grace added to the broker-shipped remaining budget
    # before the local deadline trips (absorbs clock skew + queue jitter)
    "pinot.server.query.deadline.grace.ms": 50,
    # -- server admission control (server/admission.py) -----------------
    # Overload protection at the transport edge: a query is REJECTED
    # with a typed errorCode-211 (+ retryAfterMs hint) instead of
    # queueing toward a deadline miss when (a) the scheduler's bounded
    # queue is full (.queue.limit, also enforced inside the schedulers
    # as a backstop; 0 = unbounded), (b) its remaining deadline budget
    # is below the EWMA-estimated queue wait + execution time
    # (.exec.ewma.alpha smooths the estimates), (c) memory/HBM pressure
    # (residency-tier + realtime-ingest bytes vs their budgets) is at/
    # over .memory.threshold, or (d) the queue is past .shed.start
    # occupancy and the query's tenant weight ranks below the
    # occupancy-scaled cutoff (lowest-priority tenants shed first,
    # DAGOR-style).
    "pinot.server.admission.enabled": True,
    "pinot.server.admission.queue.limit": 128,
    "pinot.server.admission.shed.start": 0.5,
    "pinot.server.admission.memory.threshold": 0.95,
    "pinot.server.admission.exec.ewma.alpha": 0.2,
    # realtime ingestion backpressure (ingest/realtime_manager.py):
    # .memory.bytes bounds one partition consumer's mutable bytes plus
    # sealed-segments-awaiting-build bytes — approaching the budget
    # shrinks fetch batches adaptively, reaching it PAUSES the consumer
    # (0 = unbounded, the pre-backpressure behavior). .lag.pause.ms
    # bounds how far a paused partition may fall behind: past it, the
    # manager sheds memory by force-sealing the mutable into the build
    # pipeline instead of pausing indefinitely (0 = no ceiling).
    # .fetch.max.rows caps one fetch's messages (the adaptive ceiling).
    "pinot.server.ingest.memory.bytes": 0,
    "pinot.server.ingest.lag.pause.ms": 0.0,
    "pinot.server.ingest.fetch.max.rows": 10_000,
    "pinot.broker.http.port": 8099,
    "pinot.broker.fanout.threads": 16,
    "pinot.broker.adaptive.selector": "hybrid",  # latency|inflight|hybrid
    # end-to-end query budget (ref CommonConstants BROKER_TIMEOUT_MS):
    # OPTION(timeoutMs=...) > table override > this default. The broker
    # ships the REMAINING budget to servers, waits deadline-derived
    # times, and cancels still-pending server work on expiry.
    "pinot.broker.timeout.ms": 60000,
    # hedged scatter (speculative retry, "The Tail at Scale"): after an
    # adaptive delay — p95 over the selector's pooled per-server latency
    # reservoirs (true per-request tails), clamped to [delay.min,
    # delay.max] — re-issue still-pending plan entries on a different
    # healthy replica and keep the first clean response. Off by default:
    # it doubles worst-case fan-out.
    "pinot.broker.hedge.enabled": False,
    "pinot.broker.hedge.delay.min.ms": 25,
    "pinot.broker.hedge.delay.max.ms": 1000,
    # -- broker retry budget (broker/adaptive.py RetryBudget) -----------
    # Finagle-style per-table retry budget so failures and overload
    # rejections cannot amplify into retry storms: every clean primary
    # response DEPOSITS .ratio tokens (capped at .cap), every retry or
    # hedge WITHDRAWS one; a table starts with .min tokens so a cold
    # broker can still salvage the odd failure. Exhausted budget means
    # the failure surfaces as a typed partial instead of re-offering
    # the load that is sinking the fleet.
    "pinot.broker.retry.budget.enabled": True,
    "pinot.broker.retry.budget.ratio": 0.2,
    "pinot.broker.retry.budget.min": 3.0,
    "pinot.broker.retry.budget.cap": 10.0,
    # -- brownout mode (health/brownout.py) -----------------------------
    # Graceful degradation closing the SLO observe->act loop: sustained
    # SLO burn (the PR-14 watchdog) or sustained shed rate (admission
    # rejections + overload partials per query over the short window at/
    # over .shed.rate.threshold) climbs a per-role degradation ladder —
    # disable hedging -> serve result-cache entries up to
    # .stale.ttl.grace.seconds past TTL with staleResult=true -> shrink
    # dispatch batch windows by .batch.window.scale -> shed secondary
    # workloads at admission. Hysteresis: one rung up only after the
    # signal holds .up.seconds, one rung down only after it stays clear
    # .down.seconds (exit threshold is half the entry threshold).
    "pinot.brownout.enabled": True,
    "pinot.brownout.shed.rate.threshold": 0.1,
    "pinot.brownout.up.seconds": 10.0,
    "pinot.brownout.down.seconds": 30.0,
    "pinot.brownout.batch.window.scale": 0.25,
    "pinot.brownout.stale.ttl.grace.seconds": 120.0,
    # multi-stage engine budget: OPTION(timeoutMs=...) > this knob >
    # pinot.broker.timeout.ms — the budget travels in every stage and is
    # enforced on every mailbox wait ("" = inherit the broker default)
    "pinot.broker.mse.timeout.ms": None,
    # MSE stage hedging ("The Tail at Scale", MSE edition): after an
    # adaptive delay — a quantile of the dispatcher's pooled per-worker
    # STAGE-latency reservoirs, clamped to [delay.min, delay.max] — a
    # still-running leaf stage instance is re-issued on another alive
    # worker holding the same local segment view; the first attempt to
    # finish CLEAN claims the (query, stage, worker-slot) output and
    # sends, the loser is cancelled and sends nothing (exactly one EOS
    # per sender slot — no double-merge by construction). Off by
    # default: it doubles worst-case leaf fan-out.
    "pinot.broker.mse.hedge.enabled": False,
    "pinot.broker.mse.hedge.delay.min.ms": 25,
    "pinot.broker.mse.hedge.delay.max.ms": 1000,
    "pinot.broker.mse.hedge.quantile": 0.95,
    # pipelined intermediate stages: senders chunk stage output into
    # <= chunk.rows frames and fold-capable receivers (aggregate /
    # final_agg over a receive) merge frames AS THEY ARRIVE instead of
    # barriering on receive_all — upstream compute overlaps downstream
    # merge, and fan-in no longer serializes on the slowest sender.
    # watermark.rows bounds the decoded-but-unfolded buffer (the fold
    # granularity); enabled=False restores the full-barrier receive.
    "pinot.server.mse.pipeline.enabled": True,
    "pinot.server.mse.pipeline.chunk.rows": 8192,
    "pinot.server.mse.pipeline.watermark.rows": 8192,
    # leaf-stage output cache (mse/stage_cache.py): one worker's whole
    # scan/leaf_agg stage block per (segment version set, stage-plan
    # fingerprint) — epoch-invalidated like the tier-2 partial cache,
    # never caches partials, and skips tables with a mutable tail.
    # backend 'tiered' mounts the shared remote L2 (cache-server role /
    # ring) under the local tier so ONE replica's warm leaf output
    # serves the fleet: keys carry content CRC versions (never the
    # per-process generation stamps), payloads are typed Block serde
    "pinot.server.mse.stage.cache.enabled": True,
    "pinot.server.mse.stage.cache.bytes": 64 << 20,
    "pinot.server.mse.stage.cache.ttl.seconds": 300.0,
    "pinot.server.mse.stage.cache.backend": "local",
    "pinot.server.mse.stage.cache.remote.address": "127.0.0.1:9600",
    # negative cache: memoize pruned-to-zero plans (epoch-keyed) so
    # dashboard misfires skip routing + scatter entirely
    "pinot.broker.negative.cache.enabled": True,
    "pinot.broker.negative.cache.bytes": 1 << 20,
    "pinot.broker.negative.cache.ttl.seconds": 60.0,
    # tier-1 whole-result cache: opt-in — a cached response bypasses
    # scatter/gather entirely, including failure detection
    "pinot.broker.result.cache.enabled": False,
    "pinot.broker.result.cache.bytes": 64 << 20,
    "pinot.broker.result.cache.ttl.seconds": 60.0,
    # cache tables with a consuming side (appends don't move the routing
    # epoch, so hits may be TTL-stale) — off unless you can tolerate that
    "pinot.broker.result.cache.realtime": False,
    # tier-1 backend: local | tiered (shared remote L2, see server keys)
    "pinot.broker.result.cache.backend": "local",
    "pinot.broker.result.cache.remote.address": "127.0.0.1:9600",
    # hybrid tables: cache the offline side's merged partial keyed by the
    # OFFLINE epoch so only the realtime side re-scatters
    "pinot.broker.result.cache.hybrid.offline": True,
    # the cache-server role (cluster/roles.py run_cache_server)
    "pinot.cache.server.port": 9600,
    "pinot.cache.server.bytes": 512 << 20,
    "pinot.cache.server.ttl.seconds": 300.0,
    # remote-tier payload compression: payloads at/above this size are
    # wrapped with a segment/codec.py codec before the wire (and decoded
    # transparently on GET); <= 0 disables
    "pinot.cache.server.compress.threshold.bytes": 16384,
    # shared remote-client knobs (both tiers' L2 mounts)
    "pinot.cache.remote.timeout.seconds": 2.0,
    "pinot.cache.remote.pool.size": 2,
    "pinot.cache.remote.breaker.failures": 3,
    "pinot.cache.remote.breaker.reset.seconds": 5.0,
    # cache ring: `...remote.address` with >= 2 comma-separated addresses
    # consistent-hashes the key space client-side (cache/ring.py);
    # virtual-node count trades placement evenness for ring-build cost
    "pinot.cache.remote.ring.vnodes": 64,
    "pinot.controller.port": 9000,
    "pinot.controller.deep.store.uri": "",
    "pinot.controller.retention.frequency.seconds": 60,
    "pinot.coordination.liveness.ttl.seconds": 15.0,
    # minimal-disruption rebalancer (controller/rebalancer.py): a move
    # never drops a segment below min(replication, min.available.replicas)
    # live loaded copies; max.parallel.moves moves share one batched
    # routing-epoch bump (set 1 for byte-identical seeded chaos replays)
    "pinot.controller.rebalance.min.available.replicas": 1,
    "pinot.controller.rebalance.max.parallel.moves": 4,
    "pinot.controller.rebalance.journal.max.bytes": 1 << 20,
    # automatic failure repair (controller/repair.py): an instance whose
    # heartbeat age exceeds grace on two consecutive ticks (debounced —
    # flapping never churns replicas) gets its segments re-replicated
    "pinot.controller.repair.enabled": True,
    "pinot.controller.repair.grace.seconds": 30.0,
    "pinot.controller.repair.frequency.seconds": 10.0,
    # minion task fabric, controller side (controller/task_manager.py):
    # lease TTL + heartbeat-renewed leases; an expired lease requeues the
    # task with capped exponential backoff until max.attempts
    "pinot.controller.task.lease.seconds": 30.0,
    "pinot.controller.task.max.attempts": 3,
    "pinot.controller.task.retry.backoff.seconds": 1.0,
    "pinot.controller.task.retry.backoff.cap.seconds": 30.0,
    # cadence of the generator scan + lease-expiry sweep
    "pinot.controller.task.frequency.seconds": 30.0,
    "pinot.controller.task.generators.enabled": True,
    "pinot.controller.task.journal.max.bytes": 1 << 20,
    # minion task fabric, worker side (minion/worker.py)
    "pinot.minion.poll.seconds": 1.0,
    "pinot.minion.heartbeat.seconds": 2.0,
    "pinot.minion.task.types": "",   # csv; "" = all registered executors
    "pinot.minion.work.dir": "",     # "" = per-worker tempdir sandbox
    # worker-side executor pool: a minion runs up to this many tasks
    # concurrently (each with its own lease heartbeat); per-type caps
    # layer on top via pinot.minion.executor.concurrency.<TaskType>
    "pinot.minion.executor.concurrency": 2,
    # -- distributed tracing (utils/tracing.py + utils/trace_store.py) --
    # master switch: off = NO trace machinery at all (no RequestTrace,
    # no wire context, no tail capture, no clock stamp, no profiler
    # annotation). On = shadow span collection per query (stitched trees
    # kept only for trace=true responses and slow-query tail capture).
    "pinot.trace.enabled": True,
    # bounded per-role in-memory trace retention behind /debug/traces
    "pinot.trace.store.capacity": 256,
    # tail-based slow-query capture: queries at/over the threshold keep
    # their full stitched trace in the broker store and emit a
    # structured slow-query log line EVEN when trace=false (0 = off)
    "pinot.broker.slow.query.threshold.ms": 10000.0,
    # server-local tail capture over the server's own span tree (0=off;
    # sampled traces are stored in the server store regardless)
    "pinot.server.slow.query.threshold.ms": 0.0,
    "pinot.minion.slow.task.threshold.ms": 0.0,
    # per-role debug/metrics HTTP surface (utils/trace_store.py
    # DebugHttpServer): /metrics + /debug/traces + /debug/queries for
    # roles without an HTTP edge. 0 = ephemeral port (printed at
    # startup), >0 = fixed port, <0 = disabled.
    "pinot.server.admin.port": 0,
    "pinot.minion.admin.port": 0,
    "pinot.cache.server.admin.port": 0,
    # -- fleet health plane (pinot_tpu/health/) -------------------------
    # metrics history: a background sampler appends one flat
    # MetricsRegistry.sample() per interval to a bounded per-role ring
    # holding window.seconds worth — /debug/metrics/history serves it,
    # the SLO watchdog evaluates burn rates over it, and the selfmetrics
    # connector exposes it to the time-series engine. enabled=False
    # builds NO history machinery at all (the bench.py --health A-side).
    "pinot.metrics.history.enabled": True,
    "pinot.metrics.history.interval.ms": 1000.0,
    "pinot.metrics.history.window.seconds": 300.0,
    # SLO watchdog (health/slo.py): declarative targets evaluated as
    # multi-window burn rates over the history; a target left at 0 is
    # disabled. query.p99.ms bounds the role's per-sample latency p99;
    # error.rate bounds (exceptions + errorCode-250) per query;
    # freshness.ms bounds the worst per-partition ingestion lag.
    # latency.budget is the fraction of samples ALLOWED over a
    # sample-fraction target (burn = bad fraction / budget); a breach
    # needs BOTH the short and long window burn over burn.threshold.
    "pinot.slo.query.p99.ms": 0.0,
    "pinot.slo.error.rate": 0.0,
    "pinot.slo.freshness.ms": 0.0,
    "pinot.slo.window.short.seconds": 60.0,
    "pinot.slo.window.long.seconds": 300.0,
    "pinot.slo.burn.threshold": 1.0,
    "pinot.slo.latency.budget": 0.01,
    # per-query workload accounting (utils/accounting.ChargeSlip +
    # health/workload.py): device kernel ms, rows/bytes scanned,
    # transfer bytes, cache hit/miss bytes charged per query and rolled
    # into per-(tenant, table, plan) WorkloadStats at /debug/workload.
    # False = no slips, no rollup (the bench.py --health A-side).
    "pinot.workload.accounting.enabled": True,
    # cluster rollup (health/rollup.py): the controller's periodic
    # fleet sweep over every registered instance's admin_url into
    # GET /cluster/health + /cluster/metrics; scrape failures mark the
    # instance degraded, never throw.
    "pinot.cluster.health.enabled": True,
    "pinot.cluster.health.interval.seconds": 5.0,
    "pinot.cluster.health.scrape.timeout.seconds": 2.0,
}


def _env_name(key: str) -> str:
    # 'pinot.server.query.port' -> PINOT_TPU_SERVER_QUERY_PORT (the
    # shared 'pinot.' prefix folds into the env prefix)
    if key.startswith("pinot."):
        key = key[len("pinot."):]
    return ENV_PREFIX + key.replace(".", "_").upper()


class PinotConfiguration:
    def __init__(self, properties_file: Optional[str] = None,
                 overrides: Optional[Dict[str, Any]] = None):
        self._file: Dict[str, str] = {}
        if properties_file:
            self._file = load_properties(properties_file)
        self._overrides = dict(overrides or {})

    # ------------------------------------------------------------------
    def get(self, key: str, default: Any = None) -> Any:
        if key in self._overrides:
            return self._overrides[key]
        env = os.environ.get(_env_name(key))
        if env is not None:
            return env
        if key in self._file:
            return self._file[key]
        if key in KEYS:
            return KEYS[key]
        return default

    def get_int(self, key: str, default: int = 0) -> int:
        return int(self.get(key, default))

    def get_float(self, key: str, default: float = 0.0) -> float:
        return float(self.get(key, default))

    def get_bool(self, key: str, default: bool = False) -> bool:
        v = self.get(key, default)
        if isinstance(v, bool):
            return v
        return str(v).strip().lower() in ("1", "true", "yes", "on")

    def get_str(self, key: str, default: str = "") -> str:
        return str(self.get(key, default))

    def is_set(self, key: str) -> bool:
        """True when the key was EXPLICITLY configured (constructor
        override or properties file) rather than falling through to the
        env/catalog defaults — harnesses use this to layer their own
        defaults without clobbering operator choices."""
        return key in self._overrides or key in self._file

    def with_overrides(self, extra: Dict[str, Any]) -> "PinotConfiguration":
        """A derived config: same properties-file contents, overrides
        layered on top of (and winning over) the existing ones. Use this
        instead of rebuilding from `_overrides` alone — that would drop
        every file-based setting."""
        derived = PinotConfiguration(overrides={**self._overrides, **extra})
        derived._file = dict(self._file)
        return derived

    def subset(self, prefix: str) -> Dict[str, Any]:
        """All effective keys under a dotted prefix (catalog + file +
        overrides; env consulted per key)."""
        if not prefix.endswith("."):
            prefix += "."
        names = {k for k in KEYS if k.startswith(prefix)}
        names |= {k for k in self._file if k.startswith(prefix)}
        names |= {k for k in self._overrides if k.startswith(prefix)}
        return {k[len(prefix):]: self.get(k) for k in sorted(names)}


def load_properties(path: str) -> Dict[str, str]:
    """Minimal java .properties reader (key=value / key: value)."""
    out: Dict[str, str] = {}
    with open(path) as f:
        for raw in f:
            line = raw.strip()
            if not line or line.startswith(("#", "!")):
                continue
            # split at the FIRST occurrence of either separator (java
            # .properties semantics — 'k: a=b' must not split at '=')
            cuts = [i for i in (line.find("="), line.find(":")) if i >= 0]
            if not cuts:
                continue
            i = min(cuts)
            out[line[:i].strip()] = line[i + 1:].strip()
    return out
