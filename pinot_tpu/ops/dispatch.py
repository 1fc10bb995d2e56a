"""KernelDispatcher: the engine's pipelined device-launch stage.

Reference parity: the role of pinot-core's per-server query worker pool
(query/scheduler/QueryScheduler.java submitting segment work to
executors) — but shaped like an inference-serving dispatcher, because
the hot path here is ONE device program per query, not N segment tasks:

  * dispatch ring — a single dispatch thread + bounded queue replaces
    the ad-hoc dispatch lock: callers enqueue STAGED launches (columns
    already HBM-resident, predicate params already resolved) and get
    futures. The ring orders collective-bearing programs on host
    platforms (XLA's intra-process CPU collectives deadlock when two
    partitioned programs interleave their rendezvous), while real
    accelerators keep fully concurrent submission through a launch pool.
  * shape-bucketed micro-batching — concurrent queries coalesce on the
    kernel-factory key (plan fingerprint, shape bucket): same
    `DevicePlan`, same padded (S, D, G) bucket, same staged-array shape
    signature — NOT the same concrete segment batch, so the dashboard
    fleet batches across tables and partitions. One launch carries all
    members: params always stack along a leading query axis; column
    blocks broadcast when every member staged the same batch, or stack
    along the leading axis too when members come from different tables
    (ops/kernels.py `compiled_batched_kernel(plan, B, stacked)`), and
    doc-sharded mesh engines ride `compiled_batched_sharded_kernel`
    (vmap INSIDE shard_map, one set of collectives per batch — the
    CPU-collective lock is held once per batch, not once per query).
    Results split back per caller. Batched kernels are cached per
    (plan, pow2 batch bucket, variant) — a cross-query retrace is a
    bug, and `kernels.trace_count()` / `kernels.trace_log()` / the
    per-plan-labelled `kernel_retrace` meter make one loud.
  * hold behind a busy device — on the launch pool's path (every real
    accelerator launch) the ring hands a batchable launch over only
    while fewer than `_HOLD_DEPTH` launches are in flight. A launch
    popped behind a busy device would wait on the device's queue
    anyway, alone, re-reading the columns; held in the ring instead it
    keeps collecting key-equal launches up to `batch_max` and leaves as
    ONE batch the moment `_busy_end` frees the slot (through `_cv`, not
    polled). In flight is counted where the ring decides, at hand-off
    to the launch pool. With nothing in flight the window and the
    callers target apply unchanged; the lone-caller inline path, a
    launch that cannot batch, and XLA:CPU's collective branch (one
    launch at a time under `_CPU_COLLECTIVE_LOCK` already) are as they
    were. `heldMs` on the span and the `dispatch_held` meter say how
    often it engages. The pow2 buckets a held batch grows into compile
    on their first launch like any other, nothing precompiles them: a
    warm-up has to meet them (the benchmark's does: eight clients
    starting at once pile up behind a round's first launches).
  * staging/compute overlap — device->host result fetch runs on a fetch
    pool OFF the ring, so the next launch overlaps the previous fetch;
    `execute_async` staging runs on a staging pool so host-side padding
    + `jax.device_put` for query N+1 proceed while query N's kernel
    occupies the device.

Every wait of a traced launch is measured where it happens and lands on
its `DeviceDispatch` span: `queueWaitMs` (submit -> handed over for
launch; inline: submit -> launch; `submitMs` before it, staged ->
submitted; on the ring `heldMs` is its part spent held for an in-flight
slot, 0.0 where not held, and `dispatchMs` comes after it, handed over ->
the launch call starts),
then `launchMs` (the kernel call returning:
trace + compile on a first shape, else the asynchronous enqueue),
`deviceWaitMs` (launch returned -> `jax.block_until_ready`: a HOST
clock, an upper bound on device time, never kernel time) and `d2hMs`
(`np.asarray`: the device->host copy), with the wall-clock stamps
`launchNs` / `readyNs` round launch + device wait, and last `handoffMs`
(copy landed -> the caller's thread holds the result). `kernelMs`/`fetchMs`
keep their older, coarser meaning (see `_submit_serialized`). The same
phases go to the jax profiler's host plane as `pinot:<phase>` trace
annotations (`phase_annotation`), so an xprof view shows them above the
device ops.

Deadline/cancel checks are honored while a launch waits in the ring: a
cancelled query's future fails and the query leaves its batch before
launch. Chaos tests hook the ring via the `server.dispatch.before`
failpoint site (delay a dispatch, fail it, or reorder around it) and
the per-member `server.dispatch.batch` site inside the coalesced path
(an erroring member fails only its own future; peers complete).

Knobs (utils/config.py): pinot.server.dispatch.mode (pipelined |
serialized — the latter reproduces the pre-ring inline dispatch for
A/B), .ring.size, .batch.window.ms, .batch.max, .batch.cross.table
(shape-bucket coalescing across tables; off = same-segment-batch
coalescing only), and pinot.server.dispatch.doc.bucket.max (largest doc
bucket that may stack cross-table — bounds the [B, S, D] stacked
footprint).
"""
from __future__ import annotations

import contextlib
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import TimeoutError as _FutureTimeout
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import jax

from pinot_tpu.ops import kernels
from pinot_tpu.ops.plan_ir import batch_params
from pinot_tpu.utils.failpoints import fire

#: XLA's intra-process CPU collectives rendezvous by (devices, op) — two
#: partitioned computations RUNNING concurrently (even from different
#: engine instances: host-platform devices are process-global)
#: interleave their rendezvous and deadlock. Collective-bearing launches
#: therefore hold this process-global lock across dispatch +
#: block_until_ready; real accelerators have a hardware-ordered
#: collective queue and never take it.
_CPU_COLLECTIVE_LOCK = threading.Lock()

#: shared worker pools (module-level: fetch/launch work is engine-
#: agnostic, and per-engine pools would leak threads across the many
#: short-lived engines tests create)
_LAUNCH_THREADS = 8
_FETCH_THREADS = 4
_STAGING_THREADS = 4
_UPLOAD_THREADS = 4
_pools_lock = threading.Lock()
_launch_pool: Optional[ThreadPoolExecutor] = None
_fetch_pool: Optional[ThreadPoolExecutor] = None
_staging_pool: Optional[ThreadPoolExecutor] = None
_upload_pool: Optional[ThreadPoolExecutor] = None


def launch_pool() -> ThreadPoolExecutor:
    global _launch_pool
    with _pools_lock:
        if _launch_pool is None:
            _launch_pool = ThreadPoolExecutor(
                max_workers=_LAUNCH_THREADS,
                thread_name_prefix="kernel-launch")
        return _launch_pool


def fetch_pool() -> ThreadPoolExecutor:
    global _fetch_pool
    with _pools_lock:
        if _fetch_pool is None:
            _fetch_pool = ThreadPoolExecutor(
                max_workers=_FETCH_THREADS,
                thread_name_prefix="kernel-fetch")
        return _fetch_pool


def staging_pool() -> ThreadPoolExecutor:
    global _staging_pool
    with _pools_lock:
        if _staging_pool is None:
            _staging_pool = ThreadPoolExecutor(
                max_workers=_STAGING_THREADS,
                thread_name_prefix="kernel-staging")
        return _staging_pool


def upload_pool() -> ThreadPoolExecutor:
    """Residency row uploads (host->device device_put) fan out here so a
    multi-row miss double-buffers: row N+1's copy engines run while row
    N's transfer is in flight, and — because staging itself runs on the
    staging pool under execute_async — the whole upload burst overlaps
    the previous query's device round trip. A DEDICATED pool: staging
    tasks submit these and wait, so sharing the staging pool would
    deadlock once its workers are all waiting on their own subtasks."""
    global _upload_pool
    with _pools_lock:
        if _upload_pool is None:
            _upload_pool = ThreadPoolExecutor(
                max_workers=_UPLOAD_THREADS,
                thread_name_prefix="residency-upload")
        return _upload_pool


#: launches the ring lets onto the device at once (the non-collective
#: path): a batch popped while this many are in flight is HELD in the
#: ring, where it keeps growing, and launched the moment one lands. A
#: launch behind a busy device waits either way: on the device's queue
#: alone, or here, joining a batch that reads the columns once.
_HOLD_DEPTH = 2
#: a held batch's members' cancel checks run this often (s)
_HOLD_POLL_S = 0.05


def _pow2(n: int) -> int:
    v = 1
    while v < n:
        v *= 2
    return v


#: chunk size for bounded future waits: long enough to stay off the
#: hot path's profile, short enough that a deadline trips promptly
_RESULT_POLL_S = 0.25

#: default hard backstop on any wait_result call. Callers with a query
#: attached pass a cancel_check that trips the deadline far sooner;
#: this exists so a caller with NO budget (warmup/prestage, a query
#:  submitted without an id) still cannot park a thread forever on a
#: wedged device link. An explicit max_wait_s=None opts out.
DEFAULT_WAIT_CAP_S = 600.0


def wait_result(future: Future, cancel_check=None,
                max_wait_s: Optional[float] = DEFAULT_WAIT_CAP_S,
                poll_s: float = _RESULT_POLL_S):
    """Deadline-bounded ``future.result()``: the unbounded-wait fix the
    hang-risk lint demands on every dispatcher wait.

    The ring promises to complete every popped launch's future, but
    that invariant lives a module away from the caller blocked in
    ``.result()`` — a producer bug (or a launch stuck on a wedged
    device) must surface as the QUERY's own deadline error, not as a
    server thread parked forever. So the wait is chunked: each poll
    runs ``cancel_check`` (the ResourceAccountant checker carrying the
    query's remaining PR-3 deadline budget — it raises
    BrokerTimeoutError/QueryCancelledError past the wall), and
    ``max_wait_s`` (DEFAULT_WAIT_CAP_S unless overridden) is the hard
    backstop for budget-less callers — prestage/warmup paths, or a
    query submitted without an id, where cancel_check is None.
    """
    deadline = None if max_wait_s is None else time.monotonic() + max_wait_s
    while True:
        try:
            return future.result(timeout=poll_s)
        except (_FutureTimeout, TimeoutError):
            if future.done():
                # either the WORK raised a timeout error, or the future
                # completed inside the poll-expiry race window (result()
                # timed out, the dispatcher thread landed the value
                # before this check) — a zero-timeout result()
                # disambiguates: the landed value if there is one, the
                # work's own exception otherwise. Never re-raise the
                # poll's timeout for a future that is done.
                return future.result(timeout=0)
            if cancel_check is not None:
                cancel_check()
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(
                    f"device launch incomplete after {max_wait_s}s "
                    f"(dispatcher wedged?)") from None


def phase_annotation(phase: str, span):
    """`jax.profiler.TraceAnnotation("pinot:<phase>")` round one host
    phase of a TRACED launch (span = its DeviceDispatch handle): level
    1, which a profiler started with host_tracer_level >= 1 records on
    this thread's line, and a few hundred ns when none runs. Untraced
    launches (span None: pinot.trace.enabled=false) make no call."""
    if span is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation("pinot:" + phase,
                                        trace_id=span.trace_id or "")


def gc_annotation(generation: int):
    """`jax.profiler.TraceAnnotation("pinot:gc")` round one collection
    of the server process (tracing.GcProbe enters it at the collection's
    start and leaves it at its stop), tagged with its generation: the
    collector on the host plane above the device's idle gaps."""
    return jax.profiler.TraceAnnotation("pinot:gc", generation=generation)


def start_copy(out) -> None:
    """Queue the device->host copy of a just-launched result behind its
    kernel, as `np.asarray` on the unready array used to: the explicit
    wait for the device that follows (so that device wait and copy each
    get a clock) then costs no second round trip to the device. Kernel
    stand-ins that return host arrays have nothing to start."""
    start = getattr(out, "copy_to_host_async", None)
    if start is not None:
        start()


def host_arg_bytes(params) -> int:
    """Bytes of a launch's params that are still host (numpy) arrays:
    the packed [K, S] parameters of one query, or a batch's [B, K, S]
    (plan_ir.batch_params). They reach the device as arguments of the
    jit call, one transfer a launch."""
    return sum(v.nbytes for v in (params or {}).values()
               if isinstance(v, np.ndarray))


def split_packed(arr: np.ndarray, n: int) -> List[np.ndarray]:
    """Zero-copy per-member split of a batched result fetch (ROADMAP
    item): the N coalesced callers receive VIEWS into the ONE packed
    device->host array (basic indexing on the leading query axis), not N
    host-side copies — the fetch pool materializes each launch's bytes
    exactly once regardless of batch size. Padding members (replicated
    leader params past `n`) are simply never viewed. The view guarantee
    is asserted here because a silent regression to copies would
    multiply fetch-pool memory traffic by the batch size with no
    functional symptom."""
    members = [arr[i] for i in range(n)]
    assert all(m.base is not None and np.shares_memory(m, arr)
               for m in members), "batched split must return views"
    return members


def compiled_batched_kernel(plan, B: int, stacked: bool = False):
    """Compat alias: the batched factory now lives in ops/kernels.py as
    part of the unified kernel factory (keyed on plan fingerprint +
    shape bucket, broadcast and stacked variants)."""
    return kernels.compiled_batched_kernel(plan, B, stacked)


def split_charge(live: List["Launch"], kernel_ms: float) -> None:
    """Workload accounting for one launch: charge its device kernel ms
    across the coalesced members by DOC SHARE — a member that brought
    90% of the scanned docs bought 90% of the launch. The invariant the
    property test pins: the per-member charges sum to the launch total
    (each share is an exact fraction of kernel_ms over the live-member
    doc total). Members whose query detached (no slip: warmup, MSE
    internal calls, finished queries) still count in the denominator —
    their share is simply unrecorded, never redistributed, so an
    attributed member's bill does not depend on its neighbors'
    bookkeeping."""
    if kernel_ms is None or kernel_ms <= 0:
        return
    total_docs = sum(max(0, it.docs) for it in live)
    n = len(live)
    for it in live:
        if it.slip is None:
            continue
        share = (kernel_ms * (max(0, it.docs) / total_docs)
                 if total_docs > 0 else kernel_ms / n)
        try:
            it.slip.add(device_kernel_ms=share)
        except Exception:  # noqa: BLE001 — accounting must never
            # fail a query's result delivery
            pass


class _LaunchClock:
    """The clock reads round one TRACED kernel launch, turned into its
    DeviceDispatch span attrs; an untraced launch (span None) makes none
    of them. Monotonic seconds time the phases, `time.time_ns()` stamps
    launch and ready on the wall clock the spans' `startNs` share."""

    __slots__ = ("traces0", "t0", "launch_ns", "attrs")

    def __init__(self, popped: Optional[float] = None):
        self.traces0 = kernels.trace_count()
        self.t0 = time.monotonic()
        self.launch_ns = time.time_ns()
        self.attrs: Dict[str, Any] = {}
        if popped is not None:
            # ring path: popped off the ring -> the launch call starts
            # (the batch's inputs put together, the launch pool's
            # hand-off); queueWaitMs ends where this begins
            self.attrs["dispatchMs"] = round((self.t0 - popped) * 1e3, 3)

    def launched(self) -> None:
        """The kernel call returned. A call that traced a kernel says
        so: `retraceEvents` (best-effort: a concurrent launch's trace
        may land in the window) and `compileMs`, the call's own time —
        trace + lower + compile or cache load, an upper bound on the
        compile."""
        self.t0, t0 = time.monotonic(), self.t0
        launch_ms = round((self.t0 - t0) * 1e3, 3)
        self.attrs.update(launchNs=self.launch_ns, launchMs=launch_ms)
        retraces = kernels.trace_count() - self.traces0
        if retraces > 0:
            self.attrs.update(retraceEvents=retraces, compileMs=launch_ms)

    def ready(self) -> None:
        """jax.block_until_ready returned. deviceWaitMs is a HOST clock
        from the launch call returning: queueing behind other launches
        on the device and the wake-up are in it, so it bounds device
        time from above and is never kernel time."""
        self.t0, t0 = time.monotonic(), self.t0
        self.attrs.update(readyNs=time.time_ns(),
                          deviceWaitMs=round((self.t0 - t0) * 1e3, 3))

    def copied(self, live: List["Launch"]) -> None:
        """np.asarray returned: the device->host copy. Each member's
        hand-over to its caller starts here (Launch.end_span)."""
        self.t0, t0 = time.monotonic(), self.t0
        self.attrs["d2hMs"] = round((self.t0 - t0) * 1e3, 3)
        for it in live:
            it.done_ts = self.t0


class _NoClock:
    """An untraced launch's clock: reads nothing, holds no attrs."""

    attrs: Dict[str, Any] = {}

    def launched(self) -> None:
        pass

    ready = launched

    def copied(self, live) -> None:
        pass


_NO_CLOCK = _NoClock()


def _clock_for(span, popped: Optional[float] = None):
    return _NO_CLOCK if span is None else _LaunchClock(popped)


class Launch:
    """One staged device launch waiting in the ring.

    `call` runs the already-compiled single-query kernel; `params` is
    the staged dict (the packed [K, S] parameters a HOST array in it,
    which rides the launch as a jit argument: `_charge_args` counts
    its bytes; what else it holds is on the device); the other batching
    fields (plan/cols/num_docs/D/G) are only read when
    `batch_key` is set and the ring coalesces this launch with
    fingerprint-equal peers. `batch_key` is the SHAPE-BUCKET key (plan,
    S, D, G, array-shape signature) — members of one batch may stage
    different tables; `cols_key` is the concrete staged-batch identity
    the dispatcher compares to choose broadcast (all members share one
    set of column blocks) vs stacked (each member's blocks stack along a
    leading axis) execution. `factory(B, stacked)` builds the batched
    kernel for this launch's engine (plain vmap or vmap-in-shard_map on
    doc-sharded meshes). `cancel_check` is polled while queued — raising
    removes the launch from its batch and fails the future with the
    raised error (the ResourceAccountant deadline/cancel checker)."""

    __slots__ = ("call", "plan", "cols", "params", "num_docs", "D", "G",
                 "batch_key", "cols_key", "factory", "dedup_factory",
                 "collective", "cancel_check", "site_ctx", "future",
                 "span", "enq_ts", "staged_ts", "done_ts", "slip", "docs")

    def __init__(self, call: Callable[[], Any], plan=None, cols=None,
                 params=None, num_docs=None, D: int = 0, G: int = 0,
                 batch_key: Optional[tuple] = None,
                 cols_key: Optional[tuple] = None,
                 factory: Optional[Callable[[int, bool], Any]] = None,
                 dedup_factory: Optional[Callable[[int, int], Any]] = None,
                 collective: bool = False,
                 cancel_check: Optional[Callable[[], None]] = None,
                 site_ctx: Optional[Dict[str, Any]] = None,
                 span=None, slip=None, docs: int = 0,
                 staged_ts: float = 0.0):
        self.call = call
        self.plan = plan
        self.cols = cols
        self.params = params
        self.num_docs = num_docs
        self.D = D
        self.G = G
        self.batch_key = batch_key
        self.cols_key = cols_key
        self.factory = factory
        #: optional (B, U) -> kernel for SAME-COLS MEMBER GROUPING in a
        #: stacked batch: members with identity-equal staged blocks
        #: share one stack entry (engines that can't dedup leave it None)
        self.dedup_factory = dedup_factory
        self.collective = collective
        self.cancel_check = cancel_check
        self.site_ctx = site_ctx or {}
        self.future: Future = Future()
        #: tracing.SpanHandle captured on the CALLER thread (contextvars
        #: don't flow into the ring/launch/fetch pools) — the dispatcher
        #: attaches queue-wait / batch / kernel / fetch attrs through it
        self.span = span
        #: accounting.ChargeSlip captured on the CALLER thread (same
        #: discipline as span): the dispatcher charges this launch's
        #: device kernel ms through it — a coalesced launch's bill
        #: splits across members by `docs` share (split_charge)
        self.slip = slip
        #: real docs staged for this member (the cost-split weight)
        self.docs = int(docs)
        #: time.monotonic() of a TRACED launch's two hand-overs (0.0 =
        #: not taken): staging done (the engine's `_staging_attrs`) and
        #: the result's copy landed (the dispatcher's); with enq_ts they
        #: give submitMs and handoffMs
        self.staged_ts = staged_ts
        self.done_ts = 0.0
        self.enq_ts = 0.0

    def queue_attrs(self, now: float) -> Dict[str, Any]:
        """Span attrs of the way into the ring, at `now` (popped for
        launch): submitMs (staged -> submitted: coalesce key, this
        object, the ring's lock) and queueWaitMs (submitted -> now)."""
        attrs = {"queueWaitMs": round((now - self.enq_ts) * 1e3, 3)
                 if self.enq_ts else 0.0}
        if self.staged_ts and self.enq_ts:
            attrs["submitMs"] = round(
                (self.enq_ts - self.staged_ts) * 1e3, 3)
        return attrs

    def end_span(self) -> None:
        """End the DeviceDispatch span on the thread that now holds the
        result: handoffMs runs from the copy landed to here (the
        dispatcher's bookkeeping, the future's wake-up, the GIL)."""
        if self.span is None:
            return
        if self.done_ts:
            self.span.end(handoffMs=round(
                (time.monotonic() - self.done_ts) * 1e3, 3))
        else:
            self.span.end()


class KernelDispatcher:
    """Owns device launches for one engine: ring + batching + overlap."""

    #: ring thread exits after this much idle time (a fresh submit
    #: respawns it) — engines are created freely in tests and a
    #: threads-forever design would leak one per instance
    IDLE_EXIT_S = 5.0

    def __init__(self, config=None, metrics=None,
                 labels: Optional[Dict[str, str]] = None):
        from pinot_tpu.utils.config import PinotConfiguration
        from pinot_tpu.utils.metrics import get_registry
        cfg = config or PinotConfiguration()
        self.mode = cfg.get_str("pinot.server.dispatch.mode") or "pipelined"
        self.ring_size = max(1, cfg.get_int("pinot.server.dispatch.ring.size"))
        self.batch_max = max(1, cfg.get_int("pinot.server.dispatch.batch.max"))
        # window.ms=auto sizes the coalesce wait from an EWMA of observed
        # caller inter-arrival times, clamped to [0.5x, 4x] of the static
        # catalog default: a bursty fleet waits about one inter-arrival
        # (just long enough for its peers to land), a lone tight-loop
        # caller converges to the floor — and lone IDLE callers never
        # consult the window at all (inline fast path)
        from pinot_tpu.utils.config import KEYS
        raw_window = cfg.get("pinot.server.dispatch.batch.window.ms")
        static_s = max(0.0, float(
            KEYS["pinot.server.dispatch.batch.window.ms"]) / 1e3)
        self.window_auto = str(raw_window).strip().lower() == "auto"
        if self.window_auto:
            self.window_s = static_s
        else:
            self.window_s = max(0.0, float(raw_window) / 1e3)
        self._window_floor_s = 0.5 * static_s
        self._window_ceil_s = 4.0 * static_s
        self._arrival_ewma_s: Optional[float] = None
        self._last_arrival: Optional[float] = None
        self._metrics = metrics if metrics is not None \
            else get_registry("server")
        self._labels = labels
        self._cv = threading.Condition()
        self._pending: List[Launch] = []
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        #: callers currently inside an engine execute for this engine —
        #: the batching window only waits when >1 (a lone client never
        #: pays window latency for a batch that cannot form)
        self._active = 0
        #: launches in flight (handed to the device, result not yet
        #: fetched), under _cv: a lone submit takes the inline fast path
        #: only while this is 0, and the ring holds a batch while it is
        #: _HOLD_DEPTH or more (_busy_end wakes it)
        self._inflight = 0
        self._trace_seen = kernels.trace_count()
        self._trace_seen_by_plan = kernels.trace_count_by_plan()
        self._trace_meter_lock = threading.Lock()

    # -- caller accounting --------------------------------------------
    @contextlib.contextmanager
    def active(self):
        self.enter_active()
        try:
            yield
        finally:
            self.exit_active()

    def enter_active(self) -> None:
        with self._cv:
            self._active += 1
            self._cv.notify_all()

    def exit_active(self) -> None:
        with self._cv:
            self._active = max(0, self._active - 1)
            self._cv.notify_all()

    # -- in-flight count ----------------------------------------------
    def _busy_begin(self) -> None:
        with self._cv:
            self._inflight += 1

    def _busy_end(self) -> None:
        with self._cv:
            self._inflight -= 1
            self._cv.notify_all()

    # -- metrics helpers ----------------------------------------------
    def observe(self, name: str, value: float) -> None:
        self._metrics.add_timing(name, value, labels=self._labels)

    def _charge_args(self, live: List["Launch"], params) -> None:
        """The host->device bytes a launch's own arguments cost (the
        packed parameters: no `_put` counts them, the jit call makes the
        transfer): `hbm_transfer_bytes` once a launch, `paramsXferBytes`
        (the launch's total) on every member's span, and each member's
        own pack on its charge slip."""
        nbytes = host_arg_bytes(params)
        if not nbytes:
            return
        self._metrics.add_meter("hbm_transfer_bytes", nbytes,
                                labels=self._labels)
        for it in live:
            if it.span is not None:
                it.span.set(paramsXferBytes=nbytes)
            if it.slip is not None:
                it.slip.add(transfer_bytes=host_arg_bytes(it.params))

    def _set_depth_locked(self) -> None:
        self._metrics.set_gauge("dispatch_queue_depth", len(self._pending),
                                labels=self._labels)

    def _meter_traces(self) -> None:
        # read-modify-write under a lock: finishes land concurrently on
        # caller/launch/fetch threads, and a racy double-read would
        # double-count the retrace meter precisely under the concurrent
        # load it exists to watch
        with self._trace_meter_lock:
            now = kernels.trace_count()
            delta = now - self._trace_seen
            if delta <= 0:
                return
            self._trace_seen = now
            # per-plan-fingerprint attribution: a retrace storm names the
            # plan that churned, straight from /metrics
            by_plan = kernels.trace_count_by_plan()
            plan_deltas = {}
            for fp, n in by_plan.items():
                d = n - self._trace_seen_by_plan.get(fp, 0)
                if d > 0:
                    plan_deltas[fp] = d
            self._trace_seen_by_plan = by_plan
        self._metrics.add_meter("kernel_retrace", delta,
                                labels=self._labels)
        # attribution rides a SEPARATE series name: reusing
        # kernel_retrace with an extra label would double-count any
        # sum() across label sets (the aggregate must stay summable)
        for fp, d in plan_deltas.items():
            labels = dict(self._labels or {})
            labels["plan"] = fp
            self._metrics.add_meter("kernel_retrace_by_plan", d,
                                    labels=labels)

    # -- adaptive batching window --------------------------------------
    def _note_arrival_locked(self) -> None:
        """EWMA of submit inter-arrival gaps (auto window mode). Gaps
        past the clamp ceiling are recorded AT the ceiling: idle pauses
        must not take many queries to forget, only to remember."""
        if not self.window_auto:
            return
        now = time.monotonic()
        if self._last_arrival is not None:
            gap = min(now - self._last_arrival, self._window_ceil_s)
            cur = self._arrival_ewma_s
            self._arrival_ewma_s = gap if cur is None \
                else 0.8 * cur + 0.2 * gap
        self._last_arrival = now

    def current_window_s(self) -> float:
        """The coalesce wait in effect: static knob, or the clamped
        inter-arrival EWMA under window.ms=auto — scaled down while the
        brownout ladder's batch_shrink rung is engaged
        (health/brownout.py): under overload, queue latency buys more
        goodput than coalescing efficiency."""
        from pinot_tpu.health.brownout import window_scale
        scale = window_scale("server")
        if not self.window_auto or self._arrival_ewma_s is None:
            return self.window_s * scale
        return scale * min(self._window_ceil_s,
                           max(self._window_floor_s,
                               self._arrival_ewma_s))

    # -- submission ----------------------------------------------------
    def submit(self, launch: Launch) -> Future:
        """Enqueue a staged launch; returns its future (an np.ndarray of
        the packed kernel output, or the launch's error). Blocks for ring
        space (backpressure), polling the launch's cancel check."""
        launch.enq_ts = time.monotonic()
        if self.mode == "serialized":
            return self._submit_serialized(launch)
        with self._cv:
            self._note_arrival_locked()
            idle = (self._active <= 1 and not self._pending
                    and self._inflight == 0)
        if idle:
            # lone-query fast path: no concurrency means nothing to
            # coalesce or overlap — dispatch inline and pay ZERO ring
            # latency (single-stream p50 stays at the pre-ring floor).
            # A racing second caller just falls back to the collective
            # lock inside, which is the pre-ring behavior anyway.
            return self._submit_serialized(launch)
        with self._cv:
            while len(self._pending) >= self.ring_size and not self._closed:
                if launch.cancel_check is not None:
                    try:
                        launch.cancel_check()
                    except BaseException as e:  # noqa: BLE001
                        launch.future.set_exception(e)
                        return launch.future
                self._cv.wait(0.05)
            if self._closed:
                launch.future.set_exception(
                    RuntimeError("dispatcher closed"))
                return launch.future
            self._pending.append(launch)
            self._set_depth_locked()
            self._ensure_thread_locked()
            self._cv.notify_all()
        return launch.future

    def _submit_serialized(self, launch: Launch) -> Future:
        """Inline dispatch + fetch on the caller thread, the collective
        lock held across both: the exact pre-PR `_dispatch_guard`
        behavior. Serves both the `serialized` compat mode (A/B baseline
        + escape hatch) and the pipelined mode's lone-query fast path.
        The dispatch failpoint fires here too, so chaos schedules hit
        every dispatch regardless of path."""
        try:
            fire("server.dispatch.before", **launch.site_ctx)
            if launch.cancel_check is not None:
                launch.cancel_check()
            guard = _CPU_COLLECTIVE_LOCK if launch.collective \
                else contextlib.nullcontext()
            span = launch.span
            self._charge_args([launch], launch.params)
            self._busy_begin()
            clock = _clock_for(span)
            t0 = time.monotonic()
            try:
                with guard:
                    with phase_annotation("launch", span):
                        out = launch.call()
                        start_copy(out)
                    clock.launched()
                    with phase_annotation("device_wait", span):
                        jax.block_until_ready(out)
                    clock.ready()
                    with phase_annotation("d2h", span):
                        packed = np.asarray(out)
                    kernel_ms = (time.monotonic() - t0) * 1e3
                    clock.copied([launch])
            finally:
                self._busy_end()
                self._meter_traces()
            if span is not None:
                # inline path: kernelMs is the whole sync round trip
                # (launch + device wait + copy) and fetchMs 0 — the
                # older, coarser pair the accounting and the benchmark's
                # launch_fetch_ms read; launchMs/deviceWaitMs/d2hMs are
                # its three parts
                span.set(
                    batchSize=1, variant="inline",
                    kernelMs=round(kernel_ms, 3),
                    fetchMs=0.0, **launch.queue_attrs(t0), **clock.attrs)
            split_charge([launch], kernel_ms)
            launch.future.set_result(packed)
        except BaseException as e:  # noqa: BLE001 — future carries it
            launch.future.set_exception(e)
        return launch.future

    def close(self) -> None:
        with self._cv:
            self._closed = True
            for it in self._pending:
                it.future.set_exception(RuntimeError("dispatcher closed"))
            self._pending.clear()
            self._cv.notify_all()

    # -- ring thread ---------------------------------------------------
    def _ensure_thread_locked(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="kernel-dispatch")
            self._thread.start()

    def _loop(self) -> None:
        while True:
            with self._cv:
                end = time.monotonic() + self.IDLE_EXIT_S
                while not self._pending:
                    if self._closed:
                        self._thread = None
                        return
                    left = end - time.monotonic()
                    if left <= 0:
                        self._thread = None
                        return
                    self._cv.wait(left)
                leader = self._pending.pop(0)
                self._set_depth_locked()
                self._cv.notify_all()
            self._dispatch_one(leader)

    def _dispatch_one(self, leader: Launch) -> None:
        """Every exit path MUST complete every popped launch's future —
        a future left unset strands its caller in .result() forever (the
        unbounded-wait class the deadline work removed), so the whole
        body is guarded and failures fan out to the batch."""
        batch = [leader]
        try:
            # chaos hook: delay/fail a dispatch inside the ring (a delay
            # here also widens the coalescing window, which is exactly
            # what a chaos test wants to provoke batching determinism)
            fire("server.dispatch.before", **leader.site_ctx)
            held_at = self._coalesce(leader, batch)
            self._dispatch_batch(batch, held_at)
        except BaseException as e:  # noqa: BLE001 — futures carry it
            self._fail(batch, e)

    def _dispatch_batch(self, batch: List[Launch],
                        held_at: Optional[float] = None) -> None:
        # deadline/cancel checks honored while queued: a cancelled query
        # leaves the batch before launch. The `server.dispatch.batch`
        # failpoint fires PER MEMBER inside the coalesced path: an
        # erroring member fails only its own future — peers stay in the
        # batch and complete (chaos tests pin this isolation).
        coalesced = len(batch) > 1
        live: List[Launch] = []
        for it in batch:
            try:
                if it.cancel_check is not None:
                    it.cancel_check()
                if coalesced:
                    fire("server.dispatch.batch", batch_size=len(batch),
                         **it.site_ctx)
                live.append(it)
            except BaseException as e:  # noqa: BLE001
                it.future.set_exception(e)
        if not live:
            return
        self.observe("dispatch_batch_size", float(len(live)))
        if held_at is not None:
            self._metrics.add_meter("dispatch_held", 1, labels=self._labels)
        now = time.monotonic()
        for it in live:
            if it.span is not None:
                # each coalesced member reports into its OWN trace: the
                # shared launch's facts land on N distinct span trees.
                # heldMs: the part of queueWaitMs this member spent
                # waiting for an in-flight slot (0 where not held)
                held = 0.0 if held_at is None \
                    else (now - max(held_at, it.enq_ts)) * 1e3
                it.span.set(batchSize=len(live), heldMs=round(held, 3),
                            **it.queue_attrs(now))
        batched = len(live) > 1
        if batched:
            # pad to the batch-size bucket with replicated leader inputs
            # so jit's shape cache only ever sees bucketed batch sizes
            bucket = _pow2(len(live))
            lead = live[0]
            pad = bucket - len(live)
            # ONE [B, K, S] host array of the members' packed parameters
            # (what else a member's params hold is on the device already)
            plist = batch_params(
                [it.params for it in live] + [lead.params] * pad)
            self._charge_args(live, plist)
            # broadcast when every member staged the SAME column blocks
            # (one shared pass over one copy of the data); stacked when
            # members come from different tables/partitions in the same
            # shape bucket (blocks stack along a new leading axis —
            # device-resident rows, never a re-upload)
            stacked = any(it.cols_key != lead.cols_key for it in live)
            # same-cols member grouping: members whose staged blocks are
            # identity-equal (same table/segments, different literals)
            # share ONE stack entry — a mixed batch of 8 queries over 3
            # tables stacks 3 column sets, not 8
            uniq_pos: Dict[tuple, int] = {}
            for it in live:
                uniq_pos.setdefault(it.cols_key, len(uniq_pos))
            dedup = (stacked and lead.dedup_factory is not None
                     and len(uniq_pos) < len(live))
            if dedup:
                kern = lead.dedup_factory(bucket, _pow2(len(uniq_pos)))
            elif lead.factory is not None:
                kern = lead.factory(bucket, stacked)
            else:
                kern = kernels.compiled_batched_kernel(
                    lead.plan, bucket, stacked)
            if dedup:
                self._metrics.add_meter("dispatch_batch_cross_table",
                                        len(live), labels=self._labels)
                self._metrics.add_meter(
                    "dispatch_batch_dedup", len(live) - len(uniq_pos),
                    labels=self._labels)
                by_pos = [None] * len(uniq_pos)
                for it in live:
                    p = uniq_pos[it.cols_key]
                    if by_pos[p] is None:
                        by_pos[p] = it
                ubucket = _pow2(len(uniq_pos))
                upad = ubucket - len(uniq_pos)
                clist = tuple(it.cols for it in by_pos) \
                    + (lead.cols,) * upad
                ndlist = tuple(it.num_docs for it in by_pos) \
                    + (lead.num_docs,) * upad
                idx = np.asarray(
                    [uniq_pos[it.cols_key] for it in live]
                    + [uniq_pos[lead.cols_key]] * pad, np.int32)
                call = lambda: kern(clist, plist, ndlist,  # noqa: E731
                                    idx, D=lead.D, G=lead.G)
            elif stacked:
                self._metrics.add_meter("dispatch_batch_cross_table",
                                        len(live), labels=self._labels)
                clist = tuple(it.cols for it in live) + (lead.cols,) * pad
                ndlist = tuple(it.num_docs for it in live) \
                    + (lead.num_docs,) * pad
                call = lambda: kern(clist, plist, ndlist,  # noqa: E731
                                    D=lead.D, G=lead.G)
            else:
                call = lambda: kern(lead.cols, plist,  # noqa: E731
                                    lead.num_docs, D=lead.D, G=lead.G)
            variant = ("dedup" if dedup else
                       "stacked" if stacked else "broadcast")
            for it in live:
                if it.span is not None:
                    it.span.set(variant=variant)
        else:
            call = live[0].call
            self._charge_args(live, live[0].params)
            if live[0].span is not None:
                live[0].span.set(variant="single")
        if live[0].collective:
            # CPU-collective ordering: ONE partitioned program in flight
            # process-wide; block on the ring (compute completion), then
            # hand the ready buffers to the fetch pool so the NEXT
            # launch overlaps this result's host assembly
            span = self._lead_span(live)
            clock = _clock_for(span, now)
            self._busy_begin()
            t0 = time.monotonic()
            try:
                with _CPU_COLLECTIVE_LOCK:
                    with phase_annotation("launch", span):
                        out = call()
                        start_copy(out)
                    clock.launched()
                    with phase_annotation("device_wait", span):
                        jax.block_until_ready(out)
                    clock.ready()
            except BaseException as e:  # noqa: BLE001
                self._busy_end()
                for it in live:
                    it.future.set_exception(e)
                return
            fetch_pool().submit(self._finish, live, out, batched,
                                (time.monotonic() - t0) * 1e3, clock)
        else:
            # real accelerators order their own queue and non-partitioned
            # host programs don't rendezvous, so nothing orders launches
            # here but the hold (_coalesce). In flight from THIS hand-off,
            # where the ring decides, not from the pool thread's start
            self._busy_begin()
            try:
                launch_pool().submit(self._run_and_finish, live, call,
                                     batched, now)
            except BaseException:
                self._busy_end()
                raise

    def _coalesce(self, leader: Launch,
                  batch: List[Launch]) -> Optional[float]:
        """Grow `batch` ([leader]) with fingerprint-equal launches from
        the ring, in two regimes told apart by what is observed:

          * device free: wait up to the batching window, but only while
            the engine observably has more callers than the batch holds
            (a lone query never waits);
          * _HOLD_DEPTH launches in flight (non-collective path): HOLD
            the batch, keep collecting up to batch_max, and return the
            moment `_busy_end` frees a slot (signalled through _cv, not
            polled). A launch behind a busy device waits either way; held
            here it joins a batch that reads the columns once. While
            held, members' cancel checks run every _HOLD_POLL_S and a
            cancelled member leaves with its own error; close() fails
            the rest. Launches of other keys wait their turn behind the
            held batch (FIFO); one that cannot grow is never held.

        Returns the time the hold began (None: not held), for heldMs."""
        if leader.batch_key is None or self.batch_max <= 1:
            return None
        holds = not leader.collective
        deadline = time.monotonic() + self.current_window_s()
        held_at = next_poll = None
        with self._cv:
            while True:
                i = 0
                while i < len(self._pending) and len(batch) < self.batch_max:
                    if self._pending[i].batch_key == leader.batch_key:
                        batch.append(self._pending.pop(i))
                        self._cv.notify_all()
                    else:
                        i += 1
                now = time.monotonic()
                if holds and self._inflight >= _HOLD_DEPTH:
                    if self._closed:
                        self._fail(batch, RuntimeError("dispatcher closed"))
                        break
                    if held_at is None:
                        held_at = now
                        next_poll = now + _HOLD_POLL_S
                    elif now >= next_poll:
                        next_poll = now + _HOLD_POLL_S
                        self._drop_cancelled(batch)
                        if not batch:
                            break
                    self._cv.wait(next_poll - now)
                    continue
                if held_at is not None:
                    break  # the slot is free: launch what has gathered
                target = min(self.batch_max, max(1, self._active))
                if len(batch) >= target or now >= deadline:
                    break
                self._cv.wait(deadline - now)
            self._set_depth_locked()
        return held_at

    @staticmethod
    def _fail(batch: List[Launch], error: BaseException) -> None:
        for it in batch:
            if not it.future.done():
                it.future.set_exception(error)
        batch.clear()

    @staticmethod
    def _drop_cancelled(batch: List[Launch]) -> None:
        """Run each member's cancel check; one that raises leaves the
        batch with the raised error on its future (as `submit` does for
        a launch waiting for ring space)."""
        for it in list(batch):
            if it.cancel_check is None:
                continue
            try:
                it.cancel_check()
            except BaseException as e:  # noqa: BLE001
                it.future.set_exception(e)
                batch.remove(it)

    @staticmethod
    def _lead_span(live: List[Launch]):
        """The span a shared launch's profiler annotations are tagged
        with: the first traced member's (its trace id names the batch)."""
        return next((it.span for it in live if it.span is not None), None)

    def _run_and_finish(self, live: List[Launch], call, batched: bool,
                        popped: float) -> None:
        span = self._lead_span(live)
        clock = _clock_for(span, popped)
        t0 = time.monotonic()
        try:
            with phase_annotation("launch", span):
                out = call()
                start_copy(out)
        except BaseException as e:  # noqa: BLE001
            self._busy_end()
            for it in live:
                it.future.set_exception(e)
            self._meter_traces()
            return
        kernel_ms = (time.monotonic() - t0) * 1e3
        clock.launched()
        self._finish(live, out, batched, kernel_ms, clock)

    def _finish(self, live: List[Launch], out, batched: bool,
                kernel_ms: float, clock) -> None:
        """Wait for the device, fetch (device->host) + split per caller;
        runs OFF the ring. `clock` holds a traced launch's reads so far
        (launched, and ready where the ring already waited under the
        collective lock); coalesced members each get the batch's values,
        as they do kernelMs (ring path: the launch call, plus the wait
        on the collective path, where d2hMs also holds the hand-off
        to the fetch pool) and fetchMs (the rest: device wait + copy).
        Each of the two ends on a clock read taken just BEFORE the
        clock's own (`launched`, `copied`), so kernelMs + fetchMs never
        exceed launchMs + deviceWaitMs + d2hMs, however long the thread
        waits between the reads; the busy bookkeeping after the copy is
        in neither.
        The busy interval (opened at launch) closes when the
        fetch lands — and BEFORE the futures resolve: a caller woken by
        its result must observe an idle dispatcher, or its next lone
        submit would race the busy bookkeeping and needlessly take the
        ring path (the inline fast path is what keeps lone p50 at the
        floor)."""
        span = self._lead_span(live)
        t0 = time.monotonic()
        try:
            if "readyNs" not in clock.attrs:
                with phase_annotation("device_wait", span):
                    jax.block_until_ready(out)
                clock.ready()
            with phase_annotation("d2h", span):
                arr = np.asarray(out)
            fetch_ms = (time.monotonic() - t0) * 1e3
            clock.copied(live)
        except BaseException as e:  # noqa: BLE001
            self._busy_end()
            self._meter_traces()
            for it in live:
                if not it.future.done():
                    it.future.set_exception(e)
            return
        self._busy_end()
        self._meter_traces()
        for it in live:
            if it.span is not None:
                it.span.set(kernelMs=round(kernel_ms, 3),
                            fetchMs=round(fetch_ms, 3), **clock.attrs)
        split_charge(live, kernel_ms)
        try:
            if batched:
                for member, it in zip(split_packed(arr, len(live)), live):
                    it.future.set_result(member)
            else:
                live[0].future.set_result(arr)
        except BaseException as e:  # noqa: BLE001
            for it in live:
                if not it.future.done():
                    it.future.set_exception(e)
