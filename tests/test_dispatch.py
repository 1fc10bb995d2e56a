"""Concurrent-query dispatch pipeline (ops/dispatch.py).

Pins the tentpole properties deterministically:
  * shared-plan micro-batching — fingerprint-equal concurrent queries
    coalesce into ONE vmapped launch and split back per caller,
    BIT-IDENTICAL to per-query execution (property-tested over random
    literal sets)
  * cancel/deadline discipline — a cancelled query leaves its batch
    before launch; a deadline that expires while queued surfaces as
    BrokerTimeoutError without executing
  * retrace guard — steady-state traffic over warmed (plan, batch-size
    bucket) shapes compiles NOTHING new (kernels.trace_count is the
    compile odometer; a regression here re-compiles the hot path per
    query and tanks serving latency)
  * seeded chaos — the server.dispatch.before failpoint replays exactly

Determinism trick: a one-shot delay failpoint on server.dispatch.before
holds the ring on the FIRST pop while the remaining threads enqueue, so
the batch composition is exact rather than a scheduling race.
"""
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              TableConfig, TableType)
from pinot_tpu.ops import dispatch, kernels
from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.query.context import QueryContext
from pinot_tpu.segment.creator import SegmentCreator
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.utils.accounting import (BrokerTimeoutError,
                                        QueryCancelledError,
                                        ResourceAccountant)
from pinot_tpu.utils.config import PinotConfiguration
from pinot_tpu.utils.failpoints import FailpointError, failpoints

HOLD_S = 0.25  # ring-hold long enough for peers to stage + enqueue


@pytest.fixture()
def segs(tmp_path):
    schema = Schema("t", [
        FieldSpec("d", DataType.INT, FieldType.DIMENSION),
        FieldSpec("m", DataType.INT, FieldType.METRIC)])
    tc = TableConfig("t", TableType.OFFLINE)
    tc.indexing.no_dictionary_columns = ["m"]
    creator = SegmentCreator(tc, schema)
    rng = np.random.default_rng(11)
    out = []
    for i in range(3):
        cols = {"d": rng.integers(0, 10, 4000).astype(np.int32),
                "m": rng.integers(0, 100, 4000).astype(np.int32)}
        p = str(tmp_path / f"s{i}")
        creator.build(cols, p, f"t_{i}")
        out.append(load_segment(p))
    return out


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.clear()
    yield
    failpoints.clear()


def make_engine(**overrides):
    return TpuOperatorExecutor(config=PinotConfiguration(overrides=overrides))


def agg_values(results):
    """Comparable value tuple per segment result (exact: int sums/counts
    stay integral in f64, so equality is bit-meaningful)."""
    out = []
    for r in results:
        if hasattr(r, "groups"):
            out.append(tuple(sorted(
                (k, tuple(float(v) for v in inters))
                for k, inters in r.groups.items())))
        else:
            out.append(tuple(float(v) for v in r.intermediates))
    return tuple(out)


def run_concurrent(eng, segs, ctxs, hold=HOLD_S):
    """Run ctxs concurrently with the ring held on the first pop, so all
    of them are enqueued before coalescing — deterministic batching.
    times=2: the first delay may be consumed by a racing thread's
    lone-query fast path (inline dispatch); the second then holds the
    ring leader while the rest enqueue."""
    failpoints.arm("server.dispatch.before", delay=hold, times=2)
    try:
        with ThreadPoolExecutor(len(ctxs)) as pool:
            futs = [pool.submit(eng.execute, segs, c) for c in ctxs]
            return [f.result() for f in futs]
    finally:
        failpoints.disarm("server.dispatch.before")


class TestMicroBatching:
    def test_coalesce_and_split_matches_per_query(self, segs):
        eng = make_engine()
        ctxs = [QueryContext.from_sql(
            f"SELECT SUM(m), COUNT(*), MIN(m) FROM t WHERE d < {k}")
            for k in range(1, 7)]
        singles = [agg_values(eng.execute(segs, c)[0]) for c in ctxs]
        reg = eng._dispatcher._metrics
        max0 = reg.timer("dispatch_batch_size").max_ms
        got = run_concurrent(eng, segs, ctxs)
        assert all(not rem for _r, rem in got)
        assert [agg_values(r) for r, _rem in got] == singles
        # batching actually happened (not six serialized singles)
        assert reg.timer("dispatch_batch_size").max_ms >= max(max0, 2)

    def test_group_by_batched_matches_per_query(self, segs):
        eng = make_engine()
        ctxs = [QueryContext.from_sql(
            f"SELECT d, SUM(m) FROM t WHERE m BETWEEN {a} AND {a + 40} "
            f"GROUP BY d") for a in (0, 10, 20, 30)]
        singles = [agg_values(eng.execute(segs, c)[0]) for c in ctxs]
        got = run_concurrent(eng, segs, ctxs)
        assert [agg_values(r) for r, _rem in got] == singles

    def test_bit_identical_property_over_random_literal_sets(self, segs):
        """Property: for ANY plan-fingerprint-equal query set, batched
        execution is bit-identical to per-query execution."""
        eng = make_engine()
        rng = np.random.default_rng(23)
        for _trial in range(4):
            k = int(rng.integers(2, 9))
            bounds = rng.integers(0, 100, size=(k, 2))
            ctxs = [QueryContext.from_sql(
                "SELECT SUM(m), COUNT(*), MAX(m) FROM t "
                f"WHERE m BETWEEN {min(a, b)} AND {max(a, b)} AND d < 8")
                for a, b in bounds]
            singles = [agg_values(eng.execute(segs, c)[0]) for c in ctxs]
            got = run_concurrent(eng, segs, ctxs)
            assert [agg_values(r) for r, _rem in got] == singles

    def test_serialized_mode_matches_pipelined(self, segs):
        """The A/B baseline mode (pre-ring inline dispatch) must stay
        result-identical — it's both the bench baseline and the escape
        hatch."""
        pipe = make_engine()
        ser = make_engine(**{"pinot.server.dispatch.mode": "serialized"})
        for sql in ("SELECT SUM(m), COUNT(*) FROM t WHERE d < 5",
                    "SELECT d, COUNT(*) FROM t GROUP BY d"):
            ctx = QueryContext.from_sql(sql)
            a, _ = pipe.execute(segs, ctx)
            b, _ = ser.execute(segs, ctx)
            assert agg_values(a) == agg_values(b)


class TestCancelAndDeadline:
    def test_cancelled_query_leaves_batch_before_launch(self, segs):
        eng = make_engine()
        ctxs = [QueryContext.from_sql(
            f"SELECT SUM(m), COUNT(*) FROM t WHERE d < {k}")
            for k in range(1, 5)]
        singles = [agg_values(eng.execute(segs, c)[0]) for c in ctxs]

        def cancelled():
            raise QueryCancelledError("cancelled by test")

        failpoints.arm("server.dispatch.before", delay=HOLD_S, times=2)
        try:
            with ThreadPoolExecutor(4) as pool:
                futs = [pool.submit(eng.execute, segs, c,
                                    cancelled if i == 1 else None)
                        for i, c in enumerate(ctxs)]
                with pytest.raises(QueryCancelledError):
                    futs[1].result()
                # survivors split correctly without the cancelled member
                for i in (0, 2, 3):
                    res, rem = futs[i].result()
                    assert not rem
                    assert agg_values(res) == singles[i]
        finally:
            failpoints.disarm("server.dispatch.before")

    def test_deadline_honored_while_queued(self, segs):
        eng = make_engine()
        ctx = QueryContext.from_sql("SELECT SUM(m) FROM t WHERE d < 5")
        eng.execute(segs, ctx)  # warm (staging off the timed path)
        acc = ResourceAccountant()
        acc.begin_query("q-dl", timeout_s=0.02)
        # hold the ring so the query sits QUEUED past its whole budget
        failpoints.arm("server.dispatch.before", delay=0.2, times=1)
        try:
            with ThreadPoolExecutor(2) as pool:
                blocker = pool.submit(eng.execute, segs, ctx)
                time.sleep(0.05)  # ring now busy; budget now expired
                with pytest.raises(BrokerTimeoutError):
                    eng.execute(segs, ctx, acc.checker("q-dl"))
                blocker.result()
        finally:
            failpoints.disarm("server.dispatch.before")
            acc.finish_query("q-dl")


class TestRetraceGuard:
    def test_steady_state_zero_retraces_and_zero_column_bytes(self, segs):
        """CI guard (ISSUE 6): a repeated-query steady state — singles
        AND coalesced batches over warmed shapes — must neither compile
        (compile odometer) nor ship ONE column byte host->device
        (transfer odometer): columns are resident, blocks are assembled
        and cached, params are plan-keyed. Either regression silently
        re-pays the host->device upload or a recompile per query in production."""
        from pinot_tpu.ops import residency
        eng = make_engine()
        ctxs = [QueryContext.from_sql(
            f"SELECT SUM(m), COUNT(*), MIN(m) FROM t WHERE d < {k}")
            for k in range(1, 9)]
        for c in ctxs:
            eng.execute(segs, c)      # warm singles (stage + compile)
        run_concurrent(eng, segs, ctxs)   # warm the batched bucket
        t0 = kernels.trace_count()
        b0 = residency.transfer_bytes()
        for c in ctxs:
            eng.execute(segs, c)
        run_concurrent(eng, segs, ctxs)
        assert kernels.trace_count() == t0, \
            "steady-state traffic re-compiled a kernel"
        assert residency.transfer_bytes() == b0, \
            "steady-state traffic uploaded host->device bytes"

    def test_steady_state_zero_retrace(self, segs):
        """CI guard: warmed (plan, shape, batch-size bucket) traffic must
        not compile ANYTHING — a compile-cache miss here re-traces the
        hot path per query in production."""
        eng = make_engine()

        def round_of(base):
            ctxs = [QueryContext.from_sql(
                f"SELECT SUM(m), COUNT(*) FROM t WHERE d < {base + k}")
                for k in range(8)]
            got = run_concurrent(eng, segs, ctxs)
            assert all(not rem for _r, rem in got)

        ctx0 = QueryContext.from_sql("SELECT SUM(m), COUNT(*) FROM t "
                                     "WHERE d < 1")
        eng.execute(segs, ctx0)      # warm the single-kernel shape
        round_of(0)                  # warm the bucket-8 batched shape
        before = kernels.trace_count()
        meter0 = eng._dispatcher._metrics.meter("kernel_retrace")
        round_of(1)                  # same shapes, fresh literals
        round_of(2)
        eng.execute(segs, ctx0)
        assert kernels.trace_count() == before, \
            "steady-state traffic re-compiled a kernel"
        assert eng._dispatcher._metrics.meter("kernel_retrace") == meter0


class TestDispatchChaos:
    def test_seeded_chaos_replays_exactly(self, segs):
        eng = make_engine()
        ctx = QueryContext.from_sql("SELECT SUM(m), COUNT(*) FROM t "
                                    "WHERE d < 4")
        eng.execute(segs, ctx)  # warm: compiles happen outside the chaos

        def run_round():
            fp = failpoints.arm("server.dispatch.before",
                                error=FailpointError("dispatch chaos"),
                                probability=0.5, seed=1234)
            outcomes = []
            try:
                for _ in range(10):
                    try:
                        res, rem = eng.execute(segs, ctx)
                        assert not rem
                        outcomes.append("ok")
                    except FailpointError:
                        outcomes.append("chaos")
            finally:
                failpoints.disarm("server.dispatch.before")
            return outcomes, list(fp.decisions)

        o1, d1 = run_round()
        o2, d2 = run_round()
        assert o1 == o2 and d1 == d2  # same seed -> exact replay
        assert "chaos" in o1 and "ok" in o1  # both paths exercised

    def test_dispatch_error_fails_only_that_query(self, segs):
        eng = make_engine()
        ctx = QueryContext.from_sql("SELECT COUNT(*) FROM t WHERE d < 3")
        eng.execute(segs, ctx)
        failpoints.arm("server.dispatch.before",
                       error=FailpointError("one-shot"), times=1)
        try:
            with pytest.raises(FailpointError):
                eng.execute(segs, ctx)
        finally:
            failpoints.disarm("server.dispatch.before")
        res, rem = eng.execute(segs, ctx)  # ring fully recovered
        assert not rem and res


class TestZeroCopySplit:
    def test_split_packed_returns_views(self):
        """ROADMAP item: per-member splits of a batched fetch are VIEWS
        into the one packed array, never host-side copies."""
        from pinot_tpu.ops import dispatch
        arr = np.arange(24.0).reshape(4, 6)
        members = dispatch.split_packed(arr, 3)
        assert len(members) == 3
        for i, m in enumerate(members):
            assert m.base is not None and np.shares_memory(m, arr)
            assert np.array_equal(m, arr[i])

    def test_batched_fetch_split_is_zero_copy_end_to_end(self, segs):
        """Through the REAL coalesced path: spy on split_packed and
        assert every member handed to a caller future shares memory with
        the packed fetch (and results stay correct)."""
        from pinot_tpu.ops import dispatch
        eng = make_engine()
        ctxs = [QueryContext.from_sql(
            f"SELECT SUM(m), COUNT(*) FROM t WHERE d < {k}")
            for k in range(1, 6)]
        singles = [agg_values(eng.execute(segs, c)[0]) for c in ctxs]
        calls = []
        orig = dispatch.split_packed

        def spy(arr, n):
            members = orig(arr, n)
            calls.append((arr, members))
            return members

        dispatch.split_packed = spy
        try:
            got = run_concurrent(eng, segs, ctxs)
        finally:
            dispatch.split_packed = orig
        assert [agg_values(r) for r, _rem in got] == singles
        assert calls, "no batch formed — the spy never fired"
        for arr, members in calls:
            for m in members:
                assert m.base is not None and np.shares_memory(m, arr)


class TestPipelineMetrics:
    def test_dispatch_metrics_populated(self, segs):
        eng = make_engine()
        reg = eng._dispatcher._metrics
        c0 = reg.timer("dispatch_batch_size").count
        ctxs = [QueryContext.from_sql(
            f"SELECT SUM(m), COUNT(*) FROM t WHERE d < {k}")
            for k in range(1, 5)]
        for c in ctxs:
            eng.execute(segs, c)
        run_concurrent(eng, segs, ctxs)
        t = reg.timer("dispatch_batch_size")
        assert t.count > c0
        assert t.max_ms >= 2  # a real batch formed
        assert reg.gauge("dispatch_queue_depth") is not None
        assert reg.meter("kernel_retrace") > 0  # compiles were metered

    def test_execute_async_overlaps_caller(self, segs):
        """execute_async returns before the device result lands, so the
        caller can run host-path work in parallel."""
        eng = make_engine()
        ctx = QueryContext.from_sql("SELECT SUM(m), COUNT(*) FROM t "
                                    "WHERE d < 6")
        want = agg_values(eng.execute(segs, ctx)[0])
        failpoints.arm("server.dispatch.before", delay=0.2, times=1)
        try:
            t0 = time.perf_counter()
            fut = eng.execute_async(segs, ctx)
            submitted_in = time.perf_counter() - t0
            res, rem = fut.result(timeout=10)
        finally:
            failpoints.disarm("server.dispatch.before")
        assert submitted_in < 0.15, "execute_async blocked the caller"
        assert not rem and agg_values(res) == want


class TestWaitResult:
    """Deadline-bounded future waits (dispatch.wait_result) — the fix
    idiom the hang-risk lint demands at every dispatcher wait."""

    def test_returns_value(self):
        from concurrent.futures import Future
        f = Future()
        f.set_result(41)
        assert dispatch.wait_result(f) == 41

    def test_completion_in_poll_expiry_race_window_returns_value(self):
        """Regression: a future that completes AFTER the 0.25s poll's
        result() raised but BEFORE the done() check must yield its
        value, not a spurious TimeoutError. The original code re-raised
        the poll's own timeout whenever done() was True — under
        sustained load (4 polls/sec per in-flight launch) that window
        failed healthy queries with 'timeout' while the packed result
        sat in the future."""
        from concurrent.futures import Future

        class RacyFuture(Future):
            """Simulates the race: the first result(timeout=) call
            raises the poll timeout, then the value lands."""
            def __init__(self):
                super().__init__()
                self._polled = False

            def result(self, timeout=None):
                if not self._polled:
                    self._polled = True
                    self.set_result(17)     # lands DURING the poll
                    raise TimeoutError()    # ...which already expired
                return super().result(timeout)

        assert dispatch.wait_result(RacyFuture(), poll_s=0.01) == 17

    def test_work_raised_timeout_propagates(self):
        """A TimeoutError raised BY the work is the query's own deadline
        tripping — it must propagate as-is, not spin the poll loop."""
        from concurrent.futures import Future
        f = Future()
        f.set_exception(TimeoutError("work deadline"))
        with pytest.raises(TimeoutError, match="work deadline"):
            dispatch.wait_result(f, poll_s=0.01)

    def test_cancel_check_runs_each_poll(self):
        from concurrent.futures import Future
        calls = []

        def checker():
            calls.append(1)
            if len(calls) >= 3:
                raise RuntimeError("query cancelled")

        with pytest.raises(RuntimeError, match="query cancelled"):
            dispatch.wait_result(Future(), cancel_check=checker, poll_s=0.005)
        assert len(calls) == 3

    def test_hard_cap_bounds_budgetless_wait(self):
        from concurrent.futures import Future
        t0 = time.perf_counter()
        with pytest.raises(TimeoutError, match="dispatcher wedged"):
            dispatch.wait_result(Future(), max_wait_s=0.05, poll_s=0.01)
        assert time.perf_counter() - t0 < 2.0


class _Span:
    """A DeviceDispatch span handle's surface, as the ring uses it."""
    trace_id = "t"

    def __init__(self):
        self.attrs = {}

    def set(self, **attrs):
        self.attrs.update(attrs)

    def end(self, **attrs):
        self.attrs.update(attrs)


class _Device:
    """A launch stub that holds the "device" for a fixed time: a launch
    is in flight from the ring's hand-off until its call returns."""

    def __init__(self, hold_s=0.15):
        self.hold_s = hold_s
        self.log = []          # (key, members) a launch, in launch order
        self.started = threading.Event()

    def _run(self, key, members, out):
        self.log.append((key, members))
        self.started.set()
        time.sleep(self.hold_s)
        return out

    def launch(self, key, cancel_check=None):
        def factory(bucket, stacked):
            # plist: the batch's params, one dict (plan_ir.batch_params);
            # what is no host array stays a tuple of the members' own
            return lambda cols, plist, num_docs, D, G: self._run(
                key, len({id(p) for p in plist["member"]}),  # padding
                np.zeros((bucket, 1)))                 # repeats one
        la = dispatch.Launch(
            call=lambda: self._run(key, 1, np.zeros((1, 1))),
            params={"member": object()}, batch_key=key, cols_key="cols",
            factory=factory, cancel_check=cancel_check, span=_Span())
        return la


def _ring(n_callers):
    from pinot_tpu.utils.metrics import MetricsRegistry
    disp = dispatch.KernelDispatcher(metrics=MetricsRegistry())
    for _ in range(n_callers):
        disp.enter_active()
    return disp


def _busy_ring(device, keys, depth, monkeypatch, cancel_at=None):
    """A ring whose in-flight depth is `depth`, with that many launches
    on the device (the first `depth` of `keys`, started one by one) and
    the rest submitted behind them, in order."""
    monkeypatch.setattr(dispatch, "_HOLD_DEPTH", depth)
    disp = _ring(len(keys))

    def check():
        if check.armed:
            raise QueryCancelledError("cancelled while held")
    check.armed = False
    launches = [device.launch(k, check if i == cancel_at else None)
                for i, k in enumerate(keys)]
    for la in launches[:depth]:
        device.started.clear()
        disp.submit(la)
        assert device.started.wait(5)
    for la in launches[depth:]:
        disp.submit(la)
    return disp, launches, check


def _wait_all(launches):
    for la in launches:
        assert dispatch.wait_result(la.future, max_wait_s=10) is not None


@pytest.mark.parametrize("depth", [1, 2])
class TestHeldRing:
    """The ring holds a batch while `_HOLD_DEPTH` launches are in flight
    and lets it grow (dispatch._coalesce), instead of queueing single
    launches on a busy device. The depth is a constant of the module;
    the mechanism is pinned at 1 (ISSUE 31's "six submits of one key:
    one launch of 1 and one of 5") and at 2."""

    def test_submits_behind_a_busy_device_leave_as_one_batch(
            self, depth, monkeypatch):
        device = _Device()
        busy = [("busy", i) for i in range(depth - 1)] + ["k"]
        disp, launches, _ = _busy_ring(device, busy + ["k"] * 5, depth,
                                       monkeypatch)
        _wait_all(launches)
        assert device.log == [(k, 1) for k in busy] + [("k", 5)]
        for la in launches[:depth]:
            a = la.span.attrs  # went straight on: inline, or not held
            assert a["batchSize"] == 1 and not a.get("heldMs")
        for la in launches[depth:]:
            a = la.span.attrs
            assert a["batchSize"] == 5 and a["variant"] == "broadcast"
            # held until a launch landed; the hold is IN the ring's
            # wait, not beside it
            assert 50 < a["heldMs"] <= a["queueWaitMs"] + 0.01
        assert disp._metrics.meter("dispatch_held") == 1
        t = disp._metrics.timer("dispatch_batch_size")
        assert t.max_ms == 5.0
        assert disp._inflight == 0

    def test_a_lone_caller_on_an_idle_ring_goes_inline(self, depth,
                                                       monkeypatch):
        monkeypatch.setattr(dispatch, "_HOLD_DEPTH", depth)
        device = _Device(hold_s=0.01)
        disp = _ring(1)
        for _ in range(3):
            la = device.launch("k")
            assert disp.submit(la).done()  # ran on this thread
            assert la.span.attrs["variant"] == "inline"
            assert "heldMs" not in la.span.attrs
        assert disp._metrics.meter("dispatch_held") == 0
        assert disp._thread is None

    def test_an_idle_device_keeps_the_window(self, depth, monkeypatch):
        """Nothing in flight: the 2 ms window and the callers target
        decide, as before the hold; nothing is held."""
        monkeypatch.setattr(dispatch, "_HOLD_DEPTH", depth)
        device = _Device(hold_s=0.0)
        disp = _ring(4)
        failpoints.arm("server.dispatch.before", delay=0.1, times=1)
        launches = [device.launch("k") for _ in range(4)]
        for la in launches:
            disp.submit(la)
        _wait_all(launches)
        assert device.log == [("k", 4)]
        assert [la.span.attrs["heldMs"] for la in launches] == [0.0] * 4
        assert disp._metrics.meter("dispatch_held") == 0

    @pytest.mark.parametrize("event", ["cancel", "close"])
    def test_a_member_leaves_a_held_batch(self, depth, monkeypatch, event):
        device = _Device(hold_s=0.3)
        busy = [("busy", i) for i in range(depth)]
        disp, launches, check = _busy_ring(
            device, busy + ["k"] * 4, depth, monkeypatch,
            cancel_at=depth + 1)
        held = launches[depth:]
        time.sleep(0.05)  # the four are held behind the busy device
        if event == "cancel":
            check.armed = True
            with pytest.raises(QueryCancelledError):
                dispatch.wait_result(held[1].future, max_wait_s=10)
            # it left while the batch was still held, and its peers
            # complete as a batch of three
            assert len(device.log) == depth
            _wait_all(launches[:depth] + [held[0], held[2], held[3]])
            assert device.log[depth:] == [("k", 3)]
        else:
            disp.close()
            for la in held:
                with pytest.raises(RuntimeError, match="dispatcher closed"):
                    dispatch.wait_result(la.future, max_wait_s=10)
            # the launches already on the device land
            _wait_all(launches[:depth])
            assert len(device.log) == depth

    def test_other_keys_keep_their_turn(self, depth, monkeypatch):
        """FIFO across keys: a launch of another key waits behind the
        held batch, and the held batch takes its own key's later
        arrivals with it."""
        device = _Device()
        busy = [("busy", i) for i in range(depth)]
        disp, launches, _ = _busy_ring(
            device, busy + ["a", "b", "a", "b"], depth, monkeypatch)
        _wait_all(launches)
        assert device.log[depth:] == [("a", 2), ("b", 2)]
        assert disp._metrics.meter("dispatch_held") >= 1
