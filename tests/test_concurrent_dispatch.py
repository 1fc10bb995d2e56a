"""Concurrent engine dispatch: staged repeat queries from N server
threads overlap on the device instead of serializing behind the engine
lock.

Ref: the reference serves 100k+ QPS through QueryScheduler
(query/scheduler/QueryScheduler.java:134) — VERDICT r3 item 10. The real
win is measured by bench.py's pipelined metric on hardware; this test
pins the concurrency PROPERTY deterministically by substituting a slow
kernel: if dispatch held the engine lock, 8 threads would take ~8x one
dispatch; overlapped they take ~1x.
"""
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              TableConfig, TableType)
from pinot_tpu.ops import kernels
from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.ops.plan_ir import PACK
from pinot_tpu.query.context import QueryContext
from pinot_tpu.segment.creator import SegmentCreator
from pinot_tpu.segment.loader import load_segment

KERNEL_S = 0.15


@pytest.fixture()
def segs(tmp_path):
    schema = Schema("t", [
        FieldSpec("d", DataType.INT, FieldType.DIMENSION),
        FieldSpec("m", DataType.INT, FieldType.METRIC)])
    tc = TableConfig("t", TableType.OFFLINE)
    tc.indexing.no_dictionary_columns = ["m"]
    creator = SegmentCreator(tc, schema)
    rng = np.random.default_rng(2)
    out = []
    for i in range(2):
        cols = {"d": rng.integers(0, 10, 1000).astype(np.int32),
                "m": rng.integers(0, 100, 1000).astype(np.int32)}
        p = str(tmp_path / f"s{i}")
        creator.build(cols, p, f"t_{i}")
        out.append(load_segment(p))
    return out


def test_dispatch_overlaps_across_threads(segs, monkeypatch):
    calls = []

    def slow_compiled_kernel(plan, mesh=None):
        def kernel(cols, params, num_docs, D, G=0):
            calls.append(time.perf_counter())
            time.sleep(KERNEL_S)  # a dispatch in flight
            S = params[PACK].shape[1]  # num_docs is its row 0
            return np.zeros((S, 1 + len(plan.agg_ops)), np.float32)
        return kernel

    monkeypatch.setattr(kernels, "compiled_kernel", slow_compiled_kernel)
    eng = TpuOperatorExecutor()
    ctx = QueryContext.from_sql("SELECT SUM(m) FROM t WHERE d < 5")
    # warm the caches so the measured loop is pure dispatch
    eng.execute(segs, ctx)

    t0 = time.perf_counter()
    n = 8
    with ThreadPoolExecutor(n) as pool:
        res = list(pool.map(lambda _: eng.execute(segs, ctx), range(n)))
    wall = time.perf_counter() - t0
    assert all(not rem for _r, rem in res)
    # serialized behind the lock this would be >= n * KERNEL_S (1.2s);
    # overlapped it is ~KERNEL_S plus scheduling slop
    assert wall < n * KERNEL_S / 2, \
        f"8 concurrent dispatches took {wall:.2f}s — serialized?"
    # and they genuinely overlapped: some dispatch STARTED before the
    # previous one could have finished
    starts = sorted(calls[-n:])
    assert starts[1] - starts[0] < KERNEL_S / 2


def test_results_stay_correct_under_concurrency(segs):
    eng = TpuOperatorExecutor()
    ctx = QueryContext.from_sql("SELECT SUM(m), COUNT(*) FROM t WHERE d < 5")
    from pinot_tpu.query import executor_cpu
    want = [executor_cpu.execute_segment(s, ctx) for s in segs]
    want_sum = sum(float(r.intermediates[0]) for r in want)
    want_cnt = sum(int(r.intermediates[1]) for r in want)

    def one(_):
        results, rem = eng.execute(segs, ctx)
        assert not rem
        got_sum = sum(float(r.intermediates[0]) for r in results)
        got_cnt = sum(int(r.intermediates[1]) for r in results)
        assert got_cnt == want_cnt
        assert abs(got_sum - want_sum) <= 1e-3 * max(1.0, abs(want_sum))
        return True

    with ThreadPoolExecutor(8) as pool:
        assert all(pool.map(one, range(32)))


@pytest.mark.parametrize("clients", [4, 6])
def test_a_busy_device_batches_the_queries_behind_it(segs, monkeypatch,
                                                     clients):
    """Real kernels, a device made slow: while the first launch is in
    flight the ring holds the queries behind it, they leave as ONE batch,
    and every caller gets the answer it gets alone (none dropped, none
    answered with a neighbour's literals)."""
    import jax
    from pinot_tpu.ops import dispatch
    # one device, as on the chip: the launch pool's path (the suite's
    # 8-device default orders launches under the CPU-collective lock)
    eng = TpuOperatorExecutor(devices=jax.devices()[:1])
    ctxs = [QueryContext.from_sql(
        f"SELECT SUM(m), COUNT(*) FROM t WHERE d < {k}")
        for k in range(1, clients + 1)]

    def values(results):
        return [tuple(float(v) for v in r.intermediates) for r in results]
    alone = [values(eng.execute(segs, c)[0]) for c in ctxs]
    timer = eng._dispatcher._metrics.timer
    launches0 = timer("dispatch_batch_size").count
    held0 = eng._dispatcher._metrics.meter("dispatch_held")
    # a launch stays in flight this long after its call returned; one in
    # flight holds the ring (the module's constant may let more through)
    monkeypatch.setattr(dispatch, "start_copy",
                        lambda out: time.sleep(KERNEL_S))
    monkeypatch.setattr(dispatch, "_HOLD_DEPTH", 1)
    with ThreadPoolExecutor(clients) as pool:
        got = list(pool.map(lambda c: eng.execute(segs, c), ctxs))
    assert [values(res) for res, _rem in got] == alone
    assert all(not rem for _res, rem in got)
    # the first went alone (inline or off the ring's window), the rest in
    # one held batch: two ring launches at most, where a ring that
    # launches behind a busy device makes one a query
    t = timer("dispatch_batch_size")
    assert t.count - launches0 <= 2
    assert t.max_ms >= clients - 2
    assert eng._dispatcher._metrics.meter("dispatch_held") - held0 >= 1
    assert eng._dispatcher._inflight == 0
