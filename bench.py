"""Benchmark: SSB Q1.1-shaped scan-aggregation on the TPU query engine.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...breakdown}.

Config #2 from BASELINE.md: flat-lineorder range-filter + SUM, no index.
  SELECT SUM(lo_extendedprice * lo_discount) FROM ssb
  WHERE lo_orderdate BETWEEN 19940101 AND 19940131
    AND lo_discount BETWEEN 4 AND 6 AND lo_quantity BETWEEN 26 AND 35

value = device rows-scanned/sec (one chip) with PIPELINE_DEPTH queries in
flight — the serving-path number (ref Pinot is built for 100k+ QPS; the
engine dispatches outside its staging lock so concurrent round trips
overlap on the async device queue). The breakdown records sequential p50
latency, the measured host<->device round trip (a trivial x+1 sync — the
floor every sequential query pays), per-phase host times, and effective
HBM GB/s against the device's published peak (DEVICE_PEAKS). The run
names its device and refuses to report from anything but a TPU it has a
peak for (require_chip): a CPU backend's numbers are not per-chip numbers.

vs_baseline = speedup over the numpy reference executor at max_threads=8
(honest multi-core host baseline; the 1-thread number is also recorded).

Segments are built once into ./bench_data (git-ignored) and reloaded on
later runs; columns stay HBM-resident across queries (the segment cache of
SURVEY.md §7.5), so steady-state timing reflects the scan path, not I/O.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from pinot_tpu.ops.plan_ir import batch_params  # noqa: E402

NUM_SEGMENTS = 16
DOCS_PER_SEGMENT = 8_000_000
PIPELINE_DEPTH = 16
DATA_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench_data")
QUERY = ("SELECT SUM(lo_extendedprice * lo_discount), COUNT(*) FROM ssb "
         "WHERE lo_orderdate BETWEEN 19940101 AND 19940131 "
         "AND lo_discount BETWEEN 4 AND 6 AND lo_quantity BETWEEN 26 AND 35")
#: bytes the kernel reads per row with cardinality-aware id staging:
#: i8 discount ids + i16 orderdate ids + i8 quantity ids + 2 f32 values
#: (the engine reports the ACTUAL staged bytes at runtime; this is the
#: fallback for the derived GB/s when introspection fails)
BYTES_PER_ROW = 1 + 2 + 1 + 4 + 4

#: published per-chip peaks, keyed by the device_kind JAX reports (Google
#: Cloud documentation, "TPU v5e": 819 GB/s of HBM bandwidth, 197 TFLOP/s
#: in bf16). A device that is not here is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
}


def require_chip() -> dict:
    """The device this process measures on, as JAX reports it — or exit:
    a per-chip rate or a roofline share printed from XLA:CPU, or against
    a peak the table does not hold, is a number about nothing. Called
    before any data is built, so a chipless run fails in seconds."""
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" or device["kind"] not in DEVICE_PEAKS:
        raise SystemExit(
            f"bench: no per-chip numbers from {device}: needs a TPU whose "
            f"device_kind is in DEVICE_PEAKS ({sorted(DEVICE_PEAKS)})")
    return device


def measure_device_kernel(ex, segments, iters: int = 20):
    """Direct steady-state kernel timing (device only — no link, no host
    assembly): the number VERDICT r4 asked for (device_time_ms) plus the
    actual staged bytes so GB/s is measured, not modeled."""
    import jax

    from pinot_tpu.ops import kernels as _k
    from pinot_tpu.query.context import QueryContext
    eng = ex.tpu_engine
    ctx = QueryContext.from_sql(QUERY)
    with eng._engine_lock:
        plan_info = eng._plan(segments, ctx)
        if plan_info is None:
            return None, None
        plan, _slots = plan_info
        cols, params, _S, _S_real, D, G = eng._stage(segments, ctx, plan)
        kern = _k.compiled_kernel(plan)
    # num_docs rides the packed parameters (plan_ir.PACK)
    jax.block_until_ready(kern(cols, params, None, D=D, G=G))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = kern(cols, params, None, D=D, G=G)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    nbytes = sum(v.nbytes for v in cols.values())
    return dt, nbytes


def build_data():
    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  TableConfig, TableType)
    from pinot_tpu.segment.creator import SegmentCreator

    schema = Schema("ssb", [
        FieldSpec("lo_orderdate", DataType.INT, FieldType.DIMENSION),
        FieldSpec("lo_discount", DataType.INT, FieldType.DIMENSION),
        FieldSpec("lo_quantity", DataType.INT, FieldType.DIMENSION),
        FieldSpec("lo_extendedprice", DataType.INT, FieldType.METRIC),
    ])
    tc = TableConfig("ssb", TableType.OFFLINE)
    # high-cardinality measure stays raw (no dictionary); random ints are
    # incompressible, so skip chunk compression for build/load speed
    tc.indexing.no_dictionary_columns = ["lo_extendedprice"]
    tc.indexing.compression = "PASS_THROUGH"
    creator = SegmentCreator(tc, schema)
    dates = np.array([y * 10000 + m * 100 + d
                      for y in range(1992, 1999)
                      for m in range(1, 13) for d in range(1, 29)],
                     dtype=np.int32)
    for i in range(NUM_SEGMENTS):
        out = os.path.join(DATA_DIR, f"seg_{i}")
        if os.path.exists(os.path.join(out, "metadata.json")):
            continue
        rng = np.random.default_rng(1000 + i)
        n = DOCS_PER_SEGMENT
        cols = {
            "lo_orderdate": dates[rng.integers(0, len(dates), n)],
            "lo_discount": rng.integers(0, 11, n).astype(np.int32),
            "lo_quantity": rng.integers(1, 51, n).astype(np.int32),
            "lo_extendedprice": rng.integers(90_000, 10_000_000, n).astype(np.int32),
        }
        creator.build(cols, out, f"ssb_{i}")


def load():
    from pinot_tpu.segment.loader import load_segment
    return [load_segment(os.path.join(DATA_DIR, f"seg_{i}"))
            for i in range(NUM_SEGMENTS)]


def measure_link_rt_ms(n: int = 5) -> float:
    """Round trip of a trivial device sync — the latency floor every
    sequential query pays on this host<->device link."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros((8,), jnp.float32)
    np.asarray(f(x))
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        np.asarray(f(x))
        ts.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(ts)


def phase_breakdown(engine, segments, n: int = 20) -> dict:
    """Host-side per-phase times (ms) for the steady-state query."""
    from pinot_tpu.query.context import QueryContext

    def t(fn, n=n):
        fn()
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn()
        return (time.perf_counter() - t0) / n * 1e3, out

    parse_ms, ctx = t(lambda: QueryContext.from_sql(QUERY))
    plan_ms, plan_info = t(lambda: engine._plan(segments, ctx))
    plan = plan_info[0]
    stage_ms, _ = t(lambda: engine._stage(segments, ctx, plan))
    return {"parse_ms": round(parse_ms, 3), "plan_ms": round(plan_ms, 3),
            "stage_steady_ms": round(stage_ms, 3)}


def time_sequential(ex, n_iters: int, warmup: int = 2):
    for _ in range(warmup):
        resp = ex.execute(QUERY)
    lat = []
    for _ in range(n_iters):
        t0 = time.perf_counter()
        resp = ex.execute(QUERY)
        lat.append(time.perf_counter() - t0)
    return lat, resp


def time_pipelined(ex, depth: int, n_iters: int):
    with ThreadPoolExecutor(depth) as pool:
        list(pool.map(lambda _: ex.execute(QUERY), range(depth)))  # warm
        t0 = time.perf_counter()
        list(pool.map(lambda _: ex.execute(QUERY), range(n_iters)))
        dt = (time.perf_counter() - t0) / n_iters
    return dt


def deadline_overhead_main():
    """--deadline-overhead: cost of the reliability layer's cooperative
    deadline checks on the UNCACHED scatter path (ISSUE 3 satellite).

    Measures p50 over the host executor with and without a registered
    cancel-checker (the exact closure the server threads into the
    per-segment loop), on many small segments so the per-segment check
    count (not one big scan) dominates the comparison, plus the full
    broker scatter p50 through a real MiniCluster for context. Asserts
    the checks add <2% p50 and writes BENCH_reliability.json."""
    import statistics as stats
    import tempfile

    import numpy as np

    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  TableConfig, TableType)
    from pinot_tpu.query.executor import QueryExecutor
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment
    from pinot_tpu.utils.accounting import ResourceAccountant

    num_segments, docs = 64, 20_000
    query = ("SELECT SUM(v), COUNT(*) FROM t "
             "WHERE k BETWEEN 100 AND 800 OPTION(skipCache=true)")
    schema = Schema("t", [
        FieldSpec("k", DataType.INT, FieldType.DIMENSION),
        FieldSpec("v", DataType.INT, FieldType.METRIC),
    ])
    creator = SegmentCreator(TableConfig("t", TableType.OFFLINE), schema)
    tmp = tempfile.mkdtemp(prefix="bench_reliability_")
    segments = []
    for i in range(num_segments):
        rng = np.random.default_rng(i)
        d = os.path.join(tmp, f"seg_{i}")
        creator.build({"k": rng.integers(0, 1000, docs).astype(np.int32),
                       "v": rng.integers(0, 100, docs).astype(np.int32)},
                      d, f"t_{i}")
        segments.append(load_segment(d))

    accountant = ResourceAccountant()
    accountant.begin_query("bench", timeout_s=3600.0)

    ex_base = QueryExecutor(segments, use_tpu=False)
    ex_checked = QueryExecutor(segments, use_tpu=False,
                               cancel_check=accountant.checker("bench"))

    def one(ex):
        t0 = time.perf_counter()
        ex.execute(query)
        return (time.perf_counter() - t0) * 1e3

    # strictly interleaved base/checked samples: ambient drift (thermal,
    # noisy neighbors) hits both configs equally instead of masquerading
    # as check overhead across two separated runs
    for _ in range(3):
        one(ex_base), one(ex_checked)
    base_lat, checked_lat = [], []
    for _ in range(40):
        base_lat.append(one(ex_base))
        checked_lat.append(one(ex_checked))
    base = stats.median(base_lat)
    checked = stats.median(checked_lat)
    overhead_pct = (checked - base) / base * 100.0

    # full scatter path through a real broker/server round trip
    from pinot_tpu.cluster.mini import MiniCluster
    cluster = MiniCluster(num_servers=2)
    cluster.start()
    cluster.add_table("t")
    for i, seg in enumerate(segments):
        cluster.add_segment("t", seg, server_idx=i % 2)
    try:
        for _ in range(3):
            cluster.query(query)
        lat = []
        for _ in range(20):
            t0 = time.perf_counter()
            resp = cluster.query(query)
            lat.append((time.perf_counter() - t0) * 1e3)
        assert not resp.exceptions, resp.exceptions
        scatter_p50 = stats.median(lat)
    finally:
        cluster.stop()

    out = {
        "metric": "deadline_check_overhead_pct",
        "value": round(overhead_pct, 3),
        "unit": "%",
        "p50_base_ms": round(base, 3),
        "p50_checked_ms": round(checked, 3),
        "num_segments": num_segments,
        "docs_per_segment": docs,
        "scatter_p50_ms": round(scatter_p50, 2),
        "asserted_max_pct": 2.0,
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_reliability.json"), "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))
    # epsilon absorbs scheduler noise on sub-ms medians; the check is a
    # dict-get + time compare per segment, far below either bound
    assert overhead_pct < 2.0 or (checked - base) < 0.5, \
        f"deadline checks cost {overhead_pct:.2f}% p50 (>{2.0}%)"


def concurrency_main(smoke: bool = False):
    """--concurrency [--smoke]: A/B the dispatch pipeline (ISSUE 4).

    Closed-loop N-client driver over fingerprint-equal queries with
    per-client literals (the dashboard-fleet case), run twice IN THE
    SAME PROCESS: dispatch.mode=serialized (the pre-PR inline dispatch:
    collective-bearing kernels hold the process-global lock across
    dispatch + fetch) vs pipelined (dispatch ring + shared-plan
    micro-batching + staging/compute overlap). Records aggregate QPS,
    single-client p50, batch-size stats, and the steady-state retrace
    count; asserts the acceptance bars (full mode) and writes
    BENCH_dispatch.json. --smoke shrinks data + durations to fit the
    tier-1 timeout.

    On CPU hosts the bench forces the 8-virtual-device mesh the server
    runs under in CI — that is exactly the configuration where the old
    path serializes every kernel process-wide, which is the bottleneck
    this pipeline removes."""
    import statistics as stats
    import tempfile
    import threading

    import jax

    jax.config.update("jax_num_cpu_devices", 8)

    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  TableConfig, TableType)
    from pinot_tpu.ops import dispatch as dispatch_mod
    from pinot_tpu.ops import kernels
    from pinot_tpu.ops.engine import TpuOperatorExecutor
    from pinot_tpu.query.context import QueryContext
    from pinot_tpu.query.executor import QueryExecutor
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment
    from pinot_tpu.utils.config import PinotConfiguration

    # the serving regime the pipeline targets: per-query
    # DEVICE COMPUTE is small next to per-launch overhead, so the win is
    # amortizing launches, not adding FLOPs. Small segments put the CPU
    # stand-in in the same regime; scale up on real accelerators.
    num_segments = 4
    docs = 2_000
    clients = 8
    duration_s = 1.2 if smoke else 6.0
    p50_iters = 12 if smoke else 40

    schema = Schema("ssb", [
        FieldSpec("lo_orderdate", DataType.INT, FieldType.DIMENSION),
        FieldSpec("lo_discount", DataType.INT, FieldType.DIMENSION),
        FieldSpec("lo_quantity", DataType.INT, FieldType.DIMENSION),
        FieldSpec("lo_extendedprice", DataType.INT, FieldType.METRIC),
    ])
    tc = TableConfig("ssb", TableType.OFFLINE)
    tc.indexing.no_dictionary_columns = ["lo_extendedprice"]
    tc.indexing.compression = "PASS_THROUGH"
    creator = SegmentCreator(tc, schema)
    tmp = tempfile.mkdtemp(prefix="bench_dispatch_")
    dates = np.array([y * 10000 + m * 100 + d
                      for y in range(1992, 1999)
                      for m in range(1, 13) for d in range(1, 29)],
                     dtype=np.int32)
    segments = []
    for i in range(num_segments):
        rng = np.random.default_rng(3000 + i)
        out = os.path.join(tmp, f"seg_{i}")
        creator.build({
            "lo_orderdate": dates[rng.integers(0, len(dates), docs)],
            "lo_discount": rng.integers(0, 11, docs).astype(np.int32),
            "lo_quantity": rng.integers(1, 51, docs).astype(np.int32),
            "lo_extendedprice": rng.integers(
                90_000, 10_000_000, docs).astype(np.int32),
        }, out, f"ssb_{i}")
        segments.append(load_segment(out))
    total_rows = sum(s.num_docs for s in segments)

    # the dashboard fleet: one plan fingerprint, per-client literals
    queries = [
        ("SELECT SUM(lo_extendedprice * lo_discount), COUNT(*) FROM ssb "
         "WHERE lo_orderdate BETWEEN 19940101 AND 19940131 "
         f"AND lo_discount BETWEEN {a} AND {a + 2} "
         "AND lo_quantity BETWEEN 26 AND 35")
        for a in range(clients)]

    def warm_batch_buckets(engine):
        """Deterministically trace every batched (plan, bucket) shape the
        measured window can produce, so steady-state retraces are a real
        regression signal, not warmup noise."""
        prep = engine._prepare_agg(
            segments, QueryContext.from_sql(queries[0]))
        assert prep is not None, "bench query must stage on-device"
        launch = prep[3]
        guard = dispatch_mod._CPU_COLLECTIVE_LOCK if launch.collective \
            else None
        b = 2
        while b <= max(2, dispatch_mod._pow2(clients)):
            kern = dispatch_mod.compiled_batched_kernel(launch.plan, b)
            plist = batch_params([launch.params] * b)
            if guard is not None:
                with guard:
                    jax.block_until_ready(kern(
                        launch.cols, plist, launch.num_docs,
                        D=launch.D, G=launch.G))
            else:
                jax.block_until_ready(kern(
                    launch.cols, plist, launch.num_docs,
                    D=launch.D, G=launch.G))
            b *= 2

    # clients drive the SERVER-SIDE execution path
    # (QueryExecutor.execute_context, what query_server.py calls per
    # request) with pre-parsed contexts: SQL parse + broker reduce are
    # per-request Python that the GIL serializes in this reproduction
    # regardless of dispatch — a JVM/C++ server does them on independent
    # cores, so including them would just measure the GIL, not the
    # pipeline under test
    def make_mode(mode):
        engine = TpuOperatorExecutor(config=PinotConfiguration(
            overrides={"pinot.server.dispatch.mode": mode}))
        ex = QueryExecutor(segments, use_tpu=True, engine=engine)
        ctxs = [QueryContext.from_sql(q) for q in queries]
        for c in ctxs:  # stage + compile the single-kernel path
            results, _stats = ex.execute_context(c)
            assert results
        return engine, ex, ctxs

    eng_ser, ex_ser, ctxs_ser = make_mode("serialized")
    eng_pipe, ex_pipe, ctxs_pipe = make_mode("pipelined")
    warm_batch_buckets(eng_pipe)

    # single-client p50: STRICTLY INTERLEAVED A/B samples, so ambient
    # drift (thermal, noisy neighbors, allocator state) hits both modes
    # equally instead of masquerading as pipeline overhead
    def one(ex, ctxs, i):
        t0 = time.perf_counter()
        ex.execute_context(ctxs[i % len(ctxs)])
        return (time.perf_counter() - t0) * 1e3

    for i in range(4):
        one(ex_ser, ctxs_ser, i), one(ex_pipe, ctxs_pipe, i)
    lat_ser, lat_pipe = [], []
    for i in range(p50_iters):
        # alternate which mode goes first within the pair: a fixed order
        # hands the second call a systematically warmer CPU
        if i % 2 == 0:
            lat_ser.append(one(ex_ser, ctxs_ser, i))
            lat_pipe.append(one(ex_pipe, ctxs_pipe, i))
        else:
            lat_pipe.append(one(ex_pipe, ctxs_pipe, i))
            lat_ser.append(one(ex_ser, ctxs_ser, i))

    def closed_window(ex, ctxs, window_s):
        counts = [0] * clients
        stop_at = time.perf_counter() + window_s

        def client(ci):
            j = 0
            while time.perf_counter() < stop_at:
                ex.execute_context(ctxs[(ci + j) % len(ctxs)])
                counts[ci] += 1
                j += 1

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return sum(counts), time.perf_counter() - t0

    # ALTERNATING closed-loop windows (ser/pipe/ser/pipe...): one long
    # window per mode would compare two different moments of a shared
    # box; interleaved short windows hand ambient drift to both modes
    reg = eng_pipe._dispatcher._metrics
    batch_t0 = reg.timer("dispatch_batch_size")
    batch_c0, batch_max0 = batch_t0.count, batch_t0.max_ms
    traces0 = kernels.trace_count()
    rounds = 2 if smoke else 6
    ser_n = ser_wall = pipe_n = pipe_wall = 0.0
    for _r in range(rounds):
        n, w = closed_window(ex_ser, ctxs_ser, duration_s / rounds)
        ser_n += n
        ser_wall += w
        n, w = closed_window(ex_pipe, ctxs_pipe, duration_s / rounds)
        pipe_n += n
        pipe_wall += w
    batch_t = reg.timer("dispatch_batch_size")
    serialized = {"qps": ser_n / ser_wall, "queries_completed": int(ser_n)}
    pipelined = {
        "qps": pipe_n / pipe_wall,
        "queries_completed": int(pipe_n),
        "retraces_steady": kernels.trace_count() - traces0,
        "batch_launches": batch_t.count - batch_c0,
        "batch_size_max": max(batch_t.max_ms, batch_max0),
    }
    serialized["p50_single_ms"] = round(stats.median(lat_ser), 2)
    pipelined["p50_single_ms"] = round(stats.median(lat_pipe), 2)
    # PAIRED median delta: sample i of each mode ran back-to-back, so
    # the per-pair difference cancels ambient drift (cpu frequency,
    # noisy neighbors) that makes the two independent medians swing
    # ±10% on a small shared box
    paired_delta_ms = stats.median(
        p - s for s, p in zip(lat_ser, lat_pipe))
    speedup = pipelined["qps"] / max(serialized["qps"], 1e-9)
    p50_delta_pct = paired_delta_ms / serialized["p50_single_ms"] * 100.0
    out = {
        "metric": "concurrent_dispatch_qps_speedup",
        "value": round(speedup, 2),
        "unit": "x",
        "clients": clients,
        "duration_s": duration_s,
        "num_segments": num_segments,
        "docs_per_segment": docs,
        "total_rows": total_rows,
        "smoke": smoke,
        "serialized": {k: (round(v, 2) if isinstance(v, float) else v)
                       for k, v in serialized.items()},
        "pipelined": {k: (round(v, 2) if isinstance(v, float) else v)
                      for k, v in pipelined.items()},
        "p50_single_delta_pct": round(p50_delta_pct, 2),
        "p50_paired_delta_ms": round(paired_delta_ms, 3),
        "asserted": {"min_speedup": 2.0, "max_p50_regress_pct": 5.0,
                     "max_steady_retraces": 0},
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_dispatch.json"), "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))
    assert pipelined["retraces_steady"] == 0, \
        f"steady-state retraces: {pipelined['retraces_steady']}"
    if not smoke:
        assert speedup >= 2.0, f"pipelined speedup {speedup:.2f}x < 2x"
        # epsilon absorbs scheduler noise on few-ms medians (the lone-
        # query fast path makes the two single-client code paths nearly
        # identical; any real regression shows up far above this)
        assert p50_delta_pct < 5.0 or paired_delta_ms < 0.5, \
            f"single-client p50 regressed {p50_delta_pct:.1f}% " \
            f"({paired_delta_ms:.2f}ms paired)"


def _mse_throughput_leg(smoke: bool = False) -> dict:
    """Factory-batched vs serialized leaf dispatch for fingerprint-equal
    MSE traffic (ISSUE 10 acceptance leg). Two measurements:

    1. **Leaf-dispatch closed loop** (`leaf_qps_*` — the acceptance
       number): 8 clients drive the EXACT MSE leaf-stage execution path
       (the `leaf_query_fn` bridge: QueryExecutor over the instance's
       segments with the leaf_agg pushdown context, device engine
       included) with per-query literals; under the pipelined dispatcher
       the concurrent fingerprint-equal leaf stages COALESCE into one
       `jit(vmap)` launch, the serialized arm pays one XLA launch (+
       collective-lock hold on GSPMD hosts) per stage per query. This is
       the layer the tentpole refactors, so its ratio carries the
       structural floor: >= 1.5x on the CPU stand-in, >= 2x on real
       accelerators (each serialized launch additionally pays its own
       host<->device sync there).
    2. **End-to-end MSE join closed loop** (`e2e_*`, context): the same
       leaf shape wrapped in a full broker->stages->mailbox join through
       two MiniClusters with ORDER-ALTERNATING windows + paired
       sequential single-query p50. On the few-core GIL-bound CPU
       stand-in the end-to-end loop is HOST-bound (SQL parse, planning,
       stage submit, mailbox serde dominate at ~9 core-ms/query), so the
       e2e ratio is asserted only on real accelerators; the CPU stand-in
       asserts no e2e regression, paired p50 within noise, and ZERO
       steady-state retraces on the measured windows.

    Both loops warm to a STEADY state first (closed windows repeat until
    throughput stops moving): a cold process's first windows run several
    times slower — thread pools, jit caches, OS scheduling — and would
    poison whichever arm they landed on."""
    import gc
    import shutil
    import statistics as stats
    import tempfile
    import threading

    import jax
    import numpy as np

    from pinot_tpu.cluster.mini import MiniCluster
    from pinot_tpu.models.schema import Schema
    from pinot_tpu.models.table_config import TableConfig
    from pinot_tpu.ops import kernels as _kernels
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment
    from pinot_tpu.utils.config import PinotConfiguration

    # CPU hosts force the 8-virtual-device mesh CI runs under (same as
    # --batching): every staged kernel is then GSPMD-partitioned, so
    # SERIALIZED leaf dispatch holds the process-global collective lock
    # across launch + sync for every stage of every query — the exact
    # per-launch fixed cost the factory amortizes to once per batch
    try:
        jax.config.update("jax_num_cpu_devices", 8)
    except RuntimeError:
        pass  # backend already initialized (pytest: conftest forced 8)

    num_segments = 4 if smoke else 8
    docs = 2_000
    clients = 8
    window_s = 0.5 if smoke else 2.0
    warm_windows = 1 if smoke else 3
    rounds = 2 if smoke else 4

    fact_schema = Schema.from_dict({
        "schemaName": "bf",
        "dimensionFieldSpecs": [{"name": "k", "dataType": "LONG"}],
        "metricFieldSpecs": [{"name": "v", "dataType": "LONG"}]})
    dim_schema = Schema.from_dict({
        "schemaName": "bd",
        "dimensionFieldSpecs": [{"name": "k", "dataType": "LONG"},
                                {"name": "name", "dataType": "STRING"}]})
    fc = SegmentCreator(TableConfig.from_dict(
        {"tableName": "bf", "tableType": "OFFLINE"}), fact_schema)
    dc = SegmentCreator(TableConfig.from_dict(
        {"tableName": "bd", "tableType": "OFFLINE"}), dim_schema)
    tmp = tempfile.mkdtemp(prefix="bench_mse_tp_")
    seg_dirs = []
    for i in range(num_segments):
        rng = np.random.default_rng(100 + i)
        d = os.path.join(tmp, f"bf_{i}")
        fc.build({"k": rng.integers(0, 8, docs).astype(np.int64),
                  "v": rng.integers(0, 1000, docs).astype(np.int64)},
                 d, f"bf_{i}")
        seg_dirs.append(d)
    dim_dir = os.path.join(tmp, "bd_0")
    dc.build({"k": np.arange(8, dtype=np.int64),
              "name": [f"g{i}" for i in range(8)]}, dim_dir, "bd_0")

    def make_cluster(mode):
        overrides = {"pinot.server.dispatch.mode": mode}
        if mode == "pipelined":
            # the adaptive window (this PR's satellite) sizes the
            # coalesce wait from observed arrivals — the serving shape
            overrides["pinot.server.dispatch.batch.window.ms"] = "auto"
        c = MiniCluster(num_servers=1, use_tpu=True,
                        config=PinotConfiguration(overrides=overrides))
        c.start()
        c.add_table("bf")
        c.add_table("bd")
        for d in seg_dirs:
            c.add_segment("bf", load_segment(d), server_idx=0)
        c.add_segment("bd", load_segment(dim_dir), server_idx=0)
        return c

    # fingerprint-equal MSE joins: the aggregate subquery's literal
    # varies per query (no cache tier can absorb the leaf) while the
    # plan shape is constant, so concurrent leaf stages coalesce on the
    # factory key. The leaf is the scan-heavy global aggregate (the
    # shape whose per-launch fixed cost dominates — exactly what the
    # factory amortizes); the residual join + sort stay tiny.
    def sql_for(j):
        a = (j * 13) % 400
        return ("SELECT d.name, t.s FROM "
                f"(SELECT SUM(f.v) AS s, COUNT(*) AS c FROM bf f "
                f"WHERE f.v BETWEEN {a} AND {a + 500}) t "
                "JOIN bd d ON d.k < t.c ORDER BY d.name LIMIT 20")

    def closed_window(cluster, seq0):
        counts = [0] * clients
        errors = []
        stop_at = time.perf_counter() + window_s

        def client(ci):
            j = seq0 + ci * 1009
            while time.perf_counter() < stop_at:
                resp = cluster.query(sql_for(j))
                if resp.exceptions:
                    errors.append(resp.exceptions)
                    return
                counts[ci] += 1
                j += 1

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # surfaced AFTER join: an assert inside a worker thread dies
        # silently, and a failing arm would otherwise just under-count
        # and corrupt the measured ratio
        assert not errors, errors[0]
        return sum(counts) / (time.perf_counter() - t0)

    def single_p50(cluster, seq0, iters):
        lat = []
        for j in range(iters):
            t0 = time.perf_counter()
            resp = cluster.query(sql_for(seq0 + j))
            assert not resp.exceptions, resp.exceptions
            lat.append((time.perf_counter() - t0) * 1e3)
        return stats.median(lat)

    serial = make_cluster("serialized")
    pipe = make_cluster("pipelined")

    # -- sub-leg 1: the leaf-dispatch layer ----------------------------
    # the exact context _leaf_agg_pushdown builds for this subquery, run
    # through the exact bridge MSE workers use (QueryExecutor + shared
    # engine) — the MSE leaf path minus broker/mailbox, i.e. the layer
    # the factory refactors
    from pinot_tpu.query.context import QueryContext
    from pinot_tpu.query.executor import QueryExecutor
    from pinot_tpu.query.expressions import Function, Identifier, Literal

    leaf_segs = [load_segment(d) for d in seg_dirs]

    def leaf_ctx(j):
        a = (j * 13) % 400
        v = Identifier("v")
        sel = [Function("sum", (v,)),
               Function("count", (Identifier("*"),))]
        q = QueryContext(
            table="bf", select=sel, aliases=[None] * 2, distinct=False,
            filter=Function("between", (v, Literal(a), Literal(a + 500))),
            group_by=[], having=None, order_by=[], limit=1 << 31,
            offset=0, options={"numGroupsLimit": str(1 << 31)})
        q._extract_aggregations()
        return q

    def leaf_loop(engine, seq0):
        counts = [0] * clients
        errors = []
        stop_at = time.perf_counter() + window_s

        def client(ci):
            j = seq0 + ci * 1009
            try:
                while time.perf_counter() < stop_at:
                    ex = QueryExecutor(leaf_segs, use_tpu=True,
                                       engine=engine)
                    results, _stats = ex.execute_context(leaf_ctx(j))
                    assert results
                    counts[ci] += 1
                    j += 1
            except BaseException as e:  # noqa: BLE001 — surface at join
                errors.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[0]  # a dead arm must fail the run
        return sum(counts) / (time.perf_counter() - t0)

    def steady_warm(run_window, max_w=3 if smoke else 10):
        """Repeat untimed windows until throughput stops moving (<10%
        window-over-window) — the box takes several seconds of load to
        reach its steady state."""
        prev = run_window(0)
        for w in range(1, max_w):
            cur = run_window(w)
            if abs(cur - prev) <= 0.10 * prev:
                return
            prev = cur

    leaf_eng = {
        "serialized": serial.servers[0].executor._shared_engine(),
        "pipelined": pipe.servers[0].executor._shared_engine(),
    }
    gc.disable()
    try:
        for eng in leaf_eng.values():  # compile + stage once
            QueryExecutor(leaf_segs, use_tpu=True,
                          engine=eng).execute_context(leaf_ctx(0))
        steady_warm(lambda w: leaf_loop(leaf_eng["serialized"],
                                        3000 + w * 61))
        steady_warm(lambda w: leaf_loop(leaf_eng["pipelined"],
                                        3000 + w * 61))
        leaf_ratios, leaf_s_all, leaf_p_all = [], [], []
        leaf_retrace0 = _kernels.trace_count()
        for r in range(rounds):
            order = ["serialized", "pipelined"] if r % 2 == 0 \
                else ["pipelined", "serialized"]
            qps = {}
            for m in order:
                qps[m] = leaf_loop(leaf_eng[m], 4000 + r * 37)
            leaf_ratios.append(qps["pipelined"] / qps["serialized"])
            leaf_s_all.append(qps["serialized"])
            leaf_p_all.append(qps["pipelined"])
        leaf_retraces = _kernels.trace_count() - leaf_retrace0

        # -- sub-leg 2: end-to-end MSE join through the clusters -------
        for c in (serial, pipe):
            for j in range(3):
                resp = c.query(sql_for(j))
                assert not resp.exceptions, resp.exceptions
        steady_warm(lambda w: closed_window(serial, 5000 + w * 61))
        steady_warm(lambda w: closed_window(pipe, 5000 + w * 61))

        ratios, qps_s_all, qps_p_all, p50_deltas = [], [], [], []
        retrace0 = _kernels.trace_count()
        for r in range(rounds):
            if r % 2 == 0:
                qps_s = closed_window(serial, 10_000 + r * 37)
                qps_p = closed_window(pipe, 10_000 + r * 37)
            else:
                qps_p = closed_window(pipe, 10_000 + r * 37)
                qps_s = closed_window(serial, 10_000 + r * 37)
            ratios.append(qps_p / qps_s)
            qps_s_all.append(qps_s)
            qps_p_all.append(qps_p)
            iters = 4 if smoke else 10
            p50_s = single_p50(serial, 20_000 + r * 53, iters)
            p50_p = single_p50(pipe, 20_000 + r * 53, iters)
            p50_deltas.append(p50_p - p50_s)
        retraces = _kernels.trace_count() - retrace0
    finally:
        gc.enable()
        serial.stop()
        pipe.stop()
        shutil.rmtree(tmp, ignore_errors=True)

    platform = jax.devices()[0].platform
    leaf_speedup = stats.median(leaf_ratios)
    e2e_speedup = stats.median(ratios)
    min_leaf = 2.0 if platform != "cpu" else 1.5
    leg = {
        "clients": clients,
        "window_s": window_s,
        "rounds": rounds,
        "num_segments": num_segments,
        "docs_per_segment": docs,
        "platform": platform,
        "leaf_qps_serialized": round(stats.median(leaf_s_all), 1),
        "leaf_qps_factory_batched": round(stats.median(leaf_p_all), 1),
        "leaf_speedup": round(leaf_speedup, 2),
        "leaf_round_ratios": [round(x, 2) for x in leaf_ratios],
        "leaf_retraces_steady": leaf_retraces,
        "e2e_qps_serialized": round(stats.median(qps_s_all), 1),
        "e2e_qps_factory_batched": round(stats.median(qps_p_all), 1),
        "e2e_speedup": round(e2e_speedup, 2),
        "e2e_round_ratios": [round(x, 2) for x in ratios],
        "e2e_p50_single_paired_delta_ms": round(
            stats.median(p50_deltas), 3),
        "e2e_retraces_steady": retraces,
        "asserted": {
            "min_leaf_qps_speedup": min_leaf,
            "min_e2e_qps_speedup": (2.0 if platform != "cpu"
                                    else "report-only (host-bound "
                                         "stand-in; no-regression "
                                         "asserted)"),
            "max_steady_retraces": 0,
            "qps_bar_note": ("leaf layer: 2.0 on accelerators, 1.5 "
                             "structural floor on the CPU stand-in; "
                             "e2e gated on accelerators only — the "
                             "GIL-bound stand-in is host-bound at ~9 "
                             "core-ms/query (see docstring)"),
            "full_mode_only": smoke},
    }
    if not smoke:
        assert leaf_speedup >= min_leaf, \
            f"factory-batched MSE leaf dispatch {leaf_speedup:.2f}x < " \
            f"{min_leaf}x over serialized"
        if platform != "cpu":
            assert e2e_speedup >= 2.0, \
                f"end-to-end MSE join speedup {e2e_speedup:.2f}x < 2x"
        else:
            assert e2e_speedup >= 0.9, \
                f"end-to-end MSE join REGRESSED {e2e_speedup:.2f}x"
        assert leaf_retraces == 0 and retraces == 0, \
            f"steady-state retraces on the MSE leaf path " \
            f"(leaf={leaf_retraces}, e2e={retraces})"
    return leg


def mse_main(smoke: bool = False, out_path: str = None):
    """--mse [--smoke]: MSE reliability + stage-cache A/B (ISSUE 7).

    Chaos-off join/window workload through a real MiniCluster (TCP
    mailboxes, real segments), measuring:

    1. **Deadline-plumbing overhead** — PAIRED adjacent on/off runs of
       an UNCACHED join (per-iteration literals defeat every cache
       tier), overhead = median of per-pair deltas. Pairing + in-pair
       order alternation + untimed gc.collect() between samples cancel
       the dominant noise (GC pauses and thread scheduling on few-core
       hosts; ~10 stage threads race 2 cores here). Asserts <2% p50
       with a small absolute epsilon.
    2. **Leaf-stage cache speedup** — an aggregate-subquery join over
       immutable segments: the leaf stage is a two-phase leaf_agg whose
       per-segment aggregation dominates the query while its per-group
       output block is tiny, so a warm hit on the (version set,
       stage-plan fingerprint) key removes nearly the whole leaf cost.
       Cold clears the stage caches each iteration. Asserts >=1.5x
       warm-over-cold in full mode.
    3. **Factory-batched leaf throughput** (ISSUE 10, `throughput` key)
       — 8-client closed loop of fingerprint-equal MSE joins, pipelined
       (leaf stages coalesce through the unified kernel factory) vs
       serialized leaf dispatch, order-alternating windows with
       median-of-paired-ratios + paired single-query p50 + a zero
       steady-state retrace guard; see _mse_throughput_leg.

    Writes BENCH_mse.json. --smoke shrinks data + iterations and skips
    the ratio asserts (timings are noise at smoke scale)."""
    import gc
    import statistics as stats
    import tempfile

    import numpy as np

    from pinot_tpu.cluster.mini import MiniCluster
    from pinot_tpu.models.schema import Schema
    from pinot_tpu.models.table_config import TableConfig
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment

    num_segments = 8 if smoke else 24
    docs = 4_000 if smoke else 32_000
    iters = 10 if smoke else 24

    fact_schema = Schema.from_dict({
        "schemaName": "bf",
        "dimensionFieldSpecs": [{"name": "k", "dataType": "LONG"},
                                {"name": "tag", "dataType": "STRING"}],
        "metricFieldSpecs": [{"name": "v", "dataType": "LONG"}]})
    dim_schema = Schema.from_dict({
        "schemaName": "bd",
        "dimensionFieldSpecs": [{"name": "k", "dataType": "LONG"},
                                {"name": "name", "dataType": "STRING"}]})
    fc = SegmentCreator(TableConfig.from_dict(
        {"tableName": "bf", "tableType": "OFFLINE"}), fact_schema)
    dc = SegmentCreator(TableConfig.from_dict(
        {"tableName": "bd", "tableType": "OFFLINE"}), dim_schema)

    tmp = tempfile.mkdtemp(prefix="bench_mse_")
    # one server: the stage pipeline is identical (real mailboxes, all
    # five stages), but the whole fact scan lands on one worker — the
    # cache A/B measures scan-vs-cache, not thread scheduling on a
    # few-core host, and the paired overhead estimator runs quieter
    cluster = MiniCluster(num_servers=1)
    cluster.start()
    cluster.add_table("bf")
    cluster.add_table("bd")
    for i in range(num_segments):
        rng = np.random.default_rng(i)
        d = os.path.join(tmp, f"bf_{i}")
        fc.build({"k": rng.integers(0, 64, docs).astype(np.int64),
                  "tag": [f"t{v}" for v in rng.integers(0, 9, docs)],
                  "v": rng.integers(0, 1000, docs).astype(np.int64)},
                 d, f"bf_{i}")
        cluster.add_segment("bf", load_segment(d), server_idx=0)
    d = os.path.join(tmp, "bd_0")
    dc.build({"k": np.arange(64, dtype=np.int64),
              "name": [f"g{i % 8}" for i in range(64)]}, d, "bd_0")
    cluster.add_segment("bd", load_segment(d), server_idx=0)

    # leaf-scan-heavy join: the string filter makes the fact scan (tag
    # materialization + predicate over every row) the dominant cost
    # while the selective output keeps shuffle/join/agg small — the
    # shape the leaf-stage cache is built for
    join_q = ("SELECT d.name, SUM(f.v) AS s FROM bf f "
              "JOIN bd d ON f.k = d.k "
              "WHERE f.tag = 't3' AND f.v BETWEEN {lo} AND {hi} "
              "GROUP BY d.name ORDER BY d.name LIMIT 100")
    # the cache A/B workload: aggregate-subquery join — the leaf stage
    # is a two-phase leaf_agg (the heavy per-segment aggregation runs ON
    # the scanning worker), its output is 64 per-group intermediates, so
    # the stage cache removes nearly the whole leaf cost on a warm hit
    cache_q = ("SELECT d.name, t.s FROM "
               "(SELECT f.k AS k, SUM(f.v) AS s FROM bf f "
               "WHERE f.tag = 't3' GROUP BY f.k) t "
               "JOIN bd d ON t.k = d.k ORDER BY d.name, t.s LIMIT 200")
    window_q = ("SELECT f.k, f.v, RANK() OVER (PARTITION BY f.k "
                "ORDER BY f.v DESC) AS r FROM bf f "
                "WHERE f.tag = 't1' AND f.v < {lo} "
                "ORDER BY f.k, r LIMIT 50")
    caches = [s.mse_worker.stage_cache for s in cluster.servers]

    def run(sql):
        # GC outside the timed window: object-column serde allocates
        # heavily and a gen-2 pause mid-query (~25ms here) would alias
        # into whichever arm it lands on
        gc.collect()
        t0 = time.perf_counter()
        resp = cluster.query(sql)
        assert not resp.exceptions, resp.exceptions
        return (time.perf_counter() - t0) * 1e3

    def uncached(i):
        return join_q.format(lo=i, hi=i + 30)

    gc.disable()
    try:
        # -- 1. deadline-plumbing overhead: paired on/off ---------------
        # per-iteration literal => fresh fingerprint => every tier
        # (stage cache included) misses: the honest uncached join p50.
        # Adjacent pairs with alternating in-pair order; the estimator
        # is the MEDIAN PER-PAIR DELTA, which cancels ambient drift a
        # pooled median cannot
        for i in range(2):
            run(uncached(900 + i))
        # A/A control: identical arms, same pairing discipline — the
        # measured noise floor the A/B verdict is judged against
        aa = []
        for i in range(max(6, iters // 2)):
            a = run(uncached(700 + 2 * i))
            b = run(uncached(701 + 2 * i))
            aa.append(a - b if i % 2 == 0 else b - a)
        aa_delta_ms = stats.median(aa)
        on_lat, off_lat, deltas = [], [], []
        for i in range(iters):
            first_on = i % 2 == 0
            pair = {}
            for arm in (first_on, not first_on):
                cluster.mse.enforce_deadlines = arm
                pair[arm] = run(uncached(2 * i + (0 if arm else 1)))
            on_lat.append(pair[True])
            off_lat.append(pair[False])
            deltas.append(pair[True] - pair[False])
        p50_off = stats.median(off_lat)
        p50_on = stats.median(on_lat)
        paired_delta_ms = stats.median(deltas)
        overhead_pct = paired_delta_ms / p50_off * 100.0

        # -- 2. leaf-stage cache: cold vs warm --------------------------
        cold_lat, warm_lat = [], []
        run(cache_q)  # warm code paths once
        for _ in range(iters):
            for c in caches:
                c.clear()
            cold_lat.append(run(cache_q))
            run(cache_q)  # populate-confirm pass
            warm_lat.append(run(cache_q))
        p50_cold = stats.median(cold_lat)
        p50_warm = stats.median(warm_lat)
        speedup = p50_cold / p50_warm if p50_warm else 0.0
        hits = sum(c.stats.hits for c in caches)
        assert hits >= iters, f"stage cache never hit ({hits})"

        # -- 3. window workload p50 (context, chaos off) ----------------
        for i in range(2):
            run(window_q.format(lo=200 + i))
        win_lat = [run(window_q.format(lo=300 + i)) for i in range(iters)]
    finally:
        gc.enable()
        cluster.stop()

    # -- 4. factory-batched vs serialized leaf dispatch (ISSUE 10) ------
    throughput = _mse_throughput_leg(smoke=smoke)

    out = {
        "metric": "mse_deadline_overhead_pct",
        "value": round(overhead_pct, 3),
        "unit": "%",
        "p50_join_deadline_off_ms": round(p50_off, 3),
        "p50_join_deadline_on_ms": round(p50_on, 3),
        "paired_delta_ms": round(paired_delta_ms, 3),
        "aa_noise_floor_ms": round(aa_delta_ms, 3),
        "p50_join_cold_ms": round(p50_cold, 3),
        "p50_join_warm_ms": round(p50_warm, 3),
        "stage_cache_speedup": round(speedup, 2),
        "stage_cache_hits": hits,
        "p50_window_ms": round(stats.median(win_lat), 3),
        "num_segments": num_segments,
        "docs_per_segment": docs,
        "smoke": smoke,
        "throughput": throughput,
        "asserted": {"max_overhead_pct": 2.0, "min_cache_speedup": 1.5,
                     "full_mode_only": smoke},
    }
    if out_path is None:
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_mse.json")
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))
    if not smoke:
        # epsilon absorbs residual scheduler noise (2-core host, ~10
        # stage threads per query); the plumbing itself is time compares
        # at op boundaries, far below either bound
        assert overhead_pct < 2.0 or paired_delta_ms < 2.0, \
            f"deadline plumbing costs {overhead_pct:.2f}% join p50 (>2%)"
        assert speedup >= 1.5, \
            f"leaf-stage cache speedup {speedup:.2f}x < 1.5x warm/cold"


def _groups_build_cluster(tmp: str, num_segments: int, docs: int):
    """4 servers in 2 replica groups (group 0 = servers 0/1, group 1 =
    servers 2/3), every segment fully copied in both groups — the
    fault-domain acceptance topology."""
    import numpy as np

    from pinot_tpu.cluster.mini import MiniCluster
    from pinot_tpu.models.schema import Schema
    from pinot_tpu.models.table_config import TableConfig
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment

    schema = Schema.from_dict({
        "schemaName": "rg",
        "dimensionFieldSpecs": [{"name": "k", "dataType": "LONG"}],
        "metricFieldSpecs": [{"name": "v", "dataType": "LONG"}]})
    creator = SegmentCreator(TableConfig.from_dict(
        {"tableName": "rg", "tableType": "OFFLINE"}), schema)
    cluster = MiniCluster(num_servers=4)
    cluster.start()
    cluster.add_table("rg", num_replica_groups=2, tenant="bench")
    total = 0
    for i in range(num_segments):
        rng = np.random.default_rng(100 + i)
        d = os.path.join(tmp, f"rg_{i}")
        creator.build({"k": rng.integers(0, 64, docs).astype(np.int64),
                       "v": rng.integers(0, 1000, docs).astype(np.int64)},
                      d, f"rg_{i}")
        cluster.add_segment("rg", load_segment(d), server_idx=i % 2,
                            replicas=[2 + i % 2])
        total += docs
    return cluster, total


def _groups_chaos_journal(tmp: str, seed: int, n_queries: int):
    """One sequential chaos run against the `broker.group.scatter` site:
    a seeded coin kills scatters to group 0 (SIGKILL-equivalent: the
    request raises before the wire) until the failure detector demotes
    the group. Returns (per-query outcomes, per-site decision journal) —
    two same-seed runs must match EXACTLY."""
    from pinot_tpu.utils.failpoints import FaultSchedule

    sched = FaultSchedule([
        ("broker.group.scatter",
         {"error": ConnectionError("chaos: replica group 0 killed"),
          "probability": 0.5, "seed": seed, "where": {"group": 0}})])
    cluster, _total = None, None
    try:
        import shutil
        run_dir = os.path.join(tmp, f"journal_{seed}")
        os.makedirs(run_dir, exist_ok=True)
        cluster, _total = _groups_build_cluster(run_dir, num_segments=4,
                                                docs=500)
        # pin demotion: once the chaos kills one member, group 0 stays
        # out of routing for the whole run — replay must not depend on
        # when a wall-clock backoff happens to expire
        for b in cluster.brokers:
            b.failure_detector.base_backoff_s = 3600.0
            b.failure_detector.max_backoff_s = 3600.0
        sched.arm()
        outcomes = []
        for i in range(n_queries):
            resp = cluster.query(
                f"SELECT COUNT(*), SUM(v) FROM rg WHERE v >= {i % 7}")
            outcomes.append((len(resp.exceptions),
                             resp.rows[0][0] if resp.rows else None))
        decisions = sched.decisions()
        shutil.rmtree(run_dir, ignore_errors=True)
        return outcomes, decisions
    finally:
        sched.disarm()
        if cluster is not None:
            cluster.stop()


def groups_main(smoke: bool = False, out_path: str = None):
    """--groups [--smoke]: replica-group fault-domain acceptance (ISSUE
    8). 2 replica groups x 2 servers, 8-client closed loop:

    1. **all-alive phase** — baseline aggregate QPS.
    2. **group-kill phase** — every member of replica group 0 is killed
       (SIGKILL-equivalent transport death) while the loop runs; the
       loop keeps going. Asserts **zero failed queries** across the
       whole run (the mid-scatter failures fail over: the whole group
       demotes, unanswered segments re-scatter onto group 1) and
       reports the convergent one-group QPS + p99.
    3. **seeded chaos journal** — a sequential run with a seeded coin
       killing `broker.group.scatter` hits on group 0 is executed
       TWICE; outcomes + failpoint decision journals must be identical
       (the per-seed replay contract), digest recorded.

    Writes BENCH_groups.json. --smoke shrinks data + durations and
    skips the throughput-ratio assert (timings are noise at smoke
    scale); zero-failures and replay-identical are asserted always."""
    import hashlib
    import tempfile
    import threading

    num_segments = 4 if smoke else 12
    docs = 800 if smoke else 20_000
    duration_s = 1.2 if smoke else 5.0
    clients = 8

    tmp = tempfile.mkdtemp(prefix="bench_groups_")
    cluster, total_rows = _groups_build_cluster(tmp, num_segments, docs)

    lock = threading.Lock()

    def closed_loop(duration: float):
        """8-client closed loop; returns (latencies_s, failures)."""
        stop_at = time.perf_counter() + duration
        lat, failures = [], []

        def client(cid: int):
            i = cid
            while time.perf_counter() < stop_at:
                t0 = time.perf_counter()
                resp = cluster.query(
                    f"SELECT COUNT(*), SUM(v) FROM rg WHERE v >= {i % 7}")
                dt = time.perf_counter() - t0
                with lock:
                    lat.append(dt)
                    if resp.exceptions:
                        failures.append(resp.exceptions)
                i += clients
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return lat, failures

    def p(q, vals):
        if not vals:
            return 0.0
        return sorted(vals)[min(len(vals) - 1,
                                max(0, round(q * len(vals)) - 1))]

    # warm code paths (parse/plan/serde jit noise off the measurement)
    for i in range(4):
        resp = cluster.query(f"SELECT COUNT(*), SUM(v) FROM rg "
                             f"WHERE v >= {i}")
        assert not resp.exceptions, resp.exceptions

    lat_all, fail_all = closed_loop(duration_s)
    qps_all = len(lat_all) / duration_s

    # -- the kill: every member of group 0, while the loop runs --------
    killer = threading.Timer(duration_s * 0.25,
                             cluster.kill_replica_group, args=("rg", 0))
    killer.start()
    lat_kill, fail_kill = closed_loop(duration_s)
    killer.join()
    qps_kill = len(lat_kill) / duration_s

    # -- steady state on the surviving group ---------------------------
    lat_one, fail_one = closed_loop(duration_s)
    qps_one = len(lat_one) / duration_s
    cluster.stop()

    # -- seeded chaos journal: replay must be byte-identical -----------
    seed = 20260803
    run_a = _groups_chaos_journal(tmp, seed, n_queries=12 if smoke else 40)
    run_b = _groups_chaos_journal(tmp, seed, n_queries=12 if smoke else 40)
    replay_identical = run_a == run_b
    journal_digest = hashlib.sha1(repr(run_a).encode()).hexdigest()[:16]
    chaos_failed = sum(1 for exc_count, _rows in run_a[0] if exc_count)

    failed = len(fail_all) + len(fail_kill) + len(fail_one)
    out = {
        "metric": "group_kill_failed_queries",
        "value": failed,
        "unit": "queries",
        "qps_all_alive": round(qps_all, 1),
        "qps_during_kill": round(qps_kill, 1),
        "qps_one_group": round(qps_one, 1),
        "p50_all_alive_ms": round(p(0.50, lat_all) * 1e3, 2),
        "p99_all_alive_ms": round(p(0.99, lat_all) * 1e3, 2),
        "p99_during_kill_ms": round(p(0.99, lat_kill) * 1e3, 2),
        "p99_one_group_ms": round(p(0.99, lat_one) * 1e3, 2),
        "queries_total": len(lat_all) + len(lat_kill) + len(lat_one),
        "chaos_journal_digest": journal_digest,
        "chaos_replay_identical": replay_identical,
        "chaos_run_failed_queries": chaos_failed,
        "num_segments": num_segments,
        "docs_per_segment": docs,
        "total_rows": total_rows,
        "clients": clients,
        "smoke": smoke,
        "asserted": {"failed_queries": 0, "replay_identical": True,
                     "chaos_failed_queries": 0,
                     "min_one_group_qps_frac": None if smoke else 0.25},
    }
    if out_path is None:
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_groups.json")
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))
    assert failed == 0, \
        f"{failed} queries failed across the group-kill run: " \
        f"{(fail_all + fail_kill + fail_one)[:3]}"
    assert chaos_failed == 0, \
        f"{chaos_failed} chaos-journal queries failed: {run_a[0][:5]}"
    assert replay_identical, "same-seed chaos journal diverged"
    if not smoke:
        assert qps_one >= 0.25 * qps_all, \
            f"one-group throughput collapsed: {qps_one:.0f} vs " \
            f"{qps_all:.0f} all-alive QPS"


def batching_main(smoke: bool = False, out_path: str = None):
    """--batching [--smoke]: A/B the unified kernel factory (ISSUE 9).

    Two closed-loop legs, each run twice IN THE SAME PROCESS against
    `pinot.server.dispatch.mode=serialized` (the pre-ring inline
    dispatch baseline):

      mixed_table — three tables with the same plan shape but their own
        data, segment counts, and doc counts (padding into one shape
        bucket); 8 clients spread across them. The PR-4 coalescer could
        never batch these (keys included the concrete segment batch);
        the unified factory stacks their column blocks along a leading
        batch axis and launches once per bucket.
      doc_sharded — a (segments x docs) mesh engine, which PR 4
        excluded from batching entirely (`vmap` over `shard_map`
        unsupported). The factory vmaps INSIDE shard_map, so the whole
        batch pays one set of collectives — and on CPU hosts holds the
        process-global collective lock once per BATCH, not per query.

    Records, per leg: closed-loop aggregate QPS (median of per-round
    paired ratios), paired single-query p50, batch stats, steady-state
    retrace count, and the DEVICE-level amortization (single-launch vs
    batch-8 per-query launch+sync). Two bars, residency-bench style
    (backend-gated — see PR 6's warm-vs-cold precedent):

      * device_speedup_batch8 >= 2x on BOTH legs, always — the layer
        the kernel factory refactors. On real accelerators the
        per-launch fixed cost includes a host<->device sync, so this
        amortization IS the serving win.
      * closed-loop QPS >= 2x on real accelerators; >= 1.5x structural
        floor on the few-core CPU stand-in, where each query's
        GIL-serialized host work (result assembly, futures) is
        comparable to its device time and is NOT deleted by batching —
        that host share caps the end-to-end ratio regardless of how
        well launches amortize (observed 1.7-2.3x across host
        throttling states; a sub-floor run usually means the box
        changed state mid-window — rerun).

    Also asserts zero steady-state retraces and no single-query p50
    regression beyond noise, and that cross-table stacked batches
    actually carried the mixed leg. Writes BENCH_batching.json.
    --smoke shrinks data + durations to fit the tier-1 timeout.

    On CPU hosts the mixed leg forces the 8-virtual-device mesh CI runs
    under — every kernel is GSPMD-partitioned, so serialized mode holds
    the collective lock across dispatch + fetch per query, the exact
    regime the factory amortizes."""
    import contextlib
    import statistics as stats
    import tempfile
    import threading

    import jax

    try:
        jax.config.update("jax_num_cpu_devices", 8)
    except RuntimeError:
        pass  # backend already initialized (in-process smoke run)
    if len(jax.devices()) < 8:
        raise SystemExit("batching bench needs 8 (virtual) devices")

    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  TableConfig, TableType)
    from pinot_tpu.ops import dispatch as dispatch_mod
    from pinot_tpu.ops import kernels
    from pinot_tpu.ops.engine import TpuOperatorExecutor
    from pinot_tpu.parallel.mesh import make_mesh
    from pinot_tpu.query.context import QueryContext
    from pinot_tpu.query.executor import QueryExecutor
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment
    from pinot_tpu.utils.config import PinotConfiguration

    clients = 8
    duration_s = 1.2 if smoke else 12.0
    p50_iters = 12 if smoke else 40
    rounds = 2 if smoke else 6
    # three tables, one plan shape: same columns, own doc counts that
    # pad into ONE 2048-doc bucket, segment counts that pad into one
    # S bucket — the mixed dashboard fleet
    table_docs = {"ssb_a": (4, 1500), "ssb_b": (4, 1800), "ssb_c": (3, 2000)}

    tmp = tempfile.mkdtemp(prefix="bench_batching_")
    dates = np.array([y * 10000 + m * 100 + d
                      for y in range(1992, 1999)
                      for m in range(1, 13) for d in range(1, 29)],
                     dtype=np.int32)

    def build_table(name, num_segments, docs, seed):
        schema = Schema(name, [
            FieldSpec("lo_orderdate", DataType.INT, FieldType.DIMENSION),
            FieldSpec("lo_discount", DataType.INT, FieldType.DIMENSION),
            FieldSpec("lo_quantity", DataType.INT, FieldType.DIMENSION),
            FieldSpec("lo_extendedprice", DataType.INT, FieldType.METRIC),
        ])
        tc = TableConfig(name, TableType.OFFLINE)
        tc.indexing.no_dictionary_columns = ["lo_extendedprice"]
        tc.indexing.compression = "PASS_THROUGH"
        creator = SegmentCreator(tc, schema)
        segs = []
        for i in range(num_segments):
            rng = np.random.default_rng(seed + i)
            out = os.path.join(tmp, f"{name}_{i}")
            creator.build({
                "lo_orderdate": dates[rng.integers(0, len(dates), docs)],
                "lo_discount": rng.integers(0, 11, docs).astype(np.int32),
                "lo_quantity": rng.integers(1, 51, docs).astype(np.int32),
                "lo_extendedprice": rng.integers(
                    90_000, 10_000_000, docs).astype(np.int32),
            }, out, f"{name}_{i}")
            segs.append(load_segment(out))
        return segs

    tables = {name: build_table(name, n, docs, 7000 + 100 * i)
              for i, (name, (n, docs)) in enumerate(table_docs.items())}
    names = list(tables)

    def sql_for(table, a):
        return ("SELECT SUM(lo_extendedprice * lo_discount), COUNT(*) "
                f"FROM {table} "
                "WHERE lo_orderdate BETWEEN 19940101 AND 19940131 "
                f"AND lo_discount BETWEEN {a} AND {a + 2} "
                "AND lo_quantity BETWEEN 26 AND 35")

    def warm_buckets(launches):
        """Trace every batched (plan, bucket, variant) shape the
        measured window can produce — broadcast per bucket, stacked per
        bucket when >1 table — so steady-state retraces are a real
        regression signal, not warmup noise."""
        lead = launches[0]
        guard = dispatch_mod._CPU_COLLECTIVE_LOCK if lead.collective \
            else contextlib.nullcontext()
        b = 2
        n_uniq = len({ln.cols_key for ln in launches})
        while b <= max(2, dispatch_mod._pow2(clients)):
            variants = [False] + ([True] if len(launches) > 1 else [])
            for stacked in variants:
                kern = lead.factory(b, stacked)
                if stacked:
                    members = [launches[i % len(launches)]
                               for i in range(b)]
                    with guard:
                        jax.block_until_ready(kern(
                            tuple(m.cols for m in members),
                            batch_params([m.params for m in members]),
                            tuple(m.num_docs for m in members),
                            D=lead.D, G=lead.G))
                else:
                    with guard:
                        jax.block_until_ready(kern(
                            lead.cols, batch_params([lead.params] * b),
                            lead.num_docs,
                            D=lead.D, G=lead.G))
            # same-cols member-grouped (dedup) variants: a stacked batch
            # with duplicate tables dedups its stack, keyed (plan, B, U)
            # — warm every U bucket a b-member batch over these tables
            # can produce so the measured window compiles nothing
            if lead.dedup_factory is not None and len(launches) > 1:
                u = 1
                while u <= dispatch_mod._pow2(min(b, n_uniq)):
                    kern = lead.dedup_factory(b, u)
                    uniqs = [launches[i % len(launches)]
                             for i in range(u)]
                    idx = np.zeros(b, np.int32)
                    with guard:
                        jax.block_until_ready(kern(
                            tuple(m.cols for m in uniqs),
                            batch_params([lead.params] * b),
                            tuple(m.num_docs for m in uniqs),
                            idx, D=lead.D, G=lead.G))
                    u *= 2
            b *= 2

    def closed_window(jobs, window_s):
        """jobs: per-client (executor, ctxs) pairs."""
        counts = [0] * len(jobs)
        stop_at = time.perf_counter() + window_s

        def client(ci):
            ex, ctxs = jobs[ci]
            j = 0
            while time.perf_counter() < stop_at:
                ex.execute_context(ctxs[j % len(ctxs)])
                counts[ci] += 1
                j += 1

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(jobs))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return sum(counts), time.perf_counter() - t0

    def run_leg(make_engine, leg_tables, warm_stacked, leg):
        """One serialized-vs-unified A/B over alternating closed-loop
        windows; returns the leg report dict. `leg` labels the engines'
        dispatcher metrics so each leg reads ITS OWN batch stats — the
        registry is process-global and cumulative, so unlabelled reads
        would report the other leg's maxima."""
        labels = {"bench_leg": leg}

        def make_mode(mode):
            engine = make_engine(mode, labels)
            exs = {tn: QueryExecutor(segs, use_tpu=True, engine=engine)
                   for tn, segs in leg_tables.items()}
            jobs = []
            for ci in range(clients):
                tn = list(leg_tables)[ci % len(leg_tables)]
                ctxs = [QueryContext.from_sql(sql_for(tn, a))
                        for a in range(8)]
                jobs.append((exs[tn], ctxs))
            for ex, ctxs in jobs:   # stage + compile the single path
                for c in ctxs:
                    results, _stats = ex.execute_context(c)
                    assert results, "bench query must stage on-device"
            return engine, jobs

        eng_ser, jobs_ser = make_mode("serialized")
        eng_uni, jobs_uni = make_mode("pipelined")
        launches = []
        if warm_stacked:
            for tn, segs in leg_tables.items():
                prep = eng_uni._prepare_agg(
                    segs, QueryContext.from_sql(sql_for(tn, 0)))
                assert prep is not None
                launches.append(prep[3])
            assert len({ln.batch_key for ln in launches}) == 1, \
                "tables must share one shape bucket for this bench"
        else:
            prep = eng_uni._prepare_agg(
                next(iter(leg_tables.values())),
                QueryContext.from_sql(sql_for(next(iter(leg_tables)), 0)))
            assert prep is not None
            launches.append(prep[3])
        warm_buckets(launches)

        # DEVICE-level amortization: steady-state launch+sync time of one
        # single-query kernel vs one batch-8 launch (stacked when the leg
        # mixes tables), per query. This is the layer the kernel factory
        # refactors, and the number that transfers to real accelerators —
        # there the per-launch fixed cost includes a host<->device
        # sync, so amortizing launches IS the serving win. The
        # closed-loop QPS ratio below additionally carries per-query
        # HOST work (result assembly, futures — GIL-serialized on the
        # few-core CPU stand-in) that batching does not delete, which
        # caps it well under the device-level ratio on fast hosts.
        lead = launches[0]
        guard = dispatch_mod._CPU_COLLECTIVE_LOCK if lead.collective \
            else contextlib.nullcontext()
        B = 8

        def timed(fn, iters=20):
            with guard:
                jax.block_until_ready(fn())  # warm
                t0 = time.perf_counter()
                for _ in range(iters):
                    jax.block_until_ready(fn())
                return (time.perf_counter() - t0) / iters * 1e3

        single_ms = timed(lead.call)
        kern = lead.factory(B, warm_stacked)
        if warm_stacked:
            members = [launches[i % len(launches)] for i in range(B)]
            clist = tuple(m.cols for m in members)
            plist8 = batch_params([m.params for m in members])
            ndlist = tuple(m.num_docs for m in members)
            batch8_ms = timed(lambda: kern(clist, plist8, ndlist,
                                           D=lead.D, G=lead.G))
        else:
            plist8 = batch_params([lead.params] * B)
            batch8_ms = timed(lambda: kern(lead.cols, plist8,
                                           lead.num_docs,
                                           D=lead.D, G=lead.G))
        device_speedup = single_ms / (batch8_ms / B)

        # paired single-client p50: strictly interleaved A/B samples
        def one(jobs, i):
            ex, ctxs = jobs[i % len(jobs)]
            t0 = time.perf_counter()
            ex.execute_context(ctxs[i % len(ctxs)])
            return (time.perf_counter() - t0) * 1e3

        for i in range(4):
            one(jobs_ser, i), one(jobs_uni, i)
        lat_ser, lat_uni = [], []
        for i in range(p50_iters):
            if i % 2 == 0:
                lat_ser.append(one(jobs_ser, i))
                lat_uni.append(one(jobs_uni, i))
            else:
                lat_uni.append(one(jobs_uni, i))
                lat_ser.append(one(jobs_ser, i))

        reg = eng_uni._dispatcher._metrics
        batch_t0 = reg.timer("dispatch_batch_size", labels=labels)
        batch_c0, batch_max0 = batch_t0.count, batch_t0.max_ms
        xtab0 = reg.meter("dispatch_batch_cross_table", labels=labels)
        traces0 = kernels.trace_count()
        ser_n = ser_wall = uni_n = uni_wall = 0.0
        round_ratios = []
        for _r in range(rounds):
            # alternate which mode goes first within the round: a fixed
            # order hands the second window a systematically different
            # box (frequency scaling, neighbors) on a small shared host
            order = [(jobs_ser, "s"), (jobs_uni, "u")] if _r % 2 == 0 \
                else [(jobs_uni, "u"), (jobs_ser, "s")]
            qps = {}
            for jobs, tag in order:
                n, w = closed_window(jobs, duration_s / rounds)
                qps[tag] = n / w
                if tag == "s":
                    ser_n += n
                    ser_wall += w
                else:
                    uni_n += n
                    uni_wall += w
            round_ratios.append(qps["u"] / max(qps["s"], 1e-9))
        batch_t = reg.timer("dispatch_batch_size", labels=labels)
        paired_delta_ms = stats.median(
            p - s for s, p in zip(lat_ser, lat_uni))
        serialized = {
            "qps": round(ser_n / ser_wall, 2),
            "queries_completed": int(ser_n),
            "p50_single_ms": round(stats.median(lat_ser), 2),
        }
        unified = {
            "qps": round(uni_n / uni_wall, 2),
            "queries_completed": int(uni_n),
            "p50_single_ms": round(stats.median(lat_uni), 2),
            "retraces_steady": kernels.trace_count() - traces0,
            "batch_launches": batch_t.count - batch_c0,
            "batch_size_max": max(batch_t.max_ms, batch_max0),
            "cross_table_batched_queries": int(
                reg.meter("dispatch_batch_cross_table",
                          labels=labels) - xtab0),
        }
        # PAIRED per-round ratio, median across rounds: each round's two
        # windows run back to back, so the per-round ratio cancels the
        # multi-second throughput drift this shared box exhibits (a slow
        # patch landing on one mode's only long window would otherwise
        # masquerade as a pipeline property); totals are also reported
        return {
            "serialized": serialized,
            "unified": unified,
            "speedup": round(stats.median(round_ratios), 2),
            "speedup_total": round(
                (uni_n / uni_wall) / max(ser_n / ser_wall, 1e-9), 2),
            "round_ratios": [round(r, 2) for r in round_ratios],
            "device_single_ms": round(single_ms, 3),
            "device_batch8_per_query_ms": round(batch8_ms / B, 3),
            "device_speedup_batch8": round(device_speedup, 2),
            "p50_paired_delta_ms": round(paired_delta_ms, 3),
            "p50_single_delta_pct": round(
                paired_delta_ms / serialized["p50_single_ms"] * 100.0, 2),
        }

    # the serving-default 2ms coalesce window stays: a wider window on
    # the few-core CPU stand-in turns each batch into a lock-step
    # barrier (every client's GIL-bound host phase synchronizes behind
    # the launch instead of overlapping the next batch's device time) —
    # partial bucket-padded batches amortize launches while keeping the
    # host and device phases pipelined
    def overrides(mode):
        return {"pinot.server.dispatch.mode": mode}

    # leg 1: mixed tables on the default (GSPMD segments-mesh) engine
    mixed = run_leg(
        lambda mode, labels: TpuOperatorExecutor(
            config=PinotConfiguration(overrides=overrides(mode)),
            metrics_labels=labels),
        tables, warm_stacked=True, leg="mixed")

    # leg 2: doc-sharded mesh engine (4 segments x 2 docs), one table —
    # the path that previously fell off batching entirely
    mesh = make_mesh(jax.devices()[:8], doc_axis=2)
    sharded = run_leg(
        lambda mode, labels: TpuOperatorExecutor(
            mesh=mesh, config=PinotConfiguration(
                overrides=overrides(mode)),
            metrics_labels=labels),
        {"ssb_a": tables["ssb_a"]}, warm_stacked=False, leg="doc_sharded")

    on_accelerator = jax.devices()[0].platform != "cpu"
    qps_floor = 2.0 if on_accelerator else 1.5
    out = {
        "metric": "unified_factory_batching_qps_speedup",
        "value": round(min(mixed["speedup"], sharded["speedup"]), 2),
        "unit": "x",
        "clients": clients,
        "duration_s": duration_s,
        "tables": {tn: {"segments": n, "docs": d}
                   for tn, (n, d) in table_docs.items()},
        "smoke": smoke,
        "platform": jax.devices()[0].platform,
        "mixed_table": mixed,
        "doc_sharded": sharded,
        "asserted": {"min_device_speedup_batch8": 2.0,
                     "min_qps_speedup": qps_floor,
                     "qps_bar_note": "2.0 on accelerators; 1.5 structural "
                                     "floor on the GIL-bound CPU stand-in "
                                     "(see docstring)",
                     "max_p50_regress_pct": 5.0,
                     "max_steady_retraces": 0},
    }
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_batching.json")
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))
    for leg_name, leg in (("mixed_table", mixed), ("doc_sharded", sharded)):
        assert leg["unified"]["retraces_steady"] == 0, \
            f"{leg_name} steady-state retraces: " \
            f"{leg['unified']['retraces_steady']}"
    assert mixed["unified"]["cross_table_batched_queries"] > 0, \
        "no cross-table batch formed in the measured window"
    if not smoke:
        for leg_name, leg in (("mixed_table", mixed),
                              ("doc_sharded", sharded)):
            assert leg["device_speedup_batch8"] >= 2.0, \
                f"{leg_name} device amortization " \
                f"{leg['device_speedup_batch8']:.2f}x < 2x"
            assert leg["speedup"] >= qps_floor, \
                f"{leg_name} speedup {leg['speedup']:.2f}x < {qps_floor}x"
            # epsilon absorbs scheduler noise on few-ms medians
            assert leg["p50_single_delta_pct"] < 5.0 \
                or leg["p50_paired_delta_ms"] < 0.5, \
                f"{leg_name} single-client p50 regressed " \
                f"{leg['p50_single_delta_pct']:.1f}%"


# ---------------------------------------------------------------------------
# --startree: device star-tree pre-agg vs scan (ISSUE 16)
# ---------------------------------------------------------------------------

def startree_main(smoke: bool = False, out_path: str = None):
    """--startree [--smoke]: A/B the device star-tree pre-agg leg
    (ISSUE 16) against the device scan path.

    Scaling leg — the same dimensional distribution is built at a base
    row count and at ``factor``x rows (100x in the full run), each with
    a star-tree. Two engines run every query: one serving from the
    pre-agg leg, one with ``pinot.server.startree.enabled=false`` (the
    scan path). Both end-to-end p50 and the DEVICE-level steady-state
    launch+sync time are recorded. The star-tree table's pre-agg record
    count is bounded by the dimension-combination space, not the row
    count, so its kernel reads the SAME [S, D] shape at both sizes —
    device time stays ~flat while the scan kernel's D bucket grows with
    the data. (End-to-end p50 carries fixed per-query host work — parse,
    plan, result assembly — so the device-level ratio is the asserted
    signal; the p50s are reported for color.)

    Coalesce leg — 8 clients loop fingerprint-equal star-tree queries
    (same plan, different predicate constants) against one pipelined
    engine: the unified-factory coalesce key (plan fingerprint + shape
    bucket) must batch them (`dispatch_batch_size` max > 1) with ZERO
    steady-state retraces after the shape buckets are warmed.

    Every query is parity-checked against the scan engine (1e-6
    relative, the repo's device-parity standard — the pre-agg leg runs
    f32 like the scan path). Writes BENCH_startree.json. --smoke
    shrinks rows/iters/windows to fit the tier-1 timeout."""
    import contextlib
    import statistics as stats
    import tempfile
    import threading

    import jax

    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  StarTreeIndexConfig, TableConfig,
                                  TableType)
    from pinot_tpu.ops import dispatch as dispatch_mod
    from pinot_tpu.ops import kernels
    from pinot_tpu.ops.engine import TpuOperatorExecutor
    from pinot_tpu.query.executor import QueryExecutor
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment
    from pinot_tpu.utils.config import PinotConfiguration

    base_docs = 1_200 if smoke else 3_000
    factor = 10 if smoke else 100
    num_segments = 2 if smoke else 4
    p50_iters = 6 if smoke else 30
    dev_iters = 8 if smoke else 25
    window_s = 0.8 if smoke else 2.5
    clients = 8

    tmp = tempfile.mkdtemp(prefix="bench_startree_")
    schema = Schema("stb", [
        FieldSpec("country", DataType.STRING),
        FieldSpec("browser", DataType.STRING),
        FieldSpec("locale", DataType.STRING),
        FieldSpec("impressions", DataType.LONG, FieldType.METRIC),
        FieldSpec("cost", DataType.DOUBLE, FieldType.METRIC),
    ])
    tc = TableConfig("stb", TableType.OFFLINE)
    tc.indexing.star_tree_configs = [StarTreeIndexConfig(
        dimensions_split_order=["country", "browser", "locale"],
        function_column_pairs=["SUM__impressions", "MAX__cost",
                               "SUM__cost"],
        max_leaf_records=10)]
    creator = SegmentCreator(tc, schema)

    def build(tag, docs_per_seg, seed):
        segs = []
        for i in range(num_segments):
            rng = np.random.default_rng(seed + i)
            out = os.path.join(tmp, f"stb_{tag}_{i}")
            creator.build({
                "country": [f"c{v}" for v in
                            rng.integers(0, 20, docs_per_seg)],
                "browser": [f"b{v}" for v in
                            rng.integers(0, 6, docs_per_seg)],
                "locale": [f"l{v}" for v in
                           rng.integers(0, 10, docs_per_seg)],
                "impressions": rng.integers(
                    0, 1000, docs_per_seg).astype(np.int64),
                "cost": rng.random(docs_per_seg) * 100,
            }, out, f"stb_{tag}_{i}")
            segs.append(load_segment(out))
        return segs

    sizes = {"1x": build("1x", base_docs // num_segments, 4000),
             f"{factor}x": build("nx", base_docs * factor // num_segments,
                                 5000)}

    def parity_sqls(alt):
        return [
            "SELECT SUM(impressions), COUNT(*) FROM stb "
            f"WHERE country = 'c{alt}'",
            "SELECT SUM(impressions) FROM stb "
            f"WHERE country IN ('c1','c2','c{alt}') AND browser = 'b2'",
            "SELECT MAX(cost), SUM(cost), COUNT(*) FROM stb",
            "SELECT browser, SUM(impressions), COUNT(*) FROM stb "
            f"WHERE locale = 'l{alt % 10}' "
            "GROUP BY browser ORDER BY browser LIMIT 100",
        ]

    p50_sql = parity_sqls(3)[0]

    def rows_close(a, b):
        if len(a) != len(b):
            return False
        for x, y in zip(a, b):
            if isinstance(x, float) or isinstance(y, float):
                if not (abs(float(x) - float(y))
                        <= 1e-6 * max(1.0, abs(float(x)))):
                    return False
            elif x != y:
                return False
        return True

    labels = {"bench_leg": "startree"}
    eng_tree = TpuOperatorExecutor(
        config=PinotConfiguration(), metrics_labels=labels)
    eng_scan = TpuOperatorExecutor(
        config=PinotConfiguration(overrides={
            "pinot.server.startree.enabled": False}),
        metrics_labels={"bench_leg": "startree_scan"})
    reg = eng_tree._dispatcher._metrics

    from pinot_tpu.query.context import QueryContext

    def timed_device(launch, iters):
        guard = dispatch_mod._CPU_COLLECTIVE_LOCK if launch.collective \
            else contextlib.nullcontext()
        with guard:
            jax.block_until_ready(launch.call())  # warm
            t0 = time.perf_counter()
            for _ in range(iters):
                jax.block_until_ready(launch.call())
            return (time.perf_counter() - t0) / iters * 1e3

    report_sizes = {}
    for tag, segs in sizes.items():
        ex_tree = QueryExecutor(segs, use_tpu=True, engine=eng_tree)
        ex_scan = QueryExecutor(segs, use_tpu=True, engine=eng_scan)
        served0 = reg.meter("startree_served", labels=labels)
        for sql in parity_sqls(3) + parity_sqls(7):
            rt = ex_tree.execute(sql)
            rs = ex_scan.execute(sql)
            assert not rt.exceptions and not rs.exceptions, (tag, sql)
            ra = sorted(map(str, rt.result_table.rows))
            rb = sorted(map(str, rs.result_table.rows))
            assert len(ra) == len(rb), (tag, sql)
            for a, b in zip(ra, rb):
                assert rows_close(eval(a), eval(b)), (tag, sql, a, b)
        served = reg.meter("startree_served", labels=labels) - served0
        assert served > 0, f"{tag}: no query served from the pre-agg leg"

        # device-level steady state: one launch+sync, params cache warm
        ctx = QueryContext.from_sql(p50_sql)
        prep_t = eng_tree._prepare_startree(segs, ctx)
        assert prep_t is not None, f"{tag}: pre-agg leg refused to stage"
        launch_t = prep_t[4]
        prep_s = eng_scan._prepare_agg(segs, QueryContext.from_sql(p50_sql))
        assert prep_s is not None
        launch_s = prep_s[3]
        dev_tree_ms = timed_device(launch_t, dev_iters)
        dev_scan_ms = timed_device(launch_s, dev_iters)

        def p50(ex):
            lat = []
            for _ in range(p50_iters):
                t0 = time.perf_counter()
                ex.execute(p50_sql)
                lat.append((time.perf_counter() - t0) * 1e3)
            return stats.median(lat)

        report_sizes[tag] = {
            "docs": sum(s.num_docs for s in segs),
            "preagg_records": sum(
                int(f.tree.meta.num_records) for f in prep_t[2]),
            "device_tree_ms": round(dev_tree_ms, 3),
            "device_scan_ms": round(dev_scan_ms, 3),
            "p50_tree_ms": round(p50(ex_tree), 2),
            "p50_scan_ms": round(p50(ex_scan), 2),
            "startree_served": int(served),
        }

    big = f"{factor}x"
    tree_growth = report_sizes[big]["device_tree_ms"] \
        / max(report_sizes["1x"]["device_tree_ms"], 1e-9)
    scan_growth = report_sizes[big]["device_scan_ms"] \
        / max(report_sizes["1x"]["device_scan_ms"], 1e-9)

    # -- coalesce leg: fingerprint-equal queries share one launch -----
    segs = sizes[big]
    ex_tree = QueryExecutor(segs, use_tpu=True, engine=eng_tree)
    coal_sqls = [parity_sqls(i)[0] for i in range(clients)]
    for sql in coal_sqls:  # stage + params-cache every predicate
        ex_tree.execute(sql)
    launch = eng_tree._prepare_startree(
        segs, QueryContext.from_sql(coal_sqls[0]))[4]
    guard = dispatch_mod._CPU_COLLECTIVE_LOCK if launch.collective \
        else contextlib.nullcontext()
    b = 2
    while b <= dispatch_mod._pow2(clients):
        kern = launch.factory(b, False)
        with guard:
            jax.block_until_ready(kern(
                launch.cols, batch_params([launch.params] * b), launch.num_docs,
                D=launch.D, G=launch.G))
        b *= 2
    traces0 = kernels.trace_count()
    batch_t0 = reg.timer("dispatch_batch_size", labels=labels)
    count0, max0 = batch_t0.count, batch_t0.max_ms

    stop_at = time.perf_counter() + window_s
    done = [0] * clients

    def client(ci):
        j = 0
        while time.perf_counter() < stop_at:
            ex_tree.execute(coal_sqls[(ci + j) % clients])
            done[ci] += 1
            j += 1

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    retraces = kernels.trace_count() - traces0
    batch_t = reg.timer("dispatch_batch_size", labels=labels)
    coalesce = {
        "clients": clients,
        "queries_completed": int(sum(done)),
        "qps": round(sum(done) / wall, 2),
        "batch_launches": batch_t.count - count0,
        "batch_size_max": max(batch_t.max_ms, max0),
        "retraces_steady": retraces,
    }

    out = {
        "metric": "startree_device_time_growth_at_{}".format(big),
        "value": round(tree_growth, 2),
        "unit": "x",
        "scan_growth": round(scan_growth, 2),
        "smoke": smoke,
        "platform": jax.devices()[0].platform,
        "sizes": report_sizes,
        "coalesce": coalesce,
        "asserted": {
            "parity": "pre-agg rows == scan rows, 1e-6 relative",
            "max_steady_retraces": 0,
            "min_batch_size": 2,
            "full_run_only": "device tree growth ~flat (< 3x) while "
                             "rows grow {}x; scan growth exceeds "
                             "tree growth".format(factor),
        },
    }
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_startree.json")
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))
    assert coalesce["retraces_steady"] == 0, \
        f"steady-state retraces: {coalesce['retraces_steady']}"
    assert coalesce["batch_size_max"] >= 2, \
        "fingerprint-equal star-tree queries never coalesced"
    if not smoke:
        assert tree_growth < 3.0, \
            f"pre-agg device time grew {tree_growth:.2f}x at {big} rows"
        assert scan_growth > tree_growth, \
            f"scan growth {scan_growth:.2f}x did not exceed tree " \
            f"growth {tree_growth:.2f}x"
        assert report_sizes[big]["device_tree_ms"] \
            < report_sizes[big]["device_scan_ms"], \
            "pre-agg kernel slower than the scan kernel at scale"


# ---------------------------------------------------------------------------
# --ingest: production ingestion under mixed read/write load (ISSUE 11)
# ---------------------------------------------------------------------------

def _pct(q, vals):
    if not vals:
        return 0.0
    return sorted(vals)[min(len(vals) - 1, max(0, round(q * len(vals)) - 1))]


def ingest_main(smoke: bool = False, out_path: str = None):
    """--ingest [--smoke]: the production-ingestion acceptance driver.

    One upsert REALTIME table consumed from an in-memory stream while a
    closed-loop query fleet reads it — the reference's "millions of
    events per second ingested while serving queries" scenario (SURVEY
    §6) at bench scale. Four legs:

      * mixed load — N producer threads + 8 query clients + a freshness
        prober (publish a sentinel pk, poll until queryable). Reports
        sustained events/sec, freshness p50/p95 (event ts -> queryable),
        query p50/p99, and the ZERO-GAP assertion: query p99 inside
        seal windows (mutable rotation -> commit) vs steady windows —
        the async build pipeline means a seal is never query-visible
        (bounded by CPU contention on the stand-in, gated tighter on
        accelerators).
      * backpressure — an overdriven producer against a small
        `pinot.server.ingest.memory.bytes` budget: mutable+pending
        bytes stay BOUNDED (adaptive fetch -> pause -> seal -> resume)
        while the same load with no budget grows unbounded; every row
        still lands.
      * chaos — a seeded SimulatedCrash (ingest.upsert.apply) kills the
        consumer MID-BATCH under the query load; queries keep serving
        from the old segment set with zero failures while a new manager
        recovers from the committed offsets + validDocIds snapshots;
        convergence is exactly-once (no duplicate, no lost rows).
      * journal — the chaos leg runs twice with the same seed; the
        failpoint decision journals must be byte-identical (the PR-3
        chaos bar).

    Writes BENCH_ingest.json (backend-gated).
    """
    import threading

    import jax

    from pinot_tpu.ingest.memory_stream import InMemoryStream
    from pinot_tpu.ingest.realtime_manager import (
        IngestionDelayTracker, RealtimeSegmentDataManager)
    from pinot_tpu.ingest.stream import LongMsgOffset, StreamConfig
    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  TableConfig, TableType, UpsertConfig)
    from pinot_tpu.ops.engine import TpuOperatorExecutor
    from pinot_tpu.query.executor import QueryExecutor
    from pinot_tpu.segment.loader import load_segment
    from pinot_tpu.server.data_manager import TableDataManager
    from pinot_tpu.utils.config import PinotConfiguration
    from pinot_tpu.utils.failpoints import SimulatedCrash, failpoints
    from pinot_tpu.utils.metrics import MetricsRegistry
    import tempfile

    on_cpu = jax.devices()[0].platform == "cpu"
    if smoke:
        window_s, clients, n_pks, flush_rows = 2.0, 3, 400, 500
        max_events, probe_every = 5_000, 0.05
        bp_budget, bp_events, bp_flush = 64 * 1024, 4_000, 400
        chaos_events, chaos_pks = 3_000, 300
    else:
        window_s, clients, n_pks, flush_rows = 20.0, 8, 20_000, 15_000
        max_events, probe_every = 120_000, 0.025
        bp_budget, bp_events, bp_flush = 512 * 1024, 100_000, 5_000
        chaos_events, chaos_pks = 24_000, 2_000

    schema = Schema("u", [
        FieldSpec("pk", DataType.LONG, FieldType.DIMENSION),
        FieldSpec("ver", DataType.LONG, FieldType.DIMENSION),
        FieldSpec("d", DataType.INT, FieldType.DIMENSION),
        FieldSpec("val", DataType.INT, FieldType.METRIC),
    ], primary_key_columns=["pk"])

    def table_cfg():
        tc = TableConfig("u", TableType.REALTIME)
        tc.upsert = UpsertConfig(mode="FULL", comparison_column="ver")
        return tc

    SQLS = [
        "SELECT COUNT(*), SUM(val) FROM u LIMIT 5",
        "SELECT d, COUNT(*), SUM(val) FROM u GROUP BY d ORDER BY d LIMIT 30",
        "SELECT pk, val FROM u WHERE val > 500 ORDER BY val DESC LIMIT 10",
    ]

    engine = TpuOperatorExecutor(config=PinotConfiguration())
    metrics = MetricsRegistry("bench_ingest")

    def run_query(serving, sql):
        tdm = serving["tdm"]
        sdms = tdm.acquire_segments()
        try:
            ex = QueryExecutor([s.segment for s in sdms], use_tpu=True,
                               engine=engine)
            return ex.execute(sql)
        finally:
            TableDataManager.release_all(sdms)

    def query_fleet(serving, stop_evt, n_clients):
        lats, fails = [], []
        lock = threading.Lock()

        def client(ci):
            i = ci
            while not stop_evt.is_set():
                sql = SQLS[i % len(SQLS)]
                i += 1
                t0 = time.time()
                try:
                    r = run_query(serving, sql)
                    if r.exceptions:
                        raise RuntimeError(str(r.exceptions[:1]))
                    with lock:
                        lats.append((t0, time.time() - t0))
                except Exception as e:  # noqa: BLE001
                    with lock:
                        fails.append(repr(e))
        ts = [threading.Thread(target=client, args=(ci,))
              for ci in range(n_clients)]
        for t in ts:
            t.start()
        return ts, lats, fails

    # ------------------------------------------------------------------
    # leg 1: mixed read/write load + freshness + seal windows
    # ------------------------------------------------------------------
    topic = InMemoryStream("bench_ingest_mixed", 1)
    store = tempfile.mkdtemp(prefix="bench_ingest_")
    tdm = TableDataManager("u_REALTIME")
    commits, opens = [], []
    tracker = IngestionDelayTracker(metrics=metrics)
    mgr = RealtimeSegmentDataManager(
        table_cfg(), schema, StreamConfig(
            stream_type="inmemory", topic="bench_ingest_mixed",
            flush_threshold_rows=flush_rows),
        0, tdm, store, metrics=metrics, ingestion_delay_tracker=tracker,
        on_commit=lambda n, o: commits.append((time.time(), n, o)),
        on_open=lambda n: opens.append((time.time(), n)))

    last_val = {}
    published = [0]
    pub_lock = threading.Lock()  # producer + prober both publish
    stop_evt = threading.Event()
    rng = np.random.default_rng(7)

    def producer():
        ver = 0
        while not stop_evt.is_set() and published[0] < max_events:
            if published[0] - mgr.rows_indexed > 5_000:
                # bounded-lag producer: a producer running unboundedly
                # ahead of a GIL-bound consumer only measures queue
                # growth; the sustained number is consumption-bound
                # either way (the backpressure leg measures the
                # overdriven case explicitly)
                time.sleep(0.002)
                continue
            now_ms = int(time.time() * 1000)
            for _ in range(200):
                if published[0] >= max_events:
                    break
                pk = int(rng.integers(0, n_pks))
                val = int(rng.integers(0, 1000))
                ver += 1
                with pub_lock:
                    topic.publish({"pk": pk, "ver": ver, "d": pk % 20,
                                   "val": val}, ts_ms=now_ms)
                    last_val[pk] = val
                    published[0] += 1

    freshness = []

    def prober():
        i = 0
        while not stop_evt.is_set():
            i += 1
            pk = 10**12 + i
            t0 = time.time()
            with pub_lock:
                topic.publish({"pk": pk, "ver": 1, "d": 0, "val": 0},
                              ts_ms=int(t0 * 1000))
                last_val[pk] = 0
                published[0] += 1
            sql = f"SELECT COUNT(*) FROM u WHERE pk = {pk} LIMIT 5"
            while not stop_evt.is_set():
                r = run_query({"tdm": tdm}, sql)
                if not r.exceptions and r.rows and r.rows[0][0] >= 1:
                    freshness.append(time.time() - t0)
                    break
                time.sleep(0.002)
            time.sleep(probe_every)

    mgr.start()
    prod_t = threading.Thread(target=producer)
    probe_t = threading.Thread(target=prober)
    t_start = time.time()
    prod_t.start()
    probe_t.start()
    fleet, lats, fails = query_fleet({"tdm": tdm}, stop_evt, clients)
    time.sleep(window_s)
    prod_stop = time.time()
    # let consumption fully drain before the final exactness check
    deadline = time.time() + 180
    while time.time() < deadline and mgr.rows_indexed < published[0]:
        time.sleep(0.02)
    stop_evt.set()
    for t in [prod_t, probe_t, *fleet]:
        t.join(timeout=10)
    drained = mgr.rows_indexed
    elapsed = prod_stop - t_start
    mgr.stop(drain=True)
    events_per_sec = drained / max(time.time() - t_start, 1e-9)

    # exactly-once visibility after the drain: one row per pk, last wins
    final = run_query({"tdm": tdm}, "SELECT COUNT(*), SUM(val) FROM u "
                                    "LIMIT 5").rows[0]
    expect_count, expect_sum = len(last_val), float(sum(last_val.values()))

    # seal windows: [rotation, commit] pairs (first open = initial ctor)
    seal_windows = []
    rot = [t for t, _n in opens[1:]]
    com = [t for t, _n, _o in commits]
    for i in range(min(len(rot), len(com))):
        seal_windows.append((rot[i], com[i] + 0.05))
    in_seal, steady = [], []
    for t0, dt in lats:
        if any(a <= t0 <= b for a, b in seal_windows):
            in_seal.append(dt)
        else:
            steady.append(dt)
    InMemoryStream.delete("bench_ingest_mixed")

    # ------------------------------------------------------------------
    # leg 2: backpressure — bounded bytes vs unbounded growth
    # ------------------------------------------------------------------
    def backpressure_leg(budget):
        name = f"bench_ingest_bp_{budget}"
        t2 = InMemoryStream(name, 1)
        tdm2 = TableDataManager("u_REALTIME")
        cfg = PinotConfiguration(overrides={
            "pinot.server.ingest.memory.bytes": budget,
            "pinot.server.ingest.fetch.max.rows": 2000,
        })
        m2 = RealtimeSegmentDataManager(
            table_cfg(), schema, StreamConfig(
                stream_type="inmemory", topic=name,
                flush_threshold_rows=bp_flush),
            0, tdm2, tempfile.mkdtemp(prefix="bench_ingest_bp_"),
            config=cfg, metrics=metrics)
        for i in range(bp_events):  # overdriven: everything is queued
            t2.publish({"pk": i, "ver": 1, "d": i % 20, "val": 1})
        peak = [0]
        done = threading.Event()

        def sampler():
            while not done.is_set():
                peak[0] = max(peak[0], m2.ingest_bytes())
                time.sleep(0.005)
        st = threading.Thread(target=sampler)
        m2.start()
        st.start()
        deadline = time.time() + 120
        while time.time() < deadline and m2.rows_indexed < bp_events:
            time.sleep(0.02)
        rows = m2.rows_indexed
        done.set()
        st.join()
        m2.stop(drain=True)
        InMemoryStream.delete(name)
        return peak[0], rows

    bounded_peak, bounded_rows = backpressure_leg(bp_budget)
    unbounded_peak, _rows = backpressure_leg(0)

    # ------------------------------------------------------------------
    # leg 3: chaos — seeded consumer SIGKILL mid-batch + journal replay
    # ------------------------------------------------------------------
    def chaos_leg(seed, tag):
        name = f"bench_ingest_chaos_{tag}"
        t3 = InMemoryStream(name, 1)
        store3 = tempfile.mkdtemp(prefix=f"bench_ingest_chaos_{tag}_")
        tdm3 = TableDataManager("u_REALTIME")
        commits3 = []
        rng3 = np.random.default_rng(seed)
        last3 = {}
        ver = 0
        for _ in range(chaos_events):  # deterministic pre-published log
            pk = int(rng3.integers(0, chaos_pks))
            val = int(rng3.integers(0, 1000))
            ver += 1
            t3.publish({"pk": pk, "ver": ver, "d": pk % 20, "val": val})
            last3[pk] = val
        fp = failpoints.arm("ingest.upsert.apply",
                            error=SimulatedCrash("kill"), times=1,
                            probability=0.002, seed=seed)
        sc = StreamConfig(stream_type="inmemory", topic=name,
                          flush_threshold_rows=max(200, chaos_events // 8))
        m3 = RealtimeSegmentDataManager(
            table_cfg(), schema, sc, 0, tdm3, store3, metrics=metrics,
            on_commit=lambda n, o: commits3.append((n, o)))
        serving = {"tdm": tdm3}
        stop3 = threading.Event()
        fleet3, lats3, fails3 = query_fleet(serving, stop3, clients)
        m3.start()
        deadline = time.time() + 60
        while time.time() < deadline and not m3._crashed:
            time.sleep(0.01)
        crashed = m3._crashed
        m3.stop()  # joins the dead thread; flushes in-flight builds

        # restart exactly as a fresh server process would
        resume = max((int(str(o)) for _n, o in commits3), default=0)
        tdm4 = TableDataManager("u_REALTIME")
        recovered = []
        for nm in sorted(os.listdir(store3)):
            path = os.path.join(store3, nm)
            if os.path.isdir(path) and not nm.startswith("_"):
                seg = load_segment(path)
                tdm4.add_segment(seg)
                recovered.append(seg)
        m4 = RealtimeSegmentDataManager(
            table_cfg(), schema, sc, 0, tdm4, store3, metrics=metrics,
            start_offset=LongMsgOffset(resume), start_seq=len(recovered),
            recover_segments=recovered)
        m4.start()
        serving["tdm"] = tdm4  # queries swap to the recovered view

        want = (len(last3), float(sum(last3.values())))
        got = (None, None)
        deadline = time.time() + 120
        while time.time() < deadline:
            r = run_query(serving, "SELECT COUNT(*), SUM(val) FROM u "
                                   "LIMIT 5")
            if not r.exceptions:
                got = (r.rows[0][0], float(r.rows[0][1]))
                if got == want:
                    break
            time.sleep(0.05)
        stop3.set()
        for t in fleet3:
            t.join(timeout=10)
        m4.stop(drain=True)
        decisions = list(fp.decisions)
        failpoints.disarm("ingest.upsert.apply")
        InMemoryStream.delete(name)
        return {"crashed": crashed, "converged": got == want,
                "got": got, "want": want, "failed_queries": len(fails3),
                "queries": len(lats3), "decisions": decisions}

    seed = 20260803
    chaos_a = chaos_leg(seed, "a")
    chaos_b = chaos_leg(seed, "b")
    replay_identical = chaos_a["decisions"] == chaos_b["decisions"]

    seal_p99 = _pct(0.99, in_seal)
    steady_p99 = _pct(0.99, steady)
    seal_gate = 2.0 if not on_cpu else 6.0
    out = {
        "metric": "ingest_events_per_sec_sustained",
        "value": round(events_per_sec),
        "unit": "events/s",
        "events_published": published[0],
        "events_indexed": drained,
        "window_s": round(elapsed, 1),
        "clients": clients,
        "freshness_p50_ms": round(_pct(0.50, freshness) * 1e3, 1),
        "freshness_p95_ms": round(_pct(0.95, freshness) * 1e3, 1),
        "query_p50_ms": round(_pct(0.50, [d for _t, d in lats]) * 1e3, 2),
        "query_p99_ms": round(_pct(0.99, [d for _t, d in lats]) * 1e3, 2),
        "queries_total": len(lats),
        "failed_queries": len(fails),
        "seals": len(commits),
        "seal_window_p99_ms": round(seal_p99 * 1e3, 2),
        "steady_window_p99_ms": round(steady_p99 * 1e3, 2),
        "seal_window_queries": len(in_seal),
        "exact_count": [final[0], expect_count],
        "exact_sum": [float(final[1]), expect_sum],
        "backpressure": {
            "budget_bytes": bp_budget,
            "bounded_peak_bytes": bounded_peak,
            "unbounded_peak_bytes": unbounded_peak,
            "rows": bounded_rows,
        },
        "chaos": {k: v for k, v in chaos_a.items() if k != "decisions"},
        "chaos_replay_identical": replay_identical,
        "host_cpu_cores": os.cpu_count(),
        "backend": jax.devices()[0].platform,
        "smoke": smoke,
        "asserted": {
            "failed_queries": 0,
            "exactly_once": True,
            "seal_p99_over_steady_max": seal_gate,
            "bounded_peak_over_budget_max": 1.5,
            "replay_identical": True,
        },
    }
    if out_path is None:
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_ingest.json")
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))

    # -- gates ---------------------------------------------------------
    assert len(fails) == 0, f"mixed-load queries failed: {fails[:3]}"
    assert drained == published[0], (drained, published[0])
    assert final[0] == expect_count and float(final[1]) == expect_sum, \
        (final, expect_count, expect_sum)
    assert len(commits) >= 2, "no seals happened — widen the window"
    assert bounded_rows == bp_events, "backpressure starved the consumer"
    assert bounded_peak <= bp_budget * 1.5, \
        f"mutable bytes escaped the budget: {bounded_peak} vs {bp_budget}"
    assert chaos_a["crashed"] and chaos_b["crashed"], "chaos never fired"
    assert chaos_a["failed_queries"] == 0 and chaos_b["failed_queries"] == 0
    assert chaos_a["converged"] and chaos_b["converged"], \
        (chaos_a["got"], chaos_a["want"])
    assert replay_identical, "same-seed chaos journal diverged"
    if not smoke:
        assert unbounded_peak > bounded_peak, \
            "backpressure contrast missing (unbounded never grew)"
        if in_seal and steady:
            assert seal_p99 <= seal_gate * max(steady_p99, 1e-4), \
                f"seal-visible p99 spike: {seal_p99*1e3:.1f}ms vs " \
                f"steady {steady_p99*1e3:.1f}ms"


def health_main(smoke: bool = False, out_path: "str | None" = None):
    """--health [--smoke]: the fleet health plane must be ~free (ISSUE 14).

    Two overhead legs over identical MiniClusters in one process, with
    an A/A noise floor:

    * accounting leg — pinot.workload.accounting.enabled=false (no
      ChargeSlips, no WorkloadStats rollup) vs on (the default):
      strictly interleaved paired A/B. Asserts <2% p50.
    * sampling leg — alternating BLOCKS of queries with the metrics
      sampler + SLO watchdog running (aggressive 50ms interval — 20x
      the default cadence) vs stopped, on the accounting-off cluster.
      A background thread can't be isolated per query pair, so blocks
      alternate to cancel drift. Asserts <2% p50.

    Also asserts the qualitative contract: the accounting-on side's
    WorkloadStats carry real rows-scanned totals and a per-tenant cost
    gauge. Writes BENCH_health.json; smoke runs in tier-1 via
    tests/test_health_plane.py.
    """
    import statistics as stats
    import tempfile

    import numpy as np

    from pinot_tpu.cluster.mini import MiniCluster
    from pinot_tpu.health.history import MetricsHistory, MetricsSampler
    from pinot_tpu.health.slo import SloWatchdog
    from pinot_tpu.health.workload import get_workload
    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  TableConfig, TableType)
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment
    from pinot_tpu.utils.config import PinotConfiguration

    num_segments = 8 if smoke else 32
    docs = 5_000 if smoke else 20_000
    iters = 16 if smoke else 40
    blocks = 4 if smoke else 8
    block_n = 8 if smoke else 16
    query = ("SELECT SUM(v), COUNT(*) FROM t "
             "WHERE k BETWEEN 100 AND 800 OPTION(skipCache=true)")

    schema = Schema("t", [
        FieldSpec("k", DataType.INT, FieldType.DIMENSION),
        FieldSpec("v", DataType.INT, FieldType.METRIC),
    ])
    creator = SegmentCreator(TableConfig("t", TableType.OFFLINE), schema)
    tmp = tempfile.mkdtemp(prefix="bench_health_")
    segments = []
    for i in range(num_segments):
        rng = np.random.default_rng(i)
        d = os.path.join(tmp, f"seg_{i}")
        creator.build({"k": rng.integers(0, 1000, docs).astype(np.int32),
                       "v": rng.integers(0, 100, docs).astype(np.int32)},
                      d, f"t_{i}")
        segments.append(load_segment(d))

    def make_cluster(cfg):
        c = MiniCluster(num_servers=2, config=cfg)
        c.start()
        c.add_table("t")
        for i, seg in enumerate(segments):
            c.add_segment("t", seg, server_idx=i % 2)
        return c

    off_cfg = PinotConfiguration(overrides={
        "pinot.workload.accounting.enabled": False})
    on_cfg = PinotConfiguration()  # defaults: accounting armed
    c_off = make_cluster(off_cfg)
    c_on = make_cluster(on_cfg)

    get_workload("server").clear()

    def one(c, q=query):
        t0 = time.perf_counter()
        resp = c.query(q)
        assert not resp.exceptions, resp.exceptions
        return (time.perf_counter() - t0) * 1e3

    def paired_pct(run_a, run_b, n):
        ratios, deltas, a_lat, b_lat = [], [], [], []
        for i in range(n):
            if i % 2 == 0:
                a, b = run_a(), run_b()
            else:
                b, a = run_b(), run_a()
            a_lat.append(a)
            b_lat.append(b)
            ratios.append(b / a)
            deltas.append(b - a)
        return ((stats.median(ratios) - 1.0) * 100.0,
                stats.median(deltas),
                stats.median(a_lat), stats.median(b_lat))

    #: the sampler under test: aggressive interval, both role
    #: registries' worth of series, SLO targets armed so every tick
    #: pays full burn-rate evaluation
    slo_cfg = PinotConfiguration(overrides={
        "pinot.slo.query.p99.ms": 10_000.0,
        "pinot.slo.error.rate": 0.01,
        "pinot.slo.window.short.seconds": 5.0,
        "pinot.slo.window.long.seconds": 30.0})
    hist = MetricsHistory(1024)
    try:
        for _ in range(8):
            one(c_off), one(c_on)
        noise_pct, _, _, _ = paired_pct(
            lambda: one(c_off),
            lambda: (one(c_on), one(c_off))[1], iters)
        noise_pct = abs(noise_pct)

        # -- leg 1: accounting off vs on, paired --------------------------
        acct_pct, acct_delta_ms, p50_off, p50_acct = paired_pct(
            lambda: one(c_off), lambda: one(c_on), iters)

        # -- leg 2: sampler+watchdog running vs stopped, block-paired -----
        with_s, without_s = [], []
        for b in range(blocks):
            sampler = MetricsSampler("server", interval_s=0.05,
                                     history=hist)
            sampler.add_hook(SloWatchdog("server", hist,
                                         config=slo_cfg).evaluate)
            run_first = b % 2 == 0
            for phase in (0, 1):
                sampling = (phase == 0) == run_first
                if sampling:
                    ticks_before = len(hist)
                    sampler.start()
                lat = [one(c_off) for _ in range(block_n)]
                if sampling:
                    # a fast block can finish inside the sampler's first
                    # 50ms wait; hold it open (latencies are already
                    # collected) until it has ticked so every sampling
                    # block actually exercises the sample+watchdog path
                    deadline = time.perf_counter() + 2.0
                    while (len(hist) == ticks_before
                           and time.perf_counter() < deadline):
                        time.sleep(0.005)
                    sampler.stop()
                    with_s.append(stats.median(lat))
                else:
                    without_s.append(stats.median(lat))
        p50_sampling = stats.median(with_s)
        p50_nosampling = stats.median(without_s)
        sampling_pct = (p50_sampling / p50_nosampling - 1.0) * 100.0

        # qualitative contract: the on-side actually attributed work
        wl = get_workload("server")
        top = wl.top(5)
        assert top and top[0]["rowsScanned"] > 0, top
        assert wl.tenants(), "no per-tenant cost accumulated"
        assert len(hist) > 0, "sampler appended nothing"
    finally:
        c_off.stop()
        c_on.stop()

    out = {
        "metric": "health_plane_overhead_pct",
        "value": round(max(acct_pct, sampling_pct), 3),
        "unit": "%",
        "accounting_overhead_pct": round(acct_pct, 3),
        "accounting_paired_delta_ms": round(acct_delta_ms, 3),
        "sampling_overhead_pct": round(sampling_pct, 3),
        "p50_off_ms": round(p50_off, 3),
        "p50_accounting_ms": round(p50_acct, 3),
        "p50_sampling_ms": round(p50_sampling, 3),
        "p50_nosampling_ms": round(p50_nosampling, 3),
        "aa_noise_floor_pct": round(noise_pct, 3),
        "sampler_interval_ms": 50.0,
        "history_samples": len(hist),
        "num_segments": num_segments,
        "docs_per_segment": docs,
        "iters": iters,
        "smoke": smoke,
        "asserted_max_pct": 2.0,
    }
    if out_path is None and not smoke:
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_health.json")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(out, f, indent=2)
    print(json.dumps(out))
    # the STRICT <2% bar belongs to the
    # full run (the committed BENCH_health.json); smoke runs inside
    # tier-1 on a loaded CI box whose A/A floor alone can be 3-8%, so it
    # asserts the qualitative contract (no multi-ms / tens-of-percent
    # regression) without flaking on scheduler noise
    if smoke:
        bound = max(25.0, 2.0 * noise_pct + 5.0)
        eps_ms = max(2.0, 0.10 * p50_off)
    else:
        bound = max(2.0, noise_pct + 1.0)
        eps_ms = 0.5
    assert acct_pct < bound or acct_delta_ms < eps_ms, \
        (f"workload accounting costs {acct_pct:.2f}% p50 "
         f"({acct_delta_ms:.3f}ms paired; bound {bound:.2f}%, "
         f"A/A floor {noise_pct:.2f}%)")
    assert sampling_pct < bound \
        or (p50_sampling - p50_nosampling) < eps_ms, \
        (f"metrics sampling costs {sampling_pct:.2f}% p50 "
         f"(bound {bound:.2f}%, A/A floor {noise_pct:.2f}%)")


def overload_main(smoke: bool = False, out_path: "str | None" = None):
    """--overload [--smoke]: admission control must preserve goodput
    under offered load past capacity (ISSUE 15).

    An OPEN-LOOP driver — arrivals on a clock, never waiting for
    responses, the only honest way to measure overload — at 1x/2x/4x of
    measured capacity against two MiniClusters in one process:

    * protected — admission control + bounded scheduler queues + the
      per-table retry budget + overload-aware hedging (the defaults);
    * unprotected — ``pinot.server.admission.enabled=false`` +
      ``pinot.broker.retry.budget.enabled=false`` (the pre-PR-15
      behavior), hedging equally enabled.

    Per-query execution cost is pinned by a fixed-delay
    ``server.execute.before`` failpoint so capacity is deterministic
    (4 worker threads / delay) and an over-admitted query measurably
    BURNS a worker thread — the resource the protection exists to
    guard. Every query ships a fixed end-to-end budget; outcomes are
    counted as ok (clean in-budget answer), typed (errorCode partial/
    rejection), or hung (no typed outcome within budget + grace).

    Asserted (full run): protected goodput at 4x >= 70% of measured 1x
    capacity while the unprotected leg collapses below that bar; ZERO
    hung queries anywhere; protection overhead < 2% p50 at 1x against
    the A/A noise floor. The overhead A/B toggles the protection flags
    on ONE live cluster in alternating blocks (same sockets, same
    threads) — comparing two separate cluster instances measures
    cluster-placement noise, not the protection code. Smoke (tier-1 via
    tests/test_overload.py) asserts the qualitative contract with
    CI-noise-tolerant bounds. Writes BENCH_overload.json.
    """
    import statistics as stats
    import tempfile
    import threading

    import numpy as np

    from pinot_tpu.broker.failure_detector import ConnectionFailureDetector
    from pinot_tpu.cluster.mini import MiniCluster
    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  TableConfig, TableType)
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment
    from pinot_tpu.utils.config import PinotConfiguration
    from pinot_tpu.utils.failpoints import failpoints

    num_segments = 4
    docs = 2_000
    # one worker thread per server + a long pinned exec keep the 4x
    # offered load CHEAP on the host (tens of arrivals/s): the A/B must
    # measure the protection dynamics, not the 2-core box's GIL
    exec_delay_s = 0.12 if smoke else 0.2
    budget_ms = 1000.0 if smoke else 1500.0
    duration_s = 1.6 if smoke else 4.0
    hung_grace_s = 2.5
    mults = (1, 4) if smoke else (1, 2, 4)
    overhead_iters = 12 if smoke else 40
    workers_total = 2  # 2 servers x 1 scheduler thread

    schema = Schema("t", [
        FieldSpec("k", DataType.INT, FieldType.DIMENSION),
        FieldSpec("v", DataType.INT, FieldType.METRIC),
    ])
    creator = SegmentCreator(TableConfig("t", TableType.OFFLINE), schema)
    tmp = tempfile.mkdtemp(prefix="bench_overload_")
    segments = []
    for i in range(num_segments):
        rng = np.random.default_rng(i)
        d = os.path.join(tmp, f"seg_{i}")
        creator.build({"k": rng.integers(0, 1000, docs).astype(np.int32),
                       "v": rng.integers(0, 100, docs).astype(np.int32)},
                      d, f"t_{i}")
        segments.append(load_segment(d))

    base = {
        "pinot.server.query.num.threads": 1,
        "pinot.broker.timeout.ms": int(budget_ms),
        "pinot.broker.hedge.enabled": True,
        "pinot.broker.hedge.delay.min.ms": 40,
        "pinot.broker.hedge.delay.max.ms": 300,
    }
    # queue limit sized so a full queue's drain (limit x exec / worker)
    # still fits the budget with the exec itself on top
    prot_cfg = PinotConfiguration(overrides={
        **base, "pinot.server.admission.queue.limit": 3})
    unprot_cfg = PinotConfiguration(overrides={
        **base,
        "pinot.server.admission.enabled": False,
        "pinot.broker.retry.budget.enabled": False,
        "pinot.brownout.enabled": False})

    def make_cluster(cfg):
        c = MiniCluster(num_servers=2, config=cfg)
        c.start()
        c.add_table("t")
        for i, seg in enumerate(segments):
            # full replication: per-query routing lands the whole set on
            # ONE server (round-robin across queries), the twin is the
            # hedge/retry target
            c.add_segment("t", seg, server_idx=0, replicas=[1])
        return c

    c_prot = make_cluster(prot_cfg)
    c_unprot = make_cluster(unprot_cfg)
    query = ("SELECT SUM(v), COUNT(*) FROM t WHERE k BETWEEN 100 AND 800 "
             "OPTION(skipCache=true)")

    def one(c):
        """One clean closed-loop query latency (warmup + overhead legs).
        A lone deadline partial here means the HOST stalled (loaded CI
        box), not that the protection failed — retry a couple of times
        before treating it as real; anything non-250 stays fatal."""
        from pinot_tpu.utils import errorcodes as _ec
        for attempt in range(3):
            t0 = time.perf_counter()
            resp = c.query(query)
            if not resp.exceptions:
                return (time.perf_counter() - t0) * 1e3
            codes = {e.get("errorCode") for e in resp.exceptions}
            assert codes == {_ec.EXECUTION_TIMEOUT}, resp.exceptions
        raise AssertionError(
            f"3 consecutive deadline misses at idle load: "
            f"{resp.exceptions}")

    def set_protection(flag: bool) -> None:
        """Toggle the protection machinery on the LIVE protected
        cluster: the overhead A/B must flip only the code under test,
        never the sockets/threads it runs on."""
        for s in c_prot.servers:
            s.transport.admission.enabled = flag
        for b in c_prot.brokers:
            b._retry_budget.enabled = flag

    def block_pct(toggle: bool, blocks: int, block_n: int):
        """Block-paired p50s on c_prot: alternating protection-on/-off
        blocks (toggle=True) or all-off blocks split the same way
        (toggle=False — the A/A floor). Returns (overhead %, delta ms,
        baseline p50 ms)."""
        on_p50, off_p50 = [], []
        for blk in range(blocks):
            run_on = blk % 2 == 0
            for phase in (0, 1):
                protected = (phase == 0) == run_on
                set_protection(protected if toggle else False)
                lat = [one(c_prot) for _ in range(block_n)]
                (on_p50 if ((phase == 0) == run_on)
                 else off_p50).append(stats.median(lat))
        set_protection(True)
        base_p50 = stats.median(off_p50)
        return ((stats.median(on_p50) / base_p50 - 1.0) * 100.0,
                stats.median(on_p50) - base_p50, base_p50)

    def reset_brokers():
        """Between legs: fresh failure-detector state (an earlier leg's
        exiles must not leak), settled server queues."""
        for c in (c_prot, c_unprot):
            for b in c.brokers:
                b.failure_detector = ConnectionFailureDetector()

    def open_loop(c, rate_qps, leg_duration_s, pool):
        counts = {"ok": 0, "typed": 0, "hung": 0}
        ok_lat = []
        abandoned = set()  # query ids the waiter already counted hung
        lock = threading.Lock()
        budget_s = budget_ms / 1000.0

        def fire_one(qid):
            t0 = time.perf_counter()
            typed = False
            untyped_raise = False
            try:
                resp = c.query(query)
                typed = bool(resp.exceptions)
            except Exception:  # noqa: BLE001 — an untyped raise is a bug
                untyped_raise = True
            dur = time.perf_counter() - t0
            with lock:
                if qid in abandoned:
                    return  # the waiter counted this query hung already
                if untyped_raise or dur > budget_s + hung_grace_s:
                    counts["hung"] += 1
                elif typed:
                    counts["typed"] += 1
                else:
                    counts["ok"] += 1
                    ok_lat.append(dur * 1e3)

        n = max(1, int(rate_qps * leg_duration_s))
        start = time.perf_counter()
        futs = []
        for i in range(n):
            target = start + i / rate_qps
            delay = target - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            futs.append(pool.submit(fire_one, i))
        deadline = time.perf_counter() + budget_s + hung_grace_s + 5.0
        for i, f in enumerate(futs):
            remaining = max(0.0, deadline - time.perf_counter())
            try:
                f.result(timeout=remaining)
            except Exception:  # noqa: BLE001 — hung; exactly-once with
                with lock:     # fire_one via the abandoned set
                    abandoned.add(i)
                    counts["hung"] += 1
        elapsed = max(leg_duration_s, time.perf_counter() - start)
        return {
            "offered_qps": round(rate_qps, 2),
            "queries": n,
            "ok": counts["ok"],
            "typed": counts["typed"],
            "hung": counts["hung"],
            "goodput_qps": round(counts["ok"] / elapsed, 2),
            "ok_p50_ms": (round(stats.median(ok_lat), 1)
                          if ok_lat else None),
        }

    from pinot_tpu.utils.metrics import get_registry
    try:
        # -- warm both clusters (EWMA estimates, routing, compile) -----
        for _ in range(6):
            one(c_prot), one(c_unprot)

        # -- overhead leg at 1x, NO injected delay: the protection's
        # own cost is a few dict lookups per query ---------------------
        blocks = 4 if smoke else 8
        noise_pct, _, _ = block_pct(False, blocks, overhead_iters // 2)
        noise_pct = abs(noise_pct)
        over_pct, over_delta_ms, p50_unprot = block_pct(
            True, blocks, overhead_iters // 2)

        # -- pin per-query cost, measure capacity closed-loop ----------
        fp = failpoints.arm("server.execute.before", delay=exec_delay_s)
        cap_pool = ThreadPoolExecutor(max_workers=workers_total + 2)
        cap_t0 = time.perf_counter()
        cap_n = [0]
        cap_stop = cap_t0 + (1.6 if smoke else 3.0)

        def cap_loop():
            while time.perf_counter() < cap_stop:
                resp = c_prot.query(query)
                if not resp.exceptions:
                    # a typed rejection here is the protection working
                    # (momentary rr imbalance overflows one server's
                    # tiny queue); capacity counts CLEAN answers only
                    cap_n[0] += 1
        cap_futs = [cap_pool.submit(cap_loop)
                    for _ in range(workers_total + 2)]
        for f in cap_futs:
            f.result(timeout=60)
        cap_pool.shutdown(wait=True)
        capacity_qps = cap_n[0] / (time.perf_counter() - cap_t0)
        # the structural ceiling: workers / per-query delay
        capacity_qps = min(capacity_qps, workers_total / exec_delay_s)

        # -- open-loop legs --------------------------------------------
        legs = {}
        pool = ThreadPoolExecutor(max_workers=256,
                                  thread_name_prefix="overload-client")
        for mult in mults:
            for name, c in (("protected", c_prot),
                            ("unprotected", c_unprot)):
                reset_brokers()
                legs[f"{name}_{mult}x"] = open_loop(
                    c, mult * capacity_qps, duration_s, pool)
                time.sleep(budget_ms / 1000.0 * 0.5)  # drain queues
        pool.shutdown(wait=True)
        failpoints.clear()

        reg_server = get_registry("server").sample()["counters"]
        admission_rejects = sum(
            v for k, v in reg_server.items()
            if k.startswith("server_admission_rejected"))
        reg_broker = get_registry("broker").sample()["counters"]
        retries_issued = sum(v for k, v in reg_broker.items()
                             if k.startswith("broker_retries_issued"))
        broker_queries = sum(v for k, v in reg_broker.items()
                             if k == "broker_queries"
                             or k.startswith("broker_queries{"))
    finally:
        failpoints.clear()
        c_prot.stop()
        c_unprot.stop()

    prot_4x = legs["protected_4x"]["goodput_qps"]
    unprot_4x = legs["unprotected_4x"]["goodput_qps"]
    hung_total = sum(leg["hung"] for leg in legs.values())
    out = {
        "metric": "overload_protected_goodput_frac_of_capacity_at_4x",
        "value": round(prot_4x / capacity_qps, 3),
        "unit": "fraction",
        "capacity_qps": round(capacity_qps, 2),
        "exec_delay_ms": exec_delay_s * 1e3,
        "budget_ms": budget_ms,
        "legs": legs,
        "protected_4x_goodput_qps": prot_4x,
        "unprotected_4x_goodput_qps": unprot_4x,
        "collapse_ratio": round(prot_4x / max(unprot_4x, 0.01), 2),
        "hung_queries_total": hung_total,
        "admission_rejects": admission_rejects,
        "broker_retries_issued": retries_issued,
        "broker_queries": broker_queries,
        "retry_ratio": round(retries_issued / max(broker_queries, 1), 4),
        "overhead_pct_at_1x": round(over_pct, 3),
        "overhead_paired_delta_ms": round(over_delta_ms, 3),
        "aa_noise_floor_pct": round(noise_pct, 3),
        "p50_unprotected_ms": round(p50_unprot, 3),
        "smoke": smoke,
        "asserted": {"min_protected_frac_at_4x": 0.7 if not smoke else 0.4,
                     "max_overhead_pct": 2.0, "max_hung": 0},
    }
    if out_path is None and not smoke:
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_overload.json")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(out, f, indent=2)
    print(json.dumps(out))

    # -- gates ----------------------------------------------------------
    assert hung_total == 0, f"{hung_total} hung/untyped queries"
    if smoke:
        # qualitative bars: a loaded CI box makes absolute goodput
        # noisy, but protection must still clearly hold the line
        assert prot_4x >= 0.4 * capacity_qps, \
            (f"protected goodput {prot_4x} < 40% of capacity "
             f"{capacity_qps:.1f} at 4x (smoke)")
        bound = max(25.0, 2.0 * noise_pct + 5.0)
        eps_ms = max(2.0, 0.10 * p50_unprot)
        assert over_pct < bound or over_delta_ms < eps_ms, \
            (f"admission costs {over_pct:.2f}% p50 at 1x "
             f"(bound {bound:.2f}%, floor {noise_pct:.2f}%)")
    else:
        assert prot_4x >= 0.7 * capacity_qps, \
            (f"protected goodput {prot_4x} < 70% of capacity "
             f"{capacity_qps:.1f} at 4x")
        assert unprot_4x < 0.7 * capacity_qps, \
            (f"unprotected leg did not collapse ({unprot_4x} vs "
             f"capacity {capacity_qps:.1f}) — the A/B proves nothing")
        bound = max(2.0, noise_pct + 1.0)
        assert over_pct < bound or over_delta_ms < 0.5, \
            (f"admission costs {over_pct:.2f}% p50 at 1x "
             f"(bound {bound:.2f}%, A/A floor {noise_pct:.2f}%)")


# ---------------------------------------------------------------------------
# --logs: CLP log-analytics workload (ISSUE 17)
# ---------------------------------------------------------------------------

_LOG_TEMPLATES = (
    lambda r: f"INFO  request req-{int(r.integers(0, 10**6))} served in "
              f"{int(r.integers(1, 500))} ms from host h{int(r.integers(0, 8))}",
    lambda r: f"WARN  GC pause of {round(float(r.random()) * 4, 2)} seconds "
              f"detected at offset {int(r.integers(0, 10**9))}",
    lambda r: f"ERROR Connection to 10.0.{int(r.integers(0, 32))}."
              f"{int(r.integers(1, 255))}:{int(r.integers(1000, 9000))} "
              f"refused after {int(r.integers(1, 6))} retries",
    lambda r: f"INFO  user u{int(r.integers(0, 500))} logged in from "
              f"10.1.{int(r.integers(0, 32))}.{int(r.integers(1, 255))}",
    lambda r: f"ERROR task t{int(r.integers(0, 9999))} failed on host "
              f"h{int(r.integers(0, 8))}: code={int(r.integers(400, 600))}",
    lambda r: f"WARN  disk /dev/sd{chr(97 + int(r.integers(0, 4)))}1 at "
              f"{int(r.integers(1, 99))}% capacity",
)


def _log_corpus(rng, n):
    k = len(_LOG_TEMPLATES)
    return [_LOG_TEMPLATES[int(rng.integers(0, k))](rng) for _ in range(n)]


def logs_main(smoke: bool = False, out_path: "str | None" = None):
    """--logs [--smoke]: the CLP log-analytics acceptance driver
    (ISSUE 17). Four legs over a realistic templated log corpus:

    * pushdown A/B — the SAME LIKE queries through the device CLP
      pushdown leg (logtype/dict/encoded-var match kernels over staged
      int32 pseudo-columns, no string decode) and through the host
      decode path; every answer parity-checked bit-exact, p50 ratio
      reported. Gate: device >= 2x host on the CPU stand-in (>= 5x on
      accelerators) — the host path pays string matching over the
      decoded column, the device path reads fixed-width ids.
    * coalesce — N clients loop fingerprint-equal LIKE queries whose
      pattern CONSTANTS differ (patterns live in staged params, never
      in the plan): batched launches must form with ZERO steady-state
      retraces once the pow2 shape buckets are warm.
    * ingest — realtime log ingestion into the mutable CLP column
      (template dictionary built AT INGEST, not at seal), sustained
      events/s with >= 2 seal rotations and exactly-once visibility,
      then a seeded SimulatedCrash (`ingest.realtime.consume`) killing
      the consumer MID-BATCH: a fresh manager recovers from the
      committed offset + sealed segments and converges to exactly-once
      (COUNT and SUM(ts) both exact) with ZERO failed queries.
    * mixed tenants — one MiniCluster serving an OLAP table (tenant
      weight 4) and the log table (weight 1) through the PR-8/15
      weighted-fair + brownout broker stack: the OLAP fleet's p99
      during mixed traffic must stay within its SLO target.

    Writes BENCH_logs.json (backend-gated bars). --smoke shrinks
    corpus/windows to fit tier-1 (tests/test_clp_device.py).
    """
    import contextlib
    import statistics as stats
    import tempfile
    import threading

    import jax

    from pinot_tpu.cluster.mini import MiniCluster
    from pinot_tpu.ingest.memory_stream import InMemoryStream
    from pinot_tpu.ingest.realtime_manager import RealtimeSegmentDataManager
    from pinot_tpu.ingest.stream import LongMsgOffset, StreamConfig
    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  TableConfig, TableType)
    from pinot_tpu.ops import dispatch as dispatch_mod
    from pinot_tpu.ops import kernels
    from pinot_tpu.ops.engine import TpuOperatorExecutor
    from pinot_tpu.query.context import QueryContext
    from pinot_tpu.query.executor import QueryExecutor
    from pinot_tpu.segment import index_types as seg_it
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment
    from pinot_tpu.server.data_manager import TableDataManager
    from pinot_tpu.utils.config import PinotConfiguration
    from pinot_tpu.utils.failpoints import SimulatedCrash, failpoints

    on_cpu = jax.devices()[0].platform == "cpu"
    if smoke:
        docs, num_segments, p50_iters = 1_500, 2, 6
        clients, window_s = 6, 0.8
        max_events, flush_rows = 4_000, 600
        chaos_events, chaos_flush = 2_500, 400
        mix_window_s, olap_clients, log_clients = 1.0, 3, 3
    else:
        docs, num_segments, p50_iters = 25_000, 4, 30
        clients, window_s = 8, 2.5
        max_events, flush_rows = 60_000, 8_000
        chaos_events, chaos_flush = 20_000, 3_000
        mix_window_s, olap_clients, log_clients = 4.0, 4, 4

    tmp = tempfile.mkdtemp(prefix="bench_logs_")
    schema = Schema("logs", [
        FieldSpec("ts", DataType.LONG, FieldType.DATE_TIME),
        FieldSpec("message", DataType.STRING),
    ])
    tc = TableConfig("logs", TableType.OFFLINE)
    tc.indexing.clp_columns = ["message"]
    segs, raw_bytes, clp_bytes = [], 0, 0
    for i in range(num_segments):
        rng = np.random.default_rng(1700 + i)
        msgs = _log_corpus(rng, docs)
        out_dir = os.path.join(tmp, f"logs_{i}")
        SegmentCreator(tc, schema).build(
            {"ts": np.arange(docs, dtype=np.int64), "message": msgs},
            out_dir, f"logs_{i}")
        seg = load_segment(out_dir)
        segs.append(seg)
        raw_bytes += sum(len(m.encode()) for m in msgs)
        clp_bytes += len(bytes(seg.dir.get_buffer("message", seg_it.CLP)))

    labels = {"bench_leg": "logs"}
    eng = TpuOperatorExecutor(config=PinotConfiguration(),
                              metrics_labels=labels)
    reg = eng._dispatcher._metrics
    dev = QueryExecutor(segs, use_tpu=True, engine=eng)
    host = QueryExecutor(segs, use_tpu=False)

    # ------------------------------------------------------------------
    # leg 1: pushdown A/B — parity + p50 ratio
    # ------------------------------------------------------------------
    needles = ["%refused%", "%failed on host%", "INFO%", "%capacity",
               "%logged in%"]
    sqls = [f"SELECT COUNT(*) FROM logs WHERE message LIKE '{p}'"
            for p in needles]
    served0 = reg.meter("clp_served", labels=labels)
    for sql in sqls:
        a, b = dev.execute(sql), host.execute(sql)
        assert not a.exceptions and not b.exceptions, sql
        assert a.result_table.rows[0][0] == b.result_table.rows[0][0], \
            (sql, a.result_table.rows, b.result_table.rows)
    served = reg.meter("clp_served", labels=labels) - served0
    assert served == len(sqls), \
        f"only {served}/{len(sqls)} LIKE queries served device-side"

    def p50(ex, sql):
        lat = []
        for _ in range(p50_iters):
            t0 = time.perf_counter()
            ex.execute(sql)
            lat.append((time.perf_counter() - t0) * 1e3)
        return stats.median(lat)

    ab = {}
    for p, sql in zip(needles[:3], sqls[:3]):
        d, h = p50(dev, sql), p50(host, sql)
        ab[p] = {"device_p50_ms": round(d, 3), "host_p50_ms": round(h, 3),
                 "speedup": round(h / max(d, 1e-9), 2)}
    speedup_min = min(v["speedup"] for v in ab.values())

    # ------------------------------------------------------------------
    # leg 2: coalesce — constant-different LIKE queries, zero retraces
    # ------------------------------------------------------------------
    coal_sqls = [f"SELECT COUNT(*) FROM logs WHERE message LIKE "
                 f"'%failed on host h{i % 8}:%'" for i in range(clients)]
    for sql in coal_sqls:   # stage blocks + params, trace b=1
        assert not dev.execute(sql).exceptions
    launch = eng._prepare_agg(
        segs, QueryContext.from_sql(coal_sqls[0]))[3]
    guard = dispatch_mod._CPU_COLLECTIVE_LOCK if launch.collective \
        else contextlib.nullcontext()
    b = 2
    while b <= dispatch_mod._pow2(clients):  # warm pow2 batch buckets
        kern = launch.factory(b, False)
        with guard:
            jax.block_until_ready(kern(
                launch.cols, batch_params([launch.params] * b), launch.num_docs,
                D=launch.D, G=launch.G))
        b *= 2
    traces0 = kernels.trace_count()
    batch_t0 = reg.timer("dispatch_batch_size", labels=labels)
    count0, max0 = batch_t0.count, batch_t0.max_ms
    stop_at = time.perf_counter() + window_s
    done = [0] * clients

    def coal_client(ci):
        j = 0
        while time.perf_counter() < stop_at:
            dev.execute(coal_sqls[(ci + j) % clients])
            done[ci] += 1
            j += 1

    threads = [threading.Thread(target=coal_client, args=(i,))
               for i in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    batch_t = reg.timer("dispatch_batch_size", labels=labels)
    coalesce = {
        "clients": clients,
        "queries_completed": int(sum(done)),
        "qps": round(sum(done) / wall, 2),
        "batch_launches": batch_t.count - count0,
        "batch_size_max": max(batch_t.max_ms, max0),
        "retraces_steady": kernels.trace_count() - traces0,
    }

    # ------------------------------------------------------------------
    # leg 3: realtime ingest — events/s, then seeded mid-batch kill
    # ------------------------------------------------------------------
    def rt_cfg():
        c = TableConfig("logs", TableType.REALTIME)
        c.indexing.clp_columns = ["message"]
        return c

    def query_fleet(serving, stop_evt, n_clients, sql_of):
        lats, fails = [], []
        lock = threading.Lock()

        def client(ci):
            i = ci
            while not stop_evt.is_set():
                i += 1
                t0 = time.time()
                try:
                    tdm = serving["tdm"]
                    sdms = tdm.acquire_segments()
                    try:
                        r = QueryExecutor(
                            [s.segment for s in sdms],
                            use_tpu=False).execute(sql_of(i))
                        if r.exceptions:
                            raise RuntimeError(str(r.exceptions[:1]))
                    finally:
                        TableDataManager.release_all(sdms)
                    with lock:
                        lats.append(time.time() - t0)
                except Exception as e:  # noqa: BLE001
                    with lock:
                        fails.append(repr(e))
        ts = [threading.Thread(target=client, args=(ci,))
              for ci in range(n_clients)]
        for t in ts:
            t.start()
        return ts, lats, fails

    log_sql = "SELECT COUNT(*) FROM logs WHERE message LIKE '%refused%'"

    # -- 3a: sustained throughput + exactly-once at rest ---------------
    topic = InMemoryStream("bench_logs_ingest", 1)
    store = tempfile.mkdtemp(prefix="bench_logs_rt_")
    tdm = TableDataManager("logs_REALTIME")
    commits = []
    rng = np.random.default_rng(77)
    mgr = RealtimeSegmentDataManager(
        rt_cfg(), schema, StreamConfig(
            stream_type="inmemory", topic="bench_logs_ingest",
            flush_threshold_rows=flush_rows),
        0, tdm, store, on_commit=lambda n, o: commits.append((n, o)))
    for i in range(max_events):  # pre-published deterministic log
        topic.publish({"ts": i, "message": _log_corpus(rng, 1)[0]})
    stop_evt = threading.Event()
    fleet, lats, fails = query_fleet(
        {"tdm": tdm}, stop_evt, 2, lambda i: log_sql)
    t_start = time.time()
    mgr.start()
    deadline = time.time() + 300
    while time.time() < deadline and mgr.rows_indexed < max_events:
        time.sleep(0.02)
    elapsed = time.time() - t_start
    stop_evt.set()
    for t in fleet:
        t.join(timeout=10)
    drained = mgr.rows_indexed
    mgr.stop(drain=True)
    events_per_sec = drained / max(elapsed, 1e-9)
    sdms = tdm.acquire_segments()
    try:
        r = QueryExecutor([s.segment for s in sdms],
                          use_tpu=False).execute(
            "SELECT COUNT(*), SUM(ts) FROM logs LIMIT 5")
        exact = (int(r.rows[0][0]), float(r.rows[0][1]))
    finally:
        TableDataManager.release_all(sdms)
    want = (max_events, float(max_events * (max_events - 1) // 2))
    InMemoryStream.delete("bench_logs_ingest")

    # -- 3b: seeded mid-batch kill -> restart -> exactly-once ----------
    topic3 = InMemoryStream("bench_logs_chaos", 1)
    store3 = tempfile.mkdtemp(prefix="bench_logs_chaos_")
    tdm3 = TableDataManager("logs_REALTIME")
    commits3 = []
    rng3 = np.random.default_rng(88)
    for i in range(chaos_events):
        topic3.publish({"ts": i, "message": _log_corpus(rng3, 1)[0]})
    # probability tuned so the seeded kill lands MID-STREAM: the full
    # run has ~200 fetch hits, so p=0.01 fires deep enough that sealed
    # segments exist to recover; smoke's 25 hits need a hotter trigger
    fp = failpoints.arm("ingest.realtime.consume",
                        error=SimulatedCrash("kill"), times=1,
                        probability=0.05 if smoke else 0.01,
                        seed=20260807)
    sc3 = StreamConfig(stream_type="inmemory", topic="bench_logs_chaos",
                       flush_threshold_rows=chaos_flush)
    m3 = RealtimeSegmentDataManager(
        rt_cfg(), schema, sc3, 0, tdm3, store3,
        on_commit=lambda n, o: commits3.append((n, o)))
    serving = {"tdm": tdm3}
    stop3 = threading.Event()
    fleet3, lats3, fails3 = query_fleet(serving, stop3, 2,
                                        lambda i: log_sql)
    m3.start()
    deadline = time.time() + 120
    while time.time() < deadline and not m3._crashed:
        time.sleep(0.01)
    crashed = m3._crashed
    m3.stop()  # joins the dead thread
    # restart exactly as a fresh server process would: committed offset
    # + sealed segments from the store; the crashed mutable VANISHES
    resume = max((int(str(o)) for _n, o in commits3), default=0)
    tdm4 = TableDataManager("logs_REALTIME")
    recovered = []
    for nm in sorted(os.listdir(store3)):
        path = os.path.join(store3, nm)
        if os.path.isdir(path) and not nm.startswith("_"):
            seg = load_segment(path)
            tdm4.add_segment(seg)
            recovered.append(seg)
    m4 = RealtimeSegmentDataManager(
        rt_cfg(), schema, sc3, 0, tdm4, store3,
        start_offset=LongMsgOffset(resume), start_seq=len(recovered),
        recover_segments=recovered)
    m4.start()
    serving["tdm"] = tdm4  # queries swap to the recovered view
    chaos_want = (chaos_events,
                  float(chaos_events * (chaos_events - 1) // 2))
    chaos_got = (None, None)
    deadline = time.time() + 180
    while time.time() < deadline:
        sdms = tdm4.acquire_segments()
        try:
            r = QueryExecutor([s.segment for s in sdms],
                              use_tpu=False).execute(
                "SELECT COUNT(*), SUM(ts) FROM logs LIMIT 5")
        finally:
            TableDataManager.release_all(sdms)
        if not r.exceptions:
            chaos_got = (int(r.rows[0][0]), float(r.rows[0][1]))
            if chaos_got == chaos_want:
                break
        time.sleep(0.05)
    stop3.set()
    for t in fleet3:
        t.join(timeout=10)
    m4.stop(drain=True)
    decisions = list(fp.decisions)
    failpoints.disarm("ingest.realtime.consume")
    InMemoryStream.delete("bench_logs_chaos")

    # ------------------------------------------------------------------
    # leg 4: mixed tenants — OLAP p99 within SLO under log traffic
    # ------------------------------------------------------------------
    slo_ms = 400.0 if on_cpu else 100.0
    olap_schema = Schema("ssb", [
        FieldSpec("k", DataType.INT, FieldType.DIMENSION),
        FieldSpec("v", DataType.INT, FieldType.METRIC),
    ])
    olap_creator = SegmentCreator(TableConfig("ssb", TableType.OFFLINE),
                                  olap_schema)
    c = MiniCluster(num_servers=1, config=PinotConfiguration(overrides={
        "pinot.slo.query.p99.ms": slo_ms}))
    c.start()
    c.add_table("ssb", tenant="olap", tenant_weight=4.0)
    c.add_table("logs", tenant="logs", tenant_weight=1.0)
    for i in range(2):
        rngo = np.random.default_rng(40 + i)
        d = os.path.join(tmp, f"ssb_{i}")
        olap_creator.build(
            {"k": rngo.integers(0, 1000, 4000).astype(np.int32),
             "v": rngo.integers(0, 100, 4000).astype(np.int32)},
            d, f"ssb_{i}")
        c.add_segment("ssb", load_segment(d), server_idx=0)
    for seg in segs[:2]:
        c.add_segment("logs", seg, server_idx=0)
    olap_sql = ("SELECT SUM(v), COUNT(*) FROM ssb "
                "WHERE k BETWEEN 100 AND 800 OPTION(skipCache=true)")

    def mix_window(with_logs):
        stop_m = threading.Event()
        olap_lat, log_lat, mfails = [], [], []
        lock = threading.Lock()

        def olap_client():
            while not stop_m.is_set():
                t0 = time.perf_counter()
                r = c.query(olap_sql)
                dt = (time.perf_counter() - t0) * 1e3
                with lock:
                    if r.exceptions:
                        mfails.append(str(r.exceptions[:1]))
                    else:
                        olap_lat.append(dt)

        def log_client(ci):
            j = ci
            while not stop_m.is_set():
                j += 1
                t0 = time.perf_counter()
                r = c.query("SELECT COUNT(*) FROM logs WHERE message "
                            f"LIKE '%failed on host h{j % 8}:%' "
                            "OPTION(skipCache=true)")
                dt = (time.perf_counter() - t0) * 1e3
                with lock:
                    if r.exceptions:
                        mfails.append(str(r.exceptions[:1]))
                    else:
                        log_lat.append(dt)

        ts = [threading.Thread(target=olap_client)
              for _ in range(olap_clients)]
        if with_logs:
            ts += [threading.Thread(target=log_client, args=(i,))
                   for i in range(log_clients)]
        for t in ts:
            t.start()
        time.sleep(mix_window_s)
        stop_m.set()
        for t in ts:
            t.join(timeout=10)
        return olap_lat, log_lat, mfails

    c.query(olap_sql)  # warm both paths before measuring
    c.query("SELECT COUNT(*) FROM logs WHERE message LIKE '%refused%'")
    iso_lat, _, iso_fails = mix_window(with_logs=False)
    mixed_lat, mixed_log_lat, mixed_fails = mix_window(with_logs=True)
    c.stop()
    mixed = {
        "slo_p99_ms": slo_ms,
        "olap_tenant_weight": 4.0,
        "logs_tenant_weight": 1.0,
        "olap_iso_p50_ms": round(_pct(0.50, iso_lat), 2),
        "olap_iso_p99_ms": round(_pct(0.99, iso_lat), 2),
        "olap_mixed_p50_ms": round(_pct(0.50, mixed_lat), 2),
        "olap_mixed_p99_ms": round(_pct(0.99, mixed_lat), 2),
        "log_mixed_p50_ms": round(_pct(0.50, mixed_log_lat), 2),
        "olap_queries": len(iso_lat) + len(mixed_lat),
        "log_queries": len(mixed_log_lat),
        "failed_queries": len(iso_fails) + len(mixed_fails),
    }

    out = {
        "metric": "clp_device_like_speedup_vs_host_decode",
        "value": speedup_min,
        "unit": "x",
        "docs": num_segments * docs,
        "clp_compression_ratio": round(raw_bytes / max(clp_bytes, 1), 2),
        "pushdown_ab": ab,
        "clp_served": int(served),
        "coalesce": coalesce,
        "ingest": {
            "events_per_sec": round(events_per_sec),
            "events_published": max_events,
            "events_indexed": int(drained),
            "seals": len(commits),
            "exact": [list(exact), list(want)],
            "query_p50_ms": round(_pct(0.50, lats) * 1e3, 2),
            "failed_queries": len(fails),
        },
        "chaos": {
            "crashed": bool(crashed),
            "converged": chaos_got == chaos_want,
            "got": list(chaos_got),
            "want": list(chaos_want),
            "seals_before_kill": len(commits3),
            "resume_offset": resume,
            "decisions": len(decisions),
            "failed_queries": len(fails3),
        },
        "mixed_tenants": mixed,
        "host_cpu_cores": os.cpu_count(),
        "backend": jax.devices()[0].platform,
        "smoke": smoke,
        "asserted": {
            "parity": "device LIKE == host LIKE, bit-exact counts",
            "min_speedup": 2.0 if on_cpu else 5.0,
            "max_steady_retraces": 0,
            "min_batch_size": 2,
            "exactly_once": True,
            "olap_p99_within_slo": True,
            "failed_queries": 0,
        },
    }
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_logs.json")
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))

    # -- gates ---------------------------------------------------------
    assert coalesce["retraces_steady"] == 0, \
        f"steady-state retraces: {coalesce['retraces_steady']}"
    assert coalesce["batch_size_max"] >= 2, \
        "fingerprint-equal CLP queries never coalesced"
    assert drained == max_events and exact == want, (exact, want)
    assert len(commits) >= 2, "no seal rotations — widen the window"
    assert len(fails) == 0, f"ingest-window queries failed: {fails[:3]}"
    assert crashed, "chaos never fired"
    assert chaos_got == chaos_want, (chaos_got, chaos_want)
    assert len(fails3) == 0, f"chaos-window queries failed: {fails3[:3]}"
    assert mixed["failed_queries"] == 0, "mixed-traffic queries failed"
    if not smoke:
        gate = 2.0 if on_cpu else 5.0
        assert speedup_min >= gate, \
            f"device LIKE speedup {speedup_min}x under the {gate}x bar"
        assert mixed["olap_mixed_p99_ms"] <= slo_ms, \
            (f"OLAP p99 {mixed['olap_mixed_p99_ms']}ms broke the "
             f"{slo_ms}ms SLO under mixed traffic")


def _rebalance_build_cluster(tmp: str, num_segments: int, docs: int):
    """3 servers, replication 2: every segment lives on servers 0 and 1,
    server 2 is empty — the rebalance target and the repair headroom.
    Returns (cluster, segment_names, expected_answers) where
    expected_answers[k] = (count, sum) for ``WHERE k >= k``."""
    import numpy as np

    from pinot_tpu.cluster.mini import MiniCluster
    from pinot_tpu.models.schema import Schema
    from pinot_tpu.models.table_config import TableConfig
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment
    from pinot_tpu.utils.config import PinotConfiguration

    schema = Schema.from_dict({
        "schemaName": "rb",
        "dimensionFieldSpecs": [{"name": "k", "dataType": "LONG"}],
        "metricFieldSpecs": [{"name": "v", "dataType": "LONG"}]})
    tc = TableConfig.from_dict(
        {"tableName": "rb", "tableType": "OFFLINE",
         "segmentsConfig": {"replication": 2}})
    creator = SegmentCreator(tc, schema)
    # roomy retry budget: when a server is killed mid-loop, all 8
    # clients' in-flight queries retry at once — availability, not
    # retry-storm damping, is what this bench measures
    cfg = PinotConfiguration().with_overrides(
        {"pinot.broker.retry.budget.min": 64.0,
         "pinot.broker.retry.budget.cap": 256.0})
    cluster = MiniCluster(num_servers=3, config=cfg)
    cluster.start()
    cluster.add_table("rb", table_config=tc, schema=schema)
    ks, vs, names = [], [], []
    for i in range(num_segments):
        rng = np.random.default_rng(300 + i)
        k = rng.integers(0, 8, docs).astype(np.int64)
        v = rng.integers(0, 1000, docs).astype(np.int64)
        d = os.path.join(tmp, f"rb_{i}")
        creator.build({"k": k, "v": v}, d, f"rb_{i}")
        seg = load_segment(d)
        cluster.add_segment("rb", seg, server_idx=i % 2,
                            replicas=[(i + 1) % 2])
        ks.append(k)
        vs.append(v)
        names.append(seg.name)
    k = np.concatenate(ks)
    v = np.concatenate(vs)
    expected = {kk: (int((k >= kk).sum()), int(v[k >= kk].sum()))
                for kk in range(5)}
    return cluster, names, expected


def _rebalance_chaos_journal(tmp: str, sub: str, seed: int,
                             num_segments: int):
    """One seeded chaos run of a pure-state rebalance plan (engine only,
    max.parallel.moves=1): returns (journal sha1, failpoint decisions).
    Two same-seed runs must match byte for byte."""
    import hashlib

    from pinot_tpu.controller.cluster_state import (
        ClusterState, InstanceState, SegmentState)
    from pinot_tpu.controller.rebalancer import Rebalancer
    from pinot_tpu.models.schema import Schema
    from pinot_tpu.models.table_config import TableConfig
    from pinot_tpu.utils.config import PinotConfiguration
    from pinot_tpu.utils.failpoints import FaultSchedule
    from pinot_tpu.utils.metrics import MetricsRegistry

    st = ClusterState()
    for i in range(3):
        st.register_instance(InstanceState(f"server_{i}"))
    st.add_table(
        TableConfig.from_dict({"tableName": "rb", "tableType": "OFFLINE"}),
        Schema.from_dict({"schemaName": "rb", "dimensionFieldSpecs":
                          [{"name": "k", "dataType": "LONG"}]}))
    for i in range(num_segments):
        st.upsert_segment(SegmentState(f"rb_{i}", "rb_OFFLINE",
                                       [f"server_{i % 2}"],
                                       dir_path=f"/deep/rb_{i}"))
    jp = os.path.join(tmp, f"chaos_{sub}.journal")
    rb = Rebalancer(
        st, load_fn=lambda *a: None, unload_fn=lambda *a: None,
        config=PinotConfiguration().with_overrides(
            {"pinot.controller.rebalance.max.parallel.moves": 1}),
        journal_path=jp, metrics=MetricsRegistry("controller"))
    sched = FaultSchedule([
        ("controller.rebalance.move",
         {"delay": 0.002, "probability": 0.5, "seed": seed}),
    ])
    sched.arm()
    try:
        job = rb.run("rb_OFFLINE", {
            f"rb_{i}": {"from": [f"server_{i % 2}"],
                        "to": [f"server_{(i + 1) % 3}"]}
            for i in range(num_segments)})
    finally:
        sched.disarm()
        rb.close()
    assert job.status == "DONE", job.progress()
    with open(jp, "rb") as f:
        digest = hashlib.sha1(f.read()).hexdigest()
    return digest, sched.decisions()


def rebalance_main(smoke: bool = False, out_path: "str | None" = None):
    """--rebalance [--smoke]: self-healing acceptance (ISSUE 18).

    Leg A — **live rebalance, zero downtime**: an 8-client closed loop
    runs while EVERY segment moves from servers {0,1} to {1,2} through
    the journaled move engine (load+warm target -> one batched
    assignment/routing commit -> drain source, never below the
    availability floor). Asserts zero failed queries, zero wrong
    answers (a query routed to an unloaded target, or a source drained
    early, would return silently short rows), and a commit-time guard
    that every instance in the new assignment already holds its
    segment (the flip-before-load regression the one-shot assignment
    flip had).

    Leg B — **kill + automatic repair**: server 1 is killed
    (SIGKILL-equivalent) mid-loop; the RepairChecker debounces the dead
    heartbeat (two stale ticks), re-replicates its segments from their
    dirs onto the surviving server through the same move engine, and
    `segments_missing_replicas` drains to 0. Asserts zero failed
    queries (broker failover bridges the gap) and repair convergence.

    Leg C — **seeded chaos determinism**: the same plan under a seeded
    delay schedule at `controller.rebalance.move` (parallelism 1) runs
    twice; move journals must be byte-identical and the failpoint
    decision logs equal.

    Writes BENCH_rebalance.json. --smoke shrinks data + durations and
    skips the throughput-floor assert; zero-failures, correctness,
    convergence, and replay-identical are asserted always."""
    import tempfile
    import threading

    from pinot_tpu.utils.metrics import MetricsRegistry

    num_segments = 4 if smoke else 8
    docs = 800 if smoke else 20_000
    duration_s = 1.2 if smoke else 5.0
    clients = 8

    tmp = tempfile.mkdtemp(prefix="bench_rebalance_")
    cluster, seg_names, expected = _rebalance_build_cluster(
        tmp, num_segments, docs)

    lock = threading.Lock()

    def closed_loop(duration: float):
        """8-client closed loop; returns (latencies, failures, wrong)."""
        stop_at = time.perf_counter() + duration
        lat, failures, wrong = [], [], []

        def client(cid: int):
            i = cid
            while time.perf_counter() < stop_at:
                kk = i % 5
                t0 = time.perf_counter()
                resp = cluster.query(
                    f"SELECT COUNT(*), SUM(v) FROM rb WHERE k >= {kk}")
                dt = time.perf_counter() - t0
                with lock:
                    lat.append(dt)
                    if resp.exceptions:
                        failures.append(resp.exceptions)
                    elif (resp.rows[0][0], resp.rows[0][1]) != expected[kk]:
                        wrong.append((kk, resp.rows[0], expected[kk]))
                i += clients
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return lat, failures, wrong

    def p(q, vals):
        if not vals:
            return 0.0
        return sorted(vals)[min(len(vals) - 1,
                                max(0, round(q * len(vals)) - 1))]

    for i in range(4):  # warm parse/plan/serde
        resp = cluster.query(f"SELECT COUNT(*), SUM(v) FROM rb "
                             f"WHERE k >= {i % 5}")
        assert not resp.exceptions, resp.exceptions

    lat_base, fail_base, wrong_base = closed_loop(duration_s)
    qps_base = len(lat_base) / duration_s

    # -- leg A: live rebalance under load ------------------------------
    rb = cluster.make_rebalancer(
        journal_path=os.path.join(tmp, "rebalance.journal"))
    inner_commit = rb.commit_fn
    guard_violations = []

    def checked_commit(table, assignment):
        # flip-before-load guard: at commit time, EVERY instance in the
        # new assignment must already hold the segment (loaded+warmed)
        for name, insts in assignment.items():
            for iid in insts:
                srv = next(s for s in cluster.servers
                           if s.instance_id == iid)
                tdm = srv.data_manager.table(table, create=False)
                if tdm is None or tdm.current_segment(name) is None:
                    guard_violations.append((name, iid))
        inner_commit(table, assignment)

    rb.commit_fn = checked_commit
    move_result = {}

    def run_move():
        try:
            job = rb.run("rb_OFFLINE", {
                name: {"from": ["server_0", "server_1"],
                       "to": ["server_1", "server_2"]}
                for name in seg_names})
            move_result["status"] = job.status
            move_result["moves_done"] = job.progress()["done"]
        except Exception as exc:  # noqa: BLE001 — surface, don't hang
            move_result["status"] = f"error: {exc!r}"

    mover = threading.Timer(duration_s * 0.25, run_move)
    mover.start()
    lat_move, fail_move, wrong_move = closed_loop(duration_s)
    mover.join()
    qps_move = len(lat_move) / duration_s
    drained = all(
        cluster.servers[0].data_manager.table(
            "rb_OFFLINE").current_segment(n) is None for n in seg_names)

    # -- leg B: kill server_1 + automatic repair under load ------------
    reg = MetricsRegistry("controller")
    rb.metrics = reg
    rep = cluster.make_repair_checker(rb)
    rep.metrics = reg
    rep.grace_s = 0.02
    repair_result = {"converged": False, "ticks": 0,
                     "convergence_s": None}

    def kill_and_repair():
        time.sleep(duration_s * 0.25)
        t_kill = time.perf_counter()
        cluster.kill_server(1)
        deadline = time.perf_counter() + max(duration_s * 4, 20.0)
        while time.perf_counter() < deadline:
            out = rep.check_once()
            repair_result["ticks"] += 1
            missing = reg.sample()["gauges"].get(
                'segments_missing_replicas{table="rb_OFFLINE"}')
            if out["stale"] and out["repaired"] == {} and missing == 0:
                repair_result["converged"] = True
                repair_result["convergence_s"] = round(
                    time.perf_counter() - t_kill, 3)
                return
            time.sleep(0.03)

    repairer = threading.Thread(target=kill_and_repair)
    repairer.start()
    lat_kill, fail_kill, wrong_kill = closed_loop(duration_s)
    repairer.join()
    qps_kill = len(lat_kill) / duration_s
    rb.close()
    cluster.stop()

    # -- leg C: same-seed chaos -> byte-identical journals -------------
    seed = 20260807
    dig_a, dec_a = _rebalance_chaos_journal(tmp, "a", seed, num_segments)
    dig_b, dec_b = _rebalance_chaos_journal(tmp, "b", seed, num_segments)
    journals_identical = dig_a == dig_b and dec_a == dec_b

    out = {
        "metric": "self_healing_failed_queries",
        "value": len(fail_move) + len(fail_kill),
        "unit": "queries",
        "rebalance": {
            "failed_queries": len(fail_move),
            "wrong_answers": len(wrong_move),
            "guard_violations": len(guard_violations),
            "job_status": move_result.get("status"),
            "moves_done": move_result.get("moves_done"),
            "sources_drained": drained,
            "qps_during_move": round(qps_move, 1),
            "p99_during_move_ms": round(p(0.99, lat_move) * 1e3, 2),
        },
        "repair": {
            "failed_queries": len(fail_kill),
            "wrong_answers": len(wrong_kill),
            "converged": repair_result["converged"],
            "convergence_s": repair_result["convergence_s"],
            "repair_ticks": repair_result["ticks"],
            "qps_during_kill_repair": round(qps_kill, 1),
            "p99_during_kill_repair_ms": round(p(0.99, lat_kill) * 1e3, 2),
        },
        "determinism": {
            "journals_identical": journals_identical,
            "journal_digest": dig_a[:16],
        },
        "baseline": {
            "failed_queries": len(fail_base),
            "wrong_answers": len(wrong_base),
            "qps": round(qps_base, 1),
            "p50_ms": round(p(0.50, lat_base) * 1e3, 2),
            "p99_ms": round(p(0.99, lat_base) * 1e3, 2),
        },
        "queries_total": len(lat_base) + len(lat_move) + len(lat_kill),
        "num_segments": num_segments,
        "docs_per_segment": docs,
        "clients": clients,
        "smoke": smoke,
        "asserted": {"failed_queries": 0, "wrong_answers": 0,
                     "guard_violations": 0, "converged": True,
                     "journals_identical": True,
                     "min_qps_frac": None if smoke else 0.25},
    }
    if out_path is None:
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_rebalance.json")
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))
    assert move_result.get("status") == "DONE", move_result
    assert not guard_violations, \
        f"routing flipped before load: {guard_violations[:3]}"
    assert not fail_base and not fail_move and not fail_kill, \
        (f"failed queries: base={len(fail_base)} move={len(fail_move)} "
         f"kill={len(fail_kill)}: "
         f"{(fail_base + fail_move + fail_kill)[:3]}")
    assert not wrong_base and not wrong_move and not wrong_kill, \
        (f"wrong answers: {wrong_base[:2]} {wrong_move[:2]} "
         f"{wrong_kill[:2]}")
    assert drained, "sources not drained after the move"
    assert repair_result["converged"], \
        f"repair did not converge: {repair_result}"
    assert journals_identical, "same-seed chaos journals diverged"
    if not smoke:
        assert qps_move >= 0.25 * qps_base, \
            f"rebalance collapsed throughput: {qps_move:.0f} vs " \
            f"{qps_base:.0f} baseline QPS"
        assert qps_kill >= 0.25 * qps_base, \
            f"kill+repair collapsed throughput: {qps_kill:.0f} vs " \
            f"{qps_base:.0f} baseline QPS"


def _mesh_build_table(tmp, name, num_segments, docs, seed):
    """SSB-Q1.1-shaped table (same column mix as the batching bench):
    dict dims + a raw metric, integer-valued so the merged path's sums
    are bit-exact against the host fold."""
    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  TableConfig, TableType)
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment

    schema = Schema(name, [
        FieldSpec("lo_orderdate", DataType.INT, FieldType.DIMENSION),
        FieldSpec("lo_discount", DataType.INT, FieldType.DIMENSION),
        FieldSpec("lo_quantity", DataType.INT, FieldType.DIMENSION),
        FieldSpec("lo_extendedprice", DataType.INT, FieldType.METRIC),
    ])
    tc = TableConfig(name, TableType.OFFLINE)
    tc.indexing.no_dictionary_columns = ["lo_extendedprice"]
    tc.indexing.compression = "PASS_THROUGH"
    creator = SegmentCreator(tc, schema)
    dates = np.array([y * 10000 + m * 100 + d
                      for y in range(1992, 1999)
                      for m in range(1, 13) for d in range(1, 29)],
                     dtype=np.int32)
    segs = []
    for i in range(num_segments):
        rng = np.random.default_rng(seed + i)
        out = os.path.join(tmp, f"{name}_{i}")
        creator.build({
            "lo_orderdate": dates[rng.integers(0, len(dates), docs)],
            "lo_discount": rng.integers(0, 11, docs).astype(np.int32),
            "lo_quantity": rng.integers(1, 51, docs).astype(np.int32),
            # small ints: every grouped f32 partial sum stays under
            # 2^24, so merged-vs-host parity is EXACT equality even in
            # f32 staging (the non-grouped SUM is isum-plane exact
            # regardless of magnitude)
            "lo_extendedprice": rng.integers(1, 500, docs).astype(np.int32),
        }, out, f"{name}_{i}")
        segs.append(load_segment(out))
    return segs


_MESH_SQLS = (
    # SSB Q1.1: range filters + SUM of product + COUNT — the isum plane
    # makes the SUM bit-exact, so merged-vs-host parity is == not ~=
    "SELECT SUM(lo_extendedprice * lo_discount), COUNT(*) FROM {t} "
    "WHERE lo_orderdate BETWEEN 19940101 AND 19940631 "
    "AND lo_discount BETWEEN 1 AND 3 AND lo_quantity < 25",
    # group-by with min/max: the merged kernel's pmin/pmax semiring plus
    # the host-side global-key factorization
    "SELECT lo_discount, SUM(lo_extendedprice), MIN(lo_quantity), "
    "MAX(lo_quantity), COUNT(*) FROM {t} GROUP BY lo_discount "
    "ORDER BY lo_discount LIMIT 20",
)


def _mesh_measure(engine_on, engine_off, segs, table, total_docs,
                  rounds, window_s, p50_iters, labels_on):
    """One paired merge-ON vs merge-OFF A/B at a fixed mesh size —
    the BENCH_batching discipline: alternating back-to-back windows,
    per-round paired ratios (median cancels box drift), interleaved
    single-query p50, steady-state retrace delta asserted zero."""
    import statistics as stats

    from pinot_tpu.ops import kernels
    from pinot_tpu.query.context import QueryContext
    from pinot_tpu.query.executor import QueryExecutor

    ex_on = QueryExecutor(segs, use_tpu=True, engine=engine_on)
    ex_off = QueryExecutor(segs, use_tpu=True, engine=engine_off)
    ctxs = [QueryContext.from_sql(q.format(t=table)) for q in _MESH_SQLS]

    # warm: compile every (plan, mesh) shape both modes will run, and
    # assert the merged path answers BIT-IDENTICALLY to the host fold
    # (integer data: the isum plane and exact group counts make ==
    # legitimate, not a tolerance check)
    for sql in (q.format(t=table) for q in _MESH_SQLS):
        r_on = ex_on.execute(sql)
        r_off = ex_off.execute(sql)
        assert not r_on.exceptions and not r_off.exceptions, (
            r_on.exceptions, r_off.exceptions)
        assert r_on.rows == r_off.rows, (
            f"merged path diverged from host fold: {sql}: "
            f"{r_on.rows} vs {r_off.rows}")

    def one(ex, i):
        t0 = time.perf_counter()
        ex.execute_context(ctxs[i % len(ctxs)])
        return (time.perf_counter() - t0) * 1e3

    for i in range(4):  # settle caches on both paths
        one(ex_on, i), one(ex_off, i)
    traces0 = kernels.trace_count()

    lat_on, lat_off = [], []
    for i in range(p50_iters):
        if i % 2 == 0:
            lat_off.append(one(ex_off, i))
            lat_on.append(one(ex_on, i))
        else:
            lat_on.append(one(ex_on, i))
            lat_off.append(one(ex_off, i))

    def window(ex):
        n = 0
        t0 = time.perf_counter()
        stop_at = t0 + window_s
        while time.perf_counter() < stop_at:
            ex.execute_context(ctxs[n % len(ctxs)])
            n += 1
        return n, time.perf_counter() - t0

    on_n = on_wall = off_n = off_wall = 0.0
    ratios = []
    for r in range(rounds):
        order = [(ex_off, "off"), (ex_on, "on")] if r % 2 == 0 \
            else [(ex_on, "on"), (ex_off, "off")]
        qps = {}
        for ex, tag in order:
            n, w = window(ex)
            qps[tag] = n / w
            if tag == "on":
                on_n += n
                on_wall += w
            else:
                off_n += n
                off_wall += w
        ratios.append(qps["on"] / max(qps["off"], 1e-9))

    reg = engine_on._dispatcher._metrics
    return {
        "rows_per_sec": round(on_n * total_docs / on_wall),
        "rows_per_sec_hostfold": round(off_n * total_docs / off_wall),
        "merge_speedup": round(stats.median(ratios), 2),
        "p50_ms": round(stats.median(lat_on), 2),
        "p50_ms_hostfold": round(stats.median(lat_off), 2),
        "retraces_steady": kernels.trace_count() - traces0,
        "merge_served": int(reg.meter("mesh_merge_served",
                                      labels=labels_on)),
    }


def mesh_main(smoke: bool = False, out_path: "str | None" = None):
    """--mesh [--smoke]: measured multi-chip scaling (ISSUE 19).

    Two legs, both through PARSED SQL on (segments x docs) mesh engines
    with the collective broker merge ON, each paired A/B against the
    host-IndexedTable-fold escape hatch
    (`pinot.server.mesh.collective.merge=false`) in alternating
    back-to-back windows — the BENCH_batching discipline:

      segments_axis — weak scaling over 1 -> 2 -> 4 -> 8 devices with
        FIXED PER-CHIP data (segment count scales with the mesh, so
        each chip always holds the same bytes). Headline: rows/sec/chip
        efficiency vs the 1-device run. On real accelerators each chip
        adds its own HBM bandwidth, so efficiency >= 0.8 is the gate.
        The CPU stand-in's 8 "devices" share the same few cores — total
        work grows with the mesh while compute does not, so per-chip
        efficiency is structurally ~1/n there; the CPU gate is instead
        structural: TOTAL rows/s must hold (>= 0.5x the 1-device rate,
        i.e. sharding+collectives overhead stays bounded), every curve
        point is measured, and the merged path actually served.
      doc_axis — ONE huge segment sharded across the `docs` axis (the
        segments axis cannot help a single segment; this is the leg
        that motivates the second mesh dimension). Measured against the
        same segment on a 1-device engine.

    Every leg asserts zero steady-state retraces and that the merged
    rows are BIT-IDENTICAL to the host fold (integer data: isum plane).
    Writes BENCH_mesh.json. --smoke shrinks device counts, data, and
    windows to fit tier-1 (structural assertions only)."""
    import shutil
    import tempfile

    import jax

    try:
        jax.config.update("jax_num_cpu_devices", 8)
    except RuntimeError:
        pass  # backend already initialized (in-process smoke run)
    if len(jax.devices()) < 8:
        raise SystemExit("mesh bench needs 8 (virtual) devices")

    from pinot_tpu.ops.engine import TpuOperatorExecutor
    from pinot_tpu.parallel.mesh import make_mesh
    from pinot_tpu.utils.config import PinotConfiguration

    counts = (1, 2) if smoke else (1, 2, 4, 8)
    segs_per_chip = 2 if smoke else 4
    docs = 1200 if smoke else 6000
    rounds = 2 if smoke else 4
    window_s = 0.5 if smoke else 2.5
    p50_iters = 8 if smoke else 30
    doc_leg_docs = 16_000 if smoke else 96_000
    doc_leg_axis = 2 if smoke else 8

    on_accelerator = jax.devices()[0].platform != "cpu"
    tmp = tempfile.mkdtemp(prefix="bench_mesh_")

    def engines(mesh, leg):
        labels_on = {"bench_leg": leg, "merge": "on"}
        eng_on = TpuOperatorExecutor(mesh=mesh, metrics_labels=labels_on)
        eng_off = TpuOperatorExecutor(
            mesh=mesh,
            config=PinotConfiguration(overrides={
                "pinot.server.mesh.collective.merge": False}),
            metrics_labels={"bench_leg": leg, "merge": "off"})
        return eng_on, eng_off, labels_on

    try:
        # -- leg 1: segments axis, weak scaling, fixed per-chip data --
        seg_points = []
        for n in counts:
            doc_axis = 2 if n % 2 == 0 else 1
            mesh = make_mesh(jax.devices()[:n], doc_axis=doc_axis)
            num_segments = segs_per_chip * n
            segs = _mesh_build_table(
                tmp, f"ssb_m{n}", num_segments, docs, seed=9000 + n)
            eng_on, eng_off, labels_on = engines(mesh, f"seg{n}")
            m = _mesh_measure(eng_on, eng_off, segs, f"ssb_m{n}",
                              num_segments * docs, rounds, window_s,
                              p50_iters, labels_on)
            m.update(devices=n, mesh={"segments": n // doc_axis,
                                      "docs": doc_axis},
                     segments=num_segments, docs_per_segment=docs)
            m["rows_per_sec_per_chip"] = round(m["rows_per_sec"] / n)
            seg_points.append(m)
        base_per_chip = seg_points[0]["rows_per_sec_per_chip"]
        for m in seg_points:
            m["efficiency"] = round(
                m["rows_per_sec_per_chip"] / max(base_per_chip, 1), 3)

        # -- leg 2: docs axis, ONE huge segment ------------------------
        big = _mesh_build_table(tmp, "ssb_big", 1, doc_leg_docs, seed=17)
        mesh_doc = make_mesh(jax.devices()[:doc_leg_axis],
                             doc_axis=doc_leg_axis)
        eng_on, eng_off, labels_on = engines(mesh_doc, "docleg")
        doc_leg = _mesh_measure(eng_on, eng_off, big, "ssb_big",
                                doc_leg_docs, rounds, window_s,
                                p50_iters, labels_on)
        mesh_one = make_mesh(jax.devices()[:1], doc_axis=1)
        eng1_on, eng1_off, labels1 = engines(mesh_one, "docleg1")
        doc_base = _mesh_measure(eng1_on, eng1_off, big, "ssb_big",
                                 doc_leg_docs, rounds, window_s,
                                 p50_iters, labels1)
        doc_leg.update(
            devices=doc_leg_axis,
            mesh={"segments": 1, "docs": doc_leg_axis},
            segments=1, docs_per_segment=doc_leg_docs,
            single_device_rows_per_sec=doc_base["rows_per_sec"],
            doc_shard_speedup=round(
                doc_leg["rows_per_sec"]
                / max(doc_base["rows_per_sec"], 1), 2))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    eff_floor = 0.8
    cpu_total_floor = 0.5
    out = {
        "metric": "mesh_weak_scaling_efficiency",
        "value": seg_points[-1]["efficiency"],
        "unit": "frac",
        "smoke": smoke,
        "platform": jax.devices()[0].platform,
        "segments_axis": seg_points,
        "doc_axis": doc_leg,
        "asserted": {
            "merged_rows_bit_identical_to_host_fold": True,
            "max_steady_retraces": 0,
            "min_efficiency_accelerator": eff_floor,
            "cpu_structural_floor":
                f"total rows/s at max mesh >= {cpu_total_floor}x the "
                f"1-device rate (shared-core stand-in: per-chip "
                f"efficiency is ~1/n there by construction)",
        },
    }
    if out_path is None:
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_mesh.json")
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))

    for m in seg_points + [doc_leg]:
        assert m["retraces_steady"] == 0, \
            f"steady-state retraces at {m.get('devices')}dev: " \
            f"{m['retraces_steady']}"
    for m in seg_points:
        if m["devices"] > 1:
            assert m["merge_served"] > 0, \
                f"merged path never served at {m['devices']}dev"
    if not smoke:
        if on_accelerator:
            for m in seg_points:
                assert m["efficiency"] >= eff_floor, \
                    f"weak-scaling efficiency {m['efficiency']} at " \
                    f"{m['devices']}dev under the {eff_floor} gate"
            assert doc_leg["doc_shard_speedup"] >= 2.0, \
                f"doc-axis leg speedup {doc_leg['doc_shard_speedup']}"
        else:
            top = seg_points[-1]
            assert top["rows_per_sec"] >= \
                cpu_total_floor * seg_points[0]["rows_per_sec"], \
                f"total throughput collapsed on the CPU stand-in: " \
                f"{top['rows_per_sec']} vs " \
                f"{seg_points[0]['rows_per_sec']} at 1 device"


def main():
    device = require_chip()
    peak_gbps = DEVICE_PEAKS[device["kind"]]["hbm_gbps"]
    os.makedirs(DATA_DIR, exist_ok=True)
    build_data()
    segments = load()
    total_rows = sum(s.num_docs for s in segments)

    from pinot_tpu.query.executor import QueryExecutor

    tpu_ex = QueryExecutor(segments, use_tpu=True)
    seq_lat, tpu_resp = time_sequential(tpu_ex, n_iters=10)
    pipe_dt = time_pipelined(tpu_ex, PIPELINE_DEPTH, n_iters=64)

    cpu8_ex = QueryExecutor(segments, use_tpu=False, max_threads=8)
    cpu8_lat, cpu_resp = time_sequential(cpu8_ex, n_iters=2, warmup=1)
    cpu1_ex = QueryExecutor(segments, use_tpu=False, max_threads=1)
    cpu1_lat, cpu1_resp = time_sequential(cpu1_ex, n_iters=2, warmup=1)

    # sanity: int SUM and COUNT are BIT-EXACT on the device path (isum
    # plane accumulation, ops/kernels.py _isum_slot)
    t, c = tpu_resp.rows[0], cpu_resp.rows[0]
    assert c[1] == t[1], f"count mismatch: {t} vs {c}"
    assert float(t[0]) == float(c[0]), f"sum mismatch: {t} vs {c}"
    assert cpu1_resp.rows[0][1] == c[1]

    rows_per_sec = total_rows / pipe_dt
    seq_rows_per_sec = total_rows / statistics.median(seq_lat)
    cpu8_rps = total_rows / statistics.median(cpu8_lat)
    cpu1_rps = total_rows / statistics.median(cpu1_lat)
    # this bench host has few cores (often 1) — threads can't speed numpy
    # up there, so the honest host baseline is whichever config is fastest
    host_best = max(cpu1_rps, cpu8_rps)
    dev_dt, staged_bytes = measure_device_kernel(tpu_ex, segments)
    if staged_bytes is None:
        staged_bytes = total_rows * BYTES_PER_ROW
    eff_gbps = staged_bytes / 1e9 / pipe_dt
    dev_gbps = staged_bytes / 1e9 / dev_dt if dev_dt else 0.0
    out = {
        "metric": "ssb_q1_scan_agg_rows_per_sec_per_chip",
        "value": round(rows_per_sec / device["count"]),
        "unit": "rows/s",
        "device": device,
        "vs_baseline": round(rows_per_sec / host_best, 2),
        "host_cpu_cores": os.cpu_count(),
        "pipeline_depth": PIPELINE_DEPTH,
        "p50_query_ms": round(statistics.median(seq_lat) * 1e3, 1),
        "p90_query_ms": round(
            sorted(seq_lat)[max(0, -(-len(seq_lat) * 9 // 10) - 1)] * 1e3, 1),
        "pipelined_query_ms": round(pipe_dt * 1e3, 2),
        "sequential_rows_per_sec": round(seq_rows_per_sec),
        "link_rt_ms": round(measure_link_rt_ms(), 1),
        "effective_gbps": round(eff_gbps, 1),
        "roofline_frac_v5e": round(eff_gbps / device["count"]
                                   / peak_gbps, 3),
        # device-only steady-state kernel (no link/host costs): with
        # cardinality-aware i8/i16 id staging the kernel reads ~40% fewer
        # bytes and is now VPU-COMPUTE-bound (mask evaluation + exact-sum
        # planes), not HBM-bound — GB/s understates the win; rows/s is
        # the honest headline
        "device_time_ms": round(dev_dt * 1e3, 2) if dev_dt else None,
        "device_rows_per_sec": round(total_rows / dev_dt) if dev_dt else None,
        "device_gbps": round(dev_gbps, 1),
        "staged_bytes_per_row": round(staged_bytes / total_rows, 1),
        "host_rows_per_sec_8t": round(cpu8_rps),
        "host_rows_per_sec_1t": round(cpu1_rps),
        "vs_host_1t": round(rows_per_sec / cpu1_rps, 2),
    }
    out.update(phase_breakdown(tpu_ex.tpu_engine, segments))
    print(json.dumps(out))


# ---------------------------------------------------------------------------
# --vector: ANN top-K as a batched device matmul (ISSUE 20)
# ---------------------------------------------------------------------------

def vector_main(smoke: bool = False, out_path: str = None):
    """--vector [--smoke]: the vector-similarity device leg's acceptance
    driver (ISSUE 20).

    Compute A/B — the same K-nearest query answered two ways: the HOST
    path walks the segments serially (per-segment VectorIndex.top_k:
    a [n, d] matmul + full lexsort each) and merges; the DEVICE path is
    ONE batched einsum + jax.lax.top_k over the staged [S, docs, d]
    block with a trivial cross-segment merge. Speedup gates at 2x on the
    CPU stand-in and 5x on a real accelerator (full run only).

    Exact parity — on a table below the IVF threshold the device leg
    must return doc ids BIT-IDENTICAL to VectorIndex.top_k (both sides
    break score ties toward the lower doc id by construction).

    Recall — on the IVF table, device answers (nprobe-pruned via the
    staged cell mask) score recall@K against the exact ground truth
    computed from the same stored vectors; gate 0.9.

    Coalesce — 8 clients loop fingerprint-equal ANN queries (same
    col/K/plan, DIFFERENT query vectors — the vectors ride params, not
    the plan) against one pipelined engine: they must batch into shared
    jit(vmap) launches (batch max > 1) with ZERO steady-state retraces.

    Writes BENCH_vector.json. --smoke shrinks sizes to tier-1 budget."""
    import contextlib
    import statistics as stats
    import tempfile
    import threading

    import jax

    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  TableConfig)
    from pinot_tpu.ops import dispatch as dispatch_mod
    from pinot_tpu.ops import kernels, vector_device
    from pinot_tpu.ops.engine import TpuOperatorExecutor
    from pinot_tpu.query.context import QueryContext
    from pinot_tpu.query.executor import QueryExecutor
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment
    from pinot_tpu.utils.config import PinotConfiguration

    docs_per_seg = 4200 if smoke else 8192   # >= IVF_THRESHOLD: coarse layer
    num_segments = 2 if smoke else 4
    d, k = 16, 10
    p50_iters = 5 if smoke else 25
    dev_iters = 8 if smoke else 25
    recall_queries = 8 if smoke else 50
    window_s = 0.8 if smoke else 2.5
    clients = 8

    tmp = tempfile.mkdtemp(prefix="bench_vector_")

    # clustered embeddings (a Gaussian mixture), not white noise: IVF
    # recall on uniform-random data is meaningless — in d=16 the true
    # neighbor set of a random point scatters across every cell. Real
    # embedding spaces cluster, which is exactly what the coarse layer
    # exploits; queries perturb stored vectors (the lookup workload).
    centers = np.random.default_rng(5999).normal(size=(32, d)) * 2.0

    def build_table(name, n_per_seg, nseg, seed):
        schema = Schema(name, [
            FieldSpec("id", DataType.INT, FieldType.DIMENSION),
            FieldSpec("vec", DataType.STRING, FieldType.DIMENSION)])
        tc = TableConfig(name=name)
        tc.indexing.vector_index_columns = ["vec"]
        creator = SegmentCreator(tc, schema)
        segs = []
        for i in range(nseg):
            rng = np.random.default_rng(seed + i)
            which = rng.integers(0, len(centers), n_per_seg)
            vecs = (centers[which]
                    + 0.3 * rng.normal(size=(n_per_seg, d))
                    ).astype(np.float32)
            out = os.path.join(tmp, f"{name}_{i}")
            creator.build({
                "id": np.arange(n_per_seg) + i * n_per_seg,
                "vec": np.array([json.dumps([float(x) for x in row])
                                 for row in vecs], object),
            }, out, f"{name}_{i}")
            segs.append(load_segment(out))
        return segs

    segs = build_table("emb", docs_per_seg, num_segments, 6000)
    segs_exact = build_table("embx", 1000, 1, 6100)
    indexes = [vector_device._index_of(s, "vec") for s in segs]
    assert all(ix is not None and ix.centroids is not None
               for ix in indexes), "IVF layer did not engage"

    labels = {"bench_leg": "vector"}
    eng = TpuOperatorExecutor(config=PinotConfiguration(),
                              metrics_labels=labels)
    reg = eng._dispatcher._metrics
    ex_dev = QueryExecutor(segs, use_tpu=True, engine=eng)
    ex_host = QueryExecutor(segs, use_tpu=False)

    rng = np.random.default_rng(9)

    def data_query():
        # perturb a stored (already-normalized) vector — the ANN lookup
        # workload: the query lives in the indexed embedding space
        ix = indexes[int(rng.integers(0, num_segments))]
        base = ix.vectors[int(rng.integers(0, len(ix.vectors)))]
        return (base + 0.05 * rng.normal(size=d)).astype(np.float32)

    def qsql(qv, table="emb", kk=k, lim=None):
        lit = json.dumps([float(x) for x in qv])
        sql = (f"SELECT id FROM {table} "
               f"WHERE vector_similarity(vec, '{lit}', {kk})")
        return sql if lim is None else f"{sql} LIMIT {lim}"

    # -- exact parity: device ids bit-identical to VectorIndex.top_k --
    ex_exact = QueryExecutor(segs_exact, use_tpu=True, engine=eng)
    ix_exact = vector_device._index_of(segs_exact[0], "vec")
    assert ix_exact.centroids is None  # exact path
    for _ in range(5):
        qv = rng.normal(size=d).astype(np.float32)
        r = ex_exact.execute(qsql(qv, table="embx"))
        assert not r.exceptions, r.exceptions
        got = sorted(row[0] for row in r.rows)
        want = sorted(int(i) for i in ix_exact.top_k(qv, k))
        assert got == want, (got, want)

    # -- IVF recall@k vs exact ground truth over the stored vectors.
    # vector_similarity is a per-segment FILTER (K matches per segment,
    # host contract) — ground truth is the union of per-segment exact
    # top-k, and the query's LIMIT spans the whole union.
    def exact_union(qv, kk):
        qn = (qv / max(np.linalg.norm(qv), 1e-30)).astype(np.float32)
        docs = set()
        for si, ix in enumerate(indexes):
            sc = ix.vectors @ qn
            order = np.lexsort((np.arange(len(sc)), -sc))
            docs |= {si * docs_per_seg + int(t) for t in order[:kk]}
        return docs

    recalls = []
    for _ in range(recall_queries):
        qv = data_query()
        r = ex_dev.execute(qsql(qv, lim=k * num_segments))
        assert not r.exceptions, r.exceptions
        got = {row[0] for row in r.rows}
        truth = exact_union(qv, k)
        recalls.append(len(got & truth) / len(truth))
    recall = float(np.mean(recalls))

    # -- compute A/B: serialized host walk vs one batched launch ------
    qv0 = data_query()
    prep = eng._prepare_vector(segs, QueryContext.from_sql(qsql(qv0)),
                               None)
    assert prep is not None, "device leg refused the bench query"
    launch = prep[2]
    guard = dispatch_mod._CPU_COLLECTIVE_LOCK if launch.collective \
        else contextlib.nullcontext()
    with guard:
        jax.block_until_ready(launch.call())  # warm
        t0 = time.perf_counter()
        for _ in range(dev_iters):
            jax.block_until_ready(launch.call())
        device_ms = (time.perf_counter() - t0) / dev_iters * 1e3

    def host_walk():
        cand = []
        for si, ix in enumerate(indexes):
            for t in ix.top_k(qv0, k):
                cand.append(si * docs_per_seg + int(t))
        return cand

    host_walk()  # warm any lazy state
    t0 = time.perf_counter()
    for _ in range(dev_iters):
        host_walk()
    host_ms = (time.perf_counter() - t0) / dev_iters * 1e3
    speedup = host_ms / max(device_ms, 1e-9)

    def p50(ex, sql):
        lat = []
        for _ in range(p50_iters):
            t0 = time.perf_counter()
            ex.execute(sql)
            lat.append((time.perf_counter() - t0) * 1e3)
        return stats.median(lat)

    p50_dev = p50(ex_dev, qsql(qv0))
    p50_host = p50(ex_host, qsql(qv0))

    # -- coalesce: 8 clients, same plan, different query vectors ------
    coal_q = [data_query() for _ in range(clients)]
    for qv in coal_q:          # params-cache every query vector
        ex_dev.execute(qsql(qv))
    b = 2
    while b <= dispatch_mod._pow2(clients):   # warm the batch buckets
        kern = launch.factory(b, False)
        with guard:
            jax.block_until_ready(kern(
                launch.cols, batch_params([launch.params] * b), launch.num_docs,
                D=launch.D, G=launch.G))
        b *= 2
    traces0 = kernels.trace_count()
    batch_t0 = reg.timer("dispatch_batch_size", labels=labels)
    count0, max0 = batch_t0.count, batch_t0.max_ms
    stop_at = time.perf_counter() + window_s
    done = [0] * clients

    def client(ci):
        j = 0
        while time.perf_counter() < stop_at:
            ex_dev.execute(qsql(coal_q[(ci + j) % clients]))
            done[ci] += 1
            j += 1

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    retraces = kernels.trace_count() - traces0
    batch_t = reg.timer("dispatch_batch_size", labels=labels)
    platform = jax.devices()[0].platform
    gate = 2.0 if platform == "cpu" else 5.0
    out = {
        "metric": "vector_device_vs_host_speedup",
        "value": round(speedup, 2),
        "unit": "x",
        "smoke": smoke,
        "platform": platform,
        "docs": docs_per_seg * num_segments,
        "dim": d, "k": k,
        "device_ms": round(device_ms, 3),
        "host_walk_ms": round(host_ms, 3),
        "p50_device_ms": round(p50_dev, 2),
        "p50_host_ms": round(p50_host, 2),
        "recall_at_k": round(recall, 3),
        "vector_served": int(reg.meter("vector_served", labels=labels)),
        "coalesce": {
            "clients": clients,
            "queries_completed": int(sum(done)),
            "qps": round(sum(done) / wall, 2),
            "batch_launches": batch_t.count - count0,
            "batch_size_max": max(batch_t.max_ms, max0),
            "retraces_steady": retraces,
        },
        "asserted": {
            "exact_parity": "device doc ids == VectorIndex.top_k",
            "min_recall_at_k": 0.9,
            "max_steady_retraces": 0,
            "min_batch_size": 2,
            "full_run_only": f"device >= {gate}x host "
                             f"({platform} gate)",
        },
    }
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_vector.json")
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))
    assert recall >= 0.9, f"IVF recall@{k} = {recall:.3f} < 0.9"
    assert retraces == 0, f"steady-state retraces: {retraces}"
    assert out["coalesce"]["batch_size_max"] >= 2, \
        "fingerprint-equal ANN queries never coalesced"
    if not smoke:
        assert speedup >= gate, \
            f"device {speedup:.2f}x host, below the {gate}x {platform} gate"


# ---------------------------------------------------------------------------
# --timeseries: dashboards as device group-bys (ISSUE 20)
# ---------------------------------------------------------------------------

def timeseries_main(smoke: bool = False, out_path: str = None):
    """--timeseries [--smoke]: the device time-bucket leg's acceptance
    driver (ISSUE 20).

    A/B — the same simpleql dashboard query served (a) through the
    device group-by kernel with floor((t-start)/step) FUSED into the
    group key (pinot.server.timeseries.bucket.enabled=true) and (b) by
    the host expression-column leaf (the pre-ISSUE-20 path, which the
    device scan leg can't admit). Full run asserts the fused leg wins
    end-to-end. A sliding-refresh loop (start advances every query, the
    dashboard steady state) must cause ZERO retraces: start/step/count
    ride params, only count_pad is in the plan.

    Selfmetrics — the PR-14 dogfood dashboards run end-to-end through
    the device leg (query_history(use_tpu=True)), making metrics
    history a third device workload beside queries and log search.

    Writes BENCH_timeseries.json. --smoke shrinks to tier-1 budget."""
    import statistics as stats
    import tempfile

    import jax

    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  TableConfig)
    from pinot_tpu.ops import kernels
    from pinot_tpu.ops.engine import TpuOperatorExecutor
    from pinot_tpu.query.executor import QueryExecutor
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment
    from pinot_tpu.timeseries.engine import query as ts_query
    from pinot_tpu.utils.config import PinotConfiguration

    docs_per_seg = 10_000 if smoke else 100_000
    num_segments = 2 if smoke else 4
    n_tags = 8
    # a 30-point dashboard panel: 32-pad buckets x 8 tags = 256 padded
    # groups — inside the kernel's one-hot/MXU scatter regime on both
    # backends (the one-hot cost is linear in padded groups, which is
    # what the CPU stand-in pays; accelerators eat it on the MXU)
    buckets = 30
    step = 20
    t0_, t1 = 100_000, 100_000 + buckets * step
    p50_iters = 5 if smoke else 20
    slide_iters = 6 if smoke else 20

    tmp = tempfile.mkdtemp(prefix="bench_ts_")
    schema = Schema("metrics", [
        FieldSpec("ts", DataType.LONG, FieldType.DIMENSION),
        FieldSpec("host", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("value", DataType.DOUBLE, FieldType.METRIC)])
    creator = SegmentCreator(TableConfig(name="metrics"), schema)
    segs = []
    for i in range(num_segments):
        rng = np.random.default_rng(7000 + i)
        out_dir = os.path.join(tmp, f"m_{i}")
        creator.build({
            "ts": rng.integers(t0_, t1, docs_per_seg),
            "host": np.array([f"h{v}" for v in
                              rng.integers(0, n_tags, docs_per_seg)],
                             object),
            "value": rng.normal(size=docs_per_seg),
        }, out_dir, f"m_{i}")
        segs.append(load_segment(out_dir))

    labels = {"bench_leg": "ts"}
    eng_dev = TpuOperatorExecutor(config=PinotConfiguration(),
                                  metrics_labels=labels)
    eng_off = TpuOperatorExecutor(
        config=PinotConfiguration(overrides={
            "pinot.server.timeseries.bucket.enabled": False}),
        metrics_labels={"bench_leg": "ts_off"})
    reg = eng_dev._dispatcher._metrics
    ex_dev = QueryExecutor(segs, use_tpu=True, engine=eng_dev)
    ex_off = QueryExecutor(segs, use_tpu=True, engine=eng_off)

    def dash(start):
        return (f"fetch(metrics, value, ts, {start}, {t1}, {step}) "
                f"| groupby(host) | sum(host) | keep_last_value()")

    # -- parity: fused bucket leg == expression-column leaf -----------
    served0 = reg.meter("timeseries_leaf_device", labels=labels)
    bd = ts_query(dash(t0_), ex_dev)
    bh = ts_query(dash(t0_), ex_off)
    assert reg.meter("timeseries_leaf_device", labels=labels) > served0, \
        "bucket group-by did not serve through the device leg"
    dd = {s.tag_key(): s.values for s in bd.series}
    hh = {s.tag_key(): s.values for s in bh.series}
    assert set(dd) == set(hh), "series sets diverge"
    for key in dd:
        # f32 device sums of SIGNED values: cancellation makes relative
        # error meaningless near zero, hence the atol floor
        np.testing.assert_allclose(
            dd[key], hh[key], rtol=1e-3, atol=1e-3, equal_nan=True)

    # -- sliding refresh: params move, the kernel must not retrace ----
    traces0 = kernels.trace_count()
    for j in range(slide_iters):
        ts_query(dash(t0_ + (j % 4) * step), ex_dev)
    slide_retraces = kernels.trace_count() - traces0

    def p50(ex):
        lat = []
        for _ in range(p50_iters):
            t0 = time.perf_counter()
            ts_query(dash(t0_), ex)
            lat.append((time.perf_counter() - t0) * 1e3)
        return stats.median(lat)

    p50_dev = p50(ex_dev)
    p50_off = p50(ex_off)

    # -- selfmetrics dashboards through the device leg ----------------
    from pinot_tpu.health.history import MetricsHistory, MetricsSampler
    from pinot_tpu.health.selfmetrics import query_history
    from pinot_tpu.utils.metrics import MetricsRegistry
    role = "bench-ts"
    sreg = MetricsRegistry(role)
    hist = MetricsHistory(64)
    sampler = MetricsSampler(role, history=hist, registry=sreg)
    base = 1_000_000
    for i in range(20):
        sreg.add_meter("queries", 3)
        s = sampler.sample_once()
        s["ts"] = base + i
    served0 = reg.meter("timeseries_leaf_device", labels=labels)
    block = query_history(
        f"fetch(selfmetrics, value, ts, {base}, {base + 20}, 1) "
        f"| where(family = 'queries') | sum() | rate()",
        role=role, history=hist, use_tpu=True, engine=eng_dev)
    assert block.series and np.allclose(block.series[0].values[1:], 3.0)
    selfm_device = reg.meter("timeseries_leaf_device",
                             labels=labels) > served0

    platform = jax.devices()[0].platform
    leaf_gate = 1.1 if platform == "cpu" else 2.0
    out = {
        "metric": "timeseries_device_vs_expression_leaf_p50",
        "value": round(p50_off / max(p50_dev, 1e-9), 2),
        "unit": "x",
        "smoke": smoke,
        "platform": platform,
        "docs": docs_per_seg * num_segments,
        "buckets": buckets, "tags": n_tags,
        "p50_device_ms": round(p50_dev, 2),
        "p50_expression_leaf_ms": round(p50_off, 2),
        "slide_retraces": slide_retraces,
        "selfmetrics_device": bool(selfm_device),
        "timeseries_leaf_device": int(
            reg.meter("timeseries_leaf_device", labels=labels)),
        "asserted": {
            "parity": "fused bucket leg == expression leaf "
                      "(1e-3 rel, 1e-3 abs — f32 signed sums)",
            "max_slide_retraces": 0,
            "selfmetrics_device": True,
            "full_run_only": f"device >= {leaf_gate}x expression leaf "
                             f"({platform} gate)",
        },
    }
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_timeseries.json")
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))
    assert slide_retraces == 0, \
        f"sliding refresh retraced {slide_retraces}x"
    assert selfm_device, \
        "selfmetrics dashboard bypassed the device bucket leg"
    if not smoke:
        ratio = p50_off / max(p50_dev, 1e-9)
        assert ratio >= leaf_gate, \
            f"device {p50_dev:.2f}ms only {ratio:.2f}x the expression " \
            f"leaf ({p50_off:.2f}ms), below the {leaf_gate}x " \
            f"{platform} gate"


if __name__ == "__main__":
    if "--deadline-overhead" in sys.argv:
        deadline_overhead_main()
    elif "--concurrency" in sys.argv:
        concurrency_main(smoke="--smoke" in sys.argv)
    elif "--mse" in sys.argv:
        mse_main(smoke="--smoke" in sys.argv)
    elif "--groups" in sys.argv:
        groups_main(smoke="--smoke" in sys.argv)
    elif "--batching" in sys.argv:
        batching_main(smoke="--smoke" in sys.argv)
    elif "--startree" in sys.argv:
        startree_main(smoke="--smoke" in sys.argv)
    elif "--ingest" in sys.argv:
        ingest_main(smoke="--smoke" in sys.argv)
    elif "--health" in sys.argv:
        health_main(smoke="--smoke" in sys.argv)
    elif "--overload" in sys.argv:
        overload_main(smoke="--smoke" in sys.argv)
    elif "--logs" in sys.argv:
        logs_main(smoke="--smoke" in sys.argv)
    elif "--rebalance" in sys.argv:
        rebalance_main(smoke="--smoke" in sys.argv)
    elif "--mesh" in sys.argv:
        mesh_main(smoke="--smoke" in sys.argv)
    elif "--vector" in sys.argv:
        vector_main(smoke="--smoke" in sys.argv)
    elif "--timeseries" in sys.argv:
        timeseries_main(smoke="--smoke" in sys.argv)
    else:
        main()
