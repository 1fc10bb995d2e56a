"""All five BASELINE.md benchmark configs, host vs device, one JSON file.

Writes BENCH_extra.json:
  1 baseball_sum        — baseballStats-shaped full-scan SELECT SUM(runs)
                          (schema from the reference's
                          examples/batch/baseballStats/baseballStats_schema
                          .json; raw CSV is quickstart-downloaded and not
                          in-tree, so rows are synthesized to shape)
  2 ssb_q1              — range-filter + SUM (same data/query as bench.py)
  3 ssb_groupby         — SSB Q2.x-shaped GROUP BY over low-card dims
  4 distinct_percentile — NYC-taxi-shaped DISTINCTCOUNTHLL + PERCENTILE
                          TDIGEST on a high-cardinality column (device
                          sketch path: HLL register max-scatter over hashed
                          split planes + histogram partials for the digest)
  5 startree            — pre-aggregated SSB group-by via the star-tree
                          path vs the same query full-scan

Each entry: rows, device p50 ms + rows/s (pipelined where the engine
overlaps round trips), host-numpy p50 ms + rows/s, speedup. Segments
build once under ./bench_data_extra (git-ignored). The file names the
device it ran on; without a TPU the run refuses (bench.require_chip), and
a config that fails ends the run with its error, not an "error" entry.
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

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "bench_data_extra")
PIPELINE_DEPTH = 8


def _build(name, schema_fields, cols_fn, num_segments, docs_per_segment,
           no_dict=(), star_tree=None):
    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  TableConfig, TableType)
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment

    schema = Schema(name, [FieldSpec(n, getattr(DataType, t),
                                     FieldType.METRIC if m
                                     else FieldType.DIMENSION)
                           for n, t, m in schema_fields])
    tc = TableConfig(name, TableType.OFFLINE)
    tc.indexing.no_dictionary_columns = list(no_dict)
    tc.indexing.compression = "PASS_THROUGH"
    if star_tree is not None:
        tc.indexing.star_tree_configs = [star_tree]
    creator = SegmentCreator(tc, schema)
    segs = []
    for i in range(num_segments):
        out = os.path.join(DATA, f"{name}_{i}")
        if not os.path.exists(os.path.join(out, "metadata.json")):
            rng = np.random.default_rng(7000 + i)
            creator.build(cols_fn(rng, docs_per_segment), out, f"{name}_{i}")
        segs.append(load_segment(out))
    return segs


def _measure(segments, sql, check=None, pipeline=True, iters=6):
    from pinot_tpu.query.executor import QueryExecutor
    total = sum(s.num_docs for s in segments)

    tpu = QueryExecutor(segments, use_tpu=True)
    resp = tpu.execute(sql)  # warmup: stage + compile
    assert not resp.exceptions, resp.exceptions
    lat = []
    for _ in range(iters):
        t0 = time.perf_counter()
        resp = tpu.execute(sql)
        lat.append(time.perf_counter() - t0)
    dev_p50 = statistics.median(lat)
    dev_rps = total / dev_p50
    if pipeline:
        with ThreadPoolExecutor(PIPELINE_DEPTH) as pool:
            list(pool.map(lambda _: tpu.execute(sql), range(PIPELINE_DEPTH)))
            n = PIPELINE_DEPTH * 4
            t0 = time.perf_counter()
            list(pool.map(lambda _: tpu.execute(sql), range(n)))
            piped = (time.perf_counter() - t0) / n
        dev_rps = total / piped

    cpu = QueryExecutor(segments, use_tpu=False, max_threads=8)
    cresp = cpu.execute(sql)
    lat = []
    for _ in range(max(2, iters // 3)):
        t0 = time.perf_counter()
        cresp = cpu.execute(sql)
        lat.append(time.perf_counter() - t0)
    host_p50 = statistics.median(lat)

    if check is not None:
        check(resp, cresp)
    used_device = len(tpu.tpu_engine._block_cache) > 0
    return {
        "rows": total,
        "device_p50_ms": round(dev_p50 * 1e3, 1),
        "device_rows_per_sec": round(dev_rps),
        "host_p50_ms": round(host_p50 * 1e3, 1),
        "host_rows_per_sec": round(total / host_p50),
        "speedup": round(dev_rps / (total / host_p50), 2),
        "device_engaged": used_device,
    }


def _approx_equal(a, b, rel=2e-3):
    fa, fb = float(a), float(b)
    return abs(fa - fb) <= rel * max(1.0, abs(fb))


def config1_baseball():
    fields = [("playerID", "STRING", False), ("yearID", "INT", False),
              ("teamID", "STRING", False), ("league", "STRING", False),
              ("runs", "INT", True), ("hits", "INT", True),
              ("homeRuns", "INT", True)]

    def cols(rng, n):
        return {
            "playerID": np.array([f"p{i}" for i in
                                  rng.integers(0, 20000, n)], object),
            "yearID": rng.integers(1871, 2014, n).astype(np.int32),
            "teamID": np.array([f"T{i}" for i in rng.integers(0, 150, n)],
                               object),
            "league": np.array([("NL", "AL")[i] for i in
                                rng.integers(0, 2, n)], object),
            "runs": rng.integers(0, 180, n).astype(np.int32),
            "hits": rng.integers(0, 260, n).astype(np.int32),
            "homeRuns": rng.integers(0, 74, n).astype(np.int32),
        }

    segs = _build("baseball", fields, cols, 4, 2_500_000)

    def check(a, b):
        assert a.result_table.rows[0][1] == b.result_table.rows[0][1]
        assert _approx_equal(a.result_table.rows[0][0],
                             b.result_table.rows[0][0])

    return _measure(segs, "SELECT SUM(runs), COUNT(*) FROM baseball", check)


def config2_ssb_q1():
    import bench
    os.makedirs(bench.DATA_DIR, exist_ok=True)
    bench.build_data()
    segs = bench.load()

    def check(a, b):
        assert a.result_table.rows[0][1] == b.result_table.rows[0][1]
        assert _approx_equal(a.result_table.rows[0][0],
                             b.result_table.rows[0][0])

    return _measure(segs, bench.QUERY, check)


def _ssb_flat_fields():
    return [("lo_orderdate", "INT", False), ("lo_discount", "INT", False),
            ("lo_quantity", "INT", False), ("d_year", "INT", False),
            ("p_category", "STRING", False), ("s_region", "STRING", False),
            ("lo_revenue", "INT", True)]


def _ssb_flat_cols(rng, n):
    return {
        "lo_orderdate": rng.integers(19920101, 19981230, n).astype(np.int32),
        "lo_discount": rng.integers(0, 11, n).astype(np.int32),
        "lo_quantity": rng.integers(1, 51, n).astype(np.int32),
        "d_year": rng.integers(1992, 1999, n).astype(np.int32),
        "p_category": np.array([f"MFGR#{i}" for i in
                                rng.integers(1, 6, n)], object),
        "s_region": np.array([("AMERICA", "ASIA", "EUROPE", "AFRICA")[i]
                              for i in rng.integers(0, 4, n)], object),
        "lo_revenue": rng.integers(100, 1_000_000, n).astype(np.int32),
    }


def config3_ssb_groupby():
    segs = _build("ssbgb", _ssb_flat_fields(), _ssb_flat_cols, 8, 4_000_000,
                  no_dict=("lo_revenue",))
    sql = ("SELECT d_year, p_category, SUM(lo_revenue) FROM ssbgb "
           "WHERE s_region = 'AMERICA' GROUP BY d_year, p_category "
           "ORDER BY d_year, p_category LIMIT 100")

    def check(a, b):
        ra = [(r[0], r[1]) for r in a.result_table.rows]
        rb = [(r[0], r[1]) for r in b.result_table.rows]
        assert ra == rb
        for x, y in zip(a.result_table.rows, b.result_table.rows):
            assert _approx_equal(x[2], y[2])

    return _measure(segs, sql, check)


def config4_distinct_percentile():
    fields = [("trip_id", "LONG", False), ("fare", "DOUBLE", True)]

    def cols(rng, n):
        return {
            "trip_id": rng.integers(0, 1 << 40, n).astype(np.int64),
            "fare": np.round(rng.gamma(2.5, 8.0, n), 2),
        }

    segs = _build("taxi", fields, cols, 4, 2_000_000,
                  no_dict=("trip_id", "fare"))
    sql = ("SELECT DISTINCTCOUNTHLL(trip_id), "
           "PERCENTILETDIGEST95(fare) FROM taxi")

    def check(a, b):
        # device HLL registers are bit-identical to the host sketch; the
        # device tdigest feeds histogram partials (within sketch error)
        assert a.result_table.rows[0][0] == b.result_table.rows[0][0]
        assert _approx_equal(a.result_table.rows[0][1],
                             b.result_table.rows[0][1], rel=0.02)

    return _measure(segs, sql, check, iters=3)


def config5_startree():
    from pinot_tpu.models.table_config import StarTreeIndexConfig
    st = StarTreeIndexConfig(
        dimensions_split_order=["d_year", "p_category"],
        function_column_pairs=["SUM__lo_revenue", "COUNT__*"],
        max_leaf_records=1000)
    segs = _build("ssbst", _ssb_flat_fields(), _ssb_flat_cols, 2, 2_000_000,
                  no_dict=(), star_tree=st)
    sql = ("SELECT d_year, SUM(lo_revenue) FROM ssbst "
           "GROUP BY d_year ORDER BY d_year LIMIT 100")

    from pinot_tpu.query.executor import QueryExecutor
    total = sum(s.num_docs for s in segs)
    cpu = QueryExecutor(segs, use_tpu=False, max_threads=8)
    resp = cpu.execute(sql)  # star-tree path (pre-aggregated traversal)
    t0 = time.perf_counter()
    resp = cpu.execute(sql)
    st_ms = (time.perf_counter() - t0) * 1e3
    # full-scan reference: same query with star-tree disabled via option
    sql_noopt = sql + " OPTION(useStarTree=false)"
    full = cpu.execute(sql_noopt)
    t0 = time.perf_counter()
    full = cpu.execute(sql_noopt)
    full_ms = (time.perf_counter() - t0) * 1e3
    assert [r[0] for r in resp.result_table.rows] == \
        [r[0] for r in full.result_table.rows]
    for x, y in zip(resp.result_table.rows, full.result_table.rows):
        assert _approx_equal(x[1], y[1])
    return {
        "rows": total,
        "startree_p50_ms": round(st_ms, 1),
        "fullscan_p50_ms": round(full_ms, 1),
        "speedup_vs_fullscan": round(full_ms / st_ms, 2),
        "docs_scanned_startree": resp.stats.num_docs_scanned,
        "docs_scanned_fullscan": full.stats.num_docs_scanned,
    }


def main():
    import bench
    device = bench.require_chip()
    os.makedirs(DATA, exist_ok=True)
    out = {"device": device}
    for key, fn in [("baseball_sum", config1_baseball),
                    ("ssb_q1", config2_ssb_q1),
                    ("ssb_groupby", config3_ssb_groupby),
                    ("distinct_percentile", config4_distinct_percentile),
                    ("startree", config5_startree)]:
        t0 = time.time()
        out[key] = fn()
        out[key]["measure_s"] = round(time.time() - t0, 1)
        print(f"{key}: {json.dumps(out[key])}", file=sys.stderr)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_extra.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"metric": "bench_extra_configs",
                      "value": len(out) - 1, "unit": "configs",
                      "vs_baseline": 1.0, "device": device}))


if __name__ == "__main__":
    main()
