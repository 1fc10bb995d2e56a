#!/usr/bin/env python3
"""chip_smoke.py — the served query path, end to end, on the chip.

  client -> broker HTTP -> framed-TCP server -> QueryExecutor ->
  TpuOperatorExecutor -> device -> broker reduce

This parent never imports jax: a process that touches JAX holds the chip,
and one chip means one server process. It starts StartController, ONE
`StartServer --tpu` and StartBroker through `python -m
pinot_tpu.tools.admin`, builds bench.py's table (SSB flat lineorder, 16
segments x 8,000,000 docs = 128M rows) from --seed, pushes it through the
controller's deep store (AddTable, UploadSegment) and asks by POST
/query/sql. The expected answers are computed here with plain numpy over
the generated columns, segment by segment as they are made.

Every timed query carries OPTION(trace=true, skipCache=true): it must show
a DeviceDispatch span that did not fall back, no exceptions, every server
responding, and the reference's answer. COUNT, MIN/MAX, HLL and ungrouped
integer SUM are bit-equal; grouped SUM (f32 on the device) and the
t-digest percentile stay within GROUPED_SUM_RTOL / TDIGEST_RTOL. Warm
repeats must upload nothing and compile nothing. Then the server is
restarted and asked everything once more: the persistent compile cache
must gain no entry.

The server runs with JAX_PLATFORMS=tpu, so without a chip it fails at
start-up and so does this script. --cpu-rehearsal (tiny, XLA:CPU) is for
debugging the script itself: it says so, reports "ok": false and exits
with REHEARSAL_EXIT, never 0.

Last stdout line: one JSON object with exactly these keys, the device as
the server process reported it:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
The line before it, `chip_smoke: report {...}`, is one JSON object with
everything the run observed. A run that fails prints neither.
"""
from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import shutil
import signal
import socket
import statistics
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

FULL_SEGMENTS = 16
FULL_DOCS = 8_000_000
REHEARSAL_EXIT = 10
#: grouped SUM accumulates in f32 on the device (x64 off): each segment's
#: group sum is a carry over ~1953 chunk adds, a random walk of about
#: 0.29 * sqrt(1953) * 2^-23 = 1.5e-6 relative at one sigma, shrinking as
#: segments average out. XLA:CPU measured 8.1e-7 at 2 x 8M docs and 5.2e-7
#: at 3 x 8M (PERF.md bring-up); inputs rounded to bf16 would miss by 1e-3.
GROUPED_SUM_RTOL = 4e-6
#: t-digest over an 8192-bucket device histogram vs the exact quantile
TDIGEST_RTOL = 2e-3
#: HLL (log2m=12) standard error is 1.04/sqrt(4096) = 1.6%; this is 4 sigma
HLL_RTOL = 0.065
QUERY_OPTIONS = "OPTION(trace=true, skipCache=true, timeoutMs=900000)"
FALLBACK_OUTCOMES = ("hostFallback", "scanFallback")

SSB_DATES = np.array([y * 10000 + m * 100 + d
                      for y in range(1992, 1999)
                      for m in range(1, 13) for d in range(1, 29)],
                     dtype=np.int32)
IN_DATES = (19920101, 19940215, 19950707, 19961111, 19981228)
IN_DISCOUNTS = (1, 3, 5)
PRICE_LO, PRICE_HI = 90_000, 10_000_000
HIST_SHIFT = 10  # reference histogram bin = 1024 price units


class SmokeFailure(Exception):
    pass


# ---------------------------------------------------------------------------
# the main table: bench.py's SSB flat lineorder, data from the seed
# ---------------------------------------------------------------------------
def ssb_table():
    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  TableConfig, TableType)
    schema = Schema("ssb", [
        FieldSpec("lo_orderdate", DataType.INT, FieldType.DIMENSION),
        FieldSpec("lo_discount", DataType.INT, FieldType.DIMENSION),
        FieldSpec("lo_quantity", DataType.INT, FieldType.DIMENSION),
        FieldSpec("lo_extendedprice", DataType.INT, FieldType.METRIC),
    ])
    tc = TableConfig("ssb", TableType.OFFLINE)
    tc.indexing.no_dictionary_columns = ["lo_extendedprice"]
    tc.indexing.compression = "PASS_THROUGH"
    return tc, schema


def ssb_columns(seed: int, i: int, docs: int) -> dict:
    rng = np.random.default_rng([seed, i])
    return {
        "lo_orderdate": SSB_DATES[rng.integers(0, len(SSB_DATES), docs)],
        "lo_discount": rng.integers(0, 11, docs).astype(np.int32),
        "lo_quantity": rng.integers(1, 51, docs).astype(np.int32),
        "lo_extendedprice": rng.integers(PRICE_LO, PRICE_HI,
                                         docs).astype(np.int32),
    }


def ssb_reference(cols: dict) -> dict:
    """One segment's share of every expected answer — plain numpy."""
    from pinot_tpu.query.aggregation.sketches import HyperLogLog
    date, disc = cols["lo_orderdate"], cols["lo_discount"]
    qty, price32 = cols["lo_quantity"], cols["lo_extendedprice"]
    price = price32.astype(np.int64)
    ref = {}
    m = ((date >= 19940101) & (date <= 19940131) & (disc >= 4) & (disc <= 6)
         & (qty >= 26) & (qty <= 35))
    ref["q1"] = (int((price[m] * disc[m]).sum()), int(m.sum()))
    m = qty < 25
    ref["gb_small"] = (
        np.bincount(disc[m], minlength=11).astype(np.int64),
        np.bincount(disc[m], weights=price[m], minlength=11))
    gid = np.searchsorted(SSB_DATES, date)
    counts = np.bincount(gid, minlength=len(SSB_DATES))
    by_group = price[np.argsort(gid, kind="stable")]
    # reduceat over the non-empty groups only: their starts delimit them
    live = counts > 0
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])[live]
    mn = np.full(len(SSB_DATES), PRICE_HI, dtype=np.int64)
    mx = np.full(len(SSB_DATES), -1, dtype=np.int64)
    mn[live] = np.minimum.reduceat(by_group, starts)
    mx[live] = np.maximum.reduceat(by_group, starts)
    ref["gb_big"] = (counts.astype(np.int64), mn, mx)
    hll = HyperLogLog(12)
    hll.add_array(price32)
    ref["hll_registers"] = hll.registers
    present = np.zeros(PRICE_HI, dtype=bool)
    present[price32] = True
    ref["present"] = np.packbits(present)
    ref["hist"] = np.bincount(price32 >> HIST_SHIFT,
                              minlength=(PRICE_HI >> HIST_SHIFT) + 1)
    ref["topn"] = np.sort(price32[qty == 50])[::-1][:10]
    m = np.isin(date, IN_DATES) & np.isin(disc, IN_DISCOUNTS)
    ref["in_list"] = (int(m.sum()), int(qty[m].sum()))
    return ref


def segment_job(job):
    """Pool worker: make segment i, compute its reference share, build
    it, push it through the controller's deep store."""
    seed, i, docs, work, coordinator = job
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.tools import admin
    cols = ssb_columns(seed, i, docs)
    ref = ssb_reference(cols)
    tc, schema = ssb_table()
    seg_dir = os.path.join(work, "build", f"ssb_{i}")
    SegmentCreator(tc, schema).build(cols, seg_dir, f"ssb_{i}")
    seg_bytes = sum(os.path.getsize(os.path.join(seg_dir, f))
                    for f in os.listdir(seg_dir))
    with open(os.devnull, "w") as quiet:
        stdout, sys.stdout = sys.stdout, quiet
        try:
            rc = admin.main(["UploadSegment", "--coordinator", coordinator,
                             "--table", "ssb", "--segment-dir", seg_dir])
        finally:
            sys.stdout = stdout
    if rc != 0:
        raise SmokeFailure(f"UploadSegment ssb_{i} returned {rc}")
    shutil.rmtree(seg_dir)  # the deep store holds it now
    return i, ref, seg_bytes, "jax" in sys.modules


def merge_reference(refs: list) -> dict:
    """Fold per-segment shares into the expected rows of each query."""
    from pinot_tpu.query.aggregation.sketches import HyperLogLog
    out = {}
    out["scan_sum"] = [[float(sum(r["q1"][0] for r in refs)),
                        sum(r["q1"][1] for r in refs)]]
    cnt = sum(r["gb_small"][0] for r in refs)
    tot = sum(r["gb_small"][1] for r in refs)
    out["groupby_onehot"] = [[d, int(cnt[d]), float(tot[d])]
                             for d in range(11)]
    cnt = sum(r["gb_big"][0] for r in refs)
    mn = np.minimum.reduce([r["gb_big"][1] for r in refs])
    mx = np.maximum.reduce([r["gb_big"][2] for r in refs])
    out["groupby_scatter"] = [
        [int(SSB_DATES[g]), int(cnt[g]), float(mn[g]), float(mx[g])]
        for g in range(len(SSB_DATES)) if cnt[g]]
    registers = np.maximum.reduce([r["hll_registers"] for r in refs])
    out["hll"] = [[HyperLogLog.from_registers(registers, 12).cardinality()]]
    present = np.bitwise_or.reduce([r["present"] for r in refs])
    out["hll_exact_distinct"] = int(np.unpackbits(present).sum())
    hist = sum(r["hist"] for r in refs)
    # the 95th percentile lies in this reference bin (1024 wide)
    rank = 0.95 * hist.sum()
    b = int(np.searchsorted(np.cumsum(hist), rank))
    out["tdigest_bin"] = (b << HIST_SHIFT, (b + 1) << HIST_SHIFT)
    top = np.sort(np.concatenate([r["topn"] for r in refs]))[::-1][:10]
    out["topn"] = [[int(v), 50] for v in top]
    out["in_list"] = [[sum(r["in_list"][0] for r in refs),
                       float(sum(r["in_list"][1] for r in refs))]]
    return out


MAIN_QUERIES = [
    # (name, kernel family, SQL)
    ("scan_sum", "filter + exact-sum planes (SSB Q1.1)",
     "SELECT SUM(lo_extendedprice * lo_discount), COUNT(*) FROM ssb "
     "WHERE lo_orderdate BETWEEN 19940101 AND 19940131 "
     "AND lo_discount BETWEEN 4 AND 6 AND lo_quantity BETWEEN 26 AND 35"),
    ("groupby_onehot", "group-by G=11, one-hot lax.scan",
     "SELECT lo_discount, COUNT(*), SUM(lo_extendedprice) FROM ssb "
     "WHERE lo_quantity < 25 GROUP BY lo_discount "
     "ORDER BY lo_discount LIMIT 20"),
    ("groupby_scatter",
     "group-by G=2352: COUNT factored one-hot, MIN/MAX scatter",
     "SELECT lo_orderdate, COUNT(*), MIN(lo_extendedprice), "
     "MAX(lo_extendedprice) FROM ssb GROUP BY lo_orderdate "
     "ORDER BY lo_orderdate LIMIT 3000"),
    ("hll", "HLL max-scatter over split planes",
     "SELECT DISTINCTCOUNTHLL(lo_extendedprice) FROM ssb"),
    ("tdigest", "histogram slots (8192 buckets)",
     "SELECT PERCENTILETDIGEST95(lo_extendedprice) FROM ssb"),
    ("topn", "ORDER BY LIMIT, lax.top_k",
     "SELECT lo_extendedprice, lo_quantity FROM ssb WHERE lo_quantity = 50 "
     "ORDER BY lo_extendedprice DESC LIMIT 10"),
    ("in_list", "IN leaves, LUT gather on i16/i8 ids",
     "SELECT COUNT(*), SUM(lo_quantity) FROM ssb "
     f"WHERE lo_orderdate IN ({', '.join(map(str, IN_DATES))}) "
     f"AND lo_discount IN ({', '.join(map(str, IN_DISCOUNTS))})"),
]


#: the grouped kernel a main query's launch has to meet (`groupPath`)
GROUP_PATHS = {"groupby_onehot": "onehot", "groupby_scatter": "onehot2"}


def group_attrs(resp: dict) -> dict:
    """Which grouped kernel a query's launches ran, as its DeviceDispatch
    spans say (`groupPath`: onehot | onehot2 | scatter; `groupFold`:
    device | host; on a server of several chips `meshDevices` and what
    the grouped program's collectives carry), so that a change of path
    cannot hide behind a leg that silently ran another kernel. An
    ungrouped query states nothing."""
    out = {}
    for d in spans(resp.get("traceInfo"), "DeviceDispatch"):
        for span_key, key in (("groupPath", "group_path"),
                              ("groupFold", "group_fold"),
                              ("groupKeySpace", "group_key_space"),
                              ("meshDevices", "mesh_devices"),
                              ("meshExchangeBytes", "mesh_exchange_bytes"),
                              ("meshGatherBytes", "mesh_gather_bytes")):
            if span_key in d:
                out.setdefault(key, []).append(d[span_key])
    return out


def check_main(name: str, rows: list, want: dict) -> dict:
    """Raises unless `rows` is the reference's answer; returns what the
    approximate answers were measured against."""
    if name == "tdigest":
        lo, hi = want["tdigest_bin"]
        got = float(rows[0][0])
        if not lo * (1 - TDIGEST_RTOL) <= got <= hi * (1 + TDIGEST_RTOL):
            raise SmokeFailure(
                f"tdigest: p95 {got} outside exact bin [{lo}, {hi}) "
                f"+- {TDIGEST_RTOL}")
        return {"p95": got, "exact_p95_bin": [lo, hi], "rtol": TDIGEST_RTOL}
    expect = want[name]
    seen = {}
    if name == "hll":
        exact = want["hll_exact_distinct"]
        if abs(rows[0][0] - exact) > HLL_RTOL * exact:
            raise SmokeFailure(f"hll: {rows[0][0]} vs {exact} distinct")
        seen = {"estimate": rows[0][0], "exact_distinct": exact}
    if len(rows) != len(expect):
        raise SmokeFailure(f"{name}: {len(rows)} rows, want {len(expect)}")
    approx_col = 2 if name == "groupby_onehot" else None
    worst = 0.0
    for got, exp in zip(rows, expect):
        for c, (g, e) in enumerate(zip(got, exp)):
            if c == approx_col:
                worst = max(worst, abs(float(g) - e) / abs(e))
                good = worst <= GROUPED_SUM_RTOL
            else:
                good = float(g) == float(e)
            if not good:
                raise SmokeFailure(f"{name}: row {got} != expected {exp}")
    if approx_col is not None:
        seen = {"grouped_sum_max_rel_err": worst, "rtol": GROUPED_SUM_RTOL}
    return seen


# ---------------------------------------------------------------------------
# the pseudo-column legs, at their tier-1 smoke sizes: compile and parity
# ---------------------------------------------------------------------------
def leg_tables(seed: int) -> list:
    """[(leg, served meter, TableConfig, Schema, [columns per segment],
    sql, check(rows))] — data, reference and check from plain numpy."""
    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  StarTreeIndexConfig, TableConfig,
                                  TableType)
    legs = []

    # star-tree: pre-aggregated records answer a group-by
    rng = np.random.default_rng([seed, 101])
    st_segs = [{
        "country": [f"c{v}" for v in rng.integers(0, 12, 3000)],
        "browser": [f"b{v}" for v in rng.integers(0, 5, 3000)],
        "impressions": rng.integers(0, 1000, 3000).astype(np.int64),
    } for _ in range(2)]
    tc = TableConfig("st", TableType.OFFLINE)
    tc.indexing.star_tree_configs = [StarTreeIndexConfig(
        dimensions_split_order=["country", "browser"],
        function_column_pairs=["SUM__impressions", "COUNT__*"],
        max_leaf_records=10)]
    schema = Schema("st", [
        FieldSpec("country", DataType.STRING),
        FieldSpec("browser", DataType.STRING),
        FieldSpec("impressions", DataType.LONG, FieldType.METRIC)])
    want = {}
    for seg in st_segs:
        for c, v in zip(seg["country"], seg["impressions"]):
            want[c] = want.get(c, 0) + int(v)
    st_rows = [[c, float(want[c])] for c in sorted(want)]
    legs.append(("startree", "startree_served", tc, schema, st_segs,
                 "SELECT country, SUM(impressions) FROM st "
                 "GROUP BY country ORDER BY country LIMIT 100",
                 lambda rows: [[r[0], float(r[1])] for r in rows] == st_rows))

    # CLP: LIKE over a log column without decoding it
    templates = ["INFO task {} started on host web-0{} in 0.5s",
                 "ERROR task {} failed on host web-0{}: code=500",
                 "WARN task {} slow on host db-0{} in 12.75s",
                 "GC pause {} ms in region r{}"]
    rng = np.random.default_rng([seed, 102])
    clp_segs = []
    for _ in range(2):
        kinds = rng.integers(0, len(templates), 1500)
        a, b = rng.integers(0, 10_000, 1500), rng.integers(1, 4, 1500)
        clp_segs.append({
            "ts": np.arange(1500, dtype=np.int64),
            "message": [templates[k].format(x, y)
                        for k, x, y in zip(kinds, a, b)]})
    tc = TableConfig("logs", TableType.OFFLINE)
    tc.indexing.clp_columns = ["message"]
    schema = Schema("logs", [
        FieldSpec("ts", DataType.LONG, FieldType.DATE_TIME),
        FieldSpec("message", DataType.STRING)])
    n_failed = sum("failed" in m for s in clp_segs for m in s["message"])
    legs.append(("clp_like", "clp_served", tc, schema, clp_segs,
                 "SELECT COUNT(*) FROM logs WHERE message LIKE '%failed%'",
                 lambda rows: rows == [[n_failed]]))

    # vector similarity: per-segment cosine top-K as one matmul + top_k
    dim, k, n = 8, 5, 400
    centers = np.random.default_rng([seed, 103]).normal(size=(8, dim)) * 2.0
    vec_segs, normed = [], []
    for s in range(2):
        rng = np.random.default_rng([seed, 104, s])
        vecs = (centers[rng.integers(0, 8, n)]
                + 0.3 * rng.normal(size=(n, dim))).astype(np.float32)
        normed.append((vecs / np.maximum(np.linalg.norm(
            vecs, axis=-1, keepdims=True), 1e-30)).astype(np.float32))
        vec_segs.append({
            "id": np.arange(n) + s * n,
            "vec": np.array([json.dumps([float(x) for x in r])
                             for r in vecs], object)})
    q = (normed[0][17] + 0.05 * np.random.default_rng(
        [seed, 105]).normal(size=dim)).astype(np.float32)
    qn = (q / max(float(np.linalg.norm(q)), 1e-30)).astype(np.float32)
    want_ids = []
    for s, v in enumerate(normed):
        scores = v @ qn
        order = np.lexsort((np.arange(n), -scores))  # ties: lower doc id
        want_ids += [int(d) + s * n for d in order[:k]]
    tc = TableConfig("emb", TableType.OFFLINE)
    tc.indexing.vector_index_columns = ["vec"]
    schema = Schema("emb", [
        FieldSpec("id", DataType.INT, FieldType.DIMENSION),
        FieldSpec("vec", DataType.STRING, FieldType.DIMENSION)])
    qjson = json.dumps([float(x) for x in q])
    legs.append(("vector", "vector_served", tc, schema, vec_segs,
                 f"SELECT id FROM emb WHERE vector_similarity(vec, "
                 f"'{qjson}', {k}) LIMIT 100",
                 lambda rows: sorted(r[0] for r in rows)
                 == sorted(want_ids)))

    # time-bucket group-by: the time-series leaf's SQL shape
    t0, step, buckets = 1000, 20, 6
    hosts = ["a(1)", "h1", "h2", "h3"]
    rng = np.random.default_rng([seed, 106])
    ts_segs, want = [], {}
    for _ in range(2):
        ts = rng.integers(t0, t0 + buckets * step, 2000)
        hs = rng.integers(0, len(hosts), 2000)
        val = rng.integers(0, 1000, 2000)  # int-valued: f32 sums are exact
        ts_segs.append({"ts": ts, "host": np.array(
            [hosts[h] for h in hs], object), "value": val.astype(np.float64)})
        for t, h, v in zip(ts, hs, val):
            key = (float((t - t0) // step), hosts[h])
            want[key] = want.get(key, 0.0) + float(v)
    schema = Schema("metrics", [
        FieldSpec("ts", DataType.LONG, FieldType.DIMENSION),
        FieldSpec("host", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("value", DataType.DOUBLE, FieldType.METRIC)])
    bucket = f"floor((ts - {t0}) / {step})"
    legs.append(("time_bucket", "timeseries_leaf_device",
                 TableConfig("metrics", TableType.OFFLINE), schema, ts_segs,
                 f"SELECT {bucket}, host, SUM(value) FROM metrics "
                 f"WHERE ts >= {t0} AND ts < {t0 + buckets * step} "
                 f"GROUP BY {bucket}, host LIMIT 1000",
                 lambda rows: {(float(r[0]), r[1]): float(r[2])
                               for r in rows} == want))
    return legs


# ---------------------------------------------------------------------------
# cluster plumbing
# ---------------------------------------------------------------------------
def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, body=None, timeout: float = 60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def series_delta(before: dict, after: dict, metric: str) -> float:
    return sum(v - before.get(k, 0) for k, v in after.items()
               if k.startswith(metric + "{"))


def spans(node, name: str) -> list:
    """Every span called `name` in a trace tree."""
    if not isinstance(node, dict):
        return []
    found = [node] if node.get("operator") == name else []
    for child in node.get("children", ()):
        found += spans(child, name)
    return found


class Cluster:
    """The smoke's processes; every one it starts, it stops."""

    def __init__(self, work: str, rehearsal: bool):
        self.work = work
        self.procs = {}
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = REPO + os.pathsep + \
            self.env.get("PYTHONPATH", "")
        # downloads, tars and every other temp file stay in the work dir
        self.env["TMPDIR"] = os.path.join(work, "tmp")
        # controller and broker import jax but must never start a backend:
        # with a platform that does not exist, one that tried would raise
        self.env["JAX_PLATFORMS"] = "no_chip_for_this_role"
        self.server_env = dict(self.env)
        # tpu, not "": without a chip JAX must raise, not hand back CPUs
        self.server_env["JAX_PLATFORMS"] = "cpu" if rehearsal else "tpu"
        # persist EVERY compile, so the restarted server can show that it
        # compiled nothing (the defaults skip compiles under one second)
        self.server_env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        self.server_env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
        self.coord_port = free_port()
        self.broker_port = free_port()
        self.coordinator = f"127.0.0.1:{self.coord_port}"
        self.admin_url = None

    def spawn(self, name: str, args: list, env: dict) -> None:
        log = open(os.path.join(self.work, "logs", f"{name}.log"), "ab")
        self.procs[name] = subprocess.Popen(
            [sys.executable, "-m", "pinot_tpu.tools.admin", *args],
            env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT)
        log.close()

    def log_tail(self, name: str, n: int = 40) -> str:
        with open(os.path.join(self.work, "logs", f"{name}.log"),
                  errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def wait(self, predicate, what: str, timeout: float = 180.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for name, proc in self.procs.items():
                if proc.poll() is not None:
                    raise SmokeFailure(
                        f"{name} exited with {proc.returncode} while "
                        f"waiting for {what}:\n{self.log_tail(name)}")
            try:
                got = predicate()
            except (OSError, ValueError, KeyError):
                got = None
            if got:
                return got
            time.sleep(0.25)
        raise SmokeFailure(f"timed out after {timeout:.0f}s waiting for "
                           f"{what}")

    def start_controller_and_broker(self) -> None:
        self.spawn("controller", [
            "StartController", "--state-dir",
            os.path.join(self.work, "state"), "--port", str(self.coord_port),
            "--deep-store", "file://" + os.path.join(self.work, "store")],
            self.env)

        def controller_up():
            with socket.create_connection(("127.0.0.1", self.coord_port),
                                          timeout=1):
                return True
        self.wait(controller_up, "the controller")
        self.spawn("broker", ["StartBroker", "--coordinator",
                              self.coordinator, "--http-port",
                              str(self.broker_port)], self.env)

    def start_server(self) -> dict:
        """Start the one server; returns its /debug/device report."""
        from pinot_tpu.controller.coordination import CoordinationClient
        # no configuration file: the HBM budgets are per chip, and the
        # engine multiplies them by the chips it holds
        self.spawn("server", ["StartServer", "--instance-id", "server_0",
                              "--coordinator", self.coordinator, "--tpu"],
                   self.server_env)

        def admin_url():
            client = CoordinationClient(self.coordinator)
            try:
                inst = client.get_state()["instances"].get("server_0") or {}
            finally:
                client.close()
            url = inst.get("admin_url")
            # a restarted server registers a new admin port: take it only
            # once it answers
            return url if url and http_json(url + "/debug/device") else None
        self.admin_url = self.wait(admin_url, "the server to register")
        return http_json(self.admin_url + "/debug/device")

    def stop(self, name: str) -> None:
        proc = self.procs.pop(name, None)
        if proc is None or proc.poll() is not None:
            return
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=15)

    def stop_all(self) -> None:
        for name in list(self.procs):
            self.stop(name)

    def counters(self) -> dict:
        """The server's /metrics, by series (`name{labels}`)."""
        with urllib.request.urlopen(self.admin_url + "/metrics",
                                    timeout=60) as r:
            text = r.read().decode()
        out = {}
        for line in text.splitlines():
            if line and not line.startswith("#"):
                series, _, value = line.rpartition(" ")
                out[series.removeprefix("pinot_tpu_server_")] = float(value)
        return out

    def counter(self, name: str) -> float:
        return sum(v for k, v in self.counters().items()
                   if k == name or k.startswith(name + "{"))

    def query(self, sql: str, timeout: float = 960.0):
        """One POST /query/sql; returns (rows, response, client ms)."""
        t0 = time.perf_counter()
        resp = http_json(f"http://127.0.0.1:{self.broker_port}/query/sql",
                         {"sql": f"{sql} {QUERY_OPTIONS}"}, timeout=timeout)
        ms = (time.perf_counter() - t0) * 1e3
        if resp.get("exceptions"):
            raise SmokeFailure(f"exceptions from {sql!r}: "
                               f"{resp['exceptions']}")
        if resp["numServersResponded"] != resp["numServersQueried"] \
                or resp["numServersQueried"] < 1:
            raise SmokeFailure(
                f"{resp['numServersResponded']} of "
                f"{resp['numServersQueried']} servers answered {sql!r}")
        dispatches = spans(resp.get("traceInfo"), "DeviceDispatch")
        served = [d for d in dispatches
                  if d.get("outcome") not in FALLBACK_OUTCOMES]
        if spans(resp.get("traceInfo"), "SegmentResultCache") or not served \
                or len(served) != len(dispatches):
            raise SmokeFailure(
                f"not device-served: {sql!r}: DeviceDispatch spans "
                f"{dispatches}")
        rows = (resp.get("resultTable") or {}).get("rows") or []
        return rows, resp, ms


# ---------------------------------------------------------------------------
def native_library() -> str:
    """Build the native library HERE, from what git holds, or run
    without: it is compiled -march=native, so one built elsewhere must
    never load."""
    from pinot_tpu.native import build
    if os.path.exists(build.OUT):
        os.remove(build.OUT)
    if shutil.which("g++") is None:
        return "absent"
    try:
        build.build(verbose=False)
    except subprocess.CalledProcessError:
        return "absent"
    return "built"


def run(args) -> dict:
    t_start = time.monotonic()
    import pinot_tpu  # noqa: F401 — without the program, fail before making anything
    work = os.path.join(REPO, "chip_smoke_data")
    shutil.rmtree(work, ignore_errors=True)
    for sub in ("logs", "tmp", "build", "tables"):
        os.makedirs(os.path.join(work, sub))
    os.environ["TMPDIR"] = os.path.join(work, "tmp")
    native = native_library()  # before anything here imports the loader

    cluster = Cluster(work, args.cpu_rehearsal)
    try:
        return drive(args, cluster, work, native, t_start)
    finally:
        cluster.stop_all()
        if not args.keep:
            shutil.rmtree(work, ignore_errors=True)


def add_table(cluster, work, tc, schema) -> None:
    from pinot_tpu.tools import admin
    paths = []
    for kind, obj in (("table", tc), ("schema", schema)):
        paths.append(os.path.join(work, "tables", f"{tc.name}_{kind}.json"))
        with open(paths[-1], "w") as f:
            json.dump(obj.to_dict(), f)
    if admin.main(["AddTable", "--coordinator", cluster.coordinator,
                   "--table", paths[0], "--schema", paths[1]]) != 0:
        raise SmokeFailure(f"AddTable {tc.name} failed")


def wait_loaded(cluster, table: str, column: str, docs: int) -> None:
    """A LIMIT-only selection stays on the host and reports totalDocs
    over the segments the server has loaded so far."""
    def loaded():
        resp = http_json(
            f"http://127.0.0.1:{cluster.broker_port}/query/sql",
            {"sql": f"SELECT {column} FROM {table} LIMIT 1 "
                    f"OPTION(skipCache=true)"})
        return resp.get("totalDocs") == docs and not resp.get("exceptions")
    cluster.wait(loaded, f"{table} to load {docs} docs", timeout=600.0)


def drive(args, cluster, work, native, t_start) -> dict:
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.tools import admin
    rows_total = args.segments * args.docs_per_segment
    print(f"chip_smoke: seed={args.seed} segments={args.segments} x "
          f"{args.docs_per_segment} docs = {rows_total} rows, native "
          f"library {native}"
          + (" — CPU REHEARSAL, not a result" if args.cpu_rehearsal else ""),
          flush=True)

    cluster.start_controller_and_broker()
    device = cluster.start_server()
    print(f"chip_smoke: server reports {json.dumps(device)}", flush=True)
    if not args.cpu_rehearsal and device.get("platform") != "tpu":
        raise SmokeFailure(f"server is not on a TPU: {device}")
    if not args.cpu_rehearsal and device["count"] != args.chips:
        raise SmokeFailure(f"--chips {args.chips}, but the server holds "
                           f"{device['count']} devices")
    if device["native_lib"] != native:
        raise SmokeFailure(f"server native library {device['native_lib']}, "
                           f"this run {native}")
    cache_dir = device["compile_cache_dir"]
    env_cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache_dir != (env_cache or os.path.join(REPO, ".jax_compile_cache")):
        raise SmokeFailure(f"compile cache at {cache_dir!r}")

    # -- the table: built in parallel, pushed through the deep store ----
    t0 = time.monotonic()
    tc, schema = ssb_table()
    add_table(cluster, work, tc, schema)
    jobs = [(args.seed, i, args.docs_per_segment, work, cluster.coordinator)
            for i in range(args.segments)]
    refs, seg_bytes = [None] * args.segments, 0
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(args.workers, args.segments)) as pool:
        for i, ref, nbytes, worker_has_jax in pool.imap_unordered(
                segment_job, jobs):
            if worker_has_jax:
                raise SmokeFailure("a segment worker imported jax")
            refs[i] = ref
            seg_bytes += nbytes
    want = merge_reference(refs)
    del refs
    build_s = time.monotonic() - t0
    t0 = time.monotonic()
    wait_loaded(cluster, "ssb", "lo_quantity", rows_total)
    load_s = time.monotonic() - t0
    print(f"chip_smoke: table built and uploaded in {build_s:.1f}s, "
          f"loaded {load_s:.1f}s later ({seg_bytes} segment bytes)",
          flush=True)

    # -- main path: cold once, then warm with the result caches bypassed -
    queries = []
    for name, family, sql in MAIN_QUERIES:
        before = cluster.counters()
        rows, resp, cold_ms = cluster.query(sql)
        accuracy = check_main(name, rows, want)
        mid = cluster.counters()
        warm, warm_arg_bytes = [], 0
        for _ in range(args.warm):
            rows, resp, ms = cluster.query(sql)
            check_main(name, rows, want)
            warm.append(ms)
            # the packed parameters ride every launch as its own jit
            # argument, a hit's too: counted by `hbm_transfer_bytes`,
            # named on the span, and no upload of anything cached
            warm_arg_bytes += sum(
                d.get("paramsXferBytes", 0)
                for d in spans(resp["traceInfo"], "DeviceDispatch"))
        after = cluster.counters()
        entry = {
            "name": name, "family": family, "device_served": True,
            "cold_ms": cold_ms, "warm_median_ms": statistics.median(warm),
            "warm_samples": len(warm), "warm_ms": warm,
            "cold_upload_bytes": series_delta(before, mid,
                                              "hbm_transfer_bytes"),
            "cold_compiles": series_delta(before, mid, "kernel_retrace"),
            "warm_upload_bytes": series_delta(
                mid, after, "hbm_transfer_bytes") - warm_arg_bytes,
            "warm_launch_arg_bytes": warm_arg_bytes,
            "warm_compiles": series_delta(mid, after, "kernel_retrace"),
            "device_kernel_fetch_ms": spans(
                resp["traceInfo"], "DeviceDispatch")[0].get("kernelMs"),
            **group_attrs(resp),
            **accuracy,
        }
        print(f"chip_smoke: {json.dumps(entry)}", flush=True)
        if name in GROUP_PATHS \
                and entry.get("group_path") != [GROUP_PATHS[name]]:
            raise SmokeFailure(f"{name}: groupPath {entry.get('group_path')}"
                               f", not {GROUP_PATHS[name]}")
        if entry["warm_upload_bytes"] or entry["warm_compiles"]:
            raise SmokeFailure(f"{name}: warm repeats uploaded "
                               f"{entry['warm_upload_bytes']} bytes and "
                               f"compiled {entry['warm_compiles']} times")
        queries.append(entry)

    # -- the legs: one query each, device-served and equal to numpy ------
    legs = leg_tables(args.seed)

    def wait_legs_loaded():
        for _leg, _meter, tc, schema, segs, _sql, _check in legs:
            wait_loaded(cluster, tc.name, schema.fields[0].name,
                        sum(len(next(iter(c.values()))) for c in segs))
    for leg, _meter, tc, schema, segs, _sql, _check in legs:
        add_table(cluster, work, tc, schema)
        for i, cols in enumerate(segs):
            seg_dir = os.path.join(work, "build", f"{tc.name}_{i}")
            SegmentCreator(tc, schema).build(cols, seg_dir,
                                             f"{tc.name}_{i}")
            if admin.main(["UploadSegment", "--coordinator",
                           cluster.coordinator, "--table", tc.name,
                           "--segment-dir", seg_dir]) != 0:
                raise SmokeFailure(f"UploadSegment {tc.name}_{i} failed")
    wait_legs_loaded()
    leg_results = []
    for leg, meter, tc, _schema, _segs, sql, check in legs:
        served0 = cluster.counter(meter)
        rows, _resp, ms = cluster.query(sql)
        if cluster.counter(meter) <= served0:
            raise SmokeFailure(f"{leg}: {meter} did not move")
        if not check(rows):
            raise SmokeFailure(f"{leg}: rows differ from numpy: {rows}")
        leg_results.append({"name": leg, "served_meter": meter,
                            "device_served": True, "cold_ms": ms,
                            **group_attrs(_resp)})
        print(f"chip_smoke: {json.dumps(leg_results[-1])}", flush=True)

    counters = cluster.counters()
    device = http_json(cluster.admin_url + "/debug/device")
    resident = {k.split('device="')[1].split('"')[0]: v
                for k, v in counters.items()
                if k.startswith("hbm_resident_bytes{") and 'device="' in k}
    if device["count"] > 1 and (len(resident) != device["count"]
                                or not all(resident.values())):
        raise SmokeFailure(f"a chip holds no resident bytes: {resident}")

    # -- second run of the server: the compile cache must cover it -------
    entries_first = device["compile_cache_entries"]
    cluster.stop("server")
    t0 = time.monotonic()
    cluster.start_server()
    wait_loaded(cluster, "ssb", "lo_quantity", rows_total)
    wait_legs_loaded()
    for name, _family, sql in MAIN_QUERIES:
        rows, _resp, ms = cluster.query(sql)
        check_main(name, rows, want)
        next(q for q in queries if q["name"] == name)["restart_cold_ms"] = ms
    for leg, _meter, _tc, _schema, _segs, sql, check in legs:
        rows, _resp, _ms = cluster.query(sql)
        if not check(rows):
            raise SmokeFailure(f"{leg}: rows differ after restart: {rows}")
    second = http_json(cluster.admin_url + "/debug/device")
    new_entries = second["compile_cache_entries"] - entries_first
    restart = {"seconds": time.monotonic() - t0,
               "compile_cache_entries": second["compile_cache_entries"],
               "new_compile_cache_entries": new_entries}
    print(f"chip_smoke: restarted server {json.dumps(restart)}", flush=True)
    if new_entries or not entries_first:
        raise SmokeFailure(f"compile cache: {entries_first} entries after "
                           f"the first server, {new_entries} new after the "
                           f"second")

    if "jax" in sys.modules:
        raise SmokeFailure("the parent imported jax")
    reduced = []
    if args.segments < FULL_SEGMENTS * args.chips:
        reduced.append(f"segments {FULL_SEGMENTS * args.chips} -> "
                       f"{args.segments}")
    if args.docs_per_segment != FULL_DOCS:
        reduced.append(f"docs per segment {FULL_DOCS} -> "
                       f"{args.docs_per_segment}")
    return {
        "rehearsal": args.cpu_rehearsal,
        "device": {"platform": device["platform"],
                   "kind": device["device_kind"], "count": device["count"]},
        "versions": {k: device[k] for k in ("jax", "jaxlib", "libtpu")},
        "x64": device["x64"],
        "seed": args.seed, "chips": args.chips, "rows": rows_total,
        "segments": args.segments,
        "docs_per_segment": args.docs_per_segment, "reduced": reduced,
        "segment_bytes": seg_bytes,
        "uploaded_bytes": sum(v for k, v in counters.items()
                              if k.startswith("hbm_transfer_bytes{")),
        "hbm_cache_bytes": sum(v for k, v in counters.items()
                               if k.startswith("hbm_cache_bytes{")
                               and 'device="' not in k),
        "hbm_resident_bytes_by_device": resident,
        "memory": device["memory"],
        "build_upload_s": build_s, "load_s": load_s,
        "queries": queries, "legs": leg_results, "restart": restart,
        "native_lib": native,
        "compile_cache_dir": cache_dir,
        "compile_cache_entries": second["compile_cache_entries"],
        "parent_imported_jax": False,
        "elapsed_s": time.monotonic() - t_start,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=21)
    p.add_argument("--chips", type=int, default=1,
                   help="devices the ONE server process must hold; the "
                        "table and the server's HBM budgets scale with it")
    p.add_argument("--segments", type=int, default=None,
                   help=f"default {FULL_SEGMENTS} per chip; the cut to "
                        f"make if the time limit forces one (never docs "
                        f"or columns)")
    p.add_argument("--docs-per-segment", type=int, default=None,
                   help="only with --cpu-rehearsal")
    p.add_argument("--warm", type=int, default=5)
    p.add_argument("--workers", type=int,
                   default=max(1, min(12, (os.cpu_count() or 2) // 2)))
    p.add_argument("--time-limit", type=int, default=1150,
                   help="seconds before the run gives up and stops its "
                        "processes")
    p.add_argument("--keep", action="store_true",
                   help="keep chip_smoke_data/ (logs, store) afterwards")
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="tiny run on XLA:CPU to debug this script; never "
                        "a pass")
    args = p.parse_args(argv)
    if args.docs_per_segment is not None and not args.cpu_rehearsal:
        p.error("--docs-per-segment needs --cpu-rehearsal: a chip run "
                "cuts segments only")
    if args.segments is None:
        args.segments = 2 if args.cpu_rehearsal \
            else FULL_SEGMENTS * args.chips
    if args.docs_per_segment is None:
        args.docs_per_segment = 20_000 if args.cpu_rehearsal else FULL_DOCS
    if args.warm < 5 and not args.cpu_rehearsal:
        p.error("--warm must be at least 5")

    def give_up(signum, _frame):
        raise SmokeFailure(f"signal {signum}: time limit or termination")
    signal.signal(signal.SIGALRM, give_up)
    signal.signal(signal.SIGTERM, give_up)
    signal.alarm(args.time_limit)
    try:
        report = run(args)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        signal.alarm(0)
    if args.cpu_rehearsal:
        print("chip_smoke: CPU REHEARSAL finished — this is not a chip "
              "result and not a pass", flush=True)
    print(f"chip_smoke: report {json.dumps(report)}", flush=True)
    # the verdict: these keys and no others, the device as the server
    # process reported it
    print(json.dumps({"ok": not args.cpu_rehearsal,
                      "device": report["device"]}), flush=True)
    return REHEARSAL_EXIT if args.cpu_rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
