"""CLP log-analytics subsystem (ISSUE 17): device-side LIKE/regex
pushdown over CLP columns, realtime log ingestion, minion compaction.

  * codec properties — seeded random messages (unicode, floats,
    non-roundtrip digit tokens, empty/whitespace edges) round-trip
    through encode/decode AND write_clp_column/CLPForwardIndexReader;
    `get(doc_id)` random access matches `decode_all`
  * device parity — LIKE/regex filters over CLP columns answer
    BIT-IDENTICALLY to the host decode path through the real engine,
    across a pushdown matrix (substring, multi-piece, anchors, floats,
    ints, IPs, unicode); served queries meter `clp_served`, fallbacks
    meter `clp_fallback{reason=}` with EXACT structured reasons
  * retraces — fingerprint-equal queries with different pattern
    constants share one kernel (constants resolve at staging, the
    pattern never enters the plan): ZERO steady-state retraces
  * realtime — a MutableSegment with `indexing.clp_columns` encodes at
    ingest (template store, not raw strings), answers host queries,
    seals into a CLP segment the device leg serves
  * compaction — `ClpCompactionTask` generator/executor converge plain
    log segments onto CLP form; a SimulatedCrash at `minion.clp.compact`
    leaves sources serving and the re-leased task re-encodes
    BYTE-IDENTICAL output
  * minion fairness — tenant-weighted lease clocks (weight 3 leases 3x
    weight 1 under contention; weight 1.0 degenerates to round-robin)
  * auto star-tree — the workload-driven generator schedules builds
    only for tables the /debug/workload rollup shows as hot
"""
import time

import numpy as np
import pytest

from pinot_tpu.controller.cluster_state import ClusterState, SegmentState
from pinot_tpu.controller.task_manager import PENDING, TaskManager, TaskQueue
from pinot_tpu.controller.tasks import TaskConfig, TaskContext, run_task
from pinot_tpu.health.workload import WorkloadRegistry
from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              TableConfig, TableType)
from pinot_tpu.ops import clp_device, kernels
from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.query.executor import QueryExecutor
from pinot_tpu.segment import clp
from pinot_tpu.segment import index_types as it
from pinot_tpu.segment.creator import SegmentCreator
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.utils.config import PinotConfiguration
from pinot_tpu.utils.failpoints import SimulatedCrash, failpoints

MESSAGES = [
    "INFO task 1234 started on host web-01 in 0.5s",
    "WARN task 9999 slow on host web-02 in 12.75s",
    "ERROR task 1234 failed on host web-01: code=500",
    "INFO user alice logged in from 10.0.0.1",
    "INFO user bob42 logged in from 10.0.0.2",
    "disk /dev/sda1 at 93% capacity",
    "disk /dev/sdb2 at 17% capacity",
    "GC pause 45 ms in region r7",
    "GC pause 450 ms in region r12",
    "",
    "ERROR task 777 failed on host db-01: code=503",
    "checkpoint written to /data/ckpt/000123 bytes=4096",
    "retrying connection to 10.0.0.1 attempt 3",
    "negative value -17 seen at offset -3.5",
    "unicode héllo wörld 42 done",
]


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.clear()
    yield
    failpoints.clear()


def log_schema(name="logs"):
    return Schema(name, [
        FieldSpec("ts", DataType.LONG, FieldType.DATE_TIME),
        FieldSpec("message", DataType.STRING),
    ])


def build_log_seg(tmp, name, msgs, clp_col=True, table="logs"):
    tc = TableConfig(table, TableType.OFFLINE)
    if clp_col:
        tc.indexing.clp_columns = ["message"]
    out = str(tmp / name)
    SegmentCreator(tc, log_schema(table)).build(
        {"ts": np.arange(len(msgs), dtype=np.int64), "message": list(msgs)},
        out, name)
    return out


def _engine(name, **overrides):
    return TpuOperatorExecutor(
        config=PinotConfiguration(overrides=overrides),
        metrics_labels={"clp_test": name})


def _meter(eng, name, reason=None):
    labels = {"clp_test": eng._labels["clp_test"]}
    if reason is not None:
        labels["reason"] = reason
    return eng._metrics.meter(name, labels=labels)


# ---------------------------------------------------------------------------
# codec properties
# ---------------------------------------------------------------------------
class TestCodecProperties:
    _WORDS = ["alpha", "beta", "état", "GET", "host", "wörld", "retry",
              "x", "[queue]", "a=b"]

    @classmethod
    def _rand_msg(cls, rng):
        parts = []
        for _ in range(int(rng.integers(0, 9))):
            kind = int(rng.integers(0, 7))
            if kind == 0:
                parts.append(str(cls._WORDS[int(
                    rng.integers(0, len(cls._WORDS)))]))
            elif kind == 1:   # int64-range -> encoded var
                parts.append(str(int(rng.integers(-10**12, 10**12))))
            elif kind == 2:   # repr-roundtrip float -> encoded var
                parts.append(repr(round(float(rng.random()) * 100, 3)))
            elif kind == 3:   # leading zero: no int round-trip -> dict var
                parts.append("0" + str(int(rng.integers(0, 999))))
            elif kind == 4:   # ip-ish multi-dot token -> dict var
                parts.append(".".join(str(int(v))
                                      for v in rng.integers(0, 256, 4)))
            elif kind == 5:   # beyond int64 -> dict var
                parts.append(str(int(rng.integers(1, 9)) * 10**20))
            else:             # mixed alnum -> dict var
                parts.append(f"req-{int(rng.integers(0, 10**6))}")
        seps = [" ", "  ", "=", ": ", ", "]
        out = ""
        for p in parts:
            out += p + seps[int(rng.integers(0, len(seps)))]
        return out

    def test_random_messages_roundtrip(self):
        rng = np.random.default_rng(1717)
        msgs = [self._rand_msg(rng) for _ in range(300)]
        msgs += ["", "   ", "===", "no digits at all", "\t tab \t lead"]
        for m in msgs:
            lt, dv, ev = clp.encode_message(m)
            assert clp.decode_message(lt, dv, ev) == m
        reader = clp.CLPForwardIndexReader(clp.write_clp_column(msgs))
        assert reader.num_docs == len(msgs)
        assert list(reader.decode_all()) == msgs

    def test_get_matches_decode_all(self):
        reader = clp.CLPForwardIndexReader(clp.write_clp_column(MESSAGES))
        dec = list(reader.decode_all())
        # random access, out of order
        order = np.random.default_rng(3).permutation(len(MESSAGES))
        for i in order:
            assert reader.get(int(i)) == dec[int(i)] == MESSAGES[int(i)]


# ---------------------------------------------------------------------------
# device parity through the real engine
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def segs(tmp_path_factory):
    """Three CLP segments with rotated corpora (distinct doc counts so
    shape buckets get exercised) + the concatenated host truth."""
    tmp = tmp_path_factory.mktemp("clp_device")
    out, all_msgs = [], []
    for si in range(3):
        msgs = [MESSAGES[(i + si) % len(MESSAGES)]
                for i in range(100 + si * 7)]
        out.append(load_segment(build_log_seg(tmp, f"seg{si}", msgs)))
        all_msgs.extend(msgs)
    return out, all_msgs


#: LIKE patterns the planner pushes to the device (matrix: bare
#: substring, template+var, anchors, floats, IPs, unicode, full-message)
PUSHED = [
    "%failed%", "%web-01%", "INFO%", "%capacity", "%task 1234 failed%",
    "%10.0.0.1%", "%héllo%", "%code=500", "%", "%user alice%",
    "%pause 45 ms%", "%pause 450 ms%", "%in 0.5s%", "%attempt 3",
    "GC pause 45 ms in region r7",
]

#: LIKE/regex patterns that take the host path, with their EXACT
#: structured fallback reason
FALLBACKS = [
    ("%task 12%", True, "wildcard"),       # digit partial token
    ("%e%", True, "wildcard"),             # sub-token needle, enc chars
    ("%-17%", True, "wildcard"),           # sign char partial
    ("%ali%ce%", True, "partial"),         # facing partials
    ("%task%failed%code=500", True, "partial"),  # facing across pieces
    ("task 12_4", True, "charWildcard"),   # single-char wildcard
    ("user (alice|bob)", False, "regex"),  # regex alternation
]


class TestDeviceParity:
    def test_like_matrix_parity_and_meters(self, segs):
        loaded, all_msgs = segs
        eng = _engine("parity")
        dev = QueryExecutor(loaded, use_tpu=True, engine=eng)
        host = QueryExecutor(loaded, use_tpu=False)
        for pat in PUSHED + [p for p, is_like, _ in FALLBACKS if is_like]:
            sql = f"SELECT COUNT(*) FROM logs WHERE message LIKE '{pat}'"
            a, b = dev.execute(sql), host.execute(sql)
            assert not a.exceptions and not b.exceptions, pat
            assert a.result_table.rows[0][0] == \
                b.result_table.rows[0][0], pat
        # every pushed pattern served device-side; each host-path
        # pattern metered its exact structured reason
        assert _meter(eng, "clp_served") == len(PUSHED)
        for pat, is_like, reason in FALLBACKS:
            if is_like:
                assert _meter(eng, "clp_fallback", reason=reason) >= 1, pat

    def test_regexp_like_fallback_reason(self, segs):
        loaded, _ = segs
        eng = _engine("regex_fb")
        dev = QueryExecutor(loaded, use_tpu=True, engine=eng)
        host = QueryExecutor(loaded, use_tpu=False)
        sql = ("SELECT COUNT(*) FROM logs "
               "WHERE REGEXP_LIKE(message, 'user (alice|bob)')")
        a, b = dev.execute(sql), host.execute(sql)
        assert not a.exceptions and not b.exceptions
        assert a.result_table.rows[0][0] == b.result_table.rows[0][0]
        assert _meter(eng, "clp_fallback", reason="regex") >= 1
        assert _meter(eng, "clp_served") == 0

    def test_mixed_shapes_parity(self, segs):
        """CLP leaves composed with ordinary predicates, OR trees and
        GROUP BY answer identically to the host path."""
        loaded, _ = segs
        dev = QueryExecutor(loaded, use_tpu=True, engine=_engine("mixed"))
        host = QueryExecutor(loaded, use_tpu=False)
        for sql in [
            "SELECT COUNT(*) FROM logs WHERE message LIKE '%failed%' "
            "AND ts < 50",
            "SELECT COUNT(*) FROM logs WHERE message LIKE '%failed%' "
            "OR message LIKE 'INFO%'",
            "SELECT ts, COUNT(*) FROM logs WHERE message LIKE '%failed%' "
            "GROUP BY ts ORDER BY ts LIMIT 5",
        ]:
            a, b = dev.execute(sql), host.execute(sql)
            assert not a.exceptions and not b.exceptions, sql
            assert sorted(map(str, a.result_table.rows)) == \
                sorted(map(str, b.result_table.rows)), sql

    def test_fallback_reasons_exact(self, segs):
        """The planner's structured reasons, asserted pattern by
        pattern (the meter test above only proves >=1 each)."""
        loaded, _ = segs
        for pat, is_like, want in FALLBACKS:
            meta, reason = clp_device.plan_leaf(loaded, "message", pat,
                                                is_like)
            assert meta is None and reason == want, (pat, reason, want)
        for pat in PUSHED:
            meta, reason = clp_device.plan_leaf(loaded, "message", pat,
                                                True)
            assert meta is not None, (pat, reason)
        assert set(r for _, _, r in FALLBACKS) <= \
            set(clp_device.FALLBACK_REASONS)

    def test_knob_disables_the_leg(self, segs):
        loaded, all_msgs = segs
        eng = _engine("knob", **{"pinot.server.clp.enabled": False})
        dev = QueryExecutor(loaded, use_tpu=True, engine=eng)
        r = dev.execute(
            "SELECT COUNT(*) FROM logs WHERE message LIKE '%failed%'")
        assert not r.exceptions
        assert r.result_table.rows[0][0] == \
            sum(1 for m in all_msgs if "failed" in m)
        assert _meter(eng, "clp_served") == 0
        assert _meter(eng, "clp_fallback", reason="disabled") >= 1

    def test_non_resident_tier_still_serves(self, segs):
        """pinot.server.hbm.resident.bytes=0: the CLP pseudo-columns go
        through the one staging path (row-by-row upload, on-device
        assembly), nothing is retained, answers unchanged."""
        loaded, all_msgs = segs
        eng = _engine("nonres", **{"pinot.server.hbm.resident.bytes": 0})
        dev = QueryExecutor(loaded, use_tpu=True, engine=eng)
        r = dev.execute(
            "SELECT COUNT(*) FROM logs WHERE message LIKE '%web-01%'")
        assert not r.exceptions
        assert r.result_table.rows[0][0] == \
            sum(1 for m in all_msgs if "web-01" in m)
        assert _meter(eng, "clp_served") == 1
        assert {k[1] for k in eng.stager._block_cache} >= {"clpid"}
        assert len(eng.residency) == 0 and eng.residency.bytes == 0


class TestZeroRetrace:
    def test_pattern_constants_share_one_kernel(self, segs):
        """The pattern never enters the DeviceLeaf: fingerprint-equal
        queries whose LIKE constants differ resolve their LUTs at
        staging and replay the SAME compiled kernel — zero retraces
        once the shape is warm."""
        loaded, all_msgs = segs
        eng = _engine("retrace")
        dev = QueryExecutor(loaded, use_tpu=True, engine=eng)
        sql = "SELECT COUNT(*) FROM logs WHERE message LIKE '%web-01%'"
        assert not dev.execute(sql).exceptions  # warm the shape bucket
        t0 = kernels.trace_count()
        for needle in ["web-02", "db-01", "capacity", "alice"]:
            r = dev.execute("SELECT COUNT(*) FROM logs "
                            f"WHERE message LIKE '%{needle}%'")
            assert not r.exceptions
            assert r.result_table.rows[0][0] == \
                sum(1 for m in all_msgs if needle in m)
        assert kernels.trace_count() == t0


# ---------------------------------------------------------------------------
# realtime log ingestion
# ---------------------------------------------------------------------------
class TestMutableClpIngestion:
    def _mutable(self):
        from pinot_tpu.ingest import MutableSegment
        tc = TableConfig("logs", TableType.REALTIME)
        tc.indexing.clp_columns = ["message"]
        return MutableSegment("logs__0__0__1", tc, log_schema())

    def test_ingest_encodes_and_queries(self):
        seg = self._mutable()
        n = 200
        for i in range(n):
            seg.index({"ts": i, "message": MESSAGES[i % len(MESSAGES)]})
        seg.index({"ts": n, "message": None})
        assert seg.num_docs == n + 1
        # ingest stored TEMPLATES: cardinality is the logtype count, an
        # order of magnitude under the doc count
        card = seg.metadata.columns["message"].cardinality
        assert 0 < card <= len(MESSAGES)
        r = QueryExecutor([seg], use_tpu=False).execute(
            "SELECT COUNT(*) FROM logs WHERE message LIKE '%failed%'")
        want = sum(1 for i in range(n)
                   if "failed" in MESSAGES[i % len(MESSAGES)])
        assert r.rows[0][0] == want

    def test_seal_builds_clp_segment_device_serves(self, tmp_path):
        seg = self._mutable()
        msgs = [MESSAGES[i % len(MESSAGES)] for i in range(150)]
        for i, m in enumerate(msgs):
            seg.index({"ts": i, "message": m})
        # the seal path: to_columns() -> SegmentCreator under the SAME
        # table config (realtime_manager wires exactly this)
        out = str(tmp_path / "sealed")
        SegmentCreator(seg.table_config, seg.schema).build(
            seg.to_columns(), out, "logs__0__0__1")
        sealed = load_segment(out)
        assert it.CLP in sealed.metadata.columns["message"].indexes
        assert list(sealed.data_source("message").values()) == msgs
        eng = _engine("sealed")
        r = QueryExecutor([sealed], use_tpu=True, engine=eng).execute(
            "SELECT COUNT(*) FROM logs WHERE message LIKE '%web-01%'")
        assert not r.exceptions
        assert r.result_table.rows[0][0] == \
            sum(1 for m in msgs if "web-01" in m)
        assert _meter(eng, "clp_served") == 1


# ---------------------------------------------------------------------------
# minion compaction
# ---------------------------------------------------------------------------
def compaction_state(tmp, n_segments=2):
    """Plain (non-CLP) sealed log segments under a table whose config
    declares clp_columns — the generator's work list."""
    cfg = TableConfig("logs")
    cfg.indexing.clp_columns = ["message"]
    cfg.task_configs = {"ClpCompactionTask": {}}
    state = ClusterState()
    state.add_table(cfg, log_schema())
    for i in range(n_segments):
        msgs = [MESSAGES[(j + i) % len(MESSAGES)] for j in range(80)]
        d = build_log_seg(tmp, f"s{i}", msgs, clp_col=False)
        m = load_segment(d).metadata
        state.upsert_segment(SegmentState(
            f"s{i}", "logs_REALTIME", [], dir_path=d, num_docs=80,
            start_time=m.start_time, end_time=m.end_time))
    return state


def _manager(state):
    return TaskManager(state, config=PinotConfiguration(overrides={
        "pinot.controller.task.generators.enabled": True,
        "pinot.controller.task.retry.backoff.seconds": 0.0}))


class TestClpCompaction:
    def test_generator_converges_and_device_serves(self, tmp_path):
        state = compaction_state(tmp_path)
        tm = _manager(state)
        assert tm.run_once()["generated"] == 1
        task = tm.queue.lease("w0")
        res = run_task(
            TaskConfig(task.task_type, task.table, list(task.segments),
                       dict(task.params), task_id=task.task_id),
            TaskContext(state, str(tmp_path / "out"),
                        task_id=task.task_id))
        assert sorted(res["compactedSegments"]) == ["s0_clp", "s1_clp"]
        assert res["clpColumns"] == ["message"]
        tm.queue.complete(task.task_id, "w0", res)
        names = {s.name for s in state.table_segments("logs_REALTIME")}
        assert names == {"s0_clp", "s1_clp"}
        rebuilt = [load_segment(state.segments["logs_REALTIME"][n].dir_path)
                   for n in sorted(names)]
        for seg in rebuilt:
            assert it.CLP in seg.metadata.columns["message"].indexes
            assert seg.num_docs == 80
        # compacted segments serve the DEVICE pushdown leg; parity with
        # a host scan over the ORIGINAL plain segments
        eng = _engine("compact_serve")
        r = QueryExecutor(rebuilt, use_tpu=True, engine=eng).execute(
            "SELECT COUNT(*) FROM logs WHERE message LIKE '%failed%'")
        assert not r.exceptions
        assert _meter(eng, "clp_served") == 1
        orig = [load_segment(str(tmp_path / f"s{i}")) for i in range(2)]
        want = QueryExecutor(orig, use_tpu=False).execute(
            "SELECT COUNT(*) FROM logs WHERE message LIKE '%failed%'")
        assert r.result_table.rows[0][0] == want.rows[0][0]
        # second tick: it.CLP metadata marker -> nothing left to do
        assert tm.run_once()["generated"] == 0

    def test_no_clp_columns_generates_nothing(self, tmp_path):
        state = compaction_state(tmp_path)
        state.tables["logs"].indexing.clp_columns = []
        assert _manager(state).run_once()["generated"] == 0

    def _run_flow(self, tmp_path, tag, chaos):
        """generate -> lease -> (crash -> expire -> re-lease) -> encode;
        returns the compacted segments' raw CLP buffers."""
        tmp = tmp_path / tag
        tmp.mkdir()
        state = compaction_state(tmp)
        tm = _manager(state)
        assert tm.run_once()["generated"] == 1
        (entry,) = tm.queue.list(PENDING)
        task = tm.queue.lease("w0", lease_ttl_s=0.01)
        cfg = TaskConfig(task.task_type, task.table, list(task.segments),
                         dict(task.params), task_id=task.task_id)
        ctx = TaskContext(state, str(tmp / "out"), task_id=task.task_id)
        if chaos:
            failpoints.arm("minion.clp.compact",
                           error=SimulatedCrash("chaos kill"), times=1)
            with pytest.raises(SimulatedCrash):
                run_task(cfg, ctx)
            # crash fired BEFORE any re-encode: sources untouched and
            # still answering via the host decode path
            segs = [load_segment(s.dir_path)
                    for s in state.table_segments("logs_REALTIME")]
            assert {s.name for s in segs} == {"s0", "s1"}
            r = QueryExecutor(segs, use_tpu=False).execute(
                "SELECT COUNT(*) FROM logs WHERE message LIKE '%failed%'")
            assert r.rows[0][0] > 0
            # worker vanished: lease expiry requeues, another picks it up
            time.sleep(0.02)
            assert tm.queue.expire_leases() == [entry.task_id]
            task = tm.queue.lease("w1")
            assert task.task_id == entry.task_id
        res = run_task(cfg, ctx)
        tm.queue.complete(task.task_id, task.worker, res)
        assert sorted(res["compactedSegments"]) == ["s0_clp", "s1_clp"]
        return {
            n: bytes(load_segment(
                state.segments["logs_REALTIME"][n].dir_path
            ).dir.get_buffer("message", it.CLP))
            for n in res["compactedSegments"]}

    def test_crashed_compaction_releases_and_reencodes_byte_identical(
            self, tmp_path):
        baseline = self._run_flow(tmp_path, "nochaos", chaos=False)
        chaosed = self._run_flow(tmp_path, "chaos", chaos=True)
        assert baseline == chaosed  # CLP buffer BYTES, not just answers


# ---------------------------------------------------------------------------
# tenant-weighted minion lease
# ---------------------------------------------------------------------------
class TestTenantWeightedLease:
    def _fill(self, q, n_a=6, n_b=2):
        for i in range(n_a):
            q.submit(TaskConfig("PurgeTask", "A_OFFLINE", [f"a{i}"]))
        for i in range(n_b):
            q.submit(TaskConfig("PurgeTask", "B_OFFLINE", [f"b{i}"]))

    def test_weighted_shares(self):
        """Weight 3 vs 1: under contention table A leases 3x as often —
        the deterministic virtual-clock sequence, not just the ratio."""
        q = TaskQueue(tenant_weight_of=lambda t: 3.0
                      if t.startswith("A") else 1.0)
        self._fill(q)
        got = [q.lease("w").table[0] for _ in range(8)]
        assert got == ["A", "B", "A", "A", "A", "B", "A", "A"]

    def test_default_weight_is_round_robin(self):
        q = TaskQueue()  # no weight provider: plain round-robin
        self._fill(q, n_a=3, n_b=3)
        got = [q.lease("w").table[0] for _ in range(6)]
        assert got == ["A", "B", "A", "B", "A", "B"]

    def test_manager_reads_tenant_config_weight(self, tmp_path):
        """TaskManager wires TableConfig.tenants.weight into the queue's
        weight provider."""
        state = ClusterState()
        cfg_a, cfg_b = TableConfig("A"), TableConfig("B")
        cfg_a.tenants.weight = 3.0
        state.add_table(cfg_a, log_schema("A"))
        state.add_table(cfg_b, log_schema("B"))
        tm = TaskManager(state)
        assert tm._tenant_weight("A_OFFLINE") == 3.0
        assert tm._tenant_weight("B_REALTIME") == 1.0
        assert tm._tenant_weight("unknown_OFFLINE") == 1.0


# ---------------------------------------------------------------------------
# workload-driven star-tree scheduling
# ---------------------------------------------------------------------------
ST_TREE_CFG = {"dimensionsSplitOrder": ["d"],
               "functionColumnPairs": ["SUM__m"],
               "maxLeafRecords": 5}


def startree_state(tmp):
    schema = Schema("ct", [
        FieldSpec("d", DataType.STRING),
        FieldSpec("ts", DataType.LONG, FieldType.DATE_TIME),
        FieldSpec("m", DataType.LONG, FieldType.METRIC),
    ])
    cfg = TableConfig("ct")
    cfg.task_configs = {"AutoStarTreeTask": {
        "starTreeIndexConfigs": [ST_TREE_CFG],
        "minCostMs": 100.0, "minQueries": 2}}
    state = ClusterState()
    state.add_table(cfg, schema)
    rng = np.random.default_rng(7)
    cols = {"d": [f"k{v}" for v in rng.integers(0, 5, 100)],
            "ts": np.arange(100, dtype=np.int64),
            "m": rng.integers(0, 50, 100).astype(np.int64)}
    d = str(tmp / "s0")
    SegmentCreator(TableConfig("ct"), schema).build(cols, d, "s0")
    m = load_segment(d).metadata
    state.upsert_segment(SegmentState(
        "s0", "ct_REALTIME", [], dir_path=d, num_docs=100,
        start_time=m.start_time, end_time=m.end_time))
    return state


class TestAutoStarTree:
    def test_cold_workload_schedules_nothing(self, tmp_path):
        tm = _manager(startree_state(tmp_path))
        tm.workload_provider = lambda: WorkloadRegistry("t_cold")
        assert tm.run_once()["generated"] == 0

    def test_hot_fingerprint_schedules_build(self, tmp_path):
        tm = _manager(startree_state(tmp_path))
        reg = WorkloadRegistry("t_hot")
        tm.workload_provider = lambda: reg
        # one cheap query: below both floors -> still nothing
        reg.record(tenant="t", table="ct_REALTIME", fingerprint="fp",
                   cpu_ms=10.0)
        assert tm.run_once()["generated"] == 0
        # repeated expensive fingerprint -> hot -> a build is scheduled
        for _ in range(2):
            reg.record(tenant="t", table="ct_REALTIME", fingerprint="fp",
                       cpu_ms=500.0)
        assert tm.run_once()["generated"] == 1
        (entry,) = tm.queue.list(PENDING)
        assert entry.task_type == "StarTreeBuildTask"
        assert entry.segments == ["s0"]

    def test_other_tables_heat_does_not_leak(self, tmp_path):
        """A hot fingerprint on an UNRELATED table must not trigger this
        table's builds."""
        tm = _manager(startree_state(tmp_path))
        reg = WorkloadRegistry("t_leak")
        tm.workload_provider = lambda: reg
        for _ in range(3):
            reg.record(tenant="t", table="other_REALTIME",
                       fingerprint="fp", cpu_ms=900.0)
        assert tm.run_once()["generated"] == 0


# ---------------------------------------------------------------------------
# bench --logs smoke (satellite d/f: the mixed-tenant OLAP-SLO scenario
# rides in tier-1 at smoke scale)
# ---------------------------------------------------------------------------
class TestBenchSmoke:
    def test_logs_bench_smoke(self, tmp_path):
        """The --logs acceptance scenario at smoke scale: pushdown A/B
        with bit-exact parity + clp_served metering, constant-different
        LIKE coalescing with ZERO steady-state retraces, realtime CLP
        ingestion with exactly-once convergence through a seeded
        mid-batch consumer kill, and the mixed-tenant window where the
        weighted OLAP fleet keeps serving beside log LIKE traffic."""
        import importlib
        import json
        import os
        import sys
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        bench = importlib.import_module("bench")
        out = str(tmp_path / "BENCH_logs_smoke.json")
        bench.logs_main(smoke=True, out_path=out)
        with open(out) as f:
            data = json.load(f)
        assert data["clp_served"] >= 5
        assert data["coalesce"]["retraces_steady"] == 0
        assert data["coalesce"]["batch_size_max"] >= 2
        assert data["ingest"]["exact"][0] == data["ingest"]["exact"][1]
        assert data["ingest"]["failed_queries"] == 0
        assert data["chaos"]["crashed"] and data["chaos"]["converged"]
        assert data["chaos"]["failed_queries"] == 0
        assert data["mixed_tenants"]["failed_queries"] == 0
        assert data["mixed_tenants"]["olap_queries"] > 0
        assert data["mixed_tenants"]["log_queries"] > 0
