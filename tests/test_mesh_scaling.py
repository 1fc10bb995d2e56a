"""Multi-chip scale-out acceptance suite (ISSUE 19).

The conftest forces an 8-device virtual CPU platform; engines here get
explicit (segments x docs) meshes so the collective broker merge
(ops/collective.py) is the path under test: per-segment partials fold
ON DEVICE — one psum/pmin/pmax over the whole mesh — instead of being
shipped to the host IndexedTable fold. Covered:

  * real-SQL parity vs the host executor on 1x1 / 2x2 / 4x2 meshes;
  * property test: merged rows are BIT-IDENTICAL to the escape hatch
    (`pinot.server.mesh.collective.merge=false`, the host fold) across
    randomized agg/group-by/filter shapes — integer columns under the
    test suite's x64 staging make exact equality legitimate;
  * zero steady-state retraces across repeated merged launches;
  * per-chip residency observability: `hbm_cache_bytes{device=}` /
    `hbm_resident_bytes{device=}` gauges and the /debug/health rollup;
  * per-chip admission: a skewed mesh rejects on the MOST-LOADED chip
    while the pooled number still looks healthy;
  * the `server.mesh.collective` failpoint: armed errors fall back to
    the host fold (mesh_merge_fallback{reason=chaos}) with correct
    rows, and same-seed decision journals replay byte-identical;
  * `bench.py --mesh --smoke` end to end (BENCH_mesh.json contract).
"""
import json

import numpy as np
import pytest

import jax

from pinot_tpu.ops import kernels
from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.ops.residency import ResidencyManager
from pinot_tpu.parallel.mesh import make_mesh
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.executor import QueryExecutor
from pinot_tpu.server.admission import AdmissionController
from pinot_tpu.utils.config import PinotConfiguration
from pinot_tpu.utils.failpoints import FailpointError, failpoints
from tests.queries.harness import (
    build_segments, synthetic_columns, synthetic_schema,
    synthetic_table_config)

NUM_DOCS = 700  # not a power of two: padding must mask right
#: (total devices, doc axis) -> 1x1, 2x2, 4x2 (segments x docs)
MESH_SHAPES = [(1, 1), (4, 2), (8, 2)]


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.clear()
    yield
    failpoints.clear()


@pytest.fixture(scope="module")
def segs(tmp_path_factory):
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    tmp = tmp_path_factory.mktemp("mesh_scaling")
    data = [synthetic_columns(NUM_DOCS, seed=131 + i) for i in range(6)]
    return build_segments(tmp, synthetic_schema(), synthetic_table_config(),
                          data)


@pytest.fixture(scope="module")
def host(segs):
    return QueryExecutor(segs, use_tpu=False)


def _mesh_engine(n, doc_axis, labels=None, **overrides):
    cfg = PinotConfiguration(overrides=overrides) if overrides else None
    mesh = make_mesh(jax.devices()[:n], doc_axis=doc_axis)
    return TpuOperatorExecutor(mesh=mesh, config=cfg,
                               metrics_labels=labels)


def _assert_parity(dr, hr, exact=False):
    assert not dr.exceptions and not hr.exceptions, (
        dr.exceptions, hr.exceptions)
    assert len(dr.rows) == len(hr.rows), (dr.rows, hr.rows)
    for a, b in zip(dr.rows, hr.rows):
        for x, y in zip(a, b):
            if exact or not (isinstance(x, float) or isinstance(y, float)):
                assert x == y, (dr.rows, hr.rows)
            else:
                assert abs(float(x) - float(y)) <= \
                    1e-5 * max(1.0, abs(float(y))), (dr.rows, hr.rows)


PARITY_SQLS = [
    "SELECT SUM(intCol), COUNT(*), MIN(intCol), MAX(intCol) "
    "FROM testTable WHERE intCol > 250",
    "SELECT SUM(intCol * rawIntCol), AVG(intCol) FROM testTable "
    "WHERE stringCol IN ('s1', 's4', 's8') AND intCol < 800",
    "SELECT groupCol, COUNT(*), SUM(intCol), MIN(rawIntCol) "
    "FROM testTable GROUP BY groupCol ORDER BY groupCol LIMIT 50",
    "SELECT stringCol, groupCol, COUNT(*), MAX(intCol) FROM testTable "
    "GROUP BY stringCol, groupCol ORDER BY COUNT(*) DESC, stringCol, "
    "groupCol LIMIT 25",
]


class TestMeshParity:
    """Real SQL, every mesh geometry, parity vs the host executor."""

    @pytest.mark.parametrize("n,doc_axis", MESH_SHAPES)
    def test_sql_parity(self, segs, host, n, doc_axis):
        engine = _mesh_engine(n, doc_axis)
        device = QueryExecutor(segs, use_tpu=True, engine=engine)
        for sql in PARITY_SQLS:
            _assert_parity(device.execute(sql), host.execute(sql))
        if n > 1:
            reg = engine._dispatcher._metrics
            assert reg.meter("mesh_merge_served") > 0, \
                "multi-chip parity queries never took the merged path"


def _random_sql(rng):
    """A random agg/group-by/filter shape over the integer columns —
    integer data + x64 staging keep every aggregate exactly
    representable, so merged-vs-host-fold comparison is == not ~=."""
    aggs = list(rng.choice(
        ["SUM(intCol)", "COUNT(*)", "MIN(intCol)", "MAX(rawIntCol)",
         "SUM(rawIntCol)", "AVG(intCol)", "SUM(intCol * rawIntCol)",
         "MIN(rawIntCol)", "MAX(intCol)"],
        size=rng.integers(1, 4), replace=False))
    filters = ["", " WHERE intCol > %d" % rng.integers(0, 900),
               " WHERE rawIntCol BETWEEN %d AND %d" % (
                   rng.integers(0, 40), rng.integers(50, 120)),
               " WHERE stringCol IN ('s1', 's5') AND intCol < %d"
               % rng.integers(200, 1000)]
    where = filters[rng.integers(0, len(filters))]
    group = ["", "groupCol", "stringCol", "stringCol, groupCol"][
        rng.integers(0, 4)]
    if group:
        sql = (f"SELECT {group}, {', '.join(aggs)} FROM testTable"
               f"{where} GROUP BY {group} ORDER BY {group} LIMIT 200")
    else:
        sql = f"SELECT {', '.join(aggs)} FROM testTable{where}"
    return sql


class TestCollectiveBitParity:
    """The merged collective vs the host-fold escape hatch: same rows,
    BIT-identical, across randomized query shapes."""

    def test_property_merged_equals_host_fold(self, segs):
        eng_on = _mesh_engine(8, 2, labels={"leg": "bp_on"})
        eng_off = _mesh_engine(
            8, 2, labels={"leg": "bp_off"},
            **{"pinot.server.mesh.collective.merge": False})
        ex_on = QueryExecutor(segs, use_tpu=True, engine=eng_on)
        ex_off = QueryExecutor(segs, use_tpu=True, engine=eng_off)
        rng = np.random.default_rng(20260807)
        for _ in range(12):
            sql = _random_sql(rng)
            r_on = ex_on.execute(sql)
            r_off = ex_off.execute(sql)
            assert not r_on.exceptions and not r_off.exceptions, (
                sql, r_on.exceptions, r_off.exceptions)
            assert r_on.rows == r_off.rows, (
                f"merged path diverged from host fold: {sql}: "
                f"{r_on.rows} vs {r_off.rows}")
        # the registry is process-global: scope reads by each engine's
        # label so the two engines' counters stay distinguishable
        reg = eng_on._dispatcher._metrics
        assert reg.meter("mesh_merge_served",
                         labels={"leg": "bp_on"}) > 0
        # the escape hatch is a REAL knob: the off engine metered every
        # eligible query as a disabled-reason fallback
        assert reg.meter("mesh_merge_fallback",
                         labels={"leg": "bp_off",
                                 "reason": "disabled"}) > 0
        assert reg.meter("mesh_merge_served",
                         labels={"leg": "bp_off"}) == 0


class TestZeroRetrace:
    def test_steady_state_merged_launches_never_retrace(self, segs):
        engine = _mesh_engine(8, 2)
        device = QueryExecutor(segs, use_tpu=True, engine=engine)
        warm = [
            "SELECT SUM(intCol), COUNT(*) FROM testTable WHERE intCol > 100",
            "SELECT groupCol, COUNT(*), SUM(intCol) FROM testTable "
            "WHERE intCol > 100 GROUP BY groupCol "
            "ORDER BY groupCol LIMIT 50",
        ]
        for sql in warm:
            device.execute(sql)
        traces0 = kernels.trace_count()
        # same plan shapes, fresh filter constants: params change,
        # the compiled merged kernel must not
        for lo in (150, 300, 450, 600):
            device.execute(
                f"SELECT SUM(intCol), COUNT(*) FROM testTable "
                f"WHERE intCol > {lo}")
            device.execute(
                f"SELECT groupCol, COUNT(*), SUM(intCol) FROM testTable "
                f"WHERE intCol > {lo} GROUP BY groupCol "
                f"ORDER BY groupCol LIMIT 50")
        assert kernels.trace_count() == traces0, \
            "steady-state retrace on the merged path"


class TestPerChipObservability:
    def test_per_device_gauges_emitted(self, segs):
        engine = _mesh_engine(8, 2)
        device = QueryExecutor(segs, use_tpu=True, engine=engine)
        device.execute("SELECT SUM(intCol), COUNT(*) FROM testTable "
                       "WHERE intCol > 100")
        reg = engine._dispatcher._metrics
        # pooled gauge stays (dashboards keyed on it keep working) ...
        assert reg.gauge("hbm_cache_bytes") is not None
        # ... and every chip gets its own split under a device= label
        labels = [f"{d.platform}:{d.id}" for d in engine.devices]
        assert len(labels) == 8
        for lab in labels:
            assert reg.gauge("hbm_cache_bytes",
                             labels={"device": lab}) is not None, lab
            assert reg.gauge("hbm_resident_bytes",
                             labels={"device": lab}) is not None, lab
        # resident rows were committed to specific chips — the split is
        # real attribution, not an even smear
        by_dev = engine.residency.bytes_by_device()
        assert sum(by_dev.values()) == engine.residency.bytes
        assert sum(reg.gauge("hbm_resident_bytes", labels={"device": lab})
                   for lab in labels) == engine.residency.bytes

    def test_health_rollup_reports_max_device(self, segs):
        from pinot_tpu.health.rollup import role_health_summary
        engine = _mesh_engine(8, 2)
        device = QueryExecutor(segs, use_tpu=True, engine=engine)
        device.execute("SELECT SUM(intCol), COUNT(*) FROM testTable "
                       "WHERE intCol > 100")
        out = role_health_summary(
            "server", registry=engine._dispatcher._metrics)
        hbm = out["subsystems"]["hbm"]
        assert hbm["ok"] and hbm["totalBytes"] > 0
        assert hbm["maxDevice"] in {f"{d.platform}:{d.id}"
                                    for d in engine.devices}
        assert hbm["maxDeviceBytes"] == \
            max(hbm["perDeviceBytes"].values())
        assert len(hbm["perDeviceBytes"]) == 8


class _FakeDev:
    def __init__(self, i):
        self.platform = "cpu"
        self.id = i


class _FakeSeg:
    def __init__(self, name):
        self.name = name


class TestSkewedMeshAdmission:
    """Per-chip budgeting: one hot chip trips admission long before the
    POOLED number looks full — the pooled view hides exactly the skew
    that OOMs a single chip."""

    def test_pressure_tracks_most_loaded_chip(self):
        rm = ResidencyManager(1000, admission=False,
                              devices=[_FakeDev(i) for i in range(4)])
        assert rm.device_budget_bytes == 250
        segs = [_FakeSeg(f"seg{i}") for i in range(4)]
        # skew: chip cpu:0 nearly full, others nearly empty
        assert rm.admit(segs[0], "fwd", "a", "i64", "row", 240,
                        device="cpu:0")
        assert rm.admit(segs[1], "fwd", "a", "i64", "row", 10,
                        device="cpu:1")
        # pooled fill is 25% — healthy; the max chip is at 96%
        assert rm.bytes == 250
        assert rm.max_device_bytes() == 240
        assert rm.pressure() == pytest.approx(240 / 250)

    def test_admission_rejects_on_skewed_chip(self):
        rm = ResidencyManager(1000, admission=False,
                              devices=[_FakeDev(i) for i in range(4)])
        rm.admit(_FakeSeg("s"), "fwd", "a", "i64", "row", 245,
                 device="cpu:0")
        ac = AdmissionController(num_threads=2, memory_threshold=0.95,
                                 memory_pressure_fn=rm.pressure)
        rej = ac.admit(table="t")
        assert rej is not None and "memory pressure" in str(rej)
        # drain the hot chip -> admission recovers
        rm.drop_all()
        ac._pressure_at = 0.0  # expire the memo
        assert ac.admit(table="t") is None

    def test_per_chip_share_evicts_only_that_chip(self):
        rm = ResidencyManager(1000, admission=False,
                              devices=[_FakeDev(i) for i in range(4)])
        keep = _FakeSeg("keep")
        rm.admit(keep, "fwd", "cold", "i64", "row", 200, device="cpu:1")
        victims = [_FakeSeg(f"v{i}") for i in range(3)]
        for i, s in enumerate(victims):
            rm.admit(s, "fwd", f"c{i}", "i64", "row", 100, device="cpu:0")
        # chip0 at 300/250 after this admit: ITS oldest rows evict;
        # chip1's resident row must survive untouched
        assert rm.admit(_FakeSeg("hot"), "fwd", "hot", "i64", "row", 100,
                        device="cpu:0")
        by_dev = rm.bytes_by_device()
        assert by_dev["cpu:1"] == 200
        assert by_dev["cpu:0"] <= rm.device_budget_bytes

    def test_oversized_row_declined_against_chip_share(self):
        rm = ResidencyManager(1000, admission=False,
                              devices=[_FakeDev(i) for i in range(4)])
        # fits the pooled budget, can never fit one chip's share
        assert not rm.admit(_FakeSeg("big"), "fwd", "big", "i64", "row",
                            400, device="cpu:0")
        assert rm.bytes == 0


class TestMeshCollectiveFailpoint:
    def test_armed_error_falls_back_to_host_fold(self, segs, host):
        engine = _mesh_engine(8, 2)
        device = QueryExecutor(segs, use_tpu=True, engine=engine)
        # ungrouped: the collective merge's shape (a GROUP BY is folded
        # inside the plain kernel on every engine, kernels.fold_groups)
        sql = ("SELECT COUNT(*), SUM(intCol), MAX(rawIntCol) FROM testTable "
               "WHERE intCol < 900")
        with failpoints.armed("server.mesh.collective",
                              error=FailpointError("mesh chaos")):
            _assert_parity(device.execute(sql), host.execute(sql))
        reg = engine._dispatcher._metrics
        assert reg.meter("mesh_merge_fallback",
                         labels={"reason": "chaos"}) > 0
        # disarmed: the merged path resumes on the SAME engine
        served0 = reg.meter("mesh_merge_served")
        _assert_parity(device.execute(sql), host.execute(sql))
        assert reg.meter("mesh_merge_served") > served0

    def test_same_seed_journals_replay_byte_identical(self, segs):
        engine = _mesh_engine(8, 2)
        device = QueryExecutor(segs, use_tpu=True, engine=engine)
        sqls = [f"SELECT SUM(intCol), COUNT(*) FROM testTable "
                f"WHERE intCol > {lo}" for lo in (100, 300, 500, 700)]

        def run():
            with failpoints.armed("server.mesh.collective",
                                  error=FailpointError("mesh chaos"),
                                  probability=0.5, seed=7) as fp:
                for sql in sqls:
                    r = device.execute(sql)
                    assert not r.exceptions, r.exceptions
                return json.dumps(fp.decisions).encode()

        j1, j2 = run(), run()
        assert j1 == j2, "same-seed chaos journals diverged"
        assert b"true" in j1, "the 0.5 coin never fired in 4 queries"


class TestShardMapOneHotScan:
    """G <= ONEHOT_MAX_GROUPS with >= _ONEHOT_CHUNK docs per doc shard
    takes _scatter_sum's chunked one-hot `lax.scan` INSIDE shard_map —
    the parity segments above (700 docs) never reach it, so only a bench
    smoke leg used to."""

    def test_small_group_by_over_scan_chunk(self, tmp_path):
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 virtual devices")
        docs = 2 * kernels._ONEHOT_CHUNK - 91  # pads to 4096 per doc shard
        segs = build_segments(
            tmp_path, synthetic_schema(), synthetic_table_config(),
            [synthetic_columns(docs, seed=977 + i) for i in range(2)])
        labels = {"leg": "onehot-scan"}
        engine = _mesh_engine(4, 2, labels=labels)
        sql = ("SELECT groupCol, COUNT(*), SUM(intCol), MAX(rawIntCol) "
               "FROM testTable WHERE intCol < 900 GROUP BY groupCol "
               "ORDER BY groupCol LIMIT 50")
        before = sum(1 for e in kernels.trace_log()
                     if e["kind"] in ("sharded", "merged"))
        _assert_parity(
            QueryExecutor(segs, use_tpu=True, engine=engine).execute(sql),
            QueryExecutor(segs, use_tpu=False).execute(sql))
        traced = [e for e in kernels.trace_log()
                  if e["kind"] in ("sharded", "merged")][before:]
        assert traced, "group-by never reached a shard_map kernel"
        # a doc-sharded GROUP BY leaves the device a result a segment:
        # neither the collective merge nor the plain kernels' fold
        reg = engine._dispatcher._metrics
        assert reg.meter("mesh_merge_served", labels=labels) == 0
        assert reg.meter("group_fold",
                         labels=dict(labels, where="host")) > 0


# tier-1 smoke of the acceptance driver
class TestMeshBenchSmoke:
    def test_mesh_bench_smoke(self, tmp_path):
        """The --mesh acceptance scenario at smoke scale: weak-scaling
        segments-axis leg + one-huge-segment doc-axis leg, merged
        collective A/B'd against the host fold, bit-parity and zero
        steady-state retraces asserted inside."""
        import importlib
        import os
        import sys
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        bench = importlib.import_module("bench")
        out = str(tmp_path / "BENCH_mesh_smoke.json")
        bench.mesh_main(smoke=True, out_path=out)
        with open(out) as f:
            data = json.load(f)
        assert data["metric"] == "mesh_weak_scaling_efficiency"
        assert [p["devices"] for p in data["segments_axis"]] == [1, 2]
        for p in data["segments_axis"]:
            assert p["retraces_steady"] == 0
            assert p["rows_per_sec"] > 0
        assert data["segments_axis"][-1]["merge_served"] > 0
        assert data["doc_axis"]["segments"] == 1
        assert data["doc_axis"]["retraces_steady"] == 0


class TestMergeKnobAndContext:
    def test_single_device_mesh_never_merges(self, segs, host):
        """A 1-device engine has nothing to fold across — the merged
        branch must not engage (and must not meter a fallback: there
        was no mesh decision to make)."""
        engine = _mesh_engine(1, 1, labels={"leg": "one"})
        device = QueryExecutor(segs, use_tpu=True, engine=engine)
        _assert_parity(device.execute(PARITY_SQLS[0]),
                       host.execute(PARITY_SQLS[0]))
        reg = engine._dispatcher._metrics
        assert reg.meter("mesh_merge_served",
                         labels={"leg": "one"}) == 0
        assert reg.meter("mesh_merge_fallback",
                         labels={"leg": "one", "reason": "disabled"}) == 0
