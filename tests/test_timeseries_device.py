"""Time-series device leg (ISSUE 20): dashboards as device group-bys.

  * parity — the simpleql leaf's `floor((ts - start) / step)` bucket
    FUSES into the device group-by kernel's key
    (pinot.server.timeseries.bucket.enabled) and answers within f32
    tolerance of the host expression-column leaf, across aggregations
    and the full transform set; served leaves meter
    `timeseries_leaf_device`
  * retraces — start/step/count ride staged params (only the bucket
    digit's widths are in the plan): a sliding dashboard refresh causes
    ZERO retraces
  * simpleql parens — stage splitting is paren-depth aware: a where()
    predicate like `host = 'a(1)' AND floor(x / 2) > 1` stays ONE stage
    with its argument string verbatim (the old `[^)]*` regex stopped at
    the first close paren and broke both)
  * gapfill — the vectorized stacked-grid transforms
    (timeseries/gapfill.py) match their per-series NaN-aware references
  * leaf cap — the `pinot.timeseries.leaf.max.groups` knob bounds one
    leaf fetch (env-overridable); overflow fails LOUD, never truncates
  * selfmetrics — the PR-14 dogfood dashboards route through the
    device bucket leg (query_history(use_tpu=True)): a third device
    workload beside queries and log search
  * failpoints — `timeseries.leaf.fetch` arms with ctx matching; an
    armed error surfaces instead of silently serving
  * bench smoke — the --timeseries acceptance scenario rides tier-1
"""
import json
import os
import time

import numpy as np
import pytest

from pinot_tpu.health.history import MetricsHistory, MetricsSampler
from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              TableConfig)
from pinot_tpu.ops import kernels
from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.query.executor import QueryExecutor
from pinot_tpu.segment.creator import SegmentCreator
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.timeseries import gapfill
from pinot_tpu.timeseries.engine import _parse_simpleql
from pinot_tpu.timeseries.engine import query as ts_query
from pinot_tpu.timeseries.spi import (LeafTimeSeriesPlanNode,
                                      TimeSeriesAggregationNode)
from pinot_tpu.utils.config import PinotConfiguration
from pinot_tpu.utils.failpoints import SimulatedCrash, failpoints
from pinot_tpu.utils.metrics import MetricsRegistry

HOSTS = ["a(1)", "h1", "h2", "h3"]
T0, STEP, BUCKETS = 1000, 20, 6
T1 = T0 + BUCKETS * STEP


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.clear()
    yield
    failpoints.clear()


@pytest.fixture(scope="module")
def segs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("tssegs")
    schema = Schema("metrics", [
        FieldSpec("ts", DataType.LONG, FieldType.DIMENSION),
        FieldSpec("host", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("value", DataType.DOUBLE, FieldType.METRIC)])
    creator = SegmentCreator(TableConfig(name="metrics"), schema)
    out = []
    for i in range(2):
        rng = np.random.default_rng(900 + i)
        n = 2000
        seg_dir = os.path.join(str(tmp), f"m_{i}")
        creator.build({
            "ts": rng.integers(T0, T1, n),
            "host": np.array([HOSTS[v] for v in
                              rng.integers(0, len(HOSTS), n)], object),
            "value": rng.normal(size=n),
        }, seg_dir, f"m_{i}")
        out.append(load_segment(seg_dir))
    return out


def _engine(name, **overrides):
    return TpuOperatorExecutor(
        config=PinotConfiguration(overrides=overrides),
        metrics_labels={"ts_test": name})


def _meter(eng, name):
    return eng._metrics.meter(
        name, labels={"ts_test": eng._labels["ts_test"]})


def _dash(start=T0, tail="| groupby(host) | sum(host)"):
    return f"fetch(metrics, value, ts, {start}, {T1}, {STEP}) {tail}"


def _series_map(block):
    return {s.tag_key(): s.values for s in block.series}


def _assert_blocks_equal(a, b):
    da, db = _series_map(a), _series_map(b)
    assert set(da) == set(db)
    for key in da:
        # f32 device sums of SIGNED values: cancellation makes relative
        # error meaningless near zero, hence the atol floor
        np.testing.assert_allclose(da[key], db[key], rtol=1e-3,
                                   atol=1e-3, equal_nan=True)


# ---------------------------------------------------------------------------
# device bucket leg parity
# ---------------------------------------------------------------------------
class TestDeviceBucketParity:
    def test_dashboard_parity_and_meter(self, segs):
        eng = _engine("parity")
        dev = QueryExecutor(segs, use_tpu=True, engine=eng)
        host = QueryExecutor(segs, use_tpu=False)
        bd = ts_query(_dash(), dev)
        bh = ts_query(_dash(), host)
        assert _meter(eng, "timeseries_leaf_device") >= 1
        assert len(bd.series) == len(HOSTS)
        _assert_blocks_equal(bd, bh)

    def test_transform_pipeline_parity(self, segs):
        eng = _engine("transforms")
        dev = QueryExecutor(segs, use_tpu=True, engine=eng)
        host = QueryExecutor(segs, use_tpu=False)
        for tail in [
            "| sum()",
            "| sum() | rate()",
            "| groupby(host) | avg(host) | gapfill(5)",
            "| groupby(host) | max(host) | interpolate()",
            "| groupby(host) | min(host) | keep_last_value()",
            "| groupby(host) | sum(host) | scale(2.5)",
        ]:
            _assert_blocks_equal(ts_query(_dash(tail=tail), dev),
                                 ts_query(_dash(tail=tail), host))
        assert _meter(eng, "timeseries_leaf_device") >= 6

    def test_knob_disables_the_leg(self, segs):
        eng = _engine("knob", **{
            "pinot.server.timeseries.bucket.enabled": False})
        dev = QueryExecutor(segs, use_tpu=True, engine=eng)
        host = QueryExecutor(segs, use_tpu=False)
        _assert_blocks_equal(ts_query(_dash(), dev),
                             ts_query(_dash(), host))
        assert _meter(eng, "timeseries_leaf_device") == 0


class TestZeroRetraceSliding:
    def test_sliding_window_shares_one_kernel(self, segs):
        """The dashboard steady state: start advances every refresh;
        start/step/count ride params, so the warm kernel replays with
        ZERO retraces."""
        eng = _engine("slide")
        dev = QueryExecutor(segs, use_tpu=True, engine=eng)
        host = QueryExecutor(segs, use_tpu=False)
        ts_query(_dash(T0), dev)   # warm the shape bucket
        t0c = kernels.trace_count()
        for j in range(1, 5):
            start = T0 + j * STEP
            _assert_blocks_equal(ts_query(_dash(start), dev),
                                 ts_query(_dash(start), host))
        assert kernels.trace_count() == t0c
        assert _meter(eng, "timeseries_leaf_device") >= 5


# ---------------------------------------------------------------------------
# simpleql paren-depth splitting (satellite)
# ---------------------------------------------------------------------------
class TestSimpleqlParens:
    def test_where_with_parens_stays_one_stage(self):
        node = _parse_simpleql(
            "fetch(m, value, ts, 0, 100, 10) "
            "| where(host = 'a(1)' AND floor(x / 2) > 1) "
            "| groupby(host) | sum(host)")
        assert isinstance(node, TimeSeriesAggregationNode)
        leaf = node.child
        assert isinstance(leaf, LeafTimeSeriesPlanNode)
        assert leaf.filter_sql == "host = 'a(1)' AND floor(x / 2) > 1"
        assert leaf.group_by_tags == ("host",)

    def test_function_call_commas_stay_one_argument(self):
        node = _parse_simpleql(
            "fetch(m, value, ts, 0, 100, 10) "
            "| where(mod(x, 3) = 1 AND host IN ('a', 'b')) | sum()")
        leaf = node.child
        assert leaf.filter_sql == "mod(x, 3) = 1 AND host IN ('a', 'b')"

    def test_unbalanced_parens_raise(self):
        for bad in [
            "fetch(m, value, ts, 0, 100, 10) | where(floor(x > 1)",
            "fetch(m, value, ts, 0, 100, 10) | sum(",
        ]:
            with pytest.raises(ValueError):
                _parse_simpleql(bad)

    def test_paren_host_value_end_to_end(self, segs):
        """A tag literally containing parens filters correctly through
        the verbatim where() predicate — on both leaf paths."""
        eng = _engine("paren")
        dev = QueryExecutor(segs, use_tpu=True, engine=eng)
        host = QueryExecutor(segs, use_tpu=False)
        q = _dash(tail="| where(host = 'a(1)') | groupby(host) "
                       "| sum(host)")
        bd, bh = ts_query(q, dev), ts_query(q, host)
        assert len(bd.series) == 1
        assert bd.series[0].tags == {"host": "a(1)"}
        _assert_blocks_equal(bd, bh)


# ---------------------------------------------------------------------------
# vectorized gapfill transforms
# ---------------------------------------------------------------------------
class TestGapfillUnits:
    A = np.array([[np.nan, 1.0, np.nan, np.nan, 4.0, np.nan],
                  [2.0, np.nan, 3.0, np.nan, np.nan, np.nan],
                  [np.nan] * 6])

    def test_keep_last_value(self):
        out = gapfill.keep_last_value(self.A.copy())
        np.testing.assert_allclose(
            out[0], [np.nan, 1, 1, 1, 4, 4], equal_nan=True)
        np.testing.assert_allclose(out[1], [2, 2, 3, 3, 3, 3])
        assert np.isnan(out[2]).all()

    def test_gapfill_constant(self):
        out = gapfill.gapfill(self.A.copy(), 7.5)
        np.testing.assert_allclose(out[0], [7.5, 1, 7.5, 7.5, 4, 7.5])
        np.testing.assert_allclose(out[2], [7.5] * 6)

    def test_interpolate_interior_only(self):
        out = gapfill.interpolate(self.A.copy())
        # interior gaps fill linearly; leading/trailing stay NaN
        np.testing.assert_allclose(
            out[0], [np.nan, 1, 2, 3, 4, np.nan], equal_nan=True)
        np.testing.assert_allclose(
            out[1], [2, 2.5, 3, np.nan, np.nan, np.nan], equal_nan=True)

    def test_rate(self):
        arr = np.array([[0.0, 10.0, 30.0, 30.0]])
        out = gapfill.rate(arr, step=10)
        np.testing.assert_allclose(
            out[0], [np.nan, 1.0, 2.0, 0.0], equal_nan=True)

    def test_aggregate_matches_nan_references(self):
        rng = np.random.default_rng(3)
        stacked = rng.normal(size=(10, 7))
        stacked[rng.random(stacked.shape) < 0.3] = np.nan
        stacked[4] = np.nan   # one all-NaN series
        gids = np.array([0, 0, 1, 1, 1, 2, 2, 0, 2, 1])
        import warnings
        for agg, ref in [("sum", np.nansum), ("avg", np.nanmean),
                         ("min", np.nanmin), ("max", np.nanmax)]:
            out = gapfill.aggregate(stacked, gids, 3, agg)
            for g in range(3):
                rows = stacked[gids == g]
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", RuntimeWarning)
                    want = ref(rows, axis=0)
                # all-NaN buckets stay NaN (nansum would say 0)
                want = np.where(np.isnan(rows).all(axis=0), np.nan, want)
                np.testing.assert_allclose(out[g], want, equal_nan=True)


# ---------------------------------------------------------------------------
# leaf group cap knob (satellite)
# ---------------------------------------------------------------------------
class TestLeafCapKnob:
    def test_env_override_caps_the_fetch(self, segs, monkeypatch):
        monkeypatch.setenv("PINOT_TPU_TIMESERIES_LEAF_MAX_GROUPS", "1")
        host = QueryExecutor(segs, use_tpu=False)
        # 4 hosts x 6 buckets = 24 group rows > count * 1 = 6: LOUD,
        # never a silent truncation that skews downstream sums
        with pytest.raises(RuntimeError, match="cap"):
            ts_query(_dash(), host)

    def test_default_cap_admits_the_dashboard(self, segs):
        host = QueryExecutor(segs, use_tpu=False)
        block = ts_query(_dash(), host)
        assert len(block.series) == len(HOSTS)


# ---------------------------------------------------------------------------
# selfmetrics dashboards through the device leg
# ---------------------------------------------------------------------------
class TestSelfMetricsDevice:
    def test_dogfood_dashboard_serves_device_side(self, segs):
        from pinot_tpu.health.selfmetrics import query_history
        role = "selfm-dev"
        reg = MetricsRegistry(role)
        hist = MetricsHistory(64)
        sampler = MetricsSampler(role, history=hist, registry=reg)
        base = int(time.time())
        for i in range(10):
            reg.add_meter("queries", 4)
            s = sampler.sample_once()
            s["ts"] = base + i
        eng = _engine("selfm")
        served0 = _meter(eng, "timeseries_leaf_device")
        block = query_history(
            f"fetch(selfmetrics, value, ts, {base}, {base + 10}, 1) "
            f"| where(family = 'queries') | sum() | rate()",
            role=role, history=hist, use_tpu=True, engine=eng)
        assert _meter(eng, "timeseries_leaf_device") > served0
        vals = block.series[0].values
        assert np.allclose(vals[1:], 4.0)


# ---------------------------------------------------------------------------
# failpoint: timeseries.leaf.fetch
# ---------------------------------------------------------------------------
class TestLeafFetchFailpoint:
    def test_armed_site_fires_with_ctx_match(self, segs):
        host = QueryExecutor(segs, use_tpu=False)
        with failpoints.armed("timeseries.leaf.fetch",
                              where={"table": "metrics"}) as fp:
            ts_query(_dash(), host)
            assert fp.fired == 1
        with failpoints.armed("timeseries.leaf.fetch",
                              where={"table": "other"}) as fp:
            ts_query(_dash(), host)
            assert fp.fired == 0

    def test_armed_error_surfaces(self, segs):
        host = QueryExecutor(segs, use_tpu=False)
        with failpoints.armed("timeseries.leaf.fetch",
                              error=SimulatedCrash("leaf kill")):
            with pytest.raises(SimulatedCrash):
                ts_query(_dash(), host)
        assert len(ts_query(_dash(), host).series) == len(HOSTS)


# ---------------------------------------------------------------------------
# bench --timeseries smoke (the acceptance scenario rides tier-1)
# ---------------------------------------------------------------------------
class TestBenchSmoke:
    def test_timeseries_bench_smoke(self, tmp_path):
        import importlib
        import sys
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        bench = importlib.import_module("bench")
        out = str(tmp_path / "BENCH_timeseries_smoke.json")
        bench.timeseries_main(smoke=True, out_path=out)
        with open(out) as f:
            data = json.load(f)
        assert data["slide_retraces"] == 0
        assert data["selfmetrics_device"] is True
        assert data["timeseries_leaf_device"] >= 1
