"""HBM segment residency (ISSUE 6): the per-(segment, column)
device-resident tier with frequency-based admission (ops/residency.py).

Pins the tentpole properties deterministically:

  * cross-batch residency — a different pruned subset (or a batch that
    gained a segment) re-ships ONLY rows the device has never seen; the
    kernel-ready [S, D] block assembles on-device (the column transfer
    odometer is the witness)
  * admission — a cold one-pass scan cannot flush the hot working set;
    warmup-seeded rows bypass the frequency duel
  * invalidation — the segment-replace path drops the old version's
    resident rows while sparing the just-warmed live object's; a
    same-name/new-object segment can NEVER serve a stale block
  * warmup — SegmentWarmup replay stages the hot plans' columns into
    HBM (seeded) before the segment serves, including on an L2
    result-cache hit
  * params-cache bounding — a batch's predicate params evict with its
    last resident block instead of stranding until global LRU pressure
  * chaos — seeded segment replacement mid-traffic never serves a stale
    block and converges to the no-chaos run's results
"""
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              TableConfig, TableType)
from pinot_tpu.ops import residency as residency_mod
from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.ops.staging import batch_id
from pinot_tpu.ops.residency import ResidencyManager
from pinot_tpu.query.context import QueryContext
from pinot_tpu.segment.creator import SegmentCreator
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.utils.config import PinotConfiguration
from pinot_tpu.utils.failpoints import failpoints

SQL = "SELECT SUM(m), COUNT(*) FROM t WHERE d < 5"


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.clear()
    yield
    failpoints.clear()


def make_schema():
    return Schema("t", [
        FieldSpec("d", DataType.INT, FieldType.DIMENSION),
        FieldSpec("m", DataType.INT, FieldType.METRIC)])


def make_creator():
    tc = TableConfig("t", TableType.OFFLINE)
    tc.indexing.no_dictionary_columns = ["m"]
    return SegmentCreator(tc, make_schema())


def build_seg(tmp_path, name, n=4000, seed=11, m_value=None):
    rng = np.random.default_rng(seed)
    m = (np.full(n, m_value, dtype=np.int32) if m_value is not None
         else rng.integers(0, 100, n).astype(np.int32))
    p = str(tmp_path / f"{name}_{seed}_{m_value}")
    make_creator().build(
        {"d": rng.integers(0, 10, n).astype(np.int32), "m": m}, p, name)
    return load_segment(p)


@pytest.fixture()
def segs(tmp_path):
    return [build_seg(tmp_path, f"t_{i}", seed=11 + i) for i in range(3)]


def make_engine(**overrides):
    return TpuOperatorExecutor(config=PinotConfiguration(overrides=overrides))


def agg_values(results):
    return tuple(tuple(float(v) for v in r.intermediates) for r in results)


# ---------------------------------------------------------------------------
# ResidencyManager policy unit tests (no device work)
# ---------------------------------------------------------------------------

def _seg(name):
    return SimpleNamespace(name=name)


class TestAdmissionPolicy:
    def test_cold_scan_cannot_flush_hot_set(self):
        rm = ResidencyManager(300, admission=True, sample_window=10_000)
        hot = [_seg(f"h{i}") for i in range(3)]
        for s in hot:
            assert rm.get(s, "ids", "c", "i1") is None
            assert rm.admit(s, "ids", "c", "i1", object(), 100)
        for _ in range(5):  # build the working set's frequency
            for s in hot:
                assert rm.get(s, "ids", "c", "i1") is not None
        for i in range(5):  # one cold pass over another table
            c = _seg(f"cold{i}")
            rm.get(c, "ids", "c", "i1")
            assert not rm.admit(c, "ids", "c", "i1", object(), 100)
        assert rm.rejected == 5
        for s in hot:  # working set survived intact
            assert rm.get(s, "ids", "c", "i1") is not None

    def test_repeated_traffic_earns_admission(self):
        """A genuinely hot newcomer accrues frequency across its misses
        and eventually wins the duel against a colder victim."""
        rm = ResidencyManager(200, admission=True, sample_window=10_000)
        a, b = _seg("a"), _seg("b")
        for s in (a, b):
            rm.get(s, "ids", "c", "i1")
            assert rm.admit(s, "ids", "c", "i1", object(), 100)
        new = _seg("new")
        for _ in range(3):  # misses still count toward admission credit
            rm.get(new, "ids", "c", "i1")
        rm.get(new, "ids", "c", "i1")
        assert rm.admit(new, "ids", "c", "i1", object(), 100)
        assert rm.evicted == 1  # displaced the coldest resident

    def test_seeded_admission_bypasses_duel(self):
        rm = ResidencyManager(200, admission=True, sample_window=10_000)
        for name in ("a", "b"):
            s = _seg(name)
            for _ in range(10):
                rm.get(s, "ids", "c", "i1")
            rm.admit(s, "ids", "c", "i1", object(), 100)
        warm = _seg("warm")
        with rm.seeding():
            rm.get(warm, "ids", "c", "i1")
            assert rm.admit(warm, "ids", "c", "i1", object(), 100)
        assert rm.get(warm, "ids", "c", "i1") is not None

    def test_frequency_ages_out(self):
        rm = ResidencyManager(1000, admission=True, sample_window=64)
        s = _seg("s")
        for _ in range(40):
            rm.get(s, "ids", "c", "i1")
        peak = rm.frequency("s", "ids", "c")
        for i in range(40):  # unrelated traffic fills the sample window
            rm.get(_seg(f"o{i}"), "ids", "c", "i1")
        assert rm.frequency("s", "ids", "c") < peak

    def test_invalidate_spares_live_object(self):
        rm = ResidencyManager(1000)
        old, new = _seg("x"), _seg("x")
        rm.admit(old, "ids", "c", "i1", object(), 10)
        rm.admit(new, "ids", "c", "i1", object(), 10)
        assert rm.invalidate_segment("x", keep=new) == 1
        assert rm.get(new, "ids", "c", "i1") is not None
        assert rm.get(old, "ids", "c", "i1") is None


# ---------------------------------------------------------------------------
# Cross-batch residency through the engine
# ---------------------------------------------------------------------------

class TestCrossBatchResidency:
    def test_changed_batch_ships_zero_column_bytes(self, segs):
        """THE tentpole property: a different pruned subset of already-
        resident segments assembles its [S, D] blocks on-device — zero
        bytes cross the host->device link for columns."""
        eng = make_engine()
        ctx = QueryContext.from_sql(SQL)
        res, rem = eng.execute(segs, ctx)
        assert not rem
        want_sub = agg_values(make_engine().execute(segs[:2], ctx)[0])
        c0 = residency_mod.column_transfer_bytes()
        res2, rem2 = eng.execute(segs[:2], ctx)  # different composition
        assert not rem2
        assert residency_mod.column_transfer_bytes() == c0, \
            "resident rows were re-shipped for a recomposed batch"
        assert agg_values(res2) == want_sub  # on-device assembly is exact

    def test_new_segment_uploads_only_its_rows(self, segs):
        eng = make_engine()
        ctx = QueryContext.from_sql(SQL)
        start = residency_mod.column_transfer_bytes()
        eng.execute(segs[:2], ctx)
        two_segments = residency_mod.column_transfer_bytes() - start
        assert two_segments > 0
        c0 = residency_mod.column_transfer_bytes()
        m0 = eng.residency.misses
        res, rem = eng.execute(segs, ctx)  # one NEW segment joins
        assert not rem
        delta = residency_mod.column_transfer_bytes() - c0
        assert 0 < delta < two_segments  # only the newcomer's rows
        # exactly the new segment's two rows (ids:d + val:m) missed
        assert eng.residency.misses - m0 == 2
        assert agg_values(res) == agg_values(make_engine().execute(
            segs, ctx)[0])

    def test_hist_slot_params_cached_zero_steady_transfers(self, segs):
        """Histogram/tdigest slots carry per-batch bucket bounds; they
        ride the params cache like leaf params, so a repeated sketch
        query uploads nothing at all."""
        from pinot_tpu.query.executor import QueryExecutor
        eng = make_engine()
        ex = QueryExecutor(segs, use_tpu=True, engine=eng)
        sql = "SELECT PERCENTILETDIGEST95(m), COUNT(*) FROM t"
        r1 = ex.execute(sql)
        assert eng.stager._block_cache, "sketch query fell back to host"
        b0 = residency_mod.transfer_bytes()
        r2 = ex.execute(sql)
        assert residency_mod.transfer_bytes() == b0, \
            "repeated hist query re-uploaded slot params"
        assert r2.rows == r1.rows

    def test_group_by_blocks_ride_residency(self, segs):
        eng = make_engine()
        ctx = QueryContext.from_sql(
            "SELECT d, SUM(m) FROM t GROUP BY d")
        eng.execute(segs, ctx)
        c0 = residency_mod.column_transfer_bytes()
        res, rem = eng.execute(segs[:2], ctx)
        assert not rem
        assert residency_mod.column_transfer_bytes() == c0
        want = make_engine().execute(segs[:2], ctx)[0]
        got = {k: tuple(float(x) for x in v)
               for r in res for k, v in r.groups.items()}
        expect = {k: tuple(float(x) for x in v)
                  for r in want for k, v in r.groups.items()}
        assert got == expect


# ---------------------------------------------------------------------------
# Invalidation / identity
# ---------------------------------------------------------------------------

class TestInvalidation:
    def test_same_name_new_object_never_serves_stale(self, tmp_path):
        eng = make_engine()
        ctx = QueryContext.from_sql("SELECT SUM(m), COUNT(*) FROM t")
        v1 = build_seg(tmp_path, "t_0", n=500, m_value=1)
        v2 = build_seg(tmp_path, "t_0", n=500, m_value=2)
        r1, _ = eng.execute([v1], ctx)
        assert agg_values(r1) == ((500.0, 500.0),)
        r2, _ = eng.execute([v2], ctx)  # same name, new object
        assert agg_values(r2) == ((1000.0, 500.0),)
        r1b, _ = eng.execute([v1], ctx)  # and back — still exact
        assert agg_values(r1b) == ((500.0, 500.0),)

    def test_invalidate_segment_drops_every_tier(self, segs):
        eng = make_engine()
        ctx = QueryContext.from_sql(SQL)
        eng.execute(segs, ctx)
        name = segs[0].name
        assert eng.residency.resident_for(name) > 0
        eng.invalidate_segment(name)
        assert eng.residency.resident_for(name) == 0
        assert not any(any(s.name == name for s in e[0])
                       for e in eng.stager._block_cache.values())
        assert not any(any(s.name == name for s in v[0])
                       for v in eng.stager._params_cache.values())
        assert not any(v[0].name == name for v in eng.stager._host_rows.values())
        res, rem = eng.execute(segs, ctx)  # re-stages cleanly
        assert not rem and res

    def test_replace_event_swaps_residency_to_live_object(self, tmp_path):
        """Through the REAL server path: a same-name segment replace
        drops the old version's resident rows via the segment-event
        hook, warmup re-stages the new version (seeded) BEFORE it
        serves, and answers flip to the new data."""
        from pinot_tpu.server.data_manager import InstanceDataManager
        from pinot_tpu.server.datatable import deserialize_results
        from pinot_tpu.server.query_server import ServerQueryExecutor
        v1 = build_seg(tmp_path, "t_0", n=500, m_value=1)
        v2 = build_seg(tmp_path, "t_0", n=500, m_value=2)
        dm = InstanceDataManager("srv0")
        ex = ServerQueryExecutor(dm, use_tpu=True,
                                 config=PinotConfiguration())
        sql = "SELECT SUM(m), COUNT(*) FROM t"
        try:
            dm.table("t_OFFLINE").add_segment(v1)
            results, _exc, _st = deserialize_results(
                ex.execute("t_OFFLINE", sql))
            assert float(results[0].intermediates[0]) == 500.0
            eng = ex._shared_engine()
            assert eng.residency.resident_for("t_0") > 0
            dm.table("t_OFFLINE").add_segment(v2)  # replace
            with eng._engine_lock:
                pinned = [e[0] for k, e in
                          eng.residency._entries.items() if k[1] == "t_0"]
            # warmup re-staged the NEW object; the old one is gone
            assert pinned and all(p is v2 for p in pinned)
            results, _exc, _st = deserialize_results(
                ex.execute("t_OFFLINE", sql + " OPTION(skipCache=true)"))
            assert float(results[0].intermediates[0]) == 1000.0
        finally:
            dm.shutdown()
            ex.segment_cache.close()
            ex.fingerprint_log.close()


# ---------------------------------------------------------------------------
# Warmup -> proactive residency
# ---------------------------------------------------------------------------

class TestWarmupSeeding:
    def test_warm_stages_columns_seeded(self, segs):
        from pinot_tpu.cache.segment_cache import SegmentResultCache
        from pinot_tpu.cache.warmup import FingerprintLog, SegmentWarmup
        eng = make_engine()
        log = FingerprintLog()
        ctx = QueryContext.from_sql(SQL)
        log.record("t", ctx.fingerprint(), SQL)
        cache = SegmentResultCache()
        w = SegmentWarmup(log, cache, use_tpu=True, engine_fn=lambda: eng)
        assert w.warm("t", segs[0]) >= 1
        name = segs[0].name
        assert eng.residency.resident_for(name) > 0
        # seeded: one replay left MORE than one access worth of credit
        assert eng.residency.frequency(name, "val", "m") > 1
        # L2-hit path still prestages: drop the device tier, warm again —
        # the result cache hits, but columns come back resident anyway
        eng.stager.drop_caches()
        assert eng.residency.resident_for(name) == 0
        assert w.warm("t", segs[0]) >= 1
        assert eng.residency.resident_for(name) > 0


# ---------------------------------------------------------------------------
# Params-cache bounding (satellite)
# ---------------------------------------------------------------------------

class TestParamsCacheBounded:
    def test_params_evict_with_last_block(self, segs):
        # budget fits ONE batch's blocks (~295KB each), so staging batch
        # B evicts batch A's blocks — and with them A's params entries.
        # The knob is bytes PER CHIP: the engine's pool is knob x devices
        # (the conftest forces 8), so 62,500 a chip is the 500,000 pool
        eng = make_engine(**{"pinot.server.hbm.cache.bytes": 62_500})
        assert eng.stager.cache_budget_bytes == 62_500 * len(eng.devices) == 500_000
        ctx = QueryContext.from_sql(SQL)
        eng.execute(segs[:2], ctx)
        key_a = batch_id(segs[:2])
        assert any(k[0] == key_a for k in eng.stager._params_cache)
        eng.execute(segs, ctx)
        assert not any(k[0] == key_a for k in eng.stager._block_cache), \
            "test premise: batch A's blocks should have evicted"
        assert not any(k[0] == key_a for k in eng.stager._params_cache), \
            "params for a fully evicted batch were stranded"

    def test_invalidate_drops_params_for_segment(self, segs):
        eng = make_engine()
        ctx = QueryContext.from_sql(SQL)
        eng.execute(segs, ctx)
        assert eng.stager._params_cache
        eng.invalidate_segment(segs[1].name)
        assert not any(any(s.name == segs[1].name for s in v[0])
                       for v in eng.stager._params_cache.values())


# ---------------------------------------------------------------------------
# Chaos: segment replacement mid-traffic (acceptance)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
class TestResidencyChaos:
    SQL = "SELECT SUM(m), COUNT(*) FROM rt OPTION(skipCache=true)"

    def _run(self, tmp_path, tag, chaos=None):
        from pinot_tpu.cluster.mini import MiniCluster
        (tmp_path / tag).mkdir(exist_ok=True)
        v1 = build_seg(tmp_path / tag, "rt_0", n=400, m_value=1)
        v2 = build_seg(tmp_path / tag, "rt_0", n=400, m_value=2)
        c = MiniCluster(num_servers=1, use_tpu=True, chaos=chaos)
        c.start()
        try:
            c.add_table("rt")
            c.add_segment("rt", v1, server_idx=0)
            seen = []
            errors = []
            stop = threading.Event()

            def traffic():
                while not stop.is_set():
                    r = c.query(self.SQL)
                    if r.exceptions:
                        errors.append(r.exceptions)
                    elif r.rows:
                        seen.append(tuple(float(x) for x in r.rows[0]))

            threads = [threading.Thread(target=traffic) for _ in range(3)]
            for t in threads:
                t.start()
            time.sleep(0.3)
            c.add_segment("rt", v2, server_idx=0)  # replace mid-traffic
            time.sleep(0.3)
            stop.set()
            for t in threads:
                t.join()
            final = tuple(float(x) for x in c.query(self.SQL).rows[0])
            eng = c.servers[0].executor._engine
            pinned = []
            if eng is not None:
                with eng._engine_lock:
                    pinned = [e[0] for k, e in
                              eng.residency._entries.items()
                              if k[1] == "rt_0"]
            return {"seen": set(seen), "errors": errors, "final": final,
                    "stale_pins": [p for p in pinned if p is not v2]}
        finally:
            c.stop()

    def test_replace_mid_traffic_never_serves_stale(self, tmp_path):
        """ISSUE 6 acceptance: seeded chaos delaying execution around a
        same-name segment replace — every observed answer is exactly the
        old or the new version's (a stale resident block would produce
        either a wrong value or a torn mix), the final state converges
        to the no-chaos run's, and no stale object stays pinned."""
        v1_rows, v2_rows = (400.0, 400.0), (800.0, 400.0)
        baseline = self._run(tmp_path, "nochaos", chaos=None)
        assert baseline["final"] == v2_rows
        assert not baseline["errors"]
        assert baseline["seen"] <= {v1_rows, v2_rows}

        chaos = [
            ("server.execute.before",
             {"delay": 0.01, "probability": 0.5, "seed": 1234}),
            ("server.execute.segment",
             {"delay": 0.005, "probability": 0.5, "seed": 99}),
        ]
        run = self._run(tmp_path, "chaos", chaos=chaos)
        assert not run["errors"]
        assert run["seen"], "traffic never completed a query"
        assert run["seen"] <= {v1_rows, v2_rows}, \
            f"stale/torn answers observed: {run['seen']}"
        assert run["final"] == baseline["final"] == v2_rows
        assert not run["stale_pins"]
