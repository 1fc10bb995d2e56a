"""Two-tier query result cache (pinot_tpu/cache/): broker whole-result
cache + server per-segment partial cache with version-based invalidation.

Covers the hard part explicitly: correctness under mutation — queries
racing segment replace and realtime appends must never see stale reads,
and on a hybrid table only the mutable tail re-executes.
"""
import threading
import time

import numpy as np
import pytest

from pinot_tpu.cache import (BrokerResultCache, LruTtlCache,
                             SegmentResultCache, segment_version)
from pinot_tpu.cache.segment_cache import (is_cacheable_segment,
                                           is_cacheable_shape)
from pinot_tpu.cluster.mini import MiniCluster
from pinot_tpu.ingest.mutable_segment import MutableSegment
from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              TableConfig, TableType)
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.executor import QueryExecutor
from pinot_tpu.segment.creator import SegmentCreator
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.server.data_manager import InstanceDataManager, TableDataManager


def _schema():
    return Schema.from_dict({
        "schemaName": "t",
        "dimensionFieldSpecs": [{"name": "d", "dataType": "LONG"}],
        "metricFieldSpecs": [{"name": "m", "dataType": "LONG"}]})


def _table_config():
    return TableConfig.from_dict({"tableName": "t", "tableType": "OFFLINE"})


def _build(tmp_path, name, d, m):
    out = str(tmp_path / name)
    SegmentCreator(_table_config(), _schema()).build(
        {"d": np.asarray(d, np.int64), "m": np.asarray(m, np.int64)},
        out, name)
    return load_segment(out)


# ---------------------------------------------------------------------------
class TestFingerprint:
    def test_stable_and_canonical(self):
        sql = "SELECT SUM(m), d FROM t WHERE d > 3 GROUP BY d LIMIT 7"
        a = QueryContext.from_sql(sql).fingerprint()
        b = QueryContext.from_sql(sql).fingerprint()
        assert a == b

    def test_cache_options_do_not_change_fingerprint(self):
        base = QueryContext.from_sql("SELECT COUNT(*) FROM t")
        skip = QueryContext.from_sql(
            "SELECT COUNT(*) FROM t OPTION(skipCache=true)")
        trace = QueryContext.from_sql(
            "SELECT COUNT(*) FROM t OPTION(trace=true)")
        assert base.fingerprint() == skip.fingerprint() == trace.fingerprint()

    def test_result_affecting_parts_change_fingerprint(self):
        fps = {QueryContext.from_sql(sql).fingerprint() for sql in [
            "SELECT COUNT(*) FROM t",
            "SELECT COUNT(*) FROM t2",
            "SELECT COUNT(*) FROM t WHERE d = 1",
            "SELECT COUNT(*) FROM t GROUP BY d",
            "SELECT COUNT(*) FROM t LIMIT 5",
            "SELECT COUNT(*) FROM t OPTION(numGroupsLimit=10)",
            "SELECT DISTINCT d FROM t",
        ]}
        assert len(fps) == 7


class TestLruTtlCache:
    def test_lru_eviction_respects_recency(self):
        c = LruTtlCache(max_bytes=10, ttl_seconds=60)
        c.put("a", b"xxxx")
        c.put("b", b"yyyy")
        assert c.get("a") == b"xxxx"     # refresh a
        c.put("c", b"zzzz")              # over budget: evicts b, not a
        assert c.get("b") is None
        assert c.get("a") == b"xxxx"
        assert c.stats.evictions == 1

    def test_ttl_expiry(self):
        t = [0.0]
        c = LruTtlCache(max_bytes=100, ttl_seconds=5, clock=lambda: t[0])
        c.put("k", b"v")
        assert c.get("k") == b"v"
        t[0] = 5.1
        assert c.get("k") is None
        assert c.stats.expirations == 1

    def test_oversized_payload_refused(self):
        c = LruTtlCache(max_bytes=4, ttl_seconds=60)
        assert not c.put("k", b"12345")
        assert len(c) == 0

    def test_invalidate_predicate(self):
        c = LruTtlCache(max_bytes=100, ttl_seconds=60)
        c.put(("seg_0", 1), b"a")
        c.put(("seg_1", 1), b"b")
        assert c.invalidate(lambda k: k[0] == "seg_0") == 1
        assert c.get(("seg_0", 1)) is None
        assert c.get(("seg_1", 1)) == b"b"


# ---------------------------------------------------------------------------
class TestSegmentCacheTier2:
    def test_cacheability(self, tmp_path):
        imm = _build(tmp_path, "imm", [1, 2], [1, 2])
        mut = MutableSegment("t__0__0__1", TableConfig("t", TableType.REALTIME),
                             _schema())
        assert is_cacheable_segment(imm)
        assert not is_cacheable_segment(mut)
        # upsert segments (live validity bitmap) must not be cached
        imm.valid_doc_ids = object()
        assert not is_cacheable_segment(imm)
        agg = QueryContext.from_sql("SELECT SUM(m) FROM t")
        sel = QueryContext.from_sql("SELECT d FROM t LIMIT 5")
        assert is_cacheable_shape(agg)
        assert not is_cacheable_shape(sel)

    def test_segment_version_prefers_crc(self, tmp_path):
        a = _build(tmp_path, "va", [1, 2, 3], [1, 1, 1])
        b = load_segment(str(tmp_path / "va"))
        assert a.metadata.crc != 0
        assert segment_version(a) == segment_version(b)  # same content
        c = _build(tmp_path, "vc", [1, 2, 3], [2, 2, 2])
        assert segment_version(a) != segment_version(c)

    def test_repeat_query_hits_and_matches(self, tmp_path):
        segs = [_build(tmp_path, f"s{i}", range(100), [i + 1] * 100)
                for i in range(3)]
        cache = SegmentResultCache()
        sql = "SELECT COUNT(*), SUM(m) FROM t WHERE d < 50"
        cold = QueryExecutor(segs, use_tpu=False,
                             segment_cache=cache).execute(sql)
        assert cache.stats.puts == 3 and cache.stats.hits == 0
        warm = QueryExecutor(segs, use_tpu=False,
                             segment_cache=cache).execute(sql)
        assert cache.stats.hits == 3
        assert warm.result_table.rows == cold.result_table.rows

    def test_group_by_and_distinct_hit(self, tmp_path):
        segs = [_build(tmp_path, f"g{i}", [j % 4 for j in range(80)],
                       range(80)) for i in range(2)]
        cache = SegmentResultCache()
        for sql in ("SELECT d, SUM(m) FROM t GROUP BY d ORDER BY d LIMIT 10",
                    "SELECT DISTINCT d FROM t LIMIT 10"):
            first = QueryExecutor(segs, use_tpu=False,
                                  segment_cache=cache).execute(sql)
            hits0 = cache.stats.hits
            second = QueryExecutor(segs, use_tpu=False,
                                   segment_cache=cache).execute(sql)
            assert cache.stats.hits == hits0 + 2
            assert second.result_table.rows == first.result_table.rows

    def test_a_device_group_by_is_cached_as_its_batch_s_one_partial(
            self, tmp_path):
        """The engine folds a GROUP BY's per-segment partials before they
        leave the device, so what comes back is ONE result for the batch:
        it is cached under the batch's key, and the same query asked
        again (no skipCache) never reaches the device."""
        segs = [_build(tmp_path, f"f{i}", [j % 5 for j in range(90)],
                       [i + j for j in range(90)]) for i in range(3)]
        cache = SegmentResultCache()
        sql = ("SELECT d, SUM(m), COUNT(*) FROM t GROUP BY d ORDER BY d "
               "LIMIT 10")
        ex = QueryExecutor(segs, use_tpu=True, segment_cache=cache)
        engine = ex.tpu_engine
        served = lambda: engine._metrics.meter(  # noqa: E731
            "scan_served", labels=dict(engine._labels or {}))
        before = served()
        cold = ex.execute(sql)
        assert served() == before + 1
        assert cache.stats.puts == 1 and len(cache) == 1
        warm = ex.execute(sql)
        assert served() == before + 1, "the repeat ran on the device"
        assert cache.stats.hits == 1
        assert warm.result_table.rows == cold.result_table.rows
        host = QueryExecutor(segs, use_tpu=False).execute(sql)
        assert warm.result_table.rows == host.result_table.rows
        # skipCache neither reads nor fills it
        ex.execute(sql + " OPTION(skipCache=true)")
        assert served() == before + 2 and cache.stats.puts == 1
        # other segments, another key: a subset is not served from it
        sub = QueryExecutor(segs[:2], use_tpu=True, segment_cache=cache)
        assert sub.execute(sql).result_table.rows \
            != cold.result_table.rows
        # a member replaced or removed takes the batch's entry with it
        assert len(cache) == 2  # (f0, f1, f2) and (f0, f1)
        assert cache.invalidate_segment("f1") == 2 and len(cache) == 0
        assert cache.get_batch(segs, QueryContext.from_sql(
            sql).fingerprint()) is None

    def test_mutable_segment_never_cached(self):
        mut = MutableSegment("t__0__0__1",
                             TableConfig("t", TableType.REALTIME), _schema())
        for i in range(10):
            mut.index({"d": i, "m": 1})
        cache = SegmentResultCache()
        sql = "SELECT COUNT(*) FROM t"
        r = QueryExecutor([mut], use_tpu=False,
                          segment_cache=cache).execute(sql)
        assert r.rows[0][0] == 10
        assert len(cache) == 0
        # appended rows are visible on the very next query
        mut.index({"d": 10, "m": 1})
        r = QueryExecutor([mut], use_tpu=False,
                          segment_cache=cache).execute(sql)
        assert r.rows[0][0] == 11
        assert cache.stats.hits == 0

    def test_replace_invalidates_by_version(self, tmp_path):
        seg_v1 = _build(tmp_path, "r1", [1, 2, 3], [1, 1, 1])
        cache = SegmentResultCache()
        sql = "SELECT SUM(m) FROM t"
        r = QueryExecutor([seg_v1], use_tpu=False,
                          segment_cache=cache).execute(sql)
        assert r.rows[0][0] == 3
        # same name, new content -> new crc -> the cached partial is
        # unreachable, NOT stale-served
        out = str(tmp_path / "r1b")
        SegmentCreator(_table_config(), _schema()).build(
            {"d": np.asarray([1, 2, 3], np.int64),
             "m": np.asarray([5, 5, 5], np.int64)}, out, "r1")
        seg_v2 = load_segment(out)
        assert seg_v2.name == seg_v1.name
        r = QueryExecutor([seg_v2], use_tpu=False,
                          segment_cache=cache).execute(sql)
        assert r.rows[0][0] == 15

    def test_cached_partial_is_a_private_copy(self, tmp_path):
        """Reduce mutates result containers in place; a hit must hand out
        a fresh copy, not the stored object."""
        seg = _build(tmp_path, "p1", [0, 1] * 10, range(20))
        cache = SegmentResultCache()
        sql = "SELECT d, SUM(m) FROM t GROUP BY d ORDER BY d LIMIT 10"
        a = QueryExecutor([seg], use_tpu=False,
                          segment_cache=cache).execute(sql)
        b = QueryExecutor([seg], use_tpu=False,
                          segment_cache=cache).execute(sql)
        c = QueryExecutor([seg], use_tpu=False,
                          segment_cache=cache).execute(sql)
        assert a.result_table.rows == b.result_table.rows == c.result_table.rows

    def test_trace_carries_cache_hit_attr(self, tmp_path):
        seg = _build(tmp_path, "tr1", range(10), range(10))
        cache = SegmentResultCache()
        sql = "SELECT SUM(m) FROM t OPTION(trace=true)"
        QueryExecutor([seg], use_tpu=False, segment_cache=cache).execute(sql)
        r = QueryExecutor([seg], use_tpu=False,
                          segment_cache=cache).execute(sql)
        assert r.trace is not None
        assert r.trace.get("cacheHit") is True
        flat = str(r.trace)
        assert "SegmentResultCache" in flat

    def test_data_manager_hook_invalidates(self, tmp_path):
        idm = InstanceDataManager("s0")
        events = []
        idm.add_segment_listener(lambda *a: events.append(a))
        tdm = idm.table("t_OFFLINE")
        v0 = tdm.version
        seg = _build(tmp_path, "h1", [1], [1])
        tdm.add_segment(seg)
        assert tdm.version == v0 + 1
        assert events[-1] == ("add", "t_OFFLINE", "h1")
        tdm.add_segment(_build(tmp_path, "h1b", [1], [2]))
        tdm.add_segment(load_segment(str(tmp_path / "h1")))  # replace h1
        assert events[-1] == ("replace", "t_OFFLINE", "h1")
        tdm.remove_segment("h1")
        assert events[-1] == ("remove", "t_OFFLINE", "h1")
        assert tdm.version == v0 + 4


# ---------------------------------------------------------------------------
class TestBrokerCacheTier1:
    @pytest.fixture()
    def cluster(self, tmp_path):
        c = MiniCluster(num_servers=2, result_cache=True)
        c.start()
        c.add_table("t")
        for i in range(4):
            seg = _build(tmp_path, f"b{i}", range(100), [i] * 100)
            c.add_segment("t", seg, server_idx=i % 2)
        yield c, tmp_path
        c.stop()

    def test_repeat_query_served_from_cache(self, cluster):
        c, _ = cluster
        sql = "SELECT COUNT(*), SUM(m) FROM t WHERE d < 50"
        cold = c.query(sql)
        assert not cold.exceptions and not cold.cache_hit
        warm = c.query(sql)
        assert warm.cache_hit
        assert warm.result_table.rows == cold.result_table.rows
        assert c.broker.result_cache.stats.hits >= 1

    def test_skip_cache_option_bypasses(self, cluster):
        c, _ = cluster
        sql = "SELECT COUNT(*) FROM t"
        c.query(sql)
        assert not c.query(sql + " OPTION(skipCache=true)").cache_hit
        assert not c.query(sql + " OPTION(useCache=false)").cache_hit
        assert c.query(sql).cache_hit

    def test_segment_add_and_remove_invalidate(self, cluster):
        c, tmp_path = cluster
        sql = "SELECT COUNT(*) FROM t"
        assert c.query(sql).rows[0][0] == 400
        assert c.query(sql).cache_hit
        seg = _build(tmp_path, "extra", range(10), [9] * 10)
        c.add_segment("t", seg, server_idx=0)
        r = c.query(sql)  # epoch moved: recomputed, fresh count
        assert not r.cache_hit
        assert r.rows[0][0] == 410
        c.remove_segment("t", "extra")
        # back to the ORIGINAL segment set: the original epoch's entry is
        # addressable again and is still correct (content-hash epochs are
        # set-addressed, not event-ordered) — the answer must be 400
        # either way, never the 410 of the removed-segment era
        assert c.query(sql).rows[0][0] == 400

    def test_segment_replace_invalidates(self, cluster):
        c, tmp_path = cluster
        sql = "SELECT SUM(m) FROM t"
        before = c.query(sql).rows[0][0]
        assert c.query(sql).cache_hit
        # rebuild b0 (same name, new values) and swap it in
        out = str(tmp_path / "b0v2")
        SegmentCreator(_table_config(), _schema()).build(
            {"d": np.arange(100, dtype=np.int64),
             "m": np.full(100, 100, np.int64)}, out, "b0")
        c.add_segment("t", load_segment(out), server_idx=0)
        r = c.query(sql)
        assert not r.cache_hit
        assert r.rows[0][0] == before + 100 * 100  # b0 had m=0

    def test_realtime_table_not_cached(self, tmp_path):
        c = MiniCluster(num_servers=1, result_cache=True)
        c.start()
        try:
            c.add_table("t", table_type="REALTIME")
            seg = _build(tmp_path, "rt0", range(10), [1] * 10)
            c.add_segment("t", seg, server_idx=0, table_type="REALTIME")
            sql = "SELECT COUNT(*) FROM t"
            assert c.query(sql).rows[0][0] == 10
            r = c.query(sql)
            assert not r.cache_hit  # consuming side: whole-result unsafe
        finally:
            c.stop()

    def test_partial_responses_not_cached(self, tmp_path):
        c = MiniCluster(num_servers=2, result_cache=True)
        c.start()
        try:
            c.add_table("t")
            c.add_segment("t", _build(tmp_path, "pr0", range(10), [1] * 10),
                          server_idx=0)
            c.add_segment("t", _build(tmp_path, "pr1", range(10), [1] * 10),
                          server_idx=1)
            c.servers[1].transport.stop()
            c._connections["server_1"].close()
            sql = "SELECT COUNT(*) FROM t"
            r = c.query(sql)
            assert r.exceptions  # unreplicated segment lost
            r = c.query(sql)
            assert not r.cache_hit  # the partial answer was NOT memoized
        finally:
            c.stop()


# ---------------------------------------------------------------------------
class TestMutationRaces:
    """Satellite: queries racing segment replace + realtime appends on a
    hybrid segment set — no stale reads, mutable tail always re-executes."""

    @pytest.mark.slow
    def test_threaded_no_stale_reads(self, tmp_path):
        self._run_race(tmp_path)

    def test_threaded_no_stale_reads_quick(self, tmp_path):
        self._run_race(tmp_path, appends=60, duration_s=2.0)

    def _run_race(self, tmp_path, appends=300, duration_s=8.0):
        idm = InstanceDataManager("s0")
        tdm = idm.table("t_REALTIME")
        cache = SegmentResultCache(metrics=None)
        # immutable bulk: 2 sealed segments (SUM(m) = 2 * 1000)
        for i in range(2):
            tdm.add_segment(_build(tmp_path, f"race_imm{i}",
                                   range(1000), [1] * 1000))
        mut = MutableSegment("t__0__0__1",
                             TableConfig("t", TableType.REALTIME), _schema())
        tdm.add_segment(mut)

        # replace thread: rebuild race_imm0 with the SAME totals but new
        # crc, over and over — version keying must keep answers exact
        stop = threading.Event()
        replace_errs = []

        def replacer():
            n = 0
            try:
                while not stop.is_set():
                    n += 1
                    out = str(tmp_path / f"race_imm0_v{n}")
                    SegmentCreator(_table_config(), _schema()).build(
                        {"d": np.arange(1000, dtype=np.int64) + n,
                         "m": np.ones(1000, np.int64)}, out, "race_imm0")
                    tdm.add_segment(load_segment(out))
            except Exception as e:  # noqa: BLE001
                replace_errs.append(e)

        t = threading.Thread(target=replacer, daemon=True)
        t.start()
        sql = "SELECT COUNT(*), SUM(m) FROM t"
        deadline = time.time() + duration_s
        try:
            for i in range(appends):
                mut.index({"d": 10_000 + i, "m": 1})
                sdms = tdm.acquire_segments()
                try:
                    r = QueryExecutor([s.segment for s in sdms],
                                      use_tpu=False,
                                      segment_cache=cache).execute(sql)
                finally:
                    TableDataManager.release_all(sdms)
                expect = 2000 + i + 1
                # the row ingested right before this query MUST be visible
                assert r.rows[0][0] == expect, (i, r.rows)
                assert r.rows[0][1] == expect
                if time.time() > deadline:
                    break
        finally:
            stop.set()
            t.join(timeout=10)
        assert not replace_errs
        # the immutable bulk was served from cache (mutable tail was not):
        # every query re-executed at most the mutable segment + the
        # freshly replaced immutable
        assert cache.stats.hits > 0
        assert cache.stats.misses > 0


# ---------------------------------------------------------------------------
class TestBrokerCacheUnit:
    def _resp(self, queried=1, responded=1, exceptions=()):
        from pinot_tpu.query.reduce import BrokerResponse, ResultTable
        r = BrokerResponse(result_table=ResultTable(["c"], ["LONG"], [(1,)]))
        r.num_servers_queried = queried
        r.num_servers_responded = responded
        r.exceptions = list(exceptions)
        return r

    def test_put_get_roundtrip_copies(self):
        c = BrokerResultCache()
        assert c.put("fp", "t", "e", self._resp())
        hit = c.get("fp", "t", "e")
        assert hit is not None and hit.rows == [(1,)]
        hit.result_table.rows.append((2,))  # caller mutation must not leak
        assert c.get("fp", "t", "e").rows == [(1,)]

    def test_incomplete_or_errored_not_cached(self):
        c = BrokerResultCache()
        assert not c.put("f", "t", "e", self._resp(
            exceptions=[{"errorCode": 427, "message": "x"}]))
        assert not c.put("f", "t", "e", self._resp(queried=2, responded=1))

    def test_epoch_changes_key(self):
        c = BrokerResultCache()
        c.put("fp", "t", "epoch1", self._resp())
        assert c.get("fp", "t", "epoch2") is None

    def test_invalidate_table(self):
        c = BrokerResultCache()
        c.put("f1", "t", "e", self._resp())
        c.put("f2", "u", "e", self._resp())
        assert c.invalidate_table("t") == 1
        assert c.get("f1", "t", "e") is None
        assert c.get("f2", "u", "e") is not None


class TestRoutingEpoch:
    def test_epoch_moves_on_segment_changes(self):
        from pinot_tpu.broker.routing import (RoutingTable, SegmentInfo,
                                              TableRoute)
        tr = TableRoute("t_OFFLINE")
        rt = RoutingTable(offline=tr)
        e0 = rt.epoch()
        tr.segments["s0"] = SegmentInfo("s0", ["srv0"], version=111)
        e1 = rt.epoch()
        assert e1 != e0
        tr.segments["s0"] = SegmentInfo("s0", ["srv0"], version=222)
        e2 = rt.epoch()  # replace: version changed
        assert e2 != e1
        del tr.segments["s0"]
        assert rt.epoch() == e0
        # replica placement does NOT move the epoch
        tr.segments["s0"] = SegmentInfo("s0", ["srv0"], version=111)
        ea = rt.epoch()
        tr.segments["s0"] = SegmentInfo("s0", ["srv0", "srv1"], version=111)
        assert rt.epoch() == ea
        # time boundary DOES
        rt.time_boundary = 5
        assert rt.epoch() != ea


# ---------------------------------------------------------------------------
class TestMetricsSatellites:
    def test_type_emitted_once_per_name(self):
        from pinot_tpu.utils.metrics import MetricsRegistry
        m = MetricsRegistry("x")
        m.add_meter("q", labels={"table": "a"})
        m.add_meter("q", labels={"table": "b"})
        text = m.prometheus_text()
        assert text.count("# TYPE pinot_tpu_x_q counter") == 1

    def test_label_escaping(self):
        from pinot_tpu.utils.metrics import MetricsRegistry
        m = MetricsRegistry("x")
        m.add_meter("q", labels={"t": 'a"b\\c\nd'})
        text = m.prometheus_text()
        assert 't="a\\"b\\\\c\\nd"' in text

    def test_timer_quantiles(self):
        from pinot_tpu.utils.metrics import MetricsRegistry
        m = MetricsRegistry("x")
        for v in range(1, 101):
            m.add_timing("lat", float(v))
        t = m.timer("lat")
        assert t.quantile(0.5) == 50.0
        assert t.quantile(0.95) == 95.0
        assert t.quantile(0.99) == 99.0
        text = m.prometheus_text()
        assert 'pinot_tpu_x_lat{quantile="0.5"} 50' in text
        assert 'pinot_tpu_x_lat{quantile="0.99"} 99' in text

    def test_timer_reservoir_bounded(self):
        from pinot_tpu.utils.metrics import Timer
        t = Timer()
        for v in range(10_000):
            t.update(float(v))
        assert len(t._reservoir) == Timer.RESERVOIR_SIZE
        assert t.count == 10_000
        # reservoir holds a representative sample, not just the tail
        assert t.quantile(0.5) < 9_000


class TestEngineParamsCacheLru:
    def test_bounded_lru_shape(self, monkeypatch):
        # no device work: the params cache trims to its capacity
        # constant, coldest first, and a hit refreshes its entry
        from pinot_tpu.ops.staging import BlockStager
        assert BlockStager.PARAMS_CACHE_ENTRIES == 4096
        monkeypatch.setattr(BlockStager, "PARAMS_CACHE_ENTRIES", 3)
        st = BlockStager(devices=[])
        segs = (object(),)
        with st.lock:
            for i in range(3):
                st.params_put_locked((i,), (segs, {"p": i}))
            assert st.params_get_locked((0,), segs)[1] == {"p": 0}
            st.params_put_locked((3,), (segs, {"p": 3}))
            assert list(st._params_cache) == [(2,), (0,), (3,)]
            # same key, other segment objects: a miss
            assert st.params_get_locked((0,), (object(),)) is None
