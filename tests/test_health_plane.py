"""Fleet health plane (ISSUE 14): metrics history, cluster rollup,
per-query cost attribution, SLO burn-rate watchdog.

Layers under test:

  * MetricsRegistry.sample() + # HELP exposition + remove_gauge (the
    stale labeled-series fix) + concurrent scrape safety;
  * MetricsHistory ring / MetricsSampler cadence + hook isolation;
  * SloWatchdog multi-window burn math, A/A silence, and the
    end-to-end breach under a seeded failpoint latency regression;
  * WorkloadRegistry rollup + the coalesced-launch cost split
    (property-tested: member charges sum to the launch total);
  * ClusterHealthMonitor sweep: live/degraded verdicts, scrape-failure
    degradation without a throw, fleet counter rollup;
  * /debug endpoints (history/sample/health/workload, /debug/queries
    tenant + remainingDeadlineMs) over DebugHttpServer;
  * selfmetrics: the time-series engine answering simpleql over the
    role's own history (the engine's first real consumer);
  * the bench --health smoke leg (tier-1 overhead gate).
"""
import json
import logging
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

from pinot_tpu.health.history import (MetricsHistory, MetricsSampler,
                                      get_history, start_sampling,
                                      stop_sampling)
from pinot_tpu.health.rollup import (ClusterHealthMonitor, ScrapeTarget,
                                     role_health_summary)
from pinot_tpu.health.slo import SloWatchdog
from pinot_tpu.health.workload import WorkloadRegistry, get_workload
from pinot_tpu.utils import metrics as metrics_mod
from pinot_tpu.utils.accounting import ResourceAccountant
from pinot_tpu.utils.config import PinotConfiguration
from pinot_tpu.utils.failpoints import failpoints
from pinot_tpu.utils.metrics import MetricsRegistry, get_registry


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.clear()
    yield
    failpoints.clear()


@pytest.fixture()
def fresh_server_registry():
    """Swap the process-global 'server' registry for a fresh one so
    cumulative timer reservoirs from other tests can't leak into
    latency-quantile assertions."""
    with metrics_mod._reg_lock:
        old = metrics_mod._registries.get("server")
        fresh = MetricsRegistry("server")
        metrics_mod._registries["server"] = fresh
    try:
        yield fresh
    finally:
        with metrics_mod._reg_lock:
            if old is not None:
                metrics_mod._registries["server"] = old
            else:
                metrics_mod._registries.pop("server", None)


def _build_segment(tmp_path, name="s0", docs=500):
    from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                                  TableConfig)
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment
    schema = Schema("t", [
        FieldSpec("k", DataType.INT, FieldType.DIMENSION),
        FieldSpec("v", DataType.INT, FieldType.METRIC)])
    rng = np.random.default_rng(7)
    d = str(tmp_path / name)
    SegmentCreator(TableConfig(name="t"), schema).build(
        {"k": rng.integers(0, 100, docs).astype(np.int32),
         "v": rng.integers(0, 10, docs).astype(np.int32)}, d, name)
    return load_segment(d)


# ---------------------------------------------------------------------------
# registry: sample / HELP / remove_gauge / concurrent scrape
# ---------------------------------------------------------------------------

class TestRegistrySurface:
    def test_sample_is_flat_and_timestamped(self):
        reg = MetricsRegistry("r1")
        reg.add_meter("queries", 3)
        reg.add_meter("queries", 2, labels={"table": "t"})
        reg.set_gauge("task_queue_depth", 7.0)
        with reg.time("query_execution"):
            pass
        s = reg.sample()
        assert s["role"] == "r1" and s["ts"] <= time.time()
        assert s["counters"]["queries"] == 3
        assert s["counters"]['queries{table="t"}'] == 2
        assert s["gauges"]["task_queue_depth"] == 7.0
        t = s["timers"]["query_execution"]
        assert t["count"] == 1 and t["p99"] >= 0

    def test_help_lines_from_catalog(self):
        reg = MetricsRegistry("r2")
        reg.add_meter("queries")          # cataloged
        reg.add_meter("totally_uncataloged_thing")
        text = reg.prometheus_text()
        lines = text.splitlines()
        i = lines.index("# TYPE pinot_tpu_r2_queries counter")
        assert lines[i - 1].startswith("# HELP pinot_tpu_r2_queries "), \
            lines[i - 1]
        # uncataloged names emit TYPE only — no fabricated HELP
        assert "# TYPE pinot_tpu_r2_totally_uncataloged_thing counter" \
            in lines
        assert not any(
            ln.startswith("# HELP pinot_tpu_r2_totally_uncataloged")
            for ln in lines)
        # one HELP per family, even with several label sets
        reg.add_meter("queries", labels={"table": "x"})
        text = reg.prometheus_text()
        assert text.count("# HELP pinot_tpu_r2_queries ") == 1

    def test_remove_gauge_drops_series(self):
        reg = MetricsRegistry("r3")
        reg.set_gauge("ingestion_delay_ms", 120.0,
                      labels={"partition": "0"})
        reg.set_gauge("ingestion_delay_ms", 80.0,
                      labels={"partition": "1"})
        assert reg.remove_gauge("ingestion_delay_ms",
                                labels={"partition": "0"})
        text = reg.prometheus_text()
        assert 'partition="0"' not in text
        assert 'partition="1"' in text
        assert 'ingestion_delay_ms{partition="0"}' \
            not in reg.sample()["gauges"]
        # removing a series that never existed reports False
        assert not reg.remove_gauge("ingestion_delay_ms",
                                    labels={"partition": "9"})

    def test_delay_tracker_remove_partition_regression(self):
        """The satellite fix: a removed partition's labeled gauge must
        LEAVE the exposition — the old zeroing behavior kept the stale
        series on /metrics forever."""
        from pinot_tpu.ingest.realtime_manager import IngestionDelayTracker
        reg = MetricsRegistry("r4")
        tr = IngestionDelayTracker(metrics=reg, labels={"table": "t"})
        tr.record(0, int(time.time() * 1000) - 500)
        tr.record(1, int(time.time() * 1000) - 100)
        assert 'partition="0"' in reg.prometheus_text()
        tr.remove_partition(0)
        text = reg.prometheus_text()
        assert 'partition="0"' not in text, \
            "removed partition's gauge lingers on /metrics"
        assert 'partition="1"' in text
        assert tr.delay_ms(0) is None

    def test_concurrent_scrape_safety(self):
        """Hammer prometheus_text()/sample() against concurrent
        writers: every page parses, counters are monotonic."""
        reg = MetricsRegistry("r5")
        stop = threading.Event()
        errors = []

        def writer(i):
            n = 0
            while not stop.is_set():
                n += 1
                reg.add_meter("queries", labels={"w": str(i)})
                reg.set_gauge("task_queue_depth", n % 50,
                              labels={"w": str(i)})
                reg.add_timing("query_execution", n % 7,
                               labels={"w": str(i)})

        line_rx = re.compile(
            r'^(# (TYPE|HELP) .+|[a-zA-Z_:][\w:]*(\{[^}]*\})? '
            r'[-+0-9.eE]+(nan|inf)?)$')

        def reader():
            last: dict = {}
            try:
                for _ in range(30):
                    text = reg.prometheus_text()
                    for ln in text.splitlines():
                        assert line_rx.match(ln), f"unparseable: {ln!r}"
                    s = reg.sample()
                    for k, v in s["counters"].items():
                        assert v >= last.get(k, 0.0), \
                            f"counter {k} went backwards"
                        last[k] = v
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        writers = [threading.Thread(target=writer, args=(i,))
                   for i in range(4)]
        readers = [threading.Thread(target=reader) for _ in range(3)]
        for t in writers + readers:
            t.start()
        for t in readers:
            t.join(20)
        stop.set()
        for t in writers:
            t.join(5)
        assert not errors, errors


# ---------------------------------------------------------------------------
# history ring + sampler
# ---------------------------------------------------------------------------

class TestHistory:
    def test_ring_bound_and_window(self):
        h = MetricsHistory(capacity=4)
        for i in range(10):
            h.append({"ts": 1000.0 + i, "counters": {"c": float(i)}})
        assert len(h) == 4
        assert [s["ts"] for s in h.samples()] == [1006.0, 1007.0,
                                                  1008.0, 1009.0]
        win = h.samples(window_s=2.0, now=1009.0)
        assert [s["ts"] for s in win] == [1007.0, 1008.0, 1009.0]
        assert h.latest()["ts"] == 1009.0

    def test_counter_delta_and_reset_clamp(self):
        h = MetricsHistory()
        h.append({"ts": 0.0, "counters": {"c": 10.0}})
        h.append({"ts": 10.0, "counters": {"c": 25.0}})
        delta, secs = h.counter_delta("c", 60.0, now=10.0)
        assert (delta, secs) == (15.0, 10.0)
        # restart between samples: the registry reset must not read as
        # negative traffic — clamp to the newest absolute value
        h.append({"ts": 20.0, "counters": {"c": 3.0}})
        delta, _ = h.counter_delta("c", 60.0, now=20.0)
        assert delta == 3.0

    def test_family_sum_and_timer_series(self):
        h = MetricsHistory()
        h.append({"ts": 0.0,
                  "counters": {'e{t="a"}': 1.0, 'e{t="b"}': 2.0},
                  "timers": {'q{t="a"}': {"p99": 5.0},
                             'q{t="b"}': {"p99": 9.0}}})
        h.append({"ts": 5.0,
                  "counters": {'e{t="a"}': 4.0, 'e{t="b"}': 2.0},
                  "timers": {'q{t="a"}': {"p99": 7.0}}})
        assert h.counter_sum_delta("e", 60.0, now=5.0)[0] == 3.0
        series = h.timer_series("q", "p99", 60.0, now=5.0)
        assert series == [(0.0, 9.0), (5.0, 7.0)]  # worst across labels
        # prefix matching must not cross families ("e" vs "extra")
        h.append({"ts": 6.0, "counters": {'e{t="a"}': 4.0, 'e{t="b"}': 2.0,
                                          "extra": 100.0}})
        assert h.counter_sum_delta("e", 60.0, now=6.0)[0] == 3.0
        assert h.counter_sum_delta("extra", 60.0, now=6.0)[0] == 100.0

    def test_sampler_appends_and_hook_isolation(self):
        reg = MetricsRegistry("hsamp")
        h = MetricsHistory()
        s = MetricsSampler("hsamp", history=h, registry=reg)
        calls = []
        s.add_hook(lambda: calls.append(1))
        s.add_hook(lambda: 1 / 0)  # a hook bug must not stop sampling
        s.sample_once()
        s.sample_once()
        assert len(h) == 2 and calls == [1, 1]
        assert reg.sample()["counters"]["metrics_history_samples"] == 2.0

    def test_sampler_thread_lifecycle(self):
        reg = MetricsRegistry("hthread")
        h = MetricsHistory()
        s = MetricsSampler("hthread", interval_s=0.02, history=h,
                           registry=reg)
        s.start()
        deadline = time.time() + 5.0
        while len(h) < 3 and time.time() < deadline:
            time.sleep(0.02)
        s.stop()
        n = len(h)
        assert n >= 3
        time.sleep(0.1)
        assert len(h) == n, "sampler kept appending after stop"

    def test_start_sampling_knobs(self):
        cfg_off = PinotConfiguration(
            overrides={"pinot.metrics.history.enabled": False})
        assert start_sampling("knobrole", cfg_off) is None
        cfg = PinotConfiguration(overrides={
            "pinot.metrics.history.interval.ms": 10.0,
            "pinot.metrics.history.window.seconds": 1.0})
        try:
            s1 = start_sampling("knobrole", cfg)
            assert s1 is not None
            assert start_sampling("knobrole", cfg) is s1  # idempotent
            # capacity sized from window/interval
            assert get_history("knobrole").capacity >= 8
        finally:
            stop_sampling("knobrole")


# ---------------------------------------------------------------------------
# SLO watchdog
# ---------------------------------------------------------------------------

def _slo_cfg(**over):
    base = {"pinot.slo.query.p99.ms": 50.0,
            "pinot.slo.window.short.seconds": 30.0,
            "pinot.slo.window.long.seconds": 60.0,
            "pinot.slo.burn.threshold": 1.0,
            "pinot.slo.latency.budget": 0.1}
    base.update(over)
    return PinotConfiguration(overrides=base)


class TestSloWatchdog:
    def test_disabled_without_targets(self):
        dog = SloWatchdog("sd", MetricsHistory(),
                          config=PinotConfiguration())
        assert not dog.enabled
        assert dog.evaluate() == {}

    def test_latency_burn_multi_window(self):
        reg = MetricsRegistry("slo1")
        h = MetricsHistory()
        now = 1000.0
        # cumulative counters, 10 queries per 6s tick; from i=8 every
        # query runs over target (slo_latency_bad tracks queries 1:1).
        # The burn is a WINDOWED bad/total ratio — deliberately not the
        # registry timer p99s, whose lifetime reservoir would make
        # every sample carry the same sticky cumulative quantile.
        for i in range(10):
            h.append({"ts": now - 60 + i * 6,
                      "counters": {
                          "queries": 10.0 * (i + 1),
                          "slo_latency_bad":
                              0.0 if i < 8 else 10.0 * (i - 7)}})
        dog = SloWatchdog("slo1", h, config=_slo_cfg(), metrics=reg)
        v = dog.evaluate(now=now)["query.p99.ms"]
        # short window (30s, ts>=970): samples i=5..9 -> 20 bad of 40
        # queries -> frac .5 / budget .1 = burn 5; long (60s): 20 bad
        # of 90 -> burn 20/90/.1
        assert v["burnShort"] == pytest.approx(5.0)
        assert v["burnLong"] == pytest.approx((20.0 / 90.0) / 0.1,
                                              abs=1e-3)
        assert v["breached"]
        assert reg.sample()["gauges"]['slo_burn_rate{slo="query.p99.ms"}'] \
            == pytest.approx(5.0)

    def test_short_blip_does_not_breach(self):
        h = MetricsHistory()
        now = 1000.0
        # 10 queries per 5s tick; a blip at i>=18 makes 8 of them bad
        for i in range(20):
            h.append({"ts": now - 95 + i * 5,
                      "counters": {
                          "queries": 10.0 * (i + 1),
                          "slo_latency_bad":
                              0.0 if i < 18 else 8.0 * (i - 17)}})
        dog = SloWatchdog(
            "slo2", h, config=_slo_cfg(
                **{"pinot.slo.window.short.seconds": 10.0,
                   "pinot.slo.window.long.seconds": 90.0,
                   "pinot.slo.latency.budget": 0.5}),
            metrics=MetricsRegistry("slo2"))
        v = dog.evaluate(now=now)["query.p99.ms"]
        assert v["burnShort"] > 1.0      # the blip fills the short window
        assert v["burnLong"] < 1.0       # but not the long one
        assert not v["breached"]         # -> no page

    def test_error_rate_burn(self):
        h = MetricsHistory()
        h.append({"ts": 0.0, "counters": {"broker_queries": 100.0,
                                          "broker_query_errors": 0.0}})
        h.append({"ts": 30.0, "counters": {"broker_queries": 200.0,
                                           "broker_query_errors": 5.0}})
        cfg = _slo_cfg(**{"pinot.slo.query.p99.ms": 0.0,
                          "pinot.slo.error.rate": 0.01})
        dog = SloWatchdog("slo3", h, config=cfg,
                          metrics=MetricsRegistry("slo3"))
        v = dog.evaluate(now=30.0)["error.rate"]
        # 5 errors / 100 queries = .05 over a .01 target -> burn 5
        assert v["burnShort"] == pytest.approx(5.0)
        assert v["breached"]

    def test_freshness_burn(self):
        h = MetricsHistory()
        for i in range(4):
            h.append({"ts": float(i * 10),
                      "gauges": {'ingestion_delay_ms{partition="0"}':
                                 50_000.0 if i >= 2 else 100.0}})
        cfg = _slo_cfg(**{"pinot.slo.query.p99.ms": 0.0,
                          "pinot.slo.freshness.ms": 1000.0,
                          "pinot.slo.latency.budget": 0.25})
        dog = SloWatchdog("slo4", h, config=cfg,
                          metrics=MetricsRegistry("slo4"))
        v = dog.evaluate(now=30.0)["freshness.ms"]
        assert v["burnShort"] == pytest.approx(2.0)  # 2/4 bad / .25

    def test_e2e_breach_under_failpoint_delay(
            self, tmp_path, fresh_server_registry, caplog):
        """The acceptance leg: an injected latency regression (seeded
        failpoint delay on the server execute path) fires SLO_BREACH +
        the burn gauge; the A/A baseline stays silent; a sustained
        breach logs its onset ONCE."""
        from pinot_tpu.server.data_manager import InstanceDataManager
        from pinot_tpu.server.query_server import ServerQueryExecutor
        seg = _build_segment(tmp_path)
        dm = InstanceDataManager("slo-e2e")
        dm.table("t").add_segment(seg)
        cfg = _slo_cfg(**{"pinot.slo.query.p99.ms": 100.0,
                          "pinot.slo.window.short.seconds": 600.0,
                          "pinot.slo.window.long.seconds": 600.0})
        # the executor reads the same target: queries over it bump the
        # slo_latency_bad counter the watchdog's latency burn reads
        ex = ServerQueryExecutor(dm, use_tpu=False, config=cfg)
        reg = fresh_server_registry
        h = MetricsHistory()
        sampler = MetricsSampler("server", history=h, registry=reg)
        dog = SloWatchdog("server", h, config=cfg, metrics=reg)
        sampler.add_hook(dog.evaluate)

        def run(n):
            for i in range(n):
                ex.execute("t", "SELECT COUNT(*) FROM t",
                           query_id=f"q{time.time_ns()}")
                sampler.sample_once()

        # A/A baseline: fast queries, no breach, no gauge over threshold
        with caplog.at_level(logging.WARNING, logger="pinot_tpu.slo"):
            run(4)
            assert not dog.breached()
            assert "SLO_BREACH" not in caplog.text
            # the regression: every execute now pays a seeded 250ms
            failpoints.arm("server.execute.before", delay=0.25, seed=14)
            run(4)
        assert dog.breached()
        v = dog.verdicts()["query.p99.ms"]
        assert v["burnShort"] > 1.0
        breach_lines = [r for r in caplog.records
                        if "SLO_BREACH" in r.getMessage()]
        assert len(breach_lines) == 1, "sustained breach must log onset once"
        payload = json.loads(
            breach_lines[0].getMessage().split("SLO_BREACH ", 1)[1])
        assert payload["slo"] == "query.p99.ms"
        assert reg.sample()["counters"]['slo_breaches{slo="query.p99.ms"}'] \
            == 1.0


# ---------------------------------------------------------------------------
# workload accounting + the coalesced cost split
# ---------------------------------------------------------------------------

class TestWorkload:
    def test_rollup_and_tenant_gauge(self):
        reg = MetricsRegistry("wl1")
        wl = WorkloadRegistry("wl1", metrics=reg)
        wl.record(tenant="acme", table="t1", fingerprint="fp1",
                  cpu_ms=10.0, device_kernel_ms=5.0, rows_scanned=100)
        wl.record(tenant="acme", table="t1", fingerprint="fp1",
                  cpu_ms=2.0, rows_scanned=50, error=True)
        wl.record(tenant="beta", table="t2", fingerprint="fp2",
                  cpu_ms=100.0)
        top = wl.top(10)
        assert top[0]["tenant"] == "beta"
        acme = next(e for e in top if e["tenant"] == "acme")
        assert acme["queries"] == 2 and acme["errors"] == 1
        assert acme["rowsScanned"] == 150
        assert acme["costMs"] == pytest.approx(17.0)
        assert wl.tenants()["acme"] == pytest.approx(17.0)
        g = reg.sample()["gauges"]
        assert g['workload_tenant_cost_ms{tenant="beta"}'] == 100.0
        payload = wl.payload(k=1)
        assert len(payload["topK"]) == 1
        assert payload["tenantCostMs"]["acme"] == pytest.approx(17.0)

    def test_eviction_keeps_expensive(self):
        wl = WorkloadRegistry("wl2", metrics=MetricsRegistry("wl2"),
                              max_entries=3)
        for i in range(3):
            wl.record(tenant="t", table=f"tab{i}", fingerprint="f",
                      cpu_ms=(i + 1) * 100.0)
        wl.record(tenant="t", table="fresh", fingerprint="f", cpu_ms=1.0)
        tables = {e["table"] for e in wl.top(10)}
        assert "tab0" not in tables          # cheapest evicted
        assert {"tab1", "tab2", "fresh"} == tables

    def test_unattributed_keys_do_not_collide_with_blank(self):
        wl = WorkloadRegistry("wl3", metrics=MetricsRegistry("wl3"))
        wl.record(tenant="", table="", fingerprint="", cpu_ms=1.0)
        e = wl.top(1)[0]
        assert e["tenant"] == "-" and e["table"] == "-"

    def test_split_charge_property(self):
        """The acceptance invariant, property-tested: across random doc
        distributions (incl. zero-doc members), the per-member kernel-ms
        charges sum EXACTLY to the launch total, proportional to doc
        share."""
        from pinot_tpu.ops.dispatch import Launch, split_charge
        rng = np.random.default_rng(1234)
        for trial in range(50):
            n = int(rng.integers(1, 12))
            docs = rng.integers(0, 100_000, n)
            if trial % 7 == 0:
                docs[:] = 0          # degenerate: even split
            kernel_ms = float(rng.uniform(0.1, 500.0))
            acct = ResourceAccountant()
            launches = []
            for i in range(n):
                qid = f"q{trial}-{i}"
                acct.begin_query(qid, None)
                launches.append(Launch(
                    call=lambda: None, slip=acct.slip(qid),
                    docs=int(docs[i])))
            split_charge(launches, kernel_ms)
            charges = [acct.usage(f"q{trial}-{i}").device_kernel_ms
                       for i in range(n)]
            assert sum(charges) == pytest.approx(kernel_ms, rel=1e-9), \
                (trial, docs, kernel_ms, charges)
            total = docs.sum()
            for i in range(n):
                want = (kernel_ms * docs[i] / total if total
                        else kernel_ms / n)
                assert charges[i] == pytest.approx(want, rel=1e-9)

    def test_split_charge_skips_detached_without_redistributing(self):
        from pinot_tpu.ops.dispatch import Launch, split_charge
        acct = ResourceAccountant()
        acct.begin_query("q0", None)
        live = [Launch(call=lambda: None, slip=acct.slip("q0"), docs=250),
                Launch(call=lambda: None, slip=None, docs=750)]
        split_charge(live, 100.0)
        # the attributed member pays ITS share only — the slip-less
        # peer's share is unrecorded, never redistributed
        assert acct.usage("q0").device_kernel_ms == pytest.approx(25.0)

    def test_eight_coalesced_queries_split_one_launch(self):
        """Eight concurrent fingerprint-equal launches coalesce into ONE
        batched launch; each member's kernel charge is its doc share of
        the one launch's measured total, and the charges sum to it."""
        from pinot_tpu.ops import dispatch as dispatch_mod
        from pinot_tpu.ops.dispatch import KernelDispatcher, Launch

        cfg = PinotConfiguration(overrides={
            "pinot.server.dispatch.batch.window.ms": 250.0,
            "pinot.server.dispatch.batch.max": 8})
        disp = KernelDispatcher(config=cfg,
                                metrics=MetricsRegistry("wl4"))
        kernel_calls = []

        def factory(B, stacked):
            def kern(cols, plist, num_docs, D=0, G=0):
                kernel_calls.append(B)
                time.sleep(0.01)
                return np.zeros((B, 4), np.float64)
            return kern

        observed = {}
        real_split = dispatch_mod.split_charge

        def spy_split(live, kernel_ms):
            observed["kernel_ms"] = kernel_ms
            observed["n"] = len(live)
            real_split(live, kernel_ms)

        acct = ResourceAccountant()
        docs = [100, 200, 300, 400, 500, 600, 700, 800]
        launches = []
        for i, d in enumerate(docs):
            acct.begin_query(f"c{i}", None)
            launches.append(Launch(
                call=lambda: np.zeros(4), plan="fp", cols=(), params={"i": i},
                num_docs=None, D=8, G=0, batch_key=("fp", 8, 8, 0),
                cols_key=("same",), factory=factory,
                slip=acct.slip(f"c{i}"), docs=d))
        barrier = threading.Barrier(9)

        def submit(launch):
            # enter BEFORE the barrier: the ring must observe 8 active
            # callers when the first launch arrives, or the lone-query
            # inline fast path serves them serially with nothing to
            # coalesce
            disp.enter_active()
            try:
                barrier.wait(5)
                return dispatch_mod.wait_result(disp.submit(launch),
                                                max_wait_s=30.0)
            finally:
                disp.exit_active()

        dispatch_mod.split_charge = spy_split
        try:
            threads = [threading.Thread(target=submit, args=(ln,))
                       for ln in launches]
            for t in threads:
                t.start()
            barrier.wait(5)
            for t in threads:
                t.join(30)
        finally:
            dispatch_mod.split_charge = real_split
            disp.close()
        assert kernel_calls == [8], \
            f"expected one batched launch of 8, got {kernel_calls}"
        assert observed["n"] == 8
        charges = [acct.usage(f"c{i}").device_kernel_ms
                   for i in range(8)]
        assert all(c > 0 for c in charges)
        assert sum(charges) == pytest.approx(observed["kernel_ms"],
                                             rel=1e-9)
        total = sum(docs)
        for c, d in zip(charges, docs):
            assert c == pytest.approx(
                observed["kernel_ms"] * d / total, rel=1e-9)

    def test_executor_charges_rows_and_records_workload(
            self, tmp_path, fresh_server_registry):
        """End-to-end server path: a finished query's usage (rows/bytes
        scanned, attribution dimensions) lands in the server workload
        rollup keyed by (tenant, table, fingerprint)."""
        from pinot_tpu.server.data_manager import InstanceDataManager
        from pinot_tpu.server.query_server import ServerQueryExecutor
        seg = _build_segment(tmp_path, docs=400)
        dm = InstanceDataManager("wl-e2e")
        dm.table("t").add_segment(seg)
        ex = ServerQueryExecutor(dm, use_tpu=False)
        wl = get_workload("server")
        wl.clear()
        ex.execute("t", "SELECT COUNT(*) FROM t WHERE k < 50",
                   query_id="wlq1", tenant="acme")
        top = wl.top(5)
        assert top, "no workload recorded"
        e = top[0]
        assert e["tenant"] == "acme" and e["table"] == "t"
        assert e["planFingerprint"] not in ("", "-")
        assert e["queries"] == 1
        assert e["rowsScanned"] > 0
        assert e["bytesScanned"] > 0
        wl.clear()


# ---------------------------------------------------------------------------
# cluster rollup
# ---------------------------------------------------------------------------

def _fake_target(iid, role="server", counters=None, degraded=False,
                 boom=False):
    def fetch():
        if boom:
            raise ConnectionError("connection refused")
        return {"health": {"verdict": "degraded" if degraded else "live",
                           "degraded": ["slo"] if degraded else [],
                           "subsystems": {}},
                "sample": {"ts": time.time(), "role": role,
                           "counters": dict(counters or {}),
                           "gauges": {"g": 1.0}, "timers": {}}}
    return ScrapeTarget(instance_id=iid, fetch=fetch, role=role)


class TestClusterRollup:
    def test_sweep_verdicts_and_metrics(self):
        reg = MetricsRegistry("roll1")
        targets = [
            _fake_target("s1", counters={"queries": 10.0}),
            _fake_target("s2", counters={"queries": 5.0,
                                         'q{t="a"}': 2.0}),
            _fake_target("s3", boom=True),
            _fake_target("s4", degraded=True),
        ]
        ages = {"s1": 1.0, "s2": 999.0, "s3": 2.0}
        mon = ClusterHealthMonitor(lambda: targets,
                                   liveness_fn=lambda: ages,
                                   liveness_ttl_s=15.0, metrics=reg)
        payload = mon.sweep()
        inst = payload["instances"]
        assert inst["s1"]["verdict"] == "live"
        assert inst["s1"]["liveness"] == "live"
        # a reachable instance with a stale heartbeat is degraded
        assert inst["s2"]["liveness"] == "stale"
        assert inst["s2"]["verdict"] == "degraded"
        # a scrape failure degrades with the reason, never throws
        assert inst["s3"]["verdict"] == "degraded"
        assert not inst["s3"]["reachable"]
        assert "ConnectionError" in inst["s3"]["reason"]
        # an instance reporting its own degradation passes through
        assert inst["s4"]["verdict"] == "degraded"
        assert inst["s4"]["degraded"] == ["slo"]
        # no heartbeat signal at all reads "unknown", not a lie
        assert inst["s4"]["liveness"] == "unknown"
        assert payload["instancesLive"] == 1
        assert payload["instancesDegraded"] == 3
        g = reg.sample()["gauges"]
        assert g["cluster_instances_live"] == 1.0
        assert g["cluster_instances_degraded"] == 3.0
        assert reg.sample()["counters"]["cluster_scrape_failures"] == 1.0
        # cluster metrics: counters summed across instances, gauges kept
        # per instance
        cm = mon.cluster_metrics()
        assert cm["counters"]["queries"] == 15.0
        assert cm["counters"]['q{t="a"}'] == 2.0
        assert cm["gaugesByInstance"]["s1"]["g"] == 1.0

    def test_sweep_survives_broken_targets_fn(self):
        mon = ClusterHealthMonitor(
            lambda: 1 / 0, metrics=MetricsRegistry("roll2"))
        payload = mon.sweep()   # must not raise
        assert payload["instances"] == {}

    def test_first_get_answers_without_prior_sweep(self):
        mon = ClusterHealthMonitor(
            lambda: [_fake_target("x", counters={"c": 1.0})],
            metrics=MetricsRegistry("roll3"))
        assert mon.cluster_health()["instances"]["x"]["verdict"] == "live"
        mon2 = ClusterHealthMonitor(
            lambda: [_fake_target("x", counters={"c": 1.0})],
            metrics=MetricsRegistry("roll3"))
        assert mon2.cluster_metrics()["counters"]["c"] == 1.0

    def test_role_health_summary_subsystems(self):
        reg = MetricsRegistry("roll4")
        s = role_health_summary("roll4", registry=reg)
        assert s["verdict"] == "live" and s["degraded"] == []
        # an open remote-tier breaker degrades the data path
        reg.set_gauge("remote_cache_breaker_state", 1.0,
                      labels={"node": "n1"})
        s = role_health_summary("roll4", registry=reg)
        assert s["verdict"] == "degraded"
        assert "breakers" in s["degraded"]
        reg.set_gauge("remote_cache_breaker_state", 0.0,
                      labels={"node": "n1"})
        # a paused ingestion partition degrades ingestion
        reg.set_gauge("ingest_consumer_paused", 1.0,
                      labels={"partition": "0"})
        reg.set_gauge("ingestion_delay_ms", 1234.0,
                      labels={"partition": "0"})
        s = role_health_summary("roll4", registry=reg)
        assert "ingestion" in s["degraded"]
        assert s["subsystems"]["ingestion"]["maxDelayMs"] == 1234.0
        assert s["subsystems"]["ingestion"]["pausedPartitions"] == 1


# ---------------------------------------------------------------------------
# /debug endpoints
# ---------------------------------------------------------------------------

class TestDebugEndpoints:
    def test_debug_http_health_plane_routes(self):
        from pinot_tpu.utils.trace_store import DebugHttpServer
        role = "dbgrole"
        reg = get_registry(role)
        reg.add_meter("queries", 3)
        hist = get_history(role)
        hist.clear()
        hist.append(reg.sample())
        wl = get_workload(role)
        wl.clear()
        wl.record(tenant="acme", table="t", fingerprint="f", cpu_ms=2.0)
        srv = DebugHttpServer([role])
        srv.start()
        try:
            def get(path):
                with urllib.request.urlopen(
                        f"http://{srv.host}:{srv.port}{path}",
                        timeout=5) as r:
                    return json.loads(r.read())
            s = get("/debug/metrics/sample")
            assert s["counters"]["queries"] == 3.0
            hy = get("/debug/metrics/history")
            assert hy["role"] == role and len(hy["samples"]) == 1
            hl = get("/debug/health")
            assert hl["verdict"] == "live"
            assert hl["historySamples"] == 1
            w = get("/debug/workload")
            assert w["topK"][0]["tenant"] == "acme"
        finally:
            srv.stop()

    def test_inflight_tenant_and_remaining_deadline(self):
        from pinot_tpu.utils.trace_store import InflightRegistry
        reg = InflightRegistry()
        reg.begin("q1", sql="SELECT 1", tenant="acme",
                  deadline=time.time() + 30.0)
        reg.begin("q2", sql="SELECT 2")
        reg.annotate("q2", tenant="beta", deadline=time.time() + 5.0)
        snap = {e["queryId"]: e for e in reg.snapshot()}
        assert snap["q1"]["tenant"] == "acme"
        assert 0 < snap["q1"]["remainingDeadlineMs"] <= 30_000
        assert snap["q2"]["tenant"] == "beta"
        assert 0 < snap["q2"]["remainingDeadlineMs"] <= 5_000
        # a query with no budget reports None, not a fake number
        reg.begin("q3", sql="SELECT 3")
        snap = {e["queryId"]: e for e in reg.snapshot()}
        assert snap["q3"]["remainingDeadlineMs"] is None
        assert snap["q3"]["tenant"] is None


# ---------------------------------------------------------------------------
# selfmetrics: the time-series engine's first real consumer
# ---------------------------------------------------------------------------

class TestSelfMetrics:
    def test_simpleql_over_own_history(self):
        from pinot_tpu.health.selfmetrics import query_history
        role = "selfm"
        reg = MetricsRegistry(role)
        hist = MetricsHistory(64)
        sampler = MetricsSampler(role, history=hist, registry=reg)
        base = int(time.time())
        for i in range(10):
            reg.add_meter("queries", 5)
            reg.set_gauge("task_queue_depth", float(i))
            with reg.time("query_execution"):
                pass
            s = sampler.sample_once()
            s["ts"] = base + i   # pin whole-second timestamps
        start, end = base, base + 10
        # gauge series straight through the engine
        block = query_history(
            f"fetch(selfmetrics, value, ts, {start}, {end}, 1) "
            f"| where(family = 'task_queue_depth') | sum()",
            role=role, history=hist)
        assert len(block.series) == 1
        assert block.series[0].values.tolist() == [float(i)
                                                   for i in range(10)]
        # cumulative counter piped through rate(): 5/step after warmup
        block = query_history(
            f"fetch(selfmetrics, value, ts, {start}, {end}, 1) "
            f"| where(family = 'queries') | sum() | rate()",
            role=role, history=hist)
        vals = block.series[0].values
        assert np.allclose(vals[1:], 5.0)
        # timer fields ride the name suffix (count is cumulative; step 1
        # keeps the leaf's in-bucket SUM an identity)
        block = query_history(
            f"fetch(selfmetrics, value, ts, {start}, {end}, 1) "
            f"| where(name = 'query_execution:count') | max()",
            role=role, history=hist)
        assert block.series[0].values[-1] == 10.0

    def test_empty_history_fails_loud(self):
        from pinot_tpu.health.selfmetrics import query_history
        with pytest.raises(ValueError, match="no metrics-history"):
            query_history(
                "fetch(selfmetrics, value, ts, 0, 10, 1) | sum()",
                role="selfm-empty", history=MetricsHistory())


# ---------------------------------------------------------------------------
# tier-1 smoke of the acceptance driver
# ---------------------------------------------------------------------------

class TestHealthBenchSmoke:
    def test_health_bench_smoke(self, tmp_path):
        """The --health acceptance scenario at smoke scale: the paired
        accounting A/B + block-paired sampling legs run end to end and
        the qualitative overhead contract holds (the strict <2% bar
        belongs to the full run in BENCH_health.json)."""
        import bench
        out = str(tmp_path / "BENCH_health_smoke.json")
        bench.health_main(smoke=True, out_path=out)
        with open(out) as f:
            data = json.load(f)
        assert data["history_samples"] > 0
        assert data["smoke"] is True
