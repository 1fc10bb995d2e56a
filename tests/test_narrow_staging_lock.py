"""Only the block look-ups stay under the staging lock (ISSUE 33): plan,
literal resolve and the packed parameters leave it, and the pack rides
the launch as a host argument, one transfer a batch.

  (a) the host-stacked [B, K, S] argument (plan_ir.batch_params) gives,
      bit for bit, what PR 32's per-member device arrays gave: on 1, 4
      and 8 devices, B in {1, 2, 4, 8}, for a range plan, a `neq`, a
      `vrange64`, a float `vrange`, a `tb:*` plan and one with a LUT
      table beside the pack;
  (b) the lock is narrow: with `_resolve_leaf` made to sleep 50 ms,
      eight threads staging eight distinct literals finish in well
      under 8 x 50 ms and every traced `lockHeldMs` is under the sleep;
  (c) a parameter-cache hit resolves nothing and puts nothing, a miss
      puts nothing (a LUT table: one), and the launch's span carries
      `paramsXferBytes` = K x S x 4 x B, which `hbm_transfer_bytes`
      counts once a launch.
"""
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from pinot_tpu.ops import kernels
from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.ops.plan_ir import PACK, batch_params
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.executor import QueryExecutor
from pinot_tpu.timeseries.engine import query as ts_query
from pinot_tpu.utils import tracing
from pinot_tpu.utils.failpoints import failpoints
from tests.test_param_packing import (BUCKETS, STEP, T0, _capture,
                                      _dispatch_spans, _segment)


@pytest.fixture(scope="module")
def segs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("narrowlock")
    return [_segment(tmp, i) for i in range(3)]


def _engine(devices: int) -> TpuOperatorExecutor:
    if len(jax.devices()) < devices:
        pytest.skip(f"needs {devices} virtual devices")
    return TpuOperatorExecutor(devices=jax.devices()[:devices])


# -- (a) the argument's new shape, bit for bit --------------------------------
#: leg -> SQL with one literal `{a}`, or None: the time-bucket dashboard
LEGS = {
    "range": "SELECT SUM(m), COUNT(*) FROM t WHERE d BETWEEN {a} AND 8",
    "neq": "SELECT SUM(m), COUNT(*) FROM t WHERE d <> {a}",
    "vrange64": "SELECT COUNT(*) FROM t WHERE x > {a}000000000",
    "vrange": "SELECT SUM(m), COUNT(*) FROM t WHERE f > {a}.5 AND f <= 1800.25",
    "lut": "SELECT d, COUNT(*) FROM t WHERE d IN ({a}, 7, 9) "
           "AND f < 1{a}00.5 GROUP BY d",
    "tb": None,
}


def _members(eng, segs, leg: str, n: int):
    """n staged launches of one plan that differ in a literal."""
    launches = _capture(eng)
    for a in range(1, n + 1):
        if LEGS[leg] is None:
            ts_query(f"fetch(t, f, ts, {T0 + a}, {T0 + BUCKETS * STEP}, "
                     f"{STEP}) | groupby(d) | sum(d)",
                     QueryExecutor(segs, use_tpu=True, engine=eng))
        else:
            res, rem = eng.execute(segs, QueryContext.from_sql(
                LEGS[leg].format(a=a)))
            assert not rem
    members = [la for la in launches
               if bool(la.plan.tbucket) == (LEGS[leg] is None)][:n]
    assert len(members) == n
    assert len({la.plan for la in members}) == 1
    assert len({la.params[PACK].tobytes() for la in members}) == n
    return members


def _as_pr32_staged(eng, params: dict) -> dict:
    """A member's params as PR 32 staged them: the pack one device array
    a member, put with a NamedSharding over `segments` on a mesh."""
    pack = params[PACK]
    assert type(pack) is np.ndarray and pack.dtype == np.int32
    if eng._mesh is None:
        put = jax.numpy.asarray(pack)
    else:
        put = jax.device_put(
            pack, NamedSharding(eng._mesh, P(None, "segments")))
    return dict(params, **{PACK: put})


@pytest.mark.parametrize("devices", [1, 4, 8])
@pytest.mark.parametrize("leg", sorted(LEGS))
def test_host_stacked_pack_answers_as_per_member_device_packs(segs, leg,
                                                              devices):
    eng = _engine(devices)
    members = _members(eng, segs, leg, 8)
    lead = members[0]
    has_lut = any(k.endswith(":lut") for k in lead.params)
    assert has_lut == (leg == "lut")
    K, S = lead.params[PACK].shape
    for B in (1, 2, 4, 8):
        batch = members[:B]
        if B == 1:
            kern = kernels.compiled_kernel(lead.plan)
            host = kern(lead.cols, lead.params, None, D=lead.D, G=lead.G)
            dev = kern(lead.cols, _as_pr32_staged(eng, lead.params), None,
                       D=lead.D, G=lead.G)
        else:
            kern = lead.factory(B, False)
            plist = batch_params([la.params for la in batch])
            assert type(plist[PACK]) is np.ndarray
            assert plist[PACK].shape == (B, K, S)
            # what is not in the pack stays one device array a member
            assert all(isinstance(v, tuple) and len(v) == B
                       for k, v in plist.items() if k != PACK)
            host = kern(lead.cols, plist, None, D=lead.D, G=lead.G)
            dev = kern(lead.cols, batch_params(
                [_as_pr32_staged(eng, la.params) for la in batch]), None,
                D=lead.D, G=lead.G)
        host, dev = np.asarray(host), np.asarray(dev)
        assert host.dtype == dev.dtype and host.shape == dev.shape
        assert host.tobytes() == dev.tobytes(), (leg, devices, B)
        # and member i of the batch is what the query gets alone
        if B > 1:
            alone = kernels.compiled_kernel(lead.plan)
            for i, la in enumerate(batch):
                one = np.asarray(alone(la.cols, la.params, None,
                                       D=la.D, G=la.G))
                assert host[i].tobytes() == one.tobytes(), (leg, B, i)


# -- (b) the lock is narrow ---------------------------------------------------
SLEEP_S = 0.05


def test_eight_resolves_overlap_and_the_lock_is_held_for_look_ups_only(
        segs, monkeypatch):
    eng = _engine(1)
    sql = "SELECT SUM(m), COUNT(*) FROM t WHERE d BETWEEN {a} AND 9 " \
          "AND f < 1500"
    # the blocks go up and the kernel is looked up once, unstubbed
    assert eng._prepare_agg(segs, QueryContext.from_sql(sql.format(a=0)))
    resolve = TpuOperatorExecutor._resolve_leaf
    resolved = []

    def slow(segments, expr):
        resolved.append(threading.get_ident())
        time.sleep(SLEEP_S)
        return resolve(segments, expr)
    monkeypatch.setattr(TpuOperatorExecutor, "_resolve_leaf",
                        staticmethod(slow))
    root = tracing.SpanHandle(tracing.TraceNode("ServerRequest"), "t")
    start = threading.Barrier(8)

    def stage(a):
        ctx = QueryContext.from_sql(sql.format(a=a))
        start.wait(5)
        t0 = time.perf_counter()
        prep = eng._prepare_agg(segs, ctx, parent_span=root)
        return prep, time.perf_counter() - t0
    t0 = time.perf_counter()
    with ThreadPoolExecutor(8) as pool:
        out = list(pool.map(stage, range(1, 9)))
    wall = time.perf_counter() - t0
    # one dictionary leaf a query ('d'; 'f' is a raw vrange): 8 sleeps
    assert len(resolved) == 8 and len(set(resolved)) == 8
    assert all(prep is not None for prep, _s in out)
    # serialized under the lock they would take 8 x 50 ms
    assert wall < 8 * SLEEP_S / 2, wall
    spans = [prep[3].span.node.attrs for prep, _s in out]
    for attrs in spans:
        assert attrs["lockHeldMs"] < SLEEP_S * 1e3, attrs
        assert attrs["paramsMs"] >= SLEEP_S * 1e3 * 0.9, attrs
        assert attrs["paramPuts"] == 0
        tiled = attrs["planMs"] + attrs["blocksMs"] + attrs["paramsMs"]
        assert attrs["stagingMs"] == pytest.approx(tiled, abs=0.01)
        assert attrs["blocksMs"] <= attrs["lockHeldMs"] + 0.001
    # nobody queued behind a sleeper: the waits are the look-ups' own
    assert max(a["lockWaitMs"] for a in spans) < SLEEP_S * 1e3


# -- (c) no put for the pack; its bytes on the launch's span ------------------
def _traced(qe, sql):
    resp = qe.execute(f"SET trace = true; {sql} OPTION(skipCache=true)")
    assert not resp.exceptions
    span, = _dispatch_spans(resp.trace)
    return span


@pytest.mark.parametrize("devices", [1, 4])
def test_a_hit_resolves_and_puts_nothing_and_the_launch_carries_the_pack(
        segs, devices, monkeypatch):
    eng = _engine(devices)
    qe = QueryExecutor(segs, use_tpu=True, engine=eng)
    resolve = TpuOperatorExecutor._resolve_leaf
    calls = []
    monkeypatch.setattr(
        TpuOperatorExecutor, "_resolve_leaf",
        staticmethod(lambda s, e: calls.append(e) or resolve(s, e)))
    meter = eng._metrics.meter
    plain = "SELECT SUM(m), COUNT(*) FROM t WHERE d BETWEEN {a} AND 8"
    lut = "SELECT d, COUNT(*) FROM t WHERE d IN ({a}, 7, 9) GROUP BY d"
    _traced(qe, plain.format(a=0)), _traced(qe, lut.format(a=0))  # blocks up
    for sql, miss_puts in ((plain, 0), (lut, 1)):
        del calls[:]
        before = meter("hbm_transfer_bytes")
        miss = _traced(qe, sql.format(a=2))
        assert len(calls) == 1 and miss["paramPuts"] == miss_puts
        # the cache is an LRU: the newest entry is this query's
        pack = list(eng.stager._params_cache.values())[-1][1][PACK]
        K, S = pack.shape
        assert miss["paramsXferBytes"] == K * S * 4
        assert miss["variant"] == "inline" and miss["batchSize"] == 1
        # the meter counts the launch's argument, and a LUT's put
        assert meter("hbm_transfer_bytes") - before == \
            K * S * 4 + miss["transferBytes"]
        assert (miss["transferBytes"] > 0) == bool(miss_puts)
        del calls[:]
        before = meter("hbm_transfer_bytes")
        hit = _traced(qe, sql.format(a=2))
        assert not calls and hit["paramPuts"] == 0
        assert hit["transferBytes"] == 0
        assert hit["paramsXferBytes"] == K * S * 4
        assert meter("hbm_transfer_bytes") - before == K * S * 4
        assert hit["lockHeldMs"] <= hit["stagingMs"]


def test_a_batch_carries_one_stacked_pack(segs):
    """Five queries coalesced into one launch: ONE [8, K, S] argument
    (the bucket, padded with the leader's), its bytes on every member's
    span and counted once."""
    failpoints.clear()
    eng = _engine(1)
    qe = QueryExecutor(segs, use_tpu=True, engine=eng)
    sql = "SELECT SUM(m), COUNT(*) FROM t WHERE d BETWEEN {a} AND 9"
    _traced(qe, sql.format(a=0))
    before = eng._metrics.meter("hbm_transfer_bytes")
    failpoints.arm("server.dispatch.before", delay=0.25, times=2)
    try:
        with ThreadPoolExecutor(5) as pool:
            spans = list(pool.map(
                lambda a: _traced(qe, sql.format(a=a)), range(1, 6)))
    finally:
        failpoints.disarm("server.dispatch.before")
        failpoints.clear()
    pack = list(eng.stager._params_cache.values())[-1][1][PACK]
    K, S = pack.shape
    launches = {}
    for span in spans:
        bucket = 1
        while bucket < span["batchSize"]:
            bucket *= 2
        assert span["paramsXferBytes"] == K * S * 4 * bucket, span
        assert span["paramPuts"] == 0
        launches[span["launchNs"]] = span["paramsXferBytes"]
    assert max(s["batchSize"] for s in spans) > 1, "nothing coalesced"
    assert eng._metrics.meter("hbm_transfer_bytes") - before == \
        sum(launches.values())
