"""One packed parameter array a query (ops/plan_ir.py `pack_params`,
ops/kernels.py `unpack_params`, engine `_stage`); since PR 33 a host
array that rides the launch as a jit argument, never put.

  * every leaf kind whose parameters are [S]-shaped ('range', 'neq',
    'vrange' under f32 and f64 staging, 'vrange64', the 'hist:' slots'
    bounds, the time bucket's 'tb:' cells) rides ONE int32 [K, S] array:
    the kernel run on the pack answers bit for bit what it answers on
    the per-array parameters, and a float bound reaches it with the bits
    the host computed
  * a parameter-cache miss puts nothing (1 for a LUT leaf's [S, C]
    table), a hit nothing, on a 4-device mesh engine too; the cache
    keeps the pack as the numpy array the launch takes
  * the row layout is a function of the plan: new literals and the batch
    buckets 2 / 4 / 8 of one plan trace nothing new
"""
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import jax

from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              TableConfig)
from pinot_tpu.ops import kernels
from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.ops.plan_ir import NUM_DOCS, PACK, pack_layout, pack_params
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.executor import QueryExecutor
from pinot_tpu.segment.creator import SegmentCreator
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.timeseries.engine import query as ts_query
from pinot_tpu.utils.failpoints import failpoints

T0, STEP, BUCKETS = 1000, 20, 6


def _segment(tmp, i, d_values=10):
    schema = Schema("t", [
        FieldSpec("d", DataType.INT, FieldType.DIMENSION),
        FieldSpec("ts", DataType.LONG, FieldType.DIMENSION),
        FieldSpec("m", DataType.INT, FieldType.METRIC),
        FieldSpec("f", DataType.FLOAT, FieldType.METRIC),
        FieldSpec("x", DataType.LONG, FieldType.METRIC)])
    tc = TableConfig(name="t")
    tc.indexing.no_dictionary_columns = ["m", "f", "x"]
    rng = np.random.default_rng(40 + i)
    n = 3000 + 500 * i
    seg_dir = os.path.join(str(tmp), f"t_{i}")
    SegmentCreator(tc, schema).build({
        "d": rng.integers(0, d_values, n).astype(np.int32),
        "ts": rng.integers(T0, T0 + BUCKETS * STEP, n),
        "m": rng.integers(0, 100, n).astype(np.int32),
        "f": (rng.random(n) * 2000).astype(np.float32),
        "x": rng.integers(0, 1 << 40, n, dtype=np.int64),
    }, seg_dir, f"t_{i}")
    return load_segment(seg_dir)


@pytest.fixture(scope="module")
def segs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("packsegs")
    return [_segment(tmp, i) for i in range(3)]


def _capture(eng):
    """Every launch the engine submits from here on, as staged."""
    launches = []
    submit = eng._dispatcher.submit

    def spy(launch):
        launches.append(launch)
        return submit(launch)
    eng._dispatcher.submit = spy
    return launches


def _per_array(plan, params):
    """The host's inverse of the pack: the named arrays the per-array
    staging gave the kernel, and num_docs."""
    pack = np.asarray(params[PACK])
    vdt = np.dtype(kernels._value_dtype())
    out = {k: v for k, v in params.items() if k != PACK}
    row = 0
    for name, kind in pack_layout(plan):
        width = 1 if kind == "i" else vdt.itemsize // 4
        words = np.ascontiguousarray(pack[row:row + width].T)
        out[name] = words.reshape(-1) if kind == "i" \
            else words.view(vdt).reshape(-1)
        row += width
    assert row == pack.shape[0]
    return out, out.pop(NUM_DOCS)


def _both_ways(launch):
    """(the kernel's answer on the pack, on the per-array parameters)."""
    plan = launch.plan
    kernel = kernels.compiled_topn_kernel(plan) if plan.mode == "topn" \
        else kernels.compiled_kernel(plan)
    static = {"D": launch.D} if plan.mode == "topn" \
        else {"D": launch.D, "G": launch.G}
    arrays, num_docs = _per_array(plan, launch.params)
    packed = np.asarray(kernel(launch.cols, launch.params, None, **static))
    plain = np.asarray(kernel(launch.cols, arrays, num_docs, **static))
    return packed, plain


#: leaf kind -> (x64, SQL, the layout rows it must bring)
LEGS = {
    "range": (True, "SELECT SUM(m), COUNT(*) FROM t WHERE d BETWEEN 2 AND 6",
              ["leaf0:lo", "leaf0:hi"]),
    "neq": (True, "SELECT SUM(m), COUNT(*) FROM t WHERE d <> 3",
            ["leaf0:idx"]),
    "vrange-f64": (True, "SELECT SUM(m), COUNT(*) FROM t "
                         "WHERE f > 0.1 AND f <= 1234.56789",
                   ["leaf0:lo", "leaf0:hi", "leaf1:lo", "leaf1:hi"]),
    "vrange-f32": (False, "SELECT SUM(m), COUNT(*) FROM t "
                          "WHERE f > 0.15625 AND f <= 1234.5",
                   ["leaf0:lo", "leaf0:hi", "leaf1:lo", "leaf1:hi"]),
    "vrange64": (False, "SELECT COUNT(*) FROM t WHERE x > 20000000",
                 ["leaf0:lohi", "leaf0:lolo", "leaf0:hihi", "leaf0:hilo"]),
    "vrange64-hll": (True, "SELECT DISTINCTCOUNTHLL(x) FROM t "
                           "WHERE x > 5000",
                     ["leaf0:lohi", "leaf0:lolo", "leaf0:hihi",
                      "leaf0:hilo"]),
    "hist": (True, "SELECT PERCENTILETDIGEST95(f), COUNT(*) FROM t "
                   "WHERE d < 8",
             ["slot0:hlo", "slot0:hscale", "leaf0:lo", "leaf0:hi"]),
    "hist-f32": (False, "SELECT PERCENTILETDIGEST95(f), COUNT(*) FROM t",
                 ["slot0:hlo", "slot0:hscale"]),
    "topn": (True, "SELECT m, f FROM t WHERE d >= 4 ORDER BY m DESC "
                   "LIMIT 7", ["leaf0:lo", "leaf0:hi"]),
    "groupby": (True, "SELECT d, SUM(m) FROM t WHERE d <> 1 AND f < 1500 "
                      "GROUP BY d", ["leaf0:idx", "leaf1:lo", "leaf1:hi"]),
}


@pytest.mark.parametrize("leg", sorted(LEGS))
def test_packed_kernel_answers_as_the_per_array_one(segs, leg):
    x64, sql, rows = LEGS[leg]
    with jax.enable_x64(x64):
        eng = TpuOperatorExecutor()
        launches = _capture(eng)
        host = QueryExecutor(segs, use_tpu=False).execute(sql)
        dev = QueryExecutor(segs, use_tpu=True, engine=eng).execute(sql)
        assert not dev.exceptions and len(launches) == 1, leg
        if "DIGEST" not in sql:  # the sketch is approximate either way
            assert sorted(map(tuple, dev.rows)) == \
                sorted(map(tuple, host.rows))
        launch, = launches
        assert launch.num_docs is None
        names = [name for name, _kind in pack_layout(launch.plan)]
        assert names[0] == NUM_DOCS and names[1:] == rows
        # only the pack: no [S] array is left beside it (a GROUP BY's
        # fold brings its [S, U] inverse remap tables, one a group column)
        beside = {f"ginv{i}" for i in range(len(launch.plan.group_fold))}
        assert set(launch.params) == {PACK} | beside
        assert launch.params[PACK].dtype == np.int32
        packed, plain = _both_ways(launch)
        assert packed.dtype == plain.dtype
        assert packed.tobytes() == plain.tobytes()


def test_time_bucket_cells_ride_the_pack(segs):
    eng = TpuOperatorExecutor()
    launches = _capture(eng)
    dash = (f"fetch(t, f, ts, {T0}, {T0 + BUCKETS * STEP}, {STEP}) "
            f"| groupby(d) | sum(d)")
    ts_query(dash, QueryExecutor(segs, use_tpu=True, engine=eng))
    launch = next(la for la in launches if la.plan.tbucket)
    names = [name for name, _kind in pack_layout(launch.plan)]
    assert names[1:5] == ["tb:shi", "tb:slo", "tb:step", "tb:count"]
    arrays, _nd = _per_array(launch.plan, launch.params)
    assert arrays["tb:step"].tolist() == [STEP] * len(arrays["tb:step"])
    assert arrays["tb:slo"][0] == T0 and arrays["tb:count"][0] == BUCKETS
    packed, plain = _both_ways(launch)
    assert packed.tobytes() == plain.tobytes()


@pytest.mark.parametrize("x64", [False, True], ids=["f32", "f64"])
def test_float_bounds_reach_the_kernel_bit_exact(x64):
    """pack -> unpack, under jit, gives back the bits the host wrote: a
    bit-cast, never a conversion (NaN payloads, -0.0, subnormals and the
    nextafter neighbours of a literal all survive)."""
    from pinot_tpu.ops.plan_ir import DeviceLeaf, DevicePlan
    plan = DevicePlan(
        filter_ir=("and", ("leaf", 0), ("leaf", 1)),
        leaves=(DeviceLeaf("vrange", "f"), DeviceLeaf("range", "d")),
        value_irs=(("col", "f"),), agg_ops=(("hist:64", 0, None),))
    with jax.enable_x64(x64):
        vdt = np.float64 if x64 else np.float32
        odd = np.array([0.1, -0.0, np.nextafter(vdt(2.5), vdt(np.inf)),
                        np.finfo(vdt).tiny / 4, np.inf, -np.inf,
                        np.finfo(vdt).max, 1 / 3], dtype=vdt)
        S = len(odd)
        rows = {NUM_DOCS: np.arange(S, dtype=np.int32),
                "slot0:hlo": odd, "slot0:hscale": odd[::-1].copy(),
                "leaf0:lo": -odd, "leaf0:hi": odd / vdt(3),
                "leaf1:lo": np.full(S, -1, np.int32),
                "leaf1:hi": np.full(S, 2**31 - 1, np.int32)}
        pack = pack_params(plan, rows)
        assert pack.dtype == np.int32
        assert pack.shape == (3 + 4 * (2 if x64 else 1), S)
        got, num_docs = jax.jit(
            lambda p: kernels.unpack_params(plan, p))({PACK: pack})
        assert np.asarray(num_docs).tolist() == list(range(S))
        for name, want in rows.items():
            if name == NUM_DOCS:
                continue
            have = np.asarray(got[name])
            assert have.dtype == want.dtype, name
            assert have.tobytes() == want.tobytes(), name
        # a stacked batch of packs unpacks member by member
        both, nd2 = jax.jit(lambda p: kernels.unpack_params(plan, p))(
            {PACK: np.stack([pack, pack[:, ::-1]])})
        assert np.asarray(both["leaf0:lo"]).tobytes() == \
            np.stack([rows["leaf0:lo"], rows["leaf0:lo"][::-1]]).tobytes()
        assert np.asarray(nd2).shape == (2, S)


def test_a_leaf_resolves_once_a_distinct_dictionary(segs, tmp_path,
                                                    monkeypatch):
    """`resolve_predicate` reads a segment only through its column's
    dictionary: segments that share one (`Dictionary.content_key`) share
    the resolved dictIds, and one whose dictionary differs gets its own."""
    from pinot_tpu.ops import engine as engine_mod
    calls = []
    resolve = engine_mod.resolve_predicate

    def counted(seg, expr):
        calls.append(seg.name)
        return resolve(seg, expr)
    monkeypatch.setattr(engine_mod, "resolve_predicate", counted)
    assert len({s.data_source("d").dictionary.content_key
                for s in segs}) == 1
    narrow = _segment(tmp_path, 7, d_values=5)  # d: 0..4 only
    assert narrow.data_source("d").dictionary.content_key != \
        segs[0].data_source("d").dictionary.content_key
    sql = "SELECT SUM(m), COUNT(*) FROM t WHERE d BETWEEN 3 AND 6 AND d <> 4"
    for batch, expected in ((segs, 2), (segs + [narrow], 4)):
        del calls[:]
        eng = TpuOperatorExecutor(devices=jax.devices()[:1])
        dev = QueryExecutor(batch, use_tpu=True, engine=eng).execute(sql)
        host = QueryExecutor(batch, use_tpu=False).execute(sql)
        assert not dev.exceptions and dev.rows == host.rows
        assert len(calls) == expected, calls  # leaves x distinct dictionaries


def _puts(eng, segs, sql):
    """`_put` calls of one query (the engine keeps no count of its own:
    a staging pass counts its puts on its `_StagePass`)."""
    calls = []
    put = eng._put

    def counted(arr, *args, **kwargs):
        calls.append(arr.shape)
        return put(arr, *args, **kwargs)
    eng._put = counted
    try:
        results, remaining = eng.execute(segs, QueryContext.from_sql(sql))
    finally:
        del eng._put
    assert not remaining, sql
    return len(calls)


#: SQL template -> puts a parameter-cache miss costs: the pack none, it
#: is the launch's own argument; a LUT table one
PUT_LEGS = {
    "SELECT SUM(m), COUNT(*) FROM t WHERE d BETWEEN {a} AND 8 AND f < 1500":
        0,
    "SELECT SUM(m) FROM t WHERE d <> {a} AND x > 5000": 0,
    "SELECT PERCENTILETDIGEST95(f) FROM t WHERE d > {a}": 0,
    "SELECT d, COUNT(*) FROM t WHERE d IN ({a}, 7, 9) GROUP BY d": 1,
    "SELECT m FROM t WHERE d > {a} ORDER BY m LIMIT 5": 0,
}


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("template", sorted(PUT_LEGS))
def test_a_miss_puts_no_pack_and_a_hit_nothing(segs, template, devices):
    eng = TpuOperatorExecutor(devices=jax.devices()[:devices])
    assert (eng._mesh is not None) == (devices > 1)
    _puts(eng, segs, template.format(a=1))  # the column blocks go up
    for a in (2, 3):
        sql = template.format(a=a)
        assert _puts(eng, segs, sql) == PUT_LEGS[template], sql  # miss
        assert _puts(eng, segs, sql) == 0, sql                   # hit
    # the cache keeps what the launch takes: a host array, on a mesh too
    for _segs, params in eng.stager._params_cache.values():
        assert type(params[PACK]) is np.ndarray
        assert params[PACK].dtype == np.int32


def test_the_span_counts_no_put_for_the_pack(segs):
    eng = TpuOperatorExecutor()
    qe = QueryExecutor(segs, use_tpu=True, engine=eng)
    counts = []
    for a in (1, 2, 2):
        resp = qe.execute("SET trace = true; SELECT SUM(m) FROM t "
                          f"WHERE d < {a} AND f >= 0.5 OPTION(skipCache=true)")
        assert not resp.exceptions
        span, = _dispatch_spans(resp.trace)
        counts.append(span["paramPuts"])
    assert counts[1:] == [0, 0]


def _dispatch_spans(tree):
    out, todo = [], [tree]
    while todo:
        node = todo.pop()
        todo += node.get("children", [])
        if node.get("operator") == "DeviceDispatch":
            out.append(node)
    return out


def _batch_of(eng, segs, sqls):
    """Run `sqls` at once with the ring held on its first pops, so that
    they coalesce into one batch (tests/test_dispatch.py's trick)."""
    failpoints.arm("server.dispatch.before", delay=0.25, times=2)
    try:
        with ThreadPoolExecutor(len(sqls)) as pool:
            futs = [pool.submit(eng.execute, segs, QueryContext.from_sql(q))
                    for q in sqls]
            return [f.result() for f in futs]
    finally:
        failpoints.disarm("server.dispatch.before")


@pytest.mark.parametrize("devices", [1, 4, 8])
def test_literals_and_batch_buckets_trace_nothing_new(segs, devices):
    """The layout is the plan's: it is in no cache key. Once a plan's
    single kernel and its batch buckets 2, 4 and 8 have compiled, other
    literals in batches of the same buckets compile nothing: on one
    device (the launch pool's path, held batches) and on a segments
    mesh (GSPMD places the host-stacked [B, K, S] argument itself)."""
    failpoints.clear()
    eng = TpuOperatorExecutor(devices=jax.devices()[:devices])
    sql = "SELECT SUM(m), COUNT(*) FROM t WHERE d BETWEEN {a} AND {b} " \
          "AND f < {c}"
    want = {}

    def round_of(n, shift):
        sqls = [sql.format(a=(i + shift) % 5, b=5 + (i + shift) % 5,
                           c=100.5 + 64 * i + shift) for i in range(n)]
        for q, (res, rem) in zip(sqls, _batch_of(eng, segs, sqls)):
            assert not rem
            got = tuple(tuple(float(v) for v in r.intermediates)
                        for r in res)
            assert want.setdefault(q, got) == got
        return sqls

    seen = set()
    for n in (1, 2, 3, 4, 7, 8):      # buckets 1, 2, 4, 8 compile here
        seen |= set(round_of(n, 0))
    sizes = eng._dispatcher._metrics.timer("dispatch_batch_size").max_ms
    assert sizes >= 5, "no batch reached the bucket of 8"
    before = kernels.trace_count()
    retraced = eng._dispatcher._metrics.meter("kernel_retrace")
    for shift in (1, 2):      # a second and a third batch of each bucket
        for n in (2, 4, 8, 3, 1):
            round_of(n, shift)
    assert kernels.trace_count() == before
    assert eng._dispatcher._metrics.meter("kernel_retrace") == retraced
    # and each answer is the one the query gets alone, off the ring
    lone = TpuOperatorExecutor(devices=jax.devices()[:1])
    for q, got in want.items():
        res, _rem = lone.execute(segs, QueryContext.from_sql(q))
        assert got == tuple(tuple(float(v) for v in r.intermediates)
                            for r in res), q
    failpoints.clear()
