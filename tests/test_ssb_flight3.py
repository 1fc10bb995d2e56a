"""SSB flight 3, Q3.2 (revenue by customer city x supplier city x year
within one nation), at a size a test run can hold: the configuration's
OWN columns (benchmark/datagen, seeded), three segments of unequal docs,
served by `ServerQueryExecutor` over `_shared_engine()` holding one
device, as the cell's server holds one chip, in f32 as the chip runs
it. The key space is 250 x 250 x 7 = 437,500 groups, past
ONEHOT2_MAX_GROUPS: the XLA scatter-add, folded on the device into a
pow2 table of 524,288 slots. Every answer has to be the rows
`benchmark/reference.py` gives (keys, order, exact COUNT; the f32 SUM
within the configuration's limit), and the launch's span has to name
the scatter's work: each segment's kept rows compacted to a rung
(`scatterCap`, `scatterRows`). The reference reads `c_nation = N`
as a range of cities, which holds only because a city is its nation's
name cut or padded to 9 characters plus a digit: pinned here."""
import copy
import dataclasses
import os
import sys

import numpy as np
import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import datagen  # noqa: E402  (benchmark/)
import reference  # noqa: E402  (benchmark/)
import traffic  # noqa: E402  (benchmark/)

from pinot_tpu.ops import kernels  # noqa: E402
from pinot_tpu.ops.plan_ir import DevicePlan  # noqa: E402
from pinot_tpu.query.context import QueryContext  # noqa: E402
from pinot_tpu.query.reduce import reduce_results  # noqa: E402
from pinot_tpu.query.results import GroupByResult  # noqa: E402
from pinot_tpu.segment.creator import SegmentCreator  # noqa: E402
from pinot_tpu.segment.loader import load_segment  # noqa: E402
from pinot_tpu.utils.config import PinotConfiguration  # noqa: E402

SEED = 2_600_000_043  # past 2**31, as a benchmark run's seed may be
#: segments of unequal docs, none a power of two
DOCS = (150000, 67001, 95500)
#: the key space, and the fold's pow2 table: 256 x 256 x 8
KEY_SPACE, FOLDED = 250 * 250 * 7, 1 << 19
#: SUM and the COUNT every grouped plan carries
SLOTS = 2
#: the flight-2 table the one-hot comparison runs on
FLIGHT2_DOCS = (20000, 13001)


@pytest.fixture(scope="module")
def cell():
    _bench, cell, config, mix = traffic.load_cell(ROOT, "ssb3_q32_c1")
    assert cell["config"] == "ssb_cities_128m_1chip" and cell["chips"] == 1
    return config, mix


def build(config, docs, tmp, seed=SEED):
    """(loaded segments, the plain reference of the same rows, the
    made columns of each segment)."""
    tc, schema = datagen.table_and_schema(config)
    ref = reference.Reference(config, datagen.domains(config))
    segs, made_all = [], []
    for i, n in enumerate(docs):
        made = datagen.make_columns(config, seed, i, n)
        ref.add(reference.segment_share(config, made))
        name = f"{config['table']}_{i}"
        SegmentCreator(tc, schema).build(
            {k: v[0] for k, v in made.items()}, str(tmp / name), name)
        segs.append(load_segment(str(tmp / name)))
        made_all.append(made)
    return segs, ref, made_all


@pytest.fixture(scope="module")
def table(cell, tmp_path_factory):
    config, _mix = cell
    return build(config, DOCS, tmp_path_factory.mktemp("ssb3"))


@pytest.fixture(scope="module")
def served(cell, table):
    """The server's executor over the table; `_shared_engine()` finds
    one device."""
    from pinot_tpu.server.data_manager import InstanceDataManager
    from pinot_tpu.server.query_server import ServerQueryExecutor
    config, _mix = cell
    dm = InstanceDataManager("server_0")
    ex = ServerQueryExecutor(dm, use_tpu=True, config=PinotConfiguration())
    one, everything = jax.devices()[:1], jax.devices
    jax.devices = lambda *a: one
    try:
        engine = ex._shared_engine()
    finally:
        jax.devices = everything
    for seg in table[0]:
        dm.table(config["table"] + "_OFFLINE").add_segment(seg)
    yield ex, engine, dm
    dm.shutdown()
    ex.segment_cache.close()
    ex.fingerprint_log.close()


def spans(tree, name: str) -> list:
    out = [tree] if tree.get("operator") == name else []
    for c in tree.get("children", ()):
        out += spans(c, name)
    return out


def ask(ex, table: str, sql: str):
    """(broker response, server results, DeviceDispatch spans) of one
    query through the server's executor and the broker's reduce, in
    f32."""
    from pinot_tpu.server.datatable import deserialize_results_ex
    with jax.enable_x64(False):
        payload = ex.execute(table + "_OFFLINE", sql, trace_ctx={
            "traceId": "ssb3", "spanId": "1", "sampled": True})
    results, exceptions, _stats, trace = deserialize_results_ex(payload)
    assert not exceptions
    resp = reduce_results(QueryContext.from_sql(sql), results)
    return resp, results, spans(trace, "DeviceDispatch")


def with_count(template: dict, sql: str):
    """The template and its SQL with COUNT(*) after the SUM, so that the
    row count of each group is compared exactly too."""
    counted = copy.deepcopy(template)
    counted["select"].insert(1, ["count"])
    return counted, sql.replace(" AS revenue, ", " AS revenue, COUNT(*), ")


def test_q3_2_answers_as_the_reference_does(cell, table, served):
    config, mix = cell
    _segs, ref, _made = table
    ex, engine, _dm = served
    limit = config["limits"]["grouped_sum_max_rel_err"]
    assert len(engine.devices) == 1 and engine._mesh is None
    assert [t["name"] for t in mix["templates"]] == ["q3_2"]
    queries = traffic.make_queries(mix, config["table"], SEED, 1, 6, False)
    assert len({q[1]["n"][2] for q in queries}) >= 5  # several nations
    worst = 0.0
    for t, literals, sql in queries:
        nation = literals["n"][2]
        assert f"c_nation = '{nation}' AND s_nation = '{nation}'" in sql
        for template, text in ((mix["templates"][t], sql),
                               with_count(mix["templates"][t], sql)):
            resp, results, dispatches = ask(ex, config["table"], text)
            want = ref.answer(template, literals)
            got = [list(r) for r in resp.result_table.rows]
            assert 50 < len(want) <= 600
            assert len(got) == len(want)
            for g, w in zip(got, want):
                # SUM [, COUNT], then c_city, s_city, d_year
                assert (g[-3], g[-2], int(g[-1])) == tuple(w[-3:]), (g, w)
                assert g[-3][:9].rstrip() == nation[:9]
                worst = max(worst, abs(float(g[0]) - w[0]) / w[0])
                if len(w) == 5:
                    assert int(g[1]) == w[1]
            # one result for the batch, not one a segment
            assert len(results) == 1 \
                and isinstance(results[0], GroupByResult)
            span, = dispatches
            assert "outcome" not in span, "fell back to the host"
            assert span["groupPath"] == "scatter"
            assert span["groupKeySpace"] == KEY_SPACE
            assert span["groupFold"] == "device"
            assert span["groupsPresent"] == len(want)
            assert span["groupResultBytes"] == FOLDED * SLOTS * 4
            # each segment's kept rows compacted to the rung that holds
            # the most any segment kept, a slot each
            cap = kernels.compact_cap(span["D"], max(
                kept_rows(made, nation) for made in table[2]))
            assert cap == span["D"] >> 9
            assert span["scatterCap"] == cap
            assert span["scatterRows"] == span["S"] * cap * SLOTS
            assert span["S"] >= len(DOCS) and span["D"] >= max(DOCS)
    assert worst <= limit, worst


def kept_rows(made, nation: str) -> int:
    """Rows of one segment Q3.2 keeps: both nations N, 1992-1997."""
    year = made["d_year"][0].astype(int)
    return int(((made["c_nation"][0] == nation)
                & (made["s_nation"][0] == nation)
                & (year >= 1992) & (year <= 1997)).sum())


def test_scatter_rows_is_metered(cell, served):
    config, mix = cell
    ex, engine, _dm = served
    labels = dict(engine._labels or {})
    before = engine._metrics.meter("scatter_rows", labels=labels)
    _t, _lit, sql = traffic.make_queries(
        mix, config["table"], SEED, 2, 1, False)[0]
    _resp, _results, (span,) = ask(ex, config["table"], sql)
    assert engine._metrics.meter("scatter_rows", labels=labels) - before \
        == span["scatterRows"] > 0


def test_the_compaction_rung_is_metered(cell, served):
    config, mix = cell
    ex, engine, _dm = served
    _t, _lit, sql = traffic.make_queries(
        mix, config["table"], SEED, 3, 1, False)[0]
    labels = dict(engine._labels or {})
    _resp, _results, (span,) = ask(ex, config["table"], sql)
    cap = span["scatterCap"]
    assert cap > 0
    before = engine._metrics.meter("scatter_compact",
                                   labels=dict(labels, cap=str(cap)))
    ask(ex, config["table"], sql)
    assert engine._metrics.meter("scatter_compact",
                                 labels=dict(labels, cap=str(cap))) \
        - before == 1


def test_a_query_that_keeps_every_row_takes_the_full_scatter(cell, table,
                                                              served):
    """Q3.2's GROUP BY with no WHERE keeps every row, past the top rung:
    the full scatter, `scatterCap` 0 and every padded row of every padded
    segment a slot; the counts as numpy's."""
    config, _mix = cell
    _segs, _ref, made_all = table
    ex, _engine, _dm = served
    sql = (f"SELECT SUM(lo_revenue) AS revenue, COUNT(*), c_city, s_city, "
           f"d_year FROM {config['table']} GROUP BY c_city, s_city, d_year "
           f"ORDER BY COUNT(*) DESC, c_city, s_city, d_year LIMIT 5")
    resp, _results, (span,) = ask(ex, config["table"], sql)
    assert span["groupPath"] == "scatter" and span["scatterCap"] == 0
    assert span["scatterRows"] == span["S"] * span["D"] * SLOTS
    counts = {}
    for made in made_all:
        for key in zip(made["c_city"][0], made["s_city"][0],
                       made["d_year"][0].astype(int)):
            counts[key] = counts.get(key, 0) + 1
    top = sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))[:5]
    assert [(int(r[1]), r[2], r[3], int(r[4]))
            for r in resp.result_table.rows] \
        == [(n, c, s, y) for (c, s, y), n in top]


def test_a_flight_2_query_hands_the_scatter_nothing(served, tmp_path_factory):
    """Q2.1 on flight 2's own table (G = 7,000: `onehot2`) and its
    category roll-up (G = 175: `onehot`) over the same engine: no row
    reaches XLA's scatter-add, and the span says 0, not nothing."""
    ex, _engine, dm = served
    _bench, _cell, config, mix = traffic.load_cell(ROOT, "ssb2_q2_c1")
    segs, ref, _made = build(config, FLIGHT2_DOCS,
                             tmp_path_factory.mktemp("ssb2"))
    for seg in segs:
        dm.table(config["table"] + "_OFFLINE").add_segment(seg)
    t, literals, sql = traffic.make_queries(
        mix, config["table"], SEED, 1, 1, False)[0]
    assert mix["templates"][t]["name"] == "q2_1"
    resp, _results, (span,) = ask(ex, config["table"], sql)
    assert len(resp.result_table.rows) \
        == len(ref.answer(mix["templates"][t], literals))
    assert (span["groupPath"], span["groupKeySpace"], span["scatterRows"]) \
        == ("onehot2", 7000, 0)
    _bench, _cell, _config, cat = traffic.load_cell(ROOT, "ssb2_cat_c1")
    _t, _lit, sql = traffic.make_queries(
        cat, config["table"], SEED, 1, 1, False)[0]
    _resp, _results, (span,) = ask(ex, config["table"], sql)
    assert (span["groupPath"], span["groupKeySpace"], span["scatterRows"]) \
        == ("onehot", 175, 0)


PLAN = DevicePlan(
    filter_ir=None, leaves=(), value_irs=(("col", "m"),),
    agg_ops=(("sum", 0, None), ("count", None, None), ("max", 0, None)),
    group_cols=("a",), group_strides=(1,), num_groups=6)


@pytest.mark.parametrize("G,D,nonfinite,kept,rows", [
    # Q3.2: about 10,970 kept rows a segment, on the D / 512 rung
    (KEY_SPACE, 1 << 23, False, 10970, 16 * 16384 * 2),
    (KEY_SPACE, 1 << 23, False, 16384, 16 * 16384 * 2),      # rung edge
    (KEY_SPACE, 1 << 23, False, 16385, 16 * 131072 * 2),     # next rung
    (KEY_SPACE, 1 << 23, False, 1 << 20, 16 * (1 << 20) * 2),  # top rung
    (KEY_SPACE, 1 << 23, False, (1 << 20) + 1,
     16 * (1 << 23) * 2),                           # past it: the full scatter
    (KEY_SPACE, 1 << 15, False, 5, 16 * (1 << 15) * 2),  # under the minimum
    (7000, 1 << 15, False, 5, 0),                          # onehot2
    (7000, 1 << 15, True, 5, 16 * (1 << 15) * 2),  # Inf/NaN keep the scatter
    (7000, 1 << 16, True, 5, 16 * 128 * 2),        # ... and compact
    (7000, 4096, False, 5, 16 * 4096 * 2),         # under onehot2's chunk
    (175, 1 << 15, False, 5, 0),                           # onehot, whole chunks
    (175, 3 * 4096 + 100, False, 5, 16 * 100 * 2),         # onehot's tail
    (175, 1000, False, 5, 16 * 1000 * 2),          # under onehot's chunk
])
def test_scatter_rows_follows_the_kernel_s_own_routing(G, D, nonfinite, kept,
                                                       rows):
    """Rows x additive slots (MAX scatters too, but not by adding), as
    `group_path` routes them and, on `scatter`, as the kernel compacts
    them: the rung `compact_cap` reads from the most rows a segment
    kept."""
    plan = dataclasses.replace(PLAN, num_groups=G, nonfinite=nonfinite)
    with jax.enable_x64(False):
        path = kernels.group_path(G, D, kernels._value_dtype(),
                                  finite=not nonfinite)
        cap = kernels.compact_cap(D, kept) if path == "scatter" else 0
        assert kernels.scatter_rows(plan, G, 16, D, D, cap) == rows
        assert kernels.compacts(plan, G, D) == (path == "scatter"
                                                and D >= 1 << 16)
    assert (path == "onehot2") <= (rows == 0)


def test_c_nation_is_a_function_of_c_city_and_a_range_of_cities(cell,
                                                                table):
    """As dbgen makes them: every row's nation is its city's parent, and
    each nation's pool entry `[first city, last city, nation]` selects,
    as a `between` over the city domain, exactly its own ten cities:
    what `reference.answer` reads for `c_nation = N`."""
    config, mix = cell
    _segs, _ref, made_all = table
    nations = config["pools"]["ssb_nations"]
    cities = np.asarray(config["pools"]["ssb_cities"], dtype=object)
    assert len(nations) == 25 and len(set(cities)) == 250
    for made in made_all:
        for side in ("c", "s"):
            city, _codes, _dom = made[f"{side}_city"]
            nation, codes, _dom = made[f"{side}_nation"]
            assert (nation == np.asarray(nations, dtype=object)[
                made[f"{side}_city"][1] // 10]).all()
            prefixes = np.array([c[:9] for c in city], dtype=object)
            assert (prefixes == np.array(
                [n[:9].ljust(9) for n in nation], dtype=object)).all()
            assert len(set(codes)) == 25
    entries = mix["pools"]["nation"]
    assert [e[2] for e in entries] == nations
    for k, (first, last, nation) in enumerate(entries):
        picked = (cities >= first) & (cities <= last)
        assert picked.sum() == 10
        assert (np.flatnonzero(picked) == np.arange(10 * k, 10 * k + 10)).all()
        assert all(c[:9].rstrip() == nation[:9] for c in cities[picked])
