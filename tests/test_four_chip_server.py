"""One server process holding several chips (ISSUE 29), on the virtual
CPU devices the conftest forces:

  (a) the SERVED path — `ServerQueryExecutor` with `_shared_engine()`'s
      implicit segments mesh — answers SSB Q1.1-Q1.3 exactly as a plain
      numpy reference does, over 8 segments of unequal docs;
  (b) the HBM budgets are knobs PER CHIP: an engine's pools are the knob
      times the devices it holds, a one-device engine reads the knob
      unchanged, the resident tier holds each chip to the knob;
  (c) blocks assemble per shard: bit for bit the block the old anchor
      assembly made (every row to device 0, one stack, reshard), every
      resident row on its slab's device, nothing moved chip to chip for a
      fresh batch and only the moved rows counted for a recomposed one;
  (d) a mesh engine's traced DeviceDispatch says how many devices it
      spans and what each chip holds; a one-device engine's says nothing.
"""
import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              TableConfig, TableType)
from pinot_tpu.ops import kernels
from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.parallel.mesh import make_mesh
from pinot_tpu.query.executor import QueryExecutor
from pinot_tpu.segment.creator import SegmentCreator
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.utils.config import PinotConfiguration

#: 8 segments of unequal docs, none a power of two
DOCS = (700, 1300, 450, 2048 + 5, 999, 1601, 350, 1200)
YEARS = (1992, 1993, 1994)
DATES = np.array([y * 10000 + m * 100 + d for y in YEARS
                  for m in range(1, 13) for d in range(1, 29)])

#: SSB flight 1 as benchmark/templates/ssb_flight1.json asks it
Q1 = {
    "q1_1": ("lo_orderdate BETWEEN 19930101 AND 19931228 AND lo_discount "
             "BETWEEN 1 AND 3 AND lo_quantity < 25",
             lambda c: (c["lo_orderdate"] >= 19930101)
             & (c["lo_orderdate"] <= 19931228) & (c["lo_discount"] >= 1)
             & (c["lo_discount"] <= 3) & (c["lo_quantity"] < 25)),
    "q1_2": ("lo_orderdate BETWEEN 19940101 AND 19940128 AND lo_discount "
             "BETWEEN 4 AND 6 AND lo_quantity BETWEEN 26 AND 35",
             lambda c: (c["lo_orderdate"] >= 19940101)
             & (c["lo_orderdate"] <= 19940128) & (c["lo_discount"] >= 4)
             & (c["lo_discount"] <= 6) & (c["lo_quantity"] >= 26)
             & (c["lo_quantity"] <= 35)),
    "q1_3": ("lo_orderdate BETWEEN 19940206 AND 19940212 AND lo_discount "
             "BETWEEN 5 AND 7 AND lo_quantity BETWEEN 26 AND 35",
             lambda c: (c["lo_orderdate"] >= 19940206)
             & (c["lo_orderdate"] <= 19940212) & (c["lo_discount"] >= 5)
             & (c["lo_discount"] <= 7) & (c["lo_quantity"] >= 26)
             & (c["lo_quantity"] <= 35)),
}


def q1_sql(name: str, options: str = "skipCache=true") -> str:
    return ("SELECT SUM(lo_extendedprice * lo_discount), COUNT(*) FROM ssb "
            f"WHERE {Q1[name][0]} OPTION({options})")


def ssb_columns(seed: int, segment: int, docs: int) -> dict:
    rng = np.random.default_rng([seed, segment])
    return {"lo_orderdate": rng.choice(DATES, docs).astype(np.int32),
            "lo_discount": rng.integers(0, 11, docs).astype(np.int32),
            "lo_quantity": rng.integers(1, 51, docs).astype(np.int32),
            "lo_extendedprice": rng.integers(90000, 10000000,
                                             docs).astype(np.int32)}


@pytest.fixture(scope="module")
def ssb(tmp_path_factory):
    """(segments, their columns) of the flat lineorder, seeded."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    tmp = tmp_path_factory.mktemp("four_chip")
    schema = Schema("ssb", [
        FieldSpec("lo_orderdate", DataType.INT, FieldType.DIMENSION),
        FieldSpec("lo_discount", DataType.INT, FieldType.DIMENSION),
        FieldSpec("lo_quantity", DataType.INT, FieldType.DIMENSION),
        FieldSpec("lo_extendedprice", DataType.INT, FieldType.METRIC)])
    tc = TableConfig("ssb", TableType.OFFLINE)
    tc.indexing.no_dictionary_columns = ["lo_extendedprice"]
    creator = SegmentCreator(tc, schema)
    segs, cols = [], []
    for i, docs in enumerate(DOCS):
        made = ssb_columns(29, i, docs)
        creator.build(made, str(tmp / f"ssb_{i}"), f"ssb_{i}")
        segs.append(load_segment(str(tmp / f"ssb_{i}")))
        cols.append(made)
    return segs, cols


def reference(cols: list, name: str) -> tuple:
    """(exact integer SUM, COUNT) over every segment, in numpy."""
    total, count = 0, 0
    for c in cols:
        keep = Q1[name][1](c)
        total += int((c["lo_extendedprice"][keep].astype(np.int64)
                      * c["lo_discount"][keep]).sum())
        count += int(keep.sum())
    return total, count


def implicit_engine(n: int, **overrides) -> TpuOperatorExecutor:
    return TpuOperatorExecutor(
        devices=jax.devices()[:n],
        config=PinotConfiguration(overrides=overrides) if overrides else None)


# -- (a) the served path -----------------------------------------------------
@pytest.fixture(scope="module")
def served(ssb):
    """A server's executor whose `_shared_engine()` finds four devices."""
    from pinot_tpu.server.data_manager import InstanceDataManager
    from pinot_tpu.server.query_server import ServerQueryExecutor
    segs, _cols = ssb
    dm = InstanceDataManager("server_0")
    ex = ServerQueryExecutor(dm, use_tpu=True, config=PinotConfiguration())
    four, everything = jax.devices()[:4], jax.devices
    jax.devices = lambda *a: four  # the host this server runs on has four
    try:
        engine = ex._shared_engine()
    finally:
        jax.devices = everything
    for seg in segs:
        dm.table("ssb_OFFLINE").add_segment(seg)
    yield ex, engine
    dm.shutdown()
    ex.segment_cache.close()
    ex.fingerprint_log.close()


@pytest.mark.parametrize("name", sorted(Q1))
def test_served_four_device_engine_answers_as_numpy_does(ssb, served, name):
    from pinot_tpu.server.datatable import deserialize_results_ex
    ex, engine = served
    assert len(engine.devices) == 4 and not engine._explicit_mesh
    assert engine._mesh.axis_names == ("segments",)
    results, exceptions, _stats, trace = deserialize_results_ex(
        ex.execute("ssb_OFFLINE", q1_sql(name), trace_ctx={
            "traceId": f"four-chip-{name}", "spanId": "1", "sampled": True}))
    assert not exceptions
    got = (sum(int(r.intermediates[0]) for r in results),
           sum(int(r.intermediates[1]) for r in results))
    assert got == reference(ssb[1], name) and got[1] > 0
    dispatches = spans(trace, "DeviceDispatch")
    assert dispatches and all("outcome" not in d for d in dispatches)
    assert all(d["meshDevices"] == 4 for d in dispatches)


def spans(tree, name: str) -> list:
    out = [tree] if tree.get("operator") == name else []
    for c in tree.get("children", ()):
        out += spans(c, name)
    return out


def test_a_grouped_query_is_folded_across_the_four_devices(ssb, served):
    """ISSUE 35: the per-segment partials of a GROUP BY are folded inside
    the kernel over a global key space; on the segments mesh the fold's
    sum over the sharded segment axis is GSPMD's all-reduce. ONE result
    comes back for the eight segments, equal to numpy's."""
    from pinot_tpu.query.context import QueryContext
    from pinot_tpu.query.reduce import reduce_results
    from pinot_tpu.query.results import GroupByResult
    from pinot_tpu.server.datatable import deserialize_results_ex
    from pinot_tpu.utils.metrics import MetricsRegistry
    ex, engine = served
    sql = ("SELECT SUM(lo_extendedprice), COUNT(*), MAX(lo_extendedprice), "
           "lo_discount, lo_quantity FROM ssb WHERE lo_orderdate BETWEEN "
           "19930101 AND 19940128 GROUP BY lo_discount, lo_quantity "
           "ORDER BY lo_discount, lo_quantity LIMIT 1000 "
           "OPTION(skipCache=true)")
    results, exceptions, _stats, trace = deserialize_results_ex(
        ex.execute("ssb_OFFLINE", sql, trace_ctx={
            "traceId": "four-chip-grouped", "spanId": "1", "sampled": True}))
    assert not exceptions
    assert len(results) == 1 and isinstance(results[0], GroupByResult)
    assert results[0].stats.num_segments_processed == len(DOCS)
    span, = spans(trace, "DeviceDispatch")
    assert "outcome" not in span and span["meshDevices"] == 4
    assert span["groupFold"] == "device" and span["groupKeySpace"] == 11 * 50
    want = {}
    for c in ssb[1]:
        keep = (c["lo_orderdate"] >= 19930101) & (c["lo_orderdate"] <= 19940128)
        for d, q, p in zip(c["lo_discount"][keep], c["lo_quantity"][keep],
                           c["lo_extendedprice"][keep]):
            t = want.setdefault((int(d), int(q)), [0, 0, 0])
            t[0] += int(p)
            t[1] += 1
            t[2] = max(t[2], int(p))
    broker = MetricsRegistry("broker")
    rows = reduce_results(QueryContext.from_sql(sql), results, broker
                          ).result_table.rows
    # ISSUE 38: the broker finishes the one folded result as columns
    assert broker.meter("broker_reduce", labels={"path": "columns"}) == 1
    got = [((int(r[3]), int(r[4])), [int(r[0]), int(r[1]), int(r[2])])
           for r in rows]
    assert got == sorted(want.items()) and len(got) > 500
    assert results[0].stats.num_docs_scanned == sum(t[1] for t in want.values())


# -- (b) budgets per chip ----------------------------------------------------
KNOBS = {"pinot.server.hbm.cache.bytes": 1_000_000,
         "pinot.server.hbm.resident.bytes": 600_000,
         "pinot.server.host.row.cache.bytes": 5_000_000}


@pytest.mark.parametrize("n", [1, 2, 4, 8])
def test_hbm_pools_are_the_knob_times_the_devices(n):
    eng = implicit_engine(n, **KNOBS)
    assert eng.stager.cache_budget_bytes == n * 1_000_000
    assert eng.residency.budget_bytes == n * 600_000
    assert eng.residency.device_budget_bytes == 600_000
    # host memory is the process's, whatever chips it holds
    assert eng.stager.host_budget_bytes == 5_000_000


def test_hbm_environment_names_are_per_chip_too(monkeypatch):
    monkeypatch.setenv("PINOT_TPU_HBM_CACHE_BYTES", "4096")
    monkeypatch.setenv("PINOT_TPU_HBM_RESIDENT_BYTES", "2048")
    assert implicit_engine(1).stager.cache_budget_bytes == 4096
    eng = implicit_engine(4)
    assert (eng.stager.cache_budget_bytes, eng.residency.budget_bytes,
            eng.residency.device_budget_bytes) == (4 * 4096, 4 * 2048, 2048)


def test_an_explicit_mesh_counts_every_device_it_holds():
    eng = TpuOperatorExecutor(mesh=make_mesh(jax.devices()[:8], doc_axis=2),
                              config=PinotConfiguration(overrides=KNOBS))
    assert eng.stager.cache_budget_bytes == 8 * 1_000_000
    assert eng.residency.device_budget_bytes == 600_000


def test_a_chip_over_its_own_share_evicts_though_the_pool_has_room(ssb):
    """Four chips, two segments a chip; an int32 row is 2,048..16,384 B.
    A knob of 40,000 B a chip holds any chip's rows of the query's four
    columns but not a second query's on top: the chip evicts its own
    oldest rows while the pool (160,000 B) is never full."""
    segs, _cols = ssb
    eng = implicit_engine(4, **{"pinot.server.hbm.resident.bytes": 40_000,
                                "pinot.server.hbm.admission.enabled": False})
    device = QueryExecutor(segs, use_tpu=True, engine=eng)
    assert not device.execute(q1_sql("q1_1")).exceptions
    first = eng.residency.evicted
    resp = device.execute(
        "SELECT SUM(lo_quantity * lo_extendedprice), MAX(lo_orderdate) "
        "FROM ssb WHERE lo_discount > 2 OPTION(skipCache=true)")
    assert not resp.exceptions
    assert eng.residency.evicted > first
    assert eng.residency.bytes < eng.residency.budget_bytes
    assert max(eng.residency.bytes_by_device().values()) <= 40_000


# -- (c) per-shard assembly --------------------------------------------------
def anchor_assembled(engine, bkey, entry):
    """The block as the engine made it until PR 29: every resident row
    copied to device 0, stacked there, resharded over the mesh."""
    _batch, kind, col, S, D, dtype_str = bkey
    segments = entry[0]
    rows = [engine.residency.get(seg, kind, col, dtype_str)
            for seg in segments]
    assert all(r is not None for r in rows)
    rows = [jax.device_put(r, engine.devices[0]) for r in rows]
    block = kernels.compiled_row_assembler(
        S, D, tuple(int(r.shape[0]) for r in rows), dtype_str)(tuple(rows))
    if engine._mesh is None:
        return block
    spec = P("segments", "docs") if engine._doc_axis > 1 \
        else P("segments", None)
    return jax.device_put(block, NamedSharding(engine._mesh, spec))


def label(device) -> str:
    return f"{device.platform}:{device.id}"


def check_blocks(engine) -> int:
    """Every cached block equals the anchor-assembled one bit for bit, on
    the same sharding, each shard where the anchor's reshard put it;
    returns how many were checked."""
    checked = 0
    for bkey, entry in list(engine.stager._block_cache.items()):
        if bkey[1] in ("vmask", "vector", "startree"):
            continue  # pseudo-columns: their rows go by other names
        want = anchor_assembled(engine, bkey, entry)
        got = entry[1]
        assert got.shape == want.shape and got.dtype == want.dtype
        assert np.array_equal(np.asarray(got), np.asarray(want))
        if engine._mesh is not None:
            assert got.sharding.is_equivalent_to(want.sharding, got.ndim)
            theirs = {s.device: s.index for s in want.addressable_shards}
            for shard in got.addressable_shards:
                assert theirs[shard.device] == shard.index
        checked += 1
    return checked


@pytest.mark.parametrize("n,doc_axis", [(1, 1), (2, 1), (4, 1), (8, 1),
                                        (8, 2)])
def test_block_is_bit_equal_to_the_anchor_assembled_one(ssb, n, doc_axis):
    segs, cols = ssb
    if doc_axis > 1:  # the explicit (4, 2) mesh
        eng = TpuOperatorExecutor(
            mesh=make_mesh(jax.devices()[:n], doc_axis=doc_axis))
    else:
        eng = implicit_engine(n)
    resp = QueryExecutor(segs, use_tpu=True, engine=eng).execute(
        q1_sql("q1_1"))
    assert not resp.exceptions
    assert tuple(int(v) for v in resp.rows[0]) == reference(cols, "q1_1")
    assert check_blocks(eng) >= 4  # the query's columns and the doc mask
    if n == 1:
        assert eng._mesh is None
        return
    # every resident row lives on its slab's device: slot i of S belongs
    # to segments-shard i // (S / shards)
    shards = eng._seg_axis
    for bkey, entry in eng.stager._block_cache.items():
        S = bkey[3]
        assert S % shards == 0
        for slot, seg in enumerate(entry[0]):
            home = eng.stager._slot_device(slot, S)
            assert home is eng.stager._shards[slot // (S // shards)][0]
            key = eng.residency._key(seg, bkey[1], bkey[2], bkey[5])
            held = eng.residency._entries.get(key)
            if held is not None:
                assert held[3] == label(home) \
                    and held[1].devices() == {home}
    assert eng.stager.cross_chip_bytes == 0
    assert not eng._metrics.meter("hbm_cross_chip_bytes")


def test_a_recomposed_batch_moves_only_the_rows_that_changed_chip(ssb):
    """Eight segments over four chips put segment i on chip i // 2. The
    batch [4, 5, 6, 7] wants its slot k on chip k: segments 4, 5 and 6
    are copied chip to chip (2 -> 0, 2 -> 1, 3 -> 2), segment 7 stays."""
    segs, cols = ssb
    eng = implicit_engine(4)
    QueryExecutor(segs, use_tpu=True, engine=eng).execute(q1_sql("q1_1"))
    assert eng.stager.cross_chip_bytes == 0
    uploaded = eng._metrics.meter("hbm_transfer_bytes")
    tail = segs[4:]
    resp = QueryExecutor(tail, use_tpu=True, engine=eng).execute(
        q1_sql("q1_1"))
    assert tuple(int(v) for v in resp.rows[0]) == reference(cols[4:], "q1_1")
    moved = 0
    tail_ids = tuple(id(s) for s in tail)
    blocks = [(k, e) for k, e in eng.stager._block_cache.items()
              if tuple(id(s) for s in e[0]) == tail_ids]
    assert len(blocks) >= 4
    for bkey, entry in blocks:
        for slot, seg in enumerate(entry[0]):
            held = eng.residency._entries[
                eng.residency._key(seg, bkey[1], bkey[2], bkey[5])]
            if held[3] != label(eng.stager._slot_device(slot, bkey[3])):
                assert slot < 3
                moved += held[1].nbytes
    assert moved > 0 and eng.stager.cross_chip_bytes == moved
    assert eng._metrics.meter("hbm_cross_chip_bytes") == moved
    # chip to chip, never the host link: only parameters were uploaded
    assert eng._metrics.meter("hbm_transfer_bytes") - uploaded < 4096
    assert check_blocks(eng) >= 8


def test_no_chip_holds_more_than_its_slab(ssb):
    """The slabs a block is made from are its addressable shards: each
    chip's shard is [S / chips, D], never the whole block."""
    segs, _cols = ssb
    eng = implicit_engine(4)
    QueryExecutor(segs, use_tpu=True, engine=eng).execute(q1_sql("q1_2"))
    for bkey, entry in eng.stager._block_cache.items():
        S, D = bkey[3], bkey[4]
        shards = entry[1].addressable_shards
        assert sorted(label(s.device) for s in shards) == \
            sorted(label(d) for d in eng.devices)
        assert all(s.data.shape == (S // 4, D) for s in shards)


# -- (d) what a traced dispatch says of the chips ----------------------------
def traced_dispatches(segs, engine, name: str) -> list:
    resp = QueryExecutor(segs, use_tpu=True, engine=engine).execute(
        "SET trace = true; " + q1_sql(name))
    assert not resp.exceptions, resp.exceptions
    found = [d for d in spans(resp.trace, "DeviceDispatch")
             if "outcome" not in d]
    assert found
    return found


@pytest.mark.parametrize("n", [2, 4])
def test_a_mesh_engines_traced_dispatch_names_its_chips(ssb, n):
    eng = implicit_engine(n)
    for d in traced_dispatches(ssb[0], eng, "q1_3"):
        assert d["meshDevices"] == n and d["crossChipBytes"] == 0
        # what each chip holds is /metrics' (hbm_*_bytes{device=}), not
        # the span's: a traced dispatch asks no chip for its memory
        assert not {"chipPeakBytes", "chipBytesInUse"} & set(d)
        # read with the engine lock released: the named phases still hold
        assert d["lockWaitMs"] >= 0 and d["stagingMs"] > 0


def test_a_one_device_engines_dispatch_says_nothing_of_chips(ssb):
    for d in traced_dispatches(ssb[0], implicit_engine(1), "q1_3"):
        assert not {"meshDevices", "crossChipBytes"} & set(d)


def test_untraced_queries_set_no_chip_attrs(ssb, monkeypatch):
    eng = implicit_engine(4)
    monkeypatch.setattr(eng, "_chip_attrs", lambda dsp: pytest.fail(
        "an untraced query set a traced dispatch's chip attributes"))
    resp = QueryExecutor(ssb[0], use_tpu=True, engine=eng).execute(
        q1_sql("q1_1"))
    assert not resp.exceptions
