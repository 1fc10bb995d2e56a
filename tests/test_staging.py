"""One staging path (ISSUE 32): every [S, W] block family goes through
`BlockStager.stage_block_locked` (ops/staging.py).

One parametrised test, block family x case:

  * miss_then_hit   — the first query uploads, the repeat is a block hit
                      and ships no column byte
  * recomposed      — a batch that gains a segment uploads (and builds
                      on the host) only the rows never seen
  * invalidate      — `invalidate_segment(name, keep=)` empties every
                      tier of the name, sparing `keep`
  * drop_caches     — `drop_caches(host=)` empties the tiers and zeroes
                      the byte counters
  * zero_budget     — a resident budget of 0 still serves, through the
                      same path, and retains nothing
  * superseded      — (`__valid__` only) a moved mask stamp leaves no
                      block, resident row or host row of the old stamp
"""
import json

import numpy as np
import pytest

from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              StarTreeIndexConfig, TableConfig, TableType)
from pinot_tpu.ops import residency as residency_mod
from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.ops.staging import _entry_nbytes
from pinot_tpu.query.executor import QueryExecutor
from pinot_tpu.segment.bitmap import Bitmap
from pinot_tpu.segment.creator import SegmentCreator
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.utils.config import PinotConfiguration
from tests.queries.harness import assert_responses_equal

N_SEG, N_DOCS, DIM = 3, 1500, 8


def _build(tmp, tc, schema, columns_of):
    out = []
    for i in range(N_SEG):
        d = str(tmp / f"{tc.name}_{i}")
        SegmentCreator(tc, schema).build(
            columns_of(np.random.default_rng(91 + i)), d,
            f"{tc.name}_{i}")
        out.append(load_segment(d))
    return out


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    """family -> (segments, sql, the block kind that names the family)."""
    tmp = tmp_path_factory.mktemp("staging")
    schema = Schema("t", [
        FieldSpec("a", DataType.INT, FieldType.DIMENSION),
        FieldSpec("b", DataType.INT, FieldType.DIMENSION),
        FieldSpec("c", DataType.INT, FieldType.DIMENSION),
        FieldSpec("m", DataType.INT, FieldType.METRIC)])
    tc = TableConfig("t", TableType.OFFLINE)
    tc.indexing.no_dictionary_columns = ["m"]
    # a, b, c ~ 800 distinct values each: the dense key space (~5e8) is
    # far over MAX_DEVICE_GROUPS, so GROUP BY a, b, c compacts its keys
    plain = _build(tmp, tc, schema, lambda rng: {
        "a": rng.integers(0, 1000, N_DOCS).astype(np.int32),
        "b": rng.integers(0, 1000, N_DOCS).astype(np.int32),
        "c": rng.integers(0, 1000, N_DOCS).astype(np.int32),
        "m": rng.integers(0, 1000, N_DOCS).astype(np.int32)})
    # the same files loaded again, two of three with a live bitmap
    # (segment 2 stays append-only: its mask row is constant all-ones)
    upsert = [load_segment(str(tmp / f"t_{i}")) for i in range(N_SEG)]
    for s in upsert[:2]:
        bm = Bitmap.all_set(s.num_docs)
        for doc in range(0, s.num_docs, 3):
            bm.clear(doc)
        s.valid_doc_ids = bm

    st_schema = Schema("st", [
        FieldSpec("country", DataType.STRING),
        FieldSpec("browser", DataType.STRING),
        FieldSpec("impressions", DataType.LONG, FieldType.METRIC)])
    st_tc = TableConfig("st", TableType.OFFLINE)
    st_tc.indexing.star_tree_configs = [StarTreeIndexConfig(
        dimensions_split_order=["country", "browser"],
        function_column_pairs=["SUM__impressions"], max_leaf_records=10)]
    tree = _build(tmp, st_tc, st_schema, lambda rng: {
        "country": [f"c{v}" for v in rng.integers(0, 12, N_DOCS)],
        "browser": [f"b{v}" for v in rng.integers(0, 5, N_DOCS)],
        "impressions": rng.integers(0, 1000, N_DOCS).astype(np.int64)})

    vec_schema = Schema("emb", [
        FieldSpec("id", DataType.INT, FieldType.DIMENSION),
        FieldSpec("vec", DataType.STRING, FieldType.DIMENSION)])
    vec_tc = TableConfig(name="emb")
    vec_tc.indexing.vector_index_columns = ["vec"]
    vec = _build(tmp, vec_tc, vec_schema, lambda rng: {
        "id": np.arange(200),
        "vec": np.array([json.dumps([float(x) for x in r]) for r in
                         rng.normal(size=(200, DIM)).astype(np.float32)],
                        object)})
    qv = json.dumps([0.25] * DIM)
    return {
        "ids": (plain, "SELECT COUNT(*) FROM t WHERE a < 500", "ids2"),
        "val": (plain, "SELECT SUM(m) FROM t WHERE m > 10", "val"),
        "vmask": (upsert, "SELECT COUNT(*) FROM t WHERE a < 500", "vmask"),
        "gkey": (plain, "SELECT a, b, c, SUM(m) FROM t GROUP BY a, b, c "
                        "ORDER BY a, b, c LIMIT 100000", "gkey"),
        "startree": (tree, "SELECT SUM(impressions) FROM st "
                           "WHERE country = 'c3'", "startree"),
        "vector": (vec, "SELECT id FROM emb WHERE "
                        f"vector_similarity(vec, '{qv}', 5) LIMIT 100",
                   "vector"),
    }


def _engine(**overrides):
    return TpuOperatorExecutor(
        config=PinotConfiguration(overrides=overrides),
        metrics_labels={"staging_test": "t"})


def _meter(eng, name):
    return eng._metrics.meter(name, labels=eng._labels)


def _serve(eng, segs, sql, kind):
    """Run on the device, hold the answer to the host executor's, and
    require the family's block among those staged."""
    got = QueryExecutor(segs, use_tpu=True, engine=eng).execute(sql)
    want = QueryExecutor(segs, use_tpu=False).execute(sql)
    assert not got.exceptions and not want.exceptions, got.exceptions
    assert_responses_equal(want, got, sql)
    assert kind in {k[1] for k in eng.stager._block_cache}, \
        f"no {kind} block staged: the query left the device path"


def _consistent(st):
    """The byte counters equal what the tiers hold."""
    assert st._cache_bytes == sum(st._block_bytes.values())
    assert set(st._block_bytes) == set(st._block_cache)
    assert st._host_bytes == sum(
        _entry_nbytes(v[1]) for v in st._host_rows.values())


def _miss_then_hit(eng, segs, sql, kind):
    b0 = residency_mod.column_transfer_bytes()
    _serve(eng, segs, sql, kind)
    b1 = residency_mod.column_transfer_bytes()
    assert b1 > b0
    hits, misses = _meter(eng, "hbm_block_hit"), _meter(eng, "hbm_block_miss")
    _serve(eng, segs, sql, kind)
    assert residency_mod.column_transfer_bytes() == b1
    assert _meter(eng, "hbm_block_hit") > hits
    assert _meter(eng, "hbm_block_miss") == misses
    _consistent(eng.stager)


def _recomposed(eng, segs, sql, kind):
    _serve(eng, segs[:-1], sql, kind)
    admitted = eng.residency.admitted
    families, rest = divmod(admitted, len(segs) - 1)
    assert families > 0 and rest == 0
    built = _meter(eng, "host_row_miss")
    b1 = residency_mod.column_transfer_bytes()
    _serve(eng, segs, sql, kind)
    # one new segment: one upload and one host build a row family
    assert eng.residency.admitted - admitted == families
    assert _meter(eng, "host_row_miss") - built == families
    # (a star-tree's per-batch selection mask counts as column bytes too)
    new_rows = eng.residency.resident_bytes_by_segment()[segs[-1].name]
    assert new_rows <= residency_mod.column_transfer_bytes() - b1 \
        < eng.residency.bytes
    _consistent(eng.stager)


def _invalidate(eng, segs, sql, kind):
    st, name = eng.stager, segs[0].name
    _serve(eng, segs, sql, kind)
    blocks = len(st._block_cache)
    eng.invalidate_segment(name, keep=segs[0])  # the live object: spared
    assert len(st._block_cache) == blocks
    assert eng.residency.resident_for(name) > 0
    eng.invalidate_segment(name)
    assert not st._block_cache and st._cache_bytes == 0
    assert not st._batch_blocks and not st._params_cache
    assert eng.residency.resident_for(name) == 0
    assert not any(v[0].name == name for v in st._host_rows.values())
    assert any(v[0].name == segs[1].name for v in st._host_rows.values())
    _consistent(st)
    _serve(eng, segs, sql, kind)


def _drop_caches(eng, segs, sql, kind):
    st = eng.stager
    _serve(eng, segs, sql, kind)
    eng.stager.drop_caches(host=False)
    assert not st._block_cache and not st._block_bytes
    assert st._cache_bytes == 0 and not st._batch_blocks
    assert not st._params_cache
    assert len(eng.residency) == 0 and eng.residency.bytes == 0
    assert st._host_rows and st._host_bytes > 0
    eng.stager.drop_caches(host=True)
    assert not st._host_rows and st._host_bytes == 0
    b0 = residency_mod.column_transfer_bytes()
    _serve(eng, segs, sql, kind)
    assert residency_mod.column_transfer_bytes() > b0
    _consistent(st)


def _zero_budget(eng, segs, sql, kind):
    assert not eng.residency.enabled
    _miss_then_hit(eng, segs, sql, kind)
    assert len(eng.residency) == 0 and eng.residency.admitted == 0
    # nothing was retained: another batch uploads every row again
    b0 = residency_mod.column_transfer_bytes()
    _serve(eng, segs[:-1], sql, kind)
    assert residency_mod.column_transfer_bytes() > b0
    assert len(eng.residency) == 0 and eng.residency.bytes == 0


def _superseded(eng, segs, sql, kind):
    st = eng.stager
    _serve(eng, segs, sql, kind)
    bitmap = segs[0].valid_doc_ids
    old = bitmap.version
    bitmap.clear(next(d for d in range(segs[0].num_docs)
                      if bitmap.contains(d)))
    assert bitmap.version != old
    _serve(eng, segs, sql, kind)
    stamps = [k[5] for k in st._block_cache if k[1] == "vmask"]
    assert stamps == [(bitmap.version, segs[1].valid_doc_ids.version, -1)]
    want = {(s.name, f"vmask:{v}") for s, v in zip(segs, stamps[0])}
    assert {(k[1], k[2]) for k in eng.residency._entries
            if k[2].startswith("vmask:")} == want
    assert {(v[0].name, k[1]) for k, v in st._host_rows.items()
            if k[1].startswith("vmask:")} == want
    _consistent(st)


CASES = {f.__name__[1:]: f for f in (
    _miss_then_hit, _recomposed, _invalidate, _drop_caches, _zero_budget,
    _superseded)}
FAMILIES = ("ids", "val", "vmask", "gkey", "startree", "vector")


@pytest.mark.parametrize("family,case", [
    (f, c) for f in FAMILIES for c in CASES
    if c != "superseded" or f == "vmask"])
def test_block_family_through_the_one_path(tables, family, case):
    budget = {"pinot.server.hbm.resident.bytes": 0} \
        if case == "zero_budget" else {}
    CASES[case](_engine(**budget), *tables[family])
