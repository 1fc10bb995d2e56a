"""A GROUP BY past 1,024 groups on one server holding four chips (ISSUE
37), on the virtual CPU devices the conftest forces.

The engine a server builds on a host of four has the implicit
`("segments",)` mesh: blocks staged a shard, the plain-jit kernels under
GSPMD. The factored one-hot pass (`groupPath` = `onehot2`) is a Pallas
kernel on the TPU, which GSPMD cannot split, so on a mesh it runs a shard
of the segment axis under a `shard_map`, and the fold's reductions over
segments end in all-reduces (`kernels.fold_groups`). Off the TPU the same
tiles are an XLA loop under the same `shard_map`, which is what runs
here: answers, spans and the exchange read from the compiled program.
What the chip's compiler makes of the Pallas call on a v5e:2x2 is
`tests/test_grouped_onehot2.py`'s (one file describes the topology).
"""
import numpy as np
import pytest

import jax

from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              TableConfig, TableType)
from pinot_tpu.ops import kernels
from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.query.executor import QueryExecutor
from tests.queries.harness import build_segments

RTOL = 5e-7  # tests/test_grouped_onehot2.py's
YEARS = np.arange(1992, 1999)
BRANDS = np.array([f"MFGR#{i:04d}" for i in range(260)])
REGIONS = np.array(["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"])
#: docs a segment: unequal, none a power of two, every D bucket 16,384
DOCS = (9000, 12000, 8500, 16000, 10001, 9999, 15000, 8800)
SQL = ("SELECT d_year, p_brand1, SUM(lo_revenue), COUNT(*) FROM dims "
       "WHERE s_region = 'ASIA' AND lo_revenue > 400000 "
       "GROUP BY d_year, p_brand1 ORDER BY d_year, p_brand1 LIMIT 5000 "
       "OPTION(skipCache=true)")


def dims_columns(segment: int, docs: int) -> dict:
    """A segment's seeded columns. Its dictionaries differ from its
    neighbours': it lacks a stretch of 40 brands and, every other
    segment, a year, so a group's local key differs segment to segment
    and the fold's remap has work to do."""
    rng = np.random.default_rng([37, segment])
    brands = np.delete(BRANDS, np.arange(40) + 25 * segment)
    years = YEARS[1:] if segment % 2 else YEARS
    return {"d_year": rng.choice(years, docs).astype(np.int32),
            "p_brand1": rng.choice(brands, docs),
            "s_region": rng.choice(REGIONS, docs),
            "lo_revenue": rng.integers(1, 1 << 20, docs).astype(np.int32)}


@pytest.fixture(scope="module")
def dims(tmp_path_factory):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    schema = Schema("dims", [
        FieldSpec("d_year", DataType.INT, FieldType.DIMENSION),
        FieldSpec("p_brand1", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("s_region", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("lo_revenue", DataType.INT, FieldType.METRIC)])
    tc = TableConfig("dims", TableType.OFFLINE)
    tc.indexing.no_dictionary_columns = ["lo_revenue"]
    cols = [dims_columns(i, docs) for i, docs in enumerate(DOCS)]
    return build_segments(tmp_path_factory.mktemp("dims4"), schema, tc,
                          cols), cols


def reference(cols: list) -> dict:
    """{(year, brand): (exact integer SUM, COUNT)} over every segment."""
    out = {}
    for c in cols:
        keep = (c["s_region"] == "ASIA") & (c["lo_revenue"] > 400000)
        for y, b, r in zip(c["d_year"][keep], c["p_brand1"][keep],
                           c["lo_revenue"][keep]):
            s, n = out.get((int(y), str(b)), (0, 0))
            out[(int(y), str(b))] = (s + int(r), n + 1)
    return out


def _dispatches(tree):
    out = [tree] if tree.get("operator") == "DeviceDispatch" else []
    for c in tree.get("children", ()):
        out += _dispatches(c)
    return out


def _served(segs, devices, labels):
    """(rows, the DeviceDispatch span, the engine) of SQL on an engine
    over `devices`, built as a server builds its own: no mesh given."""
    engine = TpuOperatorExecutor(devices=devices, metrics_labels=labels)
    got = QueryExecutor(segs, use_tpu=True, engine=engine).execute(
        "SET trace = true; " + SQL)
    assert not got.exceptions
    span, = _dispatches(got.trace)
    assert "outcome" not in span, "fell back to the host"
    return got.result_table.rows, span, engine


def test_four_device_group_by_equals_numpy_and_one_device(dims):
    segs, cols = dims
    want = reference(cols)
    assert len(want) > kernels.ONEHOT_MAX_GROUPS
    # the filter empties some groups of the key space
    assert len(want) < len(YEARS) * len(BRANDS)
    with jax.enable_x64(False):
        four, span, engine = _served(segs, jax.devices()[:4], {"t": "4"})
        one, span1, _e = _served(segs, jax.devices()[:1], {"t": "1"})
    assert engine._mesh is not None and engine._mesh.axis_names == (
        "segments",)
    assert span["groupPath"] == span1["groupPath"] == "onehot2"
    assert span["groupFold"] == span1["groupFold"] == "device"
    assert span["meshDevices"] == 4 and "meshDevices" not in span1
    assert span["groupKeySpace"] > kernels.ONEHOT_MAX_GROUPS
    # keys, order and COUNT exactly; SUM within the pass's tolerance
    assert [tuple(r[:2]) for r in four] == sorted(want)
    assert [tuple(r[:2]) for r in one] == sorted(want)
    for r4, r1 in zip(four, one):
        total, count = want[tuple(r4[:2])]
        assert r4[3] == r1[3] == count
        assert r4[2] == pytest.approx(total, rel=RTOL)
        assert r4[2] == pytest.approx(r1[2], rel=RTOL)


def test_the_exchange_is_read_once_from_the_compiled_program(
        dims, monkeypatch):
    """`meshExchangeBytes`: two all-reduces a launch (the sums with their
    carried errors; the counts with the segments' matched docs), no
    all-gather; read when the shapes are first staged, kept after, and
    metered a launch. A one-device engine sets neither."""
    from pinot_tpu.ops import device
    segs, _cols = dims
    labels = {"t": "exchange"}
    reads = []
    read = device.collective_bytes
    monkeypatch.setattr(device, "collective_bytes",
                        lambda hlo: reads.append(read(hlo)) or reads[-1])
    with jax.enable_x64(False):
        _rows, span, engine = _served(segs, jax.devices()[:4], labels)
        words = span["groupResultBytes"] // 4  # the folded row's slots
        # a chip hands over: the f32 sums and their carried errors, the
        # i32 counts, and the [S] matched docs
        assert span["meshExchangeBytes"] == 4 * (3 * words // 2 + len(DOCS))
        assert span["meshGatherBytes"] == 0
        got = QueryExecutor(segs, use_tpu=True, engine=engine).execute(
            "SET trace = true; " + SQL)
        again, = _dispatches(got.trace)
        assert again["meshExchangeBytes"] == span["meshExchangeBytes"]
        assert reads == [(span["meshExchangeBytes"], 0)]
        assert engine._metrics.meter(
            "mesh_exchange_bytes", labels=labels) == \
            2 * span["meshExchangeBytes"]
        _rows, span1, _e = _served(segs, jax.devices()[:1], {"t": "x1"})
    assert "meshExchangeBytes" not in span1
    assert "meshGatherBytes" not in span1
    assert len(reads) == 1
