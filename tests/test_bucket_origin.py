"""The fused time bucket's per-segment origin: the kernel's bucket digit
is as wide as the most buckets one segment of the batch spans, each
segment keys its rows from its own first bucket of the window
(`timeseries_device.origin`), and the fold shifts each segment's digit
back onto the window's axis of `count_pad` buckets (`kernels._fold_shard`;
past the remap cap the host does, `_assemble_group`).

Four segments of 2.5 hours each, end to end, so that every other
boundary falls inside an hour, 400 hosts (a key space past 1,024, the
factored one-hot pass), against the host executor, on one device and on
a four-device segments mesh:

  * windows that split an hour at a segment boundary, start inside a
    segment, lie inside one segment, and cover every segment, grouped by
    `DATETRUNC('HOUR', ts)` and by the time-series leaf's
    `floor((ts - start) / step)`; the span's `bucketWidth` is what the
    segments span and `groupKeySpace` that width times the hosts
  * segments that each reach across the window keep width == count_pad
  * past the remap cap the host decodes each segment's bucket from its
    own origin
  * a window sliding over the staggered segments traces one kernel
  * the fold itself, against numpy, under a four-device shard_map and
    without one
  * the bucket's quotient, made without an integer divide, is
    floor(delta / step) exactly wherever the gate can pass
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              TableConfig, TableType)
from pinot_tpu.ops import kernels, timeseries_device
from pinot_tpu.ops.plan_ir import DevicePlan
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.executor import QueryExecutor
from pinot_tpu.ops.engine import TpuOperatorExecutor
from tests.queries.harness import build_segments

T0 = 1_451_606_400_000  # 2016-01-01T00:00Z, on the hour
MIN, HOUR = 60_000, 3_600_000
HOSTS = np.array([f"host_{i:03d}" for i in range(400)])
#: docs a segment: past the factored pass's chunk, none a power of two
DOCS = 8200
STEP = 20 * MIN  # the leaf's bucket


def _columns(seed: int, first: int, last: int) -> dict:
    """A segment's rows: every host, instants in [first, last] with both
    ends present, small INT values (their sums exact in f32)."""
    rng = np.random.default_rng([46, seed])
    ts = rng.integers(first, last + 1, DOCS).astype(np.int64)
    ts[:2] = first, last
    return {"ts": np.sort(ts),
            "host": HOSTS[np.arange(DOCS) % len(HOSTS)],
            "value": rng.integers(0, 100, DOCS).astype(np.int32)}


def _table(tmp, spans) -> list:
    schema = Schema("t", [
        FieldSpec("ts", DataType.LONG, FieldType.DIMENSION),
        FieldSpec("host", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("value", DataType.INT, FieldType.METRIC)])
    tc = TableConfig("t", TableType.OFFLINE)
    tc.indexing.no_dictionary_columns = ["ts", "value"]
    return build_segments(tmp, schema, tc, [
        _columns(i, first, last) for i, (first, last) in enumerate(spans)])


@pytest.fixture(scope="module")
def staggered(tmp_path_factory):
    """Segment i holds [T0 + 2.5 i hours, T0 + 2.5 (i + 1) hours): hours
    0-2, 2-4, 5-7, 7-9; the boundaries at 02:30 and 07:30 split an
    hour."""
    return _table(tmp_path_factory.mktemp("staggered"), [
        (T0 + i * 150 * MIN, T0 + (i + 1) * 150 * MIN - 1) for i in range(4)])


@pytest.fixture(scope="module")
def wide(tmp_path_factory):
    """Two segments that each hold the same ten hours."""
    return _table(tmp_path_factory.mktemp("wide"),
                  [(T0, T0 + 10 * HOUR - 1)] * 2)


@pytest.fixture(scope="module", params=[1, 4], ids=["one", "mesh4"])
def engine(request):
    """An engine over one device, or over four: the implicit segments
    mesh, where the pass and the fold run a shard under shard_map."""
    if len(jax.devices()) < request.param:
        pytest.skip("needs 4 virtual devices")
    return TpuOperatorExecutor(devices=jax.devices()[:request.param],
                               metrics_labels={"t": f"origin{request.param}"})


def _key(kind: str, lo: int) -> str:
    if kind == "hour":
        return "DATETRUNC('HOUR', ts)"
    return f"floor((ts - {lo - 7 * MIN}) / {STEP})"


def _grid(kind: str, lo: int):
    """(start, step) of the window's bucket grid."""
    if kind == "hour":
        return lo - lo % HOUR, HOUR
    return lo - 7 * MIN, STEP


def _sql(kind: str, lo: int, hi: int) -> str:
    by = f"{_key(kind, lo)}, host"
    return (f"SELECT {by}, COUNT(*), SUM(value) FROM t "
            f"WHERE ts >= {lo} AND ts < {hi} GROUP BY {by} "
            f"LIMIT 100000 OPTION(skipCache=true)")


def _dispatches(tree):
    out = [tree] if tree.get("operator") == "DeviceDispatch" else []
    for c in tree.get("children", ()):
        out += _dispatches(c)
    return out


def _rows(resp) -> list:
    return sorted((r[0], r[1], int(r[2]), float(r[3]))
                  for r in resp.result_table.rows)


def _ask(segs, engine, sql):
    """(device rows, host rows, the DeviceDispatch span), in f32 as one
    chip runs."""
    with jax.enable_x64(False):
        got = QueryExecutor(segs, use_tpu=True, engine=engine).execute(
            "SET trace = true; " + sql)
    want = QueryExecutor(segs, use_tpu=False).execute(sql)
    assert not got.exceptions and not want.exceptions
    span, = _dispatches(got.trace)
    assert "outcome" not in span, "fell back to the host"
    return _rows(got), _rows(want), span


def _expected(segs, kind, lo, hi):
    """(count, width, count_pad) the window and the segments it keeps
    give: the width is the most buckets of the grid one kept segment's
    whole [min, max] spans, at most count_pad."""
    start, step = _grid(kind, lo)
    kept = [s.metadata.columns["ts"] for s in segs]
    kept = [m for m in kept if m.max_value >= lo and m.min_value < hi]
    count = (hi - 1 - start) // step + 1
    pad = 8
    while pad < count:
        pad *= 2
    span = max((int(m.max_value) - start) // step
               - (int(m.min_value) - start) // step + 1 for m in kept)
    return count, min(span, pad), pad


WINDOWS = {
    # hour 2 lies in segments 0 and 1
    "splits_an_hour": (T0 + HOUR, T0 + 4 * HOUR),
    # starts 47 minutes into segment 1: its origin is the window's first
    # bucket, segment 2's the third, segment 3's the fifth
    "starts_mid_segment": (T0 + 3 * HOUR + 17 * MIN + 123, T0 + 8 * HOUR),
    "inside_one_segment": (T0 + 5 * HOUR + 10 * MIN, T0 + 6 * HOUR + 50 * MIN),
    "covers_every_segment": (T0, T0 + 10 * HOUR),
}


@pytest.mark.parametrize("kind", ["hour", "floor"])
@pytest.mark.parametrize("window", list(WINDOWS))
def test_device_answers_are_the_host_s(staggered, engine, window, kind):
    lo, hi = WINDOWS[window]
    got, want, span = _ask(staggered, engine, _sql(kind, lo, hi))
    assert got == want and len(got) > len(HOSTS)
    count, width, pad = _expected(staggered, kind, lo, hi)
    assert (span["groupFold"], span["groupPath"]) == ("device", "onehot2")
    assert (span["bucketCount"], span["bucketWidth"], span["bucketPad"]) \
        == (count, width, pad)
    assert span["groupKeySpace"] == width * len(HOSTS)
    assert span["groupsPresent"] == len(want)


def test_hour_widths_are_what_a_segment_spans(staggered):
    """2.5 hours starting on the hour or on the half hour touch 3
    hours; 20-minute buckets 8 or 9, by where the grid falls."""
    for lo, hi in WINDOWS.values():
        assert _expected(staggered, "hour", lo, hi)[1] == 3
        assert _expected(staggered, "floor", lo, hi)[1] in (8, 9)


@pytest.mark.parametrize("kind", ["hour", "floor"])
def test_segments_that_span_the_window_keep_the_window_s_width(
        wide, engine, kind):
    lo, hi = T0 + 2 * HOUR, T0 + 7 * HOUR
    sql = _sql(kind, lo, hi)
    got, want, span = _ask(wide, engine, sql)
    assert got == want
    count, width, pad = _expected(wide, kind, lo, hi)
    assert width == pad == span["bucketWidth"] == span["bucketPad"]
    plan, _slots = engine._plan(wide, QueryContext.from_sql(sql))
    assert plan.tbucket == ("ts", pad, pad)
    assert plan.num_groups == span["groupKeySpace"] == pad * len(HOSTS)


@pytest.mark.parametrize("kind", ["hour", "floor"])
def test_past_the_remap_cap_the_host_decodes_each_segment_s_origin(
        staggered, engine, kind):
    lo, hi = WINDOWS["starts_mid_segment"]
    engine._gmap_cache.clear()
    engine.GMAP_MAX_BYTES = 16  # under any remap of 400 hosts
    try:
        got, want, span = _ask(staggered, engine, _sql(kind, lo, hi))
    finally:
        del engine.GMAP_MAX_BYTES
        engine._gmap_cache.clear()
    assert span["groupFold"] == "host"
    assert span["bucketWidth"] < span["bucketPad"]
    assert got == want


@pytest.mark.parametrize("kind", ["hour", "floor"])
def test_a_sliding_window_traces_one_kernel(staggered, engine, kind):
    """Three hours sliding an hour at a time: each window keeps two of
    the staggered segments, a different two as it goes, and their
    origins move; only params change."""
    windows = [(T0 + j * HOUR, T0 + (j + 3) * HOUR) for j in range(1, 7)]
    _ask(staggered, engine, _sql(kind, *windows[0]))  # warm the shapes
    traced = kernels.trace_count()
    sets = set()
    for lo, hi in windows[1:]:
        got, want, span = _ask(staggered, engine, _sql(kind, lo, hi))
        assert got == want
        assert span["bucketWidth"] == _expected(staggered, kind, lo, hi)[1]
        sets.add(tuple(i for i, s in enumerate(staggered)
                       if s.metadata.columns["ts"].max_value >= lo
                       and s.metadata.columns["ts"].min_value < hi))
    assert kernels.trace_count() == traced
    assert len(sets) == 3 and {len(s) for s in sets} == {2}


# -- the fold alone ------------------------------------------------------------
W, PAD, CARD, S = 3, 8, 6, 8


def _fold_inputs(seed: int):
    """Per-segment partials over `W` local buckets x `CARD` local ids,
    each segment's remap of the union's 8 ids, and its origin offset
    (the last two lie past where a whole width fits the window)."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, 4, (S, CARD * W)).astype(np.float32)
    sums = (counts * rng.integers(1, 50, (S, CARD * W))).astype(np.float32)
    ginv = np.full((S, 8), -1, np.int32)
    for s in range(S):
        union = np.sort(rng.choice(8, CARD, replace=False))
        ginv[s, union] = rng.permutation(CARD)
    off = np.array([0, 1, 2, 5, 3, 0, 6, 7], np.int32)
    return sums, counts, ginv, off


def _fold_reference(slot, ginv, off):
    """numpy, a cell at a time: global (union id u, window bucket j)
    reads segment s's local (ginv[s, u], j - off[s])."""
    out = np.zeros((8, PAD))
    for s in range(S):
        for u in range(8):
            for j in range(PAD):
                b = j - off[s]
                if ginv[s, u] >= 0 and 0 <= b < W:
                    out[u, j] += slot[s, ginv[s, u] * W + b]
    return out.reshape(-1)


@pytest.mark.parametrize("chips", [0, 4], ids=["alone", "mesh4"])
def test_the_fold_shifts_each_segment_onto_the_window(chips):
    if len(jax.devices()) < chips:
        pytest.skip("needs 4 virtual devices")
    plan = DevicePlan(
        filter_ir=None, leaves=(), value_irs=(("col", "m"),),
        agg_ops=(("sum", 0, None), ("count", None, None)),
        group_cols=("h",), group_strides=(W,), num_groups=CARD * W,
        tbucket=("ts", W, PAD), group_fold=(8, PAD))
    sums, counts, ginv, off = _fold_inputs(46)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:chips]),
                             ("segments",)) if chips else None
    with jax.enable_x64(False):
        row = np.asarray(jax.jit(lambda a, b, g, o: kernels.fold_groups(
            plan, [("sum", a), ("count", b)], {"ginv0": g, "tb:off": o},
            mesh))(jnp.asarray(sums), jnp.asarray(counts),
                   jnp.asarray(ginv), jnp.asarray(off)))
    G = 8 * PAD
    assert row.dtype == np.int32 and row.shape == (2 * G + S,)
    table = row[:2 * G].reshape(2, G)
    assert table[1].tolist() == _fold_reference(counts, ginv, off).tolist()
    assert table[0].view(np.float32).tolist() \
        == _fold_reference(sums, ginv, off).tolist()
    # a bucket past a segment's width or before its origin is absent
    assert table[1].sum() < counts.sum()
    assert row[2 * G:].tolist() == counts.sum(axis=1).astype(int).tolist()


# -- the quotient without a divide -----------------------------------------------
def test_the_bucket_quotient_is_exact_without_an_integer_divide():
    """Every step from a millisecond to the widest window, deltas on and
    next to every multiple that matters and at random over the whole
    envelope: below 2^21 buckets the id is floor(delta / step), and the
    gate passes exactly the rows a divide would let through."""
    top = timeseries_device.MAX_WINDOW
    steps = [1, 7, 1000, MIN, STEP, HOUR, 24 * HOUR, 7 * 24 * HOUR,
             (1 << 30) + 3, top]
    rng = np.random.default_rng(46)
    ks = [0, 1, 2, 3, 1000, 1 << 16, (1 << 20) - 1, 1 << 20,
          (1 << 20) + 1, (1 << 21) - 1, 1 << 21, 1 << 22, 1 << 30]
    rows = []
    for step in steps:
        d = [k * step + e for k in ks for e in (-1, 0, 1)]
        d += rng.integers(-top, top, 4000).tolist()
        d += (rng.integers(0, 1 << 21, 2000) * step
              + rng.integers(0, step, 2000)).tolist()
        rows.append([x for x in d if -top <= x <= top][:5000])
    n = min(map(len, rows))
    delta = np.array([r[:n] for r in rows], np.int64)
    step = np.array(steps, np.int64)
    start = T0 + 12_345
    t = start + delta
    count = np.full(len(steps), 1 << 20, np.int32)
    with jax.enable_x64(False):
        b, gate = timeseries_device.compiled_bucket_ids(
            jnp.asarray((t >> 24).astype(np.int32)),
            jnp.asarray((t & 0xFFFFFF).astype(np.int32)),
            jnp.full(len(steps), start >> 24, jnp.int32),
            jnp.full(len(steps), start & 0xFFFFFF, jnp.int32),
            jnp.asarray(step.astype(np.int32)), jnp.asarray(count),
            width=1 << 22)
    b, gate = np.asarray(b), np.asarray(gate)
    q = delta // step[:, None]
    assert gate.tolist() == ((delta >= 0) & (q < count[:, None])).tolist()
    exact = (q >= 0) & (q < 1 << 21)
    assert exact.sum() > len(steps) * 1000
    assert b[exact].tolist() == q[exact].tolist()
