"""f32-staging parity: strict raw-value comparisons with x64 DISABLED.

The production TPU default is jax_enable_x64=False, where raw columns stage
as float32. ADVICE r1 (high): _vrange_bounds computed the open-interval
bound with float64 nextafter, which collapses back to the literal when cast
to float32 — 'x > 5' executed as 'x >= 5'. These tests pin the fix by
running the device path under jax.enable_x64(False).
"""
import numpy as np
import pytest

import jax

from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              TableConfig, TableType)
from pinot_tpu.query.executor import QueryExecutor

from tests.queries.harness import assert_responses_equal, build_segments


@pytest.fixture(scope="module")
def segs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("f32seg")
    schema = Schema("testTable", [
        FieldSpec("rawInt", DataType.INT, FieldType.METRIC),
        FieldSpec("rawFloat", DataType.FLOAT, FieldType.METRIC),
        FieldSpec("dimCol", DataType.INT, FieldType.DIMENSION),
    ])
    tc = TableConfig("testTable", TableType.OFFLINE)
    tc.indexing.no_dictionary_columns = ["rawInt", "rawFloat"]
    rng = np.random.default_rng(7)
    n = 4096
    cols = {
        # plant many exact boundary hits so strict-vs-nonstrict differs
        "rawInt": np.where(rng.random(n) < 0.3, 5,
                           rng.integers(-50, 50, n)).astype(np.int32),
        "rawFloat": np.where(rng.random(n) < 0.3, np.float32(2.5),
                             rng.random(n).astype(np.float32) * 10),
        "dimCol": rng.integers(0, 100, n).astype(np.int32),
    }
    return build_segments(tmp, schema, tc, [cols])


STRICT_QUERIES = [
    "SELECT COUNT(*), SUM(dimCol) FROM testTable WHERE rawInt > 5",
    "SELECT COUNT(*), SUM(dimCol) FROM testTable WHERE rawInt < 5",
    "SELECT COUNT(*), SUM(dimCol) FROM testTable WHERE rawInt >= 5",
    "SELECT COUNT(*), SUM(dimCol) FROM testTable WHERE rawInt <= 5",
    "SELECT COUNT(*), SUM(dimCol) FROM testTable WHERE rawFloat > 2.5",
    "SELECT COUNT(*), SUM(dimCol) FROM testTable WHERE rawFloat < 2.5",
    "SELECT COUNT(*) FROM testTable WHERE rawFloat > 2.5 AND rawInt > 5",
]


@pytest.mark.parametrize("sql", STRICT_QUERIES)
def test_strict_bounds_f32(segs, sql):
    with jax.enable_x64(False):
        cpu = QueryExecutor(segs, use_tpu=False)
        tpu = QueryExecutor(segs, use_tpu=True)
        a, b = cpu.execute(sql), tpu.execute(sql)
        # the device path must actually have run (not fallen back) for this
        # to pin the f32 bound computation; parity alone suffices either way
        assert_responses_equal(a, b, sql)


def test_strict_gt_excludes_boundary(segs):
    """x > 5 must exclude the planted exact-5 rows under f32 staging."""
    with jax.enable_x64(False):
        tpu = QueryExecutor(segs, use_tpu=True)
        gt = tpu.execute("SELECT COUNT(*) FROM testTable WHERE rawInt > 5")
        ge = tpu.execute("SELECT COUNT(*) FROM testTable WHERE rawInt >= 5")
        n_gt = gt.result_table.rows[0][0]
        n_ge = ge.result_table.rows[0][0]
        # ~30% of 4096 rows are exactly 5
        assert n_ge - n_gt > 1000, (n_gt, n_ge)
