"""Real SQL through the engine on a multi-device (segments x docs) mesh.

The conftest forces an 8-device virtual CPU platform; the engine here gets
an explicit 4x2 mesh so column blocks shard over BOTH axes and the kernel
runs under shard_map with psum/pmin/pmax collectives over `docs`
(SURVEY §2.6 rows 6-7). Every query asserts parity against the host
(numpy) executor — the BaseQueriesTest pattern, multichip edition.
"""
import numpy as np
import pytest

import jax

from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.parallel.mesh import make_mesh
from pinot_tpu.query.executor import QueryExecutor
from tests.queries.harness import (
    build_segments, synthetic_columns, synthetic_schema,
    synthetic_table_config)

NUM_DOCS = 700  # deliberately not a power of two: padding must mask right


@pytest.fixture(scope="module")
def mesh_harness(tmp_path_factory):
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    tmp = tmp_path_factory.mktemp("multichip")
    data = [synthetic_columns(NUM_DOCS, seed=31 + i) for i in range(6)]
    segs = build_segments(tmp, synthetic_schema(), synthetic_table_config(),
                          data)
    mesh = make_mesh(jax.devices()[:8], doc_axis=2)
    engine = TpuOperatorExecutor(mesh=mesh)
    device = QueryExecutor(segs, use_tpu=True, engine=engine)
    host = QueryExecutor(segs, use_tpu=False)
    return device, host, engine


def _parity(device, host, sql):
    dr = device.execute(sql)
    hr = host.execute(sql)
    assert not dr.exceptions and not hr.exceptions
    assert len(dr.rows) == len(hr.rows), (dr.rows, hr.rows)
    for a, b in zip(dr.rows, hr.rows):
        for x, y in zip(a, b):
            if isinstance(x, float) or isinstance(y, float):
                assert abs(float(x) - float(y)) <= \
                    1e-5 * max(1.0, abs(float(y))), (dr.rows, hr.rows)
            else:
                assert x == y, (dr.rows, hr.rows)
    return dr


class TestMultichipSql:
    def test_sum_count_filter(self, mesh_harness):
        device, host, engine = mesh_harness
        r = _parity(device, host,
                    "SELECT SUM(intCol), COUNT(*) FROM testTable "
                    "WHERE intCol BETWEEN 100 AND 700")
        assert r.rows

    def test_group_by(self, mesh_harness):
        device, host, _ = mesh_harness
        _parity(device, host,
                "SELECT groupCol, SUM(floatCol), COUNT(*) "
                "FROM testTable GROUP BY groupCol ORDER BY groupCol LIMIT 50")

    def test_min_max(self, mesh_harness):
        """min/max combine over the docs axis via pmin/pmax, not psum."""
        device, host, _ = mesh_harness
        _parity(device, host,
                "SELECT MIN(intCol), MAX(intCol), AVG(intCol) "
                "FROM testTable WHERE intCol > 300")

    def test_in_filter_lut(self, mesh_harness):
        device, host, _ = mesh_harness
        _parity(device, host,
                "SELECT COUNT(*), SUM(intCol) FROM testTable "
                "WHERE stringCol IN ('s1', 's3', 's7')")

    def test_expression_aggregate(self, mesh_harness):
        device, host, _ = mesh_harness
        _parity(device, host,
                "SELECT SUM(intCol * floatCol) FROM testTable "
                "WHERE intCol < 900 AND rawIntCol > 10")

    def test_engine_actually_offloaded(self, mesh_harness):
        """The queries above must run the DEVICE path (no silent host
        fallback): the engine's block cache fills with sharded arrays."""
        device, host, engine = mesh_harness
        device.execute("SELECT SUM(doubleCol) FROM testTable")
        assert engine.stager._block_cache, "device path never staged a block"
        from jax.sharding import NamedSharding
        any_block = next(iter(engine.stager._block_cache.values()))[1]
        sh = any_block.sharding
        assert isinstance(sh, NamedSharding)
        assert dict(zip(sh.mesh.axis_names, sh.mesh.devices.shape)) == \
            {"segments": 4, "docs": 2}
        # blocks shard over BOTH axes: 8 addressable shards
        assert len(any_block.addressable_shards) == 8
        d0 = any_block.addressable_shards[0].data.shape
        assert d0[0] * 4 == any_block.shape[0]
        assert d0[1] * 2 == any_block.shape[1]
