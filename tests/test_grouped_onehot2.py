"""Grouped SUM/COUNT above ONEHOT_MAX_GROUPS groups as a factored one-hot
matmul (ISSUE 28).

  * `kernels.group_path` is the one place that decides: its edges
  * `_onehot2_sums` through `make_kernel` against numpy: COUNT exact, SUM
    of integers and of non-integer f32 within 5e-7, several additive
    slots in one pass, MIN beside them on the scatter, rows masked out,
    padding docs, a tail past the last chunk
  * the same pass under `vmap` (the batched kernel) and inside
    `shard_map` (the sharded kernel)
  * a served GROUP BY: an INT sum takes the pass, a FLOAT sum whose
    groups hold Inf, -Inf and NaN keeps the scatter and answers as the
    host does in every group

The suite runs with x64 ON, where the device sums are f64 and keep the
scatter; what one chip runs (x64 off, f32) is entered with
`jax.enable_x64(False)`.
"""
import contextlib
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pinot_tpu.models import (DataType, FieldSpec, FieldType, Schema,
                              TableConfig, TableType)
from pinot_tpu.ops import kernels
from pinot_tpu.ops.engine import TpuOperatorExecutor
from pinot_tpu.ops.plan_ir import DeviceLeaf, DevicePlan, batch_params
from pinot_tpu.parallel import make_mesh
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.executor import QueryExecutor
from tests.queries.harness import build_segments

CH = kernels._ONEHOT2_CHUNK
RTOL = 5e-7


# -- the path function's edges -------------------------------------------------
@pytest.mark.parametrize("G,D,dtype,finite,want", [
    (kernels.ONEHOT_MAX_GROUPS, CH, jnp.float32, True, "onehot"),
    (kernels.ONEHOT_MAX_GROUPS, CH, jnp.float64, False, "onehot"),
    (11, kernels._ONEHOT_CHUNK - 1, jnp.float32, True, "scatter"),
    (kernels.ONEHOT_MAX_GROUPS + 1, CH, jnp.float32, True, "onehot2"),
    (7000, 1 << 23, jnp.float32, True, "onehot2"),
    (kernels.ONEHOT2_MAX_GROUPS, CH, jnp.float32, True, "onehot2"),
    (kernels.ONEHOT2_MAX_GROUPS + 1, CH, jnp.float32, True, "scatter"),
    (1 << 20, 1 << 23, jnp.float32, True, "scatter"),
    (7000, CH, jnp.float64, True, "scatter"),
    (7000, CH - 1, jnp.float32, True, "scatter"),
    (7000, CH, jnp.float32, False, "scatter"),
])
def test_group_path_edges(G, D, dtype, finite, want):
    assert kernels.group_path(G, D, dtype, finite=finite) == want


# -- the pass against numpy ----------------------------------------------------
def _plan(G, ops=("sum", "count", "sumsq", "min"), filtered=False):
    """GROUP BY one id column `g` of cardinality G over raw columns `v`
    (slot values) and `f` (the filter's)."""
    return DevicePlan(
        filter_ir=("leaf", 0) if filtered else None,
        leaves=(DeviceLeaf("vrange", "f"),) if filtered else (),
        value_irs=(("col", "v"),),
        agg_ops=tuple((op, None if op == "count" else 0, None)
                      for op in ops),
        group_cols=("g",), group_strides=(1,), num_groups=G,
        raw_cols=("f", "v") if filtered else ("v",))


def _data(S, D, G, seed, integers):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, G, (S, D)).astype(np.int32)
    keys[:, :3] = [0, G - 1, G // 2]  # both ends of the key space are hit
    if integers:
        vals = rng.integers(-(1 << 24) + 1, 1 << 24, (S, D))
    else:
        vals = rng.normal(size=(S, D)) * np.exp(rng.normal(size=(S, D)) * 3)
    num_docs = np.array([D - 37 * (s + 1) for s in range(S)], np.int32)
    filt = rng.random((S, D)).astype(np.float32)
    return keys, vals.astype(np.float32), num_docs, filt


def _reference(keys, vals, m, G, ops):
    """numpy, f64, a row at a time: [S, G, n_slots] and, for the sums'
    tolerance, the per-group sums of |contribution|."""
    S = keys.shape[0]
    out = np.zeros((S, G, len(ops)))
    scale = np.ones((S, G, len(ops)))
    v = vals.astype(np.float64)
    for j, op in enumerate(ops):
        for s in range(S):
            k, sel = keys[s][m[s]], v[s][m[s]]
            if op == "min":
                out[s, :, j] = np.inf
                np.minimum.at(out[s, :, j], k, sel)
                continue
            c = sel ** kernels._ADDITIVE[op]
            np.add.at(out[s, :, j], k, c)
            scale[s, :, j] = 0
            np.add.at(scale[s, :, j], k, np.abs(c))
    return out, np.maximum(scale, 1.0)


def _check(got, keys, vals, m, G, ops):
    want, scale = _reference(keys, vals, m, G, ops)
    got = np.asarray(got, np.float64)
    assert got.shape == want.shape
    for j, op in enumerate(ops):
        if op in ("count", "min"):
            assert np.array_equal(got[..., j], want[..., j]), op
        else:
            err = np.abs(got[..., j] - want[..., j]) / scale[..., j]
            assert err.max() <= RTOL, (op, err.max())


@pytest.mark.parametrize("integers", [True, False],
                         ids=["int<2^24", "f32"])
@pytest.mark.parametrize("tail", [0, 200], ids=["whole", "tail"])
@pytest.mark.parametrize("G", [1025, 7000, 8192, 65536])
def test_onehot2_pass_against_numpy(G, tail, integers):
    S, D = 2, CH + tail
    ops = ("sum", "count", "sumsq", "min") if G < 65536 else ("sum", "count")
    keys, vals, num_docs, _f = _data(S, D, G, G + tail, integers)
    with jax.enable_x64(False):
        assert kernels.group_path(G, D, kernels._value_dtype(),
                                  finite=True) == "onehot2"
        kernel = jax.jit(kernels.make_kernel(_plan(G, ops)),
                         static_argnames=("D", "G"))
        cols = {"ids:g": jnp.asarray(keys), "val:v": jnp.asarray(vals)}
        # the additive slots share ONE pass; only MIN is left to scatter
        jaxpr = str(jax.make_jaxpr(kernel, static_argnums=(3,))(
            cols, {}, jnp.asarray(num_docs), D))
        assert jaxpr.count("dot_general") == 1
        assert jaxpr.count("scatter_dims_to_operand_dims") == ("min" in ops)
        got = kernel(cols, {}, jnp.asarray(num_docs), D=D)
        assert got.dtype == jnp.float32
    valid = np.arange(D)[None, :] < num_docs[:, None]
    _check(got, keys, vals, valid, G, ops)


def test_onehot2_under_vmap():
    """The batched kernel: B = 2 queries that differ in a filter literal
    share the columns; the pass is vmapped over the contributions."""
    G, S, D, ops = 7000, 2, CH + 128, ("count", "sum", "sum3")
    keys, vals, num_docs, filt = _data(S, D, G, 5, integers=False)
    his = (0.25, 0.8)
    with jax.enable_x64(False):
        kernel = kernels.make_batched_kernel(_plan(G, ops, filtered=True), 2)
        cols = {"ids:g": jnp.asarray(keys), "val:v": jnp.asarray(vals),
                "val:f": jnp.asarray(filt)}
        plist = batch_params(
            [{"leaf0:lo": jnp.zeros(S, jnp.float32),
              "leaf0:hi": jnp.full(S, hi, jnp.float32)} for hi in his])
        got = np.asarray(kernel(cols, plist, jnp.asarray(num_docs), D=D))
    assert got.shape == (2, S, G, len(ops))
    valid = np.arange(D)[None, :] < num_docs[:, None]
    for b, hi in enumerate(his):
        _check(got[b], keys, vals, valid & (filt <= np.float32(hi)), G, ops)
    assert got[0][..., 0].sum() < got[1][..., 0].sum()


def test_onehot2_inside_shard_map():
    """The sharded kernel over a (segments=2, docs=2) mesh: each doc
    shard holds one chunk, so the scan runs INSIDE shard_map and its
    carry must enter as varying as it leaves."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    G, S, D, ops = 1500, 2, 2 * CH, ("sum", "count", "max")
    keys, vals, num_docs, _f = _data(S, D, G, 9, integers=True)
    mesh = make_mesh(jax.devices()[:4], doc_axis=2)
    with jax.enable_x64(False):
        assert kernels.group_path(G, D // 2, kernels._value_dtype(),
                                  finite=True) == "onehot2"
        kernel = kernels.make_sharded_kernel(_plan(G, ops), mesh)
        got = np.asarray(kernel(
            {"ids:g": jnp.asarray(keys), "val:v": jnp.asarray(vals)}, {},
            jnp.asarray(num_docs), D=D))
    valid = np.arange(D)[None, :] < num_docs[:, None]
    want, scale = _reference(keys, vals, valid, G, ("sum", "count"))
    assert np.array_equal(got[..., 1], want[..., 1])
    assert (np.abs(got[..., 0] - want[..., 0]) / scale[..., 0]).max() <= RTOL
    ref_max = np.full((S, G), -np.inf)
    for s in range(S):
        np.maximum.at(ref_max[s], keys[s][valid[s]], vals[s][valid[s]])
    assert np.array_equal(got[..., 2], ref_max)


# -- the TPU's driver: the same tiles as one Pallas kernel -----------------------
def _sums(G, keys, mask, vals):
    return jnp.stack(kernels._onehot2_sums(
        [mask, jnp.where(mask, vals, 0)], jnp.where(mask, keys, 0), G), -1)


@pytest.mark.parametrize("G,tail", [(1025, 0), (7000, 200), (70000, 0)])
def test_pallas_driver_equals_the_xla_loop(G, tail, monkeypatch):
    """Off the TPU `_onehot2_sums` runs its tiles as an XLA loop; here the
    Pallas driver runs the same tiles through Pallas's TPU interpreter,
    and must give the same bits."""
    from jax.experimental.pallas import tpu as pltpu
    S, D = 2, CH + tail
    keys, vals, num_docs, _f = _data(S, D, G, 3 * G, integers=False)
    mask = np.arange(D)[None, :] < num_docs[:, None]
    with jax.enable_x64(False):
        loop = np.asarray(jax.jit(_sums, static_argnums=0)(
            G, keys, mask, vals))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pltpu.force_tpu_interpret_mode():
            jaxpr = str(jax.make_jaxpr(_sums, static_argnums=0)(
                G, keys, mask, vals))
            assert jaxpr.count("pallas_call[") == 1
            assert "name=onehot2" in jaxpr and "scan" not in jaxpr
            kernel = np.asarray(jax.jit(_sums, static_argnums=0)(
                G, keys, mask, vals))
    assert kernel.tobytes() == loop.tobytes()
    _check(kernel, keys, vals, mask, G, ("count", "sum"))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _compiling_for_the_chip(monkeypatch):
    """x64 off, the TPU's branch of `_onehot2_sums`, and no compile
    cache (an entry written for a described chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.enable_x64(False):
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()


@pytest.mark.parametrize("G,ops", [
    (7000, ("sum", "count")),                       # ssb2_q2_c1: 4 planes
    (8192, ("count",)),                             # the t-digest histogram
    (kernels.ONEHOT2_MAX_GROUPS, ("sum", "count", "sumsq", "sum3", "sum4")),
])
def test_pallas_driver_compiles_for_the_v5e(G, ops, one_chip, monkeypatch):
    """The chip's compiler takes the kernel at the benchmark's widths
    (16 segments of 8M docs) and at the widest plan the path function
    admits: tiling, VMEM and all. Nothing runs."""
    S, D = 16, 1 << 23
    with _compiling_for_the_chip(monkeypatch):
        kernel = jax.jit(kernels.make_kernel(_plan(G, ops)),
                         static_argnames=("D", "G"))
        compiled = kernel.lower(
            {"ids:g": jax.ShapeDtypeStruct((S, D), jnp.int32,
                                           sharding=one_chip),
             "val:v": jax.ShapeDtypeStruct((S, D), jnp.float32,
                                           sharding=one_chip)},
            {}, jax.ShapeDtypeStruct((S,), jnp.int32, sharding=one_chip),
            D=D).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 1 and "onehot2" in hlo
    assert "scatter" not in hlo
    # what a launch holds beside the table (planes, keys, contributions)
    # stays under half the chip
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 30


def test_fused_time_bucket_and_its_fold_compile_for_the_v5e(one_chip,
                                                          monkeypatch):
    """The tsbs_ts_dgb1_c1 cell's kernel as the engine plans it: the
    hour of DATETRUNC('HOUR', ts) computed from the raw timestamp's
    (hi, lo) planes as the key's lowest digit (16 padded hours) under
    4,000 hosts, G = 64,000 on the Pallas pass, 4 segments of 2^23 docs
    folded over 4,096 x 16 = 65,536 global keys. Nothing runs."""
    from pinot_tpu.ops.plan_ir import PACK, pack_layout
    S, D = 4, 1 << 23
    plan = DevicePlan(
        filter_ir=("and", ("leaf", 0), ("leaf", 1)),
        leaves=(DeviceLeaf("vrange64", "ts"), DeviceLeaf("vrange64", "ts")),
        value_irs=(("col", "usage_user"),),
        agg_ops=(("sum", 0, None), ("count", None, None)),
        group_cols=("hostname",), group_strides=(16,), num_groups=64000,
        dict_cols=("hostname",), raw_cols=("usage_user",),
        raw64_cols=("ts",), tbucket=("ts", 16), group_fold=(4096, 16))

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    with _compiling_for_the_chip(monkeypatch):
        kernel = jax.jit(kernels.make_kernel(plan),
                         static_argnames=("D", "G"))
        compiled = kernel.lower(
            {"ids:hostname": shape((S, D), jnp.int16),
             "val:usage_user": shape((S, D), jnp.float32),
             "valhi:ts": shape((S, D), jnp.int32),
             "vallo:ts": shape((S, D), jnp.int32)},
            {PACK: shape((len(pack_layout(plan)), S), jnp.int32),
             "ginv0": shape((S, 4096), jnp.int32)}, None, D=D).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 1 and "onehot2" in hlo
    assert "scatter" not in hlo
    # one folded row leaves: 2 slots x 65,536 keys, then S matched counts
    out, = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.shape == (2 * 65536 + S,) and out.dtype == jnp.int32
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 30


# -- four chips: the pass a shard, the fold's all-reduces ------------------------
def _q2_plan():
    """SSB Q2.x as the engine plans it once the fold is set: d_year (7)
    x p_brand1 (1,000) = 7,000 local keys, SUM + COUNT, a filter leaf,
    folded over the union's key space in pow2 digits (8 x 1,024)."""
    return DevicePlan(
        filter_ir=("leaf", 0), leaves=(DeviceLeaf("vrange", "f"),),
        value_irs=(("col", "v"),),
        agg_ops=(("sum", 0, None), ("count", None, None)),
        group_cols=("y", "b"), group_strides=(1000, 1), num_groups=7000,
        raw_cols=("f", "v"), group_fold=(8, 1024))


def _q2_args(S, D, put):
    """(cols, params, num_docs) of `_q2_plan` as a four-chip server
    stages them: every [S, ...] array sharded over `segments`.
    put(shape, dtype, spec) makes one."""
    seg, blk = ("segments",), ("segments", None)
    cols = {"ids:y": put((S, D), jnp.int8, blk),
            "ids:b": put((S, D), jnp.int16, blk),
            "val:v": put((S, D), jnp.float32, blk),
            "val:f": put((S, D), jnp.float32, blk)}
    params = {"leaf0:lo": put((S,), jnp.float32, seg),
              "leaf0:hi": put((S,), jnp.float32, seg),
              "ginv0": put((S, 8), jnp.int32, blk),
              "ginv1": put((S, 1024), jnp.int32, blk)}
    return cols, params, put((S,), jnp.int32, seg)


def test_mesh_kernel_runs_the_pallas_pass_a_shard(monkeypatch):
    """On a segments mesh the TPU's branch is ONE `pallas_call` named
    onehot2 INSIDE a shard_map over `segments` (GSPMD refuses a Mosaic
    kernel: "cannot be automatically partitioned"), and the fold a second
    shard_map whose reductions end in psums; without a mesh neither
    shard_map is there and the body is the one chip's, as it was."""
    from jax.sharding import Mesh, PartitionSpec as P
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = Mesh(np.array(jax.devices()[:4]), ("segments",))
    S, D = 8, CH
    args = _q2_args(S, D, lambda shape, dt, _spec: jnp.zeros(shape, dt))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def jaxpr_of(mesh):
        kernel = kernels.make_kernel(_q2_plan(), mesh=mesh)
        return jax.make_jaxpr(lambda *a: kernel(*a, D=D))(*args)

    with jax.enable_x64(False):
        on_mesh, alone = jaxpr_of(mesh), jaxpr_of(None)
    assert "shard_map" not in str(alone)
    assert str(alone).count("pallas_call[") == 1
    maps = [e for e in on_mesh.jaxpr.eqns if e.primitive.name == "shard_map"]
    assert len(maps) == 2
    for eqn in maps:
        assert eqn.params["mesh"].axis_names == ("segments",)
        assert set(eqn.params["in_specs"]) == {P("segments")}
    pass_, fold = (str(e.params["jaxpr"]) for e in maps)
    assert pass_.count("pallas_call[") == 1 and "name=onehot2" in pass_
    assert "scan" not in pass_ and "psum" not in pass_
    # the pass's partials leave their shard_map as they entered: a shard
    assert maps[0].params["out_specs"] == (P("segments"),)
    # the fold: every reduction over segments ends in its all-reduce and
    # the row leaves held whole by every chip
    assert "pallas_call" not in fold and fold.count("psum") >= 3
    assert "all_gather" not in fold and "all_to_all" not in fold
    assert maps[1].params["out_specs"] == (P(),)
    assert str(on_mesh).count("pallas_call[") == 1


def _mesh_shapes(topo, S, D):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices), ("segments",))
    return mesh, _q2_args(S, D, lambda shape, dt, spec: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, P(*spec))))


def _assert_a_shard_a_chip(compiled, rows):
    """The compiled four-chip program: the Mosaic kernel is in it, no
    collective but the fold's all-reduces (so no all-gather, least of all
    of an operand with a doc axis), and a chip's temporaries are its own
    shard's planes, keys and contributions (one chip compiled alone for
    8 such segments reads 1,073,838,592 B a query)."""
    from pinot_tpu.ops import device
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 1 and "onehot2" in hlo
    for op in ("all-gather", "all-to-all", "collective-permute",
               "reduce-scatter"):
        assert f" {op}(" not in hlo and f" {op}-start(" not in hlo, op
    # f32 sums + carried errors, i32 counts, the segments' matched docs
    assert device.collective_bytes(hlo) == (
        rows * 4 * (3 * 8192 + 32), 0)
    assert compiled.memory_analysis().temp_size_in_bytes < rows * (
        (2 << 30) + (1 << 20))


def test_mesh_kernel_compiles_for_the_v5e_2x2(topo, monkeypatch):
    """ISSUE 37's lowering: the engine's own kernel for a four-device
    segments mesh, the described v5e:2x2, 8 segments a chip x 2^23 docs,
    G = 7,000, SUM + COUNT, folded. Nothing runs."""
    mesh, (cols, params, num_docs) = _mesh_shapes(topo, 32, 1 << 23)
    with _compiling_for_the_chip(monkeypatch):
        compiled = kernels.make_kernel(_q2_plan(), mesh=mesh)
        compiled = jax.jit(compiled, static_argnames=("D", "G")).lower(
            cols, params, num_docs, D=1 << 23).compile()
    _assert_a_shard_a_chip(compiled, rows=1)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


@pytest.mark.parametrize("variant", ["broadcast", "stacked", "dedup"])
def test_mesh_batched_kernels_compile_for_the_v5e_2x2(topo, monkeypatch,
                                                      variant):
    """Every variant the dispatch ring can pick for such a plan on a
    mesh is `vmap` OVER the same body, so over both shard_maps and the
    Pallas call: jax 0.9.0 batches all three, and the chip's compiler
    takes the result (B = 2; the dedup variant B = 2 over U = 2)."""
    mesh, (cols, params, num_docs) = _mesh_shapes(topo, 32, 1 << 23)
    plist = {k: (v, v) for k, v in params.items()}
    with _compiling_for_the_chip(monkeypatch):
        if variant == "broadcast":
            kernel = kernels.make_batched_kernel(_q2_plan(), 2, False, mesh)
            lowered = kernel.lower(cols, plist, num_docs, D=1 << 23)
        elif variant == "stacked":
            kernel = kernels.make_batched_kernel(_q2_plan(), 2, True, mesh)
            lowered = kernel.lower((cols, cols), plist,
                                   (num_docs, num_docs), D=1 << 23)
        else:
            kernel = kernels.make_batched_dedup_kernel(
                _q2_plan(), 2, 2, mesh)
            lowered = kernel.lower(
                (cols, cols), plist, (num_docs, num_docs),
                jax.ShapeDtypeStruct((2,), jnp.int32), D=1 << 23)
        compiled = lowered.compile()
    _assert_a_shard_a_chip(compiled, rows=2)


# -- a served GROUP BY ---------------------------------------------------------
CARD = 1500
DOCS = CH + 500


@pytest.fixture(scope="module")
def wide_segs(tmp_path_factory):
    """GROUP BY dim has 1,500 groups; `fval` holds +Inf and -Inf in
    group 7 (their sum is NaN), +Inf alone in group 8 and a NaN in
    group 9 (which segment creation may or may not keep: the host path
    is the judge)."""
    schema = Schema("wide", [
        FieldSpec("dim", DataType.INT, FieldType.DIMENSION),
        FieldSpec("ival", DataType.INT, FieldType.METRIC),
        FieldSpec("fval", DataType.FLOAT, FieldType.METRIC),
    ])
    tc = TableConfig("wide", TableType.OFFLINE)
    tc.indexing.no_dictionary_columns = ["ival", "fval"]
    segs = []
    for i in range(2):
        rng = np.random.default_rng(40 + i)
        dim = rng.integers(0, CARD, DOCS).astype(np.int32)
        dim[:CARD] = np.arange(CARD)  # every group in every segment
        fval = (rng.random(DOCS) * 100).astype(np.float32)
        if i == 0:
            fval[7], fval[CARD + 1] = np.inf, -np.inf
            dim[CARD + 1] = 7
            fval[8], fval[9] = np.inf, np.nan
        segs.append({"dim": dim, "fval": fval,
                     "ival": rng.integers(0, 1 << 20, DOCS).astype(np.int32)})
    return build_segments(tmp_path_factory.mktemp("wide"), schema, tc, segs)


def _dispatches(tree):
    out = [tree] if tree.get("operator") == "DeviceDispatch" else []
    for c in tree.get("children", ()):
        out += _dispatches(c)
    return out


def _run(segs, sql, labels):
    with jax.enable_x64(False):
        engine = TpuOperatorExecutor(metrics_labels=labels)
        got = QueryExecutor(segs, use_tpu=True, engine=engine).execute(
            "SET trace = true; " + sql)
        want = QueryExecutor(segs, use_tpu=False).execute(sql)
        plan, _slots = engine._plan(segs, QueryContext.from_sql(sql))
    assert not got.exceptions and not want.exceptions
    span, = _dispatches(got.trace)
    assert "outcome" not in span, "fell back to the host"
    meters = {p: engine._metrics.meter("group_path",
                                       labels=dict(labels, path=p))
              for p in ("onehot", "onehot2", "scatter")}
    return got.result_table.rows, want.result_table.rows, plan, span, meters


def test_int_sum_is_served_by_the_pass(wide_segs):
    sql = ("SELECT dim, COUNT(*), SUM(ival), AVG(ival) FROM wide "
           "WHERE ival > 5000 GROUP BY dim ORDER BY dim LIMIT 2000")
    got, want, plan, span, meters = _run(wide_segs, sql, {"t": "int"})
    assert not plan.nonfinite
    assert span["groupPath"] == "onehot2" and span["G"] == 0
    assert meters == {"onehot": 0, "onehot2": 1, "scatter": 0}
    # the pass's per-segment partials are folded before they leave the
    # device: one [G, slots] table of f32-wide words, not one a segment
    assert span["groupFold"] == "device" and plan.num_groups == CARD
    assert span["groupKeySpace"] == CARD
    # over the union's key space in pow2 digits (1,500 -> 2,048)
    assert span["groupResultBytes"] == 2048 * len(plan.agg_ops) * 4
    assert span["groupsPresent"] == CARD
    assert len(got) == len(want) == CARD
    for g, w in zip(got, want):
        assert g[:2] == w[:2]
        assert g[2] == pytest.approx(w[2], rel=RTOL)
        assert g[3] == pytest.approx(w[3], rel=2 * RTOL)


def test_float_sum_with_inf_and_nan_keeps_the_scatter(wide_segs):
    """0 * Inf = NaN would reach every group of a one-hot tile: the plan
    knows `fval` is a FLOAT and stays on the scatter, whose answer is the
    host's in EVERY group: NaN in 7, Inf in 8, finite past 9."""
    sql = ("SELECT dim, SUM(fval), COUNT(*) FROM wide GROUP BY dim "
           "ORDER BY dim LIMIT 2000")
    got, want, plan, span, meters = _run(wide_segs, sql, {"t": "float"})
    assert plan.nonfinite
    assert kernels.plan_fingerprint(plan) != kernels.plan_fingerprint(
        DevicePlan(**{**plan.__dict__, "nonfinite": False}))
    assert span["groupPath"] == "scatter"
    assert meters == {"onehot": 0, "onehot2": 0, "scatter": 1}
    assert len(got) == len(want) == CARD
    assert math.isnan(got[7][1]) and math.isnan(want[7][1])
    assert got[8][1] == want[8][1] == math.inf
    for g, w in zip(got, want):
        assert g[0] == w[0] and g[2] == w[2]
        assert g[1] == pytest.approx(w[1], rel=1e-5, nan_ok=True)
        assert math.isfinite(g[1]) or g[0] in (7, 8, 9)


@pytest.mark.parametrize("expr,nonfinite", [
    ("SUM(ival)", False), ("SUM(ival * ival)", False),
    ("SUM(ival * ival * ival * 2)", False), ("MAX(fval)", False),
    ("SUM(fval)", True), ("SUM(ival / 2)", True), ("SUM(ival + fval)", True),
    ("VAR_POP(ival)", False), ("KURTOSIS(ival)", False),
    ("KURTOSIS(ival * ival)", True),
])
def test_plan_knows_which_sums_can_hold_an_inf(wide_segs, expr, nonfinite):
    """From the columns' types alone: INT is 31 bits, a product adds
    them, a slot of power p must stay under 2^127."""
    ctx = QueryContext.from_sql(
        f"SELECT dim, {expr} FROM wide GROUP BY dim LIMIT 10")
    with jax.enable_x64(False):
        engine = TpuOperatorExecutor()
        if not engine.supports(ctx):
            pytest.skip(f"{expr}: not a device aggregation here")
        planned = engine._plan(wide_segs, ctx)
    assert planned is not None
    assert planned[0].nonfinite == nonfinite
    flat = engine._plan(wide_segs, QueryContext.from_sql(
        f"SELECT {expr} FROM wide"))
    assert flat is None or not flat[0].nonfinite  # no GROUP BY: no one-hot
