"""A `scatter` GROUP BY hands XLA's scatter-add each segment's kept rows
alone, compacted to the smallest rung of `kernels.compact_rungs` that
holds the launch's largest kept count, or every row past the top rung.

  * the kernel against a numpy reference at every rung's edges (a
    segment's kept count at 0, cap - 1, cap, cap + 1, past the top rung,
    every row, a shard under COMPACT_MIN_DOCS): SUM, COUNT, MIN and MAX
    beside a per-aggregation FILTER, f64 (x64 on, the suite's default)
    and f32 (what one chip runs), and a `nonfinite` plan whose sums hold
    Inf and NaN
  * a forced rung, and the full scatter, give what the chosen rung gives
  * a batched launch whose members would sit on different rungs runs
    the largest, and each member answers as it does alone; so does a
    (segments x docs) mesh, its rung from the segments' whole counts
  * the count tree's walk against numpy's `flatnonzero`
  * structure: at the cell's shapes (S = 16, D = 2^23, G = 437,500) no
    compacted branch asks for a sort, a scatter or a prefix over S x D
  * compiled for the described v5e:2x2 segments mesh, alone and batched
"""
import contextlib
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pinot_tpu.ops import kernels
from pinot_tpu.ops.plan_ir import DeviceLeaf, DevicePlan

D = kernels.COMPACT_MIN_DOCS
RUNGS = kernels.compact_rungs(D)
G = 5000
#: the f32 key space: past ONEHOT2_MAX_GROUPS, where a finite f32 plan
#: scatters
G32 = kernels.ONEHOT2_MAX_GROUPS + 4096


def _plan(num_groups, nonfinite=False):
    """GROUP BY `g` over rows `f` in [0.5, 1.5] keeps; SUM, COUNT, MIN,
    MAX of `v`, then SUM, COUNT, MIN and MAX under FILTER (h >= 0.5)."""
    plain = (("sum", 0, None), ("count", None, None),
             ("min", 0, None), ("max", 0, None))
    return DevicePlan(
        filter_ir=("leaf", 0),
        leaves=(DeviceLeaf("vrange", "f"), DeviceLeaf("vrange", "h")),
        value_irs=(("col", "v"),),
        agg_ops=plain + tuple((op, v, 0) for op, v, _f in plain),
        agg_filter_irs=(("leaf", 1),),
        group_cols=("g",), group_strides=(1,), num_groups=num_groups,
        raw_cols=("f", "h", "v"), nonfinite=nonfinite)


def _data(kept, docs, num_groups, seed, nonfinite=False):
    """cols, params and num_docs of len(kept) segments of `docs` docs,
    padded to a power of two of at least D rows: segment s keeps exactly
    kept[s] real rows, spread over it, its last real row among them
    (nonfinite: rows 7, 9 and 11 too, holding Inf, -Inf and NaN);
    padding rows pass the filter too, and must not count."""
    S = len(kept)
    rng = np.random.default_rng(seed)
    Dp = max(D, 1 << (max(docs) - 1).bit_length())
    f = np.zeros((S, Dp), np.float32)
    for s, (n, nd) in enumerate(zip(kept, docs)):
        if n:
            at = rng.choice(nd - 1, n - 1, replace=False)
            f[s, np.append(at, nd - 1)] = 1.0
        f[s, nd:] = 1.0
    v = rng.normal(size=(S, Dp)).astype(np.float32) * 1000
    if nonfinite:
        v[:, 7], v[:, 9], v[:, 11] = np.inf, -np.inf, np.nan
        f[:, [7, 9, 11]] = 1.0
    cols = {"ids:g": rng.integers(0, num_groups, (S, Dp)).astype(np.int32),
            "val:f": f, "val:h": rng.random((S, Dp)).astype(np.float32),
            "val:v": v}
    params = {"leaf0:lo": np.full(S, 0.5, np.float32),
              "leaf0:hi": np.full(S, 1.5, np.float32),
              "leaf1:lo": np.full(S, 0.5, np.float32),
              "leaf1:hi": np.full(S, 2.0, np.float32)}
    return cols, params, np.asarray(docs, np.int32)


def _reference(cols, params, num_docs, num_groups):
    """[S, G, 8] in f64: the plan's slots by numpy, each group's own."""
    S, Dp = cols["val:f"].shape
    real = np.arange(Dp)[None, :] < num_docs[:, None]
    m = (cols["val:f"] >= 0.5) & (cols["val:f"] <= 1.5) & real
    h = (cols["val:h"] >= 0.5) & (cols["val:h"] <= 2.0)
    out = np.zeros((S, num_groups, 8))
    for s in range(S):
        for base, mm in ((0, m[s]), (4, m[s] & h[s])):
            k = cols["ids:g"][s][mm]
            x = cols["val:v"][s][mm].astype(np.float64)
            with np.errstate(invalid="ignore"):
                np.add.at(out[s, :, base], k, x)
            out[s, :, base + 1] = np.bincount(k, minlength=num_groups)
            lo = np.full(num_groups, np.inf)
            hi = np.full(num_groups, -np.inf)
            np.fmin.at(lo, k, np.where(np.isnan(x), np.inf, x))
            np.fmax.at(hi, k, np.where(np.isnan(x), -np.inf, x))
            out[s, :, base + 2], out[s, :, base + 3] = lo, hi
    return out


def _run(plan, cols, params, num_docs, rung=None):
    kernel = jax.jit(kernels.make_kernel(plan), static_argnames=("D", "G"))
    Dp = cols["val:f"].shape[1]
    if rung is not None:
        return np.asarray(jax.jit(
            lambda c, p, n, r: kernels.make_kernel(plan)(c, p, n, D=Dp,
                                                         rung=r))(
            cols, params, num_docs, jnp.int32(rung)))
    return np.asarray(kernel(cols, params, num_docs, D=Dp))


def _check(got, want, rtol):
    """Counts exact; MIN / MAX exact where the group has a row (a NaN
    row is skipped by MIN / MAX in the reference, carried by the scatter:
    compared where neither is NaN); sums to rtol, Inf and NaN where the
    reference has them."""
    for j in (1, 5):
        assert (got[..., j] == want[..., j]).all()
    for j in (2, 3, 6, 7):
        g, w = got[..., j], want[..., j]
        both = ~np.isnan(g)
        assert (g[both] == w[both]).all()
    for j in (0, 4):
        g, w = got[..., j], want[..., j]
        assert (np.isnan(g) == np.isnan(w)).all()
        fin = np.isfinite(w)
        assert (g[~fin & ~np.isnan(w)] == w[~fin & ~np.isnan(w)]).all()
        np.testing.assert_allclose(g[fin], w[fin], rtol=rtol, atol=rtol)


def _cases():
    """(label, kept counts of three segments): every rung's edges, the
    segment on the edge beside two that keep less."""
    yield "none", (0, 0, 0)
    for cap in RUNGS:
        for n in (cap - 1, cap, cap + 1):
            yield f"{n}", (n, 1, 0)
    yield "past the top rung", (RUNGS[-1] + 1, 17, 3)
    yield "every row", (D - 5, D - 5, D - 5)


@pytest.mark.parametrize("kept", [c for _l, c in _cases()],
                         ids=[label for label, _c in _cases()])
def test_each_rung_answers_as_numpy(kept):
    plan = _plan(G)
    docs = [D - 5] * len(kept)
    cols, params, num_docs = _data(kept, docs, G, seed=sum(kept) + 1)
    got = _run(plan, cols, params, num_docs)
    _check(got, _reference(cols, params, num_docs, G), 1e-9)
    # the rung the kernel chose: the smallest holding the most kept
    cap = kernels.compact_cap(D, max(kept))
    assert cap == next((c for c in RUNGS if c >= max(kept)), 0)


@pytest.mark.parametrize("rung", range(len(RUNGS) + 1))
def test_a_forced_rung_that_holds_the_rows_gives_the_same(rung):
    """Any rung at or past the one chosen, and the full scatter, give
    the chosen rung's partials; the counts bit for bit."""
    plan = _plan(G)
    kept = (RUNGS[0] - 3, 40, 0)
    cols, params, num_docs = _data(kept, [D - 5] * 3, G, seed=3)
    chosen = _run(plan, cols, params, num_docs)
    forced = _run(plan, cols, params, num_docs, rung=rung)
    assert (forced[..., 1] == chosen[..., 1]).all()
    np.testing.assert_allclose(forced, chosen, rtol=1e-12)


def test_f32_past_onehot2_compacts_and_answers():
    """What one chip runs: x64 off, a finite f32 plan past
    ONEHOT2_MAX_GROUPS, a segment on the middle rung."""
    plan = _plan(G32)
    kept = (RUNGS[1] - 1, RUNGS[0] + 1, 2)
    with jax.enable_x64(False):
        assert kernels.compacts(plan, G32, D)
        cols, params, num_docs = _data(kept, [D - 1, D - 70, D], G32, 5)
        got = _run(plan, cols, params, num_docs)
    _check(got, _reference(cols, params, num_docs, G32), 1e-5)


@pytest.mark.parametrize("kept", [(0, 5, 0), (RUNGS[0] + 1, 3, 0),
                                  (RUNGS[-1] + 1, 0, 0)])
def test_nonfinite_plan_compacts_and_keeps_inf_and_nan(kept):
    """A FLOAT sum that may hold Inf / NaN keeps the scatter in f32 at a
    key space onehot2 would take (`group_path`), and compacts: the Inf,
    -Inf and NaN rows reach their own groups only."""
    plan = _plan(7000, nonfinite=True)
    with jax.enable_x64(False):
        assert kernels.compacts(plan, 7000, D)
        assert not kernels.compacts(dataclasses.replace(
            plan, nonfinite=False), 7000, D)
        cols, params, num_docs = _data(kept, [D - 3] * 3, 7000, 9,
                                       nonfinite=True)
        got = _run(plan, cols, params, num_docs)
    want = _reference(cols, params, num_docs, 7000)
    assert np.isnan(want[..., 0]).any() and np.isinf(want[..., 2]).any()
    _check(got, want, 1e-5)


def test_a_shard_under_the_minimum_keeps_the_full_scatter():
    plan = _plan(G)
    half = D // 2
    assert kernels.compact_rungs(half) == ()
    assert not kernels.compacts(plan, G, half)
    assert kernels.compact_cap(half, 3) == 0
    cols, params, num_docs = _data((3, 0), [half - 9, half], G, seed=4)
    cols = {k: v[:, :half] for k, v in cols.items()}
    got = np.asarray(jax.jit(kernels.make_kernel(plan),
                             static_argnames=("D", "G"))(
        cols, params, num_docs, D=half))
    _check(got, _reference(cols, params, num_docs, G), 1e-9)
    assert "cond" not in str(jax.make_jaxpr(
        lambda c, p, n: kernels.make_kernel(plan)(c, p, n, D=half))(
            cols, params, num_docs))


def test_rungs_and_caps():
    """The ladder: three powers of two of the shard's docs, the smallest
    holding the count chosen, 0 past the top; none for a shard that is
    small or not a power of two."""
    assert RUNGS == (D // 512, D // 64, D // 8)
    assert kernels.compact_rungs(1 << 23) == (16384, 131072, 1048576)
    assert kernels.compact_rungs(D + 128) == ()
    for n, cap in ((0, RUNGS[0]), (RUNGS[0], RUNGS[0]),
                   (RUNGS[0] + 1, RUNGS[1]), (RUNGS[2], RUNGS[2]),
                   (RUNGS[2] + 1, 0), (D, 0)):
        assert kernels.compact_cap(D, n) == cap
        # the device's choice is the same function on a traced count
        assert int(jax.jit(lambda x: kernels.compact_rung(D, x))(
            jnp.int32(n))) == (RUNGS + (0,)).index(cap)


@pytest.mark.parametrize("counts", [(0, 0), (1, 0), (129, 5),
                                    (D // 3, 7), (D, D)])
def test_the_walk_finds_the_kept_rows_in_order(counts):
    rng = np.random.default_rng(sum(counts))
    kept = np.zeros((len(counts), D), bool)
    for s, n in enumerate(counts):
        kept[s, rng.choice(D, n, replace=False)] = True
    rows = np.broadcast_to(np.arange(D, dtype=np.int32), kept.shape)
    for cap in RUNGS + (D,):
        picked, live = jax.jit(lambda k, c=cap: kernels._kept_rows(
            kernels._kept_tree(k), c, {"row": rows, "kept": k}))(kept)
        pos, live = np.asarray(picked["row"]), np.asarray(live)
        assert (np.asarray(picked["kept"]) == live).all()
        for s, n in enumerate(counts):
            want = np.flatnonzero(kept[s])[:cap]
            assert live[s].sum() == len(want)
            assert (pos[s][live[s]] == want).all()
            assert (pos[s][~live[s]] == 0).all()


# -- a batched launch -----------------------------------------------------------
def _members():
    """Three members on three rungs of one table: member i keeps the rows
    whose `f` is i + 1 (padding rows pass member 0's filter, and must not
    count). (the shared cols and num_docs, the members' params)."""
    kept = [(RUNGS[0] - 1, 2, 0), (RUNGS[0] + 1, 0, 9), (RUNGS[2], 1, 1)]
    docs = D - 2
    cols, params, num_docs = _data((0, 0, 0), [docs] * 3, G, seed=11)
    rng = np.random.default_rng(11)
    f = np.zeros_like(cols["val:f"])
    f[:, docs:] = 1.0
    for s in range(3):
        order, at = rng.permutation(docs), 0
        for i, k in enumerate(kept):
            f[s, order[at:at + k[s]]] = i + 1
            at += k[s]
    cols["val:f"] = f
    plist = [{**params, "leaf0:lo": np.full(3, i + 0.5, np.float32),
              "leaf0:hi": np.full(3, i + 1.5, np.float32)}
             for i in range(len(kept))]
    return cols, plist, num_docs


@pytest.mark.parametrize("variant", ["broadcast", "stacked", "dedup"])
def test_a_batch_across_rungs_answers_as_each_member_alone(variant):
    plan = _plan(G)
    cols, plist, num_docs = _members()
    alone = [_run(plan, cols, p, num_docs) for p in plist]
    caps = [kernels.compact_cap(D, int(a[:, :, 1].sum(-1).max()))
            for a in alone]
    assert caps == [RUNGS[0], RUNGS[1], RUNGS[2]]
    B = len(plist)
    stacked_params = {k: tuple(p[k] for p in plist) for k in plist[0]}
    if variant == "broadcast":
        kernel = kernels.make_batched_kernel(plan, B, False)
        got = kernel(cols, stacked_params, num_docs, D=D)
    elif variant == "stacked":
        kernel = kernels.make_batched_kernel(plan, B, True)
        got = kernel((cols,) * B, stacked_params, (num_docs,) * B, D=D)
    else:
        kernel = kernels.make_batched_dedup_kernel(plan, B, 1)
        got = kernel((cols,), stacked_params, (num_docs,),
                     jnp.zeros(B, jnp.int32), D=D)
    got = np.asarray(got)
    for b in range(B):
        assert (got[b][..., 1] == alone[b][..., 1]).all()
        np.testing.assert_allclose(got[b], alone[b], rtol=1e-12)
    # ONE rung for the launch: the batch's largest, not a switch a member
    jaxpr = str(jax.make_jaxpr(
        lambda c, p, n: kernels.make_batched_kernel(plan, B, False)(
            c, p, n, D=D))(cols, stacked_params, num_docs))
    assert jaxpr.count(" cond[") == 1


@pytest.mark.parametrize("batched", [False, True], ids=["alone", "batched"])
def test_a_docs_mesh_compacts_every_shard_on_one_rung(batched):
    """Over a (segments x docs) mesh each shard holds half a segment's
    docs, and the rung is chosen from the segments' whole kept counts
    (summed over the docs axis, the most over every segment and member):
    what the host reads back. Answers as one device does, alone and as
    a batch of two whose members sit on different rungs."""
    from jax.sharding import Mesh
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("segments", "docs"))
    plan, Dm = _plan(G), 2 * D
    assert kernels.compact_rungs(Dm // 2) == RUNGS
    docs = [Dm - 7] * 4
    cols, params, num_docs = _data((0, 0, 0, 0), docs, G, seed=21)
    rng = np.random.default_rng(21)
    f = np.zeros_like(cols["val:f"])
    f[:, Dm - 7:] = 1.0
    # member 0 keeps rows whose f is 1 (the most: RUNGS[0] + 1 in
    # segment 2, over both shards), member 1 those whose f is 2
    for i, kept in enumerate(((5, 0, RUNGS[0] + 1, 9),
                              (RUNGS[1] + 1, 1, 0, 3))):
        for s_, n in enumerate(kept):
            free = np.flatnonzero(f[s_, :Dm - 7] == 0)
            f[s_, rng.choice(free, n, replace=False)] = i + 1
    cols["val:f"] = f
    plist = [{**params, "leaf0:lo": np.full(4, i + 0.5, np.float32),
              "leaf0:hi": np.full(4, i + 1.5, np.float32)}
             for i in range(2)]
    alone = [_run(plan, cols, p, num_docs) for p in plist]
    if batched:
        kernel = kernels.make_batched_sharded_kernel(plan, mesh, 2)
        got = np.asarray(kernel(cols, {k: tuple(p[k] for p in plist)
                                       for k in plist[0]},
                                num_docs, D=Dm))
    else:
        kernel = kernels.make_sharded_kernel(plan, mesh)
        got = np.stack([np.asarray(kernel(cols, p, num_docs, D=Dm))
                        for p in plist])
    for b in range(2):
        assert (got[b][..., 1] == alone[b][..., 1]).all()
        np.testing.assert_allclose(got[b], alone[b], rtol=1e-12)
    kept = [int(a[:, :, 1].sum(-1).max()) for a in alone]
    assert [kernels.compact_cap(Dm // 2, n) for n in kept] \
        == [RUNGS[1], RUNGS[2]]


# -- structure at the cell's shapes ---------------------------------------------
S_CELL, D_CELL, G_CELL = 16, 1 << 23, 437_500


def _q32_plan():
    """Q3.2 as the engine plans it: three group columns, two nation
    leaves and a year range, SUM(lo_revenue) and the COUNT every grouped
    plan carries."""
    return DevicePlan(
        filter_ir=("and", ("leaf", 0), ("leaf", 1), ("leaf", 2)),
        leaves=(DeviceLeaf("range", "c_nation"),
                DeviceLeaf("range", "s_nation"),
                DeviceLeaf("range", "d_year")),
        value_irs=(("col", "lo_revenue"),),
        agg_ops=(("sum", 0, None), ("count", None, None)),
        group_cols=("c_city", "s_city", "d_year"),
        group_strides=(1750, 7, 1), num_groups=G_CELL,
        dict_cols=("c_city", "s_city", "d_year", "c_nation", "s_nation"),
        raw_cols=("lo_revenue",))


def _q32_args(S, D_, put):
    blk, seg = ("segments", None), ("segments",)
    cols = {f"ids:{c}": put((S, D_), dt, blk) for c, dt in (
        ("c_city", jnp.int16), ("s_city", jnp.int16), ("d_year", jnp.int8),
        ("c_nation", jnp.int8), ("s_nation", jnp.int8))}
    cols["val:lo_revenue"] = put((S, D_), jnp.float32, blk)
    params = {f"leaf{i}:{b}": put((S,), jnp.int32, seg)
              for i in range(3) for b in ("lo", "hi")}
    return cols, params, put((S,), jnp.int32, seg)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


def test_no_compacted_branch_sorts_or_scatters_a_whole_segment():
    """The cell's shapes, traced (nothing runs): the one `cond` has a
    branch a rung and the full scatter last; no compacted branch holds a
    sort, a scatter or a cumulative sum whose operand has S x D elements
    (`jnp.nonzero` is a cumsum and a scatter-add over every row,
    `argsort` a sort), and each one's scatter-adds take S x cap rows."""
    args = _q32_args(S_CELL, D_CELL, lambda shape, dt, _s:
                     jax.ShapeDtypeStruct(shape, dt))
    with jax.enable_x64(False):
        jaxpr = jax.make_jaxpr(lambda *a: kernels.make_kernel(_q32_plan())(
            *a, D=D_CELL))(*args)
    conds = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "cond"]
    assert len(conds) == 1
    branches = conds[0].params["branches"]
    rungs = kernels.compact_rungs(D_CELL)
    assert len(branches) == len(rungs) + 1
    whole = S_CELL * D_CELL

    def heavy(eqn):
        return any(int(np.prod(v.aval.shape)) >= whole
                   for v in eqn.invars if hasattr(v, "aval"))

    suspects = ("sort", "cumsum", "cumlogsumexp", "cummax", "cummin")
    for cap, branch in zip(rungs, branches):
        eqns = list(_eqns(branch.jaxpr))
        for eqn in eqns:
            name = eqn.primitive.name
            if name.startswith("scatter"):
                assert eqn.invars[2].aval.shape == (S_CELL, cap), name
            else:
                assert not (name in suspects and heavy(eqn)), name
        assert sum(e.primitive.name == "scatter-add" for e in eqns) == 2
    full = list(_eqns(branches[-1].jaxpr))
    assert [e.invars[2].aval.shape for e in full
            if e.primitive.name == "scatter-add"] == [(S_CELL, D_CELL)] * 2


# -- four chips -------------------------------------------------------------------
@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@contextlib.contextmanager
def _compiling_for_the_chip():
    """x64 off and no compile cache (an entry written for a described
    chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    with jax.enable_x64(False):
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()


@pytest.mark.parametrize("batched", [False, True], ids=["alone", "batched"])
def test_compacting_kernel_compiles_for_the_v5e_2x2(topo, batched):
    """The engine's kernel for a four-device segments mesh (GSPMD: the
    rung is a max over every chip's segments, one value a launch), Q3.2's
    plan, 8 segments a chip: it compiles, and its only collectives are
    the all-reduces of that max (no all-gather of a block). The batched
    variant (B = 2) runs one rung for both members. Nothing runs."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices), ("segments",))
    D_ = 1 << 20
    cols, params, num_docs = _q32_args(
        32, D_, lambda shape, dt, spec: jax.ShapeDtypeStruct(
            shape, dt, sharding=NamedSharding(mesh, P(*spec))))
    with _compiling_for_the_chip():
        if batched:
            kernel = kernels.make_batched_kernel(_q32_plan(), 2, False, mesh)
            lowered = kernel.lower(cols, {k: (v, v) for k, v in
                                          params.items()},
                                   num_docs, D=D_)
        else:
            lowered = jax.jit(kernels.make_kernel(_q32_plan(), mesh=mesh),
                              static_argnames=("D", "G")).lower(
                cols, params, num_docs, D=D_)
        hlo = lowered.compile().as_text()
    assert " conditional(" in hlo
    for op in ("all-gather", "all-to-all", "reduce-scatter"):
        assert f" {op}(" not in hlo and f" {op}-start(" not in hlo, op
