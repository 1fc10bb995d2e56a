"""Device-side star-tree pre-aggregation (ref StarTreeFilterOperator +
StarTreeAggregationExecutor / StarTreeGroupByExecutor, run on TPU).

The host keeps what it is good at — the fit check and the recursive
tree traversal (pointer chasing over the int32 node array) — and the
device does what IT is good at: the residual aggregation over the
matched pre-agg records. Traversal yields record indices into the
pre-agg table (the DFS layout makes every node a contiguous [start,
end) slice); those become a boolean selection mask shipped as kernel
PARAMS, while the pre-agg metric/dim-code columns are staged once as
`(segment, "__startree__<ti>/<pair>")` pseudo-columns through the
engine's host-row / residency / assembled-block tiers and reused across
queries. Two star-tree queries with the same StarTreePlan therefore
differ only in params — they coalesce into ONE jit(vmap) launch through
the ops/dispatch micro-batcher, exactly like scan kernels.

Exactness: integral sum/count pairs ride exact unsigned int planes
(two 24-bit digits, each through kernels._isum_u_slot; grouped via
per-plane i32 scatter-adds), so int sums and counts are bit-identical
to the host paths for any value < 2^48. Float pairs and min/max use the
engine's value dtype (f32 unless x64), the same precision posture as
the scan path. Plan admission (`plan_startree`) proves the bounds from
the tree's actual metric columns and falls back by reason otherwise.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pinot_tpu.ops import kernels
from pinot_tpu.query.expressions import Identifier
from pinot_tpu.query.results import (AggregationResult, ExecutionStats,
                                     GroupByResult)
from pinot_tpu.query.startree_exec import _agg_pairs_needed, _filter_id_sets
from pinot_tpu.segment.startree import parse_pair

#: unsigned planes per 24-bit digit of an exact-sum slot: 4 * 7 bits
#: covers the digit, per-plane i32 sums stay exact (127 * 2^24 < 2^31)
USUM_PLANES = 4
#: slot width: (hi, lo) digits x USUM_PLANES planes x (hi, lo) f32 halves
USUM_WIDTH = 2 * 2 * USUM_PLANES
#: largest integral value an exact slot can carry (two 24-bit digits)
USUM_MAX = float(1 << 48)
#: f32 represents integers exactly up to 2^24 — the min/max admission bound
_F32_EXACT_INT = float(1 << 24)
#: mixed-radix group-key space cap (mirrors engine.MAX_DEVICE_GROUPS)
_MAX_GROUPS = 1 << 20


class StarTreePlan(NamedTuple):
    """Frozen device plan for one star-tree aggregation shape. Carries
    STRUCTURE only (slot forms, group radix) — never filter literals or
    segment identity — so fingerprint-equal queries with different
    predicate constants share one compiled kernel and one launch."""
    slots: Tuple[Tuple[str, str], ...]        # (op, "func__col") per pair
    group_dims: Tuple[str, ...] = ()
    group_cards: Tuple[int, ...] = ()
    group_strides: Tuple[int, ...] = ()
    num_groups: int = 0


class STFit(NamedTuple):
    """One segment's fitted tree + traversal result."""
    ti: int          # tree index within the segment's reader
    tree: object     # segment.startree.StarTreeV2
    recs: np.ndarray  # selected pre-agg record indices (int64)


def slot_width(op: str) -> int:
    return USUM_WIDTH if op == "usum" else 1


# ---------------------------------------------------------------------------
# Kernels (traced; purity-checked as a kernel module)
# ---------------------------------------------------------------------------

def _grouped_usum(vi, keys, m, num_groups):
    """Exact per-group sum of one 24-bit digit column: per-plane i32
    scatter-adds, each plane returned as f32-exact (hi, lo) halves —
    the grouped counterpart of kernels._isum_u_slot."""
    dt = kernels._value_dtype()
    vi = jnp.where(m, vi, 0)
    safe_keys = jnp.where(m, keys, 0)
    parts = []
    for k in range(USUM_PLANES):
        p = (vi >> jnp.int32(kernels.ISUM_U_BITS * k)) & jnp.int32(127)
        s = kernels._vmap_scatter(
            jnp.zeros((vi.shape[0], num_groups), dtype=jnp.int32),
            safe_keys, p, "add")
        parts.append((s >> jnp.int32(12)).astype(dt))
        parts.append((s & jnp.int32(4095)).astype(dt))
    return parts


def make_startree_kernel(plan: StarTreePlan, kind: str = "startree",
                         extra: tuple = ()):
    """[S, D] pre-agg residual aggregation. cols: "stid:<dim>" group
    codes, "stval:<pair>" float metrics, "sthi:/stlo:<pair>" exact-sum
    digit rows. params: "sel" [S, D] bool selection mask (the traversal
    result — the only per-query input). Flat output [S, 1 + sum(w)]
    with the selected-record count first; grouped [S, G, 1 + sum(w)]
    with the per-group record count at index 0."""
    fp = kernels.plan_fingerprint(plan)

    def kernel(cols, params, num_docs, D, G=0):
        kernels.note_trace(kind, fp, (*extra, int(num_docs.shape[-1]), D, G))
        valid = jnp.arange(D, dtype=jnp.int32)[None, :] < num_docs[:, None]
        sel = params["sel"]
        m = sel & valid
        dt = kernels._value_dtype()
        if plan.group_dims:
            ng = plan.num_groups
            keys = jnp.zeros(valid.shape, dtype=jnp.int32)
            for dim, stride in zip(plan.group_dims, plan.group_strides):
                keys = keys + cols["stid:" + dim] * jnp.int32(stride)
            outs = [kernels._scatter_sum(m, jnp.where(m, keys, 0), ng)]
            for op, name in plan.slots:
                if op == "usum":
                    outs.extend(_grouped_usum(cols["sthi:" + name], keys,
                                              m, ng))
                    outs.extend(_grouped_usum(cols["stlo:" + name], keys,
                                              m, ng))
                else:
                    outs.append(kernels._grouped_reduce(
                        op, cols["stval:" + name], keys, sel, valid, ng))
            return jnp.stack(outs, axis=-1)
        parts = [jnp.sum(m, axis=1).astype(dt)[:, None]]
        for op, name in plan.slots:
            if op == "usum":
                parts.append(kernels._isum_u_slot(
                    f"isum:u{USUM_PLANES}", cols["sthi:" + name], m))
                parts.append(kernels._isum_u_slot(
                    f"isum:u{USUM_PLANES}", cols["stlo:" + name], m))
            else:
                parts.append(kernels._masked_reduce(
                    op, cols["stval:" + name], sel, valid)[:, None])
        return jnp.concatenate(parts, axis=1)

    return kernel


@functools.lru_cache(maxsize=256)
def compiled_startree_kernel(plan: StarTreePlan):
    return jax.jit(make_startree_kernel(plan), static_argnames=("D", "G"))


def make_batched_startree_kernel(plan: StarTreePlan, B: int,
                                 stacked: bool = False):
    """Coalesced star-tree launch (mirrors kernels.make_batched_kernel):
    broadcast variant shares one staged block across members (same
    segments, different selection masks — the common dashboard case);
    stacked variant stacks per-member blocks for cross-table members."""
    kind = "startree_batched_stacked" if stacked else "startree_batched"
    base = make_startree_kernel(plan, kind=kind, extra=(B,))
    if stacked:
        def fn(clist, plist, ndlist, D, G=0):
            cs = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *clist)
            ps = kernels.stack_params(plist)
            ns = jnp.stack(ndlist)
            return jax.vmap(lambda c, p, nd: base(c, p, nd, D=D, G=G))(
                cs, ps, ns)
    else:
        def fn(cols, plist, num_docs, D, G=0):
            ps = kernels.stack_params(plist)
            idx = jnp.arange(B, dtype=jnp.int32)
            return jax.vmap(lambda p, _i: base(cols, p, num_docs, D=D, G=G))(
                ps, idx)
    return jax.jit(fn, static_argnames=("D", "G"))


@functools.lru_cache(maxsize=256)
def compiled_batched_startree_kernel(plan: StarTreePlan, B: int,
                                     stacked: bool = False):
    return make_batched_startree_kernel(plan, B, stacked)


# ---------------------------------------------------------------------------
# Host-side planning (fit check + traversal + slot admission)
# ---------------------------------------------------------------------------

def plan_startree(segments, ctx):
    """Fit + plan the device star-tree path for one segment batch.

    Returns (plan, needed, fits, None) when every segment has a fitting
    tree and every pair admits a device slot; (None, None, None, reason)
    otherwise — reason is the `startree_fallback` meter's reason= label
    (disabled | aggregation | groupBy | noTree | fit | filter |
    precision | groups) and the caller falls through to the scan path."""
    if ctx.options.get("useStarTree", "true").lower() == "false":
        return None, None, None, "disabled"
    if ctx.distinct or not ctx.aggregations:
        return None, None, None, "aggregation"
    needed = _agg_pairs_needed(ctx)
    if needed is None:
        return None, None, None, "aggregation"
    group_cols: List[str] = []
    for g in ctx.group_by:
        if not isinstance(g, Identifier):
            return None, None, None, "groupBy"
        group_cols.append(g.name)
    pairs_needed = {p for pairs in needed for p in pairs}

    fits: List[STFit] = []
    filter_missed = False
    for seg in segments:
        reader = getattr(seg, "star_tree", None)
        if reader is None or not reader.trees:
            return None, None, None, "noTree"
        fit = None
        for ti, tree in enumerate(reader.trees):
            tree_pairs = {parse_pair(p) for p in tree.meta.pairs}
            if not pairs_needed <= tree_pairs:
                continue
            if not all(c in tree.meta.dims for c in group_cols):
                continue
            id_sets = _filter_id_sets(seg, ctx.filter, tree.meta.dims)
            if id_sets is None:
                filter_missed = True
                continue
            fit = STFit(ti, tree, tree.traverse(id_sets, set(group_cols)))
            break
        if fit is None:
            return None, None, None, "filter" if filter_missed else "fit"
        fits.append(fit)

    # slot admission per pair: exact int planes when every fitted tree's
    # bounds prove the values fit, f32 for float pairs; int pairs whose
    # bounds overflow a slot fall back (the scan path is exact there)
    slots: List[Tuple[str, str]] = []
    for func, col in sorted(pairs_needed):
        lo, hi, integral = 0.0, 0.0, True
        for f in fits:
            b = f.tree.pair_bounds((func, col))
            lo, hi = min(lo, b[0]), max(hi, b[1])
            integral = integral and b[2]
        name = f"{func}__{col}"
        if func in ("sum", "count"):
            if integral and 0.0 <= lo and hi < USUM_MAX:
                slots.append(("usum", name))
            elif integral:
                return None, None, None, "precision"
            else:
                slots.append(("sum", name))
        else:  # min / max: f32 is exact for ints within +-2^24, and
            # within float tolerance for genuinely-float metrics
            if integral and not (-_F32_EXACT_INT <= lo
                                 and hi <= _F32_EXACT_INT):
                return None, None, None, "precision"
            slots.append((func, name))

    cards: List[int] = []
    strides: List[int] = []
    num_groups = 0
    if group_cols:
        cards = [max(int(seg.data_source(c).metadata.cardinality)
                     for seg in segments) for c in group_cols]
        num_groups = 1
        for c in cards:
            num_groups *= c
        if num_groups > _MAX_GROUPS:
            return None, None, None, "groups"
        strides = [int(np.prod(cards[i + 1:], dtype=np.int64))
                   for i in range(len(cards))]

    plan = StarTreePlan(slots=tuple(slots), group_dims=tuple(group_cols),
                        group_cards=tuple(cards),
                        group_strides=tuple(strides), num_groups=num_groups)
    return plan, needed, fits, None


def staged_columns(plan: StarTreePlan, value_dtype):
    """[(kernel col key, fetch form, np dtype)] the engine stages as
    pseudo-column blocks; `fetch_row` materializes one segment's row."""
    out = []
    for op, name in plan.slots:
        if op == "usum":
            out.append(("sthi:" + name, ("hi", name), np.int32))
            out.append(("stlo:" + name, ("lo", name), np.int32))
        else:
            out.append(("stval:" + name, ("val", name), value_dtype))
    for d in plan.group_dims:
        out.append(("stid:" + d, ("id", d), np.int32))
    return out


def fetch_row(tree, form, value_dtype) -> np.ndarray:
    """One tree's raw pre-agg row for a staged-column form."""
    kind, name = form
    if kind == "id":
        return np.ascontiguousarray(tree.dim_codes[name], dtype=np.int32)
    v = tree.metrics[tuple(name.split("__", 1))]
    if kind == "val":
        return v.astype(value_dtype)
    vi = v.astype(np.int64)
    if kind == "hi":
        return (vi >> 24).astype(np.int32)
    return (vi & 0xFFFFFF).astype(np.int32)


def selection_mask(fits: List[STFit], S: int, D: int) -> np.ndarray:
    """[S, D] bool params block from per-segment traversal results."""
    sel = np.zeros((S, D), dtype=bool)
    for i, f in enumerate(fits):
        sel[i, f.recs] = True
    return sel


# ---------------------------------------------------------------------------
# Host-side assembly (mirrors query/startree_exec._whole/_grouped)
# ---------------------------------------------------------------------------

def _slot_layout(plan: StarTreePlan) -> Dict[str, Tuple[int, str]]:
    offs: Dict[str, Tuple[int, str]] = {}
    off = 1  # index 0 is the matched/record-count column
    for op, name in plan.slots:
        offs[name] = (off, op)
        off += slot_width(op)
    return offs


def _usum_value(planes) -> int:
    """Reconstruct the exact integer sum from a usum slot's 16 plane
    halves (hi digit planes then lo digit planes) in python ints."""
    def digit(p):
        total = 0
        for k in range(USUM_PLANES):
            s = int(round(float(p[2 * k]))) * 4096 \
                + int(round(float(p[2 * k + 1])))
            total += s << (kernels.ISUM_U_BITS * k)
        return total
    half = 2 * USUM_PLANES
    return (digit(planes[:half]) << 24) + digit(planes[half:])


def assemble(segments, ctx, plan: StarTreePlan, needed, fits, packed):
    """Per-segment results from the packed kernel output — value-exact
    mirror of the host star-tree executor (types included: count int,
    sum/min/max float, avg (float, int) intermediates)."""
    packed = np.asarray(packed)
    layout = _slot_layout(plan)
    results = []
    for s, seg in enumerate(segments):
        if plan.group_dims:
            results.append(_assemble_group(seg, ctx, plan, needed, layout,
                                           np.asarray(packed[s],
                                                      dtype=np.float64)))
        else:
            results.append(_assemble_flat(seg, ctx, plan, needed, layout,
                                          np.asarray(packed[s],
                                                     dtype=np.float64)))
    return results


def _agg_value(fn_name: str, pairs, get):
    """One aggregation's intermediate from slot values (host parity:
    startree_exec._whole / _grouped element types)."""
    if fn_name == "count":
        return int(get(("count", "*")))
    if fn_name == "avg":
        return (float(get(pairs[0])), int(get(("count", "*"))))
    return float(get(pairs[0]))  # sum / min / max


def _slot_get(layout, row, pair):
    off, op = layout[f"{pair[0]}__{pair[1]}"]
    if op == "usum":
        return _usum_value(row[off:off + USUM_WIDTH])
    return float(row[off])


def _assemble_flat(seg, ctx, plan, needed, layout, row):
    matched = int(round(float(row[0])))
    stats = ExecutionStats(
        num_docs_scanned=matched, num_segments_processed=1,
        num_segments_matched=1 if matched else 0, total_docs=seg.num_docs)
    inters = [_agg_value(fn.name, needed[i],
                         lambda pair: _slot_get(layout, row, pair))
              for i, fn in enumerate(ctx.aggregations)]
    return AggregationResult(inters, stats)


def _assemble_group(seg, ctx, plan, needed, layout, arr):
    cnt = arr[:, 0]
    present = np.nonzero(cnt > 0.5)[0]
    matched = int(round(float(cnt.sum())))
    stats = ExecutionStats(
        num_docs_scanned=matched, num_segments_processed=1,
        num_segments_matched=1 if matched else 0, total_docs=seg.num_docs)
    dicts = [seg.data_source(c).dictionary for c in plan.group_dims]
    cards = [int(seg.data_source(c).metadata.cardinality)
             for c in plan.group_dims]
    groups: Dict[tuple, list] = {}
    for g in present:
        rem = int(g)
        ids = []
        for stride in plan.group_strides:
            ids.append(rem // stride)
            rem = rem % stride
        if any(i >= c for i, c in zip(ids, cards)):
            continue  # radix-padding key outside this segment's dict
        key = tuple(_py(d.get_value(ids[j])) for j, d in enumerate(dicts))
        row = arr[g]
        groups[key] = [_agg_value(fn.name, needed[i],
                                  lambda pair: _slot_get(layout, row, pair))
                       for i, fn in enumerate(ctx.aggregations)]
    return GroupByResult(groups, stats)


def _py(v):
    return v.item() if isinstance(v, np.generic) else v
