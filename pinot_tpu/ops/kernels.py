"""jit'd device kernels built from a DevicePlan.

The kernel computes, for stacked segment blocks [S, D]:
  mask  = filter tree over dictId compares / LUT gathers     (VPU, fused)
  vals  = dictionary-value gathers + arithmetic              (fused)
  out   = masked reductions (sum/min/max/count/sumsq) or per-group
          partials: additive slots as a one-hot matmul on the MXU (one
          level to ONEHOT_MAX_GROUPS groups, factored hi x lo above, all
          slots in one pass), min/max and whatever `group_path` sends
          there as an XLA scatter
returning per-segment partials — the host (or a psum over the mesh) merges.

Everything is shape-static: jit re-specializes per (S, D, C, G) bucket and
the engine pads inputs to bucketed sizes to bound recompiles
(SURVEY.md §7 hard-parts note on retrace storms).
"""
from __future__ import annotations

import functools
import hashlib
import threading
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pinot_tpu.ops import clp_device, timeseries_device
from pinot_tpu.ops.plan_ir import (
    NUM_DOCS, PACK, DeviceLeaf, DevicePlan, pack_layout,
)

# group-by cardinality up to which a grouped sum is ONE one-hot matmul a
# slot (f32 `one_hot` x einsum over chunks of docs); `group_path` decides
ONEHOT_MAX_GROUPS = 1024
_ONEHOT_CHUNK = 4096
# above it the key is factored, k = hi * _ONEHOT2_LANES + lo, and all
# additive slots of a plan ride one bf16 [P*H, T] @ [T, L] product a tile
# of docs (`_onehot2_sums`). One-hot work grows with G and the scatter's
# does not, so past ONEHOT2_MAX_GROUPS the scatter wins again. The sweep
# (one v5e, 16 x 8M rows, COUNT + SUM = 4 planes, ms a pass, PR 28):
#   G        2,048  7,000 16,384 32,768 65,536 131,072 262,144 524,288
#   onehot2   24.4   52.3  105.2  199.7  388.9   767.8 1,529.8 3,049.7
#   scatter 2,072.8 1,873.5 1,868.0 1,861.1 1,857.9 1,856.4 3,106.2 3,106.2
# (the scatter at 1,048,576: 3,106.7). The pass is linear in G, 41.7 of
# its 52.3 ms at G = 7,000 in the kernel (about 90% of the MXU's bf16 peak),
# and crosses the scatter near 530,000. The bound sits where it still
# wins 2x, with room for a plan of more planes: its work grows with P,
# the scatter's with slots. The sweep kept every row: the scatter hands
# XLA only the rows the filter keeps (`_compacting_slots`), so its cost
# is the kept rows' and the crossing near 530,000 holds for an
# unfiltered GROUP BY alone; a selective one leaves the scatter cheaper
# well below the bound, which stays where the one-hot pass is sure.
ONEHOT2_MAX_GROUPS = 1 << 18
_ONEHOT2_LANES = 128
_ONEHOT2_CHUNK = 8192          # docs a segment under which: scatter
_ONEHOT2_TILE_ELEMS = 1 << 21  # left-operand elements of one matmul
_ONEHOT2_VMEM_BYTES = 64 << 20

# a `scatter` GROUP BY hands XLA's scatter-add each segment's first `cap`
# kept rows, cap the smallest rung that holds the launch's largest kept
# count (`compact_rung`), where a shard has COMPACT_MIN_DOCS docs or more:
# under it the whole scatter is a few ms and the ladder's three more
# compiled branches are not worth their compile
COMPACT_MIN_DOCS = 1 << 16
_COMPACT_SHIFTS = (9, 6, 3)   # the rungs: D / 512, D / 64, D / 8
_COMPACT_LANES = 128          # rows a tile of the count tree
_COMPACT_WALK_ELEMS = 1 << 25  # [S, slots, lanes] elements a walk step
_KEY = "key"                  # the group key among the compacted columns

# ---------------------------------------------------------------------------
# trace (recompile) accounting: kernel bodies run at TRACE time only, so a
# counter bumped inside them counts XLA compilations, not dispatches. The
# dispatch ring meters the delta as `kernel_retrace` — steady-state traffic
# over warmed shape buckets must keep this flat (a growing count means a
# shape/bucket leak re-compiling the hot path).
#
# Each trace also lands in a bounded log (`trace_log()`) carrying the
# kernel kind, the plan fingerprint, and the shape bucket — so a retrace
# storm is attributable from metrics alone: the per-plan-fingerprint
# `kernel_retrace{plan=...}` label says WHICH plan is churning, and the
# log says WHICH shape buckets it churned through.
# ---------------------------------------------------------------------------
_trace_lock = threading.Lock()
_trace_count = 0
_TRACE_LOG_MAX = 256
_trace_log: "deque" = deque(maxlen=_TRACE_LOG_MAX)
_trace_by_plan: Dict[str, int] = {}


# lint: impure(the compile odometer is DELIBERATELY trace-time-impure: it runs once per trace to count retraces, mutates only under _trace_lock, and contributes nothing to the traced computation)
def note_trace(kind: str = "kernel", plan_fp: str = "",
               bucket: tuple = ()) -> None:
    global _trace_count
    with _trace_lock:
        _trace_count += 1
        if plan_fp:
            _trace_by_plan[plan_fp] = _trace_by_plan.get(plan_fp, 0) + 1
        _trace_log.append({"seq": _trace_count, "kind": kind,
                           "plan": plan_fp, "bucket": tuple(bucket)})


def trace_count() -> int:
    with _trace_lock:
        return _trace_count


def trace_count_by_plan() -> Dict[str, int]:
    """Compile count per plan fingerprint (snapshot)."""
    with _trace_lock:
        return dict(_trace_by_plan)


def trace_log(n: Optional[int] = None) -> List[dict]:
    """The last `n` (default: all retained) compiles, oldest first:
    {seq, kind, plan, bucket} — kind names the kernel variant
    ('agg'/'topn'/'sharded'/'batched'/'batched_stacked'/...), plan is
    plan_fingerprint(), bucket is the traced shape key (B, S, D, G as
    applicable). Feeds retrace-storm forensics without a debugger."""
    with _trace_lock:
        entries = list(_trace_log)
    return entries[-n:] if n is not None else entries


@functools.lru_cache(maxsize=4096)
def plan_fingerprint(plan: DevicePlan) -> str:
    """Short stable id of a plan STRUCTURE (not its literals): the label
    kernels compile under, and the `plan` label on the kernel_retrace
    meter. repr() of the frozen dataclass is deterministic and total."""
    text = repr(plan) + ("+nonfinite" if getattr(plan, "nonfinite", False)
                         else "")
    if getattr(plan, "group_fold", ()):
        text += f"+fold{plan.group_fold}"
    return hashlib.sha1(text.encode()).hexdigest()[:12]


def _named(fn, name: str):
    """Name `fn` before jax.jit: the XLA module, and with it the device
    trace's `XLA Modules` line and every op under it, reads
    `jit_<name>`. Names are note_trace's kind + the plan fingerprint
    (`agg_<fp>`, `batched_b4_stacked_<fp>`, ...), so the /debug trace
    log, `kernel_retrace_by_plan` and a profile agree on one name."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _value_dtype() -> jnp.dtype:
    return jnp.float64 if jax.config.read("jax_enable_x64") else jnp.float32


@functools.lru_cache(maxsize=1024)
def compiled_row_assembler(S: int, D: int, row_lens: Tuple[int, ...],
                           dtype_str: str):
    """jit'd ON-DEVICE assembly of per-segment resident rows into the
    kernel-ready [S, D] stacked block (ops/residency.py): each row is a
    [Dr_i] device array padded to its segment's own pow2 doc bucket, so
    assembly is a zero-fill plus one dynamic_update_slice per row — HBM
    traffic only, never the host link. One compile per (S, D, row-length
    tuple, dtype) shape; row lengths are pow2 buckets, so the cache stays
    small and steady-state traffic (which hits the assembled-block cache
    and never re-assembles) compiles nothing."""
    dtype = jnp.dtype(dtype_str)

    def assemble(rows):
        note_trace("assembler", bucket=(S, D))
        if len(rows) == S and all(ln == D for ln in row_lens):
            return jnp.stack(rows)
        out = jnp.zeros((S, D), dtype=dtype)
        for i, r in enumerate(rows):
            out = jax.lax.dynamic_update_slice(out, r[None, :], (i, 0))
        return out

    return jax.jit(_named(assemble, f"assemble_s{S}"))


# ---------------------------------------------------------------------------
# packed parameters (ops/plan_ir.py `pack_layout` / `pack_params`)
# ---------------------------------------------------------------------------

def unpack_params(plan, params, num_docs=None):
    """(params, num_docs) as the kernel bodies read them. Params staged
    with the packed [K, S] int32 array (`PACK`: the engine's scan and
    top-N legs, the vector leg's residual filter) come back as the named
    [S] arrays, each a slice at a static row, floats bit-cast back to
    the value dtype (a float64 from its two word rows), and `num_docs`
    is row 0 unless the caller brought its own. Per-array params pass
    through untouched. Rank-agnostic: a stacked [B, K, S] pack gives
    [B, S] arrays."""
    pack = params.get(PACK)
    if pack is None:
        return params, num_docs
    out = {k: v for k, v in params.items() if k != PACK}
    dt = _value_dtype()
    width = jnp.dtype(dt).itemsize // 4
    row = 0
    for name, kind in pack_layout(plan):
        if kind == "i":
            out[name] = pack[..., row, :]
            row += 1
        elif width == 1:
            out[name] = jax.lax.bitcast_convert_type(pack[..., row, :], dt)
            row += 1
        else:
            words = jnp.moveaxis(pack[..., row:row + width, :], -2, -1)
            out[name] = jax.lax.bitcast_convert_type(words, dt)
            row += width
    packed_docs = out.pop(NUM_DOCS)
    return out, packed_docs if num_docs is None else num_docs


def unpack_batch(plan, plist, num_docs, stacked: bool):
    """The mesh kernels' way in, OUTSIDE shard_map (so the rows keep
    their [.., S] specs): a batch's params (`plan_ir.batch_params`)
    stacked and unpacked, and the batch's num_docs: the members' own
    stacked ([B, S]) where their blocks differ, else the one [S] they
    share (packed: member 0's row, every member having staged the same
    segments)."""
    ps, packed_docs = unpack_params(plan, stack_params(plist))
    if stacked:
        ns = stack_members(num_docs)
        return ps, packed_docs if ns is None else ns
    return ps, packed_docs[0] if num_docs is None else num_docs


def stack_params(plist):
    """Inside the jit: a batch's params (`plan_ir.batch_params`: the
    packs one host-stacked [B, K, S] array, what is on the device a
    tuple of the B members' arrays) with every leaf [B, ...]."""
    return {k: jnp.stack(v) if isinstance(v, tuple) else v
            for k, v in plist.items()}


def stack_members(members):
    """B members' staged pytrees (column dicts, num_docs arrays, or the
    None a packed launch brings for num_docs) stacked leaf for leaf
    along a new leading axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *members)


# ---------------------------------------------------------------------------
# IR evaluation (runs at trace time)
# ---------------------------------------------------------------------------

def _eval_filter(node, plan: DevicePlan, cols: Dict[str, jnp.ndarray],
                 params: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    op = node[0]
    if op == "and":
        out = _eval_filter(node[1], plan, cols, params)
        for child in node[2:]:
            out = out & _eval_filter(child, plan, cols, params)
        return out
    if op == "or":
        out = _eval_filter(node[1], plan, cols, params)
        for child in node[2:]:
            out = out | _eval_filter(child, plan, cols, params)
        return out
    if op == "not":
        return ~_eval_filter(node[1], plan, cols, params)
    assert op == "leaf"
    i = node[1]
    leaf = plan.leaves[i]
    if leaf.kind == "range":
        ids = cols["ids:" + leaf.column]
        lo = _clamp_to(params[f"leaf{i}:lo"], ids.dtype)[:, None]
        hi = _clamp_to(params[f"leaf{i}:hi"], ids.dtype)[:, None]
        return (ids >= lo) & (ids <= hi)
    if leaf.kind == "neq":
        ids = cols["ids:" + leaf.column]
        idx = params[f"leaf{i}:idx"]
        if ids.dtype != idx.dtype:
            # -1 and in-range ids fit any narrow id dtype
            idx = jnp.clip(idx, jnp.iinfo(ids.dtype).min,
                           jnp.iinfo(ids.dtype).max).astype(ids.dtype)
        return ids != idx[:, None]
    if leaf.kind == "lut":
        ids = cols["ids:" + leaf.column]
        table = params[f"leaf{i}:lut"]  # [S, C] bool
        return jnp.take_along_axis(table, ids, axis=1)
    if leaf.kind == "vrange":
        vals = cols["val:" + leaf.column]
        lo = params[f"leaf{i}:lo"][:, None]
        hi = params[f"leaf{i}:hi"][:, None]
        return (vals >= lo) & (vals <= hi)
    if leaf.kind == "vrange64":
        # exact closed-interval compare on (hi, lo) i32 split planes:
        # lexicographic (hi strictly dominates; lo always in [0, 2^24))
        vhi = cols["valhi:" + leaf.column]
        vlo = cols["vallo:" + leaf.column]
        a_hi = params[f"leaf{i}:lohi"][:, None]
        a_lo = params[f"leaf{i}:lolo"][:, None]
        b_hi = params[f"leaf{i}:hihi"][:, None]
        b_lo = params[f"leaf{i}:hilo"][:, None]
        ge = (vhi > a_hi) | ((vhi == a_hi) & (vlo >= a_lo))
        le = (vhi < b_hi) | ((vhi == b_hi) & (vlo <= b_lo))
        return ge & le
    if leaf.kind == "clp":
        # LIKE/regex over a CLP log column: candidate-logtype LUT plus
        # variable-slot conditions (ops/clp_device.py)
        return clp_device.eval_leaf(i, leaf, cols, params)
    raise ValueError(f"unknown leaf kind {leaf.kind}")


def _clamp_to(arr, dtype):
    """Compare-bound params clamp into a narrow id dtype so comparisons
    run at the block's native width (an out-of-range sentinel like
    2^31-1 clamps to 'matches everything', preserving semantics)."""
    if arr.dtype == dtype:
        return arr
    info = jnp.iinfo(dtype)
    return jnp.clip(arr, info.min, info.max).astype(dtype)


def _eval_value(ir, cols: Dict[str, jnp.ndarray],
                params: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    op = ir[0]
    if op == "col":
        # value columns are always staged as materialized [S, D] blocks
        # (engine stages dictionary takes host-side; in-kernel gathers
        # measured ~8x slower on TPU)
        return cols["val:" + ir[1]]
    if op == "ids":
        return cols["ids:" + ir[1]]
    if op == "lit":
        return jnp.asarray(ir[1], dtype=_value_dtype())
    a = _eval_value(ir[1], cols, params)
    if op == "neg":
        return -a
    b = _eval_value(ir[2], cols, params)
    if op == "add":
        return a + b
    if op == "sub":
        return a - b
    if op == "mul":
        return a * b
    if op == "div":
        return a / b
    raise ValueError(f"unknown value ir op {ir[0]}")


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------

def _masked_reduce(op: str, vals: Optional[jnp.ndarray], mask: jnp.ndarray,
                   valid: jnp.ndarray) -> jnp.ndarray:
    """[S, D] -> [S] masked reduction. `valid` excludes padding docs."""
    m = mask & valid
    dt = _value_dtype()
    if op == "count":
        return jnp.sum(m, axis=1).astype(dt)
    assert vals is not None
    if op == "sum":
        return jnp.sum(jnp.where(m, vals, 0), axis=1, dtype=dt)
    if op == "sumsq":
        return jnp.sum(jnp.where(m, vals * vals, 0), axis=1, dtype=dt)
    if op == "sum3":
        return jnp.sum(jnp.where(m, vals * vals * vals, 0), axis=1, dtype=dt)
    if op == "sum4":
        v2 = vals * vals
        return jnp.sum(jnp.where(m, v2 * v2, 0), axis=1, dtype=dt)
    if op == "min":
        return jnp.min(jnp.where(m, vals, jnp.inf), axis=1)
    if op == "max":
        return jnp.max(jnp.where(m, vals, -jnp.inf), axis=1)
    raise ValueError(f"unknown reduction {op}")


#: additive slot ops -> the power of the value a row contributes
_ADDITIVE = {"count": 0, "sum": 1, "sumsq": 2, "sum3": 3, "sum4": 4}


def group_path(num_groups: int, docs: int, dtype, finite: bool) -> str:
    """How a grouped sum over `docs` docs a segment into `num_groups`
    groups runs — 'onehot' | 'onehot2' | 'scatter' — from static shapes
    alone. The ONE place that decides: `_compute_slots` and
    `_scatter_sum` build what it says, and the engine writes the same
    word on the DeviceDispatch span (`groupPath`), so they cannot drift.

    dtype: the contributions' (the factored path splits an f32 into
    three bf16 terms; under jax_enable_x64 the f64 sums keep the
    scatter). finite: no contribution can be Inf/NaN — in a one-hot
    product 0 * Inf = NaN reaches every group of the tile, where a
    scatter touches only its own (`DevicePlan.nonfinite`). docs under
    the chunk (a small doc shard) keep the scatter, as ever."""
    if num_groups <= ONEHOT_MAX_GROUPS:
        return "onehot" if docs >= _ONEHOT_CHUNK else "scatter"
    if num_groups <= ONEHOT2_MAX_GROUPS and docs >= _ONEHOT2_CHUNK \
            and finite and jnp.dtype(dtype) == jnp.float32:
        return "onehot2"
    return "scatter"


def scatter_rows(plan: DevicePlan, num_groups: int, S: int, D: int,
                 docs: int, cap: int = 0) -> int:
    """Rows x additive slots a grouped launch over [S, D] hands to XLA's
    scatter-add (padding included), routed as `_compute_slots` and
    `_scatter_sum` route them: none where `group_path` sends every
    additive slot to `onehot2`; on `onehot` the docs past its last whole
    chunk; on `scatter` each shard's `cap` compacted rows a segment, or
    its whole docs where cap is 0 (the full scatter). docs: one shard's,
    as `group_path` is asked; cap: the rung the launch ran, which the
    device chose from the kept counts (`compact_cap`)."""
    dt = _value_dtype()
    if group_path(num_groups, docs, dt, finite=not plan.nonfinite) \
            == "onehot2":
        return 0
    slots = sum(1 for op, _v, _f in plan.agg_ops if op in _ADDITIVE)
    if group_path(num_groups, docs, dt, finite=False) == "onehot":
        return S * (D // docs) * (docs % _ONEHOT_CHUNK) * slots
    return S * (D // docs) * (cap or docs) * slots


def compacts(plan: DevicePlan, num_groups: int, docs: int) -> bool:
    """Whether a grouped launch whose shards hold `docs` docs a segment
    compacts its kept rows before the scatter: its path is `scatter`
    (`group_path`, the one place that decides) and the shard has rungs."""
    return bool(num_groups) and bool(compact_rungs(docs)) and group_path(
        num_groups, docs, _value_dtype(),
        finite=not plan.nonfinite) == "scatter"


def compact_rungs(docs: int) -> Tuple[int, ...]:
    """The capacities a segment's kept rows are compacted to, smallest
    first: () where a shard of `docs` docs does not compact (under
    COMPACT_MIN_DOCS, or not the power of two every doc bucket is)."""
    if docs < COMPACT_MIN_DOCS or docs & (docs - 1):
        return ()
    return tuple(docs >> s for s in _COMPACT_SHIFTS)


def compact_rung(docs: int, most_kept):
    """The index into `compact_rungs(docs)` of the smallest rung that
    holds `most_kept` rows, len(rungs) past the top one (the full
    scatter). The ONE function that chooses: the kernel calls it on a
    traced count (its `lax.switch` index), the engine on the counts the
    fetched result carries (`compact_cap`)."""
    rung = 0
    for cap in compact_rungs(docs):
        rung = rung + (most_kept > cap)
    return rung


def compact_cap(docs: int, most_kept: int) -> int:
    """The rows a segment a launch handed the scatter: the rung
    `compact_rung` chose, 0 for the full scatter."""
    rungs = compact_rungs(docs)
    i = int(compact_rung(docs, most_kept))
    return rungs[i] if i < len(rungs) else 0


def group_fold(plan: DevicePlan, out_groups: int = 0, remap_bytes: int = 0,
               max_groups: int = 0, max_remap_bytes: int = 0) -> str:
    """Where a GROUP BY's per-segment partials become one result —
    'device' (`fold_groups`, inside the kernel: [G, slots] leaves the
    device) | 'host' ([S, G, slots] leaves it and the engine builds a
    result a segment) — from the plan and static sizes alone. The ONE
    place that decides, as `group_path` is for the path: the engine asks
    with the global key space its remap came to and the bytes of its
    tables, against the caps it already holds every device group table
    and every remap to (`MAX_DEVICE_GROUPS`, `GMAP_MAX_BYTES`), and
    writes the word on the DeviceDispatch span (`groupFold`). A key
    space or a compacted plan's [S, G] table past the caps would cost
    more than the partials it saves. A fused time bucket folds like any
    other key: its digit means the same in every segment of a launch."""
    if out_groups > max_groups or remap_bytes > max_remap_bytes:
        return "host"
    return "device"


def _two_sum(a, b):
    """a + b as (the rounded sum, what the rounding lost): Knuth's
    TwoSum, exact for finite floats whatever their order."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _sum_segments(x: jnp.ndarray, chips: Optional[str] = None) -> jnp.ndarray:
    """[..., S, G] float partials -> [..., G], summed over the segment
    axis as a pairwise tree that carries each addition's rounding error
    and adds the errors back once at the root: the result is the exact
    sum rounded once (to a second-order term), where a plain f32 sum of
    sixteen partials loses up to sixteen roundings (the host fold it
    replaces added them in f64). A non-finite partial gives the plain
    sum (inf - inf in the error term would read NaN).

    chips: the mesh axis the segments are sharded over, inside a
    shard_map (`fold_groups`): x holds this chip's segments, the tree
    runs over them, and the chips' roots and carried errors are added by
    ONE all-reduce each, the exchange between chips. Those last
    additions (three on four chips) round in f32 and are not carried."""
    S = x.shape[-2]
    pad = (1 << max(S - 1, 0).bit_length()) - S
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-2] + (pad, x.shape[-1]), x.dtype)],
            axis=-2)
    err = jnp.zeros_like(x)
    while x.shape[-2] > 1:
        half = x.shape[-2] // 2
        pair = x.reshape(x.shape[:-2] + (half, 2, x.shape[-1]))
        epair = err.reshape(pair.shape)
        x, lost = _two_sum(pair[..., 0, :], pair[..., 1, :])
        err = epair[..., 0, :] + epair[..., 1, :] + lost
    total, err = x[..., 0, :], err[..., 0, :]
    if chips:
        total, err = jax.lax.psum((total, err), chips)
    return jnp.where(jnp.isfinite(total) & jnp.isfinite(err),
                     total + err, total)


def fold_groups(plan: DevicePlan, slots, params, mesh=None) -> jnp.ndarray:
    """[S, G] per-segment partials a slot -> ONE packed integer row
    [n_slots * G_out + S]: the [n_slots, G_out] group table over the
    GLOBAL key space `plan.group_fold`, a slot after the other (slots
    interleaved a group, `stack(axis=-1).reshape(-1)`, compiled for 66 s
    at 131,072 groups on the v5e against under 1 s for this), then each
    segment's matched count (the ExecutionStats the host fold would have
    summed).

    Dictionary ids and compacted codes are segment-local, so a global
    group reads each segment's partial through the engine's remap
    (`_group_remap`): a dense plan's `ginv<i>`
    [S, U_i] tables give column i's local id of a union value (-1: the
    segment's dictionary lacks it), recombined with the plan's own
    strides; a compacted plan's `ginv` [S, G_out] gives the local code.
    A gather a segment, then a reduction over the segment axis. A fused
    time bucket, the key's lowest digit (`plan.group_fold`'s last), is
    the same bucket in every segment (start and step are the query's):
    it is carried as it is, with no table.

    mesh: the segments mesh the partials and the tables are sharded
    over. Each chip then folds its own segments (`_fold_shard` under a
    shard_map: the gathers never leave the chip) and the chips' rows
    are added by all-reduces, after which every chip holds the whole
    row. Left to GSPMD the same body compiled, for a v5e:2x2, to an
    all-reduce, three all-to-alls, nine collective-permutes and an
    all-gather of the answer.

    Exactness: a count is an integer under 2^24 a segment (f32-exact)
    and is summed as an integer, so a table past 2^24 rows a group
    stays exact; sums add in the value dtype with the roundings carried
    (`_sum_segments`); min / max fold with min / max. The row's dtype is
    the integer as wide as the value dtype: counts ride it as integers,
    every other slot bit-cast."""
    names = ["ginv"] if plan.group_compact \
        else [f"ginv{ci}" for ci in range(len(plan.group_cols))]
    arrs = [s for _op, s in slots]
    tables = [params[n] for n in names]
    if mesh is None:
        return _fold_shard(plan, None, arrs, tables)
    seg = jax.sharding.PartitionSpec("segments")
    return jax.shard_map(
        functools.partial(_fold_shard, plan, "segments"), mesh=mesh,
        in_specs=(seg, seg), out_specs=jax.sharding.PartitionSpec(),
    )(arrs, tables)


def _fold_shard(plan: DevicePlan, chips: Optional[str], arrs,
                tables) -> jnp.ndarray:
    """`fold_groups` over the segments at hand: all of them (chips
    None), or one chip's share inside a shard_map over the mesh axis
    `chips`, where every reduction over segments ends in its
    all-reduce."""
    bits = jnp.int64 if _value_dtype() == jnp.float64 else jnp.int32
    if plan.group_compact:
        idx, = tables
        there = idx >= 0
        idx = jnp.where(there, idx, 0)

        def globally(s):
            return jnp.take_along_axis(s, idx, axis=-1)
    else:
        # a dense key is mixed radix over the plan's own strides, so a
        # segment's [G] partials are a [C_0, .., C_k] block: each digit
        # is gathered along its own axis through its column's table,
        # whole rows at a time (an element-wise gather over an iota of
        # the key space compiled for a minute at 131,072 groups: XLA
        # folded the iota's divisions as constants). The last axis is
        # the fused time bucket's digit (1 without one), never remapped
        k = len(plan.group_cols)
        local = [(plan.num_groups if ci == 0 else plan.group_strides[ci - 1])
                 // plan.group_strides[ci] for ci in range(k)]
        local.append(plan.group_strides[-1] if k else plan.num_groups)
        S = arrs[0].shape[0]
        there = True
        for ci, ids in enumerate(tables):
            there = there & (ids >= 0).reshape(
                (S,) + (1,) * ci + (-1,) + (1,) * (k - ci))
        there = jnp.broadcast_to(
            there, (S,) + tuple(plan.group_fold[:k]) + (local[-1],)
        ).reshape(S, -1)

        def globally(s):
            x = s.reshape((S,) + tuple(local))
            for ci, ids in enumerate(tables):
                x = jax.vmap(lambda xs, i, _a=ci: jnp.take(
                    xs, i, axis=_a, mode="clip"))(
                        x, jnp.maximum(ids, 0))
            return x.reshape(S, -1)

    def across(f, reduce):
        return reduce(f, chips) if chips else f

    folded = []
    matched = None
    for (op, _vidx, fidx), s in zip(plan.agg_ops, arrs):
        g = globally(s)
        if op == "count":
            f = across(jnp.sum(jnp.where(there, g, 0).astype(bits), axis=-2),
                       jax.lax.psum)
            if fidx is None and matched is None:
                # every matched doc lands in exactly one group
                matched = jnp.sum(s.astype(bits), axis=-1)
        elif op == "min":
            f = across(jnp.min(jnp.where(there, g, jnp.inf), axis=-2),
                       jax.lax.pmin)
        elif op == "max":
            f = across(jnp.max(jnp.where(there, g, -jnp.inf), axis=-2),
                       jax.lax.pmax)
        else:
            f = _sum_segments(jnp.where(there, g, 0), chips)
        folded.append(f if f.dtype == bits
                      else jax.lax.bitcast_convert_type(f, bits))
    if chips:
        # [S] from the chips' [S / n]: each lays its own stretch into
        # zeros and the all-reduce fills in the others'
        mine = jnp.arange(jax.lax.axis_size(chips))[:, None] \
            == jax.lax.axis_index(chips)
        matched = jax.lax.psum(
            jnp.where(mine, matched[None, :], 0).reshape(-1), chips)
    return jnp.concatenate(folded + [matched], axis=-1)


def _contribution(op: str, vals: Optional[jnp.ndarray],
                  m: jnp.ndarray) -> jnp.ndarray:
    """What each row adds to an additive slot, 0 where masked out. A
    count's stays bool: one exact bf16 plane on the factored path."""
    if op == "count":
        return m
    assert vals is not None
    if op == "sum":
        v = vals
    elif op == "sumsq":
        v = vals * vals
    elif op == "sum3":
        v = vals * vals * vals
    else:
        v2 = vals * vals
        v = v2 * v2
    return jnp.where(m, v, 0).astype(_value_dtype())


def _grouped_reduce(op: str, vals: Optional[jnp.ndarray], keys: jnp.ndarray,
                    mask: jnp.ndarray, valid: jnp.ndarray,
                    num_groups: int, mesh=None) -> jnp.ndarray:
    """[S, N] + keys [S, N] -> [S, G] per-group partials, one slot, over
    the rows it is given: an additive slot by `_scatter_sum`'s path, MIN
    and MAX by XLA's scatter; a masked row adds 0 (MIN / MAX: their
    identity) at key 0. A `scatter` GROUP BY of a large shard hands it
    its kept rows alone (`_compacting_slots`): N is then the rung's
    capacity, not the segment's docs, and the scatter costs what the
    filter keeps."""
    m = mask & valid
    safe_keys = jnp.where(m, keys, 0)
    if op in _ADDITIVE:
        contrib = _contribution(op, vals, m).astype(_value_dtype())
        return _scatter_sum(contrib, safe_keys, num_groups, mesh)
    if op == "min":
        init = jnp.full((vals.shape[0], num_groups), jnp.inf, dtype=vals.dtype)
        v = jnp.where(m, vals, jnp.inf)
        return _vmap_scatter(init, safe_keys, v, "min")
    if op == "max":
        init = jnp.full((vals.shape[0], num_groups), -jnp.inf, dtype=vals.dtype)
        v = jnp.where(m, vals, -jnp.inf)
        return _vmap_scatter(init, safe_keys, v, "max")
    raise ValueError(f"unknown grouped reduction {op}")


def _lane_prefix(x: jnp.ndarray) -> jnp.ndarray:
    """[..., L] of small counts (each at most L, so exact in bf16) -> the
    inclusive prefix along the last axis, int32: one product with a 0/1
    triangle on the MXU, exact in its f32 accumulation."""
    L = x.shape[-1]
    lanes = jnp.arange(L)
    tri = (lanes[:, None] <= lanes[None, :]).astype(jnp.bfloat16)
    return jnp.einsum("...l,lm->...m", x.astype(jnp.bfloat16), tri,
                      preferred_element_type=jnp.float32).astype(jnp.int32)


def _kept_tree(kept: jnp.ndarray):
    """The count tree of a [S, D] kept mask (D a power of two, at least
    COMPACT_MIN_DOCS): (tiles [S, D / L, L], the mask as rows of L;
    within [S, D / L^2, L] int32, the inclusive prefix of the tiles'
    counts within each group of L tiles; top [S, D / L^2] int32, the
    inclusive prefix of the groups' counts, whose last column is the
    segment's kept count). One reduction over the lanes of the mask, the
    rest on counts: no sort, no scatter, no prefix over a whole
    segment."""
    S, D = kept.shape
    L = _COMPACT_LANES
    tiles = kept.reshape(S, D // L, L)
    count = jnp.sum(tiles, axis=-1, dtype=jnp.int32)          # [S, D / L]
    within = _lane_prefix(count.reshape(S, -1, L))
    return tiles, within, jnp.cumsum(within[:, :, -1], axis=1)


def _rows_at(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """table [S, n, w], idx [S, c] -> [S, c, w]: whole rows of each
    segment's own table (a gather of w-wide slices)."""
    return jax.vmap(lambda t, i: t[i])(table, idx)


def _kept_rows(tree, cap: int, cols):
    """({name: [S, cap]} each [S, D] column of `cols` at each segment's
    r-th kept row for r < cap, in order; live [S, cap] bool, whether the
    segment has an r-th row: a slot past its count holds row 0's values,
    and is not live).

    Each slot walks the count tree down: the groups' prefix, compared
    with its rank, says which group of L tiles holds the row, and the
    rank drops by the rows before it; that group's row of tile prefixes
    says the tile, and the tile's own L rows of the mask, as a prefix,
    the lane. Every column is then read as the same tile's L-wide row and
    that lane picked out (on the v5e a row gather costs what a gather of
    one element does, ~10 ns an index; `take_along_axis` over [S, D] took
    twice that). In chunks that keep a step's [S, chunk, L] under
    _COMPACT_WALK_ELEMS."""
    tiles, within, top = tree
    S = top.shape[0]
    L = _COMPACT_LANES
    chunk = min(cap, max(L, _pow2_floor(_COMPACT_WALK_ELEMS // (S * L))))
    rows = {k: v.reshape(S, -1, L) for k, v in cols.items()}
    lanes = jnp.arange(L, dtype=jnp.int32)

    def descend(r, prefix):
        """(the group each rank lies in, the rank within it), prefix
        [S, c or 1, w] the inclusive prefix over the groups' counts."""
        below = prefix <= r[..., None]
        return (jnp.sum(below, axis=-1, dtype=jnp.int32),
                r - jnp.max(jnp.where(below, prefix, 0), axis=-1))

    def pick(row, lane):
        hit = lanes == lane[..., None]
        if row.dtype == jnp.bool_:
            return jnp.any(hit & row, axis=-1)
        return jnp.sum(jnp.where(hit, row, 0), axis=-1, dtype=row.dtype)

    def walk(start):
        r = start + jnp.broadcast_to(
            jnp.arange(chunk, dtype=jnp.int32), (S, chunk))
        live = r < top[:, -1:]
        group, r = descend(r, top[:, None, :])
        tile, r = descend(r, _rows_at(
            within, jnp.minimum(group, within.shape[1] - 1)))
        tile = jnp.where(live, group * L + tile, 0)
        lane, _r = descend(r, _lane_prefix(_rows_at(tiles, tile)))
        lane = jnp.where(live, lane, 0)
        return {k: pick(_rows_at(v, tile), lane)
                for k, v in rows.items()}, live

    if chunk == cap:
        return walk(0)
    picked, live = jax.lax.map(
        walk, jnp.arange(cap // chunk, dtype=jnp.int32) * chunk)

    def whole(x):  # [cap / chunk, S, chunk] -> [S, cap]
        return x.transpose(1, 0, 2).reshape(S, cap)

    return {k: whole(v) for k, v in picked.items()}, whole(live)


def _compacting_slots(plan: DevicePlan, cols, params, keys: jnp.ndarray,
                      kept: jnp.ndarray, num_groups: int, rung=None):
    """Every slot of a `scatter` GROUP BY, each segment's kept rows
    compacted first: [(op, [S, G])], what the full scatter gives.

    keys [S, D]: the group keys (`_group_keys`); kept [S, D]: the rows
    the filter, the time gate and validity keep. The rung (`compact_rung`
    of the largest kept count of the launch; the batched and sharded
    factories pass the whole launch's) picks ONE branch of a
    `lax.switch`: at rung cap the count tree finds each segment's first
    cap kept rows and reads the key and every staged column there into
    [S, cap] (`_kept_rows`; what no slot reads XLA drops: the key's own
    columns, the main filter's), and FILTER masks, values and the
    scatters run over those; past the top rung the full [S, D] scatter
    runs as it always did. The key is gathered as one column, not as the
    columns it is made of: on the v5e a gather costs ~20 ns an index,
    the key's pass over the block a few ms. No row is dropped: a slot
    past a segment's count is not live, and adds 0 (MIN / MAX: their
    identity) at key 0."""
    rungs = compact_rungs(kept.shape[1])
    with jax.named_scope("compact"):
        tree = _kept_tree(kept)
        if rung is None:
            rung = compact_rung(kept.shape[1], jnp.max(tree[2][:, -1]))

    def slots_of(rows, keys, live):
        masks = [_eval_filter(ir, plan, rows, params)
                 for ir in plan.agg_filter_irs]
        values = [None if ir is None else _eval_value(ir, rows, params)
                  for ir in plan.value_irs]
        out = []
        for op, vidx, fidx in plan.agg_ops:
            # G > ONEHOT_MAX_GROUPS wherever a shard compacts, so every
            # slot takes XLA's scatter here, at the full size as well
            with jax.named_scope("reduce:" + op):
                out.append(_grouped_reduce(
                    op, None if vidx is None else values[vidx], keys,
                    live if fidx is None else masks[fidx], live,
                    num_groups))
        return tuple(out)

    def compacted(cap):
        def branch():
            with jax.named_scope(f"compact{cap}"):
                rows, live = _kept_rows(tree, cap, {**cols, _KEY: keys})
            return slots_of(rows, rows.pop(_KEY), live)
        return branch

    outs = jax.lax.switch(rung, [compacted(c) for c in rungs]
                          + [lambda: slots_of(cols, keys, kept)])
    return [(op, s) for (op, _v, _f), s in zip(plan.agg_ops, outs)]


def _scatter_sum(contrib: jnp.ndarray, keys: jnp.ndarray,
                 num_groups: int, mesh=None) -> jnp.ndarray:
    """Sum one slot's contributions per group key, by `group_path`: a
    chunked one-hot matmul to ONEHOT_MAX_GROUPS groups (SURVEY.md §7:
    group-bys become one-hot/segment-sum scatter-adds), the factored
    one above it for a bool `contrib` (a count: finite whatever the
    columns hold), else XLA's scatter-add."""
    S, D = contrib.shape
    dt = _value_dtype()
    path = group_path(num_groups, D, dt, finite=contrib.dtype == jnp.bool_)
    if path == "onehot2":
        with jax.named_scope("onehot2"):
            return _onehot2_sums([contrib], keys, num_groups, mesh)[0]
    contrib = contrib.astype(dt)
    if path == "onehot":
        nchunk = D // _ONEHOT_CHUNK
        main = nchunk * _ONEHOT_CHUNK

        def body(carry, xs):
            k, c = xs  # [S, CH]
            onehot = jax.nn.one_hot(k, num_groups, dtype=c.dtype, axis=-1)
            return carry + jnp.einsum("sdg,sd->sg", onehot, c), None

        k_chunks = keys[:, :main].reshape(S, nchunk, _ONEHOT_CHUNK).swapaxes(0, 1)
        c_chunks = contrib[:, :main].reshape(S, nchunk, _ONEHOT_CHUNK).swapaxes(0, 1)
        init = jnp.zeros((S, num_groups), contrib.dtype)
        # under shard_map the chunks vary over the mesh axes, and scan
        # requires its carry to enter with the type it leaves with
        vma = tuple(jax.typeof(keys).vma | jax.typeof(contrib).vma)
        if vma:
            init = jax.lax.pcast(init, vma, to="varying")
        out, _ = jax.lax.scan(body, init, (k_chunks, c_chunks))
        if main < D:
            out = _vmap_scatter(out, keys[:, main:], contrib[:, main:], "add")
        return out
    return _vmap_scatter(jnp.zeros((S, num_groups), contrib.dtype), keys,
                         contrib, "add")


def _bf16_terms(c: jnp.ndarray) -> List[jnp.ndarray]:
    """A contribution as bf16 planes whose sum is exactly `c`: a bool
    (count) is one; an f32 is its three-term split, 3 x 8 = 24 mantissa
    bits. Each term is rounded by `reduce_precision`, which XLA may not
    elide: an f32 -> bf16 -> f32 round trip it folds away on the TPU
    (`xla_allow_excess_precision`), and the split with it (measured:
    4.5e-4 off). Inf/NaN do not survive it (Inf - Inf): `group_path`
    keeps such plans away."""
    if c.dtype == jnp.bool_:
        return [c.astype(jnp.bfloat16)]
    terms = []
    for _ in range(3):
        t = jax.lax.reduce_precision(c, exponent_bits=8, mantissa_bits=7)
        terms.append(t.astype(jnp.bfloat16))
        c = c - t
    return terms


def _onehot2_tile(k: jnp.ndarray, planes: jnp.ndarray, H: int) -> jnp.ndarray:
    """One tile of one segment's docs, k (1, T) int32 and planes (P, T)
    bf16, to its (P*H, L) f32 addend. Docs lie on the lanes of both
    operands, so neither one-hot is built transposed: left[p*H + h, d] =
    plane_p[d] where hi[d] == h, right[l, d] = [lo[d] == l], left @
    right^T."""
    L = _ONEHOT2_LANES
    P, T = planes.shape
    hot_hi = (k >> (L.bit_length() - 1)) == jax.lax.broadcasted_iota(
        jnp.int32, (H, T), 0)
    # selects in f32 (the v5e's VPU has no bf16), one cast for all
    planes = planes.astype(jnp.float32)
    left = jnp.concatenate(
        [jnp.where(hot_hi, planes[p:p + 1, :], 0.0) for p in range(P)],
        axis=0).astype(jnp.bfloat16)                           # (P*H, T)
    hot_lo = (k & (L - 1)) == jax.lax.broadcasted_iota(jnp.int32, (L, T), 0)
    right = jnp.where(hot_lo, 1.0, 0.0).astype(jnp.bfloat16)   # (L, T)
    return jax.lax.dot_general(
        left, right, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)


def _onehot2_kernel(keys_ref, planes_ref, out_ref, *, H: int):
    """Pallas body: out_ref (P*H, L) stays resident over one segment's
    tiles (the grid's last axis) and starts from zero at the first."""
    out_ref[...] = jnp.where(pl.program_id(1) == 0, 0.0, out_ref[...]) \
        + _onehot2_tile(keys_ref[...], planes_ref[...], H)


def _onehot2_sums(contribs: List[jnp.ndarray], keys: jnp.ndarray,
                  num_groups: int, mesh=None) -> List[jnp.ndarray]:
    """Per-group sums of several contributions in ONE pass over the docs:
    a factored one-hot matmul on the MXU.

    contribs: [S, D] each, bool (a count) or f32, 0 where masked out;
    keys [S, D] int32 (one outside [0, num_groups) matches no group).
    The key splits as k = hi * L + lo (L = _ONEHOT2_LANES, H = ceil(G /
    L) rounded up to 8) — the key, not the group columns, so both
    factors are wide whatever the columns' cardinalities — and a tile of
    docs contributes

        out[s, p, h, l] += sum_d [hi == h] * plane_p[s, d] * [lo == l]

    a [P*H, T] @ [T, L] product, bf16 operands, f32 accumulation
    (`_onehot2_tile`). The one-hots are 0/1 and the planes the exact
    bf16 terms of the contributions (`_bf16_terms`), so every product is
    exact and only the f32 accumulation rounds, as in the scatter.
    Counts are exact to 2^24 docs a segment. Returns one [S, G] f32
    array a contribution.

    On the TPU the tiles run as ONE Pallas kernel (`onehot2` in the
    device trace; both one-hots live and die in VMEM). Left to XLA the
    same loop is a dozen ops a tile: 2.4 M device events in the
    benchmark's 12 s traced window, which the profiler could not write
    out in 150 s, and three quarters slower (G = 7,000: 91 ms against 52).
    Every other backend takes that loop. Checked on jax 0.9.0 (ISSUE
    37): `pallas_call(interpret=True)` inside a shard_map fails its
    type check ("requires varying manual axes to match": the
    interpreter's constants are not varying as the refs are); the TPU
    interpret mode (`pltpu.force_tpu_interpret_mode`) runs there bit for
    bit but not under `vmap` (`safe_zip`: the batched grid axis has no
    `dimension_semantics`), while the chip's compiler takes the call
    under `vmap`, `shard_map` and both.

    mesh: the engine's segments mesh where keys and contributions are
    sharded over one. GSPMD refuses a Mosaic kernel ("cannot be
    automatically partitioned"), so the tiles, the Pallas call or the
    loop alike, run under a shard_map over `segments`: each chip over
    its own segments, no doc axis gathered, the [S, P * H, L] partials
    sharded as the blocks are."""
    S, D = keys.shape
    L = _ONEHOT2_LANES
    H = -(-num_groups // (8 * L)) * 8  # f32 sublanes: the P pieces align
    widths = [1 if c.dtype == jnp.bool_ else 3 for c in contribs]
    P = sum(widths)
    # docs a tile: the left operand stays under 8 MB of f32 in VMEM, so
    # a wide key space takes short tiles (its rows amortise the step)
    T = max(128, min(_ONEHOT2_CHUNK,
                     _pow2_floor(_ONEHOT2_TILE_ELEMS // (P * H))))
    # [S, P, D] by a select chain over the plane axis, not a stack: XLA
    # then writes the planes in ONE fusion, straight into the kernel's
    # layout; a stack held every term in HBM beside them and cost a
    # relayout copy a plane (G = 7,000: 66 -> 59 ms a pass)
    terms = [t[:, None, :] for c in contribs for t in _bf16_terms(c)]
    p_ids = jax.lax.broadcasted_iota(jnp.int32, (1, P, 1), 1)
    planes = terms[-1]
    for p in reversed(range(P - 1)):
        planes = jnp.where(p_ids == p, terms[p], planes)
    keys = keys[:, None, :]
    tail = -D % _ONEHOT2_CHUNK
    if tail:  # zero planes add nothing
        pad = ((0, 0), (0, 0), (0, tail))
        planes, keys = jnp.pad(planes, pad), jnp.pad(keys, pad)
    n = (D + tail) // T

    def tile_sums(keys, planes):
        """[S', 1, n * T] keys and [S', P, n * T] planes -> [S', P * H, L]:
        every segment it is handed, S' = S or one shard's share of it."""
        Sl = keys.shape[0]
        # under shard_map the result varies over the mesh as its inputs do
        vma = jax.typeof(keys).vma | jax.typeof(planes).vma
        if jax.default_backend() == "tpu":
            return pl.pallas_call(
                functools.partial(_onehot2_kernel, H=H),
                out_shape=jax.ShapeDtypeStruct((Sl, P * H, L), jnp.float32,
                                               vma=vma),
                grid=(Sl, n),
                in_specs=[pl.BlockSpec((None, 1, T), lambda s, t: (s, 0, t)),
                          pl.BlockSpec((None, P, T), lambda s, t: (s, 0, t))],
                out_specs=pl.BlockSpec((None, P * H, L),
                                       lambda s, t: (s, 0, 0)),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "arbitrary"),
                    vmem_limit_bytes=_ONEHOT2_VMEM_BYTES),
                name="onehot2",
            )(keys, planes)

        def tiles(x):  # [S', R, n * T] -> [n, S', R, T]
            return x.reshape(Sl, -1, n, T).transpose(2, 0, 1, 3)

        acc = jnp.zeros((Sl, P * H, L), jnp.float32)
        if vma:  # scan's carry must enter with the type it leaves with
            acc = jax.lax.pcast(acc, tuple(vma), to="varying")
        add = jax.vmap(functools.partial(_onehot2_tile, H=H))
        acc, _ = jax.lax.scan(lambda a, kp: (a + add(*kp), None), acc,
                              (tiles(keys), tiles(planes)))
        return acc

    if mesh is None:
        acc = tile_sums(keys, planes)
    else:
        # a Mosaic kernel is opaque to GSPMD ("cannot be automatically
        # partitioned"): each chip runs it over its own segments, and
        # the [S, P * H, L] partials stay sharded as the blocks are
        seg = jax.sharding.PartitionSpec("segments")
        acc = jax.shard_map(tile_sums, mesh=mesh, in_specs=(seg, seg),
                            out_specs=seg)(keys, planes)
    sums = acc.reshape(S, P, H * L)[:, :, :num_groups]
    out, p = [], 0
    for w in widths:
        # smallest terms first
        out.append(sums[:, p] if w == 1
                   else sums[:, p + 2] + sums[:, p + 1] + sums[:, p])
        p += w
    return out


def _pow2_floor(n: int) -> int:
    return 1 << (max(n, 1).bit_length() - 1)


def _vmap_scatter(init: jnp.ndarray, keys: jnp.ndarray, vals: jnp.ndarray,
                  mode: str) -> jnp.ndarray:
    def one(acc, k, v):
        if mode == "add":
            return acc.at[k].add(v)
        if mode == "min":
            return acc.at[k].min(v)
        return acc.at[k].max(v)
    return jax.vmap(one)(init, keys, vals)


# ---------------------------------------------------------------------------
# Sketch slots (device HLL registers / histogram partials)
# ---------------------------------------------------------------------------

def slot_width(op: str) -> int:
    """Per-segment output width of a slot op (1 for scalar reductions;
    sketch ops return register/bucket vectors; isum returns exact-sum
    planes)."""
    if op.startswith("hll:"):
        return 1 << int(op.split(":")[1])
    if op.startswith("hist:"):
        return int(op.split(":")[1])
    if op == "isum":
        return ISUM_WIDTH
    if op.startswith("isum:u"):
        return 2 * int(op.split(":")[1][1:])
    return 1


#: exact integer SUM slot: 6 signed six-bit planes of the i32-evaluated
#: value (v = sum_k plane_k << 6k, top plane arithmetic-shifted so sign
#: rides it), each plane i32-summed exactly (63 * 2^24 docs < 2^31) and
#: returned as f32-exact (hi, lo) 12-bit halves — see _isum_slot
ISUM_PLANES = 6
ISUM_WIDTH = 2 * ISUM_PLANES


def _eval_value_int(ir, cols) -> jnp.ndarray:
    """Evaluate a value IR in EXACT int32 arithmetic (staged f32 blocks
    hold int-exact values <= 2^24; the engine admits only IRs whose
    interval bounds — including every intermediate node — fit i32, so no
    multiply/add here can overflow)."""
    op = ir[0]
    if op == "col":
        return cols["val:" + ir[1]].astype(jnp.int32)
    if op == "lit":
        return jnp.int32(int(ir[1]))
    a = _eval_value_int(ir[1], cols)
    if op == "neg":
        return -a
    b = _eval_value_int(ir[2], cols)
    if op == "add":
        return a + b
    if op == "sub":
        return a - b
    if op == "mul":
        return a * b
    raise ValueError(f"non-exact int ir op {op}")


def _isum_slot(vi, mv) -> jnp.ndarray:
    """Bit-exact SUM of an i32-evaluated value with x64 off: split into
    signed 6-bit planes (digits 0-4 masked, top digit arithmetic-shifted),
    reduce each plane in int32 (never overflows), then split each plane
    sum into two f32-exact 12-bit halves. Host reconstructs
    sum = sum_k (hi_k * 4096 + lo_k) << 6k  (engine _isum_value).
    Ref SumAggregationFunction's exact double accumulation."""
    vi = jnp.where(mv, vi, 0)
    dt = _value_dtype()
    parts = []
    for k in range(ISUM_PLANES):
        if k < ISUM_PLANES - 1:
            p = (vi >> jnp.int32(6 * k)) & jnp.int32(63)
        else:
            p = vi >> jnp.int32(30)  # signed top digit
        s = jnp.sum(p, axis=1, dtype=jnp.int32)
        parts.append((s >> jnp.int32(12)).astype(dt))  # signed hi half
        parts.append((s & jnp.int32(4095)).astype(dt))
    return jnp.stack(parts, axis=1)


#: unsigned isum digit width: 127 * 2^24 docs < 2^31, so 7-bit planes are
#: i32-safe at the engine's doc cap while needing ceil(bits/7) planes —
#: fewer shift+mask+sum passes than the signed 6x6 scheme
ISUM_U_BITS = 7


def _isum_u_slot(op: str, vi, mv) -> jnp.ndarray:
    """Non-negative exact SUM: ceil(bits/7) unsigned planes (plan-time
    bounds prove the value fits), same f32-exact (hi, lo) halves."""
    planes = int(op.split(":")[1][1:])
    vi = jnp.where(mv, vi, 0)
    dt = _value_dtype()
    parts = []
    for k in range(planes):
        p = (vi >> jnp.int32(ISUM_U_BITS * k)) & jnp.int32(127)
        s = jnp.sum(p, axis=1, dtype=jnp.int32)
        parts.append((s >> jnp.int32(12)).astype(dt))
        parts.append((s & jnp.int32(4095)).astype(dt))
    return jnp.stack(parts, axis=1)


def _fmix32(h):
    """murmur3 finalizer — keep in lockstep with sketches._fmix32."""
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def _hll_slot(op: str, cols, mask) -> jnp.ndarray:
    """HLL register partials [S, m]: hash the (hi, lo) i32 split planes,
    bucket by h1's low log2m bits, rank = clz(h2)+1, max-scatter into
    registers (ref DistinctCountHLLAggregationFunction; the scatter is the
    same machinery as the group-by max path). Bit-identical to the host
    sketch (sketches.HyperLogLog.add_array)."""
    _, log2m_s, col = op.split(":", 2)
    m = 1 << int(log2m_s)
    hi = cols["valhi:" + col].astype(jnp.uint32)
    lo = cols["vallo:" + col].astype(jnp.uint32)
    h1 = _fmix32(_fmix32(lo ^ jnp.uint32(0x9E3779B9)) ^ hi)
    h2 = _fmix32(_fmix32(hi ^ jnp.uint32(0x85EBCA77)) ^ lo)
    bucket = (h1 & jnp.uint32(m - 1)).astype(jnp.int32)
    rank = jnp.where(h2 == 0, 33,
                     jax.lax.clz(h2.astype(jnp.int32)) + 1)
    dt = _value_dtype()
    rank = jnp.where(mask, rank, 0).astype(dt)  # 0 = empty register
    bucket = jnp.where(mask, bucket, 0)
    init = jnp.zeros((mask.shape[0], m), dtype=dt)
    return _vmap_scatter(init, bucket, rank, "max")


def _hist_slot(op: str, j: int, vals, params, mask, mesh=None) -> jnp.ndarray:
    """Fixed-bucket histogram partials [S, B] over the value block:
    bucket = clip((v - lo) * scale) then a masked count a bucket (feeds
    TDigest centroids host-side, ref PercentileTDigestAggregationFunction)."""
    B = int(op.split(":")[1])
    lo = params[f"slot{j}:hlo"][:, None]
    scale = params[f"slot{j}:hscale"][:, None]
    bucket = jnp.clip((vals - lo) * scale, 0, B - 1).astype(jnp.int32)
    bucket = jnp.where(mask, bucket, 0)
    return _scatter_sum(mask, bucket, B, mesh)


# ---------------------------------------------------------------------------
# Kernel assembly
# ---------------------------------------------------------------------------

def _filter_mask(plan: DevicePlan, cols, params, shape) -> jnp.ndarray:
    """[S, N] rows the plan's WHERE keeps (every row without one)."""
    if plan.filter_ir is not None:
        return _eval_filter(plan.filter_ir, plan, cols, params)
    return jnp.ones(shape, dtype=bool)


def _group_keys(plan: DevicePlan, cols, params, shape):
    """(keys [S, N] int32, the fused time bucket's gate [S, N] or None)
    of a GROUP BY's rows (shape [S, N]): the compacted code, or the
    mixed-radix key over the plan's strides, with the time bucket as its
    lowest digit."""
    if plan.group_compact:
        keys = cols["gkey"]
    else:
        keys = jnp.zeros(shape, dtype=jnp.int32)
        for col, stride in zip(plan.group_cols, plan.group_strides):
            keys = keys + cols["ids:" + col] * jnp.int32(stride)
    if not plan.tbucket:
        return keys, None
    # fused time bucket: floor((t - start) / step) from the (hi, lo)
    # raw64 planes becomes the key's lowest digit; out-of-window rows
    # gate out of every slot (their wrapped deltas never reach the
    # scatter)
    tcol, count_pad = plan.tbucket
    b, gate = timeseries_device.bucket_ids(
        cols["valhi:" + tcol], cols["vallo:" + tcol],
        params["tb:shi"], params["tb:slo"],
        params["tb:step"], params["tb:count"], count_pad)
    return keys + b, gate


def _kept_counts(plan: DevicePlan, cols, params, valid) -> jnp.ndarray:
    """[S] int32: the rows of each segment a GROUP BY keeps (filter,
    time gate, validity), what its unfiltered COUNT slot sums to."""
    mask = _filter_mask(plan, cols, params, valid.shape) & valid
    if plan.tbucket:
        mask = mask & _group_keys(plan, cols, params, valid.shape)[1]
    return jnp.sum(mask, axis=1, dtype=jnp.int32)


def _compute_slots(plan: DevicePlan, cols, params, valid, G: int = 0,
                   mesh=None, rung=None):
    """Shared kernel body: filter + values + per-slot reductions over a
    (possibly shard-local) [S, D] block. Returns
    ([(op, [S]- or [S, G]-array)], matched_count [S] or None).
    G: group count for compact-key plans (plan.num_groups is 0 there).
    mesh: the segments mesh a plain-jit kernel's blocks are sharded over
    (None: one device, or already inside a shard_map): only the factored
    one-hot pass, a GROUP BY's or a histogram slot's, asks for it
    (`_onehot2_sums`).
    rung: a `scatter` GROUP BY's compaction rung where the caller chose
    it for a whole launch (`compact_rung`; the batched and sharded
    factories), None to choose it from these segments."""
    dt = _value_dtype()
    num_groups = plan.num_groups or G
    with jax.named_scope("filter"):
        mask = _filter_mask(plan, cols, params, valid.shape)
    if compacts(plan, num_groups, valid.shape[1]):
        with jax.named_scope("group_keys"):
            keys, gate = _group_keys(plan, cols, params, valid.shape)
        kept = mask & valid if gate is None else mask & gate & valid
        return _compacting_slots(plan, cols, params, keys, kept,
                                 num_groups, rung), None
    with jax.named_scope("filter"):
        # per-aggregation FILTER (WHERE ...) masks AND into the main mask
        # per slot (ref FilteredAggregationOperator)
        agg_masks = [_eval_filter(ir, plan, cols, params)
                     for ir in plan.agg_filter_irs]

    values = []
    with jax.named_scope("values"):
        for ir in plan.value_irs:
            values.append(None if ir is None
                          else _eval_value(ir, cols, params))

    slots = []
    if num_groups:
        with jax.named_scope("group_keys"):
            keys, gate = _group_keys(plan, cols, params, valid.shape)
            if gate is not None:
                mask = mask & gate
        def slot_mask(fidx):
            return mask if fidx is None else mask & agg_masks[fidx]

        sums = {}
        if group_path(num_groups, valid.shape[1], dt,
                      finite=not plan.nonfinite) == "onehot2":
            # every additive slot in one pass: the `lo` one-hot is built
            # once a chunk, and the planes stack into one left operand
            additive = [j for j, a in enumerate(plan.agg_ops)
                        if a[0] in _ADDITIVE]
            scope = "+".join(plan.agg_ops[j][0] for j in additive)
            with jax.named_scope("reduce:" + scope), \
                    jax.named_scope("onehot2"):
                contribs = [
                    _contribution(op, None if vidx is None else values[vidx],
                                  slot_mask(fidx) & valid)
                    for op, vidx, fidx in (plan.agg_ops[j] for j in additive)]
                # keys as they are: a masked row adds zeros wherever it
                # lands, and a key outside [0, G) matches no group
                sums = dict(zip(additive, _onehot2_sums(
                    contribs, keys, num_groups, mesh)))
        for j, (op, vidx, fidx) in enumerate(plan.agg_ops):
            if j in sums:
                slots.append((op, sums[j]))
                continue
            with jax.named_scope("reduce:" + op):
                vals = None if vidx is None else values[vidx]
                slots.append((op, _grouped_reduce(
                    op, vals, keys, slot_mask(fidx), valid, num_groups,
                    mesh)))
        return slots, None
    with jax.named_scope("reduce:matched"):
        matched = jnp.sum(mask & valid, axis=1).astype(dt)
    for j, (op, vidx, fidx) in enumerate(plan.agg_ops):
        with jax.named_scope("reduce:" + op):
            m = mask if fidx is None else mask & agg_masks[fidx]
            if op.startswith("hll:"):
                slot = _hll_slot(op, cols, m & valid)
            elif op.startswith("hist:"):
                slot = _hist_slot(op, j, values[vidx], params, m & valid,
                                  mesh)
            elif op == "isum":
                vi = _eval_value_int(plan.value_irs[vidx], cols)
                slot = _isum_slot(vi, m & valid)
            elif op.startswith("isum:u"):
                vi = _eval_value_int(plan.value_irs[vidx], cols)
                slot = _isum_u_slot(op, vi, m & valid)
            else:
                vals = None if vidx is None else values[vidx]
                slot = _masked_reduce(op, vals, m, valid)
            slots.append((op, slot))
    return slots, matched


def make_kernel(plan: DevicePlan, kind: str = "agg", extra: tuple = (),
                mesh=None):
    """Build the traced kernel fn(cols, params, num_docs, D) -> packed array.

    cols:    dict of 'ids:<col>' int32 [S, D] / 'val:<col>' float [S, D]
    params:  dict of per-leaf predicate arrays ('leaf<i>:lo/hi/idx/lut'),
             or their packed form (`unpack_params`): the engine stages
             the pack as a HOST int32 [K, S] array and passes it here as
             it is, so the jit call's own argument path makes the one
             host->device transfer of the launch; LUT tables and CLP
             leaf arrays beside it are device arrays already
    num_docs: int32 [S] actual docs per segment (for the padding mask);
             None where the pack carries it.

    Returns ONE packed array — every separate device->host fetch is a
    sync the query would wait out in turn:
      no group-by: [S, 1 + n_slots]  (col 0 = matched doc count)
      group-by:    [S, G, n_slots]   (matched derived from the count
                                      slot host-side), or with
                                      `plan.group_fold` ONE integer row
                                      [n_slots * G_out + S] (`fold_groups`)
    Counts ride in the value dtype; exact while D < 2^24 (engine caps
    doc padding below that).

    kind/extra label this build's trace-log entries (the batched
    factories pass their own kind and batch bucket through).
    mesh: the engine's segments mesh where its blocks are sharded over
    one (GSPMD partitions the rest of the body from the inputs'
    shardings; the Pallas pass, a GROUP BY's or a histogram slot's,
    cannot be, and runs a shard under `shard_map`: `_onehot2_sums`; the
    fold's reductions end in explicit all-reduces: `fold_groups`).
    """
    fp = plan_fingerprint(plan)

    def kernel(cols, params, num_docs, D, G=0, rung=None):
        params, num_docs = unpack_params(plan, params, num_docs)
        # body runs at trace time: counts compiles
        note_trace(kind, fp, (*extra, int(num_docs.shape[-1]), D, G))
        valid = _valid_rows(plan, cols, num_docs,
                            jnp.arange(D, dtype=jnp.int32)[None, :])
        slots, matched = _compute_slots(plan, cols, params, valid, G, mesh,
                                        rung)
        if plan.group_fold:
            with jax.named_scope("fold"):
                return fold_groups(plan, slots, params, mesh)
        with jax.named_scope("pack"):
            if plan.num_groups or G:
                return jnp.stack([s for _, s in slots], axis=-1)
            return _pack_flat(matched, slots)

    return kernel


def _valid_rows(plan: DevicePlan, cols, num_docs, doc_pos) -> jnp.ndarray:
    """[S, D] bool: the rows at doc_pos [1, D] (a shard's own, under a
    docs mesh axis) that are real docs of their segment, less the
    superseded rows where upsert validDocIds ride as a staged bool block
    (they drop out of every slot AND the matched count, exactly mirroring
    the host executor's `mask &= valid.to_mask()`)."""
    valid = doc_pos < num_docs[:, None]
    if plan.valid_mask:
        valid = valid & cols["vmask"]
    return valid


def _launch_rung(plan: DevicePlan, D: int, G: int, over_members):
    """ONE compaction rung for a batched launch, from the most rows any
    segment of any member keeps; None where the plan does not compact at
    these shapes. Under `vmap` a member's own rung would be a batched
    `lax.switch` index, which runs every branch and selects: the full
    scatter always. over_members(f) maps f(cols, params, num_docs) over
    the members as the launch does."""
    if not compacts(plan, plan.num_groups or G, D):
        return None

    def most_kept(cols, params, num_docs):
        params, num_docs = unpack_params(plan, params, num_docs)
        return jnp.max(_kept_counts(plan, cols, params, _valid_rows(
            plan, cols, num_docs, jnp.arange(D, dtype=jnp.int32)[None, :])))

    return compact_rung(D, jnp.max(over_members(most_kept)))


def _pack_flat(matched, slots):
    """[S]-scalar and [S, w]-vector (sketch) slots -> one [S, 1 + sum(w)]
    array (single device->host fetch; _assemble indexes by slot offsets)."""
    parts = [matched[:, None]]
    for _op, s in slots:
        parts.append(s[:, None] if s.ndim == 1 else s)
    return jnp.concatenate(parts, axis=1)


def make_topn_kernel(plan: DevicePlan, kind: str = "topn",
                     extra: tuple = ()):
    """Selection / selection-order-by kernel (ref
    operator/query/SelectionOrderByOperator + the min/max-based combine):
    per segment, the top-K doc indices by the order value (value_irs[0];
    ascending negates), or the first K matching docs when unordered.

    Output [S, 1 + K] int32: col 0 = matched doc count, cols 1.. = doc
    indices (-1 = no more matches). The host projects ONLY the winning
    docs — a large filtered SELECT never materializes losing rows.

    kind/extra label this build's trace-log entries (the batched topn
    factory passes its own kind and batch bucket through).
    """
    fp = plan_fingerprint(plan)

    def kernel(cols, params, num_docs, D):
        params, num_docs = unpack_params(plan, params, num_docs)
        # body runs at trace time: counts compiles
        note_trace(kind, fp, (*extra, int(num_docs.shape[-1]), D))
        valid = jnp.arange(D, dtype=jnp.int32)[None, :] < num_docs[:, None]
        if plan.valid_mask:
            valid = valid & cols["vmask"]
        with jax.named_scope("filter"):
            if plan.filter_ir is not None:
                mask = _eval_filter(plan.filter_ir, plan, cols, params) \
                    & valid
            else:
                mask = valid
        dt = _value_dtype()
        with jax.named_scope("values"):
            if plan.value_irs:
                v = _eval_value(plan.value_irs[0], cols, params).astype(dt)
                score = -v if plan.topn_asc else v
                # tie-break toward lower doc ids so results are stable
            else:
                score = jnp.broadcast_to(
                    -jnp.arange(D, dtype=dt)[None, :], mask.shape)
        with jax.named_scope("reduce:topk"):
            # clamp matched scores to the finite range so a legitimate
            # -inf score (f32 overflow of huge values, or a real +/-inf
            # column value under ASC negation) still outranks every
            # unmatched doc's -inf sentinel; validity then reads the MASK
            # at the winning docs
            fin = jnp.finfo(dt)
            # NaN order values sort LAST (host sort parity: numpy puts NaN
            # at the end) — clip passes NaN through and top_k would rank
            # it first, so map it to the finite minimum among matched docs
            score = jnp.where(jnp.isnan(score), fin.min, score)
            score = jnp.where(mask, jnp.clip(score, fin.min, fin.max),
                              -jnp.inf)
            k = min(plan.topn_k, D)
            _top_vals, top_idx = jax.lax.top_k(score, k)
        with jax.named_scope("pack"):
            found = jnp.take_along_axis(mask, top_idx, axis=1)
            idx_out = jnp.where(found, top_idx, -1).astype(jnp.int32)
            matched = jnp.sum(mask, axis=1).astype(jnp.int32)
            return jnp.concatenate([matched[:, None], idx_out], axis=1)

    return kernel


@functools.lru_cache(maxsize=256)
def compiled_topn_kernel(plan: DevicePlan):
    return jax.jit(_named(make_topn_kernel(plan),
                          "topn_" + plan_fingerprint(plan)),
                   static_argnames=("D",))


@functools.lru_cache(maxsize=256)
def compiled_kernel(plan: DevicePlan, mesh=None):
    """jit-compiled kernel for a plan structure (shape specialization is
    handled inside jit's own cache; D is static because a filterless
    COUNT(*) stages no columns to infer it from; G is the compact-key
    group count — data-dependent, hence a static arg rather than plan
    state). mesh: see `make_kernel`; part of the cache's key."""
    return jax.jit(_named(make_kernel(plan, mesh=mesh),
                          "agg_" + plan_fingerprint(plan)),
                   static_argnames=("D", "G"))


# ---------------------------------------------------------------------------
# multi-chip: the same kernel under shard_map over a (segments, docs) mesh
# ---------------------------------------------------------------------------

_DOC_COMBINE = {"sum": "psum", "count": "psum", "sumsq": "psum",
                "sum3": "psum", "sum4": "psum",
                "min": "pmin", "max": "pmax",
                "hll": "pmax",   # register maxima merge across doc shards
                "hist": "psum",  # bucket counts add across doc shards
                "isum": "psum"}  # exact-sum planes add (halves stay small)


def _doc_combine(op: str) -> str:
    return _DOC_COMBINE[op.split(":")[0]]


def _shard_one(plan: DevicePlan, doc_pos, G: int):
    """Per-shard compute for ONE query: the local [S_loc, D_loc] slot
    partials BEFORE any mesh collective. Shared by the single-query and
    the batched (vmap-inside-shard_map) sharded kernels so the slot
    semantics live in exactly one place. Returns the slot arrays in
    plan.agg_ops order, with the matched count appended for non-grouped
    plans (a pytree vmap can carry). rung: `_shard_rung`'s."""
    def one(cols, params, num_docs, rung=None):
        slots, matched = _compute_slots(
            plan, cols, params, _valid_rows(plan, cols, num_docs, doc_pos),
            G, rung=rung)
        arrs = tuple(s for _, s in slots)
        return arrs if (plan.num_groups or G) else arrs + (matched,)
    return one


def _shard_rung(plan: DevicePlan, doc_pos, G: int, over_members):
    """ONE compaction rung for a launch over a (segments x docs) mesh,
    inside its shard_map: from each segment's kept count summed over the
    docs axis, the most over segments (and members: over_members(f) maps
    f(cols, params, num_docs) as the launch does), as the host reads the
    counts back, so a shard's cap holds its own share. None where the
    plan does not compact a shard."""
    d_local = doc_pos.shape[1]
    if not compacts(plan, plan.num_groups or G, d_local):
        return None

    def kept(cols, params, num_docs):
        return _kept_counts(plan, cols, params,
                            _valid_rows(plan, cols, num_docs, doc_pos))

    counts = jax.lax.psum(over_members(kept), "docs")
    return compact_rung(d_local,
                        jax.lax.pmax(jnp.max(counts), "segments"))


def _shard_combine_pack(plan: DevicePlan, outs, G: int):
    """psum/pmin/pmax each slot over the mesh `docs` axis, then pack
    into the kernel's output layout. Rank-agnostic: reductions and the
    pack only touch the trailing axes, so the batched kernels' leading
    query axis rides along untouched ([S, ...] and [B, S, ...] both
    work) — reductions commute with the batch stack."""
    combined = []
    for (op, _v, _f), s in zip(plan.agg_ops, outs):
        kind = _doc_combine(op)
        if kind == "psum":
            s = jax.lax.psum(s, "docs")
        elif kind == "pmin":
            s = jax.lax.pmin(s, "docs")
        else:
            s = jax.lax.pmax(s, "docs")
        combined.append(s)
    if plan.num_groups or G:
        return jnp.stack(combined, axis=-1)   # [..., S, G, n_slots]
    matched = jax.lax.psum(outs[-1], "docs")
    parts = [matched[..., None]]
    for s in combined:
        parts.append(s[..., None] if s.ndim == matched.ndim else s)
    return jnp.concatenate(parts, axis=-1)    # [..., S, 1 + sum(w)]


def make_sharded_kernel(plan: DevicePlan, mesh):
    """ANY DevicePlan over a (segments x docs) mesh with explicit ICI
    collectives (SURVEY §2.6 rows 6-7): column blocks shard over both axes,
    each device reduces its local [S_loc, D_loc] shard, then partials
    combine with psum/pmin/pmax over the `docs` axis. Per-segment results
    stay sharded over `segments` (the engine assembles them host-side, the
    same contract as the single-chip kernel).

    fn(cols, params, num_docs, D) -> packed array (D static: the padded
    GLOBAL doc count; each shard derives its global doc indices from
    axis_index('docs') — a shard-local arange would restart at 0 and
    mis-mask padding).
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    doc_shards = dict(zip(mesh.axis_names, mesh.devices.shape)).get("docs", 1)
    fp = plan_fingerprint(plan)

    def local(cols, params, num_docs, D, G=0):
        # body runs at trace time: counts compiles
        note_trace("sharded", fp, (int(num_docs.shape[-1]), D, G))
        d_local = D // doc_shards
        doc_pos = (jax.lax.axis_index("docs") * d_local
                   + jnp.arange(d_local, dtype=jnp.int32))[None, :]
        rung = _shard_rung(plan, doc_pos, G,
                           lambda f: f(cols, params, num_docs))
        outs = _shard_one(plan, doc_pos, G)(cols, params, num_docs, rung)
        return _shard_combine_pack(plan, outs, G)

    def col_spec(name):
        return P("segments", "docs")  # every staged block is [S, D]

    def param_spec(arr):
        # leaf params: [S] bounds or [S, C] LUTs — segment axis only
        return P("segments", *([None] * (arr.ndim - 1)))

    def fn(cols, params, num_docs, D, G=0):
        # outside shard_map: the rows come out [S], sharded as before
        params, num_docs = unpack_params(plan, params, num_docs)
        in_specs = (
            {k: col_spec(k) for k in cols},
            {k: param_spec(v) for k, v in params.items()},
            P("segments"),
        )
        ndim_out = 3 if (plan.num_groups or G) else 2
        sm = shard_map(
            functools.partial(local, D=D, G=G), mesh=mesh,
            in_specs=in_specs,
            out_specs=P("segments", *([None] * (ndim_out - 1))),
        )
        return sm(cols, params, num_docs)

    return jax.jit(_named(fn, "sharded_" + fp), static_argnames=("D", "G"))


@functools.lru_cache(maxsize=256)
def compiled_sharded_kernel(plan: DevicePlan, mesh):
    return make_sharded_kernel(plan, mesh)


# ---------------------------------------------------------------------------
# batched kernel factory: ONE launch for B fingerprint-equal queries
# ---------------------------------------------------------------------------
#
# The coalesce key is (plan fingerprint, shape bucket) — (plan, S, D, G,
# per-array shape signature) — never a concrete segment batch, so
# same-shape queries batch ACROSS tables and partitions. Two variants:
#
#   broadcast (stacked=False): every member shares the SAME staged column
#     blocks (same segment batch — the dashboard-fleet case); only the
#     per-query predicate params carry a leading batch axis, so B queries
#     share one pass over one copy of the data.
#   stacked (stacked=True): members stage DIFFERENT tables/partitions
#     whose blocks pad into the same (S, D) bucket; each member's blocks
#     stack along a new leading axis (the rows come from the residency
#     tier — device-to-device, never a re-upload) and the kernel vmaps
#     over all three of (cols, params, num_docs).
#
# Stacking happens INSIDE the jit so GSPMD owns the resulting sharding on
# mesh engines. Dispatchers pad partial batches to the pow2 bucket B with
# replicated leader inputs, so jit's shape cache only ever sees bucketed
# batch sizes — steady state is zero retraces.

def _batched_name(prefix: str, B: int, stacked: bool,
                  plan: DevicePlan) -> str:
    return (f"{prefix}_b{B}{'_stacked' if stacked else ''}_"
            f"{plan_fingerprint(plan)}")


def make_batched_kernel(plan: DevicePlan, B: int, stacked: bool = False,
                        mesh=None):
    kind = "batched_stacked" if stacked else "batched"
    base = make_kernel(plan, kind=kind, extra=(B,), mesh=mesh)

    if stacked:
        def fn(clist, plist, ndlist, D, G=0):
            cs, ns = map(stack_members, (clist, ndlist))
            ps = stack_params(plist)
            rung = _launch_rung(plan, D, G, lambda f: jax.vmap(f)(cs, ps, ns))
            return jax.vmap(lambda c, p, nd: base(
                c, p, nd, D=D, G=G, rung=rung))(cs, ps, ns)
    else:
        def fn(cols, plist, num_docs, D, G=0):
            ps = stack_params(plist)
            # the index array keeps vmap fed when a filterless plan has
            # EMPTY per-query params (vmap rejects an all-empty pytree)
            idx = jnp.arange(B, dtype=jnp.int32)
            rung = _launch_rung(plan, D, G, lambda f: jax.vmap(
                lambda p, _i: f(cols, p, num_docs))(ps, idx))
            return jax.vmap(lambda p, _i: base(
                cols, p, num_docs, D=D, G=G, rung=rung))(ps, idx)

    return jax.jit(_named(fn, _batched_name("batched", B, stacked, plan)),
                   static_argnames=("D", "G"))


@functools.lru_cache(maxsize=256)
def compiled_batched_kernel(plan: DevicePlan, B: int, stacked: bool = False,
                            mesh=None):
    """One jit per (plan, batch-size bucket B, stacked?, segments mesh)
    — see the factory note above.
    fn(cols|clist, plist, num_docs|ndlist, D, G)."""
    return make_batched_kernel(plan, B, stacked, mesh)


def make_batched_dedup_kernel(plan: DevicePlan, B: int, U: int, mesh=None):
    """Stacked-batch variant with SAME-COLS MEMBER GROUPING: members
    whose staged column blocks are identity-equal (same table/segments,
    different predicate literals — e.g. two dashboard queries of one
    fleet landing in the same stacked batch as a third table's) share
    ONE stack entry instead of re-stacking duplicate [S, D] blocks.

    clist/ndlist carry the U UNIQUE column sets (padded to the pow2 U
    bucket with the leader's); plist carries all B member params; idx is
    an int32 [B] member->unique-slot map, a TRACED argument so changing
    member composition never retraces — jit's cache keys only the
    (B, U) buckets. Each vmapped member gathers its slot from the
    stacked uniques (dynamic_index on the leading axis), so device
    memory holds U copies of the data, not B."""
    base = make_kernel(plan, kind="batched_dedup", extra=(B, U), mesh=mesh)

    def fn(clist, plist, ndlist, idx, D, G=0):
        cs, ns = map(stack_members, (clist, ndlist))
        ps = stack_params(plist)
        pick = jax.tree_util.tree_map

        def members(f):
            return jax.vmap(lambda p, i: f(
                pick(lambda c: c[i], cs), p, pick(lambda n: n[i], ns)))(
                    ps, idx)

        rung = _launch_rung(plan, D, G, members)
        return members(lambda c, p, nd: base(c, p, nd, D=D, G=G, rung=rung))

    return jax.jit(_named(fn, f"batched_b{B}_dedup{U}_"
                              f"{plan_fingerprint(plan)}"),
                   static_argnames=("D", "G"))


@functools.lru_cache(maxsize=256)
def compiled_batched_dedup_kernel(plan: DevicePlan, B: int, U: int,
                                  mesh=None):
    """One jit per (plan, B bucket, U bucket, segments mesh) —
    fn(clist[U], plist[B], ndlist[U], idx[B], D, G)."""
    return make_batched_dedup_kernel(plan, B, U, mesh)


def make_batched_topn_kernel(plan: DevicePlan, B: int,
                             stacked: bool = False):
    """The batched factory for top-N / doc-id-scan plans (mode='topn'):
    MSE leaf SCAN stages resolve their filtered doc ids through this
    kernel, so fingerprint-equal leaf stages from concurrent MSE queries
    (and single-stage selection traffic sharing the plan + shape bucket)
    coalesce into ONE launch exactly like the agg factory — broadcast
    when every member staged the same column blocks, stacked across
    tables otherwise. Output [B, S, 1 + K]."""
    kind = "topn_batched_stacked" if stacked else "topn_batched"
    base = make_topn_kernel(plan, kind=kind, extra=(B,))

    if stacked:
        def fn(clist, plist, ndlist, D, G=0):
            cs, ns = map(stack_members, (clist, ndlist))
            ps = stack_params(plist)
            return jax.vmap(
                lambda c, p, nd: base(c, p, nd, D=D))(cs, ps, ns)
    else:
        def fn(cols, plist, num_docs, D, G=0):
            ps = stack_params(plist)
            idx = jnp.arange(B, dtype=jnp.int32)  # empty-params guard
            return jax.vmap(
                lambda p, _i: base(cols, p, num_docs, D=D))(ps, idx)

    return jax.jit(_named(fn, _batched_name("topn_batched", B, stacked,
                                            plan)),
                   static_argnames=("D", "G"))


@functools.lru_cache(maxsize=256)
def compiled_batched_topn_kernel(plan: DevicePlan, B: int,
                                 stacked: bool = False):
    return make_batched_topn_kernel(plan, B, stacked)


def make_batched_sharded_kernel(plan: DevicePlan, mesh, B: int,
                                stacked: bool = False):
    """The batched kernel for doc-sharded mesh engines: vmap INSIDE
    shard_map — mesh axes outermost, batch axis innermost — so
    multi-device engines ride the same coalesce path instead of falling
    off it. (Written when `vmap` OVER `shard_map` was unsupported; jax
    0.9.0 batches one, checked at ISSUE 37, and the plain-jit mesh
    kernels are vmapped over theirs. This nests the other way and stays:
    its specs say where the batch axis lies.) Each device computes its local [*, S_loc, D_loc] shard for all
    B queries, then the whole batch pays ONE set of psum/pmin/pmax
    collectives over the stacked partials (reductions commute with the
    batch stack) instead of B per-query rendezvous — which also means
    host platforms hold the CPU-collective lock once per BATCH.
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    doc_shards = dict(zip(mesh.axis_names, mesh.devices.shape)).get("docs", 1)
    fp = plan_fingerprint(plan)
    kind = "sharded_batched_stacked" if stacked else "sharded_batched"

    def local(cols, params, num_docs, D, G=0):
        note_trace(kind, fp, (B, int(num_docs.shape[-1]), D, G))
        d_local = D // doc_shards
        doc_pos = (jax.lax.axis_index("docs") * d_local
                   + jnp.arange(d_local, dtype=jnp.int32))[None, :]
        # batch axis INNERMOST: vmap the shared per-shard compute over
        # the leading query axis, then pay ONE set of collectives on the
        # stacked partials (the combine/pack is rank-agnostic). The
        # trailing index arg keeps vmap fed when a filterless plan's
        # params pytree is empty
        one = _shard_one(plan, doc_pos, G)
        idx = jnp.arange(B, dtype=jnp.int32)
        in_axes = (0 if stacked else None, 0, 0 if stacked else None, 0)

        def members(f):
            return jax.vmap(lambda c, p, nd, _i: f(c, p, nd),
                            in_axes=in_axes)(cols, params, num_docs, idx)

        rung = _shard_rung(plan, doc_pos, G, members)
        outs = members(lambda c, p, nd: one(c, p, nd, rung))
        return _shard_combine_pack(plan, outs, G)

    def fn(cols, plist, num_docs, D, G=0):
        ps, ns = unpack_batch(plan, plist, num_docs, stacked)
        if stacked:
            cs = stack_members(cols)
            col_spec = P(None, "segments", "docs")
            nd_spec = P(None, "segments")
        else:
            cs = cols
            col_spec = P("segments", "docs")
            nd_spec = P("segments")
        in_specs = (
            {k: col_spec for k in cs},
            {k: P(None, "segments", *([None] * (v.ndim - 2)))
             for k, v in ps.items()},
            nd_spec,
        )
        ndim_out = 4 if (plan.num_groups or G) else 3
        sm = shard_map(
            functools.partial(local, D=D, G=G), mesh=mesh,
            in_specs=in_specs,
            out_specs=P(None, "segments", *([None] * (ndim_out - 2))),
        )
        return sm(cs, ps, ns)

    return jax.jit(_named(fn, _batched_name("sharded_batched", B, stacked,
                                            plan)),
                   static_argnames=("D", "G"))


@functools.lru_cache(maxsize=256)
def compiled_batched_sharded_kernel(plan: DevicePlan, mesh, B: int,
                                    stacked: bool = False):
    return make_batched_sharded_kernel(plan, mesh, B, stacked)
