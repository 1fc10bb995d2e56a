"""Device plan IR: the hashable structure a compiled kernel is keyed by.

Reference parity: the role of pinot-core's per-segment Plan tree
(plan/maker/InstancePlanMakerImplV2.java:270 chooses the operator chain per
query shape) — but here the "plan" is a pure-data IR handed to
ops/kernels.build_kernel, and compiled-function caching is keyed by it
(SURVEY.md §7 hard-parts: cache compiled kernels keyed by plan shape).

Filter IR nodes (nested tuples, hashable):
    ('and', n1, n2, ...) / ('or', ...) / ('not', n)
    ('leaf', i)        -- i-th entry of DevicePlan.leaves

Leaf kinds (resolved per-segment into parameter arrays, see ops/engine.py):
    'range' : lo[S], hi[S] int32     -- lo <= dictId <= hi  (equals folds here)
    'neq'   : idx[S] int32           -- dictId != idx (idx=-1 matches all)
    'lut'   : table[S, C] bool       -- table[s, dictId] (in/not-in/like/regex)
    'vrange': lo[S], hi[S] float     -- lo <= value <= hi (raw numeric columns)
    'vrange64': lohi/lolo/hihi/hilo[S] int32 -- exact closed-interval compare
              on big-int columns staged as (hi, lo) i32 split planes
              (hi = v >> 24, lo = v & 0xFFFFFF); works with x64 OFF where
              f32 staging would alias values above 2^24 (epoch millis)
    'clp'   : LIKE/regex over a CLP log column, evaluated against the
              column's logtype-id + variable-slot pseudo-columns
              (ops/clp_device.py compiles the pattern to per-segment
              candidate-logtype LUTs + encoded/dict variable conditions;
              leaf.meta = (mode, Kd, Ke) picks the staged slot layout)

Value IR (aggregation inputs / in-kernel transforms):
    ('col', name)       -- column values (dict gather or raw staged block)
    ('ids', name)       -- raw dictIds of a column (group keys)
    ('lit', v)
    ('add'|'sub'|'mul'|'div', a, b)
    ('neg', a)

Packed parameters: every [S]-shaped parameter of a plan (the leaf bounds
above except 'lut' and 'clp', the 'hist:' slots' bucket bounds, the time
bucket's five cells, and num_docs) is staged as ONE int32 [K, S] array
under params[PACK]. It stays a HOST (numpy) array until the launch: a
lone launch hands it to the jit'd kernel as an argument, a batch's B
packs are stacked on the host into one [B, K, S] array
(`batch_params`), and jit's own argument path makes the one transfer a
launch. `pack_layout(plan)` names its rows; the layout is a function of
the plan alone, so it is in
no cache key and a new literal never retraces. Floats are bit-cast, not
converted (a float64 bound takes two rows), so a bound reaches the
kernel exactly as the per-array staging gave it.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import numpy as np

#: key of the packed [K, S] int32 parameter array in a staged params dict
PACK = "pack"
#: row 0 of every packed array: real docs a segment (0 on padded slots)
NUM_DOCS = "num_docs"

#: a leaf kind's [S] rows: (suffix, "i" int32 | "f" value-dtype float)
_LEAF_ROWS = {
    "range": (("lo", "i"), ("hi", "i")),
    "neq": (("idx", "i"),),
    "vrange": (("lo", "f"), ("hi", "f")),
    "vrange64": (("lohi", "i"), ("lolo", "i"), ("hihi", "i"), ("hilo", "i")),
}


@functools.lru_cache(maxsize=1024)
def pack_layout(plan) -> Tuple[Tuple[str, str], ...]:
    """(name, "i" | "f") of each packed parameter, in row order. Reads
    only `tbucket`, `agg_ops` and `leaves`, which VectorPlan mirrors.
    Memoized a plan: staging asks on every parameter-cache miss."""
    rows = [(NUM_DOCS, "i")]
    if plan.tbucket:
        rows += [(f"tb:{cell}", "i")
                 for cell in ("shi", "slo", "step", "count", "off")]
    for j, (op, _vidx, _fidx) in enumerate(plan.agg_ops):
        if op.startswith("hist:"):
            rows += [(f"slot{j}:hlo", "f"), (f"slot{j}:hscale", "f")]
    for i, leaf in enumerate(plan.leaves):
        rows += [(f"leaf{i}:{suffix}", kind)
                 for suffix, kind in _LEAF_ROWS.get(leaf.kind, ())]
    return tuple(rows)


def pack_params(plan, arrays: Dict[str, np.ndarray]) -> np.ndarray:
    """The host side of the pack: `arrays` holds one [S] array a layout
    row (int32, or the staging value dtype for an "f" row); returns the
    int32 [K, S] array whose rows are their bits. `view`, never
    `astype`: a float64 row becomes its (low, high) words, two rows."""
    rows = []
    for name, kind in pack_layout(plan):
        arr = np.ascontiguousarray(arrays[name])
        if kind == "i":
            rows.append(arr.astype(np.int32, copy=False))
        else:
            words = arr.view(np.int32).reshape(arr.shape[0], -1)
            rows.extend(words.T)
    return np.stack(rows)


def batch_params(members):
    """B members' staged params dicts as ONE dict, the batched kernels'
    `plist`, made on the HOST by whoever launches the batch (the
    dispatch ring): what a member still holds on the host (the packed
    [K, S] parameters, a numpy array until the launch) is stacked here
    into one [B, K, S] array, which jit's own argument path transfers
    once a launch; what is on the device already (LUT tables, CLP leaf
    arrays, the vector leg's query arrays) stays a tuple of the B
    members' arrays and is stacked inside the jit
    (`kernels.stack_params`)."""
    return {k: (np.stack([m[k] for m in members])
                if isinstance(v, np.ndarray)
                else tuple(m[k] for m in members))
            for k, v in members[0].items()}


@dataclass(frozen=True)
class DeviceLeaf:
    kind: str         # 'range' | 'neq' | 'lut' | 'vrange' | 'vrange64' | 'clp'
    column: str
    #: kind-specific static shape info folded into the plan signature
    #: ('clp': (mode, Kd, Ke) — see ops/clp_device.py)
    meta: Tuple = ()


@dataclass(frozen=True)
class DevicePlan:
    """Hashable kernel-structure signature."""
    filter_ir: Optional[tuple]            # nested tuple tree or None
    leaves: Tuple[DeviceLeaf, ...]
    value_irs: Tuple[Optional[tuple], ...]  # one per agg slot input (None = count(*))
    #: (op, value_ir index or None, agg-filter index or None) — the third
    #: element selects an entry of agg_filter_irs to AND into the main mask
    #: for this slot (ref FilteredAggregationOperator)
    agg_ops: Tuple[Tuple[str, Optional[int], Optional[int]], ...]
    #: per-aggregation FILTER (WHERE ...) trees (same leaf space as filter_ir)
    agg_filter_irs: Tuple[tuple, ...] = ()
    group_cols: Tuple[str, ...] = ()
    group_strides: Tuple[int, ...] = ()   # mixed-radix strides over padded cards
    num_groups: int = 0                   # padded combined-key space (0 = no group-by)
    #: True: the dense mixed-radix key space exceeded MAX_DEVICE_GROUPS, so
    #: keys are staged as a per-segment COMPACTED key block ('gkey') — host
    #: factorizes the observed combined keys once per (segment, group cols)
    #: and caches the codes + decode table (ref
    #: DictionaryBasedGroupKeyGenerator's sparse map modes). The group
    #: count is then data-dependent and rides the kernel's static G arg.
    group_compact: bool = False
    #: columns staged as dictIds with a dictionary value table
    dict_cols: Tuple[str, ...] = ()
    #: columns staged as raw numeric value blocks
    raw_cols: Tuple[str, ...] = ()
    #: big-int columns staged as (hi, lo) i32 split planes, filter-only
    raw64_cols: Tuple[str, ...] = ()
    #: CLP log columns staged as (name, Kd, Ke) pseudo-column families:
    #: logtype-id block + Kd dict-var-slot id blocks + Ke encoded-var
    #: (hi, lo) i32 split slot blocks (ops/clp_device.py), filter-only
    clp_cols: Tuple[Tuple[str, int, int], ...] = ()
    #: 'agg' (default) | 'topn' — topn plans compute per-segment top-K doc
    #: indices by value_irs[0] (or first-K matching when it is None) for
    #: selection / selection-order-by offload
    mode: str = "agg"
    topn_k: int = 0
    topn_asc: bool = True
    #: True: the batch carries at least one upsert/dedup segment with a
    #: live validDocIds bitmap — the engine stages a bool [S, D] mask
    #: block ('vmask', version-stamped by the bitmap mutation counter)
    #: and kernels AND it into the padding-validity mask, so superseded
    #: rows are invisible to every slot exactly as the host executor's
    #: `mask &= valid.to_mask()` makes them (SURVEY §2.3)
    valid_mask: bool = False
    #: device time-bucket leg (ops/timeseries_device.py): (ts_col,
    #: width, count_pad) — floor((t - origin) / step) fused into the
    #: group key as its LOWEST digit, counted from each segment's own
    #: origin bucket: width is the most buckets one segment of the batch
    #: spans (it multiplies into num_groups ahead of the tag radices),
    #: count_pad the pow2 bucket of the window's bucket count (the
    #: fold's digit, the window's axis). start/step/count/origin ride
    #: params ('tb:*' i32 cells), NOT the plan, so a dashboard's sliding
    #: refresh window re-stages five scalar rows instead of retracing
    #: the kernel.
    tbucket: Tuple = ()
    #: True: an additive slot of this GROUP BY may add an Inf or a NaN (a
    #: FLOAT/DOUBLE column, a `div`, or a power that can overflow f32).
    #: In a one-hot product 0 * Inf = NaN would reach every group of the
    #: tile, so such a plan keeps the scatter (kernels.group_path). Out of
    #: the repr, and in the fingerprint only when set: plans without it
    #: compile under the names they always had.
    nonfinite: bool = field(default=False, repr=False)
    #: device fold of a GROUP BY (kernels.fold_groups): the GLOBAL key
    #: space the kernel's ONE [G, slots] result is laid over, a dense
    #: plan's union cardinality a group column, a compacted plan's one
    #: union count. () = the per-segment partials leave the device as
    #: [S, G, slots]. It follows from the staged segments' dictionaries,
    #: so the engine sets it after staging (`_group_fold`); like
    #: `nonfinite` out of the repr, and in the fingerprint only when set.
    group_fold: Tuple[int, ...] = field(default=(), repr=False)
