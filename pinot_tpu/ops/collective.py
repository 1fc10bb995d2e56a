"""Collective broker merge: the cross-segment partial fold ON DEVICE,
for ungrouped aggregations on an explicit mesh. (A GROUP BY's partials
are folded inside the plain-jit kernel on every engine,
kernels.fold_groups: one remap, one assembly.)

Reference parity: the reference broker/server merge per-segment partials
host-side (IndexedTable / the combine operators — SURVEY §2.7). On an
N-chip mesh engine that fold is the last host hop in the hot path: every
query ships [S, ...] per-segment partials over the link and reduces them
in Python. This module folds them where they already live — one
psum/pmin/pmax rendezvous over the WHOLE mesh (both the `segments` and
`docs` axes) inside the same shard_map the sharded kernels use, so a
query returns ONE merged row instead of S per-segment rows.

Layout contract (engine._assemble_merged is the only consumer):

  one query: [sum(slot widths) + S]   — merged slots at the same
             slot offsets _assemble uses (no leading matched column),
             then the per-segment matched counts as an [S] tail (the
             exact ExecutionStats the host fold would have summed).
  batched:   [B, L] — batch axis leading, same L per member, so the
             dispatch ring's split_packed contract holds unchanged.

Merge semantics per slot ride kernels._DOC_COMBINE — combining partials
across segments uses the same semiring as combining across doc shards
(sum-family psum, min pmin, max/hll pmax, hist/isum psum), so the local
segment-axis reduce + one collective over every mesh axis is exactly the
host fold's algebra, just associated differently. Bit-parity against the
host fold is property-tested in tests/test_mesh_scaling.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map

from pinot_tpu.ops import kernels
from pinot_tpu.ops.kernels import note_trace, plan_fingerprint
from pinot_tpu.ops.plan_ir import DevicePlan


def _member_fn(plan: DevicePlan, doc_shards: int, has_docs: bool):
    """Per-member shard-local compute: slot partials reduced over the
    LOCAL segment axis (pure jnp — vmappable; collectives are applied by
    the caller AFTER any batching, so a batch pays one rendezvous).
    Returns (tuple of locally-reduced slot arrays, local matched [S_loc])."""
    def member(cols, params, num_docs, D):
        d_local = D // doc_shards
        if has_docs:
            doc_pos = (jax.lax.axis_index("docs") * d_local
                       + jnp.arange(d_local, dtype=jnp.int32))[None, :]
        else:
            doc_pos = jnp.arange(D, dtype=jnp.int32)[None, :]
        valid = doc_pos < num_docs[:, None]
        if plan.valid_mask:
            valid = valid & cols["vmask"]
        slots, matched = kernels._compute_slots(plan, cols, params,
                                                valid, 0)
        # local fold over THIS shard's segments; axis 0 is the segment
        # axis for every slot shape here ([S_loc] scalar, [S_loc, w]
        # sketch)
        locs = []
        for (op, _v, _f), (_o, s) in zip(plan.agg_ops, slots):
            kind = kernels._doc_combine(op)
            if kind == "psum":
                locs.append(jnp.sum(s, axis=0))
            elif kind == "pmin":
                locs.append(jnp.min(s, axis=0))
            else:
                locs.append(jnp.max(s, axis=0))
        return tuple(locs), matched

    return member


def _collect_pack(plan: DevicePlan, locs, axes):
    """One collective per slot over EVERY mesh axis, then pack into the
    module's merged layout (rank-agnostic: a leading batch axis rides
    along untouched — the reductions already happened per member)."""
    merged = []
    for (op, _v, _f), s in zip(plan.agg_ops, locs):
        kind = kernels._doc_combine(op)
        if kind == "psum":
            merged.append(jax.lax.psum(s, axes))
        elif kind == "pmin":
            merged.append(jax.lax.pmin(s, axes))
        else:
            merged.append(jax.lax.pmax(s, axes))
    parts = [s[..., None] if kernels.slot_width(op) == 1 else s
             for (op, _v, _f), s in zip(plan.agg_ops, merged)]
    return jnp.concatenate(parts, axis=-1)        # [..., sum(widths)]


def _mesh_geometry(mesh):
    axes = tuple(mesh.axis_names)
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return axes, "docs" in axes, shape.get("docs", 1)


def _matched_tail(matched, seg_shards: int, axes):
    """Global [..., S] matched-count tail, built INSIDE the body: each
    segment shard scatters its local counts into its slice of a zeroed
    [S] vector and ONE psum over every mesh axis fills it (doc shards'
    halves add; other segment shards' zeros are the identity). Folding
    the tail in-body keeps the kernel output a single fully-replicated
    array — concatenating a replicated shard_map output with a
    segment-sharded one inside the same jit miscompiles on this jax
    (the partitioner re-reduces the replicated operand over the doc
    axis, doubling every merged slot)."""
    s_loc = matched.shape[-1]
    full = list(matched.shape)
    full[-1] = s_loc * seg_shards
    off = jax.lax.axis_index("segments") * s_loc
    idx = (jnp.int32(0),) * (len(full) - 1) + (off,)
    scattered = jax.lax.dynamic_update_slice(
        jnp.zeros(tuple(full), matched.dtype), matched, idx)
    return jax.lax.psum(scattered, axes)


def make_merged_kernel(plan: DevicePlan, mesh):
    """Single-query collective merge: fn(cols, params, num_docs, D, G)
    -> ONE packed [L] row (layout in the module docstring). D is the
    padded GLOBAL doc count; G is 0 (the launch's signature)."""
    from jax.sharding import PartitionSpec as P

    axes, has_docs, doc_shards = _mesh_geometry(mesh)
    seg_shards = dict(zip(mesh.axis_names,
                          mesh.devices.shape))["segments"]
    fp = plan_fingerprint(plan)
    member = _member_fn(plan, doc_shards, has_docs)

    def local(cols, params, num_docs, D, G=0):
        # body runs at trace time: counts compiles
        note_trace("merged", fp, (int(num_docs.shape[-1]), D, G))
        locs, matched = member(cols, params, num_docs, D)
        flat = _collect_pack(plan, locs, axes)
        tail = _matched_tail(matched, seg_shards, axes)
        return jnp.concatenate([flat, tail.astype(flat.dtype)], axis=-1)

    col_spec = P("segments", "docs") if has_docs else P("segments", None)

    def fn(cols, params, num_docs, D, G=0):
        params, num_docs = kernels.unpack_params(plan, params, num_docs)
        in_specs = (
            {k: col_spec for k in cols},
            {k: P("segments", *([None] * (v.ndim - 1)))
             for k, v in params.items()},
            P("segments"),
        )
        sm = shard_map(
            functools.partial(local, D=D, G=G), mesh=mesh,
            in_specs=in_specs,
            # the whole packed row is replicated by construction: every
            # slot AND the matched tail are reduced over every mesh axis
            out_specs=P(None),
        )
        return sm(cols, params, num_docs)

    return jax.jit(fn, static_argnames=("D", "G"))


@functools.lru_cache(maxsize=256)
def compiled_merged_kernel(plan: DevicePlan, mesh):
    return make_merged_kernel(plan, mesh)


def make_batched_merged_kernel(plan: DevicePlan, mesh, B: int,
                               stacked: bool = False):
    """Batched collective merge: vmap INSIDE shard_map exactly like
    kernels.make_batched_sharded_kernel — mesh axes outermost, batch
    innermost, so B coalesced queries pay ONE set of collectives over
    the stacked per-member partials. Output [B, L]; the dispatch ring's
    pad-to-bucket + split_packed contract holds unchanged."""
    from jax.sharding import PartitionSpec as P

    axes, has_docs, doc_shards = _mesh_geometry(mesh)
    seg_shards = dict(zip(mesh.axis_names,
                          mesh.devices.shape))["segments"]
    fp = plan_fingerprint(plan)
    member = _member_fn(plan, doc_shards, has_docs)
    kind = "merged_batched_stacked" if stacked else "merged_batched"

    def local(cols, params, num_docs, D, G=0):
        note_trace(kind, fp, (B, D, G))
        # the index array keeps vmap fed when a filterless plan has
        # EMPTY per-query params (vmap rejects an all-empty pytree)
        idx = jnp.arange(B, dtype=jnp.int32)
        in_axes = (0 if stacked else None, 0, 0 if stacked else None, 0)
        locs, matched = jax.vmap(
            lambda c, p, nd, _i: member(c, p, nd, D),
            in_axes=in_axes)(cols, params, num_docs, idx)
        flat = _collect_pack(plan, locs, axes)
        tail = _matched_tail(matched, seg_shards, axes)
        return jnp.concatenate([flat, tail.astype(flat.dtype)], axis=-1)

    def fn(cols, plist, num_docs, D, G=0):
        ps, ns = kernels.unpack_batch(plan, plist, num_docs, stacked)
        if stacked:
            cs = kernels.stack_members(cols)
            col_spec = P(None, "segments", "docs") if has_docs \
                else P(None, "segments", None)
            nd_spec = P(None, "segments")
        else:
            cs = cols
            col_spec = P("segments", "docs") if has_docs \
                else P("segments", None)
            nd_spec = P("segments")
        in_specs = (
            {k: col_spec for k in cs},
            {k: P(None, "segments", *([None] * (v.ndim - 2)))
             for k, v in ps.items()},
            nd_spec,
        )
        sm = shard_map(
            functools.partial(local, D=D, G=G), mesh=mesh,
            in_specs=in_specs,
            out_specs=P(None, None),
        )
        return sm(cs, ps, ns)

    return jax.jit(fn, static_argnames=("D", "G"))


@functools.lru_cache(maxsize=256)
def compiled_batched_merged_kernel(plan: DevicePlan, mesh, B: int,
                                   stacked: bool = False):
    """One jit per (plan, mesh, B bucket, stacked?) —
    fn(cols|clist, plist, num_docs|ndlist, D, G)."""
    return make_batched_merged_kernel(plan, mesh, B, stacked)
