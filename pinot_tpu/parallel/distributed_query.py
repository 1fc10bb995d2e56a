"""Fully-sharded query step with explicit collectives.

This is the multi-chip analog of the single-device fused kernel in
ops/kernels.py: column blocks [S, D] are sharded over BOTH mesh axes
(segments x docs), each device computes its local masked partials, then
  * psum over `docs`     — combines doc-shard partials into per-segment
    results (the ICI collective replacing the reference's in-thread
    block loop, SURVEY.md §2.6 "Multi-stage shuffle / ICI" row)
  * psum over `segments` — combines per-segment partials into the final
    aggregate (replacing combine/BaseCombineOperator's merge +
    BrokerReduceService for the single-table case)
via jax.shard_map, so the collectives are explicit and
compile to ICI all-reduces rather than relying on GSPMD inference.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def distributed_query_step(mesh: Mesh):
    """Build the jit'd sharded query step for a fixed (range-filter + SUM +
    COUNT + per-group SUM) shape — the SSB Q1.x training-step analog.

    Inputs (global shapes):
      ids    [S, D] int32  filter column dictIds, sharded (segments, docs)
      vals   [S, D] f32    measure values,        sharded (segments, docs)
      gids   [S, D] int32  group column dictIds,  sharded (segments, docs)
      lo, hi [S]    int32  per-segment dictId bounds, sharded (segments,)
      ndocs  [S]    int32  actual docs per segment,   sharded (segments,)
      num_groups     int   static group-key space

    Returns (total_sum [], total_count [], group_sums [num_groups]) —
    all fully replicated after the collectives.
    """

    def step(ids, vals, gids, lo, hi, ndocs, doc_pos, num_groups):
        # local block: [S_loc, D_loc]; doc_pos [1, D_loc] carries each
        # column's GLOBAL doc index (shard-local arange would restart at 0)
        valid = doc_pos < ndocs[:, None]
        mask = (ids >= lo[:, None]) & (ids <= hi[:, None]) & valid
        contrib = jnp.where(mask, vals, 0.0)
        # per-segment partials on this doc shard
        part_sum = jnp.sum(contrib, axis=1)
        part_cnt = jnp.sum(mask, axis=1).astype(jnp.float32)
        # group partials via scatter-add on the local shard
        safe_keys = jnp.where(mask, gids, 0)
        part_groups = jax.vmap(
            lambda k, c: jnp.zeros((num_groups,), jnp.float32).at[k].add(c)
        )(safe_keys, contrib.astype(jnp.float32))
        # combine doc shards -> true per-segment results (ICI all-reduce)
        seg_sum = jax.lax.psum(part_sum, "docs")
        seg_cnt = jax.lax.psum(part_cnt, "docs")
        seg_groups = jax.lax.psum(part_groups, "docs")
        # combine segments -> final aggregate (second ICI all-reduce)
        total_sum = jax.lax.psum(jnp.sum(seg_sum), "segments")
        total_cnt = jax.lax.psum(jnp.sum(seg_cnt), "segments")
        group_sums = jax.lax.psum(jnp.sum(seg_groups, axis=0), "segments")
        return total_sum, total_cnt, group_sums

    def make(num_groups: int, D_shard: int = 0):  # D_shard kept for signature stability
        sm = shard_map(
            partial(step, num_groups=num_groups),
            mesh=mesh,
            in_specs=(P("segments", "docs"), P("segments", "docs"),
                      P("segments", "docs"), P("segments"), P("segments"),
                      P("segments"), P(None, "docs")),
            out_specs=(P(), P(), P()),
        )
        return jax.jit(sm)

    return make
