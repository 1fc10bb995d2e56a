"""TpuOperatorExecutor: stages segment columns into HBM and runs the
fused query kernel across segments.

Reference parity: this replaces the reference's per-segment
operator chain + combine fan-out (SURVEY.md §3.2 hot loop:
AggregationOperator/GroupByOperator over ProjectionOperator/DocIdSetOperator
with per-thread segment tasks, combine/BaseCombineOperator.java:54) with
ONE device program over stacked [num_segments, padded_docs] blocks.

Responsibilities:
  * supports(ctx): structural check — which query shapes offload
  * plan: QueryContext -> DevicePlan IR (ops/plan_ir.py)
  * staging: WHICH rows a plan needs, padded to power-of-two doc buckets
    to bound retraces; the tiers that keep them (ops/staging.py)
  * per-segment predicate resolution -> kernel parameter arrays
  * multi-device: inputs sharded over the mesh's `segments` axis
  * result assembly back into AggregationResult/GroupByResult intermediates
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from pinot_tpu.ops import clp_device
from pinot_tpu.ops import collective
from pinot_tpu.ops import device as device_mod
from pinot_tpu.ops import dispatch as dispatch_mod
from pinot_tpu.ops import kernels
from pinot_tpu.ops import residency as residency_mod
from pinot_tpu.ops import startree_device
from pinot_tpu.ops import timeseries_device
from pinot_tpu.ops import vector_device
from pinot_tpu.ops.dispatch import KernelDispatcher, Launch
from pinot_tpu.ops.plan_ir import (
    NUM_DOCS, PACK, DeviceLeaf, DevicePlan, pack_params,
)
from pinot_tpu.ops.staging import BlockStager, batch_id
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.expressions import (
    Expression, Function, Identifier, Literal)
from pinot_tpu.query.filter import resolve_predicate
from pinot_tpu.query.results import (
    AggregationResult, CodedColumn, ExecutionStats, GroupByResult)
from pinot_tpu.segment.loader import DataSource, ImmutableSegment
from pinot_tpu.utils import accounting, tracing
from pinot_tpu.utils.config import PinotConfiguration
from pinot_tpu.utils.failpoints import fire

MAX_DEVICE_GROUPS = 1 << 20
#: cap on the [S, G, slots] group-by result buffer (f32/f64 accumulators)
MAX_GROUP_RESULT_BYTES = 1 << 31
_LEAF_RANGE_FUNCS = {
    "equals", "between", "greater_than", "greater_than_or_equal",
    "less_than", "less_than_or_equal",
}
_LEAF_LUT_FUNCS = {"in", "not_in", "like", "regexp_like"}


def _pow2(n: int, floor: int = 128) -> int:
    v = floor
    while v < n:
        v *= 2
    return v


# doc-padding cap: counts are packed in float32 when x64 is off, which is
# exact only below 2^24; segments larger than this are rejected to host
MAX_DOCS_PER_SEGMENT = 1 << 24


class TpuOperatorExecutor:
    def __init__(self, devices: Optional[Sequence] = None, mesh=None,
                 config=None, metrics_labels=None):
        """mesh: an explicit (segments, docs) jax Mesh — blocks shard over
        BOTH axes and the kernel runs under shard_map with psum/pmin/pmax
        collectives over `docs` (SURVEY §2.6 rows 6-7). Without one, >1
        device gets a segments-only mesh (GSPMD partitions the reductions);
        one device runs the plain jit kernel.
        config: a PinotConfiguration for the cache budgets and the
        dispatch-ring knobs (the server passes its instance config
        through; None reads env/defaults).
        metrics_labels: labels for the dispatcher's metrics (the server
        passes its instance id)."""
        device_mod.configure_compile_cache()
        # the collector's pauses on this process's spans and, in a
        # running profile, as `pinot:gc` annotations
        tracing.install_gc_probe("server", dispatch_mod.gc_annotation)
        self._doc_axis = 1
        #: collective broker merge (shard_map + psum: an UNGROUPED
        #: aggregation's partials become one row) engages only on an
        #: EXPLICIT mesh; one device and the implicit >1-device segments
        #: mesh below keep one result a segment there, which the tier-2
        #: segment cache is keyed by. A GROUP BY's partials are folded
        #: inside the plain-jit kernel on every engine (`_group_fold`;
        #: GSPMD makes the all-reduce on a segments mesh)
        self._explicit_mesh = mesh is not None
        if mesh is not None:
            self._mesh = mesh
            self.devices = list(mesh.devices.flat)
            shape = dict(zip(mesh.axis_names, mesh.devices.shape))
            self._seg_axis = shape.get("segments", 1)
            self._doc_axis = shape.get("docs", 1)
        else:
            self.devices = list(devices) if devices is not None \
                else jax.devices()
            self._mesh = None
            self._seg_axis = max(len(self.devices), 1)
            if len(self.devices) > 1:
                from jax.sharding import Mesh
                self._mesh = Mesh(np.array(self.devices), ("segments",))
        _cfg = config or PinotConfiguration()
        self._labels = metrics_labels
        #: pipelined dispatch stage: ring + micro-batching + fetch
        #: overlap (ops/dispatch.py); owns NO engine state — the block
        #: look-ups stay under the staging lock, launches ride the ring
        self._dispatcher = KernelDispatcher(config=_cfg,
                                            labels=metrics_labels)
        self._metrics = self._dispatcher._metrics
        #: the cache tiers and the one path a row takes through them
        #: (ops/staging.py); the legs below say only what to stage
        self.stager = BlockStager(self.devices, self._mesh, config=_cfg,
                                  metrics=self._metrics,
                                  labels=metrics_labels)
        #: the staging lock is the stager's, and guards the stager and
        #: nothing else: a query holds it for its block look-ups and the
        #: parameter-cache probe (`_staging_lock`); plan, literal resolve,
        #: the packed parameters, launch and fetch all run outside it
        self._engine_lock = self.stager.lock
        #: cross-table shape-bucketed batching (the kernel-factory key):
        #: pad S to pow2 buckets so fingerprint-equal queries over
        #: DIFFERENT tables/partitions share a coalesce key; doc buckets
        #: above doc.bucket.max keep the legacy same-batch key (a stacked
        #: [B, S, D] copy of huge blocks would blow the HBM budget).
        #: Gated on batching being POSSIBLE at all — when dispatch is
        #: serialized or batch.max=1, pow2 S padding would inflate every
        #: staged block for a coalesce that can never happen
        self._cross_table = (
            _cfg.get_bool("pinot.server.dispatch.batch.cross.table", True)
            and self._dispatcher.mode != "serialized"
            and self._dispatcher.batch_max > 1)
        self._doc_bucket_max = _cfg.get_int(
            "pinot.server.dispatch.doc.bucket.max")
        #: star-tree device leg (ops/startree_device.py): fitted queries
        #: aggregate pre-agg records through the kernel factory instead
        #: of scanning raw rows
        self._startree_enabled = _cfg.get_bool(
            "pinot.server.startree.enabled", True)
        #: CLP log-column LIKE/regex pushdown (ops/clp_device.py):
        #: patterns compile to logtype LUTs + variable-slot conditions
        #: evaluated as 'clp' filter leaves through the same kernel
        #: factory
        self._clp_enabled = _cfg.get_bool(
            "pinot.server.clp.enabled", True)
        #: vector-similarity device leg (ops/vector_device.py): ANN
        #: top-K as one batched matmul + lax.top_k over staged vector
        #: blocks
        self._vector_enabled = _cfg.get_bool(
            "pinot.server.vector.enabled", True)
        #: time-series device bucket leg (ops/timeseries_device.py):
        #: floor((t - start) / step) group-bys fuse the bucket id into
        #: the group-by kernel's scatter key instead of falling back to
        #: the host expression-column path
        self._ts_bucket_enabled = _cfg.get_bool(
            "pinot.server.timeseries.bucket.enabled", True)
        #: collective broker merge (ops/collective.py): on a mesh engine
        #: an ungrouped aggregation's per-segment partial fold becomes
        #: one on-device psum/pmin/pmax over the whole mesh; the host
        #: fold stays reachable as the escape hatch when this is off
        self._collective_merge = _cfg.get_bool(
            "pinot.server.mesh.collective.merge", True)
        #: host-factorized global group-key remap params of the device
        #: fold per (segment batch, group columns) — built once, re-used
        #: across queries
        self._gmap_cache: "OrderedDict[tuple, Any]" = OrderedDict()
        #: what a mesh engine's grouped programs exchange between chips,
        #: a compiled program an entry (`_mesh_exchange`)
        self._exchange_cache: Dict[tuple, Tuple[Optional[int], ...]] = {}

    # ------------------------------------------------------------------
    # capability check (structural)
    # ------------------------------------------------------------------
    #: cap on selection/order-by top-K offload (limit + offset)
    TOPN_MAX_K = 8192

    #: hard backstop on any single dispatcher future wait
    #: (dispatch_mod.wait_result): queries are bounded by their own
    #: deadline checker well before this — the cap exists for
    #: budget-less internal callers (warmup/prestage) so a wedged
    #: device link surfaces as an error instead of a parked thread.
    #: Aliased, not duplicated: ONE policy constant owns the backstop.
    LAUNCH_WAIT_CAP_S = dispatch_mod.DEFAULT_WAIT_CAP_S

    def supports(self, ctx: QueryContext) -> bool:
        if ctx.distinct:
            return self._supports_distinct(ctx)
        if not ctx.aggregations:
            return self._supports_selection(ctx)
        for f in ctx.agg_filters:
            # FILTER (WHERE ...) aggs offload as per-slot masks when the
            # condition has a device filter shape
            if f is not None and not self._filter_shape_ok(f):
                return False
        if any(fn.device_spec is None for fn in ctx.agg_functions):
            return False
        if ctx.group_by and any(
                ":" in op for fn in ctx.agg_functions
                for op in fn.device_spec.ops):
            # sketch slots (hll/hist) are vector-valued; the grouped packed
            # layout is scalar-per-slot — grouped sketches stay host-side
            return False
        for node in ctx.aggregations:
            if node.args and not (isinstance(node.args[0], Identifier)
                                  and node.args[0].name == "*"):
                if self._value_ir_shape(node.args[0]) is None:
                    return False
            if node.name == "countmv":
                return False
        for i, g in enumerate(ctx.group_by):
            if isinstance(g, Identifier):
                continue
            if (i == 0 and self._ts_bucket_enabled
                    and not self._explicit_mesh and self._doc_axis == 1
                    and timeseries_device.extract_bucket(g) is not None):
                # time-series leaf shape: the leading floor((t-start)/
                # step) group-by fuses into the kernel's scatter key
                # (detailed window/metadata admission happens in _plan).
                # The implicit >1-device segments mesh keeps per-segment
                # partials through the SAME group-by kernel, so it
                # qualifies; the explicit collective-merge mesh does not
                continue
            return False
        if ctx.filter is not None and not self._filter_shape_ok(ctx.filter):
            return False
        return True

    def _supports_distinct(self, ctx: QueryContext) -> bool:
        """DISTINCT over dict columns rides the group-by kernel (a
        presence-only group-by); detailed stagability checks happen in
        _plan with segment metadata in hand."""
        if not ctx.select or ctx.aggregations:
            return False
        for e in ctx.select:
            if not isinstance(e, Identifier) or e.name == "*":
                return False
        if ctx.filter is not None and not self._filter_shape_ok(ctx.filter):
            return False
        return True

    def _supports_selection(self, ctx: QueryContext) -> bool:
        """Selection (+ at most one ORDER BY key) offloads as a device
        top-K over the order value: only winning docs are materialized
        (ref SelectionOrderByOperator / MinMaxValueBasedSelection
        OrderByCombineOperator)."""
        if ctx.distinct or ctx.aggregations:
            return False
        if ctx.filter is not None \
                and vector_device.contains_vector(ctx.filter):
            # ANN leg: vector_similarity is not a scan-filter leaf — it
            # routes to the vector kernel (plan-time fallback with a
            # metered reason keeps host parity on every miss)
            return ctx.limit + ctx.offset > 0
        if len(ctx.order_by) > 1:
            return False
        if ctx.filter is None and not ctx.order_by:
            return False  # LIMIT-only: host early-exit is already O(K)
        k = ctx.limit + ctx.offset
        if k <= 0 or k > self.TOPN_MAX_K:
            return False
        if ctx.order_by:
            e, _asc = ctx.order_by[0]
            if not (isinstance(e, Identifier)
                    or self._value_ir_shape(e) is not None):
                return False
        if ctx.filter is not None and not self._filter_shape_ok(ctx.filter):
            return False
        return True

    def _filter_shape_ok(self, e: Expression) -> bool:
        if not isinstance(e, Function):
            return False
        if e.name in ("and", "or"):
            return all(self._filter_shape_ok(a) for a in e.args)
        if e.name == "not":
            return self._filter_shape_ok(e.args[0])
        if e.name in _LEAF_RANGE_FUNCS | _LEAF_LUT_FUNCS | {"not_equals"}:
            return bool(e.args) and isinstance(e.args[0], Identifier) and all(
                isinstance(a, Literal) for a in e.args[1:])
        return False

    def _value_ir_shape(self, e: Expression) -> Optional[tuple]:
        """Structural value IR (column stagability checked at execute)."""
        if isinstance(e, Identifier):
            return ("col", e.name)
        if isinstance(e, Literal):
            if isinstance(e.value, (int, float)) and not isinstance(e.value, bool):
                return ("lit", float(e.value))
            return None
        if isinstance(e, Function):
            ops = {"plus": "add", "minus": "sub", "times": "mul", "divide": "div"}
            if e.name in ops and len(e.args) == 2:
                a = self._value_ir_shape(e.args[0])
                b = self._value_ir_shape(e.args[1])
                if a is not None and b is not None:
                    return (ops[e.name], a, b)
        return None

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------
    def _needs_cpu_ordering(self, kernel) -> bool:
        """True when this kernel's execution must be ordered process-wide
        (dispatch.py's collective lock): PARTITIONED execution on host
        devices. EVERY staged kernel on a mesh engine is partitioned:
        _put stages inputs with NamedSharding, so even the plain-jit
        kernels (group-by without a docs axis, top-N) compile to GSPMD
        programs with all-gathers — the doc_axis==1 compiled_kernel path
        is exactly what deadlocked the suite, so don't narrow this to the
        shard_map branch. Single device, real accelerators, and non-XLA
        kernel stand-ins never order."""
        return self._mesh is not None and bool(self.devices) \
            and getattr(self.devices[0], "platform", "") == "cpu" \
            and isinstance(kernel, jax.stages.Wrapped)

    def _prepare_agg(self, segments: List[ImmutableSegment],
                     ctx: QueryContext, cancel_check=None,
                     parent_span=None, slip=None):
        """Plan, then stage (`_stage`: only its block look-ups hold the
        staging lock), then wrap the launch for the dispatch ring.
        Returns (plan, slots_of_fn, S_real, Launch, minfo), or None ->
        host fallback.

        parent_span: explicit tracing.SpanHandle for callers off the
        request thread (execute_async stages on the staging pool, where
        the trace contextvar doesn't flow); sync callers inherit the
        contextvar. The DeviceDispatch child span carries staging ms
        split into plan / wait for the lock / block look-ups under it /
        parameters, and the host->device bytes of THIS pass (`_StagePass`
        counts them itself: passes overlap, so no odometer diff would be
        one query's)."""
        dsp = self._dispatch_span(ctx, "agg", parent_span)
        info = _StagePass(dsp)
        plan_info = self._plan(segments, ctx)
        if plan_info is None:
            self.scan_fallback("plan")
            if dsp is not None:
                dsp.end(outcome="hostFallback")
            return None
        plan, slots_of_fn = plan_info
        # resolve the kernel BEFORE staging: non-batchable launches
        # (non-jit kernel stand-ins) must not pay pow2 S padding for
        # a coalesce they can never join
        if self._doc_axis > 1:
            # doc-sharded engines batch too: the factory vmaps
            # INSIDE shard_map (kernels.make_batched_sharded_kernel)
            kernel = kernels.compiled_sharded_kernel(plan, self._mesh)
            batchable = isinstance(kernel, jax.stages.Wrapped)
            factory = (lambda B, stacked, _p=plan, _m=self._mesh:
                       kernels.compiled_batched_sharded_kernel(
                           _p, _m, B, stacked))
            dedup_factory = None  # sharded in_specs are per-member
        else:
            kernel, factory, dedup_factory = self._plain_kernels(plan)
            batchable = isinstance(kernel, jax.stages.Wrapped)
        info.planned()
        try:
            cols, params, S, S_real, D, G = self._stage(
                segments, ctx, plan, batchable=batchable, info=info)
        except _NotStageable:
            self.scan_fallback("staging")
            if dsp is not None:
                dsp.end(outcome="hostFallback")
            return None
        staged_ts = self._staging_attrs(info, S=S, D=D, G=G)
        # collective broker merge (ops/collective.py): fold an
        # UNGROUPED aggregation's per-segment partials on device — one
        # psum/pmin/pmax over the whole mesh — instead of shipping
        # [S, ...] rows to the host fold. Any gate trips back to the
        # per-segment launch below, metered by reason. A GROUP BY has
        # its own fold further down, on every engine alike
        minfo = None
        if self._explicit_mesh and len(self.devices) > 1 \
                and batchable and not plan.group_cols:
            if not self._collective_merge:
                self._merge_fallback("disabled")
            else:
                chaos = False
                try:
                    fire("server.mesh.collective", table=ctx.table,
                         mode="agg")
                except BaseException:  # noqa: BLE001 — armed chaos
                    self._merge_fallback("chaos")  # -> host fold
                    chaos = True
                if not chaos:
                    try:
                        minfo = self._merged_prepare(segments, S_real, S)
                    except _MergeFallback as e:
                        self._merge_fallback(e.reason)
                    except Exception:  # noqa: BLE001 — never fail
                        self._merge_fallback("staging")  # the query
        # one grouped result a query leaves the device: the per-segment
        # partials of a GROUP BY are folded inside the kernel over a
        # global key space (kernels.fold_groups), unless
        # `kernels.group_fold` says host
        if (plan.group_cols or plan.tbucket) and batchable:
            folded = self._group_fold(segments, plan, params, S, G, info)
            if folded is not None:
                plan, params, minfo = folded
                kernel, factory, dedup_factory = self._plain_kernels(plan)
        if slip is not None:
            slip.add(transfer_bytes=info.xfer_bytes)
        self._meter("scan_served")
        num_groups = plan.num_groups or G
        scatter = None
        # the fused time bucket of this query's window: how its keys
        # decode (`_assemble*`), and what the span and meter say
        bucket = timeseries_device.plan_bucket(
            ctx.group_by[0], ctx.filter, segments) if plan.tbucket else None
        merged = minfo is not None and not plan.group_fold
        if merged:
            self._meter("mesh_merge_served")
            kernel = collective.compiled_merged_kernel(plan, self._mesh)
            factory = (lambda B, stacked, _p=plan, _m=self._mesh:
                       collective.compiled_batched_merged_kernel(
                           _p, _m, B, stacked))
            dedup_factory = None  # merged in_specs are per-member
        if num_groups:
            # which way the additive slots of this GROUP BY run: the
            # kernel builder's own decision, asked with one shard's shapes
            shard_docs = D // self._doc_axis
            path = kernels.group_path(
                num_groups, shard_docs, kernels._value_dtype(),
                finite=not plan.nonfinite)
            fold = "device" if plan.group_fold else "host"
            # what reaches XLA's scatter-add follows from the rows the
            # segments keep, which the fetched result carries
            scatter = (plan, num_groups, S, D, shard_docs, path)
            self._meter("group_path", path=path)
            self._meter("group_fold", where=fold)
            if dsp is not None:
                dsp.set(groupPath=path, groupKeySpace=num_groups,
                        groupFold=fold)
            if bucket is not None:
                unit = timeseries_device.bucket_label(bucket)
                self._meter("time_bucket", unit=unit, fold=fold,
                            width=str(bucket.width))
                if dsp is not None:
                    dsp.set(timeBucket=unit, bucketCount=bucket.count,
                            bucketWidth=bucket.width,
                            bucketPad=bucket.count_pad)
            if self._mesh is not None and batchable:
                exchanged, gathered = self._mesh_exchange(
                    kernel, S, D, G, cols, params)
                if exchanged is not None:
                    self._meter("mesh_exchange_bytes", exchanged)
                if dsp is not None:
                    dsp.set(meshExchangeBytes=exchanged,
                            meshGatherBytes=gathered)
        launch = Launch(
            # num_docs rides the packed parameters (plan_ir.PACK), a
            # host array here: the call's own argument, one transfer
            call=lambda: kernel(cols, params, None, D=D, G=G),
            plan=plan, cols=cols, params=params, num_docs=None,
            D=D, G=G,
            batch_key=self._coalesce_key(
                plan, segments, S, D, G, cols, params, batchable,
                merged=merged),
            cols_key=self._cols_key(segments, plan),
            factory=factory, dedup_factory=dedup_factory,
            collective=self._needs_cpu_ordering(kernel),
            cancel_check=cancel_check,
            site_ctx={"table": ctx.table, "mode": "agg"}, span=dsp,
            slip=slip, docs=sum(s.num_docs for s in segments),
            staged_ts=staged_ts)
        return plan, slots_of_fn, S_real, launch, minfo, scatter, bucket

    def _plain_kernels(self, plan: DevicePlan):
        """(kernel, batched factory, dedup factory) of a plan on the
        plain-jit route (no doc sharding, no collective merge). The
        engine's mesh rides every look-up (None on one device): GSPMD
        partitions the body from the staged blocks' shardings, and the
        one part it cannot split, the Pallas group-by pass, runs a shard
        of the segment axis under a shard_map over that mesh."""
        mesh = self._mesh
        return (kernels.compiled_kernel(plan, mesh),
                lambda B, stacked: kernels.compiled_batched_kernel(
                    plan, B, stacked, mesh),
                lambda B, U: kernels.compiled_batched_dedup_kernel(
                    plan, B, U, mesh))

    def _mesh_exchange(self, kernel, S, D, G, cols, params):
        """(bytes, the all-gathers' part) a chip hands to the collectives
        of a mesh engine's grouped program a launch, (None, None) where
        the program cannot be read. Read ONCE a compiled program, from the
        program itself (`device.collective_bytes`), when a query first
        stages its shapes, and kept: lowering the kernel again with the
        launch's own arguments meets jit's caches, so the read costs a
        compile only where the launch behind it would have paid it. The
        single-query program's: a coalesced batch of B runs another,
        whose collectives carry B rows."""
        key = (kernel, S, D, G, _shape_sig(cols, params))
        if key not in self._exchange_cache:
            try:
                moved = device_mod.collective_bytes(
                    kernel.lower(cols, params, None, D=D, G=G)
                    .compile().as_text())
            except Exception:  # noqa: BLE001 — an observation, no failure
                moved = (None, None)
            self._exchange_cache[key] = moved
        return self._exchange_cache[key]

    def _coalesce_key(self, plan, segments, S, D, G, cols, params,
                      batchable: bool, merged: bool = False):
        """The dispatch ring's coalesce key of a staged launch (None:
        it never batches). The mesh shape rides the key: launches never
        pair across differently-sharded engines (or merged with
        unmerged)."""
        if not batchable or self._dispatcher.batch_max <= 1:
            return None
        mesh_sig = ("mesh", self._mesh, self._doc_axis, merged)
        if self._cross_table and D <= self._doc_bucket_max:
            # the kernel-factory key: (plan fingerprint, shape bucket) —
            # fingerprint-equal queries batch across tables and
            # partitions whenever their padded buckets and staged-array
            # shapes/dtypes line up (the signature catches per-table
            # variation: LUT cardinality pads, id dtype width)
            return (plan, S, D, G, _shape_sig(cols, params), mesh_sig)
        # legacy key: identical staged segment batch only
        return (plan, batch_id(segments), D, G, mesh_sig)

    # ------------------------------------------------------------------
    # one result a batch: the collective merge (ops/collective.py,
    # ungrouped) and the GROUP BY fold (kernels.fold_groups)
    # ------------------------------------------------------------------
    #: cap on the host-factorized group-remap params shipped per
    #: (segment batch, group columns) — past this the remap upload would
    #: rival the partial rows it saves, so the host fold wins
    GMAP_MAX_BYTES = 1 << 26
    GMAP_CACHE_ENTRIES = 64

    def _merge_fallback(self, reason: str) -> None:
        """mesh_merge_fallback{reason=}: why an eligible mesh launch kept
        the host IndexedTable fold (labeled like startree_fallback)."""
        self._meter("mesh_merge_fallback", reason=reason)

    def _merged_prepare(self, segments, S_real: int, S: int):
        """Gate of the collective merge (ungrouped aggregations on an
        explicit mesh): its minfo, or raises _MergeFallback(reason)."""
        if kernels._value_dtype() == jnp.float32:
            # merged counts/isum halves sum ACROSS segments: exactness
            # needs total docs < 2^24 and < 4096 real segments (the
            # per-segment path only needs it per segment)
            total = sum(int(seg.num_docs) for seg in segments)
            if total >= MAX_DOCS_PER_SEGMENT or S_real >= 4096:
                raise _MergeFallback("precision")
        return {"S": S, "G": 0}

    def _group_fold(self, segments, plan: DevicePlan, params, S: int,
                    G_local: int, info):
        """A GROUP BY's device fold, prepared: (the plan with its
        `group_fold` set, params with the inverse remap tables beside
        the pack, the assembly's info), or None where the per-segment
        partials leave the device (`groupFold` = host): the doc-sharded
        kernels (a shard_map body cannot take the fold's gathers over
        its local segments alone), or a remap `kernels.group_fold`
        turned down. Runs after
        staging, outside the staging lock."""
        if self._doc_axis > 1 or self._fold_where(plan) == "host":
            return None  # the last: what the plan alone rules out
        try:
            gparams, G_out, decode = self._group_remap(
                segments, plan, S, G_local, info)
        except _MergeFallback:
            return None
        # a fused time bucket's digit is the fold's last: the window's
        # count_pad buckets, each segment's shifted by its origin
        fold = (G_out,) if plan.group_compact \
            else tuple(decode[1]) + plan.tbucket[2:]
        params = dict(params)
        params.update(gparams)
        return (dataclasses.replace(plan, group_fold=fold), params,
                {"G": G_out, "decode": decode})

    def _fold_where(self, plan: DevicePlan, out_groups: int = 0,
                    remap_bytes: int = 0) -> str:
        """`kernels.group_fold` against the engine's own caps."""
        return kernels.group_fold(plan, out_groups, remap_bytes,
                                  MAX_DEVICE_GROUPS, self.GMAP_MAX_BYTES)

    def _group_remap(self, segments, plan: DevicePlan, S: int,
                     G_local: int, info=None):
        """Factorize a GLOBAL group-key space once host-side: dictIds
        and compact codes are segment-local, so the device can only fold
        groups across segments through a remap to shared indices. The
        fold gathers every GROUP's partial (kernels.fold_groups):
        compact plans ship one [S, G] global->code table, dense plans
        per-column [S, U] union-index->dictId tables, -1 where a segment
        lacks the key; G is the union's key space with every digit
        padded to a pow2 (one compiled shape for batches whose unions
        differ by a few values), and `kernels.group_fold` decides
        whether it is worth it.

        Cached per (segment batch, group columns), so plans that differ
        in their aggregates share it, a remap that was turned down too;
        returns (params, G, decode info for _assemble_folded). The
        staging lock is held for the cache probe and the insert only:
        the factorization and its puts run outside it."""
        key = (batch_id(segments), plan.group_cols, plan.group_strides,
               plan.group_compact, plan.tbucket, S, G_local)
        with self._engine_lock:
            ent = self._gmap_cache.get(key)
            if ent is not None:
                self._gmap_cache.move_to_end(key)
                if isinstance(ent, str):
                    # turned down once: not factorized again
                    raise _MergeFallback(ent)
                return ent
        try:
            ent = self._factorize_groups(segments, plan, S, info)
        except _MergeFallback as e:
            ent = e.reason
        with self._engine_lock:
            self._gmap_cache[key] = ent
            while len(self._gmap_cache) > self.GMAP_CACHE_ENTRIES:
                self._gmap_cache.popitem(last=False)
        if isinstance(ent, str):
            raise _MergeFallback(ent)
        return ent

    def _factorize_groups(self, segments, plan: DevicePlan, S: int, info):
        """What a miss of `_group_remap`'s cache builds."""
        if plan.group_compact:
            per_seg = []
            for seg in segments:
                _codes, table = self._segment_gkey(seg, plan)
                dicts = [seg.data_source(c).dictionary
                         for c in plan.group_cols]
                cols_vals = [d.get_values(table[:, j])
                             for j, d in enumerate(dicts)]
                per_seg.append([tuple(_py(c[i]) for c in cols_vals)
                                for i in range(table.shape[0])])
            union = sorted(set().union(*map(set, per_seg))) \
                if per_seg else []
            index = {t: i for i, t in enumerate(union)}
            G_out = _pow2(max(len(union), 1), floor=8)
            if self._fold_where(plan, G_out, S * G_out * 4) == "host":
                raise _MergeFallback("groups")
            ginv = np.full((S, G_out), -1, np.int32)
            for s, tuples in enumerate(per_seg):
                for code, t in enumerate(tuples):
                    ginv[s, index[t]] = code
            # decode: a column a group expression, global index -> value
            return ({"ginv": self._put(ginv, info)}, G_out,
                    [list(c) for c in zip(*union)]
                    or [[] for _ in plan.group_cols])
        unions = []
        per_col_vals = []
        for colname in plan.group_cols:
            vals = []
            for seg in segments:
                card = max(
                    int(seg.metadata.columns[colname].cardinality), 1)
                d = seg.data_source(colname).dictionary
                vals.append(np.asarray(d.get_values(np.arange(card))))
            per_col_vals.append(vals)
            unions.append(np.unique(np.concatenate(vals)))
        # the key space in pow2 digits, as S, D and a compact G are
        # bucketed: a window that slides over time-partitioned segments
        # changes a union by a value or two, and the kernel's shape must
        # not follow it. A padded digit is a group no segment has. A
        # fused time bucket's digit (the window's count_pad, a pow2
        # already) stays the lowest: the kernel shifts each segment's
        # onto it, with no table
        cards = [_pow2(len(u), floor=8) for u in unions]
        G_out = math.prod(cards) * math.prod(plan.tbucket[2:])
        if self._fold_where(plan, G_out,
                            sum(S * c * 4 for c in cards)) == "host":
            raise _MergeFallback("groups")
        strides = []
        st = G_out
        for c in cards:
            st //= c
            strides.append(st)
        gparams = {}
        for ci, (union, vals) in enumerate(zip(unions, per_col_vals)):
            gi = np.full((S, cards[ci]), -1, np.int32)
            for s, v in enumerate(vals):
                gi[s, np.searchsorted(union, v)] = \
                    np.arange(len(v), dtype=np.int32)
            gparams[f"ginv{ci}"] = self._put(gi, info)
        return gparams, G_out, (tuple(strides), tuple(cards), tuple(unions))

    # ------------------------------------------------------------------
    # star-tree device leg (ops/startree_device.py)
    # ------------------------------------------------------------------
    def _startree_candidate(self, segments) -> bool:
        """Cheap structural gate before the star-tree planner runs: only
        batches where EVERY segment carries a tree reach the planner, so
        treeless tables pay one getattr per segment and the fallback
        meter never fires where a tree could never serve (its reason
        labels stay meaningful). Upsert guard (PR 11): a partially-valid
        bitmap means pre-agg records include retracted rows, which no
        selection mask over the PRE-AGG table can subtract — scan path
        only (the host star-tree executor applies the same rule).
        Doc-sharded meshes keep the scan leg: the star-tree kernel has
        no shard_map variant, and pre-agg tables are small enough that
        sharding them buys nothing."""
        if not self._startree_enabled or self._doc_axis > 1:
            return False
        for s in segments:
            reader = getattr(s, "star_tree", None)
            if reader is None or not reader.trees:
                return False
            vd = getattr(s, "valid_doc_ids", None)
            if vd is not None and not vd.is_full():
                return False
        return True

    def scan_fallback(self, reason: str) -> None:
        """scan_fallback{reason=}: why a query the device could have
        scanned ran on the host instead — `unsupported` (supports() said
        no: QueryExecutor meters it beside that call), `plan` (no
        DevicePlan for these segments), `staging` (a column or literal
        would not stage). With `scan_served` the plain leg's routing
        shows in /metrics without a trace, as the star-tree, CLP, vector
        and mesh legs' does."""
        self._meter("scan_fallback", reason=reason)

    def _st_fallback(self, reason: str) -> None:
        """startree_fallback{reason=}: why a tree-carrying batch went to
        the scan path (labeled like server_admission_rejected)."""
        self._meter("startree_fallback", reason=reason)

    def _clp_fallback(self, reason: str) -> None:
        """clp_fallback{reason=}: why a LIKE/regex over a CLP column left
        the device path (pattern outside the pushable subset, slot caps,
        staging failure, ...) — vocabulary in clp_device.FALLBACK_REASONS."""
        self._meter("clp_fallback", reason=reason)

    def _clp_leaf(self, e: Function, segments, col: str):
        """'clp' DeviceLeaf for a LIKE/regexp_like predicate over a
        CLP-indexed column, or None (fallback metered with a reason).
        The pattern itself stays OUT of the leaf — like every other leaf
        kind, constants resolve at parameter staging so fingerprint-equal
        queries with different patterns share one compiled kernel."""
        if not self._clp_enabled:
            self._clp_fallback("disabled")
            return None
        if e.name not in ("like", "regexp_like") or len(e.args) != 2 \
                or not isinstance(e.args[1], Literal):
            self._clp_fallback("predicate")
            return None
        meta, reason = clp_device.plan_leaf(
            segments, col, str(e.args[1].value), e.name == "like")
        if meta is None:
            self._clp_fallback(reason)
            return None
        return DeviceLeaf("clp", col, meta)

    # ------------------------------------------------------------------
    # vector-similarity device leg (ops/vector_device.py)
    # ------------------------------------------------------------------
    def _vector_fallback(self, reason: str) -> None:
        """vector_fallback{reason=}: why a vector_similarity query left
        the device path for the host index search — vocabulary in
        vector_device.FALLBACK_REASONS."""
        self._meter("vector_fallback", reason=reason)

    def _plan_vector(self, segments, ctx: QueryContext):
        """(VectorPlan, (vector fn, qvec, k), residual ctx) when the ANN
        query admits the device path; (None, reason, None) otherwise.
        The residual ctx carries the non-vector conjuncts ONLY — _stage's
        leaf-expression walk must see exactly the tree the plan's leaves
        were built from, and vector_similarity is not a leaf."""
        if not self._vector_enabled:
            return None, "disabled", None
        fn, residual, reason = vector_device.split_filter(ctx.filter)
        if fn is None:
            return None, reason, None
        if ctx.order_by:
            # score order is implicit in the kernel; an explicit ORDER BY
            # key on top would need a second sort the leg doesn't do
            return None, "hybrid", None
        try:
            col, qvec, k = vector_device.parse_args(fn)
        except (ValueError, TypeError):
            return None, "hybrid", None
        shape, reason = vector_device.admit(
            segments, col, qvec, k, self.TOPN_MAX_K)
        if shape is None:
            return None, reason, None
        dim_pad, ivf, cells_pad = shape
        seg0 = segments[0]
        classify, dict_cols, raw_cols = self._make_classifier(seg0)
        leaves: List[DeviceLeaf] = []
        filter_ir = None
        if residual is not None:
            filter_ir = self._build_filter_ir(residual, segments, leaves,
                                              classify)
            if filter_ir is None:
                return None, "hybrid", None
        raw64 = {lf.column for lf in leaves if lf.kind == "vrange64"}
        plan = vector_device.VectorPlan(
            col=col, dim_pad=dim_pad,
            k_pad=vector_device._pow2(k), ivf=ivf, cells_pad=cells_pad,
            filter_ir=filter_ir, leaves=tuple(leaves),
            dict_cols=tuple(sorted(dict_cols)),
            raw_cols=tuple(sorted(raw_cols - raw64)),
            raw64_cols=tuple(sorted(raw64)),
            clp_cols=clp_device.staged_cols(leaves),
            valid_mask=self._needs_valid_mask(segments))
        rctx = QueryContext(
            table=ctx.table, select=ctx.select, aliases=ctx.aliases,
            distinct=False, filter=residual, group_by=[], having=None,
            order_by=[], limit=ctx.limit, offset=ctx.offset,
            options=ctx.options)
        return plan, (fn, qvec, k), rctx

    def _stage_vector(self, segments, rctx: QueryContext, plan,
                      fn, qvec, k, info, batchable: bool = True):
        """Residual-filter staging as `_stage` does it (the VectorPlan
        duck-types DevicePlan for every field it reads), with the vector
        block / IVF cell pseudo-columns looked up under the same hold of
        the staging lock, plus the per-QUERY params, built and put
        outside it. Query params cache under their own key — the vector
        fn expression, not the residual filter — so two queries sharing
        a residual but not a query vector can never alias."""
        with self._staging_lock(info):
            cols, S, S_real, D, _G, pkey, cached = \
                self._stage_blocks_locked(segments, rctx, plan, batchable)
            dim_pad = plan.dim_pad
            # `(segment, "__vec__/<col>/<leg>")` pseudo-columns: rows pad
            # to the segment's OWN pow2 doc bucket (times dim_pad for the
            # flattened vector leg), so every batch composition shares
            # them
            cols["vec:" + plan.col] = self.stager.stage_block_locked(
                segments, S, D * dim_pad, "vector", (plan.col, "block"),
                np.float32, lambda _i, seg: (
                    "vector", f"__vec__/{plan.col}/block",
                    _pow2(seg.num_docs) * dim_pad,
                    lambda sg: vector_device.vector_row(
                        sg, plan.col, dim_pad, _pow2(sg.num_docs))))
            if plan.ivf:
                cols["vcell:" + plan.col] = self.stager.stage_block_locked(
                    segments, S, D, "vector", (plan.col, "cells"),
                    np.int32, lambda _i, seg: (
                        "vector", f"__vec__/{plan.col}/cells",
                        _pow2(seg.num_docs),
                        lambda sg: vector_device.cell_row(
                            sg, plan.col, _pow2(sg.num_docs))))
            vkey = (batch_id(segments), plan, fn, "__vec__", S)
            vcached = self.stager.params_get_locked(vkey, segments)
        params = self._stage_params(segments, rctx, plan, S, S_real,
                                    pkey, cached, info)
        if vcached is None:
            qp = vector_device.query_params(segments, plan, qvec, k, S)
            vcached = (tuple(segments),
                       {key: self._put(arr, info) for key, arr in qp.items()})
            self._params_insert(vkey, vcached, info)
        params.update(vcached[1])
        return cols, params, S, S_real, D

    def _prepare_vector(self, segments, ctx: QueryContext, cancel_check):
        """Plan + stage an ANN launch through the kernel factory: the
        launch carries the same (plan fingerprint, shape bucket) coalesce
        key as scans, and the query vector/topK ride params — so
        fingerprint-equal concurrent ANN queries (different vectors, same
        shape) batch into ONE jit(vmap) launch. Returns
        (plan, S_real, Launch) or None -> host path (reason metered)."""
        dsp = self._dispatch_span(ctx, "vector")
        slip = accounting.current_slip()
        info = _StagePass(dsp)
        plan, qinfo, rctx = self._plan_vector(segments, ctx)
        if plan is None:
            self._vector_fallback(qinfo)
            if dsp is not None:
                dsp.end(outcome="hostFallback", reason=qinfo)
            return None
        fn, qvec, k = qinfo
        kernel = vector_device.compiled_vector_kernel(plan)
        batchable = isinstance(kernel, jax.stages.Wrapped)
        info.planned()
        try:
            cols, params, S, S_real, D = self._stage_vector(
                segments, rctx, plan, fn, qvec, k, info,
                batchable=batchable)
        except _NotStageable:
            self._vector_fallback("staging")
            if dsp is not None:
                dsp.end(outcome="hostFallback", reason="staging")
            return None
        staged_ts = self._staging_attrs(info, S=S, D=D)
        if slip is not None:
            slip.add(transfer_bytes=info.xfer_bytes)
        self._meter("vector_served")
        launch = Launch(
            call=lambda: kernel(cols, params, None, D=D),
            plan=plan, cols=cols, params=params, num_docs=None,
            D=D, G=0, batch_key=self._coalesce_key(
                plan, segments, S, D, 0, cols, params, batchable),
            cols_key=self._cols_key(segments, plan),
            factory=(lambda B, stacked, _p=plan:
                     vector_device.compiled_batched_vector_kernel(
                         _p, B, stacked)),
            collective=self._needs_cpu_ordering(kernel),
            cancel_check=cancel_check,
            site_ctx={"table": ctx.table, "mode": "vector"}, span=dsp,
            slip=slip, docs=sum(s.num_docs for s in segments),
            staged_ts=staged_ts)
        return plan, S_real, launch

    def _execute_vector(self, segments, ctx: QueryContext,
                        cancel_check=None):
        """ANN leg of _execute_topn. Host fallback keeps exact parity:
        query/filter._vector_similarity_mask serves any batch this
        returns unserved."""
        fire("server.vector.search", table=ctx.table)
        if self._doc_axis > 1:
            self._vector_fallback("staging")
            return [], segments
        prep = self._prepare_vector(segments, ctx, cancel_check)
        if prep is None:
            return [], segments
        plan, S_real, launch = prep
        with self._dispatcher.active():
            packed = self._await_launch(launch)
        t_asm = time.perf_counter()
        results = vector_device.assemble(segments, ctx, plan,
                                         np.asarray(packed), S_real)
        self._note_assemble(launch, t_asm)
        return results, []

    def _prepare_startree(self, segments: List[ImmutableSegment],
                          ctx: QueryContext, cancel_check=None,
                          parent_span=None, slip=None):
        """Star-tree leg of prepare: fit check + host tree traversal
        (startree_device.plan_startree), then stage the fitted trees'
        pre-agg pseudo-columns and wrap the residual-aggregation launch
        for the dispatch ring. Returns (plan, needed, fits, S_real,
        Launch), or None -> the caller falls through to the scan-path
        prepare (and transitively to the host path). Mirrors
        _prepare_agg's lock/span/odometer discipline exactly; the
        DeviceDispatch span carries starTree=true so traces distinguish
        pre-agg serves from scans."""
        dsp = self._dispatch_span(ctx, "startree", parent_span,
                                  starTree=True)
        info = _StagePass(dsp)
        plan, needed, fits, reason = startree_device.plan_startree(
            segments, ctx)
        if plan is None:
            self._st_fallback(reason)
            if dsp is not None:
                dsp.end(outcome="scanFallback", reason=reason)
            return None
        kernel = startree_device.compiled_startree_kernel(plan)
        batchable = isinstance(kernel, jax.stages.Wrapped)
        factory = (lambda B, stacked, _p=plan:
                   startree_device.compiled_batched_startree_kernel(
                       _p, B, stacked))
        info.planned()
        try:
            cols, params, num_docs, S_real, D = self._stage_startree(
                segments, ctx, plan, fits, info, batchable=batchable)
        except _NotStageable:
            self._st_fallback("staging")
            if dsp is not None:
                dsp.end(outcome="scanFallback", reason="staging")
            return None
        staged_ts = self._staging_attrs(
            info, S=int(num_docs.shape[0]), D=D, G=plan.num_groups)
        if slip is not None:
            slip.add(transfer_bytes=info.xfer_bytes)
        self._meter("startree_served")
        # the same coalesce key as scans: fingerprint-equal star-tree
        # queries (same slots/radix, any predicate constants) share ONE
        # jit(vmap) launch
        batch_key = self._coalesce_key(
            plan, segments, int(num_docs.shape[0]), D, 0, cols, params,
            batchable)
        # the staged-block identity carries the fitted tree indexes:
        # members whose filters fit DIFFERENT trees of one segment must
        # stack, not share a broadcast block
        tis = tuple(f.ti for f in fits)
        launch = Launch(
            call=lambda: kernel(cols, params, num_docs, D=D, G=0),
            plan=plan, cols=cols, params=params, num_docs=num_docs,
            D=D, G=0, batch_key=batch_key,
            cols_key=(batch_id(segments), tis),
            factory=factory, dedup_factory=None,
            collective=self._needs_cpu_ordering(kernel),
            cancel_check=cancel_check,
            site_ctx={"table": ctx.table, "mode": "startree"}, span=dsp,
            slip=slip, docs=sum(s.num_docs for s in segments),
            staged_ts=staged_ts)
        return plan, needed, fits, S_real, launch

    def _stage_startree(self, segments, ctx: QueryContext, plan, fits,
                        info, batchable: bool = True):
        """Stage the fitted trees' pre-agg metric/dim-code rows as
        `(segment, "__startree__<ti>/<col>")` pseudo-columns through the
        same host-row / residency / assembled-block tiers as real
        columns (under the staging lock), plus the per-query [S, D]
        selection mask (the traversal result) as kernel params, built
        and put outside it. D is the pow2 bucket of the LARGEST fitted
        tree's record count: star records make num_records exceed
        num_docs, so the scan path's bucket cannot be reused."""
        S_real = len(segments)
        max_recs = max(int(f.tree.meta.num_records) for f in fits)
        if max_recs > MAX_DOCS_PER_SEGMENT:
            raise _NotStageable()
        D = _pow2(max_recs)  # docs axis is 1 here (_startree_candidate)
        S = self._padded_S(
            S_real, bucket=batchable and D <= self._doc_bucket_max)
        vdt = np.float64 if jax.config.read("jax_enable_x64") else np.float32

        # per-SEGMENT pseudo-column names (`__startree__<ti>/<col>`):
        # one segment can hold several trees materializing the same
        # pair over different record layouts, and host/resident rows
        # must key on the tree actually fitted — the block key carries
        # the whole ti tuple for the same reason. Rows pad to the tree's
        # OWN pow2 record bucket (batch-independent, so every batch
        # composition shares them)
        tis = tuple(f.ti for f in fits)
        cols: Dict[str, jnp.ndarray] = {}
        # selection mask + record counts: cached like predicate params —
        # a repeat query (same batch, same plan shape, same filter)
        # re-traverses nothing and uploads nothing. The fitted tree
        # indexes are deterministic in (segments, plan, filter), so the
        # scan-path key form is sufficient here too.
        pkey = (batch_id(segments), plan, ctx.filter, "__startree__", S, D)
        with self._staging_lock(info):
            for ckey, form, dtype in startree_device.staged_columns(
                    plan, vdt):
                cols[ckey] = self.stager.stage_block_locked(
                    segments, S, D, "startree", (ckey, tis), dtype,
                    lambda i, _seg: (
                        "startree", f"__startree__{fits[i].ti}/{ckey}",
                        _pow2(int(fits[i].tree.meta.num_records)),
                        lambda _sg: startree_device.fetch_row(
                            fits[i].tree, form, dtype)))
            cached = self.stager.params_get_locked(pkey, segments)
        if cached is None:
            sel = startree_device.selection_mask(fits, S, D)
            num_docs = np.zeros(S, dtype=np.int32)
            num_docs[:S_real] = [int(f.tree.meta.num_records) for f in fits]
            cached = (tuple(segments),
                      {"sel": self._put(sel, info, block=True)},
                      self._put(num_docs, info))
            self._params_insert(pkey, cached, info)
        return cols, dict(cached[1]), cached[2], S_real, D

    # -- staging trace attrs -------------------------------------------
    @staticmethod
    def _dispatch_span(ctx: QueryContext, mode: str, parent_span=None,
                       **attrs):
        """The query's DeviceDispatch span, a child of `parent_span` (the
        caller's handle where staging runs off the request thread; else
        the contextvar's). None untraced."""
        parent_span = parent_span or tracing.capture()
        if parent_span is None:
            return None
        return parent_span.child("DeviceDispatch", table=ctx.table,
                                 mode=mode, **attrs)

    @contextlib.contextmanager
    def _staging_lock(self, info):
        """The staging lock (the stager's) round what it guards and
        nothing else: one pass's `*_locked` look-ups. The wait for it
        and the hold are measured where they happen and added to the
        pass (`lockWaitMs`; `blocksMs` and its share of `lockHeldMs`),
        and what the stager uploaded under the hold (rows of a block
        miss) joins the pass's bytes: exact, only a holder uploads. An
        untraced pass reads no clock and makes no annotation. Wait and
        hold go to the profiler's host plane as `pinot:lock_wait` /
        `pinot:staging`."""
        dsp = info.span
        t0 = time.perf_counter() if dsp is not None else 0.0
        with dispatch_mod.phase_annotation("lock_wait", dsp):
            self._engine_lock.acquire()
        t1 = time.perf_counter() if dsp is not None else 0.0
        up0 = self.stager.uploaded_bytes
        try:
            with dispatch_mod.phase_annotation("staging", dsp):
                yield
        finally:
            info.xfer_bytes += self.stager.uploaded_bytes - up0
            self._engine_lock.release()
            if dsp is not None:
                info.wait_s += t1 - t0
                info.blocks_s += time.perf_counter() - t1

    def _params_insert(self, pkey: tuple, entry: tuple, info) -> None:
        """A parameter-cache miss's entry, built outside the staging
        lock, inserted under a short hold of it (its wait joins the
        pass's `lockWaitMs`, its hold `lockHeldMs`)."""
        traced = info.span is not None
        t0 = time.perf_counter() if traced else 0.0
        with self._engine_lock:
            t1 = time.perf_counter() if traced else 0.0
            self.stager.params_put_locked(pkey, entry)
        if traced:
            info.wait_s += t1 - t0
            info.insert_s += time.perf_counter() - t1

    def _chip_attrs(self, dsp) -> None:
        """A mesh engine's traced DeviceDispatch: how many devices the
        launch spans and the bytes moved chip to chip at block assembly
        since start-up (what each chip holds is on /metrics as
        `hbm_resident_bytes{device=}` / `hbm_cache_bytes{device=}`). One
        device: nothing is set."""
        if len(self.devices) < 2:
            return
        dsp.set(meshDevices=len(self.devices),
                crossChipBytes=self.stager.cross_chip_bytes)

    def _staging_attrs(self, info, **dims) -> float:
        """Returns the Launch's `staged_ts` (0.0 untraced), having set
        the pass's stretch of the span, opening -> staged, as four parts
        that tile it: planMs (plan + kernel look-up, no lock),
        lockWaitMs (the waits for the staging lock), blocksMs (the hold:
        block-cache look-ups, the parameter-cache probe and, on a miss,
        row fetch + upload) and paramsMs (the rest: literals resolved,
        the [K, S] pack built, a LUT table put, the entry inserted);
        stagingMs is the three that are work. lockHeldMs is all the
        pass held the lock (the hold, plus the insert's), paramPuts its
        `_put` calls (none for the pack: it rides the launch) and
        transferBytes what those and a block miss's rows moved."""
        dsp = info.span
        if dsp is None:
            return 0.0
        now = time.perf_counter()
        plan_s = info.t_plan - info.t_open
        params_s = now - info.t_plan - info.wait_s - info.blocks_s
        dsp.set(
            lockWaitMs=round(info.wait_s * 1e3, 3),
            lockHeldMs=round((info.blocks_s + info.insert_s) * 1e3, 3),
            stagingMs=round((plan_s + info.blocks_s + params_s) * 1e3, 3),
            planMs=round(plan_s * 1e3, 3),
            blocksMs=round(info.blocks_s * 1e3, 3),
            paramsMs=round(params_s * 1e3, 3),
            paramPuts=info.puts,
            transferBytes=info.xfer_bytes,
            **dims)
        staged_ts = time.monotonic()
        self._chip_attrs(dsp)  # after the stamp: no staging phase's time
        return staged_ts

    def execute(self, segments: List[ImmutableSegment], ctx: QueryContext,
                cancel_check=None
                ) -> Tuple[List[Any], List[ImmutableSegment]]:
        """Returns (device results, segments to fall back to host).

        Staging's block look-ups run under the staging lock (they
        mutate the block caches), plan and parameters outside it; the
        launch rides the dispatch ring, which coalesces
        fingerprint-equal concurrent queries into one batched kernel and
        fetches results off-ring — N server threads overlap their device
        round trips instead of serializing behind one sync each.
        cancel_check: polled while the launch waits in the ring (a
        cancelled/deadline-expired query leaves its batch before launch).
        """
        if ctx.distinct:
            return self._execute_distinct(segments, ctx, cancel_check)
        if not ctx.aggregations:
            return self._execute_topn(segments, ctx, cancel_check)
        with self._dispatcher.active():
            prep = self._prepare(segments, ctx, cancel_check,
                                 slip=accounting.current_slip())
            if prep is None:
                return [], segments
            launch, assemble = prep
            packed = self._await_launch(launch)
        t_asm = time.perf_counter()
        results = assemble(packed)
        self._note_assemble(launch, t_asm)
        return results, []

    def _await_launch(self, launch: Launch):
        """Submit to the ring and wait for the packed result, deadline-
        bounded: the checker carries the query's remaining budget; the
        cap backstops budget-less callers. Ends the launch's span."""
        try:
            return dispatch_mod.wait_result(
                self._dispatcher.submit(launch), launch.cancel_check,
                max_wait_s=self.LAUNCH_WAIT_CAP_S)
        finally:
            launch.end_span()

    def _prepare(self, segments, ctx: QueryContext, cancel_check,
                 parent_span=None, slip=None):
        """Plan + stage an aggregation: (Launch, packed result ->
        per-segment results), or None -> host path. Star-tree leg
        first: a fitted tree answers from pre-agg records; any fallback
        reason drops through to the scan prepare (and transitively to
        the host path)."""
        st = self._prepare_startree(segments, ctx, cancel_check,
                                    parent_span=parent_span, slip=slip) \
            if self._startree_candidate(segments) else None
        if st is not None:
            st_plan, needed, fits, _S_real, launch = st
            return launch, lambda packed: startree_device.assemble(
                segments, ctx, st_plan, needed, fits, packed)
        prep = self._prepare_agg(segments, ctx, cancel_check,
                                 parent_span=parent_span, slip=slip)
        if prep is None:
            return None
        plan, slots_of_fn, S_real, launch, minfo, scatter, bucket = prep
        if plan.group_fold:
            return launch, lambda packed: self._assemble_folded(
                segments, ctx, plan, packed, S_real, slots_of_fn, minfo,
                launch.span, scatter, bucket)
        if minfo is not None:
            return launch, lambda packed: self._assemble_merged(
                segments, ctx, plan, packed, S_real, slots_of_fn, minfo)
        return launch, lambda packed: self._assemble(
            segments, ctx, plan, packed, S_real, slots_of_fn, launch.span,
            scatter, bucket)

    @staticmethod
    def _note_assemble(launch: Launch, t0: float, parent=None) -> None:
        """`assembleMs` on the span the DeviceDispatch hangs under (the
        server's ServerRequest): packed device result -> per-segment
        results, from the dispatch span's end (`t0`) to now; summed over
        a request's launches. parent: that span's handle where the
        caller is off the request thread."""
        if launch.span is None:
            return
        parent = parent or tracing.capture()
        if parent is not None:
            parent.set(assembleMs=round(
                parent.get("assembleMs", 0.0)
                + (time.perf_counter() - t0) * 1e3, 3))

    def execute_async(self, segments: List[ImmutableSegment],
                      ctx: QueryContext, cancel_check=None):
        """Future of (device results, host-fallback segments): staging
        runs on the dispatch staging pool, so the caller can execute its
        host-path segments while this query's padding + device_put (and
        then its kernel) proceed — query N+1 stages while query N
        computes. Non-agg shapes (top-N / DISTINCT) and the serialized
        compat mode run inline on the caller, exactly like execute()."""
        from concurrent.futures import Future as _Future
        if ctx.distinct or not ctx.aggregations \
                or self._dispatcher.mode == "serialized":
            fut: "_Future" = _Future()
            try:
                fut.set_result(self.execute(segments, ctx, cancel_check))
            except BaseException as e:  # noqa: BLE001 — future carries it
                fut.set_exception(e)
            return fut
        out: "_Future" = _Future()
        self._dispatcher.enter_active()
        out.add_done_callback(lambda _f: self._dispatcher.exit_active())
        # capture on the CALLER thread: staging runs on the staging pool
        # where neither the trace contextvar nor the accounting
        # thread-local flows
        parent_span = tracing.capture()
        slip = accounting.current_slip()

        def stage_and_enqueue():
            try:
                prep = self._prepare(segments, ctx, cancel_check,
                                     parent_span=parent_span, slip=slip)
                if prep is None:
                    out.set_result(([], segments))
                    return
                launch, assemble = prep

                def finish(f):
                    launch.end_span()
                    t_asm = time.perf_counter()
                    try:
                        # lint: hang(done-callback: f is already resolved)
                        results = assemble(f.result())
                        self._note_assemble(launch, t_asm, parent_span)
                        out.set_result((results, []))
                    except BaseException as e:  # noqa: BLE001
                        out.set_exception(e)

                self._dispatcher.submit(launch).add_done_callback(finish)
            except BaseException as e:  # noqa: BLE001
                out.set_exception(e)

        dispatch_mod.staging_pool().submit(stage_and_enqueue)
        return out

    # ------------------------------------------------------------------
    def _execute_distinct(self, segments, ctx: QueryContext,
                          cancel_check=None):
        """DISTINCT d1..dk = a presence-only GROUP BY d1..dk: reuse the
        whole group-by kernel path and convert keys to DistinctResult rows
        (ref DistinctOperator; dictionary-based distinct)."""
        sel = list(ctx.select)
        gctx = QueryContext(
            table=ctx.table, select=sel + [Function("count",
                                                    (Identifier("*"),))],
            aliases=[None] * (len(sel) + 1), distinct=False,
            filter=ctx.filter, group_by=sel, having=None, order_by=[],
            limit=ctx.limit, offset=0, options=dict(ctx.options))
        gctx._extract_aggregations()
        results, remaining = self.execute(segments, gctx, cancel_check)
        from pinot_tpu.query.results import DistinctResult
        out = [DistinctResult(set(r.groups.keys()), r.stats)
               for r in results]
        return out, remaining

    # ------------------------------------------------------------------
    def _prepare_topn(self, segments, ctx: QueryContext, cancel_check,
                      mode: str):
        """Plan + stage a top-N / doc-id-scan launch THROUGH the kernel
        factory: the launch carries the same (plan fingerprint, shape
        bucket) coalesce key as agg launches, so fingerprint-equal MSE
        leaf SCAN stages (and single-stage selection traffic sharing the
        plan + bucket) batch into one `jit(vmap)` topn kernel instead of
        paying one XLA launch per stage per query. Caller must hold no
        engine state; returns (S_real, Launch) or None -> host path.
        Must be called with doc_axis == 1 (sharded top-K stays host)."""
        dsp = self._dispatch_span(ctx, mode)
        slip = accounting.current_slip()
        info = _StagePass(dsp)
        plan = self._plan_topn(segments, ctx)
        if plan is None:
            self.scan_fallback("plan")
            if dsp is not None:
                dsp.end(outcome="hostFallback")
            return None
        kernel = kernels.compiled_topn_kernel(plan)
        batchable = isinstance(kernel, jax.stages.Wrapped)
        info.planned()
        try:
            cols, params, S, S_real, D, _G = self._stage(
                segments, ctx, plan, batchable=batchable, info=info)
        except _NotStageable:
            self.scan_fallback("staging")
            if dsp is not None:
                dsp.end(outcome="hostFallback")
            return None
        staged_ts = self._staging_attrs(info, S=S, D=D)
        if slip is not None:
            slip.add(transfer_bytes=info.xfer_bytes)
        self._meter("scan_served")
        launch = Launch(
            call=lambda: kernel(cols, params, None, D=D),
            plan=plan, cols=cols, params=params, num_docs=None,
            D=D, G=0, batch_key=self._coalesce_key(
                plan, segments, S, D, 0, cols, params, batchable),
            cols_key=self._cols_key(segments, plan),
            factory=(lambda B, stacked, _p=plan:
                     kernels.compiled_batched_topn_kernel(_p, B, stacked)),
            collective=self._needs_cpu_ordering(kernel),
            cancel_check=cancel_check,
            site_ctx={"table": ctx.table, "mode": mode}, span=dsp,
            slip=slip, docs=sum(s.num_docs for s in segments),
            staged_ts=staged_ts)
        return S_real, launch

    def _execute_topn(self, segments, ctx: QueryContext, cancel_check=None):
        if ctx.filter is not None \
                and vector_device.contains_vector(ctx.filter):
            return self._execute_vector(segments, ctx, cancel_check)
        if self._doc_axis > 1:
            self.scan_fallback("unsupported")  # top-K across doc shards
            return [], segments
        prep = self._prepare_topn(segments, ctx, cancel_check, "topn")
        if prep is None:
            return [], segments
        S_real, launch = prep
        with self._dispatcher.active():
            packed = self._await_launch(launch)
        t_asm = time.perf_counter()
        results = self._assemble_topn(segments, ctx, packed, S_real)
        self._note_assemble(launch, t_asm)
        return results, []

    # ------------------------------------------------------------------
    @staticmethod
    def _make_classifier(seg0):
        """Column stagability test; records dict/raw membership as a side
        effect (ids usable for filters/group-by regardless of value type;
        value math additionally needs a numeric dictionary)."""
        dict_cols: set = set()
        raw_cols: set = set()

        def classify(col: str) -> bool:
            if not seg0.has_column(col):
                return False
            m = seg0.metadata.columns[col]
            if not m.single_value:
                return False
            if m.has_dictionary:
                dict_cols.add(col)
                return True
            if m.data_type.np_dtype.kind in "iuf":
                raw_cols.add(col)
                return True
            return False

        return classify, dict_cols, raw_cols

    def _plan(self, segments, ctx: QueryContext):
        """Build the DevicePlan from the query + first segment's schema."""
        seg0 = segments[0]
        classify, dict_cols, raw_cols = self._make_classifier(seg0)

        # value IRs for aggregation inputs
        value_irs: List[Optional[tuple]] = []
        ir_index: Dict[tuple, int] = {}

        def intern_ir(ir: Optional[tuple]) -> Optional[int]:
            if ir is None:
                return None
            if ir not in ir_index:
                ir_index[ir] = len(value_irs)
                value_irs.append(ir)
            return ir_index[ir]

        def check_value_cols(ir) -> bool:
            if ir[0] == "col":
                col = ir[1]
                if col in raw64:
                    return False  # split-plane columns have no value block
                if not classify(col):
                    return False
                m = seg0.metadata.columns[col]
                return m.data_type.np_dtype.kind in "iuf"
            if ir[0] == "lit":
                return True
            return all(check_value_cols(c) for c in ir[1:] if isinstance(c, tuple))

        # device-HLL inputs hash i32 split planes of plain int columns —
        # they join the raw64 staging set and are excluded from value IRs
        hll_cols: set = set()
        for node, fn in zip(ctx.aggregations, ctx.agg_functions):
            spec = fn.device_spec
            if spec is None:
                return None
            if any(op.startswith("hll:") for op in spec.ops):
                col = node.args[0].name
                m0 = seg0.metadata.columns.get(col)
                if m0 is None or not m0.single_value \
                        or m0.data_type.np_dtype.kind not in "iu":
                    return None
                # the i32 hi plane wraps for |v| >= 2^55 (the vrange64
                # bound): the host fold would then diverge from the
                # device hash, so such columns stay host-side
                for seg in segments:
                    m = seg.metadata.columns.get(col)
                    if m is None or m.min_value is None \
                            or m.max_value is None or max(
                                abs(int(m.min_value)),
                                abs(int(m.max_value))) >= (1 << 55):
                        return None
                hll_cols.add(col)

        # a fused time bucket reads its column's split planes, so a raw
        # filter leaf on that column reads them too: no 'val:' block of
        # it is staged (x64 would make the leaf an f64 range, and a small
        # int under f32 staging an f32 one)
        planes_only = set(hll_cols)
        if ctx.group_by and not isinstance(ctx.group_by[0], Identifier):
            shape = timeseries_device.extract_bucket(ctx.group_by[0])
            if shape is not None:
                planes_only.add(shape[0])

        # filter IR FIRST: leaves fill in build order, so the main filter's
        # leaves precede agg-filter leaves (staging resolves in this order)
        leaves: List[DeviceLeaf] = []
        filter_ir = None
        force64 = frozenset(planes_only)
        if ctx.filter is not None:
            filter_ir = self._build_filter_ir(ctx.filter, segments, leaves,
                                              classify, force64=force64)
            if filter_ir is None:
                return None

        #: columns that stage as split planes carry NO 'val:' block — they
        #: cannot feed value IRs (the whole query falls back instead)
        raw64 = {lf.column for lf in leaves
                 if lf.kind == "vrange64"} | planes_only

        # per-aggregation FILTER (WHERE ...) trees, deduplicated
        agg_filter_irs: List[tuple] = []
        fidx_of_filter: Dict[Expression, int] = {}
        agg_fidx: List[Optional[int]] = []
        for f in ctx.agg_filters:
            if f is None:
                agg_fidx.append(None)
                continue
            if f in fidx_of_filter:
                agg_fidx.append(fidx_of_filter[f])
                continue
            ir = self._build_filter_ir(f, segments, leaves, classify,
                                       force64=force64)
            if ir is None:
                return None
            fidx_of_filter[f] = len(agg_filter_irs)
            agg_fidx.append(len(agg_filter_irs))
            agg_filter_irs.append(ir)
        raw64 |= {lf.column for lf in leaves if lf.kind == "vrange64"}

        if ctx.group_by and any(
                ":" in op for fn in ctx.agg_functions
                for op in fn.device_spec.ops):
            return None  # grouped sketches: host path (see supports)

        # aggregation slots
        agg_ops: List[Tuple[str, Optional[int], Optional[int]]] = []
        slot_index: Dict[Tuple[str, Optional[int], Optional[int]], int] = {}
        slots_of_fn: List[Dict[str, int]] = []
        for i, (node, fn) in enumerate(zip(ctx.aggregations,
                                           ctx.agg_functions)):
            spec_ops = fn.device_spec.ops
            is_hll = any(op.startswith("hll:") for op in spec_ops)
            arg_ir = None
            if not is_hll and node.args \
                    and not (isinstance(node.args[0], Identifier)
                             and node.args[0].name == "*"):
                arg_ir = self._value_ir_shape(node.args[0])
                if arg_ir is None or not check_value_cols(arg_ir):
                    return None
            vidx = intern_ir(arg_ir)
            fidx = agg_fidx[i]
            # bit-exact SUM for plain int columns under f32 staging: swap
            # the slot to 'isum' (6-bit-plane i32 accumulation, ref
            # SumAggregationFunction's exact doubles); _assemble rebuilds
            # the scalar so the function still sees its 'sum' slot.
            # Grouped sums stay f32 (scalar-slot packing) — documented
            # approximation.
            int_bounds = None
            if not ctx.group_by and arg_ir is not None \
                    and not jax.config.read("jax_enable_x64"):
                int_bounds = self._int_ir_bounds(segments, arg_ir)
            mapping = {}
            for op in spec_ops:
                if op == "sum" and int_bounds is not None:
                    lo_b, hi_b = int_bounds
                    if lo_b >= 0:
                        # non-negative: fewer, wider unsigned planes
                        planes = max(
                            1, (max(hi_b, 1).bit_length() + 6) // 7)
                        op_key = f"isum:u{planes}"
                    else:
                        op_key = "isum"
                    key = (op_key, vidx, fidx)
                    if key not in slot_index:
                        slot_index[key] = len(agg_ops)
                        agg_ops.append(key)
                    mapping[op] = slot_index[key]
                    continue
                if op.startswith("hll:"):
                    # column rides in the op key (the kernel reads its
                    # split planes directly, no value IR)
                    key = (f"{op}:{node.args[0].name}", None, fidx)
                elif op == "count":
                    key = ("count", None, fidx)
                else:
                    if vidx is None:
                        return None
                    key = (op, vidx, fidx)
                if key not in slot_index:
                    slot_index[key] = len(agg_ops)
                    agg_ops.append(key)
                mapping[op] = slot_index[key]
            slots_of_fn.append(mapping)

        # group-by
        group_cols: List[str] = []
        group_strides: List[int] = []
        num_groups = 0
        group_compact = False
        tbucket: Tuple = ()
        if ctx.group_by:
            gb = list(ctx.group_by)
            tb_spec = None
            if gb and not isinstance(gb[0], Identifier):
                # leading floor((t - start) / step): the fused device
                # time-bucket leg (supports() admitted the shape; the
                # window/metadata admission happens here). The bucket id
                # becomes the key's LOWEST digit, so its width (what the
                # segments span, as a dictionary digit is what they
                # hold) seeds the mixed radix ahead of the tag
                # cardinalities.
                tb_spec = timeseries_device.plan_bucket(
                    gb[0], ctx.filter, segments)
                if tb_spec is None:
                    return None
                if any(ir is not None and tb_spec.col in self._ir_cols(ir)
                       for ir in value_irs):
                    # the timestamp stages ONLY as split planes once the
                    # bucket leg claims it — it can't also feed a value IR
                    return None
                tbucket = (tb_spec.col, tb_spec.width, tb_spec.count_pad)
                gb = gb[1:]
            card_pads = []
            for g in gb:
                col = g.name  # Identifier, checked in supports
                if not classify(col):
                    return None
                m0 = seg0.metadata.columns[col]
                if not m0.has_dictionary:
                    return None
                card = max(seg.metadata.columns[col].cardinality
                           for seg in segments)
                group_cols.append(col)
                card_pads.append(max(card, 1))
            num_groups = tb_spec.width if tb_spec is not None else 1
            for c in card_pads:
                num_groups *= c
            if tb_spec is not None and num_groups > MAX_DEVICE_GROUPS:
                # compact per-segment keys can't carry the fused bucket
                # digit — an over-wide dashboard stays on the host path
                return None
            if num_groups > MAX_DEVICE_GROUPS:
                # sparse key space: per-segment compacted keys replace the
                # dense mixed-radix product (ref DictionaryBasedGroupKey
                # Generator's map-based modes) — the OBSERVED distinct
                # count is what matters, resolved at staging
                group_compact = True
                num_groups = 0
            else:
                # memory guard: the [S, G, slots] result buffer must stay
                # sane, with S padded exactly as _stage will pad it (pow2
                # bucket only when the doc bucket is cross-table eligible,
                # then the segments-axis multiple) — an overestimate here
                # would host-fallback group-bys that actually fit
                n_slots = len(agg_ops) + 1  # +1 guaranteed count slot
                s_pad = self._padded_S(
                    len(segments),
                    bucket=self._padded_D(segments) <= self._doc_bucket_max)
                if s_pad * num_groups * n_slots * 8 > MAX_GROUP_RESULT_BYTES:
                    return None
                stride = num_groups
                for c in card_pads:
                    stride //= c
                    group_strides.append(stride)
            # group-by always needs an unfiltered count slot to detect
            # present groups
            if ("count", None, None) not in slot_index:
                slot_index[("count", None, None)] = len(agg_ops)
                agg_ops.append(("count", None, None))

        # a grouped sum that may add an Inf or a NaN keeps the scatter
        def adds_nonfinite(op, vidx) -> bool:
            power = kernels._ADDITIVE.get(op, 0)  # count, min, max: 0
            if not power:
                return False
            bits = self._ir_bits(seg0, value_irs[vidx])
            return bits is None or power * bits > 127

        nonfinite = bool(ctx.group_by) and any(
            adds_nonfinite(op, vidx) for op, vidx, _f in agg_ops)

        raw64 = {lf.column for lf in leaves
                 if lf.kind == "vrange64"} | planes_only
        if tbucket:
            # the bucket kernel reads the timestamp's (hi, lo) planes
            # regardless of how its range leaf classified
            raw64 |= {tbucket[0]}
        if group_compact:
            # the gkey block replaces per-column id planes for group-only
            # columns; keep ids only where filters/values still need them
            needed = {lf.column for lf in leaves}
            for ir in value_irs:
                needed |= self._ir_cols(ir)
            dict_cols -= set(group_cols) - needed
        plan = DevicePlan(
            filter_ir=filter_ir,
            leaves=tuple(leaves),
            value_irs=tuple(value_irs),
            agg_ops=tuple(agg_ops),
            agg_filter_irs=tuple(agg_filter_irs),
            group_cols=tuple(group_cols),
            group_strides=tuple(group_strides),
            num_groups=num_groups,
            group_compact=group_compact,
            dict_cols=tuple(sorted(dict_cols)),
            raw_cols=tuple(sorted(raw_cols - raw64)),
            raw64_cols=tuple(sorted(raw64)),
            clp_cols=clp_device.staged_cols(leaves),
            valid_mask=self._needs_valid_mask(segments),
            tbucket=tbucket,
            nonfinite=nonfinite,
        )
        return plan, slots_of_fn

    def filtered_doc_ids(self, segments, filter_expr):
        """Device-filtered doc ids for leaf SCANS (MSE join inputs, ref
        QueryRunner.java:258 routing ALL leaf stages through the v1
        engine): the top-K kernel evaluates the filter and returns the
        first TOPN_MAX_K matching doc indices per segment. Returns a list
        parallel to `segments` of sorted int64 index arrays, or None per
        segment that must fall back (overflow / unstageable / sharded
        doc axis)."""
        nothing = [None] * len(segments)
        if self._doc_axis > 1 or not segments or filter_expr is None:
            return nothing
        ctx = QueryContext(
            table="", select=[], aliases=[], distinct=False,
            filter=filter_expr, group_by=[], having=None, order_by=[],
            limit=self.TOPN_MAX_K, offset=0, options={})
        # the launch rides the kernel factory (batch_key + batched topn
        # variants), so fingerprint-equal MSE leaf scans from concurrent
        # queries coalesce into ONE stacked/broadcast topn launch
        prep = self._prepare_topn(segments, ctx, None, "doc_ids")
        if prep is None:
            return nothing
        S_real, launch = prep
        plan = launch.plan
        with self._dispatcher.active():
            packed = self._await_launch(launch)
        out = []
        for s, seg in enumerate(segments[:S_real]):
            matched = int(packed[s, 0])
            if matched > plan.topn_k:
                out.append(None)  # more matches than K: host path
                continue
            idx = packed[s, 1:]
            idx = idx[(idx >= 0) & (idx < seg.num_docs)].astype(np.int64)
            out.append(np.sort(idx))
        return out

    def _plan_topn(self, segments, ctx: QueryContext) -> Optional[DevicePlan]:
        """DevicePlan for selection / single-key order-by top-K."""
        seg0 = segments[0]
        classify, dict_cols, raw_cols = self._make_classifier(seg0)
        k = ctx.limit + ctx.offset
        if k <= 0 or k > self.TOPN_MAX_K:
            return None

        leaves: List[DeviceLeaf] = []
        filter_ir = None
        if ctx.filter is not None:
            filter_ir = self._build_filter_ir(ctx.filter, segments, leaves,
                                              classify)
            if filter_ir is None:
                return None
        raw64 = {lf.column for lf in leaves if lf.kind == "vrange64"}

        value_irs: Tuple[Optional[tuple], ...] = ()
        topn_asc = True
        if ctx.order_by:
            e, topn_asc = ctx.order_by[0]
            ir = None
            if isinstance(e, Identifier) and classify(e.name):
                m = seg0.metadata.columns[e.name]
                if m.has_dictionary:
                    # dictionaries are value-sorted: dictId order IS value
                    # order, and ids stay exact in f32 below 2^24
                    if max(s.metadata.columns[e.name].cardinality
                           for s in segments) >= (1 << 24):
                        return None
                    ir = ("ids", e.name)
                elif e.name not in raw64:
                    ir = ("col", e.name)
            elif isinstance(e, Function):
                ir = self._value_ir_shape(e)
                if ir is not None:
                    for col in self._ir_cols(ir):
                        if col in raw64 or not classify(col):
                            return None
                        mc = seg0.metadata.columns[col]
                        if mc.data_type.np_dtype.kind not in "iuf":
                            return None
            if ir is None:
                return None
            value_irs = (ir,)

        return DevicePlan(
            filter_ir=filter_ir,
            leaves=tuple(leaves),
            value_irs=value_irs,
            agg_ops=(),
            dict_cols=tuple(sorted(dict_cols)),
            raw_cols=tuple(sorted(raw_cols - raw64)),
            raw64_cols=tuple(sorted(raw64)),
            clp_cols=clp_device.staged_cols(leaves),
            mode="topn", topn_k=k, topn_asc=bool(topn_asc),
            valid_mask=self._needs_valid_mask(segments))

    def _assemble_topn(self, segments, ctx: QueryContext,
                       packed: np.ndarray, S_real: int) -> List[Any]:
        """packed [S, 1+K] int32 -> SelectionResults: project ONLY the
        winning docs host-side (incl. '*' and string columns)."""
        from pinot_tpu.query.executor_cpu import _project_rows, expand_star
        from pinot_tpu.query.filter import SegmentColumnProvider
        from pinot_tpu.query.results import SelectionResult
        filter_cols = len(set(ctx.filter_columns()))
        results = []
        for s, seg in enumerate(segments[:S_real]):
            matched = int(packed[s, 0])
            idx = packed[s, 1:]
            idx = idx[(idx >= 0) & (idx < seg.num_docs)].astype(np.int64)
            provider = SegmentColumnProvider(seg)
            rows = _project_rows(seg, ctx.select, provider, idx)
            order_values = None
            if ctx.order_by:
                order_values = _project_rows(
                    seg, [e for e, _ in ctx.order_by], provider, idx)
            stats = ExecutionStats(
                num_docs_scanned=matched,
                num_entries_scanned_in_filter=(
                    seg.num_docs * filter_cols
                    if ctx.filter is not None else 0),
                num_entries_scanned_post_filter=len(idx) * max(
                    len(ctx.select), 1),
                num_segments_processed=1,
                num_segments_matched=1 if matched else 0,
                total_docs=seg.num_docs)
            results.append(SelectionResult(
                rows, order_values=order_values,
                columns=expand_star(seg, ctx), stats=stats))
        return results

    def _build_filter_ir(self, e: Function, segments, leaves, classify,
                         force64: frozenset = frozenset()):
        """force64: no-dictionary int columns that stage ONLY as split
        planes (device-HLL inputs) — filter leaves on them must use
        vrange64, never the 'val:' block that won't exist."""
        seg0 = segments[0]
        if e.name in ("and", "or"):
            children = []
            for a in e.args:
                c = self._build_filter_ir(a, segments, leaves, classify,
                                          force64)
                if c is None:
                    return None
                children.append(c)
            return (e.name, *children)
        if e.name == "not":
            c = self._build_filter_ir(e.args[0], segments, leaves, classify,
                                      force64)
            return None if c is None else ("not", c)
        if not e.args or not isinstance(e.args[0], Identifier):
            return None
        col = e.args[0].name
        if clp_device.is_clp_column(seg0, col):
            # CLP log columns never classify (STRING, no dictionary
            # block) — LIKE/regex push down through their own leaf kind
            # instead, against the logtype/var-slot pseudo-columns
            leaf = self._clp_leaf(e, segments, col)
            if leaf is None:
                return None
            leaves.append(leaf)
            return ("leaf", len(leaves) - 1)
        if not classify(col):
            return None
        m = seg0.metadata.columns[col]
        if m.has_dictionary:
            if e.name in _LEAF_RANGE_FUNCS:
                kind = "range"
            elif e.name == "not_equals":
                kind = "neq"
            elif e.name in _LEAF_LUT_FUNCS:
                kind = "lut"
            else:
                return None
        else:
            if e.name not in _LEAF_RANGE_FUNCS:
                return None
            if col in force64:
                # split planes are the ONLY staged form of this column
                # (regardless of x64 — the HLL op reads them either way)
                kind = "vrange64"
            elif m.data_type.np_dtype.kind in "iu" and \
                    not jax.config.read("jax_enable_x64"):
                kind = self._int_filter_kind(segments, col)
                if kind is None:
                    return None
            else:
                kind = "vrange"
        leaves.append(DeviceLeaf(kind, col))
        return ("leaf", len(leaves) - 1)

    @staticmethod
    def _int_filter_kind(segments, col: str) -> Optional[str]:
        """Staging for a raw int filter column under x64-off:
        'vrange'   — |v| <= 2^24, exact in f32
        'vrange64' — |v| < 2^55, exact via (hi, lo) i32 split planes
        None       — range unknown or too wide: host fallback (an i32 hi
                     plane would silently wrap for |v| >= 2^55)"""
        big = False
        for seg in segments:
            m = seg.metadata.columns.get(col)
            if m is None or m.min_value is None or m.max_value is None:
                return None
            peak = max(abs(int(m.min_value)), abs(int(m.max_value)))
            if peak >= (1 << 55):
                return None
            if peak > (1 << 24):
                big = True
        return "vrange64" if big else "vrange"

    # ------------------------------------------------------------------
    def _padded_S(self, S_real: int, bucket: bool = True) -> int:
        """Padded segment-axis size: pow2-bucketed when cross-table
        batching is on AND this launch is bucket-eligible (so different
        tables' batches land in shared shape buckets — padded segments
        carry num_docs=0 and zero rows, masked out of every slot), then
        rounded up to the mesh's segment-axis multiple. bucket=False
        skips the pow2 pad: a launch that can never join a cross-table
        bucket (doc bucket above doc.bucket.max) must not pay inflated
        [S, D] blocks for it."""
        S = _pow2(S_real, floor=1) if (self._cross_table and bucket) \
            else S_real
        if self._mesh is not None:
            n = self._seg_axis
            S = ((S + n - 1) // n) * n
        return S

    def _padded_D(self, segments) -> int:
        """Pow2 doc bucket, rounded so the doc-shard axis tiles evenly
        (pow2 alone can never reach divisibility by doubling). The ONE
        definition of D: staging and the group-by memory guard both use
        it, so bucket eligibility (D <= doc.bucket.max) always agrees
        between them."""
        D = _pow2(max(s.num_docs for s in segments))
        if D % self._doc_axis:
            a = self._doc_axis
            D = ((D + a - 1) // a) * a
        return D

    def _stage(self, segments, ctx: QueryContext, plan: DevicePlan,
               batchable: bool = True, info=None):
        """One query's staged inputs: (cols, params, S, S_real, D, G).
        The block look-ups and the parameter-cache probe run under the
        staging lock (`_stage_blocks_locked`); what a probe's miss costs
        (`_stage_params`: literals resolved, the pack built) runs after
        its release. batchable=False (top-N / doc-id scans — launches
        that never carry a batch_key) skips the pow2 S bucket:
        shape-bucket padding only buys cross-table coalescing, which
        those paths can't use. info: the caller's `_StagePass` (None:
        an untraced pass of its own, for callers that want no counts)."""
        info = info or _StagePass(None)
        with self._staging_lock(info):
            cols, S, S_real, D, G, pkey, cached = \
                self._stage_blocks_locked(segments, ctx, plan, batchable)
        params = self._stage_params(segments, ctx, plan, S, S_real, pkey,
                                    cached, info)
        return cols, params, S, S_real, D, G

    def _stage_blocks_locked(self, segments, ctx: QueryContext,
                             plan: DevicePlan, batchable: bool):
        """The part of staging that touches what the lock guards: every
        [S, D] block of the plan through the stager's tiers, and the
        probe of the parameter cache. Returns (cols, S, S_real, D, G,
        pkey, the cached entry or None)."""
        S_real = len(segments)
        if max(s.num_docs for s in segments) > MAX_DOCS_PER_SEGMENT:
            raise _NotStageable()
        D = self._padded_D(segments)
        S = self._padded_S(
            S_real, bucket=batchable and D <= self._doc_bucket_max)

        cols: Dict[str, jnp.ndarray] = {}
        vdt = np.float64 if jax.config.read("jax_enable_x64") else np.float32

        for col in plan.dict_cols:
            # cardinality-aware id width: HBM bandwidth is the roofline,
            # so an 11-value dictionary column reads 4x fewer bytes as i8
            # (SURVEY §7 hard-parts: pick per-column by bit width)
            card = max(s.metadata.columns[col].cardinality
                       for s in segments)
            if card <= 127:
                idt = np.int8
            elif card <= 32767:
                idt = np.int16
            else:
                idt = np.int32
            cols["ids:" + col] = self._stacked(
                segments, S, D, col, f"ids{np.dtype(idt).itemsize}",
                lambda ds, _t=idt: ds.dict_ids().astype(_t), idt)
        for col in plan.raw_cols:
            self._check_value_precision(segments, col, vdt)
            cols["val:" + col] = self._stacked(
                segments, S, D, col, "val",
                lambda ds: ds.values().astype(vdt), vdt)
        for col in plan.raw64_cols:
            # big-int filter columns: (hi, lo) i32 split planes, exact
            # under x64-off where f32 staging would alias (plan_ir vrange64)
            cols["valhi:" + col] = self._stacked(
                segments, S, D, col, "valhi",
                lambda ds: (ds.values().astype(np.int64) >> 24
                            ).astype(np.int32), np.int32)
            cols["vallo:" + col] = self._stacked(
                segments, S, D, col, "vallo",
                lambda ds: (ds.values().astype(np.int64) & 0xFFFFFF
                            ).astype(np.int32), np.int32)
        for col, kd, ke in plan.clp_cols:
            # CLP log columns stage as a pseudo-column family instead of
            # values: the logtype-id row plus kd dict-var-slot id rows
            # and ke encoded-var (hi, lo) i32 split rows — the 'clp'
            # leaf matches against these without ever materializing the
            # decoded strings (ops/clp_device.py)
            def clp_fetch(fn, _c=col):
                def fetch_row(seg):
                    try:
                        r = seg.data_source(_c).clp_reader
                    except (KeyError, ValueError, AttributeError):
                        r = None
                    if r is None:
                        raise _NotStageable()
                    return fn(r)
                return fetch_row
            family = [("clpid", clp_device.row_ids)] + [
                (f"clpdv{j}", lambda r, _j=j: clp_device.row_dict_slot(r, _j))
                for j in range(kd)]
            for j in range(ke):
                family += [(f"clpehi{j}",
                            lambda r, _j=j: clp_device.row_enc_hi(r, _j)),
                           (f"clpelo{j}",
                            lambda r, _j=j: clp_device.row_enc_lo(r, _j))]
            for kind, fn in family:
                cols[f"{kind}:{col}"] = self._block(
                    segments, S, D, col, kind, clp_fetch(fn), np.int32)

        # value columns: stage MATERIALIZED values (dictionary take done
        # host-side at staging, cached in HBM) rather than in-kernel
        # take_along_axis gathers — TPU gathers run off the vector units and
        # dominated the scan kernel when measured; a dense [S, D] value
        # block turns the hot path into a pure fused multiply-reduce
        value_cols = set()
        for ir in plan.value_irs:
            value_cols |= self._ir_cols(ir)
        for col in value_cols & set(plan.dict_cols):
            if "val:" + col in cols:
                continue
            self._check_value_precision(segments, col, vdt)
            def fetch_values(ds):
                vals = ds.values()
                if vals.dtype.kind not in "iuf":
                    raise _NotStageable()
                return vals.astype(vdt)
            cols["val:" + col] = self._stacked(
                segments, S, D, col, "val", fetch_values, vdt)

        if plan.valid_mask:
            cols["vmask"] = self._stage_vmask(segments, S, D)

        G = 0
        if plan.group_compact:
            cols["gkey"], G = self._stage_gkey(segments, S, D, plan)

        # per-leaf predicate parameters (cached: filters are frozen
        # expression trees, so they key the resolved literals exactly;
        # the entry also carries hist slot bounds and num_docs — they
        # depend only on (segments, plan), so a repeat query resolves
        # NOTHING)
        pkey = (batch_id(segments), plan, ctx.filter,
                tuple(ctx.agg_filters), S,
                tuple(ctx.group_by) if plan.tbucket else None)
        cached = self.stager.params_get_locked(pkey, segments)
        return cols, S, S_real, D, G, pkey, cached

    def _stage_params(self, segments, ctx: QueryContext, plan: DevicePlan,
                      S: int, S_real: int, pkey: tuple, cached, info):
        """A staged query's params dict, with NO lock held: the cached
        entry's, or on a miss every [S] parameter resolved into ONE
        packed int32 [K, S] HOST array (plan_ir.pack_params) — it stays
        numpy, in the cache too, and reaches the device as an argument
        of the launch (one transfer a launch, a batch's packs stacked
        on the host first: plan_ir.batch_params); the [S, C] LUT tables
        and the CLP leaf arrays keep a `_put` each. The entry is
        inserted under a short hold of the lock (`_params_insert`); the
        caller gets a dict of its own (the merge leg adds to it)."""
        if cached is None:
            cached = (tuple(segments), self._resolve_params(
                segments, ctx, plan, S, S_real, info))
            self._params_insert(pkey, cached, info)
        if plan.clp_cols:
            self._meter("clp_served")
        if plan.tbucket:
            self._meter("timeseries_leaf_device")
        return dict(cached[1])

    def _resolve_params(self, segments, ctx: QueryContext,
                        plan: DevicePlan, S: int, S_real: int, info):
        """What a parameter-cache miss builds (`_stage_params`)."""
        params: Dict[str, Any] = {}
        vdt = np.float64 if jax.config.read("jax_enable_x64") else np.float32
        rows: Dict[str, np.ndarray] = {}
        rows[NUM_DOCS] = np.zeros(S, dtype=np.int32)
        rows[NUM_DOCS][:S_real] = [s.num_docs for s in segments]
        if plan.tbucket:
            # fused time-bucket cells, a segment from its own origin
            # bucket: the origin's (hi, lo) planes + step + live bucket
            # count + the origin's offset — the ONLY things that change
            # across a dashboard's sliding refresh window (pkey carries
            # group_by above: same filter + different bucket expr must
            # not alias)
            spec = timeseries_device.plan_bucket(
                ctx.group_by[0], ctx.filter, segments)
            if spec is None \
                    or (spec.width, spec.count_pad) != plan.tbucket[1:]:
                raise _NotStageable()
            rows.update(timeseries_device.leaf_params(spec, segments, S))
        # histogram sketch slots: bucket bounds from segment metadata
        # (missing min/max -> host fallback)
        for j, (op, vidx, _fidx) in enumerate(plan.agg_ops):
            if not op.startswith("hist:"):
                continue
            col = plan.value_irs[vidx][1]
            lo, span = self._hist_bounds(segments, col)
            B = int(op.split(":")[1])
            rows[f"slot{j}:hlo"] = np.full(S, lo, dtype=vdt)
            rows[f"slot{j}:hscale"] = np.full(S, B / span, dtype=vdt)
        # leaf expressions in the exact order _plan appended leaves:
        # main filter first, then each distinct agg FILTER tree
        leaf_exprs: List[Function] = []
        if ctx.filter is not None:
            leaf_exprs += self._collect_leaf_exprs(ctx.filter, plan)
        seen_filters = set()
        for f in ctx.agg_filters:
            if f is not None and f not in seen_filters:
                seen_filters.add(f)
                leaf_exprs += self._collect_leaf_exprs(f, plan)
        for i, (leaf, expr) in enumerate(zip(plan.leaves, leaf_exprs)):
            if leaf.kind == "vrange":
                lo, hi = _vrange_bounds(expr, vdt)
                rows[f"leaf{i}:lo"] = np.full(S, lo, dtype=vdt)
                rows[f"leaf{i}:hi"] = np.full(S, hi, dtype=vdt)
                continue
            if leaf.kind == "vrange64":
                a, b = _vrange_int_bounds(expr)
                for name, cell in (("lohi", a >> 24), ("lolo", a & 0xFFFFFF),
                                   ("hihi", b >> 24), ("hilo", b & 0xFFFFFF)):
                    rows[f"leaf{i}:{name}"] = np.full(S, cell, dtype=np.int32)
                continue
            if leaf.kind == "clp":
                try:
                    arrs = clp_device.leaf_params(
                        i, leaf, segments, str(expr.args[1].value),
                        expr.name == "like", S)
                except ValueError:
                    raise _NotStageable()
                for k, arr in arrs.items():
                    params[k] = self._put(arr, info)
                continue
            resolved = self._resolve_leaf(segments, expr)
            if leaf.kind == "range":
                lo = np.zeros(S, dtype=np.int32)
                hi = np.full(S, -1, dtype=np.int32)
                for s, p in enumerate(resolved):
                    if p.kind == "range":
                        lo[s], hi[s] = p.lo, p.hi
                    elif p.kind == "all":
                        lo[s], hi[s] = 0, 2**31 - 1
                    elif p.kind == "none":
                        lo[s], hi[s] = 0, -1
                    elif p.kind == "set" and len(p.ids) == 1:
                        lo[s] = hi[s] = int(p.ids[0])
                    else:
                        raise _NotStageable()
                rows[f"leaf{i}:lo"], rows[f"leaf{i}:hi"] = lo, hi
            elif leaf.kind == "neq":
                idx = np.full(S, -1, dtype=np.int32)
                for s, p in enumerate(resolved):
                    if p.kind == "notset" and len(p.ids) == 1:
                        idx[s] = int(p.ids[0])
                    elif p.kind != "all":
                        raise _NotStageable()
                rows[f"leaf{i}:idx"] = idx
            elif leaf.kind == "lut":
                C = _pow2(max(s.metadata.columns[leaf.column].cardinality
                              for s in segments), floor=8)
                table = np.zeros((S, C), dtype=bool)
                for s, (seg, p) in enumerate(zip(segments, resolved)):
                    card = seg.metadata.columns[leaf.column].cardinality
                    if p.kind == "all":
                        table[s, :card] = True
                    elif p.kind == "none":
                        pass
                    elif p.kind == "range":
                        table[s, p.lo:p.hi + 1] = True
                    elif p.kind == "set":
                        table[s, p.ids] = True
                    elif p.kind == "notset":
                        table[s, :card] = True
                        table[s, p.ids] = False
                    else:
                        raise _NotStageable()
                params[f"leaf{i}:lut"] = self._put(table, info)

        params[PACK] = pack_params(plan, rows)
        return params

    @staticmethod
    def _resolve_leaf(segments, expr: Function) -> list:
        """A dictionary leaf's predicate, resolved a segment — computed
        once a DISTINCT dictionary: `resolve_predicate` reads a segment
        only through its column's sorted dictionary, and the segments of
        one table mostly share theirs (`Dictionary.content_key`), so 32
        segments cost one binary search a bound, not 32. A leaf any
        segment cannot resolve is not stageable."""
        col = expr.args[0].name if expr.args \
            and isinstance(expr.args[0], Identifier) else None
        by_dictionary: Dict[bytes, Any] = {}
        resolved = []
        for seg in segments:
            key = None
            if col is not None and seg.has_column(col):
                ds = seg.data_source(col)
                if ds.metadata.has_dictionary:
                    key = ds.dictionary.content_key
            p = by_dictionary.get(key) if key is not None else None
            if p is None:
                p = resolve_predicate(seg, expr)
                if p is None:
                    raise _NotStageable()
                if key is not None:
                    by_dictionary[key] = p
            resolved.append(p)
        return resolved

    # ------------------------------------------------------------------
    # upsert validity masks (device-path upsert, SURVEY §2.3)
    # ------------------------------------------------------------------
    @staticmethod
    def _mask_stamp(seg) -> int:
        """Version stamp of a segment's validDocIds bitmap (-1 = no
        bitmap: the row is a constant all-ones and never goes stale)."""
        valid = getattr(seg, "valid_doc_ids", None)
        return -1 if valid is None else valid.version

    def _needs_valid_mask(self, segments) -> bool:
        return any(getattr(s, "valid_doc_ids", None) is not None
                   for s in segments)

    def _cols_key(self, segments, plan: DevicePlan) -> tuple:
        """Staged-column identity for batch dedup/broadcast decisions:
        for valid-mask plans the mask version stamps join the key, so
        two coalesced members whose upsert bitmaps moved between their
        stagings stack separately instead of silently sharing one
        member's snapshot through the broadcast variant."""
        base = batch_id(segments)
        if plan.valid_mask:
            return (base, tuple(self._mask_stamp(s) for s in segments))
        return base

    def _stage_vmask(self, segments, S, D):
        """Staged bool [S, D] validity block for a batch carrying upsert
        segments — the `(segment, "__valid__")` pseudo-column. Rows ride
        the same host-row / residency / assembled tiers as column data,
        but every key carries the bitmap's mutation counter
        ('vmask:<version>'): upsert bitmaps mutate IN PLACE without the
        segment object changing, so an in-place clear() must address
        fresh keys — the staged mask can never go stale, and the cost of
        an upsert is re-staging one bool row, not a correctness hole.
        Append-only segments in a mixed batch stage all-ones rows (stamp
        -1, never mutated). Bitmap reads are snapshots: a concurrent
        upsert lands in the NEXT staging, the same discipline as the
        host executor's per-query to_mask()."""
        stamps = tuple(self._mask_stamp(s) for s in segments)

        def fetch_row(seg):
            valid = getattr(seg, "valid_doc_ids", None)
            if valid is None:
                return np.ones(seg.num_docs, dtype=bool)
            m = valid.to_mask()
            if len(m) < seg.num_docs:
                # defensive (engine batches are immutable, sizes fixed):
                # docs beyond the bitmap are not yet upsert-accounted
                m = np.concatenate(
                    [m, np.zeros(seg.num_docs - len(m), dtype=bool)])
            return m[:seg.num_docs]

        return self.stager.stage_block_locked(
            segments, S, D, "vmask", "__valid__", bool,
            lambda i, seg: (f"vmask:{stamps[i]}", "__valid__",
                            _pow2(seg.num_docs), fetch_row),
            stamps=stamps)

    def _stage_gkey(self, segments, S, D, plan: DevicePlan):
        """Compacted combined group keys: one int32 [S, D] code block,
        codes dense per segment over OBSERVED key tuples only (ref
        DictionaryBasedGroupKeyGenerator's map modes for sparse spaces).
        Returns (device block, G = pow2 pad of the max distinct count).
        Host rows cache (codes, decode table) per (segment, group cols)."""
        sig = ",".join(plan.group_cols)
        tables = [self._segment_gkey(seg, plan)[1] for seg in segments]
        G = _pow2(max(t.shape[0] for t in tables), floor=8)
        # guard BEFORE any upload: an over-cap key space must not pay a
        # useless HBM transfer (and LRU churn) on every repeat query
        if G > MAX_DEVICE_GROUPS \
                or S * G * len(plan.agg_ops) * 8 > MAX_GROUP_RESULT_BYTES:
            raise _NotStageable()

        def fetch_codes(seg):
            # lint: unlocked(runs synchronously inside _block on the staging thread, which holds the engine RLock)
            return self._segment_gkey_locked(seg, plan)[0]

        # host_cache=False: the (codes, table) pair is already host-cached
        # by _segment_gkey; caching the padded row too would double-store
        dev = self._block(segments, S, D, sig, "gkey", fetch_codes,
                          np.int32, host_cache=False)
        return dev, G

    def _segment_gkey(self, seg, plan: DevicePlan):
        """(codes [num_docs] int32, decode table [G_s, k] int32 dictIds)
        for one segment, via the host row cache. Takes the engine lock:
        assembly calls this outside it (the RLock makes the staging-path
        call reentrant)."""
        with self._engine_lock:
            return self._segment_gkey_locked(seg, plan)

    def _segment_gkey_locked(self, seg, plan: DevicePlan):
        rkey = (id(seg), "gkey", ",".join(plan.group_cols))
        cached = self.stager.host_get_locked(rkey, seg)
        if cached is not None:
            return cached
        cards = []
        prod = 1
        for col in plan.group_cols:
            if not seg.has_column(col):
                raise _NotStageable()
            card = max(int(seg.metadata.columns[col].cardinality), 1)
            cards.append(card)
            prod *= card
            if prod > (1 << 62):
                raise _NotStageable()  # mixed-radix overflows int64
        combined = np.zeros(seg.num_docs, np.int64)
        for col, card in zip(plan.group_cols, cards):
            combined = combined * card + \
                seg.data_source(col).dict_ids().astype(np.int64)
        if prod <= (1 << 26) and prod <= 16 * max(seg.num_docs, 1):
            # dense-remap fast path: O(D + keyspace) beats the O(D log D)
            # sort for the cold first query (VERDICT r4 weak #6); gated
            # relative to num_docs so a tiny segment with a huge key
            # space doesn't pay an O(keyspace) scan
            present = np.zeros(prod, dtype=bool)
            present[combined] = True
            uniq = np.flatnonzero(present).astype(np.int64)
            remap = np.empty(prod, dtype=np.int32)  # only hit slots read
            remap[uniq] = np.arange(len(uniq), dtype=np.int32)
            inv = remap[combined]
        else:
            uniq, inv = np.unique(combined, return_inverse=True)
        table = np.empty((len(uniq), len(plan.group_cols)), np.int32)
        rem = uniq.copy()
        for j in range(len(plan.group_cols) - 1, -1, -1):
            table[:, j] = rem % cards[j]
            rem //= cards[j]
        codes = inv.astype(np.int32)
        self.stager.host_put_locked(rkey, seg, (codes, table))
        return codes, table

    def _stacked(self, segments, S, D, col, kind, fetch, dtype):
        """[S, D] block of a real column: `fetch(data source)` gives a
        segment's row, through the stager's three tiers."""

        def fetch_row(seg):
            if not seg.has_column(col):
                raise _NotStageable()
            return fetch(seg.data_source(col))

        return self._block(segments, S, D, col, kind, fetch_row, dtype)

    def _block(self, segments, S, D, col, kind, fetch_row, dtype,
               host_cache: bool = True):
        """[S, D] block whose rows are `(segment, kind, col)`, each
        padded to its segment's own pow2 doc bucket."""
        return self.stager.stage_block_locked(
            segments, S, D, kind, col, dtype,
            lambda _i, seg: (kind, col, _pow2(seg.num_docs), fetch_row),
            host_cache=host_cache)

    def _meter(self, name: str, value: float = 1, **labels: str) -> None:
        """labels: the `reason=` of a `*_fallback` meter, the `path=` of
        `group_path`, the `cap=` of `scatter_compact`."""
        if self._metrics is None:
            return
        if labels:
            labels = dict(self._labels or {}, **labels)
        self._metrics.add_meter(name, value, labels=labels or self._labels)

    # ------------------------------------------------------------------
    # residency lifecycle (invalidation, warmup seeding, proactive load)
    # ------------------------------------------------------------------
    @property
    def residency(self):
        return self.stager.residency

    def invalidate_segment(self, name: str, keep=None) -> None:
        """BlockStager.invalidate_segment: a replaced/removed segment
        NAME leaves every tier, sparing entries pinned to `keep`."""
        self.stager.invalidate_segment(name, keep=keep)

    def prestage(self, segments, ctx: QueryContext) -> bool:
        """Proactively stage a plan's columns into the device tier
        WITHOUT launching a kernel — the segment-load warmup path: replay
        stages the hot plans' columns into HBM before the segment
        serves, so its first routed query pays compute, not the link."""
        if not segments or ctx.distinct or not self.supports(ctx):
            return False
        if ctx.aggregations and self._startree_candidate(segments):
            # star-tree leg first, mirroring execute's routing: a
            # plan that will serve from pre-agg records must warm
            # THOSE blocks, not the raw scan columns
            st_plan, _needed, fits, _reason = \
                startree_device.plan_startree(segments, ctx)
            if st_plan is not None:
                kern = startree_device.compiled_startree_kernel(st_plan)
                try:
                    self._stage_startree(
                        segments, ctx, st_plan, fits, _StagePass(None),
                        batchable=isinstance(kern, jax.stages.Wrapped))
                    return True
                except _NotStageable:
                    pass
        if ctx.aggregations:
            plan_info = self._plan(segments, ctx)
            plan = plan_info[0] if plan_info is not None else None
            kern = None if plan is None \
                else (kernels.compiled_sharded_kernel(plan, self._mesh)
                      if self._doc_axis > 1
                      else kernels.compiled_kernel(plan))
        else:
            plan = self._plan_topn(segments, ctx)
            kern = None if plan is None \
                else kernels.compiled_topn_kernel(plan)
        if plan is None:
            return False
        try:
            # mirror the serving path's S bucket (agg AND top-N
            # launches ride the factory now) so warmed blocks are
            # the EXACT blocks the first routed query will consume
            self._stage(segments, ctx, plan,
                        batchable=isinstance(kern, jax.stages.Wrapped))
        except _NotStageable:
            return False
        return True

    @staticmethod
    def _int_ir_bounds(segments, ir) -> Optional[Tuple[int, int]]:
        """Interval bounds of an int-valued value IR over the batch's
        metadata, or None when any column is non-int / unbounded or any
        node (incl. intermediates) can overflow i32 — the admission test
        for the exact 'isum' device path (kernels._eval_value_int)."""
        LIM = (1 << 31) - 1

        def rec(node) -> Optional[Tuple[int, int]]:
            op = node[0]
            if op == "col":
                lo, hi = None, None
                for seg in segments:
                    m = seg.metadata.columns.get(node[1])
                    if m is None or m.data_type.np_dtype.kind not in "iu" \
                            or m.min_value is None or m.max_value is None:
                        return None
                    lo = int(m.min_value) if lo is None \
                        else min(lo, int(m.min_value))
                    hi = int(m.max_value) if hi is None \
                        else max(hi, int(m.max_value))
                return (lo, hi) if lo is not None else None
            if op == "lit":
                v = float(node[1])
                if not v.is_integer():
                    return None
                return _clamp((int(v), int(v)))
            if op == "neg":
                a = rec(node[1])
                return None if a is None else _clamp((-a[1], -a[0]))
            if op not in ("add", "sub", "mul"):
                return None
            a, b = rec(node[1]), rec(node[2])
            if a is None or b is None:
                return None
            if op == "add":
                out = (a[0] + b[0], a[1] + b[1])
            elif op == "sub":
                out = (a[0] - b[1], a[1] - b[0])
            else:
                corners = [x * y for x in a for y in b]
                out = (min(corners), max(corners))
            return _clamp(out)

        def _clamp(bounds):
            return bounds if -LIM <= bounds[0] and bounds[1] <= LIM else None

        return rec(ir)

    @staticmethod
    def _ir_bits(seg0, ir) -> Optional[int]:
        """b with |value| <= 2^b for a value IR, from its columns' TYPES
        (INT 31, LONG 63) and its literals, or None where it can hold an
        Inf or a NaN whatever the data's range: a FLOAT/DOUBLE column, a
        `div`, a literal that is one. A slot of power p (kernels.
        _ADDITIVE) then adds finite f32s iff p * b <= 127."""
        op = ir[0]
        if op == "col":
            dtype = seg0.metadata.columns[ir[1]].data_type.np_dtype
            return 8 * dtype.itemsize - 1 if dtype.kind in "iu" else None
        if op == "lit":
            v = float(ir[1])
            return max(math.frexp(v)[1], 0) if math.isfinite(v) else None
        if op not in ("neg", "add", "sub", "mul"):
            return None
        bits = [TpuOperatorExecutor._ir_bits(seg0, c) for c in ir[1:]]
        if None in bits:
            return None
        return sum(bits) if op == "mul" else max(bits) + (op != "neg")

    @staticmethod
    def _hist_bounds(segments, col: str) -> Tuple[float, float]:
        """Global (lo, span) histogram bounds over the batch's segment
        metadata min/max; span clamped positive so scale stays finite."""
        lo, hi = np.inf, -np.inf
        for seg in segments:
            m = seg.metadata.columns.get(col)
            if m is None or m.min_value is None or m.max_value is None:
                raise _NotStageable()
            lo = min(lo, float(m.min_value))
            hi = max(hi, float(m.max_value))
        return lo, max(hi - lo, 1e-30)

    def _check_value_precision(self, segments, col: str, vdt) -> None:
        """float32 staging (x64 off, the TPU default) is exact only for
        integers with |v| <= 2^24; larger int/long columns (e.g. epoch
        millis) would silently round, so they fall back to the exact-f64
        host path. Float columns stay f32: they are approximate either way.
        """
        if vdt is np.float64:
            return
        for seg in segments:
            m = seg.metadata.columns.get(col)
            if m is None or m.data_type.np_dtype.kind not in "iu":
                continue
            lo, hi = m.min_value, m.max_value
            if lo is None or hi is None or \
                    max(abs(int(lo)), abs(int(hi))) > (1 << 24):
                raise _NotStageable()

    def _put(self, arr: np.ndarray, info=None, block: bool = False):
        """One host->device put of what a launch needs beside its packed
        parameters (a LUT table, CLP leaf arrays, a selection mask, the
        vector leg's query arrays, the merge's remaps), made OUTSIDE the
        staging lock and counted on the pass (`info`). block=True marks
        [S, D] blocks, which also shard over the docs axis on a 2-axis
        mesh; everything else shards over segments only. Every byte
        through here feeds the host->device transfer odometer
        (residency.transfer_bytes) — steady state must keep it flat."""
        residency_mod.note_transfer(arr.nbytes, column=block)
        self._meter("hbm_transfer_bytes", arr.nbytes)
        if info is not None:
            info.puts += 1
            info.xfer_bytes += arr.nbytes
        if self._mesh is None:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec as P
        if block and self._doc_axis > 1 and arr.ndim == 2:
            spec = P("segments", "docs")
        else:
            spec = P("segments", *([None] * (arr.ndim - 1)))
        return jax.device_put(arr, NamedSharding(self._mesh, spec))

    @staticmethod
    def _ir_cols(ir) -> set:
        if ir is None:
            return set()
        if ir[0] == "col":
            return {ir[1]}
        out = set()
        for c in ir[1:]:
            if isinstance(c, tuple):
                out |= TpuOperatorExecutor._ir_cols(c)
        return out

    def _collect_leaf_exprs(self, e: Expression, plan: DevicePlan) -> List[Function]:
        """Leaf expressions in the same order _build_filter_ir assigned
        indexes (depth-first, left-to-right)."""
        out: List[Function] = []

        def walk(node):
            assert isinstance(node, Function)
            if node.name in ("and", "or"):
                for a in node.args:
                    walk(a)
            elif node.name == "not":
                walk(node.args[0])
            else:
                out.append(node)
        walk(e)
        return out

    # ------------------------------------------------------------------
    def _assemble(self, segments, ctx: QueryContext, plan: DevicePlan,
                  packed: np.ndarray, S_real: int,
                  mappings: List[Dict[str, int]], span=None,
                  scatter=None, bucket=None) -> List[Any]:
        t0 = time.perf_counter()
        filter_cols = len(set(ctx.filter_columns()))
        # parity with executor_cpu: COUNT(*) materializes no column, so it
        # doesn't contribute to entries-scanned-post-filter
        n_valued_aggs = sum(
            1 for node in ctx.aggregations
            if node.args and not (isinstance(node.args[0], Identifier)
                                  and node.args[0].name == "*"))
        count_j = None
        widths = [kernels.slot_width(op) for op, _v, _f in plan.agg_ops]
        slot_offsets = np.concatenate(
            [[0], np.cumsum(widths)]).astype(int)
        # hist bucket bounds are batch-global: compute once per slot, not
        # per segment x function
        hist_bounds = {
            j: self._hist_bounds(segments, plan.value_irs[vidx][1])
            for j, (op, vidx, _f) in enumerate(plan.agg_ops)
            if op.startswith("hist:")}
        is_group = bool(plan.num_groups or plan.group_compact)
        if is_group:
            for j, (op, _vidx, fidx) in enumerate(plan.agg_ops):
                if op == "count" and fidx is None:
                    count_j = j
                    break
            assert count_j is not None  # _plan guarantees a count slot
        results = []
        kept = []
        for s, seg in enumerate(segments[:S_real]):
            if is_group:
                matched = int(round(float(packed[s, :, count_j].sum())))
                kept.append(matched)
            else:
                matched = int(round(float(packed[s, 0])))
            stats = ExecutionStats(
                num_docs_scanned=matched,
                num_entries_scanned_in_filter=(
                    seg.num_docs * filter_cols if ctx.filter is not None else 0),
                num_entries_scanned_post_filter=matched * n_valued_aggs,
                num_segments_processed=1,
                num_segments_matched=1 if matched else 0,
                total_docs=seg.num_docs)
            if is_group:
                results.append(self._assemble_group(
                    seg, s, ctx, plan, packed, count_j, mappings, stats,
                    bucket))
            else:
                inters = []
                for fn, mapping in zip(ctx.agg_functions, mappings):
                    slots = {}
                    for op, j in mapping.items():
                        off = 1 + slot_offsets[j]
                        w = widths[j]
                        plan_op = plan.agg_ops[j][0]
                        if plan_op == "isum":
                            slots[op] = _isum_value(packed[s, off:off + w])
                            continue
                        if plan_op.startswith("isum:u"):
                            slots[op] = _isum_u_value(
                                packed[s, off:off + w])
                            continue
                        slots[op] = packed[s, off] if w == 1 \
                            else packed[s, off:off + w]
                        if op.startswith("hist:"):
                            lo, span = hist_bounds[j]
                            slots["hist_lo"] = lo
                            slots["hist_width"] = span / w
                    inters.append(fn.from_device_slots(slots))
                results.append(AggregationResult(inters, stats))
        if is_group:
            self._note_groups(span, packed.nbytes,
                              sum(len(r.groups) for r in results), t0)
            self._note_scatter(span, scatter, kept)
        return results

    def _note_scatter(self, span, scatter, kept: List[int]) -> None:
        """A grouped launch's scatter work, once its result is here: the
        rung the kernel chose (`kernels.compact_cap` on the most rows a
        segment kept, from the counts the result carries; a coalesced
        member reads its own, where the launch ran the batch's largest)
        and the rows x additive slots it handed XLA's scatter-add
        (`kernels.scatter_rows`). Meters `scatter_rows` and, on
        `scatter`, `scatter_compact{cap=}`; span `scatterRows` and
        `scatterCap` (0: the full scatter)."""
        if scatter is None:
            return
        plan, num_groups, S, D, docs, path = scatter
        cap = kernels.compact_cap(docs, max(kept, default=0)) \
            if path == "scatter" else 0
        rows = kernels.scatter_rows(plan, num_groups, S, D, docs, cap)
        self._meter("scatter_rows", rows)
        if path == "scatter":
            self._meter("scatter_compact", cap=str(cap))
        if span is not None:
            span.set(scatterRows=rows)
            if path == "scatter":
                span.set(scatterCap=cap)

    def _note_groups(self, span, nbytes: int, present: int,
                     t0: float) -> None:
        """A grouped fetch's numbers, on the meter and (traced) on the
        launch's DeviceDispatch span: bytes of group table fetched,
        groups present in it (summed over the segments where the host
        folds), and the time from the fetched slots to the group table
        (inside the request's assembleMs)."""
        self._meter("group_result_bytes", nbytes)
        if span is not None:
            span.set(groupResultBytes=int(nbytes), groupsPresent=present,
                     groupDecodeMs=round(
                         (time.perf_counter() - t0) * 1e3, 3))

    def _assemble_group(self, seg, s, ctx, plan, packed, count_j, mappings,
                        stats, bucket=None):
        present = np.nonzero(packed[s, :, count_j] > 0)[0]

        dicts = [seg.data_source(c).dictionary for c in plan.group_cols]
        buckets = None
        if plan.group_compact:
            # compacted codes -> per-column dictIds via the decode table
            _codes, table = self._segment_gkey(seg, plan)
            present = present[present < table.shape[0]]
            ids_per_col = [table[present, j]
                           for j in range(len(plan.group_cols))]
        else:
            # decode combined keys (mixed radix) -> per-column dictIds
            cards = [seg.metadata.columns[c].cardinality
                     for c in plan.group_cols]
            rem = present.copy()
            ids_per_col = []
            for stride in plan.group_strides:
                ids_per_col.append(rem // stride)
                rem = rem % stride
            if plan.tbucket:
                # the fused time bucket is the key's lowest digit: after
                # peeling every tag stride, the remainder is the bucket
                # counted from this segment's origin
                buckets = timeseries_device.origin(bucket, seg) + rem
            valid = np.ones(len(present), dtype=bool)
            for ids, card in zip(ids_per_col, cards):
                valid &= ids < card
            present = present[valid]
            ids_per_col = [ids[valid] for ids in ids_per_col]
            if buckets is not None:
                buckets = timeseries_device.bucket_keys(
                    bucket, buckets[valid])

        key_cols = [d.get_values(ids) for d, ids in zip(dicts, ids_per_col)]
        groups: Dict[tuple, list] = {}
        for gi, g in enumerate(present):
            key = tuple(_py(col[gi]) for col in key_cols)
            if buckets is not None:
                key = (_py(buckets[gi]),) + key
            inters = []
            for fn, mapping in zip(ctx.agg_functions, mappings):
                slots = {op: packed[s, g, j] for op, j in mapping.items()}
                inters.append(fn.from_device_slots(slots))
            groups[key] = inters
        return GroupByResult(groups, stats)

    def _assemble_merged(self, segments, ctx: QueryContext,
                         plan: DevicePlan, packed: np.ndarray,
                         S_real: int, mappings: List[Dict[str, int]],
                         minfo) -> List[Any]:
        """ONE AggregationResult covering the whole segment batch, from
        the collective-merge kernel's packed row (layout documented in
        ops/collective.py). The [S] matched tail carries exactly the
        per-segment facts the host fold would have summed, so the
        ExecutionStats equal folding the per-segment path's stats."""
        S = minfo["S"]
        stats = self._batch_stats(
            segments, ctx, S_real,
            [int(round(float(m))) for m in np.asarray(packed[-S:][:S_real])])
        widths = [kernels.slot_width(op) for op, _v, _f in plan.agg_ops]
        slot_offsets = np.concatenate(
            [[0], np.cumsum(widths)]).astype(int)
        hist_bounds = {
            j: self._hist_bounds(segments, plan.value_irs[vidx][1])
            for j, (op, vidx, _f) in enumerate(plan.agg_ops)
            if op.startswith("hist:")}
        inters = []
        for fn, mapping in zip(ctx.agg_functions, mappings):
            slots = {}
            for op, j in mapping.items():
                off = int(slot_offsets[j])  # no leading matched column
                w = widths[j]
                plan_op = plan.agg_ops[j][0]
                if plan_op == "isum":
                    slots[op] = _isum_value(packed[off:off + w])
                    continue
                if plan_op.startswith("isum:u"):
                    slots[op] = _isum_u_value(packed[off:off + w])
                    continue
                slots[op] = packed[off] if w == 1 \
                    else packed[off:off + w]
                if op.startswith("hist:"):
                    lo, span = hist_bounds[j]
                    slots["hist_lo"] = lo
                    slots["hist_width"] = span / w
            inters.append(fn.from_device_slots(slots))
        return [AggregationResult(inters, stats)]

    @staticmethod
    def _batch_stats(segments, ctx: QueryContext, S_real: int,
                     matched_i: List[int]) -> ExecutionStats:
        """The ExecutionStats of a whole segment batch from its
        per-segment matched counts: what folding the per-segment
        results' stats would give."""
        total_matched = sum(matched_i)
        filter_cols = len(set(ctx.filter_columns()))
        n_valued_aggs = sum(
            1 for node in ctx.aggregations
            if node.args and not (isinstance(node.args[0], Identifier)
                                  and node.args[0].name == "*"))
        total_docs = sum(seg.num_docs for seg in segments[:S_real])
        return ExecutionStats(
            num_docs_scanned=total_matched,
            num_entries_scanned_in_filter=(
                total_docs * filter_cols if ctx.filter is not None else 0),
            num_entries_scanned_post_filter=total_matched * n_valued_aggs,
            num_segments_processed=S_real,
            num_segments_matched=sum(1 for m in matched_i if m),
            total_docs=total_docs)

    def _assemble_folded(self, segments, ctx: QueryContext,
                         plan: DevicePlan, packed: np.ndarray, S_real: int,
                         mappings: List[Dict[str, int]], minfo,
                         span=None, scatter=None, bucket=None) -> List[Any]:
        """ONE GroupByResult for the whole segment batch, from the
        integer row `kernels.fold_groups` packed: the [n_slots, G] group
        table over the global key space, then each segment's matched
        count. No Python a group: the present groups' slot words go to
        each function as columns (`from_device_slot_columns`), and a key
        column is the remap's union with the groups' indices into it
        (`CodedColumn`); the result's `.groups` dict is built only where
        somebody reads it."""
        t0 = time.perf_counter()
        G, n_slots = minfo["G"], len(plan.agg_ops)
        row = np.asarray(packed)
        table = row[:G * n_slots].reshape(n_slots, G)
        kept = row[G * n_slots:][:S_real].tolist()
        stats = self._batch_stats(segments, ctx, S_real, kept)
        present = np.flatnonzero(table[plan.agg_ops.index(
            ("count", None, None))] > 0)  # _plan guarantees the slot
        decode = minfo["decode"]
        if plan.group_compact:
            key_columns = [CodedColumn(values, present) for values in decode]
        else:
            strides, cards, unions = decode
            key_columns = [
                CodedColumn(unions[ci],
                            (present // strides[ci]) % cards[ci])
                for ci in range(len(plan.group_cols))]
            if bucket is not None:
                # the fused time bucket leads the GROUP BY and is the
                # key's lowest digit, on the window's axis
                pad = plan.tbucket[2]
                key_columns.insert(0, CodedColumn(
                    timeseries_device.bucket_keys(bucket, np.arange(pad)),
                    present % pad))
        vdt = np.float64 if row.dtype.itemsize == 8 else np.float32
        slot_cols = [
            table[j, present] if op == "count"
            else table[j, present].view(vdt)
            for j, (op, _v, _f) in enumerate(plan.agg_ops)]
        value_columns = [
            fn.from_device_slot_columns(
                {op: slot_cols[j] for op, j in mapping.items()})
            for fn, mapping in zip(ctx.agg_functions, mappings)]
        self._note_groups(span, table.nbytes, len(present), t0)
        self._note_scatter(span, scatter, kept)
        return [GroupByResult(stats=stats, key_columns=key_columns,
                              value_columns=value_columns)]


def _isum_value(planes: np.ndarray) -> float:
    """Rebuild the exact int sum from the isum slot's packed planes
    (kernels._isum_slot): pairs of f32-exact signed (hi, lo) halves per
    6-bit value digit, top digit sign-carrying."""
    total = 0
    for k in range(kernels.ISUM_PLANES):
        s = int(planes[2 * k]) * 4096 + int(planes[2 * k + 1])
        total += s << (6 * k)
    return float(total)


def _isum_u_value(planes: np.ndarray) -> float:
    """Rebuild the exact non-negative int sum from unsigned 7-bit plane
    halves (kernels._isum_u_slot)."""
    total = 0
    for k in range(len(planes) // 2):
        s = int(planes[2 * k]) * 4096 + int(planes[2 * k + 1])
        total += s << (kernels.ISUM_U_BITS * k)
    return float(total)


class _StagePass:
    """One query's staging pass: its clock reads and its own counts,
    for the DeviceDispatch span (`_staging_attrs`) and the charge slip.
    Passes overlap — only the block look-ups hold the staging lock — so
    none of this can be an engine attribute or an odometer's diff. An
    untraced pass (span None) reads no clock; it still counts its puts
    and bytes."""

    __slots__ = ("span", "t_open", "t_plan", "wait_s", "blocks_s",
                 "insert_s", "puts", "xfer_bytes")

    def __init__(self, span):
        self.span = span
        #: perf_counter seconds: the span's opening, and plan + kernel
        #: look-up done (`planned`)
        self.t_open = self.t_plan = \
            span.node.start_ms / 1e3 if span is not None else 0.0
        #: seconds waited for the staging lock, and holding it: for the
        #: block look-ups, and to insert what a parameter miss built
        self.wait_s = self.blocks_s = self.insert_s = 0.0
        #: `_put` calls, and host->device bytes of puts and row uploads
        self.puts = 0
        self.xfer_bytes = 0

    def planned(self) -> None:
        if self.span is not None:
            self.t_plan = time.perf_counter()


def _shape_sig(cols: Dict[str, Any], params: Dict[str, Any]) -> tuple:
    """Shape signature of a staged launch — the part of the coalesce
    key that plan + (S, D, G) alone cannot pin down across tables: LUT
    leaf widths pad to each table's own cardinality bucket and dict-id
    blocks stage at cardinality-chosen widths (i8/i16/i32), so two
    tables with equal plans can still stage unstackable pytrees. Equal
    signatures guarantee members stack leaf-for-leaf."""
    return (
        tuple(sorted((k, tuple(map(int, v.shape)), str(v.dtype))
                     for k, v in cols.items())),
        tuple(sorted((k, tuple(map(int, v.shape)), str(v.dtype))
                     for k, v in params.items())),
    )


class _NotStageable(Exception):
    pass


class _MergeFallback(Exception):
    """A collective-merge gate tripped; the launch keeps the per-segment
    kernel and the host fold (reason feeds mesh_merge_fallback)."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


def _vrange_bounds(e: Function, vdt=np.float64) -> Tuple[float, float]:
    """Closed [lo, hi] bounds for a raw-value comparison, computed in the
    STAGING dtype vdt: nextafter in float64 would collapse back to the
    original value when later cast to float32, silently turning strict
    comparisons into non-strict ones (x > 5 executing as x >= 5)."""
    def lv(i):
        raw = e.args[i].value  # type: ignore[union-attr]
        try:
            if isinstance(raw, str):
                raw = int(raw) if raw.lstrip("+-").isdigit() else float(raw)
            v = vdt(raw)
            # a literal not exactly representable in the staging dtype (e.g.
            # 16777217 in f32, 2^53+1 in f64) would alias to a neighbour and
            # match rows the exact host path would not — fall back instead.
            # Compare in exact Python arithmetic: int(v)/float(v) vs raw
            # avoids rounding the reference side through the staging dtype.
            exact = (int(v) if isinstance(raw, int) and float(v).is_integer()
                     else float(v))
            if exact != raw:
                raise _NotStageable()
        except (OverflowError, ValueError, TypeError):
            raise _NotStageable() from None
        return v
    if e.name == "equals":
        return lv(1), lv(1)
    if e.name == "between":
        return lv(1), lv(2)
    if e.name == "greater_than":
        return np.nextafter(lv(1), vdt(np.inf)), vdt(np.inf)
    if e.name == "greater_than_or_equal":
        return lv(1), vdt(np.inf)
    if e.name == "less_than":
        return vdt(-np.inf), np.nextafter(lv(1), vdt(-np.inf))
    if e.name == "less_than_or_equal":
        return vdt(-np.inf), lv(1)
    raise _NotStageable()


_INT_BOUND_CLAMP = 1 << 54  # split planes stay exact below 2^55


def _vrange_int_bounds(e: Function) -> Tuple[int, int]:
    """Closed [lo, hi] INTEGER bounds for a comparison on an int column
    (vrange64 leaves). Exact Python integer arithmetic throughout."""
    import math

    def lv(i):
        raw = e.args[i].value  # type: ignore[union-attr]
        try:
            if isinstance(raw, str):
                raw = int(raw) if raw.lstrip("+-").isdigit() else float(raw)
            if isinstance(raw, bool) or raw is None:
                raise _NotStageable()
            if isinstance(raw, float) and not math.isfinite(raw):
                raise _NotStageable()  # ceil/floor of inf/nan would raise
            return raw
        except (ValueError, TypeError, OverflowError):
            raise _NotStageable() from None

    def clamp(v: int) -> int:
        return max(-_INT_BOUND_CLAMP, min(_INT_BOUND_CLAMP, v))

    if e.name == "equals":
        v = lv(1)
        if isinstance(v, float):
            if not v.is_integer():
                return 1, 0  # empty interval
            v = int(v)
        return clamp(v), clamp(v)
    if e.name == "between":
        a, b = lv(1), lv(2)
        return clamp(math.ceil(a)), clamp(math.floor(b))
    if e.name == "greater_than":
        return clamp(math.floor(lv(1)) + 1), _INT_BOUND_CLAMP
    if e.name == "greater_than_or_equal":
        return clamp(math.ceil(lv(1))), _INT_BOUND_CLAMP
    if e.name == "less_than":
        return -_INT_BOUND_CLAMP, clamp(math.ceil(lv(1)) - 1)
    if e.name == "less_than_or_equal":
        return -_INT_BOUND_CLAMP, clamp(math.floor(lv(1)))
    raise _NotStageable()


def _py(v):
    return v.item() if isinstance(v, np.generic) else v
