"""Device-side vector similarity search (ref
core/operator/filter/VectorSimilarityFilterOperator over the Lucene99
HNSW reader; here the exact/IVF matmul design of
segment/vector_index.py, run on TPU through the kernel factory).

The host keeps what it is good at — index admission, query-vector
parsing, IVF probe selection (an argsort over n_cells centroid scores)
— and the device does what IT is good at: `scores = V @ q` over every
document at once, which is the single best MXU fit in the codebase.
Each segment's [n, d] vector block (and its IVF cell assignments)
stages as `(segment, "__vec__/<col>/<leg>")` pseudo-columns through the
engine's host-row / residency / assembled-block tiers, flattened to one
[S, D * dim_pad] f32 row family so every batch composition shares the
resident rows.

The QUERY VECTOR AND topK live in staged params, never the plan: a
VectorPlan carries structure only (column, pow2 dim/K buckets, IVF
shape, residual-filter IR), so fingerprint-equal concurrent ANN queries
— different query vectors, same shape — coalesce into ONE jit(vmap)
launch through the dispatch ring exactly like scan kernels.

Host-contract parity (query/filter._vector_similarity_mask): the K
winners are chosen over ALL docs (masked only by padding validity and
the IVF probe-cell mask — NEVER by the residual predicate), and the
residual `WHERE ... AND vector_similarity(...)` conjuncts intersect
AFTER selection, so hybrid filters compose K-before-filter exactly as
the host path does. Ties break toward lower doc ids on both paths
(`jax.lax.top_k` device-side, the lexsort in VectorIndex.top_k
host-side), making exact-path doc-id results bit-identical.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pinot_tpu.ops import kernels
from pinot_tpu.query.expressions import Function, Identifier, Literal
from pinot_tpu.query.results import ExecutionStats, SelectionResult

#: `vector_fallback{reason=}` vocabulary — why a vector_similarity
#: query left the device path for the host index search:
#:   disabled  — pinot.server.vector.enabled=false
#:   noIndex   — a batch segment has no vector index on the column
#:   metric    — a non-cosine index (L2 staging keeps the host path)
#:   hybrid    — the filter shape doesn't decompose into
#:               vector_similarity AND device-stageable conjuncts
#:               (OR/NOT around the vector fn, an unstageable residual
#:               conjunct, or an ORDER BY the kernel can't honor)
#:   staging   — column staging failed / doc-sharded mesh / block caps
#:   precision — K or dimensionality outside the exact device envelope
FALLBACK_REASONS = ("disabled", "noIndex", "metric", "hybrid",
                    "staging", "precision")

#: IVF probe width — mirrors VectorIndex.top_k's default nprobe
NPROBE = 8

#: per-segment staged vector row cap (f32 bytes): above this one
#: segment's [D, dim_pad] block would dominate HBM — host path instead
MAX_VEC_ROW_BYTES = 512 << 20


def _pow2(n: int, floor: int = 8) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


class VectorPlan(NamedTuple):
    """Frozen device plan for one ANN query SHAPE. Query constants (the
    vector, K, the probe-cell mask, residual predicate literals) live in
    params; the plan carries only structure, so fingerprint-equal
    concurrent queries share one compiled kernel and one launch. The
    residual-filter fields mirror DevicePlan's so kernels._eval_filter
    and the engine's _stage run unchanged against this plan."""
    col: str
    dim_pad: int          # pow2 bucket of the vector dimensionality
    k_pad: int            # pow2 bucket of topK (actual K in params)
    ivf: bool = False
    cells_pad: int = 0    # pow2 bucket of the coarse-cell count
    # -- DevicePlan-compatible residual-filter structure ---------------
    filter_ir: Optional[tuple] = None
    leaves: tuple = ()
    value_irs: tuple = ()
    agg_ops: tuple = ()
    group_compact: bool = False
    tbucket: tuple = ()
    dict_cols: Tuple[str, ...] = ()
    raw_cols: Tuple[str, ...] = ()
    raw64_cols: Tuple[str, ...] = ()
    clp_cols: tuple = ()
    valid_mask: bool = False


# ---------------------------------------------------------------------------
# Kernels (traced; purity-checked as a kernel module)
# ---------------------------------------------------------------------------

def make_vector_kernel(plan: VectorPlan, kind: str = "vector",
                       extra: tuple = ()):
    """[S, D] batched similarity top-K. cols: "vec:<col>" f32
    [S, D * dim_pad] flattened vector blocks (+ "vcell:<col>" i32 cell
    assignments when IVF), plus whatever the residual filter staged.
    params: "vq:q" [S, dim_pad] normalized query, "vq:k" [S] i32 topK,
    "vq:cells" [S, cells_pad] bool probe mask (IVF only), plus residual
    leaf params. Output f32 [S, 1 + 2*kk]: col 0 = surviving-row count,
    then kk doc ids (-1 = empty; exact in f32 below 2^24 docs), then kk
    scores aligned with the ids."""
    fp = kernels.plan_fingerprint(plan)

    def kernel(cols, params, num_docs, D):
        # the residual filter's parameters come packed from _stage
        params, num_docs = kernels.unpack_params(plan, params, num_docs)
        kernels.note_trace(kind, fp, (*extra, int(num_docs.shape[-1]), D))
        valid = jnp.arange(D, dtype=jnp.int32)[None, :] < num_docs[:, None]
        V = cols["vec:" + plan.col].reshape(-1, D, plan.dim_pad)
        # scores = V @ q: ONE batched matvec over every doc of every
        # segment — the MXU path (padding docs/dims are zero rows, so
        # they contribute nothing and are masked out below anyway)
        scores = jnp.einsum("sde,se->sd", V, params["vq:q"],
                            preferred_element_type=jnp.float32)
        # candidate mask: padding validity + IVF probe cells. The
        # residual predicate is deliberately NOT here — K picks over all
        # docs first (host-contract K-before-filter parity).
        cand = valid
        if plan.ivf:
            cell = jnp.clip(cols["vcell:" + plan.col], 0,
                            plan.cells_pad - 1)
            cand = cand & jnp.take_along_axis(params["vq:cells"], cell,
                                              axis=1)
        score = jnp.where(cand, scores, -jnp.inf)
        kk = min(plan.k_pad, D)
        top_vals, top_idx = jax.lax.top_k(score, kk)
        # residual WHERE conjuncts (and the upsert validity mask)
        # intersect AFTER selection — rows the filter drops vanish, but
        # never promote losers into the K
        resid = valid
        if plan.valid_mask:
            resid = resid & cols["vmask"]
        if plan.filter_ir is not None:
            resid = resid & kernels._eval_filter(plan.filter_ir, plan,
                                                 cols, params)
        keep = jnp.take_along_axis(resid & cand, top_idx, axis=1)
        keep = keep & (jnp.arange(kk, dtype=jnp.int32)[None, :]
                       < params["vq:k"][:, None])
        keep = keep & (top_vals > -jnp.inf)
        idx_out = jnp.where(keep, top_idx, -1).astype(jnp.float32)
        svals = jnp.where(keep, top_vals, -jnp.inf).astype(jnp.float32)
        matched = jnp.sum(keep, axis=1).astype(jnp.float32)
        return jnp.concatenate([matched[:, None], idx_out, svals], axis=1)

    return kernel


@functools.lru_cache(maxsize=256)
def compiled_vector_kernel(plan: VectorPlan):
    return jax.jit(make_vector_kernel(plan), static_argnames=("D",))


def make_batched_vector_kernel(plan: VectorPlan, B: int,
                               stacked: bool = False):
    """Coalesced ANN launch (mirrors kernels.make_batched_topn_kernel):
    broadcast members share one staged vector block and differ only in
    params (the concurrent-dashboard / ANN-fleet case — B queries, one
    pass over one copy of the vectors); stacked members stack per-table
    blocks from the residency tier."""
    kind = "vector_batched_stacked" if stacked else "vector_batched"
    base = make_vector_kernel(plan, kind=kind, extra=(B,))
    if stacked:
        def fn(clist, plist, ndlist, D, G=0):
            cs, ns = map(kernels.stack_members, (clist, ndlist))
            ps = kernels.stack_params(plist)
            return jax.vmap(lambda c, p, nd: base(c, p, nd, D=D))(
                cs, ps, ns)
    else:
        def fn(cols, plist, num_docs, D, G=0):
            ps = kernels.stack_params(plist)
            idx = jnp.arange(B, dtype=jnp.int32)
            return jax.vmap(lambda p, _i: base(cols, p, num_docs, D=D))(
                ps, idx)
    return jax.jit(fn, static_argnames=("D", "G"))


@functools.lru_cache(maxsize=256)
def compiled_batched_vector_kernel(plan: VectorPlan, B: int,
                                   stacked: bool = False):
    return make_batched_vector_kernel(plan, B, stacked)


# ---------------------------------------------------------------------------
# Host-side planning (filter decomposition + admission)
# ---------------------------------------------------------------------------

def contains_vector(e) -> bool:
    """True when a vector_similarity call appears anywhere in a filter
    tree — the engine's routing test for the vector leg."""
    if not isinstance(e, Function):
        return False
    if e.name == "vector_similarity":
        return True
    return any(contains_vector(a) for a in e.args)


def split_filter(e):
    """(vector fn, residual expr or None, None) when the filter is the
    bare vector_similarity call or a top-level AND with EXACTLY ONE
    vector conjunct; (None, None, reason) otherwise. OR/NOT around the
    vector fn changes its semantics from "intersect with the K nearest"
    to something no top-K kernel computes — those stay host-side
    (reason 'hybrid')."""
    if not isinstance(e, Function):
        return None, None, "hybrid"
    if e.name == "vector_similarity":
        return e, None, None
    if e.name != "and":
        return None, None, "hybrid"
    vec = [a for a in e.args if isinstance(a, Function)
           and a.name == "vector_similarity"]
    rest = [a for a in e.args if not (isinstance(a, Function)
                                      and a.name == "vector_similarity")]
    if len(vec) != 1 or any(contains_vector(a) for a in rest):
        return None, None, "hybrid"
    if not rest:
        return vec[0], None, None
    residual = rest[0] if len(rest) == 1 else Function("and", tuple(rest))
    return vec[0], residual, None


def parse_args(fn: Function):
    """(column, query vector f32, K) — the host mask's exact argument
    contract (query/filter._vector_similarity_mask), including the
    default K=10."""
    if not fn.args or not isinstance(fn.args[0], Identifier):
        raise ValueError("vector_similarity needs a column")
    if len(fn.args) < 2 or not isinstance(fn.args[1], Literal):
        raise ValueError("vector_similarity needs a query vector")
    k = int(fn.args[2].value) if len(fn.args) > 2 \
        and isinstance(fn.args[2], Literal) else 10
    q = np.asarray(json.loads(str(fn.args[1].value)), np.float32).ravel()
    return fn.args[0].name, q, k


def _index_of(seg, col: str):
    try:
        ds = seg.data_source(col)
    except (KeyError, ValueError):
        return None
    return getattr(ds, "vector_index", None)


def admit(segments, col: str, qvec: np.ndarray, k: int, max_k: int):
    """((dim_pad, ivf, cells_pad), None) when every segment's index
    admits the device path; (None, reason) otherwise."""
    if k <= 0 or k > max_k:
        return None, "precision"
    dim = 0
    ivf = False
    max_cells = 0
    for seg in segments:
        index = _index_of(seg, col)
        if index is None:
            return None, "noIndex"
        if index.metric != "cosine":
            return None, "metric"
        d = int(index.vectors.shape[1]) if index.vectors.ndim == 2 else 0
        if d == 0 or (dim and d != dim):
            return None, "precision"
        dim = d
        if index.centroids is not None:
            ivf = True
            max_cells = max(max_cells, len(index.centroids))
    if dim != len(qvec):
        return None, "precision"
    dim_pad = _pow2(dim)
    if dim_pad * 4 > MAX_VEC_ROW_BYTES:
        return None, "staging"
    return (dim_pad, ivf, _pow2(max_cells) if ivf else 0), None


# ---------------------------------------------------------------------------
# Staged-row fetchers + query params
# ---------------------------------------------------------------------------

def vector_row(seg, col: str, dim_pad: int, pad_docs: int) -> np.ndarray:
    """One segment's flattened f32 vector row: [pad_docs, dim_pad]
    zero-padded then raveled, so the row is a prefix of any wider
    assembled block that shares dim_pad (inner-dim padding is uniform
    across the batch — the flat layout composes with per-row pow2 doc
    buckets)."""
    index = _index_of(seg, col)
    out = np.zeros((pad_docs, dim_pad), np.float32)
    v = index.vectors
    out[:v.shape[0], :v.shape[1]] = v
    return out.reshape(-1)


def cell_row(seg, col: str, pad_docs: int) -> np.ndarray:
    """One segment's i32 IVF cell-assignment row (zeros for exact-only
    segments — their probe mask stages all-True, so cell 0 admits)."""
    index = _index_of(seg, col)
    out = np.zeros(pad_docs, np.int32)
    a = index.assignments
    if a is not None:
        out[:len(a)] = a
    return out


def query_params(segments, plan: VectorPlan, qvec: np.ndarray, k: int,
                 S: int, nprobe: int = NPROBE) -> Dict[str, np.ndarray]:
    """Per-query staged params: the cosine-normalized zero-padded query
    vector, the actual K, and (IVF) the probe-cell mask — computed with
    VectorIndex.probe_cells so probe selection (including the
    empty-candidate fall-back-to-all rule) is host-parity by
    construction."""
    n = float(np.linalg.norm(qvec))
    qn = (qvec / max(n, 1e-30)).astype(np.float32)
    q = np.zeros(plan.dim_pad, np.float32)
    q[:len(qn)] = qn
    out = {"vq:q": np.tile(q, (S, 1)),
           "vq:k": np.full(S, k, np.int32)}
    if plan.ivf:
        cells = np.zeros((S, plan.cells_pad), dtype=bool)
        for s, seg in enumerate(segments):
            index = _index_of(seg, col=plan.col)
            if index is None or index.centroids is None:
                cells[s, :] = True
                continue
            probe = index.probe_cells(qn, nprobe)
            if probe is None:
                cells[s, :] = True
            else:
                cells[s, probe] = True
        out["vq:cells"] = cells
    return out


# ---------------------------------------------------------------------------
# Host-side assembly + broker-side merge
# ---------------------------------------------------------------------------

def unpack(packed_row: np.ndarray):
    """(doc ids int64 score-desc, scores f32) of one segment's packed
    kernel row — the raw K winners before doc-order materialization."""
    kk = (len(packed_row) - 1) // 2
    ids = np.asarray(packed_row[1:1 + kk], np.float64)
    scores = np.asarray(packed_row[1 + kk:1 + 2 * kk], np.float32)
    good = ids >= 0
    return ids[good].astype(np.int64), scores[good]


def assemble(segments, ctx, plan: VectorPlan, packed: np.ndarray,
             S_real: int) -> List[SelectionResult]:
    """packed [S, 1 + 2*kk] -> SelectionResults: surviving winners
    materialize in doc-id order truncated to LIMIT+OFFSET, exactly as
    the host SelectionOnlyOperator walks the K-hot filter mask."""
    from pinot_tpu.query.executor_cpu import _project_rows, expand_star
    from pinot_tpu.query.filter import SegmentColumnProvider
    packed = np.asarray(packed)
    fetch = ctx.limit + ctx.offset
    filter_cols = len(set(ctx.filter_columns()))
    results = []
    for s, seg in enumerate(segments[:S_real]):
        ids, _scores = unpack(packed[s])
        ids = ids[ids < seg.num_docs]
        matched = int(round(float(packed[s, 0])))
        idx = np.sort(ids)[:fetch]
        provider = SegmentColumnProvider(seg)
        rows = _project_rows(seg, ctx.select, provider, idx)
        stats = ExecutionStats(
            num_docs_scanned=matched,
            num_entries_scanned_in_filter=seg.num_docs * filter_cols,
            num_entries_scanned_post_filter=len(idx) * max(
                len(ctx.select), 1),
            num_segments_processed=1,
            num_segments_matched=1 if matched else 0,
            total_docs=seg.num_docs)
        results.append(SelectionResult(
            rows, columns=expand_star(seg, ctx), stats=stats))
    return results


def merge_top_k(packed: np.ndarray, S_real: int, k: int):
    """Broker-side cross-segment top-K merge over the packed launch
    output: the global K best (segment, doc, score) triples by score
    descending, ties toward (lower segment, lower doc) — deterministic
    regardless of segment arrival order."""
    entries = []
    packed = np.asarray(packed)
    for s in range(min(S_real, packed.shape[0])):
        ids, scores = unpack(packed[s])
        for d, sc in zip(ids, scores):
            entries.append((-float(sc), s, int(d)))
    entries.sort()
    return [(s, d, -neg) for neg, s, d in entries[:k]]
